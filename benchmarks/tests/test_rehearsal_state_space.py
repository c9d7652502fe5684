"""The configuration whose blocks hold a state-space mixer beside their
attention heads, and its driver: a rehearsal at a toy size on the CPU (a
toy manifest of its own, `data/toy_state_space/`), the published widths
of its configuration file, the arithmetic of its `Shapes`, the fold of
its multipliers, and what its reader does on a program that says
nothing of slabs."""
import json
import os
import re
import subprocess
import sys
from dataclasses import replace
from pathlib import Path

import pytest

import run
import test_progspans  # noqa: F401  (in sys.modules for the loop below)
from harness import arith_state_space as arith

# As `test_rehearsal_window_experts.py` says: `test_progspans.py` maps
# every cell the span-read entries list to the toy cell that rehearses
# the same readers (`TOY_CELL`), this PR appends a cell to those lists
# and may not edit that file, so it learns its toy cell here.
for _name in ("test_progspans", "benchmarks_tests_test_progspans"):
    if _name in sys.modules:
        sys.modules[_name].TOY_CELL.update({
            "falcon-h1-34b-instruct.long-gen": "toy-mistral.batch"})

ROOT = Path(__file__).resolve().parent.parent.parent
STATE_TOY = Path(__file__).resolve().parent / "data" / "toy_state_space" \
    / "BENCHMARK.json"
CONFIG = run.HERE / "configs" / "falcon-h1-34b-instruct.json"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


def _rehearse(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--manifest", str(STATE_TOY),
         "--workload", "toy-state-space.long-gen", "--seed", str(2**31 + 36),
         "--seconds", "2", "--trace", str(trace), "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(l) for l in proc.stdout.strip().splitlines()]
    line = lines[-1]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    return line, next(l for l in lines if l.get("event") == "notes")


def test_state_space_rehearsal_end_to_end_line():
    line, notes = _rehearse(0)
    assert set(line["metrics"]) == {"setup_s", "serve_out_tok_s"}
    assert line["metrics"]["serve_out_tok_s"]["value"] > 0
    # float32 on the CPU: the engine chooses the reference's own tokens,
    # or one a rounding away from them
    assert notes["gaps_checked"] > 0 and notes["worst_relative_gap"] < 1e-3
    # every generated position of the checked requests, and what their
    # slots held when they finished against the reference's recurrence
    assert notes["gaps_checked"] >= 2 * 16
    assert 0 < notes["slow_state_gap"] <= notes["largest_state_gap"] < 1e-4 \
        < notes["slow_state_gap_limit"]


def test_state_space_rehearsal_traced_line_reads_the_new_spans():
    line, notes = _rehearse(1)
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["drivers.compiles.batch"] == 0
    assert 0 < m["ssm.state_byte_share.batch"] < 100
    assert 0 < m["ssm.cache_byte_share.batch"] < 100
    # the span reader and the engine's counters say the same: up to two
    # rows a tick, each 3 layers x (4 x 16 x 16 float32 + 3 x 128
    # float32), read and written
    per_row = 2 * 3 * (4 * 16 * 16 * 4 + 3 * (64 + 2 * 2 * 16) * 4)
    assert 1 <= notes["per_tick"]["state_rows"] <= 2
    assert notes["per_tick"]["state_bytes"] == pytest.approx(
        notes["per_tick"]["state_rows"] * per_row)
    # no device trace on the CPU: nothing under a device metric's name
    assert "kernels.decode_roofline.batch" not in m


def test_the_state_space_toy_manifest_finds_its_files():
    m = json.loads(STATE_TOY.read_text())
    cell, = m["workloads"]
    cfg = STATE_TOY.parent / m["configs"][0]["file"]
    mix = json.loads((cfg.parent.parent / "traffic"
                      / f"{cell['traffic']}.json").read_text())
    assert (run.HERE / "drivers" / f"{mix['driver']}.py").exists()
    for p in m["per_layer"]:
        assert run.find_reader(p["name"]) is not None, p["name"]
        assert p["moves"] in {e["name"] for e in m["end_to_end"]}


def test_falcon_h1_keeps_every_published_key_but_the_depth():
    c = json.loads(CONFIG.read_text())
    assert c["reduced"] == ["num_hidden_layers"]
    never = re.compile(r"_size$|intermediate|head|_dim$|_rank$|d_ssm|d_state"
                       r"|expand|n_groups|d_conv")
    assert not any(never.search(k) for k in c["reduced"])
    if CATALOG.exists():
        row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
                   if r["name"] == "Falcon-H1-34B-Instruct")
        assert c["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key in c["reduced"]:
                assert c[key] != value and key in c["reduced_why"], key
            else:
                assert c[key] == value, key
    assert (c["hidden_size"], c["num_attention_heads"], c["head_dim"],
            c["num_key_value_heads"], c["intermediate_size"], c["vocab_size"],
            c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"],
            c["mamba_n_groups"], c["mamba_d_conv"], c["mamba_d_ssm"],
            c["mamba_chunk_size"]) \
        == (5120, 20, 128, 4, 21504, 261120, 32, 128, 256, 2, 4, 4096, 128)
    assert c["num_hidden_layers"] == 5
    assert c["published"]["num_hidden_layers"] == 72
    assert c["serving"]["ssm_state"] == "float32"
    for key in ("source", "departures", "assumed", "deployment"):
        assert c[key], key


def test_state_space_parameter_and_state_arithmetic():
    s = arith.Shapes.from_config(json.loads(CONFIG.read_text()))
    assert s.conv_dim == 5120
    assert s.hidden * (s.d_ssm + s.conv_dim + s.ssm_heads) == 47_349_760
    assert s.mixer_params() == 68_351_072
    assert s.attention_params() == 31_457_280
    assert s.ffn_params() == 330_301_440
    assert round(s.layer_params() / 1e6, 2) == 430.11
    assert round(s.matrix_params() * 2 / 1e9, 2) == 9.65
    assert s.kv_bytes_per_token() == 5 * 2048
    # a slot, a layer: 32 x 128 x 256 float32 and 3 positions of 5,120
    assert s.state_bytes_per_row() == 5 * (4_194_304 + 30_720)
    # no row priced until a run says how many decode; then each twice
    assert s.decode_step_min_bytes(0) == s.weight_bytes_per_step()
    rows = replace(s, state_rows=48.0)
    assert rows.decode_step_min_bytes(0) - s.decode_step_min_bytes(0) \
        == 2 * 48 * s.state_bytes_per_row()
    assert rows.decode_step_min_bytes(1000) - rows.decode_step_min_bytes(0) \
        == 1000 * 5 * 2048
    # mid-window, 48 rows at 1,700 tokens: 9.8 GB a tick, a fifth of it
    # the slabs
    mid = rows.decode_step_min_bytes(48 * 1700)
    assert round(mid / 1e9, 1) == 9.8
    assert 0.19 < rows.ssm_step_bytes(48.0) / mid < 0.22


def test_the_fold_puts_each_multiplier_into_the_matrix_it_follows():
    import jax
    import numpy as np

    from harness import model_state_space as model
    from harness import reference_state_space as reference
    from shallowspeed_tpu.models import transformer as T

    toy = json.loads((STATE_TOY.parent / "configs"
                      / "toy-state-space.json").read_text())
    cfg = model.transformer_config(toy, "serving")
    m = model.multipliers(toy)
    assert cfg.embed_scale == m["embedding"] == 1.7 and cfg.mixer
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups,
            cfg.ssm_conv) == (4, 16, 16, 2, 4)
    raw = jax.device_put({k: v for k, v in T.init(cfg, 0).items()
                          if k != "pos_emb"})
    served = model.fold(raw, cfg, m)
    kv = np.asarray(served["blocks"][0]["kv"]["W"]) \
        / np.asarray(raw["blocks"][0]["kv"]["W"])
    # (kv head, [k | v], head_dim): the keys' columns alone take `key`
    np.testing.assert_allclose(kv.reshape(64, 2, 2, 16)[:, :, 0],
                               0.9 * 0.45, rtol=1e-5)
    np.testing.assert_allclose(kv.reshape(64, 2, 2, 16)[:, :, 1], 0.9,
                               rtol=1e-5)
    cols = np.asarray(served["blocks"][0]["mixer"]["in_proj"]["W"]) \
        / np.asarray(raw["blocks"][0]["mixer"]["in_proj"]["W"])
    want = 0.7 * np.repeat([0.8, 1.2, 0.55, 1.4, 0.65], [64, 64, 32, 32, 4])
    np.testing.assert_allclose(cols, np.broadcast_to(want, cols.shape),
                               rtol=1e-5)
    # the reference's own statement of the same, the other way round
    back = reference.unfolded(served["blocks"][0],
                              arith.Shapes.from_config(toy), m)
    for name in ("q", "kv", "proj", "gate", "down"):
        np.testing.assert_allclose(back[name]["W"],
                                   raw["blocks"][0][name]["W"], rtol=1e-5)
    # weights made on the device are the same tree, folded
    made = model.init_weights_on_device(cfg, 2**31 + 5, m)
    assert jax.tree_util.tree_structure(made) \
        == jax.tree_util.tree_structure(served)
    # a configuration of another form than the one written is refused
    with pytest.raises(ValueError, match="not written"):
        model.transformer_config(dict(toy, mamba_norm_before_gate=True),
                                 "serving")
    with pytest.raises(ValueError, match="served only"):
        model.transformer_config(toy, "training")


def test_the_comparison_judges_the_tokens_and_the_state():
    import numpy as np

    from drivers import serve_state_space as driver

    tokens = np.full(100, driver.MEAN_GAP_TOLERANCE / 2)
    state = np.full((2, 3, 4), driver.SLOW_STATE_GAP_TOLERANCE / 2)
    slow = np.zeros((3, 4), bool)
    slow[:, 1] = True
    assert driver.judge(tokens, state, slow)["within"]
    assert not driver.judge(tokens * 3, state, slow)["within"]
    one = np.full(10_000, driver.MEAN_GAP_TOLERANCE / 2)
    one[7] = 2 * driver.WORST_GAP_TOLERANCE     # one position far out
    assert one.mean() < driver.MEAN_GAP_TOLERANCE
    assert not driver.judge(one, state, slow)["within"]
    far = state.copy()
    far[1, 2, 1] *= 3                   # ONE slow head of one layer
    assert not driver.judge(tokens, far, slow)["within"]
    far = state.copy()
    far[1, 2, 0] *= 3                   # a fast head: printed, not judged
    verdict = driver.judge(tokens, far, slow)
    assert verdict["within"] and verdict["largest_state_gap"] \
        > verdict["slow_state_gap"]
    assert not driver.judge(tokens[:0], state[:0], slow)["within"]
    # the slowest quarter of a layer's heads, by step x |A| at rest
    params = {"blocks": [{"mixer": {
        "dt_bias": np.log(np.expm1(np.array([.1, .001, .01, .05, .1, .02,
                                             .03, .09]))),
        "A_log": np.log(np.array([1., 2., 16., 1., 8., 1., 1., 1.]))}}]}
    assert driver.slowest_heads(params).tolist() \
        == [[False, True, False, False, False, True, False, False]]


def test_planted_state_faults_come_out_not_correct():
    """`tools/state_space_limits.py` at the toy size: the serving path
    left alone is within the driver's limits; rows zeroed between ticks,
    rows read from another slot and a chunk that starts from zeros are
    not."""
    toy = STATE_TOY.parent
    proc = subprocess.run(
        [sys.executable, "benchmarks/tools/state_space_limits.py",
         "--config", str(toy / "configs" / "toy-state-space.json"),
         "--traffic", str(toy / "traffic" / "toy-long-gen.json"),
         "--seed", str(2**31 + 37), "--faults",
         "none,zeroed,swapped,no_carry"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = {l["fault"]: l for l in map(json.loads,
                                      proc.stdout.strip().splitlines())}
    assert got["none"]["within"] and got["none"]["largest_state_gap"] < 1e-4
    for fault in ("zeroed", "swapped", "no_carry"):
        assert not got[fault]["within"], fault
        assert got[fault]["largest_state_gap"] > 0.1, fault
    # the tokens alone tell the two that last a whole answer
    for fault in ("zeroed", "swapped"):
        assert got[fault]["mean_relative_gap"] > 0.1, fault


def test_the_state_reader_is_silent_on_a_program_without_the_attrs():
    from readers import ssm  # noqa: F401  (namespace package)

    s = arith.Shapes.from_config(json.loads(CONFIG.read_text()))
    layers = {"steps": [], "shapes": s, "block_size": 16}
    for metric in ("ssm.state_byte_share.batch", "ssm.cache_byte_share.batch"):
        assert ssm.read(metric, layers, {}, {}) is None
    # steps, but a ring without the attrs (the parent's program)
    layers["steps"] = [{"t0": 0.0, "t1": 1.0, "decoding": 2,
                        "live_tokens": 100, "prefill": False}]
    for metric in ("ssm.state_byte_share.batch", "ssm.cache_byte_share.batch"):
        assert ssm.read(metric, layers, {}, {}) is None
