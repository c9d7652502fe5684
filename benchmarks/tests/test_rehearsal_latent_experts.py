"""The latent-attention, routed-experts configuration and its driver:
a rehearsal at a toy size on the CPU (a toy manifest of its own,
`data/toy_latent_experts/`), the published widths of its configuration
file, the arithmetic of its `Shapes`, and what its readers do on a
program that says nothing of experts."""
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
from harness import arith_latent_experts as arith

# `test_progspans.py` builds its toy manifest from `BENCHMARK.json`'s
# span-read entries and maps every cell they list to the toy cell that
# rehearses the same readers (`TOY_CELL`). This PR appends two cells to
# those lists and may not edit that file, so the two learn their toy
# cells here: in the module as `python -m pytest benchmarks/tests`
# imports it, and in the copy tier-1's `tests/test_benchmarks.py` loads
# (both are in `sys.modules` by now: this file sorts after that one). A
# `benchmark` PR should move the two lines into `TOY_CELL` itself.
for _name in ("test_progspans", "benchmarks_tests_test_progspans"):
    if _name in sys.modules:
        sys.modules[_name].TOY_CELL.update({
            "moonlight-16b-a3b.gen-batch": "toy-mistral.batch",
            "olmo-1b.chat-burst": "toy-olmo.chat"})

ROOT = Path(__file__).resolve().parent.parent.parent
LATENT_TOY = Path(__file__).resolve().parent / "data" / "toy_latent_experts" \
    / "BENCHMARK.json"
CONFIG = run.HERE / "configs" / "moonlight-16b-a3b.json"


def _rehearse(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--manifest", str(LATENT_TOY),
         "--workload", "toy-latent.gen", "--seed", str(2**31 + 11),
         "--seconds", "2", "--trace", str(trace), "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(l) for l in proc.stdout.strip().splitlines()]
    line = lines[-1]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    return line, next(l for l in lines if l.get("event") == "notes")


def test_latent_experts_rehearsal_end_to_end_line():
    line, notes = _rehearse(0)
    assert set(line["metrics"]) == {"setup_s", "serve_out_tok_s"}
    assert line["metrics"]["serve_out_tok_s"]["value"] > 0
    # float32 on the CPU: the engine chooses the reference's own tokens
    assert notes["gaps_checked"] > 0 and notes["mean_logit_gap"] < 1e-3


def test_latent_experts_rehearsal_traced_line_reads_the_new_spans():
    line, notes = _rehearse(1)
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["drivers.compiles.batch"] == 0
    assert 1 <= m["moe.experts_touched.batch"] <= 8
    assert m["moe.load_max_over_mean.batch"] >= 1
    assert 0 < m["mla.cache_byte_share.batch"] < 100
    # the span readers and the engine's counters say the same
    assert m["moe.experts_touched.batch"] == pytest.approx(
        notes["per_tick"]["experts_touched"], rel=0.02)
    # no device trace on the CPU: nothing under a device metric's name
    assert "kernels.decode_roofline.batch" not in m


def test_the_toy_manifest_of_the_rehearsal_finds_its_files():
    m = json.loads(LATENT_TOY.read_text())
    cell, = m["workloads"]
    cfg = LATENT_TOY.parent / m["configs"][0]["file"]
    mix = json.loads((cfg.parent.parent / "traffic"
                      / f"{cell['traffic']}.json").read_text())
    assert (run.HERE / "drivers" / f"{mix['driver']}.py").exists()
    for p in m["per_layer"]:
        assert run.find_reader(p["name"]) is not None, p["name"]
        assert p["moves"] in {e["name"] for e in m["end_to_end"]}


def test_moonlight_keeps_every_published_width():
    published = dict(
        hidden_size=2048, intermediate_size=11264, moe_intermediate_size=1408,
        num_hidden_layers=27, num_attention_heads=16, num_key_value_heads=16,
        kv_lora_rank=512, q_lora_rank=None, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, n_routed_experts=64,
        n_shared_experts=2, num_experts_per_tok=6, routed_scaling_factor=2.446,
        first_k_dense_replace=1, moe_layer_freq=1, n_group=1, topk_group=1,
        norm_topk_prob=True, scoring_func="sigmoid", topk_method="noaux_tc",
        vocab_size=163840, max_position_embeddings=8192, rope_theta=50000,
        rms_norm_eps=1e-5, hidden_act="silu", tie_word_embeddings=False,
        attention_bias=False)
    never = re.compile(r"_size$|intermediate|head|_dim$|_rank$|experts")
    c = json.loads(CONFIG.read_text())
    assert c["reduced"] == ["num_hidden_layers"]
    for key, value in published.items():
        if key in c["reduced"]:
            assert c[key] != value and c["published"][key] == value
            assert not never.search(key), key
            assert key in c["reduced_why"]
        else:
            assert c[key] == value, key
    # a whole period: the leading dense layer and at least four that follow
    assert c["num_hidden_layers"] >= c["first_k_dense_replace"] + 4
    for key in ("source", "departures", "assumed", "deployment"):
        assert c[key], key


def test_latent_experts_parameter_and_cache_arithmetic():
    s = arith.Shapes.from_config(json.loads(CONFIG.read_text()))
    assert s.attention_params() == 13_762_560
    assert s.expert_params() * 2 == 17_301_504          # one expert, bf16
    assert round(s.routed_layer_params() / 1e6, 1) == 584.8
    assert round(s.dense_layer_params() / 1e6, 1) == 83.0
    assert s.kv_bytes_per_token() == 9216
    assert round(s.matrix_params() * 2 / 1e9, 2) == 9.70
    whole = s.decode_step_min_bytes(0)
    # every expert until a run says how many its rows chose
    assert whole == replace(s, experts_touched=64.0).decode_step_min_bytes(0)
    fewer = replace(s, experts_touched=60.5)
    assert whole - fewer.decode_step_min_bytes(0) == pytest.approx(
        3.5 * 17_301_504 * s.routed_layers)
    assert fewer.decode_step_min_bytes(1000) - fewer.decode_step_min_bytes(0) \
        == 1000 * 9216
    # what is left is read once: no embedding (a step gathers a few rows)
    fixed = fewer.decode_step_min_bytes(0) \
        - 60.5 * 17_301_504 * s.routed_layers
    assert fixed == 2 * (8 * 13_762_560 + 3 * 2048 * 11264
                         + 7 * (2048 * 64 + 2 * 8_650_752) + 163840 * 2048)


def test_the_new_readers_are_silent_on_a_program_without_the_attrs():
    from readers import mla, moe  # noqa: F401  (namespace package)

    s = arith.Shapes.from_config(json.loads(CONFIG.read_text()))
    layers = {"steps": [], "shapes": s}
    for reader, metric in ((moe, "moe.experts_touched.batch"),
                           (moe, "moe.load_max_over_mean.batch"),
                           (mla, "mla.cache_byte_share.batch")):
        assert reader.read(metric, layers, {}, {}) is None
