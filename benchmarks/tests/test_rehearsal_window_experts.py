"""The window-and-full-layers, routed-experts configuration and its
driver: a rehearsal at a toy size on the CPU (a toy manifest of its own,
`data/toy_window_experts/`), the published widths of its configuration
file, the arithmetic of its `Shapes`, and what its reader does on a
program that says nothing of layer groups."""
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
from harness import arith_window_experts as arith

# As `test_rehearsal_latent_experts.py` says: `test_progspans.py` maps
# every cell the span-read entries list to the toy cell that rehearses
# the same readers (`TOY_CELL`), this PR appends two cells to those lists
# and may not edit that file, so the two learn their toy cells here.
for _name in ("test_progspans", "benchmarks_tests_test_progspans"):
    if _name in sys.modules:
        sys.modules[_name].TOY_CELL.update({
            "trinity-mini.reason-batch": "toy-mistral.batch",
            "olmo-1b.chat-over": "toy-mistral.batch"})

ROOT = Path(__file__).resolve().parent.parent.parent
WINDOW_TOY = Path(__file__).resolve().parent / "data" / "toy_window_experts" \
    / "BENCHMARK.json"
CONFIG = run.HERE / "configs" / "trinity-mini.json"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


def _rehearse(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--manifest", str(WINDOW_TOY),
         "--workload", "toy-window.reason", "--seed", str(2**31 + 33),
         "--seconds", "2", "--trace", str(trace), "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(l) for l in proc.stdout.strip().splitlines()]
    line = lines[-1]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    return line, next(l for l in lines if l.get("event") == "notes")


def test_window_experts_rehearsal_end_to_end_line():
    line, notes = _rehearse(0)
    assert set(line["metrics"]) == {"setup_s", "serve_out_tok_s"}
    assert line["metrics"]["serve_out_tok_s"]["value"] > 0
    # float32 on the CPU: the engine chooses the reference's own tokens
    assert notes["gaps_checked"] > 0 and notes["mean_logit_gap"] < 1e-3


def test_window_experts_rehearsal_traced_line_reads_the_new_spans():
    line, notes = _rehearse(1)
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["drivers.compiles.batch"] == 0
    assert 1 <= m["moe.experts_touched.batch"] <= 8
    # contexts of 80-144 tokens against a window of 32: the window group
    # holds 3 blocks a row (ceil(32 / 16) + 1) of the 5-9 a context has
    assert 25 < m["swa.held_share.batch"] < 65
    assert 0 < m["swa.read_byte_share.batch"] < 100
    assert notes["per_tick"]["released"] > 0
    assert 0 < notes["windowed_share"] < 0.5
    # the span reader and the engine's counters say the same
    assert m["swa.held_share.batch"] == pytest.approx(
        100 * notes["per_tick"]["window_blocks"]
        / notes["per_tick"]["full_blocks"], rel=0.05)
    # no device trace on the CPU: nothing under a device metric's name
    assert "kernels.decode_roofline.batch" not in m


def test_the_window_toy_manifest_finds_its_files():
    m = json.loads(WINDOW_TOY.read_text())
    cell, = m["workloads"]
    cfg = WINDOW_TOY.parent / m["configs"][0]["file"]
    mix = json.loads((cfg.parent.parent / "traffic"
                      / f"{cell['traffic']}.json").read_text())
    assert (run.HERE / "drivers" / f"{mix['driver']}.py").exists()
    for p in m["per_layer"]:
        assert run.find_reader(p["name"]) is not None, p["name"]
        assert p["moves"] in {e["name"] for e in m["end_to_end"]}


def test_trinity_mini_keeps_every_published_key_but_the_depth():
    c = json.loads(CONFIG.read_text())
    assert c["reduced"] == ["num_hidden_layers", "num_dense_layers",
                            "layer_types"]
    never = re.compile(r"_size$|intermediate|head|_dim$|_rank$|experts")
    assert not any(never.search(k) for k in c["reduced"])
    if CATALOG.exists():
        row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
                   if r["name"] == "Trinity-Mini")
        assert c["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key in c["reduced"]:
                assert c[key] != value and key in c["reduced_why"], key
            else:
                assert c[key] == value, key
    assert (c["hidden_size"], c["num_attention_heads"], c["head_dim"],
            c["num_key_value_heads"], c["num_experts"],
            c["num_experts_per_tok"], c["moe_intermediate_size"],
            c["intermediate_size"], c["vocab_size"], c["sliding_window"]) \
        == (2048, 32, 128, 4, 128, 8, 1024, 6144, 200192, 2048)
    # one leading dense layer, then one whole period S S S F of routed ones
    assert c["num_hidden_layers"] == c["num_dense_layers"] + 4 == 5
    assert c["layer_types"] == ["sliding_attention"] * 4 + ["full_attention"]
    assert c["published"]["num_hidden_layers"] == 32
    for key in ("source", "departures", "assumed", "deployment"):
        assert c[key], key


def test_window_experts_parameter_and_cache_arithmetic():
    s = arith.Shapes.from_config(json.loads(CONFIG.read_text()))
    assert s.attention_params() == 27_262_976
    assert s.expert_params() == 6_291_456
    assert round(s.routed_layer_params() / 1e6, 1) == 839.1
    assert round(s.dense_layer_params() / 1e6, 1) == 65.0
    assert round(s.matrix_params() * 2 / 1e9, 2) == 8.48
    assert (s.window_layers, s.full_layers) == (4, 1)
    assert s.kv_bytes_per_token_layer() == 2048
    # every expert and every token until a run says what its rows chose
    # and what their windows see
    whole = s.decode_step_min_bytes(0)
    assert whole == replace(s, experts_touched=128.0).decode_step_min_bytes(0)
    fewer = replace(s, experts_touched=126.0)
    assert whole - fewer.decode_step_min_bytes(0) == pytest.approx(
        2 * 2 * 6_291_456 * s.routed_layers)
    assert s.decode_step_min_bytes(1000) - whole == 1000 * 5 * 2048
    # 64 rows at 6,144 tokens: a window layer's queries see 2,048 of them
    seen = replace(s, windowed_share=2048 / 6144)
    assert seen.decode_step_min_bytes(64 * 6144) - whole == pytest.approx(
        64 * 2048 * (6144 + 4 * 2048))


def test_the_window_reader_is_silent_on_a_program_without_the_attrs():
    from readers import swa  # noqa: F401  (namespace package)

    s = arith.Shapes.from_config(json.loads(CONFIG.read_text()))
    layers = {"steps": [], "shapes": s, "block_size": 16}
    for metric in ("swa.held_share.batch", "swa.read_byte_share.batch"):
        assert swa.read(metric, layers, {}, {}) is None
