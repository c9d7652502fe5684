"""Walk `BENCHMARK.json`: every file a cell names exists, every per-layer
metric moves an end-to-end metric that each of its cells reports, names
and units hold only the permitted characters, and at most one cell in
four (or one) asks for 4 chips. The toy manifest of the rehearsals is
held to the same rules."""
import json
import re
from pathlib import Path

import pytest

import run

ROOT = Path(__file__).resolve().parent.parent.parent
MANIFESTS = [ROOT / "BENCHMARK.json",
             Path(__file__).resolve().parent / "data" / "toy" / "BENCHMARK.json"]
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


@pytest.fixture(params=MANIFESTS, ids=["benchmark", "toy"])
def manifest(request):
    return request.param.parent, json.loads(request.param.read_text())


def test_keys_names_and_units(manifest):
    _, m = manifest
    assert set(m) == KEYS
    assert 1 <= m["run_seconds"] <= 51 and isinstance(m["run_seconds"], int)
    named = m["configs"] + m["workloads"] + m["end_to_end"] + m["per_layer"]
    for entry in named:
        assert NAME.fullmatch(entry["name"]), entry["name"]
    for w in m["workloads"]:
        assert NAME.fullmatch(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for metric in m["end_to_end"] + m["per_layer"]:
        assert UNIT.fullmatch(metric["unit"]), metric["unit"]
        assert metric["better"] in ("lower", "higher")
    for kind in ("configs", "workloads"):
        names = [e["name"] for e in m[kind]]
        assert len(names) == len(set(names))
    metrics = [e["name"] for e in m["end_to_end"] + m["per_layer"]]
    assert len(metrics) == len(set(metrics))
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_cell_finds_its_files_and_driver(manifest):
    base, m = manifest
    configs = {c["name"]: c for c in m["configs"]}
    assert {w["config"] for w in m["workloads"]} == set(configs)
    for w in m["workloads"]:
        config_file = base / configs[w["config"]]["file"]
        config = json.loads(config_file.read_text())
        assert set(configs[w["config"]]["reduced"]) == set(config["reduced"])
        mix_file = config_file.parent.parent / "traffic" / f"{w['traffic']}.json"
        mix = json.loads(mix_file.read_text())
        assert (run.HERE / "drivers" / f"{mix['driver']}.py").exists()
        assert mix.get("dp", 1) == w["chips"]


def test_bounds_and_sources(manifest):
    _, m = manifest
    e2e = {e["name"]: e for e in m["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for e in e2e.values():
        assert 0.01 <= e["bound"] <= 0.1
        assert e["source"] in ("host_clock", "device_trace")
        assert set(e) <= {"name", "unit", "better", "bound", "source", "workloads"}
    for p in m["per_layer"]:
        assert p["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(p) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}


def test_every_cell_reports_what_its_layer_metrics_move(manifest):
    _, m = manifest
    cells = [w["name"] for w in m["workloads"]]
    for cell in cells:
        e2e = {e["name"] for e in run.metrics_of(m, "end_to_end", cell)}
        layer = run.metrics_of(m, "per_layer", cell)
        assert len(e2e) >= 2 and layer, cell
        for p in layer:
            assert p["moves"] in e2e, (cell, p["name"])
    for metric in m["end_to_end"] + m["per_layer"]:
        assert set(metric.get("workloads", cells)) <= set(cells)


def test_every_layer_metric_has_a_reader_and_one_layer_name(manifest):
    _, m = manifest
    by_family: dict = {}
    for p in m["per_layer"]:
        assert run.find_reader(p["name"]) is not None, p["name"]
        by_family.setdefault(p["name"].split(".")[0], set()).add(p["layer"])
    assert all(len(layers) == 1 for layers in by_family.values()), by_family


def test_at_most_a_quarter_of_the_cells_take_four_chips(manifest):
    _, m = manifest
    four = [w for w in m["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(m["workloads"]) // 4)


def test_the_harness_names_no_cell_configuration_or_metric():
    _, m = MANIFESTS[0].parent, json.loads(MANIFESTS[0].read_text())
    words = {e["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for e in m[k]} | {w["traffic"] for w in m["workloads"]}
    words -= {"setup_s"}         # the one metric the harness itself takes
    code = (run.HERE / "run.py").read_text()
    for word in words:
        assert word not in code, word
    # a driver computes the end-to-end metrics of its kind of work and may
    # name those; it names no cell, configuration or per-layer metric
    words -= {e["name"] for e in m["end_to_end"]}
    for driver in (run.HERE / "drivers").glob("*.py"):
        text = driver.read_text()
        for word in words:
            assert word not in text, (driver.name, word)


def test_configurations_keep_every_published_width():
    published = {
        "olmo-1b": dict(hidden_size=2048, intermediate_size=8192,
                        num_hidden_layers=16, num_attention_heads=16,
                        num_key_value_heads=16, vocab_size=50304,
                        max_position_embeddings=2048, rope_theta=10000.0,
                        tie_word_embeddings=True),
        "mistral-7b-v0.1": dict(hidden_size=4096, intermediate_size=14336,
                                num_hidden_layers=32, num_attention_heads=32,
                                num_key_value_heads=8, vocab_size=32000,
                                max_position_embeddings=32768,
                                rope_theta=10000.0, sliding_window=4096,
                                tie_word_embeddings=False, rms_norm_eps=1e-5),
    }
    never = re.compile(r"_size$|intermediate|head|_dim$|_rank$|window")
    for name, want in published.items():
        c = json.loads((run.HERE / "configs" / f"{name}.json").read_text())
        for key, value in want.items():
            if key in c["reduced"]:
                assert c[key] != value and c["published"][key] == value
                assert not never.search(key), key
                assert key in c["reduced_why"]
            else:
                assert c[key] == value, (name, key)
