"""Percentile, spread and per-request timing arithmetic on hand-made
records; the shape arithmetic against the published parameter counts;
the peaks table."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from harness import arith, peaks

BENCH = Path(__file__).resolve().parent.parent


def test_percentile_interpolates_between_ranks():
    xs = [10, 20, 30, 40, 50]
    assert arith.percentile(xs, 0) == 10 and arith.percentile(xs, 100) == 50
    assert arith.median(xs) == 30
    assert arith.percentile(xs, 95) == pytest.approx(48.0)
    assert arith.median([1, 2, 3, 4]) == pytest.approx(2.5)
    with pytest.raises(ValueError):
        arith.percentile([], 50)


def test_spread_is_the_quartile_distance_over_the_median():
    # statistics.quantiles(n=4) of 1..6 gives 1.75 and 5.25
    assert arith.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(3.5 / 3.5)
    assert arith.spread([100, 100, 100, 100, 100, 100]) == 0


def _shapes(name):
    return arith.Shapes.from_config(
        json.loads((BENCH / "configs" / f"{name}.json").read_text()))


def test_parameter_counts_match_the_published_sizes():
    assert _shapes("olmo-1b").matrix_params() == pytest.approx(1.177e9, rel=2e-3)
    m = _shapes("mistral-7b-v0.1")
    assert m.layer_matrix_params() == 218_103_808
    full = 32 * m.layer_matrix_params() + 2 * m.vocab * m.hidden
    assert full == pytest.approx(7.24e9, rel=2e-3)       # Mistral 7B
    assert m.kv_bytes_per_token() == m.layers * 4096


def test_train_flops_are_six_per_parameter_plus_attention():
    s = _shapes("olmo-1b")
    flops = s.train_flops_per_token(2048)
    attention = 3 * s.layers * 4 * s.heads * s.head_dim * (2049 / 2)
    assert flops == pytest.approx(6 * s.matrix_params() + attention)


def test_decode_bytes_count_an_untied_embedding_once():
    m = _shapes("mistral-7b-v0.1")
    weights = m.decode_step_min_bytes(0)
    assert weights == 2 * (m.matrix_params() - m.vocab * m.hidden)
    assert m.decode_step_min_bytes(1000) - weights == 1000 * m.kv_bytes_per_token()


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("cpu")


def test_observer_times_token_gaps_and_counts_emitted_tokens():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "serve_driver", BENCH / "drivers" / "serve.py")
    serve = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(serve)
    req = SimpleNamespace(rid="a", generated=[], phase="prefill", written=0)
    eng = SimpleNamespace(slots=[req, None], request_records=[],
                          counters={"prefill_chunks": 0})
    obs = serve.Observer(eng)
    eng.counters["prefill_chunks"] = 1
    req.generated, req.phase, req.written = [5, 6], "decode", 11
    obs.after_step(0.0, 1.0, 0)              # first two tokens: no gap yet
    req.generated, req.written = [5, 6, 7], 12
    obs.after_step(1.0, 1.1, 1)
    eng.slots[0] = None                      # finishes with its fifth token
    eng.request_records.append({"id": "a", "tokens_out": 5})
    obs.after_step(1.1, 1.5, 1)
    assert obs.emitted == 5
    assert obs.itl_ms == pytest.approx([100.0, 200.0, 200.0])
    assert [s["prefill"] for s in obs.steps] == [True, False, False]
    assert [s["decoding"] for s in obs.steps] == [1, 1, 0]
    assert obs.steps[1]["live_tokens"] == 12


def test_readers_take_tpot_ttft_and_step_medians_from_records():
    import run

    layers = {"steps": [{"t0": 0, "t1": .010, "prefill": False, "decoding": 2},
                        {"t0": 0, "t1": .030, "prefill": True, "decoding": 1},
                        {"t0": 0, "t1": .020, "prefill": False, "decoding": 1}],
              "slots": 2, "late_ms": {"a": 2.0, "b": 4.0},
              "due_in_window": ["a", "b"], "itl_ms": list(range(1, 101)),
              "records": [{"id": "a", "ttft_ms": 10.0}, {"id": "b", "ttft_ms": 20.0}],
              "compiles": 0}
    read = lambda m: run.find_reader(m).read(m, layers, {}, {"kind": "cpu"})
    assert read("engine.decode_step_ms.chat") == pytest.approx(15.0)
    assert read("engine.prefill_step_ms.chat") == pytest.approx(30.0)
    assert read("engine.slot_occupancy.chat") == pytest.approx(100 * 4 / 6)
    assert read("engine.ttft_ms") == pytest.approx(18.0)
    assert read("engine.itl_p95_ms") == pytest.approx(95.05)
    assert read("drivers.gen_late_ms") == pytest.approx(3.0)
    assert read("drivers.compiles.chat") == 0
    assert read("device.idle_share.chat") is None       # nothing to read
    assert run.find_reader("nolayer.metric") is None
