"""The trace reduction on a small trace recorded on the chip (a toy
ServingEngine, 8 steps; `data/probe_trace.json.gz`), and the loader on a
hand-written profile."""
import gzip
import json
from pathlib import Path

import pytest

from harness import tracered as R

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(DATA / "probe_trace.json.gz", "rt") as f:
        raw = json.load(f)["trace"]
    return {p: {l: [tuple(e) for e in ev] for l, ev in lines.items()}
            for p, lines in raw.items()}


def test_recorded_trace_reduces_to_busy_time_and_programs(recorded):
    assert R.device_planes(recorded) == ["/device:TPU:0"]
    s = R.summarize(recorded, 1)
    # no `bench:window` span in the probe: the window is the device's extent
    assert s["window_s"] == pytest.approx(0.0447, rel=0.01)
    assert s["busy_s"] == pytest.approx(469e-6, rel=0.01)
    assert 0 < s["busy_s"] < s["window_s"]
    mods = s["devices"][0]["modules"]
    ticks = R.module_durations(mods, r"^jit__decode_tick\(")
    chunks = R.module_durations(mods, r"^jit__prefill_chunk\(")
    assert (len(ticks), len(chunks)) == (7, 6)
    assert max(ticks) < 40e-6 and min(chunks) > 40e-6
    # programs cover the operations: busy time is inside module time
    assert s["busy_s"] <= sum(d for _, _, d in mods) * 1.001


def test_breakdown_names_operations_and_blames_gaps_on_spans(recorded):
    s = R.summarize(recorded, 1)
    ops = dict(s["device_ops"])
    assert len(s["device_ops"]) == 10
    assert list(ops)[0] == "fusion bf16[64,2,16,128]"   # whole-pool writes
    assert sum(ops.values()) <= s["busy_s"] * 1.001
    gaps = dict(s["idle_gaps"])
    assert set(gaps) <= {"step", "wait", "none"}
    assert sum(gaps.values()) == pytest.approx(s["window_s"] - s["busy_s"],
                                               rel=1e-6)
    assert gaps["wait"] > gaps["step"] > 0


def test_no_collectives_on_one_chip(recorded):
    s = R.summarize(recorded, 1)
    assert R.collective_seconds(s["devices"][0]) == 0


def test_self_time_subtracts_nested_children():
    ev = [("%while.1 = (s32[]) while(...)", 0.0, 10.0),
          ("%fusion.3 = f32[8]{0} fusion(...)", 1.0, 2.0),
          ("%fusion.4 = f32[8]{0} fusion(...)", 4.0, 3.0),
          ("%all-reduce.7 = f32[4]{0} all-reduce(...)", 12.0, 1.0)]
    t = R.self_times(ev)
    assert t == pytest.approx({"while s32[]": 5.0, "fusion f32[8]": 5.0,
                               "all-reduce f32[4]": 1.0})
    assert R.busy_seconds(ev) == pytest.approx(11.0)
    assert R.is_collective(ev[3][0]) and not R.is_collective(ev[1][0])
    assert R.busy_seconds(R.clip(ev, 9.0, 12.5)) == pytest.approx(1.5)


def test_idle_gaps_take_the_innermost_span():
    busy = [(1.0, 2.0), (5.0, 6.0)]
    spans = [("step", 0.0, 4.0), ("submit", 2.5, 4.0), ("wait", 6.0, 8.0)]
    gaps = R.idle_gaps(busy, sorted(spans, key=lambda e: (e[1], -e[2])), 0.0, 8.0)
    assert gaps == pytest.approx({"step": 1.0, "submit": 3.0, "wait": 2.0})


def test_loader_reads_a_profile_with_nothing_but_jax(tmp_path):
    import jax

    text = '''
    planes { name: "/device:TPU:0"
      lines { name: "XLA Ops" timestamp_ns: 1000
        events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
        events { metadata_id: 2 offset_ps: 3000000 duration_ps: 1000000 } }
      lines { name: "XLA Modules" timestamp_ns: 1000
        events { metadata_id: 3 offset_ps: 0 duration_ps: 4000000 } }
      event_metadata { key: 1 value { id: 1 name: "%fusion.1 = bf16[8,8]{1,0} fusion(%p)" } }
      event_metadata { key: 2 value { id: 2 name: "%all-reduce.2 = f32[4]{0} all-reduce(%q)" } }
      event_metadata { key: 3 value { id: 3 name: "jit__step(123)" } } }
    planes { name: "/host:CPU"
      lines { name: "python3" timestamp_ns: 0
        events { metadata_id: 1 offset_ps: 500000 duration_ps: 5000000 } }
      event_metadata { key: 1 value { id: 1 name: "bench:window" } } }
    '''
    run_dir = tmp_path / "plugins" / "profile" / "run1"
    run_dir.mkdir(parents=True)
    (run_dir / "host.xplane.pb").write_bytes(
        jax.profiler.ProfileData.text_proto_to_serialized_xspace(text))
    trace = R.load(R.find_xplane(str(tmp_path)))
    s = R.summarize(trace, 1)
    assert s["window_s"] == pytest.approx(5e-6)
    assert s["busy_s"] == pytest.approx(3e-6)
    assert R.collective_seconds(s["devices"][0]) == pytest.approx(1e-6)
    assert R.module_durations(s["devices"][0]["modules"], r"^jit__step\(") \
        == pytest.approx([4e-6])
    assert dict(s["device_ops"]) == pytest.approx(
        {"fusion bf16[8,8]": 2e-6, "all-reduce f32[4]": 1e-6})
    assert R.find_xplane(str(tmp_path / "nothing")) is None
