"""The readers of the program's own spans: the arithmetic of the helper
on hand-made rings, and the traced rehearsal lines of the toy cells,
which have to carry every metric the readers give. The toy manifest is
the accepted benchmark's file and stays as it is: the entries that read
the program's spans are taken from `BENCHMARK.json` itself, with each
cell's name swapped for its toy cell's."""
import json

import pytest

from harness import progspans
from test_rehearsal import ROOT, TOY, bench, last_line

PHASES = ("admit", "prep", "dispatch", "emit")
READ_FROM_THE_PROGRAM = ("sched.", "runtime.", "train.host_ms",
                         "engine.queue_wait_ms")
TOY_CELL = {"olmo-1b.sft": "toy-olmo.sft", "olmo-1b.chat": "toy-olmo.chat",
            "mistral-7b-v0.1.doc-batch": "toy-mistral.batch",
            "olmo-1b.sft-dp4": "toy-olmo.sft-dp4"}


@pytest.fixture(scope="module")
def toy_manifest(tmp_path_factory):
    """The toy manifest with `BENCHMARK.json`'s entries for these readers
    appended, and without `train.mfu`, whose share of the chip's peak the
    CPU refuses (test_rehearsal) before a traced training line is printed."""
    manifest = json.loads(TOY.read_text())
    added = [dict(p, workloads=[TOY_CELL[w] for w in p["workloads"]])
             for p in json.loads((ROOT / "BENCHMARK.json").read_text())["per_layer"]
             if p["name"].startswith(READ_FROM_THE_PROGRAM)]
    assert len(added) == 14
    manifest["per_layer"] = [p for p in manifest["per_layer"]
                             if p["name"] != "train.mfu"] + added
    for c in manifest["configs"]:
        c["file"] = str(TOY.parent / c["file"])
    path = tmp_path_factory.mktemp("toy") / "BENCHMARK.json"
    path.write_text(json.dumps(manifest))
    return str(path)


def span(seq, parent, name, t0, t1, **attrs):
    return (seq, parent, name, t0, t1, attrs, 0)


RING = [
    span(1, 0, "admit", 0.10, 0.20),
    span(3, 2, "decode.prep", 0.30, 0.40),
    span(4, 2, "decode.dispatch", 0.40, 0.50),
    span(5, 2, "decode.fetch", 0.50, 0.90),
    span(2, 0, "decode", 0.25, 0.95),
    span(0, None, "engine.step", 0.00, 1.00, tick=1),
    span(6, None, "engine.step", 2.00, 3.00, tick=2),    # after the window
]


@pytest.fixture
def whole_ring(monkeypatch):
    """A made ring stands for all the tracer ever closed."""
    monkeypatch.setattr(progspans, "dropped", lambda spans: 0)


def test_window_total_and_self_time(whole_ring):
    layers = {"steps": [{"t0": 0.0, "t1": 1.0}]}
    assert progspans.window(layers, RING) == (0.0, 1.0)
    inside = progspans.inside(RING, 0.0, 1.0)
    assert [e[progspans.NAME] for e in inside][-1] == "engine.step"
    assert len(inside) == 6
    assert progspans.total(inside, "engine.step") == pytest.approx(1.0)
    assert progspans.total(inside, "decode.prep", "admit") == pytest.approx(0.2)
    # self time: duration less what the child spans cover
    assert progspans.self_total(inside, "decode") == pytest.approx(0.1)
    assert progspans.self_total(inside, "engine.step") == pytest.approx(0.2)
    assert progspans.self_total(inside, "admit") == pytest.approx(0.1)


def test_training_window_is_the_last_steps_of_the_ring(whole_ring):
    ring = [span(i, None, "step", float(i), i + 0.5, step=i) for i in range(6)]
    ring.insert(3, span(9, 2, "dispatch", 2.1, 2.2))
    assert progspans.window({"step_ms": [1.0, 1.0]}, ring) == (3.0, 5.5)
    assert progspans.window({}, ring) is None
    assert progspans.window({"step_ms": [1.0]}, []) is None


def test_a_ring_that_dropped_spans_gives_nothing_rather_than_a_part(
        monkeypatch):
    """Set-up's spans are the ring's oldest and the first to go: the
    readers that need them fall silent at the first one dropped, the
    readers of the window once its start is gone too."""
    import run

    ring = [span(0, None, "compile", 0.0, 2.0), span(1, None, "cache_miss",
                                                     2.0, 2.0)] + [
        span(2 + i, None, "engine.step", 3.0 + i, 3.5 + i) for i in range(4)]
    monkeypatch.setattr(progspans, "ring", lambda: ring)
    layers = {"steps": [{"t0": 3.0, "t1": 3.5}, {"t0": 6.0, "t1": 6.5}]}
    read = lambda name: run.find_reader(name).read(name, layers, {}, {})
    monkeypatch.setattr(progspans, "dropped", lambda spans: 0)
    assert read("runtime.compile_s") == pytest.approx(2.0)
    assert read("runtime.cache_misses") == 1.0
    assert read("sched.host_ms.chat") == pytest.approx(500.0)
    # one span gone, and it closed before the window opened
    monkeypatch.setattr(progspans, "dropped", lambda spans: 1)
    assert read("runtime.compile_s") is None
    assert read("runtime.cache_misses") is None
    assert read("sched.host_ms.chat") == pytest.approx(500.0)
    # the ring now starts inside the window
    del ring[:3]
    assert read("sched.host_ms.chat") is None
    steps = [span(i, None, "step", float(i), i + 0.5) for i in range(1, 4)]
    assert progspans.window({"step_ms": [1.0, 1.0]}, steps) is None
    assert progspans.window({"step_ms": [1.0, 1.0, 1.0]}, steps) is None


def test_dropped_is_what_the_tracer_closed_less_what_the_ring_holds(
        monkeypatch):
    from shallowspeed_tpu.telemetry import trace

    monkeypatch.setattr(trace, "RING_CAP", 3)
    monkeypatch.setattr(trace, "_TRACER", trace.Tracer())
    for _ in range(3):
        with trace.tracer().span("s"):
            pass
    assert progspans.dropped(progspans.ring()) == 0
    with trace.tracer().span("s"):
        pass
    assert progspans.dropped(progspans.ring()) == 1


def test_sched_reader_on_a_made_ring(monkeypatch, whole_ring):
    import run

    sched = run.find_reader("sched.host_ms.chat")
    monkeypatch.setattr(progspans, "ring", lambda: RING)
    layers = {"steps": [{"t0": 0.0, "t1": 1.0}]}
    read = lambda what: sched.read(f"sched.{what}.chat", layers, {}, {})
    assert read("host_ms") == pytest.approx(600.0)       # 1.0 s less the fetch
    assert read("admit_ms") == pytest.approx(100.0)
    assert read("prep_ms") == pytest.approx(200.0)       # decode.prep + decode's own
    assert read("dispatch_ms") == pytest.approx(100.0)
    assert read("emit_ms") == 0.0
    assert read("nothing_ms") is None
    # a program without the ring: every reader is silent and none raises
    monkeypatch.setattr(progspans, "ring", lambda: [])
    for name in ("sched.host_ms.chat", "train.host_ms", "runtime.compile_s",
                 "runtime.cache_misses"):
        assert run.find_reader(name).read(name, layers, {}, {}) is None


def test_traced_serving_line_splits_the_host_time_by_phase(toy_manifest):
    # what the phases leave of the host's time is `engine.step`'s self
    # time: some 50 us of span bookkeeping, a fortieth of the toy's host
    # time on an idle CPU and up to a seventh beside five other test
    # workers (a hundredth on the chip), hence the bounded retry
    for _attempt in range(3):
        m = last_line(bench("toy-olmo.chat", "--rehearse", "--manifest",
                            toy_manifest, trace=1))["metrics"]
        value = lambda name: m[name]["value"]
        host = value("sched.host_ms.chat")
        parts = sum(value(f"sched.{p}_ms.chat") for p in PHASES)
        assert parts <= host * (1 + 1e-9), (parts, host)
        if parts >= 0.9 * host:
            break
    assert parts >= 0.9 * host, (parts, host)
    # the host's own time is less than a whole step as the benchmark
    # clocks it from outside. Against the longer of the two medians: on
    # the CPU a dispatch runs the toy program itself, so a prefill step's
    # dispatch can pass a whole decode step
    assert host < max(value("engine.decode_step_ms.chat"),
                      value("engine.prefill_step_ms.chat"))
    assert value("engine.queue_wait_ms") >= 0.0
    assert value("runtime.compile_s") > 0.0
    assert value("runtime.cache_misses") >= 0.0


def test_traced_batch_line_carries_the_phases_too(toy_manifest):
    m = last_line(bench("toy-mistral.batch", "--rehearse", "--manifest",
                        toy_manifest, trace=1))["metrics"]
    assert {f"sched.{p}_ms.batch" for p in PHASES + ("host",)} <= set(m)
    assert "runtime.compile_s" in m and "engine.queue_wait_ms" not in m


@pytest.mark.parametrize("workload,devices", [("toy-olmo.sft", 1),
                                              ("toy-olmo.sft-dp4", 4)])
def test_traced_training_line_carries_the_steps_host_time(workload, devices,
                                                          toy_manifest):
    proc = bench(workload, "--rehearse", "--manifest", toy_manifest, trace=1,
                 devices=devices)
    m = last_line(proc)["metrics"]
    assert 0.0 < m["train.host_ms"]["value"] < m["train.step_ms"]["value"] * 2
    assert m["runtime.compile_s"]["value"] > 0.0
    assert m["runtime.cache_misses"]["unit"] == "count"
