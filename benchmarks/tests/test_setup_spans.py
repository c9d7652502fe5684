"""The readers of what belongs to no step (`readers/setup.py`,
`readers/host.py`): their arithmetic on hand-made rings, and the traced
rehearsal lines of a toy serving cell and a toy train cell, whose parts
of set-up have to add up to the `setup_s` the benchmark clocks itself.
The toy manifest stays as it is: this PR's eleven entries are taken
from `BENCHMARK.json`, each cell's name swapped for its toy cell's."""
import json

import pytest

from harness import progspans
from test_progspans import TOY_CELL
from test_rehearsal import ROOT, TOY, bench, last_line

SETUP_PARTS = ("lower_s", "trace_s", "backend_s", "build_s", "step_s",
               "startup_s", "unspanned_s")
HOST_PARTS = ("stall_ms", "stall_gc_ms")


@pytest.fixture(scope="module")
def setup_manifest(tmp_path_factory):
    """The toy manifest with this PR's entries and `runtime.compile_s`,
    the eighth part, appended (without `train.mfu`: test_progspans)."""
    manifest = json.loads(TOY.read_text())
    accepted = json.loads((ROOT / "BENCHMARK.json").read_text())["per_layer"]
    ours = [p for p in accepted if p["name"].startswith(("setup.", "host."))]
    assert len(ours) == 11
    ours += [p for p in accepted if p["name"] == "runtime.compile_s"]
    added = [dict(p, workloads=[TOY_CELL[w] for w in p["workloads"]
                                if w in TOY_CELL]) for p in ours]
    manifest["per_layer"] = [p for p in manifest["per_layer"]
                             if p["name"] != "train.mfu"] + added
    for c in manifest["configs"]:
        c["file"] = str(TOY.parent / c["file"])
    path = tmp_path_factory.mktemp("toy_setup") / "BENCHMARK.json"
    path.write_text(json.dumps(manifest))
    return str(path)


def entry(seq, parent, name, t0, t1, **attrs):
    return (seq, parent, name, t0, t1, attrs, 0)


# process start 0, window start 20
SETUP_RING = [
    entry(0, None, "startup", 0.0, 2.0),
    entry(1, None, "backend.init", 2.0, 3.0),
    entry(3, 2, "build.init", 3.5, 6.0),
    entry(4, 2, "trace", 6.5, 7.0, fun="zeros"),
    entry(5, 2, "compile", 7.0, 8.0, fun="jit(zeros)"),  # off the build
    entry(2, None, "build", 3.0, 10.0, engine="E"),
    # a jitted function traced inside another's trace: counted once
    entry(6, None, "trace", 11.5, 12.5, fun="inner"),
    entry(7, None, "trace", 11.0, 13.0, fun="outer"),
    entry(8, None, "lower", 13.0, 14.0, fun="jit(outer)"),
    entry(9, None, "cache_miss", 15.0, 15.0),
    entry(10, None, "compile", 14.0, 15.0, fun="jit(outer)"),
    # a warm-up step that traces: the trace is taken from the step
    entry(12, 11, "trace", 16.25, 16.5, fun="late"),
    entry(13, 11, "decode", 16.0, 16.75),
    entry(11, None, "engine.step", 16.0, 17.0, tick=1),
    # 17-20 lies under nothing; the window's first step opens it
    entry(14, None, "engine.step", 20.0, 20.5, tick=2),
    entry(15, None, "compile", 20.1, 20.2, fun="in the window"),
]
SETUP_LAYERS = {"steps": [{"t0": 20.0, "t1": 20.5}]}


@pytest.fixture
def nothing_dropped(monkeypatch):
    """A made ring stands for all the tracer ever closed."""
    monkeypatch.setattr(progspans, "dropped", lambda spans: 0)


def read_metric(name, layers):
    import run

    return run.find_reader(name).read(name, layers, {}, {})


def test_the_parts_of_setup_are_a_partition(monkeypatch, nothing_dropped):
    monkeypatch.setattr(progspans, "ring", lambda: SETUP_RING)
    got = {p: read_metric(f"setup.{p}", SETUP_LAYERS) for p in SETUP_PARTS}
    assert got == pytest.approx({
        "lower_s": 1.0, "trace_s": 0.5 + 2.0 + 0.25, "backend_s": 1.0,
        "build_s": 7.0 - 0.5 - 1.0, "step_s": 1.0 - 0.25, "startup_s": 2.0,
        # 10-11, 15-16 and 17-20
        "unspanned_s": 5.0})
    compile_s = read_metric("runtime.compile_s", SETUP_LAYERS)
    assert compile_s == pytest.approx(2.0)
    assert sum(got.values()) + compile_s == pytest.approx(20.0)
    assert read_metric("setup.nothing_s", SETUP_LAYERS) is None


def test_the_partition_cuts_entries_at_its_ends_and_takes_the_first_part():
    import run

    setup = run.find_reader("setup.trace_s")
    # an entry that straddles the window's start is cut at it, one that
    # began before the process's record of its start likewise
    ring = [entry(0, None, "startup", -0.5, 1.0),
            entry(1, None, "compile", 0.5, 3.0),
            entry(2, None, "trace", 0.25, 0.75),
            entry(3, None, "build.init", 0.0, 2.0)]      # no part's name
    assert setup.partition(ring, 0.0, 2.0) == pytest.approx({
        "compile_s": 1.5, "lower_s": 0, "trace_s": 0.25, "backend_s": 0,
        "build_s": 0, "step_s": 0, "startup_s": 0.25, "unspanned_s": 0})
    assert setup.partition([], 1.0, 3.0)["unspanned_s"] == 2.0


def test_setup_is_silent_rather_than_partial(monkeypatch):
    """No `startup` entry (a program from before it), a ring that has
    dropped anything, no window: nothing, and nothing raises."""
    names = [f"setup.{p}" for p in SETUP_PARTS]
    monkeypatch.setattr(progspans, "ring", lambda: SETUP_RING)
    monkeypatch.setattr(progspans, "dropped", lambda spans: 1)
    assert [read_metric(n, SETUP_LAYERS) for n in names] == [None] * 7
    monkeypatch.setattr(progspans, "dropped", lambda spans: 0)
    assert [read_metric(n, {}) for n in names] == [None] * 7
    monkeypatch.setattr(progspans, "ring", lambda: SETUP_RING[1:])
    assert [read_metric(n, SETUP_LAYERS) for n in names] == [None] * 7
    monkeypatch.setattr(progspans, "ring", lambda: [])
    for n in names + ["host.stall_ms.batch", "host.stall_gc_ms.batch"]:
        assert read_metric(n, SETUP_LAYERS) is None


def made_window(with_gc: bool):
    """Twelve steps back to back: eleven of 10 ms, the sixth of 110."""
    ring, t = [entry(-1, None, "startup", 0.0, 0.5)], 1.0
    for i in range(12):
        dur = 0.110 if i == 5 else 0.010
        if with_gc and i == 5:
            ring.append(entry(100, i, "gc", t + 0.02, t + 0.08, generation=2,
                              collected=9))
        if with_gc and i == 8:      # a short one, in a step that is no stall
            ring.append(entry(101, i, "gc", t + 0.004, t + 0.006,
                              generation=1, collected=0))
        ring.append(entry(i, None, "engine.step", t, t + dur, tick=i))
        t += dur
    return ring, {"steps": [{"t0": 1.0, "t1": 1.01}, {"t0": t - 0.01, "t1": t}]}


@pytest.mark.parametrize("with_gc", [True, False])
def test_a_long_step_is_a_stall_and_a_collection_inside_it_explains_it(
        monkeypatch, nothing_dropped, with_gc):
    ring, layers = made_window(with_gc)
    monkeypatch.setattr(progspans, "ring", lambda: ring)
    for cell in ("chat", "batch"):
        got = [read_metric(f"host.{p}.{cell}", layers) for p in HOST_PARTS]
        assert got == pytest.approx([100.0, 60.0 if with_gc else 0.0])
    # a chunk's step at 3 x the median is no stall
    ring = [e if e[0] != 5 else entry(5, None, "engine.step", e[3],
                                      e[3] + 0.03) for e in ring]
    monkeypatch.setattr(progspans, "ring", lambda: ring)
    assert read_metric("host.stall_ms.chat", layers) == 0.0
    assert read_metric("host.stall_gc_ms.chat", layers) == 0.0


def test_a_program_that_watches_no_collections_gives_no_gc_metric(
        monkeypatch, nothing_dropped):
    """No `startup` entry: the program is from before the watch that
    hooks the collector, and a 0 would say it had looked."""
    ring, layers = made_window(False)
    monkeypatch.setattr(progspans, "ring", lambda: ring[1:])
    assert read_metric("host.stall_gc_ms.batch", layers) is None
    assert read_metric("host.stall_ms.batch", layers) == pytest.approx(100.0)


@pytest.mark.parametrize("workload,host,open_loop", [
    ("toy-mistral.batch", "batch", False), ("toy-olmo.sft", None, False),
    ("toy-olmo.chat", "chat", True)])
def test_traced_line_splits_setup_into_parts_that_add_up(
        workload, host, open_loop, setup_manifest):
    """An open loop's window opens at an instant no entry marks, before
    its first step: its cells carry no `unspanned_s`, whose end that
    is, and the parts they carry stay below `setup_s`."""
    proc = bench(workload, "--rehearse", "--manifest", setup_manifest,
                 trace=1, devices=1)
    m = last_line(proc)["metrics"]
    notes = next(d for d in map(json.loads, proc.stdout.splitlines())
                 if d.get("event") == "notes")
    carried = SETUP_PARTS[:-1] if open_loop else SETUP_PARTS
    assert {n for n in m if n.startswith("setup.")} \
        == {f"setup.{p}" for p in carried}
    parts = {p: m[f"setup.{p}"]["value"] for p in carried}
    assert all(v >= 0.0 for v in parts.values()), parts
    assert parts["trace_s"] > 0.0 and parts["build_s"] > 0.0
    assert parts["startup_s"] > 0.0 and parts["backend_s"] > 0.0
    whole = sum(parts.values()) + m["runtime.compile_s"]["value"]
    room = max(1.0, 0.02 * notes["setup_s"])
    said = (parts, m["runtime.compile_s"], notes["setup_s"])
    if open_loop:
        assert whole <= notes["setup_s"] + room, said
    else:
        assert abs(whole - notes["setup_s"]) <= room, said
    assert {n for n in m if n.startswith("host.")} \
        == ({f"host.{p}.{host}" for p in HOST_PARTS} if host else set())
