"""Telemetry subsystem gates (tier-1).

What must hold:
- span nesting + the export schema round-trip (spans.jsonl validates
  against `telemetry.schema`; trace.json is Chrome-trace shaped);
- bubble accounting: the executed-trace replay of a known 2-stage
  GPipe trace matches `verify.py`'s closed form, costed replays are
  F:B-ratio-invariant for gpipe, and the static fractions agree with
  the simulators per schedule;
- HBM live-vs-static cross-check within tolerance on a real engine;
- `--telemetry off` inserts NO fences, writes nothing and compiles
  nothing: a span reaches the bounded ring (order, parent, bound) and
  the profiler's trace as `ss:<name>` while a session is live — the
  engines' async dispatch pipeline is untouched;
- JAX's compiles become `compile` spans under the span that was open;
- `--gaps` names the span a device idled in;
- the recompile counter: every VM stage executable compiles exactly
  once across batches (pins the zero_grad sharding fix this counter
  caught);
- collective traffic accounting multiplies scan trip counts.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shallowspeed_tpu.telemetry import bubble, schema
from shallowspeed_tpu.telemetry import trace as trace_mod
from shallowspeed_tpu.telemetry.report import RunTelemetry, compile_counts
from shallowspeed_tpu.telemetry.trace import Tracer


# ------------------------------------------------------------- spans


def test_span_nesting_and_depth():
    tr = Tracer(level="steps")
    with tr.span("step", step=3):
        with tr.span("fwd", mu=0):
            pass
        with tr.span("bwd", mu=0):
            pass
    evs = tr.events
    assert [e["name"] for e in evs] == ["fwd", "bwd", "step"]
    assert [e["depth"] for e in evs] == [1, 1, 0]
    assert evs[2]["args"] == {"step": 3}
    # children nest inside the parent's interval
    assert evs[0]["ts"] >= evs[2]["ts"]
    assert evs[0]["ts"] + evs[0]["dur"] <= evs[2]["ts"] + evs[2]["dur"]


def test_span_export_schema_roundtrip(tmp_path):
    tr = Tracer(trace_dir=tmp_path, level="steps")
    with tr.span("step", step=0):
        tr.complete("queued", tr.now(), tr.now(), tid=tr.track("request r"))
    tr.close()
    # streamed JSONL validates line-by-line against the schema
    assert schema.validate_file(tmp_path / "spans.jsonl") == []
    # Chrome trace: every X event has a dur, structure is loadable
    chrome = json.loads((tmp_path / "trace.json").read_text())
    phs = {e["ph"] for e in chrome["traceEvents"]}
    assert phs == {"X", "M"}
    for e in chrome["traceEvents"]:
        assert ("dur" in e) == (e["ph"] == "X")
    # the instants and counter samples of older artifacts still validate
    for ph in ("i", "C"):
        assert schema.validate_line({"name": "m", "ph": ph, "ts": 1.0,
                                     "args": {"value": 1}}) == []


def test_schema_rejects_malformed_lines():
    assert schema.validate_line({"event": "nope"}) != []
    assert schema.validate_line({"event": "step", "step": 1}) != []
    assert schema.validate_line({"ph": "X", "name": "s", "ts": 1}) != []
    assert schema.validate_line({"what": 1}) != []
    ok_step = {"event": "step", "step": 1, "loss": 0.5,
               "tokens_per_sec": 10.0, "recompiles": 0}
    assert schema.validate_line(ok_step) == []
    assert schema.validate_line(
        {"event": "step", "step": 1, "loss": 0.5,
         "tokens_per_sec": 10.0, "recompiles": 0.5}) != []


def test_schema_v4_ledger_lines_validate():
    assert schema.SCHEMA_VERSION >= 4  # later versions extend, never narrow
    step = {"event": "step", "step": 3, "loss": 1.0,
            "tokens_per_sec": 10.0, "wall": 123.4}
    assert schema.validate_line(step) == []
    led = {"event": "ledger", "kind": "val", "seconds": 1.25,
           "wall": 123.4, "t": 0.5}
    assert schema.validate_line(led) == []
    assert schema.validate_line({"event": "ledger"})  # kind is required
    assert schema.validate_line({"event": "ledger", "kind": "x",
                                 "seconds": "long"})
    gen = {"event": "generate", "tokens_per_sec": 55.0,
           "bytes_per_token": 1024, "hbm_util": None}
    assert schema.validate_line(gen) == []
    # v1-v3 lines (no wall/ledger) keep validating
    old = {"event": "step", "step": 0, "loss": 2.0,
           "tokens_per_sec": 5.0}
    assert schema.validate_line(old) == []


def test_off_level_reaches_the_ring_and_nothing_else(monkeypatch,
                                                     tmp_path):
    """`--telemetry off`: a span is recorded in the ring, and that is
    all — no subscriber call, no file, and block_until_ready is never
    reached (the engines' async dispatch stays async)."""
    def boom(*_a, **_k):  # any fence attempt explodes
        raise AssertionError("off-level telemetry fenced device work")

    monkeypatch.setattr(trace_mod, "_block", boom)
    tr = Tracer(trace_dir=tmp_path / "t", level="off")
    seen = []
    tr.subscribers.append(seen.append)
    with tr.span("step", step=0) as sp:
        sp.fence(object())
    tr.complete("late", 0.0, 1.0)
    assert tr.track("request r") == 0
    ring = tr.ring()
    assert [(e[1], e[2], e[5]) for e in ring] == [(None, "step",
                                                  {"step": 0})]
    assert ring[0][3] <= ring[0][4]
    assert seen == []
    tr.close()
    assert not (tmp_path / "t").exists()
    # and at `steps` level fences are still skipped (dispatch preserved)
    tr2 = Tracer(level="steps")
    with tr2.span("step") as s:
        s.fence(object())
    assert len(tr2.events) == 1


def test_ring_order_parent_and_bound(monkeypatch):
    """The ring is ordered by close, `seq` by open, `parent_seq` is
    the innermost span open on the thread, and the ring keeps the last
    `RING_CAP` spans whatever the level."""
    monkeypatch.setattr(trace_mod, "RING_CAP", 4)
    tr = Tracer(level="off")
    with tr.span("engine.step", tick=7):
        with tr.span("decode"):
            with tr.span("decode.fetch"):
                pass
        with tr.span("admit"):
            pass
    by_name = {e[2]: e for e in tr.ring()}
    assert [e[2] for e in tr.ring()] == ["decode.fetch", "decode",
                                         "admit", "engine.step"]
    assert [by_name[n][0] for n in ("engine.step", "decode",
                                    "decode.fetch", "admit")] == [0, 1, 2, 3]
    assert by_name["engine.step"][1] is None
    assert by_name["decode"][1] == by_name["engine.step"][0]
    assert by_name["decode.fetch"][1] == by_name["decode"][0]
    assert by_name["admit"][1] == by_name["engine.step"][0]
    assert [by_name[n][6] for n in ("engine.step", "decode",
                                    "decode.fetch")] == [0, 1, 2]
    for i in range(6):
        with tr.span("s", i=i):
            pass
    assert [e[5]["i"] for e in tr.ring()] == [2, 3, 4, 5]
    assert tr.event_count == 10
    # a thread's spans nest under that thread's spans only
    import threading

    with tr.span("main"):
        th = threading.Thread(
            target=lambda: tr.span("worker").__enter__().__exit__())
        th.start()
        th.join()
    assert {e[2]: e[1] for e in tr.ring()}["worker"] is None


def test_spans_level_fences_on_exit(monkeypatch):
    fenced = []
    monkeypatch.setattr(trace_mod, "_block",
                        lambda arrs: fenced.extend(arrs))
    tr = Tracer(level="spans")
    tok = object()
    with tr.span("step") as s:
        s.fence(tok)
    assert fenced == [tok]


# ------------------------------------------------------------- bubble


def test_gpipe_2stage_replay_matches_closed_form():
    """The satellite gate: a known 2-stage GPipe trace replayed at unit
    cost must land exactly on verify.py's closed form
    (pp-1)/(n_mu+pp-1)."""
    n_mu, pp = 4, 2
    ops = [(k, s, m, 1.0)
           for (k, s, m) in bubble._placement("gpipe", n_mu, pp)]
    rep = bubble.replay_trace(ops, pp)
    closed = (pp - 1) / (n_mu + pp - 1)
    assert rep["bubble_fraction"] == pytest.approx(closed, abs=1e-4)
    assert rep["makespan"] == 2 * (n_mu + pp - 1)
    st = bubble.static_bubble("gpipe", n_mu, pp)
    assert st["bubble_fraction"] == pytest.approx(closed, abs=1e-4)


def test_gpipe_costed_replay_is_ratio_invariant():
    """GPipe's fill and drain scale with (c_f + c_b) together, so the
    measured F:B ratio must NOT move the fraction — the property that
    makes measured-vs-static a structural check for gpipe."""
    a = bubble.costed_replay("gpipe", 8, 2, c_f=1.0, c_b=1.0)
    b = bubble.costed_replay("gpipe", 8, 2, c_f=1.0, c_b=2.7)
    assert a["bubble_fraction"] == pytest.approx(
        b["bubble_fraction"], abs=1e-3)


@pytest.mark.parametrize("schedule,n_mu,pp,vpp", [
    ("1f1b", 8, 4, 1), ("zb", 8, 4, 1), ("gpipe", 8, 2, 2)])
def test_unit_replay_matches_static(schedule, n_mu, pp, vpp):
    """Unit-cost replay of each schedule's verified placement agrees
    with the static fraction within a round of slack (the replay packs
    zero-cost waits the round model counts as whole rounds)."""
    st = bubble.static_bubble(schedule, n_mu, pp, vpp)
    rep = bubble.costed_replay(schedule, n_mu, pp, vpp)
    # same work, same placement: makespans within 10%
    assert rep["makespan"] <= st["makespan"] * 1.1 + 1
    assert rep["bubble_fraction"] == pytest.approx(
        st["bubble_fraction"], abs=0.05)


def test_replay_rejects_unsound_trace():
    ops = [("B", 0, 0, 1.0)]  # backward with no forward anywhere
    with pytest.raises(ValueError, match="dataflow"):
        bubble.replay_trace(ops, 1)


def test_trace_bubble_wall_clock():
    evs = [dict(stage=0, ts=0.0, dur=8.0), dict(stage=1, ts=1.0, dur=8.0)]
    rep = bubble.trace_bubble(evs)
    assert rep["bubble_fraction"] == pytest.approx(1 - 16 / 18, abs=1e-4)


def test_two_point_bubble_math():
    # t(n) = (n + pp - 1) * c: n=8, pp=2, c=1 -> t1=9; 2n -> t2=17
    r = bubble.two_point_bubble(9.0, 17.0)
    assert r["bubble_fraction"] == pytest.approx(1 / 9, abs=1e-6)
    assert r["t_ideal"] == pytest.approx(8.0)
    # noise pushing t2 past 2*t1 clamps at 0, never negative
    assert bubble.two_point_bubble(1.0, 2.3)["bubble_fraction"] == 0.0


def test_span_replay_ops_filtering():
    evs = [
        {"name": "Forward", "ph": "X", "ts": 0, "dur": 5,
         "args": {"stage": 0, "mu": 0, "batch": 7}},
        {"name": "BackwardGradAcc", "ph": "X", "ts": 5, "dur": 5,
         "args": {"stage": 0, "mu": 0, "batch": 7}},
        {"name": "Forward", "ph": "X", "ts": 0, "dur": 5,
         "args": {"stage": 0, "mu": 0, "batch": 8}},
        {"name": "step", "ph": "X", "ts": 0, "dur": 99, "args": {}},
    ]
    ops = bubble.span_replay_ops(evs, batch=7)
    assert ops == [("F", 0, 0, 5), ("B", 0, 0, 5)]


# -------------------------------------------------- engine integration


def _mlp_vm(pp=2, dp=1):
    from shallowspeed_tpu.models.mlp import MLPStage
    from shallowspeed_tpu.optim import SGD
    from shallowspeed_tpu.parallel.mesh import make_mesh
    from shallowspeed_tpu.parallel.worker import PipelineExecutor

    mesh = make_mesh(dp, pp)
    stages = [MLPStage([12, 14, 13, 10], s, pp, batch_size=16)
              for s in range(pp)]
    return PipelineExecutor(mesh, stages, SGD(0.1))


class _DS:
    def __init__(self, rank=0, rows=4):
        self.rank, self.rows = rank, rows

    def load_micro_batch_input(self, b, mu):
        rng = np.random.default_rng([b, mu, self.rank])
        return rng.standard_normal((self.rows, 12)).astype(np.float32)

    def load_micro_batch_target(self, b, mu):
        y = np.zeros((self.rows, 10), np.float32)
        y[:, 0] = 1.0
        return y


def test_vm_executables_compile_exactly_once():
    """The recompile counter's first catch, pinned: the zero-grad
    accumulator must be born with the steady-state sharding, or the
    second BackwardGradAcc of every batch recompiles each stage's
    backward (worker.StageRuntime._zeros_acc)."""
    from shallowspeed_tpu.parallel.schedules import GPipeSchedule

    eng = _mlp_vm()
    for b in range(3):
        eng.train_batch(GPipeSchedule, 4, b, [_DS()])
    counts = compile_counts(eng.telemetry_entrypoints())
    exercised = {k: v for k, v in counts.items() if v > 0}
    assert exercised, "VM published no exercised entrypoints"
    multi = {k: v for k, v in exercised.items() if v > 1}
    assert not multi, f"VM executables recompiled: {multi}"


def test_vm_spans_replay_to_bubble():
    """At the `spans` level the VM's fenced per-op spans ARE the
    executed schedule trace: the replay consumes them and yields a
    bubble fraction; op count matches the schedule's compute ops."""
    from shallowspeed_tpu.parallel.schedules import GPipeSchedule

    tr = trace_mod.configure(level="spans")
    try:
        eng = _mlp_vm()
        n_mu = 4
        eng.train_batch(GPipeSchedule, n_mu, 0, [_DS()])
        ops = bubble.span_replay_ops(tr.events, batch=0)
        # pp stages x n_mu forwards + n_mu backwards each
        assert len(ops) == 2 * 2 * n_mu
        rep = bubble.replay_trace(ops, 2)
        assert 0.0 <= rep["bubble_fraction"] < 1.0
        assert rep["n_stages"] == 2
        # measured comm accounting counted the stage hops
        traffic = eng.telemetry_traffic()
        assert traffic.get("pp_p2p", 0) > 0
        assert traffic.get("dp_psum", 0) > 0
    finally:
        trace_mod.configure(level="off")


def test_run_telemetry_hbm_cross_check_and_traffic():
    """A real engine end-to-end: static report exists after one step,
    live HBM stays within the static bound, collective bytes per axis
    are positive, recompiles stay 0 across steps."""
    from jax.sharding import Mesh

    from shallowspeed_tpu.models.transformer import TransformerConfig
    from shallowspeed_tpu.optim import Adam
    from shallowspeed_tpu.parallel.pipeline_lm import PipelineLMEngine

    cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=4,
                            max_seq=32)
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("dp", "pp"))
    eng = PipelineLMEngine(cfg, Adam(1e-3), mesh, n_mubatches=2)
    rt = RunTelemetry(eng)
    rng = np.random.default_rng(0)
    tok = rng.integers(0, 64, (4, 16)).astype(np.int32)
    tgt = np.roll(tok, -1, 1).astype(np.int32)
    # skeleton capture is gated on an active tracer (the off path must
    # pay nothing) — run the steps under a steps-level tracer
    trace_mod.configure(level="steps")
    try:
        for _ in range(3):
            eng.train_batch(tok, tgt)
    finally:
        trace_mod.configure(level="off")
    fields = rt.step_fields(window_secs=1.0, steps_in_window=3)
    assert fields["recompiles"] == 0
    assert fields["hbm_within_bound"], fields
    assert fields["hbm_live_mib"] > 0
    assert fields["coll_bytes_per_step"] > 0
    assert "pp" in fields["coll_bytes_by_axis"]
    assert fields["coll_gbps"] > 0
    # the step line validates as a metrics step event
    line = {"event": "step", "step": 2, "loss": 1.0,
            "tokens_per_sec": 1.0, **fields}
    line.pop("coll_bytes_by_axis")
    assert schema.validate_line(line) == []
    summary = rt.run_summary()
    assert summary["hbm_check"]["within_bound"]


def test_memory_cross_check_tolerance():
    from shallowspeed_tpu.telemetry import memory

    assert memory.cross_check(100, 100)["within_bound"]
    assert memory.cross_check(104, 100)["within_bound"]  # inside 1.05
    assert not memory.cross_check(120, 100)["within_bound"]


# -------------------------------------------------------- collectives


def test_collective_traffic_counts_scan_trips():
    from functools import partial

    from jax.sharding import Mesh, PartitionSpec as P

    from shallowspeed_tpu.telemetry.collectives import collective_traffic
    from jax import shard_map

    mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))

    @jax.jit
    @partial(shard_map, mesh=mesh, in_specs=P("dp"), out_specs=P("dp"))
    def fn(x):
        def body(c, xi):
            return c + jax.lax.psum(xi, "dp"), None

        c, _ = jax.lax.scan(body, jnp.zeros_like(x[0]), x)
        return c[None] + jax.lax.psum(x, "dp")

    x = jax.ShapeDtypeStruct((6, 8), np.float32)  # 3 rows/device
    rep = collective_traffic(fn, x)
    dp = rep["per_axis"]["dp"]
    # scan runs 3 iterations of an 8-float psum + one 3x8 psum outside
    assert dp["calls"] == 4
    assert dp["bytes"] == 3 * 8 * 4 + 3 * 8 * 4
    assert not rep["approximate"]


def test_steprates_merges_telemetry_fields():
    from shallowspeed_tpu.metrics import StepRates

    class FakeTelem:
        def step_fields(self, window_secs=None, steps_in_window=None):
            return {"recompiles": 0, "bubble_static": 0.2,
                    "win": window_secs, "n": steps_in_window}

    # 3 ticks: init, log_point's `now`, and the post-telemetry tick
    # that books telemetry's own cost as excluded pause time
    clock = iter([0.0, 10.0, 12.0, 20.0, 21.0]).__next__
    rates = StepRates(100.0, clock=clock, telemetry=FakeTelem())
    r = rates.log_point(5)
    assert r["tokens_per_sec"] == pytest.approx(50.0)
    assert r["bubble_static"] == 0.2
    assert r["n"] == 5 and r["win"] == pytest.approx(10.0)
    # the 2s the telemetry fields took is excluded from window 2
    r2 = rates.log_point(4)
    assert r2["tokens_per_sec"] == pytest.approx(400 / 8.0)


def test_replay_rejects_mixed_window_and_pads_partial_capture():
    # two epochs' worth of the same op in one window -> rejected
    ops = [("F", 0, 0, 1.0), ("F", 0, 0, 1.0)]
    with pytest.raises(ValueError, match="duplicate"):
        bubble.replay_trace(ops, 1)
    # a partial capture (stage 1's spans missing) counts the absent
    # processor as idle instead of reporting a 1-deep pipeline
    ops = [("F", 0, m, 1.0) for m in range(4)]
    rep = bubble.replay_trace(ops, 2)
    assert rep["n_stages"] == 2
    assert rep["bubble_fraction"] == pytest.approx(0.5, abs=1e-4)
    # and naming more processors than the pipeline has is mislabeling
    with pytest.raises(ValueError, match="mislabeled"):
        bubble.replay_trace([("F", 0, 0, 1.0), ("F", 1, 0, 1.0)], 1)


def test_tracer_event_windows_survive_buffer_eviction(monkeypatch):
    monkeypatch.setattr(trace_mod, "RING_CAP", 4)
    tr = Tracer(level="steps")
    for i in range(10):
        with tr.span("e", i=i):
            pass
    assert tr.event_count == 10
    # a window starting inside the buffer returns exactly that suffix
    assert [e["args"]["i"] for e in tr.events_since(8)] == [8, 9]
    # a window starting before the eviction point returns what remains
    assert [e["args"]["i"] for e in tr.events_since(2)] == [6, 7, 8, 9]
    assert tr.events_since(10) == []


def test_chrome_trace_sources_full_stream_from_jsonl(tmp_path,
                                                    monkeypatch):
    """trace.json must carry the COMPLETE stream even when the ring
    evicted early spans, and the track names and lifecycle phases the
    ring never holds (spans.jsonl is the source of truth): a request's
    `e` phase is no `e` span."""
    monkeypatch.setattr(trace_mod, "RING_CAP", 2)
    tr = Tracer(trace_dir=tmp_path, level="steps")
    for i in range(5):
        with tr.span("e", i=i):
            pass
    tr.complete("e", tr.now(), tr.now(), tid=tr.track("request r"), i=9)
    tr.close()
    assert [e[5]["i"] for e in tr.ring()] == [3, 4]
    assert [e["args"]["i"] for e in tr.spans_named("e")] == [3, 4]
    chrome = json.loads((tmp_path / "trace.json").read_text())
    assert len(chrome["traceEvents"]) == 7


def test_make_calibration_twin_trains_at_double_n_mu():
    """The on-chip two-point path: the twin must construct (pinning
    the 11-arg constructor call against signature drift), run a step
    on a row-doubled batch, and leave the live engine untouched."""
    from jax.sharding import Mesh

    from shallowspeed_tpu.models.transformer import TransformerConfig
    from shallowspeed_tpu.optim import SGD
    from shallowspeed_tpu.parallel.pipeline_lm import PipelineLMEngine

    cfg = TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=4,
                            max_seq=32)
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("dp", "pp"))
    eng = PipelineLMEngine(cfg, SGD(0.1), mesh, n_mubatches=2)
    twin = eng.make_calibration_twin()
    assert twin.n_mu == 2 * eng.n_mu
    assert (twin.schedule, twin.pp, twin.vpp) == (
        eng.schedule, eng.pp, eng.vpp)
    rng = np.random.default_rng(0)
    tok = rng.integers(0, 64, (4, 16)).astype(np.int32)
    tgt = np.roll(tok, -1, 1).astype(np.int32)
    before = eng._step_count
    tok2 = np.concatenate([tok, tok], axis=0)
    tgt2 = np.concatenate([tgt, tgt], axis=0)
    loss = twin.train_batch(tok2, tgt2)
    assert np.isfinite(loss)
    assert eng._step_count == before  # live trajectory untouched


# ---------------------------------------- spans inside the program


# entries that something else timed: they lie under whichever span
# was open on the thread at the time, or under none
AFTER_THE_FACT = ("startup", "gc", "trace", "lower", "compile",
                  "cache_miss")


@pytest.fixture
def global_tracer():
    """A fresh process-global tracer at `off`, as a driver starts."""
    tr = trace_mod.configure(level="off")
    yield tr
    trace_mod.configure(level="off")


@pytest.fixture(scope="module")
def tiny_serving():
    from shallowspeed_tpu.models import transformer as T
    from shallowspeed_tpu.serving import ServingEngine

    cfg = T.TransformerConfig(vocab=48, d_model=24, n_heads=2,
                              n_layers=2, max_seq=96)
    eng = ServingEngine(jax.device_put(T.init(cfg, seed=1)), cfg,
                        n_blocks=48, block_size=8, max_slots=2,
                        prefill_chunk=16)

    def serve(prefix):
        rng = np.random.default_rng(3)
        for i in range(3):
            eng.submit(rng.integers(0, cfg.vocab, 20).astype(np.int32),
                       5, rid=f"{prefix}{i}")
        eng.run()

    serve("warm-")
    return eng, serve


def test_serving_spans_at_off_compile_nothing_and_nest(global_tracer,
                                                       tiny_serving):
    """The scheduler's phases reach the ring at `off`, a served request
    compiles no new executable for them, and the untagged rest of a
    step (the self time of `engine.step`) stays a small part of it."""
    eng, serve = tiny_serving
    base = eng.executable_counts()
    serve("ring-")
    assert eng.executable_counts() == base
    ring = global_tracer.ring()
    by_seq = {e[0]: e for e in ring}
    parent = lambda e: by_seq[e[1]][2] if e[1] is not None else None
    names = {e[2] for e in ring}
    assert {"engine.step", "admit", "prefill", "prefill.dispatch",
            "decode", "decode.prep", "decode.dispatch", "decode.fetch",
            "decode.emit"} <= names
    # the first token is sampled inside the chunk's program and fetched
    # with the tick's: the spans of its own sample and fetch are gone
    assert not {"prefill.sample", "prefill.fetch", "compile"} & names
    for e in ring:
        if e[2] in AFTER_THE_FACT:
            continue        # under whichever span was open, or none
        # a step is one turn of the decode loop; the chunk it holds is
        # dispatched inside that turn
        want = {"engine.step": None, "admit": "engine.step",
                "prefill": "decode", "decode": "engine.step",
                "alloc": "decode.prep"}.get(e[2], e[2].split(".")[0])
        assert parent(e) == want, (e[2], parent(e))
    steps = [e for e in ring if e[2] == "engine.step"]
    assert [e[5]["tick"] for e in steps] == sorted(
        e[5]["tick"] for e in steps)
    assert sum(e[5]["n_admitted"] for e in ring if e[2] == "admit") == 3
    # the first turn holds the first prompt's first chunk and no tick
    assert set(next(e for e in ring if e[2] == "decode")[5]) == {"ahead"}
    decode = next(e for e in ring if e[2] == "decode" and not
                  e[5].get("fused", 1))
    # one group of layers (`full`): what the tick read and what its rows
    # hold are said for the model and for the group, nothing was released
    assert set(decode[5]) == {"ahead", "n_active", "width", "blocks_read",
                              "blocks_table", "blocks_read_full",
                              "full_blocks", "released", "fused"}
    assert decode[5]["blocks_read_full"] == decode[5]["blocks_read"]
    assert decode[5]["released"] == 0
    assert decode[5]["width"] == 4
    covered = sum(e[4] - e[3] for e in ring
                  if parent(e) == "engine.step"
                  and e[2] not in AFTER_THE_FACT)
    whole = sum(e[4] - e[3] for e in steps)
    assert 0.75 * whole <= covered <= whole


def test_a_fused_step_says_so_in_its_spans_and_counters(global_tracer,
                                                        tiny_serving):
    """A step that holds a chunk dispatches one program: its spans are
    `decode` > `decode.prep`, `prefill` > `prefill.dispatch`, then
    `decode.fetch` and `decode.emit` of the program before it, and no
    `decode.dispatch`. `fused` on the `decode` span is 1 where a tick
    rode in a chunk's program, `rows_rode` on the `prefill` span the
    tick's live rows in it (0: nobody was decoding), and
    `counters["ticks_fused"]` beside `["ticks"]` their count."""
    eng, serve = tiny_serving
    before = {k: eng.counters[k] for k in ("ticks", "ticks_fused",
                                           "prefill_chunks")}
    serve("fused-")
    ring = global_tracer.ring()
    kids = {}
    for e in ring:
        kids.setdefault(e[1], []).append(e[2])
    turns = [e for e in ring if e[2] == "decode"]
    chunks = {e[1]: e for e in ring if e[2] == "prefill"}
    assert len(chunks) == eng.counters["prefill_chunks"] \
        - before["prefill_chunks"] == 6
    for t in turns:
        held = chunks.get(t[0])
        assert ("prefill" in kids[t[0]]) == (held is not None)
        assert ("decode.dispatch" in kids[t[0]]) == (
            held is None and "n_active" in t[5])
        if held is None:
            assert t[5].get("fused", 0) == 0
            continue
        assert kids[held[0]] == ["prefill.dispatch"]
        assert kids[t[0]][:2] == ["decode.prep", "prefill"]
        assert set(kids[t[0]][2:]) <= {"decode.fetch", "decode.emit"}
        assert held[5]["rows_rode"] == t[5].get("n_active", 0)
        assert t[5].get("fused", 0) == (held[5]["rows_rode"] > 0)
        # the one width of a tick's table inside a chunk's program
        assert t[5].get("width", 16) == 16      # 96 positions
    fused = sum(t[5].get("fused", 0) for t in turns)
    # two slots, three prompts of two chunks: the second and the third
    # prompt's chunks each ride with a decoder
    assert fused == eng.counters["ticks_fused"] - before["ticks_fused"] > 0
    assert fused < eng.counters["ticks"] - before["ticks"]
    assert sum(c[5]["rows_rode"] > 0 for c in chunks.values()) == fused


def test_prefill_span_says_what_the_chunk_read(global_tracer, tiny_serving):
    """`blocks_read` on a `prefill` span is the table columns the
    chunk's read took in one layer and `blocks_table` the table's
    width: a table this narrow (4 blocks of 8) is gathered whole in
    every chunk (tests/test_paged_prefill.py has the walk of a wide
    one). The engine's `prefill_blocks_*` counters are their sums."""
    eng, serve = tiny_serving
    names = ("prefill_blocks_read", "prefill_blocks_table", "prefill_chunks")
    before = {k: eng.counters[k] for k in names}
    serve("chunks-")
    chunks = [e[5] for e in global_tracer.ring() if e[2] == "prefill"]
    assert sorted((a["chunk"], a["blocks_read"], a["blocks_table"])
                  for a in chunks) == [(0, 4, 4)] * 3 + [(1, 4, 4)] * 3
    assert all(a["blocks_read_full"] == a["blocks_read"] for a in chunks)
    assert eng.counters["prefill_chunks"] - before["prefill_chunks"] == 6
    for k in names[:2]:
        assert eng.counters[k] - before[k] == sum(
            a[k.removeprefix("prefill_")] for a in chunks) == 24


def test_decode_span_says_what_the_tick_read(global_tracer, tiny_serving):
    """`blocks_read` on a `decode` span is the pool blocks its read
    walked, from the host's own positions: a decoding row at position p
    its first p // 8 + 1, an idle row one (the scratch block);
    `blocks_table` is the table's room, slots x width. The engine's
    counters of the same names are their sums."""
    eng, serve = tiny_serving
    before = {k: eng.counters[k]
              for k in ("blocks_read", "blocks_table", "ticks")}
    serve("blocks-")
    # a turn that only lands the tick in flight dispatched none
    ticks = [e[5] for e in global_tracer.ring()
             if e[2] == "decode" and "blocks_read" in e[5]]
    assert len(ticks) == eng.counters["ticks"] - before.pop("ticks")
    for a in ticks:
        assert a["blocks_table"] == eng.max_slots * a["width"]
        # prompts of 20 and 5 new tokens: positions 20-24, 3 or 4 blocks
        # a decoding row, 1 an idle one
        idle = eng.max_slots - a["n_active"]
        assert 3 * a["n_active"] + idle <= a["blocks_read"] \
            <= 4 * a["n_active"] + idle <= a["blocks_table"]
    assert any(a["blocks_read"] < a["blocks_table"] for a in ticks)
    for k in before:
        assert eng.counters[k] - before[k] == sum(a[k] for a in ticks)


def test_profiler_session_holds_the_programs_spans(global_tracer,
                                                   tiny_serving,
                                                   tmp_path):
    """Under a `jax.profiler` session the host plane holds the
    engine's spans as `ss:<name>` on the profiler's clock, nested as
    the program nests them — with no switch thrown anywhere."""
    eng, serve = tiny_serving
    jax.profiler.start_trace(str(tmp_path))
    try:
        serve("prof-")
    finally:
        jax.profiler.stop_trace()
    found = sorted(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    assert found
    host = next(p for p in
                jax.profiler.ProfileData.from_file(str(found[-1])).planes
                if p.name == "/host:CPU")
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
             for line in host.lines for e in line.events
             if e.name.startswith("ss:")]
    steps = [s for s in spans if s[0] == "ss:engine.step"]
    fetches = [s for s in spans if s[0] == "ss:decode.fetch"]
    assert steps and fetches
    assert len(steps) == sum(1 for e in global_tracer.ring()
                             if e[2] == "engine.step")
    for _, a, b in fetches:
        assert any(s <= a and b <= e for _, s, e in steps)


def test_a_compile_is_a_span_under_the_open_span(global_tracer,
                                                 tmp_path):
    """A fresh `jax.jit` call inside a span leaves one `compile` span
    whose parent is that span, and with the persistent cache on a
    `cache_miss` beside it; the same program again compiles (a fetch
    from the cache) and misses nothing."""
    from jax.experimental.compilation_cache import compilation_cache

    def fresh(x):
        return x * 3 + 1

    x5, x7 = jnp.ones(5), jnp.ones(7)   # their own compiles, out here
    with global_tracer.span("outer"):
        jax.jit(fresh)(x5).block_until_ready()
    ring = global_tracer.ring()
    outer = next(e for e in ring if e[2] == "outer")
    compiles = [e for e in ring if e[2] == "compile" and e[1] == outer[0]]
    assert [e[5]["fun"] for e in compiles] == ["jit(fresh)"]
    assert outer[3] <= compiles[0][4] <= outer[4]

    old = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        compilation_cache.reset_cache()
        with global_tracer.span("cold"):
            jax.jit(lambda x: x * 5 - 2)(x7).block_until_ready()
        with global_tracer.span("warm"):
            jax.jit(lambda x: x * 5 - 2)(x7).block_until_ready()
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    by_seq = {e[0]: e for e in global_tracer.ring()}
    under = lambda name: [by_seq[e[1]][2] for e in by_seq.values()
                          if e[2] == name and e[1] is not None]
    assert under("cache_miss") == ["cold"]
    assert under("compile")[-2:] == ["cold", "warm"]


def test_a_fresh_jit_leaves_trace_lower_compile_in_that_order(
        global_tracer):
    """What `jax.monitoring` reports of a program's way to the device
    lands under the span open at the time, each entry recorded when its
    phase ended: `trace` (Python to a jaxpr), `lower` (jaxpr to MLIR),
    `compile`, the function's name in `fun`. The same shapes again
    leave nothing."""
    def newborn(x):
        return x * 7 - 3

    call = jax.jit(newborn)
    x = jnp.ones(11)                    # its own programs, out here
    with global_tracer.span("outer"):
        call(x).block_until_ready()
    first = global_tracer.event_count
    with global_tracer.span("again"):
        call(x).block_until_ready()
    ring = global_tracer.ring()
    outer = next(e for e in ring if e[2] == "outer")
    mine = [e for e in ring if e[1] == outer[0]
            and "newborn" in e[5].get("fun", "")]
    assert [(e[2], e[5]["fun"]) for e in mine] == [
        ("trace", "newborn"), ("lower", "jit(newborn)"),
        ("compile", "jit(newborn)")]
    assert [e[4] for e in mine] == sorted(e[4] for e in mine)
    for e in mine:
        assert outer[3] <= e[4] <= outer[4] and e[3] <= e[4] and e[6] == 1
    later = [e[2] for e in ring[-(global_tracer.event_count - first):]]
    assert not {"trace", "lower", "compile"} & set(later)
    assert "again" in later


def test_a_function_traced_inside_anothers_trace_lies_inside_it(
        global_tracer):
    """A jitted function called while another is traced reports its own
    tracing, inside its caller's interval: the union of the two `trace`
    entries is the outer one, their sum would count the inner twice."""
    @jax.jit
    def inner_fn(x):
        return jnp.tanh(x) + 2

    def outer_fn(x):
        return inner_fn(jnp.sin(x) * 3) - 1

    x = jnp.ones(13)
    with global_tracer.span("s"):
        jax.jit(outer_fn)(x).block_until_ready()
    traces = {e[5]["fun"]: e for e in global_tracer.ring()
              if e[2] == "trace"}
    inner, outer = traces["inner_fn"], traces["outer_fn"]
    assert inner[1] == outer[1] is not None     # both under `s`
    slack = 1e-4    # JAX times them on time.time, the tracer stamps their end
    assert outer[3] - slack <= inner[3] and inner[4] <= outer[4]
    assert 0 < inner[4] - inner[3] < outer[4] - outer[3]


def test_startup_is_one_top_level_entry_of_every_global_tracer():
    """`startup` runs from the process's start, as the kernel has it,
    to the first `watch_compiles()`; it lies under no span, and a
    tracer that `configure()` installs later holds it again, as its
    first entry."""
    import time

    trace_mod.watch_compiles()
    try:
        intervals = []
        for _ in range(2):
            tr = trace_mod.configure(level="off")
            with tr.span("work"):
                trace_mod.watch_compiles()      # once a process
            ups = [e for e in tr.ring() if e[2] == "startup"]
            assert len(ups) == 1 and tr.ring()[0] is ups[0]
            assert ups[0][:3] == (0, None, "startup") and ups[0][6] == 0
            intervals.append(ups[0][3:5])
        assert intervals[0] == intervals[1]
        t0, t1 = intervals[0]
        assert t0 == trace_mod.process_start() <= trace_mod._IMPORTED_AT
        assert t0 < t1 <= time.perf_counter()
        # this process is younger than an hour and older than its imports
        assert 0.5 < time.perf_counter() - t0 < 3600
    finally:
        trace_mod.configure(level="off")


def test_a_long_collection_is_an_entry_and_a_short_one_leaves_nothing(
        global_tracer):
    """A collection of `GC_SPAN_MIN_S` or more becomes a `gc` entry
    under the span that was open, with its generation and what it
    freed; a young collection of microseconds leaves nothing, so a
    step's entries in the ring stay what they are."""
    import gc

    trace_mod.watch_compiles()
    gc.collect()
    was_enabled = gc.isenabled()
    gc.disable()                # only the collections this test asks for
    try:
        with global_tracer.span("outer"):
            junk = [[i] for i in range(200_000)]
            for j in junk:
                j.append(j)                     # cycles: only gc frees them
            del junk, j
            first = global_tracer.event_count
            gc.collect()
            after_full = global_tracer.event_count
            gc.collect(0)
            gc.collect(0)
            assert global_tracer.event_count == after_full
    finally:
        if was_enabled:
            gc.enable()
    ring = global_tracer.ring()
    outer = ring[-1]
    (entry,) = ring[-(global_tracer.event_count - first):-1]
    assert entry[2] == "gc" and entry[1] == outer[0] and entry[6] == 1
    assert entry[5]["generation"] == 2 and entry[5]["collected"] >= 200_000
    assert entry[4] - entry[3] >= trace_mod.GC_SPAN_MIN_S
    assert outer[3] <= entry[3] <= entry[4] <= outer[4]


def test_a_collection_stands_in_a_live_profiler_trace_as_ss_gc(
        global_tracer, tmp_path):
    """While a profiler session is live a collection is annotated like
    any span: `ss:gc` in the `/host:CPU` plane, on the device's clock."""
    import gc

    trace_mod.watch_compiles()
    with global_tracer.span("before"):      # resolves the annotations
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        with global_tracer.span("outer"):
            gc.collect()
    finally:
        jax.profiler.stop_trace()
    gc.collect()                            # no session: no annotation
    found = sorted(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    host = next(p for p in
                jax.profiler.ProfileData.from_file(str(found[-1])).planes
                if p.name == "/host:CPU")
    spans = {e.name: (e.start_ns, e.start_ns + e.duration_ns)
             for line in host.lines for e in line.events
             if e.name in ("ss:gc", "ss:outer")}
    assert set(spans) == {"ss:gc", "ss:outer"}
    assert spans["ss:outer"][0] <= spans["ss:gc"][0] \
        <= spans["ss:gc"][1] <= spans["ss:outer"][1]
    assert trace_mod._GC_NOTE is None


def test_a_collection_inside_a_subscriber_re_enters_the_tracer(tmp_path):
    """A collection can start while a subscriber (the flight recorder)
    is mid-call under the tracer's lock, on the thread that holds it:
    its `gc` entry goes through `_close` and `_emit` again, which the
    re-entrant lock lets through. The entry reaches the ring, the file
    and the subscribers, inside the line that was being delivered."""
    import gc

    trace_mod.watch_compiles()
    tr = trace_mod.configure(trace_dir=tmp_path, level="steps")
    seen = []

    def collecting(ev):
        seen.append(ev["name"])
        if ev["name"] == "outer":
            junk = [[i] for i in range(200_000)]
            for j in junk:
                j.append(j)
            del junk, j
            gc.collect()
            seen.append("outer delivered")

    tr.subscribers.append(collecting)
    was_enabled = gc.isenabled()
    gc.disable()
    try:
        with tr.span("outer"):
            pass
    finally:
        if was_enabled:
            gc.enable()
        trace_mod.configure(level="off")
    assert seen[-3:] == ["outer", "gc", "outer delivered"]
    assert [e[2] for e in tr.ring()][-2:] == ["outer", "gc"]
    lines = [json.loads(line)["name"]
             for line in (tmp_path / "spans.jsonl").read_text().splitlines()]
    assert lines[-2:] == ["outer", "gc"]


def test_the_engines_constructors_are_build_spans(global_tracer):
    """`build {engine}` is the whole constructor of the two engines the
    benchmark runs, with the parts that are not a compile under their
    own names: the pools' allocation in the serving engine; the
    weights drawn on the host and the placement in the train engine.
    `backend.init` is `device_stamp()`'s touch of the backend."""
    from jax.sharding import Mesh

    from shallowspeed_tpu import runtime
    from shallowspeed_tpu.models import transformer as T
    from shallowspeed_tpu.optim import SGD
    from shallowspeed_tpu.parallel.context import ContextParallelEngine
    from shallowspeed_tpu.serving import ServingEngine

    cfg = T.TransformerConfig(vocab=48, d_model=24, n_heads=2,
                              n_layers=2, max_seq=96)
    params = jax.device_put(T.init(cfg, seed=1))
    runtime.device_stamp()
    ServingEngine(params, cfg, n_blocks=40, block_size=8, max_slots=2,
                  prefill_chunk=16)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("dp", "sp"))
    ContextParallelEngine(cfg, SGD(0.1), mesh, seed=0)
    ring = [e for e in global_tracer.ring() if e[2] not in AFTER_THE_FACT]
    assert [e[2] for e in ring] == ["backend.init", "build", "build.init",
                                    "build.place", "build"]
    stamp, serving, init, place, train = ring
    assert stamp[1] is None and serving[1] is None and train[1] is None
    assert serving[5] == {"engine": "ServingEngine"}
    assert train[5] == {"engine": "ContextParallelEngine"}
    assert init[1] == place[1] == train[0] and init[4] <= place[3]
    # what the constructors trace, lower and compile falls under them
    by_seq = {e[0]: e for e in global_tracer.ring()}
    under = {by_seq[e[1]][2] for e in global_tracer.ring()
             if e[2] in ("trace", "lower", "compile") and e[1] is not None}
    assert under <= {"build", "build.init", "build.place"}


def test_gaps_names_the_span_a_device_idled_in(tmp_path, capsys):
    """`--gaps`: busy is the union of a device's operations, and each
    idle gap goes to the innermost `ss:` span open at its middle."""
    from shallowspeed_tpu.telemetry.__main__ import main
    from shallowspeed_tpu.telemetry.profiler import device_gaps

    trace = {
        "/device:TPU:0": {"XLA Ops": [
            ["%a = f32[8] fusion()", 0.0, 1.0],
            ["%b = f32[8] fusion()", 0.5, 1.0],     # overlaps: busy 0-1.5
            ["%c = f32[8] fusion()", 2.0, 1.0],
            ["%d = f32[8] fusion()", 3.5, 0.5]],
            "XLA Modules": [["jit__decode_tick(1)", 0.0, 4.0]]},
        "/device:TPU:1": {"XLA Ops": [["%a = f32[8] fusion()", 0.0, 4.0]]},
        "/host:CPU": {"python": [
            ["ss:engine.step", 1.0, 2.8], ["ss:decode", 1.2, 1.0],
            ["ss:decode.prep", 1.4, 0.6], ["bench:step", 0.9, 3.0],
            ["ss:engine.step", 3.85, 0.1]]},
    }
    # a collection inside a step takes the gap that lies under it
    stalled = dict(trace, **{"/host:CPU": {"python": trace["/host:CPU"][
        "python"] + [["ss:gc", 3.05, 0.4]]}})
    assert device_gaps(stalled)[0]["idle_by_span"] == {
        "decode.prep": pytest.approx(0.5), "gc": pytest.approx(0.5)}
    dev0, dev1 = device_gaps(trace)
    assert dev0["traced_s"] == 4.0 and dev0["busy_s"] == pytest.approx(3.0)
    # gap 1.5-2.0 (middle 1.75) sits in decode.prep, innermost of three;
    # gap 3.0-3.5 (middle 3.25) in engine.step alone
    assert dev0["idle_by_span"] == {"decode.prep": pytest.approx(0.5),
                                    "engine.step": pytest.approx(0.5)}
    assert dev1["idle_s"] == 0.0 and dev1["idle_by_span"] == {}
    path = tmp_path / "trace.json"
    path.write_text(json.dumps(trace))
    assert main(["--gaps", str(path)]) == 0
    out = capsys.readouterr().out
    assert "/device:TPU:0: traced 4.000 s, busy 3.000 s, idle 1.000 s" in out
    assert "idle in decode.prep" in out
    path.write_text(json.dumps(stalled))
    assert main(["--gaps", str(path)]) == 0
    assert "idle in gc" in capsys.readouterr().out
    path.write_text(json.dumps({"/host:CPU": trace["/host:CPU"]}))
    assert main(["--gaps", str(path)]) == 1


def test_gaps_on_a_trace_recorded_on_the_chip(capsys):
    """Three decode ticks of `olmo-1b.chat` on a TPU v5e (PR 26; device
    0's operations and the host's `ss:` / `bench:` spans, names cut
    short): between two ticks the device waits 2.7 ms, and for most of
    it the host is still inside `decode.fetch`, which returns 2.3 ms
    after the tick's last operation."""
    from pathlib import Path

    from shallowspeed_tpu.telemetry.__main__ import main
    from shallowspeed_tpu.telemetry.profiler import (device_gaps,
                                                     load_device_trace)

    path = Path(__file__).parent / "data" / "chat_ticks_trace.json.gz"
    (dev,) = device_gaps(load_device_trace(path))
    assert dev["device"] == "/device:TPU:0"
    assert dev["busy_s"] + dev["idle_s"] == pytest.approx(dev["traced_s"])
    assert 0.04 < dev["idle_s"] / dev["traced_s"] < 0.08
    top = next(iter(dev["idle_by_span"]))
    assert top == "decode.fetch"
    assert dev["idle_by_span"][top] > 0.9 * dev["idle_s"]
    assert "bench:step" not in dev["idle_by_span"]
    assert main(["--gaps", str(path)]) == 0
    assert "idle in decode.fetch" in capsys.readouterr().out
