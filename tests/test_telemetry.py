"""Telemetry subsystem gates (tier-1).

What must hold:
- span nesting + the export schema round-trip (spans.jsonl validates
  against `telemetry.schema`; trace.json is Chrome-trace shaped);
- bubble accounting: the executed-trace replay of a known 2-stage
  GPipe trace matches `verify.py`'s closed form, costed replays are
  F:B-ratio-invariant for gpipe, and the static fractions agree with
  the simulators per schedule;
- HBM live-vs-static cross-check within tolerance on a real engine;
- `--telemetry off` inserts NO fences and buffers nothing — the
  engines' async dispatch pipeline is untouched;
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
from shallowspeed_tpu.telemetry.trace import Tracer, _NULL_SPAN


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
        tr.event("marker", note="x")
        tr.counter("hbm_bytes", 123)
    tr.close()
    # streamed JSONL validates line-by-line against the schema
    assert schema.validate_file(tmp_path / "spans.jsonl") == []
    # Chrome trace: every X event has a dur, structure is loadable
    chrome = json.loads((tmp_path / "trace.json").read_text())
    phs = {e["ph"] for e in chrome["traceEvents"]}
    assert phs == {"X", "i", "C"}
    for e in chrome["traceEvents"]:
        assert ("dur" in e) == (e["ph"] == "X")


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


def test_off_level_is_nullop_and_fenceless(monkeypatch):
    """`--telemetry off` must insert NO fences and buffer nothing: the
    span is the shared null object and block_until_ready is never
    reached (the engines' async dispatch stays async)."""
    def boom(*_a, **_k):  # any fence attempt explodes
        raise AssertionError("off-level telemetry fenced device work")

    monkeypatch.setattr(trace_mod, "_block", boom)
    tr = Tracer(level="off")
    sp = tr.span("step", step=0)
    assert sp is _NULL_SPAN
    with sp:
        sp.fence(object())
    tr.event("x")
    tr.counter("c", 1)
    assert tr.events == []
    # and at `steps` level fences are still skipped (dispatch preserved)
    tr2 = Tracer(level="steps")
    with tr2.span("step") as s:
        s.fence(object())
    assert len(tr2.events) == 1


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
    monkeypatch.setattr(trace_mod, "_BUF_CAP", 4)
    tr = Tracer(level="steps")
    tr._events = __import__("collections").deque(maxlen=4)
    for i in range(10):
        tr.event("e", i=i)
    assert tr.event_count == 10
    # a window starting inside the buffer returns exactly that suffix
    assert [e["args"]["i"] for e in tr.events_since(8)] == [8, 9]
    # a window starting before the eviction point returns what remains
    assert [e["args"]["i"] for e in tr.events_since(2)] == [6, 7, 8, 9]


def test_chrome_trace_sources_full_stream_from_jsonl(tmp_path,
                                                     monkeypatch):
    """trace.json must carry the COMPLETE stream even when the RAM
    buffer evicted early events (spans.jsonl is the source of truth)."""
    tr = Tracer(trace_dir=tmp_path, level="steps")
    tr._events = __import__("collections").deque(maxlen=2)
    for i in range(6):
        tr.event("e", i=i)
    tr.close()
    chrome = json.loads((tmp_path / "trace.json").read_text())
    assert len(chrome["traceEvents"]) == 6


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
