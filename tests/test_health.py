"""Training-health observability (`telemetry/health.py` + `anomaly.py`).

The acceptance gates, pinned:
- every engine family's compiled step reports finite grad-norm /
  update-ratio / nonfinite fields with EXACTLY one executable per
  entrypoint (the health pack adds outputs, never entrypoints — the
  same counter the analysis retrace rule reads);
- dp / fsdp / pipeline health reductions match the single-device
  oracle to fp tolerance;
- an injected NaN fires the sentinel, and under health="guard" the
  update is skipped BIT-identically (params and optimizer state
  byte-equal to before the step) while the skip counter increments;
- the anomaly detectors (robust-EWMA spikes, divergence, dead layer)
  and the elastic dead-heartbeat restart behave as documented.
"""

import json

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from shallowspeed_tpu.models import transformer as T
from shallowspeed_tpu.models.mlp import MLPStage
from shallowspeed_tpu.optim import SGD, Adam
from shallowspeed_tpu.telemetry import anomaly, health

CFG = T.TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                          max_seq=32)
SIZES = [784, 32, 31, 10]


@pytest.fixture(scope="module", autouse=True)
def _drop_compiled_caches_after_module():
    """This module compiles many short-lived engines (6 LM families x
    modes, 3 MLP engines); their executables' baked-in constants stay
    live in the pjit cache until collected and would otherwise tip
    test_telemetry's live-vs-static HBM cross-check (a 1.05x bound on
    CUMULATIVE process-wide live arrays) later in the same suite run."""
    yield
    import gc

    jax.clear_caches()
    gc.collect()

tree_leaves = jax.tree_util.tree_leaves


def lm_batch(seed=0, b=8, t=32, vocab=64):
    rng = np.random.default_rng(seed)
    tok = rng.integers(0, vocab, (b, t)).astype(np.int32)
    return tok, np.roll(tok, -1, axis=1).astype(np.int32)


def mesh2(dp, other, name):
    devs = np.array(jax.devices()[: dp * other]).reshape(dp, other)
    return Mesh(devs, ("dp", name))


def oracle_engine(opt=None, health_mode="monitor"):
    from shallowspeed_tpu.parallel.context import ContextParallelEngine

    return ContextParallelEngine(
        CFG, opt or Adam(1e-3), mesh2(1, 1, "sp"), seed=0,
        health=health_mode)


class _DS:
    """Minimal per-rank Dataset stand-in for the MLP engines."""

    def __init__(self, seed=0, rows=16, n_mu=4, poison=False):
        rng = np.random.default_rng(seed)
        self.rows, self.n_mu = rows, n_mu
        self.x = rng.standard_normal((rows, 784)).astype(np.float32)
        self.y = np.eye(10, dtype=np.float32)[
            rng.integers(0, 10, rows)]
        if poison:  # one nonfinite in ONE microbatch's input
            self.x[self.rows // self.n_mu + 1, 3] = np.nan

    def load_mubatch_stack(self, b):
        m = self.rows // self.n_mu
        return (self.x.reshape(self.n_mu, m, 784),
                self.y.reshape(self.n_mu, m, 10))

    def load_micro_batch_input(self, b, m):
        mb = self.rows // self.n_mu
        return self.x[m * mb:(m + 1) * mb]

    def load_micro_batch_target(self, b, m):
        mb = self.rows // self.n_mu
        return self.y[m * mb:(m + 1) * mb]

    def get_num_batches(self):
        return 1


def state_bytes(engine):
    return ([np.asarray(l).tobytes() for l in tree_leaves(engine.params)],
            [np.asarray(l).tobytes()
             for l in tree_leaves(engine.opt_state)])


def poison_params(engine):
    """Inject one NaN into the params (token engines' batches are int,
    so the gradient poison goes in through a weight). Note the skipped
    step's update_ratio then reads NaN, not 0 — ||old - old|| over a
    NaN-bearing tree NaN-propagates; the bit-identity assertion is the
    skip contract, and the float-input engines (test_fused_guard...)
    pin the clean ratio-0 behavior with finite params."""
    host = jax.device_get(engine.get_canonical_params())
    host = jax.tree_util.tree_map(lambda a: np.array(a), host)
    tree_leaves(host)[0].ravel()[0] = np.nan
    engine.set_canonical_params(host)


# ------------------------------------------------- pack correctness


def test_dp_sp_health_matches_single_device_oracle():
    from shallowspeed_tpu.parallel.context import ContextParallelEngine

    tok, tgt = lm_batch(0)
    o = oracle_engine()
    o.train_batch(tok, tgt)
    ref = o.health_snapshot()
    eng = ContextParallelEngine(CFG, Adam(1e-3), mesh2(2, 2, "sp"),
                                seed=0, health="monitor")
    eng.train_batch(tok, tgt)
    got = eng.health_snapshot()
    assert got["nonfinite"] == 0
    for k in ("grad_norm", "param_norm", "update_ratio"):
        assert got[k] == pytest.approx(ref[k], rel=1e-4), k
    for g, r in zip(got["groups"].values(), ref["groups"].values()):
        assert g == pytest.approx(r, rel=1e-4)


def test_fsdp_health_matches_single_device_oracle():
    from shallowspeed_tpu.parallel.fsdp import FSDPEngine

    tok, tgt = lm_batch(0)
    o = oracle_engine()
    o.train_batch(tok, tgt)
    ref = o.health_snapshot()
    eng = FSDPEngine(CFG, Adam(1e-3),
                     Mesh(np.array(jax.devices()[:4]), ("dp",)),
                     seed=0, health="monitor")
    eng.train_batch(tok, tgt)
    got = eng.health_snapshot()
    for k in ("grad_norm", "param_norm", "update_ratio"):
        assert got[k] == pytest.approx(ref[k], rel=1e-4), k


def test_pipeline_tp_health_matches_oracle():
    """pp x tp: block stats psum over BOTH sharded axes in-program
    (keep this parity tight: it is the check that catches a wrongly
    transposed tp reduction)."""
    from shallowspeed_tpu.parallel.pipeline_lm import PipelineLMEngine

    tok, tgt = lm_batch(0)
    o = oracle_engine()
    o.train_batch(tok, tgt)
    ref = o.health_snapshot()
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(1, 2, 2),
                ("dp", "pp", "tp"))
    eng = PipelineLMEngine(CFG, Adam(1e-3), mesh, n_mubatches=2,
                           seed=0, health="monitor")
    eng.train_batch(tok, tgt)
    got = eng.health_snapshot()
    for k in ("grad_norm", "param_norm", "update_ratio"):
        assert got[k] == pytest.approx(ref[k], rel=1e-4), k


# ------------------- every family: finite fields, one executable each


def _exercised_cache_sizes(engine, fns):
    out = {}
    for name, fn in fns:
        size = getattr(fn, "_cache_size", None)
        if size is not None:
            out[name] = int(size())
    return out


def _lm_engines():
    from shallowspeed_tpu.parallel.context import ContextParallelEngine
    from shallowspeed_tpu.parallel.fsdp import FSDPEngine
    from shallowspeed_tpu.parallel.pipeline_lm import PipelineLMEngine
    from shallowspeed_tpu.parallel.tensor import TensorParallelEngine

    def ctx(h):
        return ContextParallelEngine(CFG, Adam(1e-3), mesh2(2, 2, "sp"),
                                     seed=0, health=h)

    def ctx_z1(h):
        return ContextParallelEngine(CFG, Adam(1e-3), mesh2(2, 1, "sp"),
                                     seed=0, zero1=True, health=h)

    def pp(h):
        return PipelineLMEngine(CFG, Adam(1e-3), mesh2(1, 2, "pp"),
                                n_mubatches=2, seed=0, health=h)

    def zb(h):
        return PipelineLMEngine(CFG, SGD(0.05), mesh2(1, 2, "pp"),
                                n_mubatches=2, seed=0, schedule="zb",
                                health=h)

    def fsdp(h):
        return FSDPEngine(CFG, Adam(1e-3),
                          Mesh(np.array(jax.devices()[:2]), ("dp",)),
                          seed=0, health=h)

    def tp(h):
        return TensorParallelEngine(CFG, Adam(1e-3), mesh2(1, 2, "tp"),
                                    seed=0, health=h)

    return {"context": ctx, "context-zero1": ctx_z1, "pipeline": pp,
            "pipeline-zb": zb, "fsdp": fsdp, "tensor": tp}


@pytest.mark.parametrize("family", ["context", "context-zero1",
                                    "pipeline", "pipeline-zb", "fsdp",
                                    "tensor"])
def test_lm_family_health_finite_and_one_executable(family):
    eng = _lm_engines()[family]("monitor")
    for step in range(3):
        eng.train_batch(*lm_batch(step))
    snap = eng.health_snapshot()
    assert snap["nonfinite"] == 0
    for k in ("grad_norm", "param_norm", "update_ratio"):
        assert np.isfinite(snap[k]) and snap[k] > 0, (family, k, snap)
    # exactly one executable per compiled entrypoint after 3 steps —
    # the pack added outputs, not entrypoints, and caused no retraces
    fns = [("step", getattr(eng, "_step_fn", None)),
           ("grads", getattr(eng, "_loss_grads_fn", None)
            or getattr(eng, "_grads_fn", None)),
           ("update", getattr(eng, "_update_fn", None))]
    counts = _exercised_cache_sizes(eng, [(n, f) for n, f in fns
                                          if f is not None])
    exercised = {k: v for k, v in counts.items() if v > 0}
    assert exercised, family
    assert all(v == 1 for v in exercised.values()), (family, counts)


def test_mlp_families_health_finite_and_one_executable():
    from shallowspeed_tpu.engine import FusedDPEngine
    from shallowspeed_tpu.parallel.mesh import make_mesh
    from shallowspeed_tpu.parallel.schedules import GPipeSchedule
    from shallowspeed_tpu.parallel.spmd_pipeline import SPMDPipelineEngine
    from shallowspeed_tpu.parallel.worker import PipelineExecutor
    from shallowspeed_tpu.telemetry.report import compile_counts

    fused = FusedDPEngine(MLPStage(SIZES, 0, 1, batch_size=32),
                          SGD(0.1), make_mesh(2, 1), health="monitor")
    for b in range(3):
        fused.train_batch(0, [_DS(0), _DS(1)])
    snap = fused.health_snapshot()
    assert snap["nonfinite"] == 0 and np.isfinite(snap["grad_norm"])
    assert set(snap["groups"]) == {"layer0", "layer1", "layer2"}
    assert int(fused._step._cache_size()) == 1

    spmd = SPMDPipelineEngine(
        SIZES, SGD(0.1),
        Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "pp")),
        4, 4, 32, health="monitor")
    for b in range(3):
        spmd.train_batch(0, [_DS(0), _DS(1)])
    ssnap = spmd.health_snapshot()
    assert ssnap["nonfinite"] == 0
    # same data, same semantics: fused and the compiled pipeline agree
    assert ssnap["grad_norm"] == pytest.approx(snap["grad_norm"],
                                               rel=1e-4)
    assert int(spmd._step_fn._cache_size()) == 1

    vm = PipelineExecutor(
        Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("dp", "pp")),
        [MLPStage(SIZES, s, 2, batch_size=32) for s in range(2)],
        SGD(0.1), health="monitor")
    for b in range(3):
        vm.train_batch(GPipeSchedule, 4, b, [_DS(0, rows=32)])
    vsnap = vm.health_snapshot()
    assert vsnap["nonfinite"] == 0
    assert np.isfinite(vsnap["grad_norm"]) and vsnap["grad_norm"] > 0
    assert np.isfinite(vsnap["update_ratio"])
    # per-stage packs merged over pp with stage-prefixed groups
    assert any(k.startswith("s0.") for k in vsnap["groups"])
    counts = compile_counts(vm.telemetry_entrypoints())
    exercised = {k: v for k, v in counts.items() if v > 0}
    assert exercised and all(v == 1 for v in exercised.values()), counts


# --------------------------------------- NaN injection + guarded skip


@pytest.mark.parametrize("family", ["context", "context-zero1",
                                    "pipeline", "fsdp"])
def test_lm_guard_skips_bit_identically(family):
    eng = _lm_engines()[family]("guard")
    tok, tgt = lm_batch(0)
    eng.train_batch(tok, tgt)         # healthy step updates
    poison_params(eng)
    p0, s0 = state_bytes(eng)
    eng.train_batch(*lm_batch(1))     # poisoned grads -> skip
    snap = eng.health_snapshot()
    assert snap["nonfinite"] > 0, family
    assert snap["skipped"] == 1, family
    assert not snap["update_ratio"] > 0, family  # 0, or NaN-poisoned
    p1, s1 = state_bytes(eng)
    assert p0 == p1 and s0 == s1, (
        f"{family}: a guarded skip must leave params AND optimizer "
        f"state bit-identical")


def test_lm_monitor_reports_but_does_not_skip():
    eng = _lm_engines()["context"]("monitor")
    eng.train_batch(*lm_batch(0))
    poison_params(eng)
    p0, _ = state_bytes(eng)
    eng.train_batch(*lm_batch(1))
    snap = eng.health_snapshot()
    assert snap["nonfinite"] > 0 and snap.get("skipped", 0) == 0
    p1, _ = state_bytes(eng)
    assert p0 != p1  # monitor observes; it does not guard


def test_fused_guard_skips_bit_identically_on_input_nan():
    from shallowspeed_tpu.engine import FusedDPEngine
    from shallowspeed_tpu.parallel.mesh import make_mesh

    eng = FusedDPEngine(MLPStage(SIZES, 0, 1, batch_size=16),
                        SGD(0.1), make_mesh(1, 1), health="guard")
    eng.train_batch(0, [_DS(0)])
    assert eng.health_snapshot()["skipped"] == 0
    p0, s0 = state_bytes(eng)
    eng.train_batch(0, [_DS(1, poison=True)])
    snap = eng.health_snapshot()
    assert snap["nonfinite"] > 0 and snap["skipped"] == 1
    p1, s1 = state_bytes(eng)
    assert p0 == p1 and s0 == s1
    # recovery: the next healthy batch trains again
    eng.train_batch(0, [_DS(2)])
    assert eng.health_snapshot()["skipped"] == 0
    assert state_bytes(eng)[0] != p0


def test_vm_guard_skips_all_stages_in_lockstep():
    from shallowspeed_tpu.parallel.schedules import GPipeSchedule
    from shallowspeed_tpu.parallel.worker import PipelineExecutor

    vm = PipelineExecutor(
        Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("dp", "pp")),
        [MLPStage(SIZES, s, 2, batch_size=32) for s in range(2)],
        SGD(0.1), health="guard")
    p0, s0 = state_bytes(vm)
    vm.train_batch(GPipeSchedule, 4, 0, [_DS(0, rows=32, poison=True)])
    snap = vm.health_snapshot()
    assert snap["nonfinite"] > 0 and snap["skipped"] == 1
    assert vm.health_skipped == 1
    p1, s1 = state_bytes(vm)
    assert p0 == p1 and s0 == s1
    vm.train_batch(GPipeSchedule, 4, 1, [_DS(1, rows=32)])
    assert vm.health_skipped == 1  # healthy batch trained
    assert state_bytes(vm)[0] != p0


def test_skip_counter_rides_step_fields():
    """The step-line counter increments across guarded skips
    (HealthMonitor -> StepRates merge)."""
    eng = _lm_engines()["context"]("guard")
    mon = health.HealthMonitor()
    eng.train_batch(*lm_batch(0))
    mon.observe(0, 2.0, eng.health_snapshot())
    poison_params(eng)
    eng.train_batch(*lm_batch(1))
    mon.observe(1, 2.0, eng.health_snapshot())
    eng.train_batch(*lm_batch(2))
    mon.observe(2, 2.0, eng.health_snapshot())
    fields = mon.step_fields()
    assert fields["health_skipped_total"] == 2
    assert fields["health_nonfinite"] > 0
    assert "nonfinite" in fields["health_verdicts"]


def test_transient_skip_between_log_points_is_counted():
    """A skip mid-window must reach the next snapshot even though
    last_health is overwritten every step (the device-side CUMULATIVE
    counters, health.note_step): poison exactly one step, recover, and
    only THEN observe."""
    from shallowspeed_tpu.engine import FusedDPEngine
    from shallowspeed_tpu.parallel.mesh import make_mesh

    eng = FusedDPEngine(MLPStage(SIZES, 0, 1, batch_size=16),
                        SGD(0.1), make_mesh(1, 1), health="guard")
    eng.train_batch(0, [_DS(0)])
    eng.train_batch(0, [_DS(1, poison=True)])   # skipped, not observed
    eng.train_batch(0, [_DS(2)])                # clean again
    snap = eng.health_snapshot()
    assert snap["nonfinite"] == 0               # the LAST step is clean
    assert snap["skipped_total"] == 1           # ...the skip still counted
    assert snap["nonfinite_steps_total"] == 1
    # and the monitor surfaces it on the next log point
    mon = health.HealthMonitor()
    verdicts = mon.observe(2, 2.0, snap)
    assert any(v.kind == "nonfinite" for v in verdicts)
    assert mon.step_fields()["health_skipped_total"] == 1


# ---------------------------------------------------- host-side units


def test_merge_packs_recovers_global_norms():
    import math

    a = {"grad_norm": 3.0, "param_norm": 4.0, "nonfinite": 1,
         "groups": {"layer0": 3.0}, "update_ratio": 0.5}
    b = {"grad_norm": 4.0, "param_norm": 3.0, "nonfinite": 2,
         "groups": {"layer0": 4.0}, "update_ratio": 1.0}
    m = health.merge_packs([a, b])
    assert m["grad_norm"] == pytest.approx(5.0)
    assert m["param_norm"] == pytest.approx(5.0)
    assert m["nonfinite"] == 3
    assert set(m["groups"]) == {"s0.layer0", "s1.layer0"}
    # sqrt((0.5*4)^2 + (1.0*3)^2) / 5
    assert m["update_ratio"] == pytest.approx(
        math.sqrt(2.0 ** 2 + 3.0 ** 2) / 5.0, rel=1e-6)
    assert health.merge_packs([]) is None


def test_robust_ewma_flags_outlier_not_baseline():
    ew = anomaly.RobustEWMA(alpha=0.1, warmup=5)
    rng = np.random.default_rng(0)
    zs = [ew.update(5.0 + 0.1 * rng.standard_normal())
          for _ in range(30)]
    assert all(abs(z) < 6 for z in zs if z is not None)
    z = ew.update(50.0)
    assert z is not None and z > 6


def test_detector_loss_spike_and_divergence():
    det = anomaly.AnomalyDetector(spike_z=6.0, div_factor=0.2,
                                  patience=3, warmup=4)
    for i in range(10):
        assert det.observe(i, loss=4.0 - 0.01 * i) == []
    v = det.observe(10, loss=40.0)
    assert [x.kind for x in v] == ["loss_spike"]
    kinds = []
    for i in range(11, 30):
        kinds += [x.kind for x in det.observe(i, loss=40.0)]
    assert "divergence" in kinds
    # a nonfinite loss is divergence immediately
    det2 = anomaly.AnomalyDetector()
    v = det2.observe(0, loss=float("nan"))
    assert [x.kind for x in v] == ["divergence"]


def test_detector_dead_layer_needs_patience_and_live_global():
    det = anomaly.AnomalyDetector(patience=3)
    pack = {"grad_norm": 1.0, "nonfinite": 0,
            "groups": {"head": 0.0, "blocks": 1.0}}
    assert det.observe(0, pack=pack) == []
    assert det.observe(1, pack=pack) == []
    v = det.observe(2, pack=pack)
    assert [x.kind for x in v] == ["dead_layer"]
    assert "head" in v[0].detail
    # reported once, not every observation after
    assert det.observe(3, pack=pack) == []


def test_guard_policy_modes_and_verdict_actions():
    p = anomaly.GuardPolicy.for_mode("guard")
    assert p.action("nonfinite") == "skip_step"
    assert p.action("divergence") == "warn"
    mon = health.HealthMonitor(policy=p)
    v = mon.observe(0, 2.0, {"grad_norm": 1.0, "param_norm": 1.0,
                             "nonfinite": 3, "groups": {}})
    assert v[0].kind == "nonfinite" and v[0].action == "skip_step"


def test_monitor_declares_dead_after_sustained_nonfinite():
    mon = health.HealthMonitor(dead_after=3)
    bad = {"grad_norm": float("nan"), "param_norm": 1.0,
           "nonfinite": 5, "groups": {}}
    assert mon.heartbeat_status() == "ok"
    for step in range(3):
        mon.observe(step, 2.0, bad)
    assert mon.heartbeat_status().startswith("dead")
    # recovery clears nothing retroactively but new healthy steps
    # keep the run counted; the status is sticky by design (the
    # supervisor restart is the way back)
    assert mon.nonfinite_steps == 3


# ----------------------------------------------- schema + elastic


def test_schema_accepts_v1_and_v2_lines():
    from shallowspeed_tpu.telemetry import schema

    # PR-2 dialect: no schema_version, no health fields
    assert schema.validate_line(
        {"event": "run_start", "dp": 2}) == []
    assert schema.validate_line(
        {"event": "step", "step": 1, "loss": 2.0,
         "tokens_per_sec": 10.0}) == []
    # health-extended dialect
    assert schema.validate_line(
        {"event": "run_start", "schema_version": schema.SCHEMA_VERSION
         }) == []
    assert schema.validate_line(
        {"event": "step", "step": 1, "loss": 2.0,
         "tokens_per_sec": 10.0, "health_grad_norm": 1.5,
         "health_nonfinite": 0, "health_skipped_total": 2,
         "health_verdicts": ["loss_spike"]}) == []
    assert schema.validate_line(
        {"event": "health", "step": 3, "health_grad_norm": 1.0}) == []
    # and still rejects malformed lines
    assert schema.validate_line(
        {"event": "step", "step": 1, "loss": 2.0,
         "tokens_per_sec": 10.0, "health_nonfinite": "three"})
    assert schema.validate_line(
        {"event": "run_start", "schema_version": "two"})


def test_metrics_logger_stamps_schema_version(tmp_path):
    from shallowspeed_tpu.metrics import MetricsLogger
    from shallowspeed_tpu.telemetry import schema

    path = tmp_path / "m.jsonl"
    MetricsLogger(path, dp=1)
    rec = json.loads(path.read_text().splitlines()[0])
    assert rec["schema_version"] == schema.SCHEMA_VERSION
    assert schema.validate_file(path) == []


def test_elastic_kills_numerically_dead_child(tmp_path):
    """A child that beats its heartbeat but reports 'dead ...' is
    killed for a checkpoint restart — the hang timeout alone would
    never fire on a beating loop."""
    import sys

    from shallowspeed_tpu.elastic import Supervisor

    hb = tmp_path / "hb"
    hb.write_text("ok")
    child = (
        "import time, sys\n"
        f"open({str(hb)!r}, 'w').write('dead nonfinite gradients')\n"
        "time.sleep(60)\n")
    sup = Supervisor([sys.executable, "-c", child],
                     hang_timeout=30.0, heartbeat_file=str(hb),
                     poll_interval=0.1)
    code, secs, fail_class = sup._run_once()
    assert code == -9
    assert secs < 20  # killed on the verdict, not the hang timeout
    assert fail_class == "numeric"  # round 10: classed for MTTR


def test_elastic_dead_kill_works_without_hang_timeout(tmp_path):
    """The health-verdict kill needs only a heartbeat file — a
    supervisor built without a hang timeout must still escalate."""
    import sys

    from shallowspeed_tpu.elastic import Supervisor

    hb = tmp_path / "hb"
    hb.write_text("ok")
    child = (
        "import time\n"
        f"open({str(hb)!r}, 'w').write('dead divergence')\n"
        "time.sleep(60)\n")
    sup = Supervisor([sys.executable, "-c", child],
                     hang_timeout=None, heartbeat_file=str(hb),
                     poll_interval=0.1)
    code, secs, fail_class = sup._run_once()
    assert code == -9 and secs < 20
    assert fail_class == "numeric"


def test_elastic_restart_clears_stale_dead_status(tmp_path):
    """A leftover 'dead ...' from the previous child must NOT kill the
    restarted child: _run_once resets the status to 'ok' at spawn."""
    import sys

    from shallowspeed_tpu.elastic import Supervisor

    hb = tmp_path / "hb"
    hb.write_text("dead nonfinite gradients")  # previous child's verdict
    sup = Supervisor([sys.executable, "-c", "import time; time.sleep(2)"],
                     hang_timeout=30.0, heartbeat_file=str(hb),
                     poll_interval=0.1)
    code, secs, fail_class = sup._run_once()
    assert code == 0, "fresh child was killed on the STALE dead status"
    assert fail_class is None


def test_heartbeat_status_roundtrip(tmp_path):
    from shallowspeed_tpu import elastic

    hb = tmp_path / "hb"
    elastic.write_heartbeat(hb, "ok")
    assert elastic.read_heartbeat_status(hb) == "ok"
    elastic.write_heartbeat(hb, "dead loss divergence")
    assert elastic.read_heartbeat_status(hb).startswith("dead")
    hb.write_text("")  # a plain touch stays a valid beat
    assert elastic.read_heartbeat_status(hb) == "ok"
    assert elastic.read_heartbeat_status(tmp_path / "absent") == "ok"
