"""Numerics observatory (round 18): runtime precision telemetry over
the fp8-e4m3 trainer and shadow-parity gating.

Coverage map:
- the numerics pack rides the ONE compiled fp8 step (zero new
  executables, zero recompiles across steps / shadow sampling /
  fallback — the health-pack contract, `_cache_size() == 1` pins);
- fp8.py typed errors, the `_scales` 1e-12 divide floor, amax-history
  roll + buffer donation;
- `NumericsMonitor`: scale-collapse at the floor, parity-envelope
  verdicts, the warn -> fallback_bf16 -> abort escalation;
- chaos `scale_poison@N`: seeded layer choice, typed error on engines
  without an amax history;
- schema v13: num_* step lines validate (good AND bad), pre-v13 lines
  keep validating;
- `flops.device_peak_flops` doubles the fp8 peak;
- the --goodput numerics block + `shadow_parity` ledger exclusion;
- the static prover's calibration ranges contain measured RUNTIME
  amax telemetry (the certificate's conditioning holds live);
- the end-to-end drill (tier-1): a seeded scale_poison run under
  --health guard detects the collapse at the poisoned step, dumps a
  flight record + profiler capture, falls back to bf16, and finishes
  within the fault-free oracle's loss envelope.
"""

import json
import math
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from shallowspeed_tpu import chaos  # noqa: E402
from shallowspeed_tpu.fp8 import (AMAX_HISTORY, Fp8TrainEngine,  # noqa: E402
                                  init_fp8_mlp)
from shallowspeed_tpu.ops.matmul import E4M3_MAX  # noqa: E402
from shallowspeed_tpu.optim import SGD, MomentumSGD  # noqa: E402
from shallowspeed_tpu.telemetry.anomaly import GuardPolicy  # noqa: E402
from shallowspeed_tpu.telemetry.numerics import (COLLAPSE_FLOOR,  # noqa: E402
                                                 PARITY_LOSS_BUDGET,
                                                 NumericsMonitor)
from shallowspeed_tpu.telemetry.schema import (SCHEMA_VERSION,  # noqa: E402
                                               validate_line)

ROOT = Path(__file__).resolve().parents[1]
SIZES = [12, 16, 10]


@pytest.fixture(autouse=True)
def _no_ambient_plan(monkeypatch):
    for var in (chaos.ENV_SPEC, chaos.ENV_STATE, chaos.ENV_SEED):
        monkeypatch.delenv(var, raising=False)
    chaos.configure(None)
    yield
    chaos.configure(None)


def _batch(seed=0, bs=8):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((bs, SIZES[0])).astype(np.float32)
    y = np.eye(SIZES[-1], dtype=np.float32)[rng.integers(0, SIZES[-1], bs)]
    return x, y


def _engine(**kw):
    return Fp8TrainEngine(SIZES, MomentumSGD(0.05, momentum=0.9), **kw)


# ------------------------------------------------- the compiled step


def test_numerics_pack_rides_one_executable():
    """The zero-new-executables contract: the clamp stats and
    amax/scale telemetry are computed INSIDE the one jitted step —
    N steps, shadow sampling, and the bf16 fallback never grow any
    executable cache past one entry each."""
    eng = _engine()
    for i in range(6):
        eng.train_batch(*_batch(i))
    assert int(eng._step_fn._cache_size()) == 1
    # the oracle and fallback are LAZY: a run that never needs them
    # compiles nothing extra
    assert eng._parity_fn is None and eng._fallback_fn is None
    pack = eng.health_snapshot()
    for key in ("fp8_amax", "fp8_scale", "fp8_overflow", "fp8_underflow"):
        assert key in pack, key
        assert len(pack[key]) == len(SIZES) - 1
        assert all(math.isfinite(v) for v in pack[key])
    # clamp fractions are fractions
    assert all(0.0 <= v <= 1.0 for v in pack["fp8_overflow"])
    assert all(0.0 <= v <= 1.0 for v in pack["fp8_underflow"])

    parity = eng.shadow_parity(*_batch(7))
    assert set(parity) == {"parity_loss_rel", "parity_grad_relmax"}
    eng.fallback_bf16()
    for i in range(3):
        eng.train_batch(*_batch(10 + i))
    assert int(eng._step_fn._cache_size()) == 1
    assert int(eng._parity_fn._cache_size()) == 1
    assert int(eng._fallback_fn._cache_size()) == 1


def test_fallback_keeps_pack_and_state_shape():
    """The bf16 fallback's pack is structurally identical (scales keep
    rolling, clamp fractions are exact zeros — nothing is quantized)."""
    eng = _engine()
    eng.train_batch(*_batch(0))
    before = eng.health_snapshot()
    eng.fallback_bf16()
    eng.train_batch(*_batch(1))
    after = eng.health_snapshot()
    assert set(before) == set(after)
    assert after["fp8_overflow"] == [0.0] * (len(SIZES) - 1)
    assert after["fp8_underflow"] == [0.0] * (len(SIZES) - 1)
    # the history kept rolling under fallback: fresh finite scales
    assert all(s > 0 for s in after["fp8_scale"])


def test_scales_divide_floor_pin():
    """A zeroed amax history must floor the delayed scale at exactly
    1e-12 — never zero (the divide the prover certifies nonzero)."""
    hist = jnp.zeros((2, AMAX_HISTORY), jnp.float32)
    scales = np.asarray(Fp8TrainEngine._scales(hist))
    assert scales.tolist() == pytest.approx([1e-12, 1e-12])
    assert (scales > 0).all()
    hist = hist.at[1, 3].set(448.0)
    scales = np.asarray(Fp8TrainEngine._scales(hist))
    assert scales[0] == pytest.approx(1e-12)
    assert scales[1] == pytest.approx(448.0 / E4M3_MAX)


def test_amax_history_rolls_and_donates():
    """Slot 0 after a step is THIS step's measured absmax (layer 0's is
    the input absmax, exactly computable); older slots shift right; and
    the donated input buffers are actually consumed."""
    eng = _engine()
    x, y = _batch(0)
    old_hist = eng.amax_hist
    marker = eng.amax_hist[0, 0]
    eng.train_batch(x, y)
    hist = np.asarray(eng.amax_hist)
    assert hist[0, 0] == pytest.approx(float(np.max(np.abs(x))), rel=1e-6)
    assert hist[0, 1] == pytest.approx(float(marker))
    # donate_argnums=(0,1,2): the old history buffer was consumed
    assert old_hist.is_deleted()


def test_fp8_typed_errors():
    with pytest.raises(ValueError, match="unsupported precision"):
        Fp8TrainEngine(SIZES, SGD(0.01), precision="int4")
    with pytest.raises(ValueError, match="positive dims"):
        Fp8TrainEngine([12], SGD(0.01))
    with pytest.raises(ValueError, match="positive dims"):
        Fp8TrainEngine([12, 0, 10], SGD(0.01))


# ------------------------------------------------- host-side monitor


def _pack(scales, over=None, amax=None):
    n = len(scales)
    return {"fp8_scale": list(scales),
            "fp8_amax": list(amax or [1.0] * n),
            "fp8_overflow": list(over or [0.0] * n),
            "fp8_underflow": [0.0] * n}


def test_monitor_scale_collapse_and_escalation():
    """Collapse at the floor fires ON ARRIVAL with the guard's
    fallback action; after the fallback is taken the same kind comes
    back as abort (warn -> fall back -> abort)."""
    mon = NumericsMonitor(policy=GuardPolicy.for_mode("guard"))
    out = mon.observe(0, _pack([0.5, 0.5]))
    assert out == []
    out = mon.observe(1, _pack([1e-12, 0.5], over=[0.9, 0.0]))
    assert [v.kind for v in out] == ["scale_collapse"]
    assert out[0].action == "fallback_bf16"
    assert "layer 0" in out[0].detail
    # still collapsed: reported once, not every step
    assert mon.observe(2, _pack([1e-12, 0.5])) == []
    mon.note_fallback()
    # recovers, then collapses AGAIN -> the middle rung is spent
    mon.observe(3, _pack([0.5, 0.5]))
    out = mon.observe(4, _pack([1e-12, 0.5]))
    assert [v.action for v in out] == ["abort"]
    assert mon.step_fields()["num_precision"] == "bf16"


def test_monitor_parity_envelope():
    mon = NumericsMonitor(policy=GuardPolicy.for_mode("guard"))
    ok = mon.note_parity(8, {"parity_loss_rel": 0.01,
                             "parity_grad_relmax": 0.9})
    assert ok == []
    bad = mon.note_parity(16, {"parity_loss_rel": 0.16,
                               "parity_grad_relmax": 1.0})
    assert [v.kind for v in bad] == ["parity_drift"]
    assert bad[0].action == "fallback_bf16"
    fields = mon.step_fields()
    assert fields["num_parity_loss_rel"] == pytest.approx(0.16)
    assert fields["num_shadow_total"] == 2
    assert fields["num_verdicts"] == ["parity_drift"]
    # the verdict window drains
    assert "num_verdicts" not in mon.step_fields()


def test_monitor_oscillation_score():
    """A scale ping-ponging between two values every observation
    scores ~1.0; a constant scale scores 0."""
    mon = NumericsMonitor()
    for i in range(12):
        mon.observe(i, _pack([0.25 if i % 2 else 0.5, 0.5]))
    fields = mon.step_fields()
    assert fields["num_osc"] == pytest.approx(1.0)
    assert fields["num_scale_min"] == pytest.approx(0.25)


# ----------------------------------------------------- chaos fault


def test_chaos_scale_poison_is_seeded_and_once_only(tmp_path):
    plan = chaos.FaultPlan.parse("scale_poison@3", seed=5,
                                 state_dir=tmp_path / "cs")
    chaos.configure(plan)
    eng = _engine()
    eng.train_batch(*_batch(0))
    hist0 = np.asarray(eng.amax_hist).copy()
    chaos.on_step(2, eng)  # not due yet
    assert np.array_equal(np.asarray(eng.amax_hist), hist0)
    chaos.on_step(3, eng)
    hist = np.asarray(eng.amax_hist)
    zeroed = [i for i in range(hist.shape[0]) if (hist[i] == 0.0).all()]
    assert len(zeroed) == 1
    # seeded: the same plan on a fresh engine picks the same layer
    plan2 = chaos.FaultPlan.parse("scale_poison@3", seed=5,
                                  state_dir=tmp_path / "cs2")
    chaos.configure(plan2)
    eng2 = _engine()
    eng2.train_batch(*_batch(0))
    chaos.on_step(3, eng2)
    hist2 = np.asarray(eng2.amax_hist)
    assert [i for i in range(hist2.shape[0])
            if (hist2[i] == 0.0).all()] == zeroed
    # once-only: markers survive, a second pass does not re-fire
    eng3 = _engine()
    chaos.configure(chaos.FaultPlan.parse("scale_poison@3", seed=5,
                                          state_dir=tmp_path / "cs"))
    chaos.on_step(3, eng3)
    assert not np.asarray(eng3.amax_hist == 0.0).all(axis=1).any()


def test_chaos_scale_poison_typed_error_without_history():
    chaos.configure(chaos.FaultPlan.parse("scale_poison@0", seed=1))
    with pytest.raises(RuntimeError, match="amax_hist"):
        chaos.on_step(0, object())


# ------------------------------------------------------- schema v13


def test_schema_v13_step_lines():
    assert SCHEMA_VERSION >= 13
    base = {"event": "step", "step": 4, "loss": 0.5,
            "tokens_per_sec": 100.0, "t": 1.0, "wall": 1.0}
    good = dict(base, num_overflow_max=0.5, num_underflow_max=0.0,
                num_scale_min=1e-12, num_amax_max=3.2, num_drift_z=1.5,
                num_osc=0.0, num_parity_loss_rel=0.01,
                num_parity_grad_relmax=0.9, num_shadow_total=3,
                num_precision="fp8", num_verdicts=["scale_collapse"])
    assert validate_line(good) == []
    # pre-v13 lines (no num_* fields) keep validating
    assert validate_line(base) == []
    bad = dict(base, num_overflow_max="lots")
    assert any("num_overflow_max" in p for p in validate_line(bad))
    bad = dict(base, num_verdicts="scale_collapse")
    assert any("num_verdicts" in p for p in validate_line(bad))


def test_step_fields_from_live_run_validate():
    """The exact dict the driver logs (StepRates merge) passes the
    schema — the contract the committed r18 artifact is gated on."""
    from shallowspeed_tpu.metrics import StepRates

    eng = _engine()
    mon = NumericsMonitor(policy=GuardPolicy.for_mode("guard"))
    rates = StepRates(8, numerics=mon)
    for i in range(3):
        eng.train_batch(*_batch(i))
        mon.observe(i, eng.health_snapshot())
    mon.note_parity(2, eng.shadow_parity(*_batch(2)))
    fields = rates.log_point(3)
    line = {"event": "step", "step": 2, "loss": 0.1,
            **{k: (round(v, 6) if isinstance(v, float) else v)
               for k, v in fields.items()}}
    assert validate_line(line) == [], line
    assert line["num_precision"] == "fp8"
    assert "num_scale_min" in line and "num_parity_loss_rel" in line


# ------------------------------------------------------ the fp8 peak


def test_device_peak_flops_fp8_doubles_bf16():
    from shallowspeed_tpu.flops import device_peak_flops

    class _Dev:
        device_kind = "TPU v7"

    bf16 = device_peak_flops(_Dev())
    assert device_peak_flops(_Dev(), dtype="fp8") == bf16 * 2.0
    assert device_peak_flops(_Dev(), dtype="float8_e4m3fn") == bf16 * 2.0


# ------------------------------------------------- goodput reduction


def test_goodput_numerics_block(tmp_path):
    from shallowspeed_tpu.telemetry.goodput import (EXCLUDED_KINDS,
                                                    format_report,
                                                    run_goodput)

    assert "shadow_parity" in EXCLUDED_KINDS
    log = tmp_path / "m.jsonl"
    lines = [
        {"event": "run_start", "schema_version": 13, "t": 0.0,
         "wall": 100.0},
        {"event": "step", "step": 0, "loss": 0.5, "tokens_per_sec": 10.0,
         "num_overflow_max": 0.0, "num_scale_min": 0.002,
         "num_precision": "fp8", "t": 1.0, "wall": 101.0},
        {"event": "ledger", "kind": "shadow_parity", "seconds": 0.5,
         "t": 1.5, "wall": 101.5},
        {"event": "step", "step": 8, "loss": 0.4, "tokens_per_sec": 10.0,
         "num_overflow_max": 0.55, "num_scale_min": 1e-12,
         "num_parity_loss_rel": 0.16, "num_parity_grad_relmax": 1.0,
         "num_shadow_total": 2, "num_precision": "fp8",
         "num_verdicts": ["scale_collapse", "parity_drift"],
         "t": 2.0, "wall": 102.0},
        {"event": "step", "step": 9, "loss": 0.3, "tokens_per_sec": 10.0,
         "num_overflow_max": 0.0, "num_scale_min": 0.002,
         "num_precision": "bf16", "t": 3.0, "wall": 103.0},
    ]
    log.write_text("".join(json.dumps(r) + "\n" for r in lines))
    rep = run_goodput(log)
    num = rep["numerics"]
    assert num["steps_observed"] == 3 and num["steps_fp8"] == 2
    assert num["overflow_max"] == pytest.approx(0.55)
    assert num["scale_min"] == pytest.approx(1e-12)
    assert num["parity_loss_rel_max"] == pytest.approx(0.16)
    assert num["verdicts"] == {"scale_collapse": 1, "parity_drift": 1}
    assert num["fell_back_bf16"] and num["final_precision"] == "bf16"
    assert num["shadow_samples"] == 2
    # shadow-parity seconds land in the excluded/loss buckets
    assert rep["losses"]["shadow_parity"] == pytest.approx(0.5)
    txt = format_report(rep)
    assert "FELL BACK to bf16" in txt and "scale_collapse" in txt


# ------------------------------------- live plane + fleet surfaces


def test_monitor_status_metrics_and_flight_dump(tmp_path):
    from shallowspeed_tpu.telemetry.monitor import Monitor

    mon = Monitor(flight=16, flight_dir=tmp_path)
    mon.note_line({"event": "step", "step": 4, "loss": 0.5,
                   "tokens_per_sec": 10.0, "num_overflow_max": 0.55,
                   "num_scale_min": 1e-12, "num_precision": "fp8",
                   "num_parity_loss_rel": 0.16,
                   "num_verdicts": ["scale_collapse"]})
    st = mon.status()
    assert st["numerics"]["num_scale_min"] == pytest.approx(1e-12)
    assert st["numerics"]["last_verdicts"] == ["scale_collapse"]
    assert "scale_collapse" in st["health"]
    prom = mon.prometheus()
    assert "num_overflow_max 0.55" in prom
    assert "num_precision_fp8 1" in prom
    dumps = list(tmp_path.glob("flightrec_*.json"))
    assert dumps, "a numerics verdict must dump the flight ring"
    rec = json.loads(dumps[0].read_text())
    assert "scale_collapse" in str(rec.get("reason", rec))


def test_fleet_view_carries_numerics(tmp_path):
    from shallowspeed_tpu.telemetry.fleet import (FleetCollector,
                                                  format_fleet_status)

    paths = []
    for name, prec, parity in (("r0", "fp8", 0.01), ("r1", "bf16", 0.2)):
        p = tmp_path / f"{name}.jsonl"
        p.write_text(json.dumps(
            {"event": "step", "step": 3, "loss": 0.5,
             "tokens_per_sec": 10.0, "num_precision": prec,
             "num_parity_loss_rel": parity, "num_overflow_max": 0.1,
             "t": 1.0, "wall": 1.0}) + "\n")
        paths.append(p)
    coll = FleetCollector(paths=paths)
    st = coll.refresh()
    num = st["numerics"]
    assert num["worst_parity_loss_rel"]["replica"] == "r1"
    assert num["worst_parity_loss_rel"]["value"] == pytest.approx(0.2)
    assert num["fell_back_bf16"] == ["r1"]
    txt = format_fleet_status(st)
    assert "numerics:" in txt and "FELL BACK" in txt


# ------------------------------- static certificate vs live telemetry


def test_static_calibration_ranges_contain_runtime_amax():
    """The prover's certificate is conditioned on the probe's measured
    calibration intervals — live runtime amax telemetry from the SAME
    distribution must stay inside them, or the certificate never
    applied to the run (the cross-check the observatory exists for)."""
    from shallowspeed_tpu.analysis.targets import build_fp8_train

    probe = build_fp8_train()
    ranges = {ep.name: ep.ranges for ep in probe.entrypoints}
    lo, hi = ranges["_step"]["amax_hist"]
    eng = _engine(seed=0)
    seen = []
    for i in range(12):
        eng.train_batch(*_batch(i))
        seen.extend(eng.health_snapshot()["fp8_amax"])
    assert seen
    assert all(lo <= a <= hi for a in seen), (lo, hi, seen)
    # and the measured scales stay off the collapse floor
    assert min(eng.health_snapshot()["fp8_scale"]) > COLLAPSE_FLOOR


# ------------------------------------------ the transformer's fp8 flag


def test_transformer_fp8_dense_config():
    from shallowspeed_tpu.models import transformer as tf

    if tf._FP8_DTYPE is None:
        with pytest.raises(ValueError, match="float8_e4m3fn"):
            tf.TransformerConfig(fp8_dense=True)
        return
    cfg = tf.TransformerConfig(vocab=32, d_model=32, n_heads=2,
                               n_layers=1, max_seq=16)
    cfg8 = tf.TransformerConfig(vocab=32, d_model=32, n_heads=2,
                                n_layers=1, max_seq=16, fp8_dense=True)
    params = tf.init(cfg, seed=0)
    rng = np.random.default_rng(0)
    tok = jnp.asarray(rng.integers(0, 32, (2, 16)), jnp.int32)
    tgt = jnp.asarray(rng.integers(0, 32, (2, 16)), jnp.int32)
    l0 = float(tf.loss(params, tok, tgt, cfg))
    l8 = float(tf.loss(params, tok, tgt, cfg8))
    assert math.isfinite(l8)
    assert abs(l8 - l0) / abs(l0) <= PARITY_LOSS_BUDGET
    g = jax.grad(tf.loss)(params, tok, tgt, cfg8)
    assert all(np.isfinite(np.asarray(x)).all()
               for x in jax.tree_util.tree_leaves(g))
    # monkeypatch-free typed-error check: simulate a build without the
    # dtype by the documented gate
    real = tf._FP8_DTYPE
    tf._FP8_DTYPE = None
    try:
        with pytest.raises(ValueError, match="float8_e4m3fn"):
            tf.TransformerConfig(fp8_dense=True)
    finally:
        tf._FP8_DTYPE = real


# ------------------------------------------------ end-to-end drill


def _run_driver(tmp_path, tag, *extra):
    log = tmp_path / f"{tag}.jsonl"
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    r = subprocess.run(
        [sys.executable, "train.py", "--engine", "fp8", "--epochs", "1",
         "--max-batches", "14", "--shadow-every", "4", "--log-every",
         "4", "--health", "guard", "--flight-recorder", "64",
         "--profile", "host", "--log-file", str(log),
         "--chaos-state", str(tmp_path / f"cs_{tag}"), *extra],
        capture_output=True, text=True, cwd=ROOT, env=env, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    recs = [json.loads(ln) for ln in log.read_text().splitlines()]
    return r, recs, log


def test_fp8_driver_scale_poison_drill(tmp_path):
    """The acceptance drill: poison one layer's amax history mid-run;
    shadow parity + the scale-collapse detector must catch it AT the
    poisoned step, dump a flight record and a profiler capture, fall
    back to bf16, and finish sane (the slow-tier variant below holds
    the run against a live fault-free oracle; here a fixed envelope
    keeps the default tier to ONE subprocess)."""
    r, recs, log = _run_driver(tmp_path, "poison",
                               "--chaos", "scale_poison@6")

    steps = [x for x in recs if x.get("event") == "step"]
    assert steps, recs
    # detection at the poisoned step, on the step line
    hit = [x for x in steps
           if "scale_collapse" in (x.get("num_verdicts") or ())]
    assert [x["step"] for x in hit] == [6], steps
    assert hit[0]["num_scale_min"] == pytest.approx(1e-12)
    assert hit[0]["num_overflow_max"] > 0.1
    # the guard fell back: every later line is bf16 and the fault +
    # fallback are on the ledger
    assert all(x["num_precision"] == "bf16" for x in steps
               if x["step"] >= 6)
    assert any(x.get("event") == "fault"
               and x.get("kind") == "scale_poison" for x in recs)
    assert any(x.get("event") == "ledger"
               and x.get("kind") == "fp8_fallback" for x in recs)
    assert any(x.get("event") == "ledger"
               and x.get("kind") == "shadow_parity" for x in recs)
    # incident artifacts, next to the log file
    assert list(tmp_path.glob("flightrec_*.json"))
    assert list(tmp_path.glob("profcap_*.json"))
    assert "falling back to the bf16" in r.stdout
    # fixed loss envelope: the recovered run keeps LEARNING (measured
    # final val ~0.15 on this config; an un-recovered poisoned run
    # plateaus >1.0 — the live-oracle bound is the slow-tier drill)
    val = [x for x in recs if x.get("event") == "val"][-1]["val_loss"]
    assert val <= 0.5, val
    # schema: the whole artifact validates
    from shallowspeed_tpu.telemetry.schema import validate_file

    assert validate_file(log) == []


@pytest.mark.slow
def test_fp8_drill_within_live_oracle_envelope(tmp_path):
    """Slow-tier completion of the drill above: the guarded poisoned
    run finishes within 1.5x of a LIVE fault-free oracle run's final
    val loss (measured margin ~0.8x — the bf16 master step is simply
    the better trainer on this config)."""
    _, oracle_recs, _ = _run_driver(tmp_path, "oracle")
    _, recs, _ = _run_driver(tmp_path, "poison",
                             "--chaos", "scale_poison@6")
    val = [x for x in recs if x.get("event") == "val"][-1]["val_loss"]
    oval = [x for x in oracle_recs
            if x.get("event") == "val"][-1]["val_loss"]
    assert val <= oval * 1.5, (val, oval)


def test_committed_numerics_artifact_validates():
    """The committed r18 drill artifact stays schema-clean and keeps
    its story: a scale_collapse verdict, the bf16 fallback, shadow
    samples, and the shadow_parity ledger bucket."""
    from shallowspeed_tpu.telemetry.schema import validate_file

    art = ROOT / "docs_runs" / "numerics_r18_metrics.jsonl"
    assert validate_file(art) == []
    recs = [json.loads(ln) for ln in art.read_text().splitlines()]
    steps = [x for x in recs if x.get("event") == "step"]
    assert any("scale_collapse" in (x.get("num_verdicts") or ())
               for x in steps)
    assert steps[-1]["num_precision"] == "bf16"
    assert any(x.get("event") == "ledger"
               and x.get("kind") == "shadow_parity" for x in recs)
