"""Per-step time attribution — the roofline waterfall.

BASELINE.md's "MFU gap accounting" answered *where the other 40%
goes* once, by hand, from an xprof capture. This module makes that
decomposition a live, self-checking metric: every spans-level step
line reconciles the MEASURED (span-fenced) step time against analytic
components computed from machinery that already exists —

- **compute**: per-op roofline time from the step program's jaxpr
  (`analysis/walker.dot_flops` matmuls at the MXU peak,
  `walker.eqn_bytes` for everything else at the HBM roofline — the
  same walk the lint rules and the collective accounting ride), with
  scan-trip multipliers and shard_map-aware device normalization;
- **exposed communication**: the walker's per-axis collective bytes
  split into exposed vs hidden by PR 4's dataflow exposure
  (`parallel/overlap.collective_exposure`) and priced at the ICI wire
  rate — hidden bytes cost nothing (they ride under compute);
- **pipeline bubble**: PR 2's `costed_replay`/`static_bubble`
  fraction, passed through;
- **host/dispatch gap**: the log window's wall time not covered by
  any fenced step span.

What is left is `attrib_unexplained_frac` — the live version of the
manual gap table, and itself the regression alarm: a step that slows
down without its analytic components changing shows up here first.

Device rates come from `flops.py`'s peak tables on TPU, where the
components are honest fractions of peak and `unexplained` IS the
residual MFU gap BASELINE.md used to account for by hand. On hosts
with no published peak (the CPU test meshes) probe-calibrated rates
set only the RELATIVE MXU/HBM split; the compute component is then
SELF-SCALED over the first two spans-level windows (the first usually
contains the compile-heavy step 0) and frozen at the second, so those
windows balance by construction (`attrib_compute_scale` records the
factor) and every later window's `unexplained_frac` measures drift
from that frozen baseline — a step that slows down without its
analytic components changing raises the alarm on any host, loaded or
not, which is the regression-alarm semantics the gap table needs
(absolute roofline truth off-TPU would just measure host-load noise).
"""

from __future__ import annotations

import time

import numpy as np

from shallowspeed_tpu import flops as _flops

# fp8-operand matmul FLOPs run the MXU at this multiple of the table/
# calibrated rate (mirrors flops.device_peak_flops's fp8 branch — the
# v7 spec's dense fp8 4.6PF vs bf16 2.3PF). Pricing the jaxpr's
# float8-operand dots at 2x is what makes the attribution gate's
# headline work: an fp8-on step's attrib_mxu_frac must SHRINK vs the
# bf16 baseline because the same dots cost half the roofline seconds.
FP8_FLOPS_RATIO = 2.0

# ------------------------------------------------------- device rates

_CALIBRATED: dict | None = None


def _median_timed(fn, reps: int = 5) -> float:
    fn()  # warmup (compile, allocator)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def _calibrate() -> dict:
    """Effective device rates measured in place (cached per process),
    each as a SLOPE between two probe sizes so the per-dispatch launch
    overhead (hundreds of microseconds on a loaded CPU host — it
    dwarfs a small probe) cancels out: f32 matmuls at n=256/512 for
    FLOP/s, 1/4 MiB elementwise sweeps for bytes/s. The slope is the
    effective mid-size rate a compiled program's ops actually see;
    ops smaller than the probes run below it, which is why
    `step_waterfall` prices every matmul at the max of its compute and
    memory roofline times (a small matmul is memory-bound and the
    bytes term carries it). ICI defaults to the memory rate
    (virtual-device collectives are memcpys)."""
    global _CALIBRATED
    if _CALIBRATED is not None:
        return _CALIBRATED
    import jax
    import jax.numpy as jnp

    def slope(points):  # [(work, seconds)] -> work/s with offset removed
        (w1, t1), (w2, t2) = points
        if t2 - t1 <= 1e-9:
            return w2 / max(t2, 1e-9)  # noise floor: direct large rate
        return (w2 - w1) / (t2 - t1)

    mm_pts = []
    for n in (256, 512):
        a = jnp.asarray(np.random.default_rng(0).normal(size=(n, n)),
                        jnp.float32)
        mm = jax.jit(lambda x: x @ x)
        t_mm = _median_timed(lambda: jax.block_until_ready(mm(a)))
        mm_pts.append((2.0 * n ** 3, t_mm))
    ew_pts = []
    for m in (1 << 18, 1 << 20):  # 1 MiB, 4 MiB f32
        x = jnp.zeros((m,), jnp.float32)
        ew = jax.jit(lambda v: v * 1.0000001 + 1.0)
        t_ew = _median_timed(lambda: jax.block_until_ready(ew(x)))
        ew_pts.append((2.0 * m * 4, t_ew))  # read + write
    rate = {
        "flops": max(slope(mm_pts), 1e6),
        "hbm": max(slope(ew_pts), 1e6),
        "source": "calibrated",
    }
    rate["ici"] = rate["hbm"]
    _CALIBRATED = rate
    return rate


def recalibrate() -> dict:
    """Drop the cached calibration and probe again (tests use this to
    shrug off a host-load transient that skewed the first probe)."""
    global _CALIBRATED
    _CALIBRATED = None
    return _calibrate()


def device_rates(dtype: str = "bf16", device=None) -> dict:
    """{"flops", "hbm", "ici", "source"} for one JAX device: the
    published peaks when the device kind is known ("table"), else the
    in-place calibration ("calibrated")."""
    peak = _flops.device_peak_flops(device, dtype)
    if peak is None:
        return _calibrate()
    hbm = _flops.device_mem_bandwidth(device) or peak / 300.0
    ici = _flops.device_ici_bandwidth(device) or hbm / 4.0
    return {"flops": peak, "hbm": hbm, "ici": ici, "source": "table"}


# ---------------------------------------------------- roofline costing


def roofline_of_jaxpr(closed) -> dict:
    """Per-op roofline inputs of one program call: matmul FLOPs and
    non-matmul HBM bytes, each split by whether the op sits inside a
    `shard_map` (per-device shapes — price against ONE device's peak)
    or outside (GSPMD global shapes — price against the fleet peak).
    Scan bodies multiply by their trip count; `cond` takes the
    per-field max over branches (upper bound); `while` counts once and
    flags `approximate`; a `pallas_call` body multiplies by its grid
    size. Collectives are skipped here — their bytes are wire traffic
    (`collectives.traffic_of_jaxpr`), not HBM work.
    """
    from shallowspeed_tpu.analysis.walker import (COLLECTIVES,
                                                  _as_jaxpr, dot_flops,
                                                  eqn_bytes, sub_jaxprs)

    acc = {"flops_shard": 0, "flops_global": 0,
           "flops_fp8_shard": 0, "flops_fp8_global": 0,
           "dot_bytes_shard": 0, "dot_bytes_global": 0,
           "bytes_shard": 0, "bytes_global": 0}
    state = {"approx": False}

    def pallas_grid(eqn) -> int:
        gm = eqn.params.get("grid_mapping")
        grid = getattr(gm, "grid", ()) or ()
        n = 1
        for g in grid:
            if isinstance(g, (int, np.integer)):
                n *= int(g)
            else:
                state["approx"] = True
        return n

    def walk(jaxpr, trips: int, in_shmap: bool, out: dict):
        j = _as_jaxpr(jaxpr)
        for eqn in j.eqns:
            name = eqn.primitive.name
            if name in COLLECTIVES:
                continue
            subs = sub_jaxprs(eqn)
            if subs:
                n = trips
                if name == "scan":
                    length = eqn.params.get("length")
                    if length is None:
                        state["approx"] = True
                    else:
                        n = trips * int(length)
                elif name == "while":
                    state["approx"] = True
                elif name == "pallas_call":
                    n = trips * pallas_grid(eqn)
                    state["approx"] = True  # tile reuse is not modeled
                child_sh = in_shmap or name == "shard_map"
                if name == "cond":
                    # one branch runs: per-field max is an upper bound
                    trials = []
                    for s in subs:
                        trial = {k: 0 for k in acc}
                        walk(s, n, child_sh, trial)
                        trials.append(trial)
                    if len({tuple(sorted(t.items()))
                            for t in trials}) > 1:
                        state["approx"] = True
                    for k in out:
                        out[k] += max(t[k] for t in trials)
                else:
                    for s in subs:
                        walk(s, n, child_sh, out)
                continue
            fl = dot_flops(eqn)
            key = "shard" if in_shmap else "global"
            if fl:
                out["flops_" + key] += fl * trips
                # float8-operand dots are the quantized matmuls
                # (ops/matmul.fp8_dense) — tracked as a subset so
                # roofline_seconds can price them at FP8_FLOPS_RATIO
                if any(str(getattr(v.aval, "dtype", "")
                           ).startswith("float8")
                       for v in eqn.invars):
                    out["flops_fp8_" + key] += fl * trips
                out["dot_bytes_" + key] += eqn_bytes(eqn) * trips
            else:
                out["bytes_" + key] += eqn_bytes(eqn) * trips

    walk(closed.jaxpr, 1, False, acc)
    acc["approximate"] = state["approx"]
    return acc


# --------------------------------------------------------- the waterfall


def roofline_seconds(roof: dict, rates: dict,
                     n_devices: int = 1) -> dict:
    """Roofline seconds per step: matmuls take the max of their
    compute and operand-byte times (a small matmul is memory-bound —
    its FLOPs alone would undercount it), everything else the HBM
    roofline. Aggregated per locality bucket (max of sums, a mild
    lower bound on the per-op sum of maxes)."""
    nd = max(1, int(n_devices))
    mxu = hbm = 0.0
    for key, div in (("shard", 1), ("global", nd)):
        fl = roof.get("flops_" + key, 0)
        fp8 = min(roof.get("flops_fp8_" + key, 0), fl)
        # fp8-operand dots run at FP8_FLOPS_RATIO x the base rate
        flop_s = ((fl - fp8) / rates["flops"]
                  + fp8 / (rates["flops"] * FP8_FLOPS_RATIO))
        mxu += max(flop_s,
                   roof.get("dot_bytes_" + key, 0) / rates["hbm"]) / div
        hbm += roof.get("bytes_" + key, 0) / rates["hbm"] / div
    return {"mxu_s": mxu, "hbm_s": hbm}


def step_waterfall(t_step: float, roofline: dict | None = None,
                   coll_bytes: int = 0,
                   exposed_frac: float | None = None,
                   bubble_fraction: float | None = None,
                   host_gap: float | None = None,
                   n_devices: int = 1, rates: dict | None = None,
                   dtype: str = "bf16",
                   compute_scale: float | None = None) -> dict:
    """Reconcile one measured (fenced) step time `t_step` (seconds)
    against the analytic components; returns the `attrib_*` step-line
    fields (telemetry schema v4).

    - `roofline`: `roofline_of_jaxpr` output for the step program(s).
    - `coll_bytes`: per-device collective payload bytes per step
      (`collectives` convention); `exposed_frac` splits them into
      exposed (priced at the wire rate) vs hidden (free — overlapped
      under compute). None means no exposure info: all bytes count as
      exposed (conservative).
    - `bubble_fraction`: the pipeline's measured (or static) bubble.
    - `host_gap`: seconds of the window not inside any fenced step
      span, already divided down to PER-STEP terms by the caller.
    - `compute_scale`: the frozen self-calibration factor applied to
      the compute component on rate-calibrated hosts (RunTelemetry
      derives it at the first window; None = absolute rates, the TPU
      path).

    `attrib_unexplained_frac = max(0, 1 - sum(components))` — when the
    components sum past 1 (the byte model is an unfused upper bound)
    unexplained clamps to 0, the safe direction for an alarm.
    """
    assert t_step > 0.0, t_step
    if rates is None:
        rates = device_rates(dtype=dtype)
    out = {"attrib_t_step_ms": round(t_step * 1e3, 3),
           "attrib_rates_source": rates.get("source", "table")}
    explained = 0.0
    if roofline is not None:
        secs = roofline_seconds(roofline, rates, n_devices)
        scale = 1.0 if compute_scale is None else float(compute_scale)
        comp = scale * (secs["mxu_s"] + secs["hbm_s"]) / t_step
        out["attrib_compute_frac"] = round(comp, 4)
        out["attrib_mxu_frac"] = round(scale * secs["mxu_s"] / t_step,
                                       4)
        if compute_scale is not None:
            out["attrib_compute_scale"] = round(scale, 4)
        explained += comp
    if coll_bytes:
        frac = 1.0 if exposed_frac is None else float(exposed_frac)
        wire = coll_bytes * frac / rates["ici"] / t_step
        out["attrib_comm_exposed_frac"] = round(wire, 4)
        explained += wire
    if bubble_fraction is not None:
        out["attrib_bubble_frac"] = round(float(bubble_fraction), 4)
        explained += float(bubble_fraction)
    if host_gap is not None:
        hf = max(0.0, float(host_gap)) / t_step
        out["attrib_host_frac"] = round(hf, 4)
        explained += hf
    out["attrib_unexplained_frac"] = round(max(0.0, 1.0 - explained), 4)
    return out


def window_step_spans(events, names=("step", "batch")) -> list[float]:
    """Fenced step-span durations (seconds) in a tracer event window:
    top-level "X" spans named `step` (the compiled engines) or `batch`
    (the pipeline VM). Nested phase spans (grads/update/per-op) are
    excluded by name."""
    return [e["dur"] / 1e6 for e in events
            if e.get("ph") == "X" and e.get("name") in names
            and e.get("dur")]
