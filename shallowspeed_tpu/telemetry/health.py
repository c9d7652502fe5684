"""Training-health pack — on-device numerics monitors for every engine.

PR 2 gave the system eyes on the *hardware* (spans, bubble, HBM,
collectives, recompiles); this module watches the *model*: a NaN'd
gradient, a diverging loss, or a dead layer otherwise surfaces only as
a corrupted loss line many steps later. Production stacks earn the
"healthy optimizer trajectory" assumption the schedule papers make with
in-step numerics monitors and guarded updates — exactly what this
provides.

Device side (`grad_health` / `update_health`): computed INSIDE the
engines' compiled train steps — global and per-group gradient/param L2
norms, the update-to-param ratio, and non-finite counts — returned as
one small extra output pytree, so the health pack adds **zero extra jit
entrypoints and zero recompiles** (the step executable simply grows a
few scalar outputs; pinned by `tests/test_health.py`'s compile-count
tests, the same counter the analysis retrace rule reads).

Reductions are correct on every mesh, through ONE rule: the pack is
computed on the engine's fully REDUCED
gradients, and each per-leaf statistic is `psum`'d over exactly the
mesh axes that leaf's PartitionSpec *shards* — the one piece of truth
every engine already owns. Concretely:

- dp / sp data axes: the reduced grads are replicated across them, so
  a replicated leaf's local statistic IS the global one (no psum, no
  double count);
- fsdp / zero-2 dp-scattered grads: the leaf's spec carries 'dp', each
  device's shard-local sum-of-squares psums over 'dp' to the exact
  global norm (shards partition the leaf);
- pp (compiled pipelines): block leaves' specs carry 'pp', so the psum
  spans stages and the pack is globally correct in-program — including
  zb and interleaved-vpp stacked layouts, whose permuted block stacks
  still partition the parameter set over 'pp';
- tp / ep: Megatron/expert-sharded leaves' specs carry those axes and
  their shard-sums likewise partition the leaf;
- pp (the interpreted VM): stages are separate executables — each
  stage computes a LOCAL pack and the driver merges them
  (`merge_packs`);
- GSPMD-jit engines (no shard_map): pass no specs — plain `jnp`
  reductions are already global; XLA inserts the collectives.

Host side: `HealthMonitor` aggregates the per-step packs, runs the
streaming anomaly detector (`telemetry/anomaly.py` — robust EWMA
z-scores over the loss and grad-norm series), attaches policy actions
(warn | skip_step | abort) to its verdicts, merges health fields into
every step line (`metrics.StepRates(health=...)`), and feeds a
liveness/health status into the elastic supervisor's heartbeat file so
a numerically-dead run restarts from the last good checkpoint, not
just a hung one (`elastic.write_heartbeat` / `read_heartbeat`).

The skip itself is compiled into the step: `--health guard` gates the
optimizer update on `nonfinite == 0` through
`optim._Optimizer.guarded_step`, leaving params and optimizer state
bit-identical on a skipped step.
"""

from __future__ import annotations

MODES = ("off", "monitor", "guard")


def _group_of(path) -> str:
    """Stable leaf-group name from a tree path's first component: list
    engines (the MLP family's per-layer param lists) group per layer,
    dict engines (the transformer family) per component (tok_emb /
    blocks / head / ...). Coarse on purpose — the groups feed the
    dead-layer detector and per-group norms on step lines, not a full
    per-tensor dump."""
    from jax import tree_util as jtu

    key = path[0]
    if isinstance(key, jtu.SequenceKey):
        return f"layer{key.idx}"
    if isinstance(key, jtu.DictKey):
        return str(key.key)
    if isinstance(key, jtu.GetAttrKey):
        return str(key.name)
    return str(key)


def spec_axes(specs) -> list:
    """Flattened per-leaf tuples of mesh axis names a PartitionSpec
    pytree shards (the axes a leaf's statistic must psum over); pass
    the result as `grad_health`/`update_health`'s axes list."""
    from jax.sharding import PartitionSpec as P

    def axes_of(spec):
        used = []
        for e in spec:
            if e is None:
                continue
            for a in (e if isinstance(e, (tuple, list)) else (e,)):
                if a not in used:
                    used.append(a)
        return tuple(used)

    import jax

    return [axes_of(s) for s in jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))]


def _reduced_sq(x, axes):
    """Sum of squares of one leaf (f32 accumulation), psum'd over the
    leaf's sharded axes (none outside shard_map / for replicated
    leaves)."""
    import jax
    import jax.numpy as jnp

    sq = jnp.sum(jnp.square(x.astype(jnp.float32)))
    if axes:
        sq = jax.lax.psum(sq, tuple(axes))
    return sq


def grad_health(params, grads, grad_axes=None, param_axes=None) -> dict:
    """The traced health pack: global + per-group gradient norms, the
    param norm, and the non-finite count, as a small pytree of f32/i32
    scalars. Call INSIDE the compiled step, on the engine's fully
    REDUCED grads (post-psum / post-scatter). `grad_axes`/`param_axes`:
    flattened per-leaf sharded-axis tuples (`spec_axes` of the specs
    the values leave the program with); None = all leaves replicated /
    GSPMD-global."""
    import jax
    import jax.numpy as jnp

    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    gax = grad_axes or [()] * len(flat)
    gsq = jnp.float32(0.0)
    nf = jnp.int32(0)
    groups: dict = {}
    for (path, g), axes in zip(flat, gax):
        sq = _reduced_sq(g, axes)
        n = jnp.sum(~jnp.isfinite(g)).astype(jnp.int32)
        if axes:
            n = jax.lax.psum(n, tuple(axes))
        gsq = gsq + sq
        nf = nf + n
        name = _group_of(path)
        groups[name] = groups.get(name, jnp.float32(0.0)) + sq
    p_leaves = jax.tree_util.tree_leaves(params)
    pax = param_axes or [()] * len(p_leaves)
    psq = jnp.float32(0.0)
    for p, axes in zip(p_leaves, pax):
        psq = psq + _reduced_sq(p, axes)
    return {
        "grad_norm": jnp.sqrt(gsq),
        "param_norm": jnp.sqrt(psq),
        "nonfinite": nf,
        "groups": {k: jnp.sqrt(v) for k, v in groups.items()},
    }


def update_health(pack: dict, params, new_params, param_axes=None,
                  skipped=None) -> dict:
    """Finish the pack after the optimizer update: the update-to-param
    ratio ||new - old|| / ||old|| (0 on a skipped step — the skip is
    visible in the series), plus the `skipped` flag under guard."""
    import jax
    import jax.numpy as jnp

    leaves = jax.tree_util.tree_leaves(params)
    pax = param_axes or [()] * len(leaves)
    dsq = jnp.float32(0.0)
    for old, new, axes in zip(leaves,
                              jax.tree_util.tree_leaves(new_params),
                              pax):
        dsq = dsq + _reduced_sq(
            new.astype(jnp.float32) - old.astype(jnp.float32), axes)
    pack = dict(pack)
    pack["update_ratio"] = jnp.sqrt(dsq) / (pack["param_norm"] + 1e-12)
    if skipped is not None:
        pack["skipped"] = jnp.asarray(skipped).astype(jnp.int32)
    return pack


def param_l2(tree):
    """Global L2 of a pytree (f32 accumulation, no psums) — shared by
    the split-update programs (zero.py, the VM's _opt) so the norm
    convention cannot drift per call site."""
    import jax
    import jax.numpy as jnp

    t = jnp.float32(0.0)
    for l in jax.tree_util.tree_leaves(tree):
        t = t + jnp.sum(jnp.square(l.astype(jnp.float32)))
    return jnp.sqrt(t)


def note_step(engine, pack) -> None:
    """Record one step's pack on `engine`: stores `last_health` and
    lazily updates device-side CUMULATIVE counters (one tiny add per
    step, no host sync) — a transient guarded skip or nonfinite step
    between log points must reach the next snapshot even though
    `last_health` itself is overwritten every step."""
    import jax.numpy as jnp

    cum = getattr(engine, "_health_cum", None)
    nf_step = (pack["nonfinite"] > 0).astype(jnp.int32)
    new = {"nonfinite_steps_total":
           nf_step if cum is None
           else cum["nonfinite_steps_total"] + nf_step}
    if "skipped" in pack:
        prev = 0 if cum is None else cum.get("skipped_total", 0)
        new["skipped_total"] = prev + pack["skipped"]
    engine._health_cum = new
    engine.last_health = pack


def engine_snapshot(engine) -> dict | None:
    """The engines' shared `health_snapshot` body: last pack + the
    cumulative counters, fetched as one host dict."""
    if engine.last_health is None:
        return None
    cum = getattr(engine, "_health_cum", None) or {}
    return fetch_pack({**engine.last_health, **cum})


# --------------------------------------------------------- host side


def fetch_pack(pack) -> dict | None:
    """Device pack -> plain-python dict (one host sync; call at log
    points only, like every other telemetry fetch)."""
    if pack is None:
        return None
    import jax
    import numpy as np

    host = jax.device_get(pack)
    out = {
        "grad_norm": float(host["grad_norm"]),
        "param_norm": float(host["param_norm"]),
        "nonfinite": int(host["nonfinite"]),
        "groups": {k: float(v) for k, v in host["groups"].items()},
    }
    for k in ("update_ratio",):
        if k in host:
            out[k] = float(host[k])
    for k in ("skipped", "skipped_total", "nonfinite_steps_total"):
        if k in host:
            out[k] = int(host[k])
    # fp8 delayed-scaling bookkeeping (fp8.Fp8TrainEngine): per-layer
    # activation absmax, the scale it produced, and (round 18) the
    # clamp fractions at each quantize — the numerics pack the
    # NumericsMonitor reduces host-side
    for k in ("fp8_amax", "fp8_scale", "fp8_overflow", "fp8_underflow"):
        if k in host:
            out[k] = [float(v) for v in np.asarray(host[k]).ravel()]
    return out


def merge_packs(packs: list) -> dict | None:
    """Driver-side merge of per-STAGE host packs (the interpreted VM's
    pp stages are separate executables; zb/vpp pipelines hand the
    driver one pack per logical stage). Norms combine as
    sqrt(sum-of-squares) — stages partition the parameter set — counts
    sum, groups get a stage prefix, and the global update ratio is
    recovered from the per-stage (ratio, param_norm) pairs."""
    packs = [p for p in packs if p]
    if not packs:
        return None
    import math

    gsq = sum(p["grad_norm"] ** 2 for p in packs)
    psq = sum(p["param_norm"] ** 2 for p in packs)
    out = {
        "grad_norm": math.sqrt(gsq),
        "param_norm": math.sqrt(psq),
        "nonfinite": sum(p["nonfinite"] for p in packs),
        "groups": {f"s{i}.{k}": v for i, p in enumerate(packs)
                   for k, v in p["groups"].items()},
    }
    if all("update_ratio" in p for p in packs):
        dsq = sum((p["update_ratio"] * p["param_norm"]) ** 2
                  for p in packs)
        out["update_ratio"] = math.sqrt(dsq) / (math.sqrt(psq) + 1e-12)
    if any("skipped" in p for p in packs):
        # stages skip in lockstep (one global ok); any stage's flag
        out["skipped"] = max(p.get("skipped", 0) for p in packs)
    return out


class HealthMonitor:
    """Host-side aggregator: per-step health packs in, verdicts and
    step-line fields out.

    `observe(step, loss, pack)` runs the anomaly detector and returns
    the (policy-annotated) verdicts for this observation; the driver
    decides what an `abort` action does (the convention is a forensic
    snapshot + labeled SystemExit, like the existing divergence exit).
    `step_fields()` is merged into step lines by
    `metrics.StepRates(health=...)`; `heartbeat_status()` feeds the
    elastic supervisor ("ok" or "dead <reason>" — a dead status makes
    the supervisor kill and restart the run from the last good
    checkpoint instead of waiting for the hang timeout)."""

    def __init__(self, policy=None, dead_after: int = 3, **detector_kw):
        from shallowspeed_tpu.telemetry.anomaly import (AnomalyDetector,
                                                        GuardPolicy)

        self.detector = AnomalyDetector(**detector_kw)
        self.policy = policy or GuardPolicy()
        self.dead_after = dead_after
        self.skipped_total = 0
        self.nonfinite_steps = 0
        self._consec_nonfinite = 0
        self._prev_nf_total = 0
        self.dead_reason: str | None = None
        self._last: dict = {}
        self._verdicts_since_log: list = []

    def observe(self, step: int, loss, pack: dict | None) -> list:
        """One observation (typically per log point — the packs are
        computed every step on device; fetching them is the host sync).
        Returns this observation's verdicts with `action` set."""
        from shallowspeed_tpu.telemetry.anomaly import Verdict

        verdicts = self.detector.observe(step, loss=loss, pack=pack)
        if pack is not None:
            self._last = dict(pack)
            # prefer the engines' device-side CUMULATIVE counters
            # (health.note_step): a transient skip/nonfinite step
            # between log points is counted even though the last pack
            # in the window is clean
            if "skipped_total" in pack:
                self.skipped_total = pack["skipped_total"]
            elif pack.get("skipped"):
                self.skipped_total += 1
            if "nonfinite_steps_total" in pack:
                delta = pack["nonfinite_steps_total"] \
                    - self._prev_nf_total
                self._prev_nf_total = pack["nonfinite_steps_total"]
                self.nonfinite_steps = pack["nonfinite_steps_total"]
                bad_window = delta > 0
                if bad_window and pack.get("nonfinite", 0) == 0:
                    # the event happened mid-window; the detector only
                    # saw the clean last pack — surface it anyway
                    verdicts.append(Verdict(
                        "nonfinite", step, severity="error",
                        detail=f"{delta} step(s) since the last log "
                               f"point had non-finite gradients"))
            else:
                bad_window = pack.get("nonfinite", 0) > 0
                if bad_window:
                    self.nonfinite_steps += 1
            if bad_window:
                self._consec_nonfinite += 1
            else:
                self._consec_nonfinite = 0
        for v in verdicts:
            v.action = self.policy.action(v.kind)
        if self._consec_nonfinite >= self.dead_after:
            self.dead_reason = (f"nonfinite gradients for "
                                f"{self._consec_nonfinite} consecutive "
                                f"observations")
        elif any(v.kind == "divergence" for v in verdicts):
            self.dead_reason = "loss divergence"
        self._verdicts_since_log.extend(verdicts)
        return verdicts

    def step_fields(self) -> dict:
        """Health fields for the next step line (schema.py types them);
        drains the verdict window."""
        out: dict = {}
        p = self._last
        if p:
            out["health_grad_norm"] = round(p.get("grad_norm", 0.0), 6)
            out["health_param_norm"] = round(p.get("param_norm", 0.0), 6)
            if "update_ratio" in p:
                out["health_update_ratio"] = round(p["update_ratio"], 9)
            out["health_nonfinite"] = int(p.get("nonfinite", 0))
        out["health_skipped_total"] = self.skipped_total
        verdicts = self._verdicts_since_log
        self._verdicts_since_log = []
        if verdicts:
            out["health_verdicts"] = [v.kind for v in verdicts]
        return out

    def heartbeat_status(self) -> str:
        return f"dead {self.dead_reason}" if self.dead_reason else "ok"

    def unhealthy(self) -> bool:
        """Whether the run's state, as of the last observed pack, is
        one a checkpoint must NOT capture: non-finite gradients or a
        dead verdict. The drivers gate saves on this — checkpointing a
        poisoned iterate would turn the recovery stack's restore point
        into the very state it needs to recover FROM (found by the
        round-10 chaos NaN-storm drill)."""
        return bool(self.dead_reason) or self._consec_nonfinite > 0
