"""Target probes — the real compiled train steps, instrumented.

A `TargetProbe` builds one engine family at the tiny CPU-friendly
configuration the test suite exercises, runs its public train/eval API
a couple of times (the retrace audit's behavioral probe — same shapes,
fresh data, so a stable cache key must yield exactly one executable),
then captures each jitted entrypoint's jaxpr via `jax.make_jaxpr` on
shape/dtype structs of the live arguments. Rules (`rules.py`) consume
the probe; nothing here judges — it only observes.

Engine imports live inside the builders so `shallowspeed_tpu.analysis`
stays importable without tracing anything.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable

import jax
import numpy as np

from shallowspeed_tpu.analysis.walker import iter_eqns, sub_jaxprs

GiB = 1 << 30
DEFAULT_BUDGET = 16 * GiB  # one v4/v5e-class chip's HBM


def _sds(tree):
    """Shape/dtype skeleton of a pytree of arrays (tracing args that can
    never alias or consume the engine's live buffers)."""
    return jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(np.shape(l), np.asarray(l).dtype)
        if not hasattr(l, "aval") and not hasattr(l, "dtype")
        else jax.ShapeDtypeStruct(l.shape, l.dtype), tree)


@dataclass
class EntryPoint:
    name: str
    fn: Any                       # the jitted callable
    args: tuple                   # SDS pytrees, one per positional arg
    arg_names: tuple              # for messages, same length as args
    donate: tuple = ()            # arg indices that MUST be donated
    calls: int = 0                # public-API calls the probe ran
    n_compiles_expected: int = 1
    observed_compiles: int | None = None  # _cache_size after exercising
    # arg name -> (lo, hi): measured init/calibration absmax bounds
    # seeding the precision-flow pass's interval propagation
    ranges: dict | None = None


@dataclass
class TargetProbe:
    name: str
    mesh: Any
    compute_dtype: Any            # declared compute dtype (None = f32)
    entrypoints: list = field(default_factory=list)
    hbm_budget: int = DEFAULT_BUDGET
    _jaxprs: dict = field(default_factory=dict)
    _flows: dict = field(default_factory=dict)

    # ---------------------------------------------------- jaxpr access

    def jaxpr_of(self, ep: EntryPoint):
        """The entrypoint's ClosedJaxpr (cached; None if untraceable)."""
        if ep.name not in self._jaxprs:
            try:
                self._jaxprs[ep.name] = jax.make_jaxpr(ep.fn)(*ep.args)
            except Exception as e:  # surfaced by the CLI, not swallowed
                raise RuntimeError(
                    f"tracing {self.name}::{ep.name} failed") from e
        return self._jaxprs[ep.name]

    def walk(self, ep: EntryPoint):
        jaxpr = self.jaxpr_of(ep)
        return iter_eqns(jaxpr) if jaxpr is not None else iter(())

    def jaxpr_scopes(self, ep: EntryPoint):
        """Yield (plain jaxpr, path) for the top jaxpr and every
        sub-jaxpr scope — rules that need per-scope def-use maps (the
        dtype lint) walk scopes instead of flat eqns."""
        top = self.jaxpr_of(ep)
        if top is None:
            return

        def rec(j, path):
            yield j, path
            for eqn in j.eqns:
                for sub in sub_jaxprs(eqn):
                    yield from rec(sub, path + (eqn.primitive.name,))

        yield from rec(top.jaxpr, ())

    def flow(self, ep: EntryPoint):
        """The entrypoint's precision-flow result (`provenance.py`),
        computed once and shared by every rule that reads per-value
        provenance (double-rounding, accumulation, scale pairing,
        range safety)."""
        if ep.name not in self._flows:
            from shallowspeed_tpu.analysis.provenance import \
                flow_entrypoint
            self._flows[ep.name] = flow_entrypoint(self, ep)
        return self._flows[ep.name]

    def top_jit(self, ep: EntryPoint):
        """The outermost jit eqn (donation lives there), or None."""
        jaxpr = self.jaxpr_of(ep)
        if jaxpr is not None:
            for eqn in jaxpr.jaxpr.eqns:
                if eqn.primitive.name == "jit":
                    return eqn
        return None

    def seal(self):
        """Record per-entrypoint compile counts NOW (before any rule's
        `make_jaxpr` could touch caches) — the retrace audit reads this
        snapshot, taken right after the exercise calls."""
        for ep in self.entrypoints:
            size = getattr(ep.fn, "_cache_size", None)
            if size is not None and ep.calls:
                ep.observed_compiles = size()
        return self


# ------------------------------------------------------------ MLP probes


class _SynthDS:
    """Duck-typed stand-in for `data.dataset.Dataset` (only the method
    the fused engines read): deterministic per-batch microbatch stacks."""

    def __init__(self, n_mu, mubs, d_in, d_out, seed):
        self._shape = (n_mu, mubs)
        self._dims = (d_in, d_out)
        self._seed = seed

    def load_mubatch_stack(self, batch_id):
        n_mu, mubs = self._shape
        d_in, d_out = self._dims
        rng = np.random.default_rng([self._seed, batch_id])
        x = rng.standard_normal((n_mu, mubs, d_in)).astype(np.float32)
        y = np.eye(d_out, dtype=np.float32)[
            rng.integers(0, d_out, (n_mu, mubs))]
        return x, y


def build_engine(budget: int = DEFAULT_BUDGET) -> TargetProbe:
    """`engine.FusedDPEngine` — the dp-only fused MLP trainer."""
    from shallowspeed_tpu.engine import FusedDPEngine
    from shallowspeed_tpu.models.mlp import MLPStage
    from shallowspeed_tpu.optim import SGD
    from shallowspeed_tpu.parallel.mesh import make_mesh

    sizes, gbs, n_mu, dp = [12, 16, 10], 16, 2, 2
    eng = FusedDPEngine(MLPStage(sizes, 0, 1, batch_size=gbs), SGD(0.1),
                        make_mesh(dp, 1))
    ds = [_SynthDS(n_mu, gbs // dp // n_mu, sizes[0], sizes[-1], r)
          for r in range(dp)]
    for b in range(2):
        eng.train_batch(b, ds)
    x = np.random.default_rng(0).standard_normal(
        (8, sizes[0])).astype(np.float32)
    eng.infer(x)
    eng.infer(x + 1)

    probe = TargetProbe("engine", eng.mesh, None, hbm_budget=budget)
    xs, ys = (jax.ShapeDtypeStruct((dp, n_mu, gbs // dp // n_mu, d),
                                   np.float32)
              for d in (sizes[0], sizes[-1]))
    probe.entrypoints = [
        EntryPoint("_step", eng._step,
                   (_sds(eng.params), _sds(eng.opt_state), xs, ys),
                   ("params", "opt_state", "xs", "ys"),
                   donate=(0, 1), calls=2),
        EntryPoint("_infer", eng._infer,
                   (_sds(eng.params),
                    jax.ShapeDtypeStruct((8, sizes[0]), np.float32)),
                   ("params", "x"), calls=2),
    ]
    return probe.seal()


def build_engine_overlap(budget: int = DEFAULT_BUDGET) -> TargetProbe:
    """`engine.FusedDPEngine(overlap=...)` — the bucketed backward-
    overlapped dp reduction. The `overlap-bucket` rule's live target:
    proves every dp reduction is a registered bucket AND that the
    bucket collectives are dataflow-interleaved with backward compute
    (the acceptance shape for `parallel/overlap.py`)."""
    from shallowspeed_tpu.engine import FusedDPEngine
    from shallowspeed_tpu.models.mlp import MLPStage
    from shallowspeed_tpu.optim import SGD
    from shallowspeed_tpu.parallel.mesh import make_mesh
    from shallowspeed_tpu.parallel.overlap import OverlapConfig

    sizes, gbs, n_mu, dp = [12, 16, 14, 10], 16, 2, 2
    eng = FusedDPEngine(MLPStage(sizes, 0, 1, batch_size=gbs), SGD(0.1),
                        make_mesh(dp, 1),
                        overlap=OverlapConfig(bucket_mb=0.001))
    ds = [_SynthDS(n_mu, gbs // dp // n_mu, sizes[0], sizes[-1], r)
          for r in range(dp)]
    for b in range(2):
        eng.train_batch(b, ds)

    probe = TargetProbe("engine:overlap", eng.mesh, None,
                        hbm_budget=budget)
    xs, ys = (jax.ShapeDtypeStruct((dp, n_mu, gbs // dp // n_mu, d),
                                   np.float32)
              for d in (sizes[0], sizes[-1]))
    probe.entrypoints = [
        EntryPoint("_step", eng._step,
                   (_sds(eng.params), _sds(eng.opt_state), xs, ys),
                   ("params", "opt_state", "xs", "ys"),
                   donate=(0, 1), calls=2),
    ]
    return probe.seal()


def build_spmd_pipeline(budget: int = DEFAULT_BUDGET) -> TargetProbe:
    """`parallel.SPMDPipelineEngine` — the compiled GPipe MLP step."""
    from shallowspeed_tpu.optim import SGD
    from shallowspeed_tpu.parallel.mesh import make_mesh
    from shallowspeed_tpu.parallel.spmd_pipeline import SPMDPipelineEngine

    sizes, gbs, n_mu, dp, pp = [12, 14, 13, 10], 16, 2, 2, 2
    mubs = gbs // dp // n_mu
    eng = SPMDPipelineEngine(sizes, SGD(0.1), make_mesh(dp, pp), n_mu,
                             mubs, gbs)
    ds = [_SynthDS(n_mu, mubs, sizes[0], sizes[-1], r)
          for r in range(dp)]
    for b in range(2):
        eng.train_batch(b, ds)

    probe = TargetProbe("spmd_pipeline", eng.mesh, None,
                        hbm_budget=budget)
    wmax = max(sizes)
    xs = jax.ShapeDtypeStruct((dp, n_mu, mubs, wmax), np.float32)
    ys = jax.ShapeDtypeStruct((dp, n_mu, mubs, sizes[-1]), np.float32)
    probe.entrypoints = [
        EntryPoint("_step", eng._step_fn,
                   (_sds(eng.params), _sds(eng.opt_state), xs, ys),
                   ("params", "opt_state", "xs", "ys"),
                   donate=(0, 1), calls=2),
    ]
    return probe.seal()


# ----------------------------------------------------- transformer probes


def _lm_cfg(**kw):
    from shallowspeed_tpu.models import transformer as T

    base = dict(vocab=64, d_model=32, n_heads=4, n_layers=4, max_seq=32)
    base.update(kw)
    return T.TransformerConfig(**base)


def _lm_batch(seed, b=8, t=16, vocab=64):
    rng = np.random.default_rng(seed)
    tok = rng.integers(0, vocab, (b, t)).astype(np.int32)
    return tok, np.roll(tok, -1, axis=1).astype(np.int32)


def build_gspmd(budget: int = DEFAULT_BUDGET) -> TargetProbe:
    """The GSPMD family via its Megatron-TP subclass on ('dp','tp') —
    placement-annotated params, one jitted step, XLA collectives."""
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from shallowspeed_tpu.optim import Adam
    from shallowspeed_tpu.parallel.tensor import TensorParallelEngine

    cfg = _lm_cfg(compute_dtype=jnp.bfloat16)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    eng = TensorParallelEngine(cfg, Adam(1e-3), mesh)
    for s in range(2):
        tok, tgt = _lm_batch(s)
        eng.train_batch(tok, tgt)
    tok, tgt = _lm_batch(7)
    eng.eval_loss(tok, tgt)
    eng.eval_loss(tok, tgt)

    probe = TargetProbe("gspmd", mesh, cfg.compute_dtype,
                        hbm_budget=budget)
    data = jax.ShapeDtypeStruct((8, 16), np.int32)
    step = jax.ShapeDtypeStruct((), np.uint32)
    probe.entrypoints = [
        EntryPoint("_step", eng._step_fn,
                   (_sds(eng.params), _sds(eng.opt_state), data, data,
                    step),
                   ("params", "opt_state", "tokens", "targets", "step"),
                   donate=(0, 1), calls=2),
        EntryPoint("_eval", eng._eval_fn,
                   (_sds(eng.params), data, data),
                   ("params", "tokens", "targets"), calls=2),
    ]
    return probe.seal()


def build_pipeline_lm(schedule: str = "gpipe", virtual_pp: int = 1,
                      compute_dtype="bf16",
                      budget: int = DEFAULT_BUDGET) -> TargetProbe:
    """`parallel.PipelineLMEngine` over ('dp','pp') — one probe per
    compiled schedule (gpipe / 1f1b / interleaved 1f1b / ZB-H1)."""
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from shallowspeed_tpu.optim import SGD
    from shallowspeed_tpu.parallel.pipeline_lm import PipelineLMEngine

    dt = jnp.bfloat16 if compute_dtype == "bf16" else None
    cfg = _lm_cfg(compute_dtype=dt)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "pp"))
    eng = PipelineLMEngine(cfg, SGD(0.1), mesh, n_mubatches=2,
                           schedule=schedule, virtual_pp=virtual_pp)
    for s in range(2):
        tok, tgt = _lm_batch(s)
        eng.train_batch(tok, tgt)
    tok, tgt = _lm_batch(7)
    eng.eval_loss(tok, tgt)
    eng.eval_loss(tok, tgt)

    label = "interleaved" if virtual_pp > 1 else schedule
    probe = TargetProbe(f"pipeline_lm:{label}", mesh, dt,
                        hbm_budget=budget)
    placed = eng.place(tok)
    data = jax.ShapeDtypeStruct(placed.shape, placed.dtype)
    step = jax.ShapeDtypeStruct((), np.uint32)
    probe.entrypoints = [
        EntryPoint("_step", eng._step_fn,
                   (_sds(eng.params), _sds(eng.opt_state), data, data,
                    step),
                   ("params", "opt_state", "tokens", "targets", "step"),
                   donate=(0, 1), calls=2),
        EntryPoint("_eval", eng._eval_fn,
                   (_sds(eng.params), data, data),
                   ("params", "tokens", "targets"), calls=2),
    ]
    return probe.seal()


# ----------------------------------------------------- fp8 training probe


def build_fp8_train(budget: int = DEFAULT_BUDGET) -> TargetProbe:
    """`fp8.Fp8TrainEngine` — the fp8-e4m3 forward-matmul training step
    (ROADMAP item 5). The precision-flow rules' primary target: the
    traced step must prove in-program quantization paired to its scale
    on BOTH dot sides (forward and the hand STE VJP), f32 accumulation,
    in-range converts (the saturating clip), and no compounding
    rounding. `ranges` carries measured calibration stats from the live
    warmup steps, seeding the interval pass."""
    import jax.numpy as jnp  # noqa: F401  (symmetry with other builders)

    from shallowspeed_tpu.fp8 import Fp8TrainEngine
    from shallowspeed_tpu.optim import MomentumSGD

    sizes, bs = [12, 16, 10], 8
    eng = Fp8TrainEngine(sizes, MomentumSGD(0.05, momentum=0.9), seed=0)
    rng = np.random.default_rng(0)

    def batch(i):
        x = rng.standard_normal((bs, sizes[0])).astype(np.float32)
        y = np.eye(sizes[-1], dtype=np.float32)[
            rng.integers(0, sizes[-1], bs)]
        return x, y

    for i in range(2):
        eng.train_batch(*batch(i))
    xe, ye = batch(7)
    eng.eval_loss(xe, ye)
    eng.eval_loss(xe, ye)

    # calibration: measured post-warmup absmax bounds seed the interval
    # propagation (params drift during training — these are the stats
    # the certificate is conditioned on, same contract as the scales)
    pmax = max(float(np.max(np.abs(l))) for l in
               jax.tree_util.tree_leaves(eng.params)) * 4.0
    hist = np.asarray(eng.amax_hist)
    ranges = {
        "params": (-pmax, pmax),
        "x": (-6.0, 6.0),            # standard-normal features, 6 sigma
        "y": (0.0, 1.0),             # one-hot targets
        "amax_hist": (float(hist.min()) / 4.0, float(hist.max()) * 4.0),
    }

    probe = TargetProbe("fp8_train", None, None, hbm_budget=budget)
    x_sds = jax.ShapeDtypeStruct((bs, sizes[0]), np.float32)
    y_sds = jax.ShapeDtypeStruct((bs, sizes[-1]), np.float32)
    probe.entrypoints = [
        EntryPoint("_step", eng._step_fn,
                   (_sds(eng.params), _sds(eng.opt_state),
                    _sds(eng.amax_hist), x_sds, y_sds),
                   ("params", "opt_state", "amax_hist", "x", "y"),
                   donate=(0, 1, 2), calls=2, ranges=ranges),
        EntryPoint("_loss", eng._loss_fn,
                   (_sds(eng.params), _sds(eng.amax_hist), x_sds, y_sds),
                   ("params", "amax_hist", "x", "y"), calls=2,
                   ranges=ranges),
    ]
    return probe.seal()


# ------------------------------------------------------- serving probe


def build_serving_decode(budget: int = DEFAULT_BUDGET) -> TargetProbe:
    """The serving fast-decode tick (`serving/engine._decode_tick`) at
    the full quantized configuration: int8 weights (fused-dequant
    matmul), int8 KV pools, and the paged Pallas flash-decode kernel.
    The `dequant-fusion` rule's live target — the traced tick must
    never materialize a full-size dequantized weight copy — plus the
    standard dtype/memory sweeps over the kernel's sub-jaxpr."""
    import jax.numpy as jnp  # noqa: F401  (symmetry with other builders)

    from shallowspeed_tpu.models import transformer as T
    from shallowspeed_tpu.serving.engine import ServingEngine, _decode_tick

    cfg = T.TransformerConfig(vocab=32, d_model=32, n_heads=4,
                              n_layers=2, max_seq=64)
    eng = ServingEngine(T.init(cfg, seed=0), cfg, n_blocks=8,
                        block_size=8, max_slots=2, prefill_chunk=8,
                        weight_quant="int8", kv_quant="int8",
                        attn_impl="flash")
    eng.submit(np.arange(6, dtype=np.int32) % cfg.vocab, 4)
    eng.run()

    def tick(params, pools, tok, pos, bt, temp, seeds, idx, prev, ahead):
        return _decode_tick(params, pools, tok, pos, bt, temp, seeds,
                            idx, prev, ahead, cfg=cfg, top_k=0, top_p=0.0)

    s = eng.max_slots
    w = 4
    probe = TargetProbe("serving:decode", None, None, hbm_budget=budget)
    probe.entrypoints = [
        EntryPoint("_decode_tick", tick,
                   (_sds(eng.params), _sds(eng.pools),
                    jax.ShapeDtypeStruct((s,), np.int32),
                    jax.ShapeDtypeStruct((s,), np.int32),
                    jax.ShapeDtypeStruct((s, w), np.int32),
                    jax.ShapeDtypeStruct((s,), np.float32),
                    jax.ShapeDtypeStruct((s,), np.uint32),
                    jax.ShapeDtypeStruct((s,), np.int32),
                    jax.ShapeDtypeStruct((s,), np.int32),
                    jax.ShapeDtypeStruct((s,), np.bool_)),
                   ("params", "pools", "tok", "pos", "bt", "temp",
                    "seeds", "idx", "prev", "ahead")),
    ]
    return probe.seal()


# ----------------------------------------------------------- the registry

TARGET_BUILDERS: dict[str, Callable] = {
    "engine": build_engine,
    "engine:overlap": build_engine_overlap,
    "spmd_pipeline": build_spmd_pipeline,
    "gspmd": build_gspmd,
    "pipeline_lm:gpipe": lambda budget=DEFAULT_BUDGET:
        build_pipeline_lm("gpipe", budget=budget),
    "pipeline_lm:1f1b": lambda budget=DEFAULT_BUDGET:
        build_pipeline_lm("1f1b", budget=budget),
    "pipeline_lm:interleaved": lambda budget=DEFAULT_BUDGET:
        build_pipeline_lm("1f1b", virtual_pp=2, budget=budget),
    "pipeline_lm:zb": lambda budget=DEFAULT_BUDGET:
        build_pipeline_lm("zb", compute_dtype=None, budget=budget),
    "serving": build_serving_decode,
    "fp8_train": build_fp8_train,
}

# CLI aliases: family names expand to their member probes
TARGET_GROUPS: dict[str, tuple] = {
    "pipeline_lm": ("pipeline_lm:gpipe", "pipeline_lm:1f1b",
                    "pipeline_lm:interleaved"),
    "zb": ("pipeline_lm:zb",),
    "all": tuple(TARGET_BUILDERS),
}


def resolve_targets(name: str) -> tuple:
    if name in TARGET_GROUPS:
        return TARGET_GROUPS[name]
    if name in TARGET_BUILDERS:
        return (name,)
    raise SystemExit(
        f"unknown target {name!r}; pick from "
        f"{sorted((*TARGET_BUILDERS, *TARGET_GROUPS))}")
