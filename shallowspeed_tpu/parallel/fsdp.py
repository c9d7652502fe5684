"""FSDP / ZeRO-3: parameters, gradients, AND optimizer state sharded
over the data-parallel axis.

ZeRO stage 3 (Rajbhandari et al., 2020) / torch FSDP eliminate all
replicated training state: every rank owns 1/dp of each parameter, gathers
full parameters just-in-time for each layer's compute, re-gathers for the
backward, and reduce-scatters gradients so each rank keeps only its
gradient shard for the (sharded) optimizer update. The reference framework
replicates everything (SURVEY §2: its DP is gradient-all-reduce only,
`/root/reference/shallowspeed/pipe.py:302-327`).

TPU-native formulation: FSDP is a *placement decision*, not a runtime.
Each parameter leaf gets `PartitionSpec('dp' on its largest divisible
dim)`; the batch is sharded over 'dp' as usual; the training step is the
same jitted `(params, opt_state, batch) -> (params, opt_state, loss)`
program as every other GSPMD engine. XLA's SPMD partitioner then inserts
exactly the collective schedule ZeRO-3 hand-codes — all-gather each
weight where the forward/backward needs it full, reduce-scatter each
gradient where the update needs it sharded — and its latency-hiding
scheduler overlaps those collectives with compute. Optimizer moments
inherit the parameter sharding via `zeros_like` (see `GSPMDEngine`), so
the per-device footprint of params + grads + moments is 1/dp with no
extra machinery: ZeRO-1 and ZeRO-2 fall out as strict subsets.
"""

from __future__ import annotations

import jax
from jax.sharding import Mesh, PartitionSpec as P

from shallowspeed_tpu.models import transformer as T
from shallowspeed_tpu.parallel.gspmd import GSPMDEngine

tree_map = jax.tree_util.tree_map


def add_dp(spec: P, shape: tuple, dp: int) -> P:
    """Add 'dp' to the LARGEST dimension not already sharded and divisible
    by dp (the biggest shard-able axis minimizes the number of leaves that
    stay replicated and spreads the big matrices); return the spec
    unchanged if none qualifies (e.g. tiny biases when dp > their length).
    The single placement rule behind both pure FSDP (empty base spec) and
    ZeRO-3-over-TP (`parallel/composite.py`)."""
    entries = list(spec) + [None] * (len(shape) - len(spec))
    candidates = [(d, i) for i, d in enumerate(shape)
                  if entries[i] is None and d and d % dp == 0]
    if not candidates:
        return spec
    _, i = max(candidates)
    entries[i] = "dp"
    return P(*entries)


def fsdp_spec(shape: tuple, dp: int) -> P:
    """Pure-FSDP placement: `add_dp` from a fully replicated base."""
    return add_dp(P(), shape, dp)


class FSDPEngine(GSPMDEngine):
    """Fully-sharded data-parallel trainer for the transformer family.

    Mesh: 1-D `('dp',)` — FSDP is pure data parallelism with sharded
    state. Composes with `compute_dtype=bfloat16` (mixed precision) like
    every transformer engine; `zero1` is meaningless here (the optimizer
    state is already fully sharded) and rejected.

    With `overlap=OverlapConfig(...)` the GSPMD step is replaced by an
    explicit shard_map program (`_build_overlapped`): every sharded
    leaf is `all_gather`'d where the forward needs it full — each
    gather's dataflow depends only on its own shard, so XLA's
    latency-hiding scheduler prefetches layer i+1's params under layer
    i's compute — and autodiff transposes each gather into a
    `reduce_scatter` placed exactly where that leaf's gradient
    finalizes in the backward (grads reduce INSIDE the backward, per
    leaf, instead of GSPMD's after-the-fact resharding). Replicated
    leaves (tiny biases dp cannot divide) reduce through bucketed
    psum-on-backward tags. Same math as the GSPMD step — pinned by
    `tests/test_overlap.py` against it.
    """

    supports_overlap = True

    def __init__(self, cfg: T.TransformerConfig, optimizer, mesh: Mesh,
                 seed: int = 0, zero1: bool = False, zero2: bool = False,
                 health: str = "off", overlap=None):
        if zero1 or zero2:
            raise ValueError(
                "FSDP already shards the optimizer state (ZeRO-3 is a "
                "superset of ZeRO-1/2); drop zero1/zero2")
        super().__init__(cfg, optimizer, mesh, seed=seed, zero1=False,
                         health=health, overlap=overlap)
        if overlap is not None:
            self._build_overlapped(cfg, optimizer, mesh, health, overlap)

    def validate(self, cfg: T.TransformerConfig, mesh: Mesh) -> None:
        assert mesh.axis_names == ("dp",), (
            f"FSDPEngine expects a 1-D ('dp',) mesh, got {mesh.axis_names}")

    def param_specs(self, cfg: T.TransformerConfig) -> dict:
        dp = self.mesh.devices.shape[0]
        # shapes from the host init the base class already built
        return tree_map(lambda a: fsdp_spec(a.shape, dp), self._params_host)

    # ---------------------------------------------- overlapped step

    def _build_overlapped(self, cfg, optimizer, mesh, health, ov):
        """Replace the GSPMD `_step_fn` with the explicit gather/
        reduce-scatter shard_map program (class docstring). Same
        signature, same placements, same executable count — the swap
        is invisible to the driver/telemetry/checkpoint surfaces."""
        import copy
        from functools import partial

        from shallowspeed_tpu.optim import Adafactor
        from shallowspeed_tpu.parallel import overlap as OV
        from jax import shard_map

        if isinstance(optimizer, Adafactor):
            raise ValueError(
                "--overlap fsdp runs the optimizer update on local "
                "shards; Adafactor's factored second moments reduce "
                "over whole matrix dims and need the GSPMD update — "
                "drop --overlap or pick an elementwise optimizer")

        specs = tree_map(lambda l: l.sharding.spec, self.params)
        opt_specs = tree_map(lambda l: l.sharding.spec, self.opt_state)
        leaves, tdef = jax.tree_util.tree_flatten(self.params)
        flat_specs = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, P))
        dims = [next((i for i, ax in enumerate(sp) if ax == "dp"), None)
                for sp in flat_specs]
        dp = self.dp

        # replicated leaves reduce through bucketed psum tags, in
        # backward-finalization order; sharded leaves reduce per-leaf
        # via the gather transpose (one reduce_scatter each)
        repl = [i for i, d in enumerate(dims) if d is None]
        raw = OV.plan_buckets([leaves[i] for i in repl[::-1]],
                              ov.bucket_bytes)
        plan_repl = [[repl[::-1][j] for j in bk] for bk in raw]
        self._bucket_sigs = (
            [OV.bucket_signature([leaves[i] for i in bk])
             for bk in plan_repl]
            + [OV.bucket_signature([leaves[i]])
               for i, d in enumerate(dims) if d is not None])

        opt = copy.copy(optimizer)
        opt.clip_axes = ("dp",)  # shard-local sq-sums need the psum
        health_mode = health
        has_dropout = cfg.dropout > 0.0 or cfg.attn_dropout > 0.0
        seed = getattr(self, "_seed", 0)

        def gather_full(shards):
            ls = jax.tree_util.tree_flatten(shards)[0]
            full = [l if dims[i] is None
                    else jax.lax.all_gather(l, "dp", axis=dims[i],
                                            tiled=True)
                    for i, l in enumerate(ls)]
            tree = jax.tree_util.tree_unflatten(tdef, full)
            return OV.reduce_grads_on_backward(tree, ("dp",), plan_repl)

        def train_key(step):
            if not has_dropout:
                return None
            key = jax.random.fold_in(jax.random.PRNGKey(seed), step)
            # decorrelate masks across the batch shards
            return jax.random.fold_in(key, jax.lax.axis_index("dp"))

        def local_step(params, opt_state, tokens, targets, step):
            def L(p):
                return T.loss(gather_full(p), tokens, targets, cfg,
                              dropout_key=train_key(step))

            loss, grads = jax.value_and_grad(L)(params)
            # local losses are means over B/dp rows: mean-of-means is
            # the global mean, and the summed cotangents carry a dp
            # factor the global gradient does not
            grads = tree_map(lambda g: g / dp, grads)
            loss = jax.lax.pmean(loss, "dp")
            if health_mode == "off":
                new_p, new_s = opt.step(params, grads, opt_state)
                return new_p, new_s, loss
            from shallowspeed_tpu.telemetry.health import (grad_health,
                                                           spec_axes,
                                                           update_health)

            gax = spec_axes(specs)
            pack = grad_health(params, grads, grad_axes=gax,
                               param_axes=gax)
            if health_mode == "guard":
                ok = pack["nonfinite"] == 0
                new_p, new_s = opt.guarded_step(params, grads,
                                                opt_state, ok)
                pack = update_health(pack, params, new_p,
                                     param_axes=gax, skipped=1 - ok)
            else:
                new_p, new_s = opt.step(params, grads, opt_state)
                pack = update_health(pack, params, new_p,
                                     param_axes=gax)
            return new_p, new_s, loss, pack

        step_out = ((specs, opt_specs, P()) if health == "off"
                    else (specs, opt_specs, P(), P()))

        @partial(jax.jit, donate_argnums=(0, 1))
        @partial(shard_map, mesh=mesh,
                 in_specs=(specs, opt_specs, P("dp"), P("dp"), P()),
                 out_specs=step_out)
        def _step(params, opt_state, tokens, targets, step):
            return local_step(params, opt_state, tokens, targets, step)

        self._step_fn = _step
        OV.register_program(_step, "dp", self._bucket_sigs,
                            engine="FSDPEngine")
