"""Comm/compute interleaving — bucketed overlapped gradient reduction.

The reference's headline capability is DDP-style interleaving of
communication with computation: it registers a per-parameter hook that
fires an `Iallreduce` the moment a parameter's gradient is final, so
reduction of layer i overlaps the backward of layer i-1
(`/root/reference/shallowspeed/pipe.py:302-327`). Our compiled engines
so far did the naive thing the reference improves on: accumulate the
whole gradient, then reduce — and because the accumulation `lax.scan`
is a single dataflow node, every byte of that reduction is *exposed*
(nothing independent is left to schedule under it).

This module is the compiled-XLA formulation of the same idea, shared by
every engine family:

- **Bucket plans** (`plan_buckets`): partition the grad pytree's leaves,
  in backward-finalization order, into size-targeted buckets
  (`--bucket-mb`). One bucket = one `psum` call over its member
  leaves, issued where the bucket's last gradient is final (jax binds
  one reduction per member; XLA's all-reduce combiner merges
  neighbours), instead of one late bulk reduction.
- **Reduce-on-backward tags** (`reduce_grads_on_backward`): a custom-VJP
  identity whose backward psums a bucket's cotangents over the data
  axes *at the point the bucket's last leaf gradient is produced* —
  inside the autodiff backward, the compiled equivalent of the
  reference's grad hooks. An optional `acc` (the unreduced sum of
  earlier microbatches from a peeled accumulation scan) is folded in
  before the reduction, so total wire bytes match the bulk path
  exactly. Engines with hand-written backwards (the MLP family) place
  the same per-bucket psums directly between layer VJPs
  (`bucketed_stage_backward`).
- **Scatter tags** (`scatter_grads_on_backward`): the ZeRO-2 flavor —
  the backward emits a per-leaf `psum_scatter` over 'dp' (half an
  all-reduce's bytes), embedded at the leaf's local shard slot, so the
  sharded-optimizer path reduces inside the backward too.
- **Exposure accounting** (`collective_exposure`): a dataflow measure
  of how much collective traffic a compiled program can hide — a
  collective is *overlapped* when the same scope contains MXU-heavy
  compute that neither feeds it nor depends on it (exactly what XLA's
  latency-hiding scheduler needs to run them concurrently), *exposed*
  otherwise. `exposed_comm_frac` = exposed bytes / total collective
  bytes; telemetry stamps it on every step line (schema v3) and the
  `overlap-bucket` analysis rule fails a registered program whose
  bucket collectives have no independent compute to hide under.
- **Registry** (`register_program`): engines that build an overlapped
  program record its bucket signatures on the jitted fn; the analysis
  rule then proves every grad-sized dp reduction in the program is a
  registered bucket and that the interleaving dataflow actually exists.

Double-buffered p2p hops (the pipeline-engine side of the same trade —
send the previous tick's activation while computing the current one,
`SPMDPipelineEngine(overlap=...)`) live in `spmd_pipeline.py`; the ring
attention path already carries its hop and its chunk compute as
independent dataflow (`ops/attention.py`), which this module's exposure
accounting now verifies instead of assuming.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.core import Literal

from shallowspeed_tpu.analysis.walker import (COLLECTIVES,
                                              _as_jaxpr, aval_bytes,
                                              collective_axes,
                                              sub_jaxprs)
from shallowspeed_tpu.utils import pvary_over

tree_map = jax.tree_util.tree_map

MiB = float(1 << 20)


# ------------------------------------------------------------ config


@dataclass(frozen=True)
class OverlapConfig:
    """Per-engine comm/compute interleaving knobs.

    bucket_mb: target bucket payload (reference `pipe.py` bucketing
    semantics: a bucket closes when adding the next leaf would exceed
    the target; a single oversized leaf gets its own bucket).
    double_buffer_hops: pipeline engines only — defer each stage hop
    one tick so the `ppermute` of tick t's output overlaps tick t+1's
    compute (costs pp-1 extra warmup/drain ticks, removes the hop from
    the per-tick critical path)."""

    bucket_mb: float = 4.0
    double_buffer_hops: bool = True

    @property
    def bucket_bytes(self) -> int:
        return max(1, int(self.bucket_mb * MiB))


def from_flags(overlap: str, bucket_mb: float) -> OverlapConfig | None:
    """Driver-flag adapter: `--overlap off|on` + `--bucket-mb`."""
    if overlap == "off":
        return None
    return OverlapConfig(bucket_mb=bucket_mb)


# ------------------------------------------------------- bucket plans


def leaf_bytes(leaf) -> int:
    """Payload bytes of one array-ish leaf (arrays, SDS, avals)."""
    shape = getattr(leaf, "shape", ())
    dtype = np.dtype(getattr(leaf, "dtype", np.float32))
    return int(np.prod(shape, dtype=np.int64)) * dtype.itemsize if shape \
        else dtype.itemsize


def plan_buckets(leaves, bucket_bytes: int) -> list[list[int]]:
    """Partition leaf indices into contiguous buckets of at most
    `bucket_bytes` each, IN THE ORDER GIVEN — callers pass leaves in
    backward-finalization order (the last layer's grads are final
    first). Every index lands in exactly one bucket; a single leaf
    larger than the target gets a bucket of its own (the reference's
    bucketing does the same — you cannot split a tensor's allreduce)."""
    buckets: list[list[int]] = []
    cur: list[int] = []
    cur_b = 0
    for i, leaf in enumerate(leaves):
        b = leaf_bytes(leaf)
        if cur and cur_b + b > bucket_bytes:
            buckets.append(cur)
            cur, cur_b = [], 0
        cur.append(i)
        cur_b += b
    if cur:
        buckets.append(cur)
    return buckets


def plan_param_buckets(params, bucket_bytes: int):
    """Bucket plan for a params pytree, in backward-finalization order
    (reversed flatten order — autodiff finalizes the deepest layers'
    cotangents first). Returns (plan, leaves, treedef): `plan` indexes
    into the ORIGINAL flatten order."""
    leaves, tdef = jax.tree_util.tree_flatten(params)
    n = len(leaves)
    rev = plan_buckets(leaves[::-1], bucket_bytes)
    plan = [[n - 1 - j for j in bucket] for bucket in rev]
    return plan, leaves, tdef


# ------------------------------------------- reduce-on-backward tags

# A `pcast(..., to="varying")` whose transpose is ours to place: the
# forward casts params that are INVARIANT over `axes` to varying (what
# shard_map would insert implicitly where they meet per-device data),
# the backward psums each bucket's cotangents back to invariant — the
# type a custom VJP must return for an invariant primal. Applied to the
# params a loss is differentiated against, the transpose runs when ALL
# the bucket's cotangents are final — for a bucket of layer-i leaves,
# right after layer i's backward matmuls, dataflow-independent of the
# backward of layers < i. `acc` (unreduced grads of earlier
# microbatches, from a peeled accumulation scan) is folded in BEFORE
# the psum so wire bytes equal the bulk path's.


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _reduce_tag(axes, leaves, acc):
    return pvary_over(leaves, axes)


def _reduce_tag_fwd(axes, leaves, acc):
    return pvary_over(leaves, axes), acc


def _reduce_tag_bwd(axes, acc, g):
    if acc is not None:
        g = tuple(jnp.add(a, b) for a, b in zip(g, acc))
    g = jax.lax.psum(g, axes)
    zeros = None if acc is None else tuple(jnp.zeros_like(a) for a in acc)
    return (g, zeros)


_reduce_tag.defvjp(_reduce_tag_fwd, _reduce_tag_bwd)


def reduce_grads_on_backward(params, axes, plan, acc=None):
    """Tag `params` (invariant over `axes`) so differentiating through
    the tagged tree reduces each bucket's cotangents over `axes` inside
    the backward. `plan` indexes the tree's flatten order
    (`plan_param_buckets`); leaves not covered by any bucket pass
    through untagged (shard_map's own typing reduces them)."""
    leaves, tdef = jax.tree_util.tree_flatten(params)
    acc_leaves = (None if acc is None
                  else jax.tree_util.tree_flatten(acc)[0])
    out = list(leaves)
    for bucket in plan:
        sub = tuple(leaves[i] for i in bucket)
        sub_acc = (None if acc_leaves is None
                   else tuple(acc_leaves[i] for i in bucket))
        tagged = _reduce_tag(tuple(axes), sub, sub_acc)
        for slot, i in enumerate(bucket):
            out[i] = tagged[slot]
    return jax.tree_util.tree_unflatten(tdef, out)


# ------------------------------------------------------ scatter tags


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _scatter_tag(axis, extra_axes, dims, leaves, acc):
    return pvary_over(leaves, (axis,) + tuple(extra_axes))


def _scatter_tag_fwd(axis, extra_axes, dims, leaves, acc):
    return pvary_over(leaves, (axis,) + tuple(extra_axes)), acc


def _scatter_tag_bwd(axis, extra_axes, dims, acc, g):
    if acc is not None:
        g = tuple(jnp.add(a, b) for a, b in zip(g, acc))
    if extra_axes:
        # e.g. 'sp' in the (dp, sp) context mesh: full-sum the data
        # axes the scatter does not cover (one multi-operand bind)
        g = jax.lax.psum(g, tuple(extra_axes))
    size = jax.lax.psum(1, axis)
    idx = jax.lax.axis_index(axis)
    out = []
    for gl, dim in zip(g, dims):
        if dim is None:
            out.append(jax.lax.psum(gl, axis))
            continue
        shard = jax.lax.psum_scatter(gl, axis, scatter_dimension=dim,
                                     tiled=True)
        # cotangent shape must match the primal: embed the reduced
        # shard at this device's slot (zeros elsewhere); the caller
        # slices it back out after value_and_grad — free data motion,
        # and the reduce-scatter itself ran inside the backward.
        full = jnp.zeros_like(gl)
        start = [0] * gl.ndim
        start[dim] = idx * (gl.shape[dim] // size)
        out.append(jax.lax.dynamic_update_slice(full, shard, start))
    zeros = None if acc is None else tuple(jnp.zeros_like(a) for a in acc)
    return (tuple(out), zeros)


_scatter_tag.defvjp(_scatter_tag_fwd, _scatter_tag_bwd)


def scatter_grads_on_backward(params, axis, dims, plan, acc=None,
                              extra_axes=()):
    """ZeRO-2 flavor of `reduce_grads_on_backward`: each bucket's
    backward emits per-leaf `psum_scatter` over `axis` (dims[i] = the
    leaf's scatter dimension, None = plain psum), after an optional
    full psum over `extra_axes`. The cotangents come back full-shaped
    with the reduced shard embedded at this device's slot — slice with
    `take_local_shard` after `value_and_grad`. Those embedded shards
    differ per device, so a scattered leaf's cotangent is typed varying
    over `axis` and its primal must be too: differentiate against
    `scatter_tag_input(params, axis, dims)`."""
    leaves, tdef = jax.tree_util.tree_flatten(params)
    acc_leaves = (None if acc is None
                  else jax.tree_util.tree_flatten(acc)[0])
    out = list(leaves)
    for bucket in plan:
        sub = tuple(leaves[i] for i in bucket)
        sub_acc = (None if acc_leaves is None
                   else tuple(acc_leaves[i] for i in bucket))
        sub_dims = tuple(dims[i] for i in bucket)
        tagged = _scatter_tag(axis, tuple(extra_axes), sub_dims, sub,
                              sub_acc)
        for slot, i in enumerate(bucket):
            out[i] = tagged[slot]
    return jax.tree_util.tree_unflatten(tdef, out)


def scatter_tag_input(params, axis, dims):
    """`params` typed as `scatter_grads_on_backward` must receive them:
    leaves with a scatter dim varying over `axis` (their cotangent is
    this device's embedded shard), the rest left invariant (plain
    psum)."""
    leaves, tdef = jax.tree_util.tree_flatten(params)
    return jax.tree_util.tree_unflatten(tdef, [
        l if dim is None else pvary_over(l, (axis,))
        for l, dim in zip(leaves, dims)])


def take_local_shard(leaf, dim, axis):
    """Slice this device's shard back out of an embedded-scatter
    cotangent (see `_scatter_tag_bwd`); identity for dim=None."""
    if dim is None:
        return leaf
    size = jax.lax.psum(1, axis)
    idx = jax.lax.axis_index(axis)
    shard = leaf.shape[dim] // size
    start = [0] * leaf.ndim
    start[dim] = idx * shard
    return jax.lax.dynamic_slice(
        leaf, start, [s if d != dim else shard
                      for d, s in enumerate(leaf.shape)])


# ------------------------------------- hand-written-backward variant


class BucketEmitter:
    """Interleaved-reduction bookkeeping for hand-written backwards:
    `add` each leaf's finalized (accumulated) gradient as the layer
    loop produces it; the moment a bucket's leaves are all present,
    ONE multi-operand psum over `axes` is emitted right there in the
    traced program — between that layer's and the next (earlier)
    layer's backward matmuls, so the collective's dataflow is
    independent of the remaining backward."""

    def __init__(self, plan, axes):
        self._remaining = [set(b) for b in plan]
        self._axes = tuple(axes)
        self._pending: dict[int, Any] = {}
        self.reduced: dict[int, Any] = {}

    def add(self, leaf_id: int, val) -> None:
        self._pending[leaf_id] = val
        self._flush()

    def _flush(self) -> None:
        have = set(self._pending)
        for bi, need in enumerate(self._remaining):
            if need and need <= have:
                ids = sorted(need, reverse=True)  # finalization order
                red = jax.lax.psum(
                    tuple(self._pending[i] for i in ids), self._axes)
                for i, r in zip(ids, red):
                    self.reduced[i] = r
                    del self._pending[i]
                self._remaining[bi] = set()

    def done(self) -> dict:
        self._flush()
        assert not self._pending, sorted(self._pending)
        return self.reduced


def bucketed_stage_backward(stage, params, stash, dout, acc, plan,
                            axes):
    """`MLPStage.backward` with the DP reduction interleaved: after
    layer i's (dW, db) are computed and folded into the peeled-scan
    accumulator, every bucket completed so far is psum'd RIGHT THERE —
    between layer i's and layer i-1's backward matmuls in the traced
    program, so each bucket collective is dataflow-independent of the
    remaining backward (the compiled equivalent of the reference's
    per-parameter `Iallreduce` hooks, `pipe.py:302-327`).

    `plan` buckets leaf ids in finalization order, leaf id 2*i = layer
    i's W, 2*i+1 = its b (from `mlp_leaf_order`). Returns the fully
    reduced grads pytree (same structure as `params`)."""
    from shallowspeed_tpu.ops import functional as F

    if stage.is_last_stage:
        head = stash[-1]
        dout = F.mse_loss_grad(head["probs"], dout, stage.batch_size)
        dout = F.softmax_grad(dout, head["logits"])
    n = stage.n_linears
    em = BucketEmitter(plan, axes)
    for i in range(n - 1, -1, -1):
        entry = stash[i]
        if "mask" in entry:
            dout = F.relu_grad(dout, entry["mask"])
        dout, dw, db = F.linear_grad(dout, entry["x"], params[i]["W"])
        em.add(2 * i, acc[i]["W"] + dw)
        em.add(2 * i + 1, acc[i]["b"] + db)
    reduced = em.done()
    return [{"W": reduced[2 * i], "b": reduced[2 * i + 1]}
            for i in range(n)]


def mlp_leaf_order(params) -> list:
    """The MLP family's leaves in backward-finalization order (layer
    n-1 first, W before b within a layer), with leaf id 2*i / 2*i+1 —
    the order `plan_buckets` should see and the id convention
    `bucketed_stage_backward` consumes."""
    order = []
    for i in range(len(params) - 1, -1, -1):
        order.append((2 * i, params[i]["W"]))
        order.append((2 * i + 1, params[i]["b"]))
    return order


# ------------------------------------------------- program registry


def register_program(fn, axis: str, buckets: list, engine: str = "") \
        -> None:
    """Record an overlapped program's bucket layout on its jitted fn:
    `buckets` is a list of signature groups, one per reduction
    collective the program should emit, each a tuple of (shape, dtype
    str) per operand. The `overlap-bucket` analysis rule reads this to
    prove every grad-sized dp reduction in the program is a registered
    bucket and that the interleaving dataflow exists."""
    info = {"axis": axis, "engine": engine,
            "buckets": [tuple(b) for b in buckets]}
    try:
        fn._overlap_info = info
    except AttributeError:  # exotic callables: fall back to a registry
        _FALLBACK.append((fn, info))


_FALLBACK: list = []


def registered(fn):
    info = getattr(fn, "_overlap_info", None)
    if info is not None:
        return info
    for f, i in _FALLBACK:
        if f is fn:
            return i
    return None


def bucket_signature(leaves) -> tuple:
    """Signature group of one reduction collective: the sorted
    (shape, dtype) multiset of its operands."""
    return tuple(sorted(
        (tuple(getattr(l, "shape", ())),
         str(np.dtype(getattr(l, "dtype", np.float32))))
        for l in leaves))


# -------------------------------------------- exposure accounting

# The collectives priced as communication (ppermute is the pipeline/
# ring hop; all_gather is FSDP's param prefetch; pmin/pmax carry only
# scalar statistics and are left out).
COMM_PRIMS = COLLECTIVES - {"pmin", "pmax"}


def _operand_bytes(eqn) -> int:
    return sum(aval_bytes(v.aval) for v in eqn.invars
               if not isinstance(v, Literal))


def _eqn_is_heavy(eqn, cache: dict) -> bool:
    """MXU-heavy: a dot_general/conv, or a sub-jaxpr (scan, jit,
    remat, ...) containing one — the compute a collective can hide
    under."""
    name = eqn.primitive.name
    if name in ("dot_general", "conv_general_dilated"):
        return True
    subs = sub_jaxprs(eqn)
    if not subs:
        return False
    key = id(eqn)
    if key not in cache:
        cache[key] = any(
            _eqn_is_heavy(e, cache)
            for s in subs for e in _as_jaxpr(s).eqns)
    return cache[key]


def _scope_overlap(jaxpr, trips: int, acc: dict, cache: dict,
                   axes_filter=None):
    """One scope's collectives classified overlapped/exposed by
    dataflow: a collective is overlapped when some heavy eqn in the
    SAME scope neither feeds it nor depends on it (XLA's latency-hiding
    scheduler can then run them concurrently); exposed otherwise.
    Conservative across scopes: a collective only overlaps with compute
    it shares a scope with."""
    j = _as_jaxpr(jaxpr)
    eqns = j.eqns
    prod: dict[int, int] = {}
    for i, eqn in enumerate(eqns):
        for v in eqn.outvars:
            prod[id(v)] = i
    anc = [0] * len(eqns)  # ancestor bitsets over eqn indices
    for i, eqn in enumerate(eqns):
        m = 0
        for v in eqn.invars:
            if isinstance(v, Literal):
                continue
            jdx = prod.get(id(v))
            if jdx is not None:
                m |= anc[jdx] | (1 << jdx)
        anc[i] = m
    heavy = [i for i, eqn in enumerate(eqns)
             if _eqn_is_heavy(eqn, cache)]
    for i, eqn in enumerate(eqns):
        name = eqn.primitive.name
        if name in COMM_PRIMS:
            if axes_filter is not None and not (
                    set(collective_axes(eqn)) & set(axes_filter)):
                continue
            nbytes = _operand_bytes(eqn) * trips
            overlappable = any(
                h != i and not (anc[i] >> h) & 1
                and not (anc[h] >> i) & 1 for h in heavy)
            acc["total"] += nbytes
            acc["n"] += 1
            if overlappable:
                acc["overlapped"] += nbytes
                acc["n_overlapped"] += 1
            else:
                acc["exposed"] += nbytes
            continue
        subs = sub_jaxprs(eqn)
        if not subs:
            continue
        t = trips
        if name == "scan":
            n = eqn.params.get("length")
            if n is None:
                acc["approx"] = True
                n = 1
            t = trips * int(n)
        elif name in ("while", "cond"):
            acc["approx"] = True
        for s in subs:
            _scope_overlap(s, t, acc, cache, axes_filter)


def collective_exposure(closed, axes=None) -> dict:
    """Dataflow exposure of one traced program (a ClosedJaxpr):
    per-step collective bytes split into overlapped (independent heavy
    compute exists in the same scope) and exposed. `axes` restricts the
    accounting to collectives touching those mesh axes (None = all).

    Bytes follow `telemetry.collectives`' convention (local operand
    payload × loop trips). `exposed_comm_frac` is None when the program
    has no (matching) collectives — GSPMD-partitioned programs' compiler-
    inserted collectives are invisible at jaxpr level, and a fraction of
    nothing would read as perfect overlap."""
    acc = {"total": 0, "exposed": 0, "overlapped": 0, "n": 0,
           "n_overlapped": 0, "approx": False}
    _scope_overlap(closed.jaxpr, 1, acc, {}, axes)
    frac = (acc["exposed"] / acc["total"]) if acc["total"] else None
    return {
        "total_bytes": acc["total"],
        "exposed_bytes": acc["exposed"],
        "overlapped_bytes": acc["overlapped"],
        "n_collectives": acc["n"],
        "n_overlapped": acc["n_overlapped"],
        "exposed_comm_frac": None if frac is None else round(frac, 6),
        "overlap_ratio": None if frac is None else round(1.0 - frac, 6),
        "approximate": acc["approx"],
    }
