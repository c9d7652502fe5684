"""Generic jaxpr walker — the traversal every rule shares.

`iter_eqns` yields every equation of a (closed) jaxpr depth-first,
recursing through EVERY higher-order primitive's sub-jaxprs — jit,
shard_map, scan, while, cond (all branches), remat2/checkpoint,
custom_vjp/jvp calls — without a per-primitive table: any eqn param that
IS (or contains) a Jaxpr/ClosedJaxpr is a sub-jaxpr. Each yield carries

- the equation,
- its provenance path (the chain of enclosing primitive names), and
- the axis environment: mesh axis name -> size for every axis bound by
  an enclosing `shard_map` (read off the eqn's `mesh` param), which is
  what the collective rule checks psum/ppermute axes against.

Also home to the byte accounting (`aval_bytes`) and the static
live-buffer high-water estimator (`peak_bytes`) the memory rule uses.
"""

from __future__ import annotations

from typing import Iterator

import jax
import numpy as np
from jax.extend.core import ClosedJaxpr, Jaxpr, Literal

# Data-moving collective primitives, by the names jax emits under
# shard_map's varying-axes typing (`lax.psum` binds `psum_invariant`,
# `lax.psum_scatter` binds `reduce_scatter`). The one table the static
# rules, the traffic accountant and the overlap exposure model match.
COLLECTIVES = frozenset({
    "psum_invariant", "pmin", "pmax", "ppermute", "pbroadcast",
    "all_gather", "all_gather_invariant", "reduce_scatter", "all_to_all",
    "pgather"})
# the subset that SUMS across devices (gather/permute move bits verbatim)
REDUCE_COLLECTIVES = frozenset({"psum_invariant", "reduce_scatter"})


def collective_axes(eqn) -> tuple:
    """The mesh-axis names a collective (or `axis_index`) eqn runs over
    — jax names the param `axes` or `axis_name` depending on the
    primitive. Positional ints are dropped: they cannot mismatch a
    mesh."""
    axes = eqn.params.get("axes", eqn.params.get("axis_name"))
    if axes is None:
        return ()
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    return tuple(a for a in axes if isinstance(a, str))


def _as_jaxpr(obj):
    """The plain Jaxpr inside `obj` if it is one (closed or not)."""
    if isinstance(obj, ClosedJaxpr):
        return obj.jaxpr
    if isinstance(obj, Jaxpr):
        return obj
    return None


def sub_jaxprs(eqn) -> list:
    """Every sub-jaxpr in this equation's params (cond's `branches`
    tuple, scan/jit/shard_map's `jaxpr`, while's cond/body, ...)."""
    out = []
    for v in eqn.params.values():
        items = v if isinstance(v, (tuple, list)) else (v,)
        for item in items:
            j = _as_jaxpr(item)
            if j is not None:
                out.append(j)
    return out


def _bound_axes(eqn, axis_env: dict) -> dict:
    """The axis environment a `shard_map` eqn's body executes under."""
    mesh = eqn.params.get("mesh")
    if mesh is None:
        return axis_env
    new = dict(axis_env)
    auto = eqn.params.get("auto", frozenset()) or frozenset()
    for name in mesh.axis_names:  # Mesh.shape: OrderedDict name -> size
        if name not in auto:
            new[name] = int(mesh.shape[name])
    return new


def iter_eqns(jaxpr, path: tuple = (),
              axis_env: dict | None = None) -> Iterator[tuple]:
    """Yield (eqn, path, axis_env) for every equation, depth-first.
    `axis_env` maps bound mesh-axis names to sizes at that eqn."""
    j = _as_jaxpr(jaxpr)
    assert j is not None, f"not a jaxpr: {type(jaxpr)}"
    env = dict(axis_env or {})
    for eqn in j.eqns:
        yield eqn, path, env
        child_env = (_bound_axes(eqn, env)
                     if eqn.primitive.name == "shard_map" else env)
        for sub in sub_jaxprs(eqn):
            yield from iter_eqns(sub, path + (eqn.primitive.name,),
                                 child_env)


# ------------------------------------------------------------------ bytes


def aval_bytes(aval) -> int:
    """On-device bytes of one abstract value (0 for non-array avals)."""
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    try:
        item = np.dtype(dtype).itemsize
    except TypeError:
        # jax extended dtypes (typed PRNG keys, `key<fry>`) are not
        # numpy dtypes but expose their physical payload size — the
        # serving decode tick's per-row samplers put them in scope
        item = int(getattr(dtype, "itemsize", 0) or 0)
    return int(np.prod(shape, dtype=np.int64)) * item


def _inner_extra(eqn) -> int | None:
    """EXTRA transient bytes an eqn's sub-jaxprs allocate beyond the
    operands the caller already holds live (max over branches — only
    one cond branch runs; scan iterations reuse one body's
    transients). Subtracting the sub-jaxpr's own inputs is what keeps
    nesting from re-counting the same buffers at every level (jit ->
    shard_map -> scan would otherwise multiply params+opt_state by the
    nesting depth). None when the eqn has no sub-jaxprs."""
    subs = sub_jaxprs(eqn)
    if not subs:
        return None
    extra = 0
    for s in subs:
        j = _as_jaxpr(s)
        inputs = sum(aval_bytes(v.aval)
                     for v in (*j.invars, *j.constvars))
        extra = max(extra, peak_bytes(s) - inputs)
    return max(extra, 0)


def peak_bytes(jaxpr) -> int:
    """Static live-buffer high-water estimate for one jaxpr, in bytes.

    Liveness walk in program order: a var becomes live when defined
    (inputs/consts at entry) and dies after its last textual use; each
    eqn's transient peak is the live set plus its outputs plus the
    deepest sub-jaxpr's own peak. This is an ESTIMATE of what XLA's
    buffer assignment must accommodate, not a simulation of it — no
    fusion, rematerialization, or aliasing — so it upper-bounds
    same-shape executions and is stable across compiler versions, which
    is exactly what a budget gate wants. Donated-input reuse is likewise
    ignored (conservative)."""
    j = _as_jaxpr(jaxpr)
    last_use: dict = {}
    for i, eqn in enumerate(j.eqns):
        for v in eqn.invars:
            if not isinstance(v, Literal):
                last_use[v] = i
    for v in j.outvars:
        if not isinstance(v, Literal):
            last_use[v] = len(j.eqns)

    live = sum(aval_bytes(v.aval) for v in (*j.invars, *j.constvars))
    peak = live
    for i, eqn in enumerate(j.eqns):
        out_b = sum(aval_bytes(v.aval) for v in eqn.outvars)
        extra = _inner_extra(eqn)
        if extra is None:  # leaf eqn: just its outputs
            peak = max(peak, live + out_b)
        elif eqn.primitive.name in ("scan", "while"):
            # stacked/loop outputs accumulate ACROSS iterations while
            # one iteration's body transients are live — additive
            peak = max(peak, live + out_b + extra)
        else:
            # call-like (jit/shard_map/cond/remat): the call's outputs
            # materialize INSIDE the sub-jaxpr, already in its peak
            peak = max(peak, live + max(out_b, extra))
        live += out_b
        # a var dies at its last textual use; outvars never read again
        # (incl. DropVars) die immediately — default their last use to i
        for v in set(v for v in (*eqn.invars, *eqn.outvars)
                     if not isinstance(v, Literal)):
            if last_use.get(v, i) == i:
                live -= aval_bytes(v.aval)
    return peak
