"""Collective traffic accounting — bytes and calls per mesh axis.

Walks the jaxpr of a compiled entrypoint (the SAME traversal
`analysis/walker.py` does for the lint rules) and totals, per mesh
axis, the bytes each collective moves per call of the program plus the
call counts — with loop trip multipliers applied, which the lint walk
does not need: a `psum` inside a `lax.scan` over n_mu microbatches
runs n_mu times per step, and that factor is exactly what a
bytes-per-step number must include.

Byte convention: the LOCAL operand bytes entering the collective
(summed over its array operands), i.e. the per-device payload handed
to the ICI — the number a bandwidth model multiplies by the axis's
algorithm factor. The per-primitive algorithm factors (ring all-gather
moves (n-1)/n * global bytes, etc.) are deliberately NOT applied: the
report states what the program hands the fabric, joined at log points
with measured step time into an implied achieved GB/s.

Trip counts: `scan` multiplies by its `length` param; `while` is
unbounded — counted once and flagged `approximate`; `cond` takes the
max over branches (one branch runs) and flags approximate when
branches differ.
"""

from __future__ import annotations

import jax
from jax.extend.core import Literal

from shallowspeed_tpu.analysis.walker import (COLLECTIVES,
                                              _as_jaxpr, aval_bytes,
                                              collective_axes,
                                              sub_jaxprs)


def _operand_bytes(eqn) -> int:
    return sum(aval_bytes(v.aval) for v in eqn.invars
               if not isinstance(v, Literal))


def _scan_length(eqn) -> int | None:
    n = eqn.params.get("length")
    return int(n) if n is not None else None


def collective_traffic(fn, *args) -> dict:
    """Per-axis collective traffic of one call of `fn(*args)` (args may
    be ShapeDtypeStructs — nothing executes; tracing only).

    Returns {"per_axis": {axis: {"bytes", "calls"}},
             "per_primitive": {prim: {"bytes", "calls"}},
             "total_bytes", "approximate"}.
    Bytes are per device per program call (see module docstring).
    """
    return traffic_of_jaxpr(jax.make_jaxpr(fn)(*args))


def traffic_of_jaxpr(closed) -> dict:
    """`collective_traffic` on an already-traced ClosedJaxpr — callers
    holding one (report.py shares a single trace between this and the
    memory estimate; tracing a big pipeline step costs seconds)."""
    acc_axis: dict[str, dict] = {}
    acc_prim: dict[str, dict] = {}
    state = {"approx": False}

    def add(table, key, nbytes, trips):
        slot = table.setdefault(key, {"bytes": 0, "calls": 0})
        slot["bytes"] += nbytes * trips
        slot["calls"] += trips

    def walk(jaxpr, trips: int):
        j = _as_jaxpr(jaxpr)
        for eqn in j.eqns:
            name = eqn.primitive.name
            if name in COLLECTIVES:
                nbytes = _operand_bytes(eqn)
                axes = collective_axes(eqn) or ("?",)
                for ax in axes:
                    add(acc_axis, ax, nbytes, trips)
                add(acc_prim, name, nbytes, trips)
                continue
            subs = sub_jaxprs(eqn)
            if not subs:
                continue
            if name == "scan":
                n = _scan_length(eqn)
                if n is None:
                    state["approx"] = True
                    n = 1
                for s in subs:
                    walk(s, trips * n)
            elif name == "while":
                state["approx"] = True
                for s in subs:
                    walk(s, trips)
            elif name == "cond":
                # one branch runs: keep the heaviest branch's totals
                # (collective-identical branches — the engines' gated
                # pipeline phases — are exact; otherwise approximate)
                snap_ax = {k: dict(v) for k, v in acc_axis.items()}
                snap_pr = {k: dict(v) for k, v in acc_prim.items()}
                best = None
                totals = []
                for s in subs:
                    trial_ax = {k: dict(v) for k, v in snap_ax.items()}
                    trial_pr = {k: dict(v) for k, v in snap_pr.items()}
                    acc_axis.clear(); acc_axis.update(trial_ax)
                    acc_prim.clear(); acc_prim.update(trial_pr)
                    walk(s, trips)
                    tot = sum(v["bytes"] for v in acc_axis.values())
                    totals.append(tot)
                    if best is None or tot > best[0]:
                        best = (tot,
                                {k: dict(v) for k, v in acc_axis.items()},
                                {k: dict(v) for k, v in acc_prim.items()})
                if len(set(totals)) > 1:
                    state["approx"] = True
                acc_axis.clear(); acc_axis.update(best[1])
                acc_prim.clear(); acc_prim.update(best[2])
            else:
                for s in subs:
                    walk(s, trips)

    walk(closed.jaxpr, 1)
    return {
        "per_axis": {k: dict(v) for k, v in sorted(acc_axis.items())},
        "per_primitive": {k: dict(v)
                          for k, v in sorted(acc_prim.items())},
        # per-primitive sum: a psum over ('dp','sp') is ONE payload (it
        # appears under both axes in per_axis for attribution)
        "total_bytes": sum(v["bytes"] for v in acc_prim.values()),
        "approximate": state["approx"],
    }
