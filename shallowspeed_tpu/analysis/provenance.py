"""Precision-flow abstract interpretation over traced jaxprs.

`walker.iter_eqns` answers "what equations exist"; this module answers
"what does each VALUE carry" — the per-value provenance the quantized-
training rules need. One forward pass over an entrypoint's jaxpr
(recursing through jit / shard_map / scan / while / cond / remat /
custom-vjp sub-jaxprs with explicit environment mapping) assigns every
intermediate a `VInfo`:

- ``round_m``    mantissa width of the narrowest float convert the
  value has crossed since its last rescale (None = never rounded). A
  later narrowing convert COMPOUNDS error only when it drops strictly
  below this width (f32->bf16->fp8), or re-enters a quantized storage
  dtype without a fresh rescale — re-rounding at the same width (the
  ubiquitous bf16 -> f32-arithmetic -> bf16 mixed-precision pattern)
  is a single rounding of a new value and stays silent.
- ``qid``        quantized-storage lineage: which int8/fp8 leaf (input
  param or in-program quantization) these bits come from. Survives
  upcasts and shape ops, breaks at gathers/slices — the same chain
  discipline as the `dequant-fusion` rule.
- ``sids``       scale lineage: which quantization scales this value IS
  (a `Ws` input leaf, a delayed-scaling factor, or a product of them).
- ``applied``    scales already multiplied onto this value's lineage —
  a second application is a double-scaled output.
- ``itv``        a conservative absmax interval (lo, hi), seeded from
  the probe's init/calibration stats (`EntryPoint.ranges`) and scalar
  literals, propagated through interval arithmetic. Only PROVABLE
  violations fire: the pass special-cases `x - max(x)` so softmax's
  shifted exponent is known non-positive.

The quantization-scale pairing has two sources:

1. input leaves: any dict with both ``Wq`` and ``Ws`` keys (the
   `models.transformer.quantize_weights` layout) pairs the quantized
   leaf with its scale leaf;
2. in-program quantization: ``(x / s)`` (s scale-like: rank <= 1 or
   broadcast-inflated) followed by a narrowing convert to an int8/fp8
   dtype creates a fresh quantized lineage paired to ``s``.

Every `dot_general` consuming a paired quantized lineage must see its
scale exactly once — pre-applied on the operand, riding the OTHER
operand (the transpose/VJP form: cotangent scaled before the dot), or
multiplied onto the accumulator afterwards. Unresolved or doubled
applications surface as `DotUse`/events for the scale-consistency rule.

The pass is deliberately conservative: unknown primitives produce
unknown `VInfo`s, loop carries drop their intervals (no fixpoint), and
call-like primitives whose invar layout the pass cannot map seed an
empty environment — rules built on top only fire on facts the flow
actually proved.
"""

from __future__ import annotations

import itertools
import math
from dataclasses import dataclass, field, replace

import jax
import numpy as np
from jax.extend.core import Literal

from shallowspeed_tpu.analysis.walker import (REDUCE_COLLECTIVES, _as_jaxpr,
                                              collective_axes)

# quantized-storage dtypes (same set the dequant-fusion rule uses)
QUANT_DTYPES = {"int8", "uint8", "float8_e4m3fn", "float8_e5m2"}

# shape ops that preserve the full value set (lineage AND interval)
_SHAPE_OPS = {"reshape", "transpose", "broadcast_in_dim", "squeeze",
              "copy", "stop_gradient", "rev", "expand_dims"}

# ops that SELECT a subset of elements: interval/rounded survive,
# quant/scale lineage breaks (matching dequant-fusion's gather rule)
_SELECT_OPS = {"gather", "slice", "dynamic_slice", "take",
               "dynamic_update_slice", "scatter", "concatenate",
               "select_n", "pad"}

_MANTISSA = {"float64": 52, "float32": 23, "bfloat16": 7, "float16": 10,
             "float8_e4m3fn": 3, "float8_e5m2": 2}
_M2DT = {m: dt for dt, m in _MANTISSA.items()}


def _min_rm(*infos):
    """Combine rounding states: the result may carry any operand's
    rounding, so keep the narrowest (min mantissa) that is set."""
    rms = [i.round_m for i in infos if i.round_m is not None]
    return min(rms) if rms else None


def _dt(x) -> str | None:
    d = getattr(getattr(x, "aval", x), "dtype", None)
    if d is None:
        return None
    try:
        return str(np.dtype(d))
    except TypeError:
        # jax extended dtypes (typed PRNG keys, `key<fry>`)
        return str(d)


def _is_float(dt: str | None) -> bool:
    return dt is not None and (dt.startswith("float")
                               or dt.startswith("bfloat"))


def _narrowing(src: str | None, dst: str | None) -> bool:
    """float->float convert that DROPS mantissa bits (a rounding)."""
    return (src in _MANTISSA and dst in _MANTISSA
            and _MANTISSA[dst] < _MANTISSA[src])


def finfo_max(dt: str) -> float:
    import ml_dtypes
    try:
        return float(ml_dtypes.finfo(dt).max)
    except Exception:
        return math.inf


def _size(v) -> int:
    shape = getattr(getattr(v, "aval", v), "shape", None)
    if shape is None:
        return 0
    return int(np.prod(shape, dtype=np.int64))


def _scale_shape(v) -> bool:
    """Structurally a scale: at most one non-1 dim ((,), (N,), (1, N),
    (N, 1), ...) — checked at USE time so a scale stays a scale no
    matter which reductions/clamps produced it."""
    shape = getattr(getattr(v, "aval", v), "shape", None)
    if shape is None:
        return False
    return sum(1 for d in shape if d != 1) <= 1


@dataclass(frozen=True)
class VInfo:
    """Abstract value: everything the precision rules need to know
    about one jaxpr var. Frozen — propagation builds new ones."""
    dtype: str | None = None
    round_m: int | None = None        # mantissa of narrowest rounding
    qid: int | None = None            # quantized-storage lineage
    sids: frozenset = frozenset()     # scale identities this value IS
    applied: frozenset = frozenset()  # scale ids applied on this lineage
    pending: frozenset = frozenset()  # DotUse indices awaiting a scale
    itv: tuple | None = None          # (lo, hi) proven element bounds
    scale_like: bool = False          # rank<=1 or broadcast-inflated
    maxof: object = None              # var this is the reduce_max of
    div_sid: int | None = None        # scale id of the last rescale div


_UNKNOWN = VInfo()


@dataclass
class QuantLeaf:
    qid: int
    label: str          # human name: arg/leaf path or trace site
    sid: int | None     # the paired scale identity (None = unpaired)
    dtype: str = ""
    shape: tuple = ()


@dataclass
class DotUse:
    """One dot_general consuming a paired quantized lineage."""
    qid: int
    label: str
    path: tuple
    shape: tuple
    resolved: bool = False
    how: str = ""       # pre-applied | cotangent-scaled | accumulator


@dataclass
class Event:
    kind: str           # double-round | dot | carry-accum | range | ...
    path: tuple
    data: dict = field(default_factory=dict)


@dataclass
class FlowResult:
    events: list = field(default_factory=list)
    dot_uses: list = field(default_factory=list)
    quants: dict = field(default_factory=dict)   # qid -> QuantLeaf


# ------------------------------------------------------------- intervals


def _itv_add(a, b):
    return None if a is None or b is None else (a[0] + b[0], a[1] + b[1])


def _itv_sub(a, b):
    return None if a is None or b is None else (a[0] - b[1], a[1] - b[0])


def _itv_mul(a, b):
    if a is None or b is None:
        return None
    with np.errstate(invalid="ignore"):
        ps = [a[i] * b[j] for i in (0, 1) for j in (0, 1)]
    ps = [0.0 if p != p else p for p in ps]  # 0*inf -> treat as 0
    return (min(ps), max(ps))


def _itv_div(a, b):
    if a is None or b is None or (b[0] <= 0.0 <= b[1]):
        return None
    return _itv_mul(a, (1.0 / b[1], 1.0 / b[0]))


def _itv_join(a, b):
    return None if a is None or b is None else (min(a[0], b[0]),
                                                max(a[1], b[1]))


def _amax(itv) -> float:
    return max(abs(itv[0]), abs(itv[1]))


def _mono(fn, itv):
    """Interval image of a monotone-increasing scalar fn, inf-safe."""
    def safe(x):
        try:
            return fn(x)
        except OverflowError:
            return math.inf
        except ValueError:
            return -math.inf
    return (safe(itv[0]), safe(itv[1]))


# -------------------------------------------------------------- the pass


class _Flow:
    def __init__(self):
        self.res = FlowResult()
        self._qids = itertools.count()
        self._sids = itertools.count()
        self._budget = 200_000  # eqn visits; huge jaxprs stay linear
        self._made_by: dict = {}  # var -> producing eqn (all scopes)

    # -- identity allocation ------------------------------------------

    def new_quant(self, label, sid, dtype="", shape=()) -> int:
        qid = next(self._qids)
        self.res.quants[qid] = QuantLeaf(qid, label, sid, dtype, shape)
        return qid

    def new_sid(self) -> int:
        return next(self._sids)

    def event(self, kind, path, **data):
        self.res.events.append(Event(kind, path, data))

    # -- environment helpers ------------------------------------------

    def info_of(self, env, atom) -> VInfo:
        if isinstance(atom, Literal):
            val = atom.val
            itv = None
            if np.ndim(val) == 0 and _is_float(_dt(atom)):
                f = float(val)
                if math.isfinite(f):
                    itv = (f, f)
            return VInfo(dtype=_dt(atom), itv=itv,
                         scale_like=np.ndim(val) == 0)
        got = env.get(atom)
        if got is not None:
            return got
        rank = len(getattr(atom.aval, "shape", ()))
        return VInfo(dtype=_dt(atom), scale_like=rank <= 1)

    def default_out(self, v) -> VInfo:
        rank = len(getattr(v.aval, "shape", ()))
        return VInfo(dtype=_dt(v), scale_like=rank <= 1)

    # -- sub-jaxpr mapping --------------------------------------------

    def _drop_loopy(self, info: VInfo) -> VInfo:
        """A loop-carried value's interval/max-tag is only valid for
        iteration 0 — drop what grows, keep storage lineage."""
        return replace(info, itv=None, maxof=None,
                       pending=frozenset())

    def run_call(self, eqn, env, path, axis_env):
        """Generic call-like primitive: map infos 1:1 when the invar
        layouts line up, interpret, map outs back. Anything the pass
        cannot map (pallas_call grids, scatter-prefetch layouts) is
        interpreted with an EMPTY seed — events still surface, lineage
        doesn't cross the boundary."""
        name = eqn.primitive.name
        subs = [s for s in (_as_jaxpr(p) for p in _sub_params(eqn))
                if s is not None]
        if not subs:
            return False
        in_infos = [self.info_of(env, v) for v in eqn.invars]

        if name == "scan":
            body = subs[0]
            nc = eqn.params.get("num_consts", 0)
            ncar = eqn.params.get("num_carry", 0)
            seeds = list(in_infos)
            for i in range(nc, nc + ncar):
                seeds[i] = self._drop_loopy(seeds[i])
            sub_env = dict(zip(body.invars, seeds))
            self.interp(body, sub_env, path + (name,), axis_env)
            self._check_carries(body, sub_env,
                                body.invars[nc:nc + ncar],
                                body.outvars[:ncar], path, "scan")
            outs = [self.info_of(sub_env, v) for v in body.outvars]
            for i in range(min(ncar, len(outs))):
                outs[i] = self._drop_loopy(outs[i])
            for v, info in zip(eqn.outvars, outs):
                env[v] = replace(info, dtype=_dt(v))
            return True

        if name == "while":
            cn = eqn.params.get("cond_nconsts", 0)
            bn = eqn.params.get("body_nconsts", 0)
            cond_j = _as_jaxpr(eqn.params.get("cond_jaxpr"))
            body_j = _as_jaxpr(eqn.params.get("body_jaxpr"))
            carry = [self._drop_loopy(i)
                     for i in in_infos[cn + bn:]]
            if cond_j is not None:
                self.interp(cond_j, dict(zip(
                    cond_j.invars, in_infos[:cn] + carry)),
                    path + (name,), axis_env)
            if body_j is not None:
                sub_env = dict(zip(
                    body_j.invars, in_infos[cn:cn + bn] + carry))
                self.interp(body_j, sub_env, path + (name,), axis_env)
                self._check_carries(
                    body_j, sub_env, body_j.invars[bn:],
                    body_j.outvars, path, "while")
                outs = [self._drop_loopy(self.info_of(sub_env, v))
                        for v in body_j.outvars]
                for v, info in zip(eqn.outvars, outs):
                    env[v] = replace(info, dtype=_dt(v))
            return True

        if name == "cond":
            branch_outs = []
            for b in subs:
                seeds = in_infos[1:]
                if len(b.invars) != len(seeds):
                    seeds = [_UNKNOWN] * len(b.invars)
                sub_env = dict(zip(b.invars, seeds))
                self.interp(b, sub_env, path + (name,), axis_env)
                branch_outs.append(
                    [self.info_of(sub_env, v) for v in b.outvars])
            for i, v in enumerate(eqn.outvars):
                infos = [bo[i] for bo in branch_outs if i < len(bo)]
                env[v] = _join_infos(infos, _dt(v))
            return True

        # jit / closed_call / remat2 / custom_jvp_call /
        # custom_vjp_call(_jaxpr) / shard_map / ...: 1:1 when mappable
        new_axes = dict(axis_env)
        if name == "shard_map":
            mesh = eqn.params.get("mesh")
            auto = eqn.params.get("auto", frozenset()) or frozenset()
            if mesh is not None:
                for ax in mesh.axis_names:
                    if ax not in auto:
                        new_axes[ax] = int(mesh.shape[ax])
        body = subs[0]
        seeds = (in_infos if len(body.invars) == len(eqn.invars)
                 else [_UNKNOWN] * len(body.invars))
        sub_env = dict(zip(body.invars, seeds))
        self.interp(body, sub_env, path + (name,), new_axes)
        if len(body.outvars) == len(eqn.outvars):
            for v, bv in zip(eqn.outvars, body.outvars):
                env[v] = replace(self.info_of(sub_env, bv),
                                 dtype=_dt(v))
        else:
            for v in eqn.outvars:
                env[v] = self.default_out(v)
        # remaining subs (cond already handled): events only
        for extra in subs[1:]:
            self.interp(extra, {}, path + (name,), new_axes)
        return True

    def _check_carries(self, body, sub_env, carry_in, carry_out,
                       path, prim):
        """A loop carry whose out is `carry_in + contribution`, with
        the contribution NOT derived from the carry, is an ACCUMULATOR
        — it must carry f32 (the accumulation-dtype rule's loop half;
        the peeled-microbatch grad sums live here). The independence
        check is what keeps bf16 residual streams (`x + f(x)`, where
        f(x) depends on the carry) from being misread as accumulators:
        those re-round every iteration by construction and are the
        documented mixed-precision activation path, not a sum."""
        made_by = {}
        for eqn in body.eqns:
            for v in eqn.outvars:
                made_by[v] = eqn
        # forward dependency sweep: which carries does each var depend on
        deps: dict = {id(ci): {i} for i, ci in enumerate(carry_in)}
        for eqn in body.eqns:
            d: set = set()
            for v in eqn.invars:
                if not isinstance(v, Literal):
                    d |= deps.get(id(v), set())
            for v in eqn.outvars:
                deps[id(v)] = d
        for i, (ci, co) in enumerate(zip(carry_in, carry_out)):
            dt = _dt(co)
            if not _is_float(dt) or dt in ("float32", "float64"):
                continue
            eqn = made_by.get(co)
            # look through a trailing convert
            if eqn is not None and eqn.primitive.name \
                    == "convert_element_type":
                eqn = made_by.get(eqn.invars[0])
            if eqn is None or eqn.primitive.name not in ("add",
                                                         "add_any"):
                continue
            sides = eqn.invars
            direct = [v for v in sides
                      if _strips_to(v, ci, made_by)]
            others = [v for v in sides if v not in direct]
            if not direct or any(
                    i in deps.get(id(v), set()) for v in others):
                continue
            self.event("carry-accum", path, prim=prim, dtype=dt,
                       shape=tuple(getattr(co.aval, "shape", ())))

    # -- equation dispatch --------------------------------------------

    def interp(self, jaxpr, env, path=(), axis_env=None):
        j = _as_jaxpr(jaxpr)
        axis_env = axis_env or {}
        for cv in getattr(j, "constvars", ()):
            env.setdefault(cv, self.default_out(cv))
        for eqn in j.eqns:
            if self._budget <= 0:
                return
            self._budget -= 1
            for v in eqn.outvars:
                self._made_by[v] = eqn
            if self.run_call(eqn, env, path, axis_env):
                continue
            self.eqn(eqn, env, path, axis_env)

    def eqn(self, eqn, env, path, axis_env):
        name = eqn.primitive.name
        ins = [self.info_of(env, v) for v in eqn.invars]
        out = eqn.outvars[0] if eqn.outvars else None

        def put(info: VInfo):
            if out is not None:
                env[out] = replace(info, dtype=_dt(out))
            for extra in eqn.outvars[1:]:
                env[extra] = self.default_out(extra)

        if name == "convert_element_type":
            put(self.convert(eqn, ins[0], path))
            return
        if name in _SHAPE_OPS:
            info = ins[0]
            if name == "broadcast_in_dim" and out is not None:
                if _size(eqn.invars[0]) * 8 <= _size(out) \
                        or _size(eqn.invars[0]) <= 1:
                    info = replace(info, scale_like=True)
            put(info)
            return
        if name in _SELECT_OPS:
            if name in ("select_n", "concatenate",
                        "dynamic_update_slice", "scatter", "pad"):
                lo_i = 1 if name == "select_n" else 0
                vals = [i for i, v in zip(ins[lo_i:],
                                          eqn.invars[lo_i:])
                        if _is_float(_dt(v))]
                itv = vals[0].itv if vals else None
                for i in vals:
                    itv = _itv_join(itv, i.itv)
                put(VInfo(round_m=_min_rm(*vals), itv=itv))
            else:
                put(replace(ins[0], qid=None, sids=frozenset(),
                            applied=frozenset(), pending=frozenset(),
                            maxof=None))
            return
        if name == "clamp":
            lo, x, hi = ins[0], ins[1], ins[2]
            itv = x.itv
            if lo.itv is not None and hi.itv is not None:
                itv = (lo.itv[0],
                       hi.itv[1]) if itv is None else (
                    max(itv[0], lo.itv[0]), min(itv[1], hi.itv[1]))
            put(replace(x, itv=itv, maxof=None))
            return
        if name == "dot_general":
            put(self.dot(eqn, ins[0], ins[1], path))
            return
        if name in ("add", "add_any", "sub"):
            put(self.addsub(eqn, name, ins, path))
            return
        if name in ("mul", "div"):
            put(self.muldiv(eqn, name, ins, path, env))
            return
        if name in ("max", "min"):
            a, b = ins[0], ins[1]
            carrier = a if _size(eqn.invars[0]) >= _size(
                eqn.invars[1]) else b
            itv = None
            if a.itv and b.itv:
                f = max if name == "max" else min
                itv = (f(a.itv[0], b.itv[0]), f(a.itv[1], b.itv[1]))
            elif name == "max" and (a.itv or b.itv):
                # max(x, c) is bounded below by c even if x is unknown
                known = a.itv or b.itv
                itv = (known[0], math.inf)
                itv = None if not math.isfinite(known[0]) else itv
            # `max` can only RAISE the subtrahend, so the x - max(x)
            # <= 0 proof survives a floor (softmax's `max -inf m`);
            # `min` could lower it, which would break the bound
            tag = (a.maxof or b.maxof) if name == "max" else None
            put(replace(carrier, itv=itv, maxof=tag))
            return
        if name in ("reduce_sum", "cumsum"):
            n = max(_size(eqn.invars[0]) // max(_size(out), 1), 1)
            itv = (_itv_mul(ins[0].itv, (n, n))
                   if ins[0].itv else None)
            put(VInfo(itv=itv))
            return
        if name in ("reduce_max", "reduce_min", "argmax", "argmin",
                    "cummax", "cummin"):
            tag = eqn.invars[0] if name == "reduce_max" else None
            put(VInfo(itv=ins[0].itv, maxof=tag,
                      round_m=ins[0].round_m))
            return
        if name in REDUCE_COLLECTIVES:
            n = 1
            for ax in collective_axes(eqn):
                n *= axis_env.get(ax, 1)
            put(VInfo(itv=_itv_mul(ins[0].itv, (n, n))
                      if ins[0].itv else None,
                      round_m=ins[0].round_m))
            return
        if name in _UNARY_ITV:
            put(self.unary(eqn, name, ins[0], path))
            return
        if name == "integer_pow":
            y = eqn.params.get("y", 2)
            itv = None
            src = ins[0].itv
            if src is not None and (
                    y >= 0 or not src[0] <= 0.0 <= src[1]):
                try:
                    cands = [float(src[0]) ** y, float(src[1]) ** y]
                except (OverflowError, ZeroDivisionError):
                    cands = None
                if cands is not None:
                    if y % 2 == 0 and src[0] <= 0 <= src[1]:
                        cands.append(0.0)
                    itv = (min(cands), max(cands))
            put(VInfo(itv=itv, sids=ins[0].sids,
                      scale_like=ins[0].scale_like))
            return
        if out is not None:
            put(self.default_out(out))
        for extra in eqn.outvars[1:]:
            env[extra] = self.default_out(extra)

    # -- primitive semantics ------------------------------------------

    def convert(self, eqn, src: VInfo, path) -> VInfo:
        sdt, ddt = _dt(eqn.invars[0]), _dt(eqn.outvars[0])
        info = src
        if _narrowing(sdt, ddt):
            m = _MANTISSA[ddt]
            # compounds only when this rounding drops STRICTLY below
            # the value's previous rounding width, or re-enters a
            # quantized storage dtype with no fresh rescale; re-rounding
            # at the same width (bf16 -> f32 arithmetic -> bf16) is one
            # rounding of a new value
            if src.round_m is not None and (
                    m < src.round_m or ddt in QUANT_DTYPES):
                self.event(
                    "double-round", path, src=sdt, dst=ddt,
                    first=_M2DT.get(src.round_m, str(src.round_m)),
                    shape=tuple(getattr(eqn.outvars[0].aval, "shape",
                                        ())),
                    origin=("storage" if src.qid is not None
                            else "compute"))
            if src.itv is not None:
                lim = finfo_max(ddt)
                if _amax(src.itv) > lim:
                    self.event(
                        "range", path, op="convert", dst=ddt,
                        itv=src.itv, bound=lim,
                        problem="overflow",
                        shape=tuple(getattr(eqn.outvars[0].aval,
                                            "shape", ())))
            info = replace(info, round_m=m if src.round_m is None
                           else min(m, src.round_m))
        if ddt in QUANT_DTYPES and src.div_sid is not None:
            # in-program quantization: (x / s) rounded into quantized
            # storage — fresh lineage paired to s
            qid = self.new_quant(
                f"traced quant @{'/'.join(path) or 'top'}",
                src.div_sid, ddt,
                tuple(getattr(eqn.outvars[0].aval, "shape", ())))
            info = replace(info, qid=qid,
                           round_m=_MANTISSA.get(ddt, 0))
        return replace(info, div_sid=src.div_sid)

    def addsub(self, eqn, name, ins, path) -> VInfo:
        a, b = ins[0], ins[1]
        if name == "sub" and b.maxof is not None \
                and b.maxof is eqn.invars[0]:
            # x - max(x) (possibly floored): provably <= 0 — the
            # softmax/logsumexp shift. Only the upper bound is claimed;
            # a floor on the max makes the result MORE negative.
            return VInfo(round_m=a.round_m, itv=(-math.inf, 0.0))
        itv = _itv_add(a.itv, b.itv) if name != "sub" \
            else _itv_sub(a.itv, b.itv)
        return VInfo(round_m=_min_rm(a, b), itv=itv,
                     applied=a.applied | b.applied,
                     pending=a.pending | b.pending)

    def muldiv(self, eqn, name, ins, path, env) -> VInfo:
        a, b = ins[0], ins[1]
        itv = _itv_mul(a.itv, b.itv) if name == "mul" \
            else _itv_div(a.itv, b.itv)
        if a.sids and b.sids:   # product of scales is a scale
            return VInfo(itv=itv, sids=a.sids | b.sids,
                         scale_like=a.scale_like and b.scale_like)
        # orient: `val` is the data side, `sc` the (possible) scale side
        val, sc = (a, b) if not a.sids else (b, a)
        sc_sl = sc.scale_like or _scale_shape(
            eqn.invars[1 if sc is b else 0])
        out = VInfo(round_m=val.round_m, itv=itv, qid=val.qid,
                    applied=val.applied, pending=val.pending,
                    div_sid=val.div_sid)
        if sc_sl:
            # a rescale: the value's rounding no longer compounds
            out = replace(out, round_m=None)
        if name == "div" and sc is b and sc_sl:
            # quantizing rescale `x / s`: lazily make `s` a scale
            # identity so a following narrowing convert pairs to it
            # and the later dequant multiply by (a product with) `s`
            # resolves the pairing
            sid = next(iter(sc.sids), None)
            if sid is None:
                sid = self.new_sid()
                self._tag_scale_chain(eqn.invars[1], sid, env)
            out = replace(out, div_sid=sid)
        if sc.sids:
            hit = frozenset(s for s in sc.sids if s in val.applied)
            if hit:
                self.event("double-scale", path,
                           labels=self._sid_labels(hit))
            resolved = frozenset(
                i for i in val.pending
                if self.res.dot_uses[i].qid in self.res.quants
                and self.res.quants[
                    self.res.dot_uses[i].qid].sid in sc.sids)
            for i in resolved:
                self.res.dot_uses[i].resolved = True
                self.res.dot_uses[i].how = "accumulator"
            out = replace(out, pending=out.pending - resolved,
                          applied=out.applied | sc.sids)
        return out

    def _tag_scale_chain(self, var, sid, env, depth=8):
        """Attach a fresh scale identity to a divisor var AND its
        shape/convert ancestors, so any later value derived from the
        same scale (the dequant multiply's operand) carries the sid."""
        while depth and not isinstance(var, Literal):
            info = env.get(var) or VInfo(dtype=_dt(var))
            env[var] = replace(info, sids=info.sids | {sid},
                               scale_like=True)
            eqn = self._made_by.get(var)
            if eqn is None or eqn.primitive.name not in (
                    "convert_element_type", *_SHAPE_OPS):
                return
            var = eqn.invars[0]
            depth -= 1

    def _sid_labels(self, sids) -> tuple:
        names = []
        for q in self.res.quants.values():
            if q.sid in sids:
                names.append(q.label)
        return tuple(names) or tuple(sorted(sids))

    def dot(self, eqn, lhs: VInfo, rhs: VInfo, path) -> VInfo:
        odt = _dt(eqn.outvars[0])
        ldt, rdt = _dt(eqn.invars[0]), _dt(eqn.invars[1])
        (lc, _), _ = eqn.params["dimension_numbers"]
        lshape = getattr(eqn.invars[0].aval, "shape", ())
        k = int(np.prod([lshape[i] for i in lc], dtype=np.int64)) or 1
        itv = None
        if lhs.itv is not None and rhs.itv is not None:
            bound = k * _amax(lhs.itv) * _amax(rhs.itv)
            itv = (-bound, bound)
        self.event(
            "dot", path, out_dtype=odt, in_dtypes=(ldt, rdt),
            quant=(lhs.qid is not None or rhs.qid is not None
                   or ldt in QUANT_DTYPES or rdt in QUANT_DTYPES),
            shape=tuple(getattr(eqn.outvars[0].aval, "shape", ())),
            k=k)
        pending = set()
        applied = lhs.applied | rhs.applied
        for me, other, var in ((lhs, rhs, eqn.invars[0]),
                               (rhs, lhs, eqn.invars[1])):
            if me.qid is None:
                continue
            leaf = self.res.quants.get(me.qid)
            if leaf is None or leaf.sid is None:
                continue
            use = DotUse(me.qid, leaf.label, path,
                         tuple(getattr(var.aval, "shape", ())))
            if leaf.sid in me.applied:
                use.resolved, use.how = True, "pre-applied"
            elif leaf.sid in other.sids or leaf.sid in other.applied:
                # VJP form: the cotangent arrives pre-multiplied by
                # the scale, so the product is correctly scaled
                use.resolved, use.how = True, "cotangent-scaled"
                applied = applied | {leaf.sid}
            self.res.dot_uses.append(use)
            if not use.resolved:
                pending.add(len(self.res.dot_uses) - 1)
        return VInfo(itv=itv, pending=frozenset(pending),
                     applied=frozenset(applied))

    def unary(self, eqn, name, src: VInfo, path) -> VInfo:
        odt = _dt(eqn.outvars[0])
        fn, lo_cap, hi_cap = _UNARY_ITV[name]
        itv = None
        if src.itv is not None:
            if name == "exp":
                lim = finfo_max(odt) if odt else math.inf
                if math.isfinite(src.itv[1]) \
                        and src.itv[1] > math.log(lim):
                    self.event("range", path, op=name, itv=src.itv,
                               bound=lim, dst=odt, problem="overflow")
                tiny = _finfo_tiny(odt)
                if tiny > 0.0 and math.isfinite(src.itv[1]) \
                        and src.itv[1] < math.log(tiny):
                    self.event("range", path, op=name, itv=src.itv,
                               bound=tiny, dst=odt,
                               problem="underflow")
            if name in ("log", "log1p", "rsqrt", "sqrt"):
                shift = -1.0 if name == "log1p" else 0.0
                needs_pos = name in ("log", "rsqrt")
                bad = (src.itv[1] <= shift if needs_pos
                       else src.itv[1] < shift)
                if bad:
                    self.event("range", path, op=name, itv=src.itv,
                               dst=odt, problem="domain",
                               bound=shift)
            if name == "neg":
                itv = (-src.itv[1], -src.itv[0])
            elif name == "abs":
                itv = (0.0 if src.itv[0] <= 0 <= src.itv[1]
                       else min(abs(src.itv[0]), abs(src.itv[1])),
                       _amax(src.itv))
            else:
                itv = _mono(fn, src.itv)
            if itv is not None and (itv[0] != itv[0]
                                    or itv[1] != itv[1]):
                itv = None
        if itv is None and lo_cap is not None:
            itv = (lo_cap, hi_cap)
        elif itv is not None and lo_cap is not None:
            itv = (max(itv[0], lo_cap), min(itv[1], hi_cap))
        keep_lineage = name in ("neg", "abs", "round", "floor",
                                "ceil")
        return VInfo(
            itv=itv,
            round_m=src.round_m if name in ("neg", "abs") else None,
            qid=src.qid if keep_lineage else None,
            sids=src.sids if keep_lineage else frozenset(),
            div_sid=src.div_sid if keep_lineage else None,
            scale_like=src.scale_like)


def _finfo_tiny(dt) -> float:
    import ml_dtypes
    try:
        return float(ml_dtypes.finfo(dt).tiny)
    except Exception:
        return 0.0


# monotone/caps table: name -> (pointwise fn, lo cap, hi cap)
_UNARY_ITV = {
    "exp": (math.exp, None, None),
    "log": (lambda x: math.log(x) if x > 0 else -math.inf, None, None),
    "log1p": (lambda x: math.log1p(x) if x > -1 else -math.inf,
              None, None),
    "sqrt": (lambda x: math.sqrt(max(x, 0.0)), None, None),
    "rsqrt": (lambda x: 1.0 / math.sqrt(x) if x > 0 else math.inf,
              None, None),
    "tanh": (math.tanh, -1.0, 1.0),
    "logistic": (lambda x: 1.0 / (1.0 + math.exp(-min(max(x, -700),
                                                      700))),
                 0.0, 1.0),
    "erf": (math.erf, -1.0, 1.0),
    "neg": (lambda x: x, None, None),   # negated inline in unary()
    "abs": (abs, None, None),           # computed inline in unary()
    "sin": (lambda x: x, -1.0, 1.0),
    "cos": (lambda x: x, -1.0, 1.0),
    "floor": (math.floor, None, None),
    "ceil": (math.ceil, None, None),
    "round": (lambda x: float(round(x)), None, None),
    "sign": (lambda x: float(np.sign(x)), -1.0, 1.0),
    "exp2": (lambda x: 2.0 ** min(x, 10000.0), None, None),
}


def _sub_params(eqn):
    out = []
    for v in eqn.params.values():
        items = v if isinstance(v, (tuple, list)) else (v,)
        out.extend(items)
    return out


def _join_infos(infos, dtype) -> VInfo:
    """Least-upper-bound over cond branches: interval union, rounding
    OR, lineage kept only when every branch agrees."""
    if not infos:
        return VInfo(dtype=dtype)
    itv = infos[0].itv
    qid = infos[0].qid
    sids = infos[0].sids
    for i in infos:
        itv = _itv_join(itv, i.itv)
        qid = qid if i.qid == qid else None
        sids = sids if i.sids == sids else frozenset()
    return VInfo(dtype=dtype, round_m=_min_rm(*infos), qid=qid,
                 sids=sids, itv=itv)


def _strips_to(v, target, made_by, depth=8) -> bool:
    """`v` IS `target` modulo converts/shape ops (the direct-carry side
    of an accumulator add)."""
    while depth:
        if v is target:
            return True
        eqn = made_by.get(v)
        if eqn is None or eqn.primitive.name not in (
                "convert_element_type", *_SHAPE_OPS):
            return False
        v = eqn.invars[0]
        depth -= 1
    return False


# ----------------------------------------------------------- seeding


def seed_entrypoint(ep) -> tuple:
    """Flat per-invar VInfo seeds for one entrypoint, in jaxpr invar
    order: Wq/Ws pairs from the arg pytrees get quant/scale
    identities; `ep.ranges` (arg name -> (lo, hi), the init/calibration
    absmax stats) seeds float-leaf intervals; fp8-dtype inputs start
    life already rounded. Returns (seeds, flow) — the flow carries the
    pre-registered QuantLeafs."""
    flow = _Flow()
    seeds: list = []
    ranges = getattr(ep, "ranges", None) or {}
    for arg, arg_name in zip(ep.args, ep.arg_names):
        flat = jax.tree_util.tree_flatten_with_path(arg)[0]
        rng = ranges.get(arg_name)
        pend_pairs: dict = {}   # parent path -> [wq idx, sid]
        infos = []
        for path, leaf in flat:
            dt = _dt(leaf)
            rank = len(getattr(leaf, "shape", ()))
            info = VInfo(dtype=dt, scale_like=rank <= 1,
                         round_m=_MANTISSA.get(dt)
                         if dt in QUANT_DTYPES else None)
            if rng is not None and _is_float(dt):
                info = replace(info, itv=(float(rng[0]),
                                          float(rng[1])))
            key = getattr(path[-1], "key", None) if path else None
            parent = tuple(str(p) for p in path[:-1])
            if key == "Wq" and dt in QUANT_DTYPES:
                ent = pend_pairs.setdefault(parent, {})
                ent["wq"] = (len(infos), info,
                             f"{arg_name}{_fmt_path(path)}",
                             dt, tuple(leaf.shape))
            elif key == "Ws":
                ent = pend_pairs.setdefault(parent, {})
                sid = flow.new_sid()
                ent["sid"] = sid
                info = replace(info, sids=frozenset({sid}),
                               scale_like=True)
            infos.append(info)
        for ent in pend_pairs.values():
            if "wq" in ent and "sid" in ent:
                i, info, label, dt, shape = ent["wq"]
                qid = flow.new_quant(label, ent["sid"], dt, shape)
                infos[i] = replace(infos[i], qid=qid)
        seeds.extend(infos)
    return seeds, flow


def _fmt_path(path) -> str:
    try:
        return jax.tree_util.keystr(path)
    except Exception:
        return "." + ".".join(str(p) for p in path)


def flow_entrypoint(probe, ep) -> FlowResult:
    """Run the precision-flow pass over one entrypoint's jaxpr."""
    closed = probe.jaxpr_of(ep)
    seeds, flow = seed_entrypoint(ep)
    j = closed.jaxpr
    env = {}
    if len(seeds) == len(j.invars):
        env = dict(zip(j.invars, seeds))
    flow.interp(closed, env, (), {})
    return flow.res
