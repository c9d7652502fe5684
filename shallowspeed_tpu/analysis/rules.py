"""The rule registry and the shipped lint rules.

Each rule is a pure function `(probe) -> list[Finding]` over a
`TargetProbe` (`targets.py`): the probe holds the real train-step
entrypoints, their traced jaxprs, the constructing mesh, and the
declared compute dtype. Rules never execute device code except where
the check IS behavioral (the retrace audit reads compilation-cache
sizes after the probe exercised each entrypoint with the test suite's
shape/dtype set). The precision rules additionally share ONE
abstract-interpretation pass per entrypoint (`provenance.py`, cached
by `TargetProbe.flow`) carrying per-value dtype/rounding/scale/range
provenance.

Shipped rules:

- ``dtype-promotion``  f32 leaking onto declared-bf16 compute paths:
  matmuls with mixed bf16/f32 operands (weak-type promotion) or fed by
  an explicit bf16->f32 upcast, and round-trip convert chains.
- ``donation``         step-like entrypoints whose params/opt-state
  buffers are not donated (an extra HBM copy of the model per step).
- ``collective``       psum/ppermute/all_gather/... axis names checked
  against the axes bound by the enclosing shard_map's mesh (and that
  mesh against the probe's); ppermute permutations must be valid and —
  on the 'pp' pipeline axis — a single cycle, the shape every schedule
  here is built on.
- ``retrace``          >1 compilation per entrypoint after the probe
  ran the test-suite shape/dtype set through it (retrace storms).
- ``memory-highwater`` static live-buffer byte estimate per entrypoint
  jaxpr vs the probe's HBM budget.
- ``overlap-bucket``   registered-overlap programs: every grad-sized
  dp reduction is a planned bucket with compute in its scope.
- ``dequant-fusion``   quantized weights dequantize INTO the matmul,
  never into a materialized full-size buffer.
- ``fp8-double-rounding`` / ``accumulation-dtype`` /
  ``reduction-precision`` / ``scale-consistency`` / ``range-safety``
  — the precision-flow prover (see each rule's docstring): statically
  certifies the quantized training step's numerics.
"""

from __future__ import annotations

from typing import Callable

import jax
import numpy as np
from jax.extend.core import Literal

from shallowspeed_tpu.analysis.findings import (Finding, Severity,
                                                apply_suppressions)
from shallowspeed_tpu.analysis.walker import (COLLECTIVES,
                                              REDUCE_COLLECTIVES,
                                              aval_bytes, collective_axes,
                                              peak_bytes)

RULES: dict[str, Callable] = {}



def rule(name: str):
    def register(fn):
        RULES[name] = fn
        fn.rule_name = name
        return fn
    return register


def run_rules(probe, only: tuple = ()) -> list:
    """All findings for one probe: identical findings deduplicated with
    a count (a rule firing on 4 layers x 4 matmuls is ONE fact),
    suppressions applied, HIGH first."""
    findings: list[Finding] = []
    for name, fn in RULES.items():
        if only and name not in only:
            continue
        findings.extend(fn(probe))
    grouped: dict[tuple, Finding] = {}
    counts: dict[tuple, int] = {}
    for f in findings:
        key = (f.rule, f.severity, f.target, f.site, f.path, f.message)
        counts[key] = counts.get(key, 0) + 1
        grouped.setdefault(key, f)
    deduped = []
    for key, f in grouped.items():
        if counts[key] > 1:
            f.message += f" (x{counts[key]})"
        deduped.append(f)
    apply_suppressions(deduped)
    deduped.sort(key=lambda f: (-int(f.severity), f.rule, f.site))
    return deduped


# ------------------------------------------------------- dtype promotion


def _f32_origin(var, made_by, bf16, f32, budget: int = 128) -> str:
    """Classify where a mixed-matmul's f32 operand comes from, walking
    its producer chain within the scope:

    - "accum": the chain roots in a dot_general with bf16 input(s) —
      a deliberate `preferred_element_type=f32` accumulation (or its
      transpose); f32 here is the documented score-path numerics.
    - "cast": the chain crosses a bf16->f32 convert — the data WAS
      bf16; in a backward jaxpr this is the transpose of an intended
      downcast (cotangents of `.astype(bf16)` arrive f32). Pays f32
      rate for this matmul but is structurally forced by the primal's
      cast placement.
    - "local": the chain resolves fully in-scope with NO bf16 origin
      anywhere (f32 constants / scalars) — a genuine weak-type
      promotion: bf16 data was meant to flow here and never did.
    - "unknown": the chain leaves the scope (scan carries, stashed
      residuals) or exceeds the walk budget.
    """
    seen: set = set()
    frontier = [var]
    fully_resolved = True
    has_accum = has_cast = False
    while frontier and budget > 0:
        v = frontier.pop()
        if id(v) in seen:
            continue
        seen.add(id(v))
        eqn = made_by.get(v)
        if eqn is None:  # scope input / const — producer invisible
            fully_resolved = False
            continue
        budget -= 1
        name = eqn.primitive.name
        if name == "dot_general":
            in_dts = {np.dtype(iv.aval.dtype) for iv in eqn.invars[:2]
                      if hasattr(iv.aval, "dtype")}
            if bf16 in in_dts:
                has_accum = True
                continue
        if (name == "convert_element_type"
                and getattr(eqn.invars[0].aval, "dtype", None) is not None
                and np.dtype(eqn.invars[0].aval.dtype) == bf16):
            has_cast = True
            continue
        for iv in eqn.invars:
            if (not isinstance(iv, Literal)
                    and getattr(iv.aval, "dtype", None) is not None
                    and np.dtype(iv.aval.dtype) == f32):
                frontier.append(iv)
    if has_accum:
        return "accum"
    if has_cast:
        return "cast"
    if budget <= 0 or frontier or not fully_resolved:
        return "unknown"
    return "local"


@rule("dtype-promotion")
def dtype_promotion(probe) -> list:
    """f32 on a declared-bf16 compute path. Three shapes:

    - a dot_general with MIXED float operand dtypes — jax promoted one
      side (classic weak-type accident); HIGH.
    - a dot_general whose f32 operand is directly the output of a
      bf16->f32 `convert_element_type` — the matmul was meant to run on
      the MXU in bf16 and someone upcast its input; HIGH. (bf16-in,
      f32-accumulate matmuls — `preferred_element_type` — are the
      CORRECT pattern and never flagged.)
    - convert round trips a->b->a (any target): dead casts that cost a
      pass over the array each way; MEDIUM.
    """
    out = []
    bf16 = np.dtype(jax.numpy.bfloat16)
    f32 = np.dtype(np.float32)
    declared = (np.dtype(probe.compute_dtype)
                if probe.compute_dtype is not None else None)

    def dt(v):
        d = getattr(v.aval, "dtype", None)
        return None if d is None else np.dtype(d)

    for ep in probe.entrypoints:
        for jaxpr, path in probe.jaxpr_scopes(ep):
            made_by = {}
            for eqn in jaxpr.eqns:
                for v in eqn.outvars:
                    made_by[v] = eqn
                name = eqn.primitive.name
                if name == "convert_element_type":
                    src = eqn.invars[0]
                    prev = made_by.get(src)
                    if (prev is not None
                            and prev.primitive.name
                            == "convert_element_type"
                            and dt(prev.invars[0]) == dt(eqn.outvars[0])
                            and dt(src) != dt(eqn.outvars[0])):
                        # rank in the message anchors suppressions to a
                        # value CLASS (rank-1 norm scales vs rank-5
                        # attention probabilities), so suppressing one
                        # cannot mask regressions of the other
                        rank = len(getattr(eqn.outvars[0].aval,
                                           "shape", ()))
                        out.append(Finding(
                            "dtype-promotion", Severity.MEDIUM,
                            probe.name, ep.name, path,
                            f"round-trip convert chain "
                            f"{dt(prev.invars[0])}->{dt(src)}->"
                            f"{dt(eqn.outvars[0])} on a rank-{rank} "
                            f"intermediate — two dead passes over the "
                            f"array"))
                if name != "dot_general" or declared != bf16:
                    continue
                lhs, rhs = eqn.invars[:2]
                dts = {dt(lhs), dt(rhs)}
                if dts == {bf16, f32}:
                    opnd = lhs if dt(lhs) == f32 else rhs
                    origin = _f32_origin(opnd, made_by, bf16, f32)
                    if origin in ("accum", "cast"):
                        # the f32 side is (the transpose of) a matmul
                        # that deliberately accumulates in f32
                        # (`preferred_element_type`), or of an intended
                        # downcast (`.astype(bf16)`) whose cotangent is
                        # structurally f32 — the score path's documented
                        # numerics; not an accident
                        out.append(Finding(
                            "dtype-promotion", Severity.LOW, probe.name,
                            ep.name, path,
                            f"mixed bf16/f32 dot_general on the f32 "
                            f"accumulation path ({origin}: score-path "
                            f"numerics / cast transpose) — intended, "
                            f"costs f32-rate MXU for this matmul"))
                    else:
                        sev = (Severity.MEDIUM if origin == "unknown"
                               else Severity.HIGH)
                        out.append(Finding(
                            "dtype-promotion", sev, probe.name,
                            ep.name, path,
                            "dot_general with mixed bf16/f32 operands "
                            "on a declared-bf16 path — weak-type "
                            "promotion runs this matmul in f32 (half "
                            "MXU rate, 2x operand bytes)"
                            + (" [f32 operand's producer is outside "
                               "this scope]" if origin == "unknown"
                               else "")))
                    continue
                if dts == {f32}:
                    for opnd in (lhs, rhs):
                        src = made_by.get(opnd)
                        if (src is not None
                                and src.primitive.name
                                == "convert_element_type"
                                and dt(src.invars[0]) == bf16):
                            out.append(Finding(
                                "dtype-promotion", Severity.HIGH,
                                probe.name, ep.name, path,
                                "f32 dot_general fed by a bf16->f32 "
                                "upcast on a declared-bf16 path — the "
                                "matmul should take bf16 operands "
                                "(accumulate in f32 via "
                                "preferred_element_type instead)"))
                            break
    return out


# --------------------------------------------------------------- donation


@rule("donation")
def donation(probe) -> list:
    """Step-like entrypoints must donate their params/opt-state args:
    without `donate_argnums` XLA keeps input AND output copies of the
    model live across the step — an extra params+moments of HBM that
    the biggest configs cannot spare."""
    out = []
    for ep in probe.entrypoints:
        if not ep.donate:
            continue
        jit_eqn = probe.top_jit(ep)
        if jit_eqn is None:
            out.append(Finding(
                "donation", Severity.HIGH, probe.name, ep.name,
                (), "step-like entrypoint is not jitted — every call "
                    "pays Python dispatch and nothing can be donated"))
            continue
        donated = jit_eqn.params.get("donated_invars", ())
        # flat invars are the flattened args in order; map each arg
        # index to its leaf range
        sizes = [len(jax.tree_util.tree_leaves(a)) for a in ep.args]
        starts = np.cumsum([0] + sizes)
        n_flat = len(donated)
        for argi in ep.donate:
            lo, hi = int(starts[argi]), int(starts[argi + 1])
            if hi > n_flat or not all(donated[lo:hi]):
                missing = ([] if hi > n_flat else
                           [i for i in range(lo, hi) if not donated[i]])
                out.append(Finding(
                    "donation", Severity.HIGH, probe.name, ep.name,
                    ("jit",),
                    f"argument {argi} ({ep.arg_names[argi]}) is not "
                    f"donated ({len(missing) or hi - lo} of "
                    f"{hi - lo} leaves un-aliased) — the step keeps a "
                    f"second copy of those buffers live in HBM"))
    return out


# ------------------------------------------------------------- collective


def _cycle_count(perm) -> int:
    """Number of cycles in a permutation given as (src, dst) pairs."""
    nxt = {int(s): int(d) for s, d in perm}
    seen, cycles = set(), 0
    for start in nxt:
        if start in seen:
            continue
        cycles += 1
        cur = start
        while cur not in seen:
            seen.add(cur)
            cur = nxt.get(cur, cur)
    return cycles


@rule("collective")
def collective(probe) -> list:
    """Mesh-axis hygiene for every collective eqn: axis names must be
    bound by an enclosing shard_map whose mesh matches the probe's; a
    ppermute's permutation must be a bijection over in-range sources/
    destinations, and on the pipeline ('pp') axis a SINGLE cycle —
    stage hops here are rings, and a multi-cycle permutation would
    partition the stages into disconnected sub-pipelines."""
    out = []
    probe_axes = set(probe.mesh.axis_names) if probe.mesh else set()
    for ep in probe.entrypoints:
        for eqn, path, env in probe.walk(ep):
            name = eqn.primitive.name
            if name == "shard_map" and probe.mesh is not None:
                mesh = eqn.params.get("mesh")
                if mesh is not None and not set(
                        mesh.axis_names) <= probe_axes:
                    out.append(Finding(
                        "collective", Severity.HIGH, probe.name,
                        ep.name, path,
                        f"shard_map over mesh axes "
                        f"{tuple(mesh.axis_names)} inside a program "
                        f"whose constructing mesh has "
                        f"{tuple(probe.mesh.axis_names)}"))
                continue
            if name not in COLLECTIVES and name != "axis_index":
                continue
            axes = collective_axes(eqn)
            unbound = [a for a in axes if a not in env]
            if unbound:
                out.append(Finding(
                    "collective", Severity.HIGH, probe.name, ep.name,
                    path,
                    f"{name} over axis {unbound} not bound by any "
                    f"enclosing shard_map (bound: "
                    f"{sorted(env) or 'none'})"))
                continue
            if name != "ppermute":
                continue
            perm = tuple(eqn.params.get("perm", ()))
            ax = axes[0] if axes else None
            size = env.get(ax)
            srcs = [int(s) for s, _ in perm]
            dsts = [int(d) for _, d in perm]
            if (len(set(srcs)) != len(srcs)
                    or len(set(dsts)) != len(dsts)
                    or (size is not None and any(
                        not (0 <= x < size) for x in srcs + dsts))):
                out.append(Finding(
                    "collective", Severity.HIGH, probe.name, ep.name,
                    path,
                    f"ppermute over '{ax}' (size {size}) with an "
                    f"invalid permutation {perm}: duplicate or "
                    f"out-of-range sources/destinations"))
                continue
            if ax == "pp" and perm and (
                    len(perm) != size or _cycle_count(perm) != 1):
                out.append(Finding(
                    "collective", Severity.HIGH, probe.name, ep.name,
                    path,
                    f"ppermute over 'pp' is not a single "
                    f"{size}-cycle ({perm}): pipeline stage hops "
                    f"must form one ring, or stages de-sync into "
                    f"disconnected sub-pipelines"))
    return out


# ---------------------------------------------------------------- retrace


@rule("retrace")
def retrace(probe) -> list:
    """>1 compilation per entrypoint after the probe exercised it with
    the shape/dtype set the test suite uses. Every extra executable is
    seconds of XLA compile time and a sign the cache key is unstable
    (python scalars re-traced as weak types, shifting shapes, ...)."""
    out = []
    for ep in probe.entrypoints:
        # read the snapshot TargetProbe.seal() took right after the
        # exercise calls — not the live cache, which later rules'
        # make_jaxpr tracing could perturb on some jax versions
        n = ep.observed_compiles
        if n is None or ep.calls == 0:
            continue
        if n > ep.n_compiles_expected:
            out.append(Finding(
                "retrace", Severity.HIGH, probe.name, ep.name, (),
                f"{n} compilations after {ep.calls} same-shaped calls "
                f"(expected {ep.n_compiles_expected}) — the jit cache "
                f"key is unstable for this entrypoint"))
    return out


# ---------------------------------------------------------- overlap-bucket


@rule("overlap-bucket")
def overlap_bucket(probe) -> list:
    """Comm/compute-interleaving hygiene for programs registered as
    overlapped (`parallel/overlap.register_program`):

    - every grad-sized reduction (`psum`/`psum_scatter`/
      `reduce_scatter`) over the registered data axis must match one of
      the registered bucket signatures — a stray dp psum outside the
      plan means some gradient bypasses the bucketed reduction (HIGH);
    - every registered bucket must actually appear (MEDIUM — the plan
      and the program drifted);
    - the interleaving dataflow must exist: at least one collective on
      the registered axis with independent MXU-heavy compute in its
      scope, which is what XLA's latency-hiding scheduler needs to
      overlap it (HIGH otherwise — the reduction is fully exposed and
      "overlap" is a lie).

    Sub-KiB reductions (health-pack statistics, loss means) are not
    gradient traffic and are exempt. Unregistered programs are skipped
    — the bulk reduction is the documented oracle, not a defect."""
    from collections import Counter

    from shallowspeed_tpu.parallel import overlap as OV

    out = []
    for ep in probe.entrypoints:
        info = OV.registered(ep.fn)
        if info is None:
            continue
        axis = info["axis"]
        # a bucket's psum binds one eqn per member leaf, so the match
        # is leaf by leaf against the registered buckets' members
        expected = Counter(leaf for bucket in info["buckets"]
                           for leaf in bucket)
        seen: Counter = Counter()
        for eqn, path, env in probe.walk(ep):
            name = eqn.primitive.name
            if name not in REDUCE_COLLECTIVES:
                continue
            if axis not in collective_axes(eqn):
                continue
            for v in eqn.invars:
                if isinstance(v, Literal):
                    continue
                (sig,) = OV.bucket_signature([v.aval])
                nbytes = aval_bytes(v.aval)
                if seen[sig] < expected[sig]:
                    seen[sig] += 1
                elif nbytes >= 1024:
                    # (smaller unmatched operands are scalar statistics
                    # — health pack, loss means — not gradient payload)
                    out.append(Finding(
                        "overlap-bucket", Severity.HIGH, probe.name,
                        ep.name, path,
                        f"{name} over '{axis}' ({nbytes} B) is not a "
                        f"member of a registered reduction bucket — "
                        f"this gradient bypasses the bucketed "
                        f"overlapped reduction"))
        missing = expected - seen
        if missing:
            out.append(Finding(
                "overlap-bucket", Severity.MEDIUM, probe.name, ep.name,
                (),
                f"{sum(missing.values())} registered bucket member(s) "
                f"never appeared in the traced program — the bucket "
                f"plan and the compiled reduction drifted"))
        expo = OV.collective_exposure(probe.jaxpr_of(ep), axes=(axis,))
        if expo["n_collectives"] and not expo["n_overlapped"]:
            out.append(Finding(
                "overlap-bucket", Severity.HIGH, probe.name, ep.name,
                (),
                f"no '{axis}' collective in this registered-overlapped "
                f"program has independent compute in its scope — every "
                f"reduction is a dataflow barrier and nothing can "
                f"overlap"))
    return out


# --------------------------------------------------------- dequant fusion

# quantized-storage dtypes the serving decode path reads (int8 weights
# and KV blocks; fp8-e4m3 weights where the build ships it)
_QUANT_DTYPES = {"int8", "uint8", "float8_e4m3fn", "float8_e5m2"}

# shape-preserving primitives a weight buffer may pass through between
# its upcast and its consumer without changing what's materialized
_PASSTHROUGH = {"reshape", "transpose", "broadcast_in_dim", "squeeze",
                "copy"}


def _is_quant(var) -> bool:
    dt = getattr(var.aval, "dtype", None)
    return (dt is not None and str(np.dtype(dt)) in _QUANT_DTYPES
            and len(getattr(var.aval, "shape", ())) >= 2)


@rule("dequant-fusion")
def dequant_fusion(probe) -> list:
    """Quantized weights must dequantize INTO the matmul, never into a
    buffer. The whole point of int8/fp8 weight storage is reading one
    byte per element from HBM; the classic way to lose it is

        (wq.astype(f32) * scale) @ x     # a full (K, N) dequant copy

    where the scale multiply (or any other elementwise op) materializes
    a full-weight-size floating buffer between the upcast and the dot.
    The FUSED form (`ops.matmul.dequant_matmul`) upcasts the values
    directly into the dot operand — XLA folds that convert into the
    operand load — and applies the scale to the f32 ACCUMULATOR.

    Mechanically: for every `convert_element_type` whose input chains
    back (through shape-preserving ops only — a gather breaks the
    chain, so gathered int8 KV *views* are exempt) to an int8/fp8
    buffer of rank >= 2, every consumer of the upcast value must be a
    `dot_general` (possibly through more shape-preserving ops). Any
    elementwise consumer producing a full-weight-size floating output
    is a materialized dequantized copy: HIGH."""
    out = []
    for ep in probe.entrypoints:
        for jaxpr, path in probe.jaxpr_scopes(ep):
            made_by = {}
            consumers: dict = {}
            for eqn in jaxpr.eqns:
                for v in eqn.outvars:
                    made_by[v] = eqn
                for v in eqn.invars:
                    if not isinstance(v, Literal):
                        consumers.setdefault(v, []).append(eqn)

            def root_of(var):
                seen = 0
                while seen < 32:
                    eqn = made_by.get(var)
                    if eqn is None \
                            or eqn.primitive.name not in _PASSTHROUGH:
                        return var
                    var = eqn.invars[0]
                    seen += 1
                return var

            def check_uses(var, size, depth=0):
                """Every (transitive, through passthrough) use of the
                upcast buffer must be a dot; return the offending eqn
                otherwise."""
                for use in consumers.get(var, ()):
                    name = use.primitive.name
                    if name == "dot_general":
                        continue
                    if name in _PASSTHROUGH and depth < 8:
                        bad = check_uses(use.outvars[0], size, depth + 1)
                        if bad is not None:
                            return bad
                        continue
                    out_avals = [o.aval for o in use.outvars]
                    # jnp.issubdtype, not np: bf16/fp8 are ml_dtypes
                    # extensions numpy does not class as floating
                    if any(int(np.prod(getattr(a, "shape", ()),
                                       dtype=np.int64)) == size
                           and jax.numpy.issubdtype(
                               getattr(a, "dtype", np.int32),
                               jax.numpy.floating)
                           for a in out_avals):
                        return use
                return None

            for eqn in jaxpr.eqns:
                if eqn.primitive.name != "convert_element_type":
                    continue
                src = root_of(eqn.invars[0])
                if isinstance(src, Literal) \
                        or not _is_quant(src):
                    continue
                o = eqn.outvars[0]
                odt = getattr(o.aval, "dtype", None)
                if odt is None or not jax.numpy.issubdtype(
                        odt, jax.numpy.floating):
                    continue
                size = int(np.prod(o.aval.shape, dtype=np.int64))
                if size != int(np.prod(src.aval.shape,
                                       dtype=np.int64)):
                    continue   # the upcast is of a slice, not the weight
                bad = check_uses(o, size)
                if bad is not None:
                    out.append(Finding(
                        "dequant-fusion", Severity.HIGH, probe.name,
                        ep.name, path,
                        f"{str(np.dtype(src.aval.dtype))} weight "
                        f"{tuple(src.aval.shape)} upcast to "
                        f"{np.dtype(odt)} is consumed by "
                        f"'{bad.primitive.name}' at full weight size — "
                        f"a materialized dequantized copy; apply the "
                        f"scale to the f32 accumulator instead "
                        f"(ops.matmul.dequant_matmul)"))
    return out


# --------------------------------------------- precision-flow rules
#
# The five quantized-training rules ride ONE shared abstract-
# interpretation pass (`provenance.py`, cached per entrypoint by
# `TargetProbe.flow`): per-value storage-dtype lineage, rounding
# state, quantization-scale pairing, and calibration-seeded absmax
# intervals. They are the static gate for ROADMAP item 5 — the
# fp8_train probe must come out clean before a long quantized run is
# worth burning.


@rule("fp8-double-rounding")
def fp8_double_rounding(probe) -> list:
    """A value that crossed one narrowing float convert and crosses a
    SECOND — to a strictly narrower format, or back into quantized
    storage — without an intervening rescale (f32->bf16->fp8, or fp8
    re-quantized straight). Stacking roundings of decreasing width
    compounds error beyond the target format's half-ulp and is never
    intended — correct requantization rescales (divides by a fresh
    scale) first, which resets the rounding state. Re-rounding at the
    SAME width (bf16 -> f32 arithmetic -> bf16, the standard mixed-
    precision pattern) is one rounding of a new value and is exempt."""
    out = []
    for ep in probe.entrypoints:
        for ev in probe.flow(ep).events:
            if ev.kind != "double-round":
                continue
            d = ev.data
            out.append(Finding(
                "fp8-double-rounding", Severity.HIGH, probe.name,
                ep.name, ev.path,
                f"value already rounded to {d['first']} is rounded "
                f"again to {d['dst']} (shape {d['shape']}) with no "
                f"intervening rescale — double rounding compounds "
                f"quantization error; rescale (x / s) before the "
                f"second convert"))
    return out


@rule("accumulation-dtype")
def accumulation_dtype(probe) -> list:
    """Every contraction and loop-carried sum must prove widest-type
    accumulation:

    - a dot_general with QUANTIZED-lineage operands (int8/fp8 storage,
      however upcast) must emit f32 (`preferred_element_type`) — the
      whole point of quantized storage is 1-byte reads into a wide
      accumulator, and a narrow output rounds K products away (HIGH);
    - a scan/while carry that is an accumulator (carry + independent
      contribution per iteration) must carry f32 — the peeled-
      microbatch grad sums re-round every add otherwise (HIGH);
    - a plain narrow-float dot with a narrow output is informational
      (LOW): the MXU accumulates f32 internally and rounds once at the
      output, which is the documented activation-path numerics, but
      long contractions feeding accumulators deserve an explicit
      `preferred_element_type=f32`."""
    out = []
    wide = ("float32", "float64")
    for ep in probe.entrypoints:
        flow = probe.flow(ep)
        for ev in flow.events:
            if ev.kind == "carry-accum":
                d = ev.data
                out.append(Finding(
                    "accumulation-dtype", Severity.HIGH, probe.name,
                    ep.name, ev.path,
                    f"{d['prim']}-carried accumulator (shape "
                    f"{d['shape']}) accumulates in {d['dtype']} — "
                    f"every iteration re-rounds the running sum; "
                    f"carry f32 and cast once at the end"))
            if ev.kind != "dot":
                continue
            d = ev.data
            odt = d["out_dtype"]
            if odt in wide or odt is None:
                continue
            floats = [t for t in d["in_dtypes"]
                      if t and (t.startswith("float")
                                or t.startswith("bfloat"))]
            if not floats:
                continue
            if d["quant"]:
                out.append(Finding(
                    "accumulation-dtype", Severity.HIGH, probe.name,
                    ep.name, ev.path,
                    f"dot_general over quantized-storage operands "
                    f"{d['in_dtypes']} emits {odt} (K={d['k']}) — "
                    f"quantized matmuls must accumulate f32 "
                    f"(preferred_element_type) with the scale applied "
                    f"to the accumulator"))
            elif all(t not in wide for t in floats):
                out.append(Finding(
                    "accumulation-dtype", Severity.LOW, probe.name,
                    ep.name, ev.path,
                    f"narrow dot_general {d['in_dtypes']}->{odt}: "
                    f"MXU accumulates f32 internally and rounds once "
                    f"at the output (standard activation numerics); "
                    f"prefer preferred_element_type=f32 where the "
                    f"result feeds an accumulator"))
    return out


@rule("reduction-precision")
def reduction_precision(probe) -> list:
    """Grad-sized cross-device reductions must run in f32: a bf16/fp8
    `psum` rounds at every hop of the reduction tree, and a gradient
    reduced wrong is unrecoverable after the optimizer step. Operands
    whose chain proves f32 (the repo's grads — cast transposes emit
    f32 cotangents) pass by construction since the operand DTYPE is
    f32. Sub-KiB reductions (health-pack statistics, loss means) are
    exempt, matching the `overlap-bucket` rule's threshold."""
    out = []
    for ep in probe.entrypoints:
        for eqn, path, env in probe.walk(ep):
            name = eqn.primitive.name
            if name not in REDUCE_COLLECTIVES:
                continue
            for v in eqn.invars:
                if isinstance(v, Literal):
                    continue
                dt = getattr(v.aval, "dtype", None)
                if dt is None or not jax.numpy.issubdtype(
                        dt, jax.numpy.floating):
                    continue
                if np.dtype(dt).itemsize >= 4:
                    continue
                nbytes = aval_bytes(v.aval)
                if nbytes < 1024:
                    continue  # scalar statistics, not gradient payload
                axes = collective_axes(eqn)
                out.append(Finding(
                    "reduction-precision", Severity.HIGH, probe.name,
                    ep.name, path,
                    f"{name} over {axes or '?'} reduces a "
                    f"{np.dtype(dt)} operand of {nbytes} B — every "
                    f"hop of the reduction tree re-rounds; upcast the "
                    f"operand to f32 (or prove the chain f32) before "
                    f"grad-sized cross-device sums"))
    return out


@rule("scale-consistency")
def scale_consistency(probe) -> list:
    """Every quantized leaf consumed by a matmul must see its paired
    scale EXACTLY once, applied to the accumulator (or riding the
    cotangent on the transpose/VJP side). A forgotten scale silently
    mis-scales activations or gradients by orders of magnitude; a
    doubled one squares it. Pairing comes from the param layout
    (Wq/Ws dicts) or from in-program quantization (x/s followed by a
    narrowing convert to quantized storage)."""
    out = []
    for ep in probe.entrypoints:
        flow = probe.flow(ep)
        for use in flow.dot_uses:
            if use.resolved:
                continue
            out.append(Finding(
                "scale-consistency", Severity.HIGH, probe.name,
                ep.name, use.path,
                f"quantized leaf {use.label} (shape {use.shape}) is "
                f"consumed by a dot_general but its scale is never "
                f"applied to the result — the output is mis-scaled "
                f"by the quantization factor (forgotten Ws / "
                f"delayed-scaling factor)"))
        for ev in flow.events:
            if ev.kind != "double-scale":
                continue
            out.append(Finding(
                "scale-consistency", Severity.HIGH, probe.name,
                ep.name, ev.path,
                f"quantization scale of {ev.data.get('labels')} is "
                f"applied TWICE on the same value lineage — the "
                f"output is scaled by the square of the factor"))
    return out


@rule("range-safety")
def range_safety(probe) -> list:
    """Interval propagation over the calibration-seeded bounds: fires
    only on PROVABLE dtype-range violations — an exp whose input's
    lower bound already overflows the storage dtype, a narrowing
    convert whose operand provably exceeds the target's max (e.g. f32
    values in [0, 1000] cast to e4m3 with max 448 and no saturating
    clamp), or a log/rsqrt over a provably non-positive range. The
    pass understands the softmax shift (x - max(x) <= 0) and
    saturation clamps, so the standard guarded patterns stay clean."""
    out = []
    for ep in probe.entrypoints:
        for ev in probe.flow(ep).events:
            if ev.kind != "range":
                continue
            d = ev.data
            lo, hi = d["itv"]
            itv = f"[{lo:.3g}, {hi:.3g}]"
            if d["problem"] == "overflow":
                msg = (f"{d['op']} with provable input range {itv} "
                       f"overflows {d['dst']} (max "
                       f"{d['bound']:.3g}) — saturate (clamp) or "
                       f"rescale before the narrowing")
            elif d["problem"] == "underflow":
                msg = (f"{d['op']} with provable input range {itv} "
                       f"underflows {d['dst']} entirely (min normal "
                       f"{d['bound']:.3g}) — the result is all "
                       f"zeros/denormals")
            else:
                msg = (f"{d['op']} over a provably non-positive "
                       f"range {itv} — the result is NaN/inf for "
                       f"the whole array; add the guard epsilon")
            out.append(Finding(
                "range-safety", Severity.HIGH, probe.name, ep.name,
                ev.path, msg))
    return out


# ------------------------------------------------------- memory highwater


@rule("memory-highwater")
def memory_highwater(probe) -> list:
    """Static live-buffer high-water per entrypoint jaxpr vs the
    probe's budget. Always emits one LOW informational finding per
    entrypoint (the number lands in the report snapshot); HIGH when the
    estimate exceeds the budget."""
    out = []
    for ep in probe.entrypoints:
        jaxpr = probe.jaxpr_of(ep)
        if jaxpr is None:
            continue
        est = peak_bytes(jaxpr.jaxpr)
        args_b = sum(aval_bytes(v.aval) for v in jaxpr.jaxpr.invars)
        mib = est / (1 << 20)
        if est > probe.hbm_budget:
            out.append(Finding(
                "memory-highwater", Severity.HIGH, probe.name, ep.name,
                (),
                f"estimated live-buffer peak {mib:.1f} MiB exceeds the "
                f"{probe.hbm_budget / (1 << 20):.0f} MiB budget "
                f"(inputs alone: {args_b / (1 << 20):.1f} MiB)"))
        else:
            out.append(Finding(
                "memory-highwater", Severity.LOW, probe.name, ep.name,
                (),
                f"estimated live-buffer peak {mib:.2f} MiB "
                f"(budget {probe.hbm_budget / (1 << 20):.0f} MiB)"))
    return out
