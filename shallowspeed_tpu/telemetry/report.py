"""RunTelemetry — the driver-facing aggregator.

One object per training run. The engines expose
`telemetry_entrypoints()` (name, jitted fn, ShapeDtypeStruct args —
recorded at their first real step, so the skeletons match what
actually runs); RunTelemetry turns that plus the live process state
into:

- a one-time STATIC report: per-axis collective bytes/calls per step
  (`collectives.py` jaxpr walk) and the static HBM peak prediction
  (`memory.static_peak_bytes`, the analysis memory rule's number);
- per-log-point STEP FIELDS merged into `metrics.StepRates` lines:
  live HBM high-water + the live-vs-static cross-check, implied
  collective GB/s over the closed window, and the recompile counter
  (jit cache sizes beyond the first-step baseline — the class of bug
  the gspmd `pos_emb` placement drift was, PR 1, now visible on every
  step line);
- an end-of-run summary (written into the trace dir next to the spans).

Everything here degrades gracefully: no entrypoints yet -> static
fields appear at the first log point after a step; an engine without
`telemetry_entrypoints` -> step fields reduce to HBM + recompiles.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

from shallowspeed_tpu.telemetry import collectives, memory

MiB = float(1 << 20)


def percentile(vals, q: float) -> float | None:
    """Nearest-rank percentile (q in [0, 100]) without numpy dtype
    surprises — None on empty input. The ONE quantile definition the
    repo shares: the request-latency summary below, the goodput
    reducer's serving block and
    the streaming sketches (`sketch.LogHistogram.quantile`) all use
    this rank rule, so live and offline quantiles can only disagree by
    the sketch's documented rel_err — never by rank convention.

    Rank = floor(q/100 * (n-1) + 0.5): round-HALF-UP. Python's
    round() rounds half to even (banker's), which maps an exact .5
    rank DOWN whenever the lower rank is even — p50 of 18 samples
    would read sample 8, not 9."""
    vals = sorted(float(v) for v in vals)
    if not vals:
        return None
    k = min(len(vals) - 1,
            max(0, math.floor(q / 100.0 * (len(vals) - 1) + 0.5)))
    return vals[k]


def request_summary(recs) -> dict | None:
    """Reduce schema-v6 `"request"` records (dicts with ttft_ms /
    tpot_ms / tokens_* / preempted — the serving engine's completion
    stamps) to the SLO headline: p50/p95 time-to-first-token and
    time-per-output-token, total tokens moved, preemption count.
    Returns None when there are no request records, so training-run
    summaries stay unchanged."""
    recs = [r for r in recs if isinstance(r, dict) and "ttft_ms" in r]
    if not recs:
        return None
    ttft = [r["ttft_ms"] for r in recs
            if isinstance(r.get("ttft_ms"), (int, float))]
    tpot = [r["tpot_ms"] for r in recs
            if isinstance(r.get("tpot_ms"), (int, float))]
    rnd = lambda v: None if v is None else round(v, 3)  # noqa: E731
    return {
        "n_requests": len(recs),
        "ttft_ms_p50": rnd(percentile(ttft, 50)),
        "ttft_ms_p95": rnd(percentile(ttft, 95)),
        "tpot_ms_p50": rnd(percentile(tpot, 50)),
        "tpot_ms_p95": rnd(percentile(tpot, 95)),
        "tokens_in": sum(int(r.get("tokens_in", 0)) for r in recs),
        "tokens_out": sum(int(r.get("tokens_out", 0)) for r in recs),
        "preempted": sum(int(r.get("preempted", 0)) for r in recs),
    }


def request_timeline(source, rid: str | None = None) -> dict:
    """Reconstruct per-request phase timelines from schema-v8
    ``"lifecycle"`` events (`serving/engine.ServingEngine._lifecycle`:
    submit -> queued -> admitted -> prefill chunk k -> decoding ->
    preempted -> requeued -> finished).

    `source` is a metrics-JSONL path or an iterable of parsed records;
    `rid` filters to one request. Returns, per request id:

        {"phases": [{"phase", "wall", "ms_in_prev", ...}, ...],
         "by_phase_ms": {phase: total ms spent IN that phase},
         "complete": started with submit and ended with finished,
         "attempts": cross-engine dispatch attempts merged,
         "e2e_ms": submit -> finished wall span (None if incomplete)}

    Time spent "in" a phase is attributed by the NEXT transition's
    ms_in_prev (or wall delta when absent), so the sum of by_phase_ms
    reconciles with e2e_ms up to stamp rounding — the fleet view's
    worst-ttft exemplar resolves to which PHASE through this.

    One rid's events can span MULTIPLE engine attempts (a failover
    re-dispatch; schema v11 stamps ``attempt``, and a resumed attempt
    opens with ``submit`` carrying the ``resumed`` marker). The
    reduction is keyed on (rid, attempt): each attempt's seq counter
    restarts at 0, so a rid-only sort would interleave two attempts'
    events, and the wall-delta fallback across two PROCESSES' clocks
    would book the cross-attempt gap — arbitrary skew — into a phase.
    Attempts merge in order; no ms is attributed across an attempt
    boundary (the stitched waterfall's rq_failover_gap owns that
    interval, with skew corrected)."""
    if isinstance(source, (str, Path)):
        recs = []
        for line in Path(source).read_text().splitlines():
            if not line.strip():
                continue
            try:
                recs.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    else:
        recs = list(source)
    per: dict[str, dict[int, list]] = {}
    seen_submits: dict[str, int] = {}
    for rec in recs:
        if not isinstance(rec, dict) or rec.get("event") != "lifecycle":
            continue
        r = rec.get("id")
        if not isinstance(r, str) or (rid is not None and r != rid):
            continue
        att = rec.get("attempt")
        if not isinstance(att, int) or isinstance(att, bool):
            # pre-v11 logs: derive the attempt index from the resumed
            # markers — every "submit" after the first opens a new one
            if rec.get("phase") == "submit":
                seen_submits[r] = seen_submits.get(r, -1) + 1
            att = max(0, seen_submits.get(r, 0))
        per.setdefault(r, {}).setdefault(att, []).append(rec)
    out = {}
    for r, attempts in per.items():
        phases = []
        by_phase: dict[str, float] = {}
        # order attempts by index, then walk each attempt's events by
        # its OWN seq counter; the (prev, cur) accounting below never
        # crosses an attempt boundary
        ordered = []
        for att in sorted(attempts):
            events = attempts[att]
            events.sort(key=lambda e: (e.get("seq", 0),
                                       e.get("wall", 0.0)))
            ordered.append(events)
        for events in ordered:
            for prev, cur in zip([None] + events, events):
                entry = {k: cur[k] for k in
                         ("phase", "wall", "ms_in_prev", "prev", "slot",
                          "tick", "chunk", "tokens", "attempt",
                          "resumed", "trace", "blocks") if k in cur}
                phases.append(entry)
                if prev is None:
                    continue
                ms = cur.get("ms_in_prev")
                if not isinstance(ms, (int, float)):
                    w0, w1 = prev.get("wall"), cur.get("wall")
                    ms = ((w1 - w0) * 1e3
                          if isinstance(w0, (int, float))
                          and isinstance(w1, (int, float)) else 0.0)
                name = cur.get("prev", prev.get("phase", "?"))
                by_phase[name] = by_phase.get(name, 0.0) + float(ms)
        complete = bool(phases) and phases[0]["phase"] == "submit" \
            and phases[-1]["phase"] == "finished"
        e2e = None
        if complete and len(ordered) == 1 \
                and isinstance(phases[0].get("wall"), (int, float)) \
                and isinstance(phases[-1].get("wall"), (int, float)):
            # the single-attempt wall span; across attempts the stamps
            # come from different processes' clocks, so the honest e2e
            # is the stitcher's (router-clock) number, not a raw delta
            e2e = round((phases[-1]["wall"] - phases[0]["wall"]) * 1e3,
                        3)
        # v14: prefill the prefix cache skipped, booked EXPLICITLY (a
        # cache-hit request's rq_prefill is honestly fast — the
        # prefill_cached stamps say how many tokens never ran)
        skipped = sum(p.get("tokens", 0) for p in phases
                      if p.get("phase") == "prefill_cached"
                      and isinstance(p.get("tokens"), int))
        out[r] = {"phases": phases,
                  "by_phase_ms": {k: round(v, 3)
                                  for k, v in sorted(by_phase.items())},
                  "complete": complete,
                  "attempts": len(ordered),
                  "skipped_tokens": skipped,
                  "e2e_ms": e2e}
    return out


def request_waterfall(journey: dict) -> dict | None:
    """Reduce one stitched journey (`telemetry/tracing.build_journeys`)
    into the per-request latency waterfall: ``rq_*_ms`` components plus
    matching ``rq_*_frac`` fractions that sum to the measured e2e BY
    CONSTRUCTION — ``rq_unexplained`` is the residual between the
    named segments and the router-measured e2e, so it doubles as the
    stitching-quality alarm (clock misfit or missing streams inflate
    it). None when the journey has no usable e2e."""
    from shallowspeed_tpu.telemetry.tracing import COMPONENTS

    e2e = journey.get("e2e_ms")
    if not isinstance(e2e, (int, float)) or e2e <= 0.0:
        return None
    comps = {name: 0.0 for name in COMPONENTS}
    for seg in journey.get("segments") or ():
        comps[seg["component"]] = (comps.get(seg["component"], 0.0)
                                   + float(seg["ms"]))
    out = {"e2e_ms": round(float(e2e), 3)}
    named = 0.0
    for name in COMPONENTS:
        out[f"{name}_ms"] = round(comps[name], 3)
        out[f"{name}_frac"] = round(comps[name] / e2e, 4)
        named += comps[name]
    out["rq_unexplained_ms"] = round(e2e - named, 3)
    out["rq_unexplained_frac"] = round((e2e - named) / e2e, 4)
    return out


def sds(tree):
    """Shape/dtype skeleton of a pytree (targets.py's `_sds` contract:
    safe to trace, can never alias live buffers)."""
    import jax

    return jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(np.shape(l), np.asarray(l).dtype)
        if not hasattr(l, "aval") and not hasattr(l, "dtype")
        else jax.ShapeDtypeStruct(l.shape, l.dtype), tree)


def step_entrypoints(params, opt_state, tok, tgt, step_fn=None,
                     grads_fn=None, update_fn=None, grads=None,
                     eval_fn=None, step_arg: bool = True) -> list:
    """The engines' shared skeleton capture (one call at their first
    TRACED step — the call sites gate on the tracer level, so the
    default `off` path never imports this module): (name, fn, SDS
    args) per compiled entrypoint, step program first. Pass `step_fn`
    for fused-step engines, or `grads_fn`+`update_fn`+`grads` for the
    ZeRO split; `step_arg=False` for the MLP engines whose step fns
    take no step counter."""
    import jax

    tok, tgt = sds(tok), sds(tgt)
    stp = ((jax.ShapeDtypeStruct((), np.uint32),) if step_arg else ())
    if step_fn is not None:
        eps = [{"name": "_step", "fn": step_fn,
                "args": (sds(params), sds(opt_state), tok, tgt, *stp)}]
    else:
        eps = [
            {"name": "_grads", "fn": grads_fn,
             "args": (sds(params), tok, tgt, *stp)},
            {"name": "_update", "fn": update_fn,
             "args": (sds(params), sds(grads), sds(opt_state))},
        ]
    if eval_fn is not None:
        eps.append({"name": "_eval", "fn": eval_fn,
                    "args": (sds(params), tok, tgt)})
    return eps


def record_engine_entrypoints(engine, tok, tgt, grads=None,
                              step_arg: bool = True) -> list:
    """`step_entrypoints` with the engines' conventional attribute
    names resolved in ONE place (fused step: `_step_fn`/`_step`; ZeRO
    split: `_grads_fn`/`_loss_grads_fn` + `_update_fn`; optional
    `_eval_fn`) — every engine's `_record_entrypoints` is a one-line
    call here, so the entrypoint convention cannot drift per engine."""
    step_fn = getattr(engine, "_step_fn", getattr(engine, "_step",
                                                  None))
    grads_fn = update_fn = None
    if step_fn is None:
        grads_fn = (getattr(engine, "_grads_fn", None)
                    or getattr(engine, "_loss_grads_fn", None))
        update_fn = engine._update_fn
    return step_entrypoints(
        engine.params, engine.opt_state, tok, tgt, step_fn=step_fn,
        grads_fn=grads_fn, update_fn=update_fn, grads=grads,
        eval_fn=getattr(engine, "_eval_fn", None), step_arg=step_arg)


def compile_counts(entrypoints) -> dict:
    """name -> live jit-cache size for every entrypoint that exposes
    one (`fn._cache_size`, the same counter analysis' retrace rule
    reads)."""
    out = {}
    for ep in entrypoints:
        size = getattr(ep["fn"], "_cache_size", None)
        if size is not None:
            try:
                out[ep["name"]] = int(size())
            except Exception:
                pass
    return out


class RunTelemetry:
    """Aggregates telemetry for one engine over one training run."""

    def __init__(self, engine, tracer=None, check_tolerance: float = 1.05):
        self.engine = engine
        self.tracer = tracer
        self.tol = check_tolerance
        self._static = None
        self._bubble: dict = {}
        # optional goodput.GoodputLedger the driver also stamps —
        # surfaced in run_summary so telemetry.json carries the
        # in-process loss totals next to the static report
        self.ledger = None
        # memory observatory (round 20): per-window leak/drift
        # detector over the live-bytes + host-RSS series this
        # telemetry already samples; verdicts ride step lines as
        # mem_verdicts (schema v15)
        self.memwatch = memory.MemoryWatch()
        self._mem_windows = 0

    # -------------------------------------------------------- static

    def _entrypoints(self) -> list:
        fn = getattr(self.engine, "telemetry_entrypoints", None)
        if fn is None:
            return []
        return fn()

    def static_report(self) -> dict | None:
        """Computed once, lazily (needs a step to have run so the
        engines know their batch skeletons). Entrypoints published
        without args (the VM's per-stage executables) count for the
        recompile counter but are skipped here — the VM measures its
        traffic directly (`telemetry_traffic`)."""
        if self._static is not None:
            return self._static
        eps = [ep for ep in self._entrypoints()
               if ep.get("args") is not None]
        if not eps:
            return None
        rep = {}
        for ep in eps:
            try:
                # ONE make_jaxpr per entrypoint, shared by every
                # accounting — tracing a big pipeline step costs
                # seconds and must not run twice
                import jax

                from shallowspeed_tpu.analysis.walker import peak_bytes

                closed = jax.make_jaxpr(ep["fn"])(*ep["args"])
                traffic = collectives.traffic_of_jaxpr(closed)
                peak = peak_bytes(closed.jaxpr)
            except Exception as e:
                rep[ep["name"]] = {"error": repr(e)[:200]}
                continue
            try:
                # exposure (schema v3) in its own guard: a failure in
                # the newer dataflow walk must not discard the v1/v2
                # traffic/HBM accounting (step_fields tolerates None)
                from shallowspeed_tpu.parallel.overlap import (
                    collective_exposure)

                expo = collective_exposure(closed)
            except Exception:
                expo = None
            rep[ep["name"]] = {"collectives": traffic,
                               "static_peak_bytes": peak,
                               "exposure": expo}
        self._static = {"entrypoints": rep,
                        "step": eps[0]["name"]}  # first = the step fn
        return self._static

    # ---------------------------------------------------------- steps

    @property
    def bubble(self) -> dict:
        """The bubble fields currently attached to step lines."""
        return dict(self._bubble)

    def set_bubble(self, **fields) -> None:
        """Attach bubble accounting (static fraction and, when a
        calibration or an executed trace produced one, the measured
        fraction) — merged into every subsequent step line."""
        self._bubble.update(fields)

    def step_fields(self, window_secs: float | None = None,
                    steps_in_window: int | None = None) -> dict:
        """The telemetry fields a step line carries."""
        out: dict = {}
        counts = compile_counts(self._entrypoints())
        if counts:
            # an entrypoint's FIRST executable is the expected compile
            # (the analysis retrace rule's n_compiles_expected=1);
            # every executable beyond one is a recompile — the counter
            # the acceptance gate requires to stay 0 after step 1
            out["compiles"] = sum(counts.values())
            out["recompiles"] = sum(max(0, c - 1)
                                    for c in counts.values())
        live = memory.live_hbm_high_water()
        out["hbm_live_mib"] = round(live["max_device_bytes"] / MiB, 2)
        stats = memory.device_memory_stats()
        peaks = [v.get("peak_bytes_in_use") for v in stats.values()
                 if v.get("peak_bytes_in_use")]
        if peaks:
            out["hbm_alloc_peak_mib"] = round(max(peaks) / MiB, 2)
        # schema v15 (memory observatory): decompose the live total by
        # registered owner — the untracked residual is the leak alarm
        # — and feed the leak/drift detector with this window's
        # device + host-RSS samples
        if memory.registered_owners():
            acct = memory.per_owner_accounting()
            out["hbm_owned_mib"] = {
                name: round(b / MiB, 2)
                for name, b in acct["owners"].items()}
            out["hbm_untracked_mib"] = round(
                acct["untracked_bytes"] / MiB, 2)
        rss = memory.host_rss_bytes()
        if rss:
            out["host_rss_mib"] = round(rss / MiB, 2)
        self._mem_windows += 1
        mem_verdicts = self.memwatch.observe(
            self._mem_windows,
            device_bytes=live["max_device_bytes"],
            rss_bytes=rss or None)
        if mem_verdicts:
            out["mem_verdicts"] = [str(v) for v in mem_verdicts]
        static = self.static_report()
        if static is not None:
            step_ep = static["entrypoints"].get(static["step"], {})
            peak = step_ep.get("static_peak_bytes")
            if peak:
                chk = memory.cross_check(live["max_device_bytes"], peak,
                                         self.tol)
                out["hbm_static_mib"] = round(peak / MiB, 2)
                out["hbm_within_bound"] = chk["within_bound"]
            traffic = step_ep.get("collectives")
            if traffic:
                out["coll_bytes_per_step"] = traffic["total_bytes"]
                out["coll_bytes_by_axis"] = {
                    ax: v["bytes"]
                    for ax, v in traffic["per_axis"].items()}
                if window_secs and steps_in_window:
                    gbps = (traffic["total_bytes"] * steps_in_window
                            / window_secs / 1e9)
                    out["coll_gbps"] = round(gbps, 6)
            # schema v3: the step program's dataflow comm exposure
            # (parallel/overlap.collective_exposure) — the fraction of
            # collective bytes with no independent compute to hide
            # under; absent for programs with no jaxpr-level
            # collectives (GSPMD-inserted ones are invisible here)
            expo = step_ep.get("exposure")
            if expo and expo.get("exposed_comm_frac") is not None:
                out["exposed_comm_frac"] = expo["exposed_comm_frac"]
                out["overlap_ratio"] = expo["overlap_ratio"]
                out["overlap"] = bool(getattr(self.engine, "overlap",
                                              None))
        measured = getattr(self.engine, "telemetry_traffic", None)
        if measured is not None:
            out["coll_bytes_measured"] = measured()
        out.update(self._bubble)
        return out

    # -------------------------------------------------------- summary

    def run_summary(self) -> dict:
        """End-of-run record: static report + final live sample +
        bubble + compile counters + the last health pack (written next
        to the trace)."""
        static = self.static_report()
        live = memory.live_hbm_high_water()
        counts = compile_counts(self._entrypoints())
        snap = getattr(self.engine, "health_snapshot", None)
        out = {
            "engine": type(self.engine).__name__,
            "static": static,
            "hbm_live_mib": round(live["max_device_bytes"] / MiB, 2),
            # every device's share, not only the fullest one: live
            # array shards per device, and the allocator's own view
            # where the backend has one — what shows that a mesh of N
            # chips really holds state on all N
            "hbm_live_per_device": live["per_device"],
            "device_stats": memory.device_memory_stats(),
            "compile_counts": counts,
            "bubble": self._bubble or None,
            # the engine's last on-device health pack (grad/param
            # norms, update ratio, nonfinite; telemetry/health.py) —
            # None with health='off' or before the first step
            "health": snap() if snap is not None else None,
            # in-process goodput-ledger totals when the driver stamps
            # one (the cross-restart reduction lives in
            # goodput.run_goodput over the metrics JSONL)
            "goodput_ledger": (
                {"seconds": self.ledger.seconds(),
                 "counts": self.ledger.counts()}
                if self.ledger is not None else None),
        }
        if static is not None:
            peak = static["entrypoints"].get(
                static["step"], {}).get("static_peak_bytes")
            if peak:
                out["hbm_check"] = memory.cross_check(
                    live["max_device_bytes"], peak, self.tol)
        # the final per-owner decomposition (memory observatory):
        # telemetry.json carries who held what at the end of the run
        if memory.registered_owners():
            out["hbm_owners"] = memory.per_owner_accounting()
        return out

    def write_summary(self, trace_dir) -> Path:
        path = Path(trace_dir) / "telemetry.json"
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(self.run_summary(), indent=2,
                                   default=str))
        return path
