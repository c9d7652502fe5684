"""Runtime telemetry — structured, attributable time for every engine.

The reference's observability is bare `print` (SURVEY §5,
`/root/reference/train.py:135-137`); `metrics.py` made runs
machine-comparable but only at end-of-window granularity. This package
makes the *inside* of a step visible without xprof:

- `trace`        the one span recorder (`tracer().span("fwd", step=s)`):
                 a bounded in-memory ring at every level, `ss:<name>`
                 annotations in a live `jax.profiler` trace; JAX's
                 tracing, lowering and compiles, the process's start
                 and long garbage collections as entries of the same
                 ring; host wall-clock and, at the `spans`
                 level, device time via `block_until_ready` fences at
                 phase boundaries; exports JSONL and
                 Chrome-trace/Perfetto.
- `bubble`       pipeline bubble accounting: executed schedule traces
                 (or a two-point step-time calibration for the fused
                 engines) replayed against `parallel/verify.py`'s
                 static makespan tables.
- `collectives`  per-mesh-axis traffic (bytes, call counts) derived
                 from the same jaxpr walk `analysis/walker.py` does,
                 joined with measured step time at log points.
- `memory`       live HBM high-water via `jax.live_arrays()` / device
                 memory stats, cross-checked against the static
                 prediction `analysis/rules.py`'s memory rule uses;
                 round 20 adds the memory observatory: an ownership
                 registry (`register_owner`) decomposing resident bytes
                 per owner, host RSS, `forensics()` OOM dumps, and the
                 `MemoryWatch` leak/drift detector.
- `report`       `RunTelemetry`: the driver-facing aggregator that
                 turns all of the above plus retrace/recompile counters
                 into per-step-line fields.
- `health`       on-device training-health pack (grad/param norms,
                 update ratio, nonfinite sentinel) computed INSIDE
                 every engine's compiled step, plus the host-side
                 `HealthMonitor` + guarded-step policy (round 7).
- `anomaly`      streaming detectors over the health series: robust
                 EWMA z-scores (loss/grad spikes), divergence,
                 dead-layer; verdict -> action policy.
- `sketch`       mergeable log-bucketed histogram sketches — streaming
                 p50/p95/p99 in constant memory, serialized as
                 schema-v7 "monitor" events, merged across processes.
- `monitor`      the live telemetry plane (round 12): /status.json +
                 /metrics endpoints (--monitor-port), SLO burn-rate
                 alerts (--slo), anomaly flight recorder
                 (--flight-recorder), and the --live JSONL tailer.
- `fleet`        fleet observability (round 13): `FleetCollector`
                 aggregates N replicas (polled endpoints and/or
                 tailed JSONLs) into merged quantiles, fleet SLO burn,
                 per-replica breakdown, straggler detection (schema-v8
                 "straggler" events), and a replica-labelled
                 /status.json + /metrics of its own (--fleet).
- `python -m shallowspeed_tpu.telemetry --validate f.jsonl ...`
                 schema gate for committed `docs_runs/*.jsonl` traces
                 (pre-commit hook); `--live f.jsonl [--once]` renders
                 the live status view of a growing metrics file.

Levels: `off` (every span reaches the tracer's bounded ring, and the
profiler's trace as `ss:<name>` while a session is live; no file, no
fences), `steps` (spans also stream to spans.jsonl; host timestamps
only, the async dispatch pipeline is preserved), `spans` (device
fences at span exits: accurate attributed time, serialized dispatch —
the documented measurement mode).
"""

# trace has no jax/numpy imports at module level; the heavier modules
# (collectives/memory/report pull in jax + analysis.walker) resolve
# lazily so `python -m shallowspeed_tpu.telemetry --validate` — the
# pre-commit hook — stays a millisecond stdlib-only run.
from shallowspeed_tpu.telemetry.trace import (  # noqa: F401
    Tracer, configure, spanned, tracer)

_LAZY = {
    "static_bubble": "bubble", "trace_bubble": "bubble",
    "two_point_bubble": "bubble",
    "collective_traffic": "collectives",
    "device_memory_stats": "memory", "live_hbm_high_water": "memory",
    # memory observatory (round 20): per-owner HBM accounting, host
    # RSS, OOM forensics, leak/drift watch
    "register_owner": "memory", "unregister_owner": "memory",
    "clear_owners": "memory", "registered_owners": "memory",
    "per_owner_accounting": "memory", "top_live_arrays": "memory",
    "host_rss_bytes": "memory", "forensics": "memory",
    "MemoryWatch": "memory",
    "RunTelemetry": "report",
    # training health (round 7): on-device numerics pack + host monitor
    "HealthMonitor": "health", "grad_health": "health",
    "update_health": "health", "merge_packs": "health",
    "fetch_pack": "health",
    "AnomalyDetector": "anomaly", "GuardPolicy": "anomaly",
    "RobustEWMA": "anomaly", "Verdict": "anomaly",
    # goodput (round 9)
    "GoodputLedger": "goodput", "run_goodput": "goodput",
    # live telemetry plane (round 12): streaming sketches, /status +
    # /metrics endpoints, SLO burn-rate alerts, flight recorder
    "LogHistogram": "sketch", "MetricSketches": "sketch",
    "Monitor": "monitor", "StatusServer": "monitor",
    "FlightRecorder": "monitor", "SloRule": "monitor",
    "parse_slos": "monitor", "FileTailer": "monitor",
    "PortInUseError": "monitor", "prom_escape": "monitor",
    # fleet observability (round 13): multi-replica collector,
    # straggler detection, fleet endpoints
    "FleetCollector": "fleet", "Replica": "fleet",
    "format_fleet_status": "fleet",
    "request_timeline": "report",
    # distributed request tracing (round 16): trace context, the
    # cross-process stitcher, the per-request latency waterfall
    "new_trace_id": "tracing", "new_span_id": "tracing",
    "stitch": "tracing", "goodput_block": "tracing",
    "PHASE_COMPONENT": "tracing",
    "request_waterfall": "report",
    # continuous profiling plane (round 17): always-on host sampler,
    # span-tagged phase attribution, trigger-armed capture windows,
    # the single jax.profiler entry point, flamegraph reduction
    "SamplingProfiler": "profiler", "ProfilerPlane": "profiler",
    "CaptureWindow": "profiler", "device_trace_ctx": "profiler",
    "merge_profiles": "profiler",
    "flame_tree": "profiler", "profile_main": "profiler",
}


def __getattr__(name):
    mod = _LAZY.get(name)
    if mod is None:
        raise AttributeError(name)
    import importlib

    return getattr(importlib.import_module(
        f"shallowspeed_tpu.telemetry.{mod}"), name)
