"""CLI: validate committed JSONL, reduce a run's goodput ledger, or
watch a run live.

    python -m shallowspeed_tpu.telemetry --validate docs_runs/*.jsonl
    python -m shallowspeed_tpu.telemetry --validate docs_runs/
    python -m shallowspeed_tpu.telemetry --goodput run/metrics.jsonl
    python -m shallowspeed_tpu.telemetry --goodput run/router.jsonl \
        run/replica_r0.jsonl run/replica_r1.jsonl
    python -m shallowspeed_tpu.telemetry --trace-stitch \
        run/router.jsonl run/replica_r0.jsonl run/replica_r1.jsonl \
        --out stitched.json
    python -m shallowspeed_tpu.telemetry --profile run/metrics.jsonl \
        --out flame.json
    python -m shallowspeed_tpu.telemetry --gaps run/profile_dir
    python -m shallowspeed_tpu.telemetry --live run/metrics.jsonl
    python -m shallowspeed_tpu.telemetry --live f.jsonl --once
    python -m shallowspeed_tpu.telemetry --fleet http://127.0.0.1:9100 \
        http://127.0.0.1:9101 --port 9200
    python -m shallowspeed_tpu.telemetry --fleet r0.jsonl r1.jsonl --once

--validate is the pre-commit gate for committed `docs_runs/*.jsonl`
snapshots — a pure-stdlib check that costs only the package import
(~1 s), not a trace of anything. --goodput prints the run-level
wall-clock decomposition (goodput + named losses) of one metrics
JSONL, including runs that span supervisor restarts; extra files
after the first are replica logs joined BY TRACE ID into the
per-request waterfall (tracing) block. --trace-stitch joins a
router log + N replica logs (schema v11 trace context) on one
skew-corrected timeline and writes a Perfetto-loadable Chrome trace
(--out) with per-replica tracks and a per-request journey track —
queue-wait -> dispatch -> prefill -> decode -> failover gap ->
re-prefill -> decode -> finish (telemetry/tracing.py). --gaps reduces
a jax.profiler trace (a driver's --profile-dir, a capture window) to
each device's busy and idle seconds and the idle seconds by the
program span (`ss:<name>`) open at the time: why the chip waited
(telemetry/profiler.py). --live tails a
GROWING metrics JSONL and renders the same view the --monitor-port
/status.json endpoint serves (streaming sketch quantiles, goodput so
far, health, SLO burn rates with --slo) — live monitoring for runs
started without an endpoint; --once renders the current state and
exits (the pre-commit smoke mode). --fleet aggregates N replicas
(status URLs and/or metrics JSONL files) into one fleet view — merged
quantiles, per-replica breakdown, fleet SLO burn, straggler detection
— optionally re-served on --port as the fleet's own /status.json +
/metrics (telemetry/fleet.py).
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="python -m shallowspeed_tpu.telemetry")
    g = p.add_mutually_exclusive_group(required=True)
    g.add_argument("--validate", nargs="+", metavar="PATH",
                   help="JSONL files (or directories scanned for "
                        "*.jsonl) to check against the telemetry/"
                        "metrics schema")
    g.add_argument("--goodput", nargs="+", metavar="JSONL",
                   help="reduce one metrics JSONL to the goodput "
                        "report (wall-clock decomposition + losses, "
                        "per-failure-class MTTR, availability, the "
                        "injected-fault tally on chaos drills, and "
                        "p50/p95 ttft/tpot on serving runs with "
                        "schema-v6 request events); extra files are "
                        "replica logs joined by trace id into the "
                        "per-request waterfall block (schema v11)")
    g.add_argument("--trace-stitch", nargs="+", metavar="JSONL",
                   help="join a router log + N replica logs on one "
                        "skew-corrected timeline (schema-v11 trace "
                        "context; per-stanza offsets fitted from the "
                        "router's dispatch/ack pairs) and write a "
                        "Perfetto-loadable Chrome trace to --out; "
                        "prints the clock fit and each request's "
                        "latency waterfall")
    g.add_argument("--profile", nargs="+", metavar="JSONL",
                   help="reduce the schema-v12 'profile' events of one "
                        "or more metrics JSONLs (the host sampling "
                        "profiler's cumulative snapshots; multiple "
                        "files/stanzas merge replica-prefixed) to a "
                        "flamegraph JSON (--out) + a printed "
                        "top-frames/phases summary")
    g.add_argument("--gaps", metavar="TRACE",
                   help="a jax.profiler trace directory or "
                        ".xplane.pb: device busy/idle seconds and the "
                        "idle seconds by innermost program span")
    g.add_argument("--live", metavar="JSONL",
                   help="tail a growing metrics JSONL and render the "
                        "live status view (the /status.json surface "
                        "for endpoint-less runs); Ctrl-C exits")
    g.add_argument("--fleet", nargs="+", metavar="TARGET",
                   help="aggregate N replicas into one fleet view: "
                        "http(s) targets are polled /status.json + "
                        "/sketches.json endpoints, anything else is a "
                        "metrics JSONL to tail (telemetry/fleet.py) — "
                        "merged quantiles, per-replica breakdown, "
                        "fleet SLO burn, straggler detection")
    p.add_argument("--once", action="store_true",
                   help="with --live/--fleet: render the current "
                        "state once and exit instead of following")
    p.add_argument("--slo", default="",
                   help="with --live/--fleet: evaluate these SLOs "
                        "over the (merged) stream (telemetry/monitor "
                        "DSL, e.g. 'ttft_p95_ms<500,"
                        "availability>0.99')")
    p.add_argument("--interval", type=float, default=2.0,
                   help="with --live/--fleet: seconds between renders")
    p.add_argument("--port", type=int, default=None,
                   help="with --fleet: ALSO serve the fleet's own "
                        "/status.json + /metrics (replica-labelled) "
                        "here (0 = free port)")
    p.add_argument("--log-file", default=None,
                   help="with --fleet: append straggler/alert events "
                        "(schema v8) to this JSONL")
    p.add_argument("--out", default=None, metavar="PATH",
                   help="with --trace-stitch: where the Chrome trace "
                        "JSON lands (default: stitched_trace.json "
                        "next to the first input); with --profile: "
                        "where the flamegraph JSON lands (omitted = "
                        "summary only)")
    args = p.parse_args(argv)

    if args.profile:
        from shallowspeed_tpu.telemetry.profiler import profile_main

        return profile_main(args.profile, out=args.out)

    if args.gaps:
        from shallowspeed_tpu.telemetry.profiler import gaps_main

        return gaps_main(args.gaps)

    if args.trace_stitch:
        from shallowspeed_tpu.telemetry.tracing import stitch_main

        out = args.out
        if out is None:
            out = str(Path(args.trace_stitch[0]).parent
                      / "stitched_trace.json")
        return stitch_main(args.trace_stitch, out=out)

    if args.fleet:
        from shallowspeed_tpu.telemetry.fleet import fleet_main

        return fleet_main(args.fleet, slos=args.slo, once=args.once,
                          interval=args.interval, port=args.port,
                          log_file=args.log_file)

    if args.live:
        from shallowspeed_tpu.telemetry.monitor import live_main

        return live_main(args.live, slos=args.slo, once=args.once,
                         interval=args.interval)

    if args.goodput:
        from shallowspeed_tpu.telemetry.goodput import (format_report,
                                                        run_goodput)

        print(format_report(run_goodput(
            args.goodput[0], extra_paths=args.goodput[1:])))
        return 0

    from shallowspeed_tpu.telemetry.schema import validate_file

    files: list[Path] = []
    for raw in args.validate:
        path = Path(raw)
        if path.is_dir():
            files.extend(sorted(path.glob("*.jsonl")))
        else:
            files.append(path)
    if not files:
        print("no .jsonl files to validate")
        return 0
    problems = []
    for f in files:
        if not f.exists():
            problems.append(f"{f}: no such file")
            continue
        problems.extend(validate_file(f))
    for prob in problems:
        print(prob, file=sys.stderr)
    print(f"validated {len(files)} file(s): "
          f"{'OK' if not problems else f'{len(problems)} problem(s)'}")
    return 1 if problems else 0


if __name__ == "__main__":
    raise SystemExit(main())
