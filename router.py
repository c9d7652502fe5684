"""Fleet serving driver: an SLO-aware, fault-tolerant router over N
`serve.py --serve` replica processes.

The serving counterpart of `python -m shallowspeed_tpu.elastic`: where
the elastic supervisor restarts ONE training job from checkpoint, this
drives a FLEET of decode replicas and makes replica failure invisible
to clients — requests that were mid-decode on a killed replica
re-dispatch (seeded, idempotent) to a surviving one and their streams
continue token-identical to the solo `generate()` oracle. Pieces (all
in `shallowspeed_tpu/serving/router.py`):

- a `FleetCollector` + fleet `/status.json` endpoint the replicas
  self-register with (`--monitor-port`, default 0 = free port) — also
  the router's admission-weight source;
- per-replica circuit breakers, per-request deadlines/timeouts with
  failover, fleet-edge backpressure (typed reject + retry-after);
- classified respawn with per-class backoff (elastic.RestartPolicy),
  hang detection off each replica's heartbeat file;
- burn-driven autoscaling (`--autoscale`): sustained critical ttft
  burn spawns a replica, sustained idle drains one gracefully
  (deregistration included).

Requests use serve.py's JSONL format (ids, prompts or `prompt_len`
demos, per-request sampler/seed, `at` arrival offsets). Every routing
decision lands in `--log-file` (schema v10: "route"/"failover"/
"scale" events, breaker + restart_downtime ledger stamps, fleet-edge
"request" records), so

    python -m shallowspeed_tpu.telemetry --goodput run/router.jsonl

reports request percentiles, per-replica MTTR, and fleet availability
from the router log alone — and, with the per-replica logs appended,
the per-request latency waterfall block (schema v11 trace context).
The whole fleet's logs stitch onto one skew-corrected timeline:

    python -m shallowspeed_tpu.telemetry --trace-stitch \
        run/router.jsonl run/replica_r*.jsonl --out trace.json

(Perfetto-loadable; every failover visible as a gap on the failed-over
request's journey track.) Fleet chaos drills: `--chaos-fleet
'r0=kill@6;r1=stall@4:0.5' --chaos-state DIR` hands each named
replica its own seeded fault plan.

    python router.py --replicas 3 --requests reqs.jsonl \
        --log-file run/router.jsonl --slo 'ttft_p95_ms<500' \
        --autoscale --max-replicas 4 --hang-timeout 10
"""

from __future__ import annotations

import argparse
import json
import os
import shlex
import sys
import time
from collections import deque
from pathlib import Path


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    m = p.add_argument_group("model (forwarded to every replica)")
    m.add_argument("--vocab", type=int, default=256)
    m.add_argument("--d-model", type=int, default=64)
    m.add_argument("--n-heads", type=int, default=4)
    m.add_argument("--n-layers", type=int, default=2)
    m.add_argument("--max-seq", type=int, default=512)
    m.add_argument("--rope", action="store_true")
    m.add_argument("--init-seed", type=int, default=0)
    m.add_argument("--ckpt", default=None)
    s = p.add_argument_group("serving (forwarded to every replica)")
    s.add_argument("--n-blocks", type=int, default=128)
    s.add_argument("--block-size", type=int, default=16)
    s.add_argument("--slots", type=int, default=4)
    s.add_argument("--prefill-chunk", type=int, default=64)
    s.add_argument("--prefix-cache", default="on",
                   choices=["off", "on"],
                   help="prefix caching on every replica (serve.py "
                        "--prefix-cache) AND sticky prefix-affinity "
                        "routing router-side: prompts fingerprint by "
                        "their leading aligned chunks and a replica "
                        "that already served a prefix earns a bounded "
                        "dispatch bonus, so shared-prompt traffic "
                        "lands where its KV blocks already live")
    s.add_argument("--replica-args", default="",
                   help="extra raw serve.py args appended to every "
                        "replica's command (shlex-split), e.g. "
                        "'--weight-quant int8 --spec-k 4'")
    s.add_argument("--profile", default="off",
                   choices=["off", "host", "host+device"],
                   help="continuous profiling plane (telemetry/"
                        "profiler), forwarded to every replica "
                        "(serve.py --profile) AND run router-side: "
                        "each replica streams schema-v12 'profile' "
                        "events the FleetCollector merges into a "
                        "replica-labelled fleet flamegraph "
                        "(/profile.json on the fleet endpoint), the "
                        "router samples its own dispatch loop into "
                        "--log-file, and a firing straggler event "
                        "arms a router-side capture window")
    f = p.add_argument_group("fleet")
    f.add_argument("--replicas", type=int, default=2,
                   help="initial replica count")
    f.add_argument("--min-replicas", type=int, default=1)
    f.add_argument("--max-replicas", type=int, default=4)
    f.add_argument("--autoscale", action="store_true",
                   help="close the loop: sustained critical SLO burn "
                        "spawns a replica, sustained idle drains one "
                        "(graceful, deregistered, zero drops)")
    f.add_argument("--slo", default="",
                   help="fleet-edge SLOs over the router's own "
                        "observations (monitor DSL, e.g. "
                        "'ttft_p95_ms<500,availability>0.99') — also "
                        "the autoscale burn signal")
    f.add_argument("--scale-hold", type=float, default=5.0,
                   help="seconds a critical burn must persist before "
                        "a scale-up")
    f.add_argument("--idle-drain", type=float, default=30.0,
                   help="seconds of fleet idle before a scale-down "
                        "drain")
    f.add_argument("--scale-cooldown", type=float, default=10.0)
    r = p.add_argument_group("router")
    r.add_argument("--monitor-port", type=int, default=0,
                   help="the fleet endpoint (collector /status.json + "
                        "/metrics + POST /register|/deregister); "
                        "replicas self-register here. 0 = free port, "
                        "printed at start")
    r.add_argument("--log-file", default=None,
                   help="router metrics JSONL (schema v10 route/"
                        "failover/scale events + ledger stamps + "
                        "fleet-edge request records)")
    r.add_argument("--requests", default="-",
                   help="JSONL request file (serve.py format), or - "
                        "for stdin")
    r.add_argument("--request-timeout", type=float, default=30.0,
                   help="seconds without new tokens before a request "
                        "fails over to another replica")
    r.add_argument("--deadline", type=float, default=None,
                   help="default per-request e2e deadline in seconds "
                        "(typed failure past it); per-request "
                        "'deadline' fields in the JSONL override")
    r.add_argument("--queue-budget", type=int, default=256,
                   help="router pending-queue budget; past it submit "
                        "rejects typed with retry-after")
    e = p.add_argument_group("supervision (elastic taxonomy)")
    e.add_argument("--hang-timeout", type=float, default=None,
                   help="kill+respawn a replica whose heartbeat goes "
                        "stale this long")
    e.add_argument("--term-grace", type=float, default=5.0)
    e.add_argument("--max-restarts", type=int, default=3,
                   help="per-replica restart budget (per-class "
                        "jittered backoff, elastic.RestartPolicy)")
    e.add_argument("--backoff", type=float, default=1.0)
    c = p.add_argument_group("chaos (fleet drills)")
    c.add_argument("--chaos-fleet", default="",
                   help="per-replica fault plans: "
                        "'r0=kill@6;r1=stall@4:0.5' — each named "
                        "replica runs its own seeded plan "
                        "(serve.py --chaos); faults index engine "
                        "ticks")
    c.add_argument("--chaos-state", default="",
                   help="fired-marker base dir (per-replica subdirs; "
                        "MUST survive respawns — required with "
                        "--chaos-fleet)")
    c.add_argument("--chaos-seed", type=int, default=0)
    p.add_argument("--run-dir", default=None,
                   help="replica logs + heartbeat files land here "
                        "(default: the --log-file's directory, else "
                        "a tempdir)")
    p.add_argument("--platform", default=None,
                   help="jax platform override forwarded to replicas "
                        "(e.g. cpu)")
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    import tempfile

    from shallowspeed_tpu import chaos, runtime
    from shallowspeed_tpu.metrics import MetricsLogger
    from shallowspeed_tpu.serving.router import (FleetOverloaded,
                                                 ReplicaProc, Router)
    from shallowspeed_tpu.telemetry.fleet import FleetCollector
    from shallowspeed_tpu.telemetry.monitor import StatusServer
    from shallowspeed_tpu.telemetry.report import request_summary

    from serve import load_requests

    chaos_map = {}
    if args.chaos_fleet:
        if not args.chaos_state:
            raise SystemExit("--chaos-fleet needs --chaos-state "
                             "(fired-fault markers must survive "
                             "respawns, or every respawned replica "
                             "re-fires every fault)")
        chaos_map = chaos.parse_fleet_spec(args.chaos_fleet)
    run_dir = Path(args.run_dir) if args.run_dir else (
        Path(args.log_file).parent if args.log_file
        else Path(tempfile.mkdtemp(prefix="router_")))
    run_dir.mkdir(parents=True, exist_ok=True)
    reqs = ([] if args.requests == "-" and sys.stdin.isatty()
            else load_requests(args.requests, args.vocab))

    metrics = MetricsLogger(args.log_file, kind="router",
                            replicas=args.replicas, slo=args.slo,
                            autoscale=args.autoscale)
    collector = FleetCollector()
    fleet_srv = StatusServer(collector, port=args.monitor_port)
    fleet_url = f"http://{fleet_srv.host}:{fleet_srv.port}"
    print(json.dumps({"event": "fleet_listening",
                      "url": fleet_srv.url("/status.json")}),
          flush=True)
    collector.start(poll=0.5)

    # One chip per replica process. A chip belongs to one process at a
    # time, so replicas that inherit this environment unchanged would
    # each open every chip and all but the first would fail. The count
    # comes from a throwaway child: this parent never touches a backend.
    n_chips = None
    if args.platform != "cpu":
        dev = runtime.probe_device_stamp()
        print(json.dumps({"event": "device", **dev}), flush=True)
        if dev["platform"] == "tpu":
            n_chips = dev["count"]
            want = max(args.replicas,
                       args.max_replicas if args.autoscale else 0)
            if want > n_chips:
                raise SystemExit(
                    f"router: {want} replicas need {want} chips (one "
                    f"process per chip) but this host has {n_chips} "
                    f"{dev['kind']}; lower --replicas/--max-replicas "
                    f"or pass --platform cpu")
    chip_of: dict[str, int] = {}
    router_box: list = []         # the Router, once constructed

    def replica_env(name: str):
        if n_chips is None:
            return None           # inherit: no chips to divide
        live = (set(router_box[0].replica_names()) if router_box
                else set(chip_of))
        used = {c for n, c in chip_of.items() if n in live}
        free = [c for c in range(n_chips) if c not in used]
        if not free:
            raise RuntimeError(
                f"no free chip for replica {name}: {n_chips} chip(s), "
                f"held by {sorted(n for n in chip_of if n in live)}")
        chip_of[name] = free[0]
        return {**os.environ, **runtime.one_chip_env(free[0])}

    serve_py = str(Path(__file__).resolve().parent / "serve.py")
    model_args = ["--vocab", str(args.vocab),
                  "--d-model", str(args.d_model),
                  "--n-heads", str(args.n_heads),
                  "--n-layers", str(args.n_layers),
                  "--max-seq", str(args.max_seq),
                  "--init-seed", str(args.init_seed),
                  "--n-blocks", str(args.n_blocks),
                  "--block-size", str(args.block_size),
                  "--slots", str(args.slots),
                  "--prefill-chunk", str(args.prefill_chunk),
                  "--prefix-cache", args.prefix_cache]
    if args.rope:
        model_args.append("--rope")
    if args.ckpt:
        model_args += ["--ckpt", args.ckpt]
    if args.platform:
        model_args += ["--platform", args.platform]
    if args.profile != "off":
        model_args += ["--profile", args.profile]
    model_args += shlex.split(args.replica_args)

    # router-side profiling plane (round 17): the router's own host
    # sampler (dispatch loop, progress polls) streams into --log-file;
    # a firing straggler event arms a bounded capture window next to it
    from shallowspeed_tpu.telemetry import profiler as profiler_mod

    plane = profiler_mod.from_args(args, metrics, out_dir=run_dir)
    if plane is not None:
        collector.straggler_listeners.append(plane.on_straggler)

    def spawn(name: str) -> ReplicaProc:
        hb = str(run_dir / f"hb_{name}")
        child_argv = [sys.executable, serve_py, "--serve",
                      "--monitor-port", "0",
                      "--fleet-register", fleet_url,
                      "--replica", name,
                      "--log-file", str(run_dir / f"replica_{name}"
                                                  ".jsonl"),
                      "--heartbeat-file", hb] + model_args
        if name in chaos_map:
            child_argv += ["--chaos", chaos_map[name],
                           "--chaos-state",
                           str(Path(args.chaos_state) / name),
                           "--chaos-seed", str(args.chaos_seed)]
        return ReplicaProc(name, child_argv, collector,
                           heartbeat_file=hb,
                           hang_timeout=args.hang_timeout,
                           term_grace=args.term_grace,
                           stdout_path=str(run_dir
                                           / f"replica_{name}.out"),
                           env=replica_env(name))

    router = Router(
        spawn, n_replicas=args.replicas, collector=collector,
        metrics=metrics, slos=args.slo,
        queue_budget=args.queue_budget,
        request_timeout=args.request_timeout,
        default_deadline_s=args.deadline,
        progress_interval=0.2,
        policy_kw=dict(max_restarts=args.max_restarts,
                       backoff=args.backoff, jitter=0.1),
        autoscale=args.autoscale, min_replicas=args.min_replicas,
        max_replicas=args.max_replicas,
        scale_hold_s=args.scale_hold, idle_drain_s=args.idle_drain,
        scale_cooldown_s=args.scale_cooldown,
        sticky=(args.prefix_cache == "on"),
        sticky_block=args.block_size)
    router_box.append(router)

    t0 = time.time()
    i = 0
    retry: deque = deque()        # (ready_at, request) after overload
    reported = 0
    try:
        while i < len(reqs) or retry or router.unfinished():
            now = time.time() - t0

            def _offer(r):
                nonlocal retry
                try:
                    router.submit(
                        r["prompt"], r["max_new"],
                        temperature=r.get("temperature", 0.0),
                        seed=r.get("seed", 0), rid=r["id"],
                        deadline_s=r.get("deadline", None))
                except FleetOverloaded as e:
                    # fleet-edge backpressure: honor retry-after
                    retry.append((now + e.retry_after, r))
                except (KeyError, TypeError, ValueError) as e:
                    print(json.dumps(
                        {"event": "error", "id": r.get("id"),
                         "error": f"{type(e).__name__}: {e}"}),
                        flush=True)

            while i < len(reqs) and reqs[i]["at"] <= now:
                _offer(reqs[i])
                i += 1
            # entries are NOT ready_at-ordered (retry_after varies per
            # rejection) — scan the whole deque, not head-until-stuck
            for _ in range(len(retry)):
                ready_at, r = retry.popleft()
                if ready_at <= now:
                    _offer(r)
                else:
                    retry.append((ready_at, r))
            if not router.step():
                time.sleep(0.02)
            if not router.replica_names():
                # every replica retired (restart budgets exhausted):
                # nothing can ever become routable again — fail
                # EVERYTHING that remains (not-yet-offered arrivals,
                # the retry deque, and the router's own pending +
                # in-flight queues) instead of spinning forever;
                # every submitted id gets a terminal record
                dead = "fleet dead: every replica retired"
                for r in ([reqs[j] for j in range(i, len(reqs))]
                          + [r for _, r in retry]):
                    print(json.dumps(
                        {"event": "error", "id": r.get("id"),
                         "error": dead}), flush=True)
                retry.clear()
                i = len(reqs)
                router.fail_unfinished(dead)
                # fall through: the record loop below prints the
                # failed results, then the loop condition drains
            for rec in router.records[reported:]:
                reported += 1
                out = {"event": "result", **rec}
                if rec["status"] == "done":
                    out["tokens"] = [int(t) for t
                                     in router.results[rec["id"]]]
                print(json.dumps(out), flush=True)
    finally:
        wall = time.time() - t0
        done = [r for r in router.records if r["status"] == "done"]
        summary = request_summary(
            [r for r in done if "ttft_ms" in r]) or {}
        summary.update({
            "wall_s": round(wall, 3),
            "replicas": router.replica_names(),
            "counters": dict(router.counters),
        })
        print(json.dumps({"event": "summary", **summary}),
              flush=True)
        router.shutdown()
        if plane is not None:
            plane.close()
        collector.stop()
        fleet_srv.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
