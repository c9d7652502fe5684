"""Decode-server driver: continuous batching over the paged KV cache.

The serving counterpart of `train_lm.py` — builds a transformer LM
(seeded init or random-weights demo; real deployments load a
checkpoint via `--ckpt`), then serves a stream of requests through
`shallowspeed_tpu.serving.ServingEngine`: requests join and leave the
running decode batch between ticks (no recompiles after warmup), long
prompts prefill in chunks, one a step, with that step's decode tick
riding in the chunk's program (one program a step; a request's first
token reaches the host one step after its prompt's last chunk), and every
completion stamps a schema-v6 `"request"` SLO record (ttft/tpot/queue
depth/preemptions) into the metrics JSONL that
`python -m shallowspeed_tpu.telemetry --goodput` reduces to p50/p95.

Requests arrive as JSONL (`--requests FILE`, `-` = stdin), one object
per line:

    {"id": "r0", "prompt": [17, 3, 92], "max_new": 24}
    {"id": "r1", "prompt_len": 512, "prompt_seed": 7, "max_new": 16,
     "temperature": 1.0, "seed": 5, "at": 0.25}

`prompt` is explicit token ids; `prompt_len`(+`prompt_seed`) draws a
random prompt — the tokenizer-free demo path. `at` is the submission
offset in seconds from run start (default 0: submit immediately), so
a request file doubles as an offered-load trace.

Each completion prints one `{"event": "result", ...}` JSONL line to
stdout; the run ends with the request-latency summary
(`telemetry/report.request_summary`).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    m = p.add_argument_group("model")
    m.add_argument("--vocab", type=int, default=256)
    m.add_argument("--d-model", type=int, default=64)
    m.add_argument("--n-heads", type=int, default=4)
    m.add_argument("--n-layers", type=int, default=2)
    m.add_argument("--max-seq", type=int, default=512)
    m.add_argument("--rope", action="store_true")
    m.add_argument("--rope-theta", type=float, default=10000.0)
    m.add_argument("--norm", default="layernorm",
                   choices=["layernorm", "rmsnorm"])
    m.add_argument("--ffn", default="gelu", choices=["gelu", "swiglu"])
    m.add_argument("--d-ff", type=int, default=0,
                   help="dense FFN width (0 = 4 x d-model)")
    m.add_argument("--latent", type=int, nargs=4, default=None,
                   metavar=("RANK", "NOPE", "ROPE", "V"),
                   help="latent attention (MLA): kv_lora_rank and the "
                        "per-head qk_nope / qk_rope / v sizes; the paged "
                        "cache then holds one RANK + ROPE row a token a "
                        "layer (needs --rope)")
    m.add_argument("--routed-experts", type=int, nargs=4, default=None,
                   metavar=("EXPERTS", "PER_TOKEN", "SHARED", "WIDTH"),
                   help="dropless sigmoid-routed SwiGLU experts of WIDTH "
                        "with SHARED always-on ones, in every layer past "
                        "--dense-layers")
    m.add_argument("--routed-scale", type=float, default=1.0)
    m.add_argument("--dense-layers", type=int, default=0,
                   help="leading layers that keep the dense FFN")
    m.add_argument("--model-config", default=None, metavar="FILE",
                   help="a JSON object of TransformerConfig's own fields "
                        "that the flags above do not reach (n_kv_heads, "
                        "attn_head_dim, embed_scale, layers: one [window, "
                        "rotary] pair a layer, a state-space mixer's "
                        "ssm_heads / ssm_head_dim / ssm_state / ssm_groups "
                        "/ ssm_conv; any other field too), "
                        "laid over them, and `block_parts`: what each "
                        "block holds beyond the plain one (qk_norm, "
                        "attn_gate, post_norm)")
    m.add_argument("--init-seed", type=int, default=0,
                   help="weight-init seed for the demo model")
    m.add_argument("--ckpt", default=None,
                   help="checkpoint dir to load params from "
                        "(shallowspeed_tpu.checkpoint layout)")
    s = p.add_argument_group("serving")
    s.add_argument("--n-blocks", type=int, default=128)
    s.add_argument("--block-size", type=int, default=16)
    s.add_argument("--slots", type=int, default=4,
                   help="decode-slot capacity (the compiled tick's "
                        "fixed row count)")
    s.add_argument("--prefill-chunk", type=int, default=64)
    s.add_argument("--table-bucket", type=int, default=4)
    s.add_argument("--kv-quant", default="", choices=["", "int8"])
    s.add_argument("--weight-quant", default="",
                   choices=["", "int8", "fp8"],
                   help="quantized weight storage with fused dequant "
                        "(per-out-channel f32 scales; halves the "
                        "param sweep behind every decode tick)")
    s.add_argument("--spec-k", type=int, default=0,
                   help="speculative decoding: up to K self-drafted "
                        "(n-gram prompt-lookup) tokens per decoding "
                        "request per tick, verified in the same "
                        "compiled tick's free rows; 0 = off. Output "
                        "streams are token-identical to spec-off")
    s.add_argument("--spec-ngram", type=int, default=3,
                   help="longest n-gram the draft proposer matches")
    s.add_argument("--top-k", type=int, default=0)
    s.add_argument("--top-p", type=float, default=0.0)
    s.add_argument("--prefix-cache", default="on",
                   choices=["off", "on"],
                   help="content-addressed prefix caching: requests "
                        "sharing block-aligned prompt prefixes map the "
                        "shared KV blocks straight into their tables "
                        "(refcounted, copy-on-write at the tail) and "
                        "skip that prefill. Streams are token-identical "
                        "to off — off is the parity oracle")
    p.add_argument("--requests", default="-",
                   help="JSONL request file, or - for stdin (ignored "
                        "under --serve unless explicitly set)")
    p.add_argument("--serve", action="store_true",
                   help="replica mode: stay up and accept requests "
                        "over HTTP (POST /submit, GET /requests, "
                        "POST /drain on the monitor endpoint — "
                        "--monitor-port defaults to 0) until a drain "
                        "completes; the surface a fleet router "
                        "(router.py) drives")
    p.add_argument("--max-queue", type=int, default=256,
                   help="with --serve: typed EngineOverloaded "
                        "rejection past this many queued+running "
                        "requests (backpressure, not silent growth)")
    p.add_argument("--heartbeat-file", default=None,
                   help="liveness+health beat file (written ~5 Hz by "
                        "the serve loop; a chaos freeze fault stops "
                        "it) — the router's hang detection reads its "
                        "mtime, like the elastic supervisor's")
    p.add_argument("--log-file", default=None,
                   help="metrics JSONL (request/generate events)")
    p.add_argument("--log-every", type=int, default=16,
                   help="decode ticks between 'generate' stat lines")
    c = p.add_argument_group("chaos (shallowspeed_tpu.chaos)")
    c.add_argument("--chaos", default="",
                   help="tick-indexed fault plan for THIS server "
                        "(chaos DSL, e.g. 'stall@4:0.5,kill@9'; step "
                        "faults index engine ticks) — serving drills "
                        "of the recovery/observability stack")
    c.add_argument("--chaos-state", default="",
                   help="fired-fault marker dir (must survive "
                        "restarts under a supervisor)")
    c.add_argument("--chaos-seed", type=int, default=0)
    o = p.add_argument_group("live monitoring (telemetry/monitor)")
    o.add_argument("--replica", default=None,
                   help="replica label for fleet views: stamped on "
                        "the run_start line and served from "
                        "/status.json, so a FleetCollector names this "
                        "process in per-replica breakdowns and "
                        "straggler events")
    o.add_argument("--fleet-register", default=None, metavar="URL",
                   help="announce this replica's own monitor endpoint "
                        "to a fleet collector (POST URL/register; "
                        "needs --monitor-port)")
    o.add_argument("--monitor-port", type=int, default=None,
                   help="serve /status.json + /metrics (Prometheus "
                        "text) on 127.0.0.1:PORT while the run is "
                        "live (0 = pick a free port, printed at start)")
    o.add_argument("--slo", default="",
                   help="declarative SLOs evaluated over dual burn-"
                        "rate windows, e.g. "
                        "'ttft_p95_ms<500,availability>0.99'; state "
                        "transitions land as schema-v7 'alert' events")
    o.add_argument("--flight-recorder", type=int, default=0,
                   help="keep the last N metrics/span records in a "
                        "ring and dump flightrec_<step>.json on an "
                        "anomaly verdict, chaos fault, or SLO alert "
                        "(0 = off)")
    o.add_argument("--shed-load", action="store_true",
                   help="wire SLO alerts into Engine.on_alert: pause "
                        "admission while a critical burn persists "
                        "(default: alerts are telemetry-only)")
    o.add_argument("--profile", default="off",
                   choices=["off", "host", "host+device"],
                   help="continuous profiling plane (telemetry/"
                        "profiler): 'host' runs the always-on stack "
                        "sampler (schema-v12 'profile' events in the "
                        "metrics JSONL, /profile.json on the monitor "
                        "endpoint) and arms burn/fault/anomaly-"
                        "triggered capture windows (profcap_*.json); "
                        "'host+device' additionally wraps each "
                        "capture in a bounded jax.profiler device "
                        "trace")
    o.add_argument("--profile-hz", type=float, default=None,
                   help="host sampler rate (default 67 Hz — off the "
                        "100/50 Hz scheduler beats)")
    p.add_argument("--platform", default=None,
                   help="jax platform override (e.g. cpu)")
    return p.parse_args(argv)


def load_requests(path: str, vocab: int) -> list[dict]:
    import numpy as np

    raw = (sys.stdin.read() if path == "-"
           else Path(path).read_text())
    reqs = []
    for i, line in enumerate(raw.splitlines()):
        if not line.strip():
            continue
        rec = json.loads(line)
        rec.setdefault("id", f"r{i}")
        if "prompt" in rec:
            # explicit token ids are the caller's exact prompt — an
            # out-of-vocab id is an error, never a silent remap
            rec["prompt"] = np.asarray(rec["prompt"], np.int32)
            if rec["prompt"].size and (
                    int(rec["prompt"].min()) < 0
                    or int(rec["prompt"].max()) >= vocab):
                raise ValueError(
                    f"request {rec['id']!r}: prompt token ids must be "
                    f"in [0, {vocab}); got range "
                    f"[{int(rec['prompt'].min())}, "
                    f"{int(rec['prompt'].max())}]")
        else:
            # the tokenizer-free demo path draws in-vocab ids directly
            rng = np.random.default_rng(rec.get("prompt_seed", i))
            rec["prompt"] = rng.integers(
                0, vocab, rec["prompt_len"]).astype(np.int32)
        rec.setdefault("at", 0.0)
        reqs.append(rec)
    reqs.sort(key=lambda r: r["at"])
    return reqs


def main(argv=None) -> int:
    args = parse_args(argv)
    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    from shallowspeed_tpu import runtime

    runtime.enable_compile_cache()
    # what the run actually got: with no accelerator JAX falls back to
    # the CPU (kernels interpreted) with only a warning
    print(json.dumps({"event": "device", **runtime.device_stamp()}),
          flush=True)
    from shallowspeed_tpu.elastic import install_sigterm_exit
    from shallowspeed_tpu.metrics import MetricsLogger
    from shallowspeed_tpu.models import transformer as T
    from shallowspeed_tpu.serving import ServingEngine
    from shallowspeed_tpu.telemetry.report import request_summary

    # supervisor kill path (same contract as the train drivers):
    # SIGTERM becomes SystemExit so the finally block below flushes
    # the request/ledger tail and the final summary line before the
    # supervisor's SIGKILL deadline — a killed server must leave a
    # reducible metrics file, not a truncated one
    install_sigterm_exit()

    kinds = {}
    if args.latent:
        kinds.update(zip(("kv_lora_rank", "qk_nope_head_dim",
                          "qk_rope_head_dim", "v_head_dim"), args.latent))
    if args.routed_experts:
        kinds.update(zip(("n_routed_experts", "moe_top_k",
                          "n_shared_experts", "expert_d_ff"),
                         args.routed_experts),
                     routed_scaling_factor=args.routed_scale,
                     first_dense_layers=args.dense_layers)
    fields = dict(
        vocab=args.vocab, d_model=args.d_model, n_heads=args.n_heads,
        n_layers=args.n_layers, max_seq=args.max_seq, rope=args.rope,
        rope_theta=args.rope_theta, norm=args.norm, ffn=args.ffn,
        d_ff=args.d_ff, **kinds)
    parts = ()
    if args.model_config:
        extra = json.loads(Path(args.model_config).read_text())
        parts = tuple(extra.pop("block_parts", ()))
        if "layers" in extra:       # JSON has no tuples; the config is hashed
            extra["layers"] = tuple(map(tuple, extra["layers"]))
        fields.update(extra)
    cfg = T.TransformerConfig(**fields)
    if args.ckpt:
        from shallowspeed_tpu import checkpoint

        params = checkpoint.restore(args.ckpt)["params"]
    else:
        params = jax.device_put(T.init(cfg, seed=args.init_seed,
                                       parts=parts))
    # replica mode: requests arrive over HTTP; the default "-" must
    # not block on a subprocess's empty stdin
    reqs = ([] if args.serve and args.requests == "-"
            else load_requests(args.requests, cfg.vocab))
    if args.serve and args.monitor_port is None:
        args.monitor_port = 0
    run_info = dict(kind="serve", vocab=cfg.vocab,
                    d_model=cfg.d_model, n_layers=cfg.n_layers,
                    n_blocks=args.n_blocks, block_size=args.block_size,
                    slots=args.slots, prefill_chunk=args.prefill_chunk,
                    kv_quant=args.kv_quant,
                    weight_quant=args.weight_quant,
                    spec_k=args.spec_k,
                    prefix_cache=args.prefix_cache)
    if args.replica:
        run_info["replica"] = args.replica
    metrics = MetricsLogger(args.log_file, **run_info)
    # chaos (serving drills): tick-indexed faults through the same
    # plan machinery the train drivers use; fault stamps land in this
    # replica's metrics JSONL so fleet views see what was injected
    from shallowspeed_tpu import chaos

    chaos.setup(args.chaos, seed=args.chaos_seed,
                state_dir=args.chaos_state or None,
                log_file=args.log_file)
    eng = ServingEngine(
        params, cfg, n_blocks=args.n_blocks,
        block_size=args.block_size, max_slots=args.slots,
        prefill_chunk=args.prefill_chunk,
        table_bucket=args.table_bucket, kv_quant=args.kv_quant,
        weight_quant=args.weight_quant,
        spec_k=args.spec_k, spec_ngram=args.spec_ngram,
        top_k=args.top_k, top_p=args.top_p, metrics=metrics,
        log_every=args.log_every,
        prefix_cache=(args.prefix_cache == "on"))

    # live telemetry plane: /status.json + /metrics endpoint, SLO
    # burn-rate alerts (optionally shedding load via Engine.on_alert),
    # anomaly flight recorder — all fed by the same metrics lines the
    # JSONL gets (MetricsLogger.monitor). In --serve mode the request
    # gateway is grafted onto the SAME endpoint (POST /submit, GET
    # /requests, POST /drain), so one registered URL serves both the
    # fleet's observation polls and the router's dispatch.
    from shallowspeed_tpu.telemetry.monitor import (close_monitor,
                                                    from_args)

    gateway = None
    if args.serve:
        from shallowspeed_tpu.serving.router import RequestGateway

        gateway = RequestGateway(max_queue=args.max_queue)
    mon, server = from_args(args, metrics, extra=gateway)
    if server is not None:
        print(json.dumps({"event": "monitor_listening",
                          "url": server.url("/status.json")}),
              flush=True)
    if mon is not None and args.shed_load:
        mon.alert_listeners.append(eng.on_alert)
    if mon is not None:
        # memory observatory (round 20): block exhaustion trips a full
        # forensic flight dump — per-owner HBM bytes, top arrays, the
        # allocator snapshot, block-table widths, the in-flight set.
        # The listener fires BEFORE the engine stamps its oom ledger
        # line, so this rich payload wins the flight recorder's
        # (reason="oom", step=tick) dedup over the bare ledger trigger.
        eng.oom_listeners.append(
            lambda en, exc: mon.memory_flight_dump(
                en.oom_forensics(exc), step=en.counters["ticks"]))
    # continuous profiling plane (round 17): the always-on host stack
    # sampler streams schema-v12 "profile" snapshots into the same
    # metrics JSONL, and critical SLO burns / chaos fault stamps /
    # anomaly verdicts arm bounded high-rate capture windows
    # (profcap_<step>.json next to the flight-recorder dumps)
    from shallowspeed_tpu.telemetry import profiler as profiler_mod
    from shallowspeed_tpu.telemetry.trace import tracer

    plane = profiler_mod.from_args(args, metrics)
    if plane is not None:
        chaos.add_observer(plane.on_fault)
        if mon is not None:
            mon.profiler = plane
            mon.alert_listeners.append(plane.on_alert)
    if args.fleet_register:
        # announce this replica to a fleet collector (best effort —
        # the fleet may come up after us and poll-register instead)
        if server is None:
            p_err = ("--fleet-register needs --monitor-port (the "
                     "fleet polls our endpoint)")
            raise SystemExit(p_err)
        import urllib.request

        body = json.dumps({
            "url": server.url("/status.json"),
            "name": args.replica or f"pid{__import__('os').getpid()}",
        }).encode()
        try:
            urllib.request.urlopen(urllib.request.Request(
                args.fleet_register.rstrip("/") + "/register",
                data=body,
                headers={"Content-Type": "application/json"}),
                timeout=5).read()
        except Exception as e:
            print(json.dumps({"event": "error",
                              "error": f"fleet register failed: "
                                       f"{type(e).__name__}: {e}"}),
                  flush=True)

    t0 = time.time()
    i = 0
    reported: set[str] = set()
    drained_clean = False
    last_hb = 0.0
    try:
        while True:
            now = time.time() - t0
            if args.heartbeat_file and time.time() - last_hb > 0.2 \
                    and not chaos.heartbeat_frozen():
                # liveness + health beat (~5 Hz between engine steps):
                # the router's hang detection reads the mtime exactly
                # like the elastic supervisor's — a chaos freeze fault
                # stops the beats while the loop keeps serving, which
                # is the hang drill
                from shallowspeed_tpu.elastic import write_heartbeat

                try:
                    write_heartbeat(args.heartbeat_file, "ok")
                except OSError:
                    pass
                last_hb = time.time()
            while i < len(reqs) and reqs[i]["at"] <= now:
                r = reqs[i]
                i += 1
                try:
                    # a request line may carry its own trace id (an
                    # upstream edge's context); absent, the engine
                    # mints one so standalone lifecycle streams still
                    # stitch (schema v11)
                    eng.submit(r["prompt"], r["max_new"],
                               temperature=r.get("temperature", 0.0),
                               seed=r.get("seed", 0), rid=r["id"],
                               trace=r.get("trace"))
                except (KeyError, TypeError, ValueError) as e:
                    # one bad request (too long for max_seq/pool,
                    # duplicate id, missing/mistyped fields) must not
                    # kill the server — report it and keep draining
                    print(json.dumps(
                        {"event": "error", "id": r["id"],
                         "error": f"{type(e).__name__}: {e}"}))
            if gateway is not None:
                with tracer().span("gateway"):
                    gateway.pump(eng)
            if eng.pending():
                eng.step()
            elif i < len(reqs):
                time.sleep(min(0.05, max(0.0, reqs[i]["at"] - now)))
            elif gateway is not None \
                    and not gateway.drain_requested:
                time.sleep(0.02)        # idle replica: await HTTP work
            if gateway is not None:
                with tracer().span("gateway"):
                    gateway.publish(eng)
            for rec in eng.request_records[len(reported):]:
                reported.add(rec["id"])
                print(json.dumps({
                    "event": "result", "id": rec["id"],
                    "tokens": [int(t) for t in eng.results[rec["id"]]],
                    "ttft_ms": rec["ttft_ms"],
                    "tpot_ms": rec.get("tpot_ms")}), flush=True)
            if gateway is not None:
                if gateway.drain_requested and gateway.idle() \
                        and eng.drain():
                    drained_clean = True
                    break
            elif i >= len(reqs) and not eng.pending():
                break
        if drained_clean and args.fleet_register and server is not None:
            # clean drain completes with DEREGISTRATION — a drained
            # replica must not linger in the fleet as "unreachable",
            # burning availability forever (the old one-way register)
            import urllib.request

            try:
                urllib.request.urlopen(urllib.request.Request(
                    args.fleet_register.rstrip("/") + "/deregister",
                    data=json.dumps({
                        "url": server.url("/status.json"),
                        "name": args.replica or None}).encode(),
                    headers={"Content-Type": "application/json"}),
                    timeout=5).read()
            except Exception as e:
                print(json.dumps({"event": "error",
                                  "error": f"fleet deregister failed: "
                                           f"{type(e).__name__}: {e}"}),
                      flush=True)
    finally:
        # reached on clean drain AND on the SIGTERM SystemExit: the
        # summary line + the monitor's final sketch snapshot land in
        # the outputs either way, so a supervisor-killed server still
        # reduces (--goodput) and merges (schema-v7 monitor events)
        wall = time.time() - t0
        summary = request_summary(eng.request_records) or {}
        summary.update({
            "wall_s": round(wall, 3),
            "tok_per_sec": round(
                sum(r["tokens_out"] for r in eng.request_records)
                / max(wall, 1e-9), 2),
            "ticks": eng.counters["ticks"],
            "prefill_chunks": eng.counters["prefill_chunks"],
            "preemptions": eng.counters["preempted"],
            "shed_toggles": eng.counters["shed_toggles"],
            "spec_drafted": eng.counters["spec_drafted"],
            "spec_accepted": eng.counters["spec_accepted"],
            "pending_at_exit": eng.pending(),
            "drained": drained_clean,
            "executables": eng.executable_counts(),
            # every layer group's pool together (`eng.allocs`)
            "blocks_free_at_drain":
                f"{sum(al.n_free for al in eng.allocs)}"
                f"/{sum(al.n_usable for al in eng.allocs)}",
            # with the prefix cache on, finished requests donate their
            # blocks to the cold list: free + cold == usable at drain
            "blocks_cold_at_drain": sum(al.n_cold for al in eng.allocs),
        })
        print(json.dumps({"event": "summary", **summary}), flush=True)
        if plane is not None:
            chaos.remove_observer(plane.on_fault)
            plane.close()
        close_monitor(mon, server)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
