"""CLI driver for the long-context transformer LM family.

The MLP driver (`train.py`) keeps the reference's exact surface
(`/root/reference/train.py:62-155`); this driver exposes the capability the
reference never had: context-parallel training of a causal transformer with
ring attention over a (dp, sp) mesh (`shallowspeed_tpu/parallel/context.py`).

Data is a synthetic character-level copy-ahead corpus by default (this image
has zero egress), or any plain-text file via --text.

Example (virtual 8-device mesh, sequence sharded 4-way):

    python train_lm.py --platform cpu --host-devices 8 --dp 2 --sp 4 \
        --seq-len 256 --steps 200
"""

from __future__ import annotations

import argparse
import contextlib
import sys
import time
from pathlib import Path

import numpy as np


def parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--dp", type=int, default=1, help="data-parallel degree")
    p.add_argument("--pp", type=int, default=1,
                   help="pipeline-parallel degree: GPipe over transformer "
                        "blocks, backward schedule derived by autodiff "
                        "(needs n_layers %% pp == 0)")
    p.add_argument("--pp-schedule", choices=["gpipe", "1f1b", "zb"],
                   default="gpipe",
                   help="compiled pipeline schedule: gpipe (autodiff "
                        "backward), 1f1b (PipeDream-Flush: bounded "
                        "min(pp, n_mu) activation stash), or zb "
                        "(ZB-H1 zero-bubble: hand-split B/W backward, "
                        "deferred weight grads fill the drain bubble; "
                        "full residual stash, no recompute)")
    p.add_argument("--virtual-pp", type=int, default=1,
                   help="interleaved virtual pipeline stages per device "
                        "(Megatron-style; gpipe schedule, needs "
                        "n_layers %% (pp*virtual_pp) == 0)")
    p.add_argument("--n-mubatches", type=int, default=4,
                   help="microbatches per batch in the pipeline (--pp > 1)")
    p.add_argument("--sp", type=int, default=1,
                   help="sequence/context-parallel degree (ring attention)")
    p.add_argument("--tp", type=int, default=1,
                   help="tensor-parallel degree (Megatron placement); "
                        "composes with --sp on a (dp, sp, tp) mesh "
                        "(GSPMD) or with --pp on a (dp, pp, tp) mesh "
                        "(explicit psum inside the pipeline)")
    p.add_argument("--ep", type=int, default=1,
                   help="expert-parallel degree (requires --experts > 0); "
                        "composes with --dp, and with --sp on a "
                        "(dp, sp, ep) mesh for long-context MoE")
    p.add_argument("--experts", type=int, default=0,
                   help="number of MoE experts per block (0 = dense FFN)")
    p.add_argument("--moe-top-k", type=int, default=2)
    p.add_argument("--moe-capacity-factor", type=float, default=2.0,
                   help="expert buffer slots = cf * top_k * tokens / E; "
                        "lower = faster steps, more dropped assignments "
                        "(drop fraction is logged per step)")
    p.add_argument("--moe-routing", default="sequence",
                   choices=["sequence", "priority"],
                   help="expert slot assignment: sequence order (GShard) "
                        "or batch-priority (V-MoE: overflow drops the "
                        "router's least-confident assignments)")
    p.add_argument("--moe-z-weight", type=float, default=0.0,
                   help="router z-loss weight (ST-MoE stabilizer; "
                        "1e-3 typical, 0 = off)")
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=256)
    p.add_argument("--d-model", type=int, default=128)
    p.add_argument("--n-heads", type=int, default=4)
    p.add_argument("--kv-heads", type=int, default=0,
                   help="grouped-query attention: K/V head count "
                        "(0 = n-heads, plain MHA); the decode KV cache "
                        "shrinks by n-heads/kv-heads")
    p.add_argument("--n-layers", type=int, default=2)
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--optimizer", default="adam",
                   choices=["sgd", "momentum", "adam", "adamw",
                            "adafactor"])
    p.add_argument("--weight-decay", type=float, default=0.01,
                   help="decoupled weight decay (adamw/adafactor)")
    p.add_argument("--grad-clip", type=float, default=0.0,
                   help="global-norm gradient clipping (0 = off)")
    p.add_argument("--lr-schedule", default="constant",
                   choices=["constant", "linear", "cosine"],
                   help="lr schedule; linear/cosine warm up over "
                        "--warmup-steps then decay to --lr-end at "
                        "--steps")
    p.add_argument("--warmup-steps", type=int, default=0)
    p.add_argument("--lr-end", type=float, default=0.0,
                   help="final learning rate the linear/cosine schedules "
                        "decay to (default 0)")
    p.add_argument("--attn-window", type=int, default=0,
                   help="sliding-window attention: each position sees "
                        "only the last N positions (0 = full causal; "
                        "XLA-attention engines only)")
    p.add_argument("--logit-softcap", type=float, default=0.0,
                   help="final-logit soft-capping: cap*tanh(logits/cap) "
                        "(Gemma-2 style; 30.0 typical, 0 = off)")
    p.add_argument("--bf16", action="store_true",
                   help="mixed precision: bfloat16 compute (MXU-native), "
                        "float32 master weights/optimizer state")
    p.add_argument("--norm", default="layernorm",
                   choices=["layernorm", "rmsnorm"])
    p.add_argument("--ffn", default="gelu", choices=["gelu", "swiglu"],
                   help="dense FFN flavor (ignored with --experts)")
    p.add_argument("--rope", action="store_true",
                   help="rotary position embeddings (replaces the learned "
                        "absolute embedding; composes with every engine "
                        "and sequence sharding)")
    p.add_argument("--tie-embeddings", action="store_true",
                   help="weight tying: the output head reuses tok_emb^T "
                        "(no separate head matrix)")
    p.add_argument("--label-smoothing", type=float, default=0.0,
                   help="mix the one-hot target with the uniform "
                        "distribution in the loss")
    p.add_argument("--attn-dropout", type=float, default=0.0,
                   help="attention-PROBABILITY dropout (pre-AV-matmul "
                        "mask); plain XLA attention substrate only — "
                        "rejected with --pp, --sp>1, or a fused "
                        "substrate")
    p.add_argument("--dropout", type=float, default=0.0,
                   help="dropout rate on embeddings and attention/FFN "
                        "outputs (GPT-2 placement); active in training "
                        "steps only — eval and decode never drop")
    p.add_argument("--remat", action="store_true",
                   help="rematerialize each block's activations in the "
                        "backward (jax.checkpoint): ~1 extra forward of "
                        "FLOPs for O(layers)->O(1) activation memory")
    p.add_argument("--remat-policy", default="full",
                   choices=["full", "attn", "dots"],
                   help="what --remat SAVES per block: full = nothing "
                        "(max saving, +1 fwd of recompute), attn = the "
                        "attention output (never re-runs the attention "
                        "substrate), dots = every matmul output "
                        "(elementwise-only recompute; use when "
                        "microbatched activations fit)")
    p.add_argument("--xent-chunk", type=int, default=0,
                   help="chunked cross-entropy: compute the loss over "
                        "this many positions at a time (logits remat'd "
                        "per chunk) — never materializes the (B*T, vocab) "
                        "logits; 0 = whole-batch log-softmax")
    p.add_argument("--d-ff", type=int, default=0,
                   help="FFN hidden width (0 = 4*d_model)")
    p.add_argument("--fsdp", action="store_true",
                   help="ZeRO-3/FSDP: shard params, grads, AND optimizer "
                        "state over the dp axis (XLA derives the "
                        "just-in-time all-gather / reduce-scatter "
                        "schedule); stacks onto --sp/--tp via the 3-D "
                        "composite engine")
    p.add_argument("--zero1", action="store_true",
                   help="ZeRO-1: shard optimizer state over the dp axis "
                        "(1/dp per-device Adam moment footprint; GSPMD "
                        "derives the reduce/all-gather pattern)")
    p.add_argument("--zero2", action="store_true",
                   help="ZeRO-2: ZeRO-1 plus dp-sharded gradients — the "
                        "DP reduction becomes a reduce-scatter and the "
                        "persistent grad buffer is 1/dp per device")
    p.add_argument("--overlap", default="off", choices=["off", "on"],
                   help="comm/compute interleaving (shallowspeed_tpu."
                        "parallel.overlap): the dp gradient reduction "
                        "moves INSIDE the backward, one size-targeted "
                        "bucket at a time (with --accum the last "
                        "microbatch is peeled out of the accumulation "
                        "scan); --fsdp gains explicit per-leaf "
                        "all-gather prefetch + in-backward "
                        "reduce-scatter. Context engine (any --zero "
                        "level) and pure --fsdp; the bulk reduction "
                        "stays the oracle")
    p.add_argument("--bucket-mb", type=float, default=4.0,
                   help="with --overlap on: target bytes per reduction "
                        "bucket (MiB)")
    p.add_argument("--attn", default="ring",
                   choices=["ring", "ring-flash", "ulysses",
                            "ulysses-flash", "flash"],
                   help="attention substrate: ring (any --sp; XLA local "
                        "compute), ring-flash (any --sp; the fused "
                        "Pallas kernel as the ring's local compute — no "
                        "head-divisibility constraint), ulysses "
                        "(all-to-all; needs n_heads %% sp == 0), "
                        "ulysses-flash (all-to-all + fused Pallas kernel) "
                        "or the fused Pallas flash kernel (--sp 1 only; "
                        "also drops into each --pp stage, incl. --pp "
                        "--tp); with --tp/--fsdp alone the GSPMD engines "
                        "use XLA attention (K/V all-gather under --sp)")
    p.add_argument("--data-dir", type=str, default="",
                   help="memmapped token-shard corpus directory "
                        "(scripts/build_token_shards.py): streams "
                        "windows off disk — deterministic resumable "
                        "order, held-out val.bin split, no whole-file "
                        "RAM load. Replaces --text; vocab/tokenizer "
                        "come from the shard index")
    p.add_argument("--text", type=str, default="",
                   help="train on this UTF-8 text file (byte-level vocab, "
                        "or subword with --tokenizer bpe)")
    p.add_argument("--tokenizer", default="byte", choices=["byte", "bpe"],
                   help="text tokenization: raw bytes (vocab 256) or "
                        "byte-level BPE trained on --text to --vocab-size "
                        "(saved/restored with --save-dir)")
    p.add_argument("--vocab-size", type=int, default=512,
                   help="BPE target vocabulary (--tokenizer bpe)")
    p.add_argument("--generate", type=int, default=0,
                   help="after training, sample this many tokens from the "
                        "model (KV-cache decode) and print them")
    p.add_argument("--temperature", type=float, default=0.8)
    p.add_argument("--top-k", type=int, default=0)
    p.add_argument("--top-p", type=float, default=0.0,
                   help="nucleus sampling: keep the smallest probability "
                        "mass >= p (0 = off; composes with --top-k)")
    p.add_argument("--kv-int8", action="store_true",
                   help="decode with an int8-quantized KV cache (halves "
                        "the cache sweep's HBM bytes — measured 1.18x "
                        "decode on bandwidth-bound GQA long-context, "
                        "BASELINE.md; streams are deterministic but not "
                        "bit-equal to the bf16 cache). Replicated decode "
                        "path only — the pipelined per-stage cache stays "
                        "bf16")
    p.add_argument("--prompt", type=str, default="",
                   help="UTF-8 prompt for --generate (byte-level; default: "
                        "a 16-token prefix from the data stream)")
    p.add_argument("--sample-only", action="store_true",
                   help="skip training: restore --save-dir's latest "
                        "checkpoint (implies --resume) and just --generate")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--log-every", type=int, default=20)
    p.add_argument("--ema-decay", type=float, default=0.0,
                   help="keep an exponential moving average of the "
                        "weights (e.g. 0.999); validation and sampling "
                        "use the averaged weights, checkpoints carry "
                        "them (0 = off)")
    p.add_argument("--accum", type=int, default=1,
                   help="gradient accumulation: split each batch into N "
                        "sequential microbatches per device (activation "
                        "memory of one microbatch, same gradient); plain "
                        "dp/sp engine only")
    p.add_argument("--prefetch", type=int, default=2,
                   help="input-pipeline depth: batches built + placed on "
                        "device this many steps ahead on a background "
                        "thread (0 = synchronous)")
    p.add_argument("--async-save", action="store_true",
                   help="write checkpoints on a background thread: the "
                        "device->host snapshot is synchronous (pins the "
                        "state), compression/IO never blocks training")
    p.add_argument("--keep-checkpoints", "--keep-last", type=int,
                   default=0, dest="keep_checkpoints",
                   help="checkpoint rotation: keep only the N newest "
                        "ckpt_* dirs (0 = keep all); a long elastic "
                        "run otherwise accumulates multi-GB "
                        "checkpoints without bound. The newest "
                        "VERIFIED checkpoint is never rotated away, "
                        "whatever its age — if everything newer is "
                        "corrupt, the one restorable state survives")
    p.add_argument("--save-every", type=int, default=100,
                   help="checkpoint every N steps when --save-dir is set")
    p.add_argument("--save-dir", type=str, default="")
    p.add_argument("--resume", action="store_true")
    p.add_argument("--auto-resume", action="store_true",
                   help="resume from the latest checkpoint if one exists, "
                        "start fresh otherwise — the restart-safe mode "
                        "the elastic supervisor (shallowspeed_tpu."
                        "elastic) relies on")
    p.add_argument("--heartbeat-file", type=str, default="",
                   help="touch this file at every log point; the elastic "
                        "supervisor watches its mtime for hang detection")
    p.add_argument("--log-file", type=str, default="")
    p.add_argument("--profile-dir", type=str, default="",
                   help="write a jax.profiler trace of the training loop")
    p.add_argument("--telemetry", default="off",
                   choices=["off", "steps", "spans"],
                   help="runtime telemetry level: steps = host-clock "
                        "spans + per-step-line HBM/collective/recompile "
                        "fields (async dispatch preserved); spans = "
                        "device-fenced phase spans + measured pipeline "
                        "bubble (accurate attributed time; serializes "
                        "dispatch — a measurement mode, not a "
                        "throughput mode)")
    p.add_argument("--health", default="off",
                   choices=["off", "monitor", "guard"],
                   help="training-health observability (shallowspeed_"
                        "tpu.telemetry.health): monitor = compute the "
                        "on-device health pack (grad/param norms, "
                        "update ratio, nonfinite sentinel) inside every "
                        "compiled step — zero extra executables — and "
                        "run the streaming anomaly detector (loss/grad "
                        "spikes, divergence, dead layers) over the "
                        "step lines; guard = monitor + gate the "
                        "optimizer update on the nonfinite sentinel "
                        "(a poisoned step is skipped bit-identically, "
                        "params and moments untouched). Health "
                        "verdicts ride --heartbeat-file, so the "
                        "elastic supervisor restarts a numerically "
                        "dead run from the last good checkpoint")
    p.add_argument("--trace-dir", type=str, default="",
                   help="write the telemetry trace here: spans.jsonl "
                        "(streamed), trace.json (Chrome/Perfetto), "
                        "telemetry.json (run summary). Implies "
                        "--telemetry steps when the level is off")
    p.add_argument("--monitor-port", type=int, default=None,
                   help="live telemetry plane (telemetry/monitor): "
                        "serve /status.json + /metrics (Prometheus "
                        "text) on 127.0.0.1:PORT — streaming sketch "
                        "quantiles over step time / tok/s, goodput so "
                        "far, health verdict, last fault — while the "
                        "run is live (0 = pick a free port)")
    p.add_argument("--replica", type=str, default=None,
                   help="replica label for fleet views (telemetry/"
                        "fleet): stamped on run_start and served from "
                        "/status.json so a FleetCollector names this "
                        "process in breakdowns and straggler events")
    p.add_argument("--slo", type=str, default="",
                   help="declarative SLOs over dual burn-rate "
                        "windows, e.g. 'step_p95_ms<250,"
                        "availability>0.99'; transitions land as "
                        "schema-v7 'alert' events in --log-file")
    p.add_argument("--flight-recorder", type=int, default=0,
                   help="anomaly flight recorder: ring of the last N "
                        "metrics/span records, dumped to flightrec_"
                        "<step>.json (next to --log-file) when an "
                        "anomaly verdict fires, a chaos fault stamps, "
                        "or an SLO alert trips (0 = off)")
    p.add_argument("--profile", default="off",
                   choices=["off", "host", "host+device"],
                   help="continuous profiling plane (telemetry/"
                        "profiler): always-on host stack sampler "
                        "streaming schema-v12 'profile' events into "
                        "--log-file (step spans tag the samples when "
                        "--telemetry is on, so host time decomposes "
                        "into named buckets) + burn/fault/anomaly-"
                        "triggered capture windows (profcap_*.json "
                        "next to flightrec_*); 'host+device' wraps "
                        "each capture in a bounded jax.profiler trace")
    p.add_argument("--profile-hz", type=float, default=None,
                   help="host sampler rate (default 67 Hz)")
    p.add_argument("--chaos", type=str, default="",
                   help="deterministic fault injection (shallowspeed_"
                        "tpu.chaos): a seeded plan like "
                        "'kill@9,corrupt@2,stall@5:0.5' (or a JSON "
                        "path) scheduling faults at named injection "
                        "points — process kill, SIGKILL inside the "
                        "checkpoint write window, NaN-poisoned "
                        "params, data-loader stall, heartbeat "
                        "freeze, ENOSPC on save, post-hoc checkpoint "
                        "corruption. Falls back to the supervisor-"
                        "exported SHALLOWSPEED_CHAOS env. Each fault "
                        "fires once and stamps a schema-v5 'fault' "
                        "event into --log-file")
    p.add_argument("--chaos-state", type=str, default="",
                   help="fired-fault marker directory (default: "
                        "<save-dir>/.chaos) — must survive restarts "
                        "so a restarted child replays fault windows "
                        "clean")
    p.add_argument("--chaos-seed", type=int, default=0)
    p.add_argument("--val-every", type=int, default=0,
                   help="every N steps evaluate held-out loss/perplexity "
                        "(--text: last 10%% of the file; synthetic: a "
                        "disjoint seed stream)")
    p.add_argument("--platform", type=str, default=None,
                   choices=["cpu", "tpu"])
    p.add_argument("--host-devices", type=int, default=0)
    return p.parse_args(argv)


def prepare_text(args):
    """(vocab, tokenizer, train ids, val ids) for the configured text
    pipeline. Byte mode: ids ARE the bytes (vocab 256, tokenizer None).
    BPE mode: train a ByteBPE on the training split (or load the one
    saved next to the checkpoints — --resume/--sample-only restore text
    fidelity with the model), then encode each split. Runs before the
    model config is built because the tokenizer defines the vocab."""
    from pathlib import Path

    tokenizer = None
    text_data = val_data = None
    train_bytes = val_bytes = None
    if args.data_dir:
        # streaming shard corpus: vocab + tokenizer come FROM the shard
        # directory (the builder bound them); --text would shadow it
        from shallowspeed_tpu.data.token_shards import (TokenShards,
                                                        ValSplit)

        if args.text:
            raise SystemExit("--data-dir replaces --text (the shard "
                             "index already fixes the token stream)")
        shards = TokenShards(args.data_dir, args.seq_len)
        tok_path = Path(args.data_dir) / "tokenizer.json"
        if tok_path.exists():
            from shallowspeed_tpu.data.tokenizer import ByteBPE

            tokenizer = ByteBPE.load(tok_path)
            assert tokenizer.vocab_size == shards.vocab, (
                tokenizer.vocab_size, shards.vocab)
        elif args.tokenizer == "bpe":
            # the shard index fixes the token stream; a bpe request
            # against byte-built shards would silently train a
            # different vocabulary than asked
            raise SystemExit(
                f"--tokenizer bpe but {args.data_dir} has no "
                f"tokenizer.json (it was built byte-level) — rebuild "
                f"with build_token_shards.py --tokenizer bpe")
        if args.val_every and not shards.has_val:
            raise SystemExit(
                f"--val-every needs a held-out split but {args.data_dir}"
                f" has no val.bin — rebuild with --val-fraction")
        if args.val_every and shards.has_val \
                and shards.val_tokens <= args.seq_len + 1:
            raise SystemExit(
                f"val.bin holds {shards.val_tokens} tokens — shorter "
                f"than seq_len+2; rebuild with a larger --val-fraction")
        val_data = ValSplit(shards) if shards.has_val else None
        return shards.vocab, tokenizer, shards, val_data
    if args.text:
        raw = open(args.text, "rb").read()
        assert len(raw) > args.seq_len + 1, "text too short for --seq-len"
        if args.val_every:
            split = max(int(len(raw) * 0.9), args.seq_len + 2)
            train_bytes, val_bytes = raw[:split], raw[split:]
            assert len(val_bytes) > args.seq_len + 1, (
                "text too short to hold out a 10% validation tail")
        else:
            train_bytes = raw

    if args.tokenizer == "bpe":
        from shallowspeed_tpu.data.tokenizer import ByteBPE, train_bpe

        tok_path = (Path(args.save_dir) / "tokenizer.json"
                    if args.save_dir else None)
        reuse = args.resume or args.sample_only
        if reuse and tok_path is not None and tok_path.exists():
            # resuming: the checkpointed weights are bound to the saved
            # merges — restore them and ignore --vocab-size. A FRESH run
            # always retrains (and overwrites), so a stale tokenizer.json
            # can never silently pin a new run's vocabulary.
            tokenizer = ByteBPE.load(tok_path)
        elif train_bytes is not None:
            tokenizer = train_bpe(train_bytes, args.vocab_size)
            if tok_path is not None:
                tok_path.parent.mkdir(parents=True, exist_ok=True)
                tokenizer.save(tok_path)
        else:
            raise SystemExit("--tokenizer bpe needs --text to train on "
                             "(or a tokenizer.json under --save-dir)")
        vocab = tokenizer.vocab_size
        if train_bytes is not None:
            text_data = tokenizer.encode(train_bytes)
            assert len(text_data) > args.seq_len + 1, (
                "tokenized text too short for --seq-len")
        if val_bytes is not None:
            val_data = tokenizer.encode(val_bytes)
            assert len(val_data) > args.seq_len + 1, (
                "tokenized validation tail too short for --seq-len")
    else:
        vocab = 256
        if train_bytes is not None:
            text_data = np.frombuffer(train_bytes, np.uint8).astype(
                np.int32)
        if val_bytes is not None:
            val_data = np.frombuffer(val_bytes, np.uint8).astype(np.int32)
    return vocab, tokenizer, text_data, val_data


def make_batch(args, vocab, step: int, text_data=None):
    """(tokens, targets) (B, T) int32 batch for `step` — random-access
    (seeded per step), so a resumed run continues the exact stream an
    uninterrupted run would have seen."""
    b, t = args.batch_size, args.seq_len
    if hasattr(text_data, "batch"):
        # shard-backed stream (TokenShards train view or ValSplit):
        # same purity contract, order delegated to the dataset
        return text_data.batch(step, b, seed=args.seed)
    rng = np.random.default_rng([args.seed, step])
    if text_data is not None:
        starts = rng.integers(0, len(text_data) - t - 1, b)
        tok = np.stack([text_data[s:s + t] for s in starts])
        tgt = np.stack([text_data[s + 1:s + t + 1] for s in starts])
        return tok, tgt
    # synthetic: repeat a random motif; next-token is learnable
    motif = rng.integers(0, vocab, (b, 16))
    tok = np.tile(motif, (1, t // 16 + 1))[:, :t].astype(np.int32)
    tgt = np.roll(tok, -1, axis=1).astype(np.int32)
    return tok, tgt


def train(args) -> float:
    t_proc0 = time.time()  # goodput ledger: init = entry -> step loop
    import jax

    # multi-host: connect to the JAX distributed service when a
    # coordinator is configured (env vars / pod metadata; the gang
    # supervisor injects them) — single-process no-op, like train.py
    from shallowspeed_tpu import distributed

    distributed.initialize()
    from jax.sharding import Mesh

    from shallowspeed_tpu import chaos, checkpoint
    from shallowspeed_tpu.elastic import (EXIT_CORRUPT_CKPT,
                                          install_sigterm_exit)
    from shallowspeed_tpu.metrics import MetricsLogger
    from shallowspeed_tpu.models.transformer import TransformerConfig
    from shallowspeed_tpu.optim import OPTIMIZERS
    from shallowspeed_tpu.parallel.context import ContextParallelEngine
    from shallowspeed_tpu.utils import rprint

    # a supervisor hang/health kill sends SIGTERM first (--term-grace):
    # exit through the finally blocks so the metrics/ledger tail the
    # goodput reducer reads is flushed, not truncated mid-write
    install_sigterm_exit()
    # deterministic fault injection (--chaos flag or the supervisor-
    # exported env); fired-fault markers default to living WITH the
    # checkpoints so they survive supervisor restarts
    chaos.setup(args.chaos, seed=args.chaos_seed,
                state_dir=args.chaos_state
                or (Path(args.save_dir) / ".chaos"
                    if args.save_dir else None),
                log_file=args.log_file or None)

    if ((args.resume or args.sample_only or args.auto_resume)
            and not args.save_dir):
        raise SystemExit(
            "--resume/--auto-resume/--sample-only require --save-dir")
    if (args.prompt or args.sample_only) and not args.generate:
        args.generate = 128  # --prompt/--sample-only imply sampling
    prompt_len = len(args.prompt.encode()) if args.prompt else 16
    if args.generate and args.generate + prompt_len > args.seq_len:
        raise SystemExit(f"--generate {args.generate} + the {prompt_len}-"
                         f"token prompt exceeds --seq-len {args.seq_len} "
                         f"(= max_seq)")
    composite = args.sp > 1 and args.tp > 1
    if args.pp > 1 and (args.zero1 or args.zero2 or args.fsdp) \
            and args.dp < 2:
        raise SystemExit("--pp with --zero1/--zero2/--fsdp shards over "
                         "dp; need --dp >= 2")
    if args.pp > 1 and (args.zero2 or args.fsdp) and args.ep > 1:
        raise SystemExit("--pp with --zero2/--fsdp takes a "
                         "('dp','pp'[,'tp'|'sp']) mesh (no --ep: "
                         "expert-leaf grads are ep-sharded, outside "
                         "the per-leaf ZeRO scatter rule)")
    if args.pp > 1 and sum(a > 1 for a in (args.tp, args.sp,
                                           args.ep)) > 1:
        raise SystemExit("--pp takes ONE extra model axis: --tp, --sp, "
                         "or --ep")
    if args.pp > 1 and args.virtual_pp > 1 and args.ep > 1:
        raise SystemExit("--virtual-pp needs collective-free chunk "
                         "bodies (no --ep all-to-all inside a "
                         "cond-gated chunk)")
    if args.pp > 1 and args.experts and args.tp > 1:
        raise SystemExit("--experts with --pp composes with --dp/--sp/"
                         "--ep (not --tp)")
    if args.pp > 1 and args.sp > 1 and args.attn not in (
            "ring", "ring-flash", "ulysses-flash"):
        raise SystemExit(f"--pp with --sp needs a sequence-parallel "
                         f"attention substrate (--attn ring, ring-flash "
                         f"or ulysses-flash), got {args.attn}")
    if args.pp > 1 and args.sp > 1 and args.pp_schedule == "1f1b":
        print("note: on an sp mesh the 1F1B ticks cannot skip (the F/B "
              "halves run unmasked so every device issues the same "
              "collective schedule) — measured ~0.5x GPipe's throughput "
              "(BASELINE.md '1F1B x sp'); --pp-schedule gpipe is the "
              "fast choice here", file=sys.stderr)
    if args.pp > 1 and args.sp == 1 and args.attn not in ("ring", "flash"):
        raise SystemExit(f"--attn {args.attn} is not available with --pp "
                         "(XLA attention by default, or the fused Pallas "
                         "kernel via --attn flash)")
    if args.pp > 1 and args.pp_schedule == "zb":
        # mirror of PipelineLMEngine's pinned zb carve-outs, with CLI
        # vocabulary (tests/test_pipeline_zb.py pins the mechanisms);
        # gated on pp > 1 like every sibling check — at pp=1 the
        # schedule flag is inert (no pipeline engine is built)
        if any(a > 1 for a in (args.tp, args.sp, args.ep)):
            raise SystemExit("--pp-schedule zb runs on a ('dp','pp') "
                             "mesh (no --tp/--sp/--ep: collectives "
                             "inside the per-round switch de-sync)")
        if args.virtual_pp > 1:
            raise SystemExit("--pp-schedule zb needs --virtual-pp 1 "
                             "(per-chunk B/W tables are not built)")
        if args.experts:
            raise SystemExit("--pp-schedule zb needs the dense block "
                             "family (no --experts)")
        if args.dropout > 0.0 or args.attn_dropout > 0.0:
            raise SystemExit("--pp-schedule zb trains without dropout "
                             "(the hand-split backward does not thread "
                             "mask keys F->B)")
        if args.remat:
            raise SystemExit("--pp-schedule zb IS the no-recompute "
                             "schedule (it stashes residuals F->B); "
                             "drop --remat")
    if args.ep > 1 and args.tp > 1:
        raise SystemExit("--ep composes with --dp/--sp (not --tp)")
    if args.keep_checkpoints < 0:
        raise SystemExit("--keep-checkpoints takes 0 (keep all) or a "
                         "positive count")
    if args.fsdp and (args.ep > 1 or args.experts or args.zero1
                      or args.zero2):
        raise SystemExit("--fsdp composes with --dp/--sp/--tp/--pp (and already "
                         "subsumes --zero1/--zero2; MoE uses --ep)")
    if args.zero1 and args.zero2:
        raise SystemExit("--zero2 subsumes --zero1; pick one")
    if args.overlap != "off" and (
            args.pp > 1 or args.tp > 1 or args.ep > 1 or args.experts
            or (args.fsdp and (args.sp > 1 or args.tp > 1))):
        raise SystemExit(
            "--overlap on supports the context engine (--dp/--sp, any "
            "--zero level, --accum) and pure --fsdp; the GSPMD tp/ep/"
            "composite engines schedule compiler-inserted collectives "
            "and the LM pipeline keeps its own hop schedule")
    # --attn-window composes with every substrate: the XLA/ring/ulysses
    # paths mask (ops/attention.py) and the flash kernel skips
    # out-of-window tiles (ops/flash_attention.py) — no guard needed.
    if not 0.0 <= args.ema_decay < 1.0:
        raise SystemExit(f"--ema-decay must be in [0, 1), got "
                         f"{args.ema_decay} (1.0 would freeze the average "
                         f"at the initial weights)")
    if args.accum > 1 and (args.tp > 1 or args.ep > 1 or args.experts
                           or args.fsdp or args.pp > 1):
        raise SystemExit("--accum composes with --dp/--sp (the context "
                         "engine) for now; the pipeline engine already "
                         "microbatches via --n-mubatches")
    if args.fsdp and (args.sp > 1 or args.tp > 1) and args.pp <= 1:
        # ZeRO-3 on top of the 3-D mesh; with --pp the pipeline engine
        # owns fsdp x sp (round 5) so this must not reroute it
        composite = True
    if (args.fsdp or args.tp > 1) and args.pp <= 1 and args.attn != "ring":
        raise SystemExit(f"--attn {args.attn} is not available with "
                         "--tp/--fsdp (the GSPMD engines use XLA attention; "
                         "under --sp the composite engine's context "
                         "parallelism is the K/V all-gather formulation)")
    if args.ep > 1 and args.experts == 0:
        raise SystemExit("--ep requires --experts > 0")
    if args.experts and args.tp > 1:
        raise SystemExit("--experts composes with --dp/--sp/--ep (not "
                         "--tp) for now")
    if args.experts and args.moe_top_k > args.experts:
        raise SystemExit(f"--moe-top-k {args.moe_top_k} cannot exceed "
                         f"--experts {args.experts}")
    if args.attn_dropout > 0.0 and (
            args.pp > 1 or args.sp > 1
            or args.attn not in ("ring",)):
        raise SystemExit("--attn-dropout needs the plain XLA attention "
                         "substrate (no --pp/--sp>1, --attn ring)")
    if args.experts and args.pp <= 1 and args.attn != "ring":
        raise SystemExit(f"--attn {args.attn} is not available with "
                         "--experts (the MoE engine uses XLA attention)")
    if composite:
        model_par = args.sp * args.tp
    elif args.pp > 1:
        model_par = args.pp * args.tp * args.sp * args.ep
    elif (args.ep > 1 or args.experts) and args.sp > 1:
        model_par = args.sp * args.ep  # long-context MoE: (dp, sp, ep)
    else:
        model_par = max(args.tp, args.sp, args.ep)
    n_dev = len(jax.devices())
    if args.dp * model_par > n_dev:
        raise SystemExit(f"requested dp*model_parallel="
                         f"{args.dp * model_par} devices but only "
                         f"{n_dev} present")
    assert args.batch_size % args.dp == 0
    assert args.seq_len % args.sp == 0

    vocab, tokenizer, text_data, val_data = prepare_text(args)
    import jax.numpy as jnp

    cfg = TransformerConfig(vocab=vocab, d_model=args.d_model,
                            n_heads=args.n_heads, n_layers=args.n_layers,
                            max_seq=args.seq_len, n_experts=args.experts,
                            moe_top_k=args.moe_top_k,
                            moe_capacity_factor=args.moe_capacity_factor,
                            moe_z_weight=args.moe_z_weight,
                            moe_routing=args.moe_routing,
                            compute_dtype=jnp.bfloat16 if args.bf16 else None,
                            remat=args.remat,
                            remat_policy=args.remat_policy,
                            xent_chunk=args.xent_chunk, d_ff=args.d_ff,
                            rope=args.rope,
                            norm=args.norm, ffn=args.ffn,
                            n_kv_heads=args.kv_heads,
                            dropout=args.dropout,
                            attn_dropout=args.attn_dropout,
                            tie_embeddings=args.tie_embeddings,
                            label_smoothing=args.label_smoothing,
                            logit_softcap=args.logit_softcap,
                            attn_window=args.attn_window)
    if jax.default_backend() == "tpu" and 256 < args.d_model <= 1024:
        # measured on a v5e (BASELINE.md's matmul table):
        # ops with K and N both <= 1024 run far below MXU peak (fixed
        # per-pass costs dominate), so d_model <= 1024 configs cap out
        # around 26-35% MFU while d_model >= 2048 reaches ~57%. Tiny
        # (demo-sized, <=256) models are exempt — nobody MFU-tunes those.
        from shallowspeed_tpu.utils import rprint as _rprint

        _rprint(f"note: d_model={args.d_model} puts the attention/FFN "
                f"projections in the MXU's starved small-matmul regime "
                f"on this chip (~26-35% MFU vs ~57% at d_model>=2048); "
                f"prefer fewer/wider layers or raise batch*seq "
                f"(BASELINE.md 'narrow-matmul' section)")
    from shallowspeed_tpu.optim import SCHEDULES

    if args.lr_schedule == "constant":
        lr = args.lr  # static float keeps SGD stateless (no step counter)
    else:
        lr = SCHEDULES[args.lr_schedule](
            peak=args.lr, warmup=args.warmup_steps, total=args.steps,
            end=args.lr_end)
    opt_kw = {"grad_clip": args.grad_clip or None}
    if args.optimizer in ("adamw", "adafactor"):
        opt_kw["weight_decay"] = args.weight_decay
    opt = OPTIMIZERS[args.optimizer](lr=lr, **opt_kw)
    devs = np.array(jax.devices()[: args.dp * model_par])
    if args.pp > 1:
        from shallowspeed_tpu.parallel.pipeline_lm import PipelineLMEngine

        if args.tp > 1:
            mesh = Mesh(devs.reshape(args.dp, args.pp, args.tp),
                        ("dp", "pp", "tp"))
            pp_attn = "flash" if args.attn == "flash" else "xla"
        elif args.sp > 1:
            mesh = Mesh(devs.reshape(args.dp, args.pp, args.sp),
                        ("dp", "pp", "sp"))
            pp_attn = args.attn  # ring / ring-flash / ulysses-flash
        elif args.ep > 1:
            # ep x pp: experts sharded over 'ep' inside each stage,
            # stage-local all-to-all dispatch; ep also multiplies the
            # data dimension (rows shard over dp x ep)
            mesh = Mesh(devs.reshape(args.dp, args.pp, args.ep),
                        ("dp", "pp", "ep"))
            pp_attn = "flash" if args.attn == "flash" else "xla"
        else:
            mesh = Mesh(devs.reshape(args.dp, args.pp), ("dp", "pp"))
            pp_attn = "flash" if args.attn == "flash" else "xla"
        engine = PipelineLMEngine(cfg, opt, mesh,
                                  n_mubatches=args.n_mubatches,
                                  seed=args.seed,
                                  schedule=args.pp_schedule,
                                  attn=pp_attn,
                                  virtual_pp=args.virtual_pp,
                                  zero1=args.zero1, zero2=args.zero2,
                                  fsdp=args.fsdp, health=args.health)
    elif composite:
        from shallowspeed_tpu.parallel.composite import Composite3DEngine

        mesh = Mesh(devs.reshape(args.dp, args.sp, args.tp),
                    ("dp", "sp", "tp"))
        engine = Composite3DEngine(cfg, opt, mesh, seed=args.seed,
                                   zero1=args.zero1, zero2=args.zero2,
                                   fsdp=args.fsdp, health=args.health)
    elif args.fsdp:
        from shallowspeed_tpu.parallel.fsdp import FSDPEngine
        from shallowspeed_tpu.parallel.overlap import from_flags

        mesh = Mesh(devs.reshape(args.dp), ("dp",))
        engine = FSDPEngine(cfg, opt, mesh, seed=args.seed,
                            health=args.health,
                            overlap=from_flags(args.overlap,
                                               args.bucket_mb))
    elif args.ep > 1 or args.experts:
        from shallowspeed_tpu.parallel.expert import ExpertParallelEngine

        if args.sp > 1:
            mesh = Mesh(devs.reshape(args.dp, args.sp, args.ep),
                        ("dp", "sp", "ep"))
        else:
            mesh = Mesh(devs.reshape(args.dp, args.ep), ("dp", "ep"))
        engine = ExpertParallelEngine(cfg, opt, mesh, seed=args.seed,
                                      zero1=args.zero1, zero2=args.zero2,
                                      health=args.health)
    elif args.tp > 1:
        from shallowspeed_tpu.parallel.tensor import TensorParallelEngine

        mesh = Mesh(devs.reshape(args.dp, args.tp), ("dp", "tp"))
        engine = TensorParallelEngine(cfg, opt, mesh, seed=args.seed,
                                      zero1=args.zero1, zero2=args.zero2,
                                      health=args.health)
    else:
        from shallowspeed_tpu.parallel.overlap import from_flags

        mesh = Mesh(devs.reshape(args.dp, args.sp), ("dp", "sp"))
        engine = ContextParallelEngine(cfg, opt, mesh, seed=args.seed,
                                       attn=args.attn, zero1=args.zero1,
                                       zero2=args.zero2, accum=args.accum,
                                       health=args.health,
                                       overlap=from_flags(
                                           args.overlap, args.bucket_mb))

    start_step = 0
    restored_ckpt = None
    if args.auto_resume and not args.resume:
        # elastic restarts: resume iff a checkpoint EXISTS (cheap
        # probe — restore_latest does the one verification pass,
        # quarantining corrupt dirs and falling back), else fresh
        if checkpoint.has_checkpoint(args.save_dir):
            args.resume = True
    restore_secs = 0.0
    if args.resume or args.sample_only:  # save-dir presence checked early
        t_restore = time.time()
        start_step, restored_ckpt, quarantined = \
            checkpoint.restore_latest(engine, args.save_dir)
        if restored_ckpt is None:
            if args.auto_resume and not args.sample_only:
                # the restart-safe mode falls back to a fresh start —
                # deterministic seeded data means the replayed
                # trajectory is the same one the lost checkpoints held
                rprint(f"--auto-resume: no restorable checkpoint under "
                       f"{args.save_dir!r}"
                       + (f" ({len(quarantined)} quarantined)"
                          if quarantined else "") + "; starting fresh")
                args.resume = False
            elif quarantined:
                # strict --resume with every checkpoint corrupt: a
                # distinct exit code so the supervisor classes this as
                # checkpoint corruption, not a generic crash
                print(f"--resume: every checkpoint under "
                      f"{args.save_dir!r} failed verification "
                      f"({len(quarantined)} quarantined)",
                      file=sys.stderr)
                raise SystemExit(EXIT_CORRUPT_CKPT)
            else:
                raise SystemExit(
                    f"--resume: no checkpoint under {args.save_dir!r}")
        else:
            restore_secs = time.time() - t_restore
            if quarantined:
                rprint(f"quarantined {len(quarantined)} corrupt "
                       f"checkpoint(s); fell back to {restored_ckpt}")
            rprint(f"resumed from {restored_ckpt} at step {start_step}")

    if not args.sample_only and start_step >= args.steps:
        raise SystemExit(
            f"checkpoint is already at step {start_step} >= --steps "
            f"{args.steps}; nothing to do")

    # run_start carries start_step so the goodput reducer can tell
    # replayed-from-checkpoint steps from fresh work after a restart
    metrics = MetricsLogger(args.log_file, dp=args.dp, sp=args.sp,
                            seq_len=args.seq_len, d_model=args.d_model,
                            n_layers=args.n_layers,
                            start_step=start_step,
                            **({"replica": args.replica}
                               if args.replica else {}))

    # ---- goodput ledger (telemetry/goodput): every non-step second is
    # stamped into the same JSONL the step lines live in — init,
    # restore, val/save pauses, data stalls, recompile/skip counts —
    # so `python -m shallowspeed_tpu.telemetry --goodput <log-file>`
    # can decompose the run's wall clock even across supervisor
    # restarts (elastic.py stamps the downtime between processes)
    from shallowspeed_tpu.telemetry.goodput import GoodputLedger

    ledger = GoodputLedger(metrics)
    if restore_secs:
        ledger.note("restore", seconds=restore_secs)

    # ---- runtime telemetry (shallowspeed_tpu/telemetry): span tracing,
    # HBM/collective/recompile step-line fields, bubble accounting
    from shallowspeed_tpu import telemetry as tele

    if args.trace_dir and args.telemetry == "off":
        args.telemetry = "steps"  # --trace-dir implies tracing
    tracer = tele.configure(trace_dir=args.trace_dir or None,
                            level=args.telemetry)
    telem = (tele.RunTelemetry(engine, tracer)
             if args.telemetry != "off" else None)
    if telem is not None:
        telem.ledger = ledger  # loss totals ride telemetry.json too
        # memory observatory (round 20): register the long-lived trees
        # so step lines decompose live HBM per owner (hbm_owned_mib)
        # with the residual surfaced as hbm_untracked_mib — a growing
        # residual is the leak alarm. Resolvers, not snapshots: the
        # engine rotates/donates these trees every step.
        from shallowspeed_tpu.telemetry import memory as memlib
        memlib.register_owner(
            "train.params", lambda: getattr(engine, "params", None))
        memlib.register_owner(
            "train.opt_state", lambda: getattr(engine, "opt_state", None))
    # ---- training health (telemetry/health.py): the engines compute
    # the pack on device every step; the monitor fetches it at log
    # points, runs the anomaly detectors, and its fields ride the same
    # step lines. Heartbeats carry its verdict so the elastic
    # supervisor can restart a numerically-dead run from checkpoint.
    monitor = None
    if args.health != "off":
        from shallowspeed_tpu.telemetry.anomaly import GuardPolicy
        from shallowspeed_tpu.telemetry.health import HealthMonitor

        monitor = HealthMonitor(policy=GuardPolicy.for_mode(args.health))
    # ---- live telemetry plane (telemetry/monitor.py): streaming
    # sketches + /status.json + /metrics endpoint + SLO burn-rate
    # alerts + flight recorder, fed by every metrics line (the logger
    # forwards them), the exact StepRates window rates, chaos fault
    # stamps, and (at spans level) the tracer's phase spans
    from shallowspeed_tpu.telemetry.monitor import (close_monitor,
                                                    from_args)

    live_mon, live_srv = from_args(args, metrics)
    if live_mon is not None:
        chaos.add_observer(live_mon.note_line)
        if tracer is not None and args.telemetry != "off":
            tracer.subscribers.append(live_mon.record_span)
        if live_srv is not None:
            rprint(f"monitor: {live_srv.url('/status.json')} "
                   f"(+ /metrics)")
    # continuous profiling plane (round 17): host stack sampler into
    # the same metrics JSONL + trigger-armed capture windows; the
    # tracer's step/phase spans tag each sample via trace.PHASE_HOOKS,
    # so `--profile <log>` decomposes the host's time by name
    from shallowspeed_tpu.telemetry import profiler as profiler_mod

    plane = profiler_mod.from_args(args, metrics)
    if plane is not None:
        chaos.add_observer(plane.on_fault)
        if live_mon is not None:
            live_mon.profiler = plane
            live_mon.alert_listeners.append(plane.on_alert)
    if telem is not None and hasattr(engine, "schedule_info"):
        # pipeline engines: the verified schedule's static bubble rides
        # on every step line from the start; the measured fraction
        # (two-point calibration) joins at the first spans-level log
        si = engine.schedule_info()
        telem.set_bubble(bubble_static=tele.static_bubble(
            si["schedule"], si["n_mu"], si["pp"],
            si["vpp"])["bubble_fraction"])
    saver = checkpoint.AsyncSaver() if args.async_save else None

    def save_ckpt(ckpt_dir, step):
        extra = ({"ema": ema_canonical()} if ema is not None else None)
        keep = args.keep_checkpoints or None
        if saver is not None:
            saver.save(ckpt_dir, engine, step, extra=extra, keep=keep)
        else:
            checkpoint.save(ckpt_dir, engine, step, extra=extra,
                            keep=keep)

    def _warn_save_failed(err):
        # a failed save (ENOSPC, IO error) must not kill a healthy run:
        # the atomic-rename contract means latest() still points at the
        # previous checkpoint — keep training, name the loss in the
        # ledger so --goodput shows the widened restart exposure.
        # MULTI-PROCESS: swallowing is process-0-only state while the
        # peers already sit in the save barrier — carrying on here
        # would wedge the gang on the next mismatched collective, so
        # re-raise and let the gang supervisor restart everyone (the
        # async path's collective success-bit exchange is the
        # equivalent contract).
        if jax.process_count() > 1:
            raise err
        rprint(f"warning: checkpoint save failed ({err}); the previous "
               f"checkpoint remains the restore point")
        ledger.note("ckpt_save_failed", count=1)

    # ---- EMA of the weights: driver-owned, engine-agnostic (a pure
    # elementwise update on the engine's live params tree, whatever its
    # sharding); eval/sampling swap the averaged tree in temporarily
    from shallowspeed_tpu.optim import ema_init, ema_update

    ema = None
    ema_path = (Path(restored_ckpt) / "ema.npz"
                if restored_ckpt is not None else None)
    have_saved_ema = ema_path is not None and ema_path.exists()
    if args.ema_decay == 0.0 and have_saved_ema:
        if args.sample_only:
            # the checkpoint carries an average — sampling the raw
            # iterate instead would silently change output quality
            rprint("checkpoint has EMA weights; sampling the average "
                   "(pass --ema-decay 0 explicitly? it is the default — "
                   "delete ema.npz to sample the raw iterate)")
            args.ema_decay = -1.0  # sentinel: load + use, never update
        else:
            rprint("warning: checkpoint has ema.npz but --ema-decay is "
                   "unset; the running average will NOT be continued")
    if args.ema_decay != 0.0:
        if have_saved_ema:
            # ema.npz is stored in the CANONICAL layout (like params.npz)
            # so it survives topology changes; install it through the
            # engine's own canonical-import path, with the same structure
            # guard restore() applies to params
            host = checkpoint.load_pytree(ema_path)
            mismatch = checkpoint._structure_mismatch(
                host, engine.get_canonical_params())
            if mismatch is None:
                live = engine.params
                engine.set_canonical_params(host)
                ema = engine.params
                engine.params = live
            else:
                rprint(f"warning: ema.npz does not match this model "
                       f"({mismatch}); restarting the average from the "
                       f"restored weights")
                ema = ema_init(engine.params)
        else:
            ema = ema_init(engine.params)

    def ema_canonical():
        """The average in the engine-agnostic checkpoint layout."""
        with ema_weights():
            return engine.get_canonical_params()

    @contextlib.contextmanager
    def ema_weights():
        """Temporarily swap the averaged weights into the engine."""
        if ema is None:
            yield
            return
        live = engine.params
        engine.params = ema
        try:
            yield
        finally:
            engine.params = live

    def val_loss(step: int) -> float:
        """Held-out loss: --text tail, or a seed stream disjoint from
        training (steps are seeded [seed, step]; val uses [seed+1, ...]).
        Each call draws a FRESH batch of held-out windows — seeded by
        the TRAINING STEP (round 4: the old eval-counter seed made a
        resumed run draw different val windows than the uninterrupted
        run at the same step, so val curves were not comparable across
        restarts) — so the metric tracks the distribution, not a fixed
        handful of examples. With --ema-decay, evaluates the averaged
        weights (what you would ship), not the raw iterate."""
        val_args = args if val_data is not None else argparse.Namespace(
            **{**vars(args), "seed": args.seed + 1})
        tok, tgt = make_batch(val_args, vocab, 10**9 + step, val_data)
        with ema_weights():
            return float(engine.eval_loss(local_rows(tok),
                                          local_rows(tgt)))

    if args.sample_only:
        try:
            with ema_weights():
                sample_and_print(args, engine, cfg, vocab, text_data,
                                 tokenizer, metrics=metrics)
        finally:
            if plane is not None:
                chaos.remove_observer(plane.on_fault)
                plane.close()
            if live_mon is not None:
                chaos.remove_observer(live_mon.note_line)
                close_monitor(live_mon, live_srv)
        return float("nan")

    from shallowspeed_tpu.metrics import StepRates

    # window + cumulative tok/s with val/save time excluded from both;
    # the WINDOW rate is what step lines and step events report first
    # (the cumulative average buries the sustained rate under compile
    # time — round-4 endurance lesson). With telemetry on, every
    # log_point line additionally carries the telemetry fields.
    rates = StepRates(args.batch_size * args.seq_len, telemetry=telem,
                      health=monitor, ledger=ledger, monitor=live_mon)
    # everything before the step loop (imports, engine build, data
    # prep; restore is itemized separately) is init time
    ledger.note("init", seconds=max(0.0, time.time() - t_proc0
                                    - restore_secs))
    data_stall = 0.0  # next(placed) wait since the last log point
    last_logged = start_step - 1
    loss = float("nan")
    from shallowspeed_tpu.data.prefetch import prefetch_to_device, sync_every
    from shallowspeed_tpu.distributed import local_rows

    def batches():
        for step in range(start_step, args.steps):
            # chaos stall fault: injected HERE, in the producer, so a
            # prefetched pipeline may absorb it (that's the overlap
            # working) while --prefetch 0 must surface it as ledger
            # data_stall seconds
            chaos.on_data_load(step)
            tok, tgt = make_batch(args, vocab, step, text_data)
            # multi-host: every process builds the same seeded global batch
            # and feeds its own row-block (no-op single-process)
            yield local_rows(tok), local_rows(tgt)

    # batches are built + placed `--prefetch` steps ahead on a background
    # thread (H2D streams under the running step), and the loss stays a
    # lazy device scalar except at log points — the dispatch loop never
    # blocks on the host
    placed = prefetch_to_device(
        batches(), lambda b: (engine.place(b[0]), engine.place(b[1])),
        depth=args.prefetch)
    # the ONE jax.profiler entry point (telemetry/profiler): falsy dir
    # = no-op; an active whole-run trace makes the profiling plane's
    # capture windows skip their device half (xprof doesn't nest)
    from shallowspeed_tpu.telemetry.profiler import device_trace_ctx

    profile_ctx = device_trace_ctx(args.profile_dir)
    t_loop_done = None  # set at loop exit; teardown time is ledgered
    try:
        with profile_ctx:
            placed_it = iter(placed)
            for step in range(start_step, args.steps):
                # chaos step faults: kill / param poison / heartbeat
                # freeze, each at most once per plan (markers survive
                # supervisor restarts, so the replay runs clean)
                chaos.on_step(step, engine)
                # input-pipeline stall accounting: with prefetch ahead
                # this wait is ~0; a slow producer shows up as
                # data_stall seconds in the goodput ledger
                t_fetch = time.time()
                try:
                    tok, tgt = next(placed_it)
                except StopIteration:
                    break
                data_stall += time.time() - t_fetch
                loss_dev = engine.train_batch_async(tok, tgt)
                if ema is not None:
                    ema = ema_update(ema, engine.params, args.ema_decay)
                if sync_every(step, args.log_every, args.steps):
                    loss = float(loss_dev)
                    if monitor is not None:
                        # one device_get for the pack, then the
                        # streaming detectors; verdict fields ride the
                        # step line via StepRates(health=...)
                        verdicts = monitor.observe(
                            step, loss, engine.health_snapshot())
                        for v in verdicts:
                            rprint(str(v))
                        fatal = [v for v in verdicts
                                 if v.action == "abort"]
                        if fatal:
                            if live_mon is not None:
                                # the process exits before the next
                                # metrics line — dump the incident
                                # ring NOW, while it still exists
                                live_mon.flight_dump(
                                    "anomaly:" + ",".join(
                                        v.kind for v in fatal),
                                    step=step,
                                    trigger=[str(v) for v in fatal])
                            if args.save_dir:
                                save_ckpt(f"{args.save_dir}/diverged",
                                          step)
                                if saver is not None:
                                    saver.wait()
                            raise SystemExit(
                                f"health policy abort at step {step}: "
                                + "; ".join(v.detail for v in fatal))
                    if args.heartbeat_file \
                            and not chaos.heartbeat_frozen():
                        # liveness + health signal for the elastic
                        # supervisor: a stale mtime means a hung step
                        # loop; a 'dead ...' status means a numerically
                        # dead one (restart from the last good
                        # checkpoint either way). A chaos freeze fault
                        # suppresses the beat — the run keeps stepping
                        # and only the supervisor's staleness clock
                        # can catch it (the hang drill).
                        from shallowspeed_tpu.elastic import (
                            write_heartbeat)

                        write_heartbeat(
                            args.heartbeat_file,
                            monitor.heartbeat_status()
                            if monitor is not None else "ok")
                    if not np.isfinite(loss):
                        # failure detection: divergence gets a labeled exit
                        # (and the params snapshot when --save-dir is set)
                        # instead of silently training on NaNs
                        if live_mon is not None:
                            live_mon.flight_dump(
                                "divergence:nonfinite_loss", step=step,
                                trigger={"loss": str(loss)})
                        if args.save_dir:
                            # under diverged/ so checkpoint.latest() keeps
                            # resolving to the last GOOD checkpoint for
                            # --resume; this snapshot is forensic only
                            save_ckpt(f"{args.save_dir}/diverged", step)
                            if saver is not None:
                                saver.wait()
                            path = f"{args.save_dir}/diverged/ckpt_{step}"
                            rprint(f"diverged-state snapshot: {path}")
                        raise SystemExit(
                            f"loss became non-finite ({loss}) at step "
                            f"{step}; try --grad-clip, a lower --lr, or "
                            f"--lr-schedule with --warmup-steps")
                    r = rates.log_point(step - last_logged)
                    last_logged = step
                    if data_stall > 0.01:
                        ledger.note("data_stall", seconds=data_stall)
                    data_stall = 0.0
                    # achieved TFLOP/s + fraction-of-peak (exact matmul
                    # count per token; None off-TPU where no peak is
                    # known). Rates are GLOBAL — divide by the engine's
                    # mesh size, not one chip's peak.
                    from shallowspeed_tpu.flops import mfu as _mfu

                    n_dev = getattr(getattr(engine, "mesh", None),
                                    "devices", np.zeros(1)).size
                    kw = dict(dtype="bf16" if args.bf16 else "f32",
                              n_devices=n_dev)
                    perf = _mfu(r["tokens_per_sec"], cfg, args.seq_len,
                                **kw)
                    cum = _mfu(r["tokens_per_sec_cum"], cfg,
                               args.seq_len, **kw)
                    mfu_txt = ("" if perf["mfu"] is None else
                               f"  {perf['tflops']:.1f} TF/s "
                               f"({perf['mfu'] * 100:.1f}% MFU)")
                    rprint(f"step {step:5d}  loss {loss:.4f}  "
                           f"tok/s {r['tokens_per_sec']:,.0f}{mfu_txt}")
                    # telemetry fields ride the same step line (HBM,
                    # collective bytes/GB/s, recompiles, bubble)
                    tfields = {k: v for k, v in r.items()
                               if k not in ("tokens_per_sec",
                                            "tokens_per_sec_cum")}
                    metrics.log(event="step", step=step,
                                loss=round(loss, 6),
                                tokens_per_sec=round(
                                    r["tokens_per_sec"], 1),
                                tflops=round(perf["tflops"], 2),
                                mfu=(None if perf["mfu"] is None
                                     else round(perf["mfu"], 4)),
                                tokens_per_sec_cum=round(
                                    r["tokens_per_sec_cum"], 1),
                                tflops_cum=round(cum["tflops"], 2),
                                mfu_cum=(None if cum["mfu"] is None
                                         else round(cum["mfu"], 4)),
                                **tfields)
                    if telem is not None:
                        parts = []
                        if "bubble_measured" in tfields:
                            parts.append(
                                f"bubble {tfields['bubble_measured']:.1%}"
                                f" (static "
                                f"{tfields['bubble_static']:.1%})")
                        elif "bubble_static" in tfields:
                            parts.append(f"bubble static "
                                         f"{tfields['bubble_static']:.1%}")
                        if "coll_bytes_per_step" in tfields:
                            mib = tfields["coll_bytes_per_step"] / 2**20
                            parts.append(f"coll {mib:,.1f} MiB/step")
                        if "hbm_live_mib" in tfields:
                            parts.append(
                                f"hbm {tfields['hbm_live_mib']:,.0f}"
                                + (f"/{tfields['hbm_static_mib']:,.0f}"
                                   f" MiB" if "hbm_static_mib" in
                                   tfields else " MiB"))
                        if tfields.get("recompiles"):
                            parts.append(
                                f"RECOMPILES {tfields['recompiles']}")
                        if parts:
                            rprint("             " + "  ".join(parts))
                    if (telem is not None
                            and args.telemetry == "spans"
                            and args.pp > 1
                            and hasattr(engine, "schedule_info")
                            and "bubble_measured" not in telem.bubble):
                        # two-point bubble calibration: one extra
                        # engine compile, training state untouched;
                        # excluded from the throughput windows
                        from shallowspeed_tpu.telemetry import (
                            bubble as _bubble)

                        tc = time.time()
                        htok, htgt = make_batch(args, vocab, step,
                                                text_data)
                        cal = _bubble.calibrate_compiled(
                            engine, tracer, local_rows(htok),
                            local_rows(htgt))
                        rates.pause(time.time() - tc, kind="calibration")
                        if cal is not None:
                            telem.set_bubble(
                                bubble_static=cal["bubble_static"],
                                bubble_measured=cal["bubble_measured"])
                            metrics.log(event="bubble", step=step,
                                        **cal["bubble_detail"],
                                        bubble_static=cal[
                                            "bubble_static"],
                                        bubble_measured=cal[
                                            "bubble_measured"])
                            rprint(f"             bubble measured "
                                   f"{cal['bubble_measured']:.1%} vs "
                                   f"static {cal['bubble_static']:.1%} "
                                   f"({si['schedule']}, n_mu="
                                   f"{si['n_mu']}, pp={si['pp']})")
                    if args.experts and hasattr(engine, "router_stats"):
                        # routing observability: the capacity drop is
                        # silent in the loss (ops/moe.py), so surface it
                        rs = engine.router_stats(tok)
                        if rs is not None:
                            rprint(f"             moe drop "
                                   f"{rs['drop_fraction']:.1%}  load "
                                   f"{rs['expert_load']}")
                            metrics.log(event="moe_router", step=step,
                                        **rs)
                if args.val_every and ((step + 1) % args.val_every == 0
                                       or step == args.steps - 1):
                    # drain queued TRAIN work first, so its wall time isn't
                    # booked as val time (val points need not be log points)
                    jax.block_until_ready(loss_dev)
                    tv = time.time()
                    vl = val_loss(step)
                    rates.pause(time.time() - tv, kind="val")
                    rprint(f"step {step:5d}  val_loss {vl:.4f}  "
                           f"ppl {np.exp(min(vl, 20)):,.2f}")
                    metrics.log(event="val", step=step,
                                val_loss=round(vl, 6),
                                perplexity=round(float(np.exp(min(vl, 20))),
                                                 3))
                if args.save_dir and ((step + 1) % args.save_every == 0
                                      or step == args.steps - 1):
                    # save wall time (the device->host fetch of a big
                    # model) must not depress the next window's rate
                    ts = time.time()
                    # never checkpoint a poisoned iterate: the restore
                    # point must not BE the state the supervisor is
                    # about to recover from (found by the chaos
                    # NaN-storm drill). Two signals: the monitor's
                    # last-observed pack, and THIS step's loss — a
                    # poison landing between a log point and a save
                    # would slip past the monitor alone. The float()
                    # sync is free here: the save fetches the whole
                    # state to host anyway.
                    cur_loss = float(loss_dev)
                    if (monitor is not None and monitor.unhealthy()) \
                            or not np.isfinite(cur_loss):
                        status = (monitor.heartbeat_status()
                                  if monitor is not None
                                  and monitor.unhealthy()
                                  else f"loss {cur_loss}")
                        rprint(f"step {step}: state is {status!r} — "
                               f"skipping checkpoint save")
                        ledger.note("ckpt_save_skipped_unhealthy",
                                    count=1)
                    else:
                        try:
                            save_ckpt(args.save_dir, step)
                        except (checkpoint.CheckpointError,
                                OSError) as e:
                            _warn_save_failed(e)
                        except RuntimeError as e:
                            # the async saver surfaces its worker's
                            # failure on the NEXT call, wrapped
                            if "checkpoint" not in str(e):
                                raise
                            _warn_save_failed(e)
                    rates.pause(time.time() - ts, kind="ckpt_save")
            t_loop_done = time.time()
    finally:
        # abandoning mid-stream must not leave placed batches pinned on
        # device by a blocked producer thread
        if hasattr(placed, "close"):
            placed.close()
        if telem is not None:
            tracer.close()  # flush spans.jsonl, write trace.json
            if args.trace_dir:
                path = telem.write_summary(args.trace_dir)
                rprint(f"telemetry: {path} (+ spans.jsonl, trace.json)")
        if plane is not None:
            # final profile snapshot + any in-flight capture land in
            # the outputs before the monitor's own final snapshot
            chaos.remove_observer(plane.on_fault)
            plane.close()
        if live_mon is not None:
            # final sketch snapshot into the JSONL (the offline
            # merge/parity path reads it), then stop the endpoint
            chaos.remove_observer(live_mon.note_line)
            close_monitor(live_mon, live_srv)
        if t_loop_done is not None:
            # loop exit -> here: profiler trace write, prefetch close,
            # tracer flush + summary — wall clock the ledger must name
            ledger.note("teardown",
                        seconds=max(0.0, time.time() - t_loop_done))
        if saver is not None:
            if sys.exc_info()[0] is None:
                # wait() is the COLLECTIVE failure-exchange point: if
                # process 0's background write failed, every process
                # raises here together instead of peers sailing into
                # sample_and_print's collectives against a dying rank
                saver.wait()
                saver.close()  # stop the worker; surface any IO error
            else:
                # an exception is already propagating (e.g. the divergence
                # SystemExit with its forensic-snapshot path) — don't let a
                # checkpoint-write error from close() replace it
                try:
                    saver.close()
                except Exception as ckpt_err:
                    print(f"[warn] async checkpoint save failed during "
                          f"teardown: {ckpt_err!r}", file=sys.stderr)

    plan = chaos.active()
    if plan is not None and plan.unfired():
        # a clean exit with scheduled-but-unfired faults means the
        # drill injected less than planned — say so, or a green run
        # overstates what it proved
        rprint(f"chaos: scheduled fault(s) never fired: "
               f"{', '.join(plan.unfired())}")
    if args.generate > 0:
        t_sample = time.time()
        with ema_weights():
            sample_and_print(args, engine, cfg, vocab, text_data,
                             tokenizer, metrics=metrics)
        # post-training sampling is wall-clock the goodput ledger must
        # name (decode compile alone can be seconds)
        ledger.note("sample", seconds=time.time() - t_sample)
    return loss


def sample_and_print(args, engine, cfg, vocab, text_data, tokenizer=None,
                     metrics=None):
    """KV-cache decode from the trained/restored model: --prompt (bytes,
    or BPE ids with --tokenizer bpe) or a 16-token data-stream prefix."""
    from shallowspeed_tpu.models.generate import generate
    from shallowspeed_tpu.utils import rprint

    # length already validated fail-fast at argument-checking time
    # (--prompt/--sample-only force args.generate to be set there;
    # byte count upper-bounds the BPE token count, so the check holds)
    if args.prompt:
        if tokenizer is not None:
            prompt = tokenizer.encode(args.prompt)[None, :]
        else:
            prompt = np.frombuffer(args.prompt.encode(), np.uint8).astype(
                np.int32)[None, :]
    else:
        prompt, _ = make_batch(args, vocab, 0, text_data)
        prompt = prompt[:1, :16]  # one row, short prefix
    if not args.kv_int8 and hasattr(engine, "generate") \
            and getattr(engine, "tp", 1) == 1 \
            and getattr(engine, "sp", 1) == 1 \
            and getattr(engine, "ep", 1) == 1 \
            and not getattr(engine, "fsdp", False):
        # vpp >= 1 both route here (round 5): the pipelined decode
        # walks pp*vpp logical phases, chunks in logical order
        # pipeline engine: decode ON the pp-sharded params (no re-gather
        # onto one device's memory); token-stream-identical to the
        # replicated path. --kv-int8 routes to the replicated path
        # (the quantized cache lives in models/generate only)
        t0 = time.time()
        out = engine.generate(prompt, args.generate,
                              temperature=args.temperature,
                              top_k=args.top_k, top_p=args.top_p,
                              seed=args.seed)
        out = np.asarray(out)  # drain the dispatch before timing stops
        dt = time.time() - t0
        rprint(f"decode: {prompt.shape[0] * args.generate / dt:,.0f} "
               f"tok/s (pp-sharded decode; includes prefill+compile)")
    else:
        if args.kv_int8 and hasattr(engine, "generate"):
            # the quantized cache lives in the replicated decode path
            # only — say so OUT LOUD, because this re-gathers the full
            # params onto one device (the memory cost the pipelined
            # decode exists to avoid)
            rprint("note: --kv-int8 decodes on the REPLICATED path "
                   "(full params re-gathered to one device); the "
                   "pipelined per-stage cache stays bf16 — drop "
                   "--kv-int8 to decode on the pp-sharded params")
        from shallowspeed_tpu.models.generate import (decode_report,
                                                      prompt_bucket_len)

        params = engine.get_canonical_params()
        kvq = "int8" if args.kv_int8 else ""
        # time the STEADY-STATE decode: the first call compiles and
        # prefills, so rate it over a second call's scan only when the
        # generation is long enough to care; otherwise report the
        # single-shot rate with compile included, and say so
        t0 = time.time()
        out = np.asarray(generate(
            params, prompt, cfg, args.generate,
            temperature=args.temperature, top_k=args.top_k,
            top_p=args.top_p, seed=args.seed, kv_quant=kvq))
        dt = time.time() - t0
        cache_len = prompt_bucket_len(prompt.shape[1], args.generate,
                                      cfg.max_seq) + args.generate
        rep = decode_report(params, cfg, prompt.shape[0], cache_len,
                            args.generate, dt, kv_quant=kvq)
        util = ("" if rep["hbm_util"] is None else
                f"  ({rep['hbm_util']:.0%} of the "
                f"{rep['hbm_peak_gbps']:,.0f} GB/s HBM roofline)")
        rprint(f"decode: {rep['tokens_per_sec']:,.0f} tok/s  "
               f"~{rep['bytes_per_token'] / 2**20:.1f} MiB/token sweep "
               f"-> {rep['hbm_gbps']:.1f} GB/s{util} "
               f"[includes prefill+compile]")
        if metrics is not None:
            metrics.log(event="generate", **rep)
    if tokenizer is not None:
        rprint(f"prompt: {tokenizer.decode_bytes(prompt[0])!r}")
        rprint(f"sample: {tokenizer.decode_bytes(out[0])!r}")
    else:
        rprint(f"prompt: {bytes(int(x) for x in prompt[0])!r}")
        rprint(f"sample: {bytes(int(x) for x in out[0])!r}")


if __name__ == "__main__":
    _args = parse_args()
    # same platform bootstrap as train.py
    from train import configure_platform

    configure_platform(_args)
    train(_args)
