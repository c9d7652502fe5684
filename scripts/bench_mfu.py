"""MFU benchmark: transformer-LM training at MXU-saturating scale.

The reference's perf story is wall-clock prints (`/root/reference/
train.py:131-137`); the TPU bar is fraction-of-peak. This script trains a
saturating config (d_model >= 1024, seq >= 2048, bf16 + flash attention)
for a fixed number of steady-state steps and reports achieved TFLOP/s and
MFU against the detected chip peak (`shallowspeed_tpu/flops.py`).

Usage: python scripts/bench_mfu.py [--d-model 1024 --n-layers 8 ...]
Prints one JSON line per config.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np  # noqa: E402


def run(args) -> dict:
    import jax
    from jax.sharding import Mesh

    from shallowspeed_tpu.flops import mfu, transformer_flops_per_token
    from shallowspeed_tpu.models.transformer import TransformerConfig
    from shallowspeed_tpu.optim import Adafactor, AdamW
    from shallowspeed_tpu.parallel.context import ContextParallelEngine

    cfg = TransformerConfig(
        vocab=args.vocab, d_model=args.d_model, n_heads=args.n_heads,
        n_layers=args.n_layers, max_seq=args.seq_len,
        dtype=np.float32, compute_dtype=np.dtype("bfloat16"),
        rope=True, norm="rmsnorm", ffn=args.ffn, remat=args.remat,
        remat_policy=args.remat_policy, xent_chunk=args.xent_chunk)
    opt = (Adafactor(3e-4) if args.optimizer == "adafactor"
           else AdamW(3e-4))
    devs = np.array(jax.devices()[:1])
    mesh = Mesh(devs.reshape(1, 1), ("dp", "sp"))
    eng = ContextParallelEngine(cfg, opt, mesh, seed=0,
                                attn=args.attn, accum=args.accum)

    rng = np.random.default_rng(0)
    toks = rng.integers(0, args.vocab,
                        (args.batch_size, args.seq_len)).astype(np.int32)
    tgts = np.roll(toks, -1, axis=1)

    # steady state: the whole S-step run is ONE XLA dispatch (train_run's
    # lax.scan), so per-dispatch host latency cannot pollute the timing
    stack_t = np.broadcast_to(toks, (args.steps, *toks.shape)).copy()
    stack_g = np.broadcast_to(tgts, (args.steps, *tgts.shape)).copy()
    jax.device_get(eng.train_run(stack_t, stack_g))  # compile (excluded)
    best = 0.0
    for _ in range(3):
        t0 = time.perf_counter()
        losses = eng.train_run(stack_t, stack_g)
        jax.device_get(losses)  # the run is done when its losses are here
        dt = time.perf_counter() - t0
        best = max(best, args.steps * args.batch_size * args.seq_len / dt)

    stats = mfu(best, cfg, args.seq_len, dtype="bf16")
    return {
        "metric": "transformer_train_mfu",
        "config": {
            "d_model": args.d_model, "n_layers": args.n_layers,
            "n_heads": args.n_heads, "seq_len": args.seq_len,
            "batch": args.batch_size, "vocab": args.vocab,
            "ffn": args.ffn, "attn": args.attn, "remat": args.remat,
            "remat_policy": args.remat_policy,
            "xent_chunk": args.xent_chunk, "accum": args.accum,
            "optimizer": args.optimizer,
            "params_m": round(sum(
                x.size for x in jax.tree_util.tree_leaves(eng.params))
                / 1e6, 1),
        },
        "tokens_per_sec": round(best, 0),
        "flops_per_token": round(
            transformer_flops_per_token(cfg, args.seq_len)),
        "tflops": round(stats["tflops"], 1),
        "peak_tflops": stats["peak_tflops"],
        "mfu": None if stats["mfu"] is None else round(stats["mfu"], 4),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--d-model", type=int, default=1024)
    ap.add_argument("--n-heads", type=int, default=16)
    ap.add_argument("--n-layers", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=2048)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--ffn", default="swiglu", choices=["gelu", "swiglu"])
    ap.add_argument("--attn", default="flash",
                    choices=["flash", "ring", "ring-flash", "ulysses",
                             "ulysses-flash"])
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--remat-policy", default="full",
                    choices=["full", "attn", "dots"])
    ap.add_argument("--xent-chunk", type=int, default=0)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "adafactor"])
    args = ap.parse_args()
    print(json.dumps(run(args)))


if __name__ == "__main__":
    main()
