"""Microbenchmark: Pallas flash attention vs XLA attention on the real chip.

Reproduces the BASELINE.md "flash-attention kernel vs XLA attention" table:
device-resident (B, T, H, D) inputs, forward and forward+backward timings,
best of `--reps` timed runs after a compile warmup, synced via device_get.

    python scripts/bench_attention.py [--seqs 2048 8192] [--batch 2]
        [--heads 8] [--head-dim 64] [--dtype bf16|f32] [--reps 5]

Prints one line per (T, pass) with both times and the speedup.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))  # runnable from any cwd without install


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--seqs", type=int, nargs="+", default=[2048, 8192])
    p.add_argument("--batch", type=int, default=2)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--head-dim", type=int, default=64)
    p.add_argument("--dtype", default="bf16", choices=["f32", "bf16"],
                   help="bf16 is the BASELINE.md table's dtype (and the "
                        "realistic training dtype)")
    p.add_argument("--reps", type=int, default=5)
    return p.parse_args()


def main():
    args = parse_args()
    import jax
    import jax.numpy as jnp

    from shallowspeed_tpu.ops.attention import attention
    from shallowspeed_tpu.ops.flash_attention import flash_attention

    dt = jnp.bfloat16 if args.dtype == "bf16" else jnp.float32
    rng = np.random.default_rng(0)

    def sync_cost() -> float:
        """One device_get round-trip to the host —
        measured so it can be subtracted from the timed runs instead of
        being amortized into short-T per-call times."""
        z = jax.device_put(jnp.zeros(()))
        jax.device_get(z)
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            jax.device_get(z)
            best = min(best, time.perf_counter() - t0)
        return best

    sync_s = sync_cost()

    def timed(fn, *xs, iters=20) -> float:
        """Per-call seconds over `iters` queued dispatches per timed run,
        with the single end-of-run sync round-trip subtracted."""
        fn(*xs)  # compile warmup
        jax.device_get(jnp.zeros(()))
        best = float("inf")
        for _ in range(args.reps):
            t0 = time.perf_counter()
            out = None
            for _ in range(iters):
                out = fn(*xs)
            jax.device_get(jax.tree_util.tree_leaves(out)[0].ravel()[:1])
            elapsed = max(time.perf_counter() - t0 - sync_s, 1e-9)
            best = min(best, elapsed / iters)
        return best

    for t in args.seqs:
        shape = (args.batch, t, args.heads, args.head_dim)
        q, k, v = (jnp.asarray(rng.normal(size=shape), dt) for _ in range(3))

        xla_fwd = jax.jit(lambda q, k, v: attention(q, k, v, causal=True))
        fla_fwd = jax.jit(
            lambda q, k, v: flash_attention(q, k, v, causal=True))

        def loss(fn):
            return lambda q, k, v: (
                fn(q, k, v).astype(jnp.float32) ** 2).sum()

        xla_bwd = jax.jit(jax.grad(loss(
            lambda q, k, v: attention(q, k, v, causal=True)),
            argnums=(0, 1, 2)))
        fla_bwd = jax.jit(jax.grad(loss(
            lambda q, k, v: flash_attention(q, k, v, causal=True)),
            argnums=(0, 1, 2)))

        for name, ref_fn, fl_fn in (("fwd", xla_fwd, fla_fwd),
                                    ("fwd+bwd", xla_bwd, fla_bwd)):
            tx = timed(ref_fn, q, k, v)
            tf = timed(fl_fn, q, k, v)
            print(f"T={t:6d} {name:8s} xla {tx * 1e3:8.2f} ms   "
                  f"flash {tf * 1e3:8.2f} ms   speedup {tx / tf:5.2f}x")


if __name__ == "__main__":
    main()
