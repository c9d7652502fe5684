"""One layer's read of a prefill chunk through its request's table, on
the chip: the kernel (`ops.flash_attention.paged_flash_prefill`) against
the gathered read it replaced in `_prefill_chunk` (`gather_table` +
`masked_attention`), at the head shapes, table widths and fills of the
benchmark's serving cells (PERF.md, PR 34).

    python scripts/bench_paged_prefill.py [--shapes mistral olmo ...]
        [--tq 0 128 256] [--chunks 0 8 16] [--reps 20]

Each timing is one jitted program that runs the read `reps` times in
turn (each query depends on the last result), so dispatch is paid once;
the line gives microseconds a read, the matmul operations of the keys
the chunk's true rows can see over that time, and the kernel's largest
difference from the gathered read on the true rows. Exits 3 where there
is no TPU: a time from the CPU says nothing."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from shallowspeed_tpu.models.kv_cache import masked_attention, position_mask
from shallowspeed_tpu.ops.flash_attention import paged_flash_prefill
from shallowspeed_tpu.serving.cache import gather_table

BS, C = 16, 512
# query heads, KV heads, table width, pool blocks, window, and the
# chunks timed: (first position in the table's coordinates, true length)
SHAPES = {
    # `doc-batch`: a 2,049-3,840-token document's table, first / middle /
    # last chunk
    "mistral": dict(h=32, hkv=8, w=256, n=2049, window=4096,
                    chunks=[(0, 512), (1536, 512), (3328, 512)]),
    # `chat`: a 128-token prompt in the narrowest table, and the second
    # chunk of a 1,024-token one
    "olmo-8": dict(h=16, hkv=16, w=8, n=1281, window=0,
                   chunks=[(0, 128)]),
    "olmo-64": dict(h=16, hkv=16, w=64, n=1281, window=0,
                    chunks=[(0, 512), (512, 512)]),
    # `reason-batch`: the full group at 6 k and 12 k of context, the
    # window group once its table has rolled (161 blocks held)
    "trinity-full": dict(h=32, hkv=4, w=768, n=4097, window=0,
                         chunks=[(0, 512), (5632, 512), (11776, 512)]),
    "trinity-window": dict(h=32, hkv=4, w=176, n=4097, window=2048,
                           chunks=[(0, 512), (2048, 512)]),
}
HD = 128


def inputs(sh, seed):
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    pool = {name: jax.random.normal(jax.random.fold_in(key, i),
                                    (sh["n"], sh["hkv"], BS, HD),
                                    jnp.bfloat16)
            for i, name in enumerate(("k", "v"))}
    bt = jnp.asarray(rng.permutation(np.arange(1, sh["n"]))[:sh["w"]],
                     jnp.int32)
    q = jax.random.normal(jax.random.fold_in(key, 9), (C, sh["h"], HD),
                          jnp.bfloat16)
    return q, pool, bt


def gathered(sh):
    """The read the chunk ran before: the table gathered at its whole
    width, made head-major, float32 scores over all of it."""
    def read(q, pool, bt, at0, n_tok):
        at = at0 + jnp.arange(C)
        valid = position_mask(sh["w"] * BS, at[:, None], sh["window"])
        # (the configuration is read for int8 pools only)
        return masked_attention(q[None], gather_table(pool, bt[None]),
                                valid[None, None, None], None)[0]

    return read


def timed(read, args, reps):
    @jax.jit
    def many(q, *rest):
        def body(_, q):
            return q + (read(q, *rest) * 1e-3).astype(q.dtype)

        return jax.lax.fori_loop(0, reps, body, q)

    many(*args).block_until_ready()
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        many(*args).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best / reps * 1e6


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="+", default=list(SHAPES))
    ap.add_argument("--tq", nargs="+", type=int, default=[0],
                    help="queries a tile; 0 = the kernel's own")
    ap.add_argument("--chunks", nargs="+", type=int, default=[0],
                    help="blocks a compute step; 0 = the kernel's own")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args()
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.platform, "kind": dev.device_kind}))
    if dev.platform != "tpu":
        return 3
    for name in a.shapes:
        sh = SHAPES[name]
        q, pool, bt = inputs(sh, a.seed)
        for at0, n_tok in sh["chunks"]:
            args = (q, pool, bt, jnp.int32(at0), jnp.int32(n_tok))
            i = np.arange(n_tok)
            keys = (np.minimum(at0 + i + 1, sh["window"]) if sh["window"]
                    else at0 + i + 1).sum()
            gflop = 4 * sh["h"] * HD * int(keys) / 1e9
            ref = jax.jit(gathered(sh))(*args).astype(jnp.float32)[:n_tok]
            us = timed(gathered(sh), args, a.reps)
            line = {"shape": name, "at0": at0, "n_tok": n_tok,
                    "table_blocks": sh["w"]}
            print(json.dumps({**line, "read": "gathered",
                              "us": round(us, 1)}), flush=True)
            for tq in a.tq:
                for chunk in a.chunks:
                    read = lambda *args: paged_flash_prefill(
                        *args, window=sh["window"], tq=tq or None,
                        chunk=chunk or None)
                    got = jax.jit(read)(*args).astype(jnp.float32)[:n_tok]
                    err = float(jnp.abs(got - ref).max() / jnp.abs(ref).max())
                    us = timed(read, args, a.reps)
                    print(json.dumps({
                        **line, "read": "kernel", "tq": tq, "chunk": chunk,
                        "us": round(us, 1),
                        "tflop_s": round(gflop / us * 1e3, 1),
                        "relmax_vs_gathered": round(err, 5)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
