"""One layer's single-query read of a paged pool, on the chip: the
kernel (`ops.flash_attention.paged_flash_decode`) against the gathered
read it replaced in the decode tick (`gather_table` +
`masked_attention`; for the latent pool the absorbed contraction over
the gathered rows), at the three published head shapes and at the live
block counts of the benchmark's serving cells (PERF.md, PR 29).

    python scripts/bench_paged_decode.py [--shapes olmo mistral latent]
        [--chunks 0 8 16 32] [--reps 50]

Each timing is one jitted program that runs the read `reps` times in
turn (each query depends on the last result), so dispatch is paid once;
the line gives microseconds a read, the bytes of the live blocks over
that time, and the kernel's largest difference from the gathered read
on the same inputs. Exits 3 where there is no TPU: a time from the CPU
says nothing."""

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
from shallowspeed_tpu.ops.flash_attention import paged_flash_decode
from shallowspeed_tpu.serving.cache import gather_table

BS = 16
# rows, query heads, KV heads, head size, table width, pool blocks, pool
# leaves, window, and the live blocks of each row (dead rows: one scratch
# block) as the cells have them (PERF.md §5, ledger PR 28)
SHAPES = {
    "olmo": dict(s=16, h=16, hkv=16, hd=128, w=64, n=1281, leaves=("k", "v"),
                 window=0, live=[40, 36, 38, 20] + [0] * 12),
    "olmo-burst": dict(s=16, h=16, hkv=16, hd=128, w=128, n=1281,
                       leaves=("k", "v"), window=0,
                       live=[70, 60, 50, 40, 30, 20] + [0] * 10),
    "mistral": dict(s=8, h=32, hkv=8, hd=128, w=256, n=2049,
                    leaves=("k", "v"), window=4096,
                    live=[150, 170, 185, 195, 200, 210, 220, 230]),
    "latent": dict(s=32, h=16, hkv=1, hd=640, w=512, n=12289,
                   leaves=("ckr",), window=0,
                   live=[80 + 7 * i for i in range(32)]),
}


def inputs(sh, seed):
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    pool = {}
    for i, name in enumerate(sh["leaves"]):
        pool[name] = jax.random.normal(
            jax.random.fold_in(key, i), (sh["n"], sh["hkv"], BS, sh["hd"]),
            jnp.bfloat16)
    bt = np.zeros((sh["s"], sh["w"]), np.int32)
    pos = np.zeros(sh["s"], np.int32)
    free = rng.permutation(np.arange(1, sh["n"]))
    at = 0
    for r, nb in enumerate(sh["live"]):
        if nb:
            bt[r, :nb] = free[at:at + nb]
            at += nb
            pos[r] = nb * BS - 1 - int(rng.integers(0, BS))
    q = jax.random.normal(jax.random.fold_in(key, 9),
                          (sh["s"], sh["h"], sh["hd"]), jnp.bfloat16)
    return q, pool, jnp.asarray(bt), jnp.asarray(pos)


def gathered(sh):
    """The read the tick ran before: the table gathered at the bucket's
    width, one query a row contracted over all of it."""
    def read(q, pool, bt, pos):
        valid = position_mask(sh["w"] * BS, pos[:, None], sh["window"])
        if len(pool) == 1:
            (leaf,) = pool.values()
            rows = leaf[bt].reshape(sh["s"], -1, sh["hd"])
            s = jnp.einsum("bhx,bsx->bhs", q, rows,
                           preferred_element_type=jnp.float32)
            s = jnp.where(valid[:, None], s * sh["hd"] ** -0.5, -1e30)
            p = jax.nn.softmax(s, -1).astype(rows.dtype)
            return jnp.einsum("bhs,bsx->bhx", p, rows,
                              preferred_element_type=jnp.float32
                              ).astype(q.dtype)
        # (the configuration is read for int8 pools only)
        return masked_attention(q[:, None], gather_table(pool, bt),
                                valid[:, None, None, None, :], None)[:, 0]

    return read


def timed(read, args, reps):
    q, pool, bt, pos = args

    @jax.jit
    def many(q, pool, bt, pos):
        def body(_, q):
            return q + (read(q, pool, bt, pos) * 1e-3).astype(q.dtype)

        return jax.lax.fori_loop(0, reps, body, q)

    many(q, pool, bt, pos).block_until_ready()
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        many(q, pool, bt, pos).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return best / reps * 1e6


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", nargs="+", default=list(SHAPES))
    ap.add_argument("--chunks", nargs="+", type=int, default=[0, 8, 16, 32],
                    help="blocks a compute step; 0 = the kernel's own")
    ap.add_argument("--reps", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    a = ap.parse_args()
    dev = jax.devices()[0]
    print(json.dumps({"device": dev.platform, "kind": dev.device_kind}))
    if dev.platform != "tpu":
        return 3
    for name in a.shapes:
        sh = SHAPES[name]
        args = inputs(sh, a.seed)
        live = sum(max(1, nb) for nb in sh["live"])
        mb = (live * len(sh["leaves"]) * sh["hkv"] * BS * sh["hd"] * 2) / 1e6
        ref = jax.jit(gathered(sh))(*args)
        us = timed(gathered(sh), args, a.reps)
        print(json.dumps({"shape": name, "read": "gathered", "us": round(us, 1),
                          "live_blocks": live, "table_blocks": sh["s"] * sh["w"],
                          "live_mb": round(mb, 2)}), flush=True)
        for chunk in a.chunks:
            read = lambda q, pool, bt, pos: paged_flash_decode(
                q, pool, bt, pos, window=sh["window"], chunk=chunk or None)
            got = jax.jit(read)(*args)
            err = float(jnp.abs(got.astype(jnp.float32)
                                - ref.astype(jnp.float32)).max()
                        / jnp.abs(ref.astype(jnp.float32)).max())
            us = timed(read, args, a.reps)
            print(json.dumps({"shape": name, "read": "kernel", "chunk": chunk,
                              "us": round(us, 1),
                              "live_gb_s": round(mb / us * 1e3, 1),
                              "relmax_vs_gathered": round(err, 5)}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
