"""Narrow-matmul efficiency probe (VERDICT r2 item 5) — corrected.

Round 2 recorded "(16384,1024)@(1024,4096) at ~21 TFLOP/s vs 159-170 at
K>=2048" and BASELINE.md blamed a narrow-K tiling pathology. Re-measured
with a methodology that keeps host transfer and dispatch out of the
timing (see below), the cliff is real but half the story was
measurement error:

- fetching any full matrix result to the host costs more than the matmul;
- consuming only out[0,0] lets XLA dead-code-narrow the matmul
  (apparent 1200+ "TFLOP/s");
- small per-dispatch chains sit on the 50-200ms dispatch-latency floor.

Correct method (here): a `lax.scan` chain of `iters` matmuls per
dispatch, the weight perturbed per step (defeats loop hoisting), the
full product consumed by a sum into the carry (defeats DCE), one scalar
fetched. Measured 2026-07-31 on the v5e:

    (16384,1024)@(1024,4096)  ~75 TFLOP/s   (not 21)
    (16384,1024)@(1024,8192) ~125 TFLOP/s   (wide N recovers the MXU)
    (16384,2048)@(2048,8192) ~133 TFLOP/s
    (16384, 512)@( 512,2048)  ~25 TFLOP/s   (genuinely starved)
    (16384,1024)@(1024,1024)  (proj-shaped) — see output

The surviving pathology is SMALL ops (K and N both ~<=1024), where
fixed per-pass costs can't amortize — which is why d_model<=1024 model
configs underuse the chip (their proj/down projections are exactly this
shape). A hand-tiled Pallas matmul (`shallowspeed_tpu/ops/matmul.py`)
does NOT beat Mosaic here (~65 vs ~75 TFLOP/s at K=1024) — kept as an
op + evidence, not wired into models. The model-level mitigation is
documented in BASELINE.md (larger batch*seq, or d_model >= 2048), and
`train_lm.py` warns when a config lands in the starved regime.

Usage: python scripts/bench_matmul.py [--m 16384] [--iters 100]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np  # noqa: E402


def bench_tflops(mm, m, k, n, iters=100, reps=3):
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.bfloat16)
    y = jnp.asarray(rng.normal(size=(k, n)), jnp.bfloat16)

    @jax.jit
    def chain(x, y):
        def body(c, i):
            yy = y + i.astype(y.dtype) * jnp.bfloat16(1e-6)
            z = mm(x, yy)
            return c + z.astype(jnp.float32).sum(), None

        s, _ = jax.lax.scan(body, jnp.float32(0.0), jnp.arange(iters))
        return s

    jax.device_get(chain(x, y))  # compile + drain
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.device_get(chain(x, y))
        best = min(best, (time.perf_counter() - t0) / iters)
    return 2.0 * m * n * k / best / 1e12, best


def main():
    import jax

    from shallowspeed_tpu.ops.matmul import blocked_matmul

    ap = argparse.ArgumentParser()
    ap.add_argument("--m", type=int, default=16384)
    ap.add_argument("--iters", type=int, default=100)
    args = ap.parse_args()
    m = args.m

    shapes = [(1024, 4096), (1024, 8192), (2048, 8192), (512, 2048),
              (1024, 1024), (4096, 1024)]
    for k, n in shapes:
        for name, mm in (
            ("xla", lambda x, y: x @ y),
            ("pallas", lambda x, y: blocked_matmul(
                x, y, bm=512, bk=min(1024, x.shape[1]), bn=1024)),
        ):
            try:
                tf, dt = bench_tflops(mm, m, k, n, iters=args.iters)
                rec = {"tflops": round(tf, 1),
                       "ms": round(dt * 1e3, 3), "error": None}
            except Exception as e:
                rec = {"tflops": None, "ms": None,
                       "error": repr(e)[:120]}
            print(json.dumps({"metric": "matmul_tflops", "m": m, "k": k,
                              "n": n, "variant": name, **rec}),
                  flush=True)


if __name__ == "__main__":
    main()
