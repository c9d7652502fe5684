"""Decode benchmark: prefill tok/s and steady-state decode tok/s.

Round 1 had no generation perf number at all (VERDICT item 6). The whole
generation — parallel prefill + a `lax.scan` decode loop — is ONE
compiled XLA program (`models/generate.py`), so per-dispatch host
latency is paid once per measurement, not per token.

Method: time `generate(max_new=N1)` and `generate(max_new=N2)` (compiled,
best of 3 each); steady decode rate = (N2-N1) * B / (t2 - t1) — the
difference cancels the prefill and the fixed dispatch cost. Prefill tok/s
= B * Tp / t(max_new=1). GQA rows show the decode-bandwidth win of the
unrepeated-cache grouped attention (`generate._cached_attention`).

Usage: python scripts/bench_decode.py  — prints one JSON line per config.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np  # noqa: E402


def time_generate(params, prompt, cfg, max_new, reps=3, kv_quant=""):
    import jax

    from shallowspeed_tpu.models.generate import generate

    out = generate(params, prompt, cfg, max_new, temperature=0.0,
                   kv_quant=kv_quant)
    jax.device_get(out)  # compile + drain (excluded)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.device_get(generate(params, prompt, cfg, max_new,
                                temperature=0.0, kv_quant=kv_quant))
        best = min(best, time.perf_counter() - t0)
    return best


def run_config(batch, prompt_len, max_seq, kv_heads=0, d_model=1024,
               n_layers=8, n_heads=16, kv_quant=""):
    import jax

    from shallowspeed_tpu.models import transformer as T

    cfg = T.TransformerConfig(
        vocab=256, d_model=d_model, n_heads=n_heads, n_layers=n_layers,
        max_seq=max_seq, dtype=np.float32,
        compute_dtype=np.dtype("bfloat16"), rope=True, norm="rmsnorm",
        ffn="swiglu", n_kv_heads=kv_heads)
    params = jax.device_put(T.init(cfg, seed=0))
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab, (batch, prompt_len)).astype(np.int32)

    n1 = 32
    n2 = min(256, max_seq - prompt_len)
    t_pre = time_generate(params, prompt, cfg, 1, kv_quant=kv_quant)
    t1 = time_generate(params, prompt, cfg, n1, kv_quant=kv_quant)
    t2 = time_generate(params, prompt, cfg, n2, kv_quant=kv_quant)
    decode_tps = (n2 - n1) * batch / max(t2 - t1, 1e-9)
    return {
        "metric": "decode_throughput",
        "config": {"batch": batch, "prompt_len": prompt_len,
                   "max_seq": max_seq, "d_model": d_model,
                   "n_layers": n_layers, "n_heads": n_heads,
                   "kv_heads": kv_heads or n_heads,
                   "kv_quant": kv_quant or "bf16"},
        "prefill_tokens_per_sec": round(batch * prompt_len / t_pre, 0),
        "decode_tokens_per_sec": round(decode_tps, 1),
        "decode_ms_per_token": round(1000.0 / (decode_tps / batch), 3),
    }


def run_pp_config(pp, batch=4, prompt_len=64, max_seq=256, d_model=128,
                  n_layers=4, n_heads=4):
    """Pipelined decode on pp-sharded params vs replicated decode, on a
    virtual pp-device CPU mesh (a pp>1 mesh needs distinct devices, so
    absolute tok/s is not chip-representative — the RATIO is the cost
    of the per-token pp-phase latency chain; token-exactness is asserted
    in tests/test_generate.py)."""
    import jax
    from jax.sharding import Mesh

    from shallowspeed_tpu.models import transformer as T
    from shallowspeed_tpu.optim import SGD
    from shallowspeed_tpu.parallel.pipeline_lm import PipelineLMEngine

    cfg = T.TransformerConfig(
        vocab=256, d_model=d_model, n_heads=n_heads, n_layers=n_layers,
        max_seq=max_seq, dtype=np.float32, rope=True, norm="rmsnorm",
        ffn="swiglu")
    mesh = Mesh(np.array(jax.devices()[:pp]).reshape(1, pp),
                ("dp", "pp"))
    eng = PipelineLMEngine(cfg, SGD(0.1), mesh, n_mubatches=1, seed=0)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab,
                          (batch, prompt_len)).astype(np.int32)

    def timed(max_new, reps=3):
        eng.generate(prompt, max_new, temperature=0.0)  # compile
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            eng.generate(prompt, max_new, temperature=0.0)
            best = min(best, time.perf_counter() - t0)
        return best

    n1, n2 = 16, min(128, max_seq - prompt_len)
    t1, t2 = timed(n1), timed(n2)
    tps = (n2 - n1) * batch / max(t2 - t1, 1e-9)
    return {
        "metric": "pp_decode_throughput",
        "config": {"pp": pp, "batch": batch, "prompt_len": prompt_len,
                   "d_model": d_model, "n_layers": n_layers},
        "decode_tokens_per_sec": round(tps, 1),
        "decode_ms_per_token": round(1000.0 / (tps / batch), 3),
    }


def main():
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--pp", type=int, default=0,
                    help="benchmark pipelined decode over a virtual "
                         "pp-device CPU mesh instead of the single-chip "
                         "KV-cache decode")
    ap.add_argument("--long-context", action="store_true",
                    help="the cache-share-dominant regime (b8, ~8k "
                         "context): bf16 vs int8 KV cache head-to-head "
                         "(round 5 — the lever the round-4 roofline "
                         "named for when the cache dominates)")
    args = ap.parse_args()
    if args.long_context:
        for kv_quant in ("", "int8"):
            print(json.dumps(run_config(
                batch=8, prompt_len=7936, max_seq=8192,
                kv_quant=kv_quant)), flush=True)
            print(json.dumps(run_config(
                batch=8, prompt_len=7936, max_seq=8192, kv_heads=4,
                kv_quant=kv_quant)), flush=True)
        return
    if args.pp:
        import os

        flags = os.environ.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count="
                f"{max(args.pp, 2)}").strip()
        import jax

        jax.config.update("jax_platforms", "cpu")
        for pp in sorted({1, 2, args.pp}):
            print(json.dumps(run_pp_config(pp)), flush=True)
        return
    for kwargs in (
        {"batch": 1, "prompt_len": 512, "max_seq": 2048},
        {"batch": 8, "prompt_len": 512, "max_seq": 2048},
        {"batch": 32, "prompt_len": 128, "max_seq": 1024},
        # GQA 4x fewer kv heads: the cache sweep shrinks 4x
        {"batch": 8, "prompt_len": 512, "max_seq": 2048, "kv_heads": 4},
        {"batch": 1, "prompt_len": 512, "max_seq": 2048, "kv_heads": 4},
    ):
        print(json.dumps(run_config(**kwargs)), flush=True)


if __name__ == "__main__":
    main()
