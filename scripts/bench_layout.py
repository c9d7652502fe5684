"""Attention data-layout experiment: token-major vs head-major.

Round-4 gap accounting (BASELINE.md) measured 5.0% of d2048 step time in
"data formatting" — the (B,T,H,hd) <-> (B*H,T,hd) layout copies around
the flash kernel — and estimated a head-major layout (transposing the
projection weights instead of the activations) worth ~2 MFU points.
VERDICT r4 item 3: take the win or record a measured refutation.

This script measures exactly that sub-graph at the bench shapes, fwd +
bwd, as one fused scan per variant (the `bench_matmul.py` methodology:
weight-dependency chain across steps so XLA can neither hoist nor DCE):

- token_major: qkv dot -> reshape -> transpose-fold -> kernel ->
  transpose-unfold -> out-proj dot (the current model path).
- head_major: qkv einsum 'btd,dhxc->xbhtc' (projection weights carry the
  head split; the kernel's (B*H,T,hd) view is then a FREE reshape) ->
  kernel -> out einsum 'bhtc,hcd->btd'.

Identical math (same W layout bits, same kernel) — only the placement of
the layout permutation differs, so the delta is the data-formatting cost
XLA can or cannot fuse away.

Usage: python scripts/bench_layout.py [--steps 10 --batch 8 ...]
Prints one JSON line per variant plus the verdict.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np  # noqa: E402


def build(args):
    import jax
    import jax.numpy as jnp

    from shallowspeed_tpu.ops import flash_attention as fa

    B, T, H, hd = args.batch, args.seq_len, args.n_heads, args.head_dim
    D = H * hd
    bq = fa._pick_block(T, 512)
    bk = fa._pick_block(T, 512)
    kw = dict(causal=True, window=0, bq=bq, bk=bk, nqb_chunk=T // bq,
              interpret=False)

    # shared flash core on pre-folded (B*H, T, hd) operands, with the
    # hand VJP from the module (so both variants run the same kernels)
    @jax.custom_vjp
    def flash3(q3, k3, v3):
        o3, _ = fa._chunk_fwd(q3, k3, v3, 0, **kw)
        return o3

    def flash3_fwd(q3, k3, v3):
        o3, lse = fa._chunk_fwd(q3, k3, v3, 0, **kw)
        return o3, (q3, k3, v3, o3, lse)

    def flash3_bwd(res, do3):
        q3, k3, v3, o3, lse = res
        delta = fa._delta_of(do3, o3, lse)
        dq3 = fa._chunk_dq(q3, k3, v3, do3, lse, delta, 0, **kw)
        dk3, dv3 = fa._chunk_dkv(q3, k3, v3, do3, lse, delta, 0,
                                 groups=1, **kw)
        return (dq3.astype(q3.dtype), dk3.astype(k3.dtype),
                dv3.astype(v3.dtype))

    flash3.defvjp(flash3_fwd, flash3_bwd)
    cdt = jnp.bfloat16

    def token_major(x, Wqkv, Wo):
        # current model path: token-major dot, fold/unfold activations
        qkv = (x @ Wqkv.astype(cdt)).reshape(B, T, H, 3, hd)
        q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
        o3 = flash3(fa._to_bhsd(q), fa._to_bhsd(k), fa._to_bhsd(v))
        o = fa._from_bhsd(o3, B, H).reshape(B, T, D)
        return x + o @ Wo.astype(cdt)

    def head_major(x, Wqkv, Wo):
        # head-major: the permutation rides the PROJECTION WEIGHTS; the
        # kernel view is a free reshape of the einsum output
        w = Wqkv.astype(cdt).reshape(D, H, 3, hd)
        qkv = jnp.einsum("btd,dhxc->xbhtc", x, w)
        q3, k3, v3 = (qkv[i].reshape(B * H, T, hd) for i in range(3))
        o3 = flash3(q3, k3, v3)
        o = o3.reshape(B, H, T, hd)
        return x + jnp.einsum("bhtc,hcd->btd", o,
                              Wo.astype(cdt).reshape(H, hd, D))

    rng = np.random.default_rng(0)
    x0 = jnp.asarray(rng.standard_normal((B, T, D)), cdt) * 0.02
    Wqkv0 = jnp.asarray(rng.standard_normal((D, 3 * D)), jnp.float32) * 0.02
    Wo0 = jnp.asarray(rng.standard_normal((D, D)), jnp.float32) * 0.02

    def stepper(block):
        def loss(Wqkv, Wo):
            return jnp.sum(block(x0, Wqkv, Wo).astype(jnp.float32))

        def step(carry, _):
            Wqkv, Wo = carry
            gq, go = jax.grad(loss, argnums=(0, 1))(Wqkv, Wo)
            # dependency chain: next step's weights depend on this
            # step's grads, so XLA cannot hoist or elide any step
            return (Wqkv + 1e-6 * gq, Wo + 1e-6 * go), gq[0, 0]

        @jax.jit
        def run():
            (_, _), probes = jax.lax.scan(step, (Wqkv0, Wo0), None,
                                          length=args.steps)
            return probes

        return run

    def hm_qkv_only(x, Wqkv, Wo):
        # head-major projections, token-major out-projection: isolates
        # the qkv-side fold cost from the out-side einsum cost
        w = Wqkv.astype(cdt).reshape(D, H, 3, hd)
        qkv = jnp.einsum("btd,dhxc->xbhtc", x, w)
        q3, k3, v3 = (qkv[i].reshape(B * H, T, hd) for i in range(3))
        o3 = flash3(q3, k3, v3)
        o = fa._from_bhsd(o3, B, H).reshape(B, T, D)
        return x + o @ Wo.astype(cdt)

    def hm_out_only(x, Wqkv, Wo):
        qkv = (x @ Wqkv.astype(cdt)).reshape(B, T, H, 3, hd)
        q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
        o3 = flash3(fa._to_bhsd(q), fa._to_bhsd(k), fa._to_bhsd(v))
        o = o3.reshape(B, H, T, hd)
        return x + jnp.einsum("bhtc,hcd->btd", o,
                              Wo.astype(cdt).reshape(H, hd, D))

    def no_permute(x, Wqkv, Wo):
        # LOWER BOUND, deliberately wrong math: plain reshapes where the
        # transposes were (different token<->head association, same
        # shapes/FLOPs). The gap token_major - no_permute is the TOTAL
        # winnable data-formatting cost; if it is ~0 the copies are
        # already fused into adjacent ops and there is nothing to take.
        qkv = (x @ Wqkv.astype(cdt)).reshape(B, T, H, 3, hd)
        q, k, v = qkv[..., 0, :], qkv[..., 1, :], qkv[..., 2, :]
        o3 = flash3(q.reshape(B * H, T, hd), k.reshape(B * H, T, hd),
                    v.reshape(B * H, T, hd))
        return x + o3.reshape(B, T, D) @ Wo.astype(cdt)

    return {"token_major": stepper(token_major),
            "head_major": stepper(head_major),
            "hm_qkv_only": stepper(hm_qkv_only),
            "hm_out_only": stepper(hm_out_only),
            "no_permute_lower_bound": stepper(no_permute)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=2048)
    ap.add_argument("--n-heads", type=int, default=16)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--rounds", type=int, default=5)
    args = ap.parse_args()

    import jax

    runners = build(args)
    out = {}
    for name, run in runners.items():
        probes = jax.device_get(run())  # compile + correctness probe
        assert np.all(np.isfinite(probes)), name
        out[name] = float("inf")
    # interleave variants across rounds so slow host drift hits
    # every variant equally; per-variant min over rounds
    for _ in range(args.rounds):
        for name, run in runners.items():
            t0 = time.perf_counter()
            jax.device_get(run())
            out[name] = min(out[name],
                            (time.perf_counter() - t0) / args.steps)
    for name in out:
        out[name] = round(out[name] * 1e3, 3)
        print(json.dumps({"variant": name, "ms_per_step": out[name]}))
    best = min(out, key=out.get)
    ratio = out["token_major"] / out[best]
    print(json.dumps({
        "metric": "attn_layout_speedup_best_vs_token_major",
        "best_variant": best,
        "value": round(ratio, 4),
        "verdict": (f"{best} wins" if best != "token_major"
                    and ratio > 1.01 else
                    "token_major holds (refutation measured)")}))


if __name__ == "__main__":
    main()
