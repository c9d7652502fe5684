"""Where a routed-experts configuration's limits of comparison come from,
measured once on the chip: how often the program's bf16 hidden state
chooses other experts than the float32 reference, what that does to the
logits, and what a reference with 8-bit weights reads.

    python3 benchmarks/tools/routing_near_ties.py --tokens 2048 --seed 7

One sequence of random ids through the program's blocks without a cache
(`models/transformer.py`'s functions, in the configuration's serving
precision) and through `harness/reference_latent_experts.py`. Printed,
one JSON line each: by routed layer the share of tokens whose chosen
experts differ; the chosen-logit gaps and the rms logit difference of
the last 512 positions, for the tokens that differ in no layer and for
the rest; the same gaps against the reference with every matrix rounded
to int8 with a scale a column (the nearest precision below bf16 that
the program stores weights in). `drivers/serve_latent_experts.py`'s
limits sit between the two readings. Not part of a benchmark run:
`run.py` never reads this file."""

from __future__ import annotations

import argparse
import json
import sys
from functools import partial
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(HERE), str(HERE.parent)]
LAST = 512


def stats(v) -> dict:
    if not v.size:
        return {"n": 0}
    return {"n": int(v.size), "mean": float(v.mean()),
            "p90": float(np.quantile(v, 0.9)), "max": float(v.max()),
            "nonzero": float((v > 0).mean()),
            "over_0.15": float((v > 0.15).mean())}


def main() -> int:
    import jax
    import jax.numpy as jnp

    from harness import arith_latent_experts as arith
    from harness import model_latent_experts as model
    from harness import reference_latent_experts as ref
    from shallowspeed_tpu.models import transformer as T
    from shallowspeed_tpu.ops.latent_attention import latent_attention

    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=str(
        HERE / "configs" / "moonlight-16b-a3b.json"))
    ap.add_argument("--tokens", type=int, default=2048)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    c = model.load_config(args.config)
    cfg = model.transformer_config(c, "serving")
    s = arith.Shapes.from_config(c)
    theta, scale = float(c["rope_theta"]), float(c["routed_scaling_factor"])
    last = min(LAST, args.tokens)
    tokens = np.random.default_rng(args.seed).integers(
        0, s.vocab, args.tokens).astype(np.int32)
    pos = jnp.arange(args.tokens)

    @partial(jax.jit, static_argnames=("cfg",))
    def program_block(blk, x, cfg):
        """`T._block` for a latent block, giving the chosen experts too."""
        t = x.shape[1]
        h = T._norm(blk["ln1"], x, cfg)
        qn, qr, lat, kr = T.latent_qkv(
            blk, h, cfg, lambda u: T.rope_rotate(u, pos, cfg.rope_theta))
        causal = jnp.tril(jnp.ones((t, t), bool))[None, None]
        a = latent_attention(qn, qr, lat, kr, blk["kv_b"], causal,
                             T.latent_scale(cfg))
        x = x + T._dense(blk["proj"], a.reshape(1, t, -1))
        h2 = T._norm(blk["ln2"], x, cfg)
        if "experts" not in blk:
            return T._ffn(blk, x, cfg, h2)[0], None
        y, idx = T.routed_ffn(blk, h2, cfg)
        return x + y, idx[0]

    @jax.jit
    def reference_choice(blk, x):
        f32 = lambda a: a.astype(jnp.float32)
        h = ref._rmsnorm(ref._attention(blk, x, s, theta), f32(blk["ln2"]["g"]))
        score = jax.nn.sigmoid(h @ f32(blk["experts"]["router"])) \
            + f32(blk["experts"]["route_bias"])
        return jax.lax.top_k(score, s.experts_per_token)[1]

    def reference_pass(p):
        layer, final_norm = ref._jitted(s, theta, scale)
        with jax.default_matmul_precision("highest"):
            x = p["tok_emb"][jnp.asarray(tokens)].astype(jnp.float32)
            chosen = []
            for blk in p["blocks"]:
                if "experts" in blk:
                    chosen.append(np.sort(np.asarray(reference_choice(blk, x))))
                x = layer(blk, x)
            hid = final_norm(x, p["ln_f"]["g"].astype(jnp.float32))
            return np.asarray(ref.head_logits(p, hid[-last:])), chosen

    def weights():
        return T.cast_params(model.init_weights_on_device(cfg, args.seed),
                             cfg.compute_dtype)

    params = weights()
    x = params["tok_emb"][jnp.asarray(tokens)][None]
    program_chosen = []
    for blk in params["blocks"]:
        x, idx = program_block(blk, x, cfg)
        if idx is not None:
            program_chosen.append(np.sort(np.asarray(idx)))
    logits_p = np.asarray(T.head_logits(
        params, T._norm(params["ln_f"], x, cfg)[0, -last:], cfg
    ).astype(jnp.float32))
    del x
    logits_r, reference_chosen = reference_pass(params)

    differ = np.stack([(a != b).any(-1) for a, b
                       in zip(program_chosen, reference_chosen)])   # (L, T)
    some = differ[:, -last:].any(0)
    picked = logits_p.argmax(-1)
    rows = np.arange(last)
    gap = logits_r.max(-1) - logits_r[rows, picked]
    rms = np.sqrt(((logits_p - logits_r) ** 2).mean(-1))
    print(json.dumps({
        "device": jax.devices()[0].device_kind, "tokens": args.tokens,
        "differ_by_routed_layer": differ.mean(1).round(4).tolist(),
        "differ_in_some_layer": float(differ.any(0).mean()),
        "same_experts": {"gap": stats(gap[~some]), "rms": stats(rms[~some])},
        "other_experts": {"gap": stats(gap[some]), "rms": stats(rms[some])},
        "all": {"gap": stats(gap)}}), flush=True)

    def to_int8_and_back(w):
        if w.ndim < 2 or not jnp.issubdtype(w.dtype, jnp.floating):
            return w
        f = w.astype(jnp.float32)
        step = jnp.maximum(jnp.abs(f).max(-2, keepdims=True), 1e-8) / 127.0
        return (jnp.round(f / step) * step).astype(w.dtype)

    # in place, leaf by leaf: a second copy of the weights does not fit
    params = jax.tree_util.tree_map(
        jax.jit(to_int8_and_back, donate_argnums=0), params)
    logits_8, _ = reference_pass(params)
    print(json.dumps({"reference_weights": "int8", "gap": stats(
        logits_8.max(-1) - logits_8[rows, picked])}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
