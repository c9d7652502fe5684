"""Where the limits of `drivers/serve_window_experts.py`'s comparison
come from, measured once on the chip: what the program's chosen tokens
read against the float32 reference, and what the same tokens read
against the reference with every matrix rounded to int8 (the nearest
precision below bf16 that the program stores weights in), which has to
come out as not correct.

    python3 benchmarks/tools/window_experts_limits.py --tokens 4096 --seed 7

One sequence of random ids through the program's forward pass without a
cache (`models/transformer.py:forward`, in the configuration's serving
precision); the tokens it would choose at the last LAST positions are
held against `harness/reference_window_experts.py` twice. Printed, one
JSON line each: the share of gaps over 0.15 and the mean gap for the
reference as it is and for the rounded one. The serving path's own
readings (the cache, the kernel, 12 k contexts) are on the `notes` line
of every benchmark run; this gives the other side. Not part of a
benchmark run: `run.py` never reads this file."""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(HERE), str(HERE.parent)]
LAST = 512


def main() -> int:
    import jax
    import jax.numpy as jnp

    from harness import arith_window_experts as arith
    from harness import model_window_experts as model
    from harness import reference_window_experts as ref
    from shallowspeed_tpu.models import transformer as T

    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default=str(HERE / "configs" / "trinity-mini.json"))
    ap.add_argument("--tokens", type=int, default=4096)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    c = model.load_config(args.config)
    cfg = model.transformer_config(c, "serving")
    shapes = arith.Shapes.from_config(c)
    params = model.init_weights_on_device(cfg, args.seed)
    seq = np.random.default_rng(args.seed).integers(
        0, shapes.vocab, args.tokens, dtype=np.int32)

    @jax.jit
    def chosen(params, tokens):
        hid = T.forward_with_aux(params, tokens[None], cfg, head=False)[0][0]
        return T.head_logits(T.cast_params(params, cfg.compute_dtype),
                             hid[-LAST:], cfg).argmax(-1)

    picks = np.asarray(chosen(params, jnp.asarray(seq)))

    def gaps(p):
        hid, = ref.hidden_states(
            p, [seq], shapes, model.layer_pattern(c), float(c["rope_theta"]),
            float(c["route_scale"]), cfg.embed_scale)
        logits = np.asarray(ref.head_logits(p, hid[-LAST:]))
        return logits.max(-1) - logits[np.arange(LAST), picks]

    def to_int8_and_back(w):
        if w.ndim < 2 or w.dtype == jnp.float32:     # norms, the routing bias
            return w
        f = w.astype(jnp.float32)
        scale = jnp.max(jnp.abs(f), axis=-2, keepdims=True) / 127.0
        return (jnp.round(f / scale) * scale).astype(w.dtype)

    def report(name, g):
        print(json.dumps({"against": name, "tokens": args.tokens,
                          "seed": args.seed, "n": int(g.size),
                          "share_over_0.15": float((g > 0.15).mean()),
                          "mean_gap": float(g.mean()),
                          "max_gap": float(g.max())}), flush=True)

    report("reference", gaps(params))
    # rounded leaf by leaf into the buffers it came from: two copies of
    # the weights do not fit the chip
    leaves, tree = jax.tree_util.tree_flatten(params)
    del params
    rounded = jax.jit(to_int8_and_back, donate_argnums=0)
    for i in range(len(leaves)):
        leaves[i] = rounded(leaves[i])
    report("reference_int8", gaps(jax.tree_util.tree_unflatten(tree, leaves)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
