"""The plain reference for a decoder with window and full attention
layers mixed, gated QK-normed attention under four norms and
sigmoid-routed experts with a shared expert (the `afmoe` layer):
`jax.numpy`, float32 with `jax.default_matmul_precision("highest")`, no
cache, no batching, no kernels. It follows the equations of ISSUE 33:

- embedding: x = E[tok] * sqrt(hidden) where `mup` is set;
- attention, h = RMSNorm(x; w_in): q = h W_q as heads, [k | v] = h W_kv as
  K/V heads, g = h W_g; q and k RMS-normed over each head's dimensions
  (one learned scale vector each); in a sliding layer q and k rotated
  (half split), in a full layer not at all; scores q k^T / sqrt(head),
  causal, and in a sliding layer key j visible to query i iff
  i - window < j <= i; each K/V head shared by heads / kv_heads query
  heads; a = softmax(.) v * sigmoid(g); x <- x + RMSNorm(a W_o; w_post);
- FFN, h = RMSNorm(x; w_pre): x <- x + RMSNorm(y; w_post), y a SwiGLU in
  the leading dense layers, else s = sigmoid(h W_r); the K largest of
  s + b chosen; w = s of the chosen (no bias), w / (sum w + 1e-20) *
  scale; y = sum_i w_i E_i(h) + S(h), every expert visited in a plain
  loop over all tokens and masked by its weights, no token dropped.

Routing near-ties are as `reference_latent_experts.py` says: which
experts a token takes is discontinuous in its hidden state, so the
comparison judges the share of positions that disagree and their mean,
not the worst one (`drivers/serve_window_experts.py`).

It shares only the weight layout with the program (W_q head-major, W_kv
(kv head, [k | v], head_dim), rotary on halves) and imports nothing of
`ops/moe.py` or the program's attention. Weights are upcast a layer at
a time (an expert at a time inside the loop) and queries go in blocks
of 512, so that 12,288 tokens x 128 experts fit beside 8.5 GB of bf16
weights once the engine's pools are freed."""

from __future__ import annotations

from functools import lru_cache, partial

import numpy as np

Q_BLOCK = 512
EPS = 1e-5


def _rmsnorm(x, g):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * g


def _rope(x, theta):
    """x (T, heads, D): rotate dimension i with i + D/2."""
    import jax.numpy as jnp

    t, _, d = x.shape
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(p, x, s, theta, window, rotary):
    import jax
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)
    t, hd = x.shape[0], s.head_dim
    group = s.heads // s.kv_heads
    h = _rmsnorm(x, f32(p["ln1"]["g"]))
    q = (h @ f32(p["q"]["W"])).reshape(t, s.heads, hd)
    kv = (h @ f32(p["kv"]["W"])).reshape(t, s.kv_heads, 2, hd)
    k, v = kv[:, :, 0], kv[:, :, 1]
    gate = jax.nn.sigmoid(h @ f32(p["attn_gate"]["W"]))
    q = _rmsnorm(q, f32(p["q_norm"]["g"]))
    k = _rmsnorm(k, f32(p["k_norm"]["g"]))
    if rotary:
        q, k = _rope(q, theta), _rope(k, theta)
    q = q.reshape(t, s.kv_heads, group, hd)
    # queries in blocks of Q_BLOCK, one block's scores alive at a time
    block = min(t, Q_BLOCK)
    assert t % block == 0, "pad the sequence to whole blocks of queries"
    keys = jnp.arange(t)

    def one_block(args):
        qb, rows = args
        sc = jnp.einsum("qhgd,khd->hgqk", qb, k) * hd ** -0.5
        seen = keys[None, :] <= rows[:, None]
        if window:
            seen &= keys[None, :] > rows[:, None] - window
        sc = jnp.where(seen[None, None], sc, -jnp.inf)
        return jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(sc, -1), v)

    o = jax.lax.map(one_block, (q.reshape(-1, block, s.kv_heads, group, hd),
                                keys.reshape(-1, block)))
    o = o.reshape(t, s.heads * hd) * gate
    return x + _rmsnorm(o @ f32(p["proj"]["W"]), f32(p["ln1_post"]["g"]))


def _swiglu(h, gate, up, down):
    import jax

    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def _routed(p, h, s, scale):
    import jax
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)
    ex = p["experts"]
    score = jax.nn.sigmoid(h @ f32(ex["router"]))                # (T, E)
    _, chosen = jax.lax.top_k(score + f32(ex["route_bias"]),
                              s.experts_per_token)
    picked = jnp.take_along_axis(score, chosen, -1)
    w = picked / (picked.sum(-1, keepdims=True) + 1e-20) * scale
    # (T, E): a token's weight on each expert, 0 where it was not chosen
    mix = jnp.zeros_like(score).at[
        jnp.arange(h.shape[0])[:, None], chosen].set(w)

    def one_expert(y, e):
        gate, up, down, w_e = e
        return y + w_e[:, None] * _swiglu(h, f32(gate), f32(up), f32(down)), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                        (ex["gate"], ex["up"], ex["down"], mix.T))
    sh = p["shared"]
    return y + _swiglu(h, f32(sh["gate"]["W"]), f32(sh["up"]["W"]),
                       f32(sh["down"]["W"]))


def _layer(p, x, *, shapes, theta, scale, window, rotary):
    """One block on x (T, hidden), everything in float32."""
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)
    x = _attention(p, x, shapes, theta, window, rotary)
    h = _rmsnorm(x, f32(p["ln2"]["g"]))
    if "experts" in p:
        y = _routed(p, h, shapes, scale)
    else:
        y = _swiglu(h, f32(p["gate"]["W"]), f32(p["up"]["W"]),
                    f32(p["down"]["W"]))
    return x + _rmsnorm(y, f32(p["ln2_post"]["g"]))


@lru_cache(maxsize=None)
def _jitted(shapes, theta: float, scale: float, window: int, rotary: bool):
    import jax

    return jax.jit(partial(_layer, shapes=shapes, theta=theta, scale=scale,
                           window=window, rotary=rotary))


def hidden_states(params, sequences, shapes, pattern, theta: float,
                  scale: float, embed_scale: float):
    """Final-norm hidden states (T, hidden), float32, of each sequence of
    token ids; `pattern` is (window, rotary) a layer; each layer's
    weights are brought up once and used for every sequence."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        emb = jnp.asarray(params["tok_emb"])
        xs = [emb[jnp.asarray(s)].astype(jnp.float32) * embed_scale
              for s in sequences]
        for blk, (window, rotary) in zip(params["blocks"], pattern,
                                         strict=True):
            layer = _jitted(shapes, float(theta), float(scale), int(window),
                            bool(rotary))
            blk = jax.device_put(blk)
            xs = [layer(blk, x) for x in xs]
        g = jnp.asarray(params["ln_f"]["g"]).astype(jnp.float32)
        return [jax.jit(_rmsnorm)(x, g) for x in xs]


def head_logits(params, hidden):
    """Vocabulary logits (rows, vocab), float32, of `hidden` rows."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        return hidden @ jnp.asarray(params["head"]["W"]).astype(jnp.float32)


def chosen_logit_gaps(params, prompt, generated, shapes, pattern, theta,
                      scale, embed_scale, last: int = 32,
                      length: int = 0) -> np.ndarray:
    """Teacher-force one finished request: for each of its last `last`
    generated positions, the reference's largest logit minus the
    reference logit of the token the engine chose (0 where they agree).
    The sequence is padded to whole blocks of queries, or to `length`
    (the traffic's longest request: one shape for every request, one
    compile; attention is causal, so what follows a position changes
    nothing)."""
    seq = np.concatenate([np.asarray(prompt), np.asarray(generated)])
    last = min(last, len(generated))
    # the token at index i was chosen from the logits at position i - 1
    idx = np.arange(len(seq) - last, len(seq))
    pad = max(length - len(seq), -len(seq) % Q_BLOCK)
    tokens = np.concatenate([seq[:-1], np.zeros(pad + 1, seq.dtype)])
    hid, = hidden_states(params, [tokens], shapes, pattern, theta, scale,
                         embed_scale)
    logits = np.asarray(head_logits(params, hid[idx - 1]))
    return logits.max(-1) - logits[np.arange(last), seq[idx]]
