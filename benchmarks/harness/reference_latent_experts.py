"""The plain reference for a decoder with latent attention (MLA) and
sigmoid-routed experts with shared experts (the `deepseek_v3` layer):
`jax.numpy`, float32 with `jax.default_matmul_precision("highest")`, no
cache, no batching, no kernels. It follows the published equations:

- attention: h = RMSNorm(x); q = h W_q as heads of [q_nope | q_rope];
  [c_raw | k_rope_raw] = h W_kva; c = RMSNorm(c_raw); rotary on q_rope
  per head and on the one k_rope all heads share; [k_nope | v]_head =
  c W_kvb (the EXPANDED form, never the absorbed one);
  score = (q_nope . k_nope + q_rope . k_rope) / sqrt(dn + dr), causal;
- dense layers: x + W_down(silu(W_gate h2) * W_up h2);
- routed layers: s = sigmoid(h2 W_g); the K largest of s + b chosen;
  w = s of the chosen (no bias), w / (sum w + 1e-20) * scale;
  y = sum_i w_i E_i(h2) + S(h2); every expert visited in a plain loop
  over all tokens and masked by its weights, no token dropped.

Routing near-ties. Which experts a token takes is a discontinuous
function of its hidden state: where the K-th and the (K+1)-th of
score + bias are nearly equal, a bf16 hidden state (the router itself is
float32 in the program too) chooses the other one, swaps one of K
expert outputs for another's, and from that layer on the token follows
another path than this reference's. Measured on the chip at the
published widths (PR 28, `tools/routing_near_ties.py`, two seeds): 4-5%
of tokens differ in their experts in the first routed layer, 43-47% in
the seventh, 47-55% in some layer; the tokens that differ in none are
within 0.026 rms of these logits (as a dense model is), the others
within 0.36. No
precision of the program avoids it, so a comparison against this
reference judges the share of positions that disagree, not the worst
one (`drivers/serve_latent_experts.py`).

It shares only the weight layout with the program (W_q head-major
[nope | rope], W_kva [c | k_rope], W_kvb (rank, head, [nope | v]), rotary
on halves) and imports nothing of `ops/moe.py` or the program's
attention. Weights are upcast a layer at a time (an expert at a time
inside the loop) and queries go in blocks of 512, so that 6,144 tokens
fit beside 9.7 GB of bf16 weights once the engine's pools are freed."""

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


def _attention(p, x, s, theta):
    import jax
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)
    t = x.shape[0]
    dn, dr, r = s.q_nope, s.q_rope, s.kv_rank
    h = _rmsnorm(x, f32(p["ln1"]["g"]))
    q = (h @ f32(p["q"]["W"])).reshape(t, s.heads, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], theta)
    kva = h @ f32(p["kv_a"]["W"])
    c = _rmsnorm(kva[:, :r], f32(p["kv_norm"]["g"]))
    k_rope = _rope(kva[:, None, r:], theta)[:, 0]               # (T, dr)
    kv = jnp.einsum("tr,rhx->thx", c, f32(p["kv_b"]))
    k_nope, v = kv[..., :dn], kv[..., dn:]
    # queries in blocks of Q_BLOCK, one block's scores alive at a time
    block = min(t, Q_BLOCK)
    assert t % block == 0, "pad the sequence to whole blocks of queries"
    keys = jnp.arange(t)

    def one_block(args):
        qn, qr, rows = args
        sc = (jnp.einsum("qhd,khd->hqk", qn, k_nope)
              + jnp.einsum("qhd,kd->hqk", qr, k_rope)) * (dn + dr) ** -0.5
        sc = jnp.where(keys[None, None, :] <= rows[None, :, None], sc,
                       -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)

    o = jax.lax.map(one_block, (
        q_nope.reshape(-1, block, s.heads, dn),
        q_rope.reshape(-1, block, s.heads, dr), keys.reshape(-1, block)))
    o = o.reshape(t, s.heads * s.v_head)
    return x + o @ f32(p["proj"]["W"])


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


def _layer(p, x, *, shapes, theta, scale):
    """One block on x (T, hidden), everything in float32."""
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)
    x = _attention(p, x, shapes, theta)
    h = _rmsnorm(x, f32(p["ln2"]["g"]))
    if "experts" in p:
        return x + _routed(p, h, shapes, scale)
    return x + _swiglu(h, f32(p["gate"]["W"]), f32(p["up"]["W"]),
                       f32(p["down"]["W"]))


@lru_cache(maxsize=None)
def _jitted(shapes, theta: float, scale: float):
    import jax

    return jax.jit(partial(_layer, shapes=shapes, theta=theta, scale=scale)), \
        jax.jit(_rmsnorm)


def hidden_states(params, sequences, shapes, theta: float, scale: float):
    """Final-norm hidden states (T, hidden), float32, of each sequence of
    token ids; each layer's weights are brought up once and used for
    every sequence."""
    import jax
    import jax.numpy as jnp

    layer, final_norm = _jitted(shapes, float(theta), float(scale))
    with jax.default_matmul_precision("highest"):
        emb = jnp.asarray(params["tok_emb"])
        xs = [emb[jnp.asarray(s)].astype(jnp.float32) for s in sequences]
        for blk in params["blocks"]:
            blk = jax.device_put(blk)
            xs = [layer(blk, x) for x in xs]
        g = jnp.asarray(params["ln_f"]["g"]).astype(jnp.float32)
        return [final_norm(x, g) for x in xs]


def head_logits(params, hidden):
    """Vocabulary logits (rows, vocab), float32, of `hidden` rows."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        return hidden @ jnp.asarray(params["head"]["W"]).astype(jnp.float32)


def chosen_logit_gaps(params, prompt, generated, shapes, theta, scale,
                      last: int = 32, length: int = 0) -> np.ndarray:
    """Teacher-force one finished request: for each of its last `last`
    generated positions, the reference's largest logit minus the
    reference logit of the token the engine chose (0 where they agree).
    The sequence is padded to whole blocks of queries, or to `length`
    (the traffic's longest request: one shape for every request, and one
    compile of some 60 s on the chip instead of one for each length;
    attention is causal, so what follows a position changes nothing)."""
    seq = np.concatenate([np.asarray(prompt), np.asarray(generated)])
    last = min(last, len(generated))
    # the token at index i was chosen from the logits at position i - 1
    idx = np.arange(len(seq) - last, len(seq))
    pad = max(length - len(seq), -len(seq) % Q_BLOCK)
    tokens = np.concatenate([seq[:-1], np.zeros(pad + 1, seq.dtype)])
    hid, = hidden_states(params, [tokens], shapes, theta, scale)
    logits = np.asarray(head_logits(params, hid[idx - 1]))
    return logits.max(-1) - logits[np.arange(last), seq[idx]]
