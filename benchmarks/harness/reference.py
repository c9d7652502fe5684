"""The plain reference: a dense decoder forward pass in `jax.numpy`,
float32 with `jax.default_matmul_precision("highest")`, no cache, no
batching, no kernels. It follows the published equations (pre-norm
blocks, rotary embeddings on halves, SwiGLU, a LayerNorm without
parameters for `olmo`, RMSNorm with a scale for `mistral`) and shares
only the weight layout with the program: the fused qkv matrix is
head-major (head, q|k|v, head_dim), the fused kv matrix (head, k|v,
head_dim).

Weights are upcast one layer at a time and attention is taken in blocks
of queries, so that a sequence of thousands of tokens fits beside bf16
weights of 11 GB once the engine's pools are freed."""

from __future__ import annotations

from functools import lru_cache, partial

import numpy as np

Q_BLOCK = 512
EPS = 1e-5


def _norm(x, kind, g):
    import jax
    import jax.numpy as jnp

    if kind == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * g
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + EPS)     # no scale, no shift


def _rope(x, theta):
    import jax.numpy as jnp

    t, _, d = x.shape
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(p, x, *, heads, kv_heads, head_dim, norm, theta, window):
    """One block on x (T, hidden), everything in float32."""
    import jax
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)
    t = x.shape[0]
    h = _norm(x, norm, f32(p["ln1"]["g"]))
    if "qkv" in p:
        qkv = (h @ f32(p["qkv"]["W"])).reshape(t, heads, 3, head_dim)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    else:
        q = (h @ f32(p["q"]["W"])).reshape(t, heads, head_dim)
        kv = (h @ f32(p["kv"]["W"])).reshape(t, kv_heads, 2, head_dim)
        k, v = kv[:, :, 0], kv[:, :, 1]
    q, k = _rope(q, theta), _rope(k, theta)
    group = heads // kv_heads
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    keys = jnp.arange(t)
    outs = []
    for s in range(0, t, Q_BLOCK):
        qb = q[s:s + Q_BLOCK]
        rows = jnp.arange(s, s + qb.shape[0])
        sc = jnp.einsum("qhd,khd->hqk", qb, k) * head_dim ** -0.5
        ok = keys[None, :] <= rows[:, None]
        if window:
            ok = ok & (keys[None, :] > rows[:, None] - window)
        sc = jnp.where(ok[None], sc, -jnp.inf)
        outs.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v))
    a = jnp.concatenate(outs, 0).reshape(t, heads * head_dim)
    x = x + a @ f32(p["proj"]["W"])
    h = _norm(x, norm, f32(p["ln2"]["g"]))
    up = h @ f32(p["up"]["W"])
    if "gate" in p:
        up = jax.nn.silu(h @ f32(p["gate"]["W"])) * up
    else:
        up = jax.nn.gelu(up)
    return x + up @ f32(p["down"]["W"])


@lru_cache(maxsize=None)
def _jitted(shapes, norm: str, theta: float):
    """One jitted block and final norm per model, so that every sequence
    of one padded length compiles once."""
    import jax

    layer = jax.jit(partial(
        _layer, heads=shapes.heads, kv_heads=shapes.kv_heads,
        head_dim=shapes.head_dim, norm=norm, theta=theta,
        window=shapes.window))
    return layer, jax.jit(partial(_norm, kind=norm))


def hidden_states(params, sequences, shapes, program: dict, theta: float):
    """Final-norm hidden states (T, hidden), float32, of each sequence of
    token ids. `params` may live on the host or the device, in any float
    type; each layer's weights are brought up once and used for every
    sequence, which is the same arithmetic as one sequence at a time."""
    import jax
    import jax.numpy as jnp

    layer, final_norm = _jitted(shapes, program["norm"], theta)
    with jax.default_matmul_precision("highest"):
        emb = jnp.asarray(params["tok_emb"])
        xs = [emb[jnp.asarray(s)].astype(jnp.float32) for s in sequences]
        for blk in params["blocks"]:
            blk = jax.device_put(blk)
            xs = [layer(blk, x) for x in xs]
        g = jnp.asarray(params["ln_f"]["g"]).astype(jnp.float32)
        return [final_norm(x, g=g) for x in xs]


def head_logits(params, hidden):
    """Vocabulary logits (rows, vocab), float32, of `hidden` rows."""
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        if "head" in params:
            w = jnp.asarray(params["head"]["W"]).astype(jnp.float32)
            return hidden @ w
        return hidden @ jnp.asarray(params["tok_emb"]).astype(jnp.float32).T


def chosen_logit_gaps(params, prompt, generated, shapes, program, theta,
                      last: int = 32) -> np.ndarray:
    """Teacher-force one finished request: for each of its last `last`
    generated positions, the reference's largest logit minus the
    reference logit of the token the engine chose (0 where they agree)."""
    seq = np.concatenate([np.asarray(prompt), np.asarray(generated)])
    n_gen = len(generated)
    last = min(last, n_gen)
    # the token at index i was chosen from the logits at position i - 1
    idx = np.arange(len(seq) - last, len(seq))
    pad = -len(seq) % Q_BLOCK                   # few distinct shapes
    tokens = np.concatenate([seq[:-1], np.zeros(pad + 1, seq.dtype)])
    hid, = hidden_states(params, [tokens], shapes, program, theta)
    logits = np.asarray(head_logits(params, hid[idx - 1]))
    return logits.max(-1) - logits[np.arange(last), seq[idx]]


def batch_loss(params, tokens, targets, shapes, program, theta) -> float:
    """Mean next-token cross-entropy of a (rows, T) batch of equal-length
    rows."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def row_nll(logits, tgt):
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.take_along_axis(logp, tgt[:, None], -1).mean()

    params = dict(params, tok_emb=jnp.asarray(params["tok_emb"]))
    hids = hidden_states(params, list(np.asarray(tokens)), shapes, program,
                         theta)
    return sum(float(row_nll(head_logits(params, h), jnp.asarray(tgt)))
               for h, tgt in zip(hids, np.asarray(targets))) / len(hids)
