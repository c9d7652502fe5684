"""The plain reference for a decoder whose every block holds a
state-space mixer beside its attention heads (the `falcon_h1` layer):
`jax.numpy`, float32 with `jax.default_matmul_precision("highest")`, no
cache, no chunks, no kernels, the published multipliers applied on the
ACTIVATIONS where the published code applies them (`m`, by name, from
`harness/model_state_space.py:multipliers`). It follows the equations of
ISSUE 36; RMS is RMSNorm with a learned scale, eps 1e-5:

    x0 = E[tok] * embedding
    h  = RMS(x; g_in)
    x  = x + Attn(h * attention_in) * attention_out
           + Mixer(h * ssm_in) * ssm_out          (both read the ONE h)
    h2 = RMS(x; g_ff)
    x  = x + W_down(silu(W_gate h2 * mlp_gate) * (W_up h2)) * mlp_down
    logits = W_head RMS(x_L; g_f) * lm_head

    Attn:  q = W_q h; k = (W_k h) * key; v = W_v h; q, k rotated over all
           of a head's dimensions (half split); causal softmax(q k^T /
           sqrt(head_dim)) v, each K/V head shared by heads / kv_heads
           query heads; W_o
    Mixer: u = (W_in h) * [z ssm_z | x ssm_x | B ssm_b | C ssm_c | dt ssm_dt]
           xBC_t = silu(sum_j w_conv[j] xBC_{t-3+j} + b_conv), zeros
           before t = 0; x as heads, B and C a group, head i of group
           i // (heads / groups)
           D_t = softplus(dt_t + dt_bias)      A = -exp(A_log)
           S_t = exp(D_t A) S_{t-1} + D_t x_t B_t^T     (S_-1 = 0)
           y_t = S_t C_t + Dskip x_t
           y = y * silu(z); y = y / sqrt(mean over each group's part of
           y^2 + 1e-5) * g_norm; W_out

THE RECURRENCE IS COMPUTED AS WRITTEN, one token at a time under
`lax.scan`: independent of the blocked form the program uses
(`shallowspeed_tpu/ops/ssm.py`), of which it imports nothing.

The weights it is handed may be the program's, with each multiplier
already inside the matrix it follows (`folded=True`): it then takes
them out again first (`unfolded`: this file's own statement of where
each one went, in float32), so that what it multiplies activations by
is what the program folded and a multiplier folded into the wrong
matrix fails the comparison. Weights are upcast a layer at a time and
queries go in blocks of 512, so that 5 layers at 5 k tokens fit beside
9.65 GB of bf16 weights once the engine's pools are freed; the head's
logits are taken a slice of the vocabulary at a time.
"""

from __future__ import annotations

from functools import lru_cache, partial

import numpy as np

Q_BLOCK = 512
VOCAB_SLICES = 8
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


def _in_proj_columns(s, m):
    import jax.numpy as jnp

    gn = s.ssm_groups * s.ssm_state
    parts = ((m["ssm_z"], s.d_ssm), (m["ssm_x"], s.d_ssm), (m["ssm_b"], gn),
             (m["ssm_c"], gn), (m["ssm_dt"], s.ssm_heads))
    return jnp.concatenate([jnp.full((n,), v, jnp.float32) for v, n in parts])


def unfolded(p, s, m):
    """A block's matrices as the published checkpoint would hold them,
    float32, from the matrices the program serves: `attention_in` out of
    W_q and W_kv, `key` out of W_kv's key columns ((kv head, [k | v],
    head_dim)), `attention_out` out of W_o, `ssm_in` and the column
    multipliers out of W_in, `ssm_out` out of W_out, the MLP's two out
    of W_gate and W_down."""
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)
    key_cols = jnp.tile(jnp.concatenate([
        jnp.full((s.head_dim,), m["key"], jnp.float32),
        jnp.ones((s.head_dim,), jnp.float32)]), s.kv_heads)
    mix = p["mixer"]
    return {**p,
            "q": {"W": f32(p["q"]["W"]) / m["attention_in"]},
            "kv": {"W": f32(p["kv"]["W"]) / (m["attention_in"] * key_cols)},
            "proj": {"W": f32(p["proj"]["W"]) / m["attention_out"]},
            "gate": {"W": f32(p["gate"]["W"]) / m["mlp_gate"]},
            "down": {"W": f32(p["down"]["W"]) / m["mlp_down"]},
            "mixer": {**mix,
                      "in_proj": {"W": f32(mix["in_proj"]["W"])
                                  / (m["ssm_in"] * _in_proj_columns(s, m))},
                      "out_proj": {"W": f32(mix["out_proj"]["W"])
                                   / m["ssm_out"]}}}


def _attention(p, h, s, m, theta):
    import jax
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)
    t, hd = h.shape[0], s.head_dim
    group = s.heads // s.kv_heads
    h = h * m["attention_in"]
    q = (h @ f32(p["q"]["W"])).reshape(t, s.heads, hd)
    kv = (h @ f32(p["kv"]["W"])).reshape(t, s.kv_heads, 2, hd)
    k, v = kv[:, :, 0] * m["key"], kv[:, :, 1]
    q, k = _rope(q, theta), _rope(k, theta)
    q = q.reshape(t, s.kv_heads, group, hd)
    # queries in blocks of Q_BLOCK, one block's scores alive at a time
    block = min(t, Q_BLOCK)
    assert t % block == 0, "pad the sequence to whole blocks of queries"
    keys = jnp.arange(t)

    def one_block(args):
        qb, rows = args
        sc = jnp.einsum("qhgd,khd->hgqk", qb, k) * hd ** -0.5
        sc = jnp.where((keys[None, :] <= rows[:, None])[None, None], sc,
                       -jnp.inf)
        return jnp.einsum("hgqk,khd->qhgd", jax.nn.softmax(sc, -1), v)

    o = jax.lax.map(one_block, (q.reshape(-1, block, s.kv_heads, group, hd),
                                keys.reshape(-1, block)))
    return (o.reshape(t, s.heads * hd) @ f32(p["proj"]["W"])) \
        * m["attention_out"]


def _mixer(p, h, s, m, at):
    """The mixer's output (T, hidden) and its state S (heads, head_dim,
    state) after the token at index `at`."""
    import jax
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)
    t = h.shape[0]
    heads, hd, n, g = s.ssm_heads, s.ssm_head_dim, s.ssm_state, s.ssm_groups
    u = ((h * m["ssm_in"]) @ f32(p["in_proj"]["W"])) * _in_proj_columns(s, m)
    z, xbc, dt = (u[:, :s.d_ssm], u[:, s.d_ssm:s.d_ssm + s.conv_dim],
                  u[:, s.d_ssm + s.conv_dim:])
    # depthwise, causal, zeros before the first token: tap j sees t - (K-1) + j
    w, taps = f32(p["conv_w"]), s.ssm_conv
    padded = jnp.concatenate([jnp.zeros((taps - 1, s.conv_dim)), xbc])
    xbc = jax.nn.silu(sum(w[j] * padded[j:j + t] for j in range(taps))
                      + f32(p["conv_b"]))
    x = xbc[:, :s.d_ssm].reshape(t, heads, hd)
    bm = xbc[:, s.d_ssm:s.d_ssm + g * n].reshape(t, g, n)
    cm = xbc[:, s.d_ssm + g * n:].reshape(t, g, n)
    of_head = jnp.arange(heads) // (heads // g)       # head i -> its group
    step = jax.nn.softplus(dt + f32(p["dt_bias"]))    # (T, heads), no clamp
    a = -jnp.exp(f32(p["A_log"]))

    def token(carry, xs):
        S, kept = carry
        x_t, b_t, c_t, d_t, i = xs
        S = jnp.exp(d_t * a)[:, None, None] * S \
            + (d_t[:, None] * x_t)[:, :, None] * b_t[of_head][:, None, :]
        kept = jnp.where(i == at, S, kept)
        return (S, kept), jnp.einsum("hpn,hn->hp", S, c_t[of_head])

    zeros = jnp.zeros((heads, hd, n), jnp.float32)
    (_, kept), y = jax.lax.scan(token, (zeros, zeros),
                                (x, bm, cm, step, jnp.arange(t)))
    y = y + f32(p["d_skip"])[:, None] * x
    y = y.reshape(t, s.d_ssm) * jax.nn.silu(z)        # the gate first
    parts = y.reshape(t, g, -1)
    parts = parts * jax.lax.rsqrt(jnp.mean(parts * parts, -1, keepdims=True)
                                  + EPS)
    y = parts.reshape(t, s.d_ssm) * f32(p["mixer_norm"]["g"])
    return (y @ f32(p["out_proj"]["W"])) * m["ssm_out"], kept


def _layer(p, x, at, *, shapes, m, theta, folded):
    """One block on x (T, hidden), everything in float32: the block's
    output and its mixer's state after the token at index `at`."""
    import jax
    import jax.numpy as jnp

    f32 = lambda a: a.astype(jnp.float32)
    m = dict(m)
    if folded:
        p = unfolded(p, shapes, m)
    h = _rmsnorm(x, f32(p["ln1"]["g"]))
    mixed, state = _mixer(p["mixer"], h, shapes, m, at)
    x = x + _attention(p, h, shapes, m, theta) + mixed
    h = _rmsnorm(x, f32(p["ln2"]["g"]))
    y = jax.nn.silu((h @ f32(p["gate"]["W"])) * m["mlp_gate"]) \
        * (h @ f32(p["up"]["W"]))
    return x + (y @ f32(p["down"]["W"])) * m["mlp_down"], state


@lru_cache(maxsize=None)
def _jitted(shapes, m: tuple, theta: float, folded: bool):
    import jax

    return jax.jit(partial(_layer, shapes=shapes, m=m, theta=theta,
                           folded=folded))


def hidden_states(params, sequences, shapes, m: dict, theta: float,
                  folded: bool = False, state_at=None):
    """Final-norm hidden states (T, hidden), float32, of each sequence of
    token ids, and each sequence's mixer states, a layer (heads,
    head_dim, state), after the token at index `state_at[i]` (default:
    its last); each layer's weights are brought up once and used for
    every sequence."""
    import jax
    import jax.numpy as jnp

    if state_at is None:
        state_at = [len(s) - 1 for s in sequences]
    with jax.default_matmul_precision("highest"):
        emb = jnp.asarray(params["tok_emb"])
        xs = [emb[jnp.asarray(s)].astype(jnp.float32) * m["embedding"]
              for s in sequences]
        layer = _jitted(shapes, tuple(sorted(m.items())), float(theta),
                        bool(folded))
        states = [[] for _ in sequences]
        for blk in params["blocks"]:
            blk = jax.device_put(blk)
            for i, at in enumerate(state_at):
                xs[i], state = layer(blk, xs[i], jnp.int32(at))
                states[i].append(state)
        g = jnp.asarray(params["ln_f"]["g"]).astype(jnp.float32)
        return [jax.jit(_rmsnorm)(x, g) for x in xs], states


def head_logits(params, hidden, m: dict, folded: bool = False):
    """Vocabulary logits (rows, vocab), float32, of `hidden` rows, a
    slice of the vocabulary at a time (the head is 2.7 GB in bf16)."""
    import jax
    import jax.numpy as jnp

    by = m["lm_head"]
    w = jnp.asarray(params["head"]["W"])
    n = -(-w.shape[1] // VOCAB_SLICES)

    # `hidden` is an argument, not a constant of the program: a constant
    # of tens of MB would ride in the executable and in its entry of the
    # persistent compile cache (`logit_stats`)
    @jax.jit
    def one(hidden, cols):
        cols = cols.astype(jnp.float32)
        return (hidden @ (cols / by if folded else cols)) * by

    with jax.default_matmul_precision("highest"):
        return jnp.concatenate([one(hidden, w[:, i:i + n])
                                for i in range(0, w.shape[1], n)], -1)


def logit_stats(params, hidden, chosen, m: dict, folded: bool = False):
    """(largest logit, logit of `chosen`, standard deviation) of each of
    `hidden`'s rows, float32, reduced on the device a slice of the
    vocabulary at a time: thousands of rows of 261,120 logits are never
    held at once, nor brought to the host. `hidden` and `chosen` are
    ARGUMENTS of the jitted slice: closed over, they were constants of
    its program, 60-80 MB for a whole answer, a new key every run, and
    two such entries pushed every program of the cell out of a compile
    cache held to 192 MiB, so that every run compiled cold (PERF.md
    section 6, PR 36)."""
    import jax
    import jax.numpy as jnp

    by = m["lm_head"]
    w = jnp.asarray(params["head"]["W"])
    n = -(-w.shape[1] // VOCAB_SLICES)
    chosen = jnp.asarray(chosen, jnp.int32)

    @jax.jit
    def one(hidden, chosen, cols, first):
        cols = cols.astype(jnp.float32)
        logits = (hidden @ (cols / by if folded else cols)) * by
        at = chosen - first
        here = (at >= 0) & (at < cols.shape[1])
        picked = jnp.take_along_axis(
            logits, jnp.clip(at, 0, cols.shape[1] - 1)[:, None], 1)[:, 0]
        return (logits.max(-1), jnp.where(here, picked, 0.0),
                logits.sum(-1), (logits * logits).sum(-1))

    with jax.default_matmul_precision("highest"):
        parts = [one(hidden, chosen, w[:, i:i + n], i)
                 for i in range(0, w.shape[1], n)]
    top = jnp.max(jnp.stack([p[0] for p in parts]), 0)
    picked = sum(p[1] for p in parts)
    mean = sum(p[2] for p in parts) / w.shape[1]
    var = sum(p[3] for p in parts) / w.shape[1] - mean * mean
    return np.asarray(top), np.asarray(picked), np.sqrt(np.asarray(var))


def teacher_forced(params, prompt, generated, shapes, m, theta,
                   folded: bool = True, last: int = 32, length: int = 0):
    """Teacher-force one finished request through the reference. Two
    things come back.

    The gaps: for each of its last `last` generated positions, the
    reference's largest logit minus the reference logit of the token the
    engine chose (0 where they agree), OVER the standard deviation of
    the reference's logits at that position: this model's logits are
    small (the head's multiplier, and the paths' on N(0, 1/fan_in)
    weights), so a gap is judged by the spread it sits in.

    The states: each layer's S (heads, head_dim, state), float32, after
    the last token the request FED (prompt + generated less its last
    token, which was sampled and never fed): what the request's slot
    holds when it finishes.

    The sequence is padded to whole blocks of queries, or to `length`
    (the traffic's longest request: one shape, one compile; every layer
    is causal, so what follows a position changes nothing)."""
    seq = np.concatenate([np.asarray(prompt), np.asarray(generated)])
    last = min(last, len(generated))
    # the token at index i was chosen from the logits at position i - 1
    idx = np.arange(len(seq) - last, len(seq))
    pad = max(length - len(seq), -len(seq) % Q_BLOCK)
    tokens = np.concatenate([seq[:-1], np.zeros(pad + 1, seq.dtype)])
    (hid,), (states,) = hidden_states(params, [tokens], shapes, m, theta,
                                      folded, [len(seq) - 2])
    top, picked, std = logit_stats(params, hid[idx - 1], seq[idx], m, folded)
    return (top - picked) / std, states


def state_gaps(held, states) -> np.ndarray:
    """(layers, heads): how far the state a slot `held` (a layer (heads,
    head_dim, state)) lies from the reference's `states`, a head: the
    norm of the difference over the norm of the reference's."""
    import jax.numpy as jnp

    def one(a, b):
        a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
        return jnp.sqrt(jnp.sum((a - b) ** 2, (1, 2))
                        / jnp.sum(b * b, (1, 2)))

    return np.stack([np.asarray(one(a, b)) for a, b in zip(held, states)])
