"""The state-space mixer of a hybrid block (Mamba-2's SSD layer), in
plain `jax.numpy`: what lies between the mixer's input projection and
its output projection.

A token's projection `u` splits into a gate `z` (heads x head_dim), the
convolved part `xBC` (heads x head_dim inputs, then `groups` x `state`
values each of B and C) and one step size a head. `xBC` goes through a
causal depthwise convolution of width `conv` and a SiLU; then each head
keeps a (head_dim, state) matrix S that every token decays and adds to,

    S_t = exp(D_t A) S_{t-1} + D_t x_t B_t^T      y_t = S_t C_t + Dskip x_t

with D_t = softplus(dt_t + dt_bias) and A = -exp(A_log) a head; the
heads of a group share B and C. `y` is gated by silu(z) and RMS-normed
over each group's heads (`gated_norm`).

What a sequence leaves behind for its next token is FIXED in size: the
last `conv - 1` inputs of the convolution and S. `ssm_scan` takes a
chunk of tokens from such a state to the state after the chunk's last
TRUE row in the blocked form (within a block of `block` tokens the
lower-triangular decay mask over C B^T, across blocks the carried S);
`ssm_step` takes one token a row. Rows at or past `n_tok` get step size
0, which is decay 1 and no input: padding is exact, not approximately
masked. Decays, the cumulative sums and S are float32; the contractions
take operands in the inputs' dtype and accumulate in float32.

Sizes come from a config object's `ssm_*` fields
(`models/transformer.py:TransformerConfig`); nothing here reads weights
but the convolution's, which the caller hands over."""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
# tokens a block of `ssm_scan`: a tile size, which changes no result
# (the one published model of this kind states 128)
BLOCK = 128


def d_ssm(cfg) -> int:
    """Width of the mixer's inner stream: heads x head_dim."""
    return cfg.ssm_heads * cfg.ssm_head_dim


def conv_dim(cfg) -> int:
    """Width of what the convolution sees: x, then B and C a group."""
    return d_ssm(cfg) + 2 * cfg.ssm_groups * cfg.ssm_state


def proj_dim(cfg) -> int:
    """Columns of the input projection: z | xBC | dt."""
    return d_ssm(cfg) + conv_dim(cfg) + cfg.ssm_heads


def state_shapes(cfg, rows: int) -> dict:
    """{leaf: shape} of what `rows` sequences carry from token to token:
    `conv` the last conv - 1 inputs of the convolution (positions major,
    the channels on the lanes), `ssm` the heads' matrices (float32)."""
    return {"conv": (rows, cfg.ssm_conv - 1, conv_dim(cfg)),
            "ssm": (rows, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state)}


def zero_state(cfg, rows: int, dtype) -> dict:
    """What `rows` sequences carry before their first token: zeros, the
    convolution's inputs in `dtype`, the heads' matrices float32."""
    shapes = state_shapes(cfg, rows)
    return {"conv": jnp.zeros(shapes["conv"], dtype),
            "ssm": jnp.zeros(shapes["ssm"], F32)}


def split_projection(u, cfg):
    """u (..., proj_dim) -> z (..., d_ssm), xBC (..., conv_dim), dt (..., heads)."""
    a, b = d_ssm(cfg), d_ssm(cfg) + conv_dim(cfg)
    return u[..., :a], u[..., a:b], u[..., b:]


def split_conv(xbc, cfg):
    """xBC (B, T, conv_dim) -> x (B, T, H, P), B and C (B, T, G, N)."""
    lead, g, n = xbc.shape[:-1], cfg.ssm_groups, cfg.ssm_state
    a = d_ssm(cfg)
    return (xbc[..., :a].reshape(*lead, cfg.ssm_heads, cfg.ssm_head_dim),
            xbc[..., a:a + g * n].reshape(*lead, g, n),
            xbc[..., a + g * n:].reshape(*lead, g, n))


def causal_conv(w, bias, xbc, tail, n_tok=None):
    """Depthwise causal convolution and SiLU over a chunk.

    xbc (B, T, C) are the chunk's inputs, `tail` (B, K - 1, C) the K - 1
    inputs before its first (zeros before a sequence's first token), w
    (K, C) with w[K - 1] on the current token, bias (C,). Returns the
    activations (B, T, C) in xbc's dtype and the tail after the chunk's
    `n_tok` true rows (default: all T), in `tail`'s dtype."""
    k, t = w.shape[0], xbc.shape[1]
    full = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
    acc = bias.astype(F32)
    for j in range(k):
        acc = acc + w[j].astype(F32) * full[:, j:j + t].astype(F32)
    # chunk row i is full[i + K - 1]: the last K - 1 true rows start at
    # full[n_tok]
    new_tail = jax.lax.dynamic_slice_in_dim(
        full, t if n_tok is None else n_tok, k - 1, axis=1)
    return jax.nn.silu(acc).astype(xbc.dtype), new_tail.astype(tail.dtype)


def _by_group(v, groups: int, axis: int = 2):
    """The heads at `axis` as (G, H / G): head i is of group i // (H / G)."""
    return v.reshape(*v.shape[:axis], groups, v.shape[axis] // groups,
                     *v.shape[axis + 1:])


def ssm_scan(x, dt, a, b, c, d_skip, state, n_tok=None, block: int = BLOCK):
    """The recurrence over a chunk, in blocks.

    x (B, T, H, P); dt (B, T, H) float32 step sizes (after softplus);
    a (H,) float32, negative; b, c (B, T, G, N); d_skip (H,); state
    (B, H, P, N) float32, the chunk's starting S. Returns y (B, T, H, P)
    in x's dtype and S after row `n_tok` - 1 (default: the last)."""
    bsz, t, h, p = x.shape
    g = b.shape[2]
    if n_tok is not None:
        dt = jnp.where((jnp.arange(t) < n_tok)[None, :, None], dt, 0.0)
    blk = min(block, t)
    pad = -t % blk
    if pad:     # a short last block: rows of step size 0 change nothing
        grow = lambda v: jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
        x, dt, b, c = grow(x), grow(dt), grow(b), grow(c)
    nb = (t + pad) // blk
    # (blocks, B, L, ...): the scan runs over the leading axis
    cut = lambda v: jnp.moveaxis(v.reshape(bsz, nb, blk, *v.shape[2:]), 1, 0)
    lower = jnp.tril(jnp.ones((blk, blk), bool))
    cdt = x.dtype

    def one_block(s, xs):
        xb, dtb, bb, cb = xs
        cum = jnp.cumsum(dtb * a, axis=1)                       # (B, L, H)
        # within the block: token l takes from token s <= l what decays
        # from s to l, exp(cum_l - cum_s) dt_s (C_l . B_s) x_s
        cbt = jnp.einsum("blgn,bsgn->bgls", cb, bb,
                         preferred_element_type=F32)
        diff = cum[:, :, None, :] - cum[:, None, :, :]          # (B, L, S, H)
        decay = jnp.exp(jnp.where(lower[None, :, :, None], diff, -jnp.inf))
        m = _by_group(jnp.moveaxis(decay * dtb[:, None, :, :], 3, 1), g, 1) \
            * cbt[:, :, None]                                   # (B,G,H/G,L,S)
        y = jnp.einsum("bgkls,bsgkp->blgkp", m.astype(cdt), _by_group(xb, g),
                       preferred_element_type=F32)
        # from the carried state: exp(cum_l) S C_l
        y = y + jnp.einsum("blgn,bgkpn->blgkp", cb,
                           _by_group(s, g, 1).astype(cdt),
                           preferred_element_type=F32) \
            * _by_group(jnp.exp(cum), g)[..., None]
        # the state after the block: what is left of S, and each token's
        # input decayed from its own position to the block's end
        w = jnp.exp(cum[:, -1:, :] - cum) * dtb                 # (B, L, H)
        xw = (xb.astype(F32) * w[..., None]).astype(cdt)
        s = s * jnp.exp(cum[:, -1, :])[:, :, None, None] + jnp.einsum(
            "blgkp,blgn->bgkpn", _by_group(xw, g), bb,
            preferred_element_type=F32).reshape(s.shape)
        return s, y.reshape(bsz, blk, h, p)

    state, ys = jax.lax.scan(one_block, state.astype(F32),
                             (cut(x), cut(dt), cut(b), cut(c)))
    y = jnp.moveaxis(ys, 0, 1).reshape(bsz, t + pad, h, p)[:, :t]
    y = y + d_skip.astype(F32)[:, None] * x[:, :t].astype(F32)
    return y.astype(cdt), state


def ssm_step(x, dt, a, b, c, d_skip, state):
    """One token a row: x (S, H, P), dt (S, H) float32, b, c (S, G, N),
    state (S, H, P, N) float32. Returns y (S, H, P) in x's dtype and the
    new state, every product in float32 (elementwise: one pass over the
    state, which is all of the work)."""
    g = b.shape[1]
    per_head = lambda v: jnp.repeat(v.astype(F32), x.shape[1] // g, axis=1)
    xf = x.astype(F32)
    new = state * jnp.exp(dt * a)[..., None, None] \
        + (dt[..., None] * xf)[..., None] * per_head(b)[:, :, None, :]
    y = (new * per_head(c)[:, :, None, :]).sum(-1) \
        + d_skip.astype(F32)[:, None] * xf
    return y.astype(x.dtype), new


def gated_norm(scale, y, z, groups: int, eps: float = 1e-5):
    """y (..., d_ssm) times silu(z), then RMS-normed over each of
    `groups` equal parts of the width, times the learned `scale`
    (d_ssm,): the gate first, the norm after it. float32 inside, y's
    dtype out."""
    v = y.astype(F32) * jax.nn.silu(z.astype(F32))
    parts = v.reshape(*v.shape[:-1], groups, -1)
    parts = parts * jax.lax.rsqrt(
        jnp.mean(parts * parts, axis=-1, keepdims=True) + eps)
    return (parts.reshape(v.shape) * scale.astype(F32)).astype(y.dtype)
