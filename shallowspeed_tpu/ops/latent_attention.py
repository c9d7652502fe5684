"""Multi-head latent attention (DeepSeek-V2's MLA) over latent rows.

A token leaves one row behind for all heads: `c` (r values, the joint
down-projection after its RMSNorm) and `k_rope` (dr rotary key values).
Head h's keys and values are an up-projection of the row,
`[k_nope_h | v_h] = c @ kv_b[:, h]`, and its score against a query is

    (q_nope_h . k_nope_h(s) + q_rope_h . k_rope(s)) * scale.

Two readings of the same numbers:

- **expanded**: up-project every row to per-head keys and values, then
  ordinary attention. The plain form; `models/transformer.py` runs it
  where there is no cache.
- **absorbed**: fold `kv_b`'s key half into the query
  (`q~_h = q_nope_h @ kv_b[:, h, :dn]^T`, r values) and its value half
  into the output (`o_h = (sum_s p c(s)) @ kv_b[:, h, dn:]`), so the
  rows are read as they are stored: all heads share one "key" of
  r + dr values and one "value" of r. The serving engine reads its
  paged latent pool this way (`serving/engine.py`); nothing per head is
  ever formed over the cache.

Scores and the softmax are float32; the probabilities meet the values in
the values' dtype, as in `kv_cache.masked_attention`."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _softmax(s, valid, scale, dtype):
    p = jax.nn.softmax(jnp.where(valid, s * scale, jnp.float32(-1e30)), -1)
    return p.astype(dtype)


def latent_attention(q_nope, q_rope, c, k_rope, kv_b, valid, scale: float):
    """The expanded form. q_nope (B,T,H,dn), q_rope (B,T,H,dr) rotated;
    c (B,S,r), k_rope (B,S,dr) the latent rows; kv_b (r, H, dn + dv);
    `valid` boolean, broadcastable to the (B,H,T,S) scores. Returns
    (B,T,H,dv) in the queries' dtype."""
    dn, f32 = q_nope.shape[-1], jnp.float32
    kv = jnp.einsum("bsr,rhx->bshx", c, kv_b,
                    preferred_element_type=f32).astype(c.dtype)
    s = jnp.einsum("bthn,bshn->bhts", q_nope, kv[..., :dn],
                   preferred_element_type=f32) \
        + jnp.einsum("bthd,bsd->bhts", q_rope, k_rope,
                     preferred_element_type=f32)
    o = jnp.einsum("bhts,bshv->bthv", _softmax(s, valid, scale, c.dtype),
                   kv[..., dn:], preferred_element_type=f32)
    return o.astype(q_nope.dtype)


def absorb_query(q_nope, q_rope, kv_b, width: int):
    """[q_nope @ kv_b's key half | q_rope | 0...] (..., H, width): the
    query that meets stored rows [c | k_rope | 0...] directly."""
    dn = q_nope.shape[-1]
    qa = jnp.einsum("...hn,rhn->...hr", q_nope, kv_b[..., :dn],
                    preferred_element_type=jnp.float32).astype(q_nope.dtype)
    qx = jnp.concatenate([qa, q_rope], -1)
    return jnp.pad(qx, ((0, 0),) * (qx.ndim - 1)
                   + ((0, width - qx.shape[-1]),))


def unabsorb_output(oc, kv_b, dn: int):
    """Probability-weighted rows (..., H, >= r) through kv_b's value
    half: (..., H, dv). The rows' tail past r (the rotary key, the
    padding) is dropped here: reading the rows whole costs a few more
    operations and no sliced copy of them."""
    r = kv_b.shape[0]
    return jnp.einsum("...hr,rhv->...hv", oc[..., :r], kv_b[..., dn:],
                      preferred_element_type=jnp.float32)


def latent_attention_absorbed(q_nope, q_rope, rows, kv_b, valid,
                              scale: float):
    """The absorbed form over stored rows (B,S,X) = [c | k_rope | 0...]
    (X >= r + dr: the cache rounds its rows up to whole lanes): one
    contraction over X for the scores, one over S for the values. Same
    arguments and result as `latent_attention` otherwise."""
    f32 = jnp.float32
    qx = absorb_query(q_nope, q_rope, kv_b, rows.shape[-1])
    s = jnp.einsum("bthx,bsx->bhts", qx, rows, preferred_element_type=f32)
    oc = jnp.einsum("bhts,bsx->bthx", _softmax(s, valid, scale, rows.dtype),
                    rows, preferred_element_type=f32)
    o = unabsorb_output(oc.astype(rows.dtype), kv_b, q_nope.shape[-1])
    return o.astype(q_nope.dtype)
