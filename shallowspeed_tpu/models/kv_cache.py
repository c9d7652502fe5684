"""KV-cache primitives shared by batch decode and the serving runtime.

Round 11 refactor: `models/generate.py` owned these ops privately; the
serving subsystem (`shallowspeed_tpu/serving/` — paged block pools read
through a gathered block table) needs the SAME write/quantize/attend
math so paged decode provably matches the contiguous cache. The ops
moved here unchanged; `generate.py` re-exports them under its old
names, so its numerics (and every pinned stream) are bit-identical.

Layout contract (round 5, head-major): contiguous caches are
(B, Hkv, slots, hd) per block; the serving pools are
(n_blocks, Hkv, block_size, hd) — the SAME innermost (positions, hd)
sweep per (batch/block, head), so the decode read stays one contiguous
DMA per head whether the slots come from one buffer or a gathered
table. int8 caches ride one f32 scale per (row, head, position); the
scales stay OUTSIDE the attention einsums (K's multiplies the score,
V's folds into the probability row) so HBM reads remain int8.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from shallowspeed_tpu.models import transformer as T

KV_QUANT_MODES = ("", "int8")


def init_kv_cache(cfg: T.TransformerConfig, batch: int,
                  cache_len: int | None = None, kv_quant: str = ""):
    """Per-block K/V buffers (B, Hkv, cache_len, head_dim), zero-filled —
    under GQA the cache holds the UNREPEATED kv heads, shrinking its
    memory by the query-group factor.

    HEAD-MAJOR layout (round 5): the decode sweep reads one head's
    whole history per (batch, head) — with the old (B, S, Hkv, hd)
    layout those reads were hd*2 = 128-byte rows at an Hkv*hd*2-byte
    stride (sub-DMA-granularity: the b8 8k MHA sweep measured 257 GB/s
    vs the 819 GB/s roofline); head-major makes each (b, h) sweep one
    contiguous (S, hd) block. The per-token write transposes a
    (B, 1, Hkv, hd) slice — noise next to the read it fixes.

    `cache_len` defaults to cfg.max_seq; `generate` passes the SIZED
    length (prompt bucket + max_new) instead — decode is HBM-bound on
    the cache sweep, so a max_seq-sized buffer on a short generation
    pays bandwidth for slots that can never be read (round-4 decode
    hygiene, VERDICT r3).

    `kv_quant="int8"` (round 5 — the batched-long-context lever the
    round-4 roofline named): K/V store as int8 with one f32 scale per
    (batch, position, head); the cache sweep's bytes halve vs bf16.
    The scales ride OUTSIDE the attention einsums (K's scale multiplies
    the score, V's folds into the probability row), so HBM reads stay
    int8 — see `cached_attention`."""
    if kv_quant not in KV_QUANT_MODES:
        # a typed error, not an assert: asserts vanish under python -O,
        # and an unknown mode must fail loudly in production too
        raise ValueError(
            f"unsupported kv_quant={kv_quant!r}; expected one of "
            f"{KV_QUANT_MODES} ('' = cache in the compute dtype)")
    dt = cfg.compute_dtype or cfg.dtype
    shape = (batch, cfg.kv_heads, cache_len or cfg.max_seq, cfg.head_dim)
    # a block with a state-space mixer also carries the mixer's state,
    # of a fixed size whatever `cache_len` is
    state = mixer_state(cfg, batch) if cfg.mixer else {}
    if kv_quant:
        sshape = shape[:3] + (1,)
        return [{"k": jnp.zeros(shape, jnp.int8),
                 "k_s": jnp.zeros(sshape, jnp.float32),
                 "v": jnp.zeros(shape, jnp.int8),
                 "v_s": jnp.zeros(sshape, jnp.float32), **state}
                for _ in range(cfg.n_layers)]
    return [{"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt), **state}
            for _ in range(cfg.n_layers)]


STATE_LEAVES = ("conv", "ssm")


def mixer_state(cfg: T.TransformerConfig, rows: int) -> dict:
    """The zero state of `rows` sequences in one mixer layer: `conv` the
    convolution's last inputs in the compute dtype, `ssm` the heads'
    matrices in float32 (`ops/ssm.py:zero_state`)."""
    from shallowspeed_tpu.ops.ssm import zero_state

    return zero_state(cfg, rows, cfg.compute_dtype or cfg.dtype)


def quantize_kv(x):
    """(values int8, scales f32): symmetric per-(b, head, t) absmax
    quantization over the head_dim axis (x: (B, Hkv, T, hd))."""
    xf = x.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xf), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def cache_write(cache_blk, k, v, pos):
    """Write this slice's K/V at `pos` (k/v arrive token-major
    (B, T, Hkv, hd) from the block; the cache is head-major),
    quantizing when the cache is int8 (the scale leaves' presence is
    the dispatch)."""
    k = jnp.swapaxes(k, 1, 2)
    v = jnp.swapaxes(v, 1, 2)
    if "k_s" in cache_blk:
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        upd = {"k": kq, "k_s": ks, "v": vq, "v_s": vs}
    else:
        upd = {"k": k.astype(cache_blk["k"].dtype),
               "v": v.astype(cache_blk["v"].dtype)}
    return {
        **cache_blk,
        **{name: jax.lax.dynamic_update_slice_in_dim(
            cache_blk[name], val, pos, axis=2)
           for name, val in upd.items()},
    }


def masked_attention(q, cache_blk, valid, cfg):
    """The cache-attention core: q (B, Tq, H, hd) attends over a K/V
    view under an explicit validity mask. The view is the contiguous
    head-major cache (B, Hkv, S, hd), or the serving runtime's PAGED
    view (B, W, Hkv, P, hd) — `serving/cache.gather_table`: W pages of
    P positions, S = W*P, position w*P + p. `valid` is a boolean
    broadcastable against the (B, Hkv, G, Tq, S) score tensor —
    contiguous decode passes the position prefix (`cached_attention`),
    the serving runtime per-row masks over its gathered block table.
    One contraction serves both (the contiguous cache is one page), so
    paged and contiguous logits can only differ by gather/fp-reorder
    noise (pinned to 1e-4 in tests/test_serving.py).

    A single query per row contracts over the pages as gathered
    (making the table head-major first would copy all of it for one
    use): the form the serving tick ran until PR 29 and the reference
    of the kernel that took its place
    (`ops.flash_attention.paged_flash_decode`). A chunk of queries
    (prefill) folds its row's pages into one head-major page first:
    the serving chunk's read of an int8 pool, and the reference of the
    kernel that reads the others (`paged_flash_prefill`). The scores
    are (B, Hkv, G, Tq, S) float32 in HBM, whatever part of S is live.

    GQA caches hold Hkv heads and are read UNREPEATED (grouped einsum):
    decode is HBM-bandwidth-bound on the cache sweep, so the group
    factor shrinks the per-step traffic, not just the cache footprint.
    Scores accumulate in f32; int8 caches keep their scales outside the
    einsums (K's on the score, V's folded into the probability row) so
    the HBM reads stay int8.
    """
    b, tq, h, hd = q.shape
    if cache_blk["k"].ndim == 4:                # contiguous: one page
        cache_blk = {n: l[:, None] for n, l in cache_blk.items()}
    elif tq > 1:
        # a chunk of queries amortises a head-major copy of its row's
        # table (and the P.V matmul then needs no relayout of the
        # probabilities); a single query per row does not
        cache_blk = {n: jnp.swapaxes(l, 1, 2).reshape(
            b, 1, l.shape[2], -1, l.shape[-1])
            for n, l in cache_blk.items()}
    k, v = cache_blk["k"], cache_blk["v"]       # (B, W, Hkv, P, hd)
    w, kvh, page = k.shape[1:4]
    quant = "k_s" in cache_blk
    qg = q.reshape(b, tq, kvh, h // kvh, hd)
    scale = 1.0 / jnp.sqrt(jnp.float32(hd))

    def rowwise(s_leaf):                        # (B,W,Hkv,P,1) -> (B,Hkv,1,1,S)
        return jnp.swapaxes(s_leaf[..., 0], 1, 2).reshape(
            b, kvh, 1, 1, w * page)

    if quant:
        # int8 sweep: the einsum reads int8 rows (the cast fuses into
        # the load; int8 values are EXACT in bf16, so the MXU runs at
        # its bf16 rate with f32 accumulation); K's per-(b, head, t)
        # scale is constant over hd, so it multiplies the SCORE
        # instead of dequantizing the cache
        cdt = cfg.compute_dtype or cfg.dtype
        qg, k, v = qg.astype(cdt), k.astype(cdt), v.astype(cdt)
    s = jnp.einsum("bqhgd,bwhkd->bhgqwk", qg, k,
                   preferred_element_type=jnp.float32)
    s = s.reshape(b, kvh, h // kvh, tq, w * page)
    if quant:
        s = s * rowwise(cache_blk["k_s"])
    s = s * scale
    s = jnp.where(valid, s, jnp.float32(-1e30))
    p = jax.nn.softmax(s, axis=-1)
    if quant:
        # V's scale varies along the summation index — fold it into the
        # (tiny) probability rows, keeping the V read int8
        p = p * rowwise(cache_blk["v_s"])
    p = p.astype(v.dtype).reshape(b, kvh, h // kvh, tq, w, page)
    out = jnp.einsum("bhgqwk,bwhkd->bqhgd", p, v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, tq, h, hd).astype(q.dtype)


def position_mask(slots: int, pos, window: int = 0):
    """The contiguous-cache validity prefix: slots [0, pos] are live
    (the tail beyond `pos` is zeros — masked out by position, so its
    contents never matter), optionally windowed to the training mask's
    sliding window."""
    valid = jnp.arange(slots) <= pos
    if window > 0:
        valid = valid & (jnp.arange(slots) > pos - window)
    return valid


def cached_attention(q, cache_blk, pos, cfg, window: int | None = None):
    """q: (B, 1, H, hd) at position `pos`; attends over cache[:, :pos+1]
    — `masked_attention` under the contiguous position prefix, cut to
    the layer's `window` (of `cfg.layer_specs`; 0 = all of it, None = a
    uniform model's `cfg.window`)."""
    valid = position_mask(cache_blk["k"].shape[2], pos,
                          cfg.window if window is None else window)
    return masked_attention(q, cache_blk,
                            valid[None, None, None, None, :], cfg)
