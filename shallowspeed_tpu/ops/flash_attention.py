"""Flash attention — fused blockwise attention as Pallas TPU kernels.

The hot op of the transformer family (`models/transformer.py`). XLA compiles
the naive `ops.attention` into einsum+softmax+einsum with the full (T, T)
score matrix materialized in HBM; this kernel computes attention blockwise in
VMEM with an online softmax (the FlashAttention-2 formulation), so HBM
traffic is O(T·D) instead of O(T²) and the MXU stays fed from on-chip
memory.

All kernels share one streaming structure: a 3-D grid
(batch·kv-head, out-block, reduction-block) whose INNERMOST axis is the
reduction, so VMEM holds one (block_q, block_k) tile's operands at a time
— per-step VMEM is O(block²), independent of sequence length:

- forward: grid (bh, q-block, k-block); the online-softmax state
  (running max m, normalizer l, unnormalized acc) persists in VMEM
  scratch across the sequential k steps; the output block is normalized
  and the log-sum-exp saved at the last k step.
- backward-dq: grid (bh, q-block, k-block); recomputes p from (q, k,
  lse), forms ds = p * (dp - delta), and accumulates dq = Σ ds·k into a
  revisited f32 output block.
- backward-dkv: grid (bh, k-block, q-block); accumulates dv = Σ pᵀ·do
  and dk = Σ dsᵀ·q the same way.

Every entry point picks between this streaming form and a resident fast
path (whole K/V — or Q/dO/stats for dkv — held in VMEM with a fori_loop
reduction) when the sequence fits `_RESIDENT_BYTES`; the resident form's
causal/window loop bounds skip masked tiles' DMA entirely. In the
streaming form, masked-out tiles skip their COMPUTE with `pl.when`
(whole-tile Mosaic predication) but the grid still visits them.

**Sliding windows** (`window > 0`): position i sees keys
[i - window + 1, i] — identical semantics to `ops.attention`'s
`window=` mask. Out-of-window k-tiles are skipped exactly like causal
future tiles. A long sequence with a small window costs O(T·window).

**Grouped-query attention** is native: pass k/v with fewer heads
(n_kv_heads) than q and the kernels never materialize repeated K/V.
Group folding maps GQA onto the exact same kernel bodies: q's heads
fold as extra ROWS — (B, T, H, D) -> (B·Hkv, G·T, D) with each
G-chunk of rows one query head sharing that kv head — so every q-row
block attends against the SAME resident/streamed K/V tile, which is
precisely the reuse GQA exists to exploit. Kernels recover logical
positions as `row mod T` (blocks never straddle chunks since
block_q | T). MHA is the G=1 special case — one code path.

**Position offsets / ring attention.** Every kernel takes a dynamic
scalar `rel` = (global q position) - (global k position) offset, so the
same kernels compute any DIAGONAL CHUNK of a larger attention problem:
masks compare `rel + local_row >= local_col`. `ring_flash_attention`
builds sequence-parallel ring attention from these chunks — K/V blocks
rotate over the mesh axis with `lax.ppermute` while each device merges
its queries' per-chunk (o, lse) with the standard log-sum-exp chunk
merge, and a hand-written VJP runs the ring again in reverse with the
dk/dv accumulators traveling alongside the K/V blocks. Same contract as
`ops.attention.ring_attention`, but the local compute is this fused
kernel instead of a materialized (T_local, T_local) XLA score matrix.

Wrapped in `jax.custom_vjp`, so `jax.grad` through the transformer uses the
fused backward. On non-TPU backends the kernels run in Pallas interpret mode
(exact same code path, used by the CPU test suite); on TPU they compile via
Mosaic. Layout contract matches `ops.attention`: (batch, seq, heads,
head_dim).

Written per /opt/skills/guides/pallas_guide.md (blockwise VMEM tiling,
online-softmax accumulators, preferred_element_type=f32 on every MXU dot,
@pl.when for edge blocks).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

_NEG = -1e30  # plain float: jnp scalars would be captured consts in kernels
_LANES = 128  # Mosaic min lane width: row stats (lse/delta) pad to this
# Default kernel tile sizes (auto-shrunk per sequence by _pick_block).
# Exported so out-of-module replay paths (parallel/zb.py's split
# backward) tile identically to every in-module entry point.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


# Resident-K/V fast path bound: with tk*d at or under this, the whole K and
# V comfortably fit VMEM next to the working blocks, and the single-kernel
# fori_loop formulation avoids the streaming version's per-tile scratch
# round-trips. Above it, stream (VMEM-unbounded). Byte-based (dtype-aware):
# 8k x 64 f32 K/V picks streaming while the same shape in bf16 stays
# resident — an element-count gate let the f32 case overflow the 16MB
# scoped-vmem ceiling by a hair.
_RESIDENT_BYTES = 1 << 20  # 1MB per whole-sequence operand held in VMEM


def _mask(s, qrow, kcol, causal, window):
    """Apply the causal and/or sliding-window mask to a score tile.
    `qrow`/`kcol` are GLOBAL positions (the q side already includes the
    chunk's `rel` offset). Returns (masked scores, mask or None)."""
    valid = None
    if causal:
        valid = qrow >= kcol
    if window > 0:
        wv = kcol > qrow - window
        valid = wv if valid is None else valid & wv
    if valid is not None:
        s = jnp.where(valid, s, _NEG)
    return s, valid


def _kblock_bounds(qstart, block_q, block_k, nkb, causal, window):
    """fori_loop bounds over k-blocks for the q block whose first GLOBAL
    row is `qstart` (resident fwd/dq paths). Tiles outside [lo, hi)
    contain no unmasked entry — their DMA is never issued."""
    lo = jnp.int32(0)
    hi = jnp.int32(nkb)
    if causal:
        hi = jnp.clip((qstart + block_q - 1) // block_k + 1, 0, nkb)
    if window > 0:
        first_col = jnp.maximum(0, qstart - (window - 1))
        lo = jnp.clip(first_col // block_k, 0, nkb)
    return lo, hi


def _tile_live(qstart, jk, block_q, block_k, causal, window):
    """Whether the tile at global-q-start `qstart`, k-block `jk` has any
    unmasked entry (streaming paths' `pl.when` predicate)."""
    live = True
    if causal:  # last q row >= first k col
        live = (qstart + block_q - 1) >= (jk * block_k)
    if window > 0:  # last k col inside the earliest row's window
        wlive = (jk * block_k + block_k - 1) >= (qstart - (window - 1))
        live = wlive if live is True else live & wlive
    return live


# ----------------------------------------------------------------- forward


def _fwd_kernel_resident(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale,
                         causal, window, rel, block_q, block_k, seq_k,
                         nqb_chunk):
    """Grid (bh, nqb): whole K/V resident in VMEM, fori_loop over k-blocks
    with the online-softmax carry in registers. Fast path for small T."""
    iq = pl.program_id(1)
    iqm = iq % nqb_chunk  # chunk-local block index (GQA row folding)
    qstart = rel + iqm * block_q
    q = q_ref[:].astype(jnp.float32)                       # (bq, D)
    d = q.shape[-1]

    nkb = seq_k // block_k
    lo, hi = _kblock_bounds(qstart, block_q, block_k, nkb, causal, window)

    qrow = qstart + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)

    def body(j, carry):
        m, l, acc = carry
        kb = k_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        vb = v_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, kb.T, preferred_element_type=jnp.float32) * scale
        kcol = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s, valid = _mask(s, qrow, kcol, causal, window)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if valid is not None:
            p = jnp.where(valid, p, 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.dot(
            p, vb, preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((block_q, 1), _NEG)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, acc0))

    o_ref[:] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    lse_ref[:] = jnp.broadcast_to(
        m + jnp.log(jnp.maximum(l, 1e-30)), (block_q, _LANES))


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                acc_scr, *, scale, causal, window, rel, block_q,
                block_k, nkb, nqb_chunk):
    """Grid (bh, nqb, nkb) — the K reduction is the INNERMOST grid axis,
    so VMEM holds one (block_q, block_k)-tile's operands at a time; the
    online-softmax state (m, l, acc) lives in scratch that persists
    across the sequential innermost steps, and the (bh, iq) output block
    is finalized at the last K step. Fully-masked causal/window tiles
    skip their matmuls via `pl.when`."""
    iq = pl.program_id(1)
    jk = pl.program_id(2)
    iqm = iq % nqb_chunk
    qstart = rel + iqm * block_q

    @pl.when(jk == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    live = _tile_live(qstart, jk, block_q, block_k, causal, window)

    @pl.when(live)
    def _accum():
        q = q_ref[:].astype(jnp.float32)                   # (bq, D)
        kb = k_ref[:].astype(jnp.float32)                  # (bk, D)
        vb = v_ref[:].astype(jnp.float32)
        s = jnp.dot(q, kb.T, preferred_element_type=jnp.float32) * scale
        qrow = qstart + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kcol = jk * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s, valid = _mask(s, qrow, kcol, causal, window)
        m = m_scr[:, 0:1]
        l = l_scr[:, 0:1]
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if valid is not None:
            p = jnp.where(valid, p, 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jnp.dot(
            p, vb, preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(jk == nkb - 1)
    def _finalize():
        l = l_scr[:, 0:1]
        o_ref[:] = (acc_scr[:] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        # row stats broadcast across a 128-lane dim (Mosaic min tile width)
        lse_ref[:] = jnp.broadcast_to(
            m_scr[:, 0:1] + jnp.log(jnp.maximum(l, 1e-30)),
            (block_q, _LANES))


# ---------------------------------------------------------------- backward


def _dq_kernel_resident(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                        dq_ref, *, scale, causal, window, rel, block_q,
                        block_k, seq_k, nqb_chunk):
    """Grid (bh, nqb): whole K/V resident in VMEM, fori_loop over k-blocks
    with shrunk causal/window bounds. Fast path for small T."""
    iq = pl.program_id(1)
    iqm = iq % nqb_chunk
    qstart = rel + iqm * block_q
    q = q_ref[:].astype(jnp.float32)
    do = do_ref[:].astype(jnp.float32)
    lse = lse_ref[:, 0:1]
    delta = delta_ref[:, 0:1]
    d = q.shape[-1]

    nkb = seq_k // block_k
    lo, hi = _kblock_bounds(qstart, block_q, block_k, nkb, causal, window)

    qrow = qstart + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)

    def body(j, dq):
        kb = k_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        vb = v_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, kb.T, preferred_element_type=jnp.float32) * scale
        kcol = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s, _valid = _mask(s, qrow, kcol, causal, window)
        p = jnp.exp(s - lse)
        dp = jnp.dot(do, vb.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        return dq + jnp.dot(ds, kb, preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(
        lo, hi, body, jnp.zeros((block_q, d), jnp.float32))
    dq_ref[:] = dq.astype(dq_ref.dtype)


def _dkv_kernel_resident(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dk_ref, dv_ref, *, scale, causal, window, rel,
                         block_q, block_k, seq_q, nqb_chunk, groups):
    """Grid (bh, nkb): whole Q/dO/stats resident in VMEM; for each of the
    `groups` query-head chunks (GQA row folding; static unroll), a
    fori_loop over that chunk's live q-blocks accumulates into the
    SHARED dk/dv block. Fast path for small T — the stats are
    (T, 128)-lane f32, so this path's VMEM grows 512B/row and is gated
    tighter than the forward's."""
    jk = pl.program_id(1)
    kb = k_ref[:].astype(jnp.float32)                      # (bk, D)
    vb = v_ref[:].astype(jnp.float32)
    d = kb.shape[-1]

    # chunk-local q-block bounds: with global row = rel + local row, a
    # q block is live for this k block iff its last global row reaches
    # the k block (causal) and its first global row is within window
    if causal:
        first = jnp.clip(
            (jk * block_k - rel) // block_q, 0, nqb_chunk)
    else:
        first = jnp.int32(0)
    if window > 0:
        last = jnp.clip(
            (jk * block_k + block_k - 1 + window - 1 - rel) // block_q
            + 1, 0, nqb_chunk)
    else:
        last = jnp.int32(nqb_chunk)

    kcol = jk * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    def chunk_body(base, carry):
        # `base` = this chunk's first block index in the folded row space
        def body(i, carry):
            dk, dv = carry
            row0 = (base + i) * block_q
            qb = q_ref[pl.ds(row0, block_q), :].astype(jnp.float32)
            dob = do_ref[pl.ds(row0, block_q), :].astype(jnp.float32)
            lse = lse_ref[pl.ds(row0, block_q), 0:1]
            delta = delta_ref[pl.ds(row0, block_q), 0:1]
            s = jnp.dot(qb, kb.T,
                        preferred_element_type=jnp.float32) * scale
            qrow = rel + i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            s, _valid = _mask(s, qrow, kcol, causal, window)
            p = jnp.exp(s - lse)
            dv = dv + jnp.dot(p.T, dob,
                              preferred_element_type=jnp.float32)
            dp = jnp.dot(dob, vb.T, preferred_element_type=jnp.float32)
            ds = p * (dp - delta) * scale
            dk = dk + jnp.dot(ds.T, qb,
                              preferred_element_type=jnp.float32)
            return dk, dv

        return jax.lax.fori_loop(first, last, body, carry)

    dk = jnp.zeros((block_k, d), jnp.float32)
    dv = jnp.zeros((block_k, d), jnp.float32)
    for gi in range(groups):  # static: groups is a compile-time constant
        dk, dv = chunk_body(gi * nqb_chunk, (dk, dv))
    dk_ref[:] = dk.astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               *, scale, causal, window, rel, block_q, block_k,
               nqb_chunk):
    """Grid (bh, nqb, nkb) — the K reduction runs as the INNERMOST grid
    axis so VMEM holds one (block_q, block_k)-tile's operands at a time;
    dq_ref is the (bh, iq) block, revisited across j, f32 accumulated.
    Fully-masked causal/window tiles skip their matmuls via `pl.when`."""
    iq = pl.program_id(1)
    jk = pl.program_id(2)
    iqm = iq % nqb_chunk
    qstart = rel + iqm * block_q

    @pl.when(jk == 0)
    def _init():
        dq_ref[:] = jnp.zeros_like(dq_ref)

    live = _tile_live(qstart, jk, block_q, block_k, causal, window)

    @pl.when(live)
    def _accum():
        q = q_ref[:].astype(jnp.float32)
        do = do_ref[:].astype(jnp.float32)
        lse = lse_ref[:, 0:1]
        delta = delta_ref[:, 0:1]
        kb = k_ref[:].astype(jnp.float32)
        vb = v_ref[:].astype(jnp.float32)
        s = jnp.dot(q, kb.T, preferred_element_type=jnp.float32) * scale
        qrow = qstart + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kcol = jk * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s, _valid = _mask(s, qrow, kcol, causal, window)
        p = jnp.exp(s - lse)
        dp = jnp.dot(do, vb.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dq_ref[:] += jnp.dot(ds, kb, preferred_element_type=jnp.float32)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, *, scale, causal, window, rel, block_q,
                block_k, nqb_chunk):
    """Grid (bh, nkb, nqb_total) — Q reduction innermost (across ALL
    query-group chunks under GQA, so group members' contributions
    accumulate into the shared dk/dv block), (bh, jk) output block
    revisited across i with f32 accumulation; same VMEM story as
    `_dq_kernel`."""
    jk = pl.program_id(1)
    iq = pl.program_id(2)
    iqm = iq % nqb_chunk
    qstart = rel + iqm * block_q

    @pl.when(iq == 0)
    def _init():
        dk_ref[:] = jnp.zeros_like(dk_ref)
        dv_ref[:] = jnp.zeros_like(dv_ref)

    live = _tile_live(qstart, jk, block_q, block_k, causal, window)

    @pl.when(live)
    def _accum():
        kb = k_ref[:].astype(jnp.float32)                  # (bk, D)
        vb = v_ref[:].astype(jnp.float32)
        qb = q_ref[:].astype(jnp.float32)
        dob = do_ref[:].astype(jnp.float32)
        lse = lse_ref[:, 0:1]
        delta = delta_ref[:, 0:1]
        s = jnp.dot(qb, kb.T, preferred_element_type=jnp.float32) * scale
        qrow = qstart + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        kcol = jk * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s, _valid = _mask(s, qrow, kcol, causal, window)
        p = jnp.exp(s - lse)
        dv_ref[:] += jnp.dot(p.T, dob, preferred_element_type=jnp.float32)
        dp = jnp.dot(dob, vb.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        dk_ref[:] += jnp.dot(ds.T, qb, preferred_element_type=jnp.float32)


# ----------------------------------------------------- layout helpers


def _to_bhsd(x):
    """(B, T, H, D) -> (B*H, T, D) for the (batch·head, block) grid."""
    b, t, h, d = x.shape
    return jnp.reshape(jnp.transpose(x, (0, 2, 1, 3)), (b * h, t, d))


def _from_bhsd(x, b, h):
    bh, t, d = x.shape
    return jnp.transpose(jnp.reshape(x, (b, h, t, d)), (0, 2, 1, 3))


def _fold_q(x, kvh):
    """GQA row folding: (B, T, H, D) -> (B*Hkv, G*T, D) where query head
    h = kv*G + g lands in rows [g*T, (g+1)*T) of batch-row b*Hkv + kv —
    each G-chunk of rows is one query head sharing that kv head."""
    b, t, h, d = x.shape
    g = h // kvh
    x = jnp.transpose(x, (0, 2, 1, 3))          # (B, H, T, D)
    return jnp.reshape(x, (b * kvh, g * t, d))  # heads split as (kvh, g)


def _unfold_q(x, b, h):
    """Inverse of `_fold_q`: (B*Hkv, G*T, D) -> (B, T, H, D)."""
    bkv, gt, d = x.shape
    kvh = bkv // b
    g = h // kvh
    x = jnp.reshape(x, (b, kvh, g, gt // g, d))
    x = jnp.reshape(x, (b, h, gt // g, d))
    return jnp.transpose(x, (0, 2, 1, 3))


def _pick_block(t: int, want: int) -> int:
    while t % want:
        want //= 2
    return max(want, 1)


def _sds(shape, dtype, like):
    """ShapeDtypeStruct inheriting `like`'s shard_map variance (vma), so the
    kernels compose with explicit-sharding engines (pallas_call under
    shard_map requires explicit output vma)."""
    vma = getattr(getattr(like, "aval", None), "vma", None)
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


# ------------------------------------------------------------ chunk API
# Folded-space primitives shared by `flash_attention` (rel = 0) and
# `ring_flash_attention` (rel = per-step global offset). All take/return
# (B*Hkv, rows|tk, D) arrays.


def _chunk_fwd(q3, k3, v3, rel, *, causal, window, bq, bk, nqb_chunk,
               interpret, out_dtype=None):
    """One chunk's flash forward. `out_dtype` overrides the o output's
    dtype (default: q3's): the RING path passes f32 so each chunk's
    normalized output reaches the log-sum-exp merge unrounded — with a
    bf16 chunk output every ring hop quantized its partial to bf16
    before the merge, compounding ~sqrt(n_chunks) x the single-rounding
    bf16 floor (the BENCH_r05 `ring_chunk` 2.3x-above-floor finding,
    VERDICT r5 weak #2; BASELINE.md 'ring-chunk numerics envelope').
    The kernel accumulator is f32 either way — this only widens what
    leaves the kernel; single-chunk callers keep the narrow output."""
    bh, rows, d = q3.shape
    tk = k3.shape[1]
    scale = 1.0 / float(np.sqrt(d))
    out_shape = [
        _sds((bh, rows, d), out_dtype or q3.dtype, q3),
        _sds((bh, rows, _LANES), jnp.float32, q3),
    ]
    if tk * d * q3.dtype.itemsize <= _RESIDENT_BYTES:
        kernel = functools.partial(
            _fwd_kernel_resident, scale=scale, causal=causal,
            window=window, rel=rel, block_q=bq, block_k=bk, seq_k=tk,
            nqb_chunk=nqb_chunk)
        return pl.pallas_call(
            kernel,
            grid=(bh, rows // bq),
            in_specs=[
                pl.BlockSpec((None, bq, d), lambda i, j: (i, j, 0)),
                pl.BlockSpec((None, tk, d), lambda i, j: (i, 0, 0)),
                pl.BlockSpec((None, tk, d), lambda i, j: (i, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((None, bq, d), lambda i, j: (i, j, 0)),
                pl.BlockSpec((None, bq, _LANES), lambda i, j: (i, j, 0)),
            ],
            out_shape=out_shape,
            interpret=interpret,
        )(q3, k3, v3)
    from jax.experimental.pallas import tpu as pltpu

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window, rel=rel,
        block_q=bq, block_k=bk, nkb=tk // bk, nqb_chunk=nqb_chunk)
    return pl.pallas_call(
        kernel,
        grid=(bh, rows // bq, tk // bk),
        in_specs=[
            pl.BlockSpec((None, bq, d), lambda i, j, k_: (i, j, 0)),
            pl.BlockSpec((None, bk, d), lambda i, j, k_: (i, k_, 0)),
            pl.BlockSpec((None, bk, d), lambda i, j, k_: (i, k_, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, bq, d), lambda i, j, k_: (i, j, 0)),
            pl.BlockSpec((None, bq, _LANES), lambda i, j, k_: (i, j, 0)),
        ],
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),  # running max m
            pltpu.VMEM((bq, _LANES), jnp.float32),  # running norm l
            pltpu.VMEM((bq, d), jnp.float32),       # unnormalized out
        ],
        interpret=interpret,
    )(q3, k3, v3)


def _chunk_dq(q3, k3, v3, do3, lse, delta, rel, *, causal, window, bq, bk,
              nqb_chunk, interpret):
    bh, rows, d = q3.shape
    tk = k3.shape[1]
    scale = 1.0 / float(np.sqrt(d))
    if tk * d * q3.dtype.itemsize <= _RESIDENT_BYTES:
        kernel = functools.partial(
            _dq_kernel_resident, scale=scale, causal=causal,
            window=window, rel=rel, block_q=bq, block_k=bk, seq_k=tk,
            nqb_chunk=nqb_chunk)
        return pl.pallas_call(
            kernel,
            grid=(bh, rows // bq),
            in_specs=[
                pl.BlockSpec((None, bq, d), lambda i, j: (i, j, 0)),
                pl.BlockSpec((None, tk, d), lambda i, j: (i, 0, 0)),
                pl.BlockSpec((None, tk, d), lambda i, j: (i, 0, 0)),
                pl.BlockSpec((None, bq, d), lambda i, j: (i, j, 0)),
                pl.BlockSpec((None, bq, _LANES), lambda i, j: (i, j, 0)),
                pl.BlockSpec((None, bq, _LANES), lambda i, j: (i, j, 0)),
            ],
            out_specs=pl.BlockSpec((None, bq, d), lambda i, j: (i, j, 0)),
            out_shape=_sds((bh, rows, d), jnp.float32, q3),
            interpret=interpret,
        )(q3, k3, v3, do3, lse, delta)
    kernel = functools.partial(
        _dq_kernel, scale=scale, causal=causal, window=window, rel=rel,
        block_q=bq, block_k=bk, nqb_chunk=nqb_chunk)
    return pl.pallas_call(
        kernel,
        grid=(bh, rows // bq, tk // bk),
        in_specs=[
            pl.BlockSpec((None, bq, d), lambda i, j, k_: (i, j, 0)),
            pl.BlockSpec((None, bk, d), lambda i, j, k_: (i, k_, 0)),
            pl.BlockSpec((None, bk, d), lambda i, j, k_: (i, k_, 0)),
            pl.BlockSpec((None, bq, d), lambda i, j, k_: (i, j, 0)),
            pl.BlockSpec((None, bq, _LANES), lambda i, j, k_: (i, j, 0)),
            pl.BlockSpec((None, bq, _LANES), lambda i, j, k_: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, bq, d), lambda i, j, k_: (i, j, 0)),
        out_shape=_sds((bh, rows, d), jnp.float32, q3),
        interpret=interpret,
    )(q3, k3, v3, do3, lse, delta)


def _chunk_dkv(q3, k3, v3, do3, lse, delta, rel, *, causal, window, bq,
               bk, nqb_chunk, groups, interpret):
    bh, rows, d = q3.shape
    tk = k3.shape[1]
    scale = 1.0 / float(np.sqrt(d))
    # lse/delta stats are always f32 and get a deliberate 2x allowance;
    # under GQA the WHOLE folded Q/dO/stats must sit in VMEM, so both
    # gates are absolute in `rows`.
    stats_bytes = rows * _LANES * jnp.dtype(jnp.float32).itemsize
    resident = (rows * d * q3.dtype.itemsize <= _RESIDENT_BYTES
                and stats_bytes <= 2 * _RESIDENT_BYTES)
    out_shape = [
        _sds((bh, tk, d), jnp.float32, q3),
        _sds((bh, tk, d), jnp.float32, q3),
    ]
    if resident:
        kernel = functools.partial(
            _dkv_kernel_resident, scale=scale, causal=causal,
            window=window, rel=rel, block_q=bq, block_k=bk,
            seq_q=rows // groups, nqb_chunk=nqb_chunk, groups=groups)
        return pl.pallas_call(
            kernel,
            grid=(bh, tk // bk),
            in_specs=[
                pl.BlockSpec((None, rows, d), lambda i, j: (i, 0, 0)),
                pl.BlockSpec((None, bk, d), lambda i, j: (i, j, 0)),
                pl.BlockSpec((None, bk, d), lambda i, j: (i, j, 0)),
                pl.BlockSpec((None, rows, d), lambda i, j: (i, 0, 0)),
                pl.BlockSpec((None, rows, _LANES), lambda i, j: (i, 0, 0)),
                pl.BlockSpec((None, rows, _LANES), lambda i, j: (i, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((None, bk, d), lambda i, j: (i, j, 0)),
                pl.BlockSpec((None, bk, d), lambda i, j: (i, j, 0)),
            ],
            out_shape=out_shape,
            interpret=interpret,
        )(q3, k3, v3, do3, lse, delta)
    kernel = functools.partial(
        _dkv_kernel, scale=scale, causal=causal, window=window, rel=rel,
        block_q=bq, block_k=bk, nqb_chunk=nqb_chunk)
    return pl.pallas_call(
        kernel,
        grid=(bh, tk // bk, rows // bq),
        in_specs=[
            pl.BlockSpec((None, bq, d), lambda i, j, k_: (i, k_, 0)),
            pl.BlockSpec((None, bk, d), lambda i, j, k_: (i, j, 0)),
            pl.BlockSpec((None, bk, d), lambda i, j, k_: (i, j, 0)),
            pl.BlockSpec((None, bq, d), lambda i, j, k_: (i, k_, 0)),
            pl.BlockSpec((None, bq, _LANES), lambda i, j, k_: (i, k_, 0)),
            pl.BlockSpec((None, bq, _LANES), lambda i, j, k_: (i, k_, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, bk, d), lambda i, j, k_: (i, j, 0)),
            pl.BlockSpec((None, bk, d), lambda i, j, k_: (i, j, 0)),
        ],
        out_shape=out_shape,
        interpret=interpret,
    )(q3, k3, v3, do3, lse, delta)


def _delta_of(do3, o3, like_lse):
    """delta_i = rowsum(dO_i * O_i) — the softmax-jacobian diagonal term,
    broadcast across the 128-lane stats dim like lse."""
    return jnp.broadcast_to(
        jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                axis=-1, keepdims=True),
        like_lse.shape)


# ------------------------------------------------------------- entry points


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K,
                    interpret: bool | None = None):
    """Fused multi-head attention; same contract as `ops.attention`.

    q: (batch, seq, heads, head_dim); k, v: (batch, seq, kv_heads,
    head_dim) with kv_heads | heads — kv_heads < heads is native GQA (no
    repeated K/V is ever materialized). Returns (batch, seq, heads,
    head_dim). `window > 0` restricts position i to keys
    [i - window + 1, i] (sliding-window attention; out-of-window tiles
    are skipped, not just masked). Sequence lengths must be divisible by
    the (auto-shrunk) block sizes.
    `interpret=None` auto-selects Pallas interpret mode off-TPU.
    """
    o, _ = _flash_fwd(q, k, v, causal, window, block_q, block_k, interpret)
    return o


flash_attention.supports_gqa = True
flash_attention.supports_window = True


def _geometry(q, k, block_q, block_k):
    b, tq, h, d = q.shape
    kvh = k.shape[2]
    assert h % kvh == 0, (h, kvh)
    bq = _pick_block(tq, block_q)
    bk = _pick_block(k.shape[1], block_k)
    return b, tq, h, d, kvh, h // kvh, bq, bk, tq // bq


def _flash_fwd(q, k, v, causal, window, block_q, block_k, interpret):
    if interpret is None:
        interpret = _interpret_default()
    b, tq, h, d, kvh, g, bq, bk, nqb_chunk = _geometry(q, k, block_q,
                                                       block_k)
    q3 = _fold_q(q, kvh)                         # (b*kvh, g*tq, d)
    k3, v3 = _to_bhsd(k), _to_bhsd(v)            # (b*kvh, tk, d)
    o3, lse = _chunk_fwd(q3, k3, v3, 0, causal=causal, window=int(window),
                         bq=bq, bk=bk, nqb_chunk=nqb_chunk,
                         interpret=interpret)
    o = _unfold_q(o3, b, h)
    return o, (q, k, v, o, lse)


def _flash_fwd_rule(q, k, v, causal, window, block_q, block_k, interpret):
    o, res = _flash_fwd(q, k, v, causal, window, block_q, block_k,
                        interpret)
    return o, res


def _flash_bwd_rule(causal, window, block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    if interpret is None:
        interpret = _interpret_default()
    b, tq, h, d, kvh, g, bq, bk, nqb_chunk = _geometry(q, k, block_q,
                                                       block_k)
    window = int(window)
    q3, k3, v3 = _fold_q(q, kvh), _to_bhsd(k), _to_bhsd(v)
    o3, do3 = _fold_q(o, kvh), _fold_q(do, kvh)
    delta = _delta_of(do3, o3, lse)
    kw = dict(causal=causal, window=window, bq=bq, bk=bk,
              nqb_chunk=nqb_chunk, interpret=interpret)
    dq3 = _chunk_dq(q3, k3, v3, do3, lse, delta, 0, **kw)
    dk3, dv3 = _chunk_dkv(q3, k3, v3, do3, lse, delta, 0, groups=g, **kw)
    return (_unfold_q(dq3, b, h).astype(q.dtype),
            _from_bhsd(dk3, b, kvh).astype(k.dtype),
            _from_bhsd(dv3, b, kvh).astype(v.dtype))


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# ------------------------------------------------------------ ring flash


def _merge_chunks(o_acc, lse_acc, o_i, lse_i):
    """Standard log-sum-exp merge of two normalized attention chunks:
    each o is a softmax-weighted average with total mass exp(lse).
    lse carries the 128-lane stats dim (all lanes identical); the o
    weighting uses lane 0."""
    m = jnp.maximum(lse_acc, lse_i)
    a = jnp.exp(lse_acc - m)                    # (bh, rows, _LANES)
    b = jnp.exp(lse_i - m)
    denom = jnp.maximum(a + b, 1e-30)
    o = (o_acc * a[..., 0:1] + o_i.astype(jnp.float32) * b[..., 0:1]) \
        / denom[..., 0:1]
    return o, m + jnp.log(denom)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def ring_flash_attention(q, k, v, axis_name: str, causal: bool = True,
                         window: int = 0):
    """Ring attention with the fused flash kernel as the local compute.

    Same contract as `ops.attention.ring_attention` (q: (batch,
    seq_local, heads, head_dim); k/v may carry fewer GQA kv heads; the
    global sequence is the concatenation of blocks in mesh-axis order),
    but each ring step runs the blockwise Pallas kernel on its
    (local q) x (visiting K/V block) chunk — masks offset by the chunk's
    global position delta, out-of-range tiles skipped — instead of
    materializing a (T_local, T_local) XLA score matrix. Per-chunk
    (o, lse) merge with the standard log-sum-exp rule; the hand-written
    VJP rides the ring in reverse with dk/dv accumulators traveling
    alongside the K/V blocks (each block collects its gradient from
    every query shard exactly once, then arrives home)."""
    o, _ = _ring_fwd(q, k, v, axis_name, causal, window)
    return o


ring_flash_attention.supports_gqa = True
ring_flash_attention.supports_window = True


def _ring_geometry(q, k):
    b, t, h, d = q.shape
    kvh = k.shape[2]
    assert h % kvh == 0, (h, kvh)
    bq = _pick_block(t, DEFAULT_BLOCK_Q)
    bk = _pick_block(k.shape[1], DEFAULT_BLOCK_K)
    return b, t, h, d, kvh, h // kvh, bq, bk, t // bq


def _ring_fwd(q, k, v, axis_name, causal, window):
    from jax import lax

    interpret = _interpret_default()
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    b, t, h, d, kvh, g, bq, bk, nqb_chunk = _ring_geometry(q, k)
    window = int(window)
    q3 = _fold_q(q, kvh)
    k3, v3 = _to_bhsd(k), _to_bhsd(v)
    # f32 chunk outputs: the lse-merge carry is f32, so a bf16 chunk
    # output would round every partial once per ring hop before
    # merging (see _chunk_fwd's out_dtype note)
    kw = dict(causal=causal, window=window, bq=bq, bk=bk,
              nqb_chunk=nqb_chunk, interpret=interpret,
              out_dtype=jnp.float32)
    perm = [(j, (j + 1) % n) for j in range(n)]

    # Ring step i: device idx holds the K/V block of device (idx - i)
    # mod n, so the position offset rel = (global q start) - (global k
    # start) is i*t when idx >= i and (i-n)*t otherwise. The step count
    # n is STATIC (mesh axis size), so the ring unrolls as a Python loop
    # and each chunk gets a COMPILE-TIME rel — kernels stay free of
    # dynamic scalars, and under causal masking the idx < i branch
    # (q entirely before the visiting block) skips its kernels outright.
    zq = q3.astype(jnp.float32).sum() * 0.0
    o3 = jnp.zeros(q3.shape, jnp.float32) + zq
    lse = jnp.full((q3.shape[0], q3.shape[1], _LANES), _NEG) + zq
    kb, vb = k3, v3
    for i in range(n):
        if i == 0:
            o3, lse = _merge_chunks(o3, lse, *_chunk_fwd(q3, kb, vb, 0,
                                                         **kw))
        elif causal and window == 0:
            # future block on idx < i: fully masked — skip the kernel
            def live(ops, i=i):
                return _merge_chunks(ops[0], ops[1], *_chunk_fwd(
                    q3, ops[2], ops[3], i * t, **kw))

            o3, lse = lax.cond(idx >= i, live,
                               lambda ops: (ops[0], ops[1]),
                               (o3, lse, kb, vb))
        else:
            def fwd_at(rel):
                def f(ops):
                    return _merge_chunks(ops[0], ops[1], *_chunk_fwd(
                        q3, ops[2], ops[3], rel, **kw))

                return f

            o3, lse = lax.cond(idx >= i, fwd_at(i * t),
                               fwd_at((i - n) * t), (o3, lse, kb, vb))
        if i + 1 < n:
            kb = lax.ppermute(kb, axis_name, perm)
            vb = lax.ppermute(vb, axis_name, perm)
    o = _unfold_q(o3.astype(q.dtype), b, h)
    return o, (q, k, v, _unfold_q(o3, b, h), lse)


def _ring_fwd_rule(q, k, v, axis_name, causal, window):
    o, res = _ring_fwd(q, k, v, axis_name, causal, window)
    return o, res


def _ring_bwd_rule(axis_name, causal, window, res, do):
    from jax import lax

    q, k, v, o_f32, lse = res
    interpret = _interpret_default()
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    b, t, h, d, kvh, g, bq, bk, nqb_chunk = _ring_geometry(q, k)
    window = int(window)
    q3, k3, v3 = _fold_q(q, kvh), _to_bhsd(k), _to_bhsd(v)
    o3, do3 = _fold_q(o_f32, kvh), _fold_q(do, kvh)
    delta = _delta_of(do3, o3, lse)
    kw = dict(causal=causal, window=window, bq=bq, bk=bk,
              nqb_chunk=nqb_chunk, interpret=interpret)
    perm = [(j, (j + 1) % n) for j in range(n)]

    # Reverse ring, same static-rel unrolling as the forward. dk/dv
    # accumulators travel WITH their K/V block (rotated together every
    # hop): after n hops each block is home, having collected its
    # gradient contribution from every query shard exactly once.
    zq = q3.astype(jnp.float32).sum() * 0.0
    dq3 = jnp.zeros(q3.shape, jnp.float32) + zq
    dkb = jnp.zeros(k3.shape, jnp.float32) + zq
    dvb = jnp.zeros(k3.shape, jnp.float32) + zq
    kb, vb = k3, v3

    def contrib_at(rel):
        def f(ops):
            dq, dkb, dvb, kb, vb = ops
            dq = dq + _chunk_dq(q3, kb, vb, do3, lse, delta, rel, **kw)
            dk_i, dv_i = _chunk_dkv(q3, kb, vb, do3, lse, delta, rel,
                                    groups=g, **kw)
            return dq, dkb + dk_i, dvb + dv_i

        return f

    for i in range(n):
        ops = (dq3, dkb, dvb, kb, vb)
        if i == 0:
            dq3, dkb, dvb = contrib_at(0)(ops)
        elif causal and window == 0:
            dq3, dkb, dvb = lax.cond(
                idx >= i, contrib_at(i * t),
                lambda ops: (ops[0], ops[1], ops[2]), ops)
        else:
            dq3, dkb, dvb = lax.cond(
                idx >= i, contrib_at(i * t), contrib_at((i - n) * t),
                ops)
        # rotate grads with their block; the LAST hop brings every
        # block's accumulator home (unlike the fwd, this hop is needed)
        dkb = lax.ppermute(dkb, axis_name, perm)
        dvb = lax.ppermute(dvb, axis_name, perm)
        kb = lax.ppermute(kb, axis_name, perm)
        vb = lax.ppermute(vb, axis_name, perm)
    return (_unfold_q(dq3, b, h).astype(q.dtype),
            _from_bhsd(dkb, b, kvh).astype(k.dtype),
            _from_bhsd(dvb, b, kvh).astype(v.dtype))


ring_flash_attention.defvjp(_ring_fwd_rule, _ring_bwd_rule)


# ------------------------------------------------------ paged flash decode
#
# Single-query attention for the serving runtime's paged cache: the
# decode tick's read, for K/V pools and the latent pool alike
# (`serving/engine._decode_tick`). The XLA reference
# (`serving/cache.gather_table` + `kv_cache.masked_attention`; the
# prefill chunk's read of a K/V pool is `paged_flash_prefill`, below)
# first MATERIALIZES every row's table at
# the bucket's width and then contracts one query over all of it, in
# f32 on the VPU: the tick moved ~640 MB a layer for 19 MB of live
# blocks (`olmo-1b.chat`, PERF.md PR 29). This kernel reads the pool
# through the table instead. `bt` and `pos` are scalar-prefetch
# operands, the pools stay in HBM, and ONE program walks the
# rows in turn: row r reads blocks [first block of its window,
# pos // bs] of its own table and nothing else, so a dead row (pos 0,
# table all scratch) costs one block and bucket padding costs nothing,
# while the compile key stays (rows, table width).
#
# A pool block is one contiguous (Hkv, bs, hd) slab for all its KV
# heads and is the DMA's unit; `chunk` of them are in flight per
# compute step, landing head-major in a double-buffered VMEM scratch
# (Hkv, chunk, bs, hd), so a step is two batched matmuls over
# chunk * bs positions and the NEXT step's slabs (the next row's first,
# after a row's last) load under it. A grid step per (row, head, block)
# cost 0.15-0.35 us each and was all overhead (PR 24: 65 against 37 ms).
#
# Tiles are read as stored: bf16 (or int8) meets the MXU with f32
# accumulation, scores and the running softmax are f32 and live in
# VMEM only, probabilities meet V in V's dtype: `masked_attention`'s
# arithmetic. int8 pools keep their f32 scale planes outside the dots
# (K's on the score row, V's folded into the probability row). A pool
# of ONE leaf (the latent pool: Hkv 1, every query head a row of the
# matmul) is read as keys and values both, one DMA serving both.
# Interpreted, the reference parity is fp-reorder noise only (pinned
# <= 1e-4 in tests/test_flash_attention.py).


# slab DMAs issued (and waited for) a loop iteration. One an iteration
# cost 60 ns a slab, twice a 20 KB slab's transfer: the latent read took
# 368 us a layer so, 247 with a whole step of 32 written out, 262 at 4
# (chip run, PR 29). Every descriptor written out is traced and lowered
# on the host once a program, which no compilation cache holds: a step
# of 48 written out at three places cost 1.7 s a tick program.
_DMA_UNROLL = 4


def _paged_decode_kernel(bt_ref, pos_ref, q_ref, *refs, scale, bs, chunk,
                         window, n_pools, quant):
    """One program for the whole slot batch. `refs`: the pool leaves in
    HBM (keys and values, or the one leaf that is both), with `quant`
    the two scale tables (`_scale_table`), the output, then one VMEM
    buffer of two slots per operand ((2, Hkv, chunk, bs, hd) for a
    leaf, (2, Hkv, 1, lanes) for a scale table) and a DMA
    semaphore per slot."""
    from jax.experimental.pallas import tpu as pltpu

    n_in = n_pools + 2 * quant
    pools, scales, o_ref = refs[:n_pools], refs[n_pools:n_in], refs[n_in]
    bufs, sbufs, sem = (refs[n_in + 1:n_in + 1 + n_pools],
                        refs[n_in + 1 + n_pools:-1], refs[-1])
    n_rows, hkv, g, _ = q_ref.shape
    t = chunk * bs
    unroll = min(chunk, _DMA_UNROLL)
    # 16-bit operands multiply exactly into the f32 accumulator in one
    # pass; a process-wide "highest" (the tests') would ask Mosaic for
    # a multi-pass mode that only f32 operands have
    prec = (None if q_ref.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)

    def blocks_of(r):
        """Row r's position and the table columns [lo, hi) it reads."""
        p = pos_ref[r]
        lo = jnp.maximum(p - (window - 1), 0) // bs if window > 0 else 0
        if quant:   # a scale table is indexed by whole steps; what this
            #         adds is masked like the window's first block
            lo = lo // chunk * chunk
        return p, lo, p // bs + 1

    def dma(r, b0, hi, slot, start, unroll=unroll):
        """Start (or wait for) the slabs of table columns [b0, b0 +
        chunk) of row r that lie below `hi`, `unroll` to a loop
        iteration, then one by one."""
        def go(src, dst):
            cp = pltpu.make_async_copy(src, dst, sem.at[slot])
            cp.start() if start else cp.wait()

        def one(i, _=None):
            for pool, buf in zip(pools, bufs):
                go(pool.at[bt_ref[r, b0 + i]], buf.at[slot, :, i])

        def several(j, _):
            for i in range(unroll):
                one(j * unroll + i)

        n = jnp.minimum(chunk, hi - b0)
        groups = n // unroll if unroll > 1 else 0
        jax.lax.fori_loop(0, groups, several, None)
        jax.lax.fori_loop(groups * unroll, n, one, None)
        for table, sbuf in zip(scales, sbufs):
            go(table.at[r, b0 // chunk], sbuf.at[slot])

    # a row's last step is partial: positions past `pos` are masked out
    # of the scores, but 0 * (whatever VMEM held) must stay 0, so the
    # value side starts from zeros
    bufs[-1][...] = jnp.zeros_like(bufs[-1])
    dma(0, blocks_of(0)[1], blocks_of(0)[2], 0, True, unroll=1)

    def row(r, slot):
        p, lo, hi = blocks_of(r)
        steps = (hi - lo + chunk - 1) // chunk
        nxt = jnp.minimum(r + 1, n_rows - 1)
        _, nlo, nhi = blocks_of(nxt)
        q = q_ref[r]                                       # (Hkv, g, hd)

        def step(c, carry):
            m, l, acc, slot = carry
            b0 = lo + c * chunk
            last = c + 1 == steps

            # the next step's slabs (the next row's first after this
            # row's last) load under this step's matmuls
            @pl.when(jnp.logical_not(last) | (r + 1 < n_rows))
            def _prefetch():
                dma(jnp.where(last, nxt, r), jnp.where(last, nlo, b0 + chunk),
                    jnp.where(last, nhi, hi), 1 - slot, True)

            dma(r, b0, hi, slot, False)
            k, v = (buf[slot].reshape(hkv, t, buf.shape[-1])
                    for buf in (bufs[0], bufs[-1]))
            if quant:
                # int8 values are exact in the compute dtype; the scales
                # stay outside the dots (masked_attention's rule)
                k, v = k.astype(q.dtype), v.astype(q.dtype)
            s = jnp.einsum("hgd,htd->hgt", q, k, precision=prec,
                           preferred_element_type=jnp.float32)
            if quant:
                s = s * sbufs[0][slot][..., :t]
            s = s * scale
            col = b0 * bs + jax.lax.broadcasted_iota(jnp.int32, (1, 1, t), 2)
            valid = col <= p
            if window > 0:
                valid = valid & (col > p - window)
            s = jnp.where(valid, s, _NEG)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            pr = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + pr.sum(axis=-1, keepdims=True)
            if quant:   # the normalizer l is accumulated UNSCALED above
                pr = pr * sbufs[1][slot][..., :t]
            acc = acc * alpha + jnp.einsum(
                "hgt,htd->hgd", pr.astype(v.dtype), v, precision=prec,
                preferred_element_type=jnp.float32)
            return m_new, l, acc, 1 - slot

        m, l, acc, slot = jax.lax.fori_loop(
            0, steps, step,
            (jnp.full((hkv, g, 1), _NEG, jnp.float32),
             jnp.zeros((hkv, g, 1), jnp.float32),
             jnp.zeros((hkv, g, o_ref.shape[-1]), jnp.float32), slot))
        o_ref[r] = (acc / l).astype(o_ref.dtype)
        return slot

    jax.lax.fori_loop(0, n_rows, row, jnp.int32(0))


def _scale_table(plane, bt, chunk):
    """An int8 pool's scale plane (N, Hkv, bs, 1) read through the
    table into (S, steps, Hkv, 1, chunk * bs rounded up to whole
    lanes): a compute step's scales
    with their positions on the lanes, which is how a score row meets
    them, and with a minor dimension the DMA can address (Mosaic slices
    no HBM operand whose minor dimension is under a lane tile). XLA
    gathers these at the table's width; they are 1/32 of the pool."""
    s, w = bt.shape
    hkv, bs = plane.shape[1:3]
    steps = -(-w // chunk)
    g = plane[jnp.pad(bt, ((0, 0), (0, steps * chunk - w)))][..., 0]
    g = g.reshape(s, steps, chunk, hkv, bs)
    g = jnp.swapaxes(g, 2, 3).reshape(s, steps, hkv, 1, chunk * bs)
    return jnp.pad(g, ((0, 0),) * 4 + ((0, -chunk * bs % _LANES),))


# The widest block table `paged_flash_decode` takes, rows x columns x 4
# bytes. The table is a scalar-prefetch operand and lies whole in the
# chip's scalar memory: 1 MiB on a v5e, which a table of exactly that
# overflows by the kernel's other scalars (compiled for the described
# v5e, PR 37: 64 x 2048 goes, 64 x 4096 does not). Widths double, so
# half of it is the widest that goes.
PAGED_TABLE_BYTES = 512 * 1024


def paged_decode_addresses(pool_blk) -> bool:
    """Whether `paged_flash_decode` can be compiled for this pool.
    Mosaic slices no HBM operand whose minor dimension is not whole
    lanes, so the slab DMA exists for rows of 128, 256, ... values
    (every published head size, and any latent pool, whose rows are
    rounded up to whole lanes); a pool of 64-wide heads keeps the
    gathered read when compiled. Interpreted, every shape goes."""
    return _interpret_default() or all(
        leaf.shape[-1] % _LANES == 0
        for name, leaf in pool_blk.items() if not name.endswith("_s"))


# bytes of pool blocks one compute step holds, all leaves together: the
# olmo shape (128 KB a block) read fastest at 8 blocks a step, mistral
# (64 KB) at 16, the latent pool (20 KB) at 32-64 (chip run, PR 29)
_STEP_BYTES = 1 << 20


@functools.partial(jax.jit, static_argnames=("window", "scale", "chunk",
                                             "interpret"))
def paged_flash_decode(q, pool_blk, bt, pos, *, window: int = 0,
                       scale: float | None = None,
                       chunk: int | None = None,
                       interpret: bool | None = None):
    """Single-token attention through a paged block table, fused.

    q: (S, H, hd) — one query token per slot; pool_blk: one layer's
    pool, {"k"/"v": (N, Hkv, bs, hd)[, "k_s"/"v_s": (N, Hkv, bs, 1)
    f32 scales — int8 pools]} or ONE leaf (N, 1, bs, hd) that is keys
    and values both (the latent pool: `q` is then the absorbed query
    and the result a probability-weighted sum of whole rows); bt:
    (S, W) int32 block tables (padding columns point at the scratch
    block); pos: (S,) int32 — each slot's current position (valid cache
    span is [0, pos], optionally windowed). `scale` multiplies the
    scores (default hd ** -0.5); `chunk` is how many blocks one compute
    step holds (default: `_STEP_BYTES` of them, in eights). Returns
    (S, H, hd) in q's dtype.

    Matches `masked_attention(q, gather_table(pool, bt), valid)`, the
    XLA reference, to fp-reorder noise
    interpreted (<= 1e-4 pinned): same score/softmax path, same
    outside-the-dot int8 scale placement, no gathered copy, and only
    the blocks a row's mask admits are read at all. GQA is native
    (the G query heads of a KV head are the rows of its matmul).

    Jitted in its own right: a program that calls it once a layer
    traces and lowers the kernel once, not once a layer (16 of them
    were 15 s of host time a tick program on the chip's host, which no
    compilation cache holds)."""
    if interpret is None:
        interpret = _interpret_default()
    from jax.experimental.pallas import tpu as pltpu

    s, h, hd = q.shape
    quant = "k_s" in pool_blk
    leaves = (list(pool_blk.values()) if len(pool_blk) == 1
              else [pool_blk["k"], pool_blk["v"]])
    hkv, bs = leaves[0].shape[1:3]
    assert h % hkv == 0, (h, hkv)
    if chunk is None:
        block_bytes = sum(l[0].size * l.dtype.itemsize for l in leaves)
        chunk = max(8, _STEP_BYTES // block_bytes // 8 * 8)
    chunk = max(1, min(bt.shape[1], chunk))
    scales = ([_scale_table(pool_blk[n], bt, chunk) for n in ("k_s", "v_s")]
              if quant else [])
    kernel = functools.partial(
        _paged_decode_kernel, bs=bs, chunk=chunk, window=int(window),
        scale=float(hd ** -0.5 if scale is None else scale),
        n_pools=len(leaves), quant=quant)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[vmem] + [hbm] * (len(leaves) + len(scales)),
        out_specs=vmem,
        scratch_shapes=[pltpu.VMEM((2, hkv, chunk) + l.shape[2:], l.dtype)
                        for l in leaves]
        + [pltpu.VMEM((2,) + t.shape[2:], t.dtype) for t in scales]
        + [pltpu.SemaphoreType.DMA((2,))],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=_sds((s, hkv, h // hkv, leaves[-1].shape[-1]), q.dtype, q),
        interpret=interpret,
    )(bt, pos, q.reshape(s, hkv, h // hkv, hd), *leaves, *scales)
    return out.reshape(s, h, -1)


# ----------------------------------------------------- paged flash prefill
#
# The prefill chunk's read of a K/V pool (`serving/engine._prefill_chunk`):
# C queries at consecutive positions of ONE request, over that request's
# table. The XLA read it replaces gathered the table at the width of the
# WHOLE prompt's bucket, whatever part of it was written, made the row
# head-major and formed (Hkv, G, C, W * bs) float32 scores in HBM: 268 MB
# a layer, three passes, for `mistral-7b-v0.1.doc-batch` (PERF.md, PR 34).
# This kernel is the decode kernel's walk with a tile of queries in the
# place of one: the grid runs over query tiles, each walks the table
# columns its rows can see (from the first block of its first row's
# window to the block of its last true row's position) in double-buffered
# steps of `chunk` blocks, and a step is two matmuls a KV head with the
# head's G query heads folded into the rows (`(G * tq, hd) x (hd, t)`),
# scores and softmax state float32 in VMEM. A tile wholly past `n_tok`
# reads nothing and gives zeros; a padding row inside a live tile repeats
# the last true row (it may not look past what is written).


# What the chip's timings chose (`scripts/bench_paged_prefill.py`; PERF.md,
# PR 34). A step's cost has a part that follows its query rows alone (the
# running max and sum of every row are read, reduced over lanes, spread
# and written back once a head and step), so few large steps beat many
# small ones: at `mistral-7b-v0.1`'s shape and 3,840 live keys a layer
# took 1,164 / 879 / 496 / 320 us at 128 / 256 / 512 / 1,024 keys a step.
_PREFILL_STEP_KEYS = 1024
# query rows of one matmul, the G query heads of a KV head together (a
# head's float32 scores over a step are rows x 4 KB), and query heads x
# queries of a tile (its softmax state is 1.5 KB a head and query, in
# VMEM for the whole tile): the chunk's K/V is read once a tile
_PREFILL_ROWS, _PREFILL_HEAD_ROWS = 2048, 16384
# what the kernel may hold of a v5e's 128 MiB of VMEM (the compiler's own
# limit is 16 MiB): a tile's state, its queries and output twice (the
# grid's pipeline), the two K/V buffers and a head's scores
_PREFILL_VMEM = 64 << 20
# tables of at most this many positions keep the gathered read: at
# `olmo-1b`'s shape (16 heads) XLA scores a table of 128 positions in 36
# us and one of 1,024 in 77, where the kernel takes 50 and 90 (its tile's
# set-up and the two transposes of the queries); past 2,048 positions it
# reads in a quarter to a half of XLA's time at every fill
_PREFILL_MIN_KEYS = 1024


def _paged_prefill_kernel(bt_ref, at_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf,
                          vbuf, m_scr, l_scr, acc_scr, sem, *, scale, bs,
                          chunk, window, tq):
    """One query tile: `q_ref` / `o_ref` (Hkv, G * tq, hd), row g * tq + i
    the tile's i-th query in the KV head's g-th query head; `at_ref` the
    chunk's first position in the table's coordinates and its true
    length; `kbuf` / `vbuf` (2, Hkv, chunk, bs, hd); the running max,
    sum (lane-broadcast) and unnormalised output of every head's rows."""
    from jax.experimental.pallas import tpu as pltpu

    hkv, rows, hd = q_ref.shape
    t = chunk * bs
    unroll = min(chunk, _DMA_UNROLL)
    prec = (None if q_ref.dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)
    at0, n_tok = at_ref[0], at_ref[1]
    row0 = pl.program_id(0) * tq

    def dma(b0, hi, slot, start):
        def one(i, _=None):
            for pool, buf in ((k_hbm, kbuf), (v_hbm, vbuf)):
                cp = pltpu.make_async_copy(pool.at[bt_ref[b0 + i]],
                                           buf.at[slot, :, i], sem.at[slot])
                cp.start() if start else cp.wait()

        def several(j, _):
            for i in range(unroll):
                one(j * unroll + i)

        n = jnp.minimum(chunk, hi - b0)
        groups = n // unroll if unroll > 1 else 0
        jax.lax.fori_loop(0, groups, several, None)
        jax.lax.fori_loop(groups * unroll, n, one, None)

    @pl.when(pl.program_id(0) == 0)
    def _clean():
        # a partial step leaves what the buffer held behind its last
        # slab: masked out of the scores, but 0 * NaN is NaN
        vbuf[...] = jnp.zeros_like(vbuf)

    @pl.when(row0 >= n_tok)
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(row0 < n_tok)
    def _live():
        last = at0 + jnp.minimum(row0 + tq, n_tok) - 1
        first = at0 + row0
        lo = jnp.maximum(first - (window - 1), 0) // bs if window > 0 else 0
        hi = last // bs + 1
        steps = (hi - lo + chunk - 1) // chunk
        # each row's position; padding rows stand on the last true one
        at = jnp.minimum(first + jax.lax.broadcasted_iota(
            jnp.int32, (rows, 1), 0) % tq, last)
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        dma(lo, hi, 0, True)

        def step(c, slot):
            b0 = lo + c * chunk

            @pl.when(c + 1 < steps)
            def _prefetch():
                dma(b0 + chunk, hi, 1 - slot, True)

            dma(b0, hi, slot, False)
            col = b0 * bs + jax.lax.broadcasted_iota(jnp.int32, (1, t), 1)
            valid = col <= at
            if window > 0:
                valid = valid & (col > at - window)

            def head(h, _):
                k = kbuf[slot, h].reshape(t, hd)
                v = vbuf[slot, h].reshape(t, hd)
                s = jax.lax.dot_general(
                    q_ref[h], k, (((1,), (1,)), ((), ())), precision=prec,
                    preferred_element_type=jnp.float32) * scale
                s = jnp.where(valid, s, _NEG)
                m = m_scr[h][:, :1]
                m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
                # a masked score is _NEG and its exp 0 beside any true
                # maximum; a row that has met none yet (its window
                # starts later) sums ones, which the alpha of its first
                # true maximum, exp(_NEG - m), wipes to 0
                pr = jnp.exp(s - m_new)
                alpha = jnp.exp(m - m_new)
                l_scr[h] = jnp.broadcast_to(
                    l_scr[h][:, :1] * alpha + pr.sum(axis=-1, keepdims=True),
                    l_scr.shape[1:])
                m_scr[h] = jnp.broadcast_to(m_new, m_scr.shape[1:])
                acc_scr[h] = acc_scr[h] * alpha + jnp.dot(
                    pr.astype(v.dtype), v, precision=prec,
                    preferred_element_type=jnp.float32)

            jax.lax.fori_loop(0, hkv, head, None)
            return 1 - slot

        jax.lax.fori_loop(0, steps, step, jnp.int32(0))

        def out(h, _):
            o_ref[h] = (acc_scr[h] / l_scr[h][:, :1]).astype(o_ref.dtype)

        jax.lax.fori_loop(0, hkv, out, None)


def paged_prefill_addresses(pool_blk, table_blocks: int) -> bool:
    """Whether the prefill chunk reads this pool, through a table of
    `table_blocks` columns, with `paged_flash_prefill`: a K/V pool in
    the compute dtype whose blocks the slab DMA can address
    (`paged_decode_addresses`), under a table wide enough for the walk
    to beat XLA's read of all of it (`_PREFILL_MIN_KEYS`). The latent
    pool (its chunk read is another contraction), int8 pools and narrow
    tables keep the gathered read."""
    return ("k" in pool_blk and "k_s" not in pool_blk
            and table_blocks * pool_blk["k"].shape[2] > _PREFILL_MIN_KEYS
            and paged_decode_addresses(pool_blk))


@functools.partial(jax.jit, static_argnames=("window", "scale", "chunk",
                                             "tq", "interpret"))
def paged_flash_prefill(q, pool_blk, bt, at0, n_tok, *, window: int = 0,
                        scale: float | None = None,
                        chunk: int | None = None, tq: int | None = None,
                        interpret: bool | None = None):
    """Causal attention of a chunk of queries through ONE request's
    block table, fused.

    q: (C, H, hd), query i at position `at0 + i` of the table's own
    coordinates (a window group's table starts at its request's first
    live block); pool_blk: one layer's {"k", "v": (N, Hkv, bs, hd)};
    bt: (W,) int32, as wide as the prompt will ever need; `n_tok`: the
    chunk's true length (rows past it are padding). Query i sees table
    positions (at0 + i - window, at0 + i] (all of [0, at0 + i] where
    `window` is 0), which the chunk's own keys, written before the
    read, are part of. Only table columns from the first block of the
    first query's window to `(at0 + n_tok - 1) // bs` are read, however
    wide the table. `chunk`: blocks a compute step (default:
    `_PREFILL_STEP_KEYS` positions), `tq`: queries a tile (default:
    what `_PREFILL_ROWS` and `_PREFILL_HEAD_ROWS` allow). Returns
    (C, H, hd) in q's dtype; rows past `n_tok` hold nothing of use.

    Matches `masked_attention(q, gather_table(pool, bt), valid)`, the
    XLA read it took the place of, on the true rows to fp-reorder noise
    interpreted (<= 1e-4, tests/test_paged_prefill.py): the same score
    and softmax arithmetic, float32 in VMEM where that read kept it in
    HBM. Jitted in its own right, as `paged_flash_decode` is."""
    if interpret is None:
        interpret = _interpret_default()
    from jax.experimental.pallas import tpu as pltpu

    c, h, hd = q.shape
    k, v = pool_blk["k"], pool_blk["v"]
    hkv, bs = k.shape[1:3]
    g = h // hkv
    assert h == hkv * g, (h, hkv)
    if chunk is None:
        chunk = max(1, _PREFILL_STEP_KEYS // bs)
    chunk = max(1, min(bt.shape[0], chunk))
    if tq is None:
        tq = max(16, min(_PREFILL_ROWS // g, _PREFILL_HEAD_ROWS // h))
    tq = min(tq, -(-c // 16) * 16)
    nq = -(-c // tq)
    rows = g * tq
    # (C, Hkv, G, hd) -> a tile's rows KV-head-major, query heads inside
    qt = jnp.pad(q, ((0, nq * tq - c), (0, 0), (0, 0)))
    qt = qt.reshape(nq, tq, hkv, g, hd).transpose(2, 0, 3, 1, 4)
    qt = qt.reshape(hkv, nq, rows, hd)
    kernel = functools.partial(
        _paged_prefill_kernel, bs=bs, chunk=chunk, window=int(window),
        scale=float(hd ** -0.5 if scale is None else scale), tq=tq)
    tile = pl.BlockSpec((hkv, None, rows, hd), lambda i, *_: (0, i, 0, 0))
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    f32 = jnp.float32
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(nq,),
        in_specs=[tile, hbm, hbm],
        out_specs=tile,
        scratch_shapes=[pltpu.VMEM((2, hkv, chunk, bs, hd), k.dtype),
                        pltpu.VMEM((2, hkv, chunk, bs, hd), v.dtype),
                        pltpu.VMEM((hkv, rows, _LANES), f32),
                        pltpu.VMEM((hkv, rows, _LANES), f32),
                        pltpu.VMEM((hkv, rows, hd), f32),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=_sds((hkv, nq, rows, hd), q.dtype, q),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_PREFILL_VMEM),
        interpret=interpret,
    )(bt, jnp.stack([at0, n_tok]).astype(jnp.int32), qt, k, v)
    out = out.reshape(hkv, nq, g, tq, hd).transpose(1, 3, 0, 2, 4)
    return out.reshape(nq * tq, h, hd)[:c]
