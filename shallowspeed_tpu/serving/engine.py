"""Continuous-batching decode server over the paged KV cache.

`generate()` serves ONE batch synchronously: every row prefills
together, decodes together, and finishes together — concurrent
requests of different lengths either recompile per shape or block
head-of-line behind the longest. This engine serves a STREAM:

- **Fixed-capacity decode slots.** One compiled decode tick advances
  every running request by one token. The tick's row count is pinned
  to `max_slots` and its block-table width is bucketed
  GEOMETRICALLY in blocks, so requests join and leave the running
  batch between ticks with NO recompiles after warmup — one executable
  per (width bucket), pinned like `test_vm_executables_compile_exactly
  _once`. Empty slots still execute (their cache writes are steered to
  the reserved scratch block) — occupancy is DATA, not shape.
- **Chunked prefill.** Prompts prefill `prefill_chunk` tokens per
  engine step — an 8k prompt admitted mid-run delays in-flight decodes
  by at most one chunk per tick instead of one full prefill. Chunks
  are padded to the fixed chunk length with the true length traced
  (the `prompt_bucket_len` idea at chunk granularity), pad positions
  are never written. The step's decode tick RIDES IN THE CHUNK'S
  PROGRAM (`_prefill_chunk`): a chunk is compute-bound at hundreds of
  rows and a tick bound by the bytes of the same weights, so one
  program a step streams them once and the tick's rows cost their
  cache reads and little else. Whether the tick rides follows from
  what the step holds, a chunk or none; nothing chooses it.
- **Admission + preemption.** A request is admitted when a slot and
  its prompt's blocks are free; a decode append that finds the pool
  empty EVICTS the newest-admitted running request (its blocks free
  immediately, it re-queues at the front and later re-prefills its
  prompt + already-generated tokens, continuing its stream where it
  left off). Evicting the NEWEST — the request that has waited least
  — is what makes the policy livelock-free: the oldest running
  request always progresses, and `submit` rejects requests that
  could never fit alone, so the allocator cannot deadlock.
- **Per-request SLO telemetry.** Every completion stamps a schema-v6
  `"request"` event (ttft_ms, tpot_ms, queue depth, preemptions,
  tokens in/out) into the run's metrics JSONL; periodic `"generate"`
  lines carry tick throughput and the live-blocks HBM sweep
  (`cache.paged_read_bytes_per_tick` — the serving generalization of
  `decode_read_bytes_per_token`).
- **Per-request lifecycle tracing** (round 13). Every request carries
  a phase timeline (submit -> queued -> admitted -> prefill chunk k ->
  decoding -> preempted -> requeued -> finished): each transition
  stamps a schema-v8 `"lifecycle"` event (with the ms spent in the
  previous phase — `report.request_timeline` reconstructs the whole
  accounting) and, under a live tracer, closes the previous phase as
  a span on the request's own NAMED Chrome-trace track, cross-linked
  to the engine tick counter. Fleet views resolve a burning SLO to
  "which request, which phase, which replica" through this.

- **Fast decode path** (round 14, ROADMAP item 1) — three composable
  levers:
  - *Quantized weight storage* (`weight_quant="int8"|"fp8"`): the
    params tree is quantized ONCE at init (`T.quantize_weights`) into
    int8/fp8-e4m3 matrices + per-out-channel f32 scales; every dense
    in the tick runs the fused-dequant matmul
    (`ops.matmul.dequant_matmul` — scale on the f32 accumulator,
    never a materialized dequantized copy; proved by the analysis
    `dequant-fusion` rule over this very tick). The params term of
    `paged_read_bytes_per_tick` shrinks to ~0.5x bf16.
  - *Paged flash-decode kernel*: the tick's attention, for K/V pools
    and the latent pool alike, runs
    `ops.flash_attention.paged_flash_decode` — one program that walks
    each row's live blocks through the table (scalar prefetch, manual
    DMA from the pool in HBM), online softmax across a row's blocks,
    int8 KV + scales read natively. No gathered table is built,
    whatever the bucket's width. The prefill chunk reads a K/V pool
    the same way where its table is wide (`paged_flash_prefill`: a
    tile of queries in the place of one, the table walked only as far
    as the chunk's last true position); `gather_table` +
    `masked_attention` are the reference both kernels are pinned
    against (<= 1e-4) and the read of the pools and tables they do not
    take. `attn_impl` selects nothing.
  - *Speculative decoding* (`spec_k > 0`): a self-drafting n-gram
    prompt-lookup proposer (`_propose`) fills FREE rows of the
    fixed-capacity tick with up to K draft tokens per decoding
    request at consecutive positions; the same compiled tick verifies
    them all in one pass (each row's mask admits the rows before it —
    the in-tick writes land before any read). Acceptance is the
    deterministic accept/resample rule specialized to a point-mass
    (deterministic) draft distribution under a counter-based sampler:
    every emitted token IS the oracle draw `sample(fold_in(
    PRNGKey(seed), i), logits_i)` at its own index — row j's logits
    are the true next-token logits whenever all earlier drafts
    matched their oracle draws — so the output stream is
    TOKEN-IDENTICAL to solo `generate()` at every temperature, not
    merely distribution-equal. Rejected rows' cache writes sit beyond
    the request's advanced position and are overwritten before any
    mask can admit them (the prefill-padding argument). Zero new
    executables: drafts are data in rows that already executed empty.

- **One program in flight.** The decode loop dispatches program N+1
  before it fetches program N's tokens (`ServingEngine._decode_step`):
  the host knows N+1's rows without them, each row's input token is
  read from N's output on the device, and the fetch and the
  bookkeeping of N run while the device runs N+1. A prompt's first
  token travels the same way: its last chunk's program samples it into
  the request's own row of `nxt`, the next tick reads it from there,
  and the host learns it at the landing, one step after the dispatch.
  No fetch blocks before a step's dispatch. The host's view trails the
  device by one program; the tokens are the same.

- **A second kind of state** (`cfg.mixer`). A block with a state-space
  mixer beside its attention heads keeps, next to its K/V pool, one
  row of state a SLOT (`serving/cache.py`: the slabs), rewritten whole
  at every token. The tick advances the rows that decode and writes
  every other row back as it was: a tick runs between two chunks of
  one prompt, and one tick more runs on a slot whose request has just
  finished, so a row that is not decoding must leave its slab alone.
  A prefill chunk starts from zeros at position 0 and from its slot's
  row otherwise, and leaves the state after its last true token. An
  evicted request re-prefills prompt + generated, which rebuilds its
  state. A prefix-cache hit would need a snapshot of the state at the
  matched block and a rejected draft a roll-back: `prefix_cache` and
  `spec_k` are refused for such a model.

Stream parity: sampling uses the SAME per-request key schedule as
`generate()` — token i of a request with sampling seed s draws from
`fold_in(PRNGKey(s), i)` — and the paged attention computes what
`kv_cache.masked_attention` computes on the contiguous path (the
tick's kernel and the chunk's are pinned to it), so
each request's stream reproduces its solo `generate()` stream
token-for-token (pinned in tests/test_serving.py; see `generate`'s
stream-stability contract for the ~1e-6 numerics caveat).
"""

from __future__ import annotations

import time
import weakref
from collections import deque
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from shallowspeed_tpu import chaos
from shallowspeed_tpu.models import generate as G
from shallowspeed_tpu.ops.flash_attention import (PAGED_TABLE_BYTES,
                                                   paged_decode_addresses,
                                                   paged_flash_decode,
                                                   paged_flash_prefill,
                                                   paged_prefill_addresses)
from shallowspeed_tpu.telemetry.trace import spanned, tracer
from shallowspeed_tpu.telemetry.tracing import new_span_id, new_trace_id
from shallowspeed_tpu.models import transformer as T
from shallowspeed_tpu.models.kv_cache import masked_attention, position_mask
from shallowspeed_tpu.ops.latent_attention import (absorb_query,
                                                   latent_attention_absorbed,
                                                   unabsorb_output)
from shallowspeed_tpu.serving.cache import (LATENT, SCRATCH_BLOCK,
                                            BlockAllocator, OutOfBlocks,
                                            PrefixIndex, blocks_for,
                                            chunk_hashes, gather_table,
                                            group_blocks, group_of_layer,
                                            init_block_pool, kv_leaves,
                                            layer_groups,
                                            paged_read_bytes_per_tick,
                                            param_read_bytes,
                                            pool_block_size, state_leaves,
                                            state_row_bytes, write_chunk,
                                            write_rows)


# finished-request timelines the engine retains in memory for
# in-process consumers (bench phase accounting, tests); older entries
# evict FIFO — the metrics JSONL carries the complete lifecycle stream
TIMELINE_CAP = 1024


class EngineDraining(RuntimeError):
    """`submit()` after `drain()` began.

    A draining replica finishes the work it already accepted and
    admits nothing new — the typed rejection (instead of the old
    implicit behavior: queued-forever under load shedding, silent
    acceptance after a drain request) is what lets a fleet router
    re-route the request instead of wedging it on a replica that is
    about to deregister. `pending` carries the in-flight count so the
    caller can size its retry-after."""

    def __init__(self, pending: int):
        super().__init__(
            f"engine is draining ({pending} accepted request(s) still "
            f"in flight); submit to another replica")
        self.pending = int(pending)


def table_width(n_blocks: int, base: int) -> int:
    """Geometric block-table width bucket (base, 2*base, 4*base, ...):
    the compile key for the gathered reads. Linear bucketing would
    compile O(prompt/bucket) executables as a long prompt's table
    grows; geometric pins the executable count at O(log) — the
    serving analog of `prompt_bucket_len`."""
    w = max(1, int(base))
    n = max(1, int(n_blocks))
    while w < n:
        w *= 2
    return w


def _rope_rows(x, pos, theta: float):
    """`T.rope_rotate` with a PER-ROW position: x (S, 1, H, D), pos
    (S,) — each slot decodes at its own global position. Swapping the
    row axis into rope_rotate's sequence axis reuses the ONE rotary
    implementation (same half-split math and f32 phases), so a row's
    values equal the contiguous path's at the same position by
    construction."""
    return jnp.swapaxes(
        T.rope_rotate(jnp.swapaxes(x, 0, 1), pos, theta), 0, 1)


def _sample_rows(logits, temp, seeds, idx, top_k: int, top_p: float):
    """Row-wise `generate._sample`: per-row temperature and sampling
    key (`fold_in(PRNGKey(seed), idx)` — `idx` is the request's token
    index, so a slot's draws equal its solo `generate()` draws).
    temp == 0 rows take the greedy argmax; top_k/top_p are engine-wide
    statics (lax.top_k needs a static k)."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    l = G.filter_logits(logits / jnp.maximum(temp, 1e-6)[:, None],
                        top_k, top_p)

    def draw(seed, i, row):
        key = jax.random.fold_in(jax.random.PRNGKey(seed), i)
        return jax.random.categorical(key, row, axis=-1)

    sampled = jax.vmap(draw)(seeds, idx, l).astype(jnp.int32)
    return jnp.where(temp <= 0.0, greedy, sampled)


def _latent_read(p, pool, bt, q_nope, q_rope, valid, cfg):
    """A latent layer's attention over its gathered table (the prefill
    chunk's read of a latent pool; the tick's is `_latent_decode`),
    absorbed:
    the (rows, W, 1, bs, r + dr) pages ARE (rows, W*bs, r + dr) latent
    rows in position order (one shared "head": nothing to make
    head-major), and every query head contracts with them as stored."""
    g = pool[LATENT][bt]
    rows = g.reshape(g.shape[0], -1, g.shape[-1])
    return latent_attention_absorbed(q_nope, q_rope, rows, p["kv_b"],
                                     valid, T.latent_scale(cfg))


def _latent_decode(p, pool, bt, pos, q_nope, q_rope, cfg):
    """The tick's read of a latent layer: absorb the query, walk each
    row's live blocks with the paged kernel (one shared "head", every
    query head a row of its matmuls, the row read once as key and
    value), un-absorb. q_nope/q_rope: (S, 1, H, .)."""
    qx = absorb_query(q_nope[:, 0], q_rope[:, 0], p["kv_b"],
                      pool[LATENT].shape[-1])
    oc = paged_flash_decode(qx, pool, bt, pos, scale=T.latent_scale(cfg))
    return unabsorb_output(oc, p["kv_b"], q_nope.shape[-1]
                           ).astype(q_nope.dtype)


def _ffn_counted(p, x, cfg, h, lives):
    """`T._ffn` for the tick and the chunk. A routed block also gives,
    for each mask of `lives`, the (E,) int32 count of the assignments
    the rows it marks made (a mask has h's leading shape; padding rows
    and empty slots run the layer too, their choices are not the
    traffic's, and a chunk's rows are counted apart from the rows of a
    tick that rides with it); else None."""
    if "experts" not in p:
        return T._ffn(p, x, cfg, h)[0], None
    y, idx = T.routed_ffn(p, h, cfg)
    hot = jax.nn.one_hot(idx, cfg.n_routed_experts, dtype=jnp.int32)
    return (T.ffn_residual(p, x, y, cfg),
            [(hot * live[..., None, None]).sum((0, 1, 2)) for live in lives])


def _group_tables(bt, base, pos):
    """What each layer group addresses its cache by, [(table, position
    in the table's own coordinates)]: `bt` is one table a group (a lone
    array: one group), `base[g]` the position of the first token of
    table g's first entry (None: every table starts at position 0).
    A full group's base is 0; a window group's table starts at the
    first block its request still holds, and relative to that
    everything is as in a full one."""
    bts = tuple(bt) if isinstance(bt, (tuple, list)) else (bt,)
    return [(b, pos if base is None else pos - base[g])
            for g, b in enumerate(bts)]


def _project(p, h, pool, rotary, rope, cfg):
    """A layer's projections of its norm output h (B, T, d), rotated at
    the rows' own positions (`rope`): a latent layer's (q_nope, q_rope,
    c, k_rope), else (q, k, v). Which follows from the pool."""
    if LATENT in pool:
        return T.latent_qkv(p, h, cfg, rope)
    q, k, v = T._qkv(p, h, cfg)
    if rotary:
        q, k = rope(q), rope(k)
    return q, k, v


def _rows_tick(bt, base, pos, bs):
    """The tick's rows' addresses: for each layer group (table,
    position in the table's coordinates, the block each row writes),
    the offset in that block (a base is a whole number of blocks), and
    which rows are live (S,): a row that holds nothing has a table of
    scratch."""
    rows = jnp.arange(pos.shape[0])
    tables = [(b, at, b[rows, at // bs])
              for b, at in _group_tables(bt, base, pos)]
    return tables, pos % bs, tables[0][0][:, 0] != SCRATCH_BLOCK


def _tick_attend(p, pool, proj, table, off, window, cfg):
    """The tick's side of a layer's cache: every row's new entry
    written at (its block, `off`), then each row's read of the blocks
    its position (and `window`) admit, where they lie
    (`paged_flash_decode`, `_latent_decode`). `proj`: `_project` of the
    rows, (S, 1, ...) each; `table`: the layer's group of `_rows_tick`.
    Returns (the pool's leaves as written, the heads' output (S, H * hd))."""
    bt_g, at, blk = table
    if LATENT in pool:
        qn, qr, c, kr = proj
        pool = write_rows(pool, c, kr, blk, off, False)
        a = _latent_decode(p, pool, bt_g, at, qn, qr, cfg)
    else:
        q, k, v = proj
        pool = {**pool, **write_rows(pool, k[:, 0], v[:, 0], blk, off,
                                     "k_s" in pool)}
        # heads that are not whole lanes wide (no published size; toy
        # configurations on the chip) are beyond the kernel's DMA when
        # compiled and keep the gathered read
        if paged_decode_addresses(pool):
            a = paged_flash_decode(q[:, 0], pool, bt_g, at, window=window)
        else:
            valid = position_mask(bt_g.shape[1] * pool_block_size(pool),
                                  at[:, None], window)
            a = masked_attention(q, gather_table(pool, bt_g),
                                 valid[:, None, None, None, :], cfg)
    return pool, a.reshape(a.shape[0], -1)


def _chunk_attend(p, pool, proj, table, n_tok, window, cfg):
    """The chunk's side of a layer's cache: its `n_tok` true rows
    written through the request's table (`write_chunk`), then the
    causal read over the table, earlier chunks included: where it lies
    (`paged_flash_prefill`) or gathered at the table's width under the
    position mask, as `paged_prefill_addresses` says. `proj`:
    `_project` of the chunk, (1, C, ...) each; `table`: (the (1, W)
    table, the chunk's first position in its coordinates). Returns
    (the pool's leaves as written, the heads' output (1, C, H * hd))."""
    bt_g, at0 = table
    c = proj[0].shape[1]

    def valid():                        # the gathered reads' (C, W * bs)
        return position_mask(bt_g.shape[1] * pool_block_size(pool),
                             (at0 + jnp.arange(c))[:, None], window)

    if LATENT in pool:
        qn, qr, lat, kr = proj
        pool = write_chunk(pool, lat[0][:, None], kr[0][:, None],
                           bt_g[0], at0, n_tok, False)
        a = _latent_read(p, pool, bt_g, qn, qr, valid()[None, None], cfg)
    else:
        q, k, v = proj
        pool = {**pool, **write_chunk(pool, k[0], v[0], bt_g[0], at0,
                                      n_tok, "k_s" in pool)}
        if paged_prefill_addresses(pool, bt_g.shape[1]):
            a = paged_flash_prefill(q[0], pool, bt_g[0], at0, n_tok,
                                    window=window)
        else:
            a = masked_attention(q, gather_table(pool, bt_g),
                                 valid()[None, None, None], cfg)
    return pool, a.reshape(1, c, -1)


def _advance_rows(slabs, left, live):
    """A tick's write of a mixer's slabs: the row is the slot, a live
    row takes the state its token left and a row that does not decode
    (an empty slot, a prompt between two chunks, a request just
    finished) keeps what its row held. One elementwise pass over the
    whole slab, which XLA:TPU fuses with the readout's reduction and
    runs on the donated buffer: a scatter of the new rows (to a
    scratch row for the others) has to be handed them in HBM first,
    two passes more."""
    return {n: jnp.where(live.reshape((-1,) + (1,) * (slab.ndim - 1)),
                         left[n], slab) for n, slab in slabs.items()}


# A block of either program is a jitted function, inlined where it is
# called: a model's layers of one kind are traced to a jaxpr ONCE a
# program, and the loop re-binds that jaxpr's equations a layer where,
# written out, it ran the model's Python a layer. That is host time of
# every program's set-up, which no compilation cache holds (the chip's
# host needs ~1 s to trace and lower a 16-layer tick), and it is what
# keeps a chunk's program, which carries the tick's rows too, as quick
# to set up as the chunk alone was. `inline=True` because a real call
# a layer costs device time: XLA:TPU does not fuse across it (the
# matmul that ends a block with the next block's norm), and a tick of
# `olmo-1b` took 4.16 ms where it takes 3.97 (PERF.md, PR 37). Inlined,
# the programs are the loop's own, operation for operation.


@partial(jax.jit, static_argnames=("cfg", "window", "rotary"),
         inline=True)
def _tick_layer(p, pool, x, pos, table, off, live, *, cfg, window, rotary):
    """One block of `_decode_tick` on the rows' x (S, 1, d): `table`
    is the layer's group of `_rows_tick`, `live` (S, 1). Returns (x,
    the layer's pool as written, the routed counts or None)."""
    pool, slabs = kv_leaves(pool), state_leaves(pool)
    rope = lambda u: _rope_rows(u, pos, cfg.rope_theta)
    h = T._norm(p["ln1"], x, cfg)
    pool, a = _tick_attend(p, pool, _project(p, h, pool, rotary, rope, cfg),
                           table, off, window, cfg)
    x = T.attn_residual(p, x, a[:, None], h, cfg)
    if slabs:
        y, left = T.mixer(p["mixer"], h, cfg, slabs)
        x = x + y
        pool = {**pool, **_advance_rows(slabs, left, live)}
    x, n = _ffn_counted(p, x, cfg, T._norm(p["ln2"], x, cfg), (live,))
    return x, pool, n


@partial(jax.jit, static_argnames=("cfg", "top_k", "top_p"),
         donate_argnums=(1,))
def _decode_tick(params, pools, tok, pos, bt, temp, seeds, idx, prev,
                 ahead, base=None, *, cfg: T.TransformerConfig, top_k: int,
                 top_p: float):
    """One compiled decode tick over the whole slot batch: the program
    of a step that holds no prefill chunk (in a step that holds one the
    same rows ride in the chunk's program, `_prefill_chunk`).

    tok/pos/temp/seeds/idx: (S,) per-slot last token, write position,
    sampling state; bt: (S, W) block tables (W is the bucketed width —
    the ONLY shape that varies across ticks), one a layer group
    (`cache.layer_groups`; a tuple of them, or the one table of a model
    with one group), and `base` (groups, S) where a group's tables do
    not start at position 0 (`_group_tables`). The decode loop keeps
    one program in flight (`ServingEngine._decode_step`), so the host
    may not have a row's last token yet: `prev` is the previous
    program's `nxt` as it left that program (a device array the host
    never fetched for this), and a row whose `ahead` flag is set reads
    `prev[row]` where the others read the host's `tok[row]` (every row
    when nothing is in flight). A request keeps its slot, so the row
    is the same in both programs: a decoding row's last token, and the
    first token of a request whose prompt's last chunk was the program
    before. Each slot writes its
    token's K/V at (bt[pos // bs], pos % bs) and attends over the
    blocks of its table that its position (and window) admit, read
    from the pool where they lie (`paged_flash_decode`: no gathered
    table, whatever W is; `paged_decode_addresses` says which pools
    that excludes when compiled); inactive slots carry pos=0 /
    bt=scratch, cost one block and their results are ignored host-side.
    A latent layer writes its one latent row instead and reads the
    same way, absorbed (`_latent_decode`); which a layer is follows
    from its params and its pool, not from an option.
    Returns (next token per slot, updated pools, and the routed layers'
    (layers, E) int32 assignment counts of the live rows, None for a
    model without routed layers). The pools are
    DONATED and every write to them is indexed on the leading
    dimension alone (`write_rows`' flat view here, whole blocks in
    `_prefill_chunk`), which is what lets XLA:TPU update the donated
    buffers in place: a scatter indexed on dimensions 0 and 2 wants a
    layout the parameter does not have, and paid two pool-sized
    copies per leaf per run for it
    (tests/test_tpu_compile.py holds the compiled programs to this).

    A layer with a mixer (`cfg.mixer`) also carries its slabs in its
    pool's dict, one row a slot: the tick's row IS the slot, every row
    is advanced, and a row that is not live keeps what it held
    (`_advance_rows`).

    Draft rows (speculative decoding) are ordinary rows at consecutive
    positions of a shared table: the pool write happens before the
    read, so row j's attention sees rows i < j of the same tick — the
    single-pass verify."""
    params = T.cast_params(params, cfg.compute_dtype)
    tok = jnp.where(ahead, prev, tok)
    x = T.embed_tokens(params, tok, cfg)[:, None, :]        # (S, 1, d)
    if not cfg.rope:
        x = x + params["pos_emb"][pos][:, None, :]
    if cfg.compute_dtype is not None:
        x = x.astype(cfg.compute_dtype)
    tables, off, live = _rows_tick(bt, base, pos, pool_block_size(pools[0]))
    live = live[:, None]                                    # (S, 1)
    new_pools, counts = [], []
    for p, pool, (window, rotary), g in zip(
            params["blocks"], pools, cfg.layer_specs, group_of_layer(cfg)):
        x, pool, n = _tick_layer(p, pool, x, pos, tables[g], off, live,
                                 cfg=cfg, window=window, rotary=rotary)
        if n is not None:
            counts += n
        new_pools.append(pool)
    x = T._norm(params["ln_f"], x, cfg)
    logits = T.head_logits(params, x[:, 0], cfg).astype(jnp.float32)
    nxt = _sample_rows(logits, temp, seeds, idx, top_k, top_p)
    return nxt, new_pools, jnp.stack(counts) if counts else None


@partial(jax.jit, static_argnames=("cfg", "window", "rotary"),
         inline=True)
def _chunk_layer(p, pool, x, pos, table, n_tok, pos0, slot, tick, lives, *,
                 cfg, window, rotary):
    """One block of `_prefill_chunk` on x (1, C + S, d), the chunk's C
    rows and then the S rows of the tick that rides with it (S = 0 and
    `tick` None: the chunk alone). `pos`: every row's position;
    `table`: the layer's group of `_group_tables`; `tick`: (the
    layer's group of `_rows_tick`, the rows' offsets, which are live
    (S,)); `lives`: the masks the routed counts are taken under, (1,
    C + S) each. Returns (x, the layer's pool as written, the routed
    counts, one a mask, or None)."""
    c = x.shape[1] - (0 if tick is None else tick[1].shape[0])
    pool, slabs = kv_leaves(pool), state_leaves(pool)
    rope = lambda u: T.rope_rotate(u, pos, cfg.rope_theta)
    # the tick's rows of a (1, C + S, ...) array as the tick has them
    ride = lambda u: jnp.swapaxes(u[:, c:], 0, 1)           # (S, 1, ...)
    h = T._norm(p["ln1"], x, cfg)
    proj = _project(p, h, pool, rotary, rope, cfg)
    pool, a = _chunk_attend(p, pool, [u[:, :c] for u in proj], table, n_tok,
                            window, cfg)
    if tick is not None:
        t_table, off, t_live = tick
        pool, at = _tick_attend(p, pool, [ride(u) for u in proj], t_table,
                                off, window, cfg)
        a = jnp.concatenate([a, at[None]], 1)
    x = T.attn_residual(p, x, a, h, cfg)
    if slabs:
        y, left = T.mixer(
            p["mixer"], h[:, :c], cfg,
            {n: jnp.where(pos0 == 0, 0, slab[slot][None]).astype(slab.dtype)
             for n, slab in slabs.items()}, n_tok)
        slabs = {n: slab.at[slot].set(left[n][0])
                 for n, slab in slabs.items()}
        if tick is not None:
            yt, left = T.mixer(p["mixer"], ride(h), cfg, slabs)
            y = jnp.concatenate([y, jnp.swapaxes(yt, 0, 1)], 1)
            slabs = _advance_rows(slabs, left, t_live)
        x = x + y
        pool = {**pool, **slabs}
    x, n = _ffn_counted(p, x, cfg, T._norm(p["ln2"], x, cfg), lives)
    return x, pool, n


@partial(jax.jit, static_argnames=("cfg", "top_k", "top_p"),
         donate_argnums=(1,))
def _prefill_chunk(params, pools, tokens, pos0, n_tok, bt, cow_src,
                   cow_dst, base=None, slot=None, tick=None, *,
                   cfg: T.TransformerConfig, top_k: int = 0,
                   top_p: float = 0.0):
    """One chunk of a request's prefill and, riding in the same
    program, the step's decode tick: the program of a step that holds
    a chunk. Everything row-wise (the norms, the projections, the FFN,
    the experts) runs ONCE over the chunk's C rows and the tick's S
    rows together, so a step streams each weight once; everything that
    addresses a cache keeps the two reads and the two writes the two
    programs have (`_chunk_attend`, `_tick_attend`). The chunk is
    compute-bound at hundreds of rows and the tick bound by the same
    weights' bytes, so the rows that ride cost their cache reads and
    little else.

    tokens (1, C) — C is the
    fixed chunk length, `n_tok` the traced true count (the tail is
    padding: never written, and masked out of every true row's read).
    Writes the chunk's K/V through the block table (`write_chunk`: the
    few blocks its consecutive positions touch, merged and written
    back whole, in place) and attends causally over the table
    (earlier chunks included). `bt` is one (1, W) table a layer group
    and `base` (groups,) where a group's table does not start at
    position 0, as `_decode_tick` takes them; `cow_src` / `cow_dst`
    likewise one pair a group. A K/V pool under a wide table is read
    where it lies (`paged_flash_prefill`: the table's columns from the
    first query's window to the chunk's last true position, however
    wide the table and however long the prompt;
    `paged_prefill_addresses` says which pools and tables); a latent
    pool, an int8 pool and a table of a thousand positions or fewer
    are gathered at the table's width and scored under the position
    mask, which is the quicker read there.

    `tick` is `_decode_tick`'s per-row arguments in its own order
    (tok, pos, bt, temp, seeds, idx, prev, ahead, base), which the
    engine always passes: rows that decode are advanced as the tick
    advances them, a row that holds nothing (all of them in a step in
    which nobody decodes) reads one block. The prefilling request's own
    row `slot` is such a row, and carries the chunk's sample: the
    chunk's last true position takes that row's place under the head,
    sampled with `temp[slot]`, `seeds[slot]`, `idx[slot]`, so
    `nxt[slot]` is the request's next token (token index
    len(generated), exactly like `generate()`'s post-prefill sample)
    where this chunk is its prompt's last, and means nothing before.
    The tick's table is as wide as the engine says, ONE width whatever
    the rows hold (`ServingEngine._ride_blocks`), so the programs stay
    one a chunk-table width. Returns (`nxt` (S,), the updated, donated
    pools, the routed layers' (layers, E) assignment counts of the
    chunk's true rows, those of the tick's live rows; None for a model
    without routed layers).

    Without `tick` (callers that lower or run the chunk alone) the
    program is the chunk's part and returns (f32 logits at the chunk's
    last true position, the pools, the chunk's counts).

    PREFIX-CACHE ALIGNMENT CONTRACT: cache hits are granular to WHOLE
    blocks — `pos0` on a hit is the matched aligned token count, so the
    partial tail (and on a fully-aligned match, the final token of the
    copied tail block) always re-prefills through here; the engine
    never trusts a partially-filled shared block. `cow_src`/`cow_dst`
    are the copy-on-write pair: before any write, every pool leaf
    copies block `cow_src` into block `cow_dst` (one block per layer),
    so a request that diverges inside an otherwise-shared tail block
    writes its OWN copy and the shared block stays bit-unchanged.
    Cache-off and already-diverged calls pass scratch for both — a
    scratch->scratch self-copy that is a no-op by construction (nothing
    reads scratch). Riding the copy inside this one jitted program (as
    data, every call) keeps `executable_counts()` flat: cache hits
    change block-table *data*, never the compiled-program set.

    `slot` is also the request's row of a mixer's slabs: the chunk
    starts from zeros where `pos0` is 0 and from that row otherwise,
    and writes the state after its `n_tok` true rows. A mixer keeps
    its two calls here, the chunk's scan and the tick's one-step
    update, each with its own read of the mixer's projections: no cell
    prefills a hybrid model inside its window (PERF.md §7h), so sharing
    them is not worth the code now. The prefilling request's row is
    not live in the tick, so the two writes never meet."""
    params = T.cast_params(params, cfg.compute_dtype)
    c = tokens.shape[1]
    bs = pool_block_size(pools[0])
    group = group_of_layer(cfg)
    cow_src, cow_dst = jnp.atleast_1d(cow_src), jnp.atleast_1d(cow_dst)
    pools = [{**pool, **{name: leaf.at[cow_dst[g]].set(leaf[cow_src[g]])
                         for name, leaf in kv_leaves(pool).items()}}
             for pool, g in zip(pools, group)]
    pos = pos0 + jnp.arange(c)
    x = G._embed(params, tokens, pos0, cfg)                  # (1, C, d)
    tables = _group_tables(bt, base, pos0)
    lives = [jnp.arange(c) < n_tok]
    if tick is not None:
        tok, t_pos, t_bt, temp, seeds, idx, prev, ahead, t_base = tick
        xt = T.embed_tokens(params, jnp.where(ahead, prev, tok), cfg)
        if not cfg.rope:
            xt = xt + params["pos_emb"][t_pos]
        x = jnp.concatenate([x, xt.astype(x.dtype)[None]], 1)  # (1, C+S, d)
        t_tables, off, t_live = _rows_tick(t_bt, t_base, t_pos, bs)
        pos = jnp.concatenate([pos, t_pos])
        # each side's rows among all of them, for the routed counts
        lives = [jnp.pad(lives[0], (0, t_live.shape[0])),
                 jnp.pad(t_live, (c, 0))]
    lives = [live[None, :] for live in lives]               # (1, rows)
    new_pools, counts = [], []
    for p, pool, (window, rotary), g in zip(
            params["blocks"], pools, cfg.layer_specs, group):
        x, pool, n = _chunk_layer(
            p, pool, x, pos, tables[g], n_tok, pos0, slot,
            None if tick is None else (t_tables[g], off, t_live), lives,
            cfg=cfg, window=window, rotary=rotary)
        if n is not None:
            counts.append(n)
        new_pools.append(pool)
    # a side's (layers, E): the chunk's counts, then the tick's
    counts = [jnp.stack(side) for side in zip(*counts)] \
        or [None] * len(lives)
    last = jax.lax.dynamic_index_in_dim(x, n_tok - 1, 1, False)   # (1, d)
    if tick is None:
        logits = T.head_logits(params, T._norm(params["ln_f"], last, cfg),
                               cfg).astype(jnp.float32)
        return logits, new_pools, counts[0]
    x = T._norm(params["ln_f"], x[0, c:].at[slot].set(last[0]), cfg)
    logits = T.head_logits(params, x, cfg).astype(jnp.float32)
    nxt = _sample_rows(logits, temp, seeds, idx, top_k, top_p)
    return nxt, new_pools, *counts


def clear_program_caches() -> None:
    """Forget every traced serving program, the blocks' own traces
    included: for a test that swaps a kernel for its reference, or the
    interpreter for the compiler, under programs already traced."""
    for program in (_decode_tick, _prefill_chunk, _tick_layer, _chunk_layer):
        program.clear_cache()


class _Req:
    """Host-side request state (never crosses into a trace)."""

    __slots__ = ("rid", "prompt", "max_new", "temp", "seed", "arrival",
                 "generated", "n_preempt", "phase", "slot", "ctx",
                 "tables", "base", "written", "admit_seq", "admit_t",
                 "queued_at", "wait_s", "first_tok_t", "last_tok",
                 "in_flight", "timeline", "track", "trace_t0", "n_drafted",
                 "n_accepted", "ctx_ids", "spec_idx",
                 "trace", "span", "parent", "attempt",
                 "hit_blocks", "skipped_tok", "cow")

    def __init__(self, rid, prompt, max_new, temp, seed, arrival):
        self.rid = rid
        self.prompt = prompt
        self.max_new = int(max_new)
        self.temp = float(temp)
        self.seed = int(seed)
        self.arrival = arrival
        self.generated: list[int] = []
        self.n_preempt = 0
        self.phase = "queued"           # queued -> prefill -> decode
        self.slot = None
        self.ctx = prompt               # prompt (+ generated on requeue)
        # one block table a layer group (`cache.layer_groups`), and for
        # each the blocks already released off its front: table g's
        # first entry holds positions from base[g] * block_size on
        self.tables: list[list[int]] = []
        self.base: list[int] = []
        self.written = 0                # cache positions filled
        self.admit_seq = -1
        self.admit_t = None
        self.queued_at = arrival        # start of the CURRENT queue stint
        self.wait_s = 0.0               # queue time over every stint
        self.first_tok_t = None
        self.last_tok = 0
        # tokens of this request still on the device: 1 while the
        # program in flight holds a row of it, or its prompt's last
        # chunk, whose token the host has not fetched yet
        # (`ServingEngine._decode_step`), else 0. The position that
        # program wrote last is booked in `written` with the token
        self.in_flight = 0
        # lifecycle tracing (schema v8): the host-side phase timeline,
        # plus this request's named Chrome-trace track
        self.timeline: list[dict] = []
        self.track = None
        self.trace_t0 = None
        # speculative decoding (schema v9): drafted/accepted tallies
        # + the lazily-built n-gram occurrence index (`_spec_state`)
        self.n_drafted = 0
        self.n_accepted = 0
        self.ctx_ids = None
        self.spec_idx = None
        # trace context (schema v11, telemetry/tracing.py): trace id
        # propagated from the fleet router (or minted here for
        # standalone serving), this engine attempt's own span id, the
        # upstream dispatch span, and the 0-based cross-engine
        # dispatch attempt counter
        self.trace = None
        self.span = None
        self.parent = None
        self.attempt = 0
        # prefix caching (schema v14): blocks mapped from the shared
        # index across every admission stint, prefill tokens those
        # mappings skipped, and the pending (src, dst) copy-on-write
        # pair the first prefill chunk after a fully-aligned hit
        # resolves (None otherwise)
        self.hit_blocks = 0
        self.skipped_tok = 0
        self.cow = None

    @property
    def table(self) -> list[int]:
        """The first group's table (the only one, in a model with one
        kind of layer)."""
        return self.tables[0] if self.tables else []


class ServingEngine:
    """Paged-cache continuous-batching decode server (module
    docstring). `submit`/`poll`/`step`/`run` are the programmatic API
    `serve.py` drives; `metrics` (a `metrics.MetricsLogger`) receives
    the schema-v6 `"request"` events and periodic `"generate"` tick
    lines."""

    @spanned("build", engine="ServingEngine")
    def __init__(self, params, cfg: T.TransformerConfig, *,
                 n_blocks=64, block_size: int = 16,
                 max_slots: int = 4, prefill_chunk: int = 32,
                 table_bucket: int = 4, kv_quant: str = "",
                 weight_quant: str = "", attn_impl: str = "gather",
                 spec_k: int = 0, spec_ngram: int = 3,
                 top_k: int = 0, top_p: float = 0.0, metrics=None,
                 log_every: int = 0, clock=time.time,
                 lifecycle: bool = True, chaos_plan=None,
                 prefix_cache: bool = False):
        # `attn_impl` selects nothing any more: the tick reads every
        # pool through the paged kernel, and the chunk the K/V pools
        # under wide tables (`paged_prefill_addresses`). The two names
        # are still accepted because the benchmark's drivers and
        # `serve.py` pass one (ROADMAP D1).
        if attn_impl not in ("gather", "flash"):
            raise ValueError(
                f"unsupported attn_impl={attn_impl!r}; expected "
                f"'gather' or 'flash' (both name the same programs)")
        if cfg.mixer and (prefix_cache or spec_k > 0):
            # a hit would need a snapshot of the mixer's state at the
            # matched block, a rejected draft a roll-back of it: neither
            # exists (ROADMAP R4)
            raise ValueError(
                "a model with a state-space mixer keeps one state a slot "
                "and no snapshot of it: prefix_cache and spec_k > 0 are "
                f"not served for it (got prefix_cache={prefix_cache}, "
                f"spec_k={spec_k})")
        # quantize ONCE at init (host-side, idempotent): every tick
        # then reads 1-byte weights through the fused-dequant matmul
        self.params = T.quantize_weights(params, weight_quant)
        self.weight_quant = weight_quant
        # speculative decoding: K draft tokens per decoding request per
        # tick, drafted by the n-gram prompt-lookup proposer
        self.spec_k = int(spec_k)
        self.spec_ngram = int(spec_ngram)
        self.cfg = cfg
        self.block_size = int(block_size)
        self.max_slots = int(max_slots)
        self.prefill_chunk = int(prefill_chunk)
        self.table_bucket = int(table_bucket)
        self.kv_quant = kv_quant
        self.top_k = int(top_k)
        self.top_p = float(top_p)
        self.metrics = metrics
        self.log_every = int(log_every)
        self.clock = clock
        # per-request lifecycle tracing (round 13): schema-v8
        # "lifecycle" metrics events + one named Chrome-trace track
        # per request. Costs one host dict per phase transition; only
        # writes when a metrics sink / live tracer is attached.
        self.lifecycle = bool(lifecycle)
        # chaos plan consulted at every engine step (tick-indexed:
        # stall sleeps, kill/nan poison ride the same hooks training
        # uses). None falls back to the process-global plan, so
        # serve.py --chaos and supervisor-exported drills just work;
        # tests pass an explicit plan to fault ONE of N engines.
        self.chaos_plan = chaos_plan
        # the layers by kind of cache growth: pools sized a group
        # (`n_blocks`: an int, or {"full": ..., "window": ...}), an
        # allocator a group, and every request one table a group
        self.groups = layer_groups(cfg)
        sizes = group_blocks(cfg, n_blocks)
        self.pools = init_block_pool(cfg, sizes, block_size, kv_quant,
                                     slots=self.max_slots)
        # slab bytes one decoding row's state is, all layers, read plus
        # written: what a tick moves for it (0: the model has no mixer)
        self._state_bytes = 2 * cfg.n_layers * state_row_bytes(cfg)
        # prefix caching (round 19): a content-addressed index over
        # block-aligned prompt chunks. `_admit` probes it, finished
        # requests donate their sealed prefix blocks (refcount-zero
        # indexed blocks park on the allocator's cold LRU list instead
        # of freeing), and the divergence/tail block copies-on-write
        # inside `_prefill_chunk`. Off by default: the strict
        # n_free == n_usable drain invariant holds exactly as before;
        # on, the extended invariant is n_free + n_cold == n_usable at
        # drain (cold = donated, still-matchable cache).
        #
        # With a window group a hit needs more than the full group's
        # chain: an m-block hit resumes at position m * bs - 1 at the
        # earliest, so it needs, in every window group, the blocks from
        # `first_live_block(m * bs - 1)` to m - 1 still indexed (a
        # finished request donates what its window still held of its
        # prompt, under the same chain hashes, in that group's own
        # index). `_match_prefix` takes the longest m that every group
        # can serve, else it is a miss; a released block is in no
        # table and no index entry outlives its block (`drop_block`),
        # so a hit never reads one.
        self.prefixes = [PrefixIndex(block_size) if prefix_cache else None
                         for _ in self.groups]
        self.allocs = [BlockAllocator(sizes[g.name], index=ix, group=g.name)
                       for g, ix in zip(self.groups, self.prefixes)]
        # the first group's (a model with one kind of layer has no
        # other): what `serve.py`, the router and the drivers read
        self.prefix, self.alloc = self.prefixes[0], self.allocs[0]
        # a window group's table never passes its bound, so neither
        # does its bucketed width (a whole number of `table_bucket`s)
        ahead = max(self.prefill_chunk, self.spec_k + 1)
        self._held_bound = [g.held_bound(self.block_size, ahead)
                            for g in self.groups]
        self._windowed = any(g.window for g in self.groups)
        # the tick's tables inside the chunk's program (`_prefill_chunk`)
        # have ONE width a group, whatever the rows hold, so that the
        # width is no compile key there and a chunk program warmed with
        # every row dead is the program of every later step: room for
        # the most blocks a request can come to hold (`max_seq`
        # positions, or the whole pool), halved until the paged
        # kernel's scalar memory takes a table of `max_slots` such
        # rows (a tick wider than that compiles nowhere)
        cap = max(self.table_bucket,
                  PAGED_TABLE_BYTES // (4 * self.max_slots))
        self._ride_blocks = []
        for g, al in enumerate(self.allocs):
            room = min(self._peak_blocks(g, cfg.max_seq), al.n_usable)
            while self._width(g, room) > cap:
                room //= 2
            self._ride_blocks.append(room)
        # constant param term at the STORAGE dtypes actually served
        # (int8/fp8 values + f32 scales when weight_quant is on)
        self._p_bytes = param_read_bytes(self.params, cfg)
        self.slots: list[_Req | None] = [None] * self.max_slots
        self.queue: deque[_Req] = deque()
        self.results: dict[str, np.ndarray] = {}
        self.request_records: list[dict] = []
        # finished requests' phase timelines (host dicts, kept for
        # in-process consumers: bench's phase accounting, tests) —
        # the JSONL "lifecycle" stream is the out-of-process surface
        self.timelines: dict[str, list] = {}
        self.counters = {"submitted": 0, "finished": 0, "preempted": 0,
                         "ticks": 0,
                         # ticks dispatched while the tick before
                         # them was still in flight (its tokens on
                         # the device, not yet on the host)
                         "ticks_ahead": 0,
                         # ticks that rode in a prefill chunk's program
                         # (a step that holds a chunk dispatches one
                         # program, not two)
                         "ticks_fused": 0, "prefill_chunks": 0,
                         "shed_toggles": 0, "spec_drafted": 0,
                         "spec_accepted": 0, "prefix_lookups": 0,
                         "prefix_hits": 0, "prefix_skipped_tokens": 0,
                         "oom_events": 0,
                         # sums over the decode ticks (divide by the
                         # ticks between two readings): distinct experts
                         # a routed layer's live rows chose, its largest
                         # expert's count over the mean count, latent
                         # cache rows read. 0 where the model has no
                         # such layer.
                         "experts_touched": 0.0, "max_load": 0.0,
                         "latent_tokens": 0,
                         # pool blocks the decode ticks' reads walked
                         # (every row, dead and draft rows too; one
                         # layer of each group, and `blocks_read_<group>`
                         # that group's part) and the blocks their
                         # tables had room for
                         "blocks_read": 0, "blocks_table": 0,
                         # the same of the prefill chunks: table columns
                         # a chunk's read walked in one layer of each
                         # group (all of them where the pool is gathered)
                         # and the width of its tables
                         "prefill_blocks_read": 0,
                         "prefill_blocks_table": 0,
                         # blocks handed back to a window group's free
                         # list because they left their request's window
                         "released": 0}
        if cfg.mixer:
            # rows whose mixer state the decode ticks advanced, the
            # slab bytes they read plus wrote for it, and the prefill
            # chunks that started from a carried state
            self.counters.update(state_rows=0, state_bytes=0,
                                 state_carried=0)
        for g in self.groups:
            # `<group>_blocks`: blocks the ticks' live rows held there
            self.counters[f"blocks_read_{g.name}"] = 0
            self.counters[f"{g.name}_blocks"] = 0
        # OOM forensics (round 20, the memory observatory): every
        # RECOVERED OutOfBlocks stamps a typed `oom` ledger line and
        # notifies these listeners with (engine, exc) — serve.py wires
        # the monitor's memory flight dump here (same hook pattern as
        # `on_alert`). Throttled to once per tick: one blocked admit
        # retrying every tick must not flood the ledger.
        self.oom_listeners: list = []
        self._oom_tick = -1
        # ownership registry: the observatory decomposes live HBM by
        # owner. Weakref'd resolvers — registration must not extend
        # this engine's (or its donated pools') lifetime; the LAST
        # engine constructed in a process owns the names (the
        # one-engine-per-process serving deployment; in-process
        # multi-engine tests re-register or ignore).
        from shallowspeed_tpu.telemetry import memory as _memlib

        ref = weakref.ref(self)

        def _own(attr):
            def resolve():
                e = ref()
                return getattr(e, attr) if e is not None else None
            return resolve

        _memlib.register_owner("serving.params", _own("params"))
        _memlib.register_owner("serving.kv_pools", _own("pools"))
        # SLO load shedding (round 12, telemetry/monitor): while
        # `admission_paused`, `_admit` leaves the queue alone — running
        # requests keep every slot/block they hold and drain the
        # latency backlog; queued requests wait (submit() still
        # accepts). `on_alert` is the monitor-facing hook that pauses
        # while ANY SLO rule's critical burn persists (tracked per
        # rule — one rule resolving must not release another rule's
        # shed) — OFF by default: serve.py wires it only under
        # --shed-load, so the alert plane is telemetry-only otherwise.
        self.admission_paused = False
        self._critical_slos: set[str] = set()
        # graceful drain (round 15, fleet router): `drain()` flips this
        # — accepted work (queued AND running) completes, new submits
        # raise the typed EngineDraining. Distinct from the shed pause
        # above: shedding holds the queue and resumes; draining empties
        # the engine for deregistration/scale-down and never resumes.
        self.draining = False
        self._admit_counter = 0
        self._win_tokens = 0            # tokens since the last log line
        self._win_t = clock()
        self._last_touched = [0] * len(self.groups)
        self._last_state_rows = 0       # rows the last tick's mixers advanced
        self._win_drafted = 0           # spec-decode window tallies
        self._win_accepted = 0
        self._win_prefix_lookups = 0    # prefix-cache window tallies
        self._win_prefix_hits = 0
        # decode-tick width buckets already executed (and so already
        # compiled): the FIRST tick at a new width re-traces — stamped
        # as a `table_rebucket` ledger event so attribution can book
        # the retrace instead of leaving it unexplained; revisits hit
        # the jit cache and stamp nothing
        self._tick_widths: set[tuple] = set()
        self._last_width = 0
        # the program in flight (`_decode_step`): (the tick's requests,
        # their drafts, its `nxt` and routed counts, both still on the
        # device, and the request whose prompt's last chunk the program
        # held and whose first token `nxt` carries, if any), or None.
        # `_no_tok` stands in for `nxt` in a program dispatched with
        # nothing in flight, whose rows all read the host's tokens.
        self._flight = None
        self._no_tok = jnp.zeros((self.max_slots,), jnp.int32)

    # ------------------------------------------------------ public API

    def submit(self, prompt, max_new: int, temperature: float = 0.0,
               seed: int = 0, rid: str | None = None,
               generated=(), trace: str | None = None,
               parent: str | None = None, attempt: int = 0) -> str:
        """Queue one request. Rejects (typed ValueError) requests that
        could never run: prompt + max_new past cfg.max_seq, or a block
        footprint larger than the whole pool (the no-deadlock
        precondition — an admitted request can always finish alone).
        Raises the typed `EngineDraining` after `drain()` began.

        `generated` resumes a half-decoded stream FROM ANOTHER ENGINE:
        the tokens already emitted elsewhere re-prefill with the prompt
        and sampling continues at token index len(generated) — exactly
        the evict-newest continuation mechanism, now crossing a process
        boundary. Because token i of a request always draws from
        `fold_in(PRNGKey(seed), i)`, the continued stream is
        token-identical to the solo `generate()` stream no matter which
        engine emitted the prefix (the fleet router's seeded idempotent
        re-dispatch rides this). `max_new` stays the TOTAL budget; the
        result stream includes the resumed prefix.

        `trace`/`parent`/`attempt` are the schema-v11 trace context
        the router propagates (one trace per fleet request, `parent`
        = the dispatch span, `attempt` = the 0-based cross-engine
        dispatch counter); standalone submissions mint their own
        trace so a lone serve.py's lifecycle stream still stitches.
        This engine mints a fresh span per attempt either way."""
        if self.draining:
            raise EngineDraining(self.pending())
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        tp = prompt.shape[0]
        generated = [int(t) for t in generated]
        if tp < 1 or max_new < 1:
            raise ValueError(f"empty request: prompt {tp} tokens, "
                             f"max_new={max_new}")
        if len(generated) >= max_new:
            raise ValueError(
                f"continuation already carries {len(generated)} of "
                f"max_new={max_new} tokens — nothing left to decode")
        if tp + max_new > self.cfg.max_seq:
            raise ValueError(f"prompt {tp} + max_new {max_new} exceeds "
                             f"max_seq={self.cfg.max_seq}")
        # the final sampled token is never written (sample-after-decode,
        # like generate()), so the request's peak footprint is
        # tp + max_new - 1 cache positions
        for g, al in enumerate(self.allocs):
            need = self._peak_blocks(g, tp + max_new - 1)
            if need > al.n_usable:
                raise ValueError(
                    f"request needs {need} blocks but the {al.group!r} "
                    f"group's pool holds {al.n_usable} usable — it could "
                    f"never be scheduled (raise n_blocks or shrink the "
                    f"request)")
        rid = rid if rid is not None else f"r{self.counters['submitted']}"
        if rid in self.results or any(
                r.rid == rid for r in self._all_live()):
            raise ValueError(f"duplicate request id {rid!r}")
        req = _Req(rid, prompt, max_new, temperature, seed,
                   self.clock())
        req.trace = trace if isinstance(trace, str) and trace \
            else new_trace_id()
        req.span = new_span_id()
        req.parent = parent if isinstance(parent, str) and parent \
            else None
        req.attempt = max(0, int(attempt))
        if generated:
            # resume mid-stream: identical state to a post-eviction
            # requeue — ctx re-prefills prompt + prefix, the next
            # sample draws at token index len(generated)
            req.generated = generated
            req.ctx = np.concatenate(
                [prompt, np.asarray(generated, np.int32)])
        self.queue.append(req)
        self.counters["submitted"] += 1
        extra = {"resumed": len(generated)} if generated else {}
        self._lifecycle(req, "submit", tokens=int(tp), **extra)
        self._lifecycle(req, "queued")
        return rid

    def poll(self, rid: str) -> dict:
        """{"status": queued|running|done, "tokens": generated so far}."""
        if rid in self.results:
            return {"status": "done", "tokens": self.results[rid]}
        for r in self._all_live():
            if r.rid == rid:
                status = "queued" if r.phase == "queued" else "running"
                return {"status": status,
                        "tokens": np.asarray(r.generated, np.int32)}
        raise KeyError(rid)

    def pending(self) -> int:
        return len(self.queue) + sum(1 for s in self.slots
                                     if s is not None)

    def step(self) -> bool:
        """One scheduler tick: admissions, then ONE program: the next
        prefill chunk (FIFO across prefilling requests) with the decode
        tick over every decoding slot riding in it (`_prefill_chunk`),
        or, in a step that holds no chunk, the decode tick alone
        (`_decode_tick`). Returns whether any work ran — decodes
        advance every step even while a long prompt prefills, which is
        the chunked-prefill no-stall contract, and a step that holds a
        chunk streams the weights once, not twice.

        The program a step dispatches stays IN FLIGHT when the step
        returns: its tokens are fetched and booked by the next step,
        after that step has dispatched its own program
        (`_decode_step`). That holds for a prompt's FIRST token too: it
        is sampled by the program of the prompt's last chunk and
        reaches the host (`poll`, `first_tok_t`) one step later, with
        the tick's. So the host's view (`poll`, the records, a freed
        slot) trails the device by one program, and a step that finds
        one in flight and nothing left to dispatch lands it and counts
        as work: `run()` and a `drain()` loop step until `pending()` is
        0 and so deliver every token."""
        plan = self.chaos_plan if self.chaos_plan is not None \
            else chaos.active()
        # the scheduler's host phases as spans (telemetry/trace.py):
        # always in the tracer's ring, in the profiler's trace as
        # `ss:<name>` while a session is live, and the sampling
        # profiler's phase names while it runs. `*.fetch` spans block
        # on the device; every other one is the host's own time.
        tr = tracer()
        with tr.span("engine.step", tick=self.counters["ticks"]):
            if plan is not None:
                # tick-indexed faults: a serving drill reuses the
                # training hooks — stall sleeps here (and must surface
                # as replica skew the fleet's straggler detector names
                # — AND as the profiler capture's dominant host
                # phase), kill/nan poison the params like a training
                # step would
                with tr.span("chaos"):
                    plan.on_data_load(self.counters["ticks"])
                    plan.on_step(self.counters["ticks"], engine=self)
            with tr.span("admit") as sp:
                before = self._admit_counter
                did = self._admit()
                sp.set(n_admitted=self._admit_counter - before)
            pre = [r for r in self.slots
                   if r is not None and r.phase == "prefill"]
            did = self._decode_step(
                min(pre, key=lambda r: r.admit_seq)         # FIFO
                if pre else None) or did
        return did

    def run(self, max_steps: int | None = None) -> dict:
        """Drain: step until every submitted request finished (or
        `max_steps`, for bounded tests). Returns {rid: tokens}."""
        steps = 0
        while self.pending():
            if max_steps is not None and steps >= max_steps:
                break
            if not self.step():
                raise RuntimeError(
                    "scheduler made no progress with requests pending "
                    f"(queue={len(self.queue)}, free_blocks="
                    f"{[al.n_free for al in self.allocs]})")
            steps += 1
        return dict(self.results)

    def drain(self) -> bool:
        """Graceful drain: stop admitting NEW submissions (they raise
        the typed `EngineDraining`), let everything already accepted —
        queued and running — run to completion. Idempotent; returns
        True when all accepted work has finished, so a scale-down loop
        is `while not eng.drain(): eng.step()` followed by
        deregistration. Queue shedding (`on_alert`) pauses and resumes;
        drain is one-way."""
        self.draining = True
        return self.pending() == 0

    def executable_counts(self) -> dict:
        """Live jit-cache sizes of the serving entrypoints — the
        compile-count pin (`fn._cache_size`, the same counter the
        analysis retrace rule and RunTelemetry read). After warmup
        these must NOT grow as requests churn. `sample` was the first
        token's own program until that token came to be sampled inside
        `_prefill_chunk`; the key stays for those who sum the three."""
        return {"decode_tick": int(_decode_tick._cache_size()),
                "prefill_chunk": int(_prefill_chunk._cache_size()),
                "sample": 0}

    # ------------------------------------------------------- lifecycle

    def _lifecycle(self, req, phase: str, **extra) -> None:
        """One phase transition on `req`'s timeline: submit -> queued
        -> admitted -> prefill (per chunk) -> decoding -> preempted ->
        requeued -> ... -> finished. Stamps a schema-v8 "lifecycle"
        metrics event (with the ms spent in the PREVIOUS phase, so
        `report.request_timeline` reconstructs the whole span
        accounting) and, when tracing is live, closes the previous
        phase as an X span on the request's named trace track —
        cross-linked to the engine tick spans via the tick counter."""
        if not self.lifecycle:
            return
        now = self.clock()
        prev = req.timeline[-1] if req.timeline else None
        entry = {"phase": phase, "wall": now, **extra}
        req.timeline.append(entry)
        if self.metrics is not None:
            rec = {"id": req.rid, "phase": phase,
                   "seq": len(req.timeline) - 1,
                   "tick": self.counters["ticks"],
                   # schema v11: the cross-process join keys — one
                   # trace per fleet request, one span per engine
                   # attempt, attempt = the cross-engine dispatch
                   # counter the (rid, attempt) reduction keys on
                   "trace": req.trace, "span": req.span,
                   "attempt": req.attempt, **extra}
            if req.parent is not None:
                rec["parent"] = req.parent
            if req.slot is not None:
                rec["slot"] = req.slot
            if prev is not None:
                rec["prev"] = prev["phase"]
                rec["ms_in_prev"] = round((now - prev["wall"]) * 1e3, 3)
            self.metrics.log(event="lifecycle", **rec)
        tr = tracer()
        if tr.level != "off":
            if req.track is None:
                req.track = tr.track(f"request {req.rid}")
            t1 = tr.now()
            if prev is not None and req.trace_t0 is not None:
                tr.complete(prev["phase"], req.trace_t0, t1,
                            tid=req.track, id=req.rid,
                            tick=self.counters["ticks"])
            req.trace_t0 = t1

    # ------------------------------------------------------- scheduler

    def _all_live(self):
        yield from (s for s in self.slots if s is not None)
        yield from self.queue

    def on_alert(self, alert: dict) -> None:
        """SLO burn-rate alert hook (`Monitor.alert_listeners`): pause
        admission while ANY rule's critical burn persists, resume when
        the LAST critical rule resolves or de-escalates. Alerts are
        per-rule state transitions, so membership is tracked per SLO
        spec — rule B resolving while rule A still burns critical must
        not release A's shed. Stamps a ledger-style `"ledger"` line
        (kind `load_shed`) at each pause/resume toggle so the goodput
        reducer can see the shed windows next to the request records."""
        slo = str(alert.get("slo"))
        if (alert.get("state") == "firing"
                and alert.get("severity") == "critical"):
            self._critical_slos.add(slo)
        else:
            self._critical_slos.discard(slo)
        want = bool(self._critical_slos)
        if want == self.admission_paused:
            return
        self.admission_paused = want
        self.counters["shed_toggles"] += 1
        if self.metrics is not None:
            self.metrics.log(event="ledger", kind="load_shed",
                             count=1 if want else 0,
                             slo=sorted(self._critical_slos)[0]
                             if want else slo)

    def headroom(self) -> dict:
        """The capacity plane's admission-headroom estimate: blocks
        still needed to finish EVERY accepted request (queued and
        running) at its max-token budget, vs what the pool can
        surrender (free + reclaimable cold). Negative headroom means
        the accepted work is overcommitted — evictions are coming
        unless requests finish early — which is the router's
        shed-before-evict placement signal. Uses submit()'s footprint
        model (tp + max_new - 1 cache positions), so a request's
        deficit falls as its table grows."""
        out = None
        for g, al in enumerate(self.allocs):
            needed = 0
            for r in self._all_live():
                final = self._peak_blocks(
                    g, r.prompt.shape[0] + r.max_new - 1)
                needed += max(0, final - (len(r.tables[g]) if r.tables
                                          else 0))
            room = al.n_free + al.n_cold - needed
            # of several groups, the one that runs out first
            if out is None or room < out["headroom_blocks"]:
                out = {"live_blocks": al.n_live, "blocks_needed": needed,
                       "headroom_blocks": room}
        if self._state_bytes:
            # the second kind of state: a slab row a slot, held from
            # admission on whatever the context grows to
            held = sum(1 for r in self.slots if r is not None)
            out["state_rows"] = held
            out["state_bytes"] = held * self._state_bytes // 2
        return out

    def _peak_blocks(self, g: int, n_tokens: int) -> int:
        """Most blocks a request of `n_tokens` cache positions holds in
        group `g` at once: all of them, or a window group's bound."""
        need = blocks_for(n_tokens, self.block_size)
        bound = self._held_bound[g]
        return min(need, bound) if bound else need

    def _width(self, g: int, n_blocks: int) -> int:
        """Table width of group `g` for `n_blocks` held: the geometric
        bucket, which a window group's bound caps."""
        w = table_width(n_blocks, self.table_bucket)
        bound = self._held_bound[g]
        if bound:
            w = min(w, -(-bound // self.table_bucket) * self.table_bucket)
        return w

    def _note_oom(self, e: OutOfBlocks) -> None:
        """Record one RECOVERED block-exhaustion event: bump the
        counter, notify the forensics listeners, stamp the typed `oom`
        ledger line. Throttled to once per tick — a blocked queue
        retrying every tick is ONE pressure episode, not a stamp per
        retry. Listeners run FIRST so the rich forensic payload (per-
        owner bytes, allocator snapshot) wins the flight recorder's
        (reason, step) dedup over the bare ledger line's trigger."""
        tick = self.counters["ticks"]
        if tick == self._oom_tick:
            return
        self._oom_tick = tick
        self.counters["oom_events"] += 1
        for fn in list(self.oom_listeners):
            try:
                fn(self, e)
            except Exception:
                pass  # a broken listener must not kill the scheduler
        if self.metrics is not None:
            extra = {"id": str(e.rid)} if e.rid is not None else {}
            self.metrics.log(event="ledger", kind="oom", tick=tick,
                             requested=e.requested, free=e.n_free,
                             cold=e.n_cold, live=e.n_live, **extra)

    def oom_forensics(self, e: OutOfBlocks | None = None,
                      top_k: int = 8) -> dict:
        """The memory flight-dump payload for this engine: the
        process-wide per-owner decomposition, top-K largest live
        arrays, backend allocator stats and host RSS
        (`telemetry/memory.forensics`) joined with the block
        allocator's snapshot, the headroom estimate, per-request
        block-table widths, and the in-flight request set. Host-side
        only — allocates no device memory, safe inside an OOM
        handler."""
        from shallowspeed_tpu.telemetry import memory as memlib

        out = memlib.forensics(top_k)
        out["allocator"] = self.alloc.snapshot()
        out["allocators"] = [al.snapshot() for al in self.allocs]
        out["headroom"] = self.headroom()
        out["block_tables"] = {r.rid: len(r.table)
                               for r in self.slots if r is not None}
        out["in_flight"] = [r.rid for r in self._all_live()]
        if e is not None:
            out["oom"] = {"requested": e.requested, "free": e.n_free,
                          "cold": e.n_cold, "live": e.n_live,
                          "rid": e.rid}
        return out

    def _admit(self) -> bool:
        did = False
        if self.admission_paused and any(s is not None
                                         for s in self.slots):
            # shed: drain the in-flight work, admit nothing new. The
            # all-slots-empty carve-out keeps the scheduler live — a
            # pause with nothing running would wedge `run()` (no
            # progress, requests pending) without shedding any load.
            return False
        while self.queue and None in self.slots:
            req = self.queue[0]
            bs = self.block_size
            # prefix-cache probe: map the longest indexed aligned
            # prefix straight into the block tables and start chunked
            # prefill at the divergence point. A FULLY-aligned match
            # (every block of ctx indexed) still re-prefills its last
            # token: the tail block copies-on-write into a fresh block
            # so decode can append without mutating the shared one,
            # and the final-position logits come from a real chunk.
            m, matched = self._match_prefix(req.ctx)
            full = m > 0 and m * bs == len(req.ctx)
            written = len(req.ctx) - 1 if full else m * bs
            # a full group takes the whole prompt's blocks now, as
            # ever; a window group those of the first chunk (the next
            # ones as `_ensure_blocks` releases what left the window)
            upto = [len(req.ctx) if not g.window
                    else min(len(req.ctx), written + self.prefill_chunk)
                    for g in self.groups]
            held, tables = [], []
            try:
                for al, ids, n in zip(self.allocs, matched, upto):
                    if ids:
                        al.acquire(ids)
                        held.append((al, ids))
                    fresh = al.alloc(blocks_for(n, bs) - m
                                     + (1 if full else 0), rid=req.rid)
                    held.append((al, fresh))
                    tables.append(fresh)
            except OutOfBlocks as e:
                for al, ids in held:      # all-or-nothing admission
                    al.release(ids)
                self._note_oom(e)
                break                # wait for blocks to free
            self.queue.popleft()
            slot = self.slots.index(None)
            req.slot = slot
            req.base = [m - len(ids) for ids in matched]
            # a fully aligned hit holds the matched tail block (the CoW
            # source) by the acquire above until the copy lands in the
            # first prefill chunk; the table gets the fresh copy instead
            req.cow = [(ids[-1], fresh[0])
                       for ids, fresh in zip(matched, tables)] \
                if full else None
            req.tables = [ids[:len(ids) - full] + fresh
                          for ids, fresh in zip(matched, tables)]
            req.written = written
            skipped = req.written
            req.phase = "prefill"
            req.admit_seq = self._admit_counter
            self._admit_counter += 1
            req.admit_t = self.clock()
            # queue wait accumulates PER STINT (a preempted request's
            # on-device time between stints must not count as waiting)
            req.wait_s += req.admit_t - req.queued_at
            self.slots[slot] = req
            self._lifecycle(req, "admitted", slot=slot)
            if m > 0:
                req.hit_blocks += m
                req.skipped_tok += skipped
                self.counters["prefix_hits"] += 1
                self.counters["prefix_skipped_tokens"] += skipped
                self._win_prefix_hits += 1
                self._lifecycle(req, "prefill_cached", blocks=m,
                                tokens=int(skipped))
            did = True
        return did

    def _match_prefix(self, ctx) -> tuple:
        """(m, [the matched block ids a group]): the longest aligned
        prefix of `ctx`, in blocks, that EVERY group's index can serve
        — a full group all of blocks 0..m-1, a window group those from
        `first_live_block(m * bs - 1)` on (what the first query after
        the hit, at the earliest, still sees) — else (0, nothing)."""
        none = 0, [[] for _ in self.groups]
        if self.prefix is None:
            return none
        self.counters["prefix_lookups"] += 1
        self._win_prefix_lookups += 1
        bs = self.block_size
        found = [ix.lookup(chunk_hashes(ctx, bs)) for ix in self.prefixes]
        # run[c]: indexed blocks in a row up to and including column c
        runs = []
        for ids in found:
            run, n = [], 0
            for bid in ids:
                n = n + 1 if bid is not None else 0
                run.append(n)
            runs.append(run)
        for m in range(len(found[0]), 0, -1):
            first = [g.first_live_block(m * bs - 1, bs) for g in self.groups]
            if all(run[m - 1] >= m - lo for run, lo in zip(runs, first)):
                return m, [ids[lo:m] for ids, lo in zip(found, first)]
        return none

    def _layer_attrs(self, counts, rows_read: int) -> dict:
        """What a program run's span says of the layers that differ by
        kind: `latent_tokens` (cache rows its latent layers each read),
        and from the routed layers' (layers, E) assignment counts
        `experts_touched` (distinct experts chosen, mean a layer) and
        `max_load` (the busiest expert's count over the mean count,
        mean a layer). `counts` arrived with the sampled tokens."""
        attrs = {}
        if self.cfg.latent:
            attrs["latent_tokens"] = int(rows_read)
        if counts is not None:
            mean = np.maximum(counts.sum(-1), 1) / counts.shape[-1]
            attrs["experts_touched"] = float((counts > 0).sum(-1).mean())
            attrs["max_load"] = float((counts.max(-1) / mean).mean())
        return attrs

    def _blocks_walked(self, pos) -> list[int]:
        """Pool blocks the read of a tick walks in ONE layer of each
        group, from the tick's own positions: each row those of its
        table that its position and the group's window admit, a dead
        row (pos 0) one. (With int8 pools the kernel rounds a window's
        first block down to a whole step and reads up to a step less
        one more.)"""
        bs = self.block_size
        return [int((pos // bs + 1 - (np.maximum(pos - g.window + 1, 0) // bs
                                      if g.window else 0)).sum())
                for g in self.groups]

    def _chunk_walked(self, req, n_tok: int, bts) -> list[int]:
        """Table columns the read of `req`'s next chunk walks in ONE
        layer of each group, from the host's own `written`: from the
        first block of the first query's window to the block of the
        chunk's last true position (`paged_flash_prefill`; a query tile
        walks its own part of them), or the table's whole width where
        it is gathered (`paged_prefill_addresses`)."""
        bs = self.block_size
        at0 = [req.written - b * bs for b in req.base]
        return [(at + n_tok - 1) // bs + 1 - g.first_live_block(at, bs)
                if paged_prefill_addresses(kv_leaves(self.pools[g.layers[0]]),
                                           bt.size)
                else bt.size
                for g, at, bt in zip(self.groups, at0, bts)]

    def _rows_tables(self, rows, n: int, room=None) -> tuple:
        """The programs' table arguments for `rows`, [(row of the
        arrays, request)], of `n` rows in all: one (n, width) table a
        group, scratch where a row holds nothing and as wide as the
        bucket of the longest (or of `room[g]` blocks, if that is
        more), and `base` (groups, n) — the position of each table's
        first token — where the model has a window group (else None:
        every table starts at 0)."""
        bts = []
        for g in range(len(self.groups)):
            w = self._width(g, max([len(r.tables[g]) for _, r in rows]
                                   + [room[g] if room else 0]))
            bt = np.full((n, w), SCRATCH_BLOCK, np.int32)
            for i, r in rows:
                bt[i, :len(r.tables[g])] = r.tables[g]
            bts.append(bt)
        if not self._windowed:
            return tuple(bts), None
        base = np.zeros((len(self.groups), n), np.int32)
        if rows:
            base[:, [i for i, _ in rows]] = np.transpose(
                [r.base for _, r in rows]) * self.block_size
        return tuple(bts), base

    def _prefill_chunk_of(self, req, n_tok: int, rows, rode: int,
                          released: int, tr) -> tuple:
        """Dispatch `req`'s next chunk of `n_tok` tokens with the step's
        tick riding in its program: `rows` are the tick's per-row
        arguments (`_decode_prep`), `rode` of them live; `released`
        is what making room for the chunk handed back. Returns (`nxt`,
        the tick's routed counts, and `req` where this chunk is its
        prompt's last, else None): what the step puts in flight.

        Nothing here waits for the device. After its prompt's last
        chunk the request is a decoder whose first token is in flight,
        like any decoder's next token: the chunk's last position is
        booked with that token when the flight lands (`written` stays
        one short of the prompt until then, `in_flight` is 1), so the
        next tick gives it a row at position `written + in_flight` and
        sampling index `len(generated) + in_flight`, reading its input
        token from `prev[slot]` on the device."""
        c = self.prefill_chunk
        with tr.span("prefill", rid=req.rid, chunk=req.written // c,
                     released=released, rows_rode=rode) as sp:
            self._lifecycle(req, "prefill", chunk=req.written // c,
                            tokens=int(n_tok))
            tokens = np.zeros((1, c), np.int32)
            tokens[0, :n_tok] = req.ctx[req.written:req.written + n_tok]
            # as wide as the most the prompt will hold, in every chunk:
            # a window group's table grows to its bound over the first
            # chunks, and each width on the way would be a program
            bts, base = self._rows_tables(
                [(0, req)], 1, [self._peak_blocks(g, len(req.ctx))
                                for g in range(len(self.groups))])
            # copy-on-write rides the chunk as DATA on every call
            # (scratch self-copy when there is nothing to copy) — zero
            # executables
            cow = np.asarray(req.cow if req.cow is not None
                             else [(SCRATCH_BLOCK, SCRATCH_BLOCK)]
                             * len(self.groups), np.int32)
            if self._state_bytes:
                carried = int(req.written > 0)
                sp.set(state_carried=carried)
                self.counters["state_carried"] += carried
            with tr.span("prefill.dispatch"):
                nxt, self.pools, _, counts = _prefill_chunk(
                    self.params, self.pools, tokens, np.int32(req.written),
                    np.int32(n_tok), bts, cow[:, 0], cow[:, 1],
                    None if base is None else base[:, 0],
                    np.int32(req.slot), rows, cfg=self.cfg,
                    top_k=self.top_k, top_p=self.top_p)
            walked = self._chunk_walked(req, n_tok, bts)
            read = {"blocks_read": sum(walked),
                    "blocks_table": sum(bt.size for bt in bts)}
            sp.set(**read, **{f"blocks_read_{g.name}": n
                              for g, n in zip(self.groups, walked)})
            for name, value in read.items():
                self.counters[f"prefill_{name}"] += value
            if req.cow is not None:
                # the copy is dispatched: drop the references that kept
                # the shared source blocks alive for it
                for al, (src, _) in zip(self.allocs, req.cow):
                    al.release([src])
                req.cow = None
            req.written += n_tok
            self.counters["prefill_chunks"] += 1
            if req.written < len(req.ctx):
                return nxt, counts, None
            # prompt complete: `nxt[slot]` is this request's next token
            # (index len(generated) — 0 for a fresh request, the
            # continuation index after a preemption), in flight
            req.written -= 1
            req.in_flight = 1
            req.phase = "decode"
            self._lifecycle(req, "decoding")
            return nxt, counts, req

    def _decode_step(self, chunk=None) -> bool:
        """One turn of the decode loop, which keeps ONE program in
        flight; `chunk` is the request whose next prefill chunk this
        step holds (None: nobody is prefilling).

        Program N+1 is prepared and dispatched BEFORE program N's
        tokens are fetched: the host knows which rows N+1 has without
        them (positions advance by one, a request finishes by count,
        tables grow from positions), and each row's input token is
        taken from N's `nxt` on the device (`_decode_tick`'s `prev` /
        `ahead`). Then N is landed, fetched (`decode.fetch`, which
        waits for the program BEFORE the one this span dispatched) and
        emitted, while the device runs N+1. Nothing is speculated: only
        when the host learns a token changes, never which token it is.

        With a chunk the step's one program is the chunk's, and the
        tick's rows ride in it (`_prefill_chunk_of`; all of them dead
        where nobody decodes, and then a chunk that is not its prompt's
        last leaves nothing to land). The chunk's blocks are made sure
        of first, then the rows'; where either evicts the prefilling
        request (the newest admitted goes first) the step is the tick
        alone. The `prefill` span lies inside this turn's `decode`
        span, around the dispatch.

        Who lands the program in flight: the next turn, as above (also
        when it has nothing to dispatch: the last tick of a drain);
        `_ensure_blocks` when the pool runs out, before it evicts.
        Draft rows (`spec_k > 0`) are proposed from the last token on
        the host, so with them every program is landed in the turn that
        dispatched it: the same loop with nothing in flight."""
        had = self._flight is not None
        if chunk is None and not had and not any(
                r is not None and r.phase == "decode" for r in self.slots):
            return False
        tr = tracer()
        with tr.span("decode") as sp:
            released = self.counters["released"]
            with tr.span("decode.prep"):
                n_tok = 0
                if chunk is not None:
                    n_tok = min(self.prefill_chunk,
                                len(chunk.ctx) - chunk.written)
                    if not self._ensure_blocks(chunk, chunk.written + n_tok):
                        chunk = None    # evicted for blocks: it prefills anew
                for_chunk = self.counters["released"] - released
                prep = self._decode_prep(chunk)
                if chunk is not None and chunk.slot is None:
                    chunk = None        # evicted for the rows' blocks
            released = self.counters["released"] - released - for_chunk
            # what is in flight NOW: prep lands it itself where it ran
            # out of blocks
            ahead = int(prep is not None and bool(prep[0])
                        and self._flight is not None)
            sp.set(ahead=ahead)
            new = None
            if prep is not None:
                actives, drafts, rows = prep
                pos, bts = rows[1], rows[2]
                if chunk is not None:
                    nxt, counts, first = self._prefill_chunk_of(
                        chunk, n_tok, rows, len(actives), for_chunk, tr)
                else:
                    with tr.span("decode.dispatch"):
                        nxt, self.pools, counts = _decode_tick(
                            self.params, self.pools, *rows, cfg=self.cfg,
                            top_k=self.top_k, top_p=self.top_p)
                    first = None
                if actives or first is not None:
                    new = (actives, drafts, nxt, counts, first)
                if actives:
                    fused = int(chunk is not None)
                    self.counters["ticks_ahead"] += ahead
                    self.counters["ticks_fused"] += fused
                    walked = self._blocks_walked(pos)
                    read = {"blocks_read": sum(walked),
                            "blocks_table": sum(bt.size for bt in bts)}
                    for i, g in enumerate(self.groups):
                        read[f"blocks_read_{g.name}"] = walked[i]
                        read[f"{g.name}_blocks"] = sum(len(r.tables[i])
                                                       for r in actives)
                    if self._state_bytes:
                        read["state_rows"] = len(actives)
                        read["state_bytes"] = len(actives) * self._state_bytes
                    sp.set(n_active=len(actives), width=bts[0].shape[1],
                           released=released, fused=fused, **read)
                    for name, value in read.items():
                        self.counters[name] += value
            if self.spec_k > 0:
                # the next drafts need this program's tokens on the
                # host: it is the one to land, and nothing stays in
                # flight
                self._flight, new = new, None
            self._land(sp)
            if new is not None:
                self._flight = new
                for r in new[0]:
                    r.in_flight = 1
        # no work only where every decoder was evicted for blocks and
        # prep landed nothing on the way
        return had or prep is not None

    def _land(self, sp=None) -> bool:
        """Fetch and book the program in flight, if there is one: its
        tokens and the tick's routed counts leave the device in one
        wait, the layers' attrs of THAT tick go on `sp` (the `decode`
        span open now, which may have dispatched the program after it)
        and into the counters, `_decode_emit` appends. Returns whether
        one was landed."""
        if self._flight is None:
            return False
        (actives, drafts, nxt, counts, first), self._flight = \
            self._flight, None
        tr = tracer()
        with tr.span("decode.fetch"):
            # one wait for both: the counts leave the device beside
            # the tokens, not in a second round trip after them
            nxt, counts = jax.device_get((nxt, counts))
        if actives:
            attrs = self._layer_attrs(
                counts, sum(r.written + 1 for r in actives))
            if sp is not None:
                sp.set(**attrs)
            for name, value in attrs.items():
                self.counters[name] += value
        with tr.span("decode.emit"):
            self._decode_emit(actives, drafts, nxt, first)
        return True

    def _decode_prep(self, chunk=None):
        """The next tick's host-side inputs: (its requests, their
        speculative drafts, `_decode_tick`'s per-row arguments in its
        own order), or None when no request has a row to take and the
        step holds no chunk. With `chunk`, the request whose prefill
        chunk the rows will ride with (`_prefill_chunk`'s `tick`), the
        rows are made even where all of them are dead, the tables are
        of the one width the chunk's program takes (`_ride_blocks`),
        and the chunk's sampling state stands in the request's own row,
        which is dead in the tick. Making sure of the rows' blocks may
        evict that request: the rows are then the tick's alone.

        Works from the host's positions PLUS what is in flight: a
        request with a token in flight (`in_flight`: a row in the tick
        in flight, or its prompt's last chunk) writes one position
        further and samples one index further than the host has
        booked, and reads its token from the device; one whose token
        in flight is its last takes no row. Which rows are live is
        exact either way."""
        owed = lambda r: len(r.generated) + r.in_flight < r.max_new
        for req in [r for r in self.slots
                    if r is not None and r.phase == "decode"]:
            # not evicted meanwhile, nor finished by a landing
            if req.slot is not None and owed(req):
                self._ensure_blocks(req, req.written + req.in_flight + 1)
        actives = [r for r in self.slots
                   if r is not None and r.phase == "decode" and owed(r)]
        if chunk is not None and chunk.slot is None:
            chunk = None
        if not actives and chunk is None:
            return None
        s = self.max_slots
        # speculative drafts claim the tick's FREE rows (empty slots
        # and prefilling requests' idle rows, but for the row that
        # carries a chunk's sample) — occupancy is data, so
        # drafting costs zero executables and zero extra tick time
        drafts: dict[str, tuple] = {}
        if self.spec_k > 0:
            taken = {r.slot for r in actives}
            if chunk is not None:
                taken.add(chunk.slot)
            free = [i for i in range(s) if i not in taken]
            for r in sorted(actives, key=lambda r: r.admit_seq):
                if not free:
                    break
                cap = min(self.spec_k, len(free),
                          r.max_new - len(r.generated) - 1)
                if cap <= 0:
                    continue
                d = self._grow_for_drafts(r, self._propose(r, cap))
                if d:
                    drafts[r.rid] = (r, [(free.pop(0), t) for t in d])
        tok = np.zeros(s, np.int32)
        pos = np.zeros(s, np.int32)
        temp = np.zeros(s, np.float32)
        seeds = np.zeros(s, np.uint32)
        idx = np.zeros(s, np.int32)
        ahead = np.zeros(s, np.bool_)
        rows = [(r.slot, r) for r in actives] + [
            (row, r) for r, assigned in drafts.values()
            for row, _ in assigned]
        bts, base = self._rows_tables(
            rows, s, None if chunk is None else self._ride_blocks)
        for r in actives:
            tok[r.slot] = r.last_tok
            ahead[r.slot] = r.in_flight
            pos[r.slot] = r.written + r.in_flight
            temp[r.slot] = r.temp
            seeds[r.slot] = r.seed
            idx[r.slot] = len(r.generated) + r.in_flight
        for r, assigned in drafts.values():
            # draft row j: the j-th draft token at position written+j,
            # sampling at oracle token index len(generated)+j — the
            # same fold_in schedule the solo stream uses at that index
            for j, (row, dtok) in enumerate(assigned, start=1):
                tok[row] = dtok
                pos[row] = r.written + j
                temp[row] = r.temp
                seeds[row] = r.seed
                idx[row] = len(r.generated) + j
        if chunk is not None:
            temp[chunk.slot] = chunk.temp
            seeds[chunk.slot] = chunk.seed
            idx[chunk.slot] = len(chunk.generated)
        w = tuple(bt.shape[1] for bt in bts)
        if chunk is None and w not in self._tick_widths:
            # FIRST tick at this width bucket compiles a fresh
            # executable (geometric bucketing keeps the count O(log
            # max_len)); later returns to the width hit the jit cache,
            # so only first visits stamp — a phantom stamp per
            # width flip under alternating traffic would over-book
            # compile pauses that never happened. The warmup width
            # (empty seen-set) is booked as compile, not a rebucket.
            if self._tick_widths and self.metrics is not None:
                self.metrics.log(event="ledger", kind="table_rebucket",
                                 count=1, prev_width=self._last_width,
                                 width=int(w[0]),
                                 tick=self.counters["ticks"])
            self._tick_widths.add(w)
        if chunk is None:
            self._last_width = int(w[0])
        prev = self._no_tok if self._flight is None else self._flight[2]
        return actives, drafts, (tok, pos, bts, temp, seeds, idx, prev,
                                 ahead, base)

    def _decode_emit(self, actives, drafts, nxt, first=None) -> None:
        """Book a landed program's tokens: counters, appends (which
        finish requests), the windowed tick line. `first` is the
        request whose prompt's last chunk the program held: its first
        token is `nxt[slot]` like a row's, and the position it books
        is the chunk's last."""
        bs = self.block_size
        if actives:
            self.counters["ticks"] += 1
            ends = [r.written + 1 + len(drafts.get(r.rid, (None, ()))[1])
                    for r in actives]
            self._last_touched = [
                sum(blocks_for(n, bs) - g.first_live_block(n - 1, bs)
                    for n in ends) for g in self.groups]
            self._last_state_rows = len(actives) if self._state_bytes else 0
        emitted = 0
        for r in actives + ([first] if first is not None else []):
            # speculation tallies accrue BEFORE the appends: an
            # accepted final draft can finish the request, and the
            # "request" record stamped at that instant must already
            # carry this tick's drafted/accepted counts
            assigned = drafts.get(r.rid, (None, ()))[1]
            if assigned:
                r.n_drafted += len(assigned)
                self.counters["spec_drafted"] += len(assigned)
                self._win_drafted += len(assigned)
            tok_next = int(nxt[r.slot])
            r.in_flight = 0
            r.written += 1
            self._append_token(r, tok_next)
            emitted += 1
            for row, dtok in assigned:
                # accept while the draft equals the oracle draw; the
                # next row's logits are then the TRUE logits at the
                # advanced context, so its draw is the oracle's too
                if r.rid in self.results or dtok != tok_next:
                    break
                tok_next = int(nxt[row])
                r.n_accepted += 1
                self.counters["spec_accepted"] += 1
                self._win_accepted += 1
                r.written += 1
                self._append_token(r, tok_next)
                emitted += 1
        self._win_tokens += emitted
        if actives:
            self._maybe_log()

    # ------------------------------------------------- spec decoding

    def _propose(self, req, k: int) -> list:
        """Self-drafting n-gram prompt-lookup proposer: find the most
        recent EARLIER occurrence of the context's trailing n-gram
        (longest n first, n <= spec_ngram) and draft the k tokens that
        followed it. No draft model and no device work — the draft
        source is the request's own prompt + generated stream, which
        is exactly where repeated spans (code, templates, copied
        entities) live. O(spec_ngram) dict lookups per call: the
        occurrence index is built once per request and maintained
        O(spec_ngram) per appended token (`_spec_note`) — a per-tick
        rescan would cost O(context) host time per request, growing
        with every generated token."""
        ctx, idx = self._spec_state(req)
        n_ctx = len(ctx)
        for n in range(min(self.spec_ngram, n_ctx - 1), 0, -1):
            ent = idx.get(tuple(ctx[n_ctx - n:]))
            if ent is None:
                continue
            # the index's latest entry is the tail itself (indexed
            # when its last token arrived) — the draft source is the
            # most recent occurrence BEFORE it
            start = ent[0] if ent[0] != n_ctx - n else ent[1]
            if start is not None:
                return ctx[start + n:start + n + k]
        return []

    def _spec_state(self, req) -> tuple:
        """The request's draft-lookup state, built lazily on first
        use: `ctx_ids` (prompt + generated as a plain list, appended
        in `_append_token`) and `spec_idx`, mapping each n-gram tuple
        (n <= spec_ngram) to its (latest, previous) start positions.
        Survives preemption unchanged — eviction re-prefills the SAME
        logical stream."""
        if req.spec_idx is None:
            req.ctx_ids = req.prompt.tolist() + list(req.generated)
            req.spec_idx = {}
            for j in range(len(req.ctx_ids)):
                self._spec_note(req, j)
        return req.ctx_ids, req.spec_idx

    def _spec_note(self, req, j: int) -> None:
        """Index every n-gram ending at position `j` of the context
        (latest occurrence wins; the one it displaces is kept as the
        'previous' slot `_propose` falls back to when latest is the
        trailing gram itself)."""
        ctx = req.ctx_ids
        for n in range(1, self.spec_ngram + 1):
            start = j - n + 1
            if start < 0:
                break
            gram = tuple(ctx[start:j + 1])
            ent = req.spec_idx.get(gram)
            req.spec_idx[gram] = (start,
                                  None if ent is None else ent[0])

    def _grow_for_drafts(self, req, d: list) -> list:
        """Grow `req`'s table to cover its draft rows' write positions
        WITHOUT evicting anyone — drafts are opportunistic, so on pool
        pressure they trim to the blocks already held instead of
        preempting real work (contrast `_ensure_blocks`)."""
        bs = self.block_size
        for al, table, base in zip(self.allocs, req.tables, req.base):
            grow = blocks_for(req.written + len(d) + 1, bs) \
                - base - len(table)
            if d and grow > 0:
                try:
                    table.extend(al.alloc(grow, rid=req.rid))
                except OutOfBlocks as e:
                    self._note_oom(e)
                    cap = (base + len(table)) * bs - 1 - req.written
                    d = d[:max(0, cap)]
        return d

    def _ensure_blocks(self, req, upto: int) -> bool:
        """Make every group's table of `req` cover the positions below
        `upto` that its next program writes (a tick's one position, one
        past the host's for a request with a token in flight; a prefill
        chunk's), evicting the newest-admitted running request on OOM
        (possibly `req` itself). Returns whether `req` is still running.

        A window group first RELEASES the blocks whose last position no
        query from the next one on (`written + in_flight`) can see: they
        go back to the group's free list now, and may be handed to
        another row at once, for a write that this next program or a
        later one performs. The tick in flight may still read them; the
        device runs the programs in the order they were dispatched, so
        it has read them by then.

        Before anything is evicted the tick in flight is landed:
        `_evict` rebuilds `ctx` from `generated`, which has to hold
        every token, and a request that finishes on that tick frees
        blocks, so the allocation is tried again first."""
        bs = self.block_size
        query = req.written + req.in_flight
        end = blocks_for(upto, bs)
        for g, (grp, al) in enumerate(zip(self.groups, self.allocs)):
            table = req.tables[g]
            first = grp.first_live_block(query, bs)
            if first <= req.base[g] and end <= req.base[g] + len(table):
                continue        # most ticks: inside the last block
            dead = min(first - req.base[g], len(table))
            if dead > 0:
                al.release(table[:dead])
                del table[:dead]
                req.base[g] += dead
                self.counters["released"] += dead
            if not table:
                req.base[g] = max(req.base[g], first)
            while (need := end - req.base[g] - len(table)) > 0:
                try:
                    with tracer().span("alloc"):
                        table.extend(al.alloc(need, rid=req.rid))
                except OutOfBlocks as e:
                    self._note_oom(e)
                    if self._land():
                        continue
                    live = [r for r in self.slots if r is not None]
                    victim = max(live, key=lambda r: r.admit_seq)
                    if victim is req and len(live) == 1:
                        # submit() guarantees a lone request fits —
                        # reaching here means the accounting broke
                        raise RuntimeError(
                            "allocator invariant violated: a lone request "
                            f"cannot grow its {grp.name!r} table") from None
                    self._evict(victim)
                    if victim is req:
                        return False
            bound = self._held_bound[g]
            assert not bound or len(table) <= bound, (grp.name, len(table))
        return True

    def _evict(self, req) -> None:
        """Preempt: release the block references NOW, re-queue at the
        front. The request keeps its generated tokens and sampling
        indices — on re-admission it re-prefills prompt + generated
        (re-probing the prefix index, so a still-cached prefix skips
        again) and continues its stream exactly where it stopped.
        Shared blocks other requests still reference stay live; only
        this request's references drop."""
        self._release_all(req)
        req.written = 0
        req.ctx = np.concatenate(
            [req.prompt, np.asarray(req.generated, np.int32)]) \
            if req.generated else req.prompt
        self._lifecycle(req, "preempted",
                        tokens=len(req.generated))
        self.slots[req.slot] = None
        req.slot = None
        req.phase = "queued"
        req.queued_at = self.clock()
        req.n_preempt += 1
        self.counters["preempted"] += 1
        self.queue.appendleft(req)
        self._lifecycle(req, "requeued")

    def _release_all(self, req) -> None:
        """Drop every block reference `req` holds, in every group (a
        pending copy-on-write source's too)."""
        for al, (src, _) in zip(self.allocs, req.cow or ()):
            al.release([src])
        req.cow = None
        for al, table in zip(self.allocs, req.tables):
            al.release(table)
        req.tables, req.base = [], []

    def _append_token(self, req, tok: int) -> None:
        req.generated.append(tok)
        if req.spec_idx is not None:  # keep the draft index current
            req.ctx_ids.append(tok)
            self._spec_note(req, len(req.ctx_ids) - 1)
        req.last_tok = tok
        if req.first_tok_t is None:
            req.first_tok_t = self.clock()
        if len(req.generated) >= req.max_new:
            self._finish(req)

    def _finish(self, req) -> None:
        # donate the sealed aligned prefix to the cache BEFORE the
        # release: indexed blocks whose refcount hits zero park on the
        # cold LRU list (still matchable, reclaimed under pressure)
        # instead of returning to the free list. Only blocks fully
        # covered by PREFILL-written context are sealed — decode-
        # written positions live past len(ctx) and never land in a
        # donated block.
        # A window group donates what it still holds of them, under
        # the same chain hashes (`_match_prefix`).
        if self.prefix is not None and req.tables:
            sealed = min(req.written, len(req.ctx)) // self.block_size
            for ix, table, base in zip(self.prefixes, req.tables, req.base):
                if sealed > base:
                    ix.insert(req.ctx, table[:sealed - base], first=base)
        self._release_all(req)
        self._lifecycle(req, "finished", tokens=len(req.generated))
        if self.lifecycle:
            # bounded retention (FIFO on dict insertion order): a
            # long-running server must not grow one timeline per
            # request forever; the JSONL stream is the full record
            self.timelines[req.rid] = req.timeline
            while len(self.timelines) > TIMELINE_CAP:
                self.timelines.pop(next(iter(self.timelines)))
        self.slots[req.slot] = None
        self.results[req.rid] = np.asarray(req.generated, np.int32)
        self.counters["finished"] += 1
        now = self.clock()
        rec = {
            "id": req.rid,
            "ttft_ms": round((req.first_tok_t - req.arrival) * 1e3, 3),
            "tokens_in": int(req.prompt.shape[0]),
            "tokens_out": len(req.generated),
            "e2e_ms": round((now - req.arrival) * 1e3, 3),
            "wait_ms": round(req.wait_s * 1e3, 3),
            "queue_depth": len(self.queue),
            "preempted": req.n_preempt,
            # schema v11: trace context on the completion record too,
            # so a replica log's request line joins its own lifecycle
            # stream and the router's fleet-edge record by trace id
            "trace": req.trace, "span": req.span,
            "attempt": req.attempt,
        }
        if len(req.generated) > 1:
            rec["tpot_ms"] = round(
                (now - req.first_tok_t) * 1e3 / (len(req.generated) - 1),
                3)
        if self.spec_k > 0:  # schema v9: per-request speculation record
            rec["spec_drafted"] = req.n_drafted
            rec["spec_accepted"] = req.n_accepted
        if self.prefix is not None:  # schema v14: prefix-cache record
            rec["prefix_hit_blocks"] = req.hit_blocks
            rec["prefill_skipped_tokens"] = req.skipped_tok
        self.request_records.append(rec)
        if self.metrics is not None:
            self.metrics.log(event="request", **rec)

    def _maybe_log(self) -> None:
        if (self.metrics is None or self.log_every <= 0
                or self.counters["ticks"] % self.log_every):
            return
        now = self.clock()
        dt = max(now - self._win_t, 1e-9)
        bpt = paged_read_bytes_per_tick(
            self.params, self.cfg, self._last_touched, self.block_size,
            self.max_slots, self.kv_quant, p_bytes=self._p_bytes,
            state_rows=self._last_state_rows)
        ticks_per_sec = self.log_every / dt
        extra = {}
        if self.spec_k > 0:  # schema v9: windowed speculation telemetry
            extra = {"spec_drafted": self._win_drafted,
                     "spec_accepted": self._win_accepted,
                     "spec_accept_rate": round(
                         self._win_accepted / self._win_drafted, 4)
                     if self._win_drafted else 0.0}
        if self.prefix is not None:  # schema v14: prefix-cache gauges
            extra.update(
                prefix_hit_rate=round(
                    self._win_prefix_hits / self._win_prefix_lookups, 4)
                if self._win_prefix_lookups else 0.0,
                cold_blocks=self.alloc.n_cold,
                prefix_blocks=len(self.prefix))
        hr = self.headroom()      # schema v15: capacity-plane gauges
        self.metrics.log(
            event="generate",
            tokens_per_sec=round(self._win_tokens / dt, 2),
            queue_depth=len(self.queue),
            active_slots=sum(1 for r in self.slots if r is not None),
            free_blocks=self.alloc.n_free,
            blocks_touched=sum(self._last_touched),
            bytes_per_tick=int(bpt),
            hbm_gbps=round(ticks_per_sec * bpt / 1e9, 4),
            live_blocks=hr["live_blocks"],
            blocks_needed=hr["blocks_needed"],
            headroom_blocks=hr["headroom_blocks"],
            **extra)
        self._win_tokens = 0
        self._win_drafted = 0
        self._win_accepted = 0
        self._win_prefix_lookups = 0
        self._win_prefix_hits = 0
        self._win_t = now
