"""Paged KV cache: block pools, a host-side free-list allocator, and
the gathered-table read (the prefill chunk's, and the reference of the
decode tick's paged kernel).

The contiguous decode cache (`models/kv_cache.init_kv_cache`) sizes one
(B, Hkv, slots, hd) buffer per request batch — fine for one `generate()`
call, useless for a server where requests of different lengths join and
leave continuously: every admission would recompile, and every short
request would pay the longest request's slots. The paged layout instead
carves each layer's cache into fixed `(n_blocks, Hkv, block_size, hd)`
POOLS (vLLM's PagedAttention memory model, arXiv 2309.06180, rebuilt
jit-first): a request owns an ordered list of block ids (its *block
table*), the pools are donated through every compiled program and
written IN PLACE (`write_rows`, `write_chunk`: scatters indexed on the
leading dimension only, so the donated buffer keeps its layout and no
pool-sized copy runs). The decode tick reads the pool where it lies:
`ops.flash_attention.paged_flash_decode` walks each row's live blocks
through the table, one query a row. The prefill chunk, whose queries
amortise it, reads through a GATHERED view of its one row's table —
`pool[bt]` — masked by position. Appending a token allocates at
most one block; freeing a finished request returns its blocks in O(1);
fragmentation cannot exist because any free block serves any request.

Block 0 is RESERVED as a scratch sink: compiled programs run at a fixed
slot capacity, so inactive slots (and the blocks past the true end of a
prefill chunk) still execute their cache write — they are steered to
block 0, which no live table ever contains. That keeps the tick free of
host-side branching without ever corrupting a live block.

int8 pools mirror the contiguous int8 cache exactly (same per-(row,
head, position) absmax scales via `kv_cache.quantize_kv`), so the paged
sweep halves its bytes the same way.

A layer's pool is of the layer's kind (`init_block_pool`): K and V
heads, or for a latent-attention layer ONE row a token that all heads
share; only what a write stores (`_kv_update`) and how the chunk reads
the gathered table differ (the tick's kernel reads a latent row as key
and value both).

Layers fall into GROUPS by how their cache grows (`layer_groups`, from
`cfg.layer_specs`): `full` layers keep every token of a request,
`window` layers only the blocks that a future query can still see. Each
group has pools of its own size, a `BlockAllocator` of its own, and a
request holds ONE TABLE A GROUP. A window group's table starts at the
first block the request still holds: the engine releases a block to the
group's free list once its last position has left the window of every
query to come (`first_live_block`), and hands the programs the table
with `base`, the position of its first entry's first token. Relative to
`base` everything is as in a full group (a rotated key carries its
position in its values, a mask compares differences), so the kernel and
the writes are the same for both kinds. A model with one kind of layer
has one group and one table, as before there were groups.

A layer with a state-space mixer beside its attention heads
(`cfg.mixer`) holds a SECOND kind of state next to its K/V pool, in the
same per-layer dict: the leaves `STATE_LEAVES`, one row a SLOT and not a
table of blocks, of a fixed size whatever the context is (`conv`, the
convolution's last inputs, and `ssm`, the heads' float32 matrices). A
request keeps its slot from admission to its finish or eviction, so the
tick's row index is the slab's row. Every token rewrites its row whole:
the tick passes over the whole slab once, elementwise, a row that does
not decode keeping what it held (so the slabs need no scratch row, as
block 0 is the pools': nothing is steered anywhere); the chunk takes
its request's row and puts it back, indexed on the leading dimension
alone. Both run in place on the donated buffers like the pools' writes. The block helpers
here (`pool_block_size`, the kernels' address checks, copy-on-write)
are for the K/V leaves: `kv_leaves` is a layer's dict without its slabs.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass, replace

import jax.numpy as jnp
import numpy as np

from shallowspeed_tpu.models import transformer as T
from shallowspeed_tpu.models.kv_cache import (KV_QUANT_MODES, STATE_LEAVES,
                                              mixer_state, quantize_kv)
from shallowspeed_tpu.ops.ssm import state_shapes

SCRATCH_BLOCK = 0


class OutOfBlocks(RuntimeError):
    """The free list is empty. The scheduler's preemption policy (evict
    the newest running request, re-queue it with its blocks freed)
    catches this; it never escapes a `ServingEngine.step`.

    Typed payload (round 20, the memory observatory): handlers and
    forensics read `requested`/`n_free`/`n_cold`/`n_live`/`rid`
    directly instead of string-matching the message. The message keeps
    its historical "need N blocks, F free + C cold" shape."""

    def __init__(self, requested: int, n_free: int = 0, n_cold: int = 0,
                 n_live: int = 0, rid=None, group: str = ""):
        self.requested = int(requested)
        self.n_free = int(n_free)
        self.n_cold = int(n_cold)
        self.n_live = int(n_live)
        self.rid = rid
        self.group = group      # the layer group whose pool ran out
        msg = (f"need {self.requested} blocks, {self.n_free} free + "
               f"{self.n_cold} cold")
        if group:
            msg += f" in the {group!r} group"
        if rid is not None:
            msg += f" (request {rid!r})"
        super().__init__(msg)


def blocks_for(n_tokens: int, block_size: int) -> int:
    """Blocks needed to hold `n_tokens` cache positions."""
    return max(0, -(-int(n_tokens) // int(block_size)))


LATENT = "ckr"      # a latent layer's one leaf: [c | k_rope | 0] a token
LANES = 128


def kv_leaves(pool_blk) -> dict:
    """A layer's block pool alone: its dict without a mixer's slabs."""
    return {name: leaf for name, leaf in pool_blk.items()
            if name not in STATE_LEAVES}


def state_leaves(pool_blk) -> dict:
    """A layer's per-slot slabs ({} where the layer has no mixer)."""
    return {name: pool_blk[name] for name in STATE_LEAVES
            if name in pool_blk}


def state_row_bytes(cfg: T.TransformerConfig) -> int:
    """Bytes ONE slot's state takes in ONE mixer layer (0: no mixer)."""
    if not cfg.mixer:
        return 0
    shapes = state_shapes(cfg, 1)       # no array is made to count it
    return int(np.prod(shapes["ssm"])) * 4 + int(np.prod(shapes["conv"])) \
        * np.dtype(cfg.compute_dtype or cfg.dtype).itemsize


def pool_block_size(pool_blk) -> int:
    """Positions a block holds, whatever kind of pool it is."""
    return next(iter(kv_leaves(pool_blk).values())).shape[2]


@dataclass(frozen=True)
class LayerGroup:
    """Layers whose cache grows the same way: `window` 0 keeps every
    token (`full`), else the last `window` (`window`)."""
    name: str
    window: int
    layers: tuple

    def first_live_block(self, query_pos: int, block_size: int) -> int:
        """The first block (as a column of an absolute table) that a
        query at `query_pos` or later still sees: key j is visible to
        query i iff i - window < j <= i, so every block before that of
        position `query_pos - window + 1` is dead for good."""
        if self.window <= 0:
            return 0
        return max(query_pos - self.window + 1, 0) // block_size

    def held_bound(self, block_size: int, ahead: int) -> int:
        """Most blocks a request holds in this group while it writes up
        to `ahead` positions in one program (a prefill chunk, a tick's
        draft rows): a window's `ceil(window / bs) + 1`, plus the
        blocks of what is being written. 0 = no bound (`full`)."""
        if self.window <= 0:
            return 0
        return blocks_for(self.window, block_size) + 1 \
            + blocks_for(ahead, block_size)


def layer_groups(cfg: T.TransformerConfig) -> tuple:
    """The model's layers by kind of growth, from `cfg.layer_specs`:
    the `full` group first where there is one, then the `window` group
    (the windowed layers of a model share one window size)."""
    windows = sorted({w for w, _ in cfg.layer_specs})
    return tuple(LayerGroup(
        "window" if w else "full", w,
        tuple(i for i, (wi, _) in enumerate(cfg.layer_specs) if wi == w))
        for w in windows)


def group_of_layer(cfg: T.TransformerConfig) -> tuple:
    """For each layer the index of its group in `layer_groups(cfg)`."""
    groups = layer_groups(cfg)
    return tuple(next(g for g, grp in enumerate(groups) if i in grp.layers)
                 for i in range(cfg.n_layers))


def group_blocks(cfg: T.TransformerConfig, n_blocks) -> dict:
    """{group name: n_blocks} from an int (every group that many) or a
    dict that names every group."""
    names = [g.name for g in layer_groups(cfg)]
    if isinstance(n_blocks, dict):
        if set(n_blocks) != set(names):
            raise ValueError(f"n_blocks names {sorted(n_blocks)}; the "
                             f"model's layer groups are {names}")
        return {n: int(n_blocks[n]) for n in names}
    return {n: int(n_blocks) for n in names}


def init_block_pool(cfg: T.TransformerConfig, n_blocks,
                    block_size: int, kv_quant: str = "", slots: int = 0):
    """Per-layer paged pools, zero-filled, each of its layer's kind and
    of its group's size (`n_blocks`: an int, or {group name: blocks}).

    A K/V layer holds (n_blocks, Hkv, block_size, hd) for `k` and `v`;
    int8 pools add the (n_blocks, Hkv, block_size, 1) f32 scale planes,
    matching `init_kv_cache`'s int8 variant per-position. Layout is the
    contiguous cache's head-major sweep with the slot axis folded into
    (block id, offset). A latent layer (`cfg.latent`) holds ONE row a
    token for all heads, (n_blocks, 1, block_size, r + dr rounded up to
    whole lanes): the normed down-projection and the rotated shared key
    side by side, which is how the absorbed read wants them (one
    contraction), then zeros. The rounding is not optional on the TPU:
    for a minor dimension of 576 its compact layout puts the BLOCK
    dimension minor-most, and every program then copies the whole pool
    in and out around its scatter (compiled for the described v5e, PR
    28: two pool-sized copies a layer at 576, none at 512 or 640; the
    tiled layout pads 576 to 640 lanes either way).
    Every kind shares the block ids: one allocator, one table a
    request. A model with a mixer (`cfg.mixer`) also gets its slabs in
    every layer's dict, `slots` rows."""
    if cfg.mixer:
        if slots < 1:
            raise ValueError("a model with a state-space mixer keeps one "
                             "row of state a slot: pass slots >= 1")
        pools = init_block_pool(replace(cfg, ssm_heads=0), n_blocks,
                                block_size, kv_quant)
        return [{**pool, **mixer_state(cfg, slots)} for pool in pools]
    if kv_quant not in KV_QUANT_MODES:
        raise ValueError(
            f"unsupported kv_quant={kv_quant!r}; expected one of "
            f"{KV_QUANT_MODES} ('' = pool in the compute dtype)")
    sizes = group_blocks(cfg, n_blocks)
    if min(sizes.values()) < 2:
        raise ValueError(f"n_blocks={n_blocks} leaves no usable blocks "
                         f"past the reserved scratch block")
    names = [g.name for g in layer_groups(cfg)]
    per_layer = [sizes[names[g]] for g in group_of_layer(cfg)]
    dt = cfg.compute_dtype or cfg.dtype
    if cfg.latent:
        if kv_quant:
            raise ValueError("a latent pool has no int8 form: kv_quant "
                             "must be '' with latent attention")
        tail = (1, block_size, -(-cfg.latent_width // LANES) * LANES)
        return [{LATENT: jnp.zeros((n,) + tail, dt)} for n in per_layer]
    tail = (cfg.kv_heads, block_size, cfg.head_dim)
    if kv_quant:
        stail = tail[:2] + (1,)
        return [{"k": jnp.zeros((n,) + tail, jnp.int8),
                 "k_s": jnp.zeros((n,) + stail, jnp.float32),
                 "v": jnp.zeros((n,) + tail, jnp.int8),
                 "v_s": jnp.zeros((n,) + stail, jnp.float32)}
                for n in per_layer]
    return [{"k": jnp.zeros((n,) + tail, dt), "v": jnp.zeros((n,) + tail, dt)}
            for n in per_layer]


class BlockAllocator:
    """Host-side refcounted free list over one pool's block ids.

    Pure bookkeeping — no device arrays. Every live block carries a
    refcount: `alloc` mints fresh blocks at refcount 1, `acquire` adds
    a reference to a block another holder already owns (prefix-cache
    sharing), `release`/`free` drops one reference per listed id. A
    block whose refcount hits zero returns to the free list — unless a
    `PrefixIndex` still remembers its content, in which case it parks
    on the COLD list (LRU-ordered, oldest first) where it stays
    matchable until pool pressure reclaims it: `alloc` drains cold
    blocks (dropping their index entries) before `OutOfBlocks` fires.

    Invariants (pinned in tests/test_serving.py):
    `n_free + n_live + n_cold == n_usable` always; refcounts are
    per-holder, so at drain `n_live == 0`; `release` rejects ids whose
    listed multiplicity exceeds the current refcount — including
    duplicates WITHIN one call (`free([i, i])` of a once-held block
    raises instead of double-appending `i` to the free list); block 0
    (scratch) is never handed out."""

    def __init__(self, n_blocks: int, index: "PrefixIndex | None" = None,
                 group: str = ""):
        if n_blocks < 2:
            raise ValueError(f"n_blocks={n_blocks} leaves no usable "
                             f"blocks past the reserved scratch block")
        self.n_blocks = int(n_blocks)
        self.group = group      # the layer group this pool belongs to
        # LIFO free list: recently freed (still-warm) blocks are reused
        # first; ids 1..n-1 — block 0 is the scratch sink
        self._free = list(range(self.n_blocks - 1, 0, -1))
        self._ref: dict[int, int] = {}
        # insertion-ordered dict as the LRU cold list: front = oldest
        # (first reclaimed), back = most recently parked
        self._cold: dict[int, None] = {}
        self.index = index
        self.cold_reclaims = 0
        # high-water of n_live over the allocator's lifetime (round 20
        # capacity accounting: tokens-per-peak-live-block in bench)
        self.peak_live = 0

    @property
    def n_usable(self) -> int:
        return self.n_blocks - 1

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_live(self) -> int:
        return len(self._ref)

    # back-compat alias (pre-refcount callers/tests)
    n_allocated = n_live

    @property
    def n_cold(self) -> int:
        return len(self._cold)

    def refcount(self, bid: int) -> int:
        return self._ref.get(bid, 0)

    def alloc(self, n: int, rid=None) -> list[int]:
        """Mint `n` fresh blocks at refcount 1, or raise OutOfBlocks
        WITHOUT partial allocation (all-or-nothing, so a failed
        admission never leaks). Under pressure, cold cached blocks are
        reclaimed LRU-first (their index entries dropped) before the
        raise — referenced blocks are never touched. `rid` (the
        requesting request id, when the caller has one) rides the
        typed OutOfBlocks payload into the OOM forensics."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free) + len(self._cold):
            raise OutOfBlocks(n, n_free=len(self._free),
                              n_cold=len(self._cold),
                              n_live=len(self._ref), rid=rid,
                              group=self.group)
        while len(self._free) < n:
            self._reclaim_one()
        ids = [self._free.pop() for _ in range(n)]
        for i in ids:
            self._ref[i] = 1
        self.peak_live = max(self.peak_live, len(self._ref))
        return ids

    def _reclaim_one(self) -> None:
        bid = next(iter(self._cold))          # oldest parked = LRU
        del self._cold[bid]
        if self.index is not None:
            self.index.drop_block(bid)
        self._free.append(bid)
        self.cold_reclaims += 1

    def acquire(self, ids) -> None:
        """Add one reference per listed id to blocks that are live or
        cold (prefix-cache hit). Cold blocks are revived off the LRU
        list. All-or-nothing: validates before mutating."""
        ids = list(ids)
        bad = [i for i in ids if i not in self._ref and i not in self._cold]
        if bad:
            raise ValueError(f"acquire() of unknown block(s) {bad}")
        for i in ids:
            self._cold.pop(i, None)
            self._ref[i] = self._ref.get(i, 0) + 1
        self.peak_live = max(self.peak_live, len(self._ref))

    def release(self, ids) -> None:
        """Drop one reference per listed id. At refcount zero the block
        parks cold if the index still maps its content, else returns to
        the free list. Rejects (before any mutation) ids whose listed
        multiplicity exceeds the current refcount — the duplicate-id
        double-free of old `free([i, i])` raises here."""
        ids = list(ids)
        counts: dict[int, int] = {}
        for i in ids:
            counts[i] = counts.get(i, 0) + 1
        bad = [i for i, c in counts.items() if self._ref.get(i, 0) < c]
        if bad:
            raise ValueError(
                f"release() of unallocated/over-released block(s) "
                f"{sorted(bad)}")
        for i in ids:
            self._ref[i] -= 1
            if self._ref[i] == 0:
                del self._ref[i]
                if self.index is not None and self.index.has_block(i):
                    self._cold[i] = None      # park: most-recent at back
                else:
                    self._free.append(i)

    # `free` kept as the historical name for dropping ownership
    free = release

    def snapshot(self) -> dict:
        """Point-in-time occupancy for the capacity timeline and OOM
        forensics. `consistent` restates the allocator invariant
        (n_free + n_live + n_cold == n_usable) so a dump self-reports
        bookkeeping corruption."""
        return {"group": self.group,
                "n_blocks": self.n_blocks, "n_usable": self.n_usable,
                "n_free": self.n_free, "n_live": self.n_live,
                "n_cold": self.n_cold, "peak_live": self.peak_live,
                "cold_reclaims": self.cold_reclaims,
                "consistent": (self.n_free + self.n_live + self.n_cold
                               == self.n_usable)}


def chunk_hashes(tokens, block_size: int) -> list[bytes]:
    """Chained content hashes of the FULL block-aligned chunks of
    `tokens`: hash k = blake2b(hash k-1 || tokens[k*bs:(k+1)*bs]), so a
    chunk's hash pins the entire prefix through it — two prompts share
    hash k iff their first (k+1)*bs tokens are identical. The partial
    tail (len % bs != 0 remainder) is never hashed: prefix hits are
    granular to whole blocks. Shared by the engine-side `PrefixIndex`
    and the router's sticky-affinity fingerprints so both sides agree
    on chunk identity. blake2b-128 keyed by content, not Python
    `hash()` — stable across processes and collision-safe at fleet
    scale."""
    toks = np.asarray(tokens, dtype=np.int64)
    bs = int(block_size)
    out: list[bytes] = []
    h = b""
    for k in range(len(toks) // bs):
        h = hashlib.blake2b(h + toks[k * bs:(k + 1) * bs].tobytes(),
                            digest_size=16).digest()
        out.append(h)
    return out


class PrefixIndex:
    """Content-addressed map from chained chunk hashes to block ids.

    `match(tokens)` walks the chain front-to-back and returns the block
    ids of the longest indexed aligned prefix (stops at the first
    miss). `insert` registers a finished request's sealed prefix blocks
    first-writer-wins: a chunk hash already mapped keeps its existing
    block (the duplicate block stays unindexed and frees normally), so
    one content never aliases two blocks. `drop_block` is the
    allocator's cold-reclaim hook — dropping a parent makes every
    descendant chain-unreachable via `match` even though the child
    entries linger until their own reclaim (harmless: match walks
    parent-first)."""

    def __init__(self, block_size: int):
        self.block_size = int(block_size)
        self._blocks: dict[bytes, int] = {}    # chain hash -> block id
        self._hash_of: dict[int, bytes] = {}   # block id -> chain hash
        self.hits = 0
        self.misses = 0

    def __len__(self) -> int:
        return len(self._blocks)

    def has_block(self, bid: int) -> bool:
        return bid in self._hash_of

    def match(self, tokens) -> list[int]:
        ids: list[int] = []
        for h in chunk_hashes(tokens, self.block_size):
            bid = self._blocks.get(h)
            if bid is None:
                break
            ids.append(bid)
        return ids

    def lookup(self, hashes) -> list:
        """The block id indexed under each chain hash, None where there
        is none: what a group that no longer holds a prefix's first
        blocks (`LayerGroup.first_live_block`) can still offer."""
        return [self._blocks.get(h) for h in hashes]

    def insert(self, tokens, table, first: int = 0) -> int:
        """Map the full chunks `first`, `first + 1`, ... of `tokens` to
        the given block ids, `len(table)` of them (first-writer-wins;
        `first` > 0 where the table no longer starts at the prompt's
        first block). Returns how many NEW entries landed."""
        new = 0
        for k, h in enumerate(chunk_hashes(tokens, self.block_size)):
            k -= first
            if k < 0:
                continue
            if k >= len(table):
                break
            bid = int(table[k])
            if h in self._blocks or bid in self._hash_of:
                continue
            self._blocks[h] = bid
            self._hash_of[bid] = h
            new += 1
        return new

    def drop_block(self, bid: int) -> None:
        h = self._hash_of.pop(bid, None)
        if h is not None:
            self._blocks.pop(h, None)


def gather_table(pool_blk, bt):
    """Read one layer's cache through a block table: the XLA reference
    that the decode tick's kernel and the prefill chunk's
    (`ops.flash_attention.paged_flash_decode`, `paged_flash_prefill`,
    which gather nothing) are pinned against in the tests, and the read
    of the pools those do not take (`paged_prefill_addresses`).

    pool_blk: {"k"/"v": (N, Hkv, bs, hd)[, "k_s"/"v_s": (N, Hkv, bs, 1)]}
    bt: (rows, W) int32 block ids (padding rows/tail point at the
    scratch block — the caller's position mask never admits them).
    Returns the PAGED view {"k"/"v": (rows, W, Hkv, bs, hd), ...} as the
    gather leaves it: page w, slot s of a row IS absolute position
    w*bs + s because tables are ordered. `kv_cache.masked_attention`
    contracts over it. It is as wide as the table's bucket whatever the
    rows hold: for every slot of a tick with one query each it was 63%
    of `olmo-1b.chat`'s device time (PERF.md, PR 29), and for one row
    and a chunk of queries, with float32 scores over all of it, 41% of
    a chunk in `mistral-7b-v0.1.doc-batch` (PR 34)."""
    return {name: leaf[bt] for name, leaf in pool_blk.items()}


def _kv_update(pool_blk, k_rows, v_rows, quant: bool):
    """{leaf name: (rows, Hkv, tail)} — the values a write stores, in
    the pool's own dtypes. Quantization matches
    `kv_cache.cache_write`'s int8 path value-for-value (same
    absmax-over-hd scales). A latent pool stores its two halves, c
    (rows, 1, r) as `k_rows` and the shared rotary key (rows, 1, dr) as
    `v_rows`, side by side in its one leaf, zeros after them."""
    if LATENT in pool_blk:
        row = jnp.concatenate([k_rows, v_rows], axis=-1)
        pad = pool_blk[LATENT].shape[-1] - row.shape[-1]
        return {LATENT: jnp.pad(row, ((0, 0), (0, 0), (0, pad))
                                ).astype(pool_blk[LATENT].dtype)}
    if quant:
        kq, ks = quantize_kv(k_rows[:, :, None, :])   # (rows,Hkv,1,hd)
        vq, vs = quantize_kv(v_rows[:, :, None, :])
        return {"k": kq[:, :, 0], "k_s": ks[:, :, 0],
                "v": vq[:, :, 0], "v_s": vs[:, :, 0]}
    return {"k": k_rows.astype(pool_blk["k"].dtype),
            "v": v_rows.astype(pool_blk["v"].dtype)}


def write_rows(pool_blk, k_rows, v_rows, blk_ids, offs, quant: bool):
    """Scatter per-row single-token K/V into one layer's pools.

    k_rows/v_rows: (rows, Hkv, hd) in compute dtype (a latent pool: c
    and the rotary key, `_kv_update`); blk_ids/offs:
    (rows,) int32 destination (block id, in-block offset). Rows steered
    to the scratch block may collide — by construction nothing ever
    reads scratch, so the unspecified duplicate-scatter winner is
    irrelevant.

    The scatter goes through the FLAT view (N*Hkv*bs, tail), row
    (blk*Hkv + h)*bs + off: one indexed dimension, the leading one.
    `pool.at[blk, :, off, :]` indexes dimensions 0 and 2, and XLA:TPU
    gives such a scatter an operand layout with the indexed dimensions
    major ({3,1,2,0}) while the donated pool lives in {3,2,1,0}: two
    pool-sized copies per leaf per program run (PERF.md, PR 27). The
    flat view keeps the pool's own layout, so the scatter updates the
    donated buffer in place. The reshape is a bitcast when `bs` is a
    multiple of the dtype's sublane tile and `tail` fills the lanes
    (bs 16, hd 128: bf16, f32 and int8 alike); otherwise (the
    (N, Hkv, bs, 1) scale planes of int8 pools, toy shapes) XLA
    relayouts that leaf as it did before, and nothing large rides on
    it."""
    n, hkv, bs, _ = next(iter(pool_blk.values())).shape
    row = ((blk_ids[:, None] * hkv + jnp.arange(hkv)) * bs
           + offs[:, None]).reshape(-1)               # (rows * Hkv,)
    out = {}
    for name, val in _kv_update(pool_blk, k_rows, v_rows, quant).items():
        tail = val.shape[-1]
        flat = pool_blk[name].reshape(n * hkv * bs, tail)
        out[name] = flat.at[row].set(
            val.reshape(-1, tail)).reshape(n, hkv, bs, tail)
    return out


def write_chunk(pool_blk, k_rows, v_rows, table, pos0, n_tok,
                quant: bool):
    """Write a prefill chunk: rows j < n_tok of k_rows/v_rows (C, Hkv,
    hd) land at the CONSECUTIVE positions pos0 + j of the request whose
    block table is `table` (W,); rows beyond `n_tok` are padding and
    land nowhere.

    Consecutive positions touch at most C/bs + 1 blocks, so instead of
    scattering C * Hkv rows the chunk gathers those few blocks, merges
    its rows into them and scatters WHOLE blocks back: one indexed
    dimension, the leading one, in the pool's own layout — in place
    like `write_rows`, with C/bs + 1 indices. `pos0` need not be
    block-aligned (a fully aligned prefix hit re-prefills the last
    token of its copied tail block) and `n_tok` may end mid-block, so
    the first and the last block are MERGED: slots outside
    [pos0, pos0 + n_tok) keep what they held. Block slots past the
    chunk's true end are steered to the scratch block and write its
    own contents back. Decode cannot use this form: draft rows of one
    request share a block (duplicate indices, different contents)."""
    c = k_rows.shape[0]
    bs = pool_block_size(pool_blk)
    tb = pos0 // bs + jnp.arange((c + 2 * bs - 2) // bs)   # table slots
    ids = jnp.where(tb * bs < pos0 + n_tok,
                    table[jnp.clip(tb, 0, table.shape[0] - 1)],
                    SCRATCH_BLOCK)
    slot = tb[:, None] * bs + jnp.arange(bs)               # (T, bs) pos
    fresh = (slot >= pos0) & (slot < pos0 + n_tok)
    src = jnp.clip(slot - pos0, 0, c - 1)                  # chunk row
    out = {}
    for name, val in _kv_update(pool_blk, k_rows, v_rows, quant).items():
        rows = jnp.swapaxes(val[src], 1, 2)                # (T,Hkv,bs,tail)
        merged = jnp.where(fresh[:, None, :, None], rows,
                           pool_blk[name][ids])
        out[name] = pool_blk[name].at[ids].set(merged)
    return out


# ------------------------------------------------ per-tick HBM model
#
# `models/generate.decode_read_bytes_per_token` prices one contiguous
# decode step: params + the FULL cache sweep. The paged tick's useful
# sweep is only the LIVE blocks its requests touch — the number below
# is the per-tick generalization the serving progress lines report,
# and since PR 29 what the tick's kernel reads (the engine's
# `blocks_read` / `blocks_table` counters say how much of the table's
# room that was).


def param_read_bytes(params, cfg: T.TransformerConfig) -> int:
    """Bytes one decode pass reads for the parameters alone, at the
    PER-LEAF dtypes decode actually consumes after `cast_params`
    (eval_shape — no on-device copy): float leaves at the compute
    dtype, quantized-storage leaves (int8/fp8 `Wq` + f32 `Ws` scales,
    `T.quantize_weights`) at their storage dtypes — cast_params skips
    them, so an int8-weight model prices at ~0.5x its bf16 self. One
    model can mix int8 weights, f32 scales, bf16 embeddings, and int8
    KV (priced separately below) in a single accounting. Pinned in
    tests/test_serving.py against the traced decode tick's own param
    invar bytes (the walker pin, same trick as
    `decode_read_bytes_per_token` in PR 5). Constant for an engine's
    lifetime: callers on a hot path compute it once and pass it back
    in."""
    import jax

    from shallowspeed_tpu.analysis.walker import aval_bytes

    cast = jax.eval_shape(lambda p: T.cast_params(p, cfg.compute_dtype),
                          params)
    return int(sum(aval_bytes(l) for l in
                   jax.tree_util.tree_leaves(cast)))


def paged_read_bytes_per_tick(params, cfg: T.TransformerConfig,
                              blocks_touched, block_size: int,
                              n_rows: int, kv_quant: str = "",
                              p_bytes: int | None = None,
                              state_rows: int = 0) -> int:
    """HBM bytes one decode tick usefully moves: every param leaf
    (at its ACTUAL post-cast dtype — int8/fp8 weights and f32 scales
    included, see `param_read_bytes`) + the K/V bytes of the live
    blocks the tick's active requests attend over (+ int8 scale
    planes) + the token ids. `blocks_touched` = sum over active rows
    of blocks_for(context_len) — the live-blocks generalization of the
    contiguous model's full-cache sweep — or one such sum a layer group
    (`layer_groups(cfg)`'s order), where a window group's rows count
    the blocks their windows reach and no more. Pass a precomputed `p_bytes`
    (`param_read_bytes`) on hot paths — the param term never changes.
    `state_rows`: the rows whose mixer state the tick advances, each
    read AND written whole in every layer (`state_row_bytes`).

    This is the byte model behind the fast-decode gates: the
    int8-weight tick must price at <= 0.55x its bf16 baseline (pinned
    in tests/test_serving.py against walker-traced invar bytes), and
    the serving progress lines' hbm_gbps derives from it."""
    import numpy as np

    if p_bytes is None:
        p_bytes = param_read_bytes(params, cfg)
    kv_itemsize = (1 if kv_quant == "int8"
                   else np.dtype(cfg.compute_dtype or cfg.dtype).itemsize)
    per_token = (cfg.latent_width if cfg.latent
                 else 2 * cfg.kv_heads * cfg.head_dim)
    per_block = block_size * per_token * kv_itemsize
    if kv_quant == "int8":
        per_block += 2 * cfg.kv_heads * block_size * 4   # f32 scales
    groups = layer_groups(cfg)
    if np.ndim(blocks_touched) == 0:
        blocks_touched = [blocks_touched] * len(groups)
    layer_blocks = sum(len(g.layers) * int(b)
                       for g, b in zip(groups, blocks_touched, strict=True))
    return p_bytes + layer_blocks * per_block + n_rows * 4 \
        + 2 * int(state_rows) * cfg.n_layers * state_row_bytes(cfg)
