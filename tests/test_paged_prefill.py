"""The prefill chunk's read of a K/V pool through its request's table
(`ops.flash_attention.paged_flash_prefill`, PR 34).

- **The kernel is the gathered read.** Interpreted, it matches
  `masked_attention(q, gather_table(pool, bt), valid)` on the chunk's
  true rows to 1e-4, for the three configurations' head geometries, a
  window that binds on a table that starts past position 0, chunks that
  start at 0, inside a block and end before the chunk's length, and
  with several query tiles and several (whole and partial) key steps.
- **It reads what it may and no more.** Every pool block the chunk's
  rows cannot see holds NaN: the scratch block, the table's columns past
  the last true position, the blocks that have left every window.
- **The table's width chooses the read**, and the engine's chunks give
  `generate()`'s tokens through the kernel on an unwindowed and a
  windowed model, prefilled in chunks that start inside blocks; their
  spans say which of the table's columns each chunk walked.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shallowspeed_tpu.models import transformer as T
from shallowspeed_tpu.models.generate import generate
from shallowspeed_tpu.models.kv_cache import masked_attention, position_mask
from shallowspeed_tpu.ops import flash_attention
from shallowspeed_tpu.ops.flash_attention import (paged_flash_prefill,
                                                   paged_prefill_addresses)
from shallowspeed_tpu.serving import ServingEngine
from shallowspeed_tpu.serving.cache import (blocks_for, gather_table,
                                            init_block_pool)
from shallowspeed_tpu.telemetry import trace as trace_mod

BS, HD, C = 8, 8, 32


def gathered_chunk(q, pool, bt, at0, window):
    """What `_prefill_chunk` ran before the kernel: the table gathered
    at its whole width, every row scored over all of it."""
    valid = position_mask(bt.shape[0] * BS,
                          (at0 + jnp.arange(q.shape[0]))[:, None], window)

    class Cfg:                              # all masked_attention reads
        compute_dtype, dtype = None, q.dtype

    return masked_attention(q[None], gather_table(pool, bt[None]),
                            valid[None, None, None], Cfg)[0]


# (first position in the table's coordinates, true length, window, the
# table's first block as a column of the request's whole table)
CHUNKS = {
    "first": (0, C, 0, 0),
    "mid-block": (21, C, 0, 0),
    "short-last": (43, 9, 0, 0),
    "one-token": (64, 1, 0, 0),
    "window": (45, C, 19, 0),
    "window-based": (21, 27, 19, 4),
}


@pytest.mark.parametrize("tiles", [dict(tq=16, chunk=2), dict()],
                         ids=["tiles16-step2", "defaults"])
@pytest.mark.parametrize("heads", [(16, 16), (32, 8), (32, 4)],
                         ids=["mha16", "gqa32-8", "gqa32-4"])
@pytest.mark.parametrize("case", list(CHUNKS))
def test_paged_flash_prefill_matches_gathered_read(case, heads, tiles,
                                                   request):
    """The kernel against the XLA read on the chunk's true rows, in a
    table twice as wide as the prompt needs whose every block the
    chunk may not see is NaN in the kernel's pool: the scratch block
    that pads the table, what lies past the last true position, what
    left the window (the reference reads a pool with zeros there; one
    NaN read would make the whole row NaN)."""
    at0, n_tok, window, base = CHUNKS[case]
    h, hkv = heads
    rng = np.random.default_rng(len(request.node.name))
    n, w = 40, 24
    f = lambda *sh: jnp.asarray(rng.normal(size=sh), jnp.float32)
    pool = {"k": f(n, hkv, BS, HD), "v": f(n, hkv, BS, HD)}
    held = (at0 + n_tok - 1) // BS + 1          # columns with content
    bt = np.zeros(w, np.int32)                  # the rest: scratch
    bt[:held + 3] = rng.permutation(np.arange(1, n))[:held + 3]
    # the table is the request's from block `base` on: before it lies
    # what the window group released, which the query's window, seen
    # from the table's own coordinates, has to leave alone too
    first = max(at0 - window + 1, 0) // BS if window else 0
    seen = set(bt[first:held].tolist())
    dark = jnp.asarray([b not in seen for b in range(n)])
    poisoned = {name: jnp.where(dark[:, None, None, None], jnp.nan, leaf)
                for name, leaf in pool.items()}
    clean = {name: jnp.where(dark[:, None, None, None], 0.0, leaf)
             for name, leaf in pool.items()}
    q = f(C, h, HD)
    got = paged_flash_prefill(q, poisoned, jnp.asarray(bt), jnp.int32(at0),
                              jnp.int32(n_tok), window=window, **tiles)
    assert got.shape == q.shape and bool(jnp.isfinite(got).all())
    ref = gathered_chunk(q, clean, jnp.asarray(bt), at0, window)[:n_tok]
    # the same rows from the request's whole table: a base moves nothing
    whole = np.concatenate([np.zeros(base, np.int32), bt])
    ref_abs = gathered_chunk(q, clean, jnp.asarray(whole), at0 + base * BS,
                             window)[:n_tok]
    scale = float(jnp.abs(ref).max())
    assert float(jnp.abs(ref - ref_abs).max()) / scale <= 1e-6
    assert float(jnp.abs(got[:n_tok] - ref).max()) / scale <= 1e-4


def test_tiles_past_the_chunk_give_zeros_and_padding_rows_are_finite():
    """A 5-token chunk of 32 rows in tiles of 16: the second tile reads
    nothing and is zeros; the first tile's padding rows repeat its last
    true row, whatever lies in the blocks after it."""
    rng = np.random.default_rng(5)
    f = lambda *sh: jnp.asarray(rng.normal(size=sh), jnp.float32)
    pool = {"k": f(8, 2, BS, HD), "v": f(8, 2, BS, HD)}
    bt = jnp.asarray([3, 5, 0, 0], jnp.int32)
    got = paged_flash_prefill(f(C, 4, HD)[:1].repeat(C, 0), pool, bt,
                              jnp.int32(6), jnp.int32(5), tq=16, chunk=1)
    assert bool((got[16:] == 0).all())
    np.testing.assert_allclose(got[5:16], got[4:5].repeat(11, 0), atol=1e-6)


def test_which_pools_and_tables_take_the_paged_chunk():
    """K/V pools in the compute dtype under a table of more than 1,024
    positions do (interpreted, every head size); narrower tables (XLA's
    read of all of one is the quicker, `olmo-1b`'s cells), int8 pools
    and the latent pool keep the gathered read."""
    cfg = T.TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=1,
                              max_seq=64)
    kv = init_block_pool(cfg, 4, BS)[0]
    assert paged_prefill_addresses(kv, 256)
    assert not paged_prefill_addresses(kv, 1024 // BS)
    assert not paged_prefill_addresses(
        init_block_pool(cfg, 4, BS, kv_quant="int8")[0], 256)
    assert not paged_prefill_addresses({"ckr": jnp.zeros((4, 1, BS, 128))},
                                       256)


WINDOW, BLOCK, CHUNK = 12, 4, 8
_BASE = dict(vocab=64, d_model=32, n_heads=4, n_kv_heads=2, n_layers=3,
             max_seq=128, rope=True, norm="rmsnorm", ffn="swiglu", d_ff=48)
MODELS = {
    "full": T.TransformerConfig(**_BASE),
    "windowed": T.TransformerConfig(**_BASE, attn_window=WINDOW),
    "window-and-full": T.TransformerConfig(
        **_BASE, layers=((WINDOW, True), (0, False), (WINDOW, True))),
}


@pytest.mark.parametrize("model", list(MODELS))
def test_chunked_prefill_gives_generates_tokens(model, monkeypatch):
    """Prompts of 37 and 21 tokens prefilled in chunks of 8 through
    blocks of 4 (the second request's prefill between the first one's
    ticks), far past the window where there is one, with every table
    counted as wide: the engine's tokens are `generate()`'s, greedy and
    sampled, every K/V chunk went through the kernel, and each
    `prefill` span says which columns it walked: a full group's from 0
    to the chunk's last position, a window group's from its first
    query's window, never more than the window and the chunk hold."""
    from shallowspeed_tpu.serving import engine as E

    cfg = MODELS[model]
    params = jax.device_put(T.init(cfg, seed=3))
    calls = []
    real = E.paged_flash_prefill
    monkeypatch.setattr(flash_attention, "_PREFILL_MIN_KEYS", 0)
    monkeypatch.setattr(E, "paged_flash_prefill",
                        lambda *a, **kw: calls.append(kw) or real(*a, **kw))
    E.clear_program_caches()
    tr = trace_mod.configure(level="off")
    eng = ServingEngine(params, cfg, n_blocks=64, block_size=BLOCK,
                        max_slots=2, prefill_chunk=CHUNK)
    rng = np.random.default_rng(11)
    prompts = {f"r{t}": rng.integers(0, cfg.vocab, t).astype(np.int32)
               for t in (37, 21)}
    for seed, (rid, p) in enumerate(prompts.items()):
        eng.submit(p, 12, temperature=0.8 * seed, seed=seed, rid=rid)
    eng.run()
    E.clear_program_caches()
    # traced with each layer's window (a block is jitted: layers of one
    # kind share a trace, so not once a layer)
    assert {kw["window"] for kw in calls} == {w for w, _ in cfg.layer_specs}
    for seed, (rid, p) in enumerate(prompts.items()):    # greedy, sampled
        want = generate(params, p[None, :], cfg, 12,
                        temperature=0.8 * seed, seed=seed)
        np.testing.assert_array_equal(eng.results[rid], np.asarray(want)[0])
    chunks = [e[5] for e in tr.ring() if e[2] == "prefill"]
    trace_mod.configure(level="off")
    assert len(chunks) == eng.counters["prefill_chunks"] == 5 + 3
    bound = blocks_for(WINDOW, BLOCK) + blocks_for(CHUNK, BLOCK)
    for a in chunks:
        end = min(len(prompts[a["rid"]]), (a["chunk"] + 1) * CHUNK)
        walked = {"full": blocks_for(end, BLOCK),
                  "window": blocks_for(end, BLOCK) - max(
                      a["chunk"] * CHUNK - WINDOW + 1, 0) // BLOCK}
        for g in eng.groups:
            assert a[f"blocks_read_{g.name}"] == walked[g.name]
            assert g.name == "full" or walked[g.name] <= bound
        assert a["blocks_read"] == sum(walked[g.name] for g in eng.groups) \
            <= a["blocks_table"]
    for k in ("blocks_read", "blocks_table"):
        assert eng.counters[f"prefill_{k}"] == sum(a[k] for a in chunks)
    assert eng.counters["prefill_blocks_read"] \
        < eng.counters["prefill_blocks_table"]
