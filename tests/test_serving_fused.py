"""A step that holds a prefill chunk dispatches ONE program: the decode
tick's rows ride in `_prefill_chunk` (`ServingEngine._decode_step`),
and a prompt's first token travels the way every later one does, in
flight, landed one step late.

What has to hold, over the families the tree serves (a K/V pool, window
and full groups, a latent pool with routed layers, a mixer's slabs):

- **The same tokens.** Prompts that prefill while others decode: every
  stream is its solo oracle's, greedy and sampled, and the stream of
  the loop that lands every program in the step that dispatched it
  (`spec_k > 0`), which is the order of the engine before this one.
- **A prompt's last chunk blocks on nothing**: its dispatch closes
  before any fetch of that step, the request has a row with `ahead` set
  in the very next tick, its first token is `generate()`'s.
- **The edges**: `max_new == 1`, a prompt into an empty engine (every
  row of the tick dead), the prefilling request evicted while the
  tick's rows are prepared, a decoder evicted with the fused program in
  flight, drafts, a prefix-cache hit whose copy-on-write pair rides.
- **One program a chunk-table width**, warmed by single requests.
- **The routed counts** of the tick's rows are the tick's own.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shallowspeed_tpu.models import transformer as T
from shallowspeed_tpu.models.generate import generate
from shallowspeed_tpu.serving import ServingEngine
from shallowspeed_tpu.serving.engine import _decode_tick, _prefill_chunk
from shallowspeed_tpu.telemetry.trace import tracer

from test_serving_lookahead import (ATTRS, CFGS, NAME, PARENT, SEQ, T0, T1,
                                    WINDOW_BLOCKS, assert_solo, toks)

FAMILIES = dict(CFGS, mixer=T.TransformerConfig(
    vocab=64, d_model=32, n_heads=4, n_kv_heads=2, attn_head_dim=8,
    n_layers=2, max_seq=128, rope=True, norm="rmsnorm", ffn="swiglu",
    d_ff=48, ssm_heads=4, ssm_head_dim=8, ssm_state=8, ssm_groups=2,
    ssm_conv=4))


@pytest.fixture(scope="module", params=list(FAMILIES))
def family(request):
    cfg = FAMILIES[request.param]
    parts = T.BLOCK_PARTS if cfg.layers else ()
    return cfg, jax.device_put(T.init(cfg, seed=5, parts=parts))


@pytest.fixture(scope="module")
def kv():
    return CFGS["kv"], jax.device_put(T.init(CFGS["kv"], seed=5))


def engine(model, n_blocks=48, max_slots=4, **kw):
    cfg, params = model
    if cfg.layers:
        n_blocks = {"full": n_blocks, "window": min(n_blocks, WINDOW_BLOCKS)}
    return ServingEngine(params, cfg, n_blocks=n_blocks, block_size=8,
                         max_slots=max_slots, prefill_chunk=16,
                         lifecycle=False, **kw)


def spans_since(first):
    return tracer().ring()[-(tracer().event_count - first):]


def drained(eng):
    return (eng._flight is None and eng.pending() == 0
            and all(al.n_free + al.n_cold == al.n_usable
                    for al in eng.allocs))


# (rid, prompt tokens, max_new, temperature, seed, submitted after step):
# prompts of two to four chunks arrive while earlier requests decode
MIXES = {
    "greedy": [("a", 6, 26, 0.0, 0, 0), ("b", 11, 22, 0.0, 0, 0),
               ("c", 50, 7, 0.0, 0, 2), ("d", 33, 9, 0.0, 0, 5)],
    "sampled": [("a", 6, 26, 1.0, 3, 0), ("b", 11, 22, 0.7, 8, 0),
                ("c", 50, 7, 0.9, 1, 2), ("d", 33, 9, 1.0, 6, 5)],
}


def serve(eng, mix):
    reqs = sorted(mix, key=lambda r: r[5])
    step = 0
    while reqs or eng.pending():
        while reqs and reqs[0][5] <= step:
            rid, n, max_new, temp, seed, _ = reqs.pop(0)
            eng.submit(toks(len(rid) + n, n), max_new, temperature=temp,
                       seed=seed, rid=rid)
        assert eng.step() or not eng.pending()
        step += 1
    return eng.results


@pytest.mark.parametrize("mix", list(MIXES))
def test_prompts_that_prefill_beside_decoders_keep_every_stream(family, mix):
    eng = engine(family)
    got = serve(eng, MIXES[mix])
    for rid, n, max_new, temp, seed, _ in MIXES[mix]:
        assert_solo(family, toks(len(rid) + n, n), max_new, temp, seed,
                    got[rid])
    # the chunks of c and d each carried the rows of a and b
    c = eng.counters
    assert 0 < c["ticks_fused"] <= c["prefill_chunks"]
    assert c["ticks_fused"] < c["ticks"] and drained(eng)
    if family[0].mixer:
        return          # no drafts on a mixer: the oracle alone
    # the order of the engine before this one: every program landed in
    # the step that dispatched it
    serial = engine(family, spec_k=2)
    want = serve(serial, MIXES[mix])
    assert serial.counters["ticks_ahead"] == 0
    for rid in want:
        np.testing.assert_array_equal(got[rid], want[rid], err_msg=rid)


def test_a_prompts_last_chunk_blocks_on_nothing(family):
    """b's two chunks ride with a's rows. In the step of its last chunk
    the one dispatch closes before any fetch opens, b is a decoder with
    its first token on the device and nothing on the host, and the next
    tick gives it a row that reads that token from `prev`."""
    cfg, params = family
    eng = engine(family)
    pa, pb = toks(1, 5), toks(2, 27)
    eng.submit(pa, 20, rid="a")
    for _ in range(3):
        eng.step()
    eng.submit(pb, 6, temperature=0.9 * (not cfg.latent), seed=4, rid="b")
    eng.step()                                      # admitted, chunk 0
    b = next(r for r in eng.slots if r is not None and r.rid == "b")
    assert b.phase == "prefill" and b.written == 16
    first = tracer().event_count
    eng.step()                                      # chunk 1, its last
    ring = spans_since(first)
    names = [e[NAME] for e in ring]
    assert "decode.dispatch" not in names
    assert not {"prefill.sample", "prefill.fetch"} & set(names)
    dispatch, = [e for e in ring if e[NAME] == "prefill.dispatch"]
    fetches = [e for e in ring if e[NAME] == "decode.fetch"]
    assert fetches and all(dispatch[T1] <= f[T0] for f in fetches)
    turn, = [e for e in ring if e[NAME] == "decode"]
    chunk, = [e for e in ring if e[NAME] == "prefill"]
    assert chunk[PARENT] == turn[SEQ] and dispatch[PARENT] == chunk[SEQ]
    assert turn[ATTRS]["fused"] == 1 and turn[ATTRS]["n_active"] == 1
    assert chunk[ATTRS]["rows_rode"] == 1 and chunk[ATTRS]["chunk"] == 1
    # a decoder whose first token is in flight: the chunk's last
    # position is booked with it
    assert (b.phase, b.in_flight, b.generated) == ("decode", 1, [])
    assert b.first_tok_t is None and b.written == len(pb) - 1
    assert eng._flight[4] is b
    _, _, rows = eng._decode_prep()
    _, pos, _, _, _, idx, prev, ahead, _ = rows
    assert ahead[b.slot] and pos[b.slot] == len(pb) and idx[b.slot] == 1
    assert prev is eng._flight[2]
    res = eng.run()
    assert_solo(family, pa, 20, 0.0, 0, res["a"])
    assert_solo(family, pb, 6, 0.9 * (not cfg.latent), 4, res["b"])
    assert drained(eng)


def edge_max_new_1(kv):
    """One token: sampled by the last chunk's program, landed one step
    later, and the request never takes a row."""
    eng = engine(kv)
    eng.submit(toks(1, 6), 12, rid="a")
    eng.step(), eng.step()
    one, late = toks(3, 21), toks(4, 9)
    eng.submit(one, 1, temperature=1.0, seed=5, rid="one")
    eng.submit(late, 1, rid="late")
    res = eng.run()
    assert_solo(kv, one, 1, 1.0, 5, res["one"])
    assert_solo(kv, late, 1, 0.0, 0, res["late"])
    assert_solo(kv, toks(1, 6), 12, 0.0, 0, res["a"])
    # a's ticks alone: 11 after its own chunk's token
    assert eng.counters["ticks"] == 11 and eng.counters["ticks_fused"] == 3
    return eng


def edge_alone(kv):
    """A one-chunk prompt into an empty engine: the chunk's program with
    every row of the tick dead, no tick booked, the token in flight."""
    eng = engine(kv)
    p = toks(7, 13)
    eng.submit(p, 5, temperature=0.8, seed=2, rid="p")
    first = tracer().event_count
    assert eng.step()
    ring = spans_since(first)
    chunk, = [e for e in ring if e[NAME] == "prefill"]
    turn, = [e for e in ring if e[NAME] == "decode"]
    assert chunk[ATTRS]["rows_rode"] == 0
    assert set(turn[ATTRS]) == {"ahead"} and turn[ATTRS]["ahead"] == 0
    assert "decode.fetch" not in {e[NAME] for e in ring}
    assert eng._flight[0] == [] and eng._flight[4].rid == "p"
    res = eng.run()
    assert_solo(kv, p, 5, 0.8, 2, res["p"])
    assert eng.counters["ticks"] == 4 and eng.counters["ticks_fused"] == 0
    assert eng.counters["ticks_ahead"] == 4
    return eng


def edge_evicted_in_prep(kv):
    """The pool runs out while the tick's rows are prepared beside c's
    chunk: c, the newest, is evicted in its prefill phase, the step is
    the tick alone, and c prefills anew later."""
    eng = engine(kv, n_blocks=9)                    # 8 usable
    reqs = {"a": (toks(1, 8), 34), "b": (toks(2, 8), 34)}
    for k, (p, mn) in reqs.items():
        eng.submit(p, mn, rid=k)
    seen, evict = [], eng._evict
    eng._evict = lambda r: (seen.append((r.rid, r.phase)), evict(r))[1]
    steps = 0
    while eng.pending():
        if steps == 9:
            reqs["c"] = (toks(3, 24), 6)            # 3 blocks, 2 chunks
            eng.submit(*reqs["c"], rid="c")
        chunks, first = eng.counters["prefill_chunks"], tracer().event_count
        eng.step()
        steps += 1
        if seen and len(seen[0]) == 2:
            # the step that evicted the prefilling request ran no chunk
            names = {e[NAME] for e in spans_since(first)}
            seen[0] += (eng.counters["prefill_chunks"] - chunks,
                        "decode.dispatch" in names,
                        "prefill.dispatch" in names)
    assert seen[0] == ("c", "prefill", 0, True, False), seen
    for k, (p, mn) in reqs.items():
        assert_solo(kv, p, mn, 0.0, 0, eng.results[k])
    return eng


def edge_evicted_in_flight(kv):
    """c's last chunk is in flight, its first token with it, when the
    next step's rows find no block: the flight is landed (the token is
    c's), then c, the newest and by now a decoder, is evicted and
    prefills prompt + token anew."""
    eng = engine(kv, n_blocks=10)                   # 9 usable
    reqs = {"a": (toks(1, 7), 34), "b": (toks(2, 7), 34)}
    for k, (p, mn) in reqs.items():
        eng.submit(p, mn, rid=k)
    seen, evict, land = [], eng._evict, eng._land
    flown = []                  # whose first token each landing carried
    eng._land = lambda sp=None: (
        eng._flight is not None and flown.append(eng._flight[4]),
        land(sp))[1]
    eng._evict = lambda r: (seen.append(
        (r.rid, r.phase, len(r.generated), flown[-1] is r)), evict(r))[1]
    steps = 0
    while eng.pending():
        if steps == 9:
            reqs["c"] = (toks(3, 24), 6)            # 3 blocks, 2 chunks
            eng.submit(*reqs["c"], rid="c")
        eng.step()
        steps += 1
    assert seen[0] == ("c", "decode", 1, True), seen
    for k, (p, mn) in reqs.items():
        assert_solo(kv, p, mn, 0.0, 0, eng.results[k])
    return eng


def edge_drafts(kv):
    """Drafts need every token on the host: a fused step lands its own
    program, nothing stays in flight, and the row that carries a
    chunk's sample is no draft's."""
    eng = engine(kv, spec_k=2)
    rep = np.tile(toks(5, 4), 6)                    # n-grams that repeat
    eng.submit(rep, 20, rid="rep")
    for _ in range(4):
        eng.step()
        assert eng._flight is None
    late = toks(6, 40)
    eng.submit(late, 5, temperature=0.6, seed=3, rid="late")
    while eng.pending():
        eng.step()
        assert eng._flight is None
    assert_solo(kv, rep, 20, 0.0, 0, eng.results["rep"])
    assert_solo(kv, late, 5, 0.6, 3, eng.results["late"])
    c = eng.counters
    assert c["ticks_fused"] > 0 and c["ticks_ahead"] == 0
    assert c["spec_drafted"] > 0
    return eng


def edge_prefix_hit(kv):
    """A fully aligned hit: the copy-on-write pair and the one token
    that is prefilled again ride in one program with a decoder's row,
    and the shared blocks keep what they held."""
    eng = engine(kv, prefix_cache=True)
    shared = toks(9, 32)                            # four whole blocks
    eng.submit(shared, 3, rid="first")
    eng.run()
    held = [np.asarray(leaf) for leaf in jax.tree_util.tree_leaves(eng.pools)]
    cold = sorted(eng.alloc._cold)
    eng.submit(toks(8, 6), 14, rid="dec")
    for _ in range(3):
        eng.step()
    eng.submit(shared, 7, temperature=0.7, seed=9, rid="again")
    fused = eng.counters["ticks_fused"]
    eng.step()
    assert eng.counters["prefix_hits"] == 1
    assert eng.counters["ticks_fused"] == fused + 1
    res = eng.run()
    assert {r["id"]: r["prefill_skipped_tokens"]
            for r in eng.request_records}["again"] == 31
    now = [np.asarray(leaf) for leaf in jax.tree_util.tree_leaves(eng.pools)]
    for a, b in zip(held, now):
        np.testing.assert_array_equal(a[cold[:3]], b[cold[:3]])
    assert_solo(kv, shared, 7, 0.7, 9, res["again"])
    assert_solo(kv, toks(8, 6), 14, 0.0, 0, res["dec"])
    return eng


EDGES = {"max-new-1": edge_max_new_1, "alone": edge_alone,
         "evicted-in-prep": edge_evicted_in_prep,
         "evicted-in-flight": edge_evicted_in_flight,
         "drafts": edge_drafts, "prefix-hit": edge_prefix_hit}


@pytest.mark.parametrize("edge", list(EDGES))
def test_the_edges_of_a_fused_step(kv, edge):
    assert drained(EDGES[edge](kv))


@pytest.mark.parametrize("traffic", ["joining", "bursts"])
def test_single_requests_warm_every_program(traffic):
    """The benchmark's warm-up (`warm_prompt_lengths`): one request
    alone at each chunk-table width and each tick width, two tokens
    each. The chunk's program always carries the tick's rows at ONE
    width, so those programs, compiled with every row dead, are the
    programs of any mix."""
    cfg = T.TransformerConfig(vocab=80 + 8 * (traffic == "bursts"),
                              d_model=32, n_heads=4, n_layers=1,
                              max_seq=128)          # this case's alone
    eng = engine((cfg, jax.device_put(T.init(cfg, seed=0))))
    before = eng.executable_counts()
    # chunk tables of 4, 8 and 16 blocks; ticks of 4, 8 and 16
    for n in (8, 32, 64, 100):
        eng.submit(toks(n, n), 2)
        eng.run()
    warm = eng.executable_counts()
    assert warm["prefill_chunk"] - before["prefill_chunk"] == 3
    assert warm["decode_tick"] - before["decode_tick"] == 3
    assert eng.counters["ticks_fused"] == 0
    sizes = [(5, 20), (40, 30), (90, 12), (17, 50), (64, 9), (28, 33)]
    for i, (n, max_new) in enumerate(sizes):
        eng.submit(toks(50 + i, n), max_new, temperature=0.5 * (i % 2),
                   seed=i)
        for _ in range(0 if traffic == "bursts" else 3):
            eng.step()
    eng.run()
    assert eng.counters["ticks_fused"] > 0
    assert eng.executable_counts() == warm


@pytest.mark.parametrize("chunk", ["whole", "last"])
def test_the_ticks_routed_counts_are_its_own(chunk):
    """The routed layers see the chunk's rows and the tick's in one
    call and count them apart: the tick's side is what the same rows
    give in a lone tick (tokens too), the chunk's what the chunk gives
    alone; 16 chunk rows would otherwise drown two."""
    cfg = CFGS["latent"]
    model = cfg, jax.device_put(T.init(cfg, seed=5))
    eng = engine(model)
    eng.submit(toks(1, 9), 20, rid="a")
    eng.submit(toks(2, 12), 20, temperature=1.0, seed=3, rid="b")
    for _ in range(4):
        eng.step()
    eng.submit(toks(3, 30), 4, rid="c")
    if chunk == "last":
        eng.step()                                  # c's first chunk
    else:
        eng._admit()
    eng._land()
    c = next(r for r in eng.slots if r is not None and r.rid == "c")
    n_tok = min(16, len(c.ctx) - c.written)
    assert (n_tok == 16) == (chunk == "whole")
    copy = lambda: jax.tree_util.tree_map(jnp.copy, eng.pools)
    tokens = np.zeros((1, 16), np.int32)
    tokens[0, :n_tok] = c.ctx[c.written:c.written + n_tok]
    bts, _ = eng._rows_tables([(0, c)], 1, [eng._peak_blocks(0, len(c.ctx))])
    scratch = np.zeros(1, np.int32)
    args = (tokens, np.int32(c.written), np.int32(n_tok), bts, scratch,
            scratch, None, np.int32(c.slot))
    actives, _, rows = eng._decode_prep(c)
    assert sorted(r.rid for r in actives) == ["a", "b"]
    nxt, _, of_chunk, of_tick = _prefill_chunk(
        eng.params, copy(), *args, rows, cfg=cfg)
    _, _, alone = _prefill_chunk(eng.params, copy(), *args, cfg=cfg)
    _, _, lone_rows = eng._decode_prep()
    assert lone_rows[2][0].shape[1] < rows[2][0].shape[1]    # its own width
    want, _, lone = _decode_tick(eng.params, copy(), *lone_rows, cfg=cfg,
                                 top_k=0, top_p=0.0)
    np.testing.assert_array_equal(of_tick, lone)
    np.testing.assert_array_equal(of_chunk, alone)
    live = [r.slot for r in actives]
    np.testing.assert_array_equal(np.asarray(nxt)[live],
                                  np.asarray(want)[live])
    assert int(of_tick.sum()) == 2 * cfg.moe_top_k          # one routed layer
    assert int(of_chunk.sum()) == n_tok * cfg.moe_top_k
