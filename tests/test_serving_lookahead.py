"""The decode loop keeps one program in flight
(`ServingEngine._decode_step`): program N+1 is dispatched before
program N's tokens are fetched, each row's input token taken from N's
`nxt` on the device. A program is a decode tick or, in a step that
holds a prefill chunk, the chunk's with the tick's rows riding in it
(tests/test_serving_fused.py), whose `nxt` also carries the first token
of a prompt that the chunk completes.

What has to hold, on a K/V pool and on a latent pool:

- **The same tokens.** Every request's stream equals its solo oracle
  (`models/generate.py` / `T.forward`) and the stream of the loop with
  nothing in flight, which `spec_k > 0` forces (drafts are proposed from
  the last token on the host): only WHEN the host learns a token changes.
- **Nothing is lost.** An eviction, `run()` and a `drain()` loop land the
  tick in flight before they act or return.
- **The order is what it says**: from the tracer's ring, and counted in
  `counters["ticks_ahead"]`; one executable a width, as before.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shallowspeed_tpu.models import transformer as T
from shallowspeed_tpu.models.generate import generate
from shallowspeed_tpu.serving import ServingEngine
from shallowspeed_tpu.serving.engine import _decode_tick
from shallowspeed_tpu.telemetry.trace import tracer

CFGS = {
    "kv": T.TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                              max_seq=128),
    # a latent pool, and a routed layer whose counts ride beside the tokens
    "latent": T.TransformerConfig(
        vocab=64, d_model=32, n_heads=2, n_layers=2, max_seq=128, rope=True,
        norm="rmsnorm", ffn="swiglu", d_ff=64, kv_lora_rank=16,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        n_routed_experts=4, n_shared_experts=1, moe_top_k=2, expert_d_ff=16,
        first_dense_layers=1),
    # window and full layers, each kind with a pool of its own
    # (`serving/cache.py:layer_groups`), and every part a block may hold;
    # contexts pass the window of 12 early, so the window group releases
    # blocks behind every request while a tick is in flight
    "window": T.TransformerConfig(
        vocab=64, d_model=32, n_heads=4, n_kv_heads=2, attn_head_dim=8,
        n_layers=3, max_seq=128, rope=True, norm="rmsnorm", ffn="swiglu",
        d_ff=64, layers=((12, True), (12, True), (0, False)),
        embed_scale=32 ** 0.5),
}
# the window group's pool where a test does not size one: 4 slots at
# their bound of ceil(12 / 8) + 1 + 2 blocks and one over, so a block
# released behind one request is handed to another's write at once
WINDOW_BLOCKS = 4 * 5 + 1 + 1
# ring tuples (telemetry/trace.py)
SEQ, PARENT, NAME, T0, T1, ATTRS = range(6)


@pytest.fixture(scope="module", params=list(CFGS))
def model(request):
    cfg = CFGS[request.param]
    parts = T.BLOCK_PARTS if cfg.layers else ()
    return cfg, jax.device_put(T.init(cfg, seed=5, parts=parts))


def toks(seed, n):
    return np.random.default_rng(seed).integers(0, 64, n).astype(np.int32)


def engine(model, spec_k=0, n_blocks=48, max_slots=4):
    cfg, params = model
    if cfg.layers:
        n_blocks = {"full": n_blocks, "window": min(n_blocks, WINDOW_BLOCKS)}
    return ServingEngine(params, cfg, n_blocks=n_blocks, block_size=8,
                         max_slots=max_slots, prefill_chunk=16,
                         spec_k=spec_k, lifecycle=False)


def assert_solo(model, prompt, max_new, temp, seed, got):
    """`got` is the request's solo stream: `generate()`'s on a K/V
    cache; a latent cache has no contiguous form, so there a greedy
    stream is held to the argmax of the no-cache forward pass over
    everything before each token (sampled latent requests are held to
    the parent's order alone)."""
    cfg, params = model
    assert len(got) == max_new
    if not cfg.latent:
        want = np.asarray(generate(params, prompt[None], cfg, max_new,
                                   temperature=temp, seed=seed))[0]
    elif temp == 0:
        seq = np.concatenate([prompt, got])
        lg = T.forward(params, jnp.asarray(seq[:-1])[None], cfg)[0]
        want = np.asarray(lg.argmax(-1))[len(prompt) - 1:]
    else:
        return
    np.testing.assert_array_equal(got, want)


# (rid, prompt tokens, max_new, temperature, seed, submitted after step)
SCENARIOS = {
    "greedy-and-sampled": [
        ("a", 5, 10, 0.0, 0, 0), ("b", 23, 12, 1.0, 7, 0),
        ("c", 40, 6, 0.8, 3, 0)],
    "join-and-finish-mid-run": [
        ("a", 9, 14, 0.0, 0, 0), ("b", 12, 4, 1.0, 2, 0),
        ("c", 7, 9, 0.0, 0, 3), ("d", 20, 5, 0.7, 9, 6)],
    # e's 4 chunks end in a step in which a and b decode; its first
    # tick reads the host's token beside their tokens from the device
    "prefill-ends-while-others-decode": [
        ("a", 6, 24, 0.0, 0, 0), ("b", 10, 20, 1.0, 4, 0),
        ("e", 60, 6, 0.0, 0, 2)],
    "max-new-1-and-2": [
        ("one", 11, 1, 0.0, 0, 0), ("two", 13, 2, 1.0, 5, 0),
        ("long", 8, 7, 0.0, 0, 0), ("late1", 9, 1, 1.0, 6, 2),
        ("late2", 17, 2, 0.0, 0, 3)],
}


def serve(eng, scenario):
    reqs = sorted(SCENARIOS[scenario], key=lambda r: r[5])
    step = 0
    while reqs or eng.pending():
        while reqs and reqs[0][5] <= step:
            rid, n, max_new, temp, seed, _ = reqs.pop(0)
            eng.submit(toks(len(rid) + n, n), max_new, temperature=temp,
                       seed=seed, rid=rid)
        assert eng.step() or not eng.pending()
        step += 1
    return eng.results


@pytest.mark.parametrize("scenario", list(SCENARIOS))
def test_streams_equal_the_oracle_and_the_parents_order(model, scenario):
    ahead, serial = engine(model), engine(model, spec_k=2)
    got, want = serve(ahead, scenario), serve(serial, scenario)
    for rid, n, max_new, temp, seed, _ in SCENARIOS[scenario]:
        np.testing.assert_array_equal(got[rid], want[rid], err_msg=rid)
        assert_solo(model, toks(len(rid) + n, n), max_new, temp, seed,
                    got[rid])
    # the two orders really differ, and both end with nothing held
    assert ahead.counters["ticks_ahead"] > 0
    assert serial.counters["ticks_ahead"] == 0
    for eng in (ahead, serial):
        assert eng._flight is None and eng.pending() == 0
        assert all(al.n_free == al.n_usable for al in eng.allocs)
        # a window group hands back what left the windows, both ways
        # (the fourth scenario's contexts stay inside one)
        assert (eng.counters["released"] > 0) == (
            bool(eng.cfg.layers) and scenario != "max-new-1-and-2")


def test_eviction_with_a_tick_in_flight_continues_the_stream(model):
    """Three requests that outgrow the pool: `_ensure_blocks` finds no
    block with a tick in flight, lands it (its `decode.fetch` opens
    inside `decode.prep`), evicts the newest, and every stream is its
    oracle's all the same."""
    reqs = {k: (toks(50 + i, 24), 16) for i, k in enumerate("abc")}
    # 13 usable blocks * 8 = 104 positions < 3 * (24 + 16) = 120
    eng = engine(model, n_blocks=14)
    first = tracer().event_count
    for k, (p, mn) in reqs.items():
        eng.submit(p, mn, rid=k)
    res = eng.run()
    assert eng.counters["preempted"] >= 1
    ring = tracer().ring()[-(tracer().event_count - first):]
    names = {e[SEQ]: e[NAME] for e in ring}
    assert any(e[NAME] == "decode.fetch"
               and names.get(e[PARENT]) == "decode.prep" for e in ring)
    for k, (p, mn) in reqs.items():
        assert_solo(model, p, mn, 0.0, 0, res[k])
    assert eng.alloc.n_free == eng.alloc.n_usable and eng._flight is None


@pytest.mark.parametrize("how", ["run", "drain", "run-bounded"])
def test_the_last_tick_in_flight_is_delivered(model, how):
    eng = engine(model, max_slots=2)
    want = {k: (toks(70 + i, 10), 6) for i, k in enumerate("abc")}
    for k, (p, mn) in want.items():
        eng.submit(p, mn, rid=k)
    if how == "run":
        eng.run()
    elif how == "drain":
        # the scale-down loop: its last steps have nothing to dispatch,
        # land the tick in flight, and count as work
        worked = []
        while not eng.drain():
            worked.append(eng.step())
        assert all(worked)
    else:
        # a bounded run stops with a tick in flight; nothing is lost
        # and the next run delivers it
        eng.run(max_steps=4)
        assert eng._flight is not None and eng.pending()
        held = {r.rid: len(r.generated) for r in eng.slots if r is not None}
        eng.run()
        assert all(len(eng.results[k]) > n for k, n in held.items())
    assert eng.pending() == 0 and eng._flight is None
    assert eng.counters["finished"] == 3
    for k, (p, mn) in want.items():
        assert_solo(model, p, mn, 0.0, 0, eng.results[k])
    assert eng.alloc.n_free == eng.alloc.n_usable


@pytest.mark.parametrize("spec_k", [0, 2], ids=["ahead", "drafts"])
def test_dispatch_closes_before_the_fetch_of_the_tick_before(model, spec_k):
    """From the tracer's ring: program N+1's dispatch closes before
    program N's `decode.fetch` opens, except after a drain (a program
    with none before it in flight); with drafts every program is
    fetched in the turn that dispatched it. A request's programs are
    its prompt's one chunk (`prefill.dispatch`, which samples the first
    token and blocks on nothing) and seven ticks (`decode.dispatch`)."""
    eng = engine(model, spec_k=spec_k)
    first = tracer().event_count
    for rid, n in (("x", 9), ("y", 14)):       # two runs, each drained
        eng.submit(toks(n, n), 8, rid=rid)
        eng.run()
    ring = tracer().ring()[-(tracer().event_count - first):]
    by_seq = {e[SEQ]: e for e in ring}

    def turn(e):                # the `decode` span a span lies in
        while e[NAME] != "decode":
            e = by_seq[e[PARENT]]
        return e

    dispatches = [e for e in ring
                  if e[NAME] in ("decode.dispatch", "prefill.dispatch")]
    fetches = [e for e in ring if e[NAME] == "decode.fetch"]
    ticks = eng.counters["ticks"]
    kinds = [d[NAME].split(".")[0] for d in dispatches]
    # (accepted drafts make a request's ticks fewer)
    assert kinds == (["prefill"] + ["decode"] * 7) * 2 or spec_k
    assert kinds.count("decode") == ticks and kinds[0] == "prefill"
    assert len(fetches) == len(dispatches) == ticks + 2
    flags = [turn(d)[ATTRS]["ahead"] for d in dispatches]
    assert sum(flags) == eng.counters["ticks_ahead"]
    assert eng.counters["ticks_fused"] == 0     # nobody decoded beside a chunk
    if spec_k:
        assert flags == [0] * (ticks + 2)
        assert all(d[T1] <= f[T0] and turn(d) is turn(f)
                   for d, f in zip(dispatches, fetches))
        return
    # every tick found a program in flight: the first one its prompt's
    # chunk, whose own turn followed a drain
    assert flags == ([0] + [1] * 7) * 2
    for n in range(ticks + 1):
        if flags[n + 1]:
            assert dispatches[n + 1][T1] <= fetches[n][T0]
            assert turn(dispatches[n + 1]) is turn(fetches[n])
        else:
            assert fetches[n][T1] <= dispatches[n + 1][T0]
    # a turn carries the routed / latent attrs of the tick it LANDED
    # (a chunk's landing brings a first token and no tick) and the
    # read's of the tick it dispatched
    for d, f in zip(dispatches, fetches):
        attrs = turn(f)[ATTRS]
        assert ("latent_tokens" in attrs) == (
            bool(eng.cfg.latent) and d[NAME] == "decode.dispatch")
        assert ("blocks_read" in turn(d)[ATTRS]) == (
            d[NAME] == "decode.dispatch")


def test_executables_grow_by_one_a_width():
    """One program a width, whether the tick reads its tokens from the
    host (nothing in flight: `_no_tok` stands in for `nxt`) or from the
    program before it, a tick's `nxt` or a chunk's: one signature."""
    cfg = T.TransformerConfig(vocab=72, d_model=32, n_heads=4, n_layers=1,
                              max_seq=128)          # this test's alone
    eng = engine((cfg, jax.device_put(T.init(cfg, seed=0))), max_slots=2)
    eng.table_bucket = 1
    before = int(_decode_tick._cache_size())
    # 4 + 13 - 1 = 16 positions = 2 blocks: widths 1 and 2
    eng.submit(toks(1, 4) % 72, 13, rid="grow")
    eng.run()
    assert int(_decode_tick._cache_size()) - before == 2
    # the first tick found the prompt's chunk in flight
    assert eng.counters["ticks_ahead"] == eng.counters["ticks"] == 12
    warm = eng.executable_counts()
    for i in range(3):                  # after a drain, and joining
        eng.submit(toks(2 + i, 3 + i) % 72, 9 - i, rid=f"r{i}")
        eng.step()
    eng.run()
    assert eng.executable_counts() == warm


def test_a_flagged_row_reads_its_token_from_the_tick_before(model):
    """`_decode_tick` itself: a row with `ahead` set takes `prev[row]`
    and ignores the host's `tok[row]`; the others the reverse."""
    cfg, params = model
    eng = engine(model)
    eng.submit(toks(3, 12), 4, rid="p")
    eng.submit(toks(4, 9), 4, rid="q")
    while sum(r is not None and r.phase == "decode" for r in eng.slots) < 2:
        eng.step()
    eng._land()
    _, _, rows = eng._decode_prep()
    tok, pos, bt, temp, seeds, idx, _, _, base = rows
    s = eng.max_slots
    junk = np.full(s, 63, np.int32)
    flag = np.zeros(s, np.bool_)
    flag[0] = True

    def run(tok, prev, ahead):
        # the pools are donated: each call reads a copy of its own
        pools = jax.tree_util.tree_map(jnp.copy, eng.pools)
        return np.asarray(_decode_tick(
            params, pools, tok, pos, bt, temp, seeds, idx, prev, ahead,
            base, cfg=cfg, top_k=0, top_p=0.0)[0])

    want = run(tok, junk, np.zeros(s, np.bool_))
    mixed = np.where(flag, junk, tok)
    np.testing.assert_array_equal(
        run(mixed, np.where(flag, tok, junk), flag), want)

