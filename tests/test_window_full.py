"""Window layers and full layers, each kind with block pools of its own
(`serving/cache.py:layer_groups`), and the parts a block may hold beside
the plain pre-norm block: per-head RMSNorm of queries and keys, a
sigmoid gate on the attention output, a norm after each sub-layer, full
layers that take no rotation, a scaled embedding, routed experts.

What has to hold:

- **The equations.** `T.forward` is the plain reference's
  (`benchmarks/harness/reference_window_experts.py`) to 1e-4, and so are
  the logits of the engine's two programs at every generated position,
  prefilled in chunks that cross the window and decoded far past it.
- **The same tokens** as `models/generate.py`, and as an engine whose
  window is so wide that its window group never releases a block.
- **The cache rolls.** A request never holds more than its bound of
  window-group blocks, every released block is free again, and both
  groups' free counts return after a finish, an eviction and a resume,
  and `OutOfBlocks` in either group.
- **The prefix cache** gives the same tokens on as off, and a hit reads
  no released block.
- **The spans** say what was released, held and read, group by group.
"""

import sys
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shallowspeed_tpu.models import transformer as T
from shallowspeed_tpu.models.generate import generate
from shallowspeed_tpu.serving import ServingEngine
from shallowspeed_tpu.serving import engine as E
from shallowspeed_tpu.serving.cache import (OutOfBlocks, blocks_for,
                                            group_of_layer, layer_groups)
from shallowspeed_tpu.telemetry.trace import tracer

BENCH = Path(__file__).resolve().parent.parent / "benchmarks"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
from harness import arith_window_experts, reference_window_experts  # noqa: E402

WINDOW, BS, CHUNK = 8, 4, 8
S, F = (WINDOW, True), (0, False)
# a leading dense layer, then S S F twice; 8 experts choose 2, 1 shared
CFG = T.TransformerConfig(
    vocab=64, d_model=32, n_heads=4, n_kv_heads=2, attn_head_dim=16,
    n_layers=7, max_seq=256, rope=True, norm="rmsnorm", ffn="swiglu",
    d_ff=48, layers=(S, S, S, F, S, S, F), embed_scale=32 ** 0.5,
    n_routed_experts=8, n_shared_experts=1, moe_top_k=2, expert_d_ff=16,
    routed_scaling_factor=2.826, first_dense_layers=1)
# the bound of a request's window-group blocks: the window's, and the
# blocks of the chunk being written
BOUND = blocks_for(WINDOW, BS) + 1 + blocks_for(CHUNK, BS)
SHAPES = arith_window_experts.Shapes(
    hidden=32, layers=7, dense_layers=1, heads=4, kv_heads=2, head_dim=16,
    ffn=48, expert_ffn=16, experts=8, experts_per_token=2, shared_experts=1,
    vocab=64, tied=False, window=WINDOW, window_layers=5)


def make_params(seed):
    """`T.init` with every part, its norm scales and the routing bias
    moved off 1 and 0 (a scale of 1 would hide a norm left out)."""
    rng = np.random.default_rng(seed + 100)
    params = T.init(CFG, seed=seed, parts=T.BLOCK_PARTS)
    for blk in params["blocks"]:
        for name in ("ln1", "ln2", "ln1_post", "ln2_post", "q_norm", "k_norm"):
            g = blk[name]["g"]
            blk[name]["g"] = (g + 0.3 * rng.standard_normal(g.shape)
                              ).astype(g.dtype)
        if "experts" in blk:
            blk["experts"]["route_bias"] = (
                0.05 * rng.standard_normal(CFG.n_routed_experts)
            ).astype(np.float32)
    return jax.device_put(params)


@pytest.fixture(scope="module")
def params():
    return make_params(0)


def toks(seed, n):
    return np.random.default_rng(seed).integers(0, CFG.vocab, n).astype(np.int32)


def reference_logits(params, seq):
    pad = -len(seq) % min(len(seq), reference_window_experts.Q_BLOCK)
    hid, = reference_window_experts.hidden_states(
        params, [np.concatenate([seq, np.zeros(pad, seq.dtype)])], SHAPES,
        CFG.layer_specs, CFG.rope_theta, CFG.routed_scaling_factor,
        CFG.embed_scale)
    return np.asarray(reference_window_experts.head_logits(params, hid))[:len(seq)]


def engine(params, cfg=CFG, n_blocks=None, **kw):
    kw = dict(dict(block_size=BS, max_slots=3, prefill_chunk=CHUNK,
                   lifecycle=False), **kw)
    return ServingEngine(params, cfg,
                         n_blocks=n_blocks or {"full": 64, "window": 16}, **kw)


def drained(eng):
    return all(al.n_free + al.n_cold == al.n_usable and al.n_live == 0
               and al.snapshot()["consistent"] for al in eng.allocs)


# ------------------------------------------------------------ (a) model

def test_the_layers_fall_into_a_full_and_a_window_group():
    full, window = layer_groups(CFG)
    assert (full.name, full.window, full.layers) == ("full", 0, (3, 6))
    assert (window.name, window.window) == ("window", WINDOW)
    assert window.layers == (0, 1, 2, 4, 5)
    assert group_of_layer(CFG) == (1, 1, 1, 0, 1, 1, 0)
    # a model with one kind of layer has one group, as before
    assert [g.name for g in layer_groups(replace(CFG, layers=()))] == ["full"]
    mistral, = layer_groups(T.TransformerConfig(attn_window=4096, n_layers=3))
    assert (mistral.name, mistral.layers) == ("window", (0, 1, 2))
    assert window.first_live_block(20, BS) == (20 - WINDOW + 1) // BS
    assert window.held_bound(BS, CHUNK) == BOUND and full.held_bound(BS, 8) == 0


def test_code_that_takes_one_window_a_model_refuses_a_pattern():
    """`cfg.window` is what the training engines' substrates and the FLOP
    count read: the spec of a uniform model, an assertion on a pattern;
    two window sizes in one model are no configuration yet."""
    from shallowspeed_tpu.flops import transformer_flops_per_token

    uniform = T.TransformerConfig(attn_window=4096, rope=True, n_layers=3)
    assert uniform.window == 4096
    assert uniform.layer_specs == ((4096, True),) * 3
    same = replace(uniform, attn_window=0, layers=((4096, True),) * 3)
    assert same.window == 4096
    assert transformer_flops_per_token(same, 8192) \
        == transformer_flops_per_token(uniform, 8192)
    with pytest.raises(AssertionError, match="layers differ"):
        CFG.window
    with pytest.raises(AssertionError, match="layers differ"):
        transformer_flops_per_token(CFG, 64)
    with pytest.raises(AssertionError, match="one window size"):
        replace(CFG, layers=(S, (4, True), S, F, S, S, F))


def test_forward_is_the_plain_reference(params):
    seq = toks(1, 48)                               # 6 x the window
    got = np.asarray(T.forward(params, jnp.asarray(seq)[None], CFG))[0]
    np.testing.assert_allclose(got, reference_logits(params, seq), atol=1e-4)


@pytest.mark.parametrize("part", ["q_norm", "attn_gate", "ln1_post",
                                  "ln2_post", "embed_scale", "rotary",
                                  "window"])
def test_each_part_is_read(params, part):
    """Leave one part out and the logits move: none is there in name
    alone (the reference above holds what each computes)."""
    seq = jnp.asarray(toks(2, 24))[None]
    cfg, p = CFG, params
    if part == "embed_scale":
        cfg = replace(CFG, embed_scale=1.0)
    elif part == "rotary":
        cfg = replace(CFG, layers=tuple((w, True) for w, _ in CFG.layers))
    elif part == "window":
        cfg = replace(CFG, layers=tuple((0, r) for _, r in CFG.layers))
    else:
        p = dict(params, blocks=[
            {k: v for k, v in blk.items()
             if k not in (part, "k_norm" if part == "q_norm" else part)}
            for blk in params["blocks"]])
    moved = np.abs(np.asarray(T.forward(p, seq, cfg))
                   - np.asarray(T.forward(params, seq, CFG))).max()
    assert moved > 1e-2, (part, moved)


# ----------------------------------------------------- (b) engine logits

def test_chunked_prefill_and_decode_give_the_references_logits(params):
    """Prefill in chunks that cross the window, then decode through the
    two groups to 6 x the window, teacher-forced: the logits of the
    chunk's last position and of every tick against the reference's
    full forward pass over the same tokens."""
    seq = toks(3, 6 * WINDOW + 1)
    n_prompt = 2 * WINDOW + 3                    # three chunks, the last short
    want = reference_logits(params, seq)
    eng = engine(params, max_slots=1)
    eng.submit(seq[:n_prompt], len(seq) - n_prompt, rid="r")
    eng._admit()
    req = eng.slots[0]
    got = {}
    while req.written < n_prompt:                # the chunks, by hand
        n_tok = min(CHUNK, n_prompt - req.written)
        assert eng._ensure_blocks(req, req.written + n_tok)
        tokens = np.zeros((1, CHUNK), np.int32)
        tokens[0, :n_tok] = seq[req.written:req.written + n_tok]
        bts, base = eng._rows_tables([(0, req)], 1)
        scratch = np.zeros(2, np.int32)
        logits, eng.pools, _ = E._prefill_chunk(
            eng.params, eng.pools, tokens, np.int32(req.written),
            np.int32(n_tok), bts, scratch, scratch, base[:, 0], cfg=CFG)
        req.written += n_tok
        got[req.written - 1] = np.asarray(logits)[0]
    tick = E._decode_tick.__wrapped__            # the logits, not the sample
    seen = {}

    def sampler(logits, *a):
        seen["logits"] = logits
        return jnp.argmax(logits, -1).astype(jnp.int32)

    orig, E._sample_rows = E._sample_rows, sampler
    try:
        for pos in range(n_prompt, len(seq)):
            assert eng._ensure_blocks(req, pos + 1)
            assert len(req.tables[1]) <= BOUND
            bts, base = eng._rows_tables([(0, req)], 1)
            z = np.zeros(1, np.int32)
            _, eng.pools, _ = tick(
                eng.params, eng.pools, seq[pos:pos + 1], np.int32([pos]),
                bts, np.zeros(1, np.float32), z.astype(np.uint32), z, z,
                np.zeros(1, bool), base, cfg=CFG, top_k=0, top_p=0.0)
            req.written = pos + 1
            got[pos] = np.asarray(seen["logits"])[0]
    finally:
        E._sample_rows = orig
    assert eng.counters["released"] >= blocks_for(len(seq) - WINDOW, BS) - 1
    for pos, logits in got.items():
        np.testing.assert_allclose(logits, want[pos], atol=1e-4,
                                   err_msg=str(pos))


# ------------------------------------------------------ (c) same tokens

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_greedy_tokens_equal_generate_and_a_cache_that_never_releases(seed):
    params = make_params(seed)
    reqs = {f"r{i}": (toks(10 * seed + i, n), m)
            for i, (n, m) in enumerate([(21, 30), (9, 40), (34, 17)])}
    eng = engine(params)
    for rid, (p, m) in reqs.items():
        eng.submit(p, m, rid=rid)
    got = eng.run()
    assert eng.counters["released"] > 0 and drained(eng)
    # the same model under a window so wide that nothing ever leaves it:
    # the masks are the true window's (the reference is `generate`), the
    # cache keeps every block
    for rid, (p, m) in reqs.items():
        want = np.asarray(generate(params, p[None], CFG, m, temperature=0.0))[0]
        np.testing.assert_array_equal(got[rid], want, err_msg=rid)
    keep = engine(params, n_blocks={"full": 64, "window": 64})
    keep.groups = tuple(replace(g, window=0) if g.window else g
                        for g in keep.groups)   # releases nothing, masks as is
    keep._held_bound = [0, 0]
    for rid, (p, m) in reqs.items():
        keep.submit(p, m, rid=rid)
    kept = keep.run()
    assert keep.counters["released"] == 0
    for rid in reqs:
        np.testing.assert_array_equal(got[rid], kept[rid], err_msg=rid)


def test_sampled_tokens_equal_generate(params):
    eng = engine(params)
    p = toks(7, 19)
    eng.submit(p, 25, temperature=0.9, seed=11, rid="s")
    want = np.asarray(generate(params, p[None], CFG, 25, temperature=0.9,
                               seed=11))[0]
    np.testing.assert_array_equal(eng.run()["s"], want)


def test_drafts_on_a_windowed_model_change_no_token(params):
    """`spec_k > 0`: draft rows write ahead of the request's position
    through both tables; the streams are the serial engine's."""
    motif = np.tile(toks(4, 5), 6)
    out = []
    for k in (0, 3):
        eng = engine(params, spec_k=k)
        eng.submit(motif, 30, rid="m")
        eng.submit(toks(5, 13), 22, rid="n")
        out.append(eng.run())
        assert drained(eng)
    for rid in ("m", "n"):
        np.testing.assert_array_equal(out[0][rid], out[1][rid])


# ---------------------------------------------------- (d) the allocator

def test_a_request_never_holds_more_than_its_bound(params):
    eng = engine(params)
    for i, (n, m) in enumerate([(30, 30), (11, 45), (26, 12), (40, 8)]):
        eng.submit(toks(20 + i, n), m, rid=f"r{i}")
    worst = 0
    while eng.pending():
        eng.step()
        for r in eng.slots:
            if r is None:
                continue
            worst = max(worst, len(r.tables[1]))
            # the full group keeps every block from the first on
            assert r.base[0] == 0
            assert len(r.tables[0]) >= blocks_for(r.written, BS)
            # the window group starts where its window does
            assert r.base[1] <= max(r.written - WINDOW + 1, 0) // BS
        live = sum(len(r.tables[1]) for r in eng.slots if r is not None)
        assert eng.allocs[1].n_live == live          # released = free again
    assert BOUND - 1 <= worst <= BOUND
    assert eng.counters["released"] > 0 and drained(eng)


def test_eviction_and_resume_through_both_groups(params):
    """Three requests that outgrow the full group's pool: the newest is
    evicted, gives back both tables, re-prefills through both groups
    and continues its stream."""
    reqs = {k: (toks(50 + i, 20), 20) for i, k in enumerate("abc")}
    eng = engine(params, n_blocks={"full": 26, "window": 16})
    for k, (p, m) in reqs.items():
        eng.submit(p, m, rid=k)
    res = eng.run()
    assert eng.counters["preempted"] >= 1 and drained(eng)
    for k, (p, m) in reqs.items():
        want = np.asarray(generate(params, p[None], CFG, m, temperature=0.0))[0]
        np.testing.assert_array_equal(res[k], want, err_msg=k)


@pytest.mark.parametrize("group,n_blocks", [
    ("full", {"full": 9, "window": 16}),
    ("window", {"full": 64, "window": 7})])
def test_out_of_blocks_names_the_group_that_ran_out(params, group, n_blocks):
    """A pool too small for two requests at once, in either group: the
    typed OutOfBlocks carries the group, the engine recovers (admission
    waits, or the newest is evicted) and both streams are whole."""
    eng = engine(params, n_blocks=n_blocks, max_slots=2)
    seen = []
    eng.oom_listeners.append(lambda e, exc: seen.append(exc))
    reqs = {k: (toks(60 + i, 14), 14) for i, k in enumerate("ab")}
    for k, (p, m) in reqs.items():
        eng.submit(p, m, rid=k)
    res = eng.run()
    assert seen and {e.group for e in seen} == {group}
    assert isinstance(seen[0], OutOfBlocks) and group in str(seen[0])
    assert drained(eng)
    for k, (p, m) in reqs.items():
        want = np.asarray(generate(params, p[None], CFG, m, temperature=0.0))[0]
        np.testing.assert_array_equal(res[k], want, err_msg=k)


def test_submit_refuses_what_a_group_could_never_hold(params):
    eng = engine(params, n_blocks={"full": 8, "window": 16})
    with pytest.raises(ValueError, match="'full' group"):
        eng.submit(toks(0, 20), 20)                 # 10 blocks of 7 usable
    eng = engine(params, n_blocks={"full": 64, "window": 4})
    with pytest.raises(ValueError, match="'window' group"):
        eng.submit(toks(0, 20), 20)                 # its bound of 5, of 3
    with pytest.raises(ValueError, match="layer groups"):
        engine(params, n_blocks={"full": 8})


def test_a_released_block_is_reused_while_a_tick_is_in_flight(params):
    """A window pool with no block to spare: what one request releases
    behind its window goes to another's write in the very tick that
    follows, with the tick before it still in flight, and every stream
    is its oracle's (the device runs the programs in order)."""
    reqs = {k: (toks(80 + i, 10 + i), 30) for i, k in enumerate("abc")}
    eng = engine(params, n_blocks={"full": 64, "window": 3 * (BOUND - 2) + 2})
    handed = []
    alloc, release = eng.allocs[1].alloc, eng.allocs[1].release

    def spy_release(ids):
        handed.append(("out", list(ids), bool(eng._flight)))
        return release(ids)

    def spy_alloc(n, rid=None):
        ids = alloc(n, rid=rid)
        handed.append(("in", ids, bool(eng._flight)))
        return ids

    eng.allocs[1].release, eng.allocs[1].alloc = spy_release, spy_alloc
    for k, (p, m) in reqs.items():
        eng.submit(p, m, rid=k)
    res = eng.run()
    for k, (p, m) in reqs.items():
        want = np.asarray(generate(params, p[None], CFG, m, temperature=0.0))[0]
        np.testing.assert_array_equal(res[k], want, err_msg=k)
    # some block went out and came back in, both with a tick in flight
    out = {b for kind, ids, flying in handed if kind == "out" and flying
           for b in ids}
    back = [b for kind, ids, flying in handed if kind == "in" and flying
            for b in ids if b in out]
    assert back and eng.counters["ticks_ahead"] > 0


# -------------------------------------------------- (f) the prefix cache

def test_prefix_cache_on_equals_off_and_hits_what_the_window_still_holds(
        params):
    """Requests that share a prompt, asked again after the first asker
    finished. Short answers leave the prompt's last window in the
    window group's index: the re-ask hits in both groups. A long answer
    rolled the window past the prompt: the window group can serve no
    block of it, and the re-ask is a miss in both (its tokens are the
    same either way)."""
    shared = toks(90, 26)
    plan = [("first", shared, 2), ("again", shared, 12),
            ("long", toks(91, 22), 30), ("long-again", toks(91, 22), 9)]
    got = {}
    for on in (False, True):
        eng = engine(params, prefix_cache=on)
        for rid, p, m in plan:
            eng.submit(p, m, rid=rid)
            eng.run()                              # one after the other
        got[on] = dict(eng.results)
        assert drained(eng)
        if on:
            recs = {r["id"]: r for r in eng.request_records}
            # 26 tokens = 6 whole blocks; the window group served blocks
            # first_live(6 * 4 - 1) = 4 and 5, the full group all six
            assert recs["again"]["prefix_hit_blocks"] == 6
            assert recs["long-again"]["prefix_hit_blocks"] == 0
            assert eng.counters["prefix_hits"] == 1
            assert eng.allocs[1].n_cold <= eng.allocs[0].n_cold
            # no entry of either index outlives its block
            for ix, al in zip(eng.prefixes, eng.allocs):
                assert all(b in al._cold or b in al._ref
                           for b in ix._hash_of)
    for rid, p, m in plan:
        np.testing.assert_array_equal(got[True][rid], got[False][rid], rid)
        want = np.asarray(generate(params, p[None], CFG, m, temperature=0.0))[0]
        np.testing.assert_array_equal(got[True][rid], want, err_msg=rid)


def test_a_fully_aligned_hit_copies_on_write_in_both_groups(params):
    shared = toks(92, 24)                           # 6 whole blocks
    eng = engine(params, prefix_cache=True)
    eng.submit(shared, 3, rid="a")
    eng.run()
    cold = [set(al._cold) for al in eng.allocs]
    eng.submit(shared, 10, rid="b")
    eng.run()
    rec = eng.request_records[-1]
    assert rec["prefix_hit_blocks"] == 6 and rec["prefill_skipped_tokens"] == 23
    # the shared blocks are still indexed, unwritten by b's decode
    assert all(c <= set(al._cold) for c, al in zip(cold, eng.allocs))
    want = np.asarray(generate(params, shared[None], CFG, 10,
                               temperature=0.0))[0]
    np.testing.assert_array_equal(eng.results["b"], want)
    assert drained(eng)


# ------------------------------------------------------- (g) the spans

def test_spans_and_counters_say_what_was_released_held_and_read(params):
    eng = engine(params)
    first = tracer().event_count
    handed_back = []
    release = eng.allocs[1].release
    eng.allocs[1].release = lambda ids: (handed_back.extend(ids),
                                         release(ids))[1]
    reqs = [(toks(70 + i, n), m) for i, (n, m) in
            enumerate([(25, 20), (12, 28)])]
    for i, (p, m) in enumerate(reqs):
        eng.submit(p, m, rid=f"r{i}")
    eng.run()
    ring = tracer().ring()[-(tracer().event_count - first):]
    decode = [e[5] for e in ring if e[2] == "decode" and "blocks_read" in e[5]]
    prefill = [e[5] for e in ring if e[2] == "prefill"]
    assert len(decode) == eng.counters["ticks"]
    released = sum(a["released"] for a in decode) \
        + sum(a.get("released", 0) for a in prefill)
    # what the spans say was released is what the counter says: the
    # blocks handed back to the free list less what the two requests
    # still held when they finished (their bound at most)
    assert released == eng.counters["released"] > 0
    assert released < len(handed_back) <= released + 2 * BOUND
    for a in decode:
        assert a["window_blocks"] <= BOUND * a["n_active"]
        assert a["window_blocks"] <= a["full_blocks"]
        assert a["blocks_read"] == a["blocks_read_full"] \
            + a["blocks_read_window"]
        assert a["blocks_read_window"] <= a["blocks_read_full"]
    for name in ("window_blocks", "full_blocks", "blocks_read_window",
                 "blocks_read_full", "blocks_read", "blocks_table"):
        assert eng.counters[name] == sum(a[name] for a in decode), name
    # a group's snapshot is its own
    snaps = [al.snapshot() for al in eng.allocs]
    assert [s["group"] for s in snaps] == ["full", "window"]
    assert snaps[1]["peak_live"] <= 2 * BOUND < snaps[0]["peak_live"]
    assert eng.oom_forensics()["allocators"] == snaps


def test_the_byte_model_counts_a_window_groups_blocks_once_a_layer(params):
    from shallowspeed_tpu.serving.cache import paged_read_bytes_per_tick

    per_block = BS * 2 * CFG.kv_heads * CFG.head_dim * 4     # float32
    base = paged_read_bytes_per_tick(params, CFG, [0, 0], BS, 3, p_bytes=0)
    both = paged_read_bytes_per_tick(params, CFG, [10, 3], BS, 3, p_bytes=0)
    assert both - base == per_block * (2 * 10 + 5 * 3)
    # one number: every layer that many (a model with one group)
    flat = paged_read_bytes_per_tick(params, CFG, 10, BS, 3, p_bytes=0)
    assert flat - base == per_block * 7 * 10


# ------------------------------------------------------------- serve.py

def test_serve_py_reaches_the_model_through_one_flag(tmp_path):
    """`serve.py --model-config FILE`: the layer pattern, the head size,
    the embedding's scale and the block parts, laid over the model
    flags, through `ServingEngine` on the normal path; the summary's
    free count is both groups' together."""
    import json
    import subprocess

    (tmp_path / "model.json").write_text(json.dumps({
        "n_kv_heads": 2, "attn_head_dim": 16, "embed_scale": 5.657,
        "layers": [[8, True], [8, True], [0, False]],
        "block_parts": list(T.BLOCK_PARTS)}))
    (tmp_path / "reqs.jsonl").write_text(
        '{"id": "g", "prompt_len": 30, "max_new": 24}\n'
        '{"id": "s", "prompt_len": 11, "max_new": 6, "temperature": 1.0}\n')
    root = Path(__file__).resolve().parent.parent
    proc = subprocess.run(
        [sys.executable, "serve.py", "--platform", "cpu", "--vocab", "64",
         "--d-model", "32", "--n-heads", "4", "--n-layers", "3", "--max-seq",
         "128", "--rope", "--norm", "rmsnorm", "--ffn", "swiglu",
         "--routed-experts", "8", "2", "1", "16", "--dense-layers", "1",
         "--model-config", str(tmp_path / "model.json"), "--requests",
         str(tmp_path / "reqs.jsonl"), "--n-blocks", "24", "--block-size",
         "4", "--slots", "2", "--prefill-chunk", "8", "--prefix-cache", "off"],
        cwd=root, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(l) for l in proc.stdout.splitlines()
             if l.startswith("{")]
    done = {l["id"]: l["tokens"] for l in lines if l.get("event") == "result"}
    assert len(done["g"]) == 24 and len(done["s"]) == 6
    summary = next(l for l in lines if l.get("event") == "summary")
    assert summary["blocks_free_at_drain"] == "46/46"      # 2 x 23 usable
