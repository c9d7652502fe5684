"""A state-space mixer beside the attention heads of every block
(`ops/ssm.py`, `cfg.mixer`), its per-slot state a second kind of cache
next to the paged K/V pools (`serving/cache.py`: the slabs).

What has to hold:

- **The equations.** `T.forward` is the plain reference's
  (`benchmarks/harness/reference_state_space.py`: the recurrence one
  token at a time) to 1e-4, with the published multipliers folded into
  the matrices they follow and the reference applying them unfolded; so
  are the logits of the engine's two programs, prefilled in chunks of
  uneven length and decoded through both caches.
- **The blocked scan** is the one-token recurrence, with a short last
  block and with padding rows, from any starting state.
- **The same tokens** as `models/generate.py`.
- **A slot's state is its request's.** A tick between two chunks of one
  prompt leaves that slot's state alone; a slot reused after a finish
  starts from zeros; an evicted request continues the same stream.
- **What is not served is refused**, typed: a prefix cache, drafts, a
  train step.
- **The spans and counters** say what state the programs moved.
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
from shallowspeed_tpu.ops import ssm
from shallowspeed_tpu.serving import ServingEngine
from shallowspeed_tpu.serving import engine as E
from shallowspeed_tpu.serving.cache import (init_block_pool, kv_leaves,
                                            paged_read_bytes_per_tick,
                                            pool_block_size, state_leaves,
                                            state_row_bytes)
from shallowspeed_tpu.telemetry.trace import tracer

BENCH = Path(__file__).resolve().parent.parent / "benchmarks"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))
from harness import arith_state_space, model_state_space  # noqa: E402
from harness import reference_state_space as reference  # noqa: E402

BS, CHUNK = 4, 8
# 5 query heads on 2 K/V heads would not divide: 6 on 2 (a group of 3),
# mixer heads of another size than the attention's, 2 groups
CFG = T.TransformerConfig(
    vocab=64, d_model=32, n_heads=6, n_kv_heads=2, attn_head_dim=8,
    n_layers=3, max_seq=256, rope=True, rope_theta=1e6, norm="rmsnorm",
    ffn="swiglu", d_ff=48, embed_scale=1.7, ssm_heads=4, ssm_head_dim=16,
    ssm_state=8, ssm_groups=2, ssm_conv=4)
SHAPES = arith_state_space.Shapes(
    hidden=32, layers=3, heads=6, kv_heads=2, head_dim=8, ffn=48, vocab=64,
    tied=False, ssm_heads=4, ssm_head_dim=16, ssm_state=8, ssm_groups=2,
    ssm_conv=4)
# every multiplier its own value, none a power of two: one folded into
# the wrong matrix, or left out, moves the logits
MULT = dict(embedding=1.7, attention_in=0.9, attention_out=0.6, key=0.45,
            ssm_in=0.7, ssm_out=1.3, ssm_z=0.8, ssm_x=1.2, ssm_b=0.55,
            ssm_c=1.4, ssm_dt=0.65, mlp_gate=0.75, mlp_down=1.1, lm_head=0.35)
UNIT = {k: 1.0 for k in MULT} | {"embedding": 1.7}


def raw_params(seed):
    """`T.init`, its norm scales and the convolution's bias moved off 1
    and 0 (a scale of 1 would hide a norm left out)."""
    rng = np.random.default_rng(seed + 100)
    params = T.init(CFG, seed=seed)
    for blk in params["blocks"]:
        for node in (blk["ln1"], blk["ln2"], blk["mixer"]["mixer_norm"]):
            node["g"] = (node["g"] + 0.3 * rng.standard_normal(node["g"].shape)
                         ).astype(np.float32)
        bias = blk["mixer"]["conv_b"]
        blk["mixer"]["conv_b"] = (0.2 * rng.standard_normal(bias.shape)
                                  ).astype(bias.dtype)
        blk["mixer"]["d_skip"] = (1 + 0.3 * rng.standard_normal(
            CFG.ssm_heads)).astype(np.float32)
    return jax.device_put(params)


@pytest.fixture(scope="module")
def raw():
    return raw_params(0)


@pytest.fixture(scope="module")
def params(raw):
    """What the program is served: the multipliers inside the matrices."""
    return model_state_space.fold(raw, CFG, MULT)


def toks(seed, n):
    return np.random.default_rng(seed).integers(0, CFG.vocab, n).astype(np.int32)


def reference_logits(raw, seq, m=MULT, folded=False, **kw):
    pad = -len(seq) % min(len(seq), reference.Q_BLOCK)
    (hid,), _ = reference.hidden_states(
        raw, [np.concatenate([seq, np.zeros(pad, seq.dtype)])], SHAPES, m,
        CFG.rope_theta, folded, **kw)
    return np.asarray(reference.head_logits(raw, hid, m, folded))[:len(seq)]


def engine(params, **kw):
    kw = dict(dict(n_blocks=64, block_size=BS, max_slots=2,
                   prefill_chunk=CHUNK, lifecycle=False), **kw)
    return ServingEngine(params, CFG, **kw)


def oracle(params, prompt, max_new, **kw):
    """`generate()`'s tokens: one program for every prompt of a test (a
    bucket of 64, 40 new tokens; token i does not depend on how many
    follow it)."""
    kw = dict(dict(temperature=0.0), **kw)
    return np.asarray(generate(params, prompt[None], CFG, 40, **kw)
                      )[0, :max_new]


def slab(eng, slot, layer=0):
    return {n: np.asarray(leaf[slot])
            for n, leaf in state_leaves(eng.pools[layer]).items()}


# ------------------------------------------------------------ (a) model

@pytest.mark.parametrize("m", [UNIT, MULT], ids=["unit", "folded"])
def test_forward_is_the_reference(raw, m):
    seq = toks(1, 150)              # a whole block of the scan and a short one
    got = np.asarray(T.forward(model_state_space.fold(raw, CFG, m),
                               seq[None], CFG))[0]
    np.testing.assert_allclose(got, reference_logits(raw, seq, m), atol=1e-4)


def test_the_reference_takes_the_multipliers_back_out(raw, params):
    """`unfolded` is the reference's own statement of where each
    multiplier went: handed the served weights it reads what it reads
    from the raw ones, and a multiplier left in moves its logits."""
    seq = toks(2, 24)
    want = reference_logits(raw, seq)
    np.testing.assert_allclose(reference_logits(params, seq, folded=True),
                               want, atol=1e-4)
    assert np.abs(reference_logits(params, seq) - want).max() > 1e-2


@pytest.mark.parametrize("part", ["mixer", "conv_b", "d_skip", "mixer_norm",
                                  "A_log", "dt_bias", "groups"])
def test_every_part_of_the_mixer_moves_the_logits(params, part):
    """Not one of them is decoration at these sizes: the comparisons
    above would catch each left out."""
    seq, p, cfg = toks(3, 20)[None], params, CFG
    swap = lambda f: dict(params, blocks=[
        {**blk, "mixer": f(blk["mixer"])} for blk in params["blocks"]])
    if part == "mixer":
        p = dict(params, blocks=[{k: v for k, v in blk.items() if k != "mixer"}
                                 for blk in params["blocks"]])
    elif part == "groups":               # every head reading group 0's B, C
        cfg = replace(CFG, ssm_groups=1, ssm_state=2 * CFG.ssm_state)
        p = swap(lambda m: {**m, "mixer_norm": {"g": m["mixer_norm"]["g"]}})
    elif part in ("conv_b", "d_skip"):
        p = swap(lambda m: {**m, part: jnp.zeros_like(m[part])})
    elif part == "mixer_norm":
        p = swap(lambda m: {**m, part: {"g": jnp.ones_like(m[part]["g"])}})
    else:
        p = swap(lambda m: {**m, part: m[part] + 0.5})
    moved = np.abs(np.asarray(T.forward(p, seq, cfg))
                   - np.asarray(T.forward(params, seq, CFG))).max()
    assert moved > 1e-3, (part, moved)


def test_the_gate_comes_before_the_grouped_norm():
    """`mamba_norm_before_gate` false: y * silu(z) first, then each
    group's part normed over its own width."""
    rng = np.random.default_rng(5)
    y, z = (jnp.asarray(rng.standard_normal((3, 64)), jnp.float32)
            for _ in range(2))
    g = jnp.asarray(1 + 0.3 * rng.standard_normal(64), jnp.float32)
    v = np.asarray(y * jax.nn.silu(z)).reshape(3, 2, 32)
    want = (v / np.sqrt((v * v).mean(-1, keepdims=True) + 1e-5)
            ).reshape(3, 64) * np.asarray(g)
    np.testing.assert_allclose(ssm.gated_norm(g, y, z, 2), want, atol=1e-5)
    one_group = np.asarray(ssm.gated_norm(g, y, z, 1))
    norm_first = np.asarray(ssm.gated_norm(g, y, jnp.full_like(z, 50.0), 2)
                            * jax.nn.silu(z))
    assert np.abs(one_group - want).max() > 1e-2
    assert np.abs(norm_first - want).max() > 1e-2


def test_init_leaves_every_earlier_models_weights_what_they_were():
    """The mixer is drawn after a block's other leaves: the same seed
    gives a model without one the weights it always had."""
    plain = replace(CFG, ssm_heads=0, n_layers=1)
    a, b = T.init(plain, seed=3), T.init(replace(plain, ssm_heads=4), seed=3)
    for name, leaf in a["blocks"][0].items():
        np.testing.assert_array_equal(
            jax.tree_util.tree_leaves(leaf)[0],
            jax.tree_util.tree_leaves(b["blocks"][0][name])[0], err_msg=name)
    mix = b["blocks"][0]["mixer"]
    assert set(mix) == {"in_proj", "conv_w", "conv_b", "A_log", "dt_bias",
                        "d_skip", "mixer_norm", "out_proj"}
    assert mix["in_proj"]["W"].shape == (32, 64 + (64 + 2 * 2 * 8) + 4)
    # decays and step sizes that forget at different speeds, never 0 or 1
    step = np.log1p(np.exp(mix["dt_bias"]))
    assert (step > 9e-4).all() and (step < 0.11).all()
    assert (np.exp(mix["A_log"]) >= 1).all() and (np.exp(mix["A_log"]) <= 16).all()
    # per-head vectors and the gated norm stay float32 under a bf16 cast
    cast = T.cast_params(jax.device_put(b), jnp.bfloat16)["blocks"][0]["mixer"]
    assert {n: cast[n].dtype for n in ("A_log", "dt_bias", "d_skip")} \
        == dict.fromkeys(("A_log", "dt_bias", "d_skip"), jnp.float32)
    assert cast["mixer_norm"]["g"].dtype == jnp.float32
    assert cast["in_proj"]["W"].dtype == jnp.bfloat16


# ------------------------------------------------------------- (b) scan

def _scan_inputs(seed, t, b=2):
    rng = np.random.default_rng(seed)
    h, p, g, n = 4, 16, 2, 8
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    return (f(b, t, h, p), jnp.asarray(rng.uniform(0.01, 0.6, (b, t, h)),
                                       jnp.float32),
            -jnp.asarray(rng.uniform(1, 8, (h,)), jnp.float32),
            f(b, t, g, n), f(b, t, g, n), f(h), f(b, h, p, n))


def _token_by_token(x, dt, a, b, c, d, s, n_tok):
    ys = []
    for t in range(x.shape[1]):
        y, s_next = ssm.ssm_step(x[:, t], dt[:, t], a, b[:, t], c[:, t], d, s)
        if t < n_tok:
            s = s_next
        ys.append(y)
    return jnp.stack(ys, 1), s


@pytest.mark.parametrize("t,block,n_tok", [
    (32, 8, None),      # whole blocks
    (21, 8, None),      # a short last block
    (24, 8, 13),        # padding rows: the state stops at the 13th
    (24, 8, 16),        # ... at a block's edge
    (5, 128, 3),        # a chunk shorter than a block
    (16, 16, 1),        # one true row
])
def test_the_blocked_scan_is_the_one_token_recurrence(t, block, n_tok):
    args = _scan_inputs(t, t)
    y, s = ssm.ssm_scan(*args, n_tok, block)
    want_y, want_s = _token_by_token(*args, t if n_tok is None else n_tok)
    true = slice(0, n_tok)
    np.testing.assert_allclose(y[:, true], want_y[:, true], atol=2e-5)
    np.testing.assert_allclose(s, want_s, atol=2e-5)


def test_padding_rows_are_exact_not_masked_approximately():
    """Rows at or past `n_tok` take step size 0: decay 1 and no input, so
    the state after them is BIT for bit the state after the true rows,
    whatever the padding holds."""
    x, dt, a, b, c, d, s = _scan_inputs(9, 24)
    _, s_true = ssm.ssm_scan(x[:, :16], dt[:, :16], a, b[:, :16], c[:, :16],
                             d, s, None, 8)
    junk = lambda v: v.at[:, 16:].set(1e3)
    _, s_pad = ssm.ssm_scan(junk(x), dt, a, junk(b), junk(c), d, s, 16, 8)
    np.testing.assert_array_equal(np.asarray(s_pad), np.asarray(s_true))


@pytest.mark.parametrize("n_tok", [None, 5, 1])
def test_the_convolution_carries_its_tail_across_chunks(n_tok):
    rng = np.random.default_rng(4)
    w = jnp.asarray(rng.uniform(-0.5, 0.5, (4, 6)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal(6), jnp.float32)
    x = jnp.asarray(rng.standard_normal((2, 19, 6)), jnp.float32)
    whole, _ = ssm.causal_conv(w, bias, x, jnp.zeros((2, 3, 6)))
    first, tail = ssm.causal_conv(w, bias, x[:, :8], jnp.zeros((2, 3, 6)),
                                  n_tok)
    cut = 8 if n_tok is None else n_tok
    np.testing.assert_allclose(first[:, :cut], whole[:, :cut], atol=1e-6)
    rest, _ = ssm.causal_conv(w, bias, x[:, cut:], tail)
    np.testing.assert_allclose(rest, whole[:, cut:], atol=1e-6)


# ----------------------------------------------------- (c) engine logits

def test_chunked_prefill_and_decode_give_the_references_logits(raw, params):
    """A prompt through chunks of uneven length (8, 8, 5), then ticks
    through both caches, teacher-forced: the logits of each chunk's last
    position and of every tick against the reference's full forward pass
    over the same tokens."""
    seq = toks(5, 31)
    n_prompt = 2 * CHUNK + 5
    want = reference_logits(raw, seq)
    eng = engine(params)
    eng.submit(toks(6, 3), 2, rid="other")   # takes slot 0: `r` is row 1
    eng.submit(seq[:n_prompt], len(seq) - n_prompt, rid="r")
    eng._admit()
    req = eng.slots[1]
    got = {}
    scratch = np.int32(0)
    while req.written < n_prompt:
        n_tok = min(CHUNK, n_prompt - req.written)
        assert eng._ensure_blocks(req, req.written + n_tok)
        tokens = np.zeros((1, CHUNK), np.int32)
        tokens[0, :n_tok] = seq[req.written:req.written + n_tok]
        bts, _ = eng._rows_tables([(0, req)], 1)
        logits, eng.pools, _ = E._prefill_chunk(
            eng.params, eng.pools, tokens, np.int32(req.written),
            np.int32(n_tok), bts, scratch, scratch, None, np.int32(req.slot),
            cfg=CFG)
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
            bts, _ = eng._rows_tables([(1, req)], 2)
            z = np.zeros(2, np.int32)
            tok = np.asarray([0, seq[pos]], np.int32)
            _, eng.pools, _ = tick(
                eng.params, eng.pools, tok, np.asarray([0, pos], np.int32),
                bts, np.zeros(2, np.float32), z.astype(np.uint32), z, z,
                np.zeros(2, bool), None, cfg=CFG, top_k=0, top_p=0.0)
            req.written = pos + 1
            got[pos] = np.asarray(seen["logits"])[1]
    finally:
        E._sample_rows = orig
    assert len(got) == 3 + len(seq) - n_prompt
    for pos, logits in got.items():
        np.testing.assert_allclose(logits, want[pos], atol=1e-4,
                                   err_msg=str(pos))


# ------------------------------------------------------ (d) same tokens

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_greedy_tokens_equal_generate(seed):
    """Five requests over two slots: slots are reused, chunks are of
    uneven length, ticks run between the chunks of later prompts."""
    params = model_state_space.fold(raw_params(seed), CFG, MULT)
    reqs = {f"r{i}": (toks(10 * seed + i, n), m) for i, (n, m) in
            enumerate([(21, 30), (5, 40), (34, 17), (13, 9), (27, 22)])}
    eng = engine(params)
    for rid, (p, m) in reqs.items():
        eng.submit(p, m, rid=rid)
    got = eng.run()
    assert eng.alloc.n_free == eng.alloc.n_usable
    for rid, (p, m) in reqs.items():
        np.testing.assert_array_equal(got[rid], oracle(params, p, m),
                                      err_msg=rid)


def test_sampled_tokens_equal_generate(params):
    eng = engine(params)
    p = toks(7, 19)
    eng.submit(p, 25, temperature=0.9, seed=11, rid="s")
    np.testing.assert_array_equal(
        eng.run()["s"], oracle(params, p, 25, temperature=0.9, seed=11))


def test_generate_pads_its_prompt_without_touching_the_state(params):
    """`generate()` pads the prompt to a bucket of 64: the mixer's state
    is the one after the last TRUE token."""
    p = toks(8, 11)
    out = oracle(params, p, 6)
    logits = np.asarray(T.forward(params, np.concatenate([p, out])[None],
                                  CFG))[0]
    np.testing.assert_array_equal(out, logits[10:-1].argmax(-1))


# --------------------------------------------------- (e) a slot's state

def test_a_tick_between_two_chunks_leaves_the_prefilling_slots_state(params):
    """`long` prefills in three chunks while `first` decodes: the ticks
    between its chunks run over every row, and must write `long`'s row
    back as it was."""
    eng = engine(params)
    eng.submit(toks(11, 4), 30, rid="first")
    eng.step()                                   # `first` decodes from now
    long = toks(12, 2 * CHUNK + 3)
    eng.submit(long, 4, rid="long")
    eng.step()                                   # chunk 1 of `long`, a tick
    req = next(r for r in eng.slots if r is not None and r.rid == "long")
    assert req.phase == "prefill" and req.written == CHUNK
    held = [slab(eng, req.slot, l) for l in range(CFG.n_layers)]
    assert all(np.abs(h["ssm"]).max() > 0 for h in held)
    ticks = eng.counters["ticks"]
    # a tick alone (no chunk this step): what a step between two chunks
    # of a long prompt is when a prompt ahead of it takes the chunk
    assert eng._decode_step()
    eng._land()
    assert eng.counters["ticks"] > ticks
    for l, h in enumerate(held):
        now = slab(eng, req.slot, l)
        for name in h:
            np.testing.assert_array_equal(now[name], h[name], err_msg=name)
    got = eng.run()
    for rid, (p, m) in {"first": (toks(11, 4), 30), "long": (long, 4)}.items():
        np.testing.assert_array_equal(got[rid], oracle(params, p, m),
                                      err_msg=rid)


def test_a_slot_reused_after_a_finish_starts_from_zeros(params):
    """Two slots, three requests: the third's first chunk does not see
    what the first left in the row it takes over (and the tick in flight
    when the first finished did not advance it for the third)."""
    eng = engine(params)
    a, b, c = toks(13, 9), toks(14, 6), toks(21, 11)
    eng.submit(a, 7, rid="a")
    eng.submit(b, 30, rid="b")
    eng.submit(c, 12, rid="c")
    got = eng.run()
    assert np.abs(slab(eng, 0)["ssm"]).max() > 0
    for rid, (p, m) in {"a": (a, 7), "b": (b, 30), "c": (c, 12)}.items():
        np.testing.assert_array_equal(got[rid], oracle(params, p, m),
                                      err_msg=rid)


def test_a_finished_requests_slot_holds_the_state_after_its_last_fed_token(
        params):
    """What the benchmark's comparison reads (`finished_states`): a
    request finishes by count, so when the step that finished it
    returns, its slot's row holds the state after prompt + generated
    less the last token, which was sampled and never fed; the tick in
    flight has not touched it, and the next request's first chunk takes
    the row over a step later at the earliest. Three requests on two
    slots: the first's row is read before the third takes it."""
    from drivers.serve_state_space import finished_states

    eng = engine(params)
    prompts = {"a": toks(22, 9), "b": toks(23, 21), "c": toks(24, 6)}
    for rid, n in (("a", 7), ("b", 15), ("c", 9)):
        eng.submit(prompts[rid], n, rid=rid)
    held: dict = {}
    while eng.pending():
        finished_states(eng, held, eng.step)
    assert set(held) == set(prompts)
    for rid, p in prompts.items():
        gaps, states = reference.teacher_forced(
            params, p, eng.results[rid], SHAPES, MULT, CFG.rope_theta,
            folded=True, last=len(eng.results[rid]))
        assert gaps.max() < 1e-3, rid          # the reference's own tokens
        assert reference.state_gaps(held[rid], states).max() < 1e-4, rid
    # a row that the next tick had zeroed, or another slot's, is far off
    _, states = reference.teacher_forced(
        params, prompts["a"], eng.results["a"], SHAPES, MULT, CFG.rope_theta)
    assert reference.state_gaps(held["b"], states).min() > 0.1


def test_eviction_and_readmission_continue_the_same_stream(params):
    """`_evict` needs nothing new: the request re-prefills prompt +
    generated from position 0, which rebuilds its state, in whatever
    slot it is given next."""
    p, m = toks(15, 14), 26
    want = oracle(params, p, m)
    eng = engine(params)
    eng.submit(toks(16, 5), 40, rid="stays")
    eng.submit(p, m, rid="moved")
    while len(next((r.generated for r in eng.slots
                    if r is not None and r.rid == "moved"), ())) < 9:
        eng.step()
    eng._land()
    victim = next(r for r in eng.slots if r is not None and r.rid == "moved")
    eng._evict(victim)
    assert eng.counters["preempted"] == 1 and victim.written == 0
    got = eng.run()
    np.testing.assert_array_equal(got["moved"], want)


# ------------------------------------------------------- (f) the cache

def test_the_slabs_ride_beside_the_pools_and_the_block_helpers_skip_them():
    pools = init_block_pool(CFG, 8, BS, slots=3)
    assert len(pools) == CFG.n_layers
    assert set(pools[0]) == {"k", "v", "conv", "ssm"}
    assert pools[0]["ssm"].shape == (3, 4, 16, 8)
    assert pools[0]["ssm"].dtype == jnp.float32
    assert pools[0]["conv"].shape == (3, 3, 64 + 2 * 2 * 8)
    assert set(kv_leaves(pools[0])) == {"k", "v"}
    assert set(state_leaves(pools[0])) == {"conv", "ssm"}
    assert pool_block_size(pools[0]) == BS
    # whichever leaf comes first in the dict
    assert pool_block_size(dict(reversed(list(pools[0].items())))) == BS
    with pytest.raises(ValueError, match="slots"):
        init_block_pool(CFG, 8, BS)
    plain = init_block_pool(replace(CFG, ssm_heads=0), 8, BS)
    assert set(plain[0]) == {"k", "v"} and not state_leaves(plain[0])


def test_the_byte_model_counts_the_slabs_read_and_written(params):
    row = state_row_bytes(CFG)
    assert row == 4 * 4 * 16 * 8 + 4 * 3 * (64 + 32)      # float32 toy
    assert state_row_bytes(replace(CFG, ssm_heads=0)) == 0
    none = paged_read_bytes_per_tick(params, CFG, 10, BS, 3)
    some = paged_read_bytes_per_tick(params, CFG, 10, BS, 3, state_rows=2)
    assert some - none == 2 * 2 * CFG.n_layers * row
    eng = engine(params)
    eng.submit(toks(17, 6), 5)
    eng.step()
    room = eng.headroom()
    assert room["state_rows"] == 1
    assert room["state_bytes"] == CFG.n_layers * row
    s = replace(SHAPES, state_rows=2.0)
    assert s.decode_step_min_bytes(0) - SHAPES.decode_step_min_bytes(0) \
        == s.ssm_step_bytes(2.0) == 2 * 2 * s.state_bytes_per_row()


# ------------------------------------------------------ (g) refusals

@pytest.mark.parametrize("kw", [dict(prefix_cache=True), dict(spec_k=2)],
                         ids=["prefix_cache", "spec_k"])
def test_the_engine_refuses_what_needs_a_snapshot_of_the_state(params, kw):
    with pytest.raises(ValueError, match="state-space mixer"):
        engine(params, **kw)


def test_the_train_engines_refuse_a_model_with_a_mixer(raw):
    from shallowspeed_tpu.optim import SGD
    from shallowspeed_tpu.parallel.context import ContextParallelEngine
    from shallowspeed_tpu.parallel.pipeline_lm import PipelineLMEngine

    tok = toks(18, 8)[None]
    with pytest.raises(AssertionError, match="served only"):
        T.loss(raw, tok, tok, CFG)
    with pytest.raises(AssertionError, match="served only"):
        ContextParallelEngine(CFG, SGD(0.1),
                              jax.make_mesh((1, 1), ("dp", "sp")))
    with pytest.raises(AssertionError, match="served only"):
        PipelineLMEngine(replace(CFG, n_layers=2), SGD(0.1),
                         jax.make_mesh((1, 2), ("dp", "pp")), n_mubatches=2)
    assert CFG.window == 0 and replace(CFG, ssm_heads=0).trainable.window == 0


def test_a_config_names_its_mixer_whole():
    with pytest.raises(AssertionError, match="mixer"):
        T.TransformerConfig(ssm_heads=4)
    with pytest.raises(AssertionError, match="mixer"):
        replace(CFG, ssm_groups=3)
    assert CFG.mixer and not T.TransformerConfig().mixer
    assert (ssm.d_ssm(CFG), ssm.conv_dim(CFG), ssm.proj_dim(CFG)) \
        == (64, 96, 164)


# --------------------------------------------------------- (h) tracing

def test_spans_and_counters_say_what_state_the_programs_moved(params):
    tr = tracer()
    eng = engine(params)
    mark = tr.now()
    eng.submit(toks(19, 2 * CHUNK + 2), 6, rid="a")     # three chunks
    eng.submit(toks(20, 3), 9, rid="b")
    eng.run()
    spans = [e for e in tr.ring() if e[3] >= mark]
    pre = [e[5] for e in spans if e[2] == "prefill" and "state_carried" in e[5]]
    assert [a["state_carried"] for a in pre] == [0, 1, 1, 0]
    dec = [e[5] for e in spans if e[2] == "decode" and "state_rows" in e[5]]
    per_row = 2 * CFG.n_layers * state_row_bytes(CFG)
    assert dec and all(a["state_bytes"] == a["state_rows"] * per_row
                       for a in dec)
    assert {a["state_rows"] for a in dec} <= {1, 2}
    c = eng.counters
    assert c["state_carried"] == 2
    assert c["state_rows"] == sum(a["state_rows"] for a in dec) \
        == 6 + 9 - 2            # every token but each request's first
    assert c["state_bytes"] == c["state_rows"] * per_row
    # a model without a mixer says nothing of it
    plain = replace(CFG, ssm_heads=0)
    quiet = ServingEngine(jax.device_put(T.init(plain, 0)), plain,
                          n_blocks=16, block_size=BS, lifecycle=False)
    assert not any(k.startswith("state") for k in quiet.counters)
    assert "state_rows" not in quiet.headroom()


# ------------------------------------------------------------- serve.py

def test_serve_py_reaches_the_mixer_through_the_model_config(tmp_path):
    """`serve.py --model-config FILE` carries the mixer's sizes like any
    other field of the config: the model runs through `ServingEngine` on
    the normal path, no flag of its own; what is not served for it is
    refused there too."""
    import json
    import subprocess

    (tmp_path / "model.json").write_text(json.dumps({
        "n_kv_heads": 2, "attn_head_dim": 8, "embed_scale": 1.7,
        "ssm_heads": 4, "ssm_head_dim": 16, "ssm_state": 8, "ssm_groups": 2,
        "ssm_conv": 4}))
    (tmp_path / "reqs.jsonl").write_text(
        '{"id": "g", "prompt_len": 21, "max_new": 12}\n'
        '{"id": "s", "prompt_len": 5, "max_new": 6, "temperature": 1.0}\n')
    root = Path(__file__).resolve().parent.parent
    cmd = [sys.executable, "serve.py", "--platform", "cpu", "--vocab", "64",
           "--d-model", "32", "--n-heads", "6", "--n-layers", "2",
           "--max-seq", "128", "--rope", "--norm", "rmsnorm", "--ffn",
           "swiglu", "--model-config", str(tmp_path / "model.json"),
           "--requests", str(tmp_path / "reqs.jsonl"), "--n-blocks", "24",
           "--block-size", "4", "--slots", "2", "--prefill-chunk", "8"]
    proc = subprocess.run(cmd + ["--prefix-cache", "off"], cwd=root,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(l) for l in proc.stdout.splitlines()
             if l.startswith("{")]
    done = {l["id"]: l["tokens"] for l in lines if l.get("event") == "result"}
    assert len(done["g"]) == 12 and len(done["s"]) == 6
    summary = next(l for l in lines if l.get("event") == "summary")
    assert summary["blocks_free_at_drain"] == "23/23"
    refused = subprocess.run(cmd + ["--prefix-cache", "on"], cwd=root,
                             capture_output=True, text=True, timeout=600)
    assert refused.returncode != 0
    assert "state-space mixer" in refused.stderr
