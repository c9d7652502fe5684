"""Latent attention (MLA) and dropless sigmoid-routed experts with
shared experts, at a small size on the CPU, seeded: `T.forward`, the
serving engine's paged latent pool and the routed layer against the
benchmark's float32 reference (`benchmarks/harness/
reference_latent_experts.py`, which shares nothing with the program
but the weight layout), logits and not tokens."""

import sys
from dataclasses import replace
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent.parent / "benchmarks"
for _p in (str(BENCH.parent), str(BENCH)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from harness import arith_latent_experts as arith          # noqa: E402
from harness import reference_latent_experts as reference  # noqa: E402

from shallowspeed_tpu.models import transformer as T       # noqa: E402
from shallowspeed_tpu.ops import moe                       # noqa: E402
from shallowspeed_tpu.ops.latent_attention import (        # noqa: E402
    latent_attention, latent_attention_absorbed)
from shallowspeed_tpu.serving import engine as E           # noqa: E402
from shallowspeed_tpu.serving.cache import (LATENT,        # noqa: E402
                                            init_block_pool)

THETA, SCALE = 50000.0, 2.446
CFG = T.TransformerConfig(
    vocab=128, d_model=64, n_heads=4, n_layers=3, max_seq=128, rope=True,
    rope_theta=THETA, norm="rmsnorm", ffn="swiglu", d_ff=160,
    kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    n_routed_experts=8, n_shared_experts=1, moe_top_k=2, expert_d_ff=32,
    routed_scaling_factor=SCALE, first_dense_layers=1)
SHAPES = arith.Shapes(
    hidden=64, layers=3, dense_layers=1, heads=4, q_nope=16, q_rope=8,
    v_head=16, kv_rank=16, ffn=160, expert_ffn=32, experts=8,
    experts_per_token=2, shared_experts=1, vocab=128, tied=False)
# float32 against float32 at "highest": what is left is summation order
LOGIT_TOL = 2e-4


@pytest.fixture(scope="module")
def params():
    """Seeded weights with a selection bias large enough to change the
    choice of most tokens (std 0.3 against score gaps of ~0.1)."""
    p = T.init(CFG, seed=3)
    rng = np.random.default_rng(4)
    for blk in p["blocks"][CFG.first_dense_layers:]:
        blk["experts"]["route_bias"] = rng.normal(0, 0.3, 8).astype(np.float32)
    return jax.tree_util.tree_map(jnp.asarray, p)


def _tokens(n, seed=0):
    return np.random.default_rng(seed).integers(0, CFG.vocab, n).astype(np.int32)


def _reference_logits(params, tokens):
    hid, = reference.hidden_states(params, [tokens], SHAPES, THETA, SCALE)
    return np.asarray(reference.head_logits(params, hid))


def test_forward_matches_the_reference(params):
    toks = _tokens(40)
    got = T.forward(params, jnp.asarray(toks)[None], CFG)[0]
    np.testing.assert_allclose(np.asarray(got), _reference_logits(params, toks),
                               atol=LOGIT_TOL)


def test_engine_prefill_and_decode_through_the_latent_pool(params, monkeypatch):
    """A prompt prefilled in two chunks, then 8 decode ticks, through
    the paged latent pool: the logits of the last prompt position and
    of every tick against the reference's full forward pass over the
    same tokens."""
    bs, chunk, n_prompt = 8, 16, 27
    prompt = _tokens(n_prompt, seed=1)
    pools = init_block_pool(CFG, 16, bs)
    assert set(pools[0]) == {LATENT} and pools[0][LATENT].shape[1] == 1
    table = [5, 2, 9, 11, 7]
    bt = np.zeros((1, 8), np.int32)
    bt[0, :len(table)] = table
    z = np.int32(0)
    for pos0 in (0, chunk):
        n = min(chunk, n_prompt - pos0)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :n] = prompt[pos0:pos0 + n]
        logits, pools, counts = E._prefill_chunk(
            params, pools, toks, np.int32(pos0), np.int32(n), bt, z, z, cfg=CFG)
        assert int(np.asarray(counts).sum()) == 2 * CFG.moe_top_k * n
    seen = [np.asarray(logits)[0]]
    # the tick returns tokens; let its sampler show the logits it saw
    tick = E._decode_tick.__wrapped__
    monkeypatch.setattr(
        E, "_sample_rows",
        lambda lg, *a: (seen.append(np.asarray(lg)[0]),
                        jnp.argmax(lg, -1).astype(jnp.int32))[1])
    seq = list(prompt) + [int(seen[0].argmax())]
    s = 2                                   # slot 0 live, slot 1 empty
    tables = np.zeros((s, 8), np.int32)
    tables[0] = bt[0]
    for _ in range(8):
        pos = len(seq) - 1
        nxt, pools, counts = tick(
            params, pools, np.asarray([seq[-1], 0], np.int32),
            np.asarray([pos, 0], np.int32), tables, np.zeros(s, np.float32),
            np.zeros(s, np.uint32), np.zeros(s, np.int32),
            np.zeros(s, np.int32), np.zeros(s, np.bool_), cfg=CFG, top_k=0,
            top_p=0.0)
        # only the live row's choices are counted
        assert np.asarray(counts).sum(-1).tolist() == [CFG.moe_top_k] * 2
        seq.append(int(np.asarray(nxt)[0]))
    ref = _reference_logits(params, np.asarray(seq[:-1], np.int32))
    np.testing.assert_allclose(np.stack(seen), ref[n_prompt - 1:],
                               atol=LOGIT_TOL)


def test_engine_serves_requests_and_reports_its_layers(params):
    """Through `ServingEngine` as any model: greedy streams equal
    `T.forward`'s, and the spans and counters of the routed and latent
    layers are there."""
    from shallowspeed_tpu.telemetry.trace import tracer

    eng = E.ServingEngine(params, CFG, n_blocks=32, block_size=8,
                          max_slots=4, prefill_chunk=16, lifecycle=False)
    first = tracer().event_count
    prompts = [_tokens(n, seed=n) for n in (21, 9, 30)]
    for i, p in enumerate(prompts):
        eng.submit(p, 6, rid=f"r{i}")
    out = eng.run()
    for i, p in enumerate(prompts):
        # greedy: each token is the argmax of the no-cache forward pass
        # over everything before it
        seq = np.concatenate([p, out[f"r{i}"]])
        lg = T.forward(params, jnp.asarray(seq[:-1])[None], CFG)[0]
        assert np.asarray(lg.argmax(-1))[len(p) - 1:].tolist() \
            == out[f"r{i}"].tolist()
    spans = [e["args"] for e in tracer().events_since(first)
             if e["name"] == "decode"]
    # one program is in flight: a `decode` span carries the attrs of
    # the tick it LANDED, so every tick has them on one span and only a
    # span with no tick to land has none (a chunk's turn before anyone
    # decodes, or one that lands a prompt's first token alone)
    ticks = [a for a in spans if "latent_tokens" in a]
    assert len(ticks) == eng.counters["ticks"]
    assert len(spans) - len(ticks) <= eng.counters["prefill_chunks"]
    assert all({"experts_touched", "max_load", "latent_tokens"} <= set(a)
               for a in ticks)
    assert all(1 <= a["experts_touched"] <= 8 and a["max_load"] >= 1
               for a in ticks)
    c = eng.counters
    assert c["latent_tokens"] == sum(a["latent_tokens"] for a in ticks)
    assert c["experts_touched"] == pytest.approx(
        sum(a["experts_touched"] for a in ticks))
    assert eng.alloc.n_free == eng.alloc.n_usable


def test_engine_refuses_what_a_latent_cache_has_no_form_of(params):
    with pytest.raises(ValueError, match="latent"):
        init_block_pool(CFG, 8, 8, kv_quant="int8")
    with pytest.raises(ValueError, match="latent"):
        E.ServingEngine(params, CFG, kv_quant="int8")
    # the paged kernel reads a latent pool like any other: the name
    # that once chose it is accepted and chooses nothing
    E.ServingEngine(params, CFG, attn_impl="flash")


def test_the_ticks_latent_read_equals_the_gathered_one(params):
    """`_latent_decode` (absorb the query, the paged kernel over the
    pool with the row as key and value, keep the first r lanes,
    un-absorb) against `_latent_read` over the gathered table, for
    ragged rows: a dead slot, mid-block, a block's last slot, the
    table's full width."""
    rng = np.random.default_rng(2)
    blk = params["blocks"][1]
    bs, n, w = 8, 24, 4
    pool = {LATENT: jnp.asarray(rng.normal(size=(n, 1, bs, 128)),
                                jnp.float32)}
    pos = np.asarray([0, 13, 23, bs * w - 1], np.int32)
    bt = rng.permutation(np.arange(1, n))[:4 * w].reshape(4, w)
    bt = np.where(np.arange(w)[None] <= pos[:, None] // bs, bt, 0)
    bt[0] = 0
    qn = jnp.asarray(rng.normal(size=(4, 1, 4, 16)), jnp.float32)
    qr = jnp.asarray(rng.normal(size=(4, 1, 4, 8)), jnp.float32)
    got = E._latent_decode(blk, pool, jnp.asarray(bt), jnp.asarray(pos),
                           qn, qr, CFG)
    valid = jnp.arange(w * bs)[None, :] <= pos[:, None]
    want = E._latent_read(blk, pool, jnp.asarray(bt), qn, qr,
                          valid[:, None, None, :], CFG)
    assert got.shape == (4, 4, 16)          # rows, heads, value size
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[:, 0]),
                               atol=2e-5)


@pytest.mark.parametrize("spec_k", [0, 2], ids=["plain", "drafts"])
def test_latent_tick_tokens_equal_the_gathered_reference(params, spec_k,
                                                         request):
    """Engine level, as `tests/test_serving.py` has it for K/V pools:
    the latent engine's streams through the kernel equal those of the
    same tick reading the gathered table, with and without drafts."""
    def streams():
        eng = E.ServingEngine(params, CFG, n_blocks=48, block_size=8,
                              max_slots=4, prefill_chunk=16, spec_k=spec_k,
                              lifecycle=False)
        motif = _tokens(6, seed=8)
        for i, p in enumerate((np.tile(motif, 3), _tokens(27, seed=1),
                               np.tile(motif[:4], 3))):
            eng.submit(p, 10, rid=f"r{i}")
        out = eng.run()
        return {rid: out[rid].tolist() for rid in sorted(out)}, eng

    got, eng = streams()
    request.getfixturevalue("gathered_tick")
    want, _ = streams()
    assert got == want
    if spec_k:
        assert eng.counters["spec_drafted"] > 0


def test_absorbed_attention_equals_expanded():
    rng = np.random.default_rng(0)
    b, t, s, h, dn, dr, dv, r = 2, 5, 24, 4, 16, 8, 16, 16
    f = lambda *sh: jnp.asarray(rng.normal(size=sh), jnp.float32)
    qn, qr, c, kr, kv_b = f(b, t, h, dn), f(b, t, h, dr), f(b, s, r), \
        f(b, s, dr), f(r, h, dn + dv)
    valid = jnp.asarray(rng.random((b, 1, t, s)) < 0.7).at[..., 0].set(True)
    want = latent_attention(qn, qr, c, kr, kv_b, valid, 0.2)
    rows = jnp.concatenate([c, kr, jnp.zeros((b, s, 104))], -1)  # 128 lanes
    got = latent_attention_absorbed(qn, qr, rows, kv_b, valid, 0.2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_routed_layer_matches_the_reference_with_a_bias_that_changes_the_choice(
        params):
    blk = params["blocks"][1]
    h = jnp.asarray(np.random.default_rng(5).normal(size=(48, 64)), jnp.float32)
    y, idx = T.routed_ffn(blk, h, CFG)
    want = reference._routed(blk, h, SHAPES, SCALE)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5)
    # the bias did change who was chosen, so the test tells s from s + b
    no_bias = dict(blk["experts"], route_bias=jnp.zeros(8))
    _, plain = moe.routed_experts_ffn(no_bias, h, 2, SCALE)
    assert (np.sort(np.asarray(idx), -1)
            != np.sort(np.asarray(plain), -1)).any(-1).mean() > 0.25


def test_no_assignment_is_dropped_at_ten_times_skew(params):
    """Every token's K choices reach their experts whatever the load:
    with a bias that sends all tokens to the same two experts (ten
    times the balanced load and more) the layer still equals the
    reference, and each token's weights sum to the scale."""
    ex = dict(params["blocks"][1]["experts"],
              route_bias=jnp.asarray([9., 9., 0, 0, 0, 0, 0, 0], jnp.float32))
    h = jnp.asarray(np.random.default_rng(6).normal(size=(80, 64)), jnp.float32)
    y, idx = moe.routed_experts_ffn(ex, h, 2, SCALE)
    counts = np.bincount(np.asarray(idx).ravel(), minlength=8)
    assert counts.sum() == 160 and counts[:2].tolist() == [80, 80]
    assert counts.max() >= 10 * (160 / 8) / 2.5          # 80 against 20
    blk = dict(params["blocks"][1], experts=ex)
    blk.pop("shared")
    want = reference._routed(dict(blk, shared=_zero_shared()), h, SHAPES, SCALE)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=2e-5)
    logits = jnp.einsum("td,de->te", h, ex["router"])
    _, w = moe.sigmoid_topk_routing(logits, ex["route_bias"], 2, SCALE)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), SCALE, rtol=1e-5)


def _zero_shared():
    z = lambda *sh: {"W": jnp.zeros(sh, jnp.float32)}
    return {"gate": z(64, 32), "up": z(64, 32), "down": z(32, 64)}


def _round_to_8_bits(tree):
    """Every matrix through int8 with a per-column scale and back."""
    def r8(w):
        if w.ndim < 2:
            return w
        s = jnp.maximum(jnp.abs(w).max(-2, keepdims=True), 1e-8) / 127.0
        return jnp.round(w / s) * s
    return jax.tree_util.tree_map(r8, tree)


def _bias_in_the_weights(logits, bias, top_k, scale):
    s = jax.nn.sigmoid(logits.astype(jnp.float32)) + bias
    w, idx = jax.lax.top_k(s, top_k)
    return idx, w / (w.sum(-1, keepdims=True) + 1e-20) * scale


def _without_rotary_score(p):
    """q_rope = 0: the rotary 64 dimensions add nothing to any score."""
    dn, dr = CFG.qk_nope_head_dim, CFG.qk_rope_head_dim
    keep = jnp.tile(jnp.arange(dn + dr) < dn, CFG.n_heads)
    blocks = [dict(b, q=dict(b["q"], W=b["q"]["W"] * keep))
              for b in p["blocks"]]
    return dict(p, blocks=blocks)


def _without_shared(p):
    return dict(p, blocks=[{k: v for k, v in b.items() if k != "shared"}
                           for b in p["blocks"]])


PROBES = {
    "weights-in-8-bits": lambda p, cfg, mp: (_round_to_8_bits(p), cfg),
    "bias-in-the-weights": lambda p, cfg, mp: (
        mp.setattr(moe, "sigmoid_topk_routing", _bias_in_the_weights), (p, cfg))[1],
    "no-rotary-in-the-score": lambda p, cfg, mp: (_without_rotary_score(p), cfg),
    "no-2.446": lambda p, cfg, mp: (p, replace(cfg, routed_scaling_factor=1.0)),
    "no-shared-experts": lambda p, cfg, mp: (_without_shared(p), cfg),
}


@pytest.mark.parametrize("probe", sorted(PROBES))
def test_a_missing_term_or_a_lower_precision_fails_the_comparison(
        params, probe, monkeypatch):
    """Each of these programs is wrong in one way; the comparison that
    passes the right one (same tokens, same tolerance) must refuse it."""
    toks = _tokens(40)
    ref = _reference_logits(params, toks)
    wrong_params, wrong_cfg = PROBES[probe](params, CFG, monkeypatch)
    got = np.asarray(T.forward(wrong_params, jnp.asarray(toks)[None],
                               wrong_cfg)[0])
    assert np.abs(got - ref).max() > 50 * LOGIT_TOL
