"""Serving runtime (`shallowspeed_tpu/serving/`): paged KV cache +
continuous-batching decode server.

The load-bearing invariants:

- **Stream parity.** Every request served concurrently reproduces its
  solo `generate()` token stream exactly (fixed seeds, greedy AND
  sampled) — paged attention shares `kv_cache.masked_attention` with
  the contiguous path and sampling shares the per-request
  `fold_in(PRNGKey(seed), token_index)` key schedule.
- **Compile hygiene.** Requests join/leave the running batch with ZERO
  new executables after warmup (fixed slot capacity, geometric
  block-table width buckets, donated pools) — the serving analog of
  `test_vm_executables_compile_exactly_once`.
- **Chunked prefill.** A long prompt admitted mid-run never freezes
  in-flight decodes for more than one chunk tick.
- **Allocator soundness.** alloc == free at drain; OOM evicts the
  newest running request (re-queued, stream continues exactly) and
  can never deadlock.
"""

import json
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shallowspeed_tpu.models import transformer as T
from shallowspeed_tpu.models.generate import generate, init_kv_cache, prefill
from shallowspeed_tpu.serving import (BlockAllocator, OutOfBlocks,
                                      ServingEngine, blocks_for,
                                      init_block_pool,
                                      paged_read_bytes_per_tick,
                                      table_width)

CFG = T.TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                          max_seq=128)


@pytest.fixture(scope="module")
def params():
    return jax.device_put(T.init(CFG, seed=1))


def toks(seed=0, t=12, vocab=64):
    rng = np.random.default_rng(seed)
    return rng.integers(0, vocab, (t,)).astype(np.int32)


def solo(params, prompt, max_new, cfg=CFG, **kw):
    return np.asarray(generate(params, prompt[None, :], cfg, max_new,
                               **kw))[0]


# ------------------------------------------------- allocator + pools


def test_block_allocator_invariants():
    a = BlockAllocator(8)           # block 0 reserved -> 7 usable
    assert a.n_usable == 7 and a.n_free == 7
    got = a.alloc(3)
    assert len(got) == 3 and 0 not in got       # scratch never issued
    assert a.n_free == 4 and a.n_allocated == 3
    with pytest.raises(OutOfBlocks):
        a.alloc(5)                  # all-or-nothing: nothing leaked
    assert a.n_free == 4
    with pytest.raises(ValueError):
        a.free([99])                # not allocated
    a.free(got)
    assert a.n_free == 7 and a.n_allocated == 0   # balanced at drain
    with pytest.raises(ValueError):
        BlockAllocator(1)           # nothing usable past scratch


def test_blocks_for_and_table_width():
    assert blocks_for(0, 16) == 0
    assert blocks_for(1, 16) == 1
    assert blocks_for(16, 16) == 1
    assert blocks_for(17, 16) == 2
    # geometric width buckets: O(log) executables as tables grow
    assert table_width(1, 4) == 4
    assert table_width(4, 4) == 4
    assert table_width(5, 4) == 8
    assert table_width(33, 4) == 64


def test_init_block_pool_shapes_and_errors():
    pools = init_block_pool(CFG, 8, 16)
    assert len(pools) == CFG.n_layers
    assert pools[0]["k"].shape == (8, CFG.kv_heads, 16, CFG.head_dim)
    q = init_block_pool(CFG, 8, 16, kv_quant="int8")
    assert q[0]["k"].dtype == jnp.int8
    assert q[0]["k_s"].shape == (8, CFG.kv_heads, 16, 1)
    with pytest.raises(ValueError, match="kv_quant"):
        init_block_pool(CFG, 8, 16, kv_quant="fp4")
    with pytest.raises(ValueError, match="n_blocks"):
        init_block_pool(CFG, 1, 16)


# ------------------------------------- satellites: typed errors, asarray


def test_init_kv_cache_rejects_unknown_quant_mode():
    """Satellite: the bare `assert kv_quant == "int8"` became a typed
    ValueError naming the supported modes — asserts vanish under
    `python -O`, and this gate guards a production cache layout."""
    with pytest.raises(ValueError, match="int8"):
        init_kv_cache(CFG, 2, kv_quant="fp8")
    assert init_kv_cache(CFG, 1, cache_len=8, kv_quant="int8")


def test_decode_report_rejects_nonpositive_inputs(params):
    from shallowspeed_tpu.models.generate import decode_report

    with pytest.raises(ValueError, match="seconds"):
        decode_report(params, CFG, batch=1, cache_len=8, n_tokens=4,
                      seconds=0.0)
    with pytest.raises(ValueError, match="n_tokens"):
        decode_report(params, CFG, batch=1, cache_len=8, n_tokens=0,
                      seconds=1.0)


def test_generate_converts_prompt_on_no_padding_branch(params):
    """Satellite: `generate` now runs `jnp.asarray` on BOTH branches.
    A prompt whose bucket equals its length (tp == tp_b: the
    no-padding branch) used to pass the caller's raw array straight
    into jit — an int64 host array must normalize identically on both
    branches."""
    # max_seq 128, max_new 104 -> bucket cap = 24 == tp: no padding
    p32 = toks(3, t=24)
    p64 = p32.astype(np.int64)
    a = solo(params, p32, 8, temperature=0.0)
    b = np.asarray(generate(params, p64[None, :], CFG, 8,
                            temperature=0.0))[0]
    np.testing.assert_array_equal(a, b)
    # padded branch, same dtypes
    c = solo(params, toks(3, t=10), 8, temperature=0.0)
    d = np.asarray(generate(params, toks(3, t=10).astype(np.int64)[None],
                            CFG, 8, temperature=0.0))[0]
    np.testing.assert_array_equal(c, d)


# -------------------------------------- paged vs contiguous numerics


def test_prefill_chunk_logits_match_contiguous_prefill(params):
    """The paged prefill's last-position logits match the contiguous
    `prefill`'s to 1e-4 — same cache math read through the gathered
    block table (`kv_cache.masked_attention` is shared)."""
    from shallowspeed_tpu.serving.engine import _prefill_chunk

    prompt = toks(5, t=14)
    ref, _ = prefill(params, jnp.asarray(prompt[None]), CFG,
                     init_kv_cache(CFG, 1, cache_len=32))
    # pool/chunk/width shapes shared with the engine tests below, so
    # this compiles (at most) once per suite run
    pools = init_block_pool(CFG, 32, 8)
    alloc = BlockAllocator(32)
    table = alloc.alloc(blocks_for(14, 8))
    c = 16
    tokens = np.zeros((1, c), np.int32)
    tokens[0, :14] = prompt
    bt = np.zeros((1, table_width(len(table), 4)), np.int32)
    bt[0, :len(table)] = table
    logits, pools, _ = _prefill_chunk(params, pools, tokens, np.int32(0),
                                      np.int32(14), bt, np.int32(0),
                                      np.int32(0), cfg=CFG)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("quant", [False, True], ids=["f32", "int8"])
def test_paged_attention_matches_cached_attention(params, quant):
    """Block-gathered attention == contiguous `_cached_attention` on
    identical cache contents (to fp-reorder noise): the read path's
    only difference is the gather. int8 pools quantize per (row, head,
    position) exactly like the contiguous int8 cache, so the parity
    holds there too — the default-tier int8 canary (the full int8
    stream oracle rides the slow tier)."""
    from shallowspeed_tpu.models.kv_cache import (cache_write,
                                                  cached_attention,
                                                  masked_attention)
    from shallowspeed_tpu.serving.cache import gather_table, write_rows

    rng = np.random.default_rng(0)
    bs, n_pos = 8, 19
    kv_quant = "int8" if quant else ""
    kv = [rng.normal(size=(1, n_pos, CFG.kv_heads,
                           CFG.head_dim)).astype(np.float32)
          for _ in range(2)]
    q = jnp.asarray(rng.normal(
        size=(1, 1, CFG.n_heads, CFG.head_dim)).astype(np.float32))
    cache = init_kv_cache(CFG, 1, cache_len=32, kv_quant=kv_quant)[0]
    cache = cache_write(cache, jnp.asarray(kv[0]), jnp.asarray(kv[1]), 0)
    pool = init_block_pool(CFG, 32, bs, kv_quant=kv_quant)[0]
    table = [3, 1, 5]                      # deliberately out of order
    for pos in range(n_pos):
        pool = write_rows(
            pool, jnp.asarray(kv[0][:, pos]), jnp.asarray(kv[1][:, pos]),
            jnp.asarray([table[pos // bs]]), jnp.asarray([pos % bs]),
            quant=quant)
    bt = jnp.asarray([table + [0]], jnp.int32)       # padded width 4
    pos = n_pos - 1
    ref = cached_attention(q, cache, pos, CFG)
    view = gather_table(pool, bt)
    valid = (jnp.arange(4 * bs) <= pos)[None, None, None, None, :]
    got = masked_attention(q, view, valid, CFG)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


# --------------------------------------------- stream-parity oracle


@pytest.mark.parametrize("kwargs", [
    {"temperature": 0.0},
    {"temperature": 1.0, "seed": 7},
    {"temperature": 0.7, "seed": 3},
], ids=["greedy", "sampled", "temp0.7"])
def test_solo_request_matches_generate(params, kwargs):
    """A request served alone reproduces its `generate()` stream
    token-for-token — the continuous-batching correctness oracle's
    base case, greedy and sampled (same fold_in key schedule)."""
    prompt = toks(11, t=13)
    ref = solo(params, prompt, 10, **kwargs)
    eng = ServingEngine(params, CFG, n_blocks=32, block_size=8,
                        max_slots=4, prefill_chunk=16)
    eng.submit(prompt, 10, temperature=kwargs.get("temperature", 0.0),
               seed=kwargs.get("seed", 0), rid="q")
    res = eng.run()
    np.testing.assert_array_equal(res["q"], ref)
    assert eng.alloc.n_free == eng.alloc.n_usable


def test_concurrent_mixed_lengths_match_solo_oracles(params):
    """N concurrent requests with different prompt lengths, max_new,
    and samplers — including one submitted MID-RUN (joins the running
    batch) — each reproduce their solo stream exactly."""
    # max_new=10 signatures deliberately match the solo-parity test's
    # compiled generate() oracles (warm jit cache); 12 and 6 are fresh
    reqs = {
        "a": (toks(0, t=5), 10, 0.0, 0),
        "b": (toks(1, t=23), 12, 1.0, 7),
        "c": (toks(2, t=40), 6, 0.0, 0),
        "late": (toks(3, t=17), 10, 1.0, 11),
    }
    oracle = {k: solo(params, p, mn, temperature=tmp, seed=s)
              for k, (p, mn, tmp, s) in reqs.items()}
    eng = ServingEngine(params, CFG, n_blocks=32, block_size=8,
                        max_slots=4, prefill_chunk=16)
    for k in ("a", "b", "c"):
        p, mn, tmp, s = reqs[k]
        eng.submit(p, mn, temperature=tmp, seed=s, rid=k)
    for _ in range(4):                     # a/b/c already decoding...
        eng.step()
    p, mn, tmp, s = reqs["late"]
    eng.submit(p, mn, temperature=tmp, seed=s, rid="late")  # ...joins
    res = eng.run()
    for k, ref in oracle.items():
        np.testing.assert_array_equal(res[k], ref, err_msg=k)
    assert eng.alloc.n_free == eng.alloc.n_usable


def test_zero_recompiles_across_request_churn(params):
    """After warmup, requests joining and leaving the batch add ZERO
    executables (`fn._cache_size`, the counter the analysis retrace
    rule reads) — occupancy is data, not shape: fixed slot count,
    geometric table-width buckets, fixed prefill chunk."""
    eng = ServingEngine(params, CFG, n_blocks=32, block_size=8,
                        max_slots=4, prefill_chunk=16)
    # warmup: lengths walking every width bucket the churn uses
    for i, (t, mn) in enumerate([(5, 6), (23, 8), (40, 6)]):
        eng.submit(toks(20 + i, t=t), mn, rid=f"w{i}")
    eng.run()
    warm = eng.executable_counts()
    for i, (t, mn, tmp) in enumerate(
            [(9, 7, 0.0), (31, 5, 1.0), (14, 9, 0.0), (44, 6, 0.0),
             (3, 8, 1.0)]):
        eng.submit(toks(40 + i, t=t), mn, temperature=tmp, rid=f"c{i}")
        eng.step()                  # staggered joins/leaves
    eng.run()
    assert eng.executable_counts() == warm, (
        f"request churn recompiled: {warm} -> "
        f"{eng.executable_counts()}")


def test_chunked_prefill_never_stalls_decode(params):
    """A long prompt admitted mid-run prefills one chunk per engine
    step INTERLEAVED with decode ticks: an in-flight request's stream
    advances every step (tpot bounded at one chunk tick) instead of
    freezing for the whole prefill."""
    eng = ServingEngine(params, CFG, n_blocks=32, block_size=8,
                        max_slots=4, prefill_chunk=16)
    eng.submit(toks(0, t=6), 40, rid="short")
    while (eng.poll("short")["status"] != "running"
           or len(eng.poll("short")["tokens"]) < 2):
        eng.step()
    eng.submit(toks(1, t=60), 4, rid="long")   # 4 chunks of prefill
    deltas = []
    while eng.poll("long")["status"] != "done":
        before = len(eng.poll("short")["tokens"])
        eng.step()
        deltas.append(len(eng.poll("short")["tokens"]) - before)
    assert min(deltas) >= 1, (
        f"decode stalled during chunked prefill: per-step token "
        f"deltas {deltas}")
    # and the long request still matches its solo oracle
    res = eng.run()
    np.testing.assert_array_equal(
        res["long"], solo(params, toks(1, t=60), 4, temperature=0.0))


def test_oom_evicts_requeues_and_balances(params):
    """Pool pressure: 3 requests whose steady-state footprint exceeds
    the pool force the evict-newest policy — the evicted request
    re-queues, re-prefills prompt + generated, and still reproduces
    its solo stream; the allocator balances at drain and never
    deadlocks."""
    reqs = {k: (toks(50 + i, t=24), 16) for i, k in enumerate("abc")}
    oracle = {k: solo(params, p, mn, temperature=0.0)
              for k, (p, mn) in reqs.items()}
    # 13 usable blocks * 8 = 104 positions < 3 * (24 + 16) = 120
    eng = ServingEngine(params, CFG, n_blocks=14, block_size=8,
                        max_slots=4, prefill_chunk=16)
    for k, (p, mn) in reqs.items():
        eng.submit(p, mn, rid=k)
    res = eng.run()
    for k in reqs:
        np.testing.assert_array_equal(res[k], oracle[k], err_msg=k)
    assert eng.counters["preempted"] >= 1
    assert eng.alloc.n_free == eng.alloc.n_usable
    assert eng.alloc.n_allocated == 0
    rec = {r["id"]: r for r in eng.request_records}
    assert sum(r["preempted"] for r in rec.values()) \
        == eng.counters["preempted"]


def test_submit_rejects_unservable_requests(params):
    eng = ServingEngine(params, CFG, n_blocks=8, block_size=8,
                        max_slots=2)
    with pytest.raises(ValueError, match="max_seq"):
        eng.submit(toks(0, t=100), 64)
    with pytest.raises(ValueError, match="blocks"):
        eng.submit(toks(0, t=60), 40)   # 13 blocks > 7 usable
    eng.submit(toks(0, t=8), 4, rid="ok")
    with pytest.raises(ValueError, match="duplicate"):
        eng.submit(toks(0, t=8), 4, rid="ok")


def test_int8_paged_matches_solo_int8_stream(params):
    """int8 pools quantize per (row, head, position) exactly like the
    contiguous int8 cache, so a greedy paged stream matches the solo
    `generate(kv_quant='int8')` stream."""
    prompt = toks(7, t=18)
    ref = solo(params, prompt, 10, temperature=0.0, kv_quant="int8")
    eng = ServingEngine(params, CFG, n_blocks=32, block_size=8,
                        max_slots=4, prefill_chunk=16, kv_quant="int8")
    eng.submit(prompt, 10, rid="q")
    np.testing.assert_array_equal(eng.run()["q"], ref)


def test_gqa_rope_swiglu_config_parity(params):
    """The serving tick's per-row rope + GQA pools reproduce the solo
    stream on a modern block config (rope, rmsnorm, swiglu, grouped
    KV heads)."""
    cfg = T.TransformerConfig(vocab=64, d_model=32, n_heads=4,
                              n_kv_heads=2, n_layers=2, max_seq=96,
                              rope=True, norm="rmsnorm", ffn="swiglu")
    p2 = jax.device_put(T.init(cfg, seed=2))
    prompt = toks(9, t=19)
    for kwargs in ({"temperature": 0.0}, {"temperature": 1.0, "seed": 5}):
        ref = solo(p2, prompt, 8, cfg=cfg, **kwargs)
        eng = ServingEngine(p2, cfg, n_blocks=24, block_size=8,
                            max_slots=2, prefill_chunk=16)
        eng.submit(prompt, 8, temperature=kwargs.get("temperature", 0.0),
                   seed=kwargs.get("seed", 0), rid="q")
        np.testing.assert_array_equal(eng.run()["q"], ref,
                                      err_msg=str(kwargs))


# ------------------------------------------- telemetry: schema v6 + SLO


def test_request_events_validate_schema_v6(params, tmp_path):
    from shallowspeed_tpu.metrics import MetricsLogger
    from shallowspeed_tpu.telemetry import schema

    assert schema.SCHEMA_VERSION >= 6
    path = tmp_path / "serve.jsonl"
    eng = ServingEngine(params, CFG, n_blocks=32, block_size=8,
                        max_slots=4, prefill_chunk=16,
                        metrics=MetricsLogger(path, kind="serve"),
                        log_every=4)
    eng.submit(toks(0, t=9), 8, rid="a")
    eng.submit(toks(1, t=14), 6, temperature=1.0, seed=2, rid="b")
    eng.run()
    assert schema.validate_file(path) == []
    recs = [json.loads(l) for l in path.read_text().splitlines()]
    reqs = [r for r in recs if r.get("event") == "request"]
    assert {r["id"] for r in reqs} == {"a", "b"}
    for r in reqs:
        assert r["ttft_ms"] >= 0 and r["tpot_ms"] >= 0
        assert r["tokens_in"] > 0 and r["tokens_out"] > 0
        assert "queue_depth" in r and "preempted" in r
    gen = [r for r in recs if r.get("event") == "generate"]
    assert gen and all("hbm_gbps" in g and "free_blocks" in g
                       for g in gen)
    # malformed request lines are rejected
    assert schema.validate_line({"event": "request", "id": "x"}) != []
    assert schema.validate_line(
        {"event": "request", "id": "x", "ttft_ms": 1.0, "tokens_in": 1,
         "tokens_out": 1, "queue_depth": "deep"}) != []


def test_goodput_reduces_request_percentiles(params, tmp_path):
    """The `--goodput` reducer reports p50/p95 ttft and tpot from the
    schema-v6 request events, and the formatted report prints them."""
    from shallowspeed_tpu.metrics import MetricsLogger
    from shallowspeed_tpu.telemetry.goodput import (format_report,
                                                    run_goodput)

    path = tmp_path / "serve.jsonl"
    eng = ServingEngine(params, CFG, n_blocks=32, block_size=8,
                        max_slots=4, prefill_chunk=16,
                        metrics=MetricsLogger(path, kind="serve"))
    for i in range(4):
        eng.submit(toks(i, t=7 + 5 * i), 6, rid=f"r{i}")
    eng.run()
    rep = run_goodput(path)
    req = rep["requests"]
    assert req["n_requests"] == 4
    assert req["ttft_ms_p50"] <= req["ttft_ms_p95"]
    assert req["tpot_ms_p50"] <= req["tpot_ms_p95"]
    assert req["tokens_out"] == 24
    assert "requests 4" in format_report(rep)


def test_request_summary_percentiles():
    from shallowspeed_tpu.telemetry.report import (percentile,
                                                   request_summary)

    assert request_summary([]) is None
    assert percentile([], 50) is None
    recs = [{"ttft_ms": float(i), "tpot_ms": float(10 * i),
             "tokens_in": 2, "tokens_out": 3, "preempted": i % 2}
            for i in range(1, 21)]
    s = request_summary(recs)
    assert s["n_requests"] == 20
    assert s["ttft_ms_p50"] == pytest.approx(10.0, abs=1.0)
    assert s["ttft_ms_p95"] == pytest.approx(19.0, abs=1.0)
    assert s["tpot_ms_p95"] == pytest.approx(190.0, abs=10.0)
    assert s["tokens_out"] == 60 and s["preempted"] == 10
    # single-token generations carry no tpot — summary degrades
    s1 = request_summary([{"ttft_ms": 5.0, "tokens_in": 1,
                           "tokens_out": 1}])
    assert s1["tpot_ms_p50"] is None and s1["ttft_ms_p50"] == 5.0


# ------------------------------------ fast decode path (round 14)
#
# Three composable levers, each gated separately: quantized weight
# storage with fused dequant, the paged Pallas flash-decode kernel,
# and self-drafting speculative decoding. The oracles are the same
# ones PR 7 pinned: solo `generate()` streams and the gather_table
# XLA read path.


def test_quantize_weights_modes_and_errors(params):
    with pytest.raises(ValueError, match="weight_quant"):
        T.quantize_weights(params, "int4")
    assert T.quantize_weights(params, "") is params
    qp = T.quantize_weights(params, "int8")
    assert T.weight_quant_mode(qp) == "int8"
    assert T.weight_quant_mode(params) == ""
    blk = qp["blocks"][0]
    assert blk["qkv"]["Wq"].dtype == jnp.int8
    assert blk["qkv"]["Ws"].dtype == jnp.float32
    assert blk["qkv"]["Ws"].shape == (blk["qkv"]["Wq"].shape[1],)
    assert "W" not in blk["qkv"] and "b" in blk["qkv"]
    # norms and embeddings stay unquantized (O(d) / gathered rows)
    assert "g" in blk["ln1"] and qp["tok_emb"].dtype == params[
        "tok_emb"].dtype
    # idempotent: re-quantizing an already-quantized tree is a no-op
    qp2 = T.quantize_weights(qp, "int8")
    np.testing.assert_array_equal(np.asarray(qp2["blocks"][0]["qkv"][
        "Wq"]), np.asarray(blk["qkv"]["Wq"]))


def test_cast_params_preserves_quantized_storage(params):
    """The mixed-precision boundary must not rewiden quantized
    leaves: Wq stays int8/fp8 (a bf16 cast would be the materialized
    dequant copy the analysis rule flags) and the f32 scales stay f32
    (numerics, not bulk bytes)."""
    qp = T.quantize_weights(params, "int8")
    cast = jax.eval_shape(lambda p: T.cast_params(p, jnp.bfloat16), qp)
    blk = cast["blocks"][0]
    assert blk["qkv"]["Wq"].dtype == jnp.int8
    assert blk["qkv"]["Ws"].dtype == jnp.float32
    assert blk["qkv"]["b"].dtype == jnp.bfloat16   # plain floats cast
    assert cast["tok_emb"].dtype == jnp.bfloat16


def test_dequant_matmul_matches_explicit_dequant():
    """The fused form computes the same number as the materialized
    dequant (per-out-channel scale is constant along K, so scaling
    the accumulator is exact reassociation)."""
    from shallowspeed_tpu.ops.matmul import dequant_matmul

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(5, 16)), jnp.float32)
    w = rng.normal(size=(16, 8)).astype(np.float32)
    ws = (np.abs(w).max(axis=0) / 127.0).astype(np.float32)
    wq = np.clip(np.round(w / ws), -127, 127).astype(np.int8)
    ref = x @ jnp.asarray(wq.astype(np.float32) * ws)
    got = dequant_matmul(x, jnp.asarray(wq), jnp.asarray(ws))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("mode", ["int8", "fp8"])
def test_quantized_weight_serving_matches_solo_stream(params, mode):
    """A request served with quantized weight storage reproduces the
    solo `generate()` stream over the SAME quantized tree — the
    fused-dequant tick is numerics-equal to the contiguous path's
    dequant-dispatching `_dense`, greedy and sampled."""
    if mode == "fp8" and T._FP8_DTYPE is None:
        pytest.skip("no float8_e4m3fn in this jax build")
    qp = jax.device_put(T.quantize_weights(params, mode))
    prompt = toks(13, t=14)
    for kwargs in ({"temperature": 0.0}, {"temperature": 1.0, "seed": 9}):
        ref = np.asarray(generate(qp, prompt[None], CFG, 8,
                                  **kwargs))[0]
        eng = ServingEngine(params, CFG, n_blocks=32, block_size=8,
                            max_slots=4, prefill_chunk=16,
                            weight_quant=mode)
        eng.submit(prompt, 8, temperature=kwargs.get("temperature", 0.0),
                   seed=kwargs.get("seed", 0), rid="q")
        np.testing.assert_array_equal(eng.run()["q"], ref,
                                      err_msg=f"{mode} {kwargs}")


def test_int8_weight_tick_bytes_beat_bf16_baseline():
    """THE byte-model acceptance gate: the int8-weight decode tick's
    read bytes price at <= 0.55x the bf16 baseline per
    `paged_read_bytes_per_tick`, and the param term is pinned EXACTLY
    against the traced tick's own param invar bytes (walker
    `aval_bytes` over `jax.make_jaxpr` on eval_shape structs — no
    device copies), int8 weights + f32 scales + bf16 embeddings +
    int8 KV mixed in one model."""
    from shallowspeed_tpu.analysis.walker import aval_bytes
    from shallowspeed_tpu.serving.cache import param_read_bytes
    from shallowspeed_tpu.serving.engine import _decode_tick

    cfg = T.TransformerConfig(vocab=64, d_model=128, n_heads=4,
                              n_layers=2, max_seq=64,
                              compute_dtype=jnp.bfloat16)
    params = T.init(cfg, seed=0)
    qp = T.quantize_weights(params, "int8")
    bs, touched, rows = 8, 2, 4
    base = paged_read_bytes_per_tick(params, cfg, touched, bs, rows,
                                     kv_quant="int8")
    fast = paged_read_bytes_per_tick(qp, cfg, touched, bs, rows,
                                     kv_quant="int8")
    assert fast <= 0.55 * base, (fast, base, fast / base)

    # walker pin: trace the tick over the post-cast tree (the dtypes
    # the engine actually serves — `cast_params` inside is then the
    # identity) and compare invar bytes term by term
    cast = jax.eval_shape(
        lambda p: T.cast_params(p, cfg.compute_dtype), qp)
    pools = jax.eval_shape(
        lambda: init_block_pool(cfg, 8, bs, kv_quant="int8"))
    s, w = rows, 4
    i32 = lambda *sh: jax.ShapeDtypeStruct(sh, np.int32)  # noqa: E731
    closed = jax.make_jaxpr(
        lambda p, pl, tok, pos, bt, temp, seeds, idx, prev, ahead:
        _decode_tick(p, pl, tok, pos, bt, temp, seeds, idx, prev, ahead,
                     cfg=cfg, top_k=0, top_p=0.0))(
        cast, pools, i32(s), i32(s), i32(s, w),
        jax.ShapeDtypeStruct((s,), np.float32),
        jax.ShapeDtypeStruct((s,), np.uint32), i32(s), i32(s),
        jax.ShapeDtypeStruct((s,), np.bool_))
    n_param = len(jax.tree_util.tree_leaves(cast))
    traced_param_bytes = sum(aval_bytes(v.aval)
                             for v in closed.jaxpr.invars[:n_param])
    assert traced_param_bytes == param_read_bytes(qp, cfg)
    # ...and the per-block KV term equals one traced pool block's bytes
    pool_leaves = jax.tree_util.tree_leaves(pools)
    per_block_traced = sum(aval_bytes(l) for l in pool_leaves) \
        // (cfg.n_layers * 8)
    model_per_block = (fast - param_read_bytes(qp, cfg) - rows * 4) \
        // (cfg.n_layers * touched)
    assert model_per_block == per_block_traced


def test_flash_decode_engine_matches_solo_stream(params):
    """The tick's read is the paged Pallas kernel (interpret mode on
    CPU) whatever `attn_impl` says: both accepted names reproduce the
    solo stream of `generate()` (which reads its contiguous cache with
    `masked_attention`) token-for-token — kernel-vs-reference logits
    sit at ~1e-7, far inside sampling's decision boundaries on this
    model."""
    prompt = toks(17, t=21)
    ref = solo(params, prompt, 10, temperature=0.0)
    for name in ("flash", "gather"):
        eng = ServingEngine(params, CFG, n_blocks=32, block_size=8,
                            max_slots=4, prefill_chunk=16,
                            attn_impl=name)
        eng.submit(prompt, 10, rid="q")
        np.testing.assert_array_equal(eng.run()["q"], ref)
    with pytest.raises(ValueError, match="attn_impl"):
        ServingEngine(params, CFG, n_blocks=8, attn_impl="paged")


def _mixed_streams(params, cfg, spec_k, **kw):
    """Three requests of different lengths through one engine, two of
    them self-similar so that `spec_k` has drafts to verify."""
    eng = ServingEngine(params, cfg, n_blocks=48, block_size=8,
                        max_slots=4, prefill_chunk=16, spec_k=spec_k, **kw)
    for i, p in enumerate((spec_prompt(5, t=18), toks(3, t=29),
                           spec_prompt(9, t=11))):
        eng.submit(p % cfg.vocab, 12, rid=f"r{i}")
    out = eng.run()
    return {rid: out[rid].tolist() for rid in sorted(out)}, eng


@pytest.mark.parametrize("spec_k", [0, 2], ids=["plain", "drafts"])
@pytest.mark.parametrize("form", ["mha", "gqa-window", "int8"])
def test_tick_tokens_equal_the_gathered_reference(params, form, spec_k,
                                                  request):
    """Engine level: the streams of a tick that reads through the
    kernel equal those of the same tick reading the gathered table
    (`conftest.gathered_tick`), with and without draft rows, which
    share a table at consecutive positions and must see each other's
    writes of the same tick."""
    cfg, kw = CFG, {}
    if form == "gqa-window":
        cfg = T.TransformerConfig(vocab=64, d_model=32, n_heads=4,
                                  n_kv_heads=2, n_layers=2, max_seq=64,
                                  rope=True, attn_window=12)
        params = jax.device_put(T.init(cfg, seed=2))
    elif form == "int8":
        kw = {"kv_quant": "int8"}
    got, eng = _mixed_streams(params, cfg, spec_k, **kw)
    request.getfixturevalue("gathered_tick")
    want, ref_eng = _mixed_streams(params, cfg, spec_k, **kw)
    assert got == want
    assert eng.counters["ticks"] == ref_eng.counters["ticks"]
    if spec_k:
        assert eng.counters["spec_drafted"] > 0


def test_flash_decode_full_stack_matches_int8_oracle(params):
    """All three levers at once (int8 weights + int8 KV + flash
    kernel) still reproduce the solo oracle over the same quantized
    tree and int8 cache — the levers compose without drift."""
    qp = jax.device_put(T.quantize_weights(params, "int8"))
    prompt = toks(19, t=18)
    ref = np.asarray(generate(qp, prompt[None], CFG, 9,
                              temperature=0.0, kv_quant="int8"))[0]
    eng = ServingEngine(params, CFG, n_blocks=32, block_size=8,
                        max_slots=4, prefill_chunk=16,
                        weight_quant="int8", kv_quant="int8",
                        attn_impl="flash", spec_k=2)
    eng.submit(prompt, 9, rid="q")
    np.testing.assert_array_equal(eng.run()["q"], ref)


# --------------------------------------------- speculative decoding


def spec_prompt(seed=0, t=18):
    """Self-similar prompt (repeated motif): gives the n-gram
    prompt-lookup proposer something to draft from."""
    rng = np.random.default_rng(seed)
    motif = rng.integers(0, 64, max(2, t // 3)).astype(np.int32)
    return np.concatenate([motif] * (-(-t // len(motif))))[:t]


def test_spec_decode_temp0_stream_identical_and_accepts(params):
    """Temp-0 spec-on streams are token-identical to solo
    `generate()` for EVERY prompt, and on at least one of the probed
    prompts speculation accepts drafts — which must show up as a tick
    count below max_new's one-token-per-tick floor (accepted drafts
    emit extra tokens per tick)."""
    accepted_somewhere = False
    for seed in (0, 5, 9, 23):
        prompt = spec_prompt(seed, t=18)
        ref = solo(params, prompt, 16, temperature=0.0)
        eng = ServingEngine(params, CFG, n_blocks=32, block_size=8,
                            max_slots=4, prefill_chunk=16, spec_k=3)
        eng.submit(prompt, 16, rid="q")
        res = eng.run()
        np.testing.assert_array_equal(res["q"], ref,
                                      err_msg=f"seed={seed}")
        acc = eng.counters["spec_accepted"]
        assert eng.counters["spec_drafted"] >= acc
        if acc > 0:
            accepted_somewhere = True
            # 16 tokens, 1 sampled at prefill -> 15 ticks unsped
            assert eng.counters["ticks"] < 15
        assert eng.alloc.n_free == eng.alloc.n_usable
    assert accepted_somewhere, (
        "no probed prompt produced an accepted draft — the proposer "
        "or the accept rule broke")


def test_spec_decode_seeded_sampling_parity(params):
    """The oracle-sampler parity pin: under seeded SAMPLING the
    spec-on stream equals the solo stream too — every emitted token
    is the oracle draw `sample(fold_in(PRNGKey(seed), i), logits_i)`
    at its own index (the accept rule re-draws the oracle sample, so
    the output distribution is the oracle sampler's by construction,
    not merely in expectation)."""
    prompt = spec_prompt(7, t=15)
    for seed, temp in ((3, 1.0), (11, 0.7)):
        ref = solo(params, prompt, 12, temperature=temp, seed=seed)
        eng = ServingEngine(params, CFG, n_blocks=32, block_size=8,
                            max_slots=4, prefill_chunk=16, spec_k=3)
        eng.submit(prompt, 12, temperature=temp, seed=seed, rid="q")
        np.testing.assert_array_equal(eng.run()["q"], ref,
                                      err_msg=f"seed={seed}")


def test_spec_decode_concurrent_and_under_preemption(params):
    """Spec-on continuous batching under pool pressure: concurrent
    requests (drafts competing for free rows) with forced eviction
    still reproduce every solo stream, and the allocator balances at
    drain — draft-grown tables free cleanly."""
    reqs = {k: (spec_prompt(40 + i, t=20), 12)
            for i, k in enumerate("abc")}
    oracle = {k: solo(params, p, mn, temperature=0.0)
              for k, (p, mn) in reqs.items()}
    # tight pool: 13 usable blocks * 8 = 104 positions < 3 * 32
    eng = ServingEngine(params, CFG, n_blocks=14, block_size=8,
                        max_slots=4, prefill_chunk=16, spec_k=3)
    for k, (p, mn) in reqs.items():
        eng.submit(p, mn, rid=k)
    res = eng.run()
    for k in reqs:
        np.testing.assert_array_equal(res[k], oracle[k], err_msg=k)
    assert eng.alloc.n_free == eng.alloc.n_usable
    assert eng.alloc.n_allocated == 0


def test_spec_decode_zero_new_executables(params):
    """Drafts are DATA in rows that already executed empty: after
    spec-off warmup over the same width buckets, turning speculation
    on compiles nothing new."""
    eng = ServingEngine(params, CFG, n_blocks=32, block_size=8,
                        max_slots=4, prefill_chunk=16)
    eng.submit(spec_prompt(1, t=18), 10, rid="w0")
    eng.run()
    warm = eng.executable_counts()
    eng2 = ServingEngine(params, CFG, n_blocks=32, block_size=8,
                         max_slots=4, prefill_chunk=16, spec_k=3)
    eng2.submit(spec_prompt(2, t=18), 10, rid="s0")
    eng2.submit(spec_prompt(3, t=12), 8, rid="s1")
    eng2.run()
    assert eng2.counters["spec_drafted"] > 0
    assert eng2.executable_counts() == warm, (
        f"speculation recompiled: {warm} -> {eng2.executable_counts()}")


def test_spec_telemetry_schema_v9_and_status_surface(params, tmp_path):
    """Speculation telemetry rides the monitor plane: request lines
    carry the per-request drafted/accepted record, generate lines the
    windowed acceptance rate (all schema-v9-valid), and the monitor
    surfaces spec_accept_rate in /status.json's serving block and
    /metrics."""
    from shallowspeed_tpu.metrics import MetricsLogger
    from shallowspeed_tpu.telemetry import schema
    from shallowspeed_tpu.telemetry.monitor import Monitor

    assert schema.SCHEMA_VERSION >= 9
    path = tmp_path / "spec.jsonl"
    eng = ServingEngine(params, CFG, n_blocks=32, block_size=8,
                        max_slots=4, prefill_chunk=16, spec_k=3,
                        metrics=MetricsLogger(path, kind="serve"),
                        log_every=2)
    eng.submit(spec_prompt(21, t=18), 12, rid="a")
    eng.run()
    assert schema.validate_file(path) == []
    recs = [json.loads(l) for l in path.read_text().splitlines()]
    req = next(r for r in recs if r.get("event") == "request")
    assert req["spec_drafted"] >= req["spec_accepted"] >= 0
    assert req["spec_drafted"] == eng.counters["spec_drafted"]
    gens = [r for r in recs if r.get("event") == "generate"]
    assert gens and all("spec_accept_rate" in g for g in gens)
    mon = Monitor()
    for r in recs:
        mon.note_line(r)
    srv = mon.status()["serving"]
    assert "spec_accept_rate" in srv
    assert "spec_accept_rate" in mon.prometheus()
    # malformed speculation fields are rejected
    assert schema.validate_line(
        {"event": "request", "id": "x", "ttft_ms": 1.0, "tokens_in": 1,
         "tokens_out": 1, "spec_drafted": "many"}) != []
    assert schema.validate_line(
        {"event": "generate", "tokens_per_sec": 1.0,
         "spec_accept_rate": "high"}) != []


# --------------------------- drain + cross-engine failover (round 15)


def test_engine_drain_typed_rejection_and_completion(params):
    """Graceful drain: accepted work (queued AND running) completes,
    new submits raise the typed EngineDraining (the old post-drain
    behavior was implicit), drain() reports completion, and the
    allocator balances — the replica-side half of the router's
    scale-down path."""
    from shallowspeed_tpu.serving import EngineDraining

    eng = ServingEngine(params, CFG, n_blocks=32, block_size=8,
                        max_slots=2, prefill_chunk=16)
    oracle = {k: solo(params, toks(70 + i, t=10), 6, temperature=0.0)
              for i, k in enumerate("abc")}
    for i, k in enumerate("abc"):      # c queues behind 2 slots
        eng.submit(toks(70 + i, t=10), 6, rid=k)
    eng.step()
    assert eng.drain() is False        # in-flight work remains
    with pytest.raises(EngineDraining) as ei:
        eng.submit(toks(80, t=8), 4, rid="late")
    assert ei.value.pending == 3
    res = eng.run()
    for k, ref in oracle.items():
        np.testing.assert_array_equal(res[k], ref, err_msg=k)
    assert eng.drain() is True         # idempotent, now complete
    assert eng.alloc.n_free == eng.alloc.n_usable
    # shed-pause is a different mechanism and must still resume;
    # draining is one-way
    assert eng.draining and not eng.admission_paused


@pytest.mark.parametrize("kwargs", [
    {"temperature": 0.0},
    {"temperature": 0.7, "seed": 11},
], ids=["greedy", "sampled"])
def test_failover_continuation_on_fresh_engine_matches_solo(params,
                                                            kwargs):
    """The cross-process failover mechanism at the engine level (the
    fleet drill's in-process canary): decode a request halfway on one
    engine, then re-submit prompt + tokens-so-far on a FRESH engine
    instance (`submit(generated=)` — a different process in the
    drill). The continuation re-prefills and keeps drawing from
    `fold_in(PRNGKey(seed), i)` at the continued indices, so the
    completed stream is token-identical to the solo `generate()`
    oracle."""
    prompt = toks(33, t=14)
    ref = solo(params, prompt, 10, **kwargs)
    eng1 = ServingEngine(params, CFG, n_blocks=32, block_size=8,
                        max_slots=4, prefill_chunk=16)
    eng1.submit(prompt, 10, temperature=kwargs.get("temperature", 0.0),
                seed=kwargs.get("seed", 0), rid="q")
    while len(eng1.poll("q")["tokens"]) < 4:   # mid-decode "death"
        eng1.step()
    prefix = [int(t) for t in eng1.poll("q")["tokens"]]
    assert 4 <= len(prefix) < 10
    np.testing.assert_array_equal(prefix, ref[:len(prefix)])
    eng2 = ServingEngine(params, CFG, n_blocks=32, block_size=8,
                         max_slots=4, prefill_chunk=16)
    eng2.submit(prompt, 10,
                temperature=kwargs.get("temperature", 0.0),
                seed=kwargs.get("seed", 0), rid="q",
                generated=prefix)
    res = eng2.run()
    np.testing.assert_array_equal(res["q"], ref)
    assert eng2.alloc.n_free == eng2.alloc.n_usable
    # a continuation that already has everything is a caller bug
    with pytest.raises(ValueError, match="nothing left"):
        eng2.submit(prompt, 4, rid="full", generated=[1, 2, 3, 4])


# ------------------------------- satellites: rebucket + atomicity


def test_rebucket_ledger_and_log_executable_growth(params, tmp_path):
    """A long-running request crossing geometric table-width buckets
    re-traces the decode tick O(log max_len) times — not O(len) — and
    every crossing stamps a `table_rebucket` ledger event, so
    attribution never books the retrace as unexplained."""
    from shallowspeed_tpu.metrics import MetricsLogger
    from shallowspeed_tpu.telemetry import schema
    from shallowspeed_tpu.serving.engine import _decode_tick

    path = tmp_path / "rebucket.jsonl"
    before = int(_decode_tick._cache_size())
    cfg2 = T.TransformerConfig(vocab=64, d_model=32, n_heads=4,
                               n_layers=2, max_seq=256)
    p2 = jax.device_put(T.init(cfg2, seed=3))
    # block_size 4, bucket 1: a 4 + 60 = 64-position request walks
    # widths 1 -> 2 -> 4 -> 8 -> 16 (forced boundary crossings)
    eng = ServingEngine(p2, cfg2, n_blocks=32, block_size=4,
                        max_slots=2, prefill_chunk=8, table_bucket=1,
                        metrics=MetricsLogger(path, kind="serve"))
    eng.submit(toks(2, t=4), 60, rid="long")
    eng.run()
    grown = int(_decode_tick._cache_size()) - before
    # O(log): 5 distinct widths for 16 blocks, never one-per-block
    assert 1 <= grown <= 6, grown
    assert schema.validate_file(path) == []
    stamps = [json.loads(l) for l in path.read_text().splitlines()
              if '"table_rebucket"' in l]
    assert stamps, "no table_rebucket ledger stamp at the crossing"
    for s in stamps:
        assert s["event"] == "ledger" and s["width"] != s["prev_width"]
    # crossings observed = distinct consecutive width changes >= grown-1
    assert len(stamps) >= grown - 1


def test_alloc_partial_failure_is_atomic():
    """The all-or-nothing claim in `BlockAllocator.alloc`'s docstring,
    pinned: a failing alloc leaves n_free AND the allocated set
    unchanged (no leaked ids), and the free list still serves the
    original capacity afterwards."""
    a = BlockAllocator(6)               # 5 usable
    got = a.alloc(2)
    free_before, alloc_before = a.n_free, a.n_allocated
    for ask in (4, 100):
        with pytest.raises(OutOfBlocks):
            a.alloc(ask)
        assert a.n_free == free_before
        assert a.n_allocated == alloc_before
    rest = a.alloc(3)                   # the full remainder still works
    assert len(set(got) | set(rest)) == 5
    a.free(got + rest)
    assert a.n_free == a.n_usable


def test_write_rows_scratch_sink_isolation():
    """Pad/inactive rows steered to the scratch block never corrupt
    live reads: writes to SCRATCH_BLOCK land (possibly colliding) in
    block 0 only, every other block is bit-unchanged, and a gathered
    table (which by contract never contains block 0) reads back
    exactly what was written before the scratch traffic."""
    from shallowspeed_tpu.serving.cache import (SCRATCH_BLOCK,
                                                gather_table, write_rows)

    cfg = CFG
    bs = 8
    pool = init_block_pool(cfg, 8, bs)[0]
    rng = np.random.default_rng(2)
    k1 = jnp.asarray(rng.normal(size=(1, cfg.kv_heads, cfg.head_dim)),
                     jnp.float32)
    v1 = jnp.asarray(rng.normal(size=(1, cfg.kv_heads, cfg.head_dim)),
                     jnp.float32)
    pool = write_rows(pool, k1, v1, jnp.asarray([3]), jnp.asarray([5]),
                      quant=False)
    live_before = {n: np.asarray(l) for n, l in pool.items()}
    bt = jnp.asarray([[3, 1]], jnp.int32)
    view_before = {n: np.asarray(l)
                   for n, l in gather_table(pool, bt).items()}
    # a burst of scratch writes, including COLLIDING offsets (three
    # rows, same block 0, same offset — the duplicate-scatter winner
    # is unspecified and must not matter)
    ks = jnp.asarray(rng.normal(size=(3, cfg.kv_heads, cfg.head_dim)),
                     jnp.float32)
    vs = jnp.asarray(rng.normal(size=(3, cfg.kv_heads, cfg.head_dim)),
                     jnp.float32)
    pool = write_rows(pool, ks, vs,
                      jnp.full((3,), SCRATCH_BLOCK, jnp.int32),
                      jnp.asarray([0, 0, 4]), quant=False)
    for name, leaf in pool.items():
        np.testing.assert_array_equal(
            np.asarray(leaf)[1:], live_before[name][1:],
            err_msg=f"{name}: scratch write leaked past block 0")
    view_after = gather_table(pool, bt)
    for name in view_before:
        np.testing.assert_array_equal(
            np.asarray(view_after[name]), view_before[name],
            err_msg=f"{name}: gathered read changed after scratch "
                    f"traffic")


# ---- the in-place write forms against the indexed scatter they replace
#
# `pool.at[blk, :, off, :].set(val)` is what PR 27 took off the device
# path (two pool-sized layout copies per leaf on the TPU); it stays
# here as the reference: on the CPU the new forms are bit-identical.

_POOL_KINDS = {"f32": (jnp.float32, ""), "bf16": (jnp.bfloat16, ""),
               "int8": (jnp.float32, "int8")}


def _filled(pool, rng):
    """The pool with every slot of every leaf drawn from `rng`."""
    return {name: jnp.asarray(
        rng.integers(-127, 128, leaf.shape) if leaf.dtype == jnp.int8
        else rng.normal(size=leaf.shape), leaf.dtype)
        for name, leaf in pool.items()}


def _pool_case(kind, kv_heads, n_blocks=12, bs=8, seed=0):
    """(cfg, a pool with every block filled from the seed, quant)."""
    dt, kv_quant = _POOL_KINDS[kind]
    cfg = replace(CFG, n_kv_heads=kv_heads, compute_dtype=dt)
    pool = init_block_pool(cfg, n_blocks, bs, kv_quant=kv_quant)[0]
    return (cfg, _filled(pool, np.random.default_rng(seed)),
            bool(kv_quant))


def _kv_rows(cfg, n, seed):
    rng = np.random.default_rng(seed)
    dt = cfg.compute_dtype or cfg.dtype
    return [jnp.asarray(rng.normal(size=(n, cfg.kv_heads, cfg.head_dim)),
                        dt) for _ in range(2)]


def _indexed_scatter(pool, k, v, blk, off, quant):
    """The write as it was before PR 27."""
    from shallowspeed_tpu.serving.cache import _kv_update

    return {name: pool[name].at[blk, :, off, :].set(val)
            for name, val in _kv_update(pool, k, v, quant).items()}


def _assert_pools_bit_equal(got, ref, blocks=slice(None)):
    assert got.keys() == ref.keys()
    for name in ref:
        g, r = np.asarray(got[name]), np.asarray(ref[name])
        assert g.dtype == r.dtype and g.shape == r.shape, name
        np.testing.assert_array_equal(
            g[blocks].view(np.uint8), r[blocks].view(np.uint8),
            err_msg=f"{name}: differs from the indexed scatter")


_ROW_CASES = {
    # (block ids, offsets); block 0 is the scratch sink
    "distinct": ([3, 7, 1, 9], [0, 5, 7, 2]),
    "scratch-colliding": ([3, 0, 0, 0, 7], [1, 0, 0, 4, 6]),
    "drafts-one-block": ([4, 4, 4, 4, 2], [2, 3, 4, 5, 0]),
    "drafts-two-blocks": ([4, 4, 6, 6, 0], [6, 7, 0, 1, 0]),
}


@pytest.mark.parametrize("case", list(_ROW_CASES))
@pytest.mark.parametrize("kv_heads", [0, 2], ids=["mha", "gqa"])
@pytest.mark.parametrize("kind", list(_POOL_KINDS))
def test_write_rows_equals_indexed_scatter_bitwise(kind, kv_heads, case):
    """The decode tick's row scatter through the flat (N*Hkv*bs, hd)
    view stores exactly what the (block, :, offset, :) scatter stored,
    scales included: distinct rows, draft rows of one request inside
    one block and across two, and colliding scratch rows (whose winner
    is unspecified, so scratch itself is not compared there)."""
    from shallowspeed_tpu.serving.cache import write_rows

    cfg, pool, quant = _pool_case(kind, kv_heads)
    blk, off = (jnp.asarray(a, jnp.int32) for a in _ROW_CASES[case])
    k, v = _kv_rows(cfg, len(blk), seed=5)
    got = write_rows(pool, k, v, blk, off, quant)
    ref = _indexed_scatter(pool, k, v, blk, off, quant)
    live = slice(1, None) if case == "scratch-colliding" else slice(None)
    _assert_pools_bit_equal(got, ref, live)
    assert any(not np.array_equal(np.asarray(got[n]), np.asarray(pool[n]))
               for n in pool), "nothing was written"


_CHUNK_CASES = {
    # (pos0, n_tok) of a 16-row chunk over blocks of 8
    "aligned-full": (8, 16),
    "tail-block-unaligned": (7, 16),      # bs-1 into a copied tail block
    "ends-mid-block": (16, 11),
    "unaligned-and-mid-block": (15, 6),
    "one-token": (23, 1),
}


@pytest.mark.parametrize("case", list(_CHUNK_CASES))
@pytest.mark.parametrize("kv_heads", [0, 2], ids=["mha", "gqa"])
@pytest.mark.parametrize("kind", list(_POOL_KINDS))
def test_write_chunk_equals_indexed_scatter_bitwise(kind, kv_heads, case):
    """The prefill chunk's merged whole-block write lands rows
    j < n_tok at positions pos0 + j and nothing else: every live block
    equals what the per-row scatter (padding steered to scratch) left,
    for a `pos0` inside a block and an `n_tok` that ends inside one.
    Scratch is not compared: the row form parks padding there, the
    block form writes scratch's own contents back."""
    from shallowspeed_tpu.serving.cache import SCRATCH_BLOCK, write_chunk

    cfg, pool, quant = _pool_case(kind, kv_heads)
    bs, c = 8, 16
    table = jnp.asarray([5, 2, 9, 4, 0, 0], jnp.int32)     # padded W 6
    pos0, n_tok = _CHUNK_CASES[case]
    k, v = _kv_rows(cfg, c, seed=6)
    got = write_chunk(pool, k, v, table, jnp.int32(pos0),
                      jnp.int32(n_tok), quant)
    pos = pos0 + np.arange(c)
    keep = np.arange(c) < n_tok
    blk = np.where(keep, np.asarray(table)[np.clip(pos // bs, 0, 5)],
                   SCRATCH_BLOCK)
    off = np.where(keep, pos % bs, 0)
    ref = _indexed_scatter(pool, k, v, jnp.asarray(blk, jnp.int32),
                           jnp.asarray(off, jnp.int32), quant)
    _assert_pools_bit_equal(got, ref, slice(1, None))
    _assert_pools_bit_equal({n: l[:1] for n, l in got.items()},
                            {n: l[:1] for n, l in pool.items()})


@pytest.mark.parametrize("kv_quant", ["", "int8"], ids=["f32", "int8"])
def test_prefill_chunk_diverging_in_tail_leaves_shared_block_bit_unchanged(
        params, kv_quant):
    """The program-level copy-on-write contract under the block-form
    write: a request that diverges at the LAST slot of a shared tail
    block (`pos0` = bs-1 into its copy) leaves the shared block and
    the shared prefix block bit-unchanged; its own copy keeps the
    donor's first bs-1 slots and takes the new row at slot bs-1."""
    from shallowspeed_tpu.serving.engine import _prefill_chunk

    bs, c = 8, 8
    rng = np.random.default_rng(11)
    pools = [_filled(pool, rng)
             for pool in init_block_pool(CFG, 12, bs, kv_quant=kv_quant)]
    before = [{n: np.asarray(l).copy() for n, l in p.items()}
              for p in pools]
    prefix, tail, own = 3, 6, 9            # shared, shared, this request's
    bt = jnp.asarray([[prefix, own, 0, 0]], jnp.int32)
    tokens = jnp.asarray(toks(3, t=c)[None, :])
    _, after, _ = _prefill_chunk(params, pools, tokens,
                                 jnp.int32(2 * bs - 1), jnp.int32(1), bt,
                                 jnp.int32(tail), jnp.int32(own), cfg=CFG)
    for b, a in zip(before, after):
        for n in b:
            got = np.asarray(a[n])
            untouched = [i for i in range(1, 12) if i != own]
            np.testing.assert_array_equal(got[untouched], b[n][untouched],
                                          err_msg=f"{n}: a block other "
                                          f"than the copy changed")
            np.testing.assert_array_equal(got[own][:, :bs - 1],
                                          b[n][tail][:, :bs - 1])
            assert not np.array_equal(got[own][:, bs - 1],
                                      b[n][tail][:, bs - 1]), n


def test_paged_read_bytes_per_tick_model(params):
    """The live-blocks HBM model: params once + touched blocks' K/V
    (+ int8 scales) + token ids — the serving generalization of
    `decode_read_bytes_per_token`'s full-cache sweep."""
    from shallowspeed_tpu.analysis.walker import aval_bytes

    cast = jax.eval_shape(
        lambda p: T.cast_params(p, CFG.compute_dtype), params)
    p_bytes = int(sum(aval_bytes(l)
                      for l in jax.tree_util.tree_leaves(cast)))
    bs, touched, rows = 16, 5, 4
    got = paged_read_bytes_per_tick(params, CFG, touched, bs, rows)
    per_block = 2 * CFG.kv_heads * bs * CFG.head_dim * 4  # f32 cache
    assert got == p_bytes + CFG.n_layers * touched * per_block + rows * 4
    q = paged_read_bytes_per_tick(params, CFG, touched, bs, rows,
                                  kv_quant="int8")
    per_block_q = (2 * CFG.kv_heads * bs * CFG.head_dim
                   + 2 * CFG.kv_heads * bs * 4)
    assert q == p_bytes + CFG.n_layers * touched * per_block_q + rows * 4
    assert q < got                      # int8 sweeps fewer bytes


# ------------------------------------ prefix caching (round 19)


def test_block_allocator_double_free_rejected():
    """Satellite: duplicate ids inside ONE free() call used to slip
    through the membership check (each id individually "allocated"),
    corrupting the free list. Now the per-call multiplicity is
    validated against the refcount BEFORE anything mutates."""
    a = BlockAllocator(8)
    got = a.alloc(2)
    with pytest.raises(ValueError, match="over-released"):
        a.free([got[0], got[0]])
    # atomic: the failed call mutated nothing — both ids still live
    assert a.n_allocated == 2 and a.n_free == 5
    a.free(got)
    assert a.n_free == a.n_usable
    # same rule across calls: a second release past refcount 0 raises
    b = a.alloc(1)
    a.free(b)
    with pytest.raises(ValueError):
        a.free(b)


def test_refcount_cold_lru_reclaim_order():
    """Refcounted sharing + the cold list: released-but-indexed
    blocks park on an LRU cold list (oldest reclaimed first, index
    entry dropped at reclaim), acquire() revives them, and a block
    with a live reference is NEVER reclaimed — the pool exhausts with
    OutOfBlocks instead."""
    from shallowspeed_tpu.serving.cache import PrefixIndex

    idx = PrefixIndex(block_size=4)
    a = BlockAllocator(8, index=idx)          # 7 usable
    tokens = np.arange(12, dtype=np.int32)    # 3 aligned blocks
    got = a.alloc(3)
    assert idx.insert(tokens, got) == 3
    a.release(got)          # refcount 0 + indexed -> cold, in order
    assert a.n_cold == 3 and a.n_free == 4 and a.n_live == 0
    assert a.n_free + a.n_live + a.n_cold == a.n_usable
    # a cache hit revives the chain from cold
    assert idx.match(tokens) == got
    a.acquire([got[1]])
    assert a.n_cold == 2 and a.n_live == 1 and a.refcount(got[1]) == 1
    # drain the free list, then force reclaims: OLDEST cold first,
    # and its index entry vanishes with it
    a.alloc(4)
    assert a.alloc(1) == [got[0]] and not idx.has_block(got[0])
    assert a.cold_reclaims == 1
    assert a.alloc(1) == [got[2]]
    assert a.n_cold == 0
    with pytest.raises(OutOfBlocks):
        a.alloc(1)          # got[1] is referenced — never reclaimed
    assert a.refcount(got[1]) == 1
    # releasing more references than held is rejected atomically
    with pytest.raises(ValueError):
        a.release([got[1], got[1]])
    assert a.refcount(got[1]) == 1


def test_prefix_cache_parity_tail_only_and_records(params):
    """The parity gate: cache-hit streams are token-identical to the
    oracle at temperature 0 AND under seeded sampling; a fully-shared
    block-aligned prompt re-prefills only the copied tail block (one
    chunk with prefill_chunk == block_size); request records carry the
    v14 prefix_hit_blocks / prefill_skipped_tokens fields."""
    shared = toks(90, t=32)                   # 4 aligned blocks of 8
    eng = ServingEngine(params, CFG, n_blocks=32, block_size=8,
                        max_slots=4, prefill_chunk=8, prefix_cache=True)
    ref = solo(params, shared, 6, temperature=0.0)
    eng.submit(shared, 6, rid="cold")
    np.testing.assert_array_equal(eng.run()["cold"], ref)
    cold_chunks = eng.counters["prefill_chunks"]
    assert cold_chunks == 4
    # full-aligned hit under seeded sampling: CoW tail, 1 chunk only
    ref2 = solo(params, shared, 6, temperature=0.8, seed=5)
    eng.submit(shared, 6, temperature=0.8, seed=5, rid="hit")
    np.testing.assert_array_equal(eng.run()["hit"], ref2)
    assert eng.counters["prefill_chunks"] - cold_chunks == 1
    rec = next(r for r in eng.request_records if r["id"] == "hit")
    assert rec["prefix_hit_blocks"] == 4
    assert rec["prefill_skipped_tokens"] == 31    # all but the CoW tok
    # divergent tail: leading 3 blocks hit, the rest prefills fresh
    ext = np.concatenate([shared[:24], toks(91, t=10)])
    ref3 = solo(params, ext, 6, temperature=0.0)
    eng.submit(ext, 6, rid="ext")
    np.testing.assert_array_equal(eng.run()["ext"], ref3)
    rec = next(r for r in eng.request_records if r["id"] == "ext")
    assert rec["prefix_hit_blocks"] == 3
    assert rec["prefill_skipped_tokens"] == 24
    # drain invariant, extended: live zero, free + cold == usable
    assert eng.alloc.n_live == 0
    assert eng.alloc.n_free + eng.alloc.n_cold == eng.alloc.n_usable


def test_prefix_cache_mid_run_join_parity(params):
    """A sharer that joins MID-RUN (while the donor is still
    decoding) must stream the oracle whether it misses (donor not
    finished -> nothing donated yet) or hits a prefix some earlier
    request already sealed."""
    shared = toks(92, t=24)
    refs = {"a": solo(params, shared, 8, temperature=0.0),
            "b": solo(params, shared, 8, temperature=0.9, seed=3),
            "c": solo(params, shared, 8, temperature=0.0)}
    eng = ServingEngine(params, CFG, n_blocks=32, block_size=8,
                        max_slots=4, prefill_chunk=8, prefix_cache=True)
    eng.submit(shared, 8, rid="a")
    for _ in range(2):                       # a is mid-prefill/decode
        eng.step()
    eng.submit(shared, 8, temperature=0.9, seed=3, rid="b")
    while eng.poll("a")["status"] != "done":
        eng.step()
    eng.submit(shared, 8, rid="c")           # after donation: a hit
    res = eng.run()
    for k, ref in refs.items():
        np.testing.assert_array_equal(res[k], ref, err_msg=k)
    assert eng.counters["prefix_hits"] >= 1  # c at minimum


def test_prefix_cache_cow_leaves_shared_block_bit_unchanged(params):
    """Copy-on-write at the tail: a second request over the SAME
    fully-aligned prompt copies the tail block and rewrites its own
    last token in the copy — every byte of the donor's indexed blocks
    (the shared tail included) is bit-identical afterwards."""
    shared = toks(93, t=16)                  # 2 aligned blocks
    eng = ServingEngine(params, CFG, n_blocks=32, block_size=8,
                        max_slots=4, prefill_chunk=8, prefix_cache=True)
    eng.submit(shared, 4, rid="a")
    eng.run()
    matched = eng.prefix.match(shared)
    assert len(matched) == 2
    sel = np.asarray(matched, np.int32)
    snap = [{n: np.asarray(leaf[sel]).copy()
             for n, leaf in pool.items()} for pool in eng.pools]
    ref = solo(params, shared, 4, temperature=0.0)
    eng.submit(shared, 4, rid="b")
    np.testing.assert_array_equal(eng.run()["b"], ref)
    for pool, before in zip(eng.pools, snap):
        for n, leaf in pool.items():
            np.testing.assert_array_equal(
                np.asarray(leaf[sel]), before[n],
                err_msg=f"{n}: CoW consumer mutated a shared block")


def test_prefix_cache_oom_evict_requeue_shared(params):
    """Preemption under sharing: a pool too small for the concurrent
    set forces evictions mid-flight; evicted requests drop their
    references, re-probe the index on re-admission, and every stream
    still matches its solo oracle. The allocator balances at drain
    under the extended invariant."""
    shared = toks(94, t=16)

    def mk(i):
        return np.concatenate([shared, toks(100 + i, t=6)])

    oracle = {f"r{i}": solo(params, mk(i), 12, temperature=0.0)
              for i in range(3)}
    # 9 usable blocks * 8 = 72 positions < 3 * blocks_for(33) * 8
    eng = ServingEngine(params, CFG, n_blocks=10, block_size=8,
                        max_slots=4, prefill_chunk=8, prefix_cache=True)
    for i in range(3):
        eng.submit(mk(i), 12, rid=f"r{i}")
    res = eng.run()
    for k, ref in oracle.items():
        np.testing.assert_array_equal(res[k], ref, err_msg=k)
    assert eng.counters["preempted"] >= 1, "pool never pressured"
    assert eng.alloc.n_live == 0
    assert eng.alloc.n_free + eng.alloc.n_cold == eng.alloc.n_usable


def test_prefix_cache_zero_new_executables(params):
    """The hit path (prefix map-in + CoW copy + short tail prefill)
    is DATA through programs that already executed cold: after a
    prefix-OFF warmup over the same shapes, serving hits with the
    cache on compiles nothing new."""
    eng = ServingEngine(params, CFG, n_blocks=32, block_size=8,
                        max_slots=4, prefill_chunk=8)
    eng.submit(toks(95, t=16), 6, rid="w")
    eng.run()
    warm = eng.executable_counts()
    eng2 = ServingEngine(params, CFG, n_blocks=32, block_size=8,
                         max_slots=4, prefill_chunk=8,
                         prefix_cache=True)
    eng2.submit(toks(95, t=16), 6, rid="a")
    eng2.run()
    eng2.submit(toks(95, t=16), 6, rid="b")   # full-aligned CoW hit
    eng2.run()
    assert eng2.counters["prefix_hits"] == 1
    assert eng2.executable_counts() == warm, (
        f"prefix caching recompiled: {warm} -> "
        f"{eng2.executable_counts()}")


def test_prefix_telemetry_schema_v14_and_status_surface(params,
                                                        tmp_path):
    """Prefix-cache telemetry rides the monitor plane: request lines
    carry prefix_hit_blocks / prefill_skipped_tokens, generate lines
    the windowed prefix_hit_rate + cold/indexed gauges (all schema-
    v14-valid), and the monitor surfaces them in /status.json's
    serving block and /metrics."""
    from shallowspeed_tpu.metrics import MetricsLogger
    from shallowspeed_tpu.telemetry import schema
    from shallowspeed_tpu.telemetry.monitor import Monitor

    assert schema.SCHEMA_VERSION >= 14
    path = tmp_path / "prefix.jsonl"
    eng = ServingEngine(params, CFG, n_blocks=32, block_size=8,
                        max_slots=4, prefill_chunk=8,
                        prefix_cache=True,
                        metrics=MetricsLogger(path, kind="serve"),
                        log_every=2)
    shared = toks(96, t=16)
    eng.submit(shared, 6, rid="a")
    eng.run()
    eng.submit(shared, 6, rid="b")
    eng.run()
    assert schema.validate_file(path) == []
    recs = [json.loads(l) for l in path.read_text().splitlines()]
    hit = next(r for r in recs if r.get("event") == "request"
               and r.get("id") == "b")
    assert hit["prefix_hit_blocks"] == 2
    assert hit["prefill_skipped_tokens"] == 15
    gens = [r for r in recs if r.get("event") == "generate"]
    assert gens and all("prefix_hit_rate" in g and "cold_blocks" in g
                        and "prefix_blocks" in g for g in gens)
    # the prefill_cached lifecycle phase stamps the hit at admission
    lcs = [r for r in recs if r.get("event") == "lifecycle"
           and r.get("phase") == "prefill_cached"]
    assert lcs and lcs[0]["blocks"] == 2 and lcs[0]["tokens"] == 15
    mon = Monitor()
    for r in recs:
        mon.note_line(r)
    srv = mon.status()["serving"]
    assert "prefix_hit_rate" in srv and "cold_blocks" in srv
    prom = mon.prometheus()
    assert "prefix_hit_rate" in prom and "prefix_blocks" in prom
    # malformed prefix fields are rejected
    assert schema.validate_line(
        {"event": "request", "id": "x", "ttft_ms": 1.0, "tokens_in": 1,
         "tokens_out": 1, "prefix_hit_blocks": "many"}) != []
    assert schema.validate_line(
        {"event": "generate", "tokens_per_sec": 1.0,
         "prefix_hit_rate": "high"}) != []
