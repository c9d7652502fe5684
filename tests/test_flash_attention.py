"""Flash-attention kernel correctness vs the reference `ops.attention`:
forward and full custom-VJP backward, causal and bidirectional, over
uneven block/sequence combinations. Runs the actual Pallas kernels in
interpret mode on CPU — the same code path Mosaic compiles on TPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shallowspeed_tpu.ops.attention import attention
from shallowspeed_tpu.ops.flash_attention import flash_attention


def qkv(b=2, t=128, h=2, d=32, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(rng.normal(size=(b, t, h, d)).astype(np.float32)
                 for _ in range(3))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("t,bq,bk", [(128, 64, 64), (128, 128, 32),
                                     (96, 32, 32)])
def test_forward_matches_reference(causal, t, bq, bk):
    q, k, v = qkv(t=t)
    want = np.asarray(attention(q, k, v, causal=causal))
    got = np.asarray(flash_attention(q, k, v, causal, block_q=bq,
                                     block_k=bk, interpret=True))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_gradients_match_reference(causal):
    q, k, v = qkv(t=64, d=16)

    def ref_loss(q, k, v):
        return (attention(q, k, v, causal=causal) ** 2).sum()

    def flash_loss(q, k, v):
        return (flash_attention(q, k, v, causal, block_q=32, block_k=32,
                                interpret=True) ** 2).sum()

    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_ref, g_fl):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-4, atol=5e-5, err_msg=f"d{name}")


def test_block_autoshrink_odd_sequence():
    """T=40 not divisible by 128: blocks shrink to a divisor automatically."""
    q, k, v = qkv(t=40, d=16)
    want = np.asarray(attention(q, k, v, causal=True))
    got = np.asarray(flash_attention(q, k, v, True, interpret=True))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_transformer_with_flash_attention():
    """The LM family runs end-to-end with the kernel as its attn_fn."""
    from functools import partial

    from shallowspeed_tpu.models import transformer as T

    cfg = T.TransformerConfig(vocab=32, d_model=32, n_heads=2, n_layers=1,
                              max_seq=64)
    params = T.init(cfg, seed=0)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 32, (2, 64)).astype(np.int32)
    targets = np.roll(tokens, -1, axis=1).astype(np.int32)

    attn = partial(flash_attention, causal=True, block_q=32, block_k=32,
                   interpret=True)
    l_flash, g_flash = jax.value_and_grad(
        lambda p: T.loss(p, tokens, targets, cfg, attn_fn=attn))(params)
    l_ref, g_ref = jax.value_and_grad(
        lambda p: T.loss(p, tokens, targets, cfg))(params)
    assert abs(float(l_flash) - float(l_ref)) < 1e-5
    for a, b in zip(jax.tree_util.tree_leaves(g_ref),
                    jax.tree_util.tree_leaves(g_flash)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-4, atol=1e-5)


@pytest.mark.parametrize("window", [1, 16, 48, 64, 1000])
@pytest.mark.parametrize("causal", [True, False])
def test_window_matches_reference(window, causal):
    """Sliding-window flash == masked `ops.attention` (the semantics
    oracle, `attention(..., window=w)`), fwd and VJP, including tile
    boundary cases (window smaller / larger than a block; window 1 =
    self-only; window >= T = no-op)."""
    q, k, v = qkv(t=64, d=16)
    want = np.asarray(attention(q, k, v, causal=causal, window=window))
    got = np.asarray(flash_attention(q, k, v, causal, window,
                                     block_q=16, block_k=16,
                                     interpret=True))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    g_ref = jax.grad(lambda *a: (
        attention(*a, causal=causal, window=window) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(lambda *a: (
        flash_attention(*a, causal, window, block_q=16, block_k=16,
                        interpret=True) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_ref, g_fl):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-4, atol=5e-5, err_msg=f"d{name}")


def test_window_streaming_matches_resident(monkeypatch):
    """The streaming (3-D grid) kernels honor windows identically."""
    import shallowspeed_tpu.ops.flash_attention as fa

    q, k, v = qkv(t=128, d=16)
    want = np.asarray(attention(q, k, v, causal=True, window=40))
    monkeypatch.setattr(fa, "_RESIDENT_BYTES", 0)
    got = np.asarray(fa.flash_attention(q, k, v, True, 40, block_q=32,
                                        block_k=32, interpret=True))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    g_ref = jax.grad(lambda *a: (
        attention(*a, causal=True, window=40) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(lambda *a: (
        fa.flash_attention(*a, True, 40, block_q=32, block_k=32,
                           interpret=True) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_ref, g_fl):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-4, atol=5e-5, err_msg=f"d{name}")


@pytest.mark.parametrize("kvh,window", [(1, 0), (2, 0), (2, 24), (4, 0)])
def test_gqa_native_matches_repeated(kvh, window):
    """GQA q-row group folding == attention over jnp.repeat'ed K/V: the
    kernel must produce identical outputs AND identical (k, v) grads —
    the repeated formulation's dk/dv sum over group members."""
    h = 4
    rng = np.random.default_rng(3)
    q = rng.normal(size=(2, 64, h, 16)).astype(np.float32)
    k = rng.normal(size=(2, 64, kvh, 16)).astype(np.float32)
    v = rng.normal(size=(2, 64, kvh, 16)).astype(np.float32)
    g = h // kvh
    k_rep = np.repeat(k, g, axis=2)
    v_rep = np.repeat(v, g, axis=2)

    want = np.asarray(attention(q, k_rep, v_rep, causal=True,
                                window=window))
    got = np.asarray(flash_attention(q, k, v, True, window, block_q=16,
                                     block_k=16, interpret=True))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)

    g_ref = jax.grad(lambda q, k, v: (attention(
        q, jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2),
        causal=True, window=window) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(lambda q, k, v: (flash_attention(
        q, k, v, True, window, block_q=16, block_k=16,
        interpret=True) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_ref, g_fl):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=5e-4, atol=5e-5, err_msg=f"d{name}")


def test_gqa_streaming_matches_resident(monkeypatch):
    """GQA group folding on the streaming (3-D grid) kernels too."""
    import shallowspeed_tpu.ops.flash_attention as fa

    rng = np.random.default_rng(5)
    q = rng.normal(size=(1, 128, 4, 16)).astype(np.float32)
    k = rng.normal(size=(1, 128, 2, 16)).astype(np.float32)
    v = rng.normal(size=(1, 128, 2, 16)).astype(np.float32)
    want = np.asarray(fa.flash_attention(q, k, v, causal=True,
                                         interpret=True))
    monkeypatch.setattr(fa, "_RESIDENT_BYTES", 0)
    got = np.asarray(fa.flash_attention(q, k, v, causal=True,
                                        interpret=True))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    def loss(fn):
        return lambda *a: (fn(*a, True) ** 2).sum()

    g_stream = jax.grad(loss(fa.flash_attention),
                        argnums=(0, 1, 2))(q, k, v)
    monkeypatch.undo()
    g_res = jax.grad(loss(fa.flash_attention), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_stream, g_res):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_streaming_fwd_matches_resident(monkeypatch):
    """Force the streaming (3-D grid + scratch) forward and check it equals
    the resident fast path — the CPU suite's small shapes otherwise only
    exercise the resident branch."""
    import shallowspeed_tpu.ops.flash_attention as fa

    rng = np.random.default_rng(11)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 256, 2, 16)), jnp.float32)
               for _ in range(3))
    want = np.asarray(fa.flash_attention(q, k, v, causal=True))
    monkeypatch.setattr(fa, "_RESIDENT_BYTES", 0)
    got = np.asarray(fa.flash_attention(q, k, v, causal=True))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    def loss(fn):
        return lambda *a: (fn(*a, True) ** 2).sum()

    g_stream = jax.grad(loss(fa.flash_attention), argnums=(0, 1, 2))(q, k, v)
    monkeypatch.undo()
    g_res = jax.grad(loss(fa.flash_attention), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_stream, g_res):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


# ------------------------------------------------------------ ring flash


def _shmap_ring(fn, sp, axis="sp"):
    from functools import partial

    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:sp]).reshape(sp), (axis,))
    return jax.jit(partial(
        shard_map(lambda q, k, v: fn(q, k, v),
                  mesh=mesh, in_specs=(P(None, axis), P(None, axis),
                                       P(None, axis)),
                  out_specs=P(None, axis))))


@pytest.mark.parametrize("sp", [1, 2, 4])
@pytest.mark.parametrize("kvh,window", [(4, 0), (2, 0), (2, 24)])
def test_ring_flash_matches_oracle(sp, kvh, window):
    """ring_flash_attention over a sequence-sharded axis == full
    `attention` on the gathered sequence — fwd AND grads (the
    hand-written ring VJP with traveling dk/dv accumulators), across
    sp widths, GQA group factors, and sliding windows."""
    from shallowspeed_tpu.ops.flash_attention import ring_flash_attention

    h, t, d = 4, 64, 16
    rng = np.random.default_rng(7)
    q = rng.normal(size=(2, t, h, d)).astype(np.float32)
    k = rng.normal(size=(2, t, kvh, d)).astype(np.float32)
    v = rng.normal(size=(2, t, kvh, d)).astype(np.float32)
    g = h // kvh
    want = np.asarray(attention(q, np.repeat(k, g, axis=2),
                                np.repeat(v, g, axis=2), causal=True,
                                window=window))

    ring = _shmap_ring(
        lambda a, b_, c: ring_flash_attention(a, b_, c, "sp", True,
                                              window), sp)
    got = np.asarray(ring(q, k, v))
    np.testing.assert_allclose(got, want, rtol=3e-5, atol=3e-5)

    # grads: ring VJP vs autodiff through the repeated-KV oracle
    def ref_loss(q, k, v):
        return (attention(q, jnp.repeat(k, g, axis=2),
                          jnp.repeat(v, g, axis=2), causal=True,
                          window=window) ** 2).sum()

    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)

    ring_grad = _shmap_ring(
        lambda a, b_, c: jax.grad(
            lambda x, y, z: (ring_flash_attention(
                x, y, z, "sp", True, window) ** 2).sum(),
            argnums=(0, 1, 2))(a, b_, c), sp)

    # out_specs for grads: a 3-tuple sharded like the inputs
    from functools import partial

    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map

    mesh = Mesh(np.array(jax.devices()[:sp]).reshape(sp), ("sp",))
    spec = P(None, "sp")
    # differentiate each device's LOCAL partial of the loss (no psum in
    # the differentiated function): run SPMD, every device seeds its own
    # partial with 1 and the ring VJP's reverse hops deliver the
    # cross-device cotangents, so the per-device grad outputs ARE the
    # global-loss grads.
    ring_grad = jax.jit(partial(shard_map(
        lambda a, b_, c: jax.grad(
            lambda x, y, z: (ring_flash_attention(
                x, y, z, "sp", True, window) ** 2).sum(),
            argnums=(0, 1, 2))(a, b_, c),
        mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=(spec, spec, spec))))
    g_got = ring_grad(q, k, v)
    for name, a, b_ in zip("qkv", g_ref, g_got):
        np.testing.assert_allclose(np.asarray(b_), np.asarray(a),
                                   rtol=1e-3, atol=1e-4,
                                   err_msg=f"d{name} sp={sp}")


def test_ring_flash_noncausal():
    from shallowspeed_tpu.ops.flash_attention import ring_flash_attention

    rng = np.random.default_rng(9)
    q, k, v = (rng.normal(size=(1, 32, 2, 8)).astype(np.float32)
               for _ in range(3))
    want = np.asarray(attention(q, k, v, causal=False))
    ring = _shmap_ring(
        lambda a, b_, c: ring_flash_attention(a, b_, c, "sp", False), 4)
    np.testing.assert_allclose(np.asarray(ring(q, k, v)), want,
                               rtol=3e-5, atol=3e-5)


def test_ring_flash_streaming_chunks(monkeypatch):
    """Force the streaming (3-D grid) chunk kernels inside the ring and
    check fwd + grads against the oracle — long-context rings stream."""
    import shallowspeed_tpu.ops.flash_attention as fa

    monkeypatch.setattr(fa, "_RESIDENT_BYTES", 0)
    rng = np.random.default_rng(13)
    q, k, v = (rng.normal(size=(1, 64, 2, 16)).astype(np.float32)
               for _ in range(3))
    want = np.asarray(attention(q, k, v, causal=True))
    ring = _shmap_ring(
        lambda a, b_, c: fa.ring_flash_attention(a, b_, c, "sp", True), 4)
    np.testing.assert_allclose(np.asarray(ring(q, k, v)), want,
                               rtol=3e-5, atol=3e-5)

    from functools import partial

    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map

    g_ref = jax.grad(lambda *a: (attention(*a, causal=True) ** 2).sum(),
                     argnums=(0, 1, 2))(q, k, v)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(4), ("sp",))
    spec = P(None, "sp")
    # grad of the LOCAL loss partial — see test_ring_flash_matches_oracle
    # for why the differentiated function must not contain the psum
    ring_grad = jax.jit(partial(shard_map(
        lambda a, b_, c: jax.grad(
            lambda x, y, z: (fa.ring_flash_attention(
                x, y, z, "sp", True) ** 2).sum(),
            argnums=(0, 1, 2))(a, b_, c),
        mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=(spec, spec, spec))))
    for name, a, b_ in zip("qkv", g_ref, ring_grad(q, k, v)):
        np.testing.assert_allclose(np.asarray(b_), np.asarray(a),
                                   rtol=1e-3, atol=1e-4,
                                   err_msg=f"d{name}")


# ------------------------------------------------ ring-chunk envelope


def _ring_pair_err(out_dtype):
    """Relative error of the two-chunk ring composition (`_chunk_fwd` +
    `_merge_chunks`, second-half queries over an earlier block at
    rel=t/2 and the own block at rel=0 — exactly what
    `ring_flash_attention` composes) against the f32 XLA oracle, at
    bf16 inputs with the given chunk-output dtype. Returns
    (flash_err, xla_bf16_floor)."""
    import shallowspeed_tpu.ops.flash_attention as fa

    rng = np.random.default_rng(7)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 256, 4, 32)) * 0.5,
                           jnp.bfloat16) for _ in range(3))
    t2 = 128
    qh = q[:, t2:]
    (_, _, _, _, kvh, _, bq, bk, nqb) = fa._ring_geometry(qh, k[:, :t2])
    kw = dict(causal=True, window=0, bq=bq, bk=bk, nqb_chunk=nqb,
              interpret=True, out_dtype=out_dtype)
    q3 = fa._fold_q(qh, kvh)
    o0, l0 = fa._chunk_fwd(q3, fa._to_bhsd(k[:, :t2]),
                           fa._to_bhsd(v[:, :t2]), t2, **kw)
    o1, l1 = fa._chunk_fwd(q3, fa._to_bhsd(k[:, t2:]),
                           fa._to_bhsd(v[:, t2:]), 0, **kw)
    o, _ = fa._merge_chunks(o0.astype(jnp.float32), l0, o1, l1)
    got = fa._unfold_q(o.astype(q3.dtype), 2, 4)

    def rel_err(a, b):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        return float(np.abs(a - b).max()) / max(1e-6,
                                                float(np.abs(b).max()))

    f32 = jnp.float32
    oracle = attention(q.astype(f32), k.astype(f32), v.astype(f32),
                       causal=True)[:, t2:]
    floor = rel_err(attention(q, k, v, causal=True)[:, t2:], oracle)
    return rel_err(got, oracle), floor


def test_ring_chunk_numerics_envelope():
    """Pin the ring-chunk merge's numerics envelope (VERDICT r5 weak
    #2): with the f32 chunk carry the two-chunk composition must sit
    AT the XLA-bf16 rounding floor (<= 1.25x, headroom for interpret-
    vs-Mosaic drift), where the old bf16 chunk output measured 2.3x
    above it on-chip (BENCH_r05). The bf16-chunk variant is measured
    alongside to prove the carry — not some unrelated drift — is what
    closes the gap. BASELINE.md 'ring-chunk numerics envelope'
    documents the mechanism; `chip_smoke.py`'s kernels phase holds the
    compiled kernels to the same kind of bound on the chip."""
    err_f32, floor = _ring_pair_err(jnp.float32)
    err_bf16, _ = _ring_pair_err(None)  # old behavior: chunk o in bf16
    assert err_f32 <= 1.25 * floor, (
        f"f32-carry ring chunk error {err_f32} above the bf16 floor "
        f"{floor} — the merge lost its f32 carry")
    assert err_f32 < err_bf16, (
        f"f32 carry ({err_f32}) should beat the bf16 chunk output "
        f"({err_bf16}) — the envelope mechanism changed")


# ------------------------------------------------ paged flash decode


def _random_pool(rng, n, hkv, bs, hd, kind):
    """A pool of `kind` filled with random values EVERYWHERE, the
    scratch block and the blocks no table names included: what a row
    does not own it must not see."""
    f = lambda *sh: jnp.asarray(rng.normal(size=sh), jnp.float32)
    if kind == "latent":
        return {"ckr": f(n, 1, bs, hd)}
    if kind == "int8":
        i8 = lambda: jnp.asarray(
            rng.integers(-127, 128, (n, hkv, bs, hd)), jnp.int8)
        sc = lambda: jnp.asarray(
            rng.uniform(0.01, 0.03, (n, hkv, bs, 1)), jnp.float32)
        return {"k": i8(), "k_s": sc(), "v": i8(), "v_s": sc()}
    return {"k": f(n, hkv, bs, hd), "v": f(n, hkv, bs, hd)}


@pytest.mark.parametrize("chunk", [1, 2, None],
                         ids=["step1", "step2", "step-default"])
@pytest.mark.parametrize("heads,kind,window", [
    ((4, 4, 8), "kv", 0),         # MHA, full-precision pools
    ((4, 2, 8), "kv", 0),         # GQA
    ((4, 4, 8), "int8", 0),       # int8 pools + f32 scale planes
    ((4, 2, 8), "int8", 0),       # GQA + int8
    ((4, 4, 8), "kv", 6),         # a window that binds inside a block
    ((4, 2, 8), "int8", 13),      # everything at once
    ((4, 1, 40), "latent", 0),    # one shared head, K is V, rows of
    ((4, 1, 40), "latent", 11),   # 32 + 8 values: the latent pool
], ids=["mha", "gqa", "int8", "gqa-int8", "window", "gqa-int8-window",
        "latent", "latent-window"])
def test_paged_flash_decode_matches_gather_reference(heads, kind, window,
                                                     chunk, request):
    """THE fast-decode kernel pin: `paged_flash_decode` (one program
    walks each row's live blocks through the table, manual DMA from the
    pool, online softmax across a row's steps, int8 KV + scales read
    natively, a one-leaf pool read as keys and values both) matches the
    XLA reference — `serving/cache.gather_table` + `masked_attention`
    (tests/conftest.py:gathered_read) — to <= 1e-4 in interpret mode,
    for ragged rows in ONE call: an all-scratch row at position 0, a
    row that ends mid-block, one on a block's last slot, one on its
    first, one at the table's full width; with steps of one block, of
    two (whole and partial steps, a next row prefetched after a last
    step) and the default. `gather_table` deliberately stays in the
    tree as this reference and as the prefill chunk's read."""
    from conftest import gathered_read
    from shallowspeed_tpu.ops.flash_attention import paged_flash_decode

    h, hkv, hd = heads
    rng = np.random.default_rng(len(request.node.name))
    bs, n, w = 8, 32, 5
    pool = _random_pool(rng, n, hkv, bs, hd, kind)
    pos = np.asarray([0, 13, 23, 32, bs * w - 1], np.int32)
    bt = rng.permutation(np.arange(1, n))[:len(pos) * w].reshape(-1, w)
    bt = np.where(np.arange(w)[None] <= pos[:, None] // bs, bt, 0)
    bt[0] = 0                                # a dead slot: all scratch
    bt, pos = jnp.asarray(bt, jnp.int32), jnp.asarray(pos)
    q = jnp.asarray(rng.normal(size=(len(pos), h, hd)), jnp.float32)
    scale = 0.3 if kind == "latent" else None
    got = paged_flash_decode(q, pool, bt, pos, window=window, scale=scale,
                             chunk=chunk)
    ref = gathered_read(q, pool, bt, pos, window=window, scale=scale)
    assert got.shape == ref.shape == (len(pos), h, hd)
    err = float(jnp.abs(got - ref).max())
    assert err / float(jnp.abs(ref).max()) <= 1e-4, err


def test_paged_flash_decode_scratch_rows_are_harmless():
    """Inactive slots (pos=0, table all scratch) run through the
    kernel like any other row — no NaNs, no reads outside block 0 —
    matching the engine's occupancy-is-data contract."""
    from shallowspeed_tpu.models import transformer as T
    from shallowspeed_tpu.ops.flash_attention import paged_flash_decode
    from shallowspeed_tpu.serving.cache import init_block_pool

    cfg = T.TransformerConfig(vocab=64, d_model=32, n_heads=4,
                              n_layers=1, max_seq=64)
    pool = init_block_pool(cfg, 4, 8)[0]
    q = jnp.ones((2, cfg.n_heads, cfg.head_dim), jnp.float32)
    bt = jnp.zeros((2, 2), jnp.int32)        # all scratch
    out = paged_flash_decode(q, pool, bt, jnp.zeros((2,), jnp.int32))
    assert bool(jnp.isfinite(out).all())
