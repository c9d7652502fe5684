"""Ring attention correctness: the sequence-sharded ring must match full
attention (forward AND gradients) on a real multi-device mesh — the test
strategy the reference applies to its parallelism (equivalence against the
serial run, `scripts/DDP_PyTorch_MNIST.py:159-167`) applied to context
parallelism.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from shallowspeed_tpu.ops.attention import (attention, ring_attention,
                                            ulysses_attention)

B, T, H, D = 2, 32, 4, 16


def naive_attention(q, k, v, causal):
    """O(T^2) numpy reference, independent of the jnp implementation."""
    b, t, h, d = q.shape
    out = np.zeros_like(q, dtype=np.float64)
    for bi in range(b):
        for hi in range(h):
            s = (q[bi, :, hi].astype(np.float64)
                 @ k[bi, :, hi].astype(np.float64).T) / np.sqrt(d)
            if causal:
                s = np.where(np.tril(np.ones((t, t), bool)), s, -np.inf)
            p = np.exp(s - s.max(axis=-1, keepdims=True))
            p /= p.sum(axis=-1, keepdims=True)
            out[bi, :, hi] = p @ v[bi, :, hi].astype(np.float64)
    return out.astype(q.dtype)


@pytest.fixture
def qkv():
    rng = np.random.default_rng(7)
    mk = lambda: rng.normal(size=(B, T, H, D)).astype(np.float32)
    return mk(), mk(), mk()


def ring_on_mesh(q, k, v, sp, causal):
    mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))
    spec = P(None, "sp")
    fn = shard_map(
        partial(ring_attention, axis_name="sp", causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return np.asarray(jax.jit(fn)(q, k, v))


@pytest.mark.parametrize("causal", [True, False])
def test_full_attention_matches_naive(qkv, causal):
    q, k, v = qkv
    got = np.asarray(attention(q, k, v, causal=causal))
    np.testing.assert_allclose(got, naive_attention(q, k, v, causal),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("sp", [1, 2, 4, 8])
@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_full(qkv, sp, causal):
    q, k, v = qkv
    want = np.asarray(attention(q, k, v, causal=causal))
    got = ring_on_mesh(q, k, v, sp, causal)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_ring_gradients_match_full(qkv):
    """jax.grad straight through the ring (scan + ppermute) must equal the
    full-attention gradient — the property context-parallel training rests on."""
    q, k, v = qkv
    sp = 4
    mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))
    spec = P(None, "sp")

    def full_loss(q, k, v):
        return (attention(q, k, v, causal=True) ** 2).sum()

    @partial(shard_map, mesh=mesh, in_specs=(spec, spec, spec),
             out_specs=P())
    def ring_loss(q, k, v):
        o = ring_attention(q, k, v, axis_name="sp", causal=True)
        return jax.lax.psum((o.astype(jnp.float32) ** 2).sum(), "sp")

    g_full = jax.grad(full_loss, argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    for gf, gr in zip(g_full, g_ring):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gf),
                                   rtol=5e-4, atol=5e-5)


def ulysses_on_mesh(q, k, v, sp, causal, use_flash=False):
    mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))
    spec = P(None, "sp")
    fn = shard_map(
        partial(ulysses_attention, axis_name="sp", causal=causal,
                use_flash=use_flash),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return np.asarray(jax.jit(fn)(q, k, v))


@pytest.mark.parametrize("sp", [1, 2, 4])
@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_matches_full(qkv, sp, causal):
    """All-to-all sequence parallelism must equal full attention (H=4, so
    sp in {1,2,4} covers heads-per-device in {4,2,1})."""
    q, k, v = qkv
    want = np.asarray(attention(q, k, v, causal=causal))
    got = ulysses_on_mesh(q, k, v, sp, causal)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_ulysses_gradients_match_full(qkv):
    """jax.grad straight through the two all-to-alls must equal the
    full-attention gradient."""
    q, k, v = qkv
    sp = 4
    mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))
    spec = P(None, "sp")

    def full_loss(q, k, v):
        return (attention(q, k, v, causal=True) ** 2).sum()

    @partial(shard_map, mesh=mesh, in_specs=(spec, spec, spec),
             out_specs=P())
    def uly_loss(q, k, v):
        o = ulysses_attention(q, k, v, axis_name="sp", causal=True)
        return jax.lax.psum((o.astype(jnp.float32) ** 2).sum(), "sp")

    g_full = jax.grad(full_loss, argnums=(0, 1, 2))(q, k, v)
    g_uly = jax.jit(jax.grad(uly_loss, argnums=(0, 1, 2)))(q, k, v)
    for gf, gu in zip(g_full, g_uly):
        np.testing.assert_allclose(np.asarray(gu), np.asarray(gf),
                                   rtol=5e-4, atol=5e-5)


def test_ulysses_rejects_indivisible_heads(qkv):
    """H=4 over sp=8 cannot shard heads; the op must refuse loudly."""
    q, k, v = qkv
    with pytest.raises(Exception, match="divisible"):
        ulysses_on_mesh(q, k, v, sp=8, causal=True)


def test_ring_long_sequence_small_blocks():
    """Long-context shape: T >> block size; every device holds T/sp tokens."""
    rng = np.random.default_rng(3)
    t = 256
    q, k, v = (rng.normal(size=(1, t, 2, 8)).astype(np.float32)
               for _ in range(3))
    want = np.asarray(attention(q, k, v, causal=True))
    got = ring_on_mesh(q, k, v, sp=8, causal=True)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("sp", [1, 2, 4])
def test_ulysses_flash_matches_full(qkv, sp):
    """All-to-all sequence parallelism with the Pallas flash kernel as the
    local attention must equal plain full attention."""
    q, k, v = qkv
    want = np.asarray(attention(q, k, v, causal=True))
    got = ulysses_on_mesh(q, k, v, sp, True, use_flash=True)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_ulysses_flash_gradients_match_full(qkv):
    """The flash kernel's custom VJP composes with the all-to-all
    transposes: gradients must equal the full-attention gradients."""
    q, k, v = qkv
    sp = 4
    mesh = Mesh(np.array(jax.devices()[:sp]), ("sp",))
    spec = P(None, "sp")

    def full_loss(q, k, v):
        return (attention(q, k, v, causal=True) ** 2).sum()

    @partial(shard_map, mesh=mesh, in_specs=(spec, spec, spec),
             out_specs=P())
    def uf_loss(q, k, v):
        o = ulysses_attention(q, k, v, axis_name="sp", causal=True,
                              use_flash=True)
        return jax.lax.psum((o.astype(jnp.float32) ** 2).sum(), "sp")

    want = jax.grad(full_loss, argnums=(0, 1, 2))(q, k, v)
    got = jax.grad(uf_loss, argnums=(0, 1, 2))(q, k, v)
    for w, g in zip(want, got):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=5e-4, atol=5e-5)
