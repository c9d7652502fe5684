"""L1 ops contract tests — the porting contract from the reference.

Ports the reference's finite-difference validation strategy
(`/root/reference/tests/test_functional.py`: central differences with EPS,
shape contracts, softmax shift-invariance, MSE values) and strengthens it:
every hand-written gradient is ALSO cross-checked against `jax.vjp` of the
forward function, which is exact to float rounding.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from shallowspeed_tpu.ops import functional as F

EPS = 1e-3  # float32 central differences
RNG = np.random.default_rng(0)


def rand(*shape):
    return jnp.asarray(RNG.normal(size=shape).astype(np.float32))


def central_diff(f, x, dout, eps=EPS):
    """Numerical VJP: sum(dout * df/dx_i) for each i, via central differences."""
    x = np.asarray(x, dtype=np.float64)
    dout = np.asarray(dout, dtype=np.float64)
    g = np.zeros_like(x)
    it = np.nditer(x, flags=["multi_index"])
    for _ in it:
        idx = it.multi_index
        xp, xm = x.copy(), x.copy()
        xp[idx] += eps
        xm[idx] -= eps
        fp = np.asarray(f(jnp.asarray(xp, jnp.float32)), dtype=np.float64)
        fm = np.asarray(f(jnp.asarray(xm, jnp.float32)), dtype=np.float64)
        g[idx] = ((fp - fm) / (2 * eps) * dout).sum()
    return g


# ---------------------------------------------------------------- shapes


def test_shapes():
    x = rand(4, 7)
    w = rand(5, 7)
    b = rand(1, 5)
    assert F.relu(x).shape == x.shape
    assert F.linear(x, w, b).shape == (4, 5)
    dx, dw, db = F.linear_grad(rand(4, 5), x, w)
    assert dx.shape == x.shape and dw.shape == w.shape and db.shape == b.shape
    assert F.softmax(x).shape == x.shape
    assert F.mse_loss(x, x, 4).shape == ()


# ---------------------------------------------------------------- relu


def test_relu_values():
    x = jnp.array([[-1.0, 0.0, 2.5]])
    np.testing.assert_allclose(F.relu(x), [[0.0, 0.0, 2.5]])


def test_relu_grad_matches_fd():
    x = rand(3, 4)
    dout = rand(3, 4)
    got = F.relu_grad(dout, x > 0)
    want = central_diff(F.relu, x, dout)
    np.testing.assert_allclose(got, want, atol=1e-4)


# ---------------------------------------------------------------- linear


def test_linear_grad_matches_fd():
    x, w, b = rand(3, 4), rand(5, 4), rand(1, 5)
    dout = rand(3, 5)
    dx, dw, db = F.linear_grad(dout, x, w)
    np.testing.assert_allclose(
        dx, central_diff(lambda v: F.linear(v, w, b), x, dout), atol=1e-3
    )
    np.testing.assert_allclose(
        dw, central_diff(lambda v: F.linear(x, v, b), w, dout), atol=1e-3
    )
    np.testing.assert_allclose(
        db, central_diff(lambda v: F.linear(x, w, v), b, dout), atol=1e-3
    )


def test_linear_grad_matches_vjp():
    x, w, b = rand(3, 4), rand(5, 4), rand(1, 5)
    dout = rand(3, 5)
    _, vjp = jax.vjp(F.linear, x, w, b)
    vdx, vdw, vdb = vjp(dout)
    dx, dw, db = F.linear_grad(dout, x, w)
    np.testing.assert_allclose(dx, vdx, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(dw, vdw, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(db, vdb, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------- softmax


def test_softmax_rows_sum_to_one():
    p = F.softmax(rand(6, 10))
    np.testing.assert_allclose(p.sum(axis=1), np.ones(6), atol=1e-5)
    assert bool((p >= 0).all())


def test_softmax_shift_invariance():
    # Reference property test (`test_functional.py:116-122`).
    x = rand(4, 9)
    np.testing.assert_allclose(F.softmax(x), F.softmax(x + 100.0), atol=1e-5)


def test_softmax_grad_matches_fd():
    x = rand(3, 5)
    dout = rand(3, 5)
    got = F.softmax_grad(dout, x)
    want = central_diff(F.softmax, x, dout)
    np.testing.assert_allclose(got, want, atol=1e-3)


def test_softmax_grad_matches_vjp():
    x = rand(3, 5)
    dout = rand(3, 5)
    _, vjp = jax.vjp(F.softmax, x)
    (want,) = vjp(dout)
    np.testing.assert_allclose(F.softmax_grad(dout, x), want, rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------- mse


def test_mse_loss_value():
    pred = jnp.array([[1.0, 2.0], [3.0, 4.0]])
    target = jnp.array([[1.0, 0.0], [0.0, 4.0]])
    # sum of squared errors = 4 + 9 = 13, over batch_size 2
    np.testing.assert_allclose(F.mse_loss(pred, target, 2), 13.0 / 2)


def test_mse_loss_grad_matches_vjp():
    pred, target = rand(4, 3), rand(4, 3)
    got = F.mse_loss_grad(pred, target, 8)
    _, vjp = jax.vjp(lambda p: F.mse_loss(p, target, 8), pred)
    (want,) = vjp(jnp.float32(1.0))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_mse_global_batch_scaling_invariant():
    """Sum of per-microbatch grads (each scaled by GLOBAL bs) equals the
    full-batch grad — the invariant that makes DP+μbatching exact
    (reference `functional.py:43-44` + SURVEY §3.5)."""
    pred, target = rand(8, 3), rand(8, 3)
    full = F.mse_loss_grad(pred, target, 8)
    parts = [F.mse_loss_grad(pred[i : i + 2], target[i : i + 2], 8) for i in range(0, 8, 2)]
    np.testing.assert_allclose(jnp.concatenate(parts), full, rtol=1e-6)


# ---------------------------------------------------------------- jit


@pytest.mark.parametrize("fn_args", [
    (F.relu, (rand(2, 3),)),
    (F.softmax, (rand(2, 3),)),
    (F.linear, (rand(2, 3), rand(4, 3), rand(1, 4))),
])
def test_ops_are_jittable(fn_args):
    fn, args = fn_args
    np.testing.assert_allclose(jax.jit(fn)(*args), fn(*args), rtol=1e-6)
