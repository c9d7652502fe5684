"""Reference-math parity: the compiled TPU engine vs the pure-NumPy step.

The reference cannot execute in this image (mpi4py/mpirun absent, and its
OpenML fetch needs egress), so the strongest available parity check is
the one its own DDP script uses — absolute weight divergence against an
independently-executed implementation of the same math
(`/root/reference/scripts/DDP_PyTorch_MNIST.py:159-167`). The NumPy
step below IS the reference's math (same forward, hand-written
backward, microbatch grad accumulation over the GLOBAL-batch-scaled MSE
grad, SGD; `functional.py`, `layers.py`, `optimizer.py`); here we train
both it and the jitted `FusedDPEngine` from the SAME seeded init on the
same batches and require the weights to stay together.
"""

import numpy as np

from shallowspeed_tpu.engine import FusedDPEngine
from shallowspeed_tpu.models.mlp import MLPStage, init_stage_params
from shallowspeed_tpu.optim import SGD
from shallowspeed_tpu.parallel.mesh import make_mesh

# the reference's training config (`/root/reference/train.py:56-59,98,107`)
LAYER_SIZES = [784, 128, 127, 126, 125, 124, 123, 10]
GBS = 128
N_MU = 4
LR = 0.006


def numpy_baseline_step_fn():
    """Reference-equivalent pure-NumPy training step (measured, not copied:
    same math as shallowspeed_tpu.ops.functional on the NumPy substrate)."""
    params = [{k: np.asarray(v) for k, v in layer.items()}
              for layer in init_stage_params(LAYER_SIZES)]
    n = len(params)

    def step(xs, ys):  # xs: (N_MU, mubs, 784); mutates `params` in place
        grads = [{"W": np.zeros_like(p["W"]), "b": np.zeros_like(p["b"])}
                 for p in params]
        for mu in range(N_MU):
            x, t = xs[mu], ys[mu]
            acts = [x]
            masks = []
            h = x
            for i, p in enumerate(params):
                z = h @ p["W"].T + p["b"]
                if i < n - 1:
                    masks.append(z > 0)
                    h = np.maximum(z, 0.0)
                else:
                    h = z
                acts.append(h)
            e = np.exp(h - h.max())
            probs = e / (e.sum(axis=1, keepdims=True) + 1e-7)
            dout = -2.0 * (t - probs) / GBS
            g = probs * dout
            dout = g - probs * g.sum(axis=-1, keepdims=True)
            for i in range(n - 1, -1, -1):
                if i < n - 1:
                    dout = dout * masks[i]
                grads[i]["W"] += dout.T @ acts[i]
                grads[i]["b"] += dout.sum(axis=0, keepdims=True)
                dout = dout @ params[i]["W"]
        for p, g in zip(params, grads):
            p["W"] -= LR * g["W"]
            p["b"] -= LR * g["b"]

    step.params = params
    return step


def make_data(seed, n_batches):
    rng = np.random.default_rng(seed)
    xs = rng.normal(size=(n_batches, N_MU, GBS // N_MU, 784)).astype(
        np.float32)
    labels = rng.integers(0, 10, (n_batches, GBS))
    ys = np.zeros((n_batches, GBS, 10), np.float32)
    for b in range(n_batches):
        ys[b, np.arange(GBS), labels[b]] = 1.0
    return xs, ys.reshape(n_batches, N_MU, GBS // N_MU, 10)


def test_fused_engine_matches_numpy_reference_math():
    n_batches = 12
    xs, ys = make_data(0, n_batches)

    np_step = numpy_baseline_step_fn()

    class _DS:
        def get_num_batches(self):
            return n_batches

        def load_mubatch_stack(self, batch_id):
            return xs[batch_id], ys[batch_id]

    eng = FusedDPEngine(MLPStage(LAYER_SIZES, 0, 1, batch_size=GBS),
                        SGD(LR), make_mesh(1, 1))
    ds = _DS()

    # identical seeded init before any step
    for i, (np_p, j_p) in enumerate(zip(np_step.params, eng.params)):
        np.testing.assert_array_equal(np_p["W"], np.asarray(j_p["W"]),
                                      err_msg=f"init layer {i}")

    for b in range(n_batches):
        np_step(xs[b], ys[b])
        eng.train_batch(b, [ds])

    # the reference's own parity criterion: small absolute weight
    # divergence after training (float reassociation only)
    for i, (np_p, j_p) in enumerate(zip(np_step.params, eng.params)):
        np.testing.assert_allclose(
            np.asarray(j_p["W"]), np_p["W"], rtol=5e-4, atol=1e-5,
            err_msg=f"layer {i} W diverged from the reference math")
        np.testing.assert_allclose(
            np.asarray(j_p["b"]).ravel(), np_p["b"].ravel(),
            rtol=5e-4, atol=1e-5,
            err_msg=f"layer {i} b diverged from the reference math")
