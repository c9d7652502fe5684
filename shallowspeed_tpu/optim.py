"""Optimizers as pure pytree transforms.

Capability parity with the reference's stateless SGD
(`/root/reference/shallowspeed/optimizer.py:4-13`, `param.data -= lr * grad`),
re-designed functionally: `step(params, grads, state) -> (params, state)` is a
pure function that jits and shards like any other part of the training step
(optax-style, but self-contained). Momentum-SGD, Adam, AdamW, learning-rate
schedules, and global-norm gradient clipping are additions beyond the
reference surface.

Every optimizer accepts `lr` as either a float or a schedule — a callable
`t -> lr` evaluated on the (0-based) step counter carried in the optimizer
state, traced into the compiled step so the schedule runs on-device. A
`grad_clip` argument applies global-norm clipping before the update.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Callable, Union

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

tree_map = jax.tree_util.tree_map

LR = Union[float, Callable[[jax.Array], jax.Array]]

# ------------------------------------------------------------- schedules


def constant(peak: float, warmup: int = 0, total: int = 0, end: float = 0.0):
    """Constant schedule. Signature-compatible with warmup_linear/
    warmup_cosine (warmup/total/end accepted and ignored) so call sites can
    construct any SCHEDULES entry uniformly."""
    return lambda t: jnp.asarray(peak, jnp.float32)


def warmup_linear(peak: float, warmup: int, total: int, end: float = 0.0):
    """Linear 0 -> peak over `warmup` steps, then linear peak -> end at
    `total` steps (clamped after)."""
    def sched(t):
        t = jnp.asarray(t, jnp.float32)
        up = peak * t / max(warmup, 1)
        frac = jnp.clip((t - warmup) / max(total - warmup, 1), 0.0, 1.0)
        down = peak + (end - peak) * frac
        return jnp.where(t < warmup, up, down)

    return sched


def warmup_cosine(peak: float, warmup: int, total: int, end: float = 0.0):
    """Linear 0 -> peak over `warmup` steps, then cosine peak -> end at
    `total` steps (clamped after). The standard LM-pretraining schedule."""
    def sched(t):
        t = jnp.asarray(t, jnp.float32)
        up = peak * t / max(warmup, 1)
        frac = jnp.clip((t - warmup) / max(total - warmup, 1), 0.0, 1.0)
        down = end + (peak - end) * 0.5 * (1 + jnp.cos(math.pi * frac))
        return jnp.where(t < warmup, up, down)

    return sched


SCHEDULES = {"constant": constant, "linear": warmup_linear,
             "cosine": warmup_cosine}

# -------------------------------------------------------------- clipping


def _varying_axes(x, axes: tuple) -> tuple:
    """The subset of `axes` the value actually varies over (shard_map VMA
    typing). A leaf invariant over an axis is already fully reduced there
    — psumming it would count it axis-size times."""
    vma = jax.typeof(x).vma
    return tuple(a for a in axes if a in vma)


def global_norm(grads: Any, axes: tuple = ()) -> jax.Array:
    """L2 norm over every leaf of the gradient pytree (f32 accumulation).

    `axes`: mesh axis names to `lax.psum` squared sums over — required
    when called inside `shard_map` with grads *sharded* over those axes
    (e.g. per-stage grads over 'pp' in the pipeline engines), so the norm
    is the true global one, not the local shard's. Per-leaf variance is
    respected: a pytree mixing pp-sharded block grads with replicated
    (already-reduced) embedding grads sums each exactly once."""
    leaves = jax.tree_util.tree_leaves(grads)
    total = jnp.float32(0.0)
    for l in leaves:
        sq = jnp.sum(jnp.square(l.astype(jnp.float32)))
        ax = _varying_axes(sq, axes) if axes else ()
        if ax:
            sq = jax.lax.psum(sq, ax)
        total = total + sq
    return jnp.sqrt(total)


def clip_by_global_norm(grads: Any, max_norm: float,
                        axes: tuple = ()) -> Any:
    """Scale the whole pytree so its global norm is at most `max_norm`."""
    norm = global_norm(grads, axes)
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-12))
    return tree_map(lambda g: (g * scale).astype(g.dtype), grads)




# ------------------------------------------------------------ optimizers


class _Optimizer:
    """Shared lr/schedule/clip plumbing.

    `clip_axes` (class default `()`): mesh axis names whose shards must be
    psum-combined for the clipping norm. Engines that trace `step` inside a
    `shard_map` where grads are *sharded* (not invariant) set this on their
    private copy of the optimizer (see `SPMDPipelineEngine`); with grads
    replicated or under GSPMD-jit the default is already the global norm."""

    clip_axes: tuple = ()

    def __init__(self, lr: LR, grad_clip: float | None = None):
        self.lr = lr
        self.grad_clip = grad_clip

    def _lr_at(self, t) -> jax.Array:
        if callable(self.lr):
            return jnp.asarray(self.lr(t), jnp.float32)
        return jnp.asarray(self.lr, jnp.float32)

    def _prep(self, grads: Any) -> Any:
        if self.grad_clip is not None:
            return clip_by_global_norm(grads, self.grad_clip, self.clip_axes)
        return grads

    def guarded_step(self, params: Any, grads: Any, state: Any, ok):
        """`step` with the whole update gated on the traced bool `ok`
        (shape (), e.g. the health pack's `nonfinite == 0` sentinel):
        when `ok` is False every parameter AND optimizer-state leaf —
        moments, step counters, schedule state — is the old value,
        bit-identical, so a skipped step is indistinguishable from
        never having run. This is the `skip_step` guard the health
        layer (`telemetry/health.py`) compiles into the engines' train
        steps; it lives here, next to `_prep`'s clipping, because both
        gate the update on the same global gradient statistics."""
        new_p, new_s = self.step(params, grads, state)

        def keep(new, old):
            return jnp.where(ok, new, old)

        return (tree_map(keep, new_p, params),
                tree_map(keep, new_s, state))

    def map_state_trees(self, state: Any, fn) -> Any:
        """Apply `fn` — a params-shaped-tree -> params-shaped-tree
        transform (e.g. an engine's stack/unstack between its layout and
        the canonical checkpoint layout) — to every params-shaped moment
        tree inside `state`, passing scalars (step counters) through.

        This is the seam that makes optimizer state engine-agnostic in
        checkpoints (`checkpoint.py`): an engine that can re-layout its
        params can re-layout exactly-params-shaped moments with the SAME
        transform. Default: no params-shaped trees (stateless SGD).
        Optimizers whose state is NOT params-shaped (Adafactor's factored
        vr/vc) raise ValueError — callers fall back to re-initializing.
        """
        return state


class SGD(_Optimizer):
    """Plain SGD. Reference: `optimizer.py:4-13`. Stateless with a static
    lr (exactly the reference's shape); carries a step counter only when
    driven by a schedule."""

    def init(self, params: Any) -> Any:
        if callable(self.lr):
            return {"t": jnp.zeros((), jnp.int32)}
        return ()

    def step(self, params: Any, grads: Any, state: Any = ()):
        grads = self._prep(grads)
        sched = callable(self.lr)
        t = state["t"] if sched else jnp.zeros((), jnp.int32)
        lr = self._lr_at(t)
        # update math may promote to f32 (lr is a strong f32 scalar, grads
        # may be f32 master-dtype); params keep their own dtype
        new = tree_map(lambda p, g: (p - lr * g).astype(p.dtype),
                       params, grads)
        return new, ({"t": t + 1} if sched else state)


class MomentumSGD(_Optimizer):
    """SGD with classical momentum (addition beyond the reference)."""

    def __init__(self, lr: LR, momentum: float = 0.9,
                 grad_clip: float | None = None):
        super().__init__(lr, grad_clip)
        self.momentum = momentum

    def init(self, params: Any) -> Any:
        vel = tree_map(jnp.zeros_like, params)
        if callable(self.lr):
            return {"v": vel, "t": jnp.zeros((), jnp.int32)}
        return vel

    def step(self, params: Any, grads: Any, state: Any):
        grads = self._prep(grads)
        sched = callable(self.lr)
        vel0 = state["v"] if sched else state
        t = state["t"] if sched else jnp.zeros((), jnp.int32)
        lr = self._lr_at(t)
        vel = tree_map(lambda v, g: (self.momentum * v + g).astype(v.dtype),
                       vel0, grads)
        new = tree_map(lambda p, v: (p - lr * v).astype(p.dtype),
                       params, vel)
        return new, ({"v": vel, "t": t + 1} if sched else vel)

    def map_state_trees(self, state: Any, fn) -> Any:
        if isinstance(state, dict) and "v" in state:
            return {"v": fn(state["v"]), "t": state["t"]}
        return fn(state)


class Adam(_Optimizer):
    """Adam (addition; matches the reference's PyTorch-DDP baseline script,
    `scripts/DDP_PyTorch_MNIST.py`, which trains with torch Adam)."""

    def __init__(self, lr: LR, b1: float = 0.9, b2: float = 0.999,
                 eps: float = 1e-8, grad_clip: float | None = None):
        super().__init__(lr, grad_clip)
        self.b1, self.b2, self.eps = b1, b2, eps

    weight_decay = 0.0  # AdamW overrides; keeps `_update` shared

    def init(self, params: Any) -> Any:
        return {"m": tree_map(jnp.zeros_like, params),
                "v": tree_map(jnp.zeros_like, params),
                "t": jnp.zeros((), jnp.int32)}

    def step(self, params: Any, grads: Any, state: Any):
        grads = self._prep(grads)
        lr = self._lr_at(state["t"])  # schedule indexed 0-based
        t = state["t"] + 1
        m = tree_map(
            lambda m_, g: (self.b1 * m_ + (1 - self.b1) * g).astype(m_.dtype),
            state["m"], grads)
        v = tree_map(
            lambda v_, g: (self.b2 * v_
                           + (1 - self.b2) * g * g).astype(v_.dtype),
            state["v"], grads)
        tf = t.astype(jnp.float32)
        bc1 = 1 - self.b1 ** tf
        bc2 = 1 - self.b2 ** tf
        wd = self.weight_decay
        new = tree_map(
            lambda p, m_, v_: (p - lr * ((m_ / bc1) /
                                         (jnp.sqrt(v_ / bc2) + self.eps)
                                         + wd * p)).astype(p.dtype),
            params, m, v)
        return new, {"m": m, "v": v, "t": t}

    def map_state_trees(self, state: Any, fn) -> Any:
        return {"m": fn(state["m"]), "v": fn(state["v"]), "t": state["t"]}


class AdamW(Adam):
    """Adam with decoupled weight decay (Loshchilov & Hutter, 2019):
    the decay term `wd * p` joins the update *after* the moment estimate,
    scaled by lr — torch.optim.AdamW semantics."""

    def __init__(self, lr: LR, b1: float = 0.9, b2: float = 0.999,
                 eps: float = 1e-8, weight_decay: float = 0.01,
                 grad_clip: float | None = None):
        super().__init__(lr, b1, b2, eps, grad_clip)
        self.weight_decay = weight_decay


class Adafactor(_Optimizer):
    """Adafactor (Shazeer & Stern, 2018) — the TPU-era memory-efficient
    optimizer: matrix leaves store FACTORED second moments (a row vector +
    a column vector instead of a full matrix; O(n+m) not O(nm) state), so
    the optimizer footprint all but vanishes next to Adam's 2x params.
    Composes with ZeRO-1/2 like any other state (the factored vectors
    shard over dp too) — together the two give DeepSpeed-style memory
    scaling with a fraction of the bytes to shard in the first place.

    Implementation notes:
    - leaves with ndim >= 2 factor their TRAILING two dims; leading dims
      (stacked pipeline blocks (L, d, k·d), MoE experts (E, d, ff)) stay
      elementwise, so every engine's parameter layout factors usefully.
    - ndim <= 1 leaves (biases, norms) keep a full second moment.
    - beta2 follows the paper's schedule 1 - t^(-0.8); updates are
      RMS-clipped at `clip_threshold`; with `scale_parameter` the step is
      multiplied by max(eps_scale, RMS(param)) — the paper's relative
      step — so `lr` plays the role of the relative step size.
    - no first moment by default (`beta1=0.0` — the memory point);
      set beta1 > 0 to trade memory for momentum.
    - the per-leaf RMS statistics (clip, parameter scale) are computed
      over whatever the leaf IS where the step runs: under model-sharded
      shard_map engines (pp-stacked blocks) that is the local shard —
      a standard, benign approximation (the paper's statistics are
      per-matrix heuristics to begin with); under GSPMD engines the
      statistics are exact.
    """

    def __init__(self, lr: LR, beta1: float = 0.0, decay_pow: float = 0.8,
                 eps: float = 1e-30, eps_scale: float = 1e-3,
                 clip_threshold: float = 1.0, scale_parameter: bool = True,
                 weight_decay: float = 0.0,
                 grad_clip: float | None = None):
        super().__init__(lr, grad_clip)
        self.beta1 = beta1
        self.decay_pow = decay_pow
        self.eps = eps
        self.eps_scale = eps_scale
        self.clip_threshold = clip_threshold
        self.scale_parameter = scale_parameter
        self.weight_decay = weight_decay

    @staticmethod
    def _factored(p) -> bool:
        """Factor the trailing two dims — iff they are unsharded. The
        row/col statistics REDUCE those dims, so a mesh axis living there
        would make the statistics shard-local (wrong under shard_map) or
        force extra collectives (under GSPMD); such leaves (e.g. Megatron
        column/row-sharded matrices) keep a full second moment instead.
        Leading stacked dims (pipeline blocks (L, ...), MoE experts
        (E, ...)) may be sharded freely — their axes survive into vr/vc."""
        if p.ndim < 2:
            return False
        sh = getattr(p, "sharding", None)
        if isinstance(sh, NamedSharding):
            spec = list(sh.spec) + [None] * (p.ndim - len(sh.spec))
            return spec[-1] is None and spec[-2] is None
        return True

    def _slot(self, p):
        if self._factored(p):
            # the factored zeros inherit the parameter's placement on the
            # surviving (leading) dims — a pp-stacked (L, d, k) block
            # leaf yields P('pp', ...)-sharded vr/vc — which is what lets
            # the sharded engines read optimizer-state specs off the
            # leaves
            vr = jnp.zeros(p.shape[:-1], jnp.float32)
            vc = jnp.zeros(p.shape[:-2] + p.shape[-1:], jnp.float32)
            sh = getattr(p, "sharding", None)
            if isinstance(sh, NamedSharding):
                spec = list(sh.spec) + [None] * (p.ndim - len(sh.spec))
                vr = jax.device_put(
                    vr, NamedSharding(sh.mesh, PartitionSpec(*spec[:-1])))
                vc = jax.device_put(
                    vc, NamedSharding(sh.mesh,
                                      PartitionSpec(*spec[:-2]
                                                    + spec[-1:])))
            slot = {"vr": vr, "vc": vc}
        else:
            slot = {"v": jnp.zeros_like(p, jnp.float32)}
        if self.beta1 > 0.0:
            slot["m"] = jnp.zeros_like(p, jnp.float32)
        return slot

    def init(self, params: Any) -> Any:
        leaves, tdef = jax.tree_util.tree_flatten(params)
        return {"slots": tuple(self._slot(p) for p in leaves),
                "t": jnp.zeros((), jnp.int32)}

    def step(self, params: Any, grads: Any, state: Any):
        grads = self._prep(grads)
        lr = self._lr_at(state["t"])
        t = state["t"] + 1
        beta2 = 1.0 - t.astype(jnp.float32) ** (-self.decay_pow)

        p_leaves, tdef = jax.tree_util.tree_flatten(params)
        g_leaves = jax.tree_util.tree_leaves(grads)
        new_p, new_slots = [], []
        for p, g, slot in zip(p_leaves, g_leaves, state["slots"]):
            gf = g.astype(jnp.float32)
            g2 = gf * gf + self.eps
            slot = dict(slot)
            # branch on the slot's structure (decided at init, where real
            # shardings are visible), never on the traced param
            if "vr" in slot:
                vr = beta2 * slot["vr"] + (1 - beta2) * g2.mean(axis=-1)
                vc = beta2 * slot["vc"] + (1 - beta2) * g2.mean(axis=-2)
                slot["vr"], slot["vc"] = vr, vc
                # v̂ = (vr / mean(vr)) ⊗ vc — the rank-1 reconstruction
                rfac = vr / vr.mean(axis=-1, keepdims=True)
                u = gf * jax.lax.rsqrt(rfac[..., :, None]
                                       * vc[..., None, :])
            else:
                v = beta2 * slot["v"] + (1 - beta2) * g2
                slot["v"] = v
                u = gf * jax.lax.rsqrt(v)
            # RMS clip: tame early steps when the moment estimate is cold
            rms_u = jnp.sqrt(jnp.mean(u * u))
            u = u / jnp.maximum(1.0, rms_u / self.clip_threshold)
            a = lr
            if self.scale_parameter:
                a = a * jnp.maximum(
                    self.eps_scale,
                    jnp.sqrt(jnp.mean(jnp.square(p.astype(jnp.float32)))))
            if self.beta1 > 0.0:
                m = self.beta1 * slot["m"] + (1 - self.beta1) * u
                slot["m"] = m
                u = m
            # decay with the same parameter-scaled step as the main
            # update: under scale_parameter the schedule lr is a
            # *relative* step size, so decay strength must track RMS(p)
            # too or leaves with small/large RMS decay disproportionately
            upd = a * u + a * self.weight_decay * p.astype(jnp.float32)
            new_p.append((p.astype(jnp.float32) - upd).astype(p.dtype))
            new_slots.append(slot)
        return (jax.tree_util.tree_unflatten(tdef, new_p),
                {"slots": tuple(new_slots), "t": t})

    def map_state_trees(self, state: Any, fn) -> Any:
        raise ValueError(
            "Adafactor state is factored (per-leaf vr/vc vectors keyed to "
            "the flattened engine params), not params-shaped; it cannot "
            "be re-laid-out by a params-tree transform. Engines whose "
            "layout IS canonical interchange it directly.")


OPTIMIZERS = {"sgd": SGD, "momentum": MomentumSGD, "adam": Adam,
              "adamw": AdamW, "adafactor": Adafactor}


# ------------------------------------------------------------------- EMA


@partial(jax.jit, donate_argnums=(0,))
def ema_update(ema: Any, params: Any, decay) -> Any:
    """One exponential-moving-average step: ema <- d*ema + (1-d)*params.

    Pure elementwise pytree transform: works on ANY engine's live params
    (replicated, ZeRO/FSDP-sharded, pipeline-stacked) because the output
    inherits each leaf's sharding; the old ema buffer is donated, so the
    running average costs one params-sized buffer total. Engines stay
    untouched — the driver owns the averaging (and evaluates/samples by
    temporarily swapping the averaged tree in)."""
    d = jnp.float32(decay)
    return tree_map(
        lambda e, p: (d * e + (1.0 - d) * p.astype(jnp.float32))
        .astype(e.dtype), ema, params)


def ema_init(params: Any) -> Any:
    """Start the average AT the current params (standard warm init —
    an all-zeros start would bias early evals toward zero)."""
    return tree_map(lambda p: p + 0, params)  # copy, keeps sharding
