"""Matmuls over quantized operands: the fused-dequant product behind
quantized weight storage (`dequant_matmul`) and the fp8-e4m3 training
matmul with its hand-written straight-through backward (`fp8_dense`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


# ------------------------------------------------ fused-dequant matmul
#
# Quantized WEIGHT storage (round 14, ROADMAP item 1): decode is
# HBM-bound on the parameter sweep, so int8 (or fp8-e4m3) weights with
# per-out-channel f32 scales halve-or-better the bytes behind
# `serving/cache.param_read_bytes`. The trap is dequantizing wrong: a
# `(wq * scale).astype(f32)` materializes a FULL-SIZE dequantized copy
# of the weight — the exact HBM traffic the storage was meant to
# remove. The contract here is the fused form, proved statically by
# the analysis `dequant-fusion` rule over the traced decode tick.


def dequant_matmul(x, wq, ws, *, compute_dtype=None):
    """x (..., K) @ quantized wq (K, N) with per-out-channel f32 scales
    ws (N,), the dequant FUSED into the matmul:

    - wq's VALUES are cast to the compute dtype inside the dot. That is
      a value cast, not a dequant — int8 integers and e4m3 floats are
      both exactly representable in bf16/f32 — and XLA folds it into
      the operand load, so HBM reads stay 1 byte/element.
    - accumulation is f32 (`preferred_element_type`), matching every
      other MXU dot in the repo.
    - the scale multiplies the f32 ACCUMULATOR (shape (..., N)), never
      the weight: no (K, N) dequantized buffer ever exists. The
      per-out-channel scale is constant along the contraction axis,
      which is what makes this reassociation exact.

    Returns (..., N) in x's dtype. The analysis `dequant-fusion` rule
    walks consumers of every int8/fp8 weight upcast and flags any
    full-weight-size elementwise use — this function is its clean
    fixture."""
    cdt = compute_dtype or x.dtype
    acc = jax.lax.dot_general(
        x.astype(cdt), wq.astype(cdt),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return (acc * ws.astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------- fp8-e4m3 training matmul
#
# ROADMAP item 5's forward path (round 16): fp8-e4m3 storage for the
# forward matmul's operands, with the same fused-dequant discipline as
# `dequant_matmul` — the scale product lands on the f32 ACCUMULATOR,
# never on an operand-sized buffer. The backward is a straight-through
# estimator written by hand: naive autodiff through the quantization
# casts would round-trip the COTANGENTS through e4m3 (a second
# narrowing with no rescale — exactly what the analysis
# `fp8-double-rounding` rule flags), so the custom VJP keeps gradients
# f32 end-to-end and re-uses the stored fp8 operands only inside f32-
# accumulated dots. The `fp8_train` analysis target proves all of this
# statically on the traced step.

E4M3_MAX = 448.0  # ml_dtypes.finfo(float8_e4m3fn).max
E4M3_TINY = 2.0 ** -9  # smallest e4m3 subnormal (1 * 2^-9)


def _check_fp8_operands(x, w):
    """fp8_dense's shape contract as a typed error (the repo's
    config-validation convention): the hand VJP contracts the batch
    axis for dw, so only 2-D activations/weights are expressible."""
    if x.ndim != 2 or w.ndim != 2:
        raise ValueError(
            f"fp8_dense takes 2-D operands x (B, K) @ w (K, N); got "
            f"x.shape={tuple(x.shape)}, w.shape={tuple(w.shape)} — "
            f"reshape (..., K) activations to (-1, K) at the call site")
    if x.shape[1] != w.shape[0]:
        raise ValueError(
            f"fp8_dense contraction mismatch: x (B, K={x.shape[1]}) @ "
            f"w (K={w.shape[0]}, N)")


def fp8_clamp_stats(x, scale):
    """Traced per-tensor clamp statistics for one activation quantize —
    the numerics pack's raw ingredients, computed on the SAME (x,
    scale) pair `fp8_quantize` sees so the fractions describe exactly
    what the dot consumed:

    - overflow: fraction of elements saturated by the ±E4M3_MAX clip
      (a too-SMALL delayed scale — amax history collapsed or lagging a
      range expansion);
    - underflow: fraction of NONZERO elements that round to zero in
      e4m3 (|x/scale| below half the smallest subnormal — a too-LARGE
      scale flushing real signal; exact zeros are excluded so ReLU
      sparsity does not read as underflow).

    Returns two f32 scalars; a handful of VPU ops per call, designed to
    ride the compiled step under the health pack's zero-new-executables
    contract. The weight side is deliberately not measured: its
    just-in-time per-out-channel scale makes saturation impossible by
    construction."""
    y = jnp.abs(x.astype(jnp.float32)) / scale
    overflow = jnp.mean((y > E4M3_MAX).astype(jnp.float32))
    nz = y > 0.0
    under = jnp.logical_and(nz, y < 0.5 * E4M3_TINY)
    denom = jnp.maximum(jnp.sum(nz.astype(jnp.float32)), 1.0)
    underflow = jnp.sum(under.astype(jnp.float32)) / denom
    return overflow, underflow


def fp8_quantize(x, scale):
    """`x / scale`, saturated to the e4m3 range and rounded once into
    fp8 storage. The clip is what makes the convert provably in-range
    for the analysis `range-safety` rule; the divide is the rescale
    that pairs the quantized lineage to `scale` for `scale-consistency`
    (and resets the rounding state for `fp8-double-rounding`)."""
    y = x.astype(jnp.float32) / scale
    return jnp.clip(y, -E4M3_MAX, E4M3_MAX).astype(jnp.float8_e4m3fn)


def _w_scale(w):
    """Just-in-time per-out-channel weight scale. `stop_gradient`: the
    scale is quantization bookkeeping, not a trainable path."""
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=0)
    return jax.lax.stop_gradient(jnp.maximum(amax / E4M3_MAX, 1e-12))


@jax.custom_vjp
def fp8_dense(x, w, sx):
    """x (B, K) @ w (K, N), both quantized to fp8-e4m3 for the dot:
    `x` with the DELAYED per-tensor scale `sx` (from the caller's amax
    history — this step's stats only feed the NEXT step's scale), `w`
    with a just-in-time per-out-channel scale. f32 accumulation; the
    dequant `* (sx * sw)` is reassociated onto the accumulator (both
    scales are constant along the contraction axis). Returns (..., N)
    f32. 2-D activations only (the hand VJP contracts the batch
    axis for dw)."""
    _check_fp8_operands(x, w)
    sw = _w_scale(w)
    acc = jax.lax.dot_general(
        fp8_quantize(x, sx), fp8_quantize(w, sw),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return acc * (sx * sw)


def _fp8_dense_fwd(x, w, sx):
    _check_fp8_operands(x, w)
    sw = _w_scale(w)
    xq, wq = fp8_quantize(x, sx), fp8_quantize(w, sw)
    acc = jax.lax.dot_general(
        xq, wq, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return acc * (sx * sw), (xq, wq, sx, sw)


def _fp8_dense_bwd(res, g):
    """Straight-through estimator: quantization treated as identity, so
    dx = g @ w^T and dw = x^T @ g, computed FROM the stored fp8
    operands with every dequant on an f32 accumulator:

    - dx: the cotangent arrives pre-multiplied by `sw` (the analysis
      prover's "cotangent-scaled" form — `wq`'s scale rides the other
      dot operand), and `sx` dequantizes the accumulator.
    - dw: `xq`'s dequant by `sx` is reassociated onto the accumulator
      (`sx` is per-tensor, constant along every axis).
    - the scales get zero cotangents: bookkeeping, not parameters.

    Saturated elements keep their pass-through gradient (plain STE; no
    clip mask — delayed scaling keeps saturation rare by construction).
    """
    xq, wq, sx, sw = res
    g = g.astype(jnp.float32)
    dx = jax.lax.dot_general(
        g * sw, wq, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * sx
    dw = jax.lax.dot_general(
        xq, g, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * sx
    return dx, dw, jnp.zeros_like(sx)


fp8_dense.defvjp(_fp8_dense_fwd, _fp8_dense_bwd)
