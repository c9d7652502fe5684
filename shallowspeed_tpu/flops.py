"""Model-FLOPs accounting and MFU (model FLOPs utilization).

The reference has no notion of utilization — its "perf story" is
wall-clock epoch prints (`/root/reference/train.py:131-137`). On TPU the
bar is fraction-of-peak: the MXU has a fixed bf16 throughput per chip, so
achieved-TFLOP/s divided by that peak is the hardware-honest headline.

FLOPs are counted exactly from the model config — every matmul's 2*M*N*K,
not the 6N approximation — and follow the standard *model* FLOPs
convention (PaLM appendix B): forward + 2x backward = 3x forward, counting
only algorithmically required work. Rematerialization's extra forward is
deliberately NOT counted (that is what makes this MFU, not HFU).
"""

from __future__ import annotations

# Peak dense matmul throughput per JAX DEVICE, FLOP/s (bf16, published
# spec sheets). The unit is deliberately the device, not the chip: on
# v2/v3 JAX exposes each TensorCore as a separate device (2 per chip —
# `jax.local_devices()` on a v3-8 host lists 8 devices on 4 chips), so
# their entries are the per-core half of the chip spec (v2: 45/2, v3:
# 123/2). From v4 on the two cores are fused (megacore) and device ==
# chip, so those entries are chip peaks. This is what makes
# `mfu(n_devices=mesh size)` correct on every generation: mesh axes
# count devices, and the table is per-device. f32 is derived below as the
# measured-practical MXU f32 ratio (~1/8 of bf16 via multi-pass
# emulation on v4/v5 generations).
_PEAKS_BF16 = {
    "TPU v2": 22.5e12,   # per core; chip spec 45 TFLOP/s, 2 cores/chip
    "TPU v3": 61.5e12,   # per core; chip spec 123 TFLOP/s, 2 cores/chip
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,    # v5p
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,  # Trillium / v6e
    "TPU v6e": 918e12,
    "TPU v7": 2307e12,   # Ironwood (per-chip, dense fp8 4.6PF -> bf16 2.3)
}


# HBM bandwidth per JAX DEVICE, bytes/s (published spec sheets; same
# device-vs-chip convention as _PEAKS_BF16 — v2/v3 entries are the
# per-core half of the shared chip HBM). The v5e entry matches the
# 819 GB/s this repo's own decode sweeps measured at the roofline
# (BASELINE.md "flash-decode kernel evaluation").
_HBM_BPS = {
    "TPU v2": 350e9,     # 700 GB/s chip, 2 cores
    "TPU v3": 450e9,     # 900 GB/s chip, 2 cores
    "TPU v4": 1228e9,
    "TPU v5 lite": 819e9,
    "TPU v5e": 819e9,
    "TPU v5": 2765e9,    # v5p
    "TPU v5p": 2765e9,
    "TPU v6 lite": 1640e9,
    "TPU v6e": 1640e9,
    "TPU v7": 7370e9,
}

def _lookup_kind(device, table) -> float | None:
    """Longest-prefix match of `device`'s kind against a peaks table
    ("TPU v5 lite" beats "TPU v5"); None when unknown (CPU meshes)."""
    import jax

    if device is None:
        devs = jax.devices()
        if not devs:
            return None
        device = devs[0]
    kind = getattr(device, "device_kind", "")
    best = None
    for name, val in table.items():
        if kind.startswith(name):
            if best is None or len(name) > best[0]:
                best = (len(name), val)
    return None if best is None else best[1]


def device_peak_flops(device=None, dtype: str = "bf16") -> float | None:
    """Peak FLOP/s of one JAX device of `device`'s kind (default:
    jax.devices()[0]). "Device" is a whole chip on v4+ and a single
    TensorCore on v2/v3 (see _PEAKS_BF16) — the right denominator for
    per-device throughput either way.

    Returns None when the device kind is unknown (CPU test meshes) —
    callers should then skip MFU reporting rather than invent a peak.
    """
    p = _lookup_kind(device, _PEAKS_BF16)
    if p is None:
        return None
    if dtype in ("f32", "float32", "fp32"):
        return p / 8.0  # multi-pass MXU emulation; measured-practical
    if dtype in ("fp8", "float8", "e4m3", "float8_e4m3fn"):
        # dense fp8 runs the MXU at 2x its bf16 rate on generations
        # that support it natively (see the v7 entry's 4.6PF -> 2.3
        # note)
        return p * 2.0
    return p


def device_mem_bandwidth(device=None) -> float | None:
    """Peak HBM bytes/s of one JAX device (None off-TPU) — the
    denominator for memory-roofline utilization (decode sweeps)."""
    return _lookup_kind(device, _HBM_BPS)


def _avg_causal_context(seq_len: int, window: int = 0) -> float:
    """Average number of visible key positions per query under causal
    masking, optionally with a sliding window of `window` positions."""
    t = seq_len
    if window and window < t:
        w = window
        # positions 0..w-1 see i+1 keys; positions w-1..t-1 see w keys
        return (w * (w + 1) / 2 + (t - w) * w) / t
    return (t + 1) / 2


def transformer_flops_per_token(cfg, seq_len: int,
                                include_backward: bool = True) -> float:
    """Exact matmul FLOPs per token for one train (fwd+bwd) or fwd step.

    Counts every projection, the FFN (dense gelu/swiglu or top-k MoE),
    the attention score/value matmuls (causal-averaged, window-aware),
    and the vocab head. Norms/softmax/rotary are vector ops — omitted,
    as is standard (they are HBM-bound, not MXU work).
    """
    d = cfg.d_model
    # one source of truth with transformer.init (ADVICE r2: a hardcoded
    # 4*d here would silently misreport MFU if d_ff ever diverges)
    ff = cfg.ffn_dim
    per_layer = 0.0
    # attention projections
    if cfg.gqa:
        per_layer += 2.0 * d * d                            # q proj
        per_layer += 2.0 * d * (2 * cfg.kv_heads * cfg.head_dim)  # kv
    else:
        per_layer += 2.0 * d * 3 * d                        # fused qkv
    per_layer += 2.0 * d * d                                # out proj
    # attention itself: QK^T and AV are each 2*head_dim*ctx per head
    ctx = _avg_causal_context(seq_len, cfg.window)
    per_layer += 2 * (2.0 * cfg.n_heads * cfg.head_dim * ctx)
    # FFN
    if cfg.n_experts > 0:
        per_layer += 2.0 * d * cfg.n_experts                # router
        per_layer += cfg.moe_top_k * (2.0 * d * ff + 2.0 * ff * d)
    elif cfg.ffn == "swiglu":
        per_layer += 3 * 2.0 * d * ff                       # gate, up, down
    else:
        per_layer += 2 * 2.0 * d * ff                       # up, down
    total = cfg.n_layers * per_layer
    total += 2.0 * d * cfg.vocab                            # head logits
    if include_backward:
        total *= 3.0  # fwd + 2x bwd (PaLM appendix B convention)
    return total


def mfu(tokens_per_sec: float, cfg, seq_len: int,
        dtype: str = "bf16", device=None, n_devices: int | None = None,
        include_backward: bool = True, n_chips: int | None = None) -> dict:
    """Achieved TFLOP/s and fraction-of-peak for a measured throughput.

    `tokens_per_sec` is usually the GLOBAL rate; pass `n_devices` = the
    number of JAX devices producing it (the mesh size — on v2/v3 that
    counts TensorCores, matching the per-core table entries) so the
    denominator is the fleet peak, not one device's — otherwise a dp=4
    run reports 4x its true utilization. Returns {"tflops": achieved,
    "peak_tflops": fleet peak or None, "mfu": fraction or None}. MFU is
    None off-TPU (unknown peak)."""
    if n_chips is not None:  # deprecated pre-round-4 keyword
        import warnings

        warnings.warn("mfu(n_chips=...) is deprecated; pass n_devices",
                      DeprecationWarning, stacklevel=2)
        # None-sentinel default so an EXPLICIT n_devices=1 still
        # conflicts (1 being the old default must not mask it)
        if n_devices is not None and n_devices != n_chips:
            raise ValueError(
                f"both n_devices={n_devices} and n_chips={n_chips} "
                f"given and they disagree; pass only n_devices")
        n_devices = n_chips
    if n_devices is None:
        n_devices = 1
    fpt = transformer_flops_per_token(cfg, seq_len, include_backward)
    achieved = tokens_per_sec * fpt
    peak = device_peak_flops(device, dtype)
    if peak is not None:
        peak *= max(1, int(n_devices))
    return {
        "tflops": achieved / 1e12,
        "peak_tflops": None if peak is None else peak / 1e12,
        "mfu": None if peak is None else achieved / peak,
    }


# Back-compat alias (pre-round-4 name; the table was always per-device)
chip_peak_flops = device_peak_flops
