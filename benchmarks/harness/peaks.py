"""Published peaks of the chips the benchmark may run on, keyed by the
`device_kind` JAX reports. A device that is not here is an error: a
roofline share against a guessed peak is worse than none."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of
    # HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect.
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9, "ici_bytes_per_s": 200e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def peaks_for(device_kind: str) -> dict:
    """The peaks of one chip of `device_kind`; raises for an unknown one."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add them "
            f"to benchmarks/harness/peaks.py with their source") from None
