"""Layer `train engine`: step completions on the host clock."""
from harness import arith, peaks


def read(metric, layers, trace, device):
    what = metric.split(".")[1]
    if what == "step_ms":
        ms = layers.get("step_ms")
        return arith.median(ms) if ms else None
    if what == "mfu":
        peak = peaks.peaks_for(device["kind"])["bf16_flops"]
        flops = layers["shapes"].train_flops_per_token(layers["seq_len"])
        return 100.0 * layers["tok_s_chip"] * flops / peak
    return None
