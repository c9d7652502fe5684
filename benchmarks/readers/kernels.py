"""Layer `kernels`: the compiled programs' device time against the least
time the chip could take, from shapes (arithmetic in `harness/arith.py`)
and the published peaks."""
from harness import arith, peaks, tracered


def _program_seconds(layers, trace, key):
    if not trace or key not in layers["programs"]:
        return None
    d = tracered.module_durations(trace["devices"][0]["modules"],
                                  layers["programs"][key])
    return arith.median(d) if d else None


def read(metric, layers, trace, device):
    what = metric.split(".")[1]
    if what == "decode_roofline":
        took = _program_seconds(layers, trace, "decode")
        steps = [s for s in layers.get("steps", ()) if s["decoding"]]
        if took is None or not steps:
            return None
        live = sum(s["live_tokens"] for s in steps) / len(steps)
        least = layers["shapes"].decode_step_min_bytes(live) \
            / peaks.peaks_for(device["kind"])["hbm_bytes_per_s"]
        return 100.0 * least / took
    if what == "train_step_roofline":
        took = _program_seconds(layers, trace, "step")
        if took is None:
            return None
        flops = layers["shapes"].train_flops_per_token(layers["seq_len"]) \
            * layers["tokens_per_step"] / layers["chips"]
        return 100.0 * flops / peaks.peaks_for(device["kind"])["bf16_flops"] \
            / took
    return None
