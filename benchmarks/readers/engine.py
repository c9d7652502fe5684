"""Layer `serving engine`: spans around `eng.step()`, the engine's
request records and counters."""
from harness import arith


def _step_ms(layers, prefill: bool):
    ms = [(s["t1"] - s["t0"]) * 1e3 for s in layers.get("steps", ())
          if s["prefill"] == prefill]
    return arith.median(ms) if ms else None


def read(metric, layers, trace, device):
    what = metric.split(".")[1]
    if what == "decode_step_ms":
        return _step_ms(layers, False)
    if what == "prefill_step_ms":
        return _step_ms(layers, True)
    if what == "slot_occupancy":
        steps = layers.get("steps")
        if not steps:
            return None
        return 100.0 * sum(s["decoding"] for s in steps) \
            / (len(steps) * layers["slots"])
    if what == "ttft_ms":
        ms = [r["ttft_ms"] + layers["late_ms"].get(r["id"], 0.0)
              for r in layers.get("records", ())]
        return arith.median(ms) if ms else None
    if what == "itl_p95_ms":
        gaps = layers.get("itl_ms")
        return arith.percentile(gaps, 95.0) if gaps else None
    return None
