"""Layer `state cache`: the two kinds of state a decode tick moves in a
model whose blocks hold a state-space mixer beside their attention
heads, each as a share of the tick's least bytes (the configuration's
arithmetic, `harness/arith_state_space.py`), in %, mean over the
window's decode ticks. A program without these attrs (a commit from
before the slabs), or shapes without a mixer, give nothing.

- `state_byte_share`: the slab bytes the tick read plus wrote, from the
  `state_bytes` attr of the engine's `decode` spans (the host's own
  count: rows that decode x a row's state x layers x 2).
- `cache_byte_share`: the keys and values the tick's rows read, from the
  spans' `blocks_read` (pool blocks one layer walked), every layer: the
  twin of `mla.cache_byte_share` and `swa.read_byte_share`."""
from harness import spanattrs


def _mean(layers, attr):
    values = spanattrs.in_window(layers, "decode", attr)
    return sum(values) / len(values) if values else None


def read(metric, layers, trace, device):
    what = metric.split(".")[1]
    shapes = layers.get("shapes")
    steps = [s for s in layers.get("steps", ()) if s["decoding"]]
    if not hasattr(shapes, "ssm_step_bytes") or not steps:
        return None
    live = sum(s["live_tokens"] for s in steps) / len(steps)
    least = shapes.decode_step_min_bytes(live)
    if what == "state_byte_share":
        moved = _mean(layers, "state_bytes")
        return None if moved is None else 100.0 * moved / least
    if what == "cache_byte_share":
        blocks = _mean(layers, "blocks_read")
        if blocks is None or _mean(layers, "state_bytes") is None:
            return None
        return 100.0 * blocks * layers["block_size"] \
            * shapes.kv_bytes_per_token() / least
    return None
