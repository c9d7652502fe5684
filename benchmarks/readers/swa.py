"""Layer `window cache`: what the cache's layer groups hold and read, from
the attrs of the engine's `decode` spans (`window_blocks`, the blocks the
tick's live rows hold in the window group; `blocks_read_window` and
`blocks_read_full`, the pool blocks one layer of each group walked) and
the steps the driver recorded. Each metric is the mean over the window's
decode ticks. A program without these attrs (a commit from before the
groups), or a model without a window group, gives nothing.

- `held_share`: the window group's held blocks over the blocks a cache
  that never releases would hold for the same rows (`full_blocks`: the
  full group holds every block of a context), in %.
- `read_byte_share`: the keys and values the tick read, every layer of
  both groups, over the tick's least bytes (the configuration's
  arithmetic), in %: the twin of `mla.cache_byte_share`."""
from harness import spanattrs


def _mean(layers, attr):
    values = spanattrs.in_window(layers, "decode", attr)
    return sum(values) / len(values) if values else None


def read(metric, layers, trace, device):
    what = metric.split(".")[1]
    shapes = layers.get("shapes")
    if what == "held_share":
        held, never = _mean(layers, "window_blocks"), _mean(layers, "full_blocks")
        return 100.0 * held / never if held is not None and never else None
    if what == "read_byte_share" and hasattr(shapes, "window_layers"):
        win, full = (_mean(layers, f"blocks_read_{g}")
                     for g in ("window", "full"))
        steps = [s for s in layers.get("steps", ()) if s["decoding"]]
        if win is None or full is None or not steps:
            return None
        live = sum(s["live_tokens"] for s in steps) / len(steps)
        block_bytes = layers["block_size"] * shapes.kv_bytes_per_token_layer()
        read_bytes = block_bytes * (win * shapes.window_layers
                                    + full * shapes.full_layers)
        return 100.0 * read_bytes / shapes.decode_step_min_bytes(live)
    return None
