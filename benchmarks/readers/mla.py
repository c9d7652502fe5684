"""Layer `latent cache`: the share of a decode tick's least bytes that
is the latent cache, from the `latent_tokens` attr of the engine's
`decode` spans (cache rows the tick's live rows read in each latent
layer; mean over the window's ticks) and the configuration's arithmetic
(`harness/arith_latent_experts.py`). A program without the attr, or
shapes without a latent cache, give nothing."""
from harness import spanattrs


def read(metric, layers, trace, device):
    shapes = layers.get("shapes")
    rows = spanattrs.in_window(layers, "decode", "latent_tokens")
    if metric.split(".")[1] != "cache_byte_share" or not rows \
            or not hasattr(shapes, "kv_rank"):
        return None
    live = sum(rows) / len(rows)
    return 100.0 * live * shapes.kv_bytes_per_token() \
        / shapes.decode_step_min_bytes(live)
