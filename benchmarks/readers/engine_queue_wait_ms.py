"""Layer `serving engine`: how long a request stood in the engine's
queue before a slot took it, on the engine's own clock (`wait_ms` of its
request records); median over the requests finished in the window."""
from harness import arith


def read(metric, layers, trace, device):
    waits = [r["wait_ms"] for r in layers.get("records", ()) if "wait_ms" in r]
    return arith.median(waits) if waits else None
