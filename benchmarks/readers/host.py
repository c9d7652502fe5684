"""Layer `runtime`: the host's stalls inside the window, from the
program's own entries in the tracer's ring. A stall is an
`engine.step` longer than `STALL_FACTOR` times the window's median
step: a step that holds a prefill chunk is 2.2-3.3 x a tick's step and
no stall, the stalls the ledger's spreads come from are 6.5-35 x.
`stall_ms` is what the stalls took from the window, each step's
duration less the median; `stall_gc_ms` the `gc` entries (collections
of a millisecond or more) that lie inside those steps: how much of
`stall_ms` a collection explains. Both in ms over the whole window. A
program that records no `startup` watches no collections either (one
watch installs both), and gives no `stall_gc_ms`."""
from harness import arith, progspans
from harness.progspans import NAME, T0, T1

STALL_FACTOR = 5.0


def read(metric, layers, trace, device):
    ring = progspans.ring()
    win = progspans.window(layers, ring)
    if win is None:
        return None
    spans = progspans.inside(ring, *win)
    steps = [e for e in spans if e[NAME] == "engine.step"]
    if not steps:
        return None
    median = arith.median([e[T1] - e[T0] for e in steps])
    stalls = [e for e in steps if e[T1] - e[T0] > STALL_FACTOR * median]
    what = metric.split(".")[1]
    if what == "stall_ms":
        return 1e3 * sum(e[T1] - e[T0] - median for e in stalls)
    if what == "stall_gc_ms" and any(e[NAME] == "startup" for e in ring):
        gcs = [e for e in spans if e[NAME] == "gc"]
        return 1e3 * sum(max(0.0, min(g[T1], s[T1]) - max(g[T0], s[T0]))
                         for s in stalls for g in gcs)
    return None
