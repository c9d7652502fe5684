"""Layer `runtime`: JAX's compile path as the program's recorder sees it
through `jax.monitoring`, before the window opens (set-up). `compile_s`
is the seconds inside backend compiles, a fetch from the persistent
cache included; `cache_misses` counts the programs that cache did not
hold. Set-up's entries are the ring's oldest, so once the ring has
dropped any, both metrics are silent: a part of them would read as a
warm start."""
from harness import progspans


def read(metric, layers, trace, device):
    spans = progspans.ring()
    win = progspans.window(layers, spans)
    if win is None or progspans.dropped(spans):
        return None
    before = [e for e in spans if e[progspans.T1] <= win[0]]
    what = metric.split(".")[1]
    if what == "compile_s":
        return progspans.total(before, "compile")
    if what == "cache_misses":
        return float(sum(1 for e in before if e[progspans.NAME] == "cache_miss"))
    return None
