"""Layer `runtime`: what `setup_s` is made of. Every instant from the
process's start (the `startup` entry's start) to the window's start
goes to the FIRST of `PARTS` that has an entry over it, and to
`unspanned_s` where none has: a `compile` inside a `build` is taken
from the `build`, and nested `trace` entries (a jitted function traced
inside another's trace) count once. So the seven metrics here and
`runtime.compile_s` are a partition: they add up to the window's start
less the process's start, which is the `notes` line's `setup_s` plus
the wait for the window's first step. `unspanned_s` is the benchmark's
own work (weights drawn on the device, data, the reference's loss, a
`block_until_ready` outside any span) and the sleeps of an open loop's
ramp. Where an open loop's window opens before its first arrival that
wait is seconds of the WINDOW, no entry marks its start, and it would
all go to `unspanned_s`: the manifest lists that metric for the cells
whose window opens on work, and the other parts are exact everywhere
(nothing of theirs runs while the loop sleeps). Silent where
`runtime.compile_s` is (no ring, no window, a ring that has dropped
anything) and where the program records no `startup`."""
from harness import progspans
from harness.progspans import NAME, PARENT, T0, T1


def _named(*names):
    return lambda e: e[NAME] in names


def _top_level_step(e):
    return e[NAME] in ("engine.step", "step") and e[PARENT] is None


# in the order in which they claim an instant
PARTS = (("compile_s", _named("compile")), ("lower_s", _named("lower")),
         ("trace_s", _named("trace")), ("backend_s", _named("backend.init")),
         ("build_s", _named("build")), ("step_s", _top_level_step),
         ("startup_s", _named("startup")))


def partition(spans, start: float, end: float) -> dict[str, float]:
    """Seconds of `[start, end]` by part, `unspanned_s` the rest: a
    sweep over the entries' edges, each stretch between two edges to
    the first part that has an entry open over it."""
    out = dict.fromkeys([part for part, _ in PARTS] + ["unspanned_s"], 0.0)
    edges = []
    for e in spans:
        k = next((k for k, (_, takes) in enumerate(PARTS) if takes(e)), None)
        a, b = max(e[T0], start), min(e[T1], end)
        if k is not None and b > a:
            edges += [(a, k, 1), (b, k, -1)]
    open_entries, at = [0] * len(PARTS), start
    for t, k, step in sorted(edges):
        first = next((i for i, n in enumerate(open_entries) if n), None)
        out["unspanned_s" if first is None else PARTS[first][0]] += t - at
        open_entries[k] += step
        at = t
    out["unspanned_s"] += end - at
    return out


def read(metric, layers, trace, device):
    spans = progspans.ring()
    win = progspans.window(layers, spans)
    start = next((e[T0] for e in spans if e[NAME] == "startup"), None)
    if win is None or start is None or progspans.dropped(spans):
        return None
    return partition(spans, start, win[0]).get(metric.split(".")[1])
