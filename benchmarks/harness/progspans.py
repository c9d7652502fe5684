"""The program's own spans, read from inside its process.

`shallowspeed_tpu.telemetry.trace` keeps every closed span in a bounded
ring of tuples `(seq, parent_seq, name, t0, t1, attrs, ...)` on
`time.perf_counter`, the clock of the benchmark's `Recorder` too, and
the readers are loaded into the program's process, so they take the
ring as it stands after the run. A program without the ring (a commit
from before it) gives no spans, and a reader that finds none returns
nothing. The ring is bounded: once it has dropped its oldest entries a
reader gives nothing rather than a part (`dropped`, `window`)."""

from __future__ import annotations

SEQ, PARENT, NAME, T0, T1, ATTRS = range(6)


def ring() -> list[tuple]:
    try:
        from shallowspeed_tpu.telemetry import trace
    except ImportError:
        return []
    take = getattr(trace.tracer(), "ring", None)
    return take() if callable(take) else []


def dropped(spans: list[tuple]) -> int:
    """How many spans the ring has let go of, given what it holds now."""
    from shallowspeed_tpu.telemetry import trace

    return max(0, trace.tracer().event_count - len(spans))


def window(layers: dict, spans: list[tuple]) -> tuple[float, float] | None:
    """The measured window on the ring's clock. Serving: from the start
    of the window's first `eng.step()` to the end of its last, as the
    driver stamped them. Training: the driver runs no step after its
    window, so its steps are the last `len(step_ms) + 1` top-level
    `step` spans. None where the ring no longer holds the whole window:
    the ring is ordered by close, so it holds every span that closed
    after its oldest entry did."""
    win = None
    if layers.get("steps"):
        win = layers["steps"][0]["t0"], layers["steps"][-1]["t1"]
    elif layers.get("step_ms"):
        want = len(layers["step_ms"]) + 1
        steps = [e for e in spans if e[NAME] == "step" and e[PARENT] is None]
        if len(steps) >= want:
            win = steps[-want][T0], steps[-1][T1]
    if win is None or not spans:
        return None
    if dropped(spans) and spans[0][T1] > win[0]:
        return None
    return win


def inside(spans: list[tuple], t0: float, t1: float) -> list[tuple]:
    return [e for e in spans if e[T0] >= t0 and e[T1] <= t1]


def total(spans: list[tuple], *names: str) -> float:
    """Seconds of the named spans, children included."""
    return sum(e[T1] - e[T0] for e in spans if e[NAME] in names)


def self_total(spans: list[tuple], name: str) -> float:
    """Seconds of the named spans less the part their child spans cover
    (children of one parent run on its thread, one after another)."""
    own = {e[SEQ]: e for e in spans if e[NAME] == name}
    covered = 0.0
    for e in spans:
        parent = own.get(e[PARENT])
        if parent is not None:
            covered += max(0.0, min(e[T1], parent[T1]) - max(e[T0], parent[T0]))
    return total(list(own.values()), name) - covered
