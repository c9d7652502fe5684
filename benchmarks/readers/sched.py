"""Layer `serving engine`, from the inside: the scheduler's host phases
as the engine's own spans record them (`engine.step` and its children;
`shallowspeed_tpu/serving/engine.py`). Each metric is the mean over the
window's steps, in ms. `host_ms` is a step less the spans that block on
the device (`*.fetch`); `admit`, `prep`, `dispatch` and `emit` are its
parts. `prep` is everything the host does around the two dispatches
that has no span of its own: `decode.prep` and the self time of
`decode` and of `prefill`. What the four leave of `host_ms` is the self
time of `engine.step`."""
from harness import progspans

PARTS = {
    "admit_ms": lambda s: progspans.total(s, "admit"),
    "prep_ms": lambda s: progspans.total(s, "decode.prep")
    + progspans.self_total(s, "decode") + progspans.self_total(s, "prefill"),
    "dispatch_ms": lambda s: progspans.total(
        s, "decode.dispatch", "prefill.dispatch", "prefill.sample"),
    "emit_ms": lambda s: progspans.total(s, "decode.emit"),
    "host_ms": lambda s: progspans.total(s, "engine.step")
    - progspans.total(s, "decode.fetch", "prefill.fetch"),
}


def read(metric, layers, trace, device):
    part = PARTS.get(metric.split(".")[1])
    spans = progspans.ring()
    win = progspans.window(layers, spans)
    if part is None or win is None:
        return None
    spans = progspans.inside(spans, *win)
    steps = sum(1 for e in spans if e[progspans.NAME] == "engine.step")
    return 1e3 * part(spans) / steps if steps else None
