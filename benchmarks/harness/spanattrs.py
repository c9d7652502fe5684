"""Attributes of the program's spans, read from the tracer's ring
(`harness/progspans.py`): what the engine says of a program run on the
span around it (`shallowspeed_tpu/serving/engine.py:_layer_attrs`)."""
from harness import progspans


def in_window(layers: dict, span: str, attr: str) -> list:
    """The named attr of every span of that name inside the measured
    window; empty where the program sets no such attr (a commit from
    before it) or the ring no longer holds the window."""
    spans = progspans.ring()
    win = progspans.window(layers, spans)
    if win is None:
        return []
    return [e[progspans.ATTRS][attr] for e in progspans.inside(spans, *win)
            if e[progspans.NAME] == span and attr in e[progspans.ATTRS]]
