"""Layer `train engine`: the host's time in one `train_batch_async`
call (placing the batch and dispatching the step; the driver blocks
elsewhere), from the engine's own `step` spans; mean over the window's
steps, in ms."""
from harness import progspans


def read(metric, layers, trace, device):
    spans = progspans.ring()
    win = progspans.window(layers, spans)
    if win is None:
        return None
    steps = [e for e in progspans.inside(spans, *win)
             if e[progspans.NAME] == "step" and e[progspans.PARENT] is None]
    return 1e3 * progspans.total(steps, "step") / len(steps) if steps else None
