"""Layer `experts`: what the routed layers' router did, as the engine's
`decode` spans carry it (attrs `experts_touched`, the distinct experts
the tick's live rows chose, mean a routed layer; `max_load`, the busiest
expert's count over the mean count, mean a routed layer). Each metric
is the mean over the window's decode ticks. A program without these
attrs gives nothing."""
from harness import spanattrs

ATTR = {"experts_touched": "experts_touched", "load_max_over_mean": "max_load"}


def read(metric, layers, trace, device):
    attr = ATTR.get(metric.split(".")[1])
    values = spanattrs.in_window(layers, "decode", attr) if attr else []
    return sum(values) / len(values) if values else None
