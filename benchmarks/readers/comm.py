"""Layer `collectives`: time of collective operations from the device
trace, device 0."""
from harness import tracered


def read(metric, layers, trace, device):
    what = metric.split(".")[1]
    if what == "collective_share" and trace:
        return 100.0 * tracered.collective_seconds(trace["devices"][0]) \
            / trace["window_s"]
    return None
