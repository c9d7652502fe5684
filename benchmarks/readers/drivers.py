"""Layer `drivers`: the benchmark's own generator and loop."""
from harness import arith


def read(metric, layers, trace, device):
    what = metric.split(".")[1]
    if what == "gen_late_ms":
        late = [layers["late_ms"][i] for i in layers.get("due_in_window", ())
                if i in layers["late_ms"]]
        return arith.median(late) if late else None
    if what == "compiles":
        return float(layers["compiles"])
    return None
