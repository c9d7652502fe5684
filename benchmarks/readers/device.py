"""Layer `device`: busy and idle time from the trace, peak memory from
the backend."""


def read(metric, layers, trace, device):
    what = metric.split(".")[1]
    if what == "idle_share" and trace:
        return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
    if what == "peak_hbm_gb" and device.get("memory_peak_bytes"):
        return device["memory_peak_bytes"] / 1e9
    return None
