"""The benchmark's entry point: one cell, one run, one JSON line.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Driven by data: the cell, its configuration file and its traffic file are
found by name in `BENCHMARK.json`; the traffic file names the driver
(`drivers/<name>.py`); each per-layer metric is read by the reader its
name leads to (`readers/<layer>_<metric>.py`, else `readers/<layer>.py`).
Nothing here names a cell, a configuration or a metric.

With `--trace 0` the line carries the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics, the device's busy time and the
breakdown; a traced run profiles the last TRACE_SECONDS of the window.
A run that finds no TPU, or fewer chips than the cell asks for, fails.
`--rehearse` lets the control flow run on the CPU at a toy size (tests);
such a line names the CPU as its device and carries no device metric."""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
from functools import lru_cache
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
TRACE_SECONDS = 8.0
NO_DEVICE = 3
SETUP_SPANS = ("weights", "make_data", "engine", "reference", "warm", "fill",
               "check")


def process_age_s() -> float:
    """Seconds since this process was started, from the kernel's record."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


@lru_cache(maxsize=None)
def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.parent.name + "_" + path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_reader(metric: str):
    parts = metric.split(".")
    for stem in ("_".join(parts[:2]), parts[0]):
        path = HERE / "readers" / f"{stem}.py"
        if path.exists():
            return load_module(path)
    return None


def metrics_of(manifest: dict, section: str, cell: str) -> list[dict]:
    return [m for m in manifest[section]
            if "workloads" not in m or cell in m["workloads"]]


class Job:
    """What a driver is given, and the hooks it calls around its window."""

    def __init__(self, config, traffic, seed, seconds, chips, trace_dir):
        from harness.spans import Recorder

        self.config, self.traffic = config, traffic
        self.seed, self.seconds, self.chips = seed, seconds, chips
        self.trace_dir = trace_dir          # None = not a traced run
        self.recorder = Recorder(annotate=trace_dir is not None)
        self.setup_s = None
        self.reserved = None
        self._window_note = None

    def window_opens(self, t_origin: float):
        self.setup_s = process_age_s() - (self.recorder.clock() - t_origin)

    def on_loop(self, now: float):
        if self.trace_dir is None or self._window_note is not None \
                or now < self.seconds - TRACE_SECONDS:
            return
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._window_note = jax.profiler.TraceAnnotation("bench:window")
        self._window_note.__enter__()

    def window_closes(self):
        if self._window_note is not None:
            import jax

            self._window_note.__exit__(None, None, None)
            jax.profiler.stop_trace()

    def memory_peak(self) -> int:
        """`peak_bytes_in_use` of the fullest chip. It counts buffers and
        leaves out what the backend reserves for the compiled programs'
        scratch (`peak_bytes_reserved`, 10.7 GB in a 1.2 B train step),
        which the `notes` line prints beside it: the two peaks need not
        coincide, and their sum passed the chip's limit on four chips."""
        import jax

        stats = [d.memory_stats() or {} for d in jax.devices()[:self.chips]]
        self.reserved = max(s.get("peak_bytes_reserved", 0) for s in stats)
        return int(max(s.get("peak_bytes_in_use", 0) for s in stats))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest", default=str(ROOT / "BENCHMARK.json"))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    manifest_path = Path(args.manifest).resolve()
    base = manifest_path.parent
    with open(manifest_path) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    config_entry = next(c for c in manifest["configs"]
                        if c["name"] == cell["config"])
    sys.path[:0] = [str(HERE), str(ROOT)]

    from harness import model, tracered, traffic
    from shallowspeed_tpu import runtime

    # configs/<config>.json is named by the manifest; the traffic mix is
    # traffic/<traffic>.json beside that configs directory
    config_file = base / config_entry["file"]
    config = model.load_config(config_file)
    mix = traffic.load_traffic(config_file.parent.parent / "traffic"
                               / f"{cell['traffic']}.json")

    cache_dir = runtime.enable_compile_cache()
    stamp = runtime.device_stamp()
    if not args.rehearse and (stamp["platform"] != "tpu"
                              or stamp["count"] < cell["chips"]):
        print(f"workload {cell['name']} needs {cell['chips']} TPU chip(s); "
              f"JAX found {stamp}", file=sys.stderr)
        return NO_DEVICE
    print(json.dumps({"event": "start", "device": stamp,
                      "compile_cache": cache_dir}), flush=True)

    trace_dir = None
    if args.trace:
        trace_dir = str(ROOT / "chiprun_out" / "traces" / cell["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
    job = Job(config, mix, args.seed, args.seconds, cell["chips"], trace_dir)
    driver = load_module(HERE / "drivers" / f"{mix['driver']}.py")
    out = driver.run(job)

    device = {"platform": stamp["platform"], "kind": stamp["kind"],
              "count": stamp["count"],
              "memory_peak_bytes": out["memory_peak_bytes"]}
    line = {"correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": {}, "device": device}
    if not args.trace:
        values = dict(out["end_to_end"], setup_s=job.setup_s)
        for m in metrics_of(manifest, "end_to_end", cell["name"]):
            if m["name"] in values:
                line["metrics"][m["name"]] = {"value": values[m["name"]],
                                              "unit": m["unit"]}
    else:
        summary = {}
        found = tracered.find_xplane(trace_dir)
        if found:
            summary = tracered.summarize(tracered.load(found), cell["chips"])
        shutil.rmtree(trace_dir, ignore_errors=True)   # tens of MB a run
        for m in metrics_of(manifest, "per_layer", cell["name"]):
            reader = find_reader(m["name"])
            value = reader.read(m["name"], out["layers"], summary, device) \
                if reader else None
            if value is not None:
                line["metrics"][m["name"]] = {"value": value,
                                              "unit": m["unit"]}
        if summary:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            line["breakdown"] = {"device_ops": summary["device_ops"],
                                 "idle_gaps": summary["idle_gaps"]}
    print(json.dumps({"event": "notes", "setup_s": job.setup_s,
                      "peak_bytes_reserved": job.reserved,
                      "spans_s": {n: round(b - a, 3) for n, a, b
                                  in job.recorder.spans
                                  if n in SETUP_SPANS},
                      **out["notes"]}), flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
