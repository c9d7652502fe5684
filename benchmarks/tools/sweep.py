"""Find a serving cell's parameters once, on the chip: run one cell
several times in one process (one start-up, one set of compiled
programs), each time with some traffic parameters overridden, and print
one line per run. Used to find the knee of an open-loop mix:

    python3 benchmarks/tools/sweep.py --workload olmo-1b.chat --seconds 20 \
        --set arrivals.rate_per_s=0.5,1,1.5,2,3

The knee is the highest rate whose backlog (`pending_at_end`) is no more
than the slots; the cell's file then takes 0.8 x knee. Not part of a
benchmark run: `run.py` never reads this file."""

from __future__ import annotations

import argparse
import copy
import itertools
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent.parent
LAYER_METRICS = ("engine.decode_step_ms", "engine.prefill_step_ms",
                 "engine.slot_occupancy", "engine.ttft_ms")
sys.path[:0] = [str(HERE), str(HERE.parent)]


def main() -> int:
    import run as bench
    from harness import model, traffic
    from shallowspeed_tpu import runtime

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--set", action="append", default=[],
                    help="dotted.key=v1,v2,... of the traffic file")
    args = ap.parse_args()
    manifest = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    cell = next(w for w in manifest["workloads"] if w["name"] == args.workload)
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config = model.load_config(HERE.parent / entry["file"])
    mix = traffic.load_traffic(HERE / "traffic" / f"{cell['traffic']}.json")
    runtime.enable_compile_cache()
    stamp = runtime.device_stamp()
    if stamp["platform"] != "tpu":
        print(f"a sweep needs the chip; JAX found {stamp}", file=sys.stderr)
        return bench.NO_DEVICE
    driver = bench.load_module(HERE / "drivers" / f"{mix['driver']}.py")
    keys = [s.split("=", 1)[0] for s in args.set]
    grids = [s.split("=", 1)[1].split(",") for s in args.set]
    for combo in itertools.product(*grids):
        t = copy.deepcopy(mix)
        for key, raw in zip(keys, combo):
            node, *path = t, *key.split(".")
            for part in path[:-1]:
                node = node[part]
            old = node[path[-1]]
            node[path[-1]] = type(old)(raw) if not isinstance(old, bool) \
                else raw == "true"
        job = bench.Job(config, t, args.seed, args.seconds, cell["chips"], None)
        out = driver.run(job)
        print(json.dumps({
            "set": dict(zip(keys, combo)), "correct": out["correct"],
            **out["end_to_end"],
            **{k: out["notes"].get(k) for k in
               ("pending_at_end", "finished_in_window", "worst_logit_gap")},
            **{name: bench.find_reader(name).read(name, out["layers"], {}, stamp)
               for name in LAYER_METRICS},
            "peak_gb": out["memory_peak_bytes"] / 1e9}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
