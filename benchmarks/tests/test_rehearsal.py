"""End-to-end rehearsals of both drivers at a toy size on the CPU: the
shape of the last line, and that a CPU run fails when it is asked for
what only the chip can give."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent.parent
TOY = Path(__file__).resolve().parent / "data" / "toy" / "BENCHMARK.json"


def bench(workload, *extra, trace=0, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run(
        [sys.executable, "benchmarks/run.py", "--manifest", str(TOY),
         "--workload", workload, "--seed", str(2**31 + 11), "--seconds", "2",
         "--trace", str(trace), *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["device"]["platform"] == "cpu"       # named for what it is
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] is not None
    return line


@pytest.mark.parametrize("workload,metric,devices", [
    ("toy-olmo.chat", "tpot_ms", 1),
    ("toy-mistral.batch", "serve_out_tok_s", 1),
    ("toy-olmo.sft", "train_tok_s_chip", 1),
    ("toy-olmo.sft-dp4", "train_tok_s_chip", 4),
])
def test_end_to_end_line(workload, metric, devices):
    line = last_line(bench(workload, "--rehearse", devices=devices))
    assert set(line["metrics"]) == {"setup_s", metric}
    assert line["metrics"][metric]["value"] > 0
    assert line["device"]["count"] == devices


def test_traced_serving_line_has_layer_metrics_and_no_device_metric():
    line = last_line(bench("toy-olmo.chat", "--rehearse", trace=1))
    names = set(line["metrics"])
    assert {"engine.decode_step_ms.chat", "engine.itl_p95_ms",
            "engine.ttft_ms", "drivers.gen_late_ms"} <= names
    assert line["metrics"]["drivers.compiles.chat"]["value"] == 0
    # the CPU has no device trace: nothing is written under those names
    assert not {n for n in names if n.startswith(("kernels.", "device.idle"))}
    assert "busy_s" not in line["device"] and "breakdown" not in line


@pytest.mark.parametrize("workload", ["toy-olmo.chat", "toy-olmo.sft"])
def test_without_a_tpu_the_run_fails_and_prints_no_result(workload):
    proc = bench(workload)
    assert proc.returncode not in (0, None)
    assert "needs 1 TPU chip" in proc.stderr
    assert '"correct"' not in proc.stdout


def test_a_share_of_the_chips_peak_is_refused_on_the_cpu():
    proc = bench("toy-olmo.sft", "--rehearse", trace=1)
    assert proc.returncode != 0 and '"correct"' not in proc.stdout
    assert "no published peaks for device kind 'cpu'" in proc.stderr
