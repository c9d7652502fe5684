"""chip_smoke.py off the chip: it refuses to run, and its phases hold at
toy width.

The smoke's contract is "exit 0 only on a TPU, with every check
passing". What the CPU can show of that: `main()` exits non-zero here
and names the platform it found; the SAME train and serve phases, driven
from the same size table at toy width with the kernels interpreted, pass
their checks (loss falls, every request answered, allocator balanced,
executables flat between the waves), and the checks themselves reject
what they must; the compile-cache helper leaves JAX's configuration
alone when `JAX_COMPILATION_CACHE_DIR` is set and otherwise points at
the one in-checkout path; and what the router and the elastic supervisor
construct in their parent process initialises no JAX backend.
"""

import os
import subprocess
import sys
from pathlib import Path

import chip_smoke

ROOT = Path(__file__).resolve().parents[1]
CPU_ENV = {**os.environ, "JAX_PLATFORMS": "cpu"}


# ------------------------------------------------------ refuses the CPU


def test_main_exits_nonzero_on_cpu_and_names_it(monkeypatch, capsys):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    rc = chip_smoke.main([])
    cap = capsys.readouterr()
    assert rc != 0
    assert "platform 'cpu'" in cap.err
    # the first thing reported is the device; no JSON result follows it
    assert cap.out.splitlines()[0].startswith("device: cpu")
    assert not any(line.startswith("{") for line in cap.out.splitlines())


def test_last_stdout_line_is_the_result_and_nothing_more(
        monkeypatch, capsys, tmp_path):
    """What reads the smoke reads its LAST line: one JSON object with
    exactly `ok` and `device` {platform, kind, count}. Everything else
    the run found goes on the `summary:` line before it."""
    import json

    from shallowspeed_tpu import runtime

    stamp = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(runtime, "probe_device_stamp", lambda: stamp)
    phase = {"ok": True, "failed": [], "wall_s": 0.0, "tokens": {}}
    for name in ("run_train_phase", "run_serve_phase", "run_kernels_phase"):
        monkeypatch.setattr(chip_smoke, name,
                            lambda *a, **kw: dict(phase))
    assert chip_smoke.main(["--out", str(tmp_path)]) == 0
    lines = capsys.readouterr().out.splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": stamp}
    assert lines[-2].startswith("summary: ")
    assert json.loads((tmp_path / "summary.json").read_text())["ok"] is True

    monkeypatch.setattr(chip_smoke, "run_kernels_phase", lambda *a, **kw: {
        **phase, "ok": False, "failed": ["out of tolerance"]})
    assert chip_smoke.main(["--out", str(tmp_path)]) == 1
    last = capsys.readouterr().out.splitlines()[-1]
    assert json.loads(last) == {"ok": False, "device": stamp}


# ------------------------------------------------ the phases, toy width


def test_train_phase_holds_at_toy_width(tmp_path):
    res = chip_smoke.run_train_phase("toy", tmp_path, on_tpu=False,
                                     env=CPU_ENV)
    assert res["ok"], res["failed"]
    steps = chip_smoke.SIZES["toy"]["train"]["steps"]
    assert len(res["loss"]) == steps and res["loss"][-1] < res["loss"][0]
    assert res["last_step"]["recompiles"] == 0
    # kernels interpreted off the chip: nothing lowered to Mosaic
    assert res["mosaic_programs"] == []


def test_serve_phase_holds_at_toy_width(tmp_path):
    res = chip_smoke.run_serve_phase("toy", tmp_path, config="int8",
                                     platform="cpu", env=CPU_ENV)
    assert res["ok"], res["failed"]
    w1, w2 = chip_smoke.make_requests("toy", 21)
    assert sorted(res["tokens"]) == sorted(r["id"] for r in w1 + w2)
    assert all(len(t) == chip_smoke.SIZES["toy"]["requests"]["max_new"]
               for t in res["tokens"].values())
    # more than one table-width bucket was visited, and the second
    # wave still compiled nothing (run_serve_phase compared the runs)
    assert res["executables"]["decode_tick"] >= 2


# ----------------------------------------- the checks reject what they must


def _serve_events(reqs, **over):
    events = [{"event": "device", "platform": "tpu", "kind": "TPU v5 lite",
               "count": 1}]
    events += [{"event": "result", "id": r["id"],
                "tokens": [1] * r["max_new"]} for r in reqs]
    events.append({"event": "summary", "pending_at_exit": 0,
                   "blocks_free_at_drain": "100/127",
                   "blocks_cold_at_drain": 27, **over})
    return events


def test_check_serve_accepts_a_clean_run_and_rejects_each_defect():
    reqs, _ = chip_smoke.make_requests("chip", 21)
    assert chip_smoke.check_serve(_serve_events(reqs), reqs,
                                  platform="tpu") == []
    # a server that fell back to the CPU
    bad = chip_smoke.check_serve(_serve_events(reqs), reqs, platform="cpu")
    assert any("expected platform" in m for m in bad)
    # serve.py exits 0 on error lines by design; the smoke does not
    ev = _serve_events(reqs) + [{"event": "error", "id": "x",
                                 "error": "ValueError: too long"}]
    assert any("error line" in m
               for m in chip_smoke.check_serve(ev, reqs, platform="tpu"))
    # a request cut short, and one never answered
    ev = _serve_events(reqs)
    ev[1]["tokens"] = ev[1]["tokens"][:-1]
    del ev[2]
    bad = chip_smoke.check_serve(ev, reqs, platform="tpu")
    assert any("tokens, expected" in m for m in bad)
    assert any("no result" in m for m in bad)
    # a leaked block: free + cold != usable
    ev = _serve_events(reqs, blocks_cold_at_drain=26)
    assert any("unbalanced" in m
               for m in chip_smoke.check_serve(ev, reqs, platform="tpu"))


def _step_lines(losses, **over):
    return [{"event": "step", "step": i, "loss": x, "tflops": 100.0,
             "mfu": 0.5, "compiles": 1, "recompiles": 0, **over}
            for i, x in enumerate(losses)]


def test_check_train_accepts_a_clean_run_and_rejects_each_defect():
    kw = dict(steps=3, on_tpu=True, mosaic=["jax_ir9_jit__step_compile"],
              summary=None)
    assert chip_smoke.check_train(_step_lines([6.0, 5.9, 5.5]), **kw) == []
    assert any("did not fall" in m for m in chip_smoke.check_train(
        _step_lines([6.0, 5.9, 6.1]), **kw))
    assert any("non-finite" in m for m in chip_smoke.check_train(
        _step_lines([6.0, float("nan"), 5.0]), **kw))
    lines = _step_lines([6.0, 5.9, 5.5])
    lines[2]["recompiles"] = 1
    assert any("RECOMPILES" in m
               for m in chip_smoke.check_train(lines, **kw))
    # an unknown device_kind prices no peak: the MFU field comes back None
    assert any("MFU" in m for m in chip_smoke.check_train(
        _step_lines([6.0, 5.9, 5.5], mfu=None), **kw))
    # kernels that never reached Mosaic
    assert any("tpu_custom_call" in m for m in chip_smoke.check_train(
        _step_lines([6.0, 5.9, 5.5]), **{**kw, "mosaic": []}))
    # four devices asked for, one holding almost nothing
    summary = {"hbm_live_per_device": {"d0": 100, "d1": 100, "d2": 100,
                                       "d3": 1},
               "device_stats": {f"d{i}": {"bytes_in_use": 100}
                                for i in range(4)}}
    assert any("under a fifth" in m for m in chip_smoke.check_train(
        _step_lines([6.0, 5.9, 5.5]),
        **{**kw, "summary": summary, "n_devices": 4}))


# ------------------------------------------------------- compile cache


def test_compile_cache_helper_respects_the_environment(monkeypatch):
    import jax

    from shallowspeed_tpu import runtime

    before = jax.config.jax_compilation_cache_dir
    min_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    regex = jax.config.jax_hlo_source_file_canonicalization_regex
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        assert runtime.enable_compile_cache() == "/somewhere/else"
        # placed from outside: the program set nothing in code
        assert jax.config.jax_compilation_cache_dir == before

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = str(ROOT / ".jax_cache")
        assert runtime.enable_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
        assert runtime.compile_cache_dir() == fixed
        # a source location names a file from the checkout's root down,
        # so a kernel's serialized locations do not carry the
        # checkout's path into the cache key
        import re
        assert re.sub(jax.config.jax_hlo_source_file_canonicalization_regex,
                      "", runtime.__file__) == "shallowspeed_tpu/runtime.py"
    finally:
        jax.config.update("jax_hlo_source_file_canonicalization_regex", regex)
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          min_secs)
    # git must not see what lands there
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


# ----------------------------------------- parents stay off the backend


def test_router_and_supervisor_parents_initialise_no_backend(tmp_path):
    """A parent that has touched JAX holds the chip and starves the
    children that need it. Build what `router.py` main() and `python -m
    shallowspeed_tpu.elastic` build in their own process — in a fresh
    interpreter — and look at JAX's backend table afterwards."""
    code = f"""
import sys
sys.path.insert(0, {str(ROOT)!r})
import router
from shallowspeed_tpu import chaos, runtime
from shallowspeed_tpu.elastic import GangSupervisor, Supervisor
from shallowspeed_tpu.metrics import MetricsLogger
from shallowspeed_tpu.serving.router import ReplicaProc, Router
from shallowspeed_tpu.telemetry import profiler
from shallowspeed_tpu.telemetry.fleet import FleetCollector
from shallowspeed_tpu.telemetry.monitor import StatusServer
from serve import load_requests

args = router.parse_args(["--replicas", "2", "--profile", "host",
                          "--log-file", {str(tmp_path / "r.jsonl")!r}])
metrics = MetricsLogger(args.log_file, kind="router")
collector = FleetCollector()
srv = StatusServer(collector, port=0)
plane = profiler.from_args(args, metrics, out_dir={str(tmp_path)!r})
rt = Router(lambda name: None, n_replicas=0, collector=collector,
            metrics=metrics)
runtime.one_chip_env(1), runtime.compile_cache_dir()
Supervisor(["true"], log=lambda *a: None)
GangSupervisor(["true"], n_procs=2, log=lambda *a: None)
plane.close(); srv.close()

if "jax" in sys.modules:
    from jax._src import xla_bridge
    assert not xla_bridge.backends_are_initialized(), "backend is up"
print("OFF-BACKEND")
"""
    proc = subprocess.run([sys.executable, "-c", code], cwd=str(ROOT),
                          capture_output=True, text=True, timeout=120,
                          env=CPU_ENV)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "OFF-BACKEND" in proc.stdout
