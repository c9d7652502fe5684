"""Process bootstrap shared by every driver: where compiled programs are
cached, and which device the process actually got.

Both exist because a run's speed and its kernels depend on facts the
program cannot see from its own flags: a cold compile of the d2048 train
step costs minutes that a cache hit does not, and with no accelerator
JAX falls back to the CPU (Pallas kernels interpreted) with only a
warning. Drivers call `enable_compile_cache()` before their first compile
and print `device_stamp()` at start, so every log says what it ran on.
"""

from __future__ import annotations

import os
import re
from pathlib import Path

# The one in-checkout cache location (git-ignored). Fixed, because a
# path that moves per run (tempfile, pid, timestamp) starts empty and
# never hits.
CACHE_DIR = Path(__file__).resolve().parent.parent / ".jax_cache"


def compile_cache_dir() -> str:
    """Where this process's compiled programs are cached (no JAX
    import: a parent that stays off the backend may ask too)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and
    this sets nothing — whoever launched the process owns the location
    (subprocesses inherit it through the environment). Otherwise the
    cache lives at `CACHE_DIR`, the same path for every driver, router
    replica and smoke phase started from this checkout."""
    import jax

    from shallowspeed_tpu.telemetry import trace

    trace.watch_compiles()      # count this process's compiles from here
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # cache every program, not only those that took over a second to
    # compile: with JAX's default threshold a program hovering around it
    # is stored by one run and not by the next, and a warm run keeps
    # adding entries for shapes that did not change
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # a program's cache key must not follow the checkout's path. JAX
    # strips source locations from a module before it hashes it, but a
    # Pallas kernel rides in its custom call as serialized MLIR WITH its
    # locations, file names and all, which that pass does not reach: so
    # every program with a kernel in it compiled anew from each new
    # checkout path (PERF.md section 6, PR 36). File names lose the
    # checkout's root before they become locations.
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      re.escape(str(CACHE_DIR.parent) + os.sep))
    return compile_cache_dir()


def device_stamp() -> dict:
    """The device as JAX reports it: platform, kind and count. Touches
    the backend — call it only from a process meant to hold the chip
    (the first call is the backend's start: its `backend.init` span is
    that, a later one's a few microseconds)."""
    import jax

    from shallowspeed_tpu.telemetry import tracer

    with tracer().span("backend.init"):
        devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def probe_device_stamp(timeout: float = 300.0) -> dict:
    """`device_stamp()` taken in a throwaway child, for a parent that
    must stay off the backend (a chip belongs to one process at a time:
    a router or smoke parent that touched JAX would starve its own
    children). The child has exited — and released the chips — by the
    time this returns. Raises if the child cannot start JAX."""
    import json
    import subprocess
    import sys

    code = ("import json; from shallowspeed_tpu import runtime; "
            "print(json.dumps(runtime.device_stamp()))")
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=timeout, cwd=str(CACHE_DIR.parent))
    if proc.returncode != 0:
        raise RuntimeError(
            f"device probe failed (exit {proc.returncode}): "
            f"{proc.stderr.strip()[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def one_chip_env(index: int) -> dict:
    """Environment overrides that show a child process chip `index` of
    this host and nothing else — the variables libtpu reads at load
    (the set jax's own multi-process TPU tests export). Each child is
    a one-process slice of its own, so N of them run side by side;
    without this every child opens every chip and the second fails."""
    port = 8476 + index
    return {
        "TPU_VISIBLE_CHIPS": str(index),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_ADDRESSES": f"localhost:{port}",
        "TPU_PROCESS_PORT": str(port),
        "CLOUD_TPU_TASK_ID": "0",
        "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
    }
