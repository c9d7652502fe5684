"""Continuous profiling plane (round 17): always-on host sampler,
burn/fault-armed capture windows, span-tagged host time.

Acceptance pins:
- burn-triggered capture drill (default tier): a seeded `stall` chaos
  fault under a tight --slo on a real serve.py subprocess produces
  EXACTLY ONE profcap_*.json (the cooldown folds the fault and the
  SLO burn it causes into one window) whose dominant tagged phase
  names the stalled scheduler phase (the engine's `chaos` span)
  (`test_serving_stall_drill_arms_one_capture`);
- sampler safety: a profiled serving run compiles ZERO new jit
  executables vs the unprofiled warmup (`executable_counts()`
  unchanged — which also pins zero recompiles) and the sampler's
  worst inter-sample gap stays bounded
  (`test_sampler_safety_zero_new_executables`);
- snapshots are exact: top-K folded counts + `other` always sum to
  `samples`, through compaction, merge, and the flame-tree reduction.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from shallowspeed_tpu.telemetry import profiler
from shallowspeed_tpu.telemetry.profiler import (CaptureWindow,
                                                 SamplingProfiler,
                                                 device_trace_ctx,
                                                 flame_tree,
                                                 merge_profiles,
                                                 profile_main)
from shallowspeed_tpu.telemetry.schema import validate_file
from shallowspeed_tpu.telemetry.trace import Tracer

ROOT = Path(__file__).resolve().parents[1]


# ------------------------------------------------------------- tagging


def test_spans_feed_no_phase_when_no_profiler_runs():
    """With no profiler running a span touches the phase registry not
    at all (one module-global read), at any level and any depth."""
    from shallowspeed_tpu.telemetry import trace

    assert trace.PHASE_HOOKS is None
    tr = Tracer(level="off")
    with tr.span("chaos"):
        assert not profiler._TAGS
        # and the engine may nest them unconditionally
        with tr.span("decode.prep"):
            assert not profiler._TAGS
    assert [e[2] for e in tr.ring()] == ["decode.prep", "chaos"]


def test_sample_once_labels_innermost_phase_and_step_membership():
    """Deterministic, clock-free: hooks installed by hand, one
    helper-thread sample per state (the sampler skips its own thread,
    so the main thread must be the samplee). The spans are the `off`
    tracer's: phase attribution needs no telemetry level."""
    prof = SamplingProfiler()   # never started: no background samples
    tr = Tracer(level="off")
    profiler._install_hooks()
    try:
        def one():
            th = threading.Thread(target=prof.sample_once)
            th.start()
            th.join()

        with tr.span("step"):
            with tr.span("prefill.sample"):
                one()           # innermost wins; step anywhere counts
        with tr.span("decode.fetch"):
            one()
        one()                   # untagged
    finally:
        profiler._uninstall_hooks()
    assert prof.samples == 3
    assert prof.phases == {"prefill.sample": 1, "decode.fetch": 1,
                           profiler.UNTAGGED: 1}
    assert prof.step_samples == 1
    # folded stacks are root->leaf module:function strings
    assert all(";" in k and ":" in k for k in prof.folded)
    # stop() after start(): spans feed the registry only in between,
    # and the cross-thread registry is cleared
    ident = threading.get_ident()
    prof2 = SamplingProfiler(hz=200).start()
    with tr.span("x"):
        assert profiler._TAGS[ident] == ["x"]
    prof2.stop()
    with tr.span("x"):
        assert profiler._TAGS == {}


@pytest.mark.parametrize("level", ["off", "steps"])
def test_tracer_spans_feed_phase_registry_while_profiler_runs(level):
    tr = Tracer(level=level)
    prof = SamplingProfiler()
    profiler._install_hooks()
    try:
        ident = threading.get_ident()
        with tr.span("step"):
            assert profiler._TAGS[ident] == ["step"]
            with tr.span("grads"):
                assert profiler._TAGS[ident] == ["step", "grads"]
        assert profiler._TAGS[ident] == []
    finally:
        profiler._uninstall_hooks()
    from shallowspeed_tpu.telemetry import trace

    assert trace.PHASE_HOOKS is None
    del prof


# ------------------------------------------------------------ snapshot


def test_snapshot_topk_plus_other_sums_to_samples():
    prof = SamplingProfiler(top_k=2)
    prof.folded.update({"a;b": 5, "a;c": 3, "d": 2})
    prof.phases.update({profiler.UNTAGGED: 10})
    prof.samples = 10
    snap = prof.snapshot()
    assert set(snap["folded"]) == {"a;b", "a;c"}
    assert sum(snap["folded"].values()) + snap["other"] == snap["samples"]
    assert snap["other"] == 2


def test_compaction_keeps_exact_counts_for_survivors():
    prof = SamplingProfiler(top_k=2)
    prof._compact_at = 3
    prof.folded.update({f"s{i}": i + 1 for i in range(8)})  # 36 samples
    prof.samples = 36
    with prof._lock:
        prof._compact_locked()
    assert len(prof.folded) == 3            # back to _compact_at uniques
    assert prof.folded["s7"] == 8           # survivors keep exact counts
    snap = prof.snapshot()
    assert sum(snap["folded"].values()) + snap["other"] == 36


def test_merge_profiles_prefixes_replicas_and_flame_tree_sums():
    snaps = {
        "r0": {"samples": 10, "step_samples": 6, "other": 2,
               "folded": {"m:f;m:g": 5, "m:f;m:h": 3},
               "phases": {"step": 6, profiler.UNTAGGED: 4}},
        "r1": {"samples": 4, "step_samples": 0, "other": 0,
               "folded": {"m:f;m:g": 4},
               "phases": {profiler.UNTAGGED: 4}},
    }
    merged = merge_profiles(snaps)
    assert merged["samples"] == 14 and merged["step_samples"] == 6
    assert merged["folded"]["r0;m:f;m:g"] == 5
    assert merged["folded"]["r1;m:f;m:g"] == 4
    # the exact remainder survives the merge as a per-replica leaf
    assert merged["folded"][f"r0;{profiler.OTHER_KEY}"] == 2
    assert merged["phases"] == {"step": 6, profiler.UNTAGGED: 8}
    assert merged["replicas"] == ["r0", "r1"]

    tree = flame_tree(merged["folded"])
    assert tree["value"] == 14
    top = {c["name"]: c["value"] for c in tree["children"]}
    assert top == {"r0": 10, "r1": 4}       # replica-labelled first level

    def _check(node):
        for c in node.get("children", ()):
            _check(c)
        if node.get("children"):
            assert node["value"] >= max(c["value"]
                                        for c in node["children"])

    _check(tree)


# ----------------------------------------------------- capture windows


def test_capture_window_dedup_cooldown_cap_and_dominant_phase(tmp_path):
    t = [0.0]
    cw = CaptureWindow(out_dir=tmp_path, duration_s=0.1, hz=400,
                       max_captures=3, cooldown_s=30.0,
                       clock=lambda: t[0])
    profiler._install_hooks()   # so spans name phases for the capture
    try:
        with Tracer(level="off").span("chaos"):
            assert cw.arm("fault:stall", step=6, trigger={"kind": "stall"})
            time.sleep(0.12)    # the window samples the main thread here
        assert not cw.arm("fault:stall", step=6)    # (reason, step) dedup
        assert not cw.arm("slo:tpot_p95_ms", step=7)     # cooldown folds
        t[0] = 31.0
        assert cw.arm("slo:tpot_p95_ms", step=7)
        t[0] = 62.0
        assert cw.arm("anomaly", step=9)
        t[0] = 93.0
        assert not cw.arm("late", step=11)          # max_captures cap
        cw.wait()
    finally:
        profiler._uninstall_hooks()
    caps = sorted(tmp_path.glob("profcap_*.json"))
    assert len(caps) == 3, caps
    pay = json.loads((tmp_path / "profcap_6.json").read_text())
    assert pay["reason"] == "fault:stall" and pay["step"] == 6
    assert pay["samples"] > 0
    assert pay["dominant_phase"] == "chaos"
    assert pay["trigger"] == {"kind": "stall"}
    assert sum(pay["phases"].values()) == pay["samples"]


def test_capture_skips_device_trace_inside_live_xprof_session(tmp_path):
    cw = CaptureWindow(out_dir=tmp_path, duration_s=0.02,
                       device_trace=True)
    profiler._DEVICE_TRACE_DEPTH += 1   # a whole-run --profile-dir trace
    try:
        assert cw.arm("fault:stall", step=1)
        cw.wait()
    finally:
        profiler._DEVICE_TRACE_DEPTH -= 1
    pay = json.loads((tmp_path / "profcap_1.json").read_text())
    assert "device_trace" not in pay    # xprof sessions do not nest
    assert not list(tmp_path.glob("profcap_dev_*"))


def test_device_trace_ctx_falsy_dir_is_noop():
    assert not profiler._device_trace_active()
    with device_trace_ctx(None):
        assert not profiler._device_trace_active()
    with device_trace_ctx(""):
        pass
    assert profiler._DEVICE_TRACE_DEPTH == 0


# ----------------------------------------------------------- reduction


def test_profile_main_reduces_last_event_per_stanza(tmp_path, capsys):
    log = tmp_path / "m.jsonl"
    with open(log, "w") as f:
        f.write(json.dumps({"event": "run_start", "schema_version": 12,
                            "replica": "east", "wall": 1.0}) + "\n")
        f.write(json.dumps({"event": "profile", "samples": 5,
                            "folded": {"a:f": 5}, "other": 0,
                            "phases": {"step": 5}, "wall": 2.0}) + "\n")
        # cumulative: only this LAST snapshot of the stanza counts
        f.write(json.dumps({"event": "profile", "samples": 9,
                            "step_samples": 6,
                            "folded": {"a:f": 7, "a:g": 2}, "other": 0,
                            "phases": {"step": 6, "(untagged)": 3},
                            "wall": 3.0}) + "\n")
        f.write(json.dumps({"event": "run_start", "schema_version": 12,
                            "replica": "west", "wall": 4.0}) + "\n")
        f.write(json.dumps({"event": "profile", "samples": 3,
                            "folded": {"b:h": 2}, "other": 1,
                            "phases": {"(untagged)": 3},
                            "wall": 5.0}) + "\n")
    assert validate_file(log) == []
    out = tmp_path / "flame.json"
    assert profile_main([log], out=out) == 0
    tree = json.loads(out.read_text())
    assert tree["value"] == 12              # 9 + 3, not 5 + 9 + 3
    assert {c["name"] for c in tree["children"]} == {"east", "west"}
    printed = capsys.readouterr().out
    assert "phase step" in printed and "50.0%" in printed

    empty = tmp_path / "empty.jsonl"
    empty.write_text(json.dumps({"event": "run_start",
                                 "schema_version": 12}) + "\n")
    assert profile_main([empty]) == 1       # lost events fail the smoke


# ----------------------------------------------- fleet + goodput merge


def test_fleet_merges_replica_profiles_and_status_block(tmp_path):
    from shallowspeed_tpu.telemetry.fleet import FleetCollector

    def _replica(path, name, folded, phases):
        n = sum(folded.values())
        with open(path, "w") as f:
            f.write(json.dumps({"event": "run_start",
                                "schema_version": 12, "replica": name,
                                "wall": 100.0}) + "\n")
            f.write(json.dumps({"event": "request", "id": f"{name}-q0",
                                "ttft_ms": 10.0, "tokens_in": 2,
                                "tokens_out": 2, "wall": 101.0}) + "\n")
            f.write(json.dumps({"event": "profile", "samples": n,
                                "step_samples": 0, "folded": folded,
                                "other": 0, "phases": phases,
                                "wall": 102.0}) + "\n")
        assert validate_file(path) == []
        return path

    a = _replica(tmp_path / "a.jsonl", "alpha",
                 {"serve:main;engine:step": 25,
                  "serve:main;engine:_maybe_log": 5},
                 {"decode-tick": 25, "logging": 5})
    b = _replica(tmp_path / "b.jsonl", "beta",
                 {"serve:main;engine:step": 4},
                 {"prefill-chunk": 4})
    fc = FleetCollector(paths=[a, b])
    st = fc.refresh()
    prof = fc.profile_payload()
    assert prof["enabled"] and prof["samples"] == 34
    assert prof["folded"]["alpha;serve:main;engine:step"] == 25
    assert prof["folded"]["beta;serve:main;engine:step"] == 4
    # the fleet status grows a per-replica profiling block naming the
    # top phase and the hottest LEAF frame
    blk = st["profiling"]["replicas"]
    assert blk["alpha"]["top_phase"] == "decode-tick"
    assert blk["alpha"]["top_frame"] == "engine:step"
    assert blk["beta"]["samples"] == 4

    # replicas without profile events -> no block, payload disabled
    c = tmp_path / "c.jsonl"
    c.write_text(json.dumps({"event": "run_start", "schema_version": 12,
                             "replica": "gamma", "wall": 100.0}) + "\n")
    fc2 = FleetCollector(paths=[c])
    st2 = fc2.refresh()
    assert "profiling" not in st2
    assert fc2.profile_payload() == {"enabled": False}


def test_goodput_report_carries_profiling_block(tmp_path):
    from shallowspeed_tpu.telemetry.goodput import (format_report,
                                                    run_goodput)

    log = tmp_path / "m.jsonl"
    with open(log, "w") as f:
        f.write(json.dumps({"event": "run_start", "schema_version": 12,
                            "wall": 1.0}) + "\n")
        f.write(json.dumps({"event": "profile", "samples": 20,
                            "step_samples": 15,
                            "folded": {"train:main;lm:train_step": 15,
                                       "train:main;loader:next": 5},
                            "other": 0,
                            "phases": {"step": 15, "data-load": 5},
                            "wall": 9.0}) + "\n")
    rep = run_goodput(log)
    prof = rep["profiling"]
    assert prof["samples"] == 20 and prof["snapshots"] == 1
    assert prof["phases"] == {"step": 15, "data-load": 5}
    assert prof["top_frames"][0] == {"frame": "lm:train_step",
                                     "samples": 15}
    text = format_report(rep)
    assert "profiling (20 host sample(s), 1 snapshot(s))" in text
    assert "hottest frame: lm:train_step (75%)" in text


# -------------------------------------------------------- sampler safety


@pytest.fixture(scope="module")
def tiny_engine():
    import jax

    from shallowspeed_tpu.models import transformer as T
    from shallowspeed_tpu.serving import ServingEngine

    cfg = T.TransformerConfig(vocab=48, d_model=24, n_heads=2,
                              n_layers=2, max_seq=96)
    params = jax.device_put(T.init(cfg, seed=1))
    eng = ServingEngine(params, cfg, n_blocks=48, block_size=8,
                        max_slots=2, prefill_chunk=16)
    return eng, cfg


def _offer(eng, cfg, n=6, seed=0, prefix=""):
    rng = np.random.default_rng(seed)
    for i in range(n):
        eng.submit(rng.integers(0, cfg.vocab, 6 + 2 * i)
                   .astype(np.int32), 4 + i, rid=f"{prefix}q{i}")


def test_sampler_safety_zero_new_executables(tiny_engine):
    """The safety contract: the sampler never touches jax, so the
    profiled run reuses the warmup's executables exactly — zero new
    jit entry points AND zero recompiles (cache sizes unchanged) —
    and the worst inter-sample gap stays bounded."""
    eng, cfg = tiny_engine
    _offer(eng, cfg, seed=3, prefix="warm-")
    eng.run()
    base = eng.executable_counts()
    assert base and sum(base.values()) > 0

    prof = SamplingProfiler(hz=250).start()
    try:
        _offer(eng, cfg, seed=3, prefix="prof-")   # same shapes as warmup
        out = eng.run()
        # the warmed rerun drains in milliseconds — keep the window
        # open a beat so the liveness bound measures real gaps
        time.sleep(0.25)
    finally:
        prof.stop()
    assert sum(1 for rid in out if rid.startswith("prof-")) == 6
    assert eng.executable_counts() == base
    assert prof.samples > 10
    # liveness: the sampler kept its beat through the serving loop
    # (generous bound — a 1-core CI host under GIL contention)
    assert 0.0 < prof.max_gap_ms < 2000.0, prof.max_gap_ms


def test_sampler_safety_train_driver_steps_monotone(tmp_path):
    """Satellite: a profiled `--telemetry spans` training run logs
    MONOTONE step lines with zero recompiles and stable compile
    counters (the sampler is invisible to jax), its profile events
    carry a bounded max sample gap, and the tracer's step spans land
    in the tagged phase buckets."""
    log = tmp_path / "m.jsonl"
    r = subprocess.run(
        [sys.executable, "train_lm.py", "--platform", "cpu",
         "--steps", "12", "--log-every", "2", "--batch-size", "2",
         "--seq-len", "16", "--d-model", "16", "--n-layers", "1",
         "--n-heads", "2", "--vocab", "32", "--prefetch", "0",
         "--telemetry", "spans", "--profile", "host",
         "--profile-hz", "200", "--log-file", str(log)],
        capture_output=True, text=True, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
    assert validate_file(log) == []
    recs = [json.loads(l) for l in log.read_text().splitlines()]
    steps = [rec for rec in recs if rec["event"] == "step"]
    assert steps
    nums = [rec["step"] for rec in steps]
    assert nums == sorted(nums) and len(set(nums)) == len(nums)
    assert steps[-1]["recompiles"] == 0
    assert steps[-1]["compiles"] == steps[0]["compiles"]
    profs = [rec for rec in recs if rec["event"] == "profile"]
    assert profs and profs[-1]["samples"] > 0
    # the sampler never wedged the run: worst inter-sample gap stays
    # bounded (generous — a 1-core host paying XLA compile under GIL)
    assert profs[-1]["max_gap_ms"] < 10_000
    assert profs[-1]["phases"].get("step", 0) > 0


# --------------------------------------- acceptance drill (default tier)


def test_serving_stall_drill_arms_one_capture(tmp_path):
    """ISSUE-17 acceptance: a seeded `stall` chaos fault under a
    deliberately-impossible tpot SLO arms EXACTLY ONE capture window
    — the fault fires first, the SLO burn it causes lands inside the
    cooldown — and the profcap names the stalled phase (`chaos`: the
    plan stamps observers before the stall sleep, inside the engine's
    `chaos` span)."""
    reqs = tmp_path / "reqs.jsonl"
    with open(reqs, "w") as f:
        for i in range(4):
            f.write(json.dumps({"id": f"r{i}", "prompt_len": 32,
                                "prompt_seed": i + 1,
                                "max_new": 48}) + "\n")
    log = tmp_path / "metrics.jsonl"
    r = subprocess.run(
        [sys.executable, "serve.py", "--platform", "cpu",
         "--vocab", "64", "--d-model", "32", "--n-heads", "2",
         "--n-layers", "1", "--max-seq", "256",
         "--requests", str(reqs), "--log-file", str(log),
         "--profile", "host", "--profile-hz", "200",
         "--chaos", "stall@6:0.75", "--chaos-seed", "3",
         "--slo", "tpot_p95_ms<0.01",
         "--n-blocks", "64", "--slots", "2"],
        capture_output=True, text=True, cwd=ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr

    caps = sorted(tmp_path.glob("profcap_*.json"))
    assert len(caps) == 1, [c.name for c in caps]
    pay = json.loads(caps[0].read_text())
    assert pay["reason"] == "fault:stall" and pay["step"] == 6
    assert pay["samples"] > 0
    assert pay["dominant_phase"] == "chaos", pay["phases"]

    # the metrics log validates schema v12 and its cumulative profile
    # events are monotone in sample count
    assert validate_file(log) == []
    recs = [json.loads(l) for l in log.read_text().splitlines()]
    profs = [rec["samples"] for rec in recs
             if rec["event"] == "profile"]
    assert profs and profs == sorted(profs) and profs[-1] > 0
    assert sum(1 for rec in recs if rec["event"] == "fault") == 1
    assert sum(1 for rec in recs if rec["event"] == "generate") >= 1
