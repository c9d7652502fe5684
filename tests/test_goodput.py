"""Goodput ledger + reducer (telemetry/goodput.py) and the
driver/elastic integrations.

The acceptance pin lives here: a supervisor kill/restart run whose
ledger accounts for >= 95% of wall clock, with restart downtime
itemized and cross-checked against the child processes' own JSONL wall
stamps.
"""

import json
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from shallowspeed_tpu.metrics import MetricsLogger, StepRates
from shallowspeed_tpu.telemetry.goodput import (GoodputLedger,
                                                format_report,
                                                run_goodput)

ROOT = Path(__file__).resolve().parents[1]


# ------------------------------------------- windows + ledger == wall


def test_steprates_windows_plus_excluded_ledger_equals_wall():
    """The satellite invariant: because every StepRates.pause also
    stamps the ledger, sum(window seconds) + sum(excluded ledger
    seconds) == elapsed wall clock EXACTLY — the throughput windows
    and the goodput ledger cannot disagree."""
    t = {"now": 100.0}

    def clock():
        return t["now"]

    led = GoodputLedger()  # in-process totals only
    rates = StepRates(tokens_per_step=10, clock=clock, ledger=led)
    win_secs = []

    def log(steps):
        r = rates.log_point(steps)
        win_secs.append(10 * steps / r["tokens_per_sec"])

    t["now"] += 4.0            # 4 s of stepping
    log(4)
    t["now"] += 2.0            # val pause
    rates.pause(2.0, kind="val")
    t["now"] += 3.0            # 3 s of stepping
    log(3)
    t["now"] += 1.5            # checkpoint save
    rates.pause(1.5, kind="ckpt_save")
    t["now"] += 0.5
    log(1)
    wall = t["now"] - 100.0
    assert sum(win_secs) + led.excluded_seconds() == pytest.approx(wall)
    assert led.seconds() == {"val": 2.0, "ckpt_save": 1.5}


def test_ledger_lines_validate_and_accumulate(tmp_path):
    from shallowspeed_tpu.telemetry.schema import validate_file

    log = tmp_path / "m.jsonl"
    led = GoodputLedger(MetricsLogger(log))
    led.note("init", seconds=1.5)
    led.note("recompile", count=2)
    led.note("val", seconds=0.5)
    led.note("val", seconds=0.25)
    assert validate_file(log) == []
    assert led.seconds()["val"] == 0.75
    assert led.counts() == {"recompile": 2}
    kinds = [json.loads(l)["kind"] for l in log.read_text().splitlines()
             if '"ledger"' in l]
    assert kinds == ["init", "recompile", "val", "val"]


# ------------------------------------------------------------ reducer


def _write_jsonl(path, recs):
    path.write_text("\n".join(json.dumps(r) for r in recs) + "\n")


def test_run_goodput_single_run(tmp_path):
    """Synthetic single-process run: 0.1 s/step steady, an itemized
    val pause, and first-window compile excess derived by the
    reducer."""
    log = tmp_path / "m.jsonl"
    recs = [{"event": "run_start", "start_step": 0, "wall": 1000.0},
            {"event": "ledger", "kind": "init", "seconds": 0.4,
             "wall": 1000.4},
            # first step line at 1002.0: 1 step covered, steady rate
            # 0.1 s/step -> compile = 2.0 - 0.4(init) - 0.1 = 1.5
            {"event": "step", "step": 0, "loss": 1.0,
             "tokens_per_sec": 1.0, "wall": 1002.0}]
    w = 1002.0
    for s in range(1, 6):
        w += 0.2  # 2 steps per line at 0.1 s/step
        recs.append({"event": "step", "step": 2 * s, "loss": 1.0,
                     "tokens_per_sec": 1.0, "wall": round(w, 3)})
    recs.insert(6, {"event": "ledger", "kind": "val", "seconds": 0.5,
                    "wall": 1002.75})
    # the val pause really moves wall: shift the lines after it
    for r in recs[7:]:
        r["wall"] = round(r["wall"] + 0.5, 3)
    _write_jsonl(log, recs)
    rep = run_goodput(log)
    assert rep["stanzas"] == 1
    assert rep["per_step_s"] == pytest.approx(0.1, rel=0.05)
    assert rep["losses"]["init"] == pytest.approx(0.4)
    assert rep["losses"]["val"] == pytest.approx(0.5)
    assert rep["losses"]["compile"] == pytest.approx(1.5, abs=0.05)
    assert rep["goodput"] is not None
    assert rep["accounted_frac"] >= 0.99
    assert "wall clock" in format_report(rep)


CHILD = textwrap.dedent(f"""
    import json, sys, time
    sys.path.insert(0, {str(ROOT)!r})
    from pathlib import Path
    from shallowspeed_tpu.metrics import MetricsLogger
    from shallowspeed_tpu.telemetry.goodput import GoodputLedger

    log, state = sys.argv[1], sys.argv[2]
    attempts = Path(state)
    n = int(attempts.read_text()) if attempts.exists() else 0
    attempts.write_text(str(n + 1))
    start_step = 0 if n == 0 else 3   # "checkpoint" at step 3
    m = MetricsLogger(log, start_step=start_step)
    led = GoodputLedger(m)
    t0 = time.time()
    time.sleep(0.05)
    led.note("init", seconds=time.time() - t0)
    for s in range(start_step, 10):
        time.sleep(0.05)
        m.log(event="step", step=s, loss=1.0, tokens_per_sec=100.0)
        if n == 0 and s == 6:
            sys.exit(1)               # crash after logging step 6
    sys.exit(0)
""")


def test_supervisor_kill_restart_ledger_accounts_wall_clock(tmp_path):
    """The elastic-goodput acceptance: a crash-and-resume run's ledger
    accounts for >= 95% of wall clock; the restart-downtime and
    replayed-steps losses match what the child processes' own JSONL
    wall stamps imply."""
    from shallowspeed_tpu.elastic import RestartPolicy, Supervisor

    child = tmp_path / "child.py"
    child.write_text(CHILD)
    log = tmp_path / "metrics.jsonl"
    sup = Supervisor(
        [sys.executable, str(child), str(log), str(tmp_path / "n")],
        policy=RestartPolicy(max_restarts=2, backoff=0.3),
        poll_interval=0.05, ledger_file=str(log), log=lambda *a: None)
    assert sup.run() == 0

    rep = run_goodput(log)
    assert rep["stanzas"] == 2
    assert rep["counts"]["restarts"] == 1
    # child 2 resumed at step 3; child 1 died after step 6 -> steps
    # 3..6 are replayed work
    assert rep["counts"]["replayed_steps"] == 4
    # cross-check against the children's own wall stamps
    recs = [json.loads(l) for l in log.read_text().splitlines()]
    walls = {}
    stanza = -1
    for r in recs:
        if r["event"] == "run_start":
            stanza += 1
            walls[stanza] = {"start": r["wall"], "steps": []}
        elif r["event"] == "step":
            walls[stanza]["steps"].append((r["step"], r["wall"]))
    gap = walls[1]["start"] - walls[0]["steps"][-1][1]
    assert rep["losses"]["restart_downtime"] == pytest.approx(gap,
                                                              abs=1e-6)
    # the supervisor's own stamp covers the same interval (within its
    # poll latency + child-spawn time)
    stamped = [r for r in recs if r["event"] == "ledger"
               and r["kind"] == "restart_downtime"]
    assert len(stamped) == 1 and stamped[0]["attempt"] == 1
    assert 0.3 <= stamped[0]["seconds"] <= gap + 0.1
    # replay loss == replayed steps * the children's own step cadence
    deltas = [b - a for (_, a), (_, b) in
              zip(walls[1]["steps"], walls[1]["steps"][1:])]
    per_step = sorted(deltas)[len(deltas) // 2]
    assert rep["losses"]["replay"] == pytest.approx(4 * per_step,
                                                    rel=0.5)
    # the acceptance bar: >= 95% of wall clock has a name
    assert rep["accounted_frac"] >= 0.95, rep
    assert rep["goodput"] is not None and 0.0 < rep["goodput"] < 1.0


def test_supervisor_autodetects_child_log_file(tmp_path):
    from shallowspeed_tpu.elastic import Supervisor

    sup = Supervisor(["prog", "--log-file", str(tmp_path / "x.jsonl")],
                     log=lambda *a: None)
    assert sup.ledger_file == str(tmp_path / "x.jsonl")
    assert Supervisor(["prog"], log=lambda *a: None).ledger_file is None


# ------------------------------- driver integration + xprof smoke test


@pytest.mark.parametrize("driver", ["lm"])
def test_driver_goodput_profile_and_decode_lines(tmp_path, driver):
    """ONE tiny train_lm run covering four satellites: the xprof
    --profile-dir capture smoke test (a loadable, NON-EMPTY trace
    artifact — not just a directory that exists), the goodput
    ledger's driver wiring (init/val/ckpt_save stamped, the reducer
    accounts the run), the decode progress line's "generate" metrics
    event, and — with `--profile host+device` on the same run — the
    continuous profiling plane riding the SAME device-capture entry
    point (`profiler.device_trace_ctx`) as --profile-dir, streaming
    schema-v12 profile events next to the spans-level step fields,
    all schema-valid."""
    import train_lm

    log = tmp_path / "metrics.jsonl"
    prof = tmp_path / "prof"
    trace = tmp_path / "trace"
    train_lm.train(train_lm.parse_args(
        ["--dp", "1", "--seq-len", "32", "--d-model", "32",
         "--n-layers", "2", "--batch-size", "4", "--steps", "8",
         "--log-every", "2", "--val-every", "4", "--save-every", "4",
         "--save-dir", str(tmp_path / "ck"), "--log-file", str(log),
         "--profile-dir", str(prof), "--telemetry", "spans",
         "--profile", "host+device",
         "--trace-dir", str(trace), "--prefetch", "0",
         "--generate", "8", "--seed", "0"]))
    # xprof smoke, hardened (round 17): an empty directory or a
    # zero-byte artifact used to pass — require a non-empty protobuf
    # (xprof writes *.xplane.pb under plugins/profile/<ts>/)
    pbs = [p for p in prof.rglob("*.pb") if p.stat().st_size > 0]
    assert pbs, (f"no non-empty xprof .pb artifact under {prof}: "
                 f"{[str(p) for p in prof.rglob('*') if p.is_file()]}")
    # schema: the v4 artifact validates end to end
    from shallowspeed_tpu.telemetry.schema import validate_file

    assert validate_file(log) == []
    recs = [json.loads(l) for l in log.read_text().splitlines()]
    kinds = {r["kind"] for r in recs if r["event"] == "ledger"}
    assert {"init", "val", "ckpt_save"} <= kinds, kinds
    steps = [r for r in recs if r["event"] == "step"]
    assert steps and "hbm_live_mib" in steps[-1], steps[-1]
    gen = [r for r in recs if r["event"] == "generate"]
    assert len(gen) == 1 and gen[0]["tokens_per_sec"] > 0
    assert gen[0]["hbm_util"] is None  # CPU: no invented HBM peak
    # the profiling plane ran alongside: schema-v12 snapshots landed,
    # and host+device under an ACTIVE --profile-dir whole-run trace
    # means capture windows would skip their device half (xprof
    # doesn't nest) — the sampler itself must still stream
    profs = [r for r in recs if r["event"] == "profile"]
    assert profs and profs[-1]["samples"] > 0, profs
    # the reducer accounts the run (single process, generous band —
    # the strict >= 0.95 pin is the supervised kill/restart test)
    rep = run_goodput(log)
    assert rep["stanzas"] == 1
    assert rep["accounted_frac"] is not None
    assert rep["accounted_frac"] >= 0.85, rep
    # telemetry.json carries the in-process ledger totals
    summary = json.loads((trace / "telemetry.json").read_text())
    assert summary["goodput_ledger"]["seconds"].get("val", 0) > 0


def test_goodput_prefix_cache_block(tmp_path):
    """Schema-v14 prefix reduction: request lines' hit-blocks /
    skipped-tokens tallies plus the last generate tick's gauges land
    in rep["prefix"], the formatted report prints the hit-rate line,
    and a run without the fields reports prefix=None (the cache-off
    shape is unchanged)."""
    from shallowspeed_tpu.telemetry.goodput import (format_report,
                                                    run_goodput)

    log = tmp_path / "serve.jsonl"
    base = {"event": "request", "ttft_ms": 5.0, "tpot_ms": 1.0,
            "tokens_out": 8}
    _write_jsonl(log, [
        {"event": "run_start", "start_step": 0, "wall": 1000.0},
        dict(base, id="cold", tokens_in=32, prefix_hit_blocks=0,
             prefill_skipped_tokens=0, wall=1000.1),
        dict(base, id="hit", tokens_in=32, prefix_hit_blocks=4,
             prefill_skipped_tokens=31, wall=1000.2),
        dict(base, id="part", tokens_in=48, prefix_hit_blocks=2,
             prefill_skipped_tokens=16, wall=1000.3),
        {"event": "generate", "tokens_per_sec": 100.0,
         "prefix_hit_rate": 0.5, "cold_blocks": 6, "prefix_blocks": 6,
         "wall": 1000.4},
    ])
    pfx = run_goodput(log)["prefix"]
    assert pfx["requests_observed"] == 3
    assert pfx["requests_hit"] == 2
    assert pfx["hit_rate"] == pytest.approx(2 / 3, abs=1e-3)
    assert pfx["hit_blocks"] == 6
    assert pfx["prefill_skipped_tokens"] == 47
    assert pfx["skipped_frac"] == pytest.approx(47 / 112, abs=1e-3)
    assert pfx["cold_blocks"] == 6 and pfx["prefix_blocks"] == 6
    assert "prefix cache: 2/3 request(s) hit" in \
        format_report(run_goodput(log))
    # cache-off runs keep the old shape: no prefix block at all
    off = tmp_path / "off.jsonl"
    _write_jsonl(off, [
        {"event": "run_start", "start_step": 0, "wall": 1000.0},
        dict(base, id="a", tokens_in=16, wall=1000.1),
    ])
    rep = run_goodput(off)
    assert rep["prefix"] is None
    assert "prefix cache" not in format_report(rep)
