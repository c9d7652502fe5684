"""Failure detection / elastic recovery (`shallowspeed_tpu/elastic.py`).

The reference has none of this (SURVEY §5: a rank failure kills the
mpirun job). Coverage: the restart policy's budget/backoff/refill
arithmetic (pure), the supervisor loop against real child processes
(crash-then-succeed, budget exhaustion, hang detection via heartbeat
staleness), and the driver-level contract (`--auto-resume` starts fresh
without a checkpoint and resumes with one — the property every restart
relies on).
"""

import json
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import pytest

from shallowspeed_tpu.elastic import RestartPolicy, Supervisor


# ------------------------------------------------------------- policy


def test_policy_budget_and_backoff_doubling():
    p = RestartPolicy(max_restarts=3, backoff=1.0, backoff_max=3.0,
                      healthy_after=60.0)
    assert p.next_restart() == 1.0
    assert p.next_restart() == 2.0
    assert p.next_restart() == 3.0  # capped at backoff_max
    assert p.next_restart() is None  # budget exhausted


def test_policy_healthy_run_refills_budget():
    p = RestartPolicy(max_restarts=1, backoff=1.0, healthy_after=10.0)
    assert p.next_restart() == 1.0
    assert p.next_restart() is None
    p.record_run(11.0)  # child stayed up past the healthy window
    assert p.next_restart() == 1.0  # budget and backoff reset


def test_policy_short_run_does_not_refill():
    p = RestartPolicy(max_restarts=1, backoff=1.0, healthy_after=10.0)
    assert p.next_restart() == 1.0
    p.record_run(2.0)  # crash loop: stayed up 2s only
    assert p.next_restart() is None


# --------------------------------------------------------- supervisor


def _script(tmp_path, body) -> list:
    f = tmp_path / "child.py"
    f.write_text(textwrap.dedent(body))
    return [sys.executable, str(f)]


def test_supervisor_restarts_until_success(tmp_path):
    """Child crashes twice, then succeeds: the supervisor must retry
    through the failures and return 0."""
    marker = tmp_path / "attempts"
    cmd = _script(tmp_path, f"""
        from pathlib import Path
        m = Path({str(marker)!r})
        n = int(m.read_text()) if m.exists() else 0
        m.write_text(str(n + 1))
        raise SystemExit(0 if n >= 2 else 1)
    """)
    sup = Supervisor(cmd, RestartPolicy(max_restarts=5, backoff=0.01),
                     log=lambda *_: None)
    assert sup.run() == 0
    assert marker.read_text() == "3"  # 2 failures + 1 success


def test_supervisor_gives_up_when_budget_exhausted(tmp_path):
    cmd = _script(tmp_path, "raise SystemExit(7)")
    sup = Supervisor(cmd, RestartPolicy(max_restarts=2, backoff=0.01),
                     log=lambda *_: None)
    assert sup.run() == 7  # the child's failing code, after 1+2 runs


def test_supervisor_kills_hung_child(tmp_path):
    """A child that never touches its heartbeat is killed after
    hang_timeout and the restart policy takes over; a second attempt
    that finishes quickly rescues the run."""
    marker = tmp_path / "attempts"
    hb = tmp_path / "hb"
    cmd = _script(tmp_path, f"""
        import sys, time
        from pathlib import Path
        m = Path({str(marker)!r})
        n = int(m.read_text()) if m.exists() else 0
        m.write_text(str(n + 1))
        if n == 0:
            time.sleep(60)  # never heartbeats -> must be killed
        raise SystemExit(0)
    """) + ["--heartbeat-file", str(hb)]
    t0 = time.monotonic()
    # hang_timeout must exceed worst-case interpreter startup on a
    # loaded host (the healthy retry must not be killed mid-import)
    sup = Supervisor(cmd, RestartPolicy(max_restarts=2, backoff=0.01),
                     hang_timeout=15.0, poll_interval=0.2,
                     log=lambda *_: None)
    assert sup.run() == 0
    assert time.monotonic() - t0 < 55  # killed at ~15s, not waited out
    assert marker.read_text() == "2"


def test_hang_detection_survives_deleted_heartbeat(tmp_path):
    """Deleting the heartbeat file mid-run must NOT disable hang
    detection (ADVICE r2: getmtime OSError used to reset staleness to
    zero forever): the child deletes its own heartbeat then sleeps —
    the supervisor still kills it, measuring staleness from the last
    known beat."""
    marker = tmp_path / "attempts"
    hb = tmp_path / "hb"
    cmd = _script(tmp_path, f"""
        import os, sys, time
        from pathlib import Path
        m = Path({str(marker)!r})
        n = int(m.read_text()) if m.exists() else 0
        m.write_text(str(n + 1))
        if n == 0:
            os.unlink({str(hb)!r})  # vanish the liveness signal...
            time.sleep(60)          # ...and hang
        raise SystemExit(0)
    """) + ["--heartbeat-file", str(hb)]
    t0 = time.monotonic()
    sup = Supervisor(cmd, RestartPolicy(max_restarts=2, backoff=0.01),
                     hang_timeout=15.0, poll_interval=0.2,
                     log=lambda *_: None)
    assert sup.run() == 0
    assert time.monotonic() - t0 < 55
    assert marker.read_text() == "2"


def test_cli_requires_command():
    from shallowspeed_tpu.elastic import main

    with pytest.raises(SystemExit):
        main(["--max-restarts", "1"])


# -------------------------------------------------- driver integration


def test_auto_resume_fresh_then_resume(tmp_path):
    """The contract every supervised restart relies on: --auto-resume
    starts fresh when no checkpoint exists and resumes when one does."""
    base = [sys.executable, "train_lm.py", "--platform", "cpu",
            "--host-devices", "2", "--dp", "2", "--seq-len", "32",
            "--d-model", "32", "--n-layers", "1", "--log-every", "2",
            "--save-dir", str(tmp_path / "ck"), "--save-every", "4",
            "--auto-resume"]
    repo = Path(__file__).parent.parent
    r1 = subprocess.run(base + ["--steps", "4"], capture_output=True,
                        text=True, cwd=repo, timeout=300)
    assert r1.returncode == 0, r1.stdout + r1.stderr
    assert "resumed" not in r1.stdout  # fresh start
    r2 = subprocess.run(base + ["--steps", "8"], capture_output=True,
                        text=True, cwd=repo, timeout=300)
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert "resumed from" in r2.stdout  # picked up ckpt_3


def test_auto_resume_mlp_driver(tmp_path):
    """The reference-parity MLP driver honors --auto-resume the same way
    (fresh without a checkpoint, resumed with one)."""
    base = [sys.executable, "train.py", "--platform", "cpu",
            "--host-devices", "2", "--dp", "2", "--max-batches", "4",
            "--lr", "0.5", "--save-dir", str(tmp_path / "ck"),
            "--auto-resume"]
    repo = Path(__file__).parent.parent
    r1 = subprocess.run(base + ["--epochs", "1"], capture_output=True,
                        text=True, cwd=repo, timeout=300)
    assert r1.returncode == 0, r1.stdout + r1.stderr
    assert "resumed" not in r1.stdout
    r2 = subprocess.run(base + ["--epochs", "2"], capture_output=True,
                        text=True, cwd=repo, timeout=300)
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert "resumed from" in r2.stdout


# ----------------------------------------------- gang mode (round 4)


def _gang_child(tmp_path, body):
    """A stub gang member: asserts the injected env, then runs `body`."""
    script = tmp_path / "child.py"
    script.write_text(textwrap.dedent(f"""
        import os, sys, time
        assert os.environ["JAX_COORDINATOR_ADDRESS"]
        n = int(os.environ["JAX_NUM_PROCESSES"])
        pid = int(os.environ["JAX_PROCESS_ID"])
        {body}
    """))
    return [sys.executable, str(script)]


def test_gang_env_injection_and_clean_finish(tmp_path):
    from shallowspeed_tpu.elastic import GangSupervisor

    cmd = _gang_child(tmp_path, """
        (open(os.path.join(r'%s', f'saw_{pid}'), 'w')).write('1')
        assert n == 2
    """ % tmp_path)
    sup = GangSupervisor(cmd, 2, RestartPolicy(max_restarts=0),
                         poll_interval=0.05)
    assert sup.run() == 0
    assert (tmp_path / "saw_0").exists() and (tmp_path / "saw_1").exists()


def test_gang_member_failure_restarts_whole_gang(tmp_path):
    """Any member's nonzero exit kills the gang; the restart relaunches
    ALL members (a JAX multi-controller job cannot continue with a
    missing peer — the compiled collectives bake the topology)."""
    from shallowspeed_tpu.elastic import GangSupervisor

    marker = tmp_path / "crashed_once"
    cmd = _gang_child(tmp_path, """
        import pathlib
        runs = pathlib.Path(r'%s') / f'runs_{pid}'
        runs.write_text(str(int(runs.read_text()) + 1
                            if runs.exists() else 1))
        if pid == 1 and not pathlib.Path(r'%s').exists():
            while not runs.with_name('runs_0').exists():
                time.sleep(0.01)    # member 0 has counted its first run
            pathlib.Path(r'%s').write_text('x')
            sys.exit(3)     # member 1 dies on the first attempt
        if pid == 0 and runs.read_text() == '1':
            time.sleep(60)  # outlives member 1's crash: killed with the gang
    """ % (tmp_path, marker, marker))
    sup = GangSupervisor(cmd, 2,
                         RestartPolicy(max_restarts=2, backoff=0.01),
                         poll_interval=0.05)
    assert sup.run() == 0
    # BOTH members ran twice: the healthy member was killed and
    # relaunched along with the crashed one
    assert (tmp_path / "runs_0").read_text() == "2"
    assert (tmp_path / "runs_1").read_text() == "2"


def test_gang_hang_kills_and_restarts(tmp_path):
    """A single stale heartbeat (one wedged member) takes the whole
    gang down; the restart succeeds."""
    from shallowspeed_tpu.elastic import GangSupervisor

    marker = tmp_path / "hung_once"
    cmd = _gang_child(tmp_path, """
        import pathlib
        hb = sys.argv[sys.argv.index('--heartbeat-file') + 1]
        if pid == 0 and not pathlib.Path(r'%s').exists():
            pathlib.Path(r'%s').write_text('x')
            time.sleep(120)  # wedged: never beats
        for _ in range(8):
            pathlib.Path(hb).touch(); time.sleep(0.2)
    """ % (marker, marker))
    sup = GangSupervisor(cmd, 2,
                         RestartPolicy(max_restarts=2, backoff=0.01),
                         hang_timeout=6.0, poll_interval=0.1)
    t0 = time.time()
    assert sup.run() == 0
    assert time.time() - t0 < 60


def test_gang_supervises_real_multicontroller_training(tmp_path):
    """END-TO-END gang elasticity (round 4): a REAL 2-process
    multi-controller train_lm run (dp=4 across 2 procs x 2 devices,
    gradient psums crossing the boundary) under GangSupervisor; one
    member is SIGKILLed after the first checkpoint lands; the WHOLE
    gang restarts and BOTH processes resume from the checkpoint
    (multi-controller restore) and finish cleanly."""
    import os
    import signal

    ck = tmp_path / "ck"
    log = tmp_path / "gang.log"
    cmd = [sys.executable, "-m", "shallowspeed_tpu.elastic", "--procs",
           "2", "--max-restarts", "2", "--backoff", "1", "--",
           sys.executable, "train_lm.py", "--platform", "cpu",
           "--host-devices", "2", "--dp", "4", "--seq-len", "32",
           "--d-model", "32", "--steps", "260", "--log-every", "50",
           "--save-dir", str(ck), "--save-every", "40",
           "--auto-resume"]
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_COORDINATOR_ADDRESS", None)
    with open(log, "w") as logf:
        sup = subprocess.Popen(cmd, stdout=logf,
                               stderr=subprocess.STDOUT,
                               cwd=str(Path(__file__).parent.parent),
                               env=env)
    members = []
    try:
        for _ in range(180):          # wait for the first checkpoint
            time.sleep(1)
            if ck.exists() and any(
                    not p.name.endswith(".tmp")
                    for p in ck.glob("ckpt_*")):
                break
        else:
            raise AssertionError(
                f"no checkpoint appeared:\n{log.read_text()[-2000:]}")
        out = subprocess.run(["ps", "-eo", "pid,ppid"],
                             capture_output=True, text=True).stdout
        members = [int(l.split()[0]) for l in out.splitlines()[1:]
                   if l.split()[1] == str(sup.pid)]
        assert members, "no gang members found"
        os.kill(members[0], signal.SIGKILL)
        rc = sup.wait(timeout=400)
    finally:
        if sup.poll() is None:
            sup.kill()
        # the supervisor forwards nothing on SIGKILL: reap any gang
        # members it left behind so a timed-out test cannot leave two
        # training processes burning CPU under the rest of the suite
        out = subprocess.run(["ps", "-eo", "pid,ppid"],
                             capture_output=True, text=True).stdout
        stray = [int(l.split()[0]) for l in out.splitlines()[1:]
                 if l.split()[1] == str(sup.pid)] + [
                m for m in members if os.path.exists(f"/proc/{m}")]
        for pid in set(stray):
            try:
                os.kill(pid, signal.SIGKILL)
            except OSError:
                pass
    text = log.read_text()
    assert rc == 0, text[-2000:]
    assert "killing the gang" in text, text[-2000:]
    assert "resumed from" in text, text[-2000:]
    assert "[elastic] attempt 2" in text, text[-2000:]

# ------------------------------------------------- heartbeat hygiene


def test_supervisor_cleans_up_owned_heartbeat_file(tmp_path):
    """ADVICE r4: a supervisor that mkstemp'd its own heartbeat file
    must unlink it when run() returns."""
    cmd = _script(tmp_path, "raise SystemExit(0)")
    sup = Supervisor(cmd, RestartPolicy(max_restarts=1, backoff=0.01),
                     hang_timeout=30.0, poll_interval=0.1,
                     log=lambda *_: None)
    hb = Path(sup.heartbeat_file)
    assert hb.exists()
    assert sup.run() == 0
    assert not hb.exists()


def test_supervisor_leaves_caller_owned_heartbeat_file(tmp_path):
    """A heartbeat file the CALLER passed is not ours to delete."""
    hb = tmp_path / "hb"
    hb.touch()
    cmd = _script(tmp_path, "raise SystemExit(0)") + [
        "--heartbeat-file", str(hb)]
    sup = Supervisor(cmd, RestartPolicy(max_restarts=1, backoff=0.01),
                     hang_timeout=30.0, poll_interval=0.1,
                     log=lambda *_: None)
    assert sup.run() == 0
    assert hb.exists()


# ------------------------------- failure-class supervision (round 10)


def test_policy_per_class_backoff_is_independent():
    """Each failure class doubles on its own stream: two crashes then a
    hang — the hang starts from the base backoff, not the crash
    stream's doubled value."""
    p = RestartPolicy(max_restarts=10, backoff=1.0, backoff_max=64.0)
    assert p.next_restart("crash") == 1.0
    assert p.next_restart("crash") == 2.0
    assert p.next_restart("hang") == 1.0   # own stream
    assert p.next_restart("crash") == 4.0
    assert p.next_restart("hang") == 2.0
    p.record_run(1e9)  # healthy run resets every stream and the budget
    assert p.next_restart("crash") == 1.0
    assert p.next_restart("hang") == 1.0


def test_policy_jitter_is_seeded_and_bounded():
    delays = [RestartPolicy(max_restarts=4, backoff=2.0, jitter=0.5,
                            seed=7) for _ in range(2)]
    seq = [[pol.next_restart("crash") for _ in range(3)]
           for pol in delays]
    assert seq[0] == seq[1]  # same seed -> same jitter stream
    for base, got in zip([2.0, 4.0, 8.0], seq[0]):
        assert base <= got <= base * 1.5  # stretch, never shrink
    other = RestartPolicy(max_restarts=4, backoff=2.0, jitter=0.5,
                          seed=8)
    assert [other.next_restart("crash")
            for _ in range(3)] != seq[0]  # the seed matters


def _ledger_stamps(path, kind):
    return [json.loads(l) for l in Path(path).read_text().splitlines()
            if json.loads(l).get("kind") == kind]


def test_supervisor_stamps_fail_class_crash_and_corrupt(tmp_path):
    """Exit-code classification rides the restart stamps: a generic
    nonzero exit is 'crash'; EXIT_CORRUPT_CKPT is 'corrupt_ckpt'."""
    from shallowspeed_tpu.elastic import EXIT_CORRUPT_CKPT

    log = tmp_path / "m.jsonl"
    log.write_text("")
    for code, expect in ((3, "crash"),
                        (EXIT_CORRUPT_CKPT, "corrupt_ckpt")):
        marker = tmp_path / f"ran_{code}"
        cmd = _script(tmp_path, f"""
            from pathlib import Path
            m = Path({str(marker)!r})
            if m.exists():
                raise SystemExit(0)
            m.write_text('x')
            raise SystemExit({code})
        """)
        sup = Supervisor(cmd,
                         RestartPolicy(max_restarts=2, backoff=0.01),
                         ledger_file=str(log), log=lambda *_: None)
        assert sup.run() == 0
    classes = [r["fail_class"] for r in
               _ledger_stamps(log, "restart_downtime")]
    assert classes == ["crash", "corrupt_ckpt"]


def test_supervisor_numeric_class_via_dead_heartbeat(tmp_path):
    """A beating-but-dead child (heartbeat status 'dead ...') is
    killed and classed 'numeric'."""
    log = tmp_path / "m.jsonl"
    log.write_text("")
    hb = tmp_path / "hb"
    marker = tmp_path / "died_once"
    cmd = _script(tmp_path, f"""
        import time
        from pathlib import Path
        m = Path({str(marker)!r})
        if m.exists():
            raise SystemExit(0)
        m.write_text('x')
        Path({str(hb)!r}).write_text('dead nonfinite gradients')
        time.sleep(60)   # still 'alive' — only the status says dead
    """) + ["--heartbeat-file", str(hb)]
    sup = Supervisor(cmd, RestartPolicy(max_restarts=2, backoff=0.01),
                     hang_timeout=30.0, poll_interval=0.1,
                     term_grace=2.0, ledger_file=str(log),
                     log=lambda *_: None)
    t0 = time.monotonic()
    assert sup.run() == 0
    assert time.monotonic() - t0 < 40  # killed on status, not timeout
    stamps = _ledger_stamps(log, "restart_downtime")
    assert [r["fail_class"] for r in stamps] == ["numeric"]


def test_supervisor_poison_step_aborts_with_forensics(tmp_path):
    """The same step failing twice in a row is a poison step: labeled
    abort + forensic snapshot after TWO attempts, not a crash loop
    that burns the whole budget."""
    log = tmp_path / "m.jsonl"
    attempts = tmp_path / "attempts"
    cmd = _script(tmp_path, f"""
        import json
        from pathlib import Path
        a = Path({str(attempts)!r})
        n = int(a.read_text()) if a.exists() else 0
        a.write_text(str(n + 1))
        with open({str(log)!r}, 'a') as f:
            f.write(json.dumps({{"event": "step", "step": 7,
                                 "loss": 1.0, "tokens_per_sec": 1.0,
                                 "t": 0.1}}) + chr(10))
        raise SystemExit(9)   # always dies right after step 7
    """)
    sup = Supervisor(cmd, RestartPolicy(max_restarts=10, backoff=0.01),
                     ledger_file=str(log), log=lambda *_: None)
    assert sup.run() == 9
    assert attempts.read_text() == "2"  # aborted at the second strike
    aborts = _ledger_stamps(log, "poison_step_abort")
    assert len(aborts) == 1 and aborts[0]["step"] == 7
    snap = json.loads(
        Path(f"{log}.poison_step_7.json").read_text())
    assert snap["poison_step"] == 7 and snap["fail_class"] == "crash"
    assert snap["metrics_tail"]


def test_term_grace_lets_child_flush_before_sigkill(tmp_path):
    """The satellite contract: a hang-kill sends SIGTERM first, and a
    child whose handler flushes state gets `term_grace` to do it —
    the goodput-ledger tail survives the kill."""
    flushed = tmp_path / "flushed"
    marker = tmp_path / "hung_once"
    hb = tmp_path / "hb"
    cmd = _script(tmp_path, f"""
        import signal, sys, time
        from pathlib import Path
        m = Path({str(marker)!r})
        if m.exists():
            raise SystemExit(0)
        m.write_text('x')
        def flush(signum, frame):
            Path({str(flushed)!r}).write_text('ledger tail')
            sys.exit(143)
        signal.signal(signal.SIGTERM, flush)
        time.sleep(120)   # hung: never beats
    """) + ["--heartbeat-file", str(hb)]
    sup = Supervisor(cmd, RestartPolicy(max_restarts=2, backoff=0.01),
                     hang_timeout=8.0, poll_interval=0.2,
                     term_grace=10.0, log=lambda *_: None)
    assert sup.run() == 0
    assert flushed.read_text() == "ledger tail"


def test_gang_supervisor_cleans_up_heartbeat_files(tmp_path):
    """ADVICE r4: gang mode injects N tmpfiles; all N must be unlinked
    when run() returns (long-lived hosts run many gangs)."""
    from shallowspeed_tpu.elastic import GangSupervisor

    cmd = _script(tmp_path, "raise SystemExit(0)")
    sup = GangSupervisor(cmd, n_procs=2,
                         policy=RestartPolicy(max_restarts=1,
                                              backoff=0.01),
                         hang_timeout=30.0, poll_interval=0.1,
                         log=lambda *_: None)
    paths = [Path(p) for p in sup.heartbeat_files]
    assert len(paths) == 2 and all(p.exists() for p in paths)
    assert sup.run() == 0
    assert not any(p.exists() for p in paths)
