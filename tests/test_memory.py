"""Memory observatory (`telemetry/memory.py`, round 20): live HBM
accounting, the per-owner ownership registry, leak/drift detection,
and the serving OOM forensics path.

The load-bearing invariants:

- **Accounting never invents bytes.** Every live array is claimed at
  most once (first registered owner wins), so tracked <= live and the
  `untracked` residual is >= 0 by construction; stale resolver leaves
  (donated-away buffers) cost 0.
- **The OOM drill recovers AND explains itself.** A seeded
  block-exhaustion run completes every stream, stamps a typed `oom`
  ledger line that validates at schema v15, and hands its forensics
  listeners a payload whose allocator snapshot satisfies
  n_free + n_live + n_cold == n_usable with the top owner named.
- **Detection is two-sided.** `MemoryWatch` catches step changes by
  robust z-spike (mem_drift) and slow leaks by monotone-growth run
  (mem_leak) — each blind to the other's failure mode.
"""

import json
from pathlib import Path

import jax
import numpy as np
import pytest

from shallowspeed_tpu.models import transformer as T
from shallowspeed_tpu.serving import (BlockAllocator, OutOfBlocks,
                                      ServingEngine, blocks_for)
from shallowspeed_tpu.telemetry import memory

CFG = T.TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                          max_seq=128)


@pytest.fixture(scope="module")
def params():
    return jax.device_put(T.init(CFG, seed=1))


@pytest.fixture(autouse=True)
def _clean_registry():
    """The registry is module-global observability state; tests must
    not leak owners (or resolvers closing over test arrays) into each
    other."""
    memory.clear_owners()
    yield
    memory.clear_owners()


def toks(seed=0, t=12, vocab=64):
    rng = np.random.default_rng(seed)
    return rng.integers(0, vocab, (t,)).astype(np.int32)


# ------------------------------------------------- sampling primitives


def test_live_hbm_high_water_counts_resident_shards():
    a = jax.device_put(np.ones((64, 64), np.float32))   # 16 KiB
    hw = memory.live_hbm_high_water()
    assert hw["n_arrays"] >= 1
    assert hw["max_device_bytes"] >= a.nbytes
    assert sum(hw["per_device"].values()) >= a.nbytes
    # per-device sums are what max_device_bytes reduces over
    assert hw["max_device_bytes"] == max(hw["per_device"].values())
    del a


def test_static_peak_bytes_matches_walker():
    from shallowspeed_tpu.analysis.walker import peak_bytes

    x = jax.ShapeDtypeStruct((128, 128), np.float32)
    fn = lambda v: (v @ v) + 1.0                       # noqa: E731
    got = memory.static_peak_bytes(fn, x)
    assert got == peak_bytes(jax.make_jaxpr(fn)(x).jaxpr)
    assert got >= 2 * 128 * 128 * 4    # input + matmul result live


def test_cross_check_bound_semantics():
    ok = memory.cross_check(100, 100)
    assert ok["within_bound"] and ok["ratio"] == 1.0
    assert memory.cross_check(104, 100)["within_bound"]   # inside 1.05
    bad = memory.cross_check(120, 100)
    assert not bad["within_bound"] and bad["ratio"] == 1.2
    # zero static prediction never divides by zero
    assert memory.cross_check(0, 0)["within_bound"]


def test_device_memory_stats_empty_on_cpu():
    stats = memory.device_memory_stats()
    if jax.devices()[0].platform == "cpu":
        assert stats == {}
    else:  # pragma: no cover — TPU/GPU CI
        for st in stats.values():
            assert all(isinstance(v, int) for v in st.values())


def test_host_rss_bytes_positive_and_plausible():
    rss = memory.host_rss_bytes()
    assert rss > 1 << 20        # a python + jax process holds > 1 MiB
    assert rss < 1 << 44


# ------------------------------------------------- ownership registry


def test_registry_accounting_first_owner_wins():
    a = jax.device_put(np.ones((32, 32), np.float32))
    b = jax.device_put(np.ones((16, 16), np.float32))
    memory.register_owner("first", lambda: {"w": a})
    memory.register_owner("second", lambda: [a, b])   # a already claimed
    assert memory.registered_owners() == ("first", "second")
    acct = memory.per_owner_accounting()
    assert acct["owners"]["first"] == a.nbytes
    assert acct["owners"]["second"] == b.nbytes       # a not re-counted
    assert acct["tracked_bytes"] == sum(acct["owners"].values())
    assert acct["untracked_bytes"] >= 0
    assert acct["tracked_bytes"] + acct["untracked_bytes"] \
        == acct["live_bytes"]
    del a, b


def test_registry_stale_and_broken_resolvers_cost_zero():
    gone = jax.device_put(np.ones((8, 8), np.float32))
    nb = gone.nbytes
    memory.register_owner("stale", lambda g=gone: g)
    live0 = memory.per_owner_accounting()
    assert live0["owners"]["stale"] == nb
    gone.delete()    # donated-away / deleted: resolver is now stale
    acct = memory.per_owner_accounting()
    assert acct["owners"]["stale"] == 0
    memory.register_owner("none", lambda: None)
    memory.register_owner("boom", lambda: 1 / 0)
    acct = memory.per_owner_accounting()
    assert acct["owners"]["none"] == 0 and acct["owners"]["boom"] == 0
    assert acct["untracked_bytes"] >= 0
    with pytest.raises(TypeError, match="callable"):
        memory.register_owner("bad", "not-a-resolver")
    memory.unregister_owner("stale")
    assert "stale" not in memory.registered_owners()


def test_top_live_arrays_names_owners():
    big = jax.device_put(np.ones((256, 256), np.float32))   # 256 KiB
    memory.register_owner("test.big", lambda: big)
    assert 1 <= len(memory.top_live_arrays(3)) <= 3
    # every live array: a worker that has run other tests holds larger
    # ones than this 256 KiB, and the top three need not include it
    top = memory.top_live_arrays(len(jax.live_arrays()))
    sizes = [r["nbytes"] for r in top]
    assert sizes == sorted(sizes, reverse=True)
    mine = [r for r in top if r["owner"] == "test.big"]
    assert mine and mine[0]["shape"] == [256, 256]
    assert mine[0]["dtype"] == "float32"
    assert memory.top_live_arrays(0) == []
    del big


def test_forensics_payload_shape():
    x = jax.device_put(np.ones((64, 64), np.float32))
    memory.register_owner("test.x", lambda: x)
    f = memory.forensics(top_k=2)
    assert sorted(f) == ["accounting", "device_stats", "host_rss_bytes",
                         "top_arrays"]
    assert f["accounting"]["owners"]["test.x"] == x.nbytes
    assert len(f["top_arrays"]) == 2
    assert f["host_rss_bytes"] > 0
    json.dumps(f)    # flight-dump payload must be JSON-serializable
    del x


# ------------------------------------------------- leak/drift detector


def test_memory_watch_leak_fires_once_on_sustained_growth():
    w = memory.MemoryWatch(spike_z=1e9, patience=4, growth_frac=0.01)
    verdicts = []
    x = 1e6
    for step in range(10):
        x *= 1.05                          # 5% growth every window
        verdicts += w.observe(step, device_bytes=x)
    leaks = [v for v in verdicts if v.kind == "mem_leak"]
    assert len(leaks) == 1                 # reported once, not per step
    assert leaks[0].severity == "error"
    assert leaks[0].step == 4              # patience-th growth window
    # plateau resets the run; renewed growth can re-report
    for step in range(10, 14):
        assert w.observe(step, device_bytes=x) == []
    again = []
    for step in range(14, 25):
        x *= 1.05
        again += w.observe(step, device_bytes=x)
    assert [v.kind for v in again].count("mem_leak") == 1


def test_memory_watch_drift_spikes_on_step_change():
    w = memory.MemoryWatch(spike_z=6.0, patience=1000, warmup=4)
    out = []
    for step in range(20):
        out += w.observe(step, device_bytes=1e6)   # flat steady state
    assert out == []
    spiked = w.observe(20, device_bytes=2e6)   # residency doubled
    assert [v.kind for v in spiked] == ["mem_drift"]
    assert "robust sigmas" in spiked[0].detail


def test_memory_watch_series_are_independent():
    w = memory.MemoryWatch(spike_z=1e9, patience=3, growth_frac=0.01)
    rss, dev = 1e6, 1e6
    hits = []
    for step in range(8):
        rss *= 1.1                         # host leaks, device flat
        hits += w.observe(step, device_bytes=dev, rss_bytes=rss)
    assert [v.kind for v in hits] == ["mem_leak"]
    assert "host_rss" in hits[0].detail
    # rss_bytes=0 (unavailable) is skipped, not treated as a crash to 0
    assert w.observe(99, rss_bytes=0) == []


def test_guard_policy_covers_memory_kinds():
    from shallowspeed_tpu.telemetry.anomaly import GuardPolicy

    for mode in ("monitor", "guard"):
        pol = GuardPolicy.for_mode(mode)
        assert pol.action("mem_leak") == "warn"
        assert pol.action("mem_drift") == "warn"


# ------------------------------------------- typed OutOfBlocks payload


def test_out_of_blocks_typed_payload_and_snapshot():
    al = BlockAllocator(8)
    ids = al.alloc(3, rid="warm")
    snap = al.snapshot()
    assert snap["n_usable"] == 7 and snap["n_live"] == 3
    assert snap["peak_live"] == 3 and snap["consistent"]
    with pytest.raises(OutOfBlocks) as ei:
        al.alloc(9, rid="req-7")
    e = ei.value
    assert (e.requested, e.n_free, e.n_cold, e.n_live) == (9, 4, 0, 3)
    assert e.rid == "req-7"
    # historical message shape preserved (pre-typed callers matched it)
    assert "need 9 blocks, 4 free + 0 cold" in str(e)
    assert "'req-7'" in str(e)
    # all-or-nothing: the failed alloc changed nothing
    assert al.snapshot() == snap
    al.free(ids)
    done = al.snapshot()
    assert done["n_free"] == done["n_usable"]
    assert done["peak_live"] == 3          # high-water survives drain
    # rid is optional; the payload still carries the counts
    plain = OutOfBlocks(2, n_free=1)
    assert plain.rid is None and "request" not in str(plain)


# --------------------------------------------- engine capacity plane


def test_engine_headroom_deficit_model(params):
    eng = ServingEngine(params, CFG, n_blocks=14, block_size=8,
                        max_slots=4, prefill_chunk=16)
    hr0 = eng.headroom()
    assert hr0 == {"live_blocks": 0, "blocks_needed": 0,
                   "headroom_blocks": 13}
    # one queued request's deficit = its full final footprint
    eng.submit(toks(0, t=24), 16, rid="a")
    need_a = blocks_for(24 + 16 - 1, 8)
    assert eng.headroom()["blocks_needed"] == need_a
    assert eng.headroom()["headroom_blocks"] == 13 - need_a
    # overcommit: accepted max-token budgets exceed the pool
    eng.submit(toks(1, t=24), 16, rid="b")
    eng.submit(toks(2, t=24), 16, rid="c")
    assert eng.headroom()["headroom_blocks"] < 0
    eng.run()
    end = eng.headroom()
    assert end["blocks_needed"] == 0 and end["live_blocks"] == 0


def test_oom_drill_recovers_with_forensics(params, tmp_path):
    """THE pinned OOM drill: seeded block exhaustion must recover via
    the evict path (every stream completes), stamp typed `oom` ledger
    lines that validate at schema v15, and hand the forensics listener
    a payload that names the top owner and self-checks the allocator
    invariant."""
    from shallowspeed_tpu.metrics import MetricsLogger
    from shallowspeed_tpu.telemetry import schema

    assert schema.SCHEMA_VERSION >= 15
    path = tmp_path / "oomdrill.jsonl"
    # 13 usable blocks * 8 = 104 positions < 3 * (24 + 16) = 120
    eng = ServingEngine(params, CFG, n_blocks=14, block_size=8,
                        max_slots=4, prefill_chunk=16,
                        metrics=MetricsLogger(path, kind="serve"),
                        log_every=1)
    dumps = []
    eng.oom_listeners.append(
        lambda en, exc: dumps.append(en.oom_forensics(exc)))
    for i, k in enumerate("abc"):
        eng.submit(toks(50 + i, t=24), 16, rid=k)
    res = eng.run()

    # recovery: every stream completed despite exhaustion
    assert set(res) == set("abc")
    assert all(len(r) == 16 for r in res.values())   # full max_new each
    assert eng.counters["oom_events"] >= 1
    assert eng.counters["preempted"] >= 1
    assert eng.alloc.n_free == eng.alloc.n_usable

    # forensics: listener got the rich payload at exhaustion time
    d = dumps[0]
    snap = d["allocator"]
    assert snap["consistent"]
    assert snap["n_free"] + snap["n_live"] + snap["n_cold"] \
        == snap["n_usable"]
    assert snap["n_live"] > 0              # exhaustion, not a leak
    acct = d["accounting"]
    assert acct["owners"]["serving.params"] > 0
    assert acct["owners"]["serving.kv_pools"] > 0
    assert acct["untracked_bytes"] >= 0
    top_owner = max(acct["owners"], key=acct["owners"].get)
    assert top_owner in ("serving.params", "serving.kv_pools")
    assert d["oom"]["requested"] >= 1
    assert d["headroom"]["headroom_blocks"] < 0    # overcommitted
    assert d["in_flight"] and d["block_tables"]
    assert all(w >= 1 for w in d["block_tables"].values())
    json.dumps(d)                          # flight-dump serializable

    # the metrics log validates and carries the v15 surface
    assert schema.validate_file(path) == []
    recs = [json.loads(ln) for ln in path.read_text().splitlines()]
    ooms = [r for r in recs
            if r.get("event") == "ledger" and r.get("kind") == "oom"]
    assert ooms
    for r in ooms:
        assert r["requested"] >= 1
        assert r["free"] + r["cold"] < r["requested"]
        assert "live" in r and "tick" in r
    gens = [r for r in recs if r.get("event") == "generate"]
    assert gens
    for g in gens:
        assert "headroom_blocks" in g and "live_blocks" in g
        assert "blocks_needed" in g
    # one ledger stamp per pressure episode (tick), not per retry
    assert len(ooms) == len({r["tick"] for r in ooms})

    # goodput reduces the same log into the memory block
    from shallowspeed_tpu.telemetry.goodput import (format_report,
                                                    run_goodput)

    rep = run_goodput(path)
    mem = rep["memory"]
    assert mem["oom_events"] == len(ooms)
    assert mem["worst_headroom_blocks"] < 0
    assert mem["worst_oom"]["requested"] >= 1
    assert mem["final_live_blocks"] == 0
    text = format_report(rep)
    assert "memory:" in text and "recovered OOM" in text


def test_goodput_memory_block_absent_without_memory_lines(tmp_path):
    from shallowspeed_tpu.telemetry.goodput import run_goodput

    path = tmp_path / "plain.jsonl"
    path.write_text(json.dumps(
        {"event": "step", "step": 1, "loss": 1.0, "wall": 1.0,
         "tokens_per_sec": 10.0}) + "\n")
    assert run_goodput(path)["memory"] is None


# --------------------------------------------- monitor + fleet surface


def test_monitor_memory_surface_and_oom_flight_dump(tmp_path):
    from shallowspeed_tpu.telemetry.monitor import Monitor

    mon = Monitor(flight=16, flight_dir=tmp_path, snapshot_every=0)
    mon.note_line({"event": "step", "step": 4, "loss": 1.0, "wall": 1.0,
                   "hbm_live_mib": 12.5,
                   "hbm_owned_mib": {"train.params": 8.0},
                   "hbm_untracked_mib": 4.5, "host_rss_mib": 900.0,
                   "hbm_within_bound": True})
    st = mon.status()
    assert st["memory"]["hbm_owned_mib"] == {"train.params": 8.0}
    assert st["memory"]["host_rss_mib"] == 900.0
    prom = mon.prometheus()
    assert "shallowspeed_hbm_live_mib 12.5" in prom
    assert "shallowspeed_host_rss_mib 900" in prom
    # a tailer-mode oom ledger line keeps the stamp AND dumps flight
    mon.note_line({"event": "ledger", "kind": "oom", "tick": 9,
                   "requested": 3, "free": 1, "cold": 0, "live": 12,
                   "wall": 2.0})
    assert mon.memory["last_oom"]["requested"] == 3
    assert mon.counters["flight_dumps"] == 1
    dump = json.loads(Path(mon.flight.dumps[0]).read_text())
    assert dump["reason"] == "oom" and dump["step"] == 9
    # the live-mode path: the engine listener's rich payload wins the
    # (reason, step) dedup when it arrives FIRST
    mon2 = Monitor(flight=16, flight_dir=tmp_path / "live",
                   snapshot_every=0)
    mon2.memory_flight_dump({"accounting": {"owners": {}}}, step=3)
    mon2.note_line({"event": "ledger", "kind": "oom", "tick": 3,
                    "requested": 2, "free": 0, "cold": 0, "live": 5,
                    "wall": 1.0})
    assert mon2.counters["flight_dumps"] == 1      # deduped
    rich = json.loads(Path(mon2.flight.dumps[0]).read_text())
    assert rich["trigger"] == {"accounting": {"owners": {}}}
    assert mon2.memory["oom_forensics"] == {"accounting": {"owners": {}}}
    # a mem_verdicts step line trips the incident path + health warn
    mon2.note_line({"event": "step", "step": 8, "loss": 1.0,
                    "wall": 3.0,
                    "mem_verdicts": ["[health] mem_leak at step 8: x"]})
    assert mon2.health.startswith("warn:")
    assert mon2.counters["flight_dumps"] == 2


def test_step_line_memory_fields_validate_v15():
    from shallowspeed_tpu.telemetry import schema

    line = {"event": "step", "step": 3, "loss": 2.0,
            "tokens_per_sec": 5.0, "hbm_owned_mib": {"a": 1.0},
            "hbm_untracked_mib": 0.5, "host_rss_mib": 100.0,
            "mem_verdicts": ["[health] mem_drift at step 3: y"]}
    assert schema.validate_line(line) == []
    assert schema.validate_line(
        {**line, "hbm_untracked_mib": "lots"}) != []
    assert schema.validate_line({**line, "hbm_owned_mib": 3}) != []


def test_fleet_memory_rollup_and_digest():
    from shallowspeed_tpu.telemetry.fleet import (FleetCollector,
                                                  format_fleet_status)

    fc = FleetCollector()
    r0 = fc.add_url("http://127.0.0.1:1/status.json", "r0")
    r1 = fc.add_url("http://127.0.0.1:2/status.json", "r1")
    # inject polled payloads directly (what refresh() would store)
    r0._status = {"serving": {"headroom_blocks": 11, "queue_depth": 0},
                  "memory": {"hbm_live_mib": 10.0}}
    r1._status = {"serving": {"headroom_blocks": -4, "queue_depth": 2},
                  "memory": {"hbm_live_mib": 30.0,
                             "last_oom": {"requested": 2, "tick": 7}}}
    st = fc.status()
    mem = st["memory"]
    assert mem["headroom_blocks"] == {"r0": 11, "r1": -4}
    assert mem["worst_headroom"] == {"replica": "r1", "value": -4}
    assert mem["oom_recovered"] == ["r1"]
    assert mem["replicas"]["r1"]["hbm_live_mib"] == 30.0
    text = format_fleet_status(st)
    assert "worst headroom -4 blocks @ r1" in text
    assert "OOM recovered: r1" in text
