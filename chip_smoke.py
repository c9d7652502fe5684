"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives both hot paths once through the entry points a user would call, at
the full width of the one LM shape with a driver record on a chip (the
round-5 `transformer_mfu` configuration: d_model 2048, 16 heads of 128,
4 layers, seq 2048, batch 8, vocab 256, RoPE + RMSNorm + SwiGLU, bf16,
AdamW), with seeded random weights and seeded synthetic data:

- **train**: `python train_lm.py ... --attn flash --steps N` — every
  step's loss finite, the last below the first, TF/s and MFU on every
  step line (the peak for this `device_kind` came from `flops.py`), no
  recompile after step 1, the lowered step carries the Mosaic call;
- **serve**: `python serve.py ...` two ways (a bf16 cache, and
  `--kv-quant int8`), each run twice — a first wave of mixed-length
  requests, then that wave plus a second wave of the same shapes
  arriving mid-run: every request answered with exactly `max_new`
  tokens, no error line, the allocator balanced at drain, the
  executable counts identical between the two runs, every decode tick
  carrying the Mosaic call (the tick reads its pools through the paged
  kernel);
- **kernels**: every Pallas entry point, compiled, against its XLA
  reference, at this table's shapes and at the checks' own defaults;
- with four or more devices, **train** again as `--dp 4` and as
  `--dp 2 --pp 2 --pp-schedule 1f1b`, each with every device holding its
  share of the state.

It runs only on a TPU: the first thing it reports is the device as JAX
sees it, and anything but `tpu` is a failure that names what it found.
This parent never initialises a JAX backend — a chip belongs to one
process at a time — so every phase is a child process, run one after
another, killed with its process group if it outlives its time limit.

    python chip_smoke.py [--out DIR]

Prints one line per phase, then a `summary: {...}` line with every
phase's findings (also written to `<out>/summary.json`), then, as the
last line of stdout, ONE JSON object with exactly these keys:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
Exit 0 only if every check of every phase held; 1 when a check failed;
2 when there is no TPU (and then no JSON result at all).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

# Every size in one place, so tests/ drive the same phases at toy width
# on the CPU and the commands can be debugged before chip time is spent.
SIZES = {
    "chip": {
        "model": {"d_model": 2048, "n_heads": 16, "n_layers": 4,
                  "vocab": 256},
        # lr: at this width train_lm's default 1e-3, with no warm-up,
        # overshoots (loss 6.19 -> 8.25 by step 4, then noisy); 1e-4
        # falls cleanly, 6.19 -> 5.30 in 16 steps (chip run, PR 21)
        "train": {"seq_len": 2048, "batch_size": 8, "steps": 12,
                  "lr": "1e-4"},
        "serve": {"max_seq": 2048, "n_blocks": 128, "block_size": 16,
                  "slots": 4, "prefill_chunk": 64},
        # ascending, so the decode tick visits every table-width bucket
        # (4, 8, 16 blocks) within the first wave; three are longer than
        # one prefill chunk
        "requests": {"prompt_lens": [24, 40, 70, 150], "max_new": 12},
        "kernels": {
            "flash": {"b": 1, "t": 2048, "h": 16, "d": 128, "gqa_kvh": 4,
                      "window": 256},
            "paged": {"d_model": 2048, "n_heads": 16, "gqa_kvh": 4,
                      "window": 24},
            # 20 query heads on 4 K/V heads of 128: a group of FIVE rows
            # a K/V head's matmul, which no power-of-two model sends
            "paged_group5": {"d_model": 2560, "n_heads": 20, "gqa_kvh": 4},
        },
        "timeout": {"train": 600, "serve": 300, "kernels": 600},
    },
    "toy": {
        "model": {"d_model": 32, "n_heads": 2, "n_layers": 2, "vocab": 64},
        # at this width the loss falls ~0.005 a step under ~0.1 of
        # batch-to-batch noise: 40 steps make "last below first" hold
        "train": {"seq_len": 64, "batch_size": 4, "steps": 40,
                  "lr": "1e-3"},
        "serve": {"max_seq": 128, "n_blocks": 48, "block_size": 8,
                  "slots": 3, "prefill_chunk": 16},
        "requests": {"prompt_lens": [6, 12, 20, 40], "max_new": 5},
        "kernels": {
            "flash": {"b": 1, "t": 128, "h": 2, "d": 32, "gqa_kvh": 1,
                      "window": 32},
            "paged": {"d_model": 64, "n_heads": 2, "gqa_kvh": 1,
                      "window": 24},
            "paged_group5": {"d_model": 160, "n_heads": 5, "gqa_kvh": 1},
        },
        "timeout": {"train": 300, "serve": 300, "kernels": 600},
    },
}

SERVE_CONFIGS = {
    "dense": [],
    "int8": ["--kv-quant", "int8"],
}

# the four-chip training layouts the smoke runs itself (ISSUE 21 §6)
MULTICHIP_TRAIN = {
    "train_dp4": ["--dp", "4"],
    "train_dp2_pp2": ["--dp", "2", "--pp", "2", "--pp-schedule", "1f1b"],
}

MOSAIC_CALL = "tpu_custom_call"


# ------------------------------------------------------------ children


def run_child(argv, *, out_path: Path, timeout: float, env=None) -> dict:
    """Run one phase child to completion from the repo root, stdout and
    stderr to `out_path`. The child leads its own process group, and the
    whole group is killed if it outlives `timeout` or this parent is
    interrupted — the smoke stops every process it starts."""
    t_spawn = time.time()
    with open(out_path, "w") as fh:
        proc = subprocess.Popen(argv, stdout=fh, stderr=subprocess.STDOUT,
                                stdin=subprocess.DEVNULL, cwd=str(ROOT),
                                env=env, start_new_session=True)
        timed_out = False
        try:
            proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            timed_out = True
        finally:
            if proc.poll() is None:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
                proc.wait()
    return {"rc": proc.returncode, "timed_out": timed_out,
            "t_spawn": t_spawn, "wall_s": round(time.time() - t_spawn, 1)}


def child_env(base, ir_dir: Path) -> dict:
    """The child's environment: the caller's, plus a dump of every
    program JAX lowers (StableHLO text, written at lowering time — so it
    is there on a compile-cache hit too). That dump is where the smoke
    reads whether the REAL step / tick carries the Mosaic call."""
    env = dict(os.environ if base is None else base)
    env["JAX_DUMP_IR_TO"] = str(ir_dir)
    return env


def fresh(*paths: Path) -> None:
    """Remove this phase's own outputs of an earlier run (the metrics
    logger appends)."""
    for p in paths:
        if p.is_dir():
            shutil.rmtree(p)
        elif p.exists():
            p.unlink()


def read_jsonl(path: Path) -> list[dict]:
    """The JSON-object lines of a file (drivers mix in plain text)."""
    events = []
    if not path.exists():
        return events
    for line in path.read_text(errors="replace").splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                events.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    return events


def mosaic_programs(ir_dir: Path) -> list[str]:
    """Names of the lowered programs whose text holds a Mosaic call."""
    names = []
    if ir_dir.is_dir():
        for f in sorted(ir_dir.iterdir()):
            if MOSAIC_CALL in f.read_text(errors="replace"):
                names.append(f.name)
    return names


def child_failure(run: dict, console: Path) -> list[str]:
    """A child that did not exit 0, as one sentence with the end of its
    console; [] for one that did."""
    if run["rc"] == 0:
        return []
    lines = (console.read_text(errors="replace").splitlines()
             if console.exists() else [])
    return [f"exit code {run['rc']}"
            + (" (timed out, killed)" if run["timed_out"] else "")
            + ":\n" + "\n".join(lines[-12:])]


# --------------------------------------------------------------- train


def train_argv(size: str, log: Path, trace_dir: Path, extra=()) -> list:
    m, t = SIZES[size]["model"], SIZES[size]["train"]
    return [sys.executable, "train_lm.py",
            "--d-model", str(m["d_model"]), "--n-heads", str(m["n_heads"]),
            "--n-layers", str(m["n_layers"]),
            "--seq-len", str(t["seq_len"]),
            "--batch-size", str(t["batch_size"]),
            "--bf16", "--rope", "--norm", "rmsnorm", "--ffn", "swiglu",
            "--attn", "flash", "--optimizer", "adamw", "--lr", t["lr"],
            "--steps", str(t["steps"]), "--log-every", "1",
            "--log-file", str(log),
            # steps-level telemetry: the recompile counter on every step
            # line, and telemetry.json with every device's share
            "--telemetry", "steps", "--trace-dir", str(trace_dir),
            *extra]


def check_train(events, *, steps: int, on_tpu: bool, mosaic: list,
                summary: dict | None, n_devices: int = 1) -> list[str]:
    """Every way the train phase can have failed, as sentences."""
    bad = []
    lines = [e for e in events if e.get("event") == "step"]
    if len(lines) != steps:
        return [f"{len(lines)} step lines, expected {steps}"]
    losses = [e["loss"] for e in lines]
    if not all(isinstance(x, (int, float)) and math.isfinite(x)
               for x in losses):
        bad.append(f"non-finite loss in {losses}")
    elif not losses[-1] < losses[0]:
        bad.append(f"loss did not fall: first {losses[0]}, "
                   f"last {losses[-1]}")
    for e in lines:
        if "recompiles" not in e:
            bad.append(f"step {e['step']}: no recompile counter on the "
                       f"step line")
            break
    if any(e.get("recompiles") for e in lines[1:]):
        bad.append("RECOMPILES after step 1: "
                   + str([e.get("recompiles") for e in lines]))
    if on_tpu:
        if not all(e.get("tflops") and e.get("mfu") for e in lines):
            bad.append("step lines lack TF/s or MFU (no peak for this "
                       "device_kind in flops.py?)")
        if not any("step" in name for name in mosaic):
            bad.append(f"no lowered step program holds a {MOSAIC_CALL} "
                       f"(found it in: {mosaic or 'nothing'})")
    if n_devices > 1:
        if summary is None:
            bad.append("no telemetry.json: cannot see the devices' share")
        else:
            live = summary.get("hbm_live_per_device", {})
            stats = summary.get("device_stats", {})
            if len(live) != n_devices:
                bad.append(f"live arrays on {len(live)} device(s), "
                           f"expected {n_devices}: {live}")
            elif min(live.values()) < 0.2 * max(live.values()):
                bad.append(f"a device holds under a fifth of the "
                           f"fullest one's live bytes: {live}")
            in_use = {d: s.get("bytes_in_use", 0)
                      for d, s in stats.items()}
            if on_tpu and (len(in_use) != n_devices
                           or not all(v > 0 for v in in_use.values())):
                bad.append(f"memory_stats() shows bytes_in_use on fewer "
                           f"than {n_devices} devices: {in_use}")
    return bad


def run_train_phase(size: str, out: Path, *, name: str = "train",
                    extra=(), n_devices: int = 1, on_tpu: bool = True,
                    env=None) -> dict:
    log, trace_dir = out / f"{name}.jsonl", out / f"{name}_trace"
    ir_dir, console = out / f"{name}_ir", out / f"{name}.out"
    fresh(log, trace_dir, ir_dir)
    run = run_child(train_argv(size, log, trace_dir, extra),
                    out_path=console,
                    timeout=SIZES[size]["timeout"]["train"],
                    env=child_env(env, ir_dir))
    events = read_jsonl(log)
    tele = trace_dir / "telemetry.json"
    summary = json.loads(tele.read_text()) if tele.exists() else None
    mosaic = mosaic_programs(ir_dir)
    bad = child_failure(run, console) or check_train(
        events, steps=SIZES[size]["train"]["steps"], on_tpu=on_tpu,
        mosaic=mosaic, summary=summary, n_devices=n_devices)
    lines = [e for e in events if e.get("event") == "step"]
    return {
        "ok": not bad, "failed": bad, "wall_s": run["wall_s"],
        "first_step_s": (round(lines[0]["wall"] - run["t_spawn"], 1)
                         if lines else None),
        "loss": [e["loss"] for e in lines],
        "last_step": ({k: lines[-1].get(k) for k in
                       ("tokens_per_sec", "tflops", "mfu", "compiles",
                        "recompiles")} if lines else None),
        "mosaic_programs": mosaic,
        "hbm_live_per_device": (summary or {}).get("hbm_live_per_device"),
    }


# --------------------------------------------------------------- serve


def make_requests(size: str, seed: int) -> tuple[list, list]:
    """(first wave, second wave) from a seed. Both waves have the same
    prompt lengths (so the second can need no executable the first did
    not), different prompts, one sampled request each; the longer
    prompts of the first wave and the whole second wave arrive after
    the run has started."""
    spec = SIZES[size]["requests"]
    waves = []
    for w in (1, 2):
        wave = []
        for i, n in enumerate(spec["prompt_lens"]):
            rec = {"id": f"w{w}-{i}", "prompt_len": n,
                   "prompt_seed": seed * 1000 + w * 100 + i,
                   "max_new": spec["max_new"],
                   "at": round((0.0 if w == 1 else 1.0)
                               + (0.15 * i if i >= 2 or w == 2 else 0.0),
                               3)}
            if i == 1:
                rec.update(temperature=1.0, seed=seed + w)
            wave.append(rec)
        waves.append(wave)
    return waves[0], waves[1]


def serve_argv(size: str, reqs: Path, log: Path, extra=()) -> list:
    m, s = SIZES[size]["model"], SIZES[size]["serve"]
    return [sys.executable, "serve.py",
            "--vocab", str(m["vocab"]), "--d-model", str(m["d_model"]),
            "--n-heads", str(m["n_heads"]),
            "--n-layers", str(m["n_layers"]),
            "--max-seq", str(s["max_seq"]), "--rope",
            "--n-blocks", str(s["n_blocks"]),
            "--block-size", str(s["block_size"]),
            "--slots", str(s["slots"]),
            "--prefill-chunk", str(s["prefill_chunk"]),
            "--requests", str(reqs), "--log-file", str(log), *extra]


def check_serve(events, reqs, *, platform: str) -> list[str]:
    """Every way one serve.py run can have failed, as sentences.
    `events` are the JSON lines of its stdout."""
    bad = []
    dev = next((e for e in events if e.get("event") == "device"), None)
    if dev is None or dev.get("platform") != platform:
        bad.append(f"server ran on {dev}, expected platform {platform!r}")
    errors = [e for e in events if e.get("event") == "error"]
    if errors:
        bad.append(f"error line(s): {errors}")
    results = {e["id"]: e for e in events if e.get("event") == "result"}
    for r in reqs:
        got = results.get(r["id"])
        if got is None:
            bad.append(f"request {r['id']}: no result")
        elif len(got.get("tokens", [])) != r["max_new"]:
            bad.append(f"request {r['id']}: {len(got.get('tokens', []))} "
                       f"tokens, expected {r['max_new']}")
    summary = next((e for e in events if e.get("event") == "summary"),
                   None)
    if summary is None:
        return bad + ["no summary line"]
    if summary.get("pending_at_exit"):
        bad.append(f"{summary['pending_at_exit']} request(s) pending at "
                   f"exit")
    free, usable = (int(x) for x in
                    summary["blocks_free_at_drain"].split("/"))
    cold = int(summary["blocks_cold_at_drain"])
    if free + cold != usable:
        bad.append(f"allocator unbalanced at drain: {free} free + {cold} "
                   f"cold != {usable} usable")
    return bad


def run_serve_phase(size: str, out: Path, *, config: str, seed: int = 21,
                    platform: str = "tpu", env=None) -> dict:
    """One serving configuration, run twice: the first wave alone, then
    both waves. Passing needs both runs clean and the SAME executable
    counts in both — the second wave compiled nothing."""
    wave1, wave2 = make_requests(size, seed)
    runs = {}
    for tag, reqs in (("w1", wave1), ("w12", wave1 + wave2)):
        stem = f"serve_{config}_{tag}"
        req_file, log = out / f"{stem}.reqs.jsonl", out / f"{stem}.jsonl"
        ir_dir, console = out / f"{stem}_ir", out / f"{stem}.out"
        fresh(log, ir_dir)
        req_file.write_text("".join(json.dumps(r) + "\n" for r in reqs))
        run = run_child(
            serve_argv(size, req_file, log, SERVE_CONFIGS[config]),
            out_path=console, timeout=SIZES[size]["timeout"]["serve"],
            env=child_env(env, ir_dir))
        events = read_jsonl(console)
        bad = child_failure(run, console) or check_serve(
            events, reqs, platform=platform)
        mosaic = mosaic_programs(ir_dir)
        if (not bad and platform == "tpu"
                and not any("decode_tick" in n for n in mosaic)):
            bad.append(f"no lowered decode tick holds a {MOSAIC_CALL} "
                       f"(found it in: {mosaic or 'nothing'})")
        summary = next((e for e in events
                        if e.get("event") == "summary"), {})
        first_tok = [e["wall"] for e in read_jsonl(log)
                     if e.get("event") == "lifecycle"
                     and e.get("phase") == "decoding"]
        runs[tag] = {
            "failed": bad, "wall_s": run["wall_s"],
            "first_token_s": (round(min(first_tok) - run["t_spawn"], 1)
                              if first_tok else None),
            "executables": summary.get("executables"),
            "tok_per_sec": summary.get("tok_per_sec"),
            "ttft_ms_p50": summary.get("ttft_ms_p50"),
            "tpot_ms_p50": summary.get("tpot_ms_p50"),
            "mosaic_programs": mosaic,
            "tokens": {e["id"]: e["tokens"] for e in events
                       if e.get("event") == "result"},
        }
    bad = [f"{tag}: {msg}" for tag, r in runs.items()
           for msg in r["failed"]]
    if not bad and runs["w1"]["executables"] != runs["w12"]["executables"]:
        bad.append(f"the second wave compiled: executables "
                   f"{runs['w1']['executables']} after the first wave, "
                   f"{runs['w12']['executables']} after the second")
    # the two-wave run is the one reported
    return {**runs["w12"], "ok": not bad, "failed": bad,
            "wall_s": round(runs["w1"]["wall_s"] + runs["w12"]["wall_s"],
                            1),
            "first_token_s": {t: runs[t]["first_token_s"] for t in runs}}


# ------------------------------------------------------------- kernels


def relmax(a, b) -> float:
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.abs(a - b).max()) / max(1e-6, float(np.abs(b).max()))


def kernel_numerics_errs(b=2, t=512, h=8, d=64, gqa_kvh=2,
                         window=64) -> dict:
    """Compiled flash kernels vs XLA attention at one shape: flash
    fwd+bwd (plain causal, GQA, sliding window) and one ring CHUNK pair
    (the `_chunk_fwd` + log-sum-exp merge the ring kernel is built
    from, with a nonzero global offset). Returns {case: {"flash",
    "xla_bf16_floor"}}; RAISES on any kernel failure (the kernels
    child exits on it)."""
    import jax
    import jax.numpy as jnp

    from shallowspeed_tpu.ops import flash_attention as FA
    from shallowspeed_tpu.ops.attention import attention

    rng = np.random.default_rng(7)

    def mk(kvh=None):
        kh = kvh or h
        return (jnp.asarray(rng.normal(size=(b, t, h, d)) * 0.5,
                            jnp.bfloat16),
                jnp.asarray(rng.normal(size=(b, t, kh, d)) * 0.5,
                            jnp.bfloat16),
                jnp.asarray(rng.normal(size=(b, t, kh, d)) * 0.5,
                            jnp.bfloat16))

    def grads(f, q, k, v):
        def loss(q, k, v):
            return (f(q, k, v).astype(jnp.float32) ** 2).mean()

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    # self-calibrating criterion: the bf16 flash kernel and bf16 XLA
    # attention are BOTH compared against an f32 XLA oracle; the
    # kernel passes when its error stays within a small multiple of
    # XLA-bf16's own rounding error (an absolute bf16 tolerance
    # would be a guess; this measures the rounding floor in place)
    errs = {}
    for name, kvh, w in (("causal", None, 0), ("gqa", gqa_kvh, 0),
                         ("window", None, window)):
        q, k, v = mk(kvh)
        q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))

        def fl(q, k, v, w=w):
            return FA.flash_attention(q, k, v, causal=True, window=w)

        def xl(q, k, v, w=w):
            return attention(q, k, v, causal=True, window=w)

        oracle = [jax.jit(xl)(q32, k32, v32)]
        oracle += list(jax.jit(
            lambda q, k, v: grads(xl, q, k, v))(q32, k32, v32))
        got_f = [jax.jit(fl)(q, k, v)]
        got_f += list(jax.jit(
            lambda q, k, v: grads(fl, q, k, v))(q, k, v))
        got_x = [jax.jit(xl)(q, k, v)]
        got_x += list(jax.jit(
            lambda q, k, v: grads(xl, q, k, v))(q, k, v))
        e_f = max(relmax(a, o) for a, o in zip(got_f, oracle))
        e_x = max(relmax(a, o) for a, o in zip(got_x, oracle))
        errs[name] = {"flash": round(e_f, 5),
                      "xla_bf16_floor": round(e_x, 5)}

    # one ring chunk pair: second-half queries vs (earlier block at
    # rel=t/2, own block at rel=0), merged — the exact primitives
    # ring_flash_attention composes, compiled on this chip
    q, k, v = mk()
    t2 = t // 2
    qh = q[:, t2:]
    (_, _, _, _, kvh_, _, bq, bk, nqb_chunk) = FA._ring_geometry(
        qh, k[:, :t2])
    # out_dtype f32: the exact chunk-output dtype the ring passes
    # (round 6 — the bf16 chunk rounding was the r5 2.3x-above-
    # floor finding; BASELINE.md 'ring-chunk numerics envelope')
    kw = dict(causal=True, window=0, bq=bq, bk=bk,
              nqb_chunk=nqb_chunk, interpret=FA._interpret_default(),
              out_dtype=jnp.float32)
    q3 = FA._fold_q(qh, kvh_)

    @jax.jit
    def ring_pair(q3, k, v):
        o0, l0 = FA._chunk_fwd(q3, FA._to_bhsd(k[:, :t2]),
                               FA._to_bhsd(v[:, :t2]), t2, **kw)
        o1, l1 = FA._chunk_fwd(q3, FA._to_bhsd(k[:, t2:]),
                               FA._to_bhsd(v[:, t2:]), 0, **kw)
        o, _ = FA._merge_chunks(o0.astype(jnp.float32), l0, o1, l1)
        return FA._unfold_q(o.astype(q3.dtype), b, h)

    oref32 = attention(q.astype(jnp.float32), k.astype(jnp.float32),
                       v.astype(jnp.float32), causal=True)[:, t2:]
    oref16 = attention(q, k, v, causal=True)[:, t2:]
    errs["ring_chunk"] = {
        "flash": round(relmax(ring_pair(q3, k, v), oref32), 5),
        "xla_bf16_floor": round(relmax(oref16, oref32), 5)}
    return errs


def kernel_numerics_pass(errs: dict) -> bool:
    """Within 3x the measured XLA-bf16 rounding floor plus a 0.005
    absolute allowance (fwd-only cases have tiny floors)."""
    return all(e["flash"] <= 3.0 * e["xla_bf16_floor"] + 0.005
               for e in errs.values())


def paged_decode_cases(gqa_kvh=2, window=24) -> tuple:
    """(name, kv_heads, int8 pool, window) of each paged-decode check."""
    return (("paged_decode", 0, False, 0),
            ("paged_decode_gqa", gqa_kvh, False, 0),
            ("paged_decode_int8", 0, True, 0),
            ("paged_decode_window", 0, False, window))


def paged_decode_errs(cases, d_model=512, n_heads=4, bs=16,
                      dtype=None) -> dict:
    """Paged flash-decode kernel vs its XLA reference
    (`serving/cache.gather_table` + `kv_cache.masked_attention`), one
    entry per case of `paged_decode_cases` (interpret mode
    off-TPU, Mosaic-compiled on it). Self-calibrating like
    `kernel_numerics_errs`: the chip runs f32 matmuls as bf16 passes by
    default, so the kernel ("flash") and the reference as the server
    runs it ("xla_floor") are both measured against the reference at
    highest matmul precision. RAISES on any kernel failure. Heads are
    128 wide by default: compiled, the kernel's slab DMA addresses rows
    of whole lanes only (`flash_attention.paged_decode_addresses`)."""
    import jax
    import jax.numpy as jnp

    from shallowspeed_tpu.models import transformer as T
    from shallowspeed_tpu.models.kv_cache import masked_attention
    from shallowspeed_tpu.ops.flash_attention import paged_flash_decode
    from shallowspeed_tpu.serving.cache import (gather_table,
                                                init_block_pool,
                                                write_rows)

    rng = np.random.default_rng(11)
    entries = {}
    for name, kvh, quant, window in cases:
        cfg = T.TransformerConfig(vocab=64, d_model=d_model,
                                  n_heads=n_heads, n_kv_heads=kvh,
                                  n_layers=1, max_seq=512,
                                  attn_window=window,
                                  compute_dtype=dtype)
        n, s, w = 32, 4, 4
        pool = init_block_pool(cfg, n, bs,
                               "int8" if quant else "")[0]
        bt = rng.integers(1, n, (s, w)).astype(np.int32)
        pos = np.asarray([bs * w - 1, 17, 40, 3], np.int32)
        for row in range(s):
            for p in range(pos[row] + 1):
                k = jnp.asarray(rng.normal(
                    size=(1, cfg.kv_heads, cfg.head_dim)), jnp.float32)
                v = jnp.asarray(rng.normal(
                    size=(1, cfg.kv_heads, cfg.head_dim)), jnp.float32)
                pool = write_rows(pool, k, v,
                                  jnp.asarray([bt[row, p // bs]]),
                                  jnp.asarray([p % bs]), quant)
        q = jnp.asarray(rng.normal(
            size=(s, cfg.n_heads, cfg.head_dim)),
            dtype or jnp.float32)
        got = paged_flash_decode(q, pool, jnp.asarray(bt),
                                 jnp.asarray(pos), window=window)
        span = jnp.arange(w * bs)
        valid = span[None, :] <= pos[:, None]
        if window > 0:
            valid = valid & (span[None, :] > pos[:, None] - window)

        def reference():
            return masked_attention(
                q[:, None], gather_table(pool, jnp.asarray(bt)),
                valid[:, None, None, None, :], cfg)[:, 0]

        with jax.default_matmul_precision("highest"):
            oracle = reference()
        entries[name] = {"flash": round(relmax(got, oracle), 7),
                         "xla_floor": round(
                             relmax(reference(), oracle), 7),
                         "ref": "gather_table+masked_attention"}
    return entries


def paged_prefill_errs(cases, d_model=512, n_heads=4, bs=16, c=64,
                       dtype=None) -> dict:
    """The prefill chunk's paged kernel (`paged_flash_prefill`) vs the
    same XLA reference, measured as `paged_decode_errs` measures: one
    entry per K/V case of `paged_decode_cases` (the kernel takes no
    int8 pool). A chunk of `c` rows, the last five of them padding,
    that starts inside a block of a table twice as wide as what is
    written."""
    import jax
    import jax.numpy as jnp

    from shallowspeed_tpu.models import transformer as T
    from shallowspeed_tpu.models.kv_cache import (masked_attention,
                                                  position_mask)
    from shallowspeed_tpu.ops.flash_attention import paged_flash_prefill
    from shallowspeed_tpu.serving.cache import gather_table

    rng = np.random.default_rng(13)
    entries = {}
    for name, kvh, quant, window in cases:
        if quant:
            continue
        cfg = T.TransformerConfig(vocab=64, d_model=d_model,
                                  n_heads=n_heads, n_kv_heads=kvh,
                                  n_layers=1, max_seq=512,
                                  compute_dtype=dtype)
        n, w, at0, n_tok = 32, 16, 3 * bs + 5, c - 5
        rand = lambda *shape: jnp.asarray(rng.normal(size=shape),
                                          dtype or jnp.float32)
        pool = {leaf: rand(n, cfg.kv_heads, bs, cfg.head_dim)
                for leaf in ("k", "v")}
        bt = jnp.asarray(rng.permutation(np.arange(1, n))[:w], jnp.int32)
        q = rand(c, cfg.n_heads, cfg.head_dim)
        got = paged_flash_prefill(q, pool, bt, jnp.int32(at0),
                                  jnp.int32(n_tok), window=window)[:n_tok]
        valid = position_mask(w * bs, (at0 + jnp.arange(c))[:, None], window)

        def reference():
            return masked_attention(
                q[None], gather_table(pool, bt[None]),
                valid[None, None, None], cfg)[0][:n_tok]

        with jax.default_matmul_precision("highest"):
            oracle = reference()
        entries[name.replace("decode", "prefill")] = {
            "flash": round(relmax(got, oracle), 7),
            "xla_floor": round(relmax(reference(), oracle), 7),
            "ref": "gather_table+masked_attention"}
    return entries


def paged_decode_pass(entries: dict, compiled: bool) -> bool:
    """Within 3x the reference's own default-precision error plus an
    allowance. Interpreted, both sides compute f32 scores and what
    remains is gather/reorder noise: 1e-4, the bar the CPU suite pins.
    Compiled, Mosaic runs the kernel's f32 dots as ONE bf16 pass while
    XLA keeps the single-query MHA reference in exact f32 (its floor
    reads 0.0), so the kernel's envelope is bf16 operand rounding —
    0.0019-0.0037 measured on a v5e (chip run, PR 21) — and gets the
    flash kernels' 0.005 allowance; a masking or indexing error costs
    10x that."""
    allowance = 0.005 if compiled else 1e-4
    return all(e["flash"] <= 3.0 * e["xla_floor"] + allowance
               for e in entries.values())


def kernels_phase(size: str) -> int:
    """The `--phase kernels` child: every Pallas entry point, as this
    backend builds it, against its XLA reference. Prints one JSON line;
    any exception is a failure (nothing here is caught)."""
    from shallowspeed_tpu import runtime

    runtime.enable_compile_cache()
    dev = runtime.device_stamp()
    on_tpu = dev["platform"] == "tpu"
    if size == "chip" and not on_tpu:
        print(f"chip_smoke kernels: need a TPU, found {dev}",
              file=sys.stderr)
        return 2
    from shallowspeed_tpu.ops import flash_attention as FA

    spec = SIZES[size]["kernels"]
    failed = []
    interpreted = FA._interpret_default()
    if on_tpu and interpreted:
        failed.append("on a TPU, but the kernels default to interpret "
                      "mode")

    flash = {"default_shapes": kernel_numerics_errs(),
             "smoke_shapes": kernel_numerics_errs(**spec["flash"])}
    for where, errs in flash.items():
        if not kernel_numerics_pass(errs):
            failed.append(f"flash kernels out of tolerance at {where}: "
                          f"{errs}")

    pg, g5 = spec["paged"], spec["paged_group5"]
    five = (("paged_decode_gqa5", g5["gqa_kvh"], False, 0),)
    paged = {
        "default_shapes": paged_decode_errs(paged_decode_cases()),
        "smoke_shapes": paged_decode_errs(
            paged_decode_cases(pg["gqa_kvh"], pg["window"]),
            d_model=pg["d_model"], n_heads=pg["n_heads"]),
        "group_of_five": paged_decode_errs(
            five, d_model=g5["d_model"], n_heads=g5["n_heads"]),
    }
    prefill = {
        "default_shapes": paged_prefill_errs(paged_decode_cases()),
        "smoke_shapes": paged_prefill_errs(
            paged_decode_cases(pg["gqa_kvh"], pg["window"]),
            d_model=pg["d_model"], n_heads=pg["n_heads"]),
        "group_of_five": paged_prefill_errs(
            five, d_model=g5["d_model"], n_heads=g5["n_heads"]),
    }
    for what, checks in (("decode", paged), ("prefill", prefill)):
        for where, entries in checks.items():
            if not paged_decode_pass(entries, compiled=not interpreted):
                failed.append(f"paged {what} out of tolerance at {where}: "
                              f"{entries}")

    print(json.dumps({"ok": not failed, "failed": failed, "device": dev,
                      "interpreted": interpreted, "flash": flash,
                      "paged": paged, "paged_prefill": prefill}))
    return 1 if failed else 0


def run_kernels_phase(size: str, out: Path, env=None) -> dict:
    console = out / "kernels.out"
    run = run_child([sys.executable, str(ROOT / "chip_smoke.py"),
                     "--phase", "kernels", "--size", size],
                    out_path=console,
                    timeout=SIZES[size]["timeout"]["kernels"], env=env)
    events = read_jsonl(console)
    result = events[-1] if events else {}
    bad = list(result.get("failed", [])) or child_failure(run, console)
    return {"ok": not bad, "failed": bad, "wall_s": run["wall_s"],
            **{k: result.get(k) for k in ("interpreted", "flash", "paged",
                                          "paged_prefill")}}


# ---------------------------------------------------------------- main


def cache_entries(cache_dir: str) -> int:
    p = Path(cache_dir)
    return sum(1 for _ in p.iterdir()) if p.is_dir() else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default=str(ROOT / "chiprun_out"
                                         / "chip_smoke"),
                    help="where phase logs, request files and IR dumps "
                         "go (default: chiprun_out/chip_smoke)")
    ap.add_argument("--phase", default=None, choices=["kernels"],
                    help=argparse.SUPPRESS)     # the kernels child
    ap.add_argument("--size", default="chip", choices=sorted(SIZES),
                    help=argparse.SUPPRESS)     # the kernels child
    args = ap.parse_args(argv)
    if args.phase == "kernels":
        return kernels_phase(args.size)

    # the package must be beside this script: chip_smoke.py alone in a
    # directory has nothing to drive, and fails here
    from shallowspeed_tpu import runtime

    dev = runtime.probe_device_stamp()
    print(f"device: {dev['platform']} {dev['kind']} x{dev['count']}",
          flush=True)
    if dev["platform"] != "tpu":
        print(f"chip_smoke: this is a TPU check and JAX found "
              f"platform {dev['platform']!r} ({dev['kind']} "
              f"x{dev['count']}); not running", file=sys.stderr)
        return 2

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    cache_dir = runtime.compile_cache_dir()
    entries_before = cache_entries(cache_dir)
    t0 = time.time()
    phases: dict[str, dict] = {}

    def report(name: str, res: dict) -> None:
        phases[name] = res
        extra = "".join(
            f" {k} {res[k]}" for k in ("first_step_s", "first_token_s")
            if res.get(k) is not None)
        print(f"{name}: {'ok' if res['ok'] else 'FAILED'} "
              f"{res['wall_s']}s{extra}", flush=True)
        for msg in res["failed"]:
            print(f"  {name}: {msg}", flush=True)

    report("train", run_train_phase("chip", out))
    for config in SERVE_CONFIGS:
        report(f"serve_{config}",
               run_serve_phase("chip", out, config=config))
    report("kernels", run_kernels_phase("chip", out))
    if dev["count"] >= 4:
        for name, extra in MULTICHIP_TRAIN.items():
            report(name, run_train_phase("chip", out, name=name,
                                         extra=extra, n_devices=4))

    for res in phases.values():
        res.pop("tokens", None)
    ok = all(res["ok"] for res in phases.values())
    summary = json.dumps({
        "ok": ok, "device": dev,
        "failed": [n for n, res in phases.items() if not res["ok"]],
        "wall_s": round(time.time() - t0, 1),
        "compile_cache": {"dir": cache_dir,
                          "entries_before": entries_before,
                          "entries_after": cache_entries(cache_dir)},
        "phases": phases})
    (out / "summary.json").write_text(summary + "\n")
    print(f"summary: {summary}", flush=True)
    # the last line is the result, and holds these keys and no others
    print(json.dumps({"ok": ok, "device": {
        "platform": str(dev["platform"]), "kind": str(dev["kind"]),
        "count": int(dev["count"])}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
