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
- **serve**: `python serve.py ...` three ways (`--attn-impl gather`,
  `flash`, `flash --kv-quant int8`), each run twice — a first wave of
  mixed-length requests, then that wave plus a second wave of the same
  shapes arriving mid-run: every request answered with exactly
  `max_new` tokens, no error line, the allocator balanced at drain, the
  executable counts identical between the two runs, every decode tick
  carrying the Mosaic call (the tick reads its pools through the paged
  kernel whatever `--attn-impl` says; the two names are the same
  programs since PR 29, and their token agreement is still *reported*);
- **kernels**: every Pallas entry point, compiled, against its XLA
  reference, at this table's shapes and at the ones `bench.py` lists;
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
            "matmul": [(2048, 1024, 4096), (512, 2048, 2048)],
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
            "matmul": [(128, 128, 256)],
        },
        "timeout": {"train": 300, "serve": 300, "kernels": 600},
    },
}

SERVE_CONFIGS = {
    "gather": [],
    "flash": ["--attn-impl", "flash"],
    "flash_int8": ["--attn-impl", "flash", "--kv-quant", "int8"],
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


def token_agreement(a: dict, b: dict) -> dict:
    """How far two configurations' streams agree, request by request:
    reported, not required (on the chip f32 matmuls run as bf16 passes,
    and a near-tie argmax may fall either way)."""
    same = sum(a[k] == b.get(k) for k in a)
    prefix = [next((i for i, (x, y) in enumerate(zip(a[k], b.get(k, [])))
                    if x != y), len(a[k])) for k in a]
    return {"requests_identical": f"{same}/{len(a)}",
            "tokens_until_first_difference": prefix}


# ------------------------------------------------------------- kernels


def kernels_phase(size: str) -> int:
    """The `--phase kernels` child: every Pallas entry point, as this
    backend builds it, against its XLA reference. Prints one JSON line;
    any exception is a failure (nothing here is caught)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from shallowspeed_tpu import runtime

    runtime.enable_compile_cache()
    dev = runtime.device_stamp()
    on_tpu = dev["platform"] == "tpu"
    if size == "chip" and not on_tpu:
        print(f"chip_smoke kernels: need a TPU, found {dev}",
              file=sys.stderr)
        return 2
    import bench
    from shallowspeed_tpu.ops import flash_attention as FA
    from shallowspeed_tpu.ops import matmul as MM

    spec = SIZES[size]["kernels"]
    failed = []
    interpreted = FA._interpret_default() or MM._interpret_default()
    if on_tpu and interpreted:
        failed.append("on a TPU, but the kernels default to interpret "
                      "mode")

    flash = {"bench_shapes": bench.kernel_numerics_errs(),
             "smoke_shapes": bench.kernel_numerics_errs(**spec["flash"])}
    for where, errs in flash.items():
        if not bench.kernel_numerics_pass(errs):
            failed.append(f"flash kernels out of tolerance at {where}: "
                          f"{errs}")

    def paged_cases(gqa_kvh, window):
        return (("paged_decode", 0, False, 0),
                ("paged_decode_gqa", gqa_kvh, False, 0),
                ("paged_decode_int8", 0, True, 0),
                ("paged_decode_window", 0, False, window))

    pg = spec["paged"]
    paged = {
        "bench_shapes": bench.paged_decode_errs(
            cases=paged_cases(2, 24)),
        "smoke_shapes": bench.paged_decode_errs(
            d_model=pg["d_model"], n_heads=pg["n_heads"],
            cases=paged_cases(pg["gqa_kvh"], pg["window"])),
    }
    for where, entries in paged.items():
        if not bench.paged_decode_pass(entries, compiled=not interpreted):
            failed.append(f"paged decode out of tolerance at {where}: "
                          f"{entries}")

    # blocked_matmul: bf16 operands, f32 accumulation, against the same
    # product at highest precision; XLA's own default-precision dot is
    # the floor it is allowed a small multiple of
    matmul = {}
    rng = np.random.default_rng(3)
    for m, k, n in spec["matmul"]:
        x = jnp.asarray(rng.normal(size=(m, k)), jnp.bfloat16)
        y = jnp.asarray(rng.normal(size=(k, n)), jnp.bfloat16)
        got = MM.blocked_matmul(x, y, out_dtype=jnp.float32)
        with jax.default_matmul_precision("highest"):
            oracle = jnp.dot(x.astype(jnp.float32),
                             y.astype(jnp.float32))
        floor = jnp.dot(x, y, preferred_element_type=jnp.float32)
        e = {"blocked": round(bench.relmax(got, oracle), 7),
             "xla_floor": round(bench.relmax(floor, oracle), 7)}
        matmul[f"{m}x{k}x{n}"] = e
        if e["blocked"] > 3.0 * e["xla_floor"] + 1e-4:
            failed.append(f"blocked_matmul {m}x{k}x{n} out of "
                          f"tolerance: {e}")

    print(json.dumps({"ok": not failed, "failed": failed, "device": dev,
                      "interpreted": interpreted, "flash": flash,
                      "paged": paged, "matmul": matmul}))
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
                                          "matmul")}}


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

    agreement = token_agreement(phases["serve_gather"]["tokens"],
                                phases["serve_flash"]["tokens"])
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
        "gather_vs_flash_tokens": agreement,
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
