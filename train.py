"""CLI training driver — L6.

Same surface as the reference (`/root/reference/train.py:62-155`):
`python train.py [--dp N] [--pp M] [--schedule naive|gpipe|pipedream]` — but
no `mpirun`: one controller process sees every TPU device through a
(dp, pp) `jax.sharding.Mesh` (`train.py:87-94`'s communicator splits become
mesh axes). Extra flags (epochs, batch size, engine, ...) replace the
reference's module-level constants (`train.py:56-59`) without changing the
defaults.

Engines:
- `fused` (pp=1 only): the whole batch step is one jitted XLA program
  (`shallowspeed_tpu/engine.py`).
- `vm`: the instruction-stream pipeline VM (`shallowspeed_tpu/parallel/
  worker.py`), required for pp>1.
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

import numpy as np

EPOCHS = 20           # reference `train.py:56`
GLOBAL_BATCH_SIZE = 128  # reference `train.py:58`
N_MUBATCHES = 4       # reference `train.py:59`
LAYER_SIZES = [784, 128, 127, 126, 125, 124, 123, 10]  # reference `train.py:98`
LR = 0.006            # reference `train.py:107`


def parse_args(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--dp", type=int, default=1,
                   help="Degree of data parallelism (=number of full model replicas)")
    p.add_argument("--pp", type=int, default=1, help="Number of pipeline stages")
    p.add_argument("--schedule", type=str,
                   choices=["pipedream", "gpipe", "naive"], default="naive")
    p.add_argument("--engine", type=str,
                   choices=["auto", "vm", "fused", "spmd", "fp8"],
                   default="auto",
                   help="auto: fused for pp=1, spmd (compiled GPipe) for "
                        "pp>1 with --schedule gpipe, else the instruction "
                        "VM. fp8: the single-device fp8-e4m3 trainer "
                        "(shallowspeed_tpu.fp8) under the numerics "
                        "observatory — per-step numerics pack, shadow-"
                        "parity sampling, guard-driven bf16 fallback")
    p.add_argument("--epochs", type=int, default=EPOCHS)
    p.add_argument("--batch-size", type=int, default=GLOBAL_BATCH_SIZE)
    p.add_argument("--mubatches", type=int, default=N_MUBATCHES)
    p.add_argument("--lr", type=float, default=LR)
    p.add_argument("--optimizer", type=str, default="sgd",
                   choices=["sgd", "momentum", "adam", "adamw"])
    p.add_argument("--grad-clip", type=float, default=0.0,
                   help="global-norm gradient clipping (0 = off)")
    p.add_argument("--overlap", default="off", choices=["off", "on"],
                   help="comm/compute interleaving (shallowspeed_tpu."
                        "parallel.overlap): bucketed dp gradient "
                        "reduction issued inside the backward (fused "
                        "engine) and double-buffered stage hops + the "
                        "peeled bucketed reduction (spmd engine); the "
                        "default bulk reduction is the oracle")
    p.add_argument("--bucket-mb", type=float, default=4.0,
                   help="with --overlap on: target bytes per reduction "
                        "bucket (MiB); smaller = more, earlier "
                        "collectives")
    p.add_argument("--weight-decay", type=float, default=0.01,
                   help="decoupled weight decay (adamw only)")
    p.add_argument("--data-dir", type=str, default="data/mnist_784")
    p.add_argument("--max-batches", type=int, default=0,
                   help="limit batches per epoch (0 = all); for smoke tests")
    p.add_argument("--save-dir", type=str, default="",
                   help="checkpoint directory; saves after every epoch")
    p.add_argument("--resume", action="store_true",
                   help="resume from the latest checkpoint in --save-dir")
    p.add_argument("--auto-resume", action="store_true",
                   help="resume from the latest checkpoint if one exists, "
                        "start fresh otherwise (restart-safe; pairs with "
                        "the elastic supervisor, shallowspeed_tpu.elastic)")
    p.add_argument("--profile-dir", type=str, default="",
                   help="write a jax.profiler trace of the training epochs")
    p.add_argument("--heartbeat-file", type=str, default="",
                   help="touch this file at every epoch log point — the "
                        "elastic supervisor's liveness signal "
                        "(shallowspeed_tpu/elastic.py hang detection)")
    p.add_argument("--chaos", type=str, default="",
                   help="deterministic fault injection (shallowspeed_"
                        "tpu.chaos). On this driver kill/nan/freeze "
                        "faults fire per EPOCH, stall@N fires at "
                        "dataset BATCH id N (the Dataset.load_batch "
                        "hook — batch ids restart each epoch, so it "
                        "lands in the first epoch that loads batch N), "
                        "and save faults count checkpoint saves; "
                        "falls back to the supervisor-exported "
                        "SHALLOWSPEED_CHAOS env")
    p.add_argument("--chaos-state", type=str, default="",
                   help="fired-fault marker dir (default: "
                        "<save-dir>/.chaos); must survive restarts")
    p.add_argument("--chaos-seed", type=int, default=0)
    p.add_argument("--log-file", type=str, default="",
                   help="append per-epoch JSONL metrics here")
    p.add_argument("--telemetry", default="off",
                   choices=["off", "steps", "spans"],
                   help="runtime telemetry level (shallowspeed_tpu."
                        "telemetry): steps = host-clock spans + "
                        "HBM/collective/recompile fields per epoch "
                        "line; spans = device-fenced per-instruction "
                        "spans — on the VM engine this records the "
                        "executed schedule trace and reports the "
                        "measured pipeline bubble vs verify.py's "
                        "static prediction (serializes dispatch; a "
                        "measurement mode)")
    p.add_argument("--trace-dir", type=str, default="",
                   help="write spans.jsonl + trace.json (Chrome/"
                        "Perfetto) + telemetry.json here; implies "
                        "--telemetry steps when the level is off")
    p.add_argument("--monitor-port", type=int, default=None,
                   help="live telemetry plane (telemetry/monitor): "
                        "/status.json + /metrics on 127.0.0.1:PORT "
                        "while the run is live (0 = free port)")
    p.add_argument("--replica", type=str, default=None,
                   help="replica label for fleet views (telemetry/"
                        "fleet): stamped on run_start and served "
                        "from /status.json")
    p.add_argument("--slo", type=str, default="",
                   help="declarative SLOs over dual burn-rate windows "
                        "(telemetry/monitor DSL); 'alert' events land "
                        "in --log-file")
    p.add_argument("--flight-recorder", type=int, default=0,
                   help="ring of the last N metrics/span records, "
                        "dumped to flightrec_<step>.json on anomaly "
                        "verdicts, chaos faults, or SLO alerts "
                        "(0 = off)")
    p.add_argument("--profile", default="off",
                   choices=["off", "host", "host+device"],
                   help="continuous profiling plane (telemetry/"
                        "profiler): always-on host stack sampler "
                        "(schema-v12 'profile' events, span-tagged "
                        "phase buckets when --telemetry is on) + "
                        "burn/fault-triggered capture windows "
                        "(profcap_*.json); 'host+device' wraps each "
                        "capture in a bounded jax.profiler trace")
    p.add_argument("--profile-hz", type=float, default=None,
                   help="host sampler rate (default 67 Hz)")
    p.add_argument("--health", default="off",
                   choices=["off", "monitor", "guard"],
                   help="training-health observability (telemetry/"
                        "health.py): monitor = on-device grad/param "
                        "norms + nonfinite sentinel inside every "
                        "compiled step, anomaly verdicts per epoch "
                        "line; guard = monitor + skip any update with "
                        "non-finite gradients bit-identically. "
                        "Disables the fused whole-epoch dispatch (the "
                        "pack rides the per-batch step)")
    p.add_argument("--shadow-every", type=int, default=16,
                   help="--engine fp8: run the frozen master-precision "
                        "oracle step on the live batch every N training "
                        "steps (0 = off) and gate the loss/grad parity "
                        "against the numerics envelopes; the oracle "
                        "seconds are ledger-excluded as shadow_parity. "
                        "Step 0 is always skipped — the delayed amax "
                        "history has not warmed and its parity is "
                        "legitimately loose")
    p.add_argument("--log-every", type=int, default=10,
                   help="--engine fp8: step-line cadence (schema v13 "
                        "num_* fields ride each line)")
    p.add_argument("--platform", type=str, default=None,
                   choices=["cpu", "tpu"],
                   help="force a JAX platform (same effect as the "
                        "JAX_PLATFORMS env var; with --host-devices, "
                        "simulates meshes on the CPU)")
    p.add_argument("--host-devices", type=int, default=0,
                   help="with --platform cpu: number of virtual host devices "
                        "for mesh simulation (XLA --xla_force_host_platform_"
                        "device_count)")
    return p.parse_args(argv)


def configure_platform(args):
    """Must run before the first JAX backend initialization."""
    import os

    if args.host_devices:
        flags = os.environ.get("XLA_FLAGS", "")
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{args.host_devices}").strip()
    if args.platform:
        import jax

        jax.config.update("jax_platforms", args.platform)
    from shallowspeed_tpu import distributed, runtime
    from shallowspeed_tpu.utils import rprint

    runtime.enable_compile_cache()
    # multi-host: connect to the JAX distributed service when a coordinator
    # is configured (env vars / TPU pod metadata); single-process no-op
    distributed.initialize()
    # what the run actually got: with no accelerator JAX falls back to
    # the CPU (kernels interpreted) with only a warning
    dev = runtime.device_stamp()
    rprint(f"device: {dev['platform']} {dev['kind']} x{dev['count']}")


def build(args):
    import jax

    from shallowspeed_tpu.data.dataset import Dataset
    from shallowspeed_tpu.data.mnist import ensure_mnist
    from shallowspeed_tpu.engine import FusedDPEngine
    from shallowspeed_tpu.models.mlp import MLPStage
    from shallowspeed_tpu.optim import OPTIMIZERS
    from shallowspeed_tpu.parallel.mesh import make_mesh
    from shallowspeed_tpu.parallel.worker import PipelineExecutor

    dp, pp = args.dp, args.pp
    assert dp >= 1 and pp >= 1
    assert args.batch_size % dp == 0, "Batch size must be divisible by DP"
    n_devices = len(jax.devices())
    if dp * pp > n_devices:
        raise SystemExit(
            f"requested dp*pp={dp * pp} devices but only {n_devices} present")

    mesh = make_mesh(dp, pp)
    opt_kw = {"grad_clip": args.grad_clip or None}
    if args.optimizer == "adamw":
        opt_kw["weight_decay"] = args.weight_decay
    optimizer = OPTIMIZERS[args.optimizer](lr=args.lr, **opt_kw)

    data_dir = ensure_mnist(Path(args.data_dir))
    local_bs = args.batch_size // dp
    assert local_bs % args.mubatches == 0, (
        f"local batch {local_bs} must be divisible by --mubatches "
        f"{args.mubatches}")
    mubatch_size = local_bs // args.mubatches
    train_ds = [Dataset(data_dir, args.batch_size, mubatch_size).load(r, dp)
                for r in range(dp)]
    # Validation: whole local batch as one microbatch (reference
    # `train.py:122-128` uses mubatch_size == global batch, 1 μbatch).
    val_ds = [Dataset(data_dir, args.batch_size, local_bs, validation=True)
              .load(r, dp) for r in range(dp)]

    from shallowspeed_tpu.parallel.spmd_pipeline import SPMDPipelineEngine

    engine_kind = args.engine
    if engine_kind == "auto":
        engine_kind = ("fused" if pp == 1
                       else "spmd" if args.schedule == "gpipe" else "vm")
    if engine_kind == "fused" and pp != 1:
        raise SystemExit("--engine fused requires --pp 1")
    if engine_kind == "spmd" and args.schedule != "gpipe":
        raise SystemExit("--engine spmd implements the gpipe schedule; use "
                         "--schedule gpipe (or --engine vm)")

    from shallowspeed_tpu.parallel.overlap import from_flags

    ov = from_flags(args.overlap, args.bucket_mb)
    if engine_kind == "fused":
        stage = MLPStage(LAYER_SIZES, 0, 1, batch_size=args.batch_size)
        engine = FusedDPEngine(stage, optimizer, mesh,
                               health=args.health, overlap=ov)
    elif engine_kind == "spmd":
        engine = SPMDPipelineEngine(LAYER_SIZES, optimizer, mesh,
                                    args.mubatches, mubatch_size,
                                    args.batch_size,
                                    health=args.health, overlap=ov)
    else:
        if ov is not None:
            raise SystemExit(
                "--overlap on needs a compiled engine (fused or spmd); "
                "the instruction VM already issues its collectives "
                "per-instruction")
        stages = [MLPStage(LAYER_SIZES, s, pp, batch_size=args.batch_size)
                  for s in range(pp)]
        engine = PipelineExecutor(mesh, stages, optimizer,
                                  health=args.health)
    return engine, train_ds, val_ds


def compute_accuracy(engine, val_ds) -> float:
    """Reference `compute_accuracy` (`train.py:21-47`): argmax of the
    last-stage output vs the one-hot target, streamed over val batches."""
    from shallowspeed_tpu.parallel.schedules import InferenceSchedule

    correct = total = 0
    for batch_id in range(val_ds[0].get_num_batches()):
        targets = np.concatenate(
            [ds.load_micro_batch_target(batch_id, 0) for ds in val_ds])
        if hasattr(engine, "infer"):  # fused / spmd engines
            x = np.concatenate(
                [ds.load_micro_batch_input(batch_id, 0) for ds in val_ds])
            out = np.asarray(engine.infer(x))
        else:  # pipeline VM
            out = np.asarray(
                engine.infer_batch(InferenceSchedule, 1, batch_id, val_ds))
        pred = out.argmax(axis=-1)
        correct += int((pred == targets.argmax(axis=-1)).sum())
        total += len(pred)
    return correct / total


def train_fp8(args) -> float:
    """The numerics-observatory driver (round 18): a STEP-based loop
    over the fp8-e4m3 trainer (`shallowspeed_tpu.fp8`) whose every
    line carries the runtime precision telemetry — the per-layer
    clamp/scale pack reduced by `telemetry.numerics.NumericsMonitor`,
    shadow-parity samples against the frozen f32 oracle every
    `--shadow-every` steps, and the guard escalation those verdicts
    drive (warn -> fallback_bf16 -> abort). Returns the final
    validation loss (the MSE head has no argmax accuracy story worth
    reporting next to the parity numbers)."""
    import jax  # noqa: F401  (backend init before any engine build)

    from shallowspeed_tpu import chaos
    from shallowspeed_tpu.data.dataset import Dataset
    from shallowspeed_tpu.data.mnist import ensure_mnist
    from shallowspeed_tpu.elastic import install_sigterm_exit
    from shallowspeed_tpu.fp8 import Fp8TrainEngine
    from shallowspeed_tpu.metrics import MetricsLogger, StepRates
    from shallowspeed_tpu.optim import OPTIMIZERS
    from shallowspeed_tpu.telemetry import profiler as profiler_mod
    from shallowspeed_tpu.telemetry.anomaly import GuardPolicy
    from shallowspeed_tpu.telemetry.goodput import GoodputLedger
    from shallowspeed_tpu.telemetry.health import HealthMonitor
    from shallowspeed_tpu.telemetry.monitor import close_monitor, from_args
    from shallowspeed_tpu.telemetry.numerics import NumericsMonitor
    from shallowspeed_tpu.utils import rprint

    for flag, val in (("--dp", args.dp != 1), ("--pp", args.pp != 1),
                      ("--save-dir", bool(args.save_dir)),
                      ("--telemetry", args.telemetry != "off"),
                      ("--overlap", args.overlap != "off")):
        if val:
            raise SystemExit(
                f"--engine fp8 is the single-device numerics trainer; "
                f"{flag} is not supported with it")
    install_sigterm_exit()
    chaos.setup(args.chaos, seed=args.chaos_seed,
                state_dir=args.chaos_state or None,
                log_file=args.log_file or None)
    t_proc0 = time.time()
    opt_kw = {"grad_clip": args.grad_clip or None}
    if args.optimizer == "adamw":
        opt_kw["weight_decay"] = args.weight_decay
    optimizer = OPTIMIZERS[args.optimizer](lr=args.lr, **opt_kw)
    engine = Fp8TrainEngine(LAYER_SIZES, optimizer)

    data_dir = ensure_mnist(Path(args.data_dir))
    train_ds = Dataset(data_dir, args.batch_size,
                       args.batch_size).load(0, 1)
    val_ds = Dataset(data_dir, args.batch_size, args.batch_size,
                     validation=True).load(0, 1)
    n_batches = train_ds.get_num_batches()
    if args.max_batches:
        n_batches = min(n_batches, args.max_batches)
    total_steps = n_batches * args.epochs

    metrics = MetricsLogger(
        args.log_file, engine=type(engine).__name__, dp=1, pp=1,
        schedule="fp8", batch_size=args.batch_size,
        **({"replica": args.replica} if args.replica else {}))
    ledger = GoodputLedger(metrics)
    live_mon, live_srv = from_args(args, metrics)
    if live_mon is not None:
        chaos.add_observer(live_mon.note_line)
    plane = profiler_mod.from_args(args, metrics)
    if plane is not None:
        chaos.add_observer(plane.on_fault)
        if live_mon is not None:
            live_mon.profiler = plane
            live_mon.alert_listeners.append(plane.on_alert)

    # the observatory's two host-side reducers: the numerics monitor
    # is ALWAYS on for this engine (it is the point of the driver);
    # grad-health verdicts join it under --health
    policy = GuardPolicy.for_mode(args.health) \
        if args.health != "off" else None
    num_mon = NumericsMonitor(policy=policy)
    monitor = HealthMonitor(policy=policy) \
        if args.health != "off" else None
    guarded = args.health == "guard"

    def val_loss() -> float:
        t0 = time.time()
        tot = 0.0
        nb = val_ds.get_num_batches()
        for b in range(nb):
            tot += engine.eval_loss(val_ds.load_micro_batch_input(b, 0),
                                    val_ds.load_micro_batch_target(b, 0))
        rates.pause(time.time() - t0, kind="val")
        return tot / max(nb, 1)

    rates = StepRates(args.batch_size, health=monitor, numerics=num_mon,
                      ledger=ledger, monitor=live_mon)
    ledger.note("init", seconds=time.time() - t_proc0)
    last_logged = -1
    loss = float("nan")
    try:
        for step in range(total_steps):
            # step faults (incl. scale_poison@N) fire per training
            # STEP on this driver — its cadence is the step, not the
            # epoch
            chaos.on_step(step, engine)
            batch_id = step % n_batches
            x = train_ds.load_micro_batch_input(batch_id, 0)
            y = train_ds.load_micro_batch_target(batch_id, 0)
            loss = engine.train_batch(x, y)
            # the pack fetch is one tiny host sync per step — this
            # engine's contract is observability, and the collapse
            # signature (a poisoned scale self-heals as fresh amaxes
            # roll in) is only visible AT the poisoned step
            verdicts = num_mon.observe(step, engine.health_snapshot())
            if (args.shadow_every and step
                    and step % args.shadow_every == 0):
                t_sh = time.time()
                parity = engine.shadow_parity(x, y)
                rates.pause(time.time() - t_sh, kind="shadow_parity")
                verdicts += num_mon.note_parity(step, parity)
            if monitor is not None:
                verdicts += monitor.observe(step, loss,
                                            engine.health_snapshot())
            fatal = []
            for v in verdicts:
                rprint(str(v))
                if v.action == "fallback_bf16" and guarded \
                        and engine.precision == "fp8":
                    engine.fallback_bf16()
                    num_mon.note_fallback()
                    ledger.note("fp8_fallback", count=1)
                    rprint(f"numerics guard: falling back to the bf16 "
                           f"master-precision step at step {step} "
                           f"({v.kind})")
                elif v.action == "abort" and guarded:
                    fatal.append(v)
            at_end = step == total_steps - 1
            if verdicts or at_end or step - last_logged >= args.log_every:
                r = rates.log_point(step - last_logged)
                last_logged = step
                metrics.log(event="step", step=step,
                            loss=round(float(loss), 6),
                            tokens_per_sec=round(r.pop(
                                "tokens_per_sec"), 1),
                            tokens_per_sec_cum=round(r.pop(
                                "tokens_per_sec_cum"), 1), **r)
                rprint(f"step {step:5d}  loss {loss:.5f}  "
                       f"precision {engine.precision}"
                       + (f"  parity "
                          f"{num_mon._last_parity['loss_rel']:.3g}"
                          if num_mon._last_parity else ""))
                if args.heartbeat_file and not chaos.heartbeat_frozen():
                    from shallowspeed_tpu.elastic import write_heartbeat

                    write_heartbeat(args.heartbeat_file,
                                    monitor.heartbeat_status()
                                    if monitor is not None else "ok")
            if fatal:
                if live_mon is not None:
                    live_mon.flight_dump(
                        "numerics:" + ",".join(v.kind for v in fatal),
                        step=step, trigger=[str(v) for v in fatal])
                raise SystemExit(
                    f"numerics policy abort at step {step}: "
                    + "; ".join(v.detail for v in fatal))
        final = val_loss()
        rprint(f"final val loss {final:.5f}  precision "
               f"{engine.precision}  shadow samples "
               f"{num_mon.shadow_total}")
        metrics.log(event="val", step=max(total_steps - 1, 0),
                    val_loss=round(final, 6))
        return final
    finally:
        if plane is not None:
            chaos.remove_observer(plane.on_fault)
            plane.close()
        if live_mon is not None:
            chaos.remove_observer(live_mon.note_line)
            close_monitor(live_mon, live_srv)
        plan = chaos.active()
        if plan is not None and plan.unfired():
            rprint(f"chaos: scheduled fault(s) never fired: "
                   f"{', '.join(plan.unfired())}")


def train(args) -> float:
    import jax

    from shallowspeed_tpu import chaos, checkpoint
    from shallowspeed_tpu.elastic import (EXIT_CORRUPT_CKPT,
                                          install_sigterm_exit)
    from shallowspeed_tpu.metrics import MetricsLogger
    from shallowspeed_tpu.parallel.schedules import (
        GPipeSchedule, NaiveParallelSchedule, PipeDreamSchedule)
    from shallowspeed_tpu.utils import assert_replicas_in_sync, get_model_hash, rprint

    if args.engine == "fp8":
        return train_fp8(args)

    schedule_cls = {
        "naive": NaiveParallelSchedule,
        "gpipe": GPipeSchedule,
        "pipedream": PipeDreamSchedule,
    }[args.schedule]

    # supervisor kill path: exit through finally blocks on SIGTERM so
    # the metrics tail flushes before the SIGKILL deadline
    install_sigterm_exit()
    chaos.setup(args.chaos, seed=args.chaos_seed,
                state_dir=args.chaos_state
                or (Path(args.save_dir) / ".chaos"
                    if args.save_dir else None),
                log_file=args.log_file or None)

    t_proc0 = time.time()  # goodput ledger: init = entry -> epoch loop
    engine, train_ds, val_ds = build(args)
    n_batches = train_ds[0].get_num_batches()
    if args.max_batches:
        n_batches = min(n_batches, args.max_batches)

    start_epoch = 0
    if args.auto_resume and not args.resume:
        # elastic restarts: resume iff a checkpoint EXISTS (cheap
        # probe; restore_latest verifies, quarantines, falls back)
        if not args.save_dir:
            raise SystemExit("--auto-resume requires --save-dir")
        if checkpoint.has_checkpoint(args.save_dir):
            args.resume = True
    if args.resume:
        if not args.save_dir:
            raise SystemExit("--resume requires --save-dir")
        start_epoch, ck, quarantined = checkpoint.restore_latest(
            engine, args.save_dir)
        if ck is None:
            if args.auto_resume:
                rprint(f"--auto-resume: no restorable checkpoint under "
                       f"{args.save_dir!r}; starting fresh")
            elif quarantined:
                print(f"--resume: every checkpoint under "
                      f"{args.save_dir!r} failed verification "
                      f"({len(quarantined)} quarantined)",
                      file=sys.stderr)
                raise SystemExit(EXIT_CORRUPT_CKPT)
            else:
                raise SystemExit(f"--resume: no checkpoint found under "
                                 f"{args.save_dir!r}")
        else:
            rprint(f"resumed from {ck} at epoch {start_epoch}")

    metrics = MetricsLogger(
        args.log_file, dp=args.dp, pp=args.pp, schedule=args.schedule,
        engine=type(engine).__name__, batch_size=args.batch_size,
        **({"replica": args.replica} if args.replica else {}))

    # goodput ledger (telemetry/goodput): init / val-eval / save time
    # stamped into the same JSONL so `--goodput` decomposes the run
    from shallowspeed_tpu.telemetry.goodput import GoodputLedger

    ledger = GoodputLedger(metrics)

    # ---- runtime telemetry (shallowspeed_tpu/telemetry)
    from shallowspeed_tpu import telemetry as tele

    if args.trace_dir and args.telemetry == "off":
        args.telemetry = "steps"  # --trace-dir implies tracing
    tracer = tele.configure(trace_dir=args.trace_dir or None,
                            level=args.telemetry)
    telem = (tele.RunTelemetry(engine, tracer)
             if args.telemetry != "off" else None)
    if telem is not None:
        telem.ledger = ledger
        # memory observatory (round 20): register the long-lived trees
        # so step lines decompose live HBM per owner; resolvers, not
        # snapshots — the engine rotates/donates these every step
        from shallowspeed_tpu.telemetry import memory as memlib
        memlib.register_owner(
            "train.params", lambda: getattr(engine, "params", None))
        memlib.register_owner(
            "train.opt_state", lambda: getattr(engine, "opt_state", None))

    # ---- live telemetry plane (telemetry/monitor.py): endpoint +
    # SLO alerts + flight recorder, fed by every metrics line
    from shallowspeed_tpu.telemetry.monitor import (close_monitor,
                                                    from_args)

    live_mon, live_srv = from_args(args, metrics)
    if live_mon is not None:
        chaos.add_observer(live_mon.note_line)
        if args.telemetry != "off":
            tracer.subscribers.append(live_mon.record_span)
        if live_srv is not None:
            rprint(f"monitor: {live_srv.url('/status.json')} "
                   f"(+ /metrics)")
    # continuous profiling plane (round 17): host stack sampler into
    # the metrics JSONL + trigger-armed capture windows; tracer spans
    # feed the sampler's phase buckets via trace.PHASE_HOOKS, so
    # --telemetry steps/spans gives named host-time attribution
    from shallowspeed_tpu.telemetry import profiler as profiler_mod

    plane = profiler_mod.from_args(args, metrics)
    if plane is not None:
        chaos.add_observer(plane.on_fault)
        if live_mon is not None:
            live_mon.profiler = plane
            live_mon.alert_listeners.append(plane.on_alert)
    if telem is not None and args.pp > 1:
        telem.set_bubble(bubble_static=tele.static_bubble(
            args.schedule, args.mubatches,
            args.pp)["bubble_fraction"])

    # ---- training health: monitor fed at epoch log points (the pack
    # itself is computed on device every batch; guard skips are
    # enacted in-step regardless of the host cadence)
    monitor = None
    if args.health != "off":
        from shallowspeed_tpu.telemetry.anomaly import GuardPolicy
        from shallowspeed_tpu.telemetry.health import HealthMonitor

        monitor = HealthMonitor(policy=GuardPolicy.for_mode(args.health))

    # Fused engines: stage the epoch's batches on device once (HBM-resident)
    # and run each epoch as a single dispatch — unless health is on,
    # whose per-step pack rides the per-batch step program.
    staged = (engine.stage_epoch(train_ds, n_batches)
              if hasattr(engine, "train_epoch") and args.health == "off"
              else None)

    # the ONE jax.profiler entry point (telemetry/profiler): a falsy
    # dir is a no-op, and an active whole-run trace makes the capture
    # windows skip their own device half (xprof traces don't nest)
    from shallowspeed_tpu.telemetry.profiler import device_trace_ctx

    profile_ctx = device_trace_ctx(args.profile_dir)
    ledger.note("init", seconds=time.time() - t_proc0)
    start = time.time()
    accuracy = 0.0
    with profile_ctx:
        for epoch in range(start_epoch, args.epochs):
            # chaos step faults fire per EPOCH on this driver (its
            # checkpoint cadence is the epoch)
            chaos.on_step(epoch, engine)
            t_val = time.time()
            accuracy = compute_accuracy(engine, val_ds)
            ledger.note("val", seconds=time.time() - t_val)
            rprint(f"Epoch: {epoch}, Time Spent: {time.time() - start:.2f}s, "
                   f"Accuracy: {accuracy * 100:.2f}%")
            if args.heartbeat_file and not chaos.heartbeat_frozen():
                from shallowspeed_tpu.elastic import write_heartbeat

                write_heartbeat(args.heartbeat_file,
                                monitor.heartbeat_status()
                                if monitor is not None else "ok")
            t_epoch = time.time()
            trace_mark = 0
            if staged is not None:
                engine.train_epoch(staged)
            elif hasattr(engine, "train_epoch"):
                # fused/spmd engines under --health: per-batch stepping
                # (the health pack rides the batch step program)
                for batch_id in range(n_batches):
                    engine.train_batch(batch_id, train_ds)
            else:
                for batch_id in range(n_batches):
                    if batch_id == n_batches - 1:
                        # the bubble replay reads ONLY this batch's
                        # spans: batch ids repeat across epochs (and
                        # eval reuses them), so a bare batch filter
                        # would mix epochs into one replay
                        trace_mark = tracer.event_count
                    engine.train_batch(schedule_cls, args.mubatches, batch_id,
                                       train_ds)
            # JAX dispatch is async: wait for the params update to land so
            # the logged epoch time measures compute, not dispatch.
            jax.block_until_ready(engine.params)
            metrics.epoch(epoch, accuracy, n_batches * args.batch_size,
                          time.time() - t_epoch)
            if monitor is not None:
                # the last batch's pack + anomaly verdicts, once per
                # epoch (the MLP driver has no step lines)
                verdicts = monitor.observe(epoch, None,
                                           engine.health_snapshot())
                for v in verdicts:
                    rprint(str(v))
                metrics.log(event="health", step=epoch,
                            **monitor.step_fields())
            if telem is not None:
                # VM at the `spans` level: the per-instruction fenced
                # spans ARE the executed schedule trace — replay the
                # last batch's ops against the dataflow structure and
                # report the measured bubble vs the static prediction
                if (args.telemetry == "spans" and staged is None
                        and args.pp > 1):
                    from shallowspeed_tpu.telemetry import bubble as _b

                    ops = _b.span_replay_ops(
                        tracer.events_since(trace_mark),
                        batch=n_batches - 1)
                    if ops:
                        rep = _b.replay_trace(ops, args.pp)
                        telem.set_bubble(
                            bubble_measured=rep["bubble_fraction"])
                tf = telem.step_fields()
                metrics.log(event="telemetry", epoch=epoch, **tf)
                if "bubble_measured" in tf:
                    rprint(f"  telemetry: bubble measured "
                           f"{tf['bubble_measured']:.1%} vs static "
                           f"{tf.get('bubble_static', 0.0):.1%}  "
                           f"hbm {tf.get('hbm_live_mib', 0):,.0f} MiB")
            if args.save_dir:
                t_save = time.time()
                if monitor is not None and monitor.unhealthy():
                    # never checkpoint a poisoned iterate (see
                    # train_lm.py; found by the chaos NaN-storm drill)
                    rprint(f"epoch {epoch}: health is "
                           f"{monitor.heartbeat_status()!r} — "
                           f"skipping checkpoint save")
                    ledger.note("ckpt_save_skipped_unhealthy", count=1)
                else:
                    try:
                        checkpoint.save(args.save_dir, engine, epoch)
                    except (checkpoint.CheckpointError, OSError) as e:
                        if jax.process_count() > 1:
                            # peers already sit in the save barrier —
                            # swallowing on process 0 would wedge the
                            # gang; die and let the supervisor restart
                            raise
                        # atomic rename: latest() still points at the
                        # previous checkpoint — keep training
                        rprint(f"warning: checkpoint save failed "
                               f"({e}); the previous checkpoint "
                               f"remains the restore point")
                        ledger.note("ckpt_save_failed", count=1)
                ledger.note("ckpt_save", seconds=time.time() - t_save)

    accuracy = compute_accuracy(engine, val_ds)
    rprint(f"Epoch: {args.epochs}, Time Spent: {time.time() - start:.2f}s, "
           f"Accuracy: {accuracy * 100:.2f}%")
    metrics.final(accuracy, time.time() - start)
    if telem is not None:
        tracer.close()  # flush spans.jsonl, write trace.json
        if args.trace_dir:
            path = telem.write_summary(args.trace_dir)
            rprint(f"telemetry: {path} (+ spans.jsonl, trace.json)")
    if plane is not None:
        chaos.remove_observer(plane.on_fault)
        plane.close()
    if live_mon is not None:
        chaos.remove_observer(live_mon.note_line)
        close_monitor(live_mon, live_srv)

    plan = chaos.active()
    if plan is not None and plan.unfired():
        rprint(f"chaos: scheduled fault(s) never fired: "
               f"{', '.join(plan.unfired())}")
    # Sanity check: DP replicas hold bit-identical weights (reference
    # `train.py:154-155`, `utils.py:27-31`).
    params = engine.params
    assert_replicas_in_sync(params)
    rprint(f"model hash: {get_model_hash(params)}")
    return accuracy


if __name__ == "__main__":
    _args = parse_args()
    configure_platform(_args)
    train(_args)
