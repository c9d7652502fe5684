"""Training driver: any configuration under any `train` traffic file,
through the engine `train_lm.py` builds for a `dp` mesh
(`ContextParallelEngine`, which draws its weights from the seed on the
host), with the configuration's recipe.

Steps are dispatched back to back with at most two in flight, so the
device never waits for the host and the host never runs more than one
step ahead; each step's completion is stamped when its loss is ready,
and the window closes on the last one."""

from __future__ import annotations

import math

import numpy as np

from harness import arith, model, reference, traffic

PROGRAMS = {"step": r"^jit__step\("}
MODE = "training"
# The engine's first-step loss against the reference's loss on the same
# batch and weights. At the seeded weights the logits are small, and the
# engine's bf16 forward pass lands within 2e-5 nats of the float32
# reference (chip, PR 24); a wrong mask, a shifted target or a dropped
# layer moves the loss by hundredths to whole nats. After some tens of
# steps the same comparison differs by 0.06 (bf16 on grown logits), so it
# is made at the first step, where it is sharp.
LOSS_TOLERANCE = 0.002
IN_FLIGHT = 2


def build_engine(cfg, c: dict, t: dict, seed: int, devices):
    from jax.sharding import Mesh

    from shallowspeed_tpu.optim import OPTIMIZERS
    from shallowspeed_tpu.parallel.context import ContextParallelEngine

    opt = OPTIMIZERS[c["optimizer"]](lr=float(c["training"]["lr"]))
    mesh = Mesh(np.array(devices).reshape(int(t["dp"]), 1), ("dp", "sp"))
    return ContextParallelEngine(cfg, opt, mesh, seed=seed,
                                 attn=c["training"]["attn"])


def run(job) -> dict:
    import jax

    c, t, rec = job.config, job.traffic, job.recorder
    shapes = arith.Shapes.from_config(c)
    cfg = model.transformer_config(c, MODE)
    dp = int(t["dp"])
    devices = jax.devices()[:dp]
    if len(devices) < dp:
        raise SystemExit(f"traffic needs dp={dp}, found {len(devices)} devices")
    with rec.span("make_data"):
        batches = traffic.train_batches(t, job.seed, shapes.vocab, dp)
    with rec.span("engine"):
        eng = build_engine(cfg, c, t, job.seed, devices)
    step_fn = eng._step_fn
    # The reference's loss on the first batch, from the engine's own first
    # weights while the device still holds them: the first step donates
    # them, and drawing them again on the host would cost every run half a
    # minute. About 1.5 s of set-up on one chip.
    with rec.span("reference"):
        ref_loss = reference.batch_loss(
            eng.params, *batches[0], shapes, c["program"],
            float(c["rope_theta"]))
    with rec.span("warm"):
        first_loss = float(eng.train_batch_async(*batches[0]))
        for k in range(1, int(t["warm_steps"])):
            loss = eng.train_batch_async(*batches[k % len(batches)])
        jax.block_until_ready(loss)

    tokens_per_step = batches[0][0].size
    compiles_before = step_fn._cache_size()
    clock = rec.clock
    t_origin = clock()
    job.window_opens(t_origin)
    pending, done_at, losses = [], [], []
    k = int(t["warm_steps"])
    while True:
        now = clock() - t_origin
        job.on_loop(now)
        if now >= job.seconds:
            break
        with rec.span("data"):
            batch = batches[k % len(batches)]
        with rec.span("step"):
            pending.append(eng.train_batch_async(*batch))
        k += 1
        if len(pending) >= IN_FLIGHT:
            with rec.span("wait"):
                loss = pending.pop(0)
                jax.block_until_ready(loss)
            done_at.append(clock())
            losses.append(loss)
    with rec.span("wait"):
        for loss in pending:
            jax.block_until_ready(loss)
            done_at.append(clock())
            losses.append(loss)
    window_s = clock() - t_origin
    job.window_closes()
    compiles = step_fn._cache_size() - compiles_before
    peak = job.memory_peak()
    n_steps = len(done_at)
    loss_values = [float(x) for x in losses]
    bad = sum(1 for x in loss_values if not math.isfinite(x))
    loss_err = abs(first_loss - ref_loss)
    tok_s_chip = n_steps * tokens_per_step / (window_s * dp)
    step_ms = [(b - a) * 1e3 for a, b in zip(done_at, done_at[1:])]
    return {
        "correct": bool(bad == 0 and math.isfinite(first_loss)
                        and n_steps > 0 and loss_err <= LOSS_TOLERANCE),
        "attempted": n_steps, "failed": bad,
        "end_to_end": {"train_tok_s_chip": tok_s_chip},
        "memory_peak_bytes": peak,
        "notes": {"first_loss": first_loss, "reference_loss": ref_loss,
                  "loss_err": loss_err, "steps": n_steps, "window_s": window_s,
                  "last_loss": loss_values[-1] if loss_values else None},
        "layers": {
            "programs": PROGRAMS, "shapes": shapes, "window_s": window_s,
            "step_ms": step_ms, "tok_s_chip": tok_s_chip,
            "seq_len": int(t["seq_len"]), "tokens_per_step": tokens_per_step,
            "chips": dp, "compiles": compiles,
        },
    }
