"""Fused training engines — the compiled fast path.

The pipeline VM (`parallel/worker.py`) interprets instruction streams with one
dispatch per instruction, mirroring the reference's executor
(`/root/reference/shallowspeed/pipe.py:434-466`). For dp×1 topologies the
whole batch step can instead be **one** jitted XLA program: `lax.scan` over
the microbatch stack (grad accumulation, `layers.py:135-136` semantics), the
DP reduction over the 'dp' mesh axis, and the optimizer update — zero Python
dispatch inside the step, which is what the TPU wants.

The DP reduction has two modes. The default (the oracle) is the bulk
reduction: per-leaf `lax.psum` of the fully accumulated grads AFTER the
microbatch scan — and because the scan is a single dataflow node, every
byte of that reduction is *exposed* (there is no independent compute
left for XLA's latency-hiding scheduler to hide it under). With
`overlap=OverlapConfig(...)` the engine instead peels the last
microbatch out of the scan and interleaves size-targeted bucket psums
into its hand-written layer-by-layer backward
(`parallel/overlap.bucketed_stage_backward`) — the compiled equivalent
of the reference's per-parameter `Iallreduce` hooks interleaving
reduction of layer i with the backward of layer i-1
(`pipe.py:302-327`). Same math, same wire bytes, strictly lower
exposed-communication fraction (telemetry's `exposed_comm_frac`).

Sequential training (`--dp 1 --pp 1`, reference `train.py:62-155` with no
flags) is the dp=1 special case.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


from shallowspeed_tpu.models.mlp import MLPStage, accumulate_grads, zero_grads_like
from shallowspeed_tpu.utils import pvary_over as _pvary

tree_map = jax.tree_util.tree_map


def _note_step(engine, pack):
    # health.note_step, imported lazily (telemetry stays off the module
    # import path): stores last_health + device-side cumulative counters
    from shallowspeed_tpu.telemetry.health import note_step

    note_step(engine, pack)



class FusedDPEngine:
    """One-executable data-parallel trainer over the 'dp' axis of the mesh.

    Equivalent semantics to `PipelineExecutor` with pp=1 and any schedule
    (they all reduce to: zero, k x (fwd, bwd-acc), allreduce, step on a
    single stage) — verified against the VM in tests.
    """

    def __init__(self, stage: MLPStage, optimizer, mesh: Mesh,
                 health: str = "off", overlap=None):
        from shallowspeed_tpu.telemetry.health import MODES

        assert stage.n_stages == 1
        assert health in MODES, health
        self.health = health
        self.last_health = None
        self.overlap = overlap  # parallel.overlap.OverlapConfig | None
        self.stage = stage
        self.optimizer = optimizer
        # accept a (dp, 1) 2-D mesh or a 1-D ('dp',) mesh
        if mesh.axis_names != ("dp",):
            devs = mesh.devices.reshape(-1)
            mesh = Mesh(devs, ("dp",))
        self.mesh = mesh
        self.dp = mesh.devices.size
        self.rep = NamedSharding(mesh, P())
        self.shard4 = NamedSharding(mesh, P("dp"))  # (dp, n_mu, mubs, d)

        self.params = jax.device_put(stage.init(), self.rep)
        self.opt_state = jax.device_put(optimizer.init(self.params), self.rep)

        stage_ref = self.stage
        opt_ref = self.optimizer

        # bucket plan for the overlapped reduction: the stage's leaves
        # in backward-finalization order, partitioned by target bytes
        if overlap is not None:
            from shallowspeed_tpu.parallel import overlap as OV

            order = OV.mlp_leaf_order(self.params)
            raw = OV.plan_buckets([l for _, l in order],
                                  overlap.bucket_bytes)
            ov_plan = [[order[j][0] for j in b] for b in raw]
            leaf_by_id = dict(order)
            self._bucket_sigs = [
                OV.bucket_signature([leaf_by_id[i] for i in b])
                for b in ov_plan]
        else:
            ov_plan = None
            self._bucket_sigs = []

        def batch_grads(params, x_mu, y_mu):
            """The ONE encoding of the per-device gradient computation
            on (n_mu, mubs, d) microbatch stacks: grad-accumulating
            scan over microbatches (`layers.py:135-136` semantics),
            then the DP reduction — per-leaf bulk psums after the scan
            (the oracle), or, with `overlap`, bucket psums interleaved
            into the peeled last microbatch's layer-by-layer backward
            (`pipe.py:302-327` equivalent). Shared by the plain and
            health-instrumented steps so the two can never train
            differently."""

            def mu_body(acc, xy):
                x, y = xy
                _, stash = stage_ref.forward(params, x)
                _, grads = stage_ref.backward(params, stash, y)
                return accumulate_grads(acc, grads), None

            # the zero init is axis-invariant but the accumulated grads vary
            # per dp shard — cast the carry to varying for shard_map's typing
            acc0 = _pvary(zero_grads_like(params), ("dp",))
            if ov_plan is None:
                acc, _ = jax.lax.scan(mu_body, acc0, (x_mu, y_mu))
                return tree_map(lambda g: jax.lax.psum(g, "dp"), acc)
            from shallowspeed_tpu.parallel.overlap import (
                bucketed_stage_backward)

            # peel the last microbatch: the first n_mu-1 accumulate in
            # the scan (unreduced); the peeled backward finalizes each
            # leaf's total and psums each bucket as soon as its leaves
            # are final — interleaved with the remaining backward
            acc, _ = jax.lax.scan(mu_body, acc0,
                                  (x_mu[:-1], y_mu[:-1]))
            _, stash = stage_ref.forward(params, x_mu[-1])
            return bucketed_stage_backward(
                stage_ref, params, stash, y_mu[-1], acc, ov_plan,
                ("dp",))

        def local_step(params, opt_state, x_mu, y_mu):
            """batch_grads + optimizer update (the _epoch/_run body)."""
            return opt_ref.step(params, batch_grads(params, x_mu, y_mu),
                                opt_state)

        health_mode = health

        def step_with_health(params, opt_state, x_mu, y_mu):
            """local_step + the fused health pack (telemetry/health.py):
            grads after the dp psum are replicated, so the pack needs no
            further reductions; under "guard" the update is gated on the
            nonfinite sentinel (optim.guarded_step — a skipped step is
            bit-identical to never having run)."""
            from shallowspeed_tpu.telemetry.health import (grad_health,
                                                           update_health)

            total = batch_grads(params, x_mu, y_mu)
            pack = grad_health(params, total)
            if health_mode == "guard":
                ok = pack["nonfinite"] == 0
                new_p, new_s = opt_ref.guarded_step(params, total,
                                                    opt_state, ok)
                pack = update_health(pack, params, new_p,
                                     skipped=1 - ok)
            else:
                new_p, new_s = opt_ref.step(params, total, opt_state)
                pack = update_health(pack, params, new_p)
            return new_p, new_s, pack

        step_out = ((P(), P()) if health == "off" else (P(), P(), P()))

        @partial(jax.jit, donate_argnums=(0, 1))
        @partial(shard_map, mesh=mesh,
                 in_specs=(P(), P(), P("dp"), P("dp")),
                 out_specs=step_out)
        def _step(params, opt_state, xs, ys):
            if health_mode == "off":
                return local_step(params, opt_state, xs[0], ys[0])
            return step_with_health(params, opt_state, xs[0], ys[0])

        @partial(jax.jit)
        @partial(shard_map, mesh=mesh, in_specs=(P(), P("dp")),
                 out_specs=P("dp"))
        def _infer(params, x):
            return stage_ref.infer(params, x)

        def _make_run(n_epochs: int):
            @partial(jax.jit, donate_argnums=(0, 1))
            @partial(shard_map, mesh=mesh,
                     in_specs=(P(), P(), P(None, "dp"), P(None, "dp")),
                     out_specs=(P(), P()))
            def _run(params, opt_state, xs, ys):
                # xs: (n_batches, dp, n_mu, mubs, d) — whole run device-
                # resident, ONE XLA dispatch: scan over epochs of (scan over
                # batches of (scan over microbatches)). HBM-residency and
                # fused dispatch are the TPU answer to the reference's
                # per-microbatch host loads (`dataset.py:66-80`).
                def batch_body(carry, xy):
                    p, o = carry
                    x, y = xy
                    return local_step(p, o, x[0], y[0]), None

                def epoch_body(carry, _):
                    carry, _ = jax.lax.scan(batch_body, carry, (xs, ys))
                    return carry, None

                (params, opt_state), _ = jax.lax.scan(
                    epoch_body, (params, opt_state), None, length=n_epochs)
                return params, opt_state

            if overlap is not None:
                from shallowspeed_tpu.parallel import overlap as OV

                OV.register_program(_run, "dp", self._bucket_sigs,
                                    engine="FusedDPEngine")
            return _run

        self._step = _step
        self._infer = _infer
        self._make_run = _make_run
        self._run_cache: dict[int, Any] = {}
        if overlap is not None:
            from shallowspeed_tpu.parallel import overlap as OV

            OV.register_program(_step, "dp", self._bucket_sigs,
                                engine="FusedDPEngine")

    # ------------------------------------------------------------- steps

    def train_batch(self, batch_id: int, datasets):
        """datasets: dp per-rank Dataset shards; assembles the
        (dp, n_mu, mubs, d) stacks and runs the fused step."""
        from shallowspeed_tpu.telemetry import tracer

        stacks = [ds.load_mubatch_stack(batch_id) for ds in datasets]
        xs = np.stack([s[0] for s in stacks])
        ys = np.stack([s[1] for s in stacks])
        with tracer().span("step", batch=batch_id) as sp:
            xs = jax.device_put(xs, self.shard4)
            ys = jax.device_put(ys, self.shard4)
            if self._telemetry_eps is None and tracer().level != "off":
                self._record_entrypoints(xs, ys)
            out = self._step(self.params, self.opt_state, xs, ys)
            self.params, self.opt_state = out[0], out[1]
            if self.health != "off":
                _note_step(self, out[2])
            sp.fence(self.params[0]["b"])

    def infer(self, x: np.ndarray) -> jax.Array:
        """Forward on a (rows, 784) batch sharded over dp (rows % dp == 0)."""
        x = jax.device_put(x, NamedSharding(self.mesh, P("dp")))
        return self._infer(self.params, x)

    # ------------------------------------------------------ epoch staging

    def stage_epoch(self, datasets, n_batches: int | None = None):
        """Device-put the whole epoch once: returns (xs, ys) of shape
        (n_batches, dp, n_mu, mubs, d), sharded over 'dp' on axis 1."""
        from shallowspeed_tpu.data.dataset import stack_epoch

        xs, ys = stack_epoch(datasets, n_batches)
        shard = NamedSharding(self.mesh, P(None, "dp"))
        return jax.device_put(xs, shard), jax.device_put(ys, shard)

    def train_epoch(self, staged):
        """One dispatch for a full epoch over pre-staged device data."""
        self.train_run(staged, 1)

    def train_run(self, staged, n_epochs: int):
        """One dispatch for a full n_epochs training run over pre-staged
        device data (same epoch data each epoch, as the reference has no
        shuffling — `dataset.py:66-80` indexes deterministically)."""
        from shallowspeed_tpu.telemetry import tracer

        xs, ys = staged
        run = self._run_cache.get(n_epochs)
        if run is None:
            run = self._run_cache[n_epochs] = self._make_run(n_epochs)
        with tracer().span("run", n_epochs=n_epochs) as sp:
            self.params, self.opt_state = run(self.params,
                                              self.opt_state, xs, ys)
            sp.fence(self.params[0]["b"])

    # ----------------------------------------------- telemetry surface

    _telemetry_eps = None

    def _record_entrypoints(self, xs, ys):
        from shallowspeed_tpu.telemetry.report import (
            record_engine_entrypoints)

        self._telemetry_eps = record_engine_entrypoints(
            self, xs, ys, step_arg=False)

    def telemetry_entrypoints(self) -> list:
        """(name, fn, SDS args) for telemetry's static accounting
        (report.py); empty before the first traced `train_batch`."""
        return list(self._telemetry_eps or ())

    def health_snapshot(self) -> dict | None:
        """The last train_batch's health pack as a host dict (one
        device_get); None before the first step or with health='off'.
        The fused train_epoch/train_run paths do not carry the pack —
        drivers step per-batch when health is on."""
        from shallowspeed_tpu.telemetry.health import engine_snapshot

        return engine_snapshot(self)

    # -------------------------------------------------- checkpoint interface

    # the pp=1 layout IS canonical, so moments interchange as-is
    canonical_opt_identity = True

    def get_canonical_params(self):
        """pp=1 params ARE the canonical flat layer list; host conversion
        happens once in checkpoint.save_pytree."""
        return self.params

    def set_canonical_params(self, layers):
        self.params = jax.device_put(
            [{k: np.asarray(v) for k, v in layer.items()} for layer in layers],
            self.rep)

    def set_opt_state(self, state):
        self.opt_state = jax.device_put(state, self.rep)
