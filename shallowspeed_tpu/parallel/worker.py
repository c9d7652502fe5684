"""The pipeline VM executor — L4.

Capability parity with the reference `Worker` (`/root/reference/shallowspeed/
pipe.py:330-466`): allocates input/output comm buffers per schedule, interprets
the instruction stream through a class→method dispatch table
(`pipe.py:420-432`), and runs Forward/Backward/Zero/Step against the model.

Re-designed for single-controller JAX:

- The reference runs one `Worker` per MPI process; here ONE
  `PipelineExecutor` drives every stage of the pipeline from one Python
  process. Each stage gets a `StageRuntime` pinned to one *column* of the
  (dp, pp) mesh; the executor advances all stages' instruction streams with a
  make-progress loop over FIFO channels. JAX dispatch is asynchronous, so
  compute for different stages/devices overlaps in wall-clock even though
  dispatch is sequential — the single-controller analogue of the reference's
  concurrent ranks.
- `Send`/`Recv` (`pipe.py:367-381`, blocking MPI) become `jax.device_put`
  of the buffer onto the consumer stage's sharding — an async ICI transfer.
- DP is folded *into* each stage executable as SPMD: batches are sharded
  over the 'dp' axis of the stage's submesh, `BackwardGradAcc` keeps
  per-replica partial gradient sums exactly like the reference's per-rank
  `param.grad +=` (`layers.py:135-136`), and `BackwardGradAllReduce` performs
  one bucketed `lax.psum` of the whole accumulated pytree over 'dp'
  (replacing the per-parameter `Iallreduce`+`Waitall` choreography,
  `pipe.py:302-327`; the bucketing is the improvement the reference's own
  docstring points at, `pipe.py:309-310`).
- Activation stashes live in a per-stage dict keyed by mubatch_id — the
  executor-level equivalent of the reference's `_cache[f"input_{mubatch_id}"]`
  (`layers.py:70,117`), sized by the schedule (GPipe: n_mu; 1F1B: pipeline
  depth).
"""

from __future__ import annotations

from collections import deque
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


from shallowspeed_tpu.models.mlp import MLPStage
from shallowspeed_tpu.parallel.instructions import (
    BackwardGradAcc,
    BackwardGradAllReduce,
    Forward,
    LoadMuBatchInput,
    LoadMuBatchTarget,
    OptimizerStep,
    RecvActivations,
    RecvOutputGrad,
    SendActivations,
    SendInputGrad,
    ZeroGrad,
)

tree_map = jax.tree_util.tree_map


class StageRuntime:
    """Device state + jitted executables for one pipeline stage.

    Owns: params (replicated over the stage's dp-submesh), the gradient
    accumulator (leading dp axis, sharded), optimizer state, activation
    stashes, and the comm buffers (`pipe.py:336-353,446-454`).
    """

    def __init__(self, stage: MLPStage, devices: np.ndarray, optimizer,
                 health: str = "off"):
        from shallowspeed_tpu.telemetry.health import MODES

        assert health in MODES, health
        self.health = health
        self.last_pack = None  # this STAGE's local health pack
        self._nf_batches = None  # device-side cumulative: batches with
        #                          nonfinite grads ON THIS STAGE
        self.stage = stage
        self.submesh = Mesh(np.asarray(devices).reshape(-1), axis_names=("dp",))
        self.dp = self.submesh.devices.size
        self.optimizer = optimizer

        self.rep = NamedSharding(self.submesh, P())        # replicated
        self.row = NamedSharding(self.submesh, P("dp"))    # batch-sharded

        self.params = jax.device_put(stage.init(), self.rep)
        self.opt_state = (jax.device_put(optimizer.init(self.params), self.rep)
                          if optimizer is not None else None)
        self.grad_acc = None     # (dp, ...) pytree, sharded over 'dp'
        self.reduced_grads = None  # replicated pytree after AllReduce
        self.stash: dict[int, object] = {}
        self.input_buffers: list = []
        self.output_buffers: list = []

        mesh, rt = self.submesh, self

        @partial(jax.jit)
        @partial(shard_map, mesh=mesh, in_specs=(P(), P("dp")),
                 out_specs=(P("dp"), P("dp")))
        def _fwd(params, x):
            out, stash = rt.stage.forward(params, x)
            return out, stash

        @partial(jax.jit)
        @partial(shard_map, mesh=mesh, in_specs=(P(), P("dp")),
                 out_specs=P("dp"))
        def _infer(params, x):
            return rt.stage.infer(params, x)

        @partial(jax.jit)
        @partial(shard_map, mesh=mesh,
                 in_specs=(P(), P("dp"), P("dp"), P("dp")),
                 out_specs=(P("dp"), P("dp")))
        def _bwd_acc(params, stash, dout, acc):
            dx, grads = rt.stage.backward(params, stash, dout)
            new_acc = tree_map(lambda a, g: a + g[None], acc, grads)
            return dx, new_acc

        @partial(jax.jit)
        @partial(shard_map, mesh=mesh, in_specs=(P(),),
                 out_specs=P("dp"))
        def _zeros_acc(params):
            # the accumulator must be born with the SAME sharding the
            # steady-state path produces (a shard_map output under
            # out_specs P('dp')): a plain device_put(zeros, row) carries
            # a differently-normalized sharding in the jit cache key, so
            # the second BackwardGradAcc of every batch silently
            # recompiled each stage's _bwd_acc — caught by telemetry's
            # recompile counter (PR 2), invisible before it
            return tree_map(
                lambda p: jnp.zeros((1,) + p.shape, p.dtype), params)

        health_mode = health
        ar_out = ((P("dp"), P()) if health == "off"
                  else (P("dp"), P(), P()))

        @partial(jax.jit)
        @partial(shard_map, mesh=mesh,
                 in_specs=(P(), P("dp"), P("dp"), P("dp")),
                 out_specs=ar_out)
        def _bwd_allreduce(params, stash, dout, acc):
            dx, grads = rt.stage.backward(params, stash, dout)
            new_acc = tree_map(lambda a, g: a + g[None], acc, grads)
            # One bucketed all-reduce of the whole accumulated pytree over
            # the dp axis (vs per-param Iallreduce, `pipe.py:302-316`).
            total = tree_map(
                lambda a: jax.lax.psum(a, "dp")[0], new_acc)
            if health_mode == "off":
                return dx, total
            # this STAGE's local health pack, fused into the same
            # executable (no extra entrypoint); the executor merges the
            # per-stage packs over pp on the host (health.merge_packs)
            from shallowspeed_tpu.telemetry.health import grad_health

            return dx, total, grad_health(params, total)

        def _opt(params, grads, opt_state, ok=None):
            # Per-stage update outside shard_map: `grad_clip` here clips by
            # the *stage's* gradient norm (stages are independent programs
            # in this interpreted engine). The compiled SPMD engine
            # (`spmd_pipeline.py`) clips by the true cross-stage global
            # norm via clip_axes=("pp",). Under health="guard" the
            # executor passes the GLOBAL ok (all stages' sentinels
            # host-combined) so the whole pipeline skips in lockstep.
            from shallowspeed_tpu.telemetry.health import update_health

            from shallowspeed_tpu.telemetry.health import param_l2

            if health_mode == "guard":
                new_p, new_s = rt.optimizer.guarded_step(
                    params, grads, opt_state, ok)
                upd = update_health({"param_norm": param_l2(params)},
                                    params, new_p,
                                    skipped=1 - ok.astype("int32"))
                return new_p, new_s, upd
            new_p, new_s = rt.optimizer.step(params, grads, opt_state)
            if health_mode == "off":
                return new_p, new_s
            upd = update_health({"param_norm": param_l2(params)},
                                params, new_p)
            return new_p, new_s, upd

        self._fwd = _fwd
        self._infer = _infer
        self._bwd_acc = _bwd_acc
        self._bwd_allreduce = _bwd_allreduce
        self._zeros_acc = _zeros_acc
        self._opt = jax.jit(_opt) if optimizer is not None else None

    # ------------------------------------------------------------ state ops

    def zero_grad(self):
        """Fresh (dp, ...) zero accumulator (`pipe.py:411-412`), built
        through the compiled producer so its sharding matches the
        steady-state `_bwd_acc` output (see `_zeros_acc`)."""
        self.grad_acc = self._zeros_acc(self.params)
        self.reduced_grads = None

    def forward(self, x, mubatch_id: int, training: bool = True):
        if training:
            out, stash = self._fwd(self.params, x)
            self.stash[mubatch_id] = stash
            return out
        return self._infer(self.params, x)

    def backward(self, dout, mubatch_id: int, allreduce: bool):
        stash = self.stash.pop(mubatch_id)
        if allreduce:
            out = self._bwd_allreduce(self.params, stash, dout,
                                      self.grad_acc)
            dx, self.reduced_grads = out[0], out[1]
            if self.health != "off":
                self.last_pack = out[2]
                # cumulative, lazily on device (no sync): a transient
                # bad batch between snapshot fetches is still counted
                bad = (out[2]["nonfinite"] > 0).astype("int32")
                self._nf_batches = (bad if self._nf_batches is None
                                    else self._nf_batches + bad)
        else:
            dx, self.grad_acc = self._bwd_acc(self.params, stash, dout,
                                              self.grad_acc)
        return dx

    def optimizer_step(self, ok=None):
        assert self.reduced_grads is not None, \
            "OptimizerStep before BackwardGradAllReduce"
        if self.health == "guard":
            self.params, self.opt_state, upd = self._opt(
                self.params, self.reduced_grads, self.opt_state, ok)
            self.last_pack = {**(self.last_pack or {}), **upd}
        elif self.health != "off":
            self.params, self.opt_state, upd = self._opt(
                self.params, self.reduced_grads, self.opt_state)
            self.last_pack = {**(self.last_pack or {}), **upd}
        else:
            self.params, self.opt_state = self._opt(
                self.params, self.reduced_grads, self.opt_state)
        self.reduced_grads = None


class PipelineExecutor:
    """Single-controller interpreter for per-stage instruction streams.

    `execute(schedules, batch_id, datasets)` is the counterpart of the
    reference's `Worker.execute(sched, batch_id)` (`pipe.py:434-466`), run for
    all stages at once: per-stage program counters advance whenever not
    blocked on an empty channel, sends enqueue async device-to-device
    transfers, and the loop terminates when every stream is drained (the FIFO
    pairing that MPI message ordering provided, `pipe.py:367-381`).
    """

    def __init__(self, mesh: Mesh, stages: Sequence[MLPStage], optimizer,
                 health: str = "off"):
        assert mesh.axis_names == ("dp", "pp")
        self.mesh = mesh
        self.dp, self.pp = mesh.devices.shape
        assert len(stages) == self.pp
        self.health = health
        self.health_skipped = 0   # batches skipped under "guard"
        self._guard_ok = None     # this batch's host-combined sentinel
        self.runtimes = [
            StageRuntime(stage, mesh.devices[:, s], optimizer,
                         health=health)
            for s, stage in enumerate(stages)]
        self._infer_outputs: list = []
        # measured comm accounting (telemetry): device-to-device hop
        # bytes (pp) and per-device dp-psum payload bytes, cumulative
        self.comm_bytes: dict[str, int] = {}

    @property
    def last(self) -> StageRuntime:
        return self.runtimes[-1]

    # ------------------------------------------------------------- data

    def _stacked(self, datasets, batch_id, mubatch_id, target: bool):
        """(dp * mubs, dim) host batch assembled from the per-replica strided
        shards, placed sharded over the stage's dp axis."""
        parts = [
            (ds.load_micro_batch_target if target
             else ds.load_micro_batch_input)(batch_id, mubatch_id)
            for ds in datasets]
        return np.concatenate(parts, axis=0)

    # ------------------------------------------------------------ execute

    def execute(self, schedules, batch_id: int, datasets,
                training: bool = True):
        """Run one batch. `schedules`: one Schedule per stage. `datasets`:
        list of dp per-rank Dataset shards (reference loads one shard per DP
        rank, `train.py:113-119`)."""
        from shallowspeed_tpu.telemetry import tracer

        progs = [list(_flatten(s.steps())) for s in schedules]
        pcs = [0] * self.pp
        self._infer_outputs = []
        # channels keyed (src, dst) hold in-flight device arrays (FIFO)
        channels: dict[tuple[int, int], deque] = {}

        def chan(src, dst):
            return channels.setdefault((src, dst), deque())

        total = sum(len(p) for p in progs)
        done = 0
        with tracer().span("batch", batch=batch_id,
                           training=training) as sp:
            while done < total:
                progress = False
                for s in range(self.pp):
                    rt = self.runtimes[s]
                    while pcs[s] < len(progs[s]):
                        cmd = progs[s][pcs[s]]
                        if isinstance(cmd, RecvActivations) \
                                and not chan(s - 1, s):
                            break
                        if isinstance(cmd, RecvOutputGrad) \
                                and not chan(s + 1, s):
                            break
                        if isinstance(cmd, OptimizerStep) \
                                and self.health == "guard" \
                                and self._guard_ok is None \
                                and any(r.reduced_grads is None
                                        for r in self.runtimes):
                            # the guarded update needs every stage's
                            # nonfinite sentinel: block the FIRST step
                            # of the batch until all stages have
                            # reduced (the reductions never depend on
                            # a step, so this cannot deadlock); once
                            # the combined sentinel exists, later
                            # stages step freely
                            break
                        self._dispatch(cmd, rt, s, batch_id, datasets,
                                       chan, training)
                        pcs[s] += 1
                        done += 1
                        progress = True
                if not progress:
                    raise RuntimeError(f"pipeline deadlock at pcs={pcs}")
            sp.fence(*[rt.params[0]["b"] for rt in self.runtimes])

    def _dispatch(self, cmd, rt: StageRuntime, s: int, batch_id, datasets,
                  chan, training):
        from shallowspeed_tpu.telemetry import tracer

        tr = tracer()
        if isinstance(cmd, ZeroGrad):
            rt.zero_grad()
            self._guard_ok = None  # a fresh batch, a fresh sentinel
        elif isinstance(cmd, OptimizerStep):
            with tr.span("OptimizerStep", stage=s, batch=batch_id) as sp:
                ok = None
                if self.health == "guard":
                    if self._guard_ok is None:
                        # ONE host sync per batch: combine every
                        # stage's nonfinite sentinel into the global
                        # skip decision all stages share
                        nf = sum(int(jax.device_get(
                            r.last_pack["nonfinite"]))
                            for r in self.runtimes)
                        self._guard_ok = np.asarray(nf == 0)
                        if nf:
                            self.health_skipped += 1
                    ok = self._guard_ok
                rt.optimizer_step(ok)
                sp.fence(rt.params[0]["b"])
        elif isinstance(cmd, LoadMuBatchInput):
            data = self._stacked(datasets, batch_id, cmd.mubatch_id, False)
            rt.input_buffers[cmd.buffer_id] = jax.device_put(data, rt.row)
        elif isinstance(cmd, LoadMuBatchTarget):
            data = self._stacked(datasets, batch_id, cmd.mubatch_id, True)
            rt.output_buffers[cmd.buffer_id] = jax.device_put(data, rt.row)
        elif isinstance(cmd, Forward):
            # the compute instructions carry (stage, mu, batch) span
            # attribution: at the `spans` level this IS the executed
            # schedule trace telemetry.bubble.trace_bubble replays
            # against verify.py's makespan model
            with tr.span("Forward", stage=s, mu=cmd.mubatch_id,
                         batch=batch_id) as sp:
                out = rt.forward(rt.input_buffers[cmd.buffer_id],
                                 cmd.mubatch_id, training)
                rt.output_buffers[cmd.buffer_id] = out
                sp.fence(out)
            if not training and rt is self.last:
                self._infer_outputs.append(out)
        elif isinstance(cmd, BackwardGradAcc):
            with tr.span("BackwardGradAcc", stage=s, mu=cmd.mubatch_id,
                         batch=batch_id) as sp:
                dx = rt.backward(rt.output_buffers[cmd.buffer_id],
                                 cmd.mubatch_id, False)
                rt.input_buffers[cmd.buffer_id] = dx
                sp.fence(dx)
        elif isinstance(cmd, BackwardGradAllReduce):
            with tr.span("BackwardGradAllReduce", stage=s,
                         mu=cmd.mubatch_id, batch=batch_id) as sp:
                dx = rt.backward(rt.output_buffers[cmd.buffer_id],
                                 cmd.mubatch_id, True)
                rt.input_buffers[cmd.buffer_id] = dx
                sp.fence(dx)
            # one bucketed dp-psum of the whole grad pytree ran inside:
            # measured collective accounting (bytes entering the psum)
            self.comm_bytes["dp_psum"] = self.comm_bytes.get(
                "dp_psum", 0) + self._grad_bytes(rt)
        elif isinstance(cmd, SendActivations):
            nxt = self.runtimes[s + 1]
            buf = rt.output_buffers[cmd.buffer_id]
            self.comm_bytes["pp_p2p"] = self.comm_bytes.get(
                "pp_p2p", 0) + int(buf.nbytes)
            chan(s, s + 1).append(jax.device_put(buf, nxt.row))
        elif isinstance(cmd, RecvActivations):
            rt.input_buffers[cmd.buffer_id] = chan(s - 1, s).popleft()
        elif isinstance(cmd, SendInputGrad):
            prv = self.runtimes[s - 1]
            buf = rt.input_buffers[cmd.buffer_id]
            self.comm_bytes["pp_p2p"] = self.comm_bytes.get(
                "pp_p2p", 0) + int(buf.nbytes)
            chan(s, s - 1).append(jax.device_put(buf, prv.row))
        elif isinstance(cmd, RecvOutputGrad):
            rt.output_buffers[cmd.buffer_id] = chan(s + 1, s).popleft()
        else:
            raise TypeError(f"unknown instruction {cmd!r}")

    @staticmethod
    def _grad_bytes(rt: StageRuntime) -> int:
        """Per-device payload of the stage's bucketed dp-psum: the
        whole params-shaped grad pytree (each device holds one (1, ...)
        shard of the (dp, ...) accumulator)."""
        return sum(int(l.nbytes) for layer in rt.params
                   for l in layer.values())

    # ----------------------------------------------- telemetry surface

    def telemetry_entrypoints(self) -> list:
        """Per-stage compiled executables (args=None: the VM measures
        its traffic directly via `comm_bytes` instead of a jaxpr walk,
        but the recompile counter still reads these caches)."""
        out = []
        for s, rt in enumerate(self.runtimes):
            for name, fn in (("fwd", rt._fwd), ("bwd", rt._bwd_acc),
                             ("bwd_ar", rt._bwd_allreduce),
                             ("opt", rt._opt), ("infer", rt._infer)):
                if fn is not None:
                    out.append({"name": f"s{s}.{name}", "fn": fn,
                                "args": None})
        return out

    def telemetry_traffic(self) -> dict:
        """MEASURED cumulative comm bytes (pp hop transfers, dp psum
        payloads) — the interpreted engine's counterpart of the
        compiled engines' static jaxpr-walk accounting."""
        return dict(self.comm_bytes)

    def health_snapshot(self) -> dict | None:
        """The last batch's health pack: per-STAGE local packs (each
        stage is its own executable) fetched and merged over pp on the
        host (health.merge_packs — norms combine as sqrt-of-sum-of-
        squares since stages partition the params; groups get an
        `s<i>.` prefix). None before the first batch or health='off'."""
        from shallowspeed_tpu.telemetry.health import (fetch_pack,
                                                       merge_packs)

        import jax

        merged = merge_packs(
            [fetch_pack(rt.last_pack) for rt in self.runtimes])
        if merged is None:
            return None
        # cumulative counters: batches-with-nonfinite is the max over
        # the per-stage device counters (one backward's NaN reaches a
        # contiguous stage suffix, so the worst stage saw every bad
        # batch); guarded skips are counted exactly on the host (the
        # guard already syncs once per batch)
        nf = [int(jax.device_get(rt._nf_batches))
              for rt in self.runtimes if rt._nf_batches is not None]
        if nf:
            merged["nonfinite_steps_total"] = max(nf)
        if self.health == "guard":
            merged["skipped"] = 1 if (self._guard_ok is not None
                                      and not self._guard_ok) else 0
            merged["skipped_total"] = self.health_skipped
        return merged

    def allocate_buffers(self, num_buffers: int):
        """Reference allocates numpy comm buffers per schedule
        (`pipe.py:446-454`); JAX arrays are immutable so buffers here are
        just slots — allocation is slot-count bookkeeping."""
        for rt in self.runtimes:
            n = num_buffers // 2
            rt.input_buffers = [None] * n
            rt.output_buffers = [None] * n

    # --------------------------------------------------------- conveniences

    def train_batch(self, schedule_cls, n_mubatches: int, batch_id: int,
                    datasets):
        scheds = [schedule_cls(n_mubatches, self.pp, s) for s in range(self.pp)]
        self.allocate_buffers(max(s.num_buffers for s in scheds))
        self.execute(scheds, batch_id, datasets, training=True)

    def infer_batch(self, schedule_cls, n_mubatches: int, batch_id: int,
                    datasets):
        """Forward-only streaming; returns the last stage's outputs for ALL
        microbatches, concatenated in microbatch order (reference
        `compute_accuracy`, `train.py:31-43`, uses one microbatch)."""
        scheds = [schedule_cls(n_mubatches, self.pp, s) for s in range(self.pp)]
        self.allocate_buffers(max(s.num_buffers for s in scheds))
        self.execute(scheds, batch_id, datasets, training=False)
        outs = self._infer_outputs
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)

    @property
    def params(self):
        return [rt.params for rt in self.runtimes]

    @property
    def opt_state(self):
        return [rt.opt_state for rt in self.runtimes]

    # -------------------------------------------------- checkpoint interface

    def get_canonical_params(self):
        """Concatenate per-stage layer lists into the whole-model flat list."""
        return [layer for rt in self.runtimes for layer in rt.params]

    def set_canonical_params(self, layers):
        i = 0
        for rt in self.runtimes:
            n = rt.stage.n_linears
            rt.params = jax.device_put(list(layers[i:i + n]), rt.rep)
            i += n
        assert i == len(layers), (i, len(layers))

    def set_opt_state(self, states):
        assert len(states) == len(self.runtimes), (
            f"{len(states)} per-stage states for {len(self.runtimes)} stages")
        for rt, st in zip(self.runtimes, states):
            rt.opt_state = jax.device_put(st, rt.rep)

    @property
    def optimizer(self):
        return self.runtimes[0].optimizer

    def canon_opt_export(self):
        """Merge the per-stage optimizer states into the canonical
        whole-model state (the pp=1 layout): every params-shaped moment
        tree is a per-stage layer list, so the canonical moment is their
        concatenation in stage order — the exact transform
        `get_canonical_params` applies to the params. Stage-invariant
        scalars (step counters) come from stage 0 (all stages step in
        lockstep). None when the optimizer's state is not params-shaped."""
        states = [jax.device_get(rt.opt_state) for rt in self.runtimes]
        try:
            per_stage = []
            for st in states:
                trees: list = []
                self.optimizer.map_state_trees(
                    st, lambda t: (trees.append(t), t)[1])
                per_stage.append(trees)
        except ValueError:
            return None
        k = len(per_stage[0])
        if any(len(t) != k for t in per_stage):
            return None
        if k == 0:  # stateless / counter-only: any stage's copy
            return states[0]
        merged = iter([
            [layer for stage in per_stage for layer in stage[i]]
            for i in range(k)])
        return self.optimizer.map_state_trees(
            states[0], lambda _t: next(merged))

    def canon_opt_import(self, canon):
        """Split a canonical whole-model state back into per-stage
        states (the inverse of `canon_opt_export`)."""
        try:
            out, lo = [], 0
            for rt in self.runtimes:
                hi = lo + rt.stage.n_linears
                out.append(self.optimizer.map_state_trees(
                    canon, lambda tree, lo=lo, hi=hi: list(tree[lo:hi])))
                lo = hi
            return out
        except ValueError:
            return None


def _flatten(steps_gen):
    for step in steps_gen:
        yield from step
