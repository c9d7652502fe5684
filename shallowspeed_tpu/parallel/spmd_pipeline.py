"""Fused SPMD pipeline engine — the compiled GPipe performance path.

The pipeline VM (`worker.py`) interprets instruction streams with Python
dispatch per instruction (faithful to the reference's executor,
`/root/reference/shallowspeed/pipe.py:434-466`). This module compiles the
ENTIRE GPipe batch step — warmup, steady state, drain, gradient all-reduce,
optimizer update — into ONE jitted XLA program over a 2-D (dp, pp)
`jax.sharding.Mesh` (SURVEY §7 step 7, option (a)):

- Every device runs the same program (SPMD) under `shard_map`; the stage id
  is `lax.axis_index('pp')`.
- Stage-to-stage activation/grad hops are `lax.ppermute` over the 'pp' axis
  (the ICI neighbor exchange replacing blocking `MPI.Send/Recv`,
  `pipe.py:367-381`).
- The clock runs `n_mu + pp - 1` forward ticks then `n_mu + pp - 1`
  backward ticks via `lax.scan`; bubble ticks compute on zeros and their
  results are masked out — the standard SPMD pipelining formulation (cf. the
  scaling-book pipelining recipe); XLA's latency-hiding scheduler overlaps
  tick t's compute with the neighbor permute.
- Heterogeneous stage widths (the reference's [784,128,...,10] stages,
  SURVEY §7 hard part 1) are handled by zero-padding every stage to an equal
  layer count L and a common max width Wmax. Zero padding is exact for
  linear+ReLU algebra (padded rows/cols contribute 0); the softmax head
  masks padded logits to -1e30. Gradients of padding are forced to zero, so
  the optimizer never moves padded entries.
- DP composes orthogonally: batches are sharded over 'dp', the accumulated
  grads get one bucketed `lax.psum` over 'dp' (replacing per-param
  Iallreduce + Waitall, `pipe.py:302-327`), and the optimizer update runs
  replicated over 'dp' / sharded over 'pp'.

Semantics match GPipe-with-sum-accumulation (microbatch grads summed, loss
scaled by global batch size, `functional.py:43-44`), verified against the
fused sequential engine in tests/test_spmd_pipeline.py.
"""

from __future__ import annotations

import copy
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


from shallowspeed_tpu.models.mlp import init_linear_np, stage_layer_sizes
from shallowspeed_tpu.utils import pvary_over as _pvary

tree_map = jax.tree_util.tree_map


def _note_step(engine, pack):
    # health.note_step, imported lazily (telemetry stays off the module
    # import path): stores last_health + device-side cumulative counters
    from shallowspeed_tpu.telemetry.health import note_step

    note_step(engine, pack)



def _pad_to(arr: np.ndarray, shape) -> np.ndarray:
    out = np.zeros(shape, arr.dtype)
    out[tuple(slice(0, s) for s in arr.shape)] = arr
    return out


class StageStack:
    """Stage-stacked, width-padded parameters + static per-stage metadata.

    Layout: W (pp, L, Wmax, Wmax), b (pp, L, 1, Wmax); flags (pp, L):
    `valid` (layer exists on this stage) and `relu` (layer has a ReLU —
    everything except the last stage's final linear, `layers.py:251-260`).
    """

    def __init__(self, sizes: list[int], pp: int):
        self.sizes = list(sizes)
        self.pp = pp
        self.wmax = max(sizes)
        per_stage = [stage_layer_sizes(sizes, s, pp) for s in range(pp)]
        self.n_linears = [len(ls) - 1 for ls in per_stage]
        self.L = max(self.n_linears)
        self.in_dim = per_stage[0][0]
        self.out_dim = per_stage[-1][-1]

    def init(self):
        pp, L, wmax = self.pp, self.L, self.wmax
        W = np.zeros((pp, L, wmax, wmax), np.float32)
        b = np.zeros((pp, L, 1, wmax), np.float32)
        valid = np.zeros((pp, L), np.float32)
        relu = np.zeros((pp, L), np.float32)
        for s in range(pp):
            local = stage_layer_sizes(self.sizes, s, pp)
            for i in range(len(local) - 1):
                layer = init_linear_np(local[i], local[i + 1])
                W[s, i] = _pad_to(layer["W"], (wmax, wmax))
                b[s, i] = _pad_to(layer["b"], (1, wmax))
                valid[s, i] = 1.0
                is_last_linear = (s == pp - 1) and (i == len(local) - 2)
                relu[s, i] = 0.0 if is_last_linear else 1.0
        head_mask = np.zeros((wmax,), np.float32)
        head_mask[: self.out_dim] = 1.0
        return {"W": W, "b": b}, {"valid": valid, "relu": relu,
                                  "head_mask": head_mask}

    def unstack_params(self, stacked) -> list[list[dict]]:
        """Back to the per-stage list-of-{'W','b'} pytree (unpadded), for
        parity checks and checkpoint interchange with the other engines."""
        W = np.asarray(stacked["W"])
        b = np.asarray(stacked["b"])
        out = []
        for s in range(self.pp):
            local = stage_layer_sizes(self.sizes, s, self.pp)
            layers = []
            for i in range(len(local) - 1):
                layers.append({
                    "W": W[s, i, : local[i + 1], : local[i]].copy(),
                    "b": b[s, i, :, : local[i + 1]].copy(),
                })
            out.append(layers)
        return out


class SPMDPipelineEngine:
    """GPipe training with the whole batch step compiled as one XLA program.

    API-compatible with `FusedDPEngine` (train_batch / stage_epoch /
    train_epoch / infer) so `train.py` and the bench can swap engines.
    """

    def __init__(self, sizes, optimizer, mesh: Mesh, n_mubatches: int,
                 mubatch_size: int, global_batch_size: int,
                 health: str = "off", overlap=None):
        from shallowspeed_tpu.telemetry.health import MODES

        assert health in MODES, health
        self.health = health
        self.last_health = None
        self.overlap = overlap  # parallel.overlap.OverlapConfig | None
        assert mesh.axis_names == ("dp", "pp")
        self.mesh = mesh
        self.dp, self.pp = mesh.devices.shape
        self.n_mu = n_mubatches
        self.mubs = mubatch_size  # per-replica microbatch rows
        self.stack = StageStack(sizes, self.pp)
        self.optimizer = optimizer
        self.wmax = self.stack.wmax
        self.out_dim = self.stack.out_dim
        self.gbs = global_batch_size

        params_h, meta_h = self.stack.init()
        self.p_shard = NamedSharding(mesh, P("pp"))
        self.rep = NamedSharding(mesh, P())
        self.params = jax.device_put(params_h, self.p_shard)
        # static per-stage metadata: small, baked in replicated
        self._valid_full = jnp.asarray(meta_h["valid"])
        self._relu_full = jnp.asarray(meta_h["relu"])
        self._head_mask = jnp.asarray(meta_h["head_mask"])

        template = optimizer.init(self.params)
        opt_specs = tree_map(
            lambda l: P("pp") if getattr(l, "ndim", 0) >= 1 else P(), template)
        self._opt_specs = opt_specs
        self.opt_state = jax.device_put(
            template,
            tree_map(lambda s: NamedSharding(mesh, s), opt_specs))

        self._build()

    # ---------------------------------------------------------------- build

    def _build(self):
        mesh = self.mesh
        n_mu, mubs, wmax = self.n_mu, self.mubs, self.wmax
        L = self.stack.L
        pp = self.pp
        gbs = self.gbs
        # opt.step is traced inside shard_map with grads SHARDED over 'pp'
        # (each device holds only its stage's slice), so the clipping norm
        # must psum over 'pp' to be global. Private copy: the caller's
        # optimizer may also drive engines with full-gradient contexts.
        opt = copy.copy(self.optimizer)
        opt.clip_axes = ("pp",)
        valid_full, relu_full = self._valid_full, self._relu_full
        head_mask = self._head_mask

        right = [(i, (i + 1) % pp) for i in range(pp)]
        left = [((i + 1) % pp, i) for i in range(pp)]

        def stage_fwd(W, b, valid, relu_f, x, is_last):
            """One stage's padded forward on one (mubs, wmax) block.
            Returns (out, stash)."""
            h = x
            xs, masks = [], []
            for l in range(L):
                xs.append(h)
                z = h @ W[l].T + b[l]
                a = jnp.where(relu_f[l] > 0, jnp.maximum(z, 0.0), z)
                masks.append((z > 0) & (relu_f[l] > 0))
                h = jnp.where(valid[l] > 0, a, h)
            # softmax head (meaningful on the last stage only): reference
            # numerics — global max shift + 1e-7 (`functional.py:24-27`) —
            # restricted to the valid class columns.
            logits = h
            ml = jnp.where(head_mask > 0, logits, jnp.float32(-1e30))
            e = jnp.exp(ml - jnp.max(ml))
            probs = e / (e.sum(axis=1, keepdims=True) + 1e-7)
            out = jnp.where(is_last, probs, h)
            stash = {"xs": jnp.stack(xs), "masks": jnp.stack(masks),
                     "probs": probs}
            return out, stash

        def head_grad(probs, target, dout, is_last):
            """MSELoss head: target -> upstream grad
            (`layers.py:157-163`), then softmax VJP expressed via
            probs; non-last stages pass `dout` through. The ONE
            encoding shared by the scanned backward tick and the
            peeled bucketed replay."""
            g0 = -2.0 * (target - probs) / gbs
            gg = probs * g0
            d_head = gg - probs * gg.sum(axis=-1, keepdims=True)
            return jnp.where(is_last, d_head, dout)

        def bwd_layer(W, valid, relu_f, stash, l, d):
            """One layer's padded backward step: (d_next, dW_l, db_l).
            Padding layers are identity (gradient passes through, zero
            weight grads). Shared by stage_bwd and the peeled replay so
            the overlapped path can never diverge from the oracle's
            math."""
            d_in = d
            d_act = jnp.where(relu_f[l] > 0,
                              jnp.where(stash["masks"][l], d, 0.0), d)
            dW = d_act.T @ stash["xs"][l]
            db = d_act.sum(axis=0, keepdims=True)
            d_prev = d_act @ W[l]
            d = jnp.where(valid[l] > 0, d_prev, d_in)
            return (d, jnp.where(valid[l] > 0, dW, 0.0),
                    jnp.where(valid[l] > 0, db, 0.0))

        def stage_bwd(W, valid, relu_f, dout, stash, is_last, target):
            """One stage's padded backward; returns (dx, dW, db)."""
            d = head_grad(stash["probs"], target, dout, is_last)
            dWs, dbs = [], []
            for l in range(L - 1, -1, -1):
                d, dW, db = bwd_layer(W, valid, relu_f, stash, l, d)
                dWs.append(dW)
                dbs.append(db)
            dWs.reverse()
            dbs.reverse()
            return d, jnp.stack(dWs), jnp.stack(dbs)

        # Comm/compute interleaving (parallel/overlap.py). Two opt-in
        # pieces share the `overlap` config:
        # - double-buffered p2p hops (stride 2): each tick permutes the
        #   PREVIOUS tick's output while computing the current one, so
        #   the hop leaves the per-tick critical path (single-buffer
        #   ticks serialize compute -> ppermute -> next compute). Costs
        #   pp-1 extra warmup/drain ticks: microbatch m sits at stage s
        #   at tick stride*s + m.
        # - bucketed dp reduction: the final backward tick is peeled
        #   out of the scan and its layer loop emits each grad bucket's
        #   psum the moment the bucket's leaves are final — interleaved
        #   with the remaining backward instead of one exposed bulk
        #   reduction after the scan.
        ov = self.overlap
        stride = 2 if (ov is not None and ov.double_buffer_hops) else 1
        if ov is not None:
            from shallowspeed_tpu.parallel import overlap as OVM

            order = []
            for l in range(L - 1, -1, -1):  # backward-finalization order
                order.append((2 * l, jax.ShapeDtypeStruct(
                    (self.wmax, self.wmax), jnp.float32)))
                order.append((2 * l + 1, jax.ShapeDtypeStruct(
                    (1, self.wmax), jnp.float32)))
            raw = OVM.plan_buckets([x for _, x in order],
                                   ov.bucket_bytes)
            ov_plan = [[order[j][0] for j in bk] for bk in raw]
            by_id = dict(order)
            self._bucket_sigs = [
                OVM.bucket_signature([by_id[i] for i in bk])
                for bk in ov_plan]
        else:
            ov_plan = None
            self._bucket_sigs = []

        fwd_ticks = n_mu + stride * (pp - 1)
        bwd_ticks = n_mu + stride * (pp - 1)

        def local_step(params, opt_state, xs, ys):
            """Per-device GPipe batch step.
            Blocks: params W (1, L, wmax, wmax); xs (1, n_mu, mubs, wmax)
            width-padded (stage 0 consumes); ys (1, n_mu, mubs, out_dim)
            compact (the last stage pads on the fly)."""
            W = params["W"][0]
            b = params["b"][0]
            s = jax.lax.axis_index("pp")
            is_first = s == 0
            is_last = s == pp - 1
            valid = valid_full[s]
            relu_f = relu_full[s]
            xs, ys = xs[0], ys[0]

            # ---------------- forward phase
            def fwd_compute(cur, stashes, t):
                m = t - stride * s  # microbatch this stage handles at t
                active = (m >= 0) & (m < n_mu)
                mc = jnp.clip(m, 0, n_mu - 1)
                x_own = jax.lax.dynamic_index_in_dim(xs, mc, keepdims=False)
                x_in = jnp.where(is_first, x_own, cur)
                out, stash = stage_fwd(W, b, valid, relu_f, x_in, is_last)

                def upd(buf, new):
                    newb = jax.lax.dynamic_update_index_in_dim(buf, new, mc, 0)
                    return jnp.where(active, newb, buf)

                return out, tree_map(upd, stashes, stash)

            def fwd_tick(carry, t):
                # single-buffer: compute, then hop this tick's output
                # (the next tick's compute waits on the permute)
                cur, stashes = carry
                out, stashes = fwd_compute(cur, stashes, t)
                nxt = jax.lax.ppermute(out, "pp", right)
                return (nxt, stashes), None

            def fwd_tick_db(carry, t):
                # double-buffered: hop the PREVIOUS tick's output while
                # computing this tick's — the ppermute and the matmuls
                # share no dataflow, so the latency-hiding scheduler
                # runs them concurrently (delivery takes two ticks,
                # hence the stride-2 microbatch placement)
                cur, inflight, stashes = carry
                recv = jax.lax.ppermute(inflight, "pp", right)
                out, stashes = fwd_compute(cur, stashes, t)
                return (recv, out, stashes), None

            stash0 = {
                "xs": jnp.zeros((n_mu, L, mubs, wmax)),
                "masks": jnp.zeros((n_mu, L, mubs, wmax), bool),
                "probs": jnp.zeros((n_mu, mubs, wmax)),
            }
            zblk = jnp.zeros((mubs, wmax))
            if stride == 1:
                init = _pvary((zblk, stash0), ("pp", "dp"))
                (cur, stashes), _ = jax.lax.scan(
                    fwd_tick, init, jnp.arange(fwd_ticks))
            else:
                init = _pvary((zblk, zblk, stash0), ("pp", "dp"))
                (cur, _, stashes), _ = jax.lax.scan(
                    fwd_tick_db, init, jnp.arange(fwd_ticks))

            # ---------------- backward phase (reversed microbatch order,
            # GPipe `pipe.py:234-235`; the last stage leads)
            def bwd_mu_stash(t):
                r = t - stride * (pp - 1 - s)  # reversed index at tick t
                m = n_mu - 1 - r
                active = (r >= 0) & (r < n_mu)
                mc = jnp.clip(m, 0, n_mu - 1)
                stash_m = tree_map(
                    lambda buf: jax.lax.dynamic_index_in_dim(
                        buf, mc, keepdims=False), stashes)
                # targets stay compact (out_dim cols) in HBM; pad here on
                # device — padded target entries are zero, matching padded
                # probs, so the head grad on padding is exactly zero.
                y_own = jax.lax.dynamic_index_in_dim(ys, mc, keepdims=False)
                y_own = jnp.pad(y_own, ((0, 0), (0, wmax - y_own.shape[-1])))
                return active, stash_m, y_own

            def bwd_compute(cur, gW, gb, t):
                active, stash_m, y_own = bwd_mu_stash(t)
                dx, dW, db = stage_bwd(W, valid, relu_f, cur, stash_m,
                                       is_last, y_own)
                gW = gW + jnp.where(active, dW, 0.0)
                gb = gb + jnp.where(active, db, 0.0)
                return jnp.where(active, dx, 0.0), gW, gb

            def bwd_tick(carry, t):
                cur, gW, gb = carry
                dx, gW, gb = bwd_compute(cur, gW, gb, t)
                nxt = jax.lax.ppermute(dx, "pp", left)
                return (nxt, gW, gb), None

            def bwd_tick_db(carry, t):
                cur, inflight, gW, gb = carry
                recv = jax.lax.ppermute(inflight, "pp", left)
                dx, gW, gb = bwd_compute(cur, gW, gb, t)
                return (recv, dx, gW, gb), None

            # with a bucket plan the final tick is peeled out of the
            # scan so its layer loop can interleave the dp reduction
            n_scan = bwd_ticks - (1 if ov_plan is not None else 0)
            if stride == 1:
                binit = _pvary((zblk, jnp.zeros_like(W),
                                jnp.zeros_like(b)), ("pp", "dp"))
                (cur, gW, gb), _ = jax.lax.scan(
                    bwd_tick, binit, jnp.arange(n_scan))
            else:
                binit = _pvary((zblk, zblk, jnp.zeros_like(W),
                                jnp.zeros_like(b)), ("pp", "dp"))
                (cur, _, gW, gb), _ = jax.lax.scan(
                    bwd_tick_db, binit, jnp.arange(n_scan))

            if ov_plan is None:
                # bulk oracle: one psum per stacked leaf AFTER the scan
                # — fully exposed (the scan is its only producer), kept
                # as the reduction-order reference (`pipe.py:302-327`)
                grads = {"W": jax.lax.psum(gW, "dp")[None],
                         "b": jax.lax.psum(gb, "dp")[None]}
            else:
                from shallowspeed_tpu.parallel.overlap import (
                    BucketEmitter)

                # peeled final backward tick (only stage 0 is still
                # active — every other stage's grads are already
                # final): replay stage_bwd's layer loop and emit each
                # bucket's psum the moment its layers' totals are
                # final, dataflow-independent of the earlier layers'
                # backward matmuls still being computed.
                t_last = bwd_ticks - 1
                active, stash_m, y_own = bwd_mu_stash(t_last)
                d = head_grad(stash_m["probs"], y_own, cur, is_last)
                em = BucketEmitter(ov_plan, ("dp",))
                for l in range(L - 1, -1, -1):
                    d, dW_l, db_l = bwd_layer(W, valid, relu_f,
                                              stash_m, l, d)
                    em.add(2 * l, gW[l] + jnp.where(active, dW_l, 0.0))
                    em.add(2 * l + 1,
                           gb[l] + jnp.where(active, db_l, 0.0))
                red = em.done()
                grads = {
                    "W": jnp.stack([red[2 * l] for l in range(L)])[None],
                    "b": jnp.stack([red[2 * l + 1]
                                    for l in range(L)])[None]}
            if health_mode == "off":
                return opt.step(params, grads, opt_state)
            # health pack fused into the step (telemetry/health.py):
            # params/grads are pp-sharded stage stacks, so each leaf's
            # statistic psums over 'pp' to span every stage in-program;
            # under "guard" the update gates on the (pp-global)
            # nonfinite sentinel — all stages skip in lockstep,
            # bit-identically (optim.guarded_step).
            from shallowspeed_tpu.telemetry.health import (grad_health,
                                                           update_health)

            pax = [("pp",), ("pp",)]  # {'W','b'} stacks, P('pp') each
            pack = grad_health(params, grads, grad_axes=pax,
                               param_axes=pax)
            if health_mode == "guard":
                ok = pack["nonfinite"] == 0
                new_p, new_s = opt.guarded_step(params, grads,
                                                opt_state, ok)
                pack = update_health(pack, params, new_p,
                                     param_axes=pax, skipped=1 - ok)
            else:
                new_p, new_s = opt.step(params, grads, opt_state)
                pack = update_health(pack, params, new_p,
                                     param_axes=pax)
            return new_p, new_s, pack

        health_mode = self.health
        p_specs = {"W": P("pp"), "b": P("pp")}
        step_out = ((p_specs, self._opt_specs) if health_mode == "off"
                    else (p_specs, self._opt_specs, P()))

        @partial(jax.jit, donate_argnums=(0, 1))
        @partial(shard_map, mesh=mesh,
                 in_specs=(p_specs, self._opt_specs, P("dp"), P("dp")),
                 out_specs=step_out)
        def _step(params, opt_state, xs, ys):
            return local_step(params, opt_state, xs, ys)

        @partial(jax.jit, donate_argnums=(0, 1))
        @partial(shard_map, mesh=mesh,
                 in_specs=(p_specs, self._opt_specs, P(None, "dp"),
                           P(None, "dp")),
                 out_specs=(p_specs, self._opt_specs))
        def _epoch(params, opt_state, xs, ys):
            def body(carry, xy):
                p, o = carry
                x, y = xy
                out = local_step(p, o, x, y)
                # the fused-epoch path never carries the health pack
                # (drivers step per-batch when health is on)
                return out[:2], None

            (params, opt_state), _ = jax.lax.scan(
                body, (params, opt_state), (xs, ys))
            return params, opt_state

        @partial(jax.jit)
        @partial(shard_map, mesh=mesh, in_specs=(p_specs, P("dp")),
                 out_specs=P("dp"))
        def _infer(params, x):
            # Each stage applies its slice every tick; after pp compute+shift
            # rounds the block that started at stage 0 has traversed
            # f_{pp-1} ∘ ... ∘ f_0 and wrapped around to stage 0. A psum-mask
            # then makes the result pp-invariant.
            W = params["W"][0]
            b = params["b"][0]
            s = jax.lax.axis_index("pp")
            is_last = s == pp - 1
            valid = valid_full[s]
            relu_f = relu_full[s]

            def tick(h, _):
                out, _stash = stage_fwd(W, b, valid, relu_f, h, is_last)
                return jax.lax.ppermute(out, "pp", right), None

            h0 = _pvary(x, ("pp",))
            h, _ = jax.lax.scan(tick, h0, None, length=pp)
            return jax.lax.psum(jnp.where(s == 0, h, 0.0), "pp")

        self._step_fn = _step
        self._epoch_fn = _epoch
        self._infer_fn = _infer
        if ov is not None:
            from shallowspeed_tpu.parallel import overlap as OVM

            for fn in (_step, _epoch):
                OVM.register_program(fn, "dp", self._bucket_sigs,
                                     engine="SPMDPipelineEngine")

    # ------------------------------------------------------------- data

    def _pad_batch(self, arr):
        out = np.zeros(arr.shape[:-1] + (self.wmax,), np.float32)
        out[..., : arr.shape[-1]] = arr
        return out

    def stage_batch(self, datasets, batch_id):
        """(dp, n_mu, mubs, *) stacks sharded over 'dp' (axis 0), replicated
        over 'pp'. Inputs are width-padded; targets stay compact."""
        stacks = [ds.load_mubatch_stack(batch_id) for ds in datasets]
        xs = np.stack([s[0] for s in stacks])
        ys = np.stack([s[1] for s in stacks])
        shard = NamedSharding(self.mesh, P("dp"))
        return (jax.device_put(self._pad_batch(xs), shard),
                jax.device_put(ys, shard))

    def train_batch(self, batch_id, datasets):
        from shallowspeed_tpu.telemetry import tracer

        xs, ys = self.stage_batch(datasets, batch_id)
        with tracer().span("step", batch=batch_id,
                           schedule="gpipe") as sp:
            if self._telemetry_eps is None and tracer().level != "off":
                self._record_entrypoints(xs, ys)
            out = self._step_fn(self.params, self.opt_state, xs, ys)
            self.params, self.opt_state = out[0], out[1]
            if self.health != "off":
                _note_step(self, out[2])
            sp.fence(self.params["b"])

    def stage_epoch(self, datasets, n_batches=None):
        from shallowspeed_tpu.data.dataset import stack_epoch

        xs, ys = stack_epoch(datasets, n_batches)
        shard = NamedSharding(self.mesh, P(None, "dp"))
        return (jax.device_put(self._pad_batch(xs), shard),
                jax.device_put(ys, shard))

    def train_epoch(self, staged):
        from shallowspeed_tpu.telemetry import tracer

        xs, ys = staged
        with tracer().span("epoch") as sp:
            self.params, self.opt_state = self._epoch_fn(
                self.params, self.opt_state, xs, ys)
            sp.fence(self.params["b"])

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

    def schedule_info(self) -> dict:
        """Executed-schedule identity for bubble accounting: this
        engine IS the compiled GPipe tick program. With double-buffered
        hops the stage spacing is 2 ticks (microbatch m sits at stage s
        at tick 2s+m), trading pp-1 extra warmup/drain ticks for hops
        off the per-tick critical path."""
        db = bool(self.overlap is not None
                  and self.overlap.double_buffer_hops)
        return {"schedule": "gpipe", "n_mu": self.n_mu, "pp": self.pp,
                "vpp": 1, "hop_double_buffer": db}

    def health_snapshot(self) -> dict | None:
        """The last train_batch's health pack as a host dict (one
        device_get); None before the first step or with health='off'.
        The fused train_epoch path does not carry the pack."""
        from shallowspeed_tpu.telemetry.health import engine_snapshot

        return engine_snapshot(self)

    def infer(self, x: np.ndarray) -> jax.Array:
        """Forward a (rows, in_dim) batch; returns (rows, out_dim) probs."""
        xp = self._pad_batch(x.reshape(x.shape[0], -1))
        xd = jax.device_put(xp, NamedSharding(self.mesh, P("dp")))
        out = self._infer_fn(self.params, xd)
        return out[:, : self.out_dim]

    # ------------------------------------------------------------- misc

    @property
    def unstacked_params(self):
        return self.stack.unstack_params(jax.device_get(self.params))

    # -------------------------------------------------- checkpoint interface

    def get_canonical_params(self):
        return [layer for stage_p in self.unstacked_params
                for layer in stage_p]

    def _stack_layers(self, layers) -> dict:
        """Re-pad a canonical flat layer list into the stage-stacked
        {'W','b'} layout (host-side) — shared by params restore and the
        canonical optimizer-moment import."""
        st = self.stack
        W = np.zeros((st.pp, st.L, st.wmax, st.wmax), np.float32)
        b = np.zeros((st.pp, st.L, 1, st.wmax), np.float32)
        i = 0
        for s in range(st.pp):
            for l in range(st.n_linears[s]):
                layer = layers[i]
                W[s, l] = _pad_to(np.asarray(layer["W"]), (st.wmax, st.wmax))
                b[s, l] = _pad_to(np.asarray(layer["b"]), (1, st.wmax))
                i += 1
        assert i == len(layers), (i, len(layers))
        return {"W": W, "b": b}

    def set_canonical_params(self, layers):
        self.params = jax.device_put(self._stack_layers(layers),
                                     self.p_shard)

    def canon_export_tree(self, tree):
        """Params-shaped tree (e.g. Adam moments, stacked+padded) ->
        canonical flat layer list; the padding is zeros-in, zeros-out, so
        unpadded moments round-trip exactly."""
        return [layer
                for stage in self.stack.unstack_params(jax.device_get(tree))
                for layer in stage]

    def canon_import_tree(self, tree):
        """Inverse of `canon_export_tree` (host-side; `set_opt_state`
        applies the sharding specs)."""
        return self._stack_layers(tree)

    def set_opt_state(self, state):
        self.opt_state = jax.device_put(
            state,
            tree_map(lambda s: NamedSharding(self.mesh, s), self._opt_specs))
