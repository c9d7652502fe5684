"""Context/sequence parallelism — long-context training over a (dp, sp) mesh.

The reference cannot scale sequence length at all (SURVEY §5: no sequence
dimension anywhere). This engine makes long context a first-class axis the
TPU way: shard the *sequence* over the `sp` mesh axis, run `ring_attention`
(K/V blocks rotating over ICI via `ppermute`,
`shallowspeed_tpu/ops/attention.py`) so no device ever materializes the full
(T, T) score matrix or the full sequence's activations, and compose with
batch sharding over `dp` in the same `shard_map`:

- tokens/targets: (B, T) sharded (dp, sp) — each device holds a
  (B/dp, T/sp) tile.
- params: replicated; every device computes the gradient contribution of its
  tile and one `pmean` over ('dp', 'sp') recovers the exact global-mean
  gradient (all tiles are equal-sized, so mean-of-means is exact — the same
  scaling invariant the MLP family inherits from the reference,
  `functional.py:43-44`).
- autograd: `jax.grad` straight through the ring collective (JAX
  differentiates `ppermute`), so the backward pass runs the ring in reverse
  automatically.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


from shallowspeed_tpu.models import transformer as T
from shallowspeed_tpu.ops.attention import (attention, ring_attention,
                                            ulysses_attention)
from shallowspeed_tpu.telemetry.trace import spanned, tracer
from shallowspeed_tpu.utils import pvary_over

tree_map = jax.tree_util.tree_map


def _note_step(engine, pack):
    # health.note_step, imported lazily (telemetry stays off the module
    # import path): stores last_health + device-side cumulative counters
    from shallowspeed_tpu.telemetry.health import note_step

    note_step(engine, pack)



class ContextParallelEngine:
    """Data x sequence parallel trainer for the transformer LM family.

    `attn` selects the attention substrate:
    - "ring" (default): `ring_attention` over the 'sp' axis — correct for
      any sp, O(T_local) memory, n ppermute hops.
    - "ulysses": `ulysses_attention` — all-to-all head<->sequence
      re-sharding around one fused full-attention program; needs
      n_heads % sp == 0.
    - "ulysses-flash": same all-to-all re-sharding, but the local
      attention is the fused Pallas flash kernel — sequence parallelism
      AND the flash kernel's O(T) memory / fused softmax in one path.
    - "flash": the fused Pallas flash kernel
      (`ops/flash_attention.py`) — sp must be 1 (sequence unsharded);
      fastest single-device path on TPU.
    """

    # params (hence params-shaped moments) are already in the canonical
    # checkpoint layout; placement is not structure (checkpoint.py)
    canonical_opt_identity = True

    @spanned("build", engine="ContextParallelEngine")
    def __init__(self, cfg: T.TransformerConfig, optimizer, mesh: Mesh,
                 seed: int = 0, attn: str = "ring", zero1: bool = False,
                 zero2: bool = False, accum: int = 1,
                 health: str = "off", overlap=None):
        from shallowspeed_tpu.telemetry.health import MODES

        assert mesh.axis_names == ("dp", "sp")
        assert not (zero1 and zero2), "zero2 subsumes zero1"
        assert accum >= 1, accum
        assert health in MODES, health
        self.health = health
        self.last_health = None
        self.overlap = overlap  # parallel.overlap.OverlapConfig | None
        self.accum = accum
        self.cfg = cfg = cfg.trainable
        self.mesh = mesh
        self.dp, self.sp = mesh.devices.shape
        self.optimizer = optimizer
        self._step_count = 0
        self.rep = NamedSharding(mesh, P())
        self.tile = NamedSharding(mesh, P("dp", "sp"))

        with tracer().span("build.init"):
            host_params = T.init(cfg, seed)
        with tracer().span("build.place"):
            self.params = jax.device_put(host_params, self.rep)
            del host_params
            self.opt_state = jax.device_put(optimizer.init(self.params),
                                            self.rep)

        opt = optimizer
        # Sliding windows compose with EVERY substrate: all of them take
        # `window=` with identical semantics (`ops/attention.py` masks,
        # the flash kernel skips out-of-window tiles outright).
        w = cfg.window
        if cfg.attn_dropout > 0.0:
            # probability dropout lives on the plain substrate only; at
            # sp=1 the ring degenerates to it, so swap transparently
            assert self.sp == 1 and attn == "ring", (
                "cfg.attn_dropout needs the plain XLA attention "
                "substrate (sp=1, --attn ring); fused/resharded "
                "substrates cannot mask probabilities")
            attn = partial(attention, causal=True, window=w)
        elif attn == "flash":
            from shallowspeed_tpu.ops.flash_attention import flash_attention

            assert self.sp == 1, "--attn flash requires sp=1 (use ring)"
            attn = partial(flash_attention, causal=True, window=w)
        elif attn in ("ulysses", "ulysses-flash"):
            assert cfg.n_heads % self.sp == 0, (
                f"--attn {attn} needs n_heads ({cfg.n_heads}) divisible by "
                f"sp ({self.sp}); use ring")
            assert cfg.kv_heads % self.sp == 0, (
                f"--attn {attn} with GQA needs n_kv_heads "
                f"({cfg.kv_heads}) divisible by sp ({self.sp}); use ring")
            attn = partial(ulysses_attention, axis_name="sp", causal=True,
                           window=w, use_flash=attn == "ulysses-flash")
        elif attn == "ring-flash":
            from shallowspeed_tpu.ops.flash_attention import (
                ring_flash_attention)

            # the fused kernel as the ring's local compute: no
            # (T_local, T_local) score matrix, no head-divisibility
            # constraint — works for ANY sp (unlike ulysses)
            attn = partial(ring_flash_attention, axis_name="sp",
                           causal=True, window=w)
        else:
            attn = partial(ring_attention, axis_name="sp", causal=True,
                           window=w)

        sp = self.sp

        def local_loss(params, tokens, targets, key=None, train=True):
            t_local = tokens.shape[1]
            off = jax.lax.axis_index("sp") * t_local
            if key is not None:
                # decorrelate masks across tiles: each (dp, sp) position
                # folds its mesh coordinates into the per-step key
                key = jax.random.fold_in(
                    key, jax.lax.axis_index("dp") * sp
                    + jax.lax.axis_index("sp"))
            return T.loss(params, tokens, targets, cfg,
                          attn_fn=attn, pos_offset=off, dropout_key=key,
                          train=train)

        def train_key(step):
            if cfg.dropout == 0.0 and cfg.attn_dropout == 0.0:
                return None
            return jax.random.fold_in(jax.random.PRNGKey(seed), step)

        n_tiles = self.dp * self.sp
        accum = self.accum

        def mu_split(tokens, targets):
            """(b, t) local tile -> (accum, b/accum, t) microbatch
            stacks."""
            b, t = tokens.shape
            assert b % accum == 0, (
                f"--accum {accum} must divide the per-device batch rows "
                f"({b} here = batch / dp; sp shards the sequence dim, "
                f"not rows)")
            return (tokens.reshape(accum, b // accum, t),
                    targets.reshape(accum, b // accum, t))

        def partial_grad_sum(params_v, tok_r, tgt_r, key):
            """Gradient accumulation: scan the given microbatch stack
            of the local tile, each microbatch doing its own forward
            AND backward (the standard JAX pattern — no cross-iteration
            residuals, so activation memory is one microbatch's worth
            regardless of accum). `params_v` must be pvaried so
            per-microbatch cotangents stay UNREDUCED per-tile partials;
            the caller places the cross-tile reduction after the scan
            (or folds the returned sum into the peeled last
            microbatch's in-backward bucket reduction — the overlapped
            path). Returns (loss sum over microbatches, grad sum)."""

            def body(carry, xs):
                mu, tok_mu, tgt_mu = xs
                k_mu = (None if key is None
                        else jax.random.fold_in(key, mu))
                l, g = jax.value_and_grad(
                    lambda p: local_loss(p, tok_mu, tgt_mu, k_mu))(
                        params_v)
                return (carry[0] + l,
                        tree_map(jnp.add, carry[1], g)), None

            init = pvary_over(
                (jnp.float32(0.0),
                 tree_map(lambda l: jnp.zeros_like(l, jnp.float32),
                          params_v)),
                ("dp", "sp"))
            (loss_sum, gsum), _ = jax.lax.scan(
                body, init, (jnp.arange(tok_r.shape[0]), tok_r, tgt_r))
            return loss_sum, gsum

        def tile_loss_and_gsum(params_v, tokens, targets, key):
            """(pmean'd global loss, UNREDUCED per-tile gradient sum,
            scale to apply after the cross-tile reduction) — the single
            encoding of the loss/grad scaling, shared by the dense,
            ZeRO-1, and ZeRO-2 gradient programs; each places its own
            reduction (psum vs psum_scatter) on the returned sum. The
            global-mean gradient falls out because every tile and every
            microbatch is equal-sized (mean of means is exact — the
            reference's own scaling invariant, `functional.py:43-44`;
            its interleaved Iallreduce, `pipe.py:302-327`, is here a
            single compiled reduction)."""
            if accum == 1:
                lloc, gsum = jax.value_and_grad(
                    lambda p: local_loss(p, tokens, targets, key))(
                        params_v)
                return (jax.lax.pmean(lloc, ("dp", "sp")), gsum,
                        1.0 / n_tiles)
            tok_r, tgt_r = mu_split(tokens, targets)
            loss_sum, gsum = partial_grad_sum(params_v, tok_r, tgt_r,
                                              key)
            return (jax.lax.pmean(loss_sum / accum, ("dp", "sp")), gsum,
                    1.0 / (n_tiles * accum))

        def loss_and_grads(params, tokens, targets, step):
            key = train_key(step)
            loss, gsum, scale = tile_loss_and_gsum(
                pvary_over(params, ("dp", "sp")), tokens, targets, key)
            grads = tree_map(
                lambda g: jax.lax.psum(g, ("dp", "sp")) * scale, gsum)
            return loss, grads

        # ---- overlapped gradient programs (parallel/overlap.py): the
        # cross-tile reduction moves INSIDE the backward, one bucket at
        # a time. With accum > 1 the last microbatch is peeled out of
        # the accumulation scan (a scan is one dataflow node — every
        # reduction after it is exposed) and the earlier microbatches'
        # unreduced sum is folded into each bucket's psum, so wire
        # bytes match the bulk path exactly.
        if overlap is not None:
            from shallowspeed_tpu.parallel import overlap as OV

            ov_plan, _p_leaves, _ = OV.plan_param_buckets(
                self.params, overlap.bucket_bytes)
            self._bucket_sigs = [
                OV.bucket_signature([_p_leaves[i] for i in bk])
                for bk in ov_plan]

            def tagged_loss_and_gsum(params, tokens, targets, key,
                                     tag, tag_in=lambda p: p):
                """tile_loss_and_gsum with the reduction tags applied
                to the (peeled) last microbatch's params: returns
                (pmean'd loss, REDUCED grad sum, scale). `params`
                arrive invariant; `tag_in` types them as the tag's
                primal must be (the tag's cotangent has that type)."""
                if accum == 1:
                    lloc, gsum = jax.value_and_grad(
                        lambda p: local_loss(tag(p, None), tokens,
                                             targets, key))(
                                                 tag_in(params))
                    return (jax.lax.pmean(lloc, ("dp", "sp")), gsum,
                            1.0 / n_tiles)
                tok_r, tgt_r = mu_split(tokens, targets)
                loss_head, acc = partial_grad_sum(
                    pvary_over(params, ("dp", "sp")), tok_r[:-1],
                    tgt_r[:-1], key)
                k_last = (None if key is None
                          else jax.random.fold_in(key, accum - 1))
                l_last, gsum = jax.value_and_grad(
                    lambda p: local_loss(tag(p, acc), tok_r[-1],
                                         tgt_r[-1], k_last))(
                                             tag_in(params))
                return (jax.lax.pmean((loss_head + l_last) / accum,
                                      ("dp", "sp")),
                        gsum, 1.0 / (n_tiles * accum))

            def loss_and_grads_ov(params, tokens, targets, step):
                def tag(p, acc):
                    return OV.reduce_grads_on_backward(
                        p, ("dp", "sp"), ov_plan, acc=acc)

                loss, gsum, scale = tagged_loss_and_gsum(
                    params, tokens, targets, train_key(step), tag)
                return loss, tree_map(lambda g: g * scale, gsum)

            lag = loss_and_grads_ov
        else:
            ov_plan = None
            self._bucket_sigs = []
            lag = loss_and_grads

        health_mode = health

        def maybe_pack(params, grads, grad_specs=None):
            """The health pack for this engine's fully reduced grads:
            replicated leaves need no psum; ZeRO-2's dp-scattered
            leaves psum their statistics over the axes their spec
            shards (health.spec_axes). None with health='off'."""
            if health_mode == "off":
                return None
            from shallowspeed_tpu.telemetry.health import (grad_health,
                                                           spec_axes)

            gax = spec_axes(grad_specs) if grad_specs is not None \
                else None
            return grad_health(params, grads, grad_axes=gax)

        if zero2:
            from shallowspeed_tpu.parallel.zero import (
                make_zero1_update, shard_state_zero1, zero2_grad_specs)

            # one reduce-scatter per leaf instead of an all-reduce: grads
            # leave the program dp-SHARDED (1/dp per device), aligned
            # leaf-for-leaf with the ZeRO-1-placed moments, so the
            # optimizer update below runs fully local. The scatter dim is
            # read off the spec itself — one encoding of the placement
            # rule, no chance of divergence.
            gspecs = zero2_grad_specs(self.params, mesh)
            gdims = [next((i for i, ax in enumerate(sp) if ax == "dp"),
                          None)
                     for sp in jax.tree_util.tree_leaves(
                         gspecs, is_leaf=lambda x: isinstance(x, P))]

            z2_out = ((P(), gspecs) if health == "off"
                      else (P(), gspecs, P()))

            @jax.jit
            @partial(shard_map, mesh=mesh,
                     in_specs=(P(), P("dp", "sp"), P("dp", "sp"), P()),
                     out_specs=z2_out)
            def _loss_grads(params, tokens, targets, step):
                # pvary the params: cotangents then arrive as per-tile
                # PARTIALS (no auto-psum), and the reduction is ours to
                # place — psum_scatter over 'dp'
                key = train_key(step)
                if ov_plan is not None:
                    # overlapped: the scatter tags emit each leaf's
                    # psum_scatter INSIDE the backward (embedded at the
                    # local shard slot — sliced back out below), with
                    # the peeled-scan accumulator folded in; same wire
                    # bytes, reduction interleaved with the backward
                    from shallowspeed_tpu.parallel.overlap import (
                        scatter_grads_on_backward, scatter_tag_input,
                        take_local_shard)

                    def tag(p, acc):
                        return scatter_grads_on_backward(
                            p, "dp", gdims, ov_plan, acc=acc,
                            extra_axes=("sp",))

                    loss, grads, gscale = tagged_loss_and_gsum(
                        params, tokens, targets, key, tag,
                        partial(scatter_tag_input, axis="dp",
                                dims=gdims))
                    leaves, tdef = jax.tree_util.tree_flatten(grads)
                    grads = jax.tree_util.tree_unflatten(tdef, [
                        take_local_shard(g, dim, "dp") * gscale
                        for g, dim in zip(leaves, gdims)])
                else:
                    loss, grads, gscale = tile_loss_and_gsum(
                        pvary_over(params, ("dp", "sp")), tokens,
                        targets, key)
                    leaves, tdef = jax.tree_util.tree_flatten(grads)
                    out = []
                    for g, dim in zip(leaves, gdims):
                        # unconditionally: even at sp=1 the pvaried
                        # grads are TYPED sp-varying and need the
                        # (free) psum to retype
                        g = jax.lax.psum(g, "sp")
                        if dim is None:
                            g = jax.lax.psum(g, "dp")
                        else:
                            g = jax.lax.psum_scatter(
                                g, "dp", scatter_dimension=dim,
                                tiled=True)
                        out.append(g * gscale)
                    grads = jax.tree_util.tree_unflatten(tdef, out)
                if health_mode == "off":
                    return loss, grads
                return loss, grads, maybe_pack(params, grads, gspecs)

            self.opt_state = shard_state_zero1(self.opt_state, mesh)
            self._loss_grads_fn = _loss_grads
            self._update_fn = make_zero1_update(
                opt, self.params, self.opt_state, health=health)
            self._step_fn = None
            self._run_fn = None
        elif zero1:
            from shallowspeed_tpu.parallel.zero import (
                make_zero1_update, shard_state_zero1)

            z1_out = ((P(), P()) if health == "off" else (P(), P(), P()))

            @jax.jit
            @partial(shard_map, mesh=mesh,
                     in_specs=(P(), P("dp", "sp"), P("dp", "sp"), P()),
                     out_specs=z1_out)
            def _loss_grads(params, tokens, targets, step):
                # ZeRO-1 grad program: the grads leave the shard_map
                # already psum'd (invariant), ready for the dp-sharded
                # optimizer update (`lag`: bulk psums after the
                # accumulation, or in-backward bucket psums with
                # `overlap` — same contract either way).
                loss, grads = lag(params, tokens, targets, step)
                if health_mode == "off":
                    return loss, grads
                return loss, grads, maybe_pack(params, grads)

            self.opt_state = shard_state_zero1(self.opt_state, mesh)
            self._loss_grads_fn = _loss_grads
            self._update_fn = make_zero1_update(
                opt, self.params, self.opt_state, health=health)
            self._step_fn = None
            self._run_fn = None
        else:
            step_out = ((P(), P(), P()) if health == "off"
                        else (P(), P(), P(), P()))

            @partial(jax.jit, donate_argnums=(0, 1))
            @partial(shard_map, mesh=mesh,
                     in_specs=(P(), P(), P("dp", "sp"), P("dp", "sp"),
                               P()),
                     out_specs=step_out)
            def _step(params, opt_state, tokens, targets, step):
                loss, grads = lag(params, tokens, targets, step)
                if health_mode == "off":
                    params, opt_state = opt.step(params, grads,
                                                 opt_state)
                    return params, opt_state, loss
                from shallowspeed_tpu.telemetry.health import (
                    update_health)

                pack = maybe_pack(params, grads)
                if health_mode == "guard":
                    ok = pack["nonfinite"] == 0
                    new_p, new_s = opt.guarded_step(params, grads,
                                                    opt_state, ok)
                    pack = update_health(pack, params, new_p,
                                         skipped=1 - ok)
                else:
                    new_p, new_s = opt.step(params, grads, opt_state)
                    pack = update_health(pack, params, new_p)
                return new_p, new_s, loss, pack

            self._step_fn = _step

            # Run fusion: a whole multi-step run as ONE XLA dispatch
            # (`lax.scan` over optimizer steps, batches HBM-resident) —
            # the transformer-family counterpart of the MLP engine's
            # `train_run` (engine.py): steady-state throughput with
            # per-dispatch host latency out of the step timing.
            @partial(jax.jit, donate_argnums=(0, 1))
            @partial(shard_map, mesh=mesh,
                     in_specs=(P(), P(), P(None, "dp", "sp"),
                               P(None, "dp", "sp"), P()),
                     out_specs=(P(), P(), P()))
            def _run(params, opt_state, toks, tgts, step0):
                def body(carry, xs):
                    params, opt_state, step = carry
                    tok, tgt = xs
                    loss, grads = lag(params, tok, tgt, step)
                    params, opt_state = opt.step(params, grads, opt_state)
                    return (params, opt_state, step + 1), loss

                (params, opt_state, _), losses = jax.lax.scan(
                    body, (params, opt_state, step0), (toks, tgts))
                return params, opt_state, losses

            self._run_fn = _run

        if overlap is not None:
            from shallowspeed_tpu.parallel import overlap as OV

            if zero2:
                # the dp-axis binds here are per-leaf scatters/psums
                # (the bucket-grouped psums run over 'sp' only)
                self._bucket_sigs = [
                    OV.bucket_signature([l])
                    for l in jax.tree_util.tree_leaves(self.params)]
            fns = ([self._loss_grads_fn] if self._step_fn is None
                   else [self._step_fn, self._run_fn])
            for fn in fns:
                OV.register_program(fn, "dp", self._bucket_sigs,
                                    engine="ContextParallelEngine")

        @jax.jit
        @partial(shard_map, mesh=mesh,
                 in_specs=(P(), P("dp", "sp"), P("dp", "sp")),
                 out_specs=P())
        def _eval(params, tokens, targets):
            return jax.lax.pmean(
                local_loss(params, tokens, targets, train=False),
                ("dp", "sp"))

        @jax.jit
        @partial(shard_map, mesh=mesh,
                 in_specs=(P(), P("dp", "sp")), out_specs=P("dp", "sp"))
        def _logits(params, tokens):
            t_local = tokens.shape[1]
            off = jax.lax.axis_index("sp") * t_local
            return T.forward(params, tokens, cfg, attn_fn=attn,
                             pos_offset=off)

        if cfg.n_experts > 0:
            @jax.jit
            @partial(shard_map, mesh=mesh,
                     in_specs=(P(), P("dp", "sp")), out_specs=P())
            def _router_stats(params, tokens):
                t_local = tokens.shape[1]
                off = jax.lax.axis_index("sp") * t_local
                _, _aux, st = T.forward_with_aux(
                    params, tokens, cfg, attn_fn=attn, pos_offset=off,
                    with_stats=True)
                # equal-sized tiles: pmean is the exact global average
                return tree_map(lambda v: jax.lax.pmean(v, ("dp", "sp")),
                                st)

            self._router_stats_fn = _router_stats
        else:
            self._router_stats_fn = None

        self._eval_fn = _eval
        self._logits_fn = _logits

    # -------------------------------------------------------------- data

    def _place(self, arr: np.ndarray):
        # multi-host: arr is this process's local rows (place_global
        # stitches the global array); single-process: the global batch
        from shallowspeed_tpu.distributed import place_global

        b, t = arr.shape[:2]
        # local rows x processes = global batch; it must divide over dp
        assert (b * jax.process_count()) % self.dp == 0, (b, self.dp)
        assert t % self.sp == 0, (t, self.sp)
        assert t <= self.cfg.max_seq, (
            f"global sequence length {t} exceeds max_seq={self.cfg.max_seq}")
        return place_global(arr, self.tile)

    # -------------------------------------------------------------- steps

    def place(self, arr) -> jax.Array:
        """Public placement hook for prefetch pipelines."""
        return self._place(arr)

    def train_batch_async(self, tokens, targets) -> jax.Array:
        """One optimizer step; loss as a lazy device scalar (no host sync —
        `float()` it only at log points; see `data/prefetch.py`)."""
        step = np.uint32(self._step_count)
        self._step_count += 1
        monitored = self.health != "off"
        with tracer().span("step", step=int(step)) as sp:
            if self._step_fn is None:  # ZeRO-1/2: grads + sharded update
                with tracer().span("grads", step=int(step)) as g:
                    out = self._loss_grads_fn(
                        self.params, self._place(tokens),
                        self._place(targets), step)
                    loss, grads = out[0], out[1]
                    g.fence(loss)
                with tracer().span("update", step=int(step)) as u:
                    if self._telemetry_eps is None \
                            and tracer().level != "off":
                        self._record_entrypoints(tokens, targets,
                                                 grads=grads)
                    if self.health == "guard":
                        self.params, self.opt_state, upd = \
                            self._update_fn(self.params, grads,
                                            self.opt_state,
                                            out[2]["nonfinite"] == 0)
                        _note_step(self, {**out[2], **upd})
                    elif monitored:
                        self.params, self.opt_state, upd = \
                            self._update_fn(self.params, grads,
                                            self.opt_state)
                        _note_step(self, {**out[2], **upd})
                    else:
                        self.params, self.opt_state = self._update_fn(
                            self.params, grads, self.opt_state)
                    u.fence(self.opt_state)
            else:
                with tracer().span("place"):
                    tokens_d = self._place(tokens)
                    targets_d = self._place(targets)
                with tracer().span("dispatch"):
                    out = self._step_fn(self.params, self.opt_state,
                                        tokens_d, targets_d, step)
                self.params, self.opt_state, loss = out[:3]
                if monitored:
                    _note_step(self, out[3])
                if self._telemetry_eps is None \
                        and tracer().level != "off":
                    self._record_entrypoints(tokens, targets)
            sp.fence(loss)
        return loss

    # ----------------------------------------------- telemetry surface

    _telemetry_eps = None

    def _record_entrypoints(self, tokens, targets, grads=None):
        """One-time (first traced step) skeleton capture for
        telemetry's static accounting (report.py resolves the
        conventional entrypoint attributes)."""
        from shallowspeed_tpu.telemetry.report import (
            record_engine_entrypoints)

        self._telemetry_eps = record_engine_entrypoints(
            self, tokens, targets, grads=grads)

    def telemetry_entrypoints(self) -> list:
        """(name, fn, SDS args) per compiled entrypoint, step first
        (report.py convention); empty before the first traced step."""
        return list(self._telemetry_eps or ())

    def health_snapshot(self) -> dict | None:
        """The last step's health pack as a plain host dict (one
        device_get — call at log points); None before the first step
        or with health='off'."""
        from shallowspeed_tpu.telemetry.health import engine_snapshot

        return engine_snapshot(self)

    def train_batch(self, tokens: np.ndarray, targets: np.ndarray) -> float:
        """One optimizer step on a (B, T) int token batch; returns the loss."""
        return float(self.train_batch_async(tokens, targets))

    def train_run(self, tokens: np.ndarray, targets: np.ndarray):
        """S optimizer steps as ONE compiled dispatch. tokens/targets:
        (S, B, T) int arrays, staged HBM-resident up front; returns the
        (S,) per-step losses as a lazy device array. Dense engine only
        (ZeRO-1/2 interleave a host-side sharded update per step)."""
        assert self._run_fn is not None, (
            "train_run needs the dense engine (zero1/zero2 step on the "
            "host between grad programs)")
        assert self.health == "off", (
            "train_run fuses many steps into one dispatch; the per-step "
            "health pack (and the guard) lives in the train_batch path "
            "— build the engine with health='off' for fused runs")
        s, b, t = tokens.shape
        assert t % self.sp == 0 and t <= self.cfg.max_seq, (t, self.sp)
        assert (b * jax.process_count()) % self.dp == 0, (b, self.dp)
        sharding = NamedSharding(self.mesh, P(None, "dp", "sp"))
        toks = jax.device_put(np.asarray(tokens), sharding)
        tgts = jax.device_put(np.asarray(targets), sharding)
        step0 = np.uint32(self._step_count)
        self._step_count += s
        self.params, self.opt_state, losses = self._run_fn(
            self.params, self.opt_state, toks, tgts, step0)
        return losses

    def eval_loss(self, tokens: np.ndarray, targets: np.ndarray) -> float:
        return float(self._eval_fn(
            self.params, self._place(tokens), self._place(targets)))

    def logits(self, tokens: np.ndarray) -> jax.Array:
        return self._logits_fn(self.params, self._place(tokens))

    def router_stats(self, tokens) -> dict | None:
        """MoE routing observability on one batch (see
        `GSPMDEngine.router_stats`): per-expert assignment load (pre-drop)
        and the dropped-assignment fraction, tile-averaged over the
        (dp, sp) mesh. None for dense configs."""
        if self._router_stats_fn is None:
            return None
        st = jax.device_get(
            self._router_stats_fn(self.params, self._place(tokens)))
        return {"expert_load": [round(float(x), 4) for x in st["load"]],
                "drop_fraction": round(float(st["drop_fraction"]), 4)}

    # -------------------------------------------- checkpoint interface

    def get_canonical_params(self):
        return self.params

    def set_canonical_params(self, params):
        self.params = jax.device_put(params, self.rep)

    def set_opt_state(self, state):
        from shallowspeed_tpu.parallel.zero import replace_opt_state

        self.opt_state = replace_opt_state(self, state)
