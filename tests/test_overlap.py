"""Comm/compute interleaving (`shallowspeed_tpu/parallel/overlap.py`).

Four layers of pinning:

- **Bucket plans**: every leaf in exactly one bucket, bucket payloads
  at most the target (single oversized leaves excepted) — pure-function
  unit tests.
- **Oracle parity**: the bucketed/overlapped reduction must train
  bit-for-bit-close to the bulk-psum oracle on every engine family —
  fused dp, dp x pp SPMD pipeline (both hop modes), FSDP, and the
  context engine (dense / zero1 / zero2, with gradient accumulation so
  the peeled-microbatch path runs).
- **Program shape**: one executable per entrypoint (no new entrypoints,
  no recompiles), and the dataflow exposure (`collective_exposure`)
  strictly lower with overlap on than with the bulk reduction — the
  acceptance measure telemetry stamps on step lines as
  `exposed_comm_frac` (schema v3).
- **Health interaction**: the spec-driven health pack (PR 3) stays
  oracle-correct when grads arrive pre-reduced per bucket.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from shallowspeed_tpu.engine import FusedDPEngine
from shallowspeed_tpu.models import transformer as T
from shallowspeed_tpu.models.mlp import MLPStage
from shallowspeed_tpu.optim import SGD, Adam
from shallowspeed_tpu.parallel.context import ContextParallelEngine
from shallowspeed_tpu.parallel.fsdp import FSDPEngine
from shallowspeed_tpu.parallel.mesh import make_mesh
from shallowspeed_tpu.parallel.overlap import (OverlapConfig,
                                               bucket_signature,
                                               collective_exposure,
                                               from_flags, leaf_bytes,
                                               plan_buckets,
                                               plan_param_buckets,
                                               registered)

TOL = 2e-5  # worst-leaf relmax vs the bulk oracle (float reassociation)


def relmax(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    out = 0.0
    for x, y in zip(la, lb):
        x = np.asarray(x, np.float32)
        y = np.asarray(y, np.float32)
        out = max(out, float(np.abs(x - y).max()
                             / max(1e-8, float(np.abs(y).max()))))
    return out


def sds_of(tree):
    return jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(np.shape(l), np.asarray(l).dtype)
        if not hasattr(l, "dtype")
        else jax.ShapeDtypeStruct(l.shape, l.dtype), tree)


# -------------------------------------------------------- bucket plans


def test_plan_every_leaf_in_exactly_one_bucket():
    leaves = [np.zeros(s, np.float32) for s in
              [(64, 64), (64,), (128, 32), (8,), (1000,), (3,)]]
    plan = plan_buckets(leaves, bucket_bytes=8 << 10)
    seen = [i for b in plan for i in b]
    assert sorted(seen) == list(range(len(leaves)))
    assert all(len(set(b)) == len(b) for b in plan)


def test_plan_respects_byte_target():
    leaves = [np.zeros((50,), np.float32) for _ in range(20)]  # 200 B each
    plan = plan_buckets(leaves, bucket_bytes=1000)
    for b in plan:
        assert sum(leaf_bytes(leaves[i]) for i in b) <= 1000
    assert len(plan) == 4  # 5 x 200 B per bucket


def test_plan_oversized_leaf_gets_own_bucket():
    leaves = [np.zeros((10,), np.float32),
              np.zeros((10_000,), np.float32),
              np.zeros((10,), np.float32)]
    plan = plan_buckets(leaves, bucket_bytes=1000)
    assert [len(b) for b in plan] == [1, 1, 1]


def test_plan_preserves_given_order():
    leaves = [np.zeros((100,), np.float32) for _ in range(4)]
    plan = plan_buckets(leaves, bucket_bytes=800)  # 2 leaves per bucket
    assert plan == [[0, 1], [2, 3]]


def test_param_plan_is_backward_finalization_ordered():
    params = {"a": np.zeros((100,), np.float32),
              "b": np.zeros((100,), np.float32),
              "c": np.zeros((100,), np.float32)}
    plan, leaves, _ = plan_param_buckets(params, bucket_bytes=800)
    # reversed flatten order, contiguous: the LAST leaves bucket first
    assert plan[0] == [2, 1] and plan[-1] == [0]
    assert len(leaves) == 3


def test_from_flags():
    assert from_flags("off", 4.0) is None
    cfg = from_flags("on", 2.0)
    assert cfg.bucket_mb == 2.0 and cfg.bucket_bytes == 2 << 20


# --------------------------------------------------- fused dp engine

SIZES = [784, 128, 127, 126, 125, 124, 123, 10]


class _DS:
    def __init__(self, seed, n_mu, mubs, d_in=784, d_out=10):
        self.seed, self.n_mu, self.mubs = seed, n_mu, mubs
        self.d_in, self.d_out = d_in, d_out

    def load_mubatch_stack(self, batch_id):
        rng = np.random.default_rng([self.seed, batch_id])
        x = rng.standard_normal(
            (self.n_mu, self.mubs, self.d_in)).astype(np.float32)
        y = np.eye(self.d_out, dtype=np.float32)[
            rng.integers(0, self.d_out, (self.n_mu, self.mubs))]
        return x, y


def fused_pair(n_mu=4, dp=2, health="off"):
    gbs = 32
    mubs = gbs // dp // n_mu

    def build(ov):
        return FusedDPEngine(MLPStage(SIZES, 0, 1, batch_size=gbs),
                             SGD(0.1), make_mesh(dp, 1), health=health,
                             overlap=ov)

    ds = [_DS(r, n_mu, mubs) for r in range(dp)]
    return build(None), build(OverlapConfig(bucket_mb=0.25)), ds


def test_fused_dp_overlap_matches_bulk_oracle():
    e_off, e_on, ds = fused_pair()
    for b in range(3):
        e_off.train_batch(b, ds)
        e_on.train_batch(b, ds)
    assert relmax(e_on.params, e_off.params) <= TOL


def test_fused_dp_overlap_single_microbatch():
    # n_mu=1: the peeled microbatch IS the whole batch (empty scan head)
    e_off, e_on, ds = fused_pair(n_mu=1)
    for b in range(2):
        e_off.train_batch(b, ds)
        e_on.train_batch(b, ds)
    assert relmax(e_on.params, e_off.params) <= TOL


def test_fused_dp_compile_count_pinned():
    _, e_on, ds = fused_pair()
    for b in range(3):
        e_on.train_batch(b, ds)
    assert e_on._step._cache_size() == 1  # no recompiles, no extra eps


def test_fused_dp_exposure_strictly_lower_with_overlap():
    e_off, e_on, ds = fused_pair()
    e_off.train_batch(0, ds)
    e_on.train_batch(0, ds)
    dp, n_mu, mubs = 2, 4, 4
    xs = jax.ShapeDtypeStruct((dp, n_mu, mubs, 784), np.float32)
    ys = jax.ShapeDtypeStruct((dp, n_mu, mubs, 10), np.float32)

    def exposure(e):
        closed = jax.make_jaxpr(e._step)(
            sds_of(e.params), sds_of(e.opt_state), xs, ys)
        return collective_exposure(closed, axes=("dp",))

    off, on = exposure(e_off), exposure(e_on)
    assert off["exposed_comm_frac"] == 1.0  # post-scan bulk: a barrier
    assert on["exposed_comm_frac"] < off["exposed_comm_frac"]
    # equal wire bytes: bucketing moves the reduction, it does not
    # duplicate it
    assert on["total_bytes"] == off["total_bytes"]
    # psum binds one eqn per operand: bucketing regroups, never adds
    assert on["n_collectives"] == off["n_collectives"]


def test_fused_dp_overlap_registered():
    _, e_on, _ = fused_pair()
    info = registered(e_on._step)
    assert info is not None and info["axis"] == "dp"
    assert len(info["buckets"]) >= 2  # 0.25 MiB buckets over ~0.9 MiB
    total = sum(len(b) for b in info["buckets"])
    assert total == 2 * (len(SIZES) - 1)  # every W and b leaf covered


def test_fused_dp_run_fusion_with_overlap():
    e_off, e_on, ds = fused_pair()
    staged_off = e_off.stage_epoch(ds, 3)
    staged_on = e_on.stage_epoch(ds, 3)
    e_off.train_run(staged_off, 2)
    e_on.train_run(staged_on, 2)
    assert relmax(e_on.params, e_off.params) <= TOL


# ----------------------------------------------- spmd pipeline engine


def spmd_pair(double_buffer, dp=2, pp=2):
    from shallowspeed_tpu.parallel.spmd_pipeline import SPMDPipelineEngine

    sizes = [12, 14, 13, 10]
    gbs, n_mu = 16, 2
    mubs = gbs // dp // n_mu

    def build(ov):
        return SPMDPipelineEngine(sizes, SGD(0.1), make_mesh(dp, pp),
                                  n_mu, mubs, gbs, overlap=ov)

    ds = [_DS(r, n_mu, mubs, sizes[0], sizes[-1]) for r in range(dp)]
    return (build(None),
            build(OverlapConfig(bucket_mb=0.001,
                                double_buffer_hops=double_buffer)), ds)


@pytest.mark.parametrize("double_buffer", [False, True])
def test_spmd_pipeline_overlap_matches_bulk_oracle(double_buffer):
    e_off, e_on, ds = spmd_pair(double_buffer)
    for b in range(3):
        e_off.train_batch(b, ds)
        e_on.train_batch(b, ds)
    assert relmax(e_on.params, e_off.params) <= TOL
    assert e_on._step_fn._cache_size() == 1
    # inference unaffected by the hop restructure
    x = np.random.default_rng(0).standard_normal((8, 12)).astype(np.float32)
    assert relmax(e_on.infer(x), e_off.infer(x)) <= TOL


def test_spmd_pipeline_epoch_fusion_with_overlap():
    e_off, e_on, ds = spmd_pair(True)
    e_off.train_epoch(e_off.stage_epoch(ds, 3))
    e_on.train_epoch(e_on.stage_epoch(ds, 3))
    assert relmax(e_on.params, e_off.params) <= TOL


def test_spmd_pipeline_exposure_and_schedule_info():
    e_off, e_on, _ = spmd_pair(True)
    assert e_on.schedule_info()["hop_double_buffer"] is True
    assert e_off.schedule_info()["hop_double_buffer"] is False
    wmax = 14
    xs = jax.ShapeDtypeStruct((2, 2, 4, wmax), np.float32)
    ys = jax.ShapeDtypeStruct((2, 2, 4, 10), np.float32)

    def exposure(e):
        closed = jax.make_jaxpr(e._step_fn)(
            sds_of(e.params), sds_of(e.opt_state), xs, ys)
        return collective_exposure(closed, axes=("dp",))

    off, on = exposure(e_off), exposure(e_on)
    assert on["exposed_comm_frac"] < off["exposed_comm_frac"] == 1.0


# -------------------------------------------------- context engine

CFG = T.TransformerConfig(vocab=64, d_model=32, n_heads=4, n_layers=2,
                          max_seq=32)


def lm_batch(seed, b=8, t=32):
    rng = np.random.default_rng(seed)
    tok = rng.integers(0, 64, (b, t)).astype(np.int32)
    return tok, np.roll(tok, -1, axis=1).astype(np.int32)


def ctx_mesh(dp, sp=1):
    return Mesh(np.array(jax.devices()[:dp * sp]).reshape(dp, sp),
                ("dp", "sp"))


def ctx_pair(health="off", **kw):
    def build(ov):
        return ContextParallelEngine(CFG, Adam(1e-3), ctx_mesh(2),
                                     health=health, overlap=ov, **kw)

    return build(None), build(OverlapConfig(bucket_mb=0.02))


@pytest.mark.parametrize("kw", [dict(accum=2), dict(zero1=True, accum=2),
                                dict(zero2=True, accum=2)],
                         ids=["dense", "zero1", "zero2"])
def test_context_overlap_matches_bulk_oracle(kw):
    e_off, e_on = ctx_pair(**kw)
    for s in range(3):
        tok, tgt = lm_batch(s)
        l_off = e_off.train_batch(tok, tgt)
        l_on = e_on.train_batch(tok, tgt)
    assert abs(l_on - l_off) <= TOL * max(1.0, abs(l_off))
    assert relmax(e_on.get_canonical_params(),
                  e_off.get_canonical_params()) <= TOL
    fn = e_on._step_fn or e_on._loss_grads_fn
    assert fn._cache_size() == 1


def test_context_overlap_accum_exposure_strictly_lower():
    e_off, e_on = ctx_pair(accum=2)
    tok, tgt = lm_batch(0)

    def exposure(e):
        args = (e.params, e.opt_state, e._place(tok), e._place(tgt),
                np.uint32(0))
        closed = jax.make_jaxpr(e._step_fn)(*sds_of(args))
        return collective_exposure(closed, axes=("dp",))

    off, on = exposure(e_off), exposure(e_on)
    # the accumulation scan is one dataflow node: every bulk psum after
    # it is a barrier; the peeled+tagged program reduces in-backward
    assert off["exposed_comm_frac"] == 1.0
    assert on["exposed_comm_frac"] < off["exposed_comm_frac"]
    assert on["total_bytes"] == off["total_bytes"]


def test_context_zero2_overlap_keeps_grad_sharding():
    # the scatter tags must hand the sharded update the SAME 1/dp
    # grad layout as the bulk reduce-scatter path
    e_off, e_on = ctx_pair(zero2=True, accum=2)
    for e in (e_off, e_on):
        tok, tgt = lm_batch(0)
        e.train_batch(tok, tgt)
    for a, b in zip(jax.tree_util.tree_leaves(e_on.opt_state),
                    jax.tree_util.tree_leaves(e_off.opt_state)):
        assert getattr(a, "sharding", None) == getattr(b, "sharding",
                                                       None)


# ------------------------------------------------------ fsdp engine


def fsdp_pair(health="off", opt=Adam):
    def build(ov):
        return FSDPEngine(CFG, opt(1e-3),
                          Mesh(np.array(jax.devices()[:4]), ("dp",)),
                          health=health, overlap=ov)

    return build(None), build(OverlapConfig(bucket_mb=0.01))


def test_fsdp_overlap_matches_gspmd_oracle():
    # params compared under SGD: the k-bias slice of the fused qkv bias
    # has a TRUE gradient of ~0 (softmax shift-invariance), and Adam
    # normalizes its reduction-order fp noise into O(lr) drift
    e_off, e_on = fsdp_pair(opt=SGD)
    for s in range(3):
        tok, tgt = lm_batch(s)
        l_off = e_off.train_batch(tok, tgt)
        l_on = e_on.train_batch(tok, tgt)
    assert abs(l_on - l_off) <= TOL * max(1.0, abs(l_off))
    assert relmax(jax.device_get(e_on.params),
                  jax.device_get(e_off.params)) <= TOL
    assert e_on._step_fn._cache_size() == 1


def test_fsdp_overlap_preserves_placements():
    e_off, e_on = fsdp_pair()
    tok, tgt = lm_batch(0)
    e_on.train_batch(tok, tgt)
    for a, b in zip(jax.tree_util.tree_leaves(e_on.params),
                    jax.tree_util.tree_leaves(e_off.params)):
        assert a.sharding == b.sharding


def test_fsdp_overlap_gathers_and_scatters_in_program():
    _, e_on = fsdp_pair()
    tok = jax.ShapeDtypeStruct((8, 32), np.int32)
    closed = jax.make_jaxpr(e_on._step_fn)(
        sds_of(e_on.params), sds_of(e_on.opt_state), tok, tok,
        jax.ShapeDtypeStruct((), np.uint32))
    expo = collective_exposure(closed, axes=("dp",))
    # explicit collectives exist (the GSPMD step has none at jaxpr
    # level) and nearly all of them have independent compute to hide
    # under — gather of layer i+1 under layer i, scatter of layer i
    # under the backward of layer i-1
    assert expo["n_collectives"] > 10
    assert expo["n_overlapped"] >= 0.8 * expo["n_collectives"]


def test_fsdp_overlap_rejects_adafactor():
    from shallowspeed_tpu.optim import Adafactor

    with pytest.raises(ValueError, match="Adafactor"):
        FSDPEngine(CFG, Adafactor(1e-3),
                   Mesh(np.array(jax.devices()[:4]), ("dp",)),
                   overlap=OverlapConfig())


def test_gspmd_engines_reject_explicit_overlap():
    from shallowspeed_tpu.parallel.tensor import TensorParallelEngine

    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))
    with pytest.raises(ValueError, match="GSPMD-partitioned"):
        TensorParallelEngine(CFG, Adam(1e-3), mesh,
                             overlap=OverlapConfig())


# ------------------------------------------------- health interaction


def test_health_pack_oracle_correct_with_bucketed_grads():
    """PR-3 satellite pin: the spec-driven health reductions stay
    oracle-correct when grads arrive pre-reduced per bucket instead of
    via the bulk psum."""
    e_off, e_on = ctx_pair(health="monitor", zero2=True, accum=2)
    tok, tgt = lm_batch(0)
    e_off.train_batch(tok, tgt)
    e_on.train_batch(tok, tgt)
    h_off, h_on = e_off.health_snapshot(), e_on.health_snapshot()
    for k in ("grad_norm", "param_norm", "update_ratio"):
        assert abs(h_on[k] - h_off[k]) <= 1e-4 * max(1.0, abs(h_off[k]))
    assert h_on["nonfinite"] == h_off["nonfinite"] == 0


def test_health_guard_skips_identically_with_overlap():
    # a poisoned batch must skip bit-identically whether the nonfinite
    # sentinel saw bulk-reduced or bucket-reduced grads
    gbs, n_mu, dp = 32, 4, 2
    mubs = gbs // dp // n_mu
    eng = FusedDPEngine(MLPStage(SIZES, 0, 1, batch_size=gbs),
                        SGD(0.1), make_mesh(dp, 1), health="guard",
                        overlap=OverlapConfig(bucket_mb=0.25))
    ds = [_DS(r, n_mu, mubs) for r in range(dp)]
    eng.train_batch(0, ds)
    before = jax.device_get(eng.params)

    class _PoisonDS(_DS):
        def load_mubatch_stack(self, batch_id):
            x, y = super().load_mubatch_stack(batch_id)
            x[0, 0, 0] = np.nan
            return x, y

    eng.train_batch(1, [_PoisonDS(r, n_mu, mubs) for r in range(dp)])
    after = jax.device_get(eng.params)
    for a, b in zip(jax.tree_util.tree_leaves(before),
                    jax.tree_util.tree_leaves(after)):
        np.testing.assert_array_equal(a, b)
    assert eng.health_snapshot()["skipped_total"] == 1


def test_fsdp_health_pack_with_overlap():
    e_off, e_on = fsdp_pair(health="monitor")
    tok, tgt = lm_batch(0)
    e_off.train_batch(tok, tgt)
    e_on.train_batch(tok, tgt)
    h_off, h_on = e_off.health_snapshot(), e_on.health_snapshot()
    for k in ("grad_norm", "param_norm"):
        assert abs(h_on[k] - h_off[k]) <= 1e-4 * max(1.0, abs(h_off[k]))


# -------------------------------------------------- telemetry surface


def test_step_lines_carry_exposed_comm_frac():
    from shallowspeed_tpu import telemetry as tele

    e_off, e_on, ds = fused_pair()
    tracer = tele.configure(level="steps")
    try:
        telem_on = tele.RunTelemetry(e_on, tracer)
        telem_off = tele.RunTelemetry(e_off, tracer)
        e_on.train_batch(0, ds)
        e_off.train_batch(0, ds)
        f_on = telem_on.step_fields()
        f_off = telem_off.step_fields()
    finally:
        tele.configure(level="off")
    assert f_on["overlap"] is True and f_off["overlap"] is False
    assert f_on["exposed_comm_frac"] < f_off["exposed_comm_frac"]
    assert f_on["overlap_ratio"] > f_off["overlap_ratio"]


def test_schema_v3_accepts_old_and_new_step_lines():
    from shallowspeed_tpu.telemetry.schema import (SCHEMA_VERSION,
                                                   validate_line)

    assert SCHEMA_VERSION >= 3
    v1 = {"event": "step", "step": 1, "loss": 2.0,
          "tokens_per_sec": 10.0, "coll_gbps": 0.5}
    v2 = dict(v1, health_grad_norm=0.1, health_nonfinite=0)
    v3 = dict(v2, exposed_comm_frac=0.25, overlap_ratio=0.75,
              overlap=True)
    assert validate_line(v1) == []
    assert validate_line(v2) == []
    assert validate_line(v3) == []
    assert validate_line(dict(v3, exposed_comm_frac="high"))
    assert validate_line(dict(v3, overlap="yes"))


def test_bucket_signature_is_shape_dtype_multiset():
    a = [np.zeros((4, 4), np.float32), np.zeros((2,), np.float32)]
    assert bucket_signature(a) == bucket_signature(a[::-1])
    assert bucket_signature(a) != bucket_signature(a[:1])
