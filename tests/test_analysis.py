"""Static analysis (`shallowspeed_tpu.analysis`) tests.

Two layers:

- **Per-rule toy fixtures**: one intentionally-bad jitted program per
  rule (accidental f32 promotion, missing donation, foreign-mesh
  collective, multi-cycle pp ppermute, unstable jit cache, over-budget
  memory, stacked fp8 roundings, bf16 scan-carry grad sums, narrow
  grad psums, forgotten/VJP-side quantization scales, provable range
  overflows) asserting the rule FIRES, plus a clean twin asserting it
  stays quiet — the rules are tested like any other pure function.
- **The tier-1 gate**: every shipped compiled train-step family
  (pipeline_lm GPipe/1F1B/interleaved/ZB-H1, gspmd, spmd_pipeline,
  engine, serving decode, fp8_train) must analyze to ZERO unsuppressed
  high-severity findings; plus the CLI contract (JSON format, baseline
  diff mode, usage-error exit codes) and the stale-suppression audit.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from shallowspeed_tpu import analysis
from shallowspeed_tpu.analysis import (EntryPoint, Severity, TargetProbe,
                                       gate_count, run_rules)
from shallowspeed_tpu.analysis.findings import (clear_suppressions,
                                                registered_suppressions,
                                                suppress)
from shallowspeed_tpu.analysis.targets import TARGET_BUILDERS
from jax import shard_map


def toy_probe(fn, args, donate=(), mesh=None, compute_dtype=None,
              calls=0, budget=16 << 30, name="toy", ranges=None):
    probe = TargetProbe(name, mesh, compute_dtype, hbm_budget=budget)
    probe.entrypoints = [EntryPoint(
        "fn", fn, tuple(args), tuple(f"arg{i}" for i in range(len(args))),
        donate=tuple(donate), calls=calls, ranges=ranges)]
    return probe.seal()


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def highs(findings):
    return [f for f in findings
            if f.severity == Severity.HIGH and not f.suppressed]


# ------------------------------------------------------- dtype promotion


def test_dtype_rule_fires_on_weak_promotion():
    @jax.jit
    def bad(x):  # bf16 activations against a forgotten-f32 constant
        return x @ jnp.ones((8, 8), jnp.float32)

    probe = toy_probe(bad, [sds((4, 8), jnp.bfloat16)],
                      compute_dtype=jnp.bfloat16)
    assert highs(run_rules(probe, only=("dtype-promotion",)))


def test_dtype_rule_fires_on_upcast_matmul():
    @jax.jit
    def bad(x, w):  # bf16 data upcast, then an all-f32 matmul
        return x.astype(jnp.float32) @ w

    probe = toy_probe(
        bad, [sds((4, 8), jnp.bfloat16), sds((8, 8), jnp.float32)],
        compute_dtype=jnp.bfloat16)
    assert highs(run_rules(probe, only=("dtype-promotion",)))


def test_dtype_rule_quiet_on_f32_accumulation():
    @jax.jit
    def clean(q, k, v):  # the documented score-path pattern
        s = jnp.einsum("qd,kd->qk", q, k,
                       preferred_element_type=jnp.float32)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("qk,kd->qd", p.astype(v.dtype), v,
                          preferred_element_type=jnp.float32)

    args = [sds((4, 8), jnp.bfloat16)] * 3
    probe = toy_probe(clean, args, compute_dtype=jnp.bfloat16)
    assert not highs(run_rules(probe, only=("dtype-promotion",)))


def test_dtype_rule_flags_round_trip_convert():
    @jax.jit
    def smelly(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32) + 1.0

    probe = toy_probe(smelly, [sds((16,), jnp.float32)])
    fs = run_rules(probe, only=("dtype-promotion",))
    assert any("round-trip" in f.message for f in fs)
    assert not highs(fs)  # MEDIUM: a smell, not a gate


# --------------------------------------------------------------- donation


def test_donation_rule_fires_on_undonated_step():
    @jax.jit
    def step(params, opt, x):
        return params + x.sum(), opt + 1.0

    args = [sds((8,), jnp.float32), sds((), jnp.float32),
            sds((4,), jnp.float32)]
    probe = toy_probe(step, args, donate=(0, 1))
    found = highs(run_rules(probe, only=("donation",)))
    assert len(found) == 2  # params AND opt-state un-donated


def test_donation_rule_quiet_when_donated():
    @partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt, x):
        return params + x.sum(), opt + 1.0

    args = [sds((8,), jnp.float32), sds((), jnp.float32),
            sds((4,), jnp.float32)]
    probe = toy_probe(step, args, donate=(0, 1))
    assert not run_rules(probe, only=("donation",))


# ------------------------------------------------------------- collective


def mesh2x2(names=("dp", "pp")):
    return Mesh(np.array(jax.devices()[:4]).reshape(2, 2), names)


def test_collective_rule_fires_on_foreign_mesh():
    foreign = Mesh(np.array(jax.devices()[:2]), ("foo",))

    @jax.jit
    @partial(shard_map, mesh=foreign, in_specs=P("foo"), out_specs=P())
    def prog(x):
        return jax.lax.psum(x, "foo")

    probe = toy_probe(prog, [sds((4,), jnp.float32)], mesh=mesh2x2())
    found = highs(run_rules(probe, only=("collective",)))
    assert found and "foo" in found[0].message


def test_collective_rule_fires_on_multi_cycle_pp_ppermute():
    @jax.jit
    @partial(shard_map, mesh=mesh2x2(), in_specs=P("dp", "pp"),
             out_specs=P("dp", "pp"))
    def prog(x):  # two self-loops: stages never exchange
        return jax.lax.ppermute(x, "pp", [(0, 0), (1, 1)])

    probe = toy_probe(prog, [sds((4, 4), jnp.float32)], mesh=mesh2x2())
    found = highs(run_rules(probe, only=("collective",)))
    assert found and "single" in found[0].message


def test_collective_rule_quiet_on_ring():
    @jax.jit
    @partial(shard_map, mesh=mesh2x2(), in_specs=P("dp", "pp"),
             out_specs=P("dp", "pp"))
    def prog(x):
        x = jax.lax.ppermute(x, "pp", [(0, 1), (1, 0)])
        return jax.lax.psum(x, "dp") * 0.5

    probe = toy_probe(prog, [sds((4, 4), jnp.float32)], mesh=mesh2x2())
    assert not highs(run_rules(probe, only=("collective",)))


# ---------------------------------------------------------------- retrace


def test_retrace_rule_fires_on_unstable_cache():
    @jax.jit
    def f(x):
        return x * 2

    f(jnp.ones((4,)))
    f(jnp.ones((8,)))  # a second executable
    probe = toy_probe(f, [sds((4,), jnp.float32)], calls=2)
    assert highs(run_rules(probe, only=("retrace",)))


def test_retrace_rule_quiet_on_stable_cache():
    @jax.jit
    def f(x):
        return x * 3

    f(jnp.ones((4,)))
    f(jnp.ones((4,)) + 1)
    probe = toy_probe(f, [sds((4,), jnp.float32)], calls=2)
    assert not run_rules(probe, only=("retrace",))


# ------------------------------------------------------- memory highwater


def test_memory_rule_fires_over_budget():
    @jax.jit
    def big(x):
        y = jnp.outer(x, x)          # (2048, 2048) f32 = 16 MiB live
        return y.sum()

    probe = toy_probe(big, [sds((2048,), jnp.float32)],
                      budget=1 << 20)  # 1 MiB
    assert highs(run_rules(probe, only=("memory-highwater",)))


def test_memory_rule_quiet_within_budget():
    @jax.jit
    def small(x):
        return (x * 2).sum()

    probe = toy_probe(small, [sds((64,), jnp.float32)])
    fs = run_rules(probe, only=("memory-highwater",))
    assert fs and not highs(fs)  # informational LOW only


# ------------------------------------------------------------ suppression


def test_suppression_marks_and_ungates():
    @jax.jit
    def step(params, x):
        return params + x.sum()

    snapshot = registered_suppressions()
    try:
        suppress("donation", target="toy-sup", match="not donated",
                 reason="toy fixture: documents the mechanism")
        probe = toy_probe(step, [sds((8,), jnp.float32),
                                 sds((4,), jnp.float32)],
                          donate=(0,), name="toy-sup")
        fs = run_rules(probe, only=("donation",))
        assert fs and all(f.suppressed for f in fs)
        assert gate_count(fs) == 0
        assert "toy fixture" in fs[0].format()
    finally:
        clear_suppressions(snapshot)


def test_suppression_requires_reason():
    with pytest.raises(AssertionError):
        suppress("donation", reason="   ")


# ---------------------------------------------------------- overlap-bucket


def dp_mesh2():
    return Mesh(np.array(jax.devices()[:2]), ("dp",))


def _grad_psum_program(extra_stray=False):
    """Toy overlapped-style grad program: two dot 'layers', per-bucket
    psums interleaved so each has independent compute. With
    `extra_stray`, a third grad-sized dp psum is emitted that no
    bucket registers."""
    mesh = dp_mesh2()

    @jax.jit
    @partial(shard_map, mesh=mesh,
             in_specs=(P(), P(), P("dp")), out_specs=(P(), P(), P()))
    def step(w1, w2, x):
        h = x @ w1
        g2 = jax.lax.psum((h.T @ h,), "dp")[0]       # bucket: layer 2
        g1 = jax.lax.psum((x.T @ (h @ w2),), "dp")[0]  # bucket: layer 1
        stray = (jax.lax.psum(x.T @ x, "dp")
                 if extra_stray else jnp.zeros_like(g1))
        return g1, g2, stray

    return step


def _ov_probe(fn, register_buckets):
    from shallowspeed_tpu.parallel.overlap import (bucket_signature,
                                                   register_program)

    register_program(
        fn, "dp",
        [bucket_signature([np.zeros((64, 64), np.float32)])
         for _ in range(register_buckets)], engine="toy")
    args = [sds((64, 64), jnp.float32), sds((64, 64), jnp.float32),
            sds((8, 64), jnp.float32)]
    return toy_probe(fn, args, mesh=dp_mesh2())


def test_overlap_rule_fires_on_unregistered_dp_psum():
    probe = _ov_probe(_grad_psum_program(extra_stray=True),
                      register_buckets=2)
    found = highs(run_rules(probe, only=("overlap-bucket",)))
    assert found and "registered reduction bucket" in found[0].message


def test_overlap_rule_quiet_on_registered_buckets():
    probe = _ov_probe(_grad_psum_program(), register_buckets=2)
    assert not run_rules(probe, only=("overlap-bucket",))


def test_overlap_rule_fires_when_nothing_can_overlap():
    mesh = dp_mesh2()

    @jax.jit
    @partial(shard_map, mesh=mesh, in_specs=(P(), P("dp")),
             out_specs=P())
    def barrier(w, x):
        h = x @ w
        return jax.lax.psum((h.T @ h,), "dp")[0]  # every dot feeds it

    from shallowspeed_tpu.parallel.overlap import (bucket_signature,
                                                   register_program)

    register_program(barrier, "dp",
                     [bucket_signature([np.zeros((64, 64),
                                                 np.float32)])])
    probe = toy_probe(barrier, [sds((64, 64), jnp.float32),
                                sds((8, 64), jnp.float32)],
                      mesh=dp_mesh2())
    found = highs(run_rules(probe, only=("overlap-bucket",)))
    assert found and "independent compute" in found[0].message


def test_overlap_rule_flags_missing_registered_bucket():
    probe = _ov_probe(_grad_psum_program(), register_buckets=3)
    found = run_rules(probe, only=("overlap-bucket",))
    assert any("never appeared" in f.message
               and f.severity == Severity.MEDIUM for f in found)


def test_overlap_rule_skips_unregistered_programs():
    mesh = dp_mesh2()

    @jax.jit
    @partial(shard_map, mesh=mesh, in_specs=(P(), P("dp")),
             out_specs=P())
    def bulk(w, x):  # the documented bulk oracle — not a defect
        h = x @ w
        return jax.lax.psum(h.T @ h, "dp")

    probe = toy_probe(bulk, [sds((64, 64), jnp.float32),
                             sds((8, 64), jnp.float32)],
                      mesh=dp_mesh2())
    assert not run_rules(probe, only=("overlap-bucket",))


# --------------------------------------------------------- dequant fusion


def test_dequant_rule_fires_on_materialized_dequant():
    """The classic way to lose quantized storage: scale the upcast
    weight BEFORE the dot — a full (K, N) dequantized copy."""
    @jax.jit
    def bad(x, wq, ws):
        return x @ (wq.astype(jnp.float32) * ws)

    probe = toy_probe(bad, [sds((4, 8), jnp.float32),
                            sds((8, 16), jnp.int8),
                            sds((16,), jnp.float32)])
    found = highs(run_rules(probe, only=("dequant-fusion",)))
    assert found and "dequantized copy" in found[0].message


def test_dequant_rule_fires_on_bf16_dequant_copy():
    """A bf16 dequant copy is still a copy (ml_dtypes floats must
    class as floating for the size check)."""
    @jax.jit
    def bad(x, wq, ws):
        return x @ (wq.astype(jnp.bfloat16) * ws.astype(jnp.bfloat16))

    probe = toy_probe(bad, [sds((4, 8), jnp.float32),
                            sds((8, 16), jnp.int8),
                            sds((16,), jnp.float32)])
    assert highs(run_rules(probe, only=("dequant-fusion",)))


def test_dequant_rule_fires_on_fp8_weights():
    @jax.jit
    def bad(x, wq, ws):
        return x @ (wq.astype(jnp.float32) * ws)

    fp8 = getattr(jnp, "float8_e4m3fn", None)
    if fp8 is None:
        pytest.skip("no float8_e4m3fn in this jax build")
    probe = toy_probe(bad, [sds((4, 8), jnp.float32),
                            sds((8, 16), fp8),
                            sds((16,), jnp.float32)])
    assert highs(run_rules(probe, only=("dequant-fusion",)))


def test_dequant_rule_quiet_on_fused_form():
    """`dequant_matmul` is the clean fixture: the value upcast feeds
    the dot directly (folded into the operand load), the scale lands
    on the f32 accumulator."""
    from shallowspeed_tpu.ops.matmul import dequant_matmul

    @jax.jit
    def clean(x, wq, ws):
        return dequant_matmul(x, wq, ws)

    probe = toy_probe(clean, [sds((4, 8), jnp.float32),
                              sds((8, 16), jnp.int8),
                              sds((16,), jnp.float32)])
    assert not run_rules(probe, only=("dequant-fusion",))


def test_dequant_rule_exempts_gathered_int8_kv_views():
    """int8 KV reads go through a GATHER before their upcast (the
    paged read path); the gather breaks the weight-view chain, so the
    reference attention's gathered-view casts are not weight dequants
    and must not fire."""
    @jax.jit
    def kv_read(q, pool, bt):
        g = pool[bt]                       # (rows, W, H, bs, hd) int8
        g = jnp.swapaxes(g, 1, 2).reshape(2, 2, 16, 4)
        return jnp.einsum("rhd,rhkd->rhk", q, g.astype(jnp.float32))

    probe = toy_probe(kv_read, [sds((2, 2, 4), jnp.float32),
                                sds((8, 2, 8, 4), jnp.int8),
                                sds((2, 2), jnp.int32)])
    assert not run_rules(probe, only=("dequant-fusion",))


def test_dequant_rule_clean_on_quantized_decode_tick():
    """The live target: the serving decode tick at full quantization
    (int8 weights + int8 KV + the paged flash kernel) never
    materializes a dequantized weight copy. (Also exercised by the
    parametrized clean gate below via the 'serving' target.)"""
    results = analysis.analyze("serving", only=("dequant-fusion",))
    assert all(not fs for fs in results.values()), results


# ----------------------------------------------- fp8 double rounding

FP8 = getattr(jnp, "float8_e4m3fn", None)
fp8_only = pytest.mark.skipif(FP8 is None,
                              reason="no float8_e4m3fn in this build")


@fp8_only
def test_double_rounding_fires_on_stacked_narrowing():
    @jax.jit
    def bad(x):  # f32 -> bf16 -> e4m3: two roundings, no rescale
        return x.astype(jnp.bfloat16).astype(FP8)

    probe = toy_probe(bad, [sds((8, 8), jnp.float32)])
    found = highs(run_rules(probe, only=("fp8-double-rounding",)))
    assert found and "rounded again" in found[0].message


@fp8_only
def test_double_rounding_quiet_after_rescale():
    @jax.jit
    def clean(x, s):  # requantization done right: rescale FIRST
        h = x.astype(jnp.bfloat16)
        return (h.astype(jnp.float32) / s).astype(FP8)

    probe = toy_probe(clean, [sds((8, 8), jnp.float32),
                              sds((), jnp.float32)])
    assert not run_rules(probe, only=("fp8-double-rounding",))


def test_double_rounding_exempts_same_width_reround():
    @jax.jit
    def clean(x, b):  # the standard mixed-precision layernorm shape
        h = x.astype(jnp.float32) + b.astype(jnp.float32)
        return h.astype(jnp.bfloat16)

    probe = toy_probe(clean, [sds((8, 8), jnp.bfloat16),
                              sds((8,), jnp.bfloat16)])
    assert not run_rules(probe, only=("fp8-double-rounding",))


# ----------------------------------------------- accumulation dtype


def test_accumulation_rule_fires_on_bf16_scan_carry():
    @jax.jit
    def bad(xs):  # the peeled-microbatch grad sum, done wrong
        def tick(acc, x):
            return acc + x, None

        acc, _ = jax.lax.scan(tick, jnp.zeros((64,), jnp.bfloat16), xs)
        return acc

    probe = toy_probe(bad, [sds((4, 64), jnp.bfloat16)])
    found = highs(run_rules(probe, only=("accumulation-dtype",)))
    assert found and "carried accumulator" in found[0].message


def test_accumulation_rule_quiet_on_f32_scan_carry():
    @jax.jit
    def clean(xs):  # the hand schedules' `a + g.astype(f32)` idiom
        def tick(acc, x):
            return acc + x.astype(jnp.float32), None

        acc, _ = jax.lax.scan(tick, jnp.zeros((64,), jnp.float32), xs)
        return acc.astype(jnp.bfloat16)

    probe = toy_probe(clean, [sds((4, 64), jnp.bfloat16)])
    assert not highs(run_rules(probe, only=("accumulation-dtype",)))


def test_accumulation_rule_quiet_on_bf16_residual_stream():
    @jax.jit
    def clean(xs, w):  # h + f(h): f depends on the carry — NOT a sum
        def tick(h, _):
            return h + (h @ w).astype(h.dtype), None

        h, _ = jax.lax.scan(tick, xs, None, length=3)
        return h

    probe = toy_probe(clean, [sds((8, 64), jnp.bfloat16),
                              sds((64, 64), jnp.bfloat16)])
    assert not highs(run_rules(probe, only=("accumulation-dtype",)))


def test_accumulation_rule_fires_on_narrow_quant_dot():
    @jax.jit
    def bad(x, w):  # int8 weights, bf16 accumulator: K rounded away
        return x @ w["Wq"].astype(jnp.bfloat16) * w["Ws"]

    probe = toy_probe(bad, [sds((4, 32), jnp.bfloat16),
                            {"Wq": sds((32, 16), jnp.int8),
                             "Ws": sds((16,), jnp.bfloat16)}])
    found = highs(run_rules(probe, only=("accumulation-dtype",)))
    assert found and "quantized-storage" in found[0].message


def test_accumulation_rule_quiet_on_f32_quant_dot():
    @jax.jit
    def clean(x, w):
        acc = jax.lax.dot_general(
            x, w["Wq"].astype(jnp.bfloat16), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc * w["Ws"]

    probe = toy_probe(clean, [sds((4, 32), jnp.bfloat16),
                              {"Wq": sds((32, 16), jnp.int8),
                               "Ws": sds((16,), jnp.float32)}])
    assert not highs(run_rules(probe, only=("accumulation-dtype",)))


# --------------------------------------------- reduction precision


def test_reduction_rule_fires_on_bf16_grad_psum():
    mesh = dp_mesh2()

    @jax.jit
    @partial(shard_map, mesh=mesh, in_specs=P("dp"), out_specs=P())
    def bad(g):
        return jax.lax.psum(g, "dp")

    probe = toy_probe(bad, [sds((4, 64, 64), jnp.bfloat16)], mesh=mesh)
    found = highs(run_rules(probe, only=("reduction-precision",)))
    assert found and "re-rounds" in found[0].message


def test_reduction_rule_quiet_on_f32_and_subkib():
    mesh = dp_mesh2()

    @jax.jit
    @partial(shard_map, mesh=mesh, in_specs=(P("dp"), P("dp")),
             out_specs=(P(), P()))
    def clean(g, stat):
        return (jax.lax.psum(g.astype(jnp.float32), "dp"),
                jax.lax.psum(stat, "dp"))  # sub-KiB statistic: exempt

    probe = toy_probe(clean, [sds((4, 64, 64), jnp.bfloat16),
                              sds((4,), jnp.bfloat16)], mesh=mesh)
    assert not run_rules(probe, only=("reduction-precision",))


# ----------------------------------------------- scale consistency


def test_scale_rule_fires_on_forgotten_scale():
    @jax.jit
    def bad(x, w):  # Wq consumed, Ws never applied
        return x @ w["Wq"].astype(jnp.float32)

    probe = toy_probe(bad, [sds((4, 32), jnp.float32),
                            {"Wq": sds((32, 16), jnp.int8),
                             "Ws": sds((16,), jnp.float32)}])
    found = highs(run_rules(probe, only=("scale-consistency",)))
    assert found and "never applied" in found[0].message


def test_scale_rule_quiet_on_accumulator_scale():
    @jax.jit
    def clean(x, w):
        return (x @ w["Wq"].astype(jnp.float32)) * w["Ws"]

    probe = toy_probe(clean, [sds((4, 32), jnp.float32),
                              {"Wq": sds((32, 16), jnp.int8),
                               "Ws": sds((16,), jnp.float32)}])
    assert not run_rules(probe, only=("scale-consistency",))


def test_scale_rule_resolves_vjp_cotangent_scaling():
    """The transpose side: d/dx of a scaled quant matmul consumes Wq
    in the backward dot with the scale riding the COTANGENT (g * Ws)
    — a resolved pairing, not a forgotten scale."""
    def f(x, w):
        return ((x @ w["Wq"].astype(jnp.float32)) * w["Ws"]).sum()

    g = jax.jit(jax.grad(f, argnums=0))
    probe = toy_probe(g, [sds((4, 32), jnp.float32),
                          {"Wq": sds((32, 16), jnp.int8),
                           "Ws": sds((16,), jnp.float32)}])
    assert not highs(run_rules(probe, only=("scale-consistency",)))


def test_scale_rule_certifies_fp8_train_both_sides():
    """The live target: in-program e4m3 quantization in fp8_dense must
    pair every quantized operand to its delayed/JIT scale on the
    forward AND the hand-VJP dots."""
    results = analysis.analyze("fp8_train", only=("scale-consistency",))
    assert all(not fs for fs in results.values()), results


# --------------------------------------------------- range safety


@fp8_only
def test_range_rule_fires_on_unclamped_fp8_cast():
    @jax.jit
    def bad(x):
        return x.astype(FP8)

    probe = toy_probe(bad, [sds((8, 8), jnp.float32)],
                      ranges={"arg0": (-1000.0, 1000.0)})
    found = highs(run_rules(probe, only=("range-safety",)))
    assert found and "overflows" in found[0].message


@fp8_only
def test_range_rule_quiet_on_saturating_clamp():
    @jax.jit
    def clean(x):
        return jnp.clip(x, -448.0, 448.0).astype(FP8)

    probe = toy_probe(clean, [sds((8, 8), jnp.float32)],
                      ranges={"arg0": (-1000.0, 1000.0)})
    assert not run_rules(probe, only=("range-safety",))


def test_range_rule_fires_on_provable_exp_overflow():
    @jax.jit
    def bad(x):
        return jnp.exp(x)

    probe = toy_probe(bad, [sds((8,), jnp.float32)],
                      ranges={"arg0": (120.0, 200.0)})
    assert highs(run_rules(probe, only=("range-safety",)))


def test_range_rule_quiet_on_shifted_softmax():
    @jax.jit
    def clean(x):  # x - max(x) <= 0: exp provably in range
        return jax.nn.softmax(x, axis=-1)

    probe = toy_probe(clean, [sds((4, 8), jnp.float32)],
                      ranges={"arg0": (-500.0, 500.0)})
    assert not run_rules(probe, only=("range-safety",))


# ------------------------------- serialization, stale audit, baseline


def test_finding_to_dict_and_key():
    f = analysis.Finding("r", Severity.HIGH, "t", "s", ("pjit",),
                         "boom (x3)")
    d = f.to_dict()
    assert d["severity"] == "HIGH" and d["path"] == ["pjit"]
    assert d["key"] == "r|t|s|pjit|boom"  # dedup count stripped
    assert f.key == d["key"]


def test_stale_suppression_audit():
    from shallowspeed_tpu.analysis.findings import stale_suppressions

    snapshot = registered_suppressions()
    try:
        clear_suppressions()
        s_used = suppress("donation", target="probe-a", reason="live")
        suppress("donation", target="probe-a", match="nope",
                 reason="documents a deviation that no longer exists")
        suppress("donation", target="probe-b",
                 reason="covers a probe that did not run")
        hit = analysis.Finding("donation", Severity.HIGH, "probe-a",
                               "fn", (), "x", suppressed="live",
                               suppressed_by=s_used)
        stale = stale_suppressions({"probe-a": [hit]},
                                   ran_rules=("donation",))
        assert len(stale) == 1  # only the matched-nothing registration
        assert stale[0].severity == Severity.MEDIUM
        assert stale[0].rule == "stale-suppression"
        assert "matched no finding" in stale[0].message
        # rule didn't run -> nothing can be proven stale
        assert not stale_suppressions({"probe-a": [hit]},
                                      ran_rules=("retrace",))
    finally:
        clear_suppressions(snapshot)


def test_cli_json_and_baseline_roundtrip(tmp_path, capsys):
    import json

    from shallowspeed_tpu.analysis.__main__ import SCHEMA, main

    base = tmp_path / "baseline.json"
    assert main(["--target", "engine", "--write-baseline",
                 str(base)]) == 0
    capsys.readouterr()
    doc = json.loads(base.read_text())
    assert doc["schema"] == SCHEMA and doc["keys"] == []  # clean target

    assert main(["--target", "engine", "--baseline", str(base),
                 "--format", "json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["schema"] == SCHEMA
    assert out["gate"] == 0 and "engine" in out["targets"]
    fs = out["targets"]["engine"]["findings"]
    assert fs and all(
        set(f) >= {"rule", "severity", "target", "site", "path",
                   "message", "suppressed", "key"} for f in fs)


def test_cli_usage_errors_exit_two(tmp_path):
    from shallowspeed_tpu.analysis.__main__ import main

    with pytest.raises(SystemExit) as e:
        main(["--target", "bogus"])
    assert e.value.code == 2
    with pytest.raises(SystemExit) as e:
        main(["--rules", "bogus"])
    assert e.value.code == 2
    with pytest.raises(SystemExit) as e:
        main(["--baseline", str(tmp_path / "missing.json")])
    assert e.value.code == 2


# ----------------------------------------------- the tier-1 clean gate


@pytest.mark.parametrize("target", sorted(TARGET_BUILDERS))
def test_shipped_train_steps_are_tpu_clean(target):
    """THE acceptance gate: every compiled train-step family ships with
    zero unsuppressed high-severity findings."""
    results = analysis.analyze(target)
    gating = [f for fs in results.values() for f in fs
              if f.severity == Severity.HIGH and not f.suppressed]
    assert not gating, "\n".join(f.format() for f in gating)


def test_cli_exits_zero_on_clean_target():
    from shallowspeed_tpu.analysis.__main__ import main

    assert main(["--target", "engine", "-q"]) == 0
