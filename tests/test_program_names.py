"""The names of the compiled programs the benchmark looks for.

`benchmarks/drivers/serve.py:PROGRAMS` and
`benchmarks/drivers/train.py:PROGRAMS` find the decode tick, the prefill
chunk and the train step among a device trace's `XLA Modules` by
regular expression on `jit_<function name>(`. The names are those of
Python functions in the program, which a refactor may change; then
`kernels.decode_roofline.*` and `kernels.train_step_roofline` would
read nothing and say nothing. A rename has to fail here first: change
the benchmark's patterns in a `benchmark` PR, together with this file."""

import re

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from shallowspeed_tpu.models import transformer as T
from shallowspeed_tpu.optim import OPTIMIZERS
from shallowspeed_tpu.parallel.context import ContextParallelEngine
from shallowspeed_tpu.serving import engine as serving
from shallowspeed_tpu.serving.cache import SCRATCH_BLOCK

CFG = T.TransformerConfig(vocab=48, d_model=24, n_heads=2, n_layers=2,
                          max_seq=96)


def module_name(lowered) -> str:
    """`jit__decode_tick`, as XLA names the module (the trace shows it
    followed by the program's fingerprint in brackets)."""
    return re.search(r"module @(\S+)", lowered.as_text()).group(1)


@pytest.fixture(scope="module")
def eng():
    e = serving.ServingEngine(jax.device_put(T.init(CFG, seed=1)), CFG,
                              n_blocks=48, block_size=8, max_slots=2,
                              prefill_chunk=16)
    e.submit(np.arange(20, dtype=np.int32) % CFG.vocab, 4, rid="a")
    while not any(r is not None and r.phase == "decode" for r in e.slots):
        e.step()
    return e


def test_decode_tick_is_named_as_the_benchmark_expects(eng):
    _, _, rows = eng._decode_prep()
    lowered = serving._decode_tick.lower(
        eng.params, eng.pools, *rows, cfg=eng.cfg, top_k=eng.top_k,
        top_p=eng.top_p)
    assert re.search(r"^jit__decode_tick", module_name(lowered))


def test_prefill_chunk_is_named_as_the_benchmark_expects(eng):
    c = eng.prefill_chunk
    bt = np.full((1, eng.table_bucket), SCRATCH_BLOCK, np.int32)
    lowered = serving._prefill_chunk.lower(
        eng.params, eng.pools, np.zeros((1, c), np.int32), np.int32(0),
        np.int32(c), bt, np.int32(SCRATCH_BLOCK), np.int32(SCRATCH_BLOCK),
        cfg=eng.cfg)
    assert re.search(r"^jit__prefill_chunk", module_name(lowered))


def test_train_step_is_named_as_the_benchmark_expects():
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("dp", "sp"))
    e = ContextParallelEngine(CFG, OPTIMIZERS["adafactor"](lr=1e-3), mesh,
                              seed=0)
    tokens = np.zeros((2, 32), np.int32)
    lowered = e._step_fn.lower(e.params, e.opt_state, e.place(tokens),
                               e.place(tokens), np.uint32(0))
    assert re.search(r"^jit__step", module_name(lowered))
