"""What the TPU compiler makes of the serving programs, without a chip.

libtpu's compile-only topology runs the real XLA:TPU compiler in this
sandbox (`.claude/skills/verify/SKILL.md`, "Mosaic without a chip").
Nothing runs, so these tests read the optimized HLO: what was emitted,
not how long it takes. They skip where libtpu cannot describe a v5e.

The topology is described inside a fixture, never at import: one
process at a time may load libtpu, and every xdist worker imports every
test file. Keep further compile-only tests in THIS file for the same
reason (a second file can land on a worker that cannot load it).
"""

import math
import re

import jax
import jax.numpy as jnp
import pytest

from shallowspeed_tpu.models import transformer as T
from shallowspeed_tpu.ops import flash_attention
from shallowspeed_tpu.serving.cache import init_block_pool, layer_groups
from shallowspeed_tpu.serving.engine import _decode_tick, _prefill_chunk


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


# published head shapes (head_dim 128), two layers, bf16; the widths
# that do not shape the cache are cut so the compile stays seconds
_HEADS = {
    "olmo-1b-mha": dict(d_model=2048, n_heads=16, n_kv_heads=0),
    "mistral-7b-gqa": dict(d_model=4096, n_heads=32, n_kv_heads=8),
    # the latent row of 512 + 64 values at its published sizes (stored
    # rounded up to 640 lanes: at 576 the TPU's layout of the pool puts
    # the block dimension minor-most and this test fails with two
    # pool-sized copies a layer), a dense and a routed layer
    "moonlight-16b-latent": dict(
        d_model=2048, n_heads=16, kv_lora_rank=512, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, n_routed_experts=8,
        n_shared_experts=1, moe_top_k=2, expert_d_ff=256,
        first_dense_layers=1),
    # a window layer and a full layer, each group with its own table
    # (`serving/cache.py:layer_groups`), heads of 128 that d_model does
    # not give, every part a block may hold, a dense and a routed layer
    "trinity-window-full": dict(
        d_model=2048, n_heads=32, n_kv_heads=4, attn_head_dim=128,
        layers=((2048, True), (0, False)), embed_scale=2048 ** 0.5,
        n_routed_experts=8, n_shared_experts=1, moe_top_k=2,
        expert_d_ff=256, first_dense_layers=1),
}
# a head size no cell has: not whole lanes wide
_NARROW = dict(_HEADS, **{"heads-of-64": dict(d_model=1024, n_heads=16,
                                              n_kv_heads=0)})
# pool leaves of 42 MB and more, as the cells have them (67-252 MB): a
# leaf that fits the compiler's alternate memory (21 MB did, at 321 and
# 641 blocks, and four KV heads' at 1281 around the chunk's kernel) XLA
# may prefetch there whole around the Mosaic call that reads it, one
# copy in and one out, and the gate below would read that as the layout
# copies it exists to catch
N_BLOCKS, BLOCK, SLOTS, WIDTH, CHUNK = 2561, 16, 16, 8, 128

_SKIP_OPS = ("parameter", "bitcast", "get-tuple-element", "tuple",
             "constant")


@pytest.fixture(autouse=True)
def mosaic_not_interpreted(monkeypatch):
    """The process's backend is the CPU, where the kernels default to
    the interpreter; what is compiled here is compiled for the chip."""
    monkeypatch.setattr(flash_attention, "_interpret_default",
                        lambda: False)
    # the kernels' entry points are jitted, and so are the programs'
    # blocks that call them: each would remember either mode
    from shallowspeed_tpu.serving import engine

    kernels = (flash_attention.paged_flash_decode,
               flash_attention.paged_flash_prefill)
    for forget in [k.clear_cache for k in kernels] \
            + [engine.clear_program_caches]:
        forget()
    yield
    for forget in [k.clear_cache for k in kernels] \
            + [engine.clear_program_caches]:
        forget()


def _compiled_text(program, one_chip, heads, kv_quant="", widths=None,
                   chunk=CHUNK, n_blocks=N_BLOCKS, slots=SLOTS, ride=None):
    """The optimized HLO of `program` for the chip, its number of pool
    leaves and one of them. `widths`: the tables' widths, one a layer
    group (default: `WIDTH` each). `fused_chunk` is `_prefill_chunk`
    with the tick's `slots` rows riding in it, their tables `ride`
    wide (default: as `widths`)."""
    cfg = T.TransformerConfig(
        vocab=512, d_ff=512, n_layers=2, max_seq=2048, rope=True,
        norm="rmsnorm", ffn="swiglu", dtype=jnp.bfloat16,
        compute_dtype=jnp.bfloat16, **_NARROW[heads])

    def spec(tree):
        return jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                           sharding=one_chip), tree)

    def arr(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    parts = T.BLOCK_PARTS if cfg.layers else ()
    params = spec(jax.eval_shape(lambda: T.cast_params(
        T.init(cfg, seed=0, parts=parts), jnp.bfloat16)))
    pools = spec(jax.eval_shape(
        lambda: init_block_pool(cfg, n_blocks, BLOCK, kv_quant)))
    i32, f32 = jnp.int32, jnp.float32
    # one table a layer group, and where a group's tables do not start
    # at position 0 (a window group) their bases; one group: one table
    groups = len(layer_groups(cfg))
    widths = widths or (WIDTH,) * groups
    tables = lambda rows, widths=widths: arr(i32, rows, widths[0]) \
        if groups == 1 else tuple(arr(i32, rows, w) for w in widths)
    # `_decode_tick`'s per-row arguments, in its order
    tick = lambda widths: (
        arr(i32, slots), arr(i32, slots), tables(slots, widths),
        arr(f32, slots), arr(i32, slots), arr(i32, slots), arr(i32, slots),
        arr(jnp.bool_, slots),
        None if groups == 1 else arr(i32, groups, slots))
    if program == "decode_tick":
        traced = _decode_tick.trace(params, pools, *tick(widths), cfg=cfg,
                                    top_k=0, top_p=0.0)
    else:
        pair = (arr(i32),) * 2 if groups == 1 else (arr(i32, groups),) * 2
        rode = () if program == "prefill_chunk" else (
            arr(i32), tick(ride or widths))
        traced = _prefill_chunk.trace(
            params, pools, arr(i32, 1, chunk), arr(i32), arr(i32),
            tables(1), *pair, None if groups == 1 else arr(i32, groups),
            *rode, cfg=cfg)
    text = traced.lower(lowering_platforms=("tpu",)).compile().as_text()
    leaf = next(iter(pools[0].values()))
    return text, len(jax.tree_util.tree_leaves(pools)), leaf


def _computations(text):
    """{name: body lines} of every computation of an HLO module."""
    out, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            name = head.group(1)
            out[name] = []
            if line.startswith("ENTRY"):
                out["ENTRY"] = out[name]
        elif line.startswith("}"):
            name = None
        elif name is not None:
            out[name].append(line)
    return out


@pytest.mark.parametrize("heads", list(_HEADS))
@pytest.mark.parametrize("program", ["decode_tick", "prefill_chunk"])
def test_serving_programs_write_the_pool_in_place(one_chip, program,
                                                  heads):
    """The gate of PR 27: in the optimized TPU program every donated
    pool leaf is aliased to its output, and the ONLY entry instructions
    whose output is as large as a pool leaf are fusions that end in a
    scatter or a dynamic-update-slice (which XLA runs on the operand's
    own buffer). A pool-sized `copy`, `transpose` or relayout fusion
    here is two of them per leaf per program run on the chip: 45% of
    `olmo-1b.chat`'s device time before this test existed."""
    _assert_written_in_place(
        program, *_compiled_text(program, one_chip, heads))


def _assert_written_in_place(program, text, n_leaves, leaf, n_donated=None):
    """`n_leaves` pool leaves like `leaf`; `n_donated` where the program
    donates more than those (a mixer's slabs)."""
    pool_elems = leaf.size
    aliased = re.search(r"input_output_alias=\{(.*?)\}, entry_comp",
                        text, re.S).group(1)
    assert aliased.count("alias)") == (n_donated or n_leaves), aliased
    comps = _computations(text)
    offenders, in_place = [], 0
    for line in comps["ENTRY"]:
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \w+\[([\d,]*)\]\S* "
                     r"([\w\-]+)\(", line)
        if not m or m.group(2) in _SKIP_OPS:
            continue
        elems = 1
        for d in filter(None, m.group(1).split(",")):
            elems *= int(d)
        if elems != pool_elems:
            continue
        callee = re.search(r"calls=%?([\w.\-]+)", line)
        root = next((l for l in comps.get(callee.group(1), [])
                     if "ROOT" in l), "") if callee else ""
        if m.group(2) == "fusion" and re.search(
                r" (scatter|dynamic-update-slice)\(", root):
            in_place += 1
        else:
            offenders.append(line.strip()[:160])
    assert not offenders, "\n".join(offenders)
    # each leaf is written once a layer by the tick's rows, and in the
    # prefill chunk copied-on-write and written by the chunk's
    assert in_place == n_leaves * {"decode_tick": 1, "prefill_chunk": 2,
                                   "fused_chunk": 3}[program]


@pytest.mark.parametrize("heads", list(_HEADS))
def test_decode_tick_reads_the_pool_through_the_kernel(one_chip, heads,
                                                       monkeypatch):
    """The gate of PR 29: the tick compiled for the chip holds one
    Mosaic call a layer (`paged_flash_decode`, K/V pools and the latent
    pool alike) and NO instruction, in any computation and any dtype,
    whose result has the gathered table's shape: (slots, width, Hkv,
    block, hd), with slots and width merged as XLA wrote it, or the
    latent read's (slots, width * block, hd). The
    gathered table of all 16 slots at the bucket's width, made f32 and
    contracted elementwise, was 63% of `olmo-1b.chat`'s device time and
    56% of `moonlight-16b-a3b.gen-batch`'s."""
    from conftest import gathered_read
    from shallowspeed_tpu.serving import engine

    def table_ops(text):
        hkv, _, tail = leaf.shape[1:]
        n = rf"(?:{SLOTS},{WIDTH}|{SLOTS * WIDTH})"   # as gathered, or merged
        table = (rf"\[{n},{hkv},{BLOCK},{tail}\]|\[{n},{BLOCK},{tail}\]"
                 rf"|\[{SLOTS},{WIDTH * BLOCK},{tail}\]")
        return [l.strip()[:160] for l in text.splitlines() if re.match(
            rf"\s*(?:ROOT )?%?[\w.\-]+ = \w+(?:{table})", l)]

    text, _, leaf = _compiled_text("decode_tick", one_chip, heads)
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert not table_ops(text), "\n".join(table_ops(text))
    # what this looks for is there to be found: the same tick reading
    # through the gathered table holds it, and no kernel
    monkeypatch.setattr(engine, "paged_flash_decode", gathered_read)
    engine.clear_program_caches()
    before, _, _ = _compiled_text("decode_tick", one_chip, heads)
    engine.clear_program_caches()
    assert table_ops(before) and "tpu_custom_call" not in before


def _results(text):
    """(dtype, dimensions, line) of every instruction's result, in
    every computation."""
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (\w+)\[([\d,]*)\]", line)
        if m:
            dims = [int(d) for d in filter(None, m.group(2).split(","))]
            yield m.group(1), dims, line.strip()[:160]


@pytest.mark.parametrize("heads,widths", [
    ("mistral-7b-gqa", (256,)),             # `doc-batch`: 4,096 keys
    ("trinity-window-full", (768, 176)),    # `reason-batch`: 12 k, window
], ids=["doc-batch", "reason-batch"])
def test_prefill_chunk_reads_the_pool_through_the_kernel(one_chip, heads,
                                                         widths, monkeypatch):
    """The gate of PR 34: the chunk of 512 tokens compiled for the chip
    at the cells' table widths holds one Mosaic call a layer
    (`paged_flash_prefill`) and NO instruction, in any computation, as
    large as a group's gathered table (W x Hkv x block x hd in the
    pool's dtype, rows of hd values, however XLA merged the rest) or as
    its float32 scores (H x C x W * block): 268 MB
    a layer in `mistral-7b-v0.1.doc-batch`, three passes over them 41%
    of a chunk. It still writes the pool in place."""
    from shallowspeed_tpu.serving import engine

    def big(text):
        tables = {w * leaf.size // leaf.shape[0] for w in widths}
        scores = {n_heads * 512 * w * BLOCK for w in widths}
        return [line for dtype, dims, line in _results(text)
                if (dtype == "bf16" and math.prod(dims) in tables
                    and len(dims) > 2 and dims[-1] == leaf.shape[-1])
                or (dtype == "f32" and math.prod(dims) in scores)]

    # `reason-batch`'s window pools, 169 MB a leaf there: XLA took a
    # 42 MB leaf into its alternate memory around this chunk's kernels
    shape = dict(widths=widths, chunk=512, n_blocks=10305)
    n_heads = _HEADS[heads]["n_heads"]
    text, n_leaves, leaf = _compiled_text("prefill_chunk", one_chip, heads,
                                          **shape)
    assert text.count('custom_call_target="tpu_custom_call"') == 2
    assert not big(text), "\n".join(big(text))
    _assert_written_in_place("prefill_chunk", text, n_leaves, leaf)
    # what this looks for is there to be found: the same chunk through
    # the gathered read holds both, and no kernel
    monkeypatch.setattr(engine, "paged_prefill_addresses",
                        lambda pool, width: False)
    engine.clear_program_caches()
    before, _, _ = _compiled_text("prefill_chunk", one_chip, heads, **shape)
    engine.clear_program_caches()
    dtypes = {line.split(" = ")[1][:3] for line in big(before)}
    assert dtypes == {"bf1", "f32"} and "tpu_custom_call" not in before


@pytest.mark.parametrize("heads,slots,n_blocks,widths,ride,kernels", [
    # 8 slots, 32 k cache tokens, documents of up to 4,096 positions
    ("mistral-7b-gqa", 8, 2049, (256,), (256,), 4),
    # 32 slots, 196,608 cache tokens, prompts of up to 4,096 tokens
    # under a tick's table of `max_seq` 8,192
    ("moonlight-16b-latent", 32, 12289, (256,), (512,), 2),
], ids=["doc-batch", "gen-batch"])
def test_the_fused_chunk_keeps_both_programs_gates(one_chip, heads, slots,
                                                   n_blocks, widths, ride,
                                                   kernels):
    """The program of a step that holds a chunk (`_prefill_chunk` with
    the tick's rows riding in it), compiled for the chip at the two
    cells' shapes, is held to what the two programs it replaces are:
    every pool leaf written in place, three times a layer (the
    copy-on-write block, the chunk's blocks, the tick's rows); the
    tick's read through the kernel, so NO instruction of the gathered
    table's shape, (slots, width, Hkv, block, hd), slots and width
    merged or not, or the latent read's (slots, width * block, hd); the
    chunk's read of a K/V pool through its kernel too, so nothing as
    large as its gathered table or its float32 scores (a latent pool's
    chunk keeps the gathered read of ITS one row's table, as before)."""
    text, n_leaves, leaf = _compiled_text(
        "fused_chunk", one_chip, heads, widths=widths, chunk=512,
        n_blocks=n_blocks, slots=slots, ride=ride)
    assert text.count('custom_call_target="tpu_custom_call"') == kernels
    _assert_written_in_place("fused_chunk", text, n_leaves, leaf)
    hkv, _, tail = leaf.shape[1:]
    n_heads, w = _HEADS[heads]["n_heads"], ride[0]
    rows = {slots * w * hkv * BLOCK * tail}
    if kernels == 4:            # the chunk's table and scores, too
        rows |= {widths[0] * hkv * BLOCK * tail}
    scores = {n_heads * 512 * widths[0] * BLOCK} if kernels == 4 else set()
    big = [line for dtype, dims, line in _results(text)
           if (dtype == "bf16" and math.prod(dims) in rows
               and dims[-1] == tail and len(dims) > 2)
           or (dtype == "f32" and math.prod(dims) in scores)]
    assert not big, "\n".join(big)


@pytest.mark.parametrize("heads,width", [("moonlight-16b-latent", 512),
                                         ("olmo-1b-mha", 64)],
                         ids=["gen-batch", "chat"])
def test_the_other_chunks_keep_the_gathered_read(one_chip, heads, width):
    """`moonlight-16b-a3b`'s chunk program and `olmo-1b`'s are the
    parent's: a latent pool, and tables of at most 1,024 positions, are
    not `paged_flash_prefill`'s (`paged_prefill_addresses`)."""
    chunk, _, _ = _compiled_text("prefill_chunk", one_chip, heads,
                                 widths=(width,), chunk=512)
    assert "tpu_custom_call" not in chunk


@pytest.mark.parametrize("heads,kv_quant,kernel", [
    ("olmo-1b-mha", "int8", True),
    ("heads-of-64", "", False),
], ids=["int8-pools", "heads-of-64"])
def test_the_other_pools_compile_for_the_chip(one_chip, heads, kv_quant,
                                              kernel):
    """What no cell runs still has to compile for the chip. int8 pools
    go through the kernel (their scale planes, whose minor dimension
    Mosaic cannot slice, gathered by XLA with positions on the lanes).
    Heads that are not whole lanes wide are beyond the slab DMA
    (`paged_decode_addresses`): their tick keeps the gathered read."""
    text, _, _ = _compiled_text("decode_tick", one_chip, heads, kv_quant)
    assert text.count('custom_call_target="tpu_custom_call"') \
        == (2 if kernel else 0)


# ------------------------------------------------- the mixer's slabs
#
# `falcon-h1-34b-instruct.long-gen`'s shapes: 48 slots, 15,361 blocks,
# 20 query heads on 4 K/V heads of 128, the mixer at its published
# sizes (32 heads x 128 x 256 float32 a slot: a slab of 201 MB a layer);
# the FFN and the vocabulary cut so the compile stays seconds.
_MIXER = dict(d_model=5120, n_heads=20, n_kv_heads=4, attn_head_dim=128,
              embed_scale=5.656854, rope_theta=1e11, ssm_heads=32,
              ssm_head_dim=128, ssm_state=256, ssm_groups=2, ssm_conv=4)
_MIXER_SLOTS, _MIXER_BLOCKS = 48, 15361


def _mixer_program(program, one_chip, width):
    cfg = T.TransformerConfig(
        vocab=512, d_ff=512, n_layers=2, max_seq=8192, rope=True,
        norm="rmsnorm", ffn="swiglu", dtype=jnp.bfloat16,
        compute_dtype=jnp.bfloat16, **_MIXER)

    def spec(tree):
        return jax.tree_util.tree_map(
            lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                           sharding=one_chip), tree)

    def arr(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = spec(jax.eval_shape(lambda: T.cast_params(
        T.init(cfg, seed=0), jnp.bfloat16)))
    pools = spec(jax.eval_shape(lambda: init_block_pool(
        cfg, _MIXER_BLOCKS, BLOCK, slots=_MIXER_SLOTS)))
    i32, f32, s = jnp.int32, jnp.float32, _MIXER_SLOTS
    if program == "decode_tick":
        traced = _decode_tick.trace(
            params, pools, arr(i32, s), arr(i32, s), arr(i32, s, width),
            arr(f32, s), arr(i32, s), arr(i32, s), arr(i32, s),
            arr(jnp.bool_, s), None, cfg=cfg, top_k=0, top_p=0.0)
    else:
        traced = _prefill_chunk.trace(
            params, pools, arr(i32, 1, 512), arr(i32), arr(i32),
            arr(i32, 1, width), arr(i32), arr(i32), None, arr(i32), cfg=cfg)
    compiled = traced.lower(lowering_platforms=("tpu",)).compile()
    return compiled, pools


@pytest.mark.parametrize("program,width,kernels", [
    ("decode_tick", 256, 2),
    ("prefill_chunk", 64, 0),      # prompts of 1,024 at most: gathered
    ("prefill_chunk", 128, 2),     # a longer prompt's table: the kernel
])
def test_the_mixers_slabs_are_updated_in_place(one_chip, program, width,
                                               kernels):
    """PR 27's gate extended to the second kind of state: at the cell's
    shapes every donated leaf, slabs included, is aliased to its output;
    the ONLY instructions whose result is as large as a slab are one
    fusion a layer that takes the donated slab itself (the tick's one
    elementwise pass, fused with the readout's reduction: its new state
    never lies in HBM beside the old; the chunk's dynamic-update-slice of
    its slot's row), nothing as large as a slab is a temporary, and the
    pools are still written in place, through the kernels (20 query heads
    on 4 K/V heads: a group of 5)."""
    compiled, pools = _mixer_program(program, one_chip, width)
    text = compiled.as_text()
    slab, conv = pools[0]["ssm"], pools[0]["conv"]
    assert slab.shape == (48, 32, 128, 256) and slab.dtype == jnp.float32
    assert conv.shape == (48, 3, 5120)
    shape = "f32[" + ",".join(map(str, slab.shape)) + "]"
    comps = _computations(text)
    writes = []
    for line in comps["ENTRY"]:
        # `name = type op(...)`, the type one array or a tuple of them
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([\w\-]+)\(", line)
        if m and shape in m.group(1) and m.group(2) not in _SKIP_OPS:
            writes.append(line.strip())
    assert len(writes) == 2, "\n".join(w[:200] for w in writes)
    for w in writes:
        assert " fusion(" in w and "pools_" in w and "ssm" in w, w[:300]
        if program == "prefill_chunk":
            root = next(l for l in comps[re.search(
                r"calls=%?([\w.\-]+)", w).group(1)] if "ROOT" in l)
            assert " dynamic-update-slice(" in root, root[:200]
    # no copy of a slab in any computation, and no slab-sized scratch
    assert not [l for l in text.splitlines()
                if re.search(r" = " + re.escape(shape) + r"\S* copy", l)]
    assert compiled.memory_analysis().temp_size_in_bytes < slab.size * 4
    assert text.count('custom_call_target="tpu_custom_call"') == kernels
    kv = pools[0]["k"]
    _assert_written_in_place(
        program, text, 4, kv,
        n_donated=len(jax.tree_util.tree_leaves(pools)))
