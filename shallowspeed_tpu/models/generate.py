"""Autoregressive decoding with a KV cache for the transformer LM family.

The reference's only inference surface is a forward-only pipeline schedule
over the MLP (`/root/reference/shallowspeed/pipe.py:275-294`); sequence
models need real decoding. Designed TPU-first:

- **Static shapes.** The KV cache is a fixed head-major
  (B, Hkv, cache_len, hd) buffer per block (sized to prompt bucket +
  max_new, not max_seq); the decode loop is one `lax.scan` over
  `max_new` steps — the whole generation compiles to a single XLA
  program, no per-token Python dispatch or retracing.
- **Parallel prefill.** The prompt runs through the normal batched
  forward (`_block(..., with_kv=True)` captures each block's K/V in one
  MXU-friendly pass); only the new tokens decode sequentially.
- **f32 score path.** Decode attention accumulates scores in f32 with a
  position mask over the not-yet-written cache tail, matching
  `ops/attention.py` numerics, so cached decoding reproduces the batched
  forward's logits exactly (tested to 1e-4).

Sampling: temperature (0 = greedy argmax), optional top-k truncation and/or
nucleus (top-p) filtering, with `jax.random` counter-based keys —
reproducible given a seed.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from shallowspeed_tpu.models import transformer as T
from shallowspeed_tpu.models.kv_cache import (cache_write, cached_attention,
                                              init_kv_cache, quantize_kv)

# Round-11 refactor: the cache primitives moved to `models/kv_cache.py`
# so the serving runtime (`shallowspeed_tpu/serving/` — paged block
# pools) shares the exact write/quantize/attend math with this
# contiguous path. Old private names kept as aliases — the ops are
# UNCHANGED, so every pinned stream stays bit-identical.
_quantize_kv = quantize_kv
_cache_write = cache_write
_cached_attention = cached_attention


def _block_decode(p, x, cfg: T.TransformerConfig, cache_blk, pos,
                  spec=None):
    """One block on a single-token slice x (B, 1, d); writes this token's
    K/V at `pos` and attends over the cache. `spec` is the layer's
    (window, rotary) of `cfg.layer_specs` (None: the one of a uniform model).
    Returns (x, cache_blk)."""
    b = x.shape[0]
    window, rotary = spec or (cfg.window, cfg.rope)
    h = T._norm(p["ln1"], x, cfg)
    q, k, v = T._qkv(p, h, cfg)
    if rotary:  # rotate at this token's position; cache stores rotated K
        q = T.rope_rotate(q, pos, cfg.rope_theta)
        k = T.rope_rotate(k, pos, cfg.rope_theta)
    cache_blk = _cache_write(cache_blk, k, v, pos)
    a = _cached_attention(q, cache_blk, pos, cfg, window).reshape(b, 1, -1)
    x = T.attn_residual(p, x, a, h, cfg)
    if "mixer" in p:    # the same normed input, its own carried state
        y, left = T.mixer(p["mixer"], h, cfg, cache_blk)
        x, cache_blk = x + y, {**cache_blk, **left}
    h = T._norm(p["ln2"], x, cfg)
    x, _aux = T._ffn(p, x, cfg, h)
    return x, cache_blk


def _embed(params, tokens, pos0, cfg):
    t = tokens.shape[1]
    pos = pos0 + jnp.arange(t)
    x = T.embed_tokens(params, tokens, cfg)
    if not cfg.rope:  # rope replaces the learned absolute embedding
        x = x + params["pos_emb"][pos]
    if cfg.compute_dtype is not None:
        x = x.astype(cfg.compute_dtype)
    return x


def prefill(params, tokens, cfg: T.TransformerConfig, cache,
            last_idx=None, attn_impl: str = "xla"):
    """Batched forward over the prompt, capturing each block's K/V.

    tokens: (B, Tp). Returns (logits (B, vocab) in f32 at `last_idx`
    — default Tp-1; a TRACED index when the prompt is right-padded to
    a bucket length and the true last token sits earlier — and the
    filled cache). With padding, cache slots in [last_idx+1, Tp) hold
    pad-token garbage, but decode OVERWRITES slot p before reading it
    (the position mask admits only slots <= p), so the garbage is
    never consumed.

    `attn_impl="flash"` runs the blockwise Pallas kernel instead of
    XLA attention — long prompts OOM on the (B, H, Tp, Tp) f32 score
    materialization (an 8k b8 h16 prompt wants 32 GB of scores; the
    kernel streams tiles). `generate` auto-selects it at or past 2048
    prompt tokens (when the tile size survives the length)."""
    params = T.cast_params(params, cfg.compute_dtype)
    tp = tokens.shape[1]
    if cfg.attn_dropout > 0.0:
        # inference never drops (key=None makes it inert), but the
        # block's substrate-capability assert keys off cfg alone — a
        # model TRAINED with attn dropout must still prefill on any
        # substrate
        from dataclasses import replace as _replace

        cfg = _replace(cfg, attn_dropout=0.0)
    x = _embed(params, tokens, 0, cfg)
    if attn_impl == "flash":
        from shallowspeed_tpu.ops.flash_attention import flash_attention as fn
    else:
        fn = T.attention
    pos = jnp.arange(tp)
    for i, blk in enumerate(params["blocks"]):
        window, rotary = cfg.layer_specs[i]
        # a block with a mixer also leaves the mixer's state after the
        # prompt's last TRUE token (the bucket's padding changes none)
        x, _aux, (k, v, *left) = T._block(
            blk, x, cfg, partial(fn, causal=True, window=window),
            with_kv=True, pos=pos, rotary=rotary,
            n_tok=None if last_idx is None else last_idx + 1)
        cache[i] = {**_cache_write(cache[i], k, v, 0), **dict(*left)}
    x = T._norm(params["ln_f"], x, cfg)
    if last_idx is None:
        x_last = x[:, tp - 1]
    else:
        x_last = jax.lax.dynamic_index_in_dim(x, last_idx, 1, False)
    logits = T.head_logits(params, x_last, cfg)
    return logits.astype(jnp.float32), cache


def decode_step(params, token, pos, cache, cfg: T.TransformerConfig):
    """One cached decode step. token: (B,) int32 at position `pos`
    (traced scalar). Returns (logits (B, vocab) f32, updated cache).

    Callers in a loop should pre-cast params (`T.cast_params`) once; the
    cast here is then a same-dtype identity."""
    params = T.cast_params(params, cfg.compute_dtype)
    x = _embed(params, token[:, None], pos, cfg)
    new_cache = []
    for blk, cblk, spec in zip(params["blocks"], cache, cfg.layer_specs):
        x, cblk = _block_decode(blk, x, cfg, cblk, pos, spec)
        new_cache.append(cblk)
    x = T._norm(params["ln_f"], x, cfg)
    logits = T.head_logits(params, x[:, 0], cfg)
    return logits.astype(jnp.float32), new_cache


def filter_logits(logits, top_k: int, top_p: float):
    """Row-wise top-k then nucleus (top-p) support truncation on
    temperature-scaled logits (B, V). Shared by `_sample` and the
    serving engine's per-row sampler — ONE implementation, so the
    pinned serving-vs-`generate()` stream parity cannot drift on
    filtered runs."""
    if top_k > 0:
        kth = jax.lax.top_k(logits, top_k)[0][:, -1:]       # (B, 1)
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if 0.0 < top_p < 1.0:
        # nucleus: keep the smallest prefix of the sorted distribution
        # whose mass reaches top_p (the first token always survives)
        sort_idx = jnp.argsort(-logits, axis=-1)
        sorted_logits = jnp.take_along_axis(logits, sort_idx, axis=-1)
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep_sorted = (cum - probs) < top_p      # mass BEFORE this token
        keep = jnp.zeros_like(keep_sorted).at[
            jnp.arange(logits.shape[0])[:, None], sort_idx].set(keep_sorted)
        logits = jnp.where(keep, logits, -jnp.inf)
    return logits


def _sample(logits, rng, temperature: float, top_k: int,
            top_p: float = 0.0):
    """logits (B, V) f32 -> token ids (B,). temperature 0 = greedy;
    top_k and top_p (nucleus) filters compose (k first, then p)."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = filter_logits(logits / temperature, top_k, top_p)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


# ------------------------------------------------- decode HBM roofline
#
# BASELINE.md established (round 4) that decode is HBM-bandwidth-bound
# at ~800 GB/s on this chip: every step sweeps the KV cache and the
# weights once. These helpers surface that as a LIVE number on decode
# progress lines (tokens/sec x bytes/token vs the chip's HBM roofline,
# `flops.device_mem_bandwidth`) instead of an offline claim. The byte
# model is pinned against the traced decode program's own input-buffer
# bytes (analysis/walker.aval_bytes) in tests/test_generate.py.


def decode_read_bytes_per_token(params, cfg: T.TransformerConfig,
                                batch: int, cache_len: int,
                                kv_quant: str = "") -> int:
    """HBM READ bytes one decode step moves: every param leaf (at the
    dtype decode actually reads after `cast_params`) plus every
    block's full K/V cache sweep (+ int8 scale rows), plus the token
    ids. Equals the summed input-buffer bytes of the traced
    `decode_step` program by construction — the walker pin."""
    import numpy as np

    from shallowspeed_tpu.analysis.walker import aval_bytes

    # eval_shape: the byte count needs only the casted avals, not a
    # full on-device copy of the model in compute dtype
    cast = jax.eval_shape(lambda p: T.cast_params(p, cfg.compute_dtype),
                          params)
    p_bytes = int(sum(aval_bytes(l) for l in
                      jax.tree_util.tree_leaves(cast)))
    kv_itemsize = (1 if kv_quant == "int8"
                   else np.dtype(cfg.compute_dtype or cfg.dtype).itemsize)
    per_block = 2 * batch * cfg.kv_heads * cache_len * cfg.head_dim \
        * kv_itemsize
    if kv_quant == "int8":
        per_block += 2 * batch * cfg.kv_heads * cache_len * 4  # f32 scales
    tok_bytes = batch * 4  # int32 token ids
    return p_bytes + cfg.n_layers * per_block + tok_bytes


def decode_write_bytes_per_token(cfg: T.TransformerConfig, batch: int,
                                 kv_quant: str = "") -> int:
    """HBM WRITE bytes per decode step: the one-token K/V cache update
    per block (+ scales) and the logits row — O(1/cache_len) of the
    read sweep, reported for completeness."""
    import numpy as np

    kv_itemsize = (1 if kv_quant == "int8"
                   else np.dtype(cfg.compute_dtype or cfg.dtype).itemsize)
    per_block = 2 * batch * cfg.kv_heads * cfg.head_dim * kv_itemsize
    if kv_quant == "int8":
        per_block += 2 * batch * cfg.kv_heads * 4
    return cfg.n_layers * per_block + batch * cfg.vocab * 4


def decode_report(params, cfg: T.TransformerConfig, batch: int,
                  cache_len: int, n_tokens: int, seconds: float,
                  kv_quant: str = "") -> dict:
    """Decode progress-line fields for a timed generation: tokens/sec,
    the analytic bytes/token, the implied HBM sweep rate, and — when
    the chip's HBM peak is known — the roofline utilization. Off-TPU
    `hbm_util` is None (no invented peak), matching flops.mfu's
    convention."""
    from shallowspeed_tpu.flops import device_mem_bandwidth

    if seconds <= 0 or n_tokens <= 0:
        # typed, not an assert (asserts vanish under python -O and this
        # guards a division on a production progress line)
        raise ValueError(f"decode_report needs seconds > 0 and "
                         f"n_tokens > 0, got seconds={seconds!r}, "
                         f"n_tokens={n_tokens!r}")
    steps_per_sec = n_tokens / seconds          # decode steps (all rows)
    bpt = (decode_read_bytes_per_token(params, cfg, batch, cache_len,
                                       kv_quant)
           + decode_write_bytes_per_token(cfg, batch, kv_quant))
    gbps = steps_per_sec * bpt / 1e9
    peak = device_mem_bandwidth()
    return {
        "tokens_per_sec": round(steps_per_sec * batch, 1),
        "steps_per_sec": round(steps_per_sec, 2),
        "bytes_per_token": int(bpt),
        "hbm_gbps": round(gbps, 4),
        "hbm_peak_gbps": None if peak is None else round(peak / 1e9, 1),
        "hbm_util": None if peak is None else round(gbps * 1e9 / peak,
                                                    4),
    }


FLASH_PREFILL_THRESHOLD = 2048
"""Prompt-BUCKET length at which `generate` switches the prefill from
XLA attention to the flash kernel (long prompts OOM on the (B, H, Tp,
Tp) f32 score materialization). Flash numerics differ at the ~1e-6
level, so sampled token streams across the switch are NOT bit-identical
— callers who need cross-length stream stability pin
`flash_prefill_at` in `generate` instead of relying on the default."""


@partial(jax.jit, static_argnames=("cfg", "max_new", "temperature",
                                   "top_k", "top_p", "cache_len",
                                   "kv_quant", "flash_prefill_at"))
def _generate_padded(params, prompt, tp_actual, cfg: T.TransformerConfig,
                     max_new: int, temperature: float, top_k: int,
                     top_p: float, seed, cache_len: int,
                     kv_quant: str = "",
                     flash_prefill_at: int = FLASH_PREFILL_THRESHOLD):
    """The compiled generation core on a BUCKET-padded prompt (B, Tp_b):
    `tp_actual` is the TRACED true prompt length, so every prompt in the
    same (Tp_b, max_new, sampler) bucket reuses one executable. The KV
    cache is `cache_len` = Tp_b + max_new slots — sized to the
    generation, not cfg.max_seq. One program: parallel prefill + a
    `lax.scan` decode loop over the static step count."""
    b = prompt.shape[0]
    params = T.cast_params(params, cfg.compute_dtype)  # once, not per step
    cache = init_kv_cache(cfg, b, cache_len, kv_quant)
    # long prompts stream the prefill through the flash kernel (the
    # XLA path materializes (B, H, Tp, Tp) f32 scores); prompts that
    # bucket BELOW the threshold keep the XLA path, so their streams
    # stay bit-identical to earlier rounds. Guard the tile size too: a
    # non-power-of-two length shrinks the Pallas block toward 1 (a
    # silent performance cliff worse than the OOM it avoids).
    from shallowspeed_tpu.ops.flash_attention import _pick_block

    attn_impl = ("flash" if flash_prefill_at > 0
                 and prompt.shape[1] >= flash_prefill_at
                 and _pick_block(prompt.shape[1], 512) >= 128
                 else "xla")
    logits, cache = prefill(params, prompt, cfg, cache,
                            last_idx=tp_actual - 1,
                            attn_impl=attn_impl)
    rng0 = jax.random.PRNGKey(seed)
    tok0 = _sample(logits, jax.random.fold_in(rng0, 0), temperature,
                   top_k, top_p)

    # sample-after-decode: the final sampled token never triggers another
    # (discarded) decode pass — exactly max_new - 1 decode steps run.
    # Decode position tp_actual + i OVERWRITES its (pad-garbage) cache
    # slot before the position mask can admit it (see prefill).
    def step(carry, i):
        tok_prev, cache = carry
        logits, cache = decode_step(params, tok_prev, tp_actual + i,
                                    cache, cfg)
        tok = _sample(logits, jax.random.fold_in(rng0, i + 1),
                      temperature, top_k, top_p)
        return (tok, cache), tok

    (_, _), toks = jax.lax.scan(step, (tok0, cache),
                                jnp.arange(max_new - 1))
    return jnp.concatenate([tok0[None], toks], axis=0).T  # (B, max_new)


def prompt_bucket_len(tp: int, max_new: int, max_seq: int,
                      bucket: int = 64) -> int:
    """Round the prompt length up to a `bucket` multiple (capped so the
    bucket + generation still fit max_seq) — the compile key for
    `generate`, shared with the pipelined decode."""
    tp_b = ((tp + bucket - 1) // bucket) * bucket
    return max(tp, min(tp_b, max_seq - max_new))


def generate(params, prompt, cfg: T.TransformerConfig, max_new: int,
             temperature: float = 1.0, top_k: int = 0,
             top_p: float = 0.0, seed=0, kv_quant: str = "",
             flash_prefill_at: int = FLASH_PREFILL_THRESHOLD):
    """Generate `max_new` tokens after `prompt` (B, Tp). Returns
    (B, max_new) int32.

    Compile hygiene (round 4, VERDICT r3): the prompt is right-padded
    to a 64-token bucket and its true length is passed traced, so
    same-bucket prompts of different lengths share ONE executable
    (previously every Tp recompiled); the KV cache holds
    bucket + max_new slots, not max_seq. Token streams are identical
    to the unpadded form — the pad slots are overwritten before the
    position mask can admit them.

    **Stream-stability contract.** For a fixed (seed, sampler, weights)
    the token stream is reproducible across runs and prompt paddings,
    with two documented exceptions: (1) prompts whose 64-token BUCKET
    reaches `flash_prefill_at` (default 2048) prefill through the flash
    kernel, whose numerics differ from XLA attention at the ~1e-6
    logit level — so streams are bit-stable WITHIN each regime but not
    across the switch. Callers needing one numerics regime for every
    length pin it: `flash_prefill_at=0` disables the auto-switch (XLA
    everywhere — long prompts then pay the (B, H, Tp, Tp) f32 score
    materialization), any other value moves the boundary. (2)
    `kv_quant="int8"` (round 5): quantized KV cache — halves the
    cache-sweep bytes for batched long-context decode at a small
    numerics cost (per-head absmax scales; logits move at the ~1e-2
    level, so streams are NOT bit-identical to the bf16 cache). (3)
    PAGED decode (round 11, `shallowspeed_tpu/serving/`): the serving
    engine reads the same cache math through a gathered block table
    (`models/kv_cache.masked_attention` is the shared core) with the
    same per-request sampling keys (`fold_in(PRNGKey(seed),
    token_index)`) — but its table width is bucketed in BLOCKS, not
    this path's 64-token prompt bucket, so the softmax reduction
    shape differs and paged logits match this path to ~1e-6 (pinned
    <= 1e-4), NOT bit-exactly. In practice sampled streams coincide
    (tests/test_serving.py pins solo-request streams token-for-token
    against this function, greedy and sampled); callers needing a
    guaranteed-bit-stable stream must stay on ONE of the two paths."""
    b, tp = prompt.shape
    assert tp + max_new <= cfg.max_seq, (
        f"prompt {tp} + max_new {max_new} exceeds max_seq={cfg.max_seq}")
    # jnp.asarray on BOTH branches (round 11): the no-padding branch
    # used to hand the caller's raw array straight to jit while the
    # padded branch converted — dtype/device normalization differed by
    # prompt LENGTH (e.g. int64 host arrays weak-typing differently),
    # a shape-dependent input regime
    prompt = jnp.asarray(prompt)
    tp_b = prompt_bucket_len(tp, max_new, cfg.max_seq)
    if tp_b != tp:
        prompt = jnp.pad(prompt, ((0, 0), (0, tp_b - tp)))
    return _generate_padded(params, prompt, jnp.int32(tp), cfg, max_new,
                            temperature, top_k, top_p, seed,
                            cache_len=tp_b + max_new,
                            kv_quant=kv_quant,
                            flash_prefill_at=flash_prefill_at)
