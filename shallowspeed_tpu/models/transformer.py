"""Decoder-only transformer LM — the long-context model family.

The reference's model zoo is a single attention-free MLP
(`/root/reference/shallowspeed/layers.py:236-270`); this family extends the
framework to sequence models, designed TPU-first from the start:

- Pure-functional: `init(rng) -> params pytree`, `forward(params, tokens) ->
  logits`, `loss(params, tokens, targets)`; autograd is `jax.grad` (no
  hand-written VJPs here — the MLP family keeps those for reference parity,
  this family uses the idiomatic JAX transform).
- The attention implementation is pluggable: the same block runs full
  `attention` on one device or `ring_attention` over a sequence-sharded mesh
  axis (`shallowspeed_tpu/ops/attention.py`) — which is what makes context
  parallelism a property of the *mesh*, not of the model code.
- Pre-LN blocks, GELU MLP (4x), learned positional embeddings, weight-tied
  head kept separate (untied) for sharding simplicity; all matmul-heavy, so
  every FLOP lands on the MXU. bfloat16-friendly: compute dtype is a config
  knob, accumulations stay float32 inside attention.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name as _checkpoint_name

from shallowspeed_tpu.ops.attention import attention
from shallowspeed_tpu.ops.latent_attention import latent_attention
from shallowspeed_tpu.ops import ssm
from shallowspeed_tpu.ops.moe import moe_ffn, routed_experts_ffn


@dataclass(frozen=True)
class TransformerConfig:
    vocab: int = 256
    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 2
    max_seq: int = 1024
    dtype: np.dtype = np.float32
    # Mixed precision: params stay in `dtype` (float32 master weights, and
    # the optimizer state with them); the forward pass casts them — and all
    # activations — to `compute_dtype` so matmuls run as bf16 MXU passes.
    # Stability-critical reductions stay float32 no matter what: layernorm
    # statistics, attention scores/softmax (`ops/attention.py`), the MoE
    # router (`ops/moe.py`), and the final log-softmax in `loss`. Gradients
    # come out float32 (the transpose of the param cast converts back).
    # None = compute in the param dtype (pure float32 training).
    compute_dtype: object = None
    # Rematerialization: recompute each block's activations in the backward
    # instead of storing them (jax.checkpoint around every block). Trades
    # ~1 extra forward of FLOPs for O(n_layers) -> O(1) activation memory —
    # the standard long-context lever on HBM-bound TPUs.
    remat: bool = False
    # What the per-block checkpoint SAVES (only read when remat=True):
    # - "full": save nothing, recompute the whole block (max memory saving,
    #   +~1 forward of FLOPs — the round-2 behavior).
    # - "attn": save each block's attention output (tagged "attn_out"
    #   below) — the backward replays the cheap projections/FFN but never
    #   re-runs the attention substrate (the flash kernel's forward is the
    #   expensive, bandwidth-bound part of the replay). +(B,T,d) bf16 per
    #   block.
    # - "dots": save every matmul output AND the attention output;
    #   backward recomputes only elementwise ops (norms, gelu/silu,
    #   rotary). Near-zero recompute FLOPs at ~14*d bytes/token per block
    #   — the right point when activations fit (e.g. microbatched big
    #   models); "full" remains the extreme-length fallback.
    remat_policy: str = "full"
    # Rotary position embeddings (Su et al., RoFormer): rotate q/k by
    # per-position phases inside every block instead of adding a learned
    # absolute embedding (pos_emb is kept in the pytree for structural
    # stability across engines but NOT added when rope is on). Positions
    # are global, so RoPE composes with sequence sharding unchanged: each
    # device rotates its local q/k block by its global positions before
    # the ring/all-to-all ever moves K.
    rope: bool = False
    rope_theta: float = 10000.0
    # Block options: normalization ("layernorm" | "rmsnorm") and dense FFN
    # flavor ("gelu" | "swiglu"). SwiGLU adds a "gate" projection per block
    # (column-sharded like "up" under tensor parallelism); MoE configs
    # (n_experts > 0) replace the dense FFN entirely and ignore `ffn`.
    norm: str = "layernorm"
    ffn: str = "gelu"
    # Grouped-query attention (Ainslie et al., GQA): n_kv_heads < n_heads
    # K/V heads, each shared by a group of n_heads/n_kv_heads query heads.
    # 0 = plain multi-head attention (the fused qkv projection). With GQA
    # the projection splits into "q" and "kv" params; K/V are repeated to
    # the full head count right before the attention op (so every
    # attention substrate works unchanged), but the decode KV cache stores
    # the UNREPEATED heads — its memory shrinks by the group factor.
    n_kv_heads: int = 0
    # Mixture-of-experts (0 = dense FFN everywhere). With n_experts > 0 every
    # block's FFN becomes a top-k routed MoE (`ops/moe.py`) — the family the
    # reference lacks entirely (SURVEY §2: EP absent).
    n_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 2.0
    moe_aux_weight: float = 1e-2
    # MoE slot-assignment order: "sequence" (GShard: earlier tokens claim
    # an overflowing expert's slots) or "priority" (V-MoE batch-priority:
    # highest-gate assignments claim slots — drops hit the router's
    # least-confident choices instead of late-sequence tokens).
    moe_routing: str = "sequence"
    # Router z-loss weight (ST-MoE): penalizes router-logit magnitude —
    # the standard stabilizer for long MoE runs. 0 = off (default, so
    # existing trajectories are bit-unchanged); 1e-3 is the usual value.
    # Independent of moe_aux_weight (z-loss-only configs are fine).
    moe_z_weight: float = 0.0
    # Weight tying (Press & Wolf): the output head reuses tok_emb^T
    # instead of its own (vocab, d) matrix — the params pytree simply has
    # no "head" entry, so every engine's placement/checkpoint logic stays
    # structural. Standard for small/medium LMs; halves embedding memory.
    tie_embeddings: bool = False
    # Label smoothing (Szegedy et al.): mix the one-hot target with the
    # uniform distribution — loss = (1-ls)*NLL + ls*mean(-logp).
    label_smoothing: float = 0.0
    # Sliding-window (local) attention, Mistral-style: position i sees
    # only [i - attn_window + 1, i]. 0 = full causal attention. Composes
    # with GQA/rope/remat and the XLA-attention engines (plain dp, the
    # GSPMD family, the pipeline); the fused/resharded substrates
    # (flash, ring, ulysses) reject it. The decode cache applies the
    # same window, so sampling sees the trained distribution.
    attn_window: int = 0
    # Final-logit soft-capping (Gemma 2): logits <- cap*tanh(logits/cap)
    # bounds the head's output, taming loss spikes late in training.
    # Applied wherever head logits are produced (training loss AND
    # decode), so sampling sees the distribution that was trained.
    # 0 = off; Gemma 2 uses 30.0.
    logit_softcap: float = 0.0
    # Dropout rate on the embedding sum, each attention output, and each
    # FFN output (GPT-2 placement; attention-probability dropout is
    # deliberately omitted — it would not compose with the fused
    # flash/ring substrates). Active only when a `dropout_key` is
    # threaded into the forward: training steps pass a per-step key,
    # eval/decode paths pass None, so train/eval mode is a property of
    # the CALL, not of mutable model state (contrast the reference's
    # `Module.train()/eval()` flag, `layers.py:56-64`). Keys are derived
    # deterministically from (step, microbatch, layer), which makes the
    # masks reproducible under remat and 1F1B vjp recompute.
    dropout: float = 0.0
    # ATTENTION-PROBABILITY dropout (the classic pre-AV-matmul mask —
    # round-2 deliberately shipped only projection-output dropout and
    # the verdict flagged the silent semantics gap). Supported on the
    # plain XLA attention substrate only; configs selecting a fused or
    # resharded substrate (flash/ring at sp>1/ulysses/pipeline) are
    # rejected at build time rather than silently ignoring the rate.
    # Same train/eval contract as `dropout`: active only when a
    # dropout_key is threaded in.
    attn_dropout: float = 0.0
    # FFN hidden width; 0 = the classic 4*d_model. One knob shared by
    # init, the forward, and the FLOPs accounting (`flops.py`) so the
    # three can never drift.
    d_ff: int = 0
    # Chunked (blockwise) cross-entropy: compute the loss in chunks of
    # this many token positions, rematerializing each chunk's logits in
    # the backward — the (B*T, vocab) logits/log-probabilities are never
    # materialized or stored at once. 0 = classic whole-batch
    # log-softmax. Essential for large-vocab configs: at vocab 32k,
    # B*T=8k the classic path writes a ~1GB f32 log-prob residual;
    # chunked keeps O(chunk * vocab) transients only.
    xent_chunk: int = 0
    # fp8-e4m3 forward matmuls (round 18 — ROADMAP item 5's runtime
    # rung reaching the transformer): every dense projection (qkv/
    # q+kv, proj, up/down/gate, the untied head) runs
    # `ops.matmul.fp8_dense` — activations quantized with a
    # just-in-time per-tensor stop_gradient scale, weights with the
    # per-out-channel scale, f32 accumulation, straight-through
    # backward. Embeddings, norms and MoE banks stay in compute_dtype
    # (same exclusions as `quantize_weights`).
    fp8_dense: bool = False
    # Multi-head latent attention (DeepSeek-V2's MLA; 0 = off). Keys and
    # values of a token are ONE shared row of `kv_lora_rank` values (a
    # joint down-projection, RMS-normed) plus `qk_rope_head_dim` rotary
    # key dimensions shared by every head; an up-projection (`kv_b`)
    # expands the row to `n_heads` x (`qk_nope_head_dim` keys |
    # `v_head_dim` values). Queries are `n_heads` x (`qk_nope_head_dim` |
    # `qk_rope_head_dim`), so no head size follows from d_model / n_heads.
    # The serving cache stores the latent row itself (`serving/cache.py`)
    # and reads it in the absorbed form (`ops/latent_attention.py`).
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # Dropless routed experts with shared experts (DeepSeek-V3's layer;
    # 0 = off): layers `first_dense_layers`.. replace the dense FFN by
    # `n_routed_experts` SwiGLU experts of width `expert_d_ff`, of which
    # each token takes `moe_top_k` (sigmoid scores, chosen on score +
    # bias, weighted by the scores alone, normalised and scaled by
    # `routed_scaling_factor`; `ops/moe.py:sigmoid_topk_routing`), plus
    # one always-on SwiGLU of width `n_shared_experts * expert_d_ff`.
    # No capacity, no dropped assignment; `n_experts` (above) stays the
    # capacity-routed training layer.
    n_routed_experts: int = 0
    n_shared_experts: int = 0
    expert_d_ff: int = 0
    routed_scaling_factor: float = 1.0
    first_dense_layers: int = 0
    # The layers' attention pattern, where they differ: one (window,
    # rotary) pair a layer, window 0 = every earlier token, rotary False
    # = queries and keys of that layer are not rotated at all; the
    # windowed layers share one window size. Empty = every layer is
    # (attn_window, rope): those two are what a constructor of a
    # uniform model writes, and NOTHING reads `attn_window` but
    # `layer_specs`. Whoever needs a layer's window or rotation reads
    # `layer_specs` (the forward pass without a cache, `generate()`,
    # the serving engine, whose cache groups the layers by window:
    # `serving/cache.py:layer_groups`); code that takes one window for
    # the whole model (the training engines' attention substrates, the
    # FLOP count) reads `window`, which refuses a pattern.
    layers: tuple = ()
    # A head's size where it is not d_model / n_heads (0): queries are
    # then n_heads x attn_head_dim wide and `proj` maps that back to
    # d_model. Read through `head_dim`.
    attn_head_dim: int = 0
    # The token embedding is multiplied by this on its way in (a muP
    # model's sqrt(d_model)); 1 = as gathered.
    embed_scale: float = 1.0
    # A state-space mixer BESIDE the attention heads of every block
    # (`ops/ssm.py`; 0 heads = none): both read the block's one normed
    # input and their outputs are summed onto the residual stream. Its
    # inner stream is `ssm_heads` x `ssm_head_dim` wide, each head keeps
    # a (`ssm_head_dim`, `ssm_state`) float32 matrix from token to token,
    # the heads of one of `ssm_groups` groups share their B and C, and
    # a causal depthwise convolution of width `ssm_conv` runs ahead of
    # the recurrence. Serving only: the training engines refuse it
    # (`trainable`), no backward of the scan is measured.
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv: int = 4

    def __post_init__(self):
        assert self.norm in ("layernorm", "rmsnorm"), self.norm
        assert self.ffn in ("gelu", "swiglu"), self.ffn
        assert self.moe_routing in ("sequence", "priority"), \
            self.moe_routing
        assert self.remat_policy in ("full", "attn", "dots"), \
            self.remat_policy
        assert self.xent_chunk >= 0, self.xent_chunk
        assert 0.0 <= self.dropout < 1.0, self.dropout
        assert 0.0 <= self.attn_dropout < 1.0, self.attn_dropout
        assert 0.0 <= self.label_smoothing < 1.0, self.label_smoothing
        assert self.attn_window >= 0, self.attn_window
        assert self.n_kv_heads >= 0, (
            f"n_kv_heads must be non-negative, got {self.n_kv_heads}")
        assert self.n_heads % self.kv_heads == 0, (
            f"n_heads={self.n_heads} must be divisible by "
            f"n_kv_heads={self.kv_heads}")
        if self.latent:
            assert self.rope and not self.gqa and not self.attn_window, (
                "latent attention carries its own rotary dimensions and "
                "shares one latent row across heads: it needs rope=True "
                "and takes neither n_kv_heads nor attn_window")
            assert self.qk_rope_head_dim % 2 == 0 and self.v_head_dim > 0 \
                and self.qk_nope_head_dim > 0, "latent head sizes unset"
        if self.layers:
            assert len(self.layers) == self.n_layers and self.rope \
                and not self.attn_window and not self.latent, (
                    "a layer pattern names every layer, and replaces "
                    "attn_window; its model has no learned positions "
                    "(rope=True) and no latent attention")
            assert all(w >= 0 for w, _ in self.layers) and len(
                {w for w, _ in self.layers if w}) <= 1, (
                    f"windowed layers share one window size: {self.layers}")
        if self.ssm_heads:
            assert self.ssm_head_dim > 0 and self.ssm_state > 0 \
                and self.ssm_conv > 1 \
                and self.ssm_heads % self.ssm_groups == 0 \
                and not self.latent, (
                    "a mixer's head size and state size are set, its "
                    "groups divide its heads, and its block's attention "
                    "is K/V heads")
        if self.n_routed_experts:
            assert not self.n_experts, (
                "n_routed_experts (dropless) and n_experts (capacity "
                "routing) are two different layers; pick one")
            assert 0 < self.moe_top_k <= self.n_routed_experts \
                and self.expert_d_ff > 0, "routed expert sizes unset"
        # typed, not an assert: this gates a production precision mode
        if self.fp8_dense and _FP8_DTYPE is None:
            raise ValueError(
                "fp8_dense=True needs float8_e4m3fn support in this "
                "jax/XLA build; train in bf16/f32 instead")

    @property
    def head_dim(self) -> int:
        if self.attn_head_dim:
            return self.attn_head_dim
        assert self.d_model % self.n_heads == 0
        return self.d_model // self.n_heads

    @property
    def ffn_dim(self) -> int:
        return self.d_ff or 4 * self.d_model

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def gqa(self) -> bool:
        return self.kv_heads != self.n_heads

    @property
    def latent(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def latent_width(self) -> int:
        """Values one token leaves in one latent layer's cache."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def routed_layer(self, i: int) -> bool:
        return self.n_routed_experts > 0 and i >= self.first_dense_layers

    @property
    def mixer(self) -> bool:
        """Whether every block holds a state-space mixer."""
        return self.ssm_heads > 0

    @property
    def trainable(self) -> "TransformerConfig":
        """This config, for code that builds a train step (`loss`, and
        the engines that build theirs without it, where they take their
        config): refuses a model with no measured backward pass."""
        assert not self.mixer, (
            "a model with a state-space mixer is served only: the "
            "training engines have no backward pass of its scan")
        return self

    @property
    def layer_specs(self) -> tuple:
        """(window, rotary) of every layer: THE per-layer spec."""
        return tuple((int(w), bool(r)) for w, r in self.layers) \
            or ((self.attn_window, self.rope),) * self.n_layers

    @property
    def window(self) -> int:
        """The window of a model whose layers all share one spec, for
        code that takes one window a model."""
        assert len(set(self.layer_specs)) == 1, (
            f"one window and one rotation for the whole model here; the "
            f"layers differ: {self.layers}")
        return self.layer_specs[0][0]


def _dense_init(rng, in_d, out_d, dtype):
    w = rng.normal(0.0, 1.0 / np.sqrt(in_d), (in_d, out_d)).astype(dtype)
    return {"W": w, "b": np.zeros((out_d,), dtype)}


BLOCK_PARTS = ("qk_norm", "attn_gate", "post_norm")


def _mixer_init(rng, cfg: "TransformerConfig", dtype):
    """A block's mixer as Mamba-2 initialises it: `A_log` the log of
    U(1, 16), `dt_bias` the inverse softplus of step sizes log-uniform
    in [1e-3, 1e-1], `d_skip` 1, the convolution U(-1/2, 1/2) a tap
    (1 / sqrt(width)) with no bias to start from; these set how fast a
    state forgets, and zeros or ones would make the recurrence trivial.
    The three per-head vectors stay float32 whatever `dtype` is."""
    h, k = cfg.ssm_heads, cfg.ssm_conv
    step = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), h))
    bound = 1.0 / np.sqrt(k)
    return {
        "in_proj": _dense_init(rng, cfg.d_model, ssm.proj_dim(cfg), dtype),
        "conv_w": rng.uniform(-bound, bound,
                              (k, ssm.conv_dim(cfg))).astype(dtype),
        "conv_b": np.zeros((ssm.conv_dim(cfg),), dtype),
        "A_log": np.log(rng.uniform(1.0, 16.0, h)).astype(np.float32),
        "dt_bias": (step + np.log(-np.expm1(-step))).astype(np.float32),
        "d_skip": np.ones((h,), np.float32),
        "mixer_norm": {"g": np.ones((ssm.d_ssm(cfg),), dtype)},
        "out_proj": _dense_init(rng, ssm.d_ssm(cfg), cfg.d_model, dtype),
    }


def init(cfg: TransformerConfig, seed: int = 0, parts=()):
    """Host-side deterministic init (seeded like the MLP family's
    dims-keyed init, `layers.py:104-113`, but one seed for the whole tree).

    `parts` names what every block holds beyond the plain pre-norm
    block (`BLOCK_PARTS`): `qk_norm` the per-head RMSNorm scales
    `q_norm` / `k_norm`, `attn_gate` the sigmoid gate's projection,
    `post_norm` the norms `ln1_post` / `ln2_post` of each sub-layer's
    output. A block's kind follows what its params hold, so nothing in
    the config repeats them; they are drawn after the block's other
    leaves, which keeps every earlier model's weights what they were."""
    assert set(parts) <= set(BLOCK_PARTS), parts
    rng = np.random.default_rng(seed)
    dt = cfg.dtype
    d = cfg.d_model
    hd_all = cfg.n_heads * cfg.head_dim
    blocks = []
    for i in range(cfg.n_layers):
        blk = {
            "ln1": {"g": np.ones((d,), dt), "b": np.zeros((d,), dt)},
            "ln2": {"g": np.ones((d,), dt), "b": np.zeros((d,), dt)},
        }
        if cfg.latent:
            h, r = cfg.n_heads, cfg.kv_lora_rank
            dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                          cfg.v_head_dim)
            blk["q"] = _dense_init(rng, d, h * (dn + dr), dt)
            blk["kv_a"] = _dense_init(rng, d, r + dr, dt)
            blk["kv_norm"] = {"g": np.ones((r,), dt)}
            blk["kv_b"] = rng.normal(0.0, 1.0 / np.sqrt(r),
                                     (r, h, dn + dv)).astype(dt)
            blk["proj"] = _dense_init(rng, h * dv, d, dt)
        else:
            blk["proj"] = _dense_init(rng, hd_all, d, dt)
            if cfg.gqa:  # separate q and (smaller) fused kv projections
                blk["q"] = _dense_init(rng, d, hd_all, dt)
                blk["kv"] = _dense_init(
                    rng, d, 2 * cfg.kv_heads * cfg.head_dim, dt)
            else:
                blk["qkv"] = _dense_init(rng, d, 3 * hd_all, dt)
        if cfg.routed_layer(i):
            e, ff = cfg.n_routed_experts, cfg.expert_d_ff
            blk["experts"] = {
                "router": rng.normal(0.0, 1.0 / np.sqrt(d), (d, e)).astype(dt),
                "route_bias": np.zeros((e,), np.float32),
                "gate": rng.normal(0.0, 1.0 / np.sqrt(d), (e, d, ff)).astype(dt),
                "up": rng.normal(0.0, 1.0 / np.sqrt(d), (e, d, ff)).astype(dt),
                "down": rng.normal(0.0, 1.0 / np.sqrt(ff), (e, ff, d)).astype(dt),
            }
            if cfg.n_shared_experts:
                sff = cfg.n_shared_experts * ff
                blk["shared"] = {"gate": _dense_init(rng, d, sff, dt),
                                 "up": _dense_init(rng, d, sff, dt),
                                 "down": _dense_init(rng, sff, d, dt)}
        elif cfg.n_experts > 0:
            e, ff = cfg.n_experts, cfg.ffn_dim
            blk["moe"] = {
                "gate": rng.normal(0.0, 0.02, (d, e)).astype(dt),
                "wi": rng.normal(0.0, 1.0 / np.sqrt(d), (e, d, ff)).astype(dt),
                "bi": np.zeros((e, ff), dt),
                "wo": rng.normal(0.0, 1.0 / np.sqrt(ff), (e, ff, d)).astype(dt),
                "bo": np.zeros((e, d), dt),
            }
        else:
            if cfg.ffn == "swiglu":
                blk["gate"] = _dense_init(rng, d, cfg.ffn_dim, dt)
            blk["up"] = _dense_init(rng, d, cfg.ffn_dim, dt)
            blk["down"] = _dense_init(rng, cfg.ffn_dim, d, dt)
        if "qk_norm" in parts:
            blk["q_norm"] = {"g": np.ones((cfg.head_dim,), dt)}
            blk["k_norm"] = {"g": np.ones((cfg.head_dim,), dt)}
        if "attn_gate" in parts:
            blk["attn_gate"] = _dense_init(rng, d, hd_all, dt)
        if "post_norm" in parts:
            for name in ("ln1_post", "ln2_post"):
                blk[name] = {"g": np.ones((d,), dt), "b": np.zeros((d,), dt)}
        if cfg.mixer:
            blk["mixer"] = _mixer_init(rng, cfg, dt)
        blocks.append(blk)
    out = {
        "tok_emb": rng.normal(0.0, 0.02, (cfg.vocab, d)).astype(dt),
        "pos_emb": rng.normal(0.0, 0.02, (cfg.max_seq, d)).astype(dt),
        "blocks": blocks,
        "ln_f": {"g": np.ones((d,), dt), "b": np.zeros((d,), dt)},
    }
    if not cfg.tie_embeddings:
        out["head"] = _dense_init(rng, d, cfg.vocab, dt)
    return out


# leaves `cast_params` leaves in the master dtype: the norms (`kv_norm`
# is the latent row's RMSNorm, `q_norm` / `k_norm` a head's, `ln1_post`
# / `ln2_post` a sub-layer's output's) and the routed layer's selection
# bias, which only ever meets float32 scores
_NORM_KEYS = {"ln1", "ln2", "ln_f", "kv_norm", "q_norm", "k_norm",
              "ln1_post", "ln2_post", "mixer_norm"}
# ... and a mixer's per-head decay, step bias and skip, which only meet
# the float32 recurrence
_MASTER_KEYS = _NORM_KEYS | {"route_bias", "A_log", "dt_bias", "d_skip"}

# Quantized weight-storage leaves (see `quantize_weights`): "Wq" is the
# int8/fp8 value tensor, "Ws" the per-out-channel f32 scales. Both stay
# in their STORAGE dtype through `cast_params` — casting Wq would
# materialize the full-size dequantized copy the storage exists to
# avoid (the analysis `dequant-fusion` rule), and casting Ws to bf16
# would quantize the scales for no byte win (they are O(N), not O(K*N)).
_QUANT_KEYS = {"Wq", "Ws"}

WEIGHT_QUANT_MODES = ("", "int8", "fp8")

# fp8 weight storage uses e4m3 where this jax/XLA build ships it;
# otherwise `quantize_weights("fp8")` raises rather than silently
# storing something else.
_FP8_DTYPE = getattr(jnp, "float8_e4m3fn", None)


def quantize_weights(params, mode: str):
    """Quantize every dense projection's weight matrix for the decode
    path: each {"W": (K, N), "b"} dict in the pytree (block q/kv/qkv,
    proj, up/down/gate, the untied head) becomes {"Wq": (K, N) int8 or
    fp8-e4m3, "Ws": (N,) f32 per-out-channel scales, "b"}. Consumers
    dispatch on the "Wq" leaf (`_dense`) and run the fused-dequant
    matmul (`ops.matmul.dequant_matmul`) — the scale lands on the f32
    accumulator, the weight is read at 1 byte/element.

    Deliberately NOT quantized: embeddings (their decode read is one
    gathered row per token, not a sweep), norm scales and biases
    (O(d) — noise next to the matrices), and MoE expert banks (no
    serving path yet; ROADMAP item 5 extends this to training).
    Symmetric per-out-channel absmax scaling; mode "" returns the tree
    unchanged. A typed error, not an assert — this gates a production
    storage layout."""
    if mode not in WEIGHT_QUANT_MODES:
        raise ValueError(
            f"unsupported weight_quant={mode!r}; expected one of "
            f"{WEIGHT_QUANT_MODES} ('' = weights in the master dtype)")
    if not mode:
        return params
    if mode == "fp8" and _FP8_DTYPE is None:
        raise ValueError(
            "weight_quant='fp8' needs float8_e4m3fn support in this "
            "jax/XLA build; use 'int8'")

    def quant_dense(p):
        w = jnp.asarray(p["W"], jnp.float32)
        amax = jnp.maximum(jnp.max(jnp.abs(w), axis=0), 1e-8)   # (N,)
        if mode == "int8":
            ws = amax / 127.0
            wq = jnp.clip(jnp.round(w / ws), -127, 127).astype(jnp.int8)
        else:  # e4m3: max normal is 448
            ws = amax / 448.0
            wq = (w / ws).astype(_FP8_DTYPE)
        rest = {k: v for k, v in p.items() if k != "W"}
        return {"Wq": wq, "Ws": ws.astype(jnp.float32), **rest}

    def walk(node):
        if isinstance(node, dict):
            if "W" in node and np.ndim(node["W"]) == 2:
                return quant_dense(node)
            if "Wq" in node:          # already quantized: idempotent
                return node
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, list):
            return [walk(v) for v in node]
        return node

    return walk(params)


def weight_quant_mode(params) -> str:
    """The storage mode of a (possibly) quantized tree: "int8"/"fp8"
    when `quantize_weights` leaves are present, else ""."""
    for leaf in jax.tree_util.tree_leaves(params):
        if leaf.dtype == jnp.int8 and leaf.ndim == 2:
            return "int8"
        if _FP8_DTYPE is not None and leaf.dtype == _FP8_DTYPE:
            return "fp8"
    return ""


def cast_params(params, compute_dtype):
    """Mixed-precision boundary: float leaves to `compute_dtype` (None =
    identity; casting twice is free — same-dtype astype returns the
    operand). Shared by training forward and the decode path.

    Norm parameters (ln1/ln2/ln_f) stay in the MASTER dtype: every
    consumer immediately recasts them to f32 for the statistics
    (`_layernorm`/`_rmsnorm`, `zb.norm_fwd`), so a bf16 cast here would
    only quantize the scales and pay a dead f32->bf16->f32 round trip
    per use — the `analysis` dtype rule's round-trip finding (round 6).
    Norm OUTPUTS are cast to the activation dtype as before, so every
    matmul's operand dtypes are unchanged.

    Quantized-storage leaves (Wq/Ws, `quantize_weights`) likewise stay
    put: int8 is non-floating anyway, but fp8-e4m3 IS floating and a
    blanket cast would silently rewiden it to bf16 — the full-size
    dequantized copy the `dequant-fusion` analysis rule exists to
    catch; the f32 scales are numerics, not bulk bytes."""
    if compute_dtype is None:
        return params

    def cast(path, p):
        keys = {getattr(k, "key", None) for k in path}
        if keys & _MASTER_KEYS or keys & _QUANT_KEYS:
            return p
        return (p.astype(compute_dtype)
                if jnp.issubdtype(p.dtype, jnp.floating) else p)

    return jax.tree_util.tree_map_with_path(cast, params)


def _layernorm(p, x, eps=1e-5):
    """Statistics in float32 (bf16 mean/variance loses too much precision);
    result back in x's dtype. No-op casts under pure-f32 training."""
    xf = x.astype(jnp.float32)
    mu = xf.mean(axis=-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(axis=-1, keepdims=True)
    y = ((xf - mu) * jax.lax.rsqrt(var + eps) * p["g"].astype(jnp.float32)
         + p["b"].astype(jnp.float32))
    return y.astype(x.dtype)


def _rmsnorm(p, x, eps=1e-5):
    """RMSNorm (Zhang & Sennrich): scale by the root-mean-square only —
    no centering, no bias (p["b"] is kept in the pytree for structural
    stability but unused). f32 statistics like `_layernorm`."""
    xf = x.astype(jnp.float32)
    ms = (xf * xf).mean(axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(ms + eps) * p["g"].astype(jnp.float32)
    return y.astype(x.dtype)


def _norm(p, x, cfg: TransformerConfig):
    return (_rmsnorm if cfg.norm == "rmsnorm" else _layernorm)(p, x)


def _dense(p, x, fp8: bool = False):
    if "Wq" in p:  # quantized storage (`quantize_weights`): the scale
        #            lands on the f32 accumulator, never on the weight
        from shallowspeed_tpu.ops.matmul import dequant_matmul

        return dequant_matmul(x, p["Wq"], p["Ws"]) + p["b"]
    if fp8:  # cfg.fp8_dense: the training-time quantized matmul. The
        #      activation scale is just-in-time per-tensor (unlike the
        #      Fp8TrainEngine's delayed history — a stateless model
        #      function has nowhere to carry one) and stop_gradient:
        #      the clip is exact-in-range by construction, so the
        #      analysis range rule holds without calibration state.
        from shallowspeed_tpu.ops.matmul import E4M3_MAX, fp8_dense

        w = p["W"]
        x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
        amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x2)))
        sx = jnp.maximum(amax / E4M3_MAX, 1e-12)
        out = fp8_dense(x2, w.astype(jnp.float32), sx)
        return (out.reshape(*x.shape[:-1], w.shape[-1]).astype(x.dtype)
                + p["b"])
    return x @ p["W"] + p["b"]


def _dropout(x, rate: float, key):
    """Inverted dropout; identity when `key` is None or rate is 0 (the
    static no-op keeps eval/decode traces free of RNG ops)."""
    if key is None or rate == 0.0:
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0).astype(x.dtype)


def head_logits(params, x, cfg: TransformerConfig):
    """Vocabulary projection: the untied head, or tok_emb^T when
    cfg.tie_embeddings (no bias — the tied head has none); optionally
    soft-capped (`cfg.logit_softcap`), in f32 so tanh saturation is not
    computed in bf16."""
    logits = (x @ params["tok_emb"].T if cfg.tie_embeddings
              else _dense(params["head"], x, cfg.fp8_dense))
    if cfg.logit_softcap > 0.0:
        cap = cfg.logit_softcap
        logits = cap * jnp.tanh(logits.astype(jnp.float32) / cap)
    return logits


def token_loss(logits, targets, cfg: TransformerConfig,
               train: bool = True):
    """Mean token cross-entropy in float32, with optional label
    smoothing. THE loss every engine computes (the pipeline engines call
    it per microbatch), so smoothing/vocab changes happen in one place.
    Smoothing is a TRAINING regularizer: eval paths pass train=False so
    reported val loss/perplexity stays the plain NLL, comparable across
    runs regardless of --label-smoothing."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    ls = cfg.label_smoothing
    if train and ls > 0.0:
        nll = (1.0 - ls) * nll + ls * (-logp.mean(axis=-1))
    return nll.mean()


def _remat_policy(cfg: TransformerConfig):
    """jax.checkpoint policy for cfg.remat_policy (None = save nothing)."""
    cp = jax.checkpoint_policies
    if cfg.remat_policy == "attn":
        return cp.save_only_these_names("attn_out")
    if cfg.remat_policy == "dots":
        return cp.save_from_both_policies(
            cp.dots_with_no_batch_dims_saveable,
            cp.save_only_these_names("attn_out"))
    return None


def chunked_token_loss(params, x, targets, cfg: TransformerConfig,
                       train: bool = True):
    """`token_loss(head_logits(x))` without ever materializing the
    (B*T, vocab) logits: positions are processed in chunks of
    cfg.xent_chunk under a `lax.scan`, each chunk's logits/logsumexp
    rematerialized in the backward (`jax.checkpoint`), so peak memory is
    O(chunk * vocab) transients plus the scalar carry — vs the classic
    path's full f32 log-prob residual. Numerically it computes the SAME
    quantity (lse - target logit, f32 reductions over the same bf16
    logits), reassociated per chunk.

    `params` is the UNCAST tree; only the head leaves are cast here (XLA
    CSEs the duplicate cast against the forward's). `x` is the final-norm
    output (B, T, d)."""
    if cfg.tie_embeddings:
        hp = {"tok_emb": params["tok_emb"]}
    else:
        hp = {"head": params["head"]}
    hp = cast_params(hp, cfg.compute_dtype)
    b, t, d = x.shape
    total = b * t
    n = min(cfg.xent_chunk, total)
    xf = x.reshape(total, d)
    tf = targets.reshape(total)
    rem = (-total) % n
    ls = cfg.label_smoothing if train else 0.0
    if rem:  # pad to a whole number of chunks; mask the pad rows out
        xf = jnp.pad(xf, ((0, rem), (0, 0)))
        tf = jnp.pad(tf, (0, rem))
        wf = jnp.pad(jnp.ones((total,), jnp.float32), (0, rem))
    else:
        wf = jnp.ones((total,), jnp.float32)

    def chunk_nll(hp, xc, tc, wc):
        logits = head_logits(hp, xc, cfg).astype(jnp.float32)  # (n, V)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, tc[:, None], axis=-1)[:, 0]
        nll = lse - tgt
        if ls > 0.0:
            # -mean logp = lse - mean(logits); same algebra as token_loss
            nll = (1.0 - ls) * nll + ls * (lse - logits.mean(axis=-1))
        return (nll * wc).sum()

    body = jax.checkpoint(chunk_nll)
    k = xf.shape[0] // n

    def sbody(acc, xs):
        return acc + body(hp, *xs), None

    # the accumulator must carry x's mesh-variance type (inside a
    # shard_map the per-chunk sums are device-varying; a plain 0.0 is
    # invariant and the scan would reject the carry) — deriving the
    # zero from x itself inherits the right type at zero cost
    acc0 = (xf[0, 0] * 0).astype(jnp.float32)
    tot, _ = jax.lax.scan(
        sbody, acc0,
        (xf.reshape(k, n, d), tf.reshape(k, n), wf.reshape(k, n)))
    return tot / total


def rope_rotate(x, pos, theta: float = 10000.0):
    """Apply rotary embeddings to (B, T, H, D) at global positions `pos`
    (shape (T,) int, or a scalar for single-token decode). Pairs dimension
    halves (d, d + D/2) — the half-split formulation; phases are f32 for
    long-sequence accuracy, result in x's dtype."""
    d = x.shape[-1]
    assert d % 2 == 0, f"rope needs an even head_dim, got {d}"
    half = d // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    pos = jnp.atleast_1d(jnp.asarray(pos, jnp.float32))
    ang = pos[:, None] * freqs                               # (T, half)
    cos = jnp.cos(ang)[None, :, None, :]                     # (1, T, 1, half)
    sin = jnp.sin(ang)[None, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _qkv(p, h, cfg: TransformerConfig):
    """(q (B,T,H,hd), k, v (B,T,Hkv,hd)) from the block's projection(s):
    the fused head-major qkv, or split q / fused kv under GQA."""
    b, t, _ = h.shape
    if "kv" in p:
        q = _dense(p["q"], h, cfg.fp8_dense).reshape(
            b, t, cfg.n_heads, cfg.head_dim)
        kv = _dense(p["kv"], h, cfg.fp8_dense).reshape(
            b, t, cfg.kv_heads, 2, cfg.head_dim)
        k, v = kv[..., 0, :], kv[..., 1, :]
    else:
        qkv = _dense(p["qkv"], h, cfg.fp8_dense).reshape(
            b, t, cfg.n_heads, 3, cfg.head_dim)
        q, k, v = qkv[:, :, :, 0], qkv[:, :, :, 1], qkv[:, :, :, 2]
    if "q_norm" in p:   # each head RMS-normed over its own dimensions,
        #                 before any rotation
        q, k = _rmsnorm(p["q_norm"], q), _rmsnorm(p["k_norm"], k)
    return q, k, v


def embed_tokens(params, tokens, cfg: TransformerConfig):
    """The token embedding rows, times `cfg.embed_scale`."""
    x = params["tok_emb"][tokens]
    return x if cfg.embed_scale == 1.0 else x * jnp.asarray(
        cfg.embed_scale, x.dtype)


def attn_residual(p, x, a, h, cfg: TransformerConfig, key=None):
    """The attention sub-layer from the heads' output `a` (..., H * hd)
    to the residual stream, by what the block's params hold: the
    sigmoid gate computed from the sub-layer's input `h` (`attn_gate`),
    the output projection, the norm of the sub-layer's output
    (`ln1_post`), dropout, the add onto `x`. One function for the
    forward pass without a cache, `generate()` and the serving engine's
    two programs."""
    if "attn_gate" in p:
        g = _dense(p["attn_gate"], h, cfg.fp8_dense)
        a = a * jax.nn.sigmoid(g.astype(jnp.float32)).astype(a.dtype)
    y = _dense(p["proj"], a, cfg.fp8_dense)
    if "ln1_post" in p:
        y = _norm(p["ln1_post"], y, cfg)
    return x + _dropout(y, cfg.dropout, key)


def ffn_residual(p, x, y, cfg: TransformerConfig, key=None):
    """The FFN's output `y` onto the residual stream: normed first
    where the block holds `ln2_post`."""
    if "ln2_post" in p:
        y = _norm(p["ln2_post"], y, cfg)
    return x + _dropout(y, cfg.dropout, key)


def mixer(m, h, cfg: TransformerConfig, state=None, n_tok=None):
    """The state-space mixer `m` (a block's `mixer` params) on the
    block's norm output h (B, T, d): (its output (B, T, d), the state
    it leaves: {"conv": (B, K - 1, C), "ssm": (B, H, P, N) float32}).
    `state` is what the sequences' earlier tokens left (None: they start
    here, from zeros), `n_tok` the traced count of true rows where the
    tail of T is padding, which then changes neither output nor state.
    One token a row (T = 1, the decode tick) advances the state
    elementwise; a chunk goes through the blocked scan (`ops/ssm.py`).
    One function for the forward pass without a cache, `generate()`
    and the serving engine's two programs."""
    b, t, _ = h.shape
    if state is None:
        state = ssm.zero_state(cfg, b, h.dtype)
    z, xbc, dt = ssm.split_projection(
        _dense(m["in_proj"], h, cfg.fp8_dense), cfg)
    xbc, tail = ssm.causal_conv(m["conv_w"], m["conv_b"], xbc,
                                state["conv"], n_tok)
    x, bm, cm = ssm.split_conv(xbc, cfg)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + m["dt_bias"])
    a = -jnp.exp(m["A_log"].astype(jnp.float32))
    if t == 1:
        y, s = ssm.ssm_step(x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0],
                            m["d_skip"], state["ssm"])
        y = y[:, None]
    else:
        y, s = ssm.ssm_scan(x, dt, a, bm, cm, m["d_skip"], state["ssm"],
                            n_tok)
    y = ssm.gated_norm(m["mixer_norm"]["g"], y.reshape(b, t, -1), z,
                       cfg.ssm_groups)
    return _dense(m["out_proj"], y, cfg.fp8_dense), {"conv": tail, "ssm": s}


def latent_qkv(p, h, cfg: TransformerConfig, rotate):
    """A latent block's projections of the norm output h (B, T, d):
    (q_nope (B,T,H,dn), q_rope (B,T,H,dr), c (B,T,r), k_rope (B,T,dr)).
    `c` is the joint down-projection after its RMSNorm and `k_rope` the
    one rotary key every head shares, both as the serving cache stores
    them; `rotate(x (B,T,heads,dr))` applies the rotary phases at the
    caller's positions (a sequence's, or one per decode row)."""
    b, t, _ = h.shape
    dn, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q = _dense(p["q"], h, cfg.fp8_dense).reshape(b, t, cfg.n_heads, -1)
    kva = _dense(p["kv_a"], h, cfg.fp8_dense)
    c = _rmsnorm(p["kv_norm"], kva[..., :r])
    k_rope = rotate(kva[..., None, r:])[:, :, 0]
    return q[..., :dn], rotate(q[..., dn:]), c, k_rope


def latent_scale(cfg: TransformerConfig) -> float:
    return float(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


def repeat_kv(x, cfg: TransformerConfig):
    """Broadcast K/V heads to the full query-head count (no-op for MHA).

    Only needed for attention substrates that predate native GQA; every
    substrate in `ops/attention.py` and `ops/flash_attention.py` declares
    `supports_gqa` and consumes the unrepeated heads directly (kernel
    q-row group folding / grouped einsums), so the hot paths never
    materialize the repeat — the group-factor saving covers compute and
    bandwidth, not just cache storage."""
    g = cfg.n_heads // cfg.kv_heads
    return x if g == 1 else jnp.repeat(x, g, axis=2)


def _supports_gqa(fn) -> bool:
    """Unwrap functools.partial layers to read a substrate's GQA tag."""
    while isinstance(fn, partial):
        fn = fn.func
    return bool(getattr(fn, "supports_gqa", False))


def _supports_prob_dropout(fn) -> bool:
    while isinstance(fn, partial):
        fn = fn.func
    return bool(getattr(fn, "supports_prob_dropout", False))


def _swiglu(p, h, fp8: bool = False):
    return _dense(p["down"], jax.nn.silu(_dense(p["gate"], h, fp8))
                  * _dense(p["up"], h, fp8), fp8)


def routed_ffn(p, h, cfg: TransformerConfig):
    """A routed block's FFN on the norm output `h` (..., d): the chosen
    experts' weighted sum plus the shared expert, and each token's
    chosen experts (..., K) for whoever counts them (the serving
    engine's `experts_touched`). No token and no assignment is dropped."""
    lead = h.shape[:-1]
    y, idx = routed_experts_ffn(p["experts"], h.reshape(-1, h.shape[-1]),
                                cfg.moe_top_k, cfg.routed_scaling_factor)
    y = y.reshape(h.shape)
    if "shared" in p:
        y = y + _swiglu(p["shared"], h)
    return y, idx.reshape(*lead, cfg.moe_top_k)


def _ffn(p, x, cfg: TransformerConfig, h, key=None):
    """Post-attention half of a block: FFN (dense GELU, SwiGLU, capacity-
    routed MoE or dropless routed experts, by what the block's params
    hold) on the norm output `h`, dropout, residual onto `x`.
    Returns (x, (balance aux, router z-loss)) — both unweighted; `loss`
    owns the weights (so a z-loss-only or balance-only config needs no
    coupling between the two)."""
    if "experts" in p:
        y, idx = routed_ffn(p, h, cfg)
        e = cfg.n_routed_experts
        load = jax.nn.one_hot(idx, e, dtype=jnp.float32).reshape(-1, e)
        return (ffn_residual(p, x, y, cfg, key),
                (0.0, 0.0, {"load": load.mean(0),
                            "drop_fraction": jnp.float32(0.0)}))
    if "moe" in p:
        y, aux, z, st = moe_ffn(p["moe"], h, cfg.moe_top_k,
                                cfg.moe_capacity_factor,
                                priority=cfg.moe_routing == "priority")
        return ffn_residual(p, x, y, cfg, key), (aux, z, st)
    if "gate" in p:  # SwiGLU: silu(gate) * up, both column-parallel
        y = _swiglu(p, h, cfg.fp8_dense)
    else:
        y = _dense(p["down"], jax.nn.gelu(_dense(p["up"], h, cfg.fp8_dense)),
                   cfg.fp8_dense)
    return ffn_residual(p, x, y, cfg, key), (0.0, 0.0, None)


def _block(p, x, cfg: TransformerConfig, attn_fn, with_kv: bool = False,
           pos=None, key=None, rotary=None, n_tok=None):
    """One pre-LN block; returns (x, aux) where aux is the MoE
    load-balancing loss (0.0 for dense blocks). With `with_kv` also
    returns this block's (k, v) — the decode prefill
    (`models/generate.py`) captures them into its cache; the training
    path never requests them, so XLA dead-code-eliminates the extra
    outputs there. `pos` (global positions) is required when cfg.rope.
    `key` (training only) seeds this block's attention/FFN dropout.
    `rotary` is this layer's half of its spec (`cfg.layer_specs`; None
    = the model's `cfg.rope`), its window the caller's `attn_fn`.
    A block that holds a `mixer` adds its output beside the attention's
    (both read the one normed input), from a zero state; `with_kv` then
    gives (k, v, the mixer's state after `n_tok` true rows)."""
    b, t, d = x.shape
    if rotary is None:
        rotary = cfg.rope
    k_attn = k_ffn = k_prob = None
    if key is not None and cfg.dropout > 0.0 and cfg.attn_dropout > 0.0:
        k_attn, k_ffn, k_prob = jax.random.split(key, 3)
    elif key is not None and cfg.dropout > 0.0:
        # 2-way split kept for bit-compatibility with round-2 streams
        k_attn, k_ffn = jax.random.split(key)
    elif key is not None and cfg.attn_dropout > 0.0:
        k_prob = key
    h = _norm(p["ln1"], x, cfg)
    if "kv_a" in p:
        # latent attention without a cache: the expanded form over the
        # sequence's own rows, causal (the plain reading of the
        # equations; the serving engine reads its cache absorbed)
        assert not with_kv and pos is not None, (
            "a latent block caches its latent row through the serving "
            "engine's paged pool, not through generate()'s K/V cache")
        qn, qr, c, kr = latent_qkv(
            p, h, cfg, lambda u: rope_rotate(u, pos, cfg.rope_theta))
        causal = jnp.tril(jnp.ones((t, t), bool))[None, None]
        a = latent_attention(qn, qr, c, kr, p["kv_b"], causal,
                             latent_scale(cfg))
        a = _checkpoint_name(a.reshape(b, t, -1), "attn_out")
        x = x + _dropout(_dense(p["proj"], a, cfg.fp8_dense),
                         cfg.dropout, k_attn)
        return _ffn(p, x, cfg, _norm(p["ln2"], x, cfg), k_ffn)
    # head-major fused layout (H, 3, D): a contiguous slice of the 3d output
    # dim is a whole group of heads, so tensor-parallel column sharding of
    # qkv["W"] keeps attention fully local to each device (Megatron
    # alignment; see parallel/tensor.py). Under GQA, _qkv splits into
    # q / kv projections instead.
    q, k, v = _qkv(p, h, cfg)
    if rotary:
        assert pos is not None, "cfg.rope needs positions threaded in"
        q = rope_rotate(q, pos, cfg.rope_theta)
        k = rope_rotate(k, pos, cfg.rope_theta)
    kv_cacheable = (k, v)  # rotated, UNREPEATED — the decode cache layout
    extra = {}
    if cfg.attn_dropout > 0.0:
        assert _supports_prob_dropout(attn_fn), (
            "cfg.attn_dropout needs the plain XLA attention substrate "
            "(fused flash / resharded ring/ulysses paths cannot mask "
            "probabilities inside their score blocks)")
        extra = {"dropout": cfg.attn_dropout, "dropout_key": k_prob}
    if _supports_gqa(attn_fn):  # native GQA: no repeated K/V materialized
        a = attn_fn(q, k, v, **extra).reshape(b, t, -1)
    else:
        a = attn_fn(q, repeat_kv(k, cfg), repeat_kv(v, cfg),
                    **extra).reshape(b, t, -1)
    # name for selective remat: cfg.remat_policy "attn"/"dots" saves this
    # value so the backward replay never re-runs the attention substrate
    # (no-op outside a policied jax.checkpoint)
    a = _checkpoint_name(a, "attn_out")
    x = attn_residual(p, x, a, h, cfg, k_attn)
    if "mixer" in p:
        y, left = mixer(p["mixer"], h, cfg, None, n_tok)
        x = x + y
        kv_cacheable += (left,)
    h = _norm(p["ln2"], x, cfg)
    x, aux = _ffn(p, x, cfg, h, k_ffn)
    if with_kv:
        return x, aux, kv_cacheable
    return x, aux


def forward_with_aux(params, tokens, cfg: TransformerConfig,
                     attn_fn=None, pos_offset=0, dropout_key=None,
                     with_stats: bool = False, head: bool = True):
    """tokens: (batch, seq) int32 -> (logits (batch, seq, vocab), moe aux).

    `head=False` returns the final-norm hidden states (batch, seq, d)
    instead of logits — the chunked-cross-entropy path (`loss` with
    cfg.xent_chunk) applies the vocab projection itself, blockwise.

    With `with_stats=True` additionally returns layer-averaged MoE
    routing statistics ({"load": (E,), "drop_fraction": scalar}, or None
    for dense configs) as a third element — observability for the
    silent capacity drop (`ops/moe.py`); when unused, XLA dead-code-
    eliminates the accounting.

    `attn_fn(q, k, v)` defaults to full causal attention; a context-parallel
    caller passes `partial(ring_attention, axis_name='sp')` and the global
    `pos_offset` of its sequence block (positions are global under sequence
    sharding). `dropout_key` (training only) activates cfg.dropout; per-
    layer keys are fold_in-derived, so remat recompute sees identical
    masks.
    """
    assert attn_fn is None or not cfg.layers, (
        "a layer pattern (cfg.layers) runs the default attention, one "
        "window a layer; a caller's substrate takes one for the model")
    specs = cfg.layer_specs
    attn_of = {w: attn_fn or partial(attention, causal=True, window=w)
               for w, _ in specs}
    params = cast_params(params, cfg.compute_dtype)
    b, t = tokens.shape
    # Under jit an out-of-range gather silently clamps to pos_emb's last row;
    # guard statically where possible (pos_offset is traced in the
    # context-parallel path — the engine checks the global length instead).
    if isinstance(pos_offset, int):
        assert pos_offset + t <= cfg.max_seq, (
            f"sequence positions [{pos_offset}, {pos_offset + t}) exceed "
            f"max_seq={cfg.max_seq}")
    if cfg.dropout == 0.0 and cfg.attn_dropout == 0.0:
        dropout_key = None
    pos = pos_offset + jnp.arange(t)
    x = embed_tokens(params, tokens, cfg)
    if not cfg.rope:  # rope replaces the learned absolute embedding
        x = x + params["pos_emb"][pos]
    if dropout_key is not None:
        x = _dropout(x, cfg.dropout,
                     jax.random.fold_in(dropout_key, cfg.n_layers))
    aux_total, z_total = 0.0, 0.0
    stats_sum, n_moe = None, 0
    block_fn = _block
    if cfg.remat:
        block_fn = jax.checkpoint(_block, static_argnums=(2, 3, 4, 7),
                                  policy=_remat_policy(cfg))
    for i, blk in enumerate(params["blocks"]):
        k_i = (None if dropout_key is None
               else jax.random.fold_in(dropout_key, i))
        window, rotary = specs[i]
        x, (aux, z, st) = block_fn(blk, x, cfg, attn_of[window], False, pos,
                                   k_i, rotary)
        aux_total = aux_total + aux
        z_total = z_total + z
        if st is not None:
            stats_sum = (st if stats_sum is None else
                         jax.tree_util.tree_map(jnp.add, stats_sum, st))
            n_moe += 1
    x = _norm(params["ln_f"], x, cfg)
    out = head_logits(params, x, cfg) if head else x
    if with_stats:
        stats = (None if stats_sum is None else jax.tree_util.tree_map(
            lambda v: v / n_moe, stats_sum))
        return out, (aux_total, z_total), stats
    return out, (aux_total, z_total)


def forward(params, tokens, cfg: TransformerConfig,
            attn_fn=None, pos_offset=0, dropout_key=None):
    """Logits only (see `forward_with_aux` for the MoE aux loss)."""
    return forward_with_aux(params, tokens, cfg, attn_fn, pos_offset,
                            dropout_key)[0]


def loss(params, tokens, targets, cfg: TransformerConfig,
         attn_fn=None, pos_offset=0, dropout_key=None, train: bool = True):
    """Mean softmax cross-entropy over all (batch, seq) positions, plus the
    weighted MoE load-balancing aux loss when the config has experts.

    Under data/sequence sharding the mean over the LOCAL block is returned;
    the caller averages across shards (`lax.pmean`) — exact because all
    blocks have equal size.
    """
    cfg = cfg.trainable
    if cfg.xent_chunk > 0:
        hid, (aux, z) = forward_with_aux(params, tokens, cfg, attn_fn,
                                         pos_offset, dropout_key,
                                         head=False)
        tl = chunked_token_loss(params, hid, targets, cfg, train)
    else:
        logits, (aux, z) = forward_with_aux(params, tokens, cfg, attn_fn,
                                            pos_offset, dropout_key)
        tl = token_loss(logits, targets, cfg, train)
    total = tl + cfg.moe_aux_weight * aux
    if cfg.moe_z_weight > 0.0:
        total = total + cfg.moe_z_weight * z
    return total
