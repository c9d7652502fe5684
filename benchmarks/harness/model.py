"""From a configuration file to what the program is given: its
`TransformerConfig`, and weights made on the device from the seed.

The file's published keys carry the Hugging Face names; `program` says
which of the repo's block variants they select, and `serving` /
`training` the precisions and the training recipe."""

from __future__ import annotations

import json
from pathlib import Path


def load_config(path) -> dict:
    with open(Path(path)) as f:
        return json.load(f)


def transformer_config(c: dict, mode: str):
    """The repo's config for `mode` ("serving" or "training"). Serving
    stores and computes in bf16; training keeps f32 master weights and
    computes in bf16 with the file's recipe."""
    import jax.numpy as jnp
    import numpy as np

    from shallowspeed_tpu.models.transformer import TransformerConfig

    heads = int(c["num_attention_heads"])
    kv = int(c.get("num_key_value_heads") or heads)
    common = dict(
        vocab=int(c["vocab_size"]), d_model=int(c["hidden_size"]),
        n_heads=heads, n_layers=int(c["num_hidden_layers"]),
        max_seq=int(c["max_position_embeddings"]),
        d_ff=int(c["intermediate_size"]),
        n_kv_heads=0 if kv == heads else kv,
        rope=bool(c["program"]["rope"]),
        rope_theta=float(c["rope_theta"]),
        norm=c["program"]["norm"], ffn=c["program"]["ffn"],
        tie_embeddings=bool(c["tie_word_embeddings"]),
        attn_window=int(c.get("sliding_window") or 0))
    if mode == "serving":
        dt = jnp.dtype(c["serving"]["weights"])
        return TransformerConfig(dtype=dt,
                                 compute_dtype=jnp.dtype(c["serving"]["compute"]),
                                 **common)
    if mode == "training":
        t = c["training"]
        return TransformerConfig(
            dtype=np.dtype(t["master_weights"]),
            compute_dtype=jnp.dtype(t["compute"]), remat=bool(t["remat"]),
            remat_policy=t["remat_policy"], xent_chunk=int(t["xent_chunk"]),
            **common)
    raise ValueError(f"unknown mode {mode!r}")


def prng_key(seed: int):
    """A key from any non-negative whole number: the driver's seeds pass
    2**31, which one 32-bit word does not hold."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 31)),
                              seed >> 31)


def weights_fn(cfg):
    """A jitted `key -> weights`: the pytree `transformer.init` gives (same keys, shapes and
    distributions: matrices N(0, 1/fan_in), embeddings N(0, 0.02**2),
    norms at 1 and 0, zero biases), drawn on the device in one call, in
    the dtype the weights are served in. Dense blocks only."""
    import jax
    import jax.numpy as jnp

    if cfg.n_experts:
        raise NotImplementedError("expert blocks need an initialiser")
    dt, d, ff = cfg.dtype, cfg.d_model, cfg.ffn_dim
    kvd = 2 * cfg.kv_heads * cfg.head_dim

    def dense(key, fan_in, fan_out):
        w = jax.random.normal(key, (fan_in, fan_out), jnp.float32)
        return {"W": (w * fan_in ** -0.5).astype(dt),
                "b": jnp.zeros((fan_out,), dt)}

    def norm():
        return {"g": jnp.ones((d,), dt), "b": jnp.zeros((d,), dt)}

    def emb(key, rows):
        return (0.02 * jax.random.normal(key, (rows, d), jnp.float32)
                ).astype(dt)

    @jax.jit
    def make(key):
        keys = iter(jax.random.split(key, 8 * cfg.n_layers + 4))
        blocks = []
        for _ in range(cfg.n_layers):
            blk = {"ln1": norm(), "ln2": norm(),
                   "proj": dense(next(keys), d, d),
                   "up": dense(next(keys), d, ff),
                   "down": dense(next(keys), ff, d)}
            if cfg.gqa:
                blk["q"] = dense(next(keys), d, d)
                blk["kv"] = dense(next(keys), d, kvd)
            else:
                blk["qkv"] = dense(next(keys), d, 3 * d)
            if cfg.ffn == "swiglu":
                blk["gate"] = dense(next(keys), d, ff)
            blocks.append(blk)
        out = {"tok_emb": emb(next(keys), cfg.vocab),
               "pos_emb": emb(next(keys), cfg.max_seq),
               "blocks": blocks, "ln_f": norm()}
        if not cfg.tie_embeddings:
            out["head"] = dense(next(keys), d, cfg.vocab)
        return out

    return make


def init_weights_on_device(cfg, seed: int):
    return weights_fn(cfg)(prng_key(seed))
