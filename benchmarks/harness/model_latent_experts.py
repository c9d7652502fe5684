"""From a configuration file with latent attention and routed experts
(the `deepseek_v3` keys of the Hugging Face config) to what the program
is given: its `TransformerConfig`, and weights made on the device from
the seed. `harness/model.py` does the same for dense decoders; this is
where the expert initialiser it asks for lives.

Every matrix is N(0, 1/fan_in), the embedding N(0, 0.02**2), norm scales
1, dense biases 0 (the model has none), as in `model.weights_fn`; the
router's selection bias is N(0, 0.02**2) in float32 (the file's
`assumed`)."""

from __future__ import annotations

from harness.model import load_config, prng_key  # noqa: F401  (re-exported)

ROUTE_BIAS_STD = 0.02


def transformer_config(c: dict, mode: str):
    import jax.numpy as jnp

    from shallowspeed_tpu.models.transformer import TransformerConfig

    if mode != "serving":
        raise ValueError(f"this configuration is served only, not {mode!r}")
    written = dict(scoring_func="sigmoid", norm_topk_prob=True, n_group=1,
                   topk_group=1, q_lora_rank=None, moe_layer_freq=1,
                   tie_word_embeddings=False)
    other = {k: c[k] for k, v in written.items() if c[k] != v}
    if other:
        raise ValueError(f"a router, query or head form not written: {other}")
    return TransformerConfig(
        vocab=int(c["vocab_size"]), d_model=int(c["hidden_size"]),
        n_heads=int(c["num_attention_heads"]),
        n_layers=int(c["num_hidden_layers"]),
        max_seq=int(c["max_position_embeddings"]),
        d_ff=int(c["intermediate_size"]),
        rope=bool(c["program"]["rope"]), rope_theta=float(c["rope_theta"]),
        norm=c["program"]["norm"], ffn=c["program"]["ffn"],
        tie_embeddings=bool(c["tie_word_embeddings"]),
        kv_lora_rank=int(c["kv_lora_rank"]),
        qk_nope_head_dim=int(c["qk_nope_head_dim"]),
        qk_rope_head_dim=int(c["qk_rope_head_dim"]),
        v_head_dim=int(c["v_head_dim"]),
        n_routed_experts=int(c["n_routed_experts"]),
        n_shared_experts=int(c["n_shared_experts"]),
        moe_top_k=int(c["num_experts_per_tok"]),
        expert_d_ff=int(c["moe_intermediate_size"]),
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        first_dense_layers=int(c["first_k_dense_replace"]),
        dtype=jnp.dtype(c["serving"]["weights"]),
        compute_dtype=jnp.dtype(c["serving"]["compute"]))


def weights_fn(cfg):
    """A jitted `key -> weights`: the pytree `transformer.init` gives for
    a latent, routed configuration, drawn on the device in one call, in
    the dtype the weights are served in."""
    import jax
    import jax.numpy as jnp

    dt, d = cfg.dtype, cfg.d_model
    h, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    e, ff = cfg.n_routed_experts, cfg.expert_d_ff

    def matrix(key, fan_in, *shape):
        w = jax.random.normal(key, shape, jnp.float32)
        return (w * fan_in ** -0.5).astype(dt)

    def dense(key, fan_in, fan_out):
        return {"W": matrix(key, fan_in, fan_in, fan_out),
                "b": jnp.zeros((fan_out,), dt)}

    def swiglu(keys, width):
        return {"gate": dense(next(keys), d, width),
                "up": dense(next(keys), d, width),
                "down": dense(next(keys), width, d)}

    def norm(n):
        return {"g": jnp.ones((n,), dt), "b": jnp.zeros((n,), dt)}

    def emb(key, rows):
        return (0.02 * jax.random.normal(key, (rows, d), jnp.float32)
                ).astype(dt)

    @jax.jit
    def make(key):
        keys = iter(jax.random.split(key, 16 * cfg.n_layers + 4))
        blocks = []
        for i in range(cfg.n_layers):
            blk = {"ln1": norm(d), "ln2": norm(d),
                   "q": dense(next(keys), d, h * (dn + dr)),
                   "kv_a": dense(next(keys), d, r + dr),
                   "kv_norm": {"g": jnp.ones((r,), dt)},
                   "kv_b": matrix(next(keys), r, r, h, dn + dv),
                   "proj": dense(next(keys), h * dv, d)}
            if cfg.routed_layer(i):
                blk["experts"] = {
                    "router": matrix(next(keys), d, d, e),
                    "route_bias": ROUTE_BIAS_STD * jax.random.normal(
                        next(keys), (e,), jnp.float32),
                    "gate": matrix(next(keys), d, e, d, ff),
                    "up": matrix(next(keys), d, e, d, ff),
                    "down": matrix(next(keys), ff, e, ff, d)}
                blk["shared"] = swiglu(keys, cfg.n_shared_experts * ff)
            else:
                blk.update(swiglu(keys, cfg.ffn_dim))
            blocks.append(blk)
        return {"tok_emb": emb(next(keys), cfg.vocab),
                "pos_emb": emb(next(keys), cfg.max_seq),
                "blocks": blocks, "ln_f": norm(d),
                "head": dense(next(keys), d, cfg.vocab)}

    return make


def init_weights_on_device(cfg, seed: int):
    return weights_fn(cfg)(prng_key(seed))
