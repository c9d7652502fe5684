"""From a configuration file with window and full attention layers mixed,
gated QK-normed attention under four norms and sigmoid-routed experts
(the `afmoe` keys of the Hugging Face config) to what the program is
given: its `TransformerConfig` with the layers' pattern, and weights made
on the device from the seed. `harness/model.py` does the same for dense
decoders and `harness/model_latent_experts.py` for latent attention.

Every matrix is N(0, 1/fan_in), the embedding N(0, 0.02**2), norm scales
1, dense biases 0 (the model has none), as in `model.weights_fn`; the
router's selection bias is N(0, 0.02**2) in float32 (the file's
`assumed`). No table of learned positions is made: every layer is rotary
or takes no position at all, so the program reads none."""

from __future__ import annotations

from harness.model import load_config, prng_key  # noqa: F401  (re-exported)

ROUTE_BIAS_STD = 0.02
KINDS = {"sliding_attention": True, "full_attention": False}


def layer_pattern(c: dict) -> tuple:
    """(window, rotary) a layer: a sliding layer sees `sliding_window`
    tokens and rotates, a full layer sees all and does not rotate."""
    w = int(c["sliding_window"])
    return tuple((w, True) if KINDS[t] else (0, False)
                 for t in c["layer_types"])


def transformer_config(c: dict, mode: str):
    import jax.numpy as jnp

    from shallowspeed_tpu.models.transformer import TransformerConfig

    if mode != "serving":
        raise ValueError(f"this configuration is served only, not {mode!r}")
    written = dict(score_func="sigmoid", route_norm=True, n_group=1,
                   topk_group=1, rope_scaling=None, hidden_act="silu",
                   tie_word_embeddings=False)
    other = {k: c[k] for k, v in written.items() if c[k] != v}
    if other or len(c["layer_types"]) != int(c["num_hidden_layers"]):
        raise ValueError(f"a router, rotary or head form not written, or "
                         f"layer_types of another length: {other}")
    heads, kv = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    return TransformerConfig(
        vocab=int(c["vocab_size"]), d_model=int(c["hidden_size"]),
        n_heads=heads, n_kv_heads=0 if kv == heads else kv,
        attn_head_dim=int(c["head_dim"]),
        n_layers=int(c["num_hidden_layers"]),
        max_seq=int(c["max_position_embeddings"]),
        d_ff=int(c["intermediate_size"]),
        rope=bool(c["program"]["rope"]), rope_theta=float(c["rope_theta"]),
        norm=c["program"]["norm"], ffn=c["program"]["ffn"],
        tie_embeddings=bool(c["tie_word_embeddings"]),
        layers=layer_pattern(c),
        embed_scale=float(c["hidden_size"]) ** 0.5 if c["mup_enabled"] else 1.0,
        n_routed_experts=int(c["num_experts"]),
        n_shared_experts=int(c["num_shared_experts"]),
        moe_top_k=int(c["num_experts_per_tok"]),
        expert_d_ff=int(c["moe_intermediate_size"]),
        routed_scaling_factor=float(c["route_scale"]),
        first_dense_layers=int(c["num_dense_layers"]),
        dtype=jnp.dtype(c["serving"]["weights"]),
        compute_dtype=jnp.dtype(c["serving"]["compute"]))


def weights_fn(cfg):
    """A jitted `key -> weights`: the pytree `transformer.init(cfg,
    parts=BLOCK_PARTS)` gives for this family less the table of learned
    positions, drawn on the device in one call, in the dtype the weights
    are served in."""
    import jax
    import jax.numpy as jnp

    dt, d = cfg.dtype, cfg.d_model
    hd, qd = cfg.head_dim, cfg.n_heads * cfg.head_dim
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
            blk = {"ln1": norm(d), "ln1_post": norm(d),
                   "ln2": norm(d), "ln2_post": norm(d),
                   "q": dense(next(keys), d, qd),
                   "kv": dense(next(keys), d, 2 * cfg.kv_heads * hd),
                   "q_norm": {"g": jnp.ones((hd,), dt)},
                   "k_norm": {"g": jnp.ones((hd,), dt)},
                   "attn_gate": dense(next(keys), d, qd),
                   "proj": dense(next(keys), qd, d)}
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
                "blocks": blocks, "ln_f": norm(d),
                "head": dense(next(keys), d, cfg.vocab)}

    return make


def init_weights_on_device(cfg, seed: int):
    return weights_fn(cfg)(prng_key(seed))
