"""From a configuration file whose every block holds a state-space mixer
beside its attention heads (the `falcon_h1` keys of the Hugging Face
config) to what the program is given: its `TransformerConfig` with the
mixer's sizes, and weights made on the device from the seed.
`harness/model.py` does the same for dense decoders.

The published layer multiplies activations by constants at eleven
places (`multipliers`). Each sits on a linear map, so whoever makes the
weights FOLDS it into the matrix it follows (`fold`) and the program
carries none of them; only the embedding's rides `embed_scale`. The
plain reference applies them on the activations where the published
code does (`harness/reference_state_space.py`), so the comparison that
decides `correct` checks the fold.

Every matrix is N(0, 1/fan_in) before its fold, the embedding
N(0, 0.02**2), norm scales 1, dense biases 0 (the model has none), as in
`model.weights_fn`; the mixer's own parameters as Mamba-2 initialises
them (the file's `assumed`): `A_log` the log of U(1, 16), `dt_bias` the
inverse softplus of step sizes log-uniform in [1e-3, 1e-1], the skip 1,
the convolution U(-1/2, 1/2) a tap with bias 0. No table of learned
positions is made: every layer is rotary.

ONE matrix is drawn at another scale: the mixer's input projection is
N(0, 1/fan_in) AFTER its fold, so that z, x, B, C and the step sizes
come out of it at the unit scale Mamba-2's own layer gives them. Drawn
before the fold, the published multipliers (which a trained checkpoint's
larger weights answer) leave B about 0.013 and C about 0.036 after the
convolution, B . C about 0.007, and the state's readout S C 1e-4 to 2e-3
of the skip term beside it: a mixer whose state does nothing, which no
comparison of tokens could tell from one that keeps no state at all. At
the unit scale the readout is a sixth of the mixer's output
(PERF.md section 6, PR 36)."""

from __future__ import annotations

from harness.model import load_config, prng_key  # noqa: F401  (re-exported)

WRITTEN = dict(hidden_act="silu", rope_scaling=None, tie_word_embeddings=False,
               attention_bias=False, mlp_bias=False, mamba_proj_bias=False,
               projectors_bias=False, mamba_conv_bias=True,
               mamba_rms_norm=True, mamba_norm_before_gate=False,
               attn_layer_indices=None)


def multipliers(c: dict) -> dict:
    """The published constants, by the place each is applied at."""
    z, x, b, cc, dt = (float(v) for v in c["ssm_multipliers"])
    gate, down = (float(v) for v in c["mlp_multipliers"])
    return dict(
        embedding=float(c["embedding_multiplier"]),
        attention_in=float(c["attention_in_multiplier"]),
        attention_out=float(c["attention_out_multiplier"]),
        key=float(c["key_multiplier"]),
        ssm_in=float(c["ssm_in_multiplier"]),
        ssm_out=float(c["ssm_out_multiplier"]),
        ssm_z=z, ssm_x=x, ssm_b=b, ssm_c=cc, ssm_dt=dt,
        mlp_gate=gate, mlp_down=down, lm_head=float(c["lm_head_multiplier"]))


def transformer_config(c: dict, mode: str):
    import jax.numpy as jnp

    from shallowspeed_tpu.models.transformer import TransformerConfig
    from shallowspeed_tpu.ops.ssm import BLOCK

    if mode != "serving":
        raise ValueError(f"this configuration is served only, not {mode!r}")
    other = {k: c[k] for k, v in WRITTEN.items() if c[k] != v}
    if other or int(c["mamba_d_ssm"]) != int(c["mamba_n_heads"]) \
            * int(c["mamba_d_head"]) or int(c["mamba_chunk_size"]) != BLOCK:
        raise ValueError(f"a bias, norm, gate or rotary form not written, a "
                         f"mixer width that is not heads x head size, or "
                         f"another block of the scan than {BLOCK}: {other}")
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
        embed_scale=float(c["embedding_multiplier"]),
        ssm_heads=int(c["mamba_n_heads"]), ssm_head_dim=int(c["mamba_d_head"]),
        ssm_state=int(c["mamba_d_state"]), ssm_groups=int(c["mamba_n_groups"]),
        ssm_conv=int(c["mamba_d_conv"]),
        dtype=jnp.dtype(c["serving"]["weights"]),
        compute_dtype=jnp.dtype(c["serving"]["compute"]))


def in_proj_columns(cfg, m: dict):
    """The multiplier of each of the input projection's columns, in their
    order z | x | B | C | dt, `ssm_in` (on the mixer's input) included."""
    import jax.numpy as jnp

    d, gn = cfg.ssm_heads * cfg.ssm_head_dim, cfg.ssm_groups * cfg.ssm_state
    parts = ((m["ssm_z"], d), (m["ssm_x"], d), (m["ssm_b"], gn),
             (m["ssm_c"], gn), (m["ssm_dt"], cfg.ssm_heads))
    return m["ssm_in"] * jnp.concatenate(
        [jnp.full((n,), v, jnp.float32) for v, n in parts])


def fold(params, cfg, m: dict):
    """`params` (the pytree `transformer.init` gives for this family) with
    each multiplier inside the matrix it follows, in the leaves' own
    dtypes: `attention_in` in q and kv, `key` in kv's key columns,
    `attention_out` in `proj`, `ssm_in` and the five column multipliers
    in `in_proj`, `ssm_out` in `out_proj`, the MLP's two in `gate` and
    `down`, `lm_head` in `head`. The embedding's stays out: it is the
    config's `embed_scale`."""
    import jax.numpy as jnp

    def times(p, by):
        w = p["W"]
        return {**p, "W": (w.astype(jnp.float32) * by).astype(w.dtype)}

    # kv's columns are (kv head, [k | v], head_dim): the keys' half
    key_cols = jnp.tile(jnp.concatenate([
        jnp.full((cfg.head_dim,), m["key"], jnp.float32),
        jnp.ones((cfg.head_dim,), jnp.float32)]), cfg.kv_heads)
    blocks = []
    for blk in params["blocks"]:
        mix = blk["mixer"]
        blocks.append({
            **blk,
            "q": times(blk["q"], m["attention_in"]),
            "kv": times(blk["kv"], m["attention_in"] * key_cols),
            "proj": times(blk["proj"], m["attention_out"]),
            "gate": times(blk["gate"], m["mlp_gate"]),
            "down": times(blk["down"], m["mlp_down"]),
            "mixer": {**mix,
                      "in_proj": times(mix["in_proj"], in_proj_columns(cfg, m)),
                      "out_proj": times(mix["out_proj"], m["ssm_out"])}})
    return {**params, "blocks": blocks,
            "head": times(params["head"], m["lm_head"])}


def weights_fn(cfg, m: dict):
    """A jitted `key -> weights`: the pytree `transformer.init(cfg)` gives
    for this family less the table of learned positions, drawn on the
    device in one call and folded (`fold`) before it is rounded to the
    dtype the weights are served in."""
    import jax
    import jax.numpy as jnp

    from shallowspeed_tpu.ops import ssm

    dt, d, f32 = cfg.dtype, cfg.d_model, jnp.float32
    qd, kvd = cfg.n_heads * cfg.head_dim, 2 * cfg.kv_heads * cfg.head_dim
    h, k, c = cfg.ssm_heads, cfg.ssm_conv, ssm.conv_dim(cfg)

    def dense(key, fan_in, fan_out):        # float32 until it is folded
        w = jax.random.normal(key, (fan_in, fan_out), f32)
        return {"W": w * fan_in ** -0.5, "b": jnp.zeros((fan_out,), dt)}

    def norm(n):
        return {"g": jnp.ones((n,), dt), "b": jnp.zeros((n,), dt)}

    def mixer(keys):
        step = jnp.exp(jax.random.uniform(
            next(keys), (h,), f32, jnp.log(1e-3), jnp.log(1e-1)))
        proj = dense(next(keys), d, ssm.proj_dim(cfg))
        return {
            # N(0, 1/fan_in) once `fold` has multiplied its columns
            "in_proj": {**proj, "W": proj["W"] / in_proj_columns(cfg, m)},
            "conv_w": jax.random.uniform(next(keys), (k, c), f32, -0.5,
                                         0.5).astype(dt),
            "conv_b": jnp.zeros((c,), dt),
            "A_log": jnp.log(jax.random.uniform(next(keys), (h,), f32,
                                                1.0, 16.0)),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "d_skip": jnp.ones((h,), f32),
            "mixer_norm": {"g": jnp.ones((ssm.d_ssm(cfg),), dt)},
            "out_proj": dense(next(keys), ssm.d_ssm(cfg), d)}

    def rounded(tree):
        return jax.tree_util.tree_map(
            lambda w: w.astype(dt) if w.ndim == 2 else w, tree)

    @jax.jit
    def make(key):
        keys = iter(jax.random.split(key, 16 * cfg.n_layers + 4))
        blocks = [{"ln1": norm(d), "ln2": norm(d),
                   "q": dense(next(keys), d, qd),
                   "kv": dense(next(keys), d, kvd),
                   "proj": dense(next(keys), qd, d),
                   "gate": dense(next(keys), d, cfg.ffn_dim),
                   "up": dense(next(keys), d, cfg.ffn_dim),
                   "down": dense(next(keys), cfg.ffn_dim, d),
                   "mixer": mixer(keys)} for _ in range(cfg.n_layers)]
        emb = 0.02 * jax.random.normal(next(keys), (cfg.vocab, d), f32)
        raw = {"tok_emb": emb.astype(dt), "blocks": blocks, "ln_f": norm(d),
               "head": dense(next(keys), d, cfg.vocab)}
        return rounded(fold(raw, cfg, m))

    return make


def init_weights_on_device(cfg, seed: int, m: dict):
    return weights_fn(cfg, m)(prng_key(seed))
