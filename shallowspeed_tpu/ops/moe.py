"""Mixture-of-experts ops: capacity-based top-k routing + einsum dispatch.

The reference has no MoE / expert parallelism (SURVEY §2 parallelism
checklist: EP absent); this module adds the family the TPU-native way — the
GShard/Switch formulation rather than gather/scatter token shuffling:

- **Static shapes everywhere.** Each expert processes a fixed-capacity
  buffer of `C` token slots per batch group; routing produces dense
  `dispatch`/`combine` tensors `(G, S, E, C)` and the actual token movement
  is two einsums. Nothing here has data-dependent shapes, so the whole layer
  jits, vmaps, and shards like any matmul stack.
- **Expert parallelism is a placement decision.** Stacked expert weights
  `(E, d, ff)` shard over an `ep` mesh axis via `PartitionSpec('ep', ...)`;
  the dispatch einsum's output `(E, G, C, d)` is likewise `ep`-sharded, and
  GSPMD lowers the resharding between the token-sharded and expert-sharded
  layouts to the all-to-all collective that NCCL-style frameworks hand-code
  (see `parallel/expert.py`).
- **Load balancing** uses the standard Switch-Transformer auxiliary loss
  (fraction-routed x mean-probability per expert, scaled by E), plus the
  optional router z-loss (ST-MoE, Zoph et al.): mean(logsumexp(logits)^2)
  penalizes router-logit drift, the standard stabilizer for long MoE runs
  (large logits make top-k selections brittle, especially under bf16).

The capacity-routed layer above is the TRAINING path's (`moe_ffn`,
`moe_ffn_ep`, `parallel/expert.py`). Serving runs the dropless layer
below it (`sigmoid_topk_routing`, `routed_experts_ffn`: DeepSeek-V3's
router, SwiGLU experts, no capacity and no dropped assignment), which
`models/transformer.py` joins with its shared experts.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def expert_capacity(seq_len: int, num_experts: int, top_k: int,
                    capacity_factor: float) -> int:
    """Token slots per expert per batch group (static)."""
    return max(1, math.ceil(top_k * seq_len * capacity_factor / num_experts))


def topk_capacity_routing(gate_logits: jax.Array, capacity: int,
                          top_k: int = 2, priority: bool = False):
    """GShard-style top-k routing with per-expert capacity.

    gate_logits: (G, S, E) — G batch groups of S tokens over E experts.

    `priority=True` switches slot assignment from sequence order to
    BATCH-PRIORITY routing (Riquelme et al., V-MoE): within each k,
    tokens claim an expert's slots in descending gate-weight order, so
    when an expert overflows it drops its LOWEST-confidence assignments
    instead of whatever came late in the sequence. The drop *count* at
    fixed capacity is unchanged (overflow is overflow) — what improves
    is which mass survives: the kept fraction of total gate weight
    rises, and with it loss at aggressive capacity factors. Positional
    bias goes away too (sequence order stops mattering).

    Returns:
      combine:  (G, S, E, C) float32 — combine[g, s, e, c] is token (g, s)'s
                gate weight on expert e's slot c (0 if not routed there).
      dispatch: (G, S, E, C) bool — nonzero support of `combine`.
      aux:      scalar load-balancing loss (Switch formulation).
      stats:    {"load": (E,) f32 — fraction of (token, k) assignments
                routed to each expert (pre-drop; sums to 1),
                "drop_fraction": scalar f32 — fraction of assignments
                dropped for capacity}. Routing is stop-gradiented by
                construction here (top_k indices), so consumers may log
                these without touching the loss; unused stats are
                dead-code-eliminated by XLA.

    Tokens beyond an expert's capacity are dropped for that expert (their
    gate weight contributes nothing) — the standard static-shape tradeoff.
    The drop is SILENT in the loss (the renormalized gate mass simply
    never reaches an expert), which is exactly why `stats` exists: a
    capacity_factor too low for the current routing entropy shows up as
    drop_fraction, not as an error.
    Positions are assigned in sequence order per expert, with later k
    choices stacked after all earlier-k assignments (GShard's ordering).
    """
    g, s, e = gate_logits.shape
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)

    # Top-k expert choices per token, gates renormalized over the chosen k.
    raw_gate, topk_idx = jax.lax.top_k(probs, top_k)           # (G, S, K)
    topk_gate = raw_gate / (raw_gate.sum(-1, keepdims=True) + 1e-9)

    combine = jnp.zeros((g, s, e, capacity), jnp.float32)
    used = jnp.zeros((g, e), jnp.float32)  # slots consumed by earlier k
    kept = jnp.float32(0.0)
    assigned = jnp.zeros((e,), jnp.float32)  # pre-drop per-expert counts
    for k in range(top_k):
        onehot = jax.nn.one_hot(topk_idx[..., k], e)            # (G, S, E)
        if priority:
            # Batch-priority: rank this k's assignments per expert by
            # the RAW router probability (descending; stable, so
            # sequence order breaks ties) — the renormalized gate would
            # degenerate to 1.0 at top_k=1. Unassigned tokens score 0
            # and sort after every positive-gate assignment, so ranks
            # below `capacity` are exactly the top-gated claimants.
            score = onehot * raw_gate[..., k, None]             # (G, S, E)
            order = jnp.argsort(-score, axis=1)                 # (G, S, E)
            rank = jnp.argsort(order, axis=1).astype(jnp.float32)
            pos = rank + used[:, None, :]
        else:
            # Sequence order: tokens assigned earlier in the sequence
            # (or by an earlier k) occupy lower slots (GShard).
            pos = jnp.cumsum(onehot, axis=1) - onehot + used[:, None, :]
        keep = onehot * (pos < capacity)                        # (G, S, E)
        slot = jax.nn.one_hot((pos * onehot).sum(-1).astype(jnp.int32),
                              capacity)                         # (G, S, C)
        combine = combine + (topk_gate[..., k, None, None]
                             * keep[..., None] * slot[:, :, None, :])
        used = used + keep.sum(axis=1)
        kept = kept + keep.sum()
        assigned = assigned + onehot.sum(axis=(0, 1))
    dispatch = combine > 0.0

    # Switch aux loss on the top-1 assignment: E * sum_e f_e * P_e, where
    # f_e = fraction of tokens whose first choice is e, P_e = mean prob.
    top1 = jax.nn.one_hot(topk_idx[..., 0], e)
    aux = e * jnp.sum(top1.mean(axis=(0, 1)) * probs.mean(axis=(0, 1)))
    total = jnp.float32(g * s * top_k)
    stats = {"load": assigned / total,
             "drop_fraction": 1.0 - kept / total}
    return combine, dispatch, aux, stats


def sigmoid_topk_routing(logits: jax.Array, bias: jax.Array, top_k: int,
                         scale: float):
    """DeepSeek-V3's router without its group step (`n_group` 1): sigmoid
    scores, the `top_k` largest of score + bias chosen, weighted by the
    scores ALONE (the bias steers the choice and never the mix),
    normalised over the chosen and scaled. Everything float32.

    logits (T, E), bias (E,) -> (idx (T, K) int32, weights (T, K) f32).
    There is no capacity: every token keeps all K of its assignments."""
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, idx, axis=-1)
    return idx, w / (w.sum(-1, keepdims=True) + 1e-20) * scale


def routed_experts_ffn(p: dict, x: jax.Array, top_k: int, scale: float):
    """Dropless routed SwiGLU experts.

    p: {"router": (d, E), "route_bias": (E,) f32, "gate"/"up": (E, d, f),
        "down": (E, f, d)};  x: (T, d) -> (y (T, d), idx (T, K)).

    The router runs in float32 whatever the compute dtype (a bf16 logit
    flips near-tied choices).

    Every expert runs on every row, and the routing weights (zero for
    the experts a row did not choose) scale the hidden activations
    before ONE down contraction over (expert, width): three MXU-shaped
    products, no gather, sort or scatter, exact dropless semantics at
    any skew. It spends E / K times the chosen experts' operations,
    which a decode tick (bound by streaming the expert weights, read
    once either way) does not feel. Measured on the v5e at the
    published sizes (64 experts of 2048 x 1408, choose 6; PERF.md, PR
    28): 1.51 ms a layer at 32 rows and 3.09 ms at 512, against 3.13
    and 5.92 ms for rows sorted by expert through `jax.lax.ragged_dot`
    (XLA:TPU's own grouped-matmul kernel), which was dropped."""
    e = p["router"].shape[1]
    logits = jnp.einsum("td,de->te", x, p["router"],
                        preferred_element_type=jnp.float32)
    idx, w = sigmoid_topk_routing(logits, p["route_bias"], top_k, scale)
    mix = (jax.nn.one_hot(idx, e, dtype=jnp.float32)
           * w[..., None]).sum(1)                                # (T, E)
    g = jnp.einsum("td,edf->etf", x, p["gate"],
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("td,edf->etf", x, p["up"],
                   preferred_element_type=jnp.float32)
    hid = (jax.nn.silu(g) * u * mix.T[:, :, None]).astype(x.dtype)
    y = jnp.einsum("etf,efd->td", hid, p["down"],
                   preferred_element_type=jnp.float32)
    return y.astype(x.dtype), idx


def router_z_loss(gate_logits: jax.Array) -> jax.Array:
    """ST-MoE router z-loss: mean over tokens of logsumexp(logits)^2 —
    pulls the router's log-partition toward 0 without touching the
    routing distribution's shape."""
    z = jax.nn.logsumexp(gate_logits.astype(jnp.float32), axis=-1)
    return jnp.mean(z * z)


def moe_ffn(p: dict, x: jax.Array, top_k: int, capacity_factor: float,
            priority: bool = False, axis_name: str | None = None):
    """Mixture-of-experts feed-forward layer (drop-in for the dense GELU MLP).

    p: {"gate": (d, E), "wi": (E, d, ff), "bi": (E, ff),
        "wo": (E, ff, d), "bo": (E, d)}
    x: (G, S, d) -> (y (G, S, d), balance-aux scalar, router z-loss
    scalar, routing stats dict) — the auxiliaries come back UNWEIGHTED;
    the model config owns the weights (`moe_aux_weight`, `moe_z_weight`).
    `stats` (see `topk_capacity_routing`) is observability only — when a
    caller drops it, XLA dead-code-eliminates its computation.

    The two routing einsums below are where expert parallelism happens: with
    `wi`/`wo` sharded `P('ep', ...)` and `x` sharded over batch, GSPMD turns
    the (G,S,·)->(E,G,C,·) layout change into an all-to-all over 'ep'.

    `axis_name` (shard_map contexts only — see `moe_ffn_ep`): route the
    dispatch/combine buffers through an EXPLICIT `lax.all_to_all` pair
    over that mesh axis; `p` then holds this device's E/ep expert shard
    while the gate stays global. One body serves both paths, so the
    routing math cannot drift between them."""
    g, s, d = x.shape
    e = p["gate"].shape[1]                     # GLOBAL expert count
    cap = expert_capacity(s, e, top_k, capacity_factor)

    # Router in f32 regardless of compute dtype: bf16 gate logits can flip
    # top-k selections (routing is stability-critical; the softmax in
    # topk_capacity_routing is f32 already).
    logits = jnp.einsum("gsd,de->gse", x, p["gate"],
                        preferred_element_type=jnp.float32)     # (G, S, E)
    combine, dispatch, aux, stats = topk_capacity_routing(
        logits, cap, top_k, priority=priority)

    xin = jnp.einsum("gsec,gsd->egcd", dispatch.astype(x.dtype), x)
    if axis_name is not None:
        # (E, G, C, d) -> (E_local, ep*G, C, d): peer j receives every
        # peer's rows [j*E_local, (j+1)*E_local) — matching the
        # contiguous P(..., 'ep', ...) shard of the stacked expert
        # weights — blocks ordered by source peer on the group axis
        xin = jax.lax.all_to_all(xin, axis_name, split_axis=0,
                                 concat_axis=1, tiled=True)
    h = jax.nn.gelu(jnp.einsum("egcd,edf->egcf", xin, p["wi"])
                    + p["bi"][:, None, None, :])
    out = (jnp.einsum("egcf,efd->egcd", h, p["wo"])
           + p["bo"][:, None, None, :])
    if axis_name is not None:
        # inverse: scatter the group axis back, gather the expert axis
        out = jax.lax.all_to_all(out, axis_name, split_axis=1,
                                 concat_axis=0, tiled=True)
    y = jnp.einsum("gsec,egcd->gsd", combine.astype(x.dtype), out)
    return y, aux, router_z_loss(logits), stats


def moe_ffn_ep(p: dict, x: jax.Array, top_k: int, capacity_factor: float,
               axis_name: str = "ep", priority: bool = False):
    """`moe_ffn` for shard_map contexts — the expert parallelism is an
    EXPLICIT `lax.all_to_all`, not a GSPMD placement decision (inside
    shard_map there is no GSPMD to lower the resharding; same reason
    `ulysses_attention` hand-writes its head<->sequence all-to-alls).

    p: this device's expert shard — gate (d, E) REPLICATED over the ep
    axis (every token routes over all E global experts), wi/bi/wo/bo
    carrying only E/ep experts (leading dim E_local).
    x: (G, S, d) — this device's LOCAL tokens (the ep axis shards rows,
    multiplying dp for the data dimension).

    Dispatch: route locally over global E, build the (E, G, C, d)
    buffer, then all-to-all — scatter the expert axis, gather the group
    axis — so each device holds (E_local, ep*G, C, d): its own experts'
    slots from EVERY ep peer (the DeepSpeed-MoE / Tutel a2a pair,
    ridden over ICI here). Expert FFN runs local; the inverse a2a
    returns (E, G, C, d) and the combine einsum is local again.

    The body IS `moe_ffn` (one shared implementation — the routing math
    cannot drift between the GSPMD and explicit-collective paths):
    capacity competition is per (group row, expert) and each row is its
    own group, so resharding rows across dp x ep changes NOTHING about
    who gets dropped — asserted by the dp-only parity tests.

    Aux/z losses are means over LOCAL tokens; the caller owns the
    pmean over the data axes (('dp', 'ep') in the pipeline engine)."""
    e = p["gate"].shape[1]
    e_loc = p["wi"].shape[0]
    assert e % e_loc == 0, (e, e_loc)
    return moe_ffn(p, x, top_k, capacity_factor, priority=priority,
                   axis_name=axis_name)
