"""The operations and bytes of a decoder with latent attention and
routed experts, from the configuration's published keys alone (the
`deepseek_v3` names). `Shapes` answers to the method names of
`arith.Shapes` that the serving readers call, so `readers/kernels.py`
reads either.

The least bytes of a decode step count the experts the step's rows
CHOSE, a number the program reports (`experts_touched`, mean distinct
experts a routed layer over the window's ticks): the same work whatever
implements the layer. An implementation that streams all the experts
when fewer were chosen reads more than the least, and its share falls."""

from __future__ import annotations

from dataclasses import dataclass

from harness.arith import median  # noqa: F401  (the drivers' `arith.median`)


@dataclass(frozen=True)
class Shapes:
    hidden: int
    layers: int
    dense_layers: int        # leading layers with a dense FFN
    heads: int
    q_nope: int
    q_rope: int
    v_head: int
    kv_rank: int
    ffn: int                 # the dense layers' SwiGLU width
    expert_ffn: int
    experts: int
    experts_per_token: int
    shared_experts: int
    vocab: int
    tied: bool
    # mean distinct experts a routed layer's live rows chose in a decode
    # step; every expert until a run's counter says otherwise
    experts_touched: float | None = None

    @classmethod
    def from_config(cls, c: dict) -> "Shapes":
        return cls(
            hidden=int(c["hidden_size"]), layers=int(c["num_hidden_layers"]),
            dense_layers=int(c["first_k_dense_replace"]),
            heads=int(c["num_attention_heads"]),
            q_nope=int(c["qk_nope_head_dim"]), q_rope=int(c["qk_rope_head_dim"]),
            v_head=int(c["v_head_dim"]), kv_rank=int(c["kv_lora_rank"]),
            ffn=int(c["intermediate_size"]),
            expert_ffn=int(c["moe_intermediate_size"]),
            experts=int(c["n_routed_experts"]),
            experts_per_token=int(c["num_experts_per_tok"]),
            shared_experts=int(c["n_shared_experts"]),
            vocab=int(c["vocab_size"]), tied=bool(c["tie_word_embeddings"]))

    # ---- parameters (matrices only: the model has no biases) --------
    @property
    def routed_layers(self) -> int:
        return self.layers - self.dense_layers

    def attention_params(self) -> int:
        h = self.hidden
        return (h * self.heads * (self.q_nope + self.q_rope)          # W_q
                + h * (self.kv_rank + self.q_rope)                    # W_kva
                + self.kv_rank * self.heads * (self.q_nope + self.v_head)
                + self.heads * self.v_head * h)                       # W_o

    def expert_params(self) -> int:
        """One routed expert: a SwiGLU of width `expert_ffn`."""
        return 3 * self.hidden * self.expert_ffn

    def dense_layer_params(self) -> int:
        return self.attention_params() + 3 * self.hidden * self.ffn

    def routed_layer_fixed_params(self) -> int:
        """What every token reads of a routed layer: attention, router,
        the shared experts."""
        return self.attention_params() + self.hidden * self.experts \
            + self.shared_experts * self.expert_params()

    def routed_layer_params(self) -> int:
        return self.routed_layer_fixed_params() \
            + self.experts * self.expert_params()

    def matrix_params(self) -> int:
        emb = self.vocab * self.hidden
        return self.dense_layers * self.dense_layer_params() \
            + self.routed_layers * self.routed_layer_params() \
            + emb * (1 if self.tied else 2)

    # ---- decoding ---------------------------------------------------
    def kv_bytes_per_token(self, bytes_per_value: int = 2) -> int:
        """The latent rows a token leaves: `kv_rank + q_rope` values a
        layer (their content; the TPU stores them padded to whole
        lanes)."""
        return self.layers * (self.kv_rank + self.q_rope) * bytes_per_value

    def decode_step_min_bytes(self, live_tokens: float,
                              bytes_per_value: int = 2) -> float:
        """Least bytes one decode step moves: every matrix that every
        token uses once (attention, the dense FFN, routers, shared
        experts, the head; a step gathers a few rows of the untied
        embedding), the experts its rows chose, and the latent rows of
        the tokens the decoding slots hold."""
        touched = self.experts if self.experts_touched is None \
            else self.experts_touched
        weights = self.dense_layers * self.dense_layer_params() \
            + self.routed_layers * (self.routed_layer_fixed_params()
                                    + touched * self.expert_params()) \
            + self.vocab * self.hidden
        return weights * bytes_per_value \
            + live_tokens * self.kv_bytes_per_token(bytes_per_value)
