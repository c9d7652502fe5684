"""The operations and bytes of a decoder with window and full attention
layers mixed, a gate on the attention output and routed experts, from
the configuration's published keys alone (the `afmoe` names). `Shapes`
answers to the method names of `arith.Shapes` that the serving readers
call, so `readers/kernels.py` reads either.

The least bytes of a decode step count the experts the step's rows CHOSE
(`experts_touched`, as `arith_latent_experts`) and, of the cache, what a
step's queries can SEE: in a full layer every token of the context, in a
window layer `min(context, window)` of them. The readers hand over the
tokens the decoding slots hold, summed; `windowed_share` is the part of
that sum a window layer's queries see, which a run's driver measures
(`drivers/serve_window_experts.py`). Until one says otherwise every
expert and every token is priced, the most the step could need."""

from __future__ import annotations

from dataclasses import dataclass

from harness.arith import median  # noqa: F401  (the drivers' `arith.median`)


@dataclass(frozen=True)
class Shapes:
    hidden: int
    layers: int
    dense_layers: int        # leading layers with a dense FFN
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int                 # the dense layers' SwiGLU width
    expert_ffn: int
    experts: int
    experts_per_token: int
    shared_experts: int
    vocab: int
    tied: bool
    window: int
    window_layers: int       # layers that see `window` tokens
    # mean distinct experts a routed layer's live rows chose in a decode
    # step; every expert until a run's counter says otherwise
    experts_touched: float | None = None
    # sum over rows of min(context, window) over the sum of contexts,
    # mean over a run's decode steps; 1 until a run says otherwise
    windowed_share: float | None = None

    @classmethod
    def from_config(cls, c: dict) -> "Shapes":
        kinds = list(c["layer_types"])
        return cls(
            hidden=int(c["hidden_size"]), layers=int(c["num_hidden_layers"]),
            dense_layers=int(c["num_dense_layers"]),
            heads=int(c["num_attention_heads"]),
            kv_heads=int(c["num_key_value_heads"]),
            head_dim=int(c["head_dim"]), ffn=int(c["intermediate_size"]),
            expert_ffn=int(c["moe_intermediate_size"]),
            experts=int(c["num_experts"]),
            experts_per_token=int(c["num_experts_per_tok"]),
            shared_experts=int(c["num_shared_experts"]),
            vocab=int(c["vocab_size"]), tied=bool(c["tie_word_embeddings"]),
            window=int(c["sliding_window"]),
            window_layers=kinds.count("sliding_attention"))

    # ---- parameters (matrices only: the model has no biases) --------
    @property
    def routed_layers(self) -> int:
        return self.layers - self.dense_layers

    @property
    def full_layers(self) -> int:
        return self.layers - self.window_layers

    def attention_params(self) -> int:
        """W_q, W_o and the gate (hidden x heads x head_dim each), W_k
        and W_v (hidden x kv_heads x head_dim each)."""
        h, q = self.hidden, self.heads * self.head_dim
        return 3 * h * q + 2 * h * self.kv_heads * self.head_dim

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
    def kv_bytes_per_token_layer(self, bytes_per_value: int = 2) -> int:
        return 2 * self.kv_heads * self.head_dim * bytes_per_value

    def kv_bytes_per_token(self, bytes_per_value: int = 2) -> float:
        """Cache bytes a step reads for one token of context, every
        layer together: a full layer reads each, a window layer the
        `windowed_share` of them its queries still see."""
        share = 1.0 if self.windowed_share is None else self.windowed_share
        return (self.full_layers + share * self.window_layers) \
            * self.kv_bytes_per_token_layer(bytes_per_value)

    def weight_bytes_per_step(self, bytes_per_value: int = 2) -> float:
        """Every matrix that every token uses once (attention, the dense
        FFN, routers, shared experts, the head; a step gathers a few
        rows of the untied embedding) and the experts its rows chose."""
        touched = self.experts if self.experts_touched is None \
            else self.experts_touched
        return bytes_per_value * (
            self.dense_layers * self.dense_layer_params()
            + self.routed_layers * (self.routed_layer_fixed_params()
                                    + touched * self.expert_params())
            + self.vocab * self.hidden)

    def decode_step_min_bytes(self, live_tokens: float,
                              bytes_per_value: int = 2) -> float:
        """Least bytes one decode step moves: `weight_bytes_per_step`
        and the keys and values its queries see of the `live_tokens` the
        decoding slots hold."""
        return self.weight_bytes_per_step(bytes_per_value) \
            + live_tokens * self.kv_bytes_per_token(bytes_per_value)
