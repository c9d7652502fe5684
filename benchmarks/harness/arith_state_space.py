"""The parameters and bytes of a decoder whose every block holds a
state-space mixer beside its attention heads, from the configuration's
published keys alone (the `falcon_h1` names). `Shapes` answers to the
method names of `arith.Shapes` that the serving readers call, so
`readers/kernels.py` reads either.

A decode step moves, at the least: every matrix of the blocks and the
head once, the keys and values of the tokens the decoding slots hold,
and the mixers' state of every decoding row TWICE, read and written
back whole (the state is rewritten at every token: `state_rows`, which
a run's driver measures; until one says how many rows decode, none is
priced, the least the step could need)."""

from __future__ import annotations

from dataclasses import dataclass

from harness.arith import median  # noqa: F401  (the drivers' `arith.median`)


@dataclass(frozen=True)
class Shapes:
    hidden: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    tied: bool
    ssm_heads: int
    ssm_head_dim: int
    ssm_state: int
    ssm_groups: int
    ssm_conv: int
    # mean rows whose state a decode step advanced; none until a run's
    # counter says otherwise
    state_rows: float | None = None

    @classmethod
    def from_config(cls, c: dict) -> "Shapes":
        return cls(
            hidden=int(c["hidden_size"]), layers=int(c["num_hidden_layers"]),
            heads=int(c["num_attention_heads"]),
            kv_heads=int(c["num_key_value_heads"]),
            head_dim=int(c["head_dim"]), ffn=int(c["intermediate_size"]),
            vocab=int(c["vocab_size"]), tied=bool(c["tie_word_embeddings"]),
            ssm_heads=int(c["mamba_n_heads"]),
            ssm_head_dim=int(c["mamba_d_head"]),
            ssm_state=int(c["mamba_d_state"]),
            ssm_groups=int(c["mamba_n_groups"]),
            ssm_conv=int(c["mamba_d_conv"]))

    # ---- parameters, by part ----------------------------------------
    @property
    def d_ssm(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_dim(self) -> int:
        """What the convolution sees: x, then B and C a group."""
        return self.d_ssm + 2 * self.ssm_groups * self.ssm_state

    def attention_params(self) -> int:
        """W_q and W_o (hidden x heads x head_dim each), W_k and W_v
        (hidden x kv_heads x head_dim each)."""
        h = self.hidden
        return 2 * h * self.heads * self.head_dim \
            + 2 * h * self.kv_heads * self.head_dim

    def mixer_params(self) -> int:
        """The input projection (z | x B C | dt), the convolution's taps
        and bias, three vectors a head, the gated norm's scale, the
        output projection."""
        return self.hidden * (self.d_ssm + self.conv_dim + self.ssm_heads) \
            + self.conv_dim * (self.ssm_conv + 1) + 3 * self.ssm_heads \
            + self.d_ssm + self.d_ssm * self.hidden

    def ffn_params(self) -> int:
        return 3 * self.hidden * self.ffn

    def layer_params(self) -> int:
        return self.attention_params() + self.mixer_params() \
            + self.ffn_params()

    def matrix_params(self) -> int:
        emb = self.vocab * self.hidden
        return self.layers * self.layer_params() \
            + emb * (1 if self.tied else 2)

    # ---- decoding ---------------------------------------------------
    def kv_bytes_per_token(self, bytes_per_value: int = 2) -> int:
        return 2 * self.layers * self.kv_heads * self.head_dim \
            * bytes_per_value

    def state_bytes_per_row(self, bytes_per_value: int = 2) -> int:
        """What one slot's mixers keep from token to token, every layer
        together: the heads' float32 matrices and the convolution's last
        inputs (in the compute dtype)."""
        return self.layers * (
            4 * self.ssm_heads * self.ssm_head_dim * self.ssm_state
            + bytes_per_value * (self.ssm_conv - 1) * self.conv_dim)

    def ssm_step_bytes(self, rows: float, bytes_per_value: int = 2) -> float:
        """Slab bytes a decode step moves for `rows` decoding rows: each
        row's state read and written."""
        return 2.0 * rows * self.state_bytes_per_row(bytes_per_value)

    def weight_bytes_per_step(self, bytes_per_value: int = 2) -> int:
        """Every block's parameters and the head (a step gathers a few
        rows of the untied embedding)."""
        return bytes_per_value * (self.layers * self.layer_params()
                                  + self.vocab * self.hidden)

    def decode_step_min_bytes(self, live_tokens: float,
                              bytes_per_value: int = 2) -> float:
        """Least bytes one decode step moves: `weight_bytes_per_step`,
        the keys and values of the `live_tokens` the decoding slots
        hold, and `ssm_step_bytes` of the rows that decode."""
        return self.weight_bytes_per_step(bytes_per_value) \
            + live_tokens * self.kv_bytes_per_token(bytes_per_value) \
            + self.ssm_step_bytes(self.state_rows or 0.0, bytes_per_value)
