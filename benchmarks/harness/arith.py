"""Metric arithmetic kept with the benchmark: percentiles, the spread the
bounds are set from, and the operations and bytes a step needs, computed
from the configuration's shapes alone.

`Shapes` is read from a configuration file's published keys (the Hugging
Face names), never from the program's own config object."""

from __future__ import annotations

import math
import statistics
from dataclasses import dataclass


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) with linear interpolation between the
    two nearest ranks; raises on an empty list."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no values")
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def median(values) -> float:
    return percentile(values, 50.0)


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, with `statistics.quantiles(values, n=4)`: the spread the
    contract sets bounds from."""
    q1, _, q3 = statistics.quantiles([float(v) for v in values], n=4)
    return (q3 - q1) / statistics.median(values)


@dataclass(frozen=True)
class Shapes:
    hidden: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    gated_ffn: bool
    tied: bool
    window: int          # 0 = full causal attention

    @classmethod
    def from_config(cls, c: dict) -> "Shapes":
        heads = int(c["num_attention_heads"])
        return cls(
            hidden=int(c["hidden_size"]),
            layers=int(c["num_hidden_layers"]),
            heads=heads,
            kv_heads=int(c.get("num_key_value_heads") or heads),
            head_dim=int(c.get("head_dim") or c["hidden_size"] // heads),
            ffn=int(c["intermediate_size"]),
            vocab=int(c["vocab_size"]),
            gated_ffn=c["hidden_act"] in ("silu", "swiglu"),
            tied=bool(c["tie_word_embeddings"]),
            window=int(c.get("sliding_window") or 0))

    # ---- parameters -------------------------------------------------
    def layer_matrix_params(self) -> int:
        """Weights of one block's matrices (the published model has no
        biases; the repo's zero biases and norm vectors are not counted)."""
        h, kv = self.hidden, self.kv_heads * self.head_dim
        attn = h * (self.heads * self.head_dim) + 2 * h * kv \
            + (self.heads * self.head_dim) * h
        return attn + (3 if self.gated_ffn else 2) * h * self.ffn

    def matrix_params(self) -> int:
        """Every matrix a forward pass reads: blocks, the embedding, and
        the head where it is not tied to the embedding."""
        emb = self.vocab * self.hidden
        return self.layers * self.layer_matrix_params() \
            + emb * (1 if self.tied else 2)

    # ---- training ---------------------------------------------------
    def avg_causal_context(self, seq_len: int) -> float:
        t, w = seq_len, self.window
        if w and w < t:
            return (w * (w + 1) / 2 + (t - w) * w) / t
        return (t + 1) / 2

    def train_flops_per_token(self, seq_len: int) -> float:
        """Matmul operations the forward and backward passes need for one
        token at `seq_len` (3 x forward, PaLM appendix B; recomputation is
        not counted): every projection, the FFN, QK^T and AV over the
        causal-average context, and the vocabulary head."""
        per_layer = 2.0 * self.layer_matrix_params()
        per_layer += 2 * (2.0 * self.heads * self.head_dim
                          * self.avg_causal_context(seq_len))
        fwd = self.layers * per_layer + 2.0 * self.hidden * self.vocab
        return 3.0 * fwd

    # ---- decoding ---------------------------------------------------
    def kv_bytes_per_token(self, bytes_per_value: int = 2) -> int:
        return 2 * self.layers * self.kv_heads * self.head_dim \
            * bytes_per_value

    def decode_step_min_bytes(self, live_tokens: float,
                              bytes_per_value: int = 2) -> float:
        """Least bytes one decode step moves: every weight matrix once
        (the embedding only where it is also the head: a step gathers a
        few rows of an untied one) plus the cached keys and values of the
        tokens the decoding slots hold."""
        weights = self.layers * self.layer_matrix_params() \
            + self.vocab * self.hidden
        return weights * bytes_per_value \
            + live_tokens * self.kv_bytes_per_token(bytes_per_value)
