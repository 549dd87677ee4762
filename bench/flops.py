"""Operations a served request requires, from its shapes alone.

Counted are only the operations the model needs, at two per
multiply-accumulate:

  * prefill of S prompt tokens: every layer over all S rows, attention of
    row i over its i + 1 causal keys, and the output head for the last row
    only (that is the one that yields the first token);
  * one decoded token that attends to n keys: every layer for one row,
    attention over the n keys, and the head for that row.

What a program happens to compute beyond that (a head over every prompt
row, padded tiles, requantization) is not counted, so a change that drops
such work raises the share of the peak these counts are divided by.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Dims:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    gated: bool               # three MLP matrices (gate, up, down), else two

    def layer_weights(self) -> int:
        """Multiply-accumulates per row through one layer's matrices."""
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        mlp = (3 if self.gated else 2) * self.d_model * self.d_ff
        return self.d_model * (q + 2 * kv) + q * self.d_model + mlp


def attention_ops(dims: Dims, keys: int) -> int:
    """QK^T and AV of one query row over `keys` keys, all heads."""
    return 4 * dims.heads * dims.head_dim * keys


def head_ops(dims: Dims) -> int:
    return 2 * dims.d_model * dims.vocab


def prefill_ops(dims: Dims, seq: int) -> int:
    causal_keys = seq * (seq + 1) // 2
    per_layer = (2 * seq * dims.layer_weights()
                 + attention_ops(dims, causal_keys))
    return dims.layers * per_layer + head_ops(dims)


def decode_ops(dims: Dims, keys: int) -> int:
    """One generated token whose query attends to `keys` cached keys (its
    own included)."""
    per_layer = 2 * dims.layer_weights() + attention_ops(dims, keys)
    return dims.layers * per_layer + head_ops(dims)
