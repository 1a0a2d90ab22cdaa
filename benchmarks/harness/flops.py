"""Operations the forward and backward passes of a GPT-family decoder need,
per token. Recomputed operations (``remat``) do not count.

Per layer, with D = d_model, F = d_ff, T = sequence length:

- the four matrix products (QKV ``D x 3D``, output ``D x D``, up ``D x F``,
  down ``F x D``) cost ``2 * (4 D^2 + 2 D F)`` a token in the forward pass;
- causal attention: a token at position t reads t + 1 keys, ``(T + 1) / 2``
  on average; scores and the weighted sum of values are two products of
  ``2 * D`` operations a key, so ``4 D (T + 1) / 2`` a token;
- the head (tied to the embedding) costs ``2 D V`` a token; the embedding
  lookup, LayerNorm, softmax, GELU and the optimizer are left out (under
  1% at these widths).

The backward pass costs twice the forward (one product for the input's
gradient, one for the weight's), so a training token costs three forwards.
"""
from __future__ import annotations

from typing import Dict


def forward_flops_per_token(d: Dict[str, int], seq_len: int) -> float:
    D, F, L, V = d["d_model"], d["d_ff"], d["layers"], d["vocab"]
    per_layer = 2 * (4 * D * D + 2 * D * F) + 4 * D * (seq_len + 1) / 2
    return L * per_layer + 2 * D * V


def train_flops_per_token(d: Dict[str, int], seq_len: int) -> float:
    return 3 * forward_flops_per_token(d, seq_len)
