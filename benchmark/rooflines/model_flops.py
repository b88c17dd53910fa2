"""Operations per token that the forward and backward passes of a dense GPT
require (recomputation not counted), for model FLOP/s utilization.

Per token and layer: the four projections (qkv ``3h^2``, out ``h^2``, the two
FFN matrices ``2 h f``) at 2 operations a multiply-add, plus causal attention
``2 * 2 * S * h * 0.5`` (QK^T and PV over, on average, half the sequence).
The head is ``2 h V``.  Backward is twice forward: x3 in all.
"""
from __future__ import annotations

from typing import Dict


def train_flops_per_token(model: Dict, seq: int) -> float:
    h, layers = model["hidden_size"], model["num_layers"]
    f = model.get("intermediate_size") or 4 * h
    vocab = model["vocab_size"]
    per_layer = 2.0 * (4 * h * h + 2 * h * f) + 2.0 * seq * h
    return 3.0 * (layers * per_layer + 2.0 * h * vocab)
