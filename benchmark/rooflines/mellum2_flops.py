"""Operations the serving steps of the decoders of window and full
grouped-query layers with softmax-routed experts require, for the whole
step's share of the chip's peak (``model.serve_mfu.*``).  The configuration
file names this module under ``flops``.

A token a step carries (``block_rows``) goes, at 2 operations a multiply-add,
through every held layer: ``Wq`` and ``Wo`` (``h x 32 x 128``), ``Wk`` and
``Wv`` (``h x 4 x 128``), the router (``h x num_experts``) and the
``num_experts_per_tok`` experts it picks (``3 h x moe_intermediate_size``
each).  Attention is what ``mellum2_ragged.needed_by_counters`` counts from
the steps' two work lists, the head ``2 h V`` for the rows that yield a token
(``tokens``).  Padding rows, the norms, the rotations, the softmaxes, the pool
and ring writes and the embedding's gather count for nothing.
"""
from __future__ import annotations

from typing import Dict

from . import mellum2_ragged


def flops_per_token(config: Dict) -> float:
    m = config["model"]
    h = m["hidden_size"]
    q = m["num_attention_heads"] * m["head_dim"]
    kv = m["num_key_value_heads"] * m["head_dim"]
    layer = (2 * h * q + 2 * h * kv + h * m["num_experts"]
             + m["num_experts_per_tok"] * 3 * h * m["moe_intermediate_size"])
    return 2.0 * m["num_hidden_layers"] * layer


def serve_flops(ctx: Dict, delta: Dict[str, float]) -> float:
    """From the engine's counter deltas over the traced steps."""
    model = ctx["config"]["model"]
    return (delta["block_rows"] * flops_per_token(ctx["config"])
            + delta["tokens"] * 2.0 * model["hidden_size"] * model["vocab_size"]
            + mellum2_ragged.needed_by_counters(ctx, delta)["flops"])
