"""Operations the serving steps of the hybrid conv / grouped-query-attention
decoder with a routed feed-forward require, for the whole step's share of the
chip's peak (``model.serve_mfu.*``).  The configuration file names this module
under ``flops``.

A token a step carries (``block_rows``) goes, at 2 operations a multiply-add,
through every held layer's operator: a conv layer's ``W_in`` (``h x 3h``) and
``W_out`` (``h x h``), or an attention layer's ``Wq``, ``Wo`` (``h x h``) and
``Wk``, ``Wv`` (``h x kv_heads x head_dim``); then through the layer's
feed-forward: the dense one (``3 x h x intermediate_size``) in the first
``num_dense_layers``, else the router (``h x num_experts``) and the
``num_experts_per_tok`` chosen experts (``3 x h x moe_intermediate_size`` each;
an expert no token chose is needed by nothing).  The head, ``2 h V``, is needed
for the rows that yield a token (``tokens``).  Attention is what
``lfm2_ragged.needed_by_counters`` counts from the steps' work lists.  Padding
rows, the taps and gates of the convolution (elementwise), the norms, the
rotation, the pool and tail writes and the embedding's gather count for nothing.
"""
from __future__ import annotations

from typing import Dict

from . import lfm2_ragged


def flops_per_token(config: Dict) -> float:
    m = config["model"]
    h = m["hidden_size"]
    kinds = config["layer_types"][:m["num_hidden_layers"]]
    kv = m["num_key_value_heads"] * (h // m["num_attention_heads"])
    conv, attn = 4 * h * h, 2 * h * h + 2 * h * kv
    dense = 3 * h * m["intermediate_size"]
    routed = h * m["num_experts"] + m["num_experts_per_tok"] * 3 * h * m["moe_intermediate_size"]
    total = 0
    for l, kind in enumerate(kinds):
        total += conv if kind == "conv" else attn
        total += dense if l < m["num_dense_layers"] else routed
    return 2.0 * total


def head_flops_per_row(model: Dict) -> float:
    return 2.0 * model["hidden_size"] * model["vocab_size"]


def serve_flops(ctx: Dict, delta: Dict[str, float]) -> float:
    """From the engine's counter deltas over the traced steps."""
    return (delta["block_rows"] * flops_per_token(ctx["config"])
            + delta["tokens"] * head_flops_per_row(ctx["config"]["model"])
            + lfm2_ragged.needed_by_counters(ctx, delta)["flops"])
