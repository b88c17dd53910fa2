"""Operations the serving steps of the decoder-hybrid-decoder require, for the
whole step's share of the chip's peak (``model.serve_mfu.*``).  The
configuration file names this module under ``flops``.

A token a step carries (``block_rows``) goes, at 2 operations a multiply-add,
through every layer's operator and feed-forward.  With ``h`` the hidden size,
``di`` the state-space width, ``ds`` its state and ``dr`` its step rank: a
state-space layer's ``W_in`` (``h x 2 di``), ``W_x`` (``di x (dr + 2 ds)``),
``W_dt`` (``dr x di``) and ``W_out`` (``di x h``) and the recurrence (``di x
ds`` a row, counted as one multiply-add); an attention layer's ``Wqkv`` (``h x
(q + 2 kv)``) and ``Wo``; a cross layer's ``Wq`` and ``Wo``; a memory unit's
``W1`` (``h x di``) and ``W2``; and every layer's feed-forward (``3 x h x
intermediate_size``).  The head, ``2 h V``, is needed for the rows that yield a
token (``tokens``).  Attention is what ``phi4flash_ragged.needed_by_counters``
counts from the steps' two work lists.  Padding rows, the convolution's taps,
the gates, the norms, the differential combination, the pool, ring and state
writes and the embedding's gather count for nothing.
"""
from __future__ import annotations

from typing import Dict

from . import phi4flash_ragged


def flops_per_token(config: Dict) -> float:
    m, ssm = config["model"], config["state_space"]
    h, f = m["hidden_size"], m["intermediate_size"]
    d = h // m["num_attention_heads"]
    q, kv = m["num_attention_heads"] * d, m["num_key_value_heads"] * d
    di, ds, dr = ssm["mamba_expand"] * h, ssm["mamba_d_state"], ssm["mamba_dt_rank"]
    quarter = m["num_hidden_layers"] // 4
    state_space = 2 * h * di + di * (dr + 2 * ds) + dr * di + di * h + di * ds
    attention = h * (q + 2 * kv) + q * h
    cross = 2 * h * q
    memory_unit = 2 * h * di
    return 2.0 * ((quarter + 1) * state_space + (quarter + 1) * attention
                  + (quarter - 1) * (cross + memory_unit)
                  + m["num_hidden_layers"] * 3 * h * f)


def head_flops_per_row(model: Dict) -> float:
    return 2.0 * model["hidden_size"] * model["vocab_size"]


def serve_flops(ctx: Dict, delta: Dict[str, float]) -> float:
    """From the engine's counter deltas over the traced steps."""
    return (delta["block_rows"] * flops_per_token(ctx["config"])
            + delta["tokens"] * head_flops_per_row(ctx["config"]["model"])
            + phi4flash_ragged.needed_by_counters(ctx, delta)["flops"])
