"""Operations and bytes ragged paged attention needs in the decoder-hybrid-
decoder's steps, from the steps' real work lists.  Kernel: ``_ragged_kernel``.

The step launches the kernel sixteen times over TWO work lists.  ``work_items``
is the full-attention layer's list (a token block against every page of its
slot's context), walked by that layer and again by each cross-attention layer,
which reads the same keys and values: ``1 + cross layers`` passes.
``window_work_items`` is the window layers' list (a token block against the
ring pages that hold its window), walked once a window layer.  An item reads
one K page and one V page of every K/V head (``num_key_value_heads`` of
``head_dim``: the pool folds two heads into a row, the bytes are the same) ONCE,
and multiplies the block's real query rows of every query head against them
twice.  A block's queries (``head_dim`` wide) and outputs (a differential
pair's ``P [v1 | v2]``, ``2 x head_dim`` wide) cross HBM once a block and pass.
The kernel moves at least this (a grid step carries all the heads of its page,
whole query blocks with their padding rows, and queries padded to the row), so
the share cannot pass 100.
"""
from __future__ import annotations

from typing import Dict


def passes(model: Dict) -> Dict[str, int]:
    """How often each work list is walked a step."""
    quarter = model["num_hidden_layers"] // 4
    return {"work_items": quarter, "window_work_items": quarter}


def needed(*, items: float, mean_rows: float, rows: float, q_heads: int, kv_heads: int,
           page: int, head_dim: int, itemsize: int = 2) -> Dict[str, float]:
    """``{"flops", "bytes"}`` of one pass over ``items`` work items."""
    flops = 4.0 * mean_rows * page * head_dim * q_heads * items
    kv = 2.0 * page * head_dim * itemsize * kv_heads * items
    qo = 3.0 * rows * head_dim * itemsize * q_heads
    return {"flops": flops, "bytes": kv + qo}


def needed_by_counters(ctx: Dict, delta: Dict[str, float]) -> Dict[str, float]:
    """From the engine's counter deltas over the traced steps."""
    cfg, eng = ctx["config"]["model"], ctx["cell"]["engine"]
    blocks = delta["block_row_capacity"] / ctx["facts"]["token_block"]
    total = {"flops": 0.0, "bytes": 0.0}
    for counter, n in passes(cfg).items():
        one = needed(items=delta[counter], rows=delta["block_rows"],
                     mean_rows=delta["block_rows"] / blocks if blocks else 0.0,
                     q_heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
                     page=eng["page_size"],
                     head_dim=cfg["hidden_size"] // cfg["num_attention_heads"])
        for k in total:
            total[k] += n * one[k]
    return total
