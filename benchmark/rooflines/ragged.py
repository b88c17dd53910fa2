"""Operations and bytes ragged paged attention needs, from the steps' real
work lists.  Kernel: ``_ragged_kernel``.

A work item is one (token block, page) pair.  For every head it reads one K
page and one V page (``page * D`` elements each) and multiplies the block's
real query rows against them twice (QK^T and PV): ``4 * rows * page * D``
operations.  A block's queries and outputs cross HBM once per block.  The
engine counts real items (``work_items``), real rows (``block_rows``) and
blocks (``block_row_capacity / token_block``) per step; the sums over the
traced steps are what this takes, once for every layer (each layer calls the
kernel on the step's work list against its own pages).  Padding rows and the
clamped tail of the work list are not needed and are not counted.
"""
from __future__ import annotations

from typing import Dict


def needed(*, items: float, blocks: float, rows: float, heads: int, page: int,
           head_dim: int, itemsize: int = 2) -> Dict[str, float]:
    """``{"flops", "bytes"}`` for ``items`` work items over ``blocks`` token
    blocks holding ``rows`` real query rows in all."""
    mean_rows = rows / blocks if blocks else 0.0
    flops = 4.0 * mean_rows * page * head_dim * heads * items
    kv = 2.0 * page * head_dim * itemsize * heads * items
    qo = 2.0 * rows * head_dim * itemsize * heads
    return {"flops": flops, "bytes": kv + qo}


def needed_by_counters(ctx: Dict, delta: Dict[str, float]) -> Dict[str, float]:
    """From the engine's counter deltas over the traced steps."""
    cfg, eng = ctx["config"]["model"], ctx["cell"]["engine"]
    token_block = ctx["facts"]["token_block"]
    one = needed(items=delta["work_items"],
                 blocks=delta["block_row_capacity"] / token_block,
                 rows=delta["block_rows"], heads=cfg["num_heads"],
                 page=eng["page_size"],
                 head_dim=cfg["hidden_size"] // cfg["num_heads"])
    return {k: v * cfg["num_layers"] for k, v in one.items()}
