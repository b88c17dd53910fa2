"""Operations and bytes ragged paged attention needs under GROUPED queries,
from the steps' real work lists.  Kernel: ``_ragged_kernel``.

As ``ragged.py`` counts it (a work item is one (token block, page) pair; the
engine counts real items, real rows and blocks a step), with two head counts:
an item reads one K page and one V page for each of the pool's K/V heads
(``num_key_value_heads``), and multiplies the block's real query rows of every
QUERY head (``num_attention_heads``) against them twice.  A block's queries
and outputs cross HBM once per block, by the query heads.  Once for every layer
that holds K/V: the ``full_attention`` entries among the first
``num_hidden_layers`` of the configuration's ``layer_types``.  The kernel moves
at least this (a grid step carries all the K/V heads of its page, and whole
query blocks, padding rows included), so the share cannot pass 100.
"""
from __future__ import annotations

from typing import Dict


def attention_layers(config: Dict) -> int:
    held = config["layer_types"][:config["model"]["num_hidden_layers"]]
    return sum(1 for kind in held if kind == "full_attention")


def needed(*, items: float, blocks: float, rows: float, q_heads: int, kv_heads: int,
           page: int, head_dim: int, itemsize: int = 2) -> Dict[str, float]:
    """``{"flops", "bytes"}`` of one layer's launch."""
    mean_rows = rows / blocks if blocks else 0.0
    flops = 4.0 * mean_rows * page * head_dim * q_heads * items
    kv = 2.0 * page * head_dim * itemsize * kv_heads * items
    qo = 2.0 * rows * head_dim * itemsize * q_heads
    return {"flops": flops, "bytes": kv + qo}


def needed_by_counters(ctx: Dict, delta: Dict[str, float]) -> Dict[str, float]:
    """From the engine's counter deltas over the traced steps."""
    cfg, eng = ctx["config"]["model"], ctx["cell"]["engine"]
    one = needed(items=delta["work_items"],
                 blocks=delta["block_row_capacity"] / ctx["facts"]["token_block"],
                 rows=delta["block_rows"], q_heads=cfg["num_attention_heads"],
                 kv_heads=cfg["num_key_value_heads"], page=eng["page_size"],
                 head_dim=cfg["hidden_size"] // cfg["num_attention_heads"])
    return {k: v * attention_layers(ctx["config"]) for k, v in one.items()}
