"""Operations and bytes ragged paged attention needs in the steps of the
decoders of window and full grouped-query layers, from the steps' real work
lists.  Kernel: ``_ragged_kernel``.

The step launches the kernel once a layer over TWO work lists.
``work_items`` is the full layers' list (a token block against every page of
its slot's context in the paged pool), walked once a ``full_attention``
layer; ``window_work_items`` is the window layers' list (a token block
against the ring pages that hold its window), walked once a
``sliding_attention`` layer; both over the same blocks.  An item reads one K
page and one V page of every K/V head (``num_key_value_heads`` of
``head_dim``; K and V are pools of their own, each read once) and multiplies
the block's real query rows of every query head against them twice; a
block's queries and outputs cross HBM once a launch (``lfm2_ragged.needed``).
The kernel moves at least this (a grid step carries all the K/V heads of its
page, and whole query blocks, padding rows included), so the share cannot
pass 100.
"""
from __future__ import annotations

from typing import Dict

from .lfm2_ragged import needed


def passes(config: Dict) -> Dict[str, int]:
    """How often each work list is walked a step: once a layer of its kind
    among the layers held."""
    held = config["layer_types"][:config["model"]["num_hidden_layers"]]
    return {"work_items": held.count("full_attention"),
            "window_work_items": held.count("sliding_attention")}


def needed_by_counters(ctx: Dict, delta: Dict[str, float]) -> Dict[str, float]:
    """From the engine's counter deltas over the traced steps."""
    cfg, eng = ctx["config"]["model"], ctx["cell"]["engine"]
    blocks = delta["block_row_capacity"] / ctx["facts"]["token_block"]
    total = {"flops": 0.0, "bytes": 0.0}
    for counter, n in passes(ctx["config"]).items():
        one = needed(items=delta[counter], blocks=blocks, rows=delta["block_rows"],
                     q_heads=cfg["num_attention_heads"],
                     kv_heads=cfg["num_key_value_heads"], page=eng["page_size"],
                     head_dim=cfg["head_dim"])
        for k in total:
            total[k] += n * one[k]
    return total
