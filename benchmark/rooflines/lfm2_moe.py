"""Operations and bytes the routed experts need, from the cache's device
counters over the traced steps.  Kernel: ``_gmm_kernel`` (three grouped
products a routed layer: ``W1``, ``W3``, ``W2``), read with the activation
between them under scope ``moe.experts``.

An expert that received at least one token in a layer of a step
(``moe_experts_touched`` counts these, summed over layers and steps) has its
three matrices read once: ``3 x hidden x width`` elements.  A token-expert pair
(``moe_assignments``) is ``6 x hidden x width`` operations (three products at 2
a multiply-add), one row of ``hidden`` in (bfloat16) and one out (float32).
An expert no token chose is not read and is not counted; padding rows are
routed nowhere and are not counted.  The kernel reads at least these bytes (a
visit of a (row tile, expert) pair reads the expert's whole tile column), so
the share cannot pass 100.
"""
from __future__ import annotations

from typing import Dict


def expert_bytes(model: Dict, itemsize: int = 2) -> int:
    return 3 * model["hidden_size"] * model["moe_intermediate_size"] * itemsize


def pair_flops(model: Dict) -> int:
    return 6 * model["hidden_size"] * model["moe_intermediate_size"]


def needed_by_counters(ctx: Dict, delta: Dict[str, float]) -> Dict[str, float]:
    model = ctx["config"]["model"]
    pairs, touched = delta["moe_assignments"], delta["moe_experts_touched"]
    rows = pairs * model["hidden_size"] * (2 + 4)
    return {"flops": float(pairs * pair_flops(model)),
            "bytes": float(touched * expert_bytes(model) + rows)}
