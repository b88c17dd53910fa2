"""Operations and bytes the causal flash-attention algorithm needs per call,
from shapes.  Kernels: ``_fwd_kernel``, ``_bwd_dkv_kernel``, ``_bwd_dq_kernel``.

One matmul of the algorithm is ``2 * S * S * D`` operations for one (batch,
head), halved by causality.  Forward needs two (QK^T, PV).  Backward needs
five: the recomputed QK^T (flash keeps no probabilities), dP = dO V^T,
dV = P^T dO, dK = dS^T Q, dQ = dS K.  The two backward kernels split them and
each recomputes QK^T and dP for itself; the second computation is not needed
by the algorithm and is not counted: four matmuls go to ``_bwd_dkv_kernel``
and one to ``_bwd_dq_kernel``.  Bytes: each of q, k, v, o (and do, dq, dk, dv
in backward) crosses HBM once.
"""
from __future__ import annotations

from typing import Dict

MATMULS = {"_fwd_kernel": 2, "_bwd_dkv_kernel": 4, "_bwd_dq_kernel": 1}
# [S, D] arrays crossing HBM per (batch, head): fwd q k v o; dkv q k v do dk dv
# (o enters through the row statistics); dq q k v do dq
ARRAYS = {"_fwd_kernel": 4, "_bwd_dkv_kernel": 6, "_bwd_dq_kernel": 5}


def needed(kernel: str, *, batch: int, heads: int, seq: int, head_dim: int,
           itemsize: int = 2, causal: bool = True) -> Dict[str, float]:
    """``{"flops", "bytes"}`` one call of ``kernel`` needs."""
    if kernel not in MATMULS:
        raise KeyError(f"not a flash kernel: {kernel!r}")
    per_matmul = 2.0 * seq * seq * head_dim * (0.5 if causal else 1.0)
    pairs = batch * heads
    return {"flops": MATMULS[kernel] * per_matmul * pairs,
            "bytes": float(ARRAYS[kernel] * seq * head_dim * itemsize * pairs)}


def geometry(ctx: Dict) -> Dict[str, int]:
    """The per-device call shape of a train cell: batch over dp, heads over mp."""
    mesh = ctx["cell"].get("mesh") or {}
    cfg, traffic = ctx["config"], ctx["traffic"]
    hidden, heads = cfg["model"]["hidden_size"], cfg["model"]["num_heads"]
    return {"batch": traffic["global_batch"] // int(mesh.get("dp", 1)),
            "heads": heads // int(mesh.get("mp", 1)),
            "seq": traffic["sequence"], "head_dim": hidden // heads}


def needed_by_calls(ctx: Dict, calls: Dict[str, int]) -> Dict[str, float]:
    """Sum over the traced calls: ``calls`` maps kernel name -> call count."""
    geo = geometry(ctx)
    total = {"flops": 0.0, "bytes": 0.0}
    for kernel, count in calls.items():
        one = needed(kernel, **geo)
        total["flops"] += count * one["flops"]
        total["bytes"] += count * one["bytes"]
    return total
