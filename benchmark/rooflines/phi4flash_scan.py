"""Operations and bytes the selective-scan launch needs in the decoder-hybrid-
decoder's steps, from the steps' real runs and rows.  Kernel:
``_ssm_scan_kernel``.

A run (``ssm_runs``: a decode token or a prefill chunk) loads its slot's state
and stores it: ``2 x d_inner x d_state`` float32 a state-space layer.  A row
(``ssm_rows``) reads its step size and its input and writes its output
(``d_inner`` float32 each) and reads ``B`` and ``C`` (``d_state`` float32
each), a layer.  Seven operations a (channel, state) pair a row (the decay's
product and exponential, the update's two products and sum, the output's
product and sum); they run on the vector units, so held against the matrix
units' peak they never bound: the share is of the HBM peak.  The launch moves at
least this (padding rows' inputs ride in its blocks too), so the share cannot
pass 100.
"""
from __future__ import annotations

from typing import Dict


def state_space_layers(model: Dict) -> int:
    return model["num_hidden_layers"] // 4 + 1


def needed_by_counters(ctx: Dict, delta: Dict[str, float]) -> Dict[str, float]:
    """From the engine's counter deltas over the traced steps."""
    model, ssm = ctx["config"]["model"], ctx["config"]["state_space"]
    d_inner = ssm["mamba_expand"] * model["hidden_size"]
    d_state = ssm["mamba_d_state"]
    state = 2.0 * d_inner * d_state * 4
    row = (3.0 * d_inner + 2.0 * d_state) * 4
    layers = state_space_layers(model)
    return {"flops": 7.0 * d_inner * d_state * delta["ssm_rows"] * layers,
            "bytes": (delta["ssm_runs"] * state + delta["ssm_rows"] * row) * layers}
