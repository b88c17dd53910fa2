"""Stratified request lengths: every seed offers the same multiset.

For ``n`` requests the lengths are the ``n`` quantile mid-points of the stated
distribution (a lognormal cut to a range); the run's seed only permutes them.
Where only a part of the requests falls inside a run (a backlog that the
window never empties), they are stratified in blocks: every block of ``block``
consecutive requests holds the block's own quantile mid-points, so any stretch
of the order holds nearly the same multiset whatever the seed.
"""
from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np


def stratified_lengths(spec: Dict, n: int) -> List[int]:
    """The ``n`` quantile mid-points of ``spec``, ascending.

    ``spec``: ``{"dist": "lognormal", "median": m, "sigma": s, "min": lo,
    "max": hi}`` (the distribution is cut to ``[lo, hi]`` by clipping its
    quantiles) or ``{"dist": "constant", "value": v}``.
    """
    if n < 1:
        raise ValueError(f"need at least one request, got n={n}")
    dist = spec.get("dist")
    if dist == "constant":
        return [int(spec["value"])] * n
    if dist != "lognormal":
        raise ValueError(f"unknown length distribution {dist!r}")
    mu, sigma = math.log(float(spec["median"])), float(spec["sigma"])
    lo, hi = int(spec["min"]), int(spec["max"])
    normal = NormalDist()
    out = []
    for i in range(n):
        z = normal.inv_cdf((i + 0.5) / n)
        out.append(int(min(hi, max(lo, round(math.exp(mu + sigma * z))))))
    return out


def permuted_in_blocks(spec: Dict, n: int, block: int, rng: np.random.Generator) -> List[int]:
    """``n`` lengths of ``spec``: each run of ``block`` consecutive ones is the
    ``block`` quantile mid-points in an order drawn from ``rng`` (the last
    block may be shorter and is stratified at its own size)."""
    out: List[int] = []
    while len(out) < n:
        size = min(block, n - len(out))
        values = stratified_lengths(spec, size)
        out += [values[i] for i in rng.permutation(size)]
    return out
