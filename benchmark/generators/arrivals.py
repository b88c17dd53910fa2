"""Arrival instants: a Poisson process with its count AND its gaps fixed.

``n = round(rate * span)`` arrivals.  Their gaps are the ``n`` quantile
mid-points of the exponential law (the gaps of a Poisson process), scaled so
that they fill the span exactly.  The run's seed permutes the gaps and turns
the whole pattern round the span by a random phase: every seed offers the same
``n`` and the same multiset of gaps (as many bursts, as many lulls), and each
puts them somewhere else.
"""
from __future__ import annotations

import math
from typing import List

import numpy as np


def stratified_gaps(n: int, span_s: float) -> List[float]:
    """The ``n`` exponential quantile mid-points, ascending, summing to ``span_s``."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = span_s / sum(raw)
    return [g * scale for g in raw]


def arrival_instants(rate: float, span_s: float, rng: np.random.Generator) -> List[float]:
    """Sorted arrival instants in ``[0, span_s)``, ``round(rate * span_s)`` of them."""
    if rate <= 0 or span_s <= 0:
        raise ValueError(f"rate and span must be positive, got {rate}, {span_s}")
    n = int(round(rate * span_s))
    if n < 1:
        raise ValueError(f"rate {rate}/s over {span_s}s offers no request")
    gaps = np.asarray(stratified_gaps(n, span_s))[rng.permutation(n)]
    phase = float(rng.uniform(0.0, span_s))
    at = (phase + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])) % span_s
    return sorted(min(float(t), math.nextafter(span_s, 0.0)) for t in at)
