"""Metric arithmetic.  A rate is all the work of the window over all its
time, between two boundaries of the work itself (steps, completions) and not
between two readings of the wall clock that cut a step or a request in two; a
tail is the tail of all requests."""
from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence, Tuple


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """Linear-interpolated percentile ``q`` in [0, 100]; None when empty."""
    xs = sorted(float(v) for v in values)
    if not xs:
        return None
    if len(xs) == 1:
        return xs[0]
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def median(values: Iterable[float]) -> Optional[float]:
    return percentile(values, 50.0)


def mean(values: Sequence[float]) -> Optional[float]:
    return sum(values) / len(values) if values else None


def rate_over_steps(tokens_per_step: int, steps: Sequence[Tuple[float, float]],
                    chips: int = 1) -> Optional[float]:
    """Training rate: ALL the tokens of the window's steps over ALL its time,
    per chip.  ``steps`` is (start, end) of each step, a start being taken
    before the step asks for its batch and an end after the host has read the
    loss, so waiting for input and whatever the host does between steps is
    inside.  Both ends of the interval are step boundaries: no step is cut by
    a wall-clock edge, so the count is never off by a part of a step."""
    if not steps or steps[-1][1] <= steps[0][0]:
        return None
    return len(steps) * tokens_per_step / (steps[-1][1] - steps[0][0]) / chips


def rate_between_boundaries(tokens: float, t_first: float, t_last: float
                            ) -> Optional[float]:
    """Serving rate above the knee: ALL the tokens the engine processed
    (prompt tokens prefilled and tokens emitted) between the first and the
    last step boundary inside the window, over the time between those two
    boundaries.  Both ends are boundaries of the work, so no step is cut by a
    wall-clock edge; and every processed token counts when it is processed,
    not when its request completes (a count of whole requests over 45 s moves
    by 2% with one request more or less)."""
    if t_last <= t_first or tokens <= 0:
        return None
    return tokens / (t_last - t_first)


def gaps_landing_in(token_times: Sequence[Sequence[float]], start: float,
                    end: float) -> List[float]:
    """All gaps between successive tokens of one request whose LATER token
    lands in ``[start, end)``, over every request."""
    out = []
    for times in token_times:
        for a, b in zip(times, times[1:]):
            if start <= b < end:
                out.append(b - a)
    return out
