"""Each estimator on synthetic readings: what an injected hiccup does to it,
and that no wall-clock edge cuts a step or a request in two."""
import sys

import numpy as np
import pytest

import benchmark_tiny_tree as tiny

sys.path.insert(0, tiny.REPO)

from benchmark.harness import estimators as est  # noqa: E402


def test_percentile_interpolates():
    assert est.percentile([], 50) is None
    assert est.percentile([3.0], 95) == 3.0
    assert est.percentile([1, 2, 3, 4], 50) == 2.5
    assert est.percentile(range(101), 95) == 95
    assert est.median([5, 1, 3]) == 3


def test_train_rate_is_all_tokens_over_all_time_between_step_boundaries():
    """The judged rate carries a hiccup in full (it is all the work over all
    the time); the median step, a per-layer metric, does not move; and the
    window's wall-clock edges cut no step, so the count is never a part of a
    step off."""
    starts = [10.0 + 0.634 * i for i in range(70)]
    steps = [(a, a + 0.634) for a in starts]
    steady = est.rate_over_steps(8192, steps)
    assert steady == pytest.approx(8192 / 0.634)
    late = [(a + (0.7 if i > 30 else 0.0), b + (0.7 if i >= 30 else 0.0))
            for i, (a, b) in enumerate(steps)]                   # 0.7 s lost once
    assert est.rate_over_steps(8192, late) == pytest.approx(steady * 44.38 / 45.08, rel=1e-6)
    assert est.median([b - a for a, b in late]) == pytest.approx(0.634)
    # time between steps (waiting for a batch) is inside: starts are taken
    # before the batch is asked for, so a gap shows as a longer step
    gappy = [(a + 0.05 * i, a + 0.05 * i + 0.684) for i, (a, _) in enumerate(steps)]
    assert est.rate_over_steps(8192, gappy) == pytest.approx(8192 / 0.684)
    # a count over a fixed wall interval is a part of a step off
    wall = sum(1 for _, b in steps if b < 10.0 + 44.0) * 8192 / 44.0
    assert abs(wall - steady) / steady > 0.005
    assert est.rate_over_steps(8192, steps, chips=4) == pytest.approx(steady / 4)
    assert est.rate_over_steps(8192, []) is None


def test_itl_p95_over_all_gaps_ignores_one_stalled_step():
    rng = np.random.default_rng(0)
    base = 0.120 + 0.02 * (rng.random(360) < 0.35)              # two kinds of step
    times, t = [], 0.0
    for i, g in enumerate(base):
        t += g
        times.append(t)
    token_times = [list(times) for _ in range(20)]               # twenty requests decode together
    gaps = est.gaps_landing_in(token_times, 0.0, 1e9)
    steady = est.percentile(gaps, 95)
    stalled = [[x + (0.7 if x > 20.0 else 0.0) for x in ts] for ts in token_times]
    gaps2 = est.gaps_landing_in(stalled, 0.0, 1e9)
    assert max(gaps2) > max(gaps) + 0.6                          # the stall is in the readings
    assert est.percentile(gaps2, 95) == pytest.approx(steady, rel=0.01)
    assert np.mean(gaps2) > np.mean(gaps) * 1.01                # a mean carries it


def test_gaps_are_taken_where_the_later_token_lands():
    got = est.gaps_landing_in([[0.0, 1.0, 2.5], [1.9, 2.1]], 1.0, 2.2)
    assert sorted(got) == pytest.approx([0.2, 1.0])


def test_serving_rate_is_all_processed_tokens_between_step_boundaries():
    # 225 steps of 0.2 s with 316 tokens each: the rate is 1580 tokens/s
    ends = [10.0 + 0.2 * i for i in range(226)]
    assert est.rate_between_boundaries(225 * 316, ends[0], ends[-1]) == pytest.approx(1580.0)
    # a count of whole requests (1344 tokens each, one every 0.85 s) over a
    # fixed 45 s moves by a whole request with the window's edge; this does not
    done = [10.0 + 0.85 * i for i in range(60)]
    counts = {sum(1344 for t in done if 10.05 + e <= t < 55.05 + e) / 45.0 for e in (0.0, 0.4, 0.8)}
    assert len(counts) > 1 and max(counts) / min(counts) > 1.015
    assert est.rate_between_boundaries(0, 1.0, 2.0) is None
    assert est.rate_between_boundaries(10, 2.0, 2.0) is None
