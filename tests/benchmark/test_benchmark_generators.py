"""The generators offer every seed the same work: the same number of
requests, the same multiset of lengths and the same multiset of gaps between
arrivals, each in another order."""
import json
import os
import sys

import numpy as np
import pytest

import benchmark_tiny_tree as tiny

sys.path.insert(0, tiny.REPO)

from benchmark.generators import arrivals, lengths, requests  # noqa: E402

SEEDS = [0, 1, 2, 3, 17, 1234, 99999, 2 ** 31 - 1, 2 ** 31 + 11, 3000000019]


def traffic(name):
    with open(os.path.join(tiny.REPO, "benchmark", "traffic", name + ".json")) as f:
        return json.load(f)


def test_stratified_lengths_are_the_quantile_midpoints():
    spec = {"dist": "lognormal", "median": 100, "sigma": 0.5, "min": 10, "max": 1000}
    got = lengths.stratified_lengths(spec, 101)
    assert got == sorted(got) and got[50] == 100          # the middle one is the median
    assert min(got) >= 10 and max(got) <= 1000
    # 15.87% of a lognormal lies under median * exp(-sigma)
    assert abs(sum(1 for v in got if v < 100 * np.exp(-0.5)) - 16) <= 1
    cut = lengths.stratified_lengths({**spec, "min": 90, "max": 110}, 50)
    assert min(cut) == 90 and max(cut) == 110


def test_arrivals_keep_their_count_and_their_gaps_whatever_the_seed():
    rng = lambda seed: np.random.default_rng(seed)  # noqa: E731
    a = arrivals.arrival_instants(1.4, 45.0, rng(7))
    assert len(a) == round(1.4 * 45.0) and a == sorted(a) and 0 <= a[0] and a[-1] < 45.0
    assert a == arrivals.arrival_instants(1.4, 45.0, rng(7))
    b = arrivals.arrival_instants(1.4, 45.0, rng(8))
    assert a != b

    def round_gaps(at):         # the gaps round the span, the wrap-around one included
        return sorted(np.diff(at + [at[0] + 45.0]))

    assert round_gaps(a) == pytest.approx(round_gaps(b))
    assert round_gaps(a) == pytest.approx(arrivals.stratified_gaps(63, 45.0))
    gaps = np.diff(a)
    assert gaps.std() / gaps.mean() > 0.6        # bursts and lulls, not a comb
    assert sum(arrivals.stratified_gaps(63, 45.0)) == pytest.approx(45.0)


def test_lengths_are_stratified_in_blocks():
    spec = {"dist": "lognormal", "median": 1280, "sigma": 0.3, "min": 768, "max": 1920}
    block = sorted(lengths.stratified_lengths(spec, 16))
    for seed in (1, 2):
        got = lengths.permuted_in_blocks(spec, 40, 16, np.random.default_rng(seed))
        assert sorted(got[:16]) == block and sorted(got[16:32]) == block
        assert sorted(got[32:]) == sorted(lengths.stratified_lengths(spec, 8))
        assert got[:16] != block
    assert got != lengths.permuted_in_blocks(spec, 40, 16, np.random.default_rng(1))


CELLS = [
    ("chat_open", dict(seconds=45, lead_in_s=8.0, warm_requests=22, rate_per_s=1.4)),
    ("doc_backlog", dict(seconds=45, lead_in_s=12.0, warm_requests=16)),
]


def offered(plan, phase):
    """What a seed may not change: the count, the multisets of lengths, the
    tokens, and the multiset of gaps between arrivals."""
    sel = [r for r in plan if r.phase == phase]
    dues = [r.due_s for r in sel if r.due_s is not None]
    span = 45.0 if phase == "window" else 8.0
    gaps = (sorted(round(g, 9) for g in np.diff(dues + [dues[0] + span]))
            if dues and phase in ("lead_in", "window") else [])
    return (len(sel), sorted(len(r.prompt) for r in sel),
            sorted(r.max_new_tokens for r in sel), gaps)


@pytest.mark.parametrize("name,kw", CELLS)
def test_ten_seeds_offer_the_same_requests(name, kw):
    """Every seed offers the same number of requests, the same multisets of
    lengths and of arrival gaps, in its own order, with its own token ids."""
    t = traffic(name)
    plans = [requests.plan_requests(t, seed=s, vocab=50304, **kw) for s in SEEDS]
    for phase in ("warm", "lead_in", "window", "backlog"):
        first = offered(plans[0], phase)
        for plan in plans[1:]:
            assert offered(plan, phase) == first, phase
    orders = {tuple((len(r.prompt), r.max_new_tokens, r.due_s) for r in plan) for plan in plans}
    assert len(orders) == len(SEEDS)                        # every seed its own order
    ids = {tuple(plan[0].prompt[:8]) for plan in plans}
    assert len(ids) == len(SEEDS)                           # every seed its own ids
    again = requests.plan_requests(t, seed=SEEDS[-1], vocab=50304, **kw)
    assert all(np.array_equal(a.prompt, b.prompt) and a.max_new_tokens == b.max_new_tokens
               and a.due_s == b.due_s for a, b in zip(again, plans[-1]))    # same seed, same inputs
    for plan in plans:
        due = [r.due_s for r in plan if r.due_s is not None]
        assert due == sorted(due)                           # in submission order
        win = [r.due_s for r in plan if r.phase == "window"]
        assert all(kw["lead_in_s"] <= d < kw["lead_in_s"] + kw["seconds"] for d in win)
        for r in plan:
            assert 0 <= r.prompt.min() and r.prompt.max() < 50304 and r.max_new_tokens >= 1
    if name == "doc_backlog":       # any stretch of the backlog holds the same work
        for plan in plans:
            pool = [r for r in plan if r.phase == "backlog"]
            tokens = {sum(len(r.prompt) + r.max_new_tokens for r in pool[i:i + 16])
                      for i in range(0, len(pool), 16)}
            prompts = {sum(len(r.prompt) for r in pool[i:i + 16]) for i in range(0, 160, 16)}
            assert len(prompts) == 1 and len(tokens) == 1


def test_warm_population_is_in_mid_life():
    plan = requests.plan_requests(traffic("chat_open"), seed=5, vocab=50304, seconds=45,
                                  lead_in_s=8.0, warm_requests=22, rate_per_s=1.4)
    warm = [r for r in plan if r.phase == "warm"]
    assert len(warm) == 22 and all(r.due_s == 0.0 for r in warm)
    full = sorted(lengths.stratified_lengths(traffic("chat_open")["answer_tokens"], 22))
    assert sum(r.max_new_tokens for r in warm) < 0.65 * sum(full)   # about half, on average


def test_train_batches_are_seeded_and_labels_are_the_next_token():
    t = {"global_batch": 4, "sequence": 16}
    ids, labels = requests.train_batch(t, seed=3000000019, step=3, vocab=1000)
    ids2, _ = requests.train_batch(t, seed=3000000019, step=3, vocab=1000)
    other, _ = requests.train_batch(t, seed=3000000019, step=4, vocab=1000)
    assert ids.shape == (4, 16) and np.array_equal(ids, ids2) and not np.array_equal(ids, other)
    assert np.array_equal(labels[:, :-1], ids[:, 1:])
