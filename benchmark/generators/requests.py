"""The one general request generator: a traffic file plus a seed -> a plan.

A serving traffic file (``benchmark/traffic/<name>.json``, ``kind: serve``)
states length distributions and a ``mode``:

``open``     arrivals on a schedule at the cell's fixed rate, whatever the
             engine is doing (independent users);
``backlog``  the queue is kept topped up to ``backlog_factor`` x slots
             waiting requests (offline documents; saturated).

Both start WARM: at the first instant as many requests as the steady state
seats are submitted, their answers shortened by uniformly spread fractions, so
the population is in mid-life from the first step and a short lead-in reaches
the steady state.  How many requests are offered, the multiset of their
lengths and the multiset of the gaps between arrivals are the traffic file's
and the cell's, the same for every seed; the run's seed permutes the lengths
(prompts and answers independently), permutes the gaps, and draws the token
ids (and the weights).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

from .arrivals import arrival_instants
from .lengths import permuted_in_blocks, stratified_lengths


@dataclass
class Planned:
    due_s: Optional[float]      # seconds after the generator's start; None = on demand
    prompt: np.ndarray          # int64 token ids
    max_new_tokens: int
    phase: str                  # "warm" | "lead_in" | "window" | "backlog"


def _requests(rng, vocab: int, prompts: List[int], answers: List[int],
              dues, phase: str) -> List[Planned]:
    return [Planned(due, rng.integers(0, vocab, p, dtype=np.int64), int(a), phase)
            for due, p, a in zip(dues, prompts, answers)]


def _lengths(traffic: Dict, key: str, n: int, rng) -> List[int]:
    """``n`` stratified lengths of ``traffic[key]`` in the seed's order, in
    blocks of the traffic file's ``stratify_block`` (one block when absent)."""
    return permuted_in_blocks(traffic[key], n, int(traffic.get("stratify_block", n)), rng)


def _warm_population(traffic: Dict, rng, vocab: int, n: int) -> List[Planned]:
    """``n`` requests for the first instant.  The i-th stratified answer is
    cut to the fraction ``frac((i + 0.5) * golden ratio)`` of its length (never
    under 1): fractions spread evenly over (0, 1) and fixed, so every seed
    offers the same warm population, in its own order."""
    if n < 1:
        return []
    prompts = _lengths(traffic, "prompt_tokens", n, rng)
    answers = [max(1, int(round(a * (((i + 0.5) * 0.6180339887498949) % 1.0))))
               for i, a in enumerate(stratified_lengths(traffic["answer_tokens"], n))]
    answers = [answers[i] for i in rng.permutation(n)]
    return _requests(rng, vocab, prompts, answers, [0.0] * n, "warm")


def plan_requests(traffic: Dict, *, seed: int, vocab: int, seconds: float,
                  lead_in_s: float, warm_requests: int,
                  rate_per_s: Optional[float] = None) -> List[Planned]:
    """The whole run's requests, in submission order."""
    rng = np.random.default_rng(int(seed))
    mode = traffic["mode"]
    plan = _warm_population(traffic, rng, vocab, int(warm_requests))
    if mode == "open":
        if not rate_per_s:
            raise ValueError("an open-loop cell states its rate_per_s")
        # the window's requests and the lead-in's are made apart, so the
        # window is offered the same count, lengths and gaps by every seed
        for phase, start, span in (("lead_in", 0.0, lead_in_s),
                                   ("window", lead_in_s, seconds)):
            if span > 0 and round(rate_per_s * span) >= 1:
                dues = [start + t for t in arrival_instants(rate_per_s, span, rng)]
                n = len(dues)
                plan += _requests(rng, vocab, _lengths(traffic, "prompt_tokens", n, rng),
                                  _lengths(traffic, "answer_tokens", n, rng), dues, phase)
    elif mode == "backlog":
        n = int(traffic["pool_requests"])
        plan += _requests(rng, vocab, _lengths(traffic, "prompt_tokens", n, rng),
                          _lengths(traffic, "answer_tokens", n, rng),
                          [None] * n, "backlog")
    else:
        raise ValueError(f"unknown serving traffic mode {mode!r}")
    return plan


def train_batch(traffic: Dict, *, seed: int, step: int, vocab: int):
    """One global batch of a ``kind: train`` traffic file: token ids uniform
    from (seed, step); labels are the next token (the last wraps to the first)."""
    rng = np.random.default_rng([int(seed), int(step)])
    ids = rng.integers(0, vocab, (int(traffic["global_batch"]),
                                  int(traffic["sequence"])), dtype=np.int64)
    return ids, np.roll(ids, -1, axis=1)
