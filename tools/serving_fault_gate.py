#!/usr/bin/env python
"""Serving fault-containment CI gate (run_tests.sh; skippable via
PADDLE_TPU_SKIP_FAULT_GATE=1).

In the crash/lint/serving-gate mold: a fast, deterministic proof that the
engine CONTAINS faults instead of dying or corrupting state.  Six
scenarios on a tiny CPU model, each asserting the PR's acceptance
criteria:

  1. transient step-crash  -> retry-once absorbs it: nothing fails, every
                              request token-for-token equal to the
                              unfaulted refs, zero retraces;
  2. persistent step-crash -> only the seated (implicated) requests end
                              FAILED with the typed error attached; the
                              queued remainder completes with parity;
  3. step-stall            -> the watchdog abandons the wedged worker,
                              rebuilds the pool, and keeps serving;
  4. NaN logits            -> the fused finiteness sentry quarantines
                              exactly the poisoned slot;
  5. pool exhaustion       -> injected allocator exhaustion backpressures
                              (never fails or corrupts), then drains;
  6. shared-prefix kill    -> two requests share a prefix-cache page; the
                              hitting one is killed mid-decode (stall ->
                              rebuild).  The rebuild flushes the cache
                              (its pages lived in the discarded pool),
                              the queued survivor completes token-for-
                              token against the rebuilt pool, and shared-
                              page refcounts stay exact throughout;

plus a RANDOMIZED fault schedule sweep (several seeds): under any mix of
crashes/NaN/exhaustion/callback faults, page accounting must close
exactly — occupancy never exceeds capacity, zero pages in use at drain,
free list whole — every request must reach a typed terminal state, and
every DONE request must match the unfaulted run.

Exit codes: 0 ok, 1 containment violated.
"""
from __future__ import annotations

import os
import sys
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

import numpy as np  # noqa: E402

N_NEW = 4


def _build():
    import paddle_tpu as pt
    from paddle_tpu.models import GPTStackedForPretraining, gpt_tiny

    pt.seed(0)
    cfg = gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0)
    m = GPTStackedForPretraining(cfg)
    m.eval()
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, cfg.vocab_size, (s,))
               for s in (5, 9, 7, 12, 17, 4, 11, 6)]
    refs = [np.asarray(
        m.generate(pt.to_tensor(p[None, :], dtype="int64"),
                   max_new_tokens=N_NEW, max_seq_len=64,
                   cache_dtype="float32").numpy())[0]
        for p in prompts]
    return m, prompts, refs


def _engine(m, **kw):
    from paddle_tpu.serving import ServingEngine

    kw.setdefault("num_slots", 2)
    kw.setdefault("page_size", 16)
    kw.setdefault("max_context", 64)
    kw.setdefault("cache_dtype", "float32")
    return ServingEngine(m, **kw)


def _drain(eng, max_steps=2000):
    steps = 0
    while eng.queue.depth or eng.scheduler.active_slots:
        met = eng.step()
        steps += 1
        if met["pages_used"] > eng.allocator.capacity:
            raise AssertionError(
                f"pool over capacity: {met['pages_used']}")
        if steps >= max_steps:
            raise AssertionError("engine stopped making progress")
        if not met["active_slots"] and not met["tokens_this_step"]:
            time.sleep(0.001)
    return steps


def _accounting_closed(eng, label):
    """Exact page accounting at drain: no slot holds pages, the 4-term
    ledger closes (free + used + spec + shared == capacity is the
    allocator invariant; at drain used == spec == 0), and every page the
    prefix cache retained is at refcount 0 (no slot is referencing it)."""
    a = eng.allocator
    if a.used_pages != 0 or a.spec_pages != 0 \
            or a.free_pages + a.shared_pages != a.capacity:
        print(f"serving_fault_gate: FAIL [{label}] page accounting leaked "
              f"(used={a.used_pages}, spec={a.spec_pages}, "
              f"free={a.free_pages}, shared={a.shared_pages}, "
              f"capacity={a.capacity})")
        return False
    held = {p: c for p, c in getattr(a, "_shared", {}).items() if c}
    if held:
        print(f"serving_fault_gate: FAIL [{label}] shared pages still "
              f"referenced at drain: {held}")
        return False
    return True


def _done_parity(reqs, refs, label):
    from paddle_tpu.serving import RequestState

    bad = 0
    for r, ref in zip(reqs, refs):
        if r.state == RequestState.DONE and not np.array_equal(
                r.output_ids(), ref):
            bad += 1
    if bad:
        print(f"serving_fault_gate: FAIL [{label}] {bad} surviving "
              "request(s) diverged from the unfaulted run")
    return bad == 0


def gate() -> int:
    from paddle_tpu import serving
    from paddle_tpu.serving import (
        FaultInjector, NaNLogitsError, RequestState, StepStalledError,
        random_schedule,
    )

    m, prompts, refs = _build()
    ok = True

    # -- 1. transient crash: retry absorbs it ----------------------------
    serving.reset_serve_trace_counts()
    eng = _engine(m)
    inj = FaultInjector().inject("before_decode", at=2,
                                 kind="step_exception").install(eng)
    reqs = [eng.submit(p, N_NEW) for p in prompts[:4]]
    _drain(eng)
    mt = eng.metrics()
    tc = serving.serve_trace_counts()
    if not (inj.fired() == 1 and mt["step_retries"] == 1
            and mt["failed"] == 0 and mt["recoveries"] == 0
            and all(r.state == RequestState.DONE for r in reqs)
            and all(np.array_equal(r.output_ids(), ref)
                    for r, ref in zip(reqs, refs))
            and tc["fused"] <= 2):
        print(f"serving_fault_gate: FAIL [transient] {mt} traces={tc} "
              f"states={[r.state for r in reqs]}")
        ok = False
    ok &= _accounting_closed(eng, "transient")
    eng.close()

    # -- 2. persistent crash: only the implicated fail -------------------
    eng = _engine(m)
    FaultInjector().inject("before_decode", at=1, times=2,
                           kind="step_exception").install(eng)
    reqs = [eng.submit(p, N_NEW) for p in prompts[:4]]
    _drain(eng)
    mt = eng.metrics()
    failed = [r for r in reqs if r.state == RequestState.FAILED]
    done = [r for r in reqs if r.state == RequestState.DONE]
    if not (mt["recoveries"] == 1 and len(failed) == 2 and len(done) == 2
            and all(r.error is not None for r in failed)):
        print(f"serving_fault_gate: FAIL [persistent] {mt} "
              f"states={[r.state for r in reqs]}")
        ok = False
    ok &= _done_parity(reqs, refs, "persistent")
    ok &= _accounting_closed(eng, "persistent")
    eng.close()

    # -- 3. stall: watchdog abandons + rebuilds --------------------------
    eng = _engine(m, stall_budget_s=0.5)
    warm = eng.submit(prompts[0], 2)
    _drain(eng)                                  # compile under the big budget
    assert warm.finished
    FaultInjector().inject("before_decode", at=0, kind="step_stall",
                           duration=1.5).install(eng)
    reqs = [eng.submit(p, N_NEW) for p in prompts[:4]]
    _drain(eng)
    mt = eng.metrics()
    stalled = [r for r in reqs if isinstance(r.error, StepStalledError)]
    done = [r for r in reqs if r.state == RequestState.DONE]
    if not (mt["recoveries"] == 1 and mt["rebuilds"] == 1
            and len(stalled) == 2 and len(done) == 2):
        print(f"serving_fault_gate: FAIL [stall] {mt} "
              f"states={[r.state for r in reqs]}")
        ok = False
    ok &= _done_parity(reqs, refs, "stall")
    ok &= _accounting_closed(eng, "stall")
    eng.close()

    # -- 4. NaN logits: sentry quarantines the poisoned slot only --------
    eng = _engine(m)
    FaultInjector().inject("after_decode", at=1, kind="nan_logits",
                           slots=[0]).install(eng)
    reqs = [eng.submit(p, N_NEW) for p in prompts[:4]]
    _drain(eng)
    mt = eng.metrics()
    poisoned = [r for r in reqs if isinstance(r.error, NaNLogitsError)]
    done = [r for r in reqs if r.state == RequestState.DONE]
    if not (mt["quarantined"] == 1 and len(poisoned) == 1
            and len(done) == 3):
        print(f"serving_fault_gate: FAIL [nan] {mt} "
              f"states={[r.state for r in reqs]}")
        ok = False
    ok &= _done_parity(reqs, refs, "nan")
    ok &= _accounting_closed(eng, "nan")
    eng.close()

    # -- 5. pool exhaustion: backpressure, never corruption --------------
    eng = _engine(m)
    FaultInjector().inject("alloc", at=0, times=4,
                           kind="alloc_exhausted").install(eng)
    reqs = [eng.submit(p, N_NEW) for p in prompts[:4]]
    _drain(eng)
    if not all(r.state == RequestState.DONE
               and np.array_equal(r.output_ids(), ref)
               for r, ref in zip(reqs, refs)):
        print("serving_fault_gate: FAIL [exhaustion] "
              f"states={[r.state for r in reqs]}")
        ok = False
    ok &= _accounting_closed(eng, "exhaustion")
    eng.close()

    # -- 6. shared prefix killed mid-decode: survivor + refcounts exact --
    # Two requests share a cached prefix through the prefix cache
    # (docs/serving.md "Prefix cache"); the one that hit is killed
    # mid-decode by a stall.  The rebuild flushes the cache (its pages
    # lived in the discarded pool), the survivor — queued behind it on
    # the single slot — is admitted against the rebuilt pool and must
    # come out token-for-token; shared-page refcounts must be exact at
    # every stage (held while seated, zero after the flush and at drain).
    from paddle_tpu.serving import ServingEngine

    prng = np.random.RandomState(9)
    vocab = m.config.vocab_size
    shared = prng.randint(0, vocab, (20,))       # 1 full page + tail
    tail_b = prng.randint(0, vocab, (5,))
    eng = ServingEngine(m, num_slots=1, page_size=16, max_context=64,
                        cache_dtype="float32", stall_budget_s=0.5,
                        prefix_cache=True)
    warm = eng.submit(prompts[0], 2)
    _drain(eng)                                  # compile under the big budget
    assert warm.finished
    ra = eng.submit(shared, N_NEW)               # registers the prefix page
    _drain(eng)
    ref_a = ra.output_ids()
    if not (ra.state == RequestState.DONE
            and eng.allocator.shared_pages >= 1):
        print("serving_fault_gate: FAIL [prefix] seeding request did not "
              f"register a shared page (state={ra.state}, "
              f"shared={eng.allocator.shared_pages})")
        ok = False
    FaultInjector().inject("before_decode", at=0, kind="step_stall",
                           duration=1.5).install(eng)
    rb = eng.submit(np.concatenate([shared, tail_b]), N_NEW)  # cache hit
    rc = eng.submit(shared, N_NEW)               # queued survivor (1 slot)
    _drain(eng)
    mt = eng.metrics()
    if not (isinstance(rb.error, StepStalledError)
            and rb.state == RequestState.FAILED
            and mt["rebuilds"] == 1
            and mt["prefix_hits"] >= 1
            and mt["prefix_evictions"] >= 1       # the rebuild flush
            and rc.state == RequestState.DONE
            and np.array_equal(rc.output_ids(), ref_a)):
        print(f"serving_fault_gate: FAIL [prefix] {mt} "
              f"states={[rb.state, rc.state]} err={rb.error!r}")
        ok = False
    # the survivor completed AFTER the flush, so it re-registered the
    # prefix into the rebuilt pool: the cache is warm again, refcount 0
    if eng.allocator.shared_pages < 1:
        print("serving_fault_gate: FAIL [prefix] survivor did not "
              "re-register the prefix after the rebuild flush")
        ok = False
    ok &= _accounting_closed(eng, "prefix")
    eng.close()

    # -- 7. randomized schedules: the accounting property ----------------
    for seed in (3, 17, 42):
        rng = np.random.RandomState(seed)
        eng = _engine(m, num_slots=3)
        random_schedule(rng, horizon=25, n_faults=4, num_slots=3).install(eng)
        reqs = [eng.submit(p, N_NEW) for p in prompts]
        try:
            _drain(eng)
        except AssertionError as e:
            print(f"serving_fault_gate: FAIL [random seed={seed}] {e}")
            ok = False
            eng.close()
            continue
        if not all(r.terminal for r in reqs):
            print(f"serving_fault_gate: FAIL [random seed={seed}] "
                  "non-terminal request after drain")
            ok = False
        if any(r.state != RequestState.DONE and r.error is None
               for r in reqs):
            print(f"serving_fault_gate: FAIL [random seed={seed}] "
                  "non-DONE terminal without a typed error")
            ok = False
        ok &= _done_parity(reqs, refs, f"random seed={seed}")
        ok &= _accounting_closed(eng, f"random seed={seed}")
        eng.close()

    if not ok:
        return 1
    print("serving_fault_gate: OK (transient-retry, persistent-crash, "
          "stall-rebuild, nan-quarantine, exhaustion-backpressure, "
          "shared-prefix-kill, 3 randomized schedules — containment + "
          "exact page accounting incl. shared pages)")
    return 0


if __name__ == "__main__":
    sys.exit(gate())
