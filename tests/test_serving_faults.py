"""Serving fault containment (docs/serving.md "Failure model & SLOs").

One bad request, one wedged step, or one transient device error must never
kill the engine or strand other requests:

- typed terminal states (DONE | CANCELLED | TIMED_OUT | FAILED) with the
  error attached, ``Request.cancel()``/``deadline_s`` honored at the next
  step boundary, ``wait(timeout)`` distinguishing its own timeout from a
  failed request;
- watchdog-supervised steps: a stalled step is abandoned (zombie write-
  backs land in orphaned buffers), implicated requests FAIL, the engine
  rebuilds from the scheduler's host mirrors and keeps serving; crashed
  steps retry once, then recover with exponential re-admission backoff;
- the fused per-slot finiteness sentry quarantines NaN-poisoned slots;
- bounded queues shed load with the typed ``Overloaded`` error;
- the ``serving/faults.py`` injection harness drives all of it
  deterministically, including randomized fault schedules under which
  page accounting must stay EXACT (no leaks, no double frees) and every
  non-implicated request must match an unfaulted run token-for-token.
"""
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import inference, serving
from paddle_tpu.models import GPTStackedForPretraining, gpt_tiny
from paddle_tpu.serving import (
    DeadlineExceeded,
    FaultInjector,
    InjectedFault,
    NaNLogitsError,
    Overloaded,
    RequestCancelled,
    RequestState,
    ServingEngine,
    StepStalledError,
    random_schedule,
)

N_NEW = 4           # max_new_tokens everywhere: one shared set of refs


@pytest.fixture(scope="module")
def served():
    """One tiny model + greedy single-shot references shared by the whole
    module (engine compiles dominate runtime; the model is cheap but the
    refs pin parity for every containment test)."""
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
    return m, cfg, prompts, refs


def _engine(m, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("page_size", 16)
    kw.setdefault("max_context", 64)
    kw.setdefault("cache_dtype", "float32")
    return ServingEngine(m, **kw)


def _check_done_parity(reqs, refs):
    for r, ref in zip(reqs, refs):
        if r.state == RequestState.DONE:
            assert np.array_equal(r.output_ids(), ref), (
                f"request {r.id} diverged from the unfaulted run")


# ---------------------------------------------------------------------------
# request-level semantics (no engine stepping needed)
# ---------------------------------------------------------------------------

def test_request_wait_timeout_distinguishable_from_terminal():
    r = serving.Request(np.array([1], np.int64), 2)
    assert r.wait(timeout=0.01) is False        # wait timed out
    assert not r.terminal and r.state == RequestState.SUBMITTED
    r.error = DeadlineExceeded("x")
    r.state = RequestState.TIMED_OUT
    r._done.set()
    assert r.wait(timeout=0.01) is True         # terminal (but not DONE)
    assert not r.finished
    with pytest.raises(DeadlineExceeded):
        r.wait(raise_on_failure=True)


def test_request_cancel_is_idempotent_and_rejects_terminal():
    r = serving.Request(np.array([1], np.int64), 2)
    assert r.cancel() is True
    assert r.cancel() is True                   # still pending: fine
    r.state = RequestState.DONE
    r._done.set()
    assert r.cancel() is False                  # terminal: nothing to cancel


def test_bounded_queue_sheds_with_typed_error():
    q = serving.RequestQueue(max_depth=2)
    q.submit(serving.Request(np.array([1], np.int64), 2))
    q.submit(serving.Request(np.array([1], np.int64), 2))
    with pytest.raises(Overloaded, match="queue full"):
        q.submit(serving.Request(np.array([1], np.int64), 2))
    assert q.depth == 2


def test_fault_plan_validation():
    with pytest.raises(ValueError, match="unknown fault kind"):
        serving.FaultPlan(point="before_decode", at=0, kind="nope")
    with pytest.raises(ValueError, match="cannot fire at point"):
        serving.FaultPlan(point="alloc", at=0, kind="nan_logits")


# ---------------------------------------------------------------------------
# deadlines, cancellation, shedding through a live engine
# ---------------------------------------------------------------------------

def test_cancel_queued_and_seated_frees_pages(served):
    m, cfg, prompts, refs = served
    eng = _engine(m, num_slots=1)
    r1 = eng.submit(prompts[0], 6)
    r2 = eng.submit(prompts[1], 6)
    r3 = eng.submit(prompts[2], N_NEW)
    eng.step()                                  # r1 seated, r2/r3 queued
    assert eng.allocator.used_pages > 0
    r1.cancel()
    r2.cancel()
    eng.step()                                  # next boundary honors both
    assert r1.state == RequestState.CANCELLED
    assert r2.state == RequestState.CANCELLED
    assert isinstance(r1.error, RequestCancelled)
    assert r1.wait(timeout=1.0) is True
    eng.run_until_idle()
    assert r3.state == RequestState.DONE
    assert np.array_equal(r3.output_ids(), refs[2])
    assert eng.allocator.used_pages == 0
    assert eng.metrics()["cancelled"] == 2


def test_deadline_expires_queued_and_seated(served):
    m, cfg, prompts, refs = served
    eng = _engine(m, num_slots=1)
    ra = eng.submit(prompts[0], 6, deadline_s=0.15)
    rb = eng.submit(prompts[1], 6, deadline_s=0.15)
    eng.step()                                  # ra seated, rb queued
    time.sleep(0.2)
    eng.step()                                  # both expired at the boundary
    assert ra.state == RequestState.TIMED_OUT
    assert rb.state == RequestState.TIMED_OUT
    assert isinstance(ra.error, DeadlineExceeded)
    assert isinstance(rb.error, DeadlineExceeded)
    assert eng.allocator.used_pages == 0
    assert eng.metrics()["timed_out"] == 2
    # the engine keeps serving afterwards
    rc = eng.submit(prompts[2], N_NEW)
    eng.run_until_idle()
    assert np.array_equal(rc.output_ids(), refs[2])


def test_submit_overload_and_queue_wait_shedding(served):
    m, cfg, prompts, refs = served
    eng = _engine(m, num_slots=1, max_queue_depth=2, max_queue_wait_s=0.15)
    r1 = eng.submit(prompts[0], 6)
    r2 = eng.submit(prompts[1], 6)
    with pytest.raises(Overloaded, match="queue full"):
        eng.submit(prompts[2], 6)               # depth 2 reached: shed fast
    assert eng.metrics()["shed"] == 1
    eng.step()                                  # r1 seated; r2 still queued
    time.sleep(0.2)
    eng.step()                                  # r2 overstayed the queue
    assert r2.state == RequestState.TIMED_OUT
    assert isinstance(r2.error, Overloaded)
    assert eng.metrics()["shed"] == 2
    assert r1.state in (RequestState.DECODE, RequestState.DONE)
    eng.run_until_idle()
    assert r1.state == RequestState.DONE
    assert eng.allocator.used_pages == 0


# ---------------------------------------------------------------------------
# step crashes: retry-once, recovery, re-admission backoff
# ---------------------------------------------------------------------------

def test_transient_step_crash_retries_and_nothing_fails(served):
    m, cfg, prompts, refs = served
    serving.reset_serve_trace_counts()
    eng = _engine(m)
    inj = FaultInjector().inject("before_decode", at=2,
                                 kind="step_exception").install(eng)
    reqs = [eng.submit(p, N_NEW) for p in prompts[:4]]
    eng.run_until_idle()
    assert inj.fired("step_exception") == 1, "the schedule never fired"
    mt = eng.metrics()
    assert mt["step_retries"] == 1
    assert mt["recoveries"] == 0 and mt["failed"] == 0
    for r, ref in zip(reqs, refs):
        assert r.state == RequestState.DONE
        assert np.array_equal(r.output_ids(), ref)
    assert eng.allocator.used_pages == 0
    tc = serving.serve_trace_counts()
    assert tc["fused"] <= 2, f"transient retry must not retrace: {tc}"


def test_persistent_step_crash_fails_only_seated_requests(served):
    m, cfg, prompts, refs = served
    eng = _engine(m)
    inj = FaultInjector().inject("before_decode", at=1, times=2,
                                 kind="step_exception").install(eng)
    reqs = [eng.submit(p, N_NEW) for p in prompts[:4]]
    eng.run_until_idle()
    assert inj.fired("step_exception") == 2
    mt = eng.metrics()
    assert mt["recoveries"] == 1
    assert mt["rebuilds"] == 0, \
        "state_intact fault must recover without a pool rebuild"
    failed = [r for r in reqs if r.state == RequestState.FAILED]
    done = [r for r in reqs if r.state == RequestState.DONE]
    assert len(failed) == 2, [r.state for r in reqs]   # the seated pair
    assert len(done) == 2
    assert all(isinstance(r.error, InjectedFault) for r in failed)
    _check_done_parity(reqs, refs)
    assert eng.allocator.used_pages == 0
    assert mt["failed"] == 2


def test_non_intact_crash_rebuilds_pool_and_keeps_serving(served):
    m, cfg, prompts, refs = served
    eng = _engine(m)
    inj = FaultInjector().inject("before_decode", at=1, times=2,
                                 kind="step_exception",
                                 state_intact=False).install(eng)
    reqs = [eng.submit(p, N_NEW) for p in prompts[:4]]
    old_k = eng.cache.k._value
    eng.run_until_idle()
    mt = eng.metrics()
    assert mt["recoveries"] == 1 and mt["rebuilds"] == 1
    assert old_k.is_deleted(), "rebuild must release the suspect pool"
    done = [r for r in reqs if r.state == RequestState.DONE]
    assert len(done) == 2, [r.state for r in reqs]
    _check_done_parity(reqs, refs)       # fresh pool: parity must survive
    assert eng.allocator.used_pages == 0


def _warm(eng, prompts):
    """Run one request to completion so the fused step has compiled and
    run: a fault on a step that has NEVER run is a build failure and
    propagates (test_first_dispatch_failure_propagates)."""
    w = eng.submit(prompts[0], 2)
    eng.run_until_idle()
    assert w.finished


def test_first_dispatch_failure_propagates(served):
    """A step variant that fails the first time it is ever dispatched has
    not been built — a kernel the compiler refuses would look like this.
    The error escapes containment instead of leaving a server that exits
    clean having answered nothing but FAILED requests."""
    m, cfg, prompts, refs = served
    eng = _engine(m)
    FaultInjector().inject("before_decode", at=0,
                           kind="step_exception").install(eng)
    reqs = [eng.submit(p, N_NEW) for p in prompts[:2]]
    with pytest.raises(serving.StepBuildError, match="first dispatch"):
        eng.step()
    assert not any(r.state == RequestState.FAILED for r in reqs)
    mt = eng.metrics()
    assert mt["recoveries"] == 0 and mt["step_retries"] == 0


def test_recovery_arms_readmission_backoff(served):
    m, cfg, prompts, refs = served
    eng = _engine(m, readmission_backoff_s=0.2)
    _warm(eng, prompts)
    FaultInjector().inject("before_decode", at=0, times=2,
                           kind="step_exception").install(eng)
    reqs = [eng.submit(p, N_NEW) for p in prompts[:4]]
    eng.step()                   # seats 2, decode crashes twice -> recovery
    assert eng.metrics()["recoveries"] == 1
    assert eng.queue.depth == 2
    eng.step()                   # within the backoff window: nothing admitted
    assert eng.scheduler.active_slots == 0
    time.sleep(0.25)
    eng.step()                   # backoff expired: admission resumes
    assert eng.scheduler.active_slots > 0
    eng.run_until_idle()
    assert [r.state for r in reqs[2:]] == [RequestState.DONE] * 2
    _check_done_parity(reqs, refs)
    assert eng.allocator.used_pages == 0


# ---------------------------------------------------------------------------
# watchdog: stalled steps are abandoned and the engine rebuilds
# ---------------------------------------------------------------------------

def test_watchdog_abandons_stalled_step_and_recovers(served):
    m, cfg, prompts, refs = served
    # budget generous vs a loaded CI box's normal step time, small vs the
    # injected stall — the gap is what keeps this deterministic
    eng = _engine(m, stall_budget_s=0.5)
    w = eng.submit(prompts[0], 2)       # warmup: compiles under the much
    eng.run_until_idle()                # larger compile budget, not the stall
    assert w.finished
    old_k = eng.cache.k._value
    old_worker = eng._worker
    inj = FaultInjector().inject("before_decode", at=0, kind="step_stall",
                                 duration=2.0).install(eng)
    reqs = [eng.submit(p, N_NEW) for p in prompts[:4]]
    eng.run_until_idle()
    assert inj.fired("step_stall") == 1
    mt = eng.metrics()
    assert mt["recoveries"] == 1 and mt["rebuilds"] == 1
    stalled = [r for r in reqs if isinstance(r.error, StepStalledError)]
    assert len(stalled) == 2, [r.state for r in reqs]   # the seated pair
    _check_done_parity(reqs, refs)
    assert eng.allocator.used_pages == 0
    # the zombie worker honors cancelled(): once it drains, its cleanup
    # releases the ORPHANED pool (the rebuilt pool stays live)
    deadline = time.monotonic() + 5.0
    while not old_k.is_deleted() and time.monotonic() < deadline:
        time.sleep(0.05)
    assert old_k.is_deleted(), "zombie cleanup never released the old pool"
    assert not eng.cache.k._value.is_deleted()
    # the replaced (dead) worker's thread must exit once its zombie thunk
    # returns — one leaked daemon thread per recovery would be unbounded
    assert old_worker is not eng._worker and old_worker.dead
    old_worker._t.join(timeout=5.0)
    assert not old_worker._t.is_alive(), "dead worker thread leaked"


# ---------------------------------------------------------------------------
# NaN finiteness sentry: quarantine, not garbage
# ---------------------------------------------------------------------------

def test_nan_quarantine_fails_only_poisoned_slot(served):
    m, cfg, prompts, refs = served
    eng = _engine(m)
    inj = FaultInjector().inject("after_decode", at=1, kind="nan_logits",
                                 slots=[0]).install(eng)
    reqs = [eng.submit(p, N_NEW) for p in prompts[:4]]
    eng.run_until_idle()
    assert inj.fired("nan_logits") == 1
    mt = eng.metrics()
    assert mt["quarantined"] == 1 and mt["recoveries"] == 0
    poisoned = [r for r in reqs if isinstance(r.error, NaNLogitsError)]
    assert len(poisoned) == 1
    assert len([r for r in reqs if r.state == RequestState.DONE]) == 3
    _check_done_parity(reqs, refs)
    assert eng.allocator.used_pages == 0


def test_real_nan_weights_trip_the_in_step_sentry():
    """Not simulated: genuinely NaN-poisoned weights must trip the fused
    in-step finiteness reduction (prefill path) and FAIL the request with
    NaNLogitsError instead of streaming garbage tokens."""
    pt.seed(3)
    cfg = gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0)
    m = GPTStackedForPretraining(cfg)
    m.eval()
    w = next(iter(m.parameters()))
    w.set_value(np.full(w.shape, np.nan, np.float32))
    eng = _engine(m)
    r = eng.submit(np.array([1, 2, 3], np.int64), N_NEW)
    eng.run_until_idle(max_steps=10)
    assert r.state == RequestState.FAILED
    assert isinstance(r.error, NaNLogitsError)
    assert r.tokens == [], "no garbage token may stream from a NaN slot"
    assert eng.allocator.used_pages == 0
    assert eng.metrics()["quarantined"] == 1


# ---------------------------------------------------------------------------
# allocator exhaustion + callback failures
# ---------------------------------------------------------------------------

def test_injected_pool_exhaustion_backpressures_then_completes(served):
    m, cfg, prompts, refs = served
    eng = _engine(m)
    inj = FaultInjector().inject("alloc", at=0, times=4,
                                 kind="alloc_exhausted").install(eng)
    reqs = [eng.submit(p, N_NEW) for p in prompts[:4]]
    saw_starved = False
    steps = 0
    while eng.queue.depth or eng.scheduler.active_slots:
        met = eng.step()
        steps += 1
        assert met["pages_used"] <= eng.allocator.capacity
        if met["active_slots"] == 0 and met["queue_depth"] > 0:
            saw_starved = True          # exhaustion really backpressured
        assert steps < 300
    assert inj.fired("alloc_exhausted") >= 1
    assert saw_starved
    for r, ref in zip(reqs, refs):
        assert r.state == RequestState.DONE
        assert np.array_equal(r.output_ids(), ref)
    assert eng.allocator.used_pages == 0
    assert eng.metrics()["failed"] == 0


def test_callback_error_recorded_once_and_warned(served):
    m, cfg, prompts, refs = served
    eng = _engine(m)
    boom = RuntimeError("user callback bug")
    calls = []

    def bad_cb(req, tok):
        calls.append(tok)
        raise boom

    with pytest.warns(RuntimeWarning, match="on_token callback"):
        r = eng.submit(prompts[0], N_NEW, on_token=bad_cb)
        eng.run_until_idle()
    assert r.state == RequestState.DONE           # a callback NEVER kills it
    assert np.array_equal(r.output_ids(), refs[0])
    assert r.callback_error is boom               # first error recorded
    assert len(calls) == N_NEW                    # still invoked every token


def test_injected_callback_fault(served):
    m, cfg, prompts, refs = served
    eng = _engine(m)
    inj = FaultInjector().inject("callback", at=0,
                                 kind="callback_error").install(eng)
    seen = []
    with pytest.warns(RuntimeWarning, match="on_token callback"):
        r = eng.submit(prompts[1], N_NEW, on_token=lambda rq, t: seen.append(t))
        eng.run_until_idle()
    assert inj.fired("callback_error") == 1
    assert r.state == RequestState.DONE
    assert isinstance(r.callback_error, InjectedFault)
    assert np.array_equal(r.output_ids(), refs[1])
    assert len(seen) == N_NEW - 1                 # the faulted shot was lost


# ---------------------------------------------------------------------------
# the acceptance property: page accounting exact + survivor parity under
# RANDOMIZED fault schedules
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [7, 23, 101])
def test_randomized_fault_schedule_accounting_property(served, seed):
    m, cfg, prompts, refs = served
    rng = np.random.RandomState(seed)
    eng = _engine(m, num_slots=3)
    inj = random_schedule(rng, horizon=25, n_faults=4,
                          num_slots=3).install(eng)
    reqs = [eng.submit(p, N_NEW) for p in prompts]
    steps = 0
    while eng.queue.depth or eng.scheduler.active_slots:
        met = eng.step()
        steps += 1
        # the allocator invariants must hold at EVERY step boundary
        a = eng.allocator
        assert a.used_pages + a.free_pages == a.capacity
        assert met["pages_used"] <= a.capacity
        assert steps < 2000, "engine stopped making progress under faults"
        if not met["active_slots"] and not met["tokens_this_step"]:
            time.sleep(0.001)          # post-recovery backoff window
    # drained: zero leaked pages, every request terminal and typed
    assert eng.allocator.used_pages == 0
    assert eng.allocator.free_pages == eng.allocator.capacity
    for r in reqs:
        assert r.terminal, r.state
        if r.state != RequestState.DONE:
            assert r.error is not None, f"{r.state} without a typed error"
    # survivors match the unfaulted run token-for-token
    _check_done_parity(reqs, refs)


@pytest.mark.parametrize("seed", [11,
                                  pytest.param(29, marks=pytest.mark.slow),
                                  pytest.param(57, marks=pytest.mark.slow)])
def test_randomized_fault_schedule_with_prefix_cache(served, seed):
    """The accounting property EXTENDED to shared pages (docs/serving.md
    "Prefix cache"): under randomized fault schedules with shared-prefix
    traffic through a prefix-cache-enabled engine, the 4-term allocator
    invariant ``free + used + spec + shared == capacity`` holds at every
    step boundary — through admission splicing, retirement unref, LRU
    eviction, and watchdog recovery (the rebuild flush) — every shared
    page ends unreferenced, and survivors match the unfaulted run."""
    m, cfg, prompts, refs = served
    rng = np.random.RandomState(seed)
    # siblings share one full page (page_size 16) so hits actually occur
    prefix = rng.randint(0, cfg.vocab_size, (16,))
    sprompts = [np.concatenate([prefix, p]) for p in prompts]
    ref_eng = _engine(m, num_slots=3)
    srefs = ref_eng.generate_batch(sprompts, N_NEW)
    ref_eng.close()
    eng = _engine(m, num_slots=3, prefix_cache=True)
    random_schedule(rng, horizon=25, n_faults=4, num_slots=3).install(eng)
    reqs = [eng.submit(p, N_NEW) for p in sprompts]
    steps = 0
    while eng.queue.depth or eng.scheduler.active_slots:
        met = eng.step()
        steps += 1
        a = eng.allocator
        assert (a.used_pages + a.spec_pages + a.free_pages
                + a.shared_pages == a.capacity)
        assert met["pages_used"] <= a.capacity
        assert steps < 2000, "engine stopped making progress under faults"
        if not met["active_slots"] and not met["tokens_this_step"]:
            time.sleep(0.001)
    a = eng.allocator
    assert a.used_pages == 0 and a.spec_pages == 0
    assert a.free_pages + a.shared_pages == a.capacity
    assert all(c == 0 for c in a._shared.values()), (
        "shared page still referenced after drain")
    for r in reqs:
        assert r.terminal, r.state
        if r.state != RequestState.DONE:
            assert r.error is not None, f"{r.state} without a typed error"
    for r, ref in zip(reqs, srefs):
        if r.state == RequestState.DONE:
            assert np.array_equal(r.output_ids(), ref), (
                f"request {r.id} diverged from the unfaulted run")
    eng.close()


def test_generate_batch_raises_on_failed_requests(served):
    m, cfg, prompts, refs = served
    eng = _engine(m)
    _warm(eng, prompts)
    FaultInjector().inject("before_decode", at=0, times=2,
                           kind="step_exception").install(eng)
    with pytest.raises(serving.ServingError, match="did not complete"):
        eng.generate_batch(prompts[:2], N_NEW)
    assert eng.allocator.used_pages == 0
    # opt-out returns whatever each request produced, states inspectable
    eng2 = _engine(m)
    _warm(eng2, prompts)
    FaultInjector().inject("before_decode", at=0, times=2,
                           kind="step_exception").install(eng2)
    outs = eng2.generate_batch(prompts[:2], N_NEW, raise_on_failure=False)
    assert len(outs) == 2


# ---------------------------------------------------------------------------
# Predictor serving mode surfaces the typed terminal states
# ---------------------------------------------------------------------------

def test_predictor_serving_overload_does_not_strand_queued_rows(served):
    """A mid-batch Overloaded must cancel the rows already queued in the
    SHARED engine — otherwise they pin queue depth forever and every
    retry sheds again (permanent wedge)."""
    m, cfg, prompts, refs = served
    config = inference.Config().set_causal_lm_model(m)
    config.enable_serving_mode(max_new_tokens=4, num_slots=2, page_size=16,
                               max_context=64, cache_dtype="float32",
                               max_queue_depth=2)
    predictor = inference.create_predictor(config)
    h = predictor.get_input_handle("x0")
    h.copy_from_cpu(np.stack([prompts[0][:5], prompts[1][:5],
                              prompts[2][:5], prompts[3][:5]]))
    with pytest.raises(Overloaded):
        predictor.run()
    eng = config._get_serving_engine()
    assert eng.queue.depth == 0, "shed batch left rows queued"
    assert eng.allocator.used_pages == 0

def test_predictor_serving_mode_surfaces_deadline(served):
    m, cfg, prompts, refs = served
    config = inference.Config().set_causal_lm_model(m)
    config.enable_serving_mode(max_new_tokens=4, num_slots=2, page_size=16,
                               max_context=64, cache_dtype="float32",
                               deadline_s=0.001)
    predictor = inference.create_predictor(config)
    h = predictor.get_input_handle("x0")
    h.copy_from_cpu(prompts[0][None, :])
    # the 1ms deadline is long past by the second step boundary (the first
    # pays the prefill compile); the reap turns the request TIMED_OUT and
    # Predictor.run re-raises the typed cause
    with pytest.raises(DeadlineExceeded):
        predictor.run()


def test_step_metrics_expose_fault_counters(served):
    m, cfg, prompts, refs = served
    eng = _engine(m)
    eng.submit(prompts[0], 2)
    met = eng.step()
    for key in ("failed", "cancelled", "timed_out", "shed", "recoveries"):
        assert key in met, f"step metrics missing {key}"
    full = eng.metrics()
    for key in ("quarantined", "step_retries", "rebuilds"):
        assert key in full
    eng.run_until_idle()


# ---------------------------------------------------------------------------
# one step in flight: step N+1 is enqueued before step N is read, so what
# only N's result can say voids N+1's rows (docs/serving.md "One step in
# flight").  Four tokens a request, both prompts whole in step 0: a seated
# request emits its k-th token in the harvest of step k - 1.
# ---------------------------------------------------------------------------

def test_nan_row_in_step_n_voids_that_slots_row_in_step_n_plus_1(served):
    m, cfg, prompts, refs = served
    eng = _engine(m)
    inj = FaultInjector().inject("after_decode", at=2, kind="nan_logits",
                                 slots=[0]).install(eng)
    reqs = [eng.submit(p, N_NEW) for p in prompts[:4]]
    eng.run_until_idle()
    assert inj.fired("nan_logits") == 1
    mt = eng.metrics()
    assert mt["quarantined"] == 1 and mt["recoveries"] == 0
    # step 3 was enqueued with a row for the poisoned slot: dropped, uncounted
    assert mt["voided_rows"] == 1
    (poisoned,) = [r for r in reqs if isinstance(r.error, NaNLogitsError)]
    assert poisoned.state == RequestState.FAILED
    assert len(poisoned.tokens) == 2          # steps 0 and 1, nothing after
    assert mt["tokens"] == sum(len(r.tokens) for r in reqs)
    assert len([r for r in reqs if r.state == RequestState.DONE]) == 3
    _check_done_parity(reqs, refs)
    assert eng.allocator.used_pages == 0


@pytest.mark.parametrize("times", [1, 2])
def test_failure_at_the_wait_for_step_n_voids_step_n_plus_1(served, times):
    """A device failure is seen where the host waits for the step's tokens,
    with the next step enqueued already.  The read is tried once more; if
    that fails too the step enqueued behind it is dropped unread and the
    seated requests end FAILED, the queued ones untouched."""
    m, cfg, prompts, refs = served
    eng = _engine(m)
    inj = FaultInjector().inject("await_decode", at=2, times=times,
                                 kind="step_exception").install(eng)
    reqs = [eng.submit(p, N_NEW) for p in prompts[:4]]
    eng.run_until_idle()
    assert inj.fired("step_exception") == times
    mt = eng.metrics()
    assert mt["step_retries"] == 1
    if times == 1:                            # transient: nothing is lost
        assert mt["recoveries"] == 0 and mt["failed"] == 0
        assert mt["voided_rows"] == 0
        assert [r.state for r in reqs] == [RequestState.DONE] * 4
    else:
        assert mt["recoveries"] == 1 and mt["rebuilds"] == 0
        assert mt["voided_rows"] == 2         # both rows of step 3
        failed = [r for r in reqs if r.state == RequestState.FAILED]
        assert failed == reqs[:2]             # the seated pair
        assert all(isinstance(r.error, InjectedFault) for r in failed)
        assert [len(r.tokens) for r in failed] == [2, 2]
        assert [r.state for r in reqs[2:]] == [RequestState.DONE] * 2
    assert mt["tokens"] == sum(len(r.tokens) for r in reqs)
    _check_done_parity(reqs, refs)
    assert eng.allocator.used_pages == 0


def test_stall_at_the_wait_for_step_n_voids_step_n_plus_1(served):
    m, cfg, prompts, refs = served
    eng = _engine(m, stall_budget_s=0.5)
    _warm(eng, prompts)
    old_k = eng.cache.k._value
    inj = FaultInjector().inject("await_decode", at=2, kind="step_stall",
                                 duration=2.0).install(eng)
    reqs = [eng.submit(p, N_NEW) for p in prompts[:4]]
    eng.run_until_idle()
    assert inj.fired("step_stall") == 1
    mt = eng.metrics()
    assert mt["recoveries"] == 1 and mt["rebuilds"] == 1
    assert mt["voided_rows"] == 2             # step 3, enqueued behind it
    stalled = [r for r in reqs if isinstance(r.error, StepStalledError)]
    assert stalled == reqs[:2]
    assert [len(r.tokens) for r in stalled] == [2, 2]
    assert [r.state for r in reqs[2:]] == [RequestState.DONE] * 2
    _check_done_parity(reqs, refs)
    assert eng.allocator.used_pages == 0
    deadline = time.monotonic() + 5.0
    while not old_k.is_deleted() and time.monotonic() < deadline:
        time.sleep(0.05)
    assert old_k.is_deleted(), "zombie cleanup never released the old pool"
    assert not eng.cache.k._value.is_deleted()


def test_dispatch_failure_behind_a_step_in_flight_lands_that_step_first(
        served):
    """The enqueue of step 2 fails twice while step 1 is unread: step 1 was
    enqueued whole, so its tokens are emitted before the seated pair fails."""
    m, cfg, prompts, refs = served
    eng = _engine(m)
    FaultInjector().inject("before_decode", at=2, times=2,
                           kind="step_exception").install(eng)
    reqs = [eng.submit(p, N_NEW) for p in prompts[:4]]
    eng.run_until_idle()
    mt = eng.metrics()
    assert mt["recoveries"] == 1 and mt["step_retries"] == 1
    assert mt["voided_rows"] == 0             # nothing was enqueued behind
    failed = [r for r in reqs if r.state == RequestState.FAILED]
    assert failed == reqs[:2]
    assert [len(r.tokens) for r in failed] == [2, 2]      # steps 0 and 1
    for r, ref in zip(failed, refs):
        assert r.tokens == list(ref[len(r.prompt):][:2])
    assert [r.state for r in reqs[2:]] == [RequestState.DONE] * 2
    _check_done_parity(reqs, refs)
    assert eng.allocator.used_pages == 0


@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_request_retired_while_its_row_is_in_flight(served, how):
    """A cancel or a deadline is honored at the tick's reap, with a step that
    carries the request's row still unread: that row is void (no token
    after the terminal state), the other slot's is not."""
    m, cfg, prompts, refs = served
    eng = _engine(m)
    _warm(eng, prompts)                       # the compile is no deadline's
    base = eng.metrics()["tokens"]
    ra = eng.submit(prompts[0], N_NEW,
                    deadline_s=0.5 if how == "deadline" else None)
    rb = eng.submit(prompts[1], N_NEW)
    eng.step()                                # step 0 enqueued
    eng.step()                                # step 1 enqueued, step 0 read
    assert [len(r.tokens) for r in (ra, rb)] == [1, 1]
    if how == "cancel":
        assert ra.cancel()
    else:
        time.sleep(0.6)
    eng.step()                                # reaped; step 1 read: ra void
    assert ra.state == (RequestState.CANCELLED if how == "cancel"
                        else RequestState.TIMED_OUT)
    assert isinstance(ra.error, RequestCancelled if how == "cancel"
                      else DeadlineExceeded)
    assert len(ra.tokens) == 1 and len(rb.tokens) == 2
    eng.run_until_idle()
    mt = eng.metrics()
    assert mt["voided_rows"] == 1
    assert mt["tokens"] - base == 1 + N_NEW
    assert rb.state == RequestState.DONE
    assert np.array_equal(rb.output_ids(), refs[1])
    assert eng.allocator.used_pages == 0
