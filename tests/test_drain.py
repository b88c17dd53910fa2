"""Graceful drain + re-homing (ISSUE 19; docs/serving.md "Elasticity &
degradation ladder").

The drain contract under test:

- ``begin_drain`` stops admission immediately (typed ``Overloaded``, NOT
  counted as shed) and harvests the queue; seated work keeps running and
  ``drained`` flips once the last seated request retires;
- ``checkpoint_seated`` folds each seated request's emitted tokens into
  its prompt (``output_ids()`` is invariant under the fold), shrinks the
  remaining ``max_new`` grant, and returns the SAME Request object ready
  to requeue — which is what makes re-homed streams exactly-once and
  greedy output bitwise-identical to an undrained run;
- the placement layer re-homes harvested requests onto survivors, parks
  the unseatable ones in a held queue (still live), reaps held requests
  that cancel/expire (the cross-replica cancel bugfix), and fails them
  typed only when NO eligible replica remains;
- the randomized property: drain/kill at a random tick under in-flight
  speculative + prefix-shared + LoRA traffic keeps the 4-term page
  accounting invariant on every survivor, drains BOTH pools on the
  drained replica, and every re-homed output is bitwise-equal to an
  undrained oracle.
"""
import time
from types import SimpleNamespace

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models import GPTStackedForPretraining, gpt_tiny
from paddle_tpu.serving import (
    LoRAAdapterPool,
    Overloaded,
    PlacementScheduler,
    PrefixLocalityPlacement,
    RequestState,
    ServingEngine,
    ShardedServingEngine,
    SpeculativeEngine,
    random_adapter,
)
from paddle_tpu.serving.placement import (
    LeastLoadedPlacement,
    replica_signals,
)

N_NEW = 4


@pytest.fixture(scope="module")
def served():
    pt.seed(0)
    cfg = gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0)
    m = GPTStackedForPretraining(cfg)
    m.eval()
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, cfg.vocab_size, (s,))
               for s in (5, 9, 7, 12, 17, 4)]
    refs = [np.asarray(
        m.generate(pt.to_tensor(p[None, :], dtype="int64"),
                   max_new_tokens=N_NEW, max_seq_len=64,
                   cache_dtype="float32").numpy())[0]
        for p in prompts]
    return m, cfg, prompts, refs


def _engine(m, **kw):
    base = dict(num_slots=2, page_size=16, max_context=64,
                cache_dtype="float32")
    base.update(kw)
    return ServingEngine(m, **base)


def _cluster(m, **kw):
    base = dict(dp=2, mp=1, num_slots=2, page_size=16, max_context=64,
                cache_dtype="float32")
    base.update(kw)
    return ShardedServingEngine(m, **base)


# ---------------------------------------------------------------------------
# engine-level drain lifecycle
# ---------------------------------------------------------------------------

def test_begin_drain_stops_admission_and_harvests_queue(served):
    m, cfg, prompts, refs = served
    eng = _engine(m)
    reqs = [eng.submit(p, N_NEW) for p in prompts]
    eng.step()                                   # seat the first slots
    queued_before = eng.queue.depth
    assert queued_before > 0
    harvested = eng.begin_drain()
    assert len(harvested) == queued_before
    assert eng.queue.depth == 0
    assert eng.draining and not eng.drained      # seated work still live
    shed_before = eng.metrics()["shed"]
    with pytest.raises(Overloaded, match="draining"):
        eng.submit(prompts[0], N_NEW)
    # drain refusals are routing events, not load shedding
    assert eng.metrics()["shed"] == shed_before
    # seated work runs to completion; the engine then reports drained
    steps = 0
    while not eng.drained:
        eng.step()
        steps += 1
        assert steps < 500
    seated = [r for r in reqs if r not in harvested]
    for r in seated:
        assert r.state == RequestState.DONE
    assert eng.metrics()["draining"] is True
    eng.resume_admission()
    assert not eng.draining
    r = eng.submit(prompts[0], N_NEW)
    eng.run_until_idle()
    assert np.array_equal(r.output_ids(), refs[0])
    eng.close()


def test_checkpoint_fold_preserves_output_ids_bitwise(served):
    """The fold invariant: checkpoint mid-decode, requeue on a FRESH
    engine, and the final output_ids() match the undrained oracle
    bitwise — the emitted prefix is neither lost nor re-emitted."""
    m, cfg, prompts, refs = served
    src = _engine(m)
    emitted = {}

    def count(r, _tok):
        emitted[r.id] = emitted.get(r.id, 0) + 1

    reqs = [src.submit(p, N_NEW, on_token=count) for p in prompts[:2]]
    # run until at least one token has been emitted somewhere
    steps = 0
    while not any(r.tokens for r in reqs):
        src.step()
        steps += 1
        assert steps < 200
    # the checkpoint lands the step in flight first: what that streams is
    # part of the fold, so the count is the callbacks', taken after it
    ckpt = src.checkpoint_seated()
    assert src.scheduler.active_slots == 0
    assert src.allocator.used_pages == 0
    for r in ckpt:
        assert r.state == RequestState.SUBMITTED
        assert r.tokens == []
        assert r.rehomed == emitted[r.id]
        assert r.max_new_tokens == N_NEW - emitted[r.id]
    drained_total = src.metrics()["drained"]
    assert drained_total == len(ckpt)
    dst = _engine(m)
    for r in ckpt:
        dst.requeue(r)
    dst.run_until_idle()
    for r, ref in zip(reqs, refs):
        if r in ckpt or r.state == RequestState.DONE:
            assert r.state == RequestState.DONE, (r.state, r.error)
            assert np.array_equal(r.output_ids(), ref), (
                f"re-homed request {r.id} diverged from undrained oracle")
    src.close()
    dst.close()


def test_requeue_resets_queue_wait_clock(served):
    """A re-homed request's queue-wait shedding clock restarts at the
    survivor: time spent on the dead replica's queue must not count
    against the new queue's ``max_queue_wait_s`` (the re-homed request
    would otherwise be shed the instant it arrived)."""
    m, cfg, prompts, refs = served
    src = _engine(m)
    r = src.submit(prompts[0], N_NEW)
    # simulate a long stay on the source queue
    r.submit_t -= 3600.0
    [h] = src.begin_drain()
    assert h is r
    dst = _engine(m, max_queue_wait_s=5.0)
    dst.requeue(r)
    assert time.monotonic() - r.submit_t < 1.0
    dst.run_until_idle()
    assert r.state == RequestState.DONE, (r.state, r.error)
    assert np.array_equal(r.output_ids(), refs[0])
    src.close()
    dst.close()


def test_requeue_refuses_draining_engine_and_missing_adapter(served):
    m, cfg, prompts, refs = served
    src = _engine(m)
    r = src.submit(prompts[0], N_NEW)
    [h] = src.begin_drain()
    dst = _engine(m)
    dst.begin_drain()
    with pytest.raises(Overloaded, match="draining"):
        dst.requeue(h)
    dst.resume_admission()
    h.adapter = "tenant-x"                       # no pool on dst
    with pytest.raises(Overloaded, match="LoRA"):
        dst.requeue(h)
    src.close()
    dst.close()


# ---------------------------------------------------------------------------
# cluster-level drain / replica loss
# ---------------------------------------------------------------------------

def test_cluster_drain_parks_replica_bitwise_parity(served):
    m, cfg, prompts, refs = served
    eng = _cluster(m)
    reqs = [eng.submit(p, N_NEW) for p in prompts]
    eng.step()
    # deadline_s=0 forces the checkpoint path on whatever is seated
    eng.begin_drain_replica(0, deadline_s=0.0)
    eng.run_until_idle(max_steps=500)
    assert eng.replica_states()[0] == "parked"
    assert eng.active_dp == 1
    for r, ref in zip(reqs, refs):
        assert r.state == RequestState.DONE, (r.id, r.state, r.error)
        assert np.array_equal(r.output_ids(), ref), (
            f"request {r.id} diverged after drain re-home")
    for i, rep in enumerate(eng.replicas):
        a = rep.allocator
        assert (a.free_pages + a.used_pages + a.spec_pages
                + a.shared_pages == a.capacity), f"replica {i}"
        assert a.used_pages == 0
    # a parked replica burns no replica-steps
    before = eng.metrics()["replica_steps"]
    eng.step()
    assert eng.metrics()["replica_steps"] == before + 1
    # ...and comes back without recompilation
    eng.activate_replica(0)
    assert eng.replica_states()[0] == "active"
    out = eng.generate_batch(prompts[:2], N_NEW)
    for g, ref in zip(out, refs):
        assert np.array_equal(g, ref)
    eng.close()


def test_replica_kill_rehomes_live_requests(served):
    m, cfg, prompts, refs = served
    eng = _cluster(m)
    reqs = [eng.submit(p, N_NEW) for p in prompts]
    for _ in range(2):
        eng.step()
    eng.kill_replica(1)
    assert eng.replica_states()[1] == "dead"
    eng.run_until_idle(max_steps=500)
    for r, ref in zip(reqs, refs):
        assert r.state == RequestState.DONE, (r.id, r.state, r.error)
        assert np.array_equal(r.output_ids(), ref), (
            f"request {r.id} diverged after replica-kill re-home")
    met = eng.metrics()
    assert met["rehomed"] >= 1
    assert met["active_dp"] == 1
    eng.close()


def test_kill_all_replicas_fails_held_requests_typed(served):
    m, cfg, prompts, refs = served
    eng = _cluster(m)
    reqs = [eng.submit(p, N_NEW) for p in prompts]
    eng.kill_replica(0)
    eng.kill_replica(1)
    for r in reqs:
        assert r.terminal, r.state
        assert r.state == RequestState.FAILED
        assert isinstance(r.error, Overloaded)
    assert len(eng.placement.held) == 0
    eng.close()


def test_replica_kill_via_fault_injection(served):
    """`replica_kill` rides the cluster_step hook: occurrence-keyed like
    every other fault, the shot fires mid-traffic and the cluster
    re-homes exactly as if kill_replica were called directly."""
    from paddle_tpu.serving import FaultInjector
    m, cfg, prompts, refs = served
    eng = _cluster(m)
    inj = FaultInjector().inject("cluster_step", at=2, kind="replica_kill",
                                 slots=[1])
    eng._fault_hook = inj.hook
    reqs = [eng.submit(p, N_NEW) for p in prompts]
    eng.run_until_idle(max_steps=500)
    assert inj.fired("replica_kill") == 1
    assert eng.replica_states()[1] == "dead"
    for r, ref in zip(reqs, refs):
        assert r.state == RequestState.DONE, (r.id, r.state, r.error)
        assert np.array_equal(r.output_ids(), ref)
    eng.close()


# ---------------------------------------------------------------------------
# placement layer: held queue, cancel sweep (the cross-replica bugfix)
# ---------------------------------------------------------------------------

def _held_request(served):
    """One live request parked in a placement held queue: harvested off a
    draining engine, target replica's queue full so resubmit can't seat
    it."""
    m, cfg, prompts, refs = served
    src = _engine(m)
    req = src.submit(prompts[0], N_NEW)
    [h] = src.begin_drain()
    dst = _engine(m, max_queue_depth=1)
    blocker = dst.submit(prompts[1], N_NEW)      # fills the bounded queue
    ps = PlacementScheduler([dst])
    assert not ps.resubmit(h)
    assert list(ps.held) == [h]
    return src, dst, ps, h, blocker


def test_cancel_while_held_is_reaped_by_placement_sweep(served):
    """Regression (ISSUE 19 satellite): a request cancelled while parked
    at the placement layer sits on NO replica's queue, so no replica's
    reaper ever sees it — before the sweep it would hang its waiter
    forever."""
    src, dst, ps, h, _b = _held_request(served)
    assert h.cancel()
    assert ps.sweep() == 1
    assert h.state == RequestState.CANCELLED
    assert h.error is not None and h._done.is_set()
    assert len(ps.held) == 0
    src.close()
    dst.close()


def test_deadline_expiry_while_held_is_reaped(served):
    src, dst, ps, h, _b = _held_request(served)
    h.deadline = time.monotonic() - 1.0
    assert ps.sweep() == 1
    assert h.state == RequestState.TIMED_OUT
    src.close()
    dst.close()


def test_flush_held_seats_when_capacity_frees(served):
    m, cfg, prompts, refs = served
    src, dst, ps, h, blocker = _held_request(served)
    dst.run_until_idle()                         # blocker completes
    assert ps.sweep() == 0                       # still live, not reaped
    assert ps.flush_held() == 1
    assert len(ps.held) == 0
    dst.run_until_idle()
    assert h.state == RequestState.DONE
    assert np.array_equal(h.output_ids(), refs[0])
    assert ps.rehomed_total == 1
    src.close()
    dst.close()


# ---------------------------------------------------------------------------
# placement signals: LoRA residency + speculative acceptance (satellite)
# ---------------------------------------------------------------------------

def _fake_engine(depth=0, used=0, cap=10, active=0, adapters=None,
                 accept=None, match=0):
    e = SimpleNamespace(
        queue=SimpleNamespace(depth=depth, max_depth=None),
        allocator=SimpleNamespace(used_pages=used, capacity=cap),
        scheduler=SimpleNamespace(active_slots=active))
    if adapters is not None:
        e.lora = SimpleNamespace(adapters=lambda: {a: 0 for a in adapters})
    if accept is not None:
        e._spec_totals = {"proposed_tokens": 100,
                          "accepted_tokens": int(accept * 100)}
    if match:
        e.prefix_cache = SimpleNamespace(match_len=lambda p: match)
    return e


def test_replica_signals_reads():
    e = _fake_engine(adapters=("t1",), accept=0.75)
    assert replica_signals(e, "t1") == (True, 0.75)
    assert replica_signals(e, "t2") == (False, 0.75)
    assert replica_signals(e, None) == (False, 0.75)
    bare = _fake_engine()
    assert replica_signals(bare, "t1") == (False, 1.0)  # neutral defaults


def test_rank_for_adapter_residency_outranks_load():
    idle_cold = _fake_engine(depth=0)
    busy_resident = _fake_engine(depth=5, adapters=("t1",))
    pol = LeastLoadedPlacement()
    engines = [idle_cold, busy_resident]
    # with the tenant in hand, residency wins despite the load
    assert pol.rank_for(engines, None, adapter="t1") == [1, 0]
    # without it, historical least-loaded ordering is unchanged
    assert pol.rank_for(engines, None) == [0, 1]
    assert pol.rank(engines) == [0, 1]


def test_rank_for_acceptance_rate_breaks_load_ties():
    slow = _fake_engine(accept=0.2)
    fast = _fake_engine(accept=0.9)
    pol = LeastLoadedPlacement()
    assert pol.rank_for([slow, fast], None) == [1, 0]
    # load differences still dominate the acceptance tiebreak
    busy_fast = _fake_engine(depth=3, accept=0.9)
    assert pol.rank_for([slow, busy_fast], None) == [0, 1]


def test_prefix_locality_keeps_prefix_primary_under_signals():
    warm = _fake_engine(depth=4, match=16, accept=0.1)
    cold = _fake_engine(depth=0, match=0, accept=0.9)
    pol = PrefixLocalityPlacement()
    assert pol.rank_for([cold, warm], np.arange(20)) == [1, 0]
    # ...but residency outranks even the prefix match
    resident_cold = _fake_engine(depth=0, match=0, adapters=("t1",))
    assert pol.rank_for([resident_cold, warm], np.arange(20),
                        adapter="t1") == [0, 1]


def test_old_signature_rank_for_policies_still_work(served):
    """Pre-PR-19 policies take rank_for(engines, prompt) with no adapter
    kwarg; the placement walk falls back instead of crashing."""
    m, cfg, prompts, refs = served

    class OldPolicy:
        def rank(self, engines):
            return list(range(len(engines)))

        def rank_for(self, engines, prompt):      # no adapter kwarg
            return list(range(len(engines)))

    eng = _engine(m)
    ps = PlacementScheduler([eng], policy=OldPolicy())
    r = ps.submit(prompts[0], N_NEW, adapter=None)
    eng.run_until_idle()
    assert r.state == RequestState.DONE
    eng.close()


# ---------------------------------------------------------------------------
# the randomized drain property (satellite): spec + prefix + LoRA traffic
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [13,
                                  pytest.param(37, marks=pytest.mark.slow),
                                  pytest.param(91, marks=pytest.mark.slow)])
def test_randomized_drain_property_spec_prefix_lora(served, seed):
    """Drain (or kill — the rng picks) one replica at a random tick under
    in-flight speculative + prefix-shared + LoRA traffic:

    - 4-term page accounting (`free+used+spec+shared == capacity`) holds
      at every step boundary on every surviving replica;
    - the drained replica ends with BOTH pools empty (target pages AND
      draft pages);
    - every request terminates DONE and bitwise-equal to an undrained
      oracle (capacity remains, so nothing may fail)."""
    m, cfg, prompts, refs = served
    rng = np.random.RandomState(seed)
    prefix = rng.randint(0, cfg.vocab_size, (16,))   # one full shared page
    sprompts = [np.concatenate([prefix, p]) for p in prompts]
    adapters = [("t1" if i % 2 == 0 else None)
                for i in range(len(sprompts))]

    def _pool():
        p = LoRAAdapterPool(cfg, num_adapter_pages=2, rank=2,
                            dtype="float32")
        p.register("t1", random_adapter(cfg, 2, np.random.RandomState(7)))
        return p

    # oracle: plain engine, same pool semantics, no drain (greedy spec is
    # bitwise-equal to the plain engine — pinned by test_speculative)
    ref_eng = ServingEngine(m, lora=_pool(), num_slots=2, page_size=16,
                            max_context=80, cache_dtype="float32",
                            prefix_cache=True)
    oreqs = [ref_eng.submit(p, N_NEW, adapter=a)
             for p, a in zip(sprompts, adapters)]
    ref_eng.run_until_idle()
    oracle = [r.output_ids() for r in oreqs]
    ref_eng.close()

    def factory(model, mesh, index, **kw):
        return SpeculativeEngine(model, model, spec_k=2, mesh=mesh,
                                 lora=_pool(), prefix_cache=True, **kw)

    eng = ShardedServingEngine(m, dp=2, mp=1, engine_factory=factory,
                               num_slots=2, page_size=16, max_context=80,
                               cache_dtype="float32")
    reqs = [eng.submit(p, N_NEW, adapter=a)
            for p, a in zip(sprompts, adapters)]
    victim = int(rng.randint(2))
    drain_at = int(rng.randint(1, 6))
    kill = bool(rng.randint(2))
    deadline = float(rng.choice([0.0, 30.0]))
    steps = 0
    drained = False
    while eng.placement.pending():
        if steps == drain_at:
            if kill:
                eng.kill_replica(victim)
            else:
                eng.begin_drain_replica(victim, deadline_s=deadline)
            drained = True
        eng.step()
        steps += 1
        assert steps < 1000, "cluster stopped making progress"
        for i, rep in enumerate(eng.replicas):
            if i in eng._dead:
                continue
            a = rep.allocator
            assert (a.free_pages + a.used_pages + a.spec_pages
                    + a.shared_pages == a.capacity), (
                f"replica {i} accounting broke at step {steps}")
    assert drained
    v = eng.replicas[victim]
    if not kill:
        assert eng.replica_states()[victim] == "parked"
        assert v.allocator.used_pages == 0
        assert v.allocator.spec_pages == 0
        assert v.draft.allocator.used_pages == 0     # both pools drained
    for r, ref in zip(reqs, oracle):
        assert r.state == RequestState.DONE, (r.id, r.state, r.error)
        assert np.array_equal(r.output_ids(), ref), (
            f"request {r.id} (rehomed={r.rehomed}) diverged from the "
            "undrained oracle")
    eng.close()


def test_speculation_toggle_mid_run_keeps_greedy_parity(served):
    """Brownout rung 2's actuator: flipping ``speculation_enabled`` off
    mid-run degrades to plain decode (no draft dispatch) without
    changing greedy output; re-enabling catches the draft back up."""
    m, cfg, prompts, refs = served
    eng = SpeculativeEngine(m, m, spec_k=3, num_slots=2, page_size=16,
                            max_context=64, cache_dtype="float32")
    reqs = [eng.submit(p, N_NEW) for p in prompts[:3]]
    eng.step()
    eng.speculation_enabled = False
    for _ in range(3):
        eng.step()
    eng.speculation_enabled = True
    eng.run_until_idle()
    for r, ref in zip(reqs, refs):
        assert r.state == RequestState.DONE, (r.state, r.error)
        assert np.array_equal(r.output_ids(), ref)
    assert eng.allocator.used_pages == 0
    assert eng.draft.allocator.used_pages == 0
    eng.close()
