#!/usr/bin/env python
"""Elastic-serving CI gate: the closed loop under deterministic load.

Five scripted-load scenarios through ONE dp=2 ShardedServingEngine +
ElasticServingController (fake tick clock, queue-driven policy — the
TTFT path is exercised by tests/test_elastic_serving.py; here the wall
clock would make CPU CI flaky):

  1. scale-up on a load spike — a ``load_spike`` fault plan multiplies
     the scripted arrivals; the controller must activate the parked
     replica (typed ScaleUp) and every admitted request must finish
     DONE, bitwise-equal to the single-shot greedy oracle;
  2. scale-down on idle with a BITWISE drain — sustained underload must
     emit ScaleDown; the drained replica's seated requests checkpoint
     as token-prefix (deadline 0 forces the checkpoint path), re-home
     onto the survivor, and still match the oracle token-for-token;
  3. replica kill -> re-home with exactly-once streams — a
     ``replica_kill`` fault at the cluster_step point must mark the
     replica dead, re-home its live work (never FAILED while capacity
     remains), and each request's concatenated ``on_token`` stream
     across the re-home must equal the oracle continuation EXACTLY
     once (no token dropped, none re-emitted);
  4. brownout ladder engage + LIFO reverse — with no parked capacity
     left, sustained overload must walk BROWNOUT_RUNGS strictly in
     order (max_new clamp observable, prefill budget shrunk, typed
     Overloaded shed at the last rung), and recovery must release the
     rungs strictly LIFO with every actuator restored;
  5. anti-flap under adversarial oscillation — a headless controller
     fed randomized overload/underload flips every tick must keep ANY
     two scale actions >= cooldown_s apart.

Wired into run_tests.sh (PADDLE_TPU_SKIP_ELASTIC_GATE=1 skips).
Exit codes: 0 ok, 1 failure.  See docs/serving.md "Elasticity &
degradation ladder".
"""
from __future__ import annotations

import os
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

import numpy as np  # noqa: E402

PROMPT_LENS = (6, 14, 9, 20, 11, 17)
MAX_NEW = 12          # oracle depth; short requests compare as prefixes


class _Clock:
    """Injectable tick clock: one unit per cluster step."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


def _build():
    import paddle_tpu as pt
    from paddle_tpu.models import GPTStackedForPretraining, gpt_tiny
    from paddle_tpu.serving import ShardedServingEngine

    pt.seed(0)
    cfg = gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0)
    m = GPTStackedForPretraining(cfg)
    m.eval()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)) for n in PROMPT_LENS]
    refs = [np.asarray(
        m.generate(pt.to_tensor(p[None, :], dtype="int64"),
                   max_new_tokens=MAX_NEW, max_seq_len=64,
                   cache_dtype="float32").numpy())[0]
        for p in prompts]
    cluster = ShardedServingEngine(
        m, dp=2, mp=1, num_slots=4, page_size=16, max_context=64,
        cache_dtype="float32")
    return cluster, prompts, refs


def _ctl(cluster, clk, **over):
    """Queue-driven controller: the TTFT band is disabled (min_samples
    astronomically high) so decisions depend only on the scripted queue
    depths — fully deterministic on any host."""
    from paddle_tpu.serving import (
        ElasticConfig, ElasticServingController, SLOTargets,
    )

    kw = dict(targets=SLOTargets(queue_high=3.0, queue_low=0.5),
              min_samples=10**9, cooldown_s=3.0, brownout_cooldown_s=1.0,
              overload_sustain_s=30.0, underload_sustain_s=2.0,
              drain_deadline_s=0.0, min_dp=1, brownout_max_new=8)
    kw.update(over)
    return ElasticServingController(cluster, ElasticConfig(**kw), clock=clk)


def _bitwise(req, ref):
    out = np.asarray(req.output_ids())
    return np.array_equal(out, ref[:out.size])


def _settle(cluster, clk, reqs, ctl=None, max_steps=600):
    """Step (and optionally tick) until every request is terminal and
    nothing is queued or held at the placement layer."""
    for _ in range(max_steps):
        if all(r.terminal for r in reqs) and cluster.placement.pending() == 0:
            return
        if ctl is not None:
            ctl.tick()
        cluster.step()
        clk.t += 1.0
    raise AssertionError("cluster failed to settle")


# ---------------------------------------------------------------------------
# scenarios
# ---------------------------------------------------------------------------

def scale_up_on_spike(cluster, clk, prompts, refs) -> bool:
    """Spike the scripted arrivals via a load_spike plan; the parked
    replica must come back (typed ScaleUp) and all work must finish
    bitwise-correct."""
    from paddle_tpu.serving import FaultInjector, Overloaded, RequestState
    from paddle_tpu.serving import ScaleUp

    cluster.drain_replica(1, deadline_s=0.0)      # start scaled down
    assert cluster.replica_states() == ["active", "parked"]
    ctl = _ctl(cluster, clk)
    inj = FaultInjector()
    inj.inject("traffic", at=3, times=3, kind="load_spike", duration=6.0)
    reqs, shed, k = [], 0, 0
    for tick in range(10):
        ctx = {"multiplier": 1.0}
        inj.hook("traffic", ctx)                  # the traffic-driver point
        arrivals = int(round((1 if tick < 8 else 0) * ctx["multiplier"]))
        for _ in range(arrivals):
            try:
                reqs.append(cluster.submit(prompts[k % len(prompts)], 4))
                k += 1
            except Overloaded:
                shed += 1
        ctl.tick()
        cluster.step()
        clk.t += 1.0
    ups = [a for a in ctl.actions if isinstance(a, ScaleUp)]
    assert ups and ups[0].replica == 1, f"no ScaleUp: {ctl.actions}"
    assert cluster.replica_states() == ["active", "active"]
    assert inj.fired("load_spike") == 3
    _settle(cluster, clk, reqs)
    ctl.close()
    done = sum(r.state == RequestState.DONE for r in reqs)
    assert done == len(reqs), f"{done}/{len(reqs)} DONE (shed={shed})"
    for r in reqs:
        i = PROMPT_LENS.index(len(r.prompt))
        assert _bitwise(r, refs[i]), f"request {r.id} diverged"
    print(f"elastic_gate: scale_up_on_spike OK ({len(reqs)} requests, "
          f"spike x6 for 3 ticks, shed={shed})")
    return True


def scale_down_bitwise_drain(cluster, clk, prompts, refs) -> bool:
    """Sustained idle must emit ScaleDown; the deadline-0 drain forces
    the token-prefix checkpoint path and the re-homed requests must stay
    bitwise-equal to the undrained oracle."""
    from paddle_tpu.serving import RequestState, ScaleDown

    assert cluster.replica_states() == ["active", "active"]
    before = cluster.metrics()["rehomed"]
    reqs = [cluster.submit(p, MAX_NEW) for p in prompts]
    for _ in range(2):                            # seat on both replicas
        cluster.step()
        clk.t += 1.0
    ctl = _ctl(cluster, clk)
    for _ in range(8):
        ctl.tick()
        cluster.step()
        clk.t += 1.0
        if any(isinstance(a, ScaleDown) for a in ctl.actions):
            break
    downs = [a for a in ctl.actions if isinstance(a, ScaleDown)]
    assert downs and downs[0].replica == 1, f"no ScaleDown: {ctl.actions}"
    _settle(cluster, clk, reqs)
    ctl.close()
    assert cluster.replica_states() == ["active", "parked"]
    rehomed = cluster.metrics()["rehomed"] - before
    assert rehomed >= 1, "deadline-0 drain checkpointed nothing"
    assert any(r.rehomed > 0 for r in reqs)
    for r, ref in zip(reqs, refs):
        assert r.state == RequestState.DONE and _bitwise(r, ref), \
            f"re-homed request {r.id} diverged from the undrained oracle"
    for e in cluster.replicas:
        assert e.allocator.used_pages == 0, "pages leaked across the drain"
    print(f"elastic_gate: scale_down_bitwise_drain OK "
          f"({rehomed} checkpointed mid-generation, bitwise)")
    return True


def replica_kill_rehome(cluster, clk, prompts, refs) -> bool:
    """A replica_kill fault mid-run: live work re-homes (never FAILED
    while capacity remains) and each request's concatenated on_token
    stream across the re-home equals the oracle continuation exactly
    once."""
    from paddle_tpu.serving import FaultInjector, RequestState

    cluster.activate_replica(1)
    before = cluster.metrics()["rehomed"]
    inj = FaultInjector()
    inj.inject("cluster_step", at=2, kind="replica_kill", slots=[1])
    cluster._fault_hook = inj.hook
    streamed: dict = {}

    def on_tok(req, tok):
        streamed.setdefault(req.id, []).append(int(tok))

    reqs = [cluster.submit(p, MAX_NEW, on_token=on_tok) for p in prompts]
    # the checkpoint FOLDS streamed tokens into req.prompt — remember the
    # original lengths for the oracle-continuation comparison below
    plens = [len(r.prompt) for r in reqs]
    _settle(cluster, clk, reqs)
    cluster._fault_hook = None
    assert inj.fired("replica_kill") == 1
    assert cluster.replica_states()[1] == "dead"
    rehomed = cluster.metrics()["rehomed"] - before
    assert rehomed >= 1, "the kill re-homed nothing"
    assert any(r.rehomed > 0 for r in reqs)
    for r, ref, plen in zip(reqs, refs, plens):
        assert r.state == RequestState.DONE, \
            f"request {r.id} -> {r.state} (capacity remained: must re-home)"
        assert _bitwise(r, ref), f"request {r.id} diverged after the kill"
        want = list(ref[plen:plen + MAX_NEW])
        assert streamed.get(r.id, []) == want, \
            f"request {r.id}: stream not exactly-once across the re-home"
    print(f"elastic_gate: replica_kill_rehome OK ({rehomed} re-homed, "
          f"streams exactly-once, bitwise)")
    return True


def brownout_ladder(cluster, clk, prompts, refs) -> bool:
    """No parked capacity left (replica 1 is dead): sustained overload
    must walk BROWNOUT_RUNGS strictly in order, the last rung must shed
    with a typed Overloaded, and recovery must release LIFO with every
    actuator restored."""
    from paddle_tpu.serving import (
        BROWNOUT_RUNGS, Brownout, Overloaded, Recover,
    )

    ctl = _ctl(cluster, clk, overload_sustain_s=2.0)
    orig_budget = cluster.replicas[0].prefill_token_budget
    reqs, shed = [], 0
    for tick in range(14):
        for j in range(5):                        # sustained flood
            try:
                reqs.append(cluster.submit(prompts[(tick + j) % 6], 4))
            except Overloaded:
                shed += 1
        ctl.tick()
        cluster.step()
        clk.t += 1.0
    rungs = [a.rung for a in ctl.actions if isinstance(a, Brownout)]
    assert rungs == list(BROWNOUT_RUNGS), \
        f"ladder out of order: {rungs} != {list(BROWNOUT_RUNGS)}"
    assert cluster.max_new_cap == 8               # rung 1 engaged
    assert cluster.replicas[0].prefill_token_budget < orig_budget  # rung 3
    assert cluster.shedding and shed >= 1, "shed rung never refused work"
    # recovery: flood over -> queue drains -> rungs release LIFO
    for _ in range(400):
        ctl.tick()
        cluster.step()
        clk.t += 1.0
        if (ctl.brownout_level == 0 and all(r.terminal for r in reqs)
                and cluster.placement.pending() == 0):
            break
    assert ctl.brownout_level == 0, "ladder never fully released"
    recovered = [a.rung for a in ctl.actions if isinstance(a, Recover)]
    assert recovered == list(reversed(BROWNOUT_RUNGS)), \
        f"recovery not LIFO: {recovered}"
    assert cluster.max_new_cap is None and not cluster.shedding
    assert cluster.replicas[0].prefill_token_budget == orig_budget
    ctl.close()
    for r in reqs:
        assert r.terminal, f"request {r.id} not terminal after recovery"
        if r.state == "DONE":
            i = PROMPT_LENS.index(len(r.prompt))
            assert _bitwise(r, refs[i]), f"request {r.id} diverged"
    print(f"elastic_gate: brownout_ladder OK (4 rungs in order, "
          f"shed={shed} typed, released LIFO, actuators restored)")
    return True


def anti_flap() -> bool:
    """Headless adversarial oscillation: overload/underload flips every
    tick for 500 ticks; any two scale actions must still be >=
    cooldown_s apart (the shared-cooldown structural guarantee)."""
    from paddle_tpu.serving import (
        ClusterSignals, ElasticConfig, ElasticServingController,
        ScaleDown, ScaleUp, SLOTargets,
    )

    cfg = ElasticConfig(targets=SLOTargets(ttft_p99_s=0.5, queue_high=3.0,
                                           queue_low=0.5),
                        min_samples=0, cooldown_s=3.0,
                        underload_sustain_s=0.0)
    ctl = ElasticServingController(config=cfg)
    rng = np.random.RandomState(7)
    times = []
    for i in range(500):
        over = (i % 2 == 0) if rng.rand() < 0.8 else rng.rand() < 0.5
        sig = ClusterSignals(
            now=i * 0.25,
            ttft_p99=5.0 if over else 0.01, itl_p99=0.0, window_count=64,
            queue_per_replica=10.0 if over else 0.0, occupancy=0.5,
            active_dp=2 if not over else 1,
            parked=(1,) if over else (),
            scalable=(0, 1) if not over else (0,))
        for a in ctl.tick(sig):
            if isinstance(a, (ScaleUp, ScaleDown)):
                times.append(i * 0.25)
    ctl.close()
    assert len(times) >= 2, "oscillation produced <2 scale actions"
    gaps = [b - a for a, b in zip(times, times[1:])]
    assert min(gaps) >= cfg.cooldown_s - 1e-9, \
        f"flap: scale actions {min(gaps):.2f}s apart < {cfg.cooldown_s}s"
    print(f"elastic_gate: anti_flap OK ({len(times)} scale actions over "
          f"500 adversarial ticks, min gap {min(gaps):.2f}s >= "
          f"{cfg.cooldown_s}s)")
    return True


def gate() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    cluster, prompts, refs = _build()
    clk = _Clock()
    # warmup: compile both replicas' step programs before the clock runs
    w = [cluster.submit(p, 2) for p in prompts[:2]]
    cluster.run_until_idle(max_steps=200)
    assert all(r.terminal for r in w)
    ok = True
    try:
        ok &= scale_up_on_spike(cluster, clk, prompts, refs)
        ok &= scale_down_bitwise_drain(cluster, clk, prompts, refs)
        ok &= replica_kill_rehome(cluster, clk, prompts, refs)
        ok &= brownout_ladder(cluster, clk, prompts, refs)
        ok &= anti_flap()
    except AssertionError as e:
        print(f"elastic_gate: FAIL {e}")
        ok = False
    finally:
        cluster.close()
    if not ok:
        return 1
    print("elastic_gate: OK (scale-up, bitwise drain, kill re-home, "
          "brownout ladder, anti-flap)")
    return 0


if __name__ == "__main__":
    sys.exit(gate())
