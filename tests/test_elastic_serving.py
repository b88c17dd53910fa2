"""Elastic serving controller (ISSUE 19; docs/serving.md "Elasticity &
degradation ladder").

The policy is deliberately tiny and fully deterministic, so it is tested
the way deterministic code should be: headless (``cluster=None``), with
synthetic :class:`ClusterSignals` and a fake clock — thousands of ticks,
no model, no devices.  Covered here:

- hysteresis bands: overload/underload/dead-zone classification and the
  sustain timers gating ladder movement;
- scale priority: parked capacity absorbs overload before any brownout
  rung engages; recovery releases rungs strictly LIFO before any replica
  drains;
- the ANTI-FLAP property: for ANY input signal sequence (seeded random,
  including adversarial band-oscillation), two scale actions are never
  closer than ``cooldown_s`` — both directions gate on and arm one
  shared cooldown clock, so the property is structural, not tuned;
- clock-jump regression (satellite): the policy and the engine's
  queue-wait shedding read only ``time.monotonic``/the injected clock —
  a wall-clock (``time.time``) jump of a million seconds changes
  nothing;
- telemetry: ``serving_controller_actions_total{action}``,
  ``serving_brownout_level``, ``serving_rehomed_requests_total`` on the
  PR-9 registry, asserted through the Prometheus text exposition;
- one end-to-end closed loop on a real dp=2 tiny cluster: spike ->
  ScaleUp, idle -> ScaleDown, brownout actuators engage and restore in
  LIFO order.
"""
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models import GPTStackedForPretraining, gpt_tiny
from paddle_tpu.serving import (
    BROWNOUT_RUNGS,
    Brownout,
    ClusterSignals,
    ElasticConfig,
    ElasticServingController,
    Recover,
    RequestState,
    SLOTargets,
    ScaleDown,
    ScaleUp,
    ServingEngine,
    ShardedServingEngine,
)
from paddle_tpu.telemetry import metrics as _tmetrics


def _cfg(**kw):
    base = dict(
        targets=SLOTargets(ttft_p99_s=0.5, queue_high=4.0, queue_low=0.5,
                           recover_frac=0.5),
        window_s=10.0, min_samples=4, cooldown_s=5.0,
        brownout_cooldown_s=2.0, overload_sustain_s=1.0,
        underload_sustain_s=1.0, min_dp=1)
    base.update(kw)
    return ElasticConfig(**base)


def _sig(now, *, ttft=0.0, n=100, queue=0.0, active=2, parked=(),
         scalable=(0, 1)):
    return ClusterSignals(now=now, ttft_p99=ttft, itl_p99=0.0,
                          window_count=n, queue_per_replica=queue,
                          occupancy=0.5, active_dp=active,
                          parked=tuple(parked), scalable=tuple(scalable))


OVER = dict(ttft=2.0, queue=10.0)
UNDER = dict(ttft=0.01, queue=0.0)


def _ctl(**kw):
    return ElasticServingController(None, _cfg(**kw), clock=lambda: 0.0)


# ---------------------------------------------------------------------------
# headless policy unit tests
# ---------------------------------------------------------------------------

def test_scale_up_on_overload_prefers_lowest_parked():
    ctl = _ctl()
    acts = ctl.tick(_sig(0.0, parked=(2, 3), **OVER))
    assert acts == [ScaleUp(replica=2, reason=acts[0].reason)]
    ctl.close()


def test_scale_up_gated_by_cooldown():
    ctl = _ctl()
    assert ctl.tick(_sig(0.0, parked=(2,), **OVER))
    assert ctl.tick(_sig(1.0, parked=(3,), **OVER)) == []   # in cooldown
    assert ctl.tick(_sig(5.0, parked=(3,), **OVER))         # expired
    ctl.close()


def test_untrusted_window_does_not_flag_slo_breach():
    ctl = _ctl()
    # huge p99 but too few samples: only the queue band may trigger
    acts = ctl.tick(_sig(0.0, ttft=99.0, n=1, queue=0.0, parked=(2,)))
    assert acts == []
    ctl.close()


def test_brownout_engages_only_at_max_dp_after_sustain():
    ctl = _ctl()
    assert ctl.tick(_sig(0.0, parked=(), **OVER)) == []     # sustain young
    assert ctl.tick(_sig(0.5, parked=(), **OVER)) == []
    acts = ctl.tick(_sig(1.5, parked=(), **OVER))           # aged >= 1s
    assert len(acts) == 1 and isinstance(acts[0], Brownout)
    assert acts[0].rung == BROWNOUT_RUNGS[0] and acts[0].level == 1
    assert ctl.brownout_level == 1
    ctl.close()


def test_brownout_ladder_full_engage_then_lifo_release():
    ctl = _ctl()
    t = 0.0
    while ctl.brownout_level < len(BROWNOUT_RUNGS):
        ctl.tick(_sig(t, parked=(), **OVER))
        t += 0.5
    engaged = [a for a in ctl.actions if isinstance(a, Brownout)]
    assert [a.rung for a in engaged] == list(BROWNOUT_RUNGS)
    # rung-to-rung spacing honors the brownout cooldown
    times = [a.level for a in engaged]
    assert times == [1, 2, 3, 4]
    # recovery: strictly LIFO
    t += 10.0
    while ctl.brownout_level > 0:
        ctl.tick(_sig(t, parked=(), **UNDER))
        t += 0.5
    released = [a for a in ctl.actions if isinstance(a, Recover)]
    assert [a.rung for a in released] == list(reversed(BROWNOUT_RUNGS))
    ctl.close()


def test_scale_down_only_after_ladder_fully_released():
    ctl = _ctl()
    ctl.brownout_level = 2
    t = 0.0
    acts = []
    for _ in range(20):
        acts += ctl.tick(_sig(t, scalable=(0, 1), **UNDER))
        t += 0.5
    kinds = [type(a).__name__ for a in acts]
    # both rungs release BEFORE any drain starts, and the drain picks
    # the highest scalable index
    assert kinds[:3] == ["Recover", "Recover", "ScaleDown"]
    assert [a for a in acts if isinstance(a, ScaleDown)][0].replica == 1
    ctl.close()


def test_scale_down_respects_min_dp():
    ctl = _ctl(min_dp=1)
    t = 0.0
    acts = []
    for _ in range(20):
        acts += ctl.tick(_sig(t, active=1, scalable=(0,), **UNDER))
        t += 1.0
    assert acts == []                           # never below min_dp
    ctl.close()


def test_dead_zone_resets_sustain_timers():
    ctl = _ctl()
    ctl.tick(_sig(0.0, parked=(), **OVER))
    assert ctl._overload_since == 0.0
    # neither band: timers clear, so the next overload starts aging fresh
    ctl.tick(_sig(0.5, parked=(), ttft=0.3, queue=2.0))
    assert ctl._overload_since is None
    assert ctl.tick(_sig(1.0, parked=(), **OVER)) == []     # young again
    ctl.close()


def test_hysteresis_dead_zone_is_nonempty():
    """A signal between the bands (queue_low < q < queue_high, p99 in
    (recover_frac*target, target)) triggers NOTHING in either direction
    — the structural anti-oscillation gap."""
    ctl = _ctl()
    ctl.brownout_level = 1
    acts = []
    for t in range(30):
        acts += ctl.tick(_sig(float(t), ttft=0.3, queue=2.0,
                              parked=(2,), scalable=(0, 1)))
    assert acts == []
    ctl.close()


# ---------------------------------------------------------------------------
# the anti-flap property
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(8))
def test_anti_flap_property_random_signals(seed):
    """For ANY signal sequence — including adversarial oscillation right
    across both bands every tick — consecutive scale actions are at
    least ``cooldown_s`` apart.  Structural: both directions gate on and
    arm the one shared cooldown."""
    rng = np.random.RandomState(seed)
    cfg = _cfg(cooldown_s=3.0)
    ctl = ElasticServingController(None, cfg, clock=lambda: 0.0)
    t = 0.0
    scale_times = []
    for _ in range(400):
        t += float(rng.uniform(0.05, 0.5))
        band = rng.randint(3)
        kw = OVER if band == 0 else UNDER if band == 1 else dict(
            ttft=0.3, queue=2.0)
        sig = _sig(t, parked=((2,) if rng.rand() < 0.5 else ()),
                   scalable=(0, 1), **kw)
        for a in ctl.tick(sig):
            if isinstance(a, (ScaleUp, ScaleDown)):
                scale_times.append(t)
    for a, b in zip(scale_times, scale_times[1:]):
        assert b - a >= cfg.cooldown_s - 1e-9, (
            f"flap: scale actions {a:.2f}s and {b:.2f}s are closer than "
            f"cooldown_s={cfg.cooldown_s}")
    ctl.close()


def test_adversarial_band_oscillation_cannot_flap():
    """Flip overload<->underload EVERY tick at 10 Hz: at most one scale
    action per cooldown window can emerge."""
    cfg = _cfg(cooldown_s=5.0, underload_sustain_s=0.0)
    ctl = ElasticServingController(None, cfg, clock=lambda: 0.0)
    scale_times = []
    t = 0.0
    for i in range(600):
        t += 0.1
        kw = OVER if i % 2 == 0 else UNDER
        for a in ctl.tick(_sig(t, parked=(2,), scalable=(0, 1), **kw)):
            if isinstance(a, (ScaleUp, ScaleDown)):
                scale_times.append(t)
    assert scale_times, "policy never acted at all"
    for a, b in zip(scale_times, scale_times[1:]):
        assert b - a >= cfg.cooldown_s - 1e-9
    ctl.close()


# ---------------------------------------------------------------------------
# clock-jump regression (satellite)
# ---------------------------------------------------------------------------

def test_policy_immune_to_wall_clock_jumps(monkeypatch):
    """Identical signal sequences produce identical action sequences
    while ``time.time`` jumps around by a million seconds — the policy
    reads time ONLY through its injected monotonic clock."""
    def run(patch):
        ctl = ElasticServingController(None, _cfg(), clock=lambda: 0.0)
        jump = [0.0]
        if patch:
            monkeypatch.setattr(time, "time",
                                lambda: 1e9 + jump[0])
        out = []
        t = 0.0
        for i in range(60):
            t += 0.5
            jump[0] = (-1e6 if i % 3 else 1e6)      # wall clock thrashes
            kw = OVER if i < 30 else UNDER
            out += [type(a).__name__ for a in
                    ctl.tick(_sig(t, parked=(2,) if i < 30 else (),
                                  scalable=(0, 1, 2), **kw))]
        ctl.close()
        return out
    assert run(patch=False) == run(patch=True)


def test_queue_wait_shedding_immune_to_wall_clock_jump(monkeypatch):
    """Engine-side half of the satellite: ``max_queue_wait_s`` shedding
    is driven by time.monotonic, so a wall-clock jump mid-queue must not
    spuriously shed (nor a backwards jump keep a request alive)."""
    pt.seed(0)
    cfg = gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0)
    m = GPTStackedForPretraining(cfg)
    m.eval()
    prompts = [np.arange(5), np.arange(7)]
    eng = ServingEngine(m, num_slots=2, page_size=16, max_context=64,
                        cache_dtype="float32", max_queue_wait_s=30.0)
    # wall clock jumps forward an hour the moment the requests queue
    monkeypatch.setattr(time, "time", lambda: 1e9)
    reqs = [eng.submit(p, 3) for p in prompts]
    eng.run_until_idle()
    for r in reqs:
        assert r.state == RequestState.DONE, (
            f"request {r.id} spuriously shed on a wall-clock jump: "
            f"{r.state} ({r.error})")
    assert eng.metrics()["shed"] == 0
    eng.close()


# ---------------------------------------------------------------------------
# telemetry exposition (satellite)
# ---------------------------------------------------------------------------

def test_controller_actions_counter_and_gauge_exposition():
    ctl = _ctl()
    ctl.tick(_sig(0.0, parked=(2,), **OVER))                 # scale_up
    for t in (6.0, 7.5):
        ctl.tick(_sig(t, parked=(), **OVER))                 # brownout
    text = _tmetrics.registry().prometheus_text()
    assert "serving_controller_actions_total" in text
    assert 'action="scale_up"' in text
    assert 'action="brownout"' in text
    assert "serving_brownout_level" in text
    lvl = _tmetrics.registry().get("serving_brownout_level")
    assert lvl.value(**ctl._label) == ctl.brownout_level > 0
    ctl.close()
    # close() drops the controller's children from the exposition
    text = _tmetrics.registry().prometheus_text()
    assert f'controller="{ctl._label["controller"]}"' not in text


# ---------------------------------------------------------------------------
# end-to-end closed loop on a real dp=2 cluster
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cluster_model():
    pt.seed(0)
    cfg = gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0)
    m = GPTStackedForPretraining(cfg)
    m.eval()
    return m, cfg


def test_closed_loop_scale_up_then_down(cluster_model):
    m, cfg = cluster_model
    rng = np.random.RandomState(2)
    eng = ShardedServingEngine(m, dp=2, mp=1, num_slots=2, page_size=16,
                               max_context=64, cache_dtype="float32",
                               max_queue_depth=64)
    t = [0.0]
    ctl = ElasticServingController(eng, _cfg(
        targets=SLOTargets(ttft_p99_s=0.2, queue_high=2.0, queue_low=0.5),
        cooldown_s=3.0, drain_deadline_s=0.0), clock=lambda: t[0])
    eng.drain_replica(1)                        # start scaled-down
    assert eng.replica_states() == ["active", "parked"]
    prompts = [rng.randint(0, cfg.vocab_size, (int(rng.randint(3, 18)),))
               for _ in range(20)]
    reqs = [eng.submit(p, 4) for p in prompts]
    for _ in range(80):
        t[0] += 0.5
        ctl.tick()
        eng.step()
        if not eng.placement.pending():
            break
    assert any(isinstance(a, ScaleUp) for a in ctl.actions), (
        "spike did not scale up")
    assert all(r.state == RequestState.DONE for r in reqs)
    for _ in range(30):
        t[0] += 0.5
        ctl.tick()
        eng.step()
    assert any(isinstance(a, ScaleDown) for a in ctl.actions), (
        "idle did not scale down")
    assert eng.active_dp == 1
    ctl.close()
    eng.close()


def test_brownout_actuators_engage_and_restore_lifo(cluster_model):
    """Drive the ladder with injected signals against a REAL cluster and
    verify every rung's actuator fires and restores: max_new clamp,
    prefill budget shrink, shed refusal — then LIFO release returns the
    cluster to its original knobs."""
    m, cfg = cluster_model
    eng = ShardedServingEngine(m, dp=2, mp=1, num_slots=2, page_size=16,
                               max_context=64, cache_dtype="float32")
    ctl = ElasticServingController(eng, _cfg(brownout_max_new=2))
    orig_budget = [e.prefill_token_budget for e in eng.replicas]
    t = 0.0
    while ctl.brownout_level < len(BROWNOUT_RUNGS):
        ctl.tick(_sig(t, parked=(), **OVER))
        t += 0.5
    assert eng.max_new_cap == 2
    assert all(e.prefill_token_budget < b
               for e, b in zip(eng.replicas, orig_budget))
    assert eng.shedding
    with pytest.raises(Exception, match="browned out"):
        eng.submit(np.arange(5), 4)
    # rung 1's clamp applies to admissions made while engaged
    t += 10.0
    while ctl.brownout_level > 0:
        ctl.tick(_sig(t, parked=(), **UNDER))
        t += 0.5
    assert eng.max_new_cap is None
    assert not eng.shedding
    assert [e.prefill_token_budget for e in eng.replicas] == orig_budget
    r = eng.submit(np.arange(5), 4)
    eng.run_until_idle()
    assert r.state == RequestState.DONE
    ctl.close()
    eng.close()
