#!/usr/bin/env python
"""Disaggregated-serving CI gate: prefill/decode roles + page hand-off
under deterministic faults.

Three scenarios (the randomized sweeps live in tests/test_disagg.py;
here the schedules are pinned so a failure reproduces exactly):

  1. parity — a roles=(prefill, decode) cluster must emit BITWISE the
     ids of a colocated dp=2 cluster AND the single-shot greedy oracle,
     with every hand-off's pages/bytes accounted and zero pages leaked;
  2. mid-transfer kill, both directions — with a pinned fault mid-copy:
     * destination dies: the injected ``transfer_error`` aborts the
       copy, the destination's spec reservation rolls back, THEN the
       decode replica is killed — the source must still own the request
       and finish it in place (degraded colocated fallback), bitwise;
     * source dies: an injected ``transfer_partial`` aborts, THEN the
       prefill replica is killed — its seated work checkpoints,
       re-homes through RolePlacement's decode-last fallback onto the
       surviving decode replica, and still matches the oracle.
     After each direction BOTH pools' ledgers are audited EXACTLY:
     used == spec == 0 and free + shared == capacity;
  3. independent role scaling — roles=(prefill, prefill, decode) with
     one prefill parked: a long-prompt spike must make the PREFILL
     pool's controller emit ScaleUp (activating the parked prefill
     replica) while the decode pool's controller emits nothing — TTFT
     pressure scales prefill, never decode.

Wired into run_tests.sh (PADDLE_TPU_SKIP_DISAGG_GATE=1 skips).
Exit codes: 0 ok, 1 failure.  See docs/serving.md "Disaggregated
prefill/decode".
"""
from __future__ import annotations

import os
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

import numpy as np  # noqa: E402

PROMPT_LENS = (6, 14, 9, 20, 11, 17)
MAX_NEW = 8


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


def _build():
    import paddle_tpu as pt
    from paddle_tpu.models import GPTStackedForPretraining, gpt_tiny

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
    return m, prompts, refs


def _disagg(model, roles=("prefill", "decode"), **over):
    from paddle_tpu.serving import DisaggServingEngine

    kw = dict(num_slots=2, page_size=16, max_context=64,
              cache_dtype="float32")
    kw.update(over)
    return DisaggServingEngine(model, roles=roles, mp=1, **kw)


def _bitwise(req, ref):
    out = np.asarray(req.output_ids())
    return np.array_equal(out, ref[:out.size])


def _audit_exact(cluster, where):
    """The acceptance audit: after settling, BOTH pools hold zero
    allocated and zero in-flight (spec) pages — free + shared is the
    whole pool, to the page."""
    for i, rep in enumerate(cluster.replicas):
        a = rep.allocator
        assert a.used_pages == 0, \
            f"{where}: replica {i} leaked {a.used_pages} page(s)"
        assert a.spec_pages == 0, \
            f"{where}: replica {i} left {a.spec_pages} page(s) reserved"
        assert a.free_pages + a.shared_pages == a.capacity, \
            (f"{where}: replica {i} ledger off by "
             f"{a.capacity - a.free_pages - a.shared_pages} page(s)")


def parity(model, prompts, refs) -> bool:
    """Disagg greedy == colocated greedy == single-shot oracle, with
    hand-off accounting consistent."""
    from paddle_tpu.serving import RequestState, ShardedServingEngine

    col = ShardedServingEngine(model, dp=2, mp=1, num_slots=2,
                               page_size=16, max_context=64,
                               cache_dtype="float32")
    col_reqs = [col.submit(p, MAX_NEW) for p in prompts]
    col.run_until_idle(max_steps=1000)
    col_out = [np.asarray(r.output_ids()) for r in col_reqs]
    col.close()

    dis = _disagg(model)
    reqs = [dis.submit(p, MAX_NEW) for p in prompts]
    dis.run_until_idle(max_steps=1000)
    m = dis.metrics()
    for r, c_out, ref in zip(reqs, col_out, refs):
        assert r.state == RequestState.DONE, f"{r.id} -> {r.state}"
        out = np.asarray(r.output_ids())
        assert np.array_equal(out, c_out), \
            f"request {r.id}: disagg != colocated"
        assert _bitwise(r, ref), f"request {r.id}: disagg != oracle"
    assert m["transfers_total"] >= 1, "no hand-off happened"
    assert m["transferred_in"] == m["transferred_out"] == \
        m["transfers_total"], m
    assert m["transfer_bytes"] > 0 and m["transfer_pages"] > 0
    _audit_exact(dis, "parity")
    dis.close()
    print(f"disagg_gate: parity OK ({len(reqs)} requests bitwise, "
          f"{m['transfers_total']} hand-offs, "
          f"{m['transfer_pages']} pages / {m['transfer_bytes']} bytes)")
    return True


def kill_destination_mid_transfer(model, prompts, refs) -> bool:
    """Direction 1: the copy faults, the destination reservation rolls
    back, the destination replica dies — the source must retain
    ownership and finish the request itself."""
    from paddle_tpu.serving import FaultInjector, RequestState

    dis = _disagg(model)
    inj = FaultInjector()
    # every transfer attempt fails: the request can never leave source
    inj.inject("page_transfer", at=0, kind="transfer_error", times=99)
    inj.install(dis)
    reqs = [dis.submit(p, MAX_NEW) for p in prompts[:2]]
    for _ in range(3):
        dis.step()
    assert dis.metrics()["transfers_failed"] >= 1, \
        "the pinned transfer fault never fired"
    # mid-run audit: rollbacks already happened — no spec residue NOW
    for i, rep in enumerate(dis.replicas):
        assert rep.allocator.spec_pages == 0, \
            f"replica {i}: rolled-back reservation leaked"
    dis.kill_replica(1)                            # destination dies
    dis.run_until_idle(max_steps=1000)
    for r, ref in zip(reqs, refs):
        assert r.state == RequestState.DONE, \
            f"{r.id} -> {r.state}: source lost a request it still owned"
        assert _bitwise(r, ref), f"request {r.id} diverged"
    m = dis.metrics()
    assert m["transfers_total"] == 0, "a transfer committed to a corpse"
    _audit_exact(dis, "kill_destination")
    dis.close()
    print(f"disagg_gate: kill_destination_mid_transfer OK "
          f"({m['transfers_failed']} aborts rolled back, source kept "
          f"ownership, bitwise)")
    return True


def kill_source_mid_transfer(model, prompts, refs) -> bool:
    """Direction 2: a partial copy aborts, then the SOURCE dies — its
    checkpointed work re-homes through RolePlacement's decode-last
    fallback onto the surviving decode replica and completes bitwise."""
    from paddle_tpu.serving import FaultInjector, RequestState

    dis = _disagg(model)
    inj = FaultInjector()
    inj.inject("page_transfer", at=0, kind="transfer_partial", times=99)
    inj.install(dis)
    before = dis.metrics()["rehomed"]
    reqs = [dis.submit(p, MAX_NEW) for p in prompts[:2]]
    for _ in range(3):
        dis.step()
    assert dis.metrics()["transfers_failed"] >= 1, \
        "the pinned partial-transfer fault never fired"
    dis.kill_replica(0)                            # source (prefill) dies
    dis.run_until_idle(max_steps=1000)
    rehomed = dis.metrics()["rehomed"] - before
    assert rehomed >= 1, "the source kill re-homed nothing"
    for r, ref in zip(reqs, refs):
        assert r.state == RequestState.DONE, \
            f"{r.id} -> {r.state}: decode fallback must admit"
        assert _bitwise(r, ref), f"request {r.id} diverged across re-home"
    _audit_exact(dis, "kill_source")
    dis.close()
    print(f"disagg_gate: kill_source_mid_transfer OK ({rehomed} re-homed "
          f"onto the decode replica via role fallback, bitwise)")
    return True


def independent_role_scaling(model, prompts, refs) -> bool:
    """A long-prompt spike under roles=(prefill, prefill, decode) with
    one prefill parked: the prefill pool's controller must ScaleUp the
    parked PREFILL replica; the decode pool's controller must not act."""
    from paddle_tpu.serving import (
        DisaggElasticController, ElasticConfig, Overloaded, ScaleUp,
        SLOTargets,
    )

    dis = _disagg(model, roles=("prefill", "prefill", "decode"),
                  num_slots=2)
    clk = _Clock()
    dis.drain_replica(1, deadline_s=0.0)          # park one prefill
    assert dis.replica_states() == ["active", "parked", "active"]
    ctl = DisaggElasticController(
        dis,
        prefill_config=ElasticConfig(
            targets=SLOTargets(queue_high=2.0, queue_low=0.5),
            min_samples=10**9, cooldown_s=3.0, overload_sustain_s=30.0,
            underload_sustain_s=10**9, drain_deadline_s=0.0, min_dp=1),
        decode_config=ElasticConfig(
            signal="itl", brownout_enabled=False,
            targets=SLOTargets(queue_high=10**9, queue_low=-1.0),
            min_samples=10**9, underload_sustain_s=10**9, min_dp=1),
        clock=clk)
    assert ctl.prefill_pool.indices == [0, 1]
    assert ctl.decode_pool.indices == [2]
    reqs, shed = [], 0
    for tick in range(10):
        for _ in range(3):                        # long-prompt flood
            try:
                reqs.append(dis.submit(prompts[3], MAX_NEW))
            except Overloaded:
                shed += 1
        ctl.tick()
        dis.step()
        clk.t += 1.0
        if any(isinstance(a, ScaleUp) for a in ctl.prefill.actions):
            break
    ups = [a for a in ctl.prefill.actions if isinstance(a, ScaleUp)]
    assert ups, f"prefill pool never scaled: {ctl.prefill.actions}"
    woke = ctl.prefill_pool.indices[ups[0].replica]
    assert woke == 1, f"woke replica {woke}, wanted the parked prefill (1)"
    assert dis.replica_states()[1] == "active"
    assert not ctl.decode.actions, \
        f"decode pool acted on prefill pressure: {ctl.decode.actions}"
    for _ in range(600):
        if all(r.terminal for r in reqs) and dis.placement.pending() == 0:
            break
        ctl.tick()
        dis.step()
        clk.t += 1.0
    assert all(r.terminal for r in reqs), "spike never drained"
    done = [r for r in reqs if r.finished]
    assert done, "every spiked request shed"
    for r in done:
        assert _bitwise(r, refs[3]), f"request {r.id} diverged"
    _audit_exact(dis, "role_scaling")
    ctl.close()
    dis.close()
    print(f"disagg_gate: independent_role_scaling OK (prefill pool woke "
          f"replica 1, decode pool quiet, {len(done)} done, shed={shed})")
    return True


def gate() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    model, prompts, refs = _build()
    ok = True
    try:
        ok &= parity(model, prompts, refs)
        ok &= kill_destination_mid_transfer(model, prompts, refs)
        ok &= kill_source_mid_transfer(model, prompts, refs)
        ok &= independent_role_scaling(model, prompts, refs)
    except AssertionError as e:
        print(f"disagg_gate: FAIL {e}")
        ok = False
    if not ok:
        return 1
    print("disagg_gate: OK (parity, kill-dest, kill-source, role scaling)")
    return 0


if __name__ == "__main__":
    sys.exit(gate())
