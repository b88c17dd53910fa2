"""Disaggregated serving (serving/disagg.py): prefill/decode replica
roles with page-granular KV hand-off.

- greedy BITWISE parity: a disaggregated cluster — every decode token
  produced on a replica the request was NOT admitted to — emits exactly
  the colocated cluster's ids (fp32 + bf16 pools);
- trace discipline: hand-offs are eager pool writes, so each role still
  compiles one fused program with <= 2 python-body runs;
- ownership protocol: both pools' free+used+spec+shared == capacity at
  EVERY cluster-step boundary under randomized mid-transfer fault
  schedules (transfer_error / transfer_partial riding on the general
  fault storm), every request reaching a typed terminal;
- int8 pages transfer with their fp32 scale sidecars;
- role-aware placement ranks decode replicas last (fallback, not shed);
- transfer telemetry reaches Prometheus exposition, SLO histograms carry
  the ``role`` label;
- FaultPlan validation: transfer kinds only at the ``page_transfer``
  point.
"""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import serving
from paddle_tpu.models import GPTStackedForPretraining, gpt_tiny
from paddle_tpu.serving import (
    ROLE_COLOCATED,
    ROLE_DECODE,
    ROLE_PREFILL,
    DisaggServingEngine,
    FaultPlan,
    RolePlacement,
    ShardedServingEngine,
    random_schedule,
    random_transfer_schedule,
)


def _tiny_cfg():
    return gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0)


def _fresh_model():
    pt.seed(0)
    m = GPTStackedForPretraining(_tiny_cfg())
    m.eval()
    return m


def _workload(cfg, n=4, seed=1):
    rng = np.random.RandomState(seed)
    lengths = [3, 17, 5, 26, 14, 4][:n]
    prompts = [rng.randint(0, cfg.vocab_size, (s,)) for s in lengths]
    new_toks = [int(rng.randint(2, 7)) for _ in prompts]
    return prompts, new_toks


def _assert_pool_invariants(cluster):
    """The acceptance invariant: the 4-term accounting identity holds on
    BOTH pools — including while transfers are in flight, because the
    destination's reservation sits in its spec ledger."""
    for i, rep in enumerate(cluster.replicas):
        a = rep.allocator
        assert (a.free_pages + a.used_pages + a.spec_pages
                + a.shared_pages) == a.capacity, (
            f"replica {i}: free={a.free_pages} used={a.used_pages} "
            f"spec={a.spec_pages} shared={a.shared_pages} "
            f"cap={a.capacity}")


def _run_parity(cache_dtype):
    model = _fresh_model()
    cfg = _tiny_cfg()
    prompts, new_toks = _workload(cfg)
    kw = dict(num_slots=2, page_size=16, max_context=64,
              cache_dtype=cache_dtype)

    col = ShardedServingEngine(model, dp=2, mp=1, **kw)
    col_reqs = [col.submit(p, n) for p, n in zip(prompts, new_toks)]
    col.run_until_idle(max_steps=2000)
    col_out = [r.output_ids() for r in col_reqs]
    col.close()

    serving.reset_serve_trace_counts()
    dis = DisaggServingEngine(model, roles=(ROLE_PREFILL, ROLE_DECODE),
                              mp=1, **kw)
    reqs = [dis.submit(p, n) for p, n in zip(prompts, new_toks)]
    dis.run_until_idle(max_steps=2000)
    tc = serving.serve_trace_counts()
    # one fused program per ROLE (prefill geometry + budget-1 decode
    # geometry), each retrace-free: hand-off writes are eager pool ops
    assert tc["fused"] <= 2 * 2, tc
    m = dis.metrics()
    # most requests hand off; one may finish decoding on the prefill
    # replica while waiting out decode-slot backpressure (the designed
    # colocated fallback — progress beats placement purity)
    assert m["transfers_total"] >= len(prompts) // 2, m
    assert m["transferred_in"] == m["transferred_out"] == \
        m["transfers_total"]
    assert m["transfer_pages"] > 0 and m["transfer_bytes"] > 0
    for r, want in zip(reqs, col_out):
        assert r.finished, r.state
        got = r.output_ids()
        assert np.array_equal(got, want), (
            f"request {r.id}: disagg {got[len(r.prompt):]} != "
            f"colocated {want[len(r.prompt):]}")
    _assert_pool_invariants(dis)
    for i, rep in enumerate(dis.replicas):
        assert rep.allocator.used_pages == 0, f"replica {i} leaked"
    dis.close()


# ---------------------------------------------------------------------------
# parity: disagg greedy == colocated greedy, bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cache_dtype", ["float32", "bfloat16"])
def test_disagg_greedy_parity(cache_dtype):
    _run_parity(cache_dtype)


def test_disagg_int8_pages_transfer_with_scales():
    """Int8 pool: the hand-off must move the fp32 absmax scale sidecars
    along with the quantized pages, or the destination dequantizes
    garbage — parity against the colocated int8 cluster catches it."""
    _run_parity("int8")


# ---------------------------------------------------------------------------
# ownership under mid-transfer faults
# ---------------------------------------------------------------------------

def _run_fault_storm(seed, include_general=True):
    cfg = _tiny_cfg()
    dis = DisaggServingEngine(_fresh_model(),
                              roles=(ROLE_PREFILL, ROLE_DECODE),
                              mp=1, num_slots=2, page_size=16,
                              max_context=64, cache_dtype="float32")
    # transfer faults ride the CLUSTER's injector (the page_transfer
    # point fires on the hand-off path, like cluster_step)
    random_transfer_schedule(np.random.RandomState(100 + seed),
                             horizon=10, n_faults=3).install(dis)
    if include_general:
        for i, rep in enumerate(dis.replicas):
            random_schedule(np.random.RandomState(30 + 10 * seed + i),
                            horizon=16, num_slots=2).install(rep)
    rng = np.random.RandomState(seed)
    reqs = [dis.submit(
        rng.randint(0, cfg.vocab_size, (int(rng.randint(3, 20)),)),
        int(rng.randint(2, 6))) for _ in range(8)]
    steps = 0
    while dis.placement.pending() and steps < 4000:
        met = dis.step()
        steps += 1
        # the acceptance check: exact on BOTH pools at EVERY boundary,
        # transfers in flight or rolled back included
        _assert_pool_invariants(dis)
        if not met["active_slots"] and not met["tokens_this_step"] \
                and not dis.placement.pending():
            break
    assert all(r.terminal for r in reqs), [r.state for r in reqs]
    for r in reqs:
        if not r.finished:
            assert r.error is not None  # typed terminal, not a limbo
    for i, rep in enumerate(dis.replicas):
        assert rep.allocator.used_pages == 0, f"replica {i} leaked"
        assert rep.allocator.spec_pages == 0, f"replica {i} spec leaked"
    dis.close()


def test_disagg_page_accounting_exact_under_transfer_faults():
    _run_fault_storm(0)


@pytest.mark.slow
def test_disagg_transfer_faults_more_seeds():
    for seed in (1, 2, 3):
        _run_fault_storm(seed)


def test_transfer_error_rolls_back_source_retains():
    """A transfer that faults mid-copy must leave the destination's
    reservation rolled back and the source still owning the request —
    which then completes (re-routed or decoded in place) with bitwise
    the same ids as a fault-free run."""
    model = _fresh_model()
    cfg = _tiny_cfg()
    prompts, new_toks = _workload(cfg, n=2, seed=3)

    clean = DisaggServingEngine(model, roles=(ROLE_PREFILL, ROLE_DECODE),
                                mp=1, num_slots=2, page_size=16,
                                max_context=64, cache_dtype="float32")
    want = [o.tolist() for o in clean.generate_batch(prompts, new_toks[0])]
    clean.close()

    dis = DisaggServingEngine(model, roles=(ROLE_PREFILL, ROLE_DECODE),
                              mp=1, num_slots=2, page_size=16,
                              max_context=64, cache_dtype="float32")
    from paddle_tpu.serving import FaultInjector
    FaultInjector([
        FaultPlan(kind="transfer_error", point="page_transfer", at=0),
        FaultPlan(kind="transfer_partial", point="page_transfer", at=1),
    ]).install(dis)
    got = [o.tolist()
           for o in dis.generate_batch(prompts, new_toks[0])]
    assert got == want
    m = dis.metrics()
    assert m["transfers_failed"] == 2, m
    _assert_pool_invariants(dis)
    dis.close()


# ---------------------------------------------------------------------------
# placement + construction
# ---------------------------------------------------------------------------

def test_role_placement_ranks_decode_last():
    class _Fake:
        def __init__(self, role):
            self.role = role
            self.queue = type("Q", (), {"depth": 0})()
            self.scheduler = type("S", (), {"active_slots": 0})()
            self.allocator = type(
                "A", (), {"used_pages": 0, "capacity": 8})()
            self.prefix_cache = None

    engines = [_Fake(ROLE_DECODE), _Fake(ROLE_PREFILL),
               _Fake(ROLE_COLOCATED)]
    order = RolePlacement().rank_for(engines, np.arange(5))
    # prefill + colocated first (any relative order), decode LAST
    assert order[-1] == 0, order
    assert set(order[:2]) == {1, 2}, order


def test_all_decode_roles_rejected():
    with pytest.raises(ValueError, match="admit"):
        DisaggServingEngine(_fresh_model(),
                            roles=(ROLE_DECODE, ROLE_DECODE), mp=1,
                            num_slots=2, page_size=16, max_context=64)
    with pytest.raises(ValueError, match="unknown replica role"):
        DisaggServingEngine(_fresh_model(),
                            roles=("prefil",), mp=1, num_slots=2,
                            page_size=16, max_context=64)


def test_transfer_fault_kinds_validate_point():
    FaultPlan(kind="transfer_error", point="page_transfer", at=0)  # fine
    for kind in ("transfer_error", "transfer_partial", "transfer_stall"):
        with pytest.raises(ValueError):
            FaultPlan(kind=kind, point="before_decode", at=0)


# ---------------------------------------------------------------------------
# telemetry
# ---------------------------------------------------------------------------

def test_transfer_metrics_reach_prometheus():
    from paddle_tpu.telemetry import metrics as tmetrics

    model = _fresh_model()
    cfg = _tiny_cfg()
    prompts, new_toks = _workload(cfg, n=2, seed=5)
    dis = DisaggServingEngine(model, roles=(ROLE_PREFILL, ROLE_DECODE),
                              mp=1, num_slots=2, page_size=16,
                              max_context=64, cache_dtype="float32")
    dis.generate_batch(prompts, new_toks[0])
    text = tmetrics.registry().prometheus_text()
    assert "serving_transfer_pages" in text
    assert "serving_transfer_bytes" in text
    assert "serving_transfer_total" in text
    assert "serving_transfer_seconds" in text
    # per-role SLO histograms: the decode replica's ITL observations
    # carry its role label (docs/observability.md)
    assert 'role="decode"' in text
    assert 'role="prefill"' in text
    dis.close()
