"""Mesh-native sharded serving (ISSUE 14; docs/serving.md "Sharded
serving"): tensor-parallel fused step over ``mp``, mesh-sharded paged KV
pool, and ``dp`` replica scaling behind one placement scheduler.

Covers the acceptance criteria on the forced-8-device CPU mesh
(tests/conftest.py):

- sharded greedy serving bit-identical to the single-chip ServingEngine
  (fast tier; generate()-equality follows transitively from
  test_serving.py's engine parity) AND directly to single-chip
  ``generate()`` (slow mirror + the serving gate's sharded scenario),
  for (dp, mp) in {(1,2),(2,1),(2,2)}, with
  ``serve_trace_counts()["fused"] <= 2`` per replica (retrace-free SPMD
  step per replica);
- aggregate slot capacity and page-pool HBM scale linearly with dp;
  per-chip pool bytes shrink 1/mp (asserted on the REAL device shards);
- placement-layer properties: least-loaded routing, no replica exceeds
  its page capacity, typed shed only when ALL replicas backpressure;
- exact page accounting on every replica under randomized fault
  schedules;
- the satellites: sharded kernel-gate reasons (H % mp), local-head
  autotune shape keys, and graph_lint/cost_model recursing into
  shard_map jaxprs with shard-count scaling.
"""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import serving
from paddle_tpu.models import GPTStackedForPretraining, gpt_tiny
from paddle_tpu.serving import (
    LeastLoadedPlacement,
    Overloaded,
    PlacementScheduler,
    ServingEngine,
    ShardedServingEngine,
)

MESHES = [(1, 2), (2, 1), (2, 2)]


def _tiny_cfg():
    return gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0)


def _workload(cfg, n=4, seed=1):
    # DISTINCT prompt lengths: every distinct length compiles one
    # prefill program in the generate() oracle, so the list is as short
    # as it can be while still mixing page counts and mid-prefill overlap
    rng = np.random.RandomState(seed)
    lengths = [3, 17, 5, 26, 14, 4, 19, 7, 11, 6][:n]
    prompts = [rng.randint(0, cfg.vocab_size, (s,)) for s in lengths]
    new_toks = [int(rng.randint(2, 7)) for _ in prompts]
    return prompts, new_toks


def _generate_refs(model, prompts, new_toks):
    refs = []
    for p, n in zip(prompts, new_toks):
        out = model.generate(pt.to_tensor(p[None, :], dtype="int64"),
                             max_new_tokens=n, max_seq_len=64,
                             cache_dtype="float32")
        refs.append(np.asarray(out.numpy())[0])
    return refs


def _fresh_model():
    pt.seed(0)
    m = GPTStackedForPretraining(_tiny_cfg())
    m.eval()
    return m


# shared per-dtype fixtures, computed once and reused by every (dp, mp)
# parametrization — the parity matrix re-runs only the SHARDED side,
# keeping the fast tier-1 suite's wall clock down.  Sharing the MODEL
# across sequential engines is safe: each engine (re-)commits the
# parameters to its own mesh at construction, and the cached oracle
# outputs are plain numpy
_ORACLES: dict = {}


def _oracles(kv_dtype="float32"):
    if kv_dtype not in _ORACLES:
        cfg = _tiny_cfg()
        prompts, new_toks = _workload(cfg)
        ref_model = _fresh_model()
        # the fast tier's oracle is the single-chip ENGINE: its
        # generate()-parity is already pinned by
        # test_serving.py (churn + fused-mixed-step parity tests) and
        # re-proven directly against generate() every CI pass by the
        # serving gate's sharded scenario, so equality to generate()
        # follows transitively without paying this file a per-length
        # prefill compile.  The slow mirror below keeps the DIRECT
        # generate() comparison for every (dp, mp) config.
        chip = ServingEngine(ref_model, num_slots=2, page_size=16,
                             max_context=64, cache_dtype=kv_dtype)
        chip_reqs = [chip.submit(p, n)
                     for p, n in zip(prompts, new_toks)]
        chip.run_until_idle()
        chip_out = [r.output_ids() for r in chip_reqs]
        chip.close()
        _ORACLES[kv_dtype] = (ref_model, prompts, new_toks, chip_out)
    return _ORACLES[kv_dtype]


# ---------------------------------------------------------------------------
# parity: sharded greedy == single-chip generate() == single-chip engine
# ---------------------------------------------------------------------------

# the last case: an int8 pool's pages AND scale sidecars shard per head
# under mp = 2 and reproduce the single-chip int8 engine
@pytest.mark.parametrize("dp,mp,kv_dtype", [
    (dp, mp, "float32") for dp, mp in MESHES] + [(1, 2, "int8")])
def test_sharded_greedy_parity(dp, mp, kv_dtype):
    model, prompts, new_toks, chip_out = _oracles(kv_dtype)

    serving.reset_serve_trace_counts()
    eng = ShardedServingEngine(model, dp=dp, mp=mp,
                               num_slots=2, page_size=16, max_context=64,
                               cache_dtype=kv_dtype)
    reqs = [eng.submit(p, n) for p, n in zip(prompts, new_toks)]
    eng.run_until_idle(max_steps=2000)
    tc = serving.serve_trace_counts()
    # <= 2 python-body runs per compiled program (scout + jit trace), one
    # greedy program per replica: retrace-free SPMD step per replica
    assert tc["fused"] <= 2 * dp, tc
    for rep in eng.replicas:
        assert rep.compiled_programs == 1
    for r, chip_ids in zip(reqs, chip_out):
        assert r.finished, r.state
        got = r.output_ids()
        assert np.array_equal(got, chip_ids), (
            f"request {r.id} (replica {r.replica}) vs single-chip engine:"
            f" {got[len(r.prompt):]} != {chip_ids[len(r.prompt):]}")
    for i, rep in enumerate(eng.replicas):
        assert rep.allocator.used_pages == 0, f"replica {i} leaked"
        assert rep.scheduler.active_slots == 0
    eng.close()


@pytest.mark.slow
@pytest.mark.parametrize("dp,mp", MESHES)
def test_sharded_parity_vs_generate_direct(dp, mp):
    """The slow mirror: DIRECT single-shot generate() references for
    every (dp, mp) (the fast tier proves the same equality
    transitively through the single-chip engine; the serving gate's
    sharded scenario also runs a direct generate() comparison every CI
    pass)."""
    cfg = _tiny_cfg()
    prompts, new_toks = _workload(cfg)
    refs = _generate_refs(_fresh_model(), prompts, new_toks)
    eng = ShardedServingEngine(_fresh_model(),
                               dp=dp, mp=mp, num_slots=2, page_size=16,
                               max_context=64, cache_dtype="float32")
    reqs = [eng.submit(p, n) for p, n in zip(prompts, new_toks)]
    eng.run_until_idle(max_steps=2000)
    for r, ref in zip(reqs, refs):
        assert r.finished and np.array_equal(r.output_ids(), ref)
    eng.close()


def test_sharded_pool_bytes_shrink_per_chip():
    """The head-sharded pool really is 1/mp per chip: asserted on the
    actual device shard sizes, not just the metrics arithmetic."""
    eng = ShardedServingEngine(_fresh_model(), dp=1, mp=2,
                               num_slots=2, page_size=16, max_context=64,
                               cache_dtype="float32")
    rep = eng.replicas[0]
    pool = rep.cache.k._value
    shard_bytes = [s.data.nbytes for s in pool.addressable_shards]
    assert len(shard_bytes) == 2
    assert all(b == pool.nbytes // 2 for b in shard_bytes), shard_bytes
    mets = eng.metrics()
    assert mets["mp"] == 2
    assert mets["cache_bytes_per_chip"] * 2 == mets["cache_bytes"]
    eng.close()


def test_dp_scaling_is_linear():
    """Aggregate slot capacity and pool HBM scale linearly with dp (each
    replica owns a full pool on its own devices)."""
    base = None
    for dp in (1, 2):
        eng = ShardedServingEngine(_fresh_model(),
                                   dp=dp, mp=1, num_slots=3, page_size=16,
                                   max_context=64, cache_dtype="float32")
        mets = eng.metrics()
        if base is None:
            base = mets
        else:
            assert mets["slot_capacity"] == 2 * base["slot_capacity"]
            assert mets["pages_capacity"] == 2 * base["pages_capacity"]
            assert mets["cache_bytes"] == 2 * base["cache_bytes"]
            # dp alone does not shrink per-chip pool bytes
            assert (mets["cache_bytes_per_chip"]
                    == base["cache_bytes_per_chip"])
            # replica pools live on DISJOINT devices
            devs = [set(d.id for d in rep.cache.k._value.devices())
                    for rep in eng.replicas]
            assert devs[0].isdisjoint(devs[1]), devs
        eng.close()


# ---------------------------------------------------------------------------
# placement layer
# ---------------------------------------------------------------------------

def _drain_without_dispatch(eng, reqs):
    """Cancel every request and step once: the reap path retires them
    all BEFORE any device dispatch, so placement-layer tests (pure host
    bookkeeping) never pay a fused-step compile."""
    for r in reqs:
        r.cancel()
    eng.step()
    for rep in eng.replicas:
        assert rep.allocator.used_pages == 0


def test_placement_least_loaded_routing():
    """A queued request loads a replica; the next submit must prefer the
    idle one (queue depth is the primary signal).  Placement is pure host
    bookkeeping — the test never dispatches a fused step."""
    eng = ShardedServingEngine(_fresh_model(), dp=2, mp=1,
                               num_slots=1, page_size=16, max_context=64,
                               cache_dtype="float32")
    cfg = _tiny_cfg()
    rng = np.random.RandomState(3)
    r0 = eng.submit(rng.randint(0, cfg.vocab_size, (5,)), 4)
    r1 = eng.submit(rng.randint(0, cfg.vocab_size, (5,)), 4)
    assert {r0.replica, r1.replica} == {0, 1}, (r0.replica, r1.replica)
    assert eng.placement.routed == [1, 1]
    _drain_without_dispatch(eng, [r0, r1])
    eng.close()


def test_placement_sheds_only_when_all_replicas_backpressure():
    import time

    eng = ShardedServingEngine(_fresh_model(), dp=2, mp=1,
                               num_slots=1, page_size=16, max_context=64,
                               cache_dtype="float32", max_queue_depth=1)
    cfg = _tiny_cfg()
    rng = np.random.RandomState(4)
    mk = lambda: rng.randint(0, cfg.vocab_size, (5,))  # noqa: E731
    # one queued request per replica fills both bounded queues
    a, b = eng.submit(mk(), 4), eng.submit(mk(), 4)
    assert {a.replica, b.replica} == {0, 1}
    with pytest.raises(Overloaded):
        eng.submit(mk(), 4)
    # ONE cluster shed, counted once: placement skips full replicas via
    # the queue-room check instead of probing their submit, so no
    # replica's own shed counter was bumped for this request
    mets = eng.metrics()
    assert mets["placement_shed"] == 1
    assert mets["shed"] == 1, mets["shed"]
    # one replica seats its queued request (admission is host
    # bookkeeping; no dispatch) -> the cluster accepts again: only when
    # ALL replicas backpressure does placement shed
    rep0 = eng.replicas[0]
    with rep0._lock:
        rep0._admit(time.monotonic())
    assert rep0.queue.depth == 0
    c = eng.submit(mk(), 4)
    assert c.replica == 0
    _drain_without_dispatch(eng, [a, b, c])
    assert all(r.terminal for r in (a, b, c))
    eng.close()


def test_placement_first_replica_validation_error_propagates():
    """Oversized requests are a validation error, not backpressure — they
    must raise once, not be retried across the fleet."""
    eng = ShardedServingEngine(_fresh_model(), dp=2, mp=1,
                               num_slots=1, page_size=16, max_context=64,
                               cache_dtype="float32")
    with pytest.raises(ValueError):
        eng.submit(np.arange(60) % 100, 32)     # 92 tokens > max_context
    assert eng.placement.routed == [0, 0]
    eng.close()


def test_placement_capacity_never_exceeded_under_churn():
    """Random arrival churn across tight replicas: no replica's pool ever
    exceeds its capacity, and everything drains to zero pages."""
    eng = ShardedServingEngine(_fresh_model(), dp=2, mp=1,
                               num_slots=2, page_size=16, max_context=64,
                               num_pages=5, cache_dtype="float32")
    cfg = _tiny_cfg()
    rng = np.random.RandomState(5)
    reqs, to_submit = [], 14
    while to_submit or any(
            e.queue.depth + e.scheduler.active_slots for e in eng.replicas):
        for _ in range(min(2, to_submit)):
            reqs.append(eng.submit(
                rng.randint(0, cfg.vocab_size, (int(rng.randint(3, 25)),)),
                int(rng.randint(2, 6))))
            to_submit -= 1
        eng.step()
        for i, rep in enumerate(eng.replicas):
            assert rep.allocator.used_pages <= rep.allocator.capacity, i
    assert all(r.finished for r in reqs)
    for rep in eng.replicas:
        assert rep.allocator.used_pages == 0
    eng.close()


def test_placement_scheduler_standalone_over_plain_engines():
    """The placement layer is policy + forwarding only — it composes over
    plain single-chip engines too (no mesh required; routing asserted
    without ever dispatching a step)."""
    m = _fresh_model()
    engines = [ServingEngine(m, num_slots=1, page_size=16, max_context=64,
                             cache_dtype="float32") for _ in range(2)]
    sched = PlacementScheduler(engines, policy=LeastLoadedPlacement())
    cfg = _tiny_cfg()
    rng = np.random.RandomState(6)
    reqs = [sched.submit(rng.randint(0, cfg.vocab_size, (5,)), 3)
            for _ in range(4)]
    assert sched.routed == [2, 2]       # alternating least-loaded
    assert sched.pending() == 4
    for r in reqs:
        r.cancel()
    for e in engines:
        e.step()                        # reap-only: no dispatch
        assert e.allocator.used_pages == 0
        e.close()
    assert all(r.terminal for r in reqs)


# ---------------------------------------------------------------------------
# scheduler split: admission per replica, placement per cluster
# ---------------------------------------------------------------------------

def test_scheduler_module_split():
    from paddle_tpu.serving import admission, placement

    assert serving.AdmissionScheduler is admission.AdmissionScheduler
    assert serving.PlacementScheduler is placement.PlacementScheduler
    # the engine's scheduler attribute is the ADMISSION layer
    eng = ServingEngine(_fresh_model(), num_slots=1,
                        page_size=16, max_context=32, cache_dtype="float32")
    assert isinstance(eng.scheduler, admission.AdmissionScheduler)
    eng.close()


# ---------------------------------------------------------------------------
# sharded kernel gates + autotune local-head keys (satellites)
# ---------------------------------------------------------------------------

def test_mesh_shard_gate_reasons():
    from paddle_tpu.analysis.codes import (
        mesh_shard_gate_reason,
        paged_gate_reason,
        ragged_gate_reason,
    )

    assert mesh_shard_gate_reason(8, 2) is None
    r = mesh_shard_gate_reason(6, 4)
    assert r is not None and r.code == "GL002" and "num_heads=6" in r.detail
    # the kernel gates learn the same preconditions
    assert ragged_gate_reason(128, 64, num_heads=8, mp=2) is None
    assert paged_gate_reason(128, 64, num_heads=8, mp=2) is None
    r = ragged_gate_reason(128, 64, num_heads=6, mp=4)
    assert r is not None and "mp=4" in r.detail
    r = paged_gate_reason(200, 64, num_heads=6, mp=4)
    assert r is not None
    assert "page_size=200" in r.detail and "num_heads=6" in r.detail
    # unsharded calls unchanged (back-compat)
    assert paged_gate_reason(128, 64) is None


def test_engine_rejects_indivisible_head_shard():
    m = _fresh_model()   # gpt_tiny: 4 heads
    with pytest.raises(ValueError, match="num_heads=4.*mp=3"):
        ShardedServingEngine(m, dp=1, mp=3, num_slots=1, page_size=16,
                             max_context=32, cache_dtype="float32")


def test_autotune_local_head_shape_keys():
    """Sharded lookups key on the LOCAL (post-shard) head count; the
    unsharded key stays the historical one, so committed entries stay
    valid and a sharded engine never consumes an unsharded winner."""
    from paddle_tpu.analysis import autotune
    from paddle_tpu.ops.pallas_kernels.ragged_paged_attention import (
        ragged_token_block,
    )

    autotune.reset()
    try:
        autotune.set_entry(
            "ragged_paged_attention",
            {"page_size": 128, "head_dim": 64}, "bfloat16",
            {"token_block": 32}, source="measured")
        autotune.set_entry(
            "ragged_paged_attention",
            {"page_size": 128, "head_dim": 64, "num_heads": 2}, "bfloat16",
            {"token_block": 16}, source="measured")
        assert ragged_token_block(128, 64, "bfloat16") == 32
        assert ragged_token_block(128, 64, "bfloat16", local_heads=2) == 16
        # a sharded lookup with no sharded entry falls back to the
        # default, NOT to the unsharded winner
        assert ragged_token_block(128, 64, "bfloat16", local_heads=4) == 8
    finally:
        autotune.reset()


# ---------------------------------------------------------------------------
# lint/cost over shard_map jaxprs (satellite fix)
# ---------------------------------------------------------------------------

def test_cost_model_scales_shard_map_by_shard_count():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from paddle_tpu.analysis.cost_model import cost, cost_jaxpr

    devs = jax.devices()
    mesh = Mesh(np.array(devs[:2]), ("mp",))

    def body(x, w):
        return x @ w

    f = jax.shard_map(body, mesh=mesh,
                      in_specs=(P("mp", None), P(None, None)),
                      out_specs=P("mp", None), check_vma=False)
    x = jnp.ones((8, 16), jnp.float32)
    w = jnp.ones((16, 4), jnp.float32)
    closed = jax.make_jaxpr(f)(x, w)
    rep = cost_jaxpr(closed, program="sharded_dot")
    # per-shard dot: 2 * 4 * 16 * 4 = 512 flops; x2 shards = global 1024
    # (== the unsharded program's flops, which is the point)
    unsharded = cost(body, x, w)
    assert rep.flops == unsharded.flops == 1024, (
        rep.flops, unsharded.flops)


def test_graph_lint_walks_shard_map_without_crashing():
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    import jax
    from paddle_tpu import analysis

    mesh = Mesh(np.array(jax.devices()[:2]), ("mp",))

    def body(x, w):
        return x @ w.astype(jnp.float32)    # GL001 bait INSIDE the body

    f = jax.shard_map(body, mesh=mesh,
                      in_specs=(P("mp", None), P(None, None)),
                      out_specs=P("mp", None), check_vma=False)
    rep = analysis.lint(lambda x, w: f(x, w),
                        jnp.ones((8, 16), jnp.float32),
                        jnp.ones((16, 4), jnp.bfloat16))
    # the walker recursed INTO the shard_map body: the explicit upcast
    # feeding the dot is visible there
    assert any(f_.code == "GL001" for f_ in rep.findings), rep.render()


@pytest.mark.slow
def test_sharded_fused_step_lints_clean():
    """The sharded engine's compiled SPMD step stays GL001-clean for a
    pure-bf16 model (the walkers recurse through the shard_map'd
    attention; the serving lint CLI keeps it as a default target, so the
    fast tier runs this via the graph-lint gate — slow-marked here)."""
    from paddle_tpu import analysis

    analysis.clear_reports()
    pt.set_flags({"FLAGS_graph_lint": True})
    try:
        pt.seed(0)
        cfg = _tiny_cfg()
        m = GPTStackedForPretraining(cfg)
        pt.amp.decorate(m, level="O2", dtype="bfloat16")
        m.eval()
        eng = ShardedServingEngine(m, dp=1, mp=2, num_slots=2,
                                   page_size=16, max_context=32,
                                   cache_dtype="bfloat16")
        rng = np.random.RandomState(1)
        eng.submit(rng.randint(0, cfg.vocab_size, (5,)), 3)
        eng.run_until_idle()
        reps = eng.lint_reports()
        assert reps, "FLAGS_graph_lint on but no sharded lint reports"
        bad = [f for r in reps for f in r.findings if f.code == "GL001"]
        assert bad == [], "\n".join(f.render() for f in bad)
        eng.close()
    finally:
        pt.set_flags({"FLAGS_graph_lint": False})
        analysis.clear_reports()


# ---------------------------------------------------------------------------
# fault containment + sampling on sharded replicas
# ---------------------------------------------------------------------------

def test_sharded_page_accounting_exact_under_random_faults():
    """The acceptance invariant: page accounting stays exact (drain ->
    zero pages) on EVERY replica under randomized fault schedules, every
    request reaching a typed terminal state."""
    from paddle_tpu.serving import random_schedule

    cfg = _tiny_cfg()
    for seed in (0,):   # more seeds ride in the slow variant below
        eng = ShardedServingEngine(_fresh_model(),
                                   dp=2, mp=1, num_slots=2, page_size=16,
                                   max_context=64, cache_dtype="float32")
        for i, rep in enumerate(eng.replicas):
            random_schedule(np.random.RandomState(30 + 10 * seed + i),
                            horizon=16, num_slots=2).install(rep)
        rng = np.random.RandomState(seed)
        reqs = [eng.submit(
            rng.randint(0, cfg.vocab_size, (int(rng.randint(3, 20)),)),
            int(rng.randint(2, 6))) for _ in range(10)]
        eng.run_until_idle(max_steps=4000)
        assert all(r.terminal for r in reqs), [r.state for r in reqs]
        for r in reqs:
            if not r.finished:
                assert r.error is not None
        for i, rep in enumerate(eng.replicas):
            assert rep.allocator.used_pages == 0, f"replica {i} leaked"
            assert rep.allocator.free_pages == rep.allocator.capacity
        eng.close()


@pytest.mark.slow
def test_sharded_faults_more_seeds():
    """Extra randomized fault seeds for the per-replica accounting
    invariant (the fast tier runs seed 0 above; the fault GATE runs its
    own schedules every CI pass)."""
    from paddle_tpu.serving import random_schedule

    cfg = _tiny_cfg()
    for seed in (1, 2):
        eng = ShardedServingEngine(_fresh_model(),
                                   dp=2, mp=1, num_slots=2, page_size=16,
                                   max_context=64, cache_dtype="float32")
        for i, rep in enumerate(eng.replicas):
            random_schedule(np.random.RandomState(30 + 10 * seed + i),
                            horizon=16, num_slots=2).install(rep)
        rng = np.random.RandomState(seed)
        reqs = [eng.submit(
            rng.randint(0, cfg.vocab_size, (int(rng.randint(3, 20)),)),
            int(rng.randint(2, 6))) for _ in range(10)]
        eng.run_until_idle(max_steps=4000)
        assert all(r.terminal for r in reqs), [r.state for r in reqs]
        for i, rep in enumerate(eng.replicas):
            assert rep.allocator.used_pages == 0, f"replica {i} leaked"
        eng.close()


@pytest.mark.slow
def test_sharded_sampling_requests_complete():
    """Per-request sampling on a sharded cluster: each replica owns a
    private RNG stream (the donated key state commits to the replica's
    mesh), so mixed sampling traffic runs retrace-free and terminates.
    Slow-marked: the sampling variant compiles on every replica."""
    from paddle_tpu.serving import SamplingParams

    cfg = _tiny_cfg()
    eng = ShardedServingEngine(_fresh_model(),
                               dp=2, mp=2, num_slots=2, page_size=16,
                               max_context=64, cache_dtype="float32")
    rng = np.random.RandomState(7)
    sp = SamplingParams(do_sample=True, temperature=0.8, top_k=8)
    reqs = [eng.submit(rng.randint(0, cfg.vocab_size, (6,)), 4, sampling=sp)
            for _ in range(4)]
    # greedy and sampling traffic mix across the same replicas
    reqs += [eng.submit(rng.randint(0, cfg.vocab_size, (6,)), 4)
             for _ in range(2)]
    eng.run_until_idle(max_steps=2000)
    assert all(r.finished for r in reqs), [r.state for r in reqs]
    assert all(len(r.tokens) == 4 for r in reqs)
    for rep in eng.replicas:
        assert rep.allocator.used_pages == 0
    eng.close()
