"""Quantized serving (docs/serving.md "Quantized serving", ISSUE-17).

int8 KV pages with per-(page, head) fp32 absmax scale sidecars behind
the same BlockAllocator ledger, quantize-on-write in the fused step,
fused in-kernel dequant on the read side, and int8 weights on the
decode hot path:

- the write-side quantizer's "fresh-page step-absmax, stale-page clip"
  contract, its determinism (bitwise-identical pages AND scales for
  identical token sequences — what prefix-cache COW adoption relies
  on), and the zero-page sentinel;
- an int8-KV engine reproducing fp32 greedy generate() token-for-token
  on a tiny model, with the scale sidecars accounted, sharded, rebuilt
  and released exactly like the pages they describe;
- the randomized-fault-schedule accounting property from
  test_serving_faults.py re-run in the int8 regime: allocator
  invariants at every step boundary, drain to zero, typed terminal
  states, survivor parity;
- watchdog rebuilds re-create the pool AND its scales (the suspect
  pool's scale buffers are released with its pages);
- per-row activation scales make the int8 matmul batch-invariant.
"""
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models import GPTStackedForPretraining, gpt_tiny
from paddle_tpu.quantization.kv import (
    TINY_SCALE, dequant_pages, quantize_kv_write,
)
from paddle_tpu.serving import (
    FaultInjector, RequestState, ServingEngine, StepStalledError,
    random_schedule,
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
               for s in (5, 9, 7, 12, 17, 4, 11, 6)]
    refs = [np.asarray(
        m.generate(pt.to_tensor(p[None, :], dtype="int64"),
                   max_new_tokens=N_NEW, max_seq_len=64,
                   cache_dtype="float32").numpy())[0]
        for p in prompts]
    return m, cfg, prompts, refs


def _engine(m, **kw):
    kw.setdefault("num_slots", 3)
    kw.setdefault("page_size", 16)
    kw.setdefault("max_context", 64)
    kw.setdefault("kv_dtype", "int8")
    return ServingEngine(m, **kw)


def _scale_tensors(cache):
    return [cache.k_scale, cache.v_scale]


# ---------------------------------------------------------------------------
# write-side quantizer contract
# ---------------------------------------------------------------------------

def test_fresh_page_scale_is_step_absmax():
    import jax.numpy as jnp

    P, H, D, C = 4, 2, 8, 16
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(1, C, H, D).astype(np.float32))
    pid = jnp.full((1, C), 2, jnp.int32)
    offs = jnp.arange(C, dtype=jnp.int32)[None]
    q, s = quantize_kv_write(x, pid, offs, jnp.zeros((P, H), jnp.float32))
    want = np.abs(np.asarray(x))[0].max(axis=(0, 2)) / 127.0 + TINY_SCALE
    np.testing.assert_allclose(np.asarray(s)[2], want, rtol=1e-6)
    # untouched pages keep the zero sentinel
    assert float(np.abs(np.asarray(s)[[0, 1, 3]]).max()) == 0.0
    # round-trip error bounded by half a quantization step per head
    deq = np.asarray(q)[0].astype(np.float32) \
        * np.asarray(s)[2][None, :, None]
    step = np.asarray(s)[2].max()
    assert float(np.abs(deq - np.asarray(x)[0]).max()) <= step * 0.51


def test_stale_page_keeps_scale_and_clips():
    import jax.numpy as jnp

    P, H, D = 4, 2, 8
    # offset-0 write with SMALL values fixes the page scale...
    x0 = jnp.full((1, 1, H, D), 0.1, jnp.float32)
    q0, s0 = quantize_kv_write(
        x0, jnp.full((1, 1), 1, jnp.int32),
        jnp.zeros((1, 1), jnp.int32), jnp.zeros((P, H), jnp.float32))
    # ...then a LARGER decode token trickles into offset 3: the scale
    # must not move, and the payload clips to +127
    x1 = jnp.full((1, 1, H, D), 5.0, jnp.float32)
    q1, s1 = quantize_kv_write(
        x1, jnp.full((1, 1), 1, jnp.int32),
        jnp.full((1, 1), 3, jnp.int32), s0)
    assert np.array_equal(np.asarray(s0), np.asarray(s1))
    assert int(np.asarray(q1).min()) == 127  # fully clipped


def test_quantize_kv_write_is_deterministic():
    import jax.numpy as jnp

    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(2, 16, 2, 8).astype(np.float32))
    pid = jnp.asarray(rng.randint(1, 5, (2, 16)).astype(np.int32))
    offs = jnp.asarray(np.tile(np.arange(16, dtype=np.int32), (2, 1)))
    outs = [quantize_kv_write(x, pid, offs,
                              jnp.zeros((6, 2), jnp.float32))
            for _ in range(2)]
    assert np.array_equal(np.asarray(outs[0][0]), np.asarray(outs[1][0]))
    assert np.array_equal(np.asarray(outs[0][1]), np.asarray(outs[1][1]))


def test_dequant_zero_pages_are_zero():
    import jax.numpy as jnp

    pool = jnp.zeros((3, 2, 4, 8), jnp.int8)
    scale = jnp.zeros((3, 2), jnp.float32)
    assert float(np.abs(np.asarray(dequant_pages(pool, scale))).max()) == 0.0


def test_quantized_matmul_is_batch_invariant():
    """Per-row dynamic activation scales: a token's quantization grid
    never depends on its batch neighbors, so batched serving steps
    reproduce single-request results bitwise."""
    import jax.numpy as jnp

    from paddle_tpu.quantization.int8 import quantized_matmul_raw

    rng = np.random.RandomState(4)
    w = rng.randn(16, 8).astype(np.float32)
    ws = np.abs(w).max(axis=0) / 127.0 + 1e-12
    wq = jnp.asarray(np.clip(np.round(w / ws), -127, 127).astype(np.int8))
    ws = jnp.asarray(ws.astype(np.float32))
    x1 = rng.randn(1, 16).astype(np.float32)
    x2 = rng.randn(3, 16).astype(np.float32) * 50.0   # huge batch-mates
    solo = np.asarray(quantized_matmul_raw(jnp.asarray(x1), wq, ws))
    batched = np.asarray(quantized_matmul_raw(
        jnp.asarray(np.concatenate([x1, x2])), wq, ws))
    assert np.array_equal(solo[0], batched[0])


# ---------------------------------------------------------------------------
# engine-level: parity, accounting, rebuild, COW
# ---------------------------------------------------------------------------

def test_int8_engine_matches_fp32_generate(served):
    m, cfg, prompts, refs = served
    eng = _engine(m)
    try:
        assert eng.cache.quantized
        reqs = [eng.submit(p, N_NEW) for p in prompts]
        eng.run_until_idle(max_steps=2000)
        for r, ref in zip(reqs, refs):
            assert r.finished and np.array_equal(r.output_ids(), ref)
        assert eng.allocator.used_pages == 0
        for t in _scale_tensors(eng.cache):
            assert np.isfinite(np.asarray(t.numpy())).all()
    finally:
        eng.close()


@pytest.mark.parametrize("seed", [7,
                                  pytest.param(23, marks=pytest.mark.slow),
                                  pytest.param(41, marks=pytest.mark.slow)])
def test_int8_randomized_fault_schedule_accounting(served, seed):
    """The test_serving_faults.py accounting property, int8 regime: the
    allocator invariants hold at every step boundary under a randomized
    fault schedule, the pool drains to zero, every request lands in a
    typed terminal state, and DONE survivors match the unfaulted fp32
    run token-for-token (int8 KV reproduces it on this model)."""
    m, cfg, prompts, refs = served
    rng = np.random.RandomState(seed)
    eng = _engine(m)
    random_schedule(rng, horizon=25, n_faults=4, num_slots=3).install(eng)
    try:
        reqs = [eng.submit(p, N_NEW) for p in prompts]
        steps = 0
        while eng.queue.depth or eng.scheduler.active_slots:
            met = eng.step()
            steps += 1
            a = eng.allocator
            assert a.used_pages + a.free_pages == a.capacity
            assert met["pages_used"] <= a.capacity
            assert steps < 2000, "no progress under faults (int8)"
            if not met["active_slots"] and not met["tokens_this_step"]:
                time.sleep(0.001)
        assert eng.allocator.used_pages == 0
        assert eng.allocator.free_pages == eng.allocator.capacity
        for r in reqs:
            assert r.terminal, r.state
            if r.state != RequestState.DONE:
                assert r.error is not None
        for r, ref in zip(reqs, refs):
            if r.state == RequestState.DONE:
                assert np.array_equal(r.output_ids(), ref)
        # the pool the survivors decoded through still has sane scales
        for t in _scale_tensors(eng.cache):
            assert np.isfinite(np.asarray(t.numpy())).all()
    finally:
        eng.close()


def test_watchdog_rebuild_recreates_pool_and_scales(served):
    m, cfg, prompts, refs = served
    eng = _engine(m, stall_budget_s=0.5)
    try:
        w = eng.submit(prompts[0], 2)
        eng.run_until_idle()
        assert w.finished
        old_k = eng.cache.k._value
        old_ks = eng.cache.k_scale._value
        FaultInjector().inject("before_decode", at=0, kind="step_stall",
                               duration=2.0).install(eng)
        reqs = [eng.submit(p, N_NEW) for p in prompts[:4]]
        eng.run_until_idle()
        mt = eng.metrics()
        assert mt["recoveries"] == 1 and mt["rebuilds"] == 1
        # the three seated requests (num_slots=3) are implicated
        assert len([r for r in reqs
                    if isinstance(r.error, StepStalledError)]) == 3
        # the rebuilt pool is a FRESH int8 pool with fresh scale buffers
        assert eng.cache.quantized
        assert eng.cache.k_scale._value is not old_ks
        for t in _scale_tensors(eng.cache):
            assert t._value.shape == (cfg.num_layers, eng.num_pages,
                                      cfg.num_heads)
        # zombie cleanup releases the suspect pool's pages AND scales
        deadline = time.monotonic() + 5.0
        while not old_ks.is_deleted() and time.monotonic() < deadline:
            time.sleep(0.05)
        assert old_k.is_deleted(), "old int8 pages leaked"
        assert old_ks.is_deleted(), "old scale sidecars leaked"
        for r, ref in zip(reqs, refs):
            if r.state == RequestState.DONE:
                assert np.array_equal(r.output_ids(), ref)
        assert eng.allocator.used_pages == 0
    finally:
        eng.close()


def test_int8_prefix_cache_cow_is_bitwise(served):
    """COW regression: int8-KV prefix-cache-on outputs bitwise equal to
    cache-off, through a REAL hit (the shared prefix is registered by a
    completed request before the family arrives).  Relies on the write
    quantizer's determinism: adopted pages carry their scales, so a
    cached prefix dequantizes exactly as a re-prefilled one."""
    m, cfg, prompts, refs = served
    rng = np.random.RandomState(9)
    shared = rng.randint(0, cfg.vocab_size, (32,))   # two whole pages
    fam = [np.concatenate([shared,
                           rng.randint(0, cfg.vocab_size, (3 + 2 * i,))])
           for i in range(4)]
    outs = {}
    for cached in (False, True):
        eng = _engine(m, prefix_cache=cached)
        try:
            first = eng.submit(fam[0], N_NEW)
            eng.run_until_idle(max_steps=2000)
            rest = [eng.submit(p, N_NEW) for p in fam[1:]]
            eng.run_until_idle(max_steps=2000)
            outs[cached] = [np.asarray(r.output_ids())
                            for r in [first] + rest]
            if cached:
                assert eng.metrics()["prefix_hits"] >= 1
            assert eng.allocator.used_pages == 0
        finally:
            eng.close()
    for a, b in zip(outs[False], outs[True]):
        assert np.array_equal(a, b), "int8 COW drift"
