"""Continuous-batching serving engine: paged KV cache + ragged paged
attention + ONE fused mixed prefill/decode step (docs/serving.md).

Covers the acceptance criteria:
- retrace-freedom under churn (>= 20 varying-length requests through a
  4-slot engine, the fused step compiles <= 1 program, outputs
  token-for-token equal to single-shot greedy generate());
- fused mixed-step parity across interleaved arrivals for fp32, bf16 and
  int8 pools, with the weights as built and quantized to int8;
- ragged-kernel parity vs the per-token XLA gather oracle (interpret= on
  CPU), incl. page-straddling token blocks, shuffled work lists, zero
  lengths, and the plan builder's overflow guards;
- paged-kernel parity vs the XLA gather reference (the q-len-1 kernel
  stays the generate()/decode-engine path), incl. length-0 slots;
- block accounting soundness (reuse after free, occupancy never exceeds
  capacity, out-of-pages admission backpressures);
plus the satellites: chunked prefill into non-contiguous pages (the
direct ``_paged_lm_logits`` path), LRU eviction releasing KV-cache
buffers, PredictorPool concurrency, and the GL001/GL004-clean fused
step."""
import threading

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import inference, serving
from paddle_tpu.models import (
    GPTStackedForPretraining,
    generation,
    gpt_tiny,
)
from paddle_tpu.serving import (
    BlockAllocator,
    SamplingParams,
    ServingEngine,
)


def _tiny_cfg():
    return gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0)


def _prompt(cfg, b=1, s=6, seed=0):
    rng = np.random.RandomState(seed)
    return rng.randint(0, cfg.vocab_size, (b, s)).astype(np.int64)


# ---------------------------------------------------------------------------
# paged kernel parity (interpreter on CPU; the real kernel on TPU)
# ---------------------------------------------------------------------------

def test_paged_attention_kernel_parity_interpret():
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import paged_attention as pa

    rng = np.random.RandomState(0)
    P, H, PS, D = 9, 2, 128, 64
    S, MP = 3, 4
    assert pa.paged_shape_supported(PS, D)
    pt_tbl = jnp.array([[1, 2, 3, 4], [5, 6, 0, 0], [7, 8, 0, 0]], jnp.int32)
    for dt in (jnp.float32, jnp.bfloat16):
        q = jnp.array(rng.randn(S, H, D), dt)
        kp = jnp.array(rng.randn(P, H, PS, D), dt)
        vp = jnp.array(rng.randn(P, H, PS, D), dt)
        # boundary lengths: inactive slot, single token, inside a page,
        # page edge, mid-table, full table
        for lens in ([0, 1, 127], [128, 200, 512], [256, 0, 129]):
            ln = jnp.array(lens, jnp.int32)
            ref = np.asarray(pa._xla_paged_reference(
                q, kp, vp, pt_tbl, ln, 0.125), np.float32)
            q8 = jnp.broadcast_to(q.reshape(S * H, 1, D), (S * H, 8, D))
            out = pa._paged_pallas(q8, kp, vp, pt_tbl, ln, 0.125,
                                   interpret=True)
            got = np.asarray(out[:, 0, :].reshape(S, H, D), np.float32)
            tol = 5e-6 if dt == jnp.float32 else 1e-2
            np.testing.assert_allclose(got, ref, rtol=tol, atol=tol)
            for i, l in enumerate(lens):
                if l == 0:
                    assert not got[i].any(), "length-0 slot must emit zeros"


def test_paged_reference_matches_contiguous_single_page():
    """A one-page-per-slot table is a contiguous cache: the paged gather
    reference must agree with decode_attention's reference bitwise."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import decode_attention as da
    from paddle_tpu.ops.pallas_kernels import paged_attention as pa

    rng = np.random.RandomState(1)
    P, H, PS, D = 5, 2, 128, 64
    S = 4
    kp = jnp.array(rng.randn(P, H, PS, D), jnp.float32)
    vp = jnp.array(rng.randn(P, H, PS, D), jnp.float32)
    q = jnp.array(rng.randn(S, H, D), jnp.float32)
    tbl = jnp.array([[1], [2], [3], [4]], jnp.int32)
    for length in (1, 64, 127, 128):
        got = pa._xla_paged_reference(
            q, kp, vp, tbl, jnp.full((S,), length, jnp.int32), 0.125)
        ref = da._xla_decode_reference(
            q, kp[tbl[:, 0]], vp[tbl[:, 0]], jnp.int32(length), 0.125)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_paged_shape_eligibility_gate():
    from paddle_tpu.ops.pallas_kernels.paged_attention import (
        paged_shape_supported,
        paged_shape_unsupported_reason,
    )

    assert paged_shape_supported(128, 64)
    assert paged_shape_supported(256, 128)
    assert not paged_shape_supported(64, 64)     # page under one KV block
    assert not paged_shape_supported(200, 64)    # not a 128 multiple
    assert not paged_shape_supported(128, 80)    # head dim not 64-multiple
    r = paged_shape_unsupported_reason(16, 48)
    assert r is not None and r.code == "GL002"
    assert "paged_attention" in str(r)
    assert paged_shape_unsupported_reason(128, 64) is None


@pytest.mark.skipif(
    __import__("jax").devices()[0].platform != "tpu",
    reason="real-kernel parity needs a TPU backend (tools/tpu_smoke.py)")
def test_paged_attention_kernel_parity_tpu():
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import paged_attention as pa

    rng = np.random.RandomState(0)
    P, H, PS, D = 17, 4, 128, 64
    S, MP = 4, 4
    kp = jnp.array(rng.randn(P, H, PS, D), jnp.bfloat16)
    vp = jnp.array(rng.randn(P, H, PS, D), jnp.bfloat16)
    q = jnp.array(rng.randn(S, H, D), jnp.bfloat16)
    tbl = jnp.array(rng.permutation(P - 1)[:S * MP].reshape(S, MP) + 1,
                    jnp.int32)
    lens = jnp.array([0, 1, 200, 512], jnp.int32)
    got = np.asarray(pa.paged_attention(q, kp, vp, tbl, lens), np.float32)
    ref = np.asarray(pa._xla_paged_reference(q, kp, vp, tbl, lens, 0.125),
                     np.float32)
    np.testing.assert_allclose(got, ref, rtol=5e-2, atol=5e-2)


# ---------------------------------------------------------------------------
# ragged paged attention: the fused mixed prefill/decode kernel
# ---------------------------------------------------------------------------

def _mk_ragged_case(runs, T_MAX, NB_MAX, WL_MAX, MP, token_block=8,
                    page_size=128):
    """Plan + per-token tables/lengths for a synthetic run mix."""
    from paddle_tpu.ops.pallas_kernels import ragged_paged_attention as ra

    plan_np, stats = ra.build_ragged_plan(
        runs, token_block=token_block, page_size=page_size,
        t_max=T_MAX, nb_max=NB_MAX, wl_max=WL_MAX)
    tables = np.zeros((T_MAX, MP), np.int32)
    lengths = np.zeros((T_MAX,), np.int32)
    for (base, count, tbl), start in zip(runs, stats["run_starts"]):
        for i in range(count):
            tables[start + i] = tbl
            lengths[start + i] = base + i + 1
    return plan_np, stats, tables, lengths


def test_ragged_kernel_parity_interpret():
    """Mixed decode + prefill runs through the work-list kernel
    (interpreter) vs the per-token gather oracle: page-straddling token
    blocks, shuffled pool pages, boundary positions, fp32 + bf16."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import ragged_paged_attention as ra

    rng = np.random.RandomState(0)
    P, H, PS, D = 11, 2, 128, 64
    MP = 4
    runs = [
        (200, 1, np.array([4, 2, 9, 1], np.int32)),    # decode, 2 pages
        (0, 1, np.array([3, 0, 0, 0], np.int32)),      # decode at pos 0
        (120, 16, np.array([7, 5, 8, 6], np.int32)),   # prefill straddling
        (17, 5, np.array([10, 0, 0, 0], np.int32)),    # short prefill tail
    ]
    T_MAX, NB_MAX, WL_MAX = 32, 8, 32
    plan_np, stats, tables, lengths = _mk_ragged_case(runs, T_MAX, NB_MAX,
                                                      WL_MAX, MP)
    real = stats["n_tokens"]
    for dt, tol in ((jnp.float32, 5e-6), (jnp.bfloat16, 2e-2)):
        q = jnp.array(rng.randn(T_MAX, H, D), dt)
        kp = jnp.array(rng.randn(P, H, PS, D), dt)
        vp = jnp.array(rng.randn(P, H, PS, D), dt)
        plan = tuple(jnp.array(plan_np[k]) for k in ra.RAGGED_PLAN_FIELDS)
        ref = np.asarray(ra._xla_ragged_reference(
            q, kp, vp, jnp.array(tables), jnp.array(lengths), 0.125),
            np.float32)
        got = np.asarray(ra.ragged_paged_attention(
            q, kp, vp, jnp.array(tables), jnp.array(lengths), plan,
            sm_scale=0.125, interpret=True), np.float32)
        np.testing.assert_allclose(got[:real], ref[:real], rtol=tol,
                                   atol=tol)


def _static_grid_kernel(blk_ref, page_ref, ps_ref, ni_ref, base_ref, rows_ref,
                        q_ref, k_ref, v_ref, *rest, scale, page_size, wl_max,
                        quantized=False):
    """The kernel as it was while the launch was ``wl_max`` items long
    (before PR 30): every grid step past ``n_items`` is skipped by its
    ``live`` guards.  Kept here as the oracle of the launch that ends at
    ``n_items``: the same operations in the same order for every real item."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from paddle_tpu.ops.pallas_kernels.decode_attention import NEG_INF, _dot

    if quantized:
        ks_ref, vs_ref, o_ref, acc_sc, m_sc, l_sc = rest
    else:
        o_ref, acc_sc, m_sc, l_sc = rest
    w = pl.program_id(1)
    n = ni_ref[0]
    blk = blk_ref[w]
    live = w < n
    first = jnp.logical_or(w == 0, blk_ref[jnp.maximum(w - 1, 0)] != blk)
    last = jnp.logical_or(w == n - 1,
                          blk_ref[jnp.minimum(w + 1, wl_max - 1)] != blk)

    @pl.when(jnp.logical_and(live, first))
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    @pl.when(live)
    def _body():
        q = q_ref[0, 0]
        if quantized:
            k = k_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0]
            v = v_ref[0, 0].astype(jnp.float32) * vs_ref[0, 0]
        else:
            k = k_ref[0, 0]
            v = v_ref[0, 0]
        s = _dot(q, k, ((1,), (1,))) * np.float32(scale)
        rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = ps_ref[w] * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        row_pos = base_ref[blk] + rows
        valid = jnp.logical_and(cols <= row_pos, rows < rows_ref[blk])
        s = jnp.where(valid, s, NEG_INF)
        m_prev = m_sc[:, :1]
        l_prev = l_sc[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        l_cur = jnp.sum(p, axis=-1, keepdims=True)
        alpha = jnp.exp(m_prev - m_new)
        acc_sc[...] = acc_sc[...] * alpha + _dot(p.astype(v.dtype), v,
                                                 ((1,), (0,)))
        m_sc[...] = jnp.broadcast_to(m_new, m_sc.shape)
        l_sc[...] = jnp.broadcast_to(alpha * l_prev + l_cur, l_sc.shape)

    @pl.when(jnp.logical_and(live, last))
    def _finish():
        l = l_sc[:, :1]
        l_safe = jnp.where(l == 0.0, np.float32(1.0), l)
        o_ref[0, 0] = (acc_sc[...] / l_safe).astype(o_ref.dtype)


def _static_grid_ragged_pallas(q_blocks, k_pool, v_pool, wl_blk, wl_page,
                               wl_ps, n_items, blk_base, blk_rows, scale,
                               interpret=False, k_scale=None, v_scale=None):
    """``_ragged_pallas`` with the static grid ``(H, wl_max)`` it had."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nb, h, qb, d = q_blocks.shape
    page_size = k_pool.shape[2]
    wl_max = wl_blk.shape[0]
    quantized = k_scale is not None

    def q_index(hh, w, blk_ref, *_):
        return (blk_ref[w], hh, np.int32(0), np.int32(0))

    def kv_index(hh, w, blk_ref, page_ref, *_):
        return (page_ref[w], hh, np.int32(0), np.int32(0))

    def scale_index(hh, w, blk_ref, page_ref, *_):
        return (page_ref[w], hh)

    in_specs = [pl.BlockSpec((1, 1, qb, d), q_index),
                pl.BlockSpec((1, 1, page_size, d), kv_index),
                pl.BlockSpec((1, 1, page_size, d), kv_index)]
    operands = [q_blocks, k_pool, v_pool]
    if quantized:
        in_specs += [pl.BlockSpec((1, 1), scale_index)] * 2
        operands += [k_scale, v_scale]
    return pl.pallas_call(
        functools.partial(_static_grid_kernel, scale=scale,
                          page_size=page_size, wl_max=wl_max,
                          quantized=quantized),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6, grid=(h, wl_max), in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, qb, d), q_index),
            scratch_shapes=[pltpu.VMEM((qb, d), jnp.float32),
                            pltpu.VMEM((qb, 128), jnp.float32),
                            pltpu.VMEM((qb, 128), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((nb, h, qb, d), q_blocks.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(wl_blk.astype(jnp.int32), wl_page.astype(jnp.int32),
      wl_ps.astype(jnp.int32), jnp.reshape(n_items, (1,)).astype(jnp.int32),
      blk_base.astype(jnp.int32), blk_rows.astype(jnp.int32), *operands)


# the launch's length follows the work list: three fills of ONE geometry
# (T_MAX 32, NB_MAX 8, WL_MAX 32, four pages a slot) for one jitted function
_LAUNCH_RUNS = {
    # a single decode token on its first page: the grid is (H, 1)
    1: [(5, 1, np.array([3, 0, 0, 0], np.int32))],
    # the mixed step of test_ragged_kernel_parity_interpret
    7: [(200, 1, np.array([4, 2, 9, 1], np.int32)),
        (0, 1, np.array([3, 0, 0, 0], np.int32)),
        (120, 16, np.array([7, 5, 8, 6], np.int32)),
        (17, 5, np.array([10, 0, 0, 0], np.int32))],
    # every block reads all four pages of its slot: the list is full and the
    # launch is as long as the arrays, as every launch was before
    32: [(384 + 9 * i, 1, np.roll(np.array([1, 2, 3, 4, 5, 6, 7, 8], np.int32),
                                  i)[:4]) for i in range(8)],
}
_LAUNCH_POOLS = {"float32": 5e-6, "bfloat16": 2e-2, "int8": 5e-6}


def _launch_operands(pool, rng, t_max, pages, heads, page_size, dim):
    """Queries, K and V pools and (for the int8 pool) the per-(page, head)
    scales of one launch case: ``(q, k_pool, v_pool, scales)``."""
    import jax.numpy as jnp

    pool_shape = (pages, heads, page_size, dim)
    if pool == "int8":
        q = jnp.array(rng.randn(t_max, heads, dim), jnp.float32)
        kp, vp = (jnp.array(rng.randint(-127, 128, pool_shape), jnp.int8)
                  for _ in range(2))
        ks, vs = (jnp.array(rng.uniform(0.005, 0.02, (pages, heads)),
                            jnp.float32) for _ in range(2))
        return q, kp, vp, dict(k_scale=ks, v_scale=vs)
    q, kp, vp = (jnp.array(rng.randn(*shape), pool)
                 for shape in ((t_max, heads, dim), pool_shape, pool_shape))
    return q, kp, vp, {}


@pytest.fixture(scope="module")
def launch_fns():
    """One jitted ragged attention a pool dtype, shared by that dtype's
    cases, so that its trace count spans their item counts."""
    import jax

    from paddle_tpu.ops.pallas_kernels import ragged_paged_attention as ra

    def make():           # a function object each: jit counts traces by it
        def fn(q, kp, vp, tables, lengths, plan, **scales):
            return ra.ragged_paged_attention(
                q, kp, vp, tables, lengths, plan, sm_scale=0.125,
                interpret=True, **scales)
        return jax.jit(fn)
    return {pool: make() for pool in _LAUNCH_POOLS}


@pytest.mark.parametrize("n_items", list(_LAUNCH_RUNS))
@pytest.mark.parametrize("pool", list(_LAUNCH_POOLS))
def test_ragged_launch_follows_the_work_list(pool, n_items, launch_fns,
                                             monkeypatch):
    """The launch that ends at ``n_items`` (1, a mid fill, ``wl_max``):
    equal to the gather oracle within the pool's tolerance, BITWISE the
    static-grid launch on the same plan, and one trace of the jitted
    function for every item count; fp32, bf16 and int8 pools."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import ragged_paged_attention as ra

    rng = np.random.RandomState(n_items)
    P, H, PS, D, MP = 11, 2, 128, 64, 4
    T_MAX, NB_MAX, WL_MAX = 32, 8, 32
    plan_np, stats, tables, lengths = _mk_ragged_case(
        _LAUNCH_RUNS[n_items], T_MAX, NB_MAX, WL_MAX, MP)
    assert stats["n_items"] == stats["launched_items"] == n_items
    real = stats["n_tokens"]
    q, kp, vp, scales = _launch_operands(pool, rng, T_MAX, P, H, PS, D)
    plan = tuple(jnp.array(plan_np[k]) for k in ra.RAGGED_PLAN_FIELDS)
    tables, lengths = jnp.array(tables), jnp.array(lengths)

    fn = launch_fns[pool]
    got = np.asarray(fn(q, kp, vp, tables, lengths, plan, **scales),
                     np.float32)
    assert fn._cache_size() == 1       # no retrace, whatever ran before
    ref = np.asarray(ra._xla_ragged_reference(
        q, kp, vp, tables, lengths, 0.125, **scales), np.float32)
    np.testing.assert_allclose(got[:real], ref[:real],
                               rtol=_LAUNCH_POOLS[pool],
                               atol=_LAUNCH_POOLS[pool])
    monkeypatch.setattr(ra, "_ragged_pallas", _static_grid_ragged_pallas)
    static = np.asarray(ra.ragged_paged_attention(
        q, kp, vp, tables, lengths, plan, sm_scale=0.125, interpret=True,
        **scales), np.float32)
    np.testing.assert_array_equal(got[:real], static[:real])


_GROUPS = {"float32": 5e-6, "bfloat16": 2e-2}


@pytest.mark.parametrize("n_items", [1, 7])
@pytest.mark.parametrize("pool", list(_GROUPS))
def test_ragged_kernel_serves_a_group_of_query_heads_a_pool_head(pool, n_items):
    """Grouped queries (8 query heads over a pool of 2, G = 4): the group's
    heads are folded into the query block's rows.  Equal to the gather oracle
    (which repeats K/V heads) within the pool's tolerance, and BITWISE the
    launch with one query head a pool head over a pool whose heads are
    repeated: a query head's chain of operations is the same in both."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import ragged_paged_attention as ra

    rng = np.random.RandomState(3)
    P, HKV, G, PS, D, MP = 11, 2, 4, 128, 64, 4
    T_MAX, NB_MAX, WL_MAX = 32, 8, 32
    plan_np, stats, tables, lengths = _mk_ragged_case(
        _LAUNCH_RUNS[n_items], T_MAX, NB_MAX, WL_MAX, MP)
    real = stats["n_tokens"]
    q = jnp.array(rng.randn(T_MAX, HKV * G, D), pool)
    kp, vp = (jnp.array(rng.randn(P, HKV, PS, D), pool) for _ in range(2))
    plan = tuple(jnp.array(plan_np[k]) for k in ra.RAGGED_PLAN_FIELDS)
    tables, lengths = jnp.array(tables), jnp.array(lengths)

    got = np.asarray(ra.ragged_paged_attention(
        q, kp, vp, tables, lengths, plan, sm_scale=0.125, interpret=True),
        np.float32)
    assert got.shape == (T_MAX, HKV * G, D)
    ref = np.asarray(ra._xla_ragged_reference(q, kp, vp, tables, lengths, 0.125),
                     np.float32)
    np.testing.assert_allclose(got[:real], ref[:real], rtol=_GROUPS[pool],
                               atol=_GROUPS[pool])
    plain = np.asarray(ra.ragged_paged_attention(
        q, jnp.repeat(kp, G, axis=1), jnp.repeat(vp, G, axis=1), tables,
        lengths, plan, sm_scale=0.125, interpret=True), np.float32)
    np.testing.assert_array_equal(got[:real], plain[:real])
    with pytest.raises(ValueError, match="must divide"):
        ra.ragged_paged_attention(q[:, :7], kp, vp, tables, lengths, plan,
                                  sm_scale=0.125, interpret=True)


def test_ragged_launch_with_one_query_head_a_pool_head_is_the_launch_it_was(
        monkeypatch):
    """``G == 1`` takes the launch's old path to the letter: ``_ragged_pallas``
    is called with the arguments it always had (no ``group``), on query blocks
    ``[NB, H, QB, D]``, and the kernel it traces holds no row-to-token
    remainder; ``test_ragged_launch_follows_the_work_list`` holds its outputs
    bitwise against the launch of before.  ``G == 4`` passes ``group`` and
    folds the rows."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import ragged_paged_attention as ra

    rng = np.random.RandomState(5)
    P, PS, D, MP = 11, 128, 64, 4
    T_MAX, NB_MAX, WL_MAX = 32, 8, 32
    plan_np, _, tables, lengths = _mk_ragged_case(
        _LAUNCH_RUNS[7], T_MAX, NB_MAX, WL_MAX, MP)
    plan = tuple(jnp.array(plan_np[k]) for k in ra.RAGGED_PLAN_FIELDS)
    launch, seen = ra._ragged_pallas, []

    def spy(q_blocks, *args, **kwargs):
        seen.append((q_blocks.shape, sorted(kwargs)))
        return launch(q_blocks, *args, **kwargs)

    monkeypatch.setattr(ra, "_ragged_pallas", spy)
    texts = {}
    for heads in (2, 8):
        q = jnp.array(rng.randn(T_MAX, heads, D), jnp.float32)
        kp, vp = (jnp.array(rng.randn(P, 2, PS, D), jnp.float32) for _ in range(2))
        texts[heads] = str(jax.make_jaxpr(
            lambda q_, k_, v_: ra.ragged_paged_attention(
                q_, k_, v_, jnp.array(tables), jnp.array(lengths), plan,
                sm_scale=0.125, interpret=True))(q, kp, vp))
    assert seen[0] == ((NB_MAX, 2, 8, D), ["interpret", "k_scale", "v_scale"])
    assert seen[1] == ((NB_MAX, 2, 32, D),
                       ["group", "interpret", "k_scale", "v_scale"])
    assert " rem " not in texts[2] and " rem " in texts[8]


@pytest.mark.parametrize("window", [1, 40, 70])
@pytest.mark.parametrize("pool", list(_GROUPS))
def test_ragged_kernel_masks_keys_below_a_window(pool, window):
    """``window=W``: a token reads the newest ``W`` positions up to its own.
    The launch (interpreter) against the gather oracle given the same bound,
    over the whole work list and over the windowed one (only the pages that
    hold a readable position); with grouped queries too."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import ragged_paged_attention as ra

    rng = np.random.RandomState(window)
    P, HKV, G, PS, D, MP = 11, 2, 2, 128, 64, 4
    T_MAX, NB_MAX, WL_MAX = 32, 8, 32
    runs = _LAUNCH_RUNS[7]
    plan_np, stats, tables, lengths = _mk_ragged_case(
        runs, T_MAX, NB_MAX, WL_MAX, MP)
    real = stats["n_tokens"]
    q = jnp.array(rng.randn(T_MAX, HKV * G, D), pool)
    kp, vp = (jnp.array(rng.randn(P, HKV, PS, D), pool) for _ in range(2))
    tables, lengths = jnp.array(tables), jnp.array(lengths)
    ref = np.asarray(ra._xla_ragged_reference(
        q, kp, vp, tables, lengths, 0.125, window=window), np.float32)
    full = np.asarray(ra._xla_ragged_reference(
        q, kp, vp, tables, lengths, 0.125), np.float32)
    assert np.abs(ref[:real] - full[:real]).max() > 1e-2      # the bound binds
    windowed, wstats = ra.build_ragged_plan(
        runs, token_block=8, page_size=PS, t_max=T_MAX, nb_max=NB_MAX,
        wl_max=WL_MAX, window=window)
    assert wstats["n_items"] < stats["n_items"]
    for arrays in (plan_np, windowed):
        plan = tuple(jnp.array(arrays[k]) for k in ra.RAGGED_PLAN_FIELDS)
        got = np.asarray(ra.ragged_paged_attention(
            q, kp, vp, tables, lengths, plan, sm_scale=0.125, interpret=True,
            window=window), np.float32)
        np.testing.assert_allclose(got[:real], ref[:real], rtol=_GROUPS[pool],
                                   atol=_GROUPS[pool])


def test_ragged_launch_without_a_window_is_the_launch_it_was(monkeypatch):
    """``window=None`` passes ``_ragged_pallas`` no keyword it did not have
    and traces the program a call without the keyword traces, to the letter
    (``test_ragged_launch_follows_the_work_list`` holds that launch's outputs
    bitwise against the launch of before); a window adds one comparison."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import ragged_paged_attention as ra

    rng = np.random.RandomState(6)
    P, H, PS, D, MP = 11, 2, 128, 64, 4
    T_MAX, NB_MAX, WL_MAX = 32, 8, 32
    plan_np, _, tables, lengths = _mk_ragged_case(
        _LAUNCH_RUNS[7], T_MAX, NB_MAX, WL_MAX, MP)
    plan = tuple(jnp.array(plan_np[k]) for k in ra.RAGGED_PLAN_FIELDS)
    q = jnp.array(rng.randn(T_MAX, H, D), jnp.float32)
    kp, vp = (jnp.array(rng.randn(P, H, PS, D), jnp.float32) for _ in range(2))
    launch, seen = ra._ragged_pallas, []

    def spy(*args, **kwargs):
        seen.append(sorted(kwargs))
        return launch(*args, **kwargs)

    monkeypatch.setattr(ra, "_ragged_pallas", spy)

    def traced(**kw):
        return str(jax.make_jaxpr(
            lambda q_, k_, v_: ra.ragged_paged_attention(
                q_, k_, v_, jnp.array(tables), jnp.array(lengths), plan,
                sm_scale=0.125, interpret=True, **kw))(q, kp, vp))

    as_it_was, none, some = traced(), traced(window=None), traced(window=64)
    assert as_it_was == none != some
    assert seen == [["interpret", "k_scale", "v_scale"]] * 2 + [
        ["interpret", "k_scale", "v_scale", "window"]]
    assert some.count(" gt ") == none.count(" gt ") + 1


@pytest.mark.parametrize("base,count", [(0, 1), (511, 1), (512, 1), (700, 1),
                                        (895, 256), (1000, 37), (130, 256)])
def test_a_plan_over_a_ring_lists_the_pages_inside_the_window(base, count):
    """``build_ragged_plan(window=W)`` over a slot's ring (page-slot ``j`` at
    ring page ``j mod R``): a block's items are exactly the page-slots that
    hold a position one of its rows reads, each at its ring page, and no two
    of a step's page-slots share a ring page."""
    from paddle_tpu.ops.pallas_kernels import ragged_paged_attention as ra

    W, PS, QB, MP = 512, 128, 8, 64
    R = -(-(W - 1 + 256) // PS) + 1
    ring = (1 + 3 * R + np.arange(MP) % R).astype(np.int32)     # slot 3's
    nb = -(-count // QB)
    plan, stats = ra.build_ragged_plan(
        [(base, count, ring)], token_block=QB, page_size=PS, t_max=256,
        nb_max=nb, wl_max=nb * R, window=W)
    n = stats["n_items"]
    items = list(zip(plan["wl_blk"][:n], plan["wl_page"][:n],
                     plan["wl_pageslot"][:n]))
    want = []
    for b in range(nb):
        lo = base + b * QB
        hi = min(lo + QB, base + count) - 1
        for j in range(max(lo - W + 1, 0) // PS, hi // PS + 1):
            want.append((b, ring[j], j))
    assert items == want
    slots = {j for _, _, j in items}
    assert len({ring[j] for j in slots}) == len(slots) <= R
    # the write list names the ring pages the run's positions land in
    w = int(plan["n_writes"][0])
    assert set(plan["wr_page"][:w]) == {
        ring[p // PS] for p in range(base, base + count)}


def _plan_by_the_loop(runs, *, token_block, page_size, t_max, nb_max, wl_max,
                      window=None):
    """The work list as PR 34's builder made it: a Python loop over runs,
    blocks and page-slots (the oracle of the numpy builder; with a window a
    block's first page-slot is the one holding ``base - window + 1``)."""
    qb = token_block
    blk_tok = np.zeros((nb_max, qb), np.int32)
    tok_blk = np.zeros((t_max,), np.int32)
    tok_row = np.zeros((t_max,), np.int32)
    blk_base = np.zeros((nb_max,), np.int32)
    blk_rows = np.zeros((nb_max,), np.int32)
    items, run_starts, t, b = [], [], 0, 0
    for base, count, table in runs:
        run_starts.append(t)
        off = 0
        while off < count:
            rows = min(qb, count - off)
            blk_tok[b, :rows] = np.arange(t + off, t + off + rows)
            blk_tok[b, rows:] = t + off
            blk_base[b] = base + off
            blk_rows[b] = rows
            tok_blk[t + off:t + off + rows] = b
            tok_row[t + off:t + off + rows] = np.arange(rows)
            first = (0 if window is None
                     else max(base + off - window + 1, 0) // page_size)
            for ps_i in range(first, (base + off + rows - 1) // page_size + 1):
                items.append((b, int(table[ps_i]), ps_i))
            off += rows
            b += 1
        t += count
    plan = {"blk_tok": blk_tok, "tok_blk": tok_blk, "tok_row": tok_row,
            "blk_base": blk_base, "blk_rows": blk_rows,
            "n_items": np.array([len(items)], np.int32)}
    for col, name in enumerate(("wl_blk", "wl_page", "wl_pageslot")):
        arr = np.full((wl_max,), items[-1][col], np.int32)
        arr[:len(items)] = [it[col] for it in items]
        plan[name] = arr
    stats = {"n_tokens": t, "n_blocks": b, "n_items": len(items),
             "run_starts": run_starts, "wl_capacity": wl_max,
             "row_capacity": b * qb, "launched_items": len(items)}
    return plan, stats


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("window", [None, 512, 200])
def test_the_plan_builder_is_the_loop_it_replaced(window, seed):
    """The numpy builder gives, array for array and dtype for dtype, what the
    loop over runs, blocks and page-slots gave: random steps of decode rows
    and chunks, over page tables (``window=None``) and over ring tables
    (``window=W``: page-slot ``j`` at ring page ``j mod R``)."""
    from paddle_tpu.ops.pallas_kernels import ragged_paged_attention as ra

    rng = np.random.default_rng(100 * seed + (window or 0))
    PS, MP, budget = 128, 64, 256
    for _ in range(40):
        qb = int(rng.choice([8, 16, 32]))
        n = int(rng.integers(1, 20))
        if window is None:
            tables = rng.permutation(
                np.arange(1, 1 + n * MP)).reshape(n, MP).astype(np.int32)
        else:
            R = -(-(window - 1 + budget) // PS) + 1
            tables = (1 + R * np.arange(n)[:, None]
                      + np.arange(MP)[None, :] % R).astype(np.int32)
        runs, left = [], budget
        for r in range(n):
            count = 1
            if rng.random() < 0.3 and left > 0:
                count = int(rng.integers(1, left + 1))
                left -= count
            runs.append((int(rng.integers(0, MP * PS - count)), count,
                         tables[r]))
        geo = dict(token_block=qb, page_size=PS, t_max=n + budget,
                   nb_max=n + budget // qb,
                   wl_max=(n + budget // qb) * MP, window=window)
        want, want_stats = _plan_by_the_loop(runs, **geo)
        got, got_stats = ra.build_ragged_plan(runs, write_group=16, **geo)
        assert set(ra.RAGGED_PLAN_FIELDS) == set(got) >= set(want)
        for name, arr in want.items():
            assert got[name].dtype == arr.dtype and got[name].shape == arr.shape
            np.testing.assert_array_equal(got[name], arr, err_msg=name)
        assert {k: got_stats[k] for k in want_stats} == want_stats
        # the write list is `_build_write_list`'s, which PR 34 brought and
        # this builder calls with the operands the loop gave it
        w = got_stats["n_writes"]
        groups = {(int(tables_r[p // PS]), p % PS // 16)
                  for base, count, tables_r in runs
                  for p in range(base, base + count)}
        assert w == len(groups)
        assert set(zip(got["wr_page"][:w].tolist(),
                       got["wr_group"][:w].tolist())) == groups


_HEAD_BLOCKS = {"all_heads": 4, "some_heads": 2, "one_head": 1}


@pytest.mark.parametrize("head_block", list(_HEAD_BLOCKS))
@pytest.mark.parametrize("pool", list(_LAUNCH_POOLS))
def test_ragged_work_item_carries_a_block_of_heads(pool, head_block,
                                                   monkeypatch):
    """A grid step moves ``hb`` heads of its item's page: ``hb = H`` (what
    every served geometry gets: grid ``(1, n_items)``), ``1 < hb < H`` and
    ``hb = 1`` (grid ``(H // hb, n_items)``, the scratch re-initialised a
    head block) on the mixed step, each equal to the gather oracle within
    the pool's tolerance and BITWISE the launch that moves all heads."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import ragged_paged_attention as ra

    hb = _HEAD_BLOCKS[head_block]
    rng = np.random.RandomState(4)
    P, H, PS, D, MP = 11, 4, 128, 64, 4
    T_MAX, NB_MAX, WL_MAX = 32, 8, 32
    plan_np, stats, tables, lengths = _mk_ragged_case(
        _LAUNCH_RUNS[7], T_MAX, NB_MAX, WL_MAX, MP)
    real = stats["n_tokens"]
    q, kp, vp, scales = _launch_operands(pool, rng, T_MAX, P, H, PS, D)
    assert ra.ragged_head_block(H, PS, D, kp.dtype) == H
    plan = tuple(jnp.array(plan_np[k]) for k in ra.RAGGED_PLAN_FIELDS)
    tables, lengths = jnp.array(tables), jnp.array(lengths)

    def run():
        return np.asarray(ra.ragged_paged_attention(
            q, kp, vp, tables, lengths, plan, sm_scale=0.125, interpret=True,
            **scales), np.float32)

    whole = run()
    launch = ra._ragged_pallas
    seen = []

    def with_head_block(*args, **kwargs):
        seen.append(hb)
        return launch(*args, head_block=hb, **kwargs)

    monkeypatch.setattr(ra, "_ragged_pallas", with_head_block)
    got = run()
    assert seen == [hb]
    ref = np.asarray(ra._xla_ragged_reference(
        q, kp, vp, tables, lengths, 0.125, **scales), np.float32)
    np.testing.assert_allclose(got[:real], ref[:real],
                               rtol=_LAUNCH_POOLS[pool],
                               atol=_LAUNCH_POOLS[pool])
    np.testing.assert_array_equal(got[:real], whole[:real])
    with pytest.raises(ValueError, match="must divide"):
        launch(jnp.zeros((NB_MAX, H, 8, D), kp.dtype), kp, vp, *plan[5:8],
               plan[8], plan[3], plan[4], 0.125, interpret=True, head_block=3)


def test_ragged_head_block_follows_the_launchs_shapes():
    """``hb`` is a pure function of the local head count, the page, the head
    size and the pool's item size: it divides the heads, its K and V blocks,
    double-buffered, hold the budget, every served geometry (the cells' 16
    heads; 8, 20 and 40 local heads under ``mp``) moves all its heads, and a
    shape past the budget moves fewer."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import ragged_paged_attention as ra

    budget = ra._SCOPED_VMEM_BYTES * ra._KV_BUFFER_SHARE
    for heads in (16, 8, 20, 40):
        assert ra.ragged_head_block(heads, 128, 128, jnp.bfloat16) == heads
    assert ra.ragged_head_block(16, 128, 128, "bfloat16") == 16   # by name too
    assert 4 * 16 * 128 * 128 * 2 == 2 << 20                      # the cells: 2 MiB
    for heads, page, dim, dtype in ((40, 256, 128, jnp.float32),
                                    (16, 512, 256, jnp.bfloat16),
                                    (12, 1024, 128, jnp.float32),
                                    (7, 2048, 256, jnp.float32),
                                    (16, 128, 128, jnp.int8)):
        hb = ra.ragged_head_block(heads, page, dim, dtype)
        per_head = 4 * page * dim * jnp.dtype(dtype).itemsize
        assert heads % hb == 0 and hb >= 1
        assert hb * per_head <= budget or hb == 1
        # no larger divisor fits
        assert all(heads % more or more * per_head > budget
                   for more in range(hb + 1, heads + 1))
    assert ra.ragged_head_block(40, 256, 128, jnp.float32) == 10
    assert ra.ragged_head_block(16, 512, 256, jnp.bfloat16) == 8
    assert ra.ragged_head_block(7, 2048, 256, jnp.float32) == 1
    assert ra.ragged_head_block(16, 128, 128, jnp.int8) == 16


def test_ragged_reference_zero_length_and_decode_equivalence():
    """The oracle's semantics: a zero-length token emits zeros, and a
    one-token-per-slot plan is bitwise the paged decode reference (the
    old per-slot decode step is a strict special case)."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import paged_attention as pa
    from paddle_tpu.ops.pallas_kernels import ragged_paged_attention as ra

    rng = np.random.RandomState(1)
    P, H, PS, D = 7, 2, 128, 64
    q = jnp.array(rng.randn(3, H, D), jnp.float32)
    kp = jnp.array(rng.randn(P, H, PS, D), jnp.float32)
    vp = jnp.array(rng.randn(P, H, PS, D), jnp.float32)
    tbl = jnp.array([[1, 2], [3, 4], [5, 6]], jnp.int32)
    lens = jnp.array([0, 130, 256], jnp.int32)
    got = np.asarray(ra._xla_ragged_reference(q, kp, vp, tbl, lens, 0.125))
    want = np.asarray(pa._xla_paged_reference(q, kp, vp, tbl, lens, 0.125))
    np.testing.assert_array_equal(got, want)
    assert not got[0].any(), "length-0 token must emit zeros"


def test_ragged_plan_builder_shapes_and_guards():
    from paddle_tpu.ops.pallas_kernels import ragged_paged_attention as ra

    tbl = np.array([2, 3], np.int32)
    plan, stats = ra.build_ragged_plan(
        [(0, 10, tbl), (130, 1, tbl)], token_block=8, page_size=128,
        t_max=16, nb_max=4, wl_max=8)
    # 10 prefill tokens -> blocks of 8+2; decode at 130 -> pages 0..1
    assert stats["n_tokens"] == 11 and stats["n_blocks"] == 3
    # items: block0 (rows 0-7, 1 page) + block1 (rows 8-9, 1 page)
    #        + block2 (decode pos 130 -> 2 pages)
    assert stats["n_items"] == stats["launched_items"] == 4
    assert stats["wl_capacity"] == 8
    assert stats["run_starts"] == [0, 10]
    assert plan["blk_rows"].tolist()[:3] == [8, 2, 1]
    assert plan["blk_base"].tolist()[:3] == [0, 8, 130]
    # the arrays' tail repeats the last real entry (valid indices the
    # launch never reaches, but for the last item's look-ahead)
    assert plan["wl_blk"][stats["n_items"]:].tolist() == [2] * 4
    assert plan["wl_page"][3] == 3        # decode's second page-slot
    # overflow guards: the engine sizes the maxima so these never fire
    with pytest.raises(ValueError, match="overflow"):
        ra.build_ragged_plan([(0, 20, tbl)], token_block=8, page_size=128,
                             t_max=16, nb_max=4, wl_max=8)
    with pytest.raises(ValueError, match="overflow"):
        ra.build_ragged_plan([(0, 10, tbl)], token_block=8, page_size=128,
                             t_max=16, nb_max=1, wl_max=8)
    with pytest.raises(ValueError, match="at least one token"):
        ra.build_ragged_plan([(0, 0, tbl)], token_block=8, page_size=128,
                             t_max=16, nb_max=4, wl_max=8)
    with pytest.raises(ValueError, match="empty plan"):
        ra.build_ragged_plan([], token_block=8, page_size=128,
                             t_max=16, nb_max=4, wl_max=8)


def test_ragged_shape_eligibility_gate():
    from paddle_tpu.ops.pallas_kernels.ragged_paged_attention import (
        ragged_shape_supported,
        ragged_shape_unsupported_reason,
    )

    assert ragged_shape_supported(128, 64)
    assert ragged_shape_supported(256, 128, token_block=16)
    assert not ragged_shape_supported(64, 64)     # page under one KV block
    assert not ragged_shape_supported(128, 80)    # head dim not 64-multiple
    assert not ragged_shape_supported(128, 64, token_block=12)  # sublane
    r = ragged_shape_unsupported_reason(16, 48, token_block=4)
    assert r is not None and r.code == "GL002"
    assert "ragged_paged_attention" in str(r)
    assert "token_block" in str(r)
    assert ragged_shape_unsupported_reason(128, 64) is None


# ---------------------------------------------------------------------------
# block-pool accounting (property-style)
# ---------------------------------------------------------------------------

def test_block_allocator_invariants():
    a = BlockAllocator(9)           # null page + 8 allocatable
    assert a.capacity == 8 and a.free_pages == 8 and a.used_pages == 0
    p1 = a.alloc(3)
    p2 = a.alloc(5)
    assert a.free_pages == 0
    assert 0 not in p1 + p2          # null page never handed out
    assert a.alloc(1) is None        # exhausted: None, state unchanged
    assert a.used_pages == 8
    a.free(p1)
    assert a.free_pages == 3
    with pytest.raises(ValueError, match="not currently allocated"):
        a.free(p1[:1])               # double free must raise
    with pytest.raises(ValueError):
        a.free([0])                  # the null page was never allocated
    p3 = a.alloc(3)
    assert sorted(p3) == sorted(p1)  # freed pages are reused


def test_block_accounting_random_churn():
    """Random alloc/free churn: occupancy never exceeds capacity, a
    too-big request leaves state untouched, every page freed comes back."""
    rng = np.random.RandomState(7)
    a = BlockAllocator(17)
    live = []
    for _ in range(300):
        if live and rng.rand() < 0.45:
            a.free(live.pop(rng.randint(len(live))))
        else:
            n = int(rng.randint(1, 5))
            before = (a.free_pages, a.used_pages)
            got = a.alloc(n)
            if got is None:
                assert (a.free_pages, a.used_pages) == before
            else:
                live.append(got)
        assert a.used_pages + a.free_pages == a.capacity
        assert a.used_pages <= a.capacity
    for pages in live:
        a.free(pages)
    assert a.free_pages == a.capacity


def test_plan_step_budget_oldest_admission_first():
    """The prefill budget drains by ADMISSION order, not slot index:
    admission reuses a freed low index immediately, so index order would
    let a slot that churns through budget-sized prompts starve an older
    mid-prefill slot forever (its request would never see a token of
    budget while holding its reserved pages)."""
    from paddle_tpu.serving import AdmissionScheduler

    a = BlockAllocator(17)
    sched = AdmissionScheduler(num_slots=2, max_pages_per_slot=4,
                               page_size=16, allocator=a)
    assert sched.try_admit(object(), 32) == 0       # seq 0 -> slot 0
    assert sched.try_admit(object(), 32) == 1       # seq 1 -> slot 1
    sched.slots[1].pending = np.arange(8, dtype=np.int64)
    sched.retire(0)
    assert sched.try_admit(object(), 32) == 0       # seq 2 reuses slot 0
    sched.slots[0].pending = np.arange(8, dtype=np.int64)
    # budget covers ONE run: the older admission (slot 1) must get it
    work = sched.plan_step(8)
    assert [w.slot for w in work] == [1]
    assert work[0].kind == "prefill" and work[0].count == 8
    # with budget for both, the older admission still plans first
    sched.slots[1].pending = np.arange(8, dtype=np.int64)
    work = sched.plan_step(16)
    assert [w.slot for w in work] == [1, 0]
    assert all(w.kind == "prefill" and w.count == 8 for w in work)


# ---------------------------------------------------------------------------
# chunked prefill into non-contiguous pages (satellite): parity vs the
# contiguous-cache path and vs the full forward, fp32+bf16
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cache_dtype,atol", [("float32", 5e-5),
                                              ("bfloat16", 0.08)])
def test_chunked_prefill_into_pages_matches_contiguous(cache_dtype, atol):
    pt.seed(13)
    cfg = _tiny_cfg()
    m = GPTStackedForPretraining(cfg)
    m.eval()
    ids_np = _prompt(cfg, s=12, seed=3)
    ids = pt.to_tensor(ids_np, dtype="int64")
    full = m(ids).numpy()

    # contiguous-cache chunked prefill (the PR-2 path)
    ckv = m.new_kv_cache(1, 64, dtype=cache_dtype)
    c_pre = m(ids[:, :4], kv_cache=ckv, cache_index=0).numpy()
    c_mid = m(ids[:, 4:9], kv_cache=ckv, cache_index=4).numpy()
    c_tail = m(ids[:, 9:12], kv_cache=ckv, cache_index=9).numpy()

    # paged: deliberately OUT-OF-ORDER page ids (non-contiguous pool walk)
    pcache = m.new_paged_kv_cache(10, 16, dtype=cache_dtype)
    tbl = pt.to_tensor(np.array([[7, 2, 9, 4]], np.int32))

    def step(lo, hi):
        pos = pt.to_tensor(np.array([lo], np.int32))
        return m._paged_lm_logits(ids[:, lo:hi], pcache, tbl, pos).numpy()

    p_pre, p_mid, p_tail = step(0, 4), step(4, 9), step(9, 12)
    # paged vs contiguous agree far tighter than either is to the full
    # forward — except the FIRST chunk under bf16, where the contiguous
    # pos==0 fast path attends the fresh (unrounded) K/V while the paged
    # path reads the bf16-rounded pool: one bf16 rounding apart
    ctg_atol = 5e-5 if cache_dtype == "float32" else 5e-3
    for got, ctg, lo, hi in ((p_pre, c_pre, 0, 4), (p_mid, c_mid, 4, 9),
                             (p_tail, c_tail, 9, 12)):
        np.testing.assert_allclose(got, full[:, lo:hi], rtol=1e-2, atol=atol)
        np.testing.assert_allclose(got, ctg, rtol=1e-3, atol=ctg_atol)

    # and single-token decode over the paged chunks stays consistent
    dec = m._paged_lm_logits(
        pt.to_tensor(ids_np[:, :1], dtype="int64"), pcache, tbl,
        pt.to_tensor(np.array([12], np.int32))).numpy()
    assert np.isfinite(dec).all()


# ---------------------------------------------------------------------------
# the acceptance churn test: retrace-free continuous batching, outputs
# token-for-token equal to single-shot greedy generate()
# ---------------------------------------------------------------------------

def test_continuous_batching_churn_matches_generate():
    pt.seed(0)
    cfg = _tiny_cfg()
    m = GPTStackedForPretraining(cfg)
    m.eval()
    rng = np.random.RandomState(1)
    lengths = [3, 17, 5, 9, 14, 4, 19, 7, 11, 6] * 2   # 20 varying lengths
    prompts = [rng.randint(0, cfg.vocab_size, (s,)) for s in lengths]
    new_toks = [int(rng.randint(2, 9)) for _ in prompts]

    refs = []
    for p, n in zip(prompts, new_toks):
        out = m.generate(pt.to_tensor(p[None, :], dtype="int64"),
                         max_new_tokens=n, max_seq_len=64,
                         cache_dtype="float32")
        refs.append(np.asarray(out.numpy())[0])

    serving.reset_serve_trace_counts()
    eng = ServingEngine(m, num_slots=4, page_size=16, max_context=64,
                        cache_dtype="float32", prefill_token_budget=8)
    reqs, it, submitted = [], iter(zip(prompts, new_toks)), 0
    while submitted < len(prompts) or eng.queue.depth \
            or eng.scheduler.active_slots:
        # arrivals interleave with completions: 2 new requests per step
        for _ in range(2):
            try:
                p, n = next(it)
            except StopIteration:
                break
            reqs.append(eng.submit(p, n))
            submitted += 1
        eng.step()

    tc = serving.serve_trace_counts()
    # step bodies run ONLY while tracing (scout + jit trace = 2 per
    # compiled program): <= 2 means the fused step compiled at most once —
    # mixed prefill/decode traffic shares ONE program for the whole run
    assert tc["fused"] <= 2, tc
    assert eng.compiled_programs == 1

    for r, ref in zip(reqs, refs):
        assert r.finished
        got = r.output_ids()
        assert np.array_equal(got, ref), (
            f"request {r.id}: {got[len(r.prompt):]} vs "
            f"{ref[len(r.prompt):]}")
    # everything retired: every page back in the pool
    assert eng.allocator.used_pages == 0
    assert eng.scheduler.active_slots == 0
    mets = eng.metrics()
    assert mets["completed"] == len(prompts)
    assert mets["tokens"] == sum(new_toks)


# engine geometry, prompt lengths, new tokens a request.  Each case after
# the first aims the pool write's indexing (one row per token and head at
# ``((l*P + page)*H + h)*page_size + offset`` of the flat pool) at an edge
_MIXED_CASES = {
    # the tiny budget forces multi-step prefills to overlap other slots'
    # decode: every step really mixes phases
    "interleaved": (dict(num_slots=2, page_size=16, prefill_token_budget=6),
                    (4, 17, 7, 21, 11, 5), 4),
    # one chunk of 20 prompt tokens spans three 8-position pages
    "chunk_crosses_pages": (
        dict(num_slots=2, page_size=8, prefill_token_budget=20), (21, 13), 4),
    # four slots of six never seat and most of the budget is padding: all
    # of those rows sink into page 0 of every layer, indices repeating
    "idle_slots_and_padding": (
        dict(num_slots=6, page_size=16, prefill_token_budget=16), (5, 3), 4),
    # a pool with no page to spare: both requests reserve four pages, so
    # the last page of the pool (and of the last layer) is written
    "last_page_of_last_layer": (
        dict(num_slots=2, page_size=16, prefill_token_budget=16,
             num_pages=9), (58, 59), 5),
}


def _alone_refs(m, prompts, n_new, cache_dtype):
    """Each prompt served ALONE, prefilled in one chunk: what a model
    whose weights ``quantize_for_serving`` took gives for it (its
    contiguous-cache ``generate()`` needs the fp weights).  The int8
    projections scale activations per row, so a token's result does not
    depend on what shares its step."""
    eng = ServingEngine(m, num_slots=1, page_size=16, max_context=64,
                        cache_dtype=cache_dtype, prefill_token_budget=64)
    refs = []
    for p in prompts:
        r = eng.submit(p, n_new)
        eng.run_until_idle(max_steps=200)
        assert r.finished
        refs.append(r.output_ids())
    eng.close()
    return refs


@pytest.mark.parametrize("weights", ["as_built", "int8"])
@pytest.mark.parametrize("cache_dtype", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("case", list(_MIXED_CASES))
def test_fused_mixed_step_parity(weights, cache_dtype, case):
    """The fused mixed prefill/decode step across interleaved arrivals:
    greedy output token-for-token equal to each request on its own
    (single-shot generate(); for int8 weights an engine that serves one
    request at a time) on fp32, bf16 AND int8 pools, with the weights as
    built AND after ``quantize_for_serving``, in every geometry of
    ``_MIXED_CASES``."""
    from paddle_tpu.quantization import quantize_for_serving

    eng_kw, lengths, n_new = _MIXED_CASES[case]
    # an int8 pool (pages quantized on write, scale sidecars indexed by
    # the same offset page ids) reproduces the fp32 reference
    ref_dtype = "float32" if cache_dtype == "int8" else cache_dtype
    pt.seed(3)
    cfg = _tiny_cfg()
    m = GPTStackedForPretraining(cfg)
    m.eval()
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, cfg.vocab_size, (s,)) for s in lengths]
    if weights == "int8":
        refs = _alone_refs(quantize_for_serving(m), prompts, n_new, ref_dtype)
    else:
        refs = [np.asarray(
            m.generate(pt.to_tensor(p[None, :], dtype="int64"),
                       max_new_tokens=n_new, max_seq_len=64,
                       cache_dtype=ref_dtype).numpy())[0] for p in prompts]
    eng = ServingEngine(m, max_context=64, cache_dtype=cache_dtype, **eng_kw)
    pool = eng.cache
    # the write's row index is int32
    assert (pool.num_layers * pool.num_pages * pool.num_heads
            * pool.page_size) < 2 ** 31
    reqs, it, touched = [], iter(prompts), set()
    while len(reqs) < len(prompts) or eng.queue.depth \
            or eng.scheduler.active_slots:
        try:
            reqs.append(eng.submit(next(it), n_new))
        except StopIteration:
            pass
        met = eng.step()
        assert met["pages_used"] <= eng.allocator.capacity
        touched |= set(eng.allocator._allocated)
    for r, ref in zip(reqs, refs):
        assert r.finished
        assert np.array_equal(r.output_ids(), ref), (
            weights, cache_dtype, r.id)
    assert eng.compiled_programs == 1
    assert eng.allocator.used_pages == 0
    # every layer wrote the same pages, all of them pages the allocator
    # dealt (or the null page, the sink): a wrong offset lands elsewhere
    for side in (pool.k, pool.v):
        written = np.abs(np.asarray(side.numpy(), np.float32)).sum(
            axis=(2, 3, 4)) > 0                                   # [L, P]
        assert written[0, 1:].any() and (written == written[0]).all(), case
        assert set(np.flatnonzero(written[0, 1:]) + 1) <= touched, case
    if case == "last_page_of_last_layer":
        assert pool.num_pages - 1 in touched and written[-1, -1]
    eng.close()


def test_out_of_pages_admission_backpressures():
    """A pool too small for every request at once must queue the overflow
    (never corrupt live slots) and still finish everything as pages free."""
    pt.seed(5)
    cfg = _tiny_cfg()
    m = GPTStackedForPretraining(cfg)
    m.eval()
    rng = np.random.RandomState(3)
    # 4 slots, but only 6 allocatable pages and every request reserves 2
    # (20 prompt + 3 new = 23 tokens, 16/page): at most 3 seated at once —
    # the pool, not the slot count, is the binding constraint
    eng = ServingEngine(m, num_slots=4, page_size=16, max_context=64,
                        num_pages=7, cache_dtype="float32")
    prompts = [rng.randint(0, cfg.vocab_size, (20,)) for _ in range(6)]
    refs = [np.asarray(m.generate(pt.to_tensor(p[None, :], dtype="int64"),
                                  max_new_tokens=3, max_seq_len=64,
                                  cache_dtype="float32").numpy())[0]
            for p in prompts]
    reqs = [eng.submit(p, 3) for p in prompts]
    saw_backpressure = False
    peak_used = 0
    steps = 0
    while eng.queue.depth or eng.scheduler.active_slots:
        met = eng.step()
        steps += 1
        peak_used = max(peak_used, met["pages_used"])
        assert met["pages_used"] <= eng.allocator.capacity
        if met["queue_depth"] > 0 and met["active_slots"] > 0:
            saw_backpressure = True
        assert steps < 200, "engine made no progress"
    assert saw_backpressure, "pool never backpressured despite 6x2 > 6 pages"
    assert peak_used == 6                     # the pool really saturated
    for r, ref in zip(reqs, refs):
        assert np.array_equal(r.output_ids(), ref)
    assert eng.allocator.used_pages == 0      # blocks freed on completion
    # freed pages get REUSED: total admitted pages > capacity
    assert eng.metrics()["completed"] == 6


def test_invocation_counters_exact():
    """``fused_steps`` counts only ticks that actually dispatched the
    fused program (bench.py's serving roofline denominator),
    ``prefill_tokens`` counts the prompt tokens that piggybacked on those
    steps, and the ragged grid-occupancy means are populated."""
    pt.seed(0)
    cfg = _tiny_cfg()
    m = GPTStackedForPretraining(cfg)
    m.eval()
    rng = np.random.RandomState(0)
    eng = ServingEngine(m, num_slots=2, page_size=16, max_context=64,
                        cache_dtype="float32")
    try:
        m0 = eng.metrics()
        assert m0["fused_steps"] == 0 and m0["prefill_tokens"] == 0
        eng.step()  # idle tick: no seated work, no program ran
        assert eng.metrics()["fused_steps"] == 0
        assert eng.metrics()["steps"] == 1
        reqs = [eng.submit(rng.randint(0, cfg.vocab_size, (plen,)), 3)
                for plen in (20, 8)]
        eng.run_until_idle()
        mets = eng.metrics()
        assert all(len(r.tokens) == 3 for r in reqs)
        # every prompt token rode a fused step exactly once
        assert mets["prefill_tokens"] == 28
        # every fused dispatch is a tick, but not every tick dispatched
        # (the idle tick above never ran the program)
        assert 0 < mets["fused_steps"] < mets["steps"]
        assert 0.0 < mets["mean_grid_occupancy"] < 1.0
        assert 0.0 < mets["mean_q_row_occupancy"] <= 1.0
        # the arrays pad to their capacity, the launch does not: every
        # grid step of every dispatched kernel was a real work item
        assert mets["launched_items"] == mets["work_items"] > 0
        assert mets["launched_items"] < mets["work_capacity"]
        # a work item moves every head of its page (the tiny geometry is
        # far inside the kernel's VMEM budget): a grid step an item
        assert mets["ragged_heads_per_block"] == cfg.num_heads
        assert mets["launched_grid_steps"] == mets["launched_items"]
        # host-packing padding cost (cost_model.ragged_padding_waste):
        # a decode token fills 1 of token_block rows, so a decode-heavy
        # run must report padded rows and the matching padded-away flops
        assert mets["padded_rows"] > 0
        assert mets["padded_flops"] > 0
    finally:
        eng.close()


def test_boundary_length_requests():
    """prompt + max_new == max_context (prefill padding reaches the table
    edge) and a prefill-only request (max_new=1, never decodes) both match
    generate()."""
    pt.seed(0)
    cfg = _tiny_cfg()
    m = GPTStackedForPretraining(cfg)
    m.eval()
    rng = np.random.RandomState(0)
    eng = ServingEngine(m, num_slots=2, page_size=16, max_context=64,
                        cache_dtype="float32")
    for s0, n in ((62, 2), (1, 1), (16, 4)):   # incl. exact-page prompt
        p = rng.randint(0, cfg.vocab_size, (s0,))
        ref = np.asarray(m.generate(pt.to_tensor(p[None, :], dtype="int64"),
                                    max_new_tokens=n, max_seq_len=64,
                                    cache_dtype="float32").numpy())[0]
        r = eng.submit(p, n)
        eng.run_until_idle()
        assert np.array_equal(r.output_ids(), ref), (s0, n)
    assert eng.allocator.used_pages == 0


def test_requests_too_big_rejected_at_submit():
    cfg = _tiny_cfg()
    m = GPTStackedForPretraining(cfg)
    m.eval()
    eng = ServingEngine(m, num_slots=2, page_size=16, max_context=64,
                        num_pages=4, cache_dtype="float32")
    with pytest.raises(ValueError, match="exceeds max_context"):
        eng.submit(np.zeros(60, np.int64), 10)
    with pytest.raises(ValueError, match="pool holds only"):
        eng.submit(np.zeros(50, np.int64), 14)    # 4 pages > capacity 3
    with pytest.raises(ValueError, match="at least one token"):
        eng.submit(np.zeros(0, np.int64), 4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(np.zeros(4, np.int64), 0)


def test_eos_retires_slot_and_frees_pages():
    pt.seed(9)
    cfg = _tiny_cfg()
    m = GPTStackedForPretraining(cfg)
    m.eval()
    p = _prompt(cfg, s=6, seed=4)[0]
    base = np.asarray(m.generate(pt.to_tensor(p[None, :], dtype="int64"),
                                 max_new_tokens=6, max_seq_len=64,
                                 cache_dtype="float32").numpy())[0]
    eos = int(base[6 + 2])                    # greedy token at step 2
    eng = ServingEngine(m, num_slots=2, page_size=16, max_context=64,
                        cache_dtype="float32")
    req = eng.submit(p, 6, eos_token_id=eos)
    eng.run_until_idle()
    assert req.finished
    assert req.tokens[-1] == eos
    assert len(req.tokens) <= 6
    assert eng.allocator.used_pages == 0


def test_streaming_token_callbacks_in_order():
    pt.seed(11)
    cfg = _tiny_cfg()
    m = GPTStackedForPretraining(cfg)
    m.eval()
    seen = []
    eng = ServingEngine(m, num_slots=2, page_size=16, max_context=64,
                        cache_dtype="float32")
    req = eng.submit(_prompt(cfg, s=5, seed=6)[0], 5,
                     on_token=lambda r, t: seen.append((r.id, t)))
    eng.run_until_idle()
    assert [t for _, t in seen] == req.tokens
    assert all(rid == req.id for rid, _ in seen)
    assert req.state == serving.RequestState.DONE


def test_per_request_sampling_mix_and_reproducibility():
    """Greedy and sampling requests share ONE compiled step; greedy rows
    still match single-shot generate(); sampling is in-vocab and
    reproducible under the same global seed."""
    cfg = _tiny_cfg()
    pt.seed(0)
    m = GPTStackedForPretraining(cfg)
    m.eval()
    pg = _prompt(cfg, s=7, seed=8)[0]
    ps = _prompt(cfg, s=5, seed=9)[0]
    ref = np.asarray(m.generate(pt.to_tensor(pg[None, :], dtype="int64"),
                                max_new_tokens=5, max_seq_len=64,
                                cache_dtype="float32").numpy())[0]

    def run():
        pt.seed(1234)
        eng = ServingEngine(m, num_slots=2, page_size=16, max_context=64,
                            cache_dtype="float32")
        rg = eng.submit(pg, 5)                    # greedy
        rs = eng.submit(ps, 6, sampling=SamplingParams(
            do_sample=True, temperature=0.8, top_k=50, top_p=0.9))
        eng.run_until_idle()
        return rg.output_ids(), rs.output_ids()

    g1, s1 = run()
    g2, s2 = run()
    assert np.array_equal(g1, ref)                # greedy unaffected by mix
    assert np.array_equal(g1, g2)
    assert np.array_equal(s1, s2), "sampling must be seed-reproducible"
    assert (s1 >= 0).all() and (s1 < cfg.vocab_size).all()


def test_engine_close_releases_pool_and_rejects_use():
    cfg = _tiny_cfg()
    m = GPTStackedForPretraining(cfg)
    m.eval()
    eng = ServingEngine(m, num_slots=2, page_size=16, max_context=32,
                        cache_dtype="float32")
    k = eng.cache.k
    eng.close()
    assert k._value.is_deleted()
    with pytest.raises(RuntimeError, match="closed"):
        eng.submit(np.zeros(4, np.int64), 2)
    with pytest.raises(RuntimeError, match="closed"):
        eng.step()


# ---------------------------------------------------------------------------
# graph-lint regression: the paged decode step stays GL001/GL004-clean
# ---------------------------------------------------------------------------

def test_serving_step_bf16_stays_gl001_clean():
    """A pure-bf16 model's paged decode step must not silently promote
    its projections to fp32 (same regression class PR 3 fixed for the
    contiguous decode path)."""
    from paddle_tpu import analysis

    analysis.clear_reports()
    pt.set_flags({"FLAGS_graph_lint": True})
    try:
        pt.seed(0)
        cfg = _tiny_cfg()
        m = GPTStackedForPretraining(cfg)
        pt.amp.decorate(m, level="O2", dtype="bfloat16")
        m.eval()
        eng = ServingEngine(m, num_slots=2, page_size=16, max_context=32,
                            cache_dtype="bfloat16")
        eng.submit(_prompt(cfg, s=5, seed=1)[0], 3)
        eng.run_until_idle()
        reps = eng.lint_reports()
        assert reps, "FLAGS_graph_lint on but no serving lint reports"
        bad = [f for r in reps for f in r.findings if f.code == "GL001"]
        assert bad == [], "\n".join(f.render() for f in bad)
    finally:
        pt.set_flags({"FLAGS_graph_lint": False})
        analysis.clear_reports()


def test_serving_step_donates_pool_gl004_clean():
    """The page pool is mutated captured state: jit.to_static must donate
    it (no GL004 double-buffer finding on pool-sized inputs)."""
    from paddle_tpu import analysis

    analysis.clear_reports()
    pt.set_flags({"FLAGS_graph_lint": True})
    try:
        pt.seed(0)
        cfg = _tiny_cfg()
        m = GPTStackedForPretraining(cfg)
        m.eval()
        # 300 pages x 4 heads x 16 x 16 fp32 = ~1.2 MiB per pool tensor:
        # big enough for the linter's donation_min_bytes candidate floor
        eng = ServingEngine(m, num_slots=2, page_size=16, max_context=32,
                            num_pages=300, cache_dtype="float32")
        eng.submit(_prompt(cfg, s=5, seed=1)[0], 3)
        eng.run_until_idle()
        reps = eng.lint_reports()
        assert reps
        bad = [f for r in reps for f in r.findings if f.code == "GL004"]
        assert bad == [], "\n".join(f.render() for f in bad)
    finally:
        pt.set_flags({"FLAGS_graph_lint": False})
        analysis.clear_reports()


# ---------------------------------------------------------------------------
# satellite: LRU eviction / clear_decode_cache release KV-cache HBM
# ---------------------------------------------------------------------------

def test_lru_eviction_releases_cache_buffers():
    pt.seed(14)
    cfg = _tiny_cfg()
    m = GPTStackedForPretraining(cfg)
    m.eval()
    ids = pt.to_tensor(_prompt(cfg, b=2, s=6), dtype="int64")
    m.generate(ids, max_new_tokens=2, max_seq_len=32, cache_dtype="float32")
    first = m.__dict__["_decode_engines"][(2, 32, "float32", False, 0,
                                           False)]
    held = first.cache.k._value    # buffer to be evicted, ref held here
    for b in (48, 64, 80, 96):        # four more shapes: evicts the first
        m.generate(ids, max_new_tokens=2, max_seq_len=b,
                   cache_dtype="float32")
    engines = m.__dict__["_decode_engines"]
    assert len(engines) == generation._MAX_ENGINES
    assert (2, 32, "float32", False, 0, False) not in engines
    assert held.is_deleted(), \
        "evicted engine's KV buffers must be deleted eagerly, not GC'd"
    # clear_decode_cache releases every remaining engine's buffers
    remaining = [e.cache.k._value for e in engines.values()]
    m.clear_decode_cache()
    assert "_decode_engines" not in m.__dict__
    assert all(v.is_deleted() for v in remaining)


def test_generate_retries_on_engine_released_race():
    """A caller that looked an engine up just before eviction deleted its
    buffers must fetch a fresh engine (the `released` flag under the
    engine lock), not dispatch into deleted arrays."""
    pt.seed(7)
    cfg = _tiny_cfg()
    m = GPTStackedForPretraining(cfg)
    m.eval()
    ids = pt.to_tensor(_prompt(cfg, b=2, s=6), dtype="int64")
    ref = m.generate(ids, max_new_tokens=3, max_seq_len=32,
                     cache_dtype="float32").numpy()
    # simulate the evictor winning the race: release the cached engine
    # (buffers deleted, flag set) while it is still in the registry
    eng = m.__dict__["_decode_engines"][(2, 32, "float32", False, 0, False)]
    eng.release()
    assert eng.released and eng.cache.k._value.is_deleted()
    out = m.generate(ids, max_new_tokens=3, max_seq_len=32,
                     cache_dtype="float32").numpy()
    assert np.array_equal(out, ref)


def test_kv_cache_release_idempotent():
    cfg = _tiny_cfg()
    m = GPTStackedForPretraining(cfg)
    cache = m.new_kv_cache(1, 32, dtype="float32")
    cache.release()
    cache.release()                   # second release must not raise
    assert cache.k._value.is_deleted()


# ---------------------------------------------------------------------------
# satellite: PredictorPool concurrency
# ---------------------------------------------------------------------------

def _decode_pool(m, size):
    config = inference.Config().set_causal_lm_model(m)
    config.enable_causal_lm_decode(max_new_tokens=4, max_seq_len=64,
                                   cache_dtype="float32")
    return inference.PredictorPool(config, size)


def test_predictor_pool_concurrent_acquire_run_release():
    """Concurrent acquire/run/release through the pool: every thread gets
    an exclusive predictor, decode outputs stay correct (the shared decode
    engine serializes on its cache lock), nothing deadlocks."""
    pt.seed(2)
    cfg = _tiny_cfg()
    m = GPTStackedForPretraining(cfg)
    m.eval()
    ids = _prompt(cfg, b=2, s=6)
    ref = m.generate(pt.to_tensor(ids, dtype="int64"), max_new_tokens=4,
                     max_seq_len=64, cache_dtype="float32").numpy()
    pool = _decode_pool(m, 3)
    in_flight, in_flight_lock, errors, results = set(), threading.Lock(), [], []

    def work():
        try:
            for _ in range(3):
                p = pool.acquire(timeout=30)
                with in_flight_lock:
                    assert id(p) not in in_flight, "predictor handed twice"
                    in_flight.add(id(p))
                try:
                    out = p.run([pt.to_tensor(ids, dtype="int64")])
                    results.append(np.asarray(out[0].numpy()))
                finally:
                    with in_flight_lock:
                        in_flight.discard(id(p))
                    pool.release(p)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=work) for _ in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    assert not errors, errors
    assert len(results) == 18
    for out in results:
        assert np.array_equal(out, ref)


def test_predictor_pool_release_guards():
    pt.seed(2)
    m = GPTStackedForPretraining(_tiny_cfg())
    m.eval()
    pool = _decode_pool(m, 2)
    p = pool.acquire()
    pool.release(p)
    with pytest.raises(ValueError, match="not checked out"):
        pool.release(p)               # double release
    with pytest.raises(TimeoutError):
        a = pool.acquire()
        b = pool.acquire()
        try:
            pool.acquire(timeout=0.05)
        finally:
            pool.release(a)
            pool.release(b)
    with pool.predictor() as q:       # context manager round-trip
        assert q is not None


# ---------------------------------------------------------------------------
# inference.Config serving mode
# ---------------------------------------------------------------------------

def test_predictor_serving_mode_matches_generate():
    pt.seed(2)
    cfg = _tiny_cfg()
    m = GPTStackedForPretraining(cfg)
    m.eval()
    ids = _prompt(cfg, b=3, s=6)
    ref = m.generate(pt.to_tensor(ids, dtype="int64"), max_new_tokens=5,
                     max_seq_len=64, cache_dtype="float32").numpy()
    config = inference.Config().set_causal_lm_model(m)
    config.enable_serving_mode(max_new_tokens=5, num_slots=4, page_size=16,
                               max_context=64, cache_dtype="float32")
    assert "serving_mode" in config.summary()
    predictor = inference.create_predictor(config)
    h = predictor.get_input_handle("x0")
    h.copy_from_cpu(ids)
    predictor.run()
    out = predictor.get_output_handle(
        predictor.get_output_names()[0]).copy_to_cpu()
    assert np.array_equal(out, ref)


def test_serving_mode_validation():
    m = GPTStackedForPretraining(_tiny_cfg())
    config = inference.Config(str("/nonexistent"))
    config.enable_serving_mode(max_new_tokens=2)
    with pytest.raises(RuntimeError, match="live model"):
        inference.create_predictor(config)
    config2 = inference.Config().set_causal_lm_model(m)
    config2.enable_serving_mode(max_new_tokens=2)
    with pytest.raises(RuntimeError, match="mutually exclusive"):
        config2.enable_causal_lm_decode(max_new_tokens=2)
    config3 = inference.Config().set_causal_lm_model(m)
    config3.enable_causal_lm_decode(max_new_tokens=2)
    with pytest.raises(RuntimeError, match="mutually exclusive"):
        config3.enable_serving_mode(max_new_tokens=2)


# ---------------------------------------------------------------------------
# one step in flight: step N+1 is enqueued while step N runs
# (docs/serving.md "One step in flight")
# ---------------------------------------------------------------------------

class _SerialEngine(ServingEngine):
    """The serial oracle: the pipeline held at depth 0.  Every tick lands
    the step it enqueued before it returns, so nothing is ever planned
    against a step that is not harvested.  A test's subclass, not a mode of
    the engine: the engine has none."""

    def _dispatch_step(self, work):
        super()._dispatch_step(work)
        self._drain()


def _family(name):
    """(model, engine keywords) of one decoder family, all tiny."""
    if name == "gpt":
        pt.seed(0)
        m = GPTStackedForPretraining(_tiny_cfg())
        kw = dict(page_size=16, max_context=64)
    elif name == "hybrid":               # HybridPagedCache: state in the pages
        from paddle_tpu.models import Lfm2StackedForCausalLM, lfm2_tiny

        pt.seed(11)
        m = Lfm2StackedForCausalLM(lfm2_tiny())
        kw = dict(page_size=8, max_context=64)
    else:                                # SlotStateCache: state in the slots
        from paddle_tpu.models import Phi4FlashForCausalLM, phi4flash_tiny

        pt.seed(11)
        m = Phi4FlashForCausalLM(phi4flash_tiny())
        kw = dict(page_size=16, max_context=256)
    m.eval()
    return m, dict(kw, cache_dtype="float32", prefill_token_budget=5)


def _drive(engine, plan, arrive=2):
    """Submit ``plan`` (keywords of ``submit``) ``arrive`` a tick while the
    engine steps, to the end; every request's tokens, in submission order."""
    reqs, todo = [], list(plan)
    while todo or engine.queue.depth or engine.scheduler.active_slots:
        for kw in todo[:arrive]:
            reqs.append(engine.submit(**kw))
        del todo[:arrive]
        engine.step()
    assert all(r.state == "DONE" for r in reqs), [r.state for r in reqs]
    assert engine.allocator.used_pages == 0
    return [list(r.tokens) for r in reqs]


_FAMILIES = ("gpt", "hybrid", "slot_state")


@pytest.mark.parametrize("family", _FAMILIES)
def test_one_step_in_flight_emits_the_serial_engines_tokens_greedy(family):
    """Ten requests of unequal lengths through three slots, arriving while
    others decode, a budget of 5 so that prefill chunks and decode rows share
    every step: the engine that enqueues step N+1 before it reads step N
    emits what the engine that reads each step first emits, token for token,
    from the SAME one program."""
    m, kw = _family(family)
    rng = np.random.RandomState(3)
    plan = [dict(prompt=rng.randint(0, m.config.vocab_size, (s,)),
                 max_new_tokens=n)
            for s, n in zip((3, 17, 5, 9, 14, 4, 19, 7, 11, 6),
                            (4, 7, 2, 9, 5, 8, 3, 6, 1, 5))]
    serial = _SerialEngine(m, num_slots=3, **kw)
    want = _drive(serial, plan)
    assert serial.metrics()["overlapped_steps"] == 0
    serial.close()
    serving.reset_serve_trace_counts()
    eng = ServingEngine(m, num_slots=3, **kw)
    got = _drive(eng, plan)
    assert got == want
    mt = eng.metrics()
    assert mt["tokens"] == sum(len(t) for t in want)
    assert mt["prefill_tokens"] == sum(len(p["prompt"]) for p in plan)
    assert mt["voided_rows"] == 0          # every request ends by its length
    assert mt["overlapped_steps"] >= mt["fused_steps"] - 3
    assert serving.serve_trace_counts()["fused"] <= 2
    assert eng.compiled_programs == 1
    eng.close()


@pytest.mark.parametrize("family", _FAMILIES)
def test_one_step_in_flight_emits_the_serial_engines_tokens_sampled(family):
    """Seeded sampling beside greedy rows: three requests seated together,
    prompts of 4 to 19 tokens under a budget of 5 (so one decodes while
    another still prefills) and unequal answer lengths.  Both engines run
    the same steps in the same order, so each draw meets the same key."""
    m, kw = _family(family)
    rng = np.random.RandomState(5)
    hot = SamplingParams(do_sample=True, temperature=0.8, top_k=50, top_p=0.9)
    plan = [dict(prompt=rng.randint(0, m.config.vocab_size, (s,)),
                 max_new_tokens=n, sampling=sp)
            for s, n, sp in ((4, 9, hot), (19, 5, None), (11, 7, hot))]

    def run(cls):
        pt.seed(1234)
        eng = cls(m, num_slots=3, **kw)
        out = _drive(eng, plan, arrive=3)
        mt = eng.metrics()
        eng.close()
        return out, mt

    want, _ = run(_SerialEngine)
    got, mt = run(ServingEngine)
    assert got == want
    assert mt["voided_rows"] == 0
    assert mt["overlapped_steps"] == mt["fused_steps"] - 1


@pytest.mark.parametrize("family", _FAMILIES)
def test_requests_that_end_by_eos_match_the_serial_engine(family):
    """Fourteen requests through three slots, most with an EOS drawn from
    their own greedy answer: each such end leaves a void run in the step
    enqueued behind it, whose writes (K/V, a page's tail, a slot's state
    row and ring) must not reach the request that takes the slot and the
    pages next.  The prefix cache is on where the model has one."""
    m, kw = _family(family)
    kw = dict(kw, num_slots=3, prefix_cache=family != "slot_state")
    rng = np.random.RandomState(100)
    plan = [dict(prompt=rng.randint(0, m.config.vocab_size,
                                    (int(rng.randint(2, 24)),)),
                 max_new_tokens=int(rng.randint(3, 14))) for _ in range(14)]
    serial = _SerialEngine(m, **kw)
    answers = _drive(serial, plan)
    serial.close()
    for p, toks in zip(plan, answers):
        if rng.rand() < 0.6:
            p["eos_token_id"] = int(toks[rng.randint(0, len(toks))])
    serial = _SerialEngine(m, **kw)
    want = _drive(serial, plan)
    serial.close()
    eng = ServingEngine(m, **kw)
    assert _drive(eng, plan) == want
    mt = eng.metrics()
    assert mt["voided_rows"] >= 5, mt["voided_rows"]
    assert mt["tokens"] == sum(len(t) for t in want)
    eng.close()


def test_an_eos_in_step_n_voids_the_slots_row_in_step_n_plus_1():
    """Only step N's result can say that a request hit its EOS: step N+1 is
    enqueued with a row for it.  That row is void: never emitted, never
    counted, its pages returned once, and the request that gets those pages
    next reads nothing the void row wrote."""
    pt.seed(9)
    cfg = _tiny_cfg()
    m = GPTStackedForPretraining(cfg)
    m.eval()

    def ref(p, n):
        return np.asarray(m.generate(pt.to_tensor(p[None, :], dtype="int64"),
                                     max_new_tokens=n, max_seq_len=64,
                                     cache_dtype="float32").numpy())[0]

    pa, pb, pc = (_prompt(cfg, s=s, seed=k)[0]
                  for s, k in ((6, 4), (20, 5), (9, 6)))
    eos = int(ref(pa, 6)[6 + 2])              # a's greedy token at step 2
    # a pool of four pages: a holds one, b three, so c waits for a's page
    eng = ServingEngine(m, num_slots=2, page_size=16, max_context=64,
                        cache_dtype="float32", num_pages=5)
    ra = eng.submit(pa, 6, eos_token_id=eos)
    rb = eng.submit(pb, 20)
    rc = eng.submit(pc, 7)
    freed = []
    free = eng.allocator.free
    eng.allocator.free = lambda pages: (freed.extend(pages), free(pages))[1]
    eng.run_until_idle()
    assert ra.finished and ra.tokens[-1] == eos and len(ra.tokens) <= 3
    assert np.array_equal(rb.output_ids(), ref(pb, 20))
    assert np.array_equal(rc.output_ids(), ref(pc, 7))
    mt = eng.metrics()
    assert mt["voided_rows"] == 1
    assert mt["tokens"] == len(ra.tokens) + 20 + 7
    # a's page went back once and came out again as c's: five pages freed
    # by three retirements, the one page of a and c twice
    assert len(freed) == 5 and len(set(freed)) == 4, freed
    assert eng.allocator.used_pages == 0
    eng.close()


def test_a_saturated_run_overlaps_and_a_verify_step_never_does():
    """With every slot seated every step has a successor: nearly all steps
    are enqueued behind an unread one.  The speculative engine's verify
    step decides the next positions by its accepted count, so nothing can
    be planned ahead of it, and the code sees that by itself."""
    from paddle_tpu.serving import SpeculativeEngine

    pt.seed(0)
    cfg = _tiny_cfg()
    m = GPTStackedForPretraining(cfg)
    m.eval()
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, cfg.vocab_size, (s,))
               for s in (5, 9, 7, 12, 17, 4, 11, 6) * 3]
    kw = dict(num_slots=4, page_size=16, max_context=64,
              cache_dtype="float32")
    eng = ServingEngine(m, **kw)
    want = eng.generate_batch(prompts, 12)
    mt = eng.metrics()
    assert mt["overlapped_steps"] / mt["fused_steps"] > 0.9, mt
    eng.close()
    spec = SpeculativeEngine(m, m, spec_k=3, **kw)
    got = spec.generate_batch(prompts, 12)
    for g, w in zip(got, want):
        assert np.array_equal(g, w)
    mt = spec.metrics()
    assert mt["fused_steps"] > 0 and mt["overlapped_steps"] == 0
    assert mt["voided_rows"] == 0
    spec.close()


def test_a_plan_ahead_of_a_step_in_flight_takes_that_step_for_done():
    """``plan_step(ahead=)``: positions moved on by the step's counts, its
    chunk off the pending prompt, a request at its last token left out, a
    run of another seating ignored; the mirrors untouched."""
    from paddle_tpu.serving.admission import AdmissionScheduler

    class Req:
        def __init__(self, n_tokens, max_new):
            self.tokens, self.max_new_tokens = [0] * n_tokens, max_new

    sched = AdmissionScheduler(4, 4, 16, BlockAllocator(17))
    a = sched.try_admit(Req(0, 8), 30)        # mid-prefill: 12 pending
    sched.slots[a].pending = np.arange(12)
    b = sched.try_admit(Req(3, 8), 30)        # decoding at position 9
    sched.advance(b, 9)
    c = sched.try_admit(Req(7, 8), 30)        # emits its last token next
    sched.advance(c, 20)
    first = sched.plan_step(8)
    assert [(w.slot, w.kind, w.count, w.base, w.chained) for w in first] == [
        (a, "prefill", 8, 0, False), (b, "decode", 1, 9, False),
        (c, "decode", 1, 20, False)]
    ahead = sched.plan_step(8, first)
    assert [(w.slot, w.kind, w.count, w.base, w.completes, w.chained)
            for w in ahead] == [(a, "prefill", 4, 8, True, False),
                                (b, "decode", 1, 10, False, True)]
    assert sched.slots[a].pos == 0 and len(sched.slots[a].pending) == 12
    # slot b seated again while its run is in flight: nothing carries over
    sched.retire(b)
    b2 = sched.try_admit(Req(0, 8), 30)
    sched.slots[b2].pending = np.arange(3)
    assert b2 == b and sched.live(first[1]) is None
    again = {w.slot: w for w in sched.plan_step(8, first)}
    assert (again[b].kind, again[b].base, again[b].count) == ("prefill", 0, 3)
