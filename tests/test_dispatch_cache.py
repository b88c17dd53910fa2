"""Eager op compilation cache (core/op_cache.py + ops/dispatch.py).

Covers the ISSUE-1 tentpole: shape-keyed hit/miss behavior, LRU bound,
cached-vs-uncached numeric parity (tolerance 0) on a representative op set,
the jit.to_static tracing fallback, stats plumbing, and a two-thread
dispatch smoke test.
"""
import threading

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core import op_cache


@pytest.fixture(autouse=True)
def _fresh_cache():
    """Each test sees an empty cache/stats and the default flags."""
    pt.set_flags({"FLAGS_eager_op_cache": True})
    op_cache.clear(reset=True)
    yield
    pt.set_flags({"FLAGS_eager_op_cache": True,
                  "FLAGS_eager_op_cache_size": 1024})
    op_cache.clear(reset=True)


def _t(arr, requires_grad=False):
    t = pt.to_tensor(np.asarray(arr))
    t.stop_gradient = not requires_grad
    return t


# ---------------------------------------------------------------------------
# hit / miss keying
# ---------------------------------------------------------------------------

def test_repeat_same_shape_hits():
    x = _t(np.random.randn(8, 8).astype(np.float32))
    y = _t(np.random.randn(8, 8).astype(np.float32))
    for _ in range(5):
        pt.matmul(x, y)
    st = op_cache.stats()["matmul"]
    assert st["calls"] == 5
    assert st["misses"] == 1 and st["traces"] == 1
    assert st["hits"] == 4
    assert st["fallbacks"] == {}


def test_shape_change_misses():
    for n in (4, 8, 16):
        x = _t(np.random.randn(n, n).astype(np.float32))
        pt.matmul(x, x)
    st = op_cache.stats()["matmul"]
    assert st["misses"] == 3 and st["hits"] == 0


def test_dtype_change_misses():
    a32 = _t(np.random.randn(8).astype(np.float32))
    a64 = _t(np.random.randn(8).astype(np.float64))
    pt.tanh(a32)
    pt.tanh(a64)
    st = op_cache.stats()["tanh"]
    assert st["misses"] == 2 and st["hits"] == 0


def test_attr_change_misses():
    x = _t(np.random.randn(4, 6).astype(np.float32))
    pt.sum(x, axis=0)
    pt.sum(x, axis=1)
    pt.sum(x, axis=1)          # hit
    pt.sum(x, axis=1, keepdim=True)
    st = op_cache.stats()["sum"]
    assert st["misses"] == 3 and st["hits"] == 1


def test_grad_bit_separates_entries():
    xn = _t(np.random.randn(4, 4).astype(np.float32))
    xg = _t(np.random.randn(4, 4).astype(np.float32), requires_grad=True)
    pt.tanh(xn)                # fwd-mode entry
    pt.tanh(xg)                # vjp-mode entry: same avals, different mode
    st = op_cache.stats()["tanh"]
    assert st["misses"] == 2 and st["hits"] == 0


def test_scalar_type_does_not_collide():
    # True == 1 == 1.0 under Python equality; the key must still separate
    # them or the first caller's constant (and dtype) gets baked in
    t = _t(np.array([1, 0], np.int64))
    out_bool = t + True
    out_int = t + 1
    out_float = t + 1.0
    pt.set_flags({"FLAGS_eager_op_cache": False})
    ref_bool = t + True
    ref_int = t + 1
    ref_float = t + 1.0
    for got, want in ((out_bool, ref_bool), (out_int, ref_int),
                      (out_float, ref_float)):
        assert np.asarray(got._value).dtype == np.asarray(want._value).dtype
        np.testing.assert_array_equal(np.asarray(got._value),
                                      np.asarray(want._value))


def test_churn_guard_bounds_per_call_tracing():
    # an op that only ever misses (fresh scalar every call) must stop
    # paying a jit trace per call after the guard trips
    x = _t(np.random.randn(4).astype(np.float32))
    for i in range(100):
        x + float(i + 0.5)
    st = op_cache.stats()["add"]
    assert st["fallbacks"].get("churn", 0) > 0
    assert st["traces"] < 75  # guard capped entry builds (100 without it)
    # values stay correct through the fallback
    out = x + 1234.5
    np.testing.assert_allclose(np.asarray(out._value),
                               np.asarray(x._value) + 1234.5, rtol=0)


def test_churn_guard_not_masked_by_tensor_tensor_hits():
    # the guard is scoped per (fn, mode, avals) FAMILY: hits on the
    # tensor-tensor form of an op must not keep scalar churn compiling
    x = _t(np.random.randn(4).astype(np.float32))
    u = _t(np.random.randn(4).astype(np.float32))
    for i in range(100):
        x * u                      # same op name, hitting family
        x * (0.1 + i * 1e-4)       # varying scalar: churning family
    st = op_cache.stats()["multiply"]
    assert st["hits"] >= 99        # tensor-tensor path keeps hitting
    assert st["fallbacks"].get("churn", 0) > 0
    assert st["traces"] < 80       # 1 tensor-tensor + throttled scalars
    # a previously-cached scalar value still hits (lookup precedes guard)
    op_cache.reset_stats()
    x * 0.1
    assert op_cache.stats()["multiply"]["hits"] == 1


def test_jit_error_entry_discarded_not_poisoned():
    from paddle_tpu.ops import dispatch

    calls = {"n": 0}

    def flaky(a):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("transient")
        return a * 2.0

    op_cache.mark_stable(flaky)
    x = _t(np.random.randn(4).astype(np.float32))
    # first dispatch: the jit trace hits the transient error, the eager
    # fallback re-runs flaky (which now succeeds) — no exception escapes
    out = dispatch.apply(flaky, x, op_name="flaky")
    np.testing.assert_array_equal(np.asarray(out._value),
                                  np.asarray(x._value) * 2.0)
    st = op_cache.stats()["flaky"]
    assert st["fallbacks"].get("jit_error") == 1
    # the failed entry was dropped (not poisoned): the next call builds a
    # fresh one, and the call after that hits it
    dispatch.apply(flaky, x, op_name="flaky")
    out2 = dispatch.apply(flaky, x, op_name="flaky")
    np.testing.assert_array_equal(np.asarray(out2._value),
                                  np.asarray(x._value) * 2.0)
    st = op_cache.stats()["flaky"]
    assert st["hits"] == 1
    assert "unjittable" not in st["fallbacks"]


def test_scalar_operand_is_part_of_key():
    x = _t(np.random.randn(8).astype(np.float32))
    a = (x + 2.0)._value
    b = (x + 3.0)._value
    c = (x + 2.0)._value
    st = op_cache.stats()["add"]
    assert st["misses"] == 2 and st["hits"] == 1
    np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    assert not np.array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# LRU bound
# ---------------------------------------------------------------------------

def test_lru_eviction_respects_flag_bound():
    pt.set_flags({"FLAGS_eager_op_cache_size": 4})
    for n in range(1, 9):  # 8 distinct shape keys
        x = _t(np.random.randn(n).astype(np.float32))
        pt.tanh(x)
    info = op_cache.cache_info()
    assert info["entries"] <= 4
    assert info["capacity"] == 4
    # re-dispatching the most recent shape still hits
    x = _t(np.random.randn(8).astype(np.float32))
    pt.tanh(x)
    assert op_cache.stats()["tanh"]["hits"] == 1


# ---------------------------------------------------------------------------
# numeric parity: cached vs uncached, tolerance 0
# ---------------------------------------------------------------------------

def _fwd_bwd(fn, arrays, cached):
    pt.set_flags({"FLAGS_eager_op_cache": cached})
    ts = [_t(a, requires_grad=True) for a in arrays]
    out = fn(*ts)
    pt.autograd.backward(
        out, pt.to_tensor(np.ones(out.shape, dtype=np.asarray(
            out._value).dtype)))
    return (np.asarray(out._value),
            [np.asarray(t.grad._value) for t in ts])


REPRESENTATIVE_OPS = [
    ("unary", lambda x: pt.tanh(x),
     [np.random.RandomState(0).randn(6, 5).astype(np.float32)]),
    ("binary_broadcast", lambda x, y: pt.add(x, y),
     [np.random.RandomState(1).randn(4, 5).astype(np.float32),
      np.random.RandomState(2).randn(5).astype(np.float32)]),
    ("matmul", lambda x, y: pt.matmul(x, y),
     [np.random.RandomState(3).randn(4, 6).astype(np.float32),
      np.random.RandomState(4).randn(6, 3).astype(np.float32)]),
    ("reduction_attrs", lambda x: pt.sum(x, axis=1, keepdim=True),
     [np.random.RandomState(5).randn(4, 6).astype(np.float32)]),
]


@pytest.mark.parametrize("label,fn,arrays", REPRESENTATIVE_OPS,
                         ids=[r[0] for r in REPRESENTATIVE_OPS])
def test_cached_grad_parity_exact(label, fn, arrays):
    out_u, grads_u = _fwd_bwd(fn, arrays, cached=False)
    out_c, grads_c = _fwd_bwd(fn, arrays, cached=True)
    out_c2, grads_c2 = _fwd_bwd(fn, arrays, cached=True)  # via cache hit
    np.testing.assert_array_equal(out_u, out_c)
    np.testing.assert_array_equal(out_u, out_c2)
    # tanh's gradient is e + e * y with e = g * (1 - y) (jax's rule).  The
    # cached path jits it as ONE program, where XLA:CPU contracts e * y + e
    # into a fused multiply-add (one rounding); the uncached path runs it
    # op by op (two roundings): the last bit of a float32 in [0, 1) may
    # differ, 2**-24 = 6e-8.  Every other op here has one rounding a
    # result either way and stays equal to the bit.
    atol = 6e-8 if label == "unary" else 0.0
    for gu, gc, gc2 in zip(grads_u, grads_c, grads_c2):
        np.testing.assert_allclose(gc, gu, rtol=0.0, atol=atol)
        np.testing.assert_array_equal(gc, gc2)


def test_cached_backward_is_jitted():
    x = _t(np.random.randn(4, 4).astype(np.float32), requires_grad=True)
    y = pt.tanh(x)
    pt.autograd.backward(y, pt.to_tensor(np.ones((4, 4), np.float32)))
    st = op_cache.stats()["tanh"]
    assert st["bwd_calls"] == 1 and st["bwd_jitted"] == 1


def test_retain_graph_double_backward():
    x = _t(np.random.randn(3).astype(np.float32), requires_grad=True)
    y = (x * x).sum()
    y.backward(retain_graph=True)
    g1 = np.asarray(x.grad._value).copy()
    x.grad = None
    y.backward()
    np.testing.assert_array_equal(g1, np.asarray(x.grad._value))


def test_higher_order_grad_unaffected():
    x = _t(np.array([2.0], np.float32), requires_grad=True)
    y = (x * x * x).sum()
    (gx,) = pt.autograd.grad(y, x, create_graph=True)
    (ggx,) = pt.autograd.grad(gx.sum(), x)
    np.testing.assert_allclose(np.asarray(ggx._value), [12.0], rtol=1e-6)


# ---------------------------------------------------------------------------
# fallbacks
# ---------------------------------------------------------------------------

def test_no_caching_under_to_static():
    def fn(a, b):
        return pt.matmul(a, b) + 1.0

    compiled = pt.jit.to_static(fn)
    x = _t(np.random.randn(4, 4).astype(np.float32))
    y = _t(np.random.randn(4, 4).astype(np.float32))
    before = op_cache.cache_info()["entries"]
    out = compiled(x, y)
    assert np.isfinite(np.asarray(out._value)).all()
    assert op_cache.cache_info()["entries"] == before  # tracers never cached
    summ = op_cache.summary()
    fb = summ["fallbacks"]
    assert fb.get("tracing", 0) + fb.get("tracer_input", 0) > 0
    assert summ["hits"] == 0 and summ["misses"] == 0


def test_flag_disable_falls_back():
    pt.set_flags({"FLAGS_eager_op_cache": False})
    x = _t(np.random.randn(4).astype(np.float32))
    pt.tanh(x)
    st = op_cache.stats()["tanh"]
    assert st["fallbacks"].get("disabled") == 1
    assert op_cache.cache_info()["entries"] == 0


def test_unstable_fn_falls_back():
    from paddle_tpu.ops import dispatch

    x = _t(np.random.randn(4).astype(np.float32))
    out = dispatch.apply(lambda a: a * 2.0, x, op_name="doubler")
    np.testing.assert_array_equal(np.asarray(out._value),
                                  np.asarray(x._value) * 2.0)
    assert op_cache.stats()["doubler"]["fallbacks"].get("unstable_fn") == 1


def test_unhashable_attr_falls_back():
    from paddle_tpu.ops import dispatch

    def scaled(a, *, w):
        return a * w

    op_cache.mark_stable(scaled)
    x = _t(np.random.randn(4).astype(np.float32))
    out = dispatch.apply(scaled, x, op_name="scaled",
                         w=np.ones(4, np.float32))  # ndarray: unhashable
    assert np.isfinite(np.asarray(out._value)).all()
    assert op_cache.stats()["scaled"]["fallbacks"].get("unhashable") == 1


# ---------------------------------------------------------------------------
# stats plumbing
# ---------------------------------------------------------------------------

def test_stats_and_reset():
    x = _t(np.random.randn(4).astype(np.float32))
    pt.tanh(x)
    pt.tanh(x)
    st = op_cache.stats()
    assert st["tanh"]["calls"] == 2
    summ = op_cache.summary()
    assert summ["calls"] >= 2 and 0.0 <= summ["hit_rate"] <= 1.0
    op_cache.reset_stats()
    assert op_cache.stats() == {}
    # entries survive a stats reset; hits keep accruing from zero
    pt.tanh(x)
    assert op_cache.stats()["tanh"]["hits"] == 1


def test_log_stats_writes_summary():
    import io

    x = _t(np.random.randn(4).astype(np.float32))
    pt.tanh(x)
    buf = io.StringIO()
    op_cache.log_stats(stream=buf)
    text = buf.getvalue()
    assert "eager op-cache" in text and "tanh" in text


# ---------------------------------------------------------------------------
# thread-safety smoke
# ---------------------------------------------------------------------------

def test_two_thread_dispatch_smoke():
    x = _t(np.random.randn(8, 8).astype(np.float32))
    y = _t(np.random.randn(8, 8).astype(np.float32))
    errs = []

    def worker():
        try:
            for _ in range(100):
                z = pt.add(pt.matmul(x, y), 1.0)
            assert np.isfinite(np.asarray(z._value)).all()
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=worker) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs
    st = op_cache.stats()
    assert st["matmul"]["calls"] == 200
    assert st["add"]["calls"] == 200
    # after the first trace everything hits (no lost updates under the lock)
    assert st["matmul"]["hits"] == 199 and st["matmul"]["misses"] == 1
