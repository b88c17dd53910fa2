"""Parity tests for the fused multi-tensor AdamW Pallas kernel
(ops/pallas_kernels/fused_adamw.py) in interpret mode, against the same
update math the XLA-composed path in optimizer/optimizers.py uses.

Reference: paddle/phi/kernels/fusion/fused_adam_kernel.cu semantics
(standard AdamW with decoupled weight decay and bias correction).
"""
import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.ops.pallas_kernels.fused_adamw import fused_adamw_update

B1, B2, EPS, WD = 0.9, 0.999, 1e-8, 0.01


def _composed(p, g, m1, m2, lr, b1p, b2p):
    p32 = p.astype(np.float32)
    g32 = g.astype(np.float32)
    new_m1 = B1 * m1.astype(np.float32) + (1 - B1) * g32
    new_m2 = B2 * m2.astype(np.float32) + (1 - B2) * g32 * g32
    m1_hat = new_m1 / (1 - b1p)
    m2_hat = new_m2 / (1 - b2p)
    new_p = p32 * (1 - lr * WD) - lr * m1_hat / (np.sqrt(m2_hat) + EPS)
    return (new_p.astype(p.dtype), new_m1.astype(m1.dtype),
            new_m2.astype(m2.dtype))


@pytest.mark.parametrize("shape,dtype", [
    ((512, 1024), np.float32),       # lane-aligned, no padding
    ((3, 257), np.float32),          # unaligned -> padded tail
    ((24, 64, 64), "bfloat16"),      # slab-shaped bf16 (bench regime)
])
def test_fused_adamw_matches_composed(shape, dtype):
    rng = np.random.RandomState(0)
    dt = jnp.bfloat16 if dtype == "bfloat16" else np.float32
    p = jnp.asarray(rng.randn(*shape), dt)
    g = jnp.asarray(rng.randn(*shape) * 0.1, dt)
    m1 = jnp.asarray(rng.randn(*shape) * 0.01, dt)
    m2 = jnp.asarray(np.abs(rng.randn(*shape)) * 0.001, dt)
    lr, b1p, b2p = 1e-3, B1 ** 3, B2 ** 3

    # p/m1/m2 are DONATED into the outputs (in-place contract): snapshot
    # the composed expectation before the call invalidates the inputs
    want_p, want_m1, want_m2 = _composed(
        np.asarray(p, np.float32), np.asarray(g, np.float32),
        np.asarray(m1, np.float32), np.asarray(m2, np.float32),
        lr, b1p, b2p)
    in_shape, in_dtype = p.shape, p.dtype
    got_p, got_m1, got_m2 = fused_adamw_update(
        p, g, m1, m2, lr, b1p, b2p,
        beta1=B1, beta2=B2, eps=EPS, wd=WD, interpret=True)

    tol = 1e-2 if dtype == "bfloat16" else 1e-6
    np.testing.assert_allclose(np.asarray(got_p, np.float32), want_p,
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(got_m1, np.float32), want_m1,
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(got_m2, np.float32), want_m2,
                               rtol=tol, atol=tol)
    assert got_p.shape == in_shape and got_p.dtype == in_dtype


def test_optimizer_fused_needs_tpu():
    """Off-TPU the fused route raises at construction instead of running
    the Mosaic kernel through the interpreter without saying so."""
    import paddle_tpu as pt

    w = pt.to_tensor(np.zeros((4, 4), np.float32))
    with pytest.raises(RuntimeError, match="not a TPU"):
        pt.optimizer.AdamW(parameters=[w], use_fused_kernel=True)


def test_optimizer_routes_fused(monkeypatch):
    """AdamW(use_fused_kernel=True) without master weights must produce
    the same update as the composed path (the platform gate opened and
    the kernel interpreted, so the routing itself runs on the CPU)."""
    import functools

    import paddle_tpu as pt
    from paddle_tpu.ops.pallas_kernels import flash_attention, fused_adamw

    monkeypatch.setattr(flash_attention, "_on_tpu", lambda: True)
    monkeypatch.setattr(
        fused_adamw, "fused_adamw_update",
        functools.partial(fused_adamw.fused_adamw_update, interpret=True))

    rng = np.random.RandomState(1)
    w0 = rng.randn(16, 32).astype(np.float32)

    def one_step(use_fused):
        w = pt.to_tensor(w0.copy())
        w.stop_gradient = False
        opt = pt.optimizer.AdamW(learning_rate=1e-2, parameters=[w],
                                 multi_precision=False,
                                 use_fused_kernel=use_fused)
        loss = (w * w).sum()
        loss.backward()
        opt.step()
        return np.asarray(w._value)

    a = one_step(False)
    b = one_step(True)
    np.testing.assert_allclose(a, b, rtol=2e-6, atol=2e-6)
