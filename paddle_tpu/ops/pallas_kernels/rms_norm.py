"""Fused residual-add + RMSNorm / LayerNorm as single Pallas TPU kernels.

Reference analog: the fused norm kernels under
paddle/phi/kernels/fusion/ (fused_bias_residual_layernorm /
rms_norm_kernel) that modern-LLM blocks call between attention and FFN.

TPU-native: one VMEM pass computes h = x + residual, the row statistic,
and the scaled output — the residual sum is never written to HBM
separately (the usual extra round-trip when XLA schedules the add and
the norm apart).  Both kernels return (normed, h): h is the carry the
next residual needs.  Backward is XLA autodiff over the same math via
custom_vjp recompute — the fused win is the fwd HBM traffic.

One parameterized builder produces both variants so the eligibility
gate, VMEM block sizing, pallas_call plumbing and vjp wiring exist
once.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

__all__ = ["fused_add_rms_norm", "fused_add_layer_norm",
           "shape_supported"]

_BLOCK_ROWS = 256


def shape_supported(hidden: int) -> bool:
    """Lane constraint: the hidden (row) dim must tile the 128-wide
    lanes."""
    return hidden % 128 == 0


def _pick_rows(rows: int, hdim: int) -> int:
    """Largest power-of-two row block that (a) divides rows, (b) stays
    inside the VMEM budget: 4 row-buffers of block*hdim*4B within
    ~8 MiB (the same discipline fused_adamw documents)."""
    if rows <= 0:
        return 0
    cap = max(1, (8 * 2 ** 20) // (16 * hdim))
    b = min(_BLOCK_ROWS, rows, cap)
    while b & (b - 1):          # round down to a power of two
        b &= b - 1
    while b > 1 and rows % b:
        b //= 2
    return b


def _rms_math(h, params, eps):
    (g,) = params
    ms = jnp.mean(h * h, axis=-1, keepdims=True)
    return h * jax.lax.rsqrt(ms + eps) * g


def _ln_math(h, params, eps):
    g, b = params
    mu = jnp.mean(h, axis=-1, keepdims=True)
    d = h - mu
    var = jnp.mean(d * d, axis=-1, keepdims=True)
    return d * jax.lax.rsqrt(var + eps) * g + b


def _build(norm_math, n_params, name):
    """Produce the fused (x, residual, *params) -> (normed, h) op with
    the pallas fast path, reference fallback and custom_vjp."""

    def kernel(*refs, eps):
        x_ref, r_ref = refs[0], refs[1]
        p_refs = refs[2:2 + n_params]
        o_ref, h_ref = refs[2 + n_params], refs[3 + n_params]
        x = x_ref[...].astype(jnp.float32)
        r = r_ref[...].astype(jnp.float32)
        params = tuple(p[...].astype(jnp.float32) for p in p_refs)
        h = x + r
        o_ref[...] = norm_math(h, params, eps).astype(o_ref.dtype)
        h_ref[...] = h.astype(h_ref.dtype)

    def reference(x, r, *params, eps):
        h = (x + r).astype(jnp.float32)
        p32 = tuple(p.astype(jnp.float32) for p in params)
        return (norm_math(h, p32, eps).astype(x.dtype),
                h.astype(x.dtype))

    def fwd_impl(x, r, params, eps, interpret):
        shape = x.shape
        hdim = shape[-1]
        x2 = x.reshape(-1, hdim)
        r2 = r.reshape(-1, hdim)
        rows = x2.shape[0]
        block = _pick_rows(rows, hdim)
        # int32 index-map returns: Mosaic under x64 rejects i64 (see
        # fused_adamw.py / flash_attention.py)
        row_spec = pl.BlockSpec((block, hdim), lambda i: (i, np.int32(0)))
        p_spec = pl.BlockSpec((1, hdim),
                              lambda i: (np.int32(0), np.int32(0)))
        out, h = pl.pallas_call(
            functools.partial(kernel, eps=float(eps)),
            grid=(rows // block,),
            in_specs=[row_spec, row_spec] + [p_spec] * n_params,
            out_specs=[row_spec, row_spec],
            out_shape=[
                jax.ShapeDtypeStruct(x2.shape, x.dtype),
                jax.ShapeDtypeStruct(x2.shape, x.dtype),
            ],
            interpret=interpret,
        )(x2, r2, *(p.reshape(1, hdim) for p in params))
        return out.reshape(shape), h.reshape(shape)

    @jax.named_scope("kernel.rms_norm")
    def fused_fwd(x, r, params, eps, interpret):
        from .flash_attention import _on_tpu

        rows = math.prod(x.shape[:-1]) if x.ndim > 1 else 0
        eligible = (shape_supported(x.shape[-1]) and rows > 0
                    and _pick_rows(rows, x.shape[-1]) >= 8)
        if (interpret or _on_tpu()) and eligible:
            return fwd_impl(x, r, params, eps, interpret)
        return reference(x, r, *params, eps=eps)

    @functools.partial(jax.custom_vjp, nondiff_argnums=(2 + n_params,
                                                        3 + n_params))
    def op(x, residual, *args):
        *params, eps, interpret = args
        out, h = fused_fwd(x, residual, tuple(params), eps, interpret)
        return out, h

    def vjp_fwd(x, r, *args):
        *params, eps, interpret = args
        out, h = fused_fwd(x, r, tuple(params), eps, interpret)
        return (out, h), (x, r, tuple(params))

    def vjp_bwd(eps, interpret, res, cts):
        x, r, params = res
        _, vjp = jax.vjp(
            lambda a, b, *ps: reference(a, b, *ps, eps=eps),
            x, r, *params)
        return vjp(cts)

    op.defvjp(vjp_fwd, vjp_bwd)
    op._reference = reference
    op.__name__ = name
    return op


_rms_op = _build(_rms_math, 1, "fused_add_rms_norm")
_ln_op = _build(_ln_math, 2, "fused_add_layer_norm")


def fused_add_rms_norm(x, residual, weight, eps=1e-6, interpret=False):
    """(normed, h) with h = x + residual and
    normed = rms_norm(h) * weight — one fused VMEM pass on TPU, the
    XLA expression elsewhere/ineligible shapes."""
    return _rms_op(x, residual, weight, eps, interpret)


def fused_add_layer_norm(x, residual, weight, bias, eps=1e-5,
                         interpret=False):
    """(normed, h) with h = x + residual and normed = layer_norm(h) —
    the reference's fused_bias_residual_layernorm shape."""
    return _ln_op(x, residual, weight, bias, eps, interpret)


def _reference(x, r, g, eps):           # kept for the kernel tests
    return _rms_op._reference(x, r, g, eps=eps)


def _ln_reference(x, r, g, b, eps):
    return _ln_op._reference(x, r, g, b, eps=eps)
