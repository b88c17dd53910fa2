"""What the stacked hybrid decoders (``lfm2.py``, ``phi4flash.py``) share, on
raw arrays: the matmul on the weights' dtype, the norms and the gated
feed-forwards.  One place, so that a decoder of new layer kinds copies none
of it."""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["mm", "rms_norm", "layer_norm", "gated_ffn", "gated_ffn_fused"]


def mm(x, w):
    """``x @ w`` on the weights' dtype with float32 accumulation."""
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def rms_norm(x, g, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, g, b, eps):
    """LayerNorm with scale and bias over the last axis, in float32."""
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    xc = x32 - mu
    y = xc * jax.lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def gated_ffn(u, w1, w3, w2):
    """``(silu(u @ w1) * (u @ w3)) @ w2``."""
    y = (jax.nn.silu(mm(u, w1)) * mm(u, w3)).astype(u.dtype)
    return mm(y, w2).astype(u.dtype)


def gated_ffn_fused(u, w_in, w_out):
    """``[g, p] = split2(u @ w_in);  (p * silu(g)) @ w_out``: the gate and
    the value in one product."""
    g, p = jnp.split(mm(u, w_in), 2, axis=-1)
    return mm((p * jax.nn.silu(g)).astype(u.dtype), w_out).astype(u.dtype)
