"""Plain reference for the decoder of sliding-window and full grouped-query
attention layers with 64 softmax-routed experts in every layer
(``model_type: mellum``): a full forward over a whole sequence in float32
``jax.numpy`` at the highest matmul precision.  Nothing of the program: no
cache, no kernel, no ring, no chunks, no sorting of tokens.  ``N(x; g) = x /
sqrt(mean(x^2) + eps) * g``; ``silu(a) = a * sigmoid(a)``; ``d`` a head's
size; ``t`` a query's position, ``s`` a key's::

    x = Emb[id]
    for each layer (its kind from layer_types):
      u = N(x; g_attn)
      q, k, v = u @ Wq, u @ Wk, u @ Wv
      rot(y, t): y_i cos(t w_i) - y_{i+d/2} sin(t w_i),  y_{i+d/2} cos(t w_i)
                 + y_i sin(t w_i)   (i < d/2), cos and sin times m
        sliding_attention: w_i = theta^(-2i/d), m = 1
        full_attention (YaRN, arXiv:2309.00071): low = floor(d ln(L0 /
          (beta_fast 2 pi)) / (2 ln theta)), high = ceil(d ln(L0 / (beta_slow
          2 pi)) / (2 ln theta)), r_i = clip((i - low) / (high - low), 0, 1),
          w_i = theta^(-2i/d) (1 - r_i) + theta^(-2i/d) / factor r_i,
          m = attention_factor
      o = softmax(rot(q) rot(k)^T / sqrt(d) + mask) v, K/V head j serving
          query heads G*j .. G*j + G - 1;  mask: s <= t, and on a sliding
          layer t - s < sliding_window
      x = x + o @ Wo;  u = N(x; g_ffn)
      s = softmax(u @ Wr);  sel = top_k(s);  p = s[sel] / sum(s[sel])
      x = x + sum_{e in sel} p_e (silu(u @ W1[e]) * (u @ W3[e])) @ W2[e]
    logits = N(x; g_out) @ W_head^T

The attention runs in blocks of ``block`` queries, each against the keys
its rows may read (every key of a full layer, the window and the block's
own of a sliding one, masked), and the head in blocks of rows whose logits
go to the host as they are made: a prompt of some 9 k tokens fits the chip
beside the program's own state, and every block of a kind has one shape, so
one compiled program.  The result is a host array.

``weights`` (any float dtype; every array is cast to float32 WHERE IT IS USED,
an expert at a time): ``embed`` [V, H], ``out_norm`` [H], ``lm_head`` [V, H],
``layers``: one dict a layer with ``wq``, ``wk``, ``wv``, ``wo``,
``ln_attn``, ``ln_ffn``, ``router`` [H, E] and ``experts`` (the triple of
stacks ``w1`` [*, H, F], ``w3``, ``w2`` [*, F, H]) with ``expert_base``, the
layer's first expert in them; a dict with ``period`` holds stacks whose
slice ``period`` is the layer's.

Departures from the deployment, all the configuration file's: the logits
are taken from the last layer HELD (a depth cut); no multi-token-prediction
head.  Routing near-ties: ``routes`` [layers, S, k], another run's picks,
taken where they are a top-k of THIS forward's softmax scores to within
``route_margin`` (k distinct experts, none left out scoring more than
``route_margin`` above the lowest one taken), as ``lfm2_moe_ref.route`` does
for a sigmoid router; every number is still computed here.
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np


def _f32(a):
    return jnp.asarray(a).astype(jnp.float32)


def _w(p, name):
    a = p[name]
    return _f32(a[p["period"]] if "period" in p else a)


def _norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rotary(head_dim, section):
    """``(w [d/2] float32, m)`` of one ``rope_parameters`` section."""
    theta = float(section["rope_theta"])
    i = np.arange(head_dim // 2, dtype=np.float64)
    w = theta ** (-2.0 * i / head_dim)
    if section["rope_type"] == "default":
        return w.astype(np.float32), 1.0
    assert section["rope_type"] == "yarn", section
    l0 = section["original_max_position_embeddings"]

    def dim(turns):
        return head_dim * math.log(l0 / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim(section["beta_fast"])), 0)
    high = min(math.ceil(dim(section["beta_slow"])), head_dim - 1)
    r = np.clip((i - low) / (high - low), 0.0, 1.0)
    w = w * (1.0 - r) + w / section["factor"] * r
    return w.astype(np.float32), float(section["attention_factor"])


def _rot(y, w, m):
    """``y`` [S, heads, d] at positions 0 .. S-1."""
    half = y.shape[-1] // 2
    ang = jnp.arange(y.shape[0], dtype=jnp.float32)[:, None, None] * jnp.asarray(w)
    cos, sin = jnp.cos(ang) * m, jnp.sin(ang) * m
    a, b = y[..., :half], y[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("block", "span", "window", "s"))
def _block(q, k, v, lo, *, block, span, window, s):
    """Queries ``lo .. lo + block - 1`` against the ``span`` keys that end
    with the block's last: one shape, so one compiled program, for every
    block of a layer kind."""
    first = jnp.clip(lo + block - span, 0, k.shape[0] - span)
    t = lo + jnp.arange(block)[:, None]
    at = first + jnp.arange(span)[None, :]
    seen = (at <= t) & (at < s)
    if window is not None:
        seen = seen & (t - at < window)
    qb = jax.lax.dynamic_slice_in_dim(q, lo, block)
    kb = jax.lax.dynamic_slice_in_dim(k, first, span)
    vb = jax.lax.dynamic_slice_in_dim(v, first, span)
    scores = jnp.einsum("qhd,khd->hqk", qb, kb) / jnp.sqrt(jnp.float32(q.shape[-1]))
    scores = jnp.where(seen[None], scores, -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), vb)


def _attention(u, p, *, heads, kv_heads, w, m, window, block):
    s = u.shape[0]
    q = (u @ _w(p, "wq")).reshape(s, heads, -1)
    k = (u @ _w(p, "wk")).reshape(s, kv_heads, -1)
    v = (u @ _w(p, "wv")).reshape(s, kv_heads, -1)
    d = q.shape[-1]
    q, k = _rot(q, w, m), _rot(k, w, m)
    group = heads // kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    n = -(-s // block) * block                  # rows padded to whole blocks
    q, k, v = (jnp.pad(a, ((0, n - s), (0, 0), (0, 0))) for a in (q, k, v))
    # the keys a block's rows may read: all of them, or the window behind
    # the block's first row and the block
    span = n if window is None else min(n, -(-(window + block - 1) // block) * block)
    out = [_block(q, k, v, jnp.int32(lo), block=block, span=span, window=window, s=s)
           for lo in range(0, n, block)]
    return jnp.concatenate(out)[:s].reshape(s, heads * d) @ _w(p, "wo")


def route(u, router, *, top_k, theirs=None, margin=0.0):
    """The chosen experts [S, k] and their weights [S, k]; ``theirs`` [S,
    k]: another run's picks, taken where they are a top-k of these scores to
    within ``margin`` (the module's docstring)."""
    s = jax.nn.softmax(u @ router, axis=-1)
    _, sel = jax.lax.top_k(s, top_k)
    if theirs is not None:
        n = s.shape[-1]
        known = jnp.all((theirs >= 0) & (theirs < n), axis=-1)
        theirs = jnp.clip(theirs, 0, n - 1)
        taken = jnp.any(theirs[..., None] == jnp.arange(n), axis=-2)    # [S, E]
        lowest = jnp.min(jnp.where(taken, s, jnp.inf), axis=-1)
        left = jnp.max(jnp.where(taken, -jnp.inf, s), axis=-1)
        tied = known & (jnp.sum(taken, axis=-1) == top_k) & (left <= lowest + margin)
        sel = jnp.where(tied[..., None], theirs, sel)
    p = jnp.take_along_axis(s, sel, axis=-1)
    return sel, p / jnp.sum(p, axis=-1, keepdims=True)


def _gated(u, w1, w3, w2):
    return (jax.nn.silu(u @ _f32(w1)) * (u @ _f32(w3))) @ _f32(w2)


def _moe(u, p, *, top_k, theirs, margin):
    router = _w(p, "router")
    sel, w = route(u, router, top_k=top_k, theirs=theirs, margin=margin)
    w1, w3, w2 = p["experts"]
    y = jnp.zeros_like(u)
    for e in range(router.shape[-1]):
        share = jnp.sum(jnp.where(sel == e, w, 0.0), axis=-1, keepdims=True)
        i = p["expert_base"] + e
        y = y + share * _gated(u, w1[i], w3[i], w2[i])
    return y


def logits(weights: Dict, ids, *, layer_types, heads, kv_heads, eps, window,
           rope_parameters, top_k, routes=None, route_margin=0.0, block=256):
    """``ids`` [1, S] -> logits [1, S, V] (float32, on the host).
    ``routes`` [layers, S, k]: another run's picks (the module's docstring)."""
    if ids.shape[0] != 1:
        raise ValueError(f"one sequence at a time; ids holds {ids.shape[0]}")
    s = ids.shape[1]
    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.take(jnp.asarray(weights["embed"]), ids[0], axis=0))
        for l, (kind, p) in enumerate(zip(layer_types, weights["layers"])):
            d = p["wq"].shape[-1] // heads
            w, m = rotary(d, rope_parameters[kind])
            u = _norm(x, _w(p, "ln_attn"), eps)
            x = x + _attention(u, p, heads=heads, kv_heads=kv_heads, w=w, m=m,
                               window=window if kind == "sliding_attention" else None,
                               block=block)
            u = _norm(x, _w(p, "ln_ffn"), eps)
            theirs = None if routes is None else jnp.asarray(routes[l])[:s]
            x = x + _moe(u, p, top_k=top_k, theirs=theirs, margin=route_margin)
        x = _norm(x, _f32(weights["out_norm"]), eps)
        x = jnp.pad(x, ((0, -(-s // block) * block - s), (0, 0)))
        head = _f32(weights["lm_head"])
        out = np.empty((1, s, head.shape[0]), np.float32)
        for lo in range(0, s, block):
            rows = np.asarray(_rows(x, head, jnp.int32(lo), block=block))
            out[0, lo:lo + block] = rows[:s - lo]
    return out


@functools.partial(jax.jit, static_argnames=("block",))
def _rows(x, head, lo, *, block):
    """The logits of rows ``lo .. lo + block - 1``."""
    return jax.lax.dynamic_slice_in_dim(x, lo, block) @ head.T
