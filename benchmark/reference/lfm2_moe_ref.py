"""Plain reference for the hybrid conv / grouped-query-attention decoder with a
routed feed-forward (``model_type: lfm2_moe``): a full forward over whole
sequences in float32 ``jax.numpy`` at the highest matmul precision.  Nothing
of the program: no cache, no kernel, no batching of steps, no sorting of
tokens.  ``RMSNorm`` ``N(x; g) = x / sqrt(mean(x^2) + eps) * g``; ``silu(a) =
a * sigmoid(a)``::

    x = Emb[id]
    for each layer l (its kind from layer_types):
      u = N(x; g_op[l])
      conv:  [B, C, z] = split3(u @ W_in);  v_t = B_t * z_t
             c_t = w[0] * v_{t-2} + w[1] * v_{t-1} + w[2] * v_t   (v_{<0} = 0)
             o_t = (C_t * c_t) @ W_out
      attn:  q, k, v = u @ Wq, u @ Wk, u @ Wv  (heads of head_dim)
             q, k = N(q; g_q), N(k; g_k) per head, then rotary positions
             (rotate-half: dimension i pairs with i + head_dim/2)
             o = softmax(q k^T / sqrt(head_dim) + causal) v, K/V head j serving
             query heads G*j .. G*j + G - 1;  o = o @ Wo
      x = x + o;  u = N(x; g_ffn[l])
      l < num_dense:  y = (silu(u @ W1) * (u @ W3)) @ W2
      otherwise:      s = sigmoid(u @ Wg);  sel = top_k(s + b)
                      p = s[sel] / (sum(s[sel]) + 1e-6)
                      y = sum_{e in sel} p_e * (silu(u @ W1[e]) * (u @ W3[e])) @ W2[e]
      x = x + y
    logits = N(x; g_out) @ Emb^T

``weights`` (any float dtype; every array is cast to float32 WHERE IT IS USED,
an expert at a time, so that a caller's 10 GB of bfloat16 experts are never
held twice): ``embed`` [V, H], ``out_norm`` [H], ``layers``: a list, one dict a
layer, with ``op_norm`` [H], ``ffn_norm`` [H] and

- a conv layer: ``conv_in`` [H, 3H], ``conv_w`` [3, H] (tap-major: ``w[2]``
  multiplies the current input), ``conv_out`` [H, H];
- an attention layer: ``wq`` [H, Hq*D], ``wk``/``wv`` [H, Hkv*D], ``wo``
  [Hq*D, H], ``q_norm``/``k_norm`` [D];
- a dense layer: ``w1``/``w3`` [H, F], ``w2`` [F, H];
- a routed layer: ``router`` [H, E], ``router_bias`` [E], ``experts``: a
  triple of stacks (``w1`` [*, H, Fm], ``w3`` [*, H, Fm], ``w2`` [*, Fm, H])
  and ``expert_base``: the layer's first expert in the stacks.

Departures from the deployment, all the configuration file's: the logits are
taken from the last layer HELD (a depth cut), through the final norm and the
tied embedding.

``top_k`` is not continuous: where a token's k-th and (k+1)-th scores are
nearer than the rounding of a lower-precision run's hidden state, that run
picks another expert than this one, both rightly, and every later layer of
the token then differs by a whole expert's output.  A caller comparing such a
run passes ``routes`` [routed layers, S, k]: the experts that run sent each
position to (negative: not known).  Where they are a top-k of THIS forward's
own float32 scores to within ``route_margin`` (k distinct experts, and no
expert left out scores more than ``route_margin`` above the lowest one
taken), this forward takes them; where they are not, it keeps its own.
Every number is still computed here; only the side of a near-tie is taken
over, and a run that routes wrongly by more than the margin is followed
nowhere.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp


def _f32(a):
    return jnp.asarray(a).astype(jnp.float32)


def _norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(g)


def _rope(x, theta):
    """x [B, S, heads, D] at positions 0..S-1."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[None, :, None, None] * inv
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def _conv(u, p):
    b_, c_, z = jnp.split(u @ _f32(p["conv_in"]), 3, axis=-1)
    v = b_ * z
    w = _f32(p["conv_w"])
    back1 = jnp.pad(v, ((0, 0), (1, 0), (0, 0)))[:, :-1]       # v_{t-1}
    back2 = jnp.pad(v, ((0, 0), (2, 0), (0, 0)))[:, :-2]       # v_{t-2}
    return (c_ * (w[0] * back2 + w[1] * back1 + w[2] * v)) @ _f32(p["conv_out"])


def _attention(u, p, *, heads, kv_heads, theta, eps):
    b, s, _ = u.shape
    d = p["q_norm"].shape[-1]
    q = (u @ _f32(p["wq"])).reshape(b, s, heads, d)
    k = (u @ _f32(p["wk"])).reshape(b, s, kv_heads, d)
    v = (u @ _f32(p["wv"])).reshape(b, s, kv_heads, d)
    q = _rope(_norm(q, p["q_norm"], eps), theta)
    k = _rope(_norm(k, p["k_norm"], eps), theta)
    group = heads // kv_heads
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(d))
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(b, s, heads * d) @ _f32(p["wo"])


def _gated(u, w1, w3, w2):
    return (jax.nn.silu(u @ _f32(w1)) * (u @ _f32(w3))) @ _f32(w2)


def route(u, p, *, top_k, theirs=None, margin=0.0):
    """The chosen experts [..., k] and their weights [..., k]; ``theirs``
    [..., k]: another run's picks, taken where they are a top-k of these
    scores to within ``margin`` (the module's docstring)."""
    s = jax.nn.sigmoid(u @ _f32(p["router"]))
    biased = s + _f32(p["router_bias"])         # selects, does not weigh
    _, sel = jax.lax.top_k(biased, top_k)
    if theirs is not None:
        n = biased.shape[-1]
        known = jnp.all((theirs >= 0) & (theirs < n), axis=-1)
        theirs = jnp.clip(theirs, 0, n - 1)
        taken = jnp.any(theirs[..., None] == jnp.arange(n), axis=-2)    # [..., E]
        lowest = jnp.min(jnp.where(taken, biased, jnp.inf), axis=-1)
        left = jnp.max(jnp.where(taken, -jnp.inf, biased), axis=-1)
        tied = known & (jnp.sum(taken, axis=-1) == top_k) & (left <= lowest + margin)
        sel = jnp.where(tied[..., None], theirs, sel)
    w = jnp.take_along_axis(s, sel, axis=-1)
    return sel, w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)


def _routed(u, p, **routing):
    sel, w = route(u, p, **routing)
    w1, w3, w2 = p["experts"]
    y = jnp.zeros_like(u)
    for e in range(p["router"].shape[-1]):
        share = jnp.sum(jnp.where(sel == e, w, 0.0), axis=-1, keepdims=True)
        i = p["expert_base"] + e
        y = y + share * _gated(u, w1[i], w3[i], w2[i])
    return y


def logits(weights: Dict, ids, *, layer_types, num_dense_layers, heads, kv_heads,
           eps, rope_theta, top_k, routes=None, route_margin=0.0, hidden=False):
    """``ids`` [B, S] -> logits [B, S, V] (float32).  ``routes``: the module's
    docstring (one sequence then).  ``hidden=True`` returns the inputs of every
    routed layer's router beside them (for a caller that counts near-ties)."""
    if routes is not None and ids.shape[0] != 1:
        raise ValueError(f"routes are one sequence's; ids holds {ids.shape[0]}")
    router_inputs = []
    with jax.default_matmul_precision("highest"):
        x = _f32(weights["embed"])[ids]
        for l, (kind, p) in enumerate(zip(layer_types, weights["layers"])):
            u = _norm(x, p["op_norm"], eps)
            if kind == "conv":
                x = x + _conv(u, p)
            else:
                x = x + _attention(u, p, heads=heads, kv_heads=kv_heads,
                                   theta=rope_theta, eps=eps)
            u = _norm(x, p["ffn_norm"], eps)
            if l < num_dense_layers:
                x = x + _gated(u, p["w1"], p["w3"], p["w2"])
            else:
                theirs = None if routes is None else jnp.asarray(
                    routes[len(router_inputs)])[None, :ids.shape[1]]
                router_inputs.append(u)
                x = x + _routed(u, p, top_k=top_k, theirs=theirs,
                                margin=route_margin)
        out = _norm(x, weights["out_norm"], eps) @ _f32(weights["embed"]).T
    return (out, router_inputs) if hidden else out
