"""Plain reference for the decoder-hybrid-decoder configurations
(``model_type: phi4flash``, arXiv:2507.06607): a full forward over the whole
sequence in straightforward ``jax.numpy``, float32, matmul precision
"highest"; no kernels, no cache, no pages, nothing of the program.

With ``L`` layers and ``m = L / 2``: even ``l <= m`` Mamba-1, odd ``l < m``
attention over a window, ``l = m + 1`` full causal attention, even ``l > m``
gated memory units, odd ``l > m + 1`` cross-attention.  For ``x = Emb[id]``::

    u = LN(x; ln1_g, ln1_b)
    Mamba:   [a, z] = split2(u @ w_in)
             c_t = silu(conv_w[0] a_{t-3} + conv_w[1] a_{t-2} + conv_w[2] a_{t-1}
                        + conv_w[3] a_t + conv_b)                 a_{<0} = 0
             [r, B, C] = split(c_t @ w_x);  dt = softplus(r @ w_dt + dt_b)
             h_t = exp(dt_t[:, None] * A) * h_{t-1} + (dt_t * c_t)[:, None] * B_t[None, :]
             A = -exp(a_log) [channels, state];  h_{-1} = 0
             y_t = h_t @ C_t + d * c_t;   layer m:  M = y   (before the gate)
             o = (y * silu(z)) @ w_out
    memory:  o = (M * silu(u @ w1)) @ w2
    attn:    [q, k, v] = split(u @ wqkv + bqkv);  o = Diff(q, k, v, mask) @ wo + bo
    cross:   q = u @ wq + bq;  o = Diff(q, k^(m+1), v^(m+1), causal) @ wo + bo
    x = x + o;  u = LN(x; ln2_g, ln2_b);  [g, p] = split2(u @ fc1)
    x = x + (p * silu(g)) @ fc2
    logits = LN(x; out_g, out_b) @ Emb^T

``Diff``: heads pair by neighbours, ``q1_i = q_{2i}``, ``q2_i = q_{2i+1}``,
``k1_j = k_{2j}``, ``k2_j = k_{2j+1}`` (``v`` alike), K/V pair ``j = i //
(heads / kv_heads)`` serving query pair ``i``::

    P1 = softmax(q1_i k1_j^T / sqrt(d) + mask);  P2 = softmax(q2_i k2_j^T / sqrt(d) + mask)
    lam0 = 0.8 - 0.6 exp(-0.3 l);  lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0
    o_i = RMSNorm((P1 - lam P2) [v1_j | v2_j]; g_sub) * (1 - lam0)

The mask is ``s <= t``, and ``t - s < window`` in a window layer, written as
inequalities on ``[S, S]``.  ``M`` and layer ``m + 1``'s ``k, v`` are plain
arrays kept for the later layers.

Weights arrive as the program's own arrays in their storage type: ``embed``
[V, h], ``out_g``, ``out_b`` and ``layers``, a list of one dict a layer with
its ``kind`` (``ssm``, ``attn``, ``gmu``, ``cross``); a dict with a ``period``
holds stacks of several layers' arrays and names its own index on their leading
axis.  Each array is sliced and cast to float32 as its layer is reached, and the
head is taken in blocks of vocabulary rows that are handed to the host one by
one, so that the check needs one layer's float32 weights and a block of logits
beside what the program holds, never a float32 embedding.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
SUBLN_EPS = 1e-5


def layer_norm(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def silu(a):
    return a / (1.0 + jnp.exp(-a))


def softplus(a):
    return jnp.logaddexp(a, 0.0)


def mamba(p: Dict, u, *, d_state: int, dt_rank: int):
    """``u`` [S, h] -> (the operator's output [S, h], ``y`` [S, channels])."""
    s = u.shape[0]
    a, z = jnp.split(u @ p["w_in"], 2, axis=-1)
    pad = jnp.concatenate([jnp.zeros((3, a.shape[1]), F32), a])
    taps = p["conv_w"]                                   # [4, channels]
    c = silu(taps[0] * pad[0:s] + taps[1] * pad[1:s + 1] + taps[2] * pad[2:s + 2]
             + taps[3] * pad[3:s + 3] + p["conv_b"])
    r, b, cc = jnp.split(c @ p["w_x"], (dt_rank, dt_rank + d_state), axis=-1)
    dt = softplus(r @ p["w_dt"] + p["dt_b"])
    a_neg = -jnp.exp(p["a_log"])                         # [channels, state]

    def step(h, inp):
        dt_t, c_t, b_t, c_out = inp
        h = jnp.exp(dt_t[:, None] * a_neg) * h + (dt_t * c_t)[:, None] * b_t[None, :]
        return h, h @ c_out + p["d"] * c_t

    _, y = jax.lax.scan(step, jnp.zeros(a_neg.shape, F32), (dt, c, b, cc))
    return (y * silu(z)) @ p["w_out"], y


def diff_attention(p: Dict, q, k, v, mask, layer: int, *, heads: int, kv_heads: int):
    """``q`` [S, heads * d], ``k`` / ``v`` [S, kv_heads * d] -> [S, heads * d]."""
    s = q.shape[0]
    d = q.shape[1] // heads
    q = q.reshape(s, heads // 2, 2, d)
    k = k.reshape(s, kv_heads // 2, 2, d)
    v = v.reshape(s, kv_heads // 2, 2 * d)               # [v1_j | v2_j]
    lam0 = 0.8 - 0.6 * math.exp(-0.3 * layer)
    lam = jnp.exp(p["lq1"] @ p["lk1"]) - jnp.exp(p["lq2"] @ p["lk2"]) + lam0
    group = heads // kv_heads
    outs = []
    for i in range(heads // 2):
        j = i // group

        def probs(c):
            scores = q[:, i, c] @ k[:, j, c].T / math.sqrt(d)
            return jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)

        o = (probs(0) - lam * probs(1)) @ v[:, j]        # [S, 2d]
        o = o / jnp.sqrt((o * o).mean(-1, keepdims=True) + SUBLN_EPS) * p["g_sub"]
        outs.append(o * (1.0 - lam0))
    return jnp.concatenate(outs, axis=-1)


def forward(weights: Dict, ids, *, heads: int, kv_heads: int, window: int, eps: float,
            d_state: int, dt_rank: int):
    """``ids`` [S] -> the final hidden states [S, h] (before the last norm)."""
    s = ids.shape[0]
    x = weights["embed"][ids].astype(F32)
    d = x.shape[1] // heads
    at = jnp.arange(s)
    causal = at[None, :] <= at[:, None]
    inside = causal & (at[:, None] - at[None, :] < window)
    layers = weights["layers"]
    m = len(layers) // 2
    memory = shared_k = shared_v = None
    for l, stored in enumerate(layers):
        kind, period = stored["kind"], stored.get("period")
        p = {k: (a if period is None else a[period]).astype(F32)
             for k, a in stored.items() if k not in ("kind", "period")}
        u = layer_norm(x, p["ln1_g"], p["ln1_b"], eps)
        if kind == "ssm":
            o, y = mamba(p, u, d_state=d_state, dt_rank=dt_rank)
            if l == m:
                memory = y
        elif kind == "gmu":
            o = (memory * silu(u @ p["w1"])) @ p["w2"]
        elif kind == "attn":
            q, k, v = jnp.split(u @ p["wqkv"] + p["bqkv"],
                                (heads * d, (heads + kv_heads) * d), axis=-1)
            full = l == m + 1
            if full:
                shared_k, shared_v = k, v
            o = diff_attention(p, q, k, v, causal if full else inside, l,
                               heads=heads, kv_heads=kv_heads) @ p["wo"] + p["bo"]
        elif kind == "cross":
            q = u @ p["wq"] + p["bq"]
            o = diff_attention(p, q, shared_k, shared_v, causal, l,
                               heads=heads, kv_heads=kv_heads) @ p["wo"] + p["bo"]
        else:
            raise ValueError(f"layer {l}: kind {kind!r}")
        x = x + o
        u = layer_norm(x, p["ln2_g"], p["ln2_b"], eps)
        g, up = jnp.split(u @ p["fc1"], 2, axis=-1)
        x = x + (up * silu(g)) @ p["fc2"]
    return x


def logits(weights: Dict, ids, *, vocab_block: int = 16384, **sizes):
    """``ids`` [B, S] -> float32 logits [B, S, V], on the host."""
    with jax.default_matmul_precision("highest"):
        out = []
        for row in ids:
            x = forward(weights, row, **sizes)
            x = layer_norm(x, weights["out_g"].astype(F32), weights["out_b"].astype(F32),
                           sizes["eps"])
            emb = weights["embed"]
            out.append(np.concatenate(
                [np.asarray(x @ emb[v0:v0 + vocab_block].astype(F32).T)
                 for v0 in range(0, emb.shape[0], vocab_block)], axis=-1))
        return np.stack(out)
