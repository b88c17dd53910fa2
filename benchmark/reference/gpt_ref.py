"""Plain reference for the dense GPT configurations: forward, loss and
gradients in straightforward ``jax.numpy``, float32, matmul precision
"highest"; no kernels, no cache, no batching tricks.

Pre-LN decoder as GPT-2/GPT-3 (Brown et al. 2020, section 2.1): learned
position embeddings, LayerNorm, fused qkv projection with columns ordered
[3, heads, head_dim], causal softmax attention, GELU (tanh approximation, as
the system computes it), tied output head.  Departure from the paper, shared
with the system: attention is dense in every layer (GPT-3 alternates dense and
locally banded sparse layers; its banding pattern is not published).

Weights arrive as a dict of the system's own arrays in their storage type:
``embed`` [V, h], ``pos`` [P, h], ``ln_f_g``, ``ln_f_b`` and ``layers``, a dict
of the twelve per-layer arrays stacked on a leading layer axis.  ``logits``
upcasts one layer at a time (a jitted block indexed by a traced layer number:
one small program), so the reference fits beside a serving pool.
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp

LAYER_KEYS = ("ln1_g", "ln1_b", "qkv_w", "qkv_b", "proj_w", "proj_b",
              "ln2_g", "ln2_b", "fc1_w", "fc1_b", "fc2_w", "fc2_b")
F32 = jnp.float32


def layer_norm(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def block(p: Dict, h, *, heads: int, eps: float):
    """One decoder block on ``h`` [B, S, hidden]; ``p`` holds the layer's
    arrays (any float type; computed in float32)."""
    p = {k: v.astype(F32) for k, v in p.items()}
    b, s, hidden = h.shape
    d = hidden // heads
    x = layer_norm(h, p["ln1_g"], p["ln1_b"], eps)
    qkv = (x @ p["qkv_w"] + p["qkv_b"]).reshape(b, s, 3, heads, d)
    q, k, v = (jnp.swapaxes(qkv[:, :, i], 1, 2) for i in range(3))
    scores = jnp.einsum("bnqd,bnkd->bnqk", q, k) / math.sqrt(d)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    out = jnp.einsum("bnqk,bnkd->bnqd", jax.nn.softmax(scores, axis=-1), v)
    out = jnp.swapaxes(out, 1, 2).reshape(b, s, hidden)
    h = h + out @ p["proj_w"] + p["proj_b"]
    y = layer_norm(h, p["ln2_g"], p["ln2_b"], eps)
    y = jax.nn.gelu(y @ p["fc1_w"] + p["fc1_b"], approximate=True)
    return h + y @ p["fc2_w"] + p["fc2_b"]


@functools.partial(jax.jit, static_argnames=("heads", "eps"))
def _block_at(layers: Dict, h, i, *, heads: int, eps: float):
    with jax.default_matmul_precision("highest"):
        p = {k: jax.lax.dynamic_index_in_dim(v, i, keepdims=False)
             for k, v in layers.items()}
        return block(p, h, heads=heads, eps=eps)


@jax.jit
def _embed(embed, pos, ids):
    return embed.astype(F32)[ids] + pos.astype(F32)[jnp.arange(ids.shape[-1])][None]


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(h, g, b, embed, *, eps: float):
    with jax.default_matmul_precision("highest"):
        return layer_norm(h, g.astype(F32), b.astype(F32), eps) @ embed.astype(F32).T


def _hidden(weights: Dict, ids, *, heads: int, eps: float):
    h = _embed(weights["embed"], weights["pos"], ids)
    n_layers = weights["layers"]["qkv_w"].shape[0]
    for i in range(n_layers):
        h = _block_at(weights["layers"], h, i, heads=heads, eps=eps)
    return h


def logits(weights: Dict, ids, *, heads: int, eps: float):
    """Full forward over ``ids`` [B, S] -> float32 logits [B, S, V]."""
    h = _hidden(weights, ids, heads=heads, eps=eps)
    return _head(h, weights["ln_f_g"], weights["ln_f_b"], weights["embed"], eps=eps)


def loss(weights: Dict, ids, labels, *, heads: int, eps: float):
    """Mean cross entropy of ``labels`` [B, S] under the forward."""
    logp = jax.nn.log_softmax(logits(weights, ids, heads=heads, eps=eps), axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1).mean()


@functools.partial(jax.jit, static_argnames=("heads", "eps"))
def loss_and_grad(weights: Dict, ids, labels, *, heads: int, eps: float):
    """``(loss, d loss / d weights)``, every leaf's gradient in float32."""
    as_f32 = jax.tree_util.tree_map(lambda a: a.astype(F32), weights)
    return jax.value_and_grad(
        lambda w: loss(w, ids, labels, heads=heads, eps=eps))(as_f32)
