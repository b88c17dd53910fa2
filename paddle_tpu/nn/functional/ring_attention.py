"""Ring attention over the 'sp' (sequence-parallel) mesh axis.

This capability is ABSENT in the reference (SURVEY.md §2.2 row SP — the
reference only has single-device flash-attention kernels,
gpu/flash_attn_kernel.cu). TPU-native design: Q stays resident, K/V blocks
rotate around the sp ring with lax.ppermute over ICI, and softmax is
accumulated online (flash-attention style m/l rescaling), so sequences of
length S cost each chip O(S_local * S) compute with O(S_local) memory and
communication fully overlapped by XLA's scheduler.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from ...distributed import mesh as _mesh

__all__ = ["ring_attention_raw", "ring_attention"]

_NEG = -1e9


def _block_attend(q, k, v, scale, mask):
    """One block pair: returns (scores_max, exp_scores @ v, exp row-sums).

    q: [B, sq, N, D], k/v: [B, sk, N, D], mask: [sq, sk] bool or None."""
    s = jnp.einsum("bqnd,bknd->bnqk", q, k) * scale
    if mask is not None:
        s = jnp.where(mask[None, None], s, _NEG)
    m = jnp.max(s, axis=-1)                           # [B, N, sq]
    p = jnp.exp(s - m[..., None])
    if mask is not None:
        p = jnp.where(mask[None, None], p, 0.0)
    o = jnp.einsum("bnqk,bknd->bqnd", p, v)           # [B, sq, N, D]
    l = jnp.sum(p, axis=-1)                           # [B, N, sq]
    return m, o, l


def ring_attention_raw(q, k, v, *, causal=True, axis_name="sp"):
    """Manual-'sp' attention body (call inside shard_map): q/k/v are the
    LOCAL sequence shards [B, s_loc, N, D]."""
    sp = jax.lax.axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    b, s_loc, n, d = q.shape
    scale = float(1.0 / (d ** 0.5))
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    q_pos = rank * s_loc + jnp.arange(s_loc)

    def step(carry, i):
        k_cur, v_cur, m_acc, l_acc, o_acc = carry
        src = (rank - i) % sp                          # owner of current K/V
        k_pos = src * s_loc + jnp.arange(s_loc)
        mask = (q_pos[:, None] >= k_pos[None, :]) if causal else None
        m_blk, o_blk, l_blk = _block_attend(q, k_cur, v_cur, scale, mask)
        # online softmax merge
        m_new = jnp.maximum(m_acc, m_blk)
        alpha = jnp.exp(m_acc - m_new)                 # rescale old
        beta = jnp.exp(m_blk - m_new)                  # rescale new
        l_new = l_acc * alpha + l_blk * beta
        o_new = (o_acc * jnp.transpose(alpha, (0, 2, 1))[..., None]
                 + o_blk * jnp.transpose(beta, (0, 2, 1))[..., None])
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, m_new, l_new, o_new), None

    def _vary(t):
        # mark pp-invariant zeros as sp-varying for the scan carry; values
        # already derived from sharded inputs are varying and pass through
        try:
            return jax.lax.pcast(t, (axis_name,), to="varying")
        except ValueError:
            return t

    m0 = _vary(jnp.full((b, n, s_loc), _NEG, q.dtype))
    l0 = _vary(jnp.zeros((b, n, s_loc), q.dtype))
    o0 = _vary(jnp.zeros_like(q))
    (_, _, _, l_fin, o_fin), _ = jax.lax.scan(
        step, (k, v, m0, l0, o0), jnp.arange(sp))
    denom = jnp.transpose(l_fin, (0, 2, 1))[..., None]  # [B, s_loc, N, 1]
    return o_fin / jnp.maximum(denom, 1e-20)


def ring_attention(q, k, v, *, causal=True, axis_name="sp"):
    """Tensor-level API: q/k/v [B, S, N, D] with S sharded over 'sp'.
    Returns [B, S, N, D] with the same layout."""
    from ...ops import dispatch
    from ...tensor import Tensor

    mesh = _mesh.get_mesh()
    if axis_name not in mesh.axis_names or mesh.shape[axis_name] <= 1:
        # degenerate: plain causal attention
        def plain(q, k, v):
            scale = float(1.0 / (q.shape[-1] ** 0.5))
            s = q.shape[1]
            mask = jnp.tril(jnp.ones((s, s), jnp.bool_)) if causal else None
            m, o, l = _block_attend(q, k, v, scale, mask)
            return o / jnp.transpose(l, (0, 2, 1))[..., None]

        return dispatch.apply(plain, q, k, v, op_name="ring_attention")

    spec = PartitionSpec(None, axis_name, None, None)
    fn = jax.shard_map(
        partial(ring_attention_raw, causal=causal, axis_name=axis_name),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        axis_names=frozenset({axis_name}),
    )
    return dispatch.apply(fn, q, k, v, op_name="ring_attention")
