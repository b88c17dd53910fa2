"""Decode attention on TPU — single-query flash-decode over a KV cache.

The serving analog of ``flash_attention.py``: autoregressive decode issues
ONE query per (batch, head) against a preallocated ``[B, H, max_seq, D]``
cache of which only the first ``length`` positions are valid.  The training
flash kernel is the wrong tool here (its q axis is blocked at >=128 rows);
decode throughput on TPU is dominated by a specialized q-len-1 kernel over
the cache (PAPERS.md: "Ragged Paged Attention", arxiv 2604.15464).

Kernel shape:
- grid ``(B*H, n_kv)`` — KV blocked over ``max_seq``; online-softmax
  accumulation (running max m, denominator l, fp32 acc) across KV blocks.
- the single query row is sublane-broadcast to 8 rows so every block/
  scratch shape is tile-legal ((8, 128) fp32 tiling); the MXU pass for a
  [8, D] x [D, block_kv] dot costs the same as [1, D], so nothing is lost.
- ``length`` is a scalar-prefetch argument: the KV index maps clamp
  blocks past ``length`` to the boundary block (repeated indices elide
  the DMA) and ``pl.when`` skips their compute — decode at position p
  both reads AND computes O(p) cache, not O(max_seq).
- positions >= length inside the boundary block are masked to -inf before
  the softmax (the length mask).

CPU (and shape-ineligible calls) fall back to the numerically-identical
XLA expression, same eligibility pattern as ``flash_attention.py``.  The
kernel is forward-only: decode never differentiates through the cache.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = np.float32(-1e30)

from .flash_attention import _on_tpu  # noqa: E402  (shared platform gate)


def decode_shape_unsupported_reason(max_seq: int, head_dim: int):
    """``None`` when the kernel accepts the cache shape, else the
    structured GL002-coded reason (shared with the graph linter)."""
    from ...analysis.codes import decode_gate_reason

    return decode_gate_reason(max_seq, head_dim)


def decode_shape_supported(max_seq: int, head_dim: int) -> bool:
    """The ONE eligibility gate for this kernel (mirrors
    flash_attention.shape_supported so callers can't drift): the cache's
    seq axis divisible into 128-multiple KV blocks, head dim a 64
    multiple.  On TPU hosts an ineligible cache shape is reported once
    per shape with its GL002 reason instead of silently falling back."""
    reason = decode_shape_unsupported_reason(max_seq, head_dim)
    if reason is not None and _on_tpu():
        from ...analysis.codes import note_fallback

        note_fallback(reason)
    return reason is None


def _dot(a, b, dims, batch=((), ())):
    """MXU dot, fp32 accumulation; same precision discipline as the flash
    kernel's _dot (HIGHEST only when both operands are fp32 — under
    "highest" Mosaic rejects bf16 operands).  ``batch``: the operands'
    batch dimensions (the ragged kernel's leading head axis)."""
    fp32 = (jnp.dtype(a.dtype) == jnp.float32
            and jnp.dtype(b.dtype) == jnp.float32)
    return jax.lax.dot_general(
        a, b, (dims, batch),
        precision=(jax.lax.Precision.HIGHEST if fp32
                   else jax.lax.Precision.DEFAULT),
        preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------

def _decode_kernel(len_ref, q_ref, k_ref, v_ref, *rest,
                   scale, block_kv, n_kv, quantized=False):
    if quantized:
        ks_ref, vs_ref, o_ref, acc_sc, m_sc, l_sc = rest
    else:
        o_ref, acc_sc, m_sc, l_sc = rest
    kv_i = pl.program_id(1)
    length = len_ref[0]

    @pl.when(kv_i == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    # runtime block skip: a KV block starting at/after `length` holds no
    # valid positions — decode at position p touches O(p) cache
    @pl.when(kv_i * block_kv < length)
    def _body():
        q = q_ref[0]                                # [8, D] (row-broadcast)
        if quantized:
            # dequantize right after the DMA: the int8 block becomes fp32
            # in VMEM only — no HBM round-trip for dequantized cache
            k = k_ref[0].astype(jnp.float32) * ks_ref[0, 0]
            v = v_ref[0].astype(jnp.float32) * vs_ref[0, 0]
        else:
            k = k_ref[0]                            # [block_kv, D]
            v = v_ref[0]
        s = _dot(q, k, ((1,), (1,))) * np.float32(scale)   # [8, block_kv]
        cols = kv_i * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(cols < length, s, NEG_INF)

        m_prev = m_sc[:, :1]                        # [8, 1]
        l_prev = l_sc[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        l_cur = jnp.sum(p, axis=-1, keepdims=True)
        alpha = jnp.exp(m_prev - m_new)
        acc_sc[...] = acc_sc[...] * alpha + _dot(p.astype(v.dtype), v,
                                                 ((1,), (0,)))
        m_sc[...] = jnp.broadcast_to(m_new, m_sc.shape)
        l_sc[...] = jnp.broadcast_to(l_new := alpha * l_prev + l_cur,
                                     l_sc.shape)

    @pl.when(kv_i == n_kv - 1)
    def _finish():
        l = l_sc[:, :1]
        l_safe = jnp.where(l == 0.0, np.float32(1.0), l)
        o_ref[0] = (acc_sc[...] / l_safe).astype(o_ref.dtype)


def _pick_block_kv(s: int) -> int:
    from ...analysis.codes import default_block

    return default_block(s)


def _pick_params(s: int, d: int, dtype):
    """(block_kv, q_rows) for one cache specialization: the autotune
    table's entry for this exact (max_seq, head_dim, dtype) key when one
    exists (``analysis/autotune.py``), else the historical hard-coded
    choice (largest 128-multiple divisor up to 512, 8 query sublane
    rows)."""
    from ...analysis import autotune as _autotune

    tuned = _autotune.kernel_params(
        "decode_attention", {"max_seq": s, "head_dim": d}, dtype)
    if tuned:
        bkv = int(tuned.get("block_kv", 0))
        qr = int(tuned.get("q_rows", 8))
        if bkv > 0 and s % bkv == 0 and qr > 0 and qr % 8 == 0:
            return bkv, qr
    return _pick_block_kv(s), 8


def _decode_pallas(q, k, v, length, scale, interpret=False, block_kv=None,
                   k_scale=None, v_scale=None):
    """q: [BH, q_rows, D] (row-broadcast query; q_rows is the tunable
    sublane layout, 8 by default), k/v: [BH, S, D], length: scalar int32
    -> [BH, q_rows, D].  ``interpret=True`` runs the kernel through the
    Pallas interpreter (CPU numerics check); ``block_kv`` overrides the
    KV blocking (autotune table / sweep probes).

    ``length`` rides as a scalar-prefetch argument so the KV index maps
    can see it BEFORE each DMA is issued: blocks past the valid length are
    clamped to the boundary block, and Pallas elides copies whose block
    index repeats the previous grid step's — so a decode at position p
    streams O(p) cache from HBM, not O(max_seq).  (A pl.when alone would
    only skip the compute; BlockSpec copies fire regardless.)"""
    bh, s, d = k.shape
    qr = int(q.shape[1])
    block_kv = int(block_kv or _pick_block_kv(s))
    n_kv = s // block_kv
    quantized = k_scale is not None
    kernel = functools.partial(_decode_kernel, scale=scale,
                               block_kv=block_kv, n_kv=n_kv,
                               quantized=quantized)
    len_arr = jnp.reshape(length, (1,)).astype(jnp.int32)

    def kv_index(b, ki, len_ref):
        # lax.div, not `//`: jnp.floor_divide in an index map sends the
        # Mosaic lowering under x64 into unbounded recursion (truncation
        # differs from floor only at length 0, which the max() covers)
        last = jnp.maximum(
            jax.lax.div(len_ref[0] - 1, np.int32(block_kv)), 0)
        return (b, jnp.minimum(ki, last), np.int32(0))

    # index maps return int32: Mosaic under x64 rejects i64 (a bare 0)
    def q_index(b, ki, len_ref):
        return (b, np.int32(0), np.int32(0))

    in_specs = [
        pl.BlockSpec((1, qr, d), q_index),
        pl.BlockSpec((1, block_kv, d), kv_index),
        pl.BlockSpec((1, block_kv, d), kv_index),
    ]
    operands = [q, k, v]
    if quantized:
        in_specs += [pl.BlockSpec(
            (1, 1), lambda b, ki, len_ref: (b, np.int32(0)))] * 2
        operands += [k_scale.reshape(bh, 1).astype(jnp.float32),
                     v_scale.reshape(bh, 1).astype(jnp.float32)]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(bh, n_kv),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, qr, d), q_index),
        scratch_shapes=[
            pltpu.VMEM((qr, d), jnp.float32),
            pltpu.VMEM((qr, 128), jnp.float32),
            pltpu.VMEM((qr, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bh, qr, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(len_arr, *operands)
    return out


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

@jax.named_scope("kernel.decode")
def decode_attention(q, k_cache, v_cache, length, *, sm_scale=None,
                     k_scale=None, v_scale=None):
    """Single-query attention over a preallocated KV cache.

    q:        [B, H, D]   — the ONE new query per (batch, head)
    k_cache:  [B, H, S, D] (S = max_seq, preallocated)
    v_cache:  [B, H, S, D]
    length:   scalar int — number of valid cache positions (traced OK)
    k_scale/v_scale: [B, H] fp32 per-(batch, head) dequant scales when
              the cache is int8 — dequant happens inside the kernel body
              right after each KV-block DMA, and the output is fp32
    returns   [B, H, D]

    Routes to the Pallas flash-decode kernel on TPU when the cache shape
    is eligible, else the XLA expression (identical numerics).
    """
    b, h, s, d = k_cache.shape
    scale = float(sm_scale if sm_scale is not None else 1.0 / (d ** 0.5))
    if k_scale is not None:
        q = q.astype(jnp.float32)
    else:
        q = q.astype(k_cache.dtype)
    if _on_tpu() and decode_shape_supported(s, d):
        # sublane-broadcast the query row so blocks are tile-legal; the
        # row count and KV blocking come from the autotune table when a
        # measured entry exists for this cache specialization
        block_kv, qr = _pick_params(s, d, k_cache.dtype)
        q8 = jnp.broadcast_to(q.reshape(b * h, 1, d), (b * h, qr, d))
        out = _decode_pallas(q8, k_cache.reshape(b * h, s, d),
                             v_cache.reshape(b * h, s, d),
                             length, scale, block_kv=block_kv,
                             k_scale=k_scale, v_scale=v_scale)
        return out[:, 0, :].reshape(b, h, d)
    return _xla_decode_reference(q, k_cache, v_cache, length, scale,
                                 k_scale=k_scale, v_scale=v_scale)


def _xla_decode_reference(q, k_cache, v_cache, length, scale,
                          k_scale=None, v_scale=None):
    """jnp-composed reference: masked single-query attention, fp32
    softmax (the fallback AND the parity oracle for tpu_smoke)."""
    if k_scale is not None:
        k_cache = k_cache.astype(jnp.float32) * k_scale[:, :, None, None]
        v_cache = v_cache.astype(jnp.float32) * v_scale[:, :, None, None]
    s = jnp.einsum("bhd,bhsd->bhs", q, k_cache,
                   preferred_element_type=jnp.float32) * np.float32(scale)
    valid = jnp.arange(k_cache.shape[2]) < length
    s = jnp.where(valid[None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhs,bhsd->bhd", p.astype(q.dtype), v_cache)
