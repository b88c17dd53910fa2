"""Paged decode attention on TPU — single-query flash-decode over a paged
KV block pool.

The continuous-batching extension of ``decode_attention.py`` (PAPERS.md:
"Ragged Paged Attention", arxiv 2604.15464): serving keeps K/V in a global
pool of fixed-size pages ``[num_pages, H, page_size, D]`` and gives every
decode slot a *page table* — an int32 row naming which pool pages hold its
context, in order.  Memory then scales with live tokens (pages allocated),
not ``batch * max_seq``, and requests of wildly different lengths share one
fixed-shape compiled step.

Kernel shape:
- grid ``(S*H, max_pages)`` — S decode slots, pages of one slot walked in
  table order with online-softmax accumulation (running max m, denominator
  l, fp32 acc), exactly like the contiguous decode kernel's KV blocks.
- the page table and per-slot lengths are **scalar-prefetch** arguments:
  the KV index maps translate (slot, page-slot) -> pool page id BEFORE each
  DMA is issued.  Page-slots at/after a slot's length are clamped to its
  boundary page, so their block index repeats and Pallas elides the copy;
  ``pl.when`` skips their compute — a slot at position p streams and
  computes O(p) cache regardless of ``max_pages``.
- the single query row is sublane-broadcast to 8 rows so every block and
  scratch shape is tile-legal; positions >= length inside the boundary
  page are masked to -inf before the softmax.
- a slot with length 0 (inactive) skips every page's compute and emits
  zeros (the l==0 guard) — the XLA reference defines the same semantics.

Eligibility (``paged_shape_supported``): ``page_size`` a 128-multiple,
``head_dim`` a 64-multiple — a page is one kernel block, so the contiguous
kernel's KV-blocking rules apply to it verbatim (analysis/codes.py, one
GL002 definition).  CPU and ineligible shapes run the numerically-defined
XLA gather reference.  Forward-only: decode never differentiates through
the pool.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attention import NEG_INF, _dot
from .flash_attention import _on_tpu

__all__ = [
    "paged_attention",
    "paged_shape_supported",
    "paged_shape_unsupported_reason",
    "gather_pages",
]


def paged_shape_unsupported_reason(page_size: int, head_dim: int):
    """``None`` when the kernel accepts the pool shape, else the structured
    GL002-coded reason (shared with the graph linter)."""
    from ...analysis.codes import paged_gate_reason

    return paged_gate_reason(page_size, head_dim)


def paged_shape_supported(page_size: int, head_dim: int) -> bool:
    """The ONE eligibility gate for this kernel (mirrors
    decode_attention.decode_shape_supported): page_size a 128-multiple,
    head_dim a 64-multiple.  On TPU hosts an ineligible pool shape is
    reported once per shape with its GL002 reason instead of silently
    falling back to the gather reference."""
    reason = paged_shape_unsupported_reason(page_size, head_dim)
    if reason is not None and _on_tpu():
        from ...analysis.codes import note_fallback

        note_fallback(reason)
    return reason is None


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------

def _paged_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, *rest,
                  scale, page_size, max_pages, num_heads,
                  quantized=False):
    # quantized pools add two (1, 1) per-(page, head) scale inputs whose
    # index map mirrors the KV page translation — dequant happens right
    # after the page DMA (docs/serving.md "Quantized serving")
    if quantized:
        ks_ref, vs_ref, o_ref, acc_sc, m_sc, l_sc = rest
    else:
        o_ref, acc_sc, m_sc, l_sc = rest
    sh = pl.program_id(0)
    pi = pl.program_id(1)
    length = len_ref[sh // num_heads]

    @pl.when(pi == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    # runtime page skip: a page-slot starting at/after `length` holds no
    # valid positions — a slot at position p touches O(p) cache.  length 0
    # (inactive slot) skips everything and finishes with zeros.
    @pl.when(pi * page_size < length)
    def _body():
        q = q_ref[0]                                # [8, D] (row-broadcast)
        if quantized:
            k = k_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0]
            v = v_ref[0, 0].astype(jnp.float32) * vs_ref[0, 0]
        else:
            k = k_ref[0, 0]                         # [page_size, D]
            v = v_ref[0, 0]
        s = _dot(q, k, ((1,), (1,))) * np.float32(scale)  # [8, page_size]
        cols = pi * page_size + jax.lax.broadcasted_iota(
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
        l_sc[...] = jnp.broadcast_to(alpha * l_prev + l_cur, l_sc.shape)

    @pl.when(pi == max_pages - 1)
    def _finish():
        l = l_sc[:, :1]
        l_safe = jnp.where(l == 0.0, np.float32(1.0), l)
        o_ref[0] = (acc_sc[...] / l_safe).astype(o_ref.dtype)


def _paged_pallas(q, k_pool, v_pool, page_tables, lengths, scale,
                  interpret=False, k_scale=None, v_scale=None):
    """q: [S*H, 8, D] (row-broadcast queries), k/v pool:
    [P, H, page_size, D], page_tables: [S, max_pages] int32, lengths:
    [S] int32 -> [S*H, 8, D].  ``interpret=True`` runs the Pallas
    interpreter (CPU numerics check).

    The page table and lengths ride as scalar-prefetch arguments so the KV
    index maps can translate (slot, page-slot) -> pool page BEFORE each
    DMA: page-slots past a slot's valid length clamp to its boundary page
    (repeated block indices elide the copy), and pl.when skips their
    compute."""
    p_, h, page_size, d = k_pool.shape
    s, max_pages = page_tables.shape
    qr = int(q.shape[1])  # tunable query sublane rows (8 by default)
    quantized = k_scale is not None
    kernel = functools.partial(_paged_kernel, scale=scale,
                               page_size=page_size, max_pages=max_pages,
                               num_heads=h, quantized=quantized)
    pt_flat = jnp.reshape(page_tables, (-1,)).astype(jnp.int32)
    len_arr = jnp.reshape(lengths, (-1,)).astype(jnp.int32)

    def kv_index(sh, pi, pt_ref, len_ref):
        slot = sh // h
        last = jnp.maximum((len_ref[slot] - 1) // page_size, 0)
        page = pt_ref[slot * max_pages + jnp.minimum(pi, last)]
        return (page, sh % h, np.int32(0), np.int32(0))

    def scale_index(sh, pi, pt_ref, len_ref):
        slot = sh // h
        last = jnp.maximum((len_ref[slot] - 1) // page_size, 0)
        page = pt_ref[slot * max_pages + jnp.minimum(pi, last)]
        return (page, sh % h)

    # index maps return int32: Mosaic under x64 rejects i64 (a bare 0)
    def q_index(sh, pi, pt_ref, len_ref):
        return (sh, np.int32(0), np.int32(0))

    in_specs = [
        pl.BlockSpec((1, qr, d), q_index),
        pl.BlockSpec((1, 1, page_size, d), kv_index),
        pl.BlockSpec((1, 1, page_size, d), kv_index),
    ]
    operands = [q, k_pool, v_pool]
    if quantized:
        in_specs += [pl.BlockSpec((1, 1), scale_index),
                     pl.BlockSpec((1, 1), scale_index)]
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s * h, max_pages),
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
        out_shape=jax.ShapeDtypeStruct((s * h, qr, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(pt_flat, len_arr, *operands)
    return out


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def _pick_q_rows(page_size: int, d: int, dtype,
                 local_heads=None) -> int:
    """Query sublane-broadcast rows for one pool specialization: the
    autotune table's entry when one exists (``analysis/autotune.py``),
    else the historical 8.  ``local_heads`` (the POST-SHARD head count,
    passed when the pool is sharded per-head over ``mp``) joins the shape
    key so table entries stay valid per shard — the sharded grid
    ``(S*H/mp, max_pages)`` is a different specialization; unsharded
    lookups keep the historical key."""
    from ...analysis import autotune as _autotune

    shape = {"page_size": page_size, "head_dim": d}
    if local_heads is not None:
        shape["num_heads"] = int(local_heads)
    tuned = _autotune.kernel_params("paged_attention", shape, dtype)
    if tuned:
        qr = int(tuned.get("q_rows", 8))
        if qr > 0 and qr % 8 == 0:
            return qr
    return 8


def gather_pages(pool, page_tables, scale=None):
    """Materialize each slot's paged context as a contiguous view.

    pool: [P, H, page_size, D], page_tables: [S, max_pages] int32
    -> [S, H, max_pages*page_size, D].  Position p of slot s lives at
    ``pool[page_tables[s, p // page_size], :, p % page_size]``.  Used by
    the chunked-prefill path (attention over the whole updated context)
    and the XLA decode fallback.  ``scale`` ([P, H] fp32, quantized
    pools) dequantizes each gathered page — the result is then fp32."""
    g = jnp.take(pool, page_tables, axis=0)     # [S, MP, H, ps, D]
    s, mp, h, ps, d = g.shape
    if scale is not None:
        sg = jnp.take(scale, page_tables, axis=0)    # [S, MP, H]
        g = g.astype(jnp.float32) * sg[..., None, None]
    return jnp.transpose(g, (0, 2, 1, 3, 4)).reshape(s, h, mp * ps, d)


@jax.named_scope("kernel.paged_decode")
def paged_attention(q, k_pool, v_pool, page_tables, lengths, *,
                    sm_scale=None, k_scale=None, v_scale=None):
    """Single-query attention over a paged KV block pool.

    q:           [S, H, D]    — the ONE new query per (slot, head)
    k_pool:      [P, H, page_size, D] — the global page pool
    v_pool:      [P, H, page_size, D]
    page_tables: [S, max_pages] int32 — per-slot page ids, table order
    lengths:     [S] int32 — valid positions per slot (0 = inactive slot,
                 defined to return zeros)
    k_scale/v_scale: [P, H] fp32 per-(page, head) dequant scales when the
                 pools are int8 — dequant happens inside the kernel body
                 right after each page DMA, and the output is fp32
    returns      [S, H, D]

    Routes to the Pallas paged flash-decode kernel on TPU when the pool
    shape is eligible, else the XLA gather reference (identical numerics).
    """
    p_, h, page_size, d = k_pool.shape
    scale = float(sm_scale if sm_scale is not None else 1.0 / (d ** 0.5))
    if k_scale is not None:
        q = q.astype(jnp.float32)
    else:
        q = q.astype(k_pool.dtype)
    s = q.shape[0]
    if _on_tpu() and paged_shape_supported(page_size, d):
        # under an active serving-mesh shard the pool's head axis is
        # already LOCAL (H/mp) — key the autotune lookup on it so sharded
        # and unsharded specializations never share a table entry
        from ...distributed import serving_mesh as _srv_mesh

        sharded = _srv_mesh.mp_size(_srv_mesh.active_mesh()) > 1 \
            if _srv_mesh.active_mesh() is not None else False
        qr = _pick_q_rows(page_size, d, k_pool.dtype,
                          local_heads=h if sharded else None)
        q8 = jnp.broadcast_to(q.reshape(s * h, 1, d), (s * h, qr, d))
        out = _paged_pallas(q8, k_pool, v_pool, page_tables, lengths, scale,
                            k_scale=k_scale, v_scale=v_scale)
        return out[:, 0, :].reshape(s, h, d)
    return _xla_paged_reference(q, k_pool, v_pool, page_tables, lengths,
                                scale, k_scale=k_scale, v_scale=v_scale)


def _xla_paged_reference(q, k_pool, v_pool, page_tables, lengths, scale,
                         k_scale=None, v_scale=None, window=None):
    """jnp-composed reference: gather each slot's pages into a contiguous
    view, masked single-query attention, fp32 softmax (the fallback AND
    the parity oracle for tpu_smoke).  Matches
    ``decode_attention._xla_decode_reference`` on contiguous layouts;
    length-0 slots return zeros (the kernel's inactive-slot semantics).
    ``window``: only the newest ``window`` of a slot's positions are read."""
    k = gather_pages(k_pool, page_tables, k_scale)
    v = gather_pages(v_pool, page_tables, v_scale)
    if q.shape[1] != k.shape[1]:
        # grouped queries: pool head j serves query heads G*j .. G*j + G - 1
        group = q.shape[1] // k.shape[1]
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("shd,shkd->shk", q, k,
                   preferred_element_type=jnp.float32) * np.float32(scale)
    lengths = lengths.astype(jnp.int32)
    at = jnp.arange(k.shape[2], dtype=jnp.int32)[None, :]
    valid = at < lengths[:, None]
    if window is not None:
        valid = valid & (at >= lengths[:, None] - np.int32(window))
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(lengths[:, None, None] > 0, p, jnp.zeros_like(p))
    return jnp.einsum("shk,shkd->shd", p.astype(q.dtype), v)
