"""Ragged paged attention on TPU — ONE fused launch for mixed
prefill/decode traffic over the paged KV block pool.

The serving engine's two-phase structure (a ``[1, chunk]`` prefill program
per admitted request plus a separate all-slots decode step, PR 5) left the
``(S*H, max_pages)`` paged grid mostly idle whenever request lengths were
skewed — exactly what production traffic looks like.  Following "Ragged
Paged Attention: A High-Performance and Flexible LLM Inference Kernel for
TPU" (PAPERS.md, arxiv 2604.15464), this kernel flattens the step's work
into token granularity:

- every query token of the step — decode tokens (q_len 1) and prefill
  chunk tokens (q_len > 1) alike — is one row of a flat ``[T, H, D]``
  query buffer; the host packs rows into fixed-size **token blocks**
  (``token_block`` sublane rows, one slot per block, consecutive
  positions) so a prefill chunk fills an MXU pass that the old design
  spent on a single broadcast decode row;
- the grid iterates a host-built **work list** of (token-block, page)
  tuples — one entry per page a block actually has to read, built from
  the scheduler's host mirrors (``build_ragged_plan``).  The work-list
  arrays ride as **scalar-prefetch** arguments so the KV index map
  resolves each entry's POOL page id before its DMA is issued;
- the plan arrays pad to engine-constant maxima, the LAUNCH does not: the
  grid's second dimension is the step's real item count (``n_items``, a
  traced scalar), so one compiled program walks exactly its work list and
  no grid step is spent on the arrays' tail (which repeats the last real
  entry only so that every index it holds stays valid);
- a work item carries ``hb`` heads of its page: the grid is
  ``(H // hb, n_items)`` and a grid step moves ONE ``(1, hb, page, D)``
  block of K and one of V (the heads of a page are contiguous in the
  ``[P, H, page, D]`` pool) against ``(1, hb, QB, D)`` of queries, the
  heads one batched chain under one mask.  ``hb`` follows the launch's own
  shapes (:func:`ragged_head_block`: the largest divisor of the local
  ``H`` whose double-buffered K and V blocks hold a fixed share of the
  scoped VMEM); at every served geometry it is ``H``: one grid step, and
  one DMA a side, an item;
- a K/V head may serve a GROUP of query heads (grouped-query attention:
  the pool holds ``Hkv`` heads, the queries ``Hkv * G``): the group's
  heads are folded into the query block's rows, ``[NB, Hkv, G * QB, D]``,
  so an item's K and V pages are still read once, whatever ``G``; a row's
  token is ``row mod QB``.  With ``G == 1`` the launch, the kernel body and
  the outputs are bit for bit those of a pool with as many heads as queries;
- online softmax accumulates across a block's work items (running max m,
  denominator l, fp32 acc); per-item masking is causal at token
  granularity: row i of block b (absolute position ``blk_base[b] + i``)
  attends pool positions ``<=`` its own, rows past ``blk_rows[b]`` are
  padding (masked everywhere, output rows discarded by the host gather).

Eligibility (``ragged_shape_supported``): the paged kernel's pool rules
verbatim (``page_size`` a 128-multiple, ``head_dim`` a 64-multiple — a
page is one KV block) plus ``token_block`` an 8-multiple (one sublane
tile column); ``analysis/codes.ragged_gate_reason`` is the ONE GL002
definition.  CPU and ineligible shapes run ``_xla_ragged_reference`` — the
paged gather oracle applied per token — which is also the parity oracle
for ``tools/tpu_smoke.py``'s ragged case.  Forward-only: serving never
differentiates through the pool.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attention import NEG_INF, _dot
from .flash_attention import _on_tpu

__all__ = [
    "ragged_paged_attention",
    "ragged_shape_supported",
    "ragged_shape_unsupported_reason",
    "ragged_token_block",
    "ragged_head_block",
    "build_ragged_plan",
    "ragged_plan_shapes",
    "ragged_write_capacity",
    "plan_at_layer",
    "write_list_of",
    "RAGGED_PLAN_FIELDS",
    "RAGGED_ATTEND_FIELDS",
    "RAGGED_WRITE_FIELDS",
]

# the ordered field names of a ragged plan — the host builder emits them,
# the serving engine ships them (as traced int32 Tensors) into the fused
# step, and the kernels consume them positionally: the attention launch its
# work list, the pool write (pool_write.py) its write list
RAGGED_ATTEND_FIELDS = (
    "blk_tok",      # [NB, QB]  flat token index feeding each block row
    "tok_blk",      # [T]       inverse map: token -> its block
    "tok_row",      # [T]       inverse map: token -> its row in the block
    "blk_base",     # [NB]      absolute position of each block's row 0
    "blk_rows",     # [NB]      valid rows per block (0 = padding block)
    "wl_blk",       # [WL]      work item -> token block
    "wl_page",      # [WL]      work item -> POOL page id (pre-translated)
    "wl_pageslot",  # [WL]      work item -> page-slot (for position math)
    "n_items",      # [1]       real work items: the launch's length
)
RAGGED_WRITE_FIELDS = (
    "wr_page",      # [WR]      write item -> POOL page id (pre-translated)
    "wr_group",     # [WR]      write item -> tile group within its page
    "wr_tok",       # [WR, G]   flat token index feeding each group row
    "wr_lo",        # [WR]      first new row of the group
    "wr_n",         # [WR]      new rows of the group (lo .. lo + n - 1)
    "n_writes",     # [1]       real write items: the write launch's length
)
RAGGED_PLAN_FIELDS = RAGGED_ATTEND_FIELDS + RAGGED_WRITE_FIELDS
_PAGE_FIELDS = tuple(RAGGED_PLAN_FIELDS.index(f) for f in ("wl_page", "wr_page"))


def plan_at_layer(plan, page_base):
    """The plan of one layer of a stacked pool ``[L * P, ...]``: the pool
    page ids of both lists offset by the layer's first page."""
    return tuple(a + page_base if i in _PAGE_FIELDS else a
                 for i, a in enumerate(plan))


def write_list_of(plan):
    """The write list (:data:`RAGGED_WRITE_FIELDS`) of a plan's arrays."""
    return tuple(plan[len(RAGGED_ATTEND_FIELDS):])


def ragged_write_capacity(t_max: int, write_group: int, num_runs: int) -> int:
    """The most write items a step of ``t_max`` tokens in ``num_runs`` runs
    can hold: a run of ``c`` tokens touches at most ``c // g + 2`` tile
    groups of ``g`` positions."""
    return t_max // write_group + 2 * num_runs


def ragged_plan_shapes(*, token_block: int, t_max: int, nb_max: int,
                       wl_max: int, write_group: int, wr_max: int):
    """``[(field, shape)]`` of a plan's arrays in :data:`RAGGED_PLAN_FIELDS`
    order: what an engine's packed step input lays out."""
    shapes = {
        "blk_tok": (nb_max, token_block), "tok_blk": (t_max,),
        "tok_row": (t_max,), "blk_base": (nb_max,), "blk_rows": (nb_max,),
        "wl_blk": (wl_max,), "wl_page": (wl_max,), "wl_pageslot": (wl_max,),
        "n_items": (1,),
        "wr_page": (wr_max,), "wr_group": (wr_max,),
        "wr_tok": (wr_max, write_group), "wr_lo": (wr_max,),
        "wr_n": (wr_max,), "n_writes": (1,),
    }
    return [(f, shapes[f]) for f in RAGGED_PLAN_FIELDS]


def ragged_shape_unsupported_reason(page_size: int, head_dim: int,
                                    token_block: int = 8):
    """``None`` when the kernel accepts the layout, else the structured
    GL002-coded reason (shared with the graph linter)."""
    from ...analysis.codes import ragged_gate_reason

    return ragged_gate_reason(page_size, head_dim, token_block)


def ragged_shape_supported(page_size: int, head_dim: int,
                           token_block: int = 8) -> bool:
    """The ONE eligibility gate for this kernel (mirrors
    paged_attention.paged_shape_supported): pool rules verbatim plus the
    token block a sublane multiple.  On TPU hosts an ineligible layout is
    reported once per shape with its GL002 reason instead of silently
    falling back to the gather reference."""
    reason = ragged_shape_unsupported_reason(page_size, head_dim,
                                             token_block)
    if reason is not None and _on_tpu():
        from ...analysis.codes import note_fallback

        note_fallback(reason)
    return reason is None


def ragged_token_block(page_size: int, head_dim: int, dtype,
                       local_heads: Optional[int] = None) -> int:
    """The query token-block size (sublane rows per work item) for one
    pool specialization: the autotune table's entry when one exists
    (``analysis/autotune.py``), else the historical 8.  The serving
    engine asks ONCE at construction — the host-built plan bakes the
    block size into every step's work list.

    ``local_heads``: the POST-SHARD head count when the pool is sharded
    per-head over ``mp`` (docs/serving.md "Sharded serving").  It joins
    the shape key — the sharded launch moves ``H/mp`` heads an item, a
    different specialization than the full-head pool, so a winner
    measured unsharded must not silently dispatch a shard and vice
    versa.  Unsharded lookups keep the historical key (committed table
    entries stay valid)."""
    from ...analysis import autotune as _autotune

    shape = {"page_size": page_size, "head_dim": head_dim}
    if local_heads is not None:
        shape["num_heads"] = int(local_heads)
    tuned = _autotune.kernel_params("ragged_paged_attention", shape, dtype)
    if tuned:
        tb = int(tuned.get("token_block", 8))
        if tb >= 8 and tb % 8 == 0:
            return tb
    return 8


# the scoped VMEM a TPU kernel compiles with (v5e: 16 MiB) and the share of
# it the K and V blocks of a grid step, double-buffered, may take: the rest
# is the query/output blocks, the softmax scratch and the body's temporaries
_SCOPED_VMEM_BYTES = 16 << 20
_KV_BUFFER_SHARE = 0.5


def ragged_head_block(num_heads: int, page_size: int, head_dim: int,
                      dtype) -> int:
    """How many heads of its page one work item moves a grid step (``hb``):
    the largest divisor of ``num_heads`` — the LOCAL head count the launch
    sees, ``H/mp`` under ``shard_map`` — whose K and V blocks
    ``(1, hb, page_size, head_dim)`` of the pool's ``dtype``, two buffers
    each, stay within ``_KV_BUFFER_SHARE`` of the scoped VMEM.  A pure
    function of the launch's own shapes: the kernel and the engine's
    ``ragged_heads_per_block`` gauge both ask it, nothing is configured.
    16 heads of a 128 x 128 bf16 page are 2 MiB of buffers, 40 are 5 MiB:
    every served geometry moves all its heads, one contiguous DMA of K and
    one of V an item."""
    num_heads = int(num_heads)
    per_head = 4 * int(page_size) * int(head_dim) * jnp.dtype(dtype).itemsize
    fit = max(int(_SCOPED_VMEM_BYTES * _KV_BUFFER_SHARE) // per_head, 1)
    return max(hb for hb in range(1, num_heads + 1)
               if num_heads % hb == 0 and hb <= fit)


# ---------------------------------------------------------------------------
# host-side plan construction (numpy; built from the scheduler mirrors)
# ---------------------------------------------------------------------------

def _build_write_list(bases, counts, starts, tables, *, g: int,
                      page_size: int, t_max: int, wr_max: int):
    """The write items of a step's runs (numpy over all runs at once): one
    a tile group of ``g`` positions that a run's new positions touch."""
    first = bases // g
    per_run = (bases + counts - 1) // g - first + 1
    n_writes = int(per_run.sum())
    if n_writes > wr_max:
        raise ValueError(f"plan overflow: {n_writes} write items > "
                         f"wr_max={wr_max}")
    run = np.repeat(np.arange(len(bases)), per_run)
    ends = np.cumsum(per_run)
    grp = first[run] + np.arange(n_writes) - (ends - per_run)[run]
    p0 = np.maximum(grp * g, bases[run])                # first new position
    p1 = np.minimum((grp + 1) * g, (bases + counts)[run])
    groups_a_page = page_size // g
    page = tables[run, grp // groups_a_page]
    group = grp % groups_a_page
    if len(np.unique(page * groups_a_page + group)) != n_writes:
        raise ValueError("two write items of one step name one tile group: "
                         "a page is written by the one slot that owns it")
    lo = p0 - grp * g
    tok = starts[run] + p0 - bases[run]
    fields = {"wr_page": page, "wr_group": group, "wr_lo": lo,
              "wr_n": p1 - p0}
    out = {}
    for name, real in fields.items():
        # the tail repeats the last real item: valid indices, never walked
        out[name] = np.full((wr_max,), real[-1], np.int32)
        out[name][:n_writes] = real
    # the token feeding each row of a group: consecutive tokens, clipped
    # into the step (rows outside lo .. lo + n - 1 are masked in the launch)
    out["wr_tok"] = np.zeros((wr_max, g), np.int32)
    out["wr_tok"][:n_writes] = np.clip(
        (tok - lo)[:, None] + np.arange(g), 0, t_max - 1)
    out["n_writes"] = np.array([n_writes], np.int32)
    return out


def build_ragged_plan(runs: Sequence[Tuple[int, int, np.ndarray]], *,
                      token_block: int, page_size: int,
                      t_max: int, nb_max: int, wl_max: int,
                      write_group: int = 8, wr_max: Optional[int] = None,
                      window: Optional[int] = None
                      ) -> Tuple[Dict[str, np.ndarray], Dict[str, int]]:
    """Flatten one fused step's work into the kernel's plan arrays.

    ``runs``: one entry per contiguous token run — a decode slot (count 1)
    or a prefill chunk (count up to the step's token budget) — as
    ``(base_pos, count, table_row)`` where ``table_row`` is the slot's
    int32 page-table row.  Token flat order is run-major: run r's tokens
    occupy flat indices ``[start_r, start_r + count_r)`` in submission
    order (``stats["run_starts"]`` reports the starts).

    Every array is padded to its fixed maximum (``t_max``/``nb_max``/
    ``wl_max``) so the compiled step never retraces; the kernel's grid
    ends at ``n_items``, so the work-list tail is never walked: it
    REPEATS the last real entry only to hold valid indices (the last
    item's look-ahead reads one).  Padding block-gather rows point at the
    block's first token (a valid index; the row is masked in-kernel and
    discarded by the output gather).

    Beside the work list the WRITE LIST (pool_write.py): one item a tile
    group of ``write_group`` positions (the sublane tile of the pool's
    dtype, ``pool_write_group``; a divisor of ``page_size``) that a run's
    new positions touch, ``wr_max`` of them at most (by default
    :func:`ragged_write_capacity` of ``nb_max`` runs, which bounds any
    step: every run holds a block; an engine passes its tighter one, of a
    run a slot).
    No two items may name one group of one page: a repeat raises.

    ``window``: the plan of a window layer.  A block's items are then only
    the page-slots that hold a position one of its rows may read
    (``> base_pos - window``), and ``table_row`` may be a RING: page-slot
    ``j`` of a slot whose ring has ``R`` pages names ring page ``j mod R``
    (``wl_pageslot`` stays the logical ``j``, which is what the kernel's
    position arithmetic reads).

    Returns ``(plan_arrays, stats)``: the arrays keyed by
    :data:`RAGGED_PLAN_FIELDS`, and stats with ``n_tokens``/``n_blocks``/
    ``n_items``/``n_writes``/``run_starts``, the occupancy numerators the
    serving metrics report, and ``launched_items`` (the launch's second
    grid dimension for this step)."""
    qb, g = int(token_block), int(write_group)
    if g < 1 or page_size % g:
        raise ValueError(f"write_group={g} must divide page_size={page_size}")
    if wr_max is None:
        wr_max = ragged_write_capacity(t_max, g, nb_max)
    if not runs:
        raise ValueError("empty plan: the fused step must not be "
                         "dispatched with no runs")
    # every run at once (numpy): a step of 64 decode slots and a long
    # chunk is some hundred blocks and a thousand items, and a Python loop
    # over them cost the host milliseconds a step
    bases = np.array([r[0] for r in runs], np.int64)
    counts = np.array([r[1] for r in runs], np.int64)
    tables = np.stack([np.asarray(r[2]) for r in runs])
    if (counts < 1).any():
        raise ValueError(f"run with count={int(counts.min())}; every run "
                         "must carry at least one token")
    starts = np.cumsum(counts) - counts             # a run's first flat token
    t = int(counts.sum())
    if t > t_max:
        raise ValueError(f"plan overflow: {t} tokens > t_max={t_max}")
    run_blocks = -(-counts // qb)
    b = int(run_blocks.sum())
    if b > nb_max:
        raise ValueError(f"plan overflow: {b} blocks > nb_max={nb_max}")
    run_starts: List[int] = [int(x) for x in starts]
    blk_run = np.repeat(np.arange(len(runs)), run_blocks)
    first_blk = np.cumsum(run_blocks) - run_blocks
    off = (np.arange(b) - first_blk[blk_run]) * qb  # a block's offset in its run
    rows = np.minimum(qb, counts[blk_run] - off)
    lane = np.arange(qb)
    blk_tok = np.zeros((nb_max, qb), np.int32)
    # a block's padding rows point at its first token
    blk_tok[:b] = ((starts[blk_run] + off)[:, None]
                   + np.where(lane[None, :] < rows[:, None], lane[None, :], 0))
    blk_base = np.zeros((nb_max,), np.int32)
    blk_base[:b] = bases[blk_run] + off
    blk_rows = np.zeros((nb_max,), np.int32)
    blk_rows[:b] = rows
    tok_run = np.repeat(np.arange(len(runs)), counts)
    within = np.arange(t) - starts[tok_run]
    tok_blk = np.zeros((t_max,), np.int32)
    tok_blk[:t] = first_blk[tok_run] + within // qb
    tok_row = np.zeros((t_max,), np.int32)
    tok_row[:t] = within % qb
    # a block's items: the page-slots up to its last row's, from the first
    # its window reaches (the first of all without one)
    last_slot = (blk_base[:b].astype(np.int64) + rows - 1) // page_size
    first_slot = (np.zeros((b,), np.int64) if window is None else
                  np.maximum(blk_base[:b] - int(window) + 1, 0) // page_size)
    per_blk = last_slot - first_slot + 1
    n_items = int(per_blk.sum())
    if n_items > wl_max:
        raise ValueError(f"plan overflow: {n_items} work items > "
                         f"wl_max={wl_max}")
    item_blk = np.repeat(np.arange(b), per_blk)
    item_slot = (first_slot[item_blk] + np.arange(n_items)
                 - (np.cumsum(per_blk) - per_blk)[item_blk])
    item_page = tables[blk_run[item_blk], item_slot]
    # the tail repeats the last real item: valid indices, never walked
    wl_blk = np.full((wl_max,), item_blk[-1], np.int32)
    wl_page = np.full((wl_max,), item_page[-1], np.int32)
    wl_ps = np.full((wl_max,), item_slot[-1], np.int32)
    wl_blk[:n_items] = item_blk
    wl_page[:n_items] = item_page
    wl_ps[:n_items] = item_slot
    plan = {
        "blk_tok": blk_tok, "tok_blk": tok_blk, "tok_row": tok_row,
        "blk_base": blk_base, "blk_rows": blk_rows,
        "wl_blk": wl_blk, "wl_page": wl_page, "wl_pageslot": wl_ps,
        "n_items": np.array([n_items], np.int32),
    }
    plan.update(_build_write_list(
        bases, counts, starts, tables,
        g=g, page_size=page_size, t_max=t_max, wr_max=int(wr_max)))
    stats = {
        "n_tokens": t, "n_blocks": b, "n_items": n_items,
        "n_writes": int(plan["n_writes"][0]),
        "run_starts": run_starts,
        # occupancy: the fraction of the work-list arrays holding real
        # items and of the block rows carrying real queries
        "wl_capacity": wl_max,
        "row_capacity": b * qb,
        # the launch's second grid dimension for this step: the kernel's
        # grid ends at n_items, whatever the arrays' capacity
        "launched_items": n_items,
    }
    return plan, stats


# ---------------------------------------------------------------------------
# kernel
# ---------------------------------------------------------------------------

def _ragged_kernel(blk_ref, page_ref, ps_ref, ni_ref, base_ref, rows_ref,
                   q_ref, k_ref, v_ref, *rest, scale, page_size, wl_max,
                   quantized=False, group=1, window=None):
    # quantized pools carry two extra (1, hb) scale inputs whose index map
    # mirrors the KV page index — each page's per-head absmax scales ride
    # the same scalar-prefetched translation, so the dequant multiply
    # happens right after the page DMA with no extra HBM round-trip
    if quantized:
        ks_ref, vs_ref, o_ref, acc_sc, m_sc, l_sc = rest
    else:
        o_ref, acc_sc, m_sc, l_sc = rest
    # the grid's second dimension is n_items, so every step is a real item
    w = pl.program_id(1)
    n = ni_ref[0]
    blk = blk_ref[w]
    # block boundaries derived from the prefetched work list: a block's
    # items are contiguous, so its first/last entries bracket its online-
    # softmax accumulation.  The look-ahead at item n-1 may read the
    # array's clamped tail (the last real block again), hence `w == n - 1`.
    first = jnp.logical_or(w == 0, blk_ref[jnp.maximum(w - 1, 0)] != blk)
    last = jnp.logical_or(w == n - 1,
                          blk_ref[jnp.minimum(w + 1, wl_max - 1)] != blk)

    @pl.when(first)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    qb = q_ref.shape[2]
    # the mask is the item's, not a head's: computed once a step.
    # Token-granular causality: row i sits at absolute position
    # blk_base + i and may read every pool position <= its own; rows
    # past blk_rows are block padding (masked everywhere — their
    # output rows are finite garbage the host gather never reads).
    # Under grouped queries the block's rows are the group's heads one
    # after another, so a row's token is its index within its head's rows
    rows = jax.lax.broadcasted_iota(jnp.int32, (qb, page_size), 0)
    if group > 1:
        rows = jax.lax.rem(rows, jnp.full_like(rows, qb // group))
    cols = ps_ref[w] * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (qb, page_size), 1)
    row_pos = base_ref[blk] + rows
    valid = jnp.logical_and(cols <= row_pos, rows < rows_ref[blk])
    if window is not None:
        # a window layer: the newest ``window`` positions, the row's own
        # among them (static, so that without one the body is what it was)
        valid = jnp.logical_and(valid, cols > row_pos - np.int32(window))

    # the item's hb heads as ONE batched chain: each head's operations are
    # those a grid step ran while a step moved one head, in the same order
    # (the same bits), and the heads share nothing but the mask, so the
    # scheduler has hb independent chains to interleave under the next
    # item's DMA.  Batched, not a loop over k_ref[0, h] that updates the
    # scratch a head: on a v5e the loop's stores order the heads and an
    # item costs its arithmetic (2.3 us at 16 heads), where the batched
    # chain costs its DMA (1.4 us; PERF.md section 6, PR 32)
    q = q_ref[0]                                # [hb, QB, D]
    if quantized:
        # in-kernel dequant: int8 page x its (page, head) scale ->
        # fp32 operands (q arrives fp32 on this path; the online-
        # softmax accumulation below is fp32 regardless)
        k = k_ref[0].astype(jnp.float32) * ks_ref[0][:, None, None]
        v = v_ref[0].astype(jnp.float32) * vs_ref[0][:, None, None]
    else:
        k = k_ref[0]                            # [hb, page_size, D]
        v = v_ref[0]
    heads = ((0,), (0,))
    s = _dot(q, k, ((2,), (2,)), heads) * np.float32(scale)
    s = jnp.where(valid[None], s, NEG_INF)      # [hb, QB, page_size]

    m_prev = m_sc[:, :, :1]                     # [hb, QB, 1]
    l_prev = l_sc[:, :, :1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    l_cur = jnp.sum(p, axis=-1, keepdims=True)
    alpha = jnp.exp(m_prev - m_new)
    acc_sc[...] = acc_sc[...] * alpha + _dot(p.astype(v.dtype), v,
                                             ((2,), (1,)), heads)
    m_sc[...] = jnp.broadcast_to(m_new, m_sc.shape)
    l_sc[...] = jnp.broadcast_to(alpha * l_prev + l_cur, l_sc.shape)

    @pl.when(last)
    def _finish():
        l = l_sc[:, :, :1]
        l_safe = jnp.where(l == 0.0, np.float32(1.0), l)
        o_ref[0] = (acc_sc[...] / l_safe).astype(o_ref.dtype)


def _ragged_pallas(q_blocks, k_pool, v_pool, wl_blk, wl_page, wl_ps,
                   n_items, blk_base, blk_rows, scale, interpret=False,
                   k_scale=None, v_scale=None, head_block=None, group=1,
                   window=None):
    """q_blocks: [NB, H, QB, D] host-packed token blocks; k/v pool:
    [P, H, page_size, D]; work-list + per-block arrays as documented on
    :data:`RAGGED_PLAN_FIELDS` -> [NB, H, QB, D].  ``interpret=True`` runs
    the Pallas interpreter (CPU numerics check).  ``group`` > 1: each of
    the pool's ``H`` heads serves ``group`` query heads, folded into the
    block's rows (``QB`` here is ``group`` x the token block).  ``window``
    (a static int) masks keys at ``pos_k <= pos_q - window`` as well.

    The grid is ``(H // hb, n_items)`` — head blocks parallel, work items
    sequential so a block's online softmax accumulates across its pages —
    with ``hb`` = :func:`ragged_head_block` of the operands' own shapes
    (``H`` at every geometry a cell runs: the grid is ``(1, n_items)``)
    and ``n_items`` the traced item count: the arrays keep their constant
    ``wl_max`` length (one compile, no retrace), the launch is as long as
    the step's work list.  A grid step moves ``hb`` heads of its item's
    page — ONE ``(1, hb, page_size, D)`` block of K and one of V, which
    are contiguous in the pool — against ``(1, hb, QB, D)`` of queries.
    All plan arrays ride as scalar prefetch: the KV index map reads the
    work item's POOL page id (pre-translated on host) before each DMA,
    the q/out index maps its block.  Consecutive items of one block
    repeat the q/out block index (copies elided).

    ``head_block`` overrides ``hb`` (a divisor of ``H``) so that a test
    can walk ``H // hb > 1`` at small shapes; nothing else passes it."""
    nb, h, qb, d = q_blocks.shape
    page_size = k_pool.shape[2]
    wl_max = wl_blk.shape[0]
    quantized = k_scale is not None
    hb = (ragged_head_block(h, page_size, d, k_pool.dtype)
          if head_block is None else int(head_block))
    if hb < 1 or h % hb:
        raise ValueError(f"head_block={hb} must divide num_heads={h}")
    if k_pool.shape[1] != h or qb % group:
        raise ValueError(
            f"query blocks {q_blocks.shape} do not fold group={group} "
            f"query heads onto the pool's {k_pool.shape[1]} heads")
    kernel = functools.partial(_ragged_kernel, scale=scale,
                               page_size=page_size, wl_max=wl_max,
                               quantized=quantized, group=int(group),
                               **({} if window is None
                                  else {"window": int(window)}))

    # hh: the grid step's head BLOCK (heads hh*hb .. hh*hb + hb - 1)
    def q_index(hh, w, blk_ref, page_ref, ps_ref, ni_ref, base_ref,
                rows_ref):
        return (blk_ref[w], hh, np.int32(0), np.int32(0))

    def kv_index(hh, w, blk_ref, page_ref, ps_ref, ni_ref, base_ref,
                 rows_ref):
        return (page_ref[w], hh, np.int32(0), np.int32(0))

    def scale_index(hh, w, blk_ref, page_ref, ps_ref, ni_ref, base_ref,
                    rows_ref):
        return (page_ref[w], hh)

    in_specs = [
        pl.BlockSpec((1, hb, qb, d), q_index),
        pl.BlockSpec((1, hb, page_size, d), kv_index),
        pl.BlockSpec((1, hb, page_size, d), kv_index),
    ]
    operands = [q_blocks, k_pool, v_pool]
    if quantized:
        in_specs += [pl.BlockSpec((1, hb), scale_index),
                     pl.BlockSpec((1, hb), scale_index)]
        operands += [k_scale, v_scale]
    n_items = jnp.reshape(n_items, (1,)).astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(h // hb, n_items[0]),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hb, qb, d), q_index),
        scratch_shapes=[
            pltpu.VMEM((hb, qb, d), jnp.float32),
            pltpu.VMEM((hb, qb, 128), jnp.float32),
            pltpu.VMEM((hb, qb, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nb, h, qb, d), q_blocks.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(wl_blk.astype(jnp.int32), wl_page.astype(jnp.int32),
      wl_ps.astype(jnp.int32), n_items,
      blk_base.astype(jnp.int32), blk_rows.astype(jnp.int32),
      *operands)
    return out


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

@jax.named_scope("kernel.ragged")
def ragged_paged_attention(q, k_pool, v_pool, token_tables, lengths, plan,
                           *, sm_scale=None, interpret=False,
                           k_scale=None, v_scale=None, window=None):
    """Token-granular attention over the paged KV pool for one fused
    mixed prefill/decode step.

    q:            [T, Hq, D]  — EVERY query token of the step, flat
                  (decode tokens and prefill chunk tokens mixed); ``Hq`` is
                  the pool's ``H`` or a multiple ``G`` of it (pool head
                  ``j`` then serves query heads ``G*j .. G*j + G - 1``)
    k_pool:       [P, H, page_size, D] — the global page pool
    v_pool:       [P, H, page_size, D]
    token_tables: [T, max_pages] int32 — each token's SLOT page-table row
                  (consumed by the gather fallback; the kernel path reads
                  pool pages straight from the pre-translated work list)
    lengths:      [T] int32 — valid context per token (position + 1)
    plan:         the :data:`RAGGED_PLAN_FIELDS` arrays from
                  :func:`build_ragged_plan` (the :data:`RAGGED_ATTEND_FIELDS`
                  lead; the write list behind them is not read here)
    k_scale/v_scale: [P, H] fp32 per-(page, head) absmax scales when the
                  pool is int8 (docs/serving.md "Quantized serving") —
                  dequant happens INSIDE the kernel right after each
                  page DMA; the output is then fp32
    window:       a static int or None: a token attends the newest
                  ``window`` positions up to its own only (keys at ``pos_k
                  <= pos_q - window`` are masked); the plan then lists only
                  the pages that hold them (``build_ragged_plan(window=)``)
                  and ``token_tables`` may name any page elsewhere
    returns       [T, Hq, D]

    Routes to the Pallas ragged kernel on TPU when the layout is eligible,
    else the XLA gather reference (identical numerics; also the CPU
    serving path)."""
    p_, h, page_size, d = k_pool.shape
    hq = q.shape[1]
    if hq % h:
        raise ValueError(f"{hq} query heads over a pool of {h} heads: the "
                         "pool's head count must divide the queries'")
    group = hq // h
    scale = float(sm_scale if sm_scale is not None else 1.0 / (d ** 0.5))
    if k_scale is not None:
        # int8 pool: q joins the fp32 dequant epilogue, NOT the pool
        # dtype — an int8 q would destroy the query values outright, and
        # an implicit promotion would trip GL001
        q = q.astype(jnp.float32)
    else:
        q = q.astype(k_pool.dtype)
    (blk_tok, tok_blk, tok_row, blk_base, blk_rows,
     wl_blk, wl_page, wl_ps, n_items) = plan[:len(RAGGED_ATTEND_FIELDS)]
    qb = int(blk_tok.shape[1])
    use_kernel = (_on_tpu() and ragged_shape_supported(page_size, d, qb)) \
        or interpret
    if use_kernel:
        nb = blk_tok.shape[0]
        qg = jnp.take(q, jnp.reshape(blk_tok, (-1,)), axis=0)
        if group == 1:
            qg = jnp.transpose(qg.reshape(nb, qb, h, d), (0, 2, 1, 3))
        else:       # [NB, H, G * QB, D]: a pool head's query heads as rows
            qg = jnp.transpose(qg.reshape(nb, qb, h, group, d),
                               (0, 2, 3, 1, 4)).reshape(nb, h, group * qb, d)
        # one query head a pool head: the call the launch always was
        grouped = {} if group == 1 else {"group": group}
        if window is not None:
            grouped["window"] = int(window)
        out = _ragged_pallas(qg, k_pool, v_pool, wl_blk, wl_page, wl_ps,
                             n_items, blk_base, blk_rows, scale,
                             interpret=interpret,
                             k_scale=k_scale, v_scale=v_scale, **grouped)
        if group == 1:
            flat = jnp.transpose(out, (0, 2, 1, 3)).reshape(nb * qb, h, d)
        else:
            flat = jnp.transpose(out.reshape(nb, h, group, qb, d),
                                 (0, 3, 1, 2, 4)).reshape(nb * qb, hq, d)
        idx = tok_blk.astype(jnp.int32) * qb + tok_row.astype(jnp.int32)
        return jnp.take(flat, idx, axis=0)
    return _xla_ragged_reference(q, k_pool, v_pool, token_tables, lengths,
                                 scale, k_scale=k_scale, v_scale=v_scale,
                                 window=window)


def _xla_ragged_reference(q, k_pool, v_pool, token_tables, lengths, scale,
                          k_scale=None, v_scale=None, window=None):
    """jnp-composed reference: the paged gather oracle applied per TOKEN —
    each flat query token gathers its slot's pages and runs masked
    single-query attention over its own ``length`` positions (fp32
    softmax).  BITWISE ``paged_attention._xla_paged_reference`` with the
    per-token tables/lengths, which makes the old per-slot decode
    semantics a strict special case (T == num_slots, one token per slot).
    The fallback AND the parity oracle for tpu_smoke's ragged case;
    length-0 tokens return zeros.  Quantized pools (``k_scale`` given)
    dequantize per gathered page inside the oracle — same contract as
    the kernel's in-body dequant."""
    from .paged_attention import _xla_paged_reference

    return _xla_paged_reference(q, k_pool, v_pool, token_tables, lengths,
                                scale, k_scale=k_scale, v_scale=v_scale,
                                window=window)
