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
  query buffer; the host packs rows into **token blocks** (one slot per
  block, consecutive positions) so a prefill chunk fills an MXU pass that
  the old design spent on a single broadcast decode row;
- **the rows a block carries follow the run it belongs to** (PR 38).  A run
  no longer than ``token_block`` (8) sublane rows, a decode row, is one
  NARROW block: nothing else of that slot's pages is in the step, so its
  items are already the need.  A longer run (a prefill chunk, a long
  verify run) is cut into WIDE blocks of ``QW`` tokens, and its tail rides
  one more wide block, its other rows masked, where it is longer than a
  narrow block (else one narrow block): while every block was 8 rows a
  512-token chunk was 64 blocks and each read every page of its context
  again, 656 work items at context 1,500 where 8 blocks of 64 rows read 84.
  ``QW`` is :func:`ragged_wide_block` of the launch's own shapes: a width
  in TOKENS, the widest multiple of 8 up to 64 whose buffers (``G * QW``
  rows a head) fit the scoped VMEM: 64 at every served geometry, one, four
  or eight query heads a pool head.  Tokens decide how often a chunk's
  context is re-read, and a score's vector work is the same at any width,
  so the width does not shrink as ``G`` grows.  On the chip (TPU v5e) no
  width from 32 rows up costs just its DMA: at one query head a pool
  head, a step of 15 decode rows and a 497-token chunk at context 1,024 is
  834 items and 1,244 us a layer in blocks of 8, 342 and 588 at 32, 258
  and 514 at 64, 216 and 476 at 128 (a wide item costs 0.8 us and 41 ns a
  row and page, all 16 heads: the body's vector work, not the MXU); the
  document cell gives 17,346 / 17,740 / 18,122 tokens/s at 32 / 64 / 128
  (the narrow plan 13,500).  At eight query heads over four pool heads
  of 128 (Mellum2's full layer: 29 decode rows at contexts of 4-28 k and
  a 1,024-token chunk at context 6,656, one launch) blocks of 8 / 16 /
  32 / 48 / 64 / 128 tokens read 10,556 / 6,940 / 5,132 / 4,572 / 4,228 /
  3,776 items in 7,608 / 6,049 / 5,951 / 5,458 / 5,174 / 5,047 us (a wide
  item of 64 tokens, 512 rows a head, ~3.0 us against 0.72 for a narrow
  one); the chunk at context 16,384: 14,779 us at 8, 8,996 at 64, 8,681 at
  128; a window layer over its ring (window 1,024): 1,413 items in 1,154
  us at 8, 405 in 813 at 64; the end of one prompt and the start of the
  next (300 and 724 tokens): 4,973 us at 8, 4,052 at 64, 4,186 at 128.
  128 tokens gains at most 3.5% in a long chunk and loses in a short run
  (twice the padded arithmetic of a tail's block), so 64 is the cap.
  Where chunks are short the width hardly matters: at four query heads
  over ten pool heads (the long-context cell's shared layer, 48 decode
  rows and chunks of 120 and 57 tokens) 8 / 16 / 32 / 64 tokens take
  1,799 / 1,696 / 1,674 / 1,654 us, its window layer 455 / 441 / 461 /
  445; over eight (a step of 63 decode rows and a 35-token chunk) 573 /
  593 / 592 / 613;
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
- the SAME kernel is launched once a width, each launch over its width's
  own work list (``wl_*`` / ``ww_*``) and as long as it (``n_items`` /
  ``n_wide``): a step with no chunk launches the wide one over no item.
  What such a step still pays for the wide blocks is the wrapper's gather
  over their CAPACITY, which is why that is a bound in tokens
  (:func:`ragged_wide_capacity`) and a run whose wide blocks no longer fit
  rides narrow blocks (a decode-only step of the document cell's geometry
  pays 9.5 us a layer for them).  Two things measured and NOT in the tree:
  one launch whose body branched on its item's width (the document cell's
  device step 17.72 ms against 17.62 with two launches: nothing gained for
  a second body), and a ``lax.cond`` around a launch to spare a decode-only
  step those gathers: at the document cell's geometry with 32-token wide
  blocks the compiled program HUNG the chip until its time limit, three
  times of three, where 64-token blocks under the same ``cond``, and
  32-token ones without it, ran.  No Mosaic call of this module sits
  inside a conditional;
- a work item carries ``hb`` heads of its page: the grid is
  ``(H // hb, n_items)`` and a grid step moves ONE ``(1, hb, page, D)``
  block of K and one of V (the heads of a page are contiguous in the
  ``[P, H, page, D]`` pool) against ``(1, hb, rows, D)`` of queries, the
  heads one batched chain under one mask.  ``hb`` follows the launch's own
  shapes (:func:`ragged_head_block`: the largest divisor of the local
  ``H`` whose double-buffered K and V blocks hold a fixed share of the
  scoped VMEM); at every served geometry it is ``H``: one grid step, and
  one DMA a side, an item;
- a K/V head may serve a GROUP of query heads (grouped-query attention:
  the pool holds ``Hkv`` heads, the queries ``Hkv * G``): the group's
  heads are folded into the query block's rows, ``[NB, Hkv, G * QB, D]``
  (``G * QW`` in a wide block), so an item's K and V pages are still read
  once, whatever ``G``; a row's token is ``row mod`` the block's width.
  With ``G == 1`` the launch, the kernel body and the outputs are bit for
  bit those of a pool with as many heads as queries;
- online softmax accumulates across a block's work items (running max m,
  denominator l, fp32 acc); per-item masking is causal at token
  granularity: row i of block b (absolute position ``blk_base[b] + i``)
  attends pool positions ``<=`` its own, rows past ``blk_rows[b]`` are
  padding (masked everywhere, output rows discarded by the host gather).
  A row's result does not depend on which other rows share its block: the
  wide plan's outputs are the narrow plan's, bit for bit.

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
    "ragged_wide_block",
    "ragged_wide_capacity",
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
    "blk_tok",      # [NB, QB]  flat token index feeding each narrow block row
    "wblk_tok",     # [NBW, QW] the same of each WIDE block
    "tok_blk",      # [T]       inverse map: token -> its block (wide: NB + j)
    "tok_row",      # [T]       inverse map: token -> its row in the block
    "blk_base",     # [NB]      absolute position of each block's row 0
    "blk_rows",     # [NB]      valid rows per block (0 = padding block)
    "wblk_base",    # [NBW]     the same two of the wide blocks
    "wblk_rows",    # [NBW]
    "wl_blk",       # [WL]      work item -> token block
    "wl_page",      # [WL]      work item -> POOL page id (pre-translated)
    "wl_pageslot",  # [WL]      work item -> page-slot (for position math)
    "n_items",      # [1]       real work items: the narrow launch's length
    "ww_blk",       # [WLW]     the wide blocks' own work list (block j),
    "ww_page",      # [WLW]     page
    "ww_pageslot",  # [WLW]     and page-slot
    "n_wide",       # [1]       its real items: the wide launch's length
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
_PAGE_FIELDS = tuple(RAGGED_PLAN_FIELDS.index(f)
                     for f in ("wl_page", "ww_page", "wr_page"))


def plan_at_layer(plan, page_base):
    """The plan of one layer of a stacked pool ``[L * P, ...]``: the pool
    page ids of its lists offset by the layer's first page."""
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


# the tails a step's wide blocks have room for beyond its full blocks: a step
# holds the end of one prompt and the start of the next far more often than
# three chunks (a run whose wide blocks do not fit rides narrow blocks)
_WIDE_TAILS = 2


def ragged_wide_capacity(t_max: int, token_block: int,
                         wide_block: int) -> int:
    """``nbw_max``: the wide blocks a step of ``t_max`` tokens has room for
    (none where ``wide_block`` is no wider than ``token_block``): its full
    blocks and ``_WIDE_TAILS`` tails.  A bound in TOKENS, not in runs: the
    wrapper gathers every block the arrays hold, so capacity a step seldom
    uses is paid for by every step."""
    if int(wide_block) <= int(token_block):
        return 0
    return t_max // int(wide_block) + _WIDE_TAILS


def ragged_plan_shapes(*, token_block: int, t_max: int, nb_max: int,
                       wl_max: int, write_group: int, wr_max: int,
                       wide_block: Optional[int] = None, nbw_max: int = 0,
                       wlw_max: int = 0):
    """``[(field, shape)]`` of a plan's arrays in :data:`RAGGED_PLAN_FIELDS`
    order: what an engine's packed step input lays out."""
    shapes = {
        "blk_tok": (nb_max, token_block),
        "wblk_tok": (nbw_max, wide_block or token_block),
        "tok_blk": (t_max,), "tok_row": (t_max,),
        "blk_base": (nb_max,), "blk_rows": (nb_max,),
        "wblk_base": (nbw_max,), "wblk_rows": (nbw_max,),
        "wl_blk": (wl_max,), "wl_page": (wl_max,), "wl_pageslot": (wl_max,),
        "n_items": (1,),
        "ww_blk": (wlw_max,), "ww_page": (wlw_max,),
        "ww_pageslot": (wlw_max,), "n_wide": (1,),
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


# the most tokens a wide block carries: how often a chunk's context is re-read
# falls as 1 / QW; 128 gains at most a few percent over 64 in a long chunk
# and loses in a short run (twice the padded arithmetic of a tail's block);
# the module docstring has the tables
_WIDE_TOKENS_MAX = 64


def _wide_vmem_bytes(hb: int, rows: int, page_size: int, head_dim: int,
                     itemsize: int) -> int:
    """What a launch with ``rows`` query rows a wide block keeps in the
    scoped VMEM: K and V and the q / out blocks double-buffered, the float32
    accumulator, running max and denominator, and the body's score tiles
    (scores, probabilities, and the probabilities in the pool's dtype)."""
    kv = 4 * hb * page_size * head_dim * itemsize
    q_out = 4 * hb * rows * head_dim * itemsize
    scratch = hb * rows * (head_dim + 2 * 128) * 4
    scores = hb * rows * page_size * (4 + 4 + itemsize)
    return kv + q_out + scratch + scores


def ragged_wide_block(num_heads: int, group: int, page_size: int,
                      head_dim: int, dtype, token_block: int = 8) -> int:
    """How many tokens a WIDE block carries (``QW``): the query rows of a
    work item that belongs to a run longer than one narrow block.  A pure
    function of the launch's own shapes, as :func:`ragged_head_block` is:
    the widest multiple of 8, up to ``_WIDE_TOKENS_MAX`` tokens, whose
    buffers (:func:`_wide_vmem_bytes`, ``group * QW`` rows a head) fit the
    scoped VMEM.  A width in TOKENS, whatever ``group``: tokens decide how
    often a chunk's context is re-read, and a score's vector work is the
    same at any width.  ``token_block`` where nothing wider fits: the plan
    then holds no wide block and the launch is the narrow one alone."""
    qb, group = int(token_block), int(group)
    itemsize = jnp.dtype(dtype).itemsize
    hb = ragged_head_block(num_heads, page_size, head_dim, dtype)
    qw = _WIDE_TOKENS_MAX
    while qw > qb and (_wide_vmem_bytes(hb, group * qw, page_size,
                                        head_dim, itemsize)
                       > _SCOPED_VMEM_BYTES):
        qw -= 8
    return max(qw, qb)


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
                      window: Optional[int] = None,
                      wide_block: Optional[int] = None, nbw_max: int = 0,
                      wlw_max: Optional[int] = None
                      ) -> Tuple[Dict[str, np.ndarray], Dict[str, int]]:
    """Flatten one fused step's work into the kernel's plan arrays.

    ``runs``: one entry per contiguous token run — a decode slot (count 1)
    or a prefill chunk (count up to the step's token budget) — as
    ``(base_pos, count, table_row)`` where ``table_row`` is the slot's
    int32 page-table row.  Token flat order is run-major: run r's tokens
    occupy flat indices ``[start_r, start_r + count_r)`` in submission
    order (``stats["run_starts"]`` reports the starts).

    **Blocks.**  Without ``wide_block`` (or with one no wider than
    ``token_block``) a run is cut into narrow blocks of ``token_block``
    rows.  With one, the rows a work item carries follow the run: a run no
    longer than a narrow block (a decode row) is one narrow block; a longer
    one is cut into WIDE blocks of ``wide_block`` rows, and its tail rides
    one more wide block (its other rows masked) where it is longer than a
    narrow block, else one narrow block: never more items than narrow
    blocks alone would cost, and a function of the run's count alone,
    until the step's ``nbw_max`` wide blocks are taken: a run (in
    submission order) whose wide blocks no longer fit rides narrow blocks
    throughout, as every run did.  The wide blocks have their own arrays
    (``wblk_*``, numbered from 0; a token's ``tok_blk`` names wide block
    ``j`` as ``nb_max + j``) and their own work list (``ww_*``, ``n_wide``
    items, ``wlw_max`` at most: by default every wide block at every page
    of a table row): the launch runs once a width.

    Every array is padded to its fixed maximum (``t_max``/``nb_max``/
    ``nbw_max``/``wl_max``/``wlw_max``) so the compiled step never
    retraces; a launch's grid ends at its list's item count, so a list's
    tail is never walked: it REPEATS the last real entry only to hold valid
    indices (the last item's look-ahead reads one; a list with no item
    holds zeros: block 0, the null page).  Padding block-gather rows point at
    the block's first token (a valid index; the row is masked in-kernel and
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
    ``n_items`` (ALL items, both lists)/``n_writes``/``run_starts``, the
    occupancy numerators the serving metrics report (``row_capacity``: all
    rows launched), what rode wide blocks (``wide_blocks``/``wide_items``/
    ``wide_rows``), and ``launched_items`` (the launches' second grid
    dimensions for this step, summed)."""
    qb, g = int(token_block), int(write_group)
    qw = int(wide_block or qb)
    wide = qw > qb and nbw_max > 0
    nbw_max = int(nbw_max) if wide else 0
    if qw % 8:
        raise ValueError(f"wide_block={qw} must be a multiple of 8")
    if g < 1 or page_size % g:
        raise ValueError(f"write_group={g} must divide page_size={page_size}")
    if wr_max is None:
        wr_max = ragged_write_capacity(t_max, g, nb_max + nbw_max)
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
    # a run's blocks: its full wide blocks, then its tail (wide where longer
    # than a narrow block), while the step's wide blocks last; what is left
    # of a run, narrow blocks (with no wide block, all of it)
    run_wide = np.zeros_like(counts)
    if wide:
        run_wide = counts // qw + (counts % qw > qb)
        run_wide[np.cumsum(run_wide) > nbw_max] = 0
    lead = run_wide * qw                            # a run's rows in wide blocks
    run_narrow = -(-np.maximum(counts - lead, 0) // qb)
    bn, bw = int(run_narrow.sum()), int(run_wide.sum())
    if bn > nb_max:
        raise ValueError(f"plan overflow: {bn} blocks > nb_max={nb_max}")
    run_starts: List[int] = [int(x) for x in starts]

    def one_width(per_run, width, lead_rows, cap, items_max, name):
        """The blocks of one width, run-major, and their work list: a
        run's first block, each block's real rows, the gather rows of all
        ``cap`` blocks (a block's padding rows point at its first token),
        the blocks' positions and valid rows, the list's three arrays and
        its length.  A block's items are the page-slots up to its last
        row's, from the first its window reaches (the first of all without
        one)."""
        n = int(per_run.sum())
        run = np.repeat(np.arange(len(runs)), per_run)
        first = np.cumsum(per_run) - per_run
        off = lead_rows[run] + (np.arange(n) - first[run]) * width
        rows = np.minimum(width, counts[run] - off)
        lane = np.arange(width)
        tok = np.zeros((cap, width), np.int32)
        tok[:n] = ((starts[run] + off)[:, None]
                   + np.where(lane[None, :] < rows[:, None], lane[None, :], 0))
        base = np.zeros((cap,), np.int32)
        valid = np.zeros((cap,), np.int32)
        base[:n], valid[:n] = bases[run] + off, rows
        at = base[:n].astype(np.int64)
        last_slot = (at + rows - 1) // page_size
        first_slot = (np.zeros((n,), np.int64) if window is None else
                      np.maximum(at - int(window) + 1, 0) // page_size)
        per_blk = last_slot - first_slot + 1
        items = int(per_blk.sum())
        if items > items_max:
            raise ValueError(f"plan overflow: {items} work items > "
                             f"{name}={items_max}")
        blk = np.repeat(np.arange(n), per_blk)
        slot = (first_slot[blk] + np.arange(items)
                - (np.cumsum(per_blk) - per_blk)[blk])
        lists = []
        for real in (blk, tables[run[blk], slot], slot):
            # the tail repeats the last real item: valid indices, never
            # walked
            arr = np.full((items_max,), real[-1] if items else 0, np.int32)
            arr[:items] = real
            lists.append(arr)
        return first, rows, tok, base, valid, lists, items

    if wlw_max is None:
        wlw_max = nbw_max * tables.shape[1]
    (n_first, _, blk_tok, blk_base, blk_rows, (wl_blk, wl_page, wl_ps),
     n_narrow) = one_width(run_narrow, qb, lead, nb_max, wl_max, "wl_max")
    (w_first, w_rows, wblk_tok, wblk_base, wblk_rows,
     (ww_blk, ww_page, ww_ps), n_wide) = one_width(
         run_wide, qw, np.zeros_like(counts), nbw_max, int(wlw_max),
         "wlw_max")
    n_items = n_narrow + n_wide
    tok_run = np.repeat(np.arange(len(runs)), counts)
    within = np.arange(t) - starts[tok_run]
    past = within - lead[tok_run]                   # rows past the wide blocks
    tok_blk = np.zeros((t_max,), np.int32)
    tok_blk[:t] = np.where(past < 0,
                           nb_max + w_first[tok_run] + within // qw,
                           n_first[tok_run] + past // qb)
    tok_row = np.zeros((t_max,), np.int32)
    tok_row[:t] = np.where(past < 0, within % qw, past % qb)
    plan = {
        "blk_tok": blk_tok, "wblk_tok": wblk_tok,
        "tok_blk": tok_blk, "tok_row": tok_row,
        "blk_base": blk_base, "blk_rows": blk_rows,
        "wblk_base": wblk_base, "wblk_rows": wblk_rows,
        "wl_blk": wl_blk, "wl_page": wl_page, "wl_pageslot": wl_ps,
        "n_items": np.array([n_narrow], np.int32),
        "ww_blk": ww_blk, "ww_page": ww_page, "ww_pageslot": ww_ps,
        "n_wide": np.array([n_wide], np.int32),
    }
    plan.update(_build_write_list(
        bases, counts, starts, tables,
        g=g, page_size=page_size, t_max=t_max, wr_max=int(wr_max)))
    stats = {
        "n_tokens": t, "n_blocks": bn + bw, "n_items": n_items,
        "n_writes": int(plan["n_writes"][0]),
        "run_starts": run_starts,
        # occupancy: the fraction of the work-list arrays holding real
        # items and of the block rows carrying real queries
        "wl_capacity": wl_max,
        "row_capacity": bn * qb + bw * qw,
        # what rode wide blocks: blocks, their items, their real rows
        "wide_blocks": bw,
        "wide_items": n_wide, "wide_rows": int(w_rows.sum()),
        # the launches' second grid dimensions for this step: a grid ends
        # at its list's item count, whatever the arrays' capacity
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
    (blk_tok, wblk_tok, tok_blk, tok_row, blk_base, blk_rows, wblk_base,
     wblk_rows, wl_blk, wl_page, wl_ps, n_items, ww_blk, ww_page, ww_ps,
     n_wide) = plan[:len(RAGGED_ATTEND_FIELDS)]
    nb, qb = (int(x) for x in blk_tok.shape)
    nbw, qw = (int(x) for x in wblk_tok.shape)
    use_kernel = (_on_tpu() and ragged_shape_supported(page_size, d, qb)) \
        or interpret
    if use_kernel:
        # one query head a pool head: the call the launch always was
        grouped = {} if group == 1 else {"group": group}
        if window is not None:
            grouped["window"] = int(window)

        def launch(tok, work_list, length, base, rows):
            """One width's blocks through the kernel: the rows ``tok [n,
            width]`` names as q blocks ``[n, H, G * width, D]`` (a pool
            head's query heads one after another), attended over the
            width's own work list; its out blocks as flat rows ``[n * width,
            Hq, D]``."""
            n, width = tok.shape
            qg = jnp.take(q, jnp.reshape(tok, (-1,)), axis=0)
            if group == 1:
                qg = jnp.transpose(qg.reshape(n, width, h, d), (0, 2, 1, 3))
            else:
                qg = jnp.transpose(qg.reshape(n, width, h, group, d),
                                   (0, 2, 3, 1, 4)).reshape(
                                       n, h, group * width, d)
            out = _ragged_pallas(qg, k_pool, v_pool, *work_list, length,
                                 base, rows, scale, interpret=interpret,
                                 k_scale=k_scale, v_scale=v_scale, **grouped)
            if group == 1:
                return jnp.transpose(out, (0, 2, 1, 3)).reshape(
                    n * width, h, d)
            return jnp.transpose(out.reshape(n, h, group, width, d),
                                 (0, 3, 1, 2, 4)).reshape(n * width, hq, d)

        narrow = launch(blk_tok, (wl_blk, wl_page, wl_ps), n_items,
                        blk_base, blk_rows)
        tok_blk, tok_row = tok_blk.astype(jnp.int32), tok_row.astype(jnp.int32)
        if not nbw:
            return jnp.take(narrow, tok_blk * qb + tok_row, axis=0)
        # the wide blocks: the same kernel over their own list, its grid
        # as long as the step's wide items (none in a decode-only step)
        wider = launch(wblk_tok, (ww_blk, ww_page, ww_ps), n_wide,
                       wblk_base, wblk_rows)
        # a token's row is in its own width's output (the other index is
        # clamped into its array and not chosen)
        return jnp.where(
            (tok_blk >= nb)[:, None, None],
            jnp.take(wider, jnp.maximum((tok_blk - nb) * qw + tok_row, 0),
                     axis=0),
            jnp.take(narrow, jnp.minimum(tok_blk * qb + tok_row,
                                         nb * qb - 1), axis=0))
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
