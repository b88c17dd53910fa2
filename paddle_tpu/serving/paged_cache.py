"""Paged KV cache: a global pool of fixed-size KV pages + free-list
allocator.

``models/generation.py``'s ``KVCache`` preallocates ``[L, B, H, max_seq, D]``
— HBM scales with ``batch * max_seq`` whether or not the tokens
exist.  The paged cache replaces that with ONE pool of
``[L, num_pages, H, page_size, D]`` pages shared by every decode slot; a
slot's context is named by its *page table* (an int32 row of pool page
ids), so memory scales with live tokens and short requests stop subsidizing
long ones.

Page 0 is the **null page**: never handed out by the allocator, it absorbs
the writes of inactive slots and prefill padding (their page-table entries
all point at it) so the compiled step needs no branching — garbage lands
in a page no read ever resolves to validly.

The pool tensors are plain framework Tensors so in-place updates are
mutation-logged — ``jit.to_static`` donates them and the compiled serving
step aliases each write into the same HBM (docs/decoding.md donation
contract, unchanged).

``dtype="int8"`` selects the QUANTIZED pool regime (docs/serving.md
"Quantized serving"): pages store int8 payloads and a parallel fp32
``[L, num_pages, H]`` scale buffer holds one absmax scale per (layer,
page, head).  The scale buffers
are indexed BY PAGE ID, so they ride the same BlockAllocator ledger as
the pages themselves — alloc/free/share/spec-reserve/refcount semantics
are untouched and prefix-cache COW, speculative rollback, and the
4-term accounting invariant compose with quantization by construction.
Writes quantize in-graph at scatter time
(quantization/kv.quantize_kv_write); reads dequantize INSIDE the
attention kernels right after each page DMA.
"""
from __future__ import annotations

from collections import deque
from typing import List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ..core.dtype import to_jax_dtype
from ..models.generation import _KVBuffers
from ..tensor import Tensor

__all__ = ["NULL_PAGE", "PagedKVCache", "HybridPagedCache",
           "SlotStateCache", "BlockAllocator", "pages_for_tokens"]

# pool page 0: reserved sink for inactive-slot / padding writes
NULL_PAGE = 0


def pages_for_tokens(tokens: int, page_size: int) -> int:
    """Pages needed to hold ``tokens`` positions: ``ceil(tokens /
    page_size)``.

    THE page-math helper — admission sizing, speculative draft
    reservations, and the prefix cache's tail-only reservation all route
    through this one function so a rounding change can never diverge the
    ledgers (the all-or-nothing reservation discipline only keeps
    accounting exact while everyone agrees on the ceiling)."""
    tokens = int(tokens)
    page_size = int(page_size)
    if tokens < 0:
        raise ValueError(f"pages_for_tokens(tokens={tokens})")
    if page_size < 1:
        raise ValueError(f"pages_for_tokens(page_size={page_size})")
    return -(-tokens // page_size)


def _check_row_index(*dims: int):
    """Off the TPU a pool is written as rows of its flat ``[rows, D]`` view
    (``dims`` are the axes before ``D``); the write's row index is int32."""
    rows = 1
    for d in dims:
        rows *= int(d)
    if rows >= 2 ** 31:
        raise ValueError(
            f"a pool of {' x '.join(str(d) for d in dims)} rows: the "
            "write's row index is int32")


class PagedKVCache(_KVBuffers):
    """Global KV page pool: one Tensor pair ``k``/``v`` of shape
    ``[L, num_pages, H, page_size, D]``.
    The fused step carries each through its layer loop as ONE donated
    buffer, viewed as ``[L * num_pages, H, page_size, D]``: layer ``l``
    addresses page ``l * num_pages + page_id`` and writes its tokens in place
    (``GPTStackedDecoder._forward_paged``): on a TPU the tile groups the
    step's write list names in one launch (ops/pallas_kernels/pool_write.py),
    elsewhere as rows of ``[L * num_pages * H * page_size, D]``.  The stored shape is what the
    allocator, the prefix cache, page hand-off, sharding and checkpoints see.

    ``paged`` is the duck-type marker ``models/gpt.py`` dispatches on (a
    paged cache routes attention through the page-table write + the ragged
    work-list kernel instead of the contiguous ``dynamic_update_slice``
    path).
    """

    paged = True

    def __init__(self, num_layers: int, num_pages: int, num_heads: int,
                 page_size: int, head_dim: int, dtype: str = "bfloat16"):
        if num_pages < 2:
            raise ValueError(
                f"num_pages={num_pages}: the pool needs the null page plus "
                "at least one allocatable page")
        _check_row_index(num_layers, num_pages, num_heads, page_size)
        jd = to_jax_dtype(dtype)
        self.num_layers = num_layers
        self.num_pages = num_pages
        self.num_heads = num_heads
        self.page_size = page_size
        self.head_dim = head_dim
        self.dtype = str(dtype)
        # quantized regime: int8 pages + per-(page, head) fp32 absmax
        # scales.  Scale buffers are keyed by POOL PAGE ID so they need
        # no allocator of their own — a page's scale travels with it
        # through every ledger transition (free/used/spec/shared).
        self.quantized = self.dtype == "int8"
        self.k_scale = self.v_scale = None
        shape = (num_layers, num_pages, num_heads, page_size, head_dim)
        self.k = Tensor(jnp.zeros(shape, jd))
        self.v = Tensor(jnp.zeros(shape, jd))
        if self.quantized:
            ss = (num_layers, num_pages, num_heads)
            self.k_scale = Tensor(jnp.zeros(ss, jnp.float32))
            self.v_scale = Tensor(jnp.zeros(ss, jnp.float32))

    def _tensors(self):
        """All device buffers, INCLUDING the scale buffers — so
        ``nbytes`` counts scale bytes, ``release`` frees them, and the
        watchdog's zombie cleanup orphans them with the pages."""
        ts = super()._tensors()
        if self.quantized:
            ts = ts + [self.k_scale, self.v_scale]
        return ts


class _DeviceLog:
    """What a cache's compiled step counts and logs on the device, read on
    the host: ``counters`` (the names given as ``[n, 2]`` int32 limbs, value
    ``hi * LIMB + lo``) and ``routes`` (a routed model's flight
    recorder, ``HybridPagedCache``'s docstring)."""

    LIMB = 1 << 30
    ROUTE_ROWS = 16384

    def _new_log(self, counter_names, routed_layers: int, top_k: int,
                 route_rows: int = 0):
        self._log_names = tuple(counter_names)
        self.counters = Tensor(jnp.zeros((len(counter_names), 2), jnp.int32))
        self.routed_layers, self.top_k = routed_layers, top_k
        self.routes = Tensor(jnp.zeros(
            (routed_layers * top_k + 2, route_rows or self.ROUTE_ROWS),
            jnp.int32))
        self.route_cursor = Tensor(jnp.zeros((1,), jnp.int32))

    def _release_log(self):
        for t in (self.counters, self.routes, self.route_cursor):
            self._delete(t)

    def counts(self) -> dict:
        """The device counters as host integers (one small transfer)."""
        import numpy as np

        limbs = np.asarray(self.counters._value).astype(np.int64)
        return {name: int(hi * self.LIMB + lo)
                for name, (hi, lo) in zip(self._log_names, limbs)}

    def recent_routes(self):
        """The log's real rows as host arrays (one transfer of the ring):
        ``positions`` [N], ``pages`` [N] (the page each row wrote) and
        ``experts`` [N, routed_layers, top_k]; None once released.  Rows of
        one step stand in their order; once the ring has wrapped, steps do
        not."""
        import numpy as np

        if self.routes._value.is_deleted():
            return None
        log = np.asarray(self.routes._value)
        live = log[-1] != 0
        experts = log[:-2, live].reshape(self.routed_layers, self.top_k, -1)
        return {"positions": log[-2, live], "pages": log[-1, live],
                "experts": experts.transpose(2, 0, 1)}


class HybridPagedCache(_DeviceLog, _KVBuffers):
    """Two kinds of state on the ONE page ledger, for a model whose layers
    are of two kinds (docs/serving.md "Models with recurrent state"): a K/V
    pool for the attention layers and a **tail pool** for the layers with a
    short causal convolution, whose recurrent state is the last ``taps``
    inputs of the sequence: ``tail [L_conv, P, taps * width / 128, 128]``, a
    page's ``taps`` inputs as ONE slab of whole lane tiles (``[taps, width]``
    with 2 taps would pad its sublanes eightfold and turn every flat view
    into a copy).

    ``kv [L_attn, P, Hkv, page_size, 2 * D]``: a row holds a token's K and V
    of one head SIDE BY SIDE.  With heads of 64 a row of K alone is half a
    lane tile: the compiler then keeps the flat ``[rows, 64]`` view the
    write scatters into in another layout than the ``[P, H, page, 64]`` the
    kernel reads, and copies the whole pool between them four times a layer
    (the compiled step for the described v5e showed it).  A row of 128 is the
    layout the dense GPT's pools have; one scatter writes both, and the
    ragged kernel reads the pool as its K operand (queries zero-padded to
    ``2 * D``, so the V half adds nothing to a score) and as its V operand
    (the second half of its output is the attention's).

    The recurrent state lives in the PAGES, not in the slots: after a step
    has written position ``p``, the tail of the page holding ``p`` is the
    convolution's inputs at ``p - taps + 1 .. p``.  Token ``p + 1`` finds its
    predecessors among its step's own rows or in the tail of the page holding
    ``p``, through its page table; a full page's tail never changes again.  A
    prefix hit, an adopted shared page, a page hand-off (pages are axis 1 of
    every tensor of ``_tensors()``) and a slot seated again at position 0 so
    need no rule of their own, and the allocator, the scheduler and the
    prefix cache keep one ledger.  Rows that end no page's run in a step
    write the null page, as padding K/V rows do.

    ``counters`` is a small device array the compiled step adds to in place
    (donated like the pools; no step gains a host transfer):
    :data:`COUNTER_NAMES` as ``[n, 2]`` int32 limbs, value ``hi * 2**30 +
    lo``.  :meth:`counts` reads them, and ``ServingEngine.metrics()`` merges
    what it returns.

    ``routes`` is the routed layers' flight recorder, kept the same way: a
    ring ``[routed_layers * top_k + 2, ROUTE_ROWS]`` int32 whose column is a
    row of a step: the experts each routed layer sent it to, its position
    and the page it wrote (0: padding).  A step writes its rows side by side
    at ``route_cursor`` (one small block a step); :meth:`recent_routes`
    reads it.  A routed layer's pick between two experts whose scores are
    nearer than the hidden state's rounding is the one thing a comparison
    with a higher-precision run cannot recompute: this is where it is
    looked up."""

    paged = True
    quantized = False
    COUNTER_NAMES = ("moe_assignments", "moe_experts_touched",
                     "moe_expert_load_max", "conv_tail_rows")

    def __init__(self, attn_layers: int, conv_layers: int, num_pages: int,
                 num_kv_heads: int, page_size: int, head_dim: int,
                 conv_width: int, conv_taps: int, dtype: str = "bfloat16",
                 routed_layers: int = 0, top_k: int = 0):
        if str(dtype) == "int8":
            raise ValueError(
                "HybridPagedCache: an int8 pool is not supported (the tail "
                "pool has no scale sidecar and the grouped-query kernel no "
                "dequant path)")
        if num_pages < 2:
            raise ValueError(
                f"num_pages={num_pages}: the pool needs the null page plus "
                "at least one allocatable page")
        _check_row_index(attn_layers, num_pages, num_kv_heads, page_size)
        _check_row_index(conv_layers, num_pages, conv_taps)
        jd = to_jax_dtype(dtype)
        self.num_layers = attn_layers
        self.conv_layers = conv_layers
        self.num_pages = num_pages
        self.num_heads = num_kv_heads
        self.page_size = page_size
        self.head_dim = head_dim
        self.row_dim = 2 * head_dim     # what the ragged kernel sees as a head
        self.conv_taps = conv_taps
        self.conv_width = conv_width
        self.dtype = str(dtype)
        self.kv = Tensor(jnp.zeros(
            (attn_layers, num_pages, num_kv_heads, page_size, 2 * head_dim),
            jd))
        state = conv_taps * conv_width
        slab = (state // 128, 128) if state % 128 == 0 else (1, state)
        self.tail = Tensor(jnp.zeros((conv_layers, num_pages) + slab, jd))
        self._new_log(self.COUNTER_NAMES, routed_layers, top_k)

    def _tensors(self):
        """The page-indexed buffers (pages on axis 1 of each)."""
        return [self.kv, self.tail]

    def release(self):
        super().release()
        self._release_log()


class SlotStateCache(_DeviceLog, _KVBuffers):
    """State kept by slot beside a paged pool, for a decoder whose recurrent
    layers keep a state of fixed size (docs/serving.md "State that lives in
    the slot"):

    - ``k`` / ``v`` ``[L, P, H, page_size, W]``: the full-attention layers'
      K and V, paged on the ``BlockAllocator`` ledger like any pool (``P`` =
      ``num_pages``, page 0 the null page).  The only state that grows with a
      request's context, and the only one the allocator, the scheduler and
      ``pages_used`` see.  ``H`` and ``W`` are what the ragged kernel sees as
      heads and head width (a model may fold two heads into a row of 128:
      ``num_heads`` / ``row_dim`` say so to the engine).
    - ``ring_k`` / ``ring_v`` ``[L_win, num_slots * R + 1, H, page_size, W]``
      (only where ``window_layers``): a RING of ``R`` pages a slot and window
      layer, which the allocator never sees.  Slot ``s`` owns ring pages ``s
      * R + 1 .. s * R + R``; position ``p`` lies in its ring page ``(p //
      page_size) mod R``; ring page 0 is the sink of padding rows.  ``R =
      ceil((window - 1 + max_run) / page_size) + 1`` holds every position a
      step's rows may read (the window behind a run's first row, and the
      run, which is written before it is read) whatever the run's
      alignment, so a page is overwritten only by positions ``R`` pages on,
      which no row of the step reads (the window mask hides them).
    - the STATE ROWS, one tensor a name of ``rows`` (``{name: (layers,
      row_shape, dtype)}``: ``[layers, 2 * num_slots + 1, *row_shape]``), a
      ROW a slot and layer, row 0 the sink: the model says what a row holds
      (a Mamba-1 state as whole ``(sub, lanes)`` tiles a state index and its
      convolution's kept inputs, :func:`mamba_rows`; a delta-rule state of
      ``heads x d x d`` float32).  A slot owns TWO rows and a step reads one
      and writes the other (``state_rows``): a recurrence is not idempotent
      as a K/V row write is, so a step that is dispatched again (the engine
      retries a failed step once) must find the state the last COMPLETED
      step left, and it does, because the rows swap only when a step's
      results are harvested (``commit_step``).  None where ``rows`` is
      empty: a decoder of window and full attention layers keeps the ring
      and the pool alone.

    A run that starts at position 0 starts from zero state and reads no
    ring page older than itself (the window mask is by position), whatever
    the slot held before: seating a slot again needs no clearing pass.

    The host's part of a step lives here too (the engine calls it and knows
    no layer kinds): :meth:`pack_fields` names what rides the packed step
    input beside the plan, :meth:`pack_step` fills it (the second work list
    over the ring, each row's slot, each run's rows and state rows) and
    :meth:`commit_step` swaps the state rows of the slots a harvested step
    served.  :meth:`counts` reports ``COUNTER_NAMES`` (host integers, what
    ``state_counters`` names: ``(name, "runs" | "rows" | "chunk_rows")``,
    a step's runs, rows, or rows of runs longer than one) and the
    ``device_counters`` the compiled step adds to (``_DeviceLog``), which
    ``ServingEngine.metrics()`` merges; ``route_rows`` sizes the route log
    (``_DeviceLog.ROUTE_ROWS`` where 0)."""

    paged = True
    quantized = False
    #: the fields of the packed step input, in order
    WINDOW_FIELDS = ("wl_blk", "wl_page", "wl_pageslot", "n_items",
                     "ww_blk", "ww_page", "ww_pageslot", "n_wide", "wr_page")

    def __init__(self, *, num_pages: int, page_size: int, num_slots: int,
                 max_run: int, num_heads: int, row_dim: int, rows: dict,
                 attn_layers: int = 1, window: int = 0, window_layers: int = 0,
                 state_counters=(), device_counters=(), routed_layers: int = 0,
                 top_k: int = 0, route_rows: int = 0, dtype: str = "bfloat16"):
        if str(dtype) == "int8":
            raise ValueError(
                "SlotStateCache: an int8 pool is not supported (the ring "
                "and the state rows have no scale sidecar)")
        if num_pages < 2:
            raise ValueError(
                f"num_pages={num_pages}: the pool needs the null page plus "
                "at least one allocatable page")
        jd = to_jax_dtype(dtype)
        self.num_layers = attn_layers
        self.num_pages = num_pages
        self.num_heads = num_heads
        self.page_size = page_size
        self.head_dim = self.row_dim = row_dim
        self.dtype = str(dtype)
        self.num_slots, self.max_run, self.window = num_slots, max_run, window
        self.window_layers = window_layers
        _check_row_index(attn_layers, num_pages, num_heads, page_size)
        pool = (attn_layers, num_pages, num_heads, page_size, row_dim)
        self.k = Tensor(jnp.zeros(pool, jd))
        self.v = Tensor(jnp.zeros(pool, jd))
        self._names = ["k", "v"]
        if window_layers:
            self.ring_pages = pages_for_tokens(window - 1 + max_run,
                                               page_size) + 1
            ring = num_slots * self.ring_pages + 1
            _check_row_index(window_layers, ring, num_heads, page_size)
            wide = (window_layers, ring, num_heads, page_size, row_dim)
            self.ring_k = Tensor(jnp.zeros(wide, jd))
            self.ring_v = Tensor(jnp.zeros(wide, jd))
            self._names += ["ring_k", "ring_v"]
        n_rows = 2 * num_slots + 1
        for name, (layers, shape, row_dtype) in rows.items():
            _check_row_index(layers, n_rows, *shape[:-1])
            setattr(self, name, Tensor(jnp.zeros(
                (layers, n_rows) + tuple(shape), to_jax_dtype(row_dtype))))
            self._names.append(name)
        self.state_counters = tuple(state_counters)
        self.COUNTER_NAMES = (
            (("window_work_items", "window_wide_items") if window_layers else ())
            + tuple(name for name, _ in self.state_counters))
        self._device_log = bool(device_counters or routed_layers)
        if self._device_log:
            self._new_log(device_counters, routed_layers, top_k, route_rows)
        # which of its two state rows a slot's next step READS (0 or 1)
        self._flip = np.zeros((num_slots,), np.int64)
        self._tables = None         # the slots' ring tables, made on first use
        self._counts = dict.fromkeys(self.COUNTER_NAMES, 0)

    @staticmethod
    def mamba_rows(layers: int, d_inner: int, d_state: int, conv_taps: int,
                   dtype: str) -> dict:
        """The ``rows`` of a Mamba-1 layer: ``ssm`` ``[blocks, d_state, sub,
        lanes]`` float32 (the channels as whole ``(sub, lanes)`` tiles a state
        index, the layout ops/pallas_kernels/selective_scan.py holds in VMEM)
        and ``conv``, the convolution's last ``taps`` inputs as whole lane
        tiles."""
        lanes = 128 if d_inner % 128 == 0 else d_inner
        sub = 8 if d_inner % (8 * lanes) == 0 else 1
        return {"ssm": (layers, (d_inner // (sub * lanes), d_state, sub, lanes),
                        "float32"),
                "conv": (layers, SlotStateCache.tail_slab(conv_taps * d_inner),
                         dtype)}

    @staticmethod
    def tail_slab(n: int) -> tuple:
        """``n`` kept values as one slab of whole lane tiles where they fill
        them."""
        return (n // 128, 128) if n % 128 == 0 else (1, n)

    def _tensors(self):
        return [getattr(self, n) for n in self._names]

    def release(self):
        super().release()
        if self._device_log:
            self._release_log()

    # -- the host's part of a step ------------------------------------------
    def state_rows(self, slot: int) -> Tuple[int, int]:
        """``(read, write)``: the state rows slot ``slot``'s next step loads
        from and stores to."""
        first = 1 + 2 * int(slot)
        flip = int(self._flip[slot])
        return first + flip, first + 1 - flip

    def ring_table(self, slot: int, max_pages: int) -> np.ndarray:
        """Slot ``slot``'s ring as a page-table row: page-slot ``j`` names
        ring page ``j mod R`` of the slot's ``R``."""
        return self._ring_tables(max_pages)[slot]

    def _ring_tables(self, max_pages: int) -> np.ndarray:
        """Every slot's ring table ``[num_slots, max_pages]``, made once."""
        if self._tables is None or self._tables.shape[1] != max_pages:
            r = self.ring_pages
            self._tables = (
                1 + np.arange(self.num_slots, dtype=np.int32)[:, None] * r
                + np.arange(max_pages, dtype=np.int32)[None, :] % r)
        return self._tables

    def pack_fields(self, *, t_max: int, nb_max: int, wr_max: int,
                    nbw_max: int = 0, **_):
        """``[(name, shape)]`` of what a step ships beside its plan."""
        s = self.num_slots
        runs = [("row_slot", (t_max,)),
                ("run_first", (s,)), ("run_count", (s,)),
                ("run_src", (s,)), ("run_dst", (s,)), ("n_runs", (1,))]
        if not self.window_layers:
            return runs
        # a block, narrow or wide, reads fewer than R pages
        wl, wlw = nb_max * self.ring_pages, nbw_max * self.ring_pages
        return [("win_wl_blk", (wl,)), ("win_wl_page", (wl,)),
                ("win_wl_pageslot", (wl,)), ("win_n_items", (1,)),
                ("win_ww_blk", (wlw,)), ("win_ww_page", (wlw,)),
                ("win_ww_pageslot", (wlw,)), ("win_n_wide", (1,)),
                ("win_wr_page", (wr_max,))] + runs

    def pack_step(self, view, runs: Sequence[Tuple[int, int, int]],
                  max_pages: int, plan_geometry: dict,
                  in_flight: Sequence[int] = ()) -> dict:
        """Fill the fields of :meth:`pack_fields` (``view(name)`` is each
        one's int32 array, zeroed) for a step of ``runs`` ``(slot, base,
        count)`` in flat-token order; returns the second plan's stats (none
        without a ring).  ``in_flight`` names the slots with a run enqueued
        and not harvested yet: this step follows it on the device, so it
        loads the row that run stores to and stores to the one it loads from
        (the rows swap, here as ever, only in :meth:`commit_step`)."""
        from ..ops.pallas_kernels.ragged_paged_attention import (
            build_ragged_plan,
        )

        n = len(runs)
        if n > self.num_slots:
            raise ValueError(f"{n} runs in a step of {self.num_slots} "
                             "slots: a slot has one")
        stats = {"n_items": 0, "wide_items": 0}
        if self.window_layers:
            geometry = dict(
                plan_geometry,
                wl_max=plan_geometry["nb_max"] * self.ring_pages,
                wlw_max=plan_geometry.get("nbw_max", 0) * self.ring_pages)
            plan, stats = build_ragged_plan(
                [(base, count, self.ring_table(slot, max_pages))
                 for slot, base, count in runs],
                page_size=self.page_size, window=self.window, **geometry)
            for f in self.WINDOW_FIELDS:
                view("win_" + f)[...] = plan[f]
        slots = np.array([r[0] for r in runs], np.int64)
        counts = np.array([r[2] for r in runs], np.int64)
        first = np.cumsum(counts) - counts
        flip = self._flip[slots] ^ np.isin(slots, list(in_flight))
        src = 1 + 2 * slots + flip
        dst = 1 + 2 * slots + 1 - flip
        t = int(counts.sum())
        of_row = np.repeat(np.arange(n), counts)
        row_slot = view("row_slot")
        row_slot[...] = -1
        row_slot[:t] = slots[of_row]
        view("run_first")[:n], view("run_count")[:n] = first, counts
        view("run_src")[:n], view("run_dst")[:n] = src, dst
        view("n_runs")[0] = n
        return stats

    def commit_step(self, runs: Sequence[Tuple[int, int, int]],
                    window_items: int, window_wide_items: int = 0):
        """A step over ``runs`` was harvested: its slots' state rows swap,
        and the counters take what it did."""
        rows = chunk_rows = 0
        for slot, _, count in runs:
            self._flip[slot] ^= 1
            rows += count
            chunk_rows += count if count > 1 else 0
        c = self._counts
        if self.window_layers:
            c["window_work_items"] += int(window_items)
            c["window_wide_items"] += int(window_wide_items)
        took = {"runs": len(runs), "rows": rows, "chunk_rows": chunk_rows}
        for name, what in self.state_counters:
            c[name] += took[what]

    def counts(self) -> dict:
        out = dict(self._counts)
        if self._device_log:
            out.update(super().counts())
        return out


class BlockAllocator:
    """Free-list allocator over pool pages ``1..num_pages-1`` (page 0 is
    the null page and is never handed out).

    ``alloc`` is all-or-nothing: a request that cannot be fully served
    leaves the free list untouched and returns None — the caller
    backpressures (keeps the request queued) instead of corrupting live
    slots with partial reservations."""

    def __init__(self, num_pages: int):
        if num_pages < 2:
            raise ValueError("num_pages must be >= 2 (null page + 1)")
        self.num_pages = num_pages
        self._free: deque = deque(range(1, num_pages))
        self._allocated: set = set()
        self._spec: set = set()
        # shared (prefix-cache) pages: page id -> reader refcount.  A page
        # at refcount 0 is cache-held: not free (its KV is live and
        # indexed) but reclaimable under pool pressure via ``reclaimer``.
        self._shared: dict = {}
        # pool-pressure escape hatch: fn(deficit) -> pages reclaimed.  The
        # prefix cache installs its LRU evictor here so cache-held pages
        # are reclaimed BEFORE admission backpressures (never while
        # referenced — ``reclaim`` refuses refcount > 0).
        self.reclaimer = None
        # test-only fault injection: fn("alloc", ctx) may set
        # ctx["force_none"] to simulate pool exhaustion (paddle_tpu/faults.py,
        # the discipline of checkpoint/manager.py's _fault_hook)
        self._fault_hook = None

    @property
    def capacity(self) -> int:
        """Allocatable pages (the null page is not counted)."""
        return self.num_pages - 1

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return len(self._allocated)

    @property
    def spec_pages(self) -> int:
        """Pages held under a speculative reservation: taken from the free
        list but not yet committed — a rejected speculation rolls them
        straight back (docs/serving.md "Speculative decoding")."""
        return len(self._spec)

    @property
    def shared_pages(self) -> int:
        """Pages owned by the prefix cache (any refcount, including the
        evictable refcount-0 ones).  Every page is in exactly one of
        {free, allocated, speculative, shared}:
        ``free + used + spec + shared == capacity`` at all times."""
        return len(self._shared)

    def _reclaim_for(self, n: int):
        """Ask the installed reclaimer to evict cache-held pages when the
        free list cannot cover ``n`` — eviction before backpressure."""
        if n > len(self._free) and self.reclaimer is not None:
            self.reclaimer(n - len(self._free))

    def alloc(self, n: int) -> Optional[List[int]]:
        """n pages, or None (state unchanged) when fewer than n are free."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if self._fault_hook is not None:
            ctx = {"force_none": False, "n": n}
            self._fault_hook("alloc", ctx)
            if ctx["force_none"]:
                return None          # injected exhaustion: state unchanged
        self._reclaim_for(n)
        if n > len(self._free):
            return None
        pages = [self._free.popleft() for _ in range(n)]
        self._allocated.update(pages)
        return pages

    def free(self, pages: List[int]):
        """Return pages to the pool.  Double-free and foreign ids raise —
        silent acceptance would eventually hand one page to two slots."""
        for p in pages:
            if p not in self._allocated:
                raise ValueError(
                    f"free({p}): page is not currently allocated "
                    "(double free or foreign id)")
            self._allocated.discard(p)
            self._free.append(p)

    # -- speculative reservations ------------------------------------------
    # The propose/verify loop (serving/speculative.py) writes K/V for
    # tokens the target model may REJECT.  Pages backing only-speculative
    # positions are reserved through this API instead of ``alloc`` so the
    # accounting invariant stays exact through partial acceptance, faults,
    # and retirement: every page is in exactly one of {free, allocated,
    # speculative, shared}, and free + used + spec + shared == capacity at
    # all times.

    def reserve_spec(self, n: int) -> Optional[List[int]]:
        """Reserve ``n`` pages speculatively (all-or-nothing, like
        ``alloc``).  None when fewer than ``n`` are free — the caller
        degrades (proposes fewer tokens) instead of corrupting state.

        The disaggregated hand-off (serving/disagg.py) reuses this exact
        ledger as its DESTINATION-side transfer reservation: pages sit in
        ``spec`` while the copy is in flight, ``commit_spec`` lands them
        atomically at harvest, ``rollback_spec`` returns them on a
        mid-transfer fault — so free+used+spec+shared==capacity is exact
        on both pools at every step boundary, transfers in flight
        included."""
        if n < 0:
            raise ValueError(f"reserve_spec({n})")
        if self._fault_hook is not None:
            ctx = {"force_none": False, "n": n, "spec": True}
            self._fault_hook("alloc", ctx)
            if ctx["force_none"]:
                return None
        self._reclaim_for(n)
        if n > len(self._free):
            return None
        pages = [self._free.popleft() for _ in range(n)]
        self._spec.update(pages)
        return pages

    def commit_spec(self, pages: List[int]):
        """Promote speculatively reserved pages to regular allocations
        (their positions were ACCEPTED — from here they free through the
        normal ``free`` path at retirement).  Non-speculative ids raise."""
        for p in pages:
            if p not in self._spec:
                raise ValueError(
                    f"commit_spec({p}): page holds no speculative "
                    "reservation (double commit or foreign id)")
            self._spec.discard(p)
            self._allocated.add(p)

    def rollback_spec(self, pages: List[int]):
        """Return speculatively reserved pages to the free list (their
        positions were REJECTED, or the step they backed failed).
        Non-speculative ids raise — exactly like ``free``."""
        for p in pages:
            if p not in self._spec:
                raise ValueError(
                    f"rollback_spec({p}): page holds no speculative "
                    "reservation (double rollback or foreign id)")
            self._spec.discard(p)
            self._free.append(p)

    # -- shared (prefix-cache) pages ----------------------------------------
    # The prefix cache (serving/prefix_cache.py) indexes COMPLETED,
    # immutable full pages so later admissions splice them into their page
    # tables instead of re-prefilling.  Such pages move out of the
    # ``allocated`` ledger into ``shared`` with a reader refcount: the
    # registering slot keeps one reference, every admission that splices
    # the page takes another, retirement drops it.  Refcount 0 leaves the
    # page CACHE-HELD (evictable LRU), not free — ``reclaim`` is the only
    # path back to the free list and it refuses referenced pages, so a
    # page one slot still reads can never be handed to another.

    def share(self, page: int):
        """Move an allocated page into the shared ledger with refcount 1
        (the registering slot's own reference).  Non-allocated ids raise —
        only a page some slot exclusively owned (and therefore finished
        writing) can become shared."""
        if page not in self._allocated:
            raise ValueError(
                f"share({page}): page is not currently allocated "
                "(already shared, free, or foreign id)")
        self._allocated.discard(page)
        self._shared[page] = 1

    def ref(self, page: int):
        """Take a reader reference on a shared page (a cache hit splices
        it into another slot's page table)."""
        if page not in self._shared:
            raise ValueError(f"ref({page}): page is not shared")
        self._shared[page] += 1

    def unref(self, page: int):
        """Drop a reader reference (slot retirement).  The page stays
        shared at refcount 0 — cache-held and evictable.  Over-release
        raises, exactly like a double ``free``."""
        rc = self._shared.get(page)
        if rc is None:
            raise ValueError(f"unref({page}): page is not shared")
        if rc <= 0:
            raise ValueError(
                f"unref({page}): refcount already 0 (over-release)")
        self._shared[page] = rc - 1

    def refcount(self, page: int) -> Optional[int]:
        """Current reader refcount of a shared page (None if not shared)."""
        return self._shared.get(page)

    def reclaim(self, page: int):
        """Return a refcount-0 shared page to the free list (prefix-cache
        eviction).  Referenced pages raise — eviction must never race a
        live reader."""
        rc = self._shared.get(page)
        if rc is None:
            raise ValueError(f"reclaim({page}): page is not shared")
        if rc != 0:
            raise ValueError(
                f"reclaim({page}): page still has {rc} reader(s)")
        del self._shared[page]
        self._free.append(page)
