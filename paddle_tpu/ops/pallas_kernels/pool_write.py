"""The paged pool's write on TPU: ONE launch a layer that read-modify-writes
the tile groups the step's real tokens touch, in place.

A pool ``[P, N, page, W]`` is laid out in sublane tiles of ``g`` positions
by 128 lanes (``g`` = 16 for bfloat16, 8 for float32:
:func:`pool_write_group`), so the unit a store moves whole is ``g``
consecutive positions of one page for every head: a TILE GROUP,
``(1, N, g, W)``.  The host walks the step's runs once
(``ragged_paged_attention.build_ragged_plan``) and lists, beside the work
list, one WRITE ITEM a tile group that a run's new positions touch: the pool
page, the group within the page, which flat token feeds each of its ``g``
rows, and the rows ``lo .. lo + n - 1`` that are new.  The launch is as long
as that list (``n_writes``, a traced scalar, as the ragged launch ends at
``n_items``): a decode token is one item, sixteen aligned chunk tokens are
one item, and padding tokens are none: the null page is never written.

A grid step takes its item's tile group of every pool as an input block and
the same block as output (the pool is aliased input to output, so every
tile no item names keeps its contents and nothing is copied), and a WINDOW
of ``g`` consecutive tokens of the step's fresh rows ``[T, N, W]``: a run's
tokens are consecutive rows, a token is a whole ``(N, W)`` tile, so a
window starts at any token with no alignment to mind.  The body turns the
window's rows into the group's (token-major to head-major, a strided store
a row into a float32 scratch) and stores ``where(lo <= row < lo + n, new,
old)``.  Nothing is staged in front of the call: a form that gathered each
item's rows into a ``[wr_max, N, g, W]`` operand with XLA cost 1.1 ms of a
1.6 ms step's writes on the chip (PERF.md section 6, PR 34).

No two items of a step may name one tile group (the pipeline fetches item
``w + 1``'s block before item ``w``'s is written back): the plan builder
raises on a repeat.  A slot writes only pages it owns alone, so the engine
never builds one.

Eligibility (:func:`pool_write_supported`): a bfloat16 or float32 pool whose
page is a multiple of ``g``.  Int8 pools (their write also updates the
per-(page, head) scales) and every program off the TPU keep the XLA row
scatter in the models' own write; ``interpret=True`` runs this launch on the
CPU for the tests."""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ragged_paged_attention as _ragged

__all__ = ["pool_write", "pool_write_group", "pool_write_supported",
           "pool_write_runs"]


def pool_write_group(dtype) -> int:
    """``g``: the positions one sublane tile of a ``dtype`` pool holds, the
    write's unit (16 for bfloat16, 8 for float32)."""
    return 32 // jnp.dtype(dtype).itemsize


def pool_write_supported(page_size: int, dtype) -> bool:
    """Whether the launch can write a pool of this page and ``dtype``."""
    dtype = jnp.dtype(dtype)
    return (dtype in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))
            and int(page_size) % pool_write_group(dtype) == 0)


def pool_write_runs(page_size: int, dtype) -> bool:
    """Whether a model's write takes this launch: where the ragged kernel
    runs (it asks that module, as the grouped product does) for a pool this
    launch supports.  Observed, never set."""
    return _ragged._on_tpu() and pool_write_supported(page_size, dtype)


def _pool_write_kernel(page_ref, group_ref, lo_ref, n_ref, tok_ref,
                       start_ref, *refs):
    # lax primitives throughout: a jnp call on a traced scalar is a nested
    # jit that is traced again for every one of the body's g rows, and the
    # step's trace is part of every run's set-up
    lax = jax.lax
    n_pools = len(refs) // 4
    olds, windows, outs, scratch = (
        refs[i * n_pools:(i + 1) * n_pools] for i in range(4))
    w = pl.program_id(0)
    lo = lo_ref[w]
    hi = lax.add(lo, n_ref[w])
    start = start_ref[w]
    _, heads, g, _ = olds[0].shape
    first = lax.mul(w, np.int32(g))
    # the window's row that feeds each row of the group (a new row's token
    # lies inside the window; the others are masked, any row does)
    take = [lax.clamp(np.int32(0),
                      lax.sub(tok_ref[lax.add(first, np.int32(r))], start),
                      np.int32(g - 1)) for r in range(g)]
    tile = olds[0].shape[1:]                                # [N, g, W]
    row = lax.broadcasted_iota(jnp.int32, tile, 1)
    fresh = lax.bitwise_and(lax.ge(row, lax.broadcast(lo, tile)),
                            lax.lt(row, lax.broadcast(hi, tile)))
    for old, window, out, scr in zip(olds, windows, outs, scratch):
        for r in range(g):
            # token-major to head-major: row r of every head's tile
            scr[pl.ds(r, heads, stride=g), :] = lax.convert_element_type(
                window[take[r]], jnp.float32)
        new = lax.convert_element_type(lax.reshape(scr[...], tile), old.dtype)
        out[0] = lax.select(fresh, new, old[0])


def pool_write(pools: Sequence, rows: Sequence, write_list: Tuple, *,
               interpret: bool = False) -> Tuple:
    """Write the step's new rows into ``pools`` and return the pools.

    pools:      one or more ``[P, N, page, W]`` pools of one geometry and
                dtype (K and V; or one pool of K|V rows)
    rows:       for each pool the step's fresh rows ``[T, N, W]``, one a
                flat token (cast to the pool's dtype here)
    write_list: the ``RAGGED_WRITE_FIELDS`` arrays of the step's plan:
                ``wr_page`` [WR] pool page (pre-translated; a layer of a
                stacked pool has added its offset), ``wr_group`` [WR] tile
                group within the page, ``wr_tok`` [WR, g] the flat token
                feeding each row, ``wr_lo`` / ``wr_n`` [WR] the new rows,
                ``n_writes`` [1] the launch's length

    Every real token of the step lands at the position the row scatter
    writes it; no other byte of a pool changes."""
    wr_page, wr_group, wr_tok, wr_lo, wr_n, n_writes = write_list
    pools, rows = tuple(pools), tuple(rows)
    shape, dtype = pools[0].shape, pools[0].dtype
    _, n, page_size, width = shape
    g = pool_write_group(dtype)
    if len(rows) != len(pools) or any(
            p.shape != shape or p.dtype != dtype for p in pools):
        raise ValueError("pool_write takes pools of one shape and dtype and "
                         "one array of rows a pool")
    if not pool_write_supported(page_size, dtype):
        raise ValueError(f"no tile-group write for a {dtype} pool of "
                         f"{page_size}-position pages")
    if wr_tok.ndim != 2 or wr_tok.shape[1] != g:
        raise ValueError(
            f"the plan's write list names groups of {wr_tok.shape[-1]} "
            f"positions, a {dtype} pool's tile holds {g}: build the plan "
            "with write_group=pool_write_group(pool dtype)")
    rows = tuple(r.astype(dtype) for r in rows)
    t = rows[0].shape[0]
    if t < g:       # a step shorter than one window (tiny engines only)
        rows = tuple(jnp.pad(r, ((0, g - t), (0, 0), (0, 0))) for r in rows)
        t = g
    wr_tok = wr_tok.astype(jnp.int32)
    # an item's tokens are consecutive rows: its window starts at the token
    # of the group's row 0, held inside the step's rows
    wr_start = jnp.minimum(wr_tok[:, 0], t - g)

    def pool_index(w, page_ref, group_ref, *_):
        return (page_ref[w], np.int32(0), group_ref[w], np.int32(0))

    def window_index(w, page_ref, group_ref, lo_ref, n_ref, tok_ref,
                     start_ref):
        return (start_ref[w], np.int32(0), np.int32(0))     # in elements

    block = (1, n, g, width)
    window = tuple(pl.Element(d) for d in (g, n, width))
    n_pools, n_prefetch = len(pools), 6
    n_writes = jnp.reshape(n_writes, (1,)).astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=(n_writes[0],),
        in_specs=[pl.BlockSpec(block, pool_index)] * n_pools
        + [pl.BlockSpec(window, window_index)] * n_pools,
        out_specs=[pl.BlockSpec(block, pool_index)] * n_pools,
        scratch_shapes=[pltpu.VMEM((n * g, width), jnp.float32)] * n_pools,
    )
    out = pl.pallas_call(
        _pool_write_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(shape, dtype)] * n_pools,
        # the pools are written where they lie: operand i (after the
        # prefetched scalars) is result i
        input_output_aliases={n_prefetch + i: i for i in range(n_pools)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(wr_page.astype(jnp.int32), wr_group.astype(jnp.int32),
      wr_lo.astype(jnp.int32), wr_n.astype(jnp.int32),
      jnp.reshape(wr_tok, (-1,)), wr_start, *pools, *rows)
    return tuple(out)
