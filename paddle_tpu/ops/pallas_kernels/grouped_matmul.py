"""Grouped matrix product on TPU: the rows of ``lhs`` are sorted into
contiguous groups and group ``g`` multiplies its rows by ITS matrix,
``rhs[group_base + g]`` — the expert feed-forward of a routed layer, no
token dropped and no capacity: a group is as long as the router made it.

The kernel is the forward ``gmm`` of ``jax.experimental.pallas.ops.tpu
.megablox`` (its tile bookkeeping, ``make_group_metadata``, is imported as
it stands) with one change of interface: ``rhs`` holds the matrices of
EVERY routed layer, ``[L * G, K, N]``, and a traced ``group_base`` selects
the layer's ``G`` inside the index map of the right-hand block.  A layer
loop can so close over one stacked operand and never slice it: the 1.2 GB
of a layer's experts are read tile by tile where they lie, and only the
tiles of groups that received rows.

- the grid is ``(N // tn, active tiles)``, a tile holding the whole of
  ``K``; the second dimension is the step's real count of (row tile, group)
  pairs, a traced scalar as the ragged attention launch's ``n_items`` is: a
  group with no rows is never visited, and its matrix is never read;
- a row tile shared by several groups is visited once a group, each visit
  storing only its own rows (the kernel's mask);
- rows past the groups' total (padding rows routed nowhere) are returned
  as zeros.

Off the chip the wrapper runs ``jax.lax.ragged_dot`` over the layer's
slice of ``rhs``: the parity oracle, as ``_xla_ragged_reference`` is the
ragged kernel's.  Forward only: serving never differentiates through it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ragged_paged_attention as _ragged
from .decode_attention import _dot

__all__ = ["grouped_matmul", "gmm_tiling"]

# rows of a tile: the sublane multiple that keeps a bf16 tile whole and the
# MXU pass a visit costs small beside its right-hand tile's DMA
_TM = 128
# the right-hand tile [tk, tn] double-buffered may take this share of the
# scoped VMEM (16 MiB on a v5e); the rest is the row tile, the f32
# accumulator and the output tile
_RHS_BUFFER_BYTES = 8 << 20


def gmm_tiling(k: int, n: int, itemsize: int = 2):
    """``(tm, tk, tn)`` for a ``[*, k] x [k, n]`` product: the whole of ``k``
    (one visit of a tile finishes it: no accumulation across grid steps) and
    the largest 128-multiple divisor of ``n`` whose ``[k, tn]`` tile,
    double-buffered, stays inside :data:`_RHS_BUFFER_BYTES`."""
    fit = max(_RHS_BUFFER_BYTES // (2 * k * itemsize), 128)
    tn = max((t for t in range(128, n + 1, 128) if n % t == 0 and t <= fit),
             default=n)
    return _TM, k, tn


def _gmm_kernel(offsets_ref, gid_ref, mid_ref, base_ref, lhs_ref, rhs_ref,
                out_ref, *, tm, tn):
    del base_ref                                # consumed by the index map
    i = pl.program_id(1)
    acc = _dot(lhs_ref[...], rhs_ref[...], ((1,), (0,)))
    # the visit stores its own group's rows of the tile and keeps the rest
    # (an earlier group's, written by the visit before this one)
    group = gid_ref[i]
    rows = mid_ref[i] * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0)
    mine = jnp.logical_and(rows >= offsets_ref[group],
                           rows < offsets_ref[group + 1])
    out_ref[...] = jnp.where(mine, acc, out_ref[...])


def _gmm_pallas(lhs, rhs, group_sizes, group_base, interpret=False):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

    m, k = lhs.shape
    n = rhs.shape[2]
    tm, tk, tn = gmm_tiling(k, n, rhs.dtype.itemsize)
    (offsets, group_ids, m_tile_ids), n_active = make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tm, start_group=jnp.int32(0),
        num_nonzero_groups=group_sizes.shape[0], visit_empty_groups=False)

    def lhs_index(n_i, i, offsets, gids, mids, base):
        return mids[i], np.int32(0)

    def rhs_index(n_i, i, offsets, gids, mids, base):
        return base[0] + gids[i], np.int32(0), n_i

    def out_index(n_i, i, offsets, gids, mids, base):
        return mids[i], n_i

    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, tn=tn),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, n_active.astype(jnp.int32)),
            in_specs=[pl.BlockSpec((tm, tk), lhs_index),
                      pl.BlockSpec((None, tk, tn), rhs_index)],
            out_specs=pl.BlockSpec((tm, tn), out_index)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(offsets.astype(jnp.int32), group_ids.astype(jnp.int32),
      m_tile_ids.astype(jnp.int32),
      jnp.reshape(group_base, (1,)).astype(jnp.int32), lhs, rhs)


@jax.named_scope("kernel.gmm")
def grouped_matmul(lhs, rhs, group_sizes, group_base=0, *, interpret=False):
    """``out[r] = lhs[r] @ rhs[group_base + g(r)]`` in float32, ``g(r)`` the
    group whose run of rows holds ``r``.

    lhs:         [M, K]  rows sorted by group
    rhs:         [L * G, K, N]  every layer's matrices, stacked
    group_sizes: [G] int32  rows a group holds (their sum may fall short of
                 ``M``: the rows past it come back as zeros)
    group_base:  int32 scalar (traced or not), the layer's first matrix

    On a TPU the Pallas kernel (``M`` padded up to a whole row tile); off it
    ``jax.lax.ragged_dot`` over the layer's slice."""
    m = lhs.shape[0]
    n_groups = group_sizes.shape[0]
    group_sizes = group_sizes.astype(jnp.int32)
    lhs = lhs.astype(rhs.dtype)
    if _ragged._on_tpu() or interpret:
        pad = -m % _TM
        if pad:
            lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
        out = _gmm_pallas(lhs, rhs, group_sizes, group_base,
                          interpret=interpret)[:m]
    else:
        mine = jax.lax.dynamic_slice_in_dim(rhs, group_base, n_groups, axis=0)
        out = jax.lax.ragged_dot(lhs, mine, group_sizes,
                                 preferred_element_type=jnp.float32)
    routed = jnp.arange(m, dtype=jnp.int32) < jnp.sum(group_sizes)
    return jnp.where(routed[:, None], out, 0.0)
