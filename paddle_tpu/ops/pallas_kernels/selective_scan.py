"""The selective state-space recurrence over a serving step's flat rows.

For a row ``t`` of a run (a decode token or a prefill chunk: consecutive rows
at consecutive positions of one slot), channel ``c`` and state index ``s``::

    h_t[s, c] = exp(dt_t[c] * A[s, c]) * h_{t-1}[s, c] + dt_t[c] * x_t[c] * B_t[s]
    y_t[c]    = sum_s h_t[s, c] * C_t[s] + D[c] * x_t[c]

all in float32.  A run loads its slot's state (zero where the run starts a
sequence), steps through its rows in order and stores the state: the pool
``[rows, blocks, S, sub, lanes]`` holds a state as whole ``(sub, lanes)``
tiles of channels for each state index (``serving.paged_cache.
SlotStateCache.ssm``), a run reads row ``run_src`` and writes row ``run_dst``
(never the same: a step that runs twice finds what it found the first time).

On a TPU (:func:`scan_runs_kernel`, asked of the ragged kernel's module as
the grouped product and the pool write do) this is ONE Mosaic launch a layer,
``_ssm_scan_kernel``: the grid is ``(blocks, n_runs)``, a grid step is a run's
rows on one block of ``sub x lanes`` = 1,024 channels.  The block's ``dt``,
``x`` and ``y`` rows of the whole step stay in VMEM across its runs; the
state is ``S`` tiles of ``(sub, lanes)`` in a scratch; ``B_t[s]`` and
``C_t[s]`` are scalars read from SMEM, so a state index's update is a chain
of tile-wide operations and nothing is broadcast along sublanes.  The launch
is as long as the step's run list (``n_runs``, a traced scalar) and the pool
is aliased input to output: no row but a run's ``run_dst`` changes.

Elsewhere (the CPU's tests and the plain forward) :func:`_xla_scan` steps
through the rows with ``lax.scan``, a row's run start selecting the state;
it reads the same run list (:func:`rows_of_runs` gives it by row) and, as
the launch, changes no pool row but a run's ``run_dst``.  On the chip that
loop was measured against the launch and lost (PERF.md section 6, PR 35):
320 dependent steps a layer, each a handful of small operations on a state
that lives in HBM; a TPU that cannot take the launch says so
(``note_fallback``), it does not fall to the loop in silence.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ragged_paged_attention as _ragged

__all__ = ["selective_scan", "scan_runs_kernel", "rows_of_runs",
           "state_as_tiles", "tiles_as_state"]


def scan_runs_kernel(pool_shape) -> bool:
    """Whether the recurrence takes the launch: where the ragged kernel runs,
    for a pool of whole ``(8, 128)`` tiles.  Observed, never set.  A TPU
    whose pool has another tile takes the loop and notes the fallback, as
    the attention kernels' gates do."""
    if not _ragged._on_tpu():
        return False
    tile = tuple(pool_shape[-2:])
    if tile != (8, 128):
        from ...analysis.codes import GateReason, note_fallback

        note_fallback(GateReason(
            "GL002", "selective_scan",
            f"the state pool's tile {tile} is not (8, 128)"))
        return False
    return True


def rows_of_runs(run_first, run_count, n_runs, t: int):
    """The run list by row, for the step's ``t`` flat rows: ``(k, run,
    real)``: a row's index in its run, its run, and whether a run holds it
    (the rows past the last run's end are padding: they read as the last
    run's with ``k >= run_count``)."""
    i32 = jnp.int32
    live = jnp.arange(run_first.shape[0], dtype=i32) < jnp.reshape(n_runs, ())
    at = jnp.arange(t, dtype=i32)
    starts = (at[:, None] >= run_first[None, :].astype(i32)) & live[None, :]
    run = jnp.sum(starts, axis=1, dtype=i32) - 1
    k = at - jnp.take(run_first, run).astype(i32)
    return k, run, k < jnp.take(run_count, run)


def state_as_tiles(h, tile: Tuple[int, int]):
    """``[S, channels]`` -> the pool's ``[blocks, S, sub, lanes]``."""
    s = h.shape[0]
    sub, lanes = tile
    return jnp.transpose(h.reshape(s, -1, sub, lanes), (1, 0, 2, 3))


def tiles_as_state(tiles):
    """The pool's ``[blocks, S, sub, lanes]`` -> ``[S, channels]``."""
    return jnp.transpose(tiles, (1, 0, 2, 3)).reshape(tiles.shape[1], -1)


def _ssm_scan_kernel(first_ref, count_ref, src_ref, dst_ref, fresh_ref,
                     b_ref, c_ref, dt_ref, x_ref, a_ref, d_ref, h_in_ref,
                     y_ref, h_out_ref, h_sc):
    lax = jax.lax
    r = pl.program_id(1)
    n_state = h_sc.shape[0]

    @pl.when(r == 0)
    def _zero():        # rows no run holds (padding) read as zero, not as VMEM
        y_ref[...] = jnp.zeros_like(y_ref)

    held = h_in_ref[0, 0]
    h_sc[...] = jnp.where(fresh_ref[r] != 0, jnp.zeros_like(held), held)
    first = first_ref[r]

    def row(i, carry):
        t = lax.add(first, i)
        dt = dt_ref[t, 0]                        # [sub, lanes]
        x = x_ref[t, 0]
        dtx = dt * x
        y = d_ref[0] * x
        at = lax.mul(t, np.int32(n_state))
        for s in range(n_state):
            h = jnp.exp(dt * a_ref[0, s]) * h_sc[s] \
                + b_ref[lax.add(at, np.int32(s))] * dtx
            h_sc[s] = h
            y = y + c_ref[lax.add(at, np.int32(s))] * h
        y_ref[t, 0] = y
        return carry

    lax.fori_loop(np.int32(0), count_ref[r], row, np.int32(0))
    h_out_ref[0, 0] = h_sc[...]


def _scan_pallas(dt, x, b, c, a_t, d, pool, runs, interpret=False):
    run_first, run_count, run_src, run_dst, run_fresh, n_runs = runs
    t = dt.shape[0]
    _, nblk, n_state, sub, lanes = pool.shape
    f32 = jnp.float32

    def tiles(v):           # [T, channels] -> [T, blocks, sub, lanes]
        return v.astype(f32).reshape(t, nblk, sub, lanes)

    def rows_index(j, r, *_):
        return (np.int32(0), j, np.int32(0), np.int32(0))

    def block_index(j, r, *_):
        return (j, np.int32(0), np.int32(0), np.int32(0))

    def src_index(j, r, first, count, src, dst, fresh):
        return (src[r], j, np.int32(0), np.int32(0), np.int32(0))

    def dst_index(j, r, first, count, src, dst, fresh):
        return (dst[r], j, np.int32(0), np.int32(0), np.int32(0))

    # whole in SMEM (an index map of its own: the default one's zero is int64
    # where x64 is on, which Mosaic refuses)
    smem = pl.BlockSpec((t * n_state,), lambda j, r, *_: (np.int32(0),),
                        memory_space=pltpu.SMEM)
    rows_block = pl.BlockSpec((t, 1, sub, lanes), rows_index)
    state_block = (1, 1, n_state, sub, lanes)
    n_prefetch = 5
    n_runs = jnp.reshape(n_runs, (1,)).astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=(nblk, n_runs[0]),
        in_specs=[
            smem, smem,                                 # B and C, flat [T * S]
            rows_block, rows_block,
            pl.BlockSpec((1, n_state, sub, lanes), block_index),
            pl.BlockSpec((1, sub, lanes),
                         lambda j, r, *_: (j, np.int32(0), np.int32(0))),
            pl.BlockSpec(state_block, src_index),
        ],
        out_specs=[rows_block, pl.BlockSpec(state_block, dst_index)],
        scratch_shapes=[pltpu.VMEM((n_state, sub, lanes), f32)],
    )
    y, pool = pl.pallas_call(
        _ssm_scan_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((t, nblk, sub, lanes), f32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # the pool is written where it lies: the last operand is result 1
        input_output_aliases={n_prefetch + 6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(run_first.astype(jnp.int32), run_count.astype(jnp.int32),
      run_src.astype(jnp.int32), run_dst.astype(jnp.int32),
      run_fresh.astype(jnp.int32),
      b.astype(f32).reshape(-1), c.astype(f32).reshape(-1),
      tiles(dt), tiles(x), state_as_tiles(a_t.astype(f32), (sub, lanes)),
      d.astype(f32).reshape(nblk, sub, lanes), pool)
    return y.reshape(t, -1), pool


def _xla_scan(dt, x, b, c, a_t, d, pool, runs):
    """The recurrence as a ``lax.scan`` over the step's rows: a row that
    starts a run takes its slot's state (``run_src``; zero where
    ``run_fresh``), and every row stores what it leaves at its run's
    ``run_dst``, so the run's last row leaves the run's state there.  A
    padding row changes nothing: it stores the last run's state again."""
    run_first, run_count, run_src, run_dst, run_fresh, n_runs = runs
    k, run, real = rows_of_runs(run_first, run_count, n_runs, dt.shape[0])
    tile = pool.shape[-2:]
    f32 = jnp.float32

    def step(carry, inp):
        h, pool = carry
        dt_t, x_t, b_t, c_t, k, src, dst, fresh, real = inp
        held = tiles_as_state(jax.lax.dynamic_index_in_dim(
            pool, src, axis=0, keepdims=False))
        h = jnp.where(k == 0, jnp.where(fresh, jnp.zeros_like(h), held), h)
        new = jnp.exp(dt_t[None, :] * a_t) * h \
            + b_t[:, None] * (dt_t * x_t)[None, :]
        h = jnp.where(real, new, h)
        y = jnp.sum(h * c_t[:, None], axis=0) + d * x_t
        pool = jax.lax.dynamic_update_index_in_dim(
            pool, state_as_tiles(h, tile), dst, axis=0)
        return (h, pool), jnp.where(real, y, jnp.zeros_like(y))

    h0 = jnp.zeros(a_t.shape, f32)
    (_, pool), y = jax.lax.scan(
        step, (h0, pool),
        (dt.astype(f32), x.astype(f32), b.astype(f32), c.astype(f32),
         k, jnp.take(run_src, run), jnp.take(run_dst, run),
         jnp.take(run_fresh, run), real))
    return y, pool


@jax.named_scope("kernel.ssm_scan")
def selective_scan(dt, x, b, c, a_t, d, pool, runs, *,
                   interpret: bool = False):
    """The recurrence of one layer over a step's rows.

    dt, x:  [T, channels] the step sizes (after the softplus) and the inputs
    b, c:   [T, S] the input and output projections of the state
    a_t:    [S, channels] ``-exp(A_log)`` transposed;  d: [channels]
    pool:   [rows, blocks, S, sub, lanes] float32: every layer's state rows
            (a stacked pool viewed flat: the row ids below carry the layer's
            offset)
    runs:   ``(run_first, run_count, run_src, run_dst, run_fresh, n_runs)``:
            each run's first flat row and length, the pool rows it loads
            from and stores to, whether it starts from zero; the launch's
            length

    Returns ``(y [T, channels] float32, pool)``."""
    if scan_runs_kernel(pool.shape) or interpret:
        return _scan_pallas(dt, x, b, c, a_t, d, pool, runs,
                            interpret=interpret)
    return _xla_scan(dt, x, b, c, a_t, d, pool, runs)
