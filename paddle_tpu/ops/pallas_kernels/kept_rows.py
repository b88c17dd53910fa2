"""The convolution's kept inputs on TPU: ONE launch a layer that reads each
run's kept row and writes its new one, in place.

A causal depthwise convolution of 4 taps over a serving step's flat rows
``a [T, W]`` needs, before a run's first row, the three inputs its slot kept.
They live in the slot's state row of ``serving.paged_cache.SlotStateCache``
(``tail_slab``): ``(a_{p-3}, a_{p-2}, a_{p-1})`` as one slab ``[3 C, 128]``,
``C = W / 128`` sublane rows a part.  A run (a decode token or a prefill
chunk: consecutive rows of one slot) reads row ``run_src`` (zeros where it
starts a sequence) and leaves its last three inputs in row ``run_dst``: a
decode run leaves ``(old[1], old[2], a_t)``.

The grid is the run list, ``(n_runs,)`` (a traced scalar).  A grid step
takes the run's kept row as its pool block and writes the new row as the
block at ``run_dst`` of the same pool, aliased input to output: no other row
of the pool moves, and padding rows write nothing.  ``a`` rides as its
tiles, ``[T / 8, 8 C, 128]``: the same bytes as ``[T, W]`` (XLA passes it as
a bitcast), a tile eight step rows by one 128-lane chunk, so one row of
``a`` is one sublane of each of ``C`` tiles, a strided load (a bfloat16 row
is half of each 32-bit word of a row pair).  A step holds the two groups of
eight rows that its run's last three rows lie in.

The launch also returns the runs' OLD kept rows, ``[3 S, W]`` (``S`` the run
list's length rounded up to 8; row ``3 r + i`` is run ``r``'s ``a_{p-3+i}``,
zeros where it starts a sequence), for the convolution to read a run's
first three rows from: they are stored as rows of a group of eight runs'
tiles in a float32 scratch, written out when the group's last run is done.

Eligibility (:func:`kept_rows_runs`): on a TPU, a row of parts of whole
``(8, 128)`` tiles (``3 C x 128`` with ``C`` a multiple of 8), bfloat16 or
float32; a TPU with another row notes the fallback.  Elsewhere the models'
own XLA form (``models/decoder_ops.kept_inputs``) gathers and scatters by
row; ``interpret=True`` runs this launch on the CPU for the tests."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ragged_paged_attention as _ragged

__all__ = ["kept_rows", "kept_rows_runs", "as_tiles", "from_tiles"]

_SUB, _LANES = 8, 128
_DTYPES = (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))


def as_tiles(x):
    """``[T, W]`` -> ``[T / 8, 8 W / 128, 128]``: row ``8 g + s``, lane chunk
    ``c`` lands at ``[g, 8 c + s]``, the order ``[T, W]``'s tiles are in."""
    t, w = x.shape
    return (x.reshape(t // _SUB, _SUB, w // _LANES, _LANES)
            .transpose(0, 2, 1, 3).reshape(t // _SUB, w // _LANES * _SUB, _LANES))


def from_tiles(x):
    """The inverse of :func:`as_tiles`."""
    g, rows, lanes = x.shape
    return (x.reshape(g, rows // _SUB, _SUB, lanes)
            .transpose(0, 2, 1, 3).reshape(g * _SUB, rows // _SUB * lanes))


def _problems(row_shape, a_dtype, pool_dtype):
    shape = tuple(row_shape)
    rows, lanes = shape if len(shape) == 2 else (0, 0)
    found = []
    if lanes != _LANES or rows % (3 * _SUB):
        found.append(f"a kept row {tuple(row_shape)} is no three parts of whole "
                     f"({_SUB}, {_LANES}) tiles")
    for name, dt in (("the inputs", a_dtype), ("the pool", pool_dtype)):
        if jnp.dtype(dt) not in _DTYPES:
            found.append(f"{name} are {jnp.dtype(dt)}, not bfloat16 or float32")
    return found


def kept_rows_runs(row_shape, a_dtype, pool_dtype) -> bool:
    """Whether a model's convolution takes the launch: where the ragged
    kernel runs (it asks that module, as the scan and the rule do), for a row
    the launch supports.  Observed, never set: a TPU with another row takes
    the XLA form and notes the fallback."""
    if not _ragged._on_tpu():
        return False
    found = _problems(row_shape, a_dtype, pool_dtype)
    if found:
        from ...analysis.codes import GateReason, note_fallback

        note_fallback(GateReason("GL002", "kept_rows", "; ".join(found)))
        return False
    return True


def _row(ref, s, c):
    """Row ``s`` (< 8) of a ``[1, 8 C, 128]`` block of ``a``'s tiles as
    ``[C, 128]`` float32."""
    lax = jax.lax
    if jnp.dtype(ref.dtype).itemsize == 4:
        return ref[0, pl.ds(s, c, stride=_SUB), :].astype(jnp.float32)
    # a 32-bit word holds rows 2i (low half) and 2i + 1 (high half); a
    # bfloat16 value is the high half of the float32 it widens to
    words = ref.bitcast(jnp.uint32)[0, pl.ds(lax.div(s, np.int32(2)), c,
                                             stride=_SUB // 2), :]
    high = jnp.where(lax.rem(s, np.int32(2)) == 0,
                     lax.shift_left(words, np.uint32(16)),
                     lax.bitwise_and(words, np.uint32(0xFFFF0000)))
    return lax.bitcast_convert_type(high, jnp.float32)


def _kept_rows_kernel(first_ref, count_ref, src_ref, dst_ref, fresh_ref, n_ref,
                      pool_ref, lo_ref, hi_ref, pool_out, old_out, old_sc):
    del src_ref, dst_ref                        # consumed by the index maps
    lax = jax.lax
    r = pl.program_id(0)
    c = pool_ref.shape[1] // 3
    f32 = jnp.float32
    first, count = first_ref[r], count_ref[r]
    last = lax.sub(lax.add(first, count), np.int32(1))
    lo = lax.max(first, lax.sub(last, np.int32(2)))
    top = lax.div(last, np.int32(_SUB))

    held = pool_ref[0].astype(f32)
    old = jnp.where(fresh_ref[r] != 0, jnp.zeros_like(held), held)
    parts = [old[i * c:(i + 1) * c] for i in range(3)]

    def row(x):     # a's row x as [C, 128]; x < lo reads row lo (unused)
        x = lax.max(x, lo)
        s = lax.rem(x, np.int32(_SUB))
        return jnp.where(lax.div(x, np.int32(_SUB)) == top,
                         _row(hi_ref, s, c), _row(lo_ref, s, c))

    new = [jnp.where(count >= 3, row(lax.sub(last, np.int32(2))),
                     jnp.where(count == 2, parts[2], parts[1])),
           jnp.where(count >= 2, row(lax.sub(last, np.int32(1))), parts[2]),
           row(last)]
    pool_out[0] = jnp.concatenate(new, axis=0).astype(pool_out.dtype)

    # the old row, as rows 3 q .. 3 q + 2 of the group of eight runs' tiles
    @pl.when(r == 0)
    def _clear():
        old_sc[...] = jnp.zeros_like(old_sc)

    q = lax.rem(r, np.int32(_SUB))
    for i in range(3):
        at = lax.add(lax.mul(q, np.int32(3)), np.int32(i))
        old_sc[lax.div(at, np.int32(_SUB)),
               pl.ds(lax.rem(at, np.int32(_SUB)), c, stride=_SUB), :] = parts[i]

    @pl.when((q == _SUB - 1) | (r == n_ref[0] - 1))
    def _flush():
        old_out[...] = old_sc[...].astype(old_out.dtype)


@jax.named_scope("kernel.kept_rows")
def kept_rows(pool, a, runs, *, interpret: bool = False):
    """Keep each run's last three inputs and hand back what it kept before.

    pool: ``[rows, 3 C, 128]`` kept rows (a stacked pool viewed flat: the
          row ids below carry the layer's offset)
    a:    ``[T, W]`` the step's convolution inputs, ``W = 128 C``
    runs: ``(run_first, run_count, run_src, run_dst, run_fresh, n_runs)``:
          each run's first flat row and length, the pool rows it loads from
          and stores to, whether it starts a sequence; the launch's length

    Returns ``(pool, old)``: the pool with each run's ``run_dst`` row
    written, and ``old [3 S, W]`` in the pool's dtype (``S`` the run list's
    length rounded up to 8), row ``3 r + i`` run ``r``'s ``a_{p-3+i}`` (zero
    where it starts a sequence; rows of runs past ``n_runs`` hold anything)."""
    run_first, run_count, run_src, run_dst, run_fresh, n_runs = runs
    _, rows, lanes = pool.shape
    t, w = a.shape
    c = rows // 3
    if _problems(pool.shape[1:], a.dtype, pool.dtype) or w != c * lanes:
        raise ValueError(f"kept_rows: a [{t}, {w}] against kept rows "
                         f"{tuple(pool.shape[1:])} {pool.dtype}")
    tp = -(-t // _SUB) * _SUB
    if tp != t:     # a step of whole groups of eight rows (tiny engines only)
        a = jnp.pad(a, ((0, tp - t), (0, 0)))
    s8 = -(-run_first.shape[0] // _SUB) * _SUB
    i32 = jnp.int32
    # the index maps use lax: a jnp operator on a traced scalar converts its
    # dtype, which Mosaic's lowering of an index map recurses on
    lax, sub = jax.lax, np.int32(_SUB)

    def pool_in(r, first, count, src, dst, fresh, n):
        return (src[r], np.int32(0), np.int32(0))

    def pool_dst(r, first, count, src, dst, fresh, n):
        return (dst[r], np.int32(0), np.int32(0))

    def low(r, first, count, src, dst, fresh, n):
        end = lax.add(first[r], count[r])
        lo = lax.max(first[r], lax.sub(end, np.int32(3)))
        return (lax.div(lo, sub), np.int32(0), np.int32(0))

    def high(r, first, count, src, dst, fresh, n):
        end = lax.add(first[r], count[r])
        return (lax.div(lax.sub(end, np.int32(1)), sub), np.int32(0), np.int32(0))

    def group(r, *_):
        return (lax.div(r, sub), np.int32(0), np.int32(0))

    row_block = (1, rows, lanes)
    tile_block = (1, _SUB * c, lanes)
    n_runs = jnp.reshape(n_runs, (1,)).astype(i32)
    n_prefetch = 6
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,
        grid=(n_runs[0],),
        in_specs=[pl.BlockSpec(row_block, pool_in), pl.BlockSpec(tile_block, low),
                  pl.BlockSpec(tile_block, high)],
        out_specs=[pl.BlockSpec(row_block, pool_dst),
                   pl.BlockSpec((3,) + tile_block[1:], group)],
        scratch_shapes=[pltpu.VMEM((3, _SUB * c, lanes), jnp.float32)],
    )
    pool, old = pl.pallas_call(
        _kept_rows_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                   jax.ShapeDtypeStruct((3 * s8 // _SUB, _SUB * c, lanes), pool.dtype)],
        # the pool is written where it lies: its operand is result 0
        input_output_aliases={n_prefetch: 0},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(run_first.astype(i32), run_count.astype(i32), run_src.astype(i32),
      run_dst.astype(i32), run_fresh.astype(i32), n_runs, pool,
      as_tiles(a), as_tiles(a))
    return pool, from_tiles(old)
