"""Fused multi-tensor AdamW update as one Pallas TPU kernel per slab.

Reference: paddle/phi/kernels/fusion/fused_adam_kernel.cu (MultiTensorAdam:
one CUDA kernel updating a chunked list of param/grad/moment pointers) and
python/paddle/incubate/optimizer/distributed_fused_lamb.py.

TPU-native redesign: the stacked-GPT parameter set is already a handful of
[L, ...] SLABS (one tensor per weight role, layers stacked), so "multi
tensor" needs no pointer chunking — each slab is updated by ONE
``pallas_call`` that streams p/g/m1/m2 through VMEM in (8, 1024) fp32
blocks and writes p/m1/m2 back through input→output aliasing (true in-place
update, no double residency).  bf16 storage is upcast to fp32 in VMEM for
the update math and cast back on store — the same precision contract as
the XLA-composed path in optimizer/optimizers.py:_apply_one.

Scalars (lr, beta powers) arrive as (1,1) SMEM refs so a schedule change
never recompiles the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_adamw_update"]

_LANES = 1024        # flattened row width (8 lanes of 128)
_BLOCK_ROWS = 256    # rows per grid step: 256 rows keeps the kernel's
                     # VMEM stack (in/out blocks + fp32 upcast temps)
                     # under the 16 MiB scoped limit — 512 rows overflows
                     # it by 96 KiB on v5e (measured)


def _kernel(lr_ref, b1p_ref, b2p_ref, p_ref, g_ref, m1_ref, m2_ref,
            po_ref, m1o_ref, m2o_ref, *, beta1, beta2, eps, wd):
    p = p_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32)
    m1 = m1_ref[:].astype(jnp.float32)
    m2 = m2_ref[:].astype(jnp.float32)
    lr = lr_ref[0, 0]
    b1p = b1p_ref[0, 0]
    b2p = b2p_ref[0, 0]

    new_m1 = beta1 * m1 + (1.0 - beta1) * g
    new_m2 = beta2 * m2 + (1.0 - beta2) * g * g
    m1_hat = new_m1 / (1.0 - b1p)
    m2_hat = new_m2 / (1.0 - b2p)
    new_p = p * (1.0 - lr * wd)
    new_p = new_p - lr * m1_hat / (jnp.sqrt(m2_hat) + eps)

    po_ref[:] = new_p.astype(po_ref.dtype)
    m1o_ref[:] = new_m1.astype(m1o_ref.dtype)
    m2o_ref[:] = new_m2.astype(m2o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("beta1", "beta2", "eps", "wd",
                                             "interpret"),
                   donate_argnums=(0, 2, 3))
@jax.named_scope("kernel.fused_adamw")
def fused_adamw_update(p, g, m1, m2, lr, b1p, b2p, *,
                       beta1=0.9, beta2=0.999, eps=1e-8, wd=0.01,
                       interpret=False):
    """Return (new_p, new_m1, new_m2).

    Standalone (eager) calls donate p/m1/m2 into the outputs via
    ``donate_argnums`` so XLA may reuse their buffers; when n is
    lane-aligned the ravel/reshape folds to a bitcast and the kernel's
    ``input_output_aliases`` make the update truly in place.  When traced
    inside an outer jit (the compiled train step), the OUTER donation of
    the captured optimizer state is what guarantees single residency.

    ``lr``/``b1p``/``b2p`` are runtime scalars (traced), the rest of the
    hyperparameters are compile-time constants.
    """
    shape, dtype = p.shape, p.dtype
    n = p.size
    rows = -(-n // _LANES)
    pad = rows * _LANES - n

    def flat(x, d):
        x = jnp.ravel(x).astype(d)
        if pad:
            x = jnp.concatenate([x, jnp.zeros((pad,), d)])
        return jnp.reshape(x, (rows, _LANES))

    pf = flat(p, dtype)
    gf = flat(g, dtype)
    m1f = flat(m1, m1.dtype)
    m2f = flat(m2, m2.dtype)
    # m2 padding must stay >= 0 under sqrt; zeros are fine.

    block_rows = min(_BLOCK_ROWS, rows)
    grid = (-(-rows // block_rows),)

    scal = lambda v: jnp.reshape(jnp.asarray(v, jnp.float32), (1, 1))
    kernel = functools.partial(_kernel, beta1=float(beta1),
                               beta2=float(beta2), eps=float(eps),
                               wd=float(wd))
    # index maps must return int32: Mosaic under x64 rejects i64 index-map
    # returns ("failed to legalize 'func.return' (i64, i64)") — same
    # convention as flash_attention.py's np.int32 casts
    row_spec = pl.BlockSpec((block_rows, _LANES),
                            lambda i: (i, np.int32(0)))
    # the scalar specs need an EXPLICIT int32 index map too: a BlockSpec
    # without one defaults to python-int (0, 0), which traces as i64
    # under the package's x64 mode and fails Mosaic legalization with
    # "func.return (i64, i64)"
    smem_map = lambda i: (np.int32(0), np.int32(0))
    smem = (pl.BlockSpec((1, 1), smem_map, memory_space=pltpu.SMEM)
            if not interpret else pl.BlockSpec((1, 1), smem_map))
    new_p, new_m1, new_m2 = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[smem, smem, smem, row_spec, row_spec, row_spec, row_spec],
        out_specs=[row_spec, row_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct(pf.shape, pf.dtype),
            jax.ShapeDtypeStruct(m1f.shape, m1f.dtype),
            jax.ShapeDtypeStruct(m2f.shape, m2f.dtype),
        ],
        input_output_aliases={3: 0, 5: 1, 6: 2},
        interpret=interpret,
    )(scal(lr), scal(b1p), scal(b2p), pf, gf, m1f, m2f)

    def unflat(x, d):
        x = jnp.ravel(x)
        if pad:
            x = x[:n]
        return jnp.reshape(x, shape).astype(d)

    return (unflat(new_p, dtype), unflat(new_m1, m1.dtype),
            unflat(new_m2, m2.dtype))
