"""Flash attention on TPU — an owned Pallas kernel (fwd + bwd).

Reference analog: paddle/phi/kernels/gpu/flash_attn_kernel.cu (which
dynloads third_party/flashattn).  On TPU the memory-hierarchy-aware
attention kernel is a Pallas/Mosaic program written here from scratch:

- forward: online-softmax accumulation over KV blocks (running max m,
  running denominator l, f32 accumulator), causal blocks skipped at the
  grid level with ``pl.when``; saves per-row logsumexp for backward.
- backward: two kernels — one accumulating dK/dV per KV block over Q
  blocks, one accumulating dQ per Q block over KV blocks — both
  recomputing the probability matrix from (q, k, lse) instead of saving
  the [S, S] attention matrix, which is the whole point of flash
  attention.  ``delta = rowsum(dO * O)`` is precomputed in XLA.

All index maps use plain int arithmetic (no lax.select), so the kernel
traces cleanly whether or not the framework's int64 (x64) mode is on —
the shipped jax kernel does not.

Layouts: ``flash_attention_bnsd`` takes [B, N, S, D] (head-major);
``flash_attention_bshd`` adapts [B, S, N, D].  CPU falls back to the
numerically-identical XLA expression (pallas interpret mode is too slow
for tests).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core import flags as _flags
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
import numpy as np


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def shape_unsupported_reason(seq_len: int, head_dim: int):
    """``None`` when the kernel accepts the shape, else the structured
    GL002-coded :class:`analysis.codes.GateReason` it falls back for —
    the SAME rule and formatting the graph linter reports, so a kernel
    fallback and a lint finding describe one hazard identically."""
    from ...analysis.codes import flash_gate_reason

    return flash_gate_reason(seq_len, head_dim)


def shape_supported(seq_len: int, head_dim: int) -> bool:
    """The ONE eligibility gate for this kernel (kept here so callers —
    nn/functional/attention.py and the stacked GPT block — can't drift):
    seqlen divisible by the 128-multiple blocks, head dim a 64 multiple
    (validated on TPU at d=64 and d=128).  On TPU hosts an ineligible
    shape is reported once per shape with its GL002 reason instead of
    silently taking the slower XLA expression."""
    reason = shape_unsupported_reason(seq_len, head_dim)
    if reason is not None and _on_tpu():
        from ...analysis.codes import note_fallback

        note_fallback(reason)
    return reason is None


NEG_INF = np.float32(-1e30)


def _dot(a, b, dims):
    """MXU dot with fp32 accumulation, precision picked per operand dtype.

    For sub-fp32 operands (bf16/fp16 under AMP) precision MUST be DEFAULT:
    the package sets jax_default_matmul_precision="highest" globally (fp32
    OpTest parity), and under "highest" Mosaic receives
    contract_precision<fp32> for bf16 operands and rejects the kernel with
    "Bad lhs type".  The accumulator is fp32 via preferred_element_type, so
    DEFAULT loses nothing there.  For fp32 operands, DEFAULT would let the
    MXU round inputs through bf16 passes — select HIGHEST so an fp32 call
    keeps full fp32 contraction."""
    fp32 = (jnp.dtype(a.dtype) == jnp.float32
            and jnp.dtype(b.dtype) == jnp.float32)
    return jax.lax.dot_general(
        a, b, (dims, ((), ())),
        precision=(jax.lax.Precision.HIGHEST if fp32
                   else jax.lax.Precision.DEFAULT),
        preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_sc, m_sc, l_sc,
                *, scale, causal, block_q, block_kv, n_kv):
    kv_i = pl.program_id(2)
    q_i = pl.program_id(1)

    @pl.when(kv_i == 0)
    def _init():
        acc_sc[...] = jnp.zeros_like(acc_sc)
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    # causal: a KV block strictly above the diagonal contributes nothing
    run = True
    if causal:
        run = kv_i * block_kv <= q_i * block_q + (block_q - 1)

    @pl.when(run)
    def _body():
        # MXU discipline: dots take the STORAGE dtype (bf16 under AMP —
        # the native MXU input width) and accumulate in fp32 via
        # preferred_element_type; only the softmax runs in fp32 on the
        # VPU.  Casting operands up to fp32 here would push the matmuls
        # off the fast bf16 MXU path for zero accuracy gain (accumulation
        # is fp32 either way).
        q = q_ref[0]                                # [block_q, D]
        k = k_ref[0]                                # [block_kv, D]
        v = v_ref[0]
        s = _dot(q, k, ((1,), (1,))) * np.float32(scale)
        if causal:
            rows = q_i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            cols = kv_i * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)

        m_prev = m_sc[:, :1]                        # [block_q, 1]
        l_prev = l_sc[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)  # [block_q, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                      # [block_q, block_kv]
        l_cur = jnp.sum(p, axis=-1, keepdims=True)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + l_cur
        acc_sc[...] = acc_sc[...] * alpha + _dot(p.astype(v.dtype), v, ((1,), (0,)))
        m_sc[...] = jnp.broadcast_to(m_new, m_sc.shape)
        l_sc[...] = jnp.broadcast_to(l_new, l_sc.shape)

    @pl.when(kv_i == n_kv - 1)
    def _finish():
        l = l_sc[:, :1]
        l_safe = jnp.where(l == 0.0, np.float32(1.0), l)
        o_ref[0] = (acc_sc[...] / l_safe).astype(o_ref.dtype)
        # [block_q, 1] -> [1, block_q] -> sublane-broadcast [8, block_q]
        # (TPU block shapes need the 2nd-minor dim to be a multiple of 8)
        lse = jnp.transpose(m_sc[:, :1] + jnp.log(l_safe))
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref[0].shape).astype(jnp.float32)


@jax.named_scope("kernel.flash_fwd")
def _flash_fwd(q, k, v, scale, causal, block_q, block_kv):
    bn, s, d = q.shape
    n_q = s // block_q
    n_kv = s // block_kv
    grid = (bn, n_q, n_kv)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_kv=block_kv, n_kv=n_kv)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, np.int32(0))),
            pl.BlockSpec((1, block_kv, d), lambda b, qi, ki: (b, ki, np.int32(0))),
            pl.BlockSpec((1, block_kv, d), lambda b, qi, ki: (b, ki, np.int32(0))),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, np.int32(0))),
            pl.BlockSpec((1, 8, block_q), lambda b, qi, ki: (b, np.int32(0), qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bn, s, d), q.dtype),
            jax.ShapeDtypeStruct((bn, 8, s), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(q, k, v)
    return out, lse[:, 0, :]


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_sc, dv_sc,
                    *, scale, causal, block_q, block_kv, n_q):
    kv_i = pl.program_id(1)
    q_i = pl.program_id(2)

    @pl.when(q_i == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    run = True
    if causal:
        # a Q block strictly above this KV block never attends to it
        run = q_i * block_q + (block_q - 1) >= kv_i * block_kv

    @pl.when(run)
    def _body():
        # same MXU discipline as the fwd kernel: operands in storage
        # dtype, fp32 accumulation; fp32 only for softmax/dS on the VPU
        q = q_ref[0]                                 # [block_q, D]
        k = k_ref[0]                                 # [block_kv, D]
        v = v_ref[0]
        do = do_ref[0]                               # [block_q, D]
        lse = jnp.transpose(lse_ref[0][:1, :])       # [block_q, 1]
        delta = jnp.transpose(delta_ref[0][:1, :])   # [block_q, 1]
        s = _dot(q, k, ((1,), (1,))) * np.float32(scale)
        if causal:
            rows = q_i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            cols = kv_i * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse)                         # [block_q, block_kv]
        # dV += P^T dO
        dv_sc[...] += _dot(p.astype(do.dtype), do, ((0,), (0,)))
        # dP = dO V^T ; dS = P * (dP - delta)
        dp = _dot(do, v, ((1,), (1,)))
        ds = p * (dp - delta)
        # dK += dS^T Q * scale
        dk_sc[...] += np.float32(scale) * _dot(ds.astype(q.dtype), q, ((0,), (0,)))

    @pl.when(q_i == n_q - 1)
    def _finish():
        dk_ref[0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_sc,
                   *, scale, causal, block_q, block_kv, n_kv):
    q_i = pl.program_id(1)
    kv_i = pl.program_id(2)

    @pl.when(kv_i == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    run = True
    if causal:
        run = kv_i * block_kv <= q_i * block_q + (block_q - 1)

    @pl.when(run)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = jnp.transpose(lse_ref[0][:1, :])       # [block_q, 1]
        delta = jnp.transpose(delta_ref[0][:1, :])   # [block_q, 1]
        s = _dot(q, k, ((1,), (1,))) * np.float32(scale)
        if causal:
            rows = q_i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            cols = kv_i * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = _dot(do, v, ((1,), (1,)))
        ds = p * (dp - delta)
        dq_sc[...] += np.float32(scale) * _dot(ds.astype(k.dtype), k, ((1,), (0,)))

    @pl.when(kv_i == n_kv - 1)
    def _finish():
        dq_ref[0] = dq_sc[...].astype(dq_ref.dtype)


def _flash_bwd(q, k, v, out, lse, do, scale, causal, block_q, block_kv):
    with jax.named_scope("kernel.flash_bwd_dkv"):
        # delta and the two broadcasts feed both kernels; they sit with
        # the first
        dkv, lse, delta = _flash_bwd_dkv(q, k, v, out, lse, do, scale,
                                         causal, block_q, block_kv)
    with jax.named_scope("kernel.flash_bwd_dq"):
        dq = _flash_bwd_dq(q, k, v, do, lse, delta, scale, causal,
                           block_q, block_kv)
    return dq, dkv[0], dkv[1]


def _flash_bwd_dkv(q, k, v, out, lse, do, scale, causal, block_q, block_kv):
    bn, s, d = q.shape
    n_q = s // block_q
    n_kv = s // block_kv
    # delta_i = rowsum(dO_i * O_i): cheap elementwise+reduce, done in XLA
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    # sublane-broadcast [bn, s] -> [bn, 8, s] for legal TPU block shapes
    lse = jnp.broadcast_to(lse[:, None, :], (bn, 8, s))
    delta = jnp.broadcast_to(delta[:, None, :], (bn, 8, s))

    dkv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_kv=block_kv, n_q=n_q),
        grid=(bn, n_kv, n_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, ki, qi: (b, qi, np.int32(0))),
            pl.BlockSpec((1, block_kv, d), lambda b, ki, qi: (b, ki, np.int32(0))),
            pl.BlockSpec((1, block_kv, d), lambda b, ki, qi: (b, ki, np.int32(0))),
            pl.BlockSpec((1, block_q, d), lambda b, ki, qi: (b, qi, np.int32(0))),
            pl.BlockSpec((1, 8, block_q), lambda b, ki, qi: (b, np.int32(0), qi)),
            pl.BlockSpec((1, 8, block_q), lambda b, ki, qi: (b, np.int32(0), qi)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_kv, d), lambda b, ki, qi: (b, ki, np.int32(0))),
            pl.BlockSpec((1, block_kv, d), lambda b, ki, qi: (b, ki, np.int32(0))),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bn, s, d), q.dtype),
            jax.ShapeDtypeStruct((bn, s, d), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_kv, d), jnp.float32),
            pltpu.VMEM((block_kv, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(q, k, v, do, lse, delta)
    return dkv, lse, delta


def _flash_bwd_dq(q, k, v, do, lse, delta, scale, causal, block_q, block_kv):
    bn, s, d = q.shape
    n_q = s // block_q
    n_kv = s // block_kv
    return pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_kv=block_kv, n_kv=n_kv),
        grid=(bn, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, np.int32(0))),
            pl.BlockSpec((1, block_kv, d), lambda b, qi, ki: (b, ki, np.int32(0))),
            pl.BlockSpec((1, block_kv, d), lambda b, qi, ki: (b, ki, np.int32(0))),
            pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, np.int32(0))),
            pl.BlockSpec((1, 8, block_q), lambda b, qi, ki: (b, np.int32(0), qi)),
            pl.BlockSpec((1, 8, block_q), lambda b, qi, ki: (b, np.int32(0), qi)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, qi, ki: (b, qi, np.int32(0))),
        out_shape=jax.ShapeDtypeStruct((bn, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
    )(q, k, v, do, lse, delta)


# ---------------------------------------------------------------------------
# custom-vjp wrapper (head-major [B, N, S, D])
# ---------------------------------------------------------------------------

_flags.define_flag("FLAGS_flash_block_q", 0,
                   "flash-attention q block size override (0 = auto)")
_flags.define_flag("FLAGS_flash_block_kv", 0,
                   "flash-attention kv block size override (0 = auto)")


def _auto_block(s: int) -> int:
    from ...analysis.codes import default_block

    return default_block(s)


def _pick_blocks(s: int, d: int = 0, dtype=None):
    """Block sizes for one (seq, head_dim, dtype) specialization, in
    priority order: explicit FLAGS_flash_block_q / FLAGS_flash_block_kv
    overrides (a user pin beats the tuner, per side), then the autotune
    table (``analysis/autotune.py`` — a measured or seeded entry for this
    exact shape key; requires ``d``), then the historical ``_auto_block``
    default.  Invalid flag overrides (non-positive, non-divisor) fall
    back down the chain for that side only."""
    def override(name):
        try:
            v = int(_flags.flag(name) or 0)
        except (TypeError, ValueError):
            return None
        if v > 0:
            v = min(v, s)
            if s % v == 0:
                return v
        return None

    fq = override("FLAGS_flash_block_q")
    fkv = override("FLAGS_flash_block_kv")
    tuned = None
    if d and (fq is None or fkv is None):
        from ...analysis import autotune as _autotune

        tuned = _autotune.kernel_params(
            "flash_attention", {"seq": s, "head_dim": d}, dtype)
        if tuned:
            tbq = int(tuned.get("block_q") or 0)
            tbkv = int(tuned.get("block_kv") or 0)
            if tbq <= 0 or tbkv <= 0 or s % tbq or s % tbkv:
                tuned = None  # forced/tampered/partial params that
                #               cannot tile s — fall back whole
    bq = fq or (tuned and int(tuned["block_q"])) or _auto_block(s)
    bkv = fkv or (tuned and int(tuned["block_kv"])) or _auto_block(s)
    return bq, bkv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_bnsd(q, k, v, causal, scale):
    out, _ = _flash_bnsd_fwd(q, k, v, causal, scale)
    return out


def _flash_bnsd_fwd(q, k, v, causal, scale):
    b, n, s, d = q.shape
    bq, bkv = _pick_blocks(s, d, q.dtype)
    fq, fk, fv = (t.reshape(b * n, s, d) for t in (q, k, v))
    out, lse = _flash_fwd(fq, fk, fv, scale, causal, bq, bkv)
    return out.reshape(b, n, s, d), (q, k, v, out.reshape(b, n, s, d), lse)


def _flash_bnsd_bwd(causal, scale, res, g):
    q, k, v, out, lse = res
    b, n, s, d = q.shape
    bq, bkv = _pick_blocks(s, d, q.dtype)
    dq, dk, dv = _flash_bwd(
        q.reshape(b * n, s, d), k.reshape(b * n, s, d), v.reshape(b * n, s, d),
        out.reshape(b * n, s, d), lse, g.reshape(b * n, s, d),
        scale, causal, bq, bkv)
    return (dq.reshape(b, n, s, d), dk.reshape(b, n, s, d),
            dv.reshape(b, n, s, d))


_flash_bnsd.defvjp(_flash_bnsd_fwd, _flash_bnsd_bwd)


def flash_attention_bnsd(q, k, v, *, causal: bool = False, sm_scale=None):
    """q/k/v: [B, N, S, D] -> [B, N, S, D] (head-major layout)."""
    scale = float(sm_scale if sm_scale is not None else 1.0 / (q.shape[-1] ** 0.5))
    if _on_tpu():
        return _flash_bnsd(q, k, v, causal, scale)
    return _xla_reference_bnsd(q, k, v, causal, scale)


def _xla_reference_bnsd(qh, kh, vh, causal, scale):
    s = jnp.einsum("bnqd,bnkd->bnqk", qh, kh,
                   preferred_element_type=jnp.float32) * np.float32(scale)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bnqk,bnkd->bnqd", p.astype(qh.dtype), vh)


def flash_attention_bshd(q, k, v, *, causal: bool = False):
    """q/k/v: [B, S, N, D] -> [B, S, N, D]."""
    scale = float(1.0 / (q.shape[-1] ** 0.5))
    qh, kh, vh = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))  # [B,N,S,D]
    if _on_tpu():
        out = _flash_bnsd(qh, kh, vh, causal, scale)
    else:
        out = _xla_reference_bnsd(qh, kh, vh, causal, scale)
    return jnp.swapaxes(out, 1, 2)
