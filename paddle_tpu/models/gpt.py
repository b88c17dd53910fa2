"""GPT model family — the flagship decoder-only LM.

``GPTForPretraining`` runs layer by layer and only trains or runs a plain
forward; ``GPTStackedForPretraining`` (one scanned block) trains AND is the
one model that serves (``generate()``, the paged engine).

Reference fixtures: test/auto_parallel/get_gpt_model.py and the hybrid
parallel GPT used across test/collective/fleet/* (Megatron-style TP layers
from fleet/layers/mpu/mp_layers.py, PP partitioning from
parallel_layers/pp_layers.py, recompute from fleet/recompute/recompute.py).

TPU-native design decisions:
- TP is expressed through the mpu layers (Column/Row/VocabParallel), which
  annotate weights with 'mp'-axis NamedShardings; XLA's SPMD partitioner
  inserts the all-reduces the reference hand-codes in mp_ops.py.
- Sequence parallelism (ABSENT in the reference — SURVEY.md §2.2) is a
  first-class option: hidden states are sharded over the sequence axis
  ('sp') between attention blocks, and attention itself may run as ring
  attention over the 'sp' axis (paddle_tpu.nn.functional.attention).
- Attention keeps the whole [B, S, H] computation as large batched matmuls
  (MXU-friendly); causal masking uses an additive mask computed inside the
  traced program (no dynamic shapes).
- recompute_interval enables activation rematerialization per decoder block
  (jax.checkpoint under the hood via fleet.recompute).
"""
from __future__ import annotations

import contextlib
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import ops
from ..nn import functional as F
from ..nn.layer import Layer
from ..nn.modules.common import Dropout, Embedding, Linear
from ..nn.modules.norm import LayerNorm
from ..ops.sharding_ops import shard_constraint
from ..distributed import mesh as _mesh
from ..distributed.fleet.layers.mpu.mp_layers import (
    ColumnParallelLinear,
    ParallelCrossEntropy,
    RowParallelLinear,
    VocabParallelEmbedding,
)
from ..distributed.fleet.recompute import recompute
from ..ops.lora import lora_delta_raw
from ..tensor import Parameter, Tensor, to_tensor
from .generation import GenerationMixin, KVCache

__all__ = [
    "GPTConfig",
    "GPTModel",
    "GPTForPretraining",
    "GPTStackedDecoder",
    "GPTStackedForPretraining",
    "GPTPretrainingCriterion",
    "KVCache",
    "truncated_draft",
    "gpt_tiny",
    "gpt_small",
    "gpt_1p3b",
    "gpt_13b",
]


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: Optional[int] = None  # default 4*hidden
    max_position_embeddings: int = 1024
    hidden_dropout: float = 0.1
    attention_dropout: float = 0.1
    layer_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    use_tensor_parallel: bool = False   # mpu layers over the 'mp' axis
    sequence_parallel: bool = False     # shard activations over 'sp'
    recompute_interval: int = 0         # 0 = off; k = remat every k blocks
    # remat granularity when recompute_interval > 0.  None/"full" =
    # recompute the whole block in backward (min memory, +~fwd/3 hardware
    # FLOPs); "dots" = save matmul outputs and recompute only
    # elementwise/norm work (jax dots_with_no_batch_dims_saveable — near-zero
    # recompute FLOPs at the cost of the saved dot activations).  Applies to
    # the compiled stacked/pipelined path (scan_blocks/pipeline_blocks); the
    # eager per-layer fleet.recompute is an autograd-engine rerun where XLA
    # checkpoint policies have no meaning.
    recompute_policy: Optional[str] = None
    virtual_pp_degree: int = 1          # interleaved virtual stages per device
    # Tri-state SDPA routing: None = defer to FLAGS_use_pallas_flash_attention
    # (default), True = force the pallas kernel (when shape-eligible),
    # False = force the plain XLA expression.
    use_flash_attention: Optional[bool] = None

    def __post_init__(self):
        # validate eagerly: a typo'd policy must fail at config time, not
        # only when remat actually engages (training + interval > 0)
        if self.recompute_policy not in (None, "full", "dots",
                                         "dots_saveable"):
            raise ValueError(
                f"unknown remat policy {self.recompute_policy!r}; expected "
                "one of [None, 'full', 'dots', 'dots_saveable']")

    @property
    def ffn_size(self) -> int:
        return self.intermediate_size or 4 * self.hidden_size

    @property
    def head_dim(self) -> int:
        assert self.hidden_size % self.num_heads == 0
        return self.hidden_size // self.num_heads


def _preset(defaults, kw):
    return GPTConfig(**{**defaults, **kw})


def gpt_tiny(**kw) -> "GPTConfig":
    return _preset(dict(vocab_size=1024, hidden_size=64, num_layers=2, num_heads=4,
                        max_position_embeddings=128), kw)


def gpt_small(**kw) -> "GPTConfig":
    """GPT-2 small class (117M)."""
    return _preset(dict(hidden_size=768, num_layers=12, num_heads=12,
                        max_position_embeddings=1024), kw)


def gpt_1p3b(**kw) -> "GPTConfig":
    """GPT-3 1.3B (BASELINE config 2)."""
    return _preset(dict(hidden_size=2048, num_layers=24, num_heads=16,
                        max_position_embeddings=2048), kw)


def gpt_13b(**kw) -> "GPTConfig":
    """GPT-3 13B (BASELINE config 3)."""
    return _preset(dict(hidden_size=5120, num_layers=40, num_heads=40,
                        max_position_embeddings=2048), kw)


def _winit(cfg: GPTConfig):
    """N(0, initializer_range) weight attr (reference GPT fixtures)."""
    from ..nn.initializer import Normal
    from ..nn.param_attr import ParamAttr

    return ParamAttr(initializer=Normal(0.0, cfg.initializer_range))


def _seq_shard(x: Tensor, cfg: GPTConfig) -> Tensor:
    """Sequence-parallel layout constraint: [B, S, H] sharded (dp, sp, -)."""
    if cfg.sequence_parallel and _mesh.has_mesh() and _mesh.axis_size("sp") > 1:
        return shard_constraint(x, "dp", "sp", None)
    return x


# ---------------------------------------------------------------------------
# KV-cache decode path of the stacked decoder
# ---------------------------------------------------------------------------

def _as_pos(cache_index) -> Tensor:
    """Normalize a cache position to a scalar int32 Tensor (a TRACED
    scalar under jit — positions are data, never shapes)."""
    if isinstance(cache_index, Tensor):
        return cache_index
    return to_tensor(np.int32(cache_index or 0))


def _cache_position_ids(input_ids: Tensor, pos: Tensor) -> Tensor:
    """position_ids [B, S] = cache position offset + arange(S).

    ``pos`` is a scalar on the single-request decode path and a per-slot
    vector ``[B]`` on the continuous-batching paged path (every slot sits
    at its own position)."""
    s = input_ids.shape[-1]
    rel = ops.arange(0, s, dtype="int64")
    if len(pos.shape) == 1:
        return ops.unsqueeze(pos.astype("int64"), 1) + ops.unsqueeze(rel, 0)
    rel = rel + pos.astype("int64")
    return ops.expand(ops.unsqueeze(rel, 0), list(input_ids.shape))


def _resolve_use_flash(cfg: GPTConfig) -> bool:
    if cfg.use_flash_attention is not None:
        return bool(cfg.use_flash_attention)
    from ..core import flags as _flags

    return bool(_flags.flag("FLAGS_use_pallas_flash_attention"))


def _flash_over_mesh(q, k, v, scale):
    """Causal Pallas flash attention over [B, N, S, D] for the training
    block.  The compiler cannot partition a Mosaic kernel ("wrap the call
    in a shard_map"), so under a global mesh the kernel runs per shard —
    batch over 'dp', heads over 'mp', the layout the column-parallel qkv
    projection already produces — the way the serving path shards its
    paged attention (``_raw_attend_paged``).  An axis the mesh lacks, or
    that does not divide the dim, leaves that dim whole on every chip;
    with neither axis in play (no mesh, or a pipeline-only one) the call
    is direct."""
    from jax.sharding import PartitionSpec as _P

    from ..ops.pallas_kernels.flash_attention import flash_attention_bnsd

    def flash(q_, k_, v_):
        return flash_attention_bnsd(q_, k_, v_, causal=True, sm_scale=scale)

    mesh = _mesh.get_mesh() if _mesh.has_mesh() else None

    def axis(name, dim):
        size = dict(mesh.shape).get(name, 1) if mesh is not None else 1
        return name if size > 1 and dim % size == 0 else None

    dp, mp = axis("dp", q.shape[0]), axis("mp", q.shape[1])
    if dp is None and mp is None:
        return flash(q, k, v)
    spec = _P(dp, mp, None, None)
    with jax.named_scope("shard.flash"):
        return jax.shard_map(flash, mesh=mesh, in_specs=(spec, spec, spec),
                             out_specs=spec, check_vma=False)(q, k, v)


def _dropout(x, rate, key):
    mask = jax.random.bernoulli(key, 1.0 - rate, x.shape)
    return jnp.where(mask, x / (1.0 - rate), jnp.zeros_like(x))


def _ln_f32(x, g, b, eps):
    """fp32 LayerNorm of the stacked block (``GPTStackedDecoder._block_fn``)."""
    x = x.astype(jnp.float32)
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _raw_attend_with_cache(qh, kh, vh, ckr, cvr, posr, *, head_dim,
                           use_flash, pos_is_zero=True):
    """Raw (traced) cache write + attend.  qh/kh/vh: [B, N, S, D] head-major
    fresh projections; ckr/cvr: [B, N, max_seq, D] cache; posr: traced
    scalar position.  Returns (out [B, N, S, D], new_k, new_v).

    S == 1 is the decode step: position-indexed ``dynamic_update_slice``
    write, then the q-len-1 flash-decode kernel (XLA fallback off-TPU) over
    ``posr + 1`` valid positions.  S > 1 with ``pos_is_zero`` is the
    common whole-prompt prefill: it attends causally to itself, so
    attention runs over the fresh K/V (flash kernel when eligible) while
    the cache is populated.  S > 1 at a nonzero/unknown position (chunked
    prefill) attends over the WHOLE updated cache with an absolute-
    position causal+length mask — earlier chunks are visible."""
    from ..ops.pallas_kernels.decode_attention import decode_attention
    from ..ops.pallas_kernels.flash_attention import (
        _on_tpu, flash_attention_bnsd, shape_supported,
    )

    s = qh.shape[2]
    scale = float(1.0 / np.sqrt(head_dim))
    p = posr.astype(jnp.int32)
    zero = jnp.zeros((), p.dtype)
    idx = (zero, zero, p, zero)
    ck2 = jax.lax.dynamic_update_slice(ckr, kh.astype(ckr.dtype), idx)
    cv2 = jax.lax.dynamic_update_slice(cvr, vh.astype(cvr.dtype), idx)
    if s == 1:
        out = decode_attention(qh[:, :, 0, :], ck2, cv2, p + 1,
                               sm_scale=scale)
        out = out[:, :, None, :].astype(qh.dtype)
    elif not pos_is_zero:
        # chunked prefill: queries at absolute positions p..p+S-1 attend to
        # every cache position <= their own (covers earlier chunks)
        max_seq = ck2.shape[2]
        scores = jnp.einsum("bnqd,bnkd->bnqk", qh.astype(ck2.dtype), ck2,
                            preferred_element_type=jnp.float32) * scale
        rows = p + jax.lax.broadcasted_iota(jnp.int32, (s, max_seq), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (s, max_seq), 1)
        scores = jnp.where(cols <= rows, scores,
                           jnp.asarray(-1e9, scores.dtype))
        att = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bnqk,bnkd->bnqd", att.astype(cv2.dtype),
                         cv2).astype(qh.dtype)
    elif use_flash and _on_tpu() and shape_supported(s, head_dim):
        out = flash_attention_bnsd(qh.astype(kh.dtype), kh, vh, causal=True,
                                   sm_scale=scale).astype(qh.dtype)
    else:
        scores = jnp.einsum("bnqd,bnkd->bnqk", qh, kh,
                            preferred_element_type=jnp.float32) * scale
        causal = jnp.tril(jnp.ones((s, s), jnp.bool_))
        scores = jnp.where(causal, scores, jnp.asarray(-1e9, scores.dtype))
        att = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bnqk,bnkd->bnqd", att.astype(qh.dtype), vh)
    return out, ck2, cv2


def _pos_is_static_zero(pos: Tensor) -> bool:
    """True when the cache position is a compile-time-known 0 (the whole-
    prompt prefill) — selects the fast self-attention prefill path.  A
    traced or nonzero position routes S>1 calls to the general
    cache-masked path instead (chunked prefill stays correct)."""
    v = pos._value
    return not isinstance(v, jax.core.Tracer) and int(np.asarray(v)) == 0


def _raw_attend_paged(qh, kh, vh, pkr, pvr, tables, posr, *, head_dim,
                      page_size, ragged_plan=None, ksr=None, vsr=None):
    """Raw (traced) paged cache write + attend for continuous batching —
    dispatching between the single-pool body and, under an active serving
    mesh with ``mp > 1`` (``distributed/serving_mesh.py``), the SAME body
    run per head shard under ``shard_map``: each chip scatters into and
    attends over its own ``[P, H/mp, page_size, D]`` pool shard, with the
    page tables / positions / ragged plan replicated.  The head-parallel
    path is psum-free; the first cross-chip reduce is the row-parallel
    post-attention projection GSPMD inserts outside this function.
    ``ksr``/``vsr`` ([P, H] fp32) enable the int8-pool regime: the
    per-(page, head) scale buffers shard on the SAME head axis as the
    pools and are threaded through (updated at write time), so the
    function then returns a 5-tuple.  See :func:`_attend_paged_shard`
    for the shapes and semantics."""
    from ..distributed import serving_mesh as _srv_mesh

    quantized = ksr is not None
    mesh = _srv_mesh.active_mesh()
    if mesh is not None and _srv_mesh.mp_size(mesh) > 1:
        from jax.sharding import PartitionSpec as _P

        n_plan = len(ragged_plan) if ragged_plan is not None else 0

        def body(qh_, kh_, vh_, pkr_, pvr_, tbl_, posr_, *rest):
            if quantized:
                ksr_, vsr_ = rest[:2]
                planr = rest[2:]
            else:
                ksr_ = vsr_ = None
                planr = rest
            return _attend_paged_shard(
                qh_, kh_, vh_, pkr_, pvr_, tbl_, posr_,
                head_dim=head_dim, page_size=page_size,
                ragged_plan=planr if n_plan else None,
                ksr=ksr_, vsr=vsr_)

        hs = _P(None, "mp", None, None)     # head axis of q/k/v and pools
        ss = _P(None, "mp")                 # head axis of the scale bufs
        rep = _P()
        sm = jax.shard_map(
            body, mesh=mesh,
            in_specs=(hs, hs, hs, hs, hs, rep, rep)
            + ((ss, ss) if quantized else ()) + (rep,) * n_plan,
            out_specs=(hs, hs, hs) + ((ss, ss) if quantized else ()),
            check_vma=False)
        return sm(qh, kh, vh, pkr, pvr, tables, posr,
                  *((ksr, vsr) if quantized else ()),
                  *(tuple(ragged_plan) if n_plan else ()))
    return _attend_paged_shard(qh, kh, vh, pkr, pvr, tables, posr,
                               head_dim=head_dim, page_size=page_size,
                               ragged_plan=ragged_plan, ksr=ksr, vsr=vsr)


def _attend_paged_shard(qh, kh, vh, pkr, pvr, tables, posr, *, head_dim,
                        page_size, ragged_plan=None, ksr=None, vsr=None,
                        window=None, attend_scope=None):
    """Raw (traced) paged cache write + attend for continuous batching.

    qh/kh/vh: [S, N, C, D] head-major fresh projections (S decode slots —
    or, on the ragged fused-step path, S flat query TOKENS with C == 1);
    kh/vh may carry fewer heads than qh (grouped queries, ragged path
    only): N below is then THEIR count, the pool's;
    pkr/pvr: [P, N, page_size, D] global page pools; tables: [S, max_pages]
    int32 page tables (per-token rows on the ragged path); posr: [S]
    traced per-slot/per-token positions.  Returns
    (out [S, N, C, D], new_k_pool, new_v_pool).  ``window`` (ragged path
    only): the attention reads the newest ``window`` positions;
    ``attend_scope`` names a scope around the ragged launch (a decoder with
    several kinds of attention tells them apart by it).

    ``ksr``/``vsr`` ([P, N] fp32) switch on the int8-pool regime: the
    fresh K/V rows are quantized in-graph at scatter time
    (quantization/kv.quantize_kv_write — fresh-page step-absmax, stale-
    page clip) and every attention route dequantizes at read (inside the
    kernel body for the ragged/paged kernels, at gather for the chunked
    path).  The return grows to (out, new_k_pool, new_v_pool,
    new_k_scale, new_v_scale).

    Every write translates an absolute position through the page table:
    position p of slot s lands at ``pool[tables[s, p//page_size], :,
    p%page_size]``.  On a TPU, with the engine's plan and a bfloat16 or
    float32 pool, that is ONE launch over the plan's write list
    (ops/pallas_kernels/pool_write.py): the tile groups the step's real
    tokens touch, K and V together, read-modify-written in place; padding
    tokens write nothing.  Everywhere else (off the TPU, int8 pools, the
    plan-less callers) it is one row of D per head scattered on the pool's
    flat ``[P*N*page_size, D]`` view, which keeps the pool's own layout and
    updates it in place: inactive slots and prefill padding carry null-
    page table entries, so those rows sink into page 0 (never validly
    read; under the stacked decoder P is L*P and the sink layer l's own).
    C == 1 is the batched decode step: scatter one token per row, then
    the paged flash-decode kernel (XLA gather fallback off-TPU) over each
    row's own pages — or, with ``ragged_plan`` (the serving engine's fused
    mixed prefill/decode step), the ragged work-list kernel over the same
    write: every row is one flat query token whose causal context is its
    own position, so decode tokens and prefill chunk tokens share the ONE
    launch (ops/pallas_kernels/ragged_paged_attention.py).  C > 1 is the
    retired-from-serving chunked prefill path (kept for direct
    ``_paged_lm_logits`` callers): the chunk scatters into (possibly
    non-contiguous) pages and attends over the whole gathered context
    with an absolute-position causal mask."""
    from ..ops.pallas_kernels.paged_attention import (
        gather_pages, paged_attention,
    )
    from ..ops.pallas_kernels.pool_write import pool_write, pool_write_runs
    from ..ops.pallas_kernels.ragged_paged_attention import (
        ragged_paged_attention, write_list_of,
    )

    s_, _, c, d = qh.shape
    nh = kh.shape[1]            # the pool's head count is K's, not q's
    quantized = ksr is not None
    max_pages = tables.shape[1]
    scale = float(1.0 / np.sqrt(head_dim))
    pos = posr.astype(jnp.int32)
    tbl = tables.astype(jnp.int32)
    abs_pos = pos[:, None] + jax.lax.broadcasted_iota(
        jnp.int32, (s_, c), 1)                               # [S, C]
    ks2 = vs2 = None
    with jax.named_scope("attn.pool_write"):
        if (c == 1 and ragged_plan is not None and not quantized
                and pool_write_runs(page_size, pkr.dtype)):
            # the step's own write list: one launch, the tile groups its
            # real tokens touch, K and V together, in place
            pk2, pv2 = pool_write(
                (pkr, pvr), (kh[:, :, 0, :], vh[:, :, 0, :]),
                write_list_of(ragged_plan))
        else:
            # the clip is defensive: the engine reserves every page a
            # request can touch up front, so real token positions never
            # run past the table
            page_slot = jnp.clip(abs_pos // page_size, 0, max_pages - 1)
            page_ids = jnp.take_along_axis(tbl, page_slot, axis=1)  # [S, C]
            offs = abs_pos % page_size
            kq = jnp.transpose(kh, (0, 2, 1, 3))             # [S, C, N, D]
            vq = jnp.transpose(vh, (0, 2, 1, 3))
            if quantized:
                # int8 pools: quantize the fresh rows in-graph and update
                # the per-(page, head) scale buffers before the scatter
                from ..quantization.kv import quantize_kv_write

                kq, ks2 = quantize_kv_write(kq, page_ids, offs, ksr)
                vq, vs2 = quantize_kv_write(vq, page_ids, offs, vsr)
            # one row of D per (token, head) on the pool's free
            # [P*N*page, D] view: the write's natural layout is the pool's
            # own, so it updates the buffer in place (the sink rows
            # repeat: never unique_indices)
            rows = ((page_ids[..., None] * nh
                     + jnp.arange(nh, dtype=jnp.int32))
                    * page_size + offs[..., None])           # [S, C, N]
            pk2 = pkr.reshape(-1, d).at[rows].set(
                kq.astype(pkr.dtype)).reshape(pkr.shape)
            pv2 = pvr.reshape(-1, d).at[rows].set(
                vq.astype(pvr.dtype)).reshape(pvr.shape)
    if c == 1 and ragged_plan is not None:
        # the keyword only where a window is asked: the call every other
        # decoder makes is the one it always was
        windowed = {} if window is None else {"window": window}
        with (jax.named_scope(attend_scope) if attend_scope
              else contextlib.nullcontext()):
            out = ragged_paged_attention(qh[:, :, 0, :], pk2, pv2, tbl,
                                         pos + 1, ragged_plan, sm_scale=scale,
                                         k_scale=ks2, v_scale=vs2, **windowed)
        out = out[:, :, None, :].astype(qh.dtype)
    elif c == 1:
        out = paged_attention(qh[:, :, 0, :], pk2, pv2, tbl, pos + 1,
                              sm_scale=scale, k_scale=ks2, v_scale=vs2)
        out = out[:, :, None, :].astype(qh.dtype)
    else:
        # chunked prefill: queries at absolute positions p..p+C-1 attend to
        # every written position <= their own across the gathered pages
        # (int8 pools dequantize at gather — ck/cv come back fp32)
        ck = gather_pages(pk2, tbl, ks2)                     # [S, N, ctx, D]
        cv = gather_pages(pv2, tbl, vs2)
        scores = jnp.einsum("snqd,snkd->snqk", qh.astype(ck.dtype), ck,
                            preferred_element_type=jnp.float32) * scale
        cols = jax.lax.broadcasted_iota(
            jnp.int32, (s_, c, ck.shape[2]), 2)
        mask = cols <= abs_pos[:, :, None]
        scores = jnp.where(mask[:, None, :, :], scores,
                           jnp.asarray(-1e9, scores.dtype))
        att = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("snqk,snkd->snqd", att.astype(cv.dtype),
                         cv).astype(qh.dtype)
    if quantized:
        return out, pk2, pv2, ks2, vs2
    return out, pk2, pv2


class GPTEmbeddings(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        wa = _winit(cfg)
        if cfg.use_tensor_parallel:
            self.word_embeddings = VocabParallelEmbedding(cfg.vocab_size, cfg.hidden_size, weight_attr=wa)
        else:
            self.word_embeddings = Embedding(cfg.vocab_size, cfg.hidden_size, weight_attr=wa)
        self.position_embeddings = Embedding(cfg.max_position_embeddings, cfg.hidden_size, weight_attr=_winit(cfg))
        self.dropout = Dropout(cfg.hidden_dropout)
        self._cfg = cfg

    def forward(self, input_ids: Tensor, position_ids: Optional[Tensor] = None) -> Tensor:
        with jax.named_scope("embed"):
            if position_ids is None:
                seq_len = input_ids.shape[-1]
                position_ids = ops.arange(0, seq_len, dtype="int64")
                position_ids = ops.expand(ops.unsqueeze(position_ids, 0), list(input_ids.shape))
            h = self.word_embeddings(input_ids) + self.position_embeddings(position_ids)
            h = self.dropout(h)
            return _seq_shard(h, self._cfg)


class GPTAttention(Layer):
    """Causal multi-head self-attention, fused-QKV (single [H, 3H] matmul so
    the MXU sees one large GEMM, like the reference's fused_attention op —
    paddle/fluid/operators/fused/fused_attention_op.cu — but here fusion is
    a layout choice + XLA, not a handwritten kernel)."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self._cfg = cfg
        h = cfg.hidden_size
        wa = _winit(cfg)
        if cfg.use_tensor_parallel:
            self.qkv_proj = ColumnParallelLinear(h, 3 * h, gather_output=False, weight_attr=wa)
            self.out_proj = RowParallelLinear(h, h, input_is_parallel=True, weight_attr=_winit(cfg))
        else:
            self.qkv_proj = Linear(h, 3 * h, weight_attr=wa)
            self.out_proj = Linear(h, h, weight_attr=_winit(cfg))
        self.dropout = Dropout(cfg.hidden_dropout)

    def forward(self, x: Tensor, attn_mask: Optional[Tensor] = None) -> Tensor:
        cfg = self._cfg
        b, s = x.shape[0], x.shape[1]
        nh, hd = cfg.num_heads, cfg.head_dim
        with jax.named_scope("attn.qkv"):
            qkv = self.qkv_proj(x)                          # [B, S, 3H]
            qkv = ops.reshape(qkv, [b, s, 3, nh, hd])
            q = ops.squeeze(ops.slice(qkv, [2], [0], [1]), 2)  # [B, S, nh, hd]
            k = ops.squeeze(ops.slice(qkv, [2], [1], [2]), 2)
            v = ops.squeeze(ops.slice(qkv, [2], [2], [3]), 2)
        with jax.named_scope("attn.core"):
            out = self._attend(q, k, v, attn_mask)
        with jax.named_scope("attn.out"):
            out = ops.reshape(out, [b, s, nh * hd])
            return self.dropout(self.out_proj(out))

    def _attend(self, q, k, v, attn_mask) -> Tensor:
        """q/k/v [B, S, nh, hd] -> [B, S, nh, hd]."""
        cfg = self._cfg
        # sequence-parallel causal attention runs as a ring over 'sp'
        # (K/V rotate via ppermute; online-softmax merge) — the S axis stays
        # sharded instead of being all-gathered for the score matmul
        if (cfg.sequence_parallel and attn_mask is None
                and cfg.attention_dropout == 0.0
                and _mesh.has_mesh() and _mesh.axis_size("sp") > 1):
            from ..nn.functional.ring_attention import ring_attention

            return ring_attention(q, k, v, causal=True)
        return F.scaled_dot_product_attention(
            q, k, v,
            attn_mask=attn_mask,
            dropout_p=cfg.attention_dropout,
            is_causal=attn_mask is None,
            training=self.training,
            use_flash=cfg.use_flash_attention,
        )                                                   # [B, S, nh, hd]


class GPTMLP(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        h, f = cfg.hidden_size, cfg.ffn_size
        wa = _winit(cfg)
        if cfg.use_tensor_parallel:
            self.fc1 = ColumnParallelLinear(h, f, gather_output=False, weight_attr=wa)
            self.fc2 = RowParallelLinear(f, h, input_is_parallel=True, weight_attr=_winit(cfg))
        else:
            self.fc1 = Linear(h, f, weight_attr=wa)
            self.fc2 = Linear(f, h, weight_attr=_winit(cfg))
        self.dropout = Dropout(cfg.hidden_dropout)

    def forward(self, x: Tensor) -> Tensor:
        return self.dropout(self.fc2(F.gelu(self.fc1(x), approximate=True)))


class GPTDecoderLayer(Layer):
    """Pre-LN decoder block (reference GPT fixtures use pre-normalization)."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self._cfg = cfg
        self.ln1 = LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)
        self.attn = GPTAttention(cfg)
        self.ln2 = LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)
        self.mlp = GPTMLP(cfg)

    def forward(self, x: Tensor, attn_mask: Optional[Tensor] = None) -> Tensor:
        with jax.named_scope("attn.qkv"):
            h = self.ln1(x)
        a = self.attn(h, attn_mask)
        with jax.named_scope("attn.out"):
            x = x + a
        with jax.named_scope("mlp"):
            x = x + self.mlp(self.ln2(x))
            return _seq_shard(x, self._cfg)


class GPTModel(Layer):
    """Decoder-only transformer body -> final LayerNorm hidden states."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.config = cfg
        self.embeddings = GPTEmbeddings(cfg)
        self.layers = [GPTDecoderLayer(cfg) for _ in range(cfg.num_layers)]
        for i, layer in enumerate(self.layers):
            self.add_sublayer(f"layer_{i}", layer)
        self.final_ln = LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)

    def forward(self, input_ids: Tensor, position_ids: Optional[Tensor] = None,
                attn_mask: Optional[Tensor] = None) -> Tensor:
        h = self.embeddings(input_ids, position_ids)
        k = self.config.recompute_interval
        for i, layer in enumerate(self.layers):
            if k and (i % k == 0) and self.training:
                h = recompute(layer, h, attn_mask)
            else:
                h = layer(h, attn_mask)
        with jax.named_scope("lm_head"):
            return self.final_ln(h)


class GPTForPretraining(Layer):
    """The eager, layer-by-layer GPT for training and plain forward passes:
    LM head tied to the word embedding (reference GPT fixtures tie
    weights; logits = h @ E^T, a vocab-sharded matmul under TP).  It does
    not serve: ``generate()`` and the engine's paged contract belong to
    :class:`GPTStackedForPretraining`."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.gpt = GPTModel(cfg)
        self.config = cfg

    def forward(self, input_ids: Tensor, position_ids: Optional[Tensor] = None,
                attn_mask: Optional[Tensor] = None) -> Tensor:
        h = self.gpt(input_ids, position_ids, attn_mask)
        with jax.named_scope("lm_head"):
            w = self.gpt.embeddings.word_embeddings.weight  # [V, H]
            return ops.matmul(h, w, transpose_y=True)       # [B, S, V]


class GPTStackedDecoder(Layer):
    """All decoder blocks as STACKED parameters ([L, ...], homogeneous
    blocks) executed via lax.scan — and, when the mesh has a 'pp' axis > 1,
    as an SPMD microbatch pipeline (pp_spmd.pipeline_blocks).

    This is the performance path: the block body compiles once instead of
    L times, remat applies per block, the stacked leading dim shards over
    'pp', and the TP dims shard over 'mp' (GSPMD propagates the Megatron
    collectives from the parameter shardings). Reference analog:
    PipelineLayer segmenting + 1F1B runtime + recompute, fused into one
    XLA program.
    """

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self._cfg = cfg
        L, h, f = cfg.num_layers, cfg.hidden_size, cfg.ffn_size
        if _mesh.has_mesh() and "pp" in _mesh.get_mesh().axis_names:
            pp = _mesh.get_mesh().shape["pp"]
            if L % pp != 0:
                raise ValueError(
                    f"num_layers={L} must be divisible by the pp mesh axis "
                    f"size {pp} (uniform stage segmenting)")
        std = cfg.initializer_range
        # derive init keys from the global generator so pt.seed() controls
        # stacked-decoder init like every other layer.  Init runs ON DEVICE
        # (jax.random.normal) — at 1B+ scale, host-side numpy init would
        # mean multi-GB host->device transfers.
        from ..ops.random import default_generator

        def mk(shape, init="normal"):
            if init == "zeros":
                raw = jnp.zeros(shape, jnp.float32)
            elif init == "ones":
                raw = jnp.ones(shape, jnp.float32)
            else:
                key = default_generator.split()
                raw = jax.random.normal(key, list(shape), jnp.float32) * std
            return Parameter(raw, trainable=True)

        self.ln1_g = mk([L, h], "ones")
        self.ln1_b = mk([L, h], "zeros")
        self.qkv_w = mk([L, h, 3 * h])
        self.qkv_b = mk([L, 3 * h], "zeros")
        self.proj_w = mk([L, h, h])
        self.proj_b = mk([L, h], "zeros")
        self.ln2_g = mk([L, h], "ones")
        self.ln2_b = mk([L, h], "zeros")
        self.fc1_w = mk([L, h, f])
        self.fc1_b = mk([L, f], "zeros")
        self.fc2_w = mk([L, f, h])
        self.fc2_b = mk([L, h], "zeros")
        self._shard_params()

    _PARAM_NAMES = ("ln1_g", "ln1_b", "qkv_w", "qkv_b", "proj_w", "proj_b",
                    "ln2_g", "ln2_b", "fc1_w", "fc1_b", "fc2_w", "fc2_b")
    # post-quantize_weights() scan layout: each projection weight becomes
    # (int8 weight, per-(layer, out-channel) fp32 scale)
    _PARAM_NAMES_INT8 = tuple(
        m for n in _PARAM_NAMES
        for m in ((n + "_int8", n + "_s") if n.endswith("_w") else (n,)))

    def _stacked(self):
        int8 = getattr(self, "_weight_int8", False)
        return [getattr(self, n) for n in
                (self._PARAM_NAMES_INT8 if int8 else self._PARAM_NAMES)]

    def quantize_weights(self):
        """PTQ the stacked projection weights to int8 for serving
        (quantization.quantize_for_serving): per-(layer, out-channel)
        absmax scales, weights stored AS int8 buffers — the serving scan
        streams 1/4 the fp32 weight bytes per decode step and the MXU
        multiplies int8 natively.  Inference-only and idempotent; the
        training/cached block bodies refuse a quantized decoder."""
        if getattr(self, "_weight_int8", False):
            return
        if _mesh.has_mesh() and _mesh.axis_size("mp") > 1:
            raise ValueError(
                "quantize_weights: the stacked projection weights are "
                "mp-sharded; per-channel PTQ over gathered shards is not "
                "supported — serve tensor-parallel models with fp weights")
        for name in ("qkv_w", "proj_w", "fc1_w", "fc2_w"):
            w = np.asarray(getattr(self, name)._value,
                           np.float32)                     # [L, in, out]
            s = np.abs(w).max(axis=1) / 127.0 + 1e-12      # [L, out]
            q = np.clip(np.round(w / s[:, None, :]),
                        -127, 127).astype(np.int8)
            self.register_buffer(name + "_int8", Tensor(jnp.asarray(q)))
            self.register_buffer(
                name + "_s", Tensor(jnp.asarray(s.astype(np.float32))))
        self._weight_int8 = True

    def _shard_params(self):
        """Leading (layer) dim over 'pp'; TP dims over 'mp'."""
        if not _mesh.has_mesh():
            return
        mesh = _mesh.get_mesh()
        pp = "pp" if ("pp" in mesh.axis_names and mesh.shape["pp"] > 1) else None
        mp = "mp" if ("mp" in mesh.axis_names and mesh.shape["mp"] > 1) else None
        from ..ops.sharding_ops import shard_param

        col = {"qkv_w": (pp, None, mp), "fc1_w": (pp, None, mp),
               "qkv_b": (pp, mp), "fc1_b": (pp, mp),
               "proj_w": (pp, mp, None), "fc2_w": (pp, mp, None)}
        for name in self._PARAM_NAMES:
            p = getattr(self, name)
            spec = col.get(name, (pp,) + (None,) * (p.ndim - 1))
            spec = spec + (None,) * (p.ndim - len(spec))
            shard_param(p, *spec)

    def _refuse_int8(self, what: str):
        if getattr(self, "_weight_int8", False):
            raise ValueError(
                "decoder was quantized for serving (quantize_weights); "
                f"{what} needs the fp weights (int8 weights serve through "
                "the paged engine)")

    def _block_fn(self, with_dropout: bool = False):
        """THE decoder block, written once: LayerNorm -> fused QKV ->
        attention core -> projection -> LayerNorm -> GELU feed-forward.

        Returns ``block(p, h, attend, drop_keys=None, lora=None) ->
        (h, state)``: ``p`` one layer's slice of ``_stacked()``;
        ``attend(q, k, v) -> (out, state)`` the attention core over
        head-major ``[B, N, S, D]``, closed over whatever it carries
        (nothing in training; a layer's contiguous cache and position; the
        page pool, tables, positions, plan and scales).  What only one use
        needs is a Python-level choice made when the block is built, so
        each use traces its own operations and no other: hidden dropout
        only ``with_dropout`` (``drop_keys``: two PRNG keys), int8 x int8
        projections (fp32 activations in, fp32 dequant epilogue) only
        after ``quantize_weights()``, a LoRA delta only when ``lora =
        (8 slabs [P, dim, r], ids, scaling)`` is given.

        AMP O1: matmuls/attention run in the amp dtype (MXU path),
        LayerNorm/softmax/residual stay fp32 — the split the per-op lists
        give the unfused model, here as explicit casts because the block
        is a single dispatched op."""
        cfg = self._cfg
        nh, hd = cfg.num_heads, cfg.head_dim
        eps, hid_p = cfg.layer_norm_eps, cfg.hidden_dropout
        hid_drop = with_dropout and hid_p > 0.0
        from ..amp.auto_cast import _amp_state
        from ..quantization.int8 import quantized_matmul_raw

        cdt = _amp_state.dtype if (_amp_state.enabled
                                   and _amp_state.level == "O1") else None
        wq = bool(getattr(self, "_weight_int8", False))
        names = self._PARAM_NAMES_INT8 if wq else self._PARAM_NAMES

        def block(p, h, attend, drop_keys=None, lora=None):
            p = dict(zip(names, p))
            if cdt is not None and not wq:
                p = {n: a if n.startswith("ln") else a.astype(cdt)
                     for n, a in p.items()}

            def linear(i, name, x):
                if wq:
                    x = x.astype(jnp.float32)
                    y = quantized_matmul_raw(x, p[name + "_w_int8"],
                                             p[name + "_w_s"], p[name + "_b"])
                else:
                    # an fp32 LayerNorm output returns to the WEIGHT dtype
                    # first (== cdt under AMP O1; == the storage dtype of a
                    # pure-bf16 model, which runs OUTSIDE auto_cast): else
                    # jax promotes the bf16 weights and the matmul leaves
                    # the bf16 MXU path (graph_lint GL001)
                    x = x.astype(p[name + "_w"].dtype)
                    y = x @ p[name + "_w"] + p[name + "_b"]
                if lora is not None:
                    # per-token gathered low-rank delta on the SAME input
                    # as the base projection (serving/lora.py slab order)
                    slabs, ids, lscale = lora
                    y = y + lora_delta_raw(x, slabs[2 * i], slabs[2 * i + 1],
                                           ids, lscale)
                return y

            def drop(x, key):
                return _dropout(x, hid_p, key) if hid_drop else x

            k2, k3 = drop_keys or (None, None)
            b, s, hidden = h.shape
            with jax.named_scope("attn.qkv"):
                x = _ln_f32(h, p["ln1_g"], p["ln1_b"], eps)
                qkv = linear(0, "qkv", x).reshape(b, s, 3, nh, hd)
                q, k, v = (jnp.swapaxes(qkv[:, :, i], 1, 2) for i in range(3))  # [B,N,S,D]
            with jax.named_scope("attn.core"):
                out, state = attend(q, k, v)                # [B,N,S,D]
            with jax.named_scope("attn.out"):
                out = jnp.swapaxes(out, 1, 2).reshape(b, s, hidden)
                h = h + drop(linear(1, "proj", out), k2).astype(h.dtype)
            with jax.named_scope("mlp"):
                y = linear(2, "fc1", _ln_f32(h, p["ln2_g"], p["ln2_b"], eps))
                y = linear(3, "fc2", jax.nn.gelu(y, approximate=True))
                return h + drop(y, k3).astype(h.dtype), state

        return block

    def _train_core(self, with_dropout: bool):
        """The training attention core ``(q, k, v, key) -> out``: the
        Pallas flash kernel when shape-eligible (there is no attention
        dropout inside the kernel), else the XLA expression with an fp32
        softmax.  Both see amp-dtype q/k/v."""
        cfg = self._cfg
        hd, attn_p = cfg.head_dim, cfg.attention_dropout
        attn_drop = with_dropout and attn_p > 0.0
        use_flash = _resolve_use_flash(cfg)
        scale = float(1.0 / np.sqrt(hd))

        def core(q, k, v, key):
            from ..ops.pallas_kernels.flash_attention import (
                _on_tpu, shape_supported,
            )

            s = q.shape[2]
            if (use_flash and _on_tpu() and not attn_drop
                    and shape_supported(s, hd)):
                return _flash_over_mesh(q, k, v, scale)
            scores = jnp.einsum("bnqd,bnkd->bnqk", q, k,
                                preferred_element_type=jnp.float32)
            scores = scores * scale
            causal = jnp.tril(jnp.ones((s, s), jnp.bool_))
            scores = jnp.where(causal, scores, jnp.asarray(-1e9, scores.dtype))
            att = jax.nn.softmax(scores, axis=-1)
            if attn_drop:
                att = _dropout(att, attn_p, key)
            return jnp.einsum("bnqk,bnkd->bnqd", att.astype(q.dtype), v)

        return core

    def _forward_paged(self, hidden: Tensor, paged_cache, page_tables,
                       cache_index, ragged_plan=None, lora=None) -> Tensor:
        """Serving step over the stacked parameters with the
        [L, P, H, page_size, D] page pool: lax.scan CARRIES each pool
        (and an int8 pool's [L, P, H] scales) beside the hidden state as
        one buffer viewed [L*P, H, page_size, D], and layer ``l`` (the
        index rides xs) offsets its page ids by ``l*P``: the tables, and
        the plan's ``wl_page`` and ``wr_page``.  Donated under jit.to_static (mutation-
        logged), the buffer is updated in place: no operation of a step
        moves a layer's pool (tests/test_pool_in_place.py).  The other
        ``ragged_plan`` Tensors are scan constants.  ``lora`` is
        ``(LoRAAdapterPool, per-token adapter ids)``: the
        ``[L, pages, ...]`` adapter slabs scan alongside the parameters,
        the ids ride as a scan constant."""
        from ..ops import dispatch
        from ..ops.pallas_kernels.ragged_paged_attention import plan_at_layer

        pos = _as_pos(cache_index)
        block = self._block_fn()
        hd, page_size = self._cfg.head_dim, int(paged_cache.page_size)
        plan = tuple(ragged_plan) if ragged_plan is not None else ()
        n_plan = len(plan)
        if lora is not None:
            pool_, ids_ = lora
            lora_in = (ids_,) + tuple(pool_.stacked_slabs())  # 8 x [L,P,dim,r]
            lscale = pool_.scaling
        else:
            lora_in, lscale = (), 0.0
        n_lora = len(lora_in)
        # int8 pool: the [L, P, H] scale buffers follow the pools
        pool_in = (paged_cache.k, paged_cache.v)
        if paged_cache.quantized:
            pool_in += (paged_cache.k_scale, paged_cache.v_scale)
        nt = len(pool_in)
        n_layers, n_pages = (int(n) for n in paged_cache.k.shape[:2])

        def raw(h, posr, tbl, *rest):
            planr = rest[:n_plan] if n_plan else None
            rest = rest[n_plan:]
            if n_lora:
                idsr, *slabr = rest[:n_lora]
                rest = rest[n_lora:]
            pools, stacked = rest[:nt], rest[nt:]

            def step(carry, xs):
                base, xs = xs[0] * n_pages, xs[1:]
                if n_lora:
                    params, lr = xs[:-8], (tuple(xs[-8:]), idsr, lscale)
                else:
                    params, lr = xs, None
                plan_l = None if planr is None else plan_at_layer(planr, base)
                h_, pk, pv, *scales = carry
                ks, vs = scales or (None, None)

                def attend(q, k, v):
                    out, *new = _raw_attend_paged(
                        q, k, v, pk, pv, tbl.astype(jnp.int32) + base,
                        posr.astype(jnp.int32), head_dim=hd,
                        page_size=page_size, ragged_plan=plan_l,
                        ksr=ks, vsr=vs)
                    return out, new

                h2, new = block(params, h_, attend, lora=lr)
                return (h2, *new), None

            xs = ((jnp.arange(n_layers, dtype=jnp.int32),) + tuple(stacked)
                  + (tuple(slabr) if n_lora else ()))
            flat = tuple(p.reshape((-1,) + p.shape[2:]) for p in pools)
            with jax.named_scope("layers"):
                (h2, *new), _ = jax.lax.scan(step, (h,) + flat, xs)
            return (h2,) + tuple(n.reshape(p.shape)
                                 for n, p in zip(new, pools))

        results = dispatch.apply(
            raw, hidden, pos, page_tables, *plan, *lora_in, *pool_in,
            *self._stacked(), op_name="gpt_stacked_decoder_paged")
        for t, new in zip(pool_in, results[1:]):
            t._set_value(new._value)
        return results[0]

    def _forward_cached(self, hidden: Tensor, kv_cache, cache_index) -> Tensor:
        """Decode/prefill over the stacked parameters with the
        [L, B, H, max_seq, D] cache: lax.scan carries the hidden state and
        scans the per-layer cache slices as xs/ys.  The updated cache is
        written back in place (mutation-logged -> donated under
        jit.to_static).  The pp pipeline does not apply to serving steps —
        decode always scans."""
        from ..ops import dispatch

        self._refuse_int8("the contiguous-cache decode")
        pos = _as_pos(cache_index)
        block = self._block_fn()
        hd = self._cfg.head_dim
        use_flash = _resolve_use_flash(self._cfg)
        pos_is_zero = _pos_is_static_zero(pos)

        def raw(h, posr, ck, cv, *stacked):
            def step(carry, xs):
                *params, kc, vc = xs

                def attend(q, k, v):
                    out, kc2, vc2 = _raw_attend_with_cache(
                        q, k, v, kc, vc, posr.astype(jnp.int32), head_dim=hd,
                        use_flash=use_flash, pos_is_zero=pos_is_zero)
                    return out, (kc2, vc2)

                return block(params, carry, attend)

            with jax.named_scope("layers"):
                h2, (ck2, cv2) = jax.lax.scan(step, h,
                                              tuple(stacked) + (ck, cv))
            return h2, ck2, cv2

        out, ck_new, cv_new = dispatch.apply(
            raw, hidden, pos, kv_cache.k, kv_cache.v, *self._stacked(),
            op_name="gpt_stacked_decoder_cached")
        kv_cache.k._set_value(ck_new._value)
        kv_cache.v._set_value(cv_new._value)
        return out

    def forward(self, hidden: Tensor, n_micro: int = 1, kv_cache=None,
                cache_index=None,
                page_tables: Optional[Tensor] = None,
                ragged_plan=None, lora=None) -> Tensor:
        """hidden: [B, S, H]. With a pp axis > 1, splits B into n_micro
        microbatches and pipelines; else scans layers.  With ``kv_cache``
        (serving), runs the cached decode scan instead — the paged scan
        when the cache is a PagedKVCache."""
        from ..ops import dispatch
        from ..distributed.fleet.meta_parallel import pp_spmd

        if kv_cache is not None:
            if getattr(kv_cache, "paged", False):
                if page_tables is None:
                    raise ValueError("a paged KV cache needs page_tables")
                return self._forward_paged(hidden, kv_cache, page_tables,
                                           cache_index,
                                           ragged_plan=ragged_plan,
                                           lora=lora)
            return self._forward_cached(hidden, kv_cache, cache_index)
        if lora is not None:
            raise ValueError("per-request LoRA adapters ride the paged "
                             "serving step (kv_cache + page_tables)")
        self._refuse_int8("the training block")

        cfg = self._cfg
        with_dropout = self.training and (cfg.attention_dropout > 0.0
                                          or cfg.hidden_dropout > 0.0)
        body = self._block_fn(with_dropout)
        core = self._train_core(with_dropout)

        def block(p, h):
            if with_dropout:
                *p, key = p
                k1, *k23 = jax.random.split(key, 3)
            else:
                k1 = k23 = None
            return body(p, h, lambda q, k, v: (core(q, k, v, k1), None),
                        drop_keys=k23)[0]

        mesh = _mesh.get_mesh() if _mesh.has_mesh() else None
        pp = mesh.shape["pp"] if (mesh and "pp" in mesh.axis_names) else 1
        remat = cfg.recompute_interval > 0 and self.training
        remat_policy = cfg.recompute_policy if remat else None

        stacked_in = list(self._stacked())
        if with_dropout:
            # one key per layer, scanned alongside the stacked params
            from ..ops.random import default_generator

            base = default_generator.split()
            keys = jax.random.split(base, cfg.num_layers)
            stacked_in.append(Tensor(keys, stop_gradient=True))

        if pp > 1:
            lps = cfg.num_layers // pp

            if with_dropout:
                # decorrelate dropout across microbatches: fold the
                # microbatch index into the per-layer key
                def block_mb(p, h, idx):
                    *rest, key = p
                    return block((*rest, jax.random.fold_in(key, idx)), h)
            else:
                block_mb = None

            def run(h, stacked):
                b = h.shape[0]
                mb = b // n_micro
                xm = h.reshape(n_micro, mb, *h.shape[1:])
                out = pp_spmd.pipeline_blocks(
                    block_mb or block, stacked, xm, layers_per_stage=lps,
                    remat=remat, remat_policy=remat_policy,
                    block_takes_index=block_mb is not None,
                    n_virtual=cfg.virtual_pp_degree)
                return out.reshape(b, *h.shape[1:])
        else:
            # recompute_interval > 1 groups the remat boundary on the
            # stacked scan: [L/k, k] groups, one checkpoint per group —
            # same math, 1/k the saved residuals (the measured remat
            # search in analysis/autotune enumerates (interval, policy))
            k_remat = cfg.recompute_interval if remat else 1
            if remat and k_remat > 1 and cfg.num_layers % k_remat != 0:
                raise ValueError(
                    f"recompute_interval={k_remat} must divide "
                    f"num_layers={cfg.num_layers} on the stacked scan")

            def run(h, stacked):
                return pp_spmd.scan_blocks(block, stacked, h, remat=remat,
                                           remat_policy=remat_policy,
                                           remat_interval=k_remat)

        def raw(h, *stacked):
            with jax.named_scope("layers"):
                return run(h, stacked)

        return dispatch.apply(raw, hidden, *stacked_in,
                              op_name="gpt_stacked_decoder")


class GPTStackedForPretraining(Layer, GenerationMixin):
    """Flagship perf model: embeddings + stacked/pipelined decoder + tied
    LM head. Single-chip it scans; on a dp×sp×mp×pp mesh it runs the full
    hybrid-parallel SPMD program.  Serving: ``generate()`` over a stacked
    [L, B, H, max_seq, D] donated KV cache."""

    def __init__(self, cfg: GPTConfig, n_micro: int = 1):
        super().__init__()
        self.config = cfg
        self.n_micro = n_micro
        self.embeddings = GPTEmbeddings(cfg)
        self.decoder = GPTStackedDecoder(cfg)
        self.final_ln = LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)

    def forward(self, input_ids: Tensor, position_ids: Optional[Tensor] = None,
                labels: Optional[Tensor] = None, kv_cache=None,
                cache_index=None,
                page_tables: Optional[Tensor] = None,
                ragged_plan=None, out_rows: Optional[Tensor] = None,
                lora=None) -> Tensor:
        """Without ``labels``: returns [B, S, V] logits.  With ``labels``:
        returns the scalar LM loss through the fused linear+cross-entropy
        head (chunked over tokens, logits never fully materialized — the
        HBM-friendly path; see F.fused_linear_cross_entropy)."""
        with jax.named_scope("embed"):
            if kv_cache is not None and position_ids is None:
                position_ids = _cache_position_ids(input_ids,
                                                   _as_pos(cache_index))
                if getattr(kv_cache, "paged", False):
                    position_ids = ops.clip(
                        position_ids, min=0,
                        max=self.config.max_position_embeddings - 1)
            h = self.embeddings(input_ids, position_ids)
        h = self.decoder(h, n_micro=self.n_micro, kv_cache=kv_cache,
                         cache_index=cache_index, page_tables=page_tables,
                         ragged_plan=ragged_plan, lora=lora)
        with jax.named_scope("lm_head"):
            return self._lm_head(h, labels, out_rows)

    def _lm_head(self, h: Tensor, labels: Optional[Tensor],
                 out_rows: Optional[Tensor]) -> Tensor:
        h = self.final_ln(h)
        if out_rows is not None:
            # serving fused step: gather each slot's output row BEFORE the
            # vocab projection, so the LM head projects [S] rows instead of
            # the whole padded flat-token axis
            h = ops.gather(h, out_rows, axis=0)
        if labels is None and getattr(self, "_weight_int8", False):
            # quantize_for_serving stored the tied LM head transposed as
            # int8 [H, V] with per-vocab-row scales — one int8 MXU matmul
            from ..quantization.int8 import quantized_matmul

            return quantized_matmul(h, self.lm_head_int8,
                                    self.lm_head_scale)
        w = self.embeddings.word_embeddings.weight
        if labels is not None:
            from ..amp.auto_cast import _amp_state

            cdt = _amp_state.dtype if _amp_state.enabled else None
            return F.fused_linear_cross_entropy(h, w, labels, compute_dtype=cdt)
        return ops.matmul(h, w, transpose_y=True)

    # -- GenerationMixin cache contract ------------------------------------
    def new_kv_cache(self, batch_size: int, max_seq: int,
                     dtype: str = "bfloat16") -> KVCache:
        cfg = self.config
        return KVCache(cfg.num_layers, batch_size, cfg.num_heads, max_seq,
                       cfg.head_dim, dtype=dtype)

    def _cached_lm_logits(self, input_ids, kv_cache, cache_index):
        return self.forward(input_ids, kv_cache=kv_cache,
                            cache_index=cache_index)

    # -- ServingEngine paged-cache contract --------------------------------
    def new_paged_kv_cache(self, num_pages: int, page_size: int,
                           dtype: str = "bfloat16"):
        from ..serving.paged_cache import PagedKVCache

        cfg = self.config
        return PagedKVCache(cfg.num_layers, num_pages, cfg.num_heads,
                            page_size, cfg.head_dim, dtype=dtype)

    def _paged_lm_logits(self, input_ids, paged_cache, page_tables,
                         positions, ragged_plan=None, out_rows=None,
                         lora=None):
        return self.forward(input_ids, kv_cache=paged_cache,
                            cache_index=positions, page_tables=page_tables,
                            ragged_plan=ragged_plan, out_rows=out_rows,
                            lora=lora)


def truncated_draft(model, num_layers: int = 1):
    """A weight-sharing TRUNCATED draft for speculative serving
    (serving/speculative.py): same class, same embeddings / final LN /
    tied LM head, but only the first ``num_layers`` decoder blocks — a
    cheap proposer whose logits track the target's direct embedding path.
    Weights are copied from ``model`` (stacked parameters sliced on the
    leading layer axis), so the draft follows the target at construction
    time; it owns its own paged pool inside the engine."""
    import dataclasses

    cfg = model.config
    n = int(num_layers)
    if not 1 <= n <= cfg.num_layers:
        raise ValueError(f"truncated_draft: num_layers={n} not in "
                         f"[1, {cfg.num_layers}]")
    dcfg = dataclasses.replace(cfg, num_layers=n)
    draft = type(model)(dcfg)
    src = model.state_dict()
    # the stacked [L, ...] parameters keep their first n layers; every other
    # entry has the draft's own leading size
    draft.set_state_dict({k: np.asarray(src[k].numpy())[: dv.shape[0]]
                          for k, dv in draft.state_dict().items()
                          if k in src})
    draft.eval()
    return draft


class GPTPretrainingCriterion(Layer):
    """Next-token cross entropy with an optional loss mask (reference
    fixture GPTPretrainingCriterion)."""

    def __init__(self, cfg: Optional[GPTConfig] = None):
        super().__init__()
        tp = bool(cfg and cfg.use_tensor_parallel)
        self.loss_fn = ParallelCrossEntropy() if tp else None

    def forward(self, logits: Tensor, labels: Tensor,
                loss_mask: Optional[Tensor] = None) -> Tensor:
        if self.loss_fn is not None:
            losses = self.loss_fn(logits, labels)        # [B, S]
        else:
            losses = F.cross_entropy(logits, labels, reduction="none")
        losses = ops.reshape(losses, [-1])
        if loss_mask is not None:
            mask = ops.reshape(loss_mask, [-1]).astype(losses.dtype)
            return ops.sum(losses * mask) / ops.clip(ops.sum(mask), min=1.0)
        return ops.mean(losses)
