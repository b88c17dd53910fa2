"""A hybrid decoder of two kinds of layer — gated short convolutions and
grouped-query attention — with a routed feed-forward (``model_type:
lfm2_moe``): the stacked, serving form.

For a token's hidden state ``x`` (``N`` an RMSNorm over the last axis)::

    x = Emb[id]
    for each layer:
      u = N(x; g_op)
      conv:  [B, C, z] = split3(u @ W_in);  v_t = B_t * z_t
             c_t = w[0] * v_{t-2} + w[1] * v_{t-1} + w[2] * v_t   (v_{<0} = 0)
             o_t = (C_t * c_t) @ W_out
      attn:  q, k, v = u @ Wq, u @ Wk, u @ Wv;  q, k = N(q; g_q), N(k; g_k)
             per head;  rotary positions (rotate-half);  causal softmax, K/V
             head j serving query heads G*j .. G*j + G - 1;  o = o @ Wo
      x = x + o;  u = N(x; g_ffn)
      first num_dense_layers:  y = (silu(u @ W1) * (u @ W3)) @ W2
      the others:  s = sigmoid(f32(u) @ Wg);  sel = top_k(s + b)
                   p = s[sel] / (sum(s[sel]) + 1e-6)
                   y = sum_{e in sel} p_e * (silu(u @ W1[e]) * (u @ W3[e])) @ W2[e]
      x = x + y
    logits = N(x; g_out) @ Emb^T

``benchmark/reference/lfm2_moe_ref.py`` is the same mathematics in plain
float32, written apart from this file.

How it runs.  The layers after the leading dense ones repeat with a period
(attention, conv, conv, conv as published); the layer loop is a short
unrolled loop over the leading layers, ONE ``lax.scan`` over whole periods
whose body is a period's layers written out, and an unrolled remainder.  The
serving step (``_paged_lm_logits``, the contract ``ServingEngine`` asks for)
carries the K|V pool and the convolution's tail pool
(``serving.paged_cache.HybridPagedCache``) through that loop as donated
buffers viewed ``[L * P, ...]``, a layer adding its base to page ids.  The
experts of every routed layer are ONE operand ``[L_moe * E, ...]`` that the
loop closes over: a layer adds its base to the group ids of
``ops.pallas_kernels.grouped_matmul`` and no operation slices or copies a
layer's experts.  Routing is float32 (scores, bias, top-k, normalisation);
tokens are sorted by expert, no token is dropped, there is no capacity, and
a step's padding rows are routed to no expert.
"""
from __future__ import annotations

import weakref
from dataclasses import dataclass
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dtype import to_jax_dtype
from ..nn.layer import Layer
from ..ops import dispatch
from ..tensor import Parameter, Tensor
from .decoder_ops import gated_ffn as _gated_ffn, mm as _mm, rms_norm as _rms
from .decoder_ops import rope as _rope

__all__ = ["Lfm2Config", "Lfm2StackedForCausalLM", "lfm2_tiny",
           "PUBLISHED_LAYER_TYPES"]

CONV, ATTN = "conv", "full_attention"
#: ``layer_types`` of the published 40-layer model
PUBLISHED_LAYER_TYPES = ((CONV, CONV) + (ATTN, CONV, CONV, CONV) * 9
                         + (ATTN, CONV))


@dataclass
class Lfm2Config:
    """The published ``config.json``'s keys under their own names."""

    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 40
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    num_experts: int = 64
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    conv_L_cache: int = 3
    conv_bias: bool = False
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    max_position_embeddings: int = 128000
    # the first num_hidden_layers entries are the model's
    layer_types: Sequence[str] = PUBLISHED_LAYER_TYPES
    initializer_range: float = 0.02
    dtype: str = "bfloat16"         # the weights' storage dtype

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)[:self.num_hidden_layers]
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"num_hidden_layers={self.num_hidden_layers}")
        unknown = set(self.layer_types) - {CONV, ATTN}
        if unknown:
            raise ValueError(f"layer_types holds {sorted(unknown)}: only "
                             f"{CONV!r} and {ATTN!r} are written here")
        if self.conv_L_cache != 3 or self.conv_bias:
            raise ValueError(
                "the convolution is written for conv_L_cache=3 and no bias "
                f"(got {self.conv_L_cache}, conv_bias={self.conv_bias})")
        if not (self.norm_topk_prob and self.use_expert_bias
                and self.routed_scaling_factor == 1.0):
            raise ValueError(
                "the router is written as published: norm_topk_prob, "
                "use_expert_bias, routed_scaling_factor 1 (got "
                f"{self.norm_topk_prob}, {self.use_expert_bias}, "
                f"{self.routed_scaling_factor})")
        if self.hidden_size % self.num_attention_heads \
                or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads over hidden "
                f"{self.hidden_size} and {self.num_key_value_heads} K/V heads")
        if not 0 <= self.num_dense_layers <= self.num_hidden_layers:
            raise ValueError(f"num_dense_layers={self.num_dense_layers}")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def conv_taps(self) -> int:
        """Earlier inputs the convolution reads: the state a sequence keeps."""
        return self.conv_L_cache - 1

    def segments(self) -> Tuple[Tuple[str, ...], Tuple[str, ...], int,
                                Tuple[str, ...]]:
        """``(lead, period, n_periods, trail)``: the kinds of the leading
        dense layers, of one period of what follows, how many whole periods
        follow, and the kinds of the remainder."""
        nd = self.num_dense_layers
        lead, rest = self.layer_types[:nd], self.layer_types[nd:]
        if not rest:
            return lead, (), 0, ()
        p = next(p for p in range(1, len(rest) + 1)
                 if all(rest[i] == rest[i % p] for i in range(len(rest))))
        n = len(rest) // p
        return lead, rest[:p], n, rest[n * p:]


def lfm2_tiny(**kw) -> Lfm2Config:
    """The CPU tests' size: 2 dense layers and one period, float32."""
    defaults = dict(vocab_size=512, hidden_size=64, intermediate_size=160,
                    moe_intermediate_size=48, num_hidden_layers=6,
                    num_attention_heads=4, num_key_value_heads=2,
                    num_experts=8, num_experts_per_tok=2,
                    max_position_embeddings=1024, dtype="float32")
    defaults.update(kw)
    return Lfm2Config(**defaults)


# ---------------------------------------------------------------------------
# the mathematics, on raw arrays
# ---------------------------------------------------------------------------

def _route(cfg: Lfm2Config, u, router, bias, scoring="sigmoid"):
    """Float32 routing of rows ``u`` [N, H]: the chosen experts [N, k] and
    their weights [N, k].  ``sigmoid``: the bias selects and does not weigh,
    the weights are renormalised with an epsilon; ``softmax``: a softmax over
    every expert, the top k by it, their weights renormalised to 1 (no
    bias)."""
    logits = jnp.dot(u.astype(jnp.float32), router,
                     precision=jax.lax.Precision.HIGHEST)
    if scoring == "softmax":
        p, sel = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                               cfg.num_experts_per_tok)
        return sel.astype(jnp.int32), p / jnp.sum(p, axis=-1, keepdims=True)
    s = jax.nn.sigmoid(logits)
    _, sel = jax.lax.top_k(s + bias, cfg.num_experts_per_tok)
    p = jnp.take_along_axis(s, sel, axis=-1)
    return sel.astype(jnp.int32), p / (jnp.sum(p, axis=-1, keepdims=True) + 1e-6)


def _moe(cfg: Lfm2Config, u, router, bias, w1, w3, w2, group_base, real,
         held=None, scoring="sigmoid"):
    """The routed feed-forward of rows ``u`` [N, H] against the stacked
    experts ``w1``/``w3`` [L*E, H, F], ``w2`` [L*E, F, H], this layer's at
    ``group_base``.  ``real`` [N] bool (or None: every row): a row that is
    not real is routed to no expert and comes back zero.  ``held`` (or None:
    every expert): ``(first, count)``, the experts this layer HOLDS of the
    ``num_experts`` it routes over (the stacks hold ``count`` a layer): a
    pair whose expert lies outside them is another chip's and is dropped,
    as a padding row's is, and nothing stands in for it.  ``scoring``: the
    router's form (:func:`_route`).  Returns ``(y, counts, sel)``:
    ``counts`` the int32 triple (token-expert pairs computed here, experts
    with a token, the fullest expert's rows), ``sel`` [N, k] the experts
    each row went to (``num_experts`` for a row that is not real)."""
    from ..ops.pallas_kernels.grouped_matmul import grouped_matmul

    n, k, e = u.shape[0], cfg.num_experts_per_tok, cfg.num_experts
    with jax.named_scope("moe.route"):
        sel, p = _route(cfg, u, router, bias, scoring)
        if real is not None:
            sel = jnp.where(real[:, None], sel, e)      # past every expert
        flat = sel.reshape(-1)
        if held is not None:
            first, e = held
            flat = jnp.where((flat >= first) & (flat < first + e),
                             flat - first, e)           # past every held one
        order = jnp.argsort(flat, stable=True)          # pairs by expert
        sizes = jnp.sum(flat[:, None] == jnp.arange(e, dtype=jnp.int32),
                        axis=0, dtype=jnp.int32)
        rows = jnp.take(u, order // k, axis=0)          # [N*k, H]
    with jax.named_scope("moe.experts"):
        a = grouped_matmul(rows, w1, sizes, group_base)
        b = grouped_matmul(rows, w3, sizes, group_base)
        act = (jax.nn.silu(a) * b).astype(u.dtype)
        out = grouped_matmul(act, w2, sizes, group_base)    # [N*k, H] f32
    with jax.named_scope("moe.route"):
        back = jnp.take(out, jnp.argsort(order), axis=0).reshape(n, k, -1)
        y = jnp.sum(back * p[:, :, None], axis=1).astype(u.dtype)
    counts = jnp.stack([jnp.sum(sizes, dtype=jnp.int32),
                        jnp.sum(sizes > 0, dtype=jnp.int32), jnp.max(sizes)])
    return y, counts, sel


def _fold_counts(limbs, inc, limb: int):
    """Add the step's int32 increments to the ``[n, 2]`` (hi, lo) limbs."""
    lo = limbs[:, 1] + inc
    return jnp.stack([limbs[:, 0] + lo // limb, lo % limb], axis=1)


class _PagedStep:
    """What one serving step's rows share across its layers: where each
    row's K/V and tail are written and where a convolution finds a row's
    predecessors.  Rows are the step's flat tokens; a run (a decode token or
    a prefill chunk) is consecutive rows at consecutive positions of one
    slot.  Built once a step from positions and page tables alone."""

    def __init__(self, pos, tbl, plan, page_size: int, n_pages: int):
        self.pos, self.tbl, self.plan = pos, tbl, plan
        self.page_size, self.n_pages = page_size, n_pages
        last = tbl.shape[1] - 1

        def page_of(q):     # the pool page holding position q of the row's slot
            slot = jnp.clip(q // page_size, 0, last)
            return jnp.take_along_axis(tbl, slot[:, None], axis=1)[:, 0]

        write = page_of(pos)
        self.write_page = write
        # a real row's page is allocated; padding rows carry the null table
        self.real = write != 0
        # row t-1 is row t's predecessor: the position before, written into
        # the page this row's table holds for it (a page being written is
        # private to its slot, so two slots never agree on it)
        before = page_of(pos - 1)
        has1 = jnp.concatenate([
            jnp.zeros((1,), bool),
            (pos[:-1] + 1 == pos[1:]) & (write[:-1] == before[1:])
            & self.real[:-1]])
        has2 = has1 & jnp.concatenate([jnp.zeros((1,), bool), has1[:-1]])
        self.has1, self.has2 = has1, has2
        # the page whose tail holds what the step's rows do not: the one
        # holding the position before the run's first
        self.tail_page = jnp.where(has1, page_of(pos - 2), before)
        self.tail_live = jnp.where(has1, pos >= 2, pos >= 1)
        # a row ends its page's run unless the next row follows it there
        follows = jnp.concatenate([has1[1:] & (write[1:] == write[:-1]),
                                   jnp.zeros((1,), bool)])
        self.closes = self.real & ~follows

    def attend(self, q, k, v, kv, layer, head_dim):
        """q [T, Hq, D], k/v [T, Hkv, D] against layer ``layer``'s pages of
        the flat K|V pool ``[L * P, Hkv, page, 2D]``; returns (out [T, Hq,
        D], pool).  A row of the pool is a token's K and V side by side:
        one write.  On a TPU the plan's write list in one launch (the tile
        groups the real rows touch, in place: pool_write.py); elsewhere a
        row scatter on the pool's flat ``[rows, 2D]`` view (its own layout,
        so in place; padding rows sink into the layer's null page).  The
        queries are zero-padded to ``2D`` so that the V half adds nothing
        to a score, and the attention is the second half of what the kernel
        returns (``HybridPagedCache``)."""
        from ..ops.pallas_kernels.pool_write import (
            pool_write, pool_write_runs,
        )
        from ..ops.pallas_kernels.ragged_paged_attention import (
            plan_at_layer, ragged_paged_attention, write_list_of,
        )

        base = layer * self.n_pages
        plan = plan_at_layer(self.plan, base)
        hkv = k.shape[1]
        with jax.named_scope("attn.pool_write"):
            row = jnp.concatenate([k, v], axis=-1).astype(kv.dtype)
            if pool_write_runs(self.page_size, kv.dtype):
                (kv,) = pool_write((kv,), (row,), write_list_of(plan))
            else:
                rows = (((self.write_page + base)[:, None] * hkv
                         + jnp.arange(hkv, dtype=jnp.int32)) * self.page_size
                        + (self.pos % self.page_size)[:, None])  # [T, Hkv]
                kv = kv.reshape(-1, 2 * head_dim).at[rows].set(
                    row).reshape(kv.shape)
        wide = jnp.concatenate([q, jnp.zeros_like(q)], axis=-1)
        out = ragged_paged_attention(
            wide, kv, kv, self.tbl + base, self.pos + 1, plan,
            sm_scale=float(1.0 / np.sqrt(head_dim)))
        return out[:, :, head_dim:], kv

    def predecessors(self, v, tail, layer):
        """``(v_{t-1}, v_{t-2})`` of every row and the tail pool with this
        step's tails written: ``tail`` is ``[L * P, rows, lanes]``, a page's
        ``(v_{p-1}, v_p)`` as one slab (``HybridPagedCache.tail``)."""
        with jax.named_scope("conv.mix"):
            old = jnp.take(tail, self.tail_page + layer * self.n_pages, axis=0)
            old = old.reshape(v.shape[0], 2, v.shape[1])        # [T, 2, W]
            old = jnp.where(self.tail_live[:, None, None], old,
                            jnp.zeros_like(old))
            zero = jnp.zeros_like(v[:1])
            back1 = jnp.concatenate([zero, v[:-1]])
            back2 = jnp.concatenate([zero, zero, v[:-2]])
            prev1 = jnp.where(self.has1[:, None], back1, old[:, 1])
            prev2 = jnp.where(self.has2[:, None], back2,
                              jnp.where(self.has1[:, None], old[:, 1], old[:, 0]))
        with jax.named_scope("conv.state_write"):
            # the last row of a page's run leaves (v_{p-1}, v_p); the others
            # sink into the layer's null page.  One slab a row, written in
            # place (the sink slabs repeat)
            dest = (jnp.where(self.closes, self.write_page, 0)
                    + layer * self.n_pages)
            new = jnp.stack([prev1, v], axis=1).astype(tail.dtype)
            tail = tail.at[dest].set(new.reshape((-1,) + tail.shape[1:]))
        return prev1, prev2, tail


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

# per-kind parameter shapes of one layer, as functions of the config
def _conv_shapes(c):
    h = c.hidden_size
    return {"conv_in": (h, 3 * h), "conv_w": (c.conv_L_cache, h),
            "conv_out": (h, h)}


def _attn_shapes(c):
    h, d = c.hidden_size, c.head_dim
    kv = c.num_key_value_heads * d
    return {"wq": (h, h), "wk": (h, kv), "wv": (h, kv), "wo": (h, h),
            "q_norm": (d,), "k_norm": (d,)}


class Lfm2StackedForCausalLM(Layer):
    """Embedding + stacked hybrid decoder + tied head.  ``forward`` is the
    plain full forward over ``[B, S]`` ids; ``new_paged_kv_cache`` /
    ``_paged_lm_logits`` are the paged contract ``ServingEngine`` asks for.

    Parameters are one set a layer position, so that no layer slices a
    stack: ``lead<j>_*`` (leading dense layer ``j``), ``body<j>_*`` (layer
    ``j`` of a period) with a leading ``[n_periods]`` axis that the scan
    takes as ``xs``, ``trail<j>_*``.  The experts of every routed
    layer are ``moe_w1``/``moe_w3`` ``[L_moe * E, H, F]`` and ``moe_w2``
    ``[L_moe * E, F, H]``, routed layer ``m``'s at ``m * E``."""

    #: what ``ServingEngine`` is refused for this model, and why
    serving_unsupported = {
        "mp": "the K/V heads (fewer than a usual mp) and the experts are "
              "not sharded over chips",
        "lora": "no adapter path is written for the convolution, the "
                "grouped-query projections or the experts",
        "kv_int8": "the tail pool has no scale sidecar and the grouped-"
                   "query kernel no dequant path",
        "weight_int8": "no int8 form of the expert or convolution weights",
        "speculative": "a rejected draft token would have overwritten the "
                       "page's convolution tail, which no rollback restores",
        "disagg": "the page hand-off is untested for a cache of two kinds "
                  "of state",
    }

    def __init__(self, cfg: Lfm2Config):
        super().__init__()
        self.config = cfg
        self._newest_cache = None
        lead, period, n_periods, trail = cfg.segments()
        self._lead, self._period, self._n_periods, self._trail = (
            lead, period, n_periods, trail)
        nd = cfg.num_dense_layers
        self._n_moe = cfg.num_hidden_layers - nd
        self._n_attn = cfg.layer_types.count(ATTN)
        self._n_conv = cfg.layer_types.count(CONV)
        dt = to_jax_dtype(cfg.dtype)
        std = cfg.initializer_range
        from ..ops.random import default_generator

        def normal(shape, dtype=dt, scale=std):
            # drawn in the storage dtype on the device: no float32 copy of
            # a weight is ever made
            return jax.random.normal(default_generator.split(), shape,
                                     dtype) * jnp.asarray(scale, dtype)

        def param(name, value):
            # not trainable: no backward is written for the grouped product
            # or the paged convolution (ROADMAP.md R-M), so no forward ever
            # records one
            setattr(self, name, Parameter(value, trainable=False))
            self._names.append(name)

        self._names = []
        h, e = cfg.hidden_size, cfg.num_experts
        param("embed", normal((cfg.vocab_size, h)))
        param("out_norm", jnp.ones((h,), dt))
        f = cfg.intermediate_size
        for seg, kinds, reps, moe in (("lead", lead, (), False),
                                      ("body", period, (n_periods,), True),
                                      ("trail", trail, (), True)):
            if reps == (0,):
                continue
            for j, kind in enumerate(kinds):
                shapes = {"op_norm": (h,), "ffn_norm": (h,),
                          **(_conv_shapes(cfg) if kind == CONV
                             else _attn_shapes(cfg)),
                          **({"router": (h, e), "router_bias": (e,)} if moe
                             else {"w1": (h, f), "w3": (h, f), "w2": (f, h)})}
                for name, shape in shapes.items():
                    full = reps + shape
                    if name.endswith("_norm"):
                        value = jnp.ones(full, dt)
                    elif name == "router":
                        value = normal(full, jnp.float32)
                    elif name == "router_bias":
                        value = normal(full, jnp.float32, 0.01)
                    else:
                        value = normal(full)
                    param(f"{seg}{j}_{name}", value)
        if self._n_moe:
            fm = cfg.moe_intermediate_size
            for name, shape in (("moe_w1", (h, fm)), ("moe_w3", (h, fm)),
                                ("moe_w2", (fm, h))):
                param(name, self._stack_of_experts(
                    self._n_moe * e, shape, std, dt, default_generator.split()))

    @staticmethod
    def _stack_of_experts(n_experts, shape, std, dtype, key):
        """``[n_experts, *shape]`` drawn eight experts at a time into one
        buffer inside one program: the peak is the stack plus a chunk's
        temporaries (a draw of a whole stack at once held 4.8 GB beside it
        on the chip)."""
        chunk = next(c for c in (8, 4, 2, 1) if n_experts % c == 0)

        def fill(i, buf):
            part = jax.random.normal(jax.random.fold_in(key, i),
                                     (chunk,) + shape, dtype)
            return jax.lax.dynamic_update_slice_in_dim(
                buf, part * jnp.asarray(std, dtype), i * chunk, axis=0)

        return jax.jit(lambda: jax.lax.fori_loop(
            0, n_experts // chunk, fill,
            jnp.zeros((n_experts,) + shape, dtype)))()

    def _arrays(self):
        return [getattr(self, n) for n in self._names]

    # -- the layers, over raw arrays --------------------------------------
    def _run(self, w: dict, x, cores, state):
        """The decoder over hidden rows ``x``.  ``w``: the raw parameters by
        name.  ``cores``: ``conv(v, state, layer) -> (prev1, prev2, state)``,
        ``attend(q, k, v, state, layer) -> (out, state)``, ``note(state,
        counts, sel, layer) -> state`` (what a routed layer counted and
        chose) and ``positions`` and ``real`` (or None); ``layer`` counts the
        layers of that kind before this one.  ``state`` is whatever the cores
        carry (the pools, the step's counts and picks in serving; nothing in
        the plain forward)."""
        cfg = self.config
        e, eps = cfg.num_experts, cfg.norm_eps
        hq, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim

        def conv_op(p, u, state, layer):
            with jax.named_scope("conv.proj"):
                bcz = _mm(u, p["conv_in"]).astype(u.dtype)
            with jax.named_scope("conv.mix"):
                b_, c_, z = jnp.split(bcz, 3, axis=-1)
                v = b_ * z
            prev1, prev2, state = cores["conv"](v, state, layer)
            with jax.named_scope("conv.mix"):
                taps = p["conv_w"].astype(v.dtype)
                y = c_ * (taps[0] * prev2 + taps[1] * prev1 + taps[2] * v)
            with jax.named_scope("conv.proj"):
                return _mm(y, p["conv_out"]).astype(u.dtype), state

        def attn_op(p, u, state, layer):
            lead_shape = u.shape[:-1]
            with jax.named_scope("attn.qkv"):
                q = _mm(u, p["wq"]).astype(u.dtype).reshape(lead_shape + (hq, d))
                k = _mm(u, p["wk"]).astype(u.dtype).reshape(lead_shape + (hkv, d))
                v = _mm(u, p["wv"]).astype(u.dtype).reshape(lead_shape + (hkv, d))
            with jax.named_scope("attn.rope"):
                q = _rope(_rms(q, p["q_norm"], eps), cores["positions"],
                          cfg.rope_theta)
                k = _rope(_rms(k, p["k_norm"], eps), cores["positions"],
                          cfg.rope_theta)
            with jax.named_scope("attn.core"):
                out, state = cores["attend"](q, k, v, state, layer)
            with jax.named_scope("attn.out"):
                out = out.reshape(lead_shape + (hq * d,))
                return _mm(out, p["wo"]).astype(u.dtype), state

        def layers(ps, kinds, x, state, conv0, attn0, moe0):
            """One segment's (or one period's) layers written out: ``ps[j]``
            layer ``j``'s arrays; ``conv0`` / ``attn0`` / ``moe0`` count the
            layers of a kind before the first (``moe0`` None: dense
            feed-forwards)."""
            for j, (kind, p) in enumerate(zip(kinds, ps)):
                with jax.named_scope("conv.proj" if kind == CONV else "attn.qkv"):
                    u = _rms(x, p["op_norm"], eps)
                if kind == CONV:
                    o, state = conv_op(p, u, state, conv0)
                    conv0 = conv0 + 1
                else:
                    o, state = attn_op(p, u, state, attn0)
                    attn0 = attn0 + 1
                x = x + o
                if moe0 is None:
                    with jax.named_scope("mlp"):
                        u = _rms(x, p["ffn_norm"], eps)
                        y = _gated_ffn(u, p["w1"], p["w3"], p["w2"])
                else:
                    with jax.named_scope("moe.route"):
                        u = _rms(x, p["ffn_norm"], eps)
                    y, counts, sel = _moe(cfg, u.reshape(-1, u.shape[-1]),
                                          p["router"], p["router_bias"],
                                          w["moe_w1"], w["moe_w3"], w["moe_w2"],
                                          (moe0 + j) * e, cores["real"])
                    y = y.reshape(u.shape)
                    state = cores["note"](state, counts, sel, moe0 + j)
                x = x + y
            return x, state

        def seg(prefix, n):
            """Layer ``j``'s arrays by their short names, for ``j < n``."""
            return [{k[len(f"{prefix}{j}_"):]: v for k, v in w.items()
                     if k.startswith(f"{prefix}{j}_")} for j in range(n)]

        lead, period, n_periods, trail = (self._lead, self._period,
                                          self._n_periods, self._trail)
        with jax.named_scope("layers"):
            conv0 = attn0 = 0
            if lead:
                x, state = layers(seg("lead", len(lead)), lead, x, state,
                                  0, 0, None)
                conv0, attn0 = lead.count(CONV), lead.count(ATTN)
            if n_periods:
                body = seg("body", len(period))
                names = [sorted(p) for p in body]
                nc, na, nl = period.count(CONV), period.count(ATTN), len(period)

                def step(carry, xs):
                    i, arrays = xs[0], iter(xs[1:])
                    ps = [{n: next(arrays) for n in ns} for ns in names]
                    x_, state_ = layers(ps, period, carry[0], carry[1],
                                        conv0 + i * nc, attn0 + i * na, i * nl)
                    return (x_, state_), None

                (x, state), _ = jax.lax.scan(
                    step, (x, state),
                    (jnp.arange(n_periods, dtype=jnp.int32),
                     *(p[n] for p, ns in zip(body, names) for n in ns)))
                conv0 += n_periods * nc
                attn0 += n_periods * na
            if trail:
                x, state = layers(seg("trail", len(trail)), trail, x, state,
                                  conv0, attn0, n_periods * len(period))
        return x, state

    def _head(self, w, x):
        return jnp.dot(_rms(x, w["out_norm"], self.config.norm_eps),
                       w["embed"].T, preferred_element_type=jnp.float32)

    # -- plain forward ------------------------------------------------------
    def forward(self, input_ids: Tensor) -> Tensor:
        """``[B, S]`` ids -> ``[B, S, V]`` float32 logits: every sequence from
        position 0, no cache (the convolution reads its own sequence's
        earlier rows, attention its own causal prefix)."""
        cfg = self.config
        names = self._names
        group = cfg.num_attention_heads // cfg.num_key_value_heads
        scale = np.float32(1.0 / np.sqrt(cfg.head_dim))

        def raw(ids, *arrays):
            w = dict(zip(names, arrays))
            b, s = ids.shape

            def conv(v, state, layer):
                pad = jnp.pad(v, ((0, 0), (2, 0), (0, 0)))
                return pad[:, 1:s + 1], pad[:, :s], state

            def attend(q, k, v, state, layer):
                k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
                sc = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                                preferred_element_type=jnp.float32) * scale
                sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -1e30)
                att = jax.nn.softmax(sc, axis=-1).astype(v.dtype)
                return jnp.einsum("bhqk,bkhd->bqhd", att, v), state

            cores = {"conv": conv, "attend": attend, "real": None,
                     "positions": jnp.broadcast_to(jnp.arange(s), (b, s)),
                     "note": lambda state, counts, sel, layer: state}
            with jax.named_scope("embed"):
                x = jnp.take(w["embed"], ids, axis=0)
            x, _ = self._run(w, x, cores, ())
            with jax.named_scope("lm_head"):
                return self._head(w, x)

        return dispatch.apply(raw, input_ids, *self._arrays(),
                              op_name="lfm2_forward")

    # -- ServingEngine paged-cache contract --------------------------------
    def new_paged_kv_cache(self, num_pages: int, page_size: int,
                           dtype: str = "bfloat16"):
        from ..serving.paged_cache import HybridPagedCache

        cfg = self.config
        cache = HybridPagedCache(
            self._n_attn, self._n_conv, num_pages, cfg.num_key_value_heads,
            page_size, cfg.head_dim, cfg.hidden_size, cfg.conv_taps,
            dtype=dtype, routed_layers=self._n_moe,
            top_k=cfg.num_experts_per_tok)
        self._newest_cache = weakref.ref(cache)
        return cache

    def recent_routes(self):
        """What the newest cache's route log holds
        (``HybridPagedCache.recent_routes``), or None where no cache was
        made or it is gone."""
        cache = self._newest_cache() if self._newest_cache else None
        return None if cache is None else cache.recent_routes()

    def _paged_lm_logits(self, input_ids, paged_cache, page_tables,
                         positions, ragged_plan=None, out_rows=None,
                         lora=None):
        """The fused serving step's model part: ``input_ids`` [T, 1] flat
        tokens at ``positions`` [T] with their slots' ``page_tables``
        [T, max_pages] and the step's ragged plan; logits ``[S, 1, V]`` at
        ``out_rows``.  The cache's pools, counters and route log are updated
        in place (mutation-logged, so donated under ``jit.to_static``)."""
        if lora is not None:
            raise NotImplementedError(
                "Lfm2StackedForCausalLM: " + self.serving_unsupported["lora"])
        if ragged_plan is None or out_rows is None:
            raise ValueError(
                "Lfm2StackedForCausalLM serves through the fused ragged "
                "step: _paged_lm_logits needs ragged_plan and out_rows")
        cfg = self.config
        names = self._names
        plan = tuple(ragged_plan)
        n_plan = len(plan)
        page_size = int(paged_cache.page_size)
        n_pages = int(paged_cache.num_pages)
        held = (paged_cache.kv, paged_cache.tail, paged_cache.counters,
                paged_cache.routes, paged_cache.route_cursor)
        limb = int(paged_cache.LIMB)
        d, k, e, n_moe = (cfg.head_dim, cfg.num_experts_per_tok,
                          cfg.num_experts, self._n_moe)

        def raw(ids, pos, tbl, rows_out, *rest):
            planr, rest = rest[:n_plan], rest[n_plan:]
            (kv, tail, limbs, log, cursor), arrays = rest[:5], rest[5:]
            w = dict(zip(names, arrays))
            pos, tbl = pos.astype(jnp.int32), tbl.astype(jnp.int32)
            step = _PagedStep(pos, tbl, planr, page_size, n_pages)
            t = pos.shape[0]
            if t > log.shape[1]:
                raise ValueError(f"a step of {t} rows, a route log of "
                                 f"{log.shape[1]}")

            def conv(v, state, layer):
                prev1, prev2, tail_ = step.predecessors(v, state["tail"], layer)
                return prev1, prev2, {**state, "tail": tail_}

            def attend(q, k_, v, state, layer):
                out, kv_ = step.attend(q, k_, v, state["kv"], layer, d)
                return out.astype(q.dtype), {**state, "kv": kv_}

            def note(state, counts, sel, layer):
                zero = jnp.int32(0)
                picks = jax.lax.dynamic_update_slice(
                    state["picks"], sel.T[None],
                    (jnp.asarray(layer, jnp.int32), zero, zero))
                return {**state, "inc": state["inc"].at[:3].add(counts),
                        "picks": picks}

            cores = {"conv": conv, "attend": attend, "real": step.real,
                     "positions": pos, "note": note}
            with jax.named_scope("embed"):
                x = jnp.take(w["embed"], ids.reshape(-1), axis=0)
            state = {"kv": kv.reshape((-1,) + kv.shape[2:]),
                     "tail": tail.reshape((-1,) + tail.shape[2:]),
                     "inc": jnp.zeros((limbs.shape[0],), jnp.int32),
                     "picks": jnp.full((n_moe, k, t), e, jnp.int32)}
            x, state = self._run(w, x, cores, state)
            inc = state["inc"].at[3].set(
                self._n_conv * jnp.sum(step.closes, dtype=jnp.int32))
            with jax.named_scope("moe.route"):
                # the step's rows go into the log side by side, wrapping to
                # its start when they would run past its end
                start = jnp.where(cursor[0] + t > log.shape[1], 0, cursor[0])
                log = jax.lax.dynamic_update_slice(
                    log, jnp.concatenate([state["picks"].reshape(n_moe * k, t),
                                          pos[None], step.write_page[None]]),
                    (jnp.int32(0), start))
            with jax.named_scope("lm_head"):
                logits = self._head(w, jnp.take(x, rows_out, axis=0))
            return (logits[:, None, :], state["kv"].reshape(kv.shape),
                    state["tail"].reshape(tail.shape),
                    _fold_counts(limbs, inc, limb), log, (start + t)[None])

        results = dispatch.apply(
            raw, input_ids, positions, page_tables, out_rows, *plan, *held,
            *self._arrays(), op_name="lfm2_paged_step")
        for tensor, new in zip(held, results[1:]):
            tensor._set_value(new._value)
        return results[0]
