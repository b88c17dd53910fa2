"""A decoder-hybrid-decoder (``model_type: phi4flash``; arXiv:2507.06607): a
self-decoder of state-space (Mamba-1) and window-attention layers, one
state-space layer whose output is the MEMORY and one full-attention layer
whose keys and values are the only ones kept for the whole context, and a
cross-decoder of gated memory units and cross-attention layers that read
that memory and those keys and values and keep no state of their own.
Differential attention in every attention layer, LayerNorm with bias, no
positional encoding.  The stacked, serving form.

With ``L`` layers, ``m = L / 2`` (16 as published): even ``l <= m`` are
state-space layers, odd ``l < m`` window attention, ``l = m + 1`` full
attention, even ``l > m`` gated memory units, odd ``l > m + 1`` cross
attention.  For a token's hidden state ``x`` (``LN`` a LayerNorm with scale
and bias)::

    x = Emb[id]
    u = LN(x; g1, b1)
    state-space:  [a, z] = split2(u @ W_in)
                  c_t = silu(sum_j w[:, j] * a_{t-3+j} + bc)     (a_{<0} = 0)
                  [r, B, C] = split(c_t @ W_x);  dt = softplus(r @ W_dt + b_dt)
                  h_t = exp(dt_t * A) * h_{t-1} + (dt_t * c_t) B_t;  A = -exp(A_log)
                  y_t = h_t @ C_t + D * c_t;   layer m:  M_t = y_t
                  o = (y_t * silu(z_t)) @ W_out
    memory unit:  o = (M_t * silu(u @ W1)) @ W2
    attention:    [q, k, v] = split(u @ Wqkv + bqkv)
                  o = DiffAttn(q, k, v; window) @ Wo + bo
    cross:        q = u @ Wq + bq;  o = DiffAttn(q, k^(m+1), v^(m+1)) @ Wo + bo
    x = x + o;  u = LN(x; g2, b2);  [g, p] = split2(u @ Wfc1)
    x = x + (p * silu(g)) @ Wfc2
    logits = LN(x; g_out, b_out) @ Emb^T

``DiffAttn``: neighbouring heads pair, ``(q_{2i}, q_{2i+1}) = (q1_i, q2_i)``
and likewise K and V (pair ``j`` of K/V serving pairs ``2j, 2j + 1`` of
queries); ``o_i = RMSNorm((P1_i - lam * P2_i) [v1_j | v2_j]; g_sub) * (1 -
lam0)``, ``P`` the causal (and windowed) softmax of ``q k^T / sqrt(d)``,
``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0``, ``lam0 = 0.8 - 0.6
exp(-0.3 l)``.  ``benchmark/reference/phi4flash_ref.py`` is the same
mathematics in plain float32, written apart from this file.

How it runs.  The layer loop is ONE ``lax.scan`` over the periods (state-
space, window) before ``m``, layers ``m`` and ``m + 1`` written out, and ONE
``lax.scan`` over the periods (memory unit, cross) after them, which closes
over ``M`` and layer ``m + 1``'s pools and writes nothing.  The serving step
carries the three kinds of state of ``serving.paged_cache.SlotStateCache``
as donated buffers viewed flat over their layers.

Differential attention on the ragged kernel that is there: a pool "head" is
a PAIR of K/V heads, a K row ``[k1_j | k2_j]`` and a V row ``[v1_j | v2_j]``
of ``2 d`` = 128 lanes each, in a K pool and a V pool (the dense GPT's
layout; a row of ``d`` = 64 alone is half a lane tile).  ``q1`` rides as
``[q1, 0]`` and ``q2`` as ``[0, q2]`` (the other half adds nothing to a
score), four query rows a pool head (group 4), and the kernel's output IS
``P [v1 | v2]`` for each: every K and V byte is read once a launch and no
lane of the output is thrown away.  ``lam``, the subtraction, the sub-norm
and ``1 - lam0`` follow in XLA (scope ``attn.diff``).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dtype import to_jax_dtype
from ..nn.layer import Layer
from ..ops import dispatch
from ..tensor import Parameter, Tensor
from .decoder_ops import (
    SlotAttention, gated_ffn_fused, kept_inputs, layer_norm, mm, rms_norm,
    rows_of_step, runs_at, step_runs,
)

__all__ = ["Phi4FlashConfig", "Phi4FlashForCausalLM", "phi4flash_tiny"]

SUBLN_EPS = 1e-5


@dataclass
class Phi4FlashConfig:
    """The published ``config.json``'s keys under their own names, then the
    state-space sizes its config class defaults."""

    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    sliding_window: int = 512
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    hidden_act: str = "silu"
    mlp_bias: bool = False
    lm_head_bias: bool = False
    tie_word_embeddings: bool = True
    embd_pdrop: float = 0.0
    resid_pdrop: float = 0.0
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 0          # 0: ceil(hidden_size / 16)
    initializer_range: float = 0.02
    dtype: str = "bfloat16"         # the weights' storage dtype

    def __post_init__(self):
        n = self.num_hidden_layers
        if n < 8 or n % 4:
            raise ValueError(
                f"num_hidden_layers={n}: the two decoders are halves of "
                "whole (operator, attention) periods, 8 layers at least")
        if self.mb_per_layer != 2:
            raise ValueError("every second layer is a state-space layer "
                             f"(mb_per_layer 2), got {self.mb_per_layer}")
        if (self.hidden_act != "silu" or self.mlp_bias or self.lm_head_bias
                or not self.tie_word_embeddings):
            raise ValueError("written as published: silu, no feed-forward "
                             "or head bias, a tied head")
        if self.mamba_d_conv != 4:
            raise ValueError("the convolution is written for 4 taps, got "
                             f"{self.mamba_d_conv}")
        hq, hkv = self.num_attention_heads, self.num_key_value_heads
        if self.hidden_size % hq or hq % hkv or hkv % 2:
            raise ValueError(f"{hq} query and {hkv} K/V heads over hidden "
                             f"{self.hidden_size}: heads pair")
        if not self.mamba_dt_rank:
            self.mamba_dt_rank = math.ceil(self.hidden_size / 16)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def conv_taps(self) -> int:
        """Earlier inputs the convolution reads: what a sequence keeps."""
        return self.mamba_d_conv - 1

    @property
    def memory_layer(self) -> int:
        return self.num_hidden_layers // 2

    @property
    def self_periods(self) -> int:
        return self.num_hidden_layers // 4

    @property
    def cross_periods(self) -> int:
        return self.num_hidden_layers // 4 - 1


def phi4flash_tiny(**kw) -> Phi4FlashConfig:
    """The CPU tests' size: every kind of layer in 8, float32."""
    defaults = dict(vocab_size=512, hidden_size=64, intermediate_size=160,
                    num_hidden_layers=8, num_attention_heads=4,
                    num_key_value_heads=2, sliding_window=8, mamba_d_state=4,
                    mamba_dt_rank=4, max_position_embeddings=4096,
                    dtype="float32")
    defaults.update(kw)
    return Phi4FlashConfig(**defaults)


# ---------------------------------------------------------------------------
# the mathematics, on raw arrays
# ---------------------------------------------------------------------------

def _wide_queries(q):
    """``[..., Hq, d]`` -> ``[..., Hq, 2d]``: an even head (a ``q1``) in the
    first half of its row, an odd one (a ``q2``) in the second."""
    lead, (hq, d) = q.shape[:-2], q.shape[-2:]
    pair = q.reshape(lead + (hq // 2, 2, 1, d))
    eye = jnp.eye(2, dtype=q.dtype).reshape((1,) * len(lead) + (1, 2, 2, 1))
    return (pair * eye).reshape(lead + (hq, 2 * d))


def _differ(out, p, layer):
    """What follows the two softmaxes: ``out [..., Hq, 2d]`` holds ``P1 [v1 |
    v2]`` (even heads) and ``P2 [v1 | v2]`` (odd heads); returns the
    attention's ``[..., Hq * d]``.  ``layer`` may be traced."""
    with jax.named_scope("attn.diff"):
        lead, (hq, w) = out.shape[:-2], out.shape[-2:]
        lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(layer, jnp.float32))
        f32 = jnp.float32
        lam = (jnp.exp(jnp.sum(p["lq1"].astype(f32) * p["lk1"].astype(f32)))
               - jnp.exp(jnp.sum(p["lq2"].astype(f32) * p["lk2"].astype(f32)))
               + lam0)
        o = out.astype(f32).reshape(lead + (hq // 2, 2, w))
        o = o[..., 0, :] - lam * o[..., 1, :]
        o = rms_norm(o, p["g_sub"], SUBLN_EPS) * (1.0 - lam0)
        return o.reshape(lead + (hq // 2 * w,))


def _conv_taps(a, prev, w, b):
    """``silu(w[0] a_{t-3} + w[1] a_{t-2} + w[2] a_{t-1} + w[3] a_t + b)``;
    ``prev`` = ``(a_{t-1}, a_{t-2}, a_{t-3})``, ``w`` [4, channels]."""
    w = w.astype(a.dtype)
    return jax.nn.silu(w[0] * prev[2] + w[1] * prev[1] + w[2] * prev[0]
                       + w[3] * a + b.astype(a.dtype))


class _SlotStep:
    """What one serving step's rows share across its layers: the two plans
    and each row's ring table (``decoder_ops.SlotAttention``), and where a
    state-space layer finds and leaves a run's state.  Rows are the step's
    flat tokens; a run (a decode token or a prefill chunk) is consecutive
    rows at consecutive positions of one slot.  Built once a step from the
    packed step input alone."""

    def __init__(self, pos, tbl, plan, extra, cache):
        self.pos, self.tbl, self.plan = pos, tbl, tuple(plan)
        self.state_rows = int(cache.ssm.shape[1])
        self.kv = SlotAttention(pos, tbl, plan, extra, cache)
        self.runs = step_runs(pos, extra)
        with jax.named_scope("ssm.conv"):
            self.rows = rows_of_step(pos, self.runs)

    def predecessors(self, a, conv, layer):
        """``(a_{t-1}, a_{t-2}, a_{t-3})`` of every row and the convolution's
        pool ``[L * rows, ...]`` with what each run leaves written: a row's
        earlier inputs are its run's own rows or, before the run's first,
        the three its slot kept (none where the run starts a sequence)."""
        return kept_inputs(a, conv, layer * self.state_rows, self.rows,
                           self.runs, ("ssm.conv", "ssm.state_write"))

    def scan(self, dt, x, b, c, a_t, d, ssm, layer):
        from ..ops.pallas_kernels.selective_scan import selective_scan

        return selective_scan(dt, x, b, c, a_t, d, ssm,
                              runs_at(self.runs, layer * self.state_rows))

    def attend(self, q, k, v, pools, *, head_dim, ring_layer=None):
        """Write the rows' K and V and attend: ``q [T, Hq, 2d]`` (wide), ``k``
        / ``v`` ``[T, H, 2d]`` pair rows, against the layer's own pool: the
        ring of window layer ``ring_layer``, else the paged pool.  Returns
        ``(out [T, Hq, 2d], k_pool, v_pool)``."""
        return self.kv.attend(
            q, k, v, pools, head_dim=head_dim, ring_layer=ring_layer,
            scope="attn.shared" if ring_layer is None else "attn.window")

    def attend_shared(self, q, pools, *, head_dim):
        """A cross layer's attention: the full-attention layer's pools, read
        and not written."""
        from ..ops.pallas_kernels.ragged_paged_attention import (
            ragged_paged_attention,
        )

        with jax.named_scope("attn.shared"):
            return ragged_paged_attention(
                q, *pools, self.tbl, self.pos + 1, self.plan,
                sm_scale=float(1.0 / np.sqrt(head_dim)))


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def _shapes(c: Phi4FlashConfig, kind: str) -> dict:
    """The parameter shapes of one layer of ``kind``; float32 ones end in
    ``:f32``."""
    h, f, d = c.hidden_size, c.intermediate_size, c.head_dim
    di, ds, dr = c.d_inner, c.mamba_d_state, c.mamba_dt_rank
    qw, kvw = c.num_attention_heads * d, c.num_key_value_heads * d
    diff = {"lq1:f32": (d,), "lk1:f32": (d,), "lq2:f32": (d,),
            "lk2:f32": (d,), "g_sub": (2 * d,)}
    op = {
        "ssm": {"w_in": (h, 2 * di), "conv_w": (4, di), "conv_b": (di,),
                "w_x": (di, dr + 2 * ds), "w_dt": (dr, di),
                "dt_b:f32": (di,), "a_log:f32": (di, ds), "d:f32": (di,),
                "w_out": (di, h)},
        "gmu": {"w1": (h, di), "w2": (di, h)},
        "attn": {"wqkv": (h, qw + 2 * kvw), "bqkv": (qw + 2 * kvw,),
                 "wo": (qw, h), "bo": (h,), **diff},
        "cross": {"wq": (h, qw), "bq": (qw,), "wo": (qw, h), "bo": (h,),
                  **diff},
    }[kind]
    return {"ln1_g": (h,), "ln1_b": (h,), "ln2_g": (h,), "ln2_b": (h,),
            "fc1": (h, 2 * f), "fc2": (f, h), **op}


class Phi4FlashForCausalLM(Layer):
    """Embedding + the two stacked decoders + tied head.  ``forward`` is the
    plain full forward over ``[B, S]`` ids; ``new_paged_kv_cache`` /
    ``_paged_lm_logits`` are the paged contract ``ServingEngine`` asks for.

    Parameters are one set a layer position: ``self0_*`` / ``self1_*`` (the
    state-space and the window layer of a period before the memory layer)
    and ``cross0_*`` / ``cross1_*`` (the memory unit and the cross layer of a
    period after it) with a leading period axis that the scans take as
    ``xs``; ``mid0_*`` (the memory layer) and ``mid1_*`` (the full-attention
    layer) without."""

    #: the engine sizes this model's cache by its slots and longest run
    slot_resident_state = True
    #: what ``ServingEngine`` is refused for this model, and why
    serving_unsupported = {
        "prefix_cache": "a shared page holds the full-attention layer's K/V "
                        "but neither the window ring nor the state-space "
                        "state at its end",
        "mp": "the state rows, the ring and the scan are not sharded over "
              "chips",
        "lora": "no adapter path is written for the state-space, memory-"
                "unit or differential-attention projections",
        "kv_int8": "the ring and the state rows have no scale sidecar",
        "weight_int8": "no int8 form of the state-space weights",
        "speculative": "a rejected draft token would have advanced the "
                       "state-space state, which no rollback restores",
        "disagg": "a page hand-off carries neither the ring nor the state "
                  "rows",
    }
    SEGMENTS = (("self0", "ssm"), ("self1", "attn"), ("mid0", "ssm"),
                ("mid1", "attn"), ("cross0", "gmu"), ("cross1", "cross"))

    def __init__(self, cfg: Phi4FlashConfig):
        super().__init__()
        self.config = cfg
        dt = to_jax_dtype(cfg.dtype)
        std = cfg.initializer_range
        from ..ops.random import default_generator

        def normal(shape, dtype=dt, scale=std):
            # drawn in the storage dtype on the device: no float32 copy of
            # a weight is ever made
            return jax.random.normal(default_generator.split(), shape,
                                     dtype) * jnp.asarray(scale, dtype)

        self._names = []

        def param(name, value):
            # not trainable: no backward is written for the scan
            # (ROADMAP.md R-M), so no forward ever records one
            setattr(self, name, Parameter(value, trainable=False))
            self._names.append(name)

        h = cfg.hidden_size
        param("embed", normal((cfg.vocab_size, h)))
        param("out_g", jnp.ones((h,), dt))
        param("out_b", normal((h,)))
        reps = {"self": (cfg.self_periods,), "mid": (),
                "cross": (cfg.cross_periods,)}
        for seg, kind in self.SEGMENTS:
            lead = reps[seg[:-1]]
            for name, shape in _shapes(cfg, kind).items():
                name, _, f32 = name.partition(":")
                full = lead + shape
                if name in ("ln1_g", "ln2_g", "g_sub", "d"):
                    value = jnp.ones(full, jnp.float32 if f32 else dt)
                elif name == "a_log":
                    # A = -(1 .. d_state) a channel, the usual start
                    value = jnp.broadcast_to(jnp.log(jnp.arange(
                        1, shape[1] + 1, dtype=jnp.float32)), full)
                elif name == "dt_b":
                    # softplus(dt_b) log-uniform in [1e-3, 1e-1]
                    step = jnp.exp(jax.random.uniform(
                        default_generator.split(), full, jnp.float32,
                        np.log(1e-3), np.log(1e-1)))
                    value = step + jnp.log(-jnp.expm1(-step))
                elif name == "conv_w":
                    # the spread a depthwise convolution of 4 taps is born
                    # with (uniform in +-1/sqrt(4)): at the matrices' 0.02
                    # the state-space path would be too faint for any
                    # comparison of logits to see
                    value = normal(full, dt, 0.29)
                elif f32:
                    value = normal(full, jnp.float32, 0.1)
                else:
                    value = normal(full)
                param(f"{seg}_{name}", value)

    def _arrays(self):
        return [getattr(self, n) for n in self._names]

    # -- the layers, over raw arrays --------------------------------------
    def _run(self, w: dict, x, cores, state):
        """Both decoders over hidden rows ``x`` ``[..., H]``.  ``w``: the raw
        parameters by name.  ``cores``: ``conv(a, state, layer) -> ((a_{t-1},
        a_{t-2}, a_{t-3}), state)``; ``scan(dt, c, B, C, A^T, D, state,
        layer) -> (y, state)``; ``attend(q, k, v, state, ring_layer) -> (out,
        state)`` (``ring_layer`` None: the full-attention layer) and
        ``attend_shared(q, state) -> out``; ``layer`` counts the layers of
        that kind before this one.  ``state`` is whatever the cores carry
        (the pools in serving; nothing in the plain forward)."""
        cfg = self.config
        eps = cfg.layer_norm_eps
        hq, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        di, ds, dr = cfg.d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
        m = cfg.memory_layer

        def ssm_op(p, u, state, layer):
            """-> (o, the layer's y before the gate, state)."""
            with jax.named_scope("ssm.proj"):
                a, z = jnp.split(mm(u, p["w_in"]).astype(u.dtype), 2, axis=-1)
            prev, state = cores["conv"](a, state, layer)
            with jax.named_scope("ssm.conv"):
                c = _conv_taps(a, prev, p["conv_w"], p["conv_b"])
            with jax.named_scope("ssm.proj"):
                r, b_, c_ = jnp.split(mm(c, p["w_x"]), (dr, dr + ds), axis=-1)
                dt = jax.nn.softplus(mm(r.astype(u.dtype), p["w_dt"])
                                     + p["dt_b"])
            with jax.named_scope("ssm.scan"):
                a_t = -jnp.exp(p["a_log"].astype(jnp.float32)).T
                y, state = cores["scan"](dt, c, b_, c_, a_t, p["d"], state,
                                         layer)
                gated = (y * jax.nn.silu(z.astype(jnp.float32))).astype(u.dtype)
            with jax.named_scope("ssm.proj"):
                return mm(gated, p["w_out"]).astype(u.dtype), y, state

        def gmu_op(p, u, mem):
            with jax.named_scope("gmu"):
                gate = jax.nn.silu(mm(u, p["w1"]))
                return mm((mem * gate).astype(u.dtype), p["w2"]).astype(u.dtype)

        def out_proj(p, o, u):
            with jax.named_scope("attn.out"):
                return (mm(o.astype(u.dtype), p["wo"]) + p["bo"]).astype(u.dtype)

        def attn_op(p, u, state, layer, ring_layer):
            lead = u.shape[:-1]
            with jax.named_scope("attn.qkv"):
                qkv = (mm(u, p["wqkv"]) + p["bqkv"]).astype(u.dtype)
                q, k, v = jnp.split(qkv, (hq * d, (hq + hkv) * d), axis=-1)
                q = _wide_queries(q.reshape(lead + (hq, d)))
                k = k.reshape(lead + (hkv // 2, 2 * d))
                v = v.reshape(lead + (hkv // 2, 2 * d))
            out, state = cores["attend"](q, k, v, state, ring_layer)
            return out_proj(p, _differ(out, p, layer), u), state

        def cross_op(p, u, state, layer):
            with jax.named_scope("attn.qkv"):
                q = (mm(u, p["wq"]) + p["bq"]).astype(u.dtype)
                q = _wide_queries(q.reshape(u.shape[:-1] + (hq, d)))
            out = cores["attend_shared"](q, state)
            return out_proj(p, _differ(out, p, layer), u)

        def around(p, x, op):
            """``x + op(LN(x))`` and the feed-forward behind it; ``op``
            returns ``(o, whatever else)``."""
            with jax.named_scope("mlp"):
                u = layer_norm(x, p["ln1_g"], p["ln1_b"], eps)
            o, *rest = op(u)
            x = x + o
            with jax.named_scope("mlp"):
                u = layer_norm(x, p["ln2_g"], p["ln2_b"], eps)
                x = x + gated_ffn_fused(u, p["fc1"], p["fc2"])
            return (x, *rest)

        def seg(prefix):
            return {k[len(prefix) + 1:]: v for k, v in w.items()
                    if k.startswith(prefix + "_")}

        def scan_over(prefixes, body, carry, n):
            """``carry`` through ``body(carry, i, (p0, p1))`` for the ``n``
            periods of the two stacked layers ``prefixes``."""
            stacks = [seg(p) for p in prefixes]
            names = [sorted(s) for s in stacks]

            def step(carry, xs):
                i, arrays = xs[0], iter(xs[1:])
                ps = [{k: next(arrays) for k in ns} for ns in names]
                return body(carry, i, ps), None

            carry, _ = jax.lax.scan(
                step, carry, (jnp.arange(n, dtype=jnp.int32),
                              *(s[k] for s, ns in zip(stacks, names)
                                for k in ns)))
            return carry

        with jax.named_scope("layers"):
            def self_period(carry, i, ps):
                x, state = carry
                x, _, state = around(
                    ps[0], x, lambda u: ssm_op(ps[0], u, state, i))
                x, state = around(
                    ps[1], x, lambda u: attn_op(ps[1], u, state, 2 * i + 1, i))
                return x, state

            x, state = scan_over(("self0", "self1"), self_period, (x, state),
                                 cfg.self_periods)
            p = seg("mid0")
            x, mem, state = around(
                p, x, lambda u: ssm_op(p, u, state, cfg.self_periods))
            p = seg("mid1")
            x, state = around(
                p, x, lambda u: attn_op(p, u, state, m + 1, None))

            def cross_period(x, i, ps):
                (x,) = around(ps[0], x, lambda u: (gmu_op(ps[0], u, mem),))
                (x,) = around(ps[1], x, lambda u: (cross_op(
                    ps[1], u, state, m + 3 + 2 * i),))
                return x

            x = scan_over(("cross0", "cross1"), cross_period, x,
                          cfg.cross_periods)
        return x, state

    def _head(self, w, x):
        x = layer_norm(x, w["out_g"], w["out_b"], self.config.layer_norm_eps)
        return jnp.dot(x, w["embed"].T, preferred_element_type=jnp.float32)

    # -- plain forward ------------------------------------------------------
    def forward(self, input_ids: Tensor) -> Tensor:
        """``[B, S]`` ids -> ``[B, S, V]`` float32 logits: every sequence from
        position 0, no cache."""
        cfg = self.config
        names = self._names
        scale = np.float32(1.0 / np.sqrt(cfg.head_dim))
        window = cfg.sliding_window

        def one(ids, *arrays):
            w = dict(zip(names, arrays))
            s = ids.shape[0]
            at = jnp.arange(s)
            causal = at[None, :] <= at[:, None]
            inside = causal & (at[:, None] - at[None, :] < window)

            def conv(a, state, layer):
                pad = jnp.pad(a, ((3, 0), (0, 0)))
                return (pad[2:s + 2], pad[1:s + 1], pad[:s]), state

            def scan(dt, x, b, c, a_t, d, state, layer):
                def step(h, inp):
                    dt_t, x_t, b_t, c_t = inp
                    h = jnp.exp(dt_t[None, :] * a_t) * h \
                        + b_t[:, None] * (dt_t * x_t)[None, :]
                    return h, jnp.sum(h * c_t[:, None], axis=0) + d * x_t

                f32 = jnp.float32
                _, y = jax.lax.scan(step, jnp.zeros(a_t.shape, f32),
                                    (dt.astype(f32), x.astype(f32),
                                     b.astype(f32), c.astype(f32)))
                return y, state

            def dense(q, k, v, mask):
                group = q.shape[1] // k.shape[1]
                k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
                sc = jnp.einsum("qhd,khd->hqk", q, k,
                                preferred_element_type=jnp.float32) * scale
                att = jax.nn.softmax(jnp.where(mask, sc, -1e30), axis=-1)
                return jnp.einsum("hqk,khd->qhd", att.astype(v.dtype), v)

            def attend(q, k, v, state, ring_layer):
                if ring_layer is None:      # the keys and values kept
                    return dense(q, k, v, causal), (k, v)
                return dense(q, k, v, inside), state

            cores = {"conv": conv, "scan": scan, "attend": attend,
                     "attend_shared": lambda q, kv: dense(q, *kv, causal)}
            with jax.named_scope("embed"):
                x = jnp.take(w["embed"], ids, axis=0)
            x, _ = self._run(w, x, cores, ())
            with jax.named_scope("lm_head"):
                return self._head(w, x)

        def raw(ids, *arrays):
            return jax.vmap(lambda i: one(i, *arrays))(ids)

        return dispatch.apply(raw, input_ids, *self._arrays(),
                              op_name="phi4flash_forward")

    # -- ServingEngine paged-cache contract --------------------------------
    def new_paged_kv_cache(self, num_pages: int, page_size: int,
                           dtype: str = "bfloat16", *, num_slots: int,
                           max_run: int):
        from ..serving.paged_cache import SlotStateCache

        cfg = self.config
        return SlotStateCache(
            num_pages=num_pages, page_size=page_size, num_slots=num_slots,
            max_run=max_run, num_heads=cfg.num_key_value_heads // 2,
            row_dim=2 * cfg.head_dim, window=cfg.sliding_window,
            window_layers=cfg.self_periods,
            rows=SlotStateCache.mamba_rows(
                cfg.self_periods + 1, cfg.d_inner, cfg.mamba_d_state,
                cfg.conv_taps, dtype),
            state_counters=(("ssm_runs", "runs"), ("ssm_rows", "rows"),
                            ("cross_rows", "rows")), dtype=dtype)

    def _paged_lm_logits(self, input_ids, paged_cache, page_tables,
                         positions, ragged_plan=None, out_rows=None,
                         lora=None, slot_state=None):
        """The fused serving step's model part: ``input_ids`` [T, 1] flat
        tokens at ``positions`` [T] with their slots' ``page_tables``
        [T, max_pages], the step's ragged plan and what the cache packed
        beside it (``slot_state``: ``SlotStateCache.pack_fields`` by name);
        logits ``[S, 1, V]`` at ``out_rows``.  The cache's pools are updated
        in place (mutation-logged, so donated under ``jit.to_static``)."""
        if lora is not None:
            raise NotImplementedError(
                "Phi4FlashForCausalLM: " + self.serving_unsupported["lora"])
        if ragged_plan is None or out_rows is None or slot_state is None:
            raise ValueError(
                "Phi4FlashForCausalLM serves through the fused ragged step "
                "of an engine that packs its cache's slot state: "
                "_paged_lm_logits needs ragged_plan, out_rows and slot_state")
        cfg = self.config
        names = self._names
        plan = tuple(ragged_plan)
        n_plan = len(plan)
        fields = list(slot_state)
        cache = paged_cache
        pool_names = ("k", "v", "ring_k", "ring_v", "ssm", "conv")
        held = tuple(getattr(cache, n) for n in pool_names)
        d = cfg.head_dim

        def flat(a):        # a stacked pool viewed over its layers
            return a.reshape((-1,) + a.shape[2:])

        def raw(ids, pos, tbl, rows_out, *rest):
            planr, rest = rest[:n_plan], rest[n_plan:]
            extra, rest = dict(zip(fields, rest)), rest[len(fields):]
            pools, arrays = rest[:len(held)], rest[len(held):]
            w = dict(zip(names, arrays))
            pos, tbl = pos.astype(jnp.int32), tbl.astype(jnp.int32)
            step = _SlotStep(pos, tbl, planr, extra, cache)

            def conv(a, state, layer):
                prev, conv_ = step.predecessors(a, state["conv"], layer)
                return prev, {**state, "conv": conv_}

            def scan(dt, x, b, c, a_t, d_, state, layer):
                y, ssm = step.scan(dt, x, b, c, a_t, d_, state["ssm"], layer)
                return y, {**state, "ssm": ssm}

            def attend(q, k, v, state, ring_layer):
                mine = ("k", "v") if ring_layer is None else ("ring_k", "ring_v")
                out, pk, pv = step.attend(
                    q, k, v, tuple(state[n] for n in mine), head_dim=d,
                    ring_layer=ring_layer)
                return out, {**state, mine[0]: pk, mine[1]: pv}

            def attend_shared(q, state):
                return step.attend_shared(q, (state["k"], state["v"]),
                                          head_dim=d)

            cores = {"conv": conv, "scan": scan, "attend": attend,
                     "attend_shared": attend_shared}
            with jax.named_scope("embed"):
                x = jnp.take(w["embed"], ids.reshape(-1), axis=0)
            state = {n: flat(p) for n, p in zip(pool_names, pools)}
            x, state = self._run(w, x, cores, state)
            with jax.named_scope("lm_head"):
                logits = self._head(w, jnp.take(x, rows_out, axis=0))
            return (logits[:, None, :],
                    *(state[n].reshape(p.shape)
                      for n, p in zip(pool_names, pools)))

        results = dispatch.apply(
            raw, input_ids, positions, page_tables, out_rows, *plan,
            *(slot_state[f] for f in fields), *held, *self._arrays(),
            op_name="phi4flash_paged_step")
        for tensor, new in zip(held, results[1:]):
            tensor._set_value(new._value)
        return results[0]
