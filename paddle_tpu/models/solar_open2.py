"""A hybrid of gated delta-rule (Kimi Delta Attention) layers and gated
softmax attention without positions, every layer with routed experts beside
a shared one (``model_type: solar_open2``).  The stacked, serving form.

For a token's hidden state ``x`` (``N`` an RMSNorm, ``d`` the head size, ``H``
the linear layers' heads)::

    x = Emb[id]
    for each layer:
      u = N(x; g_op)
      KDA (the layers not in gqa_layers):
          q, k, v = split3(silu(conv4(u @ Wqkv)))     (causal, depthwise, 4 taps)
          q = q / |q| / sqrt(d), k = k / |k|         (per head)
          log alpha = -exp(A_log[h]) softplus(u @ Wa_dn @ Wa_up + dt_bias)
          beta = 2 sigmoid(u @ Wb)                   (kda_allow_neg_eigval)
          S_t = (I - beta k k^T) Diag(alpha) S_{t-1} + beta k v^T;  o = S_t^T q
          o = (N_head(o; g_o) * sigmoid(u @ Wg_dn @ Wg_up)) @ Wo
      GQA (gqa_layers):
          q, k, v = u @ Wq, u @ Wk, u @ Wv;  causal softmax, no positions, K/V
          head j serving query heads G*j .. G*j + G - 1
          o = (o * sigmoid(u @ W_gate)) @ Wo
      x = x + o;  u = N(x; g_ffn)
      s = sigmoid(f32(u) @ Wr);  sel = top_k(s + b);  p = s[sel] / (sum + 1e-6)
      y = ffn_shared(u) + sum_{e in sel, e held} p_e ffn_e(u),
          ffn(u) = (silu(u @ W1) * (u @ W3)) @ W2
      x = x + y
    logits = N(x; g_out) @ W_head^T

The experts HELD (``experts_held``, all by default) are a range of the
``n_routed_experts`` the router scores: a pair whose expert lies outside it is
another chip's part of the layer and is dropped (``lfm2._moe``'s ``held``);
nothing stands in for it.  ``benchmark/reference/solar2_ref.py`` is the same
mathematics in plain float32, written apart from this file, with the delta
rule as its token-serial recurrence.

How it runs.  The layers repeat with a period (GQA, KDA, KDA, KDA as
published); the layer loop is ONE ``lax.scan`` over whole periods whose body
is a period's layers written out.  The serving step carries the K/V pool of
the GQA layers and the delta-rule state rows and convolution tails of
``serving.paged_cache.SlotStateCache`` (TWO rows a slot, swapped at harvest)
as donated buffers viewed flat over their layers; the rule is
``ops/pallas_kernels/kda.py``.
"""
from __future__ import annotations

import weakref
from dataclasses import dataclass, field
from types import SimpleNamespace
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dtype import to_jax_dtype
from ..nn.layer import Layer
from ..ops import dispatch
from ..tensor import Parameter, Tensor
from .decoder_ops import (
    gated_ffn, kept_inputs, mm, rms_norm, rows_of_step, runs_at, step_runs,
)

__all__ = ["SolarOpen2Config", "SolarOpen2ForCausalLM", "solar_open2_tiny",
           "PUBLISHED_GQA_LAYERS"]

GQA, KDA = "gqa", "kda"
#: ``gqa_layers`` of the published 48-layer model
PUBLISHED_GQA_LAYERS = tuple(range(0, 48, 4))
L2_EPS = 1e-6


@dataclass
class SolarOpen2Config:
    """The published ``config.json``'s keys under their own names, then what
    it leaves to the family's convention (the configuration file's
    ``assumed``) and ``experts_held``."""

    vocab_size: int = 196608
    hidden_size: int = 4096
    intermediate_size: int = 10240      # no layer is dense (first_k_dense_replace 0)
    moe_intermediate_size: int = 1280
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    linear_attn_config: dict = field(default_factory=lambda: {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None})
    gqa_layers: Sequence[int] = PUBLISHED_GQA_LAYERS
    gqa_interval: int = 3
    use_gqa_gate: bool = True
    use_rope: bool = False
    partial_rotary_factor: float = 1.0
    rope_theta: float = 10000.0
    kda_use_full_proj: bool = False
    kda_allow_neg_eigval: bool = True
    n_routed_experts: int = 320
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    first_k_dense_replace: int = 0
    rms_norm_eps: float = 1e-5
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 1048576
    model_type: str = "solar_open2"
    # the experts this chip holds: (first, count); None: every one
    experts_held: Optional[Sequence[int]] = None
    initializer_range: float = 0.02
    dtype: str = "bfloat16"             # the weights' storage dtype

    def __post_init__(self):
        n = self.num_hidden_layers
        self.gqa_layers = tuple(int(i) for i in self.gqa_layers if i < n)
        lin = self.linear_attn_config
        if lin.get("short_conv_kernel_size") != 4:
            raise ValueError("the convolution is written for 4 taps, got "
                             f"{lin.get('short_conv_kernel_size')}")
        if (self.use_rope or self.kda_use_full_proj or not self.use_gqa_gate
                or not self.kda_allow_neg_eigval or self.n_shared_experts < 1):
            raise ValueError("written as published: no positions, low-rank "
                             "decay and gate projections, beta up to 2, a "
                             "gated GQA layer, a shared expert")
        if self.first_k_dense_replace or not self.norm_topk_prob \
                or self.routed_scaling_factor != 1.0 or self.tie_word_embeddings:
            raise ValueError("written as published: no dense layer, "
                             "norm_topk_prob, routed_scaling_factor 1, an "
                             "untied head")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(f"{self.num_attention_heads} query heads over "
                             f"{self.num_key_value_heads} K/V heads")
        first, count = self.held
        if not (0 <= first and count >= 1
                and first + count <= self.n_routed_experts):
            raise ValueError(f"experts_held {self.experts_held} of "
                             f"{self.n_routed_experts}")
        if n % self.period:
            raise ValueError(f"layers {self.layer_kinds} are no whole periods")

    @property
    def layer_kinds(self):
        return tuple(GQA if i in self.gqa_layers else KDA
                     for i in range(self.num_hidden_layers))

    @property
    def period(self) -> int:
        kinds = self.layer_kinds
        return next(p for p in range(1, len(kinds) + 1)
                    if all(kinds[i] == kinds[i % p] for i in range(len(kinds))))

    @property
    def held(self):
        """``(first, count)`` of the experts held here."""
        if self.experts_held is None:
            return 0, self.n_routed_experts
        first, count = self.experts_held
        return int(first), int(count)

    @property
    def linear_heads(self) -> int:
        return int(self.linear_attn_config["num_heads"])

    @property
    def linear_head_dim(self) -> int:
        return int(self.linear_attn_config["head_dim"])

    @property
    def kda_rank(self) -> int:
        """The decay's and the gate's low rank: the linear head size, Kimi
        Linear's (the config does not give it)."""
        return self.linear_head_dim


def solar_open2_tiny(**kw) -> SolarOpen2Config:
    """The CPU tests' size: one period (GQA, KDA, KDA, KDA), float32."""
    defaults = dict(vocab_size=256, hidden_size=64, moe_intermediate_size=32,
                    num_hidden_layers=4, num_attention_heads=4,
                    num_key_value_heads=2, head_dim=16,
                    linear_attn_config={"short_conv_kernel_size": 4,
                                        "head_dim": 16, "num_heads": 4,
                                        "num_kv_heads": None},
                    n_routed_experts=16, num_experts_per_tok=4,
                    max_position_embeddings=4096, dtype="float32")
    defaults.update(kw)
    return SolarOpen2Config(**defaults)


# ---------------------------------------------------------------------------
# the mathematics, on raw arrays
# ---------------------------------------------------------------------------

def _l2(x):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _shapes(c: SolarOpen2Config, kind: str) -> dict:
    """The parameter shapes of one layer of ``kind``; float32 ones end in
    ``:f32``."""
    h, d = c.hidden_size, c.head_dim
    hl, dl, r = c.linear_heads, c.linear_head_dim, c.kda_rank
    fs = c.moe_intermediate_size * c.n_shared_experts
    op = {
        GQA: {"wq": (h, c.num_attention_heads * d),
              "wk": (h, c.num_key_value_heads * d),
              "wv": (h, c.num_key_value_heads * d),
              "wgate": (h, c.num_attention_heads * d),
              "wo": (c.num_attention_heads * d, h)},
        KDA: {"wqkv": (h, 3 * hl * dl), "conv_w": (4, 3 * hl * dl),
              "wb": (h, hl), "wa_dn": (h, r), "wa_up": (r, hl * dl),
              "dt_bias:f32": (hl * dl,), "a_log:f32": (hl,),
              "wg_dn": (h, r), "wg_up": (r, hl * dl), "o_norm": (dl,),
              "wo": (hl * dl, h)},
    }[kind]
    return {"ln_op": (h,), "ln_ffn": (h,), "router:f32": (h, c.n_routed_experts),
            "router_bias:f32": (c.n_routed_experts,), "sh_w1": (h, fs),
            "sh_w3": (h, fs), "sh_w2": (fs, h), **op}


class _SlotStep:
    """What one serving step's rows share across its layers: the plan, where
    each row's K/V is written, and where a delta-rule layer finds and leaves
    a run's state and kept inputs.  Built once a step from the packed step
    input alone."""

    def __init__(self, pos, tbl, plan, extra, cache):
        self.pos, self.tbl, self.plan = pos, tbl, tuple(plan)
        self.page_size = int(cache.page_size)
        self.n_pages = int(cache.num_pages)
        self.state_rows = int(cache.kda.shape[1])
        self.runs = step_runs(pos, extra)
        with jax.named_scope("kda.conv"):
            self.rows = rows_of_step(pos, self.runs)
        self.real = self.rows.real
        last = tbl.shape[1] - 1
        self.write_page = jnp.take_along_axis(
            tbl, jnp.clip(pos // self.page_size, 0, last)[:, None], axis=1)[:, 0]

    def predecessors(self, a, conv, layer):
        return kept_inputs(a, conv, layer * self.state_rows, self.rows,
                           self.runs, ("kda.conv", "kda.state_write"))

    def rule(self, q, k, v, g, beta, pool, layer):
        from ..ops.pallas_kernels.kda import kda

        return kda(q, k, v, g, beta, pool,
                   runs_at(self.runs, layer * self.state_rows))

    def attend(self, q, k, v, pools, layer, head_dim):
        from .gpt import _attend_paged_shard
        from ..ops.pallas_kernels.ragged_paged_attention import plan_at_layer

        base = layer * self.n_pages
        out, pk, pv = _attend_paged_shard(
            q[:, :, None, :], k[:, :, None, :], v[:, :, None, :], *pools,
            self.tbl + base, self.pos, head_dim=head_dim,
            page_size=self.page_size, ragged_plan=plan_at_layer(self.plan, base),
            attend_scope="attn.gqa")
        return out[:, :, 0, :], pk, pv


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

class SolarOpen2ForCausalLM(Layer):
    """Embedding + the stacked hybrid decoder + an untied head.  ``forward``
    is the plain full forward over ``[B, S]`` ids; ``new_paged_kv_cache`` /
    ``_paged_lm_logits`` are the paged contract ``ServingEngine`` asks for.

    Parameters are one set a position of the period, ``p<j>_*``, with a
    leading ``[n_periods]`` axis that the scan takes as ``xs``; the held
    experts of every layer are ``moe_w1``/``moe_w3`` ``[L * E_held, H, F]``
    and ``moe_w2`` ``[L * E_held, F, H]``, layer ``l``'s at ``l * E_held``."""

    #: the engine sizes this model's cache by its slots and longest run
    slot_resident_state = True
    #: what ``ServingEngine`` is refused for this model, and why
    serving_unsupported = {
        "prefix_cache": "a shared page holds the GQA layers' K/V but not the "
                        "delta-rule state or the convolution's kept inputs at "
                        "its end",
        "mp": "the delta-rule state rows, the kernel and the expert share "
              "are not sharded over chips",
        "lora": "no adapter path is written for the delta-rule, gate or "
                "expert projections",
        "kv_int8": "the state rows have no scale sidecar",
        "weight_int8": "no int8 form of the delta-rule or expert weights",
        "speculative": "a rejected draft token would have advanced the "
                       "delta-rule state, which no rollback restores",
        "disagg": "a page hand-off carries none of the state rows",
    }

    def __init__(self, cfg: SolarOpen2Config):
        super().__init__()
        self.config = cfg
        self._newest_cache = None
        kinds = cfg.layer_kinds
        self._period = kinds[:cfg.period]
        self._n_periods = cfg.num_hidden_layers // cfg.period
        self._n_gqa, self._n_kda = kinds.count(GQA), kinds.count(KDA)
        dt = to_jax_dtype(cfg.dtype)
        std = cfg.initializer_range
        from ..ops.random import default_generator
        from .lfm2 import Lfm2StackedForCausalLM

        def normal(shape, dtype=dt, scale=std):
            return jax.random.normal(default_generator.split(), shape,
                                     dtype) * jnp.asarray(scale, dtype)

        self._names = []

        def param(name, value):
            # not trainable: no backward is written for the delta rule's
            # kernel or the grouped product (ROADMAP.md R-M)
            setattr(self, name, Parameter(value, trainable=False))
            self._names.append(name)

        h, v = cfg.hidden_size, cfg.vocab_size
        param("embed", normal((v, h)))
        param("out_norm", jnp.ones((h,), dt))
        param("lm_head", normal((v, h)))
        lead = (self._n_periods,)
        for j, kind in enumerate(self._period):
            for name, shape in _shapes(cfg, kind).items():
                name, _, f32 = name.partition(":")
                full = lead + shape
                if name in ("ln_op", "ln_ffn", "o_norm"):
                    value = jnp.ones(full, dt)
                elif name == "a_log":
                    # A = exp(A_log) uniform in [1, 16] a head
                    value = jnp.log(jax.random.uniform(
                        default_generator.split(), full, jnp.float32, 1.0, 16.0))
                elif name == "dt_bias":
                    # softplus(dt_bias) log-uniform in [1e-3, 1e-1]
                    step = jnp.exp(jax.random.uniform(
                        default_generator.split(), full, jnp.float32,
                        np.log(1e-3), np.log(1e-1)))
                    value = step + jnp.log(-jnp.expm1(-step))
                elif name == "conv_w":
                    # the spread a depthwise convolution of 4 taps is born
                    # with (uniform in +-1/sqrt(4))
                    value = normal(full, dt, 0.29)
                elif name == "router_bias":
                    value = normal(full, jnp.float32, 0.01)
                elif f32:
                    value = normal(full, jnp.float32)
                else:
                    value = normal(full)
                param(f"p{j}_{name}", value)
        _, held = cfg.held
        fm = cfg.moe_intermediate_size
        for name, shape in (("moe_w1", (h, fm)), ("moe_w3", (h, fm)),
                            ("moe_w2", (fm, h))):
            param(name, Lfm2StackedForCausalLM._stack_of_experts(
                cfg.num_hidden_layers * held, shape, std, dt,
                default_generator.split()))

    def _arrays(self):
        return [getattr(self, n) for n in self._names]

    # -- the layers, over raw arrays --------------------------------------
    def _run(self, w: dict, x, cores, state):
        """The decoder over hidden rows ``x`` [T, H].  ``cores``: ``conv(a,
        state, layer) -> ((a_{t-1}, a_{t-2}, a_{t-3}), state)``, ``rule(q, k,
        v, g, beta, state, layer) -> (o, state)``, ``attend(q, k, v, state,
        layer) -> (out, state)``, ``note(state, counts, sel, layer) -> state``
        and ``real`` (or None); ``layer`` counts the layers of that kind
        before this one."""
        from .lfm2 import _moe

        cfg = self.config
        eps, f32 = cfg.rms_norm_eps, jnp.float32
        hq, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        hl, dl = cfg.linear_heads, cfg.linear_head_dim
        first, n_held = cfg.held
        held = None if n_held == cfg.n_routed_experts else (first, n_held)

        def kda_op(p, u, state, layer):
            t = u.shape[0]
            with jax.named_scope("kda.proj"):
                a = mm(u, p["wqkv"]).astype(u.dtype)
            prev, state = cores["conv"](a, state, layer)
            with jax.named_scope("kda.conv"):
                w_ = p["conv_w"].astype(f32)
                c = jax.nn.silu(w_[0] * prev[2].astype(f32)
                                + w_[1] * prev[1].astype(f32)
                                + w_[2] * prev[0].astype(f32)
                                + w_[3] * a.astype(f32))
                q, k, v = (x_.reshape(t, hl, dl) for x_ in jnp.split(c, 3, -1))
            with jax.named_scope("kda.gate"):
                q = _l2(q) * np.float32(1.0 / np.sqrt(dl))
                k = _l2(k)
                beta = 2.0 * jax.nn.sigmoid(mm(u, p["wb"]))
                low = mm(mm(u, p["wa_dn"]).astype(u.dtype), p["wa_up"])
                g = (-jnp.exp(p["a_log"].astype(f32))[:, None]
                     * jax.nn.softplus(low + p["dt_bias"]).reshape(t, hl, dl))
                gate = jax.nn.sigmoid(mm(mm(u, p["wg_dn"]).astype(u.dtype),
                                         p["wg_up"]))
            with jax.named_scope("kda.scan"):
                o, state = cores["rule"](q, k, v, g, beta, state, layer)
            with jax.named_scope("kda.norm"):
                o = rms_norm(o, p["o_norm"], eps).reshape(t, hl * dl) * gate
            with jax.named_scope("kda.proj"):
                return mm(o.astype(u.dtype), p["wo"]).astype(u.dtype), state

        def gqa_op(p, u, state, layer):
            t = u.shape[0]
            with jax.named_scope("attn.qkv"):
                q = mm(u, p["wq"]).astype(u.dtype).reshape(t, hq, d)
                k = mm(u, p["wk"]).astype(u.dtype).reshape(t, hkv, d)
                v = mm(u, p["wv"]).astype(u.dtype).reshape(t, hkv, d)
            out, state = cores["attend"](q, k, v, state, layer)
            with jax.named_scope("attn.gqa"):
                gate = jax.nn.sigmoid(mm(u, p["wgate"]))
                out = out.reshape(t, hq * d).astype(f32) * gate
            with jax.named_scope("attn.out"):
                return mm(out.astype(u.dtype), p["wo"]).astype(u.dtype), state

        router = SimpleNamespace(num_experts_per_tok=cfg.num_experts_per_tok,
                                 num_experts=cfg.n_routed_experts)

        def period(ps, x, state, i):
            n_g = n_k = 0
            for j, (kind, p) in enumerate(zip(self._period, ps)):
                scope = "kda.proj" if kind == KDA else "attn.qkv"
                with jax.named_scope(scope):
                    u = rms_norm(x, p["ln_op"], eps)
                if kind == KDA:
                    o, state = kda_op(p, u, state, i * self._period.count(KDA) + n_k)
                    n_k += 1
                else:
                    o, state = gqa_op(p, u, state, i * self._period.count(GQA) + n_g)
                    n_g += 1
                x = x + o
                layer = i * len(self._period) + j
                with jax.named_scope("moe.route"):
                    u = rms_norm(x, p["ln_ffn"], eps)
                y, counts, sel = _moe(router, u, p["router"], p["router_bias"],
                                      w["moe_w1"], w["moe_w3"], w["moe_w2"],
                                      layer * n_held, cores["real"], held=held)
                with jax.named_scope("moe.shared"):
                    y = y + gated_ffn(u, p["sh_w1"], p["sh_w3"], p["sh_w2"])
                state = cores["note"](state, counts, sel, layer)
                x = x + y
            return x, state

        names = [sorted(k[len(f"p{j}_"):] for k in w if k.startswith(f"p{j}_"))
                 for j in range(len(self._period))]

        def step(carry, xs):
            i, arrays = xs[0], iter(xs[1:])
            ps = [{n: next(arrays) for n in ns} for ns in names]
            return period(ps, carry[0], carry[1], i), None

        with jax.named_scope("layers"):
            (x, state), _ = jax.lax.scan(
                step, (x, state),
                (jnp.arange(self._n_periods, dtype=jnp.int32),
                 *(w[f"p{j}_{n}"] for j, ns in enumerate(names) for n in ns)))
        return x, state

    def _head(self, w, x):
        return jnp.dot(rms_norm(x, w["out_norm"], self.config.rms_norm_eps),
                       w["lm_head"].T, preferred_element_type=jnp.float32)

    # -- plain forward ------------------------------------------------------
    def forward(self, input_ids: Tensor) -> Tensor:
        """``[B, S]`` ids -> ``[B, S, V]`` float32 logits: every sequence from
        position 0, no cache; the delta rule through the XLA chunk loops."""
        from ..ops.pallas_kernels.kda import kda

        cfg = self.config
        names = self._names
        group = cfg.num_attention_heads // cfg.num_key_value_heads
        scale = np.float32(1.0 / np.sqrt(cfg.head_dim))
        hl, dl = cfg.linear_heads, cfg.linear_head_dim

        def one(ids, *arrays):
            w = dict(zip(names, arrays))
            s = ids.shape[0]
            i32 = jnp.int32
            runs = (jnp.zeros((1,), i32), jnp.full((1,), s, i32),
                    jnp.zeros((1,), i32), jnp.zeros((1,), i32),
                    jnp.ones((1,), i32), jnp.ones((1,), i32))

            def conv(a, state, layer):
                pad = jnp.pad(a, ((3, 0), (0, 0)))
                return (pad[2:s + 2], pad[1:s + 1], pad[:s]), state

            def rule(q, k, v, g, beta, state, layer):
                o, _ = kda(q, k, v, g, beta,
                           jnp.zeros((1, hl, dl, dl), jnp.float32), runs)
                return o, state

            def attend(q, k, v, state, layer):
                k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
                sc = jnp.einsum("qhd,khd->hqk", q, k,
                                preferred_element_type=jnp.float32) * scale
                sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -1e30)
                att = jax.nn.softmax(sc, axis=-1).astype(v.dtype)
                return jnp.einsum("hqk,khd->qhd", att, v), state

            cores = {"conv": conv, "rule": rule, "attend": attend,
                     "real": None, "note": lambda state, *_: state}
            with jax.named_scope("embed"):
                x = jnp.take(w["embed"], ids, axis=0)
            x, _ = self._run(w, x, cores, ())
            with jax.named_scope("lm_head"):
                return self._head(w, x)

        def raw(ids, *arrays):      # a sequence at a time: ragged_dot has no vmap
            return jnp.stack([one(ids[b], *arrays) for b in range(ids.shape[0])])

        return dispatch.apply(raw, input_ids, *self._arrays(),
                              op_name="solar_open2_forward")

    # -- ServingEngine paged-cache contract --------------------------------
    def new_paged_kv_cache(self, num_pages: int, page_size: int,
                           dtype: str = "bfloat16", *, num_slots: int,
                           max_run: int):
        from ..serving.paged_cache import SlotStateCache

        cfg = self.config
        hl, dl = cfg.linear_heads, cfg.linear_head_dim
        cache = SlotStateCache(
            num_pages=num_pages, page_size=page_size, num_slots=num_slots,
            max_run=max_run, num_heads=cfg.num_key_value_heads,
            row_dim=cfg.head_dim, attn_layers=self._n_gqa,
            rows={"kda": (self._n_kda, (hl, dl, dl), "float32"),
                  "kda_conv": (self._n_kda,
                               SlotStateCache.tail_slab(3 * 3 * hl * dl), dtype)},
            state_counters=(("kda_runs", "runs"), ("kda_rows", "rows"),
                            ("kda_chunk_rows", "chunk_rows")),
            device_counters=("moe_pairs", "moe_local_pairs",
                             "moe_experts_touched", "moe_expert_load_max"),
            routed_layers=cfg.num_hidden_layers, top_k=cfg.num_experts_per_tok,
            dtype=dtype)
        self._newest_cache = weakref.ref(cache)
        return cache

    def recent_routes(self):
        """What the newest cache's route log holds, or None where no cache
        was made or it is gone."""
        cache = self._newest_cache() if self._newest_cache else None
        return None if cache is None else cache.recent_routes()

    def _paged_lm_logits(self, input_ids, paged_cache, page_tables,
                         positions, ragged_plan=None, out_rows=None,
                         lora=None, slot_state=None):
        """The fused serving step's model part: ``input_ids`` [T, 1] flat
        tokens at ``positions`` [T] with their slots' ``page_tables``
        [T, max_pages], the step's ragged plan and what the cache packed
        beside it (``slot_state``); logits ``[S, 1, V]`` at ``out_rows``.  The
        cache's pools, state rows, counters and route log are updated in
        place (mutation-logged, so donated under ``jit.to_static``)."""
        from .lfm2 import _fold_counts

        if lora is not None:
            raise NotImplementedError(
                "SolarOpen2ForCausalLM: " + self.serving_unsupported["lora"])
        if ragged_plan is None or out_rows is None or slot_state is None:
            raise ValueError(
                "SolarOpen2ForCausalLM serves through the fused ragged step "
                "of an engine that packs its cache's slot state: "
                "_paged_lm_logits needs ragged_plan, out_rows and slot_state")
        cfg = self.config
        names = self._names
        plan = tuple(ragged_plan)
        n_plan = len(plan)
        fields = list(slot_state)
        cache = paged_cache
        pool_names = ("k", "v", "kda", "kda_conv")
        held = tuple(getattr(cache, n) for n in pool_names) + (
            cache.counters, cache.routes, cache.route_cursor)
        n_pools = len(pool_names)
        limb = int(cache.LIMB)
        d, top_k = cfg.head_dim, cfg.num_experts_per_tok
        n_layers, e = cfg.num_hidden_layers, cfg.n_routed_experts

        def flat(a):        # a stacked pool viewed over its layers
            return a.reshape((-1,) + a.shape[2:])

        def raw(ids, pos, tbl, rows_out, *rest):
            planr, rest = rest[:n_plan], rest[n_plan:]
            extra, rest = dict(zip(fields, rest)), rest[len(fields):]
            pools, (limbs, log, cursor) = rest[:n_pools], rest[n_pools:n_pools + 3]
            w = dict(zip(names, rest[n_pools + 3:]))
            pos, tbl = pos.astype(jnp.int32), tbl.astype(jnp.int32)
            step = _SlotStep(pos, tbl, planr, extra, cache)
            t = pos.shape[0]
            if t > log.shape[1]:
                raise ValueError(f"a step of {t} rows, a route log of "
                                 f"{log.shape[1]}")

            def conv(a, state, layer):
                prev, conv_ = step.predecessors(a, state["kda_conv"], layer)
                return prev, {**state, "kda_conv": conv_}

            def rule(q, k, v, g, beta, state, layer):
                o, pool = step.rule(q, k, v, g, beta, state["kda"], layer)
                return o, {**state, "kda": pool}

            def attend(q, k, v, state, layer):
                out, pk, pv = step.attend(q, k, v, (state["k"], state["v"]),
                                          layer, d)
                return out, {**state, "k": pk, "v": pv}

            real_rows = jnp.sum(step.real, dtype=jnp.int32)

            def note(state, counts, sel, layer):
                zero = jnp.int32(0)
                picks = jax.lax.dynamic_update_slice(
                    state["picks"], sel.T[None],
                    (jnp.asarray(layer, jnp.int32), zero, zero))
                inc = jnp.stack([top_k * real_rows, *counts])
                return {**state, "inc": state["inc"] + inc, "picks": picks}

            cores = {"conv": conv, "rule": rule, "attend": attend,
                     "real": step.real, "note": note}
            with jax.named_scope("embed"):
                x = jnp.take(w["embed"], ids.reshape(-1), axis=0)
            state = {n: flat(p) for n, p in zip(pool_names, pools)}
            state.update(inc=jnp.zeros((limbs.shape[0],), jnp.int32),
                         picks=jnp.full((n_layers, top_k, t), e, jnp.int32))
            x, state = self._run(w, x, cores, state)
            with jax.named_scope("moe.route"):
                # the step's rows go into the log side by side, wrapping to
                # its start when they would run past its end
                start = jnp.where(cursor[0] + t > log.shape[1], 0, cursor[0])
                log = jax.lax.dynamic_update_slice(
                    log, jnp.concatenate([
                        state["picks"].reshape(n_layers * top_k, t),
                        pos[None], jnp.where(step.real, step.write_page, 0)[None]]),
                    (jnp.int32(0), start))
            with jax.named_scope("lm_head"):
                logits = self._head(w, jnp.take(x, rows_out, axis=0))
            return (logits[:, None, :],
                    *(state[n].reshape(p.shape) for n, p in zip(pool_names, pools)),
                    _fold_counts(limbs, state["inc"], limb), log, (start + t)[None])

        results = dispatch.apply(
            raw, input_ids, positions, page_tables, out_rows, *plan,
            *(slot_state[f] for f in fields), *held, *self._arrays(),
            op_name="solar_open2_paged_step")
        for tensor, new in zip(held, results[1:]):
            tensor._set_value(new._value)
        return results[0]
