"""A decoder of sliding-window and full grouped-query attention layers whose
every feed-forward is 64 small routed experts picked top-8 by a softmax
(``model_type: mellum``; JetBrains Mellum2).  The stacked, serving form.

For a token's hidden state ``x`` at position ``t`` (``N`` an RMSNorm, ``d``
the head size, ``G`` query heads a K/V head)::

    x = Emb[id]
    for each layer (its kind from layer_types):
      u = N(x; g_attn)
      q, k, v = u @ Wq, u @ Wk, u @ Wv          (no bias, no q/k norm)
      q, k = rot(q, t), rot(k, t)               rotate-half: dimension i pairs
          with i + d/2 at angle t * w_i;  sliding: w_i = theta^(-2i/d);
          full: YaRN's w_i, cos and sin both times attention_factor
      o = softmax(q k^T / sqrt(d) + mask) v     K/V head j serving query
          heads G*j .. G*j + G - 1;  mask: key s seen from t iff s <= t,
          and on a sliding layer t - s < sliding_window
      x = x + o @ Wo;  u = N(x; g_ffn)
      s = softmax(f32(u) @ Wr);  sel = top_k(s);  p = s[sel] / sum(s[sel])
      x = x + sum_{e in sel} p_e (silu(u @ W1[e]) * (u @ W3[e])) @ W2[e]
    logits = N(x; g_out) @ W_head^T

``benchmark/reference/mellum2_ref.py`` is the same mathematics in plain
float32, written apart from this file.

How it runs.  The layers repeat with a period (sliding, sliding, sliding,
full as published); the layer loop is ONE ``lax.scan`` over whole periods
whose body is a period's layers written out.  The serving step carries
``serving.paged_cache.SlotStateCache`` as donated buffers viewed flat over
their layers: the full layers' K and V paged on the ledger, the window
layers' in each slot's RING of pages, which the allocator never sees, and
NO state rows (``rows={}``): nothing here is recurrent.  A step holds two
rotary tables, the plain one of the window layers and YaRN's of the full
ones (a constant of the config), and two work lists, the full layers' over
every page of a row's context and the window layers' over the ring pages
its window reaches (``decoder_ops.SlotAttention``).  Routing is
``lfm2._moe``'s with the softmax form of its router.
"""
from __future__ import annotations

import weakref
from dataclasses import dataclass, field
from types import SimpleNamespace
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dtype import to_jax_dtype
from ..nn.layer import Layer
from ..ops import dispatch
from ..tensor import Parameter, Tensor
from .decoder_ops import SlotAttention, mm, rms_norm, rope, yarn_frequencies

__all__ = ["Mellum2Config", "Mellum2ForCausalLM", "mellum2_tiny",
           "PUBLISHED_LAYER_TYPES", "PUBLISHED_ROPE_PARAMETERS"]

SLIDING, FULL = "sliding_attention", "full_attention"
#: ``layer_types`` of the published 28-layer model
PUBLISHED_LAYER_TYPES = (SLIDING, SLIDING, SLIDING, FULL) * 7
#: ``rope_parameters`` as published: a section a layer kind
PUBLISHED_ROPE_PARAMETERS = {
    FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
           "original_max_position_embeddings": 8192, "beta_fast": 32,
           "beta_slow": 1, "attention_factor": 1.2772588722239782},
    SLIDING: {"rope_type": "default", "rope_theta": 500000},
}
#: how many steps' rows the route log holds: a whole context's prefill
#: chunks and the first tokens of its answer, for a comparison of ONE
#: sequence against the reference (``recent_routes``)
ROUTE_STEPS = 64


@dataclass
class Mellum2Config:
    """The published ``config.json``'s keys under their own names."""

    vocab_size: int = 98304
    hidden_size: int = 2304
    intermediate_size: int = 7168       # no layer is dense (mlp_layer_types)
    moe_intermediate_size: int = 896
    num_hidden_layers: int = 28
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 64
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    # the first num_hidden_layers entries are the model's
    layer_types: Sequence[str] = PUBLISHED_LAYER_TYPES
    mlp_layer_types: Sequence[str] = ("sparse",) * 28
    sliding_window: int = 1024
    use_sliding_window: bool = True
    max_window_layers: int = 0
    rope_parameters: dict = field(
        default_factory=lambda: {k: dict(v) for k, v in
                                 PUBLISHED_ROPE_PARAMETERS.items()})
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 131072
    attention_bias: bool = False
    hidden_act: str = "silu"
    tie_word_embeddings: bool = False
    model_type: str = "mellum"
    initializer_range: float = 0.02
    dtype: str = "bfloat16"             # the weights' storage dtype

    def __post_init__(self):
        n = self.num_hidden_layers
        self.layer_types = tuple(self.layer_types)[:n]
        self.mlp_layer_types = tuple(self.mlp_layer_types)[:n]
        if len(self.layer_types) != n or len(self.mlp_layer_types) != n:
            raise ValueError(f"layer_types / mlp_layer_types name "
                             f"{len(self.layer_types)} / "
                             f"{len(self.mlp_layer_types)} layers of {n}")
        unknown = set(self.layer_types) - {SLIDING, FULL}
        if unknown:
            raise ValueError(f"layer_types holds {sorted(unknown)}: only "
                             f"{SLIDING!r} and {FULL!r} are written here")
        if set(self.mlp_layer_types) != {"sparse"}:
            raise ValueError("every layer's feed-forward is routed as "
                             f"published, got {set(self.mlp_layer_types)}")
        if (self.attention_bias or self.hidden_act != "silu"
                or self.tie_word_embeddings or not self.norm_topk_prob
                or not self.use_sliding_window or self.max_window_layers):
            raise ValueError("written as published: no attention bias, silu, "
                             "an untied head, norm_topk_prob, the window on "
                             "the layers layer_types names")
        rope_ = self.rope_parameters
        if (rope_[SLIDING]["rope_type"] != "default"
                or rope_[FULL]["rope_type"] != "yarn"):
            raise ValueError("written for plain rotary on the sliding layers "
                             f"and YaRN on the full ones, got {rope_}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(f"{self.num_attention_heads} query heads over "
                             f"{self.num_key_value_heads} K/V heads")
        if n % self.period:
            raise ValueError(f"layers {self.layer_types} are no whole periods")

    @property
    def period(self) -> int:
        kinds = self.layer_types
        return next(p for p in range(1, len(kinds) + 1)
                    if all(kinds[i] == kinds[i % p] for i in range(len(kinds))))

    @property
    def theta(self) -> float:
        """The sliding layers' rotary base."""
        return float(self.rope_parameters[SLIDING]["rope_theta"])

    @property
    def yarn(self):
        """The full layers' rotary table: ``(frequencies [d/2], attention
        factor, (low, high))`` (``decoder_ops.yarn_frequencies``)."""
        p = self.rope_parameters[FULL]
        return yarn_frequencies(
            self.head_dim, float(p["rope_theta"]), factor=float(p["factor"]),
            original_max_position_embeddings=int(
                p["original_max_position_embeddings"]),
            beta_fast=float(p["beta_fast"]), beta_slow=float(p["beta_slow"]),
            attention_factor=float(p["attention_factor"]))


def mellum2_tiny(**kw) -> Mellum2Config:
    """The CPU tests' size: one period, G = 8 (16 query heads over 2), a
    window of 8, 8 experts top-2, YaRN over an original context of 16 with
    factor 4, float32."""
    defaults = dict(vocab_size=256, hidden_size=64, moe_intermediate_size=32,
                    num_hidden_layers=4, num_attention_heads=16,
                    num_key_value_heads=2, head_dim=16, num_experts=8,
                    num_experts_per_tok=2, sliding_window=8,
                    rope_parameters={
                        FULL: {"rope_type": "yarn", "rope_theta": 10000,
                               "factor": 4,
                               "original_max_position_embeddings": 16,
                               "beta_fast": 32, "beta_slow": 1,
                               "attention_factor": 1.1386294361119891},
                        SLIDING: {"rope_type": "default", "rope_theta": 10000}},
                    max_position_embeddings=4096, dtype="float32")
    defaults.update(kw)
    return Mellum2Config(**defaults)


def _shapes(c: Mellum2Config) -> dict:
    """The parameter shapes of one layer; float32 ones end in ``:f32``."""
    h, d = c.hidden_size, c.head_dim
    return {"ln_attn": (h,), "ln_ffn": (h,),
            "wq": (h, c.num_attention_heads * d),
            "wk": (h, c.num_key_value_heads * d),
            "wv": (h, c.num_key_value_heads * d),
            "wo": (c.num_attention_heads * d, h),
            "router:f32": (h, c.num_experts)}


class Mellum2ForCausalLM(Layer):
    """Embedding + the stacked decoder + an untied head.  ``forward`` is the
    plain full forward over ``[B, S]`` ids; ``new_paged_kv_cache`` /
    ``_paged_lm_logits`` are the paged contract ``ServingEngine`` asks for.

    Parameters are one set a position of the period, ``p<j>_*``, with a
    leading ``[n_periods]`` axis that the scan takes as ``xs``; the experts
    of every layer are ``moe_w1``/``moe_w3`` ``[L * E, H, F]`` and
    ``moe_w2`` ``[L * E, F, H]``, layer ``l``'s at ``l * E``."""

    #: the engine sizes this model's cache by its slots and longest run
    slot_resident_state = True
    #: what ``ServingEngine`` is refused for this model, and why
    serving_unsupported = {
        "prefix_cache": "a shared page holds the full layers' K/V, but the "
                        "window layers' K/V of its positions live in the "
                        "ring of the slot that wrote them",
        "mp": "the ring, the K/V heads and the experts are not sharded "
              "over chips",
        "lora": "no adapter path is written for the grouped-query or expert "
                "projections",
        "kv_int8": "the ring has no scale sidecar",
        "weight_int8": "no int8 form of the expert weights",
        "speculative": "the speculative engine packs no ring work list for "
                       "its verify runs",
        "disagg": "a page hand-off carries the full layers' pages but not "
                  "the window layers' ring",
    }

    def __init__(self, cfg: Mellum2Config):
        super().__init__()
        self.config = cfg
        self._newest_cache = None
        kinds = cfg.layer_types
        self._period = kinds[:cfg.period]
        self._n_periods = cfg.num_hidden_layers // cfg.period
        self._n_full, self._n_win = kinds.count(FULL), kinds.count(SLIDING)
        dt = to_jax_dtype(cfg.dtype)
        std = cfg.initializer_range
        from ..ops.random import default_generator
        from .lfm2 import Lfm2StackedForCausalLM

        def normal(shape, dtype=dt, scale=std):
            return jax.random.normal(default_generator.split(), shape,
                                     dtype) * jnp.asarray(scale, dtype)

        self._names = []

        def param(name, value):
            # not trainable: no backward is written for the grouped product
            # or the paged attention (ROADMAP.md R-M)
            setattr(self, name, Parameter(value, trainable=False))
            self._names.append(name)

        h = cfg.hidden_size
        param("embed", normal((cfg.vocab_size, h)))
        param("out_norm", jnp.ones((h,), dt))
        param("lm_head", normal((cfg.vocab_size, h)))
        lead = (self._n_periods,)
        for j in range(len(self._period)):
            for name, shape in _shapes(cfg).items():
                name, _, f32 = name.partition(":")
                full = lead + shape
                if name.startswith("ln_"):
                    value = jnp.ones(full, dt)
                else:
                    value = normal(full, jnp.float32 if f32 else dt)
                param(f"p{j}_{name}", value)
        fm, e = cfg.moe_intermediate_size, cfg.num_experts
        for name, shape in (("moe_w1", (h, fm)), ("moe_w3", (h, fm)),
                            ("moe_w2", (fm, h))):
            param(name, Lfm2StackedForCausalLM._stack_of_experts(
                cfg.num_hidden_layers * e, shape, std, dt,
                default_generator.split()))

    def _arrays(self):
        return [getattr(self, n) for n in self._names]

    # -- the layers, over raw arrays --------------------------------------
    def _run(self, w: dict, x, cores, state):
        """The decoder over hidden rows ``x`` [T, H].  ``cores``: ``attend(q,
        k, v, state, kind, layer) -> (out, state)`` (``layer`` counts the
        layers of that kind before this one), ``note(state, counts, sel,
        layer) -> state``, ``positions`` [T] and ``real`` [T] (or None)."""
        from .lfm2 import _moe

        cfg = self.config
        eps = cfg.rms_norm_eps
        hq, hkv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        e = cfg.num_experts
        freq, factor, _ = cfg.yarn
        tables = {SLIDING: None, FULL: (freq, factor)}
        scope = {SLIDING: "attn.window", FULL: "attn.full"}
        router = SimpleNamespace(num_experts_per_tok=cfg.num_experts_per_tok,
                                 num_experts=e)

        def attn_op(p, u, state, kind, layer):
            t = u.shape[0]
            with jax.named_scope("attn.qkv"):
                q = mm(u, p["wq"]).astype(u.dtype).reshape(t, hq, d)
                k = mm(u, p["wk"]).astype(u.dtype).reshape(t, hkv, d)
                v = mm(u, p["wv"]).astype(u.dtype).reshape(t, hkv, d)
            with jax.named_scope(scope[kind]):
                with jax.named_scope("attn.rope"):
                    q = rope(q, cores["positions"], cfg.theta, tables[kind])
                    k = rope(k, cores["positions"], cfg.theta, tables[kind])
                out, state = cores["attend"](q, k, v, state, kind, layer)
                with jax.named_scope("attn.out"):
                    out = mm(out.reshape(t, hq * d).astype(u.dtype), p["wo"])
                    return out.astype(u.dtype), state

        def period(ps, x, state, i):
            seen = {SLIDING: 0, FULL: 0}
            for j, (kind, p) in enumerate(zip(self._period, ps)):
                with jax.named_scope("attn.qkv"):
                    u = rms_norm(x, p["ln_attn"], eps)
                o, state = attn_op(p, u, state, kind,
                                   i * self._period.count(kind) + seen[kind])
                seen[kind] += 1
                x = x + o
                layer = i * len(self._period) + j
                with jax.named_scope("moe.route"):
                    u = rms_norm(x, p["ln_ffn"], eps)
                y, counts, sel = _moe(router, u, p["router"], None,
                                      w["moe_w1"], w["moe_w3"], w["moe_w2"],
                                      layer * e, cores["real"],
                                      scoring="softmax")
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
        position 0, no cache."""
        cfg = self.config
        names = self._names
        group = cfg.num_attention_heads // cfg.num_key_value_heads
        scale = np.float32(1.0 / np.sqrt(cfg.head_dim))

        def one(ids, *arrays):
            w = dict(zip(names, arrays))
            s = ids.shape[0]
            at = jnp.arange(s)
            causal = at[None, :] <= at[:, None]
            masks = {FULL: causal,
                     SLIDING: causal & (at[:, None] - at[None, :]
                                        < cfg.sliding_window)}

            def attend(q, k, v, state, kind, layer):
                k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
                sc = jnp.einsum("qhd,khd->hqk", q, k,
                                preferred_element_type=jnp.float32) * scale
                att = jax.nn.softmax(jnp.where(masks[kind], sc, -1e30), axis=-1)
                return jnp.einsum("hqk,khd->qhd", att.astype(v.dtype), v), state

            cores = {"attend": attend, "positions": at, "real": None,
                     "note": lambda state, *_: state}
            with jax.named_scope("embed"):
                x = jnp.take(w["embed"], ids, axis=0)
            x, _ = self._run(w, x, cores, ())
            with jax.named_scope("lm_head"):
                return self._head(w, x)

        def raw(ids, *arrays):      # a sequence at a time: ragged_dot has no vmap
            return jnp.stack([one(ids[b], *arrays) for b in range(ids.shape[0])])

        return dispatch.apply(raw, input_ids, *self._arrays(),
                              op_name="mellum2_forward")

    # -- ServingEngine paged-cache contract --------------------------------
    def new_paged_kv_cache(self, num_pages: int, page_size: int,
                           dtype: str = "bfloat16", *, num_slots: int,
                           max_run: int):
        from ..serving.paged_cache import SlotStateCache

        cfg = self.config
        cache = SlotStateCache(
            num_pages=num_pages, page_size=page_size, num_slots=num_slots,
            max_run=max_run, num_heads=cfg.num_key_value_heads,
            row_dim=cfg.head_dim, attn_layers=self._n_full,
            window=cfg.sliding_window, window_layers=self._n_win, rows={},
            device_counters=("moe_assignments", "moe_experts_touched",
                             "moe_expert_load_max"),
            routed_layers=cfg.num_hidden_layers, top_k=cfg.num_experts_per_tok,
            route_rows=ROUTE_STEPS * (num_slots + max_run), dtype=dtype)
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
        beside it (``slot_state``: the ring's work list, each row's slot);
        logits ``[S, 1, V]`` at ``out_rows``.  The cache's pool, ring,
        counters and route log are updated in place (mutation-logged, so
        donated under ``jit.to_static``)."""
        from .lfm2 import _fold_counts

        if lora is not None:
            raise NotImplementedError(
                "Mellum2ForCausalLM: " + self.serving_unsupported["lora"])
        if ragged_plan is None or out_rows is None or slot_state is None:
            raise ValueError(
                "Mellum2ForCausalLM serves through the fused ragged step of "
                "an engine that packs its cache's slot state: "
                "_paged_lm_logits needs ragged_plan, out_rows and slot_state")
        cfg = self.config
        names = self._names
        plan = tuple(ragged_plan)
        n_plan = len(plan)
        fields = list(slot_state)
        cache = paged_cache
        pool_names = ("k", "v", "ring_k", "ring_v")
        held = tuple(getattr(cache, n) for n in pool_names) + (
            cache.counters, cache.routes, cache.route_cursor)
        n_pools = len(pool_names)
        limb = int(cache.LIMB)
        d, top_k = cfg.head_dim, cfg.num_experts_per_tok
        n_layers, e = cfg.num_hidden_layers, cfg.num_experts
        page = int(cache.page_size)

        def flat(a):        # a stacked pool viewed over its layers
            return a.reshape((-1,) + a.shape[2:])

        def raw(ids, pos, tbl, rows_out, *rest):
            planr, rest = rest[:n_plan], rest[n_plan:]
            extra, rest = dict(zip(fields, rest)), rest[len(fields):]
            pools, (limbs, log, cursor) = rest[:n_pools], rest[n_pools:n_pools + 3]
            w = dict(zip(names, rest[n_pools + 3:]))
            pos, tbl = pos.astype(jnp.int32), tbl.astype(jnp.int32)
            kv = SlotAttention(pos, tbl, planr, extra, cache)
            real = extra["row_slot"] >= 0
            t = pos.shape[0]
            if t > log.shape[1]:
                raise ValueError(f"a step of {t} rows, a route log of "
                                 f"{log.shape[1]}")

            def attend(q, k, v, state, kind, layer):
                if kind == SLIDING:
                    mine, at = ("ring_k", "ring_v"), {"ring_layer": layer}
                else:
                    mine, at = ("k", "v"), {"layer": layer}
                out, pk, pv = kv.attend(q, k, v, (state[mine[0]], state[mine[1]]),
                                        head_dim=d, scope=None, **at)
                return out, {**state, mine[0]: pk, mine[1]: pv}

            def note(state, counts, sel, layer):
                zero = jnp.int32(0)
                picks = jax.lax.dynamic_update_slice(
                    state["picks"], sel.T[None],
                    (jnp.asarray(layer, jnp.int32), zero, zero))
                return {**state, "inc": state["inc"] + counts, "picks": picks}

            cores = {"attend": attend, "note": note, "positions": pos,
                     "real": real}
            with jax.named_scope("embed"):
                x = jnp.take(w["embed"], ids.reshape(-1), axis=0)
            state = {n: flat(p) for n, p in zip(pool_names, pools)}
            state.update(inc=jnp.zeros((limbs.shape[0],), jnp.int32),
                         picks=jnp.full((n_layers, top_k, t), e, jnp.int32))
            x, state = self._run(w, x, cores, state)
            with jax.named_scope("moe.route"):
                # the step's rows go into the log side by side, wrapping to
                # its start when they would run past its end
                write_page = jnp.take_along_axis(
                    tbl, jnp.clip(pos // page, 0, tbl.shape[1] - 1)[:, None],
                    axis=1)[:, 0]
                start = jnp.where(cursor[0] + t > log.shape[1], 0, cursor[0])
                log = jax.lax.dynamic_update_slice(
                    log, jnp.concatenate([
                        state["picks"].reshape(n_layers * top_k, t),
                        pos[None], jnp.where(real, write_page, 0)[None]]),
                    (jnp.int32(0), start))
            with jax.named_scope("lm_head"):
                logits = self._head(w, jnp.take(x, rows_out, axis=0))
            return (logits[:, None, :],
                    *(state[n].reshape(p.shape) for n, p in zip(pool_names, pools)),
                    _fold_counts(limbs, state["inc"], limb), log, (start + t)[None])

        results = dispatch.apply(
            raw, input_ids, positions, page_tables, out_rows, *plan,
            *(slot_state[f] for f in fields), *held, *self._arrays(),
            op_name="mellum2_paged_step")
        for tensor, new in zip(held, results[1:]):
            tensor._set_value(new._value)
        return results[0]
