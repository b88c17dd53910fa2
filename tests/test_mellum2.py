"""The window / full grouped-query decoder with softmax-routed experts
(``model_type: mellum``) at a tiny size on the CPU, float32: G = 8 (16 query
heads over 2), a window of 8, a ring of a few pages, 8 experts top-2, YaRN
over an original context of 16 with factor 4, so that positions pass it.  The
plain forward and the engine's chunked prefill and decode against the plain
reference (``benchmark/reference/mellum2_ref.py``); the window's edge in the
ragged launch over a ring; YaRN's table against its formula; the softmax
router; the cache; the refusals."""
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models import Mellum2Config, Mellum2ForCausalLM, mellum2_tiny
from paddle_tpu.models import decoder_ops
from paddle_tpu.models.mellum2 import FULL, SLIDING
from paddle_tpu.serving import (
    ServingEngine, SpeculativeEngine, UnsupportedServingMode,
)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.configs import mellum2_builder  # noqa: E402
from benchmark.reference import mellum2_ref  # noqa: E402

# float32 program against float32 reference: what is left is the order of
# additions (the ragged kernel's online softmax, the grouped product's
# accumulation), some 1e-7 of logits that spread ~0.2; a mechanism left out
# or changed moves them by 1e-3 and more (test_a_changed_mechanism_is_seen)
TOL = 1e-4
PAGE = 16


def _model(seed=11, **kw):
    pt.seed(seed)
    m = Mellum2ForCausalLM(mellum2_tiny(**kw))
    m.eval()
    return m


@pytest.fixture(scope="module")
def model():
    return _model()


def _ids(model, n, seed=0):
    return np.random.default_rng(seed).integers(
        0, model.config.vocab_size, n, dtype=np.int64)


def _reference(model, ids, **kw):
    kwargs = {**mellum2_builder.reference_kwargs(model), **kw}
    return np.asarray(mellum2_ref.logits(
        mellum2_builder.reference_weights(model), jnp.asarray(ids)[None],
        block=16, **kwargs))[0]


def _engine(model, **kw):
    kw = {"num_slots": 3, "page_size": PAGE, "max_context": 256,
          "prefill_token_budget": 24, "cache_dtype": "float32", **kw}
    return ServingEngine(model, **kw)


def _served(engine, prompts, new_tokens):
    reqs = [engine.submit(p, new_tokens) for p in prompts]
    engine.run_until_idle()
    assert all(r.state == "DONE" for r in reqs)
    return [list(r.tokens) for r in reqs]


def _gap(model, prompt, tokens):
    ids = np.concatenate([prompt, np.asarray(tokens[:-1], np.int64)])
    rows = _reference(model, ids)[len(prompt) - 1:]
    return float((rows.max(-1) - rows[np.arange(len(tokens)), tokens]).max())


def test_the_published_layers_repeat_a_period_of_four():
    cfg = Mellum2Config()
    assert cfg.period == 4 and cfg.layer_types[:4] == (SLIDING,) * 3 + (FULL,)
    assert cfg.layer_types.count(FULL) == 7 and cfg.theta == 500000.0
    cut = Mellum2Config(num_hidden_layers=4)
    assert cut.layer_types == (SLIDING, SLIDING, SLIDING, FULL)
    assert cut.mlp_layer_types == ("sparse",) * 4


def _yarn_by_hand(d, theta, factor, original, fast, slow):
    """transformers' ``_compute_yarn_parameters``, written out again."""
    def corr(rot):
        return d * math.log(original / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low, high = max(math.floor(corr(fast)), 0), min(math.ceil(corr(slow)), d - 1)
    pos_freqs = theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    extra, inter = 1.0 / pos_freqs, 1.0 / (factor * pos_freqs)
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    keep = 1 - ramp                                 # the extrapolation factor
    return inter * (1 - keep) + extra * keep, low, high


def test_yarn_at_the_published_sizes_is_its_formula():
    cfg = Mellum2Config(num_hidden_layers=4)
    freq, factor, (low, high) = cfg.yarn
    assert (low, high) == (18, 35)
    want, wlow, whigh = _yarn_by_hand(128, 500000, 16, 8192, 32, 1)
    assert (wlow, whigh) == (18, 35)
    np.testing.assert_allclose(freq, want, rtol=1e-6)
    base = 500000.0 ** (-np.arange(64) * 2 / 128)
    np.testing.assert_allclose(freq[:19], base[:19], rtol=1e-6)   # below low: kept
    np.testing.assert_allclose(freq[35:], base[35:] / 16, rtol=1e-6)
    # the published attention factor is YaRN's own, 0.1 ln(factor) + 1
    assert factor == 1.2772588722239782 == pytest.approx(0.1 * math.log(16) + 1.0)
    # the reference's table is written apart, and agrees
    w, m = mellum2_ref.rotary(128, cfg.rope_parameters[FULL])
    np.testing.assert_allclose(w, want, rtol=1e-6)
    assert m == factor
    w, m = mellum2_ref.rotary(128, cfg.rope_parameters[SLIDING])
    np.testing.assert_allclose(w, base, rtol=1e-6)
    assert m == 1.0


def test_the_rotary_helper_with_a_yarn_table_scales_cos_and_sin():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(5, 3, 16)), jnp.float32)
    pos = jnp.arange(5) * 7
    freq, factor, _ = mellum2_tiny().yarn
    got = decoder_ops.rope(x, pos, 10000.0, (freq, factor))
    ang = np.asarray(pos, np.float64)[:, None, None] * freq.astype(np.float64)
    a, b = np.asarray(x[..., :8]), np.asarray(x[..., 8:])
    want = factor * np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                                    b * np.cos(ang) + a * np.sin(ang)], axis=-1)
    np.testing.assert_allclose(got, want, atol=1e-5)
    # and without one, the plain frequencies and no factor
    plain = decoder_ops.rope(x, pos, 10000.0)
    w = 10000.0 ** (-np.arange(8) * 2 / 16)
    ang = np.asarray(pos, np.float64)[:, None, None] * w
    want = np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                           b * np.cos(ang) + a * np.sin(ang)], axis=-1)
    np.testing.assert_allclose(plain, want, atol=1e-5)


@pytest.mark.parametrize("field,value", [
    ("attention_bias", True), ("tie_word_embeddings", True),
    ("norm_topk_prob", False), ("use_sliding_window", False),
    ("hidden_act", "gelu"), ("mlp_layer_types", ("dense",) * 4),
    ("layer_types", ("sliding_attention", "chunked_attention") * 2),
    ("num_key_value_heads", 3)])
def test_a_published_switch_is_held_to_what_is_written(field, value):
    with pytest.raises(ValueError):
        mellum2_tiny(**{field: value})


def test_the_rope_sections_are_held_to_their_kinds():
    rope = {FULL: {"rope_type": "default", "rope_theta": 10000},
            SLIDING: {"rope_type": "default", "rope_theta": 10000}}
    with pytest.raises(ValueError, match="YaRN"):
        mellum2_tiny(rope_parameters=rope)


def test_full_forward_matches_the_reference(model):
    """70 positions: past the window of 8 and YaRN's original 16."""
    ids = _ids(model, 70)[None]
    got = np.asarray(model(pt.to_tensor(ids)).numpy())[0]
    want = _reference(model, ids[0])
    assert want.std() > 0.05        # logits that a mechanism would move
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


@pytest.mark.parametrize("budget,prompt_len", [(24, 70), (7, 23), (40, 130)])
def test_chunked_prefill_then_decode_matches_the_reference(model, budget, prompt_len):
    """Prefill in chunks of ``budget`` that cross page and window edges, and
    contexts that wrap each slot's ring (a ring of ceil((8 - 1 + budget) /
    16) + 1 pages), then 12 decode rows: every emitted token is the
    reference's argmax."""
    prompt = _ids(model, prompt_len, seed=budget)
    eng = _engine(model, prefill_token_budget=budget)
    ring = eng.cache.ring_pages
    (tokens,) = _served(eng, [prompt], 12)
    eng.close()
    assert (prompt_len + 12) > ring * PAGE      # the ring has wrapped
    assert _gap(model, prompt, tokens) < TOL


def test_requests_of_different_lengths_share_a_step(model):
    prompts = [_ids(model, n, seed=n) for n in (5, 33, 70)]
    eng = _engine(model, prefill_token_budget=24)
    got = _served(eng, prompts, 6)
    m = eng.metrics()
    eng.close()
    for p, t in zip(prompts, got):
        assert _gap(model, p, t) < TOL
    cfg = model.config
    rows = m["prefill_tokens"] + m["tokens"] - 3    # the last token is never fed back
    assert m["moe_assignments"] == rows * cfg.num_experts_per_tok * cfg.num_hidden_layers
    assert 0 < m["window_work_items"] and 0 < m["work_items"]
    # the fullest expert's rows, a layer and step, between the mean expert's
    # and all the layer's pairs
    assert (m["moe_assignments"] / cfg.num_experts <= m["moe_expert_load_max"]
            <= m["moe_assignments"])
    assert m["moe_experts_touched"] <= cfg.num_experts * cfg.num_hidden_layers * m["fused_steps"]


@pytest.mark.parametrize("control", ["window_plus_one", "window_minus_one",
                                     "plain_rotary_on_full", "no_attention_factor",
                                     "sigmoid_router"])
def test_a_changed_mechanism_is_seen(model, control, monkeypatch):
    """The comparison is tight enough: the reference with one mechanism
    changed parts from the program by far more than TOL."""
    ids = _ids(model, 70)[None]
    got = np.asarray(model(pt.to_tensor(ids)).numpy())[0]
    cfg = model.config
    kw = {}
    if control == "window_plus_one":
        kw["window"] = cfg.sliding_window + 1
    elif control == "window_minus_one":
        kw["window"] = cfg.sliding_window - 1
    elif control in ("plain_rotary_on_full", "no_attention_factor"):
        full = dict(cfg.rope_parameters[FULL])
        if control == "plain_rotary_on_full":
            full = {"rope_type": "default", "rope_theta": full["rope_theta"]}
        else:
            full["attention_factor"] = 1.0
        kw["rope_parameters"] = {**cfg.rope_parameters, FULL: full}
    else:
        def sigmoid_route(u, router, *, top_k, theirs=None, margin=0.0):
            s = jax.nn.sigmoid(u @ router)
            _, sel = jax.lax.top_k(s, top_k)
            p = jnp.take_along_axis(s, sel, axis=-1)
            return sel, p / jnp.sum(p, axis=-1, keepdims=True)

        monkeypatch.setattr(mellum2_ref, "route", sigmoid_route)
    changed = _reference(model, ids[0], **kw)
    assert np.abs(changed - got).max() > 10 * TOL, control


@pytest.mark.parametrize("interpret", [False, True])
def test_the_window_edge_over_a_ring(interpret):
    """With window W a query at position i reads key i - W + 1 and not key
    i - W, through a ring of R pages (page-slot j in ring page j mod R): the
    ragged launch (Pallas interpreter) and its XLA form, at G = 8.  Queries
    are 0 (every visible key weighs the same) and V at position p is the
    unit vector e_p, so an output row is the mean of what its row saw."""
    from paddle_tpu.ops.pallas_kernels import ragged_paged_attention as ra

    page, w, ring, d, g = 16, 8, 3, 128, 8
    base, count = 21, 18                         # a chunk past two pages
    t = count
    table = np.array([1 + j % ring for j in range(8)], np.int32)
    plan, _ = ra.build_ragged_plan(
        [(base, count, table)], token_block=8, page_size=page, t_max=t,
        nb_max=4, wl_max=4 * ring, window=w)
    plan = tuple(jnp.asarray(plan[f]) for f in ra.RAGGED_PLAN_FIELDS)
    k_pool = jnp.zeros((1 + ring, 1, page, d), jnp.float32)
    v_pool = np.zeros((1 + ring, 1, page, d), np.float32)
    for p in range(max(base - w, 0), base + count):
        v_pool[table[p // page], 0, p % page, p] = 1.0
    pos = base + np.arange(t)
    out = ra.ragged_paged_attention(
        jnp.zeros((t, g, d), jnp.float32), k_pool, jnp.asarray(v_pool),
        jnp.asarray(np.tile(table, (t, 1))), jnp.asarray(pos + 1, jnp.int32),
        plan, sm_scale=1.0, window=w, interpret=interpret)
    out = np.asarray(out)
    for r, i in enumerate(pos):
        want = np.zeros(d)
        want[i - w + 1:i + 1] = 1.0 / w
        for h in range(g):
            np.testing.assert_allclose(out[r, h], want, atol=1e-6)
        assert out[r, 0, i - w + 1] > 0 and out[r, 0, i - w] == 0


def test_the_softmax_router_renormalises():
    from types import SimpleNamespace

    from paddle_tpu.models.lfm2 import _route

    rng = np.random.default_rng(4)
    u = jnp.asarray(rng.normal(size=(9, 32)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
    cfg = SimpleNamespace(num_experts_per_tok=4, num_experts=16)
    sel, p = _route(cfg, u, router, None, "softmax")
    s = np.asarray(jax.nn.softmax(np.asarray(u, np.float64) @ np.asarray(router, np.float64)))
    want_sel = np.argsort(-s, axis=-1)[:, :4]
    np.testing.assert_array_equal(np.sort(np.asarray(sel), -1), np.sort(want_sel, -1))
    picked = np.take_along_axis(s, np.asarray(sel), -1)
    np.testing.assert_allclose(p, picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(p).sum(-1), 1.0, rtol=1e-6)
    # the sigmoid form is what it was: the bias selects, an epsilon in the sum
    bias = jnp.asarray(rng.normal(size=(16,)), jnp.float32)
    sel, p = _route(cfg, u, router, bias)
    sg = 1 / (1 + np.exp(-(np.asarray(u, np.float64) @ np.asarray(router, np.float64))))
    picked = np.take_along_axis(sg, np.asarray(sel), -1)
    np.testing.assert_allclose(p, picked / (picked.sum(-1, keepdims=True) + 1e-6),
                               rtol=1e-5)


def test_the_cache_holds_a_ring_and_no_state_rows(model):
    cache = model.new_paged_kv_cache(9, PAGE, dtype="float32", num_slots=3,
                                     max_run=24)
    cfg = model.config
    ring = math.ceil((cfg.sliding_window - 1 + 24) / PAGE) + 1
    assert cache.ring_pages == ring
    assert tuple(cache.ring_k.shape) == (3, 3 * ring + 1, cfg.num_key_value_heads,
                                         PAGE, cfg.head_dim)
    assert tuple(cache.k.shape) == (1, 9, cfg.num_key_value_heads, PAGE, cfg.head_dim)
    assert [n for n in cache._names] == ["k", "v", "ring_k", "ring_v"]
    names = [n for n, _ in cache.pack_fields(t_max=27, nb_max=6, wr_max=9)]
    assert names[:9] == ["win_" + f for f in cache.WINDOW_FIELDS]
    assert names[9:] == ["row_slot", "run_first", "run_count", "run_src",
                         "run_dst", "n_runs"]
    assert set(cache.counts()) == {"window_work_items", "window_wide_items",
                                   "moe_assignments", "moe_experts_touched",
                                   "moe_expert_load_max"}
    # the route log holds the newest steps' rows whole
    assert tuple(cache.routes.shape) == (4 * cfg.num_experts_per_tok + 2, 64 * 27)
    with pytest.raises(ValueError, match="int8"):
        model.new_paged_kv_cache(9, PAGE, dtype="int8", num_slots=2, max_run=5)


def _mp2_mesh():
    from paddle_tpu.distributed import serving_mesh

    return serving_mesh.build_serving_mesh(1, 2)


_KW = dict(page_size=PAGE, max_context=64)


@pytest.mark.parametrize("mode,build", [
    ("prefix_cache", lambda m: ServingEngine(m, prefix_cache=True, **_KW)),
    ("mp", lambda m: ServingEngine(m, mesh=_mp2_mesh(), **_KW)),
    ("lora", lambda m: ServingEngine(m, lora=object(), **_KW)),
    ("kv_int8", lambda m: ServingEngine(m, cache_dtype="int8", **_KW)),
    ("weight_int8", lambda m: ServingEngine(m, weight_dtype="int8", **_KW)),
    ("speculative", lambda m: SpeculativeEngine(m, m, **_KW)),
    ("disagg", lambda m: ServingEngine(m, role="prefill", **_KW)),
])
def test_a_mode_the_paged_path_lacks_is_refused_typed(model, mode, build):
    with pytest.raises(UnsupportedServingMode, match=mode) as e:
        build(model)
    assert model.serving_unsupported[mode] in str(e.value)


def test_every_refusal_has_its_reason(model):
    assert set(model.serving_unsupported) == {
        "mp", "lora", "kv_int8", "weight_int8", "speculative", "disagg",
        "prefix_cache"}
    assert all(len(why) > 20 for why in model.serving_unsupported.values())
