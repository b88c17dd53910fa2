"""The decoder-hybrid-decoder (``paddle_tpu/models/phi4flash.py``) against its
plain reference (``benchmark/reference/phi4flash_ref.py``) at a small size:
hidden 64, 4 query / 2 K/V heads of 16, window 8, state-space width 128 with
state 4 and step rank 4, feed-forward 160, 8 layers (every kind is there),
vocabulary 512, float32 on both sides, tolerance 1e-4.

- the program's full forward;
- through ``ServingEngine``: prefill in chunks then decode, every emitted
  token against the reference's full forward, past the window and around the
  ring more than twice; a chunk that straddles a page; requests of different
  lengths in one step; a slot seated again after a longer request;
- the state that lives in the slot: a step dispatched twice leaves what one
  completed step leaves, padding rows touch no row but the sink, the cross
  layers write no pool;
- the recurrence's two forms (the Mosaic launch under ``interpret=True`` and
  the loop over rows) on one run list; a TPU whose state pool the launch
  cannot take notes the fallback;
- every serving mode the model's paged path lacks is refused, typed.
"""
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models import Phi4FlashConfig, Phi4FlashForCausalLM, phi4flash_tiny
from paddle_tpu.serving import (
    ServingEngine,
    SpeculativeEngine,
    UnsupportedServingMode,
)
from paddle_tpu.serving.paged_cache import SlotStateCache

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.configs import phi4flash_builder  # noqa: E402
from benchmark.reference import phi4flash_ref  # noqa: E402

TOL = 1e-4
PAGE = 16


def _model(seed=11, **kw):
    pt.seed(seed)
    m = Phi4FlashForCausalLM(phi4flash_tiny(**kw))
    m.eval()
    return m


@pytest.fixture(scope="module")
def model():
    return _model()


def _ids(model, n, seed=0):
    return np.random.default_rng(seed).integers(
        0, model.config.vocab_size, n, dtype=np.int64)


def _reference(model, ids):
    """The reference's logits [S, V] for one sequence."""
    return np.asarray(phi4flash_ref.logits(
        phi4flash_builder.reference_weights(model), jnp.asarray(ids)[None],
        **phi4flash_builder.reference_kwargs(model)))[0]


def _engine(model, **kw):
    kw = {"num_slots": 4, "page_size": PAGE, "max_context": 256,
          "prefill_token_budget": 5, "cache_dtype": "float32", **kw}
    return ServingEngine(model, **kw)


def _served(engine, prompts, new_tokens):
    reqs = [engine.submit(p, new_tokens) for p in prompts]
    engine.run_until_idle()
    assert all(r.state == "DONE" for r in reqs)
    return [list(r.tokens) for r in reqs]


def _gap(model, prompt, tokens):
    """How far under its position's maximum the reference's full forward over
    prompt + emitted tokens puts each emitted token (0: it is the argmax)."""
    ids = np.concatenate([prompt, np.asarray(tokens[:-1], np.int64)])
    rows = _reference(model, ids)[len(prompt) - 1:]
    return float((rows.max(-1) - rows[np.arange(len(tokens)), tokens]).max())


def test_the_layer_kinds_follow_the_two_halves():
    cfg = Phi4FlashConfig()
    assert (cfg.memory_layer, cfg.self_periods, cfg.cross_periods) == (16, 8, 7)
    assert (cfg.d_inner, cfg.mamba_dt_rank, cfg.head_dim) == (5120, 160, 64)
    kinds = [w["kind"] for w in phi4flash_builder.reference_weights(
        _model(num_hidden_layers=12))["layers"]]
    assert kinds == ["ssm", "attn"] * 4 + ["gmu", "cross"] * 2


@pytest.mark.parametrize("field,value", [
    ("mb_per_layer", 1), ("mlp_bias", True), ("tie_word_embeddings", False),
    ("num_hidden_layers", 10), ("mamba_d_conv", 3), ("num_key_value_heads", 1)])
def test_a_published_switch_is_held_to_what_is_written(field, value):
    with pytest.raises(ValueError):
        phi4flash_tiny(**{field: value})


@pytest.mark.parametrize("layers", [8, 12])
def test_full_forward_matches_the_reference(layers):
    m = _model(seed=3, num_hidden_layers=layers)
    ids = np.stack([_ids(m, 40, seed=1), _ids(m, 40, seed=2)])
    out = m(pt.to_tensor(ids, dtype="int64")).numpy()
    for row, got in zip(ids, out):
        assert np.abs(got - _reference(m, row)).max() <= TOL


@pytest.mark.parametrize("page,budget,prompt_len,new", [
    (16, 5, 37, 70),       # a chunk straddles a page; three times round the ring
    (16, 16, 16, 100),     # a chunk is a page
    (8, 3, 10, 60),
    (128, 16, 330, 300),   # the cell's page: twice round a ring of two pages
])
def test_chunked_prefill_then_decode_matches_the_reference(model, page, budget,
                                                           prompt_len, new):
    prompt = _ids(model, prompt_len, seed=prompt_len)
    eng = _engine(model, num_slots=2, page_size=page, max_context=1024,
                  prefill_token_budget=budget)
    ring = eng.cache.ring_pages * page
    (tokens,) = _served(eng, [prompt], new)
    m = eng.metrics()
    eng.close()
    assert prompt_len + new > 2 * ring > 2 * model.config.sliding_window
    assert _gap(model, prompt, tokens) <= TOL
    assert m["ssm_rows"] == m["cross_rows"] == prompt_len + new - 1
    assert m["ssm_runs"] == -(-prompt_len // budget) + new - 1
    assert 0 < m["window_work_items"] < m["work_items"]


def test_requests_of_different_lengths_share_a_step(model):
    """Four requests of unequal lengths through four slots: runs of several
    slots share a step's flat axis, and a row's predecessor, its run's state
    and its ring are never another slot's."""
    prompts = [_ids(model, n, seed=20 + n) for n in (3, 29, 47, 12)]
    eng = _engine(model)
    together = _served(eng, prompts, 24)
    eng.close()
    for p, toks in zip(prompts, together):
        assert _gap(model, p, toks) <= TOL


def test_a_slot_seated_again_after_a_longer_request_starts_from_zero(model):
    """One slot: the state rows and the ring a long request left come back to
    a short one, whose position 0 starts from zero state and an empty ring."""
    long_, short = _ids(model, 90, seed=7), _ids(model, 6, seed=8)
    eng = _engine(model, num_slots=1)
    _served(eng, [long_], 30)
    (tokens,) = _served(eng, [short], 40)
    eng.close()
    assert _gap(model, short, tokens) <= TOL
    eng = _engine(model, num_slots=1)
    assert _served(eng, [short], 40) == [tokens]
    eng.close()


def _state(engine):
    c = engine.cache
    return [np.asarray(t._value) for t in (c.ssm, c.conv, c.k, c.v, c.ring_k, c.ring_v)]


def test_a_step_dispatched_twice_leaves_what_one_completed_step_leaves(model):
    """The engine retries a failed step once.  Here the first dispatch RUNS
    (the pools are written) and then fails: the retry must read the state the
    last harvested step left, not the one the failed dispatch advanced."""
    prompt = _ids(model, 23, seed=5)
    clean = _engine(model, num_slots=2)
    (want,) = _served(clean, [prompt], 12)
    faulty = _engine(model, num_slots=2)
    body, calls = faulty._enqueue_thunk, []

    def fails_after_running(fused, inputs, cancelled, extra_dev=()):
        out = body(fused, inputs, cancelled, extra_dev)
        calls.append(len(calls))
        if len(calls) in (3, 9):        # a prefill step and a decode step,
            # each enqueued behind a step that is still unread
            raise RuntimeError("lost after the program ran")
        return out

    faulty._enqueue_thunk = fails_after_running
    (got,) = _served(faulty, [prompt], 12)
    assert faulty.metrics()["step_retries"] == 2
    assert got == want
    for i, (a, b) in enumerate(zip(_state(clean), _state(faulty))):
        if i < 2:
            # the state rows: what each engine's slot holds as its newest
            # (the row its next step would read)
            a = a[:, clean.cache.state_rows(0)[0]]
            b = b[:, faulty.cache.state_rows(0)[0]]
        np.testing.assert_array_equal(a, b)
    clean.close()
    faulty.close()


def test_padding_rows_touch_no_state_row_but_the_sink(model):
    """One short request in an engine of four slots and a step of 9 rows: the
    rows of the state pools and the ring pages that belong to the other slots
    hold what they held (zero), whatever the padding rows computed."""
    eng = _engine(model)
    _served(eng, [_ids(model, 7, seed=1)], 3)
    c = eng.cache
    ssm, conv, _, _, ring_k, ring_v = _state(eng)
    r = c.ring_pages
    eng.close()
    for pool in (ssm, conv):
        assert np.abs(pool[:, 1:3]).max() > 0          # slot 0's two rows
        assert not pool[:, 3:].any()
    for ring in (ring_k, ring_v):
        assert np.abs(ring[:, 1:1 + r]).max() > 0
        assert not ring[:, 1 + r:].any()


def test_the_cross_layers_read_the_shared_pool_and_write_none(model):
    """Two models that differ in every cross-decoder weight and in nothing
    else leave the same bytes in every pool: no layer after the full-attention
    layer writes one (and the logits do differ, so they ran)."""
    other = _model()
    for name in other._names:
        if name.startswith("cross"):
            p = getattr(other, name)
            p._set_value(p._value * 1.5 + 0.01)
    prompt = _ids(model, 20, seed=9)
    states, tokens = [], []
    for m in (model, other):
        eng = _engine(m, prefill_token_budget=32)
        tokens.append(_served(eng, [prompt], 1))
        states.append(_state(eng))
        eng.close()
    for a, b in zip(*states):
        np.testing.assert_array_equal(a, b)
    ids = pt.to_tensor(prompt[None], dtype="int64")
    assert np.abs(model(ids).numpy() - other(ids).numpy()).max() > 1e-3


def test_the_ring_holds_a_window_and_a_run(model):
    cache = model.new_paged_kv_cache(9, 128, dtype="float32", num_slots=3, max_run=256)
    assert isinstance(cache, SlotStateCache)
    w = model.config.sliding_window
    assert cache.ring_pages == -(-(w - 1 + 256) // 128) + 1
    assert tuple(cache.ring_k.shape[:2]) == (model.config.self_periods, 3 * cache.ring_pages + 1)
    assert tuple(cache.ssm.shape[:2]) == tuple(cache.conv.shape[:2]) == (model.config.self_periods + 1, 7)
    assert list(cache.ring_table(1, 6)) == [1 + cache.ring_pages + j % cache.ring_pages
                                            for j in range(6)]
    assert cache.state_rows(2) == (5, 6)
    cache.commit_step([(2, 0, 4)], window_items=3, window_wide_items=1)
    assert cache.state_rows(2) == (6, 5) and cache.state_rows(1) == (3, 4)
    assert cache.counts() == {"window_work_items": 3, "window_wide_items": 1, "ssm_runs": 1,
                              "ssm_rows": 4, "cross_rows": 4}
    # the cell's geometry: 7 ring pages a slot and window layer
    big = Phi4FlashConfig()
    assert -(-(big.sliding_window - 1 + 256) // 128) + 1 == 7


def _mp2_mesh():
    from paddle_tpu.distributed import serving_mesh

    return serving_mesh.build_serving_mesh(1, 2)


@pytest.mark.parametrize("mode,build", [
    ("prefix_cache", lambda m: ServingEngine(m, page_size=PAGE, max_context=64,
                                             prefix_cache=True)),
    ("mp", lambda m: ServingEngine(m, page_size=PAGE, max_context=64, mesh=_mp2_mesh())),
    ("lora", lambda m: ServingEngine(m, page_size=PAGE, max_context=64, lora=object())),
    ("kv_int8", lambda m: ServingEngine(m, page_size=PAGE, max_context=64, kv_dtype="int8")),
    ("kv_int8", lambda m: ServingEngine(m, page_size=PAGE, max_context=64, cache_dtype="int8")),
    ("weight_int8", lambda m: ServingEngine(m, page_size=PAGE, max_context=64,
                                            weight_dtype="int8")),
    ("speculative", lambda m: SpeculativeEngine(m, m, page_size=PAGE, max_context=64)),
    ("disagg", lambda m: ServingEngine(m, page_size=PAGE, max_context=64, role="prefill")),
    ("disagg", lambda m: ServingEngine(m, page_size=PAGE, max_context=64, role="decode")),
])
def test_a_mode_the_paged_path_lacks_is_refused_typed(model, mode, build):
    with pytest.raises(UnsupportedServingMode, match=mode) as e:
        build(model)
    assert model.serving_unsupported[mode] in str(e.value)
    assert not hasattr(model, "_weight_int8")


def test_the_cache_refuses_an_int8_pool_and_guards_its_row_index(model):
    with pytest.raises(ValueError, match="int8"):
        model.new_paged_kv_cache(9, PAGE, dtype="int8", num_slots=2, max_run=5)
    sizes = dict(page_size=128, max_run=256, num_heads=10, row_dim=128, window=512,
                 ssm_layers=9, d_inner=5120, d_state=16, conv_taps=3)
    with pytest.raises(ValueError, match="row index is int32"):    # the ring's rows
        SlotStateCache(num_pages=9, num_slots=2 ** 14, window_layers=32, **sizes)
    with pytest.raises(ValueError, match="row index is int32"):    # the paged pool's
        SlotStateCache(num_pages=2 ** 21, num_slots=2, window_layers=8, **sizes)


def test_the_paged_step_needs_what_the_engine_packs(model):
    cache = model.new_paged_kv_cache(9, PAGE, dtype="float32", num_slots=2, max_run=5)
    with pytest.raises(ValueError, match="slot_state"):
        model._paged_lm_logits(None, cache, None, None, ragged_plan=(), out_rows=0)


_SCAN_RUNS = {
    # (first, count, src, dst, fresh) a run; a step of 16 rows, 4 run slots
    "decode_rows": [(0, 1, 1, 2, False), (1, 1, 3, 4, False),
                    (2, 1, 6, 5, True)],
    "a_chunk_between": [(0, 1, 1, 2, False), (1, 7, 4, 3, True),
                        (8, 3, 5, 6, False)],
    "one_fresh_chunk": [(0, 16, 7, 8, True)],
    "a_full_step": [(0, 5, 2, 1, False), (5, 5, 3, 4, True),
                    (10, 5, 5, 6, False), (15, 1, 8, 7, False)],
}


@pytest.mark.parametrize("case", list(_SCAN_RUNS))
def test_the_scan_launch_is_the_loop_over_rows(case):
    """``_ssm_scan_kernel`` (interpreted) and ``_xla_scan`` read one run list:
    outputs agree to float32 rounding, padding rows read zero, and of the
    pool both change the runs' ``run_dst`` rows alone, to the same bits."""
    import jax

    from paddle_tpu.ops.pallas_kernels import selective_scan as ss

    rng = np.random.default_rng(len(case))
    t, n_state, blocks, rows, slots = 16, 4, 2, 9, 4
    ch = blocks * 8 * 128
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (t, ch)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(t, ch)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(t, n_state)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(t, n_state)), jnp.float32)
    a_t = -jnp.asarray(rng.uniform(0.5, 2.0, (n_state, ch)), jnp.float32)
    d = jnp.asarray(rng.normal(size=(ch,)), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(rows, blocks, n_state, 8, 128)),
                       jnp.float32)
    listed = _SCAN_RUNS[case]
    cols = list(zip(*(listed + [(0, 0, 0, 0, False)] * (slots - len(listed)))))
    runs = tuple(jnp.asarray(col, jnp.int32) for col in cols[:4]) + (
        jnp.asarray(cols[4]), jnp.asarray([len(listed)], jnp.int32))
    y_k, pool_k = jax.jit(lambda *a: ss.selective_scan(*a, interpret=True))(
        dt, x, b, c, a_t, d, pool, runs)
    y_l, pool_l = jax.jit(ss._xla_scan)(dt, x, b, c, a_t, d, pool, runs)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_l),
                               rtol=1e-5, atol=1e-5)
    real = sum(r[1] for r in listed)
    assert not np.asarray(y_k[real:]).any() and not np.asarray(y_l[real:]).any()
    np.testing.assert_array_equal(np.asarray(pool_k), np.asarray(pool_l))
    changed = [i for i in range(rows)
               if not np.array_equal(np.asarray(pool_k[i]), np.asarray(pool[i]))]
    assert changed == sorted(r[3] for r in listed)
    # a fresh run starts from zero whatever its source row held
    fresh = [r for r in listed if r[4]]
    if fresh:
        other = pool.at[fresh[0][2]].set(7.0)
        _, again = jax.jit(ss._xla_scan)(dt, x, b, c, a_t, d, other, runs)
        np.testing.assert_array_equal(np.asarray(again[fresh[0][3]]),
                                      np.asarray(pool_l[fresh[0][3]]))


def test_a_tpu_that_cannot_take_the_scan_launch_notes_the_fallback(monkeypatch):
    """Off a TPU the loop is the path and nothing is noted; on one, a state
    pool of another tile than (8, 128) takes the loop AND says so where the
    benchmark's ``no_fallback_noted`` reads."""
    from paddle_tpu.analysis import codes
    from paddle_tpu.ops.pallas_kernels import ragged_paged_attention as ra
    from paddle_tpu.ops.pallas_kernels import selective_scan as ss

    monkeypatch.setattr(codes, "_SEEN_FALLBACKS", set())
    assert not ss.scan_runs_kernel((9, 5, 16, 8, 128))
    assert not codes._SEEN_FALLBACKS
    monkeypatch.setattr(ra, "_on_tpu", lambda: True)
    assert ss.scan_runs_kernel((9, 5, 16, 8, 128))
    assert not codes._SEEN_FALLBACKS
    assert not ss.scan_runs_kernel((9, 1, 4, 4, 32))
    (noted,) = codes._SEEN_FALLBACKS
    assert "selective_scan" in noted and "(4, 32)" in noted
