"""The hybrid conv / grouped-query-attention decoder with a routed
feed-forward (``paddle_tpu/models/lfm2.py``) against its plain reference
(``benchmark/reference/lfm2_moe_ref.py``) at a small size: hidden 64, 4 query /
2 K/V heads of 16, 8 experts top-2, 6 layers = 2 dense + one period,
vocabulary 512, float32 on both sides, so routing is identical and the
tolerance is 1e-4.

- the program's full forward;
- the paged path called as the engine calls it: prefill in chunks, then decode,
  logits at every emitted position (a conv tail across a page boundary inside
  a chunk, and across two steps);
- through ``ServingEngine``: the same, two requests sharing a two-page prefix
  with the prefix cache on, a slot seated again after a longer request;
- the expert layer: padding rows reach no expert, no token is dropped when
  every token picks the same experts, the grouped product against its oracle;
- every serving mode the model's paged path lacks is refused, typed.
"""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.jit.api import to_static
from paddle_tpu.models import Lfm2Config, Lfm2StackedForCausalLM, lfm2_tiny
from paddle_tpu.ops.pallas_kernels import ragged_paged_attention as ra
from paddle_tpu.serving import (
    ServingEngine,
    SpeculativeEngine,
    UnsupportedServingMode,
)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.configs import lfm2_builder  # noqa: E402
from benchmark.reference import lfm2_moe_ref  # noqa: E402

TOL = 1e-4
PAGE = 8


@pytest.fixture(scope="module")
def model():
    pt.seed(11)
    m = Lfm2StackedForCausalLM(lfm2_tiny())
    m.eval()
    return m


def _ids(model, n, seed=0):
    return np.random.default_rng(seed).integers(
        0, model.config.vocab_size, n, dtype=np.int64)


def _reference(model, ids):
    """The reference's logits [S, V] for one sequence (jitted here only so
    that its loop over experts is traced once a length)."""
    fn = jax.jit(functools.partial(lfm2_moe_ref.logits,
                                   **lfm2_builder.reference_kwargs(model)))
    return np.asarray(fn(lfm2_builder.reference_weights(model),
                         jnp.asarray(ids)[None]))[0]


def _engine(model, **kw):
    kw = {"num_slots": 4, "page_size": PAGE, "max_context": 64,
          "prefill_token_budget": 5, "prefix_cache": True,
          "cache_dtype": "float32", **kw}
    return ServingEngine(model, **kw)


def test_the_layer_pattern_is_lead_periods_and_remainder():
    lead, period, n, trail = Lfm2Config().segments()
    assert lead == ("conv", "conv")
    assert period == ("full_attention", "conv", "conv", "conv") and n == 9
    assert trail == ("full_attention", "conv")
    assert Lfm2Config(num_hidden_layers=10).segments()[2:] == (2, ())
    assert lfm2_tiny().segments() == (
        ("conv", "conv"), ("full_attention", "conv", "conv", "conv"), 1, ())


@pytest.mark.parametrize("field", [
    {"norm_topk_prob": False}, {"use_expert_bias": False},
    {"routed_scaling_factor": 2.0}, {"conv_L_cache": 4}, {"conv_bias": True}])
def test_a_published_switch_is_held_to_its_published_value(field):
    """The keys stand in the config for the file's round trip; the router and
    the convolution are written for the published values alone."""
    with pytest.raises(ValueError, match="written"):
        lfm2_tiny(**field)


@pytest.mark.parametrize("layers", [6, 8, 10])
def test_full_forward_matches_the_reference(layers):
    """2 dense layers, then one period, a period and a remainder of two, two
    periods: the unrolled loops and the scan agree with the plain loop."""
    pt.seed(5)
    m = Lfm2StackedForCausalLM(lfm2_tiny(num_hidden_layers=layers))
    ids = np.stack([_ids(m, 29, seed=1), _ids(m, 29, seed=2)])
    got = np.asarray(m(pt.to_tensor(ids))._value)
    want = np.asarray(lfm2_moe_ref.logits(
        lfm2_builder.reference_weights(m), jnp.asarray(ids),
        **lfm2_builder.reference_kwargs(m)))
    np.testing.assert_allclose(got, want, rtol=TOL, atol=TOL)


def _paged_logits(model, prompt, n_new, chunk, max_pages=8):
    """The paged contract as the engine calls it, one slot: prefill in chunks
    of ``chunk``, then greedy decode; ``(tokens, logits [n_new, V])`` at every
    emitted position."""
    cache = model.new_paged_kv_cache(max_pages + 1, PAGE, dtype="float32")
    table = np.arange(1, max_pages + 1, dtype=np.int32)     # pages 1..max
    t_max, qb = chunk + 1, 8
    nb_max = 1 + chunk // qb + 1
    wl_max = nb_max * max_pages
    tokens, rows = [], []

    @to_static       # one trace a helper call; the cache's tensors are donated
    def compiled(flat, tables, positions, out_rows, *plan):
        with pt.no_grad():
            return model._paged_lm_logits(flat, cache, tables, positions,
                                          ragged_plan=plan, out_rows=out_rows)

    def step(ids, base):
        n = len(ids)
        plan, _ = ra.build_ragged_plan(
            [(base, n, table)], token_block=qb, page_size=PAGE, t_max=t_max,
            nb_max=nb_max, wl_max=wl_max)
        flat = np.zeros((t_max, 1), np.int64)
        flat[:n, 0] = ids
        tables = np.zeros((t_max, max_pages), np.int32)
        tables[:n] = table
        positions = np.zeros((t_max,), np.int32)
        positions[:n] = base + np.arange(n)
        out = compiled(pt.to_tensor(flat), pt.to_tensor(tables),
                       pt.to_tensor(positions),
                       pt.to_tensor(np.array([n - 1], np.int32)),
                       *(pt.to_tensor(plan[k]) for k in ra.RAGGED_PLAN_FIELDS))
        return np.asarray(out._value)[0, 0]

    done = 0
    while done < len(prompt):
        logits = step(prompt[done:done + chunk], done)
        done += min(chunk, len(prompt) - done)
    for _ in range(n_new):
        rows.append(logits)
        tokens.append(int(logits.argmax()))
        logits = step(np.array([tokens[-1]]), done)
        done += 1
    return tokens, np.stack(rows), cache


@pytest.mark.parametrize("chunk,prompt_len", [(5, 23), (8, 16), (3, 10), (16, 21)])
def test_chunked_prefill_then_decode_matches_the_reference(model, chunk, prompt_len):
    """Pages of 8: a chunk of 5 crosses a page inside a step (positions 5-9)
    and ends pages between steps; a chunk of 8 ends every page with the step;
    decode crosses pages one token a step.  Logits at every emitted position
    against the reference's full forward."""
    prompt = _ids(model, prompt_len, seed=chunk)
    tokens, rows, cache = _paged_logits(model, prompt, 12, chunk)
    full = np.concatenate([prompt, np.asarray(tokens[:-1], np.int64)])
    want = _reference(model, full)[prompt_len - 1:]
    np.testing.assert_allclose(rows, want, rtol=TOL, atol=TOL)
    # every conv layer left one tail a step and one more where a chunk
    # crossed a page; every real row went to k experts in every routed layer
    cfg = model.config
    n_rows = prompt_len + 12
    counts = cache.counts()
    assert counts["moe_assignments"] == n_rows * cfg.num_experts_per_tok * 4
    steps = -(-prompt_len // chunk) + 12
    crossings = sum(1 for a in range(0, prompt_len, chunk)
                    if a // PAGE != (min(a + chunk, prompt_len) - 1) // PAGE)
    assert counts["conv_tail_rows"] == 5 * (steps + crossings)


def test_a_full_pages_tail_is_what_the_next_token_reads(model):
    """After position 7 (the last of page 1) is written, page 1's tail holds
    the convolution inputs at positions 6 and 7 and never changes again."""
    prompt = _ids(model, 8, seed=9)
    _, _, cache = _paged_logits(model, prompt, 1, chunk=8)
    before = np.asarray(cache.tail._value)[:, 1].reshape(5, 2, -1).copy()
    _, _, cache2 = _paged_logits(model, prompt, 6, chunk=3)
    after = np.asarray(cache2.tail._value)[:, 1].reshape(5, 2, -1)
    assert np.abs(before).max() > 0
    np.testing.assert_allclose(after, before, rtol=1e-6, atol=1e-6)


def _served(engine, prompts, n_new):
    reqs = [engine.submit(p, n_new) for p in prompts]
    engine.run_until_idle()
    assert all(r.state == "DONE" for r in reqs)
    return [list(r.tokens) for r in reqs]


def _gap(model, prompt, tokens):
    """How far below its position's maximum the reference puts each emitted
    token (0 where the reference's argmax is the token)."""
    full = np.concatenate([prompt, np.asarray(tokens[:-1], np.int64)])
    rows = _reference(model, full)[len(prompt) - 1:]
    return float((rows.max(-1) - rows[np.arange(len(tokens)), tokens]).max())


def test_the_engine_serves_it_and_counts_what_the_experts_saw(model):
    prompt = _ids(model, 23, seed=3)
    eng = _engine(model)
    (tokens,) = _served(eng, [prompt], 12)
    assert _gap(model, prompt, tokens) <= TOL
    m = eng.metrics()
    # padding rows reach no expert: 23 prompt rows and 11 decode rows, two
    # experts each, in four routed layers (the step's flat axis holds 9 rows)
    assert m["moe_assignments"] == (23 + 11) * 2 * 4
    assert m["block_rows"] == 23 + 11
    assert 0 < m["moe_experts_touched"] <= m["fused_steps"] * 4 * 8
    assert m["moe_expert_load_max"] <= m["moe_assignments"]
    assert m["conv_tail_rows"] == 5 * (m["fused_steps"] + 2)
    assert eng.compiled_programs == 1
    eng.close()


def test_the_route_log_holds_what_every_row_chose(model, monkeypatch):
    """The cache's flight recorder: one column a real row, each position of
    the one sequence once, the experts the float32 reference picks itself (as
    sets: both sides are float32 here); the builder hands them to the
    reference by position.  A ring shorter than the run wraps without losing
    a step's rows."""
    prompt = _ids(model, 23, seed=3)
    eng = _engine(model)
    (tokens,) = _served(eng, [prompt], 12)
    log = model.recent_routes()
    assert sorted(log["positions"]) == list(range(23 + 11))
    assert (log["pages"] > 0).all() and log["experts"].shape == (34, 4, 2)
    kw = lfm2_builder.reference_kwargs(model)
    assert kw["routes"].shape == (4, 34, 2) and (kw["routes"] >= 0).all()
    assert kw["route_margin"] == lfm2_builder.ROUTE_MARGIN
    ids = np.concatenate([prompt, np.asarray(tokens[:-1], np.int64)])[None]
    plain = {k: v for k, v in kw.items() if not k.startswith("route")}
    weights = lfm2_builder.reference_weights(model)
    _, inputs = lfm2_moe_ref.logits(weights, jnp.asarray(ids), hidden=True, **plain)
    routed = [p for p in weights["layers"] if "router" in p]
    for theirs, u, p in zip(kw["routes"], inputs, routed):
        own, _ = lfm2_moe_ref.route(u[0], p, top_k=2)
        assert (np.sort(np.asarray(own), -1) == np.sort(theirs, -1)).all()
    eng.close()
    assert model.recent_routes() is None        # released with the cache
    assert "routes" not in lfm2_builder.reference_kwargs(model)

    from paddle_tpu.serving.paged_cache import HybridPagedCache
    monkeypatch.setattr(HybridPagedCache, "ROUTE_ROWS", 24)    # two steps of 9
    eng = _engine(model)
    _served(eng, [prompt], 12)
    wrapped = model.recent_routes()
    assert len(wrapped["positions"]) <= 24
    assert set(wrapped["positions"][-2:]) <= set(range(34))
    two = [_ids(model, 6, seed=30), _ids(model, 6, seed=31)]
    _served(eng, two, 3)
    # positions of several sequences: nothing is handed over
    assert "routes" not in lfm2_builder.reference_kwargs(model)
    eng.close()


def test_the_reference_takes_the_side_of_a_near_tie_and_nothing_more():
    """Scores 0.9, 0.8, 0.7, 0.699, 0.5, 0.1 (bias 0), top 3: another run's
    picks are taken where they are a top-3 to within the margin: the fourth
    for the third.  Not taken: the fifth (0.2 under), a pick that leaves the
    best out, a pick twice, an expert that does not exist, no pick."""
    scores = np.array([0.9, 0.8, 0.7, 0.699, 0.5, 0.1], np.float32)
    p = {"router": np.log(scores / (1 - scores))[None].astype(np.float32),
         "router_bias": np.zeros(6, np.float32)}
    u = jnp.ones((7, 1), jnp.float32)
    theirs = np.array([[0, 1, 3], [3, 1, 0], [0, 1, 4], [1, 2, 3], [0, 1, 1],
                       [0, 1, 6], [-1, -1, -1]], np.int32)
    sel, w = lfm2_moe_ref.route(u, p, top_k=3, theirs=jnp.asarray(theirs),
                                margin=0.01)
    sel, w = np.asarray(sel), np.asarray(w)
    assert sel[:2].tolist() == theirs[:2].tolist()
    assert (np.sort(sel[2:], -1) == [0, 1, 2]).all()
    np.testing.assert_allclose(w[0], scores[[0, 1, 3]] / (scores[[0, 1, 3]].sum() + 1e-6),
                               rtol=1e-5)
    own, _ = lfm2_moe_ref.route(u, p, top_k=3, theirs=jnp.asarray(theirs), margin=0.0)
    assert (np.sort(np.asarray(own), -1) == [0, 1, 2]).all()


def test_two_requests_sharing_a_two_page_prefix_read_the_shared_tails(model):
    """The second request splices the first's two full pages (their K/V and
    their conv tails) and prefills from position 16: its tokens are those of
    the same request served alone with no prefix cache."""
    shared = _ids(model, 2 * PAGE, seed=4)
    prompts = [np.concatenate([shared, _ids(model, 5, seed=5)]),
               np.concatenate([shared, _ids(model, 7, seed=6)])]
    alone = []
    for p in prompts:
        eng = _engine(model, prefix_cache=False)
        alone += _served(eng, [p], 10)
        eng.close()
    eng = _engine(model)
    first = _served(eng, prompts[:1], 10)
    second = _served(eng, prompts[1:], 10)
    m = eng.metrics()
    eng.close()
    assert m["prefix_hits"] + m["prefix_partial_hits"] >= 1
    assert m["prefix_cached_tokens"] >= 2 * PAGE
    assert first + second == alone
    for p, toks in zip(prompts, first + second):
        assert _gap(model, p, toks) <= TOL


def test_a_slot_seated_again_after_a_longer_request_starts_from_zero(model):
    """One slot: the pages a long request filled (tails and all) come back to
    a short one, whose position 0 has no predecessors."""
    long_, short = _ids(model, 40, seed=7), _ids(model, 6, seed=8)
    eng = _engine(model, num_slots=1, prefix_cache=False)
    _served(eng, [long_], 6)
    (tokens,) = _served(eng, [short], 9)
    eng.close()
    assert _gap(model, short, tokens) <= TOL
    eng = _engine(model, num_slots=1, prefix_cache=False)
    assert _served(eng, [short], 9) == [tokens]
    eng.close()


def test_interleaved_requests_match_each_served_alone(model):
    """Four requests of unequal lengths through four slots with a budget of 5:
    runs of several slots share a step's flat axis, and a row's predecessor is
    never another slot's row."""
    prompts = [_ids(model, n, seed=20 + n) for n in (3, 9, 17, 12)]
    eng = _engine(model, prefix_cache=False)
    together = _served(eng, prompts, 8)
    eng.close()
    for p, toks in zip(prompts, together):
        assert _gap(model, p, toks) <= TOL


def test_no_token_is_dropped_when_every_token_picks_the_same_experts():
    """A bias that makes experts 0 and 1 win every row: both hold every token
    (no capacity, no drop) and the forward still equals the reference."""
    pt.seed(13)
    m = Lfm2StackedForCausalLM(lfm2_tiny())
    m.eval()
    for j in range(4):
        bias = np.zeros(tuple(getattr(m, f"body{j}_router_bias").shape), np.float32)
        bias[..., :2] = 10.0
        getattr(m, f"body{j}_router_bias")._set_value(jnp.asarray(bias))
    ids = _ids(m, 40, seed=1)
    got = np.asarray(m(pt.to_tensor(ids[None]))._value)[0]
    np.testing.assert_allclose(got, _reference(m, ids), rtol=TOL, atol=TOL)
    eng = _engine(m, prefill_token_budget=16)
    (tokens,) = _served(eng, [ids[:16]], 4)
    counts = eng.metrics()
    eng.close()
    assert _gap(m, ids[:16], tokens) <= TOL
    # two experts touched a layer a step, each holding every real row
    assert counts["moe_experts_touched"] == counts["fused_steps"] * 4 * 2
    assert counts["moe_expert_load_max"] * 2 == counts["moe_assignments"]


@pytest.mark.parametrize("rows,sizes", [
    (256, [0, 100, 3, 0, 129, 0, 0, 24]),      # empty groups, a tile shared by three
    (128, [128, 0, 0, 0, 0, 0, 0, 0]),         # every row in one group
    (200, [0, 0, 0, 0, 0, 0, 0, 0]),           # nothing routed
    (130, [1, 1, 1, 1, 1, 1, 1, 1]),           # rows past the groups' total, padded tile
])
def test_grouped_matmul_kernel_against_its_oracle(rows, sizes):
    """The Pallas kernel (interpreter) against ``lax.ragged_dot`` over the
    layer's slice, with the layer's matrices second of three in the stack."""
    from paddle_tpu.ops.pallas_kernels.grouped_matmul import grouped_matmul

    rng = np.random.default_rng(rows)
    lhs = jnp.asarray(rng.standard_normal((rows, 256)), jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((24, 256, 128)), jnp.float32)
    sizes = jnp.asarray(sizes, jnp.int32)
    got = np.asarray(grouped_matmul(lhs, rhs, sizes, jnp.int32(8), interpret=True))
    want = np.asarray(grouped_matmul(lhs, rhs, sizes, 8))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    assert not got[int(sizes.sum()):].any()


def _mp2_mesh():
    from paddle_tpu.distributed import serving_mesh

    return serving_mesh.build_serving_mesh(1, 2)


@pytest.mark.parametrize("mode,build", [
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
    # nothing was done to the model on the way to the refusal
    assert not hasattr(model, "_weight_int8")


def test_the_cache_refuses_an_int8_pool_and_guards_its_row_index(model):
    from paddle_tpu.serving.paged_cache import HybridPagedCache

    with pytest.raises(ValueError, match="int8"):
        model.new_paged_kv_cache(9, PAGE, dtype="int8")
    with pytest.raises(ValueError, match="row index is int32"):
        HybridPagedCache(1, 2 ** 20, 2 ** 11, 2, PAGE, 16, 64, 2)      # the tail pool's rows
    with pytest.raises(ValueError, match="row index is int32"):
        HybridPagedCache(2 ** 10, 1, 2 ** 11, 2 ** 10, PAGE, 16, 64, 2)  # the K/V pools'


def test_the_paged_contract_error_names_the_contract():
    class NoContract:
        config = lfm2_tiny()

    with pytest.raises(TypeError, match="new_paged_kv_cache and _paged_lm_logits"):
        ServingEngine(NoContract())
