"""The pool write's tile-group launch (``ops/pallas_kernels/pool_write.py``,
docs/serving.md "The pool write") under the Pallas interpreter against the
row scatter the CPU path keeps, and the write list ``build_ragged_plan`` emits
beside the work list.

- the write list's own properties: every real token in exactly one item, no
  tile group named twice, the item count inside the capacity an engine derives
  at the worst packings, the counts at the two GPT cells' geometries;
- every step of PR 26's four engine geometries (recorded from a running
  engine) replayed on pools of random bits: written by the launch and by the
  landed scatter (the models' own write, called as the fused step calls it),
  float32 and bfloat16, K and V pools (GPT) and one K|V pool of 8 heads (the
  hybrid decoder): equal to the bit on every page the allocator dealt, the
  null page untouched by the launch;
- the two kernels composed: the mixed step of
  ``test_serving.test_ragged_kernel_parity_interpret`` written by the launch
  and read by the ragged kernel, both interpreted;
- the wired step: an engine whose models' write takes the launch (interpreted;
  the choice is patched in the test, nothing threads an option through the
  engine) emits the tokens of the engine that scatters."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models import (
    GPTStackedForPretraining, Lfm2StackedForCausalLM, gpt_tiny, lfm2_tiny,
)
from paddle_tpu.models import gpt as gpt_module
from paddle_tpu.models import lfm2 as lfm2_module
from paddle_tpu.ops.pallas_kernels import pool_write as pw
from paddle_tpu.ops.pallas_kernels import ragged_paged_attention as ra
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving import engine as engine_module

def _bits(x):
    return np.asarray(x).view(np.uint8)


# ---------------------------------------------------------------------------
# the write list
# ---------------------------------------------------------------------------

def _table(pages):
    return np.asarray(pages, np.int32)


def _plan(runs, g, *, page_size, t_max, num_slots, token_block=8):
    nb_max = num_slots + t_max // token_block
    max_pages = len(runs[0][2])
    return ra.build_ragged_plan(
        runs, token_block=token_block, page_size=page_size, t_max=t_max,
        nb_max=nb_max, wl_max=nb_max * max_pages, write_group=g,
        wr_max=ra.ragged_write_capacity(t_max, g, num_slots))


_RUN_MIXES = {
    # decode tokens at a group's first, last and a middle row; position 0
    "decode_only": [(0, 1, [3, 0]), (15, 1, [4, 0]), (16, 1, [5, 6]),
                    (37, 1, [7, 8])],
    # a chunk inside one group, one ending a group, one starting mid-group
    # and crossing a page, beside a decode token
    "chunks": [(2, 5, [3, 4]), (8, 8, [5, 6]), (27, 21, [7, 8]),
               (40, 1, [9, 10])],
    # one run covering whole pages from an aligned start
    "aligned_pages": [(32, 64, [3, 4, 5])],
}


@pytest.mark.parametrize("g", [8, 16])
@pytest.mark.parametrize("mix", list(_RUN_MIXES))
def test_every_real_token_is_in_exactly_one_item(mix, g):
    page_size = 32
    runs = [(b, c, _table(t)) for b, c, t in _RUN_MIXES[mix]]
    plan, stats = _plan(runs, g, page_size=page_size, t_max=128, num_slots=4)
    n = stats["n_writes"]
    assert n == int(plan["n_writes"][0]) <= plan["wr_page"].shape[0]
    assert plan["wr_tok"].shape[1] == g
    # where each item's new rows land, and which token feeds each
    landed = {}
    for w in range(n):
        lo, cnt = int(plan["wr_lo"][w]), int(plan["wr_n"][w])
        assert 0 <= lo and cnt >= 1 and lo + cnt <= g
        for r in range(lo, lo + cnt):
            where = (int(plan["wr_page"][w]),
                     int(plan["wr_group"][w]) * g + r)
            assert where not in landed, where
            landed[where] = int(plan["wr_tok"][w, r])
    want = {}
    for (base, count, table), start in zip(runs, stats["run_starts"]):
        for i in range(count):
            p = base + i
            want[(int(table[p // page_size]), p % page_size)] = start + i
    assert landed == want
    # no tile group is named twice, and every index of the arrays is valid
    groups = list(zip(plan["wr_page"][:n], plan["wr_group"][:n]))
    assert len(set(groups)) == n
    assert plan["wr_tok"].min() >= 0 and plan["wr_tok"].max() < 128
    assert (plan["wr_group"] < page_size // g).all()


@pytest.mark.parametrize("g", [8, 16])
@pytest.mark.parametrize("num_slots,budget,page_size,context", [
    (32, 128, 128, 1024),       # the chat cell's engine
    (16, 512, 128, 2048),       # the document cell's
    (64, 256, 128, 2048),       # the hybrid cell's
])
def test_the_worst_packings_fit_the_derived_capacity(num_slots, budget,
                                                     page_size, context, g):
    """``t_max // g + 2 * num_slots``: every slot a decode token but one, which
    takes the whole budget from the middle of a group; and every slot a
    two-token run across a group boundary (what a speculative verify run or
    a crowd of short prefills looks like)."""
    t_max = num_slots + budget
    max_pages = context // page_size
    tables = (np.arange(num_slots * max_pages, dtype=np.int32) + 1
              ).reshape(num_slots, max_pages)
    decode = [(g * (s + 1) + s % g, 1, tables[s]) for s in range(num_slots - 1)]
    chunk = [(g // 2 + 1, budget, tables[-1])]
    plan, stats = _plan(decode + chunk, g, page_size=page_size, t_max=t_max,
                        num_slots=num_slots)
    assert stats["n_writes"] == num_slots - 1 + budget // g + 1
    straddle = [(g * (s + 1) - 1, 2, tables[s]) for s in range(num_slots)]
    plan, stats = _plan(straddle, g, page_size=page_size, t_max=t_max,
                        num_slots=num_slots)
    assert stats["n_writes"] == 2 * num_slots <= plan["wr_page"].shape[0]
    # one item more than the capacity raises, as an overflowing work list does
    with pytest.raises(ValueError, match="write items"):
        ra.build_ragged_plan(
            straddle, token_block=8, page_size=page_size, t_max=t_max,
            nb_max=t_max, wl_max=t_max * max_pages, write_group=g,
            wr_max=2 * num_slots - 1)


@pytest.mark.parametrize("runs,items", [
    # a 512-token chunk from position 1,000 (row 8 of its group): 33 groups
    ([(1000, 512, np.arange(1, 17, dtype=np.int32))], 33),
    # 16 decode slots: one item a token
    ([(100 + 37 * s, 1, np.arange(16 * s + 1, 16 * s + 17, dtype=np.int32))
      for s in range(16)], 16),
    # an aligned chunk: a sixteenth of an item a token
    ([(1024, 512, np.arange(1, 17, dtype=np.int32))], 32),
])
def test_the_item_counts_at_the_cells_geometry(runs, items):
    _, stats = _plan(runs, 16, page_size=128, t_max=528, num_slots=16)
    assert stats["n_writes"] == items


def test_a_tile_group_named_twice_raises():
    """Two slots can only agree on a page that neither writes (a shared
    prefix page is complete); a plan that has both write it is refused, as
    the launch would lose one of the writes."""
    shared = _table([3, 4])
    with pytest.raises(ValueError, match="one tile group"):
        _plan([(4, 1, shared), (6, 1, shared)], 8, page_size=16, t_max=16,
              num_slots=2)
    # another group of the same page is another item
    _, stats = _plan([(4, 1, shared), (9, 1, shared)], 8, page_size=16,
                     t_max=16, num_slots=2)
    assert stats["n_writes"] == 2
    with pytest.raises(ValueError, match="must divide"):
        ra.build_ragged_plan([(0, 1, shared)], token_block=8, page_size=8,
                             t_max=8, nb_max=2, wl_max=4, write_group=16)


def test_the_group_follows_the_dtype_and_the_launch_checks_its_list():
    assert pw.pool_write_group(jnp.float32) == 8
    assert pw.pool_write_group(jnp.bfloat16) == 16
    assert pw.pool_write_supported(128, jnp.bfloat16)
    assert pw.pool_write_supported(8, jnp.float32)
    assert not pw.pool_write_supported(8, jnp.bfloat16)     # 8 % 16
    assert not pw.pool_write_supported(128, jnp.int8)       # keeps the scatter
    assert not pw.pool_write_runs(128, jnp.bfloat16)        # not on a TPU here
    plan, _ = _plan([(0, 1, _table([1]))], 8, page_size=16, t_max=8,
                    num_slots=1)
    wl = tuple(jnp.asarray(plan[f]) for f in ra.RAGGED_WRITE_FIELDS)
    pool = jnp.zeros((2, 2, 16, 128), jnp.bfloat16)
    with pytest.raises(ValueError, match="write_group"):
        pw.pool_write((pool,), (jnp.zeros((8, 2, 128), jnp.bfloat16),), wl,
                      interpret=True)


# ---------------------------------------------------------------------------
# the launch against the landed scatter, over the engine's own steps
# ---------------------------------------------------------------------------

# PR 26's four geometries (tests/test_serving.py ``_MIXED_CASES``): engine
# keywords, prompt lengths, new tokens.  ``page`` scales a geometry whose page
# the dtype's tile group does not divide (bfloat16: 16 positions)
_GEOMETRIES = {
    "interleaved": (dict(num_slots=2, page_size=16, prefill_token_budget=6),
                    (4, 17, 7, 21, 11, 5), 4),
    "chunk_crosses_pages": (
        dict(num_slots=2, page_size=8, prefill_token_budget=20), (21, 13), 4),
    "idle_slots_and_padding": (
        dict(num_slots=6, page_size=16, prefill_token_budget=16), (5, 3), 4),
    "last_page_of_last_layer": (
        dict(num_slots=2, page_size=16, prefill_token_budget=16,
             num_pages=9), (58, 59), 5),
}


@functools.lru_cache(maxsize=None)
def _recorded_steps(case, page_scale):
    """The runs of every step a tiny engine dispatched in one geometry, its
    step geometry, and the pages the allocator dealt.  ``page_scale`` 2
    doubles the page, the budget and the prompts (a chunk of 40 over three
    16-position pages where the case's own is 20 over three of 8)."""
    eng_kw, lengths, n_new = _GEOMETRIES[case]
    eng_kw = dict(eng_kw)
    if page_scale != 1:
        eng_kw["page_size"] *= page_scale
        eng_kw["prefill_token_budget"] *= page_scale
        lengths = tuple(n * page_scale for n in lengths)
    pt.seed(3)
    cfg = gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0)
    model = GPTStackedForPretraining(cfg)
    model.eval()
    rng = np.random.RandomState(2)
    prompts = [rng.randint(0, cfg.vocab_size, (s,)) for s in lengths]
    steps = []
    build = engine_module.build_ragged_plan

    def recording(runs, **kw):
        steps.append([(int(b), int(c), np.array(t)) for b, c, t in runs])
        return build(runs, **kw)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(engine_module, "build_ragged_plan", recording)
        eng = ServingEngine(model, max_context=64 * page_scale,
                            cache_dtype="float32", **eng_kw)
        reqs, it, touched = [], iter(prompts), set()
        while len(reqs) < len(prompts) or eng.queue.depth \
                or eng.scheduler.active_slots:
            try:
                reqs.append(eng.submit(next(it), n_new))
            except StopIteration:
                pass
            eng.step()
            touched |= set(eng.allocator._allocated)
        assert all(r.finished for r in reqs)
        geometry = dict(page_size=eng.page_size, t_max=eng._t_max,
                        num_slots=eng.num_slots, num_pages=eng.num_pages,
                        token_block=eng.token_block)
        eng.close()
    return steps, geometry, sorted(touched)


def _step_arrays(runs, g, geo):
    plan, stats = _plan(runs, g, page_size=geo["page_size"],
                        t_max=geo["t_max"], num_slots=geo["num_slots"],
                        token_block=geo["token_block"])
    tables = np.zeros((geo["t_max"], len(runs[0][2])), np.int32)
    pos = np.zeros((geo["t_max"],), np.int32)
    for (base, count, row), start in zip(runs, stats["run_starts"]):
        tables[start:start + count] = row
        pos[start:start + count] = base + np.arange(count)
    plan = tuple(jnp.asarray(plan[f]) for f in ra.RAGGED_PLAN_FIELDS)
    return plan, jnp.asarray(tables), jnp.asarray(pos)


def _scatter_two_pools(pools, k, v, plan, tables, pos, page_size):
    """The GPT decoder's own write off the TPU: the row scatter."""
    q = jnp.zeros_like(k)[:, :, None, :]
    _, pk, pv = gpt_module._attend_paged_shard(
        q, k[:, :, None, :], v[:, :, None, :], *pools, tables, pos,
        head_dim=k.shape[-1], page_size=page_size, ragged_plan=plan)
    return pk, pv


def _scatter_one_pool(pools, k, v, plan, tables, pos, page_size):
    """The hybrid decoder's own write off the TPU: rows of K|V."""
    step = lfm2_module._PagedStep(pos, tables, plan, page_size,
                                  pools[0].shape[0])
    q = jnp.zeros((k.shape[0], k.shape[1], k.shape[2]), k.dtype)
    _, kv = step.attend(q, k, v, pools[0], 0, k.shape[-1])
    return (kv,)


@pytest.mark.parametrize("form", ["two_pools", "one_kv_pool"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(_GEOMETRIES))
def test_the_launch_writes_what_the_scatter_writes(case, dtype, form):
    dt = jnp.dtype(dtype)
    g = pw.pool_write_group(dt)
    page = _GEOMETRIES[case][0]["page_size"]
    steps, geo, touched = _recorded_steps(case, 1 if page % g == 0 else g // page)
    assert geo["page_size"] % g == 0
    if case == "last_page_of_last_layer":
        assert geo["num_pages"] - 1 in touched
    rng = np.random.RandomState(5)
    if form == "two_pools":         # K and V, 4 heads of 128
        heads, dim, n_pools, scatter = 4, 128, 2, _scatter_two_pools
    else:                           # one pool of K|V rows, 8 heads of 64 + 64
        heads, dim, n_pools, scatter = 8, 64, 1, _scatter_one_pool
    shape = (geo["num_pages"], heads, geo["page_size"], 128)
    first = tuple(jnp.asarray(rng.randn(*shape), dt) for _ in range(n_pools))
    launched, scattered = first, first
    real_rows = 0
    for runs in steps:
        plan, tables, pos = _step_arrays(runs, g, geo)
        k = jnp.asarray(rng.randn(geo["t_max"], heads, dim), dt)
        v = jnp.asarray(rng.randn(geo["t_max"], heads, dim), dt)
        rows = (k, v) if n_pools == 2 else (jnp.concatenate([k, v], -1),)
        launched = pw.pool_write(launched, rows, ra.write_list_of(plan),
                                 interpret=True)
        scattered = scatter(scattered, k, v, plan, tables, pos,
                            geo["page_size"])
        real_rows += sum(c for _, c, _ in runs)
    assert real_rows > 0
    for new, old, was in zip(launched, scattered, first):
        # equal to the bit on every page the allocator dealt ...
        assert np.array_equal(_bits(new[jnp.asarray(touched)]),
                              _bits(old[jnp.asarray(touched)]))
        # ... and on every other page but the null page, which only the
        # scatter's padding rows reach: the launch leaves it as it was
        assert np.array_equal(_bits(new[1:]), _bits(old[1:]))
        assert np.array_equal(_bits(new[0]), _bits(was[0]))
        assert not np.array_equal(_bits(new[jnp.asarray(touched)]),
                                  _bits(was[jnp.asarray(touched)]))


# ---------------------------------------------------------------------------
# the two kernels composed
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype,tol", [("float32", 5e-6), ("bfloat16", 2e-2)])
def test_the_launch_writes_what_the_ragged_kernel_reads(dtype, tol):
    """The mixed step of ``test_ragged_kernel_parity_interpret``: its new rows
    written by the launch, then attended by the ragged kernel (both
    interpreted), against the same step written by the scatter: the same
    bits out of the kernel and out of the gather oracle."""
    dt = jnp.dtype(dtype)
    rng = np.random.RandomState(0)
    P, H, PS, D, MP = 11, 2, 128, 64, 4
    runs = [
        (200, 1, np.array([4, 2, 9, 1], np.int32)),    # decode, 2 pages
        (0, 1, np.array([3, 0, 0, 0], np.int32)),      # decode at pos 0
        (120, 16, np.array([7, 5, 8, 6], np.int32)),   # prefill straddling
        (17, 5, np.array([10, 0, 0, 0], np.int32)),    # short prefill tail
    ]
    T_MAX, NB_MAX, WL_MAX = 32, 8, 32
    plan_np, stats = ra.build_ragged_plan(
        runs, token_block=8, page_size=PS, t_max=T_MAX, nb_max=NB_MAX,
        wl_max=WL_MAX, write_group=pw.pool_write_group(dt))
    tables = np.zeros((T_MAX, MP), np.int32)
    lengths = np.zeros((T_MAX,), np.int32)
    for (base, count, tbl), start in zip(runs, stats["run_starts"]):
        tables[start:start + count] = tbl
        lengths[start:start + count] = base + np.arange(count) + 1
    real = stats["n_tokens"]
    plan = tuple(jnp.asarray(plan_np[f]) for f in ra.RAGGED_PLAN_FIELDS)
    tables, lengths = jnp.asarray(tables), jnp.asarray(lengths)
    q = jnp.asarray(rng.randn(T_MAX, H, D), dt)
    k = jnp.asarray(rng.randn(T_MAX, H, D), dt)
    v = jnp.asarray(rng.randn(T_MAX, H, D), dt)
    pools = tuple(jnp.asarray(rng.randn(P, H, PS, D), dt) for _ in range(2))
    launched = pw.pool_write(pools, (k, v), ra.write_list_of(plan), interpret=True)
    scattered = _scatter_two_pools(pools, k, v, plan, tables, lengths - 1, PS)
    outs = {}
    for name, (pk, pv) in (("launched", launched), ("scattered", scattered)):
        assert np.array_equal(_bits(pk[1:]), _bits(scattered[0][1:]))
        outs[name] = (
            np.asarray(ra.ragged_paged_attention(
                q, pk, pv, tables, lengths, plan, sm_scale=0.125,
                interpret=True), np.float32)[:real],
            np.asarray(ra._xla_ragged_reference(
                q, pk, pv, tables, lengths, 0.125), np.float32)[:real])
    for got, want in zip(outs["launched"], outs["scattered"]):
        assert np.array_equal(_bits(got), _bits(want))
    np.testing.assert_allclose(outs["launched"][0], outs["launched"][1],
                               rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# the wired step
# ---------------------------------------------------------------------------

def _serve(model, prompts, n_new, engine=ServingEngine, **eng_kw):
    eng = engine(model, cache_dtype="float32", **eng_kw)
    reqs = [eng.submit(p, n_new) for p in prompts]
    eng.run_until_idle(max_steps=400)
    assert all(r.finished for r in reqs)
    out = [np.asarray(r.output_ids()) for r in reqs]
    mets, programs = eng.metrics(), eng.compiled_programs
    eng.close()
    return out, mets, programs


@pytest.mark.parametrize("family", ["gpt", "gpt_mp2", "hybrid"])
def test_an_engine_whose_write_takes_the_launch_emits_the_same_tokens(
        family, monkeypatch):
    """What a TPU observes is patched in here: the models' write asks
    ``pool_write_runs`` and takes the launch, interpreted.  Four requests over
    two slots (prefill chunks beside decode tokens, pages crossed, a slot
    seated again): token for token the engine that scatters, one compiled
    program, and the counters of the write list.  ``gpt_mp2``: the same body
    a head shard under ``shard_map`` (the write list rides replicated with
    the plan, a block holds the shard's local heads)."""
    pt.seed(9)
    if family.startswith("gpt"):
        model = GPTStackedForPretraining(
            gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0))
        eng_kw = dict(num_slots=2, page_size=16, max_context=64,
                      prefill_token_budget=6)
        if family == "gpt_mp2":
            from paddle_tpu.serving import ShardedServingEngine

            eng_kw.update(engine=ShardedServingEngine, dp=1, mp=2)
    else:
        model = Lfm2StackedForCausalLM(lfm2_tiny())
        eng_kw = dict(num_slots=2, page_size=8, max_context=64,
                      prefill_token_budget=5)
    model.eval()
    rng = np.random.RandomState(4)
    prompts = [rng.randint(0, model.config.vocab_size, (s,))
               for s in (4, 17, 7, 21)]
    want, mets, _ = _serve(model, prompts, 4, **eng_kw)
    calls, real_launch = [], pw.pool_write

    def launch(pools, rows, write_list):
        calls.append(len(pools))
        return real_launch(pools, rows, write_list, interpret=True)

    monkeypatch.setattr(pw, "pool_write_runs", lambda page, dtype: True)
    monkeypatch.setattr(pw, "pool_write", launch)
    got, mets_launch, programs = _serve(model, prompts, 4, **eng_kw)
    assert calls and set(calls) == {1 if family == "hybrid" else 2}
    assert programs == 1
    for a, b in zip(got, want):
        assert np.array_equal(a, b)
    # the write list's counters are the host's, whichever path wrote
    for m in (mets, mets_launch):
        assert m["write_items"] > 0
        assert m["pool_write_items"] == m["write_items"] / m["fused_steps"]
        assert m["pool_tiles_per_token"] == m["write_items"] / m["block_rows"]
        # a group holds 8 float32 positions: between an eighth of an item a
        # token (aligned chunks) and one (decode tokens)
        assert 1 / 8 <= m["pool_tiles_per_token"] <= 1.0
    assert mets["write_items"] == mets_launch["write_items"]
