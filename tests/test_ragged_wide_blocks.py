"""Wide query blocks in the ragged plan and launch (PR 38): a run longer than
one narrow block is cut into blocks of ``QW`` tokens, so a prefill chunk reads
each page of its context once a wide block, not once every 8 rows.

- the rule (``ragged_wide_block``) is a function of the launch's shapes;
- the plan: the item count the rule predicts, never more than the narrow
  plan's, for runs around every boundary, with and without a window over a
  ring;
- the launch (Pallas interpreter; the one kernel, once a width): a mixed step
  against the gather oracle for 1, 2 and 4 query heads a pool head, bit-equal
  to the narrow plan's output;
- the engine: greedy tokens of the wide plan are the narrow plan's, and the
  counters add up;
- the described v5e: the launch at each serving cell's geometry holds the wide
  operand and compiles (so it fits the scoped VMEM), no chip."""
import functools
import importlib
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.analysis import cost_model as cm
from paddle_tpu.models import GPTStackedForPretraining, gpt_tiny
from paddle_tpu.ops.pallas_kernels import ragged_paged_attention as ra
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving import engine as engine_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PS, QB = 128, 8
QW = ra.ragged_wide_block(16, 1, PS, 128, "bfloat16")     # the GPT cells'


# ---------------------------------------------------------------------------
# the rule
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("heads,group,dim,dtype,want", [
    (16, 1, 128, "bfloat16", 64),      # gpt_1p3b: one query head a pool head
    (8, 4, 128, "bfloat16", 16),       # lfm2: 32 query heads over 8 K|V rows
    (10, 4, 128, "bfloat16", 16),      # phi4 flash: 40 over 10 pair rows
    (4, 1, 64, "bfloat16", 32),        # a row half as long moves half the bytes
    (2, 8, 128, "bfloat16", 8),        # 8 query heads a pool head: no wide block
])
def test_the_wide_block_follows_the_launchs_shapes(heads, group, dim, dtype, want):
    qw = ra.ragged_wide_block(heads, group, PS, dim, dtype)
    assert qw == want and qw % 8 == 0
    # an item's scores stay within what its K and V bytes pay for, and its
    # buffers within the scoped VMEM
    itemsize = jnp.dtype(dtype).itemsize
    assert group * qw <= max(2 * dim * itemsize // ra._KV_BYTES_PER_SCORE, group * QB)
    hb = ra.ragged_head_block(heads, PS, dim, dtype)
    if qw > QB:
        assert ra._wide_vmem_bytes(hb, group * qw, PS, dim, itemsize) \
            <= ra._SCOPED_VMEM_BYTES


def test_the_wide_block_shrinks_to_what_the_scoped_vmem_holds():
    """float32 rows of 256 would pay for 256 rows an item; the buffers of 16
    heads do not fit, and the rule walks down to the widest that does."""
    qw = ra.ragged_wide_block(16, 1, PS, 256, "float32")
    assert QB < qw < 256 and qw % 8 == 0
    assert ra._wide_vmem_bytes(16, qw, PS, 256, 4) <= ra._SCOPED_VMEM_BYTES
    assert ra._wide_vmem_bytes(16, qw + 8, PS, 256, 4) > ra._SCOPED_VMEM_BYTES


@pytest.mark.parametrize("t_max,qw,want", [
    (528, 8, 0),         # no wide block
    (528, 64, 10),       # the document cell: 8 full blocks and two tails
    (160, 64, 4),        # the chat cell
    (320, 16, 22),       # the hybrid and long-context cells
])
def test_wide_capacity_is_a_bound_in_tokens(t_max, qw, want):
    assert ra.ragged_wide_capacity(t_max, QB, qw) == want


# ---------------------------------------------------------------------------
# the plan
# ---------------------------------------------------------------------------

def _plans(runs, *, qw=QW, window=None, t_max=None, max_pages=16):
    """(narrow plan, wide plan) of one step, each with its stats, at the
    capacities an engine of one run a slot gives them."""
    t_max = t_max or sum(r[1] for r in runs) + 3
    nb = len(runs) + (t_max - len(runs)) // QB
    geo = dict(token_block=QB, page_size=PS, t_max=t_max, nb_max=nb,
               wl_max=nb * max_pages, window=window)
    return (ra.build_ragged_plan(runs, **geo),
            ra.build_ragged_plan(runs, wide_block=qw,
                                 nbw_max=ra.ragged_wide_capacity(t_max, QB, qw), **geo))


def _blocks_by_the_rule(count, qw):
    """(wide blocks, narrow blocks) of a run of ``count`` tokens."""
    if count <= QB:
        return 0, 1
    tail = count % qw
    return count // qw + (tail > QB), int(0 < tail <= QB)


def test_a_chunk_of_512_at_context_1500_reads_each_page_once_a_wide_block():
    base, count = 1500 - 512, 512
    table = np.arange(1, 17, dtype=np.int32)
    (narrow, ns), (wide, ws) = _plans([(base, count, table)])
    # 8 wide blocks; block j ends at base + 64 j + 63 and reads the pages up to it
    predicted = sum((base + QW * (j + 1) - 1) // PS + 1 for j in range(count // QW))
    assert ws["n_items"] == ws["wide_items"] == predicted == 84
    assert ns["n_items"] == sum((base + QB * (j + 1) - 1) // PS + 1
                                for j in range(count // QB)) == 656
    assert ws["n_items"] <= ns["n_items"]
    assert ws["wide_rows"] == ws["n_tokens"] == count
    assert ws["row_capacity"] == count and ws["wide_blocks"] == 8
    assert int(wide["n_items"][0]) == 0 and int(wide["n_wide"][0]) == predicted


@pytest.mark.parametrize("window", [None, 512])
@pytest.mark.parametrize("count", [1, 8, 9, QW - 1, QW, QW + 1, 2 * QW + 3])
def test_runs_around_every_boundary_list_what_their_rows_read(count, window):
    """One run beside a decode row, over a page table (``window=None``) or a
    slot's ring (``window=W``): the blocks are the rule's, every token sits
    where its block says, a block's items are exactly the page-slots its rows
    read, each at its (ring) page, and no more than the narrow plan's."""
    W, MP, base = 512, 64, 1000
    R = -(-(W - 1 + 256) // PS) + 1
    ring = lambda s: (1 + s * R + np.arange(MP) % R).astype(np.int32)  # noqa: E731
    flat = lambda s: (1 + s * MP + np.arange(MP)).astype(np.int32)     # noqa: E731
    table = ring if window else flat
    runs = [(700, 1, table(0)), (base, count, table(1))]
    (narrow, ns), (plan, stats) = _plans(runs, window=window, max_pages=MP)
    nb = plan["blk_tok"].shape[0]
    n_wide, n_narrow = _blocks_by_the_rule(count, QW)
    assert stats["wide_blocks"] == n_wide
    assert stats["n_blocks"] == 1 + n_wide + n_narrow
    assert stats["wide_rows"] == min(count, n_wide * QW)
    assert stats["row_capacity"] == QB * (1 + n_narrow) + QW * n_wide
    assert stats["n_items"] <= ns["n_items"]
    # every token sits in the block and row the inverse maps name (a wide
    # block j is named nb + j)
    base_of = np.concatenate([plan["blk_base"], plan["wblk_base"]])
    rows_of = np.concatenate([plan["blk_rows"], plan["wblk_rows"]])
    rows = {}
    for t in range(stats["n_tokens"]):
        b, r = int(plan["tok_blk"][t]), int(plan["tok_row"][t])
        tok = plan["wblk_tok"][b - nb, r] if b >= nb else plan["blk_tok"][b, r]
        assert tok == t and r < rows_of[b]
        run = 0 if t == 0 else 1
        pos = runs[run][0] + (t - (0 if t == 0 else 1))
        assert base_of[b] + r == pos
        rows.setdefault(b, []).append((run, pos))
    # the two work lists, each block's items contiguous: exactly the
    # page-slots its rows read
    n, nw = int(plan["n_items"][0]), int(plan["n_wide"][0])
    assert n + nw == stats["n_items"] and nw == stats["wide_items"]
    items = list(zip(plan["wl_blk"][:n], plan["wl_page"][:n], plan["wl_pageslot"][:n]))
    items += list(zip(nb + plan["ww_blk"][:nw], plan["ww_page"][:nw],
                      plan["ww_pageslot"][:nw]))
    assert [b for b, _, _ in items] == sorted(b for b, _, _ in items)
    want = []
    for b in sorted(rows):
        run = rows[b][0][0]
        lo, hi = rows[b][0][1], rows[b][-1][1]
        first = max(lo - window + 1, 0) // PS if window else 0
        want += [(b, runs[run][2][j], j) for j in range(first, hi // PS + 1)]
    assert items == want and len(set(items)) == n + nw
    if window:
        for b in rows:
            slots = {j for bb, _, j in items if bb == b}
            assert len({j % R for j in slots}) == len(slots) <= R
    # a list's tail repeats its last real item (a list with none holds zeros)
    assert set(plan["wl_blk"][n:]) <= {plan["wl_blk"][n - 1]}
    assert set(plan["ww_blk"][nw:]) <= {plan["ww_blk"][nw - 1] if nw else 0}


def test_a_plan_without_a_wide_block_is_the_narrow_plan():
    """``tools/autotune.py`` and every caller that names no wide block get the
    plan the builder always made, the new fields empty."""
    table = np.arange(1, 17, dtype=np.int32)
    runs = [(5, 1, table), (100, 200, table + 16)]
    plan, stats = ra.build_ragged_plan(runs, token_block=QB, page_size=PS,
                                       t_max=208, nb_max=27, wl_max=27 * 16)
    assert set(plan) == set(ra.RAGGED_PLAN_FIELDS)
    assert plan["wblk_tok"].shape == (0, QB)
    assert stats["wide_items"] == stats["wide_rows"] == stats["wide_blocks"] == 0
    assert int(plan["n_items"][0]) == stats["n_items"] and int(plan["n_wide"][0]) == 0
    assert plan["ww_blk"].shape == (0,)
    assert stats["n_blocks"] == 26 and stats["row_capacity"] == 26 * QB
    shapes = dict(ra.ragged_plan_shapes(token_block=QB, t_max=208, nb_max=27,
                                        wl_max=27 * 16, write_group=8, wr_max=30))
    assert all(plan[f].shape == shapes[f] for f in ra.RAGGED_ATTEND_FIELDS)


def test_a_run_whose_wide_blocks_do_not_fit_rides_narrow_blocks():
    """The step's wide blocks are a bound in tokens: runs take them in
    submission order by the rule, and one whose blocks no longer fit is cut
    into narrow blocks throughout, as every run was."""
    table = lambda i: np.arange(1 + 16 * i, 17 + 16 * i, dtype=np.int32)  # noqa: E731
    runs = [(0, 70, table(0)), (0, 20, table(1)), (300, 40, table(2)), (0, 30, table(3))]
    geo = dict(token_block=QB, page_size=PS, t_max=176, nb_max=24, wl_max=24 * 16)
    plan, stats = ra.build_ragged_plan(runs, wide_block=64, nbw_max=3, **geo)
    # 70 = 64 + 6: one wide, one narrow; 20 and 40: a wide tail each; 30 finds
    # the three wide blocks taken and is 4 narrow blocks
    assert stats["wide_blocks"] == 3 and stats["n_blocks"] == 3 + 1 + 4
    assert stats["wide_rows"] == 64 + 20 + 40
    assert plan["blk_rows"][:5].tolist() == [6, 8, 8, 8, 6]
    assert plan["blk_base"][:5].tolist() == [64, 0, 8, 16, 24]
    full, full_stats = ra.build_ragged_plan(runs, wide_block=64, nbw_max=4, **geo)
    assert full_stats["wide_blocks"] == 4 and full_stats["n_items"] <= stats["n_items"]
    narrow, narrow_stats = ra.build_ragged_plan(runs, **geo)
    assert stats["n_items"] <= narrow_stats["n_items"]
    # the narrow blocks still have their bound
    with pytest.raises(ValueError, match="overflow"):
        ra.build_ragged_plan(runs, wide_block=64, nbw_max=1, **dict(geo, nb_max=8))


# ---------------------------------------------------------------------------
# the launch, in the Pallas interpreter
# ---------------------------------------------------------------------------

def _mixed_step(group, qw, window=None, seed=0, heads=2, dim=128):
    """Decode rows, a long chunk (full wide blocks and a wide tail), a short
    chunk (one wide block, mostly padding), a chunk whose tail rides a narrow
    block, and a run of exactly one narrow block."""
    rng = np.random.default_rng(seed)
    spec = [(300, 1), (5, 1), (200, 2 * qw + 19), (0, 9), (129, 1),
            (500, qw + 3), (7, 8)]
    mp = 8
    perm = rng.permutation(np.arange(1, 1 + len(spec) * mp)).astype(np.int32)
    runs = [(b, c, perm[i * mp:(i + 1) * mp]) for i, (b, c) in enumerate(spec)]
    (narrow, ns), (wide, ws) = _plans(runs, qw=qw, window=window, max_pages=mp)
    t_max, t = len(narrow["tok_blk"]), ns["n_tokens"]
    q = jnp.asarray(rng.standard_normal((t_max, heads * group, dim)), jnp.bfloat16)
    pool = (1 + len(spec) * mp, heads, PS, dim)
    kp = jnp.asarray(rng.standard_normal(pool), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal(pool), jnp.bfloat16)
    tables = np.zeros((t_max, mp), np.int32)
    lengths = np.zeros((t_max,), np.int32)
    for (base, count, tbl), start in zip(runs, ns["run_starts"]):
        tables[start:start + count] = tbl
        lengths[start:start + count] = base + 1 + np.arange(count)
    tables, lengths = jnp.array(tables), jnp.array(lengths)
    scale = 1.0 / math.sqrt(dim)

    def launch(plan):
        return np.asarray(ra.ragged_paged_attention(
            q, kp, vp, tables, lengths,
            tuple(jnp.array(plan[f]) for f in ra.RAGGED_PLAN_FIELDS),
            sm_scale=scale, interpret=True, window=window), np.float32)[:t]

    oracle = np.asarray(ra._xla_ragged_reference(
        q, kp, vp, tables, lengths, scale, window=window), np.float32)[:t]
    return launch(narrow), launch(wide), oracle, ns, ws


@pytest.mark.parametrize("group", [1, 2, 4])
def test_a_mixed_step_matches_the_gather_oracle(group):
    qw = ra.ragged_wide_block(2, group, PS, 128, "bfloat16")
    narrow, wide, oracle, ns, ws = _mixed_step(group, qw)
    assert 0 < ws["wide_items"] < ws["n_items"] < ns["n_items"]
    assert 0 < ws["wide_rows"] < ws["n_tokens"]
    np.testing.assert_allclose(wide, oracle, atol=0.03)
    np.testing.assert_allclose(narrow, oracle, atol=0.03)


def test_one_query_head_a_pool_head_gives_the_narrow_plans_bits():
    narrow, wide, _, _, _ = _mixed_step(1, QW)
    np.testing.assert_array_equal(wide, narrow)


@pytest.mark.parametrize("group,window", [(1, 200), (2, 200), (4, 64)])
def test_a_window_layers_wide_blocks_give_the_narrow_plans_bits(group, window):
    """A wide block visits pages that some of its rows' windows do not reach:
    fully masked for those rows, and wiped by the first page they do read."""
    narrow, wide, oracle, ns, ws = _mixed_step(group, 16, window=window)
    assert ws["n_items"] < ns["n_items"]
    np.testing.assert_array_equal(wide, narrow)
    np.testing.assert_allclose(wide, oracle, atol=0.03)


def test_a_step_without_a_wide_item_walks_its_narrow_items():
    """Decode rows only under a plan that has room for wide blocks: the step
    takes the branch that launches its narrow blocks alone, and gives the
    narrow plan's bits."""
    rng = np.random.default_rng(3)
    table = lambda i: np.arange(1 + 4 * i, 5 + 4 * i, dtype=np.int32)  # noqa: E731
    runs = [(300, 1, table(0)), (0, 1, table(1)), (127, 1, table(2)), (130, 5, table(3))]
    (narrow, ns), (wide, ws) = _plans(runs, t_max=80, max_pages=4)
    assert ws["wide_items"] == 0 and wide["wblk_tok"].shape[0] > 0
    assert ws["n_items"] == ns["n_items"] == int(wide["n_items"][0])
    assert int(wide["n_wide"][0]) == 0
    q = jnp.asarray(rng.standard_normal((80, 2, 128)), jnp.bfloat16)
    kp = jnp.asarray(rng.standard_normal((17, 2, PS, 128)), jnp.bfloat16)
    vp = jnp.asarray(rng.standard_normal((17, 2, PS, 128)), jnp.bfloat16)
    tables, lengths = np.zeros((80, 4), np.int32), np.zeros((80,), np.int32)
    for (base, count, tbl), start in zip(runs, ns["run_starts"]):
        tables[start:start + count] = tbl
        lengths[start:start + count] = base + 1 + np.arange(count)
    out = [np.asarray(ra.ragged_paged_attention(
        q, kp, vp, jnp.array(tables), jnp.array(lengths),
        tuple(jnp.array(p[f]) for f in ra.RAGGED_PLAN_FIELDS),
        interpret=True), np.float32)[:ns["n_tokens"]] for p in (narrow, wide)]
    np.testing.assert_array_equal(out[0], out[1])


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------

_REAL_LAUNCH = ra.ragged_paged_attention


def _serve(monkeypatch, *, wide):
    """Four prompts through an engine whose ragged launches run in the Pallas
    interpreter; ``wide=False`` pins the plan to narrow blocks."""
    monkeypatch.setattr(ra, "ragged_paged_attention", functools.partial(
        _REAL_LAUNCH, interpret=True))
    if not wide:
        monkeypatch.setattr(engine_mod, "ragged_wide_block",
                            lambda *a, **k: QB)
    pt.seed(0)
    cfg = gpt_tiny(hidden_size=128, num_heads=2, hidden_dropout=0.0,
                   attention_dropout=0.0, max_position_embeddings=512)
    model = GPTStackedForPretraining(cfg)
    model.eval()
    eng = ServingEngine(model, num_slots=3, page_size=PS, max_context=512,
                        cache_dtype="float32", prefill_token_budget=96)
    rng = np.random.RandomState(5)
    reqs = [eng.submit(rng.randint(0, cfg.vocab_size, (n,)), 4)
            for n in (150, 40, 9, 5)]
    steps = []
    while not all(r.finished for r in reqs):
        eng.step()
        steps.append(dict(eng.metrics()))
        assert len(steps) < 60
    tokens = [list(r.tokens) for r in reqs]
    facts = (eng.token_block, eng.wide_block)
    eng.close()
    return tokens, steps, facts


@pytest.fixture(scope="module")
def served():
    mp = pytest.MonkeyPatch()
    try:
        out = {}
        for wide in (False, True):
            with mp.context() as m:
                out[wide] = _serve(m, wide=wide)
        return out
    finally:
        mp.undo()


def test_greedy_tokens_are_the_narrow_plans(served):
    (narrow, _, nf), (wide, _, wf) = served[False], served[True]
    assert nf == (QB, QB) and wf == (QB, 64)     # float32 rows of 64: 64 tokens
    assert wide == narrow and all(len(t) == 4 for t in wide)


def test_the_counters_add_up(served):
    _, steps, _ = served[True]
    _, narrow_steps, _ = served[False]
    last = steps[-1]
    assert 0 < last["wide_items"] < last["work_items"]
    assert 0 < last["wide_block_rows"] < last["block_rows"]
    assert last["block_rows"] <= last["block_row_capacity"]
    assert last["work_items"] == last["launched_items"] < narrow_steps[-1]["work_items"]
    assert narrow_steps[-1]["wide_items"] == narrow_steps[-1]["wide_block_rows"] == 0
    assert last["block_rows"] == narrow_steps[-1]["block_rows"]
    # the prompts of 150 and 40 ride wide blocks whole or nearly; 9 is a wide
    # tail, 5 a narrow block
    assert last["wide_block_rows"] >= 150 + 40 + 9 - 2 * QB
    # a step with no prefill adds no wide item
    deltas = [(b["prefill_tokens"] - a["prefill_tokens"],
               b["wide_items"] - a["wide_items"],
               b["work_items"] - a["work_items"])
              for a, b in zip(steps, steps[1:])]
    decode_only = [d for d in deltas if d[0] == 0 and d[2] > 0]
    assert decode_only and all(d[1] == 0 for d in decode_only)
    assert all(0 <= d[1] <= d[2] for d in deltas)
    # padding is reckoned by each block's own width
    assert last["padded_rows"] == last["block_row_capacity"] - last["block_rows"]


@pytest.mark.parametrize("tokens,blocks,items,wide,want_rows", [
    (9, 2, 3, dict(), 7),                                    # as it was: 2 x 8 - 9
    (70, 3, 6, dict(wide_block=64, wide_tokens=69, wide_blocks=2, wide_items=5), 66),
    (64, 1, 4, dict(wide_block=64, wide_tokens=64, wide_blocks=1, wide_items=4), 0),
])
def test_padding_waste_is_reckoned_by_each_blocks_width(tokens, blocks, items, wide, want_rows):
    w = cm.ragged_padding_waste(tokens, blocks, items, 8, 128, 128, **wide)
    assert w["padded_rows"] == want_rows
    narrow_items = items - wide.get("wide_items", 0)
    narrow_pad = (blocks - wide.get("wide_blocks", 0)) * 8 - (tokens - wide.get("wide_tokens", 0))
    wide_pad = want_rows - narrow_pad
    per_row = 4 * 128 * 128
    want = 0
    if narrow_items:
        want += round(narrow_items * per_row * 8 * narrow_pad
                      / ((blocks - wide.get("wide_blocks", 0)) * 8))
    if wide:
        want += round(wide["wide_items"] * per_row * 64 * wide_pad
                      / (wide["wide_blocks"] * 64))
    assert w["wasted_flops"] == want
    with pytest.raises(ValueError):
        cm.ragged_padding_waste(200, 1, 1, 8, 128, 128, wide_block=64,
                                wide_tokens=100, wide_blocks=1, wide_items=1)


# ---------------------------------------------------------------------------
# the described v5e (no chip)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def one_chip():
    from jax.sharding import SingleDeviceSharding

    sys.path.insert(0, REPO)
    try:
        from benchmark import aot_compile

        topo = aot_compile.describe_topology("v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _serving_cells():
    import json

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        names = [w["name"] for w in json.load(f)["workloads"]]
    return [n for n in names if ".serve_" in n]


@pytest.mark.parametrize("cell", _serving_cells())
def test_the_wide_launch_compiles_at_each_serving_cells_geometry(cell, one_chip, monkeypatch):
    """``ragged_paged_attention`` at the geometry the cell's engine builds
    (its blocks and capacities by the engine's own functions), lowered for the
    described v5e as ``benchmark/aot_compile.py`` lowers a step: the text
    holds ``_ragged_kernel`` twice, once over the wide q operand (outside any
    conditional), and Mosaic, which refuses a kernel over the scoped VMEM,
    compiles both."""
    from benchmark.harness import manifest as bm

    ctx = bm.resolve_cell(cell)
    eng = ctx["cell"]["engine"]
    mc = importlib.import_module(ctx["config"]["builder"]).model_config(ctx["config"])
    heads, dim = mc.num_heads, mc.head_dim          # the pool's, as the kernel sees it
    program = getattr(mc, "config", mc)
    query_heads = getattr(program, "num_attention_heads", None) or program.num_heads
    group = query_heads // heads
    page, slots = eng["page_size"], eng["num_slots"]
    qb = ra.ragged_token_block(page, dim, eng["cache_dtype"])
    qw = ra.ragged_wide_block(heads, group, page, dim, eng["cache_dtype"], qb)
    assert qw > qb, "every serving cell's geometry has a wide block"
    t_max = slots + eng["prefill_token_budget"]
    max_pages = eng["max_context"] // page
    nb = slots + eng["prefill_token_budget"] // qb
    nbw = ra.ragged_wide_capacity(t_max, qb, qw)
    wl = nb * max_pages
    shapes = dict(ra.ragged_plan_shapes(
        token_block=qb, t_max=t_max, nb_max=nb, wl_max=wl, write_group=16,
        wr_max=ra.ragged_write_capacity(t_max, 16, slots),
        wide_block=qw, nbw_max=nbw, wlw_max=nbw * max_pages))

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    monkeypatch.setattr(ra, "_on_tpu", lambda: True)
    pool = struct((eng["num_pages"], heads, page, dim), jnp.bfloat16)
    plan = [struct(shapes[f], jnp.int32) for f in ra.RAGGED_PLAN_FIELDS]
    lowered = jax.jit(
        lambda q, k, v, tbl, lens, *plan: ra.ragged_paged_attention(
            q, k, v, tbl, lens, plan, sm_scale=1.0 / math.sqrt(dim))
    ).lower(struct((t_max, heads * group, dim), jnp.bfloat16), pool, pool,
            struct((t_max, max_pages), jnp.int32), struct((t_max,), jnp.int32), *plan)
    text = lowered.as_text()
    # the one kernel, launched once a width
    assert "_ragged_kernel" in text and text.count("tpu_custom_call") == 2
    wide_q = f"tensor<{nbw}x{heads}x{group * qw}x{dim}xbf16>"
    assert wide_q in text, wide_q
    assert "stablehlo.case" not in text and "stablehlo.if" not in text
    assert lowered.compile().as_text().count("tpu_custom_call") >= 2
