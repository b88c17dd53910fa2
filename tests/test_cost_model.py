"""Graph Lint v2: the static roofline cost model (golden FLOPs/bytes/
padding-waste numbers for dot_general, scan-of-dots, and each Pallas
kernel's reference path, fp32 + bf16), the GL002/GL006 cost annotations,
the measured-cost autotuner (static enumeration, table round-trip +
replay validation, kernel dispatch-through-table with fallback), and the
op_cache shape-key overflow flag."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import analysis
from paddle_tpu.analysis import autotune, codes
from paddle_tpu.analysis import cost_model as cm


def _s(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.fixture
def clean_autotune(tmp_path, monkeypatch):
    """Isolate the live autotune table from the committed package table."""
    monkeypatch.setenv("PADDLE_TPU_AUTOTUNE_TABLE",
                       str(tmp_path / "table.json"))
    autotune.reset()
    yield
    autotune.reset()


# ---------------------------------------------------------------------------
# golden FLOPs / bytes: dot_general
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype,itemsize", [(jnp.float32, 4),
                                            (jnp.bfloat16, 2)])
def test_dot_general_golden(dtype, itemsize):
    M, K, N = 512, 1024, 256

    def fn(x, w):
        return jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())))

    r = cm.cost(fn, _s((M, K), dtype), _s((K, N), dtype))
    agg = r.by_primitive["dot_general"]
    assert agg["flops"] == 2 * M * K * N
    assert agg["count"] == 1
    assert agg["bytes"] == (M * K + K * N + M * N) * itemsize
    # aligned shapes: zero padding waste
    assert r.padding_waste_bytes == 0
    # boundary = program in+out
    assert r.boundary_bytes == (M * K + K * N + M * N) * itemsize
    assert r.flops == agg["flops"]
    assert r.intensity == pytest.approx(agg["flops"] / agg["bytes"])


def test_dot_general_padding_waste_golden():
    # operand 0 [512, 1000]: last dim pads 1000 -> 1024, waste 512*24 elems
    def fn(x, w):
        return x @ w

    r = cm.cost(fn, _s((512, 1000)), _s((1000, 256)))
    assert r.padding_waste_bytes == 512 * 24 * 4
    # the padded-FLOPs delta GL002 quotes: K pads 1000 -> 1024
    closed = jax.make_jaxpr(lambda x, w: x @ w)(
        jnp.zeros((512, 1000)), jnp.zeros((1000, 256)))
    eqn = [e for e in closed.jaxpr.eqns
           if e.primitive.name == "dot_general"][0]
    assert cm.dot_flops(eqn) == 2 * 512 * 1000 * 256
    assert cm.dot_flops(eqn, padded=True) == 2 * 512 * 1024 * 256


def test_ragged_padding_waste_golden():
    # one full prefill block (8 real rows) + one decode token alone in its
    # block: 7 padded rows out of 16, uniformly spread over 3 work items
    w = cm.ragged_padding_waste(n_tokens=9, n_blocks=2, n_items=3,
                                token_block=8, page_size=128, head_dim=64,
                                dtype="bfloat16")
    assert w["padded_rows"] == 7
    # per item: 4*D*page_size*QB flops, rows_frac = 7/16
    assert w["wasted_flops"] == round(3 * 4 * 64 * 128 * 8 * 7 / 16)
    assert w["wasted_q_bytes"] == 7 * 64 * 2
    # a fully-packed plan wastes nothing
    full = cm.ragged_padding_waste(16, 2, 3, 8, 128, 64)
    assert full["padded_rows"] == 0 and full["wasted_flops"] == 0
    with pytest.raises(ValueError):
        cm.ragged_padding_waste(17, 2, 3, 8, 128, 64)


def test_scan_of_dots_golden():
    L, M = 5, 256

    def fn(x, w):
        def body(c, _):
            return c @ w, ()

        c, _ = jax.lax.scan(body, x, None, length=L)
        return c

    r = cm.cost(fn, _s((M, M)), _s((M, M)))
    assert r.by_primitive["dot_general"]["flops"] == L * 2 * M * M * M
    # the scan body's eqn cost carries its trip-count multiplier
    dot = [e for e in r.eqns if e.primitive == "dot_general"][0]
    assert dot.mult == L
    assert not r.has_unbounded_loops


def test_while_marks_unbounded():
    def fn(x):
        return jax.lax.while_loop(lambda c: c[0, 0] < 100.0,
                                  lambda c: c * 2.0, x)

    r = cm.cost(fn, _s((8, 128)))
    assert r.has_unbounded_loops


# ---------------------------------------------------------------------------
# golden numbers: each Pallas kernel's reference path (fp32 + bf16)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_reference_path_golden(dtype):
    from paddle_tpu.ops.pallas_kernels.flash_attention import (
        _xla_reference_bnsd,
    )

    B, N, S, D = 2, 4, 256, 64
    r = cm.cost(lambda q, k, v: _xla_reference_bnsd(q, k, v, True, 0.125),
                _s((B, N, S, D), dtype), _s((B, N, S, D), dtype),
                _s((B, N, S, D), dtype))
    # two einsums (scores + values), each 2*B*N*S*S*D
    assert r.by_primitive["dot_general"]["flops"] == 2 * (2 * B * N * S * S * D)
    assert r.by_primitive["dot_general"]["count"] == 2
    assert r.flops >= r.by_primitive["dot_general"]["flops"]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_reference_path_golden(dtype):
    from paddle_tpu.ops.pallas_kernels.decode_attention import (
        _xla_decode_reference,
    )

    B, H, S, D = 2, 4, 256, 64
    r = cm.cost(lambda q, k, v: _xla_decode_reference(
        q, k, v, jnp.int32(100), 0.125),
        _s((B, H, D), dtype), _s((B, H, S, D), dtype),
        _s((B, H, S, D), dtype))
    assert r.by_primitive["dot_general"]["flops"] == 2 * (2 * B * H * S * D)
    # the q-len-1 path is overwhelmingly memory-bound: the cache read
    # dominates, intensity must be tiny vs any chip's ridge
    assert r.intensity < cm.chip_spec("v2").ridge


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_reference_path_golden(dtype):
    from paddle_tpu.ops.pallas_kernels.paged_attention import (
        _xla_paged_reference,
    )

    S, H, D, P, PS, MP = 3, 2, 64, 9, 128, 2
    tables = jnp.zeros((S, MP), jnp.int32)
    r = cm.cost(lambda q, kp, vp, ln: _xla_paged_reference(
        q, kp, vp, tables, ln, 0.125),
        _s((S, H, D), dtype), _s((P, H, PS, D), dtype),
        _s((P, H, PS, D), dtype), _s((S,), jnp.int32))
    assert r.by_primitive["dot_general"]["flops"] == \
        2 * (2 * S * H * (MP * PS) * D)
    # the page gather materializes each slot's contiguous view
    assert r.by_primitive["gather"]["bytes"] > 0


# ---------------------------------------------------------------------------
# roofline arithmetic + chip specs
# ---------------------------------------------------------------------------

def test_chip_spec_resolution():
    assert cm.chip_spec("TPU v5 lite").name == "v5e"
    assert cm.chip_spec("TPU v5p").name == "v5p"
    assert cm.chip_spec("", "TPU v4").name == "v4"
    assert cm.chip_spec("v6e").peak_flops == 918e12
    with pytest.raises(ValueError, match="mystery-chip"):
        cm.chip_spec("mystery-chip")   # an unknown device is an error
    with pytest.raises(ValueError):
        cm.chip_spec("", "cpu")
    assert cm.TARGET_SPEC.name == "v5e"  # the named static-analysis target
    spec = cm.chip_spec("v4")
    assert spec.ridge == pytest.approx(275e12 / 1228e9)
    # attainable clamps at the compute roof past the ridge
    assert spec.attainable_flops(spec.ridge * 10) == spec.peak_flops
    assert spec.attainable_flops(1.0) == pytest.approx(spec.hbm_bw)


def test_roofline_fraction():
    def fn(x, w):
        return x @ w

    r = cm.cost(fn, _s((512, 512)), _s((512, 512)))
    spec = cm.HardwareSpec("toy", 1e12, 1e11)
    # measured exactly at the attainable rate -> fraction 1
    att = r.attainable_flops(spec)
    assert r.roofline_fraction(spec, r.flops / att) == pytest.approx(1.0)
    # twice slower -> 0.5
    assert r.roofline_fraction(spec, 2 * r.flops / att) == pytest.approx(0.5)
    assert r.roofline_fraction(spec, 0.0) == 0.0
    # est_seconds is the max of both roofs
    assert r.est_seconds(spec) == pytest.approx(
        max(r.flops / spec.peak_flops, r.bytes_upper / spec.hbm_bw))


def test_summary_and_render():
    def fn(x, w):
        return x @ w

    r = cm.cost(fn, _s((512, 1000)), _s((1000, 256)))
    s = r.summary(cm.chip_spec("v4"))
    assert s["program"] == "fn"
    assert s["bound"] in ("compute", "memory")
    assert s["chip"] == "v4"
    text = r.render()
    assert "GFLOP" in text and "intensity" in text


# ---------------------------------------------------------------------------
# GL002/GL006 findings carry cost annotations
# ---------------------------------------------------------------------------

def test_gl002_finding_carries_cost_estimate():
    def fn(x, w):
        return x @ w

    rep = analysis.lint(fn, _s((512, 1000)), _s((1000, 256)),
                        config=analysis.LintConfig(tile_min_bytes=1024))
    hits = [f for f in rep.findings if f.code == "GL002"]
    assert hits
    for f in hits:
        assert f.cost, "GL002 must quote an estimated cost"
        assert "padding waste" in f.cost
        assert "MFLOP" in f.cost  # dots also quote FLOPs at risk
        assert f.cost in f.render()
    # the annotation is NOT part of the fingerprint (baseline stability)
    assert "padding waste" not in hits[0].fingerprint


def test_gl006_finding_carries_cost_estimate():
    def fn(x):
        return jnp.broadcast_to(x[:, None, :], (64, 512, 128)) * 1.0

    rep = analysis.lint(
        fn, _s((64, 128)),
        config=analysis.LintConfig(blowup_min_bytes=1024, blowup_ratio=2.0))
    hits = [f for f in rep.findings if f.code == "GL006"]
    assert hits and hits[0].cost
    assert "HBM traffic" in hits[0].cost


# ---------------------------------------------------------------------------
# autotuner: static enumeration
# ---------------------------------------------------------------------------

def test_enumeration_is_legal_and_static():
    shape = {"seq": 1024, "head_dim": 64}
    cands = autotune.enumerate_candidates("flash_attention", shape,
                                          "bfloat16")
    assert cands
    for p in cands:
        assert 1024 % p["block_q"] == 0 and p["block_q"] % 128 == 0
        assert 1024 % p["block_kv"] == 0 and p["block_kv"] % 128 == 0
        assert autotune.vmem_bytes_estimate(
            "flash_attention", shape, "bfloat16", p) <= autotune.VMEM_BUDGET
    # decode candidates include the sublane-layout dimension
    dec = autotune.enumerate_candidates(
        "decode_attention", {"max_seq": 256, "head_dim": 64}, "bfloat16")
    assert {p["q_rows"] for p in dec} == {8, 16}
    assert all(256 % p["block_kv"] == 0 for p in dec)
    # paged: page is the block; only the sublane layout is tunable
    pg = autotune.enumerate_candidates(
        "paged_attention", {"page_size": 128, "head_dim": 64}, "bfloat16")
    assert pg == [{"q_rows": 8}, {"q_rows": 16}]


def test_enumeration_empty_for_gate_ineligible_shapes():
    # the kernel's own GL002 gate rejects these; nothing to tune
    assert autotune.enumerate_candidates(
        "flash_attention", {"seq": 100, "head_dim": 64}, "bfloat16") == []
    assert autotune.enumerate_candidates(
        "decode_attention", {"max_seq": 256, "head_dim": 60},
        "bfloat16") == []
    assert autotune.enumerate_candidates(
        "paged_attention", {"page_size": 100, "head_dim": 64},
        "bfloat16") == []


def test_default_params_match_historical_choices():
    from paddle_tpu.ops.pallas_kernels.decode_attention import _pick_block_kv

    assert autotune.default_params(
        "flash_attention", {"seq": 1024, "head_dim": 64},
        "bfloat16") == {"block_q": 512, "block_kv": 512}
    for s in (128, 256, 512, 1024):
        assert autotune.default_params(
            "decode_attention", {"max_seq": s, "head_dim": 64},
            "bfloat16")["block_kv"] == _pick_block_kv(s)
    assert autotune.default_params(
        "paged_attention", {"page_size": 128, "head_dim": 64},
        "bfloat16") == {"q_rows": 8}


def test_static_rank_prefers_fewer_grid_steps():
    ranked = autotune.static_rank(
        "flash_attention", {"seq": 512, "head_dim": 64}, "bfloat16")
    steps = [(512 // p["block_q"]) * (512 // p["block_kv"]) for p in ranked]
    assert steps == sorted(steps)


# ---------------------------------------------------------------------------
# autotuner: table round-trip + replay validation
# ---------------------------------------------------------------------------

def test_table_round_trip(tmp_path):
    t = autotune.AutotuneTable()
    t.put("flash_attention", {"seq": 512, "head_dim": 64}, "bfloat16",
          {"block_q": 256, "block_kv": 512}, measured_us=123.4,
          source="measured", device="v5e")
    t.put("decode_attention", {"max_seq": 256, "head_dim": 64}, "bfloat16",
          {"block_kv": 128, "q_rows": 16}, source="static-default")
    path = str(tmp_path / "t.json")
    t.save(path)
    loaded = autotune.AutotuneTable.load(path)
    assert loaded.get("flash_attention", {"seq": 512, "head_dim": 64},
                      "bfloat16") == {"block_q": 256, "block_kv": 512}
    assert loaded.get("decode_attention", {"max_seq": 256, "head_dim": 64},
                      "bfloat16") == {"block_kv": 128, "q_rows": 16}
    assert loaded.entries == t.entries
    assert autotune.validate_table(loaded) == []
    # key discipline: a different shape or dtype NEVER matches
    assert loaded.get("flash_attention", {"seq": 1024, "head_dim": 64},
                      "bfloat16") is None
    assert loaded.get("flash_attention", {"seq": 512, "head_dim": 64},
                      "float32") is None


def test_replay_validation_rejects_illegal_entries(tmp_path):
    t = autotune.AutotuneTable()
    t.put("flash_attention", {"seq": 512, "head_dim": 64}, "bfloat16",
          {"block_q": 300, "block_kv": 512})  # 300 is not a legal block
    path = str(tmp_path / "bad.json")
    t.save(path)
    problems = autotune.validate_table(t)
    assert len(problems) == 1 and "not in the legal candidate set" in \
        problems[0]
    # strict load (the CI gate) raises; lenient load drops the entry
    with pytest.raises(ValueError):
        autotune.load_table(path, strict=True)
    loaded = autotune.load_table(path)
    assert loaded.entries == {}


def test_replay_validation_rejects_gate_ineligible_shape(tmp_path):
    t = autotune.AutotuneTable()
    t.put("decode_attention", {"max_seq": 100, "head_dim": 64}, "bfloat16",
          {"block_kv": 100, "q_rows": 8})
    assert any("eligibility gate" in p for p in autotune.validate_table(t))


def test_version_check(tmp_path):
    path = str(tmp_path / "v.json")
    with open(path, "w") as f:
        json.dump({"version": 99, "entries": []}, f)
    with pytest.raises(ValueError):
        autotune.AutotuneTable.load(path)


def test_committed_table_is_valid():
    """The packaged table must always pass the replay gate (the same check
    run_tests.sh runs via tools/autotune.py --validate)."""
    path = os.path.join(os.path.dirname(autotune.__file__),
                        "autotune_table.json")
    assert os.path.exists(path)
    table = autotune.AutotuneTable.load(path)
    assert table.entries, "committed table should seed the bench keys"
    assert autotune.validate_table(table) == []


# ---------------------------------------------------------------------------
# autotuner: kernel dispatch through the table
# ---------------------------------------------------------------------------

def test_flash_pick_blocks_consults_table(clean_autotune):
    from paddle_tpu.core import flags as F
    from paddle_tpu.ops.pallas_kernels.flash_attention import _pick_blocks

    saved = F.get_flags(["FLAGS_flash_block_q", "FLAGS_flash_block_kv"])
    F.set_flags({"FLAGS_flash_block_q": 0, "FLAGS_flash_block_kv": 0})
    try:
        # no entry -> today's hard-coded default
        assert _pick_blocks(1024, 64, jnp.bfloat16) == (512, 512)
        autotune.set_entry("flash_attention",
                           {"seq": 1024, "head_dim": 64}, "bfloat16",
                           {"block_q": 256, "block_kv": 1024})
        assert _pick_blocks(1024, 64, jnp.bfloat16) == (256, 1024)
        # other specializations still fall back
        assert _pick_blocks(1024, 128, jnp.bfloat16) == (512, 512)
        assert _pick_blocks(1024, 64, jnp.float32) == (512, 512)
        # an explicit user flag beats the table on its side
        F.set_flags({"FLAGS_flash_block_q": 128})
        assert _pick_blocks(1024, 64, jnp.bfloat16) == (128, 1024)
    finally:
        F.set_flags(saved)


def test_decode_pick_params_consults_table(clean_autotune):
    from paddle_tpu.ops.pallas_kernels.decode_attention import _pick_params

    assert _pick_params(256, 64, jnp.bfloat16) == (256, 8)  # default
    autotune.set_entry("decode_attention",
                       {"max_seq": 256, "head_dim": 64}, "bfloat16",
                       {"block_kv": 128, "q_rows": 16})
    assert _pick_params(256, 64, jnp.bfloat16) == (128, 16)
    # a tampered/non-dividing live entry falls back to the default
    autotune.set_entry("decode_attention",
                       {"max_seq": 256, "head_dim": 64}, "bfloat16",
                       {"block_kv": 96, "q_rows": 16})
    assert _pick_params(256, 64, jnp.bfloat16) == (256, 8)


def test_flash_partial_forced_params_fall_back(clean_autotune):
    """force() with a dict missing block_q/block_kv must fall back to the
    hard-coded default, not KeyError inside dispatch."""
    from paddle_tpu.ops.pallas_kernels.flash_attention import (_auto_block,
                                                               _pick_blocks)

    auto = _auto_block(512)
    with autotune.force("flash_attention", {"block_kv": 256}):
        assert _pick_blocks(512, 64, jnp.bfloat16) == (auto, auto)
    with autotune.force("flash_attention", {"block_q": 0, "block_kv": 256}):
        assert _pick_blocks(512, 64, jnp.bfloat16) == (auto, auto)


def test_paged_pick_q_rows_consults_table(clean_autotune):
    from paddle_tpu.ops.pallas_kernels.paged_attention import _pick_q_rows

    assert _pick_q_rows(128, 64, jnp.bfloat16) == 8  # default
    autotune.set_entry("paged_attention",
                       {"page_size": 128, "head_dim": 64}, "bfloat16",
                       {"q_rows": 16})
    assert _pick_q_rows(128, 64, jnp.bfloat16) == 16


def test_force_context_wins_and_restores(clean_autotune):
    from paddle_tpu.ops.pallas_kernels.decode_attention import _pick_params

    with autotune.force("decode_attention",
                        {"block_kv": 128, "q_rows": 16}):
        assert _pick_params(256, 64, jnp.bfloat16) == (128, 16)
    assert _pick_params(256, 64, jnp.bfloat16) == (256, 8)


def test_tuned_configs_keep_kernel_parity_interpret(clean_autotune):
    """Every decode candidate (incl. q_rows=16, the sublane-layout
    dimension) matches the XLA oracle through the Pallas interpreter."""
    import paddle_tpu.ops.pallas_kernels.decode_attention as da

    rng = np.random.RandomState(0)
    B, H, S, D = 2, 2, 256, 64
    q = jnp.array(rng.randn(B, H, D), jnp.float32)
    k = jnp.array(rng.randn(B, H, S, D), jnp.float32)
    v = jnp.array(rng.randn(B, H, S, D), jnp.float32)
    length = jnp.int32(200)
    ref = np.asarray(da._xla_decode_reference(q, k, v, length, 0.125),
                     np.float32)
    for params in autotune.enumerate_candidates(
            "decode_attention", {"max_seq": S, "head_dim": D}, "float32"):
        qr = params["q_rows"]
        q8 = jnp.broadcast_to(q.reshape(B * H, 1, D), (B * H, qr, D))
        out = da._decode_pallas(q8, k.reshape(B * H, S, D),
                                v.reshape(B * H, S, D), length, 0.125,
                                interpret=True,
                                block_kv=params["block_kv"])
        got = np.asarray(out[:, 0, :].reshape(B, H, D), np.float32)
        np.testing.assert_allclose(got, ref, rtol=5e-6, atol=5e-6,
                                   err_msg=str(params))


def test_sweep_records_winner_and_skips_dead_candidates(clean_autotune):
    shape = {"max_seq": 256, "head_dim": 64}
    timings = {128: 2e-3, 256: 1e-3}

    def timing(params):
        if params["q_rows"] == 16:
            raise RuntimeError("mosaic rejected")  # a dead candidate
        return timings[params["block_kv"]]

    table = autotune.AutotuneTable()
    winner, results = autotune.sweep("decode_attention", shape, "bfloat16",
                                     timing, table=table, device="test")
    assert winner == {"block_kv": 256, "q_rows": 8}
    assert table.get("decode_attention", shape, "bfloat16") == winner
    dead = [s for _, s in results if s == float("inf")]
    assert len(dead) == 2  # both q_rows=16 candidates died, sweep survived
    e = table.entries[autotune.table_key("decode_attention", shape,
                                         "bfloat16")]
    assert e["source"] == "measured" and e["measured_us"] == pytest.approx(
        1e3)


# ---------------------------------------------------------------------------
# cost hook on jit.to_static
# ---------------------------------------------------------------------------

def test_to_static_cost_hook():
    saved = pt.get_flags(["FLAGS_graph_cost"])
    pt.set_flags({"FLAGS_graph_cost": True})
    analysis.clear_cost_reports()
    try:
        w = pt.to_tensor(np.ones((128, 128), np.float32))

        @pt.jit.to_static
        def step(x):
            return x @ w

        step(pt.to_tensor(np.ones((128, 128), np.float32)))
        reps = step.cost_reports()
        assert len(reps) == 1
        assert reps[0].by_primitive["dot_general"]["flops"] == \
            2 * 128 * 128 * 128
        assert any(r.program == "step" for r in analysis.cost_reports())
    finally:
        pt.set_flags(saved)
        analysis.clear_cost_reports()


def test_to_static_cost_hook_off_by_default():
    analysis.clear_cost_reports()
    w = pt.to_tensor(np.ones((64, 64), np.float32))

    @pt.jit.to_static
    def step2(x):
        return x @ w

    step2(pt.to_tensor(np.ones((64, 64), np.float32)))
    assert step2.cost_reports() == []


# ---------------------------------------------------------------------------
# op_cache shape-key overflow flag (GL007 must never under-report)
# ---------------------------------------------------------------------------

def test_op_cache_shape_key_overflow_flag(monkeypatch):
    from paddle_tpu.core import op_cache

    op_cache.reset_stats()
    monkeypatch.setattr(op_cache, "_SHAPE_KEY_CAP", 2)
    for n in (3, 5, 7, 9):
        pt.to_tensor(np.ones((n, 4), np.float32)) + pt.to_tensor(
            np.ones((n, 4), np.float32))
    st = op_cache.stats()
    assert st["add"]["shape_keys"] == 2  # saturated at the cap
    assert st["add"]["shape_keys_overflow"] is True
    # GL007 flags the op on the overflow bit even below any count threshold
    rep = analysis.churn_findings(
        config=analysis.LintConfig(churn_shape_keys=100),
        op_stats={"add": st["add"]}, static_fns={}, trace_counts={},
        program_counts={})
    hits = [f for f in rep.findings if f.code == "GL007"]
    assert hits and "saturated" in hits[0].message
    op_cache.reset_stats()
    assert op_cache.stats() == {}


def test_op_cache_no_overflow_below_cap():
    from paddle_tpu.core import op_cache

    op_cache.reset_stats()
    pt.to_tensor(np.ones((3, 4), np.float32)) + pt.to_tensor(
        np.ones((3, 4), np.float32))
    st = op_cache.stats()
    assert st["add"]["shape_keys_overflow"] is False
    op_cache.reset_stats()


# ---------------------------------------------------------------------------
# quantized-serving byte accounting (ISSUE-17): per-dtype pool/decode-step
# goldens — the capacity math serving_bench's fixed-byte sweeps stand on
# ---------------------------------------------------------------------------

def test_paged_pool_bytes_golden_per_dtype():
    # the serving gate's geometry: gpt_tiny (H=4, D=16, L=2), ps=16, 6 pages
    fp32 = cm.paged_pool_bytes(6, 4, 16, 16, num_layers=2, dtype="float32")
    bf16 = cm.paged_pool_bytes(6, 4, 16, 16, num_layers=2, dtype="bfloat16")
    int8 = cm.paged_pool_bytes(6, 4, 16, 16, num_layers=2, dtype="int8")
    assert fp32 == 2 * 2 * 6 * 4 * 16 * 16 * 4 == 98304
    assert bf16 == fp32 // 2
    # int8 pages are 1/4 the fp32 bytes; the fp32 [P, H] scale sidecars
    # (K + V, per layer) ride on top and stay a rounding error
    assert int8 == fp32 // 4 + 2 * 2 * 6 * 4 * 4 == 24960
    assert int8 < fp32 // 3          # >= 3x the pages at equal bytes


def test_page_transfer_bytes_golden_exact_to_page_geometry():
    # ISSUE-20 acceptance: the disaggregated hand-off's wire bytes are
    # EXACT to the page geometry — n=3 pages, H=4, ps=16, D=8, L=2
    fp32 = cm.page_transfer_bytes(3, 4, 16, 8, num_layers=2,
                                  dtype="float32")
    int8 = cm.page_transfer_bytes(3, 4, 16, 8, num_layers=2, dtype="int8")
    assert fp32 == 2 * 2 * 3 * 4 * 16 * 8 * 4 == 24576
    # int8 pages at 1 byte/elem + the fp32 [page, head] scale sidecars
    # (K + V, per layer) — the sidecars MUST ride the transfer
    assert int8 == 2 * 2 * 3 * 4 * 16 * 8 * 1 + 2 * 2 * 3 * 4 * 4 == 6336
    # one formula with the pool: a full-pool transfer is the pool's bytes
    assert cm.page_transfer_bytes(6, 4, 16, 16, num_layers=2,
                                  dtype="int8") == \
        cm.paged_pool_bytes(6, 4, 16, 16, num_layers=2, dtype="int8")
    assert cm.page_transfer_bytes(0, 4, 16, 8, num_layers=2) == 0


def test_page_transfer_cost_is_gl_compatible_ppermute():
    # the hand-off models as a point-to-point ppermute between the two
    # replicas: payload == wire bytes (no reduction factor), one hop,
    # and it never claims in-body overlap (it runs between steps)
    c = cm.page_transfer_cost(3, 4, 16, 8, num_layers=2, dtype="int8")
    assert c.primitive == "ppermute" and c.axis_size == 2
    assert c.payload_bytes == c.wire_bytes == 6336
    assert c.hops == 1 and c.mult == 1
    assert not c.consumed_in_body and c.overlap_fraction() == 0.0
    spec = cm.HardwareSpec("x", peak_flops=1e12, hbm_bw=1e11)
    assert c.comm_seconds(spec) > 0
    assert "disagg" in c.provenance


def test_paged_pool_bytes_matches_real_pool():
    from paddle_tpu.models import GPTStackedForPretraining, gpt_tiny

    cfg = gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0)
    m = GPTStackedForPretraining(cfg)
    for dtype in ("float32", "bfloat16", "int8"):
        cache = m.new_paged_kv_cache(6, 16, dtype=dtype)
        want = cm.paged_pool_bytes(6, cfg.num_heads, 16, cfg.head_dim,
                                   num_layers=cfg.num_layers, dtype=dtype)
        assert cache.nbytes == want, (dtype, cache.nbytes, want)
        cache.release()


def test_decode_step_kv_bytes_int8_at_most_half_fp32():
    # ISSUE-17 acceptance: the decode step is memory-bound and int8 pages
    # must at least halve its HBM-upper bound vs fp32 at ANY context
    for ctx in (64, 128, 500, 4096):
        f = cm.decode_step_kv_bytes(ctx, 16, 128, 128, num_layers=24,
                                    dtype="float32")
        b = cm.decode_step_kv_bytes(ctx, 16, 128, 128, num_layers=24,
                                    dtype="bfloat16")
        i = cm.decode_step_kv_bytes(ctx, 16, 128, 128, num_layers=24,
                                    dtype="int8")
        assert f == 2 * 24 * ctx * 16 * 128 * 4
        assert b == f // 2
        assert i <= f // 2 and i < b
    # golden at one point, scale reads included: ceil(500/128)=4 pages
    assert cm.decode_step_kv_bytes(500, 16, 128, 128, num_layers=24,
                                   dtype="int8") \
        == 2 * 24 * 500 * 16 * 128 + 2 * 24 * 4 * 16 * 4


# ---------------------------------------------------------------------------
# v3: golden collective comm costs (bytes exact to the ring formulas,
# seconds exact to wire/ici_bw + hops * ici_latency)
# ---------------------------------------------------------------------------

_ICI = cm.HardwareSpec("golden", peak_flops=1e12, hbm_bw=1e12,
                       ici_bw=1e9, ici_latency=1e-6)


def _axis_mesh(n, name="dp"):
    from jax.sharding import Mesh

    devs = jax.devices()
    if len(devs) < n:
        pytest.skip(f"needs {n} devices, host has {len(devs)}")
    return Mesh(np.asarray(devs[:n]), (name,))


def _comm_rep(body, mesh, in_specs, out_specs, *args):
    fn = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return analysis.cost(fn, *args)


@pytest.mark.parametrize("n", [2, 4])
def test_psum_golden_bytes_and_seconds(n):
    from jax.sharding import PartitionSpec as P

    mesh = _axis_mesh(n)

    def body(x):
        return jax.lax.psum(x, "dp")

    # local payload: f32[1024] = 4096 B per chip
    rep = _comm_rep(body, mesh, (P("dp"),), P(),
                    _s((1024 * n,), jnp.float32))
    assert len(rep.collectives) == 1, rep.render()
    cc = rep.collectives[0]
    payload = 1024 * 4
    assert cc.payload_bytes == payload
    # ring all-reduce: 2(n-1)/n x payload per link, 2(n-1) hops
    assert cc.wire_bytes == 2 * (n - 1) * payload // n
    assert cc.hops == 2 * (n - 1)
    assert rep.comm_bytes == cc.wire_bytes
    expect_s = cc.wire_bytes / _ICI.ici_bw + cc.hops * _ICI.ici_latency
    assert rep.comm_seconds(_ICI) == pytest.approx(expect_s)
    assert rep.comm_seconds_by_axis(_ICI) == {
        "dp": pytest.approx(expect_s)}


@pytest.mark.parametrize("n", [2, 4])
def test_all_gather_golden_bytes(n):
    from jax.sharding import PartitionSpec as P

    mesh = _axis_mesh(n)

    def body(x):
        return jax.lax.all_gather(x, "dp")

    rep = _comm_rep(body, mesh, (P("dp"),), P(None, "dp"),
                    _s((1024 * n,), jnp.float32))
    assert len(rep.collectives) == 1, rep.render()
    cc = rep.collectives[0]
    # each link carries (n-1)/n of the GATHERED bytes (n x 4096)
    out_bytes = n * 1024 * 4
    assert cc.wire_bytes == (n - 1) * out_bytes // n
    assert cc.hops == n - 1


@pytest.mark.parametrize("n", [2, 4])
def test_reduce_scatter_golden_bytes(n):
    from jax.sharding import PartitionSpec as P

    mesh = _axis_mesh(n)

    def body(x):
        return jax.lax.psum_scatter(x, "dp", tiled=True)

    rep = _comm_rep(body, mesh, (P(),), P("dp"),
                    _s((1024 * n,), jnp.float32))
    assert len(rep.collectives) == 1, rep.render()
    cc = rep.collectives[0]
    # input payload (replicated local view): n x 1024 f32
    payload = n * 1024 * 4
    assert cc.payload_bytes == payload
    assert cc.wire_bytes == (n - 1) * payload // n
    assert cc.hops == n - 1


def test_scan_multiplies_comm_bytes():
    from jax.sharding import PartitionSpec as P

    mesh = _axis_mesh(2)
    trips = 3

    def body(x):
        def tick(c, _):
            return jax.lax.psum(c, "dp"), None

        out, _ = jax.lax.scan(tick, x, None, length=trips)
        return out

    rep = _comm_rep(body, mesh, (P("dp"),), P(),
                    _s((2048,), jnp.float32))
    assert len(rep.collectives) == 1, rep.render()
    cc = rep.collectives[0]
    assert cc.mult == trips
    one = 2 * (2 - 1) * (1024 * 4) // 2
    assert cc.wire_bytes == one          # per execution
    assert rep.comm_bytes == trips * one  # x scan trips


def test_overlap_fraction_golden():
    from jax.sharding import PartitionSpec as P

    mesh = _axis_mesh(2)

    def body(x, w):
        g = jax.lax.psum(x, "dp")
        h = x @ w                 # independent: scheduled behind the wire
        return g.sum() + h.sum()

    rep = _comm_rep(body, mesh, (P("dp", None), P()), P(),
                    _s((8, 256), jnp.float32), _s((256, 256), jnp.float32))
    assert len(rep.collectives) == 1, rep.render()
    cc = rep.collectives[0]
    # the dot between issue and first consumer is the hideable compute
    assert cc.overlap_flops == 2 * 4 * 256 * 256
    t = cc.comm_seconds(_ICI)
    expect = min(1.0, (cc.overlap_flops / _ICI.peak_flops) / t)
    assert 0.0 < expect < 1.0    # the spec keeps the golden case interior
    assert rep.overlap_fraction(_ICI) == pytest.approx(expect)


def test_no_collectives_overlap_is_one():
    rep = analysis.cost(lambda x: x * 2, _s((64,), jnp.float32))
    assert rep.collectives == []
    assert rep.comm_bytes == 0
    assert rep.overlap_fraction(_ICI) == 1.0
