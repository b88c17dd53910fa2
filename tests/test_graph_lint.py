"""Graph Lint: one positive and one negative case per pass (GL001-GL007),
baseline suppression round-trip, the jit.to_static compile hook, the
kernel-gate GL002 reasons, op-cache shape-key counters, and the CLI exit
codes (0 clean / 1 new findings / 2 internal error)."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import analysis
from paddle_tpu.analysis import Baseline, LintConfig

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _codes(report):
    return [f.code for f in report.findings]


def _s(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


# ---------------------------------------------------------------------------
# GL001 dtype-promotion
# ---------------------------------------------------------------------------

def test_gl001_upcast_feeding_dot_flagged():
    def fn(x, w):
        return x.astype(jnp.float32) @ w

    rep = analysis.lint(fn, _s((64, 64), jnp.bfloat16),
                        _s((64, 64), jnp.float32))
    hits = [f for f in rep.findings if f.code == "GL001"]
    assert hits and hits[0].severity == "error"
    assert "dot_general" in hits[0].primitive
    assert hits[0].provenance  # eqn provenance is attached


def test_gl001_mixed_dtype_dot_flagged():
    def fn(x, w):
        return jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    rep = analysis.lint(fn, _s((32, 32), jnp.float32),
                        _s((32, 32), jnp.bfloat16))
    assert any(f.code == "GL001" and "mixed" in f.detail for f in rep)


def test_gl001_pure_bf16_dot_clean():
    def fn(x, w):
        return x @ w

    rep = analysis.lint(fn, _s((64, 64), jnp.bfloat16),
                        _s((64, 64), jnp.bfloat16))
    assert "GL001" not in _codes(rep)


def test_gl001_intentional_fp32_softmax_not_flagged():
    # upcasting for VPU math (softmax/norm) is fine — only dots count
    def fn(x):
        return jax.nn.softmax(x.astype(jnp.float32), axis=-1)

    rep = analysis.lint(fn, _s((32, 32), jnp.bfloat16))
    assert "GL001" not in _codes(rep)


def test_gl001_x64_leak_flagged():
    def fn(x):
        return x.astype(jnp.float64) * 2.0

    rep = analysis.lint(fn, _s((8,), jnp.float32))
    assert any(f.code == "GL001" and "x64" in f.detail for f in rep)


# ---------------------------------------------------------------------------
# GL002 tile-misalignment
# ---------------------------------------------------------------------------

def test_gl002_misaligned_dot_flagged():
    def fn(x, w):
        return x @ w

    rep = analysis.lint(fn, _s((512, 1000)), _s((1000, 256)),
                        config=LintConfig(tile_min_bytes=1024))
    hits = [f for f in rep.findings if f.code == "GL002"]
    assert hits and "1000" in hits[0].message


def test_gl002_aligned_dot_clean():
    def fn(x, w):
        return x @ w

    rep = analysis.lint(fn, _s((512, 1024)), _s((1024, 256)),
                        config=LintConfig(tile_min_bytes=1024))
    assert "GL002" not in _codes(rep)


def test_gl002_small_operands_ignored():
    # dims at/below one tile pad once — not actionable, not flagged
    def fn(x, w):
        return x @ w

    rep = analysis.lint(fn, _s((8, 64)), _s((64, 100)))
    assert "GL002" not in _codes(rep)


def test_gl002_matches_kernel_gate_rules():
    """The linter and the Pallas eligibility gates share one rule set."""
    from paddle_tpu.ops.pallas_kernels.decode_attention import (
        decode_shape_supported, decode_shape_unsupported_reason,
    )
    from paddle_tpu.ops.pallas_kernels.flash_attention import (
        shape_supported, shape_unsupported_reason,
    )

    assert shape_supported(512, 64) and shape_unsupported_reason(512, 64) is None
    r = shape_unsupported_reason(100, 48)
    assert not shape_supported(100, 48)
    assert r.code == "GL002" and "seq_len=100" in str(r) and "head_dim=48" in str(r)

    assert decode_shape_supported(128, 64)
    r = decode_shape_unsupported_reason(96, 64)
    assert not decode_shape_supported(96, 64)
    assert r.code == "GL002" and r.kernel == "decode_attention"


# ---------------------------------------------------------------------------
# GL003 host-sync
# ---------------------------------------------------------------------------

def test_gl003_callback_flagged():
    def fn(x):
        jax.debug.print("x sum {s}", s=x.sum())
        return x * 2

    rep = analysis.lint(fn, _s((8,)))
    assert any(f.code == "GL003" for f in rep)


def test_gl003_pure_compute_clean():
    def fn(x):
        return x * 2

    rep = analysis.lint(fn, _s((8,)))
    assert "GL003" not in _codes(rep)


# ---------------------------------------------------------------------------
# GL004 donation-miss
# ---------------------------------------------------------------------------

_DON_CFG = LintConfig(donation_min_bytes=4096)


def _cache_step(cache, x):
    return cache.at[0].set(x), x.sum()


def test_gl004_undonated_large_buffer_flagged():
    rep = analysis.lint(_cache_step, _s((64, 64)), _s((64,)),
                        config=_DON_CFG)
    hits = [f for f in rep.findings if f.code == "GL004"]
    assert hits and "input 0" in hits[0].message


def test_gl004_donated_buffer_clean():
    rep = analysis.lint(_cache_step, _s((64, 64)), _s((64,)),
                        config=_DON_CFG, donate_argnums=(0,))
    assert "GL004" not in _codes(rep)


def test_gl004_passthrough_input_not_flagged():
    # an input returned unchanged is alive — donating it would be wrong
    def fn(big, x):
        return big, big.sum() + x

    rep = analysis.lint(fn, _s((64, 64)), _s(()), config=_DON_CFG)
    assert "GL004" not in _codes(rep)


# ---------------------------------------------------------------------------
# GL005 dead-code
# ---------------------------------------------------------------------------

def test_gl005_dead_eqn_flagged():
    def fn(x):
        _wasted = x @ x.T  # traced, never used
        return x + 1

    rep = analysis.lint(fn, _s((16, 16)))
    assert any(f.code == "GL005" for f in rep)


def test_gl005_live_graph_clean():
    def fn(x):
        y = x @ x.T
        return x + y.sum()

    rep = analysis.lint(fn, _s((16, 16)))
    assert "GL005" not in _codes(rep)


def test_gl005_effectful_eqn_not_dead():
    def fn(x):
        jax.debug.print("{s}", s=x.sum())  # unused result, but effectful
        return x + 1

    rep = analysis.lint(fn, _s((8,)))
    assert "GL005" not in _codes(rep)


# ---------------------------------------------------------------------------
# GL006 intermediate-blowup
# ---------------------------------------------------------------------------

_BLOW_CFG = LintConfig(blowup_min_bytes=4096, blowup_ratio=4.0)


def test_gl006_broadcast_blowup_flagged():
    def fn(x):
        return jnp.broadcast_to(x[:, None], (128, 4096)) * 1.0

    rep = analysis.lint(fn, _s((128,)), config=_BLOW_CFG)
    assert any(f.code == "GL006" for f in rep)


def test_gl006_proportionate_output_clean():
    def fn(x):
        return jnp.concatenate([x, x], axis=0)  # 2x < ratio 4x

    rep = analysis.lint(fn, _s((128, 128)), config=_BLOW_CFG)
    assert "GL006" not in _codes(rep)


# ---------------------------------------------------------------------------
# GL007 retrace-churn (runtime counters)
# ---------------------------------------------------------------------------

def test_gl007_shape_churn_flagged():
    cfg = LintConfig(churn_shape_keys=4)
    rep = analysis.churn_findings(
        cfg, op_stats={"matmul": {"shape_keys": 9}},
        static_fns={}, trace_counts={})
    assert any(f.code == "GL007" and "matmul" in f.message for f in rep)


def test_gl007_decode_retrace_flagged():
    cfg = LintConfig(churn_max_decode_traces=6)
    rep = analysis.churn_findings(
        cfg, op_stats={}, static_fns={}, trace_counts={"decode": 40})
    assert any(f.code == "GL007" and "decode" in f.message for f in rep)


def test_gl007_quiet_counters_clean():
    rep = analysis.churn_findings(
        op_stats={"matmul": {"shape_keys": 3}},
        static_fns={"train_step": 1},
        trace_counts={"prefill": 2, "decode": 2})
    assert len(rep) == 0


def test_gl007_trace_limit_scales_with_compiled_programs():
    """Trace counts are process-global; N legitimately cached engines pay
    N compiles' worth of traces — that must NOT read as churn."""
    cfg = LintConfig(churn_max_decode_traces=6)
    # 4 engines x 2 traces each = 8 > 6, but 4 compiled programs are known
    rep = analysis.churn_findings(
        cfg, op_stats={}, static_fns={}, trace_counts={"decode": 8},
        program_counts={"decode": 4})
    assert len(rep) == 0
    # the same count against ONE program is genuine churn
    rep = analysis.churn_findings(
        cfg, op_stats={}, static_fns={}, trace_counts={"decode": 8},
        program_counts={"decode": 1})
    assert any(f.code == "GL007" for f in rep)


def test_op_cache_stats_export_shape_keys():
    """core/op_cache.stats() exposes per-op distinct shape-key counts
    (the GL007 feed) without any logging flag."""
    from paddle_tpu.core import op_cache

    op_cache.reset_stats()
    for n in (3, 5, 7, 9):
        pt.to_tensor(np.ones((n, 4), np.float32)) + pt.to_tensor(
            np.ones((n, 4), np.float32))
    st = op_cache.stats()
    assert st["add"]["shape_keys"] == 4
    op_cache.reset_stats()
    assert op_cache.stats() == {}


# ---------------------------------------------------------------------------
# baseline suppression round-trip
# ---------------------------------------------------------------------------

def test_baseline_round_trip(tmp_path):
    def fn(x, w):
        return x.astype(jnp.float32) @ w

    rep = analysis.lint(fn, _s((64, 64), jnp.bfloat16),
                        _s((64, 64), jnp.float32))
    assert rep.findings
    base = Baseline()
    for f in rep.findings:
        base.add(f, "accepted for the round-trip test")
    path = str(tmp_path / "baseline.json")
    base.save(path)

    loaded = Baseline.load(path)
    assert loaded.suppressions == base.suppressions
    # same program -> fully suppressed
    rep2 = analysis.lint(fn, _s((64, 64), jnp.bfloat16),
                         _s((64, 64), jnp.float32))
    assert loaded.filter_new(rep2.findings) == []

    # a NEW finding (different shapes -> different fingerprint) gets through
    rep3 = analysis.lint(fn, _s((128, 128), jnp.bfloat16),
                         _s((128, 128), jnp.float32), program="fn")
    assert loaded.filter_new(rep3.findings)


def test_baseline_rejects_unknown_version(tmp_path):
    path = tmp_path / "bad.json"
    path.write_text('{"version": 99, "suppressions": []}')
    with pytest.raises(ValueError):
        Baseline.load(str(path))


# ---------------------------------------------------------------------------
# jit.to_static compile hook
# ---------------------------------------------------------------------------

def test_to_static_hook_collects_reports():
    analysis.clear_reports()
    pt.set_flags({"FLAGS_graph_lint": True})
    try:
        w = pt.to_tensor(np.ones((16, 16), np.float32))
        w.stop_gradient = False

        @pt.jit.to_static
        def step(x):
            y = pt.matmul(x, w)
            return pt.mean(y)

        out = step(pt.to_tensor(np.ones((4, 16), np.float32)))
        assert np.isfinite(float(out))
        reps = step.lint_reports()
        assert len(reps) == 1 and reps[0].program == "step"
        assert any(r.program == "step" for r in analysis.reports())
    finally:
        pt.set_flags({"FLAGS_graph_lint": False})
        analysis.clear_reports()


def test_to_static_hook_off_by_default():
    analysis.clear_reports()

    @pt.jit.to_static
    def step(x):
        return x * 2

    step(pt.to_tensor(np.ones((4,), np.float32)))
    assert step.lint_reports() == []
    assert analysis.reports() == []


# ---------------------------------------------------------------------------
# CLI exit codes (in-process; targets=none keeps it fast — the full
# train/decode targets are exercised by the slow test below and the
# run_tests.sh gate)
# ---------------------------------------------------------------------------

def _cli():
    spec = importlib.util.spec_from_file_location(
        "graph_lint_cli", os.path.join(_REPO, "tools", "graph_lint.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_cli_exit_codes_fast(tmp_path, capsys):
    cli = _cli()
    # 0: nothing to lint, nothing new
    assert cli.run(["--targets", "none"]) == 0
    # 1: an injected bf16->fp32 promotion is a NEW finding
    assert cli.run(["--targets", "none", "--inject", "gl001"]) == 1
    out = capsys.readouterr().out
    assert "GL001" in out and "promoted_matmul" in out  # code + provenance
    # 1: dropping donation on a cache-shaped buffer
    assert cli.run(["--targets", "none", "--inject", "gl004"]) == 1
    out = capsys.readouterr().out
    assert "GL004" in out
    # 0: the injected finding is suppressed once baselined
    base = str(tmp_path / "b.json")
    assert cli.run(["--targets", "none", "--inject", "gl001",
                    "--write-baseline", base]) == 0
    assert cli.run(["--targets", "none", "--inject", "gl001",
                    "--baseline", base]) == 0
    # 2: internal error (unknown target), NOT a lint finding
    assert cli.run(["--targets", "bogus"]) == 2


@pytest.mark.slow
def test_cli_bench_models_clean_against_committed_baseline():
    """The acceptance gate: the bench GPT train step + decode engines lint
    clean against the committed baseline (exit 0)."""
    cli = _cli()
    assert cli.run(["--baseline"]) == 0


# ---------------------------------------------------------------------------
# the real fixes stay fixed: bf16 model programs keep bf16 matmuls
# ---------------------------------------------------------------------------

def test_bf16_decode_program_has_no_promoted_dots():
    """Regression for the satellite fix: a pure-bf16 stacked GPT's decode
    program must not silently run its projections in fp32."""
    from paddle_tpu.models import GPTStackedForPretraining, gpt_tiny

    analysis.clear_reports()
    pt.set_flags({"FLAGS_graph_lint": True})
    try:
        cfg = gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0)
        pt.seed(0)
        m = GPTStackedForPretraining(cfg)
        pt.amp.decorate(m, level="O2", dtype="bfloat16")
        m.eval()
        ids = pt.to_tensor(np.arange(12, dtype=np.int64).reshape(2, 6) % cfg.vocab_size)
        m.generate(ids, max_new_tokens=2, max_seq_len=128,
                   cache_dtype="bfloat16")
        reps = [r for r in analysis.reports()
                if r.program in ("prefill_step", "decode_step")]
        assert reps
        bad = [f for r in reps for f in r.findings if f.code == "GL001"]
        assert bad == [], "\n".join(f.render() for f in bad)
    finally:
        pt.set_flags({"FLAGS_graph_lint": False})
        analysis.clear_reports()


# ---------------------------------------------------------------------------
# v3 comm passes: GL008-GL011 (one positive + one negative each)
# ---------------------------------------------------------------------------

def _axis_mesh(n, name="dp"):
    from jax.sharding import Mesh

    devs = jax.devices()
    if len(devs) < n:
        pytest.skip(f"needs {n} devices, host has {len(devs)}")
    return Mesh(np.asarray(devs[:n]), (name,))


def _shmap(body, mesh, in_specs, out_specs):
    # check_vma off: the toy bodies reduce dp-varying values locally on
    # purpose (the lint passes care about the collectives, not the rep
    # typing)
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def test_gl008_unoverlapped_collective_flagged():
    from jax.sharding import PartitionSpec as P

    mesh = _axis_mesh(2)

    def body(x, w):
        g = jax.lax.psum(x, "dp")
        r = g.sum()        # blocks on the wire immediately...
        h = x @ w          # ...with this independent dot still pending
        return r + h.sum()

    fn = _shmap(body, mesh, (P("dp", None), P()), P())
    cfg = LintConfig(gl008_min_pending_flops=1000)
    rep = analysis.lint(fn, _s((8, 64)), _s((64, 64)), config=cfg)
    gl8 = [f for f in rep.findings if f.code == "GL008"]
    assert gl8, rep.render()
    assert "psum" in gl8[0].detail


def test_gl008_overlapped_collective_clean():
    from jax.sharding import PartitionSpec as P

    mesh = _axis_mesh(2)

    def body(x, w):
        g = jax.lax.psum(x, "dp")
        h = x @ w          # independent dot between issue and consumer:
        return g.sum() + h.sum()  # the wire hides behind it (overlap)

    fn = _shmap(body, mesh, (P("dp", None), P()), P())
    cfg = LintConfig(gl008_min_pending_flops=1000)
    rep = analysis.lint(fn, _s((8, 64)), _s((64, 64)), config=cfg)
    assert "GL008" not in _codes(rep), rep.render()


def test_gl009_replicated_state_flagged_sharded_clean():
    from jax.sharding import PartitionSpec as P

    mesh = _axis_mesh(2)

    def body(x, m):
        return (x * 2).sum() + m.sum()

    cfg = LintConfig(gl009_min_bytes=1024)
    # m replicated over the manual dp axis -> fires, quoting the shard win
    rep = analysis.lint(_shmap(body, mesh, (P("dp", None), P()), P()),
                        _s((8, 64)), _s((64, 64)), config=cfg)
    gl9 = [f for f in rep.findings if f.code == "GL009"]
    assert gl9, rep.render()
    assert "dp" in gl9[0].detail and "invar[1]" in gl9[0].detail
    assert gl9[0].cost and "reclaimable" in gl9[0].cost
    # x sharded over dp never fires; sharding m silences the pass
    rep2 = analysis.lint(
        _shmap(body, mesh, (P("dp", None), P("dp", None)), P()),
        _s((8, 64)), _s((64, 64)), config=cfg)
    assert "GL009" not in _codes(rep2), rep2.render()


def test_gl010_misaligned_collective_payload_flagged():
    from jax.sharding import PartitionSpec as P

    mesh = _axis_mesh(2)

    def body(x):
        return jax.lax.psum(x, "dp")

    cfg = LintConfig(tile_min_bytes=64)
    # 3x129 f32: 387 elems don't split into 2 ring chunks AND the
    # trailing dim breaks (8, 128) tiling
    rep = analysis.lint(_shmap(body, mesh, (P(),), P()),
                        _s((3, 129)), config=cfg)
    gl10 = [f for f in rep.findings if f.code == "GL010"]
    assert gl10, rep.render()
    assert "psum" in gl10[0].detail
    # aligned payload (8x128, evenly chunked): clean
    rep2 = analysis.lint(_shmap(body, mesh, (P(),), P()),
                         _s((8, 128)), config=cfg)
    assert "GL010" not in [f.code for f in rep2.findings], rep2.render()


def test_gl011_degenerate_axis_flagged_real_axis_clean():
    from jax.sharding import PartitionSpec as P

    mesh1 = _axis_mesh(1, "one")

    def body(x):
        return jax.lax.psum(x, "one")

    rep = analysis.lint(_shmap(body, mesh1, (P(),), P()), _s((512,)))
    gl11 = [f for f in rep.findings if f.code == "GL011"]
    assert gl11, rep.render()
    assert gl11[0].severity == "info"

    mesh2 = _axis_mesh(2)

    def body2(x):
        return jax.lax.psum(x, "dp")

    rep2 = analysis.lint(_shmap(body2, mesh2, (P(),), P()), _s((512,)))
    assert "GL011" not in _codes(rep2), rep2.render()


def test_gl009_baseline_round_trip():
    """A GL009 finding suppresses through the fingerprint machinery like
    any v1 code: same program -> filtered; reshaped state -> NEW."""
    from jax.sharding import PartitionSpec as P

    mesh = _axis_mesh(2)

    def body(x, m):
        return (x * 2).sum() + m.sum()

    cfg = LintConfig(gl009_min_bytes=1024)
    fn = _shmap(body, mesh, (P("dp", None), P()), P())
    rep = analysis.lint(fn, _s((8, 64)), _s((64, 64)), config=cfg,
                        program="rt")
    gl9 = [f for f in rep.findings if f.code == "GL009"]
    assert gl9
    base = Baseline()
    for f in gl9:
        base.add(f, "round-trip")
    assert base.filter_new(gl9) == []
    rep2 = analysis.lint(fn, _s((8, 128)), _s((128, 128)), config=cfg,
                         program="rt")
    new = [f for f in base.filter_new(rep2.findings) if f.code == "GL009"]
    assert new, "reshaped replicated state must be a NEW finding"


def test_cli_inject_gl009_trips_and_baselines(tmp_path, capsys):
    cli = _cli()
    assert cli.run(["--targets", "none", "--inject", "gl009"]) == 1
    out = capsys.readouterr().out
    assert "GL009" in out and "inject:gl009" in out
    base = str(tmp_path / "b9.json")
    assert cli.run(["--targets", "none", "--inject", "gl009",
                    "--write-baseline", base]) == 0
    assert cli.run(["--targets", "none", "--inject", "gl009",
                    "--baseline", base]) == 0


def test_int8_fused_step_program_gl001_clean():
    """Regression pin for the int8 serving variant: the quantized hot
    path lints under its own program name (fused_step_int8 — explicit
    dequant + per-row requant must never read as a silent promotion)."""
    from paddle_tpu.models import GPTStackedForPretraining, gpt_tiny
    from paddle_tpu.serving import ServingEngine

    analysis.clear_reports()
    pt.set_flags({"FLAGS_graph_lint": True})
    try:
        pt.seed(0)
        cfg = gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0)
        m = GPTStackedForPretraining(cfg)
        pt.amp.decorate(m, level="O2", dtype="bfloat16")
        m.eval()
        eng = ServingEngine(m, num_slots=2, page_size=16, max_context=32,
                            kv_dtype="int8", weight_dtype="int8")
        try:
            eng.submit(np.arange(5, dtype=np.int64) % cfg.vocab_size, 3)
            eng.run_until_idle()
            reps = [r for r in eng.lint_reports()
                    if r.program == "fused_step_int8"]
            assert reps, "int8 engine did not lint under fused_step_int8"
            bad = [f for r in reps for f in r.findings
                   if f.code == "GL001"]
            assert bad == [], "\n".join(f.render() for f in bad)
        finally:
            eng.close()
    finally:
        pt.set_flags({"FLAGS_graph_lint": False})
        analysis.clear_reports()
