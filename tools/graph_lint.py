#!/usr/bin/env python
"""Graph Lint CLI: lint the bench models' compiled programs.

Builds scaled-down stand-ins of the bench workloads (same graph structure
and dtype regime as bench.py's pure-bf16 rungs — a bf16-decorated stacked
GPT) and lints every compiled program:

- ``train``:  the fused fwd+bwd+AdamW train step (jit.to_static)
- ``decode``: the decode engine's prefill + decode programs (generate())
- ``serve``:  the paged fused serving steps (fp32/bf16, int8, spec+LoRA,
  mesh-sharded)
- ``mesh``:   SPMD programs with jaxpr-visible collectives under a real
  dp x mp device mesh (``--mesh-shape``): a Megatron-style fused train
  step, ring attention, the pipeline schedule, and the sharded serving
  engine — the GL008-GL011 / comm-cost-model targets (Graph Lint v3)
- ``churn``:  the GL007 runtime pass over dispatch/op-cache/trace counters

Findings are compared against a committed baseline-suppression file
(``tools/graph_lint_baseline.json``) so CI fails only on NEW findings at
or above the failure severity (default: warning; "info" findings are
printed but never gate).

Exit codes:
  0  no new findings (everything clean or baseline-suppressed)
  1  new findings at/above the failure severity
  2  internal error (the lint itself failed — NOT a lint finding)

Runs on CPU (JAX_PLATFORMS=cpu; the jaxpr is platform-independent) or on a
real TPU host unchanged.  ``--inject gl001`` / ``--inject gl004`` add a
deliberately-hazardous test model to prove the gate trips (exit 1) with
the right code and eqn provenance.

Usage:
  python tools/graph_lint.py --baseline           # the CI gate
  python tools/graph_lint.py                      # strict (no baseline)
  python tools/graph_lint.py --write-baseline     # refresh the baseline
  python tools/graph_lint.py --baseline --inject gl001   # must exit 1
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

DEFAULT_BASELINE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "graph_lint_baseline.json")

# the scaled-down bench stand-in: tiny dims, but the SAME program structure
# (stacked scan + remat, fused CE head, donated state, decode engine,
# paged-serving engine) and the same pure-bf16 dtype regime as bench.py's
# headline rungs.  Fixed shapes keep finding fingerprints stable for the
# baseline.
_TRAIN_BATCH, _TRAIN_SEQ = 2, 64
_DEC_BATCH, _DEC_PROMPT, _DEC_NEW, _DEC_MAXSEQ = 2, 8, 3, 128
_SRV_SLOTS, _SRV_PAGE, _SRV_CTX, _SRV_NEW = 2, 16, 64, 3
_SRV_PROMPTS = (5, 9)


def _build_model(pt, cfg):
    pt.seed(0)
    from paddle_tpu.models import GPTStackedForPretraining

    model = GPTStackedForPretraining(cfg)
    # bench pure-bf16 regime: bf16 params + bf16 moments (amp O2 decorate,
    # adam multi_precision=False) — the dtype discipline under lint
    pt.amp.decorate(model, level="O2", dtype="bfloat16")
    return model


def _lint_train(pt, np):
    from paddle_tpu.models import gpt_tiny

    cfg = gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0)
    model = _build_model(pt, cfg)
    opt = pt.optimizer.AdamW(learning_rate=1e-4,
                             parameters=model.parameters(),
                             multi_precision=False)
    rng = np.random.RandomState(0)
    ids = pt.to_tensor(
        rng.randint(0, cfg.vocab_size, (_TRAIN_BATCH, _TRAIN_SEQ)),
        dtype="int64")
    labels = pt.to_tensor(
        rng.randint(0, cfg.vocab_size, (_TRAIN_BATCH, _TRAIN_SEQ)),
        dtype="int64")

    @pt.jit.to_static
    def train_step(ids, labels):
        with pt.amp.auto_cast(enable=True, level="O1", dtype="bfloat16"):
            loss = model(ids, labels=labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    train_step(ids, labels)  # compile -> the FLAGS_graph_lint hook lints

    # the fused master-weight regime (bf16 params + fp32 masters/moments +
    # global-norm clip through FusedTrainStep): the GL004 donation pass
    # over the optimizer state — masters and moments are the largest
    # consumed-and-rebound buffers in the step, and an un-donated one
    # would double-buffer the whole optimizer state every step.  This is
    # the regression the train-perf push is designed to prevent.
    from paddle_tpu.models import gpt_tiny as _tiny
    from paddle_tpu.nn.clip import ClipGradByGlobalNorm

    cfg2 = _tiny(hidden_dropout=0.0, attention_dropout=0.0)
    model2 = _build_model(pt, cfg2)
    opt2 = pt.optimizer.AdamW(learning_rate=1e-4,
                              parameters=model2.parameters(),
                              multi_precision=True,
                              grad_clip=ClipGradByGlobalNorm(1.0))
    fused = pt.optimizer.FusedTrainStep(
        lambda ids, labels: model2(ids, labels=labels), opt2,
        amp_level="O1", amp_dtype="bfloat16")
    ids2 = pt.to_tensor(
        rng.randint(0, cfg2.vocab_size, (_TRAIN_BATCH, _TRAIN_SEQ)),
        dtype="int64")
    labels2 = pt.to_tensor(
        rng.randint(0, cfg2.vocab_size, (_TRAIN_BATCH, _TRAIN_SEQ)),
        dtype="int64")
    fused(ids2, labels2)  # compile -> hook lints 'fused_train_step'


def _lint_decode(pt, np):
    from paddle_tpu.models import gpt_tiny

    cfg = gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0)
    model = _build_model(pt, cfg)
    model.eval()
    rng = np.random.RandomState(1)
    prompt = pt.to_tensor(
        rng.randint(0, cfg.vocab_size, (_DEC_BATCH, _DEC_PROMPT)),
        dtype="int64")
    model.generate(prompt, max_new_tokens=_DEC_NEW,
                   max_seq_len=_DEC_MAXSEQ, cache_dtype="bfloat16")


def _lint_serve(pt, np):
    """The serving paged decode step — the hottest program under load, now
    a DEFAULT lint target instead of only being reachable via
    ``ServingEngine.lint_reports()``.  On hosts with >= 2 devices the
    mesh-sharded fused step (shard_map'd per-head attention + GSPMD
    column/row-parallel weights) lints too: the walkers must recurse into
    the shard_map body without crashing and stay exit-0."""
    import jax

    from paddle_tpu.models import gpt_tiny
    from paddle_tpu.serving import ServingEngine

    cfg = gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0)
    model = _build_model(pt, cfg)
    model.eval()
    rng = np.random.RandomState(2)
    eng = ServingEngine(model, num_slots=_SRV_SLOTS, page_size=_SRV_PAGE,
                        max_context=_SRV_CTX, cache_dtype="bfloat16")
    try:
        for plen in _SRV_PROMPTS:
            eng.submit(rng.randint(0, cfg.vocab_size, (plen,)), _SRV_NEW)
        eng.run_until_idle()
    finally:
        eng.close()
    # quantized step variant (ISSUE-17): int8 KV pages (in-kernel dequant
    # epilogue) + int8 weight projections.  The dequant is an explicit
    # astype+scale and the matmuls re-quantize per row, so GL001 must stay
    # silent — any finding here means a silent promotion crept into the
    # quantized hot path.
    model_q = _build_model(pt, cfg)
    model_q.eval()
    eng = ServingEngine(model_q, num_slots=_SRV_SLOTS, page_size=_SRV_PAGE,
                        max_context=_SRV_CTX, kv_dtype="int8",
                        weight_dtype="int8")
    try:
        for plen in _SRV_PROMPTS:
            eng.submit(rng.randint(0, cfg.vocab_size, (plen,)), _SRV_NEW)
        eng.run_until_idle()
    finally:
        eng.close()
    # speculative + multi-tenant LoRA step variants (ISSUE-15): the
    # verify program (in-graph accept/reject over gathered k+1 rows) and
    # the draft program lint alongside a LoRA-pooled step whose gathered
    # low-rank deltas must stay GL001-clean on a pure-bf16 model
    from paddle_tpu.serving import (
        LoRAAdapterPool, SpeculativeEngine, random_adapter,
    )

    model2 = _build_model(pt, cfg)
    model2.eval()
    pool = LoRAAdapterPool(cfg, num_adapter_pages=2, rank=4,
                           dtype="bfloat16")
    pool.register("tenant", random_adapter(cfg, 4, rng))
    eng = SpeculativeEngine(model2, model2, spec_k=2,
                            num_slots=_SRV_SLOTS, page_size=_SRV_PAGE,
                            max_context=_SRV_CTX, cache_dtype="bfloat16",
                            lora=pool)
    try:
        for i, plen in enumerate(_SRV_PROMPTS):
            eng.submit(rng.randint(0, cfg.vocab_size, (plen,)), _SRV_NEW,
                       adapter="tenant" if i % 2 == 0 else None)
        eng.run_until_idle()
    finally:
        eng.close()
    if len(jax.devices()) >= 2:
        from paddle_tpu.serving import ShardedServingEngine

        model_s = _build_model(pt, cfg)
        model_s.eval()
        eng = ShardedServingEngine(model_s, dp=1, mp=2,
                                   num_slots=_SRV_SLOTS,
                                   page_size=_SRV_PAGE,
                                   max_context=_SRV_CTX,
                                   cache_dtype="bfloat16")
        try:
            for plen in _SRV_PROMPTS:
                eng.submit(rng.randint(0, cfg.vocab_size, (plen,)),
                           _SRV_NEW)
            eng.run_until_idle()
        finally:
            eng.close()


# the dp x mp fused-train-step stand-in (mesh target): Megatron column/
# row-parallel 2-matmul MLP with a hand-rolled backward, grad psums over
# 'dp', and an AdamW update on fp32 masters+moments that are REPLICATED
# over 'dp' (the exact ZeRO hazard GL009 quantifies — ROADMAP item 1).
# H is sized so the bf16 weights stay under the GL009 floor (the standard
# DP regime) while the fp32 optimizer state lands above it.
_MESH_B, _MESH_H, _MESH_F = 8, 384, 2048


def _mesh_train_step_fn(jax, jnp):
    def mesh_train_step(x, w1, w2, m1, v1, mw1, m2, v2, mw2):
        # forward: column-parallel w1, row-parallel w2 (psum over 'mp')
        h = jnp.maximum(x @ w1, 0)
        y = jax.lax.psum(h @ w2, "mp")
        yf = y.astype(jnp.float32)
        # hand-rolled backward (shape-correct; values are irrelevant to a
        # static lint — what matters is the graph: two big grads, two
        # all-reduces, an update chain).  Dots stay on the bf16 MXU path
        # with fp32 grads cast AFTER the contraction (GL001 discipline).
        gy = (yf * (2.0 / yf.size)).astype(jnp.bfloat16)
        g2 = (h.T @ gy).astype(jnp.float32)
        gh = ((gy @ w2.T).astype(jnp.float32)
              * (h > 0)).astype(jnp.bfloat16)
        g1 = (x.T @ gh).astype(jnp.float32)
        # grad all-reduce over 'dp' — the bucketed-async candidate.  w2's
        # whole update sits between psum(g1) and g1's first consumer, so
        # the overlap fraction of the g1 reduction is statically nonzero.
        g1r = jax.lax.psum(g1, "dp")
        g2r = jax.lax.psum(g2, "dp")
        b1, b2, lr, eps = 0.9, 0.999, 1e-4, 1e-8
        m2n = b1 * m2 + (1 - b1) * g2r
        v2n = b2 * v2 + (1 - b2) * g2r * g2r
        mw2n = mw2 - lr * m2n / (jnp.sqrt(v2n) + eps)
        m1n = b1 * m1 + (1 - b1) * g1r
        v1n = b2 * v1 + (1 - b2) * g1r * g1r
        mw1n = mw1 - lr * m1n / (jnp.sqrt(v1n) + eps)
        # loss reduced LAST: a pmean before the backward would block the
        # program on a collective with the whole backward still pending
        # (its own GL008 finding — the linter caught exactly that in an
        # earlier draft of this stand-in)
        loss = jax.lax.pmean((yf ** 2).mean(), "dp")
        return (loss, mw1n.astype(jnp.bfloat16), mw2n.astype(jnp.bfloat16),
                m1n, v1n, mw1n, m2n, v2n, mw2n)

    return mesh_train_step


def _lint_mesh(analysis, mesh_shape, with_cost):
    """The ``mesh`` target: jaxpr-visible-collective programs linted and
    (optionally) costed under a real dp x mp device mesh.  Returns
    (lint_reports, cost_reports); skips with a note when the host has too
    few devices (the jaxpr needs a concrete mesh)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    dp, mp = mesh_shape
    need = dp * mp
    devs = jax.devices()
    if need < 2 or len(devs) < need:
        print(f"graph_lint: mesh target skipped (needs {max(need, 2)} "
              f"devices for --mesh-shape {dp},{mp}; have {len(devs)})")
        return [], []
    lint_reports, cost_reports = [], []

    def _one(fn, args, program, donate=()):
        lint_reports.append(analysis.lint(fn, *args, program=program,
                                          donate_argnums=donate))
        if with_cost:
            cost_reports.append(analysis.cost(fn, *args, program=program))

    # (a) the dp x mp fused train-step stand-in
    mesh = Mesh(np.array(devs[:need]).reshape(dp, mp), ("dp", "mp"))
    B, H, F = _MESH_B, _MESH_H, _MESH_F
    col, row = P(None, "mp"), P("mp", None)
    specs = (P("dp", None), col, row,
             col, col, col, row, row, row)
    out_specs = (P(), col, row, col, col, col, row, row, row)
    step = jax.shard_map(_mesh_train_step_fn(jax, jnp), mesh=mesh,
                         in_specs=specs, out_specs=out_specs)
    sds = jax.ShapeDtypeStruct
    args = (sds((B, H), jnp.bfloat16),
            sds((H, F), jnp.bfloat16), sds((F, H), jnp.bfloat16),
            sds((H, F), jnp.float32), sds((H, F), jnp.float32),
            sds((H, F), jnp.float32),
            sds((F, H), jnp.float32), sds((F, H), jnp.float32),
            sds((F, H), jnp.float32))
    # weights + optimizer state donated, as the real fused step does
    _one(step, args, f"mesh_train_step[dp{dp}xmp{mp}]",
         donate=tuple(range(1, 9)))

    # (b) ring attention over a sequence-parallel axis (the ppermute ring)
    from functools import partial

    from paddle_tpu.nn.functional.ring_attention import ring_attention_raw

    sp = 2
    sp_mesh = Mesh(np.array(devs[:sp]), ("sp",))
    qspec = P(None, "sp", None, None)
    ring = jax.shard_map(
        partial(ring_attention_raw, causal=True, axis_name="sp"),
        mesh=sp_mesh, in_specs=(qspec, qspec, qspec), out_specs=qspec,
        check_vma=False)
    qkv = sds((2, 256, 4, 64), jnp.float32)
    _one(ring, (qkv, qkv, qkv), f"mesh_ring_attention[sp{sp}]")

    # (c) the SPMD pipeline schedule (ppermute ticks + final psum)
    from paddle_tpu.distributed import mesh as _mesh_mod
    from paddle_tpu.distributed.fleet.meta_parallel.pp_spmd import (
        pipeline_blocks,
    )

    prev_mesh = _mesh_mod.get_mesh() if _mesh_mod.has_mesh() else None
    pp_mesh = _mesh_mod.build_mesh({"pp": 2}, devs[:2])
    _mesh_mod.set_mesh(pp_mesh)
    try:
        def pp_step(stacked_w, x_micro):
            def block(params, h):
                (w,) = params
                return jnp.maximum(h @ w, 0)

            return pipeline_blocks(block, (stacked_w,), x_micro,
                                   layers_per_stage=1)

        _one(pp_step,
             (sds((2, 128, 128), jnp.float32),
              sds((2, 2, 128), jnp.float32)),
             "mesh_pipeline_blocks[pp2]")
    finally:
        if prev_mesh is not None:
            _mesh_mod.set_mesh(prev_mesh)

    return lint_reports, cost_reports


def _lint_mesh_serve(pt, np, mesh_shape):
    """The sharded serving engine at the requested mesh shape: its fused
    step compiles through the FLAGS_graph_lint hook (reports land in
    ``analysis.reports()``)."""
    import jax

    dp, mp = mesh_shape
    if dp * mp < 2 or len(jax.devices()) < dp * mp:
        return
    from paddle_tpu.models import gpt_tiny
    from paddle_tpu.serving import ShardedServingEngine

    cfg = gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0)
    model = _build_model(pt, cfg)
    model.eval()
    rng = np.random.RandomState(3)
    eng = ShardedServingEngine(model, dp=dp, mp=mp,
                               num_slots=_SRV_SLOTS, page_size=_SRV_PAGE,
                               max_context=_SRV_CTX,
                               cache_dtype="bfloat16")
    try:
        for plen in _SRV_PROMPTS:
            eng.submit(rng.randint(0, cfg.vocab_size, (plen,)), _SRV_NEW)
        eng.run_until_idle()
    finally:
        eng.close()


def _inject(analysis, code: str):
    """A deliberately-hazardous test model per code: proves the gate exits
    1 with the right GL code and eqn provenance."""
    import jax
    import jax.numpy as jnp

    code = code.lower()
    if code == "gl001":
        def promoted_matmul(x, w):
            # the hazard under test: bf16 activations silently upcast to
            # fp32 before the contraction
            return x.astype(jnp.float32) @ w

        return analysis.lint(
            promoted_matmul,
            jax.ShapeDtypeStruct((256, 256), jnp.bfloat16),
            jax.ShapeDtypeStruct((256, 256), jnp.float32),
            program="inject:gl001")
    if code == "gl004":
        def cache_update_no_donation(cache, x):
            # a KV-cache-shaped buffer updated but NOT donated
            return cache.at[:, :, 0, :].set(x), x.sum()

        return analysis.lint(
            cache_update_no_donation,
            jax.ShapeDtypeStruct((4, 8, 128, 64), jnp.float32),  # 1 MiB
            jax.ShapeDtypeStruct((4, 8, 64), jnp.float32),
            program="inject:gl004")
    if code == "gl009":
        import numpy as np
        from jax.sharding import Mesh, PartitionSpec as P

        devs = jax.devices()
        if len(devs) < 2:
            raise ValueError("--inject gl009 needs >= 2 devices "
                             "(XLA_FLAGS=--xla_force_host_platform_"
                             "device_count=8)")
        mesh = Mesh(np.array(devs[:2]), ("dp",))

        def replicated_moment_step(x, w, m):
            # the hazard under test: a 4 MiB optimizer moment REPLICATED
            # over 'dp' instead of ZeRO-sharded
            g = jax.lax.psum(x.T @ (x @ w), "dp")
            m_new = 0.9 * m + 0.1 * g
            return w - 0.01 * m_new, m_new

        fn = jax.shard_map(replicated_moment_step, mesh=mesh,
                           in_specs=(P("dp", None), P(), P()),
                           out_specs=(P(), P()))
        return analysis.lint(
            fn,
            jax.ShapeDtypeStruct((256, 1024), jnp.float32),
            jax.ShapeDtypeStruct((1024, 1024), jnp.float32),  # 4 MiB
            jax.ShapeDtypeStruct((1024, 1024), jnp.float32),  # 4 MiB
            program="inject:gl009")
    raise ValueError(f"unknown --inject code {code!r} "
                     "(supported: gl001, gl004, gl009)")


def run(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="graph_lint.py",
        description="Lint the bench models' compiled programs "
                    "(docs/graph_lint.md)")
    ap.add_argument("--baseline", nargs="?", const=DEFAULT_BASELINE,
                    default=None, metavar="PATH",
                    help="suppress findings recorded in PATH "
                         f"(default {os.path.relpath(DEFAULT_BASELINE, _REPO)})")
    ap.add_argument("--write-baseline", nargs="?", const=DEFAULT_BASELINE,
                    default=None, metavar="PATH",
                    help="write current gate-relevant findings to PATH "
                         "(keeps existing justifications) and exit 0")
    ap.add_argument("--targets", default="train,decode,serve,mesh,churn",
                    help="comma list of train,decode,serve,mesh,churn,none "
                         "(default: all)")
    ap.add_argument("--mesh-shape", default="2,2", metavar="DP,MP",
                    help="device mesh for the mesh target (default 2,2; "
                         "skipped with a note when the host has fewer "
                         "devices)")
    ap.add_argument("--cost", action="store_true",
                    help="also compute static roofline cost reports "
                         "(FLAGS_graph_cost) and print a per-program "
                         "summary: GFLOPs, HBM bytes, intensity, "
                         "compute/memory-bound verdict, tile-padding "
                         "waste")
    ap.add_argument("--chip", default="v5e", metavar="KIND",
                    help="the TARGET chip the --cost roofline models "
                         "(e.g. 'v5e', 'v4'); the lint runs with no device "
                         "present, so the target is named, not probed")
    ap.add_argument("--inject", action="append", default=[],
                    metavar="CODE", help="add a deliberately-hazardous test "
                    "model (gl001|gl004|gl009); the gate must exit 1")
    ap.add_argument("--fail-on", default="warning",
                    choices=("info", "warning", "error"),
                    help="minimum severity that fails the gate")
    ap.add_argument("--json", action="store_true",
                    help="emit findings as JSON lines on stdout")
    args = ap.parse_args(argv)

    try:
        import numpy as np

        import paddle_tpu as pt
        from paddle_tpu import analysis

        pt.set_flags({"FLAGS_graph_lint": True})
        if args.cost:
            pt.set_flags({"FLAGS_graph_cost": True})
            analysis.clear_cost_reports()
        # the hook announces findings to stderr as programs compile; this
        # CLI renders the collected reports itself — don't print twice
        analysis.set_announce(False)
        analysis.clear_reports()

        targets = [t for t in args.targets.split(",") if t]
        known = {"train", "decode", "serve", "mesh", "churn", "none"}
        for t in targets:
            if t not in known:
                raise ValueError(f"unknown target {t!r} (expected "
                                 f"{sorted(known - {'none'})})")
        try:
            mesh_shape = tuple(int(d) for d in args.mesh_shape.split(","))
            dp_, mp_ = mesh_shape
        except Exception:
            raise ValueError(f"--mesh-shape {args.mesh_shape!r}: expected "
                             "DP,MP (e.g. 2,2)")
        if "train" in targets:
            _lint_train(pt, np)
        if "decode" in targets:
            _lint_decode(pt, np)
        if "serve" in targets:
            _lint_serve(pt, np)
        mesh_lint_reports, mesh_cost_reports = [], []
        if "mesh" in targets:
            mesh_lint_reports, mesh_cost_reports = _lint_mesh(
                analysis, (dp_, mp_), args.cost)
            _lint_mesh_serve(pt, np, (dp_, mp_))

        all_reports = list(analysis.reports()) + mesh_lint_reports
        if "churn" in targets:
            all_reports.append(analysis.churn_findings())
        for code in args.inject:
            all_reports.append(_inject(analysis, code))

        findings = [f for rep in all_reports for f in rep.findings]
        gate = [f for f in findings
                if f.rank >= analysis.SEVERITY_RANK[args.fail_on]]

        if args.write_baseline:
            baseline = (analysis.Baseline.load(args.write_baseline)
                        if os.path.exists(args.write_baseline)
                        else analysis.Baseline())
            fresh = analysis.Baseline()
            for f in gate:
                fresh.add(f, baseline.suppressions.get(
                    f.fingerprint, "TODO: justify"))
            fresh.save(args.write_baseline)
            print(f"graph_lint: wrote {len(fresh.suppressions)} "
                  f"suppression(s) to {args.write_baseline}")
            return 0

        baseline = (analysis.Baseline.load(args.baseline)
                    if args.baseline else analysis.Baseline())
        new = baseline.filter_new(gate)

        if args.json:
            for f in findings:
                print(json.dumps({
                    "code": f.code, "severity": f.severity,
                    "program": f.program, "primitive": f.primitive,
                    "message": f.message, "cost": f.cost,
                    "provenance": f.provenance,
                    "fingerprint": f.fingerprint,
                    "new": not baseline.suppresses(f),
                }))
        else:
            for rep in all_reports:
                print(rep.render())
        if args.cost:
            spec = analysis.chip_spec(args.chip)
            creps = analysis.cost_reports() + mesh_cost_reports
            if args.json:
                for c in creps:
                    print(json.dumps({"cost": c.summary(spec)}))
            else:
                print(f"graph_lint: --cost roofline summaries "
                      f"({len(creps)} program(s), chip {spec.name}):")
                for c in creps:
                    print(c.render(spec))
        n_sup = sum(1 for f in gate if baseline.suppresses(f))
        print(f"graph_lint: {len(findings)} finding(s) over "
              f"{len(all_reports)} program(s); {n_sup} baseline-suppressed; "
              f"{len(new)} NEW at/above '{args.fail_on}'")
        if new:
            print("graph_lint: NEW findings:")
            for f in new:
                print("  " + f.render())
            return 1
        return 0
    except SystemExit:
        raise
    except Exception:
        traceback.print_exc()
        print("graph_lint: internal error (exit 2)")
        return 2


if __name__ == "__main__":
    sys.exit(run())
