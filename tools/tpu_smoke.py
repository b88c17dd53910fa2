#!/usr/bin/env python
"""On-chip kernel smoke test: compiles + numerically checks every owned
Pallas kernel against its XLA reference ON THE REAL TPU.

Motivation (round 5): the CPU test suite exercises the kernels' XLA
fallbacks, so a Mosaic-only compile regression (e.g. contract-precision
fp32 on bf16 dots, i64 index-map returns, VMEM stack overflow — all
three happened) is invisible until a bench run burns 10+ minutes on the
ladder.  This script fails fast in ~2 minutes.

Usage: python tools/tpu_smoke.py

Exit codes (tri-state — CI wrappers must NOT treat 2 as a failure):
  0  all owned kernels compiled and matched their references on-chip
  1  at least one kernel failed to compile or diverged numerically
  2  no TPU backend on this host (CPU-only: nothing was smoke-tested;
     the kernels' XLA fallbacks are covered by the regular test suite)
"""
from __future__ import annotations

import os
import sys

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)


def main() -> int:
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform == "cpu":
        print("tpu_smoke: no TPU backend; nothing to smoke-test")
        return 2

    from paddle_tpu.sysconfig import enable_compile_cache

    enable_compile_cache()

    failures = []

    def check(name, fn):
        try:
            fn()
            print(f"tpu_smoke: {name}: OK")
        except Exception as e:  # noqa: BLE001 — report and continue
            head = str(e).splitlines()[:3]
            print(f"tpu_smoke: {name}: FAIL {' | '.join(head)[:300]}")
            failures.append(name)

    rng = np.random.RandomState(0)

    # -- flash attention fwd+bwd vs XLA reference (both causal modes) ----
    def flash():
        import paddle_tpu.ops.pallas_kernels.flash_attention as fa
        q = jnp.array(rng.randn(2, 4, 512, 64), jnp.bfloat16)
        k = jnp.array(rng.randn(2, 4, 512, 64), jnp.bfloat16)
        v = jnp.array(rng.randn(2, 4, 512, 64), jnp.bfloat16)
        sc = 0.125
        for causal in (False, True):
            a = fa._flash_bnsd(q, k, v, causal, sc).astype(jnp.float32)
            b = fa._xla_reference_bnsd(q, k, v, causal, sc).astype(jnp.float32)
            err = float(jnp.abs(a - b).max())
            assert err < 0.05, f"fwd causal={causal} err={err}"
            ga = jax.grad(lambda q, k, v: fa._flash_bnsd(
                q, k, v, causal, sc).astype(jnp.float32).sum(), (0, 1, 2))(q, k, v)
            gb = jax.grad(lambda q, k, v: fa._xla_reference_bnsd(
                q, k, v, causal, sc).astype(jnp.float32).sum(), (0, 1, 2))(q, k, v)
            for x, y in zip(ga, gb):
                err = float(jnp.abs(x.astype(jnp.float32)
                                    - y.astype(jnp.float32)).max())
                assert err < 0.05, f"bwd causal={causal} err={err}"

    # -- decode attention (q-len-1 flash-decode) vs jnp reference --------
    def decode_attention():
        from paddle_tpu.ops.pallas_kernels import decode_attention as da
        B, H, S, D = 2, 4, 512, 64
        q = jnp.array(rng.randn(B, H, D), jnp.bfloat16)
        k = jnp.array(rng.randn(B, H, S, D), jnp.bfloat16)
        v = jnp.array(rng.randn(B, H, S, D), jnp.bfloat16)
        assert da.decode_shape_supported(S, D)
        # boundary lengths: single position, inside a block, block edge, full
        for length in (1, 5, 127, 128, 200, 512):
            ln = jnp.int32(length)
            got = da.decode_attention(q, k, v, ln).astype(jnp.float32)
            want = da._xla_decode_reference(
                q, k, v, ln, 0.125).astype(jnp.float32)
            err = float(jnp.abs(got - want).max())
            assert err < 0.05, f"len={length} err={err}"

    # -- paged decode attention (continuous-batching serving) vs gather
    # reference: shuffled page tables, boundary lengths incl. the
    # length-0 inactive-slot case ---------------------------------------
    def paged_attention():
        from paddle_tpu.ops.pallas_kernels import paged_attention as pa
        P, H, PS, D = 17, 4, 128, 64
        S, MP = 4, 4
        kp = jnp.array(rng.randn(P, H, PS, D), jnp.bfloat16)
        vp = jnp.array(rng.randn(P, H, PS, D), jnp.bfloat16)
        q = jnp.array(rng.randn(S, H, D), jnp.bfloat16)
        # page-table edge cases: out-of-order pool pages, trailing null
        # entries past each slot's length
        tbl = jnp.array(rng.permutation(P - 1)[:S * MP].reshape(S, MP) + 1,
                        jnp.int32)
        assert pa.paged_shape_supported(PS, D)
        for lens in ((0, 1, 127, 512), (128, 200, 256, 384)):
            ln = jnp.array(lens, jnp.int32)
            got = pa.paged_attention(q, kp, vp, tbl, ln).astype(jnp.float32)
            want = pa._xla_paged_reference(
                q, kp, vp, tbl, ln, 0.125).astype(jnp.float32)
            err = float(jnp.abs(got - want).max())
            assert err < 0.05, f"lens={lens} err={err}"
            for i, l in enumerate(lens):
                if l == 0:
                    assert float(jnp.abs(got[i]).max()) == 0.0, \
                        "length-0 slot must emit zeros"
        # the eligibility gate reports GL002-coded reasons on this host
        r = pa.paged_shape_unsupported_reason(100, 48)
        assert r is not None and r.code == "GL002"

    # -- int8 KV pages (docs/serving.md "Quantized serving"): quantize-
    # on-write into a SHUFFLED pool, fused in-kernel dequant attention vs
    # the dequantized-pool oracle, and bitwise write determinism (the
    # property prefix-cache COW page adoption relies on) ------------------
    def quantized_kv():
        from paddle_tpu.ops.pallas_kernels import paged_attention as pa
        from paddle_tpu.quantization.kv import (
            dequant_pages, quantize_kv_write,
        )
        P, H, PS, D = 17, 4, 128, 64
        S, MP = 4, 4
        tbl = jnp.array(rng.permutation(P - 1)[:S * MP].reshape(S, MP) + 1,
                        jnp.int32)

        def build():
            kp = jnp.zeros((P, H, PS, D), jnp.int8)
            vp = jnp.zeros((P, H, PS, D), jnp.int8)
            ks = jnp.zeros((P, H), jnp.float32)
            vs = jnp.zeros((P, H), jnp.float32)
            offs = jnp.arange(PS, dtype=jnp.int32)[None]
            wrng = np.random.RandomState(5)
            for s in range(S):
                for j in range(MP):
                    pid = jnp.full((1, PS), tbl[s, j], jnp.int32)
                    xk = jnp.array(wrng.randn(1, PS, H, D), jnp.float32)
                    xv = jnp.array(wrng.randn(1, PS, H, D), jnp.float32)
                    qk, ks = quantize_kv_write(xk, pid, offs, ks)
                    qv, vs = quantize_kv_write(xv, pid, offs, vs)
                    kp = kp.at[tbl[s, j]].set(qk[0].transpose(1, 0, 2))
                    vp = vp.at[tbl[s, j]].set(qv[0].transpose(1, 0, 2))
            return kp, vp, ks, vs

        kp, vp, ks, vs = build()
        q = jnp.array(rng.randn(S, H, D), jnp.float32)
        ln = jnp.array((128, 200, 256, 384), jnp.int32)
        got = pa.paged_attention(q, kp, vp, tbl, ln,
                                 k_scale=ks, v_scale=vs)
        want = pa._xla_paged_reference(
            q, dequant_pages(kp, ks), dequant_pages(vp, vs), tbl, ln,
            0.125).astype(jnp.float32)
        err = float(jnp.abs(got.astype(jnp.float32) - want).max())
        assert err < 0.05, f"int8 dequant parity err={err}"
        # identical write sequence -> bitwise-identical pages AND scales
        kp2, vp2, ks2, vs2 = build()
        for a, b in ((kp, kp2), (vp, vp2), (ks, ks2), (vs, vs2)):
            assert bool(jnp.array_equal(a, b)), \
                "quantize-on-write must be deterministic"

    # -- ragged paged attention (fused mixed prefill/decode step) vs the
    # per-token gather oracle: mixed decode + page-straddling prefill
    # runs, shuffled out-of-order pool pages, boundary positions incl.
    # position 0 and an exact page edge ----------------------------------
    def ragged_attention():
        from paddle_tpu.ops.pallas_kernels import ragged_paged_attention as ra
        P, H, PS, D = 15, 4, 128, 64
        MP = 4
        assert ra.ragged_shape_supported(PS, D)
        # the wide block of this launch's shapes (a run longer than one
        # narrow block is cut into blocks of QW tokens)
        QW = ra.ragged_wide_block(H, 1, PS, D, jnp.bfloat16)
        assert QW > 8, QW
        runs = [
            (200, 1, np.array([4, 2, 9, 1], np.int32)),   # decode, 2 pages
            (0, 1, np.array([3, 0, 0, 0], np.int32)),     # decode at pos 0
            (120, 16, np.array([7, 5, 8, 6], np.int32)),  # straddles a page
            (127, 1, np.array([10, 6, 0, 0], np.int32)),  # exact page edge
            (17, 5, np.array([10, 0, 0, 0], np.int32)),   # short prefill
            # longer than QW beside the decode rows: full wide blocks, a
            # wide tail, three pages of context
            (300, 2 * QW + 21, np.array([11, 12, 13, 14], np.int32)),
        ]
        T_MAX, WL_MAX = 32 + 2 * QW + 21, 64
        NB_MAX, NBW_MAX = 16, ra.ragged_wide_capacity(T_MAX, 8, QW)
        plan_np, stats = ra.build_ragged_plan(
            runs, token_block=8, page_size=PS,
            t_max=T_MAX, nb_max=NB_MAX, wl_max=WL_MAX,
            wide_block=QW, nbw_max=NBW_MAX)
        assert 0 < stats["wide_items"] < stats["n_items"], stats
        tables = np.zeros((T_MAX, MP), np.int32)
        lengths = np.zeros((T_MAX,), np.int32)   # padding tokens: length 0
        for (base, count, tbl), start in zip(runs, stats["run_starts"]):
            for i in range(count):
                tables[start + i] = tbl
                lengths[start + i] = base + i + 1
        real = stats["n_tokens"]
        q = jnp.array(rng.randn(T_MAX, H, D), jnp.bfloat16)
        kp = jnp.array(rng.randn(P, H, PS, D), jnp.bfloat16)
        vp = jnp.array(rng.randn(P, H, PS, D), jnp.bfloat16)
        plan = tuple(jnp.array(plan_np[k]) for k in ra.RAGGED_PLAN_FIELDS)
        got = np.asarray(ra.ragged_paged_attention(
            q, kp, vp, jnp.array(tables), jnp.array(lengths), plan,
            sm_scale=0.125), np.float32)
        want = np.asarray(ra._xla_ragged_reference(
            q, kp, vp, jnp.array(tables), jnp.array(lengths), 0.125),
            np.float32)
        err = float(np.abs(got[:real] - want[:real]).max())
        assert err < 0.05, f"ragged parity err={err}"
        # length-0 tokens (inactive rows) emit zeros through the oracle
        assert float(np.abs(want[real:]).max()) == 0.0
        # the eligibility gate reports GL002-coded reasons on this host
        r = ra.ragged_shape_unsupported_reason(128, 64, token_block=12)
        assert r is not None and r.code == "GL002"

    # -- fused AdamW slab kernel vs composed update ----------------------
    def fused_adamw():
        from paddle_tpu.ops.pallas_kernels.fused_adamw import fused_adamw_update
        n = 1024 * 300 + 7   # non-lane-aligned on purpose
        p = jnp.array(rng.randn(n), jnp.bfloat16)
        g = jnp.array(rng.randn(n), jnp.bfloat16) * 0.01
        pf = np.asarray(p, np.float32)
        gf = np.asarray(g, np.float32)
        m1 = jnp.zeros(n, jnp.bfloat16)
        m2 = jnp.zeros(n, jnp.bfloat16)
        np_, _, _ = fused_adamw_update(p, g, m1, m2, 1e-3, 0.9, 0.999)
        rm1 = 0.1 * gf
        rm2 = 0.001 * gf * gf
        ref = pf * (1 - 1e-3 * 0.01) - 1e-3 * (rm1 / (1 - 0.9)) / (
            np.sqrt(rm2 / (1 - 0.999)) + 1e-8)
        err = float(np.abs(np.asarray(np_, np.float32) - ref).max())
        assert err < 5e-3, f"err={err}"

    # -- fused residual-add + RMSNorm / LayerNorm kernels ----------------
    def rms_norm():
        # numeric check against the small jnp-composed reference (same
        # tolerance discipline as the flash/adamw checks — finiteness
        # alone missed a wrong-statistic kernel class entirely)
        from paddle_tpu.ops.pallas_kernels import rms_norm as rn
        x = jnp.array(rng.randn(8, 512, 1024), jnp.bfloat16)
        r = jnp.array(rng.randn(8, 512, 1024), jnp.bfloat16)
        w = jnp.array(rng.randn(1024), jnp.float32)
        b = jnp.zeros((1024,), jnp.float32)
        cases = (
            ("fused_add_rms_norm", (x, r, w),
             lambda: rn._reference(x, r, w, eps=1e-6)),
            ("fused_add_layer_norm", (x, r, w, b),
             lambda: rn._ln_reference(x, r, w, b, eps=1e-5)),
        )
        for fn_name, args, ref_fn in cases:
            out, h = getattr(rn, fn_name)(*args)
            ref_out, ref_h = ref_fn()
            for got, want, part in ((out, ref_out, "normed"), (h, ref_h, "h")):
                err = float(jnp.abs(got.astype(jnp.float32)
                                    - want.astype(jnp.float32)).max())
                assert err < 0.05, f"{fn_name} {part} err={err}"

    # -- graph lint on-chip: the analyzer sees the same jaxprs the TPU
    # compiles; a hazardous graph must be flagged and a clean one must not
    # (the same GL001 case the CLI's --inject gate uses) -----------------
    def graph_lint():
        from paddle_tpu import analysis

        def promoted(x, w):
            return x.astype(jnp.float32) @ w

        rep = analysis.lint(promoted,
                            jnp.zeros((256, 256), jnp.bfloat16),
                            jnp.zeros((256, 256), jnp.float32))
        assert any(f.code == "GL001" for f in rep.findings), \
            "bf16->fp32 promoted matmul not flagged"
        from paddle_tpu.analysis import graph_lint as _gl
        if _gl._src_info is not None:  # provenance is best-effort
            assert rep.findings[0].provenance, "finding lost eqn provenance"

        def clean(x, w):
            return x @ w

        rep = analysis.lint(clean,
                            jnp.zeros((256, 256), jnp.bfloat16),
                            jnp.zeros((256, 256), jnp.bfloat16))
        assert not [f for f in rep.findings if f.code == "GL001"], \
            "clean bf16 matmul falsely flagged"
        # the kernel gates report GL002-coded reasons on this TPU host
        from paddle_tpu.ops.pallas_kernels.flash_attention import (
            shape_unsupported_reason,
        )
        r = shape_unsupported_reason(100, 48)
        assert r is not None and r.code == "GL002"

    # -- mesh lint (v3): the static SPMD comm passes on a REAL device
    # mesh — GL009 must fire on dp-replicated fp32 optimizer state, the
    # psum wire bytes must match the ring formula exactly, and the
    # overlap fraction must be sane -------------------------------------
    def mesh_lint():
        from jax.sharding import Mesh, PartitionSpec as P

        from paddle_tpu import analysis

        devs = jax.devices()
        if len(devs) < 2:
            print("tpu_smoke: mesh_lint: single chip; dp mesh skipped")
            return
        mesh = Mesh(np.asarray(devs[:2]), ("dp",))

        def step(x, w, m):
            g = jax.lax.psum((x.T @ (x @ w)).astype(jnp.float32), "dp")
            m2 = 0.9 * m + g
            return (w - 1e-3 * m2).astype(w.dtype), m2

        fn = jax.shard_map(
            step, mesh=mesh, in_specs=(P("dp", None), P(), P()),
            out_specs=(P(), P()))
        x = jnp.zeros((256, 1024), jnp.bfloat16)
        w = jnp.zeros((1024, 1024), jnp.bfloat16)
        m = jnp.zeros((1024, 1024), jnp.float32)
        rep = analysis.lint(fn, x, w, m, program="smoke_mesh_lint")
        gl9 = [f for f in rep.findings if f.code == "GL009"]
        # w (2 MiB bf16) and m (4 MiB fp32) are both dp-replicated and
        # above the 1 MiB floor; x is dp-sharded and must NOT fire
        assert len(gl9) == 2, f"expected 2 GL009, got {rep.render()}"
        assert all("dp" in f.detail for f in gl9), gl9
        assert not any("invar[0]" in f.detail for f in gl9), \
            "GL009 fired on the dp-sharded input"
        crep = analysis.cost(fn, x, w, m, program="smoke_mesh_lint")
        assert len(crep.collectives) == 1, crep.render()
        cc = crep.collectives[0]
        # ring all-reduce wire bytes: 2(n-1)/n x 4 MiB payload at n=2
        payload = 1024 * 1024 * 4
        assert cc.wire_bytes == payload, (cc.wire_bytes, payload)
        ov = crep.overlap_fraction()
        assert 0.0 <= ov <= 1.0, ov

    # -- checkpoint: save -> corrupt -> fallback -> resume ON-CHIP (the
    # sentry's fused all-finite reduction and the device_get snapshot
    # boundary both run against real TPU arrays here) --------------------
    def checkpoint():
        import shutil
        import tempfile

        from paddle_tpu.checkpoint import (
            CheckpointManager, all_finite, tree_all_finite,
        )
        from paddle_tpu.checkpoint.manager import PAYLOAD_NAME

        d = tempfile.mkdtemp(prefix="tpu_smoke_ckpt_")
        try:
            m = CheckpointManager(d, async_save=False)
            w1 = jnp.array(rng.randn(128, 128), jnp.bfloat16)
            m.save({"w": np.asarray(w1.astype(jnp.float32))}, step=1)
            m.save({"w": np.zeros((128, 128), np.float32)}, step=2)
            # corrupt the newest payload: digest validation must skip it
            p = f"{d}/ckpt-00000002/{PAYLOAD_NAME}"
            with open(p, "r+b") as f:
                raw = bytearray(f.read())
                raw[len(raw) // 2] ^= 0xFF
                f.seek(0)
                f.write(raw)
            info = m.latest()
            assert info is not None and info.step == 1, f"latest={info}"
            tree, _ = m.restore(info)
            err = float(jnp.abs(jnp.asarray(tree["w"])
                                - w1.astype(jnp.float32)).max())
            assert err == 0.0, f"resume diverged err={err}"
            # fused finiteness reduction on-device: one compiled program
            good = [jnp.ones((64, 64), jnp.bfloat16),
                    jnp.ones((8,), jnp.float32)]
            assert bool(tree_all_finite(good))
            assert not all_finite(good + [jnp.array([jnp.nan])])
        finally:
            shutil.rmtree(d, ignore_errors=True)

    # -- serving fault containment ON-CHIP: one injected step failure must
    # fail only the seated requests, recovery must rebuild the REAL paged
    # pool (fresh HBM, recompiled Mosaic step), and the queued remainder
    # must finish token-for-token equal to single-shot generate() ---------
    def serving_faults():
        import paddle_tpu as pt
        from paddle_tpu.models import GPTStackedForPretraining, gpt_tiny
        from paddle_tpu.serving import (
            FaultInjector, RequestState, ServingEngine,
        )

        pt.seed(0)
        cfg = gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0)
        m = GPTStackedForPretraining(cfg)
        m.eval()
        srng = np.random.RandomState(5)
        prompts = [srng.randint(0, cfg.vocab_size, (s,))
                   for s in (6, 11, 9, 14)]
        refs = [np.asarray(
            m.generate(pt.to_tensor(p[None, :], dtype="int64"),
                       max_new_tokens=4, max_seq_len=128,
                       cache_dtype="bfloat16").numpy())[0]
            for p in prompts]
        eng = ServingEngine(m, num_slots=2, page_size=128, max_context=128,
                            cache_dtype="bfloat16")
        # a persistent (retry-defeating) mid-dispatch crash: recovery must
        # rebuild the on-chip pool and keep serving
        FaultInjector().inject("before_decode", at=1, times=2,
                               kind="step_exception",
                               state_intact=False).install(eng)
        reqs = [eng.submit(p, 4) for p in prompts]
        eng.run_until_idle(max_steps=500)
        mets = eng.metrics()
        assert mets["recoveries"] == 1 and mets["rebuilds"] == 1, mets
        done = [r for r in reqs if r.state == RequestState.DONE]
        failed = [r for r in reqs if r.state == RequestState.FAILED]
        assert len(done) == 2 and len(failed) == 2, \
            [r.state for r in reqs]
        for r, ref in zip(reqs, refs):
            if r.state == RequestState.DONE:
                assert np.array_equal(r.output_ids(), ref), \
                    f"survivor {r.id} diverged after on-chip recovery"
        assert eng.allocator.used_pages == 0, "pages leaked on-chip"
        eng.close()

    # -- sharded serving: the mesh-native engine on a REAL chip mesh —
    # per-head-sharded pool + shard_map'd ragged kernel + row-parallel
    # reduce, with the free list pre-fragmented so page tables are
    # shuffled pool pages, parity vs the single-chip generate() oracle
    # (docs/serving.md "Sharded serving") ---------------------------------
    def sharded_serving():
        import paddle_tpu as pt
        from paddle_tpu.models import GPTStackedForPretraining, gpt_tiny
        from paddle_tpu.serving import ServingEngine, ShardedServingEngine

        n_dev = len(jax.devices())
        if n_dev < 2:
            print("tpu_smoke: sharded_serving: single-chip host, "
                  "mesh case skipped")
            return
        dp, mp = (2, 2) if n_dev >= 4 else (1, 2)
        pt.seed(0)
        cfg = gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0)
        m = GPTStackedForPretraining(cfg)
        m.eval()
        srng = np.random.RandomState(11)
        prompts = [srng.randint(0, cfg.vocab_size, (s,))
                   for s in (6, 17, 9, 23, 12, 7)]
        refs = [np.asarray(
            m.generate(pt.to_tensor(p[None, :], dtype="int64"),
                       max_new_tokens=4, max_seq_len=128,
                       cache_dtype="bfloat16").numpy())[0]
            for p in prompts]
        eng = ShardedServingEngine(m, dp=dp, mp=mp, num_slots=2,
                                   page_size=128, max_context=128,
                                   cache_dtype="bfloat16")
        # fragment every replica's free list so admission hands out
        # SHUFFLED (non-contiguous, reordered) pool pages — the kernel's
        # scalar-prefetch page translation is what's under test
        for rep in eng.replicas:
            held = [rep.allocator.alloc(1) for _ in range(3)]
            rep.allocator.free(held[0])
            rep.allocator.free(held[2])
            rep.allocator.free(held[1])
        reqs = [eng.submit(p, 4) for p in prompts]
        eng.run_until_idle(max_steps=500)
        for r, ref in zip(reqs, refs):
            assert r.finished and np.array_equal(r.output_ids(), ref), \
                f"request {r.id} diverged from the single-chip oracle"
        for i, rep in enumerate(eng.replicas):
            assert rep.allocator.used_pages == 0, f"replica {i} leaked"
        mets = eng.metrics()
        assert mets["cache_bytes_per_chip"] * mp == mets["cache_bytes"] // dp
        print(f"tpu_smoke: sharded_serving dp={dp} mp={mp} "
              f"routed={mets['routed']} "
              f"pool_per_chip={mets['cache_bytes_per_chip']}B")
        eng.close()

    # -- speculative serving: on-chip draft propose + ONE fused verify
    # dispatch with SHUFFLED pool pages in both pools; greedy output must
    # match the unspeculated oracle token-for-token, both allocators must
    # drain exactly (incl. the speculative-reservation ledger), and the
    # trace budget must hold (<= 2 target + <= 2 draft) -------------------
    def speculative_serving():
        import paddle_tpu as pt
        from paddle_tpu import serving
        from paddle_tpu.models import GPTStackedForPretraining, gpt_tiny
        from paddle_tpu.serving import ServingEngine, SpeculativeEngine

        pt.seed(0)
        cfg = gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0)
        m = GPTStackedForPretraining(cfg)
        m.eval()
        srng = np.random.RandomState(13)
        prompts = [srng.randint(0, cfg.vocab_size, (s,))
                   for s in (6, 17, 9, 23)]
        oracle = ServingEngine(m, num_slots=2, page_size=128,
                               max_context=128, cache_dtype="bfloat16")
        refs = oracle.generate_batch(prompts, 5)
        oracle.close()
        serving.reset_serve_trace_counts()
        eng = SpeculativeEngine(m, m, spec_k=3, num_slots=2, page_size=128,
                                max_context=128, cache_dtype="bfloat16")
        # fragment BOTH free lists: the verify and draft kernels must
        # translate shuffled page tables via scalar prefetch
        for alloc in (eng.allocator, eng.draft.allocator):
            held = [alloc.alloc(1) for _ in range(3)]
            alloc.free(held[0])
            alloc.free(held[2])
            alloc.free(held[1])
        outs = eng.generate_batch(prompts, 5)
        for got, ref in zip(outs, refs):
            assert np.array_equal(got, ref), \
                "speculative output diverged from the unspeculated oracle"
        tc = serving.serve_trace_counts()
        assert tc["fused"] <= 2 and tc["draft"] <= 2, tc
        mets = eng.metrics()
        for alloc, tag in ((eng.allocator, "target"),
                           (eng.draft.allocator, "draft")):
            assert alloc.used_pages == 0 and alloc.spec_pages == 0, \
                f"{tag} pool did not drain"
        print(f"tpu_smoke: speculative_serving accept_rate="
              f"{mets['spec_acceptance_rate']:.3f} traces={tc}")
        eng.close()

    # -- prefix cache: shared-prefix admission on-chip — a completed
    # request registers its full pages in the radix index, later siblings
    # splice those pool pages into their tables copy-on-write and prefill
    # only the uncached tail; parity vs the cache-disabled oracle proves
    # the HIT PAGES hold bitwise-correct KV (docs/serving.md "Prefix
    # cache") --------------------------------------------------------------
    def prefix_cache():
        import paddle_tpu as pt
        from paddle_tpu.models import GPTStackedForPretraining, gpt_tiny
        from paddle_tpu.serving import RequestState, ServingEngine

        pt.seed(0)
        # 256 positions: the shared prefix must fill a WHOLE 128-token
        # page (the TPU-native page size) and still leave decode room
        cfg = gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0,
                       max_position_embeddings=256)
        m = GPTStackedForPretraining(cfg)
        m.eval()
        srng = np.random.RandomState(17)
        sys_prompt = srng.randint(0, cfg.vocab_size, (128,))  # 1 full page
        prompts = [np.concatenate([sys_prompt,
                                   srng.randint(0, cfg.vocab_size, (s,))])
                   for s in (5, 9, 13)]
        oracle = ServingEngine(m, num_slots=2, page_size=128,
                               max_context=256, cache_dtype="bfloat16")
        refs = oracle.generate_batch(prompts, 4)
        oracle.close()
        eng = ServingEngine(m, num_slots=2, page_size=128, max_context=256,
                            cache_dtype="bfloat16", prefix_cache=True)
        seed_req = eng.submit(prompts[0], 4)    # registers the shared page
        eng.run_until_idle(max_steps=500)
        assert seed_req.state == RequestState.DONE
        assert eng.allocator.shared_pages >= 1, "prefix never registered"
        sibs = [eng.submit(p, 4) for p in prompts[1:]]  # concurrent hits
        eng.run_until_idle(max_steps=500)
        for r, ref in zip([seed_req] + sibs, refs):
            assert r.state == RequestState.DONE and np.array_equal(
                r.output_ids(), ref), \
                f"request {r.id} diverged with the prefix cache on"
        mets = eng.metrics()
        assert mets["prefix_hits"] + mets["prefix_partial_hits"] >= 2, mets
        assert mets["prefix_cached_tokens"] >= 256, mets
        a = eng.allocator
        assert a.used_pages == 0, "pages leaked on-chip"
        assert a.free_pages + a.shared_pages == a.capacity, \
            "shared-page ledger did not close"
        print(f"tpu_smoke: prefix_cache hit_rate="
              f"{mets['prefix_hit_rate']:.3f} "
              f"cached_tokens={mets['prefix_cached_tokens']} "
              f"shared_pages={mets['shared_pages']}")
        eng.close()

    # -- autotune: ONE real measured candidate sweep on-chip (decode
    # kernel, small cache), winner must be legal, parity must hold with
    # the winner forced, and the table must round-trip through replay
    # validation ----------------------------------------------------------
    def autotune_sweep():
        import os
        import tempfile
        import time

        import jax
        import jax.numpy as jnp

        import paddle_tpu.ops.pallas_kernels.decode_attention as da
        from paddle_tpu.analysis import autotune

        kernel = "decode_attention"
        shape = {"max_seq": 256, "head_dim": 64}
        rng2 = np.random.RandomState(7)
        q = jnp.array(rng2.randn(2, 4, 64), jnp.bfloat16)
        k = jnp.array(rng2.randn(2, 4, 256, 64), jnp.bfloat16)
        v = jnp.array(rng2.randn(2, 4, 256, 64), jnp.bfloat16)
        length = jnp.int32(200)

        def timing(params):
            # a FRESH jit per candidate: forced params are read at trace
            # time, and identical avals would otherwise reuse the previous
            # candidate's compiled executable
            jitted = jax.jit(lambda *xs: da.decode_attention(*xs))
            with autotune.force(kernel, params):
                out = jitted(q, k, v, length)
                jax.block_until_ready(out)
                t0 = time.perf_counter()
                out = jitted(q, k, v, length)
                jax.block_until_ready(out)
                return time.perf_counter() - t0

        table = autotune.AutotuneTable()
        winner, results = autotune.sweep(kernel, shape, "bfloat16", timing,
                                         table=table, device="tpu_smoke")
        cands = autotune.enumerate_candidates(kernel, shape, "bfloat16")
        assert winner is not None and winner in cands, (winner, results)
        print(f"tpu_smoke: autotune winner {winner} over "
              f"{len(cands)} candidates")
        # parity with the winner forced vs the XLA oracle
        ref = np.asarray(da._xla_decode_reference(
            q, k, v, length, 0.125), np.float32)
        with autotune.force(kernel, dict(winner, **{})):
            got = np.asarray(jax.jit(
                lambda *xs: da.decode_attention(*xs, sm_scale=0.125))(
                    q, k, v, length), np.float32)
        err = float(np.abs(got - ref).max())
        assert err < 2e-2, f"winner-config parity err={err}"
        # round-trip + replay validation of the measured entry
        with tempfile.TemporaryDirectory() as td:
            path = os.path.join(td, "t.json")
            table.save(path)
            loaded = autotune.load_table(path, strict=True)
            assert loaded.get(kernel, shape, "bfloat16") == winner

    # -- telemetry: ONE on-chip fused serving step captured with host
    # spans nesting jax.profiler TraceAnnotations while a REAL device
    # trace is recording — the host/device alignment path that CPU runs
    # can only no-op through -------------------------------------------------
    def telemetry():
        import json as _json
        import os as _os
        import tempfile

        import paddle_tpu as pt
        from paddle_tpu.models import GPTStackedForPretraining, gpt_tiny
        from paddle_tpu.serving import ServingEngine
        from paddle_tpu.telemetry import trace

        pt.seed(0)
        cfg = gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0)
        m = GPTStackedForPretraining(cfg)
        m.eval()
        trng = np.random.RandomState(9)
        eng = ServingEngine(m, num_slots=2, page_size=128, max_context=128,
                            cache_dtype="bfloat16")
        # warmup OUTSIDE the capture: compile is not the measurement
        eng.submit(trng.randint(0, cfg.vocab_size, (6,)), 2)
        eng.run_until_idle(max_steps=200)
        tr = trace.enable()
        try:
            assert tr.annotate and tr._ann_cls is not None, \
                "TraceAnnotation unavailable: host/device alignment dead"
            with tempfile.TemporaryDirectory() as td:
                jax.profiler.start_trace(td)
                try:
                    req = eng.submit(
                        trng.randint(0, cfg.vocab_size, (9,)), 3)
                    eng.run_until_idle(max_steps=200)
                finally:
                    jax.profiler.stop_trace()
                assert req.finished, req.state
                # the device capture actually wrote an xplane artifact
                arts = [f for root, _, fs in _os.walk(td)
                        for f in fs if f.endswith(".xplane.pb")]
                assert arts, "device trace capture produced no xplane"
                path = _os.path.join(td, "host.json")
                trace.export_chrome_trace(path, tracer=tr)
                with open(path) as f:
                    doc = _json.load(f)
            names = {e["name"] for e in doc["traceEvents"]
                     if e.get("ph") == "X"}
            need = {"serve.step", "serve.dispatch", "serve.device_step",
                    "serve.flight", "jit.fused_step"}
            assert need <= names, f"missing host spans: {need - names}"
        finally:
            trace.disable()
        eng.close()

    # -- distributed fault tolerance: one REAL kill-and-recover scenario
    # with spawned worker processes on this host — a rank killed
    # mid-collective must surface as a typed PeerLostError on every
    # survivor within 2x the detector TTL, the survivors re-rendezvous
    # at a new generation, and the store drains to zero collective keys.
    # The workers exercise the host-side control plane (native TCPStore
    # sockets, heartbeats, generation rendezvous); each pins its own
    # backend to CPU so three processes don't contend for the chip ------
    def dist_fault():
        from tools import dist_fault_gate

        assert dist_fault_gate.scenario_kill_rank(verbose=False), \
            "kill-and-recover scenario failed (see output above)"

    # -- elastic serving: the closed loop on the REAL chips — a parked
    # replica scales up under a queue spike (typed ScaleUp), then the
    # idle scale-down drains it through the deadline-0 token-prefix
    # checkpoint path, and every request (re-homed ones included) must
    # stay bitwise-equal to the single-chip greedy oracle
    # (docs/serving.md "Elasticity & degradation ladder") ----------------
    def elastic_serving():
        import paddle_tpu as pt
        from paddle_tpu.models import GPTStackedForPretraining, gpt_tiny
        from paddle_tpu.serving import (
            ElasticConfig, ElasticServingController, ScaleDown, ScaleUp,
            ShardedServingEngine, SLOTargets,
        )

        if len(jax.devices()) < 2:
            print("tpu_smoke: elastic_serving: single-chip host, skipped")
            return
        pt.seed(0)
        cfg = gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0)
        m = GPTStackedForPretraining(cfg)
        m.eval()
        erng = np.random.RandomState(5)
        prompts = [erng.randint(0, cfg.vocab_size, (s,))
                   for s in (6, 15, 9, 21, 12, 18)]
        refs = [np.asarray(
            m.generate(pt.to_tensor(p[None, :], dtype="int64"),
                       max_new_tokens=8, max_seq_len=128,
                       cache_dtype="bfloat16").numpy())[0]
            for p in prompts]
        eng = ShardedServingEngine(m, dp=2, mp=1, num_slots=2,
                                   page_size=128, max_context=128,
                                   cache_dtype="bfloat16")
        warm = [eng.submit(p, 2) for p in prompts[:2]]
        eng.run_until_idle(max_steps=200)          # compile both replicas
        assert all(r.terminal for r in warm)
        t = [0.0]
        ctl = ElasticServingController(
            eng, ElasticConfig(targets=SLOTargets(queue_high=2.0,
                                                  queue_low=0.5),
                               min_samples=10**9, cooldown_s=2.0,
                               overload_sustain_s=1e9,
                               underload_sustain_s=2.0,
                               drain_deadline_s=0.0, min_dp=1),
            clock=lambda: t[0])
        eng.drain_replica(1, deadline_s=0.0)       # start scaled down
        reqs = [eng.submit(p, 8) for p in prompts]  # the spike
        for _ in range(60):
            ctl.tick()
            eng.step()
            t[0] += 1.0
            if (all(r.terminal for r in reqs)
                    and eng.placement.pending() == 0
                    and eng.replica_states() == ["active", "parked"]):
                break
        acts = [type(a).__name__ for a in ctl.actions]
        assert any(isinstance(a, ScaleUp) for a in ctl.actions), acts
        assert any(isinstance(a, ScaleDown) for a in ctl.actions), acts
        assert eng.replica_states() == ["active", "parked"], \
            eng.replica_states()
        for r, ref in zip(reqs, refs):
            assert r.finished and np.array_equal(r.output_ids(), ref), \
                f"request {r.id} diverged from the single-chip oracle " \
                f"(rehomed={r.rehomed})"
        # the checkpoint path, deterministically: seat work on replica 1,
        # then force a deadline-0 drain mid-generation — the seated
        # requests fold their emitted prefix, re-home to replica 0, and
        # must STILL match the oracle bitwise
        before = eng.metrics()["rehomed"]
        eng.activate_replica(1)
        reqs2 = [eng.submit(p, 8) for p in prompts[:4]]
        for _ in range(2):
            eng.step()
        eng.drain_replica(1, deadline_s=0.0, max_steps=200)
        eng.run_until_idle(max_steps=300)
        for r, ref in zip(reqs2, refs[:4]):
            assert r.finished and np.array_equal(r.output_ids(), ref), \
                f"re-homed request {r.id} diverged (rehomed={r.rehomed})"
        mets = eng.metrics()
        assert mets["rehomed"] - before >= 1, \
            "the deadline-0 drain checkpointed nothing"
        for i, rep in enumerate(eng.replicas):
            assert rep.allocator.used_pages == 0, f"replica {i} leaked"
        ctl.close()
        print(f"tpu_smoke: elastic_serving: {acts} "
              f"rehomed={mets['rehomed']} "
              f"replica_steps={mets['replica_steps']} (bitwise)")
        eng.close()

    # -- disaggregated serving: prefill/decode roles with REAL page
    # hand-offs between two on-chip pools (the copy path goes
    # device-to-device on TPU — no host staging); disagg greedy must be
    # bitwise the single-chip oracle, every request must actually move,
    # and both pools' ledgers must drain to zero ------------------------
    def disagg_serving():
        import paddle_tpu as pt
        from paddle_tpu.models import GPTStackedForPretraining, gpt_tiny
        from paddle_tpu.serving import DisaggServingEngine

        n_dev = len(jax.devices())
        if n_dev < 2:
            print("tpu_smoke: disagg_serving: single-chip host, skipped")
            return
        pt.seed(0)
        cfg = gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0)
        m = GPTStackedForPretraining(cfg)
        m.eval()
        drng = np.random.RandomState(13)
        prompts = [drng.randint(0, cfg.vocab_size, (s,))
                   for s in (7, 19, 11, 24)]
        refs = [np.asarray(
            m.generate(pt.to_tensor(p[None, :], dtype="int64"),
                       max_new_tokens=6, max_seq_len=128,
                       cache_dtype="bfloat16").numpy())[0]
            for p in prompts]
        eng = DisaggServingEngine(m, roles=("prefill", "decode"), mp=1,
                                  num_slots=2, page_size=128,
                                  max_context=128,
                                  cache_dtype="bfloat16")
        reqs = [eng.submit(p, 6) for p in prompts]
        eng.run_until_idle(max_steps=500)
        for r, ref in zip(reqs, refs):
            assert r.finished and np.array_equal(r.output_ids(), ref), \
                f"request {r.id} diverged across the page hand-off"
        mets = eng.metrics()
        assert mets["transfers_total"] >= 1, "no hand-off happened"
        assert mets["transferred_in"] == mets["transferred_out"] == \
            mets["transfers_total"], mets
        assert mets["transfer_pages"] >= mets["transfers_total"]
        for i, rep in enumerate(eng.replicas):
            a = rep.allocator
            assert a.used_pages == 0 and a.spec_pages == 0, \
                f"replica {i} ({eng.roles[i]}) leaked pages"
        print(f"tpu_smoke: disagg_serving: "
              f"{mets['transfers_total']} hand-offs, "
              f"{mets['transfer_pages']} pages / "
              f"{mets['transfer_bytes']}B device-to-device (bitwise)")
        eng.close()

    # -- train pipeline: ONE on-chip fused train step (fwd+bwd+AdamW with
    # fp32 masters, donated) fed through the device prefetcher — proves
    # the donated program + the async input pipeline + the stall
    # histogram work against the REAL backend, not the CPU interpreter ---
    def train_pipeline():
        import paddle_tpu as pt
        from paddle_tpu.io import DevicePrefetcher
        from paddle_tpu.models import GPTStackedForPretraining, gpt_tiny
        from paddle_tpu.telemetry import registry

        pt.seed(0)
        cfg = gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0,
                       recompute_interval=1)
        m = GPTStackedForPretraining(cfg)
        pt.amp.decorate(m, level="O2", dtype="bfloat16")
        opt = pt.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=m.parameters(),
                                 multi_precision=True)
        step = pt.optimizer.FusedTrainStep(
            lambda i, l: m(i, labels=l), opt,
            amp_level="O1", amp_dtype="bfloat16")
        trng = np.random.RandomState(3)
        n = 4

        def batches():
            for _ in range(n):
                yield (trng.randint(0, cfg.vocab_size, (2, 64)),
                       trng.randint(0, cfg.vocab_size, (2, 64)))

        hist = registry().histogram("train_input_stall_seconds")
        h0 = hist.summary().get("count", 0)
        pf = DevicePrefetcher(batches(), depth=2)
        losses = [float(step(i, l)) for i, l in pf]
        pf.close()
        assert len(losses) == n and all(np.isfinite(losses)), losses
        assert step.program_count == 1, \
            f"fused step retraced: {step.program_count} programs"
        st = pf.stats()
        assert st["batches"] == n, st
        # non-degenerate histogram: one stall sample per consumed batch
        hn = hist.summary().get("count", 0) - h0
        assert hn >= n, f"stall histogram recorded {hn} samples (< {n})"
        print(f"tpu_smoke: train_pipeline: {n} fused steps, 1 program, "
              f"stall_total={st['stall_seconds_total'] * 1e3:.2f}ms")

    check("flash_attention", flash)
    check("train_pipeline", train_pipeline)
    check("decode_attention", decode_attention)
    check("paged_attention", paged_attention)
    check("quantized_kv", quantized_kv)
    check("ragged_attention", ragged_attention)
    check("fused_adamw", fused_adamw)
    check("rms_norm", rms_norm)
    check("graph_lint", graph_lint)
    check("mesh_lint", mesh_lint)
    check("checkpoint", checkpoint)
    check("serving_faults", serving_faults)
    check("sharded_serving", sharded_serving)
    check("elastic_serving", elastic_serving)
    check("disagg_serving", disagg_serving)
    check("speculative_serving", speculative_serving)
    check("prefix_cache", prefix_cache)
    check("autotune_sweep", autotune_sweep)
    check("telemetry", telemetry)
    check("dist_fault", dist_fault)

    if failures:
        print(f"tpu_smoke: FAILED: {failures}")
        return 1
    print("tpu_smoke: all owned kernels healthy on-chip")
    return 0


if __name__ == "__main__":
    sys.exit(main())
