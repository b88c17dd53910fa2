#!/usr/bin/env python
"""Pallas kernel autotuner CLI: sweep, seed, validate, report.

The persisted table (``paddle_tpu/analysis/autotune_table.json``, override
with ``PADDLE_TPU_AUTOTUNE_TABLE``) maps (kernel, shape, dtype) keys to
winning block/sublane configs.  Kernels consult it at dispatch with a
fallback to their historical hard-coded shapes (docs/graph_lint.md
"v2: autotuner").

Modes:
  --validate   strict replay validation of the committed table against the
               CURRENT static gates (tile rules + VMEM estimate).  Pure
               static analysis — runs on CPU, never times anything.  This
               is the run_tests.sh gate (PADDLE_TPU_SKIP_AUTOTUNE_GATE=1
               skips).  Exit 0 valid / 1 invalid / 2 unreadable.
  --seed       (re)write static-default entries for the bench shape keys —
               the same configs the kernels would pick with no table, but
               now flowing THROUGH the table so dispatch is exercised
               before any chip timed anything.  Measured entries are kept.
  --report     print every entry plus the static candidate ranking.
  (default)    measured sweep on a real TPU: for each bench shape key,
               time every legal candidate once on-device and persist the
               winner.  Exit 2 on CPU-only hosts (tri-state like
               tpu_smoke: nothing was timed, nothing failed).

Usage:
  python tools/autotune.py --validate
  python tools/autotune.py --seed
  python tools/autotune.py                 # on a TPU host
"""
from __future__ import annotations

import argparse
import os
import sys
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

# the bench workloads' kernel specializations (bench.py rungs + decode /
# serving phases): the shapes a sweep must cover for the table to matter
BENCH_KEYS = [
    # flash_attention: (seq, head_dim) per rung model; bf16 is the
    # headline regime, the last rung runs AMP O1 (bf16 dots) too
    ("flash_attention", {"seq": 1024, "head_dim": 128}, "bfloat16"),
    ("flash_attention", {"seq": 1024, "head_dim": 64}, "bfloat16"),
    ("flash_attention", {"seq": 512, "head_dim": 64}, "bfloat16"),
    # decode: bench caches round (prompt+new) up to a 128-multiple (256)
    ("decode_attention", {"max_seq": 256, "head_dim": 128}, "bfloat16"),
    ("decode_attention", {"max_seq": 256, "head_dim": 64}, "bfloat16"),
    # paged serving: page_size 128 pools
    ("paged_attention", {"page_size": 128, "head_dim": 128}, "bfloat16"),
    ("paged_attention", {"page_size": 128, "head_dim": 64}, "bfloat16"),
    # ragged fused mixed prefill/decode step: same pool specializations
    ("ragged_paged_attention", {"page_size": 128, "head_dim": 128},
     "bfloat16"),
    ("ragged_paged_attention", {"page_size": 128, "head_dim": 64},
     "bfloat16"),
    # measured remat-policy search on the stacked-GPT train step: the
    # bench ladder's pure-bf16 rungs (1.3B bs 8/4, small bs 16).  Each
    # candidate (recompute_interval, recompute_policy) is timed as ONE
    # full fused train step on-device — expensive (a compile per
    # candidate), which is why the winner persists in the table and
    # bench.py only ever reads it.
    ("train_remat", {"layers": 24, "hidden": 2048, "batch": 8, "seq": 1024},
     "bfloat16"),
    ("train_remat", {"layers": 24, "hidden": 2048, "batch": 4, "seq": 1024},
     "bfloat16"),
    ("train_remat", {"layers": 12, "hidden": 768, "batch": 16, "seq": 1024},
     "bfloat16"),
]

# the bench's CPU-fallback train shape: --train-sweep times these on a
# CPU-only host (a whole-train-step measurement is backend-agnostic in a
# way a Mosaic kernel launch is not; entries are provenance-tagged with
# the measuring device and only ever read back for the SAME shape key)
TRAIN_REMAT_CPU_KEYS = [
    ("train_remat", {"layers": 2, "hidden": 768, "batch": 2, "seq": 128},
     "float32"),
]


def _dtype(name):
    import jax.numpy as jnp

    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[name]


def _time_once(fn, *args) -> float:
    """One warmed measured execution (compile excluded)."""
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    out = fn(*args)
    jax.block_until_ready(out)
    return time.perf_counter() - t0


def _timing_fn(kernel, shape, dtype_name):
    """Build the per-candidate timing closure for one bench key.  Each
    closure forces the candidate through the kernel's public dispatch
    (autotune.force) so exactly the production code path is timed."""
    if kernel == "train_remat":
        return _train_remat_timing_fn(shape, dtype_name)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.analysis import autotune
    from paddle_tpu.ops.pallas_kernels import (decode_attention as da,
                                               flash_attention as fa,
                                               paged_attention as pa)

    rng = np.random.RandomState(0)
    dt = _dtype(dtype_name)
    d = shape["head_dim"]
    if kernel == "flash_attention":
        s = shape["seq"]
        q, k, v = (jnp.array(rng.randn(2, 4, s, d), dt) for _ in range(3))

        def fwd_bwd(q, k, v):
            return jax.grad(lambda *xs: fa._flash_bnsd(
                *xs, True, 0.125).astype(jnp.float32).sum(), (0, 1, 2))(
                    q, k, v)

        def run(params):
            # a FRESH jit per candidate: the forced params are read at
            # trace time, and identical avals would otherwise hit the
            # previous candidate's compiled executable
            with autotune.force(kernel, params):
                return _time_once(jax.jit(fwd_bwd), q, k, v)

        return run
    if kernel == "decode_attention":
        s = shape["max_seq"]
        q = jnp.array(rng.randn(4, 8, d), dt)
        k = jnp.array(rng.randn(4, 8, s, d), dt)
        v = jnp.array(rng.randn(4, 8, s, d), dt)

        def run(params):
            with autotune.force(kernel, params):
                return _time_once(  # fresh jit per candidate (see above)
                    jax.jit(lambda *xs: da.decode_attention(*xs)),
                    q, k, v, jnp.int32(s))

        return run
    if kernel == "paged_attention":
        ps = shape["page_size"]
        pages, slots, mp, h = 33, 4, 8, 8
        q = jnp.array(rng.randn(slots, h, d), dt)
        kp = jnp.array(rng.randn(pages, h, ps, d), dt)
        vp = jnp.array(rng.randn(pages, h, ps, d), dt)
        tbl = jnp.array(rng.permutation(pages - 1)[:slots * mp].reshape(
            slots, mp) + 1, jnp.int32)
        lens = jnp.full((slots,), ps * mp, jnp.int32)

        def run(params):
            with autotune.force(kernel, params):
                return _time_once(  # fresh jit per candidate (see above)
                    jax.jit(lambda *xs: pa.paged_attention(*xs)),
                    q, kp, vp, tbl, lens)

        return run
    if kernel == "ragged_paged_attention":
        from paddle_tpu.ops.pallas_kernels import (
            ragged_paged_attention as ra,
        )

        ps = shape["page_size"]
        pages, mp, h = 33, 4, 8
        kp = jnp.array(rng.randn(pages, h, ps, d), dt)
        vp = jnp.array(rng.randn(pages, h, ps, d), dt)
        # a representative fused mixed step: 4 decode slots deep into
        # their context + one 64-token prefill run (skewed lengths)
        tbls = [np.sort(rng.permutation(pages - 1)[:mp] + 1).astype(np.int32)
                for _ in range(5)]
        runs = [(ps * mp - 1, 1, tbls[0]), (ps - 1, 1, tbls[1]),
                (2 * ps, 1, tbls[2]), (7, 1, tbls[3]), (ps // 2, 64, tbls[4])]
        t_max = 80

        def run(params):
            with autotune.force(kernel, params):
                # plan geometry depends on the candidate's token_block —
                # rebuild it per candidate exactly like the engine would
                tb = ra.ragged_token_block(ps, d, dt)
                plan_np, stats = ra.build_ragged_plan(
                    runs, token_block=tb, page_size=ps, t_max=t_max,
                    nb_max=16, wl_max=16 * mp)
                q = jnp.array(rng.randn(t_max, h, d), dt)
                tables = np.zeros((t_max, mp), np.int32)
                lens = np.zeros((t_max,), np.int32)
                for (base, count, tr), start in zip(runs,
                                                    stats["run_starts"]):
                    tables[start:start + count] = tr
                    lens[start:start + count] = base + np.arange(count) + 1
                plan = tuple(jnp.array(plan_np[k])
                             for k in ra.RAGGED_PLAN_FIELDS)
                return _time_once(  # fresh jit per candidate (see above)
                    jax.jit(lambda qq, kk, vv, tt, ll:
                            ra.ragged_paged_attention(qq, kk, vv, tt, ll,
                                                      plan)),
                    q, kp, vp, jnp.array(tables), jnp.array(lens))

        return run
    raise ValueError(kernel)


def _train_remat_timing_fn(shape, dtype_name):
    """Timing closure for the remat-policy search: ONE steady-state fused
    train step (fwd+bwd+AdamW, AMP O1, donated) per candidate, on the
    REAL bench model shape.  The model is built once per shape key; each
    candidate mutates the remat config and compiles a fresh FusedTrainStep
    (the config is read at trace time).  A candidate that OOMs raises and
    is recorded as dead — exactly the failure mode the static model
    cannot see."""
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.analysis import autotune
    from paddle_tpu.models import (GPTStackedForPretraining, gpt_1p3b,
                                   gpt_small, gpt_tiny)

    presets = {2048: gpt_1p3b, 768: gpt_small, 64: gpt_tiny}
    mk = presets[int(shape["hidden"])]
    cfg = mk(hidden_dropout=0.0, attention_dropout=0.0,
             max_position_embeddings=max(int(shape["seq"]), 1024),
             recompute_interval=1, use_flash_attention=True)
    cfg.num_layers = int(shape["layers"])
    pt.seed(0)
    model = GPTStackedForPretraining(cfg)
    if dtype_name == "bfloat16":
        pt.amp.decorate(model, level="O2", dtype="bfloat16")
    opt = pt.optimizer.AdamW(learning_rate=1e-4,
                             parameters=model.parameters(),
                             multi_precision=dtype_name != "bfloat16")
    rng = np.random.RandomState(0)
    b, s = int(shape["batch"]), int(shape["seq"])
    ids = pt.to_tensor(rng.randint(0, cfg.vocab_size, (b, s)), dtype="int64")
    labels = pt.to_tensor(rng.randint(0, cfg.vocab_size, (b, s)),
                          dtype="int64")

    def run(params):
        cfg.recompute_interval, cfg.recompute_policy = (
            autotune.remat_params_to_config(params))
        step = pt.optimizer.FusedTrainStep(
            lambda i, l: model(i, labels=l), opt,
            amp_level="O1", amp_dtype="bfloat16")
        float(step(ids, labels))  # compile + first dispatch
        # best-of-3 steady-state: one whole-train-step sample is noisier
        # than a kernel launch, and a noise-picked winner persists
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            float(step(ids, labels))
            best = min(best, time.perf_counter() - t0)
        return best

    return run


def run(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="autotune.py",
        description="Pallas kernel autotuner (docs/graph_lint.md)")
    ap.add_argument("--validate", action="store_true",
                    help="strict replay validation of the table (CI gate)")
    ap.add_argument("--seed", action="store_true",
                    help="write static-default entries for the bench keys")
    ap.add_argument("--report", action="store_true",
                    help="print table entries + static candidate ranking")
    ap.add_argument("--train-sweep", action="store_true",
                    help="measured remat-policy sweep over the train_remat "
                         "keys only — times FULL fused train steps, so it "
                         "also runs on CPU-only hosts (against the bench's "
                         "CPU-fallback shape)")
    ap.add_argument("--table", default=None, metavar="PATH",
                    help="table path (default: the packaged table / "
                         "PADDLE_TPU_AUTOTUNE_TABLE)")
    args = ap.parse_args(argv)

    from paddle_tpu.analysis import autotune

    path = args.table or autotune.table_path()

    if args.validate:
        if not os.path.exists(path):
            print(f"autotune: no table at {path} (empty table is valid)")
            return 0
        try:
            table = autotune.AutotuneTable.load(path)
        except Exception as e:  # noqa: BLE001 — unreadable is its own verdict
            print(f"autotune: table {path} unreadable: "
                  f"{type(e).__name__}: {e}")
            return 2
        problems = autotune.validate_table(table)
        if problems:
            print(f"autotune: {path}: {len(problems)} INVALID entries:")
            for p in problems:
                print("  " + p)
            return 1
        print(f"autotune: {path}: {len(table.entries)} entries valid "
              "against the current static gates")
        return 0

    if args.seed:
        table = (autotune.AutotuneTable.load(path) if os.path.exists(path)
                 else autotune.AutotuneTable())
        n = 0
        for kernel, shape, dtype in BENCH_KEYS:
            if not autotune.enumerate_candidates(kernel, shape, dtype):
                continue
            existing = table.entries.get(
                autotune.table_key(kernel, shape, dtype))
            if existing and existing.get("source") == "measured":
                continue  # never displace a measurement with a guess
            table.put(kernel, shape, dtype,
                      autotune.default_params(kernel, shape, dtype),
                      source="static-default")
            n += 1
        table.save(path)
        print(f"autotune: seeded {n} static-default entries -> {path} "
              f"({len(table.entries)} total)")
        return 0

    if args.report:
        table = (autotune.AutotuneTable.load(path) if os.path.exists(path)
                 else autotune.AutotuneTable())
        for key in sorted(table.entries):
            e = table.entries[key]
            us = e.get("measured_us")
            print(f"{key}: {e['params']} "
                  f"[{e['source']}{f', {us:.1f}us' if us else ''}]")
            ranked = autotune.static_rank(e["kernel"], e["shape"],
                                          e["dtype"])
            print(f"  static ranking ({len(ranked)} candidates): "
                  + "; ".join(str(p) for p in ranked[:4]))
        return 0

    # -- measured sweep (TPU only, except --train-sweep) -------------------
    import jax

    on_cpu = jax.devices()[0].platform == "cpu"
    if not on_cpu:
        from paddle_tpu.sysconfig import enable_compile_cache

        enable_compile_cache()
    if args.train_sweep:
        device = ("cpu" if on_cpu
                  else getattr(jax.devices()[0], "device_kind", "tpu"))
        keys = (TRAIN_REMAT_CPU_KEYS if on_cpu else
                [k for k in BENCH_KEYS if k[0] == "train_remat"])
        table = (autotune.AutotuneTable.load(path) if os.path.exists(path)
                 else autotune.AutotuneTable())
        for kernel, shape, dtype in keys:
            cands = autotune.enumerate_candidates(kernel, shape, dtype)
            print(f"autotune: {kernel} {shape} {dtype}: timing "
                  f"{len(cands)} candidates (full train steps, "
                  f"device={device})...")
            winner, results = autotune.sweep(
                kernel, shape, dtype, _timing_fn(kernel, shape, dtype),
                table=table, device=str(device))
            for params, seconds in sorted(results, key=lambda ps: ps[1]):
                mark = " <- winner" if params == winner else ""
                t = ("FAILED" if seconds == float("inf")
                     else f"{seconds * 1e3:8.2f}ms")
                print(f"  {t}  {params}{mark}")
        table.save(path)
        print(f"autotune: wrote {len(table.entries)} entries -> {path}")
        return 0

    if on_cpu:
        print("autotune: no TPU backend; nothing to time (the table loads "
              "in validated replay mode on CPU — use --validate/--seed, "
              "or --train-sweep for the whole-step remat search)")
        return 2
    device = getattr(jax.devices()[0], "device_kind", "tpu")
    table = (autotune.AutotuneTable.load(path) if os.path.exists(path)
             else autotune.AutotuneTable())
    for kernel, shape, dtype in BENCH_KEYS:
        cands = autotune.enumerate_candidates(kernel, shape, dtype)
        if not cands:
            print(f"autotune: {kernel} {shape} {dtype}: shape ineligible, "
                  "skipped")
            continue
        print(f"autotune: {kernel} {shape} {dtype}: timing {len(cands)} "
              "candidates...")
        winner, results = autotune.sweep(
            kernel, shape, dtype, _timing_fn(kernel, shape, dtype),
            table=table, device=str(device))
        for params, seconds in sorted(results, key=lambda ps: ps[1]):
            mark = " <- winner" if params == winner else ""
            t = ("FAILED" if seconds == float("inf")
                 else f"{seconds * 1e6:8.1f}us")
            print(f"  {t}  {params}{mark}")
    table.save(path)
    print(f"autotune: wrote {len(table.entries)} entries -> {path}")
    return 0


if __name__ == "__main__":
    sys.exit(run())
