"""Benchmark: GPT train-step, decode and serving throughput on one chip.

One process on the TPU: the flagship rung (GPT-3 1.3B, batch 8 x seq 1024,
pure bf16 — fwd + bwd + AdamW fused into a single donated XLA program via
``FusedTrainStep``), then ``generate()`` decode and the continuous-batching
``ServingEngine`` on the same weights.  Prints one JSON line per metric:
{"metric", "value", "unit"}.  Without a TPU it exits non-zero; a phase
that fails raises.  (ROADMAP S1/D1: the next PR replaces this file with a
list of cells.)
"""
import json
import os
import sys
import time

import numpy as np

# bf16 matmuls for the MXU: the bench path uses AMP O1 (reference
# amp_guard list-based casting), so keep default matmul precision.
os.environ.setdefault("JAX_DEFAULT_MATMUL_PRECISION", "default")

# (model, batch, seq, steps, remat, regime): the BASELINE flagship GPT-3
# 1.3B at the one shape with a chip history (TPU_SWEEP.json), in the
# pure-bf16 regime (bf16 params AND bf16 AdamW moments) so the full
# optimizer state fits one v5e chip.  jit.to_static's abstract scout means
# NO eager step of the model ever runs — peak residency is the compiled
# step's own.  BENCH_CONFIG="model:bs:seq:steps:remat:regime" overrides.
# regime: "bf16" = pure bf16 (bf16 params+moments, no masters), "master" =
# bf16 params + fp32 master weights/moments, "fp32" = fp32 params under AMP
# O1.  BENCH_PRECISION overrides the regime for A/B runs.
_RUNG = ("1p3b", 8, 1024, 10, 1, "bf16")

_REGIMES = ("bf16", "master", "fp32")


def _parse_regime(tok: str, strict: bool = False) -> str:
    """BENCH_CONFIG back-compat: the old boolean pure_bf16 sixth field
    still parses ('1'/'true' -> bf16, '0'/'false' -> fp32).  ``strict``
    (the BENCH_PRECISION path) rejects unknown tokens instead — a typo'd
    regime must not silently record an fp32 measurement labeled as
    something else."""
    if tok in _REGIMES:
        return tok
    if strict:
        raise ValueError(
            f"BENCH_PRECISION={tok!r}: expected one of {_REGIMES}")
    return "bf16" if tok in ("1", "true", "True") else "fp32"


def _emit(metric, value, unit):
    print(json.dumps({"metric": metric, "value": value, "unit": unit}))
    sys.stdout.flush()


def _chip_spec(device_kind: str):
    """HardwareSpec (bf16 peak FLOP/s + HBM BW) of the device under the
    measurement — ONE table, owned by analysis/cost_model.py, so the MFU
    denominator and the roofline-fraction denominator can't drift apart.
    A device that is not in the table raises."""
    from paddle_tpu.analysis import chip_spec

    return chip_spec(device_kind)


def _emit_roofline(phase, name, cost_reports_with_counts, spec, seconds):
    """One ``*_roofline_fraction`` line: achieved FLOP/s over roofline-
    attainable FLOP/s for the phase's compiled program(s), from the static
    cost model (FLAGS_graph_cost) + the measured wall time.  Makes the MFU
    gap attributable per program: a low fraction on a memory-bound program
    means the gap is HBM streaming, not MXU idling."""
    try:
        flops = sum(c.flops * n for c, n in cost_reports_with_counts)
        nbytes = sum(c.bytes_upper * n for c, n in cost_reports_with_counts)
        if not flops or seconds <= 0:
            return
        intensity = flops / max(nbytes, 1)
        attainable = spec.attainable_flops(intensity)
        progs = ",".join(f"{c.program}x{n}"
                         for c, n in cost_reports_with_counts)
        # comm-aware denominators (Graph Lint v3): when any program has
        # modelled collectives, the UNHIDEABLE comm time (comm seconds x
        # (1 - overlap fraction)) is subtracted from the compute roofline's
        # wall clock instead of folding it into apparent MFU loss, and the
        # comm share is emitted as its own *_comm_roofline_fraction line.
        comm_s = sum(c.comm_seconds(spec) * n
                     for c, n in cost_reports_with_counts
                     if getattr(c, "collectives", None))
        compute_seconds = seconds
        comm_note = ""
        if comm_s > 0:
            ov = sum(c.overlap_fraction(spec) * c.comm_seconds(spec) * n
                     for c, n in cost_reports_with_counts
                     if getattr(c, "collectives", None)) / comm_s
            unhidden = comm_s * (1.0 - ov)
            compute_seconds = max(seconds - min(unhidden, seconds * 0.99),
                                  seconds * 0.01)
            comm_note = (" denominator=wall_minus_unhidden_comm "
                         f"comm_est_ms={comm_s * 1e3:.3f} "
                         f"overlap_frac={ov:.2f}")
        frac = (flops / compute_seconds) / attainable
        _emit(
            f"gpt_{name}_{phase}_roofline_fraction",
            round(frac, 4),
            f"frac=compute-roofline (programs={progs} gflop={flops / 1e9:.1f} "
            f"hbm_mib={nbytes / 2**20:.0f} intensity={intensity:.1f} "
            f"bound={'compute' if intensity >= spec.ridge else 'memory'} "
            f"attainable={attainable / 1e12:.1f}e12 chip={spec.name}"
            f"{comm_note})",
        )
        if comm_s > 0:
            # comm roofline: modelled ICI seconds / measured wall seconds —
            # how much of the step the static comm model accounts for
            _emit(
                f"gpt_{name}_{phase}_comm_roofline_fraction",
                round(comm_s / seconds, 4),
                f"frac=comm_est/wall (programs={progs} "
                f"comm_est_ms={comm_s * 1e3:.3f} wall_ms={seconds * 1e3:.3f} "
                f"ici_bw={spec.ici_bw / 1e9:.0f}GB/s chip={spec.name})",
            )
    except Exception as e:  # noqa: BLE001 — a cost line must never kill a metric
        sys.stderr.write(f"bench: roofline line ({phase}) failed: "
                         f"{type(e).__name__}: {str(e)[:300]}\n")


def main():
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: needs a TPU; JAX found platform "
                 f"'{devs[0].platform}' ({devs[0].device_kind}) — nothing "
                 "here runs a measurement on another backend")

    import paddle_tpu as pt
    from paddle_tpu import analysis
    from paddle_tpu.core import memory as pt_memory
    from paddle_tpu.models import GPTStackedForPretraining, gpt_1p3b, gpt_small
    from paddle_tpu.sysconfig import enable_compile_cache

    enable_compile_cache()

    # static roofline cost reports for every compiled program (one extra
    # abstract trace per compile, zero compute): the *_roofline_fraction
    # lines below attribute the MFU gap per program
    pt.set_flags({"FLAGS_graph_cost": True})

    custom = os.environ.get("BENCH_CONFIG")  # "model:bs:seq:steps:remat:regime"
    if custom:
        name, batch, seq, steps, remat, regime = custom.split(":")
        batch, seq, steps, remat = map(int, (batch, seq, steps, remat))
    else:
        name, batch, seq, steps, remat, regime = _RUNG
    env_precision = os.environ.get("BENCH_PRECISION")
    regime = (_parse_regime(env_precision, strict=True) if env_precision
              else _parse_regime(regime))
    param_dtype = "float32" if regime == "fp32" else "bfloat16"

    # remat config precedence: env pin > measured autotune-table winner >
    # rung default.  The table search space is (recompute_interval,
    # recompute_policy) on the stacked scan — tools/autotune.py times each
    # candidate train step once on-device and persists the winner under
    # the same shape-key discipline as the Pallas kernels.
    remat_policy = os.environ.get("BENCH_REMAT_POLICY") or None
    env_interval = os.environ.get("BENCH_REMAT_INTERVAL")
    if env_interval is not None:
        remat = int(env_interval)
    elif remat_policy is None:
        from paddle_tpu.analysis import autotune as _autotune

        mk_probe = gpt_1p3b if name == "1p3b" else gpt_small
        remat_shape = {"layers": mk_probe().num_layers,
                       "hidden": mk_probe().hidden_size,
                       "batch": batch, "seq": seq}
        tuned = _autotune.kernel_params("train_remat", remat_shape,
                                        param_dtype)
        if tuned is not None:
            remat, remat_policy = _autotune.remat_params_to_config(tuned)
            sys.stderr.write(f"bench: train_remat table hit: "
                             f"interval={remat} policy={remat_policy}\n")

    mk = gpt_1p3b if name == "1p3b" else gpt_small
    # remat policy: "dots" = selective remat (save MXU outputs, recompute
    # only VPU work in backward) — trades HBM for the ~33% recompute FLOPs
    # full remat pays; interval k groups k blocks per checkpoint boundary
    # on the stacked scan
    cfg = mk(hidden_dropout=0.0, attention_dropout=0.0,
             max_position_embeddings=max(seq, 1024),
             recompute_interval=remat,
             recompute_policy=remat_policy,
             use_flash_attention=True)

    pt.seed(0)
    model = GPTStackedForPretraining(cfg)
    if regime in ("bf16", "master"):
        # bf16 params (halved parameter HBM traffic per step): "bf16" is
        # the pure regime (bf16 moments, no masters — the reference's
        # non-multi-precision adam); "master" keeps fp32 master weights +
        # fp32 moments in the optimizer (reference multi_precision adam) —
        # the update reads/writes the masters, convergence tracks fp32
        pt.amp.decorate(model, level="O2", dtype="bfloat16")
    # BENCH_FUSED_ADAM=1: route the update through the owned Pallas
    # multi-tensor kernel (ops/pallas_kernels/fused_adamw.py) for A/B
    # against the XLA-composed chain
    opt = pt.optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters(),
                             multi_precision=regime != "bf16",
                             use_fused_kernel=os.environ.get(
                                 "BENCH_FUSED_ADAM") in ("1", "true", "True"))

    rng = np.random.RandomState(0)
    ids = pt.to_tensor(rng.randint(0, cfg.vocab_size, (batch, seq)), dtype="int64")
    labels = pt.to_tensor(rng.randint(0, cfg.vocab_size, (batch, seq)), dtype="int64")

    # ONE donated fused program: fwd + bwd + AdamW update (params, moments
    # and masters alias in place; Graph Lint GL004 gates regressions here)
    train_step = pt.optimizer.FusedTrainStep(
        lambda ids, labels: model(ids, labels=labels), opt,
        amp_level="O1", amp_dtype="bfloat16")

    # async host->device input pipeline: a small pool of distinct host
    # batches cycles through a depth-2 device prefetcher, so the timed
    # loop's device_put overlaps the running step; consumer wait (the
    # input stall the pipeline hides) is measured per batch
    _pool = [(rng.randint(0, cfg.vocab_size, (batch, seq)),
              rng.randint(0, cfg.vocab_size, (batch, seq)))
             for _ in range(min(4, steps))]

    def _host_batches(n):
        for i in range(n):
            yield _pool[i % len(_pool)]

    # Phase-logged protocol (round-3 postmortem: the failing run died at
    # the final sync with no indication of WHICH phase exhausted HBM).
    # With the abstract scout, call 1 = zero-compute capture + compile +
    # first compiled step; later calls are steady-state.
    pt_memory.log_memory("after model+optimizer build")
    try:
        loss = train_step(ids, labels)
        float(loss)  # sync phase 1
    except Exception:
        pt_memory.log_memory("FAILED during compile+first step")
        raise
    pt_memory.log_memory("after compile+first step")
    try:
        for _ in range(2):
            loss = train_step(ids, labels)
        float(loss)
    except Exception:
        pt_memory.log_memory("FAILED during steady-state warmup")
        raise
    pt_memory.log_memory("after steady-state warmup")

    from paddle_tpu.core import op_cache as pt_op_cache
    from paddle_tpu.io import DevicePrefetcher

    disp0 = train_step.dispatch_count
    eager0 = pt_op_cache.summary()["calls"]
    # BENCH_INPUT_MODE=sync: per-step inline host->device conversion (the
    # no-pipeline baseline) for A/B against the default prefetch path
    input_mode = os.environ.get("BENCH_INPUT_MODE", "prefetch")
    profile_dir = os.environ.get("BENCH_PROFILE_DIR")
    if profile_dir:
        import jax.profiler as _jprof
        _jprof.start_trace(profile_dir)
    prefetcher = None
    try:
        # the prefetcher is constructed INSIDE the timed window: its
        # producer thread starts issuing device_puts immediately, and
        # letting that head start run before t0 would flatter the
        # prefetch arm vs the sync baseline by ~depth/steps of transfer
        t0 = time.perf_counter()
        if input_mode != "sync":
            prefetcher = DevicePrefetcher(_host_batches(steps), depth=2)
            for bids, blabels in prefetcher:
                loss = train_step(bids, blabels)
        else:
            for hids, hlabels in _host_batches(steps):
                loss = train_step(pt.to_tensor(hids, dtype="int64"),
                                  pt.to_tensor(hlabels, dtype="int64"))
        final = float(loss)  # forces completion of the async chain
        dt = time.perf_counter() - t0
    finally:
        if prefetcher is not None:
            prefetcher.close()
        if profile_dir:
            _jprof.stop_trace()
            sys.stderr.write(f"bench: profile trace in {profile_dir}\n")
    assert np.isfinite(final), f"bench diverged: loss={final}"
    pf_stats = (prefetcher.stats() if prefetcher is not None
                else {"stall_seconds_total": float("nan")})
    stall_share = (pf_stats["stall_seconds_total"] / dt
                   if dt > 0 and prefetcher is not None else float("nan"))
    # per-step dispatch count: ONE fused program per step + any eager
    # dispatches that leaked into the timed loop (should be zero)
    disp_fused = train_step.dispatch_count - disp0
    disp_eager = pt_op_cache.summary()["calls"] - eager0
    disp_per_step = (disp_fused + disp_eager) / max(steps, 1)

    peak_mib = pt_memory.max_memory_allocated() / 2**20
    sys.stderr.write(pt_memory.memory_summary() + "\n")

    # eager dispatch-cache counters: the measured loop is jit.to_static
    # (cache falls back under tracing by design), but model/optimizer
    # build + data prep run eager — the hit rate here tracks how much of
    # the off-to_static surface rides the compiled fast path
    cache_sum = pt_op_cache.summary()
    sys.stderr.write("bench: dispatch-cache: " + json.dumps(cache_sum) + "\n")

    tokens_per_sec = batch * seq * steps / dt

    # Megatron-LM FLOPs/iteration: 72 b s L h^2 (1 + s/(6h) + V/(12 L h))
    h, L, V = cfg.hidden_size, cfg.num_layers, cfg.vocab_size
    flops_per_iter = 72 * batch * seq * L * h * h * (1 + seq / (6 * h) + V / (12 * L * h))
    model_flops_per_sec = flops_per_iter * steps / dt
    kind = getattr(devs[0], "device_kind", "")
    spec = _chip_spec(kind)
    peak = spec.peak_flops
    mfu = model_flops_per_sec / peak
    hbm = pt_memory.memory_stats().get("bytes_limit", 0) / 2**30

    # MFU denominator recorded so the number is auditable (round-3 weak #4)
    _emit(
        f"gpt_{name}_train_tokens_per_sec_per_chip",
        round(tokens_per_sec, 1),
        f"tokens/s (bs={batch} seq={seq} mfu={mfu:.3f} "
        f"regime={regime} remat={cfg.recompute_interval}:"
        f"{cfg.recompute_policy or 'full'} "
        f"stall_share={stall_share:.4f} "
        f"disp_per_step={disp_per_step:.2f} "
        f"peak_hbm={peak_mib:.0f}MiB hbm_cap={hbm:.1f}GiB "
        f"device='{kind}' peak_flops={peak/1e12:.0f}e12 "
        f"opcache_calls={cache_sum['calls']} "
        f"opcache_hit={cache_sum['hit_rate']:.3f})",
    )
    train_costs = train_step.cost_reports()
    # exact-FLOPs MFU: the static cost model counts the compiled program's
    # actual FLOPs (2NK dots from dimension_numbers — remat recompute
    # included), so this line moves when a REAL lever moves (remat policy,
    # fused head, regime) where the heuristic token formula cannot.
    # Companion line gpt_*_train_mfu sits next to the roofline fraction.
    if train_costs:
        exact_flops = train_costs[0].flops
        exact_mfu = (exact_flops * steps / dt) / peak
        _emit(
            f"gpt_{name}_train_mfu",
            round(exact_mfu, 4),
            f"frac (cost-model program flops={exact_flops / 1e9:.1f}gflop "
            f"x{steps} steps / {dt:.3f}s / peak={peak / 1e12:.0f}e12; "
            f"heuristic_mfu={mfu:.4f} stall_share={stall_share:.4f} "
            f"disp_per_step={disp_per_step:.2f} regime={regime})",
        )
        _emit_roofline("train", name, [(train_costs[0], steps)], spec, dt)

    # ---- decode (serving) metric: prefill + autoregressive decode over the
    # donated KV cache, same model.  Two compiled programs total
    # (prefill + one decode step); the loop is retrace-free and the cache
    # donation keeps HBM flat across steps (delta recorded in the unit).
    dec_bs, prompt_len, new_tokens = 8, 128, 64
    # smallest 128-multiple that fits the request: the cache is live ON TOP
    # of the still-resident train state, and a seq-sized cache (1024+)
    # would be 4-5x more HBM than the 256 positions actually decoded
    max_seq_cache = -(-(prompt_len + new_tokens) // 128) * 128
    prompt = pt.to_tensor(
        rng.randint(0, cfg.vocab_size, (dec_bs, prompt_len)), dtype="int64")
    # warmup compiles prefill + decode; the timed call reuses both.
    # cost registry cleared first so this phase's reports are
    # unambiguously the decode engine's (names repeat across phases)
    analysis.clear_cost_reports()
    model.generate(prompt, max_new_tokens=2, max_seq_len=max_seq_cache,
                   cache_dtype="bfloat16")
    mem_before = pt_memory.memory_allocated()
    t0 = time.perf_counter()
    out_ids = model.generate(prompt, max_new_tokens=new_tokens,
                             max_seq_len=max_seq_cache,
                             cache_dtype="bfloat16")
    np.asarray(out_ids.numpy())  # force completion of the async chain
    dec_dt = time.perf_counter() - t0
    mem_after = pt_memory.memory_allocated()
    pt_memory.log_memory("after decode bench")
    decode_tps = dec_bs * new_tokens / dec_dt
    from paddle_tpu.models import generation as _gen

    tc = _gen.trace_counts()
    _emit(
        f"gpt_{name}_decode_tokens_per_sec_per_chip",
        round(decode_tps, 1),
        f"tokens/s (bs={dec_bs} prompt={prompt_len} new={new_tokens} "
        f"cache=[{cfg.num_layers},{dec_bs},{cfg.num_heads},"
        f"{max_seq_cache},{cfg.head_dim}]xbf16 "
        f"mem_delta={(mem_after - mem_before) / 2**20:.1f}MiB "
        f"traces={tc})",
    )
    dec_costs = {c.program: c for c in analysis.cost_reports()}
    pairs = [(c, n) for c, n in (
        (dec_costs.get("prefill_step"), 1),
        (dec_costs.get("decode_step"), max(new_tokens - 1, 1)),
    ) if c is not None]
    _emit_roofline("decode", name, pairs, spec, dec_dt)

    # ---- serving (continuous batching) metric: paged KV cache + ONE
    # fused mixed prefill/decode step over all slots (ragged work-list
    # kernel), offered load > slot count so admission/retirement churn is
    # part of the measurement.  One compiled program (all-greedy traffic);
    # trace counters + ragged grid occupancy recorded in the unit prove
    # the step never retraced and show how full the launch ran.
    from paddle_tpu.serving import (
        ServingEngine, reset_serve_trace_counts, serve_trace_counts,
    )

    # prefix_cache on: random prompts share no prefixes so the hit
    # rate prints ~0 here (serving_bench --prefix-dist is the shared-
    # prefix traffic bench) — the bench line pins the cache-enabled
    # hot path's throughput trajectory
    s_kw = dict(num_slots=8, page_size=128, max_context=512,
                cache_dtype="bfloat16", prefix_cache=True)
    s_new, n_req, plens = 32, 16, (64, 200, 120, 380)
    reset_serve_trace_counts()
    analysis.clear_cost_reports()  # this phase's programs only
    # mesh-sharded serving (docs/serving.md "Sharded serving"):
    # BENCH_SERVING_MESH=dp,mp runs the phase on a ShardedServingEngine
    # — dp replicas x mp tensor-parallel chips behind one placement
    # scheduler.  Default 1,1 keeps the single-chip trajectory
    # comparable; insufficient devices fall back with a stderr note.
    s_dp, s_mp = 1, 1
    raw_mesh = os.environ.get("BENCH_SERVING_MESH", "1,1")
    try:
        s_dp, s_mp = (int(x) for x in raw_mesh.split(","))
    except ValueError:
        sys.stderr.write(f"bench: BENCH_SERVING_MESH={raw_mesh!r} "
                         "unparsable (want dp,mp); using 1,1\n")
    if s_dp < 1 or s_mp < 1:
        sys.stderr.write(f"bench: BENCH_SERVING_MESH={raw_mesh!r}: "
                         "axes must be >= 1; using 1,1\n")
        s_dp = s_mp = 1
    if s_dp * s_mp > len(jax.devices()):
        sys.stderr.write(
            f"bench: BENCH_SERVING_MESH={s_dp},{s_mp} needs "
            f"{s_dp * s_mp} devices, host has {len(jax.devices())}; "
            "using 1,1\n")
        s_dp = s_mp = 1
    # speculative serving (docs/serving.md "Speculative decoding"):
    # BENCH_SPECULATE=draft,k opts the phase into a SpeculativeEngine
    # — 'same' (acceptance 1.0) or '<n>layer' truncated draft, k
    # proposals per slot per tick.  Off by default so the trajectory
    # stays comparable; mutually exclusive with a >1 serving mesh.
    s_spec = None
    raw_spec = os.environ.get("BENCH_SPECULATE", "")
    if raw_spec:
        try:
            sd, sk = raw_spec.split(",")
            if sd != "same" and not (sd.endswith("layer")
                                     and sd[:-len("layer")].isdigit()):
                raise ValueError(sd)
            s_spec = (sd, int(sk))
        except ValueError:
            sys.stderr.write(f"bench: BENCH_SPECULATE={raw_spec!r} "
                             "unparsable (want same|<n>layer,k); "
                             "ignoring\n")
        if s_spec and s_dp * s_mp > 1:
            sys.stderr.write("bench: BENCH_SPECULATE ignored under "
                             "BENCH_SERVING_MESH>1,1 (speculation is "
                             "per-replica; use engine_factory)\n")
            s_spec = None
    # quantized serving (docs/serving.md "Quantized serving"):
    # BENCH_KV_DTYPE=float32|bfloat16|int8 flips the paged pool
    # regime, BENCH_WEIGHT_DTYPE=int8 PTQs the decode projections.
    # Off by default so the trajectory stays comparable; the weight
    # PTQ runs on a CLONE because quantize_for_serving mutates.
    s_model = model
    s_kvd = os.environ.get("BENCH_KV_DTYPE", "")
    if s_kvd:
        if s_kvd in ("float32", "bfloat16", "int8"):
            s_kw["kv_dtype"] = s_kvd
        else:
            sys.stderr.write(f"bench: BENCH_KV_DTYPE={s_kvd!r} unknown "
                             "(want float32|bfloat16|int8); ignoring\n")
    s_wd = os.environ.get("BENCH_WEIGHT_DTYPE", "")
    if s_wd:
        if s_wd == "int8":
            from paddle_tpu.distributed.serving_mesh import clone_model

            s_model = clone_model(model)
            s_kw["weight_dtype"] = "int8"
        else:
            sys.stderr.write(f"bench: BENCH_WEIGHT_DTYPE={s_wd!r} "
                             "unknown (want int8); ignoring\n")
    if s_dp * s_mp > 1:
        from paddle_tpu.serving import ShardedServingEngine

        eng = ShardedServingEngine(s_model, dp=s_dp, mp=s_mp, **s_kw)
    elif s_spec is not None:
        from paddle_tpu.serving import SpeculativeEngine

        if s_spec[0] == "same":
            s_draft = s_model
        else:
            from paddle_tpu.models import truncated_draft

            s_draft = truncated_draft(s_model,
                                      int(s_spec[0][:-len("layer")]))
        eng = SpeculativeEngine(s_model, s_draft, spec_k=s_spec[1],
                                **s_kw)
    else:
        eng = ServingEngine(s_model, **s_kw)
    # warmup compiles the fused greedy step — one request per dp
    # replica (least-loaded placement seats each on its own replica)
    # so NO replica's SPMD compile lands in the timed window
    for _ in range(s_dp):
        eng.submit(rng.randint(0, cfg.vocab_size, (plens[0],)), 2)
    eng.run_until_idle()
    m0 = eng.metrics()
    mem_before = pt_memory.memory_allocated()
    t0 = time.perf_counter()
    s_reqs = [eng.submit(
        rng.randint(0, cfg.vocab_size, (plens[i % len(plens)],)), s_new)
        for i in range(n_req)]
    eng.run_until_idle()
    s_dt = time.perf_counter() - t0
    mem_after = pt_memory.memory_allocated()
    s_tokens = sum(len(r.tokens) for r in s_reqs)
    mets = eng.metrics()
    tc = serve_trace_counts()
    # occupancy over the measured window only: the engine totals are
    # cumulative and include the warmup request's mostly-empty steps
    # (same subtraction as tools/serving_bench.py)
    d_wcap = mets["work_capacity"] - m0["work_capacity"]
    d_rcap = mets["block_row_capacity"] - m0["block_row_capacity"]
    grid_occ = ((mets["work_items"] - m0["work_items"]) / d_wcap
                if d_wcap else 0.0)
    q_row_occ = ((mets["block_rows"] - m0["block_rows"]) / d_rcap
                 if d_rcap else 0.0)
    pt_memory.log_memory("after serving bench")
    # per-chip pool accounting: the head-sharded pool holds 1/mp of
    # the page bytes per chip; aggregate page capacity grows with dp
    pool_per_chip_mib = mets["cache_bytes_per_chip"] / 2 ** 20
    _emit(
        f"gpt_{name}_serving_tokens_per_sec_per_chip",
        round(s_tokens / s_dt / max(s_dp * s_mp, 1), 1),
        f"tokens/s (mesh={s_dp}x{s_mp} slots={s_kw['num_slots']} "
        f"reqs={n_req} "
        f"page={s_kw['page_size']} ctx={s_kw['max_context']} "
        f"new={s_new} pool={mets['pages_capacity']}pages "
        f"kv_dtype={s_kw.get('kv_dtype') or s_kw['cache_dtype']} "
        f"weight_dtype={s_kw.get('weight_dtype') or 'native'} "
        f"pool_per_chip={pool_per_chip_mib:.2f}MiB "
        f"aggregate_tps={s_tokens / s_dt:.1f} "
        f"completed={mets['completed']} "
        f"grid_occ={grid_occ:.3f} "
        f"q_row_occ={q_row_occ:.3f} "
        f"prefix_hit_rate={mets.get('prefix_hit_rate', 0.0):.3f} "
        f"mem_delta={(mem_after - mem_before) / 2**20:.1f}MiB "
        + (f"spec={s_spec[0]},k={s_spec[1]} "
           f"accept_rate={mets.get('spec_acceptance_rate', 0.0):.3f} "
           if s_spec is not None else "")
        + f"traces={tc})",
    )
    # per-request SLO percentiles from the engine's telemetry
    # histograms (TTFT = submission -> first token, queue included;
    # ITL = gap between consecutive tokens of one request) — the
    # latency companions to the throughput line above
    # sharded runs: per-request SLO histograms are per replica and do
    # not merge exactly — quote replica 0 as the representative
    slo = mets.get("slo") or (
        mets["per_replica"][0].get("slo", {})
        if mets.get("per_replica") else {})

    def _ms(h, q):
        return round(h.get(q, 0.0) * 1000.0, 3)

    tt, it = slo.get("ttft", {}), slo.get("itl", {})
    sharded_run = s_dp * s_mp > 1
    print(json.dumps({
        "metric": f"gpt_{name}_serving_slo_ms",
        "mesh": f"{s_dp}x{s_mp}",
        # sharded runs quote ONE replica's histograms (percentiles of
        # different replicas do not merge); the scope tag keeps the
        # trajectory discontinuity visible when comparing commits
        "scope": "replica0" if sharded_run else "engine",
        "ttft_p50": _ms(tt, "p50"), "ttft_p95": _ms(tt, "p95"),
        "ttft_p99": _ms(tt, "p99"), "ttft_count": int(tt.get("count", 0)),
        "itl_p50": _ms(it, "p50"), "itl_p95": _ms(it, "p95"),
        "itl_p99": _ms(it, "p99"),
        "queue_wait_p50": _ms(slo.get("queue_wait", {}), "p50"),
        "unit": "ms (per-request serving SLOs; includes the warmup "
                "request's compile-dominated TTFT sample"
                + ("; replica-0 scope on a sharded mesh)" if sharded_run
                   else ")"),
    }))
    sys.stdout.flush()
    srv_costs = {c.program: c for c in analysis.cost_reports()}
    # exact invocation counts from the engine's own counter:
    # fused_steps counts actual fused dispatches (idle/recovery ticks
    # don't run the program)
    pairs = [(c, n) for c, n in (
        (srv_costs.get("fused_step"),
         max(int(mets["fused_steps"] - m0["fused_steps"]), 1)),
    ) if c is not None]
    _emit_roofline("serving", name, pairs, spec, s_dt)
    eng.close()


if __name__ == "__main__":
    main()
