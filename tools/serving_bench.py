#!/usr/bin/env python
"""Serving-engine bench + CI gate: continuous batching under offered load.

Sweep mode (default): drives the ServingEngine at increasing offered load
(requests injected per scheduler step) and prints ONE JSON line per level:

  {"metric": "serving_sweep", "offered_load": ..., "tokens_per_sec": ...,
   "mean_occupancy": ..., "mean_queue_depth": ..., "completed": ...,
   "grid_occupancy": ..., "q_row_occupancy": ..., "steps": ...,
   "ttft_ms_p50/p95/p99": ..., "itl_ms_p50/p95/p99": ...,
   "queue_wait_ms_p50": ...}

The SLO keys come from the engine's per-request telemetry histograms
(TTFT = submission -> first token, queue included; ITL = gap between
consecutive tokens of one request; docs/observability.md) — each load
level runs a FRESH engine so the percentiles are per-level, not
cumulative.  The warmup request's single compile-dominated TTFT sample
is included; at >= 8 requests per level it sits above p95 only for the
lowest loads.

tokens/sec should rise with load until the slots saturate, then flatten
while queue depth grows — the continuous-batching signature.  Runs on the
TPU ladder model when a TPU is present, and on a CPU-sized gpt_tiny
otherwise (the numbers are then about the SCHEDULER, not the chip).

``--lengths zipf`` draws prompt lengths from a bounded Zipf long-tail
instead of the fixed cycle — the skewed regime production traffic shows
and exactly where the ragged fused step beats the retired two-phase
design; ``grid_occupancy`` / ``q_row_occupancy`` (work items per work-list
capacity, real query rows per packed block row) make that win measurable
rather than anecdotal.

Gate mode (--gate, wired into run_tests.sh; PADDLE_TPU_SKIP_SERVING_GATE=1
skips): a fast correctness gate in the crash/lint-gate mold —

  - >= 12 varying-length greedy requests through a 3-slot engine with an
    undersized page pool must match single-shot generate() token-for-token;
  - the fused step must compile at most once (trace counter <= 2);
  - block accounting must close: peak pages <= capacity, 0 in use at the
    end, backpressure observed (the pool is sized to force it).

Chaos mode (--chaos): drives the engine at a fixed offered load while
paddle_tpu/faults.py injects step crashes, NaN logits, and allocator
exhaustion mid-run (and one stall when the watchdog is armed).  Prints
one JSON line per measurement window:

  {"metric": "serving_chaos", "window": "before|during|after",
   "tokens_per_sec": ..., "recoveries": ..., "failed": ...}

and asserts the degradation is GRACEFUL: the engine never dies, the
"after" window recovers to a healthy fraction of the "before" throughput,
every request reaches a typed terminal state, and page accounting closes
exactly.  Exit 1 when recovery or accounting fails.

Exit codes: 0 ok, 1 gate/bench/chaos failure.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

import numpy as np  # noqa: E402


def _build(on_tpu: bool):
    import paddle_tpu as pt
    from paddle_tpu.models import (
        GPTStackedForPretraining, gpt_small, gpt_tiny,
    )

    pt.seed(0)
    if on_tpu:
        cfg = gpt_small(hidden_dropout=0.0, attention_dropout=0.0,
                        use_flash_attention=True)
        model = GPTStackedForPretraining(cfg)
        pt.amp.decorate(model, level="O2", dtype="bfloat16")
        serving_kw = dict(num_slots=8, page_size=128, max_context=512,
                          cache_dtype="bfloat16")
        prompt_lens, max_new = (64, 200, 120, 380), 32
    else:
        cfg = gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0)
        model = GPTStackedForPretraining(cfg)
        serving_kw = dict(num_slots=4, page_size=16, max_context=64,
                          cache_dtype="float32")
        prompt_lens, max_new = (6, 14, 9, 20), 6
    model.eval()
    return model, cfg, serving_kw, prompt_lens, max_new


def _slo_keys(mets: dict) -> dict:
    """Flatten an engine's metrics()["slo"] histograms into the sweep
    line's millisecond keys (TTFT/ITL p50/p95/p99 + queue-wait p50)."""
    slo = mets.get("slo", {})

    def ms(h, q):
        return round(h.get(q, 0.0) * 1000.0, 2)

    tt, it = slo.get("ttft", {}), slo.get("itl", {})
    qw = slo.get("queue_wait", {})
    return {
        "ttft_ms_p50": ms(tt, "p50"), "ttft_ms_p95": ms(tt, "p95"),
        "ttft_ms_p99": ms(tt, "p99"), "ttft_count": int(tt.get("count", 0)),
        "itl_ms_p50": ms(it, "p50"), "itl_ms_p95": ms(it, "p95"),
        "itl_ms_p99": ms(it, "p99"),
        "queue_wait_ms_p50": ms(qw, "p50"),
    }


def _prompt_lengths(dist: str, n: int, fixed_cycle, max_prompt: int,
                    rng) -> list:
    """Per-request prompt lengths: the historical fixed cycle, or a
    bounded Zipf long-tail (``--lengths zipf``) — many short prompts, a
    few near-max ones, the skewed regime the ragged step targets."""
    if dist == "fixed":
        return [int(fixed_cycle[i % len(fixed_cycle)]) for i in range(n)]
    if dist == "zipf":
        raw = rng.zipf(1.6, size=n).astype(np.float64)
        # map the unbounded Zipf tail onto [1, max_prompt] keeping rank
        # order: heavy mass at short lengths, a thin tail near the cap
        scaled = np.minimum(raw, 64.0) / 64.0
        return [max(1, int(round(s * max_prompt))) for s in scaled]
    raise ValueError(f"unknown --lengths {dist!r} (fixed|zipf)")


def _make_draft(model, spec: str):
    """Build the draft model a ``--speculate DRAFT,K`` run proposes with:
    ``same`` (the target itself — acceptance 1.0, the pure dispatch-
    amortization measurement) or ``<n>layer`` (a weight-sharing truncated
    prefix, e.g. ``1layer`` — the cheap-draft regime)."""
    if spec == "same":
        return model
    if spec.endswith("layer"):
        from paddle_tpu.models import truncated_draft

        return truncated_draft(model, int(spec[:-len("layer")]))
    raise ValueError(f"unknown draft spec {spec!r} (same|<n>layer)")


def sweep(loads=(0.5, 1.0, 2.0, 4.0), n_requests: int = 24,
          lengths: str = "fixed", mesh=(1, 1), speculate=None,
          lora=None, kv_dtype=None, weight_dtype=None) -> int:
    import jax

    from paddle_tpu.serving import ServingEngine, ShardedServingEngine

    on_tpu = jax.devices()[0].platform != "cpu"
    dp, mp = int(mesh[0]), int(mesh[1])
    if dp * mp > len(jax.devices()):
        print(f"serving_bench: --mesh {dp},{mp} needs {dp * mp} devices, "
              f"host has {len(jax.devices())}", file=sys.stderr)
        return 1
    sharded = dp * mp > 1
    model, cfg, kw, prompt_lens, max_new = _build(on_tpu)
    if kv_dtype is not None:
        # --kv-dtype: the paged pool regime under measurement (int8 pages
        # carry per-(page, head) scale sidecars; engine quantizes on write)
        kw["cache_dtype"] = kv_dtype
    if weight_dtype is not None:
        # --weight-dtype int8: PTQ the decode-path projections before the
        # steps compile (quantization.quantize_for_serving, in the ctor)
        kw["weight_dtype"] = weight_dtype
    rng = np.random.RandomState(0)
    max_prompt = kw["max_context"] - max_new
    plens = _prompt_lengths(lengths, n_requests, prompt_lens, max_prompt,
                            rng)
    prompts = [rng.randint(0, cfg.vocab_size, (plens[i],))
               for i in range(n_requests)]
    draft = spec_k = pool = tenants = None
    if speculate is not None:
        from paddle_tpu.serving import SpeculativeEngine  # noqa: F401

        draft_spec, spec_k = speculate
        spec_k = int(spec_k)
        draft = _make_draft(model, draft_spec)
    if lora is not None:
        from paddle_tpu.serving import LoRAAdapterPool, random_adapter

        n_tenants, rank = int(lora[0]), int(lora[1])
        # the adapter slab stays floating-point even under an int8 pool
        # (LoRA deltas are computed in the activation dtype, not the KV's)
        slab_dtype = ("float32" if kw["cache_dtype"] == "int8"
                      else kw["cache_dtype"])
        pool = LoRAAdapterPool(cfg, num_adapter_pages=max(n_tenants, 1),
                               rank=rank, dtype=slab_dtype)
        arng = np.random.RandomState(42)
        tenants = [f"tenant{i}" for i in range(n_tenants)]
        for t in tenants:
            pool.register(t, random_adapter(cfg, rank, arng))
    for load in loads:
        if sharded:
            # fresh replica models per level would re-clone weights; the
            # engine re-places the ONE model each time (same mesh) — cheap
            eng = ShardedServingEngine(model, dp=dp, mp=mp, **kw)
        elif speculate is not None:
            from paddle_tpu.serving import SpeculativeEngine

            eng = SpeculativeEngine(model, draft, spec_k=spec_k,
                                    lora=pool, **kw)
        else:
            eng = ServingEngine(model, lora=pool, **kw)
        # warmup: compile EVERY replica's fused step outside the timed
        # region (one request per replica — least-loaded placement seats
        # the k-th warmup on the k-th replica while the others queue)
        for _ in range(dp if sharded else 1):
            eng.submit(prompts[0], 2)
        eng.run_until_idle()
        base = eng.metrics()
        occ, qd, rocc, steps, injected = [], [], [], 0, 0.0
        t0 = time.perf_counter()
        reqs = []
        while True:
            # inject `load` requests per step (fractional loads carry over)
            injected += load
            while len(reqs) < min(int(injected), n_requests):
                ad = (tenants[len(reqs) % len(tenants)]
                      if tenants else None)
                reqs.append(eng.submit(prompts[len(reqs)], max_new,
                                       adapter=ad))
            met = eng.step()
            steps += 1
            occ.append(met["occupancy"])
            qd.append(met["queue_depth"])
            if sharded:
                rocc.append(met["replica_occupancy"])
            pending = (eng.placement.pending() if sharded
                       else eng.queue.depth + eng.scheduler.active_slots)
            drained = len(reqs) >= n_requests and not pending
            if drained or steps > 100000:
                break
        dt = time.perf_counter() - t0
        done_tokens = sum(len(r.tokens) for r in reqs)
        mets = eng.metrics()
        # ragged-launch occupancy over the measured window only (the
        # totals are cumulative; subtract the warmup's contribution)
        d_items = mets["work_items"] - base["work_items"]
        d_wcap = mets["work_capacity"] - base["work_capacity"]
        d_rows = mets["block_rows"] - base["block_rows"]
        d_rcap = mets["block_row_capacity"] - base["block_row_capacity"]
        line = {
            "metric": "serving_sweep",
            "offered_load": load,
            "lengths": lengths,
            "tokens_per_sec": round(done_tokens / dt, 1),
            "mean_occupancy": round(float(np.mean(occ)), 4),
            "mean_queue_depth": round(float(np.mean(qd)), 2),
            "grid_occupancy": round(d_items / d_wcap, 4) if d_wcap else 0.0,
            "q_row_occupancy": round(d_rows / d_rcap, 4) if d_rcap else 0.0,
            "completed": sum(r.finished for r in reqs),
            "steps": steps,
            "platform": "tpu" if on_tpu else "cpu",
            "kv_dtype": kw["cache_dtype"],
            "weight_dtype": kw.get("weight_dtype") or "native",
        }
        if sharded:
            # mesh geometry + the dp-scaling evidence: AGGREGATE tokens/s
            # (== tokens_per_sec), aggregate slot/page capacity, per-chip
            # pool bytes (~1/mp), per-replica mean occupancy and routing.
            # Per-request SLO percentiles are per-replica histograms and
            # do not merge exactly — see metrics()["per_replica"].
            line.update({
                "dp": mets["dp"], "mp": mets["mp"],
                "aggregate_tokens_per_sec": line["tokens_per_sec"],
                "slot_capacity": mets["slot_capacity"],
                "pages_capacity": mets["pages_capacity"],
                "pool_bytes_per_chip": mets["cache_bytes_per_chip"],
                "replica_occupancy": [
                    round(float(np.mean(col)), 4)
                    for col in np.asarray(rocc, float).T],
                "routed": mets["routed"],
            })
        else:
            line.update(_slo_keys(mets))
        if speculate is not None:
            # tokens/s above already counts ACCEPTED+bonus tokens only;
            # acceptance rate is the efficiency of the draft
            line.update({
                "spec_draft": speculate[0], "spec_k": spec_k,
                "accept_rate": round(mets.get("spec_acceptance_rate", 0.0),
                                     4),
                "spec_proposed": mets.get("spec_proposed_tokens", 0),
                "draft_steps": mets.get("spec_draft_steps", 0),
            })
        if pool is not None:
            line.update({
                "lora_tenants": len(tenants), "lora_rank": pool.rank,
                "adapter_slab_bytes": pool.nbytes,
            })
        print(json.dumps(line))
        sys.stdout.flush()
        eng.close()
    return 0


def _hist_snap(engines, which: str):
    """Summed cumulative (bucket_counts, count) of one SLO histogram
    across ``engines`` — per-replica children don't merge as quantiles;
    summed COUNTS do (the elastic controller's sensing arithmetic)."""
    from paddle_tpu.telemetry import metrics as _tm

    fam = _tm.registry().get(f"serving_{which}_seconds")
    total, count = [0] * (len(_tm.LATENCY_BUCKETS) + 1), 0
    for e in engines:
        counts, _s, c, _mn, _mx = fam.labels(**e._engine_label).snapshot()
        total = [a + b for a, b in zip(total, counts)]
        count += c
    return total, count


def _role_slo(engines, which: str, base=None) -> dict:
    """Per-role SLO percentiles over the measured window: the delta
    between now and the post-warmup snapshot ``base`` (compiles inside
    warmup ITL gaps would otherwise pollute the tail)."""
    from paddle_tpu.serving.elastic import _bucket_quantile
    from paddle_tpu.telemetry import metrics as _tm

    total, count = _hist_snap(engines, which)
    if base is not None:
        b_total, b_count = base
        total = [a - b for a, b in zip(total, b_total)]
        count -= b_count
    out = {f"{which}_count": int(count)}
    for q in (0.5, 0.95, 0.99):
        v = _bucket_quantile(_tm.LATENCY_BUCKETS, total, count, q)
        out[f"{which}_ms_p{int(q * 100)}"] = round(v * 1000.0, 2)
    return out


def disagg_sweep(n_prefill: int, n_decode: int, n_requests: int = 24,
                 loads=(1.0, 2.0)) -> int:
    """``--disagg P,D``: disaggregated vs colocated at the SAME total
    replica count on a long/short mixed prompt distribution — ONE JSON
    line per (engine, load):

      {"metric": "serving_disagg_sweep", "mode": "disagg"|"colocated",
       "offered_load": ..., "tokens_per_sec": ...,
       "prefill": {"ttft_ms_p99": ..., "itl_ms_p99": ...},   # per role
       "decode":  {...},                                     # (disagg)
       "itl_ms_p99": ...,                                    # cluster
       "transfers": ..., "transfer_pages": ..., ...}

    The acceptance claim (ISSUE 20): decode-role ITL p99 STRICTLY better
    than the colocated cluster's at equal replica count.  Mechanism: a
    colocated replica's fused dispatch mixes long prefill runs into the
    same step as its seated decoders, stretching every inter-token gap;
    disaggregated decode replicas run small decode-only dispatches at
    ``decode_steps_per_tick`` cadence, never behind a prompt."""
    import jax

    from paddle_tpu.serving import (
        DisaggServingEngine, ROLE_DECODE, ROLE_PREFILL,
        ShardedServingEngine,
    )

    on_tpu = jax.devices()[0].platform != "cpu"
    total = n_prefill + n_decode
    if total > len(jax.devices()):
        print(f"serving_bench: --disagg {n_prefill},{n_decode} needs "
              f"{total} devices, host has {len(jax.devices())}",
              file=sys.stderr)
        return 1
    model, cfg, kw, prompt_lens, max_new = _build(on_tpu)
    if not on_tpu:
        # the disaggregation regime needs prompts that dwarf the decode
        # program (production: thousands of prompt tokens vs a handful
        # of decode rows) — the tiny-model sweep widens the context so
        # the long prompts are ~10x the decode-only geometry
        kw = dict(kw, max_context=128)
        max_new = 8
    rng = np.random.RandomState(0)
    # long/short mix: half the requests near the context cap (prefill
    # heavy), half short (decode dominated) — the mixed regime where
    # colocation hurts ITL most
    max_prompt = kw["max_context"] - max_new
    plens = [(max_prompt if i % 2 == 0 else max(3, max_prompt // 16))
             for i in range(n_requests)]
    prompts = [rng.randint(0, cfg.vocab_size, (n,)) for n in plens]

    def warmup(eng):
        # compiles every replica's fused step outside the timed region:
        # one long + one short prompt per replica
        for i in range(total):
            eng.submit(prompts[i % 2], 3)
        eng.run_until_idle()
        if not isinstance(eng, DisaggServingEngine):
            return
        # pre-compile every power-of-two bucket of the hand-off
        # gather/scatter (copy_pages pads to these shapes); pools are
        # idle here, so scribbling over free pages is harmless — every
        # future owner fully rewrites its pages before reading
        src = eng.replicas[eng.role_indices(ROLE_PREFILL)[0]]
        for di in eng.role_indices(ROLE_DECODE):
            dst = eng.replicas[di]
            cap = min(src.allocator.capacity, dst.allocator.capacity)
            b = 1
            while b <= min(cap, 32):
                pages = list(range(b))
                eng._page_transfer.copy_pages(src.cache, dst.cache,
                                              pages, pages)
                b *= 2

    def drive(eng, load):
        t0, injected, steps, reqs = time.perf_counter(), 0.0, 0, []
        while True:
            injected += load
            while len(reqs) < min(int(injected), n_requests):
                reqs.append(eng.submit(prompts[len(reqs)], max_new))
            eng.step()
            steps += 1
            if len(reqs) >= n_requests and not eng.placement.pending():
                break
            if steps > 100000:
                break
        dt = time.perf_counter() - t0
        toks = sum(len(r.tokens) for r in reqs)
        return reqs, steps, dt, toks

    worse = []
    for load in loads:
        results = {}
        for mode in ("colocated", "disagg"):
            # equal capability on the admitting path: BOTH clusters run
            # the TTFT-optimal whole-prompt budget (a long prompt admits
            # in ONE fused step).  Colocated replicas pay that program
            # size on EVERY decode token; disagg decode replicas run the
            # budget-1 geometry — the decoupling under measurement
            budget = kw["max_context"]
            if mode == "disagg":
                eng = DisaggServingEngine(
                    model, roles=(ROLE_PREFILL,) * n_prefill
                    + (ROLE_DECODE,) * n_decode,
                    mp=1, decode_steps_per_tick=4,
                    prefill_kw=dict(prefill_token_budget=budget), **kw)
            else:
                eng = ShardedServingEngine(model, dp=total, mp=1,
                                           prefill_token_budget=budget,
                                           **kw)
            warmup(eng)
            # measured-window bases: warmup's compile-inflated samples
            # must not pollute the sweep's tail percentiles
            pools = {"all": list(eng.replicas)}
            if mode == "disagg":
                pools["prefill"] = [eng.replicas[i]
                                    for i in eng.role_indices(ROLE_PREFILL)]
                pools["decode"] = [eng.replicas[i]
                                   for i in eng.role_indices(ROLE_DECODE)]
            bases = {(p, w): _hist_snap(engs, w)
                     for p, engs in pools.items()
                     for w in ("ttft", "itl")}
            reqs, steps, dt, toks = drive(eng, load)
            line = {
                "metric": "serving_disagg_sweep", "mode": mode,
                "offered_load": load, "replicas": total,
                "tokens_per_sec": round(toks / dt, 1),
                "completed": sum(r.finished for r in reqs),
                "steps": steps,
                "platform": "tpu" if on_tpu else "cpu",
            }
            cluster_itl = _role_slo(pools["all"], "itl",
                                    base=bases[("all", "itl")])
            line["itl_ms_p99"] = cluster_itl["itl_ms_p99"]
            if mode == "disagg":
                m = eng.metrics()
                line["prefill"] = {
                    **_role_slo(pools["prefill"], "ttft",
                                base=bases[("prefill", "ttft")]),
                    **_role_slo(pools["prefill"], "itl",
                                base=bases[("prefill", "itl")])}
                line["decode"] = {
                    **_role_slo(pools["decode"], "ttft",
                                base=bases[("decode", "ttft")]),
                    **_role_slo(pools["decode"], "itl",
                                base=bases[("decode", "itl")])}
                line.update({
                    "transfers": m["transfers_total"],
                    "transfer_pages": m["transfer_pages"],
                    "transfer_bytes": m["transfer_bytes"],
                    "transfers_failed": m["transfers_failed"],
                })
                results["disagg_itl"] = line["decode"]["itl_ms_p99"]
            else:
                results["colocated_itl"] = line["itl_ms_p99"]
            print(json.dumps(line))
            sys.stdout.flush()
            eng.close()
        if results["disagg_itl"] >= results["colocated_itl"]:
            worse.append((load, results))
    if worse:
        print(f"serving_bench: --disagg decode ITL p99 NOT better than "
              f"colocated at {worse}", file=sys.stderr)
        return 1
    print(json.dumps({"metric": "serving_disagg_verdict",
                      "decode_itl_strictly_better": True}))
    return 0


def prefix_sweep(prefix_spec: str, n_requests: int = 24,
                 families: int = 2) -> int:
    """``--prefix-dist``: shared-prefix traffic through the prefix cache
    (docs/serving.md "Prefix cache") — ONE JSON line per system-prompt
    length:

      {"metric": "serving_prefix_sweep", "prefix_len": ...,
       "prefix_hit_rate": ..., "cached_tokens_share": ...,
       "prefill_tokens_per_req": ..., "ttft_ms_p50/p95": ..., ...}

    Traffic model: ``families`` system prompts of the level's length, each
    request = family prefix + a unique bounded-Zipf tail.  TOTAL prompt
    length per request index is FIXED across levels (longest prefix +
    tail) — only the shared/unique split moves, so a falling
    ``prefill_tokens_per_req`` and TTFT are attributable to the cache,
    not to shorter prompts.  Each level runs a fresh ``prefix_cache=True``
    engine; the cache is primed per family (one request of exactly the
    shared prefix) in the untimed warmup window, so the measured window
    is the warm-cache steady state production system prompts live in.
    TTFT percentiles come from the measured requests' own timestamps
    (``t_first_token - t_submitted``) — warmup/priming excluded."""
    import jax

    from paddle_tpu.serving import ServingEngine

    on_tpu = jax.devices()[0].platform != "cpu"
    model, cfg, kw, _plens, max_new = _build(on_tpu)
    ps = kw["page_size"]
    max_prompt = kw["max_context"] - max_new
    if prefix_spec == "auto":
        # page-size multiples up to 3 pages — the whole-page granularity
        # the radix index caches at
        prefix_lens = [0, ps, 2 * ps, 3 * ps]
    else:
        prefix_lens = [int(x) for x in prefix_spec.split(",")]
    longest = max(prefix_lens)
    if longest + 1 > max_prompt:
        print(f"serving_bench: --prefix-dist {prefix_spec!r}: longest "
              f"prefix {longest} leaves no room for a tail (max prompt "
              f"{max_prompt} at max_context {kw['max_context']})",
              file=sys.stderr)
        return 1
    rng = np.random.RandomState(7)
    fam_base = [rng.randint(0, cfg.vocab_size, (longest,))
                for _ in range(families)]
    tail_cap = max(max_prompt - longest, 1)
    tails = np.minimum(rng.zipf(1.6, size=n_requests),
                       tail_cap).astype(int)
    totals = longest + tails                     # same at every level
    uniq = [rng.randint(0, cfg.vocab_size, (int(t),)) for t in totals]
    for plen in prefix_lens:
        eng = ServingEngine(model, prefix_cache=True, **kw)
        eng.submit(uniq[0][:2], 2)               # warmup: compile
        eng.run_until_idle()
        if plen:
            # prime each family's prefix into the cache (registration
            # happens at page completion during this request's decode)
            for f in range(families):
                eng.submit(np.concatenate(
                    [fam_base[f][:plen], uniq[f][:1]]), 2)
            eng.run_until_idle()
        base = eng.metrics()
        prompts = [np.concatenate([fam_base[i % families][:plen],
                                   uniq[i][:int(totals[i]) - plen]])
                   for i in range(n_requests)]
        reqs, steps = [], 0
        t0 = time.perf_counter()
        while True:
            injected = min(len(reqs) + 2, n_requests)
            while len(reqs) < injected:
                reqs.append(eng.submit(prompts[len(reqs)], max_new))
            eng.step()
            steps += 1
            pending = eng.queue.depth + eng.scheduler.active_slots
            if (len(reqs) >= n_requests and not pending) or steps > 100000:
                break
        dt = time.perf_counter() - t0
        mets = eng.metrics()
        ttft = np.asarray([r.t_first_token - r.t_submitted
                           for r in reqs if r.t_first_token is not None])
        d_prefill = mets["prefill_tokens"] - base["prefill_tokens"]
        d_hits = mets["prefix_hits"] - base["prefix_hits"]
        d_partial = (mets["prefix_partial_hits"]
                     - base["prefix_partial_hits"])
        d_miss = mets["prefix_misses"] - base["prefix_misses"]
        d_cached = (mets["prefix_cached_tokens"]
                    - base["prefix_cached_tokens"])
        looked = d_hits + d_partial + d_miss
        print(json.dumps({
            "metric": "serving_prefix_sweep",
            "prefix_len": plen,
            "families": families,
            "requests": n_requests,
            "completed": sum(r.finished for r in reqs),
            "prefix_hit_rate": round((d_hits + d_partial) / looked, 4)
            if looked else 0.0,
            "cached_tokens_share": round(
                d_cached / (d_cached + d_prefill), 4)
            if (d_cached + d_prefill) else 0.0,
            "prefill_tokens_per_req": round(d_prefill / n_requests, 2),
            "cached_tokens_per_req": round(d_cached / n_requests, 2),
            "tokens_per_sec": round(
                sum(len(r.tokens) for r in reqs) / dt, 1),
            "ttft_ms_p50": round(
                float(np.percentile(ttft, 50)) * 1000.0, 2),
            "ttft_ms_p95": round(
                float(np.percentile(ttft, 95)) * 1000.0, 2),
            "evictions": mets["prefix_evictions"],
            "shared_pages": mets["shared_pages"],
            "steps": steps,
            "platform": "tpu" if on_tpu else "cpu",
        }))
        sys.stdout.flush()
        if eng.allocator.used_pages != 0:
            print(f"serving_bench: FAIL prefix sweep leaked "
                  f"{eng.allocator.used_pages} pages at prefix_len={plen}")
            return 1
        eng.close()
    return 0


def gate() -> int:
    import paddle_tpu as pt
    from paddle_tpu import serving
    from paddle_tpu.models import GPTStackedForPretraining, gpt_tiny
    from paddle_tpu.serving import ServingEngine

    pt.seed(0)
    cfg = gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0)
    m = GPTStackedForPretraining(cfg)
    m.eval()
    rng = np.random.RandomState(1)
    lengths = [5, 18, 9, 26, 13, 7, 21, 11, 16, 6, 24, 8]
    prompts = [rng.randint(0, cfg.vocab_size, (s,)) for s in lengths]
    new_toks = [int(rng.randint(2, 7)) for _ in prompts]

    refs = []
    for p, n in zip(prompts, new_toks):
        out = m.generate(pt.to_tensor(p[None, :], dtype="int64"),
                         max_new_tokens=n, max_seq_len=64,
                         cache_dtype="float32")
        refs.append(np.asarray(out.numpy())[0])

    serving.reset_serve_trace_counts()
    # 3 slots but only 5 allocatable pages (2 pages per long request):
    # the gate exercises pool backpressure, not just slot contention
    eng = ServingEngine(m, num_slots=3, page_size=16, max_context=64,
                        num_pages=6, cache_dtype="float32")
    reqs, it, submitted = [], iter(zip(prompts, new_toks)), 0
    peak = 0
    saw_backpressure = False
    steps = 0
    while submitted < len(prompts) or eng.queue.depth \
            or eng.scheduler.active_slots:
        for _ in range(2):
            try:
                p, n = next(it)
            except StopIteration:
                break
            reqs.append(eng.submit(p, n))
            submitted += 1
        met = eng.step()
        steps += 1
        peak = max(peak, met["pages_used"])
        if met["pages_used"] > eng.allocator.capacity:
            print(f"serving_gate: FAIL pool over capacity "
                  f"({met['pages_used']} > {eng.allocator.capacity})")
            return 1
        if met["queue_depth"] > 0 and met["active_slots"] > 0:
            saw_backpressure = True
        if steps > 500:
            print("serving_gate: FAIL engine made no progress")
            return 1

    tc = serving.serve_trace_counts()
    if tc["fused"] > 2:
        print(f"serving_gate: FAIL retraced under churn: {tc}")
        return 1
    bad = 0
    for r, ref in zip(reqs, refs):
        if not (r.finished and np.array_equal(r.output_ids(), ref)):
            bad += 1
    if bad:
        print(f"serving_gate: FAIL {bad}/{len(reqs)} requests diverged "
              "from single-shot generate()")
        return 1
    if eng.allocator.used_pages != 0:
        print(f"serving_gate: FAIL {eng.allocator.used_pages} pages leaked")
        return 1
    if not saw_backpressure:
        print("serving_gate: FAIL pool never backpressured (gate sizing "
              "is supposed to force it)")
        return 1
    print(f"serving_gate: OK ({len(reqs)} requests, {steps} steps, "
          f"traces={tc}, peak_pages={peak}/{eng.allocator.capacity})")
    eng.close()
    rc = _gate_speculative(pt, serving, m, prompts, new_toks, refs)
    if rc:
        return rc
    rc = _gate_sharded(pt, serving, m, prompts, new_toks, refs)
    if rc:
        return rc
    return _gate_quantized(pt, serving, cfg, m, prompts, new_toks, refs)


def _gate_speculative(pt, serving, model, prompts, new_toks, refs) -> int:
    """The speculative half of the serving gate (ISSUE-15): (a) greedy
    speculative output token-for-token equal to the non-speculative
    engine and to generate(), (b) a same-model draft accepts EVERYTHING
    (rate 1.0), (c) page accounting — target AND draft pools, incl. the
    speculative-reservation ledger — drains to zero under randomized
    fault schedules with speculation on, (d) fused trace counts stay
    bounded: <= 2 target + <= 2 draft programs."""
    import numpy as _np

    from paddle_tpu.serving import SpeculativeEngine
    from paddle_tpu.faults import random_schedule

    serving.reset_serve_trace_counts()
    eng = SpeculativeEngine(model, model, spec_k=3, num_slots=3,
                            page_size=16, max_context=64,
                            cache_dtype="float32")
    try:
        reqs = [eng.submit(p, n) for p, n in zip(prompts, new_toks)]
        eng.run_until_idle(max_steps=2000)
        bad = sum(1 for r, ref in zip(reqs, refs)
                  if not (r.finished and _np.array_equal(r.output_ids(),
                                                         ref)))
        if bad:
            print(f"serving_gate: FAIL speculative: {bad}/{len(reqs)} "
                  "requests diverged from generate()/the non-speculative "
                  "engine")
            return 1
        mets = eng.metrics()
        if mets["spec_acceptance_rate"] != 1.0:
            print("serving_gate: FAIL same-model draft acceptance "
                  f"{mets['spec_acceptance_rate']} != 1.0")
            return 1
        tc = serving.serve_trace_counts()
        if tc["fused"] > 2 or tc["draft"] > 2:
            print(f"serving_gate: FAIL speculative step retraced: {tc}")
            return 1
    finally:
        eng.close()
    # (c): randomized fault schedules with speculation on
    for seed in (0, 1, 2):
        srng = _np.random.RandomState(seed)
        eng = SpeculativeEngine(model, model, spec_k=3, num_slots=3,
                                page_size=16, max_context=64,
                                cache_dtype="float32")
        try:
            random_schedule(srng, horizon=25, n_faults=4,
                            num_slots=3).install(eng)
            sreqs = [eng.submit(p, n) for p, n in zip(prompts, new_toks)]
            eng.run_until_idle(max_steps=3000)
            if not all(r.terminal for r in sreqs):
                print(f"serving_gate: FAIL spec-faults seed {seed}: "
                      "non-terminal request after drain")
                return 1
            for alloc, tag in ((eng.allocator, "target"),
                               (eng.draft.allocator, "draft")):
                if alloc.used_pages or alloc.spec_pages \
                        or alloc.free_pages != alloc.capacity:
                    print(f"serving_gate: FAIL spec-faults seed {seed}: "
                          f"{tag} pool did not drain (used="
                          f"{alloc.used_pages} spec={alloc.spec_pages})")
                    return 1
        finally:
            eng.close()
    print(f"serving_gate: speculative OK (accept_rate=1.0, traces={tc}, "
          "3 randomized fault schedules drained exactly)")
    return 0


def _gate_sharded(pt, serving, model, prompts, new_toks, refs) -> int:
    """The sharded half of the serving gate (4+ devices, e.g. the
    run_tests.sh forced-8-device CPU mesh): a (dp=2, mp=2)
    ShardedServingEngine must reproduce single-shot ``generate()``
    token-for-token through the placement layer, stay retrace-free per
    replica, and close page accounting on EVERY replica."""
    import jax

    from paddle_tpu.serving import ShardedServingEngine

    if len(jax.devices()) < 4:
        print("serving_gate: sharded scenario skipped "
              f"({len(jax.devices())} devices < 4)")
        return 0
    serving.reset_serve_trace_counts()
    eng = ShardedServingEngine(model, dp=2, mp=2, num_slots=2, page_size=16,
                               max_context=64, num_pages=5,
                               cache_dtype="float32")
    try:
        reqs = [eng.submit(p, n) for p, n in zip(prompts, new_toks)]
        eng.run_until_idle(max_steps=2000)
        tc = serving.serve_trace_counts()
        if tc["fused"] > 2 * eng.dp:
            print(f"serving_gate: FAIL sharded step retraced: {tc} "
                  f"(> 2 per replica x dp={eng.dp})")
            return 1
        bad = sum(1 for r, ref in zip(reqs, refs)
                  if not (r.finished
                          and np.array_equal(r.output_ids(), ref)))
        if bad:
            print(f"serving_gate: FAIL sharded: {bad}/{len(reqs)} requests "
                  "diverged from single-shot generate()")
            return 1
        for i, rep in enumerate(eng.replicas):
            if rep.allocator.used_pages != 0:
                print(f"serving_gate: FAIL sharded replica {i} leaked "
                      f"{rep.allocator.used_pages} pages")
                return 1
        mets = eng.metrics()
        print(f"serving_gate: sharded OK (dp=2 mp=2, {len(reqs)} requests, "
              f"traces={tc}, routed={mets['routed']}, "
              f"pool_per_chip={mets['cache_bytes_per_chip']}B)")
        return 0
    finally:
        eng.close()


def _gate_quantized(pt, serving, cfg, model, prompts, new_toks, refs) -> int:
    """The quantized half of the serving gate (ISSUE-17):

    (a) logit-error budget — teacher-forced logits through a SHUFFLED
        int8 pool stay within a fixed max-|error| of the fp32 oracle
        with full top-1 agreement, and a bf16-KV engine reproduces its
        own-dtype single-shot ``generate()`` token-for-token;
    (b) capacity — the cost model sizes an int8 pool to the SAME byte
        budget as the fp32 gate pool; it must seat >= 1.8x the requests
        (it actually gets ~4x: 1-byte pages + fp32 scale sidecars), and
        an engine over that pool must then really serve the workload;
    (c) int8-KV and int8-KV+int8-weight engines finish the gate workload
        retrace-free with exact page accounting, finite scale sidecars
        after drain, and (weights) top-1 token agreement vs fp32 refs;
    (d) prefix-cache COW stays BITWISE under int8 (cache-on == cache-off
        — quantize-on-write is commutative, so shared pages never drift);
    (e) a speculative int8 engine keeps same-model acceptance 1.0 and
        drains target AND draft pools;
    (f) a (dp=2, mp=2) sharded int8 engine (4+ devices) reproduces the
        refs with per-replica drain — scale sidecars shard over mp."""
    import math

    from paddle_tpu.analysis.cost_model import paged_pool_bytes
    from paddle_tpu.models import GPTStackedForPretraining
    from paddle_tpu.serving import ServingEngine, SpeculativeEngine

    H, D, L, ps = cfg.num_heads, cfg.head_dim, cfg.num_layers, 16

    # --- (a) logit-error budget vs the fp32 oracle ----------------------
    rng = np.random.RandomState(7)
    ids = pt.to_tensor(rng.randint(0, cfg.vocab_size, (1, 32)),
                       dtype="int64")
    pos = pt.to_tensor(np.array([0], np.int32))
    tbl = pt.to_tensor(np.array([[5, 1]], np.int32))  # shuffled pool walk
    oracle = model._paged_lm_logits(
        ids, model.new_paged_kv_cache(8, ps, dtype="float32"), tbl,
        pos).numpy().astype(np.float32)
    q8 = model._paged_lm_logits(
        ids, model.new_paged_kv_cache(8, ps, dtype="int8"), tbl,
        pos).numpy().astype(np.float32)
    max_err = float(np.abs(q8 - oracle).max())
    top1 = float((q8.argmax(-1) == oracle.argmax(-1)).mean())
    if max_err > 0.25 or top1 < 1.0:
        print(f"serving_gate: FAIL int8 logit budget: max|err|={max_err:.4f}"
              f" (budget 0.25), top1_agreement={top1:.4f} (need 1.0)")
        return 1

    # bf16 KV: greedy parity against the SAME-dtype single-shot oracle
    bf_refs = []
    for p, n in zip(prompts, new_toks):
        out = model.generate(pt.to_tensor(p[None, :], dtype="int64"),
                             max_new_tokens=n, max_seq_len=64,
                             cache_dtype="bfloat16")
        bf_refs.append(np.asarray(out.numpy())[0])
    serving.reset_serve_trace_counts()
    eng = ServingEngine(model, num_slots=3, page_size=ps, max_context=64,
                        kv_dtype="bfloat16")
    try:
        reqs = [eng.submit(p, n) for p, n in zip(prompts, new_toks)]
        eng.run_until_idle(max_steps=2000)
        bad = sum(1 for r, ref in zip(reqs, bf_refs)
                  if not (r.finished and np.array_equal(r.output_ids(),
                                                        ref)))
        if bad:
            print(f"serving_gate: FAIL bf16-KV: {bad}/{len(reqs)} requests "
                  "diverged from bf16 generate()")
            return 1
    finally:
        eng.close()

    # --- (b) capacity: >= 1.8x seats at an identical pool byte budget ---
    budget = paged_pool_bytes(6, H, ps, D, num_layers=L, dtype="float32")
    n_int8 = 6
    while paged_pool_bytes(n_int8 + 1, H, ps, D, num_layers=L,
                           dtype="int8") <= budget:
        n_int8 += 1
    per_seat = 64 // ps                      # worst-case pages per request
    seats_fp32, seats_int8 = 6 // per_seat, n_int8 // per_seat
    if seats_int8 < math.ceil(1.8 * seats_fp32):
        print(f"serving_gate: FAIL int8 capacity: {seats_int8} seats vs "
              f"{seats_fp32} fp32 seats at {budget}B (need >= 1.8x)")
        return 1

    # --- (c) int8-KV engine over that cost-model-sized pool -------------
    serving.reset_serve_trace_counts()
    eng = ServingEngine(model, num_slots=max(seats_int8, 1), page_size=ps,
                        max_context=64, num_pages=n_int8, kv_dtype="int8")
    try:
        reqs = [eng.submit(p, n) for p, n in zip(prompts, new_toks)]
        eng.run_until_idle(max_steps=2000)
        tc = serving.serve_trace_counts()
        bad = sum(1 for r, ref in zip(reqs, refs)
                  if not (r.finished and np.array_equal(r.output_ids(),
                                                        ref)))
        if bad:
            print(f"serving_gate: FAIL int8-KV: {bad}/{len(reqs)} requests "
                  "diverged from generate()")
            return 1
        if tc["fused"] > 2:
            print(f"serving_gate: FAIL int8-KV step retraced: {tc}")
            return 1
        if eng.allocator.used_pages != 0:
            print(f"serving_gate: FAIL int8-KV leaked "
                  f"{eng.allocator.used_pages} pages")
            return 1
        scales = [eng.cache.k_scale, eng.cache.v_scale]
        if not all(np.isfinite(np.asarray(s.numpy())).all()
                   for s in scales):
            print("serving_gate: FAIL int8-KV scale sidecars non-finite "
                  "after drain")
            return 1
    finally:
        eng.close()

    # int8 KV + int8 weights: quantize_for_serving mutates the model in
    # place, so the weight scenario runs on its OWN copy
    m8 = GPTStackedForPretraining(cfg)
    m8.set_state_dict(model.state_dict())
    m8.eval()
    serving.reset_serve_trace_counts()
    eng = ServingEngine(m8, num_slots=3, page_size=ps, max_context=64,
                        kv_dtype="int8", weight_dtype="int8")
    try:
        # engine-correctness oracle: a SERIAL (1-slot) engine in the
        # identical int8-KV + int8-weight regime.  Per-row activation
        # scales make the quantized matmuls batch-invariant, so the
        # 3-slot batched engine must reproduce it BITWISE — any drift
        # here is an engine bug, not quantization error (which is
        # bounded separately below, vs the fp32 refs)
        ser = ServingEngine(m8, num_slots=1, page_size=ps, max_context=64,
                            kv_dtype="int8")
        try:
            s_reqs = [ser.submit(p, n) for p, n in zip(prompts, new_toks)]
            ser.run_until_idle(max_steps=2000)
            q_refs = [np.asarray(r.output_ids()) for r in s_reqs]
        finally:
            ser.close()
        serving.reset_serve_trace_counts()
        reqs = [eng.submit(p, n) for p, n in zip(prompts, new_toks)]
        eng.run_until_idle(max_steps=2000)
        tc = serving.serve_trace_counts()
        bad = sum(1 for r, ref in zip(reqs, q_refs)
                  if not (r.finished and np.array_equal(r.output_ids(),
                                                        ref)))
        if bad:
            print(f"serving_gate: FAIL int8-weight: {bad}/{len(reqs)} "
                  "batched requests diverged from the serial 1-slot "
                  "engine (batch-invariance broken)")
            return 1
        if tc["fused"] > 2:
            print(f"serving_gate: FAIL int8-weight step retraced: {tc}")
            return 1
        if eng.allocator.used_pages != 0:
            print(f"serving_gate: FAIL int8-weight leaked "
                  f"{eng.allocator.used_pages} pages")
            return 1
        # quantization-quality sanity vs the fp32 refs: a random-init
        # gpt_tiny flips more tokens than a trained model would (~85%
        # agreement here); gate well below that but far above chance
        agree = total = 0
        for r, ref, p in zip(reqs, refs, prompts):
            got = np.asarray(r.output_ids())[len(p):]
            want = ref[len(p):]
            agree += int((got == want).sum())
            total += len(want)
        if agree < 0.7 * total:
            print(f"serving_gate: FAIL int8-weight token agreement "
                  f"{agree}/{total} < 70% of fp32")
            return 1
    finally:
        eng.close()

    # --- (d) prefix-cache COW stays bitwise under int8 ------------------
    srng = np.random.RandomState(11)
    shared = srng.randint(0, cfg.vocab_size, (2 * ps,))
    fam = [np.concatenate([shared,
                           srng.randint(0, cfg.vocab_size, (5 + 3 * i,))])
           for i in range(4)]
    outs = {}
    for cached in (False, True):
        eng = ServingEngine(model, num_slots=3, page_size=ps,
                            max_context=64, kv_dtype="int8",
                            prefix_cache=cached)
        try:
            # first request alone, so its prefix is cached before the rest
            first = eng.submit(fam[0], 4)
            eng.run_until_idle(max_steps=2000)
            rest = [eng.submit(p, 4) for p in fam[1:]]
            eng.run_until_idle(max_steps=2000)
            outs[cached] = [np.asarray(r.output_ids())
                            for r in [first] + rest]
            if cached and eng.metrics()["prefix_hits"] < 1:
                print("serving_gate: FAIL int8 prefix cache never hit")
                return 1
            if eng.allocator.used_pages != 0:
                print("serving_gate: FAIL int8 prefix scenario leaked "
                      f"{eng.allocator.used_pages} pages")
                return 1
        finally:
            eng.close()
    if not all(np.array_equal(a, b)
               for a, b in zip(outs[False], outs[True])):
        print("serving_gate: FAIL int8 COW drift: prefix-cache-on outputs "
              "!= cache-off (quantize-on-write must be commutative)")
        return 1

    # --- (e) speculative serving over an int8 pool ----------------------
    serving.reset_serve_trace_counts()
    eng = SpeculativeEngine(model, model, spec_k=3, num_slots=3,
                            page_size=ps, max_context=64, kv_dtype="int8")
    try:
        reqs = [eng.submit(p, n) for p, n in zip(prompts, new_toks)]
        eng.run_until_idle(max_steps=2000)
        bad = sum(1 for r, ref in zip(reqs, refs)
                  if not (r.finished and np.array_equal(r.output_ids(),
                                                        ref)))
        mets = eng.metrics()
        if bad or mets["spec_acceptance_rate"] != 1.0:
            print(f"serving_gate: FAIL speculative int8: {bad} divergent, "
                  f"accept_rate={mets['spec_acceptance_rate']}")
            return 1
        for alloc, tag in ((eng.allocator, "target"),
                           (eng.draft.allocator, "draft")):
            if alloc.used_pages or alloc.spec_pages:
                print(f"serving_gate: FAIL speculative int8 {tag} pool "
                      f"did not drain (used={alloc.used_pages} "
                      f"spec={alloc.spec_pages})")
                return 1
    finally:
        eng.close()

    # --- (f) sharded int8 (4+ devices): scale sidecars shard over mp ----
    import jax

    if len(jax.devices()) >= 4:
        from paddle_tpu.serving import ShardedServingEngine

        serving.reset_serve_trace_counts()
        eng = ShardedServingEngine(model, dp=2, mp=2, num_slots=2,
                                   page_size=ps, max_context=64,
                                   num_pages=8, kv_dtype="int8")
        try:
            reqs = [eng.submit(p, n) for p, n in zip(prompts, new_toks)]
            eng.run_until_idle(max_steps=2000)
            bad = sum(1 for r, ref in zip(reqs, refs)
                      if not (r.finished
                              and np.array_equal(r.output_ids(), ref)))
            if bad:
                print(f"serving_gate: FAIL sharded int8: {bad}/{len(reqs)} "
                      "requests diverged")
                return 1
            for i, rep in enumerate(eng.replicas):
                if rep.allocator.used_pages != 0:
                    print(f"serving_gate: FAIL sharded int8 replica {i} "
                          f"leaked {rep.allocator.used_pages} pages")
                    return 1
        finally:
            eng.close()
        shard_note = "sharded dp=2 mp=2 OK"
    else:
        shard_note = "sharded skipped (<4 devices)"

    print(f"serving_gate: quantized OK (logit max|err|={max_err:.4f}, "
          f"top1=1.0, seats {seats_int8}x-int8 vs {seats_fp32}x-fp32 at "
          f"{budget}B, COW bitwise, spec accept=1.0, {shard_note})")
    return 0


def chaos(n_requests: int = 36, lengths: str = "fixed") -> int:
    """Three offered-load phases through ONE engine — healthy, fault
    storm, recovered — asserting throughput degrades gracefully under the
    storm and RECOVERS after it, with exact page accounting throughout.
    ``--lengths zipf`` draws each phase's prompt lengths from the bounded
    Zipf long-tail, the regime where the SLO histograms must stay
    populated THROUGH the storm (ISSUE-9 acceptance)."""
    import time as _time

    import jax

    from paddle_tpu.serving import FaultInjector, RequestState, ServingEngine

    on_tpu = jax.devices()[0].platform != "cpu"
    model, cfg, kw, prompt_lens, max_new = _build(on_tpu)
    kw = dict(kw, stall_budget_s=2.0 if not on_tpu else 10.0)
    rng = np.random.RandomState(0)
    per_phase = max(n_requests // 3, 8)
    max_prompt = kw["max_context"] - max_new
    eng = ServingEngine(model, **kw)
    eng.submit(rng.randint(0, cfg.vocab_size, (prompt_lens[0],)), 2)
    eng.run_until_idle()                         # warmup compiles

    def run_phase(label):
        plens = _prompt_lengths(lengths, per_phase, prompt_lens,
                                max_prompt, rng)
        prompts = [rng.randint(0, cfg.vocab_size, (plens[i],))
                   for i in range(per_phase)]
        reqs, it, steps = [], iter(prompts), 0
        t0 = _time.perf_counter()
        while len(reqs) < per_phase or eng.queue.depth \
                or eng.scheduler.active_slots:
            for _ in range(2):
                try:
                    reqs.append(eng.submit(next(it), max_new))
                except StopIteration:
                    break
            met = eng.step()
            steps += 1
            if met["pages_used"] > eng.allocator.capacity:
                raise AssertionError("pool over capacity")
            if steps > 100000:
                raise AssertionError("no progress")
            if not met["active_slots"] and not met["tokens_this_step"]:
                _time.sleep(0.001)               # post-recovery backoff
        dt = _time.perf_counter() - t0
        toks = sum(len(r.tokens) for r in reqs)
        mets = eng.metrics()
        rate = toks / dt if dt > 0 else 0.0
        print(json.dumps({
            "metric": "serving_chaos", "window": label, "lengths": lengths,
            "tokens_per_sec": round(rate, 1), "seconds": round(dt, 3),
            "completed": sum(r.state == RequestState.DONE for r in reqs),
            "requests": len(reqs),
            "recoveries": mets["recoveries"], "failed": mets["failed"],
            "quarantined": mets["quarantined"],
            "platform": "tpu" if on_tpu else "cpu",
            # SLO percentiles are CUMULATIVE across the three windows
            # (one engine, one histogram set) — the storm's tail shows
            # up as the before->during p99 jump
            **_slo_keys(mets),
        }))
        sys.stdout.flush()
        if not all(r.terminal for r in reqs):
            raise AssertionError("non-terminal request after drain")
        if eng.allocator.used_pages != 0:
            raise AssertionError(
                f"{eng.allocator.used_pages} pages leaked")
        return rate

    try:
        healthy = run_phase("before")
        # the storm: crashes (transient + persistent), a NaN slot, an
        # exhaustion window, one stall that trips the watchdog + rebuild
        inj = FaultInjector()
        inj.inject("before_decode", at=2, kind="step_exception")
        inj.inject("before_decode", at=6, kind="step_exception", times=2)
        inj.inject("after_decode", at=10, kind="nan_logits", slots=[0])
        inj.inject("alloc", at=2, times=4, kind="alloc_exhausted")
        inj.inject("before_decode", at=14, kind="step_stall",
                   duration=kw["stall_budget_s"] * 2)
        inj.install(eng)
        stormy = run_phase("during")
        # storm over: occurrence-keyed plans are all exhausted; detach
        eng._fault_hook = None
        eng.allocator._fault_hook = None
        # the stall-triggered rebuild recompiled the step programs; pay
        # that compile in a warmup drain (as at engine start) so "after"
        # measures the recovered STEADY STATE, not one compile
        eng.submit(rng.randint(0, cfg.vocab_size, (prompt_lens[0],)), 2)
        eng.run_until_idle()
        recovered = run_phase("after")
    except AssertionError as e:
        print(f"serving_chaos: FAIL {e}")
        return 1
    mets = eng.metrics()
    if mets["recoveries"] < 1 or mets["rebuilds"] < 1:
        print("serving_chaos: FAIL the storm never forced a "
              f"recovery/rebuild ({mets['recoveries']}/{mets['rebuilds']})")
        return 1
    if recovered < 0.5 * healthy:
        print(f"serving_chaos: FAIL no recovery: after={recovered:.1f} "
              f"vs before={healthy:.1f} tok/s")
        return 1
    print(f"serving_chaos: OK (failed={mets['failed']} "
          f"recoveries={mets['recoveries']} rebuilds={mets['rebuilds']}; "
          f"before/during/after = {healthy:.1f}/{stormy:.1f}/"
          f"{recovered:.1f} tok/s)")
    eng.close()
    return 0


def trace(ttft_budget_s: float = 5.0) -> int:
    """Elasticity A/B under ONE chaos traffic trace (--trace): a diurnal
    arrival ramp with a 4x load spike (faults.py ``load_spike``) and a
    mid-run replica kill (``replica_kill``), replayed arrival-for-arrival
    through two dp=2 clusters —

      - ``elastic``: starts scaled down to one replica with an
        ElasticServingController closing the loop (queue-driven policy,
        tick clock);
      - ``static``: both replicas active the whole run, no controller
        (the provisioned-for-peak baseline).

    Prints one ``{"metric": "serving_trace", "mode": ...}`` line per run
    and asserts the elasticity win the ISSUE-19 acceptance names: the
    elastic run holds p99 TTFT within ``ttft_budget_s`` while spending
    STRICTLY fewer replica-step chip-seconds than static max-dp, every
    admitted request reaches a typed terminal state, and every completed
    output (re-homed ones included) is bitwise a prefix of the
    single-shot greedy oracle."""
    import jax

    import paddle_tpu as pt
    from paddle_tpu.serving import (
        ElasticConfig, ElasticServingController, FaultInjector, Overloaded,
        ShardedServingEngine, SLOTargets,
    )

    on_tpu = jax.devices()[0].platform != "cpu"
    if len(jax.devices()) < 2:
        print("serving_trace: <2 devices, dp=2 A/B skipped")
        return 0
    # the scripted trace: per-tick base arrivals (diurnal ramp), a 4x
    # spike over ticks 12-15, a replica kill at cluster-step 28
    base = [1] * 8 + [2] * 16 + [1] * 16 + [0] * 24
    ref_model, cfg, kw, prompt_lens, max_new = _build(on_tpu)
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)) for n in prompt_lens]
    refs = [np.asarray(
        ref_model.generate(pt.to_tensor(p[None, :], dtype="int64"),
                           max_new_tokens=max_new,
                           max_seq_len=kw["max_context"],
                           cache_dtype=kw["cache_dtype"]).numpy())[0]
        for p in prompts]

    def run(mode: str) -> dict:
        model = _build(on_tpu)[0]
        cluster = ShardedServingEngine(model, dp=2, mp=1, **kw)
        warm = [cluster.submit(p, 2) for p in prompts[:2]]
        cluster.run_until_idle(max_steps=200)      # compile both replicas
        assert all(r.terminal for r in warm)
        clk_t = [0.0]
        ctl = None
        if mode == "elastic":
            cluster.drain_replica(1, deadline_s=0.0)   # start scaled down
            ctl = ElasticServingController(
                cluster,
                ElasticConfig(targets=SLOTargets(queue_high=3.0,
                                                 queue_low=0.5),
                              min_samples=10**9, cooldown_s=3.0,
                              overload_sustain_s=1e9,
                              underload_sustain_s=2.0,
                              drain_deadline_s=0.0, min_dp=1),
                clock=lambda: clk_t[0])
        inj = FaultInjector()
        inj.inject("traffic", at=12, times=4, kind="load_spike",
                   duration=4.0)
        inj.inject("cluster_step", at=28, kind="replica_kill", slots=[1])
        inj.install(cluster)
        reqs, shed, k = [], 0, 0

        def tick_once():
            if ctl is not None:
                ctl.tick()
            cluster.step()
            clk_t[0] += 1.0

        for t, b in enumerate(base):
            ctx = {"multiplier": 1.0}
            inj.hook("traffic", ctx)
            for _ in range(int(round(b * ctx["multiplier"]))):
                try:
                    r = cluster.submit(prompts[k % len(prompts)], max_new)
                    reqs.append((r, k % len(prompts)))
                    k += 1
                except Overloaded:
                    shed += 1
            tick_once()
        # drain the tail (controller keeps scaling down as it empties)
        for _ in range(600):
            if (all(r.terminal for r, _ in reqs)
                    and cluster.placement.pending() == 0):
                break
            tick_once()
        mets = cluster.metrics()
        ttfts = [r.t_first_token - r.t_submitted for r, _ in reqs
                 if r.t_first_token is not None and r.t_submitted is not None]
        rec = {
            "metric": "serving_trace", "mode": mode,
            "ticks": len(base), "requests": len(reqs), "shed": shed,
            "done": sum(r.state == "DONE" for r, _ in reqs),
            "rehomed": mets["rehomed"],
            "replica_steps": mets["replica_steps"],
            "chip_ticks": mets["replica_step_chip_ticks"],
            "replica_states": mets["replica_states"],
            "ttft_ms_p99": round(float(np.percentile(ttfts, 99)) * 1000.0,
                                 2) if ttfts else 0.0,
            "scale_actions": len(ctl.actions) if ctl else 0,
        }
        print(json.dumps(rec))
        sys.stdout.flush()
        for r, i in reqs:
            if not r.terminal:
                raise AssertionError(f"{mode}: request {r.id} non-terminal")
            out = np.asarray(r.output_ids())
            if not np.array_equal(out, refs[i][:out.size]):
                raise AssertionError(
                    f"{mode}: request {r.id} diverged from the oracle")
        if ctl is not None:
            ctl.close()
        cluster.close()
        return rec

    try:
        el = run("elastic")
        st = run("static")
    except AssertionError as e:
        print(f"serving_trace: FAIL {e}")
        return 1
    budget_ms = ttft_budget_s * 1000.0
    if el["ttft_ms_p99"] > budget_ms:
        print(f"serving_trace: FAIL elastic p99 TTFT {el['ttft_ms_p99']}ms "
              f"over the {budget_ms:.0f}ms budget")
        return 1
    if el["replica_steps"] >= st["replica_steps"]:
        print(f"serving_trace: FAIL no chip-seconds win: elastic "
              f"{el['replica_steps']} vs static {st['replica_steps']} "
              "replica-steps")
        return 1
    if el["rehomed"] < 1:
        print("serving_trace: FAIL the kill/drain re-homed nothing")
        return 1
    print(f"serving_trace: OK (elastic p99 TTFT {el['ttft_ms_p99']}ms <= "
          f"{budget_ms:.0f}ms, {el['replica_steps']} vs "
          f"{st['replica_steps']} static replica-steps, "
          f"{el['rehomed']} re-homed bitwise)")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--gate", action="store_true",
                    help="fast CI correctness gate (run_tests.sh)")
    ap.add_argument("--chaos", action="store_true",
                    help="fault storm under offered load: assert graceful "
                         "degradation + recovery")
    ap.add_argument("--trace", action="store_true",
                    help="elasticity A/B on one chaos traffic trace "
                         "(diurnal ramp + 4x spike + replica kill): the "
                         "elastic run must hold p99 TTFT within "
                         "--ttft-budget at STRICTLY fewer replica-step "
                         "chip-seconds than static max-dp, bitwise")
    ap.add_argument("--ttft-budget", type=float, default=5.0,
                    help="--trace p99 TTFT budget in seconds")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--loads", type=str, default="0.5,1,2,4",
                    help="comma-separated offered loads (requests/step)")
    ap.add_argument("--lengths", choices=("fixed", "zipf"), default="fixed",
                    help="prompt-length distribution: the historical fixed "
                         "cycle, or a bounded Zipf long-tail (the skewed "
                         "regime the ragged fused step targets)")
    ap.add_argument("--prefix-dist", type=str, default=None,
                    metavar="L0,L1,...",
                    help="shared-prefix sweep through the prefix cache: "
                         "one line per system-prompt length (comma list "
                         "of token counts, or 'auto' for page-size "
                         "multiples 0..3), requests = family prefix + "
                         "bounded-Zipf unique tail with total length "
                         "fixed across levels. Lines report "
                         "prefix_hit_rate, cached_tokens_share, "
                         "prefill_tokens_per_req, and TTFT percentiles "
                         "— all must fall as the cached share rises")
    ap.add_argument("--speculate", type=str, default=None,
                    metavar="DRAFT,K",
                    help="sweep with speculative decoding: DRAFT is "
                         "'same' (the target itself, acceptance 1.0) or "
                         "'<n>layer' (weight-sharing truncated prefix, "
                         "e.g. 1layer); K proposals per slot per tick. "
                         "Lines gain spec_k/accept_rate/draft_steps")
    ap.add_argument("--lora", type=str, default=None,
                    metavar="N_TENANTS,RANK",
                    help="sweep with a multi-tenant LoRA pool: N random "
                         "adapters registered, requests round-robin over "
                         "them. Lines gain lora_tenants/lora_rank/"
                         "adapter_slab_bytes")
    ap.add_argument("--kv-dtype", choices=("fp32", "bf16", "int8"),
                    default=None,
                    help="paged KV pool dtype for the sweep: fp32/bf16 "
                         "store pages as-is; int8 quantizes pages on "
                         "write with per-(page, head) absmax scales and "
                         "dequantizes inside the attention kernels — "
                         "4x (vs fp32) the seats at the same pool bytes. "
                         "Sweep lines carry kv_dtype= for capacity/"
                         "latency comparison across regimes")
    ap.add_argument("--weight-dtype", choices=("int8",), default=None,
                    help="PTQ the decode-path weights to int8 before "
                         "serving (quantize_for_serving): int8 matmuls "
                         "with per-out-channel scales on the hot path")
    ap.add_argument("--disagg", type=str, default=None, metavar="P,D",
                    help="disaggregated sweep: P prefill + D decode "
                         "replicas vs a colocated cluster of P+D on a "
                         "long/short mixed workload. Emits per-role "
                         "TTFT/ITL percentiles + transfer traffic and "
                         "FAILS unless decode-role ITL p99 beats the "
                         "colocated cluster's (ISSUE-20 acceptance)")
    ap.add_argument("--mesh", type=str, default="1,1", metavar="DP,MP",
                    help="serving mesh geometry dp,mp (sweep mode): dp "
                         "replica engines x mp tensor-parallel chips "
                         "behind one placement scheduler; sweep lines "
                         "gain dp/mp/aggregate tokens/s, per-replica "
                         "occupancy and per-chip pool bytes")
    args = ap.parse_args()
    import jax

    if jax.devices()[0].platform == "tpu":
        # on the chip only: the CPU gates must not fill the directory the
        # chip tool copies with XLA:CPU executables
        from paddle_tpu.sysconfig import enable_compile_cache

        enable_compile_cache()
    if args.gate:
        return gate()
    if args.chaos:
        return chaos(max(args.requests, 36) if args.requests != 24
                     else 36, lengths=args.lengths)
    if args.trace:
        return trace(ttft_budget_s=args.ttft_budget)
    if args.prefix_dist:
        return prefix_sweep(args.prefix_dist, args.requests)
    if args.disagg:
        try:
            p, d = (int(x) for x in args.disagg.split(","))
            assert p >= 1 and d >= 1
        except Exception:
            ap.error(f"--disagg {args.disagg!r}: expected P,D "
                     f"(two ints >= 1)")
        return disagg_sweep(p, d, args.requests,
                            tuple(float(x)
                                  for x in args.loads.split(",")))
    try:
        mesh = tuple(int(x) for x in args.mesh.split(","))
        assert len(mesh) == 2 and mesh[0] >= 1 and mesh[1] >= 1
    except Exception:
        ap.error(f"--mesh {args.mesh!r}: expected DP,MP (two ints >= 1)")
    speculate = lora = None
    if args.speculate:
        parts = args.speculate.split(",")
        if len(parts) != 2:
            ap.error(f"--speculate {args.speculate!r}: expected DRAFT,K")
        speculate = (parts[0], int(parts[1]))
    if args.lora:
        parts = args.lora.split(",")
        if len(parts) != 2:
            ap.error(f"--lora {args.lora!r}: expected N_TENANTS,RANK")
        lora = (int(parts[0]), int(parts[1]))
    if (speculate or lora) and mesh != (1, 1):
        ap.error("--speculate/--lora compose with --mesh at the replica "
                 "level via ShardedServingEngine(engine_factory=...); the "
                 "bench sweeps them single-replica")
    dt_map = {"fp32": "float32", "bf16": "bfloat16", "int8": "int8"}
    return sweep(tuple(float(x) for x in args.loads.split(",")),
                 args.requests, lengths=args.lengths, mesh=mesh,
                 speculate=speculate, lora=lora,
                 kv_dtype=dt_map.get(args.kv_dtype),
                 weight_dtype=args.weight_dtype)


if __name__ == "__main__":
    sys.exit(main())
