#!/usr/bin/env python
"""Chip smoke: gpt_1p3b trains and serves on the TPU, end to end, through
the entry points a user calls.

One process, one run, full width and depth of one model the repo supports
(2048 wide, 24 layers, 16 heads of 128), weights random from a seed:

1. kernel numerics — the Pallas flash (fwd + grads) and ragged paged
   kernels at the production shapes against their XLA references;
2. train — ``GPTStackedForPretraining`` through ``FusedTrainStep`` fed by
   ``DevicePrefetcher``, pure-bf16 AdamW, batch 8 x seq 1024;
3. serve — the same weights (optimizer state freed first) through
   ``ServingEngine``: 16 greedy requests over 8 slots, chunked prefill
   mixed with decode, retirement, page reuse and prefix-cache hits;
4. with four or more devices, both phases again over a dp 2 x mp 2 mesh,
   with a table of where the weights, moments and page pool live.

Every phase asserts what it produced; any failure is a traceback and a
non-zero exit.  Without a TPU the script exits non-zero within seconds —
nothing here can run on another backend, and no flag changes that.  The
timings printed are smoke output, not speeds.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import gc
import json
import math
import os
import re
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# batch 8 x seq 1024 is the only training shape with a chip history
# (TPU_SWEEP.json's flagship case); 1 compile step + 5 steady ones
TRAIN = dict(batch=8, seq=1024, steps=6)
# 65 pages x 24 MiB of pool beside 2.6 GiB of weights; 8 prompts (lengths
# cycling 64/200/380/700) each asked twice, so the second asking can hit
# the prefix cache and must reproduce the first token for token
SERVE = dict(num_slots=8, page_size=128, max_context=1024,
             prompt_lens=(64, 200, 380, 700), new_tokens=48, n_requests=16)
KERNEL_TOL = 0.05     # max-abs error, the tolerance tools/tpu_smoke.py uses
FLASH_KERNELS = {"_fwd_kernel", "_bwd_dkv_kernel", "_bwd_dq_kernel"}
RAGGED_KERNEL = "_ragged_kernel"
POOL_WRITE_KERNEL = "_pool_write_kernel"


def say(msg: str):
    print(f"chip_smoke: {msg}", flush=True)


def on_tpu() -> bool:
    import jax

    return jax.devices()[0].platform == "tpu"


def mosaic_kernels(lowered_texts) -> set:
    """Names of the Mosaic (Pallas TPU) kernels in lowered programs — read
    from the ``tpu_custom_call`` ops of the StableHLO the compiler was
    handed, not from a platform probe or a flag."""
    names = set()
    for text in lowered_texts:
        for line in text.splitlines():
            if "@tpu_custom_call" in line:
                names.update(re.findall(r'kernel_name = "([^"]+)"', line))
    return names


def no_fallback_noted():
    from paddle_tpu.analysis import codes

    assert not codes._SEEN_FALLBACKS, \
        f"a kernel fell back to its XLA expression: {codes._SEEN_FALLBACKS}"


# ---------------------------------------------------------------------------
# kernel numerics at the production shapes (chip only)
# ---------------------------------------------------------------------------

def kernel_numerics():
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import flash_attention as fa
    from paddle_tpu.ops.pallas_kernels import ragged_paged_attention as ra

    rng = np.random.RandomState(0)
    f32 = jnp.float32

    def max_err(a, b):
        return float(jnp.abs(a.astype(f32) - b.astype(f32)).max())

    # flash fwd + grads, [b*n = 16, s = 1024, d = 128] bf16 causal
    scale = 1.0 / math.sqrt(128)
    q, k, v = (jnp.array(rng.randn(1, 16, 1024, 128), jnp.bfloat16)
               for _ in range(3))

    def loss(fn):
        return lambda q, k, v: fn(q, k, v, True, scale).astype(f32).sum()

    errs = [max_err(fa._flash_bnsd(q, k, v, True, scale),
                    fa._xla_reference_bnsd(q, k, v, True, scale))]
    got = jax.grad(loss(fa._flash_bnsd), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(fa._xla_reference_bnsd), (0, 1, 2))(q, k, v)
    errs += [max_err(g, w) for g, w in zip(got, want)]
    say("kernel flash [16,1024,128] bf16 causal: max-abs err "
        "out/dq/dk/dv = " + "/".join(f"{e:.4f}" for e in errs))
    assert max(errs) < KERNEL_TOL, errs

    # ragged, the serve phase's geometry: H 16, page 128, d 128, token
    # block 8, 65-page pool, 8 pages a slot; shuffled page tables and
    # mixed q-lengths (decode deep in a context, decode at position 0,
    # a 128-token prefill straddling pages, a page edge, a short prefill)
    heads, page, dim, qb, pool, per_slot = 16, 128, 128, 8, 65, 8
    perm = rng.permutation(pool - 1) + 1          # page 0 is the null page
    table = lambda n, at: np.pad(perm[at:at + n],  # noqa: E731
                                 (0, per_slot - n)).astype(np.int32)
    runs = [(900, 1, table(8, 0)), (0, 1, table(1, 8)),
            (200, 128, table(3, 9)), (127, 1, table(1, 12)),
            (17, 5, table(1, 13))]
    t_max = 8 + 128
    # the blocks the engine would build: the 128-token prefill rides wide
    # blocks of the launch's own width
    qw = ra.ragged_wide_block(heads, 1, page, dim, jnp.bfloat16, qb)
    nb_max = 8 + 128 // qb
    nbw_max = ra.ragged_wide_capacity(t_max, qb, qw)
    plan_np, stats = ra.build_ragged_plan(
        runs, token_block=qb, page_size=page, t_max=t_max, nb_max=nb_max,
        wl_max=nb_max * per_slot, wide_block=qw, nbw_max=nbw_max)
    assert 0 < stats["wide_items"] < stats["n_items"], stats
    tables = np.zeros((t_max, per_slot), np.int32)
    lengths = np.zeros((t_max,), np.int32)
    for (base, count, tbl), start in zip(runs, stats["run_starts"]):
        tables[start:start + count] = tbl
        lengths[start:start + count] = base + 1 + np.arange(count)
    n_real = stats["n_tokens"]
    q = jnp.array(rng.randn(t_max, heads, dim), jnp.bfloat16)
    kp, vp = (jnp.array(rng.randn(pool, heads, page, dim), jnp.bfloat16)
              for _ in range(2))
    plan = tuple(jnp.array(plan_np[f]) for f in ra.RAGGED_PLAN_FIELDS)
    assert ra.ragged_shape_supported(page, dim, qb)
    got = ra.ragged_paged_attention(q, kp, vp, jnp.array(tables),
                                    jnp.array(lengths), plan, sm_scale=scale)
    want = ra._xla_ragged_reference(q, kp, vp, jnp.array(tables),
                                    jnp.array(lengths), scale)
    err = max_err(got[:n_real], want[:n_real])
    say(f"kernel ragged H={heads} page={page} d={dim} token_block={qb} "
        f"bf16, {n_real} tokens in {stats['n_items']} work items: "
        f"max-abs err {err:.4f}")
    assert err < KERNEL_TOL, err


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def train_phase(cfg, *, batch, seq, steps, sharding=None):
    """``steps`` fused train steps (the first compiles) of the stacked GPT
    at ``cfg`` in the pure-bf16 regime, fed by the device prefetcher.
    Returns ``(model, optimizer)``; drop the optimizer to free its
    moments."""
    import paddle_tpu as pt
    from paddle_tpu.core import memory, op_cache
    from paddle_tpu.io import DevicePrefetcher
    from paddle_tpu.models import GPTStackedForPretraining

    pt.seed(0)
    model = GPTStackedForPretraining(cfg)
    pt.amp.decorate(model, level="O2", dtype="bfloat16")
    opt = pt.optimizer.AdamW(learning_rate=1e-4,
                             parameters=model.parameters(),
                             multi_precision=False)
    train_step = pt.optimizer.FusedTrainStep(
        lambda ids, labels: model(ids, labels=labels), opt,
        amp_level="O1", amp_dtype="bfloat16")

    rng = np.random.RandomState(0)

    def batches():
        for _ in range(steps):
            yield (rng.randint(0, cfg.vocab_size, (batch, seq)),
                   rng.randint(0, cfg.vocab_size, (batch, seq)))

    losses, seconds = [], []
    with DevicePrefetcher(batches(), depth=2, sharding=sharding) as feed:
        for ids, labels in feed:
            t0 = time.perf_counter()
            losses.append(float(train_step(ids, labels)))  # host read
            seconds.append(time.perf_counter() - t0)
            if len(losses) == 1:    # the capture trace is behind us
                eager0 = op_cache.summary()["calls"]
        fed = feed.stats()["batches"]
    eager = op_cache.summary()["calls"] - eager0

    say(f"train {type(model).__name__} hidden={cfg.hidden_size} "
        f"layers={cfg.num_layers} batch={batch} seq={seq}: "
        f"compile+first step {seconds[0]:.1f}s, steady step "
        f"{float(np.median(seconds[1:])):.3f}s, "
        f"peak_bytes_in_use={memory.max_memory_allocated()}")
    say("train losses " + " ".join(f"{x:.4f}" for x in losses))
    assert fed == len(losses) == steps, (fed, len(losses), steps)
    assert all(np.isfinite(losses)), losses
    # random labels under a near-zero init: the first loss is ln(vocab)
    assert abs(losses[0] - math.log(cfg.vocab_size)) < 1.0, losses[0]
    assert train_step.program_count == 1, train_step.program_count
    assert train_step.dispatch_count == steps and eager == 0, \
        f"{train_step.dispatch_count} fused + {eager} eager dispatches " \
        f"for {steps} steps"
    if on_tpu():
        found = mosaic_kernels(train_step.lowered_texts())
        say(f"train program's Mosaic kernels: {sorted(found)}")
        assert FLASH_KERNELS <= found, \
            f"Pallas flash kernels missing from the train step: {found}"
    no_fallback_noted()
    return model, opt


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def serve_phase(model, *, num_slots, page_size, max_context, prompt_lens,
                new_tokens, n_requests, dp=1, mp=1):
    """Serve ``n_requests`` greedy requests — ``n_requests // 2`` distinct
    prompts, each asked twice — through a ``ServingEngine`` (a
    ``ShardedServingEngine`` when ``dp * mp > 1``) and check every request,
    counter and page.  Returns the engine, still open."""
    from paddle_tpu.serving import (
        RequestState, ServingEngine, ShardedServingEngine,
    )

    kw = dict(num_slots=num_slots, page_size=page_size,
              max_context=max_context, cache_dtype="bfloat16",
              prefix_cache=True)
    sharded = dp * mp > 1
    eng = (ShardedServingEngine(model, dp=dp, mp=mp, **kw) if sharded
           else ServingEngine(model, **kw))
    replicas = eng.replicas if sharded else [eng]

    rng = np.random.RandomState(1)
    vocab = model.config.vocab_size
    distinct = n_requests // 2
    prompts = [rng.randint(0, vocab, (prompt_lens[i % len(prompt_lens)],))
               for i in range(distinct)]
    reqs = [eng.submit(prompts[i % distinct], new_tokens)
            for i in range(n_requests)]
    t0 = time.perf_counter()
    eng.step()                       # the first tick compiles the step
    t_first = time.perf_counter() - t0
    eng.run_until_idle()
    t_all = time.perf_counter() - t0

    for r in reqs:
        assert r.state == RequestState.DONE, (r.id, r.state)
        assert len(r.tokens) == new_tokens, (r.id, len(r.tokens))
        assert all(0 <= t < vocab for t in r.tokens), r.id
    for first, again in zip(reqs[:distinct], reqs[distinct:]):
        assert list(first.tokens) == list(again.tokens), \
            f"request {again.id} re-asked request {first.id}'s prompt " \
            f"and got different tokens"
    steps = hits = 0
    for i, rep in enumerate(replicas):
        m = rep.metrics()
        for key in ("failed", "recoveries", "step_retries"):
            assert m[key] == 0, (i, key, m[key])
        a = rep.allocator
        assert a.used_pages == 0 and a.spec_pages == 0, \
            (i, a.used_pages, a.spec_pages)
        assert (a.free_pages + a.used_pages + a.spec_pages
                + a.shared_pages) == a.capacity, i
        assert rep.compiled_programs <= 2, (i, rep.compiled_programs)
        if on_tpu():
            found = mosaic_kernels(rep.lowered_texts())
            assert RAGGED_KERNEL in found, \
                f"replica {i}: ragged Pallas kernel missing: {found}"
            assert POOL_WRITE_KERNEL in found, \
                f"replica {i}: the pool write's launch missing: {found}"
        # a work item moves all the replica's local heads of its page:
        # one grid step an item (ops/pallas_kernels/ragged_paged_attention)
        heads_an_item = m["ragged_heads_per_block"]
        assert heads_an_item == model.config.num_heads // mp, \
            (i, heads_an_item)
        assert m["launched_grid_steps"] == m["launched_items"] > 0, i
        steps += m["fused_steps"]
        hits += m["prefix_hits"] + m["prefix_partial_hits"]
    say(f"serve dp={dp} mp={mp} slots={num_slots} page={page_size} "
        f"ctx={max_context}: {n_requests} requests x {new_tokens} tokens "
        f"DONE in {steps} fused steps, {hits} prefix hits, "
        f"{heads_an_item} heads a work item, "
        f"<= 2 programs a replica; first tick (compile) {t_first:.1f}s, "
        f"all {t_all:.1f}s")
    no_fallback_noted()
    return eng


# ---------------------------------------------------------------------------
# four chips: where things live
# ---------------------------------------------------------------------------

def placement_row(name, array):
    """One line of the placement table: bytes held per device."""
    held = {}
    for s in array.addressable_shards:
        held[s.device.id] = held.get(s.device.id, 0) + s.data.nbytes
    spec = getattr(array.sharding, "spec", array.sharding)
    say(f"placement {name}: shape={tuple(array.shape)} spec={spec} "
        "bytes/device=" + " ".join(f"{d}:{b}" for d, b in sorted(held.items())))


def assert_balanced(what, tol=1.5):
    import jax

    used = [d.memory_stats()["bytes_in_use"] for d in jax.devices()]
    say(f"placement {what}: bytes_in_use/device = "
        + " ".join(str(u) for u in used))
    mean = sum(used) / len(used)
    assert max(used) <= tol * mean, \
        f"{what}: a device holds more than {tol}x the mean: {used}"


def multichip(cfg):
    """Both phases on four chips as one dp 2 x mp 2 mesh."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.distributed import mesh as dmesh

    mesh = dmesh.build_mesh({"dp": 2, "mp": 2}, jax.devices()[:4])
    dmesh.set_mesh(mesh)
    model, opt = train_phase(cfg, batch=TRAIN["batch"], seq=TRAIN["seq"],
                             steps=3,
                             sharding=NamedSharding(mesh, P("dp", None)))
    qkv_w = model.decoder.qkv_w
    placement_row("qkv_w", qkv_w._value)
    placement_row("moment1[qkv_w]",
                  opt._get_accumulator("moment1", qkv_w)._value)
    assert_balanced("after train")
    del opt
    gc.collect()
    dmesh.set_mesh(None)
    eng = serve_phase(model, dp=2, mp=2, **SERVE)
    for i, rep in enumerate(eng.replicas):
        placement_row(f"replica {i} k-pool", rep.cache.k._value)
    assert_balanced("while serving")
    eng.close()


# ---------------------------------------------------------------------------

def main() -> int:
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    limit = (dev.memory_stats() or {}).get("bytes_limit")
    say(f"jax={jax.__version__} platform={dev.platform} "
        f"device_kind={dev.device_kind} devices={device['count']} "
        f"bytes_limit={limit}")
    if dev.platform != "tpu":
        say(f"this smoke needs a TPU and JAX found platform "
            f"'{dev.platform}' ({dev.device_kind}); nothing was run")
        return 1

    from paddle_tpu.models import gpt_1p3b
    from paddle_tpu.sysconfig import enable_compile_cache

    say(f"compile cache at {enable_compile_cache()}")
    cfg = gpt_1p3b(hidden_dropout=0.0, attention_dropout=0.0,
                   use_flash_attention=True, recompute_interval=1)

    kernel_numerics()
    model, opt = train_phase(cfg, **TRAIN)
    del opt
    gc.collect()
    serve_phase(model, **SERVE).close()
    del model
    gc.collect()

    if device["count"] >= 4:
        multichip(cfg)
    else:
        say(f"multichip: not run ({device['count']} devices)")

    say(f"OK platform={dev.platform} device_kind={dev.device_kind} "
        f"devices={device['count']}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
