"""Runner for ``kind: train`` traffic: the program's train path exactly as
``chip_smoke.py`` drives it (``GPTStackedForPretraining`` ->
``optimizer.FusedTrainStep`` <- ``io.DevicePrefetcher``), one global batch a
step, every step ending in the host reading the loss."""
from __future__ import annotations

import gc
import importlib
import math
import time
from typing import Dict

import numpy as np

from ..generators.requests import train_batch
from ..harness import estimators, runtime
from ..harness.runtime import say


def _mesh_and_sharding(cell: Dict, devices):
    """The cell's mesh (None on one chip) and the batch's sharding."""
    axes = cell.get("mesh")
    if not axes:
        return None, None
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.distributed import mesh as dmesh

    mesh = dmesh.build_mesh({k: int(v) for k, v in axes.items()}, devices)
    dmesh.set_mesh(mesh)
    return mesh, NamedSharding(mesh, P("dp", None))


def place_batch(arrays, sharding):
    """Host arrays -> the program's Tensors, sharded over the mesh if there is one."""
    import jax

    import paddle_tpu as pt

    if sharding is None:
        return [pt.to_tensor(a) for a in arrays]
    return [pt.Tensor(jax.device_put(a, sharding)) for a in arrays]


def _train_step(model, trainer: Dict):
    import paddle_tpu as pt

    opt = pt.optimizer.AdamW(learning_rate=float(trainer["learning_rate"]),
                             parameters=model.parameters(), multi_precision=False)
    step = pt.optimizer.FusedTrainStep(
        lambda ids, labels: model(ids, labels=labels), opt,
        amp_level="O1", amp_dtype="bfloat16")
    return step, opt


def reference_check(builder, ctx: Dict, seed: int, sharding) -> Dict:
    """The system's loss and its gradient of the first block's ``qkv_w``
    against the plain reference: a few layers at full width, the cell's
    sequence length, through the same eager autograd + flash path."""
    import jax.numpy as jnp

    from paddle_tpu.amp.auto_cast import auto_cast

    check, traffic = ctx["cell"]["reference_check"], ctx["traffic"]
    vocab = ctx["config"]["model"]["vocab_size"]
    model = builder.build_model(ctx["config"], seed=seed, trainer=ctx["cell"]["trainer"],
                                num_layers=int(check["layers"]))
    ids, labels = train_batch({**traffic, "global_batch": int(check["sequences"])},
                              seed=seed, step=10 ** 9, vocab=vocab)
    ref = importlib.import_module(builder.REFERENCE)
    want_loss, want_grad = ref.loss_and_grad(
        builder.reference_weights(model), jnp.asarray(ids), jnp.asarray(labels),
        **builder.reference_kwargs(model))
    want = np.asarray(want_grad["layers"]["qkv_w"][0], np.float32)
    want_loss = float(want_loss)
    del want_grad
    ids_t, labels_t = place_batch((ids, labels), sharding)
    with auto_cast(enable=True, level="O1", dtype="bfloat16"):
        loss = model(ids_t, labels=labels_t)
    loss.backward()
    got = np.asarray(model.decoder.qkv_w.grad._value[0].astype(jnp.float32))
    got_loss = float(loss)
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    out = {"loss_system": got_loss, "loss_reference": want_loss,
           "loss_abs_err": abs(got_loss - want_loss), "grad_rel_err": rel,
           "ok": bool(abs(got_loss - want_loss) <= check["loss_abs_tol"]
                      and rel <= check["grad_rel_tol"])}
    del model, loss
    gc.collect()
    return out


def run(ctx: Dict, *, seed: int, seconds: float, trace: bool) -> Dict:
    import jax

    cell, traffic, config = ctx["cell"], ctx["traffic"], ctx["config"]
    chips = ctx["entry"]["chips"]
    devices = runtime.require_tpu(chips)
    from paddle_tpu.core import op_cache
    from paddle_tpu.io import DevicePrefetcher

    say(f"compile cache at {runtime.enable_compile_cache()}")
    compiles = runtime.CompileCounter()
    builder = importlib.import_module(config["builder"])
    marks = {"import": time.perf_counter()}
    mesh, sharding = _mesh_and_sharding(cell, devices)
    checked = reference_check(builder, ctx, seed, sharding)
    say(f"reference check: {checked}")
    marks["reference_check"] = time.perf_counter()

    model = builder.build_model(config, seed=seed, trainer=cell["trainer"])
    train_step, opt = _train_step(model, cell["trainer"])
    jax.block_until_ready([p._value for p in model.parameters()])
    marks["weights"] = time.perf_counter()

    vocab = config["model"]["vocab_size"]

    def batches():
        step = 0
        while True:
            yield train_batch(traffic, seed=seed, step=step, vocab=vocab)
            step += 1

    tokens_per_step = int(traffic["global_batch"]) * int(traffic["sequence"])
    losses, steps = [], []          # steps: (t_start, t_end, traced)
    session = runtime.TraceSession(ctx["entry"]["name"]) if trace else None
    trace_steps = int(cell["trace"]["steps"])
    with DevicePrefetcher(batches(), depth=int(cell["trainer"]["prefetch_depth"]),
                          sharding=sharding) as feed:
        def one_step(traced=False):
            t0 = time.perf_counter()        # waiting for the batch is inside the step
            ids, labels = next(feed)
            with jax.profiler.TraceAnnotation("bench.step"):
                loss = train_step(ids, labels)
                with jax.profiler.TraceAnnotation("bench.loss_read"):
                    losses.append(float(loss))
            steps.append((t0, time.perf_counter(), traced))

        for _ in range(int(cell["warmup_steps"])):      # the first compiles
            one_step()
        eager0 = op_cache.summary()["calls"]
        marks["compile_and_warmup"] = time.perf_counter()
        del steps[:]
        first_loss = losses[0]

        stall0 = feed.stats()["stall_seconds_total"]
        compiles0, programs0 = compiles.count, train_step.program_count
        dispatch0 = train_step.dispatch_count
        gc.collect()
        gc.freeze()         # set-up's objects are not walked again
        gc.disable()        # and no collection pauses a step of the window
        t_window = time.perf_counter()
        setup_s = t_window - runtime.T_PROCESS_START
        while time.perf_counter() - t_window < seconds:
            one_step()
        t_end = time.perf_counter()
        gc.enable()
        stall = feed.stats()["stall_seconds_total"] - stall0
        n_steps = len(steps)
        window_compiles = compiles.count - compiles0
        if session:         # the profile follows the window, so it disturbs no clock
            session.start()
            for _ in range(trace_steps):
                one_step(traced=True)
            session.end_window()
            session.finish()
        fed = feed.stats()["batches"]

    in_window = [(a, b) for a, b, traced in steps if not traced]
    clean = [b - a for a, b in in_window]
    window_s = t_end - t_window
    rate = estimators.rate_over_steps(tokens_per_step, in_window, chips)
    # Random weights give logits of variance hidden x initializer_range^2 (unit-
    # variance features against the tied N(0, range) embedding), so the first
    # loss is ln(vocab) + half that variance: 11.24 at hidden 2048, 11.85 at
    # 5120 (read 11.22-11.24 and 11.84-11.88 on the chip).  0.15 lets the seeds'
    # scatter through and no wrong scale of logits or labels.
    cfg = model.config
    expected_first = math.log(vocab) + cfg.hidden_size * cfg.initializer_range ** 2 / 2
    found = runtime.mosaic_kernels(train_step.lowered_texts())
    fallbacks = runtime.fallbacks_noted()
    eager = op_cache.summary()["calls"] - eager0
    checks = {
        "reference": checked["ok"],
        "losses_finite": bool(np.all(np.isfinite(losses))),
        "first_loss_as_random_weights_give": abs(first_loss - expected_first) < 0.15,
        "one_program": train_step.program_count == 1 == programs0,
        "no_compile_in_window": window_compiles == 0,
        "one_dispatch_a_step": (train_step.dispatch_count - dispatch0 == len(steps)
                                and eager == 0),
        "flash_kernels_present": set(cell["mosaic_kernels"]) <= found,
        "no_fallback_noted": not fallbacks,
        "every_batch_consumed": fed == len(losses),
    }
    say(f"checks: {checks}; kernels {sorted(found)}; fallbacks {fallbacks}")
    say("setup break-down (s): " + ", ".join(
        f"{k} {marks[k] - prev:.2f}" for k, prev in zip(
            marks, [runtime.T_PROCESS_START] + list(marks.values())[:-1])))
    say(f"window {window_s:.2f}s, {n_steps} steps, median step "
        f"{1e3 * estimators.median(clean):.3f} ms, first loss {first_loss:.4f}, "
        f"last {losses[-1]:.4f}, rate over the whole window {rate:.2f} tokens/s/chip, "
        f"at the median step {tokens_per_step / estimators.median(clean) / chips:.2f}")
    return {
        "correct": all(checks.values()),
        "attempted": n_steps,
        "failed": 0,
        "checks": checks,
        "end_to_end": {"setup_s": setup_s, "train_tokens_per_s_per_chip": rate},
        "clocks": {"step_s": clean, "input_stall_share": [stall / window_s]},
        "counters": {},
        "facts": {"tokens_per_step": tokens_per_step, "chips": chips,
                  "traced_steps": trace_steps,
                  "tokens_per_s_per_chip": rate},
        "session": session,
        "devices": devices,
    }
