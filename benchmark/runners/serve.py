"""Runner for ``kind: serve`` traffic: ``serving.ServingEngine`` driven through
``submit`` / ``step`` / ``on_token``.  The generator has a thread of its own
and submits at each request's due time whatever the engine is doing; this
thread ticks the engine.  Every time is read from the benchmark's own clock."""
from __future__ import annotations

import importlib
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from ..generators.requests import Planned, plan_requests
from ..harness import estimators, runtime
from ..harness.runtime import say

COUNTERS = ("fused_steps", "tokens", "prefill_tokens", "work_items", "work_capacity",
            "block_rows", "block_row_capacity", "failed", "recoveries",
            "step_retries", "timed_out", "shed", "completed", "admitted")


class Record:
    """One submitted request as the benchmark's clock saw it."""

    __slots__ = ("plan", "due", "submitted", "token_times", "request")

    def __init__(self, plan: Planned, due: Optional[float]):
        self.plan, self.due = plan, due
        self.submitted: Optional[float] = None
        self.token_times: List[float] = []
        self.request = None

    def on_token(self, request, token):
        self.token_times.append(time.perf_counter())

    @property
    def done_at(self) -> Optional[float]:
        full = len(self.token_times) >= self.plan.max_new_tokens
        return self.token_times[-1] if full else None


class Generator(threading.Thread):
    """Submits the plan: scheduled requests at ``t0 + due_s``, on-demand ones
    whenever fewer than ``backlog`` requests wait in the engine's queue."""

    def __init__(self, engine, plan: List[Planned], t0: float, backlog: int):
        super().__init__(name="bench-generator", daemon=True)
        self.engine, self.plan, self.t0, self.backlog = engine, plan, t0, backlog
        self.records: List[Record] = []
        self.stop = threading.Event()
        self.error: Optional[BaseException] = None

    def _submit(self, plan: Planned, due: Optional[float]):
        rec = Record(plan, due)
        rec.submitted = time.perf_counter()
        rec.request = self.engine.submit(plan.prompt, plan.max_new_tokens,
                                         on_token=rec.on_token)
        self.records.append(rec)

    def run(self):
        try:
            scheduled = [p for p in self.plan if p.due_s is not None]
            on_demand = [p for p in self.plan if p.due_s is None]
            for p in scheduled:
                due = self.t0 + p.due_s
                while not self.stop.is_set():
                    wait = due - time.perf_counter()
                    if wait <= 0:
                        break
                    time.sleep(min(wait, 0.05))
                if self.stop.is_set():
                    return
                self._submit(p, due)
            i = 0
            while on_demand and not self.stop.is_set():
                if self.engine.queue.depth < self.backlog:
                    self._submit(on_demand[i % len(on_demand)], None)
                    i += 1
                else:
                    time.sleep(0.002)
        except BaseException as e:  # noqa: BLE001 - read by the ticking thread
            self.error = e


def reference_check(builder, engine, model, ctx: Dict, seed: int) -> Dict:
    """One seeded request decoded through the engine (this also compiles and
    warms the fused step); the reference's full forward over prompt + emitted
    tokens must give each emitted token a logit within ``logit_gap_tol`` of
    its position's maximum."""
    import jax.numpy as jnp

    check = ctx["cell"]["reference_check"]
    vocab = ctx["config"]["model"]["vocab_size"]
    rng = np.random.default_rng([int(seed), 7])
    prompt = rng.integers(0, vocab, int(check["prompt_tokens"]), dtype=np.int64)
    req = engine.submit(prompt, int(check["new_tokens"]))
    engine.run_until_idle()
    tokens = list(req.tokens)
    ref = importlib.import_module(builder.REFERENCE)
    ids = np.concatenate([prompt, np.asarray(tokens[:-1], np.int64)])[None]
    logits = np.asarray(ref.logits(builder.reference_weights(model), jnp.asarray(ids),
                                   **builder.reference_kwargs(model)))[0]
    rows = logits[len(prompt) - 1:]
    gaps = rows.max(axis=-1) - rows[np.arange(len(tokens)), tokens]
    return {"tokens": len(tokens), "logit_gap_max": float(gaps.max()),
            "argmax_agree": int((rows.argmax(-1) == np.asarray(tokens)).sum()),
            "ok": bool(req.state == "DONE" and len(tokens) == check["new_tokens"]
                       and gaps.max() <= check["logit_gap_tol"])}


def _snapshot(engine) -> Dict[str, float]:
    m = engine.metrics()
    return {k: m[k] for k in COUNTERS}


def _delta(a: Dict, b: Dict) -> Dict[str, float]:
    return {k: b[k] - a[k] for k in a}


def run(ctx: Dict, *, seed: int, seconds: float, trace: bool,
        rate: Optional[float] = None) -> Dict:
    import jax

    cell, traffic, config = ctx["cell"], ctx["traffic"], ctx["config"]
    devices = runtime.require_tpu(ctx["entry"]["chips"])
    from paddle_tpu.serving import ServingEngine

    say(f"compile cache at {runtime.enable_compile_cache()}")
    compiles = runtime.CompileCounter()
    builder = importlib.import_module(config["builder"])
    marks = {"import": time.perf_counter()}
    model = builder.build_model(config, seed=seed)
    jax.block_until_ready([p._value for p in model.parameters()])
    marks["weights"] = time.perf_counter()
    eng_kw = dict(cell["engine"])
    engine = ServingEngine(model, **eng_kw)
    checked = reference_check(builder, engine, model, ctx, seed)
    say(f"reference check: {checked}")
    marks["compile_and_reference_check"] = time.perf_counter()

    warm = int(cell["warm_requests"])
    cell_rate = cell.get("rate_per_s")
    if rate is not None and cell_rate:      # a sweep: the warm population follows the rate
        warm = max(1, int(round(warm * rate / cell_rate)))
        cell_rate = rate
    lead_in = float(cell["lead_in_s"])
    plan = plan_requests(traffic, seed=seed, vocab=config["model"]["vocab_size"],
                         seconds=seconds, lead_in_s=lead_in, warm_requests=warm,
                         rate_per_s=cell_rate)
    slots = int(eng_kw["num_slots"])
    backlog = int(traffic.get("backlog_factor", 0) * slots)
    session = runtime.TraceSession(ctx["entry"]["name"]) if trace else None
    trace_s = float(cell["trace"]["seconds"]) if trace else 0.0

    t0 = time.perf_counter()
    w0, w1 = t0 + lead_in, t0 + lead_in + seconds
    gen = Generator(engine, plan, t0, backlog)
    gen.start()
    steps = []          # (t_end, active_slots, pages_used, queue_depth, tokens_this_step)
    snaps: Dict[str, Dict] = {}
    compiles_at: Dict[str, int] = {}
    drain_until = w1 + float(cell["drain_s_max"])

    def tick():
        with jax.profiler.TraceAnnotation("bench.tick"):
            m = engine.step()
        steps.append((time.perf_counter(), m["active_slots"], m["pages_used"],
                      m["queue_depth"], m["tokens_this_step"]))
        if not m["active_slots"] and not m["queue_depth"]:
            time.sleep(0.001)

    n_window = sum(1 for p in plan if p.phase == "window")

    def due_in_window():
        return [r for r in list(gen.records) if r.due is not None and w0 <= r.due < w1]

    def mark(name):         # between two ticks: a step boundary
        snaps[name] = _snapshot(engine)
        snaps[name]["t"] = time.perf_counter()
        compiles_at[name] = compiles.count

    try:
        while time.perf_counter() < w0:
            tick()
        mark("window_start")
        while time.perf_counter() < w1 - trace_s:
            tick()
        if session:
            mark("trace_start")
            session.start()
            while time.perf_counter() < w1:
                tick()
            mark("trace_end")
            session.end_window()
        mark("window_end")
        # the drain: only until the requests due in the window's last moments
        # have their first token (or the bound passes)
        while time.perf_counter() < drain_until and not (
                len(due_in_window()) == n_window
                and all(r.token_times for r in due_in_window())):
            tick()
        t_drained = time.perf_counter()
        if session:
            session.finish()
    finally:
        gen.stop.set()
        gen.join(timeout=10)
    if gen.error is not None:
        raise gen.error
    records = list(gen.records)
    # what finished before the drain ended is judged; the rest is cancelled
    finished = [r for r in records if r.request.terminal]
    for r in records:
        r.request.cancel()
    for _ in range(10_000):
        if not engine.scheduler.active_slots and not engine.queue.depth:
            break
        engine.step()
    alloc = engine.allocator
    ledger = {"used": alloc.used_pages, "spec": alloc.spec_pages, "free": alloc.free_pages,
              "shared": alloc.shared_pages, "capacity": alloc.capacity}
    final = _snapshot(engine)
    programs = engine.compiled_programs
    found = runtime.mosaic_kernels(engine.lowered_texts())
    facts = {"token_block": int(engine.token_block), "slots": slots,
             "pages_capacity": int(alloc.capacity), "chips": ctx["entry"]["chips"]}
    engine.close()

    in_window = [s for s in steps if w0 <= s[0] < w1]
    itl = estimators.gaps_landing_in([r.token_times for r in records], w0, w1)
    completions = [(r.done_at, len(r.plan.prompt) + r.plan.max_new_tokens)
                   for r in records if r.done_at is not None and w0 <= r.done_at < w1]
    if traffic["mode"] == "open":
        judged = due_in_window()
        ttft = [r.token_times[0] - r.due if r.token_times else float("inf") for r in judged]
        n_failed = sum(1 for r in judged if not r.token_times or r.request.state in
                       ("FAILED", "TIMED_OUT"))
        attempted = len(judged)
    else:
        judged = [r for r in records if r.done_at is not None and w0 <= r.done_at < w1]
        ttft = []
        n_failed = sum(1 for r in records if r.request.state in ("FAILED", "TIMED_OUT"))
        attempted = len(judged) + n_failed
    window = _delta(snaps["window_start"], snaps["window_end"])
    fallbacks = runtime.fallbacks_noted()
    checks = {
        "reference": checked["ok"],
        "finished_requests_done_in_full": all(
            r.request.state == "DONE"
            and len(r.request.tokens) == r.plan.max_new_tokens for r in finished),
        "no_failure_recovery_retry": all(
            final[k] == 0 for k in ("failed", "recoveries", "step_retries", "timed_out", "shed")),
        "ledger_closed": (ledger["used"] == 0 and ledger["spec"] == 0 and ledger["free"]
                          + ledger["used"] + ledger["spec"] + ledger["shared"]
                          == ledger["capacity"]),
        "at_most_two_programs": programs <= 2,
        "no_compile_in_window": compiles_at["window_end"] == compiles_at["window_start"],
        "ragged_kernel_present": set(cell["mosaic_kernels"]) <= found,
        "no_fallback_noted": not fallbacks,
        "no_callback_error": all(r.request.callback_error is None for r in records),
        "window_had_work": len(in_window) > 0 and len(itl) > 0,
    }
    lateness = [r.submitted - r.due for r in due_in_window()]
    queue_wait = [r.request.t_admitted - r.request.t_submitted for r in judged
                  if r.request.t_admitted is not None]
    fifth = max(1, len(in_window) // 5)
    say(f"checks: {checks}; kernels {sorted(found)}; fallbacks {fallbacks}; ledger {ledger}")
    say("setup break-down (s): " + ", ".join(
        f"{k} {marks[k] - prev:.2f}" for k, prev in zip(
            marks, [runtime.T_PROCESS_START] + list(marks.values())[:-1]))
        + f", lead_in {lead_in:.2f}; drain {t_drained - w1:.2f}")
    say(f"steadiness: seated first/last fifth of the window "
        f"{np.mean([s[1] for s in in_window[:fifth]]):.2f}/"
        f"{np.mean([s[1] for s in in_window[-fifth:]]):.2f} of {slots}, queue depth "
        f"{np.mean([s[3] for s in in_window[:fifth]]):.2f}/"
        f"{np.mean([s[3] for s in in_window[-fifth:]]):.2f}, pages "
        f"{np.mean([s[2] for s in in_window[:fifth]]):.1f}/"
        f"{np.mean([s[2] for s in in_window[-fifth:]]):.1f}")
    step_s = [b[0] - a[0] for a, b in zip(in_window, in_window[1:])]
    say(f"window: {len(in_window)} steps (median {1e3 * (estimators.median(step_s) or 0):.2f} ms), "
        f"{len(judged)} judged requests, {n_failed} failed, {len(itl)} gaps, "
        f"{len(completions)} completions, rate offered {cell_rate}, "
        f"counters {window}")
    e2e = {
        "setup_s": w0 - runtime.T_PROCESS_START,
        "serve_itl_p95_ms": _ms(estimators.percentile(itl, 95)),
        "serve_tokens_per_s": estimators.rate_between_boundaries(
            window["tokens"] + window["prefill_tokens"],
            snaps["window_start"]["t"], snaps["window_end"]["t"]),
    }
    say(f"estimators: {e2e}")
    return {
        "correct": all(checks.values()),
        "attempted": attempted,
        "failed": n_failed,
        "checks": checks,
        "end_to_end": e2e,
        "clocks": {"ttft_s": ttft, "itl_s": itl, "lateness_s": lateness,
                   "queue_wait_s": queue_wait, "step_s": step_s,
                   "slots_used": [s[1] for s in in_window],
                   "pages_used": [s[2] for s in in_window],
                   "queue_depth": [s[3] for s in in_window]},
        "counters": {"window": window,
                     "trace": (_delta(snaps["trace_start"], snaps["trace_end"])
                               if session else {})},
        "facts": facts,
        "session": session,
        "devices": devices,
    }


def _ms(seconds: Optional[float]) -> Optional[float]:
    return None if seconds is None else 1e3 * seconds
