"""ServingEngine: continuous batching over the paged KV cache with ONE
fused mixed prefill/decode step.

One engine serves an arbitrary stream of requests with ONE compiled
program (greedy traffic — the common case) for the whole lifetime of the
process, plus one more only if sampling requests ever arrive:

- **fused step** — every tick dispatches a single donated, retrace-free
  program serving ALL seated decode slots AND a budgeted number of
  prefill tokens from admitting requests (``prefill_token_budget``), at
  token granularity: the step's inputs are a flat ``[T, 1]`` token list
  (decode tokens and prefill chunk tokens mixed), per-token positions and
  page-table rows, and the host-built ragged work list that
  ``ops/pallas_kernels/ragged_paged_attention.py`` iterates on TPU.
  Every token's K/V scatters into the pool at its absolute position, then
  attends causally over its own slot's pages up to itself — so a prefill
  chunk's tokens see each other through the pool within the SAME launch,
  and there is no prefill/decode phase barrier left (the PR-5
  per-request ``[1, chunk]`` prefill program is retired).  A slot whose
  prompt completes this step samples its first generated token from its
  last prompt row — prefill piggybacks on decode, vLLM-style.  Padding
  tokens ride with null-page tables and position 0 so the shapes never
  change as the mix churns — zero retraces, asserted by
  ``serve_trace_counts()`` exactly like ``models/generation``.

- **one step in flight** — a tick plans, packs and ENQUEUES step N+1
  while step N still runs, and only then waits for N's tokens and harvests
  them, so the host's planning and the result's trip to the host hide
  behind the chip's work.  N+1 is planned against the state N leaves if it
  succeeds (``AdmissionScheduler.plan_step(ahead=)``), and its decode rows
  take their ids from N's sampled tokens ON THE DEVICE (``id_src`` in the
  packed input; the host has not read them).  Whatever only N's results
  can say (an EOS, a non-finite row, a failure) makes N+1's run for that
  slot VOID: never emitted, never counted, its writes confined to pages
  the slot owned when N+1 was enqueued.  Mirrors, counters and state rows
  move in harvest alone.  A step whose successor cannot be planned before
  its result is read (the speculative engine's verify step) runs with
  nothing enqueued ahead: the serial engine is this pipeline at depth 0.

The step has a greedy variant (pure argmax — no full-vocab sort,
softmax, or RNG traffic on the hot path) and a sampling variant (per-slot
traced temperature/top-k/top-p vectors; greedy rows inside a mixed batch
stay bit-exact).  The host picks per step; both stay cached, so the
retrace-freedom invariant holds per variant.

Request lifecycle: SUBMITTED (queued; admission backpressures on free
slots AND free pages) -> PREFILL -> DECODE -> one of the four terminal
states:

- ``DONE`` — hit max_new_tokens or eos;
- ``CANCELLED`` — ``Request.cancel()`` honored at the next step boundary;
- ``TIMED_OUT`` — the per-request ``deadline_s`` passed, or the request
  overstayed the queue's ``max_queue_wait_s`` (load shedding);
- ``FAILED`` — the request was implicated in a crashed/stalled/NaN step;
  the error is attached as ``Request.error``.

Fault containment (docs/serving.md "Failure model & SLOs"): one bad
request, one wedged step, or one transient device error never kills the
engine or strands other requests.

- **watchdog** — with ``stall_budget_s`` set, step dispatch runs on a
  supervised worker thread; a step that exceeds the budget is abandoned
  (the zombie's eventual write-backs land in orphaned buffers, see
  ``_rebuild``), the seated requests are FAILED, and the engine rebuilds
  its device state from the scheduler's host mirrors and keeps serving.
- **retry + backoff** — a step exception is retried once (transient
  device errors); a second failure triggers recovery, and re-admission
  backs off exponentially so a persistently sick device is not hammered.
- **finiteness sentry** — every step also returns a fused per-slot
  finiteness flag over the logits (the PR-4 fused all-finite reduction of
  ``checkpoint/sentry.py`` widened from one scalar to one flag per slot,
  riding in the SAME compiled program: zero extra host syncs); a
  NaN-poisoned slot is quarantined (FAILED) instead of streaming garbage.
- **load shedding** — the queue is bounded (``max_queue_depth`` →typed
  ``Overloaded`` raised at submit) and queue-wait bounded
  (``max_queue_wait_s`` → TIMED_OUT at the step boundary); shed/timeout/
  failure counters ride in the per-step metrics.

The invariant proven by tests/test_serving_faults.py and
tools/serving_fault_gate.py: **page accounting stays exact through every
failure path** — cancel, timeout, crash, stall, quarantine, recovery —
no leaked or double-freed pages.

See docs/serving.md for the architecture and slot/page lifecycle.
"""
from __future__ import annotations

import itertools
import math
import queue as _queue
import threading
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import ops
from ..analysis.cost_model import ragged_padding_waste
from ..distributed import serving_mesh as _srv_mesh
from ..ops import dispatch
from ..telemetry import metrics as _tmetrics
from ..telemetry import trace as _ttrace
from ..ops.pallas_kernels.ragged_paged_attention import (
    RAGGED_PLAN_FIELDS, build_ragged_plan, ragged_head_block,
    ragged_plan_shapes, ragged_token_block, ragged_wide_block,
    ragged_wide_capacity, ragged_write_capacity,
)
from ..ops.pallas_kernels.pool_write import pool_write_group
from ..tensor import Tensor, to_tensor
from .admission import AdmissionScheduler, StepWork
from .paged_cache import BlockAllocator
from .prefix_cache import PrefixCache

__all__ = [
    "RequestState", "SamplingParams", "Request", "RequestQueue",
    "ServingEngine", "serve_trace_counts", "reset_serve_trace_counts",
    "ServingError", "Overloaded", "DeadlineExceeded", "RequestCancelled",
    "StepStalledError", "NaNLogitsError",
]

_NEG = np.float32(-1e30)


# ---------------------------------------------------------------------------
# typed serving errors (docs/serving.md "Failure model & SLOs")
# ---------------------------------------------------------------------------

class ServingError(RuntimeError):
    """Base of every typed serving fault."""


class Overloaded(ServingError):
    """Load shed: the bounded queue is full (raised at ``submit``) or the
    request overstayed ``max_queue_wait_s`` (attached to a TIMED_OUT
    request).  Clients should back off and retry."""


class DeadlineExceeded(ServingError):
    """The request's ``deadline_s`` passed before it completed."""


class RequestCancelled(ServingError):
    """The request was cancelled via ``Request.cancel()``."""


class StepStalledError(ServingError):
    """A supervised step exceeded the watchdog's stall budget."""


class StepBuildError(ServingError):
    """The fused step failed the first time its variant was ever
    dispatched — a kernel the compiler refuses, a pool that does not fit.
    A build failure, not a request failure: it escapes the containment
    boundary instead of leaving a server that answers nothing."""


class UnsupportedServingMode(ServingError):
    """A serving mode was asked of a model whose paged path does not have it
    (the model names what it lacks in ``serving_unsupported``): refused at
    construction, never served wrongly."""


def refuse_unsupported(model, **asked):
    """Raise :class:`UnsupportedServingMode` for the first mode in ``asked``
    (mode -> whether it was asked for) that ``model.serving_unsupported``
    (mode -> why) names.  A model without that table supports them all."""
    lacks = getattr(model, "serving_unsupported", None) or {}
    for mode, wanted in asked.items():
        if wanted and mode in lacks:
            raise UnsupportedServingMode(
                f"{type(model).__name__} does not serve with {mode}: "
                f"{lacks[mode]}")


class NaNLogitsError(ServingError):
    """The finiteness sentry caught non-finite logits for this slot."""


class RequestState:
    SUBMITTED = "SUBMITTED"
    PREFILL = "PREFILL"
    DECODE = "DECODE"
    DONE = "DONE"
    CANCELLED = "CANCELLED"
    TIMED_OUT = "TIMED_OUT"
    FAILED = "FAILED"

    TERMINAL = frozenset({DONE, CANCELLED, TIMED_OUT, FAILED})


@dataclass
class SamplingParams:
    """Per-request sampling; every field rides as a traced per-slot vector
    inside the ONE compiled decode step (no retrace across mixes).
    Greedy (``do_sample=False``) ignores the rest."""

    do_sample: bool = False
    temperature: float = 1.0
    top_k: int = 0          # 0 = off
    top_p: float = 1.0      # 1.0 = off

    def __post_init__(self):
        if self.do_sample and not self.temperature > 0.0:
            raise ValueError("temperature must be > 0 when do_sample=True")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {self.top_k}")


class Request:
    """One generation request moving through the engine."""

    _ids = itertools.count()

    def __init__(self, prompt: np.ndarray, max_new_tokens: int,
                 sampling: Optional[SamplingParams] = None,
                 eos_token_id: Optional[int] = None,
                 on_token: Optional[Callable] = None,
                 deadline_s: Optional[float] = None):
        self.id = next(Request._ids)
        self.prompt = np.asarray(prompt, np.int64).reshape(-1)
        self.max_new_tokens = int(max_new_tokens)
        self.sampling = sampling or SamplingParams()
        self.eos_token_id = eos_token_id
        self.on_token = on_token
        self.state = RequestState.SUBMITTED
        self.tokens: List[int] = []      # generated ids, in order
        self.adapter: Optional[str] = None   # LoRA tenant (serving/lora.py)
        # fault-containment bookkeeping
        self.deadline_s = None if deadline_s is None else float(deadline_s)
        self.deadline: Optional[float] = None   # absolute monotonic; at submit
        self.submit_t: Optional[float] = None   # monotonic queue-entry time
        # SLO timestamps (time.monotonic; docs/observability.md): every
        # terminal request carries a complete, monotonically ordered set
        # of the stages it actually reached — t_submitted <= t_admitted
        # <= t_first_token <= t_terminal, with the middle two None for
        # requests that never seated / never produced a token (TTFT
        # histograms therefore exclude never-prefilled requests by
        # construction)
        self.t_submitted: Optional[float] = None
        self.t_admitted: Optional[float] = None
        self.t_first_token: Optional[float] = None
        self.t_terminal: Optional[float] = None
        self._t_last_token: Optional[float] = None   # ITL bookkeeping
        self.error: Optional[BaseException] = None
        self.callback_error: Optional[BaseException] = None
        # drain/re-home bookkeeping (docs/serving.md "Elasticity &
        # degradation ladder"): how many generated tokens were folded
        # into ``prompt`` by checkpoint_seated (output_ids() is invariant
        # across the fold), the sampling RNG state captured at the
        # checkpoint, and which replica last queued the request
        self.rehomed = 0
        self.rng_state = None
        self.replica: Optional[int] = None
        self._cancelled = False
        self._cb_warned = False
        self._done = threading.Event()

    @property
    def finished(self) -> bool:
        return self.state == RequestState.DONE

    @property
    def terminal(self) -> bool:
        return self.state in RequestState.TERMINAL

    @property
    def cancelled(self) -> bool:
        return self._cancelled

    def cancel(self) -> bool:
        """Request cancellation.  Honored at the engine's next step
        boundary (the slot is retired and its pages returned); safe from
        any thread.  Returns False when the request is already terminal
        (nothing to cancel)."""
        if self.terminal:
            return False
        self._cancelled = True
        return True

    def wait(self, timeout: Optional[float] = None,
             raise_on_failure: bool = False) -> bool:
        """Block until the request reaches a TERMINAL state (not just
        DONE).  Returns True when terminal, False when the WAIT timed out
        — distinguishable from a failed request, whose wait returns True
        with ``state`` telling which terminal it hit and ``error``
        carrying the typed cause.  With ``raise_on_failure`` a non-DONE
        terminal re-raises that error here."""
        reached = self._done.wait(timeout)
        if raise_on_failure and reached and self.state != RequestState.DONE:
            err = self.error or ServingError(
                f"request {self.id} ended {self.state}")
            raise err
        return reached

    def output_ids(self) -> np.ndarray:
        """prompt + generated ids (the ``generate()`` convention)."""
        return np.concatenate([self.prompt,
                               np.asarray(self.tokens, np.int64)])

    def timestamps(self) -> dict:
        """The per-request SLO timestamps (monotonic seconds; None means
        the request never reached that stage)."""
        return {"submitted": self.t_submitted, "admitted": self.t_admitted,
                "first_token": self.t_first_token,
                "terminal": self.t_terminal}


class RequestQueue:
    """Thread-safe FIFO; ``submit`` may be called from any thread.

    ``max_depth`` bounds the queue: an over-limit ``submit`` raises the
    typed ``Overloaded`` error immediately (fail fast — the client backs
    off) instead of queueing unboundedly."""

    def __init__(self, max_depth: Optional[int] = None):
        self._q: deque = deque()
        self._lock = threading.Lock()
        self.max_depth = None if max_depth is None else int(max_depth)

    def submit(self, request: Request) -> Request:
        with self._lock:
            if self.max_depth is not None and len(self._q) >= self.max_depth:
                raise Overloaded(
                    f"queue full ({len(self._q)}/{self.max_depth}): "
                    "request shed — back off and retry")
            self._q.append(request)
        return request

    def pop(self) -> Optional[Request]:
        with self._lock:
            return self._q.popleft() if self._q else None

    def push_front(self, request: Request):
        with self._lock:
            self._q.appendleft(request)

    def remove_where(self, pred: Callable[[Request], bool]) -> List[Request]:
        """Remove and return every queued request matching ``pred``
        (queue sweep for cancelled/expired requests; preserves FIFO order
        of the survivors)."""
        with self._lock:
            kept, dropped = deque(), []
            for r in self._q:
                if pred(r):
                    dropped.append(r)
                else:
                    kept.append(r)
            self._q = kept
            return dropped

    @property
    def depth(self) -> int:
        with self._lock:
            return len(self._q)

    def __len__(self) -> int:
        return self.depth


# python-body execution counters (same invariant as models/generation):
# the step bodies run ONLY while tracing — frozen counters across N steps
# of request churn == the retrace-freedom proof.  One key since the fused
# step collapsed the prefill/decode phase pair ("draft" counts the
# speculative engine's draft-model fused step separately — the CI bound
# is <= 2 target + <= 2 draft programs, serving/speculative.py).
# Lock-guarded: a sharded cluster traces its dp replicas' steps on
# concurrent threads, and an interleaved `+=` losing an increment would
# let a genuinely-retracing step slip under the <= 2-per-replica gates.
_SERVE_TRACE_COUNTS = {"fused": 0, "draft": 0}
_SERVE_TRACE_LOCK = threading.Lock()


def _count_fused_trace():
    with _SERVE_TRACE_LOCK:
        _SERVE_TRACE_COUNTS["fused"] += 1


def _count_draft_trace():
    with _SERVE_TRACE_LOCK:
        _SERVE_TRACE_COUNTS["draft"] += 1

# registry label for each engine's counters/histograms (one process may
# host many engines; tests create dozens — the label keeps them distinct)
_ENGINE_SEQ = itertools.count()


def serve_trace_counts() -> dict:
    return dict(_SERVE_TRACE_COUNTS)


def reset_serve_trace_counts():
    _SERVE_TRACE_COUNTS["fused"] = 0
    _SERVE_TRACE_COUNTS["draft"] = 0


def _sample_per_slot(logits: Tensor, temperature: Tensor, top_p: Tensor,
                     top_k: Tensor, do_sample: Tensor,
                     generator=None) -> Tensor:
    """Next-token selection over [S, V] logits with PER-SLOT params (all
    traced [S] vectors) -> int64 [S].  Greedy rows take the raw argmax
    (bit-identical to ``generation.sample_tokens`` greedy); sampling rows
    apply temperature, then top-k (k-th sorted value as threshold;
    k <= 0 = off) and top-p (smallest probability-sorted prefix reaching
    mass p; 1.0 = off), then draw via Gumbel-argmax with a key split from
    ``generator`` — the global one by default; mesh-sharded engines pass
    their OWN (the donated key state would otherwise ping-pong between
    replica meshes and fail the next replica's dispatch with a
    device-mismatch)."""
    if generator is None:
        from ..ops.random import default_generator as generator

    key = generator.split()

    def fn(raw, t, p, k, ds):
        raw = raw.astype(jnp.float32)
        greedy = jnp.argmax(raw, axis=-1).astype(jnp.int64)
        v = raw.shape[-1]
        scaled = raw / jnp.clip(t, 1e-6, None)[:, None]
        srt = -jnp.sort(-scaled, axis=-1)                 # descending
        kk = jnp.clip(jnp.where(k > 0, k, v), 1, v).astype(jnp.int32)
        kth = jnp.take_along_axis(srt, (kk - 1)[:, None], axis=1)
        probs = jax.nn.softmax(srt, axis=-1)
        prev_mass = jnp.cumsum(probs, axis=-1) - probs
        keep = prev_mass < p[:, None]
        pth = jnp.min(jnp.where(keep, srt, jnp.float32(np.inf)),
                      axis=-1, keepdims=True)
        filt = jnp.where(scaled < jnp.maximum(kth, pth), _NEG, scaled)
        g = jax.random.gumbel(key, filt.shape, jnp.float32)
        sampled = jnp.argmax(filt + g, axis=-1).astype(jnp.int64)
        return jnp.where(ds, sampled, greedy)

    # fresh key closure every call: opt out of the eager op cache
    return dispatch.apply_nondiff(fn, logits, temperature, top_p, top_k,
                                  do_sample, _cacheable=False)


def _drop_seq_axis(logits: Tensor) -> Tensor:
    """logits [S, 1, V] (the fused step's PRE-GATHERED slot-output rows —
    the model gathers ``out_rows`` before its vocab projection, so only
    [S] rows are ever projected) -> [S, V].  Each row is a slot's OUTPUT
    token — its decode token, or the last prompt token of a prefill run
    completing this step.  Slots with no output this step point at row 0;
    the host ignores their sampled token/finiteness."""
    def fn(lg):
        return lg[:, -1, :]

    return dispatch.apply_nondiff(fn, logits)


def _slotwise_finite(logits: Tensor) -> Tensor:
    """Per-slot finiteness of [S, V] logits -> bool [S]: the PR-4 fused
    all-finite reduction (``checkpoint/sentry.tree_all_finite``) widened
    from one scalar to one flag per slot and fused INTO the compiled
    serving step — the sentry costs zero extra host syncs (the flags ride
    the same device->host transfer as the sampled tokens)."""
    def fn(lg):
        return jnp.isfinite(lg).all(axis=-1)

    return dispatch.apply_nondiff(fn, logits)


class _StepBox:
    """One supervised unit of work (see ``_StepWorker``)."""

    __slots__ = ("fn", "result", "error", "done", "abandoned", "cleanup",
                 "lock")

    def __init__(self, fn):
        self.fn = fn
        self.result = None
        self.error: Optional[BaseException] = None
        self.done = threading.Event()
        self.abandoned = False
        self.cleanup: Optional[Callable[[], None]] = None
        self.lock = threading.Lock()


class _StepWorker:
    """Watchdog executor: runs step thunks on one daemon thread so the
    caller can bound how long it waits.  A thunk that overruns the stall
    budget is ABANDONED — a wedged XLA dispatch cannot be cancelled, so
    the thread is left to finish (or never finish) on its own, the worker
    is marked dead (the engine spawns a fresh one), and the abandoned
    box's ``cleanup`` releases the orphaned device state once the zombie
    does return.  Thunks receive a ``cancelled()`` callable and must skip
    device dispatch once it reports True (fault-injected stalls exercise
    exactly this path)."""

    def __init__(self, name: str):
        self._q: _queue.Queue = _queue.Queue()
        self.dead = False
        self._t = threading.Thread(target=self._loop, daemon=True, name=name)
        self._t.start()

    def _loop(self):
        while True:
            box = self._q.get()
            if box is None:
                return
            try:
                box.result = box.fn(lambda: box.abandoned)
            except BaseException as e:  # noqa: BLE001 — surfaced to caller
                box.error = e
            with box.lock:
                box.done.set()
            if box.abandoned and box.cleanup is not None:
                try:
                    box.cleanup()
                except Exception:  # noqa: BLE001 — zombie cleanup best-effort
                    pass

    def shutdown(self):
        self._q.put(None)

    def run(self, fn, timeout: float,
            cleanup: Optional[Callable[[], None]] = None):
        box = _StepBox(fn)
        self._q.put(box)
        if not box.done.wait(timeout):
            with box.lock:
                if not box.done.is_set():
                    # genuine overrun: abandon the thunk.  The lock makes
                    # abandon-vs-finish atomic: either the worker published
                    # its result first (we harvest it below) or it will see
                    # abandoned=True and run the cleanup when it returns.
                    box.abandoned = True
                    box.cleanup = cleanup
                    self.dead = True
                    raise StepStalledError(
                        f"supervised step exceeded the stall budget "
                        f"({timeout:.3f}s); worker abandoned")
        if box.error is not None:
            raise box.error
        return box.result


class _Flight:
    """One fused step that is enqueued and not read yet: its plan, the
    plan's stats, its output still on the device (a slot's sampled token,
    or -1 where its logits were not finite), the watchdog budget it was
    dispatched under, whether its variant had never run before, and whether
    its predecessor was still unread when it was enqueued.

    It also keeps its own account, on ``time.perf_counter_ns`` (the
    tracer's clock): ``seq`` (the engine's count of flights), what it
    carried (``rows``, ``prefill_tokens``, ``decode_rows``, as
    ``_fold_plan_stats`` counts them), ``t_enq`` (taken as the enqueue call
    returned), ``t_ready`` (taken as the blocking read returned) and
    ``wait_ns`` (how long that read blocked), and three readings of an
    output's non-blocking ``is_ready()``: ``late``, the PREDECESSOR's output
    was complete when this flight's enqueue came (the device's queue was
    empty: a bubble precedes this step); ``drained``, the same of THIS
    flight when its successor's enqueue came; ``ready_at_read``, this
    flight was complete when its read began (the host, not the device,
    decided when the step "ended").  ``parent`` is the span open when it
    was enqueued (``serve.dispatch``; None with tracing off)."""

    __slots__ = ("work", "stats", "out", "budget", "first", "overlapped",
                 "seq", "rows", "prefill_tokens", "decode_rows", "t_enq",
                 "t_ready", "wait_ns", "late", "drained", "ready_at_read",
                 "parent")

    def __init__(self, work, stats, out, budget, first, overlapped,
                 seq, late, parent):
        self.t_enq = time.perf_counter_ns()
        self.work, self.stats, self.out = work, stats, out
        self.budget, self.first, self.overlapped = budget, first, overlapped
        self.seq, self.late, self.parent = seq, late, parent
        self.rows = stats["n_tokens"]
        self.prefill_tokens = sum(w.count for w in work
                                  if w.kind == "prefill")
        self.decode_rows = self.rows - self.prefill_tokens
        self.drained = self.ready_at_read = False
        self.t_ready = self.wait_ns = 0

    def ready(self) -> bool:
        """Whether the output is complete on the device; never blocks."""
        return self.out._value.is_ready()


class ServingEngine:
    """Continuous-batching front end over a model exposing the paged-cache
    contract: ``config`` (``head_dim``, ``max_position_embeddings``,
    ``num_heads`` or ``num_key_value_heads``), ``new_paged_kv_cache(num_pages,
    page_size, dtype)`` and ``_paged_lm_logits(ids, cache, page_tables,
    positions, ragged_plan=, out_rows=, lora=)``.  A model names the modes
    its paged path lacks in ``serving_unsupported`` (they are refused
    typed, :class:`UnsupportedServingMode`); what its cache counts on the
    device (``cache.counts()``) is merged into :meth:`metrics`.

    ``num_pages`` defaults to full capacity (every slot can hold
    ``max_context`` tokens, plus the null page); size it DOWN to
    oversubscribe HBM — admission then backpressures on pool occupancy,
    not just on free slots.

    Fault-containment knobs (all optional; docs/serving.md):

    - ``stall_budget_s`` — supervise step dispatch with a watchdog; a
      stalled step fails only the seated requests and the engine rebuilds
      and keeps serving.  None (default) dispatches inline.
    - ``max_queue_depth`` / ``max_queue_wait_s`` — bounded queue + queue
      -wait shedding (typed ``Overloaded``).
    - ``readmission_backoff_s`` / ``backoff_max_s`` — exponential
      re-admission backoff after a recovery (reset by a clean step).
    """

    def __init__(self, model, *, num_slots: int = 4,
                 page_size: int = 128, max_context: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 cache_dtype: str = "bfloat16",
                 prefill_token_budget: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 stall_budget_s: Optional[float] = None,
                 compile_budget_s: float = 300.0,
                 max_queue_depth: Optional[int] = None,
                 max_queue_wait_s: Optional[float] = None,
                 readmission_backoff_s: float = 0.05,
                 backoff_max_s: float = 5.0,
                 mesh=None, lora=None, prefix_cache: bool = False,
                 kv_dtype: Optional[str] = None,
                 weight_dtype: Optional[str] = None,
                 role: Optional[str] = None):
        if not (hasattr(model, "new_paged_kv_cache")
                and hasattr(model, "_paged_lm_logits")):
            raise TypeError(
                "ServingEngine serves a model with the paged-cache contract: "
                "new_paged_kv_cache(num_pages, page_size, dtype) and "
                "_paged_lm_logits(ids, cache, page_tables, positions, "
                "ragged_plan=, out_rows=, lora=); "
                f"{type(model).__name__} lacks "
                + " and ".join(n for n in ("new_paged_kv_cache",
                                           "_paged_lm_logits")
                               if not hasattr(model, n)))
        cfg = model.config
        refuse_unsupported(
            model, lora=lora is not None,
            mp=mesh is not None and _srv_mesh.mp_size(mesh) > 1,
            kv_int8=str(kv_dtype or cache_dtype) == "int8",
            weight_int8=weight_dtype is not None,
            disagg=role not in (None, "colocated"),
            prefix_cache=bool(prefix_cache))
        # disaggregated serving (serving/disagg.py): the replica's role
        # ("prefill" | "decode" | "colocated").  Passing it explicitly
        # adds a ``role`` label to every per-engine metric child (the
        # per-role SLO breakdown the observability docs table lists);
        # None keeps the historical label set for standalone engines.
        self.role = role or "colocated"
        self._role_label = {} if role is None else {"role": str(role)}
        # quantized serving (docs/serving.md "Quantized serving"):
        # ``kv_dtype`` is the preferred name for the pool dtype (wins
        # over the historical ``cache_dtype`` when both are given) —
        # "int8" stores pool pages quantized with per-(page, head)
        # absmax scale buffers; ``weight_dtype="int8"`` PTQs the model's
        # decode projections in place before the steps compile.
        if kv_dtype is not None:
            cache_dtype = kv_dtype
        if weight_dtype is not None:
            if str(weight_dtype) != "int8":
                raise ValueError(
                    f"weight_dtype={weight_dtype!r}: only 'int8' (or None "
                    "for the model's own weights) is supported")
            from ..quantization.int8 import quantize_for_serving

            quantize_for_serving(model)
        # quantized engines get a distinct program name ("fused_step_int8")
        # so the graph-lint / cost registries (tools/graph_lint.py serve
        # target) report the int8 dequant-epilogue program separately from
        # the fp32/bf16 one instead of collapsing both under "fused_step"
        self._program_tag = ("_int8" if (str(cache_dtype) == "int8"
                                         or weight_dtype is not None)
                             else "")
        # multi-tenant LoRA (serving/lora.py): per-request adapter-page
        # ids ride the packed step input; the pool's slab Tensors are
        # captured step state (register/evict never retrace)
        self.lora = lora
        # mesh-sharded replica (docs/serving.md "Sharded serving"): the
        # page pool is sharded per-head over the mesh's 'mp' axis, step
        # inputs land replicated on the replica mesh, and the fused step
        # compiles ONCE as an SPMD program over it.  The model's weights
        # must already be committed to the same mesh
        # (serving_mesh.shard_model_for_serving) — ShardedServingEngine
        # does both per dp replica.
        self.mesh = mesh
        self._mp = _srv_mesh.mp_size(mesh) if mesh is not None else 1
        # the pool's heads: K/V's, where the model has fewer of them than
        # of query heads (the launch folds a K/V head's queries into rows)
        pool_heads = getattr(cfg, "num_key_value_heads", None) or cfg.num_heads
        if self._mp > 1:
            # hard precondition, typed: an indivisible head axis cannot be
            # sharded at all (GL002-formatted, not a shard_map crash)
            _srv_mesh.validate_head_sharding(pool_heads, self._mp)
        max_context = int(max_context or cfg.max_position_embeddings)
        if max_context > cfg.max_position_embeddings:
            raise ValueError(
                f"max_context={max_context} exceeds max_position_embeddings="
                f"{cfg.max_position_embeddings}")
        if max_context % page_size:
            raise ValueError(
                f"max_context={max_context} must be a multiple of "
                f"page_size={page_size}")
        # the per-step prefill token budget (``prefill_chunk`` accepted as
        # the historical alias): how many prompt tokens may piggyback on
        # one fused step alongside every decode slot.  Any value >= 1 is
        # legal — runs never pad past a slot's table because every real
        # token's position sits inside its admission-reserved pages.
        if prefill_token_budget is None:
            prefill_token_budget = prefill_chunk
        prefill_token_budget = int(prefill_token_budget
                                   or min(page_size, max_context))
        if prefill_token_budget < 1:
            raise ValueError(
                f"prefill_token_budget={prefill_token_budget} must be >= 1")
        max_pages_per_slot = max_context // page_size
        if num_pages is None:
            num_pages = num_slots * max_pages_per_slot + 1  # + null page
        self.model = model
        self.num_slots = int(num_slots)
        self.page_size = int(page_size)
        self.max_context = max_context
        self.prefill_token_budget = prefill_token_budget
        self.cache_dtype = str(cache_dtype)
        self.num_pages = int(num_pages)
        # a model whose cache keeps state by slot (SlotStateCache) says so:
        # the cache is sized by the slots and a step's longest run, packs
        # its own fields beside the plan and is told of every harvest
        self._slot_state = bool(getattr(model, "slot_resident_state", False))
        self.cache = self._new_pool()
        self.allocator = BlockAllocator(num_pages)
        self.scheduler = AdmissionScheduler(num_slots, max_pages_per_slot,
                                            page_size, self.allocator)
        # global prefix cache (serving/prefix_cache.py, opt-in): completed
        # full pages are radix-indexed by their token-id chunks so a later
        # admission splices the longest cached prefix into its page table
        # and prefills only the uncached tail.  Installing it also hooks
        # the allocator's pressure reclaimer (LRU eviction of refcount-0
        # cache pages BEFORE admission backpressures).
        self.prefix_cache = None
        if prefix_cache:
            self.prefix_cache = PrefixCache(self.allocator, self.page_size)
            self.scheduler.prefix_cache = self.prefix_cache
        self.queue = RequestQueue(max_depth=max_queue_depth)
        self._lock = threading.RLock()
        self._closed = False
        # drain lifecycle (docs/serving.md "Elasticity & degradation
        # ladder"): while draining, admission stops and submit sheds
        # typed; seated requests keep stepping until completion or a
        # checkpoint_seated() eviction re-homes them elsewhere
        self._draining = False

        # fixed fused-step geometry: the flat token axis, block count, and
        # work-list length are engine constants (retrace-freedom); the
        # token-block size comes from the autotune table for this pool
        # specialization (ops/pallas_kernels/ragged_paged_attention.py) —
        # keyed on the LOCAL (post-shard) head count under mp sharding
        # (the row the kernel sees is the pool's: wider than a head where a
        # cache keeps K and V side by side)
        self.head_dim = int(getattr(self.cache, "row_dim", cfg.head_dim))
        local_heads = int(getattr(self.cache, "num_heads",
                                  pool_heads)) // self._mp
        self.token_block = ragged_token_block(
            self.page_size, self.head_dim, self.cache_dtype,
            local_heads=local_heads if self._mp > 1 else None)
        # what the ragged launch makes of this geometry (it asks the same
        # function of the same shapes): the heads of a page one work item
        # moves a grid step, and so the grid steps an item costs a chip
        self.ragged_heads_per_block = ragged_head_block(
            local_heads, self.page_size, self.head_dim, self.cache_dtype)
        self._grid_steps_per_item = local_heads // self.ragged_heads_per_block
        # and the rows of a WIDE block, which a run longer than one narrow
        # block is cut into (a pool head's query heads share its rows)
        query_heads = (getattr(cfg, "num_attention_heads", None)
                       or cfg.num_heads)
        self.wide_block = ragged_wide_block(
            local_heads, query_heads // (local_heads * self._mp),
            self.page_size, self.head_dim, self.cache_dtype,
            self.token_block)
        # sampling RNG: the global generator single-chip (bit-compat with
        # generate()); a PRIVATE stream per mesh-sharded engine — the
        # donated key state commits to the replica mesh, and one shared
        # key bouncing between replicas' meshes would fail dispatch
        self._generator = None
        if mesh is not None:
            from ..ops.random import Generator, default_generator

            self._generator = Generator(
                int(np.asarray(default_generator.split())[0]) % (2 ** 31))
            # materialize the key NOW: a lazily-created key Tensor inside
            # the fused step's abstract scout would read as trace-created
            # state and break the scout's creation-ordinal matching
            self._generator._state  # noqa: B018 — lazy-init side effect
        # blocks: a slot contributes ONE run per step — a decode token
        # (one block) or a prefill run of c tokens (1 + (c-1)//qb blocks).
        # With P prefill runs sharing the budget, total blocks <=
        # (D + P) + (budget - P)//qb <= num_slots + budget//qb — tight,
        # with no double count for decode-vs-prefill (a slot is never
        # both in one step).  Subclasses override _step_geometry (the
        # speculative engine's verify runs are k+1 tokens per decode
        # slot).
        self._t_max, self._nb_max = self._step_geometry()
        # (the narrow plan's bounds hold whatever the blocks: a wide block
        # never lists more items than the narrow blocks of its rows would)
        self._wl_max = self._nb_max * max_pages_per_slot
        self._nbw_max = ragged_wide_capacity(
            self._t_max, self.token_block, self.wide_block)
        # the pool write's list (ops/pallas_kernels/pool_write.py): one
        # item a tile group of g positions a run touches; a slot
        # contributes one run a step.  (A pool whose pages g does not
        # divide keeps the row scatter and never reads the list: it is
        # built to the largest group that does.)
        self._write_group = math.gcd(pool_write_group(self.cache_dtype),
                                     self.page_size)
        # the constant capacities every step's plan is built to: the
        # keywords build_ragged_plan and ragged_plan_shapes share
        self._plan_geometry = dict(
            token_block=self.token_block, t_max=self._t_max,
            nb_max=self._nb_max, wl_max=self._wl_max,
            wide_block=self.wide_block, nbw_max=self._nbw_max,
            wlw_max=self._nbw_max * max_pages_per_slot,
            write_group=self._write_group,
            wr_max=ragged_write_capacity(self._t_max, self._write_group,
                                         num_slots))

        # fault-containment state
        self.stall_budget_s = (None if stall_budget_s is None
                               else float(stall_budget_s))
        # first call of a step variant compiles (seconds, not millis) —
        # the watchdog must not misread XLA compilation as a stall
        self.compile_budget_s = max(float(compile_budget_s),
                                    self.stall_budget_s or 0.0)
        self.max_queue_wait_s = (None if max_queue_wait_s is None
                                 else float(max_queue_wait_s))
        self.readmission_backoff_s = float(readmission_backoff_s)
        self.backoff_max_s = float(backoff_max_s)
        self._backoff_s = self.readmission_backoff_s
        self._admit_after = 0.0          # monotonic; re-admission gate
        self._worker: Optional[_StepWorker] = None
        # test-only fault injection: fn(point, ctx) may raise, stall, or
        # mutate ctx to simulate a fault at that point of the step pipeline
        # (paddle_tpu/faults.py; same discipline as checkpoint/manager.py)
        self._fault_hook: Optional[Callable] = None

        # the step that is enqueued and not harvested yet (None: the
        # pipeline is empty), and the stand-in for a predecessor's output
        # that a step with none takes (made on first use)
        self._inflight: Optional[_Flight] = None
        self._no_prev: Optional[Tensor] = None
        # flights made so far (the next one's ``seq``), and the (seq,
        # t_ready) of the last one landed
        self._flights = 0
        self._last_landed: Tuple[int, Optional[int]] = (-1, None)

        # host mirrors shipped to the jitted step each call (fixed shapes)
        self._tokens = np.zeros((num_slots,), np.int64)
        # per-slot adapter page (0 = null adapter) + the seated adapter
        # NAME pinning the page's refcount until retirement
        self._adapter = np.zeros((num_slots,), np.int32)
        self._adapter_name: List[Optional[str]] = [None] * num_slots
        self._temp = np.ones((num_slots,), np.float32)
        self._top_p = np.ones((num_slots,), np.float32)
        self._top_k = np.zeros((num_slots,), np.int32)
        self._do_sample = np.zeros((num_slots,), bool)
        # all int32 step inputs (tables/positions/out_rows + the ragged
        # plan's arrays, work list and write list) ship as ONE packed flat
        # vector: one host->device transfer per step instead of one an
        # array — at serving step rates the per-array device_put overhead
        # dominates the tiny payloads.  Layout is fixed at construction;
        # the compiled step slices it back apart with static offsets (free
        # under XLA).
        mp_ = max_pages_per_slot
        self._pack_layout = [
            ("tables", (self._t_max, mp_)),
            ("positions", (self._t_max,)),
            ("out_rows", (self.num_slots,)),
            # per flat row, the slot whose token sampled by the step ahead
            # (still on the device) is this row's id, or -1: the host's id
            ("id_src", (self._t_max,)),
            *ragged_plan_shapes(**self._plan_geometry),
        ]
        if self.lora is not None:
            # per-token adapter-page ids (0 = null adapter) — only when a
            # pool is attached, so the lora-less step program is unchanged
            self._pack_layout.append(("adapters", (self._t_max,)))
        self._pack_layout.extend(self._extra_pack_fields())
        self._pack_slices = {}
        off = 0
        for name, shp in self._pack_layout:
            n = int(np.prod(shp))
            self._pack_slices[name] = (off, off + n, shp)
            off += n
        self._pack_total = off
        # the sampling vectors only change at admission/retirement: cache
        # their device copies and re-upload only when a mirror mutates
        self._sampling_cache = None

        # cumulative totals — migrated onto the process-wide telemetry
        # registry (docs/observability.md): each key is the
        # ``serving_<key>`` counter labeled with this engine's id, and
        # the CounterSet facade keeps the historical ``+=``/``dict()``
        # idiom bit-compatible (metrics() reads the same ints as ever)
        self._engine_label = {"engine": str(next(_ENGINE_SEQ)),
                              **self._role_label}
        self._totals = _tmetrics.CounterSet(
            "serving", {"steps": 0, "tokens": 0, "admitted": 0,
                        "completed": 0,
                        # fused-step accounting: exact dispatch count (the
                        # bench roofline denominator), prefill tokens that
                        # piggybacked, and the ragged grid-occupancy
                        # numerators/denominators (see metrics())
                        "fused_steps": 0, "prefill_tokens": 0,
                        # fused steps enqueued while their predecessor was
                        # still unread, and runs whose results were dropped
                        # because only the predecessor's results could say
                        # their slot was gone (docs/serving.md)
                        "overlapped_steps": 0, "voided_rows": 0,
                        # the flights' own account (docs/serving.md "One
                        # step in flight"): fused steps enqueued behind a
                        # predecessor that had already finished, the time
                        # the tick was blocked reading a step's tokens, and
                        # the time from a step's enqueue to its tokens
                        "host_late_steps": 0, "land_wait_ns": 0,
                        "flight_ns": 0,
                        "work_items": 0, "work_capacity": 0,
                        "launched_items": 0, "launched_grid_steps": 0,
                        # the pool write's items (tile groups the steps'
                        # real tokens touched; see metrics())
                        "write_items": 0,
                        "block_rows": 0, "block_row_capacity": 0,
                        # of those, what rode WIDE blocks (a run longer
                        # than one narrow block): their items, their rows
                        "wide_items": 0, "wide_block_rows": 0,
                        # host-packing padding cost in GL002's units
                        # (analysis/cost_model.ragged_padding_waste): block
                        # rows that carried no real token and the MXU flops
                        # the launch spent on them anyway
                        "padded_rows": 0, "padded_flops": 0,
                        # fault-containment counters (admission path SLOs)
                        "failed": 0, "cancelled": 0, "timed_out": 0,
                        "shed": 0, "quarantined": 0, "step_retries": 0,
                        "recoveries": 0, "rebuilds": 0,
                        # requests checkpointed off this engine by a
                        # drain / replica loss (they terminate on the
                        # replica that re-seats them, not here)
                        "drained": 0,
                        # disaggregated hand-off (serving/disagg.py):
                        # requests whose filled pages left this replica
                        # for a decode replica / arrived from a prefill
                        # replica via PageTransfer
                        "transferred_out": 0, "transferred_in": 0},
            labels=self._engine_label)
        # per-request SLO histograms (seconds, log-bucketed): TTFT and
        # e2e are measured FROM SUBMISSION (queue time included — the
        # client-visible latency), queue_wait is submission->seating,
        # ITL is the gap between consecutive emitted tokens of one
        # request.  Surfaced as p50/p95/p99 in metrics()["slo"],
        # serving_bench sweep lines, and bench.py's *_ttft_ms/_itl_ms
        # JSON keys.
        reg = _tmetrics.registry()
        self._slo = {
            "ttft": reg.histogram(
                "serving_ttft_seconds",
                "submission -> first generated token (queue included)"),
            "itl": reg.histogram(
                "serving_itl_seconds",
                "inter-token latency between consecutive emitted tokens"),
            "queue_wait": reg.histogram(
                "serving_queue_wait_seconds",
                "submission -> seated in a decode slot"),
            "e2e": reg.histogram(
                "serving_e2e_seconds",
                "submission -> terminal state (all terminals)"),
        }
        self._slo = {k: h.labels(**self._engine_label)
                     for k, h in self._slo.items()}
        self._gauges = {
            name: reg.gauge(f"serving_{name}").labels(**self._engine_label)
            for name in ("queue_depth", "active_slots", "pages_used",
                         "pool_occupancy")}
        # prefix-cache counters (docs/serving.md "Prefix cache"): hit /
        # partial-hit / miss classified per successful admission, eviction
        # synced from the cache's own ledger (evictions fire inside the
        # allocator's pressure reclaimer, outside any engine code path).
        # Created even with the cache disabled so metrics() keys — and the
        # sharded engine's cross-replica sums — are unconditionally present
        self._prefix_totals = _tmetrics.CounterSet(
            "serving_prefix", {"hits_total": 0, "misses_total": 0,
                               "partial_hits_total": 0,
                               "evictions_total": 0},
            labels=self._engine_label)
        self._prefix_hist = reg.histogram(
            "serving_prefix_cached_tokens",
            "prompt tokens served from the prefix cache per admission",
        ).labels(**self._engine_label)
        self._step_emitted = 0           # tokens emitted in the current step
        self._step_prefill = 0           # prompt tokens dispatched in it
        self._last_metrics: dict = {}
        self._last_occupancy = (0.0, 0.0)   # (grid, q-row) of the last step

        self._build_steps()

    def _step_geometry(self) -> Tuple[int, int]:
        """(t_max, nb_max): the fixed flat-token-axis length and block
        count of the fused step.  Overridden by the speculative engine,
        whose decode slots run k+1-token verify runs."""
        return (self.num_slots + self.prefill_token_budget,
                self.num_slots
                + self.prefill_token_budget // self.token_block)

    def _extra_pack_fields(self) -> list:
        """Extra (name, shape) int32 fields appended to the packed step
        input: what a cache with slot-resident state ships beside the plan
        (its second work list, each row's slot, each run's state rows).
        A subclass hook too (the speculative engine adds the draft tokens
        and per-slot draft counts)."""
        if not self._slot_state:
            return []
        return self.cache.pack_fields(**self._plan_geometry)

    def _new_pool(self):
        """A fresh page pool, committed to the replica mesh (per-head
        sharded over 'mp') when this engine is mesh-sharded.  Used at init
        and by ``_rebuild``."""
        by_slot = (dict(num_slots=self.num_slots,
                        max_run=self.prefill_token_budget)
                   if self._slot_state else {})
        cache = self.model.new_paged_kv_cache(self.num_pages, self.page_size,
                                              dtype=self.cache_dtype,
                                              **by_slot)
        if self.mesh is not None:
            _srv_mesh.shard_paged_cache(cache, self.mesh)
        return cache

    def _host_to_dev(self, arr: np.ndarray) -> Tensor:
        """Host step input -> device Tensor: replicated onto the replica
        mesh when sharded (one explicit placement instead of relying on
        jit to resolve an uncommitted array against a submesh program),
        the default device otherwise."""
        if self.mesh is None:
            return to_tensor(arr)
        return Tensor(_srv_mesh.replicate_to_mesh(
            np.ascontiguousarray(arr), self.mesh))

    def _build_steps(self):
        """Compile-on-first-use fused-step closures over the CURRENT page
        pool.  Called at init and again by ``_rebuild`` after a
        stalled/crashed step: fresh closures capture the fresh pool
        Tensors, so an abandoned zombie step's eventual write-backs land
        in the ORPHANED old Tensors, never in live state."""
        model, cache = self.model, self.cache
        from ..jit.api import to_static

        # two compiled variants, chosen host-side per step: the greedy
        # one is a pure argmax (no full-vocab sort / softmax / gumbel, no
        # RNG-state traffic) — all-greedy traffic, the common serving
        # case, never pays the sampling machinery.  Mixed batches take
        # the sampling variant, whose per-slot `do_sample` vector still
        # reproduces greedy rows bit-exactly.  Both variants fold the
        # fused per-slot finiteness flags (the NaN sentry) gathered at
        # each slot's output row into their one output — zero extra host
        # syncs — and take the previous step's output as ``prev``.
        slices = [self._pack_slices[name] for name, _ in self._pack_layout]

        def _unpack(p):
            return tuple(jnp.reshape(p[a:b], shp) for a, b, shp in slices)

        mesh = self.mesh
        generator = self._generator
        lora_pool = self.lora
        n_plan = len(RAGGED_PLAN_FIELDS)
        n_lora = int(lora_pool is not None)
        by_slot = ([name for name, _ in self._extra_pack_fields()]
                   if self._slot_state else [])

        def _chain_ids(ids, src, prev):
            # a chained row continues the token the step ahead sampled for
            # slot ``src``, which no host has read (-1 there: not finite,
            # and the row is void)
            last = jnp.maximum(prev, 0)[jnp.clip(src, 0, None)]
            return jnp.where(src >= 0, last.astype(ids.dtype),
                             ids[:, 0])[:, None]

        def _token_or_void(tok, fin):
            return jnp.where(fin, tok.astype(jnp.int64), -1)

        def _mk_fused(with_sampling):
            def fused_step(ids, packed, temp, top_p, top_k, do_sample, prev):
                _count_fused_trace()
                with jax.named_scope("serve.unpack"):
                    (token_tables, positions, out_rows, id_src, *rest) = \
                        dispatch.apply_nondiff(_unpack, packed)
                    ids = dispatch.apply_nondiff(_chain_ids, ids, id_src,
                                                 prev)
                plan = tuple(rest[:n_plan])
                lora_in = None
                if lora_pool is not None:
                    # (pool, per-token adapter-page ids): the slab Tensors
                    # are CAPTURED state — registration mutates them in
                    # place, so tenants come and go with zero retraces
                    lora_in = (lora_pool, rest[n_plan])
                # the serving-mesh context is TRACE-time state: the paged
                # attention path reads it to shard_map the scatter+attend
                # per head shard over 'mp' (no-op for mesh=None)
                # what a cache with slot-resident state packed, by name
                extra = ({"slot_state": dict(zip(
                    by_slot, rest[n_plan + n_lora:]))} if by_slot else {})
                with _srv_mesh.activate(mesh), dispatch.no_grad():
                    logits = model._paged_lm_logits(ids, cache,
                                                    token_tables, positions,
                                                    ragged_plan=plan,
                                                    out_rows=out_rows,
                                                    lora=lora_in, **extra)
                    with jax.named_scope("serve.sample"):
                        rows = _drop_seq_axis(logits).astype("float32")
                        fin = _slotwise_finite(rows)
                        if with_sampling:
                            tok = _sample_per_slot(rows, temp, top_p, top_k,
                                                   do_sample,
                                                   generator=generator)
                        else:
                            tok = ops.argmax(rows, axis=-1)
                        # ONE output: the host reads tokens and finiteness
                        # in one transfer, the next step reads it in place
                        out = dispatch.apply_nondiff(_token_or_void, tok,
                                                     fin)
                return out

            fused_step.__name__ = "fused_step" + self._program_tag
            return fused_step

        self._fused_greedy = to_static(_mk_fused(False))
        self._fused_sample = to_static(_mk_fused(True))

    # -- submission --------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int = 32, *,
               sampling: Optional[SamplingParams] = None,
               eos_token_id: Optional[int] = None,
               on_token: Optional[Callable] = None,
               deadline_s: Optional[float] = None,
               adapter: Optional[str] = None) -> Request:
        """Queue a request; returns immediately.  Validation happens here
        so the step loop can never hit an unseatable request.  A full
        bounded queue raises the typed ``Overloaded`` error (load shed);
        ``deadline_s`` bounds the request's total lifetime — queued or
        seated, it is retired TIMED_OUT at the first step boundary past
        the deadline."""
        self._check_open()
        if self._draining:
            # typed, not counted as a capacity shed: the placement layer
            # skips draining replicas before probing their submit, so a
            # direct hit here is a client racing the drain
            raise Overloaded(
                "engine draining: admission stopped — submit elsewhere")
        prompt = np.asarray(prompt, np.int64).reshape(-1)
        if prompt.size < 1:
            raise ValueError("prompt must hold at least one token")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError(f"deadline_s must be > 0, got {deadline_s}")
        total = prompt.size + int(max_new_tokens)
        if total > self.max_context:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds max_context {self.max_context}")
        if self.scheduler.pages_needed(total) > self.allocator.capacity:
            raise ValueError(
                f"request needs {self.scheduler.pages_needed(total)} pages "
                f"but the pool holds only {self.allocator.capacity}")
        if adapter is not None and self.lora is None:
            raise ValueError(
                f"request names adapter {adapter!r} but the engine has no "
                "LoRA pool (pass lora=LoRAAdapterPool(...) at construction)")
        req = Request(prompt, max_new_tokens, sampling=sampling,
                      eos_token_id=eos_token_id, on_token=on_token,
                      deadline_s=deadline_s)
        req.adapter = adapter
        now = time.monotonic()
        req.submit_t = now
        req.t_submitted = now
        if req.deadline_s is not None:
            req.deadline = now + req.deadline_s
        try:
            return self.queue.submit(req)
        except Overloaded:
            # submit() runs on any client thread, outside the step lock:
            # the atomic inc, not the racy `+=` read-modify-write
            self._totals.inc("shed")
            raise

    # -- the serving loop --------------------------------------------------
    def step(self) -> dict:
        """One scheduler tick: reap cancelled/expired requests, admit what
        fits (admission only reserves pages and seats — no dispatch), plan
        and pack ONE fused mixed prefill/decode step over every seated
        slot's work and ENQUEUE it behind the step already in flight
        (supervised, retried once); then wait for that earlier step's
        tokens, harvest them (finiteness-checked) and retire finished
        requests (their pages free immediately).  A tick with nothing in
        flight enqueues and returns; one with nothing to enqueue harvests
        what is in flight.  A crashed or stalled step never escapes: the
        implicated requests end FAILED and the engine recovers.  Returns
        this tick's metrics; counters move in harvest only."""
        with self._lock, self._eval_mode(), \
                _ttrace.span("serve.step") as step_span:
            # under the lock: close() also serializes on it, so a racing
            # close cannot delete the pool between this check and the
            # fused dispatch
            self._check_open()
            t0 = time.perf_counter()
            self._step_emitted = 0
            self._step_prefill = 0
            with _ttrace.span("serve.plan"):
                now = time.monotonic()
                self._reap(now)
                self._admit(now)
                work = self.scheduler.plan_step(self.prefill_token_budget,
                                                self._ahead)
            if work or self._inflight is not None:
                self._dispatch_step(work)
            with _ttrace.span("serve.commit"):
                m = self._commit_step_metrics(t0)
                if step_span is not None:
                    # whether the HARVESTED step carried a prompt chunk:
                    # what engine.step_ms_decode_only/_with_prefill group by
                    step_span.set(prefill_tokens=self._step_prefill)
                return m

    def _dispatch_step(self, work):
        """One tick of the pipeline: pack ``work`` and enqueue it
        (supervised, retried once) behind the step in flight, then wait for
        THAT step and harvest it.  ``work`` was planned as if the step in
        flight succeeds; what its results contradict is void at the next
        harvest.  Overridden by the speculative engine (draft propose
        phase + verify dispatch, nothing ever ahead: the accepted count
        decides the next positions); the recovery semantics are the
        containment contract both share."""
        prev, nxt = self._inflight, None
        if work:
            # the step's flat inputs are a pure function of the host
            # mirrors and of the plan in flight; the mirrors only advance
            # in harvest — a retry after a transient failure enqueues the
            # SAME idempotent step
            with _ttrace.span("serve.pack", seq=self._flights):
                inputs, stats = self._build_step_inputs(work)
            try:
                # the nested jit.fused_step span carries the program's
                # CostReport digest (per compiled entry, so greedy and
                # sampling variants each report their own cost)
                with _ttrace.span("serve.dispatch", seq=self._flights):
                    nxt = self._run_fused(work, stats, inputs, prev)
            except StepBuildError:
                raise
            except Exception as e:  # noqa: BLE001 — containment boundary
                # the step in flight was enqueued whole: it lands first,
                # then every request still seated is implicated
                self._drain()
                prev = None
                self._contain(e)
        self._inflight = nxt
        if prev is not None:
            self._land(prev)

    @property
    def _ahead(self):
        """The plan of the step in flight (empty: nothing is)."""
        return self._inflight.work if self._inflight is not None else ()

    def _contain(self, e: BaseException):
        """A step failed past its retry, or stalled: every seated request
        is implicated, and the device state is rebuilt unless the fault
        provably fired before any device work."""
        stalled = isinstance(e, StepStalledError)
        self._recover(e, rebuild=stalled or not _state_intact(e),
                      stalled=stalled)

    def _drain(self):
        """Land the step in flight, if any: the pipeline is empty after."""
        flight, self._inflight = self._inflight, None
        if flight is not None:
            self._land(flight)

    def _settle(self):
        """:meth:`_drain` for callers outside a tick (``close``, the drain
        lifecycle): the tokens it emits are counted here, not by a tick's
        commit."""
        with self._lock:
            if self._inflight is None or self._closed:
                return
            with self._eval_mode():
                before = self._step_emitted
                self._drain()
                self._totals["tokens"] += self._step_emitted - before
                self._step_emitted = before

    def _land(self, flight: _Flight):
        """Wait for an enqueued step's tokens (ONE transfer; the read is
        tried once more after an exception) and harvest them.  A step whose
        read fails or stalls takes its successor with it — enqueued on top
        of state that cannot be trusted, it is dropped unread, every run of
        it void — and every seated request is implicated."""
        read = lambda cancelled: self._read_thunk(flight, cancelled)  # noqa: E731,E501
        try:
            try:
                got = self._supervised(read, flight.budget)
            except StepStalledError:
                raise
            except Exception as e:  # noqa: BLE001 — transient: read again
                if flight.first:
                    raise StepBuildError(
                        f"the fused step failed the first time it ran: "
                        f"{type(e).__name__}: {e}") from e
                self._totals["step_retries"] += 1
                got = self._supervised(read, flight.budget)
        except Exception as e:  # noqa: BLE001 — containment boundary
            ahead, self._inflight = self._inflight, None
            if ahead is not None:
                self._totals["voided_rows"] += len(ahead.work)
            if isinstance(e, StepBuildError):
                raise
            self._contain(e)
            return
        # exact count of fused program executions — bench.py's serving
        # roofline denominator (ticks with no seated work / failed
        # dispatches don't run one)
        self._totals["fused_steps"] += 1
        if flight.overlapped:
            self._totals["overlapped_steps"] += 1
        self._account_flight(flight)
        with _ttrace.span("serve.harvest", flight=flight.seq):
            self._harvest_fused(flight.work, flight.stats, *got)
        self._backoff_s = self.readmission_backoff_s

    def _account_flight(self, flight: _Flight):
        """A landed flight's stamps into the totals and, under a tracer,
        ONE ``serve.flight`` span from its enqueue to its tokens (its two
        ends lie in different ticks: ``Tracer.record_interval``).  A step
        with no predecessor in flight met an empty device because the
        engine was empty: the span says ``drained`` of it, the counter of
        steps the host was late for leaves it out."""
        totals = self._totals
        if flight.late:
            totals.inc("host_late_steps")
        totals.inc("land_wait_ns", flight.wait_ns)
        totals.inc("flight_ns", flight.t_ready - flight.t_enq)
        last_seq, last_ready = self._last_landed
        self._last_landed = (flight.seq, flight.t_ready)
        tracer = _ttrace._tracer
        if tracer is not None:
            # flights overlap their neighbours by one: two rows hold them
            tracer.record_interval(
                "serve.flight", flight.t_enq, flight.t_ready,
                parent=flight.parent, row=f"serve.flight.{flight.seq % 2}",
                seq=flight.seq, rows=flight.rows,
                prefill_tokens=flight.prefill_tokens,
                decode_rows=flight.decode_rows,
                overlapped=flight.overlapped,
                drained=flight.late or not flight.overlapped,
                ready_at_read=flight.ready_at_read, wait_ns=flight.wait_ns,
                prev_ready_ns=(last_ready if last_seq == flight.seq - 1
                               else None),
                landed_in=tracer.current_id())

    def _commit_step_metrics(self, t0: float) -> dict:
        """Fold the step's tallies into totals + gauges and build the
        per-step metrics dict (the ``serve.commit`` phase)."""
        dt = time.perf_counter() - t0
        emitted = self._step_emitted
        self._totals["steps"] += 1
        self._totals["tokens"] += emitted
        grid_occ, row_occ = self._last_occupancy
        sched = self.scheduler
        self._last_metrics = {
            "active_slots": sched.active_slots,
            "queue_depth": self.queue.depth,
            "pages_used": self.allocator.used_pages,
            "pages_capacity": self.allocator.capacity,
            "occupancy": sched.occupancy,
            "tokens_this_step": emitted,
            "tokens_per_sec": emitted / dt if dt > 0 else 0.0,
            # ragged-launch occupancy of the last dispatched step:
            # real work items / fixed work-list length, and real query
            # rows / packed block rows (the MXU-side figure)
            "grid_occupancy": grid_occ,
            "q_row_occupancy": row_occ,
            # fault counters ride every step's metrics (admission SLOs)
            "failed": self._totals["failed"],
            "cancelled": self._totals["cancelled"],
            "timed_out": self._totals["timed_out"],
            "shed": self._totals["shed"],
            "recoveries": self._totals["recoveries"],
        }
        self._sync_prefix_counters()
        g = self._gauges
        g["queue_depth"].set(self._last_metrics["queue_depth"])
        g["active_slots"].set(self._last_metrics["active_slots"])
        g["pages_used"].set(self._last_metrics["pages_used"])
        g["pool_occupancy"].set(self._last_metrics["occupancy"])
        return dict(self._last_metrics)

    def _run_fused(self, work, stats, inputs,
                   behind: Optional[_Flight]) -> _Flight:
        """Enqueue the fused step under the watchdog, behind ``behind`` (the
        step in flight, whose output it reads on the device); one
        immediate retry on a (transient) exception.  A stall is never
        retried — the worker is already wedged."""
        fused = (self._fused_sample if self._do_sample.any()
                 else self._fused_greedy)
        budget = self._budget_for([fused])
        never_ran = not fused.code_cache
        if behind is not None:
            prev = behind.out
        else:
            if self._no_prev is None:
                self._no_prev = self._host_to_dev(
                    np.zeros((self.num_slots,), np.int64))
            prev = self._no_prev
        thunk = lambda cancelled: self._enqueue_thunk(  # noqa: E731
            fused, inputs, cancelled, (prev,))
        try:
            out, built = self._supervised(thunk, budget)
        except StepStalledError:
            raise
        except Exception as e:  # noqa: BLE001 — transient device errors retry once
            if never_ran:
                raise StepBuildError(
                    f"{fused.__name__} failed on its first dispatch: "
                    f"{type(e).__name__}: {e}") from e
            self._totals["step_retries"] += 1
            out, built = self._supervised(thunk, budget)
        if built is not None:
            # commit on THIS thread, under the step lock: _supervised only
            # returns results of non-abandoned runs, so a zombie's build
            # never lands here
            self._sampling_cache = built
        tracer = _ttrace._tracer
        flight = _Flight(work, stats, out, budget, never_ran,
                         behind is not None, self._flights,
                         behind is not None and behind.drained,
                         tracer.current_id() if tracer is not None else None)
        self._flights += 1
        return flight

    def _budget_for(self, static_fns) -> Optional[float]:
        """Watchdog budget for one supervised dispatch: the stall budget
        per compiled program, or the much larger compile budget when the
        variant the dispatch will call has not compiled yet — XLA
        compilation is slow, not stalled."""
        if self.stall_budget_s is None:
            return None
        if any(not f.code_cache for f in static_fns):
            return max(self.compile_budget_s, self.stall_budget_s)
        return self.stall_budget_s

    def _build_step_inputs(self, work) -> Tuple[tuple, dict]:
        """Flatten one tick's :class:`StepWork` plan into the fused step's
        fixed-shape numpy inputs: the flat token list (decode tokens from
        the last-sampled mirrors, or named by slot in ``id_src`` where the
        step in flight samples them; prefill tokens from each slot's
        pending prompt), per-token positions and page-table rows, each
        slot's output-row index, and the ragged work-list arrays from
        ``build_ragged_plan``.  Padding tokens carry id 0, position 0 and
        the null-page table row — their writes sink into page 0 and their
        output rows are never gathered."""
        sched = self.scheduler
        ids = np.zeros((self._t_max,), np.int64)
        # fresh buffer per step (never reused: an abandoned zombie worker
        # may still be reading the previous step's arrays)
        packed = np.zeros((self._pack_total,), np.int32)

        def view(name):
            a, b, shp = self._pack_slices[name]
            return packed[a:b].reshape(shp)

        tables = view("tables")
        positions = view("positions")
        out_rows = view("out_rows")
        id_src = view("id_src")
        id_src[...] = -1
        adapters = view("adapters") if self.lora is not None else None
        runs = []
        t = 0
        for w in work:
            slot = sched.slots[w.slot]
            if w.kind == "prefill":
                # pending starts at the harvested position; a chunk of the
                # step in flight lies between that and this run's base
                skip = w.base - slot.pos
                ids[t:t + w.count] = slot.pending[skip:skip + w.count]
            elif w.kind == "verify":
                # speculative verification run: the slot's last sampled
                # token followed by the draft model's proposals
                ids[t] = self._tokens[w.slot]
                ids[t + 1:t + w.count] = w.drafts[:w.count - 1]
            elif w.chained:
                id_src[t] = w.slot       # sampled by the step in flight
            else:
                ids[t] = self._tokens[w.slot]
            row = sched.tables[w.slot]
            tables[t:t + w.count] = row
            positions[t:t + w.count] = w.base + np.arange(w.count,
                                                          dtype=np.int32)
            if adapters is not None:
                adapters[t:t + w.count] = self._adapter[w.slot]
            if w.has_output:
                out_rows[w.slot] = t + w.count - 1
            runs.append((w.base, w.count, row))
            t += w.count
        plan, stats = build_ragged_plan(
            runs, page_size=self.page_size, **self._plan_geometry)
        for k in RAGGED_PLAN_FIELDS:
            view(k)[...] = plan[k]
        if self._slot_state:
            # a slot with a run in flight reads the state row that run
            # writes (its rows swap when that run is harvested)
            window = self.cache.pack_step(
                view, [(w.slot, w.base, w.count) for w in work],
                tables.shape[1], self._plan_geometry,
                in_flight=[w.slot for w in self._ahead
                           if sched.live(w) is not None])
            stats["window_items"] = window["n_items"]
            stats["window_wide_items"] = window["wide_items"]
        return (ids[:, None], packed), stats

    def _enqueue_thunk(self, fused, inputs, cancelled, extra_dev=()):
        """Enqueue one compiled step: host inputs -> device, the cached
        sampling vectors appended, then ``extra_dev`` (already-on-device
        Tensors — the output of the step in flight; the speculative verify
        step's draft probability rows).  Returns the program's outputs,
        still on the device and not waited for, plus the sampling-cache
        build (committed by the dispatching thread only); None when
        abandoned before the dispatch."""
        self._hook("before_decode")
        if cancelled():          # abandoned while the fault hook stalled:
            return None          # the result is discarded; skip dispatch
        cache = self._sampling_cache
        built = None
        if cache is None:
            # snapshot copies: the cached device Tensors must not alias
            # the live mirrors a later admission mutates.  Built into a
            # LOCAL — _run_fused commits it only when this run finishes
            # within budget, so an abandoned zombie (racing a recovery
            # that already invalidated the cache and re-admitted with new
            # sampling params) can never overwrite live sampling state.
            built = cache = (
                self._host_to_dev(self._temp.copy()),
                self._host_to_dev(self._top_p.copy()),
                self._host_to_dev(self._top_k.copy()),
                self._host_to_dev(self._do_sample.copy()))
        dev = [self._host_to_dev(np.ascontiguousarray(a)) for a in inputs]
        behind = self._inflight
        if behind is not None:
            # the last look before the enqueue: a predecessor that is
            # complete by now left the device's queue empty
            behind.drained = behind.ready()
        out = fused(*dev, *cache, *extra_dev)
        return out, built

    def _read_thunk(self, flight: _Flight, cancelled):
        """Wait for ``flight``'s output and bring it to the host: each
        slot's sampled token and whether its logits were finite, in the ONE
        array the step returns.  The span records on the CALLING thread —
        under a watchdog this is the supervised _StepWorker, so the
        exported trace shows the wait on the worker's row (its parent all
        the same, the tick's serve.step: _supervised hands it over)."""
        with _ttrace.span("serve.device_step", flight=flight.seq):
            self._hook("await_decode")
            if cancelled():
                return None
            flight.ready_at_read = flight.ready()
            t0 = time.perf_counter_ns()
            out = np.asarray(flight.out.numpy())
            flight.t_ready = time.perf_counter_ns()
            flight.wait_ns = flight.t_ready - t0
            return np.maximum(out, 0), out >= 0

    def _harvest_fused(self, work, stats, toks_np: np.ndarray,
                       fin_np: np.ndarray):
        """Fold one fused step's results back into the request states:
        consume prefill runs, quarantine NaN-poisoned output slots,
        advance/emit the rest.  Mirrors and pending prompts only move
        HERE — a failed dispatch leaves them untouched for the retry, and
        the step enqueued meanwhile was planned as if this one succeeds."""
        ctx = {"tokens": toks_np, "finite": fin_np}
        self._hook("after_decode", ctx)
        sched = self.scheduler
        # a run is harvested into the seating it was planned for; one whose
        # slot was retired since (an EOS, a quarantine, a cancel: known
        # only after it was enqueued) is void — nothing emitted or counted
        live = [(w, slot) for w in work
                for slot in (sched.live(w),) if slot is not None]
        if len(live) < len(work):
            self._totals["voided_rows"] += len(work) - len(live)
        self._fold_plan_stats([w for w, _ in live], stats)
        if self._slot_state:
            # the step's results are in hand: its slots' state rows swap
            self.cache.commit_step(
                [(w.slot, w.base, w.count) for w, _ in live],
                stats["window_items"], stats["window_wide_items"])
        for w, slot in live:
            if w.kind == "prefill":
                slot.pending = slot.pending[w.count:]
            if w.has_output and not ctx["finite"][w.slot]:
                # finiteness sentry: quarantine the poisoned slot instead
                # of streaming garbage; every other slot proceeds
                self._totals["quarantined"] += 1
                self._fail_slot(w.slot, NaNLogitsError(
                    f"request {slot.request.id}: non-finite logits at "
                    f"position {slot.pos + w.count - 1} "
                    f"(slot {w.slot} quarantined)"))
                continue
            # the step wrote this run's K/V at positions base..base+count-1
            sched.advance(w.slot, w.count)
            self._register_shared(w.slot)
            if not w.has_output:
                continue                 # mid-prefill: nothing sampled yet
            req = slot.request
            tok = int(ctx["tokens"][w.slot])
            if w.kind == "prefill":
                # the prompt completed THIS step: the sampled token is the
                # request's first generated token (prefill piggybacked on
                # the decode batch) and the slot decodes from here on
                req.state = RequestState.DECODE
            self._tokens[w.slot] = tok
            self._emit(req, tok)
            if self._is_finished(req, tok):
                self._finish(w.slot)

    def _fold_plan_stats(self, work, stats):
        """Fold one dispatched plan's occupancy/padding tallies into the
        totals (shared by the base harvest and the speculative verify
        harvest)."""
        prefill = sum(w.count for w in work if w.kind == "prefill")
        self._step_prefill += prefill
        self._totals["prefill_tokens"] += prefill
        self._totals["work_items"] += stats["n_items"]
        self._totals["work_capacity"] += stats["wl_capacity"]
        self._totals["launched_items"] += stats["launched_items"]
        self._totals["launched_grid_steps"] += (
            stats["launched_items"] * self._grid_steps_per_item)
        self._totals["write_items"] += stats["n_writes"]
        self._totals["block_rows"] += stats["n_tokens"]
        self._totals["block_row_capacity"] += stats["row_capacity"]
        self._totals["wide_items"] += stats["wide_items"]
        self._totals["wide_block_rows"] += stats["wide_rows"]
        waste = ragged_padding_waste(
            stats["n_tokens"], stats["n_blocks"], stats["n_items"],
            self.token_block, self.page_size, self.head_dim,
            dtype=self.cache_dtype, wide_block=self.wide_block,
            wide_tokens=stats["wide_rows"], wide_blocks=stats["wide_blocks"],
            wide_items=stats["wide_items"])
        self._totals["padded_rows"] += waste["padded_rows"]
        self._totals["padded_flops"] += waste["wasted_flops"]
        self._last_occupancy = (
            stats["n_items"] / stats["wl_capacity"],
            stats["n_tokens"] / max(stats["row_capacity"], 1))

    def _register_shared(self, idx: int):
        """Register slot ``idx``'s newly COMPLETED full pages in the
        prefix cache (called at harvest, right after ``advance`` commits
        the step's writes).  A page is complete once ``pos`` has advanced
        past its last position — from then on the slot only writes
        strictly later pages (COW by construction), so the page is
        immutable and safe to share.  Pages complete in order, so the
        shared pages always form a prefix of ``slot.pages``.

        When another slot already registered an identical chunk (same
        token path), the existing node's page is ADOPTED: it replaces the
        slot's own page in its table row (deterministic KV — identical
        token prefixes produce bitwise-identical pages) and the private
        duplicate goes straight back to the pool."""
        cache = self.prefix_cache
        if cache is None:
            return
        sched = self.scheduler
        slot = sched.slots[idx]
        req = slot.request
        if req.adapter is not None:
            # LoRA'd KV depends on the adapter, not just the token ids —
            # a cross-tenant hit would splice in the WRONG values.  Keyed
            # per-adapter caching is future work; bypass for now.
            return
        ps = self.page_size
        full = slot.pos // ps
        if full <= slot.shared:
            return
        # written token ids at positions [0, pos): the prompt plus the
        # emitted continuation (writes trail emissions by one token)
        seq = np.concatenate(
            [np.asarray(req.prompt, np.int64),
             np.asarray(req.tokens, np.int64)])[:slot.pos]
        while slot.shared < full:
            i = slot.shared
            parent = slot.nodes[-1] if slot.nodes else None
            node, owned = cache.extend(parent, seq[i * ps:(i + 1) * ps],
                                       slot.pages[i])
            if not owned:
                self.allocator.free([slot.pages[i]])
                slot.pages[i] = node.page
                sched.tables[idx, i] = node.page
            slot.nodes.append(node)
            slot.shared += 1

    def run_until_idle(self, max_steps: Optional[int] = None) -> dict:
        """Step until queue and slots drain; returns cumulative metrics."""
        steps = 0
        while (self.queue.depth or self.scheduler.active_slots
               or self._inflight is not None):
            met = self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
            if (not met["active_slots"] and not met["tokens_this_step"]
                    and self.queue.depth):
                # admission gated by post-recovery backoff: don't spin hot
                time.sleep(0.001)
        return self.metrics()

    def generate_batch(self, prompts, max_new_tokens: int = 32, *,
                       raise_on_failure: bool = True,
                       **kwargs) -> List[np.ndarray]:
        """Convenience: submit every prompt, drain, return each request's
        prompt+generated ids (in submission order).  A request that ends
        in a non-DONE terminal state (cancelled / timed out / failed)
        raises the typed error instead of silently returning a truncated
        row; pass ``raise_on_failure=False`` to get whatever each request
        produced and inspect states yourself."""
        reqs = [self.submit(p, max_new_tokens, **kwargs) for p in prompts]
        self.run_until_idle()
        bad = [r for r in reqs if r.state != RequestState.DONE]
        if bad and raise_on_failure:
            detail = ", ".join(f"request {r.id}: {r.state}" for r in bad)
            raise ServingError(
                f"generate_batch: {len(bad)}/{len(reqs)} request(s) did "
                f"not complete ({detail})") from bad[0].error
        return [r.output_ids() for r in reqs]

    # -- drain lifecycle (docs/serving.md "Elasticity & degradation
    # ladder"): scale-down and replica-loss re-homing both go through
    # begin_drain -> [keep stepping] -> checkpoint_seated -----------------
    @property
    def draining(self) -> bool:
        return self._draining

    @property
    def drained(self) -> bool:
        """True once a draining engine holds no work at all."""
        return (self._draining and self.queue.depth == 0
                and self.scheduler.active_slots == 0)

    def begin_drain(self) -> List[Request]:
        """Stop admission and hand back every QUEUED (never-seated)
        request for re-routing via the placement layer.  Seated requests
        are untouched — ``step()`` keeps decoding them to completion; a
        caller that cannot wait evicts the stragglers with
        ``checkpoint_seated()`` once its drain deadline passes."""
        with self._lock:
            self._check_open()
            self._settle()
            self._draining = True
            return self.queue.remove_where(lambda r: True)

    def resume_admission(self):
        """Reverse ``begin_drain``: the engine admits again (scale-up of
        a previously drained replica)."""
        with self._lock:
            self._check_open()
            self._draining = False

    def checkpoint_seated(self) -> List[Request]:
        """Evict every seated request as a re-admittable token-prefix
        checkpoint and return them (drain deadline passed, or the replica
        is being killed).  The generated continuation folds into the
        prompt — ``output_ids()`` is INVARIANT across the fold and tokens
        already streamed through ``on_token`` are never re-emitted
        (exactly-once) — and the remaining ``max_new_tokens`` budget
        shrinks by what was already emitted, so a survivor re-admits the
        request at exactly the position the drained replica left it.
        Greedy continuations are bitwise-identical to an undrained run
        (greedy decode is a pure function of the context); sampling
        requests additionally carry the engine's RNG state on
        ``Request.rng_state`` (the continuation resumes the documented
        distribution — the survivor draws from its own stream).  Pages,
        LoRA references and prefix-cache reader references all release
        here, so the 4-term page-accounting invariant holds immediately
        after."""
        with self._lock:
            self._check_open()
            # what the step in flight emits belongs to the checkpoint
            self._settle()
            return [self._checkpoint_slot(i)
                    for i, _slot in self.scheduler.seated()]

    def _checkpoint_slot(self, idx: int) -> Request:
        slot = self.scheduler.slots[idx]
        req = slot.request
        if req.sampling.do_sample:
            req.rng_state = self._rng_checkpoint()
        self.scheduler.retire(idx)         # pages + cache refs free NOW
        self._clear_slot_mirrors(idx)      # LoRA reference drops here
        n_emitted = len(req.tokens)
        req.prompt = np.concatenate(
            [req.prompt, np.asarray(req.tokens, np.int64)])
        req.max_new_tokens -= n_emitted
        req.tokens = []
        req.rehomed += n_emitted
        req.state = RequestState.SUBMITTED
        self._totals.inc("drained")
        return req

    def _rng_checkpoint(self):
        """The sampling generator's state at the checkpoint (engine-own
        stream for mesh-sharded engines, the global one otherwise)."""
        gen = self._generator
        if gen is None:
            from ..ops.random import default_generator as gen
        try:
            return np.asarray(gen._state.numpy()).copy()
        except Exception:  # noqa: BLE001 — state is advisory metadata
            return None

    def requeue(self, req: Request) -> Request:
        """Queue an EXISTING request object (placement-layer re-homing
        after a drain or replica loss).  Prompt/budget validation
        happened at the original submit and the checkpoint fold preserves
        the total; the bounded-queue check still applies (typed
        ``Overloaded``).  The absolute monotonic ``deadline`` carries
        over unchanged; ``submit_t`` resets to NOW — queue-wait shedding
        measures time in THIS queue, not lifetime (the deadline already
        bounds that)."""
        self._check_open()
        self._settle()
        if self._draining:
            raise Overloaded(
                f"engine draining: request {req.id} not requeued")
        if req.adapter is not None and self.lora is None:
            raise Overloaded(
                f"request {req.id} needs adapter {req.adapter!r} but this "
                "replica has no LoRA pool")
        req.submit_t = time.monotonic()
        return self.queue.submit(req)

    # -- disaggregated hand-off (serving/disagg.py) ------------------------
    def adopt_transferred(self, req: Request, pages: List[int], pos: int,
                          last_token: int) -> Optional[int]:
        """Seat a mid-decode request whose KV pages were copied into this
        replica's pool by a :class:`~.disagg.PageTransfer`.  ``pages``
        must ALREADY be committed in this allocator's ledger (the
        transfer's destination-side reservation went spec → allocated
        before this call); ``pos`` is every KV position the source wrote
        and ``last_token`` the source's most recent sampled token — the
        next decode step feeds it at ``positions[idx] == pos`` exactly as
        the source would have, which is what makes the greedy
        continuation bitwise-identical to an untransferred run.  None
        (nothing changed) when this replica cannot seat it right now —
        draining, no free slot, or a missing LoRA adapter — and the
        caller rolls the transfer back."""
        with self._lock:
            self._check_open()
            if self._draining:
                return None
            page = 0
            if req.adapter is not None:
                if self.lora is None:
                    return None
                try:
                    page = self.lora.acquire(req.adapter)
                except ServingError:
                    return None
            idx = self.scheduler.adopt(req, pages, pos)
            if idx is None:
                if req.adapter is not None:
                    self.lora.release(req.adapter)
                return None
            self._adapter[idx] = page
            self._adapter_name[idx] = req.adapter
            sp = req.sampling
            self._temp[idx] = np.float32(sp.temperature)
            self._top_p[idx] = np.float32(sp.top_p)
            self._top_k[idx] = np.int32(sp.top_k)
            self._do_sample[idx] = bool(sp.do_sample)
            self._tokens[idx] = np.int64(last_token)
            self._sampling_cache = None
            req.state = RequestState.DECODE
            self._totals.inc("transferred_in")
            return idx

    def release_transferred(self, idx: int):
        """Source side of a committed hand-off: the request now lives on
        the destination replica, so release slot ``idx`` WITHOUT a
        terminal transition — pages back to this pool, prefix-cache
        reader references dropped, LoRA reference released.  Called only
        after the destination committed its copy (the ownership rule that
        keeps both pools' 4-term invariant exact through faults: until
        commit, this slot still owns the request)."""
        with self._lock:
            self.scheduler.retire(idx)
            self._clear_slot_mirrors(idx)
            self._totals.inc("transferred_out")

    # -- internals ---------------------------------------------------------
    @contextmanager
    def _eval_mode(self):
        was = getattr(self.model, "training", False)
        if was:
            self.model.eval()
        try:
            yield
        finally:
            if was:
                self.model.train()

    def _hook(self, point: str, ctx: Optional[dict] = None):
        if self._fault_hook is not None:
            self._fault_hook(point, ctx)

    def _supervised(self, fn, budget: Optional[float]):
        """Run ``fn(cancelled)`` under the watchdog when a stall budget is
        configured; inline otherwise."""
        if budget is None:
            return fn(lambda: False)
        if self._worker is None or self._worker.dead:
            if self._worker is not None:
                # let the replaced worker's thread exit once its zombie
                # thunk returns (otherwise one blocked daemon thread
                # leaks per stall recovery)
                self._worker.shutdown()
            self._worker = _StepWorker(f"serving-step-{id(self):x}")
        tracer = _ttrace._tracer
        if tracer is not None:
            # the worker's spans take the span open HERE as parent (the
            # enqueue's jit.fused_step: serve.dispatch; serve.device_step:
            # the tick's serve.step): the tree crosses the thread
            fn = tracer.handing_over(fn)
        return self._worker.run(fn, budget, cleanup=self._zombie_cleanup())

    def _zombie_cleanup(self) -> Callable[[], None]:
        """Cleanup an abandoned (stalled) step runs when it finally
        returns: its write-backs landed in the orphaned pool Tensors —
        release their device memory.  The speculative engine widens this
        to its draft pool."""
        cache = self.cache

        def cleanup():
            cache.release()

        return cleanup

    # -- reaping: deadlines, cancellation, queue-wait shedding -------------
    def _reap(self, now: float):
        """Step-boundary retirement of cancelled/expired requests, both
        queued and seated.  Pages return to the pool before admission runs
        so freed capacity is reusable in the same step."""
        max_wait = self.max_queue_wait_s

        def expired(r: Request) -> bool:
            return (r.cancelled
                    or (r.deadline is not None and now >= r.deadline)
                    or (max_wait is not None and r.submit_t is not None
                        and now - r.submit_t >= max_wait))

        for r in self.queue.remove_where(expired):
            if r.cancelled:
                self._terminalize(r, RequestState.CANCELLED,
                                  RequestCancelled(f"request {r.id} "
                                                   "cancelled while queued"))
            elif r.deadline is not None and now >= r.deadline:
                self._terminalize(r, RequestState.TIMED_OUT,
                                  DeadlineExceeded(
                                      f"request {r.id}: deadline_s="
                                      f"{r.deadline_s} passed while queued"))
            else:
                # atomic inc: "shed" is also incremented by submit()
                # OUTSIDE the step lock, so the `+=` read-modify-write
                # here could interleave with it and lose counts / trip
                # the monotonicity check
                self._totals.inc("shed")
                self._terminalize(r, RequestState.TIMED_OUT, Overloaded(
                    f"request {r.id}: queued longer than "
                    f"max_queue_wait_s={max_wait}"))
        for i, slot in self.scheduler.seated():
            r = slot.request
            if r.cancelled:
                self._retire_slot(i, RequestState.CANCELLED,
                                  RequestCancelled(
                                      f"request {r.id} cancelled"))
            elif r.deadline is not None and now >= r.deadline:
                self._retire_slot(i, RequestState.TIMED_OUT,
                                  DeadlineExceeded(
                                      f"request {r.id}: deadline_s="
                                      f"{r.deadline_s} passed mid-decode"))

    # -- admission ---------------------------------------------------------
    def _admit(self, now: float):
        """Seat queued requests while slots AND pages allow.  Admission is
        pure host bookkeeping now — pages reserved all-or-nothing, the
        prompt parked on ``Slot.pending`` — and the very same tick's fused
        step starts consuming the prompt under the token budget (no
        per-request prefill dispatch: the PR-5 ``[1, chunk]`` program is
        retired)."""
        if self._draining:
            return                        # drain: no new admissions, ever
        if now < self._admit_after:
            return                        # re-admission backoff after recovery
        sched = self.scheduler
        while sched.free_slot_indices():
            req = self.queue.pop()
            if req is None:
                return
            page = 0
            if req.adapter is not None:
                try:
                    # pin the tenant's adapter page for the seated life of
                    # the request (evicting it now raises AdapterInUse)
                    page = self.lora.acquire(req.adapter)
                except ServingError as e:
                    # evicted while queued: fail THIS request, typed — a
                    # silent null-adapter decode would be a wrong answer
                    self._terminalize(req, RequestState.FAILED, e)
                    continue
            total = req.prompt.size + req.max_new_tokens
            # longest cached prefix: reader references taken NOW so the
            # tail-only reservation below can never evict the hit pages
            # (the allocator's pressure reclaimer skips referenced nodes)
            c_nodes, c_pages, n_cached = (), (), 0
            if self.prefix_cache is not None and req.adapter is None:
                c_nodes, c_pages, n_cached = \
                    self.prefix_cache.acquire(req.prompt)
            idx = sched.try_admit(req, total, cached_pages=c_pages,
                                  cached_nodes=c_nodes, n_cached=n_cached)
            if idx is None:
                # pool backpressure: requeue and stop admitting (FIFO —
                # later smaller requests must not starve this one)
                if c_nodes:
                    self.prefix_cache.release(c_nodes)
                if req.adapter is not None:
                    self.lora.release(req.adapter)
                self.queue.push_front(req)
                return
            self._adapter[idx] = page
            self._adapter_name[idx] = req.adapter
            self._totals["admitted"] += 1
            if self.prefix_cache is not None and req.adapter is None:
                cacheable = self.prefix_cache._cacheable_chunks(
                    req.prompt.size) * self.page_size
                if n_cached and n_cached >= cacheable:
                    self._prefix_totals["hits_total"] += 1
                elif n_cached:
                    self._prefix_totals["partial_hits_total"] += 1
                else:
                    self._prefix_totals["misses_total"] += 1
                self._prefix_hist.observe(float(n_cached))
            req.t_admitted = now
            if req.t_submitted is not None:
                self._slo["queue_wait"].observe(now - req.t_submitted)
            sp = req.sampling
            self._temp[idx] = np.float32(sp.temperature)
            self._top_p[idx] = np.float32(sp.top_p)
            self._top_k[idx] = np.int32(sp.top_k)
            self._do_sample[idx] = bool(sp.do_sample)
            self._sampling_cache = None
            # only the uncached tail still needs prefilling: the slot is
            # seated at position n_cached and the fused step's first run
            # for it starts there (traced per-slot positions — no retrace)
            sched.slots[idx].pending = np.asarray(req.prompt[n_cached:],
                                                  np.int64)
            req.state = RequestState.PREFILL

    # -- recovery ----------------------------------------------------------
    def _recover(self, error: BaseException, *, rebuild: bool,
                 stalled: bool = False):
        """Contain a crashed or stalled step: every seated request is
        implicated (the pool they share may be half-written or consumed by
        donation) and ends FAILED with ``error`` attached; queued requests
        survive untouched.  With ``rebuild`` the device pool and compiled
        steps are reconstructed from the scheduler's host mirrors.
        Re-admission backs off exponentially (reset by a clean step)."""
        with _ttrace.span("serve.recover", error=type(error).__name__,
                          rebuild=rebuild):
            self._totals["recoveries"] += 1
            for i, _slot in self.scheduler.seated():
                self._fail_slot(i, error)
            if rebuild:
                self._rebuild(release_old=not stalled)
            now = time.monotonic()
            self._admit_after = now + self._backoff_s
            self._backoff_s = min(self._backoff_s * 2.0, self.backoff_max_s)

    def _rebuild(self, release_old: bool = True):
        """Reconstruct the engine's DEVICE state after a catastrophic step
        failure: a fresh page pool + fresh compiled step closures.  Host
        state (allocator free list, queue, counters) is authoritative and
        survives as-is.  The old pool is released eagerly unless a zombie
        worker may still touch it (a stall) — then the abandoned box's
        cleanup releases it when the zombie returns, so its write-backs
        land in orphaned Tensors, never in the new pool."""
        assert self.scheduler.active_slots == 0, \
            "rebuild with seated requests would strand their K/V"
        if self.prefix_cache is not None:
            # the fresh pool's content is zeroed: every cached KV page is
            # invalid.  All readers retired above (refcounts 0), so the
            # flush reclaims the whole shared ledger back to the free
            # list — accounting stays exact through the rebuild.
            self.prefix_cache.flush()
        assert self.allocator.used_pages == 0, \
            f"rebuild leaked {self.allocator.used_pages} pages"
        assert self.allocator.shared_pages == 0, \
            f"rebuild leaked {self.allocator.shared_pages} shared pages"
        with _ttrace.span("serve.rebuild"):
            old = self.cache
            self.cache = self._new_pool()
            self.scheduler.reset_mirrors()
            self._build_steps()
            if release_old:
                old.release()
            self._totals["rebuilds"] += 1

    # -- terminal transitions ----------------------------------------------
    def _clear_slot_mirrors(self, idx: int):
        self._tokens[idx] = 0
        self._temp[idx] = 1.0
        self._top_p[idx] = 1.0
        self._top_k[idx] = 0
        self._do_sample[idx] = False
        if self._adapter_name[idx] is not None:
            self.lora.release(self._adapter_name[idx])
        self._adapter[idx] = 0
        self._adapter_name[idx] = None
        self._sampling_cache = None

    def _terminalize(self, req: Request, state: str,
                     error: Optional[BaseException]):
        """Finish a NEVER-SEATED request in a non-DONE terminal state."""
        req.error = error
        req.state = state
        self._observe_terminal(req)
        if state == RequestState.CANCELLED:
            self._totals["cancelled"] += 1
        elif state == RequestState.TIMED_OUT:
            self._totals["timed_out"] += 1
        elif state == RequestState.FAILED:
            self._totals["failed"] += 1
        req._done.set()

    def _observe_terminal(self, req: Request):
        """Stamp ``t_terminal`` and feed the e2e histogram — called on
        EVERY terminal transition (DONE and otherwise), exactly once per
        request (terminal states never transition again)."""
        now = time.monotonic()
        req.t_terminal = now
        if req.t_submitted is not None:
            self._slo["e2e"].observe(now - req.t_submitted)

    def _retire_slot(self, idx: int, state: str,
                     error: Optional[BaseException]):
        """Retire a SEATED request into a non-DONE terminal state; its
        pages return to the pool immediately."""
        req = self.scheduler.slots[idx].request
        self.scheduler.retire(idx)
        self._clear_slot_mirrors(idx)
        self._terminalize(req, state, error)

    def _fail_slot(self, idx: int, error: BaseException):
        self._retire_slot(idx, RequestState.FAILED, error)

    def _emit(self, req: Request, tok: int, now: Optional[float] = None):
        """Emit one generated token.  ``now`` lets a multi-token step
        (speculative acceptance) stamp EVERY token it emits with the ONE
        step timestamp — the documented ITL convention: the step's first
        token observes the true inter-arrival gap, the rest observe 0
        (they arrived in the same dispatch; docs/serving.md)."""
        req.tokens.append(tok)
        self._step_emitted += 1
        if now is None:
            now = time.monotonic()
        if req.t_first_token is None:
            req.t_first_token = now
            if req.t_submitted is not None:
                self._slo["ttft"].observe(now - req.t_submitted)
        elif req._t_last_token is not None:
            self._slo["itl"].observe(now - req._t_last_token)
        req._t_last_token = now
        if req.on_token is not None:
            try:
                self._hook("callback")
                req.on_token(req, tok)
            except Exception as e:  # noqa: BLE001 — must not kill serving
                # record the FIRST callback error on the request and warn
                # once per request — never silently swallowed
                if req.callback_error is None:
                    req.callback_error = e
                if not req._cb_warned:
                    req._cb_warned = True
                    import warnings

                    warnings.warn(
                        f"on_token callback for request {req.id} raised "
                        f"{type(e).__name__}: {e} (recorded on "
                        "request.callback_error; further errors for this "
                        "request are suppressed)", RuntimeWarning,
                        stacklevel=2)

    @staticmethod
    def _is_finished(req: Request, tok: int) -> bool:
        if len(req.tokens) >= req.max_new_tokens:
            return True
        return req.eos_token_id is not None and tok == req.eos_token_id

    def _finish(self, idx: int):
        req = self.scheduler.slots[idx].request
        self.scheduler.retire(idx)         # pages free immediately
        self._clear_slot_mirrors(idx)
        self._totals["completed"] += 1
        req.state = RequestState.DONE
        self._observe_terminal(req)
        req._done.set()

    def _check_open(self):
        if self._closed:
            raise RuntimeError("ServingEngine is closed (cache released)")

    # -- observability -----------------------------------------------------
    def metrics(self) -> dict:
        """Cumulative totals + the last step's gauges.  The ragged-launch
        occupancy means make the fused step's win measurable: how full the
        plan's fixed work-list arrays ran (``mean_grid_occupancy``) and
        how many of the packed query-block rows carried real tokens
        (``mean_q_row_occupancy``) across every dispatched step (the launch
        itself ends at ``n_items``: ``launched_items == work_items``).
        ``ragged_heads_per_block`` is the ``hb`` of this engine's geometry
        (the heads of a page a work item moves a grid step) and
        ``launched_grid_steps`` the grid steps a chip's launches walked:
        ``launched_items x local heads // hb``, equal to
        ``launched_items`` wherever an item moves all its heads.
        ``pool_write_items`` is the mean length of a step's write list (the
        tile groups of the pool its real tokens touched: the pool write's
        launch on a TPU) and ``pool_tiles_per_token`` those items over the
        real tokens: 1.0 for pure decode, 1/g for aligned chunks."""
        out = dict(self._totals)
        out.update(self._last_metrics)
        out["queue_depth"] = self.queue.depth
        out["active_slots"] = self.scheduler.active_slots
        out["draining"] = self._draining
        out["pages_used"] = self.allocator.used_pages
        out["pages_capacity"] = self.allocator.capacity
        out["occupancy"] = self.scheduler.occupancy
        out["cache_bytes"] = self.cache.nbytes if not self._closed else 0
        # per-chip pool accounting: the head-sharded pool puts 1/mp of the
        # page bytes on each chip of the replica mesh (docs/serving.md
        # "Sharded serving"; mp=1 single-chip -> identical numbers)
        out["mp"] = self._mp
        out["cache_bytes_per_chip"] = out["cache_bytes"] // self._mp
        wc = self._totals["work_capacity"]
        rc = self._totals["block_row_capacity"]
        out["mean_grid_occupancy"] = (self._totals["work_items"] / wc
                                      if wc else 0.0)
        out["mean_q_row_occupancy"] = (self._totals["block_rows"] / rc
                                       if rc else 0.0)
        out["ragged_heads_per_block"] = self.ragged_heads_per_block
        wi, fs = self._totals["write_items"], self._totals["fused_steps"]
        out["pool_write_items"] = wi / fs if fs else 0.0
        rows = self._totals["block_rows"]
        out["pool_tiles_per_token"] = wi / rows if rows else 0.0
        # per-request SLO digests (seconds): count/sum/mean/min/max +
        # p50/p95/p99 per histogram — TTFT, inter-token latency, queue
        # wait, end-to-end (docs/observability.md "SLO definitions")
        out["slo"] = {k: h.summary() for k, h in self._slo.items()}
        # prefix-cache accounting (docs/serving.md "Prefix cache") — keys
        # present unconditionally (zeros when disabled) so the sharded
        # engine's cross-replica sums never miss a replica
        self._sync_prefix_counters()
        hits = self._prefix_totals["hits_total"]
        partial = self._prefix_totals["partial_hits_total"]
        misses = self._prefix_totals["misses_total"]
        cached = int(self._prefix_hist.summary()["sum"])
        out["prefix_hits"] = hits
        out["prefix_partial_hits"] = partial
        out["prefix_misses"] = misses
        out["prefix_evictions"] = self._prefix_totals["evictions_total"]
        out["prefix_cached_tokens"] = cached
        looked = hits + partial + misses
        out["prefix_hit_rate"] = (hits + partial) / looked if looked else 0.0
        written = cached + self._totals["prefill_tokens"]
        out["cached_tokens_share"] = cached / written if written else 0.0
        out["prefix_cache_pages"] = (self.prefix_cache.pages
                                     if self.prefix_cache else 0)
        out["prefix_cache_nodes"] = (self.prefix_cache.nodes
                                     if self.prefix_cache else 0)
        out["shared_pages"] = self.allocator.shared_pages
        counts = getattr(self.cache, "counts", None)
        if counts is not None and not self._closed:
            # what the model's cache counts on the device (a routed layer's
            # assignments, a convolution's tails): read here and only here
            out.update(counts())
        if self.lora is not None:
            out["lora_adapters"] = len(self.lora.adapters())
            out["lora_pages_used"] = self.lora.allocator.used_pages
            out["lora_slab_bytes"] = self.lora.nbytes
        return out

    def _sync_prefix_counters(self):
        """Mirror the cache's eviction tally onto the registry counter —
        evictions fire inside the allocator's pressure reclaimer (mid
        ``alloc``), where no engine code runs."""
        if self.prefix_cache is None:
            return
        ev = self.prefix_cache.stats["evictions"]
        if ev > self._prefix_totals["evictions_total"]:
            self._prefix_totals["evictions_total"] = ev

    @property
    def _static_fns(self):
        return (self._fused_greedy, self._fused_sample)

    @property
    def compiled_programs(self) -> int:
        return sum(len(f.code_cache) for f in self._static_fns)

    def lowered_texts(self):
        """StableHLO text of the compiled fused-step programs (Mosaic
        custom calls included)."""
        return [t for f in self._static_fns for t in f.lowered_texts()]

    def op_scopes(self):
        """Per compiled fused-step program, its optimized instructions by
        name with the scope each falls under (``jit.api.op_scopes``)."""
        return [m for f in self._static_fns for m in f.op_scopes()]

    def lint_reports(self):
        """Graph-lint reports of the compiled fused-step programs
        (populated when FLAGS_graph_lint / PADDLE_TPU_GRAPH_LINT=1 was on
        at compile time; see docs/graph_lint.md)."""
        return [r for f in self._static_fns for r in f.lint_reports()]

    def close(self):
        """Release the page pool's HBM eagerly.  The step in flight lands
        first (its tokens are emitted); pending/active requests are NOT
        drained — call ``run_until_idle`` first if they matter.
        Serializes on the step lock, so an in-flight step() finishes
        before the pool vanishes and later steps fail the open check
        cleanly instead of consuming deleted arrays."""
        with self._lock:
            if not self._closed:
                try:
                    self._settle()
                finally:
                    self._inflight = None
                    self._closed = True
                    self.cache.release()
                if self._worker is not None:
                    self._worker.shutdown()
                # drop this engine's children from the process registry:
                # a host recycling engines (or the test suite's dozens)
                # must not grow the Prometheus exposition forever.  The
                # CounterSet/histogram handles keep working — metrics()
                # stays readable after close — they just stop being
                # exported.
                _tmetrics.registry().drop_labels(**self._engine_label)


def _state_intact(e: BaseException) -> bool:
    """True when the exception provably fired BEFORE any device work (an
    injected fault flagged state_intact): device state is untouched, so
    containment can stay surgical.  Real device errors report False and
    recovery conservatively rebuilds."""
    return bool(getattr(e, "state_intact", False))
