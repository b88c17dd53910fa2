"""Host-side span tracer with Chrome-trace/Perfetto export.

Host spans are recorded in a ring buffer, and each span nests a
``jax.profiler.TraceAnnotation`` (the XLA profiler's TraceMe), so during a
device capture (``jax.profiler.start_trace``) the same named ranges appear
on the device timeline; without one the annotation is a few-ns no-op.

Contract (docs/observability.md):

- **near-zero disabled path** — ``span()`` reads ONE module global; with
  no tracer it returns a shared no-op whose ``__enter__`` gives None.
  Ids, parents and per-thread stacks exist only under a tracer.
- **thread-aware** — spans record thread id + name, so the serving
  watchdog's ``_StepWorker`` and the checkpoint writer get their own rows.
- **ring-buffered** — a bounded deque (default 65536 spans); overflow
  drops the OLDEST spans and counts them in ``Tracer.dropped``.
- **metadata** — ``span(name, **args)`` attaches JSON-safe args (``jit/
  api.py``: each program's CostReport digest); ``with span(..) as s``
  gives the open span (None when off) and ``s.set(..)`` adds args as the
  work learns them: how ``serve.step`` records what its step carried.
- **a tree** — every span has an ``id`` and the ``parent`` open on its
  thread when it started; ``Tracer.handing_over`` carries the parent to
  work another thread runs.
- **intervals** — ``Tracer.record_interval`` records a completed range
  whose two ends were stamped (``time.perf_counter_ns``) at different
  times, by whoever owns the thing it times: the serving engine's
  ``serve.flight``, from a step's enqueue to its landing a tick later.
- **the collector** — under a tracer, and only then, a ``gc.callbacks``
  hook records each collection as an ordinary span ``host.gc`` on the
  collecting thread, innermost wherever it strikes.
- **the device's side** — the tracer remembers (weakly: it keeps no
  weights or pools alive) each compiled program it saw dispatched
  (``Tracer.programs``) and maps their operations to program scopes
  (``telemetry/scopes.py``) when first asked, after the window
  (``Tracer.program_scopes()``).
"""
from __future__ import annotations

import gc
import itertools
import json
import os
import threading
import time
import weakref
from collections import deque
from typing import Any, Callable, Dict, List, Optional

__all__ = [
    "Span", "Tracer", "enable", "disable", "active", "span",
    "export_chrome_trace", "summarize", "format_summary",
]


class Span:
    """One completed host range."""

    __slots__ = ("name", "t0_ns", "dur_ns", "tid", "thread_name", "args",
                 "id", "parent")

    def __init__(self, name: str, t0_ns: int, dur_ns: int, tid: int,
                 thread_name: str, args: Optional[Dict[str, Any]],
                 id: int = 0, parent: Optional[int] = None):
        self.name = name
        self.t0_ns = t0_ns
        self.dur_ns = dur_ns
        self.tid = tid
        self.thread_name = thread_name
        self.args = args
        self.id = id
        self.parent = parent

    def __repr__(self):
        return (f"Span({self.name!r}, {self.dur_ns / 1e6:.3f} ms, "
                f"tid={self.tid})")


class _NullSpan:
    """The shared disabled-path context manager: nothing is allocated."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NOOP = _NullSpan()

#: the active tracer, or None — ONE global read is the disabled fast path
_tracer: Optional["Tracer"] = None

#: (tid, thread name), read once a thread.  Thread-local, so a later thread
#: given the same ident does not inherit the name; a rename after a thread's
#: first span keeps the old label (the trace cares about identity).
_thread = threading.local()


def _thread_info() -> tuple:
    info = getattr(_thread, "info", None)
    if info is None:
        info = _thread.info = (threading.get_ident(),
                               threading.current_thread().name)
    return info


#: row name -> (tid, row name) of ``record_interval(row=)``: ids counted up
#: from 1, far under any thread's ident (an address)
_rows: Dict[str, tuple] = {}


def _row_info(row: str) -> tuple:
    return _rows.setdefault(row, (1 + len(_rows), row))


class Tracer:
    def __init__(self, capacity: int = 65536, annotate: bool = True):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = int(capacity)
        self._buf: deque = deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        self.dropped = 0
        self.annotate = bool(annotate)
        self._ids = itertools.count(1)
        self._open = threading.local()      # .stack: this thread's open ids
        #: span name -> weak references to the compiled entries dispatched
        #: under it (an entry holds its weights and pools: theirs to free)
        self.programs: Dict[str, List[weakref.ref]] = {}
        self._resolve: Optional[Callable] = None
        self._program_scopes: Optional[Dict[str, List[Dict]]] = None
        self._ann_cls = None
        if self.annotate:
            import jax

            self._ann_cls = jax.profiler.TraceAnnotation

    def record(self, s: Span):
        # lock-free: deque.append with maxlen is atomic under the GIL and
        # evicts the oldest; `dropped` is best-effort under concurrent writers
        buf = self._buf
        if len(buf) == self.capacity:
            self.dropped += 1
        buf.append(s)

    def spans(self) -> List[Span]:
        return list(self._buf)

    def record_interval(self, name: str, t0_ns: int, t1_ns: int,
                        parent: Optional[int] = None,
                        row: Optional[str] = None, **args) -> Span:
        """Record a completed span whose start and end (``perf_counter_ns``)
        were stamped at different times, perhaps in different ticks, with
        an id of its own and the ``parent`` the caller kept from its start.
        No ``TraceAnnotation``: one cannot be back-dated, so the span is in
        this buffer and the Chrome export and not in a device capture.
        ``row`` names a row of its own in the export for spans that overlap
        what the recording thread's own row shows."""
        if row is None:
            tid, tname = _thread_info()
        else:
            tid, tname = _row_info(row)
        s = Span(name, t0_ns, t1_ns - t0_ns, tid, tname, args or None,
                 next(self._ids), parent)
        self.record(s)
        return s

    def _stack(self) -> List[Optional[int]]:
        stack = getattr(self._open, "stack", None)
        if stack is None:
            stack = self._open.stack = []
        return stack

    def current_id(self) -> Optional[int]:
        """The id of the span open on this thread."""
        stack = getattr(self._open, "stack", None)
        return stack[-1] if stack else None

    def handing_over(self, fn: Callable) -> Callable:
        """``fn`` for another thread to run: the spans it opens there take
        the span open HERE, now, as their parent."""
        parent = self.current_id()

        def run(*args, **kwargs):
            stack = self._stack()
            stack.append(parent)
            try:
                return fn(*args, **kwargs)
            finally:
                stack.pop()

        return run

    def saw_program(self, name: str, entry: Any, resolve: Callable):
        """``jit/api.py`` reports each compiled entry it dispatches under
        a ``jit.*`` span; ``resolve(entry)`` gives its scope map."""
        seen = self.programs.setdefault(name, [])
        if not any(ref() is entry for ref in seen):
            seen.append(weakref.ref(entry))
            self._resolve, self._program_scopes = resolve, None

    def program_scopes(self) -> Dict[str, List[Dict]]:
        """By span name, each seen program's ``{instruction name:
        telemetry.scopes.OpScope}``.  Made when first asked (it compiles
        text: seconds for a large step): after the window, never in a step.
        A program whose owner is gone by then (an engine closed, a replica
        scaled away) is left out."""
        if self._program_scopes is None:
            self._program_scopes = {
                name: [self._resolve(e) for e in (ref() for ref in refs)
                       if e is not None]
                for name, refs in self.programs.items()}
        return self._program_scopes

    def clear(self):
        with self._lock:
            self._buf.clear()
            self.dropped = 0
            self.programs.clear()
            self._program_scopes = None

    def __len__(self) -> int:
        with self._lock:
            return len(self._buf)


class _SpanCtx:
    """An open span, with its ``id`` and its ``parent``."""

    __slots__ = ("_tracer", "_name", "_args", "_t0", "_ann", "_stack",
                 "id", "parent")

    def __init__(self, tracer: Tracer, name: str,
                 args: Optional[Dict[str, Any]]):
        self._tracer = tracer
        self._name = name
        self._args = args or None

    def set(self, **args):
        """Add args to the span while it is open."""
        if self._args is None:
            self._args = args
        else:
            self._args.update(args)

    def __enter__(self):
        tracer = self._tracer
        self._stack = stack = tracer._stack()
        self.id = next(tracer._ids)
        self.parent = stack[-1] if stack else None
        stack.append(self.id)
        ann_cls = tracer._ann_cls
        if ann_cls is not None:
            self._ann = ann_cls(self._name)
            self._ann.__enter__()
        else:
            self._ann = None
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter_ns() - self._t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        self._stack.pop()
        tid, tname = _thread_info()
        self._tracer.record(Span(self._name, self._t0, dur, tid, tname,
                                 self._args, self.id, self.parent))
        return False


#: the ``host.gc`` span of the collection that is running (collections do
#: not nest: one slot serves every thread)
_gc_open: Optional[_SpanCtx] = None


def _on_gc(phase: str, info: Dict[str, int]):
    """The ``gc.callbacks`` hook, installed by ``enable`` and removed by
    ``disable``: a collection is a span ``host.gc`` on the thread it struck,
    under whatever span was open there."""
    global _gc_open
    if phase == "start":
        t, _gc_open = _tracer, None
        if t is not None:
            _gc_open = _SpanCtx(t, "host.gc",
                                {"generation": info["generation"]})
            _gc_open.__enter__()
    elif _gc_open is not None:
        ctx, _gc_open = _gc_open, None
        ctx.set(collected=info["collected"])
        ctx.__exit__(None, None, None)


def enable(capacity: int = 65536, annotate: bool = True) -> Tracer:
    """Install a process-wide tracer (idempotent: an already-active
    tracer is returned unchanged so nested enables compose)."""
    global _tracer
    if _tracer is None:
        _tracer = Tracer(capacity=capacity, annotate=annotate)
        gc.callbacks.append(_on_gc)
    return _tracer


def disable() -> Optional[Tracer]:
    """Deactivate tracing.  Returns the detached tracer — its buffered
    spans stay readable/exportable after deactivation."""
    global _tracer
    t, _tracer = _tracer, None
    if t is not None:
        gc.callbacks.remove(_on_gc)
    return t


def active() -> Optional[Tracer]:
    return _tracer


def span(name: str, **args):
    """Context manager recording a host span named ``name`` with
    JSON-safe ``args`` metadata.  Near-zero no-op when disabled."""
    t = _tracer
    if t is None:
        return _NOOP
    return _SpanCtx(t, name, args)


# ---------------------------------------------------------------------------
# export + aggregation
# ---------------------------------------------------------------------------

def export_chrome_trace(path: Optional[str] = None,
                        tracer: Optional[Tracer] = None) -> Dict[str, Any]:
    """Build (and optionally write) the Chrome-trace JSON document for
    ``tracer`` (default: the active one).  The document opens directly in
    chrome://tracing and https://ui.perfetto.dev; nesting is positional
    (``ph="X"`` complete events on the same pid/tid nest by interval
    containment)."""
    tr = tracer if tracer is not None else _tracer
    spans = tr.spans() if tr is not None else []
    pid = os.getpid()
    events: List[Dict[str, Any]] = []
    threads_seen = {s.tid: s.thread_name for s in reversed(spans)}
    for tid, tname in sorted(threads_seen.items()):
        events.append({"name": "thread_name", "ph": "M", "pid": pid,
                       "tid": tid, "args": {"name": tname}})
    for s in spans:
        ev: Dict[str, Any] = {
            "name": s.name, "ph": "X", "cat": "host", "pid": pid,
            "tid": s.tid, "ts": s.t0_ns / 1000.0, "dur": s.dur_ns / 1000.0,
        }
        ev["args"] = {**(s.args or {}), "id": s.id, "parent": s.parent}
        events.append(ev)
    doc = {"traceEvents": events, "displayTimeUnit": "ms",
           "otherData": {"dropped_spans": tr.dropped if tr else 0}}
    if path:
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
    return doc


def summarize(spans: Optional[List[Span]] = None,
              tracer: Optional[Tracer] = None) -> Dict[str, Dict[str, float]]:
    """Per-name aggregation over ``spans`` (default: the given/active
    tracer's buffer): count, total/mean/p50/p99/max milliseconds.

    Exact (sorted durations), not bucketed — the ring buffer bounds the
    working set."""
    if spans is None:
        tr = tracer if tracer is not None else _tracer
        spans = tr.spans() if tr is not None else []
    by_name: Dict[str, List[int]] = {}
    for s in spans:
        by_name.setdefault(s.name, []).append(s.dur_ns)
    out: Dict[str, Dict[str, float]] = {}
    for name, durs in sorted(by_name.items()):
        durs.sort()
        n = len(durs)

        def pct(q):
            return durs[min(int(q * n), n - 1)] / 1e6

        out[name] = {
            "count": n,
            "total_ms": sum(durs) / 1e6,
            "mean_ms": sum(durs) / n / 1e6,
            "p50_ms": pct(0.50),
            "p99_ms": pct(0.99),
            "max_ms": durs[-1] / 1e6,
        }
    return out


def format_summary(stats: Dict[str, Dict[str, float]]) -> str:
    """Human-readable table of :func:`summarize` output."""
    if not stats:
        return "no spans recorded"
    rows = [("name", "count", "total ms", "mean ms", "p50 ms", "p99 ms")]
    for name, st in sorted(stats.items(), key=lambda kv: -kv[1]["total_ms"]):
        rows.append((name, str(st["count"]), f"{st['total_ms']:.3f}",
                     f"{st['mean_ms']:.3f}", f"{st['p50_ms']:.3f}",
                     f"{st['p99_ms']:.3f}"))
    widths = [max(len(r[i]) for r in rows) for i in range(len(rows[0]))]
    lines = []
    for i, r in enumerate(rows):
        lines.append("  ".join(c.ljust(w) for c, w in zip(r, widths)).rstrip())
        if i == 0:
            lines.append("  ".join("-" * w for w in widths))
    return "\n".join(lines)
