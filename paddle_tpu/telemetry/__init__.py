"""Unified telemetry: metrics registry + host span tracing.

Two cooperating halves (docs/observability.md):

- :mod:`metrics` — a process-wide, lock-cheap registry of Counters,
  Gauges, and log-bucketed Histograms (labeled; JSON snapshot +
  Prometheus text exposition).  The serving engine's fault/shed/
  occupancy counters and the per-request SLO histograms (TTFT,
  inter-token latency, queue wait, end-to-end) live here.
- :mod:`trace` — a ring-buffered, thread-aware host span tracer (a
  context manager) exporting Chrome-trace/Perfetto JSON, with each span
  nesting a ``jax.profiler.TraceAnnotation`` so host phases align with
  the device timeline when an XLA capture is active.
- :mod:`scopes` — the ``jax.named_scope`` vocabulary of the compiled
  programs and the map from a device trace's operations back to it.

All are import-light (no jax at import time) so the disabled path stays
near-zero; ``paddle_tpu.profiler`` is the user-facing facade and
``enable(capacity=)`` the one switch.
"""
from __future__ import annotations

from . import metrics, scopes, trace  # noqa: F401
from .metrics import (  # noqa: F401
    Counter, CounterSet, Gauge, Histogram, Registry, registry,
)
from .trace import (  # noqa: F401
    Span, Tracer, active, disable, enable, export_chrome_trace, span,
    summarize,
)

__all__ = [
    "metrics", "scopes", "trace",
    "Counter", "CounterSet", "Gauge", "Histogram", "Registry", "registry",
    "Span", "Tracer", "active", "disable", "enable", "export_chrome_trace",
    "span", "summarize",
]
