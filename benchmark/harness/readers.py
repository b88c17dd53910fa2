"""The per-layer readers: a small fixed vocabulary, implemented once.  A
metric's file (``benchmark/layer_metrics/<name>.json``) names one with its
parameters, or names a reader file of its own (``{"file": "x.py"}`` holding a
``read(params, run, ctx)`` function) that a later PR adds beside it.  A reader
that finds nothing to read returns None and the metric is left out."""
from __future__ import annotations

import importlib
import importlib.util
import os
import re
from typing import Callable, Dict, Optional

from . import estimators
from .runtime import say


def _spans(run, name):
    session = run.get("session")
    if session is None or getattr(session, "tracer", None) is None:
        return []
    return [s for s in session.tracer.spans() if s.name == name]


def span_self_time(p, run, ctx):
    """Mean time of ``span`` not covered by the ``minus`` spans, ms a span."""
    own = _spans(run, p["span"])
    if not own:
        return None
    inner = sum(s.dur_ns for name in p.get("minus", []) for s in _spans(run, name))
    return (sum(s.dur_ns for s in own) - inner) / len(own) / 1e6


def span_percentile(p, run, ctx):
    own = [s.dur_ns / 1e6 for s in _spans(run, p["span"])]
    return estimators.percentile(own, float(p["q"]))


def _counters(p, run):
    return run["counters"].get(p.get("over", "window")) or {}


def counter_delta(p, run, ctx):
    c = _counters(p, run)
    return c[p["counter"]] * p.get("scale", 1.0) if p["counter"] in c else None


def counter_ratio(p, run, ctx):
    c = _counters(p, run)
    if not c:
        return None
    den = sum(c[k] for k in p["den"])
    return p.get("scale", 1.0) * sum(c[k] for k in p["num"]) / den if den else None


def clock_percentile(p, run, ctx):
    """A percentile (or ``"mean"``) of one of the runner's series of readings,
    times ``scale``, over ``per`` (a fact) when given."""
    values = run["clocks"].get(p["clock"]) or []
    values = [v for v in values if v != float("inf")] if p.get("finite") else values
    got = (estimators.mean(values) if p["q"] == "mean"
           else estimators.percentile(values, float(p["q"])))
    if got is None:
        return None
    if "per" in p:
        got /= run["facts"][p["per"]]
    return got * p.get("scale", 1.0)


def _reduced(run):
    session = run.get("session")
    return session.reduced if session is not None else None


def _matched_s(reduced, pattern) -> float:
    pat = re.compile(pattern)
    return sum(s for name, s in reduced["op_s"].items() if pat.search(name))


def trace_op_share(p, run, ctx):
    r = _reduced(run)
    return 100.0 * _matched_s(r, p["match"]) / r["busy_s"] if r and r["busy_s"] else None


def trace_idle_share(p, run, ctx):
    r = _reduced(run)
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"]) if r else None


def trace_exposed_share(p, run, ctx):
    r = _reduced(run)
    if not r:
        return None
    key = "collective_exposed_share_worst" if p.get("exposed") else "collective_share_worst"
    return 100.0 * r[key]


def _lookup(run, path):
    kind, _, key = path.partition(":")
    if kind == "facts":
        return run["facts"].get(key)
    if kind == "counters":
        over, _, name = key.partition(":")
        return (run["counters"].get(over) or {}).get(name)
    raise KeyError(path)


def trace_busy_per_step(p, run, ctx):
    r, steps = _reduced(run), _lookup(run, p["steps"])
    return 1e3 * r["busy_s"] / steps if r and steps else None


def roofline_share(p, run, ctx):
    """The least time the chip could take (the larger of operations over peak
    FLOP/s and bytes over peak bytes/s) over the kernel's traced time."""
    from ..trace_reduce import reduce

    r, session = _reduced(run), run.get("session")
    if not r:
        return None
    module = importlib.import_module(p["module"])
    full = dict(ctx, facts=run["facts"])
    if "kernels" in p:      # per-kernel call counts from the trace
        lo, hi = r["window_ns"]
        ops = [e for dev in session.trace["devices"].values() for e in dev]
        n_dev = len(session.trace["devices"])
        counts = {k: reduce.calls(ops, pat, lo, hi) / n_dev for k, pat in p["kernels"].items()}
        need = module.needed_by_calls(full, counts)
        kernel_s = sum(_matched_s(r, pat) for pat in p["kernels"].values())
    else:                   # the steps' real work lists, from the engine's counters
        need = module.needed_by_counters(full, run["counters"]["trace"])
        kernel_s = _matched_s(r, p["match"])
    if not kernel_s:
        return None
    peaks = ctx["peaks"]
    t_flops = need["flops"] / peaks["bf16_flops_per_s"]
    t_bytes = need["bytes"] / peaks["hbm_bytes_per_s"]
    say(f"roofline {p['module']}: needs {need['flops']:.4g} operations "
        f"({1e3 * t_flops:.3f} ms at peak) and {need['bytes']:.4g} bytes "
        f"({1e3 * t_bytes:.3f} ms at peak) against {1e3 * kernel_s:.3f} ms of kernel "
        f"time: bound by {'compute' if t_flops >= t_bytes else 'memory'}")
    return 100.0 * max(t_flops, t_bytes) / kernel_s


def memory_stat(p, run, ctx):
    stats = run.get("memory") or {}
    if p["num"] not in stats or not stats.get(p["den"]):
        return None
    return 100.0 * stats[p["num"]] / stats[p["den"]]


def derived(p, run, ctx):
    """A product of named quantities over another, times ``scale``."""
    from ..rooflines import model_flops

    def term(t):
        if isinstance(t, (int, float)):
            return t
        kind, _, key = t.partition(":")
        if kind == "peaks":
            return ctx["peaks"][key]
        if kind == "model_flops":
            return getattr(model_flops, key)(ctx["config"]["model"],
                                             ctx["traffic"]["sequence"])
        return _lookup(run, t)

    out = p.get("scale", 1.0)
    for t in p["numerator"]:
        v = term(t)
        if v is None:
            return None
        out *= v
    for t in p["denominator"]:
        v = term(t)
        if not v:
            return None
        out /= v
    return out


VOCABULARY: Dict[str, Callable] = {f.__name__: f for f in (
    span_self_time, span_percentile, counter_delta, counter_ratio, clock_percentile,
    trace_op_share, trace_idle_share, trace_exposed_share, trace_busy_per_step,
    roofline_share, memory_stat, derived)}


def read(metric: Dict, run: Dict, ctx: Dict) -> Optional[float]:
    spec = metric["reader"]
    if "file" in spec:      # a reader of the metric's own, beside its file
        path = os.path.join(ctx["bench_dir"], "layer_metrics", spec["file"])
        loaded = importlib.util.spec_from_file_location(
            "benchmark_reader_" + os.path.splitext(spec["file"])[0], path)
        module = importlib.util.module_from_spec(loaded)
        loaded.loader.exec_module(module)
        return module.read(spec, run, ctx)
    return VOCABULARY[spec["kind"]](spec, run, ctx)
