"""From trace events to numbers: busy/idle union, per-operation self time,
kernel time, collective time and its exposed part, idle gaps by host span.
Input is the format ``events.py`` writes; nothing here touches JAX."""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]
COLLECTIVES = r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all"
SHORT_GAP_NS = 20_000
SHORT_GAPS = "between_operations(<20us_each)"


_HLO = re.compile(r"^%?(\S+) = \(?(\w+\[[\d,]*\])")


def short_name(op: str) -> str:
    """The trace names an operation by its whole HLO line; the break-down
    keeps the instruction's name, its (first) result type and whether it is a
    Mosaic kernel: ``closed_call.14 bf16[48,16,8,128] tpu_custom_call``."""
    m = _HLO.match(op)
    if not m:
        return op[:120]
    return " ".join(m.groups()) + (" tpu_custom_call" if "tpu_custom_call" in op else "")


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, disjoint intervals."""
    out: List[List[int]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals: Iterable[Interval]) -> int:
    return sum(b - a for a, b in intervals)


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if min(b, hi) > max(a, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The part of union ``a`` that no interval of union ``b`` covers."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def intervals_of(ops: Sequence[Sequence], match: Optional[str] = None) -> List[Interval]:
    pat = re.compile(match) if match else None
    return [(s, s + d) for name, s, d in ops if pat is None or pat.search(name)]


def window_of(trace: Dict, span: str = "bench.trace_window") -> Interval:
    """The traced window: the host span of that name if the trace has one,
    else from the first device operation's start to the last one's end."""
    for name, s, d, *_ in trace["host"]:
        if name == span:
            return (s, s + d)
    every = [iv for ops in trace["devices"].values() for iv in intervals_of(ops)]
    return (min(a for a, _ in every), max(b for _, b in every))


def self_times(ops: Sequence[Sequence], lo: int, hi: int) -> Dict[str, int]:
    """Per operation name, the time inside ``[lo, hi)`` that no operation
    nested in it covers (a ``while`` that holds its body's operations is left
    with its own overhead only).  An operation that overlaps another without
    lying inside it runs beside it and keeps its whole time."""
    out: Dict[str, int] = {}
    stack: List[List] = []          # [name, end, self_ns]

    def close(until: int):
        while stack and stack[-1][1] <= until:
            name, _, own = stack.pop()
            out[name] = out.get(name, 0) + own

    for name, s, d in sorted(ops, key=lambda e: (e[1], -e[2])):
        a, b = max(s, lo), min(s + d, hi)
        if b <= a:
            continue
        close(a)
        if stack and b > stack[-1][1]:
            # overlaps the open operation without lying inside it: an
            # asynchronous operation running beside it, not a part of it
            out[name] = out.get(name, 0) + (b - a)
            continue
        if stack:
            stack[-1][2] -= b - a
        stack.append([name, b, b - a])
    close(hi + 1)
    return out


def calls(ops: Sequence[Sequence], match: str, lo: int, hi: int) -> int:
    pat = re.compile(match)
    return sum(1 for name, s, d in ops if lo <= s < hi and pat.search(name))


def device_summary(ops: Sequence[Sequence], lo: int, hi: int) -> Dict:
    """One device over the window: busy (union of its operations), collective
    time and the part of it during which nothing else ran there."""
    busy = clip(union(intervals_of(ops)), lo, hi)
    coll = clip(union(intervals_of(ops, COLLECTIVES)), lo, hi)
    other = clip(union((s, s + d) for name, s, d in ops
                       if not re.search(COLLECTIVES, name)
                       and not name.startswith(("while", "conditional", "call"))), lo, hi)
    return {"busy_ns": total(busy), "collective_ns": total(coll),
            "collective_exposed_ns": total(subtract(coll, other)),
            "busy": busy}


def idle_gaps(busy: Sequence[Interval], host: Sequence[Sequence], lo: int,
              hi: int, top: int = 10, window_span: str = "bench.trace_window") -> List[List]:
    """The window's idle time by what the host was doing: each gap goes to the
    innermost (shortest) host span that covers its mid-point, or failing that
    to the span that overlaps it most; gaps under 20 us are summed under one
    name.  The span that marks the traced window itself explains nothing and
    is not a candidate."""
    spans = [(s, s + d, name) for name, s, d, *_ in host if name != window_span]
    by: Dict[str, int] = {}
    for a, b in subtract([(lo, hi)], list(busy)):
        if b - a < SHORT_GAP_NS:
            by[SHORT_GAPS] = by.get(SHORT_GAPS, 0) + (b - a)
            continue
        mid = (a + b) // 2
        covering = [(e - s, name) for s, e, name in spans if s <= mid < e]
        if covering:
            best = min(covering)[1]
        else:
            over = [(min(b, e) - max(a, s), name) for s, e, name in spans
                    if min(b, e) > max(a, s)]
            best = max(over)[1] if over else "no_host_span"
        by[best] = by.get(best, 0) + (b - a)
    ranked = sorted(by.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns / 1e9] for name, ns in ranked]


def reduce_trace(trace: Dict, top: int = 10) -> Dict:
    """Everything the readers and the result line take from one trace."""
    lo, hi = window_of(trace)
    per_device = {dev: device_summary(ops, lo, hi)
                  for dev, ops in trace["devices"].items() if ops}
    if not per_device:
        raise ValueError("the trace holds no device operation")
    op_ns: Dict[str, float] = {}
    for ops in trace["devices"].values():
        for name, ns in self_times(ops, lo, hi).items():
            op_ns[name] = op_ns.get(name, 0) + ns
    n = len(per_device)
    op_s = {name: ns / n / 1e9 for name, ns in op_ns.items()}
    fullest = max(per_device, key=lambda d: per_device[d]["busy_ns"])
    window_s = (hi - lo) / 1e9
    return {
        "window_ns": (lo, hi),
        "window_s": window_s,
        "busy_s": sum(d["busy_ns"] for d in per_device.values()) / n / 1e9,
        "op_s": op_s,
        "collective_share_worst": max(d["collective_ns"] for d in per_device.values())
        / (hi - lo),
        "collective_exposed_share_worst": max(
            d["collective_exposed_ns"] for d in per_device.values()) / (hi - lo),
        "breakdown": {
            "device_ops": [[short_name(name), s] for name, s in
                           sorted(op_s.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": idle_gaps(per_device[fullest]["busy"], trace["host"], lo, hi, top),
        },
    }
