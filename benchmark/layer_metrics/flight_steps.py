"""A step's length and the collector's pauses, from the program's own account.

The engine records one ``serve.flight`` span a fused step, from the step's
enqueue (``t0_ns``) to its tokens on the host (``t0_ns + dur_ns``), with the
fields ``seq``, ``prefill_tokens``, ``drained`` (the device's queue was empty
when it was enqueued), ``ready_at_read`` (its output was complete when its read
began) and ``prev_ready_ns`` (when its predecessor's tokens were on the host).
A flight's step ran from ``t0_ns`` where ``drained``, else from
``prev_ready_ns``, to its end.  It is KEPT only where both ends are the
device's: its own read blocked (``ready_at_read`` false), and it either met an
empty device or its predecessor's read blocked too.  Kept steps are grouped by
the flight's OWN ``prefill_tokens``.  ``host.gc`` spans are the collector's
pauses; their share is of the stretch the tracer's other spans cover.

A metric's ``reader``::

    {"file": "flight_steps.py", "value": "step_ms",
     "group": "decode_only" | "with_prefill" | "all"}
    {"file": "flight_steps.py", "value": "gc_pause_share"}

Returns None on a build whose tracer holds no ``serve.flight`` span with a
``seq``.  Prints, once a run, how many flights it kept and dropped and why,
and each pause's span."""
from __future__ import annotations

import statistics
from typing import Dict, List, Optional

from benchmark.harness.runtime import say

FLIGHT, GC = "serve.flight", "host.gc"


def flights_of(spans: List) -> Dict[int, object]:
    return {s.args["seq"]: s for s in spans
            if s.name == FLIGHT and s.args and "seq" in s.args}


def steps(spans: List) -> Optional[Dict]:
    """``{"kept": {group: [ms, ...]}, "dropped": {reason: n}}``, or None."""
    flights = flights_of(spans)
    if not flights:
        return None
    kept: Dict[str, List[float]] = {"decode_only": [], "with_prefill": []}
    dropped = {"ready_at_read": 0, "start_unknown": 0}
    for seq in sorted(flights):
        s = flights[seq]
        a = s.args
        if a["ready_at_read"]:
            dropped["ready_at_read"] += 1
            continue
        if a["drained"]:
            start = s.t0_ns
        else:
            prev = flights.get(seq - 1)
            if prev is None or prev.args["ready_at_read"] or a["prev_ready_ns"] is None:
                dropped["start_unknown"] += 1
                continue
            start = max(s.t0_ns, a["prev_ready_ns"])
        group = "with_prefill" if a["prefill_tokens"] > 0 else "decode_only"
        kept[group].append((s.t0_ns + s.dur_ns - start) / 1e6)
    return {"kept": kept, "dropped": dropped}


def pauses(spans: List) -> Optional[Dict]:
    """The ``host.gc`` spans against the stretch the other spans cover; None
    where the build records neither them nor flights."""
    mine = [s for s in spans if s.name == GC]
    if not mine and not flights_of(spans):
        return None
    others = [s for s in spans if s.name not in (GC, FLIGHT)]
    if not others:
        return None
    window_ns = max(s.t0_ns + s.dur_ns for s in others) - min(s.t0_ns for s in others)
    by_id = {s.id: s for s in spans}
    under: Dict[str, int] = {}
    for s in mine:
        up = by_id.get(s.parent)
        name = up.name if up is not None else "no_span"
        under[name] = under.get(name, 0) + s.dur_ns
    return {"count": len(mine), "total_ns": sum(s.dur_ns for s in mine),
            "longest": max(mine, key=lambda s: s.dur_ns) if mine else None,
            "under": under, "window_ns": window_ns}


def _say(got: Optional[Dict], gc: Optional[Dict]):
    if got is not None:
        kept, dropped = got["kept"], got["dropped"]
        say(f"{FLIGHT}: kept {sum(len(v) for v in kept.values())} steps ("
            + ", ".join(f"{k} {len(v)}" + (f", median {statistics.median(v):.3f} ms, mean "
                                            f"{statistics.fmean(v):.3f}" if v else "")
                        for k, v in kept.items())
            + f"), dropped {sum(dropped.values())} ({dropped})")
    if gc is not None:
        longest = gc["longest"]
        say(f"{GC}: {gc['count']} pauses, {gc['total_ns'] / 1e6:.3f} ms of "
            f"{gc['window_ns'] / 1e6:.1f} ms"
            + (f", longest {longest.dur_ns / 1e6:.3f} ms (generation "
               f"{(longest.args or {}).get('generation')}); ms under each span: "
               + str({k: round(v / 1e6, 3) for k, v in sorted(gc["under"].items())})
               if longest is not None else ""))


def read(spec: Dict, run: Dict, ctx: Dict) -> Optional[float]:
    session = run.get("session")
    tracer = getattr(session, "tracer", None)
    if tracer is None:
        return None
    reduced = getattr(session, "flight_steps", None)
    if reduced is None:                                  # once a run
        spans = tracer.spans()
        reduced = session.flight_steps = (steps(spans), pauses(spans))
        _say(*reduced)
    got, gc = reduced
    if spec["value"] == "gc_pause_share":
        return 100.0 * gc["total_ns"] / gc["window_ns"] if gc and gc["window_ns"] else None
    if got is None:
        return None
    kept = got["kept"]
    mine = kept["decode_only"] + kept["with_prefill"] if spec["group"] == "all" \
        else kept[spec["group"]]
    return statistics.median(mine) if mine else None
