"""A span's durations grouped by what its step carried.  Takes the tracer's
``span`` spans (``serve.device_step``), finds each one's ``ancestor``
(``serve.step``) through ``Span.parent`` and groups by the ancestor's
``prefill_tokens``: ``decode_only`` (0) or ``with_prefill`` (> 0).  Returns the
group's median in ms, None for an empty group, and for a build whose spans have
no ``parent``.

A metric's ``reader``::

    {"file": "step_groups.py", "span": "serve.device_step",
     "ancestor": "serve.step", "group": "decode_only" | "with_prefill"}

It also prints each group's count and how far the tracer's spans fall from
their own ``TraceAnnotation`` events in the captured trace.  The captured trace
counts from its own start, which Python cannot read, so the two clocks are
anchored on the first matched pair, here, and what is reported is how far every
other pair falls from it."""
from __future__ import annotations

import statistics
from typing import Dict, List, Optional

from benchmark.harness.runtime import say


def grouped(spans: List, span: str, ancestor: str) -> Optional[Dict[str, List[float]]]:
    if not spans or not hasattr(spans[0], "parent"):
        return None
    by_id = {s.id: s for s in spans}
    groups: Dict[str, List[float]] = {"decode_only": [], "with_prefill": []}
    for s in spans:
        if s.name != span:
            continue
        up = by_id.get(s.parent)
        while up is not None and up.name != ancestor:
            up = by_id.get(up.parent)
        fields = up.args if up is not None and up.args else {}
        if "prefill_tokens" not in fields:
            continue
        key = "with_prefill" if fields["prefill_tokens"] > 0 else "decode_only"
        groups[key].append(s.dur_ns / 1e6)
    return groups


def clock_offsets_us(tracer, host: List, name: str) -> List[float]:
    """The k-th ``name`` span's start against the k-th ``name`` annotation's in
    the captured trace, less what the first pair differs by, in microseconds."""
    mine = sorted(s.t0_ns for s in tracer.spans() if s.name == name)
    theirs = sorted(e[1] for e in host if e[0] == name)
    if not mine or len(mine) != len(theirs):
        return []
    origin = mine[0] - theirs[0]
    return [(a - b - origin) / 1e3 for a, b in zip(mine, theirs)]


def read(spec: Dict, run: Dict, ctx: Dict) -> Optional[float]:
    session = run.get("session")
    tracer = getattr(session, "tracer", None)
    if tracer is None:
        return None
    groups = grouped(tracer.spans(), spec["span"], spec["ancestor"])
    if groups is None:
        return None
    mine = groups[spec["group"]]
    if not getattr(session, "step_groups_said", False):     # once a run
        session.step_groups_said = True
        say(f"{spec['span']} by its {spec['ancestor']}: "
            + ", ".join(f"{k} {len(v)} spans" + (f" (median {statistics.median(v):.3f} ms)"
                                                 if v else "") for k, v in groups.items()))
        off = (clock_offsets_us(tracer, session.trace["host"], spec["ancestor"])
               if getattr(session, "trace", None) else [])
        if off:
            say(f"{spec['ancestor']} spans, anchored on the first pair, fall "
                f"{statistics.median(off):.1f} us (median; worst "
                f"{max(off, key=abs):.1f}) from their annotations, {len(off)} pairs")
    return statistics.median(mine) if mine else None
