"""Device time by program scope.  Joins the traced window's per-operation self
times (``benchmark.trace_reduce.reduce.self_times``) with the program's own map
from an optimized-HLO instruction name to the ``jax.named_scope`` path that made
it (``run["session"].tracer.program_scopes()``, ``paddle_tpu/telemetry/scopes.py``)
and sums what the metric's file selects.

A metric's ``reader``::

    {"file": "scope_time.py",
     "select": [{"scopes": "<regex on the scope path>", "ops": "<regex on the
                 instruction name>", "backward": bool, "recompute": bool,
                 "carry": bool}, ...],          # an operation counts if ANY clause holds
     "value": "ms_per" | "share" | "ms_per_call",
     "per": "counters:trace:fused_steps" | "facts:traced_steps"}   # for ms_per

``share`` is a percentage of all operations' self time (on one chip that is the
busy time: nothing overlaps); ``ms_per_call`` divides by the selected
operations' Mosaic calls in the window.  The first metric read in a run prints
the whole table.  Where the program keeps no scope map (a build from before it
had scopes) every metric here is left out.

A trace event says which instruction ran, not which program's: the compiler
numbers ``copy.117`` in each module anew.  Where the window saw several
programs (the serving step has a greedy and a sampling variant), a name that
their maps put under different scopes is filed under ``unscoped`` by the rule
``ambiguous``, and said: it shows in ``device.serve_unscoped_share.*`` instead
of under a scope that may not be its own."""
from __future__ import annotations

import re
import time
from typing import Dict, List, Optional

from benchmark.harness.readers import _lookup
from benchmark.harness.runtime import say
from benchmark.trace_reduce.reduce import self_times

_NAME = re.compile(r"^%?(\S+) =")
_FLAGS = ("backward", "recompute", "carry")


def _instruction(line: str) -> str:
    """The instruction's name in a trace event's name (its whole HLO line)."""
    m = _NAME.match(line)
    return m.group(1) if m else line


def merged(maps: List[Dict]) -> Dict:
    """One map of several programs': a name keeps its scope where every
    program that has it agrees on scope and flags, else it is ``unscoped`` by
    the rule ``ambiguous``."""
    out: Dict = {}
    for one in maps:
        for name, s in one.items():
            had = out.setdefault(name, s)
            if had[:4] != s[:4]:
                if had.rule != "ambiguous":
                    say(f"  {name} is {had.scope} in one program and {s.scope} in "
                        f"another: unscoped")
                out[name] = type(s)("unscoped", rule="ambiguous")
    return out


def joined(run: Dict) -> Optional[List[Dict]]:
    """One row an instruction seen in the window: its scope, its self time a
    device (ns) and its Mosaic calls a device.  Cached on the session."""
    session = run.get("session")
    tracer = getattr(session, "tracer", None)
    if session is None or not hasattr(tracer, "program_scopes"):
        return None
    if getattr(session, "scope_rows", None) is not None:
        return session.scope_rows
    t0 = time.perf_counter()
    scopes = merged([one for maps in tracer.program_scopes().values() for one in maps])
    say(f"program_scopes(): {len(scopes)} instructions of "
        f"{sorted(tracer.programs)} in {time.perf_counter() - t0:.2f} s")
    if not scopes:
        return None
    lo, hi = session.reduced["window_ns"]
    devices = [ops for ops in session.trace["devices"].values() if ops]
    rows: Dict[str, Dict] = {}
    for ops in devices:
        for line, ns in self_times(ops, lo, hi).items():
            name = _instruction(line)
            row = rows.setdefault(name, {"name": name, "ns": 0.0, "calls": 0.0,
                                         "scope": scopes.get(name)})
            row["ns"] += ns / len(devices)
        for line, start, _ in ops:
            if lo <= start < hi and "tpu_custom_call" in line:
                rows[_instruction(line)]["calls"] += 1 / len(devices)
    session.scope_rows = list(rows.values())
    _say_table(session.scope_rows, run)
    return session.scope_rows


def _label(row: Dict) -> str:
    s = row["scope"]
    if s is None:
        return "not in the map"
    flags = [f for f in ("recompute", "carry") if getattr(s, f)]
    return s.scope + (" [" + ",".join(flags) + "]" if flags else "")


def _say_table(rows: List[Dict], run: Dict):
    steps = (_lookup(run, "counters:trace:fused_steps")
             or _lookup(run, "facts:traced_steps") or 1)
    total = sum(r["ns"] for r in rows) or 1.0
    by: Dict[str, List[float]] = {}
    for r in rows:
        got = by.setdefault(_label(r), [0.0, 0])
        got[0] += r["ns"]
        got[1] += 1
    busy = run["session"].reduced["busy_s"]
    say(f"device time by scope: {total / 1e6 / steps:.3f} ms a step over {steps} steps "
        f"(all operations' self time; busy time is {1e3 * busy / steps:.3f})")
    for label, (ns, n) in sorted(by.items(), key=lambda kv: -kv[1][0]):
        say(f"  scope {label:58s} {ns / 1e6 / steps:10.3f} ms/step "
            f"{100 * ns / total:6.2f} %  {n:4d} operations")
    # the longest operations, and the longest of those whose scope a rule gave
    ranked = sorted(rows, key=lambda r: -r["ns"])
    given = [r for r in ranked if r["scope"] is None or r["scope"].rule != "own"]
    for r in ranked[:24] + [r for r in given[:12] if r not in ranked[:24]]:
        rule = r["scope"].rule if r["scope"] is not None else "-"
        say(f"  by rule '{rule}': {r['name']} -> {_label(r)} "
            f"{r['ns'] / 1e6 / steps:.3f} ms/step")


def _holds(clause: Dict, row: Dict) -> bool:
    s = row["scope"]
    scope = s.scope if s is not None else "unscoped"
    if "scopes" in clause and not re.search(clause["scopes"], scope):
        return False
    if "ops" in clause and not re.search(clause["ops"], row["name"]):
        return False
    return all(bool(getattr(s, f, False)) == clause[f] for f in _FLAGS if f in clause)


def read(spec: Dict, run: Dict, ctx: Dict) -> Optional[float]:
    rows = joined(run)
    if not rows:
        return None
    mine = [r for r in rows if any(_holds(c, r) for c in spec["select"])]
    ns = sum(r["ns"] for r in mine)
    if spec["value"] == "share":
        return 100.0 * ns / sum(r["ns"] for r in rows)
    if spec["value"] == "ms_per_call":
        calls = sum(r["calls"] for r in mine)
        return ns / 1e6 / calls if calls else None
    per = _lookup(run, spec["per"])
    return ns / 1e6 / per if per else None
