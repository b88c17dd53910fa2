"""From the profiler's ``.xplane.pb`` to the reduction's own input format,
with nothing but ``jax.profiler.ProfileData``.

The format (JSON, gzip on disk)::

    {"devices": {"0": [[name, start_ns, dur_ns], ...], ...},   # device operations
     "host":    [[name, start_ns, dur_ns, thread], ...],       # host spans kept
     "meta":    {...}}

Device operations are the events of each ``/device:TPU:<n>`` plane's "XLA Ops"
line.  Host spans are the ``TraceAnnotation`` events of the host planes whose
names start with one of ``host_prefixes`` (the program's ``serve.*``/``jit.*``
spans and the benchmark's own ``bench.*``).  All times are nanoseconds on the
profiler's one clock.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Dict, Iterable, Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SUMMARY_LINES = ("Step", "XLA Modules", "XLA TraceMe", "Framework")
HOST_PREFIXES = ("serve.", "jit.", "bench.")


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def extract(xplane_path: str, host_prefixes: Iterable[str] = HOST_PREFIXES) -> Dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane_path)
    prefixes = tuple(host_prefixes)
    devices: Dict[str, list] = {}
    host = []
    seen_lines = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        seen_lines[plane.name] = [line.name for line in plane.lines]
        if m:
            names = [line.name for line in plane.lines]
            for line in plane.lines:
                # the operations' own line; a trace without one gives every
                # line that is not a summary of steps or whole modules
                if (line.name != OPS_LINE if OPS_LINE in names
                        else line.name.startswith(SUMMARY_LINES)):
                    continue
                ops = devices.setdefault(m.group(1), [])
                for ev in line.events:
                    ops.append([ev.name, int(ev.start_ns), int(ev.duration_ns)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(prefixes):
                        host.append([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns), line.name])
    for ops in devices.values():
        ops.sort(key=lambda e: e[1])
    host.sort(key=lambda e: e[1])
    return {"devices": devices, "host": host, "meta": {"lines": seen_lines}}


def save(trace: Dict, path: str):
    with gzip.open(path, "wt") as f:
        json.dump(trace, f, separators=(",", ":"))


def load(path: str) -> Dict:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        return json.load(f)
