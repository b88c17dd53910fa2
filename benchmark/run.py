#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Resolves the cell's files by name, picks the runner by the traffic file's
``kind``, measures for ``--seconds`` after set-up, and prints as the LAST line
of standard output one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` and, traced, ``breakdown``.  ``--trace 0`` reports the
cell's end-to-end metrics, ``--trace 1`` its per-layer metrics.  Without a TPU
(or with fewer chips than the cell asks for) it exits non-zero within seconds,
names the device it found and prints no result.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None,
                    help="sweep only: offer an open-loop cell this rate instead of its own")
    args = ap.parse_args(argv)

    from benchmark.harness import manifest, peaks, readers, runtime

    runtime.T_PROCESS_START = _T0
    ctx = manifest.resolve_cell(args.workload)
    seconds = args.seconds if args.seconds is not None else ctx["manifest"]["run_seconds"]
    runner = importlib.import_module(f"benchmark.runners.{ctx['traffic']['kind']}")
    extra = {"rate": args.rate} if args.rate is not None else {}
    try:
        run = runner.run(ctx, seed=args.seed, seconds=seconds, trace=bool(args.trace), **extra)
    except runtime.NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr, flush=True)
        return 2

    devices = run["devices"]
    ctx["peaks"] = peaks.peaks_for(devices[0].device_kind)
    run["memory"] = runtime.memory_stats(devices)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(run["memory"].get("peak_bytes_in_use", 0))}
    if args.trace:
        wanted = ctx["per_layer"]
        values = {m["name"]: readers.read(m, run, ctx) for m in wanted}
        reduced = run["session"].reduced
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
    else:
        wanted = ctx["end_to_end"]
        values = {m["name"]: run["end_to_end"].get(m["name"]) for m in wanted}
    units = {m["name"]: m["unit"] for m in wanted}
    missing = [n for n, v in values.items() if v is None]
    if missing:
        runtime.say(f"nothing to read for {missing}: left out of the line")
    result = {
        "correct": bool(run["correct"]),
        "attempted": int(run["attempted"]),
        "failed": int(run["failed"]),
        "metrics": {n: {"value": float(v), "unit": units[n]}
                    for n, v in values.items() if v is not None},
        "device": device,
    }
    if args.trace:
        result["breakdown"] = reduced["breakdown"]
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
