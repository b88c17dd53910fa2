"""``BENCHMARK.json`` and the files it names.  Everything that belongs to one
cell, configuration, traffic mix or per-layer metric sits in a file of its
own, found here BY NAME: adding one is adding files and appending entries."""
from __future__ import annotations

import json
import os
import re
from typing import Dict, List

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> Dict:
    return _load(os.path.join(root, "BENCHMARK.json"))


def _for_cell(metrics: List[Dict], cell: str) -> List[Dict]:
    return [m for m in metrics if "workloads" not in m or cell in m["workloads"]]


def resolve_cell(name: str, root: str = ROOT) -> Dict:
    """Everything a run of cell ``name`` needs: the manifest's entries and the
    four kinds of file, each found by the name the entry gives."""
    manifest = load_manifest(root)
    bench = os.path.join(root, manifest["paths"][0])
    entry = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                       f"{[w['name'] for w in manifest['workloads']]}")
    cfg_entry = next(c for c in manifest["configs"] if c["name"] == entry["config"])
    layer_metrics = []
    for m in _for_cell(manifest["per_layer"], name):
        spec = _load(os.path.join(bench, "layer_metrics", m["name"] + ".json"))
        layer_metrics.append({**m, "reader": spec["reader"]})
    return {
        "manifest": manifest,
        "bench_dir": bench,
        "entry": entry,
        "cell": _load(os.path.join(bench, "cells", name + ".json")),
        "config": _load(os.path.join(root, cfg_entry["file"])),
        "traffic": _load(os.path.join(bench, "traffic", entry["traffic"] + ".json")),
        "end_to_end": _for_cell(manifest["end_to_end"], name),
        "per_layer": layer_metrics,
    }


def validate(manifest: Dict) -> List[str]:
    """The contract's rules that can be checked without a run; returns the
    faults found (empty when the manifest is valid)."""
    faults = []

    def name_ok(kind, value):
        if not isinstance(value, str) or not NAME.match(value):
            faults.append(f"{kind} name {value!r} is not a valid name")

    want = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(manifest) != want:
        faults.append(f"keys {sorted(manifest)} are not exactly {sorted(want)}")
        return faults
    if not (isinstance(manifest["run_seconds"], int) and 1 <= manifest["run_seconds"] <= 51):
        faults.append(f"run_seconds {manifest['run_seconds']!r} not a whole number in 1..51")
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in manifest[group]:
            name_ok(group, e.get("name"))
            key = ("metric" if group in ("end_to_end", "per_layer") else group,
                   e.get("name"))
            if key in seen:
                faults.append(f"{key[0]} name {e.get('name')!r} appears twice")
            seen.add(key)
    configs = {c["name"]: c for c in manifest["configs"]}
    cells = {w["name"]: w for w in manifest["workloads"]}
    for c in manifest["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            faults.append(f"config {c.get('name')!r} has keys {sorted(c)}")
        for key in c.get("reduced", []):
            name_ok("reduced key", key)
        if not any(c["file"].startswith(p + "/") for p in manifest["paths"]):
            faults.append(f"config file {c['file']!r} lies outside paths")
    pairs = set()
    for w in manifest["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            faults.append(f"workload {w.get('name')!r} has keys {sorted(w)}")
        name_ok("traffic", w.get("traffic"))
        if w.get("config") not in configs:
            faults.append(f"workload {w['name']!r} names no configuration")
        if w.get("chips") not in (1, 4):
            faults.append(f"workload {w['name']!r} asks for {w.get('chips')!r} chips")
        if not 1 <= len(w.get("why", "")) <= 200:
            faults.append(f"workload {w['name']!r}: why is empty or over 200 characters")
        pair = (w.get("config"), w.get("traffic"))
        if pair in pairs:
            faults.append(f"pair {pair} appears twice")
        pairs.add(pair)
    for name in configs:
        if not any(w["config"] == name for w in manifest["workloads"]):
            faults.append(f"configuration {name!r} is used by no cell")
    four = sum(1 for w in manifest["workloads"] if w.get("chips") == 4)
    if four > max(1, len(cells) // 4):
        faults.append(f"{four} of {len(cells)} cells ask for four chips")

    def reported_in(metric):
        return set(metric.get("workloads", cells))

    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    if "setup_s" not in e2e:
        faults.append("no setup_s among the end-to-end metrics")
    for m in manifest["end_to_end"]:
        extra = set(m) - {"name", "unit", "better", "bound", "source", "workloads"}
        if extra:
            faults.append(f"end-to-end metric {m['name']!r} has extra keys {sorted(extra)}")
        if m.get("source") not in ("host_clock", "device_trace"):
            faults.append(f"end-to-end metric {m['name']!r}: source {m.get('source')!r}")
        if not (isinstance(m.get("bound"), (int, float)) and 0 < m["bound"] <= 0.1):
            faults.append(f"end-to-end metric {m['name']!r}: bound {m.get('bound')!r}")
    for m in manifest["per_layer"]:
        extra = set(m) - {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        if extra:
            faults.append(f"per-layer metric {m['name']!r} has extra keys {sorted(extra)}")
        if m.get("source") not in SOURCES:
            faults.append(f"per-layer metric {m['name']!r}: source {m.get('source')!r}")
        moved = e2e.get(m.get("moves"))
        if moved is None:
            faults.append(f"per-layer metric {m['name']!r} moves no end-to-end metric")
        elif not reported_in(m) <= reported_in(moved):
            faults.append(f"per-layer metric {m['name']!r} is reported in "
                          f"{sorted(reported_in(m) - reported_in(moved))}, "
                          f"where {m['moves']!r} is not")
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if not UNIT.match(str(m.get("unit", ""))):
            faults.append(f"metric {m['name']!r}: unit {m.get('unit')!r}")
        if m.get("better") not in ("lower", "higher"):
            faults.append(f"metric {m['name']!r}: better {m.get('better')!r}")
        for cell in m.get("workloads", []):
            if cell not in cells:
                faults.append(f"metric {m['name']!r} lists unknown cell {cell!r}")
    for cell in cells:
        mine = [m["name"] for m in _for_cell(manifest["end_to_end"], cell)]
        if "setup_s" not in mine or len(mine) < 2:
            faults.append(f"cell {cell!r} reports {mine}: needs setup_s and one more")
        if not _for_cell(manifest["per_layer"], cell):
            faults.append(f"cell {cell!r} reports no per-layer metric")
    return faults
