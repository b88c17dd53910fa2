"""The reader of the engine's own account of a step in flight
(``benchmark/layer_metrics/flight_steps.py``) on PLANTED spans, and the counter
metrics beside it on planted snapshots: every expected number is worked out
here by hand, never by the reader under test."""
import json
import os
import sys

import pytest

import benchmark_tiny_tree as tiny

sys.path.insert(0, tiny.REPO)

from benchmark.harness import manifest as bm  # noqa: E402
from benchmark.harness import readers  # noqa: E402
from paddle_tpu.telemetry.trace import Span  # noqa: E402

METRICS = os.path.join(tiny.REPO, "benchmark", "layer_metrics")
CTX = {"bench_dir": os.path.join(tiny.REPO, "benchmark")}
LAT = ["gpt_1p3b.serve_chat_r80"]
SAT = ["gpt_1p3b.serve_doc_sat", "lfm2_24b_a2b_cut.serve_reason_sat",
       "phi4_mini_flash.serve_longctx_sat"]
NEW = {
    "engine.flight_step_ms_decode_only.lat": ("ms", "lower", "program_span", LAT),
    "engine.flight_step_ms_with_prefill.lat": ("ms", "lower", "program_span", LAT),
    "engine.flight_step_ms_p50.sat": ("ms", "lower", "program_span", SAT),
    "engine.host_late_step_share.lat": ("%", "lower", "program_counter", LAT),
    "engine.host_late_step_share.sat": ("%", "lower", "program_counter", SAT),
    "engine.device_wait_share.lat": ("%", "higher", "program_counter", LAT),
    "engine.device_wait_share.sat": ("%", "higher", "program_counter", SAT),
    "engine.gc_pause_share.lat": ("%", "lower", "program_span", LAT),
    "engine.gc_pause_share.sat": ("%", "lower", "program_span", SAT),
}
MS = 1_000_000


def _metric(name):
    with open(os.path.join(METRICS, name + ".json")) as f:
        return json.load(f)


def _read(name, run):
    return readers.read(_metric(name), run, CTX)


class Tracer:
    def __init__(self, spans):
        self._spans = list(spans)

    def spans(self):
        return self._spans


def _run(spans, **counters):
    class Session:
        pass

    s = Session()
    s.tracer = Tracer(spans) if spans is not None else None
    return {"session": s, "counters": {"window": counters, "trace": {}}, "facts": {}}


def _span(name, t0_ms, t1_ms, id, parent=None, **args):
    return Span(name, int(t0_ms * MS), int((t1_ms - t0_ms) * MS), 1, "main", args or None,
                id, parent)


def _flight(seq, t_enq, t_ready, prefill=0, drained=False, ready_at_read=False,
            prev_ready="before", **more):
    """A ``serve.flight`` span in ms; ``prev_ready`` in ms, None, or (the
    default) left to ``_planted`` to fill in from the flight before."""
    args = dict(seq=seq, rows=prefill + 4, prefill_tokens=prefill, decode_rows=4,
                overlapped=True, drained=drained, ready_at_read=ready_at_read, wait_ns=0,
                prev_ready_ns=prev_ready, landed_in=None, **more)
    return _span("serve.flight", t_enq, t_ready, 1000 + seq, **args)


def _planted():
    """Nine flights of a window, ms.  A tick every 10 ms reads the flight the
    tick before enqueued; the device needs 10 ms a decode step, 14 with a chunk.

    seq 3: the window's first, its predecessor's span is not there -> dropped
    seq 4: decode, behind 3 whose read blocked: 30 -> 40 = 10.0
    seq 5: prefill, 40 -> 54 = 14.0
    seq 6: decode, 54 -> 64 = 10.0, but it was complete when its read began
           (the host came at 70): dropped, ready_at_read
    seq 7: decode, enqueued at 66 onto a drained device: 66 -> 77 = 11.0
           (its predecessor's end is the host's, its own enqueue is the start)
    seq 8: prefill, not drained, behind 7 whose read blocked: 77 -> 91 = 14.0
    seq 9: prefill, 91 -> 107 = 16.0
    seq 10: decode, 107 -> 116 = 9.0
    seq 11: decode behind 10; its predecessor's landing was not kept (a flight
            dropped unread between them): dropped, start unknown
    """
    flights = [
        _flight(3, 18, 30),
        _flight(4, 25, 40),
        _flight(5, 35, 54, prefill=8),
        _flight(6, 45, 70, ready_at_read=True),
        _flight(7, 66, 77, drained=True),
        _flight(8, 72, 91, prefill=8),
        _flight(9, 80, 107, prefill=16),
        _flight(10, 95, 116),
        _flight(11, 110, 130, prev_ready=None),
    ]
    by_seq = {f.args["seq"]: f for f in flights}
    for f in flights:
        if f.args["prev_ready_ns"] == "before":
            prev = by_seq.get(f.args["seq"] - 1)
            f.args["prev_ready_ns"] = prev.t0_ns + prev.dur_ns if prev else 17 * MS
    return flights


KEPT = {"decode_only": [10.0, 11.0, 9.0], "with_prefill": [14.0, 14.0, 16.0]}


@pytest.mark.parametrize("name,want", [
    ("engine.flight_step_ms_decode_only.lat", 10.0),
    ("engine.flight_step_ms_with_prefill.lat", 14.0),
    ("engine.flight_step_ms_p50.sat", 12.5),            # of 9, 10, 11, 14, 14, 16
])
def test_a_steps_length_from_the_flights_own_stamps(name, want, capsys):
    run = _run(_planted() + [_span("serve.step", 20, 131, 1)])
    assert _read(name, run) == pytest.approx(want)
    out = capsys.readouterr().out
    assert "serve.flight: kept 6 steps (decode_only 3, median 10.000 ms, mean 10.000, " \
           "with_prefill 3, median 14.000 ms, mean 14.667), dropped 3 " \
           "({'ready_at_read': 1, 'start_unknown': 2})" in out
    # said once a run, whichever metric asks first
    _read(name, run)
    assert "kept" not in capsys.readouterr().out


def test_the_reader_keeps_and_drops_what_its_docstring_says():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "flight_steps", os.path.join(METRICS, "flight_steps.py"))
    flight_steps = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(flight_steps)
    got = flight_steps.steps(_planted())
    assert {k: [round(v, 6) for v in vs] for k, vs in got["kept"].items()} == KEPT
    assert got["dropped"] == {"ready_at_read": 1, "start_unknown": 2}
    # a predecessor whose read did NOT block gives no start: 4 goes with 3
    flights = _planted()
    flights[0].args["ready_at_read"] = True
    got = flight_steps.steps(flights)
    assert got["kept"]["decode_only"] == pytest.approx([11.0, 9.0])
    assert got["dropped"] == {"ready_at_read": 2, "start_unknown": 2}
    # ...unless the flight met an empty device: then its own enqueue starts it
    flights[1].args["drained"] = True
    assert flight_steps.steps(flights)["kept"]["decode_only"] == pytest.approx([15.0, 11.0, 9.0])


@pytest.mark.parametrize("spans", [
    None,                                                # no tracer at all
    [],                                                  # a tracer that recorded nothing
    [_span("serve.step", 0, 10, 1, prefill_tokens=0),    # the parent's spans: no flight
     _span("serve.device_step", 2, 9, 2, parent=1)],
    [_span("serve.step", 0, 10, 1),                      # a flight span that lacks seq
     _span("serve.flight", 1, 9, 2, prefill_tokens=0)],
])
@pytest.mark.parametrize("name", sorted(n for n, v in NEW.items() if v[2] == "program_span"))
def test_a_build_without_the_spans_reads_nothing(name, spans, capsys):
    assert _read(name, _run(spans)) is None
    assert "kept" not in capsys.readouterr().out


@pytest.mark.parametrize("name", ["engine.gc_pause_share.lat", "engine.gc_pause_share.sat"])
def test_the_collectors_pauses_over_the_tracers_stretch(name, capsys):
    """Three pauses of 0.5, 2 and 0.25 ms in a stretch of 110 ms that the
    ticks' spans cover (20 -> 130; the flights, which start before the tracer
    did, do not stretch it)."""
    spans = _planted() + [
        _span("serve.step", 20, 60, 1), _span("serve.pack", 22, 30, 2, parent=1),
        _span("host.gc", 23, 23.5, 3, parent=2, generation=0, collected=4),
        _span("serve.step", 61, 130, 4), _span("serve.harvest", 100, 120, 5, parent=4),
        _span("host.gc", 101, 103, 6, parent=5, generation=2, collected=900),
        _span("host.gc", 60.25, 60.5, 7, generation=0, collected=0),
    ]
    assert _read(name, _run(spans)) == pytest.approx(100 * 2.75 / 110)
    out = capsys.readouterr().out
    assert "host.gc: 3 pauses, 2.750 ms of 110.0 ms, longest 2.000 ms (generation 2); " \
           "ms under each span: {'no_span': 0.25, 'serve.harvest': 2.0, 'serve.pack': 0.5}" in out
    # a build with flights and no collection in the stretch reads zero, not nothing
    quiet = [s for s in spans if s.name != "host.gc"]
    assert _read(name, _run(quiet)) == 0.0
    assert "host.gc: 0 pauses, 0.000 ms of 110.0 ms\n" in capsys.readouterr().out


@pytest.mark.parametrize("name,counters,want", [
    ("engine.host_late_step_share.lat", dict(host_late_steps=3, fused_steps=600), 0.5),
    ("engine.host_late_step_share.sat", dict(host_late_steps=0, fused_steps=1900), 0.0),
    ("engine.host_late_step_share.sat", dict(fused_steps=1900), None),       # the parent
    ("engine.host_late_step_share.lat", dict(host_late_steps=0, fused_steps=0), None),
    ("engine.device_wait_share.lat", dict(land_wait_ns=18_000_000_000, t=45.0), 40.0),
    ("engine.device_wait_share.sat", dict(land_wait_ns=40_500_000_000, t=45.0), 90.0),
    ("engine.device_wait_share.sat", dict(t=45.0), None),                    # the parent
])
def test_the_counter_metrics_read_the_windows_snapshots(name, counters, want):
    got = _read(name, _run(None, **counters))
    assert got is None if want is None else got == pytest.approx(want)


def test_the_new_metrics_are_appended_with_their_cells():
    """Nine entries, appended behind what was there, each with its cells, one
    layer, and a file that names the reader."""
    manifest = bm.load_manifest()
    per_layer = manifest["per_layer"]
    names = [m["name"] for m in per_layer]
    first = names.index("engine.flight_step_ms_decode_only.lat")
    assert names[first - 1] == "engine.overlapped_step_share.sat"
    assert names[first:first + 9] == list(NEW)
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for m in per_layer[first:first + 9]:
        unit, better, source, cells = NEW[m["name"]]
        assert (m["unit"], m["better"], m["source"], m["workloads"]) == \
            (unit, better, source, cells)
        assert m["layer"] == "engine tick (serving/engine.py)"
        assert m["moves"] == ("serve_itl_p95_ms" if cells == LAT else "serve_tokens_per_s")
        assert set(cells) <= set(e2e[m["moves"]]["workloads"])
        reader = _metric(m["name"])["reader"]
        if source == "program_span":
            assert reader["file"] == "flight_steps.py"
            assert os.path.exists(os.path.join(METRICS, reader["file"]))
        else:
            assert reader["kind"] == "derived"
    assert bm.validate(manifest) == []
