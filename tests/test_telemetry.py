"""Unified telemetry (docs/observability.md): metrics registry, host span
tracer, profiler facade, and the serving engine's per-request SLO
instrumentation — including its behavior under injected faults:

- Counters/Gauges/Histograms: labeled children, log-bucketed quantiles,
  JSON snapshot, Prometheus text exposition (parse + histogram
  invariants), the CounterSet dict-compat migration shim;
- span tracer: disabled no-op path, ring-buffer overflow accounting,
  thread-aware Chrome-trace export with interval nesting, the decorator;
- profiler facade: ``export()`` writes real Chrome-trace JSON,
  ``summary()`` aggregates per span name, ``export_chrome_tracing``'s
  handler exports at ``stop()``;
- SLO timestamps: every terminal request (DONE, FAILED, TIMED_OUT,
  CANCELLED) carries a complete, monotonically ordered set of the stages
  it reached; TTFT histograms exclude never-prefilled requests by
  construction; counters stay exact across a watchdog rebuild and
  randomized fault schedules.
"""
import gc
import json
import os
import re
import threading
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import profiler
from paddle_tpu.models import GPTStackedForPretraining, gpt_tiny
from paddle_tpu.serving import (
    FaultInjector, RequestState, ServingEngine, random_schedule,
)
from paddle_tpu.telemetry import metrics as tm
from paddle_tpu.telemetry import trace as tt

N_NEW = 4


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_log_buckets():
    b = tm.log_buckets(1e-3, 1e3, per_decade=2)
    assert list(b) == sorted(b)
    assert b[0] == pytest.approx(1e-3)
    assert b[-1] >= 1e3
    # 6 decades x 2 per decade + the closing edge
    assert len(b) == 13
    with pytest.raises(ValueError):
        tm.log_buckets(0.0, 1.0)
    with pytest.raises(ValueError):
        tm.log_buckets(1.0, 0.5)


def test_counter_inc_and_monotonicity():
    reg = tm.Registry()
    c = reg.counter("c_total", help="h")
    c.inc()
    c.inc(2.5)
    assert c.value() == pytest.approx(3.5)
    with pytest.raises(ValueError):
        c.inc(-1)
    # same name resolves to the SAME family; kind conflicts raise
    assert reg.counter("c_total") is c
    with pytest.raises(ValueError):
        reg.gauge("c_total")


def test_gauge_set_inc_dec():
    reg = tm.Registry()
    g = reg.gauge("g")
    g.set(5.0)
    g.labels().inc(2.0)
    g.labels().dec(3.0)
    assert g.value() == pytest.approx(4.0)


def test_labeled_children_distinct_and_cached():
    reg = tm.Registry()
    c = reg.counter("x_total")
    a = c.labels(engine="0")
    b = c.labels(engine="1")
    assert a is not b
    a.inc(3)
    assert c.value(engine="0") == 3
    assert c.value(engine="1") == 0
    # label resolution is cached: identical label sets hit one child
    assert c.labels(engine="0") is a
    assert len(c.children()) == 2


def test_histogram_quantiles_and_summary():
    reg = tm.Registry()
    h = reg.histogram("lat_seconds")
    child = h.labels()
    rng = np.random.RandomState(0)
    vals = 10 ** rng.uniform(-4, -1, size=2000)       # decades of spread
    for v in vals:
        child.observe(float(v))
    s = child.summary()
    assert s["count"] == 2000
    assert s["sum"] == pytest.approx(vals.sum(), rel=1e-9)
    assert s["min"] == pytest.approx(vals.min())
    assert s["max"] == pytest.approx(vals.max())
    # bucketed quantiles: within a bucket width of the exact ones, and
    # ordered
    for q, key in ((0.5, "p50"), (0.95, "p95"), (0.99, "p99")):
        exact = float(np.quantile(vals, q))
        ratio = s[key] / exact
        assert 1 / 1.6 < ratio < 1.6, (key, s[key], exact)
    assert s["p50"] <= s["p95"] <= s["p99"] <= s["max"]
    assert s["min"] <= s["p50"]


def test_histogram_empty_and_overflow():
    reg = tm.Registry()
    h = reg.histogram("h", buckets=(1.0, 10.0))
    assert h.summary() == {"count": 0, "sum": 0.0, "mean": 0.0, "min": 0.0,
                           "max": 0.0, "p50": 0.0, "p95": 0.0, "p99": 0.0}
    ch = h.labels()
    ch.observe(100.0)                                  # overflow bucket
    s = ch.summary()
    assert s["count"] == 1 and s["p99"] == pytest.approx(100.0)
    with pytest.raises(ValueError):
        reg.histogram("bad", buckets=(10.0, 1.0))
    with pytest.raises(ValueError):
        ch.quantile(1.5)


def test_snapshot_shape():
    reg = tm.Registry()
    reg.counter("a_total", help="ha").inc(2, engine="7")
    reg.histogram("b_seconds").observe(0.5)
    snap = reg.snapshot()
    assert snap["a_total"]["kind"] == "counter"
    assert snap["a_total"]["series"][0] == {
        "labels": {"engine": "7"}, "value": 2.0}
    hs = snap["b_seconds"]["series"][0]
    assert hs["count"] == 1 and hs["p50"] > 0
    json.dumps(snap)                                   # JSON-safe


_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})? (-?(?:[0-9.]+(?:e[+-]?[0-9]+)?))$",
    re.IGNORECASE)


def test_prometheus_text_parses_and_histogram_invariants():
    reg = tm.Registry()
    reg.counter("req_total", help="requests").inc(3, engine="0")
    reg.gauge("depth").set(2.0)
    h = reg.histogram("lat_seconds", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 5.0):
        h.observe(v, engine="0")
    text = reg.prometheus_text()
    buckets, count = [], None
    for ln in text.splitlines():
        if ln.startswith("#"):
            continue
        m = _SAMPLE_RE.match(ln)
        assert m, f"unparseable line: {ln!r}"
        if m.group(1) == "lat_seconds_bucket":
            le = re.search(r'le="([^"]*)"', m.group(2)).group(1)
            buckets.append((le, float(m.group(3))))
        elif m.group(1) == "lat_seconds_count":
            count = float(m.group(3))
    assert [v for _, v in buckets] == [1.0, 2.0, 3.0]
    assert buckets[-1][0] == "+Inf" and buckets[-1][1] == count
    assert "# TYPE lat_seconds histogram" in text
    assert 'req_total{engine="0"} 3' in text


def test_prometheus_label_escaping():
    reg = tm.Registry()
    reg.counter("e_total").inc(1, path='a"b\\c')
    text = reg.prometheus_text()
    assert r'path="a\"b\\c"' in text


def test_counter_set_atomic_inc():
    """The `cs[k] += n` idiom is a read-modify-write and only safe under
    the caller's lock; inc() goes straight to the child's atomic inc —
    interleaved with a stale dict-idiom write it must not raise."""
    reg = tm.Registry()
    cs = tm.CounterSet("p", {"k": 0}, reg=reg)
    cs.inc("k")
    cs.inc("k", 2.0)
    assert cs["k"] == 3
    with pytest.raises(ValueError):
        cs.inc("k", -1)                                # still monotonic


def test_registry_drop_labels():
    reg = tm.Registry()
    c = reg.counter("d_total")
    c.inc(1, engine="0")
    c.inc(2, engine="1")
    h = reg.histogram("d_seconds")
    held = h.labels(engine="0")
    held.observe(0.5)
    reg.drop_labels(engine="0")
    text = reg.prometheus_text()
    assert 'engine="0"' not in text
    assert 'd_total{engine="1"} 2' in text
    # the dropped handle keeps working — it just stops being exported
    held.observe(0.7)
    assert held.summary()["count"] == 2
    with pytest.raises(ValueError):
        reg.drop_labels()                              # empty filter


def test_counter_set_dict_compat():
    reg = tm.Registry()
    cs = tm.CounterSet("srv", {"steps": 0, "tokens": 3},
                       labels={"engine": "9"}, reg=reg)
    cs["steps"] += 1
    cs["tokens"] += 2
    assert cs["steps"] == 1 and isinstance(cs["steps"], int)
    assert dict(cs) == {"steps": 1, "tokens": 5}
    assert cs.as_dict() == {"steps": 1, "tokens": 5}
    assert "steps" in cs and "nope" not in cs
    assert cs.get("nope", -1) == -1
    assert sorted(cs.keys()) == ["steps", "tokens"]
    # values ARE the registry counters (the migration's whole point)
    assert reg.counter("srv_tokens").value(engine="9") == 5
    with pytest.raises(ValueError):
        cs["steps"] = 0                                # net decrease


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------

@pytest.fixture()
def tracer():
    """A fresh process-wide tracer, always detached at teardown.  Automatic
    collections are held off meanwhile: under a tracer each one is a span
    (``host.gc``), and these tests count every span they record."""
    tt.disable()
    was = gc.isenabled()
    gc.disable()
    tr = tt.enable(capacity=1024, annotate=False)
    yield tr
    tt.disable()
    if was:
        gc.enable()


def test_span_disabled_is_noop():
    assert tt.active() is None
    ctx = tt.span("x", a=1)
    assert ctx is tt._NOOP
    with ctx:
        pass                                           # records nothing


def test_span_records(tracer):
    with tt.span("outer", k="v"):
        with tt.span("inner"):
            pass
    spans = tracer.spans()
    assert [s.name for s in spans] == ["inner", "outer"]   # exit order
    outer = spans[1]
    assert outer.args == {"k": "v"} and outer.dur_ns > 0
    assert outer.tid == threading.get_ident()
    # inner nests inside outer on the perf_counter_ns timeline
    inner = spans[0]
    assert outer.t0_ns <= inner.t0_ns
    assert inner.t0_ns + inner.dur_ns <= outer.t0_ns + outer.dur_ns


def test_enable_idempotent_disable_detaches(tracer):
    assert tt.enable() is tracer                       # composes, not resets
    with tt.span("a"):
        pass
    detached = tt.disable()
    assert detached is tracer and tt.active() is None
    # buffered spans stay readable after detach
    assert [s.name for s in detached.spans()] == ["a"]
    assert tt.disable() is None                        # idempotent


def test_ring_buffer_overflow():
    tr = tt.Tracer(capacity=4, annotate=False)
    for i in range(6):
        tr.record(tt.Span(f"s{i}", i, 1, 0, "t", None))
    assert len(tr) == 4 and tr.dropped == 2
    assert [s.name for s in tr.spans()] == ["s2", "s3", "s4", "s5"]
    tr.clear()
    assert len(tr) == 0 and tr.dropped == 0
    with pytest.raises(ValueError):
        tt.Tracer(capacity=0)


def test_span_ids_and_parents(tracer):
    """Every span has an id of the tracer's counter and the parent that was
    open on ITS thread when it started; threads do not see each other."""
    seen = {}

    def worker():
        with tt.span("w.outer") as o:
            with tt.span("w.inner") as i:
                seen["w"] = (o.id, o.parent, i.id, i.parent)

    with tt.span("main.outer") as mo:
        th = threading.Thread(target=worker)
        th.start()
        th.join()
        with tt.span("main.inner") as mi:
            assert tracer.current_id() == mi.id
        assert tracer.current_id() == mo.id
    assert tracer.current_id() is None
    o_id, o_parent, i_id, i_parent = seen["w"]
    assert o_parent is None and i_parent == o_id       # not main.outer
    by = {s.name: s for s in tracer.spans()}
    assert by["main.inner"].parent == by["main.outer"].id == mo.id
    assert by["main.outer"].parent is None
    assert by["w.inner"].id == i_id and by["w.inner"].parent == o_id
    assert len({s.id for s in tracer.spans()}) == 4


def test_handing_over_names_the_parent_across_threads(tracer):
    """Work handed to another thread takes the span open where it was
    handed over as parent (the serving watchdog's _StepWorker)."""
    def work():
        with tt.span("on.worker"):
            pass
        return threading.get_ident()

    with tt.span("dispatching") as d:
        handed = tracer.handing_over(work)
        box = {}
        th = threading.Thread(target=lambda: box.setdefault("tid", handed()))
        th.start()
        th.join()
    by = {s.name: s for s in tracer.spans()}
    assert by["on.worker"].parent == d.id
    assert by["on.worker"].tid == box["tid"] != by["dispatching"].tid


def test_span_set_adds_args_while_open(tracer):
    with tt.span("a") as a:
        a.set(step=3)
        a.set(tokens=5)
    with tt.span("b", k=1) as b:
        b.set(step=4)
    by = {s.name: s for s in tracer.spans()}
    assert by["a"].args == {"step": 3, "tokens": 5}
    assert by["b"].args == {"k": 1, "step": 4}


def test_disabled_path_allocates_nothing():
    """No tracer: span() is the shared no-op whose ``as`` target is None, and
    no id counter, stack or scope map exists anywhere."""
    tt.disable()
    assert tt.active() is None
    ctx = tt.span("x")
    assert ctx is tt._NOOP and not hasattr(ctx, "__dict__")
    with ctx as opened:
        assert opened is None
    assert not hasattr(tt, "_stack") and not hasattr(tt, "_ids")


def test_record_interval_keeps_ids_and_parents(tracer, tmp_path):
    """A span whose two ends were stamped at different times: its id comes
    from the tracer's one counter, its parent is what the caller kept from
    its start, its thread the recorder's unless it names a row of its own,
    and the export carries all of it."""
    with tt.span("tick.one") as one:
        with tt.span("dispatch") as d:
            t0, parent = time.perf_counter_ns(), tracer.current_id()
        assert parent == d.id
    with tt.span("tick.two") as two:
        t1 = time.perf_counter_ns()
        got = tracer.record_interval("flight", t0, t1, parent=parent, seq=7,
                                     landed_in=tracer.current_id())
        rowed = tracer.record_interval("flight", t0, t1, row="flights.1")
    by = {s.name: s for s in tracer.spans() if s.name != "flight"}
    assert (got.t0_ns, got.dur_ns) == (t0, t1 - t0)
    assert got.parent == by["dispatch"].id and got.parent != one.id
    assert got.args == {"seq": 7, "landed_in": two.id}
    assert got.tid == by["tick.two"].tid and rowed.args is None
    assert rowed.thread_name == "flights.1" and rowed.tid != got.tid
    assert tracer.record_interval("flight", t0, t1, row="flights.1").tid == rowed.tid
    assert tracer.record_interval("flight", t0, t1, row="flights.0").tid != rowed.tid
    ids = [s.id for s in tracer.spans()]
    assert len(set(ids)) == len(ids) == 7 and got.id > two.id
    doc = tt.export_chrome_trace(str(tmp_path / "t.json"), tracer=tracer)
    flights = [e for e in doc["traceEvents"] if e["name"] == "flight"]
    assert flights[0]["args"] == {"seq": 7, "landed_in": two.id, "id": got.id,
                                  "parent": by["dispatch"].id}
    assert flights[0]["ts"] == t0 / 1000.0 and flights[0]["dur"] == (t1 - t0) / 1000.0
    rows = {e["args"]["name"] for e in doc["traceEvents"] if e["ph"] == "M"}
    assert {"flights.0", "flights.1"} <= rows


def test_the_collector_is_a_span_under_a_tracer_and_nothing_without():
    """With a tracer a collection is a span ``host.gc`` on the collecting
    thread, innermost wherever it strikes; without one ``gc.callbacks`` is as
    it was found and ``span()`` is still the shared no-op."""
    tt.disable()
    found = list(gc.callbacks)
    tr = tt.enable(capacity=1024, annotate=False)
    try:
        assert tt.enable() is tr                        # nested: one hook
        assert gc.callbacks == found + [tt._on_gc]
        with tt.span("tick") as tick:
            with tt.span("pack") as pack:
                gc.collect()
            gc.collect(0)
    finally:
        assert tt.disable() is tr
    assert gc.callbacks == found
    assert tt.disable() is None and gc.callbacks == found
    gc.collect()                                        # no tracer: no span
    pauses = [s for s in tr.spans() if s.name == "host.gc"]
    # (a collection of its own may strike anywhere in the block besides)
    asked = [s for s in pauses if s.args["generation"] == 2]
    assert asked and asked[0].parent == pack.id
    assert any(s.parent == tick.id and s.args["generation"] == 0 for s in pauses)
    for s in pauses:
        assert s.dur_ns > 0 and s.args["collected"] >= 0
        assert s.tid == threading.get_ident()
    assert len(pauses) == len([s for s in tr.spans() if s.name == "host.gc"])
    assert tt.span("x") is tt._NOOP and tt._gc_open is None


class _Entry:
    """Stands for a compiled entry: something a weak reference can hold."""


def test_program_scopes_wait_until_asked(tracer):
    """The tracer only remembers what it saw dispatched; the scope map (a
    compile of text) is made when first asked, once."""
    calls = []

    def resolve(entry):
        calls.append(entry)
        return {"fusion.1": entry}

    e1, e2 = _Entry(), _Entry()
    tracer.saw_program("jit.a", e1, resolve)
    tracer.saw_program("jit.a", e1, resolve)            # every dispatch reports
    tracer.saw_program("jit.b", e2, resolve)
    assert {k: [r() for r in v] for k, v in tracer.programs.items()} == {
        "jit.a": [e1], "jit.b": [e2]}
    assert calls == []
    got = tracer.program_scopes()
    assert got == {"jit.a": [{"fusion.1": e1}], "jit.b": [{"fusion.1": e2}]}
    assert tracer.program_scopes() is got and calls == [e1, e2]


def test_the_tracer_keeps_no_program_alive(tracer):
    """A compiled entry holds its weights and pools: the tracer remembers it
    weakly, leaves out what is gone when the map is asked for, and
    ``clear()`` forgets programs and map with the spans."""
    import gc
    import weakref

    def resolve(entry):
        return {"fusion.1": "scope"}

    kept, gone = _Entry(), _Entry()
    watch = weakref.ref(gone)
    tracer.saw_program("jit.a", kept, resolve)
    tracer.saw_program("jit.a", gone, resolve)
    del gone
    gc.collect()
    assert watch() is None                              # only the tracer knew it
    assert tracer.program_scopes() == {"jit.a": [{"fusion.1": "scope"}]}
    tracer.clear()
    assert tracer.programs == {} and tracer.program_scopes() == {}


def test_chrome_trace_export_threads_and_nesting(tracer, tmp_path):
    def worker():
        with tt.span("w.outer"):
            with tt.span("w.inner"):
                pass

    with tt.span("main.span", meta=1):
        pass
    th = threading.Thread(target=worker, name="worker-0")
    th.start()
    th.join()

    path = str(tmp_path / "trace.json")
    doc = tt.export_chrome_trace(path)
    with open(path) as f:
        assert json.load(f) == doc
    events = doc["traceEvents"]
    metas = [e for e in events if e["ph"] == "M"]
    comp = [e for e in events if e["ph"] == "X"]
    tids = {e["tid"] for e in comp}
    assert len(tids) == 2                              # main + worker rows
    assert {m["args"]["name"] for m in metas} >= {"worker-0"}
    by_name = {e["name"]: e for e in comp}
    assert by_name["main.span"]["args"] == {
        "meta": 1, "id": by_name["main.span"]["args"]["id"], "parent": None}
    inner, outer = by_name["w.inner"], by_name["w.outer"]
    assert inner["args"]["parent"] == outer["args"]["id"]
    assert inner["tid"] == outer["tid"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 0.5
    assert doc["otherData"]["dropped_spans"] == 0


def test_summarize_and_format(tracer):
    for _ in range(3):
        with tt.span("a"):
            pass
    with tt.span("b"):
        pass
    stats = tt.summarize()
    assert stats["a"]["count"] == 3 and stats["b"]["count"] == 1
    assert stats["a"]["p50_ms"] <= stats["a"]["p99_ms"] <= stats["a"]["max_ms"]
    table = tt.format_summary(stats)
    assert "a" in table and "count" in table
    assert tt.format_summary({}) == "no spans recorded"


# ---------------------------------------------------------------------------
# profiler facade
# ---------------------------------------------------------------------------

def test_profiler_export_and_summary(tmp_path, capsys):
    tt.disable()
    prof = profiler.Profiler(timer_only=True)
    prof.start()
    assert tt.active() is not None                     # facade enabled it
    with tt.span("user.range"):
        pass
    prof.step()
    prof.stop()
    assert tt.active() is None                         # and detached it
    stats = prof.summary()
    assert stats["user.range"]["count"] == 1
    assert stats["profiler.step"]["count"] == 1
    assert "user.range" in capsys.readouterr().out
    path = str(tmp_path / "prof.json")
    assert prof.export(path) == path
    with open(path) as f:
        names = {e["name"] for e in json.load(f)["traceEvents"]
                 if e.get("ph") == "X"}
    assert {"user.range", "profiler.step"} <= names
    with pytest.raises(ValueError):
        prof.export(str(tmp_path / "x.pb"), format="proto")


def test_profiler_export_chrome_tracing_handler(tmp_path):
    tt.disable()
    logdir = str(tmp_path / "logs")
    handler = profiler.export_chrome_tracing(logdir, worker_name="w7")
    with profiler.Profiler(timer_only=True, on_trace_ready=handler) as prof:
        with tt.span("in.profile"):
            pass
        prof.step()
    out = os.path.join(logdir, "w7.chrome_trace.json")
    assert os.path.exists(out)                         # stop() exported
    with open(out) as f:
        doc = json.load(f)
    assert any(e.get("name") == "in.profile" for e in doc["traceEvents"])


def test_record_event_records_span():
    tt.disable()
    tr = tt.enable(annotate=False)
    try:
        ev = profiler.RecordEvent("my.range")
        ev.begin()
        ev.end()
        assert [s.name for s in tr.spans() if s.name != "host.gc"] == ["my.range"]
    finally:
        tt.disable()


# ---------------------------------------------------------------------------
# serving SLO instrumentation
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    pt.seed(0)
    cfg = gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0)
    m = GPTStackedForPretraining(cfg)
    m.eval()
    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, cfg.vocab_size, (s,))
               for s in (5, 9, 7, 12, 17, 4, 11, 6)]
    return m, cfg, prompts


def _engine(m, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("page_size", 16)
    kw.setdefault("max_context", 64)
    kw.setdefault("cache_dtype", "float32")
    return ServingEngine(m, **kw)


def _assert_ordered_timestamps(req):
    """Every stage the request reached is stamped, in monotonic order,
    and no LATER stage is stamped without the earlier ones."""
    ts = req.timestamps()
    assert ts["submitted"] is not None, req.id
    assert ts["terminal"] is not None, (req.id, req.state)
    if ts["first_token"] is not None:
        assert ts["admitted"] is not None, req.id      # token => was seated
    chain = [ts["submitted"]]
    for key in ("admitted", "first_token", "terminal"):
        if ts[key] is not None:
            chain.append(ts[key])
    assert chain == sorted(chain), (req.id, ts)


def test_slo_happy_path(served):
    m, cfg, prompts = served
    eng = _engine(m)
    try:
        reqs = [eng.submit(p, N_NEW) for p in prompts[:4]]
        eng.run_until_idle(max_steps=500)
        assert all(r.state == RequestState.DONE for r in reqs)
        for r in reqs:
            _assert_ordered_timestamps(r)
            assert r.t_admitted is not None and r.t_first_token is not None
        mets = eng.metrics()
        slo = mets["slo"]
        assert slo["ttft"]["count"] == 4
        assert slo["e2e"]["count"] == 4
        assert slo["queue_wait"]["count"] == 4
        # N_NEW tokens each -> N_NEW-1 inter-token gaps each
        assert slo["itl"]["count"] == 4 * (N_NEW - 1)
        for h in slo.values():
            assert h["p50"] <= h["p95"] <= h["p99"]
        # TTFT >= queue wait for the same request population
        assert slo["ttft"]["min"] >= slo["queue_wait"]["min"]
        # the registry sees the SAME totals the metrics dict reports
        lab = eng._engine_label
        assert tm.registry().counter("serving_completed").value(**lab) == 4
        assert mets["completed"] == 4 and isinstance(mets["completed"], int)
    finally:
        eng.close()


def test_ttft_excludes_never_prefilled(served):
    """TIMED_OUT-in-queue and CANCELLED-in-queue requests terminate with
    submitted/terminal stamps only — the TTFT and queue-wait histograms
    never see them, the e2e histogram does."""
    m, cfg, prompts = served
    eng = _engine(m)
    try:
        base = eng.metrics()["slo"]
        dead = eng.submit(prompts[0], N_NEW, deadline_s=1e-4)
        gone = eng.submit(prompts[1], N_NEW)
        gone.cancel()
        time.sleep(0.01)                               # expire the deadline
        eng.step()                                     # boundary reap
        assert dead.state == RequestState.TIMED_OUT
        assert gone.state == RequestState.CANCELLED
        for r in (dead, gone):
            _assert_ordered_timestamps(r)
            assert r.t_admitted is None and r.t_first_token is None
        slo = eng.metrics()["slo"]
        assert slo["ttft"]["count"] == base["ttft"]["count"]
        assert slo["queue_wait"]["count"] == base["queue_wait"]["count"]
        assert slo["e2e"]["count"] == base["e2e"]["count"] + 2
    finally:
        eng.close()


def test_slo_counters_exact_across_rebuild(served):
    """A persistent step crash forces recovery + rebuild mid-flight: the
    implicated requests FAIL with ordered timestamps, survivors complete,
    and the registry counters agree exactly with request states."""
    m, cfg, prompts = served
    eng = _engine(m)
    try:
        FaultInjector().inject("before_decode", at=1, times=2,
                               kind="step_exception",
                               state_intact=False).install(eng)
        reqs = [eng.submit(p, N_NEW) for p in prompts[:4]]
        eng.run_until_idle(max_steps=500)
        mets = eng.metrics()
        assert mets["recoveries"] == 1 and mets["rebuilds"] == 1
        done = [r for r in reqs if r.state == RequestState.DONE]
        failed = [r for r in reqs if r.state == RequestState.FAILED]
        assert len(done) + len(failed) == 4 and failed
        for r in reqs:
            _assert_ordered_timestamps(r)
        slo = mets["slo"]
        assert slo["e2e"]["count"] == 4                # every terminal
        # TTFT saw exactly the requests that produced a first token
        assert slo["ttft"]["count"] == sum(
            r.t_first_token is not None for r in reqs)
        lab = eng._engine_label
        reg = tm.registry()
        assert reg.counter("serving_failed").value(**lab) == len(failed)
        assert reg.counter("serving_completed").value(**lab) == len(done)
        assert reg.counter("serving_rebuilds").value(**lab) == 1
    finally:
        eng.close()


def test_slo_timestamps_under_random_fault_schedule(served):
    """Property over a randomized fault schedule: EVERY request reaches a
    typed terminal state with a complete, ordered timestamp set, and the
    e2e histogram counts them all."""
    m, cfg, prompts = served
    rng = np.random.RandomState(7)
    eng = _engine(m, num_slots=2)
    try:
        random_schedule(rng, horizon=20, n_faults=4,
                        num_slots=2).install(eng)
        reqs = [eng.submit(prompts[i % len(prompts)], N_NEW)
                for i in range(6)]
        eng.run_until_idle(max_steps=2000)
        assert all(r.terminal for r in reqs)
        for r in reqs:
            _assert_ordered_timestamps(r)
        slo = eng.metrics()["slo"]
        assert slo["e2e"]["count"] == len(reqs)
        assert slo["ttft"]["count"] == sum(
            r.t_first_token is not None for r in reqs)
        assert eng.allocator.used_pages == 0
    finally:
        eng.close()


def test_step_phases_spanned(served):
    """One engine step under an active tracer records the full phase
    tree (plan/pack/dispatch/harvest/commit inside serve.step) plus the
    compiled program's jit span."""
    m, cfg, prompts = served
    tt.disable()
    tr = tt.enable(annotate=False)
    try:
        eng = _engine(m)
        eng.submit(prompts[0], 2)
        eng.run_until_idle(max_steps=200)
        eng.close()
        names = {s.name for s in tr.spans()}
        assert {"serve.step", "serve.plan", "serve.pack", "serve.dispatch",
                "serve.harvest", "serve.commit", "serve.device_step",
                "jit.fused_step"} <= names
    finally:
        tt.disable()


def test_step_spans_know_their_step(served):
    """serve.step records the prompt tokens of the step it HARVESTED (equal to
    what the engine's totals moved by: a step is read one tick after it is
    enqueued), nothing else, and serve.device_step, the wait for that step on
    the watchdog's thread, reaches it through its parent."""
    m, cfg, prompts = served
    tt.disable()
    tr = tt.enable(annotate=False)
    try:
        eng = _engine(m, prefill_token_budget=8, stall_budget_s=60.0)
        eng.submit(prompts[4], 3)                      # 17 tokens: 3 chunks
        steps = []
        while eng.scheduler.active_slots or eng.queue.depth:
            before = eng.metrics()["prefill_tokens"]
            eng.step()
            steps.append(eng.metrics()["prefill_tokens"] - before)
        eng.close()
    finally:
        tt.disable()
    spans = tr.spans()
    by_id = {s.id: s for s in spans}
    recorded = [s for s in spans if s.name == "serve.step"]
    assert len(recorded) == len(steps) >= 5
    assert [s.args for s in recorded] == [{"prefill_tokens": n} for n in steps]
    assert steps[:5] == [0, 8, 8, 1, 0]     # the first tick only enqueues
    device = [s for s in spans if s.name == "serve.device_step"]
    assert len(device) == len(steps) - 1
    for d, n in zip(device, steps[1:]):
        assert d.thread_name.startswith("serving-step-")
        chain = [d]
        while chain[-1].parent is not None:
            chain.append(by_id[chain[-1].parent])
        assert [c.name for c in chain] == ["serve.device_step", "serve.step"]
        assert chain[-1].args == {"prefill_tokens": n}
        assert chain[-1].tid != d.tid


def _spans_of(tr, name):
    return [s for s in tr.spans() if s.name == name]


@pytest.mark.parametrize("watchdog", [False, True])
def test_a_step_in_flight_is_one_recorded_span(served, watchdog):
    """One ``serve.flight`` a fused step, from its enqueue in one tick to its
    tokens in the next: consecutive ``seq``, the parent the ``serve.dispatch``
    that enqueued it, ``landed_in`` the NEXT tick's ``serve.step``, what it
    carried summing to the engine's own totals; every other span of the step
    names the same flight, across both ticks."""
    m, cfg, prompts = served
    tt.disable()
    tr = tt.enable(annotate=False)
    try:
        eng = _engine(m, prefill_token_budget=8,
                      stall_budget_s=60.0 if watchdog else None)
        eng.submit(prompts[4], 3)                      # 17 tokens: 3 chunks
        eng.submit(prompts[0], 4)
        eng.run_until_idle(max_steps=200)
        mets = eng.metrics()
        eng.close()
    finally:
        tt.disable()
    by_id = {s.id: s for s in tr.spans()}
    flights = _spans_of(tr, "serve.flight")
    steps = _spans_of(tr, "serve.step")
    assert len(flights) == mets["fused_steps"] >= 6
    assert [f.args["seq"] for f in flights] == list(range(len(flights)))
    assert sum(f.args["prefill_tokens"] for f in flights) == mets["prefill_tokens"] == 22
    assert sum(f.args["rows"] for f in flights) == mets["block_rows"]
    assert sum(f.dur_ns for f in flights) == mets["flight_ns"]
    assert sum(f.args["wait_ns"] for f in flights) == mets["land_wait_ns"] > 0
    assert sum(f.args["overlapped"] for f in flights) == mets["overlapped_steps"]
    assert sum(f.args["overlapped"] and f.args["drained"] for f in flights) \
        == mets["host_late_steps"]
    step_ids = [s.id for s in steps]
    for k, f in enumerate(flights):
        a = f.args
        assert set(a) == {"seq", "rows", "prefill_tokens", "decode_rows", "overlapped",
                          "drained", "ready_at_read", "wait_ns", "prev_ready_ns",
                          "landed_in"}
        assert a["rows"] == a["prefill_tokens"] + a["decode_rows"] > 0
        assert f.thread_name == f"serve.flight.{k % 2}"
        dispatch = by_id[f.parent]
        assert dispatch.name == "serve.dispatch" and dispatch.args == {"seq": k}
        assert dispatch.t0_ns <= f.t0_ns <= dispatch.t0_ns + dispatch.dur_ns
        # enqueued in one tick, read in the next
        assert step_ids.index(a["landed_in"]) == step_ids.index(dispatch.parent) + 1
        assert a["prev_ready_ns"] == (
            None if k == 0 else flights[k - 1].t0_ns + flights[k - 1].dur_ns)
        assert a["overlapped"] or a["drained"]          # an empty engine's device is empty
        assert 0 <= a["wait_ns"] <= f.dur_ns
    assert not flights[0].args["overlapped"]
    for name, key in (("serve.pack", "seq"), ("serve.dispatch", "seq"),
                      ("serve.device_step", "flight"), ("serve.harvest", "flight")):
        assert [s.args[key] for s in _spans_of(tr, name)] == list(range(len(flights))), name
    for d in _spans_of(tr, "serve.device_step"):
        # the wait ends where the flight ends, on whichever thread it ran
        flight = flights[d.args["flight"]]
        assert d.t0_ns < flight.t0_ns + flight.dur_ns <= d.t0_ns + d.dur_ns
        assert d.thread_name.startswith("serving-step-") == watchdog


@pytest.mark.parametrize("case", ["device_never_done", "device_always_done",
                                  "host_sleeps_past_a_step"])
def test_host_late_steps_count_the_steps_enqueued_onto_an_empty_device(
        served, monkeypatch, case):
    """``host_late_steps`` counts the fused steps enqueued behind a step that
    had ALREADY finished.  With a device that is never done by its successor's
    enqueue (the probe held False: a chip slower than the tick) none is
    counted; with a host that sleeps past a whole step before every enqueue
    (the real probe) every step behind a predecessor is; a step with no
    predecessor never is.  With tracing off nothing else is recorded."""
    from paddle_tpu.serving import engine as engine_mod

    m, cfg, prompts = served
    tt.disable()
    if case != "host_sleeps_past_a_step":
        monkeypatch.setattr(engine_mod._Flight, "ready",
                            lambda self: case == "device_always_done")
    eng = _engine(m, prefill_token_budget=8)
    if case == "host_sleeps_past_a_step":
        def sleeps(point, ctx):
            if point == "before_decode":
                time.sleep(0.15)        # a tiny step on the CPU: a few ms
        eng._fault_hook = sleeps
    try:
        eng.submit(prompts[4], 3)
        eng.submit(prompts[0], 4)
        eng.run_until_idle(max_steps=200)
        mets = eng.metrics()
        assert eng.step()["tokens_this_step"] == 0      # an idle tick moves nothing
        assert eng.metrics()["host_late_steps"] == mets["host_late_steps"]
    finally:
        eng.close()
    assert mets["fused_steps"] >= 6 and mets["overlapped_steps"] == mets["fused_steps"] - 1
    late = 0 if case == "device_never_done" else mets["overlapped_steps"]
    assert mets["host_late_steps"] == late
    assert 0 < mets["land_wait_ns"] <= mets["flight_ns"]
    if case == "host_sleeps_past_a_step":
        # every flight but the last waited for the next tick's sleep to be read
        assert mets["flight_ns"] >= (mets["fused_steps"] - 1) * 0.15e9
        assert mets["land_wait_ns"] < 0.15e9
    assert "mean_launch_occupancy" not in mets


def test_engine_close_drops_registry_series(served):
    """close() removes this engine's labeled series from the process
    registry (engine churn must not grow the exposition forever), while
    metrics() stays readable through the retained handles."""
    m, cfg, prompts = served
    eng = _engine(m)
    eng.submit(prompts[0], 2)
    eng.run_until_idle(max_steps=200)
    lab = f'engine="{eng._engine_label["engine"]}"'
    assert lab in tm.registry().prometheus_text()
    mets_before = eng.metrics()
    eng.close()
    assert lab not in tm.registry().prometheus_text()
    mets = eng.metrics()                               # handles still live
    assert mets["completed"] == mets_before["completed"] == 1
    assert mets["slo"]["ttft"]["count"] == 1


def test_engine_metrics_dict_bit_compat(served):
    """The metrics() surface keeps the plain-int dict contract from the
    pre-registry era (BASELINE consumers read these keys raw)."""
    m, cfg, prompts = served
    eng = _engine(m)
    try:
        eng.submit(prompts[0], 2)
        eng.run_until_idle(max_steps=200)
        met = eng.step()                               # idle step
        for key in ("failed", "cancelled", "timed_out", "shed",
                    "recoveries", "active_slots", "queue_depth",
                    "pages_used"):
            assert isinstance(met[key], int), (key, type(met[key]))
        mets = eng.metrics()
        for key in ("steps", "tokens", "admitted", "completed",
                    "fused_steps"):
            assert isinstance(mets[key], int), (key, type(mets[key]))
    finally:
        eng.close()
