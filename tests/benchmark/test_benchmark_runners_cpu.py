"""The runners and the command end to end on the CPU at a tiny size: control
flow, counts and the result line's shape.  The device check is stood in for
(the real one is tested in ``test_benchmark_manifest.py``); no time or rate
read here means anything."""
import functools
import importlib
import json
import sys

import jax
import pytest

import benchmark_tiny_tree as tiny

sys.path.insert(0, tiny.REPO)

from benchmark.harness import manifest as bm, peaks, readers, runtime  # noqa: E402
from benchmark.trace_reduce import reduce  # noqa: E402

SEED = 3000000019       # past 2**31, as the driver's seeds are


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.build(str(tmp_path_factory.mktemp("bench")))


@pytest.fixture()
def on_cpu(monkeypatch, root):
    import paddle_tpu.sysconfig as sysconfig
    from paddle_tpu.distributed import mesh as dmesh

    monkeypatch.setattr(runtime, "require_tpu", lambda chips: jax.devices()[:chips])
    monkeypatch.setattr(sysconfig, "enable_compile_cache", lambda: "off in tests")
    monkeypatch.setattr(bm, "resolve_cell", functools.partial(bm.resolve_cell, root=root))
    monkeypatch.setattr(peaks, "peaks_for", lambda kind: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    yield
    dmesh.set_mesh(None)


def _run(cell, seconds, **kw):
    ctx = bm.resolve_cell(cell)
    runner = importlib.import_module("benchmark.runners." + ctx["traffic"]["kind"])
    return ctx, runner.run(ctx, seed=SEED, seconds=seconds, trace=False, **kw)


@pytest.mark.parametrize("cell", ["tiny.train", "tiny.train4"])
def test_train_runner(on_cpu, cell):
    ctx, run = _run(cell, 1.0)
    assert run["correct"], run["checks"]
    assert run["attempted"] == len(run["clocks"]["step_s"]) > 3 and run["failed"] == 0
    assert run["end_to_end"]["setup_s"] > 0
    assert run["end_to_end"]["train_tokens_per_s_per_chip"] > 0
    ctx["peaks"] = peaks.peaks_for("any")
    run["memory"] = {"peak_bytes_in_use": 1, "bytes_limit": 4}
    got = {m["name"]: readers.read(m, run, ctx) for m in ctx["per_layer"]}
    assert got["device.train_peak_hbm_share"] == 25.0
    assert got["train.step_ms_p95"] > 0 and got["model.train_mfu"] > 0
    assert 0 <= got["input.stall_share"] < 100
    assert got["device.train_idle_share"] is None       # no trace, nothing to read
    if cell == "tiny.train":
        assert got["tiny.longest_step_ms"] >= got["train.step_ms_p95"]


def test_open_loop_runner(on_cpu):
    ctx, run = _run("tiny.chat", 2.0)
    assert run["correct"], run["checks"]
    assert run["failed"] == 0 and run["attempted"] >= 30
    e2e = run["end_to_end"]
    assert e2e["serve_itl_p95_ms"] > 0 and "serve_tokens_per_s" in e2e
    ctx["peaks"], run["memory"] = peaks.peaks_for("any"), {}
    got = {m["name"]: readers.read(m, run, ctx) for m in ctx["per_layer"]}
    assert got["gen.lateness_p95_ms"] >= 0 and got["service.ttft_p90_ms"] >= got["service.ttft_p50_ms"] > 0
    assert got["engine.tokens_per_step.lat"] > 1
    assert 0 < got["admission.slots_used_share.lat"] <= 100
    assert got["kernel.ragged_roofline_share.lat"] is None


def test_a_sweep_offers_the_rate_it_is_given(on_cpu):
    _, base = _run("tiny.chat", 1.0)
    _, double = _run("tiny.chat", 1.0, rate=40.0)
    assert double["attempted"] > 1.6 * base["attempted"]


def test_backlog_runner_keeps_the_engine_saturated(on_cpu):
    ctx, run = _run("tiny.doc", 1.5)
    assert run["correct"], run["checks"]
    assert run["end_to_end"]["serve_tokens_per_s"] > 0
    assert min(run["clocks"]["queue_depth"]) >= 1          # never ran dry
    assert run["attempted"] > 10 and run["failed"] == 0


def test_the_command_prints_the_contracts_line_last(on_cpu, capsys):
    sys.path.insert(0, tiny.REPO + "/benchmark")
    run_py = importlib.import_module("benchmark.run")
    assert run_py.main(["--workload", "tiny.doc", "--seed", str(SEED), "--seconds", "1",
                        "--trace", "0"]) == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    line = json.loads(last)
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    assert set(line["metrics"]) == {"setup_s", "serve_tokens_per_s"}
    assert line["metrics"]["serve_tokens_per_s"]["unit"] == "tokens/s"
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert line["correct"] is True


def test_trace_readers_on_a_reduced_trace():
    """The trace-side readers on a small hand-made trace (its numbers are
    worked out in ``test_benchmark_trace_reduce.py``)."""
    from test_benchmark_trace_reduce import HAND

    class Session:
        trace = HAND
        reduced = reduce.reduce_trace(HAND)
        tracer = None

    run = {"session": Session, "facts": {"traced_steps": 2, "token_block": 8},
           "counters": {"trace": {"fused_steps": 4, "work_items": 10,
                                  "block_row_capacity": 32, "block_rows": 14}}}
    ctx = {"peaks": {"bf16_flops_per_s": 1e14, "hbm_bytes_per_s": 1e12},
           "config": {"model": {"hidden_size": 2048, "num_heads": 16, "num_layers": 1}},
           "cell": {"engine": {"page_size": 128}}}
    read = lambda spec: readers.VOCABULARY[spec["kind"]](spec, run, ctx)  # noqa: E731
    assert read({"kind": "trace_idle_share"}) == pytest.approx(100 * (1 - 80 / 125))
    assert read({"kind": "trace_op_share", "match": "^kernel"}) == pytest.approx(100 * 15 / 80)
    assert read({"kind": "trace_busy_per_step", "steps": "facts:traced_steps"}) == \
        pytest.approx(0.040)
    assert read({"kind": "trace_busy_per_step", "steps": "counters:trace:fused_steps"}) == \
        pytest.approx(0.020)
    assert read({"kind": "trace_exposed_share", "exposed": True}) == pytest.approx(16.0)
    # 10,600,448 bytes at 1e12 B/s = 10.6 us against 15 us of kernel time
    assert read({"kind": "roofline_share", "module": "benchmark.rooflines.ragged",
                 "match": "^kernel"}) == pytest.approx(100 * 10.600448 / 15)
