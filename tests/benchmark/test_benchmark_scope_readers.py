"""The two readers of device time by program scope and of step spans by what
the step carried (``benchmark/layer_metrics/scope_time.py``,
``step_groups.py``), on the two recorded traces under ``data/`` with a
hand-made scope map: every expected number is worked out here from the
reduction's own per-operation times, never by the reader under test."""
import importlib.util
import json
import os
import re
import sys

import pytest

import benchmark_tiny_tree as tiny

sys.path.insert(0, tiny.REPO)

from benchmark.harness import manifest as bm  # noqa: E402
from benchmark.harness import readers  # noqa: E402
from benchmark.trace_reduce import events, reduce  # noqa: E402
from paddle_tpu.telemetry.scopes import OpScope  # noqa: E402
from paddle_tpu.telemetry.trace import Span  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
METRICS = os.path.join(tiny.REPO, "benchmark", "layer_metrics")
CTX = {"bench_dir": os.path.join(tiny.REPO, "benchmark")}


def _name(line):
    return re.match(r"%?(\S+) =", line).group(1)


def _metric(name):
    with open(os.path.join(METRICS, name + ".json")) as f:
        return json.load(f)


def _read(name, run):
    return readers.read(_metric(name), run, CTX)


class Tracer:
    """What the readers take from ``telemetry.trace.Tracer``."""

    def __init__(self, *scope_maps, spans=()):
        self._maps, self._spans = list(scope_maps), list(spans)
        self.programs = {"jit.step": [object() for _ in scope_maps]}

    def program_scopes(self):
        return {"jit.step": self._maps}

    def spans(self):
        return self._spans


def _session(trace, tracer):
    class Session:
        pass

    s = Session()
    s.trace, s.reduced, s.tracer = trace, reduce.reduce_trace(trace), tracer
    return s


# --- the chat trace: three fused steps ----------------------------------------

CARRY = OpScope("layers", carry=True)
CHAT_MAP = {
    "closed_call.14": OpScope("layers/attn.core/kernel.ragged"),
    **{n: CARRY for n in (
        "copy_bitcast_fusion.6", "copy_bitcast_fusion.7",
        "constant_dynamic-slice_fusion.10", "constant_dynamic-slice_fusion.11",
        "copy_dynamic-update-slice_fusion.6", "copy_dynamic-update-slice_fusion.7")},
    **{n: CARRY._replace(rule="carry") for n in (
        "copy.117", "copy.118", "broadcast.15.clone", "broadcast.15.clone2")},
    **{n: OpScope("layers/attn.core/attn.pool_write") for n in (
        "copy.92", "copy.94", "fusion.160", "fusion.161")},
    "copy-done.4": OpScope("layers/attn.core/attn.pool_write", rule="copied"),
    "fusion.164": OpScope("layers/mlp"), "fusion.165": OpScope("layers/mlp"),
    "fusion.87": OpScope("lm_head"),
    "bitcast_add_fusion.4": OpScope("layers/attn.qkv"),
    "convert_reduce_fusion.10": OpScope("layers/attn.out"),
    "copy.89": OpScope("unscoped", rule="none"),
}
POOL = [n for n, s in CHAT_MAP.items() if s.carry or s.scope.endswith("attn.pool_write")]
MATMUL = ["fusion.164", "fusion.165", "fusion.87", "bitcast_add_fusion.4",
          "convert_reduce_fusion.10"]


@pytest.fixture(scope="module")
def chat():
    trace = events.load(os.path.join(DATA, "chat_three_ticks.json.gz"))
    session = _session(trace, Tracer(CHAT_MAP))
    by_name = {}
    for line, s in session.reduced["op_s"].items():
        by_name[_name(line)] = by_name.get(_name(line), 0.0) + s
    run = {"session": session, "facts": {},
           "counters": {"trace": {"fused_steps": 3}}}
    return run, by_name


def test_serving_scope_metrics_on_the_chat_trace(chat, capsys):
    run, by_name = chat
    ms = lambda names: 1e3 * sum(by_name[n] for n in names) / 3  # noqa: E731
    pool = _read("cache.pool_update_ms_per_step.lat", run)
    ragged = _read("kernel.ragged_ms_per_step.lat", run)
    matmul = _read("model.serve_matmul_ms_per_step.lat", run)
    unscoped = _read("device.serve_unscoped_share.lat", run)
    assert pool == pytest.approx(ms(POOL)) and 80 < pool < 90
    assert ragged == pytest.approx(ms(["closed_call.14"])) and 19 < ragged < 20
    assert matmul == pytest.approx(ms(MATMUL))
    in_the_map = sum(by_name[n] for n in CHAT_MAP if n != "copy.89")
    total = sum(by_name.values())
    assert unscoped == pytest.approx(100 * (total - in_the_map) / total)
    # the sum rule: the scopes and what has none add up to the step's device time
    device = readers.read(_metric("model.serve_device_ms_per_step.lat"), run, CTX)
    assert pool + ragged + matmul + unscoped / 100 * device == pytest.approx(device, rel=1e-9)
    # the same files read the document cell
    assert _read("cache.pool_update_ms_per_step.sat", run) == pytest.approx(pool)
    out = capsys.readouterr().out
    assert "device time by scope" in out and "layers [carry]" in out
    assert "by rule 'carry': copy.117 -> layers [carry]" in out
    assert out.count("device time by scope") == 1      # the table is printed once a run


def test_two_programs_that_disagree_on_a_name(chat, capsys):
    """The compiler numbers each module's instructions anew and a trace event
    does not say whose it is: a name two dispatched programs put under
    different scopes counts under neither, and shows as unscoped."""
    run, by_name = chat
    other = dict(CHAT_MAP, **{"copy.92": OpScope("layers/mlp"),
                              "copy.117": CARRY})       # another rule, the same scope
    both = dict(run, session=_session(run["session"].trace, Tracer(CHAT_MAP, other)))
    ms = lambda names: 1e3 * sum(by_name[n] for n in names) / 3  # noqa: E731
    assert by_name["copy.92"] > 0
    assert _read("cache.pool_update_ms_per_step.lat", both) == pytest.approx(
        ms([n for n in POOL if n != "copy.92"]))
    assert _read("model.serve_matmul_ms_per_step.lat", both) == pytest.approx(ms(MATMUL))
    in_the_map = sum(by_name[n] for n in CHAT_MAP if n not in ("copy.89", "copy.92"))
    total = sum(by_name.values())
    assert _read("device.serve_unscoped_share.lat", both) == pytest.approx(
        100 * (total - in_the_map) / total)
    out = capsys.readouterr().out
    assert "copy.92 is layers/attn.core/attn.pool_write in one program and layers/mlp" in out
    assert "by rule 'ambiguous': copy.92 -> unscoped" in out
    assert "copy.117 is" not in out


def test_a_build_without_the_map_reads_nothing(chat):
    run, _ = chat

    class OldSpan:                      # the parent commit's: no id, no parent
        def __init__(self, name, t0_ns, dur_ns):
            self.name, self.t0_ns, self.dur_ns, self.args = name, t0_ns, dur_ns, None

    class OldTracer:                    # ... and no program_scopes()
        def spans(self):
            return [OldSpan(n, s, d) for n, s, d, _ in run["session"].trace["host"]]

    older = dict(run, session=_session(run["session"].trace, OldTracer()))
    for name in ("cache.pool_update_ms_per_step.lat", "device.serve_unscoped_share.lat",
                 "engine.step_ms_decode_only.lat", "model.train_forward_share"):
        assert _read(name, older) is None
    assert _read("kernel.ragged_ms_per_step.lat", dict(run, session=None)) is None


def _step_spans(host, prefill):
    """Tracer spans made from the recorded host annotations, with the ids and
    parents the tracer would give and ``prefill[k]`` tokens in the k-th step; on
    a clock of another origin that drifts 0.5 us a step against the trace's."""
    spans, open_step, open_dispatch, k = [], None, None, 0
    for i, (name, start, dur, _) in enumerate(host, 1):
        if name == "serve.step":
            open_step = Span(name, start + 1_790_756_287 * 10 ** 9 + 500 * k, dur, 1,
                             "main", {"prefill_tokens": prefill[k]}, i, None)
            k += 1
            spans.append(open_step)
        elif name == "serve.dispatch":
            open_dispatch = Span(name, start, dur, 1, "main", None, i, open_step.id)
            spans.append(open_dispatch)
        elif name == "serve.device_step":
            spans.append(Span(name, start, dur, 2, "worker", None, i, open_dispatch.id))
    return spans


@pytest.mark.parametrize("prefill,decode_only,with_prefill", [
    ((16, 0, 0), (112.184429 + 111.810269) / 2, 113.008979),
    ((0, 8, 8), 113.008979, (112.184429 + 111.810269) / 2),
    ((0, 0, 0), 112.184429, None),                      # an empty group reads nothing
])
def test_step_groups_on_the_chat_trace(chat, capsys, prefill, decode_only, with_prefill):
    run, _ = chat
    trace = run["session"].trace
    session = _session(trace, Tracer(CHAT_MAP, spans=_step_spans(trace["host"], prefill)))
    grouped = dict(run, session=session)
    assert _read("engine.step_ms_decode_only.lat", grouped) == pytest.approx(decode_only)
    got = _read("engine.step_ms_with_prefill.lat", grouped)
    assert got is None if with_prefill is None else got == pytest.approx(with_prefill)
    out = capsys.readouterr().out
    assert f"with_prefill {sum(1 for p in prefill if p)} spans" in out
    assert "fall 0.5 us (median; worst 1.0) from their annotations, 3 pairs" in out


# --- the train trace: two steps ---------------------------------------------------

def _train_scope(name):
    kernels = {"closed_call.9": OpScope("train.forward/layers/attn.core/kernel.flash_fwd"),
               "rematted_computation.11": OpScope(
                   "train.backward/layers/attn.core/kernel.flash_fwd", True, True),
               "checkpoint.23": OpScope(
                   "train.backward/layers/attn.core/kernel.flash_bwd_dkv", True),
               "checkpoint.22": OpScope(
                   "train.backward/layers/attn.core/kernel.flash_bwd_dq", True)}
    if name in kernels:
        return kernels[name]
    if name.startswith("convolution_add_fusion"):
        return OpScope("train.forward/layers/mlp")
    if name.startswith("fusion."):
        return OpScope("train.backward/layers/mlp", True)
    if name.startswith("convert_reduce_fusion"):
        return OpScope("train.backward/layers/attn.qkv", True, True)
    if name.startswith("bitcast_dynamic-update-slice_fusion"):
        return OpScope("train.optimizer")
    if name.startswith("all-reduce"):
        return OpScope("train.backward/layers", True, carry=True)
    return None                                         # not in the map


@pytest.fixture(scope="module")
def train():
    trace = events.load(os.path.join(DATA, "train_two_steps.json.gz"))
    names = {_name(e[0]) for e in trace["devices"]["0"]}
    scope_map = {n: _train_scope(n) for n in names if _train_scope(n) is not None}
    session = _session(trace, Tracer(scope_map))
    by_name = {}
    for line, s in session.reduced["op_s"].items():
        by_name[_name(line)] = by_name.get(_name(line), 0.0) + s
    return {"session": session, "facts": {"traced_steps": 2}, "counters": {}}, by_name


def test_train_shares_add_up_on_the_train_trace(train):
    run, by_name = train
    total = sum(by_name.values())
    share = lambda pick: 100 * sum(s for n, s in by_name.items()  # noqa: E731
                                   if _train_scope(n) is not None
                                   and pick(_train_scope(n))) / total
    forward = _read("model.train_forward_share", run)
    recompute = _read("model.train_recompute_share", run)
    backward = _read("model.train_backward_share", run)
    optimizer = _read("model.train_optimizer_share", run)
    assert forward == pytest.approx(share(lambda s: s.scope.startswith("train.forward")))
    assert recompute == pytest.approx(share(lambda s: s.recompute))
    assert backward == pytest.approx(share(lambda s: s.backward and not s.recompute))
    assert optimizer == pytest.approx(share(lambda s: s.scope == "train.optimizer"))
    assert min(forward, recompute, backward, optimizer) > 1
    unscoped = 100 * sum(s for n, s in by_name.items() if _train_scope(n) is None) / total
    assert forward + recompute + backward + optimizer == pytest.approx(100 - unscoped)
    assert _read("sharding.grad_allreduce_share", run) == 0.0      # one chip: no collective


def test_kernel_time_over_the_scopes_calls(train):
    run, by_name = train
    ops = run["session"].trace["devices"]["0"]
    lo, hi = run["session"].reduced["window_ns"]
    calls = lambda pat: reduce.calls(ops, pat, lo, hi)  # noqa: E731
    assert calls(r"^%closed_call\.9 =") == calls(r"^%rematted_computation\.11 =") == 48
    fwd = _read("kernel.flash_fwd_ms_per_call", run)
    assert fwd == pytest.approx(
        1e3 * (by_name["closed_call.9"] + by_name["rematted_computation.11"]) / 96)
    assert 0.9 < fwd < 1.1                             # PERF.md: forward 1.00 ms a call
    assert _read("kernel.flash_bwd_dkv_ms_per_call", run) == pytest.approx(
        1e3 * by_name["checkpoint.23"] / calls(r"^%checkpoint\.23 ="))
    assert _read("kernel.flash_bwd_dq_ms_per_call", run) == pytest.approx(
        1e3 * by_name["checkpoint.22"] / calls(r"^%checkpoint\.22 ="))


def test_collectives_are_found_by_kind_and_flag():
    spec = importlib.util.spec_from_file_location(
        "scope_time", os.path.join(METRICS, "scope_time.py"))
    scope_time = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(scope_time)
    clause = _metric("sharding.grad_allreduce_share")["reader"]["select"][0]
    row = lambda name, scope: {"name": name, "scope": scope}  # noqa: E731
    grad = OpScope("train.backward/layers/mlp", backward=True)
    assert scope_time._holds(clause, row("all-reduce.41", grad))
    assert scope_time._holds(clause, row("all-reduce-start.3", grad))
    assert not scope_time._holds(clause, row("all-gather.63", grad))
    assert not scope_time._holds(clause, row("all-reduce.2", OpScope("train.forward/lm_head")))
    assert not scope_time._holds(clause, row("fusion.375", grad))


def test_the_new_metrics_are_appended_with_their_cells():
    """Eighteen entries, each with its cells listed, none put before what was there."""
    per_layer = bm.load_manifest()["per_layer"]
    names = [m["name"] for m in per_layer]
    new = names[34:]
    assert len(new) == 18 and names.index("device.serve_peak_hbm_share.sat") == 33
    for m in per_layer[34:]:
        assert m["workloads"] and m["better"] == "lower"
        reader = _metric(m["name"])["reader"]
        assert reader["file"] in ("scope_time.py", "step_groups.py")
        assert os.path.exists(os.path.join(METRICS, reader["file"]))
    assert bm.validate(bm.load_manifest()) == []
