"""The trace reduction on a hand-made trace whose numbers are worked out in
the comments, the roofline functions against hand counts, the peaks table."""
import os
import sys

import pytest

import benchmark_tiny_tree as tiny

sys.path.insert(0, tiny.REPO)

from benchmark.harness import peaks  # noqa: E402
from benchmark.rooflines import flash, model_flops, ragged  # noqa: E402
from benchmark.trace_reduce import events, reduce  # noqa: E402

US = 1000        # the trace's unit is the nanosecond

# One device, a window of 100 us.  Operations (start, length in us):
#   fusion.1            0..30
#   all-reduce.1       20..50   overlaps fusion.1 for 10, alone for 20
#   while.1            55..85   a wrapper that holds its body:
#     fusion.2         55..65
#     kernel.1         70..85
#   gap 50..55 (5 us, short), gap 65..70 inside the while (not idle: the while
#   covers it), gap 85..100 (15 us... made 40 us below by ending the window at 125)
HAND = {
    "devices": {"0": [
        ["fusion.1", 0 * US, 30 * US],
        ["all-reduce.1", 20 * US, 30 * US],
        ["while.1", 55 * US, 30 * US],
        ["fusion.2", 55 * US, 10 * US],
        ["kernel.1", 70 * US, 15 * US],
    ]},
    "host": [
        ["bench.trace_window", 0, 125 * US, "main"],
        ["serve.step", 40 * US, 80 * US, "main"],          # 40..120
        ["serve.device_step", 84 * US, 20 * US, "main"],   # 84..104, inside serve.step
    ],
    "meta": {},
}


def test_union_subtract_and_clip():
    assert reduce.union([(5, 10), (0, 3), (2, 6), (20, 20)]) == [(0, 10)]
    assert reduce.total(reduce.union([(0, 4), (10, 12)])) == 6
    assert reduce.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22), (29, 40)]) == \
        [(0, 2), (4, 8), (22, 29)]
    assert reduce.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]


def test_hand_trace_busy_idle_self_time_collectives_and_gaps():
    r = reduce.reduce_trace(HAND)
    assert r["window_s"] == pytest.approx(125e-6)
    # busy = [0,50) + [55,85) = 80 us; the while covers its inner gap
    assert r["busy_s"] == pytest.approx(80e-6)
    # self times: the while keeps 30 - 10 - 15 = 5 us of its own
    assert r["op_s"]["while.1"] == pytest.approx(5e-6)
    assert r["op_s"]["fusion.1"] == pytest.approx(30e-6)
    assert r["op_s"]["kernel.1"] == pytest.approx(15e-6)
    # the all-reduce runs 30 us of the 125 us window, 20 of them with nothing
    # else on the device (fusion.1 covers its first 10)
    assert r["collective_share_worst"] == pytest.approx(30 / 125)
    assert r["collective_exposed_share_worst"] == pytest.approx(20 / 125)
    # idle: [50,55) = 5 us is short; [85,125) = 40 us has its mid-point at 105,
    # inside serve.step (40..120) and past serve.device_step (84..104)
    gaps = dict(r["breakdown"]["idle_gaps"])
    assert gaps == {"serve.step": pytest.approx(40e-6),
                    reduce.SHORT_GAPS: pytest.approx(5e-6)}
    assert sorted(r["breakdown"]["device_ops"][:2]) == [
        ["all-reduce.1", pytest.approx(30e-6)], ["fusion.1", pytest.approx(30e-6)]]
    assert reduce.calls(HAND["devices"]["0"], r"^fusion", 0, 125 * US) == 2


def test_a_gap_goes_to_the_innermost_span_that_covers_its_middle():
    busy = [(0, 10 * US), (60 * US, 100 * US)]
    host = [["outer", 0, 100 * US, "t"], ["inner", 10 * US, 50 * US, "t"]]
    assert reduce.idle_gaps(busy, host, 0, 100 * US) == [["inner", pytest.approx(50e-6)]]
    assert reduce.idle_gaps(busy, [], 0, 100 * US) == [["no_host_span", pytest.approx(50e-6)]]


def test_two_devices_average_busy_and_take_the_worst_collective():
    two = {"devices": {"0": [["fusion.1", 0, 50 * US]],
                       "1": [["fusion.1", 0, 30 * US], ["all-gather.3", 30 * US, 40 * US]]},
           "host": [["bench.trace_window", 0, 100 * US, "main"]], "meta": {}}
    r = reduce.reduce_trace(two)
    assert r["busy_s"] == pytest.approx((50e-6 + 70e-6) / 2)
    assert r["op_s"]["fusion.1"] == pytest.approx(40e-6)            # a device's mean
    assert r["collective_share_worst"] == pytest.approx(0.4)
    assert r["collective_exposed_share_worst"] == pytest.approx(0.4)


def test_an_empty_trace_is_an_error_not_a_zero():
    with pytest.raises(ValueError):
        reduce.reduce_trace({"devices": {"0": []}, "host": [], "meta": {}})


def test_events_round_trip(tmp_path):
    path = str(tmp_path / "t.json.gz")
    events.save(HAND, path)
    assert events.load(path) == HAND


DATA = os.path.join(os.path.dirname(__file__), "data")

# Two cuts of PR 23's own first traced runs on the chip (TPU v5 lite), in the
# reduction's input format: two train steps of gpt_1p3b.train_b8s1024 and three
# engine ticks of gpt_1p3b.serve_chat_r80, times rebased to the first span.
# The numbers are read off the files by hand:
# - the window is the cut's host spans end to end (first start 0, last end);
# - a train step's device program is one unbroken stretch, so all idle time is
#   the three kinds of gap listed, and busy = window - their sum;
# - kernels: a train step calls flash forward twice a layer (forward and its
#   recomputation) and each backward kernel once: 2 steps x 24 layers x 4;
#   a serving tick calls the ragged kernel once a layer: 3 ticks x 24.
RECORDED = {
    "train_two_steps": {
        "window_ns": 1_271_950_649,
        "gaps_ns": {"bench.loss_read": 4_105_862, "jit.fused_train_step": 1_160_770,
                    reduce.SHORT_GAPS: 1_234},
        "kernel_calls": 2 * 24 * 4, "kernel_ns": 168_693_800,
        "top": "add_add_fusion.2 bf16[8,1024,2048]",
    },
    "chat_three_ticks": {
        "window_ns": 339_241_735,
        "gaps_ns": {"serve.device_step": 12_775_733, reduce.SHORT_GAPS: 207},
        "kernel_calls": 3 * 24, "kernel_ns": 58_310_437,
        "top": "closed_call.14 bf16[48,16,8,128] tpu_custom_call",
    },
}


def _busy_by_sweep(ops, lo, hi):
    """Busy time by counting open operations over the sorted end points: a
    second way to the number, sharing nothing with ``reduce.union``."""
    points = []
    for _, s, d in ops:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            points += [(a, 1), (b, -1)]
    points.sort(key=lambda p: (p[0], -p[1]))
    open_ops = busy = last = 0
    for t, step in points:
        if open_ops:
            busy += t - last
        open_ops, last = open_ops + step, t
    return busy


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_recorded_trace_reduces_to_the_numbers_worked_out_by_hand(name):
    want = RECORDED[name]
    trace = events.load(os.path.join(DATA, name + ".json.gz"))
    ops = trace["devices"]["0"]
    r = reduce.reduce_trace(trace)
    assert r["window_ns"] == (0, want["window_ns"])
    busy_ns = want["window_ns"] - sum(want["gaps_ns"].values())
    assert _busy_by_sweep(ops, 0, want["window_ns"]) == busy_ns
    assert r["busy_s"] == pytest.approx(busy_ns / 1e9, rel=1e-12)
    assert dict(r["breakdown"]["idle_gaps"]) == {
        k: pytest.approx(v / 1e9, rel=1e-9) for k, v in want["gaps_ns"].items()}
    assert reduce.calls(ops, "tpu_custom_call", 0, want["window_ns"]) == want["kernel_calls"]
    kernel_s = sum(s for op, s in r["op_s"].items() if "tpu_custom_call" in op)
    assert kernel_s == pytest.approx(want["kernel_ns"] / 1e9, rel=1e-9)
    assert r["breakdown"]["device_ops"][0][0] == want["top"]
    # the whiles that hold the layers keep only their own overhead
    assert all(s < 0.002 for op, s in r["op_s"].items() if op.startswith("%while"))
    # self times add up to the busy time: nothing is counted twice (operations
    # on the device's second line that run beside others may add a little)
    assert sum(r["op_s"].values()) == pytest.approx(r["busy_s"], rel=0.01)


def test_flash_matchers_tell_the_three_kernels_apart_on_the_recorded_trace():
    import json

    with open(os.path.join(tiny.REPO, "benchmark", "layer_metrics",
                           "kernel.flash_roofline_share.json")) as f:
        kernels = json.load(f)["reader"]["kernels"]
    trace = events.load(os.path.join(DATA, "train_two_steps.json.gz"))
    ops, hi = trace["devices"]["0"], RECORDED["train_two_steps"]["window_ns"]
    got = {k: reduce.calls(ops, pat, 0, hi) for k, pat in kernels.items()}
    assert got == {"_fwd_kernel": 96, "_bwd_dkv_kernel": 48, "_bwd_dq_kernel": 48}


def test_flash_roofline_against_a_hand_count():
    # one (batch, head), S = 1024, D = 128, causal: a matmul is
    # 2 * 1024 * 1024 * 128 / 2 = 134,217,728 operations
    one = 134_217_728
    assert flash.needed("_fwd_kernel", batch=1, heads=1, seq=1024, head_dim=128) == \
        {"flops": 2 * one, "bytes": 4 * 1024 * 128 * 2}
    assert flash.needed("_bwd_dkv_kernel", batch=8, heads=16, seq=1024, head_dim=128)["flops"] \
        == 4 * one * 128
    assert flash.needed("_bwd_dq_kernel", batch=8, heads=16, seq=1024, head_dim=128)["flops"] \
        == 1 * one * 128
    ctx = {"cell": {"mesh": {"dp": 2, "mp": 2}},
           "config": {"model": {"hidden_size": 5120, "num_heads": 40}},
           "traffic": {"global_batch": 8, "sequence": 1024}}
    assert flash.geometry(ctx) == {"batch": 4, "heads": 20, "seq": 1024, "head_dim": 128}
    both = flash.needed_by_calls(ctx, {"_fwd_kernel": 2, "_bwd_dq_kernel": 1})
    assert both["flops"] == (2 * 2 + 1) * one * 80
    with pytest.raises(KeyError):
        flash.needed("_other", batch=1, heads=1, seq=8, head_dim=8)


def test_ragged_roofline_against_a_hand_count():
    # 10 work items, 4 blocks holding 4 + 8 + 1 + 1 = 14 real rows, 16 heads,
    # page 128, D 128, bf16.  K and V pages: 10 * 2 * 128*128*2 B * 16 heads
    # = 10,485,760 B; q and o: 2 * 14 * 128 * 2 B * 16 = 114,688 B.
    # operations: 4 * (14/4 rows) * 128 * 128 * 16 heads * 10 items = 36,700,160
    got = ragged.needed(items=10, blocks=4, rows=14, heads=16, page=128, head_dim=128)
    assert got == {"flops": 36_700_160.0, "bytes": 10_485_760.0 + 114_688.0}
    # the counters are a step's; each of the model's layers calls the kernel on them
    ctx = {"config": {"model": {"hidden_size": 2048, "num_heads": 16, "num_layers": 3}},
           "cell": {"engine": {"page_size": 128}}, "facts": {"token_block": 8}}
    steps = ragged.needed_by_counters(ctx, {"work_items": 10, "block_row_capacity": 32,
                                            "block_rows": 14})
    assert steps == {k: 3 * v for k, v in got.items()}


def test_model_flops_per_token_against_a_hand_count():
    model = {"hidden_size": 2048, "num_layers": 24, "intermediate_size": 8192,
             "vocab_size": 50304}
    # a layer: 2 * (4 * 2048^2 + 2 * 2048 * 8192) + 2 * 1024 * 2048 = 104,857,600
    # head: 2 * 2048 * 50304 = 206,045,184; times 3 for forward + backward
    assert model_flops.train_flops_per_token(model, 1024) == \
        3.0 * (24 * 104_857_600 + 206_045_184)


def test_peaks_are_keyed_by_device_kind_and_an_unknown_kind_is_an_error():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert (v5e["bf16_flops_per_s"], v5e["hbm_bytes_per_s"], v5e["hbm_bytes"]) == \
        (197e12, 819e9, 16e9)
    assert "Google Cloud" in v5e["source"] and "TPU v5e" in v5e["source"]
    with pytest.raises(KeyError, match="cpu"):
        peaks.peaks_for("cpu")
