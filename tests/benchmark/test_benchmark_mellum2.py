"""The Mellum2 configuration (``benchmark/configs/mellum2_builder.py``): its
file against the catalog and ``manifest.config_faults``, the program's config
and the kernel geometry ``mellum2_builder`` makes, the rooflines against hand
counts, the reference apart from the program, and the cell with its traffic
and metrics."""
import json
import os
import sys

import pytest

import benchmark_tiny_tree as tiny

sys.path.insert(0, tiny.REPO)

from benchmark.configs import mellum2_builder  # noqa: E402
from benchmark.harness import manifest as bm  # noqa: E402
from benchmark.reference import mellum2_ref  # noqa: E402
from benchmark.rooflines import lfm2_moe, mellum2_flops, mellum2_ragged  # noqa: E402
from test_benchmark_solar2 import CATALOG  # noqa: E402  (the catalog beside the guide)

BUILDER = "benchmark.configs.mellum2_builder"
COD = ("attn.full_ms_per_step.cod", "attn.window_ms_per_step.cod",
       "kernel.ragged_roofline_share.cod", "kernel.ragged_items_per_step.cod",
       "moe.experts_ms_per_step.cod", "moe.experts_roofline_share.cod",
       "moe.route_ms_per_step.cod", "moe.load_max_over_mean.cod")


@pytest.fixture(scope="module")
def config():
    manifest = bm.load_manifest()
    entries = []
    for c in manifest["configs"]:
        with open(os.path.join(tiny.REPO, c["file"])) as f:
            on_disk = json.load(f)
        if on_disk["builder"] == BUILDER:
            entries.append((c, on_disk))
    assert len(entries) == 1
    entry, on_disk = entries[0]
    cell = next(w["name"] for w in manifest["workloads"] if w["config"] == entry["name"])
    return entry, on_disk, bm.resolve_cell(cell)


def test_the_cut_is_depth_alone(config):
    entry, on_disk, _ = config
    assert bm.config_faults(entry, on_disk) == []
    assert entry["reduced"] == on_disk["reduced"] == ["num_hidden_layers"]
    assert {k: (v["published"], v["here"]) for k, v in on_disk["cut"].items()} == {
        "num_hidden_layers": (28, 4)}
    assert on_disk["deployment"].startswith("stage 0 of a seven-stage pipeline")
    for key in ("attention", "window", "rotary", "router", "dense_width", "mtp",
                "initialisation"):
        assert len(on_disk["assumed"][key]) > 40, key


def test_a_cut_of_a_width_is_a_fault(config):
    entry, on_disk, _ = config
    wide = json.loads(json.dumps(on_disk))
    wide["model"]["moe_intermediate_size"] = 448
    assert any("a width is never cut" in f for f in bm.config_faults(entry, wide))


def test_every_number_of_the_catalog_entry_stands_at_the_top_level(config):
    """The file's top level is held to the catalog's config: every key
    there, nested groups whole, and only the reduced ones differ."""
    entry, on_disk, _ = config
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(json.loads(line) for line in f
                   if json.loads(line)["source_url"] == on_disk["source"])
    for key, value in row["config"].items():
        assert on_disk["published"][key] == value, key
        if key not in entry["reduced"]:
            assert on_disk[key] == value == on_disk["model"][key], key


@pytest.mark.parametrize("key,value", [
    ("hidden_size", 2304), ("num_attention_heads", 32), ("num_key_value_heads", 4),
    ("head_dim", 128), ("sliding_window", 1024), ("num_experts", 64),
    ("moe_intermediate_size", 896), ("num_experts_per_tok", 8),
    ("vocab_size", 98304)])
def test_a_published_width_is_never_cut(config, key, value):
    _, on_disk, _ = config
    assert on_disk[key] == on_disk["published"][key] == on_disk["model"][key] == value


def test_the_layer_lists_stand_whole(config):
    _, on_disk, _ = config
    for key, period in (("layer_types", ["sliding_attention"] * 3 + ["full_attention"]),
                        ("mlp_layer_types", ["sparse"] * 4)):
        assert on_disk[key] == on_disk["published"][key] == period * 7, key
    assert on_disk["num_hidden_layers"] == on_disk["model"]["num_layers"] == 4


def test_the_builder_makes_the_programs_config_and_the_kernels_geometry(config):
    _, on_disk, _ = config
    cfg = mellum2_builder.mellum2_config(on_disk)
    assert cfg.layer_types == ("sliding_attention",) * 3 + ("full_attention",)
    assert cfg.num_hidden_layers == 4 and cfg.period == 4
    assert cfg.yarn[2] == (18, 35) and cfg.yarn[1] == 1.2772588722239782
    geo = mellum2_builder.model_config(on_disk)
    assert (geo.num_heads, geo.head_dim, geo.num_layers) == (4, 128, 1)


def test_the_cell_is_compiled_for_the_chip_with_the_others(config):
    """The ragged launch at the cell's geometry and the whole fused step are
    compiled for the described v5e by ``test_benchmark_cells_compile_v5e.py``
    (one file holds every compile): this cell is among its cases."""
    import test_benchmark_cells_compile_v5e as compiles

    _, _, ctx = config
    assert ctx["entry"]["name"] in compiles.RAGGED_CELLS
    assert ctx["entry"]["name"] in compiles.SERVE_CELLS


def test_the_rooflines_against_hand_counts(config):
    _, _, ctx = config
    delta = {"work_items": 5.0, "window_work_items": 7.0, "block_rows": 30.0,
             "block_row_capacity": 64.0, "tokens": 9.0, "moe_assignments": 11.0,
             "moe_experts_touched": 3.0}
    ctx = dict(ctx, facts={"token_block": 8})
    page_kv = 2 * 128 * 128 * 2 * 4            # a K and a V page of 4 heads of 128
    qo = 2 * 30 * 128 * 2 * 32                 # the block rows' queries and outputs
    ragged = mellum2_ragged.needed_by_counters(ctx, delta)
    assert ragged["bytes"] == (5 * page_kv + qo) + 3 * (7 * page_kv + qo)
    assert ragged["flops"] == pytest.approx(
        4 * (30 / 8) * 128 * 128 * 32 * (5 + 3 * 7))
    moe = lfm2_moe.needed_by_counters(ctx, delta)
    assert moe["bytes"] == 3 * 12386304 + 11 * 2304 * 6
    assert moe["flops"] == 11 * 6 * 2304 * 896
    per_token = 2.0 * 4 * (2 * 2304 * 4096 + 2 * 2304 * 512 + 2304 * 64
                           + 8 * 3 * 2304 * 896)
    assert mellum2_flops.serve_flops(ctx, delta) == pytest.approx(
        30 * per_token + 9 * 2 * 2304 * 98304 + ragged["flops"])


def test_the_reference_is_independent_of_the_program():
    with open(mellum2_ref.__file__) as f:
        text = f.read()
    assert "paddle_tpu" not in text.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in text


def test_the_cell(config):
    _, _, ctx = config
    cell, traffic = ctx["cell"], ctx["traffic"]
    assert cell["engine"] == {"num_slots": 96, "page_size": 128, "max_context": 32768,
                              "prefill_token_budget": 1024, "prefix_cache": False,
                              "cache_dtype": "bfloat16", "num_pages": 24577}
    # every slot can hold a whole context, plus the null page
    assert cell["engine"]["num_pages"] == 96 * 32768 // 128 + 1
    assert (cell["lead_in_s"], cell["warm_requests"], cell["drain_s_max"],
            cell["trace"]["seconds"]) == (20.0, 96, 0.0, 3.0)
    assert cell["mosaic_kernels"] == ["_ragged_kernel", "_gmm_kernel"]
    check = cell["reference_check"]
    assert (check["prompt_tokens"], check["new_tokens"]) == (9216, 32)
    assert ctx["entry"]["chips"] == 1 and ctx["entry"]["traffic"] == "code_ctx_backlog"
    assert (traffic["mode"], traffic["backlog_factor"], traffic["pool_requests"],
            traffic["stratify_block"]) == ("backlog", 2, 256, 64)
    assert traffic["prompt_tokens"] == {"dist": "lognormal", "median": 10240, "sigma": 0.5,
                                        "min": 4096, "max": 28672}
    assert traffic["answer_tokens"] == {"dist": "lognormal", "median": 320, "sigma": 0.5,
                                        "min": 64, "max": 1536}
    # a whole context fits the cell's max_context
    assert (traffic["prompt_tokens"]["max"] + traffic["answer_tokens"]["max"]
            <= cell["engine"]["max_context"])
    reported = {m["name"] for m in ctx["per_layer"]}
    assert set(COD) | {"model.serve_mfu.sat", "device.serve_peak_hbm_share.sat",
                       "admission.slots_used_share.sat",
                       "kernel.ragged_items_per_step.sat"} <= reported
    assert "kernel.ragged_roofline_share.sat" not in reported   # GPT names
    # the engine's flight metrics' cells are pinned by test_benchmark_flight_steps.py
    assert not {"engine.flight_step_ms_p50.sat", "engine.host_late_step_share.sat",
                "engine.device_wait_share.sat", "engine.gc_pause_share.sat"} & reported
    for m in ctx["manifest"]["per_layer"]:
        if m["name"] in COD:
            assert m["workloads"] == [ctx["entry"]["name"]]
            assert m["moves"] == "serve_tokens_per_s"
    assert [m["name"] for m in ctx["end_to_end"]] == ["setup_s", "serve_tokens_per_s"]


def test_no_python_file_spells_a_cells_or_configs_name(config):
    _, _, ctx = config
    names = (ctx["entry"]["name"], ctx["entry"]["config"], ctx["entry"]["traffic"])
    root = os.path.join(tiny.REPO, "benchmark")
    for dirpath, _, files in os.walk(root):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(dirpath, f)) as fh:
                    text = fh.read()
                assert not any(n in text for n in names), (f, names)
