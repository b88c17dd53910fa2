"""The decoder-hybrid-decoder configuration as the benchmark holds it: its file
against the catalog's rules, its roofline and operation counts against hand
counts, and the builder's hand-over to the reference.  Nothing here runs a
chip; the configuration's published widths are read, never built (a tiny model
stands in for the round trip)."""
import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import benchmark_tiny_tree as tiny

sys.path.insert(0, tiny.REPO)

from benchmark.configs import phi4flash_builder  # noqa: E402
from benchmark.harness import manifest as bm  # noqa: E402
from benchmark.reference import phi4flash_ref  # noqa: E402
from benchmark.rooflines import phi4flash_flops, phi4flash_ragged, phi4flash_scan  # noqa: E402

BUILDER = "benchmark.configs.phi4flash_builder"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _entries():
    manifest = bm.load_manifest()
    out = []
    for c in manifest["configs"]:
        with open(os.path.join(tiny.REPO, c["file"])) as f:
            on_disk = json.load(f)
        if on_disk["builder"] == BUILDER:
            out.append((c, on_disk))
    return manifest, out


@pytest.fixture(scope="module")
def config():
    manifest, entries = _entries()
    assert len(entries) == 1
    entry, on_disk = entries[0]
    cell = next(w["name"] for w in manifest["workloads"] if w["config"] == entry["name"])
    return entry, on_disk, bm.resolve_cell(cell)


def test_nothing_is_cut(config):
    entry, on_disk, _ = config
    assert bm.config_faults(entry, on_disk) == []
    assert entry["reduced"] == on_disk["reduced"] == [] and on_disk["cut"] == {}
    assert on_disk["deployment"] and on_disk["source"] == entry["source"]
    for key in ("state_space_sizes", "positions", "differential_attention",
                "attention_biases", "norms", "layer_kinds"):
        assert len(on_disk["assumed"][key]) > 40, key


@pytest.mark.parametrize("key,value", [
    ("hidden_size", 2560), ("num_hidden_layers", 32), ("num_attention_heads", 40),
    ("num_key_value_heads", 20), ("intermediate_size", 10240), ("sliding_window", 512),
    ("vocab_size", 200064), ("mb_per_layer", 2), ("max_position_embeddings", 262144),
    ("layer_norm_eps", 1e-5)])
def test_a_published_size_stands_at_the_top_level_in_published_and_in_the_model_group(
        config, key, value):
    _, on_disk, _ = config
    assert on_disk[key] == on_disk["published"][key] == on_disk["model"][key] == value


def test_the_three_groups_agree_on_every_published_key(config):
    """The catalog compares the file's TOP level with the source's config; the
    harness reads ``model``: both hold every published key with the published
    value, and ``model`` adds only the depth under the harness's name."""
    _, on_disk, _ = config
    published, model = on_disk["published"], on_disk["model"]
    assert set(model) == set(published) | {"num_layers"}
    assert model["num_layers"] == model["num_hidden_layers"]
    for key, value in published.items():
        assert on_disk[key] == model[key] == value, key
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f) if r["source_url"] == on_disk["source"])
        assert published == row["config"]


def test_the_builder_makes_the_programs_config_and_the_kernels_geometry(config):
    _, on_disk, ctx = config
    cfg = phi4flash_builder.flash_config(on_disk)
    assert (cfg.num_hidden_layers, cfg.self_periods, cfg.cross_periods) == (32, 8, 7)
    assert (cfg.d_inner, cfg.mamba_d_state, cfg.mamba_dt_rank, cfg.head_dim) == (5120, 16, 160, 64)
    geometry = phi4flash_builder.model_config(on_disk)
    assert (geometry.num_heads, geometry.head_dim, geometry.num_layers) == (10, 128, 1)
    assert geometry.config == cfg
    shallow = json.loads(json.dumps(on_disk))
    shallow["model"]["num_layers"] = 8
    assert phi4flash_builder.flash_config(shallow).num_hidden_layers == 8
    # the three kinds of state, reckoned as ISSUE 35 reckons them
    eng = ctx["cell"]["engine"]
    page = 128 * 20 * (64 + 64) * 2
    ring = -(-(512 - 1 + eng["prefill_token_budget"]) // 128) + 1
    assert (page, ring) == (655_360, 7)
    assert eng["num_pages"] * page == 2_685_009_920
    assert 8 * (eng["num_slots"] * ring + 1) * page == 2_354_053_120
    assert 9 * (2 * eng["num_slots"] + 1) * (5120 * 16 * 4 + 3 * 5120 * 2) == 416_102_400


def test_serve_flops_against_a_hand_count(config):
    _, on_disk, ctx = config
    h, f, di = 2560, 10240, 5120
    ssm = 2 * h * di + di * (160 + 32) + 160 * di + di * h + di * 16
    attn, cross, gmu = h * (2560 + 2560) + h * h, 2 * h * h, 2 * h * di
    per_token = 2.0 * (9 * ssm + 9 * attn + 7 * (cross + gmu) + 32 * 3 * h * f)
    assert phi4flash_flops.flops_per_token(on_disk) == per_token
    # 2 operations a parameter a token, less the embedding, the norms and biases
    assert 6.65e9 < per_token < 6.72e9
    delta = {"block_rows": 190.0, "tokens": 64.0, "work_items": 1600.0,
             "window_work_items": 400.0, "block_row_capacity": 640.0}
    full = dict(ctx, facts={"token_block": 8})
    attention = phi4flash_ragged.needed_by_counters(full, delta)
    want = 190 * per_token + 64 * 2.0 * h * 200064 + attention["flops"]
    assert phi4flash_flops.serve_flops(full, delta) == want


def test_the_ragged_roofline_counts_both_work_lists(config):
    """1,600 items on the shared list walked by 8 layers (the full-attention
    layer and 7 cross layers) and 400 on the window list walked by 8, over 80
    blocks of 190 real rows, page 128: a K and a V page of 20 heads of 64 an
    item, read once; operations by the 40 query heads."""
    _, on_disk, ctx = config
    assert phi4flash_ragged.passes(on_disk["model"]) == {"work_items": 8,
                                                         "window_work_items": 8}
    got = phi4flash_ragged.needed_by_counters(
        dict(ctx, facts={"token_block": 8}),
        {"work_items": 1600.0, "window_work_items": 400.0, "block_rows": 190.0,
         "block_row_capacity": 640.0})
    mean_rows = 190 / 80
    assert got["flops"] == pytest.approx(8 * 4.0 * mean_rows * 128 * 64 * 40 * (1600 + 400))
    kv = 2.0 * 128 * 64 * 2 * 20
    assert kv == 655_360
    assert got["bytes"] == pytest.approx(8 * kv * (1600 + 400) + 16 * 3.0 * 190 * 64 * 2 * 40)


def test_the_scan_roofline_counts_states_and_rows(config):
    _, on_disk, ctx = config
    assert phi4flash_scan.state_space_layers(on_disk["model"]) == 9
    got = phi4flash_scan.needed_by_counters(ctx, {"ssm_runs": 70.0, "ssm_rows": 190.0})
    assert got["bytes"] == 9 * (70 * 2 * 327_680 + 190 * (3 * 5120 + 32) * 4)
    assert got["flops"] == 7.0 * 5120 * 16 * 190 * 9


def test_reference_weights_hand_over_the_programs_own_arrays():
    """Round trip at the CPU tests' size: every layer's dict holds the
    program's arrays under its kind (a period's layer its slice of the scan's
    stack), and the reference on them gives the program's own forward."""
    import paddle_tpu as pt
    from paddle_tpu.models import Phi4FlashForCausalLM, phi4flash_tiny

    pt.seed(2)
    model = Phi4FlashForCausalLM(phi4flash_tiny(num_hidden_layers=12))
    weights = phi4flash_builder.reference_weights(model)
    kwargs = phi4flash_builder.reference_kwargs(model)
    assert kwargs == {"heads": 4, "kv_heads": 2, "window": 8, "eps": 1e-5,
                      "d_state": 4, "dt_rank": 4}
    assert weights["embed"] is model.embed._value
    kinds = [layer["kind"] for layer in weights["layers"]]
    assert kinds == ["ssm", "attn"] * 4 + ["gmu", "cross"] * 2
    # a period's layer hands the scan's stack whole and its index in it
    assert weights["layers"][4]["w_in"] is model.self0_w_in._value
    assert weights["layers"][4]["period"] == 2
    assert weights["layers"][11]["wq"] is model.cross1_wq._value
    assert weights["layers"][11]["period"] == 1
    assert weights["layers"][6]["a_log"] is model.mid0_a_log._value
    assert "period" not in weights["layers"][6]
    ids = np.random.default_rng(0).integers(0, 512, (1, 23))
    want = np.asarray(model(pt.to_tensor(ids))._value)
    got = np.asarray(phi4flash_ref.logits(weights, jnp.asarray(ids), **kwargs))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_the_reference_is_independent_of_the_program():
    with open(phi4flash_ref.__file__) as f:
        text = f.read()
    assert "paddle_tpu" not in text.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in text


def test_the_cell_is_the_issues(config):
    _, _, ctx = config
    cell, traffic = ctx["cell"], ctx["traffic"]
    assert cell["engine"] == {"num_slots": 64, "page_size": 128, "max_context": 8192,
                              "prefill_token_budget": 256, "prefix_cache": False,
                              "cache_dtype": "bfloat16", "num_pages": 4097}
    assert (cell["lead_in_s"], cell["warm_requests"], cell["drain_s_max"],
            cell["trace"]["seconds"]) == (20.0, 64, 0.0, 3.0)
    assert cell["mosaic_kernels"] == ["_ragged_kernel", "_ssm_scan_kernel"]
    assert (cell["reference_check"]["prompt_tokens"],
            cell["reference_check"]["new_tokens"]) == (700, 32)
    assert ctx["entry"]["chips"] == 1
    assert (traffic["mode"], traffic["backlog_factor"], traffic["pool_requests"],
            traffic["stratify_block"]) == ("backlog", 2, 256, 64)
    assert traffic["prompt_tokens"] == {"dist": "lognormal", "median": 2048, "sigma": 0.5,
                                        "min": 512, "max": 6144}
    assert traffic["answer_tokens"] == {"dist": "lognormal", "median": 1024, "sigma": 0.5,
                                        "min": 256, "max": 2048}
    reported = {m["name"] for m in ctx["per_layer"]}
    assert {"attn.shared_ms_per_step.lng", "attn.shared_time_share.lng",
            "attn.window_ms_per_step.lng", "ssm.ms_per_step.lng", "gmu.ms_per_step.lng",
            "attn.diff_ms_per_step.lng", "kernel.ragged_roofline_share.lng",
            "ssm.scan_roofline_share.lng", "model.serve_mfu.sat",
            "device.serve_peak_hbm_share.sat", "device.serve_unscoped_share.sat"} <= reported
    assert {n for n in reported if n.startswith("kernel.ragged")} == {
        "kernel.ragged_time_share.sat", "kernel.ragged_ms_per_step.sat",
        "kernel.ragged_roofline_share.lng"}
    assert [m["name"] for m in ctx["end_to_end"]] == ["setup_s", "serve_tokens_per_s"]
