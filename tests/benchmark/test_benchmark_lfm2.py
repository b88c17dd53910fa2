"""The hybrid conv / grouped-query-attention configuration with routed experts
as the benchmark holds it: its file against the catalog's rules, its roofline
and operation counts against hand counts, and the builder's hand-over to the
reference.  Nothing here runs a chip; the configuration's published widths are
read, never built (a tiny model stands in for the round trip)."""
import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import benchmark_tiny_tree as tiny

sys.path.insert(0, tiny.REPO)

from benchmark.configs import lfm2_builder  # noqa: E402
from benchmark.harness import manifest as bm  # noqa: E402
from benchmark.reference import lfm2_moe_ref  # noqa: E402
from benchmark.rooflines import lfm2_moe, lfm2_moe_flops, lfm2_ragged  # noqa: E402

BUILDER = "benchmark.configs.lfm2_builder"


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


def test_the_cut_names_the_depth_and_nothing_else(config):
    entry, on_disk, _ = config
    assert bm.config_faults(entry, on_disk) == []
    assert entry["reduced"] == on_disk["reduced"] == ["num_hidden_layers"]
    assert set(on_disk["cut"]) == {"num_hidden_layers"}
    assert on_disk["cut"]["num_hidden_layers"]["published"] == 40
    assert on_disk["deployment"] and on_disk["assumed"]


def test_every_published_key_stands_at_the_top_level_and_in_the_model_group(config):
    """The catalog compares the file's TOP level with the source's config; the
    harness reads ``model``.  Both hold every published key, and agree with
    ``published`` everywhere but the depth; ``layer_types`` stands whole at
    the top and under ``published`` and is not repeated in ``model``."""
    _, on_disk, _ = config
    published, model = on_disk["published"], on_disk["model"]
    for key, value in published.items():
        assert key in on_disk, key
        if key == "layer_types":
            assert key not in model and on_disk[key] == value and len(value) == 40
            continue
        assert on_disk[key] == model[key], key
        if key == "num_hidden_layers":
            assert (value, model[key]) == (40, 10)
        else:
            assert model[key] == value, key
    for key, value in {"hidden_size": 2048, "intermediate_size": 11776,
                       "moe_intermediate_size": 1536, "num_experts": 64,
                       "num_experts_per_tok": 4, "num_attention_heads": 32,
                       "num_key_value_heads": 8, "vocab_size": 65536}.items():
        assert model[key] == value, key


def test_the_builder_makes_the_programs_config_and_the_pools_geometry(config):
    _, on_disk, ctx = config
    cfg = lfm2_builder.lfm2_config(on_disk)
    assert cfg.num_hidden_layers == 10 and cfg.segments() == (
        ("conv", "conv"), ("full_attention", "conv", "conv", "conv"), 2, ())
    assert (cfg.head_dim, cfg.rope_theta, cfg.conv_taps) == (64, 1e6, 2)
    geometry = lfm2_builder.model_config(on_disk)
    assert (geometry.num_heads, geometry.head_dim, geometry.num_layers) == (8, 128, 2)
    assert geometry.config == cfg
    # the depth under the harness's tools' name is the depth held
    shallow = json.loads(json.dumps(on_disk))
    shallow["model"]["num_layers"] = 6
    assert lfm2_builder.lfm2_config(shallow).num_hidden_layers == 6
    eng = ctx["cell"]["engine"]
    kv_and_tails = (2 * eng["num_pages"] * 8 * eng["page_size"] * 128
                    + 8 * eng["num_pages"] * 2 * 2048) * 2
    assert kv_and_tails == 604_569_600          # the pool the compiled step aliases


def test_serve_flops_against_a_hand_count(config):
    _, on_disk, ctx = config
    h, conv, attn = 2048, 4 * 2048 ** 2, 2 * 2048 ** 2 + 2 * 2048 * 512
    dense, routed = 3 * h * 11776, h * 64 + 4 * 3 * h * 1536
    per_token = 2.0 * (8 * conv + 2 * attn + 2 * dense + 8 * routed)
    assert lfm2_moe_flops.flops_per_token(on_disk) == per_token
    assert 1.20e9 < per_token < 1.22e9
    delta = {"block_rows": 100.0, "tokens": 64.0, "work_items": 300.0,
             "block_row_capacity": 800.0}
    full = dict(ctx, facts={"token_block": 8})
    attention = lfm2_ragged.needed_by_counters(full, delta)
    want = 100 * per_token + 64 * 2.0 * h * 65536 + attention["flops"]
    assert lfm2_moe_flops.serve_flops(full, delta) == want


def test_the_ragged_roofline_counts_grouped_heads(config):
    """300 items over 100 blocks of 100 real rows, page 128, heads of 64: K and
    V pages by the 8 K/V heads, operations and query rows by the 32 query
    heads, twice (two attention layers among the ten held)."""
    _, on_disk, ctx = config
    assert lfm2_ragged.attention_layers(on_disk) == 2
    got = lfm2_ragged.needed_by_counters(
        dict(ctx, facts={"token_block": 8}),
        {"work_items": 300.0, "block_rows": 100.0, "block_row_capacity": 800.0})
    assert got["flops"] == 2 * 4.0 * 1.0 * 128 * 64 * 32 * 300
    assert got["bytes"] == 2 * (2.0 * 128 * 64 * 2 * 8 * 300 + 2.0 * 100 * 64 * 2 * 32)


def test_the_experts_roofline_counts_what_the_router_made(config):
    _, on_disk, ctx = config
    assert lfm2_moe.expert_bytes(on_disk["model"]) == 18_874_368
    got = lfm2_moe.needed_by_counters(ctx, {"moe_assignments": 3200.0,
                                            "moe_experts_touched": 500.0})
    assert got["flops"] == 3200 * 6 * 2048 * 1536
    assert got["bytes"] == 500 * 18_874_368 + 3200 * 2048 * 6


def test_reference_weights_hand_over_the_programs_own_arrays():
    """Round trip at the CPU tests' size: every layer's dict holds the
    program's arrays (a period's layer its slice of the scan's stack), the
    expert stacks are handed whole, and the reference on them gives the
    program's own forward."""
    import paddle_tpu as pt
    from paddle_tpu.models import Lfm2StackedForCausalLM, lfm2_tiny

    pt.seed(2)
    model = Lfm2StackedForCausalLM(lfm2_tiny(num_hidden_layers=10))
    weights = lfm2_builder.reference_weights(model)
    kwargs = lfm2_builder.reference_kwargs(model)
    assert len(weights["layers"]) == 10 and kwargs["layer_types"] == model.config.layer_types
    assert weights["embed"] is model.embed._value
    for l, layer in enumerate(weights["layers"]):
        assert ("conv_in" in layer) == (kwargs["layer_types"][l] == "conv")
        assert ("w1" in layer) == (l < 2) and ("router" in layer) == (l >= 2)
        if l >= 2:
            assert layer["experts"][0] is model.moe_w1._value
            assert layer["expert_base"] == (l - 2) * 8
    np.testing.assert_array_equal(np.asarray(weights["layers"][6]["wq"]),
                                  np.asarray(model.body0_wq._value)[1])
    ids = np.random.default_rng(0).integers(0, 512, (1, 19))
    want = np.asarray(model(pt.to_tensor(ids))._value)
    got = np.asarray(lfm2_moe_ref.logits(weights, jnp.asarray(ids), **kwargs))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_the_reference_is_independent_of_the_program():
    with open(lfm2_moe_ref.__file__) as f:
        text = f.read()
    assert "paddle_tpu" not in text.split('"""', 2)[2]
    assert 'default_matmul_precision("highest")' in text


def test_the_cell_is_the_issues(config):
    _, _, ctx = config
    cell, traffic = ctx["cell"], ctx["traffic"]
    assert cell["engine"] == {"num_slots": 64, "page_size": 128, "max_context": 2048,
                              "prefill_token_budget": 256, "prefix_cache": True,
                              "cache_dtype": "bfloat16", "num_pages": 1025}
    assert (cell["lead_in_s"], cell["warm_requests"], cell["drain_s_max"],
            cell["trace"]["seconds"]) == (12.0, 64, 0.0, 3.0)
    assert cell["mosaic_kernels"] == ["_ragged_kernel", "_gmm_kernel"]
    assert (cell["reference_check"]["prompt_tokens"],
            cell["reference_check"]["new_tokens"]) == (200, 32)
    assert (traffic["mode"], traffic["backlog_factor"], traffic["pool_requests"],
            traffic["stratify_block"]) == ("backlog", 2, 256, 64)
    assert traffic["prompt_tokens"] == {"dist": "lognormal", "median": 256, "sigma": 0.6,
                                        "min": 64, "max": 1024}
    assert traffic["answer_tokens"] == {"dist": "lognormal", "median": 512, "sigma": 0.5,
                                        "min": 128, "max": 1024}
    reported = {m["name"] for m in ctx["per_layer"]}
    assert {"moe.experts_time_share.rsn", "moe.experts_roofline_share.rsn",
            "kernel.ragged_roofline_share.rsn", "model.serve_mfu.sat",
            "device.serve_peak_hbm_share.sat"} <= reported
    # the ragged kernel's time is read as in the dense cells (scope and kind,
    # nothing of a model); its roofline alone needs this model's head counts
    assert {n for n in reported if n.startswith("kernel.ragged")} == {
        "kernel.ragged_time_share.sat", "kernel.ragged_ms_per_step.sat",
        "kernel.ragged_roofline_share.rsn"}
