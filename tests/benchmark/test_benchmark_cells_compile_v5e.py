"""Each cell's Pallas kernels compile for the described v5e at the cell's real
geometry (no chip, no chip time): what Mosaic refuses, it refuses here.  The
topology is described inside a fixture, after a test of this file has started,
and every compile runs in this process; where it cannot be described the tests
skip.  The two fused serving steps are compiled whole through
``benchmark/aot_compile.py`` (about 20 s each) and must fit the chip's
``bytes_limit``; the train steps take two minutes each and stay with that tool
(``PERF.md`` section 4 has what it read)."""
import json
import math
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import benchmark_tiny_tree as tiny

sys.path.insert(0, tiny.REPO)

from benchmark import aot_compile  # noqa: E402
from benchmark.harness import manifest as bm  # noqa: E402
from benchmark.rooflines import flash as flash_roofline  # noqa: E402

TRAIN_CELLS = [w["name"] for w in bm.load_manifest()["workloads"]
               if bm.resolve_cell(w["name"])["traffic"]["kind"] == "train"]
SERVE_CELLS = [w["name"] for w in bm.load_manifest()["workloads"]
               if bm.resolve_cell(w["name"])["traffic"]["kind"] == "serve"]


BYTES_LIMIT = 16_909_336_064        # one v5e chip's bytes_limit (chip run, PR 21)


@pytest.fixture(scope="module")
def topo():
    try:
        return aot_compile.describe_topology("v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def _custom_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


@pytest.mark.parametrize("cell", TRAIN_CELLS)
def test_flash_kernels_compile_at_the_cells_geometry(cell, one_chip, no_compile_cache):
    """Forward and both backward kernels at the per-device shape: batch over
    dp, heads over mp (what the flash ``shard_map`` hands each chip)."""
    from paddle_tpu.ops.pallas_kernels import flash_attention as fa

    geo = flash_roofline.geometry(bm.resolve_cell(cell))
    shape = (geo["batch"], geo["heads"], geo["seq"], geo["head_dim"])
    assert fa.shape_supported(geo["seq"], geo["head_dim"])
    arg = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    scale = 1.0 / math.sqrt(geo["head_dim"])

    def loss(q, k, v):
        return fa._flash_bnsd(q, k, v, True, scale).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(arg, arg, arg).compile()
    assert _custom_calls(compiled) >= 3         # _fwd_kernel, _bwd_dkv_kernel, _bwd_dq_kernel
    assert compiled.memory_analysis().temp_size_in_bytes < 4 << 30


@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_ragged_kernel_compiles_at_the_cells_geometry(cell, one_chip, no_compile_cache):
    """The fused step's one ragged launch at the engine's fixed geometry: the
    cell's pool, slots and prefill budget give the block and work-list
    lengths exactly as ``ServingEngine._step_geometry`` does."""
    from paddle_tpu.ops.pallas_kernels import ragged_paged_attention as ra

    ctx = bm.resolve_cell(cell)
    eng, model = ctx["cell"]["engine"], ctx["config"]["model"]
    heads = model["num_heads"]
    dim = model["hidden_size"] // heads
    page = eng["page_size"]
    qb = ra.ragged_token_block(page, dim, eng["cache_dtype"])
    assert ra.ragged_shape_supported(page, dim, qb)
    nb = eng["num_slots"] + eng["prefill_token_budget"] // qb
    wl = nb * (eng["max_context"] // page)

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = struct((eng["num_pages"], heads, page, dim), jnp.bfloat16)
    i32 = jnp.int32
    compiled = jax.jit(
        lambda q, k, v, wb, wp, ws, n, bb, br: ra._ragged_pallas(
            q, k, v, wb, wp, ws, n, bb, br, 1.0 / math.sqrt(dim))
    ).lower(struct((nb, heads, qb, dim), jnp.bfloat16), pool, pool,
            struct((wl,), i32), struct((wl,), i32), struct((wl,), i32),
            struct((1,), i32), struct((nb,), i32), struct((nb,), i32)).compile()
    assert _custom_calls(compiled) >= 1
    # the pool as the cell states it is what PR 22's traces show on the chip
    pool_bytes = 2 * model["num_layers"] * eng["num_pages"] * heads * page * dim * 2
    assert pool_bytes < 0.5 * 16e9, json.dumps(eng)


@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_fused_serving_step_compiles_whole_and_fits_the_chip(cell, topo, no_compile_cache):
    """The cell's engine at its real geometry and pool: the greedy fused step,
    lowered for one described chip, holds the ragged kernel and needs less than
    the chip's ``bytes_limit`` (weights + pool + the step's temporaries)."""
    report = aot_compile.serve_step(bm.resolve_cell(cell), topo)
    assert report["mosaic_calls"] >= 1
    assert report["device_bytes"] < BYTES_LIMIT, report
    assert report["argument_bytes"] > 0.25 * BYTES_LIMIT     # a deployment's worth of state
