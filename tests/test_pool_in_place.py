"""The fused serving step updates the K/V pool in place (docs/serving.md
"Page-table addressing"): the stacked pool rides the layer loop's carry as
one donated buffer a side and each layer's tokens are written into it where
it lies, so no operation of a step moves a layer's pool: on a TPU by ONE
Mosaic launch a layer over the step's write list, its pool operands aliased
to its results (docs/serving.md "The pool write", PR 34); off it as rows of
the pool's flat view.

Two readings of the program hold that, neither a measurement: the step
compiled for the described v5e (``benchmark/aot_compile.py``, imported
read-only; skipped where no topology can be described) and the step as it
is lowered on the CPU.

The same compiled step holds what PR 30 made of its ragged launch: the
kernel's grid ends at the step's item count (docs/serving.md "Fused mixed
step"), which the Mosaic module inside the ``tpu_custom_call`` states as a
dynamic iteration bound, and what PR 32 made of it: a grid step moves a
block of heads of its item's page, all of them at every served geometry
(the sharded ones, which no cell runs yet, are compiled here too)."""
import base64
import os
import re
import sys

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models import GPTStackedForPretraining, gpt_tiny
from paddle_tpu.serving import ServingEngine

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import aot_compile  # noqa: E402
from benchmark.harness import manifest  # noqa: E402

CELL = "gpt_1p3b.serve_chat_r80"
# the cell's widths at a cut depth and pool: seconds to compile, and a
# layer's pool (67 MB) larger than any weight slice or activation
LAYERS, PAGES = 4, 128

# results a step may hold at the size of a layer's pool without moving it
STILL = ("parameter", "tuple", "get-tuple-element", "while", "bitcast", "scatter")
_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
             "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8, "u64": 8}
_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_COMPUTATION = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_ARRAY = re.compile(r"\b([a-z]+\d+|pred)\[([\d,]*)\]")


def _largest_array_bytes(type_text: str) -> int:
    sizes = [_ITEMSIZE.get(dt, 4) * int(np.prod([int(d) for d in dims.split(",") if d] or [1]))
             for dt, dims in _ARRAY.findall(type_text)]
    return max(sizes, default=0)


def _instructions(hlo_text: str):
    """(computation, name, opcode, largest array of the result in bytes,
    called computation or None, is ROOT, results alias operands) of every
    instruction of an optimized HLO module's text."""
    computation = None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            c = _COMPUTATION.match(line)
            computation = c.group(1) if c else computation
            continue
        rest = re.sub(r"\{[^{}]*\}", "", m.group(3))      # layouts hold parentheses
        if rest.startswith("("):                           # a tuple's type
            depth = 0
            for end, ch in enumerate(rest):
                depth += (ch == "(") - (ch == ")")
                if depth == 0:
                    break
            result, tail = rest[:end + 1], rest[end + 1:]
        else:
            result, _, tail = rest.partition(" ")
        op = re.match(r"\s*([\w\-]+)\(", tail)
        if op is None:
            continue
        calls = re.search(r"calls=%?([\w.\-]+)", line)
        yield (computation, m.group(2), op.group(1), _largest_array_bytes(result),
               calls.group(1) if calls else None, bool(m.group(1)),
               "output_to_operand_aliasing={" in line)


def pool_movers(hlo_text: str, layer_pool_bytes: int):
    """The instructions whose result is a buffer as large as one layer's pool
    and is neither plumbing nor written in place: a ``scatter`` (or a fusion
    whose root is one), or a custom call whose results alias its operands (the
    pool write's launch).  What a fused computation holds inside is no buffer."""
    instructions = list(_instructions(hlo_text))
    root_op = {comp: op for comp, _, op, _, _, is_root, _ in instructions if is_root}
    fused = {calls for _, _, op, _, calls, _, _ in instructions if op == "fusion"}
    return [(name, op) for comp, name, op, nbytes, calls, _, aliased in instructions
            if nbytes >= layer_pool_bytes and op not in STILL and comp not in fused
            and not (op == "fusion" and root_op.get(calls) == "scatter")
            and not (op == "custom-call" and aliased)]


def test_the_reader_sees_a_copy_and_passes_an_in_place_scatter():
    text = """HloModule m, is_scheduled=true

%fused_scatter (p0: bf16[1024,128], p1: s32[8], p2: bf16[8,128]) -> bf16[1024,128] {
  %p0 = bf16[1024,128]{1,0:T(8,128)(2,1)} parameter(0)
  %p1 = s32[8]{0} parameter(1)
  %p2 = bf16[8,128]{1,0} parameter(2)
  %inside = bf16[1024,128]{1,0:T(8,128)(2,1)} copy(%p0)
  ROOT %scatter.1 = bf16[1024,128]{1,0:T(8,128)(2,1)} scatter(%p0, %p1, %p2), to_apply=%add
}

ENTRY %main (a: bf16[2,4,128,128]) -> (s32[], bf16[2,4,128,128]) {
  %a = bf16[2,4,128,128]{3,2,1,0:T(8,128)(2,1)} parameter(0)
  %b = bf16[1024,128]{1,0:T(8,128)(2,1)} bitcast(%a)
  %f = bf16[1024,128]{1,0:T(8,128)(2,1)} fusion(%b, %i, %u), kind=kCustom, calls=%fused_scatter
  %copy.7 = bf16[4,128,128]{2,1,0:T(8,128)(2,1)} copy(%slice)
  %w = (s32[]{:T(128)}, bf16[1024,128]{1,0:T(8,128)(2,1)}) while(%t), condition=%c, body=%d
  ROOT %t = (s32[], bf16[2,4,128,128]{3,2,1,0}) tuple(%n, %f)
}
"""
    assert pool_movers(text, 4 * 128 * 128 * 2) == [("copy.7", "copy")]
    assert pool_movers(text, 4 * 128 * 128 * 2 + 1) == []
    # a custom call with a pool-sized result moves it unless the result is
    # an operand (the pool write's launch)
    call = ('  %k = bf16[1024,128]{1,0} custom-call(%b, %u), '
            'custom_call_target="tpu_custom_call"')
    aliased = call + ", output_to_operand_aliasing={{}: (0, {})}"
    for line, movers in ((call, [("copy.7", "copy"), ("k", "custom-call")]),
                         (aliased, [("copy.7", "copy")])):
        assert pool_movers(text.replace("  ROOT %t", line + "\n  ROOT %t"),
                           4 * 128 * 128 * 2) == movers


@pytest.fixture(scope="module")
def topo():
    try:
        return aot_compile.describe_topology("v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def compiled_step(topo):
    """The cell's fused step at its cut depth and pool, compiled once for
    the described chip: ``(context, compiled)``."""
    ctx = manifest.resolve_cell(CELL)
    ctx["config"]["model"]["num_layers"] = LAYERS
    ctx["cell"]["engine"]["num_pages"] = PAGES
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(aot_compile, "_report", lambda compiled: compiled)
        return ctx, aot_compile.serve_step(ctx, topo)


HYBRID_CELL = "lfm2_24b_a2b_cut.serve_reason_sat"


def test_no_operation_of_the_hybrid_step_moves_a_layers_experts_or_a_pool(topo):
    """The hybrid decoder's fused step at its published widths, 8 experts a
    routed layer and 6 layers (2 dense + one period), compiled for the
    described chip: the experts of every routed layer ride as ONE stacked
    operand the layer loop closes over, so no operation's result is as large
    as a layer's expert matrix stack (a slice of the stack would be); the K|V
    pool and the tail pool are donated and aliased; the step holds
    the ragged launch and three grouped products a routed layer."""
    ctx = manifest.resolve_cell(HYBRID_CELL)
    model, eng = ctx["config"]["model"], ctx["cell"]["engine"]
    model["num_experts"], model["num_layers"] = 8, 6
    eng["num_pages"] = PAGES
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(aot_compile, "_report", lambda compiled: compiled)
        compiled = aot_compile.serve_step(ctx, topo)
    text, memory = compiled.as_text(), compiled.memory_analysis()
    one_matrix_stack = (model["num_experts"] * model["hidden_size"]
                        * model["moe_intermediate_size"] * 2)               # bf16
    kv_pool = PAGES * model["num_key_value_heads"] * eng["page_size"] * 128 * 2
    tail_pool = 5 * PAGES * 2 * model["hidden_size"] * 2
    assert one_matrix_stack > 50e6
    assert pool_movers(text, one_matrix_stack) == []
    # both pools are aliased into the step's outputs: written where they lie
    # (the ragged launch, once a block width; the pool write's; and three
    # grouped products a routed layer)
    assert text.count("tpu_custom_call") == 3 + 3 * 4
    assert memory.alias_size_in_bytes >= kv_pool + tail_pool
    assert memory.temp_size_in_bytes < 4 * one_matrix_stack, memory.temp_size_in_bytes
    # the K|V pool's write is the launch: its one pool operand is its result,
    # nothing scatters into a K|V-pool-sized buffer (the tail pool's slabs
    # still do, into theirs), and what the step holds beside its arguments
    # stays under 5% of the pools
    (write,) = pool_write_calls(text)
    assert write["aliased"] == [f"bf16[{PAGES},{model['num_key_value_heads']},"
                                f"{eng['page_size']},128]"]
    assert pool_scatters(text, kv_pool) == []
    assert memory.temp_size_in_bytes < 0.05 * (kv_pool + tail_pool) + 3.2 * one_matrix_stack


SLOT_STATE_CELL = "phi4_mini_flash.serve_longctx_sat"
_RESULT = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\(.*?\)|\S+)\s+([\w\-]+)\(")


def test_no_operation_of_the_slot_state_step_moves_a_pool(topo):
    """The decoder-hybrid-decoder's fused step at its published widths, 8
    layers (every kind) and a paged pool of 1,024 pages, compiled for the
    described chip: all six pools (the paged K and V, the window ring's K and
    V, the state-space rows and the convolution's) are donated and aliased,
    and every operation whose result has a pool's shape is plumbing, a
    Mosaic launch whose pool operands are its results (the pool write, the
    scan, the convolution's kept rows) or the state rows' scatter: nothing
    copies one: the convolution's rows too are written where they lie, not
    staged through the chip's on-chip memory and back."""
    pages = 1024
    ctx = manifest.resolve_cell(SLOT_STATE_CELL)
    model, eng = ctx["config"]["model"], ctx["cell"]["engine"]
    model["num_layers"] = 8
    eng["num_pages"] = pages
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(aot_compile, "_report", lambda compiled: compiled)
        compiled = aot_compile.serve_step(ctx, topo)
    text, memory = compiled.as_text(), compiled.memory_analysis()
    slots, page = eng["num_slots"], eng["page_size"]
    ring = slots * 7 + 1                    # 7 ring pages a slot: window + a run
    rows = 2 * slots + 1
    pools = {f"bf16[{pages},10,{page},128]": "kv", f"bf16[1,{pages},10,{page},128]": "kv",
             f"bf16[{2 * ring},10,{page},128]": "ring", f"bf16[2,{ring},10,{page},128]": "ring",
             f"f32[{3 * rows},5,16,8,128]": "ssm", f"f32[3,{rows},5,16,8,128]": "ssm",
             f"bf16[{3 * rows},120,128]": "conv", f"bf16[3,{rows},120,128]": "conv"}
    nbytes = (2 * pages * 10 * page * 128 * 2 + 2 * 2 * ring * 10 * page * 128 * 2
              + 3 * rows * 5 * 16 * 8 * 128 * 4 + 3 * rows * 120 * 128 * 2)
    assert memory.alias_size_in_bytes >= nbytes
    assert memory.temp_size_in_bytes < 0.05 * nbytes, memory.temp_size_in_bytes
    # the window layers' write, launch (once a block width) and scan in the
    # loop; the memory layer's scan; the full-attention layer's write and
    # launch; the cross layers' launch; the kept rows beside each scan
    assert text.count("tpu_custom_call") == 12
    assert mosaic_iteration_bounds(text).count([_DYNAMIC]) >= 4      # writes, kept rows
    movers = []
    for line in text.splitlines():
        m = _RESULT.match(line)
        if m is None or not any(shape in m.group(1) for shape in pools):
            continue
        op = m.group(2)
        aliased = op == "custom-call" and "output_to_operand_aliasing={" in line
        scatter = op == "fusion" and "ssm.state_write/scatter" in line
        if op not in STILL and not aliased and not scatter:
            movers.append(line.strip()[:160])
    assert movers == []


DELTA_RULE_CELL = "solar_open2_cut.serve_longctx_sat"


def test_no_operation_of_the_delta_rule_step_moves_the_kept_rows(topo):
    """The delta-rule hybrid's fused step at its published widths (one period:
    a GQA layer and three delta-rule layers), 8 experts held and a paged pool
    of 256 pages, compiled for the described chip: the convolution's kept
    rows (``[3 x 257, 576, 128]``, 113.7 MB) are plumbing or the result of
    ONE aliased launch a delta-rule layer, under ``kernel.kept_rows`` inside
    ``kda.state_write``; nothing stages, slices or copies them, no operation
    holds a kept row for every row of the step, and nothing under the
    convolution's two scopes but that launch is larger than the step's
    float32 activations ``[T, 24576]``."""
    from paddle_tpu.telemetry import scopes

    ctx = manifest.resolve_cell(DELTA_RULE_CELL)
    model, eng = ctx["config"]["model"], ctx["cell"]["engine"]
    model["experts_held"] = [0, 8]
    eng["num_pages"] = 256
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(aot_compile, "_report", lambda compiled: compiled)
        compiled = aot_compile.serve_step(ctx, topo)
    text = compiled.as_text()
    rows, t = 2 * eng["num_slots"] + 1, eng["num_slots"] + eng["prefill_token_budget"]
    width = 3 * 64 * 128
    kept = (f"bf16[{3 * rows},576,128]", f"bf16[3,{rows},576,128]")
    movers = []
    for line in text.splitlines():
        m = _RESULT.match(line)
        if m is None or not any(shape in m.group(1) for shape in kept):
            continue
        aliased = m.group(2) == "custom-call" and "output_to_operand_aliasing={" in line
        if m.group(2) not in STILL and not aliased:
            movers.append(line.strip()[:160])
    assert movers == []
    assert f"[{t},576,128]" not in text and f"[{t},3,{width}]" not in text
    # the delta-rule layers' three launches, each writing the kept rows where
    # they lie, beside the GQA layer's two ragged launches (a narrow and a
    # wide one), the grouped products and the rule's
    assert text.count("tpu_custom_call") == 21
    launches = [c for c in pool_write_calls(text) if c["aliased"] == [kept[0]]]
    assert len(launches) == 3 and all(c["results"] == [0] for c in launches)
    mapped = scopes.scopes_of_hlo_text(text)
    names = [n for n, s in mapped.items()
             if s.scope.endswith("kernel.kept_rows") and s.rule == "own"]
    assert len(names) == 3 and all(n.startswith("kernel.kept_rows") for n in names)
    assert {mapped[n].scope for n in names} == {"layers/kda.state_write/kernel.kept_rows"}
    largest = 0
    for comp, name, op, nbytes, _, _, aliased in _instructions(text):
        scope = mapped.get(name)
        if (scope is not None and re.search(r"kda\.(conv|state_write)", scope.scope)
                and not aliased and op not in STILL):
            largest = max(largest, nbytes)
    assert 0 < largest <= t * width * 4, largest


def test_no_operation_of_the_compiled_step_moves_a_layers_pool(compiled_step):
    ctx, compiled = compiled_step
    model, eng = ctx["config"]["model"], ctx["cell"]["engine"]
    layer_pool = (PAGES * model["num_heads"] * eng["page_size"]
                  * (model["hidden_size"] // model["num_heads"]) * 2)      # bf16
    pool = 2 * LAYERS * layer_pool                                         # K and V
    text, memory = compiled.as_text(), compiled.memory_analysis()
    # the ragged launch, once a block width, and the write
    assert text.count("tpu_custom_call") == 3
    assert pool_movers(text, layer_pool) == []
    assert memory.temp_size_in_bytes < 0.05 * pool, memory.temp_size_in_bytes
    assert memory.alias_size_in_bytes >= pool, memory.alias_size_in_bytes


def pool_write_calls(hlo_text: str):
    """The Mosaic calls of a compiled step that write a pool where it lies:
    for each custom call that aliases results to operands, the types of the
    aliased operands (``output_to_operand_aliasing`` pairs result i with an
    operand; the ragged launch and the grouped product alias nothing)."""
    calls = []
    for line in hlo_text.splitlines():
        if "tpu_custom_call" not in line or "output_to_operand_aliasing={" not in line:
            continue
        pairs = re.findall(r"\{(\d*)\}: \((\d+), \{\}\)",
                           line.split("output_to_operand_aliasing={", 1)[1])
        types = re.findall(r"(\w+\[[\d,]*\])(?:\{[^{}]*\})?",
                           line.split("operand_layout_constraints={", 1)[1])
        calls.append({"aliased": [types[int(op)] for _, op in pairs],
                      "results": [int(out or 0) for out, _ in pairs]})
    return calls


def pool_scatters(hlo_text: str, layer_pool_bytes: int):
    """The scatters (or fusions rooted in one) of a compiled step whose result
    is as large as a layer's pool: the row scatter the CPU path keeps."""
    instructions = list(_instructions(hlo_text))
    root_op = {comp: op for comp, _, op, _, _, is_root, _ in instructions if is_root}
    fused = {calls for _, _, op, _, calls, _, _ in instructions if op == "fusion"}
    return [name for comp, name, op, nbytes, calls, _, _ in instructions
            if nbytes >= layer_pool_bytes and comp not in fused
            and (op == "scatter" or (op == "fusion" and root_op.get(calls) == "scatter"))]


def test_the_readers_of_the_write_see_an_aliased_call_and_a_pool_sized_scatter():
    text = """HloModule m, is_scheduled=true

%fused_scatter (p0: bf16[1024,128], p1: s32[8], p2: bf16[8,128]) -> bf16[1024,128] {
  %p0 = bf16[1024,128]{1,0:T(8,128)(2,1)} parameter(0)
  ROOT %scatter.1 = bf16[1024,128]{1,0:T(8,128)(2,1)} scatter(%p0, %p1, %p2), to_apply=%add
}

ENTRY %main (a: bf16[8,4,32,128]) -> bf16[8,4,32,128] {
  %f = bf16[1024,128]{1,0:T(8,128)(2,1)} fusion(%b, %i, %u), kind=kCustom, calls=%fused_scatter
  %r = bf16[16,4,8,128]{3,2,1,0} custom-call(%n, %q, %k), custom_call_target="tpu_custom_call", operand_layout_constraints={s32[]{:T(128)}, bf16[16,4,8,128]{3,2,1,0}, bf16[8,4,32,128]{3,2,1,0}}
  ROOT %w = (bf16[8,4,32,128]{3,2,1,0}, bf16[8,4,32,128]{3,2,1,0}) custom-call(%n, %s, %k, %v, %x), custom_call_target="tpu_custom_call", operand_layout_constraints={s32[], s32[4]{0}, bf16[8,4,32,128]{3,2,1,0}, bf16[8,4,32,128]{3,2,1,0}, bf16[16,4,128]{2,1,0}}, output_to_operand_aliasing={{0}: (2, {}), {1}: (3, {})}
}
"""
    assert pool_write_calls(text) == [
        {"aliased": ["bf16[8,4,32,128]", "bf16[8,4,32,128]"], "results": [0, 1]}]
    assert pool_scatters(text, 1024 * 128 * 2) == ["f"]
    assert pool_scatters(text, 1024 * 128 * 2 + 1) == []


def test_the_compiled_step_writes_the_pools_with_one_aliased_launch(compiled_step):
    """The layer loop's body holds ONE Mosaic call whose results are its pool
    operands (K and V, each the whole stacked pool ``[L * P, H, page, D]``),
    and no scatter of the bfloat16 step has a pool-sized result: the row
    scatter is off this path.  On the parent's tree the write was two such
    scatters and no call aliased anything."""
    ctx, compiled = compiled_step
    model, eng = ctx["config"]["model"], ctx["cell"]["engine"]
    heads = model["num_heads"]
    stacked = (f"bf16[{LAYERS * PAGES},{heads},{eng['page_size']},"
               f"{model['hidden_size'] // heads}]")
    text = compiled.as_text()
    assert pool_write_calls(text) == [{"aliased": [stacked, stacked],
                                       "results": [0, 1]}]
    layer_pool = PAGES * heads * eng["page_size"] * (model["hidden_size"] // heads) * 2
    assert pool_scatters(text, layer_pool) == []
    # the launch is attn.pool_write's (cache.pool_update_ms_per_step.* reads
    # that scope by name), and the step leaves nothing unscoped
    from paddle_tpu.telemetry import scopes

    mapped = scopes.scopes_of_hlo_text(text)
    launches = [s for name, s in mapped.items()
                if re.search(rf"%?{re.escape(name)} = [^\n]*tpu_custom_call[^\n]*"
                             r"output_to_operand_aliasing", text)]
    assert [s.scope for s in launches] == ["layers/attn.core/attn.pool_write"]
    assert not launches[0].carry
    # what no rule gives a scope is the entry's prefetches of two arguments
    # (the packed input, one weight), as before the launch: nothing of the write
    unscoped = [n for n, s in mapped.items() if s.scope == scopes.UNSCOPED]
    assert all(n.startswith(("copy-start", "copy-done")) for n in unscoped), unscoped
    assert len(unscoped) <= 4, unscoped


_DYNAMIC = -2 ** 63         # MLIR's ShapedType::kDynamic


def mosaic_iteration_bounds(text: str):
    """The iteration bounds of every Mosaic kernel in a program's text,
    StableHLO or optimized HLO: the custom call carries its module as MLIR
    bytecode in base64 under ``body``."""
    from jaxlib.mlir import ir

    out = []
    for body in re.findall(r'body(?:\\22|"): ?(?:\\22|")([A-Za-z0-9+/=]+)', text):
        context = ir.Context()
        context.allow_unregistered_dialects = True
        with context:
            module = ir.Module.parse(base64.b64decode(body))
            asm = module.operation.get_asm(print_generic_op_form=True)
        out += [[int(b) for b in bounds.split(",")]
                for bounds in re.findall(r"iteration_bounds = array<i64: ([^>]*)>", asm)]
    return out


def test_the_reader_of_iteration_bounds_reads_a_static_and_a_dynamic_launch():
    """Two kernels lowered for the TPU platform from here (no topology, no
    compile): a static grid reads as its numbers, a traced length as dynamic."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def copy_kernel(x_ref, o_ref):
        o_ref[...] = x_ref[...]

    def launch(x, n):
        spec = pl.BlockSpec((8, 128), lambda i, j: (i, 0))
        return pl.pallas_call(copy_kernel, grid=(4, n), in_specs=[spec], out_specs=spec,
                              out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)

    x = jax.ShapeDtypeStruct((32, 128), jnp.float32)
    n = jax.ShapeDtypeStruct((), jnp.int32)
    static = jax.export.export(jax.jit(lambda x: launch(x, 3)), platforms=["tpu"])(x)
    dynamic = jax.export.export(jax.jit(launch), platforms=["tpu"])(x, n)
    assert mosaic_iteration_bounds(static.mlir_module()) == [[4, 3]]
    assert mosaic_iteration_bounds(dynamic.mlir_module()) == [[4, _DYNAMIC]]


def test_the_compiled_steps_ragged_launch_ends_at_the_item_count(compiled_step):
    """The step's ``_ragged_kernel`` launches (the only kernel the cell names:
    one over the narrow blocks' list, one over the wide blocks') each run a
    grid of the model's head BLOCKS by a DYNAMIC second dimension: the day a
    launch is again as long as ``wl_max`` (48 x 8 = 384 in this cell) that
    reads 384.  A work item moves all sixteen heads of its page in this cell,
    so the grids are one head block wide."""
    from paddle_tpu.ops.pallas_kernels import ragged_paged_attention as ra

    ctx, compiled = compiled_step
    assert ctx["cell"]["mosaic_kernels"] == ["_ragged_kernel"]
    model, eng = ctx["config"]["model"], ctx["cell"]["engine"]
    heads = model["num_heads"]
    hb = ra.ragged_head_block(heads, eng["page_size"], model["hidden_size"] // heads,
                              eng["cache_dtype"])
    # the step's other launch is the pool write's (PR 34), as long as the
    # step's write list: one dynamic dimension
    bounds = sorted(mosaic_iteration_bounds(compiled.as_text()), key=len)
    assert bounds == [[_DYNAMIC]] + 2 * [[heads // hb, _DYNAMIC]]
    assert bounds[1] == [1, _DYNAMIC]


@pytest.mark.parametrize("local_heads", [8, 20])
def test_the_ragged_kernel_compiles_at_the_sharded_local_heads(local_heads, topo):
    """What ``shard_map`` over ``mp`` 2 hands the kernel and no cell runs
    yet: 8 local heads (``gpt_1p3b``) and 20 (``gpt_13b_cut``'s 40), at the
    chat cell's pool and step geometry.  Each compiles for the described
    v5e and moves all its local heads an item: grid ``(1, n_items)``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.ops.pallas_kernels import ragged_paged_attention as ra

    ctx = manifest.resolve_cell(CELL)
    model, eng = ctx["config"]["model"], ctx["cell"]["engine"]
    page, dim = eng["page_size"], model["hidden_size"] // model["num_heads"]
    qb = ra.ragged_token_block(page, dim, eng["cache_dtype"], local_heads=local_heads)
    nb = eng["num_slots"] + eng["prefill_token_budget"] // qb
    wl = nb * (eng["max_context"] // page)
    one_chip = SingleDeviceSharding(topo.devices[0])

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = struct((PAGES, local_heads, page, dim), jnp.bfloat16)
    i32 = jnp.int32
    compiled = jax.jit(
        lambda q, k, v, wb, wp, ws, n, bb, br: ra._ragged_pallas(
            q, k, v, wb, wp, ws, n, bb, br, dim ** -0.5)
    ).lower(struct((nb, local_heads, qb, dim), jnp.bfloat16), pool, pool,
            struct((wl,), i32), struct((wl,), i32), struct((wl,), i32),
            struct((1,), i32), struct((nb,), i32), struct((nb,), i32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert ra.ragged_head_block(local_heads, page, dim, jnp.bfloat16) == local_heads
    assert mosaic_iteration_bounds(text) == [[1, _DYNAMIC]]


@pytest.mark.parametrize("local_heads,pools", [(8, 2), (20, 2), (8, 1)])
def test_the_pool_write_compiles_at_the_sharded_local_heads(local_heads, pools, topo):
    """What ``shard_map`` over ``mp`` 2 hands the write (a block is ``(1,
    H/mp, g, D)``: 8 local heads are half a bfloat16 tile of a fresh row)
    and the hybrid cell's one K|V pool of 8 heads, at the chat cell's step
    geometry: each compiles for the described v5e as one launch with a
    dynamic length whose pool operands are its results."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.ops.pallas_kernels import pool_write as pw

    ctx = manifest.resolve_cell(CELL)
    model, eng = ctx["config"]["model"], ctx["cell"]["engine"]
    page, dim = eng["page_size"], model["hidden_size"] // model["num_heads"]
    g = pw.pool_write_group(jnp.bfloat16)
    t_max = eng["num_slots"] + eng["prefill_token_budget"]
    wr = t_max // g + 2 * eng["num_slots"]
    one_chip = SingleDeviceSharding(topo.devices[0])

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = struct((PAGES, local_heads, page, dim), jnp.bfloat16)
    rows = struct((t_max, local_heads, dim), jnp.bfloat16)
    i32 = jnp.int32
    write_list = (struct((wr,), i32), struct((wr,), i32), struct((wr, g), i32),
                  struct((wr,), i32), struct((wr,), i32), struct((1,), i32))
    compiled = jax.jit(pw.pool_write, donate_argnums=(0,)).lower(
        (pool,) * pools, (rows,) * pools, write_list).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert mosaic_iteration_bounds(text) == [[_DYNAMIC]]
    (call,) = pool_write_calls(text)
    assert call["aliased"] == [f"bf16[{PAGES},{local_heads},{page},{dim}]"] * pools
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


@pytest.mark.parametrize("rows,layers,slots,t", [(576, 3, 128, 384), (120, 9, 64, 320)])
def test_the_kept_rows_launch_compiles_at_the_cells_geometry(rows, layers, slots, t, topo):
    """Solar-Open2's three delta-rule layers over 128 slots and 384 rows a
    step; Phi-4-mini-flash's nine state-space layers over 64 slots and 320:
    one call, a grid as long as the run list, the pool its own result, and
    ``a`` and the old rows handed over as bitcasts, nothing copied."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from paddle_tpu.ops.pallas_kernels import kept_rows as kr

    one = SingleDeviceSharding(topo.devices[0])

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    i32 = jnp.int32
    pool = struct((layers * (2 * slots + 1), rows, 128), jnp.bfloat16)
    compiled = jax.jit(lambda p, a, *runs: kr.kept_rows(p, a, runs),
                       donate_argnums=(0,)).lower(
        pool, struct((t, rows // 3 * 128), jnp.bfloat16),
        *[struct((slots,), i32)] * 5, struct((1,), i32)).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert mosaic_iteration_bounds(text) == [[_DYNAMIC]]
    (call,) = pool_write_calls(text)
    assert call["aliased"] == [f"bf16[{pool.shape[0]},{rows},128]"]
    assert all(ty.startswith("s32[") for ty in re.findall(r"= (\S+) copy\(", text))
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def test_the_lowered_loop_carries_the_pools():
    """On the CPU: both flat pools are operands of the layer loop's ``while``
    and nothing stacks a layer's pool back with ``dynamic_update_slice``."""
    pt.seed(0)
    model = GPTStackedForPretraining(gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0))
    model.eval()
    engine = ServingEngine(model, num_slots=2, page_size=16, max_context=64,
                           cache_dtype="float32")
    try:
        engine.submit(np.arange(5), 2)
        engine.step()
        (text,) = engine.lowered_texts()
        layers, pages, heads, page, dim = (int(n) for n in engine.cache.k.shape)
    finally:
        engine.close()
    flat = f"tensor<{layers * pages}x{heads}x{page}x{dim}xf32>"
    (loop,) = [ln for ln in text.splitlines() if "stablehlo.while" in ln]
    assert loop.count(flat) == 2, loop[-600:]
    pool_shapes = (flat, f"tensor<{pages}x{heads}x{page}x{dim}xf32>",
                   f"tensor<{layers}x{pages}x{heads}x{page}x{dim}xf32>")
    stacked_back = [ln.strip()[:200] for ln in text.splitlines()
                    if "dynamic_update_slice" in ln and any(s in ln for s in pool_shapes)]
    assert stacked_back == []
