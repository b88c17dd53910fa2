"""Program scopes (docs/observability.md "Program scopes"): the fixed
``jax.named_scope`` vocabulary inside the compiled train and serving programs,
and the map from an optimized-HLO instruction name back to it
(``StaticFunction.op_scopes()`` / ``telemetry.scopes``).  All on the CPU: a
scope is metadata, so what is pinned here is names and flags, never a time."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import sysconfig
from paddle_tpu.distributed import mesh as dmesh
from paddle_tpu.jit import api as jit_api
from paddle_tpu.models import GPTStackedForPretraining, gpt_tiny
from paddle_tpu.models import gpt as gpt_mod
from paddle_tpu.ops.pallas_kernels import (
    decode_attention, flash_attention, fused_adamw, kept_rows, paged_attention,
    rms_norm,
)
from paddle_tpu.serving import ServingEngine
from paddle_tpu.telemetry import scopes

# the interface: a name leaves this list only with the code it named
VOCABULARY = (
    "train.forward", "train.backward", "train.optimizer",
    "serve.unpack", "serve.sample",
    "embed", "layers", "attn.qkv", "attn.core", "attn.pool_write",
    "attn.out", "mlp", "lm_head", "shard.flash",
    "kernel.flash_fwd", "kernel.flash_bwd_dkv", "kernel.flash_bwd_dq",
    "kernel.ragged", "kernel.paged_decode", "kernel.decode",
    "kernel.fused_adamw", "kernel.rms_norm",
    "attn.rope", "conv.proj", "conv.mix", "conv.state_write",
    "moe.route", "moe.experts", "kernel.gmm",
    "ssm.proj", "ssm.conv", "ssm.scan", "ssm.state_write", "kernel.ssm_scan",
    "gmu", "attn.window", "attn.shared", "attn.diff",
    "kda.proj", "kda.conv", "kda.gate", "kda.scan", "kda.norm",
    "kda.state_write", "kernel.kda", "attn.gqa", "moe.shared",
    "kernel.kept_rows", "attn.full",
)

# where each scope must be found
IN_TRAIN = ("train.forward", "train.backward", "train.optimizer", "embed",
            "layers", "attn.qkv", "attn.core", "attn.out", "mlp", "lm_head")
IN_SERVE = ("serve.unpack", "serve.sample", "embed", "layers", "attn.qkv",
            "attn.core", "attn.pool_write", "kernel.ragged", "attn.out", "mlp",
            "lm_head")
# the serving step of the hybrid conv / attention decoder with routed experts
IN_HYBRID = ("serve.unpack", "serve.sample", "embed", "layers", "attn.qkv",
             "attn.rope", "attn.core", "attn.pool_write", "kernel.ragged",
             "attn.out", "conv.proj", "conv.mix", "conv.state_write", "mlp",
             "moe.route", "moe.experts", "kernel.gmm", "lm_head")


# the serving step of the decoder-hybrid-decoder (state-space, window, full
# and cross layers, gated memory units)
IN_SLOT_STATE = ("serve.unpack", "serve.sample", "embed", "layers", "attn.qkv",
                 "attn.window", "attn.shared", "attn.diff", "attn.pool_write",
                 "kernel.ragged", "attn.out", "ssm.proj", "ssm.conv", "ssm.scan",
                 "ssm.state_write", "kernel.ssm_scan", "gmu", "mlp", "lm_head")

# the serving step of the delta-rule / gated GQA hybrid with an expert share
IN_SOLAR = ("serve.unpack", "serve.sample", "embed", "layers", "attn.qkv",
            "attn.gqa", "attn.pool_write", "kernel.ragged", "attn.out",
            "kda.proj", "kda.conv", "kda.gate", "kda.scan", "kda.norm",
            "kda.state_write", "kernel.kda", "moe.route", "moe.experts",
            "moe.shared", "kernel.gmm", "lm_head")

# the serving step of the window / full grouped-query decoder with
# softmax-routed experts
IN_MELLUM = ("serve.unpack", "serve.sample", "embed", "layers", "attn.qkv",
             "attn.rope", "attn.window", "attn.full", "attn.pool_write",
             "kernel.ragged", "attn.out", "moe.route", "moe.experts",
             "kernel.gmm", "lm_head")


def _leaf(s):
    return s.scope.rsplit("/", 1)[-1]


def test_the_vocabulary_is_the_interface():
    assert scopes.SCOPES == VOCABULARY


@pytest.fixture(scope="module")
def tiny():
    pt.seed(0)
    cfg = gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0,
                   recompute_interval=1)
    return cfg, GPTStackedForPretraining(cfg)


@pytest.fixture(scope="module")
def train_step(tiny):
    cfg, model = tiny
    model.train()
    opt = pt.optimizer.AdamW(learning_rate=1e-3, parameters=model.parameters())
    step = pt.optimizer.FusedTrainStep(
        lambda ids, labels: model(ids, labels=labels), opt)
    ids = pt.to_tensor(np.arange(32).reshape(2, 16) % cfg.vocab_size,
                       dtype="int64")
    step(ids, ids)
    return step


@pytest.fixture(scope="module")
def engine(tiny, train_step):
    cfg, model = tiny
    model.eval()
    eng = ServingEngine(model, num_slots=2, page_size=8, max_context=32,
                        prefill_token_budget=8)
    eng.submit(np.arange(11) % cfg.vocab_size, 2)
    eng.run_until_idle(max_steps=50)
    yield eng
    eng.close()
    model.train()


@pytest.fixture(scope="module")
def hybrid_engine():
    from paddle_tpu.models import Lfm2StackedForCausalLM, lfm2_tiny

    pt.seed(0)
    model = Lfm2StackedForCausalLM(lfm2_tiny(num_hidden_layers=10))   # two periods: a loop
    model.eval()
    eng = ServingEngine(model, num_slots=2, page_size=8, max_context=32,
                        prefill_token_budget=8, cache_dtype="float32")
    eng.submit(np.arange(11), 2)
    eng.run_until_idle(max_steps=50)
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def slot_state_engine():
    from paddle_tpu.models import Phi4FlashForCausalLM, phi4flash_tiny

    pt.seed(0)
    model = Phi4FlashForCausalLM(phi4flash_tiny(num_hidden_layers=12))  # both loops loop
    model.eval()
    eng = ServingEngine(model, num_slots=2, page_size=8, max_context=32,
                        prefill_token_budget=8, cache_dtype="float32")
    eng.submit(np.arange(11), 2)
    eng.run_until_idle(max_steps=50)
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def solar_engine():
    from paddle_tpu.models import SolarOpen2ForCausalLM, solar_open2_tiny

    pt.seed(0)
    model = SolarOpen2ForCausalLM(solar_open2_tiny(experts_held=(4, 4)))
    model.eval()
    eng = ServingEngine(model, num_slots=2, page_size=8, max_context=32,
                        prefill_token_budget=8, cache_dtype="float32")
    eng.submit(np.arange(11), 2)
    eng.run_until_idle(max_steps=50)
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def mellum_engine():
    from paddle_tpu.models import Mellum2ForCausalLM, mellum2_tiny

    pt.seed(0)
    model = Mellum2ForCausalLM(mellum2_tiny(num_hidden_layers=8))    # two periods: a loop
    model.eval()
    eng = ServingEngine(model, num_slots=2, page_size=8, max_context=32,
                        prefill_token_budget=8, cache_dtype="float32")
    eng.submit(np.arange(11), 2)
    eng.run_until_idle(max_steps=50)
    yield eng
    eng.close()


@pytest.fixture(scope="module")
def kernel_texts():
    """What the CPU cannot run it can still trace: the Mosaic calls' jaxprs
    with their name stacks, and the wrappers that fall back to XLA here."""
    texts = {}
    q = jnp.zeros((2, 2, 256, 128), jnp.bfloat16)
    scale = 1.0 / np.sqrt(128.0)

    def loss(q_, k_, v_):
        return flash_attention._flash_bnsd(q_, k_, v_, True, scale).astype(
            jnp.float32).sum()

    texts["flash"] = jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(
        q, q, q).pretty_print(name_stack=True)
    on_tpu = flash_attention._on_tpu
    dmesh.set_mesh(dmesh.build_mesh({"dp": 2, "mp": 2}, jax.devices()[:4]))
    flash_attention._on_tpu = lambda: True
    try:
        texts["shard"] = jax.make_jaxpr(
            lambda a: gpt_mod._flash_over_mesh(a, a, a, scale))(
                q).pretty_print(name_stack=True)
    finally:
        flash_attention._on_tpu = on_tpu
        dmesh.set_mesh(None)

    def lowered(fn, *args):
        return jax.jit(fn).lower(*args).as_text(debug_info=True)

    pool = jnp.zeros((4, 2, 8, 128), jnp.float32)
    texts["paged"] = lowered(
        lambda qq, kp, tb, ln: paged_attention.paged_attention(qq, kp, kp, tb, ln),
        jnp.zeros((2, 2, 128)), pool, jnp.zeros((2, 2), jnp.int32),
        jnp.ones((2,), jnp.int32))
    cache = jnp.zeros((2, 2, 16, 128), jnp.float32)
    texts["decode"] = lowered(
        lambda qq, kc: decode_attention.decode_attention(qq, kc, kc, 3),
        jnp.zeros((2, 2, 128)), cache)
    p = jnp.ones((8, 128))
    texts["adamw"] = jax.make_jaxpr(
        lambda *a: fused_adamw.fused_adamw_update(*a, 1e-3, 0.9, 0.99,
                                                  interpret=True))(
            p, p, p, p).pretty_print(name_stack=True)
    texts["rms"] = lowered(
        lambda x, w: rms_norm.fused_add_rms_norm(x, x, w)[0],
        jnp.ones((8, 128)), jnp.ones((128,)))
    runs = (jnp.zeros((1,), jnp.int32),) * 5 + (jnp.ones((1,), jnp.int32),)
    texts["kept"] = lowered(
        lambda rows, a: kept_rows.kept_rows(rows, a, runs, interpret=True)[0],
        jnp.zeros((3, 24, 128), jnp.float32), jnp.ones((8, 1024), jnp.float32))
    return texts


IN_KERNELS = {"kernel.flash_fwd": "flash", "kernel.flash_bwd_dkv": "flash",
              "kernel.flash_bwd_dq": "flash", "shard.flash": "shard",
              "kernel.paged_decode": "paged", "kernel.decode": "decode",
              "kernel.fused_adamw": "adamw", "kernel.rms_norm": "rms",
              "kernel.kept_rows": "kept"}


@pytest.mark.parametrize("scope", VOCABULARY)
def test_scope_is_in_the_programs(scope, train_step, engine, hybrid_engine,
                                  slot_state_engine, solar_engine, mellum_engine,
                                  kernel_texts):
    """Every name of the vocabulary is on the operations of the program it
    belongs to: in ``lowered_texts()`` of the train step and of the serving
    step, and for the kernels the CPU never calls, in their traces."""
    found_somewhere = False
    if scope in IN_TRAIN:
        (text,) = train_step.lowered_texts()
        assert re.search(r'["/(]' + re.escape(scope) + r'[/)"]', text), scope
        found_somewhere = True
    if scope in IN_SERVE:
        text = "".join(engine.lowered_texts())
        assert re.search(r'["/(]' + re.escape(scope) + r'[/)"]', text), scope
        found_somewhere = True
    if scope in IN_HYBRID:
        text = "".join(hybrid_engine.lowered_texts())
        assert re.search(r'["/(]' + re.escape(scope) + r'[/)"]', text), scope
        found_somewhere = True
    if scope in IN_SLOT_STATE:
        text = "".join(slot_state_engine.lowered_texts())
        assert re.search(r'["/(]' + re.escape(scope) + r'[/)"]', text), scope
        found_somewhere = True
    if scope in IN_SOLAR:
        text = "".join(solar_engine.lowered_texts())
        assert re.search(r'["/(]' + re.escape(scope) + r'[/)"]', text), scope
        found_somewhere = True
    if scope in IN_MELLUM:
        text = "".join(mellum_engine.lowered_texts())
        assert re.search(r'["/(]' + re.escape(scope) + r'[/)"]', text), scope
        found_somewhere = True
    if scope in IN_KERNELS:
        assert scope in kernel_texts[IN_KERNELS[scope]], scope
        found_somewhere = True
    assert found_somewhere, f"{scope} is checked nowhere"


def test_flash_scopes_hold_their_own_mosaic_calls(kernel_texts):
    """Three kernels, three scopes: each name stack holds one pallas_call."""
    text = kernel_texts["flash"]
    assert text.count("pallas_call") == 3
    assert "kernel.flash_fwd" in kernel_texts["shard"]     # inside shard.flash


def test_programs_are_the_same_programs(train_step, engine):
    """Scopes are metadata: one train program, one serving program (greedy)."""
    assert train_step.program_count == 1
    assert engine.compiled_programs == 1


def test_op_scopes_of_the_serving_step(engine):
    (mapped,) = engine.op_scopes()
    leaves = {_leaf(s) for s in mapped.values()}
    assert {"attn.pool_write", "kernel.ragged", "layers", "attn.qkv", "mlp",
            "lm_head", "serve.sample"} <= leaves
    scatters = [s for name, s in mapped.items() if "scatter" in name]
    assert scatters and all(_leaf(s) == "attn.pool_write" for s in scatters)
    pool = [s for s in mapped.values() if _leaf(s) == "attn.pool_write"]
    assert all(s.scope == "layers/attn.core/attn.pool_write" and not s.carry
               and not s.backward for s in pool)
    # the layer loop itself and what moves its state are its carry
    loop = [s for name, s in mapped.items() if name.startswith("while")]
    assert loop and all(s.scope == "layers" and s.carry for s in loop)
    unscoped = [n for n, s in mapped.items() if s.scope == scopes.UNSCOPED]
    assert len(unscoped) <= len(mapped) // 10, unscoped


def test_the_pool_writes_launch_is_attn_pool_writes(tiny, engine, monkeypatch):
    """What a TPU makes of the write (PR 34): the models' own write takes the
    tile-group launch (here interpreted, the choice patched in: the CPU never
    observes a TPU).  Every instruction the launch leaves in the compiled
    step resolves to ``layers/attn.core/attn.pool_write``, as the scatters it
    replaces did (``cache.pool_update_ms_per_step.*`` reads that scope), and
    no more of the step is unscoped than in the step that scatters
    (``device.serve_unscoped_share.*``).  (That the step compiled for the
    chip holds no pool-sized scatter is tests/test_pool_in_place.py's.)"""
    from paddle_tpu.ops.pallas_kernels import pool_write as pw

    cfg, model = tiny
    launch = pw.pool_write
    monkeypatch.setattr(pw, "pool_write_runs", pw.pool_write_supported)
    monkeypatch.setattr(pw, "pool_write", lambda pools, rows, write_list:
                        launch(pools, rows, write_list, interpret=True))
    model.eval()
    # the fixture's engine with pages a bfloat16 tile group divides
    eng = ServingEngine(model, num_slots=2, page_size=16, max_context=32,
                        prefill_token_budget=8)
    try:
        eng.submit(np.arange(11) % cfg.vocab_size, 2)
        eng.run_until_idle(max_steps=50)
        (mapped,) = eng.op_scopes()
    finally:
        eng.close()
        model.train()
    (scattering,) = engine.op_scopes()
    write = {n: s for n, s in mapped.items() if _leaf(s) == "attn.pool_write"}
    assert write
    assert all(s.scope == "layers/attn.core/attn.pool_write" and not s.carry
               for s in write.values())
    # the interpreted launch is a loop over the write list inside the layer
    # loop: it is the write's, not the carry's
    inner = [s for n, s in mapped.items() if n.startswith("while")
             and _leaf(s) == "attn.pool_write"]
    assert inner

    def unscoped(m):
        return [n for n, s in m.items() if s.scope == scopes.UNSCOPED]

    assert len(unscoped(mapped)) <= len(unscoped(scattering)), unscoped(mapped)


def test_op_scopes_of_the_hybrid_serving_step(hybrid_engine):
    """The tail scatter is ``conv.state_write``'s, the K/V scatters
    ``attn.pool_write``'s; the period loop is ``layers`` and its carry; the
    routed layer's sort is ``moe.route``'s and its products ``moe.experts``'s."""
    assert hybrid_engine.compiled_programs == 1
    (mapped,) = hybrid_engine.op_scopes()
    leaves = {_leaf(s) for s in mapped.values()}
    assert {"conv.proj", "conv.mix", "conv.state_write", "attn.rope",
            "attn.pool_write", "moe.route", "mlp", "lm_head"} <= leaves
    assert any("moe.experts" in s.scope for s in mapped.values())
    scatters = {_leaf(s) for name, s in mapped.items() if "scatter" in name}
    assert {"conv.state_write", "attn.pool_write"} <= scatters
    loop = [s for name, s in mapped.items() if name.startswith("while")]
    assert loop and all(s.scope.startswith("layers") for s in loop)
    unscoped = [n for n, s in mapped.items() if s.scope == scopes.UNSCOPED]
    assert len(unscoped) <= len(mapped) // 10, unscoped


def test_op_scopes_of_the_slot_state_serving_step(slot_state_engine):
    """A window layer's launch is ``attn.window/kernel.ragged`` and a
    shared-pool one ``attn.shared/kernel.ragged``, told apart by the path;
    the recurrence is ``ssm.scan/kernel.ssm_scan``; the state rows' scatter
    is ``ssm.state_write``'s and the K/V scatters ``attn.pool_write``'s,
    never inside ``attn.window`` or ``attn.shared``; the counters the cache
    keeps are in ``metrics()``."""
    assert slot_state_engine.compiled_programs == 1
    (mapped,) = slot_state_engine.op_scopes()
    paths = {s.scope for s in mapped.values()}
    assert any(p.endswith("attn.window/kernel.ragged") for p in paths)
    assert any(p.endswith("attn.shared/kernel.ragged") for p in paths)
    assert any(p.endswith("ssm.scan/kernel.ssm_scan") for p in paths)
    assert not any("attn.window/attn.pool_write" in p or "attn.shared/attn.pool_write" in p
                   for p in paths)
    leaves = {_leaf(s) for s in mapped.values()}
    assert {"ssm.proj", "ssm.conv", "ssm.state_write", "gmu", "attn.diff",
            "attn.pool_write", "mlp", "lm_head"} <= leaves
    scatters = {_leaf(s) for name, s in mapped.items() if "scatter" in name}
    assert {"ssm.state_write", "attn.pool_write"} <= scatters
    loops = [s for name, s in mapped.items() if name.startswith("while")]
    assert len(loops) >= 2 and all(s.scope.startswith("layers") for s in loops)
    unscoped = [n for n, s in mapped.items() if s.scope == scopes.UNSCOPED]
    assert len(unscoped) <= len(mapped) // 10, unscoped
    m = slot_state_engine.metrics()
    assert m["ssm_rows"] == m["cross_rows"] == 12 and m["ssm_runs"] == 3
    assert 0 < m["window_work_items"] <= m["work_items"]


def test_op_scopes_of_the_delta_rule_serving_step(solar_engine):
    """The rule is ``kda.scan/kernel.kda``, the GQA layer's launch
    ``attn.gqa/kernel.ragged``; the kept inputs' scatter is
    ``kda.state_write``'s, the K/V write ``attn.pool_write``'s; the shared
    expert is ``moe.shared``; the counters the cache keeps, on the host and
    on the device, are in ``metrics()``."""
    assert solar_engine.compiled_programs == 1
    (mapped,) = solar_engine.op_scopes()
    paths = {s.scope for s in mapped.values()}
    assert any(p.endswith("kda.scan/kernel.kda") for p in paths)
    assert any(p.endswith("attn.gqa/kernel.ragged") for p in paths)
    leaves = {_leaf(s) for s in mapped.values()}
    assert {"kda.proj", "kda.conv", "kda.gate", "kda.norm", "kda.state_write",
            "attn.pool_write", "moe.shared", "moe.experts", "lm_head"} <= leaves
    scatters = {_leaf(s) for name, s in mapped.items() if "scatter" in name}
    assert {"kda.state_write", "attn.pool_write"} <= scatters
    unscoped = [n for n, s in mapped.items() if s.scope == scopes.UNSCOPED]
    assert len(unscoped) <= len(mapped) // 10, unscoped
    m = solar_engine.metrics()
    assert m["kda_rows"] == 12 and m["kda_runs"] == 3 and m["kda_chunk_rows"] == 11
    assert m["moe_pairs"] == 4 * 4 * 12     # layers x top-4 x real rows
    assert 0 < m["moe_local_pairs"] < m["moe_pairs"]


def test_op_scopes_of_the_window_and_full_serving_step(mellum_engine):
    """A window layer's rotation, ring write, launch and output projection
    are under ``attn.window`` and the full layer's under ``attn.full``, each
    launch ``.../kernel.ragged``; the experts ``moe.experts/kernel.gmm`` (a
    ``ragged_dot`` off the chip); the counters the cache keeps, on the host
    and on the device, are in ``metrics()``."""
    assert mellum_engine.compiled_programs == 1
    (mapped,) = mellum_engine.op_scopes()
    paths = {s.scope for s in mapped.values()}
    for kind in ("attn.window", "attn.full"):
        assert any(p.endswith(f"{kind}/kernel.ragged") for p in paths), kind
        assert any(p.endswith(f"{kind}/attn.rope") for p in paths), kind
        assert any(p.endswith(f"{kind}/attn.out") for p in paths), kind
    leaves = {_leaf(s) for s in mapped.values()}
    assert {"attn.qkv", "attn.pool_write", "moe.route", "moe.experts",
            "lm_head"} <= leaves
    scatters = {_leaf(s) for name, s in mapped.items() if "scatter" in name}
    assert "attn.pool_write" in scatters
    loops = [s for name, s in mapped.items() if name.startswith("while")]
    assert loops and all(s.scope.startswith("layers") for s in loops)
    unscoped = [n for n, s in mapped.items() if s.scope == scopes.UNSCOPED]
    assert len(unscoped) <= len(mapped) // 10, unscoped
    m = mellum_engine.metrics()
    assert m["moe_assignments"] == 8 * 2 * 12     # layers x top-2 x real rows
    assert 0 < m["window_work_items"] and 0 < m["work_items"]


def test_op_scopes_of_a_train_step_with_recomputation(train_step):
    (mapped,) = train_step.op_scopes()
    kinds = {(s.scope.split("/")[0], s.backward, s.recompute)
             for s in mapped.values()}
    assert ("train.forward", False, False) in kinds
    assert ("train.backward", True, False) in kinds
    assert ("train.backward", True, True) in kinds
    assert ("train.optimizer", False, False) in kinds
    for name, s in mapped.items():
        assert not s.recompute or s.backward, name
        if s.scope.startswith("train.backward"):
            assert s.backward, name
        if s.scope.startswith(("train.forward", "train.optimizer")):
            assert not s.backward, name
    # the LM head's scope is entered inside what jax.vjp differentiates, so
    # its backward (and the chunks it recomputes) keep the name
    assert {s.recompute for s in mapped.values()
            if s.scope == "train.backward/lm_head"} == {False, True}
    recomputed = {_leaf(s) for s in mapped.values() if s.recompute}
    assert {"attn.qkv", "mlp"} <= recomputed
    assert any(s.carry and s.scope == "train.backward/layers"
               for s in mapped.values())


@pytest.mark.parametrize("op_name,want", [
    ("jit(f)/train.forward/jvp(layers)/while/body/closed_call/attn.qkv/dot_general",
     scopes.OpScope("train.forward/layers/attn.qkv")),
    ("jit(f)/train.backward/transpose(jvp(layers))/while/body/closed_call/checkpoint/"
     "rematted_computation/mlp/dot_general",
     scopes.OpScope("train.backward/layers/mlp", backward=True, recompute=True)),
    ("jit(f)/train.backward/transpose(jvp(layers))/while/body/dynamic_update_slice",
     scopes.OpScope("train.backward/layers", backward=True, carry=True)),
    ("jit(f)/embed/embed/jit(_take)/gather", scopes.OpScope("embed")),
    ("jit(f)/layers/while/body/closed_call/attn.core/squeeze;attn.qkv/transpose",
     scopes.OpScope("layers/attn.core")),
    ("jit(f)/jit(_take)/gather", None),
])
def test_scope_of_op_name(op_name, want):
    assert scopes.scope_of_op_name(op_name) == want


HAND_WRITTEN = """HloModule jit_step, is_scheduled=true

%fused_computation (param_0: f32[4,8]) -> f32[4,8] {
  %param_0 = f32[4,8]{1,0} parameter(0)
  ROOT %tanh.1 = f32[4,8]{1,0} tanh(%param_0), metadata={op_name="jit(step)/layers/while/body/mlp/tanh"}
}

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b)
}

%body (arg_tuple: (s32[], f32[4,8], f32[3,4,8])) -> (s32[], f32[4,8], f32[3,4,8]) {
  %arg_tuple = (s32[], f32[4,8]{1,0}, f32[3,4,8]{2,1,0}) parameter(0)
  %get-tuple-element.1 = f32[4,8]{1,0} get-tuple-element(%arg_tuple), index=1
  %fusion.2 = f32[4,8]{1,0} fusion(%get-tuple-element.1), kind=kLoop, calls=%fused_computation
  %copy.5 = f32[4,8]{0,1} copy(%fusion.2)
  %get-tuple-element.2 = f32[3,4,8]{2,1,0} get-tuple-element(%arg_tuple), index=2
  %copy.6 = f32[3,4,8]{2,1,0} copy(%get-tuple-element.2)
  %get-tuple-element.0 = s32[] get-tuple-element(%arg_tuple), index=0
  ROOT %tuple.3 = (s32[], f32[4,8]{1,0}, f32[3,4,8]{2,1,0}) tuple(%get-tuple-element.0, %copy.5, %copy.6)
}

%cond (arg_tuple.1: (s32[], f32[4,8], f32[3,4,8])) -> pred[] {
  %arg_tuple.1 = (s32[], f32[4,8]{1,0}, f32[3,4,8]{2,1,0}) parameter(0)
  %get-tuple-element.7 = s32[] get-tuple-element(%arg_tuple.1), index=0
  %constant.3 = s32[] constant(3)
  ROOT %compare.1 = pred[] compare(%get-tuple-element.7, %constant.3), direction=LT, metadata={op_name="jit(step)/layers/while/cond/lt"}
}

ENTRY %main.9 (x: f32[4,8], pool: f32[3,4,8]) -> (f32[4,8], f32[3,4,8], f32[4,8]) {
  %x = f32[4,8]{1,0} parameter(0)
  %pool = f32[3,4,8]{2,1,0} parameter(1)
  %constant.0 = s32[] constant(0)
  %constant.1 = f32[] constant(0)
  %broadcast.4 = f32[3,4,8]{2,1,0} broadcast(%constant.1), dimensions={}
  %copy.1 = f32[4,8]{0,1} copy(%x)
  %exp.1 = f32[4,8]{0,1} exponential(%copy.1), metadata={op_name="jit(step)/embed/exp"}
  %tuple.1 = (s32[], f32[4,8]{1,0}, f32[3,4,8]{2,1,0}) tuple(%constant.0, %exp.1, %broadcast.4)
  %while.1 = (s32[], f32[4,8]{1,0}, f32[3,4,8]{2,1,0}) while(%tuple.1), condition=%cond, body=%body
  %get-tuple-element.8 = f32[3,4,8]{2,1,0} get-tuple-element(%while.1), index=2
  %copy.2 = f32[3,4,8]{2,1,0} copy(%get-tuple-element.8)
  %get-tuple-element.9 = f32[4,8]{1,0} get-tuple-element(%while.1), index=1
  %reduce.1 = f32[] reduce(%get-tuple-element.9, %constant.1), dimensions={0,1}, to_apply=%region_0.1, metadata={op_name="jit(step)/lm_head/reduce_sum"}
  %copy.3 = f32[4,8]{1,0} copy(%pool)
  %copy.4 = f32[4,8]{1,0} copy(%copy.3)
  ROOT %tuple.2 = (f32[4,8]{1,0}, f32[3,4,8]{2,1,0}, f32[4,8]{1,0}) tuple(%get-tuple-element.9, %copy.2, %copy.4)
}
"""


@pytest.fixture(scope="module")
def hand_written():
    return scopes.scopes_of_hlo_text(HAND_WRITTEN)


@pytest.mark.parametrize("name,scope,rule,carry", [
    ("exp.1", "embed", "own", False),
    ("fusion.2", "layers/mlp", "own", False),          # a fusion takes its root's
    ("copy.5", "layers/mlp", "copied", False),         # rule 1: whose result it copies
    ("copy.1", "embed", "consumer", False),            # rule 2: its one consumer
    ("broadcast.4", "layers", "carry", True),          # rule 3: feeds the loop's operand tuple
    ("copy.2", "layers", "carry", True),               # rule 3: copies the loop's result
    ("copy.6", "layers", "carry", True),               # rule 3: inside the body, parameter to root
    ("while.1", "layers", "carry", True),              # the loop, named by its body
    ("copy.3", "unscoped", "none", False),             # nothing to go by
])
def test_fall_back_rules_on_hand_written_hlo(hand_written, name, scope, rule, carry):
    got = hand_written[name]
    assert (got.scope, got.rule, got.carry) == (scope, rule, carry)


def test_the_map_holds_what_a_trace_can_show(hand_written):
    """No parameter, constant, tuple or get-tuple-element, and nothing from
    inside a fusion or a reducer."""
    assert set(hand_written) == {
        "exp.1", "fusion.2", "copy.5", "copy.1", "broadcast.4", "copy.2",
        "copy.6", "while.1", "copy.3", "copy.4", "compare.1", "reduce.1"}


def test_the_text_is_compiled_anew_with_metadata_in_the_key():
    """jax keys its persistent compile cache without metadata and holds the
    executable that runs, so either may carry another build's scope names:
    the map's text comes from a compile that passes over both, and the
    process's own setting of the key is as it was afterwards."""
    import jax

    flag = "jax_compilation_cache_include_metadata_in_key"
    seen = {}

    class Lowered:
        def compile(self, compiler_options=None):
            seen.update(options=compiler_options, keyed=getattr(jax.config, flag))
            return type("Compiled", (), {"as_text": lambda self: "HloModule m"})()

    assert getattr(jax.config, flag) is False
    assert jit_api._compiled_text(Lowered()) == "HloModule m"
    # any option makes jax compile anew instead of handing back what runs
    assert seen == {"options": {"xla_dump_to": ""}, "keyed": True}
    assert getattr(jax.config, flag) is False


def test_stale_compile_cache_is_an_error(train_step, monkeypatch):
    """Should a cache hand back text without this build's scopes all the same,
    ``op_scopes()`` says so, with the cache's path, not an all-unscoped map."""
    real = jit_api._lowered

    class Stale:
        def __init__(self, lowered):
            self._lowered = lowered

        def as_text(self, **kw):
            return self._lowered.as_text(**kw)

        def compile(self, **kw):
            text = re.sub(r'op_name="[^"]*"', 'op_name=""',
                          self._lowered.compile(**kw).as_text())
            return type("Compiled", (), {"as_text": lambda self: text})()

    monkeypatch.setattr(jit_api, "_lowered", lambda e: Stale(real(e)))
    with pytest.raises(RuntimeError, match="compile cache") as err:
        train_step.op_scopes()
    assert repr(sysconfig.compile_cache_dir()) in str(err.value)
