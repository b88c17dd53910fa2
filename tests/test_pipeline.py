"""SPMD pipeline-parallel tests (reference:
test/collective/fleet/hybrid_parallel_pp_transformer.py — multi-process
1F1B; here the pipeline is one SPMD program over the 'pp' mesh axis)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as pt
from paddle_tpu.distributed import mesh as M
from paddle_tpu.distributed.fleet.meta_parallel import pp_spmd
from paddle_tpu.models import (
    GPTPretrainingCriterion,
    GPTStackedForPretraining,
    gpt_tiny,
)


@pytest.fixture
def pp_mesh():
    prev = M._global_mesh
    mesh = M.build_mesh({"dp": 2, "pp": 4})
    M.set_mesh(mesh)
    yield mesh
    M._global_mesh = prev


@pytest.fixture
def no_mesh():
    prev = M._global_mesh
    M._global_mesh = None
    yield
    M._global_mesh = prev


def _toy_block():
    def block(params, h):
        (w,) = params
        return jnp.tanh(h @ w)
    return block


def test_pipeline_blocks_matches_scan(pp_mesh):
    L, h, mbs, mb, s = 8, 16, 4, 2, 12
    rng = np.random.RandomState(0)
    W = jnp.asarray(rng.randn(L, h, h).astype(np.float32) * 0.1)
    x = jnp.asarray(rng.randn(mbs, mb, s, h).astype(np.float32))
    block = _toy_block()
    ref = jax.vmap(lambda xm: pp_spmd.scan_blocks(block, (W,), xm))(x)
    Wp = jax.device_put(W, pp_spmd.stacked_param_sharding(W.shape))
    out = pp_spmd.pipeline_blocks(block, (Wp,), x, layers_per_stage=L // 4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6, atol=1e-6)


@pytest.mark.slow
def test_pipeline_blocks_grad_matches(pp_mesh):
    L, h, mbs, mb, s = 4, 8, 4, 2, 6
    rng = np.random.RandomState(1)
    W = jnp.asarray(rng.randn(L, h, h).astype(np.float32) * 0.2)
    x = jnp.asarray(rng.randn(mbs, mb, s, h).astype(np.float32))
    block = _toy_block()

    def loss_pipe(W):
        return jnp.sum(pp_spmd.pipeline_blocks(block, (W,), x, layers_per_stage=1) ** 2)

    def loss_ref(W):
        return jnp.sum(jax.vmap(lambda xm: pp_spmd.scan_blocks(block, (W,), xm))(x) ** 2)

    g1 = jax.grad(loss_pipe)(jax.device_put(W, pp_spmd.stacked_param_sharding(W.shape)))
    g2 = jax.grad(loss_ref)(W)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-4, atol=1e-7)


@pytest.mark.slow
def test_gpt_stacked_pipeline_matches_single_device(no_mesh):
    cfg = gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0, num_layers=4)
    rng = np.random.RandomState(0)
    ids = pt.to_tensor(rng.randint(0, cfg.vocab_size, (4, 16)), dtype="int64")
    lbl = pt.to_tensor(rng.randint(0, cfg.vocab_size, (4, 16)), dtype="int64")
    crit = GPTPretrainingCriterion(cfg)

    pt.seed(3)
    m1 = GPTStackedForPretraining(cfg)
    ref = float(crit(m1(ids), lbl))

    mesh = M.build_mesh({"dp": 2, "pp": 4})
    M.set_mesh(mesh)
    try:
        pt.seed(3)
        m2 = GPTStackedForPretraining(cfg, n_micro=2)
        loss = crit(m2(ids), lbl)
        assert abs(float(loss) - ref) < 1e-4
        loss.backward()
        g = m2.decoder.qkv_w.grad
        assert g is not None and np.isfinite(g.numpy()).all()
    finally:
        M._global_mesh = None


def test_gpt_stacked_trains(no_mesh):
    cfg = gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0, num_layers=2)
    pt.seed(5)
    m = GPTStackedForPretraining(cfg)
    crit = GPTPretrainingCriterion(cfg)
    opt = pt.optimizer.AdamW(learning_rate=1e-3, parameters=m.parameters())
    rng = np.random.RandomState(0)
    ids = pt.to_tensor(rng.randint(0, cfg.vocab_size, (2, 16)), dtype="int64")
    lbl = pt.to_tensor(rng.randint(0, cfg.vocab_size, (2, 16)), dtype="int64")
    losses = []
    for _ in range(4):
        loss = crit(m(ids), lbl)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss))
    assert losses[-1] < losses[0]


@pytest.mark.slow
def test_dryrun_multichip_with_pp():
    import __graft_entry__ as g

    prev = M._global_mesh
    try:
        g.dryrun_multichip(8)
    finally:
        M._global_mesh = prev


def test_pipeline_interleave_matches_scan(pp_mesh):
    """Virtual-stage interleave (reference PipelineParallelWithInterleave,
    pipeline_parallel.py:625): V=2 chunks per device, Megatron round-robin
    chunk->device layout, M >= S microbatches."""
    L, h, mbs, mb, s = 16, 8, 8, 2, 6  # S=4, V=2 -> lpc=2
    rng = np.random.RandomState(2)
    W = jnp.asarray(rng.randn(L, h, h).astype(np.float32) * 0.1)
    x = jnp.asarray(rng.randn(mbs, mb, s, h).astype(np.float32))
    block = _toy_block()
    ref = jax.vmap(lambda xm: pp_spmd.scan_blocks(block, (W,), xm))(x)
    out = pp_spmd.pipeline_blocks(block, (W,), x, layers_per_stage=L // 4,
                                  n_virtual=2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-6)


@pytest.mark.slow
def test_pipeline_interleave_grad_matches(pp_mesh):
    L, h, mbs, mb, s = 8, 8, 4, 2, 6  # S=4, V=2, lpc=1
    rng = np.random.RandomState(3)
    W = jnp.asarray(rng.randn(L, h, h).astype(np.float32) * 0.2)
    x = jnp.asarray(rng.randn(mbs, mb, s, h).astype(np.float32))
    block = _toy_block()

    def loss_pipe(W):
        return jnp.sum(pp_spmd.pipeline_blocks(
            block, (W,), x, layers_per_stage=2, n_virtual=2) ** 2)

    def loss_ref(W):
        return jnp.sum(jax.vmap(lambda xm: pp_spmd.scan_blocks(block, (W,), xm))(x) ** 2)

    g1 = jax.grad(loss_pipe)(W)
    g2 = jax.grad(loss_ref)(W)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-4, atol=1e-6)


@pytest.mark.slow
def test_gpt_stacked_interleave_trains(no_mesh):
    """GPT stacked decoder with virtual_pp_degree=2 on a pp mesh trains.

    slow: the interleaved wavefront fwd+bwd is one huge XLA graph on the
    8-device CPU mesh (>10 min compile); the fast set covers interleave
    correctness via test_pipeline_interleave_{matches_scan,grad_matches}."""
    prev = M._global_mesh
    try:
        mesh = M.build_mesh({"pp": 2, "dp": 2})
        M.set_mesh(mesh)
        cfg = gpt_tiny(num_layers=4, hidden_dropout=0.0, attention_dropout=0.0,
                       virtual_pp_degree=2)
        pt.seed(0)
        model = GPTStackedForPretraining(cfg, n_micro=2)
        crit = GPTPretrainingCriterion(cfg)
        opt = pt.optimizer.AdamW(learning_rate=1e-3, parameters=model.parameters())
        rng = np.random.RandomState(0)
        ids = pt.to_tensor(rng.randint(0, cfg.vocab_size, (4, 16)), dtype="int64")
        labels = pt.to_tensor(rng.randint(0, cfg.vocab_size, (4, 16)), dtype="int64")
        losses = []
        for _ in range(4):
            loss = crit(model(ids), labels)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss))
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]
    finally:
        M._global_mesh = prev


class TestFleetPipelineParallel:
    """fleet-API 1F1B runtime (reference pipeline_parallel.py:229):
    train_batch must actually schedule per-stage fwd/bwd with bounded
    activation residency and match plain gradient accumulation."""

    def _build(self, n_stages, lr=0.0):
        from paddle_tpu.distributed.fleet.meta_parallel.pipeline_parallel import (
            LayerDesc, PipelineLayer, PipelineParallel,
        )
        from paddle_tpu.nn.modules.common import Linear

        pt.seed(7)
        descs = [LayerDesc(Linear, 8, 8) for _ in range(4)]

        def loss_fn(out, y):
            return pt.ops.mean((out - y) ** 2)

        pl = PipelineLayer(descs, num_stages=n_stages, loss_fn=loss_fn)

        class Strat:
            pipeline_configs = {"accumulate_steps": 4, "micro_batch_size": 2}

        pp = PipelineParallel(pl, strategy=Strat())
        opt = pt.optimizer.SGD(learning_rate=0.1, parameters=pl.parameters())
        return pp, pl, opt

    def test_1f1b_matches_plain_accumulation(self):
        rng = np.random.RandomState(0)
        xb = rng.randn(8, 8).astype(np.float32)
        yb = rng.randn(8, 8).astype(np.float32)

        # pipelined (2 stages)
        pp, pl, opt = self._build(2)
        loss_pp = pp.train_batch(
            (pt.to_tensor(xb), pt.to_tensor(yb)), opt)
        w_pp = [p.numpy().copy() for p in pl.parameters()]

        # plain accumulation reference (1 stage == sequential)
        pp1, pl1, opt1 = self._build(1)
        loss_1 = pp1.train_batch(
            (pt.to_tensor(xb), pt.to_tensor(yb)), opt1)
        w_1 = [p.numpy().copy() for p in pl1.parameters()]

        np.testing.assert_allclose(float(loss_pp), float(loss_1), rtol=1e-5)
        for a, b in zip(w_pp, w_1):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)

    def test_1f1b_activation_residency_bound(self):
        """At most S micro-batches in flight (1F1B), not all M (GPipe)."""
        rng = np.random.RandomState(1)
        xb = rng.randn(8, 8).astype(np.float32)
        yb = rng.randn(8, 8).astype(np.float32)
        pp, pl, opt = self._build(2)
        pp.train_batch((pt.to_tensor(xb), pt.to_tensor(yb)), opt)
        assert pp.accumulate_steps == 4  # M
        assert pp.last_peak_inflight == 2  # == S, < M

    def test_grad_scaler_path(self):
        from paddle_tpu.amp import GradScaler

        rng = np.random.RandomState(2)
        xb = rng.randn(8, 8).astype(np.float32)
        yb = rng.randn(8, 8).astype(np.float32)
        pp, pl, opt = self._build(2)
        scaler = GradScaler(init_loss_scaling=256.0)
        loss = pp.train_batch((pt.to_tensor(xb), pt.to_tensor(yb)), opt,
                              scaler=scaler)
        assert np.isfinite(float(loss))


@pytest.mark.slow
def test_fleet_api_gpt_tp2_pp2_trains():
    """BASELINE config 2 analog (reference
    test/collective/fleet/hybrid_parallel_pp_transformer.py): GPT built as
    a PipelineLayer of TP (mpu) blocks, wrapped by fleet.distributed_model
    into PipelineParallel, trained with train_batch on a dp1 x pp2 x mp2
    mesh — losses must be finite and descend."""
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.distributed.fleet.meta_parallel.pipeline_parallel import (
        LayerDesc, PipelineLayer,
    )
    from paddle_tpu.models.gpt import (
        GPTDecoderLayer, GPTEmbeddings, GPTPretrainingCriterion, gpt_tiny,
    )
    from paddle_tpu.nn.modules.norm import LayerNorm

    prev = M._global_mesh
    try:
        strategy = DistributedStrategy()
        strategy.hybrid_configs = {
            "dp_degree": 1, "pp_degree": 2, "mp_degree": 2,
            "order": ["dp", "pp", "sharding", "sep", "mp"],
        }
        fleet.init(is_collective=True, strategy=strategy)
        cfg = gpt_tiny(use_tensor_parallel=True, num_layers=4,
                       hidden_dropout=0.0, attention_dropout=0.0)
        pt.seed(0)

        class Head(pt.nn.Layer):
            def __init__(self, emb):
                super().__init__()
                self._emb = emb

            def forward(self, h):
                return pt.ops.matmul(h, self._emb.word_embeddings.weight,
                                     transpose_y=True)

        emb = GPTEmbeddings(cfg)
        crit = GPTPretrainingCriterion(cfg)
        descs = [emb] + [GPTDecoderLayer(cfg) for _ in range(cfg.num_layers)]
        descs += [LayerNorm(cfg.hidden_size), Head(emb)]

        def loss_fn(logits, labels):
            return crit(logits, labels)

        pl = PipelineLayer(descs, num_stages=2, loss_fn=loss_fn)
        strategy.pipeline_configs = {"accumulate_steps": 2, "micro_batch_size": 2}
        model = fleet.distributed_model(pl)
        opt = pt.optimizer.AdamW(learning_rate=1e-3, parameters=pl.parameters())
        opt = fleet.distributed_optimizer(opt)

        rng = np.random.RandomState(0)
        ids = pt.to_tensor(rng.randint(0, cfg.vocab_size, (4, 16)), dtype="int64")
        labels = pt.to_tensor(rng.randint(0, cfg.vocab_size, (4, 16)), dtype="int64")
        losses = [float(model.train_batch((ids, labels), opt)) for _ in range(4)]
        assert all(np.isfinite(losses)), losses
        assert losses[-1] < losses[0], losses
        assert model.last_peak_inflight <= 2
    finally:
        M._global_mesh = prev


@pytest.mark.slow
def test_multiprocess_launch_both_nodes(tmp_path):
    """Run both 'nodes' concurrently via the launcher (auto-rank
    rendezvous) and assert both workers succeed."""
    import subprocess, sys, os, time

    _REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        [_REPO_ROOT] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                        if p])
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PADDLE_TPU_NO_JAX_DIST"] = "1"
    import random

    port = random.randint(20000, 50000)  # avoid cross-run port residue
    procs = []
    for node in range(2):
        log_dir = str(tmp_path / f"logs{node}")
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nnodes", "2", "--nproc_per_node", "1",
             "--master", f"127.0.0.1:{port}", "--rank", "auto",
             "--log_dir", log_dir,
             "tests/launch_worker_fixture.py"],
            cwd=_REPO_ROOT, env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
    rcs = [p.wait(timeout=300) for p in procs]
    assert rcs == [0, 0], [p.stdout.read().decode()[-2000:] for p in procs]
    logs = ""
    for node in range(2):
        d = tmp_path / f"logs{node}"
        for f in d.glob("workerlog.*"):
            logs += f.read_text()
    assert logs.count("WORKER_OK") == 2, logs[-2000:]


def test_hybrid_optimizer_global_clip():
    """The docstring's claim: ClipGradByGlobalNorm through
    HybridParallelOptimizer computes the GLOBAL norm over all (sharded)
    params — matching a hand-computed global norm."""
    from paddle_tpu.distributed.fleet.meta_parallel.hybrid_optimizer import (
        HybridParallelOptimizer,
    )

    prev = M._global_mesh
    try:
        M.set_mesh(M.build_mesh({"mp": 4, "dp": 2}))
        pt.seed(13)
        from paddle_tpu.ops.sharding_ops import shard_param

        w1 = pt.to_tensor(np.ones((8, 4), np.float32), stop_gradient=False)
        w2 = pt.to_tensor(np.ones((4,), np.float32) * 2, stop_gradient=False)
        shard_param(w1, "mp", None)  # mp-sharded like a TP weight
        clip = pt.nn.ClipGradByGlobalNorm(clip_norm=1.0)
        inner = pt.optimizer.SGD(learning_rate=1.0, parameters=[w1, w2],
                                 grad_clip=clip)
        opt = HybridParallelOptimizer(inner)
        w1.grad = pt.to_tensor(np.full((8, 4), 3.0, np.float32))
        w2.grad = pt.to_tensor(np.full((4,), 4.0, np.float32))
        before1, before2 = w1.numpy().copy(), w2.numpy().copy()
        opt.step()
        gnorm = np.sqrt((3.0**2) * 32 + (4.0**2) * 4)  # global, both params
        np.testing.assert_allclose(
            before1 - w1.numpy(), 3.0 / gnorm, rtol=1e-5)
        np.testing.assert_allclose(
            before2 - w2.numpy(), 4.0 / gnorm, rtol=1e-5)
    finally:
        M._global_mesh = prev
