"""The plain reference against the system at ``gpt_tiny`` size on the CPU: the
system's logits through prefill-then-decode in the paged cache, and its loss
and ``qkv_w`` gradient.  The same two checks run at the published widths in
every benchmark run's set-up; here they also show that each tolerance catches
what it is there to catch."""
import sys

import numpy as np
import pytest

import benchmark_tiny_tree as tiny

sys.path.insert(0, tiny.REPO)

from benchmark.configs import gpt_builder  # noqa: E402
from benchmark.harness import manifest as bm  # noqa: E402
from benchmark.reference import gpt_ref  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.build(str(tmp_path_factory.mktemp("bench")))


def test_reference_forward_matches_a_direct_numpy_computation():
    """The reference itself, one block at a toy size, against numpy float64."""
    rng = np.random.default_rng(0)
    h, heads, s = 8, 2, 5
    p = {k: rng.normal(size=shape) * 0.3 for k, shape in {
        "ln1_g": (h,), "ln1_b": (h,), "qkv_w": (h, 3 * h), "qkv_b": (3 * h,),
        "proj_w": (h, h), "proj_b": (h,), "ln2_g": (h,), "ln2_b": (h,),
        "fc1_w": (h, 4 * h), "fc1_b": (4 * h,), "fc2_w": (4 * h, h), "fc2_b": (h,)}.items()}
    x = rng.normal(size=(1, s, h))

    def ln(v, g, b):
        return (v - v.mean(-1, keepdims=True)) / np.sqrt(v.var(-1, keepdims=True) + 1e-5) * g + b

    y = ln(x, p["ln1_g"], p["ln1_b"]) @ p["qkv_w"] + p["qkv_b"]
    qkv = y.reshape(1, s, 3, heads, h // heads)
    out = np.zeros((1, s, heads, h // heads))
    for n in range(heads):
        q, k, v = (qkv[0, :, i, n] for i in range(3))
        sc = q @ k.T / np.sqrt(h // heads)
        sc[np.triu_indices(s, 1)] = -np.inf
        w = np.exp(sc - sc.max(-1, keepdims=True))
        out[0, :, n] = (w / w.sum(-1, keepdims=True)) @ v
    hid = x + out.reshape(1, s, h) @ p["proj_w"] + p["proj_b"]
    z = ln(hid, p["ln2_g"], p["ln2_b"]) @ p["fc1_w"] + p["fc1_b"]
    gelu = 0.5 * z * (1 + np.tanh(np.sqrt(2 / np.pi) * (z + 0.044715 * z ** 3)))
    want = hid + gelu @ p["fc2_w"] + p["fc2_b"]
    import jax
    import jax.numpy as jnp

    with jax.default_matmul_precision("highest"):
        got = gpt_ref.block({k: jnp.asarray(v, jnp.float32) for k, v in p.items()},
                            jnp.asarray(x, jnp.float32), heads=heads, eps=1e-5)
    # float32 against float64 at values of order 1: a few 1e-6
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)


def test_serving_through_the_paged_cache_agrees_with_the_reference(root):
    """Prefill in two chunks (20 prompt tokens, budget 16), then decode: every
    emitted token's reference logit is within the tolerance of its position's
    maximum.  At this size logits have a standard deviation near 0.16 and bf16
    leaves them about 0.002 off, so 0.05 (the tiny cell's tolerance) holds with
    room and a wrong token (below) misses it by a wide margin."""
    from paddle_tpu.serving import ServingEngine

    from benchmark.runners import serve

    ctx = bm.resolve_cell("tiny.chat", root=root)
    model = gpt_builder.build_model(ctx["config"], seed=3000000019)
    engine = ServingEngine(model, **ctx["cell"]["engine"])
    try:
        got = serve.reference_check(gpt_builder, engine, model, ctx, seed=3000000019)
        assert got["ok"] and got["tokens"] == 6
        assert got["logit_gap_max"] <= 0.02
        # the comparison is not vacuous: the logit of an unrelated token is far
        # below its position's maximum
        import jax.numpy as jnp

        ids = np.arange(25, dtype=np.int64)[None]
        logits = np.asarray(gpt_ref.logits(gpt_builder.reference_weights(model),
                                           jnp.asarray(ids),
                                           **gpt_builder.reference_kwargs(model)))[0]
        assert np.median(logits.max(-1) - logits[:, 7]) > ctx["cell"]["reference_check"][
            "logit_gap_tol"]
    finally:
        engine.close()


@pytest.mark.parametrize("cell", ["tiny.train", "tiny.train4"])
def test_training_loss_and_gradient_agree_with_the_reference(root, cell):
    """Loss and the first block's ``qkv_w`` gradient, two layers, through the
    system's bf16 autograd path (on one device and on a dp 2 x mp 2 mesh),
    against float32.  bf16 leaves the gradient about 0.6% off in Frobenius
    norm at this size; the tiny cells allow 3%."""
    import jax

    from paddle_tpu.distributed import mesh as dmesh

    from benchmark.runners import train

    ctx = bm.resolve_cell(cell, root=root)
    try:
        _, sharding = train._mesh_and_sharding(ctx["cell"], jax.devices()[:ctx["entry"]["chips"]])
        got = train.reference_check(gpt_builder, ctx, 3000000019, sharding)
    finally:
        dmesh.set_mesh(None)
    assert got["ok"], got
    assert got["loss_abs_err"] < 2e-3 and got["grad_rel_err"] < 0.02, got


def test_a_wrong_gradient_fails_the_tolerance():
    """Rounding only the block weights to an 8-bit float's 3 stored mantissa
    bits (activations left in float32: the mildest loss of precision below
    bf16) already moves the gradient by more than the tolerance the tiny cells
    allow; the real cells' tolerances are tighter still."""
    import jax.numpy as jnp

    rng = np.random.default_rng(1)
    cfg = {"model": {"vocab_size": 256, "hidden_size": 32, "num_layers": 2, "num_heads": 2,
                     "max_position_embeddings": 32}}
    model = gpt_builder.build_model(cfg, seed=5)
    w = gpt_builder.reference_weights(model)
    ids = jnp.asarray(rng.integers(0, 256, (2, 16)))
    labels = jnp.roll(ids, -1, axis=1)
    kw = gpt_builder.reference_kwargs(model)
    _, good = gpt_ref.loss_and_grad(w, ids, labels, **kw)

    def coarse(a):
        a = np.asarray(a, np.float32)
        m, e = np.frexp(a)
        return jnp.asarray(np.ldexp(np.round(m * 16) / 16, e))

    rough = dict(w, layers={k: coarse(v) for k, v in w["layers"].items()})
    _, bad = gpt_ref.loss_and_grad(rough, ids, labels, **kw)
    a, b = (np.asarray(g["layers"]["qkv_w"][0]) for g in (good, bad))
    assert np.linalg.norm(a - b) / np.linalg.norm(a) > tiny.TRAIN_CHECK["grad_rel_tol"]
