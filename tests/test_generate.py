"""Autoregressive decode engine: KV-cache correctness vs the full-context
forward, retrace-freedom (trace counters), sampling (greedy / top-k /
top-p), donated-cache memory flatness, the Predictor decode mode — plus
the PR's satellite regressions (clear_grad(set_to_zero), DataLoader
prefetch-producer shutdown)."""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models import (
    GPTStackedForPretraining,
    generation,
    gpt_tiny,
)


def _tiny_cfg():
    return gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0)


def _prompt(cfg, b=2, s=6, seed=0):
    rng = np.random.RandomState(seed)
    return pt.to_tensor(rng.randint(0, cfg.vocab_size, (b, s)), dtype="int64")


# ---------------------------------------------------------------------------
# KV-cache decode correctness vs the no-cache forward
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cache_dtype,rtol,atol", [
    ("float32", 1e-4, 5e-5), ("bfloat16", 1e-2, 0.08)])
def test_cached_decode_matches_full_forward(cache_dtype, rtol, atol):
    """Eager prefill + per-token decode through the cache reproduce the
    full-context logits (fp32 cache: numerically tight; bf16 cache: within
    the K/V rounding): the [L, ...] cache scans alongside the stacked
    parameters."""
    pt.seed(3)
    cfg = _tiny_cfg()
    m = GPTStackedForPretraining(cfg)
    m.eval()
    ids = _prompt(cfg, s=10, seed=1)
    full = m(ids).numpy()
    cache = m.new_kv_cache(2, 64, dtype=cache_dtype)
    pre = m(ids[:, :6], kv_cache=cache, cache_index=0).numpy()
    np.testing.assert_allclose(pre, full[:, :6], rtol=rtol, atol=atol)
    for t in range(6, 10):
        step = m(ids[:, t:t + 1], kv_cache=cache, cache_index=t).numpy()
        np.testing.assert_allclose(step[:, 0], full[:, t], rtol=rtol,
                                   atol=atol)


def test_chunked_prefill_matches_full_forward():
    """S>1 prefill at a NONZERO position must see the earlier chunks
    through the cache (general masked path), not just attend to itself."""
    pt.seed(21)
    cfg = _tiny_cfg()
    m = GPTStackedForPretraining(cfg)
    m.eval()
    ids = _prompt(cfg, s=12, seed=3)
    full = m(ids).numpy()
    cache = m.new_kv_cache(2, 64, dtype="float32")
    m(ids[:, :4], kv_cache=cache, cache_index=0)
    mid = m(ids[:, 4:9], kv_cache=cache, cache_index=4).numpy()
    np.testing.assert_allclose(mid, full[:, 4:9], rtol=1e-4, atol=5e-5)
    tail = m(ids[:, 9:12], kv_cache=cache, cache_index=9).numpy()
    np.testing.assert_allclose(tail, full[:, 9:12], rtol=1e-4, atol=5e-5)


def test_generate_greedy_logits_match_full_forward():
    """generate()'s per-step logits equal the no-cache forward over the
    (prompt + generated) sequence — greedy, so the token streams agree."""
    pt.seed(7)
    cfg = _tiny_cfg()
    m = GPTStackedForPretraining(cfg)
    m.eval()
    ids = _prompt(cfg)
    out, logits = m.generate(ids, max_new_tokens=8, max_seq_len=64,
                             cache_dtype="float32", return_logits=True)
    assert out.shape == [2, 6 + 8]
    assert np.array_equal(out.numpy()[:, :6], ids.numpy())
    full = m(out).numpy()
    gl = logits.numpy()
    for i in range(8):
        np.testing.assert_allclose(gl[:, i], full[:, 5 + i], rtol=1e-4,
                                   atol=5e-5)
    # greedy consistency: each emitted token is the argmax of its logits
    assert np.array_equal(out.numpy()[:, 6:], gl.argmax(-1))


def test_generate_greedy_deterministic():
    pt.seed(11)
    cfg = _tiny_cfg()
    m = GPTStackedForPretraining(cfg)
    m.eval()
    ids = _prompt(cfg)
    a = m.generate(ids, max_new_tokens=6, max_seq_len=64,
                   cache_dtype="float32").numpy()
    b = m.generate(ids, max_new_tokens=6, max_seq_len=64,
                   cache_dtype="float32").numpy()
    assert np.array_equal(a, b)


# ---------------------------------------------------------------------------
# retrace-freedom: N decode steps compile at most twice (prefill + decode)
# ---------------------------------------------------------------------------

def test_decode_trace_counter_64_tokens():
    """The step bodies execute only while tracing (scout + jit trace = 2
    runs per compiled program).  A 64-token decode — and a whole second
    generate() — must compile at most twice (prefill + decode) and never
    retrace after the first decode step."""
    pt.seed(5)
    cfg = _tiny_cfg()
    m = GPTStackedForPretraining(cfg)
    m.eval()
    ids = _prompt(cfg)
    generation.reset_trace_counts()
    m.generate(ids, max_new_tokens=64, max_seq_len=128, cache_dtype="float32")
    counts = generation.trace_counts()
    # at most one compile each => at most 2 python-body executions each
    assert counts["prefill"] <= 2 and counts["decode"] <= 2, counts
    m.generate(ids, max_new_tokens=64, max_seq_len=128, cache_dtype="float32")
    assert generation.trace_counts() == counts
    eng = m.__dict__["_decode_engines"][(2, 128, "float32", False, 0, False)]
    assert eng.compiled_programs == 2  # prefill + decode, nothing else


def test_decode_memory_flat_across_steps():
    """Donated-cache invariant: framework-visible memory does not grow with
    the number of decode steps (each step aliases the cache update)."""
    from paddle_tpu.core import memory as pt_memory

    pt.seed(6)
    cfg = _tiny_cfg()
    m = GPTStackedForPretraining(cfg)
    m.eval()
    ids = _prompt(cfg)
    one = pt.to_tensor(np.float32(1.0))
    eng = generation._engine_for(m, 2, 64, "float32", do_sample=False,
                                 top_k=0, use_top_p=False)
    tok, _ = eng.prefill(ids, one, one)
    pos = pt.to_tensor(np.int32(6))
    tok, pos, _ = eng.decode(tok, pos, one, one)
    before = pt_memory.memory_allocated()
    for _ in range(20):
        tok, pos, _ = eng.decode(tok, pos, one, one)
    after = pt_memory.memory_allocated()
    # per-step residue would be >= one [B, V] logits buffer per step; allow
    # only sub-single-buffer noise
    assert after - before < 2 * 1024 * np.dtype(np.float32).itemsize, (
        before, after)


# ---------------------------------------------------------------------------
# sampling: top-k / top-p filtering and reproducibility
# ---------------------------------------------------------------------------

def test_filter_logits_top_k_support():
    logits = pt.to_tensor(np.array([[0., 1., 2., 3., 4.],
                                    [4., 3., 2., 1., 0.]], np.float32))
    f = generation.filter_logits(logits, top_k=2).numpy()
    kept = f > -1e29
    assert kept.sum(axis=1).tolist() == [2, 2]
    assert kept[0].tolist() == [False, False, False, True, True]
    assert kept[1].tolist() == [True, True, False, False, False]


def test_filter_logits_top_p_mass():
    """Nucleus filter keeps the smallest prefix reaching mass p and the
    kept set renormalizes to >= p (always at least the argmax)."""
    raw = np.array([[0., 1., 2., 3., 4.]], np.float32)
    probs = np.exp(raw[0]) / np.exp(raw[0]).sum()
    logits = pt.to_tensor(raw)
    # p=0.6: the argmax alone carries ~0.636 >= 0.6 -> keep exactly it
    f = generation.filter_logits(
        logits, top_p=pt.to_tensor(np.float32(0.6))).numpy()
    assert (f > -1e29).tolist() == [[False, False, False, False, True]]
    # p=0.8: top-1 (0.636) < 0.8, top-2 (0.87) >= 0.8 -> keep two
    f = generation.filter_logits(
        logits, top_p=pt.to_tensor(np.float32(0.8))).numpy()
    kept = f > -1e29
    assert kept.sum() == 2
    assert probs[kept[0]].sum() >= 0.8


def test_sample_tokens_stay_in_top_k_support():
    logits = pt.to_tensor(
        np.array([[0.0, 5.0, 1.0, 4.0, 2.0, 3.0, -1.0, 0.5]], np.float32))
    pt.seed(123)
    seen = set()
    for _ in range(64):
        tok = generation.sample_tokens(
            logits, do_sample=True,
            temperature=pt.to_tensor(np.float32(1.0)), top_k=3)
        seen.add(int(tok.numpy()[0]))
    assert seen <= {1, 3, 5}, seen   # the top-3 ids
    assert len(seen) > 1             # and it actually samples


def test_generate_sampling_reproducible_and_in_vocab():
    cfg = _tiny_cfg()
    pt.seed(0)
    m = GPTStackedForPretraining(cfg)
    m.eval()
    ids = _prompt(cfg)
    pt.seed(42)
    a = m.generate(ids, max_new_tokens=6, do_sample=True, temperature=0.8,
                   top_k=50, top_p=0.9, max_seq_len=64,
                   cache_dtype="float32").numpy()
    pt.seed(42)
    b = m.generate(ids, max_new_tokens=6, do_sample=True, temperature=0.8,
                   top_k=50, top_p=0.9, max_seq_len=64,
                   cache_dtype="float32").numpy()
    assert np.array_equal(a, b)
    assert (a >= 0).all() and (a < cfg.vocab_size).all()


def test_generate_eos_padding():
    """Rows freeze at their first eos: every position after it is eos."""
    pt.seed(9)
    cfg = _tiny_cfg()
    m = GPTStackedForPretraining(cfg)
    m.eval()
    ids = _prompt(cfg)
    base = m.generate(ids, max_new_tokens=6, max_seq_len=64,
                      cache_dtype="float32").numpy()
    eos = int(base[0, 6 + 2])  # whatever greedy emits at step 2 of row 0
    out = m.generate(ids, max_new_tokens=6, eos_token_id=eos, max_seq_len=64,
                     cache_dtype="float32").numpy()
    gen = out[:, 6:]
    for row in gen:
        hits = np.nonzero(row == eos)[0]
        if hits.size:
            assert (row[hits[0]:] == eos).all()


def test_decode_engine_cache_is_lru_bounded():
    """Each engine pins a KV cache in HBM: distinct request shapes must
    not accumulate past the bound, and reuse must refresh recency."""
    pt.seed(14)
    cfg = _tiny_cfg()
    m = GPTStackedForPretraining(cfg)
    m.eval()
    ids = _prompt(cfg)
    for b in (16, 24, 32, 40, 48):   # five distinct max_seq_len keys
        m.generate(ids, max_new_tokens=2, max_seq_len=b + 16,
                   cache_dtype="float32")
    engines = m.__dict__["_decode_engines"]
    assert len(engines) == generation._MAX_ENGINES
    assert (2, 32, "float32", False, 0, False) not in engines  # evicted
    m.clear_decode_cache()
    assert "_decode_engines" not in m.__dict__


def test_cache_path_rejects_attn_mask():
    """The KV-cache path is causal+length-masked; a user-supplied mask
    (left padding) must fail loudly, not be silently dropped: the model
    that serves takes no mask at all."""
    cfg = _tiny_cfg()
    m = GPTStackedForPretraining(cfg)
    m.eval()
    ids = _prompt(cfg)
    cache = m.new_kv_cache(2, 64, dtype="float32")
    mask = pt.to_tensor(np.ones((2, 1, 6, 6), np.float32))
    with pytest.raises(TypeError, match="attn_mask"):
        m(ids, attn_mask=mask, kv_cache=cache, cache_index=0)


def test_generate_validates_lengths():
    cfg = _tiny_cfg()
    m = GPTStackedForPretraining(cfg)
    ids = _prompt(cfg)
    with pytest.raises(ValueError, match="exceeds the"):
        m.generate(ids, max_new_tokens=60, max_seq_len=64,
                   cache_dtype="float32")
    with pytest.raises(ValueError, match="max_position_embeddings"):
        m.generate(ids, max_new_tokens=4, max_seq_len=4096)


# ---------------------------------------------------------------------------
# pallas kernel parity (interpreter on CPU; the real kernel on TPU)
# ---------------------------------------------------------------------------

def test_decode_attention_kernel_parity_interpret():
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import decode_attention as da

    rng = np.random.RandomState(0)
    B, H, S, D = 2, 2, 256, 64
    assert da.decode_shape_supported(S, D)
    for dt in (jnp.float32, jnp.bfloat16):
        q = jnp.array(rng.randn(B, H, D), dt)
        k = jnp.array(rng.randn(B, H, S, D), dt)
        v = jnp.array(rng.randn(B, H, S, D), dt)
        for length in (1, 127, 128, 256):
            ref = np.asarray(da._xla_decode_reference(
                q, k, v, jnp.int32(length), 0.125), np.float32)
            q8 = jnp.broadcast_to(q.reshape(B * H, 1, D), (B * H, 8, D))
            out = da._decode_pallas(
                q8, k.reshape(B * H, S, D), v.reshape(B * H, S, D),
                jnp.int32(length), 0.125, interpret=True)
            got = np.asarray(out[:, 0, :].reshape(B, H, D), np.float32)
            tol = 5e-6 if dt == jnp.float32 else 1e-2
            np.testing.assert_allclose(got, ref, rtol=tol, atol=tol)


@pytest.mark.skipif(
    __import__("jax").devices()[0].platform != "tpu",
    reason="real-kernel parity needs a TPU backend (tools/tpu_smoke.py)")
def test_decode_attention_kernel_parity_tpu():
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import decode_attention as da

    rng = np.random.RandomState(0)
    B, H, S, D = 2, 4, 512, 64
    q = jnp.array(rng.randn(B, H, D), jnp.bfloat16)
    k = jnp.array(rng.randn(B, H, S, D), jnp.bfloat16)
    v = jnp.array(rng.randn(B, H, S, D), jnp.bfloat16)
    for length in (1, 5, 127, 128, 200, 512):
        got = np.asarray(da.decode_attention(q, k, v, jnp.int32(length)),
                         np.float32)
        ref = np.asarray(da._xla_decode_reference(
            q, k, v, jnp.int32(length), 0.125), np.float32)
        np.testing.assert_allclose(got, ref, rtol=5e-2, atol=5e-2)


def test_decode_shape_eligibility_gate():
    from paddle_tpu.ops.pallas_kernels.decode_attention import (
        decode_shape_supported,
    )

    assert decode_shape_supported(128, 64)
    assert decode_shape_supported(2048, 128)
    assert not decode_shape_supported(64, 64)     # too short
    assert not decode_shape_supported(200, 64)    # not a 128 multiple
    assert not decode_shape_supported(256, 80)    # head dim not 64-multiple


# ---------------------------------------------------------------------------
# inference.Predictor causal-LM decode mode
# ---------------------------------------------------------------------------

def test_predictor_causal_lm_decode_mode():
    from paddle_tpu import inference

    pt.seed(2)
    cfg = _tiny_cfg()
    m = GPTStackedForPretraining(cfg)
    m.eval()
    ids = _prompt(cfg)
    ref = m.generate(ids, max_new_tokens=5, max_seq_len=64,
                     cache_dtype="float32").numpy()

    config = inference.Config()
    config.set_causal_lm_model(m)
    config.enable_causal_lm_decode(max_new_tokens=5, max_seq_len=64,
                                   cache_dtype="float32")
    assert "causal_lm_decode" in config.summary()
    predictor = inference.create_predictor(config)
    h = predictor.get_input_handle(predictor.get_input_names()[0])
    h.copy_from_cpu(ids.numpy())
    predictor.run()
    out = predictor.get_output_handle(
        predictor.get_output_names()[0]).copy_to_cpu()
    assert np.array_equal(out, ref)


def test_predictor_decode_mode_requires_live_model(tmp_path):
    from paddle_tpu import inference

    config = inference.Config(str(tmp_path / "nope"))
    config.enable_causal_lm_decode(max_new_tokens=2)
    with pytest.raises(RuntimeError, match="live model"):
        inference.create_predictor(config)


def test_predictor_live_model_requires_explicit_decode_opts():
    """A live model alone must not silently decode with hidden defaults."""
    from paddle_tpu import inference

    m = GPTStackedForPretraining(_tiny_cfg())
    config = inference.Config().set_causal_lm_model(m)
    with pytest.raises(RuntimeError, match="enable_causal_lm_decode"):
        inference.create_predictor(config)


# ---------------------------------------------------------------------------
# satellite regressions
# ---------------------------------------------------------------------------

def test_clear_grad_set_to_zero():
    """clear_grad(set_to_zero=True) must WRITE zeros (accumulation target
    stays bound), not silently behave like set_to_zero=False."""
    pt.seed(1)
    lin = pt.nn.Linear(4, 2)
    opt = pt.optimizer.SGD(learning_rate=0.1, parameters=lin.parameters())
    x = pt.to_tensor(np.ones((3, 4), np.float32))

    lin(x).sum().backward()
    assert all(p.grad is not None for p in lin.parameters())
    g0 = {id(p): p.grad.numpy().copy() for p in lin.parameters()}
    held = {id(p): p.grad for p in lin.parameters()}  # cached handles

    opt.clear_grad(set_to_zero=True)
    for p in lin.parameters():
        assert p.grad is not None, "set_to_zero must keep the grad bound"
        assert p.grad is held[id(p)], "zeroing must be in place"
        assert not np.any(p.grad.numpy())
    # backward accumulates INTO the zeroed grad -> same as a fresh grad
    lin(x).sum().backward()
    for p in lin.parameters():
        np.testing.assert_allclose(p.grad.numpy(), g0[id(p)], rtol=1e-6)

    opt.clear_grad()  # default: unbind
    assert all(p.grad is None for p in lin.parameters())


def test_dataloader_prefetch_producer_shutdown_on_early_break():
    """A consumer that stops iterating early must release the prefetch
    producer thread (it used to park forever on q.put)."""
    from paddle_tpu.io import DataLoader, Dataset

    class Ds(Dataset):
        def __len__(self):
            return 64

        def __getitem__(self, i):
            return np.full((4,), i, np.float32)

    before = set(threading.enumerate())
    loader = DataLoader(Ds(), batch_size=2, use_buffer_reader=True,
                        prefetch_factor=2)
    it = iter(loader)
    next(it)
    next(it)
    it.close()  # early break: generator finalization
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        leaked = [t for t in threading.enumerate()
                  if t not in before and t.is_alive()]
        if not leaked:
            break
        time.sleep(0.05)
    assert not leaked, f"prefetch producer leaked: {leaked}"

    # and a full pass still yields every batch exactly once
    vals = [b.numpy()[0, 0] for b in DataLoader(
        Ds(), batch_size=2, use_buffer_reader=True, prefetch_factor=2)]
    assert len(vals) == 32
