"""The stacked decoder's ONE block body against a plain reference.

``GPTStackedDecoder._block_fn`` builds the block every use runs (training,
contiguous-cache decode, the paged serving step); the attention core is its
argument.  Here the body runs under each core and is held to a plain numpy
block written below from the definition (LayerNorm, fused QKV, causal
softmax attention, projection, GELU feed-forward; float64 over the same
rounded weights), which shares no code with the program.  A new norm, activation or position scheme extends
``_ref_block`` and the parametrization by one value.

Plus: the layered ``GPTForPretraining`` is the eager and training model and
nothing else: it takes no cache, and the engine refuses it by name.
"""
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models import (
    GPTForPretraining, GPTStackedDecoder, gpt_tiny,
)
from paddle_tpu.models import gpt as gpt_mod
from paddle_tpu.ops.pallas_kernels.ragged_paged_attention import (
    RAGGED_PLAN_FIELDS, build_ragged_plan,
)

B, S, S0 = 2, 12, 7            # sequences, tokens each, where "mid" resumes
MAX_SEQ, PAGE = 16, 8


def _cfg():
    return gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0)


def _layer_params(cfg, dtype, seed=0):
    """One layer's parameters in ``_PARAM_NAMES`` order, none of them at
    its initial value (gains off 1, biases off 0), rounded to ``dtype``."""
    rng = np.random.RandomState(seed)
    h, f = cfg.hidden_size, cfg.ffn_size
    shapes = {"ln1_g": (h,), "ln1_b": (h,), "qkv_w": (h, 3 * h),
              "qkv_b": (3 * h,), "proj_w": (h, h), "proj_b": (h,),
              "ln2_g": (h,), "ln2_b": (h,), "fc1_w": (h, f), "fc1_b": (f,),
              "fc2_w": (f, h), "fc2_b": (h,)}
    out = []
    for name in GPTStackedDecoder._PARAM_NAMES:
        a = rng.randn(*shapes[name]).astype(np.float32)
        a = a * (0.15 if name.endswith("_w") else 0.1)
        if name.endswith("_g"):
            a = a + 1.0
        out.append(jnp.asarray(a).astype(dtype))
    return out


def _ref_block(p, h, cfg):
    """The block from its definition, in numpy float64.  Returns the
    block's output and the keys and values it attended over
    ([B, S, nh, hd])."""
    (l1g, l1b, qkvw, qkvb, pw, pb, l2g, l2b, f1w, f1b, f2w, f2b) = (
        np.asarray(a.astype(jnp.float32), np.float64) for a in p)
    h = np.asarray(h.astype(jnp.float32), np.float64)
    nh, hd = cfg.num_heads, cfg.head_dim

    def layer_norm(x, g, b):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / np.sqrt(var + cfg.layer_norm_eps) * g + b

    b_, s_, hidden = h.shape
    qkv = (layer_norm(h, l1g, l1b) @ qkvw + qkvb).reshape(b_, s_, 3, nh, hd)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    scores = np.einsum("bqnd,bknd->bnqk", q, k) / np.sqrt(hd)
    scores = np.where(np.tril(np.ones((s_, s_), bool)), scores, -np.inf)
    att = np.exp(scores - scores.max(-1, keepdims=True))
    att = att / att.sum(-1, keepdims=True)
    out = np.einsum("bnqk,bknd->bqnd", att, v).reshape(b_, s_, hidden)
    h = h + out @ pw + pb
    y = layer_norm(h, l2g, l2b) @ f1w + f1b
    y = 0.5 * y * (1.0 + np.tanh(np.sqrt(2.0 / np.pi)
                                 * (y + 0.044715 * y ** 3)))
    return h + y @ f2w + f2b, k, v


def _cache_core(cfg, kc, vc, pos, pos_is_zero):
    def attend(q, k, v):
        out, kc2, vc2 = gpt_mod._raw_attend_with_cache(
            q, k, v, kc, vc, jnp.asarray(pos, jnp.int32),
            head_dim=cfg.head_dim, use_flash=False, pos_is_zero=pos_is_zero)
        return out, (kc2, vc2)
    return attend


def _run_core(core, dec, p, h, dtype):
    """The body's output [B, S, H] under ``core``, and the keys and values
    it left behind as [B, S, nh, hd] (None where the core keeps none)."""
    cfg, block = dec._cfg, dec._block_fn()
    nh, hd = cfg.num_heads, cfg.head_dim
    if core == "none":
        train = dec._train_core(False)
        out, _ = block(p, h, lambda q, k, v: (train(q, k, v, None), None))
        return out, None, None
    if core in ("cache_pos0", "cache_mid"):
        kc = jnp.zeros((B, nh, MAX_SEQ, hd), dtype)
        vc = jnp.zeros((B, nh, MAX_SEQ, hd), dtype)
        if core == "cache_pos0":
            out, (kc, vc) = block(p, h, _cache_core(cfg, kc, vc, 0, True))
        else:
            # the first S0 tokens fill the cache; the rest resume at S0
            # and must see them through it
            head, (kc, vc) = block(p, h[:, :S0],
                                   _cache_core(cfg, kc, vc, 0, True))
            tail, (kc, vc) = block(p, h[:, S0:],
                                   _cache_core(cfg, kc, vc, S0, False))
            out = jnp.concatenate([head, tail], axis=1)
        back = lambda c: jnp.swapaxes(c[:, :, :S], 1, 2)  # noqa: E731
        return out, back(kc), back(vc)
    assert core == "paged"
    # every token a flat row of its own (C == 1), its sequence's pages out
    # of order in the pool, attention through the ragged plan
    n_pages = 1 + B * (MAX_SEQ // PAGE)
    tables = np.array([[3, 1], [2, 4]], np.int32)
    plan, _ = build_ragged_plan(
        [(0, S, tables[b]) for b in range(B)], token_block=8,
        page_size=PAGE, t_max=B * S, nb_max=B * 2, wl_max=B * 2 * 2)
    plan = tuple(jnp.asarray(plan[f]) for f in RAGGED_PLAN_FIELDS)
    pk = jnp.zeros((n_pages, nh, PAGE, hd), dtype)
    pv = jnp.zeros((n_pages, nh, PAGE, hd), dtype)
    tok_tables = jnp.asarray(np.repeat(tables, S, axis=0))       # [B*S, 2]
    tok_pos = jnp.asarray(np.tile(np.arange(S, dtype=np.int32), B))

    def attend(q, k, v):
        out, pk2, pv2 = gpt_mod._raw_attend_paged(
            q, k, v, pk, pv, tok_tables, tok_pos, head_dim=hd,
            page_size=PAGE, ragged_plan=plan)
        return out, (pk2, pv2)

    out, (pk, pv) = block(p, h.reshape(B * S, 1, -1), attend)

    def back(pool):          # [P, nh, page, hd] -> [B, S, nh, hd]
        seqs = pool[jnp.asarray(tables)]             # [B, 2, nh, page, hd]
        seqs = jnp.transpose(seqs, (0, 1, 3, 2, 4)).reshape(B, -1, nh, hd)
        return seqs[:, :S]

    return out.reshape(B, S, -1), back(pk), back(pv)


# a share of the largest reference value.  Measured: 4e-7 in float32, 7e-3
# in bfloat16; a dropped bias reads 7e-2 to 1e-1 in either
@pytest.mark.parametrize("dtype,tol", [("float32", 5e-6), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("core", ["none", "cache_pos0", "cache_mid", "paged"])
def test_block_body_matches_plain_reference(core, dtype, tol):
    cfg = _cfg()
    jd = jnp.dtype(dtype)
    p = _layer_params(cfg, jd)
    h = jnp.asarray(np.random.RandomState(1).randn(B, S, cfg.hidden_size)
                    .astype(np.float32)).astype(jd)
    dec = GPTStackedDecoder(cfg)
    dec.eval()
    want, want_k, want_v = _ref_block(p, h, cfg)
    got, got_k, got_v = _run_core(core, dec, p, h, jd)
    assert got.dtype == jd
    np.testing.assert_allclose(np.asarray(got.astype(jnp.float32)), want,
                               rtol=tol, atol=tol * np.abs(want).max())
    if got_k is not None:
        # what the core left in its cache or pool is the block's own K, V
        for got_c, want_c in ((got_k, want_k), (got_v, want_v)):
            assert got_c.dtype == jd
            np.testing.assert_allclose(
                np.asarray(got_c.astype(jnp.float32)), want_c, rtol=tol,
                atol=tol * np.abs(want_c).max())


def test_layered_class_does_not_serve():
    """The layered class takes no cache, page table, plan or adapter and
    has no ``generate()``; the engine names the contract it lacks."""
    from paddle_tpu.serving import ServingEngine

    cfg = _cfg()
    m = GPTForPretraining(cfg)
    m.eval()
    ids = pt.to_tensor(np.zeros((1, 4), np.int64))
    assert m(ids).shape == [1, 4, cfg.vocab_size]
    for kw in ("kv_cache", "cache_index", "page_tables", "ragged_plan",
               "out_rows", "lora"):
        with pytest.raises(TypeError, match=kw):
            m(ids, **{kw: None})
    for name in ("generate", "new_kv_cache", "new_paged_kv_cache",
                 "_cached_lm_logits", "_paged_lm_logits"):
        assert not hasattr(m, name), name
    with pytest.raises(TypeError, match="paged-cache contract.*GPTForPretraining "
                                        "lacks new_paged_kv_cache and _paged_lm_logits"):
        ServingEngine(m, num_slots=1, page_size=16, max_context=32)
