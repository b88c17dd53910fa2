"""Speculative serving: draft propose / fused verify / rollback-exact
page accounting (serving/speculative.py; ISSUE-15)."""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import serving
from paddle_tpu.models import (
    GPTStackedForPretraining, gpt_tiny, truncated_draft,
)
from paddle_tpu.serving import (
    BlockAllocator, SamplingParams, ServingEngine, SpeculativeEngine,
)

ENG_KW = dict(num_slots=3, page_size=16, max_context=64,
              cache_dtype="float32")


def _model():
    pt.seed(0)
    cfg = gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0)
    m = GPTStackedForPretraining(cfg)
    m.eval()
    return m, cfg


def _prompts(cfg, lengths=(5, 18, 9, 26, 13), seed=1):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, cfg.vocab_size, (s,)) for s in lengths]


# ---------------------------------------------------------------------------
# BlockAllocator speculative-reservation API
# ---------------------------------------------------------------------------

class TestSpecReservations:
    def test_reserve_commit_rollback_ledger(self):
        a = BlockAllocator(8)                       # 7 allocatable
        base = a.alloc(2)
        sp = a.reserve_spec(3)
        assert len(sp) == 3
        assert (a.used_pages, a.spec_pages, a.free_pages) == (2, 3, 2)
        assert a.used_pages + a.spec_pages + a.free_pages == a.capacity
        a.commit_spec(sp[:1])
        a.rollback_spec(sp[1:])
        assert (a.used_pages, a.spec_pages, a.free_pages) == (3, 0, 4)
        a.free(base + sp[:1])
        assert a.free_pages == a.capacity

    def test_reserve_all_or_nothing(self):
        a = BlockAllocator(4)
        assert a.reserve_spec(5) is None
        assert a.spec_pages == 0 and a.free_pages == 3

    def test_typed_misuse_raises(self):
        a = BlockAllocator(6)
        sp = a.reserve_spec(2)
        with pytest.raises(ValueError):
            a.free(sp)                   # spec pages are not allocations
        with pytest.raises(ValueError):
            a.commit_spec([4])           # never reserved
        a.rollback_spec(sp)
        with pytest.raises(ValueError):
            a.rollback_spec(sp)          # double rollback

    def test_spec_counts_against_free_list(self):
        a = BlockAllocator(5)
        a.reserve_spec(4)
        assert a.alloc(1) is None        # spec pages are really held


# ---------------------------------------------------------------------------
# greedy parity + acceptance + trace bounds
# ---------------------------------------------------------------------------

class TestGreedyParity:
    def test_same_model_draft(self):
        m, cfg = _model()
        prompts = _prompts(cfg)
        ref = ServingEngine(m, **ENG_KW)
        want = ref.generate_batch(prompts, 7)
        ref.close()
        serving.reset_serve_trace_counts()
        eng = SpeculativeEngine(m, m, spec_k=3, **ENG_KW)
        got = eng.generate_batch(prompts, 7)
        for g, w in zip(got, want):
            assert np.array_equal(g, w)
        mets = eng.metrics()
        assert mets["spec_acceptance_rate"] == 1.0
        assert mets["spec_proposed_tokens"] > 0
        tc = serving.serve_trace_counts()
        assert tc["fused"] <= 2 and tc["draft"] <= 2, tc
        assert eng.allocator.used_pages == 0
        assert eng.draft.allocator.used_pages == 0
        assert eng.draft.allocator.spec_pages == 0
        eng.close()

    @pytest.mark.slow
    def test_matches_generate(self):
        m, cfg = _model()
        prompts = _prompts(cfg, lengths=(6, 14, 9))
        refs = [np.asarray(m.generate(
            pt.to_tensor(p[None, :], dtype="int64"), max_new_tokens=5,
            max_seq_len=64, cache_dtype="float32").numpy())[0]
            for p in prompts]
        eng = SpeculativeEngine(m, m, spec_k=4, **ENG_KW)
        got = eng.generate_batch(prompts, 5)
        for g, w in zip(got, refs):
            assert np.array_equal(g, w)
        eng.close()

    @pytest.mark.slow
    def test_truncated_draft_parity(self):
        m, cfg = _model()
        d = truncated_draft(m, 1)
        assert d.decoder.qkv_w.shape[0] == 1
        prompts = _prompts(cfg, lengths=(5, 18, 9))
        ref = ServingEngine(m, **ENG_KW)
        want = ref.generate_batch(prompts, 6)
        ref.close()
        eng = SpeculativeEngine(m, d, spec_k=3, **ENG_KW)
        got = eng.generate_batch(prompts, 6)
        for g, w in zip(got, want):
            assert np.array_equal(g, w)   # exact REGARDLESS of acceptance
        mets = eng.metrics()
        assert 0.0 <= mets["spec_acceptance_rate"] <= 1.0
        eng.close()

    @pytest.mark.slow
    def test_eos_truncates_accepted_run(self):
        m, cfg = _model()
        prompts = _prompts(cfg, lengths=(6, 11))
        ref = ServingEngine(m, **ENG_KW)
        r_ref = [ref.submit(p, 8, eos_token_id=int(t)) for p, t in
                 zip(prompts, (3, 7))]
        ref.run_until_idle()
        ref.close()
        eng = SpeculativeEngine(m, m, spec_k=4, **ENG_KW)
        r_got = [eng.submit(p, 8, eos_token_id=int(t)) for p, t in
                 zip(prompts, (3, 7))]
        eng.run_until_idle()
        for g, w in zip(r_got, r_ref):
            assert g.tokens == w.tokens
        assert eng.allocator.used_pages == 0
        eng.close()


# ---------------------------------------------------------------------------
# sampling: leftover-distribution resampling preserves the target dist
# ---------------------------------------------------------------------------

class TestLeftoverResampling:
    def _dist_trial(self, p_logits, q_probs, k, trials, seed=0):
        """Empirical distribution of the FIRST emitted token when the
        draft proposes from q against target logits — across S=trials
        parallel slots in few dispatches."""
        from paddle_tpu.serving.speculative import _verify_tokens
        from paddle_tpu.tensor import to_tensor

        V = p_logits.shape[-1]
        pt.seed(seed)
        rng = np.random.RandomState(seed)
        counts = np.zeros(V)
        S = 256
        q = np.asarray(q_probs, np.float32)
        for _ in range(trials // S):
            # draft proposals drawn from q (host-side — the draft's role)
            d = np.stack([rng.choice(V, size=k, p=q) for _ in range(S)])
            lg = np.broadcast_to(p_logits, (S, k + 1, V)).copy()
            out, n_acc, fin = _verify_tokens(
                to_tensor(lg), to_tensor(d.astype(np.int32)),
                to_tensor(np.full((S,), k, np.int32)),
                to_tensor(np.ones((S,), np.float32)),
                to_tensor(np.ones((S,), np.float32)),
                to_tensor(np.zeros((S,), np.int32)),
                to_tensor(np.ones((S,), bool)),
                qprobs=[to_tensor(np.broadcast_to(q, (S, V)).copy())
                        for _ in range(k)])
            out = np.asarray(out.numpy())
            for s in range(S):
                counts[int(out[s, 0])] += 1
        return counts / counts.sum()

    def test_first_token_distribution_is_target(self):
        V, k = 8, 2
        rng = np.random.RandomState(3)
        p_logits = rng.randn(k + 1, V).astype(np.float32)
        q = rng.rand(V).astype(np.float32) + 0.1
        q /= q.sum()
        emp = self._dist_trial(p_logits, q, k, trials=4096)
        want = np.exp(p_logits[0]) / np.exp(p_logits[0]).sum()
        # 4096 samples: per-bucket std <= ~0.008; assert within 5 sigma
        assert np.abs(emp - want).max() < 0.05, (emp, want)

    @pytest.mark.slow
    def test_identical_draft_always_accepts(self):
        """q == p makes the accept probability exactly 1 — no resampling
        path ever fires, n_acc == k deterministically."""
        from paddle_tpu.serving.speculative import _verify_tokens
        from paddle_tpu.tensor import to_tensor

        V, k, S = 8, 3, 16
        rng = np.random.RandomState(5)
        lg = rng.randn(S, k + 1, V).astype(np.float32)
        p = np.exp(lg) / np.exp(lg).sum(-1, keepdims=True)
        pt.seed(0)
        # proposals sampled from p itself
        d = np.stack([[rng.choice(V, p=p[s, j]) for j in range(k)]
                      for s in range(S)]).astype(np.int32)
        out, n_acc, fin = _verify_tokens(
            to_tensor(lg), to_tensor(d),
            to_tensor(np.full((S,), k, np.int32)),
            to_tensor(np.ones((S,), np.float32)),
            to_tensor(np.ones((S,), np.float32)),
            to_tensor(np.zeros((S,), np.int32)),
            to_tensor(np.ones((S,), bool)),
            qprobs=[to_tensor(p[:, j]) for j in range(k)])
        assert (np.asarray(n_acc.numpy()) == k).all()
        assert np.array_equal(np.asarray(out.numpy())[:, :k], d)
        assert np.asarray(fin.numpy()).all()

    def test_dead_qrows_masked_per_slot(self):
        """A slot with n_draft BELOW the tick's max (incl. 0) must draw
        its bonus from the pure target row — the q rows of propose
        iterations it never joined are another slot's distribution and
        must be masked to zero, not subtracted (regression: unmasked
        q_ext rows skewed the emitted distribution for mixed-nd
        ticks)."""
        from paddle_tpu.serving.speculative import _verify_tokens
        from paddle_tpu.tensor import to_tensor

        V, k, S = 8, 2, 256
        rng = np.random.RandomState(11)
        row = rng.randn(V).astype(np.float32)
        lg = np.broadcast_to(row, (S, k + 1, V)).copy()
        garbage = rng.rand(S, V).astype(np.float32)
        garbage /= garbage.sum(-1, keepdims=True)
        pt.seed(4)
        counts = np.zeros(V)
        for _ in range(16):
            out, n_acc, _fin = _verify_tokens(
                to_tensor(lg),
                to_tensor(np.zeros((S, k), np.int32)),
                to_tensor(np.zeros((S,), np.int32)),      # n_draft = 0
                to_tensor(np.ones((S,), np.float32)),
                to_tensor(np.ones((S,), np.float32)),
                to_tensor(np.zeros((S,), np.int32)),
                to_tensor(np.ones((S,), bool)),
                qprobs=[to_tensor(garbage) for _ in range(k)])
            assert (np.asarray(n_acc.numpy()) == 0).all()
            for t in np.asarray(out.numpy())[:, 0]:
                counts[int(t)] += 1
        emp = counts / counts.sum()
        want = np.exp(row) / np.exp(row).sum()
        assert np.abs(emp - want).max() < 0.05, (emp, want)

    def test_greedy_chain_ignores_qprobs(self):
        from paddle_tpu.serving.speculative import _verify_tokens
        from paddle_tpu.tensor import to_tensor

        V, k, S = 8, 2, 4
        rng = np.random.RandomState(7)
        lg = rng.randn(S, k + 1, V).astype(np.float32)
        g = lg.argmax(-1)
        d = g[:, :k].astype(np.int32)            # propose the argmax chain
        out, n_acc, fin = _verify_tokens(
            to_tensor(lg), to_tensor(d),
            to_tensor(np.full((S,), k, np.int32)),
            to_tensor(np.ones((S,), np.float32)),
            to_tensor(np.ones((S,), np.float32)),
            to_tensor(np.zeros((S,), np.int32)),
            to_tensor(np.zeros((S,), bool)))     # greedy slots
        assert (np.asarray(n_acc.numpy()) == k).all()
        assert np.array_equal(np.asarray(out.numpy()), g)

    @pytest.mark.slow
    def test_sampling_requests_complete(self):
        m, cfg = _model()
        prompts = _prompts(cfg, lengths=(6, 12, 9))
        eng = SpeculativeEngine(m, m, spec_k=3, **ENG_KW)
        sp = SamplingParams(do_sample=True, temperature=0.9, top_k=50,
                            top_p=0.95)
        reqs = [eng.submit(p, 6, sampling=sp if i % 2 else None)
                for i, p in enumerate(prompts)]
        eng.run_until_idle()
        assert all(r.finished and len(r.tokens) == 6 for r in reqs)
        assert eng.allocator.used_pages == 0
        assert eng.draft.allocator.spec_pages == 0
        eng.close()


# ---------------------------------------------------------------------------
# accounting under degradation / faults / churn
# ---------------------------------------------------------------------------

class TestSpecAccounting:
    @pytest.mark.slow
    def test_draft_pool_exhaustion_degrades_not_corrupts(self):
        m, cfg = _model()
        prompts = _prompts(cfg)
        ref = ServingEngine(m, **ENG_KW)
        want = ref.generate_batch(prompts, 7)
        ref.close()
        # 3 draft pages for 3 slots needing up to 4 pages each: constant
        # spec-reservation pressure -> skips, never wrong output
        eng = SpeculativeEngine(m, m, spec_k=3, draft_num_pages=4,
                                **ENG_KW)
        got = eng.generate_batch(prompts, 7)
        for g, w in zip(got, want):
            assert np.array_equal(g, w)
        mets = eng.metrics()
        assert mets["spec_draft_skips"] > 0
        assert eng.draft.allocator.used_pages == 0
        assert eng.draft.allocator.spec_pages == 0
        assert eng.draft.allocator.free_pages == \
            eng.draft.allocator.capacity
        eng.close()

    def test_randomized_fault_schedules_drain_exact(self):
        from paddle_tpu.faults import random_schedule

        m, cfg = _model()
        prompts = _prompts(cfg)
        for seed in (0,):   # seed sweep breadth lives in the serving gate
            rng = np.random.RandomState(seed)
            eng = SpeculativeEngine(m, m, spec_k=3, **ENG_KW)
            random_schedule(rng, horizon=25, n_faults=4,
                            num_slots=3).install(eng)
            reqs = [eng.submit(p, 6) for p in prompts]
            eng.run_until_idle(max_steps=3000)
            assert all(r.terminal for r in reqs)
            for alloc in (eng.allocator, eng.draft.allocator):
                assert alloc.used_pages == 0
                assert alloc.spec_pages == 0
                assert alloc.free_pages == alloc.capacity
            eng.close()

    @pytest.mark.slow
    def test_cancel_mid_flight_rolls_back_draft(self):
        m, cfg = _model()
        eng = SpeculativeEngine(m, m, spec_k=3, **ENG_KW)
        r1 = eng.submit(_prompts(cfg)[0], 20)
        r2 = eng.submit(_prompts(cfg)[1], 20)
        for _ in range(3):
            eng.step()
        r1.cancel()
        eng.run_until_idle()
        assert r1.state == serving.RequestState.CANCELLED
        assert r2.finished
        assert eng.draft.allocator.used_pages == 0
        assert eng.draft.allocator.spec_pages == 0
        eng.close()

    def test_multi_token_itl_convention(self):
        """Tokens accepted in one verify step share the step timestamp:
        the ITL histogram records one observation per emitted token after
        the first (zeros within a step — the documented convention)."""
        m, cfg = _model()
        eng = SpeculativeEngine(m, m, spec_k=3, **ENG_KW)
        reqs = [eng.submit(p, 7) for p in _prompts(cfg, lengths=(6, 11))]
        eng.run_until_idle()
        itl = eng.metrics()["slo"]["itl"]
        want = sum(len(r.tokens) - 1 for r in reqs)
        assert itl["count"] == want, (itl, want)
        hist = eng.metrics()["spec_accepted_per_step"]
        # one observation per harvested verify run (per decode slot per
        # step); with same-model acceptance the mean is spec_k except on
        # budget-clamped tail runs
        assert hist["count"] >= 1
        assert hist["max"] <= eng.spec_k
        eng.close()

    def test_metrics_surface(self):
        m, cfg = _model()
        eng = SpeculativeEngine(m, m, spec_k=2, **ENG_KW)
        eng.generate_batch(_prompts(cfg, lengths=(6,)), 4)
        mets = eng.metrics()
        for key in ("spec_proposed_tokens", "spec_accepted_tokens",
                    "spec_verify_steps", "spec_draft_steps",
                    "spec_acceptance_rate", "spec_accepted_per_step",
                    "draft_pages_used", "draft_spec_pages"):
            assert key in mets, key
        assert mets["spec_k"] == 2
        eng.close()

    def test_spec_k_validation(self):
        m, _cfg = _model()
        with pytest.raises(ValueError):
            SpeculativeEngine(m, m, spec_k=0, **ENG_KW)

    def test_vocab_mismatch_typed(self):
        m, _cfg = _model()
        cfg2 = gpt_tiny(vocab_size=512, hidden_dropout=0.0,
                        attention_dropout=0.0)
        d = GPTStackedForPretraining(cfg2)
        with pytest.raises(ValueError, match="vocab"):
            SpeculativeEngine(m, d, spec_k=2, **ENG_KW)


@pytest.mark.slow
class TestShardedSpeculative:
    def test_dp_replica_speculation(self):
        """Replica-level composition: every dp replica runs its own
        SpeculativeEngine behind the placement scheduler."""
        import jax

        from paddle_tpu.serving import ShardedServingEngine

        if len(jax.devices()) < 2:
            pytest.skip("needs 2 devices")
        m, cfg = _model()
        prompts = _prompts(cfg)
        ref = ServingEngine(m, **ENG_KW)
        want = ref.generate_batch(prompts, 5)
        ref.close()

        def factory(model, mesh, index, **kw):
            return SpeculativeEngine(model, model, spec_k=3, mesh=mesh,
                                     **kw)

        eng = ShardedServingEngine(m, dp=2, mp=1, engine_factory=factory,
                                   **ENG_KW)
        try:
            got = eng.generate_batch(prompts, 5)
            for g, w in zip(got, want):
                assert np.array_equal(g, w)
            for rep in eng.replicas:
                assert rep.metrics()["spec_acceptance_rate"] == 1.0
                assert rep.allocator.used_pages == 0
                assert rep.draft.allocator.used_pages == 0
        finally:
            eng.close()
