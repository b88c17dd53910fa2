"""Global prefix cache: COW shared KV pages behind a radix index
(docs/serving.md "Prefix cache").

- ``pages_for_tokens``: THE ceil-division helper admission, speculative
  reservations, and tail-only reservation all share — boundary cases
  (prompt exactly at a page edge, max_new 0/1) pinned here;
- ``BlockAllocator`` shared-page ledger: share/ref/unref/reclaim
  lifecycle, double-free/over-release detection extended to refcounted
  release, the 4-term invariant ``free + used + spec + shared ==
  capacity``, and the pressure reclaimer hook (eviction BEFORE admission
  backpressure);
- the radix index itself: page-granular longest-prefix match, the
  last-page cap (at least one token always prefills), duplicate-chunk
  dedup/adoption, leaf-first LRU eviction that never touches a
  referenced node, flush refusing while pages are referenced;
- engine-level COW regression: with the cache ON, greedy output across
  interleaved shared-prefix arrivals is token-for-token identical to a
  prefix-cache-disabled engine (fp32 + bf16, layered + stacked) — the
  sharing peer's output is bitwise what an isolated run produces, which
  is exactly the copy-on-write guarantee;
- eviction under pool pressure, speculative-decoding composition,
  prefix-locality placement ranking, and the telemetry surface
  (counters/histogram exist even with the cache disabled).
"""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.models import (
    GPTStackedForPretraining, gpt_tiny,
)
from paddle_tpu.serving import (
    BlockAllocator,
    PrefixCache,
    PrefixLocalityPlacement,
    RequestState,
    ServingEngine,
    pages_for_tokens,
)
from paddle_tpu.telemetry import metrics as tm

N_NEW = 4


# ---------------------------------------------------------------------------
# pages_for_tokens: the ONE ceil-pages helper (admission, speculative
# reservations, tail-only reservation)
# ---------------------------------------------------------------------------

def test_pages_for_tokens_boundaries():
    assert pages_for_tokens(0, 16) == 0
    assert pages_for_tokens(1, 16) == 1
    assert pages_for_tokens(15, 16) == 1
    assert pages_for_tokens(16, 16) == 1        # exactly at the page edge
    assert pages_for_tokens(17, 16) == 2
    assert pages_for_tokens(32, 16) == 2
    # admission sizing: a prompt landing exactly on a page edge with
    # max_new 0 fits its pages; ONE more token rolls a fresh page
    prompt = 32
    assert pages_for_tokens(prompt + 0, 16) == 2
    assert pages_for_tokens(prompt + 1, 16) == 3


def test_pages_for_tokens_rejects_bad_inputs():
    with pytest.raises(ValueError, match="tokens"):
        pages_for_tokens(-1, 16)
    with pytest.raises(ValueError, match="page_size"):
        pages_for_tokens(4, 0)


# ---------------------------------------------------------------------------
# BlockAllocator shared-page ledger
# ---------------------------------------------------------------------------

def _ledger(a):
    return a.free_pages + a.used_pages + a.spec_pages + a.shared_pages


def test_share_ref_unref_reclaim_lifecycle():
    a = BlockAllocator(num_pages=6)             # capacity 5 (page 0 null)
    pages = a.alloc(2)
    assert _ledger(a) == a.capacity
    a.share(pages[0])                           # allocated -> shared @ 1
    assert a.shared_pages == 1 and a.used_pages == 1
    assert a.refcount(pages[0]) == 1
    assert _ledger(a) == a.capacity
    a.ref(pages[0])
    assert a.refcount(pages[0]) == 2
    a.unref(pages[0])
    a.unref(pages[0])
    assert a.refcount(pages[0]) == 0            # stays shared at 0
    assert a.shared_pages == 1 and _ledger(a) == a.capacity
    a.reclaim(pages[0])                         # refcount 0 -> free list
    assert a.shared_pages == 0 and a.free_pages == a.capacity - 1
    assert _ledger(a) == a.capacity
    a.free([pages[1]])
    assert a.free_pages == a.capacity


def test_shared_page_error_paths():
    a = BlockAllocator(num_pages=6)
    (p,) = a.alloc(1)
    with pytest.raises(ValueError, match="not currently allocated"):
        a.share(p + 1)                          # not allocated
    a.share(p)
    with pytest.raises(ValueError):
        a.share(p)                              # no longer exclusively owned
    with pytest.raises(ValueError, match="double free or foreign"):
        a.free([p])                             # shared pages aren't freed
    a.unref(p)
    with pytest.raises(ValueError, match="over-release"):
        a.unref(p)                              # over-release past zero
    with pytest.raises(ValueError, match="not shared"):
        a.ref(999)                              # never shared
    with pytest.raises(ValueError, match="not shared"):
        a.reclaim(999)
    a.ref(p)
    with pytest.raises(ValueError, match="reader"):
        a.reclaim(p)                            # still referenced
    a.unref(p)
    a.reclaim(p)
    assert a.free_pages == a.capacity and _ledger(a) == a.capacity


def test_reclaimer_hook_runs_before_shortage():
    """Pool pressure calls the reclaimer BEFORE declaring shortage: a
    zero-refcount shared page is reclaimed to satisfy the allocation;
    without the hook the same call backpressures (returns None)."""
    a = BlockAllocator(num_pages=4)             # capacity 3
    pages = a.alloc(3)
    for p in pages:
        a.share(p)
        a.unref(p)                              # 3 shared pages @ 0
    assert a.alloc(2) is None                   # no reclaimer installed
    reclaimed = []

    def reclaimer(n):
        # reclaim up to n still-cached pages (PrefixCache.evict's contract:
        # best effort over zero-refcount pages, never raises on shortfall)
        for p in pages:
            if len(reclaimed) >= len(pages) or n <= 0:
                break
            if a.refcount(p) == 0:
                a.reclaim(p)
                reclaimed.append(p)
                n -= 1

    a.reclaimer = reclaimer
    got = a.alloc(2)
    assert got is not None and len(got) == 2
    assert len(reclaimed) == 2
    assert _ledger(a) == a.capacity
    # reclaimer that cannot free enough still ends in clean backpressure
    assert a.alloc(5) is None
    assert _ledger(a) == a.capacity


# ---------------------------------------------------------------------------
# the radix index (pure host-side: no model, no engine)
# ---------------------------------------------------------------------------

PS = 4


def _register(cache, alloc, toks):
    """Register every full page of ``toks`` the way the engine does at
    page completion (extend with a fresh page, adopt on dedup), then
    release the registering slot's own references — the state after the
    registering request retires: cached at refcount 0."""
    nodes = []
    for i in range(len(toks) // PS):
        (page,) = alloc.alloc(1)
        node, owned = cache.extend(nodes[-1] if nodes else None,
                                   toks[i * PS:(i + 1) * PS], page)
        if not owned:
            alloc.free([page])
        nodes.append(node)
    cache.release(nodes)
    return nodes


def test_radix_longest_match_acquire_release():
    a = BlockAllocator(num_pages=12)
    c = PrefixCache(a, page_size=PS)
    toks = np.arange(12, dtype=np.int64)
    nodes = _register(c, a, toks)
    assert c.nodes == 3 and a.shared_pages == 3
    # longest-prefix walk, page-granular
    assert c.match_len(np.arange(13)) == 12
    assert c.match_len(np.concatenate([toks[:8], [99, 98]])) == 8
    assert c.match_len(np.array([7, 7, 7])) == 0
    got_nodes, got_pages, n = c.acquire(np.arange(13))
    assert n == 12 and [nd.page for nd in got_nodes] == got_pages
    assert all(a.refcount(p) == 1 for p in got_pages)
    c.release(got_nodes)
    assert all(a.refcount(p) == 0 for p in got_pages)
    assert _ledger(a) == a.capacity
    for nd in nodes:
        a.reclaim(nd.page)                      # cleanup path sanity


def test_acquire_always_leaves_one_token_to_prefill():
    """A prompt that is ENTIRELY cached would admit a slot with nothing
    to prefill; the match is capped so the last token always runs."""
    a = BlockAllocator(num_pages=12)
    c = PrefixCache(a, page_size=PS)
    toks = np.arange(8, dtype=np.int64)
    _register(c, a, toks)
    _, pages, n = c.acquire(toks)               # prompt == cached prefix
    assert n == PS and len(pages) == 1          # NOT 8: last page excluded
    assert c.match_len(toks) == PS


def test_radix_dedup_adopts_existing_node():
    a = BlockAllocator(num_pages=12)
    c = PrefixCache(a, page_size=PS)
    toks = np.arange(PS, dtype=np.int64)
    (n1,) = _register(c, a, toks)
    (p2,) = a.alloc(1)
    n2, owned = c.extend(None, toks, p2)
    assert n2 is n1 and owned is False          # duplicate chunk: adopt
    assert a.refcount(n1.page) == 1             # dedup bumped the ref
    assert c.nodes == 1 and c.stats["deduped"] == 1
    a.free([p2])                                # caller frees its duplicate
    a.unref(n1.page)
    assert _ledger(a) == a.capacity


def test_lru_eviction_leaf_first_never_referenced():
    a = BlockAllocator(num_pages=12)
    c = PrefixCache(a, page_size=PS)
    old = _register(c, a, np.arange(8, dtype=np.int64))
    new = _register(c, a, np.full(PS, 77, dtype=np.int64))
    held_nodes, _, _ = c.acquire(np.full(8, 77, dtype=np.int64))
    assert len(held_nodes) == 1                 # the 77-chunk, now @ 1
    freed = c.evict(10)                         # asks for more than exists
    # both nodes of the old chain go (leaf first unlinks the parent too);
    # the referenced node survives any demand
    assert freed == 2 and c.nodes == 1
    assert c.stats["evictions"] == 2
    assert new[0] in set(c._root.children.values())
    c.release(held_nodes)
    assert c.evict(10) == 1 and c.nodes == 0
    assert a.free_pages == a.capacity
    assert all(nd.page != 0 for nd in old)      # sanity: never the null page


def test_flush_refuses_while_referenced():
    a = BlockAllocator(num_pages=12)
    c = PrefixCache(a, page_size=PS)
    _register(c, a, np.arange(PS, dtype=np.int64))
    nodes, _, _ = c.acquire(np.arange(8, dtype=np.int64))
    with pytest.raises(RuntimeError, match="reader"):
        c.flush()
    c.release(nodes)
    c.flush()
    assert c.nodes == 0 and a.free_pages == a.capacity


# ---------------------------------------------------------------------------
# engine-level: COW parity, eviction under pressure, composition
# ---------------------------------------------------------------------------

def _models():
    pt.seed(0)
    cfg = gpt_tiny(hidden_dropout=0.0, attention_dropout=0.0)
    return cfg


def _shared_prefix_prompts(cfg, rng, page_size=16):
    """Two system-prompt families x unique tails + one loner, interleaved
    so family siblings are in flight together (the COW window: a later
    sibling reads pages an earlier one wrote while both still decode)."""
    fam = [rng.randint(0, cfg.vocab_size, (20,)),
           rng.randint(0, cfg.vocab_size, (20,))]
    tails = [rng.randint(0, cfg.vocab_size, (k,)) for k in (3, 7, 5, 9)]
    return [
        np.concatenate([fam[0], tails[0]]),
        np.concatenate([fam[1], tails[1]]),
        np.concatenate([fam[0], tails[2]]),
        rng.randint(0, cfg.vocab_size, (11,)),
        np.concatenate([fam[1], tails[3]]),
        np.concatenate([fam[0], tails[1]]),
    ]


def _parity_combo(dtype):
    cfg = _models()
    model = GPTStackedForPretraining(cfg)
    model.eval()
    rng = np.random.RandomState(5)
    prompts = _shared_prefix_prompts(cfg, rng)
    kw = dict(num_slots=2, page_size=16, max_context=64, cache_dtype=dtype)
    ref_eng = ServingEngine(model, **kw)
    refs = ref_eng.generate_batch(prompts, N_NEW)
    ref_eng.close()
    eng = ServingEngine(model, prefix_cache=True, **kw)
    # interleaved arrivals: 2 slots, 6 requests — siblings overlap
    reqs = [eng.submit(p, N_NEW) for p in prompts]
    eng.run_until_idle(max_steps=1000)
    for r, ref in zip(reqs, refs):
        assert r.state == RequestState.DONE
        assert np.array_equal(r.output_ids(), ref), (
            "prefix-cache engine diverged from the cache-disabled run")
    mets = eng.metrics()
    assert mets["prefix_hits"] + mets["prefix_partial_hits"] >= 1
    assert mets["prefix_cached_tokens"] >= 16
    a = eng.allocator
    assert a.used_pages == 0 and a.spec_pages == 0
    assert a.free_pages + a.shared_pages == a.capacity
    eng.close()


def test_cache_parity_fp32_stacked():
    _parity_combo("float32")


def test_cache_parity_bf16_stacked():
    _parity_combo("bfloat16")


def test_eviction_under_pool_pressure_keeps_serving():
    """An admission that the free list alone cannot satisfy evicts LRU
    zero-refcount cache pages BEFORE backpressuring — and accounting
    stays exact through it."""
    cfg = _models()
    m = GPTStackedForPretraining(cfg)
    m.eval()
    rng = np.random.RandomState(11)
    eng = ServingEngine(m, num_slots=1, page_size=16, max_context=48,
                        num_pages=7, cache_dtype="float32",
                        prefix_cache=True)
    a = eng.allocator
    for n_prompt, n_new in ((20, 4), (40, 6), (44, 2)):
        r = eng.submit(rng.randint(0, cfg.vocab_size, (n_prompt,)), n_new)
        eng.run_until_idle(max_steps=300)
        assert r.state == RequestState.DONE, (r.state, r.error)
        assert a.free_pages + a.used_pages + a.shared_pages == a.capacity
    assert a.free_pages == 1 and a.shared_pages == 5   # cache-full pool
    # 34 tokens -> 3 pages, 1 free: the reclaimer must evict 2 LRU pages
    r = eng.submit(rng.randint(0, cfg.vocab_size, (30,)), N_NEW)
    eng.run_until_idle(max_steps=300)
    assert r.state == RequestState.DONE, (r.state, r.error)
    mets = eng.metrics()
    assert mets["prefix_evictions"] >= 2
    assert a.used_pages == 0
    assert a.free_pages + a.shared_pages == a.capacity
    eng.close()


def test_speculative_engine_composes_with_prefix_cache():
    """Cached-prefix admission seeds the draft's catch-up backlog: greedy
    speculative output stays bit-identical and BOTH pools drain."""
    from paddle_tpu.serving import SpeculativeEngine

    cfg = _models()
    m = GPTStackedForPretraining(cfg)
    m.eval()
    rng = np.random.RandomState(3)
    prompts = _shared_prefix_prompts(cfg, rng)[:4]
    kw = dict(num_slots=2, page_size=16, max_context=64,
              cache_dtype="float32")
    ref_eng = ServingEngine(m, **kw)
    refs = ref_eng.generate_batch(prompts, N_NEW)
    ref_eng.close()
    eng = SpeculativeEngine(m, m, spec_k=2, prefix_cache=True, **kw)
    reqs = [eng.submit(p, N_NEW) for p in prompts]
    eng.run_until_idle(max_steps=1000)
    for r, ref in zip(reqs, refs):
        assert r.state == RequestState.DONE, (r.state, r.error)
        assert np.array_equal(r.output_ids(), ref)
    mets = eng.metrics()
    assert mets["spec_acceptance_rate"] == 1.0      # same-model draft
    assert mets["prefix_hits"] + mets["prefix_partial_hits"] >= 1
    for alloc in (eng.allocator, eng.draft.allocator):
        assert alloc.used_pages == 0 and alloc.spec_pages == 0
    assert (eng.allocator.free_pages + eng.allocator.shared_pages
            == eng.allocator.capacity)
    eng.close()


# ---------------------------------------------------------------------------
# placement + telemetry surfaces
# ---------------------------------------------------------------------------

class _FakeQueue:
    def __init__(self, depth):
        self.depth = depth


class _FakeAlloc:
    capacity, used_pages = 10, 0


class _FakeSched:
    active_slots = 0


class _FakeReplica:
    def __init__(self, depth, match):
        self.queue = _FakeQueue(depth)
        self.allocator = _FakeAlloc()
        self.scheduler = _FakeSched()
        self.prefix_cache = None
        if match is not None:
            self.prefix_cache = type(
                "C", (), {"match_len": staticmethod(lambda p, m=match: m)})()


def test_prefix_locality_placement_ranking():
    """Longest cached prefix wins; load only breaks ties; replicas with
    no cache rank as match 0 (plain least-loaded among themselves)."""
    prompt = np.arange(32)
    pol = PrefixLocalityPlacement()
    engines = [_FakeReplica(0, 0), _FakeReplica(5, 32), _FakeReplica(0, 16)]
    assert pol.rank_for(engines, prompt) == [1, 2, 0]
    # ties on match fall back to least-loaded, then index
    engines = [_FakeReplica(3, 16), _FakeReplica(1, 16), _FakeReplica(1, None)]
    assert pol.rank_for(engines, prompt) == [1, 0, 2]
    # the base class rank() is untouched (load-only)
    assert pol.rank([_FakeReplica(2, None), _FakeReplica(0, None)]) == [1, 0]


def test_prefix_metrics_exist_with_cache_disabled():
    """metrics() keys and the Prometheus series exist whether or not the
    cache is on — dashboards and the sharded sum never KeyError."""
    cfg = _models()
    m = GPTStackedForPretraining(cfg)
    m.eval()
    eng = ServingEngine(m, num_slots=1, page_size=16, max_context=32,
                        cache_dtype="float32")
    try:
        assert eng.prefix_cache is None
        mets = eng.metrics()
        for k in ("prefix_hits", "prefix_partial_hits", "prefix_misses",
                  "prefix_evictions", "prefix_cached_tokens",
                  "prefix_hit_rate", "cached_tokens_share",
                  "prefix_cache_pages", "prefix_cache_nodes",
                  "shared_pages"):
            assert mets[k] == 0 or mets[k] == 0.0, (k, mets[k])
        text = tm.registry().prometheus_text()
        assert "serving_prefix_hits_total" in text
        assert "serving_prefix_evictions_total" in text
        assert "serving_prefix_cached_tokens" in text
    finally:
        eng.close()
