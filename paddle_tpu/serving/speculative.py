"""Speculative serving: draft-model propose, ONE fused verify dispatch.

ROADMAP item 5 cashes in the ragged fused step's variable-tokens-per-step
design (PR 8): a cheap DRAFT model proposes up to ``k`` tokens per seated
decode slot, and the target model verifies all ``k + 1`` positions of
every slot in ONE dispatch of the existing fused ragged step — each
slot's :class:`~paddle_tpu.serving.admission.StepWork` is simply a
``k+1``-token run (``kind='verify'``), planned by the same
``AdmissionScheduler.plan_step`` budget math and launched through the
same work-list kernel.  No new kernel, no phase barrier: prefill runs,
plain decode slots and verification runs mix in the same launch.

Accept/reject happens IN-GRAPH, inside the compiled verify program:

- **greedy** — the emitted tokens are the target's own argmax chain
  ``g_0..g_{n}`` up to (and including) the first position where the draft
  proposal mismatches: bit-identical to the non-speculative engine by
  construction, because every ``g_j`` is conditioned on a prefix that
  matched the target's own choices.
- **sampling** — standard leftover-distribution resampling: proposal
  ``d_{j+1}`` (drawn from the draft's post-filter distribution ``q_j``)
  is accepted with probability ``min(1, p_j(d)/q_j(d))`` against the
  target's post-filter distribution ``p_j``; the first rejection
  resamples from ``norm(max(p_j - q_j, 0))``, and full acceptance draws
  the bonus token from ``p_k`` — the emitted-token distribution is
  EXACTLY the target model's (tests/test_speculative.py proves it per
  position).

Commit protocol: the engine commits each slot's accepted prefix with
``advance(idx, n_accepted + 1)`` — K/V the target wrote for REJECTED
positions sits beyond the committed position and is never read (every
read is position-masked), so the next verify run simply overwrites it.
The page-accounting invariant (PR 5/6: exact through every path) extends
to the DRAFT pool through the new
:class:`~paddle_tpu.serving.paged_cache.BlockAllocator` speculative
reservation API: draft pages are reserved ``reserve_spec`` on demand as
propose runs extend past the slot's committed pages, promoted
``commit_spec`` for positions the target accepted, and rolled back
``rollback_spec`` on rejection, faults, and retirement — free + used +
spec == capacity at all times, and everything drains to zero.

Trace budget: the draft runs its own retrace-free fused step (its own
pool, its own packed transport) dispatched up to ``k`` times per tick —
``serve_trace_counts()`` bounds ``fused <= 2`` (verify greedy+sampling)
and ``draft <= 2``, the CI gate's (d).

Degradation, never corruption: a draft that cannot run (draft pool
exhausted, catch-up backlog) proposes nothing — the slot decodes exactly
one token through the verify step, and the missed tokens queue on the
shadow's per-slot pending list to be ingested later.  Draft context can
therefore lag but never lies; verification keeps outputs exact
regardless.  See docs/serving.md "Speculative decoding & multi-tenant
LoRA".
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import ops
from ..distributed import serving_mesh as _srv_mesh
from ..ops import dispatch
from ..ops.pallas_kernels.ragged_paged_attention import (
    RAGGED_PLAN_FIELDS, build_ragged_plan, ragged_plan_shapes,
    ragged_write_capacity,
)
from ..telemetry import metrics as _tmetrics
from ..telemetry import trace as _ttrace
from .admission import StepWork
from .engine import (
    _NEG,
    RequestState,
    ServingEngine,
    StepStalledError,
    _count_draft_trace,
    _drop_seq_axis,
    _state_intact,
    refuse_unsupported,
)
from .paged_cache import NULL_PAGE, BlockAllocator, pages_for_tokens

__all__ = ["SpeculativeEngine"]


def _sample_with_probs(logits, temperature, top_p, top_k, do_sample,
                       generator=None):
    """Per-slot sampling over [S, V] logits returning BOTH the drawn
    token [S] and the post-filter distribution q [S, V] it was drawn
    from — the draft side of leftover resampling needs q, not just the
    token.  Greedy rows return their argmax (q rows for greedy slots are
    unused by verification — the greedy chain ignores them)."""
    if generator is None:
        from ..ops.random import default_generator as generator

    key = generator.split()

    def fn(raw, t, p, k, ds):
        raw = raw.astype(jnp.float32)
        greedy = jnp.argmax(raw, axis=-1).astype(jnp.int64)
        v = raw.shape[-1]
        scaled = raw / jnp.clip(t, 1e-6, None)[:, None]
        srt = -jnp.sort(-scaled, axis=-1)
        kk = jnp.clip(jnp.where(k > 0, k, v), 1, v).astype(jnp.int32)
        kth = jnp.take_along_axis(srt, (kk - 1)[:, None], axis=1)
        probs = jax.nn.softmax(srt, axis=-1)
        prev_mass = jnp.cumsum(probs, axis=-1) - probs
        keep = prev_mass < p[:, None]
        pth = jnp.min(jnp.where(keep, srt, jnp.float32(np.inf)),
                      axis=-1, keepdims=True)
        filt = jnp.where(scaled < jnp.maximum(kth, pth), _NEG, scaled)
        q = jax.nn.softmax(filt, axis=-1)
        g = jax.random.gumbel(key, filt.shape, jnp.float32)
        sampled = jnp.argmax(filt + g, axis=-1).astype(jnp.int64)
        return jnp.where(ds, sampled, greedy), q

    return dispatch.apply_nondiff(fn, logits, temperature, top_p, top_k,
                                  do_sample, _cacheable=False)


def _filtered_probs(lg, temperature, top_p, top_k):
    """[S, R, V] logits -> post temp/top-k/top-p filtered softmax per
    (slot, row) — the target distribution p of leftover resampling,
    vectorized over the verify rows.  Must mirror the draft-side filter
    (:func:`_sample_with_probs`) exactly."""
    v = lg.shape[-1]
    scaled = lg / jnp.clip(temperature, 1e-6, None)[:, None, None]
    srt = -jnp.sort(-scaled, axis=-1)
    kk = jnp.clip(jnp.where(top_k > 0, top_k, v), 1, v).astype(jnp.int32)
    kth = jnp.take_along_axis(
        srt, jnp.broadcast_to((kk - 1)[:, None, None],
                              (lg.shape[0], lg.shape[1], 1)), axis=2)
    probs = jax.nn.softmax(srt, axis=-1)
    prev_mass = jnp.cumsum(probs, axis=-1) - probs
    keep = prev_mass < top_p[:, None, None]
    pth = jnp.min(jnp.where(keep, srt, jnp.float32(np.inf)),
                  axis=-1, keepdims=True)
    filt = jnp.where(scaled < jnp.maximum(kth, pth), _NEG, scaled)
    return jax.nn.softmax(filt, axis=-1)


def _verify_tokens(rows_lg, drafts, n_draft, temp, top_p, top_k, do_sample,
                   qprobs=None, generator=None):
    """In-graph accept/reject over the gathered verify rows.

    rows_lg: [S, k+1, V] fp32 logits (row j = the target's distribution
    after consuming the slot's j-th verify input); drafts: [S, k] int32
    proposals; n_draft: [S] int32 valid proposals per slot (0 = plain
    decode / prefill completion); qprobs: [S, k, V] draft post-filter
    distributions (sampling only).  Returns (out_tokens [S, k+1] int64,
    n_acc [S] int32, finite [S] bool) — the host emits
    ``out_tokens[s, 0 .. n_acc[s]]`` in order (eos may truncate).

    Greedy: the longest prefix of proposals matching the target argmax
    chain; emitted tokens ARE the argmax chain.  Sampling: leftover-
    distribution resampling (module docstring) — exact target
    distribution."""
    sampling = qprobs is not None
    if sampling and generator is None:
        from ..ops.random import default_generator as generator

    key = generator.split() if sampling else None

    def fn(lg, d, nd, t, p, k, ds, *q_in):
        s, k1, v = lg.shape
        kk = k1 - 1
        lg = lg.astype(jnp.float32)
        vp = jax.lax.broadcasted_iota(jnp.int32, (s, kk), 1)
        vp1 = jax.lax.broadcasted_iota(jnp.int32, (s, k1), 1)
        live = vp < nd[:, None]                       # [S, k]
        # per-slot finiteness over the slot's OWN rows only (rows past
        # n_draft may be another slot's clamped garbage)
        row_live = vp1 <= nd[:, None]                 # [S, k+1]
        fin = jnp.where(row_live[..., None], jnp.isfinite(lg),
                        True).all(axis=(1, 2))
        g = jnp.argmax(lg, axis=-1).astype(jnp.int64)  # [S, k+1]
        d64 = d.astype(jnp.int64)
        acc_g = jnp.logical_and(d64 == g[:, :kk], live)
        pref_g = jnp.cumprod(acc_g.astype(jnp.int32), axis=1)
        n_acc_g = jnp.sum(pref_g, axis=1).astype(jnp.int32)
        if not sampling:
            return g, n_acc_g, fin
        q = jnp.stack(q_in, axis=1)                   # [S, k, V]
        # mask each slot's q rows at/past its OWN n_draft: a propose
        # iteration this slot never joined gathered its q row from flat
        # row 0 (another slot's distribution) — zeroing it makes the
        # residual at a dead position max(p - 0, 0) = p, i.e. the bonus
        # draws from the pure target row, which is exactly the nd == k
        # q_ext semantics extended to every nd < k (incl. nd = 0)
        q = jnp.where(vp[..., None] < nd[:, None, None], q, 0.0)
        pt = _filtered_probs(lg, t, p, k)             # [S, k+1, V]
        dc = jnp.clip(d, 0, v - 1)
        pd = jnp.take_along_axis(pt[:, :kk], dc[..., None],
                                 axis=2)[..., 0]      # [S, k]
        qd = jnp.take_along_axis(q, dc[..., None], axis=2)[..., 0]
        ku, kg = jax.random.split(key)
        u = jax.random.uniform(ku, (s, kk), jnp.float32)
        # accept d with prob min(1, pd/qd): u*qd < pd (qd > 0 for any
        # token the draft actually sampled)
        acc_s = jnp.logical_and(u * jnp.maximum(qd, 1e-30) < pd, live)
        pref_s = jnp.cumprod(acc_s.astype(jnp.int32), axis=1)
        n_acc_s = jnp.sum(pref_s, axis=1).astype(jnp.int32)
        # residual at the first rejected position (q_ext row k = 0, so
        # full acceptance draws the bonus from the pure target row)
        q_ext = jnp.concatenate(
            [q, jnp.zeros((s, 1, v), jnp.float32)], axis=1)
        idx = n_acc_s[:, None, None]
        p_at = jnp.take_along_axis(
            pt, jnp.broadcast_to(idx, (s, 1, v)), axis=1)[:, 0]
        q_at = jnp.take_along_axis(
            q_ext, jnp.broadcast_to(idx, (s, 1, v)), axis=1)[:, 0]
        r = jnp.maximum(p_at - q_at, 0.0)
        rs = jnp.sum(r, axis=-1, keepdims=True)
        # numerical guard: an (impossible in exact math) all-zero
        # residual falls back to the target row
        r = jnp.where(rs > 0, r, p_at)
        gmb = jax.random.gumbel(kg, (s, v), jnp.float32)
        logr = jnp.where(r > 0, jnp.log(jnp.maximum(r, 1e-38)), _NEG)
        res = jnp.argmax(logr + gmb, axis=-1).astype(jnp.int64)
        d_pad = jnp.concatenate(
            [d64, jnp.zeros((s, 1), jnp.int64)], axis=1)  # [S, k+1]
        out_s = jnp.where(vp1 < n_acc_s[:, None], d_pad, res[:, None])
        ds_b = ds[:, None]
        return (jnp.where(ds_b, out_s, g),
                jnp.where(ds, n_acc_s, n_acc_g), fin)

    args = (rows_lg, drafts, n_draft, temp, top_p, top_k, do_sample)
    if sampling:
        return dispatch.apply_nondiff(fn, *args, *qprobs, _cacheable=False)
    return dispatch.apply_nondiff(fn, *args)


class _DraftShadow:
    """The draft model's serving state, slot-aligned with the target
    engine: its OWN page pool + allocator (speculative-reservation
    discipline), host mirrors, packed transport, and retrace-free fused
    step (greedy + sampling variants — the sampling one also returns the
    post-filter distribution rows verification consumes)."""

    def __init__(self, engine: "SpeculativeEngine", draft_model):
        self.engine = engine
        self.model = draft_model
        cfg = draft_model.config
        e = engine
        if cfg.vocab_size != e.model.config.vocab_size:
            raise ValueError(
                f"draft vocab {cfg.vocab_size} != target vocab "
                f"{e.model.config.vocab_size}")
        self.page_size = e.page_size
        self.max_pages_per_slot = e.max_context // e.page_size
        self.num_pages = e.draft_num_pages
        S, k = e.num_slots, e.spec_k
        # geometry: iteration 1 may carry per slot a catch-up run of up
        # to k+1 deferred tokens plus the live input, alongside the full
        # prefill budget; iterations 2..k are one token per slot
        self.t_max = S * (k + 2) + e.prefill_token_budget
        qb = e.token_block
        self.nb_max = (S * (-(-(k + 2) // qb)) + S
                       + e.prefill_token_budget // qb)
        self.wl_max = self.nb_max * self.max_pages_per_slot
        # the plan's constant capacities (one run a slot: the write list's
        # bound is the target engine's, at this shadow's t_max)
        self._plan_geometry = dict(
            token_block=qb, t_max=self.t_max, nb_max=self.nb_max,
            wl_max=self.wl_max, write_group=e._write_group,
            wr_max=ragged_write_capacity(self.t_max, e._write_group, S))
        # host mirrors (the target scheduler's discipline, shadow copies)
        self.tables = np.full((S, self.max_pages_per_slot), NULL_PAGE,
                              np.int32)
        self.pos = np.zeros((S,), np.int64)       # committed draft tokens
        self.committed: List[List[int]] = [[] for _ in range(S)]
        self.spec: List[List[int]] = [[] for _ in range(S)]
        self.pending: List[List[int]] = [[] for _ in range(S)]
        self.allocator = BlockAllocator(self.num_pages)
        self._pack_layout = [
            ("tables", (self.t_max, self.max_pages_per_slot)),
            ("positions", (self.t_max,)),
            ("out_rows", (S,)),
            *ragged_plan_shapes(**self._plan_geometry),
        ]
        self._pack_slices = {}
        off = 0
        for name, shp in self._pack_layout:
            n = int(np.prod(shp))
            self._pack_slices[name] = (off, off + n, shp)
            off += n
        self._pack_total = off
        self.cache = None
        self.build()

    def build(self):
        """(Re)build the draft pool + compiled step closures — at init
        and after an engine rebuild (fresh Tensors so a zombie's writes
        land in orphans, exactly like the target pool)."""
        e = self.engine
        if self.cache is not None:
            self.cache.release()
        self.cache = self.model.new_paged_kv_cache(
            self.num_pages, self.page_size, dtype=e.cache_dtype)
        from ..jit.api import to_static

        model, cache, mesh = self.model, self.cache, e.mesh
        generator = e._generator
        slices = [self._pack_slices[name] for name, _ in self._pack_layout]

        def _unpack(p):
            return tuple(jnp.reshape(p[a:b], shp) for a, b, shp in slices)

        def _mk(with_sampling):
            def draft_step(ids, packed, temp, top_p, top_k, do_sample):
                _count_draft_trace()
                (tables, positions, out_rows, *plan) = \
                    dispatch.apply_nondiff(_unpack, packed)
                with _srv_mesh.activate(mesh), dispatch.no_grad():
                    logits = model._paged_lm_logits(
                        ids, cache, tables, positions,
                        ragged_plan=tuple(plan), out_rows=out_rows)
                    rows = _drop_seq_axis(logits).astype("float32")
                    if with_sampling:
                        tok, q = _sample_with_probs(rows, temp, top_p,
                                                    top_k, do_sample,
                                                    generator=generator)
                        return tok, q
                    return ops.argmax(rows, axis=-1)

            return draft_step

        self._greedy = to_static(_mk(False))
        self._sample = to_static(_mk(True))

    @property
    def static_fns(self):
        return (self._greedy, self._sample)

    # -- slot lifecycle -----------------------------------------------------
    def seat(self, idx: int):
        self.tables[idx] = NULL_PAGE
        self.pos[idx] = 0
        self.committed[idx] = []
        self.spec[idx] = []
        self.pending[idx] = []

    def retire(self, idx: int):
        """Slot retired on the target: committed pages free, speculative
        reservations roll back — the draft half of the PR 5/6 exactness
        invariant."""
        if self.committed[idx]:
            self.allocator.free(self.committed[idx])
        if self.spec[idx]:
            self.allocator.rollback_spec(self.spec[idx])
        self.committed[idx] = []
        self.spec[idx] = []
        self.pending[idx] = []
        self.tables[idx] = NULL_PAGE
        self.pos[idx] = 0

    def reset(self):
        """Recovery: every slot was retired by the engine; rebuild pool +
        programs and re-assert the drained-allocator invariant."""
        assert self.allocator.used_pages == 0, \
            f"draft rebuild leaked {self.allocator.used_pages} pages"
        assert self.allocator.spec_pages == 0, \
            f"draft rebuild leaked {self.allocator.spec_pages} spec pages"
        self.build()

    # -- paging -------------------------------------------------------------
    def ensure_pages(self, idx: int, total_tokens: int) -> bool:
        """Speculatively reserve whatever pages positions
        ``[0, total_tokens)`` need beyond the slot's current reservation.
        False (nothing changed) when the draft pool cannot serve them —
        the caller degrades instead of corrupting state."""
        need = pages_for_tokens(total_tokens, self.page_size)
        have = len(self.committed[idx]) + len(self.spec[idx])
        if need <= have:
            return True
        got = self.allocator.reserve_spec(need - have)
        if got is None:
            return False
        row = self.tables[idx]
        row[have:need] = got
        self.spec[idx].extend(got)
        return True

    def commit(self, idx: int, new_pos: int):
        """Promote the speculative reservation covering the committed
        position, roll back the rest (partial-acceptance page rollback —
        rejected speculative pages return to the free list NOW)."""
        need = pages_for_tokens(new_pos, self.page_size)
        n_commit = max(need - len(self.committed[idx]), 0)
        sp = self.spec[idx]
        keep, drop = sp[:n_commit], sp[n_commit:]
        if keep:
            self.allocator.commit_spec(keep)
            self.committed[idx].extend(keep)
        if drop:
            self.allocator.rollback_spec(drop)
        self.spec[idx] = []
        row = self.tables[idx]
        row[len(self.committed[idx]):] = NULL_PAGE
        self.pos[idx] = int(new_pos)

    # -- packed transport ---------------------------------------------------
    def build_inputs(self, runs: List[Tuple[int, np.ndarray, int]]):
        """runs: (slot, token ids, base position) per slot, at most one
        run per slot -> the draft step's (ids, packed) fixed-shape
        inputs.  Every run samples from its last row (out_rows)."""
        ids = np.zeros((self.t_max,), np.int64)
        packed = np.zeros((self._pack_total,), np.int32)

        def view(name):
            a, b, shp = self._pack_slices[name]
            return packed[a:b].reshape(shp)

        tables = view("tables")
        positions = view("positions")
        out_rows = view("out_rows")
        plan_runs = []
        t = 0
        for slot, toks, base in runs:
            c = len(toks)
            ids[t:t + c] = toks
            row = self.tables[slot]
            tables[t:t + c] = row
            positions[t:t + c] = base + np.arange(c, dtype=np.int32)
            out_rows[slot] = t + c - 1
            plan_runs.append((base, c, row))
            t += c
        plan, _stats = build_ragged_plan(
            plan_runs, page_size=self.page_size, **self._plan_geometry)
        for kf in RAGGED_PLAN_FIELDS:
            view(kf)[...] = plan[kf]
        return ids[:, None], packed


class SpeculativeEngine(ServingEngine):
    """:class:`ServingEngine` with draft-model speculative decoding.

    ``draft_model`` may be ANY model implementing the paged-cache
    contract with the same vocabulary — a small model, a truncated
    weight-sharing prefix (``models.gpt.truncated_draft``), or the
    target itself (acceptance 1.0 — the CI gate's degenerate oracle).
    ``spec_k`` proposals are drafted per decode slot per tick (clamped
    per slot so speculation never overruns ``max_new_tokens`` — page
    reservations on the TARGET pool are untouched: verify writes always
    land inside the admission reservation).  ``draft_num_pages`` sizes
    the draft pool (default: full capacity, like the target's default).

    Composes with per-request LoRA (``lora=``): adapters apply to the
    TARGET's verify step; the draft proposes adapter-less (acceptance
    drops for heavily adapted tenants, correctness never does).
    """

    def __init__(self, model, draft_model, *, spec_k: int = 4,
                 draft_num_pages: Optional[int] = None, **kw):
        if spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {spec_k}")
        for m in (model, draft_model):
            refuse_unsupported(m, speculative=True)
        self.spec_k = int(spec_k)
        # brownout actuator (serving/elastic.py "disable_speculation"
        # rung): False skips the draft phase entirely — verify runs carry
        # zero proposals (plain decode, greedy output unchanged) and the
        # shadow's skipped tokens join its catch-up backlog, drained
        # through the normal lag path when speculation re-enables
        self.speculation_enabled = True
        self._draft_model = draft_model
        self.draft: Optional[_DraftShadow] = None
        self._draft_num_pages_arg = draft_num_pages
        self._spec_last: Dict[int, dict] = {}
        super().__init__(model, **kw)
        if self._mp > 1:
            raise ValueError(
                "SpeculativeEngine shards at the REPLICA level (each dp "
                "replica may speculate); mp>1 head-sharding of the draft "
                "pool is not supported — use ShardedServingEngine(dp=N, "
                "mp=1, engine_factory=...)")
        reg = _tmetrics.registry()
        self._spec_totals = _tmetrics.CounterSet(
            "serving_spec",
            {"proposed_tokens": 0, "accepted_tokens": 0, "verify_steps": 0,
             "draft_steps": 0, "draft_skips": 0},
            labels=self._engine_label)
        # per-verify-step accepted-count histogram (ISSUE-15 satellite):
        # the acceptance-rate SHAPE, not just its mean
        self._spec_hist = reg.histogram(
            "serving_spec_accepted_per_step",
            "draft tokens accepted per slot per verify step",
        ).labels(**self._engine_label)

    # -- geometry -----------------------------------------------------------
    def _step_geometry(self):
        # bootstrap order: super().__init__ computes geometry before the
        # draft shadow exists; every decode slot may run a (k+1)-token
        # verify run while prefill runs share the budget
        k1 = self.spec_k + 1
        qb = self.token_block
        t_max = self.num_slots * k1 + self.prefill_token_budget
        nb_max = (self.num_slots * (-(-k1 // qb)) + self.num_slots
                  + self.prefill_token_budget // qb)
        return t_max, nb_max

    def _extra_pack_fields(self):
        return [("drafts", (self.num_slots, self.spec_k)),
                ("n_draft", (self.num_slots,))]

    @property
    def draft_num_pages(self) -> int:
        if self._draft_num_pages_arg is not None:
            return int(self._draft_num_pages_arg)
        return self.num_slots * (self.max_context // self.page_size) + 1

    # -- compiled programs --------------------------------------------------
    def _build_steps(self):
        """Build the VERIFY step variants (replacing the base fused step)
        and the draft shadow's programs.  The verify program gathers
        ``k+1`` rows per slot, projects only those through the LM head,
        and runs the in-graph accept/reject chain."""
        if self.draft is None:
            self.draft = _DraftShadow(self, self._draft_model)
        else:
            self.draft.build()
        model, cache = self.model, self.cache
        from ..jit.api import to_static

        slices = [self._pack_slices[name] for name, _ in self._pack_layout]

        def _unpack(p):
            return tuple(jnp.reshape(p[a:b], shp) for a, b, shp in slices)

        mesh = self.mesh
        generator = self._generator
        lora_pool = self.lora
        n_plan = len(RAGGED_PLAN_FIELDS)
        k, t_max = self.spec_k, self._t_max

        def _mk_verify(with_sampling):
            def fused_step(ids, packed, temp, top_p, top_k, do_sample,
                           *qprobs):
                from .engine import _count_fused_trace

                _count_fused_trace()
                # (the base layout's ``id_src`` names rows that continue a
                # step in flight; a verify step never has one ahead)
                (token_tables, positions, out_rows, _id_src, *rest) = \
                    dispatch.apply_nondiff(_unpack, packed)
                plan = tuple(rest[:n_plan])
                rest = rest[n_plan:]
                lora_in = None
                if lora_pool is not None:
                    lora_in = (lora_pool, rest[0])
                    rest = rest[1:]
                drafts, n_draft = rest[0], rest[1]

                def rows_fn(orow, nd):
                    r = (orow[:, None] - nd[:, None]
                         + jnp.arange(k + 1, dtype=jnp.int32)[None, :])
                    return jnp.clip(r, 0, t_max - 1).reshape(-1)

                vrows = dispatch.apply_nondiff(rows_fn, out_rows, n_draft)
                with _srv_mesh.activate(mesh), dispatch.no_grad():
                    logits = model._paged_lm_logits(ids, cache,
                                                    token_tables, positions,
                                                    ragged_plan=plan,
                                                    out_rows=vrows,
                                                    lora=lora_in)
                    rows = _drop_seq_axis(logits).astype("float32")
                    lg = dispatch.apply_nondiff(
                        lambda r: r.reshape(-1, k + 1, r.shape[-1]), rows)
                    out_tok, n_acc, fin = _verify_tokens(
                        lg, drafts, n_draft, temp, top_p, top_k, do_sample,
                        qprobs=qprobs if with_sampling else None,
                        generator=generator)
                return out_tok, n_acc, fin

            return fused_step

        self._fused_greedy = to_static(_mk_verify(False))
        self._fused_sample = to_static(_mk_verify(True))
        # cached zero q-row for propose iterations that never ran
        self._zero_q = None

    # -- lifecycle hooks ----------------------------------------------------
    def _admit(self, now):
        before = {i for i, _s in self.scheduler.seated()}
        super()._admit(now)
        for i, slot in self.scheduler.seated():
            if i not in before:
                self.draft.seat(i)
                if slot.pos:
                    # prefix-cache hit on the TARGET: the draft's own pool
                    # holds none of those positions, so the skipped prompt
                    # tokens join its catch-up backlog — the propose loop
                    # drains them through the normal lag path and resumes
                    # proposing once the draft context is rebuilt
                    self.draft.pending[i] = [
                        int(t) for t in slot.request.prompt[:slot.pos]]

    def _clear_slot_mirrors(self, idx: int):
        super()._clear_slot_mirrors(idx)
        self.draft.retire(idx)

    def _rebuild(self, release_old: bool = True):
        super()._rebuild(release_old=release_old)
        self.draft.reset()

    def _zombie_cleanup(self):
        target, draft = self.cache, self.draft.cache

        def cleanup():
            target.release()
            draft.release()

        return cleanup

    @property
    def _static_fns(self):
        return (self._fused_greedy, self._fused_sample,
                *self.draft.static_fns)

    def metrics(self) -> dict:
        out = super().metrics()
        out.update({f"spec_{k}": v for k, v in self._spec_totals.items()})
        prop = self._spec_totals["proposed_tokens"]
        out["spec_acceptance_rate"] = (
            self._spec_totals["accepted_tokens"] / prop if prop else 0.0)
        out["spec_k"] = self.spec_k
        out["spec_accepted_per_step"] = self._spec_hist.summary()
        out["draft_pages_used"] = self.draft.allocator.used_pages
        out["draft_spec_pages"] = self.draft.allocator.spec_pages
        return out

    def close(self):
        with self._lock:
            if not self._closed and self.draft is not None \
                    and self.draft.cache is not None:
                self.draft.cache.release()
        super().close()

    # -- the speculative tick ----------------------------------------------
    def _dispatch_step(self, work):
        """Draft propose phase (up to k draft dispatches) -> ONE fused
        verify dispatch -> accept/commit harvest.  Failure containment
        matches the base engine: any exception in either phase implicates
        every seated request, draft speculative pages roll back through
        slot retirement, and recovery rebuilds BOTH pools."""
        try:
            with _ttrace.span("serve.propose"):
                vwork, qprobs = self._propose(work)
            with _ttrace.span("serve.pack"):
                inputs, stats = self._build_step_inputs(vwork)
            with _ttrace.span("serve.dispatch"):
                out = self._run_verify(inputs, qprobs)
        except StepStalledError as e:
            self._recover(e, rebuild=True, stalled=True)
            return
        except Exception as e:  # noqa: BLE001 — containment boundary
            self._recover(e, rebuild=not _state_intact(e))
            return
        if out is not None:
            self._totals["fused_steps"] += 1
            self._spec_totals.inc("verify_steps")
            with _ttrace.span("serve.harvest"):
                self._harvest_verify(vwork, stats, *out)
            self._backoff_s = self.readmission_backoff_s

    def _propose(self, work):
        """Run the draft phase for one tick's plan: per decode slot,
        drain any catch-up backlog, then propose up to ``spec_k`` tokens
        (clamped to the request's remaining budget and the draft pool's
        pages).  Returns the verify work list (decode entries widened to
        ``kind='verify'`` runs carrying their proposals) and the stacked
        draft q-rows for the sampling variant."""
        sched = self.scheduler
        sampling = bool(self._do_sample.any())
        k = self.spec_k
        spec_on = self.speculation_enabled
        it1: List[Tuple[int, np.ndarray, int]] = []
        decode: List[Tuple[StepWork, int]] = []      # (work, k_s)
        live = set()
        for w in work:
            slot = sched.slots[w.slot]
            if not spec_on:
                # speculation browned out: no draft dispatch at all; the
                # committed token joins the shadow's backlog at harvest
                if w.kind == "prefill":
                    self._spec_totals.inc("draft_skips")
                    self._spec_last[w.slot] = {"prefill_ran": False}
                else:
                    self._spec_last[w.slot] = {"consumed": 0,
                                               "wrote_input": False,
                                               "n_draft": 0}
                    decode.append((w, 0))
                continue
            dpos = int(self.draft.pos[w.slot])
            if w.kind == "prefill":
                # the shadow runs the same prefill run only while it is
                # exactly in step (no backlog); otherwise the chunk joins
                # the backlog and drains through decode catch-up runs
                ran = (not self.draft.pending[w.slot] and dpos == slot.pos
                       and self.draft.ensure_pages(w.slot,
                                                   dpos + w.count))
                if ran:
                    it1.append((w.slot,
                                np.asarray(slot.pending[:w.count],
                                           np.int64), dpos))
                else:
                    self._spec_totals.inc("draft_skips")
                self._spec_last[w.slot] = {"prefill_ran": ran}
                continue
            req = slot.request
            k_s = max(0, min(k, req.max_new_tokens - len(req.tokens) - 1))
            catch = list(self.draft.pending[w.slot])
            meta = {"consumed": 0, "wrote_input": False, "n_draft": 0}
            if len(catch) > k + 1:
                # deep backlog: drain only, no proposals this tick
                run = catch[:k + 1]
                k_s = 0
                if self.draft.ensure_pages(w.slot, dpos + len(run)):
                    it1.append((w.slot, np.asarray(run, np.int64), dpos))
                    meta["consumed"] = len(run)
                else:
                    self._spec_totals.inc("draft_skips")
            else:
                run = catch + [int(self._tokens[w.slot])]
                # iteration 1 writes catch+input through position
                # slot.pos; iterations 2..k_s write proposals through
                # slot.pos + k_s - 1
                ok = self.draft.ensure_pages(w.slot,
                                             slot.pos + max(k_s, 1))
                if not ok:
                    # draft pool exhausted: degrade to the pages held
                    have = (len(self.draft.committed[w.slot])
                            + len(self.draft.spec[w.slot]))
                    room = have * self.page_size - slot.pos
                    k_s = max(0, min(k_s, int(room)))
                    ok = room >= 1
                if ok:
                    it1.append((w.slot, np.asarray(run, np.int64), dpos))
                    meta.update(consumed=len(catch), wrote_input=True)
                    if k_s >= 1:
                        live.add(w.slot)
                else:
                    self._spec_totals.inc("draft_skips")
                    k_s = 0
            self._spec_last[w.slot] = meta
            decode.append((w, k_s))
        drafts: Dict[int, List[int]] = {w.slot: [] for w, _ in decode}
        qrows: List = []
        max_k = max((ks for w, ks in decode if w.slot in live), default=0)
        if it1:
            toks, q = self._draft_dispatch(it1, sampling)
            for s in live:
                drafts[s].append(int(toks[s]))
            if sampling:
                qrows.append(q)
        # iterations 2..k: one proposal per still-speculating slot
        for j in range(2, max_k + 1):
            runs = [(w.slot,
                     np.asarray([drafts[w.slot][-1]], np.int64),
                     sched.slots[w.slot].pos + j - 1)
                    for w, ks in decode if w.slot in live and ks >= j]
            if not runs:
                break
            toks, q = self._draft_dispatch(runs, sampling)
            for s, _t, _b in runs:
                drafts[s].append(int(toks[s]))
            if sampling:
                qrows.append(q)
        # assemble the verify work list (plan order preserved)
        vwork: List[StepWork] = []
        for w in work:
            if w.kind == "prefill":
                vwork.append(w)
                continue
            props = drafts.get(w.slot, []) if w.slot in live else []
            if props:
                self._spec_totals.inc("proposed_tokens", len(props))
            self._spec_last[w.slot]["n_draft"] = len(props)
            vwork.append(StepWork(w.slot, "verify", 1 + len(props),
                                  w.base, False,
                                  drafts=np.asarray(props, np.int64),
                                  seq=w.seq))
        return vwork, (self._stack_qrows(qrows) if sampling else ())

    def _build_step_inputs(self, work):
        """Base packing (verify runs already write [t0, d1..dk] token
        ids) plus the in-graph accept/reject inputs: per-slot draft
        tokens and counts ride the same packed transport."""
        inputs, stats = super()._build_step_inputs(work)
        _ids, packed = inputs
        a, b, shp = self._pack_slices["drafts"]
        dv = packed[a:b].reshape(shp)
        a, b, shp = self._pack_slices["n_draft"]
        nv = packed[a:b].reshape(shp)
        for w in work:
            if w.kind == "verify" and w.drafts is not None:
                n = len(w.drafts)
                if n:
                    dv[w.slot, :n] = w.drafts
                nv[w.slot] = n
        return inputs, stats

    def _stack_qrows(self, qrows):
        """Pad the per-iteration draft q-rows to exactly ``spec_k``
        device arrays (fixed verify-program arity); missing iterations
        ride a cached zero row."""
        if self._zero_q is None:
            from ..tensor import to_tensor

            self._zero_q = to_tensor(np.zeros(
                (self.num_slots, self.model.config.vocab_size),
                np.float32))
        out = list(qrows[:self.spec_k])
        while len(out) < self.spec_k:
            out.append(self._zero_q)
        return tuple(out)

    def _draft_dispatch(self, runs, sampling):
        """One supervised draft-step dispatch over ``runs``; returns the
        sampled tokens (host) and, under sampling, the post-filter q rows
        (LEFT ON DEVICE — they feed the verify program directly)."""
        ids, packed = self.draft.build_inputs(runs)
        fn = self.draft._sample if sampling else self.draft._greedy
        budget = self._budget_for([fn])

        def thunk(cancelled):
            with _ttrace.span("serve.draft_step"):
                if cancelled():
                    return None
                cache = self._sampling_cache
                built = None
                if cache is None:
                    built = cache = (
                        self._host_to_dev(self._temp.copy()),
                        self._host_to_dev(self._top_p.copy()),
                        self._host_to_dev(self._top_k.copy()),
                        self._host_to_dev(self._do_sample.copy()))
                out = fn(self._host_to_dev(np.ascontiguousarray(ids)),
                         self._host_to_dev(np.ascontiguousarray(packed)),
                         *cache)
                if sampling:
                    tok, q = out
                else:
                    tok, q = out, None
                return np.asarray(tok.numpy()), q, built

        tok, q, built = self._supervised(thunk, budget)
        if built is not None:
            self._sampling_cache = built
        self._spec_totals.inc("draft_steps")
        return tok, q

    def _run_verify(self, inputs, qprobs):
        """The verify dispatch, enqueued AND read in one supervised unit
        (watchdog + one retry, as the base engine's enqueue) with the draft
        q-rows appended for the sampling variant."""
        sampling = bool(self._do_sample.any())
        fused = self._fused_sample if sampling else self._fused_greedy
        budget = self._budget_for([fused])
        extra = qprobs if sampling else ()

        def thunk(cancelled):
            # dispatch AND read: the accepted counts decide the next
            # step's positions, so nothing is enqueued behind a verify step
            with _ttrace.span("serve.device_step"):
                got = self._enqueue_thunk(fused, inputs, cancelled, extra)
                if got is None:
                    return None
                (out_tok, n_acc, fin), built = got
                return (np.asarray(out_tok.numpy()),
                        np.array(np.asarray(fin.numpy()), bool), built,
                        np.asarray(n_acc.numpy()))

        try:
            toks, fin, built, n_acc = self._supervised(thunk, budget)
        except StepStalledError:
            raise
        except Exception:  # noqa: BLE001 — transient device errors retry once
            self._totals["step_retries"] += 1
            toks, fin, built, n_acc = self._supervised(thunk, budget)
        if built is not None:
            self._sampling_cache = built
        return toks, n_acc, fin

    def _harvest_verify(self, work, stats, toks_np, n_acc_np, fin_np):
        """Commit one verify step: per slot, emit the accepted prefix +
        bonus (eos may truncate it), ``advance`` by what was emitted,
        and square the draft shadow's position/pages/pending against the
        commit — rejected draft pages roll back here."""
        import time as _time

        ctx = {"tokens": toks_np, "finite": fin_np, "n_acc": n_acc_np}
        self._hook("after_decode", ctx)
        sched = self.scheduler
        self._fold_plan_stats(work, stats)
        step_now = _time.monotonic()
        for w in work:
            slot = sched.slots[w.slot]
            if slot is None:
                continue
            if w.kind == "prefill":
                consumed = slot.pending[:w.count]
                slot.pending = slot.pending[w.count:]
                meta = self._spec_last.pop(w.slot, {})
                if meta.get("prefill_ran"):
                    self.draft.commit(w.slot,
                                      int(self.draft.pos[w.slot]) + w.count)
                else:
                    # shadow skipped this chunk: it joins the backlog and
                    # drains through decode catch-up runs
                    self.draft.pending[w.slot].extend(
                        int(t) for t in consumed)
                if w.completes and not ctx["finite"][w.slot]:
                    self._totals["quarantined"] += 1
                    self._fail_slot(w.slot, _nan_err(slot, w))
                    continue
                sched.advance(w.slot, w.count)
                self._register_shared(w.slot)
                if not w.completes:
                    continue
                req = slot.request
                tok = int(ctx["tokens"][w.slot][0])
                req.state = RequestState.DECODE
                self._tokens[w.slot] = tok
                self._emit(req, tok, now=step_now)
                if self._is_finished(req, tok):
                    self._finish(w.slot)
                continue
            # verify runs
            meta = self._spec_last.pop(w.slot, {"consumed": 0,
                                                "wrote_input": False,
                                                "n_draft": 0})
            nd = int(meta.get("n_draft", 0))
            if not ctx["finite"][w.slot]:
                self._totals["quarantined"] += 1
                self._fail_slot(w.slot, _nan_err(slot, w))
                continue
            n_acc = min(int(ctx["n_acc"][w.slot]), nd)
            self._spec_totals.inc("accepted_tokens", n_acc)
            self._spec_hist.observe(float(n_acc))
            cand = [int(t) for t in ctx["tokens"][w.slot][:n_acc + 1]]
            req = slot.request
            n_emit = 0
            finished = False
            for tok in cand:
                self._emit(req, tok, now=step_now)
                n_emit += 1
                if self._is_finished(req, tok):
                    finished = True
                    break
            old_pos = slot.pos
            sched.advance(w.slot, n_emit)
            # pages the commit just completed become shareable — verify
            # writes only ever land at positions >= the committed pos, so
            # a completed page is immutable even under rejected drafts
            self._register_shared(w.slot)
            # draft shadow bookkeeping: which of the committed inputs
            # ([t0, d1..d_{n_emit-1}]) did the draft write this tick?
            seq = ([int(self._tokens[w.slot])]
                   + [int(d) for d in w.drafts[:n_emit - 1]])
            if meta["wrote_input"]:
                have = min(n_emit, max(nd, 1))
            else:
                have = 0
            consumed = meta["consumed"]
            new_dpos = int(self.draft.pos[w.slot]) + consumed + have
            self.draft.pending[w.slot] = \
                self.draft.pending[w.slot][consumed:] + seq[have:]
            self.draft.commit(w.slot, new_dpos)
            self._tokens[w.slot] = cand[n_emit - 1]
            if finished:
                self._finish(w.slot)

def _nan_err(slot, w):
    from .engine import NaNLogitsError

    return NaNLogitsError(
        f"request {slot.request.id}: non-finite logits in verify run "
        f"(slot {w.slot} quarantined)")
