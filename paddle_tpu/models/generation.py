"""Autoregressive decode engine: KV cache + retrace-free generate().

Reference analog: PaddleNLP's ``GenerationMixin`` (greedy/sampling search
over a decoder with cache) and the reference's fused_multi_transformer
decode path.  TPU-native redesign:

- **Static shapes everywhere.**  The KV cache is preallocated at
  ``[L, B, H, max_seq, D]`` (bf16 by default) and written position-by-
  position with ``jax.lax.dynamic_update_slice``; the *position* is a
  traced scalar, never a shape.  One prefill program (keyed on the prompt
  shape) and ONE decode program serve the whole generation loop — after
  warmup there are **zero retraces** no matter how many tokens are
  generated.
- **Donated cache.**  Both steps run through ``jit.to_static``, whose
  scout classifies the cache tensors (and the RNG key under sampling) as
  mutated captured state and donates them to XLA — each decode step
  aliases the cache update into the same HBM buffers, so generation
  holds ONE cache copy regardless of length (flat
  ``paddle_tpu.core.memory`` peak across steps).
- **q-len-1 attention kernel.**  Decode attention routes to the Pallas
  flash-decode kernel (``ops/pallas_kernels/decode_attention.py``) on
  TPU-eligible shapes, with the jnp-composed expression as fallback.
- Sampling (greedy / temperature / top-k / top-p) composes from
  ``ops/search`` + ``ops/random`` at Tensor level, so it traces into the
  same compiled step; temperature and top-p ride as traced scalars (one
  compiled program serves every setting), while top-k is static.

Model contract: a model mixes in :class:`GenerationMixin` and implements
``new_kv_cache(batch_size, max_seq, dtype)`` plus
``_cached_lm_logits(input_ids, kv_cache, cache_index) -> [B, S, V]``
(which must write the step's K/V into the cache in place).
"""
from __future__ import annotations

import threading
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import ops
from ..core.dtype import to_jax_dtype
from ..nn import functional as F
from ..ops import dispatch
from ..tensor import Tensor, to_tensor

__all__ = [
    "KVCache",
    "GenerationMixin",
    "filter_logits",
    "sample_tokens",
    "generate",
    "trace_counts",
    "reset_trace_counts",
]


class _KVBuffers:
    """Shared buffer bookkeeping for KV caches exposing ``k``/``v``: size
    accounting and eager release.  Used by both the contiguous
    :class:`KVCache` and the serving page pool
    (``serving.paged_cache.PagedKVCache``) so release semantics cannot
    drift between them."""

    def _tensors(self) -> List[Tensor]:
        return [self.k, self.v]

    @property
    def nbytes(self) -> int:
        return sum(int(np.prod(t._value.shape)) * t._value.dtype.itemsize
                   for t in self._tensors())

    def release(self):
        """Delete the cache's device buffers NOW.  Dropping the python
        refs leaves HBM release to GC timing — and compiled step closures
        keep the Tensors alive anyway; jax's ``Array.delete()`` frees the
        buffers eagerly.  The cache is unusable afterwards."""
        for t in self._tensors():
            self._delete(t)

    @staticmethod
    def _delete(t: Tensor):
        delete = getattr(t._value, "delete", None)
        if delete is not None:
            try:
                delete()
            except Exception:  # noqa: BLE001 — already deleted/donated
                pass


class KVCache(_KVBuffers):
    """Preallocated static-shape KV cache: one Tensor pair ``k``/``v`` of
    shape ``[L, B, H, max_seq, D]``, scanned alongside the stacked decoder
    parameters (``GPTStackedDecoder._forward_cached``).

    The tensors are plain framework Tensors so in-place updates
    (``_set_value``) are mutation-logged — ``jit.to_static`` donates them
    and the compiled decode step aliases the update into the same HBM.
    Stale content past the current length is never read (every read is
    length-masked), so a cache can be reused across generate() calls
    without re-zeroing.
    """

    def __init__(self, num_layers: int, batch_size: int, num_heads: int,
                 max_seq: int, head_dim: int, dtype: str = "bfloat16"):
        jd = to_jax_dtype(dtype)
        self.num_layers = num_layers
        self.batch_size = batch_size
        self.num_heads = num_heads
        self.max_seq = max_seq
        self.head_dim = head_dim
        self.dtype = str(dtype)
        shape = (num_layers, batch_size, num_heads, max_seq, head_dim)
        self.k = Tensor(jnp.zeros(shape, jd))
        self.v = Tensor(jnp.zeros(shape, jd))


# ---------------------------------------------------------------------------
# sampling (ops/search + ops/random at Tensor level — traces into the step)
# ---------------------------------------------------------------------------

_NEG = -1e30


def filter_logits(logits: Tensor, top_k: int = 0,
                  top_p: Optional[Tensor] = None) -> Tensor:
    """Top-k / nucleus (top-p) logit filtering over ``[B, V]``.

    ``top_k`` is static (changes the compiled graph); ``top_p`` is a
    traced scalar Tensor in (0, 1].  Filtered positions get -1e30 so the
    downstream softmax renormalizes over the kept set.  Top-p keeps the
    smallest prefix of the probability-sorted vocab whose mass reaches
    ``top_p`` (always at least the argmax token).
    """
    vocab = logits.shape[-1]
    if top_k and top_k > 0 and top_k < vocab:
        vals, _ = ops.topk(logits, top_k, axis=-1)
        kth = vals[:, -1:]                                   # [B, 1]
        logits = ops.where(logits < kth,
                           ops.full_like(logits, _NEG), logits)
    if top_p is not None:
        sorted_l = ops.sort(logits, axis=-1, descending=True)
        probs = F.softmax(sorted_l, axis=-1)
        # mass strictly above each rank; rank kept iff that mass < top_p
        prev_mass = ops.cumsum(probs, axis=-1) - probs
        keep = prev_mass < top_p
        thresh = ops.min(
            ops.where(keep, sorted_l, ops.full_like(sorted_l, -_NEG)),
            axis=-1, keepdim=True)
        logits = ops.where(logits < thresh,
                           ops.full_like(logits, _NEG), logits)
    return logits


def sample_tokens(logits: Tensor, *, do_sample: bool,
                  temperature: Optional[Tensor] = None, top_k: int = 0,
                  top_p: Optional[Tensor] = None) -> Tensor:
    """Next-token selection over ``[B, V]`` logits -> int64 ``[B]``.

    Greedy is a pure argmax; sampling applies temperature then top-k/
    top-p filtering and draws via the Gumbel-argmax trick with a key
    split from the global generator (the generator state functionalizes
    under jit.to_static, so compiled sampling stays reproducible)."""
    if not do_sample:
        return ops.argmax(logits, axis=-1)
    if temperature is not None:
        logits = logits / temperature
    logits = filter_logits(logits, top_k=top_k, top_p=top_p)
    from ..ops.random import default_generator

    key = default_generator.split()

    def fn(raw):
        g = jax.random.gumbel(key, raw.shape, jnp.float32)
        return jnp.argmax(raw.astype(jnp.float32) + g,
                          axis=-1).astype(jnp.int64)

    # fresh key closure every call: opt out of the eager op cache
    return dispatch.apply_nondiff(fn, logits, _cacheable=False)


# ---------------------------------------------------------------------------
# the two-program decode engine
# ---------------------------------------------------------------------------

# python-body execution counters: the step bodies run ONLY while tracing
# (abstract scout + jit trace — twice per compile), never on cached
# compiled calls.  Tests assert these stay frozen across N decode steps:
# the retrace-freedom invariant.
_TRACE_COUNTS = {"prefill": 0, "decode": 0}


def trace_counts() -> dict:
    return dict(_TRACE_COUNTS)


def reset_trace_counts():
    _TRACE_COUNTS["prefill"] = 0
    _TRACE_COUNTS["decode"] = 0


class _DecodeEngine:
    """One (prefill, decode) compiled-step pair bound to a model + cache.

    Cached on the model per (batch, max_seq, cache dtype, sampling
    topology) — repeated generate() calls reuse the compiled programs AND
    the cache HBM."""

    def __init__(self, model, cache: KVCache, *, do_sample: bool,
                 top_k: int, use_top_p: bool):
        from ..jit.api import to_static

        self.cache = cache
        self.do_sample = do_sample
        self.top_k = top_k
        self.use_top_p = use_top_p
        # one generate() at a time per engine: the compiled steps mutate
        # the SHARED cache, so concurrent callers (PredictorPool threads)
        # must serialize per engine — distinct engines run concurrently.
        # `released` flips under the lock when eviction deletes the cache
        # buffers; a caller that raced the eviction (engine looked up, lock
        # not yet taken) sees it and fetches a fresh engine instead of
        # dispatching into deleted arrays.
        self.lock = threading.RLock()
        self.released = False

        def prefill_step(ids, temperature, top_p):
            _TRACE_COUNTS["prefill"] += 1
            with dispatch.no_grad():
                logits = model._cached_lm_logits(ids, cache, 0)
                last = logits[:, -1, :].astype("float32")      # [B, V]
                tok = sample_tokens(
                    last, do_sample=do_sample,
                    temperature=temperature if do_sample else None,
                    top_k=top_k, top_p=top_p if use_top_p else None)
            return tok, last

        def decode_step(tok, pos, temperature, top_p):
            _TRACE_COUNTS["decode"] += 1
            with dispatch.no_grad():
                ids = ops.reshape(tok, [-1, 1])                # [B, 1]
                logits = model._cached_lm_logits(ids, cache, pos)
                last = logits[:, -1, :].astype("float32")
                nxt = sample_tokens(
                    last, do_sample=do_sample,
                    temperature=temperature if do_sample else None,
                    top_k=top_k, top_p=top_p if use_top_p else None)
            return nxt, pos + 1, last

        self.prefill = to_static(prefill_step)
        self.decode = to_static(decode_step)

    @property
    def compiled_programs(self) -> int:
        """Distinct compiled programs behind this engine (prefill entries
        are per prompt shape; decode is always exactly one)."""
        return len(self.prefill.code_cache) + len(self.decode.code_cache)

    def lint_reports(self):
        """Graph-lint reports of every compiled prefill/decode program
        (populated when FLAGS_graph_lint / PADDLE_TPU_GRAPH_LINT=1 was on
        at compile time; see docs/graph_lint.md)."""
        return self.prefill.lint_reports() + self.decode.lint_reports()

    def release(self):
        """Free the engine's KV-cache HBM eagerly (LRU eviction /
        clear_decode_cache): the compiled step closures pin the cache
        Tensors, so without an explicit ``delete()`` the buffers wait on
        GC.  Taking ``self.lock`` first means an in-flight generate() on
        this engine finishes its loop before the buffers vanish under it
        (the evictor blocks, it does not corrupt); ``released`` tells a
        caller that looked the engine up just before the eviction to
        retry with a fresh one."""
        with self.lock:
            self.cache.release()
            self.released = True


# each cached engine pins a full KV cache in HBM; bound how many distinct
# (batch, max_seq, dtype, sampling-topology) combinations stay resident
_MAX_ENGINES = 4


def _engine_for(model, batch: int, max_seq: int, cache_dtype: str, *,
                do_sample: bool, top_k: int, use_top_p: bool) -> _DecodeEngine:
    # model.__dict__ directly: Layer.__setattr__ must not see cache Tensors
    # (they are serving state, not parameters/buffers).  dict.setdefault is
    # atomic, so concurrent first calls agree on one lock/registry.
    lock = model.__dict__.setdefault("_decode_engines_lock",
                                     threading.Lock())
    with lock:
        engines = model.__dict__.setdefault("_decode_engines", {})
        key = (batch, max_seq, str(cache_dtype), bool(do_sample), int(top_k),
               bool(use_top_p))
        eng = engines.pop(key, None)
        if eng is not None and eng.released:
            eng = None        # buffers already deleted: build a fresh one
        if eng is None:
            while len(engines) >= _MAX_ENGINES:
                # LRU: dict order is move-to-back-on-use; evicting the
                # engine deletes its cache buffers explicitly (the compiled
                # step closures would otherwise pin them until GC)
                old_key = next(iter(engines))
                engines.pop(old_key).release()
            cache = model.new_kv_cache(batch, max_seq, dtype=cache_dtype)
            eng = _DecodeEngine(model, cache, do_sample=do_sample,
                                top_k=top_k, use_top_p=use_top_p)
        engines[key] = eng  # (re)insert at the back = most recently used
        return eng


def generate(model, input_ids, max_new_tokens: int = 32, *,
             do_sample: bool = False, temperature: float = 1.0,
             top_k: int = 0, top_p: Optional[float] = None,
             eos_token_id: Optional[int] = None,
             max_seq_len: Optional[int] = None,
             cache_dtype: str = "bfloat16", return_logits: bool = False):
    """Autoregressive generation from ``input_ids`` ``[B, S0]`` (int64).

    Returns ``[B, S0 + max_new_tokens]`` token ids (prompt included), or
    ``(ids, logits)`` with ``logits`` ``[B, max_new_tokens, V]`` fp32 (the
    pre-sampling logits of each generated position) when
    ``return_logits=True``.

    Without ``eos_token_id`` the loop is fully asynchronous — N compiled
    step dispatches with no host sync until the result is read.  With it,
    each step syncs the token back to decide early stop; rows keep their
    first ``eos_token_id`` and are padded with it afterwards.  Note that
    under ``return_logits`` positions at/after a row's first eos carry the
    distribution conditioned on the raw sampled continuation (the id
    padding is applied afterwards, host-side); combining it with
    ``eos_token_id`` also disables the all-rows-done early stop so every
    logits row is real.
    """
    ids = to_tensor(input_ids, dtype="int64") if not isinstance(
        input_ids, Tensor) else input_ids
    b, s0 = int(ids.shape[0]), int(ids.shape[1])
    cfg = model.config
    max_seq = int(max_seq_len or cfg.max_position_embeddings)
    if max_seq > cfg.max_position_embeddings:
        raise ValueError(
            f"max_seq_len={max_seq} exceeds max_position_embeddings="
            f"{cfg.max_position_embeddings}")
    if s0 + max_new_tokens > max_seq:
        raise ValueError(
            f"prompt ({s0}) + max_new_tokens ({max_new_tokens}) exceeds the "
            f"cache length {max_seq}; raise max_seq_len (<= "
            f"max_position_embeddings) or shorten the request")
    if max_new_tokens < 1:
        raise ValueError("max_new_tokens must be >= 1")
    if do_sample and not float(temperature) > 0.0:
        raise ValueError("temperature must be > 0 when do_sample=True")

    use_top_p = do_sample and top_p is not None
    temp_t = to_tensor(np.float32(temperature))
    top_p_t = to_tensor(np.float32(top_p if top_p is not None else 1.0))

    # generation is an eval-time graph: dropout must not trace in.
    # eng.lock: the compiled steps mutate the engine's shared cache, so a
    # second thread on the same request shape serializes here instead of
    # interleaving decode steps through one cache (PredictorPool threads).
    # The retry loop closes the lookup->lock window: an engine evicted in
    # between flips `released` under its lock, and we fetch a fresh one
    # instead of dispatching into deleted cache buffers.
    while True:
        eng = _engine_for(model, b, max_seq, cache_dtype,
                          do_sample=do_sample, top_k=int(top_k or 0),
                          use_top_p=use_top_p)
        with eng.lock:
            if eng.released:
                continue
            was_training = model.training
            if was_training:
                model.eval()
            try:
                tok, last = eng.prefill(ids, temp_t, top_p_t)
                toks: List[Tensor] = [tok]
                logit_steps: List[Tensor] = [last] if return_logits else []
                pos = to_tensor(np.int32(s0))
                done = None
                if eos_token_id is not None:
                    done = np.asarray(tok.numpy()) == eos_token_id
                for _ in range(max_new_tokens - 1):
                    if done is not None and bool(done.all()) \
                            and not return_logits:
                        # every row finished: pad the remaining steps
                        # host-side instead of decoding.  (With
                        # return_logits the loop keeps decoding so every
                        # returned row is a REAL model distribution —
                        # zero-padded rows would silently read as uniform
                        # to a perplexity/logprob consumer.)
                        toks.append(ops.full_like(tok, eos_token_id))
                        continue
                    tok, pos, last = eng.decode(tok, pos, temp_t, top_p_t)
                    toks.append(tok)
                    if return_logits:
                        logit_steps.append(last)
                    if done is not None:
                        done = done | (np.asarray(tok.numpy())
                                       == eos_token_id)
            finally:
                if was_training:
                    model.train()
            break

    gen = ops.stack(toks, axis=1)                               # [B, N]
    if eos_token_id is not None:
        # freeze every row at its first eos: positions after it become eos
        g = np.asarray(gen.numpy())
        hit = np.cumsum(g == eos_token_id, axis=1) > 0
        after = np.zeros_like(hit)
        after[:, 1:] = hit[:, :-1]
        g = np.where(after, eos_token_id, g)
        gen = to_tensor(g, dtype="int64")
    out = ops.concat([ids, gen], axis=1)
    if return_logits:
        return out, ops.stack(logit_steps, axis=1)              # [B, N, V]
    return out


class GenerationMixin:
    """Adds ``generate()`` to a causal LM exposing the cache contract
    (``new_kv_cache`` + ``_cached_lm_logits``).

    Engines (compiled prefill/decode pair + their KV-cache HBM) are cached
    per request shape, LRU-bounded at ``_MAX_ENGINES``; call
    :meth:`clear_decode_cache` to release them all eagerly (e.g. before
    resuming training on a memory-tight chip)."""

    def generate(self, input_ids, max_new_tokens: int = 32, **kwargs):
        return generate(self, input_ids, max_new_tokens, **kwargs)

    def clear_decode_cache(self):
        """Drop every cached decode engine AND delete its KV-cache device
        buffers eagerly (the compiled step closures would otherwise pin
        the HBM until GC collects the whole engine graph)."""
        lock = self.__dict__.get("_decode_engines_lock")
        engines = (self.__dict__.pop("_decode_engines", None)
                   if lock is None else None)
        if lock is not None:
            with lock:
                engines = self.__dict__.pop("_decode_engines", None)
        for eng in (engines or {}).values():
            eng.release()
