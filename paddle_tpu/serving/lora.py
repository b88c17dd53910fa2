"""Paged per-request LoRA adapters for multi-tenant serving.

One compiled fused step serves MANY fine-tuned tenants: every registered
adapter's low-rank factors live in paged device SLABS — per target matrix
``m`` with base weight ``W_m`` of ``[in, out]``, an A-slab
``[num_adapter_pages, in, r]`` and a B-slab ``[num_adapter_pages, r, out]``
— and each step token carries the int32 adapter-PAGE id of its request.
Inside the step every projection computes

    W_m @ x  +  scaling * B_m[page] @ (A_m[page] @ x)

via the gathered low-rank matmul (``ops/lora.py``), so the compiled
program never changes as tenants come and go: registration writes factor
weights into a free page IN PLACE (the slab Tensors are captured step
state, exactly like the KV pool), eviction frees the page — zero
retraces, asserted by the usual ``serve_trace_counts``.

Allocator discipline is the KV-pool's, verbatim: the slabs are fronted by
the same :class:`~paddle_tpu.serving.paged_cache.BlockAllocator`
(page 0 = the NULL adapter, all-zero factors — tokens of adapter-less
requests flow through the same program with a zero delta), registration
allocates all-or-nothing, and the page-accounting invariant (free + used
== capacity, no double free) holds through register/evict churn.  A
tenant SEATED in a decode slot pins its page via a refcount: evicting it
raises the typed :class:`AdapterInUse` instead of silently decoding with
a recycled page's weights — no silent wrong-adapter decode.

Target matrices (``GPTStackedDecoder``): ``qkv_w``, ``proj_w``,
``fc1_w``, ``fc2_w``.  The slabs are the 8-tuple
``(qkv_A, qkv_B, proj_A, proj_B, fc1_A, fc1_B, fc2_A, fc2_B)`` of
``[L, pages, dim, r]`` Tensors, which the decoder scans alongside its
stacked parameters.  See docs/serving.md "Speculative decoding & multi-tenant
LoRA" for sizing (slab bytes = 2 * r * (4h + 3h + f + f + h + h) * L *
pages * itemsize with the default targets).
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..core.dtype import to_jax_dtype
from ..tensor import Tensor
from .engine import ServingError
from .paged_cache import BlockAllocator

__all__ = ["LoRAAdapterPool", "AdapterError", "AdapterInUse",
           "UnknownAdapter", "random_adapter"]

# the per-layer slab order consumed by models/gpt.py (A then B per matrix)
TARGETS = ("qkv", "out_proj", "fc1", "fc2")
NULL_ADAPTER = 0


class AdapterError(ServingError):
    """Base of the typed LoRA adapter faults."""


class AdapterInUse(AdapterError):
    """Eviction refused: the adapter is pinned by seated request(s).
    Evicting under a live tenant would hand its page to the next
    registration and silently decode with the WRONG adapter."""


class UnknownAdapter(AdapterError):
    """The request names an adapter the pool has never seen (or one that
    was evicted before the request seated)."""


def _matrix_dims(cfg) -> Dict[str, Tuple[int, int]]:
    h, f = cfg.hidden_size, cfg.ffn_size
    return {"qkv": (h, 3 * h), "out_proj": (h, h),
            "fc1": (h, f), "fc2": (f, h)}


def random_adapter(cfg, rank: int, rng: np.random.RandomState,
                   scale: float = 0.02) -> Dict[str, list]:
    """A random adapter weight set for tests/benches: per target matrix, a
    list of ``num_layers`` ``(A [in, r], B [r, out])`` float32 pairs.
    B is NOT zero-initialized (unlike training-time LoRA) so the delta is
    visibly nonzero in parity tests."""
    dims = _matrix_dims(cfg)
    return {
        m: [(rng.randn(din, rank).astype(np.float32) * scale,
             rng.randn(rank, dout).astype(np.float32) * scale)
            for _ in range(cfg.num_layers)]
        for m, (din, dout) in dims.items()
    }


class LoRAAdapterPool:
    """Paged adapter slab pool for one model configuration.

    ``num_adapter_pages`` counts REGISTRABLE adapters (the null page is
    extra, allocator-style); ``rank`` is fixed per pool (one compiled
    step — a mixed-rank fleet runs one pool per rank bucket); ``alpha``
    defaults to ``rank`` (scaling = alpha / rank = 1.0).  The slabs are
    ``[L, P, dim, r]``: the layer axis leads, as in the decoder's
    parameters, so the serving scan slices both alike."""

    def __init__(self, cfg, *, num_adapter_pages: int = 8, rank: int = 4,
                 alpha: Optional[float] = None, dtype: str = "float32"):
        if rank < 1:
            raise ValueError(f"rank must be >= 1, got {rank}")
        if num_adapter_pages < 1:
            raise ValueError("num_adapter_pages must be >= 1")
        self.cfg = cfg
        self.rank = int(rank)
        self.alpha = float(alpha if alpha is not None else rank)
        self.scaling = self.alpha / self.rank
        self.dtype = str(dtype)
        self.num_pages = int(num_adapter_pages) + 1      # + null page
        self.allocator = BlockAllocator(self.num_pages)
        self._lock = threading.Lock()
        # name -> (page, refcount)
        self._adapters: Dict[str, List[int]] = {}
        jd = to_jax_dtype(dtype)
        L, P, r = cfg.num_layers, self.num_pages, self.rank
        dims = _matrix_dims(cfg)
        self._slabs: Dict[str, Tuple[Tensor, Tensor]] = {}
        for m in TARGETS:
            din, dout = dims[m]
            self._slabs[m] = (Tensor(jnp.zeros((L, P, din, r), jd)),
                              Tensor(jnp.zeros((L, P, r, dout), jd)))

    # -- slab view (models/gpt.py contract) --------------------------------
    def stacked_slabs(self):
        """8-tuple of ``[L, P, dim, r]`` slab Tensors, scanned alongside
        the stacked decoder parameters."""
        return tuple(t for m in TARGETS for t in self._slabs[m])

    @property
    def nbytes(self) -> int:
        return sum(int(np.prod(t._value.shape)) * t._value.dtype.itemsize
                   for pair in self._slabs.values() for t in pair)

    # -- registration / eviction -------------------------------------------
    def register(self, name: str, weights: Dict[str, list]) -> int:
        """Write an adapter's factors into a free page and return the page
        id.  ``weights``: per target matrix, ``num_layers`` ``(A, B)``
        pairs (:func:`random_adapter` shape).  All-or-nothing: a full pool
        raises the typed :class:`AdapterError` (evict somebody first) —
        the registration analog of admission backpressure.  Runtime
        registration never retraces the step: the write is an in-place
        slab update."""
        with self._lock:
            if name in self._adapters:
                raise AdapterError(f"adapter {name!r} is already registered")
            missing = [m for m in TARGETS if m not in weights]
            if missing:
                raise AdapterError(
                    f"adapter {name!r}: missing target matrices {missing}")
            pages = self.allocator.alloc(1)
            if pages is None:
                raise AdapterError(
                    f"adapter pool full ({self.allocator.capacity} pages): "
                    f"evict an adapter before registering {name!r}")
            page = pages[0]
            try:
                self._write_page(page, weights)
            except Exception:
                self.allocator.free([page])
                raise
            self._adapters[name] = [page, 0]
            return page

    def _write_page(self, page: int, weights: Dict[str, list]):
        L, r = self.cfg.num_layers, self.rank
        dims = _matrix_dims(self.cfg)
        for m in TARGETS:
            pairs = weights[m]
            if len(pairs) != L:
                raise AdapterError(
                    f"target {m!r}: expected {L} layer pairs, got "
                    f"{len(pairs)}")
            din, dout = dims[m]
            a_np = np.stack([np.asarray(a, np.float32) for a, _ in pairs])
            b_np = np.stack([np.asarray(b, np.float32) for _, b in pairs])
            if a_np.shape != (L, din, r) or b_np.shape != (L, r, dout):
                raise AdapterError(
                    f"target {m!r}: A/B shapes {a_np.shape}/{b_np.shape} "
                    f"!= expected {(L, din, r)}/{(L, r, dout)} "
                    f"(rank {r} pool)")
            at, bt = self._slabs[m]
            jd = at._value.dtype
            at._set_value(at._value.at[:, page].set(jnp.asarray(a_np, jd)))
            bt._set_value(bt._value.at[:, page].set(jnp.asarray(b_np, jd)))

    def evict(self, name: str):
        """Free the adapter's page.  Typed :class:`AdapterInUse` while any
        seated request pins it; the page's stale weights are unreachable
        once freed (no token can carry a freed page id — submission
        resolves names under the lock) and are overwritten wholesale by
        the next registration that reuses the page."""
        with self._lock:
            ent = self._adapters.get(name)
            if ent is None:
                raise UnknownAdapter(f"adapter {name!r} is not registered")
            page, refs = ent
            if refs > 0:
                raise AdapterInUse(
                    f"adapter {name!r} (page {page}) is pinned by {refs} "
                    "seated request(s); drain or cancel them first")
            del self._adapters[name]
            self.allocator.free([page])

    # -- seating refcounts (engine integration) ----------------------------
    def acquire(self, name: str) -> int:
        """Pin ``name`` for one seated request -> its page id.  Typed
        :class:`UnknownAdapter` when the name is unknown (e.g. evicted
        while the request was queued) — the engine fails that request
        instead of decoding with the null adapter silently."""
        with self._lock:
            ent = self._adapters.get(name)
            if ent is None:
                raise UnknownAdapter(
                    f"adapter {name!r} is not registered (evicted while "
                    "the request was queued?)")
            ent[1] += 1
            return ent[0]

    def release(self, name: str):
        with self._lock:
            ent = self._adapters.get(name)
            if ent is None:          # evicted concurrently is impossible
                return               # (refcount pins) — tolerate anyway
            ent[1] = max(ent[1] - 1, 0)

    def refcount(self, name: str) -> int:
        with self._lock:
            ent = self._adapters.get(name)
            return 0 if ent is None else ent[1]

    def adapters(self) -> Dict[str, int]:
        """name -> page id snapshot."""
        with self._lock:
            return {k: v[0] for k, v in self._adapters.items()}

    def merged_state_dict(self, model, name: str) -> dict:
        """Offline reference: the model's state_dict with this adapter's
        delta MERGED into the dense weights (``W + scaling * A @ B``) —
        the oracle the multi-tenant parity tests compare against."""
        with self._lock:
            ent = self._adapters.get(name)
            if ent is None:
                raise UnknownAdapter(f"adapter {name!r} is not registered")
            page = ent[0]
        sd = {k: np.asarray(v.numpy()) for k, v in model.state_dict().items()}
        names = {"qkv": "decoder.qkv_w", "out_proj": "decoder.proj_w",
                 "fc1": "decoder.fc1_w", "fc2": "decoder.fc2_w"}
        for m in TARGETS:
            at, bt = self._slabs[m]
            a = np.asarray(at._value[:, page], np.float32)
            b = np.asarray(bt._value[:, page], np.float32)
            delta = np.einsum("lir,lro->lio", a, b) * self.scaling
            w = sd[names[m]]
            sd[names[m]] = (w.astype(np.float32) + delta).astype(w.dtype)
        return sd
