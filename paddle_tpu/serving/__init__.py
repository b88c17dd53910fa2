"""Continuous-batching serving engine over a paged KV cache.

The serving analog of the reference's fused_multi_transformer serving stack,
TPU-native: one fixed-shape compiled decode step serves an ever-changing
request mix (PAPERS.md: "Ragged Paged Attention", arxiv 2604.15464).

- :mod:`paged_cache` — the global KV page pool (``PagedKVCache``) and the
  free-list ``BlockAllocator`` (page 0 reserved as the null page);
- :mod:`admission` — the per-replica scheduler: fixed decode slots,
  admission with up-front page reservation (out-of-pages admission
  backpressures into the queue), immediate page free on retirement
  (:mod:`scheduler` remains the compatibility facade);
- :mod:`prefix_cache` — the global prefix cache (``PrefixCache``):
  completed full pages radix-indexed by token-id chunks, spliced
  copy-on-write into later admissions' page tables so only the uncached
  tail prefills; LRU eviction of refcount-0 pages under pool pressure —
  docs/serving.md "Prefix cache";
- :mod:`placement` — the cluster-level scheduler: which ``dp`` replica
  seats a request (least-loaded, queue-depth backpressure signal; typed
  shed only when ALL replicas backpressure);
- :mod:`sharded` — ``ShardedServingEngine``: ``dp`` replica engines x
  ``mp`` tensor-parallel chips (per-head-sharded pool + shard_map'd
  ragged kernels + column/row-parallel weights) behind one placement
  scheduler — docs/serving.md "Sharded serving";
- :mod:`engine` — ``ServingEngine`` / ``RequestQueue``: request lifecycle
  (SUBMITTED -> PREFILL -> DECODE -> DONE | CANCELLED | TIMED_OUT |
  FAILED), chunked prefill into pages, ONE donated retrace-free jitted
  decode step over all slots, per-request sampling + deadlines +
  cancellation, watchdog-supervised steps with auto-recovery, bounded
  queues with typed ``Overloaded`` shedding, NaN-slot quarantine,
  streaming token callbacks, per-step metrics;
- ``paddle_tpu.faults`` — deterministic fault-injection harness (step
  crashes, stalls, NaN logits, pool exhaustion, callback errors) driving
  tests/test_serving_faults.py and tools/serving_fault_gate.py;
- :mod:`speculative` — ``SpeculativeEngine``: draft-model propose +
  ONE fused verify dispatch with in-graph accept/reject (greedy
  bit-identical to the plain engine; sampling preserves the target
  distribution exactly), draft pages under the allocator's
  speculative-reservation/rollback API;
- :mod:`lora` — ``LoRAAdapterPool``: paged per-request adapter slabs
  gathered per token inside the step — one compiled program serves
  many fine-tuned tenants, register/evict at runtime without retraces.

- :mod:`elastic` — ``ElasticServingController``: the closed loop over
  all of the above — windowed SLO sensing from the telemetry registry,
  deterministic hysteresis/cooldown policy emitting typed
  ScaleUp/ScaleDown/Brownout/Recover actions, graceful replica drain
  with token-prefix checkpoint re-homing, and the ordered brownout
  ladder — docs/serving.md "Elasticity & degradation ladder";
- :mod:`disagg` — ``DisaggServingEngine``: disaggregated serving —
  dedicated prefill and decode replica roles with page-granular KV
  hand-off (``PageTransfer``: destination reservation -> batched
  device-to-device page copy -> atomic commit -> source release, exact
  on both allocators under mid-transfer faults), role-aware admission
  (``RolePlacement``) and per-role elastic scaling
  (``DisaggElasticController``: TTFT drives the prefill pool, ITL the
  decode pool) — docs/serving.md "Disaggregated prefill/decode".

See docs/serving.md (incl. the "Failure model & SLOs" section).
"""
from .elastic import (  # noqa: F401
    BROWNOUT_RUNGS,
    Brownout,
    ClusterSignals,
    ElasticConfig,
    ElasticServingController,
    Recover,
    ScaleDown,
    ScaleUp,
    SLOTargets,
)
from .disagg import (  # noqa: F401
    ROLE_COLOCATED,
    ROLE_DECODE,
    ROLE_PREFILL,
    DisaggElasticController,
    DisaggServingEngine,
    PageTransfer,
    PageTransferAborted,
    RolePlacement,
)
from .engine import (  # noqa: F401
    DeadlineExceeded,
    NaNLogitsError,
    Overloaded,
    Request,
    RequestCancelled,
    RequestQueue,
    RequestState,
    SamplingParams,
    ServingEngine,
    ServingError,
    StepBuildError,
    StepStalledError,
    UnsupportedServingMode,
    serve_trace_counts,
    reset_serve_trace_counts,
)
from ..faults import (  # noqa: F401
    FaultInjector,
    FaultPlan,
    InjectedFault,
    random_schedule,
    random_transfer_schedule,
)
from .lora import (  # noqa: F401
    AdapterError,
    AdapterInUse,
    LoRAAdapterPool,
    UnknownAdapter,
    random_adapter,
)
from .paged_cache import (  # noqa: F401
    NULL_PAGE,
    BlockAllocator,
    PagedKVCache,
    pages_for_tokens,
)
from .prefix_cache import PrefixCache  # noqa: F401
from .speculative import SpeculativeEngine  # noqa: F401
from .admission import AdmissionScheduler, Slot  # noqa: F401
from .placement import (  # noqa: F401
    LeastLoadedPlacement,
    PlacementScheduler,
    PrefixLocalityPlacement,
    replica_load,
)
from .sharded import ShardedServingEngine  # noqa: F401

__all__ = [
    "Request", "RequestQueue", "RequestState", "SamplingParams",
    "ServingEngine", "ShardedServingEngine", "SpeculativeEngine",
    "LoRAAdapterPool", "AdapterError", "AdapterInUse", "UnknownAdapter",
    "random_adapter",
    "serve_trace_counts", "reset_serve_trace_counts",
    "ServingError", "Overloaded", "DeadlineExceeded", "RequestCancelled",
    "StepStalledError", "StepBuildError", "NaNLogitsError",
    "UnsupportedServingMode",
    "FaultInjector", "FaultPlan", "InjectedFault", "random_schedule",
    "random_transfer_schedule",
    "DisaggServingEngine", "DisaggElasticController", "RolePlacement",
    "PageTransfer", "PageTransferAborted",
    "ROLE_PREFILL", "ROLE_DECODE", "ROLE_COLOCATED",
    "NULL_PAGE", "BlockAllocator", "PagedKVCache", "pages_for_tokens",
    "PrefixCache",
    "AdmissionScheduler", "Slot",
    "PlacementScheduler", "LeastLoadedPlacement",
    "PrefixLocalityPlacement", "replica_load",
    "ElasticServingController", "ElasticConfig", "ClusterSignals",
    "SLOTargets", "ScaleUp", "ScaleDown", "Brownout", "Recover",
    "BROWNOUT_RUNGS",
]
