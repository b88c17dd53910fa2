"""Serving-mesh helpers: the named-axes machinery for mesh-native serving.

The serving engine's fused step becomes an SPMD program over a small
two-axis geometry (docs/serving.md "Sharded serving"):

- ``mp`` — tensor parallelism INSIDE one replica: the paged KV pool is
  sharded per-head (``[num_pages, H/mp, page_size, D]`` per chip), the
  ragged/paged attention kernels run per head shard under ``shard_map``,
  and the model weights are partitioned Megatron column/row-parallel via
  NamedSharding (GSPMD inserts the one row-parallel all-reduce after the
  post-attention / post-MLP projections — the only cross-chip reduce on
  the hot path).
- ``dp`` — replica scaling: each dp replica owns its OWN pool, slots and
  compiled fused step on a disjoint ``mp`` submesh; the placement layer
  (``serving/placement.py``) routes requests across replicas, so
  aggregate slots and page HBM grow linearly with replica count.

Deliberately separate from :mod:`paddle_tpu.distributed.mesh`'s global
training mesh: a serving process may host several replica meshes at once,
and sharding the serving pool must never re-shard training state.

The "active serving mesh" is a trace-time, thread-local context: the
engine's fused-step closure enters it around the model call, and
``models/gpt.py``'s paged attention path consults it to decide whether to
wrap the scatter+attend body in ``shard_map`` over ``mp``.  Nothing reads
it at dispatch time — compiled programs carry their partitioning in the
jaxpr.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import List, Optional, Sequence

import numpy as np

__all__ = [
    "build_serving_mesh", "replica_meshes", "active_mesh", "activate",
    "mp_size", "shard_model_for_serving", "shard_paged_cache",
    "replicate_to_mesh", "validate_head_sharding", "clone_model",
]


def _mesh_cls():
    from jax.sharding import Mesh

    return Mesh


def build_serving_mesh(dp: int, mp: int, devices: Optional[Sequence] = None):
    """One ``(dp, mp)`` mesh over the first ``dp*mp`` devices — the
    cluster-level bookkeeping view (benches report its geometry).  The
    engines themselves run on the per-replica submeshes from
    :func:`replica_meshes`."""
    import jax

    devs = list(devices) if devices is not None else jax.devices()
    dp, mp = int(dp), int(mp)
    if dp < 1 or mp < 1:
        raise ValueError(f"mesh axes must be >= 1, got dp={dp} mp={mp}")
    if dp * mp > len(devs):
        raise ValueError(
            f"serving mesh (dp={dp}, mp={mp}) needs {dp * mp} devices, "
            f"have {len(devs)}")
    arr = np.array(devs[:dp * mp]).reshape(dp, mp)
    return _mesh_cls()(arr, ("dp", "mp"))


def replica_meshes(dp: int, mp: int,
                   devices: Optional[Sequence] = None) -> List:
    """One single-axis ``('mp',)`` mesh per dp replica, over disjoint
    device rows of the ``(dp, mp)`` geometry.  Each replica's pool,
    weights and compiled fused step live entirely on its own row — which
    is exactly why aggregate HBM and slots scale linearly with ``dp``."""
    full = build_serving_mesh(dp, mp, devices)
    rows = full.devices  # [dp, mp] ndarray
    return [_mesh_cls()(rows[i], ("mp",)) for i in range(int(dp))]


def mp_size(mesh) -> int:
    """Size of the mesh's ``mp`` axis (1 when absent)."""
    try:
        return int(dict(mesh.shape).get("mp", 1))
    except Exception:  # noqa: BLE001 — absent/odd meshes count as unsharded
        return 1


# ---------------------------------------------------------------------------
# trace-time active-mesh context (consumed by models/gpt.py)
# ---------------------------------------------------------------------------

class _ActiveMesh(threading.local):
    def __init__(self):
        self.mesh = None


_active = _ActiveMesh()


def active_mesh():
    """The serving mesh of the fused step currently being traced on this
    thread (None outside a sharded engine's trace)."""
    return _active.mesh


@contextmanager
def activate(mesh):
    """Mark ``mesh`` as the active serving mesh for the duration (no-op
    for ``None``).  The engine's fused-step closure wraps the model call
    in this so the paged attention path knows to shard_map over ``mp``."""
    prev = _active.mesh
    _active.mesh = mesh
    try:
        yield
    finally:
        _active.mesh = prev


# ---------------------------------------------------------------------------
# shard preconditions
# ---------------------------------------------------------------------------

def validate_head_sharding(num_heads: int, mp: int,
                           kernel: str = "ragged_paged_attention"):
    """Raise a typed ValueError (GL002-formatted, via
    ``analysis/codes.mesh_shard_gate_reason``) when the per-head partition
    cannot exist — BEFORE shard_map would crash on an indivisible head
    axis."""
    from ..analysis.codes import mesh_shard_gate_reason

    reason = mesh_shard_gate_reason(num_heads, mp, kernel=kernel)
    if reason is not None:
        raise ValueError(str(reason))
    return num_heads // max(int(mp), 1)


# ---------------------------------------------------------------------------
# placement: weights, pool, host inputs
# ---------------------------------------------------------------------------

def _put(t, mesh, spec_names):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    sh = NamedSharding(mesh, PartitionSpec(*spec_names))
    t._set_value(jax.device_put(t._value, sh))
    return t


def replicate_to_mesh(value, mesh):
    """device_put a raw array replicated across the replica mesh (host
    step inputs: token ids, the packed plan vector, sampling params)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    return jax.device_put(value, NamedSharding(mesh, PartitionSpec()))


def _serving_param_specs(model) -> dict:
    """id(param) -> PartitionSpec names for the Megatron column/row
    partition of the serving hot path: the stacked ``[L, ...]`` parameters
    of ``GPTStackedDecoder``.  QKV and fc1 are column-parallel (output
    features over ``mp``), the post-attention projection and fc2
    row-parallel (contraction dim over ``mp`` — GSPMD's all-reduce after
    them is the hot path's only cross-chip collective); embeddings, norms
    and biases of row-parallel layers replicate."""
    tp = {"qkv_w": (None, None, "mp"), "qkv_b": (None, "mp"),
          "fc1_w": (None, None, "mp"), "fc1_b": (None, "mp"),
          "proj_w": (None, "mp", None), "fc2_w": (None, "mp", None)}
    return {id(getattr(model.decoder, name)): spec
            for name, spec in tp.items()}


def shard_model_for_serving(model, mesh):
    """Commit every parameter of ``model`` to the replica ``mesh``:
    column/row-parallel over ``mp`` for the TP-relevant weights, replicated
    for everything else.  Idempotent; mutates placements in place (the
    replica owns this model copy — see ``serving/sharded.py``)."""
    if mp_size(mesh) > 1:
        validate_head_sharding(model.config.num_heads, mp_size(mesh))
    specs = _serving_param_specs(model) if mp_size(mesh) > 1 else {}
    for p in model.parameters():
        _put(p, mesh, specs.get(id(p), ()))
    return model


def shard_paged_cache(cache, mesh):
    """Shard the paged KV pool ``[L, P, H, page_size, D]`` per-head over
    ``mp`` (axis 2) — per-chip pool bytes shrink to ``nbytes / mp``.
    Records the shard count on the cache (``cache.mesh_shards``) for the
    per-chip accounting benches report."""
    mp = mp_size(mesh)
    if mp > 1:
        validate_head_sharding(cache.num_heads, mp)
    head = "mp" if mp > 1 else None
    # K, V and an int8 pool's [L, P, H] per-(page, head) scale buffers all
    # carry the heads on axis 2
    for t in cache._tensors():
        _put(t, mesh, (None, None, head) + (None,) * (t.ndim - 3))
    cache.mesh_shards = mp
    return cache


# ---------------------------------------------------------------------------
# replica model cloning (dp scaling)
# ---------------------------------------------------------------------------

def clone_model(model, model_factory=None):
    """A fresh model instance with ``model``'s exact weights, dtypes
    included (the replica of an O2-decorated bf16 model is bf16, not the
    constructor's fp32) — each dp replica owns a full copy on its own
    submesh.  ``model_factory`` overrides construction for model classes
    whose ``__init__`` takes more than the config."""
    if model_factory is not None:
        fresh = model_factory()
    else:
        fresh = type(model)(model.config)
    src = model.state_dict()
    for name, t in fresh.state_dict().items():
        t._set_value(src[name]._value)
    if getattr(model, "training", False):
        fresh.train()
    else:
        fresh.eval()
    return fresh
