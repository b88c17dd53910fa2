"""SPMD pipeline parallelism over the 'pp' mesh axis.

Reference: fleet/meta_parallel/pipeline_parallel.py:229 (1F1B schedule with
batched NCCL isend/irecv in pp_utils/p2p_communication.py) and the
FleetExecutor interceptor runtime (fleet_executor/carrier.h:50).

TPU-native redesign: there are no per-rank processes or p2p sockets.
The whole pipeline is ONE jitted SPMD program:

- The L homogeneous blocks' parameters are STACKED along a leading axis
  ([L, ...]) and sharded over 'pp', so each pipeline stage holds its
  contiguous slice of layers in HBM — the analog of PipelineLayer's
  segment partitioning (pp_layers.py:239).
- Execution runs under ``jax.shard_map`` with only 'pp' manual (dp/sp/mp
  stay auto, so GSPMD still partitions the tensor-parallel math inside
  each stage). Microbatch activations rotate between neighbouring stages
  with ``lax.ppermute`` over ICI — the collective-permute analog of the
  reference's isend/irecv pairs — in a ``lax.scan`` over
  T = n_micro + n_stages - 1 ticks (the GPipe wavefront; XLA overlaps the
  reverse pass, giving 1F1B-class utilisation without a hand-written
  interleaved schedule).
- Backward needs no code: ppermute/scan/psum all transpose, so jax.vjp
  of the pipelined forward IS the pipelined backward.

Without a pp axis (or pp=1) the same stacked layout runs as a plain
``lax.scan`` over layers — which also compiles the block body once
instead of L times (a large compile-time win over unrolled dygraph).
"""
from __future__ import annotations

from functools import partial
from typing import Callable, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ... import mesh as _mesh

__all__ = ["scan_blocks", "pipeline_blocks", "stacked_param_sharding"]


def stacked_param_sharding(shape, pp_axis="pp"):
    """NamedSharding for a stacked [L, ...] parameter: leading dim over 'pp'."""
    mesh = _mesh.get_mesh()
    if pp_axis in mesh.axis_names and mesh.shape[pp_axis] > 1:
        return NamedSharding(mesh, PartitionSpec(pp_axis, *([None] * (len(shape) - 1))))
    return NamedSharding(mesh, PartitionSpec())


def _checkpoint(fn, policy):
    """jax.checkpoint with a named rematerialisation policy.

    None/"full" recomputes everything (min residency); "dots" saves MXU
    outputs and recomputes only VPU work (near-free backward recompute);
    "dots_saveable" additionally saves batched dots.
    """
    if policy in (None, "full"):
        return jax.checkpoint(fn)
    policies = {
        "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        "dots_saveable": jax.checkpoint_policies.dots_saveable,
    }
    if policy not in policies:
        raise ValueError(
            f"unknown remat policy {policy!r}; expected one of "
            f"{['full', *policies]}")
    return jax.checkpoint(fn, policy=policies[policy])


def scan_blocks(block_fn: Callable, stacked: Sequence, x, *, remat: bool = False,
                remat_policy: str | None = None, remat_interval: int = 1):
    """Run L stacked homogeneous blocks sequentially: x -> block(p_i, x).

    ``block_fn(params_tuple, x) -> y`` with params_tuple holding one
    layer's slices. ``stacked`` is a tuple of [L, ...] arrays.

    ``remat_interval`` groups the rematerialisation boundary: ``k > 1``
    reshapes the stacked leading dim to [L/k, k, ...] and checkpoints a
    k-block group body, so backward saves only every k-th block boundary
    (1/k the saved residuals) at the cost of k blocks' activations live
    during each group's recompute.  Identical math to ``k == 1`` — same
    block sequence, each block recomputed exactly once — so the
    (interval, policy) pair is a pure memory/locality trade the measured
    autotune search can explore (docs/training_perf.md).  Requires
    ``L % k == 0``.
    """
    k = int(remat_interval) if remat else 1
    if k <= 1:
        body = _checkpoint(block_fn, remat_policy) if remat else block_fn

        def step(h, params):
            return body(params, h), None

        out, _ = jax.lax.scan(step, x, tuple(stacked))
        return out

    L = int(np.shape(stacked[0])[0])
    if L % k != 0:
        raise ValueError(
            f"remat_interval={k} must divide the stacked layer count {L}")

    def group(params_group, h):
        # k consecutive blocks under ONE checkpoint boundary
        def inner(carry, params):
            return block_fn(params, carry), None

        h2, _ = jax.lax.scan(inner, h, params_group)
        return h2

    gbody = _checkpoint(group, remat_policy)
    grouped = tuple(a.reshape((L // k, k) + tuple(a.shape[1:]))
                    for a in stacked)

    def step(h, params_group):
        return gbody(params_group, h), None

    out, _ = jax.lax.scan(step, x, grouped)
    return out


def pipeline_blocks(block_fn: Callable, stacked: Sequence, x_micro, *,
                    layers_per_stage: int, pp_axis: str = "pp",
                    remat: bool = False, remat_policy: str | None = None,
                    block_takes_index: bool = False,
                    n_virtual: int = 1):
    """Microbatch-pipelined execution of stacked blocks over the pp axis.

    Args:
      block_fn: (params_tuple, h) -> h for ONE block; with
        ``block_takes_index`` it is (params_tuple, h, mb_idx) -> h, letting
        stochastic blocks (dropout) decorrelate across microbatches.
      stacked: tuple of [L, ...] arrays, L = n_stages * layers_per_stage,
        leading dim sharded over ``pp_axis``.
      x_micro: [M, mb, ...] microbatched input activations (replicated over
        ``pp_axis``; may be sharded over dp/sp on inner dims).
      layers_per_stage: L // n_stages.
      n_virtual: virtual pipeline stages per device (reference
        PipelineParallelWithInterleave, pipeline_parallel.py:625).  Layers
        are assigned to devices round-robin by chunk (chunk c -> device
        c % S, Megatron interleave layout) and microbatches make
        ``n_virtual`` trips around the ring; the fill/drain bubble drops
        from (S-1)/(M+S-1) to (S-1)/(V*M+S-1).  Requires M >= S so phase
        v+1's first tick never outruns phase v's drain.

    Returns [M, mb, ...] outputs (replicated over the pp axis).

    Memory note (1F1B-class residency): with ``remat=True`` each tick's
    stage execution saves only its carry ([mb, ...] activation) and
    recomputes block internals in backward, so per-device residency is
    O(ticks x microbatch-activation) — the same order 1F1B buys the
    reference, achieved here by remat instead of schedule gymnastics.
    """
    mesh = _mesh.get_mesh()
    n_stages = mesh.shape[pp_axis]
    n_micro = x_micro.shape[0]
    V = int(n_virtual)
    if V > 1:
        if n_micro < n_stages:
            raise ValueError(
                f"interleave needs n_micro ({n_micro}) >= n_stages "
                f"({n_stages})")
        if layers_per_stage % V != 0:
            raise ValueError(
                f"layers_per_stage ({layers_per_stage}) must be divisible "
                f"by n_virtual ({V})")
    if not block_takes_index:
        base = block_fn
        block_fn = lambda p, h, idx: base(p, h)  # noqa: E731
    body = _checkpoint(block_fn, remat_policy) if remat else block_fn

    lpc = layers_per_stage // V  # layers per virtual chunk

    if V > 1:
        # Megatron interleave layout: chunk c -> device c % S.  Re-order the
        # stacked leading dim so each device's rows are contiguous:
        # device d holds chunks d, S+d, 2S+d, ... (V chunks of lpc layers).
        order = np.concatenate([
            np.arange((v * n_stages + d) * lpc, (v * n_stages + d + 1) * lpc)
            for d in range(n_stages) for v in range(V)
        ])
        stacked = tuple(a[order] for a in stacked)

    def chunk_scan(local_params, h, mb_idx, v_idx):
        """Run the local virtual chunk ``v_idx`` (lpc layers)."""
        if V == 1:
            chunk = local_params
        else:
            chunk = tuple(
                jax.lax.dynamic_slice_in_dim(p, v_idx * lpc, lpc, axis=0)
                for p in local_params
            )

        def step(carry, params):
            return body(params, carry, mb_idx), None

        out, _ = jax.lax.scan(step, h, chunk)
        return out

    def spmd(stacked_local, x_local):
        stage = jax.lax.axis_index(pp_axis)
        is_last_dev = stage == n_stages - 1

        # zeros are pp-invariant; the scan carry becomes pp-varying (each
        # stage computes different activations), so pcast the initial carry
        varying = lambda z: jax.lax.pcast(z, (pp_axis,), to="varying")  # noqa: E731
        # zeros from shape, not zeros_like(x_local[0]): indexing would
        # trace a dead slice+squeeze of the input (GL005)
        state = varying(jnp.zeros(x_local.shape[1:], x_local.dtype))
        outputs = varying(jnp.zeros_like(x_local))
        # phase-wrap buffer (interleave only): device 0 parks activations
        # returning from the last device until their next trip starts
        # dtype pinned: bare zeros(()) is f64 under x64 mode and would ride
        # the whole tick-scan carry (GL001 x64-leak)
        inbuf = (varying(jnp.zeros_like(x_local)) if V > 1
                 else jnp.zeros((), x_local.dtype))

        total_ticks = V * n_micro + n_stages - 1

        def tick(carry, t):
            state, inbuf, outputs = carry
            rel = t - stage
            active = (rel >= 0) & (rel < V * n_micro)
            v_idx = jnp.clip(rel // n_micro, 0, V - 1)
            mb_idx = jnp.clip(rel % n_micro, 0, n_micro - 1)
            # stage 0 feeds from x (trip 0) or the phase-wrap buffer
            # (later trips); other stages consume the rotated carry
            if V == 1:
                entry = x_local[mb_idx]
            else:
                entry = jnp.where(v_idx == 0, x_local[mb_idx], inbuf[mb_idx])
            inp = jnp.where(stage == 0, entry, state)
            y = chunk_scan(stacked_local, inp, mb_idx, v_idx)
            y = jnp.where(active, y, jnp.zeros_like(y))
            done = active & is_last_dev & (v_idx == V - 1)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(done, y, outputs[mb_idx]), mb_idx, 0)
            # rotate activations to the next device (ICI collective-permute)
            nxt = jax.lax.ppermute(
                y, pp_axis, [(i, (i + 1) % n_stages) for i in range(n_stages)]
            )
            if V > 1:
                # park arrivals from the ring's wrap (sender = prev device,
                # who processed rel' = t - (d-1 mod S) this tick) for the
                # next trip; only stage 0's buffer is ever read
                s_rel = t - ((stage - 1) % n_stages)
                s_active = (s_rel >= 0) & (s_rel < V * n_micro)
                s_mb = jnp.clip(s_rel % n_micro, 0, n_micro - 1)
                park = s_active & (stage == 0)
                inbuf = jax.lax.dynamic_update_index_in_dim(
                    inbuf, jnp.where(park, nxt, inbuf[s_mb]), s_mb, 0)
            return (nxt, inbuf, outputs), None

        (_, _, outputs), _ = jax.lax.scan(
            tick, (state, inbuf, outputs), jnp.arange(total_ticks)
        )
        # replicate the last stage's outputs across pp so downstream (loss)
        # code sees a normal replicated activation
        outputs = jax.lax.psum(
            jnp.where(is_last_dev, outputs, jnp.zeros_like(outputs)), pp_axis
        )
        return outputs

    nd = lambda a: (None,) * (a.ndim - 1)  # noqa: E731
    in_specs = (
        tuple(PartitionSpec(pp_axis, *nd(s)) for s in stacked),
        PartitionSpec(),  # microbatches replicated over pp (dp/sp stay auto)
    )
    fn = jax.shard_map(
        spmd,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=PartitionSpec(),
        axis_names=frozenset({pp_axis}),
    )
    return fn(tuple(stacked), x_micro)
