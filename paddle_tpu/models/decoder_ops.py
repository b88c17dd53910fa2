"""What the stacked hybrid decoders (``lfm2.py``, ``phi4flash.py``) share, on
raw arrays: the matmul on the weights' dtype, the norms and the gated
feed-forwards.  One place, so that a decoder of new layer kinds copies none
of it."""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

__all__ = ["mm", "rms_norm", "layer_norm", "gated_ffn", "gated_ffn_fused",
           "StepRows", "step_runs", "runs_at", "rows_of_step",
           "kept_inputs"]


def mm(x, w):
    """``x @ w`` on the weights' dtype with float32 accumulation."""
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def rms_norm(x, g, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, g, b, eps):
    """LayerNorm with scale and bias over the last axis, in float32."""
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    xc = x32 - mu
    y = xc * jax.lax.rsqrt(jnp.mean(xc * xc, axis=-1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def gated_ffn(u, w1, w3, w2):
    """``(silu(u @ w1) * (u @ w3)) @ w2``."""
    y = (jax.nn.silu(mm(u, w1)) * mm(u, w3)).astype(u.dtype)
    return mm(y, w2).astype(u.dtype)


def gated_ffn_fused(u, w_in, w_out):
    """``[g, p] = split2(u @ w_in);  (p * silu(g)) @ w_out``: the gate and
    the value in one product."""
    g, p = jnp.split(mm(u, w_in), 2, axis=-1)
    return mm((p * jax.nn.silu(g)).astype(u.dtype), w_out).astype(u.dtype)


class StepRows(NamedTuple):
    """A step's run list by row (:func:`rows_of_step`)."""

    k: jax.Array        # a row's index in its run (0 for a padding row)
    run: jax.Array      # its run (the last run for a padding row)
    src: jax.Array      # the state row its run loads (0 for a padding row)
    dst: jax.Array      # the row a run's LAST row stores to (0, the sink, for the others)
    fresh: jax.Array    # whether its run starts a sequence
    real: jax.Array     # whether a run holds it


def step_runs(pos, extra):
    """The packed step input's run list as the launches take it:
    ``(run_first, run_count, run_src, run_dst, run_fresh, n_runs)``, a run
    fresh where its first row is at position 0."""
    first = extra["run_first"]
    return (first, extra["run_count"], extra["run_src"], extra["run_dst"],
            jnp.take(pos, first) == 0, extra["n_runs"])


def runs_at(runs, base):
    """``runs`` with their state rows in the layer of a stacked pool viewed
    flat whose first row is ``base``."""
    first, count, src, dst, fresh, n_runs = runs
    return first, count, src + base, dst + base, fresh, n_runs


def rows_of_step(pos, runs) -> StepRows:
    """The run list by row, for a convolution whose kept inputs live in a
    slot's state rows."""
    from ..ops.pallas_kernels.selective_scan import rows_of_runs

    run_first, run_count, run_src, run_dst, _, n_runs = runs
    k, run, real = rows_of_runs(run_first, run_count, n_runs, pos.shape[0])
    ends = real & (k == jnp.take(run_count, run) - 1)
    row_k = jnp.where(real, k, 0)
    return StepRows(row_k, run, jnp.where(real, jnp.take(run_src, run), 0),
                    jnp.where(ends, jnp.take(run_dst, run), 0),
                    (pos - row_k) == 0, real)


def kept_inputs(a, conv, base, rows: StepRows, runs, scopes):
    """``(a_{t-1}, a_{t-2}, a_{t-3})`` of every row of ``a`` [T, W] and the
    convolution's pool ``[L * rows, ...]`` with what each run leaves written:
    a row's earlier inputs are its run's own rows or, before the run's
    first, the three its slot kept (none where the run starts a sequence).
    ``runs``: :func:`step_runs`; ``base``: the layer's first row;
    ``scopes``: the names of the read and of the write.

    On a TPU (``kept_rows_runs``) the write is ONE launch over the run list
    (``ops/pallas_kernels/kept_rows.py``) that hands back each run's kept
    row, and a row's earlier inputs are read from it and from ``a``;
    elsewhere every row gathers its run's kept row and scatters what it
    would leave, a run's last row to its ``run_dst``, the others into the
    layer's row 0."""
    from ..ops.pallas_kernels import kept_rows as kr

    if kr.kept_rows_runs(conv.shape[1:], a.dtype, conv.dtype):
        # the launch hands back each run's old row, [3 S, W] (row 3 r + i is
        # run r's a_{p-3+i}); row k < j of a run reads its j-th earlier
        # input from there, k + 3 - j
        with jax.named_scope(scopes[1]):
            conv, old = kr.kept_rows(conv, a, runs_at(runs, base))
        with jax.named_scope(scopes[0]):
            at = 3 * rows.run + rows.k + 3
            prev = [jnp.where((rows.k >= j)[:, None], _behind(a, j),
                              jnp.take(old, jnp.clip(at - j, 0, old.shape[0] - 1),
                                       axis=0))
                    for j in (1, 2, 3)]
        return prev, conv
    with jax.named_scope(scopes[0]):
        t, width = a.shape
        old = jnp.take(conv, rows.src + base, axis=0)
        old = old.reshape(t, 3, width)          # a_{p-3}, a_{p-2}, a_{p-1}
        old = jnp.where(rows.fresh[:, None, None], jnp.zeros_like(old), old)
        prev = []
        for j in (1, 2, 3):
            at = (3 - j + rows.k)[:, None]      # which kept input, k < j
            kept = jnp.where(at <= 0, old[:, 0],
                             jnp.where(at == 1, old[:, 1], old[:, 2]))
            prev.append(jnp.where((rows.k >= j)[:, None], _behind(a, j), kept))
    with jax.named_scope(scopes[1]):
        # a run's last row leaves (a_{p-2}, a_{p-1}, a_p); the others sink
        # into the layer's row 0
        new = jnp.stack([prev[1], prev[0], a], axis=1).astype(conv.dtype)
        conv = conv.at[rows.dst + base].set(new.reshape((t,) + conv.shape[1:]))
    return prev, conv


def _behind(a, j):
    """``a`` moved ``j`` rows down: row ``t`` holds ``a[t - j]``."""
    return jnp.concatenate([jnp.zeros_like(a[:j]), a[:-j]])

