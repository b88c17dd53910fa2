"""What the stacked hybrid decoders (``lfm2.py``, ``phi4flash.py``,
``solar_open2.py``, ``mellum2.py``) share, on raw arrays: the matmul on the
weights' dtype, the norms, the gated feed-forwards, rotary positions (with
YaRN's frequencies where a layer asks for them), a step's runs and kept
inputs, and where a step's rows find their K and V in a slot cache's pool
and ring.  One place, so that a decoder of new layer kinds copies none of
it."""
from __future__ import annotations

import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["mm", "rms_norm", "layer_norm", "gated_ffn", "gated_ffn_fused",
           "rope", "yarn_frequencies", "SlotAttention",
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


def rope(x, pos, theta, yarn=None):
    """Rotate-half rotary positions: ``x`` [..., heads, D] at ``pos`` [...]
    (dimension ``i`` pairs with ``i + D/2``), at frequencies ``theta^(-2i/D)``;
    ``yarn`` (:func:`yarn_frequencies`): ``(frequencies [D/2], factor)``
    instead, the cosine and sine both times ``factor``."""
    half = x.shape[-1] // 2
    if yarn is None:
        freq = jnp.exp(jnp.arange(half, dtype=jnp.float32)
                       * np.float32(-np.log(theta) / half))
    else:
        freq = jnp.asarray(yarn[0], jnp.float32)
    ang = pos.astype(jnp.float32)[..., None, None] * freq      # [..., 1, D/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if yarn is not None:
        cos, sin = cos * np.float32(yarn[1]), sin * np.float32(yarn[1])
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def yarn_frequencies(head_dim: int, theta: float, *, factor: float,
                     original_max_position_embeddings: int, beta_fast: float,
                     beta_slow: float, attention_factor: float
                     ) -> Tuple[np.ndarray, float, Tuple[int, int]]:
    """YaRN's rotary frequencies (arXiv:2309.00071, as transformers'
    ``_compute_yarn_parameters`` reads a config): ``theta_i = theta^(-2i/D)``
    kept below the correction range ``[low, high]`` (the dimensions that turn
    more than ``beta_fast`` times over the original context), divided by
    ``factor`` above it, and ramped linearly between.  Returns
    ``(frequencies [D/2] float32, attention factor, (low, high))``; the
    attention factor is ``0.1 ln(factor) + 1`` where none is given."""
    half = head_dim // 2

    def turns(n):          # the dimension that turns n times over the context
        return (head_dim * math.log(original_max_position_embeddings
                                    / (n * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(turns(beta_fast)), 0)
    high = min(math.ceil(turns(beta_slow)), head_dim - 1)
    base = theta ** (-np.arange(half, dtype=np.float64) * 2 / head_dim)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0.0, 1.0)
    freq = base * (1.0 - ramp) + base / factor * ramp
    return freq.astype(np.float32), float(attention_factor), (low, high)


class SlotAttention:
    """Where a step's rows find their K and V in a ``SlotStateCache``: the
    PAGED pool of its full-attention layers (the step's plan and page
    tables; layer ``l`` of a stacked pool viewed flat at page ``l *
    num_pages``) and, where the cache keeps one, the RING of its window
    layers (the second work list the cache packed beside the plan, and each
    row's ring as a page-table row; window layer ``l`` at ring page ``l *
    ring size``).  Built once a step from the packed step input alone."""

    def __init__(self, pos, tbl, plan, extra, cache):
        from ..ops.pallas_kernels.ragged_paged_attention import (
            RAGGED_PLAN_FIELDS,
        )

        self.pos, self.tbl, self.plan = pos, tbl, tuple(plan)
        self.page_size = int(cache.page_size)
        self.n_pages = int(cache.num_pages)
        if not cache.window_layers:
            return
        self.window = int(cache.window)
        self.ring_pages = int(cache.ring_pages)
        self.ring_size = int(cache.ring_k.shape[1])
        # the window layers' plan: the step's blocks, another work list
        # (the ring pages inside the window) and the ring page each write
        # item lands in
        win = dict(zip(RAGGED_PLAN_FIELDS, self.plan))
        for f in cache.WINDOW_FIELDS:
            win[f] = extra["win_" + f]
        self.win_plan = tuple(win[f] for f in RAGGED_PLAN_FIELDS)
        slot = extra["row_slot"]
        # a row's ring as a page-table row (padding rows: the sink)
        at = jnp.arange(tbl.shape[1], dtype=jnp.int32)[None, :]
        self.ring_tbl = jnp.where(
            slot[:, None] >= 0,
            1 + slot[:, None] * self.ring_pages + at % self.ring_pages, 0)

    def attend(self, q, k, v, pools, *, head_dim, scope, layer=None,
               ring_layer=None):
        """Write the rows' K and V and attend: ``q`` [T, Hq, W], ``k`` / ``v``
        [T, H, W] against the ring of window layer ``ring_layer``, else the
        paged pool of full layer ``layer`` (None: ``pools`` are one layer's).
        ``scope`` names the launch's scope.  Returns ``(out [T, Hq, W],
        k_pool, v_pool)``."""
        from .gpt import _attend_paged_shard
        from ..ops.pallas_kernels.ragged_paged_attention import plan_at_layer

        window = None
        if ring_layer is not None:
            base = ring_layer * self.ring_size
            tbl, plan = self.ring_tbl + base, plan_at_layer(self.win_plan, base)
            window = self.window
        elif layer is not None:
            base = layer * self.n_pages
            tbl, plan = self.tbl + base, plan_at_layer(self.plan, base)
        else:
            tbl, plan = self.tbl, self.plan
        out, pk, pv = _attend_paged_shard(
            q[:, :, None, :], k[:, :, None, :], v[:, :, None, :], *pools,
            tbl, self.pos, head_dim=head_dim, page_size=self.page_size,
            ragged_plan=plan, window=window, attend_scope=scope)
        return out[:, :, 0, :], pk, pv


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

