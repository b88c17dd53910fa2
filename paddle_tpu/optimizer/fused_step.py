"""One donated compiled train step: loss -> backward -> scale/clip -> update.

``jit.to_static`` already functionalizes an imperative ``loss.backward();
opt.step()`` body into one XLA program — but only for callers who hand-roll
the wrapper, and the GradScaler's dynamic-scaling branch breaks the trace
(``bool(finite)`` is a host sync).  :class:`FusedTrainStep` is the
first-class train hot path:

- forward (optionally under AMP O1 auto_cast), backward, gradient
  unscale + clip, and the optimizer update compile into ONE program per
  input signature;
- parameters, optimizer moments, fp32 master weights, and the RNG state
  are donated (the jit.to_static mutation log), so the update aliases in
  place — no double-buffered copy of params+moments across the step
  (Graph Lint GL004 is the regression gate for exactly this);
- with an *enabled* GradScaler the whole dynamic-loss-scaling protocol is
  traced: grads unscale in-graph, a fused finiteness reduction gates
  every optimizer write (``where(finite, new, old)``), and the scale /
  good- / bad-step counters update as traced state — a skipped step costs
  zero host syncs instead of one ``bool()`` per step;
- compile and dispatch counters (``program_count`` / ``dispatch_count``)
  make "exactly one program, one dispatch per step" assertable in tests
  and the train-perf gate.

See docs/training_perf.md.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from ..ops import dispatch
from ..tensor import Tensor

__all__ = ["FusedTrainStep"]


class FusedTrainStep:
    """Compile ``loss_fn`` + backward + scaler + ``optimizer`` into one
    donated program.

    Args:
      loss_fn: callable over Tensor batch args returning the scalar loss
        (e.g. ``lambda ids, labels: model(ids, labels=labels)``).
      optimizer: a paddle_tpu Optimizer; its ``grad_clip`` applies inside
        the fused program (after unscaling, before the update).
      scaler: optional GradScaler/AmpScaler.  Disabled scalers are
        pass-through; an enabled one runs the traced skip-on-nonfinite
        protocol above.  NOTE: in fused mode the scaler's *python*
        ``_good_steps/_bad_steps/_found_inf`` stay untouched — the traced
        counters live on this object and ``last_step_applied`` reads the
        in-graph flag (one lazy host sync).
      amp_level: ``"O1"`` wraps the forward in ``amp.auto_cast`` with
        ``amp_dtype``; ``None`` leaves dtypes to the caller (fp32, or an
        O2-decorated model).
    """

    def __init__(self, loss_fn: Callable, optimizer, *,
                 scaler=None, amp_level: Optional[str] = None,
                 amp_dtype: str = "bfloat16"):
        if amp_level not in (None, "O1"):
            raise ValueError(
                f"amp_level must be None or 'O1', got {amp_level!r} "
                "(O2 is a model decoration — amp.decorate — not a "
                "per-step cast)")
        self._loss_fn = loss_fn
        self._optimizer = optimizer
        self._scaler = scaler
        self._amp_level = amp_level
        self._amp_dtype = amp_dtype
        # pre-created persistent state (exists BEFORE the first trace, so
        # the scout classifies it as captured+mutated -> donated):
        # in-graph "step applied" flag + traced scaler counters
        self._finite_t = Tensor(jnp.asarray(True))
        self._good_t = Tensor(jnp.asarray(0, jnp.int32))
        self._bad_t = Tensor(jnp.asarray(0, jnp.int32))

        from ..jit.api import to_static

        def fused_train_step(*batch):
            with jax.named_scope("train.forward"):
                loss = self._forward(*batch)
            self._backward_and_update(loss)
            return loss

        self._step_fn = to_static(fused_train_step)

    # -- the traced body ---------------------------------------------------
    def _forward(self, *batch):
        if self._amp_level == "O1":
            from ..amp.auto_cast import auto_cast

            with auto_cast(enable=True, level="O1", dtype=self._amp_dtype):
                return self._loss_fn(*batch)
        return self._loss_fn(*batch)

    def _scaling(self) -> bool:
        s = self._scaler
        return s is not None and s.is_enable()

    def _backward_and_update(self, loss):
        opt = self._optimizer
        if not self._scaling():
            with jax.named_scope("train.backward"):
                loss.backward()
            with jax.named_scope("train.optimizer"):
                opt.step()
            opt.clear_grad()
            return
        scaler = self._scaler
        with jax.named_scope("train.backward"):
            scaler.scale(loss).backward()
        with jax.named_scope("train.optimizer"):
            self._unscale_and_update(opt, scaler)
        opt.clear_grad()

    def _unscale_and_update(self, opt, scaler):
        # in-graph unscale + fused finiteness (the traced analog of
        # GradScaler.unscale_'s one-host-sync fused kernel)
        dispatch.note_read(scaler._scale)
        inv = 1.0 / scaler._scale._value.astype(jnp.float32)
        grads = [p.grad for p in opt._parameter_list if p.grad is not None]
        flags = []
        for g in grads:
            raw = g._value.astype(jnp.float32) * inv
            flags.append(jnp.isfinite(raw).all())
            g._set_value(raw.astype(g._value.dtype))
        finite = (functools.reduce(jnp.logical_and, flags)
                  if flags else jnp.asarray(True))
        # snapshot every optimizer-mutable tensor, run the update (clip
        # included), then gate each write on the finiteness flag — a
        # non-finite step leaves params/moments/masters/aux bitwise
        # untouched without ever leaving the compiled program
        muts = self._opt_mutables(opt)
        olds = []
        for t in muts:
            dispatch.note_read(t)
            olds.append(t._value)
        opt.step()
        for t, old in zip(muts, olds):
            t._set_value(jnp.where(finite, t._value, old))
        self._traced_scaler_update(finite)
        dispatch.note_read(self._finite_t)
        self._finite_t._set_value(finite)

    @staticmethod
    def _opt_mutables(opt):
        """Every tensor ``opt.step()`` may rebind: params, accumulators,
        fp32 master weights, aux scalars (beta powers)."""
        ts = []
        for store in opt._accumulators.values():
            ts.extend(store.values())
        ts.extend(opt._aux_state.values())
        ts.extend(getattr(opt, "_master", {}).values())
        ts.extend(opt._parameter_list)
        return ts

    def _traced_scaler_update(self, finite):
        """GradScaler.update() semantics with the counters as traced state:
        finite -> good+1 (scale *= incr every ``incr_every``), non-finite
        -> bad+1 (scale = max(scale*decr, 1) every ``decr_every``)."""
        s = self._scaler
        if not s.is_use_dynamic_loss_scaling():
            return
        good, bad = self._good_t, self._bad_t
        dispatch.note_read(good)
        dispatch.note_read(bad)
        dispatch.note_read(s._scale)
        good2 = jnp.where(finite, good._value + 1, 0)
        bad2 = jnp.where(finite, 0, bad._value + 1)
        incr = finite & (good2 >= s._incr_every)
        decr = (~finite) & (bad2 >= s._decr_every)
        scale = s._scale._value
        scale = jnp.where(incr, scale * s._incr_ratio, scale)
        scale = jnp.where(decr, jnp.maximum(scale * s._decr_ratio, 1.0),
                          scale)
        good._set_value(jnp.where(incr, 0, good2).astype(jnp.int32))
        bad._set_value(jnp.where(decr, 0, bad2).astype(jnp.int32))
        s._scale._set_value(scale)

    # -- public surface ----------------------------------------------------
    def __call__(self, *batch):
        return self._step_fn(*batch)

    @property
    def last_step_applied(self) -> bool:
        """Whether the most recent step's grads were all-finite (always
        True on the unscaled path).  Reading syncs the in-graph flag."""
        import numpy as np

        return bool(np.asarray(self._finite_t._value))

    @property
    def program_count(self) -> int:
        """Distinct compiled programs (one per input signature) — the
        trace counter the gate pins to exactly 1 for a fixed shape."""
        return sum(1 for e in self._step_fn.code_cache.values()
                   if e.jitted is not None)

    @property
    def dispatch_count(self) -> int:
        """Compiled-program executions to date."""
        return self._step_fn.dispatch_count

    def lowered_texts(self):
        return self._step_fn.lowered_texts()

    def op_scopes(self):
        return self._step_fn.op_scopes()

    def lint_reports(self):
        return self._step_fn.lint_reports()

    def cost_reports(self):
        return self._step_fn.cost_reports()
