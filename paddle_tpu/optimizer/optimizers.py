"""Concrete optimizers: SGD, Momentum, Adagrad, RMSProp, Adam, AdamW, Adamax,
Lamb (reference: python/paddle/optimizer/*.py; CUDA kernels
phi/kernels/gpu/adam_kernel.cu etc.). Updates are jnp expressions — XLA fuses
each param's update chain; under jit.to_static the whole optimizer fuses into
the train-step program."""
from __future__ import annotations

import jax.numpy as jnp

from ..ops import dispatch
from ..tensor import Tensor
from .optimizer import Optimizer

__all__ = ["SGD", "Momentum", "Adagrad", "Adadelta", "RMSProp", "Adam", "AdamW", "Adamax", "Lamb"]


class SGD(Optimizer):
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)

    def _apply_one(self, p, g):
        lr = self._lr_value()
        g_raw = self._decayed_grad(p, g._value.astype(jnp.float32))
        self._write_param(p, (p._value.astype(jnp.float32) - lr * g_raw).astype(p._value.dtype))


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None, name=None):
        self._momentum = momentum
        self._nesterov = use_nesterov
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)

    def _create_accumulators(self, params):
        for p in params:
            self._add_accumulator("velocity", p)

    def _apply_one(self, p, g):
        lr = self._lr_value()
        v = self._get_accumulator("velocity", p)
        dispatch.note_read(v)
        g_raw = self._decayed_grad(p, g._value.astype(jnp.float32))
        new_v = self._momentum * v._value + g_raw
        if self._nesterov:
            update = g_raw + self._momentum * new_v
        else:
            update = new_v
        v._set_value(new_v)
        self._write_param(p, (p._value.astype(jnp.float32) - lr * update).astype(p._value.dtype))


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None, initial_accumulator_value=0.0, name=None):
        self._epsilon = epsilon
        self._init_acc = initial_accumulator_value
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)

    def _create_accumulators(self, params):
        for p in params:
            self._add_accumulator("moment", p, fill_value=self._init_acc)

    def _apply_one(self, p, g):
        lr = self._lr_value()
        m = self._get_accumulator("moment", p)
        dispatch.note_read(m)
        g_raw = self._decayed_grad(p, g._value.astype(jnp.float32))
        new_m = m._value + g_raw * g_raw
        m._set_value(new_m)
        self._write_param(
            p,
            (p._value.astype(jnp.float32) - lr * g_raw / (jnp.sqrt(new_m) + self._epsilon)).astype(p._value.dtype),
        )


class RMSProp(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None, grad_clip=None, name=None):
        self._rho = rho
        self._epsilon = epsilon
        self._momentum = momentum
        self._centered = centered
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)

    def _create_accumulators(self, params):
        for p in params:
            self._add_accumulator("mean_square", p)
            self._add_accumulator("momentum", p)
            if self._centered:
                self._add_accumulator("mean_grad", p)

    def _apply_one(self, p, g):
        lr = self._lr_value()
        ms = self._get_accumulator("mean_square", p)
        mom = self._get_accumulator("momentum", p)
        dispatch.note_read(ms)
        dispatch.note_read(mom)
        g_raw = self._decayed_grad(p, g._value.astype(jnp.float32))
        new_ms = self._rho * ms._value + (1 - self._rho) * g_raw * g_raw
        if self._centered:
            mg = self._get_accumulator("mean_grad", p)
            dispatch.note_read(mg)
            new_mg = self._rho * mg._value + (1 - self._rho) * g_raw
            denom = jnp.sqrt(new_ms - new_mg * new_mg + self._epsilon)
            mg._set_value(new_mg)
        else:
            denom = jnp.sqrt(new_ms + self._epsilon)
        new_mom = self._momentum * mom._value + lr * g_raw / denom
        ms._set_value(new_ms)
        mom._set_value(new_mom)
        self._write_param(p, (p._value.astype(jnp.float32) - new_mom).astype(p._value.dtype))


class Adam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-08,
                 parameters=None, weight_decay=None, grad_clip=None, lazy_mode=False,
                 multi_precision=True, name=None):
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon
        self._multi_precision = multi_precision
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)

    def _create_accumulators(self, params):
        for p in params:
            # multi_precision: fp32 moments + fp32 master weights for
            # low-precision params (reference multi_precision adam);
            # without it moments live in the PARAM dtype (the reference's
            # plain adam kernel) — the pure-bf16 low-memory regime.
            acc_dt = None if self._multi_precision else p._value.dtype
            self._add_accumulator("moment1", p, dtype=acc_dt)
            self._add_accumulator("moment2", p, dtype=acc_dt)
        self._aux_state[0] = Tensor(jnp.asarray(1.0, jnp.float32))  # beta1^t
        self._aux_state[1] = Tensor(jnp.asarray(1.0, jnp.float32))  # beta2^t
        # fp32 master weights for low-precision params (reference
        # multi_precision adam)
        if self._multi_precision:
            self._master: dict = {}
            hook = getattr(self, "_accumulator_layout_hook", None)
            for p in params:
                if p._value.dtype in (jnp.bfloat16, jnp.float16):
                    m = Tensor(p._value.astype(jnp.float32))
                    if hook is not None:
                        hook(m, p)  # ZeRO: master weights shard like moments
                    self._master[id(p)] = m

    @dispatch.no_grad()
    def step(self):
        params_grads = [(p, g) for p, g in self._collect_params_grads() if g is not None]
        if self._grad_clip is not None:
            params_grads = self._grad_clip(params_grads)
        # advance bias-correction powers once per step
        b1p, b2p = self._aux_state[0], self._aux_state[1]
        dispatch.note_read(b1p)
        dispatch.note_read(b2p)
        b1p._set_value(b1p._value * self._beta1)
        b2p._set_value(b2p._value * self._beta2)
        for p, g in params_grads:
            dispatch.note_read(p)
            self._apply_one(p, g)

    def _decayed(self, p, g_raw, pv):
        """Same dispatch as Optimizer._decayed_grad, applied to the
        (possibly master fp32) parameter value: floats add coeff*pv,
        regularizer objects are CALLED (L1Decay adds coeff*sign(pv))."""
        wd = self._weight_decay
        if wd is None:
            return g_raw
        if isinstance(wd, (int, float)):
            return g_raw + float(wd) * pv
        if callable(wd):
            return g_raw + wd(pv)
        return g_raw + getattr(wd, "_coeff", 0.0) * pv

    def _apply_one(self, p, g):
        lr = self._lr_value()
        m1 = self._get_accumulator("moment1", p)
        m2 = self._get_accumulator("moment2", p)
        dispatch.note_read(m1)
        dispatch.note_read(m2)
        master = getattr(self, "_master", {}).get(id(p))
        if master is not None:
            dispatch.note_read(master)
            pv = master._value
        else:
            pv = p._value.astype(jnp.float32)
        g_raw = self._decayed(p, g._value.astype(jnp.float32), pv)
        new_m1 = self._beta1 * m1._value + (1 - self._beta1) * g_raw
        new_m2 = self._beta2 * m2._value + (1 - self._beta2) * g_raw * g_raw
        b1p = self._aux_state[0]._value
        b2p = self._aux_state[1]._value
        m1_hat = new_m1 / (1 - b1p)
        m2_hat = new_m2 / (1 - b2p)
        new_p = pv - lr * m1_hat / (jnp.sqrt(m2_hat) + self._epsilon)
        m1._set_value(new_m1.astype(m1._value.dtype))
        m2._set_value(new_m2.astype(m2._value.dtype))
        if master is not None:
            master._set_value(new_p)
        self._write_param(p, new_p.astype(p._value.dtype))


class AdamW(Adam):
    """Decoupled weight decay (reference: python/paddle/optimizer/adamw.py).

    ``use_fused_kernel=True`` routes the update through the owned Pallas
    multi-tensor kernel (ops/pallas_kernels/fused_adamw.py — the analog of
    the reference's phi/kernels/fusion/fused_adam_kernel.cu): one VMEM
    pass per slab, params/moments aliased in place."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-08,
                 parameters=None, weight_decay=0.01, lr_ratio=None,
                 apply_decay_param_fun=None, grad_clip=None, lazy_mode=False,
                 multi_precision=True, use_fused_kernel=False, name=None):
        # decoupled decay is L2 BY CONSTRUCTION (p *= 1 - lr*coeff):
        # an L1Decay here would silently become L2, so reject it
        # (reference AdamW takes float coefficients only)
        from ..regularizer import L1Decay

        if isinstance(weight_decay, L1Decay):
            raise TypeError(
                "AdamW applies DECOUPLED L2 decay; L1Decay cannot be "
                "expressed here — use Adam(weight_decay=L1Decay(...)) "
                "for L1 regularization")
        self._wd_coeff = (float(weight_decay)
                          if isinstance(weight_decay, (int, float))
                          else getattr(weight_decay, "_coeff", 0.01))
        self._apply_decay_fun = apply_decay_param_fun
        self._lr_ratio = lr_ratio
        if use_fused_kernel:
            from ..ops.pallas_kernels.flash_attention import _on_tpu

            if not _on_tpu():
                raise RuntimeError(
                    "AdamW(use_fused_kernel=True) runs a Pallas TPU kernel "
                    "and JAX's default backend is not a TPU; the "
                    "XLA-composed update (use_fused_kernel=False) runs "
                    "anywhere")
        self._use_fused_kernel = use_fused_kernel
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         None, grad_clip, lazy_mode, multi_precision, name)

    def _apply_fused(self, p, g, lr, decay):
        from ..ops.pallas_kernels.fused_adamw import fused_adamw_update

        m1 = self._get_accumulator("moment1", p)
        m2 = self._get_accumulator("moment2", p)
        dispatch.note_read(m1)
        dispatch.note_read(m2)
        new_p, new_m1, new_m2 = fused_adamw_update(
            p._value, g._value, m1._value, m2._value,
            lr, self._aux_state[0]._value, self._aux_state[1]._value,
            beta1=self._beta1, beta2=self._beta2, eps=self._epsilon,
            wd=(self._wd_coeff if decay else 0.0))
        m1._set_value(new_m1)
        m2._set_value(new_m2)
        self._write_param(p, new_p)

    def _apply_one(self, p, g):
        lr = self._lr_value()
        if self._lr_ratio is not None:
            lr = lr * self._lr_ratio(p)
        decay = True
        if self._apply_decay_fun is not None:
            decay = self._apply_decay_fun(p.name or "")
        master = getattr(self, "_master", {}).get(id(p))
        if self._use_fused_kernel and master is None:
            # fused path covers the single-precision regime (the pure-bf16
            # bench path); master-weight updates stay XLA-composed
            self._apply_fused(p, g, lr, decay)
            return
        m1 = self._get_accumulator("moment1", p)
        m2 = self._get_accumulator("moment2", p)
        dispatch.note_read(m1)
        dispatch.note_read(m2)
        if master is not None:
            dispatch.note_read(master)
            pv = master._value
        else:
            pv = p._value.astype(jnp.float32)
        g_raw = g._value.astype(jnp.float32)
        new_m1 = self._beta1 * m1._value + (1 - self._beta1) * g_raw
        new_m2 = self._beta2 * m2._value + (1 - self._beta2) * g_raw * g_raw
        b1p = self._aux_state[0]._value
        b2p = self._aux_state[1]._value
        m1_hat = new_m1 / (1 - b1p)
        m2_hat = new_m2 / (1 - b2p)
        new_p = pv
        if decay:
            new_p = new_p * (1.0 - lr * self._wd_coeff)
        new_p = new_p - lr * m1_hat / (jnp.sqrt(m2_hat) + self._epsilon)
        m1._set_value(new_m1.astype(m1._value.dtype))
        m2._set_value(new_m2.astype(m2._value.dtype))
        if master is not None:
            master._set_value(new_p)
        self._write_param(p, new_p.astype(p._value.dtype))


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-08,
                 parameters=None, weight_decay=None, grad_clip=None, name=None):
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)

    def _create_accumulators(self, params):
        for p in params:
            self._add_accumulator("moment", p)
            self._add_accumulator("inf_norm", p)
        self._aux_state[0] = Tensor(jnp.asarray(1.0, jnp.float32))

    @dispatch.no_grad()
    def step(self):
        params_grads = [(p, g) for p, g in self._collect_params_grads() if g is not None]
        if self._grad_clip is not None:
            params_grads = self._grad_clip(params_grads)
        b1p = self._aux_state[0]
        dispatch.note_read(b1p)
        b1p._set_value(b1p._value * self._beta1)
        for p, g in params_grads:
            dispatch.note_read(p)
            self._apply_one(p, g)

    def _apply_one(self, p, g):
        lr = self._lr_value()
        m = self._get_accumulator("moment", p)
        u = self._get_accumulator("inf_norm", p)
        dispatch.note_read(m)
        dispatch.note_read(u)
        g_raw = self._decayed_grad(p, g._value.astype(jnp.float32))
        new_m = self._beta1 * m._value + (1 - self._beta1) * g_raw
        new_u = jnp.maximum(self._beta2 * u._value, jnp.abs(g_raw))
        b1p = self._aux_state[0]._value
        self._write_param(
            p,
            (p._value.astype(jnp.float32) - lr / (1 - b1p) * new_m / (new_u + self._epsilon)).astype(p._value.dtype),
        )
        m._set_value(new_m)
        u._set_value(new_u)


class Lamb(Optimizer):
    """Layer-wise adaptive moments (reference: python/paddle/optimizer/lamb.py)."""

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-6, parameters=None, grad_clip=None,
                 exclude_from_weight_decay_fn=None, name=None):
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon
        self._lamb_wd = lamb_weight_decay
        self._exclude_fn = exclude_from_weight_decay_fn
        super().__init__(learning_rate, parameters, None, grad_clip, name)

    def _create_accumulators(self, params):
        for p in params:
            self._add_accumulator("moment1", p)
            self._add_accumulator("moment2", p)
        self._aux_state[0] = Tensor(jnp.asarray(1.0, jnp.float32))
        self._aux_state[1] = Tensor(jnp.asarray(1.0, jnp.float32))

    @dispatch.no_grad()
    def step(self):
        params_grads = [(p, g) for p, g in self._collect_params_grads() if g is not None]
        if self._grad_clip is not None:
            params_grads = self._grad_clip(params_grads)
        b1p, b2p = self._aux_state[0], self._aux_state[1]
        dispatch.note_read(b1p)
        dispatch.note_read(b2p)
        b1p._set_value(b1p._value * self._beta1)
        b2p._set_value(b2p._value * self._beta2)
        for p, g in params_grads:
            dispatch.note_read(p)
            self._apply_one(p, g)

    def _apply_one(self, p, g):
        lr = self._lr_value()
        m1 = self._get_accumulator("moment1", p)
        m2 = self._get_accumulator("moment2", p)
        dispatch.note_read(m1)
        dispatch.note_read(m2)
        pv = p._value.astype(jnp.float32)
        g_raw = g._value.astype(jnp.float32)
        new_m1 = self._beta1 * m1._value + (1 - self._beta1) * g_raw
        new_m2 = self._beta2 * m2._value + (1 - self._beta2) * g_raw * g_raw
        m1_hat = new_m1 / (1 - self._aux_state[0]._value)
        m2_hat = new_m2 / (1 - self._aux_state[1]._value)
        r = m1_hat / (jnp.sqrt(m2_hat) + self._epsilon)
        wd = self._lamb_wd
        if self._exclude_fn is not None and self._exclude_fn(p):
            wd = 0.0
        update = r + wd * pv
        w_norm = jnp.linalg.norm(pv)
        u_norm = jnp.linalg.norm(update)
        trust = jnp.where((w_norm > 0) & (u_norm > 0), w_norm / u_norm, 1.0)
        m1._set_value(new_m1.astype(m1._value.dtype))
        m2._set_value(new_m2.astype(m2._value.dtype))
        self._write_param(p, (pv - lr * trust * update).astype(p._value.dtype))


class Adadelta(Optimizer):
    """reference python/paddle/optimizer/adadelta.py (phi adadelta
    kernel): E[g^2] and E[dx^2] running averages; the update needs no
    global learning rate (lr multiplies the final delta for parity)."""

    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None,
                 name=None):
        self._epsilon = epsilon
        self._rho = rho
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name)

    def _create_accumulators(self, params):
        for p in params:
            self._add_accumulator("avg_squared_grad", p)
            self._add_accumulator("avg_squared_update", p)

    def _apply_one(self, p, g):
        lr = self._lr_value()
        eg = self._get_accumulator("avg_squared_grad", p)
        ex = self._get_accumulator("avg_squared_update", p)
        dispatch.note_read(eg)
        dispatch.note_read(ex)
        gv = self._decayed_grad(p, g._value.astype(jnp.float32))
        rho, eps = self._rho, self._epsilon
        new_eg = rho * eg._value + (1 - rho) * gv * gv
        delta = jnp.sqrt((ex._value + eps) / (new_eg + eps)) * gv
        new_ex = rho * ex._value + (1 - rho) * delta * delta
        eg._set_value(new_eg)
        ex._set_value(new_ex)
        self._write_param(
            p, (p._value.astype(jnp.float32) - lr * delta)
            .astype(p._value.dtype))
