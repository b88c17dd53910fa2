"""TRUE int8 execution backend (reference analog: the int8 compute
kernels behind quantization — paddle/phi/kernels/fusion/
fused_linear_int8 family and the inference engine's quantized ops; the
python QDQ pass in quantization/ptq.py only SIMULATES them).

TPU-native: the MXU multiplies int8 operands natively at double the
bf16 rate, so the real quantized path is one
``lax.dot_general(int8, int8, preferred_element_type=int32)`` with
per-output-channel weight scales and per-row (per-token) activation
scales (calibrated static, or dynamic absmax) applied as a cheap
epilogue — no custom kernel needed, the compiler owns the tiling.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..nn.layer import Layer
from ..ops import dispatch
from ..ops._factory import ensure_tensor
from ..tensor import Tensor

__all__ = ["quantized_matmul", "quantized_matmul_raw", "Int8Linear",
           "quantize_for_serving"]


def quantized_matmul_raw(xv, wq, ws, b=None, act_scale=None):
    """jnp-level body of :func:`quantized_matmul` — for callers that are
    ALREADY inside a dispatched/trace context (the stacked decoder's
    serving block body composes this per projection inside one
    lax.scan).  xv: float [..., K]; wq: int8 [K, N]; ws: fp32 [N];
    returns fp32 [..., N].

    Dynamic activation scales are PER-ROW (one absmax per token over its
    K features), not per-tensor: a token's quantization grid then never
    depends on which other tokens share its batch, so a batched serving
    step reproduces the single-request result bit-for-bit — the
    batch-invariance the serving gate pins."""
    xf = xv.astype(jnp.float32)
    if act_scale is not None:
        xs = jnp.asarray(act_scale, jnp.float32)
    else:
        xs = jnp.max(jnp.abs(xf), axis=-1, keepdims=True) / 127.0 + 1e-12
    xq = jnp.clip(jnp.round(xf / xs), -127, 127).astype(jnp.int8)
    acc = jax.lax.dot_general(
        xq, wq, (((xv.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    out = acc.astype(jnp.float32) * xs * ws.astype(jnp.float32)
    if b is not None:
        out = out + b.astype(jnp.float32)
    return out


def quantized_matmul(x, w_int8, w_scale, bias=None, act_scale=None,
                     name=None):
    """y = dequant(int8(x) @ w_int8) — int32 accumulation on the MXU.

    x: float [..., K]; w_int8: int8 [K, N]; w_scale: float [N]
    (per-output-channel); act_scale: None -> dynamic per-row absmax
    quantization of x, else the calibrated static scale.  Inference
    path: the round/clip quantizer is not differentiated (use QAT's
    fake-quant for training).
    """
    x = ensure_tensor(x)
    w_int8 = ensure_tensor(w_int8)
    w_scale = ensure_tensor(w_scale)
    args = [x, w_int8, w_scale]
    if bias is not None:
        args.append(ensure_tensor(bias))

    def fn(xv, wq, ws, *b):
        return quantized_matmul_raw(xv, wq, ws,
                                    b=b[0] if b else None,
                                    act_scale=act_scale)

    return dispatch.apply_nondiff(fn, *args)


class Int8Linear(Layer):
    """Drop-in inference replacement for a calibrated Linear: weights
    stored AS int8 (4x smaller than fp32, feeding the MXU int8 path)
    with per-output-channel scales."""

    def __init__(self, linear, act_scale=None):
        super().__init__()
        w = np.asarray(linear.weight._value, np.float32)   # [in, out]
        scale = np.abs(w).max(axis=0) / 127.0 + 1e-12      # per out-chan
        wq = np.clip(np.round(w / scale), -127, 127).astype(np.int8)
        # registered as BUFFERS so the int8 weights and scales persist
        # through state_dict like any other model state
        self.register_buffer("weight_int8", Tensor(jnp.asarray(wq)))
        self.register_buffer(
            "w_scale", Tensor(jnp.asarray(scale.astype(np.float32))))
        self.bias = getattr(linear, "bias", None)
        self._act_scale = (float(act_scale) if act_scale is not None
                           else None)

    def forward(self, x):
        return quantized_matmul(x, self.weight_int8, self.w_scale,
                                bias=self.bias,
                                act_scale=self._act_scale)


def _quantize_lm_head(model, w):
    """Tied-embedding LM head -> transposed int8 [H, V] + per-vocab-row
    fp32 scales, registered as buffers (``lm_head_int8``/
    ``lm_head_scale``) so ``quantized_matmul(h, ...)`` replaces the
    ``h @ E^T`` vocab projection."""
    arr = np.asarray(w._value, np.float32)              # [V, H]
    scale = np.abs(arr).max(axis=1) / 127.0 + 1e-12     # [V]
    q = np.clip(np.round(arr / scale[:, None]), -127, 127).astype(np.int8)
    model.register_buffer("lm_head_int8", Tensor(jnp.asarray(q.T)))
    model.register_buffer(
        "lm_head_scale", Tensor(jnp.asarray(scale.astype(np.float32))))


def quantize_for_serving(model):
    """PTQ entry point for ``weight_dtype="int8"`` serving: quantize the
    decode hot path's projections (qkv/proj/fc1/fc2 per block + the tied
    LM head) of a ``GPTStackedForPretraining`` to int8 with
    per-output-channel absmax scales, in place: the stacked decoder
    switches its scan params to the int8 variant
    (``GPTStackedDecoder.quantize_weights``).  Idempotent; refuses
    tensor-parallel models (per-channel scales over gathered shards are
    not meaningful — serve those with fp weights).  Returns ``model``.
    """
    if getattr(model, "_weight_int8", False):
        return model
    cfg = getattr(model, "config", None)
    if cfg is not None and getattr(cfg, "use_tensor_parallel", False):
        raise ValueError(
            "quantize_for_serving: tensor-parallel Linear layers are "
            "sharded — per-channel PTQ needs the unsharded weights; "
            "serve TP models with fp weights")
    dec = getattr(model, "decoder", None)
    if not hasattr(dec, "quantize_weights"):
        raise ValueError(
            "quantize_for_serving: expected a GPTStackedForPretraining "
            f"instance (got {type(model).__name__})")
    dec.quantize_weights()
    _quantize_lm_head(model, model.embeddings.word_embeddings.weight)
    model._weight_int8 = True
    return model
