"""Loss functionals (reference: python/paddle/nn/functional/loss.py;
softmax_with_cross_entropy kernel phi/kernels/gpu/cross_entropy_kernel.cu)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...tensor import Tensor
from ...ops import dispatch
from ...ops._factory import ensure_tensor


def _reduce(out, reduction):
    if reduction == "mean":
        return jnp.mean(out)
    if reduction == "sum":
        return jnp.sum(out)
    return out


def cross_entropy(
    input,  # noqa: A002
    label,
    weight=None,
    ignore_index=-100,
    reduction="mean",
    soft_label=False,
    axis=-1,
    use_softmax=True,
    label_smoothing=0.0,
    name=None,
):
    input, label = ensure_tensor(input), ensure_tensor(label)
    tensors = [input, label]
    has_w = weight is not None
    if has_w:
        tensors.append(ensure_tensor(weight))

    def fn(logits, lab, *w):
        lp = jax.nn.log_softmax(logits, axis=axis) if use_softmax else jnp.log(
            jnp.maximum(logits, 1e-30)
        )
        n_classes = logits.shape[axis]
        if soft_label:
            tgt = lab
            if label_smoothing > 0:
                tgt = tgt * (1 - label_smoothing) + label_smoothing / n_classes
            loss = -jnp.sum(tgt * lp, axis=axis)
        else:
            lab_idx = lab
            if lab_idx.ndim == lp.ndim:
                lab_idx = jnp.squeeze(lab_idx, axis=axis)
            lab_idx = lab_idx.astype(jnp.int32)
            valid = lab_idx != ignore_index
            safe = jnp.where(valid, lab_idx, 0)
            picked = jnp.take_along_axis(
                lp, jnp.expand_dims(safe, axis), axis=axis
            ).squeeze(axis)
            if label_smoothing > 0:
                smooth = -jnp.mean(lp, axis=axis)
                loss = (1 - label_smoothing) * (-picked) + label_smoothing * smooth
            else:
                loss = -picked
            loss = jnp.where(valid, loss, 0.0)
            if has_w:
                wv = jnp.take(w[0], safe)
                wv = jnp.where(valid, wv, 0.0)
                loss = loss * wv
                if reduction == "mean":
                    return jnp.sum(loss) / jnp.maximum(jnp.sum(wv), 1e-12)
            if reduction == "mean":
                denom = jnp.maximum(jnp.sum(valid.astype(loss.dtype)), 1.0)
                return jnp.sum(loss) / denom
        return _reduce(loss, reduction)

    return dispatch.apply(fn, *tensors, op_name="cross_entropy")


def softmax_with_cross_entropy(
    logits, label, soft_label=False, ignore_index=-100, numeric_stable_mode=True,
    return_softmax=False, axis=-1,
):
    out = cross_entropy(
        logits, label, soft_label=soft_label, ignore_index=ignore_index,
        reduction="none", axis=axis,
    )
    out = out.unsqueeze(axis) if not soft_label else out
    if return_softmax:
        from .activation import softmax

        return out, softmax(logits, axis=axis)
    return out


def mse_loss(input, label, reduction="mean", name=None):  # noqa: A002
    input, label = ensure_tensor(input), ensure_tensor(label)
    return dispatch.apply(
        lambda a, b: _reduce(jnp.square(a - b), reduction), input, label, op_name="mse_loss"
    )


def l1_loss(input, label, reduction="mean", name=None):  # noqa: A002
    input, label = ensure_tensor(input), ensure_tensor(label)
    return dispatch.apply(
        lambda a, b: _reduce(jnp.abs(a - b), reduction), input, label, op_name="l1_loss"
    )


def smooth_l1_loss(input, label, reduction="mean", delta=1.0, name=None):  # noqa: A002
    input, label = ensure_tensor(input), ensure_tensor(label)

    def fn(a, b):
        d = jnp.abs(a - b)
        loss = jnp.where(d < delta, 0.5 * d * d / delta, d - 0.5 * delta)
        # paddle multiplies by delta
        return _reduce(loss * delta, reduction)

    return dispatch.apply(fn, input, label, op_name="smooth_l1_loss")


def huber_loss(input, label, delta=1.0, reduction="mean", name=None):  # noqa: A002
    """reference phi huber_loss: quadratic below delta, linear above
    (NOT delta-rescaled like smooth_l1)."""
    input, label = ensure_tensor(input), ensure_tensor(label)

    def fn(a, b):
        d = jnp.abs(a - b)
        loss = jnp.where(d <= delta, 0.5 * d * d, delta * (d - 0.5 * delta))
        return _reduce(loss, reduction)

    return dispatch.apply(fn, input, label, op_name="huber_loss")


def log_loss(input, label, epsilon=1e-4, name=None):  # noqa: A002
    """reference phi log_loss: -y*log(p+eps) - (1-y)*log(1-p+eps),
    elementwise (no reduction)."""
    input, label = ensure_tensor(input), ensure_tensor(label)

    def fn(p, y):
        return (-y * jnp.log(p + epsilon)
                - (1.0 - y) * jnp.log(1.0 - p + epsilon))

    return dispatch.apply(fn, input, label, op_name="log_loss")


def margin_cross_entropy(logits, label, margin1=1.0, margin2=0.5,
                         margin3=0.0, scale=64.0, group=None,
                         return_softmax=False, reduction="mean", name=None):
    """reference phi margin_cross_entropy (ArcFace/CosFace margins):
    the target-class cosine logit is replaced by
    cos(margin1*theta + margin2) - margin3, everything scaled by
    ``scale`` before softmax cross-entropy.  Single-group path (the
    reference's model-parallel class split rides the mp sharding of the
    logits instead)."""
    logits, label = ensure_tensor(logits), ensure_tensor(label)

    def fn(z, y):
        if y.ndim == z.ndim:  # [N, 1] labels (paddle convention)
            y = jnp.squeeze(y, axis=-1)
        onehot = jax.nn.one_hot(y, z.shape[-1], dtype=z.dtype)
        # clip strictly inside (-1, 1): d(arccos) blows up at the
        # boundary and a converged class hits exactly 1.0 in fp32
        eps = 1e-6
        cos_t = jnp.clip(jnp.sum(onehot * z, axis=-1),
                         -1.0 + eps, 1.0 - eps)
        theta = jnp.arccos(cos_t)
        target = jnp.cos(margin1 * theta + margin2) - margin3
        mod = z + onehot * (target - cos_t)[:, None]
        mod = mod * scale
        logp = jax.nn.log_softmax(mod, axis=-1)
        loss = -jnp.sum(onehot * logp, axis=-1)
        return _reduce(loss, reduction), jnp.exp(logp)

    loss, sm = dispatch.apply(fn, logits, label,
                              op_name="margin_cross_entropy")
    if return_softmax:
        return loss, sm
    return loss


def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean", name=None):  # noqa: A002
    input, label = ensure_tensor(input), ensure_tensor(label)
    tensors = [input, label]
    has_w = weight is not None
    if has_w:
        tensors.append(ensure_tensor(weight))

    def fn(lp, lab, *w):
        lab = lab.astype(jnp.int32)
        valid = lab != ignore_index
        safe = jnp.where(valid, lab, 0)
        picked = jnp.take_along_axis(lp, safe[:, None], axis=1).squeeze(1)
        loss = -picked
        if has_w:
            wv = jnp.take(w[0], safe)
            loss = loss * wv
            loss = jnp.where(valid, loss, 0.0)
            if reduction == "mean":
                return jnp.sum(loss) / jnp.maximum(jnp.sum(jnp.where(valid, wv, 0.0)), 1e-12)
        loss = jnp.where(valid, loss, 0.0)
        if reduction == "mean":
            return jnp.sum(loss) / jnp.maximum(jnp.sum(valid.astype(loss.dtype)), 1.0)
        return _reduce(loss, reduction)

    return dispatch.apply(fn, *tensors, op_name="nll_loss")


def binary_cross_entropy(input, label, weight=None, reduction="mean", name=None):  # noqa: A002
    input, label = ensure_tensor(input), ensure_tensor(label)
    tensors = [input, label]
    has_w = weight is not None
    if has_w:
        tensors.append(ensure_tensor(weight))

    def fn(p, y, *w):
        p = jnp.clip(p, 1e-12, 1 - 1e-12)
        loss = -(y * jnp.log(p) + (1 - y) * jnp.log(1 - p))
        if has_w:
            loss = loss * w[0]
        return _reduce(loss, reduction)

    return dispatch.apply(fn, *tensors, op_name="binary_cross_entropy")


def binary_cross_entropy_with_logits(
    logit, label, weight=None, reduction="mean", pos_weight=None, name=None
):
    logit, label = ensure_tensor(logit), ensure_tensor(label)
    tensors = [logit, label]
    has_w = weight is not None
    has_pw = pos_weight is not None
    if has_w:
        tensors.append(ensure_tensor(weight))
    if has_pw:
        tensors.append(ensure_tensor(pos_weight))

    def fn(z, y, *rest):
        i = 0
        w = None
        pw = None
        if has_w:
            w = rest[i]
            i += 1
        if has_pw:
            pw = rest[i]
        # stable: max(z,0) - z*y + log(1+exp(-|z|)), with pos_weight factor
        if pw is not None:
            log_w = (pw - 1) * y + 1
            loss = (1 - y) * z + log_w * (jnp.logaddexp(0.0, -jnp.abs(z)) + jnp.maximum(-z, 0.0))
        else:
            loss = jnp.maximum(z, 0) - z * y + jnp.logaddexp(0.0, -jnp.abs(z))
        if w is not None:
            loss = loss * w
        return _reduce(loss, reduction)

    return dispatch.apply(fn, *tensors, op_name="bce_with_logits")


def kl_div(input, label, reduction="mean", name=None):  # noqa: A002
    input, label = ensure_tensor(input), ensure_tensor(label)

    def fn(lp, y):
        loss = y * (jnp.log(jnp.maximum(y, 1e-30)) - lp)
        if reduction == "batchmean":
            return jnp.sum(loss) / lp.shape[0]
        return _reduce(loss, reduction)

    return dispatch.apply(fn, input, label, op_name="kl_div")


def hinge_embedding_loss(input, label, margin=1.0, reduction="mean", name=None):  # noqa: A002
    input, label = ensure_tensor(input), ensure_tensor(label)

    def fn(a, y):
        loss = jnp.where(y == 1, a, jnp.maximum(0.0, margin - a))
        return _reduce(loss, reduction)

    return dispatch.apply(fn, input, label, op_name="hinge_embedding_loss")


def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean", name=None):  # noqa: A002
    input, other, label = ensure_tensor(input), ensure_tensor(other), ensure_tensor(label)

    def fn(a, b, y):
        loss = jnp.maximum(0.0, -y * (a - b) + margin)
        return _reduce(loss, reduction)

    return dispatch.apply(fn, input, other, label, op_name="margin_ranking_loss")


def cosine_embedding_loss(input1, input2, label, margin=0.0, reduction="mean", name=None):
    input1, input2, label = ensure_tensor(input1), ensure_tensor(input2), ensure_tensor(label)

    def fn(a, b, y):
        cos = jnp.sum(a * b, axis=-1) / jnp.maximum(
            jnp.linalg.norm(a, axis=-1) * jnp.linalg.norm(b, axis=-1), 1e-12
        )
        loss = jnp.where(y == 1, 1 - cos, jnp.maximum(0.0, cos - margin))
        return _reduce(loss, reduction)

    return dispatch.apply(fn, input1, input2, label, op_name="cosine_embedding_loss")


def triplet_margin_loss(
    input, positive, negative, margin=1.0, p=2.0, epsilon=1e-6, swap=False,  # noqa: A002
    reduction="mean", name=None,
):
    input, positive, negative = (
        ensure_tensor(input), ensure_tensor(positive), ensure_tensor(negative),
    )

    def fn(a, pos, neg):
        def dst(u, v):
            return jnp.power(jnp.sum(jnp.power(jnp.abs(u - v) + epsilon, p), axis=-1), 1.0 / p)

        d_pos = dst(a, pos)
        d_neg = dst(a, neg)
        if swap:
            d_neg = jnp.minimum(d_neg, dst(pos, neg))
        loss = jnp.maximum(0.0, d_pos - d_neg + margin)
        return _reduce(loss, reduction)

    return dispatch.apply(fn, input, positive, negative, op_name="triplet_margin_loss")


def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0, reduction="mean", norm_by_times=False):
    """Connectionist Temporal Classification loss.

    Reference: paddle/phi/kernels/cpu/warpctc_kernel.cc (dynloaded warpctc
    C library) and python/paddle/nn/functional/loss.py ctc_loss.  TPU-native
    redesign: the alpha (forward) recursion of Graves et al. runs in log
    space as one ``lax.scan`` over time with the whole batch and the
    2L+1-wide extended label tape vectorized per step — static shapes, no
    host loop, and the backward pass is JAX autodiff through the scan
    (replacing warpctc's hand-written beta recursion).

    ``log_probs``: [T, B, C] UNNORMALIZED logits (the reference's warpctc
    applies softmax internally; so do we).  ``labels``: int [B, Lmax].
    """
    log_probs = ensure_tensor(log_probs)
    labels = ensure_tensor(labels)
    input_lengths = ensure_tensor(input_lengths)
    label_lengths = ensure_tensor(label_lengths)
    NEG = -1e30

    def fn(lp, lab, ilen, llen):
        T, B, C = lp.shape
        lp = jax.nn.log_softmax(lp.astype(jnp.float32), axis=-1)
        lab = lab.astype(jnp.int32)
        ilen = ilen.astype(jnp.int32)
        llen = llen.astype(jnp.int32)
        Lmax = lab.shape[1]
        S = 2 * Lmax + 1

        s = jnp.arange(S)
        lab_idx = jnp.clip((s - 1) // 2, 0, max(Lmax - 1, 0))
        # extended tape: blank, l1, blank, l2, ..., blank   [B, S]
        ext = jnp.where((s % 2 == 0)[None, :], blank,
                        jnp.take_along_axis(
                            lab, jnp.broadcast_to(lab_idx[None, :], (B, S)),
                            axis=1))
        ext_prev2 = jnp.concatenate(
            [jnp.full((B, 2), -1, jnp.int32), ext[:, :-2]], axis=1)
        allow_skip = ((s >= 2)[None, :] & (ext != blank)
                      & (ext != ext_prev2))
        # positions beyond this sample's tape (s > 2*llen) stay dead
        valid_s = s[None, :] <= (2 * llen)[:, None]

        emit0 = jnp.take_along_axis(lp[0], ext, axis=1)  # [B, S]
        alpha0 = jnp.where((s[None, :] <= 1) & valid_s, emit0, NEG)

        def step(alpha, xs):
            lp_t, t = xs
            emit = jnp.take_along_axis(lp_t, ext, axis=1)
            a2 = jnp.concatenate(
                [jnp.full((B, 1), NEG), alpha[:, :-1]], axis=1)
            a3 = jnp.concatenate(
                [jnp.full((B, 2), NEG), alpha[:, :-2]], axis=1)
            a3 = jnp.where(allow_skip, a3, NEG)
            new = jnp.logaddexp(jnp.logaddexp(alpha, a2), a3) + emit
            new = jnp.where(valid_s, new, NEG)
            # frozen past each sample's input length (loss reads T_b-1)
            new = jnp.where((t < ilen[:, None]), new, alpha)
            return new, None

        alpha, _ = jax.lax.scan(
            step, alpha0, (lp[1:], jnp.arange(1, T)))
        end = jnp.clip(2 * llen, 0, S - 1)[:, None]          # final blank
        pre = jnp.clip(2 * llen - 1, 0, S - 1)[:, None]      # final label
        a_end = jnp.take_along_axis(alpha, end, axis=1)[:, 0]
        a_pre = jnp.where(
            llen > 0, jnp.take_along_axis(alpha, pre, axis=1)[:, 0], NEG)
        total = jnp.logaddexp(a_end, a_pre)
        # infeasible samples (input shorter than the label tape needs)
        # report inf like warpctc/torch, not the finite NEG sentinel —
        # isinf-based bad-sample filters must keep working
        loss = jnp.where(total <= NEG / 2, jnp.inf, -total)  # [B]
        if norm_by_times:
            # reference semantics: gradients (not the loss value) are
            # normalized by the number of time steps — value-preserving
            # grad rescale via the stop_gradient identity
            scaled = loss / jnp.maximum(ilen, 1).astype(loss.dtype)
            loss = scaled + jax.lax.stop_gradient(loss - scaled)
        if reduction == "mean":
            # reference mean: per-sample loss / label_length, then mean
            return jnp.mean(loss / jnp.maximum(llen, 1).astype(loss.dtype))
        if reduction == "sum":
            return jnp.sum(loss)
        return loss

    return dispatch.apply(fn, log_probs, labels, input_lengths,
                          label_lengths, op_name="ctc_loss")


def square_error_cost(input, label):  # noqa: A002
    input, label = ensure_tensor(input), ensure_tensor(label)
    return dispatch.apply(lambda a, b: jnp.square(a - b), input, label, op_name="square_error_cost")


@jax.custom_vjp
def _lm_head_dot(h, w):
    """Chunk logits ``h [c, H] x w [V, H] -> fp32 [c, V]`` with a backward
    that casts the fp32 cotangent down to the operand dtype BEFORE the
    dW/dh contractions.  jax's derived vjp would contract fp32 d_logits
    against the bf16 operands directly — a silent mixed-dtype promotion
    that pushes both backward matmuls off the bf16 MXU path (graph_lint
    GL001; the owned flash kernel applies the same ``ds.astype(q.dtype)``
    discipline).  fp32 operands are untouched (the cast is a no-op)."""
    return jax.lax.dot_general(h, w, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _lm_head_dot_fwd(h, w):
    return _lm_head_dot(h, w), (h, w)


def _lm_head_dot_bwd(res, g):
    h, w = res
    gh = g.astype(h.dtype)
    gw = g.astype(w.dtype)
    # dh [c, H] = g [c, V] . w [V, H];  dw [V, H] = g^T [V, c] . h [c, H]
    dh = jax.lax.dot_general(gh, w, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    dw = jax.lax.dot_general(gw, h, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return dh.astype(h.dtype), dw.astype(w.dtype)


_lm_head_dot.defvjp(_lm_head_dot_fwd, _lm_head_dot_bwd)


def fused_linear_cross_entropy(hidden, weight, labels, *, chunk_tokens=2048,
                               compute_dtype=None, reduction="mean"):
    """LM-head matmul + softmax cross entropy without materializing the full
    [N, V] logits for backward.

    Reference analog: phi/kernels/gpu/cross_entropy_kernel.cu (fused
    softmax+CE) and operators/fused — but redesigned for the TPU memory
    hierarchy: tokens are processed in chunks under ``jax.checkpoint`` inside
    a ``lax.scan``, so at any moment only one chunk's logits live in HBM
    (fwd AND bwd — backward recomputes the chunk's logits, forms the
    softmax-minus-onehot product locally, and accumulates dW / dhidden).

    hidden: [..., H]; weight: [V, H] (tied LM head); labels: int[...].
    Returns scalar (mean/sum over tokens) or per-token loss [N].
    """
    hidden, weight, labels = (
        ensure_tensor(hidden), ensure_tensor(weight), ensure_tensor(labels),
    )

    # the scope is entered INSIDE what jax.vjp differentiates, so the backward
    # pass keeps it (transpose(jvp(lm_head))): docs/observability.md
    @jax.named_scope("lm_head")
    def fn(h, w, lab):
        hs = h.shape[-1]
        h2 = h.reshape(-1, hs)
        lab1 = lab.reshape(-1).astype(jnp.int32)
        n = h2.shape[0]
        c = min(chunk_tokens, n)
        # pad to a whole number of chunks (padded tokens masked out)
        pad = (-n) % c
        if pad:
            h2 = jnp.concatenate([h2, jnp.zeros((pad, hs), h2.dtype)], 0)
            lab1 = jnp.concatenate([lab1, jnp.zeros((pad,), lab1.dtype)], 0)
        n_chunks = (n + pad) // c
        hc = h2.reshape(n_chunks, c, hs)
        lc = lab1.reshape(n_chunks, c)
        cdt = compute_dtype or h.dtype
        wt = w.astype(cdt)

        @jax.checkpoint
        def chunk_loss(hx, lx):
            # fp32 accumulation on the MXU out of low-precision operands
            logits = _lm_head_dot(hx.astype(cdt), wt)  # [c, V] fp32
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, lx[:, None], axis=-1)[:, 0]
            return lse - picked  # [c]

        def step(_, xs):
            hx, lx = xs
            return None, chunk_loss(hx, lx)

        _, losses = jax.lax.scan(step, None, (hc, lc))
        losses = losses.reshape(-1)[:n]
        if reduction == "mean":
            return jnp.mean(losses)
        if reduction == "sum":
            return jnp.sum(losses)
        return losses

    return dispatch.apply(fn, hidden, weight, labels,
                          op_name="fused_linear_cross_entropy")


def hsigmoid_loss(input, label, num_classes, weight, bias=None,  # noqa: A002
                  path_table=None, path_code=None, is_sparse=False,
                  name=None):
    """Hierarchical sigmoid loss (reference python/paddle/nn/functional/
    loss.py:872, phi hsigmoid_loss kernel over funcs/matrix_bit_code.h).

    Default complete-binary-tree coding (SimpleCode): for class l the
    code is c = l + num_classes; path node j has weight row
    (c >> (j+1)) - 1 and binary target bit j of c.  TPU-native: the whole
    [N, max_path] node/bit tables are computed with integer shifts, the
    node weights come from ONE gather, and the loss is a masked
    softplus(z) - bit*z sum — no per-sample host loop.  ``is_sparse`` is
    accepted for API parity (XLA gathers are already sparse-friendly).
    """
    input, label, weight = (ensure_tensor(input), ensure_tensor(label),
                            ensure_tensor(weight))
    bias_t = ensure_tensor(bias) if bias is not None else None
    pt_t = ensure_tensor(path_table) if path_table is not None else None
    pc_t = ensure_tensor(path_code) if path_code is not None else None

    def fn(x, lab, w, *rest):
        rest = list(rest)
        b = rest.pop(0) if bias_t is not None else None
        if pt_t is not None:
            ptab = rest.pop(0).astype(jnp.int32)
            pcode = rest.pop(0).astype(jnp.float32)
            valid = ptab >= 0
            idx = jnp.maximum(ptab, 0)
            bit = pcode
        else:
            c = lab.astype(jnp.int32) + num_classes        # [N]
            max_len = int(2 * num_classes - 1).bit_length() - 1
            j = jnp.arange(max_len)
            ks = jnp.arange(1, max_len + 2)
            length = jnp.sum((c[:, None] >> ks) > 0, axis=1)  # bitlen-1
            valid = j[None, :] < length[:, None]
            idx = jnp.maximum((c[:, None] >> (j[None, :] + 1)) - 1, 0)
            bit = ((c[:, None] >> j[None, :]) & 1).astype(jnp.float32)
        wn = w[idx]                                        # [N, L, D]
        z = jnp.einsum("nld,nd->nl", wn, x)
        if b is not None:
            z = z + b.reshape(-1)[idx]
        per_node = jax.nn.softplus(z) - bit * z
        loss = jnp.sum(jnp.where(valid, per_node, 0.0), axis=1)
        return loss[:, None]                               # [N, 1]

    args = [input, label, weight]
    if bias_t is not None:
        args.append(bias_t)
    if pt_t is not None:
        args.extend([pt_t, pc_t])
    return dispatch.apply(fn, *args, op_name="hsigmoid_loss")


def rnnt_loss(input, label, input_lengths, label_lengths, blank=0,  # noqa: A002
              fastemit_lambda=0.001, reduction="mean", name=None):
    """RNN-Transducer loss (reference python/paddle/nn/functional/
    loss.py:1912, dynloaded warp-transducer).

    TPU-native redesign: the alpha lattice recurrence
    a[t,u] = logaddexp(a[t-1,u] + blank(t-1,u), a[t,u-1] + emit(t,u-1))
    is evaluated by ONE ``lax.scan`` over ANTI-DIAGONALS d = t + u — both
    dependencies live on diagonal d-1, so every cell of a diagonal
    computes in parallel (vectorized over batch and u).  No per-cell
    host loop, static shapes, autodiff backward.  FastEmit regularization
    scales the emission-path gradient by (1 + lambda) via a
    value-preserving stop_gradient identity (warp-transducer's fastemit
    gradient scaling).

    input: [B, Tmax, Umax+1, V] logits (softmax applied internally, like
    the reference); label: int [B, Umax].
    """
    input, label = ensure_tensor(input), ensure_tensor(label)
    input_lengths = ensure_tensor(input_lengths)
    label_lengths = ensure_tensor(label_lengths)
    NEG = -1e30

    def fn(lp, lab, ilen, ulen):
        B, T, U1, V = lp.shape
        lp = jax.nn.log_softmax(lp.astype(jnp.float32), axis=-1)
        lab = lab.astype(jnp.int32)
        ilen = ilen.astype(jnp.int32)
        ulen = ulen.astype(jnp.int32)
        blank_lp = lp[..., blank]                       # [B, T, U1]
        emit_lp = jnp.take_along_axis(
            lp[:, :, :U1 - 1, :],
            jnp.clip(lab, 0, V - 1)[:, None, :, None], axis=-1)[..., 0]
        if fastemit_lambda:
            emit_lp = ((1.0 + fastemit_lambda) * emit_lp
                       - fastemit_lambda * jax.lax.stop_gradient(emit_lp))

        u = jnp.arange(U1)
        alpha0 = jnp.where(u == 0, 0.0, NEG)[None, :].repeat(B, 0)
        # per-diagonal slices via explicit [B, U1] advanced indexing
        bidx = jnp.arange(B)[:, None]

        def step(alpha, d):
            t = d - u                                   # [U1]
            tb = jnp.clip(t - 1, 0, T - 1)
            from_blank = alpha + blank_lp[bidx, tb[None, :], u[None, :]]
            ok_blank = (t >= 1) & (t - 1 <= T - 1)      # t-1 in [0, T-1]
            from_blank = jnp.where(ok_blank[None, :], from_blank, NEG)
            te = jnp.clip(t, 0, T - 1)
            up = jnp.clip(u - 1, 0, U1 - 2)
            prev_emit = jnp.concatenate(
                [jnp.full((B, 1), NEG), alpha[:, :-1]], axis=1)
            from_emit = prev_emit + emit_lp[bidx, te[None, :], up[None, :]]
            ok_emit = (u >= 1) & (t >= 0) & (t <= T - 1)
            from_emit = jnp.where(ok_emit[None, :], from_emit, NEG)
            new = jnp.logaddexp(from_blank, from_emit)
            return new, new

        ds = jnp.arange(1, T + U1 - 1)
        _, diags = jax.lax.scan(step, alpha0, ds)       # [D-1, B, U1]
        diags = jnp.concatenate([alpha0[None], diags], 0)  # [D, B, U1]
        d_final = jnp.clip(ilen - 1 + ulen, 0, T + U1 - 2)
        a_final = diags[d_final, jnp.arange(B), ulen]
        loss = -(a_final
                 + blank_lp[jnp.arange(B), jnp.clip(ilen - 1, 0, T - 1),
                            ulen])
        if reduction == "mean":
            return jnp.sum(loss) / B                     # reference: sum/B
        if reduction == "sum":
            return jnp.sum(loss)
        return loss

    return dispatch.apply(fn, input, label, input_lengths, label_lengths,
                          op_name="rnnt_loss")
