"""Loss functionals (ref: ``python/paddle/nn/functional/loss.py``).

cross_entropy fuses log_softmax + gather (one XLA computation), the TPU
equivalent of the reference's fused ``softmax_with_cross_entropy`` CUDA
kernel (``paddle/phi/kernels/gpu/cross_entropy_kernel.cu``).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ...tensor import Tensor
from ...ops.op_utils import ensure_tensor, nary, unary as _unary

__all__ = [
    "cross_entropy", "softmax_with_cross_entropy", "binary_cross_entropy",
    "binary_cross_entropy_with_logits", "mse_loss", "l1_loss", "nll_loss",
    "smooth_l1_loss", "kl_div", "margin_ranking_loss", "cosine_similarity",
    "cosine_embedding_loss", "hinge_embedding_loss", "triplet_margin_loss",
    "triplet_margin_with_distance_loss", "ctc_loss", "log_loss",
    "square_error_cost", "sigmoid_focal_loss", "dice_loss",
    "npair_loss", "poisson_nll_loss", "gaussian_nll_loss",
    "multi_label_soft_margin_loss", "soft_margin_loss", "rnnt_loss",
    "margin_cross_entropy", "hsigmoid_loss", "multi_margin_loss",
]


def _reduce(out, reduction):
    if reduction == "mean":
        return jnp.mean(out)
    if reduction == "sum":
        return jnp.sum(out)
    return out


def cross_entropy(input, label, weight=None, ignore_index=-100,
                  reduction="mean", soft_label=False, axis=-1,
                  use_softmax=True, label_smoothing=0.0, name=None):
    input, label = ensure_tensor(input), ensure_tensor(label)

    fused = _maybe_fused_cross_entropy(
        input, label, weight=weight, ignore_index=ignore_index,
        reduction=reduction, soft_label=soft_label, axis=axis,
        use_softmax=use_softmax, label_smoothing=label_smoothing)
    if fused is not None:
        return fused

    def f(logits, lab, *w):
        ax = axis % logits.ndim
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=ax) \
            if use_softmax else jnp.log(jnp.maximum(
                logits.astype(jnp.float32), 1e-30))
        n_class = logits.shape[ax]
        if soft_label or (lab.ndim == logits.ndim and
                          lab.shape[ax] == n_class and
                          jnp.issubdtype(lab.dtype, jnp.floating)):
            soft = lab.astype(jnp.float32)
            if label_smoothing > 0:
                soft = soft * (1 - label_smoothing) + label_smoothing / n_class
            loss = -jnp.sum(soft * logp, axis=ax)
            if w:
                wvec = w[0].astype(jnp.float32)
                loss = loss * jnp.sum(soft * wvec, axis=ax)
            return _reduce(loss, reduction)
        lab_i = lab.astype(jnp.int32)
        if lab_i.ndim == logits.ndim:
            lab_i = jnp.squeeze(lab_i, axis=ax)
        onehot_ll = jnp.take_along_axis(
            logp, jnp.expand_dims(jnp.clip(lab_i, 0, n_class - 1), ax),
            axis=ax)
        loss = -jnp.squeeze(onehot_ll, axis=ax)
        if label_smoothing > 0:
            smooth_loss = -jnp.mean(logp, axis=ax)
            loss = (1 - label_smoothing) * loss + label_smoothing * smooth_loss
        valid = (lab_i != ignore_index)
        loss = jnp.where(valid, loss, 0.0)
        if w:
            wvec = w[0].astype(jnp.float32)
            sample_w = jnp.take(wvec, jnp.clip(lab_i, 0, n_class - 1))
            sample_w = jnp.where(valid, sample_w, 0.0)
            loss = loss * sample_w
            if reduction == "mean":
                return jnp.sum(loss) / jnp.maximum(jnp.sum(sample_w), 1e-12)
        if reduction == "mean":
            return jnp.sum(loss) / jnp.maximum(
                jnp.sum(valid.astype(jnp.float32)), 1.0)
        return _reduce(loss, reduction)

    args = [input, label] + ([ensure_tensor(weight)] if weight is not None
                             else [])
    return nary(f, args, name="cross_entropy")


def _maybe_fused_cross_entropy(input, label, *, weight, ignore_index,
                               reduction, soft_label, axis, use_softmax,
                               label_smoothing):
    """Route hard-label cross-entropy through the fused Pallas
    softmax-xent kernel (same gate as ``scaled_dot_product_attention``:
    flag + ``device.pallas_dispatch`` + eligible shape, no runtime
    fallback).
    Returns the loss Tensor, or None when the caller should take the
    XLA path. Soft labels, class weights, and non-trailing class axes
    stay on XLA."""
    from ...framework import flags as _flags
    from ...ops.fused_kernels import record_dispatch as _record
    try:
        eligible = (use_softmax and not soft_label and weight is None
                    and input.ndim >= 1
                    and axis % input.ndim == input.ndim - 1
                    and not (label.ndim == input.ndim
                             and label.shape[-1] == input.shape[-1]
                             and jnp.issubdtype(label._data.dtype,
                                                jnp.floating))
                    and jnp.issubdtype(label._data.dtype, jnp.integer))
    except Exception:
        eligible = False
    if not (eligible and _flags.flag("use_pallas_kernels")):
        _record("fused_softmax_xent", "fallback")
        return None
    from ...framework import device as _device
    if not _device.pallas_dispatch():
        _record("fused_softmax_xent", "fallback")
        return None

    def f(logits, lab):
        from ...ops.fused_kernels import fused_softmax_xent
        n_class = logits.shape[-1]
        lab_i = lab.astype(jnp.int32)
        if lab_i.ndim == logits.ndim:
            lab_i = jnp.squeeze(lab_i, axis=-1)
        rows = int(np.prod(lab_i.shape)) if lab_i.ndim else 1
        loss = fused_softmax_xent(
            logits.reshape(rows, n_class), lab_i.reshape(rows),
            ignore_index=ignore_index, label_smoothing=label_smoothing)
        loss = loss.reshape(lab_i.shape)
        if reduction == "mean":
            valid = (lab_i != ignore_index).astype(jnp.float32)
            return jnp.sum(loss) / jnp.maximum(jnp.sum(valid), 1.0)
        return _reduce(loss, reduction)

    out = nary(f, [input, label], name="cross_entropy")
    _record("fused_softmax_xent", "pallas")
    return out


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, numeric_stable_mode=True,
                               return_softmax=False, axis=-1):
    loss = cross_entropy(logits, label, soft_label=soft_label,
                         ignore_index=ignore_index, reduction="none",
                         axis=axis)
    from .activation import softmax as _softmax
    from ...ops.manipulation import unsqueeze
    loss = unsqueeze(loss, axis)
    if return_softmax:
        return loss, _softmax(logits, axis=axis)
    return loss


def binary_cross_entropy(input, label, weight=None, reduction="mean",
                         name=None):
    def f(p, y, *w):
        p32 = jnp.clip(p.astype(jnp.float32), 1e-12, 1.0 - 1e-7)
        out = -(y * jnp.log(p32) + (1 - y) * jnp.log1p(-p32))
        if w:
            out = out * w[0]
        return _reduce(out, reduction)
    args = [ensure_tensor(input), ensure_tensor(label)]
    if weight is not None:
        args.append(ensure_tensor(weight))
    return nary(f, args, name="binary_cross_entropy")


def binary_cross_entropy_with_logits(logit, label, weight=None,
                                     reduction="mean", pos_weight=None,
                                     name=None):
    def f(z, y, *extra):
        z = z.astype(jnp.float32)
        y = y.astype(jnp.float32)
        i = 0
        w = None
        pw = None
        if weight is not None:
            w = extra[i]; i += 1
        if pos_weight is not None:
            pw = extra[i]; i += 1
        # stable: max(z,0) - z*y + log(1+exp(-|z|)), pos_weight variant
        if pw is not None:
            log_w = (pw - 1) * y + 1
            out = (1 - y) * z + log_w * (jnp.logaddexp(0.0, -jnp.abs(z))
                                         + jnp.maximum(-z, 0.0))
        else:
            out = jnp.maximum(z, 0) - z * y + jnp.logaddexp(0.0, -jnp.abs(z))
        if w is not None:
            out = out * w
        return _reduce(out, reduction)
    args = [ensure_tensor(logit), ensure_tensor(label)]
    if weight is not None:
        args.append(ensure_tensor(weight))
    if pos_weight is not None:
        args.append(ensure_tensor(pos_weight))
    return nary(f, args, name="bce_with_logits")


def mse_loss(input, label, reduction="mean", name=None):
    return nary(lambda a, b: _reduce(jnp.square(a - b), reduction),
                [ensure_tensor(input), ensure_tensor(label)], name="mse_loss")


def square_error_cost(input, label):
    return nary(lambda a, b: jnp.square(a - b),
                [ensure_tensor(input), ensure_tensor(label)],
                name="square_error_cost")


def l1_loss(input, label, reduction="mean", name=None):
    return nary(lambda a, b: _reduce(jnp.abs(a - b), reduction),
                [ensure_tensor(input), ensure_tensor(label)], name="l1_loss")


def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean",
             name=None):
    def f(logp, lab, *w):
        lab_i = lab.astype(jnp.int32)
        n_class = logp.shape[1]
        ll = jnp.take_along_axis(
            logp, jnp.expand_dims(jnp.clip(lab_i, 0, n_class - 1), 1), axis=1)
        loss = -jnp.squeeze(ll, axis=1)
        valid = lab_i != ignore_index
        loss = jnp.where(valid, loss, 0.0)
        if w:
            sw = jnp.take(w[0], jnp.clip(lab_i, 0, n_class - 1))
            sw = jnp.where(valid, sw, 0.0)
            loss = loss * sw
            if reduction == "mean":
                return jnp.sum(loss) / jnp.maximum(jnp.sum(sw), 1e-12)
        if reduction == "mean":
            return jnp.sum(loss) / jnp.maximum(
                jnp.sum(valid.astype(jnp.float32)), 1.0)
        return _reduce(loss, reduction)
    args = [ensure_tensor(input), ensure_tensor(label)]
    if weight is not None:
        args.append(ensure_tensor(weight))
    return nary(f, args, name="nll_loss")


def smooth_l1_loss(input, label, reduction="mean", delta=1.0, name=None):
    def f(a, b):
        diff = jnp.abs(a - b)
        out = jnp.where(diff < delta, 0.5 * diff * diff / delta,
                        diff - 0.5 * delta)
        return _reduce(out, reduction)
    return nary(f, [ensure_tensor(input), ensure_tensor(label)],
                name="smooth_l1_loss")


def kl_div(input, label, reduction="mean", log_target=False, name=None):
    def f(logp, q):
        if log_target:
            out = jnp.exp(q) * (q - logp)
        else:
            out = jnp.where(q > 0, q * (jnp.log(jnp.maximum(q, 1e-30)) - logp),
                            jnp.zeros_like(q))
        if reduction == "batchmean":
            return jnp.sum(out) / logp.shape[0]
        return _reduce(out, reduction)
    return nary(f, [ensure_tensor(input), ensure_tensor(label)], name="kl_div")


def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean",
                        name=None):
    def f(a, b, y):
        out = jnp.maximum(-y * (a - b) + margin, 0.0)
        return _reduce(out, reduction)
    return nary(f, [ensure_tensor(input), ensure_tensor(other),
                    ensure_tensor(label)], name="margin_ranking_loss")


def cosine_similarity(x1, x2, axis=1, eps=1e-8):
    def f(a, b):
        dot = jnp.sum(a * b, axis=axis)
        na = jnp.linalg.norm(a, axis=axis)
        nb = jnp.linalg.norm(b, axis=axis)
        return dot / jnp.maximum(na * nb, eps)
    return nary(f, [ensure_tensor(x1), ensure_tensor(x2)],
                name="cosine_similarity")


def cosine_embedding_loss(input1, input2, label, margin=0.0, reduction="mean",
                          name=None):
    def f(a, b, y):
        cos = jnp.sum(a * b, axis=-1) / jnp.maximum(
            jnp.linalg.norm(a, axis=-1) * jnp.linalg.norm(b, axis=-1), 1e-8)
        out = jnp.where(y == 1, 1 - cos, jnp.maximum(cos - margin, 0.0))
        return _reduce(out, reduction)
    return nary(f, [ensure_tensor(input1), ensure_tensor(input2),
                    ensure_tensor(label)], name="cosine_embedding_loss")


def hinge_embedding_loss(input, label, margin=1.0, reduction="mean", name=None):
    def f(x, y):
        out = jnp.where(y == 1, x, jnp.maximum(margin - x, 0.0))
        return _reduce(out, reduction)
    return nary(f, [ensure_tensor(input), ensure_tensor(label)],
                name="hinge_embedding_loss")


def triplet_margin_loss(input, positive, negative, margin=1.0, p=2,
                        epsilon=1e-6, swap=False, reduction="mean", name=None):
    def f(a, pos, neg):
        dp = jnp.linalg.norm(a - pos + epsilon, ord=p, axis=-1)
        dn = jnp.linalg.norm(a - neg + epsilon, ord=p, axis=-1)
        if swap:
            dn2 = jnp.linalg.norm(pos - neg + epsilon, ord=p, axis=-1)
            dn = jnp.minimum(dn, dn2)
        return _reduce(jnp.maximum(dp - dn + margin, 0.0), reduction)
    return nary(f, [ensure_tensor(input), ensure_tensor(positive),
                    ensure_tensor(negative)], name="triplet_margin_loss")


def triplet_margin_with_distance_loss(input, positive, negative,
                                      distance_function=None, margin=1.0,
                                      swap=False, reduction="mean", name=None):
    if distance_function is None:
        return triplet_margin_loss(input, positive, negative, margin=margin,
                                   swap=swap, reduction=reduction)
    dp = distance_function(input, positive)
    dn = distance_function(input, negative)
    if swap:
        dn2 = distance_function(positive, negative)
        from ...ops.math import minimum
        dn = minimum(dn, dn2)
    from ...ops.math import maximum as _max, mean as _mean, sum as _sum
    from ...ops.creation import zeros_like
    out = _max((dp - dn) + margin, zeros_like(dp))
    if reduction == "mean":
        return _mean(out)
    if reduction == "sum":
        return _sum(out)
    return out


def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0,
             reduction="mean", norm_by_times=False):
    """CTC via the standard alpha-recursion in log space over a lax.scan —
    replaces the reference's vendored warpctc (third_party/warpctc)."""
    log_probs = ensure_tensor(log_probs)  # (T, N, C) paddle layout
    labels = ensure_tensor(labels)        # (N, S)
    input_lengths = ensure_tensor(input_lengths)
    label_lengths = ensure_tensor(label_lengths)

    def f(lp, lab, ilen, llen):
        if lp.ndim == 3 and lab.ndim == 2 and lp.shape[1] == lab.shape[0]:
            pass
        T, N, C = lp.shape
        S = lab.shape[1]
        lp = jax.nn.log_softmax(lp.astype(jnp.float32), axis=-1)
        # extended label seq with blanks: length 2S+1
        ext = jnp.full((N, 2 * S + 1), blank, dtype=jnp.int32)
        ext = ext.at[:, 1::2].set(lab.astype(jnp.int32))
        ext_len = 2 * llen.astype(jnp.int32) + 1
        neg_inf = jnp.float32(-1e30)
        # init alpha at t=0
        alpha0 = jnp.full((N, 2 * S + 1), neg_inf)
        alpha0 = alpha0.at[:, 0].set(lp[0, jnp.arange(N), ext[:, 0]])
        alpha0 = alpha0.at[:, 1].set(
            jnp.where(ext_len > 1, lp[0, jnp.arange(N), ext[:, 1]], neg_inf))

        same_as_prev2 = jnp.concatenate(
            [jnp.ones((N, 2), dtype=bool),
             ext[:, 2:] == ext[:, :-2]], axis=1)

        def step(alpha, lp_t):
            a_prev = alpha
            a_shift1 = jnp.concatenate(
                [jnp.full((N, 1), neg_inf), alpha[:, :-1]], axis=1)
            a_shift2 = jnp.concatenate(
                [jnp.full((N, 2), neg_inf), alpha[:, :-2]], axis=1)
            a_shift2 = jnp.where(same_as_prev2, neg_inf, a_shift2)
            merged = jnp.logaddexp(jnp.logaddexp(a_prev, a_shift1), a_shift2)
            emit = jnp.take_along_axis(lp_t, ext, axis=1)
            return merged + emit, None

        def scan_step(carry, t):
            alpha, = carry
            new_alpha, _ = step(alpha, lp[t])
            new_alpha = jnp.where((t < ilen)[:, None], new_alpha, alpha)
            return (new_alpha,), None

        (alphaT,), _ = jax.lax.scan(scan_step, (alpha0,), jnp.arange(1, T))
        idx_last = ext_len - 1
        ll_final = jnp.logaddexp(
            jnp.take_along_axis(alphaT, idx_last[:, None], axis=1)[:, 0],
            jnp.take_along_axis(alphaT, jnp.maximum(idx_last - 1, 0)[:, None],
                                axis=1)[:, 0])
        loss = -ll_final
        if reduction == "mean":
            return jnp.mean(loss / jnp.maximum(llen.astype(jnp.float32), 1.0))
        return _reduce(loss, reduction)

    return nary(f, [log_probs, labels, input_lengths, label_lengths],
                name="ctc_loss")


def rnnt_loss(input, label, input_lengths, label_lengths, blank=0,
              fastemit_lambda=0.001, reduction="mean", name=None):
    """RNN-Transducer loss (ref ``python/paddle/nn/functional/loss.py``
    rnnt_loss backed by vendored ``third_party/warprnnt`` CUDA kernels).

    TPU-native: the transducer forward variable ``alpha[t, u]`` is
    computed as one ``lax.scan`` over time with a nested scan over the
    label axis (the whole lattice compiles into a single XLA program;
    gradients come from jax's AD through the scans, replacing warprnnt's
    hand-written backward kernel).

    input: ``[B, T, U+1, V]`` UNNORMALIZED logits (log_softmax applied
    internally, matching the reference's ``rnnt_loss``). label:
    ``[B, U]`` int. FastEmit regularization weights the emit path by
    ``(1 + fastemit_lambda)`` (Yu et al. 2021's gradient-side scaling
    folded into the recursion).
    """
    NEG = -1e30

    def f(acts, labels, ilen, ulen):
        B, T, U1, V = acts.shape
        U = U1 - 1
        lp = jax.nn.log_softmax(acts.astype(jnp.float32), axis=-1)
        # blank transition from every node; emit prob of the u-th label
        blank_lp = lp[..., blank]                       # [B, T, U+1]
        lab = labels.astype(jnp.int32)                  # [B, U]
        emit_lp = jnp.take_along_axis(
            lp[:, :, :U, :], lab[:, None, :, None], axis=-1)[..., 0]
        emit_lp = emit_lp + jnp.log1p(fastemit_lambda)  # [B, T, U]
        u_idx = jnp.arange(U1)
        u_valid = u_idx[None, :] <= ulen[:, None]       # [B, U+1]

        def row_update(prev_row, t):
            # vertical (blank) moves from the previous time step
            from_top = prev_row + blank_lp[:, t - 1, :]

            def emit_step(carry, u):
                # horizontal (emit) move within the current time step
                left = carry
                here = jnp.logaddexp(from_top[:, u],
                                     left + emit_lp[:, t, u - 1])
                here = jnp.where(u_valid[:, u], here, NEG)
                return here, here

            a0 = jnp.where(u_valid[:, 0], from_top[:, 0], NEG)
            _, rest = jax.lax.scan(emit_step, a0, jnp.arange(1, U1))
            row = jnp.concatenate([a0[None], rest], axis=0).T  # [B, U+1]
            # rows past this sample's input length stay frozen
            keep = (t < ilen)[:, None]
            return jnp.where(keep, row, prev_row), None

        # t = 0 row: only emit moves are possible
        def first_row(carry, u):
            left = carry
            here = jnp.where(u_valid[:, u], left + emit_lp[:, 0, u - 1], NEG)
            return here, here

        a00 = jnp.zeros((B,), jnp.float32)
        _, first_rest = jax.lax.scan(first_row, a00, jnp.arange(1, U1))
        row0 = jnp.concatenate([a00[None], first_rest], axis=0).T
        rowT, _ = jax.lax.scan(row_update, row0, jnp.arange(1, T))
        # terminal: emit the final blank from node (T-1, U)
        alpha_end = jnp.take_along_axis(
            rowT, ulen[:, None], axis=1)[:, 0]
        final_blank = jnp.take_along_axis(
            blank_lp[jnp.arange(B), ilen - 1, :], ulen[:, None],
            axis=1)[:, 0]
        loss = -(alpha_end + final_blank)
        return _reduce(loss, reduction)

    return nary(f, [ensure_tensor(input), ensure_tensor(label),
                    ensure_tensor(input_lengths).astype("int32"),
                    ensure_tensor(label_lengths).astype("int32")],
                name="rnnt_loss")


def log_loss(input, label, epsilon=1e-4, name=None):
    def f(p, y):
        return -y * jnp.log(p + epsilon) - (1 - y) * jnp.log(1 - p + epsilon)
    return nary(f, [ensure_tensor(input), ensure_tensor(label)],
                name="log_loss")


def sigmoid_focal_loss(logit, label, normalizer=None, alpha=0.25, gamma=2.0,
                       reduction="sum", name=None):
    def f(z, y, *n):
        p = jax.nn.sigmoid(z)
        ce = jnp.maximum(z, 0) - z * y + jnp.logaddexp(0.0, -jnp.abs(z))
        p_t = p * y + (1 - p) * (1 - y)
        a_t = alpha * y + (1 - alpha) * (1 - y)
        out = a_t * jnp.power(1 - p_t, gamma) * ce
        if n:
            out = out / n[0]
        return _reduce(out, reduction)
    args = [ensure_tensor(logit), ensure_tensor(label)]
    if normalizer is not None:
        args.append(ensure_tensor(normalizer))
    return nary(f, args, name="sigmoid_focal_loss")


def dice_loss(input, label, epsilon=1e-5, name=None):
    def f(p, y):
        y1 = jax.nn.one_hot(y.astype(jnp.int32)[..., 0], p.shape[-1],
                            dtype=p.dtype)
        reduce_dims = tuple(range(1, p.ndim))
        inter = jnp.sum(p * y1, axis=reduce_dims)
        union = jnp.sum(p, axis=reduce_dims) + jnp.sum(y1, axis=reduce_dims)
        return jnp.mean(1 - (2 * inter + epsilon) / (union + epsilon))
    return nary(f, [ensure_tensor(input), ensure_tensor(label)],
                name="dice_loss")


def npair_loss(anchor, positive, labels, l2_reg=0.002):
    def f(a, p, y):
        sim = a @ p.T
        y = y.reshape(-1)
        tgt = (y[:, None] == y[None, :]).astype(jnp.float32)
        tgt = tgt / jnp.sum(tgt, axis=1, keepdims=True)
        logp = jax.nn.log_softmax(sim, axis=1)
        xent = -jnp.mean(jnp.sum(tgt * logp, axis=1))
        reg = l2_reg * (jnp.mean(jnp.sum(a * a, axis=1)) +
                        jnp.mean(jnp.sum(p * p, axis=1))) * 0.25
        return xent + reg
    return nary(f, [ensure_tensor(anchor), ensure_tensor(positive),
                    ensure_tensor(labels)], name="npair_loss")


def poisson_nll_loss(input, label, log_input=True, full=False, epsilon=1e-8,
                     reduction="mean", name=None):
    def f(x, y):
        if log_input:
            out = jnp.exp(x) - y * x
        else:
            out = x - y * jnp.log(x + epsilon)
        if full:
            stirling = y * jnp.log(y + epsilon) - y + 0.5 * jnp.log(
                2 * np.pi * (y + epsilon))
            out = out + jnp.where(y > 1, stirling, 0.0)
        return _reduce(out, reduction)
    return nary(f, [ensure_tensor(input), ensure_tensor(label)],
                name="poisson_nll_loss")


def gaussian_nll_loss(input, label, variance, full=False, epsilon=1e-6,
                      reduction="mean", name=None):
    def f(mu, y, var):
        var = jnp.maximum(var, epsilon)
        out = 0.5 * (jnp.log(var) + jnp.square(y - mu) / var)
        if full:
            out = out + 0.5 * np.log(2 * np.pi)
        return _reduce(out, reduction)
    return nary(f, [ensure_tensor(input), ensure_tensor(label),
                    ensure_tensor(variance)], name="gaussian_nll_loss")


def multi_label_soft_margin_loss(input, label, weight=None, reduction="mean",
                                 name=None):
    def f(x, y, *w):
        out = -(y * jax.nn.log_sigmoid(x) + (1 - y) * jax.nn.log_sigmoid(-x))
        out = jnp.mean(out, axis=-1)
        if w:
            out = out * w[0]
        return _reduce(out, reduction)
    args = [ensure_tensor(input), ensure_tensor(label)]
    if weight is not None:
        args.append(ensure_tensor(weight))
    return nary(f, args, name="multi_label_soft_margin_loss")


def soft_margin_loss(input, label, reduction="mean", name=None):
    def f(x, y):
        return _reduce(jnp.log1p(jnp.exp(-y * x)), reduction)
    return nary(f, [ensure_tensor(input), ensure_tensor(label)],
                name="soft_margin_loss")


def margin_cross_entropy(logits, label, margin1=1.0, margin2=0.5,
                         margin3=0.0, scale=64.0, group=None,
                         return_softmax=False, reduction="mean"):
    """ArcFace/CosFace combined-margin CE over (possibly class-sharded)
    cosine logits (ref: ``loss.py:2033``; CUDA kernel
    ``margin_cross_entropy_kernel.cu``).

    TP-aware the TPU way: when called inside an ``mp`` shard_map scope the
    class dim is sharded — the margin is applied locally by the rank that
    owns the target class and softmax statistics reduce with pmax/psum,
    mirroring the ParallelCrossEntropy design (never materializes the
    gathered [N, num_classes] logits). ``group=False`` skips communication
    (data-parallel mode).
    """
    logits = ensure_tensor(logits)
    label = ensure_tensor(label)
    from jax import lax
    from ...distributed.fleet.meta_parallel.parallel_layers.mp_layers import (
        _in_axis_scope, _MP)

    ax = group.axis_name if (group not in (None, False)
                             and hasattr(group, "axis_name")) else _MP
    sharded = group is not False and _in_axis_scope(ax)

    def margin_target(tgt_cos):
        # cos(m1*theta + m2) - m3, numerically guarded acos
        theta = jnp.arccos(jnp.clip(tgt_cos, -1.0 + 1e-7, 1.0 - 1e-7))
        return jnp.cos(margin1 * theta + margin2) - margin3

    def f(lg, y):
        if y.ndim == lg.ndim:
            y = y.squeeze(-1)
        lg = lg.astype(jnp.float32)
        n_local = lg.shape[-1]
        if sharded:
            i = lax.axis_index(ax)
            start = i * n_local
        else:
            start = 0
        in_range = (y >= start) & (y < start + n_local)
        local_y = jnp.clip(y - start, 0, n_local - 1)
        onehot = jax.nn.one_hot(local_y, n_local, dtype=bool) \
            & in_range[..., None]
        modified = jnp.where(onehot, margin_target(lg), lg) * scale
        if sharded:
            m = lax.pmax(jnp.max(modified, axis=-1), ax)
            shifted = modified - m[..., None]
            sumexp = lax.psum(jnp.sum(jnp.exp(shifted), axis=-1), ax)
            tgt = jnp.take_along_axis(shifted, local_y[..., None],
                                      axis=-1)[..., 0]
            tgt = lax.psum(jnp.where(in_range, tgt, 0.0), ax)
        else:
            m = jnp.max(modified, axis=-1)
            shifted = modified - m[..., None]
            sumexp = jnp.sum(jnp.exp(shifted), axis=-1)
            tgt = jnp.take_along_axis(shifted, local_y[..., None],
                                      axis=-1)[..., 0]
        loss = (jnp.log(sumexp) - tgt)[..., None]
        softmax = jnp.exp(shifted) / sumexp[..., None]
        if reduction == "mean":
            loss = jnp.mean(loss)
        elif reduction == "sum":
            loss = jnp.sum(loss)
        return loss, softmax

    out = nary(f, [logits, label], name="margin_cross_entropy", n_out=2)
    return (out[0], out[1]) if return_softmax else out[0]


def hsigmoid_loss(input, label, num_classes, weight, bias=None,
                  path_table=None, path_code=None, is_sparse=False,
                  name=None):
    """Hierarchical sigmoid loss (ref: ``loss.py hsigmoid_loss``; tree
    encoding ``phi/kernels/funcs/matrix_bit_code.h SimpleCode``: class c
    encodes as c + num_classes; node index at bit b is (code>>(b+1))-1,
    branch bit is (code>>b)&1).

    TPU design: the per-sample variable-length tree path is evaluated as a
    fixed ``ceil(log2)`` -deep masked gather+dot — static shapes for XLA;
    ``is_sparse`` is accepted (gathers are already 'sparse' here)."""
    input = ensure_tensor(input)
    label = ensure_tensor(label)
    args = [input, label, ensure_tensor(weight)]
    has_bias = bias is not None
    if has_bias:
        args.append(ensure_tensor(bias))
    custom = path_table is not None
    if custom != (path_code is not None):
        raise ValueError("path_table and path_code must be given together")
    if custom:
        args += [ensure_tensor(path_table), ensure_tensor(path_code)]
    max_len = int(np.ceil(np.log2(max(num_classes, 2)))) + 1 \
        if not custom else None

    def f(x, y, w, *rest):
        b = rest[0] if has_bias else None
        if y.ndim == 2:
            y = y[..., 0]
        if custom:
            table = rest[-2]
            code_bits = rest[-1]
            node_idx = table.astype(jnp.int32)          # [N, L]
            bits = code_bits.astype(jnp.float32)        # [N, L]
            mask = (node_idx >= 0).astype(jnp.float32)
            node_safe = jnp.maximum(node_idx, 0)
        else:
            code = y.astype(jnp.int32) + num_classes    # [N]
            L = max_len
            bit_pos = jnp.arange(L)                     # [L]
            lengths = jnp.floor(
                jnp.log2(code.astype(jnp.float32))).astype(jnp.int32)
            mask = (bit_pos[None, :] < lengths[:, None]).astype(jnp.float32)
            node_safe = jnp.maximum(
                (code[:, None] >> (bit_pos[None, :] + 1)) - 1, 0)
            bits = ((code[:, None] >> bit_pos[None, :]) & 1).astype(
                jnp.float32)
        wpath = w[node_safe]                            # [N, L, D]
        pre = jnp.einsum("nld,nd->nl", wpath.astype(jnp.float32),
                         x.astype(jnp.float32))
        if b is not None:
            pre = pre + b.reshape(-1)[node_safe]
        # BCE-with-logits against the branch bit, masked over real path
        per_node = jnp.maximum(pre, 0) - pre * bits + jnp.log1p(
            jnp.exp(-jnp.abs(pre)))
        return jnp.sum(per_node * mask, axis=-1, keepdims=True)

    return nary(f, args, name="hsigmoid_loss")


def multi_margin_loss(input, label, p=1, margin=1.0, weight=None,
                      reduction="mean", name=None):
    """Multi-class margin (hinge) loss (ref: ``loss.py multi_margin_loss``)."""
    input = ensure_tensor(input)
    label = ensure_tensor(label)
    args = [input, label]
    if weight is not None:
        args.append(ensure_tensor(weight))

    def f(x, y, *w):
        if y.ndim == 2:
            y = y[..., 0]
        C = x.shape[1]
        tgt = jnp.take_along_axis(x, y[:, None], axis=1)
        hinge = jnp.maximum(0.0, margin - tgt + x) ** p
        if w:
            hinge = hinge * w[0][y][:, None]
        hinge = hinge * (1 - jax.nn.one_hot(y, C, dtype=x.dtype))
        return _reduce(jnp.sum(hinge, axis=1) / C, reduction)

    return nary(f, args, name="multi_margin_loss")
