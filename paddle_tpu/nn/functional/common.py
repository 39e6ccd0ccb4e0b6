"""Common functionals: linear, dropout, embedding, one_hot, interpolate,
attention (ref: ``python/paddle/nn/functional/common.py``, ``input.py``,
``extension.py``).

`scaled_dot_product_attention` routes to a Pallas flash-attention kernel on
TPU hardware (the reference's flash_attn CUDA kernel equivalent,
``paddle/phi/kernels/gpu/flash_attn_kernel.cu``) with a pure-XLA fallback.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ...tensor import Tensor
from ...ops.op_utils import ensure_tensor, nary, unary as _unary, maybe_autocast
from ...framework import random as _random
from ...framework import flags as _flags
from ...framework import device as _device

__all__ = [
    "linear", "dropout", "dropout2d", "dropout3d", "alpha_dropout",
    "feature_alpha_dropout", "embedding", "one_hot", "label_smooth",
    "interpolate", "upsample", "pixel_shuffle", "pixel_unshuffle",
    "channel_shuffle", "unfold", "fold", "bilinear",
    "scaled_dot_product_attention", "pad", "zeropad2d", "cosine_similarity",
    "temporal_shift", "class_center_sample", "sequence_mask",
    "pairwise_distance", "sparse_attention", "diag_embed",
]

from ...ops.manipulation import pad  # noqa: F401  re-export (paddle has F.pad)
from .loss import cosine_similarity  # noqa: F401


def linear(x, weight, bias=None, name=None):
    """y = x @ W + b; weight layout (in, out) like the reference."""
    x, weight = maybe_autocast("linear", ensure_tensor(x),
                               ensure_tensor(weight))

    def f(d, w, *b):
        out = d @ w
        if b:
            out = out + b[0].astype(out.dtype)
        return out
    args = [x, weight] + ([ensure_tensor(bias)] if bias is not None else [])
    return nary(f, args, name="linear")


def dropout(x, p=0.5, axis=None, training=True, mode="upscale_in_train",
            name=None):
    x = ensure_tensor(x)
    if not training or p == 0.0:
        if mode == "downscale_in_infer" and not training:
            return _unary(lambda d: d * (1 - p), x, name="dropout")
        return x
    if p == 1.0:
        return _unary(lambda d: jnp.zeros_like(d), x, name="dropout")
    key = _random.next_key()
    axes = None
    if axis is not None:
        axes = [axis] if isinstance(axis, int) else list(axis)

    def f(d):
        shape = list(d.shape)
        if axes is not None:
            shape = [s if i in axes else 1 for i, s in enumerate(shape)]
        keep = jax.random.bernoulli(key, 1.0 - p, tuple(shape))
        if mode == "upscale_in_train":
            return jnp.where(keep, d / (1.0 - p), 0.0).astype(d.dtype)
        return jnp.where(keep, d, 0.0).astype(d.dtype)
    return _unary(f, x, name="dropout")


def dropout2d(x, p=0.5, training=True, data_format="NCHW", name=None):
    ax = [0, 1] if data_format == "NCHW" else [0, 3]
    return dropout(x, p=p, axis=ax, training=training)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW", name=None):
    ax = [0, 1] if data_format == "NCDHW" else [0, 4]
    return dropout(x, p=p, axis=ax, training=training)


def alpha_dropout(x, p=0.5, training=True, name=None):
    x = ensure_tensor(x)
    if not training or p == 0.0:
        return x
    key = _random.next_key()
    alpha = 1.6732632423543772
    scale = 1.0507009873554805
    alpha_p = -alpha * scale

    def f(d):
        keep = jax.random.bernoulli(key, 1.0 - p, d.shape)
        a = ((1 - p) * (1 + p * alpha_p ** 2)) ** -0.5
        b = -a * alpha_p * p
        return (a * jnp.where(keep, d, alpha_p) + b).astype(d.dtype)
    return _unary(f, x, name="alpha_dropout")


feature_alpha_dropout = alpha_dropout


def embedding(x, weight, padding_idx=None, sparse=False, name=None):
    """Gather rows; `sparse` is accepted for parity (XLA gathers are always
    'sparse' in the sense that matters)."""
    def f(ids, w):
        out = jnp.take(w, ids.astype(jnp.int32), axis=0)
        if padding_idx is not None:
            mask = (ids == padding_idx)
            out = jnp.where(mask[..., None], 0.0, out)
        return out
    return nary(f, [ensure_tensor(x), ensure_tensor(weight)],
                name="embedding")


def one_hot(x, num_classes, name=None):
    return _unary(lambda d: jax.nn.one_hot(d.astype(jnp.int32), num_classes,
                                           dtype=jnp.float32), x,
                  name="one_hot")


def label_smooth(label, prior_dist=None, epsilon=0.1, name=None):
    def f(y, *pd):
        k = y.shape[-1]
        if pd:
            return (1 - epsilon) * y + epsilon * pd[0]
        return (1 - epsilon) * y + epsilon / k
    args = [ensure_tensor(label)]
    if prior_dist is not None:
        args.append(ensure_tensor(prior_dist))
    return nary(f, args, name="label_smooth")


def sequence_mask(lengths, maxlen=None, dtype="int64", name=None):
    lengths = ensure_tensor(lengths)
    ml = maxlen or int(np.asarray(lengths._data).max())
    from ...framework.dtype import to_jax_dtype

    def f(l):
        return (jnp.arange(ml)[None, :] < l[..., None]).astype(
            to_jax_dtype(dtype))
    return _unary(f, lengths, name="sequence_mask")


def interpolate(x, size=None, scale_factor=None, mode="nearest",
                align_corners=False, align_mode=0, data_format="NCHW",
                name=None):
    x = ensure_tensor(x)
    channel_last = data_format[-1] == "C"
    n_sp = x.ndim - 2
    if size is not None:
        if isinstance(size, Tensor):
            size = [int(v) for v in size.numpy().tolist()]
        out_sz = tuple(int(s.item()) if isinstance(s, Tensor) else int(s)
                       for s in (size if isinstance(size, (list, tuple))
                                 else [size] * n_sp))
    else:
        sf = scale_factor if isinstance(scale_factor, (list, tuple)) else \
            [scale_factor] * n_sp
        in_sp = x.shape[1:-1] if channel_last else x.shape[2:]
        out_sz = tuple(int(s * f) for s, f in zip(in_sp, sf))

    jmode = {"nearest": "nearest", "bilinear": "linear", "linear": "linear",
             "trilinear": "linear", "bicubic": "cubic", "area": "linear",
             "cubic": "cubic"}[mode]

    def f(d):
        dd = d if channel_last else jnp.moveaxis(d, 1, -1)
        tgt = (dd.shape[0],) + out_sz + (dd.shape[-1],)
        if jmode == "nearest":
            # paddle nearest uses floor indexing (align_corners=False)
            in_sp = dd.shape[1:-1]
            idx = []
            for i, (o, s) in enumerate(zip(out_sz, in_sp)):
                ratio = s / o
                idx.append(jnp.floor(jnp.arange(o) * ratio).astype(jnp.int32))
            out = dd
            for dim, ind in enumerate(idx):
                out = jnp.take(out, ind, axis=1 + dim)
        else:
            out = jax.image.resize(dd, tgt, method=jmode)
        return out if channel_last else jnp.moveaxis(out, -1, 1)
    return _unary(f, x, name="interpolate")


def upsample(x, size=None, scale_factor=None, mode="nearest",
             align_corners=False, align_mode=0, data_format="NCHW", name=None):
    return interpolate(x, size, scale_factor, mode, align_corners, align_mode,
                       data_format)


def pixel_shuffle(x, upscale_factor, data_format="NCHW", name=None):
    r = upscale_factor

    def f(d):
        if data_format == "NCHW":
            n, c, h, w = d.shape
            out = d.reshape(n, c // (r * r), r, r, h, w)
            out = out.transpose(0, 1, 4, 2, 5, 3)
            return out.reshape(n, c // (r * r), h * r, w * r)
        n, h, w, c = d.shape
        out = d.reshape(n, h, w, r, r, c // (r * r))
        out = out.transpose(0, 1, 3, 2, 4, 5)
        return out.reshape(n, h * r, w * r, c // (r * r))
    return _unary(f, x, name="pixel_shuffle")


def pixel_unshuffle(x, downscale_factor, data_format="NCHW", name=None):
    r = downscale_factor

    def f(d):
        if data_format == "NCHW":
            n, c, h, w = d.shape
            out = d.reshape(n, c, h // r, r, w // r, r)
            out = out.transpose(0, 1, 3, 5, 2, 4)
            return out.reshape(n, c * r * r, h // r, w // r)
        n, h, w, c = d.shape
        out = d.reshape(n, h // r, r, w // r, r, c)
        out = out.transpose(0, 1, 3, 2, 4, 5)
        return out.reshape(n, h // r, w // r, c * r * r)
    return _unary(f, x, name="pixel_unshuffle")


def channel_shuffle(x, groups, data_format="NCHW", name=None):
    def f(d):
        if data_format == "NCHW":
            n, c, h, w = d.shape
            return d.reshape(n, groups, c // groups, h, w) \
                .transpose(0, 2, 1, 3, 4).reshape(n, c, h, w)
        n, h, w, c = d.shape
        return d.reshape(n, h, w, groups, c // groups) \
            .transpose(0, 1, 2, 4, 3).reshape(n, h, w, c)
    return _unary(f, x, name="channel_shuffle")


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    """im2col (ref: F.unfold). Output (N, C*kh*kw, L)."""
    from .conv import _norm_tuple
    k = _norm_tuple(kernel_sizes, 2)
    s = _norm_tuple(strides, 2)
    d_ = _norm_tuple(dilations, 2)
    if isinstance(paddings, int):
        p = [(paddings, paddings)] * 2
    elif len(paddings) == 2:
        p = [(paddings[0], paddings[0]), (paddings[1], paddings[1])]
    else:
        p = [(paddings[0], paddings[2]), (paddings[1], paddings[3])]

    def f(x_):
        n, c, h, w = x_.shape
        patches = jax.lax.conv_general_dilated_patches(
            x_, filter_shape=k, window_strides=s, padding=p,
            rhs_dilation=d_, dimension_numbers=("NCHW", "OIHW", "NCHW"))
        # (N, C*kh*kw, oh, ow) -> (N, C*kh*kw, L)
        return patches.reshape(n, c * k[0] * k[1], -1)
    return _unary(f, x, name="unfold")


def fold(x, output_sizes, kernel_sizes, strides=1, paddings=0, dilations=1,
         name=None):
    """col2im — adjoint of unfold (scatter-add patches)."""
    from .conv import _norm_tuple
    out_sz = _norm_tuple(output_sizes, 2)
    k = _norm_tuple(kernel_sizes, 2)
    s = _norm_tuple(strides, 2)
    d_ = _norm_tuple(dilations, 2)
    pd = _norm_tuple(paddings, 2) if not isinstance(paddings, int) else \
        (paddings, paddings)

    def f(col):
        n, ckk, L = col.shape
        c = ckk // (k[0] * k[1])
        oh = (out_sz[0] + 2 * pd[0] - d_[0] * (k[0] - 1) - 1) // s[0] + 1
        ow = (out_sz[1] + 2 * pd[1] - d_[1] * (k[1] - 1) - 1) // s[1] + 1
        col6 = col.reshape(n, c, k[0], k[1], oh, ow)
        out = jnp.zeros((n, c, out_sz[0] + 2 * pd[0], out_sz[1] + 2 * pd[1]),
                        dtype=col.dtype)
        for i in range(k[0]):
            for j in range(k[1]):
                hi = i * d_[0]
                wj = j * d_[1]
                out = out.at[:, :, hi:hi + oh * s[0]:s[0],
                             wj:wj + ow * s[1]:s[1]].add(col6[:, :, i, j])
        return out[:, :, pd[0]:pd[0] + out_sz[0], pd[1]:pd[1] + out_sz[1]]
    return _unary(f, x, name="fold")


def bilinear(x1, x2, weight, bias=None, name=None):
    def f(a, b, w, *bs):
        out = jnp.einsum("bi,oij,bj->bo", a, w, b)
        if bs:
            out = out + bs[0]
        return out
    args = [ensure_tensor(x1), ensure_tensor(x2), ensure_tensor(weight)]
    if bias is not None:
        args.append(ensure_tensor(bias))
    return nary(f, args, name="bilinear")


def zeropad2d(x, padding, data_format="NCHW", name=None):
    from ...ops.manipulation import pad as _pad
    return _pad(x, padding, mode="constant", value=0.0,
                data_format=data_format)


def temporal_shift(x, seg_num, shift_ratio=0.25, data_format="NCHW",
                   name=None):
    def f(d):
        if data_format == "NHWC":
            d = jnp.moveaxis(d, -1, 1)
        nt, c, h, w = d.shape
        n = nt // seg_num
        v = d.reshape(n, seg_num, c, h, w)
        fold_c = int(c * shift_ratio)
        left = jnp.concatenate([v[:, 1:, :fold_c],
                                jnp.zeros_like(v[:, :1, :fold_c])], axis=1)
        right = jnp.concatenate([jnp.zeros_like(v[:, :1, fold_c:2 * fold_c]),
                                 v[:, :-1, fold_c:2 * fold_c]], axis=1)
        rest = v[:, :, 2 * fold_c:]
        out = jnp.concatenate([left, right, rest], axis=2).reshape(nt, c, h, w)
        if data_format == "NHWC":
            out = jnp.moveaxis(out, 1, -1)
        return out
    return _unary(f, x, name="temporal_shift")


def class_center_sample(label, num_classes, num_samples, group=None):
    """Partial-FC style sampling (host-side, eager only)."""
    label = ensure_tensor(label)
    lab = np.asarray(label._data).ravel()
    pos = np.unique(lab)
    if pos.size >= num_samples:
        sampled = pos
    else:
        rest = np.setdiff1d(np.arange(num_classes), pos)
        extra = np.random.choice(rest, num_samples - pos.size, replace=False)
        sampled = np.sort(np.concatenate([pos, extra]))
    remap = -np.ones(num_classes, dtype=np.int64)
    remap[sampled] = np.arange(sampled.size)
    return (Tensor(jnp.asarray(remap[lab].astype(np.int32))),
            Tensor(jnp.asarray(sampled.astype(np.int32))))


# -- attention --------------------------------------------------------------
def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None):
    """Flash attention. Layout (B, S, H, D) — paddle convention.

    On TPU hardware uses the Pallas splash/flash kernel
    (paddle_tpu.ops.pallas_ops); elsewhere an XLA softmax attention whose
    intermediates fuse well (still O(S^2) memory without the kernel).
    """
    q, k_, v = ensure_tensor(query), ensure_tensor(key), ensure_tensor(value)
    q, k_, v = maybe_autocast("matmul", q, k_, v)

    # Selection is by shape, platform and mesh only (see
    # device.pallas_dispatch) — a kernel the compiler refuses fails the
    # program with the compiler's message. Short sequences stay on XLA:
    # its fused attention wins below ~flash_min_seq (the kernel's padding
    # + grid overhead outweighs the O(S^2) saving).
    use_pallas = (attn_mask is None
                  and q.shape[1] >= int(_flags.flag("flash_min_seq"))
                  and _flags.flag("use_pallas_kernels")
                  and _device.pallas_dispatch())
    eff_drop = dropout_p if training else 0.0
    from ...ops.fused_kernels import (record_dispatch as _record,
                                      record_flash_chunks)
    if use_pallas:
        from ...ops.pallas_ops import flash_attention as _fa, mha_chunks
        out = _fa(q, k_, v, causal=is_causal, dropout_p=eff_drop)
        _record("flash_mha", "pallas")
        record_flash_chunks(*mha_chunks(
            q.shape[1], k_.shape[1], q.shape[3], q._data.dtype,
            causal=is_causal))
        return out
    _record("flash_mha", "fallback")

    key_rng = _random.next_key() if (dropout_p > 0.0 and training) else None

    def f(qd, kd, vd, *m):
        scale = 1.0 / np.sqrt(qd.shape[-1])
        # (B,S,H,D) -> (B,H,S,D)
        qt = jnp.swapaxes(qd, 1, 2)
        kt = jnp.swapaxes(kd, 1, 2)
        vt = jnp.swapaxes(vd, 1, 2)
        logits = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * scale
        if is_causal:
            S, K = logits.shape[-2], logits.shape[-1]
            mask = jnp.tril(jnp.ones((S, K), dtype=bool))
            logits = jnp.where(mask, logits, -jnp.inf)
        if m:
            mm = m[0]
            if mm.dtype == jnp.bool_:
                logits = jnp.where(mm, logits, -jnp.inf)
            else:
                logits = logits + mm.astype(logits.dtype)
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(
            qd.dtype)
        if key_rng is not None:
            keep = jax.random.bernoulli(key_rng, 1 - dropout_p, probs.shape)
            probs = jnp.where(keep, probs / (1 - dropout_p), 0.0)
        out = jnp.einsum("bhqk,bhkd->bhqd", probs, vt)
        return jnp.swapaxes(out, 1, 2)

    args = [q, k_, v]
    if attn_mask is not None:
        args.append(ensure_tensor(attn_mask))
    return nary(f, args, name="scaled_dot_product_attention")


from ...ops.creation import diag_embed  # noqa: F401,E402  (F.diag_embed parity)


def pairwise_distance(x, y, p=2.0, epsilon=1e-6, keepdim=False, name=None):
    """p-norm of (x - y + eps) along the last axis (ref
    ``nn/functional/distance.py pairwise_distance``)."""
    import math as _math

    def f(a, b):
        d = a - b + epsilon
        # p is the host-side norm order (a python scalar), not a
        # device value — no transfer happens here
        # tpu-lint: disable=TPU017
        if _math.isinf(float(p)):
            out = jnp.max(jnp.abs(d), axis=-1, keepdims=keepdim) \
                if p > 0 else jnp.min(jnp.abs(d), axis=-1, keepdims=keepdim)
        elif p == 0:
            out = jnp.sum((d != 0).astype(d.dtype), axis=-1,
                          keepdims=keepdim)
        else:
            out = jnp.sum(jnp.abs(d) ** p, axis=-1,
                          keepdims=keepdim) ** (1.0 / p)
        return out
    return nary(f, [ensure_tensor(x), ensure_tensor(y)],
                name="pairwise_distance")


def sparse_attention(query, key, value, sparse_csr_offset, sparse_csr_columns,
                     key_padding_mask=None, attn_mask=None, name=None):
    """Block-sparse attention with a CSR sparsity pattern (ref
    ``nn/functional/sparse_attention.py``; CUDA kernel
    ``sparse_attention_kernel.cu``).

    TPU realization: the CSR pattern is expanded to a boolean mask and the
    computation runs as masked dense attention — XLA has no CSR-gather
    attention primitive, and for the seq lengths this op targets the MXU
    prefers the dense masked form. Same results as the reference kernel.
    """
    q = ensure_tensor(query)
    k_ = ensure_tensor(key)
    v = ensure_tensor(value)
    offs = ensure_tensor(sparse_csr_offset)
    cols = ensure_tensor(sparse_csr_columns)
    args = [q, k_, v, offs, cols]
    if key_padding_mask is not None:
        args.append(ensure_tensor(key_padding_mask))
    if attn_mask is not None:
        args.append(ensure_tensor(attn_mask))

    def f(qd, kd, vd, od, cd, *masks):
        B, H, S, D = qd.shape
        scale = 1.0 / np.sqrt(D)
        logits = jnp.einsum("bhqd,bhkd->bhqk", qd, kd) * scale

        def fill(bh_cols, bh_offsets):
            # CSR -> dense bool [S, S]: one O(nnz) scatter; entry i
            # belongs to the row r with offsets[r] <= i < offsets[r+1]
            nnz = bh_cols.shape[0]
            pos = jnp.arange(nnz)
            rows = jnp.searchsorted(bh_offsets, pos, side="right") - 1
            valid = pos < bh_offsets[-1]
            m = jnp.zeros((S, S), bool)
            return m.at[jnp.clip(rows, 0, S - 1),
                        jnp.clip(bh_cols, 0, S - 1)].max(valid)

        mask = jax.vmap(jax.vmap(fill))(cd, od)
        neg = jnp.asarray(-1e9, logits.dtype)
        logits = jnp.where(mask, logits, neg)
        mi = 0
        if key_padding_mask is not None:
            kp = masks[mi]
            mi += 1
            logits = jnp.where(kp[:, None, None, :] != 0, logits, neg)
        if attn_mask is not None:
            # paddle semantics: 0 -> masked out (same rule as
            # key_padding_mask), not an additive bias
            am = masks[mi]
            logits = jnp.where(am[None, None, :, :] != 0 if am.ndim == 2
                               else am != 0, logits, neg)
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(
            qd.dtype)
        probs = jnp.where(mask, probs, 0.0)
        return jnp.einsum("bhqk,bhkd->bhqd", probs, vd)

    return nary(f, args, name="sparse_attention")
