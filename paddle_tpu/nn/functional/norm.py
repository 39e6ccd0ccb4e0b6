"""Normalization functionals (ref: ``python/paddle/nn/functional/norm.py``).

Batch norm's running-stat update mutates the passed mean/variance tensors
in eager mode (matching the reference's in-place running stats); under a
functional trace the updated values propagate through the buffer-threading
machinery in ``paddle_tpu.jit``.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ...tensor import Tensor
from ...ops.op_utils import ensure_tensor, nary, unary as _unary

__all__ = ["batch_norm", "layer_norm", "fused_add_layer_norm",
           "instance_norm", "group_norm", "local_response_norm",
           "normalize", "rms_norm"]


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-5,
               data_format="NCHW", use_global_stats=None, name=None):
    x = ensure_tensor(x)
    channel_last = data_format[-1] == "C" and x.ndim > 2
    ch_axis = x.ndim - 1 if channel_last else (1 if x.ndim > 1 else 0)
    reduce_axes = tuple(i for i in range(x.ndim) if i != ch_axis)
    use_batch_stats = training and not use_global_stats

    rm, rv = ensure_tensor(running_mean), ensure_tensor(running_var)

    symbolic = not isinstance(x._data, (jax.Array, jax.core.Tracer))

    def f(d, m, v, *wb):
        shape = [1] * d.ndim
        shape[ch_axis] = d.shape[ch_axis]
        out = (d - m.reshape(shape)) * jax.lax.rsqrt(
            v.reshape(shape).astype(d.dtype) + epsilon)
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(shape).astype(d.dtype)
            i += 1
        if bias is not None:
            out = out + wb[i].reshape(shape).astype(d.dtype)
        return out

    if use_batch_stats and symbolic:
        # static-graph mode: batch stats fold into the recorded op; running
        # stats are not threaded through the Program (the reference's static
        # BN updates them via in-place ops in the scope — here inference
        # graphs should be built with is_test/eval so global stats are used)
        def f_sym(d, *wb):
            return f(d, jnp.mean(d, axis=reduce_axes),
                     jnp.var(d, axis=reduce_axes), *wb)

        args = [x]
        if weight is not None:
            args.append(ensure_tensor(weight))
        if bias is not None:
            args.append(ensure_tensor(bias))
        return nary(f_sym, args, name="batch_norm")

    if use_batch_stats:
        # compute batch stats, update running stats (eager mutation)
        def stats(d):
            m = jnp.mean(d, axis=reduce_axes)
            v = jnp.var(d, axis=reduce_axes)
            return m, v
        m_arr, v_arr = stats(x._data)
        # paddle: running = momentum*running + (1-momentum)*batch
        rm._data = momentum * rm._data + (1 - momentum) * m_arr
        n = x.size // x.shape[ch_axis]
        unbiased = v_arr * (n / max(n - 1, 1))
        rv._data = momentum * rv._data + (1 - momentum) * unbiased
        mean_t = Tensor(m_arr)
        var_t = Tensor(v_arr)
    else:
        mean_t, var_t = rm, rv

    args = [x, mean_t, var_t]
    if weight is not None:
        args.append(ensure_tensor(weight))
    if bias is not None:
        args.append(ensure_tensor(bias))
    return nary(f, args, name="batch_norm")


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5,
               residual=None, name=None):
    """Layer norm; ``residual`` adds a same-shape tensor to ``x`` before
    normalization so the add+LN pair lowers as one fused cluster (the
    residual sum is not rematerialized between the add and the stats)."""
    x = ensure_tensor(x)
    if residual is not None:
        residual = ensure_tensor(residual)
    if isinstance(normalized_shape, (int, np.integer)):
        normalized_shape = (int(normalized_shape),)
    n_axes = len(tuple(normalized_shape))
    axes = tuple(range(x.ndim - n_axes, x.ndim))

    # fused Pallas path (same gate as scaled_dot_product_attention: flag
    # + device.pallas_dispatch, no runtime fallback); the kernel
    # normalizes a flattened (rows, d) view
    from ...framework import flags as _flags
    from ...framework import device as _device
    from ...ops.fused_kernels import record_dispatch as _record
    if _flags.flag("use_pallas_kernels") and x.ndim >= n_axes > 0 \
            and _device.pallas_dispatch():
        d = int(np.prod(tuple(normalized_shape)))

        def f_fused(dd, *rest):
            from ...ops.fused_kernels import fused_layer_norm
            rows = int(np.prod(dd.shape[:dd.ndim - n_axes])) \
                if dd.ndim > n_axes else 1
            i = 0
            r2 = w2 = b2 = None
            if residual is not None:
                r2, i = rest[i].reshape(rows, d), i + 1
            if weight is not None:
                w2, i = rest[i].reshape(d), i + 1
            if bias is not None:
                b2 = rest[i].reshape(d)
            out = fused_layer_norm(dd.reshape(rows, d), w2, b2,
                                   residual=r2, epsilon=epsilon)
            return out.reshape(dd.shape)

        args = [x]
        if residual is not None:
            args.append(residual)
        if weight is not None:
            args.append(ensure_tensor(weight))
        if bias is not None:
            args.append(ensure_tensor(bias))
        out = nary(f_fused, args, name="layer_norm")
        _record("fused_layer_norm", "pallas")
        return out
    _record("fused_layer_norm", "fallback")

    def f(d, *rest):
        i = 0
        if residual is not None:
            d = d + rest[i].astype(d.dtype)
            i += 1
        m = jnp.mean(d.astype(jnp.float32), axis=axes, keepdims=True)
        v = jnp.var(d.astype(jnp.float32), axis=axes, keepdims=True)
        out = ((d.astype(jnp.float32) - m) * jax.lax.rsqrt(v + epsilon))
        out = out.astype(d.dtype)
        if weight is not None:
            out = out * rest[i].astype(d.dtype)
            i += 1
        if bias is not None:
            out = out + rest[i].astype(d.dtype)
        return out

    args = [x]
    if residual is not None:
        args.append(residual)
    if weight is not None:
        args.append(ensure_tensor(weight))
    if bias is not None:
        args.append(ensure_tensor(bias))
    return nary(f, args, name="layer_norm")


def fused_add_layer_norm(x, residual, normalized_shape, weight=None,
                         bias=None, epsilon=1e-5, name=None):
    """Residual-add + layer norm as one op (``y = LN(x + residual)``).

    Thin named entry over ``layer_norm(..., residual=...)`` — the form the
    TPU016 lint rule rewrites manually-composed ``add``/``layer_norm``
    pairs into.
    """
    return layer_norm(x, normalized_shape, weight=weight, bias=bias,
                      epsilon=epsilon, residual=residual, name=name)


def rms_norm(x, weight=None, epsilon=1e-6, name=None):
    """RMSNorm — the LLM-era norm; fp32 accumulation, bf16 in/out."""
    def f(d, *w):
        x32 = d.astype(jnp.float32)
        ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        out = (x32 * jax.lax.rsqrt(ms + epsilon)).astype(d.dtype)
        if w:
            out = out * w[0].astype(d.dtype)
        return out
    args = [ensure_tensor(x)]
    if weight is not None:
        args.append(ensure_tensor(weight))
    return nary(f, args, name="rms_norm")


def instance_norm(x, running_mean=None, running_var=None, weight=None,
                  bias=None, use_input_stats=True, momentum=0.9, eps=1e-5,
                  data_format="NCHW", name=None):
    x = ensure_tensor(x)
    channel_last = data_format[-1] == "C" and x.ndim > 2
    ch_axis = x.ndim - 1 if channel_last else 1
    spatial = tuple(i for i in range(2, x.ndim)) if not channel_last else \
        tuple(i for i in range(1, x.ndim - 1))

    def f(d, *wb):
        m = jnp.mean(d, axis=spatial, keepdims=True)
        v = jnp.var(d, axis=spatial, keepdims=True)
        out = (d - m) * jax.lax.rsqrt(v + eps)
        i = 0
        if weight is not None:
            shape = [1] * d.ndim
            shape[ch_axis] = d.shape[ch_axis]
            out = out * wb[i].reshape(shape)
            i += 1
        if bias is not None:
            shape = [1] * d.ndim
            shape[ch_axis] = d.shape[ch_axis]
            out = out + wb[i].reshape(shape)
        return out

    args = [x]
    if weight is not None:
        args.append(ensure_tensor(weight))
    if bias is not None:
        args.append(ensure_tensor(bias))
    return nary(f, args, name="instance_norm")


def group_norm(x, num_groups, epsilon=1e-5, weight=None, bias=None,
               data_format="NCHW", name=None):
    x = ensure_tensor(x)
    channel_last = data_format[-1] == "C" and x.ndim > 2
    def f(d, *wb):
        dd = jnp.moveaxis(d, -1, 1) if channel_last else d
        N, C = dd.shape[0], dd.shape[1]
        rest = dd.shape[2:]
        g = dd.reshape((N, num_groups, C // num_groups) + rest)
        axes = tuple(range(2, g.ndim))
        m = jnp.mean(g, axis=axes, keepdims=True)
        v = jnp.var(g, axis=axes, keepdims=True)
        out = ((g - m) * jax.lax.rsqrt(v + epsilon)).reshape(dd.shape)
        shape = [1] * dd.ndim
        shape[1] = C
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(shape)
            i += 1
        if bias is not None:
            out = out + wb[i].reshape(shape)
        if channel_last:
            out = jnp.moveaxis(out, 1, -1)
        return out

    args = [x]
    if weight is not None:
        args.append(ensure_tensor(weight))
    if bias is not None:
        args.append(ensure_tensor(bias))
    return nary(f, args, name="group_norm")


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0,
                        data_format="NCHW", name=None):
    x = ensure_tensor(x)
    channel_last = data_format[-1] == "C"

    def f(d):
        dd = jnp.moveaxis(d, -1, 1) if channel_last else d
        sq = jnp.square(dd)
        half = size // 2
        pad_width = [(0, 0)] * dd.ndim
        pad_width[1] = (half, size - half - 1)
        padded = jnp.pad(sq, pad_width)
        acc = sum(jax.lax.slice_in_dim(padded, i, i + dd.shape[1], axis=1)
                  for i in range(size))
        out = dd / jnp.power(k + alpha * acc / size, beta)
        if channel_last:
            out = jnp.moveaxis(out, 1, -1)
        return out
    return _unary(f, x, name="local_response_norm")


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    return _unary(lambda d: d / jnp.maximum(
        jnp.linalg.norm(d, ord=p, axis=axis, keepdims=True), epsilon), x,
        name="normalize")
