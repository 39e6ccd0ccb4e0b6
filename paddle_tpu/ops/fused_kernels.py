"""Fused Pallas kernels for the non-attention hot fusion clusters.

The reference ships these as hand-written CUDA under
``paddle/phi/kernels/fusion/`` (``fused_layernorm_kernel.cu``,
``cross_entropy_kernel.cu``); XLA fuses the elementwise pieces but still
materialises the (B, V) probability matrix for cross-entropy and runs
layernorm's stats as separate reductions.  Two kernels close that gap:

 - :func:`fused_layer_norm` — one-pass (sum / sum-of-squares) mean+var
   in f32 over MXU-aligned row tiles, optional fused residual add,
   forward + backward as one ``jax.custom_vjp`` (the backward emits dx
   and accumulates dweight/dbias across row tiles in a single kernel).
 - :func:`fused_softmax_xent` — softmax-cross-entropy with an online
   logsumexp over vocab tiles so the (rows, V) probability matrix never
   exists in HBM; ``ignore_index`` and label smoothing fold into the
   tile loop, and the backward emits ``softmax(x) - onehot`` in one
   pass from the saved logsumexp.

Both run in Pallas interpret mode off-TPU (tier-1 correctness), follow
the MXU contract from :mod:`.pallas_ops` (native-dtype operands, f32
accumulation), and read their launch configs from the search-based
tuner in :mod:`.autotune` (static candidate tables, pruned by the cost
model before anything is timed).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_ops import _LANES, _NEG_INF, _ceil_to, _interpret_default

__all__ = [
    "fused_layer_norm", "fused_softmax_xent",
    "layer_norm_reference", "softmax_xent_reference",
    "tune_layer_norm", "tune_softmax_xent",
    "LN_CANDIDATES", "XENT_CANDIDATES", "record_dispatch",
]


# ---------------------------------------------------------------------------
# dispatch observability
# ---------------------------------------------------------------------------
def _trace_time_counter(name: str, help_: str, labelnames):
    """The registry's counter ``name``, or None while telemetry is off
    (the registry must stay empty then) or the registry cannot be had.
    Looked up per call (not cached) so a registry reset doesn't strand
    increments on a stale counter — dispatch decisions are trace-time
    events, not hot-loop work."""
    try:
        from ..observability.metrics import get_registry
        from ..observability.telemetry import get_telemetry
        if not get_telemetry().enabled:
            return None
        return get_registry().counter(name, help_, labelnames=labelnames)
    except Exception:
        return None


def record_dispatch(kernel: str, path: str):
    """Count one dispatch decision: ``path`` is ``pallas`` (fused kernel
    taken) or ``fallback`` (XLA path). Fed by the nn.functional dispatch
    layer; never raises."""
    c = _trace_time_counter(
        "pt_pallas_calls_total",
        "Kernel dispatch decisions by path (pallas|fallback)",
        ("kernel", "path"))
    if c is not None:
        c.inc(kernel=kernel, path=path)


def record_flash_chunks(visited: int, total: int):
    """Beside a ``flash_mha`` dispatch to Pallas, how far its causal
    skip engages: the (block_q, block_k) tiles of the call's padded
    sq x skv square the kernels compute (``state="visited"``) and all of
    them (``state="total"``), from the call's static plan
    (:func:`pallas_ops.mha_chunks`)."""
    c = _trace_time_counter(
        "pt_flash_chunks_total",
        "Tiles of the attention square the flash kernels compute "
        "(visited) of all of them (total), by dispatch", ("state",))
    if c is not None:
        c.inc(visited, state="visited")
        c.inc(total, state="total")


# ---------------------------------------------------------------------------
# fused layer norm
# ---------------------------------------------------------------------------
def _ln_refs(refs, has_res, has_w, has_b, n_out):
    """Split a layernorm kernel's ref list into (inputs..., outputs)."""
    i = 1
    x_ref = refs[0]
    res_ref = w_ref = b_ref = None
    if has_res:
        res_ref, i = refs[i], i + 1
    if has_w:
        w_ref, i = refs[i], i + 1
    if has_b:
        b_ref, i = refs[i], i + 1
    return x_ref, res_ref, w_ref, b_ref, refs[i:i + n_out], refs[i + n_out:]


def _ln_fwd_kernel(*refs, d, eps, block_rows, d_pad, has_res, has_w, has_b):
    x_ref, res_ref, w_ref, b_ref, (y_ref, mean_ref, rstd_ref), _ = _ln_refs(
        refs, has_res, has_w, has_b, 3)
    xv = x_ref[:].astype(jnp.float32)
    if has_res:
        xv = xv + res_ref[:].astype(jnp.float32)
    if d_pad != d:
        colmask = jax.lax.broadcasted_iota(
            jnp.int32, (block_rows, d_pad), 1) < d
        xm = jnp.where(colmask, xv, 0.0)
    else:
        colmask, xm = None, xv
    # one-pass mean/var in f32: E[x] and E[x^2] from a single read of the
    # tile (the Welford-style single-visit stats the CUDA kernel uses)
    s1 = jnp.sum(xm, axis=-1, keepdims=True)
    s2 = jnp.sum(xm * xm, axis=-1, keepdims=True)
    mean = s1 / d
    var = jnp.maximum(s2 / d - mean * mean, 0.0)
    rstd = jax.lax.rsqrt(var + eps)
    y = (xv - mean) * rstd
    if colmask is not None:
        y = jnp.where(colmask, y, 0.0)
    if has_w:
        y = y * w_ref[:].astype(jnp.float32)
    if has_b:
        y = y + b_ref[:].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)
    mean_ref[:] = mean
    rstd_ref[:] = rstd


def _ln_bwd_kernel(*refs, d, block_rows, d_pad, has_res, has_w, has_b):
    x_ref, res_ref, w_ref, b_ref, (g_ref, mean_ref, rstd_ref), outs = \
        _ln_refs(refs, has_res, has_w, has_b, 3)
    dx_ref = outs[0]
    dw_ref = outs[1] if has_w else None
    db_ref = outs[1 + int(has_w)] if has_b else None

    xv = x_ref[:].astype(jnp.float32)
    if has_res:
        xv = xv + res_ref[:].astype(jnp.float32)
    gv = g_ref[:].astype(jnp.float32)
    if d_pad != d:
        colmask = jax.lax.broadcasted_iota(
            jnp.int32, (block_rows, d_pad), 1) < d
        gv = jnp.where(colmask, gv, 0.0)
    else:
        colmask = None
    mean = mean_ref[:]
    rstd = rstd_ref[:]
    xhat = (xv - mean) * rstd
    if colmask is not None:
        xhat = jnp.where(colmask, xhat, 0.0)
    dy = gv * w_ref[:].astype(jnp.float32) if has_w else gv
    c1 = jnp.sum(dy, axis=-1, keepdims=True) / d
    c2 = jnp.sum(dy * xhat, axis=-1, keepdims=True) / d
    dx = (dy - c1 - xhat * c2) * rstd
    if colmask is not None:
        dx = jnp.where(colmask, dx, 0.0)
    dx_ref[:] = dx.astype(dx_ref.dtype)

    if has_w or has_b:
        # param grads accumulate across row tiles: the grid dim is
        # "arbitrary" so revisiting the single (1, d_pad) output block
        # is sequential (same trick as the flash dkv accumulator)
        @pl.when(pl.program_id(0) == 0)
        def _init():
            if has_w:
                dw_ref[:] = jnp.zeros(dw_ref.shape, jnp.float32)
            if has_b:
                db_ref[:] = jnp.zeros(db_ref.shape, jnp.float32)

        if has_w:
            dw_ref[:] = dw_ref[:] + jnp.sum(gv * xhat, axis=0, keepdims=True)
        if has_b:
            db_ref[:] = db_ref[:] + jnp.sum(gv, axis=0, keepdims=True)


def _ln_pallas_fwd(x, res, w, b, *, d, eps, block_rows, parallel, interpret):
    rows_p, d_pad = x.shape
    ni = rows_p // block_rows
    has_res, has_w, has_b = res is not None, w is not None, b is not None
    row_spec = pl.BlockSpec((block_rows, d_pad), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, d_pad), lambda i: (0, 0))
    stat_spec = pl.BlockSpec((block_rows, 1), lambda i: (i, 0))
    in_specs = [row_spec]
    args = [x]
    if has_res:
        in_specs.append(row_spec)
        args.append(res)
    if has_w:
        in_specs.append(vec_spec)
        args.append(w)
    if has_b:
        in_specs.append(vec_spec)
        args.append(b)
    return pl.pallas_call(
        functools.partial(_ln_fwd_kernel, d=d, eps=eps,
                          block_rows=block_rows, d_pad=d_pad,
                          has_res=has_res, has_w=has_w, has_b=has_b),
        grid=(ni,),
        in_specs=in_specs,
        out_specs=[row_spec, stat_spec, stat_spec],
        out_shape=[
            jax.ShapeDtypeStruct((rows_p, d_pad), x.dtype),
            jax.ShapeDtypeStruct((rows_p, 1), jnp.float32),
            jax.ShapeDtypeStruct((rows_p, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel" if parallel else "arbitrary",)),
        interpret=interpret,
        name="layer_norm_fwd",
    )(*args)


def _ln_pallas_bwd(x, res, w, b, g, mean, rstd, *, d, block_rows,
                   interpret):
    rows_p, d_pad = x.shape
    ni = rows_p // block_rows
    has_res, has_w, has_b = res is not None, w is not None, b is not None
    row_spec = pl.BlockSpec((block_rows, d_pad), lambda i: (i, 0))
    vec_spec = pl.BlockSpec((1, d_pad), lambda i: (0, 0))
    stat_spec = pl.BlockSpec((block_rows, 1), lambda i: (i, 0))
    in_specs = [row_spec]
    args = [x]
    if has_res:
        in_specs.append(row_spec)
        args.append(res)
    if has_w:
        in_specs.append(vec_spec)
        args.append(w)
    if has_b:
        in_specs.append(vec_spec)
        args.append(b)
    in_specs += [row_spec, stat_spec, stat_spec]
    args += [g, mean, rstd]
    out_specs = [row_spec]
    out_shape = [jax.ShapeDtypeStruct((rows_p, d_pad), x.dtype)]
    if has_w:
        out_specs.append(vec_spec)
        out_shape.append(jax.ShapeDtypeStruct((1, d_pad), jnp.float32))
    if has_b:
        out_specs.append(vec_spec)
        out_shape.append(jax.ShapeDtypeStruct((1, d_pad), jnp.float32))
    outs = pl.pallas_call(
        functools.partial(_ln_bwd_kernel, d=d, block_rows=block_rows,
                          d_pad=d_pad, has_res=has_res, has_w=has_w,
                          has_b=has_b),
        grid=(ni,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="layer_norm_bwd",
    )(*args)
    dx = outs[0]
    dw = outs[1] if has_w else None
    db = outs[1 + int(has_w)] if has_b else None
    return dx, dw, db


_LN_STATICS = (4, 5, 6, 7, 8)


@functools.partial(jax.custom_vjp, nondiff_argnums=_LN_STATICS)
def _ln(x, w, b, res, d, eps, block_rows, parallel, interpret):
    y, _, _ = _ln_pallas_fwd(x, res, w, b, d=d, eps=eps,
                             block_rows=block_rows, parallel=parallel,
                             interpret=interpret)
    return y


def _ln_fwd(x, w, b, res, d, eps, block_rows, parallel, interpret):
    y, mean, rstd = _ln_pallas_fwd(x, res, w, b, d=d, eps=eps,
                                   block_rows=block_rows, parallel=parallel,
                                   interpret=interpret)
    return y, (x, w, b, res, mean, rstd)


def _ln_bwd(d, eps, block_rows, parallel, interpret, residuals, g):
    x, w, b, res, mean, rstd = residuals
    dx, dw, db = _ln_pallas_bwd(x, res, w, b, g, mean, rstd, d=d,
                                block_rows=block_rows, interpret=interpret)
    return (dx,
            None if w is None else dw.astype(w.dtype),
            None if b is None else db.astype(b.dtype),
            None if res is None else dx.astype(res.dtype))


_ln.defvjp(_ln_fwd, _ln_bwd)


def _ln_tune_key(rows, d, dtype, interpret):
    return (rows, d, str(dtype), bool(interpret))


def fused_layer_norm(x, weight=None, bias=None, residual=None, *,
                     epsilon=1e-5, block_rows=None, parallel=True,
                     interpret=None):
    """Fused layernorm over a 2-D (rows, d) view; normalizes each row.

    ``residual`` (same shape as ``x``) is added before normalization —
    the transformer block's residual+LN cluster in one kernel launch.
    Returns the normalized array in ``x.dtype``; stats are f32.

    ``block_rows``/``parallel`` default to the autotuned choice when
    :func:`tune_layer_norm` has cached one (see :mod:`.autotune`),
    else 256 rows with a parallel grid.
    """
    if x.ndim != 2:
        raise ValueError(f"fused_layer_norm expects 2-D input, got {x.shape}")
    if interpret is None:
        interpret = _interpret_default()
    rows, d = x.shape
    if block_rows is None:
        from . import autotune as _at
        hit = _at.cache_get("fused_layer_norm", _ln_tune_key(
            rows, d, x.dtype, interpret)) if _at.enabled() else None
        if hit is not None:
            block_rows, parallel = int(hit[0]), bool(hit[1])
        else:
            block_rows = 256
    block_rows = min(int(block_rows), _ceil_to(rows, 8))
    d_pad = _ceil_to(d, _LANES)
    rows_p = _ceil_to(rows, block_rows)

    xp = jnp.pad(x, ((0, rows_p - rows), (0, d_pad - d)))
    wp = bp = rp = None
    if weight is not None:
        wp = jnp.pad(jnp.reshape(weight, (1, d)), ((0, 0), (0, d_pad - d)))
    if bias is not None:
        bp = jnp.pad(jnp.reshape(bias, (1, d)), ((0, 0), (0, d_pad - d)))
    if residual is not None:
        rp = jnp.pad(residual, ((0, rows_p - rows), (0, d_pad - d)))
    y = _ln(xp, wp, bp, rp, d, float(epsilon), block_rows, bool(parallel),
            interpret)
    return y[:rows, :d]


def layer_norm_reference(x, weight=None, bias=None, residual=None,
                         epsilon=1e-5):
    """Pure-jnp reference for the unit tests ((rows, d) layout)."""
    xv = x.astype(jnp.float32)
    if residual is not None:
        xv = xv + residual.astype(jnp.float32)
    m = jnp.mean(xv, axis=-1, keepdims=True)
    v = jnp.var(xv, axis=-1, keepdims=True)
    out = (xv - m) * jax.lax.rsqrt(v + epsilon)
    if weight is not None:
        out = out * weight.astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# fused softmax cross-entropy
# ---------------------------------------------------------------------------
def _xent_fwd_kernel(lab_ref, x_ref, loss_ref, lse_ref, m_scr, l_scr,
                     t_scr, s_scr, *, V, block_rows, block_v,
                     ignore_index, smoothing):
    j = pl.program_id(1)
    nv = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        t_scr[:] = jnp.zeros(t_scr.shape, jnp.float32)
        s_scr[:] = jnp.zeros(s_scr.shape, jnp.float32)

    xv = x_ref[:].astype(jnp.float32)
    col = j * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (block_rows, block_v), 1)
    colmask = col < V
    xm = jnp.where(colmask, xv, _NEG_INF)

    # online logsumexp: running max m, rescaled running sum l — the
    # (rows, V) probability matrix never leaves this tile
    m_prev = m_scr[:, :1]
    l_prev = l_scr[:, :1]
    m_cur = jnp.max(xm, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.where(colmask, jnp.exp(xm - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)

    lab = lab_ref[:]                    # (block_rows, 1) int32
    lab_c = jnp.clip(lab, 0, V - 1)
    # target logit and (for label smoothing) the running logit sum fold
    # into the same tile visit
    t_new = t_scr[:, :1] + jnp.sum(
        jnp.where(col == lab_c, xv, 0.0), axis=-1, keepdims=True)
    s_new = s_scr[:, :1] + jnp.sum(
        jnp.where(colmask, xv, 0.0), axis=-1, keepdims=True)

    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)
    t_scr[:] = jnp.broadcast_to(t_new, t_scr.shape)
    s_scr[:] = jnp.broadcast_to(s_new, s_scr.shape)

    @pl.when(j == nv - 1)
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        lse = m_scr[:, :1] + jnp.log(l_safe)
        t = t_scr[:, :1]
        loss = lse - t
        if smoothing > 0.0:
            # (1-ls)*(lse - x_y) + ls*(lse - mean(x)) folded:
            loss = lse - (1.0 - smoothing) * t \
                - smoothing * (s_scr[:, :1] / V)
        valid = lab != ignore_index
        loss_ref[:] = jnp.where(valid, loss, 0.0)
        lse_ref[:] = lse


def _xent_bwd_kernel(lab_ref, x_ref, lse_ref, g_ref, dx_ref, *, V,
                     block_rows, block_v, ignore_index, smoothing):
    j = pl.program_id(1)
    xv = x_ref[:].astype(jnp.float32)
    col = j * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (block_rows, block_v), 1)
    colmask = col < V
    # softmax(x) - onehot in ONE pass from the saved logsumexp
    p = jnp.where(colmask, jnp.exp(xv - lse_ref[:]), 0.0)
    lab = lab_ref[:]
    lab_c = jnp.clip(lab, 0, V - 1)
    onehot = jnp.logical_and(col == lab_c, colmask)
    grad = p - (1.0 - smoothing) * onehot.astype(jnp.float32)
    if smoothing > 0.0:
        grad = grad - jnp.where(colmask, smoothing / V, 0.0)
    valid = lab != ignore_index
    dx = g_ref[:] * jnp.where(valid, grad, 0.0)
    dx_ref[:] = jnp.where(colmask, dx, 0.0).astype(dx_ref.dtype)


def _xent_pallas_fwd(x, lab, *, V, block_rows, block_v, ignore_index,
                     smoothing, interpret):
    rows_p, v_pad = x.shape
    ni, nv = rows_p // block_rows, v_pad // block_v
    lab_spec = pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0))
    stat_spec = pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0))
    return pl.pallas_call(
        functools.partial(_xent_fwd_kernel, V=V, block_rows=block_rows,
                          block_v=block_v, ignore_index=ignore_index,
                          smoothing=smoothing),
        grid=(ni, nv),
        in_specs=[
            lab_spec,
            pl.BlockSpec((block_rows, block_v), lambda i, j: (i, j)),
        ],
        out_specs=[stat_spec, stat_spec],
        out_shape=[
            jax.ShapeDtypeStruct((rows_p, 1), jnp.float32),
            jax.ShapeDtypeStruct((rows_p, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_rows, _LANES), jnp.float32),
            pltpu.VMEM((block_rows, _LANES), jnp.float32),
            pltpu.VMEM((block_rows, _LANES), jnp.float32),
            pltpu.VMEM((block_rows, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="softmax_xent_fwd",
    )(lab, x)


def _xent_pallas_bwd(x, lab, lse, g, *, V, block_rows, block_v,
                     ignore_index, smoothing, interpret):
    rows_p, v_pad = x.shape
    ni, nv = rows_p // block_rows, v_pad // block_v
    stat_spec = pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0))
    return pl.pallas_call(
        functools.partial(_xent_bwd_kernel, V=V, block_rows=block_rows,
                          block_v=block_v, ignore_index=ignore_index,
                          smoothing=smoothing),
        grid=(ni, nv),
        in_specs=[
            stat_spec,
            pl.BlockSpec((block_rows, block_v), lambda i, j: (i, j)),
            stat_spec,
            stat_spec,
        ],
        out_specs=pl.BlockSpec((block_rows, block_v), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows_p, v_pad), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="softmax_xent_bwd",
    )(lab, x, lse, g)


_XENT_STATICS = (2, 3, 4, 5, 6, 7)


@functools.partial(jax.custom_vjp, nondiff_argnums=_XENT_STATICS)
def _xent(x, lab_f32, V, block_rows, block_v, ignore_index, smoothing,
          interpret):
    lab = jax.lax.bitcast_convert_type(lab_f32, jnp.int32)
    loss, _ = _xent_pallas_fwd(x, lab, V=V, block_rows=block_rows,
                               block_v=block_v, ignore_index=ignore_index,
                               smoothing=smoothing, interpret=interpret)
    return loss


def _xent_fwd(x, lab_f32, V, block_rows, block_v, ignore_index, smoothing,
              interpret):
    lab = jax.lax.bitcast_convert_type(lab_f32, jnp.int32)
    loss, lse = _xent_pallas_fwd(x, lab, V=V, block_rows=block_rows,
                                 block_v=block_v, ignore_index=ignore_index,
                                 smoothing=smoothing, interpret=interpret)
    return loss, (x, lab_f32, lse)


def _xent_bwd(V, block_rows, block_v, ignore_index, smoothing, interpret,
              residuals, g):
    x, lab_f32, lse = residuals
    lab = jax.lax.bitcast_convert_type(lab_f32, jnp.int32)
    dx = _xent_pallas_bwd(x, lab, lse, g.astype(jnp.float32), V=V,
                          block_rows=block_rows, block_v=block_v,
                          ignore_index=ignore_index, smoothing=smoothing,
                          interpret=interpret)
    return dx, jnp.zeros_like(lab_f32)


_xent.defvjp(_xent_fwd, _xent_bwd)


def _xent_tune_key(rows, V, dtype, smoothing, interpret):
    return (rows, V, str(dtype), smoothing > 0.0, bool(interpret))


def fused_softmax_xent(logits, labels, *, ignore_index=-100,
                       label_smoothing=0.0, block_rows=None, block_v=None,
                       interpret=None):
    """Per-row softmax-cross-entropy loss over 2-D (rows, V) logits.

    ``labels`` is int (rows,) — rows whose label equals ``ignore_index``
    get loss 0 (callers own the mean-over-valid normalization).  Returns
    f32 (rows,).  Launch config comes from the tuner cache when
    :func:`tune_softmax_xent` has populated it, else (256, 512).
    """
    if logits.ndim != 2:
        raise ValueError(
            f"fused_softmax_xent expects 2-D logits, got {logits.shape}")
    if interpret is None:
        interpret = _interpret_default()
    rows, V = logits.shape
    if block_rows is None and block_v is None:
        from . import autotune as _at
        hit = _at.cache_get("fused_softmax_xent", _xent_tune_key(
            rows, V, logits.dtype, label_smoothing,
            interpret)) if _at.enabled() else None
        if hit is not None:
            block_rows, block_v = int(hit[0]), int(hit[1])
    block_rows = 256 if block_rows is None else int(block_rows)
    block_v = 512 if block_v is None else int(block_v)
    block_rows = min(block_rows, _ceil_to(rows, 8))
    block_v = min(block_v, _ceil_to(V, _LANES))
    rows_p = _ceil_to(rows, block_rows)
    v_pad = _ceil_to(V, block_v)

    xp = jnp.pad(logits, ((0, rows_p - rows), (0, v_pad - V)))
    lab = jnp.asarray(labels, jnp.int32).reshape(rows)
    lab = jnp.pad(lab, (0, rows_p - rows),
                  constant_values=int(ignore_index))
    lab_f32 = jax.lax.bitcast_convert_type(lab.reshape(rows_p, 1),
                                           jnp.float32)
    loss = _xent(xp, lab_f32, V, block_rows, block_v, int(ignore_index),
                 float(label_smoothing), interpret)
    return loss[:rows, 0]


def softmax_xent_reference(logits, labels, *, ignore_index=-100,
                           label_smoothing=0.0):
    """Pure-jnp reference for the unit tests ((rows, V), int labels)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    V = logits.shape[-1]
    lab = jnp.asarray(labels, jnp.int32).reshape(-1)
    onehot_ll = jnp.take_along_axis(
        logp, jnp.clip(lab, 0, V - 1)[:, None], axis=-1)[:, 0]
    loss = -onehot_ll
    if label_smoothing > 0:
        loss = (1 - label_smoothing) * loss \
            + label_smoothing * (-jnp.mean(logp, axis=-1))
    return jnp.where(lab != ignore_index, loss, 0.0)


# ---------------------------------------------------------------------------
# autotune candidate spaces + cost seeds
# ---------------------------------------------------------------------------
# (block_rows, parallel-grid?) — semantics is part of the search space:
# "parallel" lets Mosaic pipeline row tiles, "arbitrary" forces the
# sequential revisit order (wins when tiles are few and large)
LN_CANDIDATES = ((128, 1), (256, 1), (512, 1), (1024, 1), (256, 0),
                 (1024, 0))
# (block_rows, block_v)
XENT_CANDIDATES = ((128, 512), (256, 512), (256, 1024), (512, 512),
                   (512, 1024), (1024, 512))

_F32 = 4


def _ln_cost_fn(rows, d, itemsize):
    """Per-candidate cost estimate for the layernorm search, seeded by
    the cost model's analytic FLOPs/bytes of the jnp reference."""
    from . import autotune as _at
    d_pad = _ceil_to(d, _LANES)
    sample = jnp.zeros((min(rows, 1024), d), jnp.float32)
    seed = _at.analytic_seed(
        lambda a: layer_norm_reference(a, jnp.ones((d,), jnp.float32),
                                       jnp.zeros((d,), jnp.float32)),
        sample)
    scale = rows / max(sample.shape[0], 1)
    flops = seed["flops"] * scale if seed else rows * d * 8.0
    bytes_ = seed["bytes"] * scale if seed else rows * d * itemsize * 2.0

    def cost(cfg):
        br = min(int(cfg[0]), _ceil_to(rows, 8))
        # working set: input + residual/output tiles in native dtype,
        # an f32 compute copy, the weight/bias vectors and row stats
        vmem = (2 * br * d_pad * itemsize + br * d_pad * _F32
                + 2 * d_pad * _F32 + 2 * br * _F32)
        return {"flops": flops, "bytes": bytes_, "vmem_bytes": vmem,
                "mxu_underfill": br < 8}
    return cost


def _xent_cost_fn(rows, V, itemsize):
    from . import autotune as _at
    sample_rows = min(rows, 512)
    sample = jnp.zeros((sample_rows, V), jnp.float32)
    lab = jnp.zeros((sample_rows,), jnp.int32)
    seed = _at.analytic_seed(
        lambda a, y: softmax_xent_reference(a, y), sample, lab)
    scale = rows / max(sample_rows, 1)
    flops = seed["flops"] * scale if seed else rows * V * 6.0
    bytes_ = seed["bytes"] * scale if seed else rows * V * itemsize * 2.0

    def cost(cfg):
        br = min(int(cfg[0]), _ceil_to(rows, 8))
        bv = min(int(cfg[1]), _ceil_to(V, _LANES))
        vmem = (br * bv * itemsize + br * bv * _F32
                + 4 * br * _LANES * _F32 + 3 * br * _F32)
        return {"flops": flops, "bytes": bytes_, "vmem_bytes": vmem,
                "mxu_underfill": br < 8 or bv < _LANES}
    return cost


def tune_layer_norm(x, weight=None, bias=None, *, epsilon=1e-5,
                    interpret=None, candidates=LN_CANDIDATES):
    """Eagerly search layernorm launch configs for this (rows, d, dtype)
    and cache the winner (see :func:`autotune.search`). ``x`` is the 2-D
    (rows, d) array the hot path will see. Returns (best, timings)."""
    from . import autotune as _at

    if interpret is None:
        interpret = _interpret_default()
    rows, d = x.shape
    seen, todo = set(), []
    for br, par in candidates:
        clamped = (min(int(br), _ceil_to(rows, 8)), int(par))
        if clamped not in seen:
            seen.add(clamped)
            todo.append(clamped)

    state = {"x": x}

    def run(cfg):
        # thread the output back in + host readback fence (see tune_mha)
        out = fused_layer_norm(state["x"], weight, bias, epsilon=epsilon,
                               block_rows=cfg[0], parallel=bool(cfg[1]),
                               interpret=interpret)
        state["x"] = (out.astype(jnp.float32) * 1e-3).astype(x.dtype)
        float(jnp.sum(state["x"].astype(jnp.float32)))

    best, timings = _at.search(
        "fused_layer_norm", _ln_tune_key(rows, d, x.dtype, interpret),
        run, todo, cost=_ln_cost_fn(rows, d, x.dtype.itemsize))
    _at.set_enabled(True)
    return best, timings


def tune_softmax_xent(logits, labels, *, ignore_index=-100,
                      label_smoothing=0.0, interpret=None,
                      candidates=XENT_CANDIDATES):
    """Eagerly search softmax-xent launch configs for this (rows, V,
    dtype) and cache the winner. Returns (best, timings)."""
    from . import autotune as _at

    if interpret is None:
        interpret = _interpret_default()
    rows, V = logits.shape
    seen, todo = set(), []
    for br, bv in candidates:
        clamped = (min(int(br), _ceil_to(rows, 8)),
                   min(int(bv), _ceil_to(V, _LANES)))
        if clamped not in seen:
            seen.add(clamped)
            todo.append(clamped)

    state = {"x": logits}

    def run(cfg):
        loss = fused_softmax_xent(
            state["x"], labels, ignore_index=ignore_index,
            label_smoothing=label_smoothing, block_rows=cfg[0],
            block_v=cfg[1], interpret=interpret)
        state["x"] = state["x"] + (jnp.mean(loss) * 1e-6).astype(
            logits.dtype)
        float(jnp.sum(loss))

    best, timings = _at.search(
        "fused_softmax_xent",
        _xent_tune_key(rows, V, logits.dtype, label_smoothing, interpret),
        run, todo, cost=_xent_cost_fn(rows, V, logits.dtype.itemsize))
    _at.set_enabled(True)
    return best, timings
