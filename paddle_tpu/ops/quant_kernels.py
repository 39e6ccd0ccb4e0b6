"""Quantized serving kernels: w8a16 matmul + int8 pack/unpack helpers.

The low-precision serving subsystem (:mod:`paddle_tpu.serving.quant`)
keeps weights and KV pages in int8 with the scale travelling beside the
tensor; this module owns every raw quant-dtype cast in the tree
(tpu-lint TPU022 forbids ``astype(int8)`` outside ``ops/`` and
``quantization/`` — a bare int8 array with no scale is a bug vector,
not a tensor).

Three layers, matching the house kernel conventions
(:mod:`.fused_kernels` / :mod:`.paged_attention`):

 - **pack/unpack** — :func:`quantize_weight` (per-out-channel symmetric
   absmax, deterministic round-half-away handled by ``jnp.round``),
   :func:`quantize_kv` / :func:`dequantize_kv` (dynamic per-(token,
   head) scales computed in-graph at KV write time — row-independent,
   so the continuous-batching bit-identity contract survives the drop
   to int8).
 - **w8a16_matmul** — activations in 16/32-bit, weights int8, f32 MXU
   accumulation, per-out-channel scale applied in the epilogue (AFTER
   the dot — the AUD006 dequant-placement contract: the int8→wide
   convert feeds exactly one ``dot_general``).  Pallas kernel on TPU, an
   XLA mirror elsewhere so CPU tier-1 proves the numerics: the kernel in
   interpret mode is bit-identical to the mirror on its padded tile, and
   within the re-association of a K-term f32 sum of the mirror at the
   unpadded width (XLA's CPU dot orders a narrow product differently).
 - **autotune** — :func:`tune_w8a16_matmul` routes (block_m, block_n)
   through :mod:`.autotune` ``search`` with a ``KERNEL_SCHEMA`` entry,
   same as the other fused kernels.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..framework import device as _device
from .pallas_ops import _interpret_default, _ceil_to

__all__ = ["quantize_weight", "dequantize_weight", "quantize_kv",
           "dequantize_kv", "w8a16_matmul", "w8a16_matmul_reference",
           "tune_w8a16_matmul", "QMAX"]

# symmetric int8: [-127, 127]; -128 is never produced so negation is
# always exact and the zero-point is identically 0
QMAX = 127.0


# ---------------------------------------------------------------------------
# pack / unpack
# ---------------------------------------------------------------------------
def quantize_weight(w, axis: int = -1):
    """Per-out-channel symmetric int8 quantization of a weight matrix.

    ``axis`` is the OUT-channel axis (kept; absmax reduces over every
    other axis) — for the serve stack's ``(K, N)`` weights that is
    ``axis=1``, giving a ``(N,)`` f32 scale the matmul epilogue applies
    after the dot.  All-zero channels get scale 1 so the divide is
    defined (they quantize to exact zeros either way).

    Returns ``(q_int8, scale_f32)``.  Deterministic: absmax + round is
    a pure function of the weight values.
    """
    w = jnp.asarray(w, jnp.float32)
    axis = axis % w.ndim
    red = tuple(i for i in range(w.ndim) if i != axis)
    absmax = jnp.max(jnp.abs(w), axis=red)
    scale = jnp.where(absmax > 0, absmax, 1.0) / QMAX
    shape = [1] * w.ndim
    shape[axis] = -1
    q = jnp.clip(jnp.round(w / scale.reshape(shape)), -QMAX, QMAX)
    return q.astype(jnp.int8), scale.astype(jnp.float32)


def dequantize_weight(q, scale, axis: int = -1):
    """Inverse of :func:`quantize_weight` (the XLA-mirror epilogue uses
    the fused form instead; this is for tests and calibration reports)."""
    axis = axis % q.ndim
    shape = [1] * q.ndim
    shape[axis] = -1
    return q.astype(jnp.float32) * jnp.asarray(scale).reshape(shape)


def quantize_kv(x):
    """Dynamic int8 quantization over the trailing (head_dim) axis.

    Scales are per-(token, head): ``x`` of shape ``(..., D)`` yields
    int8 values plus a ``(...,)`` f32 scale.  Computed in-graph at KV
    write time — a pure per-row function, so a row's stored bytes never
    depend on its batch neighbours (the decode bit-identity contract).
    """
    x = jnp.asarray(x, jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=-1)
    scale = jnp.where(absmax > 0, absmax, 1.0) / QMAX
    q = jnp.clip(jnp.round(x / scale[..., None]), -QMAX, QMAX)
    return q.astype(jnp.int8), scale.astype(jnp.float32)


def dequantize_kv(q, scale):
    """Rehydrate int8 KV values with their per-(token, head) scales."""
    return q.astype(jnp.float32) * jnp.asarray(scale)[..., None]


# ---------------------------------------------------------------------------
# w8a16 matmul
# ---------------------------------------------------------------------------
def w8a16_matmul_reference(x, w_q, scale):
    """XLA mirror: widen the int8 weight, f32 dot, scale in the
    epilogue.  This IS the serve-path numerics definition on CPU (the
    off-TPU dispatch lands here), so the order of operations is pinned:
    convert → one dot → per-column scale."""
    acc = jax.lax.dot_general(
        x.astype(jnp.float32), w_q.astype(jnp.float32),
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return (acc * scale).astype(x.dtype)


def _w8a16_kernel(x_ref, w_ref, s_ref, o_ref):
    acc = jnp.dot(x_ref[...].astype(jnp.float32),
                  w_ref[...].astype(jnp.float32),
                  preferred_element_type=jnp.float32)
    o_ref[...] = (acc * s_ref[...]).astype(o_ref.dtype)


def _w8a16_pallas(x, w_q, scale, *, block_m, block_n, interpret):
    m, k = x.shape
    n = w_q.shape[1]
    mp, np_ = _ceil_to(m, block_m), _ceil_to(n, block_n)
    xp = jnp.pad(x, ((0, mp - m), (0, 0))) if mp != m else x
    wp = jnp.pad(w_q, ((0, 0), (0, np_ - n))) if np_ != n else w_q
    sp = (jnp.pad(scale, (0, np_ - n)) if np_ != n else scale)[None, :]
    out = pl.pallas_call(
        _w8a16_kernel,
        grid=(mp // block_m, np_ // block_n),
        in_specs=[
            pl.BlockSpec((block_m, k), lambda mi, ni: (mi, 0)),
            pl.BlockSpec((k, block_n), lambda mi, ni: (0, ni)),
            pl.BlockSpec((1, block_n), lambda mi, ni: (0, ni)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda mi, ni: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name="w8a16_matmul",
    )(xp, wp, sp)
    return out[:m, :n]


def w8a16_matmul(x, w_q, scale, *, block_m=None, block_n=None,
                 use_pallas=None, interpret=None):
    """Quantized-weight matmul: ``x @ dequant(w_q, scale)`` computed as
    ``(x @ w_q) * scale`` with f32 accumulation.

    ``x``: ``(..., K)`` float (f32/bf16 — the "a16" half on TPU);
    ``w_q``: ``(K, N)`` int8; ``scale``: ``(N,)`` f32 per-out-channel.
    Output in ``x.dtype``.  Off-TPU the default is the XLA mirror
    (interpret-mode Pallas is a correctness vehicle, not a fast path);
    dispatch decisions are booked on
    ``pt_pallas_calls_total{kernel="w8a16_matmul"}``.
    """
    from .fused_kernels import record_dispatch
    if interpret is None:
        interpret = _interpret_default()
    if use_pallas is None:
        use_pallas = _device.pallas_dispatch()
    lead = x.shape[:-1]
    if use_pallas:
        from . import autotune as _at
        x2 = x.reshape(-1, x.shape[-1])
        if block_m is None or block_n is None:
            cached = _at.cache_get("w8a16_matmul",
                                   _tune_key(x2, w_q, interpret)) \
                if _at.enabled() else None
            bm, bn = cached if cached else (8, 128)
            block_m = block_m or int(bm)
            block_n = block_n or int(bn)
        record_dispatch("w8a16_matmul", "pallas")
        out = _w8a16_pallas(x2, w_q, scale, block_m=block_m,
                            block_n=block_n, interpret=interpret)
        return out.reshape(*lead, w_q.shape[1])
    record_dispatch("w8a16_matmul", "fallback")
    return w8a16_matmul_reference(x, w_q, scale)


# ---------------------------------------------------------------------------
# autotune
# ---------------------------------------------------------------------------
def _tune_key(x2, w_q, interpret):
    return (int(x2.shape[0]), int(x2.shape[1]), int(w_q.shape[1]),
            str(x2.dtype), bool(interpret))


def _w8a16_cost_fn(m, k, n, itemsize):
    """Per-candidate cost for the (block_m, block_n) search: int8
    weight tiles + wide activation tiles + the f32 accumulator bound
    the vmem working set; FLOPs/bytes order survivors on the
    roofline."""
    flops = 2.0 * m * k * n
    bytes_ = float(m * k * itemsize + k * n + 4 * n + m * n * itemsize)

    def cost(cfg):
        bm = min(int(cfg[0]), _ceil_to(m, 8))
        bn = min(int(cfg[1]), _ceil_to(n, 128))
        vmem = (bm * k * itemsize        # activation tile
                + k * bn                 # int8 weight tile
                + 4 * bn                 # scale row
                + bm * bn * 4            # f32 accumulator
                + bm * bn * itemsize)    # output tile
        return {"flops": flops, "bytes": bytes_, "vmem_bytes": vmem,
                "mxu_underfill": bm < 8}
    return cost


def tune_w8a16_matmul(x, w_q, scale, *, interpret=None):
    """Warmup autotune for :func:`w8a16_matmul`: generate (block_m,
    block_n) candidates from the shape, prune on the roofline, time the
    survivors on real arrays, cache the winner keyed by (M, K, N,
    dtype) under the ``w8a16_matmul`` schema.  Returns
    ``(best_config, timings)``."""
    from . import autotune as _at
    if interpret is None:
        interpret = _interpret_default()
    x2 = x.reshape(-1, x.shape[-1])
    m, k = x2.shape
    n = w_q.shape[1]
    cost = _w8a16_cost_fn(m, k, n, x.dtype.itemsize)
    cands = _at.generate_candidates(
        [("tile", m, 8), ("tile", n, 128)], cost)

    state = {"x": x2}

    def run(cfg):
        # fresh inputs per call + host readback fence (the tune_mha
        # discipline)
        out = w8a16_matmul(state["x"], w_q, scale, block_m=int(cfg[0]),
                           block_n=int(cfg[1]), use_pallas=True,
                           interpret=interpret)
        state["x"] = (out[:, :k] * 1e-3).astype(x.dtype) \
            if out.shape[1] >= k else state["x"]
        float(jnp.sum(out.astype(jnp.float32)))

    best, timings = _at.search(
        "w8a16_matmul", _tune_key(x2, w_q, interpret), run, cands,
        cost=cost)
    _at.set_enabled(True)
    return best, timings
