"""Routed SwiGLU experts for a few rows, over the experts they chose and
no others: the decode step's expert layer (:mod:`paddle_tpu.serving.
experts`, the *streamed* form).

    out[t] = sum_{e touched} w[t, e] * (silu(x[t] Wg_e) * (x[t] Wu_e)) Wd_e

``w`` (T, E) float32 is each row's router weight of each expert, zero
where the row did not choose it (and for a row that does not count).
The kernel's grid is (expert slot ``j``) x (width tile): slot ``j`` holds
expert ``ids[j]``, where ``ids`` lists the touched experts in ascending
order and repeats the last one past ``n``, and every weight block's index
map reads ``ids[j]``.  Pallas' pipeline copies a block only when its
index changes, so a slot past ``n`` costs a grid step and no bytes (its
width tile is held at the last one, and its compute sits under a
``pl.when``).  Inside a slot all T rows go through the expert in float32
from the MXU's one pass over bfloat16 operands, are scaled by their
weight of it, cast back and multiplied by ``Wd``; the (T, hidden) sum
stays in VMEM until the last slot.  Booked by the caller on
``pt_pallas_calls_total{kernel="moe_decode"}``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_sparse import _mxu_precision

__all__ = ["moe_decode", "width_tile"]

# Most VMEM the double-buffered weight blocks of a slot may take: a whole
# expert of either served model (Keye 9.4 MB, Mellum 12.4 MB) twice over,
# under the v5e's 128 MiB
_BLOCKS_BYTES = 48 << 20


def width_tile(hidden, width, itemsize):
    """Widest tile of the experts' ``width`` (the whole of it, or a
    divisor that is a multiple of 128 lanes) whose three weight blocks fit
    ``_BLOCKS_BYTES`` twice over."""
    fits = [t for t in range(width, 0, -128) if width % t == 0
            and 2 * 3 * hidden * t * itemsize <= _BLOCKS_BYTES]
    return fits[0] if fits else 128


def _kernel(ids_ref, n_ref, x_ref, w_ref, wg_ref, wu_ref, wd_ref, o_ref):
    j, f = pl.program_id(0), pl.program_id(1)

    @pl.when((j == 0) & (f == 0))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(j < n_ref[0])
    def _():
        x = x_ref[...]
        prec = _mxu_precision(x.dtype)
        a = jnp.dot(x, wg_ref[...], precision=prec,
                    preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[...], precision=prec,
                    preferred_element_type=jnp.float32)
        w = w_ref[...]                                       # (T, E)
        lane = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
        g = jnp.sum(jnp.where(lane == ids_ref[j], w, 0.0), axis=1,
                    keepdims=True)                           # (T, 1)
        act = (jax.nn.silu(a) * u * g).astype(x.dtype)
        o_ref[...] += jnp.dot(act, wd_ref[...], precision=prec,
                              preferred_element_type=jnp.float32)


def moe_decode(x, w, ids, n, wg, wu, wd, *, interpret=False):
    """``out`` (T, hidden) float32 of rows ``x`` (T, hidden) through the
    experts ``ids[:n]`` (module docstring): ``w`` (T, E) float32, ``ids``
    (E,) int32, ``n`` (1,) int32; ``wg`` / ``wu`` (E, hidden, width),
    ``wd`` (E, width, hidden)."""
    t, h = x.shape
    e, _, width = wg.shape
    tf = width_tile(h, width, wg.dtype.itemsize)
    last = width // tf - 1

    def tile(j, f, ids, n):     # a slot past n keeps the last slot's block
        return jnp.where(j < n[0], f, last)

    def up(j, f, ids, n):
        return ids[j], 0, tile(j, f, ids, n)

    def down(j, f, ids, n):
        return ids[j], tile(j, f, ids, n), 0

    def whole(j, f, ids, n):
        return 0, 0

    blocks = 3 * h * tf * wg.dtype.itemsize
    rows = t * h * (x.dtype.itemsize + 4) + t * e * 4
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(e, width // tf),
            in_specs=[pl.BlockSpec((t, h), whole),
                      pl.BlockSpec((t, e), whole),
                      pl.BlockSpec((None, h, tf), up),
                      pl.BlockSpec((None, h, tf), up),
                      pl.BlockSpec((None, tf, h), down)],
            out_specs=pl.BlockSpec((t, h), whole)),
        out_shape=jax.ShapeDtypeStruct((t, h), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=2 * (blocks + rows) + 3 * t * tf * 4
            + (8 << 20)),
        name="moe_decode",
        interpret=interpret,
    )(ids, n, x, w, wg, wu, wd)
