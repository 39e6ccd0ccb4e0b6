"""The one-token update of a Mamba-2 (SSD) layer's state in a decode step,
each row's state updated where it lies in the slot pool::

    S_h = decay_h S_h + dx_h (x) B_g        y_h = S_h C_g

``pool`` ``(Ls, slots, H, P, N)`` float32 holds every layer's slots; the
rows' states are ``pool[layer, slots]``.  ``decay`` (B, H) is
``exp(delta a)``, ``dx`` (B, H, P) is ``delta x``, ``B`` / ``C`` (B, G, N);
head ``h`` reads group ``h // (H / G)``.  Returns ``y`` (B, H, P) float32
(the read-out, before ``D x``) and the pool with the rows' states updated.
Rows that share a slot (a bucket's padding rows on the null slot 0) leave
that slot unspecified.

 - :func:`ssd_state_update` — dispatcher: on a TPU the Pallas kernel
   ``ssd_state_update``, elsewhere the twin.  The kernel's grid is (row) x
   (block of heads): the pool's block of row ``r`` is ``(layer, slots[r],
   j)`` with ``slots`` prefetched, so each row's state streams through
   VMEM two deep and is written back to the slot it came from (the pool is
   aliased to the output: no copy of it exists).  A block holds the heads
   of one group, at most ``_BLOCK_BYTES`` of state.  Inside, a head at a
   time: ``decay`` and ``dx`` are picked from the block's lanes, the update
   runs on the VPU, and the read-out against ``C`` is a sum over the state's
   lanes (an MXU pass at float32 precision would take six).
 - :func:`ssd_state_update_reference` — the XLA twin: a loop over the rows
   that slices a slot out, updates it and writes it back.  A gather of the
   rows' slots is no such thing on the chip: its compiler re-lays the whole
   pool into lane tiles first, a copy of every layer's slots a layer.

The caller is :func:`paddle_tpu.serving.ssm.mamba2_decode`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..framework import device as _device
from .pallas_ops import _interpret_default

__all__ = ["ssd_state_update", "ssd_state_update_reference", "head_block"]

# Most float32 state a grid step moves each way: Falcon-H1's 16 heads of a
# group, (16, 128, 256), half a row's state
_BLOCK_BYTES = 2 << 20


def head_block(heads, groups, p, n):
    """Heads a grid step takes: the most that divide a group's heads and
    whose ``(heads, P, N)`` float32 state fits ``_BLOCK_BYTES`` (one at
    least)."""
    per = heads // groups
    fits = [k for k in range(per, 0, -1)
            if per % k == 0 and k * p * n * 4 <= _BLOCK_BYTES]
    return fits[0] if fits else 1


def ssd_state_update_reference(pool, layer, slots, decay, dx, bmat, cmat):
    """The module's update as a loop over the rows (the XLA twin)."""
    b, h, p = dx.shape
    g = bmat.shape[1]
    decay = decay.reshape(b, g, h // g)
    dx = dx.reshape(b, g, h // g, p)
    shape = (1, 1) + pool.shape[2:]

    def row(r, carry):
        pool, y = carry
        at = (layer, slots[r], 0, 0, 0)
        s = jax.lax.dynamic_slice(pool, at, shape).reshape(g, h // g, p, -1)
        s = (decay[r][..., None, None] * s
             + dx[r][..., None] * bmat[r][:, None, None, :])
        y = y.at[r].set(jnp.sum(s * cmat[r][:, None, None, :], axis=-1
                                ).reshape(h, p))
        return jax.lax.dynamic_update_slice(pool, s.reshape(shape), at), y

    pool, y = jax.lax.fori_loop(
        0, b, row, (pool, jnp.zeros((b, h, p), jnp.float32)))
    return y, pool


def _kernel(slots_ref, dec_ref, dx_ref, b_ref, c_ref, s_ref, y_ref, o_ref):
    """One row's block of heads: ``dec_ref`` (1, hb) and ``dx_ref`` (P, hb)
    with the heads on the lanes, ``b_ref`` / ``c_ref`` (1, N) the group's,
    ``s_ref`` / ``o_ref`` (hb, P, N) the state in and out, ``y_ref``
    (P, hb)."""
    heads = s_ref.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, heads), 1)
    dec, dx, bv, cv = dec_ref[...], dx_ref[...], b_ref[...], c_ref[...]

    def head(k, y):
        pick = lane == k
        d = jnp.sum(jnp.where(pick, dec, 0.0), axis=1, keepdims=True)
        col = jnp.sum(jnp.where(pick, dx, 0.0), axis=1, keepdims=True)
        s = d * s_ref[k] + col * bv                          # (P, N)
        o_ref[k] = s        # tpu-lint: disable=TPU006
        return jnp.where(pick, jnp.sum(s * cv, axis=1, keepdims=True), y)

    y_ref[...] = jax.lax.fori_loop(0, heads, head,
                                   jnp.zeros(y_ref.shape, jnp.float32))


@functools.partial(jax.jit, static_argnames=("layer", "interpret"))
def _ssd_state_update_pallas(pool, slots, decay, dx, bmat, cmat, *, layer,
                             interpret):
    """Jitted, so that a program of many such layers traces and lowers the
    kernel once a layer index."""
    _, _, h, p, n = pool.shape
    b, g = dx.shape[0], bmat.shape[1]
    hb = head_block(h, g, p, n)
    nb, per = h // hb, h // g
    # the heads of a block on the lanes; B and C a group
    dec = decay.reshape(b, nb, 1, hb)
    dxt = jnp.swapaxes(dx.reshape(b, nb, hb, p), 2, 3)
    bg, cg = bmat.reshape(b, g, 1, n), cmat.reshape(b, g, 1, n)

    def heads(r, j, slots):
        return r, j, 0, 0

    def group(r, j, slots):
        return r, j * hb // per, 0, 0

    def state(r, j, slots):
        return layer, slots[r], j, 0, 0

    block = hb * p * n * 4
    yt, pool = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, nb),
            in_specs=[pl.BlockSpec((None, None, 1, hb), heads),
                      pl.BlockSpec((None, None, p, hb), heads),
                      pl.BlockSpec((None, None, 1, n), group),
                      pl.BlockSpec((None, None, 1, n), group),
                      pl.BlockSpec((None, None, hb, p, n), state)],
            out_specs=[pl.BlockSpec((None, None, p, hb), heads),
                       pl.BlockSpec((None, None, hb, p, n), state)]),
        out_shape=[jax.ShapeDtypeStruct((b, nb, p, hb), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operands count the prefetched slots: the pool is operand 5
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=4 * block + (8 << 20)),
        name="ssd_state_update",
        interpret=interpret,
    )(slots, dec, dxt, bg, cg, pool)
    return jnp.swapaxes(yt, 2, 3).reshape(b, h, p), pool


def ssd_state_update(pool, layer, slots, decay, dx, bmat, cmat, *,
                     use_pallas=None, interpret=None):
    """Dispatching entry: the ``ssd_state_update`` kernel on a TPU, the XLA
    twin elsewhere (``use_pallas=True`` forces the kernel: tests).  Booked
    on ``pt_pallas_calls_total{kernel="ssd_state_update"}``."""
    from .fused_kernels import record_dispatch
    if interpret is None:
        interpret = _interpret_default()
    if use_pallas is None:
        use_pallas = _device.pallas_dispatch()
    if use_pallas:
        record_dispatch("ssd_state_update", "pallas")
        return _ssd_state_update_pallas(pool, slots, decay, dx, bmat, cmat,
                                        layer=layer, interpret=interpret)
    record_dispatch("ssd_state_update", "fallback")
    return ssd_state_update_reference(pool, layer, slots, decay, dx, bmat,
                                      cmat)
