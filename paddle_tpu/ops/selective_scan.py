"""Selective scan (the recurrence of a Mamba-1 state-space layer) over one
sequence from zero state::

    s_t = exp(delta_t a) * s_{t-1} + u_t B_t        y_t = s_t C_t

``delta`` / ``u`` (S, N) float32, a value a channel; ``B`` / ``C`` (S, R),
a value a state element; ``a`` (R, N) the decay; the state ``s`` is
(R, N) float32, channels on the lanes.  A position whose ``delta`` and
``u`` are zero leaves the state as it is: that is how a caller stops a
padded sequence at its true length.  Returns ``y`` (S, N) and the last
state.

 - :func:`selective_scan` — dispatcher: on a TPU the Pallas kernel
   ``ssm_scan`` (the state of a block of channels stays in VMEM while the
   grid walks the sequence in chunks of ``_SCAN_CHUNK`` positions, so no
   ``(S, R, N)`` tensor exists), elsewhere the twin.
 - :func:`selective_scan_reference` — the XLA twin: ``lax.scan``, a
   position a step.

The caller is :mod:`paddle_tpu.serving.ssm`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..framework import device as _device
from .pallas_ops import _LANES, _interpret_default

__all__ = ["selective_scan", "selective_scan_reference"]

_SCAN_CHUNK = 64        # positions a grid step of ssm_scan walks
_SCAN_CHANNELS = 1280   # channels whose state a grid step keeps in VMEM
_ROWS = 8               # positions a loop iteration takes: one f32 tile


def selective_scan_reference(delta, u, bmat, cmat, a):
    """``s_t = exp(delta_t a) s_{t-1} + u_t B_t``, ``y_t = s_t C_t`` from
    zero state, a position a step.  ``delta`` / ``u`` (S, N) float32 (a
    position with ``delta`` and ``u`` zero leaves the state alone),
    ``bmat`` / ``cmat`` (S, R), ``a`` (R, N).  Returns ``y`` (S, N) and
    the last state (R, N), float32."""

    def step(s, xs):
        d, ut, bt, ct = xs
        s = jnp.exp(d[None, :] * a) * s + ut[None, :] * bt[:, None]
        return s, jnp.sum(s * ct[:, None], axis=0)

    s0 = jnp.zeros(a.shape, jnp.float32)
    last, y = jax.lax.scan(step, s0, (delta, u, bmat, cmat))
    return y, last


def _scan_kernel(delta_ref, u_ref, b_ref, c_ref, a_ref, y_ref, last_ref,
                 s_scr, *, chunk, channels):
    """One chunk of positions of one block of channels.  The state lives
    in ``s_scr`` (R, channels) across the chunks; inside, a 128-lane
    column of it stays in registers for ``_ROWS`` positions at a time.
    ``b_ref`` / ``c_ref`` hold each position's B and C spread over a
    tile's lanes, ``(chunk, R, 128)``."""
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        s_scr[:] = jnp.zeros(s_scr.shape, jnp.float32)

    row = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, _LANES), 0)

    def rows(r8, carry):
        at = pl.ds(pl.multiple_of(r8 * _ROWS, _ROWS), _ROWS)

        def column(c, carry):
            lanes = pl.ds(pl.multiple_of(c * _LANES, _LANES), _LANES)
            d8, u8 = delta_ref[at, lanes], u_ref[at, lanes]   # (8, 128)
            a = a_ref[:, lanes]                               # (R, 128)
            s = s_scr[:, lanes]
            y8 = jnp.zeros((_ROWS, _LANES), jnp.float32)
            for r in range(_ROWS):
                s = (jnp.exp(d8[r:r + 1] * a) * s
                     + u8[r:r + 1] * b_ref[r8 * _ROWS + r])
                y = jnp.sum(s * c_ref[r8 * _ROWS + r], axis=0,
                            keepdims=True)                    # (1, 128)
                y8 = jnp.where(row == r, y, y8)
            # refs of a kernel are written where they are: no carry
            s_scr[:, lanes] = s      # tpu-lint: disable=TPU006
            y_ref[at, lanes] = y8    # tpu-lint: disable=TPU006
            return carry

        return jax.lax.fori_loop(0, channels // _LANES, column, carry)

    jax.lax.fori_loop(0, chunk // _ROWS, rows, 0)

    @pl.when(t == pl.num_programs(1) - 1)
    def _fin():
        last_ref[...] = s_scr[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _selective_scan_pallas(delta, u, bmat, cmat, a, *, interpret):
    """Jitted, so that a program of many such layers traces and lowers
    the kernel once."""
    s, n = delta.shape
    r = a.shape[0]
    chunk = min(_SCAN_CHUNK, s)
    channels = _SCAN_CHANNELS if n % _SCAN_CHANNELS == 0 else _LANES
    if s % chunk or chunk % _ROWS or n % channels:
        raise ValueError(f"ssm_scan: {s} positions x {n} channels do not "
                         f"tile by ({chunk}, {channels})")
    spread = (s, r, _LANES)     # B_t, C_t down the sublanes, every lane
    bb = jnp.broadcast_to(bmat.astype(jnp.float32)[:, :, None], spread)
    cb = jnp.broadcast_to(cmat.astype(jnp.float32)[:, :, None], spread)
    seq = pl.BlockSpec((chunk, channels), lambda c, t: (t, c))
    col = pl.BlockSpec((chunk, r, _LANES), lambda c, t: (t, 0, 0))
    per_channel = pl.BlockSpec((r, channels), lambda c, t: (0, c))
    y, last = pl.pallas_call(
        functools.partial(_scan_kernel, chunk=chunk, channels=channels),
        grid=(n // channels, s // chunk),
        in_specs=[seq, seq, col, col, per_channel],
        out_specs=[seq, per_channel],
        out_shape=[jax.ShapeDtypeStruct((s, n), jnp.float32),
                   jax.ShapeDtypeStruct((r, n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((r, channels), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="ssm_scan",
        interpret=interpret,
    )(delta, u, bb, cb, a)
    return y, last


def selective_scan(delta, u, bmat, cmat, a, *, use_pallas=None,
                   interpret=None):
    """Dispatching entry: the ``ssm_scan`` kernel on a TPU, the XLA twin
    elsewhere (``use_pallas=True`` forces the kernel: tests).  Booked on
    ``pt_pallas_calls_total{kernel="ssm_scan"}``."""
    from .fused_kernels import record_dispatch
    if interpret is None:
        interpret = _interpret_default()
    if use_pallas is None:
        use_pallas = _device.pallas_dispatch()
    if use_pallas:
        record_dispatch("ssm_scan", "pallas")
        return _selective_scan_pallas(delta, u, bmat, cmat, a,
                                      interpret=interpret)
    record_dispatch("ssm_scan", "fallback")
    return selective_scan_reference(delta, u, bmat, cmat, a)
