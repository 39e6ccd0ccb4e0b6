"""Selective state-space (Mamba-1) mixer of a served decoder: the scan
over a prompt and the one-token update of a decode step.

The layer, on its normed input ``n`` (arXiv:2312.00752)::

    [xs, z] = n W_in                      xc = silu(conv1d(xs; w, b))
    [dt, B, C] = xc W_x                   delta = softplus(dt W_dt + b_dt)
    s_t = exp(delta_t A) * s_{t-1} + (delta_t xc_t) B_t      A = -exp(A_log)
    y_t = s_t C_t + D * xc_t              out = (y * silu(z)) W_out

``conv1d`` is causal and depthwise over the last ``d_conv`` inputs.  What
a sequence carries from one token to the next is the **state** ``s``
(float32) and the **convolution tail**, its last ``d_conv - 1`` inputs
``xs``: a row's *state slot* in :class:`paddle_tpu.serving.kv_cache.
StateSlots`.  Matmuls run in the weights' dtype with float32
accumulation; delta, the recurrence and the state are float32.

Layouts, channels on the lanes: the state is ``(d_state, d_inner)``
(the published ``(d_inner, d_state)`` would pad 16 to 128 lanes in the
chip's memory: eight times the bytes), and so are ``A_log`` ``(d_state,
d_inner)`` and ``conv.w`` ``(d_conv, d_inner)``.

 - :func:`paddle_tpu.ops.selective_scan.selective_scan` — a prompt from
   zero state: the Pallas kernel ``ssm_scan`` on a TPU, its XLA twin
   elsewhere; no ``(S, d_state, d_inner)`` tensor exists.  Positions at
   or past ``length`` leave the state as it is: what comes out is the
   state after ``length`` tokens, whatever the bucket.
 - :func:`prefill` / :func:`decode` — the mixer up to ``y`` (the memory a
   later gated memory unit reads) for a padded prompt / a batch of single
   tokens, with the new convolution tail and state; :func:`gate_out`
   finishes the layer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..ops.selective_scan import selective_scan

__all__ = ["prefill", "decode", "gate_out"]


def _f32dot(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def _inputs(params, name, xc):
    """``(delta (T, N), B (T, R), C (T, R))`` float32 of the convolved
    rows ``xc`` (T, N)."""
    rank = params[name + ".wdt"].shape[0]
    state = params[name + ".A_log"].shape[0]
    dbc = _f32dot(xc, params[name + ".wx"])
    dt, bmat, cmat = jnp.split(dbc, [rank, rank + state], axis=-1)
    delta = jax.nn.softplus(
        _f32dot(dt.astype(xc.dtype), params[name + ".wdt"])
        + params[name + ".bdt"].astype(jnp.float32))
    return delta, bmat, cmat


def _decay(params, name):
    return -jnp.exp(params[name + ".A_log"].astype(jnp.float32))


def prefill(params, name, n, length):
    """The mixer over one padded prompt ``n`` (S, hidden), from zero
    state.  Returns ``(y (S, N) float32, z (S, N), tail (d_conv - 1, N),
    state (R, N) float32)``: tail and state are those after ``length``
    tokens."""
    w = params[name + ".conv.w"].astype(jnp.float32)          # (K, N)
    k = w.shape[0]
    s = n.shape[0]
    xs, z = jnp.split(n @ params[name + ".win"], 2, axis=-1)
    # the inputs before the prompt are zeros; the tail after `length`
    # tokens is inputs length - K + 1 .. length - 1
    padded = jnp.pad(xs, ((k - 1, 0), (0, 0)))
    conv = sum(padded[j:j + s].astype(jnp.float32) * w[j] for j in range(k))
    xc = jax.nn.silu(conv + params[name + ".conv.b"].astype(jnp.float32)
                     ).astype(n.dtype)
    tail = jax.lax.dynamic_slice_in_dim(padded, length, k - 1, axis=0)
    delta, bmat, cmat = _inputs(params, name, xc)
    live = (jnp.arange(s, dtype=jnp.int32) < length)[:, None]
    delta = jnp.where(live, delta, 0.0)
    x32 = xc.astype(jnp.float32)
    y, state = selective_scan(delta, delta * x32, bmat, cmat,
                              _decay(params, name))
    y = y + params[name + ".D"].astype(jnp.float32) * x32
    return y, z, tail, state


def decode(params, name, n, tail, state):
    """One token a row: ``n`` (B, hidden), ``tail`` (B, d_conv - 1, N) the
    rows' last inputs, ``state`` (B, R, N) float32.  Returns ``(y (B, N)
    float32, z, tail, state)`` with the token taken in."""
    w = params[name + ".conv.w"].astype(jnp.float32)
    xs, z = jnp.split(n @ params[name + ".win"], 2, axis=-1)
    window = jnp.concatenate([tail.astype(xs.dtype), xs[:, None]], axis=1)
    conv = jnp.sum(window.astype(jnp.float32) * w[None], axis=1)
    xc = jax.nn.silu(conv + params[name + ".conv.b"].astype(jnp.float32)
                     ).astype(n.dtype)
    delta, bmat, cmat = _inputs(params, name, xc)
    x32 = xc.astype(jnp.float32)
    state = (jnp.exp(delta[:, None, :] * _decay(params, name)[None]) * state
             + (delta * x32)[:, None, :] * bmat[:, :, None])
    y = (jnp.sum(state * cmat[:, :, None], axis=1)
         + params[name + ".D"].astype(jnp.float32) * x32)
    return y, z, window[:, 1:], state


def gate_out(params, name, y, z):
    """``(y * silu(z)) W_out``: the end of a state-space layer (``y`` its
    own) and the whole of a gated memory unit (``y`` the memory,
    ``z = n W_in``)."""
    gated = y * jax.nn.silu(z.astype(jnp.float32))
    return gated.astype(z.dtype) @ params[name + ".wout"]
