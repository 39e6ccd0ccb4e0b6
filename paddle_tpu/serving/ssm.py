"""Selective state-space mixers of a served decoder, Mamba-1 and Mamba-2:
the scan over a prompt and the one-token update of a decode step.

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

Mamba-2 (SSD, arXiv:2405.21060; Falcon-H1's mixer), on the normed input
``n`` of a ``parallel`` layer, with the µP multipliers of the spec::

    [z, xBC, dt] = (m_in n) W_in * mu       xBC = silu(conv1d(xBC; w, b))
    [x, B, C] = xBC       x: H heads of P,  B, C: G groups of N
    delta = softplus(dt + dt_bias)          a = -exp(A_log), a scalar a head
    S_h,t = exp(delta_h,t a_h) S_h,t-1 + delta_h,t x_h,t (x) B_g,t
    y_h,t = S_h,t C_g,t + D_h x_h,t         (head h in group g = h // (H/G))
    out = m_out GroupRMSNorm(y * silu(z)) W_out     (G groups of H P / G)

The state is ``(H, P, N)`` float32 a row, the convolution tail the last
``d_conv - 1`` inputs ``xBC`` (``x``, B and C side by side, in the cache's
dtype).  The decay is one number a head and a position, so the prompt's
scan is matmuls (:func:`chunked_scan`): within a chunk of
``ssm_chunk`` positions each position reads the earlier ones through
``C B^T`` weighed by their decay, each chunk hands its end state to the
next, and only that hand-over — ``chunks`` steps over the state — is a
loop.  :func:`mamba2_prefill` / :func:`mamba2_decode` are the whole
branch, out-projection and multiplier included.  A decode step's update
of the rows' states, where they lie in the slot pool, is
:func:`paddle_tpu.ops.ssd_update.ssd_state_update`: the Pallas kernel
``ssd_state_update`` on a TPU, its twin (a loop over the rows) elsewhere.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.selective_scan import selective_scan
from ..ops.ssd_update import ssd_state_update

__all__ = ["prefill", "decode", "gate_out", "chunked_scan",
           "mamba2_prefill", "mamba2_decode"]


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


# -- Mamba-2 -----------------------------------------------------------------

def _mamba2_in(spec, params, name, n):
    """``(z (T, inner) float32, xBC (T, conv channels) in the weights'
    dtype, dt (T, H) float32)`` of the normed rows ``n`` (T, hidden)
    float32: the in-projection with the µP multipliers on its input and
    on each of its five segments."""
    w = params[name + ".win"]
    if spec.ssm_in_mult != 1.0:
        n = n * spec.ssm_in_mult
    proj = _f32dot(n.astype(w.dtype), w)
    inner, conv = spec.ssm_inner, spec.ssm_conv_channels
    gn = spec.ssm_groups * spec.ssm_state
    proj = proj * np.repeat(np.asarray(spec.ssm_mults, np.float32),
                            (inner, inner, gn, gn, spec.ssm_heads))
    z, xbc, dt = jnp.split(proj, [inner, inner + conv], axis=-1)
    return z, xbc.astype(w.dtype), dt


def _mamba2_parts(spec, params, name, conv, dt):
    """The convolution's output (T, conv channels) float32, before its
    activation, to ``x`` (T, H, P), ``B`` / ``C`` (T, G, N) and ``delta``
    (T, H), all float32."""
    xc = jax.nn.silu(conv + params[name + ".conv.b"].astype(jnp.float32))
    t, h, g = xc.shape[0], spec.ssm_heads, spec.ssm_groups
    inner, gn = spec.ssm_inner, spec.ssm_groups * spec.ssm_state
    x = xc[:, :inner].reshape(t, h, inner // h)
    bmat = xc[:, inner:inner + gn].reshape(t, g, spec.ssm_state)
    cmat = xc[:, inner + gn:].reshape(t, g, spec.ssm_state)
    delta = jax.nn.softplus(
        dt + params[name + ".dt_bias"].astype(jnp.float32))
    return x, bmat, cmat, delta


def _mamba2_out(spec, params, name, y, z):
    """``m_out GroupRMSNorm(y * silu(z)) W_out``: ``y`` (T, H, P) float32
    with ``D x`` in it, ``z`` (T, inner) float32; (T, hidden) float32."""
    t, g = y.shape[0], spec.ssm_groups
    gated = y.reshape(t, g, -1) * jax.nn.silu(z).reshape(t, g, -1)
    gated = gated * jax.lax.rsqrt(
        jnp.mean(jnp.square(gated), axis=-1, keepdims=True) + spec.norm_eps)
    w = params[name + ".wout"]
    u = gated.reshape(t, -1) * params[name + ".norm.w"].astype(jnp.float32)
    out = _f32dot(u.astype(w.dtype), w)
    return out * spec.ssm_out_mult if spec.ssm_out_mult != 1.0 else out


def _decay2(params, name):
    return -jnp.exp(params[name + ".A_log"].astype(jnp.float32))


def chunked_scan(x, delta, a, bmat, cmat, chunk):
    """The Mamba-2 recurrence over one sequence from zero state, as
    matmuls (the SSD form)::

        S_h,t = exp(delta_h,t a_h) S_h,t-1 + delta_h,t x_h,t (x) B_g,t
        y_h,t = S_h,t C_g,t

    ``x`` (S, H, P), ``delta`` (S, H) (a position whose delta is 0 leaves
    the state as it is: how a caller stops a padded prompt at its
    length), ``a`` (H,), ``bmat`` / ``cmat`` (S, G, N), all float32; S a
    multiple of ``chunk`` or shorter than it.  Returns ``y`` (S, H, P) and
    the last state (H, P, N), float32.  Within a chunk, position t reads
    position u <= t through ``C_t . B_u`` times ``exp(cum_t - cum_u)`` (the
    decay between them: an exponential a head, never a state element);
    each chunk's own inputs give its end state, and a ``lax.scan`` over
    the chunks hands each the state the chunk before left, which the
    chunk's positions read through ``C_t`` times ``exp(cum_t)``."""
    s, h, p = x.shape
    g, n = bmat.shape[1:]
    q = min(chunk, s)
    if s % q:
        raise ValueError(f"chunked_scan: {s} positions in chunks of {q}")
    c, hg = s // q, h // g
    cum = jnp.cumsum((delta * a).reshape(c, q, g, hg), axis=1)
    xd = (x * delta[..., None]).reshape(c, q, g, hg, p)
    bm, cm = bmat.reshape(c, q, g, n), cmat.reshape(c, q, g, n)
    # within a chunk: (c, g, hg, t, u), zero above the diagonal
    causal = np.tril(np.ones((q, q), bool))
    seg = (jnp.transpose(cum, (0, 2, 3, 1))[..., :, None]
           - jnp.transpose(cum, (0, 2, 3, 1))[..., None, :])
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    scores = jnp.einsum("ctgn,cugn->cgtu", cm, bm)[:, :, None] * decay
    y = jnp.einsum("cghtu,cughp->ctghp", scores, xd)
    # each chunk's inputs carried to its end, and the chunk's whole decay
    to_end = jnp.exp(cum[:, -1:] - cum)
    own = jnp.einsum("cughp,cugn->cghpn", xd * to_end[..., None], bm)

    def hand_over(state, chunk_in):
        whole, mine = chunk_in
        return whole[..., None, None] * state + mine, state

    last, entering = jax.lax.scan(
        hand_over, jnp.zeros((g, hg, p, n), jnp.float32),
        (jnp.exp(cum[:, -1]), own))
    y = y + jnp.einsum("ctgn,cghpn->ctghp", cm, entering) \
        * jnp.exp(cum)[..., None]
    return y.reshape(s, h, p), last.reshape(h, p, n)


def mamba2_prefill(spec, params, name, n, length):
    """The Mamba-2 branch over one padded prompt's normed rows ``n`` (S,
    hidden) float32, from zero state.  Returns ``(out (S, hidden) float32,
    tail (d_conv - 1, conv channels), state (H, P, N) float32)``: tail and
    state are those after ``length`` tokens."""
    w = params[name + ".conv.w"].astype(jnp.float32)          # (K, C)
    k, s = w.shape[0], n.shape[0]
    z, xbc, dt = _mamba2_in(spec, params, name, n)
    # the inputs before the prompt are zeros; the tail after `length`
    # tokens is inputs length - K + 1 .. length - 1
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    conv = sum(padded[j:j + s].astype(jnp.float32) * w[j] for j in range(k))
    tail = jax.lax.dynamic_slice_in_dim(padded, length, k - 1, axis=0)
    x, bmat, cmat, delta = _mamba2_parts(spec, params, name, conv, dt)
    live = (jnp.arange(s, dtype=jnp.int32) < length)[:, None]
    y, state = chunked_scan(x, jnp.where(live, delta, 0.0),
                            _decay2(params, name), bmat, cmat,
                            spec.ssm_chunk)
    y = y + params[name + ".D"].astype(jnp.float32)[:, None] * x
    return _mamba2_out(spec, params, name, y, z), tail, state


def mamba2_decode(spec, params, name, n, tail, pool, layer, slots):
    """One token a row: ``n`` (B, hidden) float32 normed rows, ``tail``
    (B, d_conv - 1, conv channels) the rows' last inputs, ``pool`` the
    state slots ``(Ls, slots, H, P, N)`` float32 of which the rows' are
    ``pool[layer, slots]``.  Returns ``(out (B, hidden) float32, tail,
    pool)`` with the token taken in, each row's state updated where it
    lies (float32) by :func:`paddle_tpu.ops.ssd_update.ssd_state_update`:
    on the chip the Pallas kernel ``ssd_state_update``, which streams each
    row's slot through VMEM and writes it back in place; elsewhere its
    twin, a loop over the rows that slices a slot out, updates it and
    writes it back.  Neither gathers the rows' slots: on the chip its
    compiler would re-lay the whole pool into lane tiles first, a copy of
    every layer's slots a layer (1.09 GB at Falcon-H1's widths)."""
    w = params[name + ".conv.w"].astype(jnp.float32)
    z, xbc, dt = _mamba2_in(spec, params, name, n)
    window = jnp.concatenate([tail.astype(xbc.dtype), xbc[:, None]], axis=1)
    conv = jnp.sum(window.astype(jnp.float32) * w[None], axis=1)
    x, bmat, cmat, delta = _mamba2_parts(spec, params, name, conv, dt)
    y, pool = ssd_state_update(pool, layer, slots,
                               jnp.exp(delta * _decay2(params, name)),
                               delta[..., None] * x, bmat, cmat)
    y = y + params[name + ".D"].astype(jnp.float32)[:, None] * x
    return _mamba2_out(spec, params, name, y, z), window[:, 1:], pool
