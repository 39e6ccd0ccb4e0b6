"""Routed SwiGLU experts without drops: the FFN of a sparse decoder block.

``route`` is a softmax router in float32 over all ``E`` experts, the
``k`` largest, renormalised (``norm_topk_prob``).  No capacity: every
token reaches all ``k`` of its experts.  ``experts`` then computes
``sum_e g_e * (silu(y Wg_e) * (y Wu_e)) Wd_e`` in one of two regimes,
chosen statically by the program's token count:

 - **grouped** (many tokens, prefill): the ``T * k`` token-expert pairs
   are sorted by expert, each of the three projections is one grouped
   matmul over the ``E`` groups (:func:`grouped_matmul`: each pair's
   FLOPs once), and the results are unsorted and combined.  Tokens past
   the prompt (``valid`` false) sort behind every group and cost
   nothing.  A program of more than ``SLAB_TOKENS`` tokens does this a
   slab of its tokens at a time, so that the sorted pairs stay small.
 - **dense** (few rows, decode): every row goes through every expert and
   the router's weights zero what was not chosen.  It reads each expert
   matrix exactly once, gathers none, and its ``E / k``-fold redundant
   arithmetic stays under the time the weights take to stream while the
   rows are few (on a v5e: about 240 rows, 197 TFLOP/s over 819 GB/s).

Neither regime gathers weights: no ``(tokens, k, hidden, width)``
tensor exists.  Weights: ``router`` (hidden, E), ``wg`` / ``wu``
(E, hidden, width), ``wd`` (E, width, hidden).

The Paddle-parity ``MoELayer`` (``incubate/distributed/models/moe``)
keeps its capacity-dropping one-hot dispatch for training;
``functional.dropless_moe`` there hands over to this module.
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

__all__ = ["route", "experts", "moe_ffn", "expert_counts",
           "grouped_matmul", "DENSE_MAX_TOKENS"]

# A program of at most this many rows takes the dense regime, a larger
# one the grouped: under the v5e's 240 rows (module docstring) and equal
# to the largest decode bucket anyone builds today, so every decode
# program is dense and every prefill program grouped.
DENSE_MAX_TOKENS = 128

# Most tokens the grouped regime sorts at once: a longer program runs it a
# slab of its tokens at a time (the sorted pairs of 32,768 tokens are
# 262,144 rows, 1 GB a copy in bfloat16 at 2,048 lanes).  By the row count
# alone, and what the longest program had before there were slabs.
SLAB_TOKENS = 8192


def route(y, router, top_k):
    """``(gates (T, k) f32, experts (T, k) int32)``: softmax over all
    experts in float32, the ``top_k`` largest, renormalised to sum 1."""
    logits = jnp.dot(y.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates, idx.astype(jnp.int32)


def expert_counts(idx, n_experts, valid=None):
    """Tokens routed to each expert, ``(E,)`` int32; rows where
    ``valid`` is false are not counted."""
    hit = jax.nn.one_hot(idx, n_experts, dtype=jnp.int32)     # (T, k, E)
    if valid is not None:
        hit = hit * valid.astype(jnp.int32)[:, None, None]
    return jnp.sum(hit, axis=(0, 1))


def _dense(y, gates, idx, wg, wu, wd):
    e = wg.shape[0]
    # (T, E) router weight of each expert for each row, zero if unchosen
    weight = jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.float32)
                     * gates[..., None], axis=1)
    yb = jnp.broadcast_to(y[None], (e,) + y.shape)
    a = jnp.einsum("eth,ehf->etf", yb, wg,
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("eth,ehf->etf", yb, wu,
                   preferred_element_type=jnp.float32)
    act = (jax.nn.silu(a) * u * weight.T[:, :, None]).astype(y.dtype)
    # one contraction over (expert, width): wd is read where it lies
    return jnp.einsum("etf,efh->th", act, wd,
                      preferred_element_type=jnp.float32)


def _tile(n, most):
    """Largest divisor of ``n`` that is a multiple of 128 lanes and at
    most ``most``; None if there is none."""
    return next((t for t in range(min(n, most) // 128 * 128, 0, -128)
                 if n % t == 0), None)


_GMM_ROWS = 256     # rows of sorted pairs a tile of the grouped matmul


def grouped_matmul(x, w, sizes, *, use_pallas=None, interpret=False):
    """``x[rows of group e] @ w[e]`` for every group: ``x`` (M, K) sorted
    by group, ``w`` (E, K, N), ``sizes`` (E,) rows a group.  Rows past
    the last group are unspecified.

    On a TPU this is the Pallas grouped matmul that ships with jax
    (``megablox.gmm``) at tiles of 256 rows by the whole contraction:
    on a v5e it reaches 110-135 TFLOP/s at these widths where the chip's
    expansion of ``jax.lax.ragged_dot`` reaches 30-40 (PERF.md, PR 27).
    Elsewhere, and for shapes the tiles do not divide, ``ragged_dot``.
    The choice is booked on ``pt_pallas_calls_total{kernel="moe_gmm"}``.
    """
    from ..framework import device as _device
    from ..ops.fused_kernels import record_dispatch
    m, k = x.shape
    n = w.shape[2]
    tk, tn = _tile(k, 2304), _tile(n, 1152)
    if use_pallas is None:
        use_pallas = _device.pallas_dispatch()
    if use_pallas and tk and tn and m % _GMM_ROWS == 0:
        from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
        record_dispatch("moe_gmm", "pallas")
        # bfloat16 operands take the MXU's one pass whatever the
        # process's default matmul precision says (Mosaic refuses more)
        passes = (contextlib.nullcontext() if x.dtype == jnp.float32
                  else jax.default_matmul_precision("bfloat16"))
        with jax.named_scope("moe_gmm"), passes:
            return gmm(x, w, sizes, preferred_element_type=x.dtype,
                       tiling=(_GMM_ROWS, tk, tn), interpret=interpret)
    record_dispatch("moe_gmm", "fallback")
    prec = None if x.dtype == jnp.float32 else jax.lax.Precision.DEFAULT
    return jax.lax.ragged_dot(x, w, sizes, precision=prec,
                              preferred_element_type=x.dtype)


def _grouped(y, gates, idx, wg, wu, wd, valid):
    t, k = idx.shape
    e = wg.shape[0]
    flat = idx.reshape(-1)
    if valid is not None:       # padding sorts behind the last group
        flat = jnp.where(jnp.repeat(valid, k), flat, e)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.sum(jax.nn.one_hot(flat, e, dtype=jnp.int32), axis=0)
    rows = y[order // k]                                      # (T*k, h)
    act = (jax.nn.silu(grouped_matmul(rows, wg, sizes).astype(jnp.float32))
           * grouped_matmul(rows, wu, sizes).astype(jnp.float32)
           ).astype(y.dtype)
    out = grouped_matmul(act, wd, sizes)                      # (T*k, h)
    inverse = jnp.argsort(order)
    out = out[inverse].reshape(t, k, -1).astype(jnp.float32)
    if valid is not None:       # rows behind the last group hold anything
        out = jnp.where(valid[:, None, None], out, 0.0)
    return jnp.sum(out * gates[..., None], axis=1)


def experts(y, gates, idx, wg, wu, wd, *, dense=None, valid=None):
    """The experts' weighted sum for rows ``y`` (T, hidden), float32.
    The regime (module docstring) follows the static row count, dense up
    to ``DENSE_MAX_TOKENS``; ``dense`` forces one (tests).  ``valid``
    (T,) bool marks the rows that matter (the others' results are
    unspecified)."""
    if dense is None:
        dense = y.shape[0] <= DENSE_MAX_TOKENS
    if dense:
        return _dense(y, gates, idx, wg, wu, wd)
    slabs = -(-y.shape[0] // SLAB_TOKENS)
    if slabs == 1 or y.shape[0] % slabs:
        return _grouped(y, gates, idx, wg, wu, wd, valid)
    if valid is None:
        valid = jnp.ones(y.shape[:1], bool)
    out = jax.lax.map(
        lambda a: _grouped(*a[:3], wg, wu, wd, a[3]),
        tuple(a.reshape(slabs, -1, *a.shape[1:])
              for a in (y, gates, idx, valid)))
    return out.reshape(y.shape[0], -1)


def moe_ffn(y, router, wg, wu, wd, *, top_k, dense=None, valid=None):
    """Route and compute: ``(out (T, hidden) f32, counts (E,) int32)``."""
    gates, idx = route(y, router, top_k)
    out = experts(y, gates, idx, wg, wu, wd, dense=dense, valid=valid)
    return out, expert_counts(idx, wg.shape[0], valid)
