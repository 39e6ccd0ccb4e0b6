"""Functional MoE core — GShard-style einsum dispatch/combine.

TPU-native redesign of the reference MoE
(``python/paddle/incubate/distributed/models/moe/moe_layer.py``): the
reference routes tokens with custom CUDA ops (``count_by_gate``,
``global_scatter``/``global_gather`` over NCCL).  On TPU the idiomatic
formulation is the GShard one: gating produces a dense one-hot
``dispatch`` mask (tokens × experts × capacity) and the routing IS two
einsums — XLA turns them into all_to_all when the expert axis is
sharded over the mesh, and they differentiate for free.

All functions here are pure jnp on raw arrays (tokens-major); the Layer
wrapper lives in moe_layer.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["top1_gating", "top2_gating", "dispatch", "combine",
           "dropless_moe"]


def dropless_moe(x, router, wg, wu, wd, *, top_k, form=None):
    """Top-``k`` routed SwiGLU experts with no capacity and no dropped
    token — the serving path's expert layer, handed over to
    :mod:`paddle_tpu.serving.experts` (softmax router in float32,
    renormalised top-k; sort by expert + grouped matmuls where tokens
    are many, the chosen experts streamed once each where rows are few).
    The capacity-dropping one-hot dispatch below stays what ``MoELayer``
    trains with.  ``x`` (T, hidden); ``router`` (hidden, E); ``wg`` /
    ``wu`` (E, hidden, width); ``wd`` (E, width, hidden).  ``form``
    None leaves the form to the token count (``experts.
    STREAM_MAX_TOKENS``); every form differentiates.  Returns (T, hidden) f32.
    """
    from paddle_tpu.serving import experts as _experts
    out, _ = _experts.moe_ffn(x, router, wg, wu, wd, top_k=top_k,
                              form=form)
    return out


def _one_hot(idx, n):
    return jax.nn.one_hot(idx, n, dtype=jnp.float32)


def _positions_in_expert(mask):
    """Position of each token within its expert's buffer: cumsum over
    tokens of the expert one-hot, minus 1 (T, E)."""
    return jnp.cumsum(mask, axis=0) - mask


def top1_gating(logits, capacity, prior_count=None):
    """Switch-transformer routing (top-1).

    Args: logits (T, E); capacity per expert (int); ``prior_count``
    (T, E) — tokens already buffered per expert (used by top-2's second
    pass).
    Returns (combine (T,E,C), dispatch_bool (T,E,C), aux_loss, idx (T,)).
    Aux loss follows Switch: E * sum_e(f_e * p_e) where f_e is the
    fraction of tokens routed to e and p_e the mean gate prob.
    """
    t, e = logits.shape
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    idx = jnp.argmax(gates, axis=-1)
    mask = _one_hot(idx, e)  # (T, E)

    density = jnp.mean(mask, axis=0)          # f_e
    density_proxy = jnp.mean(gates, axis=0)   # p_e
    aux = jnp.sum(density * density_proxy) * e

    pos = _positions_in_expert(mask)
    if prior_count is not None:
        pos = pos + prior_count
    in_cap = (jnp.sum(pos * mask, axis=-1) < capacity)
    mask = mask * in_cap[:, None]
    gate_val = jnp.sum(gates * mask, axis=-1)  # (T,)

    pos_idx = jnp.sum(pos * mask, axis=-1).astype(jnp.int32)
    disp = (mask[:, :, None] *
            _one_hot(pos_idx, capacity)[:, None, :])  # (T, E, C)
    comb = disp * gate_val[:, None, None]
    return comb, disp > 0, aux, gates, mask


def top2_gating(logits, capacity):
    """GShard top-2 routing: pick the best expert, mask it out, pick the
    second; normalize the two gate values; capacity respects first-pass
    buffering. Returns (combine, dispatch_bool, aux_loss)."""
    t, e = logits.shape
    comb1, disp1, aux, gates, mask1 = top1_gating(logits, capacity)

    # second choice from the renormalized remainder
    logits2 = jnp.where(mask1 > 0, -jnp.inf, logits.astype(jnp.float32))
    count1 = jnp.sum(mask1, axis=0, keepdims=True)  # tokens per expert
    comb2, disp2, _, _, _ = top1_gating(
        logits2, capacity,
        prior_count=jnp.broadcast_to(count1, (t, e)))

    denom = jnp.sum(comb1, axis=(1, 2)) + jnp.sum(comb2, axis=(1, 2))
    denom = jnp.where(denom > 0, denom, 1.0)
    comb = (comb1 + comb2) / denom[:, None, None]
    disp = jnp.logical_or(disp1, disp2)
    return comb, disp, aux


def dispatch(x, disp):
    """(T, D), (T, E, C) → expert inputs (E, C, D)."""
    return jnp.einsum("tec,td->ecd", disp.astype(x.dtype), x)


def combine(expert_out, comb):
    """(E, C, D), (T, E, C) → (T, D)."""
    return jnp.einsum("tec,ecd->td", comb.astype(expert_out.dtype),
                      expert_out)
