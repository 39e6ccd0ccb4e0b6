"""``paddle.nn.functional.flash_attention`` (ref:
``python/paddle/nn/functional/flash_attention.py:125 flash_attention``,
``:272 flash_attn_unpadded``) over the Pallas kernel
(``paddle_tpu.ops.pallas_ops``).

The reference's unpadded entry takes packed tokens + ``cu_seqlens``
(CUDA varlen kernels iterate ragged rows). XLA wants static shapes, so
here the packed input is scattered into a padded (B, max_seqlen, H, D)
batch, the kernel masks keys per row via its SMEM length vector, and the
result gathers back to packed layout — all static-shape ops, one fused
program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...ops.op_utils import ensure_tensor, nary
from ...framework import random as _random

__all__ = ["flash_attention", "flash_attn_unpadded"]


def _seed_input(dropout, training):
    if dropout > 0.0 and training:
        bits = jax.random.bits(_random.next_key(), (), jnp.uint32)
        return [ensure_tensor(
            jax.lax.bitcast_convert_type(bits, jnp.float32))]
    return []


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, *, fixed_seed_offset=None,
                    rng_name="", training=True, name=None):
    """(B, S, H, D) tensors; returns (out, softmax) — softmax is None
    unless ``return_softmax``, which falls back to the XLA path (the
    flash kernel never materialises it; same restriction as the
    reference's ``return_softmax`` + fp16 path)."""
    from ...ops.pallas_ops import flash_attention as _fa
    q, k, v = ensure_tensor(query), ensure_tensor(key), ensure_tensor(value)
    if return_softmax:
        from .common import scaled_dot_product_attention
        probs = _softmax_probs(q, k, v, causal)
        out = scaled_dot_product_attention(
            q, k, v, dropout_p=dropout, is_causal=causal, training=training)
        return out, probs
    eff = dropout if training else 0.0
    return _fa(q, k, v, causal=causal, dropout_p=eff), None


def _softmax_probs(q, k, v, causal):
    import numpy as np

    def f(qd, kd, vd):
        qt, kt = jnp.swapaxes(qd, 1, 2), jnp.swapaxes(kd, 1, 2)
        logits = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) / np.sqrt(
            qd.shape[-1])
        if causal:
            S, K = logits.shape[-2], logits.shape[-1]
            logits = jnp.where(jnp.tril(jnp.ones((S, K), bool)), logits,
                               -jnp.inf)
        return jax.nn.softmax(logits.astype(jnp.float32), axis=-1)

    return nary(f, [q, k, v], name="flash_attention_softmax")


def _validate_cu(cu, total, what, max_seqlen=None):
    import numpy as np
    c = np.asarray(cu)
    if c[0] != 0 or (np.diff(c) < 0).any() or c[-1] != total:
        raise ValueError(
            f"{what} must be nondecreasing, start at 0 and end at the "
            f"packed token count {total}; got {c.tolist()[:8]}...")
    # an understated max_seqlen is caller error — reject it
    if max_seqlen is not None and len(c) > 1:
        longest = int(np.diff(c).max())
        if longest > int(max_seqlen):
            raise ValueError(
                f"max_seqlen for {what} is {int(max_seqlen)} but the "
                f"longest sequence is {longest}")


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale, dropout=0.0,
                        causal=False, return_softmax=False,
                        fixed_seed_offset=None, rng_name="", training=True,
                        name=None):
    """Packed ragged varlen attention: ``query`` is (total_q, H, D);
    sequence i occupies rows ``cu_seqlens_q[i]:cu_seqlens_q[i+1]``.

    Runs the genuinely PACKED Pallas kernel (``ops.pallas_ops.mha_packed``):
    sequences are block-aligned in a packed buffer and off-band tiles are
    skipped, so compute is O(sum len_i^2) — no pad-to-max scatter.
    Cross-attention lengths (``cu_seqlens_q != cu_seqlens_k``) are
    supported; ``causal`` uses the flash-attn bottom-right alignment.

    cu_seqlens are VALIDATED eagerly when concrete (raising, not
    NaN-poisoning). Under a jit trace they are tracers and cannot be
    checked for free; set the ``check_varlen`` flag to validate inside
    the traced program via a host callback (debug mode).
    """
    from ...ops.pallas_ops import mha_packed
    from ...framework import flags as _flags
    q = ensure_tensor(query)
    k, v = ensure_tensor(key), ensure_tensor(value)
    cu_q = jnp.asarray(ensure_tensor(cu_seqlens_q)._data, jnp.int32)
    cu_k = jnp.asarray(ensure_tensor(cu_seqlens_k)._data, jnp.int32)
    if not isinstance(cu_q, jax.core.Tracer):
        _validate_cu(cu_q, q.shape[0], "cu_seqlens_q", max_seqlen_q)
    if not isinstance(cu_k, jax.core.Tracer):
        _validate_cu(cu_k, k.shape[0], "cu_seqlens_k", max_seqlen_k)
    eff = dropout if training else 0.0
    seeds = _seed_input(eff, True)
    check = bool(_flags.flag("check_varlen"))
    def f(qd, kd, vd, cu, cuk, *rest):
        if check:
            def _cb(c, ck):
                _validate_cu(c, qd.shape[0], "cu_seqlens_q", max_seqlen_q)
                _validate_cu(ck, kd.shape[0], "cu_seqlens_k", max_seqlen_k)

            # debug.callback is effectful — a pure_callback whose result
            # is unused would be dead-code-eliminated under jit
            jax.debug.callback(_cb, cu, cuk)
        return mha_packed(qd, kd, vd, cu, cuk, causal=causal,
                          sm_scale=scale, dropout_p=eff,
                          seed=rest[0] if rest else None)

    out = nary(f, [q, k, v, ensure_tensor(cu_q), ensure_tensor(cu_k)]
               + seeds, name="flash_attn_unpadded")
    return out, None
