"""Hand-written TPU Pallas kernels — the `phi/kernels/fusion` equivalent.

The reference ships fused CUDA kernels (flash attention:
``paddle/phi/kernels/gpu/flash_attn_kernel.cu`` + vendored
``third_party/flashattn``; fused rope/adam under
``paddle/phi/kernels/fusion/``).  On TPU the only ops worth hand-writing
are the ones XLA cannot fuse into O(S) memory itself — attention.  This
module implements FlashAttention-2 style tiled attention (forward +
backward as ``jax.custom_vjp``) with online softmax, f32 accumulation,
and MXU-aligned 128x128 tiles.

Everything here works on raw ``jnp`` arrays in **(B, H, S, D)** layout;
`flash_attention` adapts from the paddle **(B, S, H, D)** convention and
from the framework `Tensor` type.  On non-TPU backends the kernels run
in Pallas interpret mode so the exact same code path is testable on the
CPU mesh used by the test-suite.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..framework import device as _device

__all__ = ["flash_attention", "mha", "mha_reference"]

_NEG_INF = -1e30
_LANES = 128


def _ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _tile_keep_mask(seed, bh, qi, ki, block_q, block_k, p_drop):
    """Deterministic per-element keep mask for attention dropout.

    Counter-based hash (murmur3-finalizer rounds) over the element's
    GLOBAL (bh, q, k) coordinates, so the forward and both backward
    kernels regenerate the identical mask for a tile without ever
    materialising the (S, S) mask in HBM — the same trick the
    reference's vendored flashattn uses with its Philox offsets
    (``third_party/flashattn``). Plain vector int ops, so it runs the
    same on real TPU and in interpret mode (pltpu.prng_* has no
    interpret-mode lowering).
    """
    rows = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    def _i32(x):  # uint32 constant -> wrapped int32
        return jnp.int32(x - (1 << 32) if x >= (1 << 31) else x)

    h = rows * _i32(0x0001_93E9) + cols  # row-major element id, wraps
    h = h ^ seed ^ (bh * _i32(0x9E37_79B1))
    for mult in (_i32(0x85EB_CA6B), _i32(0xC2B2_AE35)):
        h = h * mult
        h = h ^ jax.lax.shift_right_logical(h, 15)
    u24 = jax.lax.shift_right_logical(h, 8)  # uniform in [0, 2^24)
    return u24 >= jnp.int32(int(p_drop * (1 << 24)))


def _interpret_default() -> bool:
    return not _device.on_tpu()


def _sds(shape, dtype, like):
    """ShapeDtypeStruct whose varying-mesh-axes (vma) match ``like`` —
    required for pallas_call outputs under shard_map(check_vma=True)
    (ring attention runs the kernel inside shard_map)."""
    vma = None
    try:
        vma = jax.typeof(like).vma
    except Exception:
        pass
    if vma is not None:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _split_refs(refs, p_drop, has_lens, has_shift=False):
    """Peel the optional SMEM scalars (dropout seed, per-row kv lengths,
    traced causal shift) off the front of a kernel's ref list."""
    i = 0
    seed_ref = lens_ref = shift_ref = None
    if p_drop > 0.0:
        seed_ref, i = refs[0], 1
    if has_lens:
        lens_ref, i = refs[i], i + 1
    if has_shift:
        shift_ref, i = refs[i], i + 1
    return seed_ref, lens_ref, shift_ref, refs[i:]


def _key_mask(lens_ref, shift_ref, b, qi, ki, block_q, block_k, q_len,
              kv_len, causal):
    """Validity mask for one (block_q, block_k) tile.

    Fixed-length: keys < kv_len, causal diagonal offset kv_len - q_len
    (end-aligned cross attention). Varlen (lens_ref set): keys < lens[b]
    per row-of-batch, causal from position 0 (self-attention semantics —
    the reference's flash_attn_unpadded path). shift_ref (traced)
    overrides the causal diagonal offset — ring attention's per-step
    (my_rank - src_rank) * block shift.
    """
    shape = (block_q, block_k)
    kcol = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    if lens_ref is not None:
        mask = kcol < lens_ref[b]
        off = 0
    else:
        mask = kcol < kv_len
        off = kv_len - q_len
    if shift_ref is not None:
        off = shift_ref[0]
    if causal:
        qrow = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        mask = jnp.logical_and(mask, kcol <= qrow + off)
    return mask


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(*refs, causal, sm_scale, block_q, block_k, q_len, kv_len,
                p_drop, has_lens, has_shift):
    seed_ref, lens_ref, shift_ref, (q_ref, k_ref, v_ref, o_ref, lse_ref,
                                    m_scr, l_scr, acc_scr) = _split_refs(
        refs, p_drop, has_lens, has_shift)
    b = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    def _compute():
        # MXU contract: feed bf16 operands, accumulate fp32 via
        # preferred_element_type — an fp32 .astype before the dot would
        # run the MXU in fp32 mode at ~1/4 throughput (this exact
        # mistake cost 56% of the r03 GPT step, profile 2026-07-30)
        s = jax.lax.dot_general(q_ref[0], k_ref[0],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale

        mask = _key_mask(lens_ref, shift_ref, b, qi, ki, block_q,
                         block_k, q_len, kv_len, causal)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        # l accumulates the UNdropped row sum (softmax denominator);
        # dropout applies to the numerator only: out = (p∘M/(1-r)) @ v / l
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if p_drop > 0.0:
            keep = _tile_keep_mask(seed_ref[0], b, qi, ki, block_q, block_k,
                                   p_drop)
            p = jnp.where(keep, p / (1.0 - p_drop), 0.0)
        pv = jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0],
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    if causal:
        # Blocks fully above the diagonal have nothing to attend to.
        _off = shift_ref[0] if shift_ref is not None else kv_len - q_len

        @pl.when(qi * block_q + block_q - 1 + _off >= ki * block_k)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        # stats ride a trailing-singleton dim: block (block_q, 1) keeps the
        # TPU (8,128) tiling rule satisfied (block (1, block_q) on a 2-D
        # (BH, S) stats array does not lower on real hardware)
        lse_ref[0] = m_scr[:, :1] + jnp.log(l_safe)


def _seed_spec_args(seed, p_drop, lens, shift=None):
    """(extra in_specs, extra args) for the SMEM scalars: dropout seed,
    per-row kv lengths, traced causal shift. All cross the custom_vjp
    boundary as f32 bitcasts (custom_vjp needs a float cotangent slot per
    traced arg)."""
    specs, args = [], ()
    for val, want in ((seed, p_drop > 0.0), (lens, lens is not None),
                      (shift, shift is not None)):
        if want:
            v32 = jax.lax.bitcast_convert_type(val, jnp.int32).reshape(-1)
            specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
            args += (v32,)
    return specs, args


def _fwd(q, k, v, seed, lens, shift, *, causal, sm_scale, block_q,
         block_k, q_len, kv_len, p_drop, interpret):
    bh, sq, d = q.shape
    skv = k.shape[1]
    nq, nk = sq // block_q, skv // block_k
    kernel = functools.partial(
        _fwd_kernel, causal=causal, sm_scale=sm_scale, block_q=block_q,
        block_k=block_k, q_len=q_len, kv_len=kv_len, p_drop=p_drop,
        has_lens=lens is not None, has_shift=shift is not None)
    seed_specs, seed_args = _seed_spec_args(seed, p_drop, lens, shift)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=seed_specs + [
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            _sds((bh, sq, d), q.dtype, q),
            _sds((bh, sq, 1), jnp.float32, q),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_fwd",
    )(*seed_args, q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
def _bwd_dq_kernel(*refs, causal, sm_scale, block_q, block_k,
                   q_len, kv_len, p_drop, has_lens, has_shift):
    seed_ref, lens_ref, shift_ref, (q_ref, k_ref, v_ref, do_ref, lse_ref,
                                    delta_ref, dq_ref,
                                    dq_scr) = _split_refs(
        refs, p_drop, has_lens, has_shift)
    b = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros(dq_scr.shape, jnp.float32)

    def _compute():
        # bf16 operands into every dot; fp32 only for accumulators and
        # the softmax math (see the fwd kernel's MXU-contract note)
        s = jax.lax.dot_general(q_ref[0], k_ref[0],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale
        mask = _key_mask(lens_ref, shift_ref, b, qi, ki, block_q,
                         block_k, q_len, kv_len, causal)
        p = jnp.exp(s - lse_ref[0])
        p = jnp.where(mask, p, 0.0)
        dp = jax.lax.dot_general(do_ref[0], v_ref[0],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if p_drop > 0.0:
            # gradient flows only through kept elements (dp ∘ M/(1-r));
            # delta = rowsum(do∘out) already reflects the dropped forward
            keep = _tile_keep_mask(seed_ref[0], b, qi, ki, block_q, block_k,
                                   p_drop)
            dp = jnp.where(keep, dp / (1.0 - p_drop), 0.0)
        ds = p * (dp - delta_ref[0])
        dq_scr[:] = dq_scr[:] + sm_scale * jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        _off = shift_ref[0] if shift_ref is not None else kv_len - q_len

        @pl.when(qi * block_q + block_q - 1 + _off >= ki * block_k)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, causal, sm_scale, block_q, block_k, q_len,
                    kv_len, p_drop, has_lens, has_shift):
    seed_ref, lens_ref, shift_ref, (q_ref, k_ref, v_ref, do_ref, lse_ref,
                                    delta_ref, dk_ref, dv_ref, dk_scr,
                                    dv_scr) = _split_refs(
        refs, p_drop, has_lens, has_shift)
    b = pl.program_id(0)
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[:] = jnp.zeros(dv_scr.shape, jnp.float32)

    def _compute():
        # bf16 operands into every dot (see the fwd kernel's MXU note)
        s = jax.lax.dot_general(q_ref[0], k_ref[0],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale
        mask = _key_mask(lens_ref, shift_ref, b, qi, ki, block_q,
                         block_k, q_len, kv_len, causal)
        p = jnp.exp(s - lse_ref[0])
        p = jnp.where(mask, p, 0.0)
        if p_drop > 0.0:
            keep = _tile_keep_mask(seed_ref[0], b, qi, ki, block_q, block_k,
                                   p_drop)
            inv = 1.0 / (1.0 - p_drop)
            p_tilde = jnp.where(keep, p * inv, 0.0)
        else:
            p_tilde = p
        # dv += p̃^T @ do (dropped probabilities fed the forward output)
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p_tilde.astype(do_ref.dtype), do_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do_ref[0], v_ref[0],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if p_drop > 0.0:
            dp = jnp.where(keep, dp * inv, 0.0)
        ds = p * (dp - delta_ref[0])
        # dk += ds^T @ q
        dk_scr[:] = dk_scr[:] + sm_scale * jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        _off = shift_ref[0] if shift_ref is not None else kv_len - q_len

        @pl.when(qi * block_q + block_q - 1 + _off >= ki * block_k)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(q, k, v, out, lse, do, seed, lens, shift, *, causal, sm_scale,
         block_q, block_k, q_len, kv_len, p_drop, interpret, dlse=None):
    bh, sq, d = q.shape
    skv = k.shape[1]
    nq, nk = sq // block_q, skv // block_k
    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1, keepdims=True)  # (bh, sq, 1)
    if dlse is not None:
        # d/ds of lse is p, so an lse cotangent folds into the delta
        # vector: ds = p∘(dp - (delta - dlse))
        delta = delta - dlse.astype(jnp.float32)
    seed_specs, seed_args = _seed_spec_args(seed, p_drop, lens, shift)
    has_lens = lens is not None
    has_shift = shift is not None

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal, sm_scale=sm_scale,
                          block_q=block_q, block_k=block_k, q_len=q_len,
                          kv_len=kv_len, p_drop=p_drop, has_lens=has_lens,
                          has_shift=has_shift),
        grid=(bh, nq, nk),
        in_specs=seed_specs + [
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=_sds((bh, sq, d), q.dtype, q),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dq",
    )(*seed_args, q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal=causal, sm_scale=sm_scale,
                          block_q=block_q, block_k=block_k, q_len=q_len,
                          kv_len=kv_len, p_drop=p_drop, has_lens=has_lens,
                          has_shift=has_shift),
        grid=(bh, nk, nq),
        in_specs=seed_specs + [
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            _sds((bh, skv, d), k.dtype, k),
            _sds((bh, skv, d), v.dtype, v),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(*seed_args, q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp wrapper on padded (BH, S, D) arrays
# ---------------------------------------------------------------------------
# seed / lens / shift are float32 (bitcast to int32 inside): custom_vjp
# needs a float cotangent slot for every traced arg, and the per-step
# dropout seed must be traced (a python int would retrace the train step
# every step). lens/shift=None are allowed: None is a static pytree.
_STATICS = (6, 7, 8, 9, 10, 11, 12, 13)


@functools.partial(jax.custom_vjp, nondiff_argnums=_STATICS)
def _flash(q, k, v, seed, lens, shift, causal, sm_scale, block_q, block_k,
           q_len, kv_len, p_drop, interpret):
    out, _ = _fwd(q, k, v, seed, lens, shift, causal=causal,
                  sm_scale=sm_scale, block_q=block_q, block_k=block_k,
                  q_len=q_len, kv_len=kv_len, p_drop=p_drop,
                  interpret=interpret)
    return out


def _flash_fwd(q, k, v, seed, lens, shift, causal, sm_scale, block_q,
               block_k, q_len, kv_len, p_drop, interpret):
    out, lse = _fwd(q, k, v, seed, lens, shift, causal=causal,
                    sm_scale=sm_scale, block_q=block_q, block_k=block_k,
                    q_len=q_len, kv_len=kv_len, p_drop=p_drop,
                    interpret=interpret)
    return out, (q, k, v, seed, lens, shift, out, lse)


def _flash_bwd(causal, sm_scale, block_q, block_k, q_len, kv_len, p_drop,
               interpret, res, do, dlse=None):
    q, k, v, seed, lens, shift, out, lse = res
    dq, dk, dv = _bwd(q, k, v, out, lse, do, seed, lens, shift,
                      causal=causal, sm_scale=sm_scale, block_q=block_q,
                      block_k=block_k, q_len=q_len, kv_len=kv_len,
                      p_drop=p_drop, interpret=interpret, dlse=dlse)
    return (dq, dk, dv, jnp.zeros((), jnp.float32),
            None if lens is None else jnp.zeros_like(lens),
            None if shift is None else jnp.zeros_like(shift))


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=_STATICS)
def _flash_lse(q, k, v, seed, lens, shift, causal, sm_scale, block_q,
               block_k, q_len, kv_len, p_drop, interpret):
    """(out, lse) variant for online-merge consumers (ring attention):
    the lse output is itself differentiable (d lse/d s = p folds into the
    backward delta vector)."""
    return _fwd(q, k, v, seed, lens, shift, causal=causal,
                sm_scale=sm_scale, block_q=block_q, block_k=block_k,
                q_len=q_len, kv_len=kv_len, p_drop=p_drop,
                interpret=interpret)


def _flash_lse_fwd(q, k, v, seed, lens, shift, causal, sm_scale, block_q,
                   block_k, q_len, kv_len, p_drop, interpret):
    out, lse = _fwd(q, k, v, seed, lens, shift, causal=causal,
                    sm_scale=sm_scale, block_q=block_q, block_k=block_k,
                    q_len=q_len, kv_len=kv_len, p_drop=p_drop,
                    interpret=interpret)
    return (out, lse), (q, k, v, seed, lens, shift, out, lse)


def _flash_lse_bwd(causal, sm_scale, block_q, block_k, q_len, kv_len,
                   p_drop, interpret, res, cots):
    do, dlse = cots
    return _flash_bwd(causal, sm_scale, block_q, block_k, q_len, kv_len,
                      p_drop, interpret, res, do, dlse=dlse)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------
def _mha_tune_key(q, k, causal, interpret):
    return (q.shape[2], k.shape[2], q.shape[3], str(q.dtype), bool(causal),
            bool(interpret))


def mha(q, k, v, *, causal=False, sm_scale=None, block_q=None, block_k=None,
        dropout_p=0.0, seed=None, seq_lens=None, causal_shift=None,
        return_lse=False, interpret=None):
    """Tiled flash attention on raw arrays in (B, H, S, D) layout.

    Pads S to the tile size and D to the 128-lane width (zero-padding is
    exact: padded head dims contribute 0 to logits; padded keys are
    masked by ``kv_len``; padded query rows are sliced off).

    ``dropout_p`` > 0 applies attention-probability dropout INSIDE the
    kernel (counter-based mask regenerated in the backward — the
    reference's flash_attn dropout path, ``flash_attn_kernel.cu``);
    ``seed`` is a traced f32 scalar that must change per training step.

    ``block_q``/``block_k`` default to an autotuned choice when
    :func:`tune_mha` has cached one for this (seq, d, dtype, causal) key
    (ref ``paddle/phi/kernels/autotune/``), else 128/128.
    """
    if interpret is None:
        interpret = _interpret_default()
    b, h, sq, d = q.shape
    skv = k.shape[2]
    if block_q is None and block_k is None:
        from . import autotune as _at
        hit = _at.cache_get("flash_mha", _mha_tune_key(
            q, k, causal, interpret)) if _at.enabled() else None
        if hit is not None:
            block_q, block_k = hit
    # explicitly passed blocks always win. Default: big q/k blocks —
    # on v5e the per-grid-step revisit overhead dominates below ~512,
    # measured 2026-07-30 at (8,16,1024,64): fwd+bwd 11.4ms at 128/128
    # vs 3.2ms at 1024/512 (exp/bench_flash.py)
    block_q = 1024 if block_q is None else block_q
    block_k = 512 if block_k is None else block_k
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    block_q = min(block_q, _ceil_to(sq, 8))
    block_k = min(block_k, _ceil_to(skv, 8))
    sq_p, skv_p = _ceil_to(sq, block_q), _ceil_to(skv, block_k)
    d_p = _ceil_to(d, _LANES)
    p_drop = float(dropout_p)
    if seed is None:
        seed = jnp.zeros((), jnp.float32)
    else:
        seed = jnp.asarray(seed, jnp.float32).reshape(())
    lens = None
    if seq_lens is not None:
        # per-sequence valid kv lengths (B,) -> (B*H,), f32-bitcast for
        # the custom_vjp boundary; varlen is self-attention semantics
        if sq != skv:
            raise ValueError("seq_lens requires self-attention (sq == skv)")
        l = jnp.asarray(seq_lens, jnp.int32).reshape(b)
        lens = jax.lax.bitcast_convert_type(
            jnp.repeat(l, h), jnp.float32)
    shift = None
    if causal_shift is not None:
        # traced diagonal offset (ring attention): col <= row + shift
        if not causal:
            raise ValueError("causal_shift requires causal=True")
        shift = jax.lax.bitcast_convert_type(
            jnp.asarray(causal_shift, jnp.int32).reshape(()), jnp.float32)

    def prep(x, s_p):
        x = x.reshape(b * h, x.shape[2], d)
        return jnp.pad(x, ((0, 0), (0, s_p - x.shape[1]), (0, d_p - d)))

    qp, kp, vp = prep(q, sq_p), prep(k, skv_p), prep(v, skv_p)
    if return_lse:
        out, lse = _flash_lse(qp, kp, vp, seed, lens, shift, causal,
                              sm_scale, block_q, block_k, sq, skv, p_drop,
                              interpret)
        return (out[:, :sq, :d].reshape(b, h, sq, d),
                lse[:, :sq, 0].reshape(b, h, sq))
    out = _flash(qp, kp, vp, seed, lens, shift, causal, sm_scale, block_q,
                 block_k, sq, skv, p_drop, interpret)
    return out[:, :sq, :d].reshape(b, h, sq, d)


def _mha_cost_fn(b, h, sq, skv, d, itemsize):
    """Per-candidate cost estimate for the flash-attention search:
    analytic FLOPs/bytes of the XLA reference (scaled from a small
    sample) order the survivors on the roofline; the vmem working set
    and MXU-fill checks reject configs before any timing."""
    from . import autotune as _at
    d_p = _ceil_to(d, _LANES)
    sb, ss = min(b * h, 4), min(sq, 256)
    sample = jnp.zeros((1, sb, ss, d), jnp.float32)
    seed = _at.analytic_seed(
        lambda a: mha_reference(a, a, a), sample)
    scale = (b * h * sq * skv) / max(sb * ss * ss, 1)
    flops = seed["flops"] * scale if seed else 4.0 * b * h * sq * skv * d
    bytes_ = seed["bytes"] * scale if seed else \
        4.0 * b * h * (sq + skv) * d * itemsize

    def cost(cfg):
        bq = min(int(cfg[0]), _ceil_to(sq, 8))
        bk = min(int(cfg[1]), _ceil_to(skv, 8))
        # per-grid-step tiles: q/o in native dtype + f32 acc, k/v
        # blocks, and the (bq, 128) m/l scratch rows
        vmem = (2 * bq * d_p * itemsize + bq * d_p * 4
                + 2 * bk * d_p * itemsize + 2 * bq * _LANES * 4)
        return {"flops": flops, "bytes": bytes_, "vmem_bytes": vmem,
                "mxu_underfill": min(bq, bk) < 8}
    return cost


def tune_mha(q, k, v, *, causal=False, interpret=None,
             candidates=((128, 128), (256, 256), (512, 256), (512, 512),
                         (1024, 256), (1024, 512))):
    """Warmup autotune for :func:`mha`: candidate (block_q, block_k)
    configs are pruned by the cost-model roofline (vmem overflow / MXU
    underfill rejected before timing — see :func:`autotune.search`),
    survivors are eagerly timed on REAL arrays, and the winner is cached
    keyed by (seq, d, dtype, causal) so subsequent (including traced)
    calls pick it up. Returns (best_config, timings). Candidates larger
    than the padded sequence are deduplicated after clamping."""
    from . import autotune as _at

    if interpret is None:
        interpret = _interpret_default()
    b, h, sq, d = q.shape
    skv = k.shape[2]
    seen, todo = set(), []
    for bq, bk in candidates:
        clamped = (min(bq, _ceil_to(sq, 8)), min(bk, _ceil_to(skv, 8)))
        if clamped not in seen:
            seen.add(clamped)
            todo.append(clamped)

    state = {"q": q}

    def run(cfg):
        # thread the output back in (fresh inputs per call); the host
        # readback is the fence
        out = mha(state["q"], k, v, causal=causal, block_q=cfg[0],
                  block_k=cfg[1], interpret=interpret)
        state["q"] = (out.astype(jnp.float32) * 1e-3).astype(q.dtype)
        float(jnp.sum(state["q"].astype(jnp.float32)))

    best, timings = _at.search(
        "flash_mha", _mha_tune_key(q, k, causal, interpret), run, todo,
        cost=_mha_cost_fn(b, h, sq, skv, d, q.dtype.itemsize))
    # explicit tuning is intent: turn cache consumption on (still
    # switch-offable via incubate.autotune.set_config kernel.enable=False)
    _at.set_enabled(True)
    return best, timings


def mha_reference(q, k, v, *, causal=False, sm_scale=None):
    """Plain-XLA reference used by the kernel unit tests ((B,H,S,D))."""
    d = q.shape[-1]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if causal:
        sq, skv = s.shape[-2], s.shape[-1]
        qrow = jax.lax.broadcasted_iota(jnp.int32, (sq, skv), 0)
        kcol = jax.lax.broadcasted_iota(jnp.int32, (sq, skv), 1)
        s = jnp.where(kcol <= qrow + (skv - sq), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(
        q.dtype)


def flash_attention(query, key, value, *, causal=False, dropout_p=0.0,
                    interpret=None):
    """Framework-facing entry: paddle (B, S, H, D) layout, Tensor in/out.

    TPU replacement for the reference's flash_attn path
    (``python/paddle/nn/functional/flash_attention.py`` →
    ``paddle/phi/kernels/gpu/flash_attn_kernel.cu``), incl. its dropout
    support. The per-call dropout seed draws from the framework
    generator, so it folds from the trace key under jit (fresh mask
    every compiled step) and from host state in eager mode.
    """
    from .op_utils import ensure_tensor, nary
    from ..framework import random as _random

    q, k, v = (ensure_tensor(t) for t in (query, key, value))
    inputs = [q, k, v]
    if dropout_p > 0.0:
        key_seed = jax.random.bits(_random.next_key(), (),
                                   jnp.uint32).astype(jnp.int32)
        seed_f32 = jax.lax.bitcast_convert_type(key_seed, jnp.float32)
        inputs.append(ensure_tensor(seed_f32))

    def f(qd, kd, vd, *rest):
        o = mha(jnp.swapaxes(qd, 1, 2), jnp.swapaxes(kd, 1, 2),
                jnp.swapaxes(vd, 1, 2), causal=causal,
                dropout_p=dropout_p, seed=rest[0] if rest else None,
                interpret=interpret)
        return jnp.swapaxes(o, 1, 2)

    return nary(f, inputs, name="flash_attention")


# ---------------------------------------------------------------------------
# packed (ragged varlen) flash attention
# ---------------------------------------------------------------------------
# True varlen: sequences stay PACKED (total_tokens, H, D) — no pad-to-max
# batch. Each sequence is block-aligned inside a packed buffer so every
# (block_q, block_k) tile belongs to exactly one sequence; per-q-block
# [klo, khi] (and per-k-block [qlo, qhi]) SMEM ranges skip everything off
# the block-diagonal band. Compute scales as sum(len_i * len_j-of-own-seq)
# = O(sum len^2), the true ragged cost, instead of the padded path's
# O(B * max_len^2). Cross-attention lengths (cu_q != cu_k) are supported;
# causal uses the bottom-right alignment (col_pos <= row_pos + len_k -
# len_q), the flash-attn varlen convention.
# Ref: ``python/paddle/nn/functional/flash_attention.py:272`` over
# ``third_party/flashattn`` cu_seqlens grids.

def _packed_mask(pq_ref, okq_ref, off_ref, pk_ref, okk_ref, causal,
                 block_q, block_k):
    pq = pq_ref[:, :1]                        # (bq, 1) int32
    okq = okq_ref[:, :1] > 0
    # k-side metadata arrives as (1, bk) lane-major rows: a column ->
    # row relayout in the kernel costs Mosaic ~100 MB of scoped VMEM at
    # 512-blocks under the causal compare (v5e, PR 21)
    pk = pk_ref[...]                          # (1, bk) int32
    okk = okk_ref[...] > 0
    mask = jnp.logical_and(okq, okk)
    if causal:
        off = off_ref[:, :1]
        mask = jnp.logical_and(mask, pk <= pq + off)
    return mask


def _pk_fwd_kernel(*refs, causal, sm_scale, block_q, block_k, p_drop):
    i = 1 if p_drop > 0.0 else 0
    seed_ref = refs[0] if p_drop > 0.0 else None
    (klo_ref, khi_ref, q_ref, k_ref, v_ref, pq_ref, okq_ref, off_ref,
     pk_ref, okk_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr) = refs[i:]
    h = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    def _compute():
        s = jax.lax.dot_general(q_ref[0], k_ref[0],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale
        mask = _packed_mask(pq_ref, okq_ref, off_ref, pk_ref, okk_ref,
                            causal, block_q, block_k)
        s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if p_drop > 0.0:
            keep = _tile_keep_mask(seed_ref[0], h, qi, ki, block_q,
                                   block_k, p_drop)
            p = jnp.where(keep, p / (1.0 - p_drop), 0.0)
        pv = jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0],
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(jnp.logical_and(ki >= klo_ref[qi], ki <= khi_ref[qi]))
    def _():
        _compute()

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:, :1] + jnp.log(l_safe)


def _pk_bwd_dq_kernel(*refs, causal, sm_scale, block_q, block_k, p_drop):
    i = 1 if p_drop > 0.0 else 0
    seed_ref = refs[0] if p_drop > 0.0 else None
    (klo_ref, khi_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
     pq_ref, okq_ref, off_ref, pk_ref, okk_ref, dq_ref,
     dq_scr) = refs[i:]
    h = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros(dq_scr.shape, jnp.float32)

    def _compute():
        s = jax.lax.dot_general(q_ref[0], k_ref[0],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale
        mask = _packed_mask(pq_ref, okq_ref, off_ref, pk_ref, okk_ref,
                            causal, block_q, block_k)
        p = jnp.exp(s - lse_ref[0])
        p = jnp.where(mask, p, 0.0)
        dp = jax.lax.dot_general(do_ref[0], v_ref[0],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if p_drop > 0.0:
            keep = _tile_keep_mask(seed_ref[0], h, qi, ki, block_q,
                                   block_k, p_drop)
            dp = jnp.where(keep, dp / (1.0 - p_drop), 0.0)
        ds = p * (dp - delta_ref[0])
        dq_scr[:] = dq_scr[:] + sm_scale * jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(ki >= klo_ref[qi], ki <= khi_ref[qi]))
    def _():
        _compute()

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _pk_bwd_dkv_kernel(*refs, causal, sm_scale, block_q, block_k, p_drop):
    i = 1 if p_drop > 0.0 else 0
    seed_ref = refs[0] if p_drop > 0.0 else None
    (qlo_ref, qhi_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
     pq_ref, okq_ref, off_ref, pk_ref, okk_ref, dk_ref, dv_ref, dk_scr,
     dv_scr) = refs[i:]
    h = pl.program_id(0)
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[:] = jnp.zeros(dv_scr.shape, jnp.float32)

    def _compute():
        s = jax.lax.dot_general(q_ref[0], k_ref[0],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * sm_scale
        mask = _packed_mask(pq_ref, okq_ref, off_ref, pk_ref, okk_ref,
                            causal, block_q, block_k)
        p = jnp.exp(s - lse_ref[0])
        p = jnp.where(mask, p, 0.0)
        if p_drop > 0.0:
            keep = _tile_keep_mask(seed_ref[0], h, qi, ki, block_q,
                                   block_k, p_drop)
            inv = 1.0 / (1.0 - p_drop)
            p_tilde = jnp.where(keep, p * inv, 0.0)
        else:
            p_tilde = p
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p_tilde.astype(do_ref.dtype), do_ref[0],
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do_ref[0], v_ref[0],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if p_drop > 0.0:
            dp = jnp.where(keep, dp * inv, 0.0)
        ds = p * (dp - delta_ref[0])
        dk_scr[:] = dk_scr[:] + sm_scale * jax.lax.dot_general(
            ds.astype(q_ref.dtype), q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(qi >= qlo_ref[ki], qi <= qhi_ref[ki]))
    def _():
        _compute()

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _pk_fwd(q, k, v, seed, meta, *, causal, sm_scale, block_q, block_k,
            p_drop, interpret):
    """q/k/v: (H, CapQ/K, D). meta: int32 arrays (see mha_packed)."""
    pos_q, ok_q, off_q, pos_k, ok_k, klo, khi, qlo, qhi = meta
    H, capq, d = q.shape
    capk = k.shape[1]
    nq, nk = capq // block_q, capk // block_k
    seed_specs, seed_args = (([pl.BlockSpec(memory_space=pltpu.SMEM)],
                              (jax.lax.bitcast_convert_type(
                                  seed, jnp.int32).reshape(-1),))
                             if p_drop > 0.0 else ([], ()))
    row_spec_q = pl.BlockSpec((block_q, 1), lambda h, i, j: (i, 0))
    row_spec_k = pl.BlockSpec((1, block_k), lambda h, i, j: (0, j))
    out, lse = pl.pallas_call(
        functools.partial(_pk_fwd_kernel, causal=causal, sm_scale=sm_scale,
                          block_q=block_q, block_k=block_k, p_drop=p_drop),
        grid=(H, nq, nk),
        in_specs=seed_specs + [
            pl.BlockSpec(memory_space=pltpu.SMEM),   # klo
            pl.BlockSpec(memory_space=pltpu.SMEM),   # khi
            pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda h, i, j: (h, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda h, i, j: (h, j, 0)),
            row_spec_q, row_spec_q, row_spec_q,
            row_spec_k, row_spec_k,
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda h, i, j: (h, i, 0)),
        ],
        out_shape=[
            _sds((H, capq, d), q.dtype, q),
            _sds((H, capq, 1), jnp.float32, q),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_packed_fwd",
    )(*seed_args, klo, khi, q, k, v, pos_q[:, None], ok_q[:, None],
      off_q[:, None], pos_k[None, :], ok_k[None, :])
    return out, lse


def _pk_bwd(q, k, v, out, lse, do, seed, meta, *, causal, sm_scale,
            block_q, block_k, p_drop, interpret):
    pos_q, ok_q, off_q, pos_k, ok_k, klo, khi, qlo, qhi = meta
    H, capq, d = q.shape
    capk = k.shape[1]
    nq, nk = capq // block_q, capk // block_k
    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1, keepdims=True)
    seed_specs, seed_args = (([pl.BlockSpec(memory_space=pltpu.SMEM)],
                              (jax.lax.bitcast_convert_type(
                                  seed, jnp.int32).reshape(-1),))
                             if p_drop > 0.0 else ([], ()))
    row_q = pl.BlockSpec((block_q, 1), lambda h, i, j: (i, 0))
    row_k = pl.BlockSpec((1, block_k), lambda h, i, j: (0, j))
    dq = pl.pallas_call(
        functools.partial(_pk_bwd_dq_kernel, causal=causal,
                          sm_scale=sm_scale, block_q=block_q,
                          block_k=block_k, p_drop=p_drop),
        grid=(H, nq, nk),
        in_specs=seed_specs + [
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda h, i, j: (h, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda h, i, j: (h, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda h, i, j: (h, i, 0)),
            row_q, row_q, row_q, row_k, row_k,
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda h, i, j: (h, i, 0)),
        out_shape=_sds((H, capq, d), q.dtype, q),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_packed_bwd_dq",
    )(*seed_args, klo, khi, q, k, v, do, lse, delta, pos_q[:, None],
      ok_q[:, None], off_q[:, None], pos_k[None, :], ok_k[None, :])

    row_q2 = pl.BlockSpec((block_q, 1), lambda h, j, i: (i, 0))
    row_k2 = pl.BlockSpec((1, block_k), lambda h, j, i: (0, j))
    dk, dv = pl.pallas_call(
        functools.partial(_pk_bwd_dkv_kernel, causal=causal,
                          sm_scale=sm_scale, block_q=block_q,
                          block_k=block_k, p_drop=p_drop),
        grid=(H, nk, nq),
        in_specs=seed_specs + [
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_q, d), lambda h, j, i: (h, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda h, j, i: (h, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda h, j, i: (h, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda h, j, i: (h, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda h, j, i: (h, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda h, j, i: (h, i, 0)),
            row_q2, row_q2, row_q2, row_k2, row_k2,
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda h, j, i: (h, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda h, j, i: (h, j, 0)),
        ],
        out_shape=[
            _sds((H, capk, d), k.dtype, k),
            _sds((H, capk, d), v.dtype, v),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_packed_bwd_dkv",
    )(*seed_args, qlo, qhi, q, k, v, do, lse, delta, pos_q[:, None],
      ok_q[:, None], off_q[:, None], pos_k[None, :], ok_k[None, :])
    return dq, dk, dv


_PK_STATICS = tuple(range(13, 19))


@functools.partial(jax.custom_vjp, nondiff_argnums=_PK_STATICS)
def _pk_flash(q, k, v, seed, pos_q, ok_q, off_q, pos_k, ok_k, klo, khi,
              qlo, qhi, causal, sm_scale, block_q, block_k, p_drop,
              interpret):
    out, _ = _pk_fwd(q, k, v, seed,
                     (pos_q, ok_q, off_q, pos_k, ok_k, klo, khi, qlo, qhi),
                     causal=causal, sm_scale=sm_scale, block_q=block_q,
                     block_k=block_k, p_drop=p_drop, interpret=interpret)
    return out


def _pk_flash_fwd(q, k, v, seed, pos_q, ok_q, off_q, pos_k, ok_k, klo, khi,
                  qlo, qhi, causal, sm_scale, block_q, block_k, p_drop,
                  interpret):
    meta = (pos_q, ok_q, off_q, pos_k, ok_k, klo, khi, qlo, qhi)
    out, lse = _pk_fwd(q, k, v, seed, meta, causal=causal,
                       sm_scale=sm_scale, block_q=block_q, block_k=block_k,
                       p_drop=p_drop, interpret=interpret)
    return out, (q, k, v, seed, meta, out, lse)


def _pk_flash_bwd(causal, sm_scale, block_q, block_k, p_drop, interpret,
                  res, do):
    q, k, v, seed, meta, out, lse = res
    dq, dk, dv = _pk_bwd(q, k, v, out, lse, do, seed, meta, causal=causal,
                         sm_scale=sm_scale, block_q=block_q,
                         block_k=block_k, p_drop=p_drop,
                         interpret=interpret)
    zmeta = tuple(jnp.zeros_like(m) for m in meta)
    return (dq, dk, dv, jnp.zeros((), jnp.float32)) + zmeta


_pk_flash.defvjp(_pk_flash_fwd, _pk_flash_bwd)


def mha_packed(q, k, v, cu_q, cu_k, *, causal=False, sm_scale=None,
               dropout_p=0.0, seed=None, block_q=None, block_k=None,
               interpret=None):
    """Ragged varlen flash attention over PACKED tokens.

    q: (total_q, H, D); k/v: (total_k, H, D); cu_q/cu_k: (B+1,) int32
    cumulative lengths (may be traced). Cross-attention lengths
    (cu_q != cu_k) are supported; ``causal`` uses bottom-right alignment
    within each pair (col_pos <= row_pos + len_k - len_q).

    Each sequence is block-aligned inside a static-capacity packed
    buffer; the kernels skip all tiles outside each block's own
    sequence, so compute is O(sum_i lq_i * lk_i), not O(B * max^2).
    """
    if interpret is None:
        interpret = _interpret_default()
    total_q, H, d_in = q.shape
    total_k = k.shape[0]
    B = cu_q.shape[0] - 1
    bq = 512 if block_q is None else block_q
    bk = 512 if block_k is None else block_k
    bq = min(bq, _ceil_to(total_q, 8))
    bk = min(bk, _ceil_to(total_k, 8))
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d_in)
    d = _ceil_to(d_in, _LANES)
    capq = (total_q + B * bq + bq - 1) // bq * bq
    capk = (total_k + B * bk + bk - 1) // bk * bk
    nq, nk = capq // bq, capk // bk
    i32 = jnp.int32
    cu_q = jnp.asarray(cu_q, i32)
    cu_k = jnp.asarray(cu_k, i32)
    lens_q = cu_q[1:] - cu_q[:-1]
    lens_k = cu_k[1:] - cu_k[:-1]
    plen_q = (lens_q + bq - 1) // bq * bq
    plen_k = (lens_k + bk - 1) // bk * bk
    starts_q = jnp.concatenate([jnp.zeros(1, i32),
                                jnp.cumsum(plen_q)])[:-1]
    starts_k = jnp.concatenate([jnp.zeros(1, i32),
                                jnp.cumsum(plen_k)])[:-1]
    off_seq = lens_k - lens_q  # bottom-right causal alignment

    def pack_meta(total, cap, cu, starts, lens, offs):
        tok = jnp.arange(total, dtype=i32)
        s_of = jnp.clip(jnp.searchsorted(cu, tok, side="right") - 1,
                        0, B - 1)
        newpos = starts[s_of] + tok - cu[s_of]
        r = jnp.arange(cap, dtype=i32)
        sp = jnp.clip(jnp.searchsorted(starts, r, side="right") - 1,
                      0, B - 1)
        local = r - starts[sp]
        valid = local < lens[sp]
        pos = jnp.where(valid, local, -1)
        return newpos, pos, valid.astype(i32), offs[sp]

    def scatter(x, cap, newpos):
        buf = jnp.zeros((cap, H, d), x.dtype)
        xp = jnp.pad(x, ((0, 0), (0, 0), (0, d - d_in)))
        return jnp.swapaxes(buf.at[newpos].set(xp), 0, 1)

    newpos_q, pos_q, ok_q, off_q = pack_meta(
        total_q, capq, cu_q, starts_q, lens_q, off_seq)
    newpos_k, pos_k, ok_k, _ = pack_meta(
        total_k, capk, cu_k, starts_k, lens_k, off_seq)
    qp = scatter(q, capq, newpos_q)
    kp = scatter(k, capk, newpos_k)
    vp = scatter(v, capk, newpos_k)  # k and v share the packing

    # per-q-block k ranges
    rb = jnp.arange(nq, dtype=i32) * bq
    sb = jnp.clip(jnp.searchsorted(starts_q, rb, side="right") - 1,
                  0, B - 1)
    has_data = rb < starts_q[sb] + plen_q[sb]
    klo = jnp.where(has_data, starts_k[sb] // bk, 1)
    khi_full = jnp.where(has_data,
                         (starts_k[sb] + plen_k[sb] - 1) // bk, 0)
    if causal:
        end_local = rb + bq - 1 - starts_q[sb]
        kcol_max = starts_k[sb] + end_local + off_seq[sb]
        khi = jnp.where(kcol_max >= starts_k[sb],
                        jnp.minimum(khi_full, kcol_max // bk), 0)
        khi = jnp.where(has_data, khi, 0)
        klo = jnp.where(jnp.logical_and(has_data,
                                        kcol_max >= starts_k[sb]),
                        klo, 1)
    else:
        khi = khi_full
    # per-k-block q ranges (dkv)
    rk = jnp.arange(nk, dtype=i32) * bk
    sk = jnp.clip(jnp.searchsorted(starts_k, rk, side="right") - 1,
                  0, B - 1)
    has_k = rk < starts_k[sk] + plen_k[sk]
    qlo_full = jnp.where(has_k, starts_q[sk] // bq, 1)
    qhi = jnp.where(has_k, (starts_q[sk] + plen_q[sk] - 1) // bq, 0)
    if causal:
        qmin_global = starts_q[sk] + (rk - starts_k[sk]) - off_seq[sk]
        qmin_global = jnp.maximum(qmin_global, starts_q[sk])
        qlo = jnp.maximum(qlo_full, qmin_global // bq)
        qlo = jnp.where(has_k, qlo, 1)
    else:
        qlo = qlo_full

    if seed is None:
        seed = jnp.zeros((), jnp.float32)
    else:
        seed = jnp.asarray(seed, jnp.float32).reshape(())
    out = _pk_flash(qp, kp, vp, seed, pos_q, ok_q, off_q, pos_k, ok_k,
                    klo, khi, qlo, qhi, causal, sm_scale, bq, bk,
                    float(dropout_p), interpret)
    out = jnp.swapaxes(out, 0, 1)                 # (capq, H, D)
    return out[newpos_q][:, :, :d_in]             # packed (total_q, H, D)
