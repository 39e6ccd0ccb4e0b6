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
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..framework import device as _device

__all__ = ["flash_attention", "mha", "mha_reference"]

_NEG_INF = -1e30
_LANES = 128
# mha's default tile (block_q, block_k). On a v5e at (8, 16, 1024, 64)
# bf16, causal, dropout 0.1, forward + dQ + dK/dV in ms a call (my chip
# run, PR 30, exp/bench_flash.py): 512/512 0.62 + 0.62 + 0.82 = 2.05,
# 256/512 2.33, 512/256 2.40, 1024/512 2.60 (the diagonal skips nothing
# there), 256/256 2.68, 384/384 2.81, 128/512 2.95; the parent's kernels
# at their 1024/512 3.07. A tile costs a fixed ~0.3 us beside its
# elements' work, so a finer diagonal loses more than it skips.
_BLOCK_Q = 512
_BLOCK_K = 512


def _ceil_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _tile_keep_mask(seed, bh, qi, ki, block_q, block_k, p_drop):
    """Deterministic per-element keep mask for attention dropout.

    Counter-based hash (murmur3-finalizer rounds) over the element's
    GLOBAL (bh, q, k) coordinates, so the forward and both backward
    kernels regenerate the identical mask for a tile without ever
    materialising the (S, S) mask in HBM — the same trick the
    reference's vendored flashattn uses with its Philox offsets
    (``third_party/flashattn``) — and any tiling of the square gives an
    element the same bit. Plain vector int ops, so it runs the same on
    real TPU and in interpret mode (pltpu.prng_* has no interpret-mode
    lowering).
    """
    def _i32(x):  # uint32 constant -> wrapped int32
        return jnp.int32(x - (1 << 32) if x >= (1 << 31) else x)

    # row-major element id, wraps: the row's term is made on a column
    # and the key's on a row, so the tile pays one add for the pair
    rows = (qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0)) * _i32(0x0001_93E9)
    cols = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_k), 1)
    h = (rows + cols) ^ (seed ^ (bh * _i32(0x9E37_79B1)))
    for mult in (_i32(0x85EB_CA6B), _i32(0xC2B2_AE35)):
        h = h * mult
        h = h ^ jax.lax.shift_right_logical(h, 15)
    # keep where the top 24 bits, uniform in [0, 2^24), reach the
    # threshold: one unsigned compare of the whole word
    return jax.lax.bitcast_convert_type(h, jnp.uint32) >= jnp.uint32(
        int(p_drop * (1 << 24)) << 8)


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


def _offset_limit(lens_ref, shift_ref, b, q_len, kv_len):
    """(causal diagonal offset, first key past the valid ones) of a call.

    Fixed-length: keys < kv_len, causal diagonal offset kv_len - q_len
    (end-aligned cross attention). Varlen (lens_ref set): keys < lens[b]
    per row-of-batch, causal from position 0 (self-attention semantics —
    the reference's flash_attn_unpadded path). shift_ref (traced)
    overrides the causal diagonal offset — ring attention's per-step
    (my_rank - src_rank) * block shift.
    """
    off, limit = kv_len - q_len, kv_len
    if lens_ref is not None:
        off, limit = 0, lens_ref[b]
    if shift_ref is not None:
        off = shift_ref[0]
    return off, limit


def _key_mask(off, limit, qi, ki, block_q, block_k, causal, ends=True):
    """Validity mask for one (block_q, block_k) tile: key column <
    ``limit`` (``ends``: the keys may end inside a tile) and, causal,
    column <= row + ``off`` (`_offset_limit`). The tile's place is in
    the scalars the tile-local iotas are compared with."""
    shape = (block_q, block_k)
    kcol = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    mask = kcol < limit - ki * block_k if ends else None
    if causal:
        qrow = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        diag = kcol - qrow <= off + qi * block_q - ki * block_k
        mask = diag if mask is None else jnp.logical_and(mask, diag)
    return mask


# -- which chunks a block meets ----------------------------------------------
# A grid step holds one block of queries (one block of keys in dK/dV) and
# walks the other operand in chunks. These two functions say which: the
# chunks every element of which is attended take no mask, the chunks that
# the diagonal or the end of the keys crosses take `_key_mask`, the rest
# are not visited. They take Python ints (the static plan, the tests) and
# traced scalars (the kernels) alike.

def _static(*xs):
    return all(isinstance(x, int) for x in xs)


def _clip(x, lo, hi):
    return min(max(x, lo), hi) if _static(x, lo, hi) else jnp.clip(x, lo, hi)


def _floordiv(x, n):  # x >= 0
    return x // n if _static(x) else jax.lax.div(x, jnp.int32(n))


def _select(cond, a, b):
    return (a if cond else b) if isinstance(cond, bool) \
        else jnp.where(cond, a, b)


def _kv_chunk_range(row0, rows, off, limit, chunk, n_chunks, causal):
    """(full, stop) for the query rows [row0, row0 + rows): key chunks
    [0, full) lie wholly under the diagonal and inside ``limit``, chunks
    [full, stop) are crossed by one of them, none from ``stop`` on holds
    an attended key."""
    end = n_chunks * chunk
    every, some = limit, limit      # keys every row / the last row sees
    if causal:
        every = _clip(row0 + off + 1, 0, limit)
        some = _clip(row0 + rows + off, 0, limit)
    every, some = _clip(every, 0, end), _clip(some, 0, end)
    return _floordiv(every, chunk), _floordiv(some + chunk - 1, chunk)


def _q_chunk_range(col0, cols, off, limit, chunk, n_chunks, causal):
    """(first, full) for the key columns [col0, col0 + cols): no query
    chunk before ``first`` attends them, chunks [first, full) are crossed
    by the diagonal or the end of the keys, chunks [full, n_chunks)
    attend every one."""
    end = n_chunks * chunk
    first = full = 0
    if causal:
        first = _floordiv(_clip(col0 - off, 0, end), chunk)
        full = _floordiv(_clip(col0 + cols - 1 - off, 0, end) + chunk - 1,
                         chunk)
    full = _select(col0 + cols > limit, n_chunks, full)
    first = _select(col0 >= limit, n_chunks, first)
    return first, full


def _loop(lo, hi, body):
    """``body(j)`` for j in [lo, hi) as a loop inside the kernel (its
    text once, whatever the trip count: an unrolled walk cost PR 28 40 s
    of tracing); a range that is empty statically traces nothing."""
    if _static(lo, hi) and lo >= hi:
        return
    jax.lax.fori_loop(lo, hi, lambda j, c: (body(j), c)[1], 0)


def _in_span(lo, hi, base, per_span):
    """A range of the whole operand's chunks as the part of it inside the
    resident span [base, base + per_span), in the span's own numbering."""
    return _clip(lo - base, 0, per_span), _clip(hi - base, 0, per_span)


def _chunk_rows(j, chunk, n_chunks):
    """The rows of chunk ``j`` of a resident span."""
    if n_chunks == 1:
        return slice(None)
    return pl.ds(pl.multiple_of(j * chunk, chunk), chunk)


# VMEM the resident operands of a grid step may hold, both pipeline
# buffers counted: of the 16 MiB a v5e kernel is given, the inner tile's
# float32 temporaries and the block's own operands need the rest.
_RESIDENT_BYTES = 6 << 20


def _resident_span(rows, chunk, row_bytes):
    """Rows of the walked operand a grid step keeps in VMEM: all of them
    where they fit `_RESIDENT_BYTES`, else the equal super-blocks (whole
    chunks) of the fewest that do. ``row_bytes``: one row of every
    resident operand."""
    fit = max(_RESIDENT_BYTES // (2 * row_bytes) // chunk, 1) * chunk
    n_super = -(-rows // fit)
    return _ceil_to(-(-rows // n_super), chunk)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
# What a tile's vector work is made of, measured on the chip (PERF.md §6,
# PR 30), decides the form of all three kernels:
#   * scores and statistics are in log2 units: the scale carries log2(e),
#     exp2 is what the hardware has, and lse goes out in natural units;
#   * a row's running maximum and sum stay lane-replicated (block_q, 128)
#     values: no (rows, 1) column is sliced out of or broadcast back into
#     one, and the sum folds its lanes once, at the end;
#   * dropout's 1 / (1 - r) is no multiply on the tile: the forward puts
#     it on the output rows, the backward into the exponent.

_LOG2E = math.log2(math.e)


def _lanes(x, n):
    """A lane-replicated (rows, 128) value as wide as an (rows, n) tile."""
    return x if n == _LANES else jnp.tile(x, (1, n // _LANES))


def _fold_lanes(x):
    """(rows, n) -> (rows, 128): the sum of the tile's lane tiles, so that
    a row's sum is the sum of the result's lanes."""
    out = x[:, :_LANES]
    for r in range(1, x.shape[1] // _LANES):
        out = out + x[:, r * _LANES:(r + 1) * _LANES]
    return out


def _fwd_kernel(*refs, causal, sm_scale, block_q, block_k, n_k, q_len,
                kv_len, p_drop, has_lens, has_shift, ends):
    seed_ref, lens_ref, shift_ref, (q_ref, k_ref, v_ref, o_ref, lse_ref,
                                    m_scr, l_scr, acc_scr) = _split_refs(
        refs, p_drop, has_lens, has_shift)
    b = pl.program_id(0)
    qi = pl.program_id(1)
    sb = pl.program_id(2)
    per_span = k_ref.shape[1] // block_k
    base = sb * per_span

    @pl.when(sb == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    off, limit = _offset_limit(lens_ref, shift_ref, b, q_len, kv_len)

    def chunk(j, masked):
        at = _chunk_rows(j, block_k, per_span)
        # MXU contract: feed bf16 operands, accumulate fp32 via
        # preferred_element_type — an fp32 .astype before the dot would
        # run the MXU in fp32 mode at ~1/4 throughput (this exact
        # mistake cost 56% of the r03 GPT step, profile 2026-07-30)
        s = jax.lax.dot_general(q_ref[0], k_ref[0, at, :],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * (sm_scale * _LOG2E)
        mask = _key_mask(off, limit, qi, base + j, block_q, block_k, causal,
                         ends) if masked else None
        if mask is not None:
            s = jnp.where(mask, s, _NEG_INF)

        # a masked score leaves exp2 as 0 under any maximum but _NEG_INF
        # itself; what a row gathers before its first key, the first
        # key's alpha = 0 wipes, and `_finalize` a row that never saw one
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp2(s - _lanes(m_new, block_k))
        alpha = jnp.exp2(m_prev - m_new)
        # l accumulates the UNdropped row sum (softmax denominator);
        # dropout applies to the numerator only: out = (p∘M/(1-r)) @ v / l
        l_scr[:] = l_scr[:] * alpha + _fold_lanes(p)
        if p_drop > 0.0:
            p = jnp.where(_tile_keep_mask(seed_ref[0], b, qi, base + j,
                                          block_q, block_k, p_drop), p, 0.0)
        pv = jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0, at, :],
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * _lanes(alpha, acc_scr.shape[1]) + pv
        m_scr[:] = m_new

    # Chunks above the diagonal have nothing to attend to, chunks wholly
    # under it nothing to mask.
    full, stop = _in_span(*_kv_chunk_range(
        qi * block_q, block_q, off, limit, block_k, n_k, causal),
        base, per_span)
    _loop(0, full, lambda j: chunk(j, False))
    _loop(full, stop, lambda j: chunk(j, True))

    @pl.when(sb == pl.num_programs(2) - 1)
    def _finalize():
        # a row that saw no key holds what its masked chunks gathered
        m = m_scr[:, :1]
        seen = m != _NEG_INF
        l = jnp.sum(l_scr[:], axis=-1, keepdims=True)
        o_ref[0] = (acc_scr[:] * jnp.where(
            seen, (1.0 / (1.0 - p_drop)) / l, 0.0)).astype(o_ref.dtype)
        # stats ride a trailing-singleton dim: block (block_q, 1) keeps the
        # TPU (8,128) tiling rule satisfied (block (1, block_q) on a 2-D
        # (BH, S) stats array does not lower on real hardware)
        lse_ref[0] = jnp.where(seen, (m + jnp.log2(l)) * (1.0 / _LOG2E),
                               _NEG_INF)


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


def _trace_time_counter(name: str, help_: str, labelnames):
    """The registry's counter ``name``, or None while telemetry is off
    (the registry must stay empty then) or the registry cannot be had.
    Looked up per call (not cached) so a registry reset doesn't strand
    increments on a stale counter — dispatch decisions and kernel
    traces are trace-time events, not hot-loop work."""
    try:
        from ..observability.metrics import get_registry
        from ..observability.telemetry import get_telemetry
        if not get_telemetry().enabled:
            return None
        return get_registry().counter(name, help_, labelnames=labelnames)
    except Exception:
        return None


def kernel_jit(kernel: str, static_argnames):
    """Decorator for the host wrapper of every Pallas kernel the tape
    differentiates: ``jax.jit`` on the wrapper's static plan
    (``static_argnames``).

    A kernel is then traced and lowered once per signature, however many
    times a program calls it, and each call carries the one serialised
    Mosaic body that trace made.  That is what lets XLA merge the
    forward the tape's deferred ``jax.vjp`` traces again with the call
    the forward pass made: a body traced at another call site carries
    another source location, and XLA merges only byte-equal calls.
    Each trace counts once on ``pt_pallas_traces_total{kernel=}``."""
    def wrap(fn):
        @functools.wraps(fn)
        def traced(*args, **kwargs):
            c = _trace_time_counter(
                "pt_pallas_traces_total",
                "Pallas kernel wrappers traced, by kernel (once a "
                "signature; every later call reuses the trace)", ("kernel",))
            if c is not None:
                c.inc(kernel=kernel)
            return fn(*args, **kwargs)
        return jax.jit(traced, static_argnames=static_argnames)
    return wrap


# A differentiated kernel's wrapper goes through `kernel_jit` on its
# static plan: a model's layers share one trace and one lowering of a
# kernel's text (24 layers x 4 calls of it were 14 s of the train cell's
# set-up), and the forward the tape's vjp traces again is the same call
# as the forward pass's, which XLA merges.
_PLAN_STATICS = ("causal", "sm_scale", "block_q", "block_k", "kv_span",
                 "q_len", "kv_len", "p_drop", "interpret")


@kernel_jit("flash_fwd", _PLAN_STATICS)
def _fwd(q, k, v, seed, lens, shift, *, causal, sm_scale, block_q,
         block_k, kv_span, q_len, kv_len, p_drop, interpret):
    bh, sq, d = q.shape
    skv = k.shape[1]
    kernel = functools.partial(
        _fwd_kernel, causal=causal, sm_scale=sm_scale, block_q=block_q,
        block_k=block_k, n_k=skv // block_k, q_len=q_len, kv_len=kv_len,
        p_drop=p_drop, has_lens=lens is not None,
        has_shift=shift is not None, ends=lens is not None or kv_len != skv)
    seed_specs, seed_args = _seed_spec_args(seed, p_drop, lens, shift)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, sq // block_q, skv // kv_span),
        in_specs=seed_specs + [
            pl.BlockSpec((1, block_q, d), lambda b, i, s: (b, i, 0)),
            pl.BlockSpec((1, kv_span, d), lambda b, i, s: (b, s, 0)),
            pl.BlockSpec((1, kv_span, d), lambda b, i, s: (b, s, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, s: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, s: (b, i, 0)),
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
def _log2_stats(lse_ref, delta_ref, at, p_drop):
    """The (rows, 1) lse and delta of a block as the backward tiles take
    them: lse in log2 units less log2(1 / (1 - r)), so that exp2(s - lse)
    is p / (1 - r), and delta times (1 - r) to match:
    dS = p∘(dP∘M / (1 - r) - delta) = (p / (1 - r))∘(dP∘M - (1 - r) delta)."""
    keep = 1.0 - p_drop
    return (lse_ref[0, at, :] * _LOG2E + math.log2(keep),
            delta_ref[0, at, :] * keep)


def _bwd_dq_kernel(*refs, causal, sm_scale, block_q, block_k, n_k,
                   q_len, kv_len, p_drop, has_lens, has_shift, ends):
    seed_ref, lens_ref, shift_ref, (q_ref, k_ref, v_ref, do_ref, lse_ref,
                                    delta_ref, dq_ref,
                                    dq_scr) = _split_refs(
        refs, p_drop, has_lens, has_shift)
    b = pl.program_id(0)
    qi = pl.program_id(1)
    sb = pl.program_id(2)
    per_span = k_ref.shape[1] // block_k
    base = sb * per_span

    @pl.when(sb == 0)
    def _init():
        dq_scr[:] = jnp.zeros(dq_scr.shape, jnp.float32)

    off, limit = _offset_limit(lens_ref, shift_ref, b, q_len, kv_len)
    lse, delta = _log2_stats(lse_ref, delta_ref, slice(None), p_drop)

    def chunk(j, masked):
        at = _chunk_rows(j, block_k, per_span)
        # bf16 operands into every dot; fp32 only for accumulators and
        # the softmax math (see the fwd kernel's MXU-contract note)
        s = jax.lax.dot_general(q_ref[0], k_ref[0, at, :],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        p = jnp.exp2(s * (sm_scale * _LOG2E) - lse)
        mask = _key_mask(off, limit, qi, base + j, block_q, block_k, causal,
                         ends) if masked else None
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dp = jax.lax.dot_general(do_ref[0], v_ref[0, at, :],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if p_drop > 0.0:
            # gradient flows only through kept elements (dp ∘ M/(1-r));
            # delta = rowsum(do∘out) already reflects the dropped forward
            dp = jnp.where(_tile_keep_mask(seed_ref[0], b, qi, base + j,
                                           block_q, block_k, p_drop),
                           dp, 0.0)
        ds = p * (dp - delta)
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds.astype(k_ref.dtype), k_ref[0, at, :],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    full, stop = _in_span(*_kv_chunk_range(
        qi * block_q, block_q, off, limit, block_k, n_k, causal),
        base, per_span)
    _loop(0, full, lambda j: chunk(j, False))
    _loop(full, stop, lambda j: chunk(j, True))

    @pl.when(sb == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0] = (dq_scr[:] * sm_scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, causal, sm_scale, block_q, block_k, n_q, q_len,
                    kv_len, p_drop, has_lens, has_shift, ends):
    seed_ref, lens_ref, shift_ref, (q_ref, k_ref, v_ref, do_ref, lse_ref,
                                    delta_ref, dk_ref, dv_ref, dk_scr,
                                    dv_scr) = _split_refs(
        refs, p_drop, has_lens, has_shift)
    b = pl.program_id(0)
    ki = pl.program_id(1)
    sb = pl.program_id(2)
    per_span = q_ref.shape[1] // block_q
    base = sb * per_span

    @pl.when(sb == 0)
    def _init():
        dk_scr[:] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[:] = jnp.zeros(dv_scr.shape, jnp.float32)

    off, limit = _offset_limit(lens_ref, shift_ref, b, q_len, kv_len)

    def chunk(i, masked):
        at = _chunk_rows(i, block_q, per_span)
        q, do = q_ref[0, at, :], do_ref[0, at, :]
        lse, delta = _log2_stats(lse_ref, delta_ref, at, p_drop)
        # bf16 operands into every dot (see the fwd kernel's MXU note)
        s = jax.lax.dot_general(q, k_ref[0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        p = jnp.exp2(s * (sm_scale * _LOG2E) - lse)     # p / (1 - r)
        mask = _key_mask(off, limit, base + i, ki, block_q, block_k, causal,
                         ends) if masked else None
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dp = jax.lax.dot_general(do, v_ref[0], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        p_tilde = p
        if p_drop > 0.0:
            keep = _tile_keep_mask(seed_ref[0], b, base + i, ki, block_q,
                                   block_k, p_drop)
            p_tilde = jnp.where(keep, p, 0.0)
            dp = jnp.where(keep, dp, 0.0)
        # dv += p̃^T @ do (dropped probabilities fed the forward output)
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p_tilde.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        # dk += ds^T @ q
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    # Query chunks before the diagonal never attend this block of keys,
    # chunks wholly past it attend all of it.
    first, full = _in_span(*_q_chunk_range(
        ki * block_k, block_k, off, limit, block_q, n_q, causal),
        base, per_span)
    _loop(first, full, lambda i: chunk(i, True))
    _loop(full, per_span, lambda i: chunk(i, False))

    @pl.when(sb == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = (dk_scr[:] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


@kernel_jit("flash_bwd", _PLAN_STATICS + ("q_span",))
def _bwd(q, k, v, out, lse, do, seed, lens, shift, *, causal, sm_scale,
         block_q, block_k, kv_span, q_span, q_len, kv_len, p_drop,
         interpret, dlse=None):
    bh, sq, d = q.shape
    skv = k.shape[1]
    delta = jnp.sum(out.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1, keepdims=True)  # (bh, sq, 1)
    if dlse is not None:
        # d/ds of lse is p, so an lse cotangent folds into the delta
        # vector: ds = p∘(dp - (delta - dlse))
        delta = delta - dlse.astype(jnp.float32)
    seed_specs, seed_args = _seed_spec_args(seed, p_drop, lens, shift)
    common = dict(causal=causal, sm_scale=sm_scale, block_q=block_q,
                  block_k=block_k, q_len=q_len, kv_len=kv_len,
                  p_drop=p_drop, has_lens=lens is not None,
                  has_shift=shift is not None,
                  ends=lens is not None or kv_len != skv)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, n_k=skv // block_k, **common),
        grid=(bh, sq // block_q, skv // kv_span),
        in_specs=seed_specs + [
            pl.BlockSpec((1, block_q, d), lambda b, i, s: (b, i, 0)),
            pl.BlockSpec((1, kv_span, d), lambda b, i, s: (b, s, 0)),
            pl.BlockSpec((1, kv_span, d), lambda b, i, s: (b, s, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, s: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, s: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, s: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, s: (b, i, 0)),
        out_shape=_sds((bh, sq, d), q.dtype, q),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dq",
    )(*seed_args, q, k, v, do, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, n_q=sq // block_q, **common),
        grid=(bh, skv // block_k, sq // q_span),
        in_specs=seed_specs + [
            pl.BlockSpec((1, q_span, d), lambda b, j, s: (b, s, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, s: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, s: (b, j, 0)),
            pl.BlockSpec((1, q_span, d), lambda b, j, s: (b, s, 0)),
            pl.BlockSpec((1, q_span, 1), lambda b, j, s: (b, s, 0)),
            pl.BlockSpec((1, q_span, 1), lambda b, j, s: (b, s, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, s: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, s: (b, j, 0)),
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
class _Plan(NamedTuple):
    """What a call's three kernels are built from, all static: the tile
    (block_q, block_k), the rows of K and V (of Q and dO in dK/dV) a grid
    step holds (`_resident_span`), the unpadded lengths."""
    causal: bool
    sm_scale: float
    block_q: int
    block_k: int
    kv_span: int
    q_span: int
    q_len: int
    kv_len: int
    p_drop: float
    interpret: bool

    def fwd(self):
        kw = self._asdict()
        del kw["q_span"]
        return kw


# seed / lens / shift are float32 (bitcast to int32 inside): custom_vjp
# needs a float cotangent slot for every traced arg, and the per-step
# dropout seed must be traced (a python int would retrace the train step
# every step). lens/shift=None are allowed: None is a static pytree.
@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _flash(q, k, v, seed, lens, shift, plan):
    return _fwd(q, k, v, seed, lens, shift, **plan.fwd())[0]


def _flash_fwd(q, k, v, seed, lens, shift, plan):
    out, lse = _fwd(q, k, v, seed, lens, shift, **plan.fwd())
    return out, (q, k, v, seed, lens, shift, out, lse)


def _flash_bwd(plan, res, do, dlse=None):
    q, k, v, seed, lens, shift, out, lse = res
    dq, dk, dv = _bwd(q, k, v, out, lse, do, seed, lens, shift, dlse=dlse,
                      **plan._asdict())
    return (dq, dk, dv, jnp.zeros((), jnp.float32),
            None if lens is None else jnp.zeros_like(lens),
            None if shift is None else jnp.zeros_like(shift))


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _flash_lse(q, k, v, seed, lens, shift, plan):
    """(out, lse) variant for online-merge consumers (ring attention):
    the lse output is itself differentiable (d lse/d s = p folds into the
    backward delta vector)."""
    return _fwd(q, k, v, seed, lens, shift, **plan.fwd())


def _flash_lse_fwd(q, k, v, seed, lens, shift, plan):
    out, lse = _fwd(q, k, v, seed, lens, shift, **plan.fwd())
    return (out, lse), (q, k, v, seed, lens, shift, out, lse)


def _flash_lse_bwd(plan, res, cots):
    do, dlse = cots
    return _flash_bwd(plan, res, do, dlse=dlse)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------
def _mha_tune_key(sq, skv, d, dtype, causal, interpret):
    return (sq, skv, d, str(jnp.dtype(dtype)), bool(causal), bool(interpret))


def _fit_blocks(block_q, block_k, sq, skv):
    """A tile no larger than the call: whole sublanes of queries, whole
    lane tiles of keys (a row's running statistics are replicated over
    the 128 lanes; keys past ``skv`` are padding, and masked)."""
    return (min(int(block_q), _ceil_to(sq, 8)),
            _ceil_to(min(int(block_k), skv), _LANES))


def _mha_plan(sq, skv, d, dtype, *, causal, sm_scale=None, block_q=None,
              block_k=None, p_drop=0.0, interpret=None):
    """The `_Plan` of a call on (.., sq, d) queries and (.., skv, d) keys,
    and the lengths its operands are padded to."""
    if interpret is None:
        interpret = _interpret_default()
    if block_q is None and block_k is None:
        from . import autotune as _at
        hit = _at.cache_get("flash_mha", _mha_tune_key(
            sq, skv, d, dtype, causal, interpret)) if _at.enabled() else None
        if hit is not None:
            block_q, block_k = hit
    # explicitly passed blocks always win
    block_q, block_k = _fit_blocks(
        _BLOCK_Q if block_q is None else block_q,
        _BLOCK_K if block_k is None else block_k, sq, skv)
    row = 2 * _ceil_to(d, _LANES) * jnp.dtype(dtype).itemsize
    # forward and dQ keep K and V in VMEM, dK/dV keeps Q, dO and the two
    # (rows, 1) float32 statistics, which fill a lane tile a row
    kv_span = _resident_span(_ceil_to(skv, block_k), block_k, row)
    q_span = _resident_span(_ceil_to(sq, block_q), block_q,
                            row + 2 * _LANES * 4)
    plan = _Plan(bool(causal),
                 1.0 / math.sqrt(d) if sm_scale is None else sm_scale,
                 block_q, block_k, kv_span, q_span, sq, skv, float(p_drop),
                 bool(interpret))
    return plan, _ceil_to(sq, q_span), _ceil_to(skv, kv_span)


def mha_chunks(sq, skv, d, dtype, *, causal):
    """(visited, total): the (block_q, block_k) tiles of the padded
    sq x skv square that `mha` computes for this shape with the blocks it
    would choose, and all of them. The forward and dQ kernels walk them a
    block of queries at a time, dK/dV a block of keys at a time."""
    plan, sq_p, skv_p = _mha_plan(sq, skv, d, dtype, causal=causal)
    n_q, n_k = sq_p // plan.block_q, skv_p // plan.block_k
    visited = sum(
        _kv_chunk_range(i * plan.block_q, plan.block_q, skv - sq, skv,
                        plan.block_k, n_k, plan.causal)[1]
        for i in range(n_q))
    return visited, n_q * n_k


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

    ``block_q``/``block_k`` are the tile one pass of the softmax works
    on: a grid step holds ``block_q`` queries and walks the keys in
    chunks of ``block_k`` (dK/dV: ``block_k`` keys, the queries in chunks
    of ``block_q``), under ``causal`` only the chunks at or below the
    diagonal. They default to an autotuned choice when :func:`tune_mha`
    has cached one for this (seq, d, dtype, causal) key (ref
    ``paddle/phi/kernels/autotune/``), else to `_BLOCK_Q`/`_BLOCK_K`.
    """
    b, h, sq, d = q.shape
    skv = k.shape[2]
    plan, sq_p, skv_p = _mha_plan(
        sq, skv, d, q.dtype, causal=causal, sm_scale=sm_scale,
        block_q=block_q, block_k=block_k, p_drop=dropout_p,
        interpret=interpret)
    d_p = _ceil_to(d, _LANES)
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
        out, lse = _flash_lse(qp, kp, vp, seed, lens, shift, plan)
        return (out[:, :sq, :d].reshape(b, h, sq, d),
                lse[:, :sq, 0].reshape(b, h, sq))
    out = _flash(qp, kp, vp, seed, lens, shift, plan)
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
        bq, bk = _fit_blocks(cfg[0], cfg[1], sq, skv)
        # what the largest of the three kernels (dK/dV) holds a grid
        # step: the resident span of Q, dO and their statistics in both
        # pipeline buffers, its own K and V blocks in and dK and dV out
        # likewise, two float32 accumulators, and the inner tile's
        # float32 temporaries (scores, probabilities, dP, dS and the
        # dropout hash: some six tiles live at once)
        q_span = _resident_span(_ceil_to(sq, bq), bq,
                                2 * d_p * itemsize + 2 * _LANES * 4)
        vmem = (2 * q_span * (2 * d_p * itemsize + 2 * _LANES * 4)
                + 2 * 4 * bk * d_p * itemsize + 2 * bk * d_p * 4
                + 6 * bq * bk * 4)
        return {"flops": flops, "bytes": bytes_, "vmem_bytes": vmem,
                "mxu_underfill": min(bq, bk) < 8}
    return cost


def tune_mha(q, k, v, *, causal=False, interpret=None,
             candidates=((128, 128), (256, 128), (128, 256), (256, 256),
                         (256, 512), (512, 256), (512, 512), (1024, 512))):
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
        clamped = _fit_blocks(bq, bk, sq, skv)
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
        "flash_mha", _mha_tune_key(sq, skv, d, q.dtype, causal, interpret),
        run, todo, cost=_mha_cost_fn(b, h, sq, skv, d, q.dtype.itemsize))
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


_PK_PLAN = ("causal", "sm_scale", "block_q", "block_k", "p_drop",
            "interpret")


@kernel_jit("flash_packed_fwd", _PK_PLAN)
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


@kernel_jit("flash_packed_bwd", _PK_PLAN)
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
