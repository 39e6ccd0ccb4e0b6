"""Decode-shaped fused attention over a paged KV-cache.

The serving engine (:mod:`paddle_tpu.serving`) keeps each sequence's KV
history in fixed-size *pages* owned by a block pool
(:class:`paddle_tpu.serving.kv_cache.PagePool`); a decode step attends
one new query token per sequence against that sequence's page list.
The reference stack reaches the same shape through
``paddle/fluid/inference`` + external serving engines; here the op is
first-class:

 - :func:`paged_attention_reference` — the XLA path: gather the page
   window ``k_pool[layer, page_tables]`` → masked softmax attention.  Row
   independent by construction, which is what makes continuous
   batching bit-stable (a sequence's logits do not depend on its batch
   neighbours or on which physical pages it landed in).
 - :func:`paged_attention` — dispatcher: Pallas kernel on TPU,
   reference elsewhere; selection is by platform only.
 - ``_paged_attention_pallas`` — the kernel: grid ``(batch, pages)``
   with the per-sequence page table scalar-prefetched so each grid
   step's ``BlockSpec`` index map *is* the page-table lookup (the page
   gather never materialises in HBM), online-softmax accumulators in
   VMEM scratch.  Interpret-runnable off-TPU.

Shapes (the model loops layers and passes the pools whole each time):
  q            (B, H, D)        one query token per sequence
  k/v_pool     (L, P, ps, H*D)  every layer's pages, P pages of ps
                                tokens, a token's heads side by side on
                                the lanes; the kernel's block is one
                                ``(ps, H*D)`` page of layer ``layer``,
                                fetched where it lies: no slice, reshape
                                or copy stands between pool and kernel
  k/v_scale    (L, P, ps, H)    int8 pools only: f32 scale per (token,
                                head), addressed like the values
  layer        static int       which layer's pages to read
  page_tables  (B, max_pages)   int32 page ids, position t lives in
                                page ``pt[b, t // ps]`` slot ``t % ps``
  lengths      (B,) int32       valid context per row (pos of the new
                                token + 1; masks padding AND the
                                reserved null page 0 that pads short
                                page tables)

Grouped heads and windows (``_paged_attention_gqa_pallas``): a pool row
may hold fewer heads than q has (``KVH * D`` lanes, ``H = KVH * G``):
query head ``j`` reads KV head ``j // G``, and the ``G`` query heads of
a KV head share one fetch of its page.  ``window=W`` makes a row see
only its last ``W`` positions.  That kernel's grid is not ``(batch,
pages)`` but one axis over the pages the batch really walks: row ``b``
contributes pages ``max(0, len - W) // ps .. (len - 1) // ps`` (all of
``0 .. (len - 1) // ps`` without a window), the rows' walks laid end to
end in a scalar-prefetched work list (:func:`_walk`), so a slot outside
a row's walk costs neither a grid step nor a fetch.  Equal heads without
a window keep the ``(batch, pages)`` kernel above them.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..framework import device as _device
from .pallas_ops import _LANES, _NEG_INF, _interpret_default

__all__ = ["paged_attention", "paged_attention_reference", "walk_pages",
           "paged_attention_int8", "paged_attention_int8_reference",
           "tune_paged_attention_int8"]


def paged_attention_reference(q, k_pool, v_pool, page_tables, lengths,
                              *, layer, sm_scale=None, window=None):
    """XLA reference: gather the page window, masked softmax attention.

    f32 scores/accumulation regardless of operand dtype (the MXU
    contract from :mod:`.pallas_ops`); output in ``q.dtype``.  A pool
    row of fewer heads than q's is grouped (query head ``j`` reads KV
    head ``j // G``); ``window`` keeps a row's last ``window`` positions.
    """
    b, h, d = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    kvh = k_pool.shape[3] // d
    if kvh != h or window:
        g = h // kvh
        k_ctx = k_pool[layer, page_tables].reshape(b, -1, kvh, d)
        v_ctx = v_pool[layer, page_tables].reshape(b, -1, kvh, d)
        s = jnp.einsum("bkgd,bckd->bkgc", q.reshape(b, kvh, g, d), k_ctx,
                       preferred_element_type=jnp.float32) * sm_scale
        pos = jnp.arange(k_ctx.shape[1], dtype=jnp.int32)[None, :]
        mask = pos < lengths[:, None]
        if window:
            mask &= pos >= lengths[:, None] - window
        s = jnp.where(mask[:, None, None, :], s, _NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bkgc,bckd->bkgd", w.astype(v_ctx.dtype), v_ctx,
                       preferred_element_type=jnp.float32)
        return o.reshape(b, h, d).astype(q.dtype)
    # (B, max_pages, ps, H*D) -> (B, C, H, D); position t sits at
    # context index t because pages fill in order
    k_ctx = k_pool[layer, page_tables].reshape(b, -1, h, d)
    v_ctx = v_pool[layer, page_tables].reshape(b, -1, h, d)
    s = jnp.einsum("bhd,bchd->bhc", q, k_ctx,
                   preferred_element_type=jnp.float32) * sm_scale
    c = k_ctx.shape[1]
    mask = jnp.arange(c, dtype=jnp.int32)[None, :] < lengths[:, None]
    s = jnp.where(mask[:, None, :], s, _NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhc,bchd->bhd", w.astype(v_ctx.dtype), v_ctx,
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype)


def _head_selectors(h, d):
    """0/1 matrices that move between the flattened ``(.., H*D)`` lane
    layout and one column per head: ``seg`` (H*D, LANES) sums each
    head's D lanes into column h, ``seg.T`` spreads column h back over
    them.  LANES pads H to the 128-lane tile so every matmul in the
    kernel is tile-aligned; the padded columns meet only zero rows."""
    lanes = -(-h // _LANES) * _LANES
    seg = (jnp.arange(h * d, dtype=jnp.int32)[:, None] // d
           == jnp.arange(lanes, dtype=jnp.int32)[None, :])
    seg = seg.astype(jnp.float32)
    return seg, seg.T


def _dot(a, b):
    # selector matmuls carry f32 scores/weights: keep the MXU at full
    # f32 contract precision (the default would round them to bf16)
    return jnp.dot(a, b, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)


def _online_softmax_page(i, length, s, v, segt, m_scr, l_scr, acc_scr,
                         *, ps, v_weight=None):
    """One page of the online softmax, shared by both kernels. ``s`` is
    (ps, LANES) scores with head h in column h, ``v`` (ps, H*D) values;
    ``v_weight`` (ps, LANES) scales each (token, head) before the value
    sum (the int8 pool's per-(token, head) v scale). The running max /
    sum / accumulator live as 8 identical sublane rows so every operand
    is a whole f32 tile."""
    pos = i * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    valid = pos < length
    s = jnp.where(valid, s, _NEG_INF)
    m_prev, l_prev = m_scr[...], l_scr[...]              # (8, LANES)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
    # re-mask after the exp: on a fully-dead page m_new stays at
    # _NEG_INF and exp(s - m_new) would be exp(0) = 1 mass
    p = jnp.where(valid, jnp.exp(s - m_new[:1]), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[:] = alpha * l_prev + jnp.sum(p, axis=0, keepdims=True)
    m_scr[:] = m_new
    if v_weight is not None:
        p = p * v_weight
    pv = jnp.sum(_dot(p, segt) * v, axis=0, keepdims=True)   # (1, H*D)
    acc_scr[:] = acc_scr[...] * _dot(alpha, segt) + pv


def _finalize(o_ref, segt, l_scr, acc_scr):
    l = l_scr[...]
    l = jnp.where(l == 0.0, 1.0, l)
    o_ref[...] = (acc_scr[...] / _dot(l, segt))[:1].astype(o_ref.dtype)


def _init_scratch(m_scr, l_scr, acc_scr):
    m_scr[:] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
    l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)


# Mosaic has no matmul whose batch (head) dim sits in the middle of a
# 3-D operand ("hd,phd->hp"), so the kernels work on 2-D tiles: a page is
# (ps, H*D), the one query row per sequence is (1, H*D), the per-head
# contraction is a VPU multiply followed by a 0/1 selector matmul that
# sums each head's D lanes (see _head_selectors).
def _paged_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, seg_ref, segt_ref,
                  o_ref, m_scr, l_scr, acc_scr, *, ps, max_pages, sm_scale):
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        _init_scratch(m_scr, l_scr, acc_scr)

    length = len_ref[b]

    @pl.when(i * ps < length)
    def _page():
        q = q_ref[...].astype(jnp.float32)          # (1, H*D)
        k = k_ref[...].astype(jnp.float32)          # (ps, H*D)
        v = v_ref[...].astype(jnp.float32)
        s = _dot(q * k, seg_ref[...]) * sm_scale     # (ps, LANES)
        _online_softmax_page(i, length, s, v, segt_ref[...], m_scr, l_scr,
                             acc_scr, ps=ps)

    @pl.when(i == max_pages - 1)
    def _fin():
        _finalize(o_ref, segt_ref[...], l_scr, acc_scr)


def _paged_call(kernel, q, pools, layer, extra, extra_specs, page_tables,
                lengths, *, name, batch_semantics, interpret):
    """Shared pallas_call plumbing: q (B, H, D) enters flattened to H*D
    lanes, the (L, P, ps, H*D) pools enter whole and the page block's
    index map picks ``(layer, pt[b, i])``; ``extra``/``extra_specs`` are
    the int8 kernel's scale operands."""
    b, h, d = q.shape
    hd = h * d
    ps = pools[0].shape[2]
    if pools[0].shape[3] != hd:
        raise ValueError(
            f"pool rows hold {pools[0].shape[3]} lanes, q has {h} heads "
            f"of {d}")
    seg, segt = _head_selectors(h, d)
    lanes = seg.shape[1]
    page_spec = pl.BlockSpec(
        (None, None, ps, hd),
        lambda bi, i, pt, ln: (layer, pt[bi, i], 0, 0))
    row_spec = pl.BlockSpec((None, 1, hd), lambda bi, i, pt, ln: (bi, 0, 0))

    def resident(a):  # one block spanning the operand, fetched once
        return pl.BlockSpec(a.shape, lambda bi, i, pt, ln: (0,) * a.ndim)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, page_tables.shape[1]),
        in_specs=[row_spec] + [page_spec] * len(pools) + list(extra_specs)
        + [resident(seg), resident(segt)],
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((8, lanes), jnp.float32),
            pltpu.VMEM((8, lanes), jnp.float32),
            pltpu.VMEM((8, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(batch_semantics, "arbitrary")),
        name=name,
        interpret=interpret,
    )(page_tables, lengths, q.reshape(b, 1, hd), *pools, *extra, seg, segt)
    return out.reshape(b, h, d)


def _paged_attention_pallas(q, k_pool, v_pool, page_tables, lengths,
                            *, layer, sm_scale, interpret):
    kernel = functools.partial(_paged_kernel, ps=k_pool.shape[2],
                               max_pages=page_tables.shape[1],
                               sm_scale=sm_scale)
    return _paged_call(kernel, q, (k_pool, v_pool), layer, (), (),
                       page_tables, lengths, name="paged_attention",
                       batch_semantics="parallel", interpret=interpret)



# ---------------------------------------------------------------------------
# grouped heads and windows: one grid axis over the pages really walked
# ---------------------------------------------------------------------------

def walk_pages(max_pages, page_size, window):
    """Most pages one row's walk covers: all ``max_pages`` slots without
    a window, the window's span plus the page it starts inside with one."""
    if not window:
        return max_pages
    return min(max_pages, -(-window // page_size) + 1)


def _walk(page_tables, lengths, *, ps, window, steps):
    """The batch's walk as a work list of ``steps`` grid steps: the rows'
    page runs laid end to end.  Returns ``(rows, pages, slots, first,
    last)``: per step the row it belongs to, the physical page to fetch
    and the logical page index (``-1`` past the end of the list, where
    row and page repeat the last live step's so that nothing is
    fetched); per row its first and last logical page."""
    lengths = jnp.maximum(lengths, 1)
    last = (lengths - 1) // ps
    first = (jnp.maximum(lengths - window, 0) // ps if window
             else jnp.zeros_like(last))
    n = last - first + 1
    ends = jnp.cumsum(n)
    g = jnp.arange(steps, dtype=jnp.int32)
    live = g < ends[-1]
    gc = jnp.minimum(g, ends[-1] - 1)
    rows = jnp.sum(gc[:, None] >= ends[None, :], axis=1).astype(jnp.int32)
    slots = first[rows] + gc - (ends[rows] - n[rows])
    pages = page_tables[rows, slots]
    return (rows, pages.astype(jnp.int32),
            jnp.where(live, slots, -1).astype(jnp.int32),
            first.astype(jnp.int32), last.astype(jnp.int32))


def _gqa_kernel(rows_ref, pages_ref, slots_ref, len_ref, first_ref,
                last_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                acc_scr, *, ps, kvh, d, sm_scale, window):
    """One page of one row: for each KV head, its G query heads (padded
    to a bf16 tile of rows) against the page's ``(ps, D)`` lanes on the
    MXU, online softmax in f32."""
    g = pl.program_id(0)
    row = rows_ref[g]
    slot = slots_ref[g]
    length = len_ref[row]
    live = slot >= 0

    @pl.when(live & (slot == first_ref[row]))
    def _init():
        _init_scratch(m_scr, l_scr, acc_scr)

    @pl.when(live)
    def _page():
        gp = q_ref.shape[1]
        pos = slot * ps + jax.lax.broadcasted_iota(jnp.int32, (gp, ps), 1)
        valid = pos < length
        if window:
            valid &= pos >= length - window
        # said outright, so that a process-wide default precision cannot
        # ask the MXU for float32 passes over bfloat16 operands
        prec = (jax.lax.Precision.HIGHEST if k_ref.dtype == jnp.float32
                else jax.lax.Precision.DEFAULT)
        for j in range(kvh):
            q = q_ref[j]                                  # (GP, D)
            k = k_ref[:, j * d:(j + 1) * d]               # (ps, D)
            v = v_ref[:, j * d:(j + 1) * d]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), precision=prec,
                preferred_element_type=jnp.float32) * sm_scale
            s = jnp.where(valid, s, _NEG_INF)              # (GP, ps)
            m_prev, l_prev = m_scr[j], l_scr[j]           # (GP, LANES)
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(valid, jnp.exp(s - m_new[:, :1]), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[j] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            m_scr[j] = m_new
            pv = jnp.dot(p.astype(v.dtype), v, precision=prec,
                         preferred_element_type=jnp.float32)   # (GP, D)
            acc_scr[j] = acc_scr[j] * alpha[:, :1] + pv

    @pl.when(live & (slot == last_ref[row]))
    def _fin():
        for j in range(kvh):
            l = l_scr[j][:, :1]
            l = jnp.where(l == 0.0, 1.0, l)
            o_ref[j] = (acc_scr[j] / l).astype(o_ref.dtype)


def _paged_attention_gqa_pallas(q, k_pool, v_pool, page_tables, lengths,
                                *, layer, sm_scale, window, steps,
                                interpret):
    b, h, d = q.shape
    ps = k_pool.shape[2]
    kvh = k_pool.shape[3] // d
    if kvh < 1 or kvh * d != k_pool.shape[3] or h % kvh:
        raise ValueError(
            f"pool rows hold {k_pool.shape[3]} lanes, q has {h} heads "
            f"of {d}")
    grp = h // kvh
    gp = -(-grp // 16) * 16         # a bf16 tile of rows a KV head
    walk = walk_pages(page_tables.shape[1], ps, window)
    if steps is None:
        steps = b * walk
    steps = min(int(steps), b * walk)
    rows, pages, slots, first, last = _walk(
        page_tables, lengths, ps=ps, window=window, steps=steps)
    qg = jnp.pad(q.reshape(b, kvh, grp, d),
                 ((0, 0), (0, 0), (0, gp - grp), (0, 0)))
    row_spec = pl.BlockSpec((None, kvh, gp, d),
                            lambda g, rows, *_: (rows[g], 0, 0, 0))
    page_spec = pl.BlockSpec(
        (None, None, ps, kvh * d),
        lambda g, rows, pages, *_: (layer, pages[g], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(steps,),
        in_specs=[row_spec, page_spec, page_spec],
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((kvh, gp, _LANES), jnp.float32),
            pltpu.VMEM((kvh, gp, _LANES), jnp.float32),
            pltpu.VMEM((kvh, gp, d), jnp.float32),
        ],
    )
    kernel = functools.partial(_gqa_kernel, ps=ps, kvh=kvh, d=d,
                               sm_scale=sm_scale, window=window)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, gp, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="paged_attention_window" if window else "paged_attention_gqa",
        interpret=interpret,
    )(rows, pages, slots, jnp.maximum(lengths, 1).astype(jnp.int32),
      first, last, qg, k_pool, v_pool)
    return out[:, :, :grp].reshape(b, h, d)


def paged_attention(q, k_pool, v_pool, page_tables, lengths, *, layer,
                    sm_scale=None, window=None, steps=None,
                    use_pallas=None, interpret=None):
    """Dispatching entry: the Pallas paged-attention kernel on TPU, the
    XLA gather+softmax reference elsewhere.  Both read layer ``layer``
    of the whole ``(L, P, ps, H*D)`` pools.

    A pool row of fewer heads than q's, or ``window``, selects the
    grouped kernel (module docstring); ``steps`` is then an upper bound
    the caller knows on the pages the batch walks (the allocator's: no
    two rows share a page, so at most the pool's usable pages plus one a
    row), ``batch * walk_pages`` when not given.

    Off-TPU the default is the reference (interpret-mode Pallas is a
    correctness vehicle, not a fast path); pass ``use_pallas=True`` to
    force the kernel (tests).  Selection is by platform only: a kernel
    the compiler refuses fails the program with the compiler's message.
    Dispatch decisions are trace-time events booked on
    ``pt_pallas_calls_total{kernel="paged_attention"}``.
    """
    from .fused_kernels import record_dispatch
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = _interpret_default()
    if use_pallas is None:
        use_pallas = _device.pallas_dispatch()  # reference off the TPU
    grouped = bool(window) or k_pool.shape[3] != q.shape[1] * q.shape[2]
    if use_pallas:
        record_dispatch("paged_attention", "pallas")
        if grouped:
            return _paged_attention_gqa_pallas(
                q, k_pool, v_pool, page_tables, lengths, layer=layer,
                sm_scale=sm_scale, window=window or 0, steps=steps,
                interpret=interpret)
        return _paged_attention_pallas(q, k_pool, v_pool, page_tables,
                                       lengths, layer=layer,
                                       sm_scale=sm_scale,
                                       interpret=interpret)
    record_dispatch("paged_attention", "fallback")
    return paged_attention_reference(q, k_pool, v_pool, page_tables,
                                     lengths, layer=layer,
                                     sm_scale=sm_scale, window=window)


# ---------------------------------------------------------------------------
# int8-KV variant (the low-precision serving subsystem)
# ---------------------------------------------------------------------------
# Same attention, but the pool stores int8 values with per-(token, head)
# f32 scales riding beside them (``PagePool(dtype=int8, scale_pages=
# True)``): k/v_pool are (L, P, ps, H*D) int8 and k/v_scale are
# (L, P, ps, H) f32.  Dequantization happens at the attention's edge —
# scores and accumulation stay f32, so the math after the unpack is the
# exact fp32 kernel above and the row-independence (bit-identity)
# argument carries over unchanged.

def paged_attention_int8_reference(q, k_pool, v_pool, k_scale, v_scale,
                                   page_tables, lengths, *, layer,
                                   sm_scale=None):
    """XLA reference for int8 pages: gather values AND scales through
    the page table, dequantize, masked softmax attention (f32)."""
    b, h, d = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)

    def ctx(pool, scale):
        vals = pool[layer, page_tables].astype(jnp.float32)
        vals = vals.reshape(*vals.shape[:-1], h, d)
        return (vals * scale[layer, page_tables][..., None]
                ).reshape(b, -1, h, d)

    k_ctx, v_ctx = ctx(k_pool, k_scale), ctx(v_pool, v_scale)
    s = jnp.einsum("bhd,bchd->bhc", q.astype(jnp.float32), k_ctx) * sm_scale
    c = k_ctx.shape[1]
    mask = jnp.arange(c, dtype=jnp.int32)[None, :] < lengths[:, None]
    s = jnp.where(mask[:, None, :], s, _NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhc,bchd->bhd", w, v_ctx)
    return o.astype(q.dtype)


def _paged_kernel_int8(pt_ref, len_ref, q_ref, k_ref, v_ref, ks_ref,
                       vs_ref, seg_ref, segt_ref, o_ref, m_scr, l_scr,
                       acc_scr, *, ps, max_pages, sm_scale):
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        _init_scratch(m_scr, l_scr, acc_scr)

    length = len_ref[b]

    @pl.when(i * ps < length)
    def _page():
        q = q_ref[...].astype(jnp.float32)          # (1, H*D)
        k = k_ref[...].astype(jnp.float32)          # (ps, H*D) int8 -> f32
        v = v_ref[...].astype(jnp.float32)
        # unpack at the edge: the per-(token, head) scale is constant
        # over a head's D lanes, so it factors out of the contraction
        # and multiplies the per-head score / weight column instead
        s = _dot(q * k, seg_ref[...]) * ks_ref[...] * sm_scale
        _online_softmax_page(i, length, s, v, segt_ref[...], m_scr, l_scr,
                             acc_scr, ps=ps, v_weight=vs_ref[...])

    @pl.when(i == max_pages - 1)
    def _fin():
        _finalize(o_ref, segt_ref[...], l_scr, acc_scr)


def _paged_attention_int8_pallas(q, k_pool, v_pool, k_scale, v_scale,
                                 page_tables, lengths, *, layer, sm_scale,
                                 interpret, batch_semantics="parallel"):
    h = q.shape[1]
    ps = k_pool.shape[2]
    lanes = -(-h // _LANES) * _LANES
    # the layer's scales ride as (P, ps, LANES) rows aligned with the
    # score columns: H lanes padded to a tile, a copy of one layer's
    # scales each call (the value pools enter whole)
    pad = ((0, 0), (0, 0), (0, lanes - h))
    scale_spec = pl.BlockSpec((None, ps, lanes),
                              lambda bi, i, pt, ln: (pt[bi, i], 0, 0))
    kernel = functools.partial(_paged_kernel_int8, ps=ps,
                               max_pages=page_tables.shape[1],
                               sm_scale=sm_scale)
    return _paged_call(kernel, q, (k_pool, v_pool), layer,
                       (jnp.pad(k_scale[layer], pad),
                        jnp.pad(v_scale[layer], pad)),
                       (scale_spec, scale_spec), page_tables, lengths,
                       name="paged_attention_int8",
                       batch_semantics=batch_semantics, interpret=interpret)


def paged_attention_int8(q, k_pool, v_pool, k_scale, v_scale,
                         page_tables, lengths, *, layer, sm_scale=None,
                         use_pallas=None, interpret=None):
    """Dispatching entry for the int8-KV pool: Pallas kernel on TPU, XLA
    gather+dequant+softmax reference elsewhere (same rule as
    :func:`paged_attention`) — booked on
    ``pt_pallas_calls_total{kernel="paged_attention_int8"}``."""
    from .fused_kernels import record_dispatch
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = _interpret_default()
    if use_pallas is None:
        use_pallas = _device.pallas_dispatch()  # reference off the TPU
    if use_pallas:
        from . import autotune as _at
        sem = "parallel"
        if _at.enabled():
            cached = _at.cache_get("paged_attention_int8",
                                   _int8_tune_key(q, k_pool, interpret))
            if cached:
                sem = str(cached[0])
        record_dispatch("paged_attention_int8", "pallas")
        return _paged_attention_int8_pallas(
            q, k_pool, v_pool, k_scale, v_scale, page_tables, lengths,
            layer=layer, sm_scale=sm_scale, interpret=interpret,
            batch_semantics=sem)
    record_dispatch("paged_attention_int8", "fallback")
    return paged_attention_int8_reference(
        q, k_pool, v_pool, k_scale, v_scale, page_tables, lengths,
        layer=layer, sm_scale=sm_scale)


def _int8_tune_key(q, k_pool, interpret):
    b, h, d = q.shape
    return (b, h, d, int(k_pool.shape[1]), int(k_pool.shape[2]),
            int(interpret))


def tune_paged_attention_int8(q, k_pool, v_pool, k_scale, v_scale,
                              page_tables, lengths, *, layer,
                              interpret=None):
    """Warmup autotune over the kernel's grid-semantics choice (the
    batch axis can run parallel or arbitrary; which wins depends on the
    page count per core) via :func:`autotune.search` under the
    ``paged_attention_int8`` schema."""
    from . import autotune as _at
    if interpret is None:
        interpret = _interpret_default()
    b, h, d = q.shape
    layers, ps = k_pool.shape[0], k_pool.shape[2]
    # int8 k/v page tiles + f32 scales + online-softmax scratch per step
    vmem = 2 * ps * h * d + 2 * ps * h * 4 + h * d * 4 + 2 * h * 4

    def cost(cfg):
        return {"flops": 4.0 * b * h * ps * d * page_tables.shape[1],
                "bytes": float(q.size * 4 + (2 * k_pool.size
                               + 2 * k_scale.size * 4) / layers),
                "vmem_bytes": vmem, "mxu_underfill": False}

    cands = _at.generate_candidates(
        [("choice", ("parallel", "arbitrary"))], cost)

    def run(cfg):
        out = _paged_attention_int8_pallas(
            q, k_pool, v_pool, k_scale, v_scale, page_tables, lengths,
            layer=layer, sm_scale=1.0 / math.sqrt(d), interpret=interpret,
            batch_semantics=str(cfg[0]))
        float(jnp.sum(out.astype(jnp.float32)))

    best, timings = _at.search(
        "paged_attention_int8", _int8_tune_key(q, k_pool, interpret),
        run, cands, cost=cost)
    _at.set_enabled(True)
    return best, timings
