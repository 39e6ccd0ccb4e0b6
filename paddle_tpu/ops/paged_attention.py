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
 - ``_paged_attention_pallas`` — the equal-heads kernel (fp32 / bf16
   pools): one grid axis over the *chunks* the batch really holds, a
   chunk being ``C`` consecutive pages of one row (``chunk_pages``: 128
   tokens' worth).  The rows' runs of chunks lie end to end in a
   scalar-prefetched work list (:func:`_walk`): the kernel copies a
   chunk's pages from the pools where they lie into one ``(C * ps,
   H*D)`` tile, a list entry ahead of the arithmetic (the page gather
   never materialises in HBM), and a slot a row does not hold costs
   neither a grid step nor a fetch; online-softmax accumulators in VMEM
   scratch.  A row whose table starts with the null page 0 holds
   nothing (what the engine pads a bucket with): its one list entry
   writes zeros and costs neither a fetch nor the arithmetic.
   :func:`chunk_walk` says, from the operands' shapes and dtype, what
   this kernel walks, or that another kernel runs.  Interpret-runnable
   off-TPU.

Shapes (the model loops layers and passes the pools whole each time):
  q            (B, H, D)        one query token per sequence
  k/v_pool     (L, P, ps, H*D)  every layer's pages, P pages of ps
                                tokens, a token's heads side by side on
                                the lanes; what a kernel fetches are
                                ``(ps, H*D)`` pages of layer ``layer``,
                                from where they lie: no slice, reshape
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
a KV head share one fetch of its pages.  ``window=W`` makes a row see
only its last ``W`` positions.  That kernel walks the same work list of
chunks, copied by hand two deep the same way; its chunk is counted in
bytes (``gqa_chunk_pages``), and row ``b`` contributes the chunks of its
pages ``max(0, len - W) // ps .. (len - 1) // ps`` (all of ``0 .. (len -
1) // ps`` without a window), the first of them starting at the page the
window starts in; the arithmetic is on the MXU with the ``G`` query
heads of a KV head as rows, one online-softmax update a chunk.  Which of
the two runs is read from the shapes (equal heads and no window, or
not), never from a name or an option.  The int8 pool's kernel
(``paged_attention_int8``) still has the grid ``(batch, pages)``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..framework import device as _device
from .pallas_ops import _LANES, _NEG_INF, _interpret_default

__all__ = ["paged_attention", "paged_attention_reference",
           "paged_attention_diff", "paged_attention_diff_reference",
           "walk_pages",
           "chunk_pages", "chunk_walk", "chunks_of",
           "paged_attention_int8", "paged_attention_int8_reference",
           "tune_paged_attention_int8"]


def paged_attention_reference(q, k_pool, v_pool, page_tables, lengths,
                              *, layer, sm_scale=None, window=None,
                              out_dtype=None):
    """XLA reference: gather the page window, masked softmax attention.

    f32 scores/accumulation regardless of operand dtype (the MXU
    contract from :mod:`.pallas_ops`); output in ``q.dtype``, or
    ``out_dtype``.  A pool
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
        return o.reshape(b, h, d).astype(out_dtype or q.dtype)
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


def _resident(a):
    """One block spanning the operand, fetched once."""
    return pl.BlockSpec(a.shape, lambda *_: (0,) * a.ndim)


def _dot(a, b):
    # selector matmuls carry f32 scores/weights: keep the MXU at full
    # f32 contract precision (the default would round them to bf16)
    return jnp.dot(a, b, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)


def _online_softmax_page(i, length, s, v, segt, m_scr, l_scr, acc_scr,
                         *, ps, v_weight=None):
    """One page of the int8 kernel's online softmax. ``s`` is
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
# 3-D operand ("hd,phd->hp"), so the equal-heads kernels work on 2-D
# tiles: pages are (ps, H*D), the one query row per sequence is (1, H*D),
# the per-head contraction is a VPU multiply followed by a 0/1 selector
# matmul that sums each head's D lanes (see _head_selectors).
def _paged_call(kernel, q, pools, layer, extra, extra_specs, page_tables,
                lengths, *, name, batch_semantics, interpret):
    """The int8 kernel's pallas_call, grid ``(batch, pages)``: q (B, H,
    D) enters flattened to H*D lanes, the (L, P, ps, H*D) pools enter
    whole and the page block's index map picks ``(layer, pt[b, i])``;
    ``extra``/``extra_specs`` are its scale operands."""
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

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, page_tables.shape[1]),
        in_specs=[row_spec] + [page_spec] * len(pools) + list(extra_specs)
        + [_resident(seg), _resident(segt)],
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


# ---------------------------------------------------------------------------
# grouped heads and windows: one grid axis over the chunks really walked
# ---------------------------------------------------------------------------

def walk_pages(max_pages, page_size, window):
    """Most pages one row's walk covers: all ``max_pages`` slots without
    a window, the window's span plus the page it starts inside with one."""
    if not window:
        return max_pages
    return min(max_pages, -(-window // page_size) + 1)


def _walk(page_tables, lengths, *, ps, window, steps, chunk, shift=None):
    """The batch's walk as a work list of ``steps`` grid steps: the rows'
    runs of chunks (``chunk`` consecutive pages of one row) laid end to
    end.  Returns ``(rows, pages, slots, first, last)``: per step the row
    it belongs to, the ``chunk`` physical pages to fetch (flat, ``chunk``
    a step; a slot past the row's last page is the null page 0) and the
    logical chunk index (``-1`` past the end of the list, where row and
    pages repeat the last live step's so that nothing is fetched); per
    row its first and last logical chunk.  ``shift`` (pages a row) moves
    a row's chunks along its table: chunk ``c`` of row ``b`` is its pages
    ``c * chunk + shift[b] ..``, which is how a windowed row's run starts
    at the page its window starts in (:func:`_window_shift`), wherever
    that lies.  A row's entries are a function of its own length and
    table alone.  No lookup in a per-row vector: the chip's compiler
    takes such a gather apart into an operation a row."""
    lengths = jnp.maximum(lengths, 1)
    # a row's tokens counted from where its chunks start
    reach = lengths if shift is None else lengths - shift * ps
    last = (reach - 1) // (ps * chunk)
    first = (jnp.maximum(reach - window, 0) // (ps * chunk) if window
             else jnp.zeros_like(last))
    n = last - first + 1
    ends = jnp.cumsum(n)
    g = jnp.arange(steps, dtype=jnp.int32)
    live = g < ends[-1]
    gc = jnp.minimum(g, ends[-1] - 1)
    before = gc[:, None] >= ends[None, :]          # rows wholly before g
    rows = jnp.sum(before, axis=1).astype(jnp.int32)

    def of_row(x):
        mine = rows[:, None] == jnp.arange(x.shape[0])[None, :]
        return jnp.sum(jnp.where(mine, x[None, :], 0), axis=1)

    slots = (of_row(first) + gc
             - jnp.sum(jnp.where(before, n[None, :], 0), axis=1))
    page = (slots[:, None] * chunk
            + jnp.arange(chunk, dtype=jnp.int32)[None, :])
    if shift is not None:
        page = page + of_row(shift)[:, None]
    held = page <= of_row((lengths - 1) // ps)[:, None]
    page = jnp.minimum(page, page_tables.shape[1] - 1)
    pages = jnp.where(held, page_tables[rows[:, None], page],
                      0).reshape(-1)
    return (rows, pages.astype(jnp.int32),
            jnp.where(live, slots, -1).astype(jnp.int32),
            first.astype(jnp.int32), last.astype(jnp.int32))


def _window_shift(lengths, *, ps, window, chunk):
    """Pages by which each row's chunks are moved along its table so that
    one of them starts at the page the row's window starts in: a window
    of ``w`` pages is then ``ceil(w / chunk)`` list entries and as many
    fetches as it has pages, not what a fixed grid of chunks would cut
    it into."""
    return (jnp.maximum(jnp.maximum(lengths, 1) - window, 0) // ps
            % chunk).astype(jnp.int32)


# K and V bytes a grid step of the grouped kernels brings in: a chunk is
# as many of a row's pages as fit (``gqa_chunk_pages``).  A grid step
# costs ~1.3 us beside what it holds, so a step wants to hold much, and a
# window wants to be one step; past a few pages the time a page stops
# falling, and the two-deep tiles are twice this of the 16 MiB a kernel
# may hold in VMEM: see PERF.md (PR 32) for the sweep on the chip
_GQA_CHUNK_BYTES = 7 << 19


def gqa_chunk_pages(k_pool, walk):
    """Pages of one row that a grid step of the grouped kernels brings
    in: ``_GQA_CHUNK_BYTES`` of K and V, at least one page, no more than
    a row's walk (``walk_pages``).  From the pool's shape and dtype
    alone; nothing tunes it."""
    page_bytes = 2 * k_pool.shape[2] * k_pool.shape[3] \
        * jnp.dtype(k_pool.dtype).itemsize
    return max(1, min(_GQA_CHUNK_BYTES // page_bytes, walk))


def _gqa_kernel(rows_ref, pages_ref, slots_ref, len_ref, first_ref,
                last_ref, shift_ref, layer_ref, q_ref, k_hbm, v_hbm, o_ref,
                k_buf, v_buf, sem, m_scr, l_scr, acc_scr, *, ps, chunk,
                steps, kvh, d, sm_scale, window):
    """One chunk of one row: the pages of it the row holds copied side by
    side into one ``(chunk * ps, KVH*D)`` tile of a two-deep buffer, the
    next list entry's copies started before this chunk's arithmetic;
    then for each KV head its G query heads (padded to a bf16 tile of
    rows) against the chunk's ``(chunk * ps, D)`` lanes on the MXU, one
    online-softmax update in f32 a chunk.  A step past the list's end
    starts, waits for and computes nothing; nor does the entry of a row
    that holds nothing (``len_ref`` 0), which leaves zeros."""
    g = pl.program_id(0)
    row = rows_ref[g]
    slot = slots_ref[g]
    live = slot >= 0
    layer = layer_ref[0]

    def origin(step):
        """The position list entry ``step``'s tile starts at."""
        return (slots_ref[step] * chunk + shift_ref[rows_ref[step]]) * ps

    def copies(step, do):
        """``do`` (start or wait) every copy of the pages list entry
        ``step`` holds of its row: the tile's slots past them keep what
        an earlier chunk left there, finite and masked by position."""
        held = len_ref[rows_ref[step]] - origin(step)

        def page(j, carry):
            at = pl.ds(pl.multiple_of(j * ps, ps), ps)
            for i, (pool, tile) in enumerate(((k_hbm, k_buf),
                                              (v_hbm, v_buf))):
                do(pltpu.make_async_copy(
                    pool.at[layer, pages_ref[step * chunk + j]],
                    tile.at[step % 2, at], sem.at[i, step % 2]))
            return carry

        jax.lax.fori_loop(0, jnp.minimum(pl.cdiv(held, ps), chunk), page, 0)

    @pl.when(g == 0)                # every row has a chunk: step 0 is live
    def _first():
        # a weight of 0 must meet a finite value in a slot never copied to
        v_buf[...] = jnp.zeros(v_buf.shape, v_buf.dtype)
        copies(0, lambda c: c.start())

    nxt = jnp.minimum(g + 1, steps - 1)

    @pl.when((g + 1 < steps) & (slots_ref[nxt] >= 0))
    def _next():
        copies(nxt, lambda c: c.start())

    @pl.when(live & (slot == first_ref[row]))
    def _init():
        _init_scratch(m_scr, l_scr, acc_scr)

    @pl.when(live & (len_ref[row] > 0))
    def _chunk():
        copies(g, lambda c: c.wait())
        length = len_ref[row]
        gp = q_ref.shape[1]
        pos = origin(g) + jax.lax.broadcasted_iota(
            jnp.int32, (gp, chunk * ps), 1)
        valid = pos < length
        if window:
            valid &= pos >= length - window
        # said outright, so that a process-wide default precision cannot
        # ask the MXU for float32 passes over bfloat16 operands
        prec = (jax.lax.Precision.HIGHEST if k_buf.dtype == jnp.float32
                else jax.lax.Precision.DEFAULT)
        for j in range(kvh):
            q = q_ref[j]                                  # (GP, D)
            k = k_buf[g % 2, :, j * d:(j + 1) * d]        # (chunk * ps, D)
            v = v_buf[g % 2, :, j * d:(j + 1) * d]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), precision=prec,
                preferred_element_type=jnp.float32) * sm_scale
            s = jnp.where(valid, s, _NEG_INF)         # (GP, chunk * ps)
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


@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "window", "chunk", "grid", "interpret", "out_dtype"))
def _paged_attention_gqa_pallas(q, k_pool, v_pool, page_tables, lengths,
                                layer, *, sm_scale, window, chunk, grid,
                                interpret, out_dtype=None):
    """Grouped heads and windows, fp32 or bf16 pools: ``grid`` steps of
    ``chunk`` pages (:func:`chunk_walk`).  As in the equal-heads kernel
    below, ``layer`` is an operand and the function jitted (a program
    traces and lowers the kernel once a pool, a window and a bucket, and
    XLA makes the layers' equal lists once), and the pools stay where
    they lie for the kernel's own copies."""
    b, h, d = q.shape
    ps = k_pool.shape[2]
    kvh = k_pool.shape[3] // d
    if kvh < 1 or kvh * d != k_pool.shape[3] or h % kvh:
        raise ValueError(
            f"pool rows hold {k_pool.shape[3]} lanes, q has {h} heads "
            f"of {d}")
    grp = h // kvh
    gp = -(-grp // 16) * 16         # a bf16 tile of rows a KV head
    shift = (_window_shift(lengths, ps=ps, window=window, chunk=chunk)
             if window else None)
    rows, pages, slots, first, last = _walk(
        page_tables, lengths, ps=ps, window=window, steps=grid,
        chunk=chunk, shift=shift)
    # a row that holds a token holds a page (not the table's first, once
    # its window has left that behind)
    held = jnp.where(jnp.all(page_tables == 0, axis=1), 0,
                     jnp.maximum(lengths, 1))
    qg = jnp.pad(q.reshape(b, kvh, grp, d),
                 ((0, 0), (0, 0), (0, gp - grp), (0, 0)))
    row_spec = pl.BlockSpec((None, kvh, gp, d),
                            lambda g, rows, *_: (rows[g], 0, 0, 0))
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=(grid,),
        in_specs=[row_spec, pool_spec, pool_spec],
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((2, chunk * ps, kvh * d), k_pool.dtype),
            pltpu.VMEM((2, chunk * ps, kvh * d), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((kvh, gp, _LANES), jnp.float32),
            pltpu.VMEM((kvh, gp, _LANES), jnp.float32),
            pltpu.VMEM((kvh, gp, d), jnp.float32),
        ],
    )
    kernel = functools.partial(_gqa_kernel, ps=ps, chunk=chunk, steps=grid,
                               kvh=kvh, d=d, sm_scale=sm_scale,
                               window=window)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, gp, d), out_dtype or q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="paged_attention_window" if window else "paged_attention_gqa",
        interpret=interpret,
    )(rows, pages, slots, held.astype(jnp.int32), first, last,
      jnp.zeros_like(last) if shift is None else shift,
      jnp.asarray(layer, jnp.int32).reshape(1), qg, k_pool, v_pool)
    return out[:, :, :grp].reshape(b, h, d)


# ---------------------------------------------------------------------------
# equal heads: the same work list, several pages of one row a grid step
# ---------------------------------------------------------------------------

# tokens a grid step of the equal-heads kernel meets as one tile.  A grid
# step costs about as much whatever it holds, and a 16-token page is a
# sixth of what that price buys in fetch time: see PERF.md
_CHUNK_TOKENS = 128


def chunk_pages(page_size, max_pages):
    """Pages of one row that a grid step of the equal-heads kernel
    brings in: ``_CHUNK_TOKENS`` worth, at least one, no more than a row
    can hold.  From the pool's page size alone; nothing tunes it."""
    return max(1, min(_CHUNK_TOKENS // page_size, max_pages))


def chunks_of(length, chunk_tokens, *, page_size=None, window=0):
    """List entries a row of ``length`` tokens (an int, or an array of
    rows) has in a work list of ``chunk_tokens`` a chunk (:func:`_walk`:
    one at least); with a ``window``, of the pages from the one its
    window starts in."""
    last = length - 1
    if not window:
        return last // chunk_tokens + 1
    first = np.maximum(length - window, 0) // page_size
    return (last // page_size - first) // (chunk_tokens // page_size) + 1


def _grouped(q, k_pool, window):
    """Whether the grouped kernels read these operands: a window, or
    fewer heads in the pool than q has."""
    return bool(window) or k_pool.shape[3] != q.shape[1] * q.shape[2]


def chunk_walk(q, k_pool, max_pages, *, window=None, steps=None):
    """What :func:`paged_attention` walks for these operands, of which
    only shapes and the pool's dtype are read (arrays or
    ``ShapeDtypeStruct``s): ``(tokens a chunk, grid length)`` of the
    equal-heads kernel, or of the grouped kernels (a window, fewer heads
    in the pool than q has), whose chunk is ``gqa_chunk_pages``;
    ``None`` for an int8 pool, which another kernel reads.

    ``steps`` is the caller's bound on the pages the batch holds plus
    one a row (:func:`paged_attention`); a row that walks ``p`` pages
    walks ``ceil(p / C)`` chunks, so the rows together walk at most
    ``ceil((steps - batch) / C) + batch``, and never more than
    ``batch * ceil(walk_pages / C)``."""
    batch, h, d = q.shape
    if k_pool.dtype == jnp.int8:
        return None
    page_size = k_pool.shape[2]
    walk = walk_pages(max_pages, page_size, window)
    c = (gqa_chunk_pages(k_pool, walk) if _grouped(q, k_pool, window)
         else chunk_pages(page_size, max_pages))
    grid = batch * -(-walk // c)
    if steps is not None:
        grid = min(grid, -(-max(int(steps) - batch, 0) // c) + batch)
    return c * page_size, grid


def _chunk_kernel(rows_ref, pages_ref, slots_ref, len_ref, last_ref,
                  layer_ref, q_ref, k_hbm, v_hbm, seg_ref, segt_ref, o_ref,
                  k_buf, v_buf, sem, m_scr, l_scr, acc_scr, *, ps, chunk,
                  steps, sm_scale):
    """One chunk of one row: the pages of it the row holds copied side by
    side into one ``(chunk * ps, H*D)`` tile of a two-deep buffer, the
    next list entry's copies started before this chunk's arithmetic, so
    that the selector matmuls and the rescale of the accumulator happen
    once a chunk.  A step past the list's end starts, waits for and
    computes nothing; nor does the entry of a row that holds nothing
    (``len_ref`` 0), which leaves zeros."""
    g = pl.program_id(0)
    row = rows_ref[g]
    slot = slots_ref[g]
    live = slot >= 0
    layer = layer_ref[0]

    def copies(step, do):
        """``do`` (start or wait) every copy of the pages list entry
        ``step`` holds of its row: the tile's slots past them keep what
        an earlier chunk left there, finite and masked by position."""
        held = len_ref[rows_ref[step]] - slots_ref[step] * (chunk * ps)

        def page(j, carry):
            at = pl.ds(pl.multiple_of(j * ps, ps), ps)
            for i, (pool, tile) in enumerate(((k_hbm, k_buf),
                                              (v_hbm, v_buf))):
                do(pltpu.make_async_copy(
                    pool.at[layer, pages_ref[step * chunk + j]],
                    tile.at[step % 2, at], sem.at[i, step % 2]))
            return carry

        # a loop, not ``chunk`` copies side by side: the program is
        # traced once a layer and a bucket, and this is most of its text
        jax.lax.fori_loop(0, jnp.minimum(pl.cdiv(held, ps), chunk), page, 0)

    @pl.when(g == 0)                # every row has a chunk: step 0 is live
    def _first():
        # a weight of 0 must meet a finite value in a slot never copied to
        v_buf[...] = jnp.zeros(v_buf.shape, v_buf.dtype)
        copies(0, lambda c: c.start())

    nxt = jnp.minimum(g + 1, steps - 1)

    @pl.when((g + 1 < steps) & (slots_ref[nxt] >= 0))
    def _next():
        copies(nxt, lambda c: c.start())

    @pl.when(live & (slot == 0))
    def _init():
        _init_scratch(m_scr, l_scr, acc_scr)

    @pl.when(live & (len_ref[row] > 0))
    def _chunk():
        copies(g, lambda c: c.wait())
        q = q_ref[...].astype(jnp.float32)                  # (1, H*D)
        k = k_buf[g % 2].astype(jnp.float32)        # (chunk * ps, H*D)
        s = _dot(q * k, seg_ref[...]) * sm_scale    # (chunk * ps, LANES)
        pos = slot * (chunk * ps) + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 0)
        valid = pos < len_ref[row]
        s = jnp.where(valid, s, _NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]             # (8, LANES)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new[:1]), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[:] = alpha * l_prev + jnp.sum(p, axis=0, keepdims=True)
        m_scr[:] = m_new
        # the weights and the accumulator's rescale spread over each
        # head's lanes by one matmul: alpha rides as eight more rows
        w = _dot(jnp.concatenate([p, alpha], axis=0), segt_ref[...])
        pv = jnp.sum(w[:chunk * ps] * v_buf[g % 2].astype(jnp.float32),
                     axis=0, keepdims=True)                  # (1, H*D)
        acc_scr[:] = acc_scr[...] * w[chunk * ps:] + pv

    @pl.when(live & (slot == last_ref[row]))
    def _fin():
        _finalize(o_ref, segt_ref[...], l_scr, acc_scr)


@functools.partial(jax.jit, static_argnames=("sm_scale", "chunk", "grid",
                                             "interpret"))
def _paged_attention_pallas(q, k_pool, v_pool, page_tables, lengths, layer,
                            *, sm_scale, chunk, grid, interpret):
    """Equal heads, fp32 or bf16 pools: ``grid`` steps of ``chunk`` pages
    (:func:`chunk_walk`).  ``layer`` is an operand and the function
    jitted, so that a program of many layers traces and lowers the
    kernel once, not once a layer (most of a decode program's build time
    otherwise).  The pools stay where they lie (``memory_space=ANY``)
    and the kernel copies a chunk's pages itself: against ``chunk`` page
    ``BlockSpec``s under Pallas' own double buffering a live step costs
    a tenth less, a step past the list's end nothing to speak of, and a
    slot past a row's last page is not fetched at all (PERF.md §6,
    PR 28)."""
    b, h, d = q.shape
    hd = h * d
    ps = k_pool.shape[2]
    rows, pages, slots, _, last = _walk(
        page_tables, lengths, ps=ps, window=0, steps=grid, chunk=chunk)
    # no row that holds a token has the null page first in its table
    held = jnp.where(page_tables[:, 0] == 0, 0, jnp.maximum(lengths, 1))
    seg, segt = _head_selectors(h, d)
    lanes = seg.shape[1]
    row_spec = pl.BlockSpec((None, 1, hd),
                            lambda g, rows, *_: (rows[g], 0, 0))
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(grid,),
        in_specs=[row_spec, pool_spec, pool_spec, _resident(seg),
                  _resident(segt)],
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((2, chunk * ps, hd), k_pool.dtype),
            pltpu.VMEM((2, chunk * ps, hd), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((8, lanes), jnp.float32),
            pltpu.VMEM((8, lanes), jnp.float32),
            pltpu.VMEM((8, hd), jnp.float32),
        ],
    )
    kernel = functools.partial(_chunk_kernel, ps=ps, chunk=chunk,
                               steps=grid, sm_scale=sm_scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="paged_attention",
        interpret=interpret,
    )(rows, pages, slots, held.astype(jnp.int32), last,
      jnp.asarray(layer, jnp.int32).reshape(1), q.reshape(b, 1, hd),
      k_pool, v_pool, seg, segt)
    return out.reshape(b, h, d)


def paged_attention(q, k_pool, v_pool, page_tables, lengths, *, layer,
                    sm_scale=None, window=None, steps=None,
                    use_pallas=None, interpret=None, out_dtype=None):
    """Dispatching entry: the Pallas paged-attention kernel on TPU, the
    XLA gather+softmax reference elsewhere.  Both read layer ``layer``
    of the whole ``(L, P, ps, H*D)`` pools.

    A pool row of fewer heads than q's, or ``window``, selects the
    grouped kernel (module docstring; :func:`chunk_walk` is the one
    place that tells what either walks).  ``steps`` is an upper bound
    the caller knows on the pages the batch walks (the allocator's: no
    two rows share a page, so at most the pool's usable pages plus one
    a row), ``batch * walk_pages`` when not given; either kernel's grid
    is made from it.
    ``out_dtype`` (the grouped kernel and the reference) is the result's
    dtype where ``q.dtype`` is too coarse for what follows.

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
    if use_pallas:
        record_dispatch("paged_attention", "pallas")
        walk = chunk_walk(q, k_pool, page_tables.shape[1], window=window,
                          steps=steps)
        if walk is None:
            raise ValueError("an int8 pool is paged_attention_int8's")
        chunk, grid = walk[0] // k_pool.shape[2], walk[1]
        if _grouped(q, k_pool, window):
            return _paged_attention_gqa_pallas(
                q, k_pool, v_pool, page_tables, lengths, layer,
                sm_scale=sm_scale, window=window or 0, chunk=chunk,
                grid=grid, interpret=interpret, out_dtype=out_dtype)
        if out_dtype is not None:
            raise ValueError("out_dtype: the grouped kernel's only")
        return _paged_attention_pallas(
            q, k_pool, v_pool, page_tables, lengths, layer,
            sm_scale=sm_scale, chunk=chunk, grid=grid, interpret=interpret)
    record_dispatch("paged_attention", "fallback")
    return paged_attention_reference(q, k_pool, v_pool, page_tables,
                                     lengths, layer=layer,
                                     sm_scale=sm_scale, window=window,
                                     out_dtype=out_dtype)


# ---------------------------------------------------------------------------
# differential attention: a K head of D lanes against a V of 2D
# ---------------------------------------------------------------------------
# Heads in order on the pool's lanes (arXiv:2410.05258 as Phi-4-mini-flash
# lays it out): K heads 2m and 2m+1 are the pair (k1_m, k2_m), V heads 2m
# and 2m+1 side by side one V_m of 2D lanes, and the four query heads
# 4m .. 4m+3 read KV pair m: head h a plain softmax over K head
# ``2m + h % 2``, weighing V_m.  A KV pair is therefore one "head" of 2D
# lanes to the grouped kernel above, with four query heads as its rows,
# once each query head is laid on the D lanes of the K head it reads and
# zeros on the other D: the contraction over 2D lanes then meets only
# that head.  No kernel of its own, and each K and V page is fetched once
# a KV pair, from where it lies.

def _diff_queries(q):
    """q (B, H, D) -> (B, H, 2D): head h on lanes ``(h % 2) * D ..``, zeros
    on the other half."""
    b, h, d = q.shape
    qq = q.reshape(b, h // 2, 2, d)
    zero = jnp.zeros((b, h // 2, d), q.dtype)
    return jnp.stack([jnp.concatenate([qq[:, :, 0], zero], axis=-1),
                      jnp.concatenate([zero, qq[:, :, 1]], axis=-1)],
                     axis=2).reshape(b, h, 2 * d)


def paged_attention_diff(q, k_pool, v_pool, page_tables, lengths, *, layer,
                         window=None, steps=None, use_pallas=None,
                         interpret=None):
    """A_h of differential attention for one query token a row: q (B, H,
    D) against pools of ``H / 2`` heads of D lanes; returns (B, H, 2D)
    float32 (the two halves of a pair are subtracted next, so the
    kernel's result is not rounded to the pool's dtype first).  Runs
    :func:`paged_attention` on pairs of 2D lanes (comment above)."""
    return paged_attention(
        _diff_queries(q), k_pool, v_pool, page_tables, lengths, layer=layer,
        sm_scale=1.0 / math.sqrt(q.shape[-1]), window=window, steps=steps,
        use_pallas=use_pallas, interpret=interpret, out_dtype=jnp.float32)


def paged_attention_diff_reference(q, k_pool, v_pool, page_tables, lengths,
                                   *, layer, window=None):
    """XLA twin of :func:`paged_attention_diff`, written from the layer's
    definition: every query head against its own K head of D lanes."""
    b, h, d = q.shape
    k_ctx = k_pool[layer, page_tables].reshape(b, -1, h // 2, d)
    v_ctx = v_pool[layer, page_tables].reshape(b, -1, h // 4, 2 * d)
    heads = jnp.arange(h)
    s = jnp.einsum("bhd,bchd->bhc", q, k_ctx[:, :, 2 * (heads // 4)
                                             + heads % 2],
                   preferred_element_type=jnp.float32) / math.sqrt(d)
    pos = jnp.arange(k_ctx.shape[1], dtype=jnp.int32)[None, :]
    mask = pos < lengths[:, None]
    if window:
        mask &= pos >= lengths[:, None] - window
    w = jax.nn.softmax(jnp.where(mask[:, None, :], s, _NEG_INF), axis=-1)
    return jnp.einsum("bhc,bchd->bhd", w.astype(v_ctx.dtype),
                      v_ctx[:, :, heads // 4],
                      preferred_element_type=jnp.float32)


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
