"""Learned sparse attention over a paged cache (the DeepSeek-Sparse-
Attention *lightning indexer*): every query scores all the keys it can
see with a small indexer, keeps the ``topk`` best, and attends over those
alone.

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        float32
    S_t     = the min(t + 1, topk) positions s <= t of largest I[t, s],
              ties to the lower position
    o_t     = softmax_{s in S_t}(q_t . k_s / sqrt(D)) v_s

Three payloads a token a layer live in pages under one table: K and V
``(L, P, ps, KVH*D)`` as every model has them, and the indexer's key in
the **index pool** ``(L, P, DI, ps)``: a page is ``DI`` rows of ``ps``
lanes (the key transposed, so that a 64-lane key wastes no lane and a
page's scores are one ``(J, DI) x (DI, ps)`` matmul).

 - :func:`paged_index_scores` -- decode: one query a row against every
   cached indexer key of its row.  The Pallas kernel walks the chunk
   work list of :mod:`.paged_attention` (``_walk``), copies a chunk's
   pages by hand two deep and writes a score a token; what lies past a
   row's length is never written and never read (the selection masks by
   length).
 - :func:`index_scores` -- prefill: a block of queries against all the
   prompt's keys, tiled; the per-head scores never reach memory.
 - :func:`topk_mask` -- the exact selection as a mask, by a search for
   the k-th largest value over the floats' bit patterns (32 counting
   passes, no sort), the tie rule by a running count of the equals.
 - :func:`select_tokens` -- decode: the same selection as a list of
   token addresses ``page * ps + slot`` in position order, the mask
   turned into a list by one-hot matmuls (no sort, gather or scatter).
 - :func:`paged_attention_sparse` -- decode: grouped-query attention
   over the listed tokens and no others.  Each listed token's K row and
   V row (all KV heads of it) is gathered from where it lies by its
   address; the Pallas kernel takes the gathered rows a tile at a time
   (two deep, Pallas' own pipeline), the ``G`` query heads of a KV head
   against the tile on the MXU with an online softmax in float32, as
   ``_gqa_kernel`` does a chunk.  The gather is XLA's and not copies the
   kernel starts by hand: Mosaic refuses a copy of fewer than 8 rows of
   a tiled array ("Slice shape along dimension 1 must be aligned to
   tiling (8)"), and in a bfloat16 pool a token's lanes interleave with
   its neighbour's 16 bits at a time (PERF.md section 7, PR 34).

Dispatch is by platform as everywhere (:func:`framework.device.
pallas_dispatch`), booked on ``pt_pallas_calls_total{kernel=
"paged_index_scores" | "index_scores" | "paged_attention_sparse"}``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..framework import device as _device
from .paged_attention import _init_scratch, _walk
from .pallas_ops import _LANES, _NEG_INF, _interpret_default

__all__ = ["paged_index_scores", "paged_index_scores_reference",
           "index_scores", "index_scores_reference", "topk_mask",
           "select_tokens", "paged_attention_sparse",
           "paged_attention_sparse_reference", "index_walk"]


def _dispatch(kernel, use_pallas, interpret):
    from .fused_kernels import record_dispatch
    if interpret is None:
        interpret = _interpret_default()
    if use_pallas is None:
        use_pallas = _device.pallas_dispatch()
    record_dispatch(kernel, "pallas" if use_pallas else "fallback")
    return use_pallas, interpret


def _mxu_precision(dtype):
    # said outright, so that a process-wide default precision cannot ask
    # the MXU for float32 passes over bfloat16 operands
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


# ---------------------------------------------------------------------------
# decode: one query a row against its row's cached indexer keys
# ---------------------------------------------------------------------------

def paged_index_scores_reference(qi, wi, index_pool, page_tables, *, layer):
    """XLA twin: gather the rows' pages, ``I`` of every slot of every
    page in table order, ``(B, max_pages * ps)`` float32.  ``qi`` (B, J,
    DI), ``wi`` (B, J) float32, ``index_pool`` (L, P, DI, ps)."""
    pages = index_pool[layer, page_tables]              # (B, mp, DI, ps)
    s = jnp.einsum("bjd,bpds->bjps", qi, pages,
                   preferred_element_type=jnp.float32,
                   precision=_mxu_precision(qi.dtype))
    out = jnp.sum(jnp.maximum(s, 0.0) * wi[:, :, None, None], axis=1)
    return out.reshape(qi.shape[0], -1)


_INDEX_CHUNK_TOKENS = 1024  # indexer keys a grid step scores: 128 KB bf16


def index_walk(batch, max_pages, page_size, steps=None):
    """``(pages a chunk, grid length)`` of :func:`paged_index_scores`'s
    work list: chunks of ``_INDEX_CHUNK_TOKENS``, the grid bounded as
    ``paged_attention.chunk_walk`` bounds its own (``steps``: the pages
    the batch can hold plus one a row)."""
    c = max(1, min(_INDEX_CHUNK_TOKENS // page_size, max_pages))
    grid = batch * -(-max_pages // c)
    if steps is not None:
        grid = min(grid, -(-max(int(steps) - batch, 0) // c) + batch)
    return c, grid


def _index_kernel(rows_ref, pages_ref, slots_ref, len_ref, layer_ref, at_ref,
                  q_ref, w_ref, pool_hbm, o_ref, buf, sem, *, ps, chunk, steps):
    """One chunk of one row: its pages ``(DI, ps)`` copied into a two-deep
    ``(chunk, DI, ps)`` tile, the next list entry's copies started first;
    then a page ``relu(qI kI^T)`` on the MXU, the heads weighed and summed
    on the sublanes, one ``(1, ps)`` row of scores a page."""
    g = pl.program_id(0)
    row = rows_ref[g]
    layer = layer_ref[0]

    def copies(step, do):
        held = len_ref[rows_ref[step]] - slots_ref[step] * (chunk * ps)

        def page(j, carry):
            do(pltpu.make_async_copy(
                pool_hbm.at[layer, pages_ref[step * chunk + j]],
                buf.at[step % 2, j], sem.at[step % 2]))
            return carry

        jax.lax.fori_loop(0, jnp.minimum(pl.cdiv(held, ps), chunk), page, 0)

    @pl.when(g == 0)
    def _first():
        copies(0, lambda c: c.start())

    nxt = jnp.minimum(g + 1, steps - 1)

    @pl.when((g + 1 < steps) & (slots_ref[nxt] >= 0))
    def _next():
        copies(nxt, lambda c: c.start())

    @pl.when((slots_ref[g] >= 0) & (len_ref[row] > 0))
    def _chunk():
        copies(g, lambda c: c.wait())
        q = q_ref[...]                                        # (J, DI)
        w = w_ref[...]                                        # (J, LANES)
        for j in range(chunk):      # a page past the row's last: stale,
            s = jnp.dot(q, buf[g % 2, j],   # finite or not, never read
                        precision=_mxu_precision(buf.dtype),
                        preferred_element_type=jnp.float32)   # (J, ps)
            s = jnp.maximum(s, 0.0) * w[:, :1]
            o_ref[j:j + 1, :] = jnp.sum(s, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("chunk", "grid", "interpret"))
def _paged_index_scores_pallas(qi, wi, index_pool, page_tables, lengths,
                               layer, *, chunk, grid, interpret):
    b, j, di = qi.shape
    ps = index_pool.shape[3]
    n_chunks = -(-page_tables.shape[1] // chunk)
    rows, pages, slots, _, _ = _walk(page_tables, lengths, ps=ps, window=0,
                                     steps=grid, chunk=chunk)
    held = jnp.where(page_tables[:, 0] == 0, 0, jnp.maximum(lengths, 1))
    live = jnp.sum((slots >= 0).astype(jnp.int32))
    at = jnp.where(slots >= 0, slots, slots[live - 1])   # the out block
    w = jnp.broadcast_to(wi.astype(jnp.float32)[:, :, None], (b, j, _LANES))

    def by_row(g, rows, *_):
        return (rows[g], 0, 0)

    out = pl.pallas_call(
        functools.partial(_index_kernel, ps=ps, chunk=chunk, steps=grid),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(grid,),
            in_specs=[pl.BlockSpec((None, j, di), by_row),
                      pl.BlockSpec((None, j, _LANES), by_row),
                      pl.BlockSpec(memory_space=pl.ANY)],
            # a step past the list's end keeps the last live step's block:
            # it stays where it is and is not written again
            out_specs=pl.BlockSpec(
                (None, None, chunk, ps),
                lambda g, rows, pages, slots, held, layer, at: (
                    rows[g], at[g], 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, chunk, di, ps), index_pool.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((b, n_chunks, chunk, ps), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="paged_index_scores",
        interpret=interpret,
    )(rows, pages, slots, held.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), at, qi, w, index_pool)
    return out.reshape(b, n_chunks * chunk * ps)[:, :page_tables.shape[1]
                                                 * ps]


def paged_index_scores(qi, wi, index_pool, page_tables, lengths, *, layer,
                       steps=None, use_pallas=None, interpret=None):
    """``I`` of each row's one query against every cached indexer key of
    its row, ``(B, max_pages * ps)`` float32 in position order.  Only
    positions under a row's length mean anything: the kernel does not
    write past them, the reference scores whatever the table's unused
    tail points at.  ``steps`` as in :func:`paged_attention.
    paged_attention`."""
    use_pallas, interpret = _dispatch("paged_index_scores", use_pallas,
                                      interpret)
    if not use_pallas:
        return paged_index_scores_reference(qi, wi, index_pool, page_tables,
                                            layer=layer)
    chunk, grid = index_walk(qi.shape[0], page_tables.shape[1],
                             index_pool.shape[3], steps)
    return _paged_index_scores_pallas(qi, wi, index_pool, page_tables,
                                      lengths, layer, chunk=chunk, grid=grid,
                                      interpret=interpret)


# ---------------------------------------------------------------------------
# prefill: a block of queries against all the prompt's keys
# ---------------------------------------------------------------------------

def index_scores_reference(qi, wi, ki):
    """``I`` (Q, S) float32 of queries ``qi`` (Q, J, DI), ``wi`` (Q, J)
    float32 against keys ``ki`` (S, DI)."""
    s = jnp.einsum("qjd,sd->qjs", qi, ki, preferred_element_type=jnp.float32,
                   precision=_mxu_precision(qi.dtype))
    return jnp.sum(jnp.maximum(s, 0.0) * wi[:, :, None], axis=1)


_TILE_Q, _TILE_S = 256, 512     # queries and keys a tile of index_scores


def _index_block_kernel(last_ref, q_ref, w_ref, k_ref, o_ref, *, heads):
    """One (TQ, TS) tile: the heads' ``relu(q k^T)`` one after another on
    the MXU, weighed and summed in float32 where they stand.  A tile of
    keys wholly past the last position the block's queries see
    (``last_ref``) is skipped: nothing reads it."""
    @pl.when(pl.program_id(1) * o_ref.shape[1] <= last_ref[0])
    def _tile():
        k = k_ref[...]                                        # (TS, DI)
        acc = jnp.zeros(o_ref.shape, jnp.float32)
        for j in range(heads):
            s = jax.lax.dot_general(
                q_ref[j], k, (((1,), (1,)), ((), ())),
                precision=_mxu_precision(k.dtype),
                preferred_element_type=jnp.float32)           # (TQ, TS)
            acc = acc + jnp.maximum(s, 0.0) * w_ref[j]
        o_ref[...] = acc


def index_scores(qi, wi, ki, *, last=None, use_pallas=None, interpret=None):
    """``I`` (Q, S) float32 of a block of queries against every key of
    the prompt (arguments as :func:`index_scores_reference`).  ``last``
    (scalar) is the last key position any of the block's queries sees:
    the kernel leaves the tiles past it unwritten."""
    q, j, di = qi.shape
    s = ki.shape[0]
    tiled = q % _TILE_Q == 0 and s % _TILE_S == 0
    use_pallas, interpret = _dispatch("index_scores",
                                      use_pallas if tiled else False,
                                      interpret)
    if not use_pallas:
        return index_scores_reference(qi, wi, ki)
    last = jnp.asarray(s - 1 if last is None else last, jnp.int32)
    return pl.pallas_call(
        functools.partial(_index_block_kernel, heads=j),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(q // _TILE_Q, s // _TILE_S),
            in_specs=[
                pl.BlockSpec((j, _TILE_Q, di), lambda a, b, *_: (0, a, 0)),
                pl.BlockSpec((j, _TILE_Q, 1), lambda a, b, *_: (0, a, 0)),
                pl.BlockSpec((_TILE_S, di), lambda a, b, *_: (b, 0))],
            out_specs=pl.BlockSpec((_TILE_Q, _TILE_S),
                                   lambda a, b, *_: (a, b))),
        out_shape=jax.ShapeDtypeStruct((q, s), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="index_scores",
        interpret=interpret,
    )(last.reshape(1), jnp.transpose(qi, (1, 0, 2)),
      jnp.transpose(wi.astype(jnp.float32))[:, :, None], ki)


# ---------------------------------------------------------------------------
# the exact selection
# ---------------------------------------------------------------------------

def _ordered(scores, valid):
    """float32 -> uint32 that orders as the floats do (-0 as +0), 0 where
    ``valid`` is false: below every float."""
    scores = jnp.where(scores == 0.0, 0.0, scores)
    bits = jax.lax.bitcast_convert_type(scores.astype(jnp.float32),
                                        jnp.int32)
    bits = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    u = jax.lax.bitcast_convert_type(bits, jnp.uint32) ^ jnp.uint32(1 << 31)
    return jnp.where(valid, u, jnp.uint32(0))


def topk_mask(scores, valid, k):
    """Exactly the ``min(k, valid entries)`` largest valid scores of each
    row as a mask, ties to the lower index.  ``scores`` (Q, S) float32,
    ``valid`` (Q, S) bool, ``k`` (Q,) int32.  The k-th largest value of a
    row is built a bit at a time from the top (32 passes that count the
    entries at or above a candidate); what is above it is in, and of what
    equals it the first ``k - above`` by index."""
    u = _ordered(scores, valid)
    k = jnp.minimum(k, jnp.sum(valid, axis=1)).astype(jnp.int32)

    def bit(i, kth):
        cand = kth | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(u >= cand[:, None], axis=1, dtype=jnp.int32) >= k
        return jnp.where(enough, cand, kth)

    kth = jax.lax.fori_loop(0, 32, bit, jnp.zeros(u.shape[:1], jnp.uint32))
    above = u > kth[:, None]
    equal = (u == kth[:, None]) & valid
    need = k - jnp.sum(above, axis=1, dtype=jnp.int32)

    def tied(_):
        rank = jnp.cumsum(equal.astype(jnp.int32), axis=1)
        return above | (equal & (rank <= need[:, None]))

    # the running count only where some row's equals straddle the cut
    return jax.lax.cond(
        jnp.any(jnp.sum(equal, axis=1, dtype=jnp.int32) != need),
        tied, lambda _: above | equal, None)


def select_tokens(scores, lengths, page_tables, *, topk, page_size,
                  pool_pages):
    """Decode's selection: of row ``b``'s scores ``scores[b, :lengths[b]]``
    the ``min(length, topk)`` largest, ties to the lower position
    (:func:`topk_mask`), as a list in position order.  Returns
    ``(positions, addresses, counts)``: ``(B, topk)`` int32 positions,
    their token addresses ``page * page_size + slot`` through the row's
    table, and how many of a row's entries count, ``(B,)``; the entries
    past them address some token of the pool.

    The mask becomes a list without a sort, a gather or a scatter (each
    an operation a row or an element on the chip): entry ``j`` lies in
    the page whose running count first passes ``j``, at the slot where
    the page's own running count reaches what is left, and both the
    page's counts and its id come out of a one-hot matmul over the row's
    pages.  Every number in them is an integer under 256, exact in
    bfloat16: a page's counts because a page holds at most 256 tokens, its
    id in two halves of eight bits because the pool the tables address
    holds at most ``pool_pages`` <= 65,536 pages."""
    b, n = scores.shape
    mp = n // page_size
    if page_size > 256 or page_tables.shape[1] != mp:
        raise ValueError("scores are (B, max_pages * page_size), pages of "
                         "at most 256 tokens")
    if pool_pages > 1 << 16:
        raise ValueError(f"a pool of {pool_pages} pages: a page's id is "
                         "picked in two halves of eight bits, so of at "
                         "most 65,536 pages")
    i32, bf16 = jnp.int32, jnp.bfloat16
    valid = jnp.arange(n, dtype=i32)[None, :] < lengths[:, None]
    counts = jnp.minimum(lengths, topk).astype(i32)
    mask = topk_mask(scores, valid, counts).reshape(b, mp, page_size)
    held = jnp.sum(mask, axis=2, dtype=i32)                   # (B, mp)
    ends = jnp.cumsum(held, axis=1)
    j = jnp.arange(topk, dtype=i32)
    page = jnp.minimum(jnp.sum(ends[:, None, :] <= j[None, :, None], axis=2,
                               dtype=i32), mp - 1)            # (B, topk)
    of_page = (page[:, :, None] == jnp.arange(mp, dtype=i32)).astype(bf16)

    def pick(x):        # x (B, mp, c) of integers under 256, by `page`
        return jnp.einsum("bkp,bpc->bkc", of_page, x.astype(bf16),
                          preferred_element_type=jnp.float32).astype(i32)

    # a page's running count, by slot: mask x upper triangle of ones
    running = jnp.einsum(
        "bpl,lm->bpm", mask.astype(bf16),
        jnp.triu(jnp.ones((page_size, page_size), bf16)),
        preferred_element_type=jnp.float32)
    before = pick(jnp.stack([ends - held], axis=2))[..., 0]
    want = j[None, :] - before + 1          # its place in its page, from 1
    slot = jnp.minimum(jnp.sum(pick(running) < want[:, :, None], axis=2,
                               dtype=i32), page_size - 1)
    ids = pick(jnp.stack([page_tables >> 8, page_tables & 255], axis=2))
    return (page * page_size + slot,
            (ids[..., 0] * 256 + ids[..., 1]) * page_size + slot, counts)


# ---------------------------------------------------------------------------
# decode: attention over the listed tokens
# ---------------------------------------------------------------------------

def paged_attention_sparse_reference(q, k_pool, v_pool, addresses, counts,
                                     *, layer, sm_scale=None):
    """XLA twin: gather the listed tokens' rows, masked softmax over the
    first ``counts`` of them, grouped heads; a row that lists nothing
    gets zeros."""
    b, h, d = q.shape
    kvh = k_pool.shape[3] // d
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    k = _listed_rows(k_pool, layer, addresses).reshape(b, -1, kvh, d)
    v = _listed_rows(v_pool, layer, addresses).reshape(b, -1, kvh, d)
    s = jnp.einsum("bkgd,bckd->bkgc", q.reshape(b, kvh, h // kvh, d), k,
                   preferred_element_type=jnp.float32) * sm_scale
    seen = jnp.arange(addresses.shape[1])[None, :] < counts[:, None]
    w = jax.nn.softmax(jnp.where(seen[:, None, None, :], s, _NEG_INF),
                       axis=-1)
    o = jnp.einsum("bkgc,bckd->bkgd", w.astype(v.dtype), v,
                   preferred_element_type=jnp.float32).reshape(b, h, d)
    return jnp.where(counts[:, None, None] > 0, o, 0.0).astype(q.dtype)


_SPARSE_TILE = 512      # listed tokens a tile of the kernel


def _sparse_kernel(count_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, tile, kvh, d, sm_scale):
    """One tile of one row's listed tokens: a KV head its G query heads
    (padded to a bf16 tile of rows) against the tile's ``(tile, D)`` lanes
    on the MXU, one online-softmax update in float32 a tile, as
    ``_gqa_kernel`` does a chunk.  A tile wholly past the row's count
    computes nothing; a row that lists nothing gets zeros."""
    t = pl.program_id(1)
    count = count_ref[pl.program_id(0)]

    @pl.when(t == 0)
    def _init():
        _init_scratch(m_scr, l_scr, acc_scr)

    @pl.when(t * tile < count)
    def _tile():
        prec = _mxu_precision(k_ref.dtype)
        valid = (t * tile + jax.lax.broadcasted_iota(
            jnp.int32, (q_ref.shape[1], tile), 1)) < count
        for j in range(kvh):
            q = q_ref[j]                                      # (GP, D)
            k = k_ref[:, j * d:(j + 1) * d]                   # (tile, D)
            v = v_ref[:, j * d:(j + 1) * d]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), precision=prec,
                preferred_element_type=jnp.float32) * sm_scale
            s = jnp.where(valid, s, _NEG_INF)
            m_prev, l_prev = m_scr[j], l_scr[j]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(valid, jnp.exp(s - m_new[:, :1]), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_scr[j] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            m_scr[j] = m_new
            pv = jnp.dot(p.astype(v.dtype), v, precision=prec,
                         preferred_element_type=jnp.float32)
            acc_scr[j] = acc_scr[j] * alpha[:, :1] + pv

    @pl.when(t == pl.num_programs(1) - 1)
    def _fin():
        for j in range(kvh):
            l = l_scr[j][:, :1]
            o_ref[j] = (acc_scr[j] / jnp.where(l == 0.0, 1.0, l)
                        ).astype(o_ref.dtype)


def _listed_rows(pool, layer, addresses):
    """The listed tokens' rows of one layer, ``(B, K, KVH*D)``: a gather
    by token address over pages and slots as one axis."""
    layers, pages, ps, lanes = pool.shape
    # the layer inside the address: a slice of one layer would be a copy
    return pool.reshape(layers * pages * ps, lanes)[
        layer * (pages * ps) + addresses]


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def _paged_attention_sparse_pallas(q, k_pool, v_pool, addresses, counts,
                                   layer, *, sm_scale, interpret):
    b, h, d = q.shape
    lanes = k_pool.shape[3]
    kvh = lanes // d
    grp = h // kvh
    gp = -(-grp // 16) * 16         # a bf16 tile of rows a KV head
    listed = addresses.shape[1]
    tile = min(_SPARSE_TILE, listed)
    if listed % tile:
        raise ValueError(f"{listed} listed tokens are no whole tiles of "
                         f"{tile}")
    qg = jnp.pad(q.reshape(b, kvh, grp, d),
                 ((0, 0), (0, 0), (0, gp - grp), (0, 0)))
    row_spec = pl.BlockSpec((None, kvh, gp, d),
                            lambda i, t, *_: (i, 0, 0, 0))
    # a tile past the row's count is the row's last live tile again: the
    # block stays where it is and nothing is fetched for it
    tile_spec = pl.BlockSpec(
        (None, tile, lanes), lambda i, t, counts: (
            i, jnp.minimum(t, jnp.maximum(counts[i] - 1, 0) // tile), 0))
    out = pl.pallas_call(
        functools.partial(_sparse_kernel, tile=tile, kvh=kvh, d=d,
                          sm_scale=sm_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, listed // tile),
            in_specs=[row_spec, tile_spec, tile_spec],
            out_specs=row_spec,
            scratch_shapes=[
                pltpu.VMEM((kvh, gp, _LANES), jnp.float32),
                pltpu.VMEM((kvh, gp, _LANES), jnp.float32),
                pltpu.VMEM((kvh, gp, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, kvh, gp, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="paged_attention_sparse",
        interpret=interpret,
    )(counts.astype(jnp.int32), qg, _listed_rows(k_pool, layer, addresses),
      _listed_rows(v_pool, layer, addresses))
    return out[:, :, :grp].reshape(b, h, d)


def paged_attention_sparse(q, k_pool, v_pool, addresses, counts, *, layer,
                           sm_scale=None, use_pallas=None, interpret=None):
    """Grouped-query attention of one query a row over the tokens its
    list names and no others: ``q`` (B, H, D), pools ``(L, P, ps,
    KVH*D)``, ``addresses`` (B, K) int32 token addresses ``page * ps +
    slot``, ``counts`` (B,) how many of a row's entries count (0: the row
    holds nothing and gets zeros).  Exact: an online softmax in float32
    over every listed token."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    use_pallas, interpret = _dispatch("paged_attention_sparse", use_pallas,
                                      interpret)
    if not use_pallas:
        return paged_attention_sparse_reference(
            q, k_pool, v_pool, addresses, counts, layer=layer,
            sm_scale=sm_scale)
    return _paged_attention_sparse_pallas(
        q, k_pool, v_pool, addresses, counts, layer, sm_scale=sm_scale,
        interpret=interpret)
