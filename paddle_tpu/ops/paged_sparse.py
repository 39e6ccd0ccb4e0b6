"""Learned sparse attention over a paged cache (the DeepSeek-Sparse-
Attention *lightning indexer*): every query scores all the keys it can
see with a small indexer, keeps the ``topk`` best, and attends over those
alone.

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        float32
    S_t     = the min(t + 1, topk) positions s <= t of largest I[t, s],
              ties to the lower position
    o_t     = softmax_{s in S_t}(q_t . k_s / sqrt(D)) v_s

Three payloads a token a layer live in pages under one table.  K and V
are **token-major**, one pool ``(L, P*ps, 2*KVH, D)`` in which a token is
its KV heads' K rows and then their V rows: at Keye's widths (4 KV heads
of 128, bfloat16) one whole ``(8, 128)`` tile, the unit a copy of a
tiled array may move (:mod:`..serving.kv_cache`: only this model's pool
is laid so; every other kernel walks pages).  The indexer's key lives in
the **index pool** ``(L, P, DI, ps)``: a page is ``DI`` rows of ``ps``
lanes (the key transposed, so that a 64-lane key wastes no lane and a
page's scores are one ``(J, DI) x (DI, ps)`` matmul).  Position ``t`` of
a row is page ``table[t // ps]`` of the index pool and token ``table[t
// ps] * ps + t % ps`` of the K and V pool.

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
   over the listed tokens and no others.  The Pallas kernel copies each
   listed token (its K and V together, one tile) from the pool in HBM by
   its address into a two-deep VMEM buffer, a grid step's copies started
   by the step before, and attends a tile of them with an online softmax
   in float32: every query head against all the tile's rows on the MXU,
   each head's scores kept on its own KV head's K rows.  Nothing of the
   pool is gathered into a copy first: each listed token's bytes cross
   HBM once.  (A page-major pool, a token a sub-tile row of its page,
   cannot be read so: Mosaic refuses a copy of fewer than 8 rows of a
   tiled array, "Slice shape along dimension 1 must be aligned to tiling
   (8)"; PERF.md section 7.)

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

def _listed_tokens(kv_pool, layer, addresses):
    """The listed tokens of one layer of a token-major pool, ``(B, K,
    2*KVH, D)``: a gather by token address, the layer inside the address
    (a slice of one layer would be a copy of it)."""
    layers, tokens, rows, d = kv_pool.shape
    return kv_pool.reshape(layers * tokens, rows, d)[
        layer * tokens + addresses]


def paged_attention_sparse_reference(q, kv_pool, addresses, counts, *,
                                     layer, sm_scale=None):
    """XLA twin: gather the listed tokens, masked softmax over the first
    ``counts`` of them, grouped heads; a row that lists nothing gets
    zeros."""
    b, h, d = q.shape
    kvh = kv_pool.shape[2] // 2
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)
    kv = _listed_tokens(kv_pool, layer, addresses)
    k, v = kv[:, :, :kvh], kv[:, :, kvh:]
    s = jnp.einsum("bkgd,bckd->bkgc", q.reshape(b, kvh, h // kvh, d), k,
                   preferred_element_type=jnp.float32) * sm_scale
    seen = jnp.arange(addresses.shape[1])[None, :] < counts[:, None]
    w = jax.nn.softmax(jnp.where(seen[:, None, None, :], s, _NEG_INF),
                       axis=-1)
    o = jnp.einsum("bkgc,bckd->bkgd", w.astype(v.dtype), v,
                   preferred_element_type=jnp.float32).reshape(b, h, d)
    return jnp.where(counts[:, None, None] > 0, o, 0.0).astype(q.dtype)


# listed tokens a grid step copies and attends: one layer at Keye's cell
# shapes on a TPU v5e 0.405 / 0.354 / 0.317 / 0.332 ms at 256 / 512 /
# 1,024 / 2,048 (exp/bench_sparse_gather.py)
_SPARSE_TILE = 1024
_COPIES_UNROLLED = 8    # copies started a loop iteration


def _attend_tile(q, kv, held, m_scr, l_scr, acc_scr, *, kvh, grp,
                 sm_scale):
    """One online-softmax update in float32 of every query head ``q``
    ``(HP, D)`` over a tile of tokens ``kv`` ``(tile, 2*KVH, D)``, of
    which the first ``held`` count.  All the tile's rows are one operand
    of the MXU: a head's scores are kept on the rows of its own KV head's
    K, and its probabilities, rolled ``KVH`` rows on, meet the same
    token's V in the second matmul.  (A strided load of one head's rows
    would unpack the bfloat16 pairs; the matmuls 2*KVH times wider than
    one head's are 6.4 GFLOP a layer at Keye's shapes, 33 µs at the
    MXU's peak.)"""
    tile, rows, d = kv.shape
    kv = kv.reshape(tile * rows, d)
    prec = _mxu_precision(kv.dtype)
    s = jax.lax.dot_general(q, kv, (((1,), (1,)), ((), ())), precision=prec,
                            preferred_element_type=jnp.float32) * sm_scale
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    head = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    own = (col % rows == head // grp) & (col // rows < held)
    s = jnp.where(own, s, _NEG_INF)
    m_prev, l_prev = m_scr[...], l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(own, jnp.exp(s - m_new[:, :1]), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    m_scr[...] = m_new
    pv = jnp.dot(pltpu.roll(p, kvh, 1).astype(kv.dtype), kv, precision=prec,
                 preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] * alpha[:, :1] + pv


def _sparse_kernel(at_ref, count_ref, q_ref, pool_hbm, o_ref, buf, sem,
                   m_scr, l_scr, acc_scr, *, kvh, grp, sm_scale):
    """Grid step ``(row, tile)``: the next step's listed tokens are copied
    from the pool (HBM) into the other half of a two-deep VMEM buffer, a
    token (its K and V, one whole tile of the pool) a copy, then this
    step's are waited for and attended.  Tokens past a row's count are
    not copied, and a tile wholly past it computes nothing; a row that
    lists nothing gets zeros."""
    tile = buf.shape[1]
    tiles = pl.num_programs(1)
    t = pl.program_id(1)
    step = pl.program_id(0) * tiles + t

    def held(s):
        return jnp.clip(count_ref[s // tiles] - s % tiles * tile, 0, tile)

    def copy(s, j):
        return pltpu.make_async_copy(pool_hbm.at[at_ref[s * tile + j]],
                                     buf.at[s % 2, j], sem.at[s % 2])

    def start(s):
        n = held(s)

        def unrolled(c, carry):
            for u in range(_COPIES_UNROLLED):
                copy(s, c * _COPIES_UNROLLED + u).start()
            return carry

        def one(j, carry):
            copy(s, j).start()
            return carry

        jax.lax.fori_loop(0, n // _COPIES_UNROLLED, unrolled, 0)
        jax.lax.fori_loop(n // _COPIES_UNROLLED * _COPIES_UNROLLED, n, one,
                          0)

    @pl.when(step == 0)
    def _first():
        # a part tile's weights past its count are zeros, and zeros times
        # what an earlier tile left there are zeros: never uninitialized
        buf[...] = jnp.zeros(buf.shape, buf.dtype)
        start(0)

    @pl.when(step + 1 < pl.num_programs(0) * tiles)
    def _next():
        start(step + 1)

    @pl.when(t == 0)
    def _init():
        _init_scratch(m_scr, l_scr, acc_scr)

    n = held(step)

    @pl.when(n == tile)
    def _whole():       # one wait for the tile's bytes
        pltpu.make_async_copy(pool_hbm.at[pl.ds(0, tile)], buf.at[step % 2],
                              sem.at[step % 2]).wait()

    @pl.when((n > 0) & (n < tile))
    def _part():
        def one(j, carry):
            copy(step, j).wait()
            return carry

        jax.lax.fori_loop(0, n, one, 0)

    @pl.when(n > 0)
    def _tile():
        _attend_tile(q_ref[...], buf[step % 2], n, m_scr, l_scr, acc_scr,
                     kvh=kvh, grp=grp, sm_scale=sm_scale)

    @pl.when(t == tiles - 1)
    def _fin():
        l = l_scr[...][:, :1]
        o_ref[...] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)
                      ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def _paged_attention_sparse_pallas(q, kv_pool, addresses, counts, layer, *,
                                   sm_scale, interpret):
    b, h, d = q.shape
    layers, tokens, rows, _ = kv_pool.shape
    kvh = rows // 2
    hp = -(-h // 16) * 16           # a bf16 tile of query rows
    listed = addresses.shape[1]
    tile = min(_SPARSE_TILE, listed)
    if listed % tile:
        raise ValueError(f"{listed} listed tokens are no whole tiles of "
                         f"{tile}")
    # a token's row of the pool with its layers as one axis, in range
    # whatever the list holds: the kernel's copies are not bounds-checked
    at = layer * tokens + jnp.clip(addresses, 0, tokens - 1)
    row_spec = pl.BlockSpec((None, hp, d), lambda i, t, *_: (i, 0, 0))
    out = pl.pallas_call(
        functools.partial(_sparse_kernel, kvh=kvh, grp=h // kvh,
                          sm_scale=sm_scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, listed // tile),
            in_specs=[row_spec, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=row_spec,
            scratch_shapes=[
                pltpu.VMEM((2, tile, rows, d), kv_pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((hp, _LANES), jnp.float32),
                pltpu.VMEM((hp, _LANES), jnp.float32),
                pltpu.VMEM((hp, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, hp, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # a step starts the next one's copies: the grid runs in order
            dimension_semantics=("arbitrary", "arbitrary"),
            disable_bounds_checks=True),
        name="paged_attention_sparse",
        interpret=interpret,
    )(at.reshape(-1).astype(jnp.int32), counts.astype(jnp.int32),
      jnp.pad(q, ((0, 0), (0, hp - h), (0, 0))),
      kv_pool.reshape(layers * tokens, rows, d))
    return out[:, :h]


def paged_attention_sparse(q, kv_pool, addresses, counts, *, layer,
                           sm_scale=None, use_pallas=None, interpret=None):
    """Grouped-query attention of one query a row over the tokens its
    list names and no others: ``q`` (B, H, D), the token-major pool
    ``(L, P*ps, 2*KVH, D)`` (a token's K heads, then its V heads),
    ``addresses`` (B, K) int32 token addresses ``page * ps + slot``,
    ``counts`` (B,) how many of a row's entries count (0: the row holds
    nothing and gets zeros).  Exact: an online softmax in float32 over
    every listed token."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    use_pallas, interpret = _dispatch("paged_attention_sparse", use_pallas,
                                      interpret)
    if not use_pallas:
        return paged_attention_sparse_reference(
            q, kv_pool, addresses, counts, layer=layer, sm_scale=sm_scale)
    return _paged_attention_sparse_pallas(
        q, kv_pool, addresses, counts, layer, sm_scale=sm_scale,
        interpret=interpret)
