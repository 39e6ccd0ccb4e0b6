"""The grouped, windowed paged-attention kernels (interpret mode) against
``paged_attention_reference`` / ``paged_attention_diff_reference`` and
against plain attention over each row's own context: grouped heads x {no
window, a window inside one page, a window across pages}, differential
operands, fp32 and bf16 pools, lengths around a page's, a chunk's and the
window's edges, rows of very different lengths and padding rows in one
batch, pages that slid out of a window returned (their table entries
null), garbage or poison in every slot a row must not read.  A row's
output is bit-identical alone and among neighbours, in another bucket and
under a tight and a loose ``steps``.  And the work list the kernels' one
grid axis walks: chunks of ``C`` consecutive pages of one row, a windowed
row's run starting at the page its window starts in; no entry, and no
fetch, for a page outside a row's walk."""
import numpy as np
import pytest

import jax.numpy as jnp

import jax

from paddle_tpu.ops import paged_attention as pa
from paddle_tpu.ops.paged_attention import (chunk_walk, chunks_of,
                                            gqa_chunk_pages,
                                            paged_attention,
                                            paged_attention_diff,
                                            paged_attention_diff_reference,
                                            paged_attention_reference,
                                            walk_pages)

PS, MAXP = 4, 8


def _case(h, kvh, d, window, dtype=np.float32, layers=2, garbage=1e4):
    """Pools full of garbage, each row's context written through its
    table; a windowed row's pages before its window are returned (null
    in the table, garbage in the pool)."""
    rng = np.random.RandomState(h * 100 + kvh * 10 + (window or 0))
    lengths = np.asarray([1, 7, PS * MAXP, 13, PS + 1], np.int32)
    b = len(lengths)
    pages = 1 + b * MAXP
    k = (garbage * rng.randn(layers, pages, PS, kvh * d)).astype(dtype)
    v = (garbage * rng.randn(layers, pages, PS, kvh * d)).astype(dtype)
    own = rng.permutation(np.arange(1, pages)).reshape(b, MAXP)
    tables = np.zeros((b, MAXP), np.int32)
    ctx = []
    for r in range(b):
        n = int(lengths[r])
        tables[r, :-(-n // PS)] = own[r, :-(-n // PS)]
        kc = rng.randn(layers, n, kvh * d).astype(dtype)
        vc = rng.randn(layers, n, kvh * d).astype(dtype)
        for t in range(n):
            k[:, tables[r, t // PS], t % PS] = kc[:, t]
            v[:, tables[r, t // PS], t % PS] = vc[:, t]
        if window:
            tables[r, :max(0, n - window) // PS] = 0
        ctx.append((kc, vc))
    q = rng.randn(b, h, d).astype(dtype)
    return q, k, v, tables, lengths, ctx


def _plain(q, kc, vc, kvh, window):
    """One row's attention over its own context, a query head at a time."""
    h, d = q.shape
    n = kc.shape[0]
    lo = max(0, n - window) if window else 0
    out = np.zeros((h, d), np.float64)
    for j in range(h):
        g = j // (h // kvh)
        kk = kc[lo:, g * d:(g + 1) * d].astype(np.float64)
        vv = vc[lo:, g * d:(g + 1) * d].astype(np.float64)
        s = kk @ q[j].astype(np.float64) / np.sqrt(d)
        w = np.exp(s - s.max())
        out[j] = (w / w.sum()) @ vv
    return out


_HEADS = {"g2": (4, 2), "g4": (8, 2), "mqa": (4, 1), "equal_heads": (2, 2)}
_WINDOWS = {"full": None, "inside_a_page": 3, "one_page": PS,
            "across_pages": 10}


# equal heads without a window is the (batch, pages) kernel, tested in
# tests/test_serving.py
@pytest.mark.parametrize("heads,span", [
    (hn, wn) for hn in _HEADS for wn in _WINDOWS
    if (hn, wn) != ("equal_heads", "full")])
def test_kernel_equals_reference_and_plain_attention(heads, span):
    (h, kvh), window = _HEADS[heads], _WINDOWS[span]
    d, layer = 16, 1
    q, k, v, tables, lengths, ctx = _case(h, kvh, d, window)
    args = (jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(tables), jnp.asarray(lengths))
    ref = np.asarray(paged_attention_reference(*args, layer=layer,
                                               window=window))
    got = np.asarray(paged_attention(*args, layer=layer, window=window,
                                     use_pallas=True, interpret=True))
    np.testing.assert_allclose(got, ref, atol=2e-5, rtol=2e-5)
    for r, (kc, vc) in enumerate(ctx):
        want = _plain(q[r], kc[layer], vc[layer], kvh, window)
        np.testing.assert_allclose(got[r], want, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("window", [None, 6])
def test_kernel_in_bfloat16(window):
    q, k, v, tables, lengths, _ = _case(8, 2, 16, window, garbage=1.0)
    bf = jnp.bfloat16
    args = (jnp.asarray(q, bf), jnp.asarray(k, bf), jnp.asarray(v, bf),
            jnp.asarray(tables), jnp.asarray(lengths))
    ref = paged_attention_reference(*args, layer=0, window=window)
    got = paged_attention(*args, layer=0, window=window, use_pallas=True,
                          interpret=True)
    assert got.dtype == bf
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), atol=3e-2)


@pytest.mark.parametrize("window", [None, 5])
def test_a_tight_step_bound_gives_the_same_result(window):
    """``steps``: the allocator's bound (no two rows share a page) is far
    under batch x walk and must change nothing."""
    q, k, v, tables, lengths, _ = _case(4, 2, 16, window)
    args = (jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(tables), jnp.asarray(lengths))
    loose = paged_attention(*args, layer=0, window=window, use_pallas=True,
                            interpret=True)
    total = int(sum((n - 1) // PS - (max(0, n - (window or n)) // PS) + 1
                    for n in lengths))
    tight = paged_attention(*args, layer=0, window=window, use_pallas=True,
                            interpret=True, steps=total)
    np.testing.assert_array_equal(np.asarray(loose), np.asarray(tight))


def _list(window, lengths, chunk, steps=None, maxp=MAXP):
    """``_walk`` as the grouped kernels call it, over tables whose every
    slot holds a page of its own; ``(row, chunk, pages)`` of each live
    entry, and the arrays."""
    b = len(lengths)
    tables = np.arange(1, 1 + b * maxp, dtype=np.int32).reshape(b, maxp)
    lens = jnp.asarray(lengths, jnp.int32)
    steps = steps or b * -(-walk_pages(maxp, PS, window) // chunk)
    shift = (pa._window_shift(lens, ps=PS, window=window, chunk=chunk)
             if window else None)
    rows, pages, slots, first, last = (np.asarray(a) for a in pa._walk(
        jnp.asarray(tables), lens, ps=PS, window=window, steps=steps,
        chunk=chunk, shift=shift))
    pages = pages.reshape(steps, chunk)
    live = slots >= 0
    entries = [(int(r), int(c), [int(x) for x in pg if x])
               for r, c, pg in zip(rows[live], slots[live], pages[live])]
    return entries, tables, (rows, pages, slots, first, last, live,
                             np.zeros(b, int) if shift is None
                             else np.asarray(shift))


@pytest.mark.parametrize("window,lengths,want", [
    (0, [1, 9, 4], [(0, 0), (1, 0), (1, 1), (1, 2), (2, 0)]),
    (4, [1, 9, 4], [(0, 0), (1, 1), (1, 2), (2, 0)]),
    (8, [17, 32], [(0, 2), (0, 3), (0, 4), (1, 6), (1, 7)]),
])
def test_walk_lists_exactly_the_pages_each_row_reads(window, lengths, want):
    """A chunk of one page: the list is the rows' pages in order."""
    entries, tables, (rows, pages, slots, first, last, live, _) = _list(
        window, lengths, 1)
    assert [(r, c) for r, c, _ in entries] == want
    assert [pg for _, _, pg in entries] == [[tables[r, s]] for r, s in want]
    # past the end nothing new is fetched: row and page repeat the last
    assert np.all(rows[~live] == want[-1][0])
    assert np.all(pages[~live] == tables[want[-1]])
    for r, n in enumerate(lengths):
        assert first[r] == (max(0, n - window) // PS if window else 0)
        assert last[r] == (n - 1) // PS


@pytest.mark.parametrize("chunk", [2, 3, 4, 5])
@pytest.mark.parametrize("window", [0, 3, 8, 10, 16])
def test_chunk_list_starts_a_windowed_row_at_its_windows_first_page(
        window, chunk):
    """Every row's entries together hold exactly the pages from the one
    its window starts in to its last, in order, ``chunk`` an entry and
    the rest in the last: none before, none after, whatever the page the
    window starts in."""
    lengths = [1, PS, 7, 13, 2 * PS + 1, 17, 23, PS * MAXP - 1, PS * MAXP]
    entries, tables, (_, _, _, first, last, _, shift) = _list(
        window, lengths, chunk)
    for r, n in enumerate(lengths):
        lo = max(0, n - window) // PS if window else 0
        hi = (n - 1) // PS
        mine = [(c, pg) for row, c, pg in entries if row == r]
        assert [c for c, _ in mine] == list(range(first[r], last[r] + 1))
        assert len(mine) == -(-(hi - lo + 1) // chunk) == chunks_of(
            n, chunk * PS, page_size=PS, window=window)
        assert [x for _, pg in mine for x in pg] == list(tables[r, lo:hi + 1])
        assert all(len(pg) == chunk for _, pg in mine[:-1])
        # the position the kernel gives an entry's first slot
        assert (first[r] * chunk + shift[r]) * PS == lo * PS
    # at most ceil(walk / chunk) entries a row: the grid chunk_walk makes
    assert len(entries) <= len(lengths) * -(
        -walk_pages(MAXP, PS, window) // chunk)


@pytest.mark.parametrize("window,want", [(0, MAXP), (3, 2), (4, 2),
                                         (10, 4), (1000, MAXP)])
def test_walk_pages(window, want):
    assert walk_pages(MAXP, PS, window) == want


def test_window_walks_fewer_steps_than_pages():
    """The window kernel's grid is the window's span, not the table's
    width: 64 rows of 8192 tokens at 128 a page walk 9 pages each."""
    assert walk_pages(64, 128, 1024) == 9
    assert walk_pages(64, 128, 0) == 64


def test_reference_equal_heads_without_window_is_unchanged():
    """The ungrouped path is the one GPT serving compiles: same numbers
    from the grouped formulation with one head a group."""
    q, k, v, tables, lengths, _ = _case(2, 2, 16, None)
    args = (jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            jnp.asarray(tables), jnp.asarray(lengths))
    plain = paged_attention_reference(*args, layer=0)
    as_window = paged_attention_reference(*args, layer=0,
                                          window=PS * MAXP + 1)
    np.testing.assert_allclose(np.asarray(plain), np.asarray(as_window),
                               atol=1e-6)


# -- several pages a grid step ------------------------------------------------
# tables of 16 pages of 4 tokens, chunks pinned to 4 pages (16 tokens), a
# window of 24 tokens (7 pages at most: two chunks)

CP, WIN, WIDE = 4, 24, 16
KINDS = {"grouped": dict(h=8, kvh=2), "mqa": dict(h=4, kvh=1),
         "window": dict(h=8, kvh=2, window=WIN),
         "equal_heads_window": dict(h=2, kvh=2, window=WIN),
         "diff": dict(h=8, kvh=4, diff=True),
         "diff_window": dict(h=8, kvh=4, diff=True, window=WIN)}
EDGES = {"one_token": 1, "one_page": PS, "under_a_chunk": CP * PS - 1,
         "a_chunk": CP * PS, "over_a_chunk": CP * PS + 1,
         "under_the_window": WIN - 1, "the_window": WIN,
         "over_the_window": WIN + 1, "the_window_and_a_page": WIN + PS,
         "longest": PS * WIDE}
DTYPES = {"fp32": (jnp.float32, 2e-5), "bf16": (jnp.bfloat16, 3e-2)}
D = 16


def _rows(lengths, kind, dtype, padding=(), poison=False):
    """Pools full of finite garbage (NaN with ``poison``, the null page
    too), each row's walk — its context, from the page its window starts
    in — written through its own pages; earlier pages are returned (null
    in the table).  Rows in ``padding`` are what the engine pads a bucket
    with: position 0, an all-null table."""
    kind = dict(dict(window=None, diff=False), **KINDS[kind])
    h, kvh, window = kind["h"], kind["kvh"], kind["window"]
    rng = np.random.RandomState(sum(lengths) + 31 * len(lengths) + h)
    b = len(lengths)
    pages = 1 + b * WIDE
    fill = np.nan if poison else (1e4 if dtype == jnp.float32 else 1.0)
    k = fill * rng.randn(2, pages, PS, kvh * D)
    v = fill * rng.randn(2, pages, PS, kvh * D)
    own = rng.permutation(np.arange(1, pages)).reshape(b, WIDE)
    tables = np.zeros((b, WIDE), np.int32)
    for r, n in enumerate(lengths):
        if r in padding:
            continue
        lo = max(0, n - window) // PS if window else 0
        hi = (n - 1) // PS
        tables[r, lo:hi + 1] = own[r, lo:hi + 1]
        # whole pages: the slots past the row's length hold stale,
        # finite values, as a page the allocator hands out does
        k[:, tables[r, lo:hi + 1]] = rng.randn(2, hi + 1 - lo, PS, kvh * D)
        v[:, tables[r, lo:hi + 1]] = rng.randn(2, hi + 1 - lo, PS, kvh * D)
    q = rng.randn(b, h, D)
    return (jnp.asarray(q, dtype), jnp.asarray(k, dtype),
            jnp.asarray(v, dtype), jnp.asarray(tables),
            jnp.asarray(lengths, jnp.int32)), kind


def _chunked(monkeypatch, args, kind, **kw):
    """The public entry with the grouped kernels' chunk pinned to ``CP``
    pages, and its XLA twin."""
    q, k = args[0], args[1]
    monkeypatch.setattr(pa, "_GQA_CHUNK_BYTES",
                        CP * 2 * PS * k.shape[3] * k.dtype.itemsize)
    entry, twin = ((paged_attention_diff, paged_attention_diff_reference)
                   if kind["diff"] else
                   (paged_attention, paged_attention_reference))
    seen = pa._diff_queries(q) if kind["diff"] else q
    assert chunk_walk(seen, k, WIDE, window=kind["window"])[0] == CP * PS
    got = entry(*args, layer=1, window=kind["window"], use_pallas=True,
                interpret=True, **kw)
    return got, twin(*args, layer=1, window=kind["window"])


def _f32(x):
    return np.asarray(x, np.float32)


@pytest.mark.parametrize("precision", DTYPES)
@pytest.mark.parametrize("length", EDGES)
@pytest.mark.parametrize("kind", ["grouped", "window", "diff",
                                  "diff_window"])
def test_one_row_in_chunks_equals_reference(monkeypatch, kind, length,
                                            precision):
    dtype, atol = DTYPES[precision]
    args, kind = _rows([EDGES[length]], kind, dtype)
    got, ref = _chunked(monkeypatch, args, kind)
    assert got.dtype == (jnp.float32 if kind["diff"] else dtype)
    np.testing.assert_allclose(_f32(got), _f32(ref), atol=atol, rtol=atol)


@pytest.mark.parametrize("precision", DTYPES)
@pytest.mark.parametrize("kind", KINDS)
def test_a_mixed_batch_with_padding_rows_in_chunks(monkeypatch, kind,
                                                   precision):
    dtype, atol = DTYPES[precision]
    lengths = [1, PS * WIDE, 1, WIN + 1, CP * PS + 1, 1, CP * PS, 37]
    args, kind = _rows(lengths, kind, dtype, padding=(2, 5))
    assert not np.asarray(args[3])[[2, 5]].any()
    held = [r for r in range(len(lengths)) if r not in (2, 5)]
    got, ref = _chunked(monkeypatch, args, kind)
    np.testing.assert_allclose(_f32(got)[held], _f32(ref)[held], atol=atol,
                               rtol=atol)
    # a padding row holds nothing: zeros, whatever the null page holds
    assert not _f32(got)[[2, 5]].any()


@pytest.mark.parametrize("precision", DTYPES)
@pytest.mark.parametrize("kind", ["grouped", "window", "diff_window"])
def test_poison_outside_a_rows_walk_reaches_no_output(monkeypatch, kind,
                                                      precision):
    """NaN in the null page (what a slot past a row's last page and a
    padding row's table name), in every page no table names (the pages
    before a window, returned) and in every page of the pool a row does
    not hold: the kernels copy a row's own pages from the one its window
    starts in to its last, and nothing else."""
    dtype, _ = DTYPES[precision]
    lengths = [1, PS + 1, CP * PS + 1, 37, PS * WIDE - 1, WIN + PS, 1]
    clean, kd = _rows(lengths, kind, dtype, padding=(6,))
    dirty, _ = _rows(lengths, kind, dtype, padding=(6,), poison=True)
    assert np.isnan(_f32(dirty[1])[:, 0]).all()
    got, _ = _chunked(monkeypatch, clean, kd)
    poisoned, _ = _chunked(monkeypatch, dirty, kd)
    assert np.isfinite(_f32(poisoned)).all()
    np.testing.assert_array_equal(_f32(got), _f32(poisoned))


@pytest.mark.parametrize("precision", DTYPES)
@pytest.mark.parametrize("length", ["one_token", "a_chunk", "over_a_chunk",
                                    "the_window", "over_the_window",
                                    "longest"])
@pytest.mark.parametrize("kind", ["grouped", "window", "diff_window"])
def test_a_row_in_chunks_is_bit_identical_alone_and_among_neighbours(
        monkeypatch, kind, length, precision):
    """Continuous batching's contract: neither the neighbours, the row's
    place in the work list, the bucket's size nor the grid's length
    reaches its output."""
    dtype, _ = DTYPES[precision]
    n = EDGES[length]
    (q, k, v, tables, lengths), kd = _rows([45, n, PS * WIDE, 5], kind,
                                           dtype)
    among = _f32(_chunked(monkeypatch, (q, k, v, tables, lengths), kd)[0])[1]
    alone = _f32(_chunked(monkeypatch, (q[1:2], k, v, tables[1:2],
                                        lengths[1:2]), kd)[0])[0]
    np.testing.assert_array_equal(among, alone)
    # first of a bucket of eight, the rest padding rows
    pad_t = jnp.zeros((8, WIDE), jnp.int32).at[0].set(tables[1])
    pad_l = jnp.ones((8,), jnp.int32).at[0].set(n)
    pad_q = jnp.zeros((8,) + q.shape[1:], dtype).at[0].set(q[1])
    padded = _f32(_chunked(monkeypatch, (pad_q, k, v, pad_t, pad_l),
                           kd)[0])[0]
    np.testing.assert_array_equal(among, padded)
    # the allocator's bound: the pages the four rows walk, and one a row
    walked = sum(int((np.asarray(tables)[r] != 0).sum()) for r in range(4))
    tight = _f32(_chunked(monkeypatch, (q, k, v, tables, lengths), kd,
                          steps=walked + 4)[0])[1]
    np.testing.assert_array_equal(among, tight)


@pytest.mark.parametrize("lanes,itemsize,walk,want", [
    # Mellum2: 4 KV heads of 128 in bf16, 256 KiB of K and V a page
    (512, 2, 64, 14), (512, 2, 9, 9),
    # Phi-4-mini-flash: 20 KV heads of 64, 640 KiB a page
    (1280, 2, 32, 5), (1280, 2, 5, 5),
    # a page larger than a step's bytes, a walk of one page
    (8192, 4, 64, 1), (512, 2, 1, 1)])
def test_gqa_chunk_pages_follows_the_page_bytes(lanes, itemsize, walk, want):
    pool = jax.ShapeDtypeStruct(
        (1, 9, 128, lanes), {2: jnp.bfloat16, 4: jnp.float32}[itemsize])
    assert gqa_chunk_pages(pool, walk) == want


@pytest.mark.parametrize("case,want", [
    # the two serve cells' decode programs: 64 rows, pages of 128 tokens
    (dict(h=32, lanes=512, pages=2049, maxp=64), (1792, 211)),
    (dict(h=32, lanes=512, pages=577, maxp=64, window=1024), (1152, 64)),
    (dict(h=40, lanes=1280, pages=2049, maxp=32), (640, 448)),
    (dict(h=40, lanes=1280, pages=321, maxp=32, window=512), (640, 64)),
    # no bound from the caller: every row its whole walk
    (dict(h=32, lanes=512, pages=None, maxp=64), (1792, 64 * 5)),
    # an int8 pool is another kernel's
    (dict(h=32, lanes=512, pages=2049, maxp=64, dtype=jnp.int8), None),
    (dict(h=4, lanes=512, pages=2049, maxp=64, window=1024,
          dtype=jnp.int8), None)])
def test_chunk_walk_answers_for_the_grouped_kernels(case, want):
    q = jax.ShapeDtypeStruct((64, case["h"], 128), jnp.bfloat16)
    pool = jax.ShapeDtypeStruct((1, case["pages"] or 9, 128, case["lanes"]),
                                case.get("dtype", jnp.bfloat16))
    steps = case["pages"] and case["pages"] - 1 + 64
    assert chunk_walk(q, pool, case["maxp"], window=case.get("window"),
                      steps=steps) == want
