"""The equal-heads paged-attention kernel (interpret mode): a work list of
chunks, ``C`` consecutive pages of one row a grid step, against
``paged_attention_reference`` for fp32 and bf16 pools: lengths around a
page's and a chunk's edges and the longest context, rows of very different
lengths in one batch, padding rows (all-null tables: zeros, and nothing
read), garbage in every slot a row must not read.  A row's output is bit-identical alone and among
neighbours, in another bucket and under a tight and a loose ``steps``
bound; the work list lists exactly the pages each row reads, and the
kernel fetches no other."""
import numpy as np
import pytest

import jax.numpy as jnp

import jax

from paddle_tpu.ops import paged_attention as pa
from paddle_tpu.ops.paged_attention import (chunk_pages, chunk_walk,
                                            chunks_of, paged_attention,
                                            paged_attention_reference)

PS, MAXP = 16, 24
C = chunk_pages(PS, MAXP)                   # 8 pages: 128 tokens a chunk
H, D, LAYERS = 2, 16, 2
LENGTHS = {"one_token": 1, "one_page": PS, "under_a_chunk": C * PS - 1,
           "a_chunk": C * PS, "over_a_chunk": C * PS + 1,
           "longest": PS * MAXP}
DTYPES = {"fp32": (jnp.float32, 2e-5), "bf16": (jnp.bfloat16, 3e-2)}


def _case(lengths, dtype, padding=()):
    """Pools full of garbage (finite, as the null page is), each row's
    context written through its own pages; rows in ``padding`` are what
    the engine pads a bucket with: position 0, an all-null table."""
    rng = np.random.RandomState(len(lengths) * 7 + sum(lengths) % 97)
    garbage = 1e4 if dtype == jnp.float32 else 1.0
    b = len(lengths)
    pages = 1 + b * MAXP
    k = garbage * rng.randn(LAYERS, pages, PS, H * D)
    v = garbage * rng.randn(LAYERS, pages, PS, H * D)
    own = rng.permutation(np.arange(1, pages)).reshape(b, MAXP)
    tables = np.zeros((b, MAXP), np.int32)
    for r, n in enumerate(lengths):
        if r in padding:
            continue
        held = -(-n // PS)
        tables[r, :held] = own[r, :held]
        for t in range(n):
            k[:, tables[r, t // PS], t % PS] = rng.randn(LAYERS, H * D)
            v[:, tables[r, t // PS], t % PS] = rng.randn(LAYERS, H * D)
    q = rng.randn(b, H, D)
    return (jnp.asarray(q, dtype), jnp.asarray(k, dtype),
            jnp.asarray(v, dtype), jnp.asarray(tables),
            jnp.asarray(lengths, jnp.int32))


def _grid(batch, max_pages, page_size, steps=None):
    """``chunk_walk`` of an fp32 batch with equal heads."""
    return chunk_walk(
        jax.ShapeDtypeStruct((batch, H, D), jnp.float32),
        jax.ShapeDtypeStruct((LAYERS, 9, page_size, H * D), jnp.float32),
        max_pages, steps=steps)


def _kernel(args, **kw):
    return paged_attention(*args, layer=1, use_pallas=True, interpret=True,
                           **kw)


def _close(got, ref, atol):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), atol=atol,
                               rtol=atol)


@pytest.mark.parametrize("precision", DTYPES)
@pytest.mark.parametrize("length", LENGTHS)
def test_one_row_equals_reference(length, precision):
    dtype, atol = DTYPES[precision]
    args = _case([LENGTHS[length]], dtype)
    got = _kernel(args)
    assert got.dtype == dtype
    _close(got, paged_attention_reference(*args, layer=1), atol)


@pytest.mark.parametrize("precision", DTYPES)
def test_rows_of_very_different_lengths_and_padding_rows(precision):
    dtype, atol = DTYPES[precision]
    lengths = [1, PS * MAXP, 1, 37, C * PS + 1, 1, C * PS, 2 * C * PS - 1]
    q, k, v, tables, lens = _case(lengths, dtype, padding=(2, 5))
    assert not np.asarray(tables)[[2, 5]].any()
    held = [r for r in range(len(lengths)) if r not in (2, 5)]
    ref = np.asarray(
        paged_attention_reference(q, k, v, tables, lens, layer=1), np.float32)
    got = _kernel((q, k, v, tables, lens))
    _close(np.asarray(got, np.float32)[held], ref[held], atol)
    # a padding row holds nothing: zeros, whatever the null page holds
    assert not np.asarray(got, np.float32)[[2, 5]].any()
    k, v = k.at[:, 0].set(jnp.nan), v.at[:, 0].set(jnp.inf)
    np.testing.assert_array_equal(
        np.asarray(_kernel((q, k, v, tables, lens)), np.float32),
        np.asarray(got, np.float32))


@pytest.mark.parametrize("precision", DTYPES)
@pytest.mark.parametrize("length", LENGTHS)
def test_a_row_is_bit_identical_alone_and_among_neighbours(length,
                                                           precision):
    """Continuous batching's contract: neither the neighbours, the row's
    place in the work list nor the bucket's size reaches its output."""
    dtype, _ = DTYPES[precision]
    n = LENGTHS[length]
    q, k, v, tables, lengths = _case([77, n, PS * MAXP, 5], dtype)
    among = np.asarray(_kernel((q, k, v, tables, lengths)), np.float32)[1]
    alone = np.asarray(_kernel((q[1:2], k, v, tables[1:2], lengths[1:2])),
                       np.float32)[0]
    np.testing.assert_array_equal(among, alone)
    # first of a bucket of eight, the rest padding rows
    pad_t = jnp.zeros((8, MAXP), jnp.int32).at[0].set(tables[1])
    pad_l = jnp.ones((8,), jnp.int32).at[0].set(n)
    pad_q = jnp.zeros((8, H, D), dtype).at[0].set(q[1])
    padded = np.asarray(_kernel((pad_q, k, v, pad_t, pad_l)), np.float32)[0]
    np.testing.assert_array_equal(among, padded)


@pytest.mark.parametrize("precision", DTYPES)
def test_a_tight_and_a_loose_step_bound_give_the_same_bits(precision):
    """``steps``: the allocator's bound (pages held plus one a row) is
    far under batch x table width and must change nothing."""
    dtype, _ = DTYPES[precision]
    lengths = [1, C * PS + 1, 40, PS * MAXP]
    args = _case(lengths, dtype)
    b = len(lengths)
    held = sum(-(-n // PS) for n in lengths)
    loose = _kernel(args)
    tight = _kernel(args, steps=held + b)
    assert _grid(b, MAXP, PS, held + b)[1] < _grid(b, MAXP, PS)[1] == b * 3
    np.testing.assert_array_equal(np.asarray(loose, np.float32),
                                  np.asarray(tight, np.float32))


@pytest.mark.parametrize("precision", DTYPES)
def test_slots_past_a_rows_last_page_are_never_fetched(precision):
    """The list pads a chunk with the null page, the kernel copies only
    the pages the row holds: poison in the null page reaches no row."""
    dtype, _ = DTYPES[precision]
    lengths = [1, PS + 1, C * PS + 1, 37, PS * MAXP - 1]
    q, k, v, tables, lens = _case(lengths, dtype)
    clean = np.asarray(_kernel((q, k, v, tables, lens)), np.float32)
    k, v = k.at[:, 0].set(jnp.nan), v.at[:, 0].set(jnp.inf)
    poisoned = np.asarray(_kernel((q, k, v, tables, lens)), np.float32)
    np.testing.assert_array_equal(clean, poisoned)


@pytest.mark.parametrize("lengths,want", [
    # (row, chunk) of every live step; C * PS = 128 tokens a chunk
    ([1, 129, 128], [(0, 0), (1, 0), (1, 1), (2, 0)]),
    ([384, 1], [(0, 0), (0, 1), (0, 2), (1, 0)]),
    ([127, 257, 16], [(0, 0), (1, 0), (1, 1), (1, 2), (2, 0)]),
])
def test_chunk_list_lists_exactly_the_pages_each_row_reads(lengths, want):
    b = len(lengths)
    tables = np.arange(1, 1 + b * MAXP, dtype=np.int32).reshape(b, MAXP)
    steps = _grid(b, MAXP, PS)[1]
    rows, pages, slots, first, last = (np.asarray(a) for a in pa._walk(
        jnp.asarray(tables), jnp.asarray(lengths, jnp.int32), ps=PS,
        window=0, steps=steps, chunk=C))
    pages = pages.reshape(steps, C)
    live = slots >= 0
    assert list(zip(rows[live], slots[live])) == want
    for (r, s), got in zip(want, pages[live]):
        held = -(-lengths[r] // PS)
        # the row's own pages of this chunk, the null page past its last
        assert list(got) == [tables[r, p] if p < held else 0
                             for p in range(s * C, (s + 1) * C)]
    # past the end nothing new is fetched: row and pages repeat the last
    assert np.all(rows[~live] == want[-1][0])
    assert np.all(pages[~live] == pages[live][-1])
    assert list(first) == [0] * b
    assert list(last) == [(n - 1) // (C * PS) for n in lengths]
    # what the scheduler counts a row for
    assert [chunks_of(n, C * PS) for n in lengths] \
        == [sum(1 for r, _ in want if r == i) for i in range(b)]


def test_chunk_length_one_is_the_page_list():
    """One form of the list for every kernel: a chunk of one page is the
    rows' pages in order."""
    tables = np.arange(1, 1 + 2 * MAXP, dtype=np.int32).reshape(2, MAXP)
    rows, pages, slots, first, last = (np.asarray(a) for a in pa._walk(
        jnp.asarray(tables), jnp.asarray([40, 17], jnp.int32), ps=PS,
        window=0, steps=8, chunk=1))
    assert list(pages[:5]) == [1, 2, 3, 25, 26]
    assert list(rows[:5]) == [0, 0, 0, 1, 1]
    assert list(slots) == [0, 1, 2, 0, 1, -1, -1, -1]
    assert list(first) == [0, 0] and list(last) == [2, 1]


@pytest.mark.parametrize("page_size,max_pages,want", [
    (16, 64, 8), (128, 64, 1), (256, 32, 1), (4, 8, 8), (16, 2, 2)])
def test_chunk_pages_follows_the_page_size(page_size, max_pages, want):
    assert chunk_pages(page_size, max_pages) == want


@pytest.mark.parametrize("batch,steps,want", [
    # gpt-345m-serve: 1,024 pages of 16 tokens, tables of 64 pages
    (32, 1023 + 32, 160), (16, 1023 + 16, 128), (8, 1023 + 8, 64),
    (32, None, 256), (32, 32, 32)])
def test_chunk_walk_bounds_the_grid(batch, steps, want):
    assert _grid(batch, 64, 16, steps) == (128, want)
