"""The grouped, windowed paged-attention kernel (interpret mode) against
``paged_attention_reference`` and against plain attention over each row's
own context: grouped heads x {no window, a window inside one page, a
window across pages}, rows of very different lengths in one batch, pages
that slid out of a window returned (their table entries null), garbage in
every slot a row must not read.  And the work list the kernel's one grid
axis walks: no step for a slot outside a row's walk."""
import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.ops import paged_attention as pa
from paddle_tpu.ops.paged_attention import (paged_attention,
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


@pytest.mark.parametrize("window,lengths,want", [
    (0, [1, 9, 4], [(0, 0), (1, 0), (1, 1), (1, 2), (2, 0)]),
    (4, [1, 9, 4], [(0, 0), (1, 1), (1, 2), (2, 0)]),
    (8, [17, 32], [(0, 2), (0, 3), (0, 4), (1, 6), (1, 7)]),
])
def test_walk_lists_exactly_the_pages_each_row_reads(window, lengths, want):
    b = len(lengths)
    tables = np.arange(1, 1 + b * MAXP, dtype=np.int32).reshape(b, MAXP)
    steps = b * walk_pages(MAXP, PS, window)
    rows, pages, slots, first, last = (np.asarray(a) for a in pa._walk(
        jnp.asarray(tables), jnp.asarray(lengths, jnp.int32), ps=PS,
        window=window, steps=steps))
    live = slots >= 0
    assert list(zip(rows[live], slots[live])) == want
    assert list(pages[live]) == [tables[r, s] for r, s in want]
    # past the end nothing new is fetched: row and page repeat the last
    assert np.all(rows[~live] == want[-1][0])
    assert np.all(pages[~live] == tables[want[-1]])
    for r, n in enumerate(lengths):
        assert first[r] == (max(0, n - window) // PS if window else 0)
        assert last[r] == (n - 1) // PS


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
