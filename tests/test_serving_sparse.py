"""Learned sparse attention (Keye-VL-2.0-30B-A3B's language model: an
indexer, an exact top-k a query, attention over the selected tokens alone,
over routed experts) through the serving stack, at the tiny preset
``benchmarks/configs/tiny-keyevl2-serve.json``: hidden 64, 4 query / 2 KV
heads of 16, 4 indexer heads of 8, top-16, 8 experts top-2, 2 layers,
seeded random weights, float32:

 - the three kernels of ``ops/paged_sparse.py`` in interpret mode against
   their XLA twins over random tables: lengths under, at and over ``topk``,
   padding rows, a row alone / among neighbours / in another bucket
   bit-identical; with ``topk`` over every length the sparse read equals
   the full paged read; the selection's tie rule;
 - prefill then decode through the cache against the plain reference's
   full forward (``benchmarks/reference/keyevl2_serve.py``) on logits, the
   dense prefill and the blocked one, continuous batching with rows
   joining and leaving;
 - the index pool: pages allocated, reserved and returned with the K and V
   pages; the scheduler's counters; ``costs_sparse.py`` against hand
   arithmetic; the runner's check against its wrong references; the new
   readers; the configuration against the catalog; the rehearsal.
"""
import copy
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for _p in (BENCH, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from paddle_tpu.ops import paged_sparse as sparse  # noqa: E402
from paddle_tpu.ops.paged_attention import (  # noqa: E402
    paged_attention_reference)
from paddle_tpu.serving import (ModelSpec, ServeConfig,  # noqa: E402
                                ServingEngine, init_params)
from paddle_tpu.serving import model as serving_model  # noqa: E402
from paddle_tpu.serving.kv_cache import PagePool, pool_shapes  # noqa: E402

CELL = "keyevl2-serve-longctx-reasoning-backlog"
NEW_METRICS = ["indexer_ms_per_step", "attn_sparse_ms_per_step",
               "paged_attn_sparse_roofline", "index_scores_roofline",
               "sparse_selected_pct", "serve_mfu_pct.keyevl2"]
PS, TOPK = 8, 16


def _load(kind, name):
    import importlib.util
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"s_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs",
                           "tiny-keyevl2-serve.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def runner():
    return _load("runners", "serve_sparse")


@pytest.fixture(scope="module")
def ref():
    return _load("reference", "keyevl2_serve")


@pytest.fixture(scope="module")
def built(config, runner):
    engine, params, spec, _ = runner.build_engine(config, 3)
    yield engine, params, spec
    engine.close()


# -- the kernels --------------------------------------------------------------

def _pools(rng, dtype, lengths, pages=40, max_pages=6, layers=2, kvh=2, d=16,
           di=8):
    """Random pools and, for rows of `lengths` (0: a padding row), tables
    of pages drawn in random order."""
    lengths = np.asarray(lengths, np.int32)
    tables = np.zeros((len(lengths), max_pages), np.int32)
    free = list(rng.permutation(np.arange(1, pages)))
    for b, n in enumerate(lengths):
        held = -(-int(n) // PS)
        tables[b, :held] = [free.pop() for _ in range(held)]

    def draw(*shape):
        return jnp.asarray(rng.randn(*shape), dtype)

    return (draw(layers, pages, PS, kvh * d), draw(layers, pages, PS, kvh * d),
            draw(layers, pages, di, PS), jnp.asarray(tables),
            jnp.asarray(np.maximum(lengths, 1)), lengths > 0)


def _token_major(k_pool, v_pool):
    """The token-major pool `(L, P*ps, 2*KVH, D)` that holds what the
    page-major pools `(L, P, ps, KVH*D)` hold: a token's K heads, then its
    V heads."""
    layers, pages, ps, lanes = k_pool.shape
    kvh = 2
    return jnp.concatenate(
        [p.reshape(layers, pages * ps, kvh, lanes // kvh)
         for p in (k_pool, v_pool)], axis=2)


LENGTHS = [3, TOPK, TOPK + 1, 40, 0, 9]     # under, at, over topk; padding


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["fp32", "bf16"])
def test_the_index_scores_kernel_equals_its_xla_twin(dtype, tol):
    rng = np.random.RandomState(0)
    _, _, index_pool, tables, lengths, live = _pools(rng, dtype, LENGTHS)
    qi = jnp.asarray(rng.randn(len(LENGTHS), 4, 8), dtype)
    wi = jnp.asarray(rng.randn(len(LENGTHS), 4), jnp.float32)
    want = sparse.paged_index_scores_reference(qi, wi, index_pool, tables,
                                               layer=1)
    got = sparse.paged_index_scores(qi, wi, index_pool, tables, lengths,
                                    layer=1, steps=39 + len(LENGTHS),
                                    use_pallas=True, interpret=True)
    assert got.shape == want.shape == (len(LENGTHS), 6 * PS)
    for b, n in enumerate(LENGTHS):     # what lies past a row is not read
        np.testing.assert_allclose(np.asarray(got[b, :n]),
                                   np.asarray(want[b, :n]), atol=tol)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 3e-2)],
                         ids=["fp32", "bf16"])
def test_the_sparse_kernel_equals_its_xla_twin(dtype, tol):
    rng = np.random.RandomState(1)
    k_pool, v_pool, index_pool, tables, lengths, live = _pools(
        rng, dtype, LENGTHS)
    scores = jnp.asarray(rng.randn(len(LENGTHS), 6 * PS), jnp.float32)
    chosen, addresses, counts = sparse.select_tokens(
        scores, lengths, tables, topk=TOPK, page_size=PS, pool_pages=40)
    counts = jnp.where(jnp.asarray(live), counts, 0)
    q = jnp.asarray(rng.randn(len(LENGTHS), 4, 16), dtype)
    kv_pool = _token_major(k_pool, v_pool)
    want = sparse.paged_attention_sparse_reference(
        q, kv_pool, addresses, counts, layer=1)
    got = sparse.paged_attention_sparse(q, kv_pool, addresses, counts,
                                        layer=1, use_pallas=True,
                                        interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)
    assert not np.asarray(got[4], np.float32).any()     # the padding row
    # a row that sees no more than topk positions reads all of them: the
    # full paged read over the same pages
    full = paged_attention_reference(q, k_pool, v_pool, tables, lengths,
                                     layer=1)
    for b, n in enumerate(LENGTHS):
        if 0 < n <= TOPK:
            np.testing.assert_allclose(np.asarray(got[b], np.float32),
                                       np.asarray(full[b], np.float32),
                                       atol=tol)


def test_with_topk_over_every_length_the_sparse_read_is_the_full_read():
    rng = np.random.RandomState(2)
    k_pool, v_pool, _, tables, lengths, live = _pools(
        rng, jnp.float32, [3, 17, 40, 48])
    scores = jnp.asarray(rng.randn(4, 6 * PS), jnp.float32)
    _, addresses, counts = sparse.select_tokens(
        scores, lengths, tables, topk=48, page_size=PS, pool_pages=40)
    assert counts.tolist() == [3, 17, 40, 48]
    q = jnp.asarray(rng.randn(4, 4, 16), jnp.float32)
    got = sparse.paged_attention_sparse(q, _token_major(k_pool, v_pool),
                                        addresses, counts, layer=0,
                                        use_pallas=True, interpret=True)
    full = paged_attention_reference(q, k_pool, v_pool, tables, lengths,
                                     layer=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(full), atol=2e-5)


@pytest.mark.parametrize("row", [0, 2, 3], ids=["under", "over", "long"])
def test_a_row_alone_among_neighbours_and_in_another_bucket_is_bit_identical(
        row):
    """Scores, selection and attention of one row: in a bucket of six
    beside the others, in a bucket of two beside a padding row, and in a
    bucket of four at another place."""
    rng = np.random.RandomState(3)
    k_pool, v_pool, index_pool, tables, lengths, live = _pools(
        rng, jnp.float32, LENGTHS)
    n = len(LENGTHS)
    qi = jnp.asarray(rng.randn(n, 4, 8), jnp.float32)
    wi = jnp.asarray(rng.randn(n, 4), jnp.float32)
    q = jnp.asarray(rng.randn(n, 4, 16), jnp.float32)
    kv_pool = _token_major(k_pool, v_pool)

    def through(rows):
        rows = np.asarray(rows)
        t, ln = tables[rows], jnp.where(rows == 4, 1, lengths[rows])
        scores = sparse.paged_index_scores(
            qi[rows], wi[rows], index_pool, t, ln, layer=1,
            steps=39 + len(rows), use_pallas=True, interpret=True)
        chosen, addresses, counts = sparse.select_tokens(
            scores, ln, t, topk=TOPK, page_size=PS, pool_pages=40)
        counts = jnp.where(jnp.asarray(rows != 4), counts, 0)
        out = sparse.paged_attention_sparse(
            q[rows], kv_pool, addresses, counts, layer=1, use_pallas=True,
            interpret=True)
        at = list(rows).index(row)
        c = int(counts[at])
        return (np.asarray(scores[at, :LENGTHS[row]]),
                np.asarray(chosen[at, :c]), np.asarray(out[at]))

    want = through(range(n))
    for rows in ([row, 4], [4, 5, row, 1]):
        for a, b in zip(want, through(rows)):
            np.testing.assert_array_equal(a, b)


@pytest.fixture
def tiles_of_eight(monkeypatch):
    """The kernel at tiles of 8 listed tokens, so that a list of 32 is
    four tiles; retraced before and after."""
    monkeypatch.setattr(sparse, "_SPARSE_TILE", 8)
    sparse._paged_attention_sparse_pallas.clear_cache()
    yield
    sparse._paged_attention_sparse_pallas.clear_cache()


@pytest.mark.parametrize("counts", [[0, 0, 0], [5, 1, 7], [13, 21, 30],
                                    [32, 32, 32], [32, 0, 9]],
                         ids=["nothing", "under_a_tile", "part_tiles",
                              "full", "mixed"])
@pytest.mark.parametrize("layer", [0, 2])
def test_the_kernel_copies_the_listed_tokens_of_a_token_major_pool(
        tiles_of_eight, counts, layer):
    """The kernel in interpret mode against its twin: lists of 32 tokens
    drawn over every page of a three-layer pool, counts of nothing, under
    a tile, of part tiles and of the whole list; what lies past a count
    is never attended (NaN there would show)."""
    rng = np.random.RandomState(20 + layer)
    layers, tokens = 3, 40 * PS
    kv_pool = rng.randn(layers, tokens, 4, 16).astype(np.float32)
    addresses = np.sort(rng.randint(1, tokens, (3, 32)), axis=1)
    counts = np.asarray(counts, np.int32)
    listed = np.concatenate([a[:c] for a, c in zip(addresses, counts)])
    kv_pool[layer, np.setdiff1d(np.arange(tokens), listed)] = np.nan
    q = jnp.asarray(rng.randn(3, 4, 16), jnp.float32)
    args = (q, jnp.asarray(kv_pool), jnp.asarray(addresses, jnp.int32),
            jnp.asarray(counts))
    got = np.asarray(sparse.paged_attention_sparse(
        *args, layer=layer, use_pallas=True, interpret=True))
    clean = np.nan_to_num(kv_pool)
    want = np.asarray(sparse.paged_attention_sparse_reference(
        q, jnp.asarray(clean), *args[2:], layer=layer))
    np.testing.assert_allclose(got, want, atol=2e-5)
    for b, c in enumerate(counts):
        if c == 0:
            assert not got[b].any()


def test_the_prefill_scores_kernel_equals_its_xla_twin_and_skips_the_unseen():
    rng = np.random.RandomState(4)
    qi = jnp.asarray(rng.randn(512, 4, 8), jnp.float32)
    wi = jnp.asarray(rng.randn(512, 4), jnp.float32)
    ki = jnp.asarray(rng.randn(1536, 8), jnp.float32)
    want = sparse.index_scores_reference(qi, wi, ki)
    got = sparse.index_scores(qi, wi, ki, last=700, use_pallas=True,
                              interpret=True)
    np.testing.assert_allclose(np.asarray(got[:, :701]),
                               np.asarray(want[:, :701]), atol=2e-5)
    # shapes the tiles do not divide take the twin
    assert sparse.index_scores(qi[:100], wi[:100], ki[:300],
                               use_pallas=True).shape == (100, 300)


def _exact(scores, n, k):
    """The exact top-`k` of scores[:n] by numpy: a stable sort, so ties go
    to the lower position."""
    s = np.where(scores[:n] == 0.0, 0.0, scores[:n])
    return set(np.argsort(-s, kind="stable")[:k].tolist())


def test_the_selection_is_exact_and_ties_go_to_the_lower_position():
    rng = np.random.RandomState(5)
    scores = rng.randn(8, 48).astype(np.float32)
    scores[:, ::3] = 0.5                    # sixteen equal scores a row
    scores[1] = 0.25                        # a row of nothing but ties
    scores[2, :20] = 0.0
    scores[2, 5:9] = -0.0                   # -0 counts as 0
    scores[3] = -np.abs(scores[3])          # all negative
    lengths = np.asarray([48, 40, 30, 47, 5, 16, 17, 1], np.int32)
    valid = np.arange(48)[None, :] < lengths[:, None]
    for k in (16, 7, 1):
        mask = np.asarray(sparse.topk_mask(
            jnp.asarray(scores), jnp.asarray(valid),
            jnp.full((8,), k, jnp.int32)))
        for r in range(8):
            assert set(np.nonzero(mask[r])[0].tolist()) == _exact(
                scores[r], lengths[r], k), (k, r)
    # row 1: sixteen of forty equal scores are the first sixteen positions
    assert np.nonzero(mask[1])[0].tolist() == [0]
    tables = np.arange(1, 49).reshape(8, 6).astype(np.int32)
    chosen, addresses, counts = (np.asarray(a) for a in sparse.select_tokens(
        jnp.asarray(scores), jnp.asarray(lengths), jnp.asarray(tables),
        topk=TOPK, page_size=PS, pool_pages=49))
    assert counts.tolist() == np.minimum(lengths, TOPK).tolist()
    for r in range(8):
        c = counts[r]
        assert set(chosen[r, :c].tolist()) == _exact(scores[r], lengths[r],
                                                     TOPK)
        assert (np.diff(chosen[r, :c]) > 0).all()       # in position order
        np.testing.assert_array_equal(
            addresses[r, :c],
            tables[r][chosen[r, :c] // PS] * PS + chosen[r, :c] % PS)
    assert chosen[1, :16].tolist() == list(range(16))


def test_a_pages_id_is_exact_up_to_the_last_page_of_the_largest_pool():
    """A page's id goes through a bfloat16 matmul in two halves of eight
    bits: exact for ids under 65,536, so a larger pool is refused."""
    rng = np.random.RandomState(6)
    scores = jnp.asarray(rng.randn(3, 6 * PS), jnp.float32)
    lengths = jnp.asarray([48, 20, 7], jnp.int32)
    tables = np.asarray([[65535, 65280, 255, 256, 257, 32768],
                         [65534, 1, 511, 512, 0, 0],
                         [40000, 0, 0, 0, 0, 0]], np.int32)
    chosen, addresses, counts = (np.asarray(a) for a in sparse.select_tokens(
        scores, lengths, jnp.asarray(tables), topk=TOPK, page_size=PS,
        pool_pages=1 << 16))
    for r in range(3):
        c = counts[r]
        np.testing.assert_array_equal(
            addresses[r, :c],
            tables[r][chosen[r, :c] // PS] * PS + chosen[r, :c] % PS)
    assert addresses.max() >= 65535 * PS
    with pytest.raises(ValueError, match="65,536"):
        sparse.select_tokens(scores, lengths, jnp.asarray(tables), topk=TOPK,
                             page_size=PS, pool_pages=(1 << 16) + 1)


# -- the engine against the reference -------------------------------------------

def _through_the_cache(engine, prompt, steps):
    row = engine.pool.admit_row(len(prompt), steps + 1,
                                engine.max_pages_per_seq)
    first, logits = engine.prefill_logits(prompt, row.table)
    rows, toks = [logits], [first]
    for k in range(steps):
        row.advance(len(prompt) + k)
        nxt, logits = engine.decode_logits(
            np.asarray(toks[-1:], np.int32),
            np.asarray([len(prompt) + k], np.int32), row.table[None])
        rows.append(logits[0])
        toks.append(int(nxt[0]))
    row.release()
    return np.stack(rows), toks


def _reference_logits(ref, params, config, prompt, toks, steps, pad=64):
    seq = np.zeros((pad,), np.int32)
    seq[:len(prompt) + steps] = list(prompt) + toks[:steps]
    return np.asarray(ref.forward(params, seq, np.int32(len(prompt) - 1),
                                  cfg=config, rows=steps + 1)[0])


@pytest.mark.parametrize("n,steps", [(1, 3), (5, 8), (15, 4), (16, 5),
                                     (17, 5), (40, 6), (64, 3)],
                         ids=lambda v: str(v))
def test_prefill_then_decode_equals_the_reference(built, config, ref, n,
                                                  steps):
    """Prompts under, at and over topk (16), shorter than their bucket and
    at its edge, decode steps that cross topk and pages."""
    engine, params, spec = built
    prompt = np.random.RandomState(n).randint(0, 256, n).tolist()
    got, toks = _through_the_cache(engine, prompt, steps)
    want = _reference_logits(ref, params, config, prompt, toks, steps, 128)
    assert np.max(np.abs(got - want)) < 2e-5
    engine.pool.check_consistency(expect_all_free=True)


@pytest.fixture(scope="module")
def blocked(config, runner):
    """The same model with prompts long enough for the blocked prefill:
    blocks of 8 queries beyond 16 positions, so that a prompt's first two
    blocks keep all they see and the later ones select."""
    old = (serving_model._DENSE_PREFILL_MAX, serving_model._PREFILL_BLOCK)
    serving_model._DENSE_PREFILL_MAX, serving_model._PREFILL_BLOCK = 16, 8
    try:
        engine, params, spec, _ = runner.build_engine(config, 3)
    finally:
        (serving_model._DENSE_PREFILL_MAX,
         serving_model._PREFILL_BLOCK) = old
    yield engine, params
    engine.close()


@pytest.mark.parametrize("n", [20, 32, 41, 64])
def test_the_blocked_prefill_equals_the_reference(blocked, config, ref, n):
    engine, params = blocked
    prompt = np.random.RandomState(100 + n).randint(0, 256, n).tolist()
    got, toks = _through_the_cache(engine, prompt, 3)
    want = _reference_logits(ref, params, config, prompt, toks, 3, 128)
    assert np.max(np.abs(got - want)) < 2e-5


def test_with_topk_over_every_length_the_layer_is_full_attention(config,
                                                                 runner):
    """The same weights served with topk = max_seq_len and served with no
    indexer at all (the existing full-attention path) give the same
    logits."""
    spec = runner.spec_from_config(config)
    params = init_params(spec, 5)
    import dataclasses
    all_of_it = dataclasses.replace(spec, sparse_topk=spec.max_seq_len)
    dense = dataclasses.replace(spec, sparse_topk=0, index_heads=0,
                                index_head_size=0)
    cfg = ServeConfig.from_dict(config["serve"])
    prompt = np.random.RandomState(9).randint(0, 256, 37).tolist()
    rows = []
    for s in (all_of_it, dense):
        engine = ServingEngine(
            s, {k: v for k, v in params.items()
                if s.sparse_topk or ".idx." not in k}, cfg)
        rows.append(_through_the_cache(engine, prompt, 4)[0])
        engine.close()
    assert np.max(np.abs(rows[0] - rows[1])) < 2e-5


def test_rows_joining_and_leaving_decode_as_each_row_alone(built):
    """Continuous batching: five requests of unlike lengths and answers
    through the scheduler (rows join as slots free, leave as they finish)
    give the tokens each gives alone through the cache."""
    engine, _, _ = built
    rng = np.random.RandomState(11)
    prompts = [rng.randint(0, 256, n).tolist() for n in (3, 40, 17, 9, 30)]
    news = [8, 3, 6, 8, 2]
    streams = [engine.scheduler.submit(p, max_new_tokens=m)
               for p, m in zip(prompts, news)]
    engine.scheduler.drain()
    for p, m, st in zip(prompts, news, streams):
        assert st.result(timeout=60) == _through_the_cache(engine, p,
                                                           m - 1)[1]
    engine.pool.check_consistency(expect_all_free=True)


def test_the_selection_program_is_the_decode_step_and_what_it_selected(built):
    """The scheduler's decode programs carry no output for a check; the
    largest bucket's twin does, computes the same step to the bit and
    leaves the pools as the step left them."""
    engine, _, spec = built
    assert engine.compiled_programs == 3 + 2 + 1
    assert set(engine.stats["program_bytes"]) >= {"serve_decode_b4_selection"}
    prompt = np.random.RandomState(12).randint(0, 256, 30).tolist()
    row = engine.pool.admit_row(30, 4, engine.max_pages_per_seq)
    first, _ = engine.prefill_logits(prompt, row.table)
    row.advance(30)
    step = (np.asarray([first], np.int32), np.asarray([30], np.int32),
            row.table[None])
    nxt, logits = engine.decode_logits(*step)
    held = [np.asarray(a).copy() for a in engine.pool.state()]
    same, again, positions, scores = engine.decode_selection(*step)
    for a, b in zip(held, engine.pool.state()):
        np.testing.assert_array_equal(a, np.asarray(b))
    row.release()
    np.testing.assert_array_equal(nxt, same)
    np.testing.assert_array_equal(logits, again)
    assert positions.shape == (2, 1, TOPK) and len(scores) == 2
    for l in range(2):
        assert scores[l].shape == (1, engine.max_pages_per_seq
                                   * engine.config.page_size)
        assert set(positions[l, 0].tolist()) == _exact(scores[l][0], 31,
                                                       TOPK)
    # the step's own outputs: nothing of the selection among them
    outs = jax.eval_shape(
        lambda *a: serving_model.decode_step(
            spec, *a, page_size=engine.config.page_size,
            index_pool=engine.pool.index_pool),
        engine._params, engine.pool.kv_pool, None,
        np.zeros((2,), np.int32), np.zeros((2,), np.int32),
        np.zeros((2, engine.max_pages_per_seq), np.int32))
    assert len(outs) == 2 + 3       # two pools, tokens, logits, counts


# -- the pool, the counters, the costs --------------------------------------------

def test_index_pages_are_allocated_reserved_and_returned_with_the_kv_pages():
    pool = PagePool(layers=2, pages=64, page_size=PS, heads=2, head_dim=16,
                    index_dim=8)
    assert pool.index_pool.shape == (2, 64, 8, PS)
    assert [a.shape for a in pool.state()] == [(2, 64 * PS, 4, 16),
                                               (2, 64, 8, PS)]
    assert pool.snapshot()["index_pages"] == 64
    assert pool.table_shape(9) == (9,)      # one table addresses all three
    rng = np.random.RandomState(13)
    held, refused = [], 0
    for _ in range(200):
        if held and rng.rand() < 0.45:
            held.pop(rng.randint(len(held))).release()
            continue
        n, more = int(rng.randint(1, 60)), int(rng.randint(1, 30))
        row = pool.admit_row(n, more, 16)
        if row is None:
            refused += 1
            continue
        for pos in range(n, n + int(rng.randint(0, more + 1))):  # decode on
            row.advance(pos)
        held.append(row)
        pool.check_consistency()
    assert refused and held
    for row in held:
        row.release()
    pool.check_consistency(expect_all_free=True)
    # the programs' outputs go back in the order they came
    kv, ix = pool.state()
    pool.swap(kv, np.ones(ix.shape, np.float32))
    assert float(pool.index_pool[0, 0, 0, 0]) == 1.0
    with pytest.raises(ValueError, match="index pool"):
        pool.swap(kv)
    with pytest.raises(ValueError, match="int8"):
        PagePool(layers=1, pages=4, page_size=4, heads=1, head_dim=8,
                 dtype=jnp.int8, scale_pages=True, index_dim=8)


def test_only_a_pool_beside_an_index_pool_is_token_major():
    """Learned sparse attention's K and V are one token-major pool; every
    other pool's shapes are as they were."""
    assert pool_shapes(2, 10, 16, 4, 8) == ((2, 10, 16, 32), (2, 10, 16, 4))
    assert pool_shapes(2, 10, 16, 4, 8, token_major=True) == (
        (2, 160, 8, 8), None)
    plain = PagePool(layers=2, pages=10, page_size=16, heads=4, head_dim=8)
    assert not plain.token_major and plain.snapshot()["layout"] == \
        "page_major"
    assert [a.shape for a in plain.state()] == [(2, 10, 16, 32)] * 2
    sparse_pool = PagePool(layers=2, pages=10, page_size=16, heads=4,
                           head_dim=8, index_dim=8)
    assert sparse_pool.token_major
    assert sparse_pool.snapshot()["layout"] == "token_major"
    assert sparse_pool.kv_pool.shape == (2, 160, 8, 8)


def test_the_page_major_view_reads_what_page_major_pools_hold(config,
                                                              runner):
    """A prefill's and two decode steps' writes into the token-major pool,
    read through `pool.k_pool[at]` / `pool.v_pool[at]`, are what the same
    model's writes make in page-major pools: the model without its indexer,
    over contexts no longer than topk, where the sparse layer is full
    attention."""
    import dataclasses
    spec = runner.spec_from_config(config)
    dense = dataclasses.replace(spec, sparse_topk=0, index_heads=0,
                                index_head_size=0)
    params = init_params(spec, 5)
    dense_params = {k: v for k, v in params.items() if ".idx." not in k}
    ps, pages = config["serve"]["page_size"], 12
    pool = PagePool(layers=spec.layers, pages=pages, page_size=ps,
                    heads=spec.n_kv_heads, head_dim=spec.head_dim,
                    index_dim=spec.index_head_size)
    shape, _ = pool_shapes(spec.layers, pages, ps, spec.n_kv_heads,
                           spec.head_dim)
    k_pool = v_pool = jnp.zeros(shape, jnp.float32)
    table = np.asarray([7, 2, 9, 4, 0, 0, 0, 0], np.int32)
    tokens = np.zeros((16,), np.int32)
    tokens[:10] = np.random.RandomState(14).randint(0, 256, 10)
    out = serving_model.prefill_step(spec, params, *pool.state()[:1], None,
                                     tokens, np.int32(10), table,
                                     page_size=ps, index_pool=pool.index_pool)
    pool.swap(*out[:2])
    k_pool, v_pool = serving_model.prefill_step(
        dense, dense_params, k_pool, v_pool, tokens, np.int32(10), table,
        page_size=ps)[:2]
    token = np.asarray([out[2]], np.int32)
    for pos in (10, 11):
        step = (token, np.asarray([pos], np.int32), table[None])
        out = serving_model.decode_step(spec, params, pool.kv_pool, None,
                                        *step, page_size=ps,
                                        index_pool=pool.index_pool)
        pool.swap(*out[:2])
        k_pool, v_pool = serving_model.decode_step(
            dense, dense_params, k_pool, v_pool, *step, page_size=ps)[:2]
        token = np.asarray(out[2], np.int32)
    at = (np.arange(spec.layers)[:, None], table[None, :3])
    for view, want in ((pool.k_pool, k_pool), (pool.v_pool, v_pool)):
        got = np.asarray(view[at])
        assert got.shape == (spec.layers, 3, ps,
                             spec.n_kv_heads * spec.head_dim)
        assert np.abs(got[:, :, :ps]).sum() > 0
        np.testing.assert_allclose(got, np.asarray(want[at]), atol=1e-5)


def test_the_scheduler_counts_what_is_scored_and_selected(built):
    engine, _, spec = built
    before = dict(engine.scheduler.stats)
    prompts = [[1] * 10, [2] * 30]
    engine.generate(prompts, max_new_tokens=4)
    got = {k: engine.scheduler.stats[k] - before[k]
           for k in ("sparse_tokens_scored", "sparse_tokens_selected")}
    # three decode steps a request: contexts n + 1, n + 2, n + 3
    contexts = [n + k for n in (10, 30) for k in (1, 2, 3)]
    assert got["sparse_tokens_scored"] == spec.layers * sum(contexts)
    assert got["sparse_tokens_selected"] == spec.layers * sum(
        min(c, TOPK) for c in contexts)
    snap = engine.healthz()
    assert snap["sparse_tokens_scored"] >= got["sparse_tokens_scored"]
    assert snap["kv"]["index_pages"] == snap["kv"]["pages"]
    assert snap["kv"]["layout"] == "token_major"


def test_the_sparse_counters_reach_the_registry_by_kind(built):
    from paddle_tpu.observability.metrics import get_registry
    from paddle_tpu.observability.telemetry import get_telemetry
    engine, _, _ = built
    was = get_telemetry().enabled
    get_telemetry().enable()
    try:
        engine.generate([[3] * 20], max_new_tokens=3)
        series = get_registry().snapshot()[
            "pt_serve_sparse_tokens_total"]["series"]
        assert {"kind=scored", "kind=selected"} <= set(series)
        assert series["kind=scored"] >= series["kind=selected"] > 0
        calls = get_registry().snapshot()["pt_pallas_calls_total"]["series"]
        assert any("paged_attention_sparse" in k for k in calls)
        assert any("paged_index_scores" in k for k in calls)
    finally:
        if not was:
            get_telemetry().disable()


KEYE = {"layers": 4, "heads": 32, "kv_heads": 4, "head_dim": 128,
        "hidden": 2048, "vocab_size": 151936, "experts": 128,
        "experts_per_token": 8, "expert_width": 768, "sparse_topk": 2048,
        "index_heads": 16, "index_head_size": 64, "kv_itemsize": 2,
        "weight_itemsize": 2}


def test_costs_sparse_counts_what_hand_arithmetic_counts():
    costs = _load(".", "costs_sparse")
    # one layer, 24 rows that see 480,000 positions in all
    flops, nbytes = costs.index_decode(480_000, 24, 16, 64, 2)
    assert flops == 2 * 480_000 * 16 * 65
    assert nbytes == (480_000 * 64 + 24 * 16 * 64) * 2
    flops, nbytes = costs.sparse_decode(24 * 2048, 24, 32, 4, 128, 2)
    assert flops == 4 * 24 * 2048 * 4096
    assert nbytes == 2 * 24 * 2048 * 512 * 2 + 2 * 24 * 4096 * 2
    assert nbytes == 100_663_296 + 393_216        # ISSUE 34's 100 MB
    assert costs.index_params(KEYE) == 2048 * (1024 + 64 + 16) == 2_260_992
    # a prompt of 3,000: position t scores t + 1 keys, attends min(.., 2048)
    scored, attended = costs.prompt_keys(3000, 2048)
    assert scored == sum(range(1, 3001))
    assert attended == sum(min(t, 2048) for t in range(1, 3001))
    assert costs.prompt_keys(100, 2048) == (5050, 5050)
    layer = (2 * 2048 * 4096 + 2 * 2048 * 512 + 2048 * 128
             + 8 * 3 * 2048 * 768 + 2_260_992)
    assert costs.token_flops(KEYE, 0, 0, 10, 2) == 2 * (
        10 * 4 * layer + 2 * 2048 * 151936)
    assert costs.token_flops(KEYE, 1000, 500, 0, 0) == 4 * (
        2 * 1000 * 16 * 65 + 4 * 500 * 4096)
    assert costs.serve_flops(KEYE, [3000], [(24, 480_000, 49_152)]) == (
        costs.token_flops(KEYE, scored, attended, 3000, 1)
        + costs.token_flops(KEYE, 480_000, 49_152, 24, 24))


# -- the benchmark's side ---------------------------------------------------------

def test_the_runners_check_tells_the_wrong_references(built, config, runner):
    """The same engine and rows against the reference as it is and against
    the forms the limits have to tell apart; at this size and in float32
    every wrong one is far outside."""
    engine, params, _ = built
    rng = np.random.RandomState(14)
    # the last sees at most topk positions through its last decode step
    prompts = [rng.randint(0, 256, n).tolist()
               for n in (40, 24, 56, TOPK - runner.STEPS)]
    keep = runner.compared(prompts, 4)
    assert keep == [0, 1, 2, 3]
    driven, facts = runner.drive_rows(engine, prompts, keep)
    assert facts["selection_logit_diff"] == 0.0
    found = runner.compare_rows(params, config, prompts, driven, facts)
    assert runner.within_limits(found), found
    assert found["logit_err"] < 1e-5 and found["select_rule_diff"] == 0
    assert found["cache_err"] < 1e-5 and found["rows_within_topk"] == 1
    assert 0 < found["cache_err_layers"] < 1e-5
    assert 0 < found["index_score_err_layers"] < 1e-5
    assert found["select_diff_share"] == 0 == found["routing_diff_share"]

    def wrong(**variant):
        return runner.compare_rows(params, config, prompts, driven, **variant)

    # the discrete choice: any other rule differs in some pair, and the
    # limit on that is none
    for variant in ({"topk": TOPK - 1}, {"select": "page", "page": 4},
                    {"window": TOPK}):
        got = wrong(**variant)
        assert got["select_rule_diff"] > 0 and not runner.within_limits(got)
    # the arithmetic: a thousand times the right reference's reading
    assert wrong(qk_norm=False)["logit_err"] > 1e-3
    got = wrong(index_key_dtype=jnp.float8_e4m3fn)
    assert got["index_score_err"] > 1e-3 and got["select_diff_share"] > 0
    got = wrong(kv_dtype=jnp.float8_e4m3fn)
    assert got["logit_err"] > 1e-3 and got["cache_err"] > runner.CACHE_RTOL
    assert not runner.within_limits(got)
    # ... planted in the last layer alone: layer 0's numbers see nothing,
    # the numbers of the row that selects all it sees tell the layer
    for wrong_dtype in ("index_key_dtype", "kv_dtype"):
        got = wrong(only_layer=1, **{wrong_dtype: jnp.float8_e4m3fn})
        assert got["cache_err"] < 1e-5 and got["index_score_err"] < 1e-5
        assert got["cache_err_quantiles_by_layer"][0][-1] < 1e-5
        assert got["cache_err_layers"] > runner.CACHE_LAYERS_RTOL
        assert not runner.within_limits(got)
    assert got["index_score_err_layers"] < 1e-5     # K and V: not the scores
    got = wrong(only_layer=1, index_key_dtype=jnp.float8_e4m3fn)
    assert got["index_score_err_layers"] > 1e-4     # 7e-8 as it should be
    # a decode step that leaves its indexer key unwritten, in that layer
    assert found["cache_tail_err_layers"] < 1e-5
    got = wrong(only_layer=1, index_keys_written="prompt")
    assert got["cache_tail_err_layers"] == 1.0 and got["cache_err"] < 1e-5
    assert not runner.within_limits(got)
    assert wrong(round_to=jnp.float8_e4m3fn)["logit_err"] > 1e-2


def test_the_checks_rows_hold_one_that_crosses_a_page_and_one_within_topk(
        runner):
    rng = np.random.RandomState(15)
    requests = [{"prompt": rng.randint(0, 256, n).tolist()}
                for n in (40, 41, 56, 33, 27)]
    prompts = runner.pick_rows(requests, PS, 4, TOPK)
    assert [len(p) for p in prompts] == [
        40, 41 - (41 + 2) % PS, TOPK - runner.STEPS, 33]
    assert (len(prompts[1]) + 2) % PS == 0
    assert prompts[2] == requests[2]["prompt"][:TOPK - runner.STEPS]
    # by length: the shortest is the one within topk
    assert 2 in runner.compared(prompts, 3)


def test_the_configuration_holds_the_catalogs_keys_and_fits(runner):
    with open(os.path.join(BENCH, "configs",
                           "keye-vl2-30b-a3b-serve.json")) as f:
        cfg = json.load(f)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        row = next(json.loads(l) for l in open(catalog)
                   if "Keye-VL-2.0-30B-A3B" in l)
        differ = [k for k, v in row["config"].items() if cfg.get(k) != v]
        assert differ == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 4 and len(cfg["reduced"]) == 1
    spec = runner.spec_from_config(cfg)
    assert (spec.layers, spec.heads, spec.n_kv_heads, spec.head_dim) == \
        (4, 32, 4, 128)
    assert (spec.sparse_topk, spec.index_heads, spec.index_head_size,
            spec.qk_norm) == (2048, 16, 64, True)
    assert (spec.experts, spec.experts_per_token, spec.expert_width) == \
        (128, 8, 768)
    assert ModelSpec.from_dict(json.loads(json.dumps(spec.to_dict()))) == spec
    shapes = jax.eval_shape(lambda: init_params(spec, 0, jnp.bfloat16))
    n = sum(int(np.prod(a.shape)) for a in shapes.values())
    assert abs(n / 1e6 - 3124) < 3          # ISSUE 34: 3.12 B parameters
    serve = cfg["serve"]
    assert serve["prefill_buckets"][-1] + serve["max_new_tokens"] == \
        serve["max_seq_len"] == 40960
    with pytest.raises(ValueError, match="sparse_topk"):
        ModelSpec(sparse_topk=8)
    with pytest.raises(ValueError, match="sparse_topk"):
        ModelSpec(layers=2, layer_types=("sliding", "full"), window=4,
                  sparse_topk=8, index_heads=2, index_head_size=8)


def test_the_new_metrics_are_the_cells_and_read_nothing_elsewhere():
    """PR 34's six metrics: entries at the end, a reader each, nothing
    read (no exception) from a run of a program that has no such scope,
    counter or model fact: the parent's, or another configuration's."""
    run_py = _load(".", "run")
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    # the Falcon-H1 and Kimi-Linear cells after it
    assert spec["workloads"][-3] == {
        "name": CELL, "config": "keye-vl2-30b-a3b-serve",
        "traffic": "longctx-reasoning-backlog", "chips": 1,
        "why": spec["workloads"][-3]["why"]}
    assert len(spec["workloads"][-3]["why"]) <= 200
    # the traffic ISSUE 34 fixed: no lever pulled
    mix = json.load(open(os.path.join(
        BENCH, "traffic", "longctx-reasoning-backlog.json")))
    assert mix["arrival"] == {"kind": "closed", "callers": 24, "pool": 24,
                              "cycles": 4, "ramp_s": 14.0}
    assert (mix["prompt_len"]["lo"], mix["prompt_len"]["hi"]) == (8192, 32768)
    assert (mix["new_tokens"]["lo"], mix["new_tokens"]["hi"]) == (2048, 8192)
    assert (mix["block"], mix["trace_s"]) == (8, 4.0)
    assert spec["configs"][-3]["reduced"] == ["num_hidden_layers"]
    # 2 + 10 + 3 + 6 metrics added after them
    assert [m["name"] for m in spec["per_layer"][-27:-21]] == NEW_METRICS
    for m in spec["per_layer"][-27:-21]:
        assert m["workloads"] == [CELL]
        assert m["moves"] == "serve_tokens_per_s"
    empty = {"trace": None, "values": {}, "counters": {}, "spans": {},
             "peak": None, "model": {}}
    other = dict(empty, counters={"prefill_tokens": 9, "admitted": 1,
                                  "decode_tokens": 3},
                 model={"layers": 12, "heads": 32, "kv_heads": 4,
                        "head_dim": 128, "hidden": 2304, "experts": 64},
                 seconds=4.0, chips=1, t_window=1.0,
                 decode_rows=[(1.5, 1.6, 3, 30, 30, 30)],
                 peak={"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9})
    for name in NEW_METRICS:
        assert run_py.read_layer_metric(name, dict(empty)) is None, name
        assert run_py.read_layer_metric(name, dict(other)) is None, name


def test_the_counter_metrics_read_the_window():
    run_py = _load(".", "run")
    costs = _load(".", "costs_sparse")
    run = {"trace": None, "model": KEYE, "seconds": 40.0, "chips": 1,
           "peak": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9},
           "t_window": 100.0,
           "counters": {"sparse_tokens_scored": 4 * 480_000,
                        "sparse_tokens_selected": 4 * 49_152},
           # a prefill and a step before the window are not its work
           "prefill_rows": [(90.0, 90.5, 9000), (101.0, 101.6, 20000)],
           "decode_rows": [(99.0, 99.1, 24, 0, 480_000, 49_152)] + [
               (100.0 + k, 100.02 + k, 24, 0, 480_000, 49_152)
               for k in range(30)]}
    assert run_py.read_layer_metric("sparse_selected_pct", run) == \
        pytest.approx(10.24)
    want = 100.0 * costs.serve_flops(
        KEYE, [20000], [(24, 480_000, 49_152)] * 30) / (40.0 * 197e12)
    assert run_py.read_layer_metric("serve_mfu_pct.keyevl2", run) == \
        pytest.approx(want)
    assert 0 < want < 100


def test_the_cells_rehearsal_on_the_cpu():
    """`tiny-keyevl2-serve.tiny-closed --allow-cpu --trace 1`: the runner,
    the check and the window at the tiny widths, ending in a line marked
    as a rehearsal with `correct` true."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "tiny-keyevl2-serve.tiny-closed", "--seconds", "2", "--seed",
         "2147483659", "--trace", "1", "--allow-cpu"],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert "rehearsal" in line and line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    notes = line["notes"]
    assert notes["logit_err"] < 1e-4 and notes["logit_err_free"] < 1e-4
    assert notes["select_rule_diff"] == 0 and notes["kv_consistent"]
    assert notes["window_compiles"] == 0
    assert notes["kv"]["index_pages"] == notes["kv"]["pages"]
