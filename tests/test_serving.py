"""AOT serving engine: paged KV-cache, zero-compile request path,
continuous batching, weight swap, and the HTTP front end.

The load-bearing guarantees under test:

 - the page-pool allocator never double-books, never leaks, and refuses
   admission rather than OOM-ing mid-decode;
 - after engine warmup the request path performs ZERO XLA compiles
   (the sentinel that trips /healthz in production must stay at 0 for
   every in-ladder shape here);
 - a sequence decoded inside a continuous batch — with neighbours
   joining and leaving — produces BIT-IDENTICAL tokens to the same
   sequence decoded alone (row-independent decode math).
"""
import gc
import json
import os
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.ops.paged_attention import (
    paged_attention, paged_attention_reference)
from paddle_tpu.serving import (
    EngineSaturated, KVPoolExhausted, ModelSpec, NULL_PAGE, PagePool,
    ServeConfig, ServingEngine, init_params, is_served_model_dir,
    load_engine, save_served_model)

SPEC = ModelSpec(vocab_size=64, hidden=32, layers=2, heads=2,
                 max_seq_len=64)
# one small bucket per family keeps the AOT build fast; decode bucket 4
# still exercises padding rows and join/leave churn
CFG = ServeConfig(decode_buckets=(4,), prefill_buckets=(16,),
                  kv_pages=32, page_size=4, max_inflight=16,
                  max_new_tokens=8)


@pytest.fixture(scope="module")
def engine():
    eng = ServingEngine(SPEC, init_params(SPEC, seed=0), CFG)
    yield eng
    eng.close()


# -- page pool ---------------------------------------------------------------

def _pool(pages=8, page_size=4):
    return PagePool(layers=1, pages=pages, page_size=page_size,
                    heads=1, head_dim=4)


def test_pool_alloc_free_reuse():
    pool = _pool(pages=8)
    a = pool.alloc(3)
    assert len(a) == 3 and NULL_PAGE not in a
    assert len(set(a)) == 3
    pool.free(a)
    b = pool.alloc(3)
    # LIFO free list: freed pages are reused before untouched ones
    assert set(b) == set(a)
    pool.free(b)
    pool.check_consistency()
    assert pool.stats["allocs"] == 6 and pool.stats["frees"] == 6


def test_pool_exhaustion_and_double_free():
    pool = _pool(pages=4)  # 3 usable (page 0 reserved as null)
    a = pool.alloc(3)
    with pytest.raises(KVPoolExhausted):
        pool.alloc(1)
    assert pool.stats["alloc_failures"] == 1
    with pytest.raises(ValueError):
        pool.free([NULL_PAGE])
    pool.free(a)
    with pytest.raises(ValueError):
        pool.free([a[0]])  # double free
    pool.check_consistency()


def test_pool_reservation_admission_control():
    pool = _pool(pages=8)  # 7 usable
    assert pool.can_admit(7) and not pool.can_admit(8)
    pool.reserve(5)
    assert pool.headroom() == 2
    assert not pool.can_admit(3)
    with pytest.raises(KVPoolExhausted):
        pool.reserve(3)
    assert pool.stats["reserve_refusals"] == 1
    # reserved allocs draw down the promise, not fresh headroom
    got = pool.alloc(2, reserved=True)
    assert pool.headroom() == 2
    pool.free(got)
    pool.release_reservation(3)
    assert pool.headroom() == 7
    pool.check_consistency()


def test_pool_fragmentation_interleaved_lifetimes():
    # interleaved alloc/free of different sizes must never lose a page
    pool = _pool(pages=16)
    rng = np.random.RandomState(0)
    live = []
    for _ in range(200):
        if live and (rng.rand() < 0.5 or pool.headroom() < 4):
            pool.free(live.pop(rng.randint(len(live))))
        else:
            live.append(pool.alloc(int(rng.randint(1, 4))))
        pool.check_consistency()
    for pages in live:
        pool.free(pages)
    assert pool.headroom() == pool.usable_pages
    assert pool.stats["high_watermark"] <= pool.usable_pages


def test_pool_pages_needed_and_padded_table():
    pool = _pool(page_size=4)
    assert pool.pages_needed(0) == 1
    assert pool.pages_needed(4) == 1
    assert pool.pages_needed(5) == 2
    t = pool.null_padded_table([3, 5], 4)
    assert t.tolist() == [3, 5, NULL_PAGE, NULL_PAGE]
    assert t.dtype == np.int32


# -- paged attention ---------------------------------------------------------

def _paged_case(layers=3, garbage=1e4):
    """Whole ``(L, P, ps, H*D)`` pools, page tables padded with the null
    page, a part-filled last page per row, and large finite garbage in
    every slot a row must not read: past its length, in the null page,
    and in the pages of the other layers' same ids it does not own."""
    rng = np.random.RandomState(1)
    b, h, d, ps, maxp = 3, 2, 8, 4, 5
    pages = 1 + b * maxp
    lengths = np.asarray([1, 7, 18], np.int32)
    q = rng.randn(b, h, d).astype(np.float32)
    k = (garbage * rng.randn(layers, pages, ps, h * d)).astype(np.float32)
    v = (garbage * rng.randn(layers, pages, ps, h * d)).astype(np.float32)
    own = rng.permutation(np.arange(1, pages)).reshape(b, maxp)
    tables = np.zeros((b, maxp), np.int32)          # tail: the null page
    ctx = {}
    for r in range(b):
        n = int(lengths[r])
        used = -(-n // ps)
        tables[r, :used] = own[r, :used]
        kc = rng.randn(layers, n, h * d).astype(np.float32)
        vc = rng.randn(layers, n, h * d).astype(np.float32)
        for t in range(n):
            k[:, tables[r, t // ps], t % ps] = kc[:, t]
            v[:, tables[r, t // ps], t % ps] = vc[:, t]
        ctx[r] = (kc, vc)
    return q, k, v, tables, lengths, ctx, (h, d)


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_paged_attention_whole_pool_matches_reference(layer):
    q, k, v, tables, lengths, ctx, (h, d) = _paged_case()
    args = [jnp.asarray(a) for a in (q, k, v, tables, lengths)]
    ref = np.asarray(paged_attention_reference(*args, layer=layer))
    out = np.asarray(paged_attention(*args, layer=layer, use_pallas=True,
                                     interpret=True))
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    # and both against plain attention over each row's own context: the
    # garbage in masked slots, the null page and other layers is not read
    for r, (kc, vc) in ctx.items():
        kr = kc[layer].reshape(-1, h, d)
        vr = vc[layer].reshape(-1, h, d)
        sc = np.einsum("hd,chd->hc", q[r], kr) / np.sqrt(d)
        w = np.exp(sc - sc.max(-1, keepdims=True))
        w /= w.sum(-1, keepdims=True)
        want = np.einsum("hc,chd->hd", w, vr)
        np.testing.assert_allclose(out[r], want, atol=2e-4, rtol=2e-4)


def test_paged_attention_refuses_a_pool_of_the_wrong_width():
    q, k, v, tables, lengths, _, _ = _paged_case(layers=1)
    # 12 lanes are no whole number of heads of 8 (8 lanes would be one
    # KV head for the two query heads: a grouped pool, and served)
    with pytest.raises(ValueError, match="lanes"):
        paged_attention(jnp.asarray(q), jnp.asarray(k[..., :12]),
                        jnp.asarray(v[..., :12]), jnp.asarray(tables),
                        jnp.asarray(lengths), layer=0, use_pallas=True,
                        interpret=True)


# -- the pools' layout: prefill, decode, reuse -------------------------------

def _plain_logits(params, tokens):
    """The served decoder as one full causal forward, no cache: logits
    of every position.  float64 numpy and nothing of jax: a compile here
    would trip the module engine's armed sentinel."""
    p = {n: np.asarray(a, np.float64) for n, a in params.items()}
    s, hd = len(tokens), SPEC.head_dim

    def ln(x, w, b):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / np.sqrt(var + 1e-5) * w + b

    h = p["embed"][np.asarray(tokens)] + p["pos"][:s]
    causal = np.tril(np.ones((s, s), bool))
    for i in range(SPEC.layers):
        x = ln(h, p[f"h{i}.ln1.w"], p[f"h{i}.ln1.b"])
        q, k, v = ((x @ p[f"h{i}.attn.w{c}"]).reshape(s, SPEC.heads, hd)
                   for c in "qkv")
        att = np.einsum("ihd,jhd->hij", q, k) / np.sqrt(hd)
        att = np.where(causal[None], att, -np.inf)
        w = np.exp(att - att.max(-1, keepdims=True))
        w /= w.sum(-1, keepdims=True)
        h = h + np.einsum("hij,jhd->ihd", w, v).reshape(s, -1) \
            @ p[f"h{i}.attn.wo"]
        x = ln(h, p[f"h{i}.ln2.w"], p[f"h{i}.ln2.b"])
        m = x @ p[f"h{i}.mlp.w1"] + p[f"h{i}.mlp.b1"]
        m = 0.5 * m * (1 + np.tanh(np.sqrt(2 / np.pi)
                                   * (m + 0.044715 * m ** 3)))
        h = h + m @ p[f"h{i}.mlp.w2"] + p[f"h{i}.mlp.b2"]
    return ln(h, p["lnf.w"], p["lnf.b"]) @ p["embed"].T


def _run_through_programs(engine, prompt, steps, pages):
    """Prefill and ``steps`` decode steps through the engine's own
    programs and pools (the public ``prefill_logits`` /
    ``decode_logits``: the executables ``engine.prefill`` / ``decode``
    call); returns the logits row of each step and the tokens fed."""
    table = engine.pool.null_padded_table(pages, engine.max_pages_per_seq)
    n = len(prompt)
    nxt, logits = engine.prefill_logits(prompt, table)
    rows, toks = [logits], [nxt]
    for j in range(steps):
        nxt, logits = engine.decode_logits(
            np.asarray(toks[-1:], np.int32), np.asarray([n + j], np.int32),
            table[None])
        rows.append(logits[0])
        toks.append(int(nxt[0]))
    return np.stack(rows), toks


_PS = CFG.page_size


@pytest.mark.parametrize("n", [_PS - 1, _PS, _PS + 1,
                               CFG.prefill_buckets[0]])
def test_prefill_then_decode_equals_the_plain_forward(engine, n):
    """Prompt lengths one short of a page, a whole page, one over, and
    the bucket's edge; six decode steps, so writes cross a page edge."""
    rng = np.random.RandomState(10 + n)
    prompt = rng.randint(1, SPEC.vocab_size, size=n)
    steps = 6
    pages = engine.pool.alloc(engine.pool.pages_needed(n + steps))
    try:
        got, toks = _run_through_programs(engine, prompt, steps, pages)
    finally:
        engine.pool.free(pages)
    full = list(prompt) + toks[:steps]
    want = _plain_logits(engine._params, full)[n - 1:]
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    engine.pool.check_consistency()


def test_a_freed_page_is_reused_and_reads_only_its_new_owner(engine):
    pool = engine.pool
    rng = np.random.RandomState(5)
    first = rng.randint(1, SPEC.vocab_size, size=11)
    pages = pool.alloc(pool.pages_needed(len(first) + 4))
    _run_through_programs(engine, first, 4, pages)
    pool.free(pages)
    # LIFO free list: the second sequence gets the first one's pages,
    # still holding its K and V, and is one token shorter than a page
    # edge so the stale slots sit right behind its own
    second = rng.randint(1, SPEC.vocab_size, size=2 * _PS - 1)
    again = pool.alloc(pool.pages_needed(len(second) + 4))
    assert set(again) & set(pages)
    try:
        got, toks = _run_through_programs(engine, second, 4, again)
    finally:
        pool.free(again)
    want = _plain_logits(engine._params,
                         list(second) + toks[:4])[len(second) - 1:]
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    pool.check_consistency()


def test_prefill_writes_whole_pages_and_only_the_sequences_own(engine):
    """Pages wholly past the prompt go to the null page; the slots of
    the last page past the prompt are zeros; no other page changes."""
    pool = engine.pool
    n = _PS + 1                              # two pages, the second 1 / ps
    pages = pool.alloc(3)
    before = [np.asarray(a).copy() for a in (pool.k_pool, pool.v_pool)]
    try:
        engine.prefill(list(range(1, n + 1)),
                       pool.null_padded_table(pages, engine.max_pages_per_seq))
    finally:
        pool.free(pages)
    for was, now in zip(before, (pool.k_pool, pool.v_pool)):
        now = np.asarray(now)
        assert now.shape == (SPEC.layers, pool.pages, _PS, SPEC.hidden)
        assert np.all(now[:, pages[1], 1:] == 0)         # masked tail
        assert np.all(now[:, pages[0]] != 0) and np.all(now[:, pages[1], 0])
        untouched = [p for p in range(1, pool.pages) if p not in pages[:2]]
        np.testing.assert_array_equal(now[:, untouched], was[:, untouched])


# -- what each program keeps in memory ---------------------------------------

_BYTES_SPEC = ModelSpec(vocab_size=64, hidden=32, layers=2, heads=2,
                        max_seq_len=32)
_BYTES_ENGINES = {}


@pytest.fixture(scope="module", autouse=True)
def _drop_bytes_engines():
    """Their pools must not outlive the module: another file's census of
    live buffers (tests/test_memory.py) may run in this process next."""
    yield
    for eng in _BYTES_ENGINES.values():
        eng.close()
    _BYTES_ENGINES.clear()
    gc.collect()            # an engine and its programs are a cycle


def _bytes_engine(precision):
    """One engine a precision, with pools far larger than anything else
    a program touches, so a pool-sized temporary cannot hide."""
    if precision not in _BYTES_ENGINES:
        cfg = ServeConfig(decode_buckets=(2,), prefill_buckets=(16,),
                          kv_pages=8192, page_size=4, max_inflight=4,
                          max_new_tokens=4, precision=precision)
        _BYTES_ENGINES[precision] = ServingEngine(
            _BYTES_SPEC, init_params(_BYTES_SPEC, seed=0), cfg)
    return _BYTES_ENGINES[precision]


@pytest.mark.parametrize("kind", ["prefill", "decode"])
@pytest.mark.parametrize("precision", ["fp32", "bf16", "int8"])
def test_programs_alias_every_pool_and_hold_no_pool_sized_temporary(
        precision, kind):
    eng = _bytes_engine(precision)
    state = eng._kv_state()
    assert len(state) == (4 if precision == "int8" else 2)
    pool_bytes = int(state[0].nbytes)
    assert state[0].shape == (2, eng.pool.pages, 4, 32)
    sfx = "" if precision == "fp32" else f"_{precision}"
    name = {"prefill": "serve_prefill_s16", "decode": "serve_decode_b2"}[kind]
    got = eng.stats["program_bytes"][name + sfx]
    assert got == eng.healthz()["program_bytes"][name + sfx]
    # every pool parameter is aliased to its output ...
    assert got["alias"] == sum(int(a.nbytes) for a in state)
    assert got["argument"] >= got["alias"]
    # ... and nothing a tenth of one pool's size is held beside them.
    # (XLA's CPU backend has no bf16 scatter: it widens the whole pool
    # to f32 and back around each one, which the chip's compiler does
    # not; tests/test_serve_chip_compile.py holds bf16 to this there.)
    if precision != "bf16":
        assert got["temp"] < pool_bytes / 10, (got, pool_bytes)
    # the executable's own text says the same: one alias a pool
    exe = (eng._prefill_exe[16] if kind == "prefill"
           else eng._decode_exe[2])
    header = exe.as_text().split("\n", 1)[0]
    assert header.count("may-alias") + header.count("must-alias") \
        == len(state), header[:300]


def test_program_bytes_gauge_when_telemetry_is_on():
    from paddle_tpu import observability as obs
    assert not obs.get_telemetry().enabled
    obs.reset_registry()
    tel = obs.get_telemetry()
    tel.enable(compile_watch=False)
    try:
        eng = ServingEngine(SPEC, init_params(SPEC, seed=0), CFG)
        eng.close()
        series = obs.get_registry().snapshot()[
            "pt_serve_program_bytes"]["series"]
        assert len(series) == 3 * eng.compiled_programs
        assert sorted(eng.stats["program_bytes"]) == [
            "serve_decode_b4", "serve_prefill_s16"]
    finally:
        tel.enabled = False
        obs.reset_registry()


# -- engine: zero-compile request path ---------------------------------------

def test_engine_zero_compiles_after_warmup(engine):
    assert engine.unexpected_compiles == 0
    outs = engine.generate([[1, 2, 3], [4, 5, 6, 7, 8]],
                           max_new_tokens=6)
    assert len(outs) == 2 and all(len(o) == 6 for o in outs)
    # every in-ladder shape was AOT-compiled at load: still zero
    assert engine.unexpected_compiles == 0
    assert engine.healthz()["ok"]


def test_engine_out_of_ladder_shapes_refused(engine):
    with pytest.raises(ValueError):
        engine.prefill_bucket_for(CFG.prefill_buckets[-1] + 1)
    with pytest.raises(ValueError):
        engine.scheduler.submit(list(range(1, 40)))  # > prefill bucket
    with pytest.raises(ValueError):
        engine.scheduler.submit([])
    with pytest.raises(ValueError):
        engine.scheduler.submit([SPEC.vocab_size + 5])


def test_engine_kv_pages_returned_after_retire(engine):
    before = engine.pool.snapshot()
    engine.generate([[7, 8, 9]], max_new_tokens=4)
    after = engine.pool.snapshot()
    assert after["used_pages"] == before["used_pages"]
    assert after["reserved_pages"] == before["reserved_pages"]
    engine.pool.check_consistency()


# -- continuous batching: bit-identity ---------------------------------------

def test_continuous_batching_bit_identical_to_solo(engine):
    rng = np.random.RandomState(2)
    prompts = [rng.randint(1, SPEC.vocab_size,
                           size=rng.randint(2, 12)).tolist()
               for _ in range(7)]
    # solo: one request at a time — each decode step is a batch of one
    # sequence padded into the bucket
    solo = [engine.generate([p], max_new_tokens=8)[0] for p in prompts]
    # batched: all seven compete for a 4-wide decode bucket, so every
    # sequence sees neighbours join and leave mid-generation
    batched = engine.generate(prompts, max_new_tokens=8)
    assert batched == solo
    assert engine.unexpected_compiles == 0


def test_saturation_refusal(engine):
    sched = engine.scheduler
    streams = []
    try:
        with pytest.raises(EngineSaturated):
            for _ in range(CFG.max_inflight + 1):
                streams.append(sched.submit([1, 2], max_new_tokens=1))
    finally:
        sched.drain()
    for st in streams:
        st.result(timeout=30)


def test_kv_headroom_blocks_admission():
    # pool sized so the second request cannot reserve its worst case
    cfg = CFG.replace(kv_pages=8, max_new_tokens=8)  # 7 usable pages
    eng = ServingEngine(SPEC, init_params(SPEC, seed=0), cfg)
    try:
        # worst case per request: ceil((6+8)/4) = 4 pages → only one fits
        s1 = eng.scheduler.submit([1, 2, 3, 4, 5, 6], max_new_tokens=8)
        s2 = eng.scheduler.submit([1, 2, 3, 4, 5, 6], max_new_tokens=8)
        eng.scheduler.step()
        snap = eng.scheduler.snapshot()
        assert snap["active_sequences"] == 1
        assert snap["queue_depth"] == 1
        assert snap["refused_kv"] >= 1
        eng.scheduler.drain()
        # head-of-line request ran after the first retired its pages
        assert s1.result(timeout=30) == s2.result(timeout=30)
        assert eng.pool.snapshot()["used_pages"] == 0
    finally:
        eng.close()


# -- weight swap -------------------------------------------------------------

def test_install_weights_zero_downtime(engine):
    prompt = [3, 1, 4, 1, 5]
    base = engine.generate([prompt], max_new_tokens=6)[0]
    old_step = engine.weights_step
    try:
        # all-zero weights make every logit equal → greedy decode is
        # deterministically token 0, observable proof the swap landed
        zeros = {k: np.zeros_like(np.asarray(v))
                 for k, v in init_params(SPEC, seed=0).items()}
        engine.install_weights(zeros, step=9)
        assert engine.weights_step == 9
        assert engine.generate([prompt], max_new_tokens=6)[0] == [0] * 6
        assert engine.unexpected_compiles == 0  # swap never recompiles
    finally:
        engine.install_weights(init_params(SPEC, seed=0), step=old_step)
    assert engine.generate([prompt], max_new_tokens=6)[0] == base


def test_install_weights_rejects_mismatched_tree(engine):
    bad = dict(init_params(SPEC, seed=0))
    first = next(iter(bad))
    bad[first] = np.zeros((3, 3), np.float32)
    with pytest.raises(ValueError):
        engine.install_weights(bad)


# -- served model dir --------------------------------------------------------

def test_save_load_roundtrip(tmp_path, engine):
    root = str(tmp_path / "served")
    save_served_model(root, SPEC, init_params(SPEC, seed=0),
                      config=CFG, step=3)
    assert is_served_model_dir(root)
    assert not is_served_model_dir(str(tmp_path))
    eng2 = load_engine(root)
    try:
        assert eng2.weights_step == 3
        assert eng2.config.decode_buckets == CFG.decode_buckets
        prompt = [2, 7, 1]
        assert (eng2.generate([prompt], max_new_tokens=5)[0]
                == engine.generate([prompt], max_new_tokens=5)[0])
        assert eng2.unexpected_compiles == 0
    finally:
        eng2.close()


def test_load_engine_missing_checkpoint(tmp_path):
    root = str(tmp_path / "empty")
    os.makedirs(root)
    with open(os.path.join(root, "serve_config.json"), "w") as f:
        json.dump({"model": SPEC.to_dict(), "serve": CFG.to_dict()}, f)
    with pytest.raises(FileNotFoundError):
        load_engine(root)


def test_serve_config_env_roundtrip(monkeypatch):
    monkeypatch.setenv("PT_SERVE_BUCKETS", "2,8")
    monkeypatch.setenv("PT_SERVE_KV_PAGES", "64")
    monkeypatch.setenv("PT_SERVE_MAX_INFLIGHT", "5")
    cfg = ServeConfig.from_env()
    assert cfg.decode_buckets == (2, 8)
    assert cfg.kv_pages == 64 and cfg.max_inflight == 5
    assert ServeConfig.from_dict(cfg.to_dict()) == cfg


def test_serve_config_normalized_clamps_ladder():
    cfg = ServeConfig(decode_buckets=(1, 2, 3),
                      prefill_buckets=(16, 4096)).normalized(SPEC)
    # decode bucket 1 is clamped to 2 (batch-1 gemv reduction order
    # differs → would break the bit-identity contract)
    assert min(cfg.decode_buckets) >= 2
    assert all(b <= SPEC.max_seq_len for b in cfg.prefill_buckets)


# -- HTTP front end ----------------------------------------------------------

def _post(url, payload):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=60) as r:
        return r.status, json.loads(r.read())


def test_http_end_to_end():
    from paddle_tpu.serving.http import ServeHTTPServer
    eng = ServingEngine(SPEC, init_params(SPEC, seed=0), CFG)
    srv = ServeHTTPServer(eng, port=0).start()
    base = f"http://{srv.host}:{srv.port}"
    try:
        with urllib.request.urlopen(base + "/healthz", timeout=30) as r:
            health = json.loads(r.read())
        assert r.status == 200 and health["ok"]

        status, out = _post(base + "/v1/generate",
                            {"tokens": [1, 2, 3], "max_new_tokens": 4})
        assert status == 200
        assert len(out["tokens"]) == 4
        assert out["latency_ms"] >= 0
        # the worst gap between two of its tokens lies inside its answer
        assert 0 < out["token_gap_max_ms"] <= \
            out["latency_ms"] - out["ttft_ms"]
        # parity with the in-process path
        assert out["tokens"] == eng.generate([[1, 2, 3]],
                                             max_new_tokens=4)[0]

        with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
            assert r.status == 200
        with urllib.request.urlopen(base + "/healthz", timeout=30) as r:
            health = json.loads(r.read())
        assert 0 < health["step_period_p50_s"] <= health["step_period_p99_s"]
        assert health["token_gap_max_mean_s"] > 0
        assert health["steps_timed"] == health["occupancy_steps"] > 0

        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(base + "/v1/generate", {"tokens": "nope"})
        assert ei.value.code == 400
        assert eng.unexpected_compiles == 0
    finally:
        srv.stop()
        eng.close()


def test_http_saturation_returns_429():
    from paddle_tpu.serving.http import ServeHTTPServer
    cfg = CFG.replace(max_inflight=1)
    eng = ServingEngine(SPEC, init_params(SPEC, seed=0), cfg)
    # stall the scheduler loop so the first request stays in flight
    eng.scheduler.start()
    srv = ServeHTTPServer(eng, port=0).start()
    base = f"http://{srv.host}:{srv.port}"
    hold = threading.Event()
    orig_step = eng.scheduler.step

    def slow_step():
        hold.wait(5.0)
        return orig_step()

    eng.scheduler.step = slow_step
    try:
        t = threading.Thread(
            target=lambda: _post(base + "/v1/generate",
                                 {"tokens": [1, 2], "max_new_tokens": 2}))
        t.start()
        # wait until the in-flight slot is taken
        deadline = 50
        while eng.scheduler.snapshot()["submitted"] == 0 and deadline:
            deadline -= 1
            threading.Event().wait(0.05)
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(base + "/v1/generate",
                  {"tokens": [3, 4], "max_new_tokens": 2})
        assert ei.value.code == 429
        hold.set()
        t.join(timeout=30)
    finally:
        hold.set()
        eng.scheduler.step = orig_step
        srv.stop()
        eng.close()


# -- where the time goes: spans, counters, stamps (PR 25) ----------------------

_PHASE_KEYS = ("wait_s", "evict_s", "admit_host_s", "prefill_s",
               "decode_prep_s", "decode_s", "book_s")


def _scheduler_thread_spans(engine, prompts, new_tokens):
    """Run `prompts` through a fresh scheduler's loop with the tracer on;
    returns (the loop thread's serve.* spans by start, the scheduler)."""
    from paddle_tpu.observability.trace import get_tracer, reset_tracer
    from paddle_tpu.serving.scheduler import ContinuousScheduler
    reset_tracer()
    tr = get_tracer().enable(capacity=1 << 16)
    sched = ContinuousScheduler(engine)
    try:
        sched.start()
        ident = sched._thread.ident & 0xFFFFFF
        streams = [sched.submit(p, max_new_tokens=new_tokens)
                   for p in prompts]
        for st in streams:
            st.result(timeout=60.0)
        sched.stop(timeout=10.0)
        spans = sorted((s for s in tr.spans()
                        if s.name.startswith("serve.") and s.tid == ident),
                       key=lambda s: s.t0_ns)
    finally:
        sched.stop(timeout=10.0)
        reset_tracer()
    return spans, sched


def test_scheduler_leaf_spans_partition_its_threads_time(engine,
                                                         monkeypatch):
    import time
    prompts = [[1 + i, 2, 3 + i, 4, 5][: 2 + i % 4] for i in range(10)]

    def slowed(exe):
        # a tiny model's step is under a millisecond on the CPU, of which
        # the spans' own cost is several percent; a device step is not
        def call(*args):
            time.sleep(0.005)
            return exe(*args)
        return call

    for exes in (engine._prefill_exe, engine._decode_exe):
        for key, exe in list(exes.items()):
            monkeypatch.setitem(exes, key, slowed(exe))
    for attempt in range(3):     # a preempted test process opens a gap
        spans, sched = _scheduler_thread_spans(engine, prompts, 6)
        names = {s.name for s in spans}
        assert {"serve.wait", "serve.evict", "serve.admit", "serve.book",
                "serve.prefill.prep", "serve.prefill.launch",
                "serve.prefill.fetch", "serve.decode.prep",
                "serve.decode.launch", "serve.decode.fetch"} <= names
        gaps = [b.t0_ns - a.t1_ns for a, b in zip(spans, spans[1:])]
        assert min(gaps) >= 0, "leaf spans must not overlap or nest"
        # the spans start at the loop's first wait and end with its last
        wall = (spans[-1].t1_ns - spans[0].t0_ns) / 1e9
        counted = sum(sched.stats[k] for k in _PHASE_KEYS)
        if max(gaps) < 1e6 and abs(counted - wall) <= 0.02 * wall:
            break
    assert max(gaps) < 1e6, f"gap of {max(gaps) / 1e6:.3f} ms between spans"
    assert abs(counted - wall) <= 0.02 * wall, (counted, wall)
    assert sched.stats["admitted"] == len(prompts)
    assert sched.stats["tpot_requests"] == len(prompts)


def test_stream_stamps_ordered_and_lock_wait_counted(engine):
    from paddle_tpu.serving.scheduler import ContinuousScheduler
    sched = ContinuousScheduler(engine)
    got = []
    sched._lock.acquire()            # a step holds this from evict to decode
    try:
        t = threading.Thread(target=lambda: got.append(
            sched.submit([5, 6, 7], max_new_tokens=4)))
        t.start()
        threading.Event().wait(0.05)
        assert not got, "submit() must wait for the step's lock"
    finally:
        sched._lock.release()
    t.join(timeout=10.0)
    st = got[0]
    assert sched.stats["lock_wait_s"] >= 0.04
    assert st.submitted_ts - st.arrived_ts >= 0.04
    assert st.admitted_ts is None and st.ttft is None and st.tpot is None
    sched.drain()
    assert len(st.result(timeout=10.0)) == 4
    stamps = [st.arrived_ts, st.submitted_ts, st.admitted_ts,
              st.first_token_ts, st.last_token_ts, st.finished_ts]
    assert stamps == sorted(stamps)
    assert st.latency == st.finished_ts - st.arrived_ts
    assert st.ttft >= st.queue_wait + 0.04 > 0.04
    assert st.tpot == pytest.approx(
        (st.last_token_ts - st.first_token_ts) / 3)
    snap = sched.snapshot()
    assert snap["lock_wait_mean_s"] >= 0.04
    assert snap["ttft_mean_s"] == pytest.approx(st.ttft)
    assert snap["tpot_mean_s"] == pytest.approx(st.tpot)
    assert snap["queue_wait_mean_s"] == pytest.approx(st.queue_wait)


def test_counters_count_with_telemetry_off_and_registry_stays_empty(engine):
    from paddle_tpu.observability import get_registry, reset_registry
    from paddle_tpu.observability.telemetry import get_telemetry
    from paddle_tpu.serving.scheduler import ContinuousScheduler
    assert not get_telemetry().enabled
    reset_registry()
    sched = ContinuousScheduler(engine)
    streams = [sched.submit([1, 2, 3], max_new_tokens=3) for _ in range(3)]
    sched.drain()
    assert all(len(st.result(timeout=10.0)) == 3 for st in streams)
    assert get_registry().snapshot() == {}
    s = sched.stats
    assert s["admitted"] == 3 and s["tokens_generated"] == 9
    assert s["prefill_s"] > 0 and s["decode_s"] > 0 and s["ttft_s"] > 0
    assert s["admit_host_s"] > 0 and s["book_s"] > 0 and s["evict_s"] > 0


def test_engine_publishes_the_equal_heads_kernels_walk(engine):
    """Tables of 16 pages of 4 tokens: a chunk is the whole row (64
    tokens), so the 4-row bucket's grid is 4 chunks."""
    from paddle_tpu.ops.paged_attention import chunk_walk
    q = jnp.zeros((4, engine.spec.heads, engine.spec.head_dim))
    assert chunk_walk(q, engine.pool.k_pool, 16,
                      steps=CFG.kv_pages - 1 + 4) == (64, 4)
    # the one place that tells what a kernel walks: under a window (of 8
    # tokens: the 3 pages it can touch, one chunk) or with fewer heads in
    # the pool than q has the grouped kernels' chunks, which at this size
    # are a row's whole walk; nothing on int8 pages
    assert chunk_walk(q, engine.pool.k_pool, 16, window=8) == (12, 4)
    assert chunk_walk(jnp.zeros((4, 2 * engine.spec.heads,
                                 engine.spec.head_dim)),
                      engine.pool.k_pool, 16) == (64, 4)
    assert chunk_walk(q, engine.pool.k_pool.astype(jnp.int8), 16) is None
    want = {"serve_decode_b4": {"chunk_tokens": 64, "grid_steps": 4}}
    assert engine.stats["paged_walk"] == want
    assert engine.healthz()["paged_walk"] == want
    assert engine.paged_walk_for(3) == want["serve_decode_b4"]


@pytest.mark.parametrize("chunk_tokens,per_request", [
    # a request of 3 prompt tokens decodes at lengths 4 and 5
    (64, 1 + 1), (4, 1 + 2), (2, 2 + 3), (1, 4 + 5)])
def test_walk_counters_sum_the_rows_chunks_and_the_grid(
        engine, monkeypatch, chunk_tokens, per_request):
    from paddle_tpu.serving.scheduler import ContinuousScheduler
    monkeypatch.setattr(engine, "_decode_walk", {
        4: {"chunk_tokens": chunk_tokens, "grid_steps": 9}})
    sched = ContinuousScheduler(engine)
    streams = [sched.submit([1, 2, 3], max_new_tokens=3) for _ in range(3)]
    sched.drain()
    assert all(len(st.result(timeout=10.0)) == 3 for st in streams)
    s = sched.stats
    assert s["paged_chunks_walked"] == 3 * per_request
    assert s["paged_grid_steps"] == 9 * s["occupancy_steps"] > 0
    assert sched.snapshot()["paged_grid_steps"] == s["paged_grid_steps"]


@pytest.mark.parametrize("window,chunk_tokens,per_request", [
    # page 4: a request of 3 prompt tokens decodes at lengths 4 and 5,
    # which a window of 2 sees from pages 0 and 0, a window of 1 from
    # pages 0 and 1: (full list, 64 a chunk) + (window list)
    (2, 4, (1 + 1) + (1 + 2)), (1, 4, (1 + 1) + (1 + 1)),
    (2, 8, (1 + 1) + (1 + 1)), (64, 4, (1 + 1) + (1 + 2))])
def test_walk_counters_add_the_sliding_layers_list(
        engine, monkeypatch, window, chunk_tokens, per_request):
    """A model with sliding layers walks two lists a decode step: each
    adds its rows' chunks (a window's from the page it starts in) and its
    grid."""
    from paddle_tpu.serving.scheduler import ContinuousScheduler
    monkeypatch.setattr(engine, "_decode_walk", {
        4: {"chunk_tokens": 64, "grid_steps": 9,
            "window": {"tokens": window, "chunk_tokens": chunk_tokens,
                       "grid_steps": 5}}})
    sched = ContinuousScheduler(engine)
    streams = [sched.submit([1, 2, 3], max_new_tokens=3) for _ in range(3)]
    sched.drain()
    assert all(len(st.result(timeout=10.0)) == 3 for st in streams)
    s = sched.stats
    assert s["paged_chunks_walked"] == 3 * per_request
    assert s["paged_grid_steps"] == (9 + 5) * s["occupancy_steps"] > 0
    assert s["paged_chunks_walked"] <= s["paged_grid_steps"]


def test_walk_counters_stay_zero_where_no_program_has_the_walk(
        engine, monkeypatch):
    """An int8 pool: ``paged_walk`` is empty and the scheduler counts
    nothing."""
    from paddle_tpu.serving.scheduler import ContinuousScheduler
    monkeypatch.setattr(engine, "_decode_walk", {})
    assert engine.paged_walk_for(1) is None
    sched = ContinuousScheduler(engine)
    sched.submit([1, 2, 3], max_new_tokens=3)
    sched.drain()
    assert sched.stats["occupancy_steps"] > 0
    assert sched.stats["paged_chunks_walked"] == 0
    assert sched.stats["paged_grid_steps"] == 0


def test_request_histograms_and_one_token_booking_a_step(engine):
    from paddle_tpu import observability as obs
    from paddle_tpu.serving.scheduler import ContinuousScheduler
    assert not obs.get_telemetry().enabled
    obs.reset_registry()
    tel = obs.get_telemetry()
    tel.enable(compile_watch=False)
    try:
        sched = ContinuousScheduler(engine)
        streams = [sched.submit([1, 2, 3], max_new_tokens=5)
                   for _ in range(3)]
        sched.drain()
        assert all(len(st.result(timeout=10.0)) == 5 for st in streams)
        sched.snapshot()        # the counters follow `stats` from here
        snap = obs.get_registry().snapshot()
        for name in ("pt_serve_request_latency_seconds",
                     "pt_serve_queue_wait_seconds", "pt_serve_ttft_seconds",
                     "pt_serve_tpot_seconds"):
            (series,) = snap[name]["series"].values()
            assert series["count"] == 3, name
        (tokens,) = snap["pt_serve_tokens_total"]["series"].values()
        assert tokens == sched.stats["tokens_generated"] == 15
        assert snap["pt_serve_paged_chunks_total"]["series"] == {
            "state=walked": sched.stats["paged_chunks_walked"],
            "state=grid": sched.stats["paged_grid_steps"]}
        # the handles are looked up once, then kept
        assert set(sched._meters) >= {"pt_serve_tokens_total",
                                      "pt_serve_ttft_seconds"}
    finally:
        tel.enabled = False       # the module's engine keeps its sentinel
        obs.reset_registry()


def test_watchdog_flight_dump_names_serve_spans(engine, monkeypatch,
                                                tmp_path):
    from paddle_tpu.observability.trace import get_tracer, reset_tracer
    from paddle_tpu.serving.scheduler import ContinuousScheduler
    import time
    monkeypatch.setenv("PT_SERVE_WATCHDOG", "1")
    monkeypatch.setenv("PT_SERVE_WATCHDOG_FLOOR_S", "0.2")
    reset_tracer()
    tr = get_tracer().enable(flight_dir=str(tmp_path))
    sched = ContinuousScheduler(engine)
    orig = engine.decode
    hang = threading.Event()

    def decode(*args, **kw):
        if hang.is_set():
            time.sleep(1.0)
        return orig(*args, **kw)

    monkeypatch.setattr(engine, "decode", decode)
    sched.start()
    try:
        sched.submit([1, 2, 3], max_new_tokens=3).result(timeout=30.0)
        hang.set()
        st = sched.submit([1, 2, 3], max_new_tokens=3)
        deadline = time.monotonic() + 10.0
        while not sched.hang_detected and time.monotonic() < deadline:
            time.sleep(0.02)
        assert sched.hang_detected
        # the flag goes up before the dump is written: wait for the dump
        while True:
            doc = json.load(open(tr.flight_path))
            if doc["reason"] != "armed" or time.monotonic() > deadline:
                break
            time.sleep(0.02)
        assert doc["reason"].startswith("serve-hang")
        names = {s["name"] for s in doc["spans"]}
        assert {"serve.decode.launch", "serve.prefill.launch",
                "serve.admit"} <= names
        st.result(timeout=10.0)
    finally:
        sched.stop(timeout=10.0)
        reset_tracer()


def _op_names(lowered):
    import re
    return set(re.findall(r'"(jit\([^"]*)"', lowered.as_text(debug_info=True)))


def test_serve_programs_carry_scope_names():
    import jax
    from paddle_tpu.serving.model import decode_step, prefill_step
    from paddle_tpu.serving.engine import aot_build_phase
    ps, pages = 4, 8
    i32 = jnp.int32
    with aot_build_phase():      # the eager zeros compile: not an incident
        params = init_params(SPEC, seed=0)
        pool = jnp.zeros((SPEC.layers, pages, ps, SPEC.hidden))
        dec = _op_names(jax.jit(
            lambda p, k, v, t, pos, pt: decode_step(
                SPEC, p, k, v, t, pos, pt, page_size=ps)).lower(
            params, pool, pool, jnp.zeros((2,), i32), jnp.zeros((2,), i32),
            jnp.zeros((2, 16), i32)))
        pre = _op_names(jax.jit(
            lambda p, k, v, t, n, pt: prefill_step(
                SPEC, p, k, v, t, n, pt, page_size=ps)).lower(
            params, pool, pool, jnp.zeros((16,), i32), i32(3),
            jnp.zeros((16,), i32)))

    def scoped(names, scope):
        return [n for n in names if f"/{scope}/" in n + "/"]

    for scope in ("embed", "layer0/attn_qkv", "layer0/kv_write",
                  "layer1/kv_write", "layer0/attn", "layer0/attn_out",
                  "layer1/mlp", "lm_head", "sample"):
        assert scoped(dec, scope), scope
        assert scoped(pre, scope), scope
    assert any(n.endswith("kv_write/scatter") for n in dec)
    assert any(n.endswith("kv_write/scatter") for n in pre)
    # the kernel takes the pools whole: nothing is left to scope kv_read
    assert not scoped(dec, "layer0/kv_read")
