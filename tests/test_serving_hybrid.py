"""A decoder-hybrid-decoder (Phi-4-mini-flash-reasoning's shape) through the
serving stack, at the tiny preset ``benchmarks/configs/
tiny-phi4flash-serve.json`` cut further for speed (window 8, pages of 4,
buckets to 64): hidden 64, 8 query / 4 KV heads of 8, 12 layers —
state-space and sliding x3, state-space, full, gated memory unit and
cross x2 — seeded random weights, float32:

 - prefill then decode through the cache (pages of two pools and a state
   slot) against the plain reference's full forward
   (``benchmarks/reference/phi4flash_serve.py``) on logits: prompts under
   and over the window, shorter than their bucket and at its edge, page
   boundaries crossed in decode;
 - rows of unlike lengths in one decode bucket, padding rows beside them,
   bit-identical to each row alone; a slot released and taken again;
 - the state after a padded bucket is the state after ``length`` tokens;
 - the ``ssm_scan`` kernel and the differential paged read against their
   XLA twins in interpret mode;
 - the allocator: refusal for want of a slot, release, consistency; the
   counters; ``costs_hybrid.py`` against hand arithmetic; the cell's
   rehearsal on the CPU.
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

from paddle_tpu.ops.paged_attention import (  # noqa: E402
    paged_attention_diff, paged_attention_diff_reference)
from paddle_tpu.ops.selective_scan import (  # noqa: E402
    selective_scan, selective_scan_reference)
from paddle_tpu.serving import ModelSpec, ssm  # noqa: E402
from paddle_tpu.serving.kv_cache import PagePool  # noqa: E402

WINDOW, PS = 8, 4


def _load(kind, name):
    import importlib.util
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"h_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs",
                           "tiny-phi4flash-serve.json")) as f:
        cfg = json.load(f)
    cfg = copy.deepcopy(cfg)
    cfg["sliding_window"] = WINDOW
    cfg["serve"].update(max_seq_len=128, page_size=PS, kv_pages=256,
                        prefill_buckets=[16, 32, 64], decode_buckets=[2, 4],
                        max_new_tokens=8)
    cfg["check"] = {"short_below": 8, "pads": [32, 80]}
    return cfg


@pytest.fixture(scope="module")
def runner():
    return _load("runners", "serve_hybrid")


@pytest.fixture(scope="module")
def ref():
    return _load("reference", "phi4flash_serve")


@pytest.fixture(scope="module")
def built(config, runner):
    engine, params, spec, _ = runner.build_engine(config, 3)
    yield engine, params, spec
    engine.close()


def test_the_spec_names_every_kind_of_layer(built):
    _, _, spec = built
    assert spec.layer_types == (
        "ssm", "sliding", "ssm", "sliding", "ssm", "sliding", "ssm", "full",
        "gmu", "cross", "gmu", "cross")
    assert spec.positions == "none" and spec.ffn == "swiglu"
    assert spec.tail_start == 8 and spec.ssm_layers == (0, 2, 4, 6)
    assert spec.global_layers == (7,) and spec.window_layers == (1, 3, 5)
    assert ModelSpec.from_dict(json.loads(json.dumps(spec.to_dict()))) == spec


@pytest.mark.parametrize("types", [
    ("gmu", "full"), ("ssm", "cross", "full"), ("ssm", "sliding", "gmu"),
    ("full", "gmu")], ids=["gmu_first", "cross_before_full",
                           "tail_after_sliding", "gmu_without_ssm"])
def test_the_spec_refuses_a_tail_it_cannot_prefill(types):
    with pytest.raises(ValueError, match="come last"):
        ModelSpec(layers=len(types), layer_types=types, window=4,
                  ssm_inner=128, ssm_dt_rank=4)


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
    slot = row.slot
    row.release()
    return np.stack(rows), toks, slot


def _reference(ref, params, config, prompt, toks, steps):
    n = len(prompt)
    pad = -(-(n + steps) // 16) * 16
    seq = np.zeros((pad,), np.int32)
    seq[:n + steps] = list(prompt) + toks[:steps]
    return np.asarray(ref.forward(params, jnp.asarray(seq), np.int32(n - 1),
                                  cfg=config, rows=steps + 1)[0])


@pytest.mark.parametrize("n,steps", [(1, 3), (5, 8), (8, 6), (16, 5),
                                     (23, 9), (41, 7), (64, 5)],
                         ids=["one_token", "crossing_window_in_decode",
                              "at_window", "bucket_edge", "pages_past",
                              "shorter_than_bucket", "largest_bucket"])
def test_prefill_then_decode_equals_the_reference(built, config, ref, n,
                                                  steps):
    """Every kind of layer, the state of ``n`` tokens (not of the padded
    bucket) carried into decode, pages crossed (pages of 4)."""
    engine, params, spec = built
    rng = np.random.RandomState(100 + n)
    prompt = rng.randint(1, spec.vocab_size, size=n).tolist()
    got, toks, _ = _through_the_cache(engine, prompt, steps)
    want = _reference(ref, params, config, prompt, toks, steps)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    engine.pool.check_consistency(expect_all_free=True)


def test_a_released_slot_is_taken_again_and_never_read(built, config, ref):
    """The next row gets the slot the last one left (LIFO) with that
    row's state still in it: prefill overwrites it whole."""
    engine, params, spec = built
    rng = np.random.RandomState(7)
    a = rng.randint(1, spec.vocab_size, size=30).tolist()
    b = rng.randint(1, spec.vocab_size, size=6).tolist()
    _, _, slot_a = _through_the_cache(engine, a, 4)
    got, toks, slot_b = _through_the_cache(engine, b, 4)
    assert slot_a == slot_b and slot_a > 0
    np.testing.assert_allclose(got, _reference(ref, params, config, b,
                                               toks, 4),
                               atol=2e-4, rtol=2e-4)


def test_rows_of_one_bucket_are_bit_identical_to_each_row_alone(built):
    """Three sequences of unlike lengths and a padding row (bucket of 4)
    decode together and alone: the state slots, like the pages, make a
    row's result a function of the row."""
    engine, _, spec = built
    rng = np.random.RandomState(9)
    rows, firsts = [], []
    for n in (3, 14, 50):
        prompt = rng.randint(1, spec.vocab_size, size=n).tolist()
        row = engine.pool.admit_row(n, 4, engine.max_pages_per_seq)
        firsts.append(engine.prefill(prompt, row.table))
        row.advance(n)
        rows.append((row, n))
    assert len({row.slot for row, _ in rows}) == 3
    tok = np.asarray(firsts, np.int32)
    pos = np.asarray([n for _, n in rows], np.int32)
    tables = np.stack([r.table for r, _ in rows])
    state0 = [np.asarray(a) for a in engine.pool.state()[-2:]]
    _, together = engine.decode_logits(tok, pos, tables)
    for i in range(3):
        # put the rows' states back: a decode step moves them on
        engine.pool.swap(*engine.pool.state()[:-2],
                         *(jnp.asarray(a) for a in state0))
        _, alone = engine.decode_logits(tok[i:i + 1], pos[i:i + 1],
                                        tables[i:i + 1])
        np.testing.assert_array_equal(together[i], alone[0])
    for row, _ in rows:
        row.release()
    engine.pool.check_consistency(expect_all_free=True)


def test_the_runners_check_tells_the_four_variants(built, config, runner):
    """The check fills the largest decode bucket and holds every row
    against the reference; the reference in a lower precision, or with a
    wrong constant, reads far from the engine."""
    engine, params, _ = built
    rng = np.random.RandomState(5)
    prompts = [rng.randint(1, 256, size=n).tolist() for n in (2, 34, 11, 57)]
    err, state_err, slow_err = runner.check_against_reference(
        engine, params, config, prompts)
    assert err < 2e-4 and len(state_err) == len(slow_err) == 4
    assert max(state_err + slow_err) < 1e-5
    engine.pool.check_consistency(expect_all_free=True)
    for variant in (dict(round_to=jnp.float8_e4m3fn),
                    dict(lambda0_layer=0), dict(window=WINDOW - 1)):
        low, _, _ = runner.check_against_reference(engine, params, config,
                                                   prompts, **variant)
        assert low > 20 * max(err, 1e-6), variant
    # a state kept in bfloat16 shows in the state, not in the logits
    _, low, slow = runner.check_against_reference(
        engine, params, config, prompts, state_dtype=jnp.bfloat16)
    assert min(low + slow) > 100 * max(state_err + slow_err)
    # the slow elements: a decay under 1 / SLOW_MEMORY a position
    mask = runner.slow_elements(params, config)
    assert mask.shape == (4, 16, 128) and 0.02 < mask.mean() < 0.5
    bdt = np.asarray(params["h0.ssm.bdt"])
    assert mask[0, 0].tolist() == (np.log1p(np.exp(bdt)) < 0.01).tolist()


def test_pick_rows_cuts_a_short_and_a_page_crossing_row(config, runner):
    requests = [{"prompt": list(range(1, n + 1))} for n in (30, 21, 40, 12)]
    prompts = runner.pick_rows(requests, config, PS, 4)
    assert [len(p) for p in prompts] == [3, 18, 40, 12]
    assert (len(prompts[1]) + 2) % PS == 0


# -- the scan and the state ---------------------------------------------------

def _scan_operands(s, n, r, seed=0):
    rng = np.random.RandomState(seed)
    delta = jnp.asarray(rng.uniform(0.001, 0.2, (s, n)), jnp.float32)
    u = jnp.asarray(rng.randn(s, n) * 0.1, jnp.float32)
    bmat = jnp.asarray(rng.randn(s, r), jnp.float32)
    cmat = jnp.asarray(rng.randn(s, r), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 16.0, (r, n)), jnp.float32)
    return delta, u, bmat, cmat, a


@pytest.mark.parametrize("s,n,r", [(16, 128, 16), (128, 256, 16),
                                   (192, 1280, 8)])
def test_the_scan_kernel_equals_its_xla_twin(s, n, r):
    ops = _scan_operands(s, n, r)
    y, last = selective_scan(*ops, use_pallas=True, interpret=True)
    y2, last2 = selective_scan_reference(*ops)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y2), atol=1e-5,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(last), np.asarray(last2),
                               atol=1e-5, rtol=1e-5)


def test_the_state_written_is_the_state_after_length_tokens(built):
    """One prompt padded to two buckets with different rubbish behind
    it: the same tail, the same state, and ``y`` the same up to it."""
    _, params, spec = built
    cdt = params["embed"].dtype
    rng = np.random.RandomState(3)
    n = 11
    rows = rng.randn(n, spec.hidden)
    outs = []
    for bucket in (16, 32):
        x = np.concatenate([rows, rng.randn(bucket - n, spec.hidden)])
        outs.append(ssm.prefill(params, "h0.ssm", jnp.asarray(x, cdt),
                                jnp.int32(n)))
    (y1, _, tail1, s1), (y2, _, tail2, s2) = outs
    np.testing.assert_allclose(np.asarray(y1)[:n], np.asarray(y2)[:n],
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(tail1), np.asarray(tail2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-7)
    assert tail1.shape == (spec.ssm_conv - 1, spec.ssm_inner)
    assert s1.shape == (spec.ssm_state, spec.ssm_inner)
    # a prompt shorter than the convolution: zeros before it
    _, _, tail, _ = ssm.prefill(params, "h0.ssm",
                                jnp.asarray(rows[:1].repeat(16, 0), cdt),
                                jnp.int32(1))
    assert not np.asarray(tail[:-1]).any() and np.asarray(tail[-1]).any()


def test_a_decode_step_continues_the_scan(built):
    """prefill(n + 1 tokens) equals prefill(n) then one decode step."""
    _, params, spec = built
    cdt = params["embed"].dtype
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(16, spec.hidden), cdt)
    y_all, _, tail_all, s_all = ssm.prefill(params, "h0.ssm", x,
                                            jnp.int32(10))
    _, _, tail, state = ssm.prefill(params, "h0.ssm", x, jnp.int32(9))
    y, _, tail, state = ssm.decode(params, "h0.ssm", x[9:10], tail[None],
                                   state[None])
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(y_all[9]),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(state[0]), np.asarray(s_all),
                               atol=1e-6)
    np.testing.assert_array_equal(np.asarray(tail[0]), np.asarray(tail_all))


# -- the differential read of the paged cache --------------------------------

@pytest.mark.parametrize("window", [0, 8], ids=["full", "window"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_differential_paged_attention_equals_its_xla_twin(window, dtype):
    """K heads of D lanes against V pairs of 2D through the grouped
    kernel (interpret mode), against the layer's definition."""
    rng = np.random.RandomState(11)
    b, h, d, ps, pages, maxp = 3, 8, 16, 4, 24, 6
    kvd = (h // 2) * d
    k_pool = jnp.asarray(rng.randn(2, pages, ps, kvd), dtype)
    v_pool = jnp.asarray(rng.randn(2, pages, ps, kvd), dtype)
    q = jnp.asarray(rng.randn(b, h, d), dtype)
    lengths = jnp.asarray([5, 23, 12], jnp.int32)
    tables = np.zeros((b, maxp), np.int32)
    free = iter(rng.permutation(np.arange(1, pages)))
    for i, n in enumerate((5, 23, 12)):
        for p in range(-(-n // ps)):
            tables[i, p] = next(free)
    tables = jnp.asarray(tables)
    got = paged_attention_diff(q, k_pool, v_pool, tables, lengths, layer=1,
                               window=window, use_pallas=True,
                               interpret=True)
    want = paged_attention_diff_reference(q, k_pool, v_pool, tables,
                                          lengths, layer=1, window=window)
    assert got.shape == (b, h, 2 * d) and got.dtype == jnp.float32
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=tol,
                               rtol=tol)
    # and the dispatcher's own XLA path (what the CPU engine runs)
    xla = paged_attention_diff(q, k_pool, v_pool, tables, lengths, layer=1,
                               window=window, use_pallas=False)
    np.testing.assert_allclose(np.asarray(xla), np.asarray(want), atol=tol,
                               rtol=tol)


# -- the allocator -----------------------------------------------------------

def _pool(**kw):
    args = dict(layers=1, pages=64, page_size=PS, heads=4, head_dim=8,
                window_layers=3, window_pages=16, window=WINDOW,
                state_layers=4, state_slots=3, state_shape=(128, 16, 4))
    args.update(kw)
    return PagePool(**args)


def test_a_row_holds_pages_of_two_pools_and_a_slot():
    pool = _pool()
    assert pool.state_slots.conv.shape == (4, 3, 3, 128)
    assert pool.state_slots.ssm.shape == (4, 3, 16, 128)
    assert pool.state_slots.ssm.dtype == jnp.float32
    assert len(pool.state()) == 6 and pool.table_shape(10) == (3, 10)
    row = pool.admit_row(9, 20, 10)
    assert row.table.shape == (3, 10) and row.slot == row.table[2, 0] > 0
    assert np.count_nonzero(row.table[0]) == 3          # 9 tokens, pages of 4
    assert np.count_nonzero(row.table[1]) == len(row.window_ids) == 3
    assert not row.table[2, 1:].any()
    assert pool.state_slots.held == 1
    pool.check_consistency()
    row.release()
    row.release()                                       # safe twice
    assert pool.state_slots.held == 0
    pool.check_consistency(expect_all_free=True)


def test_admission_refuses_for_want_of_a_slot_and_says_so():
    pool = _pool()                                      # two usable slots
    a, b = pool.admit_row(5, 5, 10), pool.admit_row(5, 5, 10)
    assert pool.last_refusal is None
    reserved = (pool.reserved_pages, pool.window_pool.reserved_pages)
    assert pool.admit_row(5, 5, 10) is None
    assert pool.last_refusal == "state"
    assert pool.state_slots.stats["refusals"] == 1
    # nothing of the refused row is left behind
    assert (pool.reserved_pages, pool.window_pool.reserved_pages) == reserved
    pool.check_consistency()
    a.release()
    c = pool.admit_row(5, 5, 10)
    assert c is not None and c.slot == a.table[2, 0]
    # pages short, slots not: the other reason
    assert pool.admit_row(4 * 64, 4, 80) is None
    assert pool.last_refusal == "kv"
    b.release()
    c.release()
    pool.check_consistency(expect_all_free=True)
    assert pool.snapshot()["state"]["high_watermark"] == 2


def test_a_lost_slot_fails_the_consistency_check():
    pool = _pool()
    pool.state_slots.take()
    pool.check_consistency()
    with pytest.raises(AssertionError, match="slot leak"):
        pool.check_consistency(expect_all_free=True)
    with pytest.raises(ValueError, match="double release"):
        pool.state_slots.release(2)


# -- counters ----------------------------------------------------------------

def test_the_scheduler_counts_slots_scans_and_shared_reads(built):
    engine, _, spec = built
    sched = engine.scheduler
    before = dict(sched.stats)
    prompts = [[5, 9, 2, 7, 1, 1, 3], [3, 4], [8] * 20]
    out = engine.generate(prompts, max_new_tokens=5)
    assert [len(t) for t in out] == [5, 5, 5]
    d = {k: sched.stats[k] - before[k] for k in (
        "ssm_tokens_scanned", "shared_kv_reads", "refused_state", "steps")}
    assert d["ssm_tokens_scanned"] == 7 + 2 + 20
    # the full layer and the two cross layers read its pool every step
    assert d["shared_kv_reads"] == 3 * d["steps"] > 0
    assert d["refused_state"] == 0
    health = engine.healthz()   # `stats` mirrors the slots from a sync on
    assert sched.stats["state_slots_held"] == 0
    assert sched.stats["state_slots_held_max"] >= 3
    assert health["kv"]["state"]["held"] == 0 and health["kv_consistent"]
    assert health["state_slots_held_max"] >= 3
    engine.pool.check_consistency(expect_all_free=True)


def test_the_scheduler_counts_the_chunks_of_both_work_lists(built):
    """Differential layers read KV pairs through the grouped kernels: a
    decode program walks one list over the full layer's pool (the cross
    layers read the same one) and one over the sliding layers'."""
    from paddle_tpu.ops.paged_attention import chunks_of
    engine, _, spec = built
    sched = engine.scheduler
    walk = engine.stats["paged_walk"]
    assert walk == engine.healthz()["paged_walk"]
    assert len(walk) == len(engine.config.decode_buckets)
    before = dict(sched.stats)
    prompts = [[5, 9, 2, 7, 1, 1, 3], [3, 4], [8] * 20]
    out = engine.generate(prompts, max_new_tokens=5)
    found = engine.paged_walk_for(len(prompts))
    assert found["window"]["tokens"] == WINDOW == spec.window
    d = {k: sched.stats[k] - before[k] for k in (
        "paged_chunks_walked", "paged_grid_steps", "occupancy_steps")}
    assert d["paged_chunks_walked"] == sum(
        chunks_of(n, found["chunk_tokens"])
        + chunks_of(n, found["window"]["chunk_tokens"], page_size=PS,
                    window=WINDOW)
        for p, o in zip(prompts, out)
        for n in range(len(p) + 1, len(p) + len(o))) > 0
    assert d["paged_grid_steps"] == d["occupancy_steps"] * (
        found["grid_steps"] + found["window"]["grid_steps"])
    assert d["paged_chunks_walked"] <= d["paged_grid_steps"]


def test_a_request_waits_for_a_slot_and_is_counted(built):
    engine, _, _ = built
    sched, slots = engine.scheduler, engine.pool.state_slots
    held = [slots.take() for _ in range(slots.slots - 1)]
    before = sched.stats["refused_state"], sched.stats["refused_kv"]
    st = sched.submit([1, 2, 3], max_new_tokens=2)
    sched.step()
    assert sched.stats["refused_state"] == before[0] + 1
    assert sched.stats["refused_kv"] == before[1]
    assert not st.done()
    for s in held:
        slots.release(s)
    sched.drain()
    assert len(st.result(timeout=30)) == 2
    engine.pool.check_consistency(expect_all_free=True)


# -- the benchmark's arithmetic ---------------------------------------------

PHI = {"layers": 32, "heads": 40, "kv_heads": 20, "head_dim": 64,
       "hidden": 2560, "ffn": 10240, "vocab_size": 200064, "window": 512,
       "ssm_inner": 5120, "ssm_state": 16, "ssm_conv": 4,
       "ssm_dt_rank": 160, "page_size": 128, "kv_itemsize": 2,
       "weight_itemsize": 2}


def test_costs_hybrid_counts_what_hand_arithmetic_counts(ref):
    costs = _load(".", "costs_hybrid")
    m = dict(PHI, layer_types=[{"mamba": "ssm"}.get(k, k)
                               for k in ref.layer_kinds(32)])
    mlp = 3 * 2560 * 10240
    ssm_p = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 4 * 5120 + 5120 * 2560
    attn = 2560 * (2560 + 1280 + 1280) + 2560 * 2560
    cross, gmu = 2 * 2560 * 2560, 2 * 2560 * 5120
    head = 2560 * 200064
    assert costs.ssm_params(m) == ssm_p == 41_144_320
    every, once, row = costs.position_params(m)
    kv = 2 * 2560 * 1280
    assert every == 9 * (ssm_p + mlp) + 8 * (attn + mlp) + kv
    assert once == (attn - kv + mlp) + 7 * (gmu + mlp) + 7 * (cross + mlp) \
        + head
    assert row == every + once
    # ISSUE 31's count of the matrices: 3,340 M in the layers
    assert abs((row - head) / 1e6 - 3340) < 2
    assert costs.serve_flops(m, 1000, 3, 50) == 2 * (
        every * 1000 + once * 3 + row * 50)
    # one differential layer, 64 rows seeing 100,000 positions in all
    flops, nbytes = costs.paged_decode_diff(100_000, 64, 40, 20, 64, 2)
    assert flops == 100_000 * 40 * (2 * 64 + 2 * 128)
    assert nbytes == 100_000 * 2 * 1280 * 2 + 64 * 2560 * 2 + 64 * 5120 * 4
    flops, nbytes = costs.ssm_decode(64, m)
    assert flops == 64 * (2 * ssm_p + 6 * 5120 * 16)
    assert nbytes == ssm_p * 2 + 64 * (2 * 16 * 5120 * 4
                                       + 2 * 3 * 5120 * 2 + 2 * 2560 * 2)
    flops, nbytes = costs.ssm_scan(2048, m)
    assert flops == 6 * 2048 * 5120 * 16
    assert nbytes == 4 * (2048 * (3 * 5120 + 32) + 2 * 16 * 5120)


def test_the_configuration_holds_the_catalogs_keys_and_fits(runner):
    with open(os.path.join(BENCH, "configs",
                           "phi4-mini-flash-serve.json")) as f:
        cfg = json.load(f)
    assert cfg["reduced"] == []
    spec = runner.spec_from_config(cfg)
    assert (spec.layers, spec.heads, spec.n_kv_heads, spec.head_dim) == \
        (32, 40, 20, 64)
    assert len(spec.ssm_layers) == 9 and len(spec.window_layers) == 8
    assert spec.global_layers == (17,) and len(spec.cross_layers) == 7
    assert spec.tail_start == 18 and spec.layer_kind(16) == "ssm"
    from paddle_tpu.serving.model import init_params
    shapes = jax.eval_shape(lambda: init_params(spec, 0, jnp.bfloat16))
    n = sum(int(np.prod(a.shape)) for a in shapes.values())
    assert abs(n / 1e6 - 3853) < 3         # ISSUE 31: 3,853 M parameters


def test_the_cells_rehearsal_on_the_cpu():
    """`tiny-phi4flash-serve.reasoning-backlog --allow-cpu`: the cell's
    traffic at the tiny widths through the runner, the check and the
    window, ending in a line marked as a rehearsal with `correct` true."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "tiny-phi4flash-serve.reasoning-backlog", "--seconds", "2",
         "--seed", "2147483659", "--allow-cpu"],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert "rehearsal" in line and line["correct"] is True
    assert line["failed"] == 0
    notes = line["notes"]
    assert notes["logit_err"] < 1e-4 and notes["kv_consistent"]
    assert notes["window_compiles"] == 0
    assert notes["state_slots"]["high_watermark"] == 8
    assert max(notes["check_prompt_lens"]) > 1024 > \
        min(notes["check_prompt_lens"])
