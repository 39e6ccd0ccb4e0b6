"""A sparse grouped-query decoder through the serving stack, at the tiny
preset ``benchmarks/configs/tiny-mellum-serve.json`` (hidden 64, 4 query /
2 KV heads of 16, 8 experts top-2 of width 32, window 8, pages of 4, two
periods of sliding x3 + full, YaRN with a small original length), seeded
random weights, float32:

 - prefill then decode through the cache against the plain reference's
   full forward (``benchmarks/reference/mellum_serve.py``) on logits, for
   contexts below the window, crossing it during decode, and several
   pages past it;
 - rows of different lengths in one decode bucket bit-identical to each
   row alone;
 - the allocator: a sliding layer's hold stays <= window + page over ten
   windows of decoding, everything free after finish / cancel / deadline,
   ``check_consistency`` after each;
 - the spec's new fields through ``to_dict`` / ``from_dict`` and a
   served-model directory; the blocked prefill attention; the counters;
 - the GPT tiny preset's logits bit-identical to what the block gave
   before it was driven by the spec.
"""
import hashlib
import json
import os
import sys
import time

import numpy as np
import pytest

import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for _p in (BENCH, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from paddle_tpu.serving import (ModelSpec, ServeConfig, ServingEngine,  # noqa: E402
                                init_params, load_engine,
                                save_served_model)
from paddle_tpu.serving import model as serve_model  # noqa: E402
from paddle_tpu.serving.kv_cache import PagePool, kv_page_budget  # noqa: E402
from paddle_tpu.serving.scheduler import (DeadlineExceeded,  # noqa: E402
                                          RequestCancelled)


def _load(kind, name):
    import importlib.util
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"t_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module", autouse=True)
def grouped_regime_at_toy_sizes():
    """The toy's prefill buckets (16 to 64 rows) are far under
    ``experts.STREAM_MAX_TOKENS``: lower it for this module so that its
    prefill programs sort and group, as the served ones do, and its
    decode programs (2 and 4 rows) stay streamed."""
    from paddle_tpu.serving import experts
    kept, experts.STREAM_MAX_TOKENS = experts.STREAM_MAX_TOKENS, 4
    yield
    experts.STREAM_MAX_TOKENS = kept


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", "tiny-mellum-serve.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def runner():
    return _load("runners", "serve_lm")


@pytest.fixture(scope="module")
def built(config, runner):
    engine, params, spec, _ = runner.build_engine(config, 3)
    yield engine, params, spec
    engine.close()


WINDOW, PS = 8, 4


def _through_the_cache(engine, prompt, steps):
    row = engine.pool.admit_row(len(prompt), steps + 1,
                                engine.max_pages_per_seq)
    first, logits = engine.prefill_logits(prompt, row.table)
    rows, toks, held = [logits], [first], []
    for k in range(steps):
        row.advance(len(prompt) + k)
        held.append(len(row.window_ids))
        nxt, logits = engine.decode_logits(
            np.asarray(toks[-1:], np.int32),
            np.asarray([len(prompt) + k], np.int32), row.table[None])
        rows.append(logits[0])
        toks.append(int(nxt[0]))
    row.release()
    return np.stack(rows), toks, held


@pytest.mark.parametrize("n,steps", [(2, 4), (5, 8), (8, 6), (23, 9),
                                     (41, 7), (64, 5)],
                         ids=["below_window", "crossing_window_in_decode",
                              "at_window", "pages_past", "ten_pages_past",
                              "bucket_edge"])
def test_prefill_then_decode_equals_the_reference(built, config, runner,
                                                  n, steps):
    engine, params, spec = built
    ref = _load("reference", "mellum_serve")
    rng = np.random.RandomState(100 + n)
    prompt = rng.randint(1, spec.vocab_size, size=n).tolist()
    got, toks, held = _through_the_cache(engine, prompt, steps)
    assert max(held) <= WINDOW // PS + 1
    pad = -(-(n + steps) // 16) * 16
    seq = np.zeros((pad,), np.int32)
    seq[:n + steps] = prompt + toks[:steps]
    want, _ = ref.forward(params, jnp.asarray(seq), np.int32(n - 1),
                          cfg=runner.reference_config(config),
                          rows=steps + 1)
    np.testing.assert_allclose(got, np.asarray(want), atol=2e-4, rtol=2e-4)
    engine.pool.check_consistency(expect_all_free=True)


def test_the_runners_check_agrees_and_counts_no_routing_difference(
        built, config, runner):
    """The check fills the largest decode bucket with rows of mixed
    lengths (under the window, past it, many pages past it) and holds
    every row against the reference."""
    engine, params, _ = built
    rng = np.random.RandomState(5)
    assert engine.config.decode_buckets[-1] == 4
    prompts = [rng.randint(1, 256, size=n).tolist() for n in (2, 34, 11, 57)]
    err, diff = runner.check_against_reference(engine, params, config,
                                               prompts)
    assert err < 2e-4 and diff == 0.0
    engine.pool.check_consistency(expect_all_free=True)
    # and a reference computed in a lower precision is told apart
    import jax
    low, _ = runner.check_against_reference(
        engine, params, config, prompts, round_to=jax.numpy.float8_e4m3fn)
    assert low > 50 * max(err, 1e-6)


def test_the_check_tells_a_wrong_row_of_a_full_bucket(built, config, runner,
                                                      monkeypatch):
    """A fault that shows only with many rows live (here: the last row of
    the bucket's logits shifted in the decode steps) fails the check;
    one row alone would pass it."""
    engine, params, _ = built
    rng = np.random.RandomState(6)
    prompts = [rng.randint(1, 256, size=n).tolist() for n in (5, 40, 9, 21)]
    real = engine.decode_logits

    def faulty(tokens, positions, tables):
        nxt, logits = real(tokens, positions, tables)
        if len(tokens) == 4:
            logits = logits.copy()
            logits[3] += 0.5
        return nxt, logits

    monkeypatch.setattr(engine, "decode_logits", faulty)
    err, _ = runner.check_against_reference(engine, params, config, prompts)
    assert err > 0.4
    err, _ = runner.check_against_reference(engine, params, config,
                                            prompts[:1])
    assert err < 2e-4


@pytest.mark.parametrize("lens,want", [
    ((30, 9, 40, 12, 25), [2, 9, 38, 12]),      # a long one among the rows
    ((30, 9, 10, 12, 25), [2, 9, 10, 22])],     # brought in from further on
    ids=["long_among_rows", "long_brought_in"])
def test_pick_rows_fills_the_bucket_with_the_runs_own_requests(
        config, runner, lens, want):
    """short_below 8, long_from 24, pages of 4, four decode steps: the
    first row stays under the window, one row is long, and both write a
    page's last position in their second step."""
    requests = [{"prompt": list(range(1, n + 1))} for n in lens]
    prompts = runner.pick_rows(requests, config, 4, 4)
    assert [len(p) for p in prompts] == want
    assert all(p == r["prompt"][:len(p)]
               for p, r in zip(prompts[:3], requests))
    for n in (len(prompts[0]), max(len(p) for p in prompts)):
        assert (n + 2) % 4 == 0


def test_rows_of_one_bucket_are_bit_identical_to_each_row_alone(built):
    """Three sequences of very different lengths (under the window, past
    it, many pages past it) decode together and alone."""
    engine, _, spec = built
    rng = np.random.RandomState(9)
    rows, firsts = [], []
    for n in (3, 14, 50):
        prompt = rng.randint(1, spec.vocab_size, size=n).tolist()
        row = engine.pool.admit_row(n, 4, engine.max_pages_per_seq)
        firsts.append(engine.prefill(prompt, row.table))
        row.advance(n)
        rows.append((row, n))
    tok = np.asarray(firsts, np.int32)
    pos = np.asarray([n for _, n in rows], np.int32)
    tables = np.stack([r.table for r, _ in rows])
    _, together = engine.decode_logits(tok, pos, tables)
    for i, (row, n) in enumerate(rows):
        _, alone = engine.decode_logits(tok[i:i + 1], pos[i:i + 1],
                                        tables[i:i + 1])
        np.testing.assert_array_equal(together[i], alone[0])
    # other order, other bucket padding: still the same bits
    _, swapped = engine.decode_logits(tok[::-1], pos[::-1], tables[::-1])
    np.testing.assert_array_equal(swapped[::-1], together)
    for row, _ in rows:
        row.release()
    engine.pool.check_consistency(expect_all_free=True)


# -- the allocator ---------------------------------------------------------

def _pool(**kw):
    args = dict(layers=1, pages=64, page_size=PS, heads=2, head_dim=16,
                window_layers=3, window_pages=16, window=WINDOW)
    args.update(kw)
    return PagePool(**args)


@pytest.mark.parametrize("prompt_len", [1, 7, 8, 9, 30])
def test_window_hold_is_bounded_over_ten_windows_of_decoding(prompt_len):
    pool = _pool()
    row = pool.admit_row(prompt_len, 10 * WINDOW, 40)
    most = WINDOW // PS + 1
    assert len(row.window_ids) <= most
    returned = 0
    for pos in range(prompt_len, prompt_len + 10 * WINDOW):
        returned += row.advance(pos)
        assert len(row.window_ids) <= most
        # exactly the pages a step at this position reads
        first = max(0, pos + 1 - WINDOW) // PS
        assert sorted(row.window_ids) == list(range(first, pos // PS + 1))
        assert np.count_nonzero(row.table[1]) == len(row.window_ids)
        pool.check_consistency()
    assert returned == pool.window_pool.stats["pages_returned"] > 0
    assert pool.window_pool.stats["row_pages_max"] <= most
    assert len(row.page_ids) == -(-(prompt_len + 10 * WINDOW) // PS)
    row.release()
    row.release()                       # safe twice
    pool.check_consistency(expect_all_free=True)


def test_admission_reckons_both_kinds():
    pool = _pool(pages=64, window_pages=7)      # 6 usable window pages
    a = pool.admit_row(20, 20, 40)
    b = pool.admit_row(20, 20, 40)
    assert a is not None and b is not None      # 3 + 3 window pages
    assert pool.admit_row(20, 20, 40) is None   # the window pool is spent
    assert pool.reserved_pages + pool.used_pages == 2 * 10
    a.release()
    c = pool.admit_row(4, 0, 40)
    assert c is not None and len(c.window_ids) == 1
    b.release()
    c.release()
    pool.check_consistency(expect_all_free=True)
    small = _pool(pages=8)                      # 7 usable full-layer pages
    assert small.admit_row(20, 20, 40) is None  # needs 10: nothing taken
    small.check_consistency(expect_all_free=True)
    assert small.window_pool.reserved_pages == 0


def test_short_prompt_writes_only_what_a_decode_step_can_read(built):
    """A prompt of five windows: the sliding layers hold the last
    window + 1 pages only, and exactly those are written."""
    engine, _, spec = built
    wpool = engine.pool.window_pool
    before = np.asarray(wpool.k_pool).copy()
    n = 5 * WINDOW + 1
    row = engine.pool.admit_row(n, 2, engine.max_pages_per_seq)
    held = sorted(row.window_ids.values())
    assert len(held) <= WINDOW // PS + 1
    engine.prefill(list(range(1, n + 1)), row.table)
    after = np.asarray(wpool.k_pool)
    changed = {int(p) for p in
               np.nonzero(np.any(after != before, axis=(0, 2, 3)))[0]}
    assert changed <= set(held) | {0}, (changed, held)
    assert set(held) <= changed
    row.release()
    engine.pool.check_consistency(expect_all_free=True)


@pytest.mark.parametrize("how", ["finish", "cancel", "deadline"])
def test_everything_is_free_after(built, how):
    engine, _, spec = built
    sched = engine.scheduler
    rng = np.random.RandomState(1)
    prompts = [rng.randint(1, spec.vocab_size, size=n).tolist()
               for n in (3, 20, 45)]
    if how == "finish":
        out = engine.generate(prompts, max_new_tokens=40)
        assert [len(o) for o in out] == [40, 40, 40]
    else:
        streams = [sched.submit(p, max_new_tokens=60,
                                deadline_ms=6e4 if how == "deadline"
                                else None) for p in prompts]
        for _ in range(12):
            sched.step()
        if how == "cancel":
            assert all(st.cancel() for st in streams)
            wanted = RequestCancelled
        else:
            for st in streams:          # the deadline passes mid-decode
                st.deadline = time.monotonic()
            sched.step()
            wanted = DeadlineExceeded
        for st in streams:
            with pytest.raises(wanted):
                st.result(timeout=5)
        engine.pool.check_consistency()
        sched.drain()
    engine.pool.check_consistency(expect_all_free=True)
    assert engine.healthz()["kv_consistent"]


def test_scheduler_counters_and_health(built):
    engine, _, spec = built
    s0 = dict(engine.scheduler.stats)
    prompts = [[5, 9, 2], list(range(1, 31))]
    out = engine.generate(prompts, max_new_tokens=5 * WINDOW)
    s1 = engine.scheduler.stats
    assert s1["prefill_tokens"] - s0["prefill_tokens"] == 33
    assert s1["decode_tokens"] - s0["decode_tokens"] == \
        sum(len(o) - 1 for o in out)
    assert s1["kv_window_pages_returned"] > s0["kv_window_pages_returned"]
    routed = s1["moe_tokens_routed"] - s0["moe_tokens_routed"]
    tokens = 33 + sum(len(o) - 1 for o in out)
    assert routed == tokens * spec.experts_per_token * spec.layers
    busiest = s1["moe_expert_max_tokens"] - s0["moe_expert_max_tokens"]
    assert routed / spec.experts <= busiest <= routed
    assert s1["moe_decode_experts_touched"] > s0["moe_decode_experts_touched"]
    kv = engine.healthz()["kv"]
    assert kv["window"]["row_pages_max"] <= WINDOW // PS + 1
    assert kv["window"]["pages_returned"] >= s1["kv_window_pages_returned"]
    assert engine.expert_counts() is not None
    engine.pool.check_consistency(expect_all_free=True)
    # the grouped kernels' two work lists, a decode program: the full
    # layers' pool and the sliding layers', counted a step and a list
    walk = engine.stats["paged_walk"]
    assert walk == engine.healthz()["paged_walk"]
    assert len(walk) == len(engine.config.decode_buckets)
    assert all(name.startswith("serve_decode_b") for name in walk)
    for program in walk.values():
        assert program["window"]["tokens"] == WINDOW
        for found in (program, program["window"]):
            assert found["chunk_tokens"] % PS == 0 < found["grid_steps"]
        # a window of 8 tokens touches 3 pages of 4: one chunk at most
        assert program["window"]["chunk_tokens"] <= 3 * PS
    from paddle_tpu.ops.paged_attention import chunks_of
    found = engine.paged_walk_for(len(prompts))
    assert found in walk.values()
    want = sum(
        chunks_of(n, found["chunk_tokens"])
        + chunks_of(n, found["window"]["chunk_tokens"], page_size=PS,
                    window=WINDOW)
        for p, o in zip(prompts, out)
        for n in range(len(p) + 1, len(p) + len(o)))
    walked = s1["paged_chunks_walked"] - s0["paged_chunks_walked"]
    grid = s1["paged_grid_steps"] - s0["paged_grid_steps"]
    assert walked == want > 0
    assert walked <= grid
    assert grid == (s1["occupancy_steps"] - s0["occupancy_steps"]) * (
        found["grid_steps"] + found["window"]["grid_steps"])


# -- the spec ---------------------------------------------------------------

def test_spec_round_trips_lists_floats_and_strings(built):
    _, _, spec = built
    d = json.loads(json.dumps(spec.to_dict()))
    back = ModelSpec.from_dict(d)
    assert back == spec
    assert back.layer_types == ("sliding", "sliding", "sliding", "full") * 2
    assert isinstance(back.norm_eps, float) and back.norm_eps == 1e-6
    assert back.norm == "rms" and back.tie_head is False
    assert isinstance(back.layers, int)
    assert ModelSpec.from_dict({"hidden": "128", "heads": 8.0}).hidden == 128
    assert ModelSpec.from_dict({"unknown": 1}) == ModelSpec()


@pytest.mark.parametrize("bad", [
    dict(norm="batch"), dict(positions="alibi"), dict(ffn="glu"),
    dict(heads=4, kv_heads=3), dict(layer_types=("full",)),
    dict(layer_types=("sliding", "full")), dict(ffn="moe"),
    dict(layer_types=("full", "strided"))])
def test_spec_refuses(bad):
    with pytest.raises(ValueError):
        ModelSpec(**bad)


def test_gpt_spec_has_the_defaults_and_the_same_parameters():
    spec = ModelSpec(vocab_size=128, hidden=64, layers=2, heads=4)
    assert (spec.n_kv_heads, spec.head_dim) == (4, 16)
    assert spec.window_layers == () and spec.global_layers == (0, 1)
    p = init_params(spec, 1)
    assert "pos" in p and "head" not in p and "h0.ln1.b" in p
    assert p["h1.attn.wk"].shape == (64, 64)


def test_int8_is_refused_for_the_new_kinds(built):
    _, params, spec = built
    with pytest.raises(ValueError, match="int8"):
        ServeConfig(precision="int8").normalized(spec)


def test_served_model_dir_round_trips(tmp_path, built, config):
    """save_served_model / load_engine (what ``python -m paddle_tpu.serving
    --model`` builds from) keep the new fields and the tokens."""
    engine, params, spec = built
    cfg = ServeConfig.from_dict(config["serve"])
    path = save_served_model(str(tmp_path / "m"), spec, params, cfg)
    meta = json.load(open(os.path.join(path, "serve_config.json")))
    assert meta["model"]["layer_types"][:4] == ["sliding"] * 3 + ["full"]
    assert meta["model"]["norm_eps"] == 1e-6
    loaded = load_engine(path)
    try:
        assert loaded.spec == spec
        assert loaded.pool.window_pool is not None
        prompts = [[7, 8, 9], list(range(2, 25))]
        assert loaded.generate(prompts, max_new_tokens=12) == \
            engine.generate(prompts, max_new_tokens=12)
        # and the toy path of ``--spec``: a JSON string of the same fields
        again = ModelSpec.from_dict(json.loads(json.dumps(spec.to_dict())))
        assert again == loaded.spec
    finally:
        loaded.close()


def test_kv_page_budget_prices_the_kv_heads():
    assert kv_page_budget(33, "bf16", 128, kv_heads=4) == 65
    assert kv_page_budget(33, "bf16", 128) == 65
    assert kv_page_budget(33, "fp32", 16, kv_heads=2) == 33
    assert kv_page_budget(33, "int8", 16, kv_heads=4) == \
        kv_page_budget(33, "int8", 16)


# -- pieces of the block -----------------------------------------------------

def test_yarn_frequencies_match_the_published_numbers(runner):
    """corr(32) = 18.08 and corr(1) = 34.98 for head 128, base 500000,
    L0 8192: ramp from pair 18 to pair 35; attention factor 0.1 ln 16 + 1.
    The program's table and the reference's agree."""
    ref = _load("reference", "mellum_serve")
    rope = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1}
    freqs, factor = ref.inv_frequencies(rope, 128)
    plain, one = ref.inv_frequencies({"rope_theta": 500000}, 128)
    assert one == 1.0 and abs(factor - (0.1 * np.log(16) + 1)) < 1e-12
    np.testing.assert_allclose(freqs[:19], plain[:19])
    np.testing.assert_allclose(freqs[35:], plain[35:] / 16)
    assert plain[20] / 16 < freqs[20] < plain[20]
    spec = ModelSpec(hidden=256, heads=2, head_size=128, positions="rotary",
                     rope_theta=500000.0, yarn_factor=16.0,
                     yarn_original_len=8192, layer_types=("sliding", "full"),
                     window=4)
    tables = serve_model._rope_tables(spec, jnp.arange(5))
    pos = np.arange(5)[:, None]
    np.testing.assert_allclose(np.asarray(tables["full"][0]),
                               np.cos(pos * freqs) * factor, atol=1e-6)
    np.testing.assert_allclose(np.asarray(tables["sliding"][1]),
                               np.sin(pos * plain), atol=1e-6)


@pytest.mark.parametrize("window", [0, 700])
@pytest.mark.parametrize("length", [2048, 1300, 400])
def test_blocked_prefill_attention_equals_dense(window, length):
    """Buckets past 1024 take the blocked form: against plain attention
    with the causal, length and window masks, grouped heads."""
    s, h, kvh, d = 2048, 4, 2, 8
    spec = ModelSpec(hidden=32, heads=h, kv_heads=kvh, head_size=d)
    rng = np.random.RandomState(window + length)
    q = rng.randn(s, h, d).astype(np.float32)
    k = rng.randn(s, kvh, d).astype(np.float32)
    v = rng.randn(s, kvh, d).astype(np.float32)
    got = np.asarray(serve_model._prefill_attention(
        spec, jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        jnp.int32(length), window)).reshape(s, h, d)
    pos = np.arange(s)
    seen = (pos[None, :] <= pos[:, None]) & (pos[None, :] < length)
    if window:
        seen &= pos[:, None] - pos[None, :] < window
    for j in range(h):
        att = q[:length, j] @ k[:, j // (h // kvh)].T / np.sqrt(d)
        att = np.where(seen[:length], att, -np.inf)
        w = np.exp(att - att.max(-1, keepdims=True))
        want = (w / w.sum(-1, keepdims=True)) @ v[:, j // (h // kvh)]
        np.testing.assert_allclose(got[:length, j], want,
                                   atol=2e-5, rtol=2e-5)
    assert np.all(np.isfinite(got))


def test_published_config_maps_to_the_published_widths(runner):
    with open(os.path.join(BENCH, "configs",
                           "mellum2-12b-a2p5b-serve.json")) as f:
        cfg = json.load(f)
    spec = runner.spec_from_config(cfg)
    assert (spec.hidden, spec.heads, spec.n_kv_heads, spec.head_dim) == \
        (2304, 32, 4, 128)
    assert (spec.experts, spec.experts_per_token, spec.expert_width) == \
        (64, 8, 896)
    assert spec.vocab_size == 98304 and spec.window == 1024
    assert spec.layers == 12 and len(cfg["layer_types"]) == 28
    assert spec.layer_types == ("sliding", "sliding", "sliding", "full") * 3
    assert spec.yarn_factor == 16 and spec.yarn_original_len == 8192
    assert not spec.tie_head and spec.norm_eps == 1e-6
    assert cfg["serve"]["prefill_buckets"] == [512, 1024, 2048, 4096, 8192]
    assert cfg["serve"]["decode_buckets"] == [8, 16, 32, 64]
    import costs_lm
    assert costs_lm.expert_params(2304, 896) == 6193152
    facts = runner.model_facts(cfg, spec, ServeConfig.from_dict(cfg["serve"]))
    # 12 layers' projections, router and 8 experts; the head apart
    assert costs_lm.layer_params(facts) == 12 * (
        2 * 2304 * 4096 + 2 * 2304 * 512 + 2304 * 64 + 8 * 6193152)
    assert costs_lm.head_params(facts) == 2304 * 98304


def test_counter_readers_of_the_new_metrics():
    run = {"counters": {"moe_tokens_routed": 6400, "moe_expert_max_tokens":
                        150, "prefill_tokens": 1000, "decode_tokens": 500,
                        "admitted": 4},
           "model": {"experts": 64, "layers": 2, "heads": 4, "kv_heads": 2,
                     "head_dim": 16, "hidden": 64, "vocab_size": 256,
                     "experts_per_token": 2, "expert_width": 32},
           "peak": {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11},
           "seconds": 2.0, "chips": 1}
    load = _load("layer_metrics", "expert_load_max_over_mean").read(run)
    assert load == 64 * 150 / 6400
    mfu = _load("layer_metrics", "serve_mfu_pct.mellum2").read(run)
    import costs_lm
    # every token through the layers; the head a prompt and a decoded row
    flops = 2 * (costs_lm.layer_params(run["model"]) * 1500
                 + costs_lm.head_params(run["model"]) * (4 + 500))
    assert flops == costs_lm.serve_flops(run["model"], 1000, 4, 500)
    assert abs(mfu - 100 * flops / 2e12) < 1e-9
    for name in ("expert_load_max_over_mean", "serve_mfu_pct.mellum2",
                 "moe_decode_roofline", "moe_prefill_roofline",
                 "paged_attn_gqa_roofline", "moe_ms_per_decode_step",
                 "attn_window_ms_per_step"):
        # nothing to read (an older program, an untraced run): no value
        assert _load("layer_metrics", name).read(
            {"counters": {}, "trace": None, "peak": None}) is None


def test_roofline_costs_count_each_byte_once():
    import costs_lm
    f, n = costs_lm.moe_decode(64, 64, 2304, 896, 8, 2)
    assert n == (64 * 6193152 + 2 * 64 * 2304) * 2      # 792 MB of experts
    assert f == 2 * 64 * 8 * 6193152
    f, n = costs_lm.paged_decode_gqa(1000, 2, 32, 4, 128, 2)
    assert n == (2 * 1000 * 512 + 2 * 2 * 4096) * 2     # once a KV head
    assert f == 4 * 1000 * 4096                          # every query head
    f, n = costs_lm.moe_prefill(100, 64, 2304, 896, 8, 2)
    assert f == 2 * 800 * 6193152


# -- the GPT block, before and after ---------------------------------------------

GOLDEN = {  # PR 26's tree on this backend (jax 0.9.0, XLA:CPU), float32
    "sha1": "2180e5c653d7fdb98cc0280302849f3e0d39712e",
    "tokens": [3, 3, 3, 183],
    "corner": ["0x1.3052780000000p-4", "0x1.1bca300000000p-5",
               "0x1.75e5280000000p-3", "0x1.2651320000000p-1",
               "-0x1.79eb480000000p-4", "-0x1.2a45f20000000p-3",
               "0x1.e77fbe0000000p-3", "0x1.2288ee0000000p-1",
               "0x1.a2f82a0000000p-4", "-0x1.5319b00000000p-4",
               "0x1.14f04e0000000p-2", "0x1.a847fa0000000p-1",
               "-0x1.2238b00000000p-3", "0x1.b28aa80000000p-4",
               "0x1.f31e440000000p-3", "0x1.f6f7400000000p-2"]}


def test_gpt_tiny_preset_logits_are_bit_identical_to_before():
    """``tiny-serve.json`` (the GPT-2 block) through the spec-driven
    functions: prefill and three decode steps give the bits the hard-coded
    block gave (recorded from the parent commit on this backend)."""
    with open(os.path.join(BENCH, "configs", "tiny-serve.json")) as f:
        cfg = json.load(f)
    spec = ModelSpec(**cfg["model"])
    engine = ServingEngine(spec, init_params(spec, 5),
                           ServeConfig.from_dict(cfg["serve"]))
    try:
        prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3]
        pool = engine.pool
        pages = pool.alloc(pool.pages_needed(len(prompt) + 4))
        table = pool.null_padded_table(pages, engine.max_pages_per_seq)
        nxt, logits = engine.prefill_logits(prompt, table)
        rows, toks = [logits], [nxt]
        for j in range(3):
            nxt, logits = engine.decode_logits(
                np.asarray(toks[-1:], np.int32),
                np.asarray([len(prompt) + j], np.int32), table[None])
            rows.append(logits[0])
            toks.append(int(nxt[0]))
        pool.free(pages)
        rows = np.stack(rows).astype(np.float32)
        assert toks == GOLDEN["tokens"]
        assert [float(x).hex() for x in rows[:, :4].ravel()] == \
            GOLDEN["corner"]
        assert hashlib.sha1(rows.tobytes()).hexdigest() == GOLDEN["sha1"]
        assert sorted(engine.stats["program_bytes"]) == sorted(
            [f"serve_prefill_s{s}" for s in cfg["serve"]["prefill_buckets"]]
            + [f"serve_decode_b{b}" for b in cfg["serve"]["decode_buckets"]])
    finally:
        engine.close()
