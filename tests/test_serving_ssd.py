"""A decoder whose every layer runs a Mamba-2 mixer in parallel with
grouped-query attention (Falcon-H1's shape) through the serving stack, at
the tiny preset ``benchmarks/configs/tiny-falconh1-serve.json`` cut further
for speed (chunks of 16, pages of 4, buckets to 64): hidden 64, 10 query /
2 KV heads of 16, Mamba-2 of 4 heads of 16 in 2 groups with a state of 32,
4 layers, every µP multiplier as published, seeded random weights, float32:

 - prefill then decode through the cache (pages and a state slot) against
   the plain reference's full forward (``benchmarks/reference/
   falconh1_serve.py``, the recurrence one position a step) on logits and
   on every layer's state: prompts off and on the scan's chunk edges,
   shorter than their bucket, pages crossed in decode;
 - the chunked scan against the sequential recurrence, state and y;
 - rows of unlike lengths in one decode bucket bit-identical to each row
   alone; a slot released and taken again;
 - the decode state update's Pallas kernel (interpret mode) against its
   twin, the loop over the rows: ``y``, the rows' slots, slots no row owns,
   padding rows on the null slot, the whole Mamba-2 branch, the dispatch
   counter;
 - ``ModelSpec``'s explicit FFN width and ``StateSlots``' convolution
   channels; the counters and ``/healthz``; ``costs_ssd.py`` against hand
   arithmetic; the cell's rehearsal on the CPU.
"""
import copy
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for _p in (BENCH, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from paddle_tpu.serving import ModelSpec, init_params, ssm  # noqa: E402
from paddle_tpu.serving.kv_cache import StateSlots  # noqa: E402

CHUNK, PS, STEPS = 16, 4, 5


def _load(kind, name):
    import importlib.util
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"s_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", "tiny-falconh1-serve.json")) as f:
        cfg = copy.deepcopy(json.load(f))
    cfg["mamba_chunk_size"] = CHUNK
    cfg["serve"].update(max_seq_len=128, page_size=PS, kv_pages=256,
                        prefill_buckets=[16, 32, 64], decode_buckets=[2, 4],
                        max_new_tokens=8)
    cfg["check"] = {"pads": [32, 80], "compare": 4}
    return cfg


@pytest.fixture(scope="module")
def runner():
    return _load("runners", "serve_ssd")


@pytest.fixture(scope="module")
def ref():
    return _load("reference", "falconh1_serve")


@pytest.fixture(scope="module")
def built(config, runner):
    engine, params, spec, _ = runner.build_engine(config, 5)
    yield engine, params, spec
    engine.close()


def test_the_spec_is_parallel_layers_with_every_multiplier(built, config):
    _, params, spec = built
    assert spec.layer_types == ("parallel",) * 4
    assert spec.global_layers == spec.ssm_layers == (0, 1, 2, 3)
    assert spec.n_kv_heads == 2 and spec.heads == 10 and spec.head_dim == 16
    assert spec.ffn_dim == 336 and spec.ssm_conv_channels == 64 + 2 * 2 * 32
    assert spec.ssm_mults == tuple(config["ssm_multipliers"])
    assert spec.key_mult == config["key_multiplier"] != 1.0
    assert params["h0.ssm.win"].shape == (64, 64 + 192 + 4)
    assert params["head"].shape == (64, 256)
    assert ModelSpec.from_dict(json.loads(json.dumps(spec.to_dict()))) == spec


@pytest.mark.parametrize("kw", [
    dict(layer_types=("parallel",)),
    dict(layer_types=("parallel",), ssm_heads=3),
    dict(layer_types=("ssm",), ssm_heads=4, ssm_dt_rank=4)],
    ids=["no_heads", "heads_not_dividing", "mamba1_with_heads"])
def test_the_spec_refuses_a_mixer_it_cannot_run(kw):
    with pytest.raises(ValueError):
        ModelSpec(layers=1, ssm_inner=64, ssm_state=8, **kw)


def test_an_explicit_ffn_width():
    """``ffn_width`` where the width is no whole multiple of the hidden
    size; without it ``hidden * ffn_mult`` as before."""
    spec = ModelSpec(hidden=64, heads=4, ffn="swiglu", ffn_width=100)
    p = init_params(spec, 0)
    assert spec.ffn_dim == 100
    assert p["h0.mlp.wgu"].shape == (64, 200)
    assert p["h0.mlp.wd"].shape == (100, 64)
    plain = ModelSpec(hidden=64, heads=4, ffn_mult=3)
    assert plain.ffn_dim == 192
    assert init_params(plain, 0)["h0.mlp.w1"].shape == (64, 192)


def test_state_slots_take_convolution_channels_apart():
    mamba2 = StateSlots(layers=2, slots=3, inner=64, state=32, conv=4,
                        conv_channels=192, heads=4)
    assert mamba2.conv.shape == (2, 3, 3, 192)
    assert mamba2.ssm.shape == (2, 3, 4, 16, 32)
    assert mamba2.slot_bytes == 2 * (3 * 192 * 4 + 64 * 32 * 4)
    slot = mamba2.take()
    snap = mamba2.snapshot()
    assert snap["held"] == 1 and snap["held_bytes"] == mamba2.slot_bytes
    mamba2.release(slot)
    assert mamba2.snapshot()["held_bytes"] == 0
    mamba1 = StateSlots(layers=2, slots=3, inner=64, state=16, conv=4)
    assert mamba1.conv.shape == (2, 3, 3, 64)
    assert mamba1.ssm.shape == (2, 3, 16, 64)


@pytest.mark.parametrize("s,chunk,length", [
    (48, 16, 48), (48, 16, 33), (10, 16, 10), (64, 64, 64), (32, 8, 1)],
    ids=["chunks", "past_length", "shorter_than_a_chunk", "one_chunk",
         "one_token"])
def test_the_chunked_scan_is_the_recurrence(ref, s, chunk, length):
    rng = np.random.RandomState(s + length)
    h, p, g, n = 4, 8, 2, 6
    x = rng.randn(s, h, p).astype(np.float32)
    delta = (np.abs(rng.randn(s, h)) * 0.3).astype(np.float32)
    delta[length:] = 0.0            # how a caller stops at the length
    a = -np.arange(1, h + 1, dtype=np.float32)
    b, c = (rng.randn(s, g, n).astype(np.float32) for _ in range(2))
    y, state = ssm.chunked_scan(x, delta, a, b, c, chunk)
    want_y, want_state = ref.mamba2_scan(*map(jnp.asarray, (x, delta, a, b, c)),
                                         upto=length)
    np.testing.assert_allclose(np.asarray(y)[:length],
                               np.asarray(want_y)[:length], atol=2e-5)
    np.testing.assert_allclose(state, want_state, rtol=2e-5, atol=2e-6)


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
    state = np.asarray(engine.pool.state_slots.ssm)[:, row.slot]
    slot = row.slot
    row.release()
    return np.stack(rows), toks, state, slot


def _reference(ref, params, config, prompt, toks, steps):
    n = len(prompt)
    pad = -(-(n + steps) // 16) * 16
    seq = np.zeros((pad,), np.int32)
    seq[:n + steps] = list(prompt) + toks[:steps]
    logits, states = ref.forward(params, jnp.asarray(seq), np.int32(n - 1),
                                 cfg=config, rows=steps + 1)
    return np.asarray(logits), np.asarray(states)


@pytest.mark.parametrize("n", [1, 15, 16, 17, 33, 64],
                         ids=["one_token", "under_a_chunk", "a_chunk",
                              "over_a_chunk", "pages_past", "bucket_edge"])
def test_the_engine_against_the_reference(built, ref, config, n):
    """Prefill then decode steps (each crosses a page of 4 now and then)
    through pages and a slot: logits and every layer's state against the
    reference's full forward.  The logits are 1/128 of the usual scale."""
    engine, params, _ = built
    prompt = list(np.random.RandomState(n).randint(0, 256, n))
    got, toks, state, _ = _through_the_cache(engine, prompt, STEPS)
    want, want_state = _reference(ref, params, config, prompt, toks, STEPS)
    scale = np.abs(want).max()
    np.testing.assert_allclose(got, want, atol=2e-5 * scale)
    np.testing.assert_allclose(state, want_state, rtol=1e-4,
                               atol=1e-5 * np.abs(want_state).max())


def test_rows_of_unlike_lengths_are_bit_identical_to_each_alone(built):
    engine, _, _ = built
    prompts = [list(np.random.RandomState(k).randint(0, 256, n))
               for k, n in ((1, 5), (2, 30), (3, 17))]
    alone = [_through_the_cache(engine, p, 3)[0] for p in prompts]
    rows = [engine.pool.admit_row(len(p), 4, engine.max_pages_per_seq)
            for p in prompts]
    got = [[engine.prefill_logits(p, r.table)] for p, r in zip(prompts, rows)]
    toks = [g[0][0] for g in got]
    lens = np.asarray([len(p) for p in prompts], np.int32)
    for k in range(3):
        for r, n in zip(rows, lens):
            r.advance(int(n) + k)
        nxt, logits = engine.decode_logits(   # 3 rows and a padding row
            np.asarray(toks, np.int32), lens + k,
            np.stack([r.table for r in rows]))
        for i in range(3):
            got[i].append((None, logits[i]))
        toks = [int(t) for t in nxt]
    for r in rows:
        r.release()
    for i in range(3):
        together = np.stack([got[i][0][1]] + [g[1] for g in got[i][1:]])
        np.testing.assert_array_equal(together, alone[i])


def test_a_released_slot_is_taken_again_whole(built, ref, config):
    """The next prefill writes its slot whole: what the row before left
    there is never read."""
    engine, params, _ = built
    first = list(np.random.RandomState(7).randint(0, 256, 40))
    second = list(np.random.RandomState(8).randint(0, 256, 9))
    *_, slot = _through_the_cache(engine, first, 4)
    got, toks, state, again = _through_the_cache(engine, second, 4)
    assert again == slot                        # LIFO: the same slot
    want, want_state = _reference(ref, params, config, second, toks, 4)
    np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max())
    np.testing.assert_allclose(state, want_state, rtol=1e-4,
                               atol=1e-5 * np.abs(want_state).max())


def test_the_counters_and_healthz(built):
    engine, _, spec = built
    sched = engine.scheduler
    before = dict(sched.stats)
    prompts = [list(np.random.RandomState(k).randint(0, 256, n))
               for k, n in ((11, 7), (12, 16), (13, 40))]
    engine.generate(prompts, max_new_tokens=3)
    sched.snapshot()
    chunks = sum(-(-len(p) // CHUNK) for p in prompts) * 4
    assert sched.stats["ssd_chunks_scanned"] - \
        before["ssd_chunks_scanned"] == chunks
    assert sched.stats["ssm_tokens_scanned"] - \
        before["ssm_tokens_scanned"] == sum(map(len, prompts))
    state = engine.healthz()["kv"]["state"]
    assert state["slot_bytes"] == engine.pool.state_slots.slot_bytes > 0
    assert state["held_bytes"] == state["held"] * state["slot_bytes"] == 0
    engine.pool.check_consistency(expect_all_free=True)
    from paddle_tpu.observability.metrics import get_registry
    held = get_registry().gauge("pt_serve_state_bytes",
                                labelnames=("state",)).snapshot_values()
    assert held[("held",)] == 0
    assert held[("free",)] == state["free"] * state["slot_bytes"]


def _update_operands(rows, slots=70, layers=4, seed=0, h=4, p=16, n=32, g=2):
    """A slot pool and one decode step's update inputs at the tiny widths;
    row r owns slot ``taken[r]`` (none owns the null slot 0)."""
    rng = np.random.RandomState(seed + rows)
    pool = rng.randn(layers, slots, h, p, n).astype(np.float32)
    taken = (rng.permutation(slots - 1)[:rows] + 1).astype(np.int32)
    decay = rng.uniform(0.5, 1.0, (rows, h)).astype(np.float32)
    dx = rng.randn(rows, h, p).astype(np.float32)
    bmat, cmat = (rng.randn(rows, g, n).astype(np.float32) for _ in "bc")
    return pool, taken, decay, dx, bmat, cmat


def _update(pool, layer, taken, *ins, use_pallas):
    from paddle_tpu.ops.ssd_update import ssd_state_update
    y, out = ssd_state_update(jnp.asarray(pool), layer, jnp.asarray(taken),
                              *map(jnp.asarray, ins), use_pallas=use_pallas,
                              interpret=True)
    return np.asarray(y), np.asarray(out)


@pytest.mark.parametrize("rows", [8, 64])
def test_the_state_update_kernel_is_the_loop(rows):
    """The Pallas kernel (interpret mode) against its twin, a loop over the
    rows, at the decode buckets 8 and 64: `y` and every slot a row owns;
    every other slot of every layer comes out as it went in."""
    pool, taken, *ins = _update_operands(rows)
    y, got = _update(pool, 2, taken, *ins, use_pallas=True)
    want_y, want = _update(pool, 2, taken, *ins, use_pallas=False)
    np.testing.assert_allclose(y, want_y, rtol=1e-6,
                               atol=1e-6 * np.abs(want_y).max())
    np.testing.assert_allclose(got[2, taken], want[2, taken], rtol=1e-6,
                               atol=1e-6 * np.abs(want[2, taken]).max())
    assert not np.array_equal(got[2, taken], pool[2, taken])
    untouched = np.ones(pool.shape[:2], bool)
    untouched[2, taken] = False
    np.testing.assert_array_equal(got[untouched], pool[untouched])


def test_padding_rows_on_the_null_slot_leave_live_slots_alone():
    """A bucket's padding rows point at slot 0: the live rows' slots and
    outputs are those of the live rows updated alone, and no slot but
    theirs and the null slot moves."""
    pool, taken, *ins = _update_operands(8, seed=3)
    padded = taken.copy()
    padded[5:] = 0
    y, got = _update(pool, 1, padded, *ins, use_pallas=True)
    alone_y, alone = _update(pool, 1, taken[:5], *(a[:5] for a in ins),
                             use_pallas=True)
    np.testing.assert_array_equal(y[:5], alone_y)
    np.testing.assert_array_equal(got[1, taken[:5]], alone[1, taken[:5]])
    untouched = np.ones(pool.shape[:2], bool)
    untouched[1, taken[:5]] = untouched[1, 0] = False
    np.testing.assert_array_equal(got[untouched], pool[untouched])


def test_mamba2_decode_takes_the_kernel_as_it_took_the_loop(config, runner,
                                                            monkeypatch):
    """The whole Mamba-2 branch of a decode step with the kernel forced
    (interpret mode, as on the chip) against the twin: outputs, tails and
    pool."""
    from paddle_tpu.framework import device as _device
    spec = runner.spec_from_config(config)
    params = init_params(spec, 11)
    rng = np.random.RandomState(11)
    rows = 8
    pool, taken, *_ = _update_operands(rows, h=spec.ssm_heads,
                                       p=spec.ssm_inner // spec.ssm_heads,
                                       n=spec.ssm_state)
    n = jnp.asarray(rng.randn(rows, spec.hidden).astype(np.float32))
    tail = jnp.asarray(rng.randn(rows, spec.ssm_conv - 1,
                                 spec.ssm_conv_channels).astype(np.float32))
    got = {}
    for on_chip in (True, False):
        monkeypatch.setattr(_device, "pallas_dispatch", lambda: on_chip)
        got[on_chip] = [np.asarray(a) for a in ssm.mamba2_decode(
            spec, params, "h1.ssm", n, tail, jnp.asarray(pool), 1,
            jnp.asarray(taken))]
    for kernel, loop in zip(got[True], got[False]):
        np.testing.assert_allclose(kernel, loop, rtol=1e-6,
                                   atol=1e-6 * np.abs(loop).max())
    assert not np.array_equal(got[True][2], pool)


@pytest.mark.parametrize("on_chip,path", [(True, "pallas"),
                                          (False, "fallback")])
def test_the_state_update_books_its_dispatch(monkeypatch, on_chip, path):
    from paddle_tpu.framework import device as _device
    from paddle_tpu.observability.metrics import get_registry, \
        reset_registry
    from paddle_tpu.observability.telemetry import get_telemetry
    from paddle_tpu.ops.ssd_update import ssd_state_update
    monkeypatch.setattr(_device, "pallas_dispatch", lambda: on_chip)
    monkeypatch.setattr(get_telemetry(), "enabled", True)
    pool, taken, *ins = _update_operands(8)
    reset_registry()
    try:
        ssd_state_update(jnp.asarray(pool), 0, jnp.asarray(taken),
                         *map(jnp.asarray, ins))
        c = get_registry().counter("pt_pallas_calls_total",
                                   labelnames=("kernel", "path"))
        booked = {p: c.value(kernel="ssd_state_update", path=p)
                  for p in ("pallas", "fallback")}
    finally:
        reset_registry()
    assert booked == {p: int(p == path) for p in booked}


def test_costs_ssd_by_hand(runner, config):
    """Falcon-H1-34B's widths: the mixer's 68.34 M matrix parameters, the
    chunked form's 5.37 MFLOP a position, a layer's 430.1 M."""
    import costs_ssd
    with open(os.path.join(BENCH, "configs", "falcon-h1-34b-serve.json")) as f:
        full = json.load(f)
    spec = runner.spec_from_config(full)
    from paddle_tpu.serving import ServeConfig
    m = runner.model_facts(spec, ServeConfig.from_dict(full["serve"]))
    assert costs_ssd.ssd_params(m) == (5120 * 9248 + 4 * 5120
                                       + 4096 * 5120) == 68_341_760
    assert costs_ssd.chunk_flops(m) == 2 * (2 * 128 * 256 + 4096 * 128
                                            + 2 * 4096 * 256) == 5_373_952
    assert costs_ssd.layer_params(m) == (2 * 5120 * 2560 + 2 * 5120 * 512
                                         + 68_341_760 + 3 * 5120 * 21504)
    flops, nbytes = costs_ssd.ssd_decode(64, m)
    assert flops == 64 * (2 * 68_341_760 + 6 * 4096 * 256)
    assert nbytes == (68_341_760 * 2 + 64 * (2 * 4096 * 256 * 4
                                             + 2 * 3 * 5120 * 2
                                             + 2 * 5120 * 2))
    flops, nbytes = costs_ssd.ssd_prefill(1000, m)
    assert flops == 1000 * (2 * 68_341_760 + 5_373_952)
    assert nbytes == (68_341_760 * 2 + 1000 * 2 * 5120 * 2
                      + 4096 * 256 * 4 + 3 * 5120 * 2)
    head = 5120 * 261120
    assert costs_ssd.serve_flops(m, 100, 2, 10) == 2 * (
        4 * costs_ssd.layer_params(m) * 110 + head * 12)


@pytest.mark.parametrize("name", ["ssd_decode_roofline",
                                  "ssd_prefill_roofline",
                                  "serve_mfu_pct.falconh1"])
def test_the_new_readers_read_nothing_without_mamba2(name):
    reader = _load("layer_metrics", name)
    phi = {"layers": 2, "ssm_inner": 64, "layer_types": ["ssm", "full"]}
    assert reader.read({"model": phi, "counters": {"prefill_tokens": 1},
                        "peak": {"flops_bf16": 1.0}, "seconds": 1.0}) is None
    assert reader.read({}) is None


def test_the_cell_rehearses_on_the_cpu():
    # the window has to hold a decode step after the ramp's prefills of up
    # to 4,096 tokens, on a CPU that other test workers share
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "tiny-falconh1-serve.longform-backlog", "--seconds", "10",
         "--allow-cpu"], capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line
    assert "serve_tokens_per_s" in line["metric_names"] or \
        "serve_tokens_per_s" in line["counted"]
    assert line["notes"]["ssd_chunks_scanned"] > 0
    assert line["notes"]["state_slots"]["slot_bytes"] > 0
