"""The scheduler launches a decode step before it reads the one before
(PR 35): the token streams are those of a synchronous loop, whatever
joins, ends, is evicted or fails while a step is in flight.

The synchronous loop is test-local (``_solo``): one request alone,
``engine.prefill`` then ``engine.decode`` resolved at once every step.
The decode math is row-independent, so a request's tokens are the same
alone and woven through a batch: any difference here is the scheduler
feeding a row the wrong token, slot, position or page.

The same equality once each for a model with sliding-window layers and
routed experts (pages handed back while a step is in flight), one with
state-space layers (a state slot a row) and one of learned sparse
attention, at the tiny presets their own test files use.
"""
import copy
import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for _p in (BENCH, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from paddle_tpu.serving import (DeadlineExceeded, ModelSpec,  # noqa: E402
                                RequestCancelled, ServeConfig,
                                ServingEngine, init_params)
from paddle_tpu.serving.engine import (DecodeStep,  # noqa: E402
                                       aot_build_phase)
from paddle_tpu.serving.scheduler import ContinuousScheduler  # noqa: E402

SPEC = ModelSpec(vocab_size=64, hidden=32, layers=2, heads=2,
                 max_seq_len=64)
CFG = ServeConfig(decode_buckets=(2, 4, 8), prefill_buckets=(16,),
                  kv_pages=160, page_size=4, max_inflight=32,
                  max_new_tokens=16)
PROMPTS = [[1 + i, 7, 3 + 2 * i, 11, 5][: 2 + i % 4] for i in range(8)]


@pytest.fixture(scope="module")
def engine():
    eng = ServingEngine(SPEC, init_params(SPEC, seed=0), CFG)
    yield eng
    assert eng.unexpected_compiles == 0     # whatever the tests drove
    eng.close()


def _solo(engine, prompt, max_new, eos=-1):
    """The synchronous loop: one request alone, every step read at once."""
    row = engine.pool.admit_row(len(prompt), max_new,
                                engine.max_pages_per_seq)
    try:
        out = [engine.prefill(prompt, row.table)]
        pos = len(prompt)
        while len(out) < max_new and out[-1] != eos:
            row.advance(pos)
            nxt = np.asarray(engine.decode(
                np.asarray(out[-1:], np.int32), np.asarray([pos], np.int32),
                row.table[None]))
            out.append(int(nxt[0]))
            pos += 1
        return out
    finally:
        row.release()


def _baseline(engine):
    snap = engine.pool.snapshot()
    assert snap["used_pages"] == 0 and snap["reserved_pages"] == 0, snap
    engine.pool.check_consistency(expect_all_free=True)


def _record_steps(engine, monkeypatch):
    """Every `engine.decode` call's (rows, bucket, fed from the device)."""
    seen, orig = [], engine.decode

    def decode(tokens, positions, tables):
        fed = engine.decode_from is not None
        step = orig(tokens, positions, tables)
        seen.append((tokens.shape[0], step.bucket, fed))
        return step

    monkeypatch.setattr(engine, "decode", decode)
    return seen


def test_decode_returns_a_step_that_resolves_when_asked(engine):
    row = engine.pool.admit_row(3, 4, engine.max_pages_per_seq)
    try:
        first = engine.prefill([4, 5, 6], row.table)
        row.advance(3)
        step = engine.decode(np.asarray([first], np.int32),
                             np.asarray([3], np.int32), row.table[None])
        assert isinstance(step, DecodeStep)
        assert (step.rows, step.bucket, len(step)) == (1, 2, 1)
        got = np.asarray(step)
        assert got.shape == (1,) and got.dtype == np.int32
        assert step[0] == got[0] == list(step)[0] == step.read()[0]
        assert step.take_aux() == {}            # no routed experts here
        assert engine.decode_from is None
    finally:
        row.release()
    _baseline(engine)


def test_a_step_fed_from_the_device_gives_the_tokens_of_one_fed_by_hand(
        engine):
    """Rows 0 and 2 of a step of three go on from its device tokens, row
    1's slot a hole; the same rows fed the tokens read back agree."""
    def run(fed):
        rows = [engine.pool.admit_row(len(p), 6, engine.max_pages_per_seq)
                for p in PROMPTS[:3]]
        try:
            toks = [engine.prefill(p, r.table)
                    for p, r in zip(PROMPTS, rows)]
            pos = np.asarray([len(p) for p in PROMPTS[:3]], np.int32)
            for r, at in zip(rows, pos):
                r.advance(int(at))
            step = engine.decode(np.asarray(toks, np.int32), pos,
                                 np.stack([r.table for r in rows]))
            keep = np.asarray([0, 2])
            for r, at in zip(rows, pos + 1):
                r.advance(int(at))
            tables = np.stack([rows[0].table, rows[2].table])
            if fed:
                engine.decode_from = (step, keep)
                nxt = engine.decode(np.zeros((2,), np.int32),
                                    pos[keep] + 1, tables)
                assert (nxt.bucket, list(nxt.slots)) == (4, [0, 2])
            else:
                nxt = engine.decode(np.asarray(step)[keep], pos[keep] + 1,
                                    tables)
            return list(np.asarray(step)), list(np.asarray(nxt))
        finally:
            for r in rows:
                r.release()

    assert run(fed=True) == run(fed=False)
    _baseline(engine)


def test_mixed_batch_rows_ending_by_count_at_different_steps(engine,
                                                              monkeypatch):
    sched = ContinuousScheduler(engine)
    seen = _record_steps(engine, monkeypatch)
    lengths = [2, 5, 3, 9, 1, 12, 7, 4]
    streams = [sched.submit(p, max_new_tokens=n)
               for p, n in zip(PROMPTS, lengths)]
    sched.drain()
    monkeypatch.undo()
    for st, p, n in zip(streams, PROMPTS, lengths):
        assert st.result(timeout=5.0) == _solo(engine, p, n)
    s = sched.stats
    assert s["tokens_generated"] == sum(lengths)
    assert s["decode_tokens"] == sum(lengths) - len(lengths)
    assert s["occupancy_steps"] == s["steps"] == len(seen) == 11
    # ended rows are left out as holes: the rows fall while the bucket
    # stays, until a smaller program fits them (one step from the host)
    assert [n for n, _, _ in seen] == [7, 6, 5, 4, 3, 3, 2, 2, 1, 1, 1]
    assert [b for _, b, _ in seen] == [8, 8, 8, 4, 4, 4, 2, 2, 2, 2, 2]
    assert [f for _, _, f in seen] == [False, True, True, False, True, True,
                                       False, True, True, True, True]
    assert s["decode_steps_ahead"] == 8
    _baseline(engine)


def test_eos_mid_batch_drops_the_overrun_and_returns_its_pages(
        engine, monkeypatch):
    plain = [_solo(engine, p, 12) for p in PROMPTS[:4]]
    # a token some stream emits in its middle, for the first time there
    eos, hit = next(
        (t, i) for i, out in enumerate(plain) for j, t in enumerate(out)
        if 2 <= j < len(out) - 2 and out.index(t) == j)
    want = [out[: out.index(eos) + 1] if eos in out else out
            for out in plain]
    assert len(want[hit]) < 12
    monkeypatch.setattr(engine, "config", engine.config.replace(eos_id=eos))
    assert [_solo(engine, p, 12, eos) for p in PROMPTS[:4]] == want
    sched = ContinuousScheduler(engine)
    streams = [sched.submit(p, max_new_tokens=12) for p in PROMPTS[:4]]
    sched.drain()
    assert [st.result(timeout=5.0) for st in streams] == want
    s = sched.stats
    booked = s["tokens_generated"] - s["admitted"]
    assert booked == sum(len(w) - 1 for w in want)
    # the row that hit eos_id had been launched once more by then: that
    # step decoded it and its token was dropped
    assert s["decode_tokens"] > booked
    assert s["completed"] == 4 and s["failed"] == 0
    _baseline(engine)


def test_an_admission_mid_run_books_the_step_in_flight_first(engine,
                                                             monkeypatch):
    sched = ContinuousScheduler(engine)
    seen = _record_steps(engine, monkeypatch)
    a = sched.submit(PROMPTS[0], max_new_tokens=10)
    b = sched.submit(PROMPTS[1], max_new_tokens=10)
    for _ in range(3):
        sched.step()
    assert sched._flight is not None and len(a.tokens) == 3
    c = sched.submit(PROMPTS[2], max_new_tokens=6)
    sched.step()            # prefill behind the step in flight, then book it
    assert len(a.tokens) == 4 and len(c.tokens) == 1
    sched.drain()
    monkeypatch.undo()
    for st, p, n in ((a, PROMPTS[0], 10), (b, PROMPTS[1], 10),
                     (c, PROMPTS[2], 6)):
        assert st.result(timeout=5.0) == _solo(engine, p, n)
    # the step after the admission takes every row's token from the host
    assert [f for _, _, f in seen[:5]] == [False, True, True, False, True]
    assert seen[3][:2] == (3, 4)
    _baseline(engine)


def test_an_admission_that_needs_an_ending_rows_pages_books_its_step_first(
        engine):
    """A pool with room for one request and a second one queued: the
    first's pages come back when its last step is booked, so that step
    is read before the second is prefilled, and no refusal is counted
    for it."""
    pool = engine.pool
    held = pool.alloc(pool.snapshot()["free_pages"] - 3)    # room: 12 tokens
    try:
        sched = ContinuousScheduler(engine)
        a = sched.submit(PROMPTS[3], max_new_tokens=3)
        b = sched.submit(PROMPTS[2], max_new_tokens=4)
        sched.step()
        sched.step()                # a's last step launched, unread
        refused = sched.stats["refused_kv"]
        assert refused >= 1 and sched.stats["admitted"] == 1
        assert not a.done() and sched._flight is not None
        assert pool.snapshot()["used_pages"] > len(held)    # a holds its own
        sched.step()                # reads it, then prefills b
        assert a.done() and len(b.tokens) == 1
        assert sched.stats["admitted"] == 2
        assert sched.stats["refused_kv"] == refused
        sched.drain()
        assert a.result(timeout=5.0) == _solo(engine, PROMPTS[3], 3)
        assert b.result(timeout=5.0) == _solo(engine, PROMPTS[2], 4)
    finally:
        pool.free(held)
    _baseline(engine)


def test_cancel_and_deadline_with_a_step_in_flight(engine):
    sched = ContinuousScheduler(engine)
    keep = sched.submit(PROMPTS[0], max_new_tokens=9)
    gone = sched.submit(PROMPTS[1], max_new_tokens=9)
    late = sched.submit(PROMPTS[2], max_new_tokens=9, deadline_ms=60_000)
    sched.step()
    sched.step()
    assert sched._flight is not None
    used = engine.pool.snapshot()["used_pages"]
    assert sched.cancel(gone.request_id) is True
    assert engine.pool.snapshot()["used_pages"] < used      # at once
    with pytest.raises(RequestCancelled):
        gone.result(timeout=1.0)
    n_gone = len(gone.tokens)
    late.deadline = time.monotonic() - 1.0      # passes with a step unread
    sched.step()
    with pytest.raises(DeadlineExceeded):
        late.result(timeout=1.0)
    sched.drain()
    # the unread step's tokens for the two were dropped, not booked
    assert len(gone.tokens) == n_gone and len(late.tokens) < 9
    assert keep.result(timeout=5.0) == _solo(engine, PROMPTS[0], 9)
    assert sched.stats["cancelled"] == 2 and sched.stats["completed"] == 1
    _baseline(engine)


def test_a_row_whose_last_step_is_in_flight_can_still_be_cancelled(engine):
    sched = ContinuousScheduler(engine)
    st = sched.submit(PROMPTS[0], max_new_tokens=2)
    sched.step()                    # prefill + its one decode step, unread
    assert not sched._active and sched._flight is not None
    assert sched.snapshot()["active_sequences"] == 0   # snapshot read it
    assert st.result(timeout=1.0) == _solo(engine, PROMPTS[0], 2)
    st = sched.submit(PROMPTS[0], max_new_tokens=2)
    sched.step()
    assert sched.cancel(st.request_id) is True
    sched.drain()
    assert len(st.tokens) == 1
    _baseline(engine)


class _Poisoned(DecodeStep):
    """A step whose program failed: launching from it works (the failure
    is on the device), reading it raises."""

    def read(self):
        raise RuntimeError("device poison")


def test_a_step_that_fails_at_the_read_fails_every_unread_step(
        engine, monkeypatch):
    sched = ContinuousScheduler(engine)
    streams = [sched.submit(p, max_new_tokens=n)
               for p, n in zip(PROMPTS, (8, 8, 3))]
    sched.step()
    sched.step()            # the third request's last step is in flight
    orig, calls = engine.decode, []

    def decode(*args):
        step = orig(*args)
        calls.append(step)
        if len(calls) == 1:
            return _Poisoned(step._tokens, step._aux, step.slots,
                             step.bucket)
        return step

    monkeypatch.setattr(engine, "decode", decode)
    sched.step()            # reads the good step (two rows fit a smaller
    #                         program), then launches the poisoned one
    assert [st.done() for st in streams] == [False, False, True]
    sched.step()            # launches behind it, then reads it: boom
    assert len(calls) == 2
    for st in streams[:2]:
        with pytest.raises(RuntimeError, match="device poison"):
            st.result(timeout=1.0)
    assert streams[2].result(timeout=1.0) == _solo(engine, PROMPTS[2], 3)
    assert sched.stats["failed"] == 2 and sched._flight is None
    _baseline(engine)
    monkeypatch.undo()
    st = sched.submit(PROMPTS[3], max_new_tokens=5)     # the loop serves on
    sched.drain()
    assert st.result(timeout=5.0) == _solo(engine, PROMPTS[3], 5)
    _baseline(engine)


def test_the_counter_says_how_often_a_step_was_launched_ahead(engine):
    sched = ContinuousScheduler(engine)
    streams = [sched.submit(p, max_new_tokens=40) for p in PROMPTS[:4]]
    sched.drain()
    assert all(len(st.result(timeout=5.0)) == 40 for st in streams)
    s = sched.snapshot()
    assert s["occupancy_steps"] == 39 and s["decode_steps_ahead"] == 38
    assert s["decode_steps_ahead"] / s["occupancy_steps"] >= 0.9
    # every step behind an admission: none is launched ahead
    sched = ContinuousScheduler(engine)
    streams = []
    for p in PROMPTS[:6]:
        streams.append(sched.submit(p, max_new_tokens=2))
        sched.step()
    sched.drain()
    assert all(len(st.result(timeout=5.0)) == 2 for st in streams)
    assert sched.stats["occupancy_steps"] == 6
    assert sched.stats["decode_steps_ahead"] == 0
    _baseline(engine)


def test_the_loop_reads_its_last_step_and_the_watchdog_sees_the_oldest(
        engine):
    """A batch whose last rows end by count leaves a step unread and
    nothing else to do: the loop reads it rather than sleep on it."""
    sched = ContinuousScheduler(engine)
    sched.start()
    try:
        streams = [sched.submit(p, max_new_tokens=6) for p in PROMPTS[:3]]
        assert all(len(st.result(timeout=30.0)) == 6 for st in streams)
        deadline = time.monotonic() + 5.0
        while sched._flight is not None and time.monotonic() < deadline:
            time.sleep(0.005)
        assert sched._flight is None and sched._step_started is None
        decodes = [r for r in sched.step_log() if r[1] == "decode"]
        assert sched._step_ewma is not None and len(decodes) >= 5
    finally:
        sched.stop(timeout=10.0)
    assert sched.stats["tokens_generated"] == 18
    _baseline(engine)


# -- the other kinds of model -------------------------------------------------

def _load(kind, name):
    import importlib.util
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"la_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _tiny(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        cfg = copy.deepcopy(json.load(f))
    if name == "tiny-phi4flash-serve":      # as tests/test_serving_hybrid.py
        cfg["sliding_window"] = 8
        cfg["serve"].update(max_seq_len=128, page_size=4, kv_pages=256,
                            prefill_buckets=[16, 32, 64],
                            decode_buckets=[2, 4], max_new_tokens=8)
    return cfg


@pytest.mark.parametrize("name, counter", [
    ("tiny-mellum-serve", "kv_window_pages_returned"),
    ("tiny-phi4flash-serve", "state_slots_held_max"),
    ("tiny-keyevl2-serve", "sparse_tokens_selected"),
])
def test_streams_equal_the_synchronous_loop_for_every_kind_of_model(
        name, counter, monkeypatch):
    cfg = _tiny(name)
    if name == "tiny-mellum-serve":
        # prefill programs that sort and group, as the served ones do
        from paddle_tpu.serving import experts
        monkeypatch.setattr(experts, "DENSE_MAX_TOKENS", 8)
    with aot_build_phase():     # the weights' jit is no request's compile
        engine = _load("runners", cfg["runner"]).build_engine(cfg, 3)[0]
    try:
        rng = np.random.RandomState(5)
        vocab = engine.spec.vocab_size
        lengths = [30, 6, 17, 24]
        prompts = [[int(t) for t in rng.randint(1, vocab, n)]
                   for n in (5, 19, 3, 11)]
        sched = engine.scheduler
        streams = [sched.submit(p, max_new_tokens=n)
                   for p, n in zip(prompts, lengths)]
        for _ in range(8):
            sched.step()
        late = sched.submit(prompts[1][:7], max_new_tokens=9)
        sched.drain()
        s = dict(sched.stats)
        for st, p, n in zip(streams + [late], prompts + [prompts[1][:7]],
                            lengths + [9]):
            assert st.result(timeout=5.0) == _solo(engine, p, n), name
        assert s[counter] > 0
        assert s["decode_steps_ahead"] >= 0.8 * s["occupancy_steps"]
        assert s["tokens_generated"] == sum(lengths) + 9
        assert engine.unexpected_compiles == 0
        _baseline(engine)
    finally:
        engine.close()


# -- the step log (PR 36) ------------------------------------------------------

def _decodes(sched):
    return [r for r in sched.step_log() if r[1] == "decode"]


def test_the_step_log_keeps_one_record_a_launched_program(engine):
    """Two prefills, a step launched after a read, two launched ahead,
    then a prefill queued behind the unread step: what each record
    holds, and which steps the period counters time."""
    sched = ContinuousScheduler(engine)
    n0 = engine.launches
    a = sched.submit(PROMPTS[0], max_new_tokens=10)
    b = sched.submit(PROMPTS[1], max_new_tokens=10)
    for _ in range(3):
        sched.step()
    pa, pb, d1, d2 = sched.step_log()
    assert pa[:5] == (n0 + 1, "prefill", 16, len(PROMPTS[0]), a.request_id)
    assert pb[:5] == (n0 + 2, "prefill", 16, len(PROMPTS[1]), b.request_id)
    assert (pa[5], pa[6]) == (a.admitted_ts, a.first_token_ts)
    assert (pa[7], pb[7]) == (0, 1)     # b's prompt ran with a seated
    # (seq, kind, bucket, rows, ahead): the first step follows a read
    assert d1[:5] == (n0 + 3, "decode", 2, 2, False)
    assert d2[:5] == (n0 + 4, "decode", 2, 2, True)
    assert d1[7] == pytest.approx(d1[6] - d1[5])    # launch to read
    assert d2[7] == pytest.approx(d2[6] - d1[6])    # read to read
    assert d2[5] < d1[6] < d2[6]        # launched before d1 was read
    assert sched._flight.step.seq == n0 + 5 == engine.launches
    c = sched.submit(PROMPTS[2], max_new_tokens=6)
    sched.step()            # c's prefill behind the unread step, then its read
    pc, d3 = sched.step_log()[4:6]
    assert pc[:5] == (n0 + 6, "prefill", 16, len(PROMPTS[2]), c.request_id)
    assert pc[7] == 2                   # a and b waited behind c's prompt
    assert d3[:5] == (n0 + 5, "decode", 2, 2, True)
    assert d3[6] >= pc[6] and d3[7] >= pc[6] - pc[5]    # it holds the prefill
    s = sched.stats
    assert s["occupancy_steps"] == 3    # d4 is launched (c seated), unread
    assert s["steps_timed"] == 2 and s["steps_slow"] == 0
    assert s["step_period_s"] == pytest.approx(d1[7] + d2[7])
    assert s["prefill_runs"] == 3
    assert s["prefill_row_stall_s"] == pytest.approx(
        (pb[6] - pb[5]) + 2 * (pc[6] - pc[5]))
    sched.drain()
    for st, p, n in ((a, PROMPTS[0], 10), (b, PROMPTS[1], 10),
                     (c, PROMPTS[2], 6)):
        assert st.result(timeout=5.0) == _solo(engine, p, n)
    log = sched.step_log()
    assert sorted(r[0] for r in log if r[1] == "decode") == \
        [r[0] for r in log if r[1] == "decode"]
    assert len({r[0] for r in log}) == len(log) == 3 + s["occupancy_steps"]
    assert s["steps_timed"] == s["occupancy_steps"] - 1
    # copies: the caller's list is its own
    last = sched.step_log(2)
    assert last == log[-2:] and sched.step_log(0) == []
    last.clear()
    assert sched.step_log(2) == log[-2:]
    h = sched.snapshot()
    periods = [r[7] for r in _decodes(sched)]
    assert h["step_period_p50_s"] == pytest.approx(np.percentile(periods, 50))
    assert h["step_period_p99_s"] == pytest.approx(np.percentile(periods, 99))
    # what one admission cost the rows already seated, row-seconds
    assert h["prefill_runs"] == 3 and h["prefill_row_stall_mean_s"] == \
        pytest.approx(h["prefill_row_stall_s"] / 3)
    assert ContinuousScheduler(engine).snapshot()[
        "prefill_row_stall_mean_s"] is None
    _baseline(engine)


def test_a_failed_launch_read_or_prefill_leaves_no_record(engine,
                                                          monkeypatch):
    sched = ContinuousScheduler(engine)
    st = sched.submit(PROMPTS[0], max_new_tokens=8)
    sched.step()                            # prefill, first step launched
    assert [r[1] for r in sched.step_log()] == ["prefill"]
    orig = engine.decode

    def poisoned(*args):
        step = orig(*args)
        return _Poisoned(step._tokens, step._aux, step.slots, step.bucket,
                         step.seq)

    monkeypatch.setattr(engine, "decode", poisoned)
    sched.step()            # launches the poisoned step, reads the good one
    assert [r[1] for r in sched.step_log()] == ["prefill", "decode"]
    sched.step()            # launches behind it, then reads it: boom
    with pytest.raises(RuntimeError, match="device poison"):
        st.result(timeout=1.0)
    assert [r[1] for r in sched.step_log()] == ["prefill", "decode"]
    assert sched.stats["occupancy_steps"] == sched.stats["steps_timed"] == 1

    def refuses(*args):
        raise RuntimeError("no launch")

    monkeypatch.setattr(engine, "decode", refuses)
    st = sched.submit(PROMPTS[1], max_new_tokens=4)
    sched.step()                            # prefill, then the launch fails
    with pytest.raises(RuntimeError, match="no launch"):
        st.result(timeout=1.0)
    assert [r[1] for r in sched.step_log()] == ["prefill", "decode",
                                                 "prefill"]
    monkeypatch.undo()
    monkeypatch.setattr(engine, "prefill", refuses)
    st = sched.submit(PROMPTS[2], max_new_tokens=4)
    sched.step()
    with pytest.raises(RuntimeError, match="no launch"):
        st.result(timeout=1.0)
    assert len(sched.step_log()) == 3 and sched.stats["prefill_runs"] == 2
    assert sched.stats["failed"] == 3
    _baseline(engine)


def test_the_step_log_is_bounded_at_its_capacity(engine):
    from paddle_tpu.serving.scheduler import StepLog
    assert ContinuousScheduler(engine)._log._ring.maxlen == 4096
    log = StepLog(capacity=8)
    for k in range(20):
        kind = "prefill" if k % 5 == 0 else "decode"
        log.append((k, kind, 2, 1, False, 0.0, 1.0, float(k)))
    assert len(log) == 8 and [r[0] for r in log.last()] == list(range(12, 20))
    assert log.periods() == [12.0, 13.0, 14.0, 16.0, 17.0, 18.0, 19.0]
    assert log.periods(3) == [17.0, 18.0, 19.0]
    assert [r[0] for r in log.last(3)] == [17, 18, 19]
    named = log.as_dicts(6)
    assert named[-1] == dict(zip(StepLog.DECODE, log.last(1)[0]))
    assert named[1]["kind"] == "prefill" and "request_id" in named[1]
    json.dumps(named)                   # a dump can hold it as it is


def test_ewma_watchdog_and_shed_eta_read_the_log(engine):
    """The moving average, the watchdog's sample and the shed ETA are
    what they were when ``_step_times`` fed them: every booked step's
    period, a step read behind a prefill as it is."""
    from paddle_tpu.serving import scheduler as sched_mod
    sched = ContinuousScheduler(engine)
    streams = [sched.submit(p, max_new_tokens=12) for p in PROMPTS[:3]]
    for _ in range(5):
        sched.step()
    streams.append(sched.submit(PROMPTS[3], max_new_tokens=12))
    sched.drain()
    assert all(len(st.result(timeout=5.0)) == 12 for st in streams)
    periods = [r[7] for r in _decodes(sched)]
    assert len(periods) == sched.stats["occupancy_steps"] > \
        sched.stats["steps_timed"]
    ewma = None
    for dt in periods:
        ewma = dt if ewma is None else 0.2 * dt + 0.8 * ewma
    assert sched._step_ewma == pytest.approx(ewma)
    assert sched._log.periods(sched_mod._WATCHDOG_STEPS) == periods
    queued = sched.submit(PROMPTS[0], max_new_tokens=7)
    with sched._lock:
        assert sched._backlog_eta_locked() == pytest.approx(
            ewma * 7 / CFG.decode_buckets[-1])
        assert sched._completion_eta_locked(5) == pytest.approx(
            ewma * 7 / CFG.decode_buckets[-1] + ewma * 6)
    assert queued.cancel() is True
    # the watchdog's sample: the newest 256 periods, prefills skipped
    log = sched_mod.StepLog()
    for k in range(300):
        log.append((2 * k, "decode", 2, 2, True, 0.0, 0.0, float(k)))
        log.append((2 * k + 1, "prefill", 16, 3, k, 0.0, 0.0, 1))
    assert log.periods(sched_mod._WATCHDOG_STEPS) == [
        float(k) for k in range(44, 300)]
    _baseline(engine)


def test_the_watchdogs_dump_holds_the_last_records_with_the_tracer_off(
        engine, tmp_path):
    from paddle_tpu.observability.trace import get_tracer, reset_tracer
    reset_tracer()
    tr = get_tracer().enable(flight_dir=str(tmp_path))
    tr.enabled = False                  # armed, and no span is kept
    try:
        sched = ContinuousScheduler(engine)
        for _ in range(2):
            streams = [sched.submit(p, max_new_tokens=40)
                       for p in PROMPTS[:4]]
            sched.drain()
            assert all(len(st.result(timeout=5.0)) == 40 for st in streams)
        assert len(sched.step_log()) == 2 * (4 + 39) and tr.spans() == []
        sched._trip_watchdog("on", 1.5, 1.0)
        doc = json.load(open(tr.flight_path))
        assert doc["reason"].startswith("serve-hang") and doc["spans"] == []
        steps = doc["extra"]["serve_steps"]
        assert len(steps) == 64
        assert steps == json.loads(json.dumps(sched._log.as_dicts(64)))
        assert steps[-1]["kind"] == "decode"
        assert steps[-1]["seq"] == sched.step_log(1)[0][0]
        assert {"launched_ts", "read_ts", "period_s", "ahead", "rows",
                "bucket"} <= set(steps[-1])
        assert any(r["kind"] == "prefill" and r["seated_rows"] == 3
                   for r in steps)
        assert sched.hang_detected and sched.stats["watchdog_trips"] == 1
    finally:
        reset_tracer()
    _baseline(engine)


def test_token_gap_max_is_the_gap_the_stamps_show(engine, monkeypatch):
    """A request whose neighbour is prefilled in the middle of its
    answer: its largest gap is the one around that prefill, as the
    log's read stamps have it."""
    sched = ContinuousScheduler(engine)
    a = sched.submit(PROMPTS[0], max_new_tokens=10)
    for _ in range(4):
        sched.step()
    assert a.token_gap_max > 0.0 and len(a.tokens) == 4
    orig = engine.prefill

    def slow_prefill(tokens, table):
        time.sleep(0.05)
        return orig(tokens, table)

    monkeypatch.setattr(engine, "prefill", slow_prefill)
    c = sched.submit(PROMPTS[2], max_new_tokens=2)
    sched.drain()
    monkeypatch.undo()
    assert a.result(timeout=5.0) == _solo(engine, PROMPTS[0], 10)
    stamps = [a.first_token_ts] + [r[6] for r in _decodes(sched)][:9]
    assert stamps[-1] == a.last_token_ts
    gaps = np.diff(stamps)
    assert a.token_gap_max == pytest.approx(gaps.max()) and \
        a.token_gap_max >= 0.05
    assert int(gaps.argmax()) == 3          # the token after c's prefill
    assert c.token_gap_max == pytest.approx(c.last_token_ts
                                            - c.first_token_ts)
    s = sched.snapshot()
    assert s["tpot_requests"] == 2
    assert s["token_gap_max_s"] == pytest.approx(a.token_gap_max
                                                 + c.token_gap_max)
    assert s["token_gap_max_mean_s"] == pytest.approx(
        s["token_gap_max_s"] / 2)
    one = sched.submit(PROMPTS[1], max_new_tokens=1)
    sched.drain()
    assert one.token_gap_max == 0.0 and sched.stats["tpot_requests"] == 2
    _baseline(engine)


def _registry_values():
    from paddle_tpu import observability as obs
    snap = obs.get_registry().snapshot()
    return {(name, series): value for name, entry in snap.items()
            if name.startswith("pt_serve_")
            for series, value in entry["series"].items()}


def _synced_values(sched):
    from paddle_tpu.serving.scheduler import _SYNCED
    out = {}
    for name, labels, key, less in _SYNCED:
        total = sched.stats[key] - (sched.stats[less] if less else 0)
        if total:
            series = ",".join(f"{k}={v}" for k, v in (labels or {}).items())
            out[(name, series)] = total
    return out


def test_the_registry_follows_stats_off_the_steps_path(engine):
    """No instrument is booked by a step that retires nothing; the
    counters are brought up to ``stats`` by ``snapshot()`` and a
    quarter second after the last time, a retirement or none."""
    from paddle_tpu import observability as obs
    tel = obs.get_telemetry()
    was = tel.enabled       # a runner's build_engine may have left it on
    obs.reset_registry()
    tel.enable(compile_watch=False)
    try:
        pool = engine.pool
        # room for the first two requests; the third waits for the second's
        held = pool.alloc(pool.snapshot()["free_pages"] - 9)
        try:
            sched = ContinuousScheduler(engine)
            long = sched.submit(PROMPTS[0], max_new_tokens=20)
            short = sched.submit(PROMPTS[1], max_new_tokens=3)
            queued = sched.submit(PROMPTS[3], max_new_tokens=4)
            sched.step()        # the first step syncs: nothing before it did
            mine = lambda: {k: v for k, v in _registry_values().items()
                            if k[0] in {n for n, *_ in _synced_values(sched)}}
            first = mine()
            assert first[("pt_serve_requests_total", "")] == 3
            assert first[("pt_serve_admission_refusals_total",
                          "reason=kv_headroom")] == 1
            sched.step()        # launches, reads, retires nothing
            assert sched.stats["tokens_generated"] > \
                first[("pt_serve_tokens_total", "")]
            assert mine() == first
            assert _registry_values()[("pt_serve_queue_depth", "")] == 1
            sched.step()        # short's last token: a retirement books
            #                     its histograms and no counter (the third
            #                     request takes its pages)
            assert short.done() and mine() == first
            gaps = lambda: [v["count"] for v in obs.get_registry().snapshot()[
                "pt_serve_token_gap_max_seconds"]["series"].values()]
            assert gaps() == [1]
            sched.step()
            assert mine() == first
            time.sleep(0.26)
            sched.step()        # the cadence
            assert mine() == _synced_values(sched)
            assert mine()[("pt_serve_completed_total", "")] == 1
            sched.step()
            assert mine() != _synced_values(sched)
            sched.snapshot()
            assert mine() == _synced_values(sched)
            sched.drain()
            assert long.done() and queued.done()
            assert mine() != _synced_values(sched)
            sched.snapshot()
            got = _registry_values()
            assert mine() == _synced_values(sched)
            assert got[("pt_serve_decode_steps_total", "launch=ahead")] \
                == sched.stats["decode_steps_ahead"]
            assert got[("pt_serve_decode_steps_total", "launch=sync")] \
                == (sched.stats["occupancy_steps"]
                    - sched.stats["decode_steps_ahead"])
            assert got[("pt_serve_queue_depth", "")] == 0
            assert got[("pt_serve_active_sequences", "")] == 0
            assert not any(name == "pt_serve_batch_occupancy"
                           for name, _ in got)
            assert gaps() == [3]
        finally:
            pool.free(held)
    finally:
        tel.enabled = was       # the module's engine keeps its sentinel
        obs.reset_registry()
    _baseline(engine)
