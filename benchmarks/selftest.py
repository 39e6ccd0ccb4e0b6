"""Self-test of the benchmark's own arithmetic, on the CPU in seconds:

    python benchmarks/selftest.py

Checks the percentile, the stratified draws (two seeds, the same work in
another order), the generator's arrivals, the knee of a sweep, and
`trace_reduce` against a hand-made trace and against the small recorded
trace in `testdata/` (cut from a chip run of this benchmark).  Touches
no device and does not import jax."""
from __future__ import annotations

import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import stats
import traffic
import trace_reduce as tr
import costs


def close(a, b, tol=1e-9):
    return abs(a - b) <= tol * max(1.0, abs(a), abs(b))


def test_percentile():
    v = list(range(1, 101))
    assert close(stats.percentile(v, 50), 50.5)
    assert close(stats.percentile(v, 95), 95.05)
    assert stats.percentile([], 50) is None
    assert close(stats.percentile([7.0], 95), 7.0)


def test_spread():
    # statistics.quantiles, exclusive method: quartiles of 1..6 are 1.75, 5.25
    assert close(stats.spread([1, 2, 3, 4, 5, 6]), 3.5 / 3.5)
    assert close(stats.spread([10.0] * 6), 0.0)


def test_grids():
    d = {"kind": "loguniform_int", "lo": 32, "hi": 256}
    g = stats.quantile_grid(d, 400)
    assert min(g) == 32 and max(g) == 256 and g == sorted(g)
    # log-uniform: the median is near the geometric mean of the range
    assert 85 <= g[200] <= 95, g[200]
    e = stats.quantile_grid({"kind": "exponential", "mean": 2.0}, 10000)
    assert close(float(np.mean(e)), 2.0, 2e-3)
    a = stats.stratified(d, 400, np.random.RandomState(1))
    b = stats.stratified(d, 400, np.random.RandomState(2))
    assert a != b and sorted(a) == sorted(b) == g
    # blocks: every 16 draws in a row span the distribution, any seed
    c = stats.stratified(d, 400, np.random.RandomState(3), block=16)
    assert sorted(c) == g and c != a
    sums = [sum(c[i:i + 16]) for i in range(0, 400, 16)]
    plain = [sum(a[i:i + 16]) for i in range(0, 400, 16)]
    assert max(sums) - min(sums) < 0.25 * (max(plain) - min(plain)), \
        (sums, plain)


def test_traffic():
    mix = json.load(open(os.path.join(HERE, "traffic", "complete-steady.json")))
    rate = mix["arrival"]["rate"]
    r1, closed = traffic.serve_requests(mix, 1, 40.0, 50304)
    r2, _ = traffic.serve_requests(mix, 3000000019, 40.0, 50304)
    assert closed is None and len(r1) == len(r2) == round(rate * 40)
    for key in ("new_tokens",):
        assert sorted(r[key] for r in r1) == sorted(r[key] for r in r2)
    assert sorted(len(r["prompt"]) for r in r1) == \
        sorted(len(r["prompt"]) for r in r2)
    due = sorted(r["due"] for r in r1)
    assert due[0] == 0.0 and due[-1] < 40.0
    gaps1 = np.diff(sorted(r["due"] for r in r1) + [40.0])
    gaps2 = np.diff(sorted(r["due"] for r in r2) + [40.0])
    assert np.allclose(np.sort(gaps1), np.sort(gaps2)) \
        and not np.allclose(gaps1, gaps2)
    # exponential gaps: their standard deviation is about their mean
    assert 0.85 < np.std(gaps1) / np.mean(gaps1) < 1.1
    assert r1[0]["prompt"] != r2[0]["prompt"]
    again, _ = traffic.serve_requests(mix, 1, 40.0, 50304)
    assert again == r1
    back = json.load(open(os.path.join(HERE, "traffic",
                                       "longprompt-backlog.json")))
    c1, closed = traffic.serve_requests(back, 1, 40.0, 50304)
    c2, _ = traffic.serve_requests(back, 2, 40.0, 50304)
    n = back["arrival"]["pool"]
    assert closed["callers"] == back["arrival"]["callers"]
    assert all(r["due"] is None for r in c1)
    # any cycle of the pool is the same work, whatever the seed
    for k in (0, 3):
        cyc = slice(k * n, (k + 1) * n)
        assert sorted(len(r["prompt"]) for r in c1[cyc]) == \
            sorted(len(r["prompt"]) for r in c2[cyc])
        assert sum(r["new_tokens"] for r in c1[cyc]) == \
            sum(r["new_tokens"] for r in c2[0:n])
    for mixkind in ({"kind": "burst", "rate": 10.0,
                     "burst": {"kind": "uniform_int", "lo": 8, "hi": 16}},
                    {"kind": "all_at_once", "requests": 8}):
        m = dict(mix, arrival=mixkind)
        rs, _ = traffic.serve_requests(m, 5, 10.0, 1000)
        assert len(rs) == (100 if mixkind["kind"] == "burst" else 8)
        assert all(0.0 <= r["due"] < 10.0 for r in rs)
    m = dict(mix, prefix={"tokens": 64, "turns": 4})
    rs, _ = traffic.serve_requests(m, 5, 10.0, 1000)
    assert rs[0]["prompt"][:64] == rs[3]["prompt"][:64] != rs[4]["prompt"][:64]
    m = dict(mix, classes=[
        {"share": 0.8, "prompt_len": mix["prompt_len"],
         "new_tokens": mix["new_tokens"]},
        {"share": 0.2, "prompt_len": {"kind": "constant", "value": 900},
         "new_tokens": {"kind": "constant", "value": 8}}])
    rs, _ = traffic.serve_requests(m, 5, 10.0, 1000)
    assert sum(len(r["prompt"]) == 900 for r in rs) == round(0.2 * len(rs))


def test_train_batches():
    b1 = traffic.train_batches({"batch": 2, "seq": 16, "ring": 3}, 9, 100)
    b2 = traffic.train_batches({"batch": 2, "seq": 16, "ring": 3}, 9, 100)
    assert len(b1) == 3 and b1[0][0].shape == (2, 16)
    assert all((x[0] == y[0]).all() for x, y in zip(b1, b2))
    assert (b1[0][0][:, 1:] == b1[0][1][:, :-1]).all()


def test_knee():
    rows = [(5, 0.1, 0), (7, 0.4, 0), (9, 1.2, 0), (11, 9.0, 0), (13, 30.0, 4)]
    assert stats.knee(rows, 4.0) == 9
    assert stats.knee([(5, 6.0, 0)], 4.0) is None
    assert stats.knee([(5, 0.0, 0), (7, 0.0, 2)], 4.0) == 5
    assert close(stats.queue_growth([1, 1, 2], [4, 5, 6]), 5 - 4 / 3)


def test_costs():
    f, b = costs.paged_decode(1000, 8, 16, 64, 4)
    assert f == 4 * 1000 * 1024 and b == 2 * 1000 * 1024 * 4 + 2 * 8 * 1024 * 4
    peak = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    t, side = costs.least_seconds(f, b, peak)
    assert side == "memory" and close(t, b / 819e9)
    f, b = costs.flash_fwd_bwd(8, 16, 1024, 64, 2)
    assert f == 7 * 8 * 16 * 1024 * 1024 * 64
    assert costs.least_seconds(f, b, peak)[1] == "compute"
    assert costs.train_flops_per_token(10, 2, 3, 4) == 60 + 144


def hand_trace():
    ms = 1_000_000
    ops = [["fusion.1", 0, 2 * ms], ["copy.7", 1 * ms, 3 * ms],
           ["kern.2", 10 * ms, 1 * ms], ["copy.7", 20 * ms, 2 * ms],
           ["kern.2", 22 * ms, 1 * ms]]
    mods = [["jit_a(1)", 0, 5 * ms], ["jit_b(2)", 10 * ms, 1 * ms],
            ["jit_a(1)", 20 * ms, 3 * ms]]
    host = [["bench:window", 0, 30 * ms], ["bench:step", 4 * ms, 5 * ms],
            ["bench:data", 12 * ms, 8 * ms], ["other", 0, 1]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": tr.OPS_LINE, "events": ops},
            {"name": tr.MODULES_LINE, "events": mods}]},
        {"name": "/host:CPU", "lines": [{"name": "t", "events": host}]}]}


def test_trace_by_hand():
    red = tr.Reduced(hand_trace())
    assert close(red.window_s, 0.030)
    # busy: [0,4) + [10,11) + [20,23) ms
    assert close(red.busy_s(), 0.008)
    per_run, runs = red.ops_per_run("^copy", "jit_a")
    assert runs == 2 and close(per_run, 0.0025)
    per_run, runs = red.ops_per_run("^kern", "jit_b")
    assert runs == 1 and close(per_run, 0.001)
    assert red.ops_per_run("^kern", "nothing") == (None, 0)
    bd = red.breakdown()
    assert bd["device_ops"][0] == ["copy.7", 0.005]
    gaps = dict(bd["idle_gaps"])
    # idle [4,10): step covers [4,9); idle [11,20): data covers [12,20);
    # idle [23,30): nothing
    assert close(gaps["step"], 0.005) and close(gaps["data"], 0.008)
    assert close(gaps["unspanned"], 0.001 + 0.001 + 0.007)
    assert close(sum(gaps.values()) + red.busy_s(), red.window_s)


def test_trace_recorded():
    path = os.path.join(HERE, "testdata", "serve_decode_trace.json")
    red = tr.Reduced(json.load(open(path)))
    want = json.load(open(os.path.join(HERE, "testdata",
                                       "serve_decode_trace.expect.json")))
    assert close(red.busy_s(), want["busy_s"], 1e-6)
    for pattern, program, per_run, runs in want["ops_per_run"]:
        got, n = red.ops_per_run(pattern, program)
        assert n == runs and close(got, per_run, 1e-6), (pattern, got, n)
    idle = sum(s for _, s in tr.idle_gaps(
        tr.clip(red.ops(0), red.t0, red.t1), red.t0, red.t1,
        [s for s in red.spans if s[0] != "window"], 100))
    assert close(idle + red.busy_s(), red.window_s, 1e-6)


def main():
    tests = [(n, f) for n, f in sorted(globals().items())
             if n.startswith("test_") and callable(f)]
    for name, fn in tests:
        fn()
        print("ok", name)
    print(f"{len(tests)} checks passed")


if __name__ == "__main__":
    main()
