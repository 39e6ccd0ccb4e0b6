"""Can a decode program be launched before the one before it was read?

    chiprun -- python3 exp/launch_ahead_probe.py [config ...]

What would falsify launch-ahead (PR 35): a second executable call that
blocks on the host until the first has finished (the donated pools are
futures), or step k+1's argument transfer serialising behind step k.
One engine a configuration (`benchmarks/configs/<config>.json`), its
largest decode bucket full of rows at half the longest context, the
engine's own executables called as `ServingEngine._run` calls them:

 - `sync`: launch, read the tokens, launch (the parent's order);
 - `pair`: two launches back to back with no read between, the second
   fed the first's token vector as the device array it is: how long each
   call takes to return;
 - `blind`: a chain of such launches with no read until the end, which
   is the device's own step time if no call blocks;
 - `ahead`: launch k+1 from k's device tokens, then read k (the order
   the scheduler takes).

Times are host-clock milliseconds on whatever device jax finds; the
results go to `chiprun_out/launch_ahead_probe.<config>.json` too.
One configuration a process where the engines are large.
"""
import importlib
import json
import os
import statistics
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
STEPS = 40      # a phase, where the longest context leaves the room


def probe(name):
    import jax
    config = json.load(open(os.path.join(
        ROOT, "benchmarks", "configs", name + ".json")))
    runner = importlib.import_module("runners." + config["runner"])
    engine, _params, spec, build_s = runner.build_engine(config, 7)
    pool, b = engine.pool, engine.config.decode_buckets[-1]
    exe, params = engine._decode_exe[b], engine._params
    ctx = spec.max_seq_len // 2
    steps = min(STEPS, (spec.max_seq_len - ctx - 24) // 3)
    rows = []
    while len(rows) < b:        # as many rows of that context as fit
        row = pool.admit_row(ctx + 3 * steps + 23, 1,
                             engine.max_pages_per_seq)
        if row is None:
            break
        rows.append(row)
    n = len(rows)
    tables = np.zeros((b, *engine.table_shape), np.int32)
    tables[:n] = np.stack([r.table for r in rows])
    pos = np.zeros((b,), np.int32)
    at = [ctx]                  # the next position to write, every row

    def launch(tok):
        pos[:n] = at[0]
        at[0] += 1
        state = pool.state()
        out = exe(params, *state, tok, pos.copy(), tables)
        pool.swap(*out[:len(state)])
        out[len(state)].copy_to_host_async()
        return out[len(state)]

    def timed(fn, *args):
        t0 = time.perf_counter()
        got = fn(*args)
        return got, (time.perf_counter() - t0) * 1e3

    med = statistics.median
    host = np.zeros((b,), np.int32)
    for _ in range(3):
        host = np.asarray(launch(host))
    found = {"config": name, "bucket": b, "rows": n, "context": ctx,
             "steps": steps, "build_s": build_s,
             "device": jax.devices()[0].device_kind}
    # sync: the parent's order
    calls, reads, t0 = [], [], time.perf_counter()
    for _ in range(steps):
        nxt, c = timed(launch, host)
        host, r = timed(np.asarray, nxt)
        calls.append(c)
        reads.append(r)
    found["sync"] = {"period_ms": (time.perf_counter() - t0) * 1e3 / steps,
                     "call_ms": med(calls), "read_ms": med(reads)}
    # pair: does the second call return before the first has finished?
    firsts, seconds, waits = [], [], []
    for _ in range(8):
        nxt, c1 = timed(launch, host)
        nxt, c2 = timed(launch, nxt)
        host, w = timed(np.asarray, nxt)
        firsts.append(c1)
        seconds.append(c2)
        waits.append(w)
    found["pair"] = {"first_call_ms": med(firsts),
                     "second_call_ms": med(seconds),
                     "second_call_ms_max": max(seconds),
                     "read_of_both_ms": med(waits)}
    # blind: the device's own step, if no call blocks
    calls, t0 = [], time.perf_counter()
    nxt = host
    for _ in range(steps):
        nxt, c = timed(launch, nxt)
        calls.append(c)
    host = np.asarray(nxt)
    found["blind"] = {"period_ms": (time.perf_counter() - t0) * 1e3 / steps,
                      "call_ms": med(calls), "call_ms_max": max(calls)}
    # ahead: launch k+1, then read k
    calls, reads = [], []
    prev = launch(host)
    t0 = time.perf_counter()
    for _ in range(steps):
        nxt, c = timed(launch, prev)
        host, r = timed(np.asarray, prev)
        prev = nxt
        calls.append(c)
        reads.append(r)
    found["ahead"] = {"period_ms": (time.perf_counter() - t0) * 1e3 / steps,
                      "call_ms": med(calls), "read_ms": med(reads)}
    np.asarray(prev)
    for row in rows:
        row.release()
    engine.close()
    return found


def main(names):
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    for name in names or ["gpt-345m-serve"]:
        found = probe(name)
        print(json.dumps(found), flush=True)
        with open(os.path.join(out, f"launch_ahead_probe.{name}.json"),
                  "w") as f:
            json.dump(found, f, indent=1)


if __name__ == "__main__":
    main(sys.argv[1:])
