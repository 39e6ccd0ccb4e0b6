"""Runner kind `serve`: one `ServingEngine` built from the cell's
configuration, offered the cell's traffic by an open or a closed loop.

Tokens are stamped without touching the program: the two bound methods
`engine.prefill` and `engine.decode` of the engine instance built here
are wrapped (a span around a call into a layer).  `prefill` returns the
request's first token as a host int, so its return is the time of the
first token; the last token's time is `GenerationStream.finished_ts`.
Every clock is `time.monotonic`, the scheduler's own."""
from __future__ import annotations

import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from reference import gpt_serve as ref
from stats import percentile, queue_growth, knee
from taps import pallas_routes, span, start_trace
from traffic import serve_requests

# |engine logits - reference logits|, prefill and four decode steps.
# The configuration serves in float32 but the program leaves its
# matmuls at XLA's default precision, which on a TPU multiplies in
# bfloat16 (one pass) and accumulates in float32; the reference runs at
# "highest".  Over 24 layers that rounding read 0.020 to 0.027 in forty
# runs, on logits whose own standard deviation is 0.64 (my chip run,
# PR 23), so the bound is three times the worst.  A wrong mask,
# position or page, or a bfloat16 cache or residual stream, moves
# logits by a good part of their own size and fails.
LOGIT_ATOL = 0.08
SAMPLE = 3          # prompts checked against the reference
SUBMITTERS = 4      # threads that call scheduler.submit, as clients' connections do


class EngineTap:
    """Spans around `engine.prefill` and `engine.decode`."""

    def __init__(self, engine):
        self.engine = engine
        self.prefill = []      # (t0, t1, prompt as tuple)
        self.decode = []       # (t0, t1, rows, context tokens)
        self.annotate = False  # also write the spans into the profiler's trace
        self.on_call = threading.Event()
        self._prefill, self._decode = engine.prefill, engine.decode
        engine.prefill, engine.decode = self.prefill_call, self.decode_call

    def prefill_call(self, tokens, page_table):
        self.on_call.set()
        t0 = time.monotonic()
        with span("prefill", self.annotate):
            first = self._prefill(tokens, page_table)
        self.prefill.append((t0, time.monotonic(), tuple(tokens)))
        return first

    def decode_call(self, tokens, positions, page_tables):
        self.on_call.set()
        t0 = time.monotonic()
        with span("decode", self.annotate):
            nxt = self._decode(tokens, positions, page_tables)
        self.decode.append((t0, time.monotonic(), int(tokens.shape[0]),
                            int(positions.sum()) + int(tokens.shape[0])))
        return nxt

    def reset(self):
        self.prefill, self.decode = [], []


def build_engine(config, seed):
    import functools
    import jax
    from paddle_tpu.observability.telemetry import get_telemetry
    from paddle_tpu.serving import (ModelSpec, ServeConfig, ServingEngine,
                                    init_params)
    get_telemetry().enable()     # the compile watcher and dispatch counts
    spec = ModelSpec(**config["model"])
    cfg = ServeConfig.from_dict(config["serve"])
    # all weights in one jitted call, on the device, from the seed
    make = jax.jit(functools.partial(init_params, spec))
    params = make(np.int32(seed % (2 ** 31 - 1)))
    t0 = time.monotonic()
    engine = ServingEngine(spec, params, cfg)
    return engine, params, spec, time.monotonic() - t0


def check_against_reference(engine, params, spec, prompts, pad, steps=4):
    """Prefill and `steps` decode steps through the engine's programs
    and cache, logits against the plain reference's full forward over
    the same tokens.  `engine.prefill` / `decode` return tokens only,
    so this calls the programs they call, the same way."""
    import jax.numpy as jnp
    pool, worst = engine.pool, 0.0
    b = engine.config.decode_buckets[0]
    maxp = engine.max_pages_per_seq
    for prompt in prompts:
        n = len(prompt)
        pages = pool.alloc(pool.pages_needed(n + steps))
        table = pool.null_padded_table(pages, maxp)
        s = engine.prefill_bucket_for(n)
        padded = np.zeros((s,), np.int32)
        padded[:n] = prompt
        *state, nxt, logits = engine._prefill_exe[s](
            engine._params, *engine._kv_state(), padded, np.int32(n),
            np.asarray(table, np.int32))
        pool.swap(*state)
        got, toks = [np.asarray(logits)], [int(nxt)]
        for k in range(steps):
            tok = np.zeros((b,), np.int32)
            pos = np.zeros((b,), np.int32)
            pt = np.zeros((b, maxp), np.int32)
            tok[0], pos[0], pt[0] = toks[-1], n + k, table
            *state, nxt, logits = engine._decode_exe[b](
                engine._params, *engine._kv_state(), tok, pos, pt)
            pool.swap(*state)
            got.append(np.asarray(logits)[0])
            toks.append(int(np.asarray(nxt)[0]))
        pool.free(pages)
        full = list(prompt) + toks[:steps]
        seq = np.zeros((pad,), np.int32)    # one shape, whatever the seed
        seq[:len(full)] = full
        want = np.asarray(ref.forward(
            params, jnp.asarray(seq), np.int32(n - 1), layers=spec.layers,
            heads=spec.heads, rows=steps + 1))
        worst = max(worst, float(np.max(np.abs(np.stack(got) - want))))
    return worst


class Load:
    """Offers requests and keeps the harness's own stamps."""

    def __init__(self, engine, tap, requests, closed):
        self.engine, self.tap = engine, tap
        self.requests, self.closed = requests, closed
        self.sent = []          # request indices in the order offered
        self.stop = threading.Event()
        self.pool = ThreadPoolExecutor(SUBMITTERS, "bench-submit")
        for r in requests:
            r.update(stream=None, refused=False, sent=None, due_at=None)

    def _submit(self, req):
        from paddle_tpu.serving.scheduler import EngineSaturated
        try:
            req["stream"] = self.engine.scheduler.submit(
                req["prompt"], max_new_tokens=req["new_tokens"])
        except EngineSaturated:
            req["refused"] = True

    def _offer(self, i, due_at):
        req = self.requests[i]
        req["due_at"], req["sent"] = due_at, time.monotonic()
        self.sent.append(i)
        self.pool.submit(self._submit, req)

    def open_loop(self, t0):
        for i in sorted(range(len(self.requests)),
                        key=lambda j: self.requests[j]["due"]):
            due_at = t0 + self.requests[i]["due"]
            wait = due_at - time.monotonic()
            if wait > 0 and self.stop.wait(wait):
                return
            self._offer(i, due_at)

    def closed_loop(self, callers):
        """`callers` requests outstanding at all times: a caller sends
        its next request when its last one is resolved.  Woken at the
        entry of every engine call (by then the step before it has
        resolved its finished requests); the timeout only matters when
        the engine has gone idle."""
        nxt, outstanding = 0, []
        while not self.stop.is_set():
            outstanding = [i for i in outstanding
                           if not self._resolved(self.requests[i])]
            while len(outstanding) < callers and nxt < len(self.requests):
                self._offer(nxt, time.monotonic())
                outstanding.append(nxt)
                nxt += 1
            self.tap.on_call.clear()
            self.tap.on_call.wait(0.02)

    @staticmethod
    def _resolved(req):
        return req["refused"] or (req["stream"] is not None
                                  and req["stream"].done())


def measure(engine, tap, requests, closed, seconds, drain_s, traced, out):
    """One window.  Returns the per-request stamps and the spans."""
    import jax
    sched = engine.scheduler
    tap.reset()
    load = Load(engine, tap, requests, closed)
    stats0 = dict(sched.stats)
    compiles0 = engine.unexpected_compiles
    trace_dir, tw = None, None
    if closed:
        thread = threading.Thread(target=load.closed_loop,
                                  args=(closed["callers"],),
                                  name="bench-load", daemon=True)
        thread.start()
        time.sleep(closed["ramp_s"])   # fill the batch: counts as set-up
        stats0 = dict(sched.stats)
        t0 = time.monotonic()
    else:
        t0 = time.monotonic() + 0.05
        thread = threading.Thread(target=load.open_loop, args=(t0,),
                                  name="bench-load", daemon=True)
        thread.start()
    t1 = t0 + seconds
    if traced:
        # a few seconds from the middle of the window, spans included
        trace_s = min(float(traced), seconds / 2)
        time.sleep(max(0.0, t0 + (seconds - trace_s) / 2 - time.monotonic()))
        trace_dir = os.path.join(out, "trace")
        tap.annotate = True
        start_trace(trace_dir)
        ta = time.monotonic()
        with span("window"):
            time.sleep(trace_s)
        tb = time.monotonic()
        tap.annotate = False
        jax.profiler.stop_trace()
        tw = (ta, tb)
    time.sleep(max(0.0, t1 - time.monotonic()))
    stats1 = dict(sched.stats)
    if closed:
        load.stop.set()
    else:
        # wait for what was due in the window, up to the drain limit
        limit = t1 + drain_s
        while time.monotonic() < limit and not all(
                Load._resolved(r) for r in requests if r["sent"]):
            time.sleep(0.01)
        load.stop.set()
    thread.join(5.0)
    load.pool.shutdown(wait=True)
    return {"t0": t0, "t1": t1, "stats0": stats0, "stats1": stats1,
            "compiles": engine.unexpected_compiles - compiles0,
            "trace_dir": trace_dir, "trace_window": tw, "load": load}


def reduce_window(m, tap, requests, closed):
    """The window's stamps to numbers (host clock)."""
    t0, t1 = m["t0"], m["t1"]
    first = {key: (a, b) for a, b, key in tap.prefill}
    ttft, tpot, wait, late = [], [], [], []
    attempted = failed = tokens_done = 0
    rows = []
    for i in m["load"].sent:
        r = requests[i]
        st = r["stream"]
        done = st is not None and st.done() and st._error is None
        entered, produced = first.get(tuple(r["prompt"]), (None, None))
        if closed:
            # a closed loop counts what finished inside the window;
            # what is still in flight when it ends is cut, not failed
            if done and t0 <= st.finished_ts <= t1:
                attempted += 1
                if len(st.tokens) != r["new_tokens"]:
                    failed += 1
                else:
                    tokens_done += len(st.tokens)
            elif r["refused"] or (st is not None and st.done()
                                  and st._error is not None):
                attempted += 1
                failed += 1
            continue
        attempted += 1
        late.append((r["sent"] - r["due_at"]) * 1e3)
        if not done or len(st.tokens) != r["new_tokens"] or produced is None:
            failed += 1
            continue
        ttft.append((produced - r["due_at"]) * 1e3)
        wait.append((entered - r["due_at"]) * 1e3)
        if len(st.tokens) > 1:
            tpot.append((st.finished_ts - produced) * 1e3
                        / (len(st.tokens) - 1))
        if st.finished_ts <= t1:
            tokens_done += len(st.tokens)
        rows.append((r["due_at"], produced))
    calls = sorted([(a, b) for a, b, _ in tap.prefill]
                   + [(a, b) for a, b, _, _ in tap.decode])
    calls = [c for c in calls if t0 <= c[0] <= t1]
    gaps = [(calls[k][1], calls[k + 1][0]) for k in range(len(calls) - 1)]
    pre = [(a, b) for a, b, _ in tap.prefill if t0 <= a <= t1]
    dec = [(a, b) for a, b, _, _ in tap.decode if t0 <= a <= t1]
    pre_s = sum(b - a for a, b in pre)
    dec_s = sum(b - a for a, b in dec)
    s0, s1 = m["stats0"], m["stats1"]
    occ_steps = s1["occupancy_steps"] - s0["occupancy_steps"]
    values = {
        "ttft_p95_ms": percentile(ttft, 95),
        "ttft_p50_ms": percentile(ttft, 50),
        "tpot_p50_ms": percentile(tpot, 50),
        "submit_wait_ms_p95": percentile(wait, 95),
        "loadgen_late_ms_p95": percentile(late, 95),
        # every token generated in the window, whether or not its request
        # also finished there: all the work over all the time
        "serve_tokens_per_s": (s1["tokens_generated"]
                               - s0["tokens_generated"]) / (t1 - t0),
        "completed_tokens_per_s": tokens_done / (t1 - t0),
        "batch_occupancy_pct": (100.0 * (s1["occupancy_sum"]
                                         - s0["occupancy_sum"]) / occ_steps
                                if occ_steps else None),
        "prefill_time_share_pct": (100.0 * pre_s / (pre_s + dec_s)
                                   if pre_s + dec_s else None),
        "step_period_ms_p50": percentile(
            [(dec[k + 1][0] - dec[k][0]) * 1e3 for k in range(len(dec) - 1)],
            50),
    }
    counters = {k: s1[k] - s0[k] for k in s1
                if isinstance(s1[k], (int, float)) and s1[k] is not None
                and isinstance(s0.get(k), (int, float))}
    spans = {"prefill": pre, "decode": dec, "sched_gap": gaps}
    # backlog at each arrival: requests due by then with no first token yet
    backlog = [sum(1 for d2, p2 in rows if d2 <= d and p2 > d)
               for d, _ in rows]
    return {"values": values, "counters": counters, "spans": spans,
            "attempted": attempted, "failed": failed, "backlog": backlog,
            "requests_timed": len(ttft)}


def run(ctx):
    import jax
    config, mix = ctx["config"], ctx["traffic"]
    on_chip = jax.devices()[0].platform == "tpu"
    engine, params, spec, first_call_s = build_engine(config, ctx["seed"])
    tap = EngineTap(engine)
    vocab = spec.vocab_size
    requests, closed = serve_requests(mix, ctx["seed"], ctx["seconds"], vocab)
    sample = [r["prompt"] for r in requests[:SAMPLE]]
    # the reference's one shape: the mix's longest prompt, the same for every seed
    pad = -(-(max(len(r["prompt"]) for r in requests) + 4) // 128) * 128
    t_check = time.monotonic()
    from paddle_tpu.serving.engine import aot_build_phase
    with aot_build_phase():     # the reference compiles; no request is in flight
        logit_err = check_against_reference(engine, params, spec, sample, pad)
    check_s = time.monotonic() - t_check
    routes = pallas_routes()
    fell_back = {k: v for k, v in routes.items() if v.get("fallback")}
    engine.scheduler.start()
    try:
        if ctx["sweep"]:
            return sweep(ctx, engine, tap, mix, vocab)
        m = measure(engine, tap, requests, closed, ctx["seconds"],
                    float(mix.get("drain_s", 10.0)),
                    mix.get("trace_s", 4.0) if ctx["trace"] else 0,
                    ctx["out"])
    finally:
        engine.scheduler.stop()
    red = reduce_window(m, tap, requests, closed)
    health = engine.healthz()
    engine.close()
    notes = {"logit_err": logit_err, "check_s": check_s,
             "build_s": first_call_s, "window_compiles": m["compiles"],
             "pallas_routes": routes, "requests_timed": red["requests_timed"],
             "decode_steps": len(red["spans"]["decode"]),
             "completed_tokens_per_s": red["values"]["completed_tokens_per_s"],
             "step_period_ms_p50": red["values"]["step_period_ms_p50"],
             "kv_consistent": health["kv_consistent"]}
    correct = (logit_err <= LOGIT_ATOL and m["compiles"] == 0
               and (not fell_back or not on_chip)
               and (not on_chip or routes.get("paged_attention", {})
                    .get("pallas", 0) >= 1)
               and red["attempted"] > 0 and health["kv_consistent"])
    red["values"]["first_call_s"] = first_call_s
    red["decode_rows"] = list(tap.decode)
    return dict(red, correct=correct, t_window=m["t0"], notes=notes,
                trace_dir=m["trace_dir"], trace_window=m["trace_window"],
                model={"heads": spec.heads, "head_dim": spec.head_dim,
                       "layers": spec.layers, "kv_itemsize": 4})


def sweep(ctx, engine, tap, mix, vocab):
    """Rates in rising order behind one set-up; stops at the first whose
    backlog grows through the window.  Prints a table."""
    import json
    rows, table = [], []
    limit = float(mix.get("sweep_grow_limit", 4.0))
    for rate in ctx["sweep"]:
        m2 = dict(mix, arrival=dict(mix["arrival"], rate=rate))
        requests, closed = serve_requests(m2, ctx["seed"], ctx["seconds"],
                                          vocab)
        m = measure(engine, tap, requests, closed, ctx["seconds"],
                    float(mix.get("drain_s", 10.0)), 0, ctx["out"])
        red = reduce_window(m, tap, requests, closed)
        half = len(red["backlog"]) // 2
        growth = queue_growth(red["backlog"][:half], red["backlog"][half:])
        row = {"rate": rate, "requests": red["attempted"],
               "failed": red["failed"], "backlog_growth": growth,
               "backlog_mean": float(np.mean(red["backlog"] or [0])),
               **{k: red["values"][k] for k in (
                   "ttft_p50_ms", "ttft_p95_ms", "tpot_p50_ms",
                   "batch_occupancy_pct", "step_period_ms_p50",
                   "loadgen_late_ms_p95")},
               "decode_step_ms_p50": percentile(
                   [(b - a) * 1e3 for a, b in red["spans"]["decode"]], 50),
               "prefill_ms_p50": percentile(
                   [(b - a) * 1e3 for a, b in red["spans"]["prefill"]], 50)}
        table.append(row)
        rows.append((rate, growth, red["failed"]))
        print(json.dumps(row), flush=True)
        if growth >= limit or red["failed"]:
            break
        engine.scheduler.drain()
    held = knee(rows, limit)
    out = {"knee": held, "cell_rate": None if held is None else 0.8 * held,
           "grow_limit": limit, "seconds": ctx["seconds"], "rows": table}
    with open(os.path.join(ctx["out"], "sweep.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps({k: out[k] for k in ("knee", "cell_rate")}), flush=True)
    return out
