"""Eager (dygraph) per-op dispatch microbenchmark.

The reference spends an entire codegen subsystem keeping eager dispatch
cheap (``paddle/fluid/eager/auto_code_generator/``, SURVEY §3.1). Our
dygraph tape instead pays one ``jax.vjp`` trace per recorded op. This
script puts a number on that: per-op wall time for

 - ``raw_jax``      : bare jax.numpy dispatch (the floor),
 - ``tape_off``     : paddle_tpu Tensor op with stop_gradient=True
                      (funnel overhead, no autograd),
 - ``tape_on``      : same op recorded on the tape (jax.vjp per op),
 - ``captured_step``: the chain behind ``jit.capture_step`` — one cached
                      jitted program plus the capture dispatch layer
                      (signature hash, state writeback),
 - ``jit_chain``    : the whole chain as one jitted program (per-op cost
                      amortized — the floor capture aims for).

The record also carries a ``capture`` block: a 10-step captured MLP
train run asserting the trace-and-cache contract (1 compile, >=9 cache
hits, recompile sentinel quiet) — and a ``numerics_contract`` block
asserting the monitored-capture contract: folding the numerics
sentinel into the captured step keeps exactly one compile, changes no
math (bit-identical loss sequence), stays quiet on healthy training,
and costs < 3% wall overhead per step.  The ``memory_contract`` block
holds the memory monitor to the same bar: footprint harvested at the
one compile, census attributing parameter bytes, and < 1% step
overhead with watermark sampling on every step.

Host-side dispatch cost: runs on the CPU backend, never on the chip.
Prints ONE json line.
"""
from __future__ import annotations

import json
import os
import time

os.environ["JAX_PLATFORMS"] = "cpu"

N_OPS = 200
REPEATS = 20
SHAPE = (64, 64)


def _bench_all(variants):
    """Interleaved min-of-REPEATS over all variants: the bench box is a
    single noisy core, and measuring variants back-to-back lets load
    drift fake a high tape/raw ratio. One round measures every variant
    once; the per-variant min over rounds drops the noise floor of each
    independently."""
    best = {name: float("inf") for name, _, _ in variants}
    for name, fn, block in variants:  # untimed warmup
        block(fn())
    for _ in range(REPEATS):
        for name, fn, block in variants:
            # one untimed call first: the runtime defers buffer cleanup
            # from the PREVIOUS variant's op storm into the next
            # dispatch, which would bill ~100us of teardown to whoever
            # runs after tape_on; this absorbs it so every slot times
            # its own steady state
            block(fn())
            t0 = time.perf_counter()
            block(fn())
            dt = time.perf_counter() - t0
            if dt < best[name]:
                best[name] = dt
    return {name: best[name] / N_OPS for name, _, _ in variants}


def _capture_contract(pt):
    """10-step captured MLP train run: the trace-and-cache acceptance
    check (exactly 1 compile, cache hits >= 9, sentinel quiet) attached
    to every bench record so perf drift in the capture layer is caught
    by the same artifact as the dispatch numbers."""
    import numpy as np
    import paddle_tpu.nn as nn
    from paddle_tpu.observability import get_telemetry

    from paddle_tpu.observability.trace import get_tracer

    np.random.seed(0)
    pt.seed(0)
    model = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 1))
    opt = pt.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                parameters=model.parameters())
    mse = nn.MSELoss()

    @pt.jit.capture_step
    def step(x, y):
        loss = mse(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    x = pt.to_tensor(np.random.randn(4, 8).astype(np.float32))
    y = pt.to_tensor(np.random.randn(4, 1).astype(np.float32))
    first = last = None
    t0 = time.perf_counter()
    for i in range(10):
        loss = float(np.asarray(step(x, y)._data))
        first = loss if first is None else first
        last = loss
    # feed the tracer the measured step time: with the captured
    # program's cost_analysis FLOPs (harvested at compile) and the
    # nominal cpu peak, the record's trace block carries a real
    # analytic-MFU figure even with the TPU unreachable
    get_tracer().on_step((time.perf_counter() - t0) / 10)
    storms = get_telemetry().snapshot()["recompile_storms"]
    return {
        "steps": 10,
        "compiles": step.stats["compiles"],
        "hits": step.stats["hits"],
        "misses": step.stats["misses"],
        "fallback": step.stats["fallback"],
        "sentinel_storms": storms,
        "loss_first": round(first, 6),
        "loss_last": round(last, 6),
        "ok": (step.stats["compiles"] == 1 and step.stats["hits"] >= 9
               and step.stats["fallback"] is None and not storms
               and last < first),
    }


def _amp_contract(pt):
    """AMP O2 acceptance check: the 10-step MLP train run captured with
    bf16-decorated params (fp32 master weights in the optimizer) vs the
    fp32 baseline from identical seeds.  The contract is exactly 1
    compile each, a quiet numerics sentinel riding inside the AMP
    program, a decreasing loss, and a final loss within tolerance of
    fp32 — low precision must change throughput, not where the model
    goes.  Timing uses the same interleaved min-of-rounds discipline as
    ``_numerics_contract`` (on CPU bf16 is emulated, so the ratio is
    reported, not gated)."""
    import numpy as np
    import jax
    import paddle_tpu.nn as nn
    from paddle_tpu.observability.numerics import get_monitor, \
        reset_monitor

    def build(amp):
        reset_monitor()
        if amp:
            get_monitor().enable(cadence=4)
        np.random.seed(3)
        pt.seed(3)
        model = nn.Sequential(nn.Linear(256, 256), nn.ReLU(),
                              nn.Linear(256, 1))
        if amp:
            pt.amp.decorate(model, level="O2", dtype="bfloat16")
        opt = pt.optimizer.Momentum(learning_rate=0.005, momentum=0.9,
                                    parameters=model.parameters(),
                                    multi_precision=True)
        mse = nn.MSELoss()

        @pt.jit.capture_step
        def step(x, y):
            loss = mse(model(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        return step

    rng = np.random.RandomState(4)
    xs = rng.randn(4096, 256).astype(np.float32)
    ys = rng.randn(4096, 1).astype(np.float32)
    y = pt.to_tensor(ys)
    # the AMP step eats bf16 activations end to end — feeding it fp32
    # inputs would silently promote every matmul back to full precision
    x32 = pt.to_tensor(xs)
    x16 = pt.to_tensor(xs).astype("bfloat16")

    def run10(step, x):
        return [float(np.asarray(step(x, y)._data, np.float32))
                for _ in range(10)]

    step_off = build(False)
    losses_off = run10(step_off, x32)
    step_amp = build(True)
    losses_amp = run10(step_amp, x16)
    mon = get_monitor()
    quiet = mon.anomaly_count() == 0
    final_off, final_amp = losses_off[-1], losses_amp[-1]
    gap = abs(final_amp - final_off)
    tol = max(0.05, 0.05 * abs(final_off))

    best = {False: float("inf"), True: float("inf")}
    steps = {False: (step_off, x32), True: (step_amp, x16)}
    for r in range(20):
        order = (False, True) if r % 2 == 0 else (True, False)
        for amp in order:
            s, x = steps[amp]
            jax.block_until_ready(s(x, y)._data)
            t0 = time.perf_counter()
            jax.block_until_ready(s(x, y)._data)
            best[amp] = min(best[amp], time.perf_counter() - t0)
    return {
        "steps": 10,
        "compiles_fp32": step_off.stats["compiles"],
        "compiles_amp": step_amp.stats["compiles"],
        "loss_final_fp32": round(final_off, 6),
        "loss_final_amp": round(final_amp, 6),
        "loss_gap": round(gap, 6),
        "loss_tolerance": round(tol, 6),
        "sentinel_quiet": quiet,
        "step_us_fp32": round(best[False] * 1e6, 1),
        "step_us_amp": round(best[True] * 1e6, 1),
        "amp_speedup_x": round(best[False] / best[True], 3)
        if best[True] else None,
        "ok": (step_off.stats["compiles"] == 1
               and step_amp.stats["compiles"] == 1
               and quiet and gap <= tol
               and losses_off[-1] < losses_off[0]
               and losses_amp[-1] < losses_amp[0]),
    }


def _numerics_contract(pt):
    """Monitored-capture acceptance check: the same 10-step MLP run
    with the numerics sentinel on vs off. The monitor's health outputs
    ride inside the one compiled program, so the contract is exactly
    1 compile each, a bit-identical loss sequence, a quiet sentinel,
    and a per-step overhead ratio under 1.03 (interleaved min-of-rounds
    timing, same noise discipline as ``_bench_all``)."""
    import numpy as np
    import jax
    import paddle_tpu.nn as nn
    from paddle_tpu.observability.numerics import get_monitor, \
        reset_monitor

    def build(monitored):
        reset_monitor()
        if monitored:
            get_monitor().enable(cadence=4)
        np.random.seed(1)
        pt.seed(1)
        model = nn.Sequential(nn.Linear(256, 256), nn.ReLU(),
                              nn.Linear(256, 1))
        opt = pt.optimizer.Momentum(learning_rate=0.005, momentum=0.9,
                                    parameters=model.parameters())
        mse = nn.MSELoss()

        @pt.jit.capture_step
        def step(x, y):
            loss = mse(model(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        return step

    # batch 8192 / ~26ms step: the health program costs a handful of
    # small reductions plus one pass for the grad norm — a near-fixed
    # fee. Against a micro-batch toy step that fee reads as 10%+;
    # the 3% bound is about a realistically-fed step, so the contract
    # measures one.
    rng = np.random.RandomState(2)
    x = pt.to_tensor(rng.randn(8192, 256).astype(np.float32))
    y = pt.to_tensor(rng.randn(8192, 1).astype(np.float32))

    def run10(step):
        return [np.asarray(step(x, y)._data).tobytes()
                for _ in range(10)]

    # correctness leg: train 10 steps each way from identical seeds.
    # the unmonitored step is built while the monitor singleton is
    # disabled, so its traced program carries no health outputs at all.
    step_off = build(False)
    losses_off = run10(step_off)
    step_on = build(True)
    losses_on = run10(step_on)
    mon = get_monitor()
    bitwise = losses_on == losses_off
    quiet = mon.anomaly_count() == 0
    reads = mon.snapshot()["reads"]

    # timing leg: both steps are warm replays now; interleave rounds so
    # load drift hits both columns equally, and run one untimed absorb
    # call before each timed one (same discipline as _bench_all — the
    # runtime defers the previous variant's buffer cleanup into the
    # next dispatch, which would bill off's teardown to on)
    best = {False: float("inf"), True: float("inf")}
    steps = {False: step_off, True: step_on}
    for r in range(20):
        order = (False, True) if r % 2 == 0 else (True, False)
        for monitored in order:
            s = steps[monitored]
            jax.block_until_ready(s(x, y)._data)
            t0 = time.perf_counter()
            jax.block_until_ready(s(x, y)._data)
            best[monitored] = min(best[monitored],
                                  time.perf_counter() - t0)
    best_off, best_on = best[False], best[True]
    ratio = best_on / best_off if best_off else None
    return {
        "steps": 10,
        "compiles_off": step_off.stats["compiles"],
        "compiles_on": step_on.stats["compiles"],
        "monitor_reads": reads,
        "loss_bitwise_identical": bitwise,
        "sentinel_quiet": quiet,
        "step_us_off": round(best_off * 1e6, 1),
        "step_us_on": round(best_on * 1e6, 1),
        "overhead_ratio": round(ratio, 4) if ratio else None,
        "ok": (step_off.stats["compiles"] == 1
               and step_on.stats["compiles"] == 1
               and bitwise and quiet
               and ratio is not None and ratio < 1.03),
    }


def _sdc_contract(pt):
    """SDC-sentry acceptance check: the same 10-step MLP run with the
    replica-fingerprint sentry on vs off. The bitcast word-sum digests
    of every updated parameter and optimizer slot ride inside the one
    compiled program (standalone recording mode — no peer exchange on
    a single process), so the contract is exactly 1 compile each, a
    bit-identical loss sequence (fingerprinting changes no math), the
    cadenced host reads actually booked with zero divergence verdicts,
    and a per-step overhead ratio under 1.01 (interleaved
    min-of-rounds timing, same noise discipline as ``_bench_all``)."""
    import numpy as np
    import jax
    import paddle_tpu.nn as nn
    from paddle_tpu.observability.sdc import get_monitor, reset_monitor

    def build(monitored):
        reset_monitor()
        if monitored:
            get_monitor().enable(cadence=4, halt=False)
        np.random.seed(5)
        pt.seed(5)
        model = nn.Sequential(nn.Linear(256, 256), nn.ReLU(),
                              nn.Linear(256, 1))
        opt = pt.optimizer.Momentum(learning_rate=0.005, momentum=0.9,
                                    parameters=model.parameters())
        mse = nn.MSELoss()

        @pt.jit.capture_step
        def step(x, y):
            loss = mse(model(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        return step

    # batch 8192 / ~26ms step: the digest program is one bitcast + sum
    # per leaf — a near-fixed fee; the 1% bound is about a
    # realistically-fed step, so the contract measures one (same
    # sizing rationale as _numerics_contract)
    rng = np.random.RandomState(6)
    x = pt.to_tensor(rng.randn(8192, 256).astype(np.float32))
    y = pt.to_tensor(rng.randn(8192, 1).astype(np.float32))

    def run10(step):
        return [np.asarray(step(x, y)._data).tobytes()
                for _ in range(10)]

    # correctness leg: train 10 steps each way from identical seeds.
    # the unfingerprinted step is built while the singleton is
    # disabled, so its traced program carries no digest outputs at all.
    step_off = build(False)
    losses_off = run10(step_off)
    step_on = build(True)
    losses_on = run10(step_on)
    mon = get_monitor().flush()
    snap = mon.snapshot()
    bitwise = losses_on == losses_off
    clean = snap["divergences_total"] == 0

    # timing leg: both steps are warm replays now; interleave rounds so
    # load drift hits both columns equally (absorb-call discipline as
    # in _bench_all / _numerics_contract)
    best = {False: float("inf"), True: float("inf")}
    steps = {False: step_off, True: step_on}
    for r in range(20):
        order = (False, True) if r % 2 == 0 else (True, False)
        for monitored in order:
            s = steps[monitored]
            jax.block_until_ready(s(x, y)._data)
            t0 = time.perf_counter()
            jax.block_until_ready(s(x, y)._data)
            best[monitored] = min(best[monitored],
                                  time.perf_counter() - t0)
    best_off, best_on = best[False], best[True]
    ratio = best_on / best_off if best_off else None
    return {
        "steps": 10,
        "compiles_off": step_off.stats["compiles"],
        "compiles_on": step_on.stats["compiles"],
        "fingerprint_reads": snap["reads"],
        "last_fingerprint": snap["last_fingerprint"],
        "divergences_total": snap["divergences_total"],
        "loss_bitwise_identical": bitwise,
        "step_us_off": round(best_off * 1e6, 1),
        "step_us_on": round(best_on * 1e6, 1),
        "overhead_ratio": round(ratio, 4) if ratio else None,
        "ok": (step_off.stats["compiles"] == 1
               and step_on.stats["compiles"] == 1
               and bitwise and clean
               and snap["reads"] >= 2
               and ratio is not None and ratio < 1.01),
    }


def _memory_contract(pt):
    """Memory-observability acceptance check: the same captured MLP
    run with the memory monitor on vs off. The footprint harvest rides
    the compile (AOT memory_analysis on the cache-shared program) and
    the watermark sampling is a host-side allocator read per step, so
    the contract is exactly 1 compile each, a bit-identical loss
    sequence (monitoring changes no math), the per-program footprint
    actually booked, and a per-step overhead ratio under 1.01 with
    sampling on every step (interleaved min-of-rounds timing, same
    noise discipline as ``_bench_all``)."""
    import numpy as np
    import jax
    import paddle_tpu.nn as nn
    from paddle_tpu.observability.memory import get_memory_monitor, \
        reset_memory_monitor

    def build(monitored):
        reset_memory_monitor()
        if monitored:
            get_memory_monitor().enable(sample_every=1)
        np.random.seed(3)
        pt.seed(3)
        model = nn.Sequential(nn.Linear(256, 256), nn.ReLU(),
                              nn.Linear(256, 1))
        opt = pt.optimizer.Momentum(learning_rate=0.005, momentum=0.9,
                                    parameters=model.parameters())
        mse = nn.MSELoss()

        @pt.jit.capture_step
        def step(x, y):
            loss = mse(model(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        return step

    # batch 8192 / ~26ms step: the allocator read + census identity map
    # is a near-fixed per-step fee; the 1% bound is about a
    # realistically-fed step, so the contract measures one (same
    # sizing rationale as _numerics_contract)
    rng = np.random.RandomState(4)
    x = pt.to_tensor(rng.randn(8192, 256).astype(np.float32))
    y = pt.to_tensor(rng.randn(8192, 1).astype(np.float32))

    def run10(step):
        return [np.asarray(step(x, y)._data).tobytes()
                for _ in range(10)]

    # correctness leg: train 10 steps each way from identical seeds.
    # the unmonitored step is built while the singleton is disabled, so
    # its capture registers no provider and harvests nothing.
    step_off = build(False)
    losses_off = run10(step_off)
    step_on = build(True)
    losses_on = run10(step_on)
    mm = get_memory_monitor()
    snap = mm.snapshot()
    harvested = bool(snap["programs"])
    census = mm.live_buffer_census()
    bitwise = losses_on == losses_off

    # timing leg: both steps are warm replays now; interleave rounds so
    # load drift hits both columns equally (absorb-call discipline as
    # in _bench_all / _numerics_contract)
    best = {False: float("inf"), True: float("inf")}
    steps = {False: step_off, True: step_on}
    for r in range(20):
        order = (False, True) if r % 2 == 0 else (True, False)
        for monitored in order:
            s = steps[monitored]
            jax.block_until_ready(s(x, y)._data)
            t0 = time.perf_counter()
            jax.block_until_ready(s(x, y)._data)
            best[monitored] = min(best[monitored],
                                  time.perf_counter() - t0)
    best_off, best_on = best[False], best[True]
    ratio = best_on / best_off if best_off else None
    return {
        "steps": 10,
        "compiles_off": step_off.stats["compiles"],
        "compiles_on": step_on.stats["compiles"],
        "footprint_harvested": harvested,
        "fit_ok": snap["fit_ok"],
        "census_param_bytes": census["by_category"].get("param", 0),
        "oom_events": snap["oom_events"],
        "loss_bitwise_identical": bitwise,
        "step_us_off": round(best_off * 1e6, 1),
        "step_us_on": round(best_on * 1e6, 1),
        "overhead_ratio": round(ratio, 4) if ratio else None,
        "ok": (step_off.stats["compiles"] == 1
               and step_on.stats["compiles"] == 1
               and harvested and bitwise
               and census["by_category"].get("param", 0) > 0
               and snap["oom_events"] == 0
               and ratio is not None and ratio < 1.01),
    }


def main():
    import jax
    import jax.numpy as jnp
    jax.config.update("jax_platforms", "cpu")
    import paddle_tpu as pt

    x = jnp.ones(SHAPE, jnp.float32)
    y = jnp.full(SHAPE, 0.5, jnp.float32)

    def raw_jax():
        z = x
        for _ in range(N_OPS):
            z = z * y + y
        return z

    tx = pt.to_tensor(x)
    ty = pt.to_tensor(y)
    tx.stop_gradient = True
    ty.stop_gradient = True

    def tape_off():
        z = tx
        for _ in range(N_OPS):
            z = z * ty + ty
        return z

    gx = pt.to_tensor(x)
    gy = pt.to_tensor(y)
    gx.stop_gradient = False
    gy.stop_gradient = False

    def tape_on():
        z = gx
        for _ in range(N_OPS):
            z = z * gy + gy
        return z

    from paddle_tpu.observability import get_telemetry
    from paddle_tpu.observability.trace import get_tracer
    tel = get_telemetry().enable()
    # tracing on for the whole bench: capture harvests per-program
    # cost_analysis FLOPs at compile time, replays record compute spans
    tr = get_tracer().enable()
    # goodput ledger decomposes that same span ring; its block rides on
    # the record like telemetry/trace do
    from paddle_tpu.observability.goodput import get_goodput
    gp = get_goodput().enable()
    # graph audit on for the whole bench: every capture_step compile in
    # this file (the chain, the contract runs) gets its
    # jaxpr audited at capture time — replays cost nothing
    from paddle_tpu.tools.audit import runtime as audit_rt
    audit_rt.enable()

    # the chain takes its inputs as ARGUMENTS: closed-over operands let
    # XLA constant-fold the whole program into one literal, which would
    # report dispatch-of-a-constant (~0.03us/op) instead of a runnable
    # step and wreck the captured/jit ratio below
    def chain(a, b):
        z = a
        for _ in range(N_OPS):
            z = z * b + b
        return z

    jitted = jax.jit(chain)
    jitted(x, y)  # compile outside the timing

    cx = pt.to_tensor(x)
    cy = pt.to_tensor(y)
    cx.stop_gradient = True
    cy.stop_gradient = True

    @pt.jit.capture_step
    def cap_chain(a, b):
        z = a
        for _ in range(N_OPS):
            z = z * b + b
        return z

    block_jax = lambda z: jax.block_until_ready(z)
    block_pt = lambda z: jax.block_until_ready(z._data)

    us = _bench_all([
        ("raw_jax", raw_jax, block_jax),
        ("tape_off", tape_off, block_pt),
        ("tape_on", tape_on, block_pt),
        ("captured_step", lambda: cap_chain(cx, cy), block_pt),
        ("jit_chain", lambda: jitted(x, y), block_jax),
    ])
    res = {
        "metric": "eager_dispatch_overhead",
        "unit": "us/op",
        **{k: round(v * 1e6, 2) for k, v in us.items()},
        "n_ops": N_OPS,
        "shape": list(SHAPE),
    }
    # each op here is mul+add fused in one funnel call; normalize names
    res["tape_overhead_ratio"] = round(res["tape_on"] / res["raw_jax"], 2) \
        if res["raw_jax"] else None
    res["captured_vs_jit_ratio"] = \
        round(res["captured_step"] / res["jit_chain"], 2) \
        if res["jit_chain"] else None
    res["value"] = res["tape_on"]
    res["precision"] = "fp32"
    res["capture"] = _capture_contract(pt)
    res["numerics_contract"] = _numerics_contract(pt)
    res["amp_contract"] = _amp_contract(pt)
    res["sdc_contract"] = _sdc_contract(pt)
    res["memory_contract"] = _memory_contract(pt)
    res["telemetry"] = tel.snapshot()
    res["trace"] = tr.snapshot()
    res["goodput"] = gp.snapshot()
    from paddle_tpu.observability.numerics import get_monitor
    res["numerics"] = get_monitor().snapshot()
    from paddle_tpu.observability.sdc import get_monitor as _sdc_mon
    res["sdc"] = _sdc_mon().snapshot()
    from paddle_tpu.observability.memory import get_memory_monitor
    res["memory"] = get_memory_monitor().snapshot()
    res["audit"] = audit_rt.snapshot()
    from paddle_tpu.distributed.supervisor import supervision_snapshot
    res["supervision"] = supervision_snapshot()
    try:
        from paddle_tpu.observability import cluster_snapshot
        res["telemetry_cluster"] = cluster_snapshot(
            url=os.environ.get("PT_AGGREGATOR_URL") or None)
    except Exception as e:  # snapshot is best-effort by contract
        res["telemetry_cluster"] = {"error": str(e)[:200]}
    print(json.dumps(res))


if __name__ == "__main__":
    main()
