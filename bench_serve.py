#!/usr/bin/env python
"""bench_serve.py — load generator for the AOT serving engine.

Open-loop arrival process (Poisson interarrivals at ``--rate``
requests/sec, or all-at-once when ``--rate 0``) against an in-process
:class:`paddle_tpu.serving.ServingEngine`, with the scheduler's
continuous-batching loop on a background thread — the same topology as
the HTTP front end minus the socket hop.

Emits ONE JSON record as the last stdout line (BENCH_* house style),
including:

 - ``latency_p50_ms`` / ``latency_p99_ms`` and tokens/sec,
 - batch occupancy and KV-pool utilization,
 - the zero-compile verdict: ``unexpected_compiles`` must be 0 after
   warmup for the run to pass (exit code 1 otherwise),
 - ``platform`` / ``device_kind`` / ``device_count`` as the device
   reports them.

The measurement runs on the TPU: on any other platform the script exits
non-zero at once (same contract as bench.py).  ``BENCH_SMOKE=1`` is the
CPU structure check, and its record says ``"platform": "cpu"``:

    BENCH_SMOKE=1 JAX_PLATFORMS=cpu python bench_serve.py --streams 64 --max-new 8
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--streams", type=int, default=64,
                    help="concurrent request streams to issue")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="open-loop arrival rate in requests/sec "
                         "(0 = all at once)")
    ap.add_argument("--prompt-len", type=int, default=12,
                    help="max prompt length (sampled 3..N per stream)")
    ap.add_argument("--max-new", type=int, default=8,
                    help="tokens to generate per request")
    ap.add_argument("--precision", default=None,
                    choices=("fp32", "bf16", "int8"),
                    help="serve precision (overrides PT_SERVE_PRECISION; "
                         "int8 = PTQ weights + int8 paged KV-cache)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--vocab", type=int, default=256)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request deadline budget in ms (0 = none); "
                         "each request samples uniformly from "
                         "[0.75x, 1.25x] so admission control sees a "
                         "distribution, not a step function")
    ap.add_argument("--result-timeout", type=float, default=300.0,
                    help="per-stream result wait budget")
    ap.add_argument("--out", default=None,
                    help="also write the record to this JSON file")
    return ap.parse_args(argv)


def emit(record, out=None):
    if out:
        try:
            with open(out, "w") as f:
                json.dump(record, f, indent=2, sort_keys=True)
        except OSError as e:
            record.setdefault("errors", {})["out_file"] = str(e)
    print(json.dumps(record), flush=True)


def main(argv=None):
    args = parse_args(argv)
    t_start = time.time()
    precision = (args.precision
                 or os.environ.get("PT_SERVE_PRECISION") or "fp32")
    record = {
        "bench": "serve",
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "ok": False,
        "streams": args.streams,
        "rate": args.rate,
        "max_new_tokens": args.max_new,
        "deadline_ms": args.deadline_ms or None,
        "precision": precision,
    }

    import jax
    dev = jax.devices()[0]
    record.update(platform=dev.platform, device_kind=dev.device_kind,
                  device_count=jax.device_count())
    if dev.platform != "tpu" and not os.environ.get("BENCH_SMOKE"):
        record["error"] = (f"bench_serve needs a TPU: platform is "
                           f"{dev.platform!r} (BENCH_SMOKE=1 is the CPU "
                           "structure check)")
        emit(record, args.out)
        return 1

    from paddle_tpu.device import place_compile_cache
    place_compile_cache()
    from paddle_tpu.observability.telemetry import get_telemetry
    from paddle_tpu.serving import (ModelSpec, ServeConfig, ServingEngine,
                                    init_params)
    from paddle_tpu.serving.scheduler import (DeadlineExceeded,
                                              EngineSaturated,
                                              RequestShed)

    get_telemetry().enable()  # metrics + compile watcher
    # graph audit on for the AOT build: every bucket executable's traced
    # jaxpr is audited while the ladder compiles (load-time only)
    from paddle_tpu.tools.audit import runtime as audit_rt
    audit_rt.enable()

    spec = ModelSpec(vocab_size=args.vocab, hidden=args.hidden,
                     layers=args.layers, heads=args.heads,
                     max_seq_len=args.max_seq)
    cfg = ServeConfig.from_env().replace(precision=precision)
    if not os.environ.get("PT_SERVE_MAX_INFLIGHT"):
        cfg = cfg.replace(max_inflight=max(cfg.max_inflight,
                                           args.streams + 1))
    if not os.environ.get("PT_SERVE_KV_PAGES"):
        # enough headroom that admission control, not pool sizing,
        # shapes the run: ~half the streams resident at worst case
        worst = -(-(args.prompt_len + args.max_new) // cfg.page_size)
        cfg = cfg.replace(kv_pages=max(cfg.kv_pages,
                                       worst * (args.streams // 2) + 2))

    t_build0 = time.time()
    engine = ServingEngine(spec, init_params(spec, args.seed), cfg)
    record["aot_build_sec"] = round(time.time() - t_build0, 3)
    record["compiled_programs"] = engine.compiled_programs
    record["decode_buckets"] = list(engine.config.decode_buckets)
    record["prefill_buckets"] = list(engine.config.prefill_buckets)
    record["kv_pages"] = engine.config.kv_pages
    pool_snap = engine.pool.snapshot()
    record["kv_pool_dtype"] = pool_snap["dtype"]
    record["kv_pool_pages"] = pool_snap["usable_pages"]
    # admission headroom vs an fp32 pool under the SAME byte budget
    # (PT_SERVE_KV_PAGES is fp32-denominated): the int8 memory win
    record["kv_page_headroom_x"] = round(
        pool_snap["usable_pages"] / max(1, cfg.kv_pages - 1), 2)

    engine.scheduler.start()
    rng = np.random.RandomState(args.seed)
    prompts = [
        rng.randint(1, spec.vocab_size,
                    size=rng.randint(3, max(4, args.prompt_len + 1)))
        .tolist()
        for _ in range(args.streams)]

    streams = [None] * args.streams
    saturation_retries = 0
    shed_at_submit = 0
    t_load0 = time.monotonic()
    for i, prompt in enumerate(prompts):
        # open-loop Poisson arrivals: the schedule does not slow down
        # when the engine backs up — that pressure is the point
        if args.rate > 0:
            time.sleep(float(rng.exponential(1.0 / args.rate)))
        deadline_ms = (float(rng.uniform(0.75, 1.25)) * args.deadline_ms
                       if args.deadline_ms > 0 else None)
        while streams[i] is None:
            try:
                streams[i] = engine.scheduler.submit(
                    prompt, max_new_tokens=args.max_new,
                    deadline_ms=deadline_ms)
            except EngineSaturated:
                saturation_retries += 1
                time.sleep(0.002)
            except RequestShed:
                # a shed request is NOT retried — admission control
                # refusing infeasible work is the behaviour under test
                shed_at_submit += 1
                break

    errors = {}
    latencies = []
    tokens_generated = 0
    deadline_losses = 0
    for i, st in enumerate(streams):
        if st is None:
            continue  # shed at admission
        try:
            out = st.result(timeout=args.result_timeout)
            tokens_generated += len(out)
            latencies.append(st.latency)
        except DeadlineExceeded:
            deadline_losses += 1
        except Exception as e:
            errors[f"stream_{i}"] = str(e)
    t_load = time.monotonic() - t_load0
    engine.scheduler.stop()

    sched = engine.scheduler.snapshot()
    kv = engine.pool.snapshot()
    lat_ms = np.asarray([l * 1e3 for l in latencies if l is not None])
    record.update({
        "completed_streams": len(latencies),
        "errors": errors or None,
        "saturation_retries": saturation_retries,
        "load_wall_sec": round(t_load, 3),
        "tokens_generated": tokens_generated,
        "tokens_per_sec": round(tokens_generated / t_load, 2)
        if t_load > 0 else None,
        "requests_per_sec": round(len(latencies) / t_load, 2)
        if t_load > 0 else None,
        "latency_p50_ms": round(float(np.percentile(lat_ms, 50)), 3)
        if lat_ms.size else None,
        "latency_p99_ms": round(float(np.percentile(lat_ms, 99)), 3)
        if lat_ms.size else None,
        "latency_mean_ms": round(float(lat_ms.mean()), 3)
        if lat_ms.size else None,
        "batch_occupancy_mean": round(sched["batch_occupancy_mean"], 4),
        "peak_active_sequences": sched["peak_active"],
        "scheduler_steps": sched["steps"],
        "admission_refusals_kv": sched["refused_kv"],
        "kv_pages_peak_used": kv["high_watermark"],
        "kv_utilization_peak": round(
            kv["high_watermark"] / max(1, kv["usable_pages"]), 4),
        "unexpected_compiles": engine.unexpected_compiles,
        "zero_compile_after_warmup": engine.unexpected_compiles == 0,
        "healthz_ok": engine.healthz()["ok"],
        "audit": audit_rt.snapshot(),
        # resilience accounting: under a deadline regime shed/expired
        # requests are EXPECTED losses — goodput is the figure of merit
        "shed_total": sched["shed"],
        "cancelled_total": sched["cancelled"],
        "deadline_exceeded_total": sched["deadline_exceeded"],
        "goodput": round(len(latencies) / args.streams, 4)
        if args.streams else None,
    })
    # with no deadline regime every stream must complete; with one,
    # shed + expired requests are the shedder doing its job — the run
    # passes on zero UNEXPECTED errors and zero request-path compiles
    expected_done = (args.streams - shed_at_submit - deadline_losses
                     if args.deadline_ms > 0 else args.streams)
    record["ok"] = (not errors
                    and len(latencies) == expected_done
                    and engine.unexpected_compiles == 0)
    engine.close()
    # quality leg: max-logit-divergence vs the fp32 oracle, replayed
    # eagerly AFTER close (the compile sentinel is disarmed, so the
    # oracle's eager compiles can't book as request-path compiles)
    if precision == "int8":
        from paddle_tpu.serving.quant import (default_calibration_prompts,
                                              logit_divergence)
        record["max_logit_divergence"] = round(logit_divergence(
            spec, init_params(spec, args.seed),
            default_calibration_prompts(spec),
            page_size=cfg.page_size), 6)
    else:
        record["max_logit_divergence"] = 0.0
    record["bench_wall_sec"] = round(time.time() - t_start, 1)
    emit(record, args.out)
    return 0 if record["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
