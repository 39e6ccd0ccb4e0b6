"""Run one cell of BENCHMARK.json once, in one process.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

prints as its last line one JSON object: `correct`, `attempted`,
`failed`, `metrics`, `device` and, traced, `breakdown`.  Nothing about
a cell lives in this file: the cell names a configuration
(`configs/<config>.json`, which names its runner, `runners/<kind>.py`)
and a traffic mix (`traffic/<traffic>.json`); a per-layer metric is read
by `layer_metrics/<name>.py` or `.json`.  See README.md.

`--sweep r1,r2,...` (serve cells) offers each rate in rising order for
`--seconds` behind one set-up and stops at the first whose queue grows;
it prints a table and no result line.  `--allow-cpu` is the off-chip
rehearsal: it prints a line marked as a rehearsal with no device metric.
"""
from __future__ import annotations

import time
T_PROCESS = time.monotonic()

import argparse
import importlib.util
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
OUT = os.path.join(BENCH, "out")
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)


def load_json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_module(kind, name):
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_for(entries, cell):
    return [m for m in entries
            if "workloads" not in m or cell in m["workloads"]]


def place_compile_cache():
    """jax's persistent cache at a fixed path inside the checkout (the
    path is part of the key), or where JAX_COMPILATION_CACHE_DIR says.
    Every program is cached, however quick its compile, so that the
    second run of a cell compiles nothing."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(OUT, "jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def device_facts(chips):
    import jax
    devs = jax.devices()[:chips]
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def read_layer_metric(name, run):
    """A reader is `layer_metrics/<name>.py` with `read(run)`, or
    `<name>.json`: {"value": key} takes a number the runner published,
    {"counter": key} a counter, {"span": name, "percentile": q} the
    percentile in ms of that span's durations, {"ops": regex,
    "program": regex, "text": regex} the device ms of operations whose
    short name (and, if given, HLO line) match, a run of the matching
    program, {"idle": true} the share in % of the traced window in
    which no operation ran on the device.  Nothing to read gives None."""
    py = os.path.join(BENCH, "layer_metrics", name + ".py")
    if os.path.exists(py):
        return load_module("layer_metrics", name).read(run)
    spec = load_json("layer_metrics", name + ".json")
    if "value" in spec:
        return run["values"].get(spec["value"])
    if "counter" in spec:
        return run["counters"].get(spec["counter"])
    if "span" in spec:
        from stats import percentile
        durs = [(b - a) * 1e3 for a, b in run["spans"].get(spec["span"], [])]
        return percentile(durs, spec["percentile"])
    if "idle" in spec:
        tr = run["trace"]
        if tr is None or not tr.window_s:
            return None
        return 100.0 * (1.0 - tr.busy_s() / tr.window_s)
    if "ops" in spec:
        if run["trace"] is None:
            return None
        per_run, _ = run["trace"].per_run(spec)
        return None if per_run is None else per_run * 1e3
    raise ValueError(f"layer metric {name}: nothing to read in {spec}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--sweep", default=None,
                    help="serve cells: comma-separated rates, rising")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="off-chip rehearsal: no device metric is printed")
    args = ap.parse_args(argv)

    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload in cells:
        cell = cells[args.workload]
    elif args.allow_cpu and "." in args.workload:
        # a rehearsal may pair any config and mix: <config>.<traffic>
        config, traffic = args.workload.split(".", 1)
        cell = {"name": args.workload, "config": config,
                "traffic": traffic, "chips": 1}
    else:
        print(f"run.py: no cell {args.workload!r} in BENCHMARK.json",
              file=sys.stderr)
        return 2
    seconds = args.seconds if args.seconds is not None \
        else float(bench["run_seconds"])

    import paddle_tpu  # noqa: F401  the system under test; absent: fail
    import jax
    devs = jax.devices()
    on_chip = devs[0].platform == "tpu" and len(devs) >= cell["chips"]
    if not on_chip and not args.allow_cpu:
        print(f"run.py: cell {cell['name']} needs {cell['chips']} TPU "
              f"chip(s); jax reports {len(devs)} x {devs[0].platform}",
              file=sys.stderr)
        return 3
    os.makedirs(OUT, exist_ok=True)
    place_compile_cache()

    config = load_json("configs", cell["config"] + ".json")
    traffic = load_json("traffic", cell["traffic"] + ".json")
    peaks = load_json("peaks.json")["peaks"]
    kind = devs[0].device_kind
    if on_chip and kind not in peaks:
        print(f"run.py: no peaks for device kind {kind!r} in peaks.json",
              file=sys.stderr)
        return 3
    ctx = {"cell": cell, "config": config, "traffic": traffic,
           "seed": args.seed, "seconds": seconds, "trace": bool(args.trace),
           "t_process": T_PROCESS, "out": os.path.join(OUT, cell["name"]),
           "peak": peaks.get(kind), "chips": cell["chips"],
           "sweep": ([float(r) for r in args.sweep.split(",")]
                     if args.sweep else None)}
    os.makedirs(ctx["out"], exist_ok=True)
    runner = load_module("runners", config["runner"])
    run = runner.run(ctx)
    if args.sweep:
        return 0

    run["values"]["setup_s"] = run["t_window"] - T_PROCESS
    run.update(config=config, traffic=traffic, peak=ctx["peak"],
               chips=cell["chips"], seconds=seconds, trace=None)
    device = device_facts(cell["chips"])
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    result = {"correct": bool(run["correct"]),
              "attempted": int(run["attempted"]),
              "failed": int(run["failed"])}
    if args.trace:
        import trace_reduce
        path = trace_reduce.find_xplane(run["trace_dir"])
        if path:
            raw = trace_reduce.load_xplane(
                path, keep_lines=lambda plane, line:
                plane.startswith("/device:") or plane.startswith("/host:"))
            reduced = trace_reduce.Reduced(raw)
            with open(os.path.join(ctx["out"], "trace_summary.json"),
                      "w") as f:
                json.dump(trace_reduce.summary(raw), f, indent=1)
            if reduced.chips:
                run["trace"] = reduced
                device["busy_s"] = reduced.busy_s()
                device["window_s"] = reduced.window_s
                result["breakdown"] = reduced.breakdown()
        wanted = metrics_for(bench["per_layer"], cell["name"])
    else:
        wanted = metrics_for(bench["end_to_end"], cell["name"])
    metrics = {}
    for m in wanted:
        value = (read_layer_metric(m["name"], run) if args.trace
                 else run["values"].get(m["name"]))
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    if run.get("notes"):
        result["notes"] = run["notes"]
    if not on_chip:
        # a CPU run has no device metric: print what was counted only
        result = {"rehearsal": "cpu run: NOT a chip run, no metric is "
                  "a device metric", "correct": result["correct"],
                  "attempted": result["attempted"],
                  "failed": result["failed"],
                  "metric_names": sorted(metrics),
                  "counted": sorted(k for k, v in run["values"].items()
                                    if v is not None),
                  "notes": run.get("notes")}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
