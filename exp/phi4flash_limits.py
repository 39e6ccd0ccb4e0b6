"""Readings behind the limits of `benchmarks/runners/serve_hybrid.py`
(`LOGIT_ATOL`, `STATE_RTOL`), on the chip:

    chiprun --chips 1 --timeout 2400 -- python3 exp/phi4flash_limits.py [seed] [rows] [only]

builds the `phi4-mini-flash-serve` engine as the cell does, picks the
check's rows from the cell's traffic, and holds the engine against the
plain reference and against its four variants (weights rounded to float8
e4m3, the SSM state rounded to bfloat16 every position, layer 0's
lambda0 in every layer, a window of 511).  The variants run on the first
`rows` rows (default 16: two blocks of the mix, so both pads); `only` is
a comma-separated list of parts of the comparisons' names to run.  Prints
one JSON line a comparison; writes them to `chiprun_out/phi4flash_limits.json`.
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "benchmarks"), ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def main():
    from runners import serve_hybrid as runner
    from traffic import serve_requests
    import run as bench_run
    bench_run.place_compile_cache()
    seed = int(sys.argv[1]) if len(sys.argv) > 1 else 31
    few = int(sys.argv[2]) if len(sys.argv) > 2 else 16
    only = sys.argv[3].split(",") if len(sys.argv) > 3 else [""]
    config = json.load(open(os.path.join(
        ROOT, "benchmarks/configs/phi4-mini-flash-serve.json")))
    mix = json.load(open(os.path.join(
        ROOT, "benchmarks/traffic/reasoning-backlog.json")))
    t0 = time.monotonic()
    engine, params, spec, build_s = runner.build_engine(config, seed)
    out = [{"what": "build", "seconds": build_s,
            "program_bytes": engine.stats["program_bytes"],
            "memory": jax.devices()[0].memory_stats()}]
    print(json.dumps(out[-1]), flush=True)
    requests, _ = serve_requests(mix, seed, 40.0, spec.vocab_size)
    prompts = runner.pick_rows(requests, config, engine.config.page_size,
                               engine.config.decode_buckets[-1])
    variants = [("reference", {}, prompts),
                ("float8_e4m3 weights", {"round_to": jnp.float8_e4m3fn},
                 prompts[:few]),
                ("bfloat16 state", {"state_dtype": jnp.bfloat16},
                 prompts[:few]),
                ("lambda0 of layer 0", {"lambda0_layer": 0}, prompts[:few]),
                ("window 511", {"window": config["sliding_window"] - 1},
                 prompts[:few]),
                ("reference, the few rows", {}, prompts[:few])]
    for what, variant, rows in variants:
        if not any(part in what for part in only):
            continue
        t = time.monotonic()
        err, state, slow = runner.check_against_reference(
            engine, params, config, rows, **variant)
        out.append({"what": what, "rows": len(rows), "logit_err": err,
                    "state_err": state, "state_err_slow": slow,
                    "seconds": time.monotonic() - t,
                    "lens": sorted(len(p) for p in rows)})
        print(json.dumps(out[-1]), flush=True)
    out.append({"what": "done", "seconds": time.monotonic() - t0,
                "memory": jax.devices()[0].memory_stats()})
    print(json.dumps(out[-1]), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "phi4flash_limits.json"),
              "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
