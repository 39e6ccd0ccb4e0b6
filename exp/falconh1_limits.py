"""Readings behind the limits of `benchmarks/runners/serve_ssd.py`
(`LOGIT_ATOL`, `STATE_RTOL`, `STATE_RTOL_DEEP`), on the chip:

    chiprun --chips 1 --timeout 2400 -- python3 exp/falconh1_limits.py [seeds] [config] [traffic]

builds the `falcon-h1-34b-serve` engine once as the cell does and, for
each of `seeds` weight seeds (default 8; the weights swapped in without a
compile), picks the check's rows from the cell's traffic, drives them
through the engine (the runner's `drive_rows`), frees the pools, holds
the compared rows against the plain reference, and makes the pools anew.
On the first seed it then holds the same engine outputs against the five
wrong references of ISSUE 38 (the state rounded to bfloat16 every
position, weights rounded to float8 e4m3, the gated norm over one group,
the two groups' B and C swapped, no `key_multiplier`) over the rows that
fit the shortest pad.  Prints one JSON line a comparison and writes them
to `chiprun_out/falconh1_limits.json`.  With `tiny-falconh1-serve` and
`tiny-closed` it walks here on the CPU.
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [os.path.join(ROOT, "benchmarks"), ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def main():
    from runners import serve_ssd as runner
    from runners.serve_hybrid import STEPS, drive_rows
    from traffic import serve_requests
    from paddle_tpu.serving.engine import aot_build_phase
    import run as bench_run
    bench_run.place_compile_cache()
    seeds = int(sys.argv[1]) if len(sys.argv) > 1 else 8
    name = sys.argv[2] if len(sys.argv) > 2 else "falcon-h1-34b-serve"
    traffic = sys.argv[3] if len(sys.argv) > 3 else "longform-backlog"
    config = json.load(open(os.path.join(
        ROOT, "benchmarks/configs", name + ".json")))
    mix = json.load(open(os.path.join(
        ROOT, "benchmarks/traffic", traffic + ".json")))
    t0 = time.monotonic()
    base = 3800000000
    engine, params, spec, build_s = runner.build_engine(config, base)
    make = runner.make_params(spec, engine.config.precision)
    out = [{"what": "build", "seconds": build_s,
            "program_bytes": engine.stats["program_bytes"],
            "memory": jax.devices()[0].memory_stats()}]
    print(json.dumps(out[-1]), flush=True)
    shapes = [(a.shape, a.dtype) for a in engine.pool.state()]

    def one(seed, params):
        requests, _ = serve_requests(mix, seed, 40.0, spec.vocab_size)
        prompts = runner.pick_rows(requests, config, engine.config.page_size,
                                   engine.config.decode_buckets[-1])
        t = time.monotonic()
        with aot_build_phase():
            driven = drive_rows(engine, prompts)
        drive_s = time.monotonic() - t
        runner.free_pools(engine)
        t = time.monotonic()
        err, state, slow = runner.compare_rows(params, config, spec,
                                               prompts, driven)
        out.append({"what": "reference", "seed": seed, "logit_err": err,
                    "state_err": state, "state_err_slow": slow,
                    "within": runner.within(err, slow),
                    "logit_scale": float(np.std(np.stack(driven[0][0]))),
                    "drive_s": drive_s, "seconds": time.monotonic() - t,
                    "lens": [len(prompts[i]) for i in
                             runner.compared(prompts, config)]})
        print(json.dumps(out[-1]), flush=True)
        return prompts, driven

    def wrong(seed, params, prompts, driven):
        """The same engine outputs against the wrong references, over the
        rows that fit the shortest pad."""
        short = min(config["check"]["pads"])
        rows = [i for i in range(len(prompts))
                if len(prompts[i]) + STEPS <= short][:6]
        for what, variant in (
                ("reference, the short rows", {}),
                ("bfloat16 state", {"state_dtype": jnp.bfloat16}),
                ("float8_e4m3 weights", {"round_to": jnp.float8_e4m3fn}),
                ("gated norm over one group", {"norm_groups": 1}),
                ("B and C of the groups swapped", {"swap_bc": True}),
                ("no key_multiplier", {"no_key_mult": True})):
            t = time.monotonic()
            err, state, slow = runner.compare_rows(
                params, config, spec, prompts, driven, rows=rows, **variant)
            out.append({"what": what, "seed": seed, "rows": rows,
                        "logit_err": err, "state_err": state,
                        "state_err_slow": slow,
                        "within": runner.within(err, slow),
                        "seconds": time.monotonic() - t})
            print(json.dumps(out[-1]), flush=True)

    for k in range(seeds):
        seed = base + 7 * k
        if k:
            # two sets of weights do not fit beside the pools: free the
            # last seed's (the engine holds the same arrays) first
            for a in jax.tree_util.tree_leaves(params):
                a.delete()
            params = make(seed)
            engine.install_weights(params)
            engine.pool.swap(*[jnp.zeros(s, d) for s, d in shapes])
        prompts, driven = one(seed, params)
        if not k:
            wrong(seed, params, prompts, driven)
    out.append({"what": "done", "seconds": time.monotonic() - t0,
                "memory": jax.devices()[0].memory_stats()})
    print(json.dumps(out[-1]), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "falconh1_limits.json"),
              "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
