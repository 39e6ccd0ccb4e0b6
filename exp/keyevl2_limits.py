"""Readings behind the limits of `benchmarks/runners/serve_sparse.py`, on
the chip:

    chiprun --chips 1 --timeout 2400 -- python3 exp/keyevl2_limits.py [seed] [only] [config] [traffic] [rows]

builds the `keye-vl2-30b-a3b-serve` engine as the cell does, picks the
check's rows from the cell's traffic, drives them through the engine
(every prompt prefilled, four decode steps of all 24 rows), frees the
pools, and holds two of the rows (the one cut to top-k tokens, whose every
layer is compared, and one in the middle) against the plain reference and
against its wrong forms: the indexer's keys as a float8 e4m3 cache would
hold them (in every layer, and in layer 2 alone), K and V likewise, layer
2's indexer keys of the decode steps unwritten, layer 2's indexer without
its ReLU, every weight rounded to float8 e4m3, a top-k of 2,047, a selection by page, the
last 2,048 positions in place of the selection, no q/k norm.  `only` is a comma-separated list of parts of the comparisons'
names to run; `config` and `traffic` name other files than the cell's (the
tiny ones walk the script off the chip); `rows` = `short` compares the row
cut to top-k tokens alone.  Prints one JSON line a
comparison; writes them to `chiprun_out/keyevl2_limits.json`.
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
    from runners import serve_sparse as runner
    from traffic import serve_requests
    import run as bench_run
    bench_run.place_compile_cache()
    seed = int(sys.argv[1]) if len(sys.argv) > 1 else 34
    only = sys.argv[2].split(",") if len(sys.argv) > 2 else [""]
    names = (sys.argv[3:5] + ["keye-vl2-30b-a3b-serve",
                              "longctx-reasoning-backlog"][len(sys.argv[3:5]):])
    config = json.load(open(os.path.join(
        ROOT, "benchmarks/configs", names[0] + ".json")))
    mix = json.load(open(os.path.join(
        ROOT, "benchmarks/traffic", names[1] + ".json")))
    engine, params, spec, build_s = runner.build_engine(config, seed)
    out = [{"what": "build", "seconds": build_s,
            "program_bytes": engine.stats["program_bytes"],
            "memory": jax.devices()[0].memory_stats()}]
    print(json.dumps(out[-1]), flush=True)
    requests, _ = serve_requests(mix, seed, 40.0, spec.vocab_size)
    prompts = runner.pick_rows(requests, engine.config.page_size,
                               engine.config.decode_buckets[-1],
                               spec.sparse_topk)
    pad = sorted(config["check"]["pads"])[1]
    order = sorted(range(len(prompts)), key=lambda i: len(prompts[i]))
    fit = [i for i in order if len(prompts[i]) + runner.STEPS <= pad]
    keep = sorted({order[0]} if sys.argv[5:6] == ["short"]
                  else {order[0], fit[-1]})
    t = time.monotonic()
    driven, facts = runner.drive_rows(engine, prompts, keep)
    print(json.dumps({"what": "driven", "seconds": time.monotonic() - t,
                      "lens": [len(prompts[i]) for i in keep], **facts}),
          flush=True)
    engine.close()
    for a in engine.pool.state():
        a.delete()
    f8 = jnp.float8_e4m3fn
    topk = config["sa_config"]["topk"]
    variants = [("reference", {}),
                ("float8_e4m3 indexer keys", {"index_key_dtype": f8}),
                ("layer 2 alone: float8_e4m3 indexer keys",
                 {"index_key_dtype": f8, "only_layer": 2}),
                ("float8_e4m3 K and V", {"kv_dtype": f8}),
                ("layer 2 alone: float8_e4m3 K and V",
                 {"kv_dtype": f8, "only_layer": 2}),
                ("layer 2 alone: no indexer key written in decode",
                 {"index_keys_written": "prompt", "only_layer": 2}),
                ("layer 2 alone: an indexer without its ReLU",
                 {"index_relu": False, "only_layer": 2}),
                ("float8_e4m3 weights", {"round_to": f8}),
                ("top-k of 2047", {"topk": topk - 1}),
                ("selection by page", {"select": "page"}),
                ("window of 2048", {"window": topk}),
                ("no q/k norm", {"qk_norm": False})]
    for what, variant in variants:
        if not any(part in what for part in only):
            continue
        t = time.monotonic()
        found = runner.compare_rows(params, config, prompts, driven, facts,
                                    **variant)
        out.append(dict(found, what=what, seconds=time.monotonic() - t,
                        within_limits=runner.within_limits(found)))
        print(json.dumps(out[-1]), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "keyevl2_limits.json"),
              "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
