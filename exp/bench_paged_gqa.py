"""The grouped paged-attention kernels alone on the chip, at the two serve
cells' shapes (64 rows, pages of 128 tokens, bf16): device ms a call of
`paged_attention_gqa` / `paged_attention_window` from the profiler's
trace, for a sweep of the chunk length (pages of one row a grid step),
beside the rule the code commits (`gqa_chunk_pages`) and, with
`--against <file>`, another tree's `ops/paged_attention.py` (the parent's,
unpacked where .gitignore lists it) on the same operands; how far each
lies from `paged_attention_reference`; what a grid step past the list's
end costs (the same call under a loose and a tight `steps`).

    chiprun -- python exp/bench_paged_gqa.py --against .chipwork/parent_paged_attention.py

Writes `chiprun_out/bench_paged_gqa.json` and prints it.
"""
import argparse
import importlib.util
import json
import os
import re
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import numpy as np
import jax
import jax.numpy as jnp

import paddle_tpu.ops.paged_attention as ours
import trace_reduce

ROWS, PS, ITERS = 64, 128, 20
INTERPRET = False       # --rehearse-on-cpu: tiny pools, interpret mode
# heads of q as the kernel meets them, lanes of a pool row, layers and
# pages of the pool, table width, window, out dtype, the rows' contexts
CASES = {
    # Mellum2: 32 query / 4 KV heads of 128; 3/4 short, 1/4 long contexts
    "mellum_full": dict(h=32, d=128, lanes=512, layers=3, pages=2049,
                        maxp=64, window=0, out=None,
                        lengths=((48, 400, 1500), (16, 4200, 8100))),
    "mellum_window": dict(h=32, d=128, lanes=512, layers=9, pages=577,
                          maxp=64, window=1024, out=None,
                          lengths=((48, 400, 1500), (16, 4200, 8100))),
    # Phi-4-mini-flash: 40 query heads on 10 KV pairs of 2 x 64 lanes
    "phi_full": dict(h=40, d=128, lanes=1280, layers=1, pages=2049,
                     maxp=32, window=0, out=jnp.float32,
                     lengths=((64, 400, 2600),)),
    "phi_window": dict(h=40, d=128, lanes=1280, layers=8, pages=321,
                       maxp=32, window=512, out=jnp.float32,
                       lengths=((64, 400, 2600),)),
}
SWEEP = (1, 2, 3, 4, 5, 6, 8, 9, 12, 16)


def load_tree(path):
    """Another tree's paged_attention.py as a sibling module of ours."""
    spec = importlib.util.spec_from_file_location(
        "paddle_tpu.ops.paged_attention_other", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def operands(case, seed=0):
    """Pools of noise, each row's pages its own (a windowed row holds
    only the pages from the one its window starts in)."""
    rng = np.random.RandomState(seed)
    lengths = np.concatenate([rng.randint(lo, hi, size=n)
                              for n, lo, hi in case["lengths"]])
    rng.shuffle(lengths)
    tables = np.zeros((ROWS, case["maxp"]), np.int32)
    free = iter(rng.permutation(np.arange(1, case["pages"])))
    for r, n in enumerate(lengths):
        first = max(0, n - case["window"]) // PS if case["window"] else 0
        for p in range(first, (n - 1) // PS + 1):
            tables[r, p] = next(free)
    shape = (case["layers"], case["pages"], PS, case["lanes"])
    key = jax.random.PRNGKey(seed)
    k, v, q = (jax.random.normal(kk, s, jnp.float32).astype(jnp.bfloat16)
               for kk, s in zip(jax.random.split(key, 3),
                                (shape, shape, (ROWS, case["h"], case["d"]))))
    held = int((tables != 0).sum())
    return (q, k, v, jnp.asarray(tables),
            jnp.asarray(lengths, jnp.int32)), held


def device_ms(fn, args):
    """Device ms a call of the grouped kernels over ITERS traced calls."""
    jax.block_until_ready(fn(*args))
    tmp = tempfile.mkdtemp(prefix="paged_trace_")
    try:
        jax.profiler.start_trace(tmp)
        for _ in range(ITERS):
            out = fn(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        raw = trace_reduce.load_xplane(
            trace_reduce.find_xplane(tmp),
            keep_lines=lambda plane, line: plane.startswith("/device:"))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    planes = trace_reduce.device_planes(raw)
    if not planes:                  # the rehearsal: no device to trace
        return {"kernel": None, "all": None}
    events = trace_reduce.line_events(planes[0][1], trace_reduce.OPS_LINE)
    pat = re.compile(r"^pallas:\w*paged_attention_(gqa|window)")
    return {"kernel": sum(e[2] for e in events if pat.match(e[0]))
            / ITERS / 1e6,
            "all": sum(e[2] for e in events) / ITERS / 1e6}


def chunked(case, chunk, steps):
    """Our kernel at a chunk length of the sweep, the grid from ``steps``
    as `chunk_walk` makes it."""
    walk = ours.walk_pages(case["maxp"], PS, case["window"])
    chunk = min(chunk, walk)
    grid = min(ROWS * -(-walk // chunk), -(-(steps - ROWS) // chunk) + ROWS)

    def call(q, k, v, tables, lengths):
        return ours._paged_attention_gqa_pallas(
            q, k, v, tables, lengths, case["layers"] - 1,
            sm_scale=case["d"] ** -0.5, window=case["window"], chunk=chunk,
            grid=grid, interpret=INTERPRET, out_dtype=case["out"])
    return jax.jit(call), chunk, grid


def public(mod, case, steps):
    """A tree's `paged_attention` as the serve programs call it."""
    def call(q, k, v, tables, lengths):
        return mod.paged_attention(
            q, k, v, tables, lengths, layer=case["layers"] - 1,
            sm_scale=case["d"] ** -0.5, window=case["window"] or None,
            steps=steps, use_pallas=True, interpret=INTERPRET,
            out_dtype=case["out"])
    return jax.jit(call)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--against", default=None)
    ap.add_argument("--cases", default=",".join(CASES))
    ap.add_argument("--chunks", default=",".join(str(c) for c in SWEEP))
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="the script's control flow at a tiny size")
    args = ap.parse_args()
    if args.rehearse_on_cpu:
        global INTERPRET, ITERS, ROWS
        INTERPRET, ITERS, ROWS = True, 1, 8
        for case in CASES.values():
            case.update(pages=65, maxp=8, layers=1,
                        window=case["window"] and 300,
                        lengths=((ROWS, 1, 1000),))
    else:
        assert jax.devices()[0].platform == "tpu", jax.devices()
    other = load_tree(args.against) if args.against else None
    res = {"device": jax.devices()[0].device_kind}
    for name in args.cases.split(","):
        case = CASES[name]
        ops, held = operands(case)
        bound = case["pages"] - 1 + ROWS          # the allocator's
        ref = np.asarray(ours.paged_attention_reference(
            *ops, layer=case["layers"] - 1, sm_scale=case["d"] ** -0.5,
            window=case["window"] or None, out_dtype=jnp.float32))
        out = res[name] = {"pages_held": held, "sweep": {}}

        def run(tag, fn, **more):
            try:
                ms = device_ms(fn, ops)
                err = float(np.abs(np.asarray(fn(*ops), np.float32)
                                   - ref).max())
                got = dict(ms, err=err, **more)
            except Exception as e:          # a tile Mosaic refuses
                got = str(e)[:300]
            print(name, tag, got, flush=True)
            return got

        for c in sorted({int(x) for x in args.chunks.split(",")}):
            fn, chunk, grid = chunked(case, c, bound)
            if str(chunk) not in out["sweep"]:
                out["sweep"][str(chunk)] = run(f"chunk={chunk}", fn,
                                               grid=grid)
        walk = ours.chunk_walk(ops[0], ops[1], case["maxp"],
                               window=case["window"], steps=bound)
        out["committed"] = run("committed", public(ours, case, bound),
                               chunk=walk[0] // PS, grid=walk[1])
        # the same list on a grid with no step past its end to speak of
        tight = held + ROWS
        out["committed_tight"] = run(
            "tight", public(ours, case, tight),
            grid=ours.chunk_walk(ops[0], ops[1], case["maxp"],
                                 window=case["window"], steps=tight)[1])
        if other is not None:
            out["other"] = run("other", public(other, case, bound))
            out["other_tight"] = run("other tight",
                                     public(other, case, tight))
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "bench_paged_gqa.json"),
              "w") as f:
        json.dump(res, f, indent=1)
    print(json.dumps(res))


if __name__ == "__main__":
    main()
