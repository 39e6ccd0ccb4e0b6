"""The sparse decode attention of one layer alone on the chip, at the Keye
cell's shapes (24 rows, 2,048 listed tokens a row out of contexts of
10k-40k, addresses at random in position order, 32 query / 4 KV heads of
128 in bfloat16, 4 layers of 5,121 pages of 128 tokens), in three forms:

 (a) `--against <file>`: another tree's `ops/paged_sparse.py` (the
     parent's, unpacked where .gitignore lists it) over page-major K and V
     pools `(L, P, ps, KVH*D)`: XLA gathers the listed rows of each pool,
     the kernel takes the gathered tiles;
 (b) ours, `paged_attention_sparse` over the token-major pool `(L, P*ps,
     2*KVH, D)`: the kernel copies each listed token (its K and V, one
     whole (8, 128) tile) from HBM itself, two tiles deep;
 (c) the token-major pool, one XLA gather of the listed tokens (2 KB rows,
     K and V together), as a program of its own.  (The gather and a
     kernel over the gathered tiles, `_attend_tile` as (b) has it, in one
     program read 0.518 ms a layer on a TPU v5e, the gather alone 0.604,
     (b) 0.354 at tiles of 512 and 0.317 at 1,024: PERF.md section 6;
     that kernel is not kept here, as a `pallas_call` lives in
     `paddle_tpu/ops/`.)

For each: device ms a call (every operation of the program, from the
profiler's trace; (b) at a sweep of the tile), the pool argument's layout
in the compiled program (its `T(...)` tiling), the operations of the
program that copy, transpose or pad at least a layer of the pool, the
program's argument and temporary bytes, and the largest difference from
the float32 twin.

    python exp/bench_sparse_gather.py --against <other tree>/paddle_tpu/ops/paged_sparse.py  # one TPU chip

Writes `chiprun_out/bench_sparse_gather.json` and prints it;
`--rehearse-on-cpu` walks the script on the CPU at a tiny size (interpret mode,
no times).
"""
import argparse
import functools
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
import paddle_tpu.ops.paged_sparse as ours
import trace_reduce

ITERS = 20
# rows, query heads, KV heads, head size, layers, pages, page size, listed
# tokens a row, the rows' contexts
CELL = dict(b=24, h=32, kvh=4, d=128, layers=4, pages=5121, ps=128,
            listed=2048, context=(10240, 40960))
TINY = dict(b=3, h=8, kvh=2, d=128, layers=2, pages=64, ps=16, listed=64,
            context=(80, 400))
TILES = (256, 512, 1024, 2048)


def load_tree(path):
    """Another tree's paged_sparse.py as a sibling module of ours."""
    spec = importlib.util.spec_from_file_location(
        "paddle_tpu.ops.paged_sparse_other", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def listed(c, seed=0):
    """Each row's pages its own, drawn at random from the pool; its
    `listed` positions at random under its context, in position order;
    their token addresses `page * ps + slot`."""
    rng = np.random.RandomState(seed)
    free = iter(rng.permutation(np.arange(1, c["pages"])))
    out = np.zeros((c["b"], c["listed"]), np.int32)
    for r in range(c["b"]):
        n = int(rng.randint(*c["context"]))
        table = np.asarray([next(free) for _ in range(-(-n // c["ps"]))])
        pos = np.sort(rng.choice(n, c["listed"], replace=False))
        out[r] = table[pos // c["ps"]] * c["ps"] + pos % c["ps"]
    return jnp.asarray(out)


def device_ms(fn, args):
    """Device ms a call (every operation of the program) over ITERS
    traced calls; None off the chip."""
    jax.block_until_ready(fn(*args))
    if jax.devices()[0].platform != "tpu":
        return None
    tmp = tempfile.mkdtemp(prefix="sparse_trace_")
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
    if not planes:
        return None
    events = trace_reduce.line_events(planes[0][1], trace_reduce.OPS_LINE)
    return sum(ev[2] for ev in events) / ITERS / 1e6


_RESULT = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = \(?\w+\[([\d,]*)\]\S* "
                     r"([\w\-]+)\(")


def compiled_facts(fn, args, pool_elems, layer_elems):
    """The pools' layouts as the compiled program takes them, the
    operations that move at least a layer of a pool, and the program's
    argument and temporary bytes."""
    exe = jax.jit(fn).lower(*args).compile()
    text = exe.as_text()
    layouts = sorted(set(re.findall(
        r"\w+\[([\d,]+)\]\{[\d,]+:(T\([^}]*)\}\S* parameter", text)))
    moved = []
    for line in text.splitlines():
        m = _RESULT.match(line)
        if m and m.group(2) in ("copy", "transpose", "pad", "copy-start",
                                "dynamic-slice", "slice"):
            elems = int(np.prod([int(x) for x in m.group(1).split(",")
                                 if x] or [1]))
            if elems >= layer_elems:
                moved.append(line.strip()[:200])
    mem = exe.memory_analysis()
    return {"pool_layouts": [f"{s}: {t}" for s, t in layouts
                             if int(np.prod([int(x) for x in s.split(",")]))
                             >= pool_elems],
            "pool_moves": moved,
            "argument_bytes": mem.argument_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes}


def form_c(kv_pool, addresses, *, layer):
    """(c)'s gather: the listed tokens, K and V together, `(B, K, 2*KVH,
    D)`."""
    return ours._listed_tokens(kv_pool, layer, addresses)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--against", default=None)
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="the script's control flow at a tiny size")
    args = ap.parse_args()
    c = dict(CELL)
    interpret = False
    if args.rehearse_on_cpu:
        c, interpret = dict(TINY), True
    else:
        assert jax.devices()[0].platform == "tpu", jax.devices()
    bf = jnp.bfloat16
    layer = c["layers"] - 2
    tokens = c["pages"] * c["ps"]
    addresses = listed(c)
    counts = jnp.full((c["b"],), c["listed"], jnp.int32)
    kk, kv_, kq = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (c["b"], c["h"], c["d"]), bf)
    shape = (c["layers"], c["pages"], c["ps"], c["kvh"] * c["d"])
    k_pool = jax.random.normal(kk, shape, bf)
    v_pool = jax.random.normal(kv_, shape, bf)
    half = (c["layers"], tokens, c["kvh"], c["d"])
    kv_pool = jnp.concatenate([k_pool.reshape(half), v_pool.reshape(half)],
                              axis=2)
    layer_elems = tokens * 2 * c["kvh"] * c["d"] // 2     # one layer, K or V
    res = {"device": jax.devices()[0].device_kind, "shapes": c,
           "layer": layer, "kv_bytes_a_layer":
           int(c["b"] * c["listed"] * 2 * c["kvh"] * c["d"] * 2)}
    # the float32 twin over the listed tokens alone (a float32 copy of
    # the whole pool would not fit beside it)
    mine = ours._listed_tokens(kv_pool, layer, addresses).astype(jnp.float32)
    want = np.asarray(ours.paged_attention_sparse_reference(
        q.astype(jnp.float32), mine.reshape(1, -1, *mine.shape[2:]),
        jnp.arange(mine.shape[0] * mine.shape[1]).reshape(mine.shape[:2]),
        counts, layer=0))
    del mine

    def measure(tag, fn, operands, pools, attends=True):
        out = np.asarray(fn(*operands), np.float32)
        got = {"ms": device_ms(fn, operands),
               **compiled_facts(fn, operands,
                                int(np.prod(pools[0].shape)), layer_elems)}
        if attends:
            got["err"] = float(np.abs(out - want).max())
        res[tag] = got
        print(tag, json.dumps(got), flush=True)

    if args.against:
        other = load_tree(args.against)
        measure("a_two_row_gathers", jax.jit(functools.partial(
            other.paged_attention_sparse, layer=layer, use_pallas=True,
            interpret=interpret)), (q, k_pool, v_pool, addresses, counts),
            (k_pool,))
    del k_pool, v_pool
    default = ours._SPARSE_TILE
    form_b = jax.jit(functools.partial(
        ours.paged_attention_sparse, layer=layer, use_pallas=True,
        interpret=interpret))
    for tile in TILES if not interpret else (16, 32):
        ours._SPARSE_TILE = tile        # read as the kernel traces
        ours._paged_attention_sparse_pallas.clear_cache()
        form_b.clear_cache()
        measure(f"b_kernel_copies_tile{tile}", form_b,
                (q, kv_pool, addresses, counts), (kv_pool,))
    ours._SPARSE_TILE = default
    ours._paged_attention_sparse_pallas.clear_cache()
    measure("c_one_token_gather_alone", jax.jit(functools.partial(
        form_c, layer=layer)), (kv_pool, addresses), (kv_pool,),
        attends=False)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "bench_sparse_gather.json"),
              "w") as fh:
        json.dump(res, fh, indent=1)
    print(json.dumps(res))


if __name__ == "__main__":
    main()
