"""The Mamba-2 decode state update alone on the chip, at the Falcon-H1 serve
cell's shapes (a pool of 4 layers x 65 slots of (32, 128, 256) float32):
device ms a call of `ops.ssd_update.ssd_state_update` as the XLA twin (a
loop over the rows) and as the Pallas kernel `ssd_state_update` at a few
block sizes, from the profiler's trace; the state bytes a call moves (each
row's state read and written) over that time; how far kernel and twin
lie apart on `y` and on the rows' slots.

    python exp/bench_ssd_update.py        # on a host with a TPU v5e

Writes `chiprun_out/bench_ssd_update.json` and prints it.
"""
import argparse
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import numpy as np
import jax
import jax.numpy as jnp

import paddle_tpu.ops.ssd_update as kernel
import trace_reduce

ITERS = 20
# layers, slots, heads, head size, state, groups
SHAPE = (4, 65, 32, 128, 256, 2)
ROWS = (64, 8)
BLOCKS = (2 << 20, 1 << 20, 512 << 10)


def operands(rows, shape, seed=0):
    ls, slots, h, p, n, g = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    pool = jax.random.normal(ks[0], (ls, slots, h, p, n), jnp.float32)
    live = np.random.RandomState(seed).permutation(np.arange(1, slots))
    taken = jnp.asarray(live[:rows], jnp.int32)
    decay = jax.random.uniform(ks[1], (rows, h), jnp.float32, 0.5, 1.0)
    dx = jax.random.normal(ks[2], (rows, h, p), jnp.float32)
    bmat, cmat = (jax.random.normal(k, (rows, g, n), jnp.float32)
                  for k in ks[3:])
    return pool, taken, decay, dx, bmat, cmat


def call(use_pallas, layer):
    """One layer's update, the pool donated as in the serve programs."""
    def f(pool, slots, decay, dx, bmat, cmat):
        return kernel.ssd_state_update(pool, layer, slots, decay, dx, bmat,
                                       cmat, use_pallas=use_pallas)
    return jax.jit(f, donate_argnums=(0,))


def device_ms(fn, pool, args):
    """Device ms a call (every operation of the program) over ITERS traced
    calls, and the pool it leaves; None off the chip."""
    y, pool = fn(pool, *args)
    jax.block_until_ready(pool)
    tmp = tempfile.mkdtemp(prefix="ssd_trace_")
    try:
        jax.profiler.start_trace(tmp)
        for _ in range(ITERS):
            y, pool = fn(pool, *args)
        jax.block_until_ready(pool)
        jax.profiler.stop_trace()
        raw = trace_reduce.load_xplane(
            trace_reduce.find_xplane(tmp),
            keep_lines=lambda plane, line: plane.startswith("/device:"))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    planes = trace_reduce.device_planes(raw)
    if not planes:
        return None, pool
    events = trace_reduce.line_events(planes[0][1], trace_reduce.OPS_LINE)
    return sum(ev[2] for ev in events) / ITERS / 1e6, pool


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="the script's control flow at a tiny size")
    args = ap.parse_args()
    global ITERS
    shape = SHAPE
    if args.rehearse_on_cpu:
        ITERS = 1
        shape = (2, 70, 4, 16, 32, 2)
    else:
        assert jax.devices()[0].platform == "tpu", jax.devices()
    ls, slots, h, p, n, g = shape
    res = {"device": jax.devices()[0].device_kind, "shape": shape}
    default = kernel._BLOCK_BYTES
    for rows in ROWS:
        pool, *ops = operands(rows, shape)
        nbytes = 2 * rows * h * p * n * 4
        loop = call(False, 1)
        want_y, pool = loop(pool, *ops)
        want_y, want = np.asarray(want_y), np.asarray(pool[1][ops[0]])
        ms, _ = device_ms(loop, pool, ops)
        out = res[f"rows{rows}"] = {"loop_ms": ms,
                                    "loop_gb_per_s": ms and nbytes / ms / 1e6}
        for budget in BLOCKS:
            kernel._BLOCK_BYTES = budget
            jax.clear_caches()      # the block is chosen as the kernel traces
            fn = call(True, 1)
            pool, *ops = operands(rows, shape)
            got_y, pool = fn(pool, *ops)
            got_y, got = np.asarray(got_y), np.asarray(pool[1][ops[0]])
            ms, _ = device_ms(fn, pool, ops)
            out[f"kernel_{budget >> 10}k"] = {
                "heads": kernel.head_block(h, g, p, n), "ms": ms,
                "gb_per_s": ms and nbytes / ms / 1e6,
                "y_rel": float(np.abs(got_y - want_y).max()
                               / np.abs(want_y).max()),
                "state_rel": float(np.abs(got - want).max()
                                   / np.abs(want).max())}
            kernel._BLOCK_BYTES = default
        print(rows, out, flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "bench_ssd_update.json"),
              "w") as fh:
        json.dump(res, fh, indent=1)
    print(json.dumps(res))


if __name__ == "__main__":
    main()
