"""The decode experts alone on the chip, at the Keye and Mellum serve
cells' shapes (bf16): device ms a call of `serving.experts.experts` in the
dense form (every expert streamed) and in the streamed form (the touched
experts' list and the Pallas kernel `moe_decode`), from the profiler's
trace, for routings that touch a given share of the experts; the bytes of
the touched experts over the streamed form's time; how far the two forms
lie apart.  `--tiles` also times the kernel at narrower width tiles.

    chiprun -- python exp/bench_moe_decode.py

Writes `chiprun_out/bench_moe_decode.json` and prints it.
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

import paddle_tpu.ops.moe_decode as kernel
from paddle_tpu.serving import experts as ex
import trace_reduce

ITERS = 20
# hidden, width, experts, top-k, rows, experts the rows choose from
CASES = {
    # the Keye cell's greedy rows touch ≈ 68 of 128 (ISSUE 37)
    "keye_b24_cell": (2048, 768, 128, 8, 24, 68),
    "keye_b24_uniform": (2048, 768, 128, 8, 24, 128),
    "keye_b8_uniform": (2048, 768, 128, 8, 8, 128),
    "mellum_b32_uniform": (2304, 896, 64, 8, 32, 64),
    "mellum_b64_uniform": (2304, 896, 64, 8, 64, 64),
}


def operands(h, f, e, k, t, pool, seed=0):
    rng = np.random.RandomState(seed)
    key = jax.random.PRNGKey(seed)
    kx, kg, ku, kd = jax.random.split(key, 4)
    y = jax.random.normal(kx, (t, h), jnp.float32).astype(jnp.bfloat16)
    wg, wu = (jax.random.normal(kk, (e, h, f), jnp.float32)
              .astype(jnp.bfloat16) * 0.02 for kk in (kg, ku))
    wd = jax.random.normal(kd, (e, f, h), jnp.float32).astype(
        jnp.bfloat16) * 0.02
    among = rng.permutation(e)[:pool]
    idx = np.stack([rng.choice(among, k, replace=False) for _ in range(t)])
    gates = rng.dirichlet(np.ones(k), size=t).astype(np.float32)
    return (y, jnp.asarray(gates), jnp.asarray(idx, jnp.int32), wg, wu,
            wd), len(np.unique(idx))


def device_ms(fn, args):
    """Device ms a call (every operation of the program) over ITERS
    traced calls; None off the chip."""
    jax.block_until_ready(fn(*args))
    tmp = tempfile.mkdtemp(prefix="moe_trace_")
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


def form(name, blocks_bytes=None):
    """`experts` in one form, traced anew (the kernel's width tile is read
    from the module as it traces)."""
    def call(*a):
        if blocks_bytes is not None:
            kernel._BLOCKS_BYTES = blocks_bytes
        return ex.experts(*a, form=name,
                          valid=jnp.ones(a[0].shape[:1], bool))
    return jax.jit(call)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cases", default=",".join(CASES))
    ap.add_argument("--tiles", action="store_true")
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="the script's control flow at a tiny size")
    args = ap.parse_args()
    global ITERS
    if args.rehearse_on_cpu:
        from paddle_tpu.framework import device as _device
        _device.pallas_dispatch = lambda: True      # interpret mode
        ITERS = 1
        for name, (h, f, e, k, t, pool) in CASES.items():
            CASES[name] = (256, 256, e // 8, 2, t, pool // 8)
    else:
        assert jax.devices()[0].platform == "tpu", jax.devices()
    res = {"device": jax.devices()[0].device_kind}
    default = kernel._BLOCKS_BYTES
    for name in args.cases.split(","):
        h, f, e, k, t, pool = CASES[name]
        ops, touched = operands(h, f, e, k, t, pool)
        nbytes = touched * 3 * h * f * 2
        dense = form("dense")
        ref = np.asarray(dense(*ops))
        out = res[name] = {"touched": touched, "experts": e,
                           "dense_ms": device_ms(dense, ops)}
        variants = {"streamed": default}
        if args.tiles:
            variants.update({f"streamed_tile{tf}": 6 * h * tf * 2
                             for tf in range(f - 128, 0, -128)
                             if f % tf == 0})
        for tag, budget in variants.items():
            fn = form("streamed", budget)
            ms = device_ms(fn, ops)
            out[tag] = {"ms": ms, "tile": kernel.width_tile(h, f, 2),
                        "err": float(np.abs(np.asarray(fn(*ops)) - ref)
                                     .max()),
                        "gb_per_s": ms and nbytes / ms / 1e6}
            kernel._BLOCKS_BYTES = default
        print(name, out, flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "bench_moe_decode.json"),
              "w") as fh:
        json.dump(res, fh, indent=1)
    print(json.dumps(res))


if __name__ == "__main__":
    main()
