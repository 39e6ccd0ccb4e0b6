"""The flash kernels alone on the chip, at the GPT-345M train shape
(8, 16, 1024, 64) bf16: device ms a call of `flash_fwd`, `flash_bwd_dq`
and `flash_bwd_dkv` from the profiler's trace, for a sweep of
(block_q, block_k), causal and not, with the cell's dropout and without;
beside them the same numbers of another tree's `ops/pallas_ops.py`
(`--against <file>`: the parent's, unpacked where .gitignore lists it)
and how far the two trees' outputs and gradients lie apart.

    chiprun -- python exp/bench_flash.py --against .chipwork/parent_pallas_ops.py

Writes `chiprun_out/bench_flash.json` and prints it.
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

import paddle_tpu.ops.pallas_ops as ours
import trace_reduce

B, H, S, D = 8, 16, 1024, 64
ITERS = 10
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
SWEEP = ((512, 512), (256, 512), (512, 256), (256, 256), (1024, 512),
         (1024, 256), (128, 512), (384, 384))


def load_tree(path):
    """Another tree's pallas_ops.py as a sibling module of ours (its
    relative imports resolve to this tree's package)."""
    spec = importlib.util.spec_from_file_location(
        "paddle_tpu.ops.pallas_ops_other", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def inputs(seed=0):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
                 .astype(jnp.bfloat16) for _ in range(3))


def grad_fn(mod, causal, p_drop, blocks):
    def loss(q, k, v, seed):
        out = mod.mha(q, k, v, causal=causal, dropout_p=p_drop, seed=seed,
                      block_q=blocks[0], block_k=blocks[1])
        return (out.astype(jnp.float32) ** 2).sum(), out
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2), has_aux=True))


def kernel_ms(fn, args):
    """Device ms a call of each kernel over ITERS traced calls."""
    jax.block_until_ready(fn(*args))
    tmp = tempfile.mkdtemp(prefix="flash_trace_")
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
    events = trace_reduce.line_events(trace_reduce.device_planes(raw)[0][1],
                                      trace_reduce.OPS_LINE)
    ms = {"names": sorted({e[0] for e in events if "flash" in e[0]})}
    for name in KERNELS:
        pat = re.compile(r"^pallas:\w*" + name + r"(_|\.|$)")
        ms[name] = sum(e[2] for e in events if pat.match(e[0])) / ITERS / 1e6
    ms["all"] = sum(ms[name] for name in KERNELS)
    return ms


def ulps_apart(a, b):
    """max |a - b| in bf16 ulps of the largest element of b."""
    a, b = (np.asarray(x, np.float32) for x in (a, b))
    top = float(np.abs(b).max())
    ulp = 2.0 ** (np.floor(np.log2(top)) - 7)
    return float(np.abs(a - b).max() / ulp)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--against", default=None)
    ap.add_argument("--blocks", default=None,
                    help="bq:bk,... in place of the sweep")
    args = ap.parse_args()
    assert jax.devices()[0].platform == "tpu", jax.devices()
    sweep = SWEEP if not args.blocks else tuple(
        tuple(int(x) for x in pair.split(":"))
        for pair in args.blocks.split(","))
    q, k, v = inputs()
    seed = jnp.asarray(1.2345, jnp.float32)
    res = {"device": jax.devices()[0].device_kind, "shape": [B, H, S, D],
           "defaults": [ours._BLOCK_Q, ours._BLOCK_K], "ours": {},
           "other": {}, "apart_ulps": {}}
    trees = [("ours", ours, sweep + ((None, None),))]
    if args.against:
        trees.append(("other", load_tree(args.against), ((None, None),)))
    for tag, mod, pairs in trees:
        for blocks in pairs:
            for causal in (True, False):
                for p_drop in (0.1, 0.0):
                    if p_drop == 0.0 and blocks != (None, None):
                        continue
                    key = "%s/%s/causal=%d/drop=%s" % (
                        blocks[0], blocks[1], causal, p_drop)
                    try:
                        res[tag][key] = kernel_ms(
                            grad_fn(mod, causal, p_drop, blocks),
                            (q, k, v, seed))
                    except Exception as e:   # a tile Mosaic refuses
                        res[tag][key] = str(e)[:200]
                    print(tag, key, res[tag][key], flush=True)
    if args.against:
        other = trees[1][1]
        for causal in (True, False):
            for p_drop in (0.1, 0.0):
                (ga, oa) = grad_fn(ours, causal, p_drop, (None, None))(
                    q, k, v, seed)
                (gb, ob) = grad_fn(other, causal, p_drop, (None, None))(
                    q, k, v, seed)
                res["apart_ulps"]["causal=%d/drop=%s" % (causal, p_drop)] = {
                    n: ulps_apart(a, b) for n, a, b in zip(
                        ("out", "dq", "dk", "dv"), (oa,) + ga, (ob,) + gb)}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "bench_flash.json"),
              "w") as f:
        json.dump(res, f, indent=1)
    print(json.dumps(res))


if __name__ == "__main__":
    main()
