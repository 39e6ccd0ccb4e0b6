"""Microbench the flash kernel on the real chip: fwd and fwd+bwd at the
GPT-345M shape, vs XLA attention, at several block configs.
Usage: python exp/bench_flash.py
"""
import json
import time

import numpy as np
import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas_ops import mha

B, H, S, D = 8, 16, 1024, 64
rng = np.random.RandomState(0)
q = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32)).astype(jnp.bfloat16)
k = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32)).astype(jnp.bfloat16)
v = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32)).astype(jnp.bfloat16)


def _chain(fn, q0, k0, v0, iters):
    """Serially-dependent chain of fn calls (outputs threaded forward,
    so every call has fresh inputs) ending in a host readback, which is
    the fence."""
    qq = q0
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(qq, k0, v0)
        first = out[0] if isinstance(out, tuple) else out
        qq = (first.astype(jnp.float32) * 1e-3).astype(q0.dtype).reshape(
            q0.shape)
    float(jnp.sum(qq.astype(jnp.float32)))  # sync
    return time.perf_counter() - t0


def timeit(fn, q0, k0, v0, iters=40):
    _chain(fn, q0, k0, v0, 2)  # warm
    t_short = _chain(fn, q0, k0, v0, 5)
    t_long = _chain(fn, q0, k0, v0, 5 + iters)
    return (t_long - t_short) / iters * 1000


def xla_attn(q, k, v):
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * (D ** -0.5)
    mask = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(mask, s, -1e9)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


results = {}
for name, fn in [
    ("xla", jax.jit(xla_attn)),
    ("flash512_512", jax.jit(lambda a, b_, c: mha(a, b_, c, causal=True,
                                                  block_q=512, block_k=512))),
    ("flash1024_256", jax.jit(lambda a, b_, c: mha(
        a, b_, c, causal=True, block_q=1024, block_k=256))),
    ("flash1024_512", jax.jit(lambda a, b_, c: mha(
        a, b_, c, causal=True, block_q=1024, block_k=512))),
    ("flash256_512", jax.jit(lambda a, b_, c: mha(
        a, b_, c, causal=True, block_q=256, block_k=512))),
]:
    try:
        results[f"{name}_fwd_ms"] = round(timeit(fn, q, k, v), 3)
    except Exception as e:
        results[f"{name}_fwd_ms"] = str(e)[:120]

for name, fn in [
    ("xla", xla_attn),
    ("flash512_512", lambda a, b_, c: mha(a, b_, c, causal=True,
                                          block_q=512, block_k=512)),
    ("flash1024_256", lambda a, b_, c: mha(a, b_, c, causal=True,
                                           block_q=1024, block_k=256)),
    ("flash1024_512", lambda a, b_, c: mha(a, b_, c, causal=True,
                                           block_q=1024, block_k=512)),
    ("flash256_512", lambda a, b_, c: mha(a, b_, c, causal=True,
                                          block_q=256, block_k=512)),
]:
    def loss(a, b_, c, fn=fn):
        return fn(a, b_, c).astype(jnp.float32).sum()
    # one compile per attention variant is the point of the benchmark
    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))  # tpu-lint: disable=TPU001
    try:
        results[f"{name}_fwdbwd_ms"] = round(timeit(g, q, k, v), 3)
    except Exception as e:
        results[f"{name}_fwdbwd_ms"] = str(e)[:120]

# correctness cross-check on-chip
o_flash = mha(q, k, v, causal=True)
o_xla = xla_attn(q, k, v)
results["max_abs_diff"] = float(jnp.max(jnp.abs(
    o_flash.astype(jnp.float32) - o_xla.astype(jnp.float32))))
print(json.dumps(results))
