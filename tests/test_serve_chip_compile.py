"""The serve programs compiled for a described TPU v5e, at the benchmark
configuration's widths and pool size, with the Pallas kernels on: what
the chip's compiler makes of the pools' layout, at no chip time.

Nothing runs here, so nothing below is a time.  What is read is what the
executable holds: every pool parameter aliased to its output, temporaries
under a tenth of one pool, and no ``copy`` / ``slice`` / ``reshape`` of a
pool's or one layer's size between the pool and the kernel.

The topology is described inside a fixture, never at import: only one
process may load the TPU library (``on-chip-measurement`` guide, §2), so
these tests stay in this one file.
"""
import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from paddle_tpu.framework import device as _device
from paddle_tpu.serving import quant
from paddle_tpu.serving.kv_cache import kv_page_budget, pool_shapes
from paddle_tpu.serving.model import (ModelSpec, decode_step, init_params,
                                      prefill_step)

# gpt-345m-serve's widths, page size, fp32 page budget, largest buckets;
# a quarter of its depth and four times its pages, so the pools are as
# large as there and a program compiles in seconds
SPEC = ModelSpec(vocab_size=50304, hidden=1024, layers=6, heads=16,
                 max_seq_len=1024)
PAGE_SIZE, FP32_PAGES = 16, 4096
DECODE_BUCKET, PREFILL_BUCKET = 32, 1024
KV_DTYPES = {"fp32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, monkeypatch, precision, kind):
    """Lower and compile one serve program the way the engine builds it
    (same functions, same donation), for the described chip."""
    # the code under test asks "am I on the chip" to choose its kernels:
    # this compile is for the chip, so the test answers for it
    monkeypatch.setattr(_device, "on_tpu", lambda: True)

    def sds(a, dtype=None):
        return jax.ShapeDtypeStruct(a.shape, dtype or a.dtype,
                                    sharding=one_chip)

    if precision == "int8":
        params = jax.eval_shape(
            lambda: quant.quantize_params(init_params(SPEC), SPEC))
    else:
        params = jax.eval_shape(functools.partial(init_params, SPEC))
    cast = jnp.bfloat16 if precision == "bf16" else None
    params = jax.tree_util.tree_map(
        lambda a: sds(a, cast if cast and a.dtype == jnp.float32 else None),
        params)
    pages = kv_page_budget(FP32_PAGES, precision, SPEC.head_dim)
    shape, sshape = pool_shapes(SPEC.layers, pages, PAGE_SIZE, SPEC.heads,
                                SPEC.head_dim)
    pool = jax.ShapeDtypeStruct(shape, KV_DTYPES[precision],
                                sharding=one_chip)
    state = [pool, pool]
    if precision == "int8":
        state += [jax.ShapeDtypeStruct(sshape, jnp.float32,
                                       sharding=one_chip)] * 2
    maxp = SPEC.max_seq_len // PAGE_SIZE
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                            sharding=one_chip)

    def program(step):
        def run(params, *args):
            *st, a, b, c = args
            scales = dict(k_scale=st[2], v_scale=st[3]) if st[2:] else {}
            return step(SPEC, params, st[0], st[1], a, b, c,
                        page_size=PAGE_SIZE, **scales)
        return jax.jit(run, donate_argnums=tuple(range(1, 1 + len(state))))

    if kind == "decode":
        lowered = program(decode_step).lower(
            params, *state, i32((DECODE_BUCKET,)), i32((DECODE_BUCKET,)),
            i32((DECODE_BUCKET, maxp)))
    else:
        lowered = program(prefill_step).lower(
            params, *state, i32((PREFILL_BUCKET,)), i32(()), i32((maxp,)))
    return lowered.compile(), state


_RESULT = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = \(?(\w+)\[([\d,]*)\]\S* "
                     r"([\w\-]+)\(")


def _moved_pool_sized(text, layer_elems):
    """Operations of the optimized HLO that move (not compute on) at
    least one layer's worth of pool elements."""
    found = []
    for line in text.splitlines():
        m = _RESULT.match(line)
        if not m or m.group(3) not in ("copy", "slice", "reshape",
                                       "transpose", "dynamic-slice",
                                       "copy-start", "pad", "convert"):
            continue
        elems = int(np.prod([int(d) for d in m.group(2).split(",") if d]))
        if elems >= layer_elems:
            found.append(line.strip()[:160])
    return found


@pytest.mark.parametrize("kind", ["prefill", "decode"])
@pytest.mark.parametrize("precision", ["fp32", "bf16", "int8"])
def test_chip_program_keeps_the_pools_in_place(one_chip, monkeypatch,
                                               precision, kind):
    exe, state = _compile(one_chip, monkeypatch, precision, kind)
    text = exe.as_text()
    kernel = "paged_attention_int8" if precision == "int8" \
        else "paged_attention"
    if kind == "decode":        # the Mosaic kernel, once a layer
        assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                              text)) >= SPEC.layers
        assert kernel in text
    pool_bytes = [int(np.prod(s.shape)) * np.dtype(s.dtype).itemsize
                  for s in state]
    mem = exe.memory_analysis()
    # donation reached the executable: every pool is aliased to an output
    # (at least its bytes: the chip's tiles may pad an int8 or scale pool)
    assert mem.alias_size_in_bytes >= sum(pool_bytes)
    header = text.split("\n", 1)[0]
    assert header.count("may-alias") + header.count("must-alias") \
        == len(state)
    # what the program holds beside its arguments is far from a pool.
    # int8 is held to less, a whole value pool: its scale pools are
    # (L, P, ps, H) with H = 16 of 128 lanes, so the chip pads them
    # eightfold wherever it touches them whole: prefill's scatter re-lays
    # one (0.5 of a value pool here), the kernel takes a layer's scales
    # sliced and padded every call (0.25).  No cell runs int8; PERF.md §7
    share = 1 if precision == "int8" else 10
    assert mem.temp_size_in_bytes < pool_bytes[0] / share, \
        (mem.temp_size_in_bytes, pool_bytes[0])
    # and nothing between a value pool and the kernel moves a layer's worth
    layer_elems = int(np.prod(state[0].shape[1:]))
    moved = _moved_pool_sized(text, layer_elems)
    assert not moved, moved
