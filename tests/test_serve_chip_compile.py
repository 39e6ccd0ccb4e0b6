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


def _compile(one_chip, monkeypatch, precision, kind, *,
             fp32_pages=FP32_PAGES, bucket=DECODE_BUCKET):
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
    pages = kv_page_budget(fp32_pages, precision, SPEC.head_dim)
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
            params, *state, i32((bucket,)), i32((bucket,)),
            i32((bucket, maxp)))
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


@pytest.mark.parametrize("bucket", [8, 32])
@pytest.mark.parametrize("precision", ["fp32", "bf16"])
def test_chip_decode_program_walks_chunks_of_the_pages_held(
        one_chip, monkeypatch, precision, bucket):
    """gpt-345m-serve's decode programs (16 heads of 64, pages of 16,
    1,024 fp32 pages, tables of 64): the equal-heads kernel's grid is
    the chunk list's bound, not bucket x 64 page slots; the list is made
    once for all layers; the temporaries stay where they were."""
    from paddle_tpu.ops.paged_attention import chunk_walk
    exe, state = _compile(one_chip, monkeypatch, precision, "decode",
                          fp32_pages=1024, bucket=bucket)
    pages = state[0].shape[1]
    q = jax.ShapeDtypeStruct((bucket, SPEC.heads, SPEC.head_dim),
                             state[0].dtype)
    tokens, grid = chunk_walk(q, state[0], SPEC.max_seq_len // PAGE_SIZE,
                              steps=pages - 1 + bucket)
    assert tokens == 128
    if precision == "fp32":         # 2,048 and 512 before the work list
        assert grid == {8: 64, 32: 160}[bucket]
    calls = [line for line in exe.as_text().splitlines()
             if "tpu_custom_call" in line and "paged_attention" in line]
    assert len(calls) == SPEC.layers
    walks = set()
    for line in calls:
        operands = line.split("custom-call(", 1)[1].split(")", 1)[0]
        operands = re.sub(r"/\*index=\d+\*/", "", operands).split(", ")
        walks.add(tuple(operands[:5]))
        shapes = line.split("operand_layout_constraints={", 1)[1]
        # rows, eight pages a grid step, chunk slots; lengths, last chunks
        assert shapes.startswith(
            f"s32[{grid}]{{0}}, s32[{8 * grid}]{{0}}, s32[{grid}]{{0}}, "
            f"s32[{bucket}]{{0}}, s32[{bucket}]{{0}}, "), shapes[:120]
        # and the two pools whole, for the kernel's own copies
        pool = "%s[%s]" % ({"fp32": "f32", "bf16": "bf16"}[precision],
                           ",".join(str(d) for d in state[0].shape))
        assert shapes.count(pool) == 2
    # one work list a decode step: every layer's call takes the same five
    assert len(walks) == 1, walks
    # 9.3 MiB (fp32) / 7.0 MiB (bf16) with the (batch, pages) kernel at
    # this depth; the list itself is a few KiB
    assert exe.memory_analysis().temp_size_in_bytes < 12 << 20


# -- Mellum2-12B-A2.5B-Instruct's programs at its published widths ----------
# (`benchmarks/configs/mellum2-12b-a2p5b-serve.json`): one period of its
# layer pattern, so a program compiles in seconds; the pools, page size and
# largest buckets are the configuration's.

LM_SPEC = ModelSpec(
    vocab_size=98304, hidden=2304, layers=4, heads=32, kv_heads=4,
    head_size=128, max_seq_len=8192, norm="rms", norm_eps=1e-6,
    positions="rotary", rope_theta=500000.0, yarn_factor=16.0,
    yarn_original_len=8192, yarn_attention_factor=1.2772588722239782,
    layer_types=("sliding", "sliding", "sliding", "full"), window=1024,
    ffn="moe", experts=64, experts_per_token=8, expert_width=896,
    tie_head=False)
LM_PAGE, LM_PAGES, LM_WINDOW_PAGES = 128, 2049, 577
# a tenth of the weights the configuration serves (12 layers, bf16): the
# temporaries of a program do not grow with depth, its weights do
LM_WEIGHT_BYTES = 2 * (12 * (2 * 2304 * 4096 + 2 * 2304 * 512 + 2304 * 64
                             + 64 * 3 * 2304 * 896 + 2 * 2304)
                       + 2 * 98304 * 2304 + 2304)


def _compile_lm(one_chip, monkeypatch, kind, size):
    monkeypatch.setattr(_device, "on_tpu", lambda: True)
    bf = jnp.bfloat16
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(functools.partial(init_params, LM_SPEC, dtype=bf)))
    full, _ = pool_shapes(1, LM_PAGES, LM_PAGE, 4, 128)
    sliding, _ = pool_shapes(3, LM_WINDOW_PAGES, LM_PAGE, 4, 128)
    state = [jax.ShapeDtypeStruct(s, bf, sharding=one_chip)
             for s in (full, full, sliding, sliding)]
    maxp = LM_SPEC.max_seq_len // LM_PAGE
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                            sharding=one_chip)
    step = decode_step if kind == "decode" else prefill_step

    def run(params, k, v, kw, vw, a, b, c):
        return step(LM_SPEC, params, k, v, a, b, c, page_size=LM_PAGE,
                    kw_pool=kw, vw_pool=vw)

    args = ((i32((size,)), i32((size,)), i32((size, 2, maxp)))
            if kind == "decode" else
            (i32((size,)), i32(()), i32((2, maxp))))
    exe = jax.jit(run, donate_argnums=(1, 2, 3, 4)).lower(
        params, *state, *args).compile()
    return exe, state


def _shapes_of(text):
    """(dtype, dims) of every instruction result in the optimized HLO."""
    for line in text.splitlines():
        m = _RESULT.match(line)
        if m:
            yield (m.group(1), tuple(int(d) for d in m.group(2).split(",")
                                     if d), m.group(3), line)


@pytest.mark.parametrize("kind,size", [("decode", 64), ("prefill", 8192)])
def test_chip_compiles_the_grouped_windowed_sparse_programs(
        one_chip, monkeypatch, kind, size):
    exe, state = _compile_lm(one_chip, monkeypatch, kind, size)
    text = exe.as_text()
    mem = exe.memory_analysis()
    pool_bytes = sum(int(np.prod(s.shape)) * 2 for s in state)
    # all four pools are donated and aliased
    assert mem.alias_size_in_bytes >= pool_bytes
    header = text.split("\n", 1)[0]
    assert header.count("may-alias") + header.count("must-alias") == 4
    # temporaries under a tenth of the weights served
    assert mem.temp_size_in_bytes < LM_WEIGHT_BYTES / 10, \
        (mem.temp_size_in_bytes, LM_WEIGHT_BYTES)
    expert = 64 * 2304 * 896
    for dtype, dims, op, line in _shapes_of(text):
        elems = int(np.prod(dims)) if dims else 1
        # nothing shaped like expert matrices is gathered, copied or
        # re-laid, and no (tokens, 8, hidden, width) tensor exists: the
        # one weight-shaped result is a layer's own 64 experts
        if dims[-2:] in ((2304, 896), (896, 2304)):
            assert op not in ("gather", "copy", "transpose",
                              "dynamic-slice", "scatter"), line[:200]
            assert elems <= expert, line[:200]
        # no (heads, S, S) score tensor at the long bucket
        assert not (len(dims) >= 2 and dims[-1] >= 8192
                    and dims[-2] >= 8192), line[:200]
    if kind == "decode":
        # the Mosaic kernel once a layer, both members of the family
        assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                              text)) >= LM_SPEC.layers
        assert "paged_attention_window" in text
        assert "paged_attention_gqa" in text
        assert mem.temp_size_in_bytes < pool_bytes / 10
    else:
        # three grouped matmuls a layer, the Pallas kernel each
        assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                              text)) >= 3 * LM_SPEC.layers
        assert "moe_gmm" in text and "ragged-dot" not in text


@pytest.mark.parametrize("window,pages", [(0, LM_PAGES),
                                          (1024, LM_WINDOW_PAGES)])
def test_chip_compiles_the_grouped_kernel_alone(one_chip, window, pages):
    from paddle_tpu.ops.paged_attention import (
        _paged_attention_gqa_pallas, chunk_walk)
    bf = jnp.bfloat16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q, pool = sds((64, 32, 128), bf), sds((1, pages, LM_PAGE, 512), bf)
    tokens, grid = chunk_walk(q, pool, 64, window=window,
                              steps=pages - 1 + 64)

    def kernel(q, k, v, tables, lengths, layer):
        return _paged_attention_gqa_pallas(
            q, k, v, tables, lengths, layer, sm_scale=128 ** -0.5,
            window=window, chunk=tokens // LM_PAGE, grid=grid,
            interpret=False)

    exe = jax.jit(kernel).lower(
        q, pool, pool, sds((64, 64), jnp.int32), sds((64,), jnp.int32),
        sds((), jnp.int32)).compile()
    assert "tpu_custom_call" in exe.as_text()
    # the walk list and the padded rows: far from a pool
    assert exe.memory_analysis().temp_size_in_bytes < 16 << 20


# -- Phi-4-mini-flash-reasoning's programs at its published widths ------------
# (`benchmarks/configs/phi4-mini-flash-serve.json`): eight layers with every
# kind of mixer (state-space and sliding x2, state-space, full, gated memory
# unit, cross), so a program compiles in seconds; the pools, slots, page size
# and largest buckets are the configuration's.

PHI_SPEC = ModelSpec(
    vocab_size=200064, hidden=2560, layers=8, heads=40, kv_heads=20,
    max_seq_len=4096, positions="none", ffn="swiglu", window=512,
    layer_types=("ssm", "sliding", "ssm", "sliding", "ssm", "full", "gmu",
                 "cross"),
    attn_bias=True, diff_attn=True, ssm_inner=5120, ssm_state=16,
    ssm_conv=4, ssm_dt_rank=160)
PHI_PAGE, PHI_PAGES, PHI_WINDOW_PAGES, PHI_SLOTS = 128, 2049, 321, 65


def _compile_phi(one_chip, monkeypatch, kind, size):
    monkeypatch.setattr(_device, "on_tpu", lambda: True)
    bf = jnp.bfloat16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(functools.partial(init_params, PHI_SPEC, dtype=bf)))
    full, _ = pool_shapes(1, PHI_PAGES, PHI_PAGE, 20, 64)
    sliding, _ = pool_shapes(2, PHI_WINDOW_PAGES, PHI_PAGE, 20, 64)
    state = [sds(full, bf), sds(full, bf), sds(sliding, bf),
             sds(sliding, bf), sds((3, PHI_SLOTS, 3, 5120), bf),
             sds((3, PHI_SLOTS, 16, 5120), jnp.float32)]
    maxp = PHI_SPEC.max_seq_len // PHI_PAGE
    step = decode_step if kind == "decode" else prefill_step

    def run(params, k, v, kw, vw, conv, ssm, a, b, c):
        return step(PHI_SPEC, params, k, v, a, b, c, page_size=PHI_PAGE,
                    kw_pool=kw, vw_pool=vw, conv_pool=conv, ssm_pool=ssm)

    i32 = functools.partial(sds, dtype=jnp.int32)
    args = ((i32((size,)), i32((size,)), i32((size, 3, maxp)))
            if kind == "decode" else
            (i32((size,)), i32(()), i32((3, maxp))))
    exe = jax.jit(run, donate_argnums=tuple(range(1, 7))).lower(
        params, *state, *args).compile()
    return exe, state


@pytest.mark.parametrize("kind,size", [("decode", 64), ("prefill", 2048)])
def test_chip_compiles_the_hybrid_programs(one_chip, monkeypatch, kind,
                                           size):
    """Pages of two pools and the state slots donated and aliased, no
    pool copied or re-laid, the differential read through the grouped
    kernel on KV pairs, the scan a Mosaic kernel."""
    exe, state = _compile_phi(one_chip, monkeypatch, kind, size)
    text = exe.as_text()
    mem = exe.memory_analysis()
    pool_bytes = sum(int(np.prod(s.shape)) * s.dtype.itemsize
                     for s in state)
    assert mem.alias_size_in_bytes >= pool_bytes
    header = text.split("\n", 1)[0]
    assert header.count("may-alias") + header.count("must-alias") == 6
    # temporaries under one K pool (the 2048 bucket's activations are a
    # third of it), a decode step's under a tenth
    k_pool = int(np.prod(state[0].shape)) * 2
    assert mem.temp_size_in_bytes < k_pool / (10 if kind == "decode" else 1)
    layer = PHI_PAGE * 1280                 # one page of one layer
    for dtype, dims, op, line in _shapes_of(text):
        # nothing of a pool's size is copied, sliced or re-laid
        if op in ("copy", "transpose", "dynamic-slice", "slice", "pad") \
                and dims and dims[-1] == 1280 and len(dims) >= 3:
            assert int(np.prod(dims)) < 256 * layer, line[:200]
        # the state never lies (5120, 16): sixteen lanes of 128
        assert dims[-2:] != (5120, 16) or op == "parameter", line[:200]
    if kind == "decode":
        assert "paged_attention_window" in text
        assert "paged_attention_gqa" in text
        assert "ssm_scan" not in text
        # two sliding layers, the full layer and the cross layer
        assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                              text)) == 4
    else:
        assert len(re.findall(r"custom_call_target=\"tpu_custom_call\"",
                              text)) == 3      # the scan, a layer
        assert "ssm_scan" in text
        # no (heads, S, S) scores at the long bucket (no width is 2048)
        for dtype, dims, op, line in _shapes_of(text):
            assert dims[-2:] != (2048, 2048), line[:200]


def _paged_calls(text):
    """(kernel name, operand names, operand shapes) of every grouped
    paged-attention call in the optimized HLO."""
    found = []
    for line in text.splitlines():
        if "tpu_custom_call" not in line or "paged_attention" not in line:
            continue
        name = re.search(r"%(paged_attention_\w+?)[.\d]* = ", line).group(1)
        operands = line.split("custom-call(", 1)[1].split(")", 1)[0]
        operands = re.sub(r"/\*index=\d+\*/", "", operands).split(", ")
        shapes = line.split("operand_layout_constraints={", 1)[1]
        found.append((name, [o.split(" ")[-1] for o in operands], shapes))
    return found


# name -> (compile, spec, calls a kernel, the parent's temporaries: 12.35
# and 16.63 MB with a page a grid step)
GROUPED_PROGRAMS = {
    "mellum": (_compile_lm, LM_SPEC,
               {"paged_attention_gqa": 1, "paged_attention_window": 3},
               12 << 20),
    "phi": (_compile_phi, PHI_SPEC,
            {"paged_attention_gqa": 2, "paged_attention_window": 2},
            16 << 20)}


@pytest.mark.parametrize("model", GROUPED_PROGRAMS)
def test_chip_decode_programs_walk_chunks_of_both_pools(one_chip,
                                                        monkeypatch, model):
    """The 64-row decode programs of the two served models: each grouped
    kernel under its name on a grid of chunks (`chunk_walk`'s, not the
    pages' 2,112 / 2,048 and 576 / 320), one work list a pool whichever
    of its layers reads it, the layer a scalar operand of one traced
    kernel, the pools whole for the kernel's own copies, the two-deep
    tiles well inside the scoped VMEM, temporaries under the parent's."""
    from paddle_tpu.ops.paged_attention import chunk_walk, walk_pages
    build, spec, want, parent_temp = GROUPED_PROGRAMS[model]
    exe, state = build(one_chip, monkeypatch, "decode", 64)
    page, heads, d = state[0].shape[2], spec.heads, spec.head_dim
    if spec.diff_attn:
        d *= 2
    maxp = spec.max_seq_len // page
    calls = _paged_calls(exe.as_text())
    assert {n: sum(1 for c in calls if c[0] == n) for n in want} == want
    lists = {}
    for name, operands, shapes in calls:
        window = spec.window if name.endswith("window") else 0
        pool = state[2 if window else 0]
        tokens, grid = chunk_walk(
            jax.ShapeDtypeStruct((64, heads, d), pool.dtype), pool, maxp,
            window=window, steps=pool.shape[1] - 1 + 64)
        chunk = tokens // page
        # a step's K and V between 2 and 3.5 MiB, twice that in VMEM
        tile = 2 * tokens * pool.shape[3] * 2
        assert 2 << 20 <= tile <= 7 << 19
        assert grid <= 64 * -(-walk_pages(maxp, page, window) // chunk)
        assert grid < (pool.shape[1] - 1 + 64) / chunk + 64
        # rows, chunk pages a step, slots; lengths, first and last chunk,
        # the window's shift; the layer; q; the pools whole
        whole = "bf16[%s]{3,2,1,0}" % ",".join(str(x) for x in pool.shape)
        assert shapes.startswith(
            f"s32[{grid}]{{0}}, s32[{chunk * grid}]{{0}}, s32[{grid}]{{0}}, "
            + "s32[64]{0}, " * 4 + "s32[1]{0}, "
            + f"bf16[64,{pool.shape[3] // 128},16,128]{{3,2,1,0}}, "
            + f"{whole}, {whole}"), shapes[:300]
        lists.setdefault(name, set()).add(tuple(operands[:7]))
    # one list a pool: every layer's call takes the same seven
    assert all(len(v) == 1 for v in lists.values()), lists
    assert exe.memory_analysis().temp_size_in_bytes < parent_temp


@pytest.mark.parametrize("positions", [256, 2048])
def test_chip_compiles_the_scan_kernel_alone(one_chip, positions):
    from paddle_tpu.ops.selective_scan import _selective_scan_pallas
    f32 = jnp.float32

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, f32, sharding=one_chip)

    exe = jax.jit(functools.partial(_selective_scan_pallas,
                                    interpret=False)).lower(
        sds((positions, 5120)), sds((positions, 5120)),
        sds((positions, 16)), sds((positions, 16)),
        sds((16, 5120))).compile()
    assert "tpu_custom_call" in exe.as_text()
    # B and C spread over a tile's lanes, and nothing (S, 16, 5120)
    assert exe.memory_analysis().temp_size_in_bytes < \
        3 * positions * 16 * 128 * 4


# -- the train cell's flash kernels (same file: one process loads the library) --

FLASH_CALLS = [
    dict(),                                     # the train cell's call
    dict(causal=False),
    dict(dropout_p=0.0),
    dict(s=8192, b=1),                          # K and V still whole in VMEM
    dict(s=1000, dropout_p=0.0),                # padded to the tile, masked
    dict(dtype=jnp.float32, dropout_p=0.0),
    dict(lens=True),
    dict(shift=True, dropout_p=0.0),
]


@pytest.mark.parametrize("call", FLASH_CALLS, ids=lambda c: ",".join(
    f"{k}={getattr(v, '__name__', v)}" for k, v in c.items()) or "train")
def test_chip_compiles_the_flash_kernels(one_chip, call):
    """Forward and the three gradients of `mha` at the GPT-345M train
    shape (8, 16, 1024, 64) bf16 causal with dropout, and the calls other
    code makes of it (ring attention's traced shift with an lse
    cotangent, varlen, a padded and a long sequence): three Mosaic
    kernels, named as the benchmark's readers find them, on operands of
    (batch x heads, sequence, 128 lanes)."""
    from paddle_tpu.ops.pallas_ops import mha
    c = dict(dict(causal=True, dropout_p=0.1, s=1024, b=8,
                  dtype=jnp.bfloat16, lens=False, shift=False), **call)
    b, s, dt = c["b"], c["s"], c["dtype"]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def grads(q, k, v, seed, lens, shift):
        def loss(q, k, v):
            r = mha(q, k, v, causal=c["causal"], dropout_p=c["dropout_p"],
                    seed=seed, interpret=False,
                    seq_lens=lens if c["lens"] else None,
                    causal_shift=shift if c["shift"] else None,
                    return_lse=c["shift"])
            if c["shift"]:
                return r[0].astype(jnp.float32).sum() + r[1].sum()
            return r.astype(jnp.float32).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    x = sds((b, 16, s, 64), dt)
    # the suite's float32 default precision is no bf16 kernel's (Mosaic:
    # "Bad lhs type"); the cells run jax's own default
    with jax.default_matmul_precision("default"):
        text = jax.jit(grads).lower(
            x, x, x, sds((), jnp.float32), sds((b,), jnp.int32),
            sds((), jnp.int32)).compile().as_text()
    calls = re.findall(r"%(\w*flash_\w+?)_*\.\d+ = [^\n]*custom-call", text)
    assert sorted(n.split("flash_")[1] for n in calls) == [
        "bwd_dkv", "bwd_dq", "fwd"], calls
    s_p = -(-s // 512) * 512
    name = {jnp.bfloat16: "bf16", jnp.float32: "f32"}[dt]
    assert f"{name}[{b * 16},{s_p},128]" in text


# -- the decode experts' kernel (same file: one process loads the library) --

@pytest.mark.parametrize("hidden,width,experts,rows", [
    (2048, 768, 128, 8), (2048, 768, 128, 24),      # Keye's buckets
    (2304, 896, 64, 8), (2304, 896, 64, 32)])       # Mellum's streamed ones
def test_chip_compiles_the_decode_experts_kernel(one_chip, monkeypatch,
                                                 hidden, width, experts,
                                                 rows):
    """The streamed form at the served widths in bf16: one Mosaic kernel
    named `moe_decode`, a whole expert a slot, nothing of an expert's
    size in HBM beside the weights."""
    from paddle_tpu.ops import moe_decode
    from paddle_tpu.serving import experts as ex
    monkeypatch.setattr(_device, "on_tpu", lambda: True)
    monkeypatch.setattr(_device, "pallas_dispatch", lambda: True)
    bf16 = jnp.bfloat16

    def sds(shape, dtype=bf16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def step(y, router, wg, wu, wd, valid):
        return ex.moe_ffn(y, router, wg, wu, wd, top_k=8, form="streamed",
                          valid=valid)

    with jax.default_matmul_precision("default"):
        exe = jax.jit(step).lower(
            sds((rows, hidden)), sds((hidden, experts)),
            sds((experts, hidden, width)), sds((experts, hidden, width)),
            sds((experts, width, hidden)), sds((rows,), jnp.bool_)
        ).compile()
    calls = re.findall(r"%(\w*moe_decode\w*?)_*\.\d+ = [^\n]*custom-call",
                       exe.as_text())
    assert len(calls) == 1, calls
    assert moe_decode.width_tile(hidden, width, 2) == width
    assert exe.memory_analysis().temp_size_in_bytes < hidden * width * 2


# -- Falcon-H1-34B's programs at its published widths ---------------------------
# (`benchmarks/configs/falcon-h1-34b-serve.json`): two of its parallel layers
# (a Mamba-2 mixer beside grouped-query attention), so a program compiles in
# seconds; the pool's pages, the slots, page size and largest buckets are the
# configuration's.

FALCON_SPEC = ModelSpec(
    vocab_size=261120, hidden=5120, layers=2, heads=20, kv_heads=4,
    head_size=128, max_seq_len=6144, ffn="swiglu", ffn_width=21504,
    norm="rms", positions="rotary", rope_theta=1e11,
    layer_types=("parallel", "parallel"), tie_head=False, ssm_inner=4096,
    ssm_heads=32, ssm_groups=2, ssm_state=256, ssm_conv=4, ssm_chunk=128,
    embed_mult=5.656854249492381, attn_out_mult=0.0375,
    key_mult=0.011048543456039804, ssm_in_mult=0.25,
    ssm_out_mult=0.08838834764831845,
    ssm_mults=(0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
               0.3535533905932738),
    mlp_mults=(0.1767766952966369, 0.011160714285714284),
    head_mult=0.0078125)
FALCON_PAGE, FALCON_PAGES, FALCON_SLOTS = 128, 3073, 65


@pytest.mark.parametrize("kind,size", [("decode", 64), ("prefill", 4096)])
def test_chip_compiles_the_parallel_mamba2_programs(one_chip, monkeypatch,
                                                    kind, size):
    """Pages and the Mamba-2 slots donated and aliased; a decode step
    updates its rows' states where they lie, the kernel `ssd_state_update`
    a layer (a gather of them would have the compiler re-lay the whole
    slot pool, 1.09 GB a layer at four layers: `mini-gather`; a copy of
    the pool beside the kernel would show in the temporaries); the grouped
    kernel reads 5 query heads a KV head; the prefill's chunked scan is
    XLA's, in under a GB of temporaries at the longest bucket."""
    monkeypatch.setattr(_device, "on_tpu", lambda: True)
    bf = jnp.bfloat16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(functools.partial(init_params, FALCON_SPEC, dtype=bf)))
    full, _ = pool_shapes(2, FALCON_PAGES, FALCON_PAGE, 4, 128)
    state = [sds(full, bf), sds(full, bf),
             sds((2, FALCON_SLOTS, 3, 5120), bf),
             sds((2, FALCON_SLOTS, 32, 128, 256), jnp.float32)]
    maxp = FALCON_SPEC.max_seq_len // FALCON_PAGE
    step = decode_step if kind == "decode" else prefill_step

    def run(params, k, v, conv, ssm, a, b, c):
        return step(FALCON_SPEC, params, k, v, a, b, c,
                    page_size=FALCON_PAGE, conv_pool=conv, ssm_pool=ssm)

    i32 = functools.partial(sds, dtype=jnp.int32)
    args = ((i32((size,)), i32((size,)), i32((size, 3, maxp)))
            if kind == "decode" else
            (i32((size,)), i32(()), i32((3, maxp))))
    exe = jax.jit(run, donate_argnums=(1, 2, 3, 4)).lower(
        params, *state, *args).compile()
    return exe, state


@pytest.mark.parametrize("kind,size", [("decode", 64), ("prefill", 4096),
                                       ("selection", 64)])
def test_chip_compiles_the_kda_and_latent_programs(one_chip, monkeypatch,
                                                  kind, size):
    """The latent pool (512 lanes of latent, the key's 64 on a 128-lane
    tile) and the KDA slots donated and aliased; a decode step updates its
    rows' states where they lie (`kda_state_update`, no `mini-gather`),
    reads the latent pages in place (`paged_attention_mla`) and streams the
    held experts its rows chose (`moe_decode`), in tens of MB of
    temporaries, and so does its twin that also hands out the routers'
    choices and the latent heads; the prefill's chunked delta rule and
    latent attention are XLA's, its experts the grouped kernel."""
    exe, state = _compile_kimi(one_chip, monkeypatch, kind, size)
    text = exe.as_text()
    mem = exe.memory_analysis()
    held = sum(int(np.prod(s.shape)) * s.dtype.itemsize for s in state)
    assert mem.alias_size_in_bytes >= held
    header = text.split("\n", 1)[0]
    assert header.count("may-alias") + header.count("must-alias") == 4
    assert "mini-gather" not in text
    slots = int(np.prod(state[3].shape)) * 4
    if kind == "decode":
        # a step's temporaries: no slot pool, no layer of it
        assert mem.temp_size_in_bytes < slots / 20
        calls = re.findall(r"%(\w+?)_*\.\d+ = [^\n]*custom_call_target="
                           r"\"tpu_custom_call\"", text)
        assert sorted(calls) == ["paged_attention_gqa"] * 2 + [
            "ssd_state_update"] * 2, calls
    else:
        assert mem.temp_size_in_bytes < 1 << 30
        assert "tpu_custom_call" not in text


# -- Kimi-Linear-48B-A3B's programs at its published widths --------------------
# (`benchmarks/configs/kimi-linear-48b-a3b-serve.json`): a KDA layer (with the
# leading dense SwiGLU) and a latent attention layer (with 128 of 256 experts
# and the shared expert), so a program compiles in seconds; the latent pool's
# pages, the slots, page size and the largest decode bucket are the
# configuration's.

KIMI_SPEC = ModelSpec(
    vocab_size=163840, hidden=2304, layers=2, heads=32, max_seq_len=24576,
    ffn="moe", ffn_width=9216, norm="rms", positions="none",
    layer_types=("kda", "mla"), tie_head=False, experts=128,
    router_experts=256, experts_per_token=8, expert_width=1024,
    kda_heads=32, kda_head_dim=128, kda_conv=4, kda_chunk=64,
    kv_lora_rank=512, qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128,
    dense_layers=1, router="sigmoid", routed_scale=2.446,
    shared_expert_width=1024)
KIMI_PAGE, KIMI_PAGES, KIMI_SLOTS = 128, 12289, 65


def _compile_kimi(one_chip, monkeypatch, kind, size):
    """One of Kimi-Linear's serve programs (`decode`, its twin `selection`
    or `prefill`) compiled the way the engine builds it, for the described
    chip: (executable, the pools' shapes)."""
    monkeypatch.setattr(_device, "on_tpu", lambda: True)
    bf = jnp.bfloat16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(functools.partial(init_params, KIMI_SPEC, dtype=bf)))
    latent, keys = pool_shapes(1, KIMI_PAGES, KIMI_PAGE, 1, 512,
                               KIMI_SPEC.latent_lanes)
    assert keys[-1] == 128
    state = [sds(latent, bf), sds(keys, bf),
             sds((1, KIMI_SLOTS, 3, 3 * 4096), bf),
             sds((1, KIMI_SLOTS, 32, 128, 128), jnp.float32)]
    maxp = KIMI_SPEC.max_seq_len // KIMI_PAGE
    step = prefill_step if kind == "prefill" else functools.partial(
        decode_step, selection=kind == "selection")

    def run(params, k, v, conv, ssm, a, b, c):
        return step(KIMI_SPEC, params, k, v, a, b, c, page_size=KIMI_PAGE,
                    conv_pool=conv, ssm_pool=ssm)

    i32 = functools.partial(sds, dtype=jnp.int32)
    args = ((i32((size,)), i32(()), i32((3, maxp))) if kind == "prefill"
            else (i32((size,)), i32((size,)), i32((size, 3, maxp))))
    exe = jax.jit(run, donate_argnums=(1, 2, 3, 4)).lower(
        params, *state, *args).compile()
    return exe, state


@pytest.mark.parametrize("kind,size", [("decode", 64), ("prefill", 4096),
                                       ("selection", 64)])
def test_chip_compiles_the_kda_and_latent_programs(one_chip, monkeypatch,
                                                  kind, size):
    """The latent pool (512 lanes of latent, the key's 64 on a 128-lane
    tile) and the KDA slots donated and aliased; a decode step updates its
    rows' states where they lie (`kda_state_update`, no `mini-gather`),
    reads the latent pages in place (`paged_attention_mla`) and streams the
    held experts its rows chose (`moe_decode`), in tens of MB of
    temporaries, and so does its twin that also hands out the routers'
    choices and the latent heads; the prefill's chunked delta rule and
    latent attention are XLA's, its experts the grouped kernel."""
    exe, state = _compile_kimi(one_chip, monkeypatch, kind, size)
    text = exe.as_text()
    mem = exe.memory_analysis()
    held = sum(int(np.prod(s.shape)) * s.dtype.itemsize for s in state)
    assert mem.alias_size_in_bytes >= held
    header = text.split("\n", 1)[0]
    assert header.count("may-alias") + header.count("must-alias") == 4
    assert "mini-gather" not in text
    calls = re.findall(r"%(\w+?)_*\.\d+ = [^\n]*custom_call_target="
                       r"\"tpu_custom_call\"", text)
    if kind != "prefill":
        assert mem.temp_size_in_bytes < 64 << 20
        assert sorted(calls) == ["kda_state_update", "moe_decode",
                                 "paged_attention_mla"], calls
    else:
        assert mem.temp_size_in_bytes < 2 << 30
        assert set(calls) == {"gmm"}, calls


# -- Keye-VL-2.0-30B-A3B's programs at its published widths --------------------
# (`benchmarks/configs/keye-vl2-30b-a3b-serve.json`): two of its learned
# sparse attention layers, so a program compiles in seconds; the pools'
# pages, page size, the largest decode bucket and the shortest prefill
# bucket are the configuration's.

KEYE_SPEC = ModelSpec(
    vocab_size=151936, hidden=2048, layers=2, heads=32, kv_heads=4,
    head_size=128, max_seq_len=40960, norm="rms", norm_eps=1e-6,
    positions="rotary", rope_theta=1e7, ffn="moe", experts=128,
    experts_per_token=8, expert_width=768, tie_head=False, qk_norm=True,
    sparse_topk=2048, index_heads=16, index_head_size=64)
KEYE_PAGE, KEYE_PAGES = 128, 5121


@pytest.mark.parametrize("kind,size", [("decode", 24), ("prefill", 8192),
                                       ("selection", 24)])
def test_chip_compiles_the_sparse_programs_on_a_token_major_pool(
        one_chip, monkeypatch, kind, size):
    """K and V one token-major pool `(L, P*ps, 8, 128)` (a token one
    (8, 128) bfloat16 tile) beside the index pool, both donated and
    aliased, neither padded: a decode step writes its rows' tokens, scores
    and selects, and `paged_attention_sparse` copies the listed tokens
    from the pool where it lies — nothing of a layer of it is copied,
    transposed or re-laid, and its temporaries are tens of MB; the prefill
    writes whole pages of tokens in place."""
    monkeypatch.setattr(_device, "on_tpu", lambda: True)
    bf = jnp.bfloat16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(functools.partial(init_params, KEYE_SPEC, dtype=bf)))
    kv, _ = pool_shapes(2, KEYE_PAGES, KEYE_PAGE, 4, 128, token_major=True)
    state = [sds(kv, bf), sds((2, KEYE_PAGES, 64, KEYE_PAGE), bf)]
    maxp = KEYE_SPEC.max_seq_len // KEYE_PAGE
    step = prefill_step if kind == "prefill" else functools.partial(
        decode_step, selection=kind == "selection")

    def run(params, kv, index, a, b, c):
        return step(KEYE_SPEC, params, kv, None, a, b, c,
                    page_size=KEYE_PAGE, index_pool=index)

    i32 = functools.partial(sds, dtype=jnp.int32)
    args = ((i32((size,)), i32(()), i32((maxp,))) if kind == "prefill"
            else (i32((size,)), i32((size,)), i32((size, maxp))))
    exe = jax.jit(run, donate_argnums=(1, 2)).lower(
        params, *state, *args).compile()
    text = exe.as_text()
    mem = exe.memory_analysis()
    pools = [int(np.prod(s.shape)) * 2 for s in state]
    assert mem.alias_size_in_bytes >= sum(pools)
    header = text.split("\n", 1)[0]
    assert header.count("may-alias") + header.count("must-alias") == 2
    assert re.search(r"bf16\[2,655488,8,128\]\{3,2,1,0:T\(8,128\)\(2,1\)\}",
                     text)
    assert not _moved_pool_sized(text, kv[1] * 8 * 128)
    assert "mini-gather" not in text
    calls = re.findall(r"%(\w+?)_*\.\d+ = [^\n]*custom_call_target="
                       r"\"tpu_custom_call\"", text)
    if kind != "prefill":
        assert mem.temp_size_in_bytes < 64 << 20
        assert sorted(calls) == ["moe_decode"] * 2 + [
            "paged_attention_sparse"] * 2 + ["paged_index_scores"] * 2, calls
    else:
        assert mem.temp_size_in_bytes < 1 << 30


def _mxu_fusions(text):
    """(scope, result shape, tiling) of every fusion of the entry that the
    compiler tiled for the matrix unit, sorted."""
    out = []
    for line in text[text.index("ENTRY"):].split("\n"):
        tiling = re.search(
            r'"window_config":(\{"kernel_window_bounds":\["[^}]*\})', line)
        if " fusion(" not in line or not tiling:
            continue
        shape = re.sub(r"\{[^}]*\}", "",
                       line.split(" = ", 1)[1].split(" fusion(")[0])
        scope = re.search(r'op_name="([^"]*)"', line)
        out.append((scope.group(1) if scope else "", shape, tiling.group(1)))
    return sorted(out)


def test_chip_twin_tiles_every_matmul_as_the_decode_program(one_chip,
                                                            monkeypatch):
    """The twin the check holds the scheduler's decode program to does the
    same arithmetic: every matmul fusion has the same result type and the
    same tiling in both.  Its latent heads are the bfloat16 the
    out-projection reads; handed out in float32 they moved the cast out of
    the heads' einsum and re-tiled the out-projection's 4,096-long
    contraction, and one seed's logits rounded a bfloat16 step apart."""
    decode, _ = _compile_kimi(one_chip, monkeypatch, "decode", 64)
    twin, _ = _compile_kimi(one_chip, monkeypatch, "selection", 64)
    found = _mxu_fusions(decode.as_text())
    assert any("attn_out" in scope for scope, _, _ in found)
    assert found == _mxu_fusions(twin.as_text())


# -- the train cell's captured step (same file: one process loads the library) --

def test_chip_train_step_runs_each_kernel_forward_once(one_chip, monkeypatch):
    """The GPT-345M train step at the cell's widths and batch (8 x 1024,
    bf16 AMP O2, AdamW with float32 masters) and 2 layers, built as
    `capture_step` builds it, compiled for the chip.  The tape's deferred
    `jax.vjp` traces each op's forward a second time; each kernel's
    wrapper is one jitted call with one serialised body, so XLA merges the
    two and the executable runs each Mosaic forward once: 2L + 1 layer
    norms, one cross entropy, L flash forwards."""
    import collections
    import json
    import os
    import paddle_tpu as pt
    from paddle_tpu.distributed import mesh as _mesh
    from paddle_tpu.incubate.models import GPTConfig, GPTForCausalLM
    monkeypatch.setattr(_device, "on_tpu", lambda: True)
    # the default mesh would be built over the CPU's devices
    monkeypatch.setattr(_mesh, "_GLOBAL_MESH", _mesh.build_mesh(
        {"dp": 1}, devices=list(one_chip.device_set)))
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs",
                           "gpt-345m.json")) as f:
        spec = dict(json.load(f)["model"], num_layers=2)
    L = spec["num_layers"]
    pt.seed(0)
    model = GPTForCausalLM(GPTConfig(**spec))
    pt.amp.decorate(model, level="O2", dtype="bfloat16")
    opt = pt.optimizer.AdamW(learning_rate=1e-4,
                             parameters=model.parameters(),
                             multi_precision=True)
    ce = pt.nn.CrossEntropyLoss()

    @pt.jit.capture_step
    def step(ids, labels):
        loss = ce(model(ids), labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    ids = pt.to_tensor(np.zeros((8, 1024), np.int64))
    leaves, struct = step._flatten((ids, ids), {})
    entry = step._compile((ids, ids), {}, step._signature(leaves, struct))
    st = step._state

    def sds(a):     # the state holds a python int or two
        return jax.ShapeDtypeStruct(np.shape(a), jnp.result_type(a),
                                    sharding=one_chip)

    state = jax.tree_util.tree_map(
        sds, (st.params, st.buffers, st.opt_states, st.rng_ctr))
    lr = [jax.ShapeDtypeStruct((), jnp.float32, weak_type=True,
                               sharding=one_chip)]
    with jax.default_matmul_precision("default"):
        text = entry.jitted.lower(*state, lr, [sds(ids._data)] * 2) \
            .compile().as_text()
    # a forward the tape's vjp traced apart would read `jvp_<name>_`
    calls = collections.Counter(
        re.sub(r"^jvp_", "", n) for n in re.findall(
            r"%(\w+?)_*\.\d+ = [^\n]*custom_call_target=\"tpu_custom_call\"",
            text))
    assert {k: calls[k] for k in ("layer_norm_fwd", "softmax_xent_fwd",
                                  "flash_fwd")} == {
        "layer_norm_fwd": 2 * L + 1, "softmax_xent_fwd": 1,
        "flash_fwd": L}, dict(calls)
    assert calls["layer_norm_bwd"] == 2 * L + 1 and \
        calls["softmax_xent_bwd"] == 1, dict(calls)
