"""Flash-attention Pallas kernel tests (interpret mode on CPU).

Mirrors the reference's flash-attn tests
(test/legacy_test/test_flash_attention.py): kernel output vs a plain
softmax-attention oracle, forward and gradients, causal and non-causal,
unaligned sequence lengths and head dims.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas_ops import mha, mha_reference


def _rand(shape, seed):
    return jnp.asarray(
        np.random.RandomState(seed).standard_normal(shape).astype(np.float32))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize(
    "b,h,sq,skv,d",
    [
        (2, 2, 128, 128, 64),
        (1, 3, 256, 256, 128),
        (2, 1, 100, 100, 32),     # unaligned S and D → padding path
        (1, 2, 128, 256, 64),     # cross attention, kv longer
    ],
)
def test_flash_forward_matches_reference(causal, b, h, sq, skv, d):
    if causal and sq != skv:
        # causal cross-attn aligns at the end; still defined
        pass
    q, k, v = (_rand((b, h, s, d), i) for i, s in
               enumerate([sq, skv, skv]))
    out = mha(q, k, v, causal=causal, interpret=True, block_q=128,
              block_k=128)
    ref = mha_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-3, rtol=2e-3)


@pytest.mark.slow
@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match_reference(causal):
    b, h, s, d = 1, 2, 128, 64
    q, k, v = (_rand((b, h, s, d), 10 + i) for i in range(3))

    def loss_kernel(q, k, v):
        o = mha(q, k, v, causal=causal, interpret=True)
        return jnp.sum(o * jnp.cos(o))

    def loss_ref(q, k, v):
        o = mha_reference(q, k, v, causal=causal)
        return jnp.sum(o * jnp.cos(o))

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=2e-2, rtol=2e-2)


@pytest.mark.slow
def test_flash_grads_unaligned():
    b, h, s, d = 1, 1, 72, 48
    q, k, v = (_rand((b, h, s, d), 20 + i) for i in range(3))

    def loss_kernel(q, k, v):
        return jnp.sum(mha(q, k, v, causal=True, interpret=True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(mha_reference(q, k, v, causal=True) ** 2)

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=2e-2, rtol=2e-2)


def test_flash_bf16():
    b, h, s, d = 1, 2, 128, 64
    q, k, v = (_rand((b, h, s, d), 30 + i).astype(jnp.bfloat16)
               for i in range(3))
    out = mha(q, k, v, causal=True, interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = mha_reference(q.astype(jnp.float32), k.astype(jnp.float32),
                        v.astype(jnp.float32), causal=True)
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref), atol=3e-2, rtol=3e-2)


@pytest.mark.slow
def test_framework_entry_tensor_layout():
    """flash_attention takes paddle (B, S, H, D) Tensors and autodiffs
    through the framework tape."""
    import paddle_tpu as pt
    from paddle_tpu.ops.pallas_ops import flash_attention

    np.random.seed(0)
    q = pt.to_tensor(np.random.randn(2, 64, 2, 32).astype(np.float32),
                     stop_gradient=False)
    k = pt.to_tensor(np.random.randn(2, 64, 2, 32).astype(np.float32),
                     stop_gradient=False)
    v = pt.to_tensor(np.random.randn(2, 64, 2, 32).astype(np.float32),
                     stop_gradient=False)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    assert tuple(out.shape) == (2, 64, 2, 32)
    out.sum().backward()
    assert q.grad is not None and np.isfinite(q.grad.numpy()).all()

    ref = mha_reference(
        jnp.swapaxes(q._data, 1, 2), jnp.swapaxes(k._data, 1, 2),
        jnp.swapaxes(v._data, 1, 2), causal=True)
    np.testing.assert_allclose(
        np.asarray(jnp.swapaxes(out._data, 1, 2)), np.asarray(ref),
        atol=2e-3, rtol=2e-3)


def _grads_and_out(fn, q, k, v):
    def loss(q, k, v):
        o = fn(q, k, v)
        return jnp.sum(o * jnp.cos(o)), o
    (_, o), g = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                           has_aux=True))(q, k, v)
    return (o,) + g


# the default tile, (128, 128) and (256, 128); a sequence padded to the
# tile, one of two tiles and the train cell's; self attention and
# end-aligned cross attention whose queries are no multiple of a tile
@pytest.mark.parametrize("blocks", [(128, 128), (256, 128), (None, None)])
@pytest.mark.parametrize("sq,skv", [(192, 192), (100, 192), (512, 512),
                                    (320, 512), (1024, 1024), (832, 1024)])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_walk_matches_reference(causal, sq, skv, blocks):
    """Forward and all three gradients of the chunk walk (loop bounds on
    the diagonal, a mask only on the chunks it crosses) against the
    dense reference."""
    q, k, v = (_rand((1, 1, s, 64), 40 + i)
               for i, s in enumerate([sq, skv, skv]))
    got = _grads_and_out(
        lambda q, k, v: mha(q, k, v, causal=causal, interpret=True,
                            block_q=blocks[0], block_k=blocks[1]), q, k, v)
    want = _grads_and_out(
        lambda q, k, v: mha_reference(q, k, v, causal=causal), q, k, v)
    for a, b_ in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=5e-5, rtol=5e-5)


def _dense_mask(sq_p, skv_p, off, limit, causal):
    rows = np.arange(sq_p)[:, None]
    cols = np.arange(skv_p)[None, :]
    mask = np.broadcast_to(cols < limit, (sq_p, skv_p))
    if causal:
        mask = mask & (cols <= rows + off)
    return mask


class TestChunkRanges:
    """The two pure functions the kernels take their loop bounds from
    (`_kv_chunk_range` for a block of queries in flash_fwd and
    flash_bwd_dq, `_q_chunk_range` for a block of keys in
    flash_bwd_dkv)."""

    def test_against_a_dense_mask(self):
        from paddle_tpu.ops.pallas_ops import (_kv_chunk_range,
                                               _q_chunk_range)
        rng = np.random.RandomState(0)
        for _ in range(300):
            bq, bk = (int(rng.choice([8, 16, 24, 64])) for _ in range(2))
            n_q, n_k = (int(rng.randint(1, 9)) for _ in range(2))
            sq_p, skv_p = n_q * bq, n_k * bk
            causal = bool(rng.randint(2))
            limit = int(rng.randint(0, skv_p + 1))
            off = int(rng.randint(-sq_p - 4, skv_p + 5))
            mask = _dense_mask(sq_p, skv_p, off, limit, causal)
            tiles = mask.reshape(n_q, bq, n_k, bk)
            some = tiles.any(axis=(1, 3))       # (n_q, n_k)
            every = tiles.all(axis=(1, 3))
            for i in range(n_q):
                full, stop = _kv_chunk_range(i * bq, bq, off, limit, bk,
                                             n_k, causal)
                assert 0 <= full <= stop <= n_k
                assert every[i, :full].all()        # no mask needed there
                assert not some[i, stop:].any()     # nothing lost
                # and nothing visited in vain, nothing masked in vain
                assert all(some[i, j] and not every[i, j]
                           for j in range(full, stop))
            for j in range(n_k):
                first, full = _q_chunk_range(j * bk, bk, off, limit, bq,
                                             n_q, causal)
                assert 0 <= first <= n_q and 0 <= full <= n_q
                assert not some[:first, j].any()
                assert every[max(first, full):, j].all()
                assert all(some[i, j] for i in range(first, full))

    def test_traced_scalars_give_the_same(self):
        from paddle_tpu.ops.pallas_ops import (_kv_chunk_range,
                                               _q_chunk_range)
        for fn in (_kv_chunk_range, _q_chunk_range):
            traced = jax.jit(lambda a, off, lim: fn(a, 16, off, lim, 8, 12,
                                                    True))
            for a, off, lim in [(0, 0, 96), (32, -40, 96), (48, 7, 50),
                                (80, 200, 96), (16, -200, 0)]:
                assert tuple(int(x) for x in traced(a, off, lim)) == \
                    tuple(fn(a, 16, off, lim, 8, 12, True))

    def test_the_train_cell_visits_three_of_four_tiles(self):
        """The issue sized the skip at 56-62.5 % of 128- or 256-wide
        tiles; on the chip a tile under 512 x 512 loses more to its own
        fixed cost than the finer diagonal saves (PERF.md, PR 30), so the
        default tile visits 3 of the cell's 4, and (256, 256) 10 of 16."""
        from paddle_tpu.ops.pallas_ops import (_kv_chunk_range, _mha_plan,
                                               mha_chunks)
        visited, total = mha_chunks(1024, 1024, 64, jnp.bfloat16,
                                    causal=True)
        assert (visited, total) == (3, 4)
        assert mha_chunks(1024, 1024, 64, jnp.bfloat16,
                          causal=False) == (total, total)
        plan, sq_p, skv_p = _mha_plan(1024, 1024, 64, jnp.bfloat16,
                                      causal=True, block_q=256, block_k=256)
        assert sum(_kv_chunk_range(i * 256, 256, 0, 1024, 256, 4, True)[1]
                   for i in range(4)) == 10
        assert (plan.block_q, plan.block_k, sq_p, skv_p) == (256, 256, 1024,
                                                             1024)

    def test_a_tile_holds_whole_lane_tiles_of_keys(self):
        """block_k is rounded up to the 128 lanes a row's running
        statistics are replicated over; the keys are padded to it and the
        padding is masked."""
        from paddle_tpu.ops.pallas_ops import _mha_plan
        for skv, block_k, want in [(100, None, 128), (1024, 64, 128),
                                   (1024, 200, 256), (300, None, 384),
                                   (4096, None, 512)]:
            plan, _, skv_p = _mha_plan(64, skv, 64, jnp.float32,
                                       causal=False, block_k=block_k,
                                       block_q=64)
            assert plan.block_k == want and skv_p % want == 0

    def test_the_kernels_call_them(self, monkeypatch):
        import paddle_tpu.ops.pallas_ops as po
        seen = []
        for name in ("_kv_chunk_range", "_q_chunk_range"):
            real = getattr(po, name)
            monkeypatch.setattr(
                po, name, lambda *a, _real=real, _name=name:
                (seen.append(_name), _real(*a))[1])
        q = _rand((1, 1, 128, 64), 0)
        jax.grad(lambda q: mha(q, q, q, causal=True, interpret=True,
                               block_q=64, block_k=64).sum())(q)
        # forward (twice: primal and the vjp's), dQ; dK/dV
        assert seen.count("_kv_chunk_range") >= 2
        assert seen.count("_q_chunk_range") == 1

    def test_resident_span(self):
        from paddle_tpu.ops.pallas_ops import _resident_span, _RESIDENT_BYTES
        # the train cell: K and V of a (1024, 128) bf16 head stay whole
        assert _resident_span(1024, 256, 2 * 128 * 2) == 1024
        for rows, chunk, row_bytes in [(8192, 256, 512), (8192, 256, 1536),
                                       (65536, 512, 1024), (24, 8, 1 << 30)]:
            span = _resident_span(rows, chunk, row_bytes)
            assert span % chunk == 0 and span <= rows
            assert span == chunk or 2 * span * row_bytes <= _RESIDENT_BYTES
            # equal super-blocks: no more padding than a chunk each
            n_super = -(-rows // span)
            assert n_super * span - rows < n_super * chunk


@pytest.mark.parametrize("causal", [False, True])
def test_flash_super_blocks_match_one_span(causal):
    """A walked operand that does not fit VMEM whole is held a
    super-block at a time, the grid's last axis over them: the same
    numbers as one span."""
    from paddle_tpu.ops.pallas_ops import _flash, _mha_plan
    q, k, v = (_rand((2, s, 128), 50 + i) for i, s in
               enumerate([256, 512, 512]))
    plan, sq_p, skv_p = _mha_plan(256, 512, 128, q.dtype, causal=causal,
                                  block_q=64, block_k=128, interpret=True)
    assert (plan.q_span, plan.kv_span, sq_p, skv_p) == (256, 512, 256, 512)
    seed = jnp.zeros((), jnp.float32)

    def run(plan):
        return _grads_and_out(
            lambda q, k, v: _flash(q, k, v, seed, None, None, plan), q, k, v)

    for a, b_ in zip(run(plan._replace(q_span=128, kv_span=256)), run(plan)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("shift", [-256, -1, 0, 256])
def test_flash_traced_shift_with_lse_cotangent(shift):
    """Ring attention's call: a traced diagonal shift (a source rank
    ahead: all masked, zeros and lse = _NEG_INF; behind: nothing
    masked), (out, lse) returned and both differentiated."""
    from paddle_tpu.ops.pallas_ops import _NEG_INF
    s, d = 256, 64
    q, k, v = (_rand((1, 2, s, d), 60 + i) for i in range(3))
    w = _rand((1, 2, s), 63)

    def dense(q, k, v, shift):
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
        rows = jnp.arange(s)[:, None]
        cols = jnp.arange(s)[None, :]
        mask = cols <= rows + shift
        logits = jnp.where(mask, logits, -jnp.inf)
        m = jnp.max(logits, axis=-1, keepdims=True)
        m = jnp.where(jnp.isfinite(m), m, 0.0)
        p = jnp.where(mask, jnp.exp(logits - m), 0.0)
        l = p.sum(-1, keepdims=True)
        seen = l[..., 0] > 0
        out = jnp.einsum("bhqk,bhkd->bhqd", p / jnp.where(l > 0, l, 1.0), v)
        lse = jnp.where(seen, m[..., 0] + jnp.log(jnp.where(l > 0, l, 1.0)
                                                  [..., 0]), _NEG_INF)
        return out, lse

    def loss(fn):
        def f(q, k, v, shift):
            out, lse = fn(q, k, v, shift)
            live = lse > _NEG_INF / 2
            return jnp.sum(out * jnp.cos(out)) + jnp.sum(
                jnp.where(live, lse * w, 0.0)), (out, lse)
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                          has_aux=True))

    kernel = lambda q, k, v, shift: mha(
        q, k, v, causal=True, causal_shift=shift, return_lse=True,
        interpret=True, block_q=128, block_k=64)
    (_, (out, lse)), g = loss(kernel)(q, k, v, jnp.int32(shift))
    (_, (out_r, lse_r)), g_r = loss(dense)(q, k, v, jnp.int32(shift))
    for a, b_ in zip((out, lse) + g, (out_r, lse_r) + g_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=5e-5, rtol=5e-5)
    if shift == -256:
        assert not np.asarray(out).any()
        assert (np.asarray(lse) == _NEG_INF).all()
        assert not any(np.asarray(x).any() for x in g)


def test_sdpa_dispatch_records_the_walk(monkeypatch):
    """Beside `path=pallas`, the dispatch books how far the causal skip
    engages: at the train cell's shape 3 of 4 tiles."""
    import paddle_tpu as pt
    import paddle_tpu.nn.functional.common as C
    import paddle_tpu.ops.pallas_ops as po
    from paddle_tpu.framework import device
    from paddle_tpu.observability.metrics import get_registry, reset_registry
    from paddle_tpu.observability.telemetry import get_telemetry
    tel = get_telemetry()
    prev, tel.enabled = tel.enabled, True
    reset_registry()
    try:
        monkeypatch.setattr(device, "on_tpu", lambda: True)
        real_fa = po.flash_attention
        monkeypatch.setattr(
            po, "flash_attention",
            lambda q, k, v, **kw: real_fa(q, k, v, **dict(kw, interpret=True)))
        x = pt.to_tensor(np.ones((1, 1024, 1, 64), np.float32))
        C.scaled_dot_product_attention(x, x, x, is_causal=True)
        c = get_registry().counter("pt_flash_chunks_total",
                                   labelnames=("state",))
        assert (c.value(state="visited"), c.value(state="total")) == (3, 4)
    finally:
        reset_registry()
        tel.enabled = prev


class TestKernelAutotune:
    """Kernel-config autotune (ref: paddle/phi/kernels/autotune/): warmup
    timing picks a block config, the cache feeds later (traced) calls."""

    @pytest.mark.slow
    def test_tune_mha_populates_cache_and_outputs_match(self):
        import jax
        from paddle_tpu.ops import autotune as at
        from paddle_tpu.ops.pallas_ops import mha, tune_mha, mha_reference
        at.cache_clear()
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(1, 2, 64, 16).astype(np.float32))
        k = jnp.asarray(rng.randn(1, 2, 64, 16).astype(np.float32))
        v = jnp.asarray(rng.randn(1, 2, 64, 16).astype(np.float32))
        best, timings = tune_mha(q, k, v, causal=True, interpret=True,
                                 candidates=((128, 128), (64, 64)))
        assert best in timings and len(timings) >= 1
        # the cached choice drives default-config calls now
        key_hit = at.cache_get(
            "flash_mha", (64, 64, 16, "float32", True, True))
        assert key_hit == best
        out = mha(q, k, v, causal=True, interpret=True)
        ref = mha_reference(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-3, atol=2e-3)

    def test_cache_roundtrip_and_set_config(self, tmp_path):
        from paddle_tpu.ops import autotune as at
        from paddle_tpu.incubate import autotune as iat
        at.cache_clear()
        at.cache_put("flash_mha", (128, 128, 64, "bfloat16", False, False),
                     (256, 128))
        p = str(tmp_path / "tune.json")
        iat.save_cache(p)
        at.cache_clear()
        assert at.cache_get(
            "flash_mha", (128, 128, 64, "bfloat16", False, False)) is None
        iat.load_cache(p)
        assert at.cache_get(
            "flash_mha",
            (128, 128, 64, "bfloat16", False, False)) == (256, 128)
        iat.set_config({"kernel": {"enable": True}})
        assert at.enabled()
        iat.set_config({"kernel": {"enable": False}})
        assert not at.enabled()


class TestFlashDropout:
    """In-kernel attention dropout (ref flash_attn dropout path,
    ``paddle/phi/kernels/gpu/flash_attn_kernel.cu``): the counter-based
    mask is deterministic given (seed, coords), so an exact oracle can
    rebuild it outside the kernel via _tile_keep_mask."""

    PD = 0.3

    def _setup(self, b=1, h=2, s=128, d=64):
        q, k, v = (_rand((b, h, s, d), i) for i in range(3))
        seed = jnp.asarray(1.2345, jnp.float32)
        return q, k, v, seed

    def _oracle(self, q, k, v, seed, pd):
        from paddle_tpu.ops.pallas_ops import _tile_keep_mask
        b, h, s, d = q.shape
        bh = b * h
        qq, kk, vv = (x.reshape(bh, s, d) for x in (q, k, v))
        p = jax.nn.softmax(
            jnp.einsum("bqd,bkd->bqk", qq, kk) / np.sqrt(d), axis=-1)
        s32 = jax.lax.bitcast_convert_type(seed, jnp.int32)
        M = jnp.stack([
            jnp.concatenate([
                jnp.concatenate([
                    _tile_keep_mask(s32, jnp.int32(bi), jnp.int32(qi),
                                    jnp.int32(ki), 128, 128, pd)
                    for ki in range(s // 128)], axis=1)
                for qi in range(s // 128)], axis=0)
            for bi in range(bh)])
        pt = jnp.where(M, p / (1 - pd), 0.0)
        return jnp.einsum("bqk,bkd->bqd", pt, vv).reshape(b, h, s, d)

    def test_forward_matches_mask_oracle(self):
        q, k, v, seed = self._setup()
        out = mha(q, k, v, dropout_p=self.PD, seed=seed, interpret=True,
                  block_q=128, block_k=128)
        ref = self._oracle(q, k, v, seed, self.PD)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.slow
    def test_grads_match_mask_oracle(self):
        q, k, v, seed = self._setup()
        g = jax.grad(lambda *a: (mha(*a[:3], dropout_p=self.PD, seed=a[3],
                                     interpret=True, block_q=128,
                                     block_k=128) ** 2).sum(),
                     argnums=(0, 1, 2))(q, k, v, seed)
        gr = jax.grad(lambda *a: (self._oracle(*a, self.PD) ** 2).sum(),
                      argnums=(0, 1, 2))(q, k, v, seed)
        for a, b_ in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=3e-4, rtol=3e-4)

    def test_mask_is_the_elements_not_the_tiles(self):
        """Bit for bit the murmur hash of the element's global (bh, row,
        column), whatever tile regenerates it."""
        from paddle_tpu.ops.pallas_ops import _tile_keep_mask
        seed, bh, n = 0x1234567, 5, 256
        with np.errstate(over="ignore"):
            rows = np.arange(n, dtype=np.uint32)[:, None]
            cols = np.arange(n, dtype=np.uint32)[None, :]
            h = rows * np.uint32(0x193E9) + cols
            h = h ^ np.uint32(seed) ^ (np.uint32(bh) * np.uint32(0x9E3779B1))
            for mult in (0x85EBCA6B, 0xC2B2AE35):
                h = h * np.uint32(mult)
                h = h ^ (h >> np.uint32(15))
            want = (h >> np.uint32(8)).astype(np.int64) >= int(
                self.PD * (1 << 24))
        for bq, bk in [(256, 256), (128, 128), (64, 256), (256, 32)]:
            got = np.block([[np.asarray(_tile_keep_mask(
                jnp.int32(seed), jnp.int32(bh), jnp.int32(qi), jnp.int32(ki),
                bq, bk, self.PD)) for ki in range(n // bk)]
                for qi in range(n // bq)])
            assert (got == want).all(), (bq, bk)

    @pytest.mark.parametrize("causal", [False, True])
    def test_any_tiling_drops_the_same_elements(self, causal):
        """Output and gradients at (128, 128) equal those at the default
        tile to float32 round-off."""
        q, k, v, seed = self._setup(h=1, s=512)
        runs = [_grads_and_out(
            lambda q, k, v: mha(q, k, v, causal=causal, dropout_p=0.4,
                                seed=seed, interpret=True, block_q=bq,
                                block_k=bk), q, k, v)
            for bq, bk in [(128, 128), (None, None)]]
        for a, b_ in zip(*runs):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=2e-5, rtol=2e-5)

    def test_keep_fraction_and_seed_sensitivity(self):
        from paddle_tpu.ops.pallas_ops import _tile_keep_mask
        s32 = jnp.int32(12345)
        m = _tile_keep_mask(s32, jnp.int32(0), jnp.int32(0), jnp.int32(0),
                            128, 128, self.PD)
        assert abs(float(m.mean()) - (1 - self.PD)) < 0.02
        m2 = _tile_keep_mask(jnp.int32(54321), jnp.int32(0), jnp.int32(0),
                             jnp.int32(0), 128, 128, self.PD)
        assert bool((m != m2).any())
        # different tiles get different masks
        m3 = _tile_keep_mask(s32, jnp.int32(0), jnp.int32(1), jnp.int32(0),
                             128, 128, self.PD)
        assert bool((m != m3).any())

    @pytest.mark.slow
    def test_dropout_changes_with_seed_and_zero_is_exact(self):
        q, k, v, _ = self._setup()
        o1 = mha(q, k, v, dropout_p=self.PD,
                 seed=jnp.asarray(1.0, jnp.float32), interpret=True)
        o2 = mha(q, k, v, dropout_p=self.PD,
                 seed=jnp.asarray(2.0, jnp.float32), interpret=True)
        assert float(jnp.abs(o1 - o2).max()) > 1e-4
        o0 = mha(q, k, v, dropout_p=0.0, interpret=True)
        np.testing.assert_allclose(np.asarray(o0),
                                   np.asarray(mha_reference(q, k, v)),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.slow
    def test_framework_entry_dropout_trains(self):
        """flash_attention with dropout through the tape: grads flow and
        two eager calls draw different masks (generator advances)."""
        import paddle_tpu as pt
        from paddle_tpu.ops.pallas_ops import flash_attention
        pt.seed(11)
        x = np.random.RandomState(0).randn(1, 128, 2, 64).astype(np.float32)
        q = pt.to_tensor(x, stop_gradient=False)
        o1 = flash_attention(q, pt.to_tensor(x), pt.to_tensor(x),
                             causal=True, dropout_p=0.4, interpret=True)
        o1.sum().backward()
        assert q.grad is not None and np.isfinite(q.grad.numpy()).all()
        o2 = flash_attention(pt.to_tensor(x), pt.to_tensor(x),
                             pt.to_tensor(x), causal=True, dropout_p=0.4,
                             interpret=True)
        assert float(np.abs(o1.numpy() - o2.numpy()).max()) > 1e-5


class TestVarlen:
    """Per-row kv-length masking (ref flash_attn_unpadded,
    ``python/paddle/nn/functional/flash_attention.py:272``)."""

    def _ref_padded(self, q, k, v, lens, causal=False):
        b, h, s, d = q.shape
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
        kcol = jnp.arange(s)[None, None, None, :]
        mask = kcol < jnp.asarray(lens)[:, None, None, None]
        if causal:
            qrow = jnp.arange(s)[None, None, :, None]
            mask = mask & (kcol <= qrow)
        logits = jnp.where(mask, logits, -jnp.inf)
        p = jax.nn.softmax(logits, axis=-1)
        p = jnp.where(jnp.isnan(p), 0.0, p)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v)

    @pytest.mark.slow
    def test_forward_matches_masked_reference(self):
        q, k, v = (_rand((3, 2, 128, 64), i) for i in range(3))
        lens = np.array([128, 70, 1], np.int32)
        for causal in (False, True):
            out = mha(q, k, v, seq_lens=lens, causal=causal, interpret=True)
            ref = self._ref_padded(q, k, v, lens, causal)
            # only rows < len are meaningful
            for bi, L in enumerate(lens):
                np.testing.assert_allclose(
                    np.asarray(out)[bi, :, :L], np.asarray(ref)[bi, :, :L],
                    atol=2e-5, rtol=2e-5)

    @pytest.mark.slow
    def test_grads_match_masked_reference(self):
        q, k, v = (_rand((2, 2, 128, 64), i) for i in range(3))
        lens = np.array([100, 40], np.int32)

        def valid_loss(out):
            # padded query rows excluded, as a caller's loss mask would
            m = (jnp.arange(128)[None, :] < jnp.asarray(lens)[:, None])
            return ((out * m[:, None, :, None]) ** 2).sum()

        g = jax.grad(lambda *a: valid_loss(
            mha(*a, seq_lens=lens, interpret=True)), argnums=(0, 1, 2))(
                q, k, v)
        gr = jax.grad(lambda *a: valid_loss(
            self._ref_padded(*a, lens)), argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=3e-4, rtol=3e-4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_a_row_shorter_than_a_chunk(self, causal):
        """Rows of 256, 70 and 1 valid keys under (128, 128) tiles (a
        block_k of 64 is rounded up to the lanes): the walk stops at the
        row's last chunk and masks only that one."""
        q, k, v = (_rand((3, 1, 256, 64), i) for i in range(3))
        lens = np.array([256, 70, 1], np.int32)
        valid = (jnp.arange(256)[None, :] < jnp.asarray(lens)[:, None])[
            :, None, :, None]
        got = _grads_and_out(
            lambda q, k, v: valid * mha(
                q, k, v, seq_lens=lens, causal=causal, interpret=True,
                block_q=128, block_k=64), q, k, v)
        want = _grads_and_out(
            lambda q, k, v: valid * self._ref_padded(q, k, v, lens, causal),
            q, k, v)
        for a, b_ in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=5e-5, rtol=5e-5)

    @pytest.mark.slow
    def test_unpadded_api_packed_layout(self):
        import paddle_tpu as pt
        from paddle_tpu.nn.functional import flash_attn_unpadded
        rs = np.random.RandomState(3)
        lens = [60, 128, 13]
        cu = np.cumsum([0] + lens).astype(np.int32)
        total, h, d = int(cu[-1]), 2, 64
        qkv = [rs.randn(total, h, d).astype(np.float32) for _ in range(3)]
        out, _ = flash_attn_unpadded(
            pt.to_tensor(qkv[0]), pt.to_tensor(qkv[1]), pt.to_tensor(qkv[2]),
            pt.to_tensor(cu), pt.to_tensor(cu), 128, 128,
            scale=1.0 / np.sqrt(d))
        assert tuple(out.shape) == (total, h, d)
        # each packed sequence must equal standalone attention on itself
        for i in range(len(lens)):
            s0, s1 = int(cu[i]), int(cu[i + 1])
            qi = jnp.asarray(qkv[0][s0:s1])[None].swapaxes(1, 2)
            ki = jnp.asarray(qkv[1][s0:s1])[None].swapaxes(1, 2)
            vi = jnp.asarray(qkv[2][s0:s1])[None].swapaxes(1, 2)
            ref = mha_reference(qi, ki, vi)[0].swapaxes(0, 1)
            np.testing.assert_allclose(out.numpy()[s0:s1], np.asarray(ref),
                                       atol=2e-3, rtol=2e-3)

    def test_flash_attention_api(self):
        import paddle_tpu as pt
        from paddle_tpu.nn.functional.flash_attention import flash_attention
        x = np.random.RandomState(0).randn(1, 128, 2, 64).astype(np.float32)
        t = pt.to_tensor(x)
        out, sm = flash_attention(t, t, t, causal=True)
        assert sm is None and tuple(out.shape) == (1, 128, 2, 64)
        out2, sm2 = flash_attention(t, t, t, causal=True,
                                    return_softmax=True)
        assert sm2 is not None
        np.testing.assert_allclose(out.numpy(), out2.numpy(), atol=2e-3,
                                   rtol=2e-3)


def test_sdpa_flash_min_seq_gate(monkeypatch):
    """SDPA must keep short sequences on the XLA path (flash's padding +
    grid overhead loses below flash_min_seq: v5e BERT s=128 measured
    808 vs 750 seq/s) and route long ones to the kernel."""
    import paddle_tpu as pt
    import paddle_tpu.nn.functional.common as C
    from paddle_tpu.framework import device

    calls = []
    monkeypatch.setattr(device, "on_tpu", lambda: True)

    import paddle_tpu.ops.pallas_ops as po
    real_fa = po.flash_attention

    def spy_fa(q, k, v, **kw):
        calls.append(tuple(q.shape))
        kw["interpret"] = True  # no real TPU in CI
        return real_fa(q, k, v, **kw)

    monkeypatch.setattr(po, "flash_attention", spy_fa)
    x_short = pt.to_tensor(np.ones((1, 128, 2, 64), np.float32))
    x_long = pt.to_tensor(np.ones((1, 512, 2, 64), np.float32))
    C.scaled_dot_product_attention(x_short, x_short, x_short)
    assert calls == []  # 128 < flash_min_seq -> XLA path
    C.scaled_dot_product_attention(x_long, x_long, x_long)
    assert calls == [(1, 512, 2, 64)]


class TestPackedVarlen:
    """True ragged varlen kernel (mha_packed): cross lengths, causal
    bottom-right alignment, tape grads, validation (ref
    ``python/paddle/nn/functional/flash_attention.py:272``)."""

    @staticmethod
    def _oracle(q, k, v, cu_q, cu_k, causal):
        d = q.shape[-1]
        out = np.zeros_like(q)
        for i in range(len(cu_q) - 1):
            qs, qe = cu_q[i], cu_q[i + 1]
            ks, ke = cu_k[i], cu_k[i + 1]
            qq = q[qs:qe].transpose(1, 0, 2)
            kk = k[ks:ke].transpose(1, 0, 2)
            vv = v[ks:ke].transpose(1, 0, 2)
            s = np.einsum("hqd,hkd->hqk", qq, kk) / np.sqrt(d)
            lq, lk = qe - qs, ke - ks
            if causal:
                mask = (np.arange(lk)[None, :]
                        <= np.arange(lq)[:, None] + (lk - lq))
                s = np.where(mask, s, -np.inf)
            with np.errstate(invalid="ignore"):
                p = np.exp(s - s.max(-1, keepdims=True))
                p = np.nan_to_num(p, nan=0.0)
                den = p.sum(-1, keepdims=True)
                p = np.where(den > 0, p / np.where(den > 0, den, 1.0), 0.0)
            out[qs:qe] = np.einsum("hqk,hkd->hqd", p, vv).transpose(1, 0, 2)
        return out

    @pytest.mark.slow
    def test_self_and_cross_all_modes(self):
        from paddle_tpu.ops.pallas_ops import mha_packed
        rs = np.random.RandomState(0)
        H, D = 2, 64
        cu = np.cumsum([0, 64, 200, 37]).astype(np.int32)
        cuk = np.cumsum([0, 80, 150, 100]).astype(np.int32)
        q = rs.randn(int(cu[-1]), H, D).astype(np.float32)
        k = rs.randn(int(cuk[-1]), H, D).astype(np.float32)
        v = rs.randn(int(cuk[-1]), H, D).astype(np.float32)
        for cu_k_used, kk, vv in ((cu, q, q), (cuk, k, v)):
            for causal in (False, True):
                got = np.asarray(mha_packed(
                    jnp.asarray(q), jnp.asarray(kk), jnp.asarray(vv),
                    jnp.asarray(cu), jnp.asarray(cu_k_used),
                    causal=causal, block_q=128, block_k=128,
                    interpret=True))
                want = self._oracle(q, kk, vv, cu, cu_k_used, causal)
                np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)

    @pytest.mark.slow
    def test_grads_vs_dense(self):
        from paddle_tpu.ops.pallas_ops import mha_packed
        rs = np.random.RandomState(1)
        H, D = 2, 64
        cu = np.cumsum([0, 50, 90]).astype(np.int32)
        q = jnp.asarray(rs.randn(int(cu[-1]), H, D).astype(np.float32))

        def loss(q, k, v):
            o = mha_packed(q, k, v, jnp.asarray(cu), jnp.asarray(cu),
                           causal=True, block_q=64, block_k=64,
                           interpret=True)
            return (o.astype(jnp.float32) ** 2).sum()

        g = jax.grad(loss, argnums=(0, 1, 2))(q, q, q)

        def dense(q, k, v):
            outs = []
            for i in range(len(cu) - 1):
                s0, s1 = int(cu[i]), int(cu[i + 1])
                qq = jnp.swapaxes(q[s0:s1], 0, 1)
                kk = jnp.swapaxes(k[s0:s1], 0, 1)
                vv = jnp.swapaxes(v[s0:s1], 0, 1)
                s = jnp.einsum("hqd,hkd->hqk", qq, kk) / np.sqrt(D)
                L = s1 - s0
                mask = jnp.tril(jnp.ones((L, L), bool))
                p = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
                outs.append(jnp.swapaxes(
                    jnp.einsum("hqk,hkd->hqd", p, vv), 0, 1))
            return (jnp.concatenate(outs) ** 2).sum()

        gw = jax.grad(dense, argnums=(0, 1, 2))(q, q, q)
        for a, b_ in zip(g, gw):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=3e-4, rtol=3e-4)

    @pytest.mark.slow
    def test_unpadded_api_cross_lengths_and_validation(self):
        # small shapes on purpose: this is the FAST-tier guard for the
        # packed path; the full-size parity lives in the slow tier
        import paddle_tpu as pt
        from paddle_tpu.nn.functional import flash_attn_unpadded
        rs = np.random.RandomState(5)
        H, D = 1, 32
        cu = np.cumsum([0, 12, 20]).astype(np.int32)
        cuk = np.cumsum([0, 16, 10]).astype(np.int32)
        q = rs.randn(int(cu[-1]), H, D).astype(np.float32)
        k = rs.randn(int(cuk[-1]), H, D).astype(np.float32)
        v = rs.randn(int(cuk[-1]), H, D).astype(np.float32)
        out, _ = flash_attn_unpadded(
            pt.to_tensor(q), pt.to_tensor(k), pt.to_tensor(v),
            pt.to_tensor(cu), pt.to_tensor(cuk), 20, 16,
            scale=1.0 / np.sqrt(D))
        want = self._oracle(q, k, v, cu, cuk, False)
        np.testing.assert_allclose(out.numpy(), want, atol=2e-3, rtol=2e-3)
        # malformed cu raises eagerly (no NaN poison)
        bad = np.array([0, 25, 10], np.int32)
        with pytest.raises(ValueError):
            flash_attn_unpadded(
                pt.to_tensor(q), pt.to_tensor(k), pt.to_tensor(v),
                pt.to_tensor(bad), pt.to_tensor(cuk), 20, 16,
                scale=1.0 / np.sqrt(D))

    @pytest.mark.slow
    def test_unpadded_grad_through_tape(self):
        import paddle_tpu as pt
        from paddle_tpu.nn.functional import flash_attn_unpadded
        from paddle_tpu import Tensor
        rs = np.random.RandomState(6)
        cu = np.cumsum([0, 30, 50]).astype(np.int32)
        q = Tensor(rs.randn(int(cu[-1]), 2, 64).astype(np.float32),
                   stop_gradient=False)
        out, _ = flash_attn_unpadded(q, q, q, pt.to_tensor(cu),
                                     pt.to_tensor(cu), 50, 50, scale=0.125,
                                     causal=True)
        pt.sum(out * out).backward()
        assert q.grad is not None
        assert np.isfinite(np.asarray(q.grad._data)).all()


def test_packed_varlen_minimal_fast():
    """FAST-tier guard for the packed kernel itself: one tiny single-
    sequence forward (every capability keeps at least one fast test;
    the richer guard + parity suites are slow-tier)."""
    from paddle_tpu.ops.pallas_ops import mha_packed
    rs = np.random.RandomState(9)
    q = jnp.asarray(rs.randn(8, 1, 8).astype(np.float32))
    cu = jnp.asarray(np.array([0, 8], np.int32))
    got = np.asarray(mha_packed(q, q, q, cu, cu, causal=True, block_q=8,
                                block_k=8, interpret=True))[:, 0]
    qq = np.asarray(q)[:, 0]
    lg = qq @ qq.T / np.sqrt(8)
    lg = np.where(np.tril(np.ones_like(lg, dtype=bool)), lg, -1e30)
    pr = np.exp(lg - lg.max(-1, keepdims=True))
    pr /= pr.sum(-1, keepdims=True)
    np.testing.assert_allclose(got, pr @ qq, atol=2e-5)


@pytest.mark.slow
def test_packed_varlen_fast_guard():
    """Minimal fast-tier guard for the packed path: ONE tiny kernel call
    (single cross pair) + the eager cu validation. Full parity suites
    are slow-tier."""
    import paddle_tpu as pt
    from paddle_tpu.ops.pallas_ops import mha_packed
    rs = np.random.RandomState(7)
    cu = np.array([0, 10], np.int32)
    cuk = np.array([0, 14], np.int32)
    q = jnp.asarray(rs.randn(10, 1, 16).astype(np.float32))
    k = jnp.asarray(rs.randn(14, 1, 16).astype(np.float32))
    v = jnp.asarray(rs.randn(14, 1, 16).astype(np.float32))
    got = np.asarray(mha_packed(q, k, v, jnp.asarray(cu), jnp.asarray(cuk),
                                causal=False, block_q=16, block_k=16,
                                interpret=True))
    s = np.einsum("qhd,khd->hqk", np.asarray(q), np.asarray(k)) / 4.0
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("hqk,khd->qhd", p, np.asarray(v))
    np.testing.assert_allclose(got, want, atol=2e-5)
    from paddle_tpu.nn.functional.flash_attention import _validate_cu
    with pytest.raises(ValueError):
        _validate_cu(np.array([0, 20, 10], np.int32), 14, "cu_seqlens_k")


def test_unpadded_rejects_understated_max_seqlen():
    """Understating max_seqlen must raise eagerly, not silently
    truncate."""
    import paddle_tpu as pt
    from paddle_tpu.nn.functional import flash_attn_unpadded
    rs = np.random.RandomState(3)
    cu = np.cumsum([0, 10, 30]).astype(np.int32)
    q = rs.randn(int(cu[-1]), 1, 16).astype(np.float32)
    with pytest.raises(ValueError, match="longest sequence"):
        flash_attn_unpadded(
            pt.to_tensor(q), pt.to_tensor(q), pt.to_tensor(q),
            pt.to_tensor(cu), pt.to_tensor(cu), 16, 30, scale=0.25)
