"""Jaxpr pattern-matching fusion pass: matchers on synthetic graphs
(f32 and AMP-style bf16 lowerings), near-misses left alone, rewritten-
vs-unrewritten fwd+grad parity, env kill switch / per-pattern opt-out,
capture integration (one compile, rewrites recorded on the entry),
bf16-in/f32-acc parity for the block kernels, and the cost-model-guided
candidate generator + schema-bump invalidation in the autotuner.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import autotune as at
from paddle_tpu.ops import fused_kernels as fk
from paddle_tpu.ops import fusion_pass as fp

QK = (((3,), (3,)), ((0, 1), (0, 1)))
PV = (((3,), (2,)), ((0, 1), (0, 1)))
DOT2 = (((1,), (0,)), ((), ()))

BF16_TOL = dict(rtol=3e-2, atol=3e-2)


@pytest.fixture(autouse=True)
def _clean_pass(monkeypatch):
    monkeypatch.delenv("PT_FUSION_PASS", raising=False)
    monkeypatch.delenv("PT_FUSION_DISABLE", raising=False)
    fp.reset_stats()
    yield
    fp.reset_stats()


def _rand(shape, seed=0, dtype=np.float32):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape)
                       .astype(dtype))


# ---------------------------------------------------------------------------
# synthetic graphs — written the way the models lower (jnp.mean inlines
# to reduce_sum/div, jnp.var stays a pjit[_var], jax.nn.softmax emits
# the reduce_max/stop_gradient/exp/sum soup)
# ---------------------------------------------------------------------------
def _ln(x, w, b):
    m = jnp.mean(x, axis=-1, keepdims=True)
    v = jnp.var(x, axis=-1, keepdims=True)
    return (x - m) * jax.lax.rsqrt(v + 1e-5) * w + b


def _res_ln(x, r, w, b):
    return _ln(x + r, w, b)


def _lnmm(x, w, b, mw, mb):
    return jax.lax.dot_general(_ln(x, w, b), mw, DOT2) + mb


def _gelu_tanh(z):
    return 0.5 * (1.0 + jnp.tanh(0.7978845608028654 *
                                 (z + 0.044715 * z ** 3))) * z


def _mbg(x, w, b):
    return _gelu_tanh(jax.lax.dot_general(x, w, DOT2) + b)


def _mbg_erf(x, w, b):
    z = jax.lax.dot_general(x, w, DOT2) + b
    return (z * 0.5) * jax.lax.erfc(-z * 0.7071067811865476)


def _attn(q, k, v, causal=False):
    s = jax.lax.dot_general(q, k, QK) * 0.125
    if causal:
        S = q.shape[2]
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jax.lax.dot_general(p, v, PV)


class _Args:
    """Shared small operands (f32)."""
    x = _rand((8, 32))
    r = _rand((8, 32), 3)
    w = _rand((32,), 1)
    b = _rand((32,), 2)
    mw = _rand((32, 48), 4)
    mb = _rand((48,), 5)
    q = _rand((2, 2, 16, 8), 6)
    k = _rand((2, 2, 16, 8), 7)
    v = _rand((2, 2, 16, 8), 8)


A = _Args


# ---------------------------------------------------------------------------
# matchers: every pattern kind, f32 graphs
# ---------------------------------------------------------------------------
class TestMatchers:

    def test_layer_norm(self):
        assert fp.count_patterns(_ln, A.x, A.w, A.b) == {"layer_norm": 1}

    def test_residual_ln(self):
        assert fp.count_patterns(_res_ln, A.x, A.r, A.w, A.b) == \
            {"residual_ln": 1}

    def test_ln_matmul(self):
        assert fp.count_patterns(_lnmm, A.x, A.w, A.b, A.mw, A.mb) == \
            {"ln_matmul": 1}

    def test_matmul_bias_gelu_tanh_and_erf(self):
        assert fp.count_patterns(_mbg, A.x, A.mw, A.mb) == \
            {"matmul_bias_gelu": 1}
        assert fp.count_patterns(_mbg_erf, A.x, A.mw, A.mb) == \
            {"matmul_bias_gelu": 1}

    @pytest.mark.parametrize("causal", [False, True])
    def test_attention_block(self, causal):
        assert fp.count_patterns(
            lambda q, k, v: _attn(q, k, v, causal), A.q, A.k, A.v) == \
            {"attention_block": 1}

    def test_mbg_claims_dot_before_ln_epilogue(self):
        # LN → matmul → gelu: the gelu cluster owns the dot, the LN
        # stays a bare layer_norm instead of ln_matmul (priority order)
        def f(x, w, b, mw, mb):
            return _gelu_tanh(
                jax.lax.dot_general(_ln(x, w, b), mw, DOT2) + mb)
        assert fp.count_patterns(f, A.x, A.w, A.b, A.mw, A.mb) == \
            {"layer_norm": 1, "matmul_bias_gelu": 1}


# ---------------------------------------------------------------------------
# matchers: AMP-style bf16 graphs (per-site converts, f32 stats island,
# bf16-rounded gelu literals, cast-wrapped softmax island)
# ---------------------------------------------------------------------------
class TestMatchersAMP:

    def test_amp_layer_norm(self):
        def f(x, w, b):
            m = jnp.mean(x.astype(jnp.float32), axis=-1, keepdims=True)
            v = jnp.var(x.astype(jnp.float32), axis=-1, keepdims=True)
            y = (x.astype(jnp.float32) - m) * \
                jax.lax.rsqrt(v + jnp.float32(1e-5))
            return y.astype(jnp.bfloat16) * w + b  # affine back in bf16
        xb = A.x.astype(jnp.bfloat16)
        assert fp.count_patterns(f, xb, A.w.astype(jnp.bfloat16),
                                 A.b.astype(jnp.bfloat16)) == \
            {"layer_norm": 1}

    def test_amp_gelu_rounded_literals(self):
        # bf16 graphs store sqrt(2/pi) as 0.796875 and the cubic
        # coefficient as 0.0446777 — _coef_close must accept both
        def f(x, w, b):
            z = jax.lax.dot_general(
                x, w, DOT2, preferred_element_type=jnp.bfloat16) + b
            return (jnp.bfloat16(0.5) * (jnp.bfloat16(1.0) + jnp.tanh(
                jnp.bfloat16(0.796875) *
                (z + jnp.bfloat16(0.0446777) * z ** 3))) * z)
        assert fp.count_patterns(
            f, A.x.astype(jnp.bfloat16), A.mw.astype(jnp.bfloat16),
            A.mb.astype(jnp.bfloat16)) == {"matmul_bias_gelu": 1}

    def test_amp_attention_cast_wrapped_softmax(self):
        def f(q, k, v):
            s = jax.lax.dot_general(
                q, k, QK, preferred_element_type=jnp.bfloat16)
            s = s.astype(jnp.float32) * 0.125
            p = jax.nn.softmax(s, axis=-1)
            return jax.lax.dot_general(p.astype(jnp.bfloat16), v, PV)
        qb, kb, vb = (t.astype(jnp.bfloat16) for t in (A.q, A.k, A.v))
        assert fp.count_patterns(f, qb, kb, vb) == {"attention_block": 1}

    def test_amp_rewrite_parity_exact(self):
        # the XLA mirror replays the convert placement of the matched
        # soup, so CPU fallback output is bit-identical
        def f(x, w, b):
            m = jnp.mean(x.astype(jnp.float32), axis=-1, keepdims=True)
            v = jnp.var(x.astype(jnp.float32), axis=-1, keepdims=True)
            y = (x.astype(jnp.float32) - m) * \
                jax.lax.rsqrt(v + jnp.float32(1e-5))
            return y.astype(jnp.bfloat16) * w + b
        xb = A.x.astype(jnp.bfloat16)
        wb = A.w.astype(jnp.bfloat16)
        bb = A.b.astype(jnp.bfloat16)
        base = f(xb, wb, bb)
        fused = fp.wrap(f)(xb, wb, bb)
        assert fp.summary()["rewrites"] == {"layer_norm": 1}
        np.testing.assert_array_equal(np.asarray(base), np.asarray(fused))


# ---------------------------------------------------------------------------
# near-misses must NOT match
# ---------------------------------------------------------------------------
class TestNearMisses:

    def test_var_with_ddof_not_layer_norm(self):
        def f(x, w, b):
            m = jnp.mean(x, axis=-1, keepdims=True)
            v = jnp.var(x, axis=-1, keepdims=True, ddof=1)
            return (x - m) * jax.lax.rsqrt(v + 1e-5) * w + b
        assert fp.count_patterns(f, A.x, A.w, A.b) == {}

    def test_escaping_interior_not_matched(self):
        # the mean escapes the cluster as a second output → not closed
        def f(x, w, b):
            m = jnp.mean(x, axis=-1, keepdims=True)
            v = jnp.var(x, axis=-1, keepdims=True)
            return (x - m) * jax.lax.rsqrt(v + 1e-5) * w + b, m
        assert fp.count_patterns(f, A.x, A.w, A.b) == {}

    def test_wrong_gelu_coefficient_not_matched(self):
        # 0.06 is outside the 1% reduced-precision tolerance on 0.044715
        def f(x, w, b):
            z = jax.lax.dot_general(x, w, DOT2) + b
            return 0.5 * (1.0 + jnp.tanh(0.7978845608028654 *
                                         (z + 0.06 * z ** 3))) * z
        assert fp.count_patterns(f, A.x, A.mw, A.mb) == {}

    def test_op_between_softmax_and_pv_not_matched(self):
        # dropout (here: any op on the probabilities) breaks the block
        def f(q, k, v):
            s = jax.lax.dot_general(q, k, QK) * 0.125
            p = jax.nn.softmax(s, axis=-1) * 0.9
            return jax.lax.dot_general(p, v, PV)
        assert fp.count_patterns(f, A.q, A.k, A.v) == {}

    def test_mean_over_wrong_axis_not_matched(self):
        def f(x, w, b):
            m = jnp.mean(x, axis=0, keepdims=True)
            v = jnp.var(x, axis=-1, keepdims=True)
            return (x - m) * jax.lax.rsqrt(v + 1e-5) * w + b
        assert fp.count_patterns(f, A.x, A.w, A.b) == {}


# ---------------------------------------------------------------------------
# rewritten vs unrewritten parity (CPU: every cluster dispatches to the
# inline XLA mirror, reason not_tpu)
# ---------------------------------------------------------------------------
class TestRewriteParity:

    def _block(self, x, r, w, b, mw, mb):
        h = _mbg(_ln(x, w, b), mw, mb)            # ln + matmul_bias_gelu
        h = jax.lax.dot_general(h, mw.T, DOT2)    # back to width 32
        return _res_ln(h, r, w, b)                # residual_ln

    def test_forward_parity(self):
        args = (A.x, A.r, A.w, A.b, A.mw, A.mb)
        base = self._block(*args)
        fused = fp.wrap(self._block)(*args)
        s = fp.summary()
        assert s["rewrites"] == {"layer_norm": 1, "matmul_bias_gelu": 1,
                                 "residual_ln": 1}
        assert all(k.endswith(":not_tpu")
                   for k in s["fallbacks"])
        assert float(jnp.max(jnp.abs(base - fused))) <= 1e-5

    def test_grad_parity(self):
        def loss(fn, *args):
            return jnp.sum(fn(*args) ** 2)
        args = (A.x, A.r, A.w, A.b, A.mw, A.mb)
        g0 = jax.grad(lambda *a: loss(self._block, *a),
                      argnums=(0, 1, 4))(*args)
        g1 = jax.grad(lambda *a: loss(fp.wrap(self._block), *a),
                      argnums=(0, 1, 4))(*args)
        for a, b in zip(g0, g1):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-5)

    def test_attention_parity_fwd_and_grad(self):
        f = lambda q, k, v: _attn(q, k, v, causal=True)
        base = f(A.q, A.k, A.v)
        fused = fp.wrap(f)(A.q, A.k, A.v)
        assert fp.summary()["rewrites"] == {"attention_block": 1}
        assert float(jnp.max(jnp.abs(base - fused))) <= 1e-5
        g0 = jax.grad(lambda q: jnp.sum(f(q, A.k, A.v) ** 2))(A.q)
        g1 = jax.grad(
            lambda q: jnp.sum(fp.wrap(f)(q, A.k, A.v) ** 2))(A.q)
        np.testing.assert_allclose(np.asarray(g0), np.asarray(g1),
                                   rtol=1e-5, atol=1e-5)

    def test_wrap_composes_with_jit(self):
        args = (A.x, A.r, A.w, A.b, A.mw, A.mb)
        base = self._block(*args)
        fused = jax.jit(fp.wrap(self._block))(*args)
        assert float(jnp.max(jnp.abs(base - fused))) <= 1e-5


# ---------------------------------------------------------------------------
# env gates
# ---------------------------------------------------------------------------
class TestEnvGates:

    def test_kill_switch(self, monkeypatch):
        monkeypatch.setenv("PT_FUSION_PASS", "0")
        out = fp.wrap(_ln)(A.x, A.w, A.b)
        assert fp.summary()["rewrites"] == {}
        assert fp.summary()["traces"] == 0
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(_ln(A.x, A.w, A.b)))

    def test_per_pattern_opt_out(self, monkeypatch):
        monkeypatch.setenv("PT_FUSION_DISABLE", "layer_norm,residual_ln")
        assert fp.count_patterns(_ln, A.x, A.w, A.b) == {}
        assert fp.count_patterns(_res_ln, A.x, A.r, A.w, A.b) == {}
        # other patterns stay live
        assert fp.count_patterns(_mbg, A.x, A.mw, A.mb) == \
            {"matmul_bias_gelu": 1}

    def test_opt_out_through_wrap(self, monkeypatch):
        monkeypatch.setenv("PT_FUSION_DISABLE", "matmul_bias_gelu")
        fp.wrap(_mbg)(A.x, A.mw, A.mb)
        assert fp.summary()["rewrites"] == {}


# ---------------------------------------------------------------------------
# telemetry counters
# ---------------------------------------------------------------------------
class TestTelemetry:

    def test_rewrite_and_fallback_counted(self):
        from paddle_tpu.observability import get_telemetry
        tel = get_telemetry()
        before = tel.snapshot()["fusion"]
        fp.wrap(_ln)(A.x, A.w, A.b)
        after = tel.snapshot()["fusion"]
        assert after["rewrites"].get("layer_norm", 0) == \
            before["rewrites"].get("layer_norm", 0) + 1
        key = "layer_norm:not_tpu"
        assert after["fallbacks"].get(key, 0) == \
            before["fallbacks"].get(key, 0) + 1


# ---------------------------------------------------------------------------
# capture integration: one compile, rewrites recorded on the entry
# ---------------------------------------------------------------------------
class TestCaptureIntegration:

    def test_exactly_one_compile_with_rewrites(self):
        import paddle_tpu as pt
        import paddle_tpu.nn as nn
        np.random.seed(0)
        pt.seed(0)
        ln = nn.LayerNorm(16)
        fc = nn.Linear(16, 16)

        @pt.jit.capture_step
        def step(x):
            return fc(ln(x))

        x = pt.to_tensor(np.random.randn(8, 16).astype(np.float32))
        outs = [np.asarray(step(x)._data) for _ in range(3)]
        assert step.stats["compiles"] == 1
        assert step.stats["hits"] >= 2
        assert step.stats["fusion_rewrites"] >= 1
        assert step.stats["fusion_patterns"]
        eager = np.asarray(fc(ln(x))._data)
        np.testing.assert_allclose(outs[0], eager, rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(outs[0], outs[-1])


# ---------------------------------------------------------------------------
# block kernels: bf16 in, f32 accumulation (interpret mode)
# ---------------------------------------------------------------------------
class TestBlockKernelBf16:

    def test_ln_matmul_bf16(self):
        x = _rand((64, 96)).astype(jnp.bfloat16)
        w = _rand((96, 64), 1).astype(jnp.bfloat16)
        lw = _rand((96,), 2).astype(jnp.bfloat16)
        out = fk.fused_ln_matmul(x, w, lw, interpret=True)
        assert out.dtype == jnp.bfloat16
        ref = fk.ln_matmul_reference(x, w, lw)
        np.testing.assert_allclose(
            np.asarray(out.astype(jnp.float32)),
            np.asarray(ref.astype(jnp.float32)), **BF16_TOL)

    def test_matmul_bias_gelu_bf16(self):
        x = _rand((48, 64)).astype(jnp.bfloat16)
        w = _rand((64, 96), 1).astype(jnp.bfloat16)
        b = _rand((96,), 2).astype(jnp.bfloat16)
        out = fk.fused_matmul_bias_gelu(x, w, b, interpret=True)
        assert out.dtype == jnp.bfloat16
        ref = fk.matmul_bias_gelu_reference(x, w, b)
        np.testing.assert_allclose(
            np.asarray(out.astype(jnp.float32)),
            np.asarray(ref.astype(jnp.float32)), **BF16_TOL)

    def test_attention_block_bf16(self):
        q = _rand((1, 2, 32, 16)).astype(jnp.bfloat16)
        k = _rand((1, 2, 32, 16), 1).astype(jnp.bfloat16)
        v = _rand((1, 2, 32, 16), 2).astype(jnp.bfloat16)
        out = fk.fused_attention_block(q, k, v, causal=True,
                                       interpret=True)
        assert out.dtype == jnp.bfloat16
        ref = fk.attention_block_reference(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out.astype(jnp.float32)),
            np.asarray(ref.astype(jnp.float32)), **BF16_TOL)


# ---------------------------------------------------------------------------
# autotuner: generated candidates, prune-before-time, schema bump
# ---------------------------------------------------------------------------
class TestCandidateGeneration:

    @pytest.fixture(autouse=True)
    def _clean_tuner(self):
        at.cache_clear()
        yield
        at.cache_clear()

    @staticmethod
    def _axes():
        return [("tile", 512, 8), ("tile", 512, 128), ("choice", (1, 0))]

    @staticmethod
    def _cost(cfg):
        br, bn, _par = cfg
        return {"flops": 1e6, "bytes": float(br * bn),
                "vmem_bytes": float(br * bn * 4),
                "mxu_underfill": br < 8 or bn < 128}

    def test_generates_from_axes_and_prunes(self):
        limit = 256 * 1024
        cands = at.generate_candidates(self._axes(), self._cost,
                                       vmem_limit=limit,
                                       max_candidates=5)
        assert 1 <= len(cands) <= 5
        for br, bn, par in cands:
            # every survivor is axis-derived (aligned pow-2 walk) and
            # inside the vmem budget
            assert br in (8, 16, 32, 64, 128, 256, 512)
            assert bn in (128, 256, 512)
            assert par in (1, 0)
            assert br * bn * 4 <= limit

    def test_all_pruned_raises(self):
        with pytest.raises(RuntimeError):
            at.generate_candidates(self._axes(), self._cost, vmem_limit=1)

    def test_search_never_times_pruned_configs(self):
        cands = at.generate_candidates(self._axes(), self._cost,
                                       vmem_limit=64 * 1024,
                                       max_candidates=32)
        timed = []

        def run(cfg):
            timed.append(cfg)
            assert self._cost(cfg)["vmem_bytes"] <= 64 * 1024

        at.search("fused_ln_matmul", ("gen", 1), run, cands,
                  cost=self._cost, vmem_limit=64 * 1024,
                  warmup=0, iters=1)
        assert timed and all(c[0] * c[1] * 4 <= 64 * 1024 for c in timed)

    def test_tune_ln_matmul_generates_and_caches(self):
        x = _rand((64, 96))
        w = _rand((96, 64), 1)
        best, timings = fk.tune_ln_matmul(x, w, interpret=True)
        assert timings                 # searched (configs were timed)
        best2, t2 = fk.tune_ln_matmul(x, w, interpret=True)
        assert tuple(best2) == tuple(best) and t2 == {}


class TestSchemaBump:

    @pytest.fixture(autouse=True)
    def _restore_schema(self):
        at.cache_clear()
        orig = dict(at.KERNEL_SCHEMA)
        yield
        at.KERNEL_SCHEMA.clear()
        at.KERNEL_SCHEMA.update(orig)
        at.cache_clear()

    def test_bump_invalidates_then_reloads_without_research(self, tmp_path):
        key = (64, 96, 64, "float32", True)
        path = str(tmp_path / "tune.json")
        timed = []

        def run(cfg):
            timed.append(cfg)

        def cost(cfg):
            return {"flops": 1.0, "bytes": 1.0, "vmem_bytes": 0.0}

        cands = [(128, 128, 1), (256, 256, 1)]
        os.environ["PT_AUTOTUNE_CACHE"] = path
        try:
            at.search("fused_ln_matmul", key, run, cands, cost=cost,
                      warmup=0, iters=1)
            n_first = len(timed)
            assert n_first >= 2        # both survivors timed

            # a kernel-layout change bumps the schema: every entry
            # written under the old version becomes invisible
            at.bump_schema("fused_ln_matmul")
            assert at.cache_get("fused_ln_matmul", key) is None
            at.cache_clear()
            at.load_cache(path)        # stale entries dropped on load
            assert at.cache_get("fused_ln_matmul", key) is None

            # re-search under the new schema, then reload in a clean
            # cache: the bumped entry answers without re-searching
            at.search("fused_ln_matmul", key, run, cands, cost=cost,
                      warmup=0, iters=1)
            n_second = len(timed)
            assert n_second > n_first
            at.cache_clear()
            at.load_cache(path)
            _, timings = at.search("fused_ln_matmul", key, run, cands,
                                   cost=cost, warmup=0, iters=1)
            assert timings == {}       # pure cache hit across the bump
            assert len(timed) == n_second
        finally:
            os.environ.pop("PT_AUTOTUNE_CACHE", None)
