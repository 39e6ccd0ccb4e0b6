"""Quantization tests (ref: test/quantization/ test_quant_aware /
test_ptq)."""
from __future__ import annotations

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import quantization as Q


def _model():
    pt.seed(3)
    return pt.nn.Sequential(
        pt.nn.Linear(8, 16), pt.nn.ReLU(), pt.nn.Linear(16, 4))


class TestFakeQuant:
    def test_quant_dequant_levels(self):
        x = np.linspace(-1, 1, 101).astype(np.float32)
        out = Q.quant_dequant(pt.to_tensor(x), scale=1.0,
                              bit_length=8).numpy()
        # 8-bit symmetric: values land on k/127 grid
        np.testing.assert_allclose(out * 127, np.round(out * 127),
                                   atol=1e-4)
        assert np.abs(out - x).max() <= 1 / 127 + 1e-6

    def test_straight_through_gradient(self):
        x = pt.to_tensor(np.array([0.3, -0.7], np.float32),
                         stop_gradient=False)
        y = Q.quant_dequant(x, scale=1.0, bit_length=8)
        y.sum().backward()
        np.testing.assert_allclose(x.grad.numpy(), np.ones(2))

    def test_per_channel(self):
        x = np.stack([np.full(4, 0.5), np.full(4, 5.0)]).astype(np.float32)
        out = Q.quant_dequant(pt.to_tensor(x),
                              scale=np.array([0.5, 5.0], np.float32),
                              bit_length=8, channel_axis=0).numpy()
        np.testing.assert_allclose(out, x, rtol=1e-2)


class TestObservers:
    def test_absmax(self):
        obs = Q.AbsmaxObserver()
        obs.observe(pt.to_tensor(np.array([1.0, -3.0], np.float32)))
        obs.observe(pt.to_tensor(np.array([2.0], np.float32)))
        assert obs.scales() == 3.0

    def test_moving_average(self):
        obs = Q.MovingAverageAbsmaxObserver(moving_rate=0.5)
        obs.observe(pt.to_tensor(np.array([4.0], np.float32)))
        obs.observe(pt.to_tensor(np.array([2.0], np.float32)))
        assert obs.scales() == pytest.approx(3.0)

    def test_per_channel_absmax(self):
        obs = Q.PerChannelAbsmaxObserver(quant_axis_=0)
        obs.observe(pt.to_tensor(np.array([[1., -2.], [3., 0.5]],
                                          np.float32)))
        np.testing.assert_allclose(obs.scales(), [2.0, 3.0])

    def test_hist_percentile(self):
        obs = Q.HistObserver(percentile=0.5)
        obs.observe(pt.to_tensor(np.linspace(0, 10, 1001,
                                             dtype=np.float32)))
        assert 4.0 < obs.scales() < 6.0  # median magnitude ≈ 5


class TestQAT:
    @pytest.mark.slow
    def test_quantize_wraps_and_trains(self):
        model = _model()
        cfg = Q.QuantConfig(
            activation=Q.FakeQuanterWithAbsMaxObserver(),
            weight=Q.FakeQuanterWithAbsMaxObserver())
        qat = Q.QAT(cfg)
        qmodel = qat.quantize(model, inplace=True)
        kinds = [type(l).__name__ for l in qmodel.sublayers()]
        assert kinds.count("QuantedLinear") == 2
        # trains end-to-end with STE gradients
        opt = pt.optimizer.SGD(learning_rate=0.1,
                               parameters=qmodel.parameters())
        X = np.random.RandomState(0).randn(32, 8).astype(np.float32)
        Y = np.random.RandomState(1).randint(0, 4, 32)
        losses = []
        for _ in range(15):
            loss = pt.nn.CrossEntropyLoss()(qmodel(pt.to_tensor(X)),
                                            pt.to_tensor(Y))
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss.numpy()))
        assert losses[-1] < losses[0]

    def test_convert_folds_scales(self):
        model = _model()
        cfg = Q.QuantConfig(
            activation=Q.FakeQuanterWithAbsMaxObserver(),
            weight=Q.FakeQuanterWithAbsMaxObserver())
        qat = Q.QAT(cfg)
        qmodel = qat.quantize(model, inplace=True)
        X = np.random.RandomState(0).randn(4, 8).astype(np.float32)
        qmodel(pt.to_tensor(X))  # calibrate
        deployed = qat.convert(qmodel, inplace=True)
        kinds = [type(l).__name__ for l in deployed.sublayers()]
        assert "QuantedLinear" not in kinds
        lin = [l for l in deployed.sublayers()
               if type(l).__name__ == "Linear"][0]
        assert hasattr(lin, "quant_scale")
        # folded weights lie on the int8 grid for their scale
        w = lin.weight.numpy()
        s = np.abs(w).max()
        grid = np.round(w / s * 127)
        np.testing.assert_allclose(w, grid * s / 127, atol=1e-6)


class TestConfigTargeting:
    def test_layer_config_survives_deepcopy(self):
        model = _model()
        cfg = Q.QuantConfig()
        cfg.add_layer_config(model[0],
                             activation=Q.FakeQuanterWithAbsMaxObserver(),
                             weight=Q.FakeQuanterWithAbsMaxObserver())
        qmodel = Q.QAT(cfg).quantize(model)  # inplace=False → deepcopy
        kinds = [type(l).__name__ for l in qmodel.sublayers()]
        assert kinds.count("QuantedLinear") == 1
        # original untouched
        assert all(type(l).__name__ != "QuantedLinear"
                   for l in model.sublayers())

    def test_type_config(self):
        model = _model()
        cfg = Q.QuantConfig()
        cfg.add_type_config(pt.nn.Linear,
                            weight=Q.FakeQuanterWithAbsMaxObserver())
        qmodel = Q.QAT(cfg).quantize(model, inplace=True)
        kinds = [type(l).__name__ for l in qmodel.sublayers()]
        assert kinds.count("QuantedLinear") == 2

    def test_hist_observer_range_growth(self):
        obs = Q.HistObserver(percentile=0.99)
        # batch of small values, then one big outlier batch
        obs.observe(pt.to_tensor(np.full(1000, 0.99, np.float32)))
        obs.observe(pt.to_tensor(np.array([10.0], np.float32)))
        # 99th percentile of {1000×0.99, 1×10.0} must stay near 1, not 10
        assert obs.scales() < 2.0


class TestPTQ:
    def test_nested_layers_observed(self):
        pt.seed(0)
        model = pt.nn.Sequential(
            pt.nn.Sequential(pt.nn.Linear(4, 8), pt.nn.ReLU()),
            pt.nn.Linear(8, 2))
        cfg = Q.QuantConfig(activation=Q.AbsmaxObserver(),
                            weight=Q.AbsmaxObserver())
        qmodel = Q.PTQ(cfg).quantize(model, inplace=True)
        kinds = [type(l).__name__ for l in qmodel.sublayers()]
        assert kinds.count("_ObservedLayer") == 2  # both Linears, not the
        # container
        qmodel(pt.to_tensor(np.ones((2, 4), np.float32)))
        deployed = Q.PTQ(cfg).convert(qmodel, inplace=True)
        linears = [l for l in deployed.sublayers()
                   if type(l).__name__ == "Linear"]
        assert all(hasattr(l, "quant_scale") for l in linears)

    def test_calibrate_and_convert(self):
        model = _model()
        cfg = Q.QuantConfig(activation=Q.AbsmaxObserver(),
                            weight=Q.AbsmaxObserver())
        ptq = Q.PTQ(cfg)
        qmodel = ptq.quantize(model, inplace=True)
        rng = np.random.RandomState(0)
        ref_out = None
        for _ in range(4):
            X = rng.randn(16, 8).astype(np.float32)
            out = qmodel(pt.to_tensor(X))
        deployed = ptq.convert(qmodel, inplace=True)
        # deployed model output stays close to float model
        X = rng.randn(16, 8).astype(np.float32)
        got = deployed(pt.to_tensor(X)).numpy()
        want = _model()(pt.to_tensor(X)).numpy()  # same seed -> same init
        np.testing.assert_allclose(got, want, atol=0.15)


class TestObserverRoundTrip:
    """Observer-driven fake-quant round-trips: scale SHAPES (per-tensor
    scalar vs per-channel vector), the symmetric zero-point-free
    contract, bf16 inputs, and zero-input degeneracy."""

    def test_scale_shapes_per_tensor_vs_per_channel(self):
        x = np.random.RandomState(0).randn(4, 6).astype(np.float32)
        per_t = Q.AbsmaxObserver()
        per_t.observe(pt.to_tensor(x))
        assert np.ndim(per_t.scales()) == 0          # one scalar scale
        assert per_t.quant_axis() is None
        per_c = Q.PerChannelAbsmaxObserver(quant_axis_=1)
        per_c.observe(pt.to_tensor(x))
        s = np.asarray(per_c.scales())
        assert s.shape == (6,)                       # one scale per channel
        assert per_c.quant_axis() == 1
        np.testing.assert_allclose(s, np.abs(x).max(axis=0))

    def test_per_channel_roundtrip_beats_per_tensor(self):
        # channel magnitudes spanning 100x: the global absmax scale
        # wipes out the small channel, per-channel scales keep it
        rng = np.random.RandomState(1)
        x = (rng.randn(64, 3) * np.array([0.05, 1.0, 5.0])) \
            .astype(np.float32)
        per_c = Q.PerChannelAbsmaxObserver(quant_axis_=1)
        per_c.observe(pt.to_tensor(x))
        s = np.asarray(per_c.scales(), np.float32)
        out_c = Q.quant_dequant(pt.to_tensor(x), scale=s,
                                channel_axis=1).numpy()
        # round-to-nearest on each channel's k*s/127 grid: error <= s/254
        assert np.all(np.abs(out_c - x) <= s / 254 + 1e-7)
        per_t = Q.AbsmaxObserver()
        per_t.observe(pt.to_tensor(x))
        out_t = Q.quant_dequant(pt.to_tensor(x),
                                scale=float(per_t.scales())).numpy()
        small = np.abs(x[:, 0])
        assert np.abs(out_c[:, 0] - x[:, 0]).max() \
            < np.abs(out_t[:, 0] - x[:, 0]).max()
        assert small.max() > 0  # the comparison above was non-vacuous

    def test_symmetric_scheme_has_no_zero_point(self):
        # symmetric int8: zero maps to exactly zero and the grid is odd
        # (q(-x) == -q(x)) — there is no zero-point offset to carry
        x = np.array([0.0, 0.37, -0.37, 0.99, -0.99], np.float32)
        out = np.asarray(Q.quant_dequant(x, scale=1.0, bit_length=8))
        assert out[0] == 0.0
        np.testing.assert_allclose(out[1::2], -out[2::2])

    def test_bf16_inputs(self):
        x = np.random.RandomState(2).randn(8, 16).astype(np.float32)
        t = pt.to_tensor(x).astype("bfloat16")
        obs = Q.AbsmaxObserver()
        obs.observe(t)
        # bf16 rounds the input, so the scale matches within bf16 eps
        assert obs.scales() == pytest.approx(np.abs(x).max(), rel=0.01)
        per_c = Q.PerChannelAbsmaxObserver(quant_axis_=1)
        per_c.observe(t)
        assert np.asarray(per_c.scales()).shape == (16,)
        out = Q.quant_dequant(t, scale=float(obs.scales()), bit_length=8)
        assert "bfloat16" in str(out.dtype)          # dtype preserved
        step = float(obs.scales()) / 127
        np.testing.assert_allclose(
            np.asarray(out.numpy(), np.float32), x,
            atol=step / 2 + 0.01 * np.abs(x).max())  # grid + bf16 rounding

    def test_zero_input_degenerate(self):
        obs = Q.AbsmaxObserver()
        assert obs.scales() == pytest.approx(1e-9)   # never-observed floor
        obs.observe(pt.to_tensor(np.zeros(4, np.float32)))
        assert obs.scales() == 0.0
        out = np.asarray(Q.quant_dequant(np.zeros(4, np.float32),
                                         scale=obs.scales()))
        assert np.isfinite(out).all() and not out.any()


class TestQuantKernels:
    """ops/quant_kernels: the serve-side int8 pack/unpack + w8a16
    matmul (every raw quant-dtype cast in the tree lives there)."""

    def _wx(self):
        rng = np.random.RandomState(0)
        x = rng.randn(5, 16).astype(np.float32)
        w = (rng.randn(16, 8) * np.linspace(0.1, 4.0, 8)) \
            .astype(np.float32)
        return x, w

    def test_quantize_weight_shapes_dtypes_grid(self):
        from paddle_tpu.ops import quant_kernels as qk
        x, w = self._wx()
        q, s = qk.quantize_weight(w, axis=1)
        assert str(q.dtype) == "int8" and q.shape == w.shape
        assert s.shape == (8,) and str(s.dtype) == "float32"
        assert np.abs(np.asarray(q, np.int32)).max() <= 127
        deq = np.asarray(qk.dequantize_weight(q, s, axis=1))
        # round-to-nearest on each column's grid: error <= scale/2
        assert np.all(np.abs(deq - w) <= np.asarray(s)[None, :] / 2 + 1e-7)

    def test_quantize_weight_zero_channel(self):
        from paddle_tpu.ops import quant_kernels as qk
        w = np.zeros((4, 3), np.float32)
        w[:, 1] = [1.0, -2.0, 0.5, 0.0]
        q, s = qk.quantize_weight(w, axis=1)
        assert np.isfinite(np.asarray(s)).all()
        deq = np.asarray(qk.dequantize_weight(q, s, axis=1))
        assert not deq[:, 0].any() and not deq[:, 2].any()

    def test_quantize_kv_row_independent_and_roundtrip(self):
        from paddle_tpu.ops import quant_kernels as qk
        kv = np.random.RandomState(3).randn(6, 2, 16).astype(np.float32)
        qb, sb = qk.quantize_kv(kv)
        assert qb.shape == kv.shape and sb.shape == (6, 2)
        # a row's stored bytes must not depend on its batch neighbours
        # (the continuous-batching bit-identity contract at int8)
        q1, s1 = qk.quantize_kv(kv[3])
        assert np.array_equal(np.asarray(qb)[3], np.asarray(q1))
        assert np.array_equal(np.asarray(sb)[3], np.asarray(s1))
        deq = np.asarray(qk.dequantize_kv(qb, sb))
        assert np.all(np.abs(deq - kv) <= np.asarray(sb)[..., None] / 2
                      + 1e-7)

    def test_w8a16_matmul_reference_numerics(self):
        from paddle_tpu.ops import quant_kernels as qk
        x, w = self._wx()
        q, s = qk.quantize_weight(w, axis=1)
        got = np.asarray(qk.w8a16_matmul_reference(x, q, s))
        # (x @ q) * s is x @ dequant(q, s) up to f32 reassociation
        deq = np.asarray(qk.dequantize_weight(q, s, axis=1))
        np.testing.assert_allclose(got, x @ deq, atol=1e-4)
        # and within the analytic quant bound of the fp32 matmul
        bound = np.abs(x) @ np.ones_like(w) * (np.asarray(s) / 2)
        assert np.all(np.abs(got - x @ w) <= bound + 1e-5)

    def test_w8a16_pallas_interpret_bit_identical_to_mirror(self):
        """Bit-identical to the mirror on the tile the kernel computes
        (m and n padded to the 8 x 128 block).  Against the mirror at the
        unpadded width only the order of the K-term f32 sum may differ:
        XLA's CPU dot emitter sums a product narrower than a vector lane
        in another order (at n=8 it is the mirror that leaves the
        ascending fused-multiply-add order, the kernel keeps it)."""
        import jax.numpy as jnp
        from paddle_tpu.ops import quant_kernels as qk
        x, w = self._wx()          # m=5, n=8: both block pads exercised
        q, s = qk.quantize_weight(w, axis=1)
        out_p = np.asarray(qk.w8a16_matmul(jnp.asarray(x), q, s,
                                           use_pallas=True,
                                           interpret=True))
        assert out_p.shape == (5, 8)
        xp = jnp.pad(jnp.asarray(x), ((0, 3), (0, 0)))
        qp, sp = jnp.pad(q, ((0, 0), (0, 120))), jnp.pad(s, (0, 120))
        tile = np.asarray(qk.w8a16_matmul_reference(xp, qp, sp))
        assert np.array_equal(out_p, tile[:5, :8])
        assert not tile[5:].any() and not tile[:, 8:].any()
        out_r = np.asarray(qk.w8a16_matmul_reference(jnp.asarray(x), q, s))
        # each side is within K * 2^-24 of the exact sum of |x_k w_k|
        k = x.shape[1]
        bound = 2 * k * 2.0 ** -24 * (
            np.abs(x) @ np.abs(np.asarray(q, np.float32))) * np.asarray(s)
        assert np.all(np.abs(out_p - out_r) <= bound)

    def test_w8a16_bf16_activations(self):
        import jax.numpy as jnp
        from paddle_tpu.ops import quant_kernels as qk
        x, w = self._wx()
        q, s = qk.quantize_weight(w, axis=1)
        ref = np.asarray(qk.w8a16_matmul_reference(x, q, s))
        out = qk.w8a16_matmul_reference(jnp.asarray(x, jnp.bfloat16), q, s)
        assert str(out.dtype) == "bfloat16"          # "a16" half honoured
        rel = np.abs(np.asarray(out, np.float32) - ref).max() \
            / np.abs(ref).max()
        assert rel < 0.02                            # bf16 rounding only

    def test_kernel_schema_has_quant_entries(self):
        from paddle_tpu.ops.autotune import KERNEL_SCHEMA
        assert "w8a16_matmul" in KERNEL_SCHEMA
        assert "paged_attention_int8" in KERNEL_SCHEMA

    @staticmethod
    def _int8_pools(layers=2):
        """fp32 ``(L, P, ps, H*D)`` pools, their int8 twins and the
        ``(L, P, ps, H)`` scales, a null-padded table and a part-filled
        last page."""
        from paddle_tpu.ops import quant_kernels as qk
        rng = np.random.RandomState(4)
        P, ps, H, D = 5, 4, 2, 8
        kp = rng.randn(layers, P, ps, H, D).astype(np.float32)
        vp = rng.randn(layers, P, ps, H, D).astype(np.float32)
        kq, ks = qk.quantize_kv(kp)
        vq, vs = qk.quantize_kv(vp)
        flat = (layers, P, ps, H * D)
        qact = rng.randn(2, H, D).astype(np.float32)
        ptab = np.array([[4, 2], [3, 0]], np.int32)
        ln = np.array([7, 3], np.int32)
        return (qact, kp.reshape(flat), vp.reshape(flat),
                np.asarray(kq).reshape(flat), np.asarray(vq).reshape(flat),
                np.asarray(ks), np.asarray(vs), ptab, ln)

    @pytest.mark.parametrize("layer", [0, 1])
    def test_paged_attention_int8_matches_fp32_within_quant_tol(self, layer):
        from paddle_tpu.ops.paged_attention import (
            paged_attention_reference, paged_attention_int8,
            paged_attention_int8_reference)
        qact, kp, vp, kq, vq, ks, vs, ptab, ln = self._int8_pools()
        o32 = np.asarray(paged_attention_reference(qact, kp, vp, ptab, ln,
                                                   layer=layer))
        o8 = np.asarray(paged_attention_int8_reference(
            qact, kq, vq, ks, vs, ptab, ln, layer=layer))
        np.testing.assert_allclose(o8, o32, atol=0.05)
        # the CPU dispatcher must be the reference bit-for-bit — the
        # serve path's numerics definition off-TPU
        o8d = np.asarray(paged_attention_int8(qact, kq, vq, ks, vs,
                                              ptab, ln, layer=layer))
        assert np.array_equal(o8d, o8)

    @pytest.mark.parametrize("layer", [0, 1])
    def test_paged_attention_int8_kernel_reads_the_whole_pool(self, layer):
        """The Pallas kernel (interpret mode) on the whole int8 pools and
        one layer's scales against the XLA reference, with large finite
        garbage in every slot past a row's length."""
        from paddle_tpu.ops.paged_attention import (
            paged_attention_int8, paged_attention_int8_reference)
        qact, _, _, kq, vq, ks, vs, ptab, ln = self._int8_pools()
        ps = kq.shape[2]
        kq, vq, ks, vs = (a.copy() for a in (kq, vq, ks, vs))
        for r in range(ptab.shape[0]):
            for t in range(int(ln[r]), ptab.shape[1] * ps):
                page, slot = ptab[r, t // ps], t % ps
                kq[:, page, slot], vq[:, page, slot] = 127, -127
                ks[:, page, slot], vs[:, page, slot] = 1e4, 1e4
        ref = np.asarray(paged_attention_int8_reference(
            qact, kq, vq, ks, vs, ptab, ln, layer=layer))
        out = np.asarray(paged_attention_int8(
            qact, kq, vq, ks, vs, ptab, ln, layer=layer, use_pallas=True,
            interpret=True))
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
