"""Fused Pallas kernels (layernorm, softmax-xent) + the search-based
autotuner: interpret-mode parity vs pure-jnp references, framework
dispatch (flag on → fused, ineligible → clean XLA fallback), cost-model
pruning, cache persistence with stale-key invalidation, and
cross-process reload via PT_AUTOTUNE_CACHE.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import autotune as at
from paddle_tpu.ops import fused_kernels as fk

FWD_TOL = dict(rtol=1e-5, atol=1e-5)
GRAD_TOL = dict(rtol=1e-4, atol=1e-4)


@pytest.fixture(autouse=True)
def _clean_tuner():
    at.cache_clear()
    enabled = at.enabled()
    yield
    at.cache_clear()
    at.set_enabled(enabled)


def _rand(shape, seed=0, dtype=np.float32):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape)
                       .astype(dtype))


# ---------------------------------------------------------------------------
# fused layernorm parity
# ---------------------------------------------------------------------------
class TestFusedLayerNorm:

    # ragged rows/features that don't divide the (block_rows, 128) tile
    @pytest.mark.parametrize("rows,d", [(8, 128), (37, 193), (130, 96),
                                        (256, 640), (5, 515)])
    def test_forward_parity(self, rows, d):
        x = _rand((rows, d))
        w = _rand((d,), 1)
        b = _rand((d,), 2)
        out = fk.fused_layer_norm(x, w, b, interpret=True)
        ref = fk.layer_norm_reference(x, w, b)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   **FWD_TOL)

    def test_forward_no_affine_and_residual(self):
        x = _rand((33, 257))
        res = _rand((33, 257), 7)
        out = fk.fused_layer_norm(x, residual=res, interpret=True)
        ref = fk.layer_norm_reference(x, residual=res)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   **FWD_TOL)

    def test_grad_parity_full(self):
        x, res = _rand((37, 193), 0), _rand((37, 193), 3)
        w, b = _rand((193,), 1), _rand((193,), 2)

        def f(fn):
            return lambda x, w, b, r: jnp.sum(
                jnp.sin(fn(x, w, b, residual=r)))

        g1 = jax.grad(f(lambda *a, **k: fk.fused_layer_norm(
            *a, **k, interpret=True)), argnums=(0, 1, 2, 3))(x, w, b, res)
        g2 = jax.grad(f(fk.layer_norm_reference),
                      argnums=(0, 1, 2, 3))(x, w, b, res)
        for got, want in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       **GRAD_TOL)

    def test_grad_parity_no_affine(self):
        x = _rand((29, 130))
        g1 = jax.grad(lambda a: jnp.sum(jnp.cos(
            fk.fused_layer_norm(a, interpret=True))))(x)
        g2 = jax.grad(lambda a: jnp.sum(jnp.cos(
            fk.layer_norm_reference(a))))(x)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   **GRAD_TOL)

    def test_bf16_in_f32_accumulate(self):
        # bf16 inputs, f32 stats: the fused output must match the f32
        # reference computed from the SAME bf16 inputs to bf16 noise
        x = _rand((64, 256)).astype(jnp.bfloat16)
        w = _rand((256,), 1).astype(jnp.bfloat16)
        out = fk.fused_layer_norm(x, w, interpret=True)
        assert out.dtype == jnp.bfloat16
        ref = fk.layer_norm_reference(x, w)
        np.testing.assert_allclose(
            np.asarray(out.astype(jnp.float32)),
            np.asarray(ref.astype(jnp.float32)), rtol=3e-2, atol=3e-2)

    def test_explicit_block_config(self):
        x = _rand((100, 100))
        for br, par in ((8, True), (64, False), (1024, True)):
            out = fk.fused_layer_norm(x, block_rows=br, parallel=par,
                                      interpret=True)
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(fk.layer_norm_reference(x)),
                **FWD_TOL)

    def test_residual_grad_parity_bf16(self):
        # the in-kernel add: bf16 in and out, f32 stats and gradients
        # accumulated in f32, against the reference on the same inputs
        bf = jnp.bfloat16
        x, res = _rand((48, 256), 0).astype(bf), _rand((48, 256), 3).astype(bf)
        w, b = _rand((256,), 1).astype(bf), _rand((256,), 2).astype(bf)

        def f(fn):
            return lambda x, w, b, r: jnp.sum(jnp.sin(
                fn(x, w, b, residual=r).astype(jnp.float32)))

        g1 = jax.grad(f(lambda *a, **k: fk.fused_layer_norm(
            *a, **k, interpret=True)), argnums=(0, 1, 2, 3))(x, w, b, res)
        g2 = jax.grad(f(fk.layer_norm_reference),
                      argnums=(0, 1, 2, 3))(x, w, b, res)
        for got, want in zip(g1, g2):
            assert got.dtype == bf
            np.testing.assert_allclose(
                np.asarray(got.astype(jnp.float32)),
                np.asarray(want.astype(jnp.float32)), rtol=5e-2, atol=5e-2)


# ---------------------------------------------------------------------------
# fused softmax cross-entropy parity
# ---------------------------------------------------------------------------
class TestFusedSoftmaxXent:

    @pytest.mark.parametrize("rows,V", [(8, 128), (29, 517), (64, 1024),
                                        (7, 90)])
    def test_forward_parity(self, rows, V):
        logits = _rand((rows, V))
        lab = jnp.asarray(np.random.RandomState(1).randint(
            0, V, rows).astype(np.int32))
        out = fk.fused_softmax_xent(logits, lab, interpret=True)
        ref = fk.softmax_xent_reference(logits, lab)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   **FWD_TOL)

    def test_ignore_index(self):
        logits = _rand((31, 200))
        lab = np.random.RandomState(1).randint(0, 200, 31).astype(np.int32)
        lab[[0, 7, 30]] = -100
        lab = jnp.asarray(lab)
        out = fk.fused_softmax_xent(logits, lab, interpret=True)
        ref = fk.softmax_xent_reference(logits, lab)
        assert float(out[0]) == 0.0 and float(out[7]) == 0.0
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   **FWD_TOL)

    def test_label_smoothing_fwd_and_grad(self):
        logits = _rand((29, 517))
        lab = np.random.RandomState(1).randint(0, 517, 29).astype(np.int32)
        lab[3] = -100
        lab = jnp.asarray(lab)
        out = fk.fused_softmax_xent(logits, lab, label_smoothing=0.1,
                                    interpret=True)
        ref = fk.softmax_xent_reference(logits, lab, label_smoothing=0.1)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   **FWD_TOL)
        g1 = jax.grad(lambda l: jnp.sum(fk.fused_softmax_xent(
            l, lab, label_smoothing=0.1, interpret=True)))(logits)
        g2 = jax.grad(lambda l: jnp.sum(fk.softmax_xent_reference(
            l, lab, label_smoothing=0.1)))(logits)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   **GRAD_TOL)

    def test_grad_is_softmax_minus_onehot(self):
        # weighted per-row cotangents exercise the bwd kernel's gloss
        # broadcast, not just sum()
        logits = _rand((16, 384))
        lab = jnp.asarray(np.random.RandomState(2).randint(
            0, 384, 16).astype(np.int32))
        wrow = jnp.arange(16, dtype=jnp.float32)
        g1 = jax.grad(lambda l: jnp.sum(fk.fused_softmax_xent(
            l, lab, interpret=True) * wrow))(logits)
        g2 = jax.grad(lambda l: jnp.sum(fk.softmax_xent_reference(
            l, lab) * wrow))(logits)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   **GRAD_TOL)

    def test_bf16_logits_f32_loss(self):
        logits = _rand((24, 300)).astype(jnp.bfloat16)
        lab = jnp.asarray(np.random.RandomState(3).randint(
            0, 300, 24).astype(np.int32))
        out = fk.fused_softmax_xent(logits, lab, interpret=True)
        assert out.dtype == jnp.float32
        ref = fk.softmax_xent_reference(logits, lab)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-2, atol=1e-2)

    def test_multi_vocab_tiles(self):
        # force the online logsumexp across several vocab tiles
        logits = _rand((9, 1500))
        lab = jnp.asarray(np.random.RandomState(4).randint(
            0, 1500, 9).astype(np.int32))
        out = fk.fused_softmax_xent(logits, lab, block_v=256,
                                    block_rows=8, interpret=True)
        ref = fk.softmax_xent_reference(logits, lab)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   **FWD_TOL)


# ---------------------------------------------------------------------------
# framework dispatch (flag + platform gate)
# ---------------------------------------------------------------------------
def _force_cpu_dispatch(monkeypatch):
    """Force the TPU-only gate open on CPU: the platform predicate is
    patched while the kernels stay in interpret mode (the tests'
    stand-in for real hardware)."""
    from paddle_tpu.framework import device
    monkeypatch.setattr(device, "on_tpu", lambda: True)
    monkeypatch.setattr(fk, "_interpret_default", lambda: True)


@pytest.fixture
def fresh_metrics():
    from paddle_tpu.observability.metrics import get_registry, \
        reset_registry
    from paddle_tpu.observability.telemetry import get_telemetry
    tel = get_telemetry()
    prev = tel.enabled
    tel.enabled = True  # counters gate on this; no watcher/server needed
    reset_registry()
    yield get_registry()
    reset_registry()
    tel.enabled = prev


class TestDispatch:

    def test_mesh_rule_mirrors_what_mosaic_can_lower(self, monkeypatch):
        """GSPMD cannot partition a Mosaic kernel (jax refuses the
        lowering), so dispatch selects Pallas only in single-device
        programs and in shard_map bodies manual over every mesh axis."""
        from jax.sharding import Mesh, PartitionSpec as P
        from paddle_tpu.framework import device
        monkeypatch.setattr(device, "on_tpu", lambda: True)
        seen = {}

        def note(tag, x):
            seen[tag] = device.pallas_dispatch()
            return x

        x = jnp.ones(4)
        assert device.pallas_dispatch()            # no mesh at all
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "mp"))
        with jax.set_mesh(mesh):
            jax.jit(lambda a: note("gspmd", a))(x)
            jax.jit(jax.shard_map(lambda a: note("manual", a), mesh=mesh,
                                  in_specs=P("dp"), out_specs=P("dp")))(x)
            jax.jit(jax.shard_map(lambda a: note("partial", a), mesh=mesh,
                                  in_specs=P("dp"), out_specs=P("dp"),
                                  axis_names={"dp"}))(x)
        one = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("dp", "mp"))
        with jax.set_mesh(one):
            jax.jit(lambda a: note("one_device", a))(x)
        assert seen == {"gspmd": False, "manual": True, "partial": False,
                        "one_device": True}

    def test_layer_norm_picks_up_fused(self, monkeypatch, fresh_metrics):
        import paddle_tpu as pt
        import paddle_tpu.nn.functional as F
        import paddle_tpu.framework.flags as flags
        _force_cpu_dispatch(monkeypatch)
        x_np = np.random.RandomState(0).randn(4, 16, 96).astype(np.float32)
        w_np = np.random.RandomState(1).randn(96).astype(np.float32)
        x = pt.to_tensor(x_np, stop_gradient=False)
        w = pt.to_tensor(w_np, stop_gradient=False)
        fused = F.layer_norm(x, 96, weight=w)
        fused.sum().backward()
        assert np.isfinite(x.grad.numpy()).all()
        c = fresh_metrics.counter("pt_pallas_calls_total",
                                  labelnames=("kernel", "path"))
        assert c.value(kernel="fused_layer_norm", path="pallas") >= 1

        flags.set_flags({"use_pallas_kernels": False})
        try:
            ref = F.layer_norm(pt.to_tensor(x_np), 96,
                               weight=pt.to_tensor(w_np))
        finally:
            flags.set_flags({"use_pallas_kernels": True})
        np.testing.assert_allclose(fused.numpy(), ref.numpy(),
                                   rtol=1e-5, atol=1e-5)
        assert c.value(kernel="fused_layer_norm", path="fallback") >= 1

    def test_cross_entropy_picks_up_fused(self, monkeypatch,
                                          fresh_metrics):
        import paddle_tpu as pt
        import paddle_tpu.nn.functional as F
        import paddle_tpu.framework.flags as flags
        _force_cpu_dispatch(monkeypatch)
        rng = np.random.RandomState(0)
        logits_np = rng.randn(8, 12, 257).astype(np.float32)
        lab_np = rng.randint(0, 257, size=(8, 12)).astype(np.int64)
        lab_np[0, :3] = -100
        logits = pt.to_tensor(logits_np, stop_gradient=False)
        fused = F.cross_entropy(logits, pt.to_tensor(lab_np),
                                ignore_index=-100, label_smoothing=0.1)
        fused.backward()
        g_fused = logits.grad.numpy()
        c = fresh_metrics.counter("pt_pallas_calls_total",
                                  labelnames=("kernel", "path"))
        assert c.value(kernel="fused_softmax_xent", path="pallas") >= 1

        flags.set_flags({"use_pallas_kernels": False})
        try:
            logits2 = pt.to_tensor(logits_np, stop_gradient=False)
            ref = F.cross_entropy(logits2, pt.to_tensor(lab_np),
                                  ignore_index=-100, label_smoothing=0.1)
            ref.backward()
        finally:
            flags.set_flags({"use_pallas_kernels": True})
        np.testing.assert_allclose(float(fused.numpy()),
                                   float(ref.numpy()), rtol=1e-5)
        np.testing.assert_allclose(g_fused, logits2.grad.numpy(),
                                   rtol=1e-4, atol=1e-5)

    def test_softmax_with_cross_entropy_dispatches(self, monkeypatch,
                                                   fresh_metrics):
        import paddle_tpu as pt
        import paddle_tpu.nn.functional as F
        _force_cpu_dispatch(monkeypatch)
        rng = np.random.RandomState(0)
        logits = pt.to_tensor(rng.randn(4, 6, 130).astype(np.float32))
        lab = pt.to_tensor(rng.randint(0, 130, size=(4, 6, 1))
                           .astype(np.int64))
        out = F.softmax_with_cross_entropy(logits, lab)
        assert tuple(out.shape) == (4, 6, 1)
        c = fresh_metrics.counter("pt_pallas_calls_total",
                                  labelnames=("kernel", "path"))
        assert c.value(kernel="fused_softmax_xent", path="pallas") >= 1

    def test_ineligible_shapes_fall_back(self, monkeypatch,
                                         fresh_metrics):
        import paddle_tpu as pt
        import paddle_tpu.nn.functional as F
        _force_cpu_dispatch(monkeypatch)
        rng = np.random.RandomState(0)
        c = fresh_metrics.counter("pt_pallas_calls_total",
                                  labelnames=("kernel", "path"))
        # soft labels → XLA
        soft = rng.rand(8, 100).astype(np.float32)
        soft /= soft.sum(-1, keepdims=True)
        out = F.cross_entropy(pt.to_tensor(rng.randn(8, 100)
                                           .astype(np.float32)),
                              pt.to_tensor(soft), soft_label=True)
        assert np.isfinite(float(out.numpy()))
        # class axis not trailing → XLA
        out = F.cross_entropy(
            pt.to_tensor(rng.randn(8, 100, 4).astype(np.float32)),
            pt.to_tensor(rng.randint(0, 100, size=(8, 4))
                         .astype(np.int64)), axis=1)
        assert np.isfinite(float(out.numpy()))
        # per-class weights → XLA
        out = F.cross_entropy(
            pt.to_tensor(rng.randn(8, 100).astype(np.float32)),
            pt.to_tensor(rng.randint(0, 100, 8).astype(np.int64)),
            weight=pt.to_tensor(np.ones(100, np.float32)))
        assert np.isfinite(float(out.numpy()))
        assert c.value(kernel="fused_softmax_xent", path="fallback") >= 3
        assert c.value(kernel="fused_softmax_xent", path="pallas") == 0


# ---------------------------------------------------------------------------
# autotuner: search, pruning, persistence, cross-process reload
# ---------------------------------------------------------------------------
    @pytest.mark.parametrize("what", ["forward", "gradient"])
    @pytest.mark.parametrize("entry", ["functional", "module"])
    def test_fused_add_layer_norm_is_layer_norm_of_the_sum(
            self, entry, what, monkeypatch, fresh_metrics):
        """`F.fused_add_layer_norm(x, r)` and `nn.LayerNorm()(x,
        residual=r)` on the kernel's route (the add inside the kernel)
        against `F.layer_norm(x + r)` composed off it."""
        import paddle_tpu as pt
        import paddle_tpu.nn.functional as F
        import paddle_tpu.framework.flags as flags

        rng = np.random.RandomState(0)
        arrays = [rng.randn(4, 16, 96).astype(np.float32),
                  rng.randn(4, 16, 96).astype(np.float32),
                  rng.randn(96).astype(np.float32),
                  rng.randn(96).astype(np.float32)]

        def run(fn):
            x, r, w, b = (pt.to_tensor(a, stop_gradient=False)
                          for a in arrays)
            out, leaves = fn(x, r, w, b)
            (out * out).sum().backward()
            return [out.numpy()] if what == "forward" else \
                [t.grad.numpy() for t in leaves]

        def composed(x, r, w, b):
            return F.layer_norm(x + r, 96, w, b), (x, r, w, b)

        def fused(x, r, w, b):
            if entry == "functional":
                return F.fused_add_layer_norm(x, r, 96, w, b), (x, r, w, b)
            ln = pt.nn.LayerNorm(96)
            ln.weight.set_value(w)
            ln.bias.set_value(b)
            return ln(x, residual=r), (x, r, ln.weight, ln.bias)

        prev = flags.get_flags("use_pallas_kernels")
        try:
            flags.set_flags({"use_pallas_kernels": False})
            want = run(composed)
        finally:
            flags.set_flags(prev)
        _force_cpu_dispatch(monkeypatch)
        got = run(fused)
        c = fresh_metrics.counter("pt_pallas_calls_total",
                                  labelnames=("kernel", "path"))
        assert c.value(kernel="fused_layer_norm", path="pallas") == 1
        assert c.value(kernel="fused_layer_norm", path="fallback") == 1
        for g, w_ in zip(got, want):
            np.testing.assert_allclose(g, w_, **GRAD_TOL)

    @pytest.mark.parametrize("size", ["tiny_fp32", "tiny_amp_o2",
                                      "gpt345m_widths_fp32",
                                      "gpt345m_widths_amp_o2"])
    def test_gpt_step_for_the_chip_holds_the_kernels(self, size,
                                                     monkeypatch,
                                                     fresh_metrics):
        """The captured GPT step traced as the chip would get it: every
        layer norm, every attention and the loss are already Pallas calls
        and no normalisation is left in XLA's hands, so a graph pass had
        no cluster to rewrite there.  Each op's forward is in the trace
        twice: the forward walk, and the tape's lazy `jax.vjp` of the op
        at `loss.backward()`; both are the one jitted kernel wrapper,
        traced once."""
        import json
        import collections
        import paddle_tpu as pt
        from paddle_tpu.framework import device
        from paddle_tpu.incubate.models import GPTConfig, GPTForCausalLM
        from paddle_tpu.ops import pallas_ops
        for f in (fk._ln_pallas_fwd, fk._ln_pallas_bwd, fk._xent_pallas_fwd,
                  fk._xent_pallas_bwd, pallas_ops._fwd, pallas_ops._bwd):
            f.clear_cache()
        monkeypatch.setattr(device, "on_tpu", lambda: True)
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        name = "gpt-345m" if size.startswith("gpt345m") else "tiny-train"
        with open(os.path.join(root, "benchmarks", "configs",
                               name + ".json")) as f:
            spec = json.load(f)["model"]
        # flash attention is dispatched from flash_min_seq = 512 tokens up
        seq = 1024 if size.startswith("gpt345m") else 512
        spec = dict(spec, num_layers=2, max_position_embeddings=seq)
        amp = size.endswith("amp_o2")
        pt.seed(0)
        model = GPTForCausalLM(GPTConfig(**spec))
        if amp:
            pt.amp.decorate(model, level="O2", dtype="bfloat16")
        opt = pt.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters(),
                                 multi_precision=amp)
        ce = pt.nn.CrossEntropyLoss()

        @pt.jit.capture_step
        def step(ids, labels):
            loss = ce(model(ids), labels)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

        ids = pt.to_tensor(np.zeros((2, seq), np.int64))
        leaves, struct = step._flatten((ids, ids), {})
        entry = step._compile((ids, ids), {},
                              step._signature(leaves, struct))
        st = step._state
        closed = jax.make_jaxpr(entry.pure)(
            st.params, st.buffers, st.opt_states, st.rng_ctr, [1e-3],
            [ids._data, ids._data])
        kernels, outside = collections.Counter(), collections.Counter()

        def walk(jaxpr):
            for e in jaxpr.eqns:
                if e.primitive.name == "pallas_call":
                    kernels[e.params["name"]] += 1
                    continue            # a kernel's body is the kernel's
                outside[e.primitive.name] += 1
                for v in e.params.values():
                    for x in (v if isinstance(v, (list, tuple)) else [v]):
                        x = getattr(x, "jaxpr", x)
                        if hasattr(x, "eqns"):
                            walk(x)

        walk(closed.jaxpr)
        L = spec["num_layers"]
        assert kernels == {
            "layer_norm_fwd": 2 * (2 * L + 1), "layer_norm_bwd": 2 * L + 1,
            "flash_fwd": 2 * L, "flash_bwd_dq": L, "flash_bwd_dkv": L,
            "softmax_xent_fwd": 2, "softmax_xent_bwd": 1}, dict(kernels)
        assert not outside["rsqrt"] and not outside["reduce_max"], outside
        # ... but each twice-traced forward is the one jitted call: a
        # kernel's wrapper is traced once a signature, whatever calls it.
        # The layer-norm backward sees two: a sharding is part of jit's
        # signature, and the cotangent in front of the MLP comes out of
        # XLA's ops carrying the default mesh's, while those in front of
        # attention and the loss come out of a kernel, carrying none
        traces = fresh_metrics.counter("pt_pallas_traces_total",
                                       labelnames=("kernel",))
        got = {k: traces.value(kernel=k) for k in _TRACED}
        assert got == {
            "layer_norm_fwd": 1, "layer_norm_bwd": 2, "flash_fwd": 1,
            "flash_bwd": 1, "softmax_xent_fwd": 1, "softmax_xent_bwd": 1}, got
        calls = fresh_metrics.counter("pt_pallas_calls_total",
                                      labelnames=("kernel", "path"))
        assert calls.value(kernel="fused_layer_norm",
                           path="pallas") == 2 * L + 1

    @pytest.mark.parametrize("mode", ["tape", "capture_step"])
    @pytest.mark.parametrize("case", ["layer_norm_residual_affine",
                                      "layer_norm_plain",
                                      "cross_entropy_ignore_smoothing"])
    def test_kernels_are_bit_identical_without_the_jit_helper(
            self, case, mode, monkeypatch):
        """Forward and gradients through the tape, and one SGD step under
        `capture_step`, read the same bits whether the layer-norm and
        cross-entropy wrappers go through `kernel_jit` or are called
        as the plain functions it wraps."""
        import inspect
        _force_cpu_dispatch(monkeypatch)
        got = _bit_run(case, mode)
        for name in ("_ln_pallas_fwd", "_ln_pallas_bwd", "_xent_pallas_fwd",
                     "_xent_pallas_bwd"):
            monkeypatch.setattr(fk, name, inspect.unwrap(getattr(fk, name)))
        want = _bit_run(case, mode)
        assert len(got) == len(want) >= 3
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)

    @pytest.mark.parametrize("kernel", ["layer_norm", "softmax_xent"])
    def test_kernel_is_traced_once_a_signature(self, kernel, monkeypatch,
                                               fresh_metrics):
        """Three calls through the tape and a backward trace the forward
        wrapper once (the backward's re-traced forward is a cache hit)
        and the backward wrapper once; a second shape traces each again."""
        import paddle_tpu as pt
        import paddle_tpu.nn.functional as F
        _force_cpu_dispatch(monkeypatch)
        for f in (fk._ln_pallas_fwd, fk._ln_pallas_bwd, fk._xent_pallas_fwd,
                  fk._xent_pallas_bwd):
            f.clear_cache()
        rng = np.random.RandomState(0)

        def call(rows):
            x = pt.to_tensor(rng.randn(rows, 72).astype(np.float32),
                             stop_gradient=False)
            if kernel == "layer_norm":
                return F.layer_norm(x, 72).sum()
            lab = pt.to_tensor(rng.randint(0, 72, rows).astype(np.int64))
            return F.cross_entropy(x, lab)

        traces = fresh_metrics.counter("pt_pallas_traces_total",
                                       labelnames=("kernel",))
        seen = lambda: (traces.value(kernel=kernel + "_fwd"),
                        traces.value(kernel=kernel + "_bwd"))
        (call(24) + call(24) + call(24)).backward()
        assert seen() == (1, 1)
        call(40).backward()
        assert seen() == (2, 2)
        calls = fresh_metrics.counter("pt_pallas_calls_total",
                                      labelnames=("kernel", "path"))
        assert calls.value(kernel="fused_" + kernel, path="pallas") == 4


# what `kernel_jit` books on pt_pallas_traces_total in a GPT train step
_TRACED = ("layer_norm_fwd", "layer_norm_bwd", "flash_fwd", "flash_bwd",
           "softmax_xent_fwd", "softmax_xent_bwd")


def _bit_run(case, mode):
    """Loss and gradients (``tape``), or loss and parameters after one
    SGD step of rate 1 (``capture_step``), of a linear layer feeding the
    fused kernel named by ``case``."""
    import paddle_tpu as pt
    import paddle_tpu.nn.functional as F
    rng = np.random.RandomState(0)
    x = pt.to_tensor(rng.randn(4, 16, 96).astype(np.float32))
    r = pt.to_tensor(rng.randn(4, 16, 96).astype(np.float32))
    lab = rng.randint(0, 257, size=(4, 16)).astype(np.int64)
    lab[0, :5] = -100
    lab = pt.to_tensor(lab)
    pt.seed(0)
    lin = pt.nn.Linear(96, 257 if case.startswith("cross") else 96)
    params = list(lin.parameters())
    if case == "layer_norm_residual_affine":
        ln = pt.nn.LayerNorm(96)
        ln.weight.set_value(rng.randn(96).astype(np.float32))
        ln.bias.set_value(rng.randn(96).astype(np.float32))
        params += [ln.weight, ln.bias]

        def loss_of(x, r, lab):
            y = ln(lin(x), residual=r)
            return (y * y).sum()
    elif case == "layer_norm_plain":
        def loss_of(x, r, lab):
            y = F.layer_norm(lin(x), 96)
            return (y * y).sum()
    else:
        def loss_of(x, r, lab):
            return F.cross_entropy(lin(x), lab, ignore_index=-100,
                                   label_smoothing=0.1)
    if mode == "tape":
        loss = loss_of(x, r, lab)
        loss.backward()
        return [loss.numpy()] + [p.grad.numpy() for p in params]
    opt = pt.optimizer.SGD(learning_rate=1.0, parameters=params)

    @pt.jit.capture_step
    def step(x, r, lab):
        loss = loss_of(x, r, lab)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    loss = step(x, r, lab)
    return [loss.numpy()] + [p.numpy() for p in params]


class TestAutotuneSearch:

    def test_layer_norm_search_times_three_plus_candidates(self):
        x = _rand((2048, 256))
        best, timings = fk.tune_layer_norm(x, interpret=True)
        assert best in timings and len(timings) >= 3
        assert at.summary()["fused_layer_norm"]["timed"] >= 3
        # the winner now drives default-config calls
        assert at.enabled()
        hit = at.cache_get("fused_layer_norm",
                           (2048, 256, "float32", True))
        assert hit == best

    def test_flash_search_times_three_plus_candidates(self):
        q = _rand((1, 1, 512, 16))
        from paddle_tpu.ops.pallas_ops import tune_mha
        best, timings = tune_mha(q, q, q, causal=True, interpret=True)
        assert best in timings and len(timings) >= 3
        assert at.summary()["flash_mha"]["timed"] >= 3

    def test_softmax_xent_search(self):
        logits = _rand((512, 1024))
        lab = jnp.zeros((512,), jnp.int32)
        best, timings = fk.tune_softmax_xent(logits, lab, interpret=True)
        assert best in timings and len(timings) >= 3
        assert at.cache_get(
            "fused_softmax_xent",
            (512, 1024, "float32", False, True)) == best

    def test_cache_hit_skips_search_and_counts(self, fresh_metrics):
        x = _rand((1024, 128))
        _, t1 = fk.tune_layer_norm(x, interpret=True)
        assert len(t1) >= 1
        best2, t2 = fk.tune_layer_norm(x, interpret=True)
        assert t2 == {}  # nothing re-timed: answered from cache
        hits = fresh_metrics.counter("pt_autotune_cache_hits_total",
                                     labelnames=("kernel",))
        assert hits.value(kernel="fused_layer_norm") >= 1

    def test_vmem_overflowing_candidate_never_timed(self):
        timed = []

        def run(cfg):
            timed.append(cfg)

        def cost(cfg):
            return {"flops": 1.0, "bytes": 1.0,
                    "vmem_bytes": 1e12 if cfg == (512, 512) else 1024,
                    "mxu_underfill": cfg == (4, 4)}

        best, timings = at.search(
            "probe_kernel", ("k",), run,
            [(128, 128), (512, 512), (4, 4), (256, 256)], cost=cost)
        assert (512, 512) not in timed      # vmem overflow pruned
        assert (4, 4) not in timed          # MXU underfill pruned
        assert set(timed) == {(128, 128), (256, 256)}
        assert best in {(128, 128), (256, 256)}

    def test_all_pruned_raises(self):
        with pytest.raises(RuntimeError, match="pruned every candidate"):
            at.search("probe_kernel", ("k2",), lambda cfg: None,
                      [(1, 1)], cost=lambda cfg: None)

    def test_roofline_ordering(self):
        # compute-bound vs bandwidth-bound: the max() of the two sides
        peak, bw = at.device_peaks()
        assert at.roofline_seconds(peak, 0.0) == pytest.approx(1.0)
        assert at.roofline_seconds(0.0, bw) == pytest.approx(1.0)

    def test_analytic_seed_from_cost_model(self):
        seed = at.analytic_seed(
            lambda a: jnp.sum(a * a), jnp.ones((128, 128), jnp.float32))
        # CPU backends may not expose cost analysis — None is a valid
        # answer; when present, both axes must be positive
        if seed is not None:
            assert seed["flops"] > 0 or seed["bytes"] > 0


class TestAutotunePersistence:

    def test_round_trip(self, tmp_path):
        at.cache_put("fused_layer_norm", (64, 256, "float32", True),
                     (256, 1))
        p = str(tmp_path / "tune.json")
        at.save_cache(p)
        at.cache_clear()
        assert at.cache_get("fused_layer_norm",
                            (64, 256, "float32", True)) is None
        at.load_cache(p)
        assert at.cache_get("fused_layer_norm",
                            (64, 256, "float32", True)) == (256, 1)

    def test_stale_jax_version_invalidated_on_load(self, tmp_path):
        at.cache_put("fused_layer_norm", (64, 256, "float32", True),
                     (256, 1))
        p = str(tmp_path / "tune.json")
        at.save_cache(p)
        with open(p) as f:
            raw = json.load(f)
        stale = {}
        for k, v in raw.items():
            kernel, schema, kind, _ver, key = json.loads(k)
            stale[json.dumps([kernel, schema, kind, "0.0.1", key])] = v
        with open(p, "w") as f:
            json.dump(stale, f)
        at.cache_clear()
        at.load_cache(p)  # must not crash, must drop the stale entry
        assert at.cache_get("fused_layer_norm",
                            (64, 256, "float32", True)) is None

    def test_stale_device_kind_and_schema_invalidated(self, tmp_path):
        at.cache_put("flash_mha", (64, 64, 16, "float32", True, True),
                     (64, 64))
        p = str(tmp_path / "tune.json")
        at.save_cache(p)
        with open(p) as f:
            raw = json.load(f)
        mutated = {}
        for k, v in raw.items():
            kernel, schema, _kind, ver, key = json.loads(k)
            mutated[json.dumps([kernel, schema, "TPU v9", ver, key])] = v
            mutated[json.dumps([kernel, schema + 1, "cpu", ver, key])] = v
        mutated["not json structured"] = [1, 2]
        with open(p, "w") as f:
            json.dump(mutated, f)
        at.cache_clear()
        at.load_cache(p)
        assert at.cache_get("flash_mha",
                            (64, 64, 16, "float32", True, True)) is None

    def test_second_process_reloads_without_searching(self, tmp_path):
        """The acceptance drill: process A searches and persists via
        PT_AUTOTUNE_CACHE; process B with the same env var answers the
        same tune request from cache — zero candidates timed, the hit
        counter incremented."""
        cache = str(tmp_path / "shared_tune.json")
        child = (
            "import os, json, jax.numpy as jnp\n"
            "from paddle_tpu.ops import autotune as at\n"
            "from paddle_tpu.ops import fused_kernels as fk\n"
            "from paddle_tpu.observability.metrics import get_registry\n"
            "x = jnp.zeros((1024, 128), jnp.float32)\n"
            "best, timings = fk.tune_layer_norm(x, interpret=True)\n"
            "reg = get_registry()\n"
            "hits = reg.counter('pt_autotune_cache_hits_total',"
            " labelnames=('kernel',))\n"
            "misses = reg.counter('pt_autotune_cache_misses_total',"
            " labelnames=('kernel',))\n"
            "print(json.dumps({'best': list(best),"
            " 'timed': len(timings),"
            " 'hits': hits.value(kernel='fused_layer_norm'),"
            " 'misses': misses.value(kernel='fused_layer_norm')}))\n"
        )
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "PT_AUTOTUNE_CACHE": cache, "PT_TELEMETRY": "1"}

        def run_child():
            out = subprocess.run([sys.executable, "-c", child], env=env,
                                 capture_output=True, text=True,
                                 timeout=240)
            assert out.returncode == 0, out.stderr[-2000:]
            return json.loads(out.stdout.strip().splitlines()[-1])

        a = run_child()
        assert a["timed"] >= 1 and a["misses"] == 1 and a["hits"] == 0
        assert os.path.exists(cache)
        b = run_child()
        assert b["timed"] == 0      # reloaded, nothing re-searched
        assert b["hits"] == 1 and b["misses"] == 0
        assert b["best"] == a["best"]


# ---------------------------------------------------------------------------
# autotuner: generated candidates, prune-before-time, schema bump
# ---------------------------------------------------------------------------
class TestCandidateGeneration:

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

    def test_all_generated_pruned_raises(self):
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

        at.search("fused_layer_norm", ("gen", 1), run, cands,
                  cost=self._cost, vmem_limit=64 * 1024,
                  warmup=0, iters=1)
        assert timed and all(c[0] * c[1] * 4 <= 64 * 1024 for c in timed)


class TestSchemaBump:

    @pytest.fixture(autouse=True)
    def _restore_schema(self):
        orig = dict(at.KERNEL_SCHEMA)
        yield
        at.KERNEL_SCHEMA.clear()
        at.KERNEL_SCHEMA.update(orig)

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
            at.search("fused_layer_norm", key, run, cands, cost=cost,
                      warmup=0, iters=1)
            n_first = len(timed)
            assert n_first >= 2        # both survivors timed

            # a kernel-layout change bumps the schema: every entry
            # written under the old version becomes invisible
            at.bump_schema("fused_layer_norm")
            assert at.cache_get("fused_layer_norm", key) is None
            at.cache_clear()
            at.load_cache(path)        # stale entries dropped on load
            assert at.cache_get("fused_layer_norm", key) is None

            # re-search under the new schema, then reload in a clean
            # cache: the bumped entry answers without re-searching
            at.search("fused_layer_norm", key, run, cands, cost=cost,
                      warmup=0, iters=1)
            n_second = len(timed)
            assert n_second > n_first
            at.cache_clear()
            at.load_cache(path)
            _, timings = at.search("fused_layer_norm", key, run, cands,
                                   cost=cost, warmup=0, iters=1)
            assert timings == {}       # pure cache hit across the bump
            assert len(timed) == n_second
        finally:
            os.environ.pop("PT_AUTOTUNE_CACHE", None)
