"""The serving path's routed experts (``paddle_tpu.serving.experts``)
against a per-token loop in float64: all three forms (dense and streamed
for few rows, sort + grouped matmul for many; the streamed form's Pallas
kernel in interpret mode), every token to one expert, an expert with no
token, padding that must cost and count nothing, experts no row chose
that are never read — and no token dropped, whatever the load."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.framework import device as _device
from paddle_tpu.serving import experts as ex

T, H, E, F, K = 24, 32, 8, 16, 2
FORMS = ["dense", "grouped", "streamed"]
CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "configs")


@pytest.fixture
def kernel_on(monkeypatch):
    """Dispatch as on the chip: the streamed form takes its Pallas kernel
    (in interpret mode, off the TPU)."""
    monkeypatch.setattr(_device, "pallas_dispatch", lambda: True)


@pytest.fixture(params=FORMS)
def form(request):
    if request.param == "streamed":
        request.getfixturevalue("kernel_on")
    return request.param


def _weights(seed=0, scale=0.3, t=T, e=E):
    rng = np.random.RandomState(seed)
    return {"y": rng.randn(t, H).astype(np.float32),
            "router": (rng.randn(H, e) * scale).astype(np.float32),
            "wg": (rng.randn(e, H, F) * scale).astype(np.float32),
            "wu": (rng.randn(e, H, F) * scale).astype(np.float32),
            "wd": (rng.randn(e, F, H) * scale).astype(np.float32)}


def _loop(w, top_k=K):
    """Token by token, expert by expert, in float64."""
    y = w["y"].astype(np.float64)
    logits = y @ w["router"].astype(np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    t_, e_ = p.shape
    out = np.zeros((t_, y.shape[1]))
    chosen = np.zeros((t_, e_), np.int64)
    for t in range(t_):
        top = np.argsort(-p[t], kind="stable")[:top_k]
        g = p[t, top] / p[t, top].sum()
        for gate, e in zip(g, top):
            a = y[t] @ w["wg"][e].astype(np.float64)
            u = y[t] @ w["wu"][e].astype(np.float64)
            out[t] += gate * ((a / (1 + np.exp(-a))) * u) \
                @ w["wd"][e].astype(np.float64)
            chosen[t, e] += 1
    return out, chosen


def _run(w, form, valid=None, top_k=K):
    out, counts = ex.moe_ffn(
        jnp.asarray(w["y"]), jnp.asarray(w["router"]), jnp.asarray(w["wg"]),
        jnp.asarray(w["wu"]), jnp.asarray(w["wd"]), top_k=top_k,
        form=form, valid=None if valid is None else jnp.asarray(valid))
    return np.asarray(out), np.asarray(counts)


@pytest.mark.parametrize("top_k", [1, 2, 4])
def test_equals_the_per_token_loop(form, top_k):
    w = _weights(top_k)
    want, chosen = _loop(w, top_k)
    got, counts = _run(w, form, top_k=top_k)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    np.testing.assert_array_equal(counts, chosen.sum(0))
    assert counts.sum() == T * top_k            # no token dropped


def test_every_token_to_one_expert_and_none_dropped(form):
    """A router that sends every token to expert 3 first (and 5 second):
    a capacity-bound dispatch would drop most of them."""
    w = _weights(7)
    w["router"][:] = 0.0
    w["y"][:, 0] = 1.0                          # a constant feature
    w["router"][0, 3], w["router"][0, 5] = 9.0, 4.0
    want, chosen = _loop(w)
    got, counts = _run(w, form)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    assert counts[3] == T and counts[5] == T and counts.sum() == 2 * T
    assert np.all(chosen.sum(0) == counts)


def test_an_expert_with_no_token(form):
    w = _weights(11)
    w["router"][:, 2] = 0.0
    w["router"][0, 2] = -50.0                   # never chosen
    w["y"][:, 0] = 1.0
    want, _ = _loop(w)
    got, counts = _run(w, form)
    assert counts[2] == 0
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_padding_rows_are_neither_counted_nor_in_the_way():
    """Rows past the prompt sort behind every group: the valid rows'
    results and the counts are those of the valid rows alone."""
    w = _weights(13)
    valid = np.arange(T) < 15
    want, chosen = _loop(w)
    got, counts = _run(w, "grouped", valid=valid)
    np.testing.assert_allclose(got[valid], want[valid], atol=2e-4,
                               rtol=2e-4)
    np.testing.assert_array_equal(counts, chosen[valid].sum(0))


def test_the_forms_agree(kernel_on):
    w = _weights(17)
    dense = _run(w, "dense")[0]
    for form in ("grouped", "streamed"):
        np.testing.assert_allclose(_run(w, form)[0], dense, atol=1e-4,
                                   rtol=1e-4)


def test_route_is_softmax_then_topk_renormalised_in_float32():
    w = _weights(19)
    gates, idx = ex.route(jnp.asarray(w["y"], jnp.bfloat16),
                          jnp.asarray(w["router"], jnp.bfloat16), K)
    assert gates.dtype == jnp.float32 and idx.dtype == jnp.int32
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.0, atol=1e-6)
    assert np.all(np.asarray(gates)[:, 0] >= np.asarray(gates)[:, 1])


def test_no_weight_gather_in_either_regime(kernel_on):
    """No form's jaxpr holds a tensor with a (tokens, ..., hidden, width)
    weight copy: the largest intermediate is far under T x k experts'
    worth of weights."""
    w = _weights(23)
    per_pair = H * F
    for form in FORMS:
        jaxpr = jax.make_jaxpr(
            lambda *a, form=form: ex.moe_ffn(*a, top_k=K, form=form))(
            *(jnp.asarray(w[k]) for k in ("y", "router", "wg", "wu", "wd")))
        sizes = [int(np.prod(v.aval.shape)) for eqn in jaxpr.eqns
                 for v in eqn.outvars if hasattr(v.aval, "shape")]
        assert max(sizes) < T * K * per_pair, (form, max(sizes))


def test_incubate_functional_hands_over_to_the_same_layer():
    from paddle_tpu.incubate.distributed.models.moe import functional as fn
    w = _weights(29)
    want, _ = _loop(w)
    got = fn.dropless_moe(*(jnp.asarray(w[k]) for k in
                            ("y", "router", "wg", "wu", "wd")), top_k=K)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("dtype", [np.float32, "bfloat16"])
def test_pallas_grouped_matmul_agrees_with_ragged_dot(dtype):
    """The chip's route (megablox.gmm, here in interpret mode) against
    the fallback, rows behind the last group aside."""
    rng = np.random.RandomState(3)
    m, k, n, e = 512, 128, 256, 4
    sizes = np.asarray([100, 0, 250, 60], np.int32)     # 102 rows unused
    x = jnp.asarray(rng.randn(m, k), dtype)
    w = jnp.asarray(rng.randn(e, k, n) * 0.1, dtype)
    got = ex.grouped_matmul(x, w, jnp.asarray(sizes), use_pallas=True,
                            interpret=True)
    want = ex.grouped_matmul(x, w, jnp.asarray(sizes), use_pallas=False)
    used = int(sizes.sum())
    assert got.dtype == want.dtype == x.dtype
    np.testing.assert_allclose(np.asarray(got[:used], np.float32),
                               np.asarray(want[:used], np.float32),
                               atol=2e-2 if dtype == "bfloat16" else 1e-4)


def test_grouped_matmul_tiles():
    assert ex._tile(2304, 2304) == 2304 and ex._tile(2304, 1152) == 1152
    assert ex._tile(896, 1152) == 896 and ex._tile(32, 1152) is None


# Keye's and Mellum's proportions at a few rows: experts, top-k, rows
FEW_ROWS = [(128, 8, 8), (128, 8, 24), (64, 8, 32)]


def _a_third_untouched(e, k, t, seed):
    """Weights at ``e`` experts whose valid rows (the first ``2t/3``)
    never choose the last third of the experts, and whose padding rows
    choose nothing else: ``(weights, valid, untouched ids)``."""
    w = _weights(seed, t=t, e=e)
    off = np.arange(e) >= e - e // 3
    w["router"][:, off] = 0.0
    w["router"][0, off] = -50.0
    w["y"][:, 0] = 1.0
    valid = np.arange(t) < 2 * t // 3
    w["y"][~valid, 0] = -1.0                    # they route to ``off``
    return w, valid, np.flatnonzero(off)


@pytest.mark.parametrize("e,k,t", FEW_ROWS)
def test_few_rows_with_a_third_of_the_experts_untouched(kernel_on, e, k, t):
    w, valid, off = _a_third_untouched(e, k, t, 31)
    want, chosen = _loop(w, k)
    got, counts = _run(w, "streamed", valid=valid, top_k=k)
    np.testing.assert_allclose(got[valid], want[valid], atol=2e-4,
                               rtol=2e-4)
    np.testing.assert_array_equal(counts, chosen[valid].sum(0))
    assert counts[off].sum() == 0 and chosen[~valid][:, off].sum() > 0
    # the list the kernel walks: the touched experts ascending, the last
    # one repeated to the end
    ids, n = ex._touched(jnp.asarray(np.argsort(-chosen, 1)[:, :k],
                                     jnp.int32), e, jnp.asarray(valid))
    hit = np.flatnonzero(counts)
    assert int(n[0]) == len(hit) <= e - len(off)
    np.testing.assert_array_equal(np.asarray(ids),
                                  np.r_[hit, [hit[-1]] * (e - len(hit))])


@pytest.mark.parametrize("e,k,t", FEW_ROWS)
def test_experts_no_row_chose_are_never_read(kernel_on, e, k, t):
    """Every untouched expert's matrices are NaN: the streamed form's
    result is finite and the loop's, the dense form's is not."""
    w, valid, off = _a_third_untouched(e, k, t, 37)
    want, _ = _loop(w, k)
    for name in ("wg", "wu", "wd"):
        w[name][off] = np.nan
    got, _ = _run(w, "streamed", valid=valid, top_k=k)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got[valid], want[valid], atol=2e-4,
                               rtol=2e-4)
    assert not np.isfinite(_run(w, "dense", valid=valid, top_k=k)[0]).all()


def test_gradient_through_the_kernel_is_the_dense_forms(kernel_on):
    from paddle_tpu.incubate.distributed.models.moe import functional as fn
    w = _weights(41)
    args = tuple(jnp.asarray(w[k]) for k in ("y", "router", "wg", "wu",
                                             "wd"))

    def grads(form):
        return jax.grad(lambda *a: jnp.sum(jnp.sin(fn.dropless_moe(
            *a, top_k=K, form=form))), argnums=range(5))(*args)

    for got, want in zip(grads("streamed"), grads("dense")):
        assert np.abs(np.asarray(want)).max() > 0
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-4)


@pytest.mark.parametrize("config", ["keye-vl2-30b-a3b-serve",
                                    "mellum2-12b-a2p5b-serve"])
def test_every_decode_bucket_streams_and_every_prefill_groups(config):
    """One form a side of ``STREAM_MAX_TOKENS``: the kernel kept pace with
    the dense form where the rows touch every expert (Mellum's 64 rows,
    PERF.md PR 37), so no bucket of either model is dense by its shape."""
    with open(os.path.join(CONFIGS, config + ".json")) as fh:
        serve = json.load(fh)["serve"]
    assert max(serve["decode_buckets"]) <= ex.STREAM_MAX_TOKENS \
        < min(serve["prefill_buckets"])


@pytest.mark.parametrize("form,on_chip,path", [
    ("streamed", True, "pallas"), ("streamed", False, "fallback"),
    ("dense", True, None), (None, True, "pallas")])
def test_the_dispatch_books_the_kernel_and_nothing_for_dense(
        monkeypatch, form, on_chip, path):
    from paddle_tpu.observability.metrics import get_registry, \
        reset_registry
    from paddle_tpu.observability.telemetry import get_telemetry
    monkeypatch.setattr(_device, "pallas_dispatch", lambda: on_chip)
    monkeypatch.setattr(get_telemetry(), "enabled", True)
    reset_registry()
    try:
        _run(_weights(43), form)
        c = get_registry().counter("pt_pallas_calls_total",
                                   labelnames=("kernel", "path"))
        booked = {p: c.value(kernel="moe_decode", path=p)
                  for p in ("pallas", "fallback")}
    finally:
        reset_registry()
    assert booked == {p: int(p == path) for p in booked}
