"""The serving path's routed experts (``paddle_tpu.serving.experts``)
against a per-token loop in float64: both regimes (dense for few rows,
sort + grouped matmul for many), every token to one expert, an expert
with no token, padding that must cost and count nothing — and no token
dropped, whatever the load."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.serving import experts as ex

T, H, E, F, K = 24, 32, 8, 16, 2


def _weights(seed=0, scale=0.3):
    rng = np.random.RandomState(seed)
    return {"y": rng.randn(T, H).astype(np.float32),
            "router": (rng.randn(H, E) * scale).astype(np.float32),
            "wg": (rng.randn(E, H, F) * scale).astype(np.float32),
            "wu": (rng.randn(E, H, F) * scale).astype(np.float32),
            "wd": (rng.randn(E, F, H) * scale).astype(np.float32)}


def _loop(w, top_k=K):
    """Token by token, expert by expert, in float64."""
    y = w["y"].astype(np.float64)
    logits = y @ w["router"].astype(np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    out = np.zeros((T, H))
    chosen = np.zeros((T, E), np.int64)
    for t in range(T):
        top = np.argsort(-p[t], kind="stable")[:top_k]
        g = p[t, top] / p[t, top].sum()
        for gate, e in zip(g, top):
            a = y[t] @ w["wg"][e].astype(np.float64)
            u = y[t] @ w["wu"][e].astype(np.float64)
            out[t] += gate * ((a / (1 + np.exp(-a))) * u) \
                @ w["wd"][e].astype(np.float64)
            chosen[t, e] += 1
    return out, chosen


def _run(w, dense, valid=None, top_k=K):
    out, counts = ex.moe_ffn(
        jnp.asarray(w["y"]), jnp.asarray(w["router"]), jnp.asarray(w["wg"]),
        jnp.asarray(w["wu"]), jnp.asarray(w["wd"]), top_k=top_k,
        dense=dense, valid=None if valid is None else jnp.asarray(valid))
    return np.asarray(out), np.asarray(counts)


@pytest.mark.parametrize("dense", [True, False], ids=["dense", "grouped"])
@pytest.mark.parametrize("top_k", [1, 2, 4])
def test_equals_the_per_token_loop(dense, top_k):
    w = _weights(top_k)
    want, chosen = _loop(w, top_k)
    got, counts = _run(w, dense, top_k=top_k)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    np.testing.assert_array_equal(counts, chosen.sum(0))
    assert counts.sum() == T * top_k            # no token dropped


@pytest.mark.parametrize("dense", [True, False], ids=["dense", "grouped"])
def test_every_token_to_one_expert_and_none_dropped(dense):
    """A router that sends every token to expert 3 first (and 5 second):
    a capacity-bound dispatch would drop most of them."""
    w = _weights(7)
    w["router"][:] = 0.0
    w["y"][:, 0] = 1.0                          # a constant feature
    w["router"][0, 3], w["router"][0, 5] = 9.0, 4.0
    want, chosen = _loop(w)
    got, counts = _run(w, dense)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    assert counts[3] == T and counts[5] == T and counts.sum() == 2 * T
    assert np.all(chosen.sum(0) == counts)


@pytest.mark.parametrize("dense", [True, False], ids=["dense", "grouped"])
def test_an_expert_with_no_token(dense):
    w = _weights(11)
    w["router"][:, 2] = 0.0
    w["router"][0, 2] = -50.0                   # never chosen
    w["y"][:, 0] = 1.0
    want, _ = _loop(w)
    got, counts = _run(w, dense)
    assert counts[2] == 0
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_padding_rows_are_neither_counted_nor_in_the_way():
    """Rows past the prompt sort behind every group: the valid rows'
    results and the counts are those of the valid rows alone."""
    w = _weights(13)
    valid = np.arange(T) < 15
    want, chosen = _loop(w)
    got, counts = _run(w, False, valid=valid)
    np.testing.assert_allclose(got[valid], want[valid], atol=2e-4,
                               rtol=2e-4)
    np.testing.assert_array_equal(counts, chosen[valid].sum(0))


def test_the_two_regimes_agree():
    w = _weights(17)
    np.testing.assert_allclose(_run(w, True)[0], _run(w, False)[0],
                               atol=1e-4, rtol=1e-4)


def test_route_is_softmax_then_topk_renormalised_in_float32():
    w = _weights(19)
    gates, idx = ex.route(jnp.asarray(w["y"], jnp.bfloat16),
                          jnp.asarray(w["router"], jnp.bfloat16), K)
    assert gates.dtype == jnp.float32 and idx.dtype == jnp.int32
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.0, atol=1e-6)
    assert np.all(np.asarray(gates)[:, 0] >= np.asarray(gates)[:, 1])


def test_no_weight_gather_in_either_regime():
    """Neither regime's jaxpr holds a tensor with a (tokens, ..., hidden,
    width) weight copy: the largest intermediate is far under T x k
    experts' worth of weights."""
    w = _weights(23)
    per_pair = H * F
    for dense in (True, False):
        jaxpr = jax.make_jaxpr(
            lambda *a, dense=dense: ex.moe_ffn(*a, top_k=K, dense=dense))(
            *(jnp.asarray(w[k]) for k in ("y", "router", "wg", "wu", "wd")))
        sizes = [int(np.prod(v.aval.shape)) for eqn in jaxpr.eqns
                 for v in eqn.outvars if hasattr(v.aval, "shape")]
        assert max(sizes) < T * K * per_pair, (dense, max(sizes))


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
