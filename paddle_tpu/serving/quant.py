"""PTQ calibration harness + the quantized served-model format.

The int8 serving pipeline in three moves:

 1. **Calibrate** — :func:`calibrate` replays a prefill/decode trace
    *eagerly* through the same :mod:`.model` step functions the engine
    compiles, with the steps' ``tap`` hook feeding the existing
    :mod:`paddle_tpu.quantization` observers: a
    :class:`~paddle_tpu.quantization.observers.PerChannelAbsmaxObserver`
    per weight matrix and an
    :class:`~paddle_tpu.quantization.observers.AbsmaxObserver` per
    activation site.  Calibration never touches an engine, so it can't
    trip an armed serve compile sentinel.
 2. **Quantize** — :func:`quantize_params` rewrites the flat weight
    dict: each projection/MLP matrix ``name`` becomes ``name::q``
    (int8) + ``name::scale`` (f32 per-out-channel); per-tensor
    activation scales ride along as ``act::<site>::scale`` leaves so
    a future a8 path needs no re-calibration.  The model's matmul
    helper dispatches on the ``::q`` key at trace time, so one set of
    step functions serves every precision.
 3. **Save/load** — :func:`save_quantized_model` writes a served-model
    dir whose ``serve_config.json`` carries a ``precision`` block and
    whose checkpoint holds the quantized tree; ``load_engine`` builds
    its restore template from :func:`quantized_template` so treedef
    validation still bites.

Quality is tracked as **max-logit-divergence** vs the fp32 oracle
(:func:`logit_divergence`) on the toy model; the tolerance is pinned by
``tests/test_serving_quant.py`` and re-measured by
``bench_serve.py --precision int8``.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from ..ops.quant_kernels import quantize_weight
from ..quantization.observers import AbsmaxObserver, PerChannelAbsmaxObserver
from .kv_cache import pool_shapes
from .model import (ModelSpec, QUANT_WEIGHT_NAMES, decode_step, init_params,
                    prefill_step)

__all__ = ["calibrate", "quantize_params", "is_quantized_params",
           "quantized_template", "save_quantized_model",
           "logit_divergence", "default_calibration_prompts",
           "PRECISION_SCHEME"]

PRECISION_SCHEME = {
    "mode": "int8",
    "weights": "per-channel-absmax (out-channel), symmetric, no zero-point",
    "activations": "per-tensor-absmax, recorded for a8 follow-on",
    "kv_cache": "int8 per-(token,head) dynamic scales in shadow scale pages",
}


def default_calibration_prompts(spec: ModelSpec, n: int = 4,
                                seed: int = 0) -> List[List[int]]:
    """Deterministic toy calibration set (the bench/test corpus)."""
    rng = np.random.RandomState(seed)
    return [rng.randint(1, spec.vocab_size,
                        size=int(rng.randint(3, 13))).tolist()
            for _ in range(n)]


# ---------------------------------------------------------------------------
# calibration
# ---------------------------------------------------------------------------
class _TapObservers:
    """The ``tap(site, activation)`` hook: one per-tensor absmax
    observer per activation site (matmul inputs + the head input)."""

    def __init__(self):
        self.observers: Dict[str, AbsmaxObserver] = {}
        self.samples = 0

    def __call__(self, site: str, x) -> None:
        obs = self.observers.get(site)
        if obs is None:
            obs = self.observers[site] = AbsmaxObserver()
        # calibration is eager host-side replay by design — the
        # observers are numpy machinery and never run under the engine
        obs.observe(np.asarray(x, np.float32))  # tpu-lint: disable=TPU003
        self.samples += 1

    def scales(self) -> Dict[str, float]:
        return {site: float(o.scales())
                for site, o in sorted(self.observers.items())}


def calibrate(spec: ModelSpec, params, prompts: Sequence[Sequence[int]],
              *, max_new: int = 4, page_size: int = 8) -> Dict[str, Any]:
    """Run the PTQ observers over a captured prefill/decode trace.

    Replays each prompt through :func:`prefill_step` and ``max_new``
    :func:`decode_step` calls eagerly (fp32, throwaway KV pools sized
    per prompt), tapping every quantizable matmul input.  Also folds
    each weight matrix through a
    :class:`PerChannelAbsmaxObserver` so the weight scales come from
    the same observer machinery QAT uses.

    Returns ``{"act_scales", "weight_scales", "samples", "prompts"}``.
    """
    from .engine import aot_build_phase
    tap = _TapObservers()
    weight_obs: Dict[str, PerChannelAbsmaxObserver] = {}
    for name in QUANT_WEIGHT_NAMES(spec):
        obs = PerChannelAbsmaxObserver(quant_axis_=1)
        obs.observe(np.asarray(params[name], np.float32))
        weight_obs[name] = obs

    # eager replay compiles per prompt shape: a sanctioned build phase,
    # so calibrating next to a LIVE armed engine (blue/green requantize)
    # never books pt_serve_unexpected_compiles_total on it
    with aot_build_phase():
        for prompt in prompts:
            total = len(prompt) + max_new
            pages = 1 + -(-total // page_size)
            shape, _ = pool_shapes(spec.layers, pages, page_size,
                                   spec.heads, spec.head_dim)
            k_pool = jnp.zeros(shape, jnp.float32)
            v_pool = jnp.zeros(shape, jnp.float32)
            table = np.arange(1, pages, dtype=np.int32)
            padded = np.zeros((len(prompt),), np.int32)
            padded[:] = np.asarray(prompt, np.int32)
            k_pool, v_pool, nxt, _ = prefill_step(
                spec, params, k_pool, v_pool, padded,
                np.int32(len(prompt)), table, page_size=page_size, tap=tap)
            tok = np.asarray(nxt, np.int32).reshape(1)
            for j in range(max_new):
                pos = np.asarray([len(prompt) + j], np.int32)
                k_pool, v_pool, tok, _ = decode_step(
                    spec, params, k_pool, v_pool, tok, pos, table[None, :],
                    page_size=page_size, tap=tap)
                tok = np.asarray(tok, np.int32)

    return {
        "act_scales": tap.scales(),
        "weight_scales": {n: np.asarray(o.scales(), np.float32)
                          for n, o in sorted(weight_obs.items())},
        "samples": tap.samples,
        "prompts": len(list(prompts)),
    }


# ---------------------------------------------------------------------------
# weight quantization
# ---------------------------------------------------------------------------
def is_quantized_params(params) -> bool:
    return any(str(k).endswith("::q") for k in params)


def quantize_params(params, spec: ModelSpec,
                    act_scales: Optional[Dict[str, float]] = None
                    ) -> Dict[str, Any]:
    """Rewrite a flat fp32 weight dict into the int8 serve layout.

    Each quantizable matrix ``name`` is replaced (in place in the key
    order) by ``name::q`` + ``name::scale``; everything else passes
    through.  ``act_scales`` (from :func:`calibrate`) are appended as
    ``act::<site>::scale`` scalar leaves.  Deterministic — same weights
    always produce the same bytes, which is what lets an engine given
    fp32 weights under ``precision=int8`` quantize inline and still
    match a saved quantized dir bit for bit.
    """
    if is_quantized_params(params):
        return dict(params)
    targets = set(QUANT_WEIGHT_NAMES(spec))
    out: Dict[str, Any] = {}
    for name, w in params.items():
        if name in targets:
            q, s = quantize_weight(w, axis=1)
            out[name + "::q"] = q
            out[name + "::scale"] = s
        else:
            out[name] = w
    for site, scale in sorted((act_scales or {}).items()):
        out[f"act::{site}::scale"] = jnp.asarray([scale], jnp.float32)
    return out


def quantized_template(spec: ModelSpec,
                       act_sites: Optional[Sequence[str]] = None
                       ) -> Dict[str, Any]:
    """Shape/treedef template for restoring a quantized checkpoint —
    the ``load_engine`` validation hook.  ``act_sites`` lists the
    calibration sites recorded in the dir's precision block."""
    base = quantize_params(init_params(spec, seed=0), spec)
    for site in act_sites or ():
        base[f"act::{site}::scale"] = jnp.zeros((1,), jnp.float32)
    return base


# ---------------------------------------------------------------------------
# quantized served-model dirs
# ---------------------------------------------------------------------------
def save_quantized_model(path: str, spec: ModelSpec, params,
                         config=None, prompts=None, *, max_new: int = 4,
                         step: int = 0) -> str:
    """Calibrate + quantize + write a self-describing quantized
    served-model dir.

    ``serve_config.json`` grows a ``precision`` block (scheme, the
    calibration corpus fingerprint, per-tensor activation scales) and
    its ``serve.precision`` is pinned to ``int8``; the checkpoint holds
    the quantized tree :func:`quantized_template` round-trips.
    """
    from ..distributed.checkpoint_manager import CheckpointManager
    from .engine import SERVE_CONFIG_NAME, ServeConfig, aot_build_phase
    os.makedirs(path, exist_ok=True)
    cfg = (config or ServeConfig.from_env()).replace(precision="int8")
    if prompts is None:
        prompts = default_calibration_prompts(spec)
    with aot_build_phase():
        # quantize_params / checkpoint save run jnp ops eagerly — a
        # sanctioned build phase, like the calibration replay above
        cal = calibrate(spec, params, prompts, max_new=max_new,
                        page_size=cfg.page_size)
        qparams = quantize_params(params, spec,
                                  act_scales=cal["act_scales"])
    meta = {
        "model": spec.to_dict(),
        "serve": cfg.to_dict(),
        "precision": {
            **PRECISION_SCHEME,
            "act_scales": cal["act_scales"],
            "calibration": {"prompts": cal["prompts"],
                            "samples": cal["samples"],
                            "max_new": max_new},
            "quantized_weights": QUANT_WEIGHT_NAMES(spec),
        },
    }
    with open(os.path.join(path, SERVE_CONFIG_NAME), "w") as f:
        json.dump(meta, f, indent=2, sort_keys=True)
    with aot_build_phase():
        mgr = CheckpointManager(os.path.join(path, "weights"))
        mgr.save(step, dict(qparams), block=True)
    return path


# ---------------------------------------------------------------------------
# quality: max-logit-divergence vs the fp32 oracle
# ---------------------------------------------------------------------------
def logit_divergence(spec: ModelSpec, params, prompts=None, *,
                     max_new: int = 4, page_size: int = 8,
                     qparams=None) -> float:
    """Max absolute logit gap between the int8 serve path (quantized
    weights + int8 KV pool) and the fp32 oracle, over prefill + decode
    of ``prompts`` — the quality contract the test tolerance pins.

    Greedy token choices FOLLOW the fp32 path so both runs score the
    same token sequence (a divergence metric, not an accuracy proxy).
    Runs eagerly inside a sanctioned build phase, so it is safe next to
    a live armed engine.
    """
    from .engine import aot_build_phase
    if prompts is None:
        prompts = default_calibration_prompts(spec)
    if qparams is None:
        qparams = quantize_params(params, spec)
    worst = 0.0
    with aot_build_phase():
        for prompt in prompts:
            total = len(prompt) + max_new
            pages = 1 + -(-total // page_size)
            shape, sshape = pool_shapes(spec.layers, pages, page_size,
                                        spec.heads, spec.head_dim)
            kf = jnp.zeros(shape, jnp.float32)
            vf = jnp.zeros(shape, jnp.float32)
            kq = jnp.zeros(shape, jnp.int8)
            vq = jnp.zeros(shape, jnp.int8)
            ks = jnp.zeros(sshape, jnp.float32)
            vs = jnp.zeros(sshape, jnp.float32)
            table = np.arange(1, pages, dtype=np.int32)
            padded = np.asarray(prompt, np.int32)
            n = np.int32(len(prompt))
            kf, vf, tok, lg_f = prefill_step(
                spec, params, kf, vf, padded, n, table, page_size=page_size)
            kq, vq, ks, vs, _, lg_q = prefill_step(
                spec, qparams, kq, vq, padded, n, table,
                page_size=page_size, k_scale=ks, v_scale=vs)
            worst = max(worst, float(jnp.max(jnp.abs(lg_q - lg_f))))
            tok = np.asarray(tok, np.int32).reshape(1)
            for j in range(max_new):
                pos = np.asarray([len(prompt) + j], np.int32)
                kf, vf, nxt, lg_f = decode_step(
                    spec, params, kf, vf, tok, pos, table[None, :],
                    page_size=page_size)
                kq, vq, ks, vs, _, lg_q = decode_step(
                    spec, qparams, kq, vq, tok, pos, table[None, :],
                    page_size=page_size, k_scale=ks, v_scale=vs)
                worst = max(worst, float(jnp.max(jnp.abs(lg_q - lg_f))))
                tok = np.asarray(nxt, np.int32)
    return worst
