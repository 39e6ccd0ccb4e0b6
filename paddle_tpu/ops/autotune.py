"""Search-based kernel autotuner (ref: ``paddle/phi/kernels/autotune/``
— ``cache.h`` AutoTuneCache, ``auto_tune_base.h`` timing loop, enabled
via ``paddle.incubate.autotune.set_config``).

TPU-native scope: XLA already autotunes its own kernels; what remains is
the choice of PALLAS kernel launch configs (flash-attention block sizes,
fused layernorm row tiles + grid semantics, fused softmax-xent tiles).
Because Pallas calls usually execute inside a jit trace (where nothing
can be timed), tuning is a WARMUP step: :func:`search` runs once per
(shape, dtype, flags) key — candidates are first pruned by a
``cost_model/`` seed (analytic FLOPs/bytes → roofline ordering; configs
whose tiles overflow vmem or underfill the MXU are rejected before any
timing), the survivors are timed eagerly, and the winner is cached for
traced calls to read.

Cache keys include the device kind, jax version, and a per-kernel
schema version, so a cache tuned in CPU interpret mode is never served
to a real TPU run (or to a kernel whose meaning of "config" changed).
The cache persists to JSON (``save_cache``/``load_cache``, or
automatically via the ``PT_AUTOTUNE_CACHE`` env var) and stale entries
are dropped on load rather than crashing.
"""
from __future__ import annotations

import json
import os
import time

__all__ = ["enabled", "set_enabled", "cache_get", "cache_put",
           "cache_clear", "save_cache", "load_cache", "time_candidates",
           "search", "prune_candidates", "roofline_seconds", "device_peaks",
           "analytic_seed", "generate_candidates", "bump_schema",
           "summary", "KERNEL_SCHEMA", "VMEM_LIMIT_BYTES"]

_enabled = False
_cache: dict = {}
_autoloaded = False
_searches: dict = {}          # kernel -> last search stats (bench block)

# Config schema version per kernel: bump when the meaning of a cached
# config tuple changes (e.g. flash_mha grew tuner-owned clamping in v2;
# ln/xent moved from static candidate tables to generated spaces in v2
# so PR 8-era winners can't be served to the generator-backed search).
KERNEL_SCHEMA = {
    "flash_mha": 2,
    "fused_layer_norm": 2,
    "fused_softmax_xent": 2,
    "w8a16_matmul": 1,
    "paged_attention_int8": 1,
}


def bump_schema(kernel: str) -> int:
    """Bump (register-if-new) a kernel's config schema version.

    The schema version is part of every cache key, so bumping it makes
    previously persisted winners invisible to :func:`cache_get` and
    dropped by :func:`load_cache` — the next :func:`search` re-times and
    re-persists under the new version instead of serving a config whose
    meaning changed. Returns the new version."""
    KERNEL_SCHEMA[kernel] = KERNEL_SCHEMA.get(kernel, 1) + 1
    return KERNEL_SCHEMA[kernel]

# ~16 MB vmem/core, minus headroom for Mosaic's own buffers.
VMEM_LIMIT_BYTES = 12 * 1024 * 1024

_ENV_CACHE_VAR = "PT_AUTOTUNE_CACHE"


def enabled() -> bool:
    return _enabled


def set_enabled(flag: bool):
    global _enabled
    _enabled = bool(flag)


# ---------------------------------------------------------------------------
# cache keys + persistence
# ---------------------------------------------------------------------------
def _env_fingerprint():
    """(device_kind, jax_version) of the process — part of every cache
    key so interpret-mode CPU tunings never leak onto real TPUs."""
    import jax
    return str(jax.devices()[0].device_kind), str(jax.__version__)


def _key(kernel: str, key: tuple) -> str:
    kind, ver = _env_fingerprint()
    return json.dumps(
        [kernel, KERNEL_SCHEMA.get(kernel, 1), kind, ver, list(key)])


def _autoload():
    """Lazily pull the persisted cache named by PT_AUTOTUNE_CACHE (if
    any) the first time the cache is consulted, so a second process
    reloads winners without re-searching."""
    global _autoloaded
    if _autoloaded:
        return
    _autoloaded = True
    path = os.environ.get(_ENV_CACHE_VAR)
    if path and os.path.exists(path):
        try:
            load_cache(path)
        except Exception:
            pass


def cache_get(kernel: str, key: tuple):
    _autoload()
    hit = _cache.get(_key(kernel, key))
    return tuple(hit) if hit is not None else None


def cache_put(kernel: str, key: tuple, config):
    _cache[_key(kernel, key)] = list(config)


def cache_clear():
    _cache.clear()
    _searches.clear()


def save_cache(path: str):
    with open(path, "w") as f:
        json.dump(_cache, f)


def load_cache(path: str):
    """Merge a persisted cache, dropping entries whose device kind, jax
    version, or kernel schema no longer match this process (stale keys
    are invalidated, not an error)."""
    with open(path) as f:
        raw = json.load(f)
    kind, ver = _env_fingerprint()
    for k, v in raw.items():
        try:
            kernel, schema, k_kind, k_ver, _ = json.loads(k)
        except Exception:
            continue                      # pre-schema or corrupt entry
        if (k_kind, k_ver) != (kind, ver):
            continue
        if schema != KERNEL_SCHEMA.get(kernel, 1):
            continue
        _cache[k] = v


# ---------------------------------------------------------------------------
# cost-model seed + pruning
# ---------------------------------------------------------------------------
def analytic_seed(fn, *example_args):
    """Seed a kernel's cost function from ``cost_model``: XLA's analytic
    FLOPs/bytes for the pure-jnp reference of the fused cluster. Returns
    ``{"flops", "bytes"}`` or None when the analysis is unavailable (the
    caller falls back to its closed-form estimate)."""
    try:
        from ..cost_model.cost_model import CostModel
        c = CostModel.analytic_cost(fn, *example_args)
        flops = float(c.get("flops", 0.0))
        bytes_ = float(c.get("bytes accessed", c.get("bytes", 0.0)))
        if flops <= 0.0 and bytes_ <= 0.0:
            return None
        return {"flops": flops, "bytes": bytes_}
    except Exception:
        return None


def device_peaks():
    """(peak FLOP/s, peak HBM bytes/s) of the device the candidates run
    on, from the one peak table (``observability.trace``) by
    ``device_kind``; an unknown kind raises."""
    import jax
    from ..observability.trace import peak_flops, peak_hbm_bw
    kind = jax.devices()[0].device_kind
    return peak_flops(kind, strict=True), peak_hbm_bw(kind, strict=True)


def roofline_seconds(flops: float, bytes_: float) -> float:
    """Roofline time estimate: the kernel is bound by whichever of MXU
    throughput or HBM bandwidth it saturates first."""
    peak, bw = device_peaks()
    return max(float(flops) / peak, float(bytes_) / bw)


def prune_candidates(candidates, cost, vmem_limit=None):
    """Filter a candidate list through a per-config cost estimate before
    any timing. ``cost(cfg)`` returns a dict with ``vmem_bytes`` (tile
    working set), ``mxu_underfill`` (tiles below the native compute tile
    → rejected), and ``flops``/``bytes`` feeding the roofline ordering;
    returning None rejects the config outright.

    Returns (survivors_sorted_best_first, pruned_configs)."""
    if vmem_limit is None:
        vmem_limit = VMEM_LIMIT_BYTES
    scored, pruned = [], []
    for cfg in candidates:
        try:
            c = cost(cfg)
        except Exception:
            c = None
        if c is None:
            pruned.append(cfg)
            continue
        if float(c.get("vmem_bytes", 0.0)) > vmem_limit:
            pruned.append(cfg)
            continue
        if c.get("mxu_underfill", False):
            pruned.append(cfg)
            continue
        scored.append((roofline_seconds(c.get("flops", 0.0),
                                        c.get("bytes", 0.0)), cfg))
    scored.sort(key=lambda sc: sc[0])
    return [cfg for _, cfg in scored], pruned


def _tile_options(total: int, align: int):
    """Aligned power-of-two tile sizes up to (and including) ``total``
    rounded up to ``align`` — the hardware-shaped axis walk every
    generated candidate space is built from."""
    cap = max(align, ((int(total) + align - 1) // align) * align)
    out, t = [], align
    while t < cap:
        out.append(t)
        t *= 2
    out.append(cap)
    return sorted(set(out))


def generate_candidates(axes, cost, vmem_limit=None, max_candidates=10):
    """Cost-model-guided candidate *generation* (vs the PR 8 static
    tables): emit launch-config tuples for a fused cluster from its
    shape, prune them through ``cost`` exactly like :func:`search`
    does (vmem overflow / MXU underfill rejected, survivors roofline-
    ordered), and keep the ``max_candidates`` best for timing.

    ``axes`` describes one config-tuple position each, in order:

    - ``("tile", total, align)`` — aligned pow-2 tile sizes covering
      ``total`` (clamped to its padded extent),
    - ``("choice", (a, b, ...))`` — an enumerated option (e.g. the
      parallel/arbitrary grid-semantics bit).

    Returns the survivors best-roofline-first; raises when the cost
    model prunes every generated config (same contract as search)."""
    import itertools
    options = []
    for ax in axes:
        kind = ax[0]
        if kind == "tile":
            _, total, align = ax
            options.append(_tile_options(total, align))
        elif kind == "choice":
            options.append(list(ax[1]))
        else:
            raise ValueError(f"unknown candidate axis kind {kind!r}")
    cands = [tuple(c) for c in itertools.product(*options)]
    survivors, pruned = prune_candidates(cands, cost, vmem_limit)
    if not survivors:
        raise RuntimeError(
            f"autotune: candidate generator pruned every config "
            f"({len(pruned)} generated and rejected)")
    return survivors[:max_candidates]


# ---------------------------------------------------------------------------
# timing + search
# ---------------------------------------------------------------------------
def time_candidates(run, candidates, warmup=1, iters=3):
    """Pick the fastest config: ``run(config)`` must execute the kernel
    and block until ready (ref ``auto_tune_base.h`` RunAndMeasureKernel).
    Returns (best_config, {config: seconds}). Configs that fail to
    compile/run are skipped."""
    timings = {}
    best, best_t = None, float("inf")
    for cfg in candidates:
        try:
            for _ in range(warmup):
                run(cfg)
            t0 = time.perf_counter()
            for _ in range(iters):
                run(cfg)
            dt = (time.perf_counter() - t0) / iters
        except Exception:
            continue
        timings[cfg] = dt
        if dt < best_t:
            best, best_t = cfg, dt
    if best is None:
        raise RuntimeError("no autotune candidate ran successfully")
    return best, timings


def _metrics():
    try:
        from ..observability.metrics import get_registry
        from ..observability.telemetry import get_telemetry
        if not get_telemetry().enabled:
            return None, None, None
        reg = get_registry()
        return (reg.counter("pt_autotune_cache_hits_total",
                            "Autotune searches answered from cache",
                            labelnames=("kernel",)),
                reg.counter("pt_autotune_cache_misses_total",
                            "Autotune searches that had to time candidates",
                            labelnames=("kernel",)),
                reg.counter("pt_autotune_search_seconds",
                            "Wall seconds spent timing autotune candidates",
                            labelnames=("kernel",)))
    except Exception:
        return None, None, None


def search(kernel: str, key: tuple, run, candidates, cost=None,
           vmem_limit=None, warmup=1, iters=3):
    """The tuner's front door: cache hit → return the cached winner
    without running anything; miss → prune ``candidates`` through
    ``cost`` (see :func:`prune_candidates`), time the survivors with
    ``run``, cache + (if ``PT_AUTOTUNE_CACHE`` is set) persist the
    winner. Returns (best_config, {config: seconds}) — timings empty on
    a cache hit."""
    hits, misses, secs = _metrics()
    cached = cache_get(kernel, key)
    if cached is not None:
        if hits is not None:
            hits.inc(kernel=kernel)
        return cached, {}
    if misses is not None:
        misses.inc(kernel=kernel)

    candidates = list(candidates)
    if cost is not None:
        survivors, pruned = prune_candidates(candidates, cost, vmem_limit)
    else:
        survivors, pruned = candidates, []
    if not survivors:
        raise RuntimeError(
            f"autotune[{kernel}]: cost model pruned every candidate "
            f"({len(pruned)} rejected)")

    t0 = time.perf_counter()
    best, timings = time_candidates(run, survivors, warmup=warmup,
                                    iters=iters)
    elapsed = time.perf_counter() - t0
    if secs is not None:
        secs.inc(elapsed, kernel=kernel)

    cache_put(kernel, key, best)
    _searches[kernel] = {
        "key": list(key),
        "best": list(best),
        "search_seconds": elapsed,
        "timed": len(timings),
        "pruned": len(pruned),
    }
    path = os.environ.get(_ENV_CACHE_VAR)
    if path:
        try:
            save_cache(path)
        except Exception:
            pass
    return tuple(best), timings


def summary():
    """Per-kernel stats of the searches this process ran (winning
    config, search seconds, timed/pruned counts) — attached to bench
    records as the ``autotune`` block."""
    return {k: dict(v) for k, v in _searches.items()}
