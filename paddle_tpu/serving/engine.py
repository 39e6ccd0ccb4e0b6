"""AOT-compiled serving engine: per-bucket zero-compile serve graphs.

At construction the engine lowers+compiles every program it will ever
run — one prefill executable per sequence bucket and one decode
executable per batch bucket — then executes each once (warmup) and
arms the **serve compile sentinel**: from that point, any compile
observed in the process books ``pt_serve_unexpected_compiles_total``
and flips ``/healthz`` to 503.  The PR 3 recompile sentinel thereby
becomes an SLO alarm: on a serving box, a compile IS an incident.

Request-path discipline that keeps the sentinel quiet (enforced by
tpu-lint TPU019): the scheduler/HTTP layers touch only numpy and the
pre-compiled executables.  Even a stray ``jnp.asarray`` on the request
path would book a tiny convert/copy compile.

KV state is donated: each executable takes the pool arrays, writes the
step's K/V in place (XLA aliases the buffers — the PR 7 capture
convention), and the engine rebinds the pool to the returned arrays.
What says the donation holds is what each program keeps in memory: the
build records every executable's temporary, argument and aliased bytes
(``engine.stats["program_bytes"]``, ``/healthz``,
``pt_serve_program_bytes{program=,kind=}``).  A program that copied,
sliced or re-laid a pool would show a pool-sized temporary there.
Beside it, ``engine.stats["paged_walk"]`` (also ``/healthz``) says what
the paged-attention kernels of each decode program walk: the tokens a
grid step meets and the grid's length, of the work list over the full
layers' pool and (``"window"``) of the one over the sliding layers'; the
scheduler sums both a decode step and a list (``paged_chunks_walked``,
``paged_grid_steps``).

Zero-downtime weight swap: with a ``CheckpointManager`` attached,
:meth:`ServingEngine.maybe_reload` hot-swaps to generation N+1 between
steps while requests keep flowing — same program executables, new
param buffers (no recompile: shapes are the signature, not values).
"""
from __future__ import annotations

import contextlib
import json
import logging
import os
import threading
import warnings
from dataclasses import dataclass, asdict
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..observability.trace import span
from .kv_cache import PagePool, NULL_PAGE, kv_page_budget
from .model import (ModelSpec, decode_step, decode_walk, init_params,
                    prefill_step)

PRECISIONS = ("fp32", "bf16", "int8")

logger = logging.getLogger("paddle_tpu.serving")

__all__ = ["ServeConfig", "ServingEngine", "DecodeStep",
           "save_served_model", "load_engine", "SERVE_CONFIG_NAME"]

SERVE_CONFIG_NAME = "serve_config.json"

# CPU/interpret runs can't honor every donation; the engine's rebind
# protocol is correct either way (the capture-layer convention)
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")

# >0 while ANY engine in the process is inside its sanctioned AOT
# build; armed sentinels ignore those compiles (a second engine coming
# up — blue/green, tests — is not a request-path incident)
_AOT_BUILD_DEPTH = 0
_AOT_BUILD_LOCK = threading.Lock()


@contextlib.contextmanager
def aot_build_phase():
    """Mark the enclosed work as a sanctioned (non-request-path) compile
    phase.  Engine construction uses it, and so does the PTQ tooling
    (``serving/quant.py``) whose eager calibration/quality replays must
    not book ``pt_serve_unexpected_compiles_total`` on a live engine."""
    global _AOT_BUILD_DEPTH
    with _AOT_BUILD_LOCK:
        _AOT_BUILD_DEPTH += 1
    try:
        yield
    finally:
        with _AOT_BUILD_LOCK:
            _AOT_BUILD_DEPTH -= 1


def _aux_counters(counts) -> Dict[str, int]:
    """Expert counts ``(L, E)`` of one program call as the scheduler's
    counters (:meth:`ServingEngine.take_aux`); ``{}`` for None."""
    if counts is None:
        return {}
    counts = np.asarray(counts)
    return {"moe_tokens_routed": int(counts.sum()),
            "moe_expert_max_tokens": int(counts.max(axis=1).sum()),
            "moe_experts_touched": int(np.count_nonzero(counts))}


class DecodeStep:
    """One launched decode step: what :meth:`ServingEngine.decode` hands
    back without waiting for the device.

    The step's tokens are a device array whose copy to the host started
    behind the program.  :meth:`read` (and ``np.asarray(step)``, and
    indexing or iterating it) waits for them, inside a
    ``serve.decode.fetch`` span, and gives the ``(rows,)`` int32 tokens
    of the rows the call was made with, in the call's order; the second
    read is free.  Whoever made the call may read it, once the call has
    returned, from any thread; the scheduler reads a step after it has
    launched the next one (``engine.decode_from``).  ``bucket`` is the
    program's batch, ``slots`` where in it each of the call's rows sat,
    ``seq`` the engine's number of the program call that launched it
    (:attr:`ServingEngine.launches`; the ``launch=`` of its spans, the
    ``fetch`` of its read among them), and :meth:`take_aux` what this
    step's routed experts counted."""

    __slots__ = ("rows", "bucket", "slots", "seq", "_tokens", "_aux",
                 "_host")

    def __init__(self, tokens, aux, slots: np.ndarray, bucket: int,
                 seq: int = 0):
        self.rows = int(slots.shape[0])
        self.bucket = bucket
        self.slots = slots
        self.seq = seq
        self._tokens = tokens       # (bucket,) on the device
        self._aux = aux             # (L, E) on the device, or None
        self._host: Optional[np.ndarray] = None

    def read(self) -> np.ndarray:
        if self._host is None:
            with span("serve.decode.fetch", rows=self.rows,
                      bucket=self.bucket, launch=self.seq):
                self._host = np.asarray(self._tokens)[self.slots]
        return self._host

    def take_aux(self) -> Dict[str, int]:
        """:meth:`ServingEngine.take_aux` of this step (waits for it)."""
        return _aux_counters(self._aux)

    def __array__(self, dtype=None, copy=None):
        out = self.read()
        return out if dtype is None else out.astype(dtype)

    def __len__(self) -> int:
        return self.rows

    def __iter__(self):
        return iter(self.read())

    def __getitem__(self, index):
        return self.read()[index]


def _env_int(name: str, default: int) -> int:
    v = os.environ.get(name)
    return int(v) if v else default


def _env_float(name: str, default: float) -> float:
    v = os.environ.get(name)
    return float(v) if v else default


def _env_buckets(name: str, default: Tuple[int, ...]) -> Tuple[int, ...]:
    v = os.environ.get(name)
    if not v:
        return tuple(default)
    return tuple(int(x) for x in v.replace(";", ",").split(",") if x.strip())


@dataclass(frozen=True)
class ServeConfig:
    """Engine shape/capacity configuration.

    Every field has an env override (read by :meth:`from_env`) so a
    deployment can retune the ladder without touching the served model
    dir:

      PT_SERVE_BUCKETS          decode batch ladder, e.g. "2,4,8,16"
      PT_SERVE_PREFILL_BUCKETS  prompt seq ladder, e.g. "16,32,64"
      PT_SERVE_KV_PAGES         total pool pages (incl. null page)
      PT_SERVE_PAGE_SIZE        tokens per page
      PT_SERVE_MAX_INFLIGHT     admission cap (queued + active)
      PT_SERVE_DEADLINE_MS      server-default request deadline (0 = none)
      PT_SERVE_MAX_QUEUE        bounded admission queue (0 = unbounded)
      PT_SERVE_DRAIN_S          graceful-drain budget on SIGTERM
      PT_SERVE_PRECISION        serve numerics: fp32 | bf16 | int8

    ``kv_pages`` is denominated in fp32 pages (a byte budget): lower
    precisions scale the physical page count up at pool construction
    (:func:`.kv_cache.kv_page_budget`), which is where the int8 mode's
    ~2x+ admission headroom comes from.  The pool of a model's
    sliding-window layers is not configured: it is sized for the largest
    decode bucket, every row holding its window plus a page.
    """

    decode_buckets: Tuple[int, ...] = (2, 4, 8, 16)
    prefill_buckets: Tuple[int, ...] = (16, 32, 64)
    kv_pages: int = 128
    page_size: int = 16
    max_inflight: int = 64
    max_new_tokens: int = 32
    eos_id: int = -1          # <0: never stops early (length-bounded)
    deadline_ms: float = 0.0  # server default; 0 = no deadline
    max_queue: int = 256      # bounded queue; 0 = unbounded
    drain_s: float = 10.0     # SIGTERM drain budget (seconds)
    precision: str = "fp32"   # fp32 | bf16 | int8

    @classmethod
    def from_env(cls, **overrides) -> "ServeConfig":
        base = cls(
            decode_buckets=_env_buckets(
                "PT_SERVE_BUCKETS", cls.decode_buckets),
            prefill_buckets=_env_buckets(
                "PT_SERVE_PREFILL_BUCKETS", cls.prefill_buckets),
            kv_pages=_env_int("PT_SERVE_KV_PAGES", cls.kv_pages),
            page_size=_env_int("PT_SERVE_PAGE_SIZE", cls.page_size),
            max_inflight=_env_int("PT_SERVE_MAX_INFLIGHT",
                                  cls.max_inflight),
            max_new_tokens=_env_int("PT_SERVE_MAX_NEW_TOKENS",
                                    cls.max_new_tokens),
            eos_id=_env_int("PT_SERVE_EOS_ID", cls.eos_id),
            deadline_ms=_env_float("PT_SERVE_DEADLINE_MS",
                                   cls.deadline_ms),
            max_queue=_env_int("PT_SERVE_MAX_QUEUE", cls.max_queue),
            drain_s=_env_float("PT_SERVE_DRAIN_S", cls.drain_s),
            precision=os.environ.get("PT_SERVE_PRECISION") or cls.precision,
        )
        return base.replace(**overrides) if overrides else base

    def replace(self, **kw) -> "ServeConfig":
        d = asdict(self)
        d.update(kw)
        d["decode_buckets"] = tuple(d["decode_buckets"])
        d["prefill_buckets"] = tuple(d["prefill_buckets"])
        return ServeConfig(**d)

    def to_dict(self) -> Dict[str, Any]:
        d = asdict(self)
        d["decode_buckets"] = list(self.decode_buckets)
        d["prefill_buckets"] = list(self.prefill_buckets)
        return d

    @classmethod
    def from_dict(cls, d: Dict[str, Any]) -> "ServeConfig":
        names = set(cls.__dataclass_fields__)
        kw = {k: v for k, v in d.items() if k in names}
        for key in ("decode_buckets", "prefill_buckets"):
            if key in kw:
                kw[key] = tuple(int(x) for x in kw[key])
        return cls(**kw)

    def normalized(self, spec: ModelSpec) -> "ServeConfig":
        """Clamp the ladders to what the model/pool can serve.

        Decode buckets are clamped to >= 2: XLA's batch-1 gemv path
        has a different reduction order, and bit-identical decode
        across batch compositions (the continuous-batching contract)
        only holds for matmul-shaped batches.  A solo sequence decodes
        in a 2-bucket with a null padding row instead.
        """
        dec = sorted({max(2, int(b)) for b in self.decode_buckets})
        pre = sorted({int(s) for s in self.prefill_buckets
                      if int(s) <= spec.max_seq_len})
        if not pre:
            pre = [spec.max_seq_len]
        if self.page_size < 1:
            raise ValueError("page_size must be >= 1")
        if self.precision not in PRECISIONS:
            raise ValueError(
                f"precision {self.precision!r} not in {PRECISIONS}")
        if self.precision == "int8" and (
                spec.ffn != "gelu" or spec.diff_attn
                or len(spec.global_layers) != spec.layers
                or spec.n_kv_heads != spec.heads):
            raise ValueError(
                "int8 serving covers equal heads, full layers and the "
                "GELU FFN only")
        return self.replace(decode_buckets=tuple(dec),
                            prefill_buckets=tuple(pre))


def _struct_like(tree):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)


def _to_serve_device(tree):
    # pin to ONE device: the executables are compiled against
    # SingleDeviceSharding, but checkpoint restores (and callers running
    # under a distributed mesh) may hand us NamedSharded arrays
    dev = jax.local_devices()[0]
    return jax.tree_util.tree_map(lambda a: jax.device_put(a, dev), tree)


class ServingEngine:
    """Programs + paged KV pool + hot-swappable weights.

    The request path (scheduler / HTTP) calls :meth:`prefill` and
    :meth:`decode`, which only ever touch numpy and the AOT-compiled
    executables built in ``_build_programs``.
    """

    def __init__(self, spec: ModelSpec, params, config: ServeConfig = None,
                 checkpoint_manager=None, weights_step: Optional[int] = None):
        self.spec = spec
        self.config = (config or ServeConfig.from_env()).normalized(spec)
        self.checkpoint_manager = checkpoint_manager
        self.max_pages_per_seq = -(-spec.max_seq_len // self.config.page_size)
        # the whole construction is a sanctioned build phase: pool
        # creation (jnp.zeros fill) and warmup compile too, and must not
        # trip an already-armed sentinel on another live engine
        with aot_build_phase():
            prec = self.config.precision
            kv_dtype = {"fp32": jnp.float32, "bf16": jnp.bfloat16,
                        "int8": jnp.int8}[prec]
            # the configured kv_pages is an fp32 byte budget — lower
            # precisions buy more physical pages for the same spend,
            # which is the admission-headroom win the bench measures
            n_window = len(spec.window_layers)
            # the sliding layers' pool: the largest bucket, every row at
            # its most (its window's span plus a page), and the null page
            window_pages = 1 + self.config.decode_buckets[-1] * min(
                self.max_pages_per_seq,
                -(-spec.window // self.config.page_size) + 1) \
                if n_window else 0
            # the state-space layers' slots: one a row of the largest
            # bucket, and the null slot; a KDA layer's is (heads, K, V)
            # beside the tails of its q, k and v
            kda = spec.kda_heads * spec.kda_head_dim \
                if spec.kda_layers else 0
            self.pool = PagePool(
                layers=len(spec.global_layers),
                pages=kv_page_budget(self.config.kv_pages, prec,
                                     spec.head_dim, spec.n_kv_heads),
                page_size=self.config.page_size, heads=spec.n_kv_heads,
                head_dim=spec.head_dim, dtype=kv_dtype,
                scale_pages=(prec == "int8"), window_layers=n_window,
                window_pages=window_pages, window=spec.window,
                state_layers=len(spec.ssm_layers),
                state_slots=1 + self.config.decode_buckets[-1],
                state_shape=((kda, 0, spec.kda_conv) if kda else
                             (spec.ssm_inner, spec.ssm_state,
                              spec.ssm_conv)),
                state_conv_channels=(3 * kda if kda
                                     else spec.ssm_conv_channels),
                state_heads=spec.ssm_heads,
                state_per_slot=((spec.kda_heads, spec.kda_head_dim,
                                 spec.kda_head_dim) if kda else ()),
                index_dim=(spec.index_head_size if spec.sparse_topk
                           else 0),
                latent=spec.latent_lanes if spec.mla_layers else ())
            # the page table the programs take: one row of pages, or the
            # full layers' row above the sliding layers' (above the row
            # that holds the state slot)
            self.table_shape = self.pool.table_shape(self.max_pages_per_seq)
            self._aux = None    # the last call's expert counts (L, E)
            self._params = _to_serve_device(self._prepare_params(params))
            self._weights_step = weights_step
            self._weights_lock = threading.Lock()
            self.unexpected_compiles = 0
            # the request the scheduler is about to prefill: names the
            # prefill spans (set by the scheduler's one thread, under
            # its lock; None for a direct caller)
            self.prefill_request_id: Optional[int] = None
            # the same hand-over for the next :meth:`decode` call alone:
            # ``(step, keep)`` says its rows are rows ``keep`` of that
            # :class:`DecodeStep`'s call and take their tokens from it,
            # on the device
            self.decode_from: Optional[Tuple[DecodeStep, np.ndarray]] = None
            # program calls so far (:attr:`launches`): the warm-up's and
            # the checks' count too
            self._launches = 0
            self._warmed = False
            self._prefill_exe: Dict[int, Any] = {}
            self._decode_exe: Dict[int, Any] = {}
            self._decode_walk: Dict[int, Dict[str, Any]] = {}
            self._selection_exe = None      # decode_selection's
            self.compiled_programs = 0
            # program name -> {"temp", "argument", "alias"} bytes, from
            # each executable's memory_analysis() at build
            # and program name -> {"chunk_tokens", "grid_steps"}, the
            # tokens a grid step of decode's paged-attention kernel meets
            # and its grid length (model.decode_walk: the sliding
            # layers' list under "window"; no entry for an int8 pool)
            self.stats: Dict[str, Any] = {"program_bytes": {},
                                          "paged_walk": {}}
            self._build_programs()
            self._warmup()
        self._arm_sentinel()
        from .scheduler import ContinuousScheduler
        self.scheduler = ContinuousScheduler(self)

    def _prepare_params(self, params):
        """Convert an incoming weight tree to the engine's precision.

        int8: deterministic inline PTQ (same weights always quantize to
        the same bytes, so an fp32 dir served under
        ``PT_SERVE_PRECISION=int8`` matches a saved quantized dir bit
        for bit); already-quantized trees pass through.  bf16: cast
        every float leaf.  fp32: identity.
        """
        prec = self.config.precision
        if prec == "int8":
            from . import quant as _quant
            if not _quant.is_quantized_params(params):
                params = _quant.quantize_params(params, self.spec)
            return params
        if prec == "bf16":
            return jax.tree_util.tree_map(
                lambda a: a.astype(jnp.bfloat16)
                if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating)
                else a, dict(params))
        return params

    # -- AOT build (the only place that is ALLOWED to compile) --------------

    def _build_programs(self) -> None:
        """Lower+compile the full program ladder ahead of time."""
        with aot_build_phase():
            self._build_programs_inner()

    def _build_programs_inner(self) -> None:
        spec, cfg = self.spec, self.config
        ps = cfg.page_size
        int8 = cfg.precision == "int8"
        p_struct = _struct_like(self._params)
        k_struct = _struct_like(self.pool.state()[0])
        s_struct = _struct_like(self.pool.k_scale) if int8 else None
        i32 = np.int32
        # fp32 keeps its PR 15 program names so audit/bench baselines
        # stay comparable; other precisions are distinct programs
        sfx = "" if cfg.precision == "fp32" else f"_{cfg.precision}"

        # the two jitted functions are named like the programs they build
        # (serve_prefill_s<S> / serve_decode_b<B>), so jax's compile log,
        # the compile watcher and profiler traces all say "serve_*"
        if self.pool.state_slots is not None \
                or self.pool.index_pool is not None:
            # every pool the model has is donated state, in the order of
            # PagePool.state(): the full layers' (one token-major pool of
            # K and V beside an index pool), the index pool, the sliding
            # layers' where there are any, the state-space layers' two last
            kv = 1 if self.pool.token_major else 2
            names = ("kv_pool",) if kv == 1 else ("k_pool", "v_pool")
            names += (
                ("index_pool",) if self.pool.index_pool is not None else ()
            ) + (("kw_pool", "vw_pool") if self.pool.window_pool else ()
                 ) + (("conv_pool", "ssm_pool")
                      if self.pool.state_slots is not None else ())
            n_state = len(names)

            def serve_prefill(params, *args):
                k_pool, v_pool = (*args[:kv], None)[:2]
                return prefill_step(spec, params, k_pool, v_pool,
                                    *args[n_state:], page_size=ps,
                                    **dict(zip(names[kv:],
                                               args[kv:n_state])))

            def serve_decode(params, *args, **kw):
                k_pool, v_pool = (*args[:kv], None)[:2]
                return decode_step(spec, params, k_pool, v_pool,
                                   *args[n_state:], page_size=ps,
                                   **dict(zip(names[kv:], args[kv:n_state])),
                                   **kw)

            def serve_decode_selection(params, *args):
                return serve_decode(params, *args, selection=True)

            donate = tuple(range(1, 1 + n_state))
            labels = ("params",) + names + ("tokens", "positions",
                                            "page_tables")
            kv_args = tuple(_struct_like(a) for a in self.pool.state())
        elif int8:
            # the scale pools are donated state exactly like the value
            # pools — the step rewrites both and the engine rebinds all
            # four (donate_argnums covers 1..4)
            def serve_prefill(params, k_pool, v_pool, k_scale, v_scale,
                              tokens, length, page_table):
                return prefill_step(spec, params, k_pool, v_pool, tokens,
                                    length, page_table, page_size=ps,
                                    k_scale=k_scale, v_scale=v_scale)

            def serve_decode(params, k_pool, v_pool, k_scale, v_scale,
                             tokens, positions, page_tables):
                return decode_step(spec, params, k_pool, v_pool, tokens,
                                   positions, page_tables, page_size=ps,
                                   k_scale=k_scale, v_scale=v_scale)

            donate = (1, 2, 3, 4)
            labels = ("params", "k_pool", "v_pool", "k_scale", "v_scale",
                      "tokens", "positions", "page_tables")
            kv_args = (k_struct, k_struct, s_struct, s_struct)
        elif self.pool.window_pool is not None:
            # the sliding layers' pools are donated state like the full
            # layers', after them in the argument order
            def serve_prefill(params, k_pool, v_pool, kw_pool, vw_pool,
                              tokens, length, page_table):
                return prefill_step(spec, params, k_pool, v_pool, tokens,
                                    length, page_table, page_size=ps,
                                    kw_pool=kw_pool, vw_pool=vw_pool)

            def serve_decode(params, k_pool, v_pool, kw_pool, vw_pool,
                             tokens, positions, page_tables):
                return decode_step(spec, params, k_pool, v_pool, tokens,
                                   positions, page_tables, page_size=ps,
                                   kw_pool=kw_pool, vw_pool=vw_pool)

            donate = (1, 2, 3, 4)
            labels = ("params", "k_pool", "v_pool", "kw_pool", "vw_pool",
                      "tokens", "positions", "page_tables")
            w_struct = _struct_like(self.pool.window_pool.k_pool)
            kv_args = (k_struct, k_struct, w_struct, w_struct)
        else:
            def serve_prefill(params, k_pool, v_pool, tokens, length,
                              page_table):
                return prefill_step(spec, params, k_pool, v_pool, tokens,
                                    length, page_table, page_size=ps)

            def serve_decode(params, k_pool, v_pool, tokens, positions,
                             page_tables):
                return decode_step(spec, params, k_pool, v_pool, tokens,
                                   positions, page_tables, page_size=ps)

            donate = (1, 2)
            labels = ("params", "k_pool", "v_pool", "tokens",
                      "positions", "page_tables")
            kv_args = (k_struct, k_struct)

        pf_jit = jax.jit(serve_prefill, donate_argnums=donate)
        dec_jit = jax.jit(serve_decode, donate_argnums=donate)

        # graph audit (tools/audit): when enabled, every bucket
        # program's traced jaxpr is audited during the build — load
        # time only, sharing the trace the AOT lower needs anyway.
        # The donation layout handed over mirrors donate_argnums.
        aud = None
        from ..tools.audit import runtime as _audit_rt
        if _audit_rt.audit_enabled():
            aud = _audit_rt
            n_p = len(jax.tree_util.tree_leaves(p_struct))
            n_kv = len(kv_args)

        def _compile(jitted, name, *args):
            if aud is None:
                exe = jitted.lower(*args).compile()
            else:
                traced = jitted.trace(*args)
                aud.audit_serve_trace(name, traced.jaxpr, n_p, n_kv,
                                      args, labels=labels)
                exe = traced.lower().compile()
            self._account_compile(name)
            self._record_program_bytes(name, exe)
            return exe

        for s in cfg.prefill_buckets:
            self._prefill_exe[s] = _compile(
                pf_jit, f"serve_prefill_s{s}{sfx}",
                p_struct, *kv_args,
                jax.ShapeDtypeStruct((s,), i32),
                jax.ShapeDtypeStruct((), i32),
                jax.ShapeDtypeStruct(self.table_shape, i32))

        for b in cfg.decode_buckets:
            walk = decode_walk(
                spec, b, k_struct, self.max_pages_per_seq,
                _struct_like(self.pool.window_pool.k_pool)
                if self.pool.window_pool is not None else None)
            if walk is not None:
                self._decode_walk[b] = self.stats["paged_walk"][
                    f"serve_decode_b{b}{sfx}"] = walk
            self._decode_exe[b] = _compile(
                dec_jit, f"serve_decode_b{b}{sfx}",
                p_struct, *kv_args,
                jax.ShapeDtypeStruct((b,), i32),
                jax.ShapeDtypeStruct((b,), i32),
                jax.ShapeDtypeStruct((b, *self.table_shape), i32))

        if spec.sparse_topk or spec.mla_layers:
            # the largest bucket's step once more, with what its sparse
            # layers scored and selected, or what its routers chose and its
            # latent layers' heads gave, among its outputs: a check's
            # program (decode_selection), not the scheduler's
            b = cfg.decode_buckets[-1]
            self._selection_exe = _compile(
                jax.jit(serve_decode_selection, donate_argnums=donate),
                f"serve_decode_b{b}{sfx}_selection", p_struct, *kv_args,
                jax.ShapeDtypeStruct((b,), i32),
                jax.ShapeDtypeStruct((b,), i32),
                jax.ShapeDtypeStruct((b, *self.table_shape), i32))

        self.compiled_programs = (len(self._prefill_exe)
                                  + len(self._decode_exe)
                                  + (self._selection_exe is not None))
        logger.info(
            "serve programs compiled: %d prefill buckets %s, %d decode "
            "buckets %s", len(self._prefill_exe),
            list(cfg.prefill_buckets), len(self._decode_exe),
            list(cfg.decode_buckets))

    def _account_compile(self, name: str) -> None:
        """Book load-time compiles on the standard compile feed (only
        when the log watcher isn't already counting them — the capture
        layer convention)."""
        try:
            from ..observability.telemetry import get_telemetry
            tel = get_telemetry()
            if not tel._watcher.installed:
                tel.record_compile(name, signature="aot-build")
        except Exception:
            pass

    def _record_program_bytes(self, name: str, exe) -> None:
        """Book what the compiled program keeps in memory: temporaries,
        arguments, and the argument bytes it aliases to outputs (the
        donated pools, when the donation reached the executable).  A
        backend that gives no memory analysis leaves the entry out."""
        try:
            mem = exe.memory_analysis()
            got = {"temp": int(mem.temp_size_in_bytes),
                   "argument": int(mem.argument_size_in_bytes),
                   "alias": int(mem.alias_size_in_bytes)}
        except Exception:
            return
        self.stats["program_bytes"][name] = got
        try:
            from ..observability.metrics import get_registry
            from ..observability.telemetry import get_telemetry
            if get_telemetry().enabled:
                g = get_registry().gauge(
                    "pt_serve_program_bytes",
                    "Bytes a compiled serve program holds, by kind",
                    labelnames=("program", "kind"))
                for kind, n in got.items():
                    g.set(n, program=name, kind=kind)
        except Exception:
            pass

    def _kv_state(self):
        """The donated pool arrays in program argument order (value
        pools, plus scale pools on a quantized engine or the sliding
        layers' pools)."""
        return self.pool.state()

    def _run(self, exe, params, *args):
        """Call one program and rebind the pools to what it returns:
        ``(token(s), logits)`` still on the device (then what the
        selection program adds).  A model of routed experts also returns
        its tokens per expert, kept for :meth:`take_aux`."""
        self._launches += 1
        state = self._kv_state()
        out = exe(params, *state, *args)
        self.pool.swap(*out[:len(state)])
        rest = out[len(state):]
        self._aux = rest[2] if len(rest) > 2 else None
        # what the caller reads next is copied to the host behind the
        # program, not on demand after it: one latency a step less
        rest[0].copy_to_host_async()
        if self._aux is not None:
            self._aux.copy_to_host_async()
        return (rest[0], rest[1], *rest[3:])

    @property
    def launches(self) -> int:
        """Program calls so far: the number of the last one launched.
        Every call is numbered, whoever makes it; its spans carry the
        number as ``launch=``, a decode step keeps it as
        :attr:`DecodeStep.seq`, and right after :meth:`prefill` returns
        it is that prefill's."""
        return self._launches

    def expert_counts(self) -> Optional[np.ndarray]:
        """Tokens the last program call routed to each expert of each
        layer, ``(L, E)`` (padding not counted); None for a model
        without routed experts."""
        return None if self._aux is None else np.asarray(self._aux)

    def take_aux(self) -> Dict[str, int]:
        """Counters of the last program call beside its tokens (a decode
        step's own are its :meth:`DecodeStep.take_aux`): for
        routed experts the token-expert pairs it routed
        (``moe_tokens_routed``, summed over layers), its busiest
        expert's tokens summed over layers (``moe_expert_max_tokens``)
        and how many (layer, expert) pairs got any token
        (``moe_experts_touched``)."""
        return _aux_counters(self._aux)

    def _warmup(self) -> None:
        """Execute every program once so first-request latency pays no
        lazy initialization, and the sentinel can be armed on a
        provably quiet path.  Warmup traffic writes only the null page."""
        for s, exe in self._prefill_exe.items():
            self._run(exe, self._params, np.zeros((s,), np.int32),
                      np.int32(1), np.zeros(self.table_shape, np.int32))
        last = self.config.decode_buckets[-1]
        for b, exe in [*self._decode_exe.items(),
                       (last, self._selection_exe)]:
            if exe is not None:
                self._run(exe, self._params, np.zeros((b,), np.int32),
                          np.zeros((b,), np.int32),
                          np.zeros((b, *self.table_shape), np.int32))
        self._aux = None
        jax.block_until_ready(self.pool.state())

    def _arm_sentinel(self) -> None:
        """After this point, ANY observed compile is a request-path
        compile: book it and trip health."""
        try:
            from ..observability.telemetry import get_telemetry
            tel = get_telemetry()
            tel.ensure_compile_watch()
            tel.add_compile_listener(self._on_compile_event)
        except Exception:
            logger.exception("serve compile sentinel not armed")
        self._warmed = True

    def _on_compile_event(self, name: str, signature: str = "") -> None:
        if not self._warmed or _AOT_BUILD_DEPTH > 0:
            return
        self.unexpected_compiles += 1
        logger.warning(
            "unexpected request-path compile: %s — the serve ladder "
            "should cover every shape; /healthz now degraded", name)
        try:
            from ..observability.metrics import get_registry
            from ..observability.telemetry import get_telemetry
            if get_telemetry().enabled:
                get_registry().counter(
                    "pt_serve_unexpected_compiles_total",
                    "Compiles observed after serve warmup (SLO alarm)",
                    labelnames=("fn",)).inc(fn=name)
        except Exception:
            pass

    def close(self) -> None:
        try:
            from ..observability.telemetry import get_telemetry
            get_telemetry().remove_compile_listener(self._on_compile_event)
        except Exception:
            pass

    # -- request path (numpy + compiled executables ONLY) -------------------

    def prefill_bucket_for(self, n: int) -> int:
        for s in self.config.prefill_buckets:
            if n <= s:
                return s
        raise ValueError(
            f"prompt length {n} exceeds largest prefill bucket "
            f"{self.config.prefill_buckets[-1]}")

    def decode_bucket_for(self, n: int) -> int:
        for b in self.config.decode_buckets:
            if n <= b:
                return b
        raise ValueError(
            f"{n} active sequences exceed largest decode bucket "
            f"{self.config.decode_buckets[-1]}")

    def paged_walk_for(self, n: int) -> Optional[Dict[str, int]]:
        """What the paged-attention kernels of the program that decodes
        ``n`` rows walk (``stats["paged_walk"]``); ``None`` where decode
        walks no work list (an int8 pool)."""
        return self._decode_walk.get(self.decode_bucket_for(n))

    def prefill(self, tokens: Sequence[int],
                page_table: np.ndarray) -> int:
        """Run one prompt; returns the first generated token.

        ``page_table`` is the sequence's :attr:`RowPages.table
        <paddle_tpu.serving.kv_cache.RowPages>` (``table_shape``).
        Three leaf spans (``observability.trace.span``): ``.prep`` is
        the padding, ``.launch`` the executable call until it returns,
        ``.fetch`` the pool rebind and the token's device-to-host copy
        (which waits for the program).  They carry the ``request_id``
        the scheduler left in ``prefill_request_id`` and ``launch``, the
        call's number (:attr:`launches`)."""
        return self._prefill(tokens, page_table, False)[0]

    def prefill_logits(self, tokens: Sequence[int],
                       page_table: np.ndarray) -> Tuple[int, np.ndarray]:
        """:meth:`prefill`, and the logits ``(V,)`` float32 the first
        token was sampled from — through the same executable and cache
        the scheduler uses, for checks against a reference."""
        return self._prefill(tokens, page_table, True)

    def _prefill(self, tokens, page_table, want_logits):
        rid, seq = self.prefill_request_id, self._launches + 1
        with span("serve.prefill.prep", request_id=rid, launch=seq):
            n = len(tokens)
            s = self.prefill_bucket_for(n)
            padded = np.zeros((s,), np.int32)
            padded[:n] = np.asarray(tokens, np.int32)
            table = np.asarray(page_table, np.int32)
            with self._weights_lock:
                params = self._params
        with span("serve.prefill.launch", request_id=rid, launch=seq):
            nxt, logits = self._run(self._prefill_exe[s], params, padded,
                                    np.int32(n), table)
        with span("serve.prefill.fetch", request_id=rid, launch=seq):
            return int(nxt), (np.asarray(logits, np.float32)
                              if want_logits else None)

    def decode(self, tokens: np.ndarray, positions: np.ndarray,
               page_tables: np.ndarray) -> DecodeStep:
        """Launch one decode step over ``n`` active rows, padded to a
        bucket, and return without waiting for it: a :class:`DecodeStep`
        that resolves to the rows' ``(n,)`` next tokens when asked
        (``np.asarray(engine.decode(...))`` is the synchronous call).

        Padding rows carry position 0 + the all-null page table, so
        their (garbage) K/V writes land in the null page.  Leaf spans
        as in :meth:`prefill`, carrying ``rows``, ``bucket`` and
        ``launch``: ``.prep`` and ``.launch`` here, ``.fetch`` where the
        step is read, under the number of the launch it waits for.

        With ``decode_from = (step, keep)`` set (this call takes it),
        the rows are rows ``keep`` of ``step``'s call, each in the slot
        it had there, and their tokens are ``step``'s output as the
        device array it is: ``tokens`` is then not read (the scheduler
        passes the last ones it has seen).  Slots of ``step``'s bucket
        no row keeps are padding.  No program differs: an executable
        takes a device vector as it takes a host one."""
        carry, self.decode_from = self.decode_from, None
        if carry is None:
            n = tokens.shape[0]
            b, slots, feed = self.decode_bucket_for(max(n, 1)), \
                np.arange(n), None
        else:
            b, slots, feed = carry[0].bucket, carry[0].slots[carry[1]], \
                carry[0]._tokens
        nxt, *_ = self._launch(self._decode_exe[b], b, slots, tokens,
                               positions, page_tables, feed)
        return DecodeStep(nxt, self._aux, slots, b, self._launches)

    def decode_logits(self, tokens: np.ndarray, positions: np.ndarray,
                      page_tables: np.ndarray
                      ) -> Tuple[np.ndarray, np.ndarray]:
        """:meth:`decode`, and the rows' logits ``(n, V)`` float32 —
        through the same executable and cache the scheduler uses."""
        return self._decode(tokens, positions, page_tables, True)

    def decode_selection(self, tokens: np.ndarray, positions: np.ndarray,
                         page_tables: np.ndarray):
        """:meth:`decode_logits` through the selection program of a model
        of learned sparse attention or of latent attention: the step of
        the largest bucket (fewer rows are padded to it) that also returns
        what it decided.  ``(next tokens (n,), logits (n, V), decided (L,
        n, k) int32, [L' x (n, ...) float32])``: for sparse attention the
        positions each sparse layer selected (a row's first ``min(length,
        topk)`` count) and its scores (up to the row's length); for latent
        attention each router's choices among all its experts and each
        latent layer's heads' outputs before the out-projection
        (:func:`~.model.decode_step`).  A step writes each row's cache
        entries of ``positions`` and nothing else, so called after
        :meth:`decode` or :meth:`decode_logits` with the same arguments it
        leaves the pages as they were: the way to hold this program to the
        scheduler's.  A step also advances the rows' state slots: a model
        that has them is called on the slots as they were before the
        step."""
        n = tokens.shape[0]
        nxt, logits, chosen, *scores = self._decode(
            tokens, positions, page_tables, True, self._selection_exe)
        return (nxt, logits, np.asarray(chosen)[:, :n],
                [np.asarray(a, np.float32)[:n] for a in scores])

    def _launch(self, exe, b, slots, tokens, positions, page_tables,
                feed=None):
        """Pad the rows into ``slots`` of a batch of ``b`` and call
        ``exe``: what :meth:`_run` returns.  ``feed`` is a whole batch's
        token vector on the device, taken in place of ``tokens``."""
        n, seq = slots.shape[0], self._launches + 1
        with span("serve.decode.prep", rows=n, bucket=b, launch=seq):
            pos = np.zeros((b,), np.int32)
            pt = np.full((b, *self.table_shape), NULL_PAGE, np.int32)
            pos[slots] = positions
            pt[slots] = page_tables
            tok = feed
            if tok is None:
                tok = np.zeros((b,), np.int32)
                tok[slots] = tokens
            with self._weights_lock:
                params = self._params
        with span("serve.decode.launch", rows=n, bucket=b, launch=seq):
            return self._run(exe, params, tok, pos, pt)

    def _decode(self, tokens, positions, page_tables, want_logits,
                selection_exe=None):
        """A decode step read at once (the checks' calls)."""
        n = tokens.shape[0]
        b = (self.config.decode_buckets[-1] if selection_exe is not None
             else self.decode_bucket_for(max(n, 1)))
        nxt, logits, *more = self._launch(
            selection_exe or self._decode_exe[b], b, np.arange(n), tokens,
            positions, page_tables)
        with span("serve.decode.fetch", rows=n, bucket=b,
                  launch=self._launches):
            return (np.asarray(nxt)[:n], np.asarray(logits, np.float32)[:n]
                    if want_logits else None, *more)

    # -- weights ------------------------------------------------------------

    @property
    def weights_step(self) -> Optional[int]:
        return self._weights_step

    def install_weights(self, params, step: Optional[int] = None) -> None:
        """Hot-swap to a new weight generation between steps.

        Same treedef/shapes required — the executables' signature is
        structural, so matching weights swap with zero compiles.
        Incoming weights pass through the engine's precision conversion
        first (fp32 trees quantize/cast to match).
        """
        params = self._prepare_params(params)
        old = jax.tree_util.tree_structure(self._params)
        new = jax.tree_util.tree_structure(params)
        if old != new:
            raise ValueError("weight swap changes the parameter tree "
                             f"({new} vs {old})")
        for (_, a), (_, b) in zip(
                sorted(self._params.items()), sorted(params.items())):
            if a.shape != b.shape:
                raise ValueError(
                    f"weight swap changes a shape: {b.shape} vs {a.shape}")
        dev = _to_serve_device(params)
        with self._weights_lock:
            self._params = dev
            self._weights_step = step
        logger.info("weights swapped to generation step=%s", step)

    def maybe_reload(self) -> Optional[int]:
        """Swap in a newer checkpoint generation if one exists
        (zero-downtime: serving N while loading N+1)."""
        mgr = self.checkpoint_manager
        if mgr is None:
            return None
        latest = mgr.latest_step()
        if latest is None or latest == self._weights_step:
            return None
        state, step = mgr.restore_latest(template=self._params)
        if step is None:
            return None
        self.install_weights(state, step)
        return step

    # -- convenience / health ----------------------------------------------

    def generate(self, prompts: Sequence[Sequence[int]],
                 max_new_tokens: Optional[int] = None) -> List[List[int]]:
        """Synchronous batch generate through the continuous-batching
        scheduler (submits all, drains the loop)."""
        streams = [self.scheduler.submit(p, max_new_tokens=max_new_tokens)
                   for p in prompts]
        self.scheduler.drain()
        # the drain above already emptied the loop; the bound is a
        # backstop so a wedged stream can never hang the caller forever
        return [st.result(timeout=300.0) for st in streams]

    def healthz(self) -> Dict[str, Any]:
        sched = getattr(self, "scheduler", None)
        draining = bool(sched is not None and sched.draining)
        hang = bool(sched is not None and sched.hang_detected)
        try:
            self.pool.check_consistency()
            kv_consistent = True
        except AssertionError:
            kv_consistent = False
        h = {
            # degraded while draining (LBs must stop routing here), on
            # any request-path compile, a tripped hang watchdog, or a
            # page-pool invariant violation
            "ok": (self.unexpected_compiles == 0 and not draining
                   and not hang and kv_consistent),
            "draining": draining,
            "hang_detected": hang,
            "kv_consistent": kv_consistent,
            "unexpected_compiles": self.unexpected_compiles,
            "compiled_programs": self.compiled_programs,
            "program_bytes": self.stats["program_bytes"],
            "paged_walk": self.stats["paged_walk"],
            "precision": self.config.precision,
            "decode_buckets": list(self.config.decode_buckets),
            "prefill_buckets": list(self.config.prefill_buckets),
            "weights_step": self._weights_step,
            "kv": self.pool.snapshot(),
        }
        if sched is not None:
            h.update(sched.snapshot())
        return h


# -- served-model directory format ------------------------------------------

def save_served_model(path: str, spec: ModelSpec, params,
                      config: Optional[ServeConfig] = None,
                      step: int = 0) -> str:
    """Write a self-describing served-model dir:
    ``serve_config.json`` (architecture + serve shapes) plus a
    CheckpointManager weight tree — the unit `Predictor` and
    :func:`load_engine` consume, and the unit the trainer republishes
    for zero-downtime swaps."""
    from ..distributed.checkpoint_manager import CheckpointManager
    os.makedirs(path, exist_ok=True)
    cfg = config or ServeConfig.from_env()
    with open(os.path.join(path, SERVE_CONFIG_NAME), "w") as f:
        json.dump({"model": spec.to_dict(), "serve": cfg.to_dict()},
                  f, indent=2, sort_keys=True)
    mgr = CheckpointManager(os.path.join(path, "weights"))
    mgr.save(step, dict(params), block=True)
    return path


def is_served_model_dir(path: str) -> bool:
    return os.path.isdir(path) and \
        os.path.exists(os.path.join(path, SERVE_CONFIG_NAME))


def load_engine(path: str, config: Optional[ServeConfig] = None,
                **config_overrides) -> ServingEngine:
    """Build a :class:`ServingEngine` from a served-model dir.

    Config precedence: explicit ``config`` arg > env overrides >
    ``serve_config.json`` on disk.
    """
    from ..distributed.checkpoint_manager import CheckpointManager
    with open(os.path.join(path, SERVE_CONFIG_NAME)) as f:
        meta = json.load(f)
    spec = ModelSpec.from_dict(meta.get("model", {}))
    if config is None:
        file_cfg = ServeConfig.from_dict(meta.get("serve", {}))
        env_kw = {}
        for fname, env in (
                ("decode_buckets", "PT_SERVE_BUCKETS"),
                ("prefill_buckets", "PT_SERVE_PREFILL_BUCKETS"),
                ("kv_pages", "PT_SERVE_KV_PAGES"),
                ("page_size", "PT_SERVE_PAGE_SIZE"),
                ("max_inflight", "PT_SERVE_MAX_INFLIGHT"),
                ("max_new_tokens", "PT_SERVE_MAX_NEW_TOKENS"),
                ("eos_id", "PT_SERVE_EOS_ID"),
                ("deadline_ms", "PT_SERVE_DEADLINE_MS"),
                ("max_queue", "PT_SERVE_MAX_QUEUE"),
                ("drain_s", "PT_SERVE_DRAIN_S"),
                ("precision", "PT_SERVE_PRECISION")):
            if os.environ.get(env):
                env_kw[fname] = getattr(ServeConfig.from_env(), fname)
        config = file_cfg.replace(**env_kw) if env_kw else file_cfg
    if config_overrides:
        config = config.replace(**config_overrides)
    mgr = CheckpointManager(os.path.join(path, "weights"))
    precision_meta = meta.get("precision") or {}
    with aot_build_phase():
        # template construction + checkpoint restore run jnp ops before
        # ServingEngine's own sanctioned phase opens — keep them from
        # booking compiles on other live engines in the process
        if precision_meta.get("mode") == "int8":
            # quantized dir: the restore template mirrors the quantized
            # tree (``::q``/``::scale`` + ``act::`` leaves) so treedef
            # validation still bites
            from .quant import quantized_template
            template = quantized_template(
                spec,
                act_sites=sorted(precision_meta.get("act_scales", {})))
        else:
            template = init_params(spec, seed=0)
        params, step = mgr.restore_latest(template=template)
    if step is None:
        raise FileNotFoundError(
            f"no valid weight checkpoint under {path}/weights")
    return ServingEngine(spec, params, config,
                         checkpoint_manager=mgr, weights_step=step)
