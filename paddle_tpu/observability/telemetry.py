"""Step telemetry: wall time, throughput, device memory, compile events.

``TrainingTelemetry`` is the process singleton every instrumented hot
path talks to (``hapi.Model`` loops, ``auto_parallel.Engine.fit``,
``CheckpointManager``, elastic heartbeats, collectives, ``DataLoader``).
Design rules, in priority order:

1. **Zero cost while disabled.**  Every hook starts with a plain
   attribute check (``if not self.enabled: return``); no metric objects
   exist, no file/socket/thread is ever created, and nothing touches
   jax.  ``import paddle_tpu.observability`` is side-effect-free.
2. **Never sync the device.**  Step timing is host wall-clock around
   the (async-dispatch) step call; collective byte counts come from
   array metadata; device memory uses ``Device.memory_stats()`` only
   when a backend already exists.  The telemetry layer must not create
   the host round-trips tpu-lint exists to catch.
3. **Never take down the run.**  Sink write failures are counted and
   dropped; the compile-log filter swallows its own exceptions.

Compile visibility: jax logs every XLA compile ("Compiling <fn> with
global shapes and types ...", ``jax/_src/interpreters/pxla.py``) when
``jax_log_compiles`` is on.  :class:`CompileWatcher` flips that config
and installs a ``logging.Filter`` on the emitting loggers, which sees
each record's structured args (function name + abstract signature),
feeds the metrics/sentinel, and suppresses the record so user stderr
stays clean (unless the user had the config on already).  The
:class:`RecompileSentinel` is the dynamic twin of lint rule TPU001's
retrace-storm heuristics: N compiles of the SAME callable with N
distinct signatures means shape/weak-type churn, and it names the
offender at runtime.  The log lines give names, not durations: beside
the filter the watcher listens to jax's own ``jax.monitoring`` events
and books what each compile stage cost
(``pt_compile_seconds_total{stage=trace|lower|backend_compile|cache_load}``)
and whether the persistent cache had the program
(``pt_compile_cache_total{result=hit|miss}``).

Enable explicitly (``configure(enabled=True, ...)``) or via env:
``PT_TELEMETRY=1`` [+ ``PT_TELEMETRY_DIR``, ``PT_METRICS_PORT``],
checked once, lazily, on the first ``get_telemetry()`` call.
"""
from __future__ import annotations

import logging
import os
import sys
import threading
import time
from collections import deque

from .events import EventSink
from .logs import get_logger
from .metrics import get_registry

__all__ = [
    "TrainingTelemetry", "StepTimer", "CompileWatcher",
    "RecompileSentinel", "get_telemetry", "configure", "reset",
]

logger = get_logger(__name__)

_TRUTHY = {"1", "true", "yes", "on"}

# loggers jax emits per-compile records on (jit/pjit path + dispatch)
_JAX_COMPILE_LOGGERS = ("jax._src.interpreters.pxla", "jax._src.dispatch")

# jax.monitoring duration events -> pt_compile_seconds_total{stage}
_COMPILE_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load",
}
# jax.monitoring plain events -> pt_compile_cache_total{result}
_COMPILE_CACHE_RESULTS = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}


def _env_flag(name):
    return os.environ.get(name, "").strip().lower() in _TRUTHY


def _resolve_identity():
    """(process_index, run_id) of this process in a cluster launch.

    ``PT_PROCESS_INDEX`` wins over the launcher-set
    ``PADDLE_TRAINER_ID``; both default to 0 (a single-process run IS
    rank 0 of a world of 1).  ``PT_RUN_ID`` defaults to ``"local"``.
    Pids are deliberately NOT part of the identity — they change on
    every elastic restart while (run_id, rank) survives.
    """
    raw = (os.environ.get("PT_PROCESS_INDEX")
           or os.environ.get("PADDLE_TRAINER_ID") or "").strip()
    try:
        idx = int(raw) if raw else 0
    except ValueError:
        idx = 0
    run_id = (os.environ.get("PT_RUN_ID") or "").strip() or "local"
    return idx, run_id


class RecompileSentinel:
    """Detects recompile storms and names the offending callable.

    Trips when one callable has been compiled ``threshold`` times with
    ``threshold`` distinct signatures — steady-state training compiles a
    step function once (or once per real shape bucket); per-step fresh
    signatures mean the input shapes / weak types churn every call.
    """

    def __init__(self, threshold=5, keep_recent=4):
        self.threshold = max(2, int(threshold))
        self._keep_recent = keep_recent
        self._lock = threading.Lock()
        self._state: dict = {}
        self._tripped: dict = {}

    def observe(self, name, signature=""):
        """Record one compile; returns trip info the first time ``name``
        crosses the threshold, else None."""
        with self._lock:
            st = self._state.get(name)
            if st is None:
                st = self._state[name] = {
                    "count": 0, "sig_hashes": set(),
                    "recent": deque(maxlen=self._keep_recent)}
            st["count"] += 1
            if len(st["sig_hashes"]) < 4096:
                st["sig_hashes"].add(hash(signature))
            if signature:
                st["recent"].append(str(signature)[:400])
            if (name not in self._tripped
                    and st["count"] >= self.threshold
                    and len(st["sig_hashes"]) >= self.threshold):
                info = {"callable": name,
                        "compiles": st["count"],
                        "distinct_signatures": len(st["sig_hashes"]),
                        "recent_signatures": list(st["recent"])}
                self._tripped[name] = info
                return info
        return None

    def compile_counts(self):
        with self._lock:
            return {n: st["count"] for n, st in self._state.items()}

    def tripped(self):
        """{callable_name: trip info} for every storm seen so far."""
        with self._lock:
            return dict(self._tripped)


class _CompileLogFilter:
    """``logging.Filter`` duck-type: parses jax's per-compile records,
    optionally suppressing them (when WE turned the logging on)."""

    def __init__(self, telemetry, swallow):
        self._tel = telemetry
        self._swallow = swallow

    def filter(self, record):
        try:
            msg = record.msg if isinstance(record.msg, str) else ""
            if msg.startswith("Compiling ") and record.args:
                args = (record.args if isinstance(record.args, tuple)
                        else (record.args,))
                # jax names the module "jit(<callable>)"; the sentinel
                # and the AOT/capture feeds key on the bare callable
                name = str(args[0])
                if name.startswith("jit(") and name.endswith(")"):
                    name = name[4:-1]
                sig = "; ".join(str(a)[:400] for a in args[1:])
                self._tel._on_compile(name, sig)
                return not self._swallow
            if msg.startswith("Finished "):
                # log_elapsed_time spans ("Finished tracing...", "Finished
                # XLA compilation...") promoted to WARNING by the very
                # config we flipped on; drop them unless the user had
                # jax_log_compiles enabled themselves
                return not self._swallow
        except Exception:  # a broken filter must never break jax logging
            return True
        return True


class CompileWatcher:
    """Hooks jax's compile path via ``jax_log_compiles`` + log filters,
    and ``jax.monitoring`` listeners for the stage durations.

    Install is lazy and idempotent: a no-op until jax has been imported
    by someone else (telemetry never imports jax itself), retried from
    the step hooks so late jax imports still get coverage.  Uninstall
    restores the user's prior ``jax_log_compiles`` value and removes
    the listeners.
    """

    def __init__(self, telemetry):
        self._tel = telemetry
        self._filters: list = []
        self._prev_log_compiles = None
        # a cache hit's load time is reported inside the backend-compile
        # event that encloses it, on the same thread: kept here until
        # that event closes, so the two stages add up to the whole
        # and a jit traced inside another's trace reports a duration that
        # the outer one's already covers: only the outermost is booked
        self._local = threading.local()
        self.installed = False

    def install(self):
        if self.installed or "jax" not in sys.modules:
            return self.installed
        try:
            jax = sys.modules["jax"]
            prev = bool(jax.config.jax_log_compiles)
            if not prev:
                jax.config.update("jax_log_compiles", True)
            self._prev_log_compiles = prev
        except Exception as e:
            logger.debug("compile watcher: cannot enable "
                         "jax_log_compiles: %s", e)
            return False
        for name in _JAX_COMPILE_LOGGERS:
            f = _CompileLogFilter(self._tel, swallow=not prev)
            logging.getLogger(name).addFilter(f)
            self._filters.append((name, f))
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_scalar_listener(self._on_scalar)
        self.installed = True
        return True

    def uninstall(self):
        if not self.installed:
            return
        for name, f in self._filters:
            logging.getLogger(name).removeFilter(f)
        self._filters = []
        jax = sys.modules["jax"]
        for remove, fn in (
                (jax.monitoring.unregister_event_duration_listener,
                 self._on_duration),
                (jax.monitoring.unregister_event_listener, self._on_event),
                (jax.monitoring.unregister_scalar_listener,
                 self._on_scalar)):
            try:
                remove(fn)
            except Exception as e:   # already cleared by someone else
                logger.debug("compile watcher: %s", e)
        if self._prev_log_compiles is False:
            try:
                jax.config.update("jax_log_compiles", False)
            except Exception as e:
                logger.debug("compile watcher: restore failed: %s", e)
        self.installed = False

    def _on_scalar(self, event, value, **kwargs):
        # jax reports a stage's start as a scalar: the trace stage nests
        if _COMPILE_STAGES.get(event) == "trace":
            self._local.depth = getattr(self._local, "depth", 0) + 1

    def _on_duration(self, event, seconds, **kwargs):
        stage = _COMPILE_STAGES.get(event)
        if stage is None:
            return
        local = self._local
        if stage == "trace":
            local.depth = max(0, getattr(local, "depth", 0) - 1)
            if local.depth:
                return
        elif stage == "cache_load":
            local.cache_load = seconds
        elif stage == "backend_compile":
            seconds = max(0.0, seconds - getattr(local, "cache_load", 0.0))
            local.cache_load = 0.0
        self._tel.compile_stage(stage, seconds)

    def _on_event(self, event, **kwargs):
        result = _COMPILE_CACHE_RESULTS.get(event)
        if result is not None:
            self._tel.compile_cache(result)


class StepTimer:
    """``with tel.step(batch_size=..., mode=...):`` convenience span."""

    __slots__ = ("_tel", "_mode", "_batch_size", "_token")

    def __init__(self, telemetry, mode="train", batch_size=None):
        self._tel = telemetry
        self._mode = mode
        self._batch_size = batch_size
        self._token = None

    def __enter__(self):
        self._token = self._tel.step_start()
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is None:
            self._tel.step_end(self._token, batch_size=self._batch_size,
                               mode=self._mode)
        return False


class TrainingTelemetry:
    """Process-wide telemetry hub (see module docstring for contract)."""

    def __init__(self):
        self.enabled = False
        self.process_index, self.run_id = _resolve_identity()
        self._lock = threading.RLock()
        self.sentinel = RecompileSentinel(
            threshold=int(os.environ.get("PT_RECOMPILE_THRESHOLD") or 5))
        self._watcher = CompileWatcher(self)
        self.sink: EventSink | None = None
        self.server = None
        self._metrics_made = False
        self._start_ts = time.time()
        self._steps = 0
        self._step_times = deque(maxlen=512)
        self._last_step_ts = None
        self._last_ckpt_step = None
        self._last_heartbeat_ts = None
        self._lease_ttl = None
        self._store_last_ok_ts = None
        self._store_last_fail_ts = None
        self._store_generation = None
        self._capture_hits = 0
        self._capture_misses: dict = {}
        self._compile_listeners: list = []
        # refresh device-memory gauges every N steps (stats read is a
        # host-side allocator query, cheap but not free)
        self._mem_every = 32

    # -- lifecycle ----------------------------------------------------------

    @property
    def registry(self):
        return get_registry()

    def enable(self, jsonl_dir=None, http_port=None, compile_watch=True,
               process_index=None, run_id=None):
        """Turn telemetry on (idempotent; each facility added at most
        once).  ``http_port=0`` binds an ephemeral port; ``None`` means
        no endpoint.  ``process_index``/``run_id`` override the
        env-resolved identity stamped on every metric series and JSONL
        record.  Returns self."""
        with self._lock:
            if process_index is not None:
                self.process_index = int(process_index)
            if run_id is not None:
                self.run_id = str(run_id)
            if not self.enabled:
                self.enabled = True
                self._make_metrics()
            self.registry.set_const_labels(
                process_index=self.process_index, run_id=self.run_id)
            if compile_watch:
                self._watcher.install()
            if jsonl_dir is not None and self.sink is None:
                self.sink = EventSink(str(jsonl_dir),
                                      run_id=self.run_id,
                                      process_index=self.process_index)
            if http_port is not None and self.server is None:
                from .server import MetricsServer
                self.server = MetricsServer(self.registry,
                                            health_cb=self.healthz,
                                            port=int(http_port))
                self.server.start()
        return self

    def publish_endpoint(self, store, world_size=None):
        """Publish this rank's ``/metrics`` endpoint into the
        coordination store under ``obs/<run_id>/endpoint/<rank>`` so the
        cluster aggregator can discover it; also (re)sets
        ``obs/<run_id>/world`` when ``world_size`` is given — EVERY rank
        writing it keeps discovery alive across a master respawn with a
        partial WAL.  ``store`` is any TCPStore-shaped client; pass a
        :class:`~paddle_tpu.distributed.resilient_store.ResilientStore`
        to survive master failover.  Returns the published "host:port".
        """
        with self._lock:
            server = self.server
        if server is None or server.port is None:
            raise RuntimeError(
                "publish_endpoint: no metrics server is running — "
                "enable(http_port=...) first")
        from .aggregator import endpoint_key, world_key
        ep = f"{server.host}:{server.port}"
        store.set(endpoint_key(self.run_id, self.process_index),
                  ep.encode("ascii"))
        if world_size is not None:
            store.set(world_key(self.run_id),
                      str(int(world_size)).encode("ascii"))
        logger.info("published metrics endpoint %s as rank %d of run "
                    "%s", ep, self.process_index, self.run_id)
        return ep

    def disable(self):
        with self._lock:
            self.enabled = False
            self._watcher.uninstall()
            if self.server is not None:
                self.server.stop()
                self.server = None
            if self.sink is not None:
                self.sink.close()
                self.sink = None
        return self

    def _make_metrics(self):
        if self._metrics_made:
            return
        self._metrics_made = True
        r = self.registry
        self._m_steps = r.counter(
            "pt_steps_total", "training/eval steps completed", ("mode",))
        self._m_step_time = r.histogram(
            "pt_step_time_seconds", "per-step wall time", ("mode",))
        self._m_throughput = r.gauge(
            "pt_throughput_samples_per_second",
            "samples/sec of the most recent step", ("mode",))
        self._m_last_step_ts = r.gauge(
            "pt_last_step_timestamp_seconds",
            "unix time the last step finished")
        self._m_compiles = r.counter(
            "pt_compiles_total", "XLA compilations observed", ("fn",))
        self._m_storms = r.counter(
            "pt_recompile_storms_total",
            "callables that tripped the recompile sentinel")
        self._m_compile_seconds = r.counter(
            "pt_compile_seconds_total",
            "host seconds spent building programs, by stage (trace = "
            "python to jaxpr, lower = jaxpr to MLIR, backend_compile = "
            "XLA, cache_load = reading the persistent compile cache)",
            ("stage",))
        self._m_compile_cache = r.counter(
            "pt_compile_cache_total",
            "persistent compile cache lookups, by result (hit|miss)",
            ("result",))
        self._m_data_wait = r.histogram(
            "pt_data_wait_seconds",
            "time the training loop waited for the next batch")
        self._m_batches = r.counter(
            "pt_data_batches_total", "batches produced by DataLoader")
        self._m_coll_ops = r.counter(
            "pt_collective_ops_total", "collective op invocations",
            ("op",))
        self._m_coll_bytes = r.counter(
            "pt_collective_bytes_total",
            "input bytes entering collectives (metadata-derived)",
            ("op",))
        from .metrics import log_buckets
        self._m_coll_bytes_hist = r.histogram(
            "pt_collective_bytes",
            "per-invocation input bytes of collectives "
            "(metadata-derived distribution; the ROADMAP 'time + "
            "bytes' pair with pt_collective_time_seconds)", ("op",),
            buckets=log_buckets(1e2, 1e9, per_decade=1))
        self._m_coll_time = r.histogram(
            "pt_collective_time_seconds",
            "host-boundary wall time of eagerly dispatched collectives "
            "(not recorded inside traces)", ("op",))
        self._m_grad_buckets = r.counter(
            "pt_grad_buckets_total",
            "gradient-reduction buckets built by train-step tracing, "
            "by reduction kind (all_reduce = fused dp pmean; "
            "reduce_scatter = planned ZeRO hierarchical schedule)",
            ("kind",))
        self._m_grad_bucket_bytes = r.histogram(
            "pt_grad_bucket_bytes",
            "flat-concatenated payload bytes of each gradient bucket "
            "(the fused all-reduce granularity, vs the per-parameter "
            "sizes it replaced)",
            buckets=log_buckets(1e2, 1e9, per_decade=1))
        self._m_ckpt_ops = r.counter(
            "pt_checkpoint_ops_total", "checkpoint operations",
            ("op", "status"))
        self._m_ckpt_save_s = r.histogram(
            "pt_checkpoint_save_seconds", "checkpoint commit duration")
        self._m_ckpt_restore_s = r.histogram(
            "pt_checkpoint_restore_seconds",
            "checkpoint restore duration")
        self._m_ckpt_latest = r.gauge(
            "pt_checkpoint_latest_step",
            "newest committed checkpoint step")
        self._m_ckpt_gc = r.counter(
            "pt_checkpoint_gc_deleted_total",
            "checkpoint directories removed by retention GC")
        self._m_ckpt_barrier_s = r.histogram(
            "pt_checkpoint_barrier_wait_seconds",
            "time spent in the multi-host commit barrier", ("status",))
        self._m_ckpt_swept = r.counter(
            "pt_checkpoint_staging_orphans_swept_total",
            "orphaned staging/partial-commit dirs removed by the "
            "startup janitor")
        self._m_hb = r.counter(
            "pt_elastic_heartbeats_total", "elastic store heartbeats",
            ("status",))
        self._m_hb_ts = r.gauge(
            "pt_elastic_last_heartbeat_timestamp_seconds",
            "unix time of the last successful heartbeat")
        self._m_mem = r.gauge(
            "pt_device_memory_bytes",
            "allocator stats summed over local devices", ("stat",))
        self._m_store_reconnects = r.counter(
            "pt_store_reconnects_total",
            "TCPStore client reconnect attempts (transient master "
            "outages absorbed by ResilientStore)", ("op",))
        self._m_store_unavail_s = r.histogram(
            "pt_store_unavailable_seconds",
            "time spent retrying before declaring the store master "
            "unavailable")
        self._m_store_gen = r.gauge(
            "pt_store_generation",
            "master generation last observed by this process")
        self._m_store_ok_ts = r.gauge(
            "pt_store_last_ok_timestamp_seconds",
            "unix time of the last successful store op")
        self._m_capture_hits = r.counter(
            "pt_capture_cache_hits_total",
            "captured-step signature-cache hits (replays with no retrace)")
        self._m_capture_misses = r.counter(
            "pt_capture_cache_misses_total",
            "captured-step cache misses", ("reason",))

    # -- step timing --------------------------------------------------------

    def step(self, mode="train", batch_size=None):
        return StepTimer(self, mode=mode, batch_size=batch_size)

    def step_start(self):
        """Opaque token for ``step_end`` (None while disabled — both
        hooks are no-ops then)."""
        if not self.enabled:
            return None
        return time.perf_counter()

    def step_end(self, token, batch_size=None, mode="train"):
        if token is None or not self.enabled:
            return
        dt = time.perf_counter() - token
        self.observe_step(dt, mode=mode, batch_size=batch_size)

    def observe_step(self, seconds, mode="train", batch_size=None):
        """Record one completed step of ``seconds`` wall time."""
        if not self.enabled:
            return
        now = time.time()
        self._m_steps.inc(mode=mode)
        self._m_step_time.observe(seconds, mode=mode)
        self._m_last_step_ts.set(now)
        throughput = None
        if batch_size and seconds > 0:
            throughput = batch_size / seconds
            self._m_throughput.set(throughput, mode=mode)
        with self._lock:
            self._steps += 1
            steps = self._steps
            self._last_step_ts = now
            self._step_times.append(float(seconds))
        if not self._watcher.installed:
            self._watcher.install()  # jax may have appeared since enable
        if steps % self._mem_every == 0:
            self._update_memory_gauges()
        if self.sink is not None:
            self.sink.emit("step", step=steps, mode=mode,
                           duration_sec=round(float(seconds), 6),
                           batch_size=batch_size,
                           throughput=(round(throughput, 2)
                                       if throughput else None))
        # derived trace gauges (overlap fraction, analytic MFU) refresh
        # per step; sys.modules-gated so a run that never imported the
        # tracer pays nothing here
        tr_mod = sys.modules.get("paddle_tpu.observability.trace")
        if tr_mod is not None:
            tr = tr_mod.current_tracer()
            if tr is not None and tr.enabled:
                tr.on_step(seconds)
        # goodput gauges refresh per step over the same span ring; same
        # sys.modules gate — never imports, never touches the device
        gp_mod = sys.modules.get("paddle_tpu.observability.goodput")
        if gp_mod is not None:
            gp = gp_mod.current_ledger()
            if gp is not None and gp.enabled:
                gp.refresh()
        # memory watermark timeline samples at step boundaries through
        # the same gate — allocator reads only, never a device sync
        mem_mod = sys.modules.get("paddle_tpu.observability.memory")
        if mem_mod is not None:
            mm = mem_mod.current_memory_monitor()
            if mm is not None and mm.enabled:
                mm.on_step(steps)

    # -- data / collectives -------------------------------------------------

    def data_wait(self, seconds):
        if not self.enabled:
            return
        self._m_data_wait.observe(seconds)
        self._m_batches.inc()

    def collective_op(self, op, nbytes=0):
        if not self.enabled:
            return
        self._m_coll_ops.inc(op=op)
        if nbytes:
            self._m_coll_bytes.inc(nbytes, op=op)
            self._m_coll_bytes_hist.observe(nbytes, op=op)

    def collective_time(self, op, seconds):
        """Host wall time around ONE eager collective dispatch (the
        caller guarantees it is not tracing — see
        ``distributed.collective._timed``)."""
        if not self.enabled:
            return
        self._m_coll_time.observe(float(seconds), op=op)

    def grad_bucket(self, nbytes, kind="all_reduce"):
        """One gradient bucket materialized at train-step trace time;
        ``nbytes`` is the flat-concatenated payload of its fused
        reduction (recorded once per trace — the honest count, like
        ``collective_op``) and ``kind`` the reduction it compiles to."""
        if not self.enabled:
            return
        self._m_grad_buckets.inc(kind=kind)
        self._m_grad_bucket_bytes.observe(float(nbytes))

    # -- checkpoints ----------------------------------------------------------

    def record_checkpoint_save(self, seconds, step=None, mode="sync",
                               ok=True):
        if not self.enabled:
            return
        self._m_ckpt_ops.inc(op="save",
                             status="ok" if ok else f"{mode}_error")
        self._m_ckpt_save_s.observe(seconds)
        if ok and step is not None:
            with self._lock:
                self._last_ckpt_step = int(step)
            self._m_ckpt_latest.set(int(step))
        if self.sink is not None:
            self.sink.emit("checkpoint_save", step=step, mode=mode,
                           ok=ok, duration_sec=round(float(seconds), 6))

    def record_checkpoint_restore(self, seconds, step=None, ok=True):
        if not self.enabled:
            return
        self._m_ckpt_ops.inc(op="restore", status="ok" if ok else "error")
        self._m_ckpt_restore_s.observe(seconds)
        if ok and step is not None:
            with self._lock:
                self._last_ckpt_step = int(step)
            self._m_ckpt_latest.set(int(step))
        if self.sink is not None:
            self.sink.emit("checkpoint_restore", step=step, ok=ok,
                           duration_sec=round(float(seconds), 6))

    def record_checkpoint_gc(self, deleted):
        if not self.enabled or not deleted:
            return
        self._m_ckpt_gc.inc(deleted)

    def record_barrier_wait(self, seconds, ok=True):
        """Time one process spent in the checkpoint commit barrier —
        a stalled barrier (straggler or dead rank) shows up here long
        before the timeout names the missing ranks."""
        if not self.enabled:
            return
        self._m_ckpt_barrier_s.observe(seconds,
                                       status="ok" if ok else "timeout")
        if not ok and self.sink is not None:
            self.sink.emit("checkpoint_barrier_timeout",
                           duration_sec=round(float(seconds), 6))

    def record_staging_sweep(self, n):
        """The startup janitor removed ``n`` orphaned staging dirs /
        partial marker sets (crash debris of dead save attempts)."""
        if not self.enabled or not n:
            return
        self._m_ckpt_swept.inc(n)
        if self.sink is not None:
            self.sink.emit("checkpoint_staging_swept", count=int(n))

    def record_async_save_failure(self, step, error):
        """Async writer failed — the manager re-raises it on the next
        call, but the metric/event makes the failure visible NOW."""
        if not self.enabled:
            return
        self._m_ckpt_ops.inc(op="save", status="async_error")
        if self.sink is not None:
            self.sink.emit("checkpoint_async_save_failed", step=step,
                           error=str(error)[:400])

    # -- elastic heartbeats -------------------------------------------------

    def heartbeat(self, ok=True, lease_ttl=None):
        if not self.enabled:
            return
        self._m_hb.inc(status="ok" if ok else "error")
        if lease_ttl is not None:
            with self._lock:
                self._lease_ttl = float(lease_ttl)
        if ok:
            now = time.time()
            self._m_hb_ts.set(now)
            with self._lock:
                self._last_heartbeat_ts = now

    # -- coordination store -------------------------------------------------

    def record_store_op(self, generation=None):
        """One store op succeeded (through ResilientStore).  Feeds the
        ``store`` healthz block: last-ok age + current generation."""
        if not self.enabled:
            return
        now = time.time()
        self._m_store_ok_ts.set(now)
        with self._lock:
            self._store_last_ok_ts = now
            if generation is not None:
                self._store_generation = int(generation)
        if generation is not None:
            self._m_store_gen.set(int(generation))

    def record_store_reconnect(self, op):
        """A store op hit a transient connection failure and is being
        retried against a (possibly respawned) master."""
        if not self.enabled:
            return
        self._m_store_reconnects.inc(op=str(op))
        if self.sink is not None:
            self.sink.emit("store_reconnect", op=str(op))

    def record_store_unavailable(self, seconds, op=None, endpoint=None):
        """ResilientStore exhausted its deadline — the master stayed
        unreachable for ``seconds``.  Positive evidence for healthz."""
        if not self.enabled:
            return
        self._m_store_unavail_s.observe(float(seconds))
        with self._lock:
            self._store_last_fail_ts = time.time()
        if self.sink is not None:
            self.sink.emit("store_unavailable", op=op, endpoint=endpoint,
                           duration_sec=round(float(seconds), 3))

    # -- capture cache (jit.capture_step) -----------------------------------

    def capture_cache_hit(self):
        """One captured-step call replayed from the signature cache."""
        self._capture_hits += 1  # GIL-atomic; host-side counter feeds
        if self.enabled:         # snapshot() even while metrics are off
            self._m_capture_hits.inc()

    def capture_cache_miss(self, reason):
        """One captured-step call that could not replay; ``reason`` is
        one of first_trace / signature_change / capture_unsafe /
        unsupported_args."""
        reason = str(reason)
        self._capture_misses[reason] = \
            self._capture_misses.get(reason, 0) + 1
        if self.enabled:
            self._m_capture_misses.inc(reason=reason)

    # -- compiles (called from the log filter) ------------------------------

    def record_compile(self, name, signature=""):
        """Public compile-event feed for sources other than jax's
        compile log (AOT pipelines, drills) — same metrics/sentinel
        path as the log filter."""
        self._on_compile(name, signature)

    def compile_stage(self, stage, seconds):
        """Seconds one compile stage took (``jax.monitoring`` feed of
        the :class:`CompileWatcher`)."""
        if self.enabled:
            self._m_compile_seconds.inc(seconds, stage=stage)

    def compile_cache(self, result):
        """One persistent-cache lookup, ``hit`` or ``miss``."""
        if self.enabled:
            self._m_compile_cache.inc(result=result)

    def ensure_compile_watch(self):
        """Install the jax compile-log watcher without flipping the rest
        of telemetry on.  Lets the serving engine's zero-compile
        sentinel see compile events even when metrics are disabled
        (compile events still reach listeners/sentinel; only metric
        booking is gated on ``enabled``)."""
        return self._watcher.install()

    def add_compile_listener(self, fn):
        """Register ``fn(name, signature)`` to be invoked on every
        observed compile (log-filter or :meth:`record_compile`).
        Listener exceptions are swallowed — observers must not break
        the compile path."""
        with self._lock:
            if fn not in self._compile_listeners:
                self._compile_listeners.append(fn)

    def remove_compile_listener(self, fn):
        with self._lock:
            try:
                self._compile_listeners.remove(fn)
            except ValueError:
                pass

    def _on_compile(self, name, signature=""):
        for fn in list(self._compile_listeners):
            try:
                fn(name, signature)
            except Exception:
                pass
        if self.enabled:
            self._m_compiles.inc(fn=name)
        if self.sink is not None:
            self.sink.emit("compile", fn=name,
                           signature=signature[:400] or None)
        trip = self.sentinel.observe(name, signature)
        if trip is not None:
            if self.enabled:
                self._m_storms.inc()
            logger.warning(
                "recompile storm: %s compiled %d times with %d distinct "
                "signatures — input shape/weak-type churn; pad to fixed "
                "shapes or mark changing args static",
                name, trip["compiles"], trip["distinct_signatures"])
            if self.sink is not None:
                self.sink.emit("recompile_storm", **trip)

    # -- device memory ------------------------------------------------------

    def device_memory(self):
        """Summed allocator stats over local devices; {} when no jax
        backend exists yet (never initializes one just to ask).
        Delegates to the one guarded read in ``observability.memory``
        — the consolidation point shared with the ``device.cuda``
        parity shims."""
        from .memory import device_memory_stats
        return device_memory_stats()

    def _update_memory_gauges(self):
        mem = self.device_memory()
        if not mem:
            return
        for k, v in mem.items():
            self._m_mem.set(v, stat=k)

    # -- snapshots / health -------------------------------------------------

    def step_percentiles_ms(self):
        """Exact host-side p50/p95 over the last <=512 steps."""
        with self._lock:
            times = sorted(self._step_times)
        if not times:
            return {"p50": None, "p95": None}
        def pick(q):
            i = min(len(times) - 1, int(q * (len(times) - 1) + 0.5))
            return round(times[i] * 1000, 3)
        return {"p50": pick(0.50), "p95": pick(0.95)}

    def snapshot(self):
        """Compact JSON-ready health summary (attached to bench
        records; the full registry dump is ``registry.snapshot()``)."""
        compile_counts = self.sentinel.compile_counts()
        top = sorted(compile_counts.items(), key=lambda kv: -kv[1])[:8]
        pct = self.step_percentiles_ms()
        with self._lock:
            steps = self._steps
            last_ckpt = self._last_ckpt_step
        mem = self.device_memory()
        # numerics block: anomaly counts (incl. AMP scaler skips) ride
        # along in every snapshot. sys.modules-gated like the tracer
        # feed — read-only, never triggers enablement.
        numerics = None
        n_mod = sys.modules.get("paddle_tpu.observability.numerics")
        if n_mod is not None:
            m = n_mod.current_monitor()
            if m is not None:
                ns = m.snapshot()
                numerics = {
                    "enabled": ns["enabled"],
                    "anomalies": ns["anomalies"],
                    "anomalies_total": ns["anomalies_total"],
                    "last_anomaly": ns["last_anomaly"],
                    "reads": ns["reads"],
                }
        goodput = None
        gp_mod = sys.modules.get("paddle_tpu.observability.goodput")
        if gp_mod is not None:
            gp = gp_mod.current_ledger()
            if gp is not None and gp.enabled:
                dec = gp.refresh()
                if dec is not None:
                    goodput = {
                        "goodput_fraction": dec["goodput_fraction"],
                        "badput_seconds": dec["badput_seconds"],
                    }
        memory = None
        mem_mod = sys.modules.get("paddle_tpu.observability.memory")
        if mem_mod is not None:
            mm = mem_mod.current_memory_monitor()
            if mm is not None:
                ms = mm.snapshot()
                memory = {
                    "enabled": ms["enabled"],
                    "fit_ok": ms["fit_ok"],
                    "programs": len(ms["programs"]),
                    "fragmentation_bytes": ms["fragmentation_bytes"],
                    "oom_events": ms["oom_events"],
                    "last_oom": ms["last_oom"],
                }
        return {
            "enabled": self.enabled,
            "pid": os.getpid(),
            "process_index": self.process_index,
            "run_id": self.run_id,
            "steps": steps,
            "step_ms_p50": pct["p50"],
            "step_ms_p95": pct["p95"],
            "compiles": sum(compile_counts.values()),
            "compiles_by_fn": dict(top),
            "recompile_storms": sorted(self.sentinel.tripped()),
            "capture": {"hits": self._capture_hits,
                        "misses": dict(self._capture_misses)},
            "peak_device_memory_bytes": mem.get("peak_bytes_in_use"),
            "device_memory_bytes": mem.get("bytes_in_use"),
            "last_checkpoint_step": last_ckpt,
            "events_dropped": self.sink.dropped if self.sink else 0,
            "numerics": numerics,
            "goodput": goodput,
            "memory": memory,
        }

    def healthz(self):
        """Liveness summary served on ``/healthz``.  ``ok`` is False
        only on positive evidence of trouble (an expired heartbeat
        lease) — a run that simply has no elastic layer is healthy."""
        now = time.time()
        with self._lock:
            last_step_ts = self._last_step_ts
            last_hb = self._last_heartbeat_ts
            ttl = self._lease_ttl
            steps = self._steps
            last_ckpt = self._last_ckpt_step
            store_ok_ts = self._store_last_ok_ts
            store_fail_ts = self._store_last_fail_ts
            store_gen = self._store_generation
        elastic = None
        lease_ok = None
        if last_hb is not None:
            age = now - last_hb
            lease_ok = (age <= ttl) if ttl is not None else True
            elastic = {"last_heartbeat_age_sec": round(age, 3),
                       "lease_ttl_sec": ttl, "lease_ok": lease_ok}
        # store block: unhealthy only on positive evidence — a declared
        # unavailability NOT followed by a later successful op.  A run
        # with no store, or one that recovered, is healthy.
        store = None
        store_ok = None
        if store_ok_ts is not None or store_fail_ts is not None:
            store_ok = not (store_fail_ts is not None
                            and (store_ok_ts is None
                                 or store_fail_ts > store_ok_ts))
            store = {
                "last_ok_age_sec": (round(now - store_ok_ts, 3)
                                    if store_ok_ts is not None else None),
                "generation": store_gen,
                "ok": store_ok,
            }
        # flight-recorder path (if the tracer exists and has one armed)
        # — read-only: healthz must never trigger env-based enablement
        flight = None
        tr_mod = sys.modules.get("paddle_tpu.observability.trace")
        if tr_mod is not None:
            tr = tr_mod.current_tracer()
            if tr is not None:
                flight = tr.flight_path
        return {
            "ok": lease_ok is not False and store_ok is not False,
            "pid": os.getpid(),
            "process_index": self.process_index,
            "run_id": self.run_id,
            "uptime_sec": round(now - self._start_ts, 1),
            "steps": steps,
            "last_step_age_sec": (round(now - last_step_ts, 3)
                                  if last_step_ts is not None else None),
            "last_checkpoint_step": last_ckpt,
            "elastic": elastic,
            "store": store,
            "recompile_storms": len(self.sentinel.tripped()),
            "flight_recorder": flight,
        }


# -- process singleton ------------------------------------------------------

_telemetry: TrainingTelemetry | None = None
_telemetry_lock = threading.Lock()


def get_telemetry() -> TrainingTelemetry:
    """The process-global telemetry hub.  Created (disabled) on first
    call; auto-enabled here iff ``PT_TELEMETRY`` is truthy — the env is
    consulted lazily so plain imports stay side-effect-free."""
    global _telemetry
    if _telemetry is None:
        with _telemetry_lock:
            if _telemetry is None:
                t = TrainingTelemetry()
                if _env_flag("PT_TELEMETRY"):
                    port = os.environ.get("PT_METRICS_PORT", "").strip()
                    t.enable(
                        jsonl_dir=(os.environ.get("PT_TELEMETRY_DIR")
                                   or None),
                        http_port=int(port) if port else None)
                _telemetry = t
    return _telemetry


def configure(enabled=True, jsonl_dir=None, http_port=None,
              compile_watch=True) -> TrainingTelemetry:
    """Programmatic switch: ``configure(enabled=True, ...)`` turns the
    global hub on (see :meth:`TrainingTelemetry.enable`);
    ``enabled=False`` turns it off."""
    t = get_telemetry()
    if enabled:
        t.enable(jsonl_dir=jsonl_dir, http_port=http_port,
                 compile_watch=compile_watch)
    else:
        t.disable()
    return t


def reset():
    """Tear down the global hub AND the global registry (test
    isolation; not needed in production)."""
    global _telemetry
    with _telemetry_lock:
        t, _telemetry = _telemetry, None
    if t is not None:
        t.disable()
    from .trace import reset_tracer
    reset_tracer()  # its metric handles die with the registry below
    from .numerics import reset_monitor
    reset_monitor()
    from .sdc import reset_monitor as reset_sdc_monitor
    reset_sdc_monitor()
    from .goodput import reset_goodput
    reset_goodput()
    from .memory import reset_memory_monitor
    reset_memory_monitor()
    from .metrics import reset_registry
    reset_registry()
