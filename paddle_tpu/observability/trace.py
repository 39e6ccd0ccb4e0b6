"""Step-phase span tracing, analytic MFU accounting, and the crash
flight recorder.

:class:`Tracer` is the process-wide span sink every instrumented layer
feeds: ``core.RecordEvent`` begin/end pairs, the profiler's
``export_chrome_tracing``, and the step-phase hooks in ``hapi.Model``,
``jit.capture``, ``DataLoader`` and the eager collectives, and the
:func:`span` primitive of the serving request path and the captured
step.  It follows the same contract as
:class:`~.telemetry.TrainingTelemetry`:

1. **Zero cost while disabled.**  Every hook starts with a plain
   attribute check; importing this module creates no threads, files or
   jax backends, and ``get_tracer()`` only flips itself on when
   ``PT_TRACE`` / ``PT_FLIGHT_RECORDER`` say so.
2. **Lock-light.**  Spans land in a bounded ``deque(maxlen=...)`` ring
   buffer — appends are GIL-atomic, so the hot path takes no lock; the
   lock guards only rare operations (enable/export/flight dumps).
3. **Tracer-safe.**  Wall-clock phase spans are skipped inside a jax
   trace (``jax.core.trace_ctx.is_top_level``, same guard as
   ``distributed.collective._timed``): timing a tracer would record the
   trace, not the step.
4. **Never sync the device, never take down the run.**  Spans carry
   host timestamps only; export/dump failures are swallowed after
   bumping a drop counter.

Every span is stamped with this process's ``(process_index, run_id)``
identity so per-rank Chrome exports stitch into one cluster timeline
(``python -m paddle_tpu.observability.merge --trace``, rank as pid).

**Spans on the profiler's clock** (:class:`span`): ``with
span("serve.decode.launch", rows=3, bucket=8):`` always opens a
``jax.profiler.TraceAnnotation("pt:serve.decode.launch", ...)`` — one
atomic load outside a profiler session; inside one the span lands in the
host plane of the same ``.xplane.pb`` as the device operations, so the
two share a clock by construction — and, only while the tracer is
enabled, also appends to the ring (Chrome export, flight recorder).  The
span measures itself either way (``.seconds``), which is what the
serving scheduler's always-on time counters add up.

**Phases** (``pt_step_phase_seconds{phase}``): ``data_wait`` /
``forward`` / ``backward`` / ``optimizer`` / ``checkpoint`` /
``collective``.  ``backward`` covers the fused forward+backward
``value_and_grad`` program in jitted train steps — XLA runs them as one
program, so the host boundary cannot split them.  The derived
``pt_compute_collective_overlap_fraction`` gauge is the fraction of
collective wall time overlapped by compute spans — the measurement half
of the GC3 overlap item (ROADMAP).

**Analytic MFU** (``pt_mfu_analytic``): per-compiled-program FLOPs are
harvested from XLA's ``cost_analysis`` at compile time
(:func:`program_flops`, cached per program name alongside the compile
counter) and divided by step wall time times the device's peak FLOP/s
(:data:`PEAK_FLOPS`), so every bench record carries an MFU estimate
even when the real TPU is unreachable.

**Flight recorder** (``PT_FLIGHT_RECORDER=<dir>``): the last-N spans +
a telemetry snapshot are dumped to ``flight-<run_id>-<rank>.json`` on
SIGTERM (via ``exp/_preempt.ExpRunGuard``), on crash (a chained
``sys.excepthook``), and on a watchdog cadence from the hot path — the
periodic refresh is what leaves a fresh file behind a SIGKILL, which
runs no handlers at all.  The current path is surfaced in ``/healthz``.

Env: ``PT_TRACE=1`` enables tracing, ``PT_TRACE_DIR`` sets the Chrome
export directory, ``PT_FLIGHT_RECORDER`` names the flight-dump
directory (and implies enable).  All checked lazily on the first
``get_tracer()`` call.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time
from collections import deque, namedtuple

from .logs import get_logger
from .metrics import get_registry, log_buckets

__all__ = [
    "Tracer", "Span", "span", "PHASES", "PEAK_FLOPS", "PEAK_HBM_BW", "peak_flops",
    "peak_hbm_bw", "program_flops", "get_tracer", "current_tracer", "reset_tracer",
]

logger = get_logger(__name__)

_TRUTHY = {"1", "true", "yes", "on"}

# the step-phase taxonomy every instrumented layer reports against
PHASES = ("data_wait", "forward", "backward", "optimizer", "checkpoint",
          "collective")

# phase -> span category; the overlap fraction intersects "collective"
# spans with "compute" spans (data_wait/checkpoint are host work —
# overlapping a collective with those is not latency hiding)
_PHASE_CAT = {
    "data_wait": "host", "checkpoint": "host",
    "forward": "compute", "backward": "compute", "optimizer": "compute",
    "collective": "collective",
}

# Peak bf16 FLOP/s and HBM bytes/s per chip by device kind (Google Cloud
# TPU system-architecture pages).  The "cpu" entries are nominal so
# CPU-only records still carry an MFU estimate and the autotuner can
# order candidates in interpret-mode tests (trend, not absolute truth).
PEAK_FLOPS = {
    "TPU v4": 275e12, "TPU v5": 459e12, "TPU v5p": 459e12,
    "TPU v5e": 197e12, "TPU v5 lite": 197e12, "TPU v6e": 918e12,
    "TPU v6 lite": 918e12, "TPU v3": 123e12, "TPU v2": 45e12,
    "cpu": 1e11,
}
PEAK_HBM_BW = {
    "TPU v4": 1.2e12, "TPU v5": 2.765e12, "TPU v5p": 2.765e12,
    "TPU v5e": 819e9, "TPU v5 lite": 819e9, "TPU v6e": 1.64e12,
    "TPU v6 lite": 1.64e12,
    "cpu": 5e10,
}

# seconds between watchdog flight-recorder refreshes from the hot path
_FLIGHT_REFRESH_SEC = 2.0

Span = namedtuple("Span", ("name", "cat", "t0_ns", "t1_ns", "tid"))


def _env_flag(name):
    return os.environ.get(name, "").strip().lower() in _TRUTHY


def _peak(table, what, device_kind, strict):
    kind = (device_kind or "").lower()
    for k in sorted(table, key=len, reverse=True):
        if kind.startswith(k.lower()):
            return table[k]
    if strict:
        raise KeyError(f"no {what} for device kind {device_kind!r}: add "
                       "it to the peak table in observability/trace.py")
    return None


def peak_flops(device_kind, strict=False):
    """Peak FLOP/s for ``device_kind`` (longest-prefix match so
    "TPU v5 lite" never matches "TPU v5").  Unknown kind: None, or a
    ``KeyError`` under ``strict`` (measurement paths use strict — a
    device that is not in the table is an error, not a default)."""
    return _peak(PEAK_FLOPS, "peak FLOP/s", device_kind, strict)


def peak_hbm_bw(device_kind, strict=False):
    """Peak HBM bytes/s for ``device_kind``; same matching and ``strict``
    rule as :func:`peak_flops`."""
    return _peak(PEAK_HBM_BW, "peak HBM bytes/s", device_kind, strict)


def _device_kind():
    """device_kind of the first local device, or None — NEVER
    initializes a jax backend just to ask (same rule as
    ``TrainingTelemetry.device_memory``)."""
    jax = sys.modules.get("jax")
    xb = sys.modules.get("jax._src.xla_bridge")
    if jax is None or xb is None or not getattr(xb, "_backends", None):
        return None
    try:
        devs = jax.local_devices()
        return devs[0].device_kind if devs else None
    except Exception:
        return None


def _tracing():
    """True when called under an open jax trace."""
    jax = sys.modules.get("jax")
    if jax is None:
        return False
    return not jax.core.trace_ctx.is_top_level()


def program_flops(jitted, *args, **kwargs):
    """Analytic FLOPs of one jitted program from XLA's cost analysis
    (None when the backend can't say).  Lowers + compiles AOT — call at
    compile time, not per step."""
    try:
        cost = jitted.lower(*args, **kwargs).compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        f = float(cost.get("flops", 0.0))
        return f or None
    except Exception:
        return None


class _PhaseSpan:
    """``with tracer.phase("backward"):`` — wall-clock one phase.
    A no-op while the tracer is disabled or a jax trace is open."""

    __slots__ = ("_tr", "_phase", "_t0")

    def __init__(self, tracer, phase):
        self._tr = tracer
        self._phase = phase
        self._t0 = None

    def __enter__(self):
        if self._tr.enabled and not _tracing():
            self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._t0 is not None and exc_type is None:
            self._tr.phase_record(self._phase, self._t0,
                                  time.perf_counter_ns())
        return False


_annotation = None   # jax.profiler.TraceAnnotation, once jax is imported


class span:
    """``with span(name, cat="host", **ids) as sp:`` — one span of the
    program, named ``pt:<name>`` in a profiler session (``ids`` become
    the event's arguments there) and ``<name>`` in the tracer's ring
    while the tracer is enabled.  After the block ``sp.seconds`` is its
    wall time.  Never imports jax and never touches a backend: before
    jax is imported the annotation is skipped."""

    __slots__ = ("name", "cat", "ids", "seconds", "_ann", "_t0")

    def __init__(self, name, cat="host", **ids):
        self.name = name
        self.cat = cat
        self.ids = ids
        self.seconds = 0.0

    def __enter__(self):
        global _annotation
        if _annotation is None:
            jax = sys.modules.get("jax")
            if jax is not None:
                _annotation = jax.profiler.TraceAnnotation
        if _annotation is not None:
            self._ann = _annotation("pt:" + self.name, **self.ids)
            self._ann.__enter__()
        else:
            self._ann = None
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb):
        t1 = time.perf_counter_ns()
        self.seconds = (t1 - self._t0) / 1e9
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        tr = _tracer
        if tr is not None and tr.enabled:
            tr.record_span(self.name, self.cat, self._t0, t1)
        return False


class Tracer:
    """Process-wide span recorder (see module docstring for contract)."""

    def __init__(self, capacity=4096):
        self.enabled = False
        from .telemetry import _resolve_identity
        self.process_index, self.run_id = _resolve_identity()
        self._lock = threading.Lock()
        self._spans: deque = deque(maxlen=int(capacity))
        # counter samples ((name, t_ns, ((series, value), ...))) feed
        # Chrome ph:"C" counter tracks — the memory watermark timeline
        self._counters: deque = deque(maxlen=int(capacity))
        self._metrics_made = False
        self.trace_dir = None
        self.flight_dir = None
        self.flight_path = None
        self._flight_last_ns = 0
        self._prev_excepthook = None
        self.dropped = 0          # export/dump failures (never raised)
        self._program_flops: dict = {}
        self._last_step_seconds = None
        self._last_mfu = None
        self._last_overlap = None
        # perf_counter -> unix epoch anchor so per-rank exports share a
        # wall clock and stitch into one aligned cluster timeline
        self._epoch_ns = time.time_ns() - time.perf_counter_ns()

    # -- lifecycle ----------------------------------------------------------

    def enable(self, trace_dir=None, flight_dir=None, capacity=None,
               process_index=None, run_id=None):
        """Turn tracing on (idempotent).  ``trace_dir`` is where
        :meth:`export_chrome` writes by default; ``flight_dir`` arms
        the flight recorder (crash hook + watchdog refresh).  Returns
        self."""
        with self._lock:
            if process_index is not None:
                self.process_index = int(process_index)
            if run_id is not None:
                self.run_id = str(run_id)
            if capacity is not None and int(capacity) != self._spans.maxlen:
                self._spans = deque(self._spans, maxlen=int(capacity))
            if trace_dir is not None:
                self.trace_dir = str(trace_dir)
            if flight_dir is not None:
                self.flight_dir = str(flight_dir)
                self.flight_path = os.path.join(
                    self.flight_dir,
                    f"flight-{self.run_id}-{self.process_index}.json")
                if self._prev_excepthook is None:
                    self._prev_excepthook = sys.excepthook
                    sys.excepthook = self._excepthook
            if not self.enabled:
                self.enabled = True
                self._make_metrics()
        if flight_dir is not None:
            # arm → dump immediately: a SIGKILL can land before the
            # first watchdog refresh and must still find a file
            self.flight_dump(reason="armed")
        return self

    def disable(self):
        with self._lock:
            self.enabled = False
            if self._prev_excepthook is not None:
                sys.excepthook = self._prev_excepthook
                self._prev_excepthook = None
            self.flight_dir = None
            self.flight_path = None
        return self

    def _make_metrics(self):
        if self._metrics_made:
            return
        self._metrics_made = True
        r = get_registry()
        self._m_phase = r.histogram(
            "pt_step_phase_seconds",
            "wall time per step phase (data_wait/forward/backward/"
            "optimizer/checkpoint/collective)", ("phase",))
        self._m_overlap = r.gauge(
            "pt_compute_collective_overlap_fraction",
            "fraction of collective wall time overlapped by compute "
            "spans in the recent span window (GC3 measurement)")
        self._m_mfu = r.gauge(
            "pt_mfu_analytic",
            "analytic MFU: cost_analysis FLOPs per step / (step wall "
            "time * device peak FLOP/s)")
        self._m_flops = r.gauge(
            "pt_program_flops",
            "analytic FLOPs of each compiled program (cost_analysis, "
            "cached at compile time)", ("program",))

    # -- span feeds ---------------------------------------------------------

    def phase(self, phase):
        """Context manager timing one phase (histogram + ring buffer)."""
        return _PhaseSpan(self, phase)

    def phase_record(self, phase, t0_ns, t1_ns):
        """One completed phase with caller-measured endpoints (ns,
        ``time.perf_counter_ns`` clock)."""
        if not self.enabled:
            return
        self._m_phase.observe((t1_ns - t0_ns) / 1e9, phase=phase)
        cat = _PHASE_CAT.get(phase, "host")
        self._spans.append(Span(phase, cat, int(t0_ns), int(t1_ns),
                                threading.get_ident() & 0xFFFFFF))
        self._maybe_flight_refresh(t1_ns)

    def record_span(self, name, cat, t0_ns, t1_ns, tid=None):
        """Raw span feed (``core.RecordEvent`` forwarding, drills).
        ``cat`` is free-form; "compute"/"collective" participate in the
        overlap fraction."""
        if not self.enabled:
            return
        if tid is None:
            tid = threading.get_ident() & 0xFFFFFF
        self._spans.append(Span(str(name), str(cat), int(t0_ns),
                                int(t1_ns), int(tid)))
        self._maybe_flight_refresh(t1_ns)

    def record_counter(self, name, t_ns, values):
        """One counter sample (e.g. the memory watermark): ``values``
        is ``{series: number}``, exported as a Chrome ``ph:"C"``
        counter event so the merged cluster timeline carries a
        per-rank track. Appends are GIL-atomic like spans."""
        if not self.enabled:
            return
        self._counters.append((str(name), int(t_ns),
                               tuple((str(k), float(v))
                                     for k, v in values.items())))

    def counters(self):
        """Snapshot of the counter-sample ring (oldest first)."""
        return [(n, t, dict(vals)) for n, t, vals in self._counters]

    def spans(self):
        """Snapshot of the ring buffer (oldest first)."""
        return list(self._spans)

    def clear(self):
        self._spans.clear()
        self._counters.clear()

    # -- analytic MFU -------------------------------------------------------

    def record_program_flops(self, name, flops):
        """Cache one compiled program's analytic FLOPs (from
        ``cost_analysis`` at compile time)."""
        if flops is None:
            return
        with self._lock:
            self._program_flops[str(name)] = float(flops)
        if self.enabled:
            self._m_flops.set(float(flops), program=str(name))

    def flops_per_step(self):
        """Sum of all registered programs' FLOPs — the analytic cost of
        one step under the convention that each registered program runs
        once per step (true for the one-jitted-program train steps this
        framework builds)."""
        with self._lock:
            return sum(self._program_flops.values()) or None

    def mfu_analytic(self, step_seconds=None):
        """FLOPs/step / (step time * device peak); None when any factor
        is unknown."""
        dt = step_seconds if step_seconds is not None \
            else self._last_step_seconds
        flops = self.flops_per_step()
        peak = peak_flops(_device_kind())
        if not (dt and flops and peak):
            return None
        return flops / (dt * peak)

    # -- derived gauges (fed from telemetry.observe_step) -------------------

    def on_step(self, seconds):
        """One step finished: refresh the overlap + MFU gauges."""
        if not self.enabled:
            return
        self._last_step_seconds = float(seconds)
        ov = self.overlap_fraction()
        if ov is not None:
            self._last_overlap = ov
            self._m_overlap.set(ov)
        mfu = self.mfu_analytic(seconds)
        if mfu is not None:
            self._last_mfu = mfu
            self._m_mfu.set(mfu)
        self._maybe_flight_refresh(time.perf_counter_ns())

    def overlap_fraction(self):
        """Fraction of collective span time overlapped by compute spans
        over the current ring-buffer window; None without collectives."""
        comp, coll = [], []
        for s in self._spans:
            if s.cat == "compute":
                comp.append((s.t0_ns, s.t1_ns))
            elif s.cat == "collective":
                coll.append((s.t0_ns, s.t1_ns))
        if not coll:
            return None
        total = sum(t1 - t0 for t0, t1 in coll)
        if total <= 0:
            return None
        merged = []
        for t0, t1 in sorted(comp):
            if merged and t0 <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], t1)
            else:
                merged.append([t0, t1])
        covered = 0
        for c0, c1 in coll:
            for m0, m1 in merged:
                lo, hi = max(c0, m0), min(c1, m1)
                if lo < hi:
                    covered += hi - lo
        return min(covered / total, 1.0)

    # -- Chrome trace export ------------------------------------------------

    def default_trace_path(self):
        if self.trace_dir is None:
            return None
        return os.path.join(
            self.trace_dir,
            f"trace-{self.run_id}-{self.process_index}.json")

    def chrome_events(self):
        """Chrome trace-event dicts for the current span window: "X"
        (complete) events, ts/dur in microseconds on the unix-epoch
        clock, pid = this rank."""
        events = [{
            "name": "process_name", "ph": "M", "pid": self.process_index,
            "tid": 0,
            "args": {"name": f"rank{self.process_index} "
                             f"({self.run_id})"},
        }]
        for s in self._spans:
            events.append({
                "name": s.name, "cat": s.cat, "ph": "X",
                "ts": (s.t0_ns + self._epoch_ns) / 1e3,
                "dur": max(s.t1_ns - s.t0_ns, 0) / 1e3,
                "pid": self.process_index, "tid": s.tid,
                "args": {"run_id": self.run_id},
            })
        for name, t_ns, vals in self._counters:
            events.append({
                "name": name, "ph": "C",
                "ts": (t_ns + self._epoch_ns) / 1e3,
                "pid": self.process_index, "tid": 0,
                "args": dict(vals),
            })
        return events

    def export_chrome(self, path=None):
        """Write the span window as Chrome trace-event JSON; returns the
        path, or None on failure (counted in ``dropped``, never
        raised)."""
        path = path or self.default_trace_path()
        if path is None:
            raise ValueError("export_chrome: no path and no trace_dir — "
                             "enable(trace_dir=...) or pass a path")
        doc = {"traceEvents": self.chrome_events(),
               "displayTimeUnit": "ms"}
        try:
            os.makedirs(os.path.dirname(os.path.abspath(path)),
                        exist_ok=True)
            tmp = f"{path}.tmp{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(doc, f)
            os.replace(tmp, path)
            return path
        except OSError as e:
            self.dropped += 1
            logger.warning("trace export failed: %s", e)
            return None

    # -- flight recorder ----------------------------------------------------

    def flight_dump(self, reason="manual", last_n=256, extra=None):
        """Dump the last ``last_n`` spans + a telemetry snapshot to the
        flight file; returns the path or None.  ``extra`` (a JSON-ready
        dict) rides along under ``"extra"`` — the OOM postmortem books
        its census/footprint/watermark evidence through it.  Safe from
        signal handlers and excepthooks (never raises)."""
        path = self.flight_path
        if path is None:
            return None
        try:
            spans = list(self._spans)[-int(last_n):]
            try:
                from .telemetry import get_telemetry
                tel_snap = get_telemetry().snapshot()
            except Exception:
                tel_snap = None
            doc = {
                "reason": str(reason),
                "ts": time.time(),
                "pid": os.getpid(),
                "process_index": self.process_index,
                "run_id": self.run_id,
                "last_step_seconds": self._last_step_seconds,
                "overlap_fraction": self._last_overlap,
                "mfu_analytic": self._last_mfu,
                "program_flops": dict(self._program_flops),
                "spans": [{"name": s.name, "cat": s.cat,
                           "t0_ns": s.t0_ns, "t1_ns": s.t1_ns,
                           "tid": s.tid} for s in spans],
                "telemetry": tel_snap,
            }
            if extra:
                doc["extra"] = dict(extra)
            os.makedirs(os.path.dirname(os.path.abspath(path)),
                        exist_ok=True)
            tmp = f"{path}.tmp{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(doc, f)
            os.replace(tmp, path)
            self._flight_last_ns = time.perf_counter_ns()
            return path
        except Exception as e:
            self.dropped += 1
            try:
                logger.warning("flight dump failed: %s", e)
            except Exception:
                pass
            return None

    def _maybe_flight_refresh(self, now_ns):
        """Watchdog half of the flight recorder: keep the on-disk dump
        at most ``_FLIGHT_REFRESH_SEC`` stale so a SIGKILL (which runs
        no handlers) still leaves a recent record behind."""
        if self.flight_path is None:
            return
        if now_ns - self._flight_last_ns >= _FLIGHT_REFRESH_SEC * 1e9:
            self.flight_dump(reason="watchdog")

    def _excepthook(self, exc_type, exc, tb):
        self.flight_dump(reason=f"crash:{exc_type.__name__}")
        prev = self._prev_excepthook or sys.__excepthook__
        prev(exc_type, exc, tb)

    # -- snapshots ----------------------------------------------------------

    def phase_percentiles_ms(self):
        """{phase: {p50, p95}} in ms from the phase histogram (only
        phases that saw samples)."""
        if not self._metrics_made:
            return {}
        out = {}
        for phase in PHASES:
            p50 = self._m_phase.percentile(0.50, phase=phase)
            if p50 is None:
                continue
            p95 = self._m_phase.percentile(0.95, phase=phase)
            out[phase] = {"p50": round(p50 * 1000, 3),
                          "p95": round(p95 * 1000, 3)}
        return out

    def snapshot(self):
        """Compact JSON-ready trace summary (attached to bench
        records)."""
        kind = _device_kind()
        ov = self.overlap_fraction()
        mfu = self.mfu_analytic()
        return {
            "enabled": self.enabled,
            "process_index": self.process_index,
            "run_id": self.run_id,
            "spans": len(self._spans),
            "counters": len(self._counters),
            "phase_ms": self.phase_percentiles_ms(),
            "overlap_fraction": (round(ov, 4) if ov is not None
                                 else None),
            "flops_per_step": self.flops_per_step(),
            "device_kind": kind,
            "device_peak_flops": peak_flops(kind),
            "mfu_analytic": (round(mfu, 6) if mfu is not None else None),
            "flight_recorder": self.flight_path,
            "dropped": self.dropped,
        }


# -- process singleton ------------------------------------------------------

_tracer: Tracer | None = None
_tracer_lock = threading.Lock()


def get_tracer() -> Tracer:
    """The process-global tracer.  Created (disabled) on first call;
    auto-enabled iff ``PT_TRACE`` is truthy or ``PT_FLIGHT_RECORDER``
    names a dump directory — env consulted lazily so plain imports stay
    side-effect-free."""
    global _tracer
    if _tracer is None:
        with _tracer_lock:
            if _tracer is None:
                t = Tracer()
                flight = os.environ.get("PT_FLIGHT_RECORDER", "").strip()
                if _env_flag("PT_TRACE") or flight:
                    t.enable(
                        trace_dir=(os.environ.get("PT_TRACE_DIR")
                                   or None),
                        flight_dir=flight or None)
                _tracer = t
    return _tracer


def current_tracer() -> Tracer | None:
    """The singleton if it already exists, else None — for callers
    (healthz, telemetry hooks) that must not trigger env-based
    enablement as a side effect."""
    return _tracer


def reset_tracer():
    """Drop the global tracer (test isolation)."""
    global _tracer
    with _tracer_lock:
        t, _tracer = _tracer, None
    if t is not None:
        t.disable()
