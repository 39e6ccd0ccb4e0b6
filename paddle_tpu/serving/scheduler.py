"""Continuous (in-flight) batching over the AOT serve programs.

One scheduler tick = one *step boundary*:

 1. **evict** cancelled and deadline-expired sequences (free pages,
    release reservations, resolve the caller's stream with the error),
 2. **admit** queued sequences while a decode slot AND worst-case KV
    headroom exist — admission reserves ``ceil((prompt+max_new)/ps)``
    pages up front (and, in a model with sliding-window layers, the
    window's span plus a page of their pool; in one with state-space
    layers, a state slot: ``PagePool.admit_row``) so an admitted
    sequence can never stall mid-decode waiting for a page (admission
    control against pool headroom),
 3. **launch** the next decode step for every active row, padded to a
    compiled batch bucket,
 4. **read and book** the step before it: append its tokens, **retire**
    the sequences it finished (free pages, release unused
    reservations, resolve the caller's stream).

**One decode step in flight beyond the one being read** (launch-ahead;
how the scheduler runs, for every model).  ``engine.decode`` returns a
:class:`~paddle_tpu.serving.engine.DecodeStep` without waiting, and the
host needs a step's tokens for nothing that decides the next launch:
decoding is greedy with the argmax on the device, the next positions are
the last plus one, the page tables grow from the admission-time
reservation, a row that ends by count is known a step ahead.  So step
k+1 is launched with step k's token vector *as the device array it is*
(``engine.decode_from``), each row in the slot it had in step k, and
only then is step k read (its copy to the host started at its launch).
The device always has a program queued behind the one it runs.

 - *Stable slots.*  A row that ends by count is left out of the next
   launch as a padding slot in place (its pages and slot go back when
   its last step is booked); the rows are compacted (one step launched
   from the host's tokens, after a read) only when a smaller bucket
   then fits them.  What ``engine.decode``
   is called with, and what the counters count, are the live rows.
 - *What falls back to a step launched after a read* (counted
   ``launch="sync"``): the first step of a batch; the step after an
   **admission** — ``engine.prefill`` is synchronous and queued behind
   the step in flight, so when it returns that step's tokens are on the
   host: they are booked, the request is seated, and every row's token
   comes from the host; an admission that lacks pages or a slot which
   rows ending in the unread step hold books that step *before* its
   prefill (returning them at their last launch, so that the prefill
   runs behind it, kept the device busier and the GPT backlog cell
   1 % slower: PERF.md §6, PR 35); a **bucket that can shrink**.  An
   eviction, a cancel or a weights reload needs none: the row becomes a
   hole, the next launch takes the parameters it finds.
 - *``eos_id``* is learnt a step late: the row has been launched once
   more by then.  That step's token for it is dropped, its K/V (and
   state-slot) write lands in the row's own reservation, and its pages
   and slot go back when the ``eos_id`` is booked; whatever takes them
   next is written by a program launched later, which the device runs
   later.  The same holds for the pages of an evicted row and for the
   window pages ``RowPages.advance`` hands back while a step is in
   flight.  The streams are exactly a synchronous loop's.
 - *Who owns what* (D7).  The scheduler thread (or, with no loop
   running, whoever calls :meth:`ContinuousScheduler.step`) alone
   launches and reads: the batch ``_active`` and the one unread step
   ``_flight`` (handle, rows, launch time: one small object) are its
   own.  The lock is held for the host's part of a step and released
   between ``step()`` calls, with a step in flight; ``submit`` touches
   the queue only; ``cancel`` takes a seated row out of the batch and
   frees its pages at once (its token of the unread step is dropped
   when that step is booked).  The watchdog's ``_step_started`` is the
   oldest unread step's launch; the step log's decode records and
   ``_step_ewma`` (the shed ETA) take the step *period* (a read to the
   next read, or a launch to its read where nothing was ahead of it).
   A program that
   fails surfaces at the read and fails the rows of every unread step,
   pages returned.  ``drain``, ``drain_gracefully``, ``stop`` and (with
   no loop running) ``snapshot`` read the last step before they
   conclude, and the loop does not sleep on an empty queue and batch
   while a step is unread.

Sequences join and leave a *running* batch only at these boundaries,
and the decode math is row-independent (see
:mod:`paddle_tpu.serving.model`), so a sequence's tokens are
bit-identical whether it decoded solo or wove through an ever-changing
batch — the property the continuous-batching tests pin.

Resilience layer (the serving-chaos contract):

 - every request may carry a **deadline** (client-supplied, or the
   server default ``ServeConfig.deadline_ms`` / ``PT_SERVE_DEADLINE_MS``);
   expired requests are evicted at the next step boundary and their
   pages returned — a timed-out caller never leaks KV pages,
 - :meth:`ContinuousScheduler.cancel` (surfaced over HTTP as
   ``POST /v1/cancel``) evicts a request wherever it is — queued or
   mid-decode — again at a step boundary (the scheduler lock IS the
   boundary: decode holds it),
 - **load shedding**: admission refuses requests whose deadline is
   infeasible against measured decode throughput (EWMA of step wall
   time) and the current backlog, and bounds the queue with
   oldest-expired eviction (``pt_serve_shed_total{reason}``),
 - **graceful drain**: :meth:`drain_gracefully` stops admission,
   finishes in-flight decodes within a budget, and cancels the rest
   (``cause="drain"``) — the SIGTERM lifecycle of the HTTP front end,
 - **hang watchdog**: a sentinel thread compares the in-flight decode
   step's wall time against N× the rolling p99; a hung device step
   books a flight dump naming the active batch, degrades ``/healthz``,
   and (``PT_SERVE_WATCHDOG=exit``) fast-exits for supervisor restart.

The whole request path here is numpy + pre-compiled executables; a
single stray jnp call would book an unexpected compile on the
engine's sentinel (tpu-lint TPU019 polices this statically).

Where the time goes (always on, ``observability.trace.span``): the
scheduler thread's time is cut into leaf spans, none nested in another —
``serve.wait`` (the loop's ``cv.wait`` with nothing to do, and a
step's taking the lock back from submitters), ``serve.evict``,
``serve.admit`` (each stretch of the admit loop's own work between
engine calls),
``serve.prefill.prep`` / ``.launch`` / ``.fetch`` and
``serve.decode.prep`` / ``.launch`` / ``.fetch`` (the last six but the
page-table half of ``decode.prep`` inside :mod:`.engine`), and
``serve.book`` (token append, retirement, the registry's sync).  A
steady step's order on the thread: ``decode.prep`` (tables, batch),
``decode.prep`` (the engine's padding), ``decode.launch`` of step k+1,
``book`` (rows ending by count leave), ``decode.fetch`` of step k,
``book``.  Inside
a profiler session they are ``pt:serve.*`` events on the device trace's
clock; the
same boundaries add to ``stats`` as float sums (``wait_s``, ``evict_s``,
``admit_host_s``, ``prefill_s``, ``decode_prep_s``, ``decode_s``,
``book_s``; ``decode_s`` is the thread's time in ``engine.decode`` and
in the reads) beside the per-request ``lock_wait_s`` (entry of
:meth:`~ContinuousScheduler.submit` to lock held), ``queue_wait_s``
(enqueued to its prefill entered, count ``admitted``), ``ttft_s`` and
``tpot_s`` (count ``tpot_requests``).  What the decode kernels walked,
a work list the program has (the full layers' pool, the sliding
layers'): ``paged_chunks_walked`` and ``paged_grid_steps``
(``pt_serve_paged_chunks_total{state="walked"|"grid"}``).  How often
launch-ahead engages: ``decode_steps_ahead`` of ``occupancy_steps``
(booked steps launched before the step before them was read;
``pt_serve_decode_steps_total{launch="ahead"|"sync"}``).  A model with
state-space layers: ``state_slots_held`` and ``state_slots_held_max``
(the pool's own numbers as of the last sync, below), ``refused_state`` (admissions refused for want
of a slot, beside ``refused_kv``), ``ssm_tokens_scanned`` (prompt
positions through the scan) and, with cross layers, ``shared_kv_reads``
(decode steps times the layers that read the shared full layer's pages).
Learned sparse attention: ``sparse_tokens_scored`` (a decode step adds, a
sparse layer, the sum of its rows' contexts: every cached indexer key a
query is scored against) and ``sparse_tokens_selected`` (the sum of
``min(context, topk)``: what the layer then attends over), exported as
``pt_serve_sparse_tokens_total{kind="scored"|"selected"}``.

**A record a launched program** (:class:`StepLog`, always on): the
scheduler keeps the last 4,096 program calls it made, one tuple each,
from the time stamps the thread takes anyway.  A decode step, written
when it is booked: ``(seq, "decode", bucket, rows, ahead, launched_ts,
read_ts, period_s)``; a prefill, written when ``engine.prefill``
returns: ``(seq, "prefill", bucket, prompt_len, request_id, call_ts,
first_token_ts, seated_rows)``.  ``seq`` is the engine's number of the
call (``launch=`` on its ``serve.*`` spans), ``period_s`` the step
period above, ``seated_rows`` the rows that held a seat (in the batch or
in the unread step) when the prefill was called and so waited behind
it.  A launch or a read that fails leaves no record.
:meth:`ContinuousScheduler.step_log` hands out copies; ``_step_ewma``,
the watchdog's p99 and ``/healthz`` ``step_period_p50_s`` / ``_p99_s``
read its decode records, and a tripped watchdog puts the last 64 into
its flight dump (``extra["serve_steps"]``), tracer on or off.  Counters
from the same entries: ``step_period_s`` over ``steps_timed`` is the
mean step period, with no wait for arrivals in it and no prefill: a step
whose read waited behind a prefill (an admission with a step in flight:
``engine.prefill`` returns after both) has the prefill's time in its
period on the host's clock, so it is booked and logged as it is and left
out of these two (and out of ``steps_slow``); ``steps_slow`` counts the
timed steps whose period exceeded 1.5 x ``_step_ewma`` as it stood before
them (a median hides that tail: a stall, and the first step of a batch,
launched from the host's tokens, where launch, program and the token's
way back come to that much; the average holds the periods read behind a
prefill too, so for the half dozen steps after a long prompt it stands
high and a stall there is not counted); ``prefill_row_stall_s`` (the sum over
prefills of the call's duration times ``seated_rows``: row-seconds spent
behind another request's prompt, the rest of the step in flight among
them) over ``prefill_runs`` (the prefills that returned a first token) is
``/healthz`` ``prefill_row_stall_mean_s``, what one admission costs the
rows already seated; and, a request, ``token_gap_max_s`` (count
``tpot_requests``): the largest gap between two consecutive tokens of
it, summed when it retires, whenever the gap happened.

**The registry follows ``stats``, off the step's path.**  A decode step
that launches, reads and retires nothing books no instrument: every
``pt_serve_*_total`` counter that ``stats`` holds and the gauges are
brought up to it (:meth:`ContinuousScheduler._sync_registry_locked`) in
:meth:`snapshot` (``/healthz``), when the loop stops, and from ``step()``
and the loop's idle pass once 0.25 s have passed since the last: a scrape
lags a quarter second at most, under any traffic.
The histograms are booked at retirement; events whose label ``stats``
does not keep (a shed's reason, an eviction's cause, a failure's stage)
where they happen.
"""
from __future__ import annotations

import itertools
import logging
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from ..observability.metrics import get_registry
from ..observability.telemetry import get_telemetry
from ..observability.trace import get_tracer, span
from ..ops.paged_attention import chunks_of

logger = logging.getLogger("paddle_tpu.serving")

__all__ = ["ContinuousScheduler", "GenerationStream", "EngineSaturated",
           "RequestShed", "RequestCancelled", "DeadlineExceeded",
           "WATCHDOG_EXIT_CODE"]

# fast-exit status when PT_SERVE_WATCHDOG=exit trips: distinct from the
# drain exit (143) so a supervisor can tell "hung device" from "asked
# to stop" in the restart ledger (canonical taxonomy:
# distributed/exit_codes.py)
from ..distributed.exit_codes import EXIT_WATCHDOG as WATCHDOG_EXIT_CODE  # noqa: E402


class EngineSaturated(RuntimeError):
    """submit() refused: in-flight cap reached (caller should shed load
    or retry with backoff — the HTTP front end maps this to 429)."""


class RequestShed(EngineSaturated):
    """submit() refused by the load shedder.

    ``reason`` is one of ``deadline_infeasible`` (the request cannot
    finish before its deadline given measured throughput + backlog),
    ``queue_full`` (bounded queue at capacity even after evicting
    expired entries), or ``draining`` (SIGTERM lifecycle — admission is
    closed).  ``retry_after`` is the shedder's backlog estimate in
    seconds (the HTTP ``Retry-After`` header)."""

    def __init__(self, message: str, *, reason: str,
                 retry_after: Optional[float] = None):
        super().__init__(message)
        self.reason = reason
        self.retry_after = retry_after


class RequestCancelled(RuntimeError):
    """The request was evicted before completing; ``cause`` is one of
    ``client`` | ``timeout`` | ``disconnect`` | ``drain``."""

    def __init__(self, message: str, *, cause: str = "client"):
        super().__init__(message)
        self.cause = cause


class DeadlineExceeded(TimeoutError):
    """The request's deadline passed before it finished decoding; its
    pages were released at the next step boundary."""


class GenerationStream:
    """Future-like handle for one submitted request.

    Stamps (``time.monotonic``, None until reached), in order:
    ``arrived_ts`` (entry of ``submit()``, before the scheduler lock),
    ``submitted_ts`` (enqueued, the lock held), ``admitted_ts`` (its
    prefill entered), ``first_token_ts``, ``last_token_ts``,
    ``finished_ts``.  ``token_gap_max`` is the largest gap so far between
    two consecutive tokens (0.0 until the second)."""

    _ids = itertools.count()

    def __init__(self, prompt: List[int], max_new_tokens: int,
                 deadline: Optional[float] = None,
                 arrived_ts: Optional[float] = None):
        self.request_id = next(self._ids)
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.tokens: List[int] = []
        self.submitted_ts = time.monotonic()
        self.arrived_ts = (self.submitted_ts if arrived_ts is None
                           else arrived_ts)
        self.admitted_ts: Optional[float] = None
        self.first_token_ts: Optional[float] = None
        self.last_token_ts: Optional[float] = None
        self.finished_ts: Optional[float] = None
        self.token_gap_max = 0.0
        self.deadline = deadline        # absolute time.monotonic(), or None
        self.cancel_cause: Optional[str] = None
        self._done = threading.Event()
        self._error: Optional[BaseException] = None
        self._sched: Optional["ContinuousScheduler"] = None

    def done(self) -> bool:
        return self._done.is_set()

    def cancel(self, cause: str = "client") -> bool:
        """Evict this request (queued or active) at the next step
        boundary, releasing its KV pages.  Returns whether the
        cancellation took effect (False once already finished)."""
        sched = self._sched
        if sched is not None:
            return sched.cancel(self.request_id, cause=cause)
        if not self._done.is_set():
            self.cancel_cause = cause
            self._finish(error=RequestCancelled(
                f"request {self.request_id} cancelled ({cause})",
                cause=cause))
            return True
        return False

    def result(self, timeout: Optional[float] = None) -> List[int]:
        """Wait for the final token list.

        A timeout CANCELS the request before raising — the abandoned
        caller must not keep decoding on borrowed KV pages (the page
        leak this layer exists to close)."""
        if not self._done.wait(timeout):
            self.cancel(cause="timeout")
            raise TimeoutError(
                f"request {self.request_id} not finished in {timeout}s")
        if self._error is not None:
            raise self._error
        return self.tokens

    @property
    def latency(self) -> Optional[float]:
        """Arrival to finish: the lock wait in ``submit()`` counts."""
        if self.finished_ts is None:
            return None
        return self.finished_ts - self.arrived_ts

    @property
    def queue_wait(self) -> Optional[float]:
        """Enqueued to admitted (its prefill entered)."""
        if self.admitted_ts is None:
            return None
        return self.admitted_ts - self.submitted_ts

    @property
    def ttft(self) -> Optional[float]:
        """Arrival to first token."""
        if self.first_token_ts is None:
            return None
        return self.first_token_ts - self.arrived_ts

    @property
    def tpot(self) -> Optional[float]:
        """Mean time per output token after the first."""
        if self.last_token_ts is None or len(self.tokens) < 2:
            return None
        return ((self.last_token_ts - self.first_token_ts)
                / (len(self.tokens) - 1))

    def _finish(self, error: Optional[BaseException] = None) -> None:
        self.finished_ts = time.monotonic()
        self._error = error
        self._done.set()


# _reserve_next_locked: book the unread step, then ask again
_READ_FIRST = object()


class _Active:
    """Per-sequence decode state while resident in the batch."""

    __slots__ = ("stream", "pages", "pos", "last_token", "launched",
                 "index")

    def __init__(self, stream, pages, pos, last_token):
        self.stream = stream
        self.pages = pages              # kv_cache.RowPages: pages + table
        self.pos = pos                  # position the next launch writes
        self.last_token = last_token    # the last one read
        # tokens the stream has once every launched step is booked: the
        # row leaves the batch at the launch that makes it max_new_tokens
        self.launched = 1
        self.index = 0                  # its row in the last step's call

    @property
    def ending(self) -> bool:
        """Its last step is launched (it ends by count) and not booked."""
        return (self.launched >= self.stream.max_new_tokens
                and not self.stream.done())


class _Flight:
    """The one decode step launched and not yet read (D7): the engine's
    handle, the rows the call was made with in its order, when it was
    launched, and whether that was before the step before it was read."""

    __slots__ = ("step", "rows", "launched_ts", "ahead")

    def __init__(self, step, rows, launched_ts, ahead):
        self.step = step                # engine.DecodeStep
        self.rows = rows
        self.launched_ts = launched_ts
        self.ahead = ahead


class StepLog:
    """The last ``capacity`` program calls the scheduler made, oldest
    first: one tuple a call (module docstring), appended by the
    scheduler's thread and read by anyone (an append and a copy of a
    deque are atomic)."""

    DECODE = ("seq", "kind", "bucket", "rows", "ahead", "launched_ts",
              "read_ts", "period_s")
    PREFILL = ("seq", "kind", "bucket", "prompt_len", "request_id",
               "call_ts", "first_token_ts", "seated_rows")

    __slots__ = ("_ring", "append")

    def __init__(self, capacity: int = 4096):
        self._ring: deque = deque(maxlen=capacity)
        self.append = self._ring.append

    def __len__(self) -> int:
        return len(self._ring)

    def last(self, n: Optional[int] = None) -> List[tuple]:
        """The newest ``n`` records (all of them for None), oldest
        first."""
        records = list(self._ring)
        return records if n is None else records[-n:] if n > 0 else []

    def periods(self, n: Optional[int] = None) -> List[float]:
        """``period_s`` of the newest ``n`` decode records, oldest
        first (the walk stops at the ``n``-th: the watchdog polls this)."""
        out: List[float] = []
        for r in reversed(self._ring.copy()):
            if r[1] == "decode":
                out.append(r[7])
                if len(out) == n:
                    break
        out.reverse()
        return out

    def as_dicts(self, n: Optional[int] = None) -> List[Dict[str, Any]]:
        """:meth:`last` with the fields named (JSON-ready: a dump)."""
        return [dict(zip(self.DECODE if r[1] == "decode" else self.PREFILL,
                         r)) for r in self.last(n)]


# the watchdog's p99 is over this many of the newest step periods
_WATCHDOG_STEPS = 256
# the registry is brought up to `stats` at least this often (seconds)
_SYNC_EVERY_S = 0.25


class ContinuousScheduler:
    """Admission + step loop; owns the queue and the active batch."""

    def __init__(self, engine):
        self.engine = engine
        self._queue: deque = deque()
        self._active: List[_Active] = []    # rows of the next launch
        self._flight: Optional[_Flight] = None  # the step not yet read
        self._lock = threading.RLock()
        self._cv = threading.Condition(self._lock)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # resilience state ---------------------------------------------------
        self._draining = False
        self.hang_detected = False
        self._watchdog_thread: Optional[threading.Thread] = None
        # launch of the oldest unread step (what the watchdog watches)
        self._step_started: Optional[float] = None
        self._step_read: float = 0.0                 # the last read's end
        self._log = StepLog()       # every program call, newest 4,096
        self._step_ewma: Optional[float] = None      # sec per decode step
        self.stats = {
            "submitted": 0, "completed": 0, "refused_inflight": 0,
            "refused_kv": 0, "steps": 0, "tokens_generated": 0,
            "occupancy_sum": 0.0, "occupancy_steps": 0,
            # of those steps, the ones launched before the step before
            # them was read (the rest followed a read: "sync")
            "decode_steps_ahead": 0,
            "peak_active": 0,
            "shed": 0, "cancelled": 0, "deadline_exceeded": 0,
            "failed": 0, "drain_seconds": None, "watchdog_trips": 0,
            # work the programs did: prompt positions prefilled, rows
            # decoded; sliding-window pages that went back to the pool
            # while their row ran; and, for routed experts, token-expert
            # pairs routed, the sum over calls and layers of the busiest
            # expert's tokens (their ratio times the expert count is the
            # load's max over mean, a call and layer), and the (layer,
            # expert) pairs the decode steps touched
            "prefill_tokens": 0, "decode_tokens": 0,
            "kv_window_pages_returned": 0,
            "moe_tokens_routed": 0, "moe_expert_max_tokens": 0,
            "moe_decode_experts_touched": 0,
            # the paged-attention kernels' walks, summed over decode
            # steps and the program's work lists: chunks the rows'
            # contexts (or windows) fill, and the lists' grid steps in
            # the bucket's program (engine.stats["paged_walk"])
            "paged_chunks_walked": 0, "paged_grid_steps": 0,
            # state-space layers: slots held now and at most, admissions
            # refused for want of one, prompt positions scanned; cross
            # layers: decode steps x layers reading the shared pages
            "state_slots_held": 0, "state_slots_held_max": 0,
            "refused_state": 0, "ssm_tokens_scanned": 0,
            "shared_kv_reads": 0,
            # learned sparse attention, summed over decode steps and
            # sparse layers: cached keys scored, and tokens selected
            "sparse_tokens_scored": 0, "sparse_tokens_selected": 0,
            # seconds of the scheduler thread by phase (module docstring)
            "wait_s": 0.0, "evict_s": 0.0, "admit_host_s": 0.0,
            "prefill_s": 0.0, "decode_prep_s": 0.0, "decode_s": 0.0,
            "book_s": 0.0,
            # seconds of requests' waits, and what to divide them by
            "lock_wait_s": 0.0, "queue_wait_s": 0.0, "admitted": 0,
            "ttft_s": 0.0, "tpot_s": 0.0, "tpot_requests": 0,
            # the step log's sums (module docstring): timed steps'
            # periods, their number, and how many ran over 1.5 x
            # `_step_ewma` as it stood before them;
            # prefills that gave a token and the row-seconds seated rows
            # waited behind them; requests' largest token gaps
            "step_period_s": 0.0, "steps_timed": 0, "steps_slow": 0,
            "prefill_runs": 0, "prefill_row_stall_s": 0.0,
            "token_gap_max_s": 0.0,
        }
        spec = engine.spec
        self._scans = bool(spec.ssm_layers)
        # layers that read the shared full layer's pages in a decode step
        self._shared_readers = (len(spec.cross_layers) + 1
                                if spec.cross_layers else 0)
        # learned sparse attention: (layers, topk), or None
        self._sparse = ((len(spec.global_layers), spec.sparse_topk)
                        if spec.sparse_topk else None)
        self._meter_registry = None     # the registry self._meters are of
        self._meters: Dict[str, Any] = {}
        # what the registry's counters hold of `stats`, and when the
        # next sync is due (0.0: at the end of the first step)
        self._synced: Dict[str, float] = {}     # by the `stats` key
        self._sync_at = 0.0

    # -- submission ----------------------------------------------------------

    def submit(self, prompt: Sequence[int],
               max_new_tokens: Optional[int] = None,
               deadline_ms: Optional[float] = None) -> GenerationStream:
        arrived = time.monotonic()
        cfg = self.engine.config
        spec = self.engine.spec
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        if any(t < 0 or t >= spec.vocab_size for t in prompt):
            raise ValueError("prompt token out of vocab range")
        self.engine.prefill_bucket_for(len(prompt))  # raises if too long
        max_new = int(max_new_tokens if max_new_tokens is not None
                      else cfg.max_new_tokens)
        max_new = max(1, min(max_new, spec.max_seq_len - len(prompt)))
        if deadline_ms is None:
            deadline_ms = getattr(cfg, "deadline_ms", 0.0)
        deadline_ms = float(deadline_ms or 0.0)
        if deadline_ms < 0:
            raise ValueError("deadline_ms must be >= 0")
        deadline = (time.monotonic() + deadline_ms / 1e3
                    if deadline_ms > 0 else None)
        with self._cv:
            self.stats["lock_wait_s"] += time.monotonic() - arrived
            if self._draining:
                self._shed_locked("draining")
                raise RequestShed("engine draining — admission closed",
                                  reason="draining")
            inflight = len(self._queue) + len(self._seated_locked())
            if inflight >= cfg.max_inflight:
                self.stats["refused_inflight"] += 1
                raise EngineSaturated(
                    f"{inflight} requests in flight (cap "
                    f"{cfg.max_inflight})")
            max_queue = int(getattr(cfg, "max_queue", 0) or 0)
            if max_queue > 0 and len(self._queue) >= max_queue:
                # bounded queue: make room by evicting already-expired
                # entries (oldest first) before refusing fresh work
                self._expire_queue_locked()
                if len(self._queue) >= max_queue:
                    eta = self._backlog_eta_locked()
                    self._shed_locked("queue_full")
                    raise RequestShed(
                        f"queue full ({max_queue} waiting)",
                        reason="queue_full", retry_after=eta)
            if deadline is not None:
                eta = self._completion_eta_locked(max_new)
                if eta is not None and time.monotonic() + eta > deadline:
                    self._shed_locked("deadline_infeasible")
                    raise RequestShed(
                        f"deadline {deadline_ms:.0f}ms infeasible: "
                        f"estimated completion in {eta * 1e3:.0f}ms",
                        reason="deadline_infeasible",
                        retry_after=self._backlog_eta_locked())
            st = GenerationStream(prompt, max_new, deadline=deadline,
                                  arrived_ts=arrived)
            st._sched = self
            self._queue.append(st)
            self.stats["submitted"] += 1
            self._cv.notify()
        return st

    def _shed_locked(self, reason: str) -> None:
        self.stats["shed"] += 1
        self._book("pt_serve_shed_total", kind="counter", reason=reason)

    def _completion_eta_locked(self, max_new: int) -> Optional[float]:
        """Seconds until a request submitted NOW would finish, from the
        measured step-time EWMA and the token backlog ahead of it.
        None until throughput has been measured (admit optimistically)."""
        ew = self._step_ewma
        if ew is None:
            return None
        return self._backlog_eta_locked() + ew * (max_new + 1)

    def _backlog_eta_locked(self) -> Optional[float]:
        ew = self._step_ewma
        if ew is None:
            return None
        backlog = sum(st.max_new_tokens for st in self._queue)
        backlog += sum(
            max(0, a.stream.max_new_tokens - len(a.stream.tokens))
            for a in self._active)
        max_batch = self.engine.config.decode_buckets[-1]
        return ew * (backlog / max(1, max_batch))

    # -- cancellation / eviction ---------------------------------------------

    def cancel(self, request_id: int, cause: str = "client") -> bool:
        """Evict a request wherever it is.  Taking the scheduler lock
        IS the step boundary (the host's part of a step holds it), so a
        seated row leaves between launches.  The step in flight may
        still hold it: its pages go back at once all the same (what
        takes them next is written by a program launched later, which
        the device runs later), and its token of that step is dropped
        when the step is booked."""
        with self._cv:
            for st in self._queue:
                if st.request_id == request_id:
                    self._queue.remove(st)
                    self._finish_evicted_locked(st, cause)
                    return True
            for a in self._seated_locked():
                if a.stream.request_id == request_id:
                    self._drop_locked(a, cause)
                    return True
        return False

    def _seated_locked(self) -> List[_Active]:
        """Every row that holds pages: the batch, and the rows whose
        last step is the one in flight."""
        if self._flight is None:
            return list(self._active)
        return self._active + [a for a in self._flight.rows if a.ending]

    def _drop_locked(self, a: _Active, cause: str) -> None:
        """Evict a seated row: out of the batch, pages back, the stream
        resolved with ``cause``'s error."""
        if a in self._active:
            self._active.remove(a)
        self._release_locked(a)
        self._finish_evicted_locked(a.stream, cause)

    def _release_locked(self, a: _Active) -> None:
        a.pages.release()

    def _finish_evicted_locked(self, st: GenerationStream,
                               cause: str) -> None:
        st.cancel_cause = cause
        if cause == "deadline":
            self.stats["deadline_exceeded"] += 1
            err: BaseException = DeadlineExceeded(
                f"request {st.request_id} missed its deadline after "
                f"{len(st.tokens)}/{st.max_new_tokens} tokens")
        else:
            err = RequestCancelled(
                f"request {st.request_id} cancelled ({cause})",
                cause=cause)
        self.stats["cancelled"] += 1
        self._book("pt_serve_cancelled_total", kind="counter", cause=cause)
        st._finish(error=err)

    def _expire_queue_locked(self, now: Optional[float] = None) -> None:
        if now is None:
            now = time.monotonic()
        expired = [st for st in self._queue
                   if st.deadline is not None and now >= st.deadline]
        for st in expired:
            self._queue.remove(st)
            self._finish_evicted_locked(st, "deadline")

    def _evict_expired_locked(self, now: float) -> None:
        """Deadline sweep at the step boundary: queued AND active."""
        self._expire_queue_locked(now)
        for a in self._seated_locked():
            if a.stream.deadline is not None and now >= a.stream.deadline:
                self._drop_locked(a, "deadline")

    # -- the step loop -------------------------------------------------------

    def step(self) -> bool:
        """One step boundary: evict / admit / launch the next decode
        step / read and book the one before it.  Returns whether any
        work was done.  It may return with a step in flight (launched,
        unread), which the next call reads."""
        stats = self.stats
        # the loop gives the lock up between steps, which is when a
        # submitter gets in; taking it back counts as waiting
        with span("serve.wait") as sp:
            # the wait `with self._lock:` made, inside a span; the one
            # holder that can wedge is a step, which the watchdog watches
            # tpu-lint: disable=TPU021
            self._lock.acquire()
        try:
            stats["wait_s"] += sp.seconds
            now = time.monotonic()
            with span("serve.evict") as sp:
                self._evict_expired_locked(now)
            stats["evict_s"] += sp.seconds
            # draining closes submit(), not the internal queue: every
            # request accepted before SIGTERM still owes a response
            self._admit_locked()
            worked = self._decode_locked()
            if now >= self._sync_at:
                # a quarter second has passed: the registry catches up
                # with `stats`
                with span("serve.book") as sp:
                    self._sync_registry_locked(now)
                stats["book_s"] += sp.seconds
            return worked or bool(self._queue)
        finally:
            self._lock.release()

    def _admit_locked(self) -> None:
        stats = self.stats
        engine = self.engine
        seated = None   # (stream, first token, pages...) of the last prefill
        while True:
            # everything between two engine calls happens inside this one
            # span, the bookkeeping of stamps too: what is left outside a
            # span is idle time of the device that no span accounts for
            with span("serve.admit") as sp:
                if seated is not None:
                    st = seated[0]
                    stats["prefill_s"] += st.first_token_ts - st.admitted_ts
                    stats["ttft_s"] += st.first_token_ts - st.arrived_ts
                    self._seat_locked(*seated)
                    seated = None
                job = self._reserve_next_locked()
                if job is not None and job is not _READ_FIRST:
                    st, pages = job
                    engine.prefill_request_id = st.request_id
                    stats["admitted"] += 1
                    # the rows that wait behind this prompt
                    waiting = len(self._seated_locked())
                    st.admitted_ts = t0 = time.monotonic()
                    stats["queue_wait_s"] += t0 - st.submitted_ts
            stats["admit_host_s"] += sp.seconds
            if job is None:
                return
            if job is _READ_FIRST:
                self._read_locked()
                continue
            try:
                first = engine.prefill(st.prompt, pages.table)
                st.first_token_ts = st.last_token_ts = t1 = time.monotonic()
                seated = (st, first, pages)
                stats["prefill_runs"] += 1
                stats["prefill_row_stall_s"] += (t1 - t0) * waiting
                self._log.append((
                    engine.launches, "prefill",
                    engine.prefill_bucket_for(len(st.prompt)),
                    len(st.prompt), st.request_id, t0, t1, waiting))
            except Exception as exc:  # resolve the caller, keep serving
                stats["prefill_s"] += time.monotonic() - t0
                pages.release()
                stats["failed"] += 1
                self._book("pt_serve_request_failures_total",
                           kind="counter", stage="prefill")
                st._finish(error=exc)
                logger.exception("prefill failed for request %d",
                                 st.request_id)
            if self._flight is not None:
                # the prefill ran behind the step in flight and is read:
                # that step's tokens are on the host.  Booked before the
                # request is seated, so the next launch is an ordinary
                # one, every row's token from the host.  Its period on
                # this clock holds the prefill: not one to time a step by
                self._read_locked(timed=False)

    def _reserve_next_locked(self):
        """Pop the head of the queue with its worst-case pages reserved
        in every kind of layer and its prompt's pages allocated:
        ``(stream, RowPages)``, or None when nothing can be admitted
        now, or ``_READ_FIRST`` when it lacks pages or a slot that rows
        ending in the unread step hold (booking it returns them)."""
        if not self._queue or \
                len(self._active) >= self.engine.config.decode_buckets[-1]:
            return None
        st = self._queue[0]
        pages = self.engine.pool.admit_row(
            len(st.prompt), st.max_new_tokens,
            self.engine.max_pages_per_seq)
        if pages is None:
            if self._flight is not None and any(
                    a.ending for a in self._flight.rows):
                return _READ_FIRST
            # head-of-line blocking is deliberate: skipping ahead
            # would starve large requests under sustained load
            short = self.engine.pool.last_refusal == "state"
            self.stats["refused_state" if short else "refused_kv"] += 1
            return None
        self._queue.popleft()
        return st, pages

    def _book_aux_locked(self, aux: Dict[str, int],
                         decode: bool = False) -> None:
        """What an engine call reported beside its tokens (the experts'
        load: ``engine.take_aux()`` of a prefill, a decode step's own),
        into the counters."""
        touched = aux.pop("moe_experts_touched", None)
        if decode and touched is not None:
            self.stats["moe_decode_experts_touched"] += touched
        for key, value in aux.items():
            self.stats[key] += value

    def _seat_locked(self, st, first, pages) -> None:
        """Book a prefilled request's first token and seat it in the
        batch (or retire it, if one token was all it asked for)."""
        st.tokens.append(first)
        self.stats["tokens_generated"] += 1
        self.stats["prefill_tokens"] += len(st.prompt)
        if self._scans:
            self.stats["ssm_tokens_scanned"] += len(st.prompt)
        self._book_aux_locked(self.engine.take_aux())
        act = _Active(st, pages, pos=len(st.prompt), last_token=first)
        if self._is_finished(act):
            self._retire_locked(act)
        else:
            self._active.append(act)
            self.stats["peak_active"] = max(
                self.stats["peak_active"], len(self._active))

    def _decode_locked(self) -> bool:
        stats = self.stats
        flight = self._flight
        if flight is not None and (
                not self._active or self.engine.decode_bucket_for(
                    len(self._active)) != flight.step.bucket):
            # nothing follows the unread step, or compacted the rows fit
            # a smaller program: read it, then launch from the host's
            # tokens, every row in the first slots
            self._read_locked()
            flight = None
            if not self._active:
                return True
        if not self._active:
            return False
        with span("serve.decode.prep") as sp:
            # grow page tables for rows whose next write crosses a page
            # boundary, and give back what slid out of a window — drawn
            # from the admission-time reservation, so it cannot fail.
            # (A page handed back while a step that reads it is in
            # flight is safe: whatever takes it is written by a program
            # launched later.)
            rows = self._active
            for a in rows:
                stats["kv_window_pages_returned"] += a.pages.advance(a.pos)
            n = len(rows)
            # the last tokens read: one step old, and not what the
            # program takes, where the rows follow an unread step
            tokens = np.asarray([a.last_token for a in rows], np.int32)
            positions = np.asarray([a.pos for a in rows], np.int32)
            tables = np.stack([a.pages.table for a in rows])
            if self._sparse is not None:
                # sums only, as the walks' below
                layers, topk = self._sparse
                stats["sparse_tokens_scored"] += layers * int(
                    positions.sum() + n)
                stats["sparse_tokens_selected"] += layers * int(
                    np.minimum(positions + 1, topk).sum())
            walk = self.engine.paged_walk_for(n)
            if walk is not None:
                # sums only, a list the program walks: the registry
                # follows them off the step's path
                # (_sync_registry_locked)
                lengths = positions + 1
                for found in (walk, walk.get("window")):
                    if found and "grid_steps" in found:
                        stats["paged_chunks_walked"] += int(chunks_of(
                            lengths, found["chunk_tokens"],
                            page_size=self.engine.config.page_size,
                            window=found.get("tokens", 0)).sum())
                        stats["paged_grid_steps"] += found["grid_steps"]
            if flight is not None:
                # the rows sit where they sat in the unread step and
                # take its tokens on the device
                self.engine.decode_from = (flight.step, np.asarray(
                    [a.index for a in rows], np.intp))
            # the watchdog watches the oldest unread step
            t0 = time.monotonic()
            if self._step_started is None:
                self._step_started = t0
        stats["decode_prep_s"] += sp.seconds
        try:
            step = self.engine.decode(tokens, positions, tables)
        except Exception as exc:
            # a failed launch fails every resident request — with their
            # pages RETURNED — and the loop keeps serving the queue; one
            # poisoned batch must not wedge the engine
            self.engine.decode_from = None
            stats["decode_s"] += time.monotonic() - t0
            self._fail_batch_locked(exc)
            return True
        with span("serve.book") as sp:
            stats["decode_s"] += time.monotonic() - t0
            stats["steps"] += 1
            still = []
            for i, a in enumerate(rows):
                a.index = i
                a.pos += 1
                a.launched += 1
                if a.launched < a.stream.max_new_tokens:
                    still.append(a)     # else it ends by count: a hole
            self._active = still
            launched = _Flight(step, rows, t0, ahead=flight is not None)
        stats["book_s"] += sp.seconds
        if flight is None:
            self._flight = launched
        else:
            self._read_locked(behind=launched)
        return True

    def _read_locked(self, behind: Optional[_Flight] = None,
                     timed: bool = True) -> None:
        """Read the step in flight and book its tokens; ``behind`` is
        the step just launched behind it, the one in flight from here.
        A row whose stream is resolved by now (evicted, or retired by an
        ``eos_id`` a step before) has its token dropped.  ``timed`` is
        False where the read waited behind a prefill: the step's period
        then holds the prefill's time (the log, ``_step_ewma`` and the
        watchdog take it as it is, as ever) and stays out of
        ``step_period_s`` / ``steps_timed`` / ``steps_slow``."""
        stats = self.stats
        flight, self._flight = self._flight, behind
        t0 = time.monotonic()
        try:
            nxt = flight.step.read()
            now = time.monotonic()
        except Exception as exc:
            # a program that failed surfaces here, and with it every
            # step launched after it: all resident rows fail
            stats["decode_s"] += time.monotonic() - t0
            self._fail_batch_locked(exc, flight.rows)
            return
        with span("serve.book") as sp:
            stats["decode_s"] += now - t0
            self._step_started = (None if behind is None
                                  else behind.launched_ts)
            # the step period: from its launch, or from the read before
            # it where it was launched ahead, to its tokens on the host
            dt = now - max(flight.launched_ts, self._step_read)
            self._step_read = now
            n, bucket = len(flight.rows), flight.step.bucket
            self._log.append((flight.step.seq, "decode", bucket, n,
                              flight.ahead, flight.launched_ts, now, dt))
            ewma = self._step_ewma
            self._step_ewma = dt if ewma is None else 0.2 * dt + 0.8 * ewma
            if timed:
                # slow against the average as it stood before the step
                stats["steps_slow"] += ewma is not None and dt > 1.5 * ewma
                stats["step_period_s"] += dt
                stats["steps_timed"] += 1
            stats["occupancy_sum"] += n / bucket
            stats["occupancy_steps"] += 1
            stats["decode_steps_ahead"] += flight.ahead
            booked, left = 0, False
            for a, t in zip(flight.rows, nxt):
                if a.stream.done():
                    continue
                try:
                    a.last_token = int(t)
                    st = a.stream
                    st.tokens.append(int(t))
                    if now - st.last_token_ts > st.token_gap_max:
                        st.token_gap_max = now - st.last_token_ts
                    st.last_token_ts = now
                    booked += 1
                    if self._is_finished(a):
                        self._retire_locked(a)
                        left = True
                except Exception as exc:
                    # per-row isolation: this request fails alone; its
                    # neighbours keep decoding and its pages come back
                    self._release_locked(a)
                    stats["failed"] += 1
                    self._book("pt_serve_request_failures_total",
                               kind="counter", stage="step")
                    a.stream._finish(error=exc)
                    left = True
                    logger.exception(
                        "step bookkeeping failed for request %d",
                        a.stream.request_id)
            if left:
                # an eos_id's row may sit in the step behind this one
                # too: that token is dropped when that step is booked
                self._active = [a for a in self._active
                                if not a.stream.done()]
            stats["tokens_generated"] += booked
            stats["decode_tokens"] += n
            stats["shared_kv_reads"] += self._shared_readers
            self._book_aux_locked(flight.step.take_aux(), decode=True)
        stats["book_s"] += sp.seconds

    def _fail_batch_locked(self, exc: BaseException,
                           also: Sequence[_Active] = ()) -> None:
        """Fail every resident request (and the unresolved among
        ``also``, the rows of a step being read), pages returned, and
        forget the step in flight: what was launched behind a failed
        program is not read."""
        rows = {id(a): a for a in self._seated_locked()}
        rows.update((id(a), a) for a in also if not a.stream.done())
        for a in rows.values():
            self._release_locked(a)
            self.stats["failed"] += 1
            self._book("pt_serve_request_failures_total",
                       kind="counter", stage="decode")
            a.stream._finish(error=exc)
        logger.error("decode step failed; %d requests failed, pages "
                     "released", len(rows), exc_info=exc)
        self._active = []
        self._flight = None
        self._step_started = None

    def _is_finished(self, a: _Active) -> bool:
        st = a.stream
        if len(st.tokens) >= st.max_new_tokens:
            return True
        eos = self.engine.config.eos_id
        return eos >= 0 and a.last_token == eos

    def _retire_locked(self, a: _Active) -> None:
        a.pages.release()
        st = a.stream
        st._finish()
        self.stats["completed"] += 1
        self._book("pt_serve_request_latency_seconds", kind="histogram",
                   value=st.latency)
        self._book("pt_serve_queue_wait_seconds", kind="histogram",
                   value=st.queue_wait)
        self._book("pt_serve_ttft_seconds", kind="histogram",
                   value=st.ttft)
        tpot = st.tpot
        if tpot is not None:
            self.stats["tpot_s"] += tpot
            self.stats["tpot_requests"] += 1
            self.stats["token_gap_max_s"] += st.token_gap_max
            self._book("pt_serve_tpot_seconds", kind="histogram",
                       value=tpot)
            self._book("pt_serve_token_gap_max_seconds", kind="histogram",
                       value=st.token_gap_max)

    def _sync_registry_locked(self, now: float) -> None:
        """Every counter of :data:`_SYNCED` and the gauges up to
        ``stats`` (module docstring: when, and why not a step)."""
        self._sync_at = now + _SYNC_EVERY_S
        stats = self.stats
        slots = self.engine.pool.state_slots
        if slots is not None:
            snap = slots.snapshot()
            stats["state_slots_held"] = snap["held"]
            stats["state_slots_held_max"] = snap["high_watermark"]
        if self._registry() is None:
            return
        synced = self._synced
        for name, labels, key, less in _SYNCED:
            total = stats[key] - (stats[less] if less else 0)
            more = total - synced.get(key, 0)
            if more:
                synced[key] = total
                self._book(name, kind="counter", value=more, labels=labels)
        self._book("pt_serve_queue_depth", kind="gauge",
                   value=len(self._queue))
        self._book("pt_serve_active_sequences", kind="gauge",
                   value=len(self._seated_locked()))

    # -- loop management -----------------------------------------------------

    def start(self) -> None:
        """Run the step loop on a background thread (HTTP-serving mode).
        Also arms the hang watchdog when ``PT_SERVE_WATCHDOG`` asks for
        it."""
        with self._lock:
            if self._thread is not None and self._thread.is_alive():
                return
            self._stop.clear()
            self._thread = threading.Thread(
                target=self._loop, name="pt-serve-scheduler", daemon=True)
            self._thread.start()
        get_tracer()    # PT_TRACE / PT_FLIGHT_RECORDER take effect here
        self._start_watchdog()

    def stop(self, timeout: float = 5.0) -> None:
        self._stop.set()
        with self._cv:
            self._cv.notify_all()
        t = self._thread
        if t is not None:
            t.join(timeout)
        w = self._watchdog_thread
        if w is not None:
            w.join(timeout)
            self._watchdog_thread = None
        # the loop is gone: the step it left in flight is read here (a
        # loop that did not end within `timeout` still holds the lock)
        if self._lock.acquire(timeout=timeout):
            try:
                if self._flight is not None:
                    self._read_locked()
                self._sync_registry_locked(time.monotonic())
            finally:
                self._lock.release()

    def _loop(self) -> None:
        while not self._stop.is_set():
            with span("serve.wait") as sp:
                with self._cv:
                    # a step in flight is work: it is read, not slept on
                    while (self._idle_locked()
                           and not self._stop.is_set()):
                        self._cv.wait(0.05)
                        now = time.monotonic()
                        if now >= self._sync_at:
                            self._sync_registry_locked(now)
            self.stats["wait_s"] += sp.seconds  # this thread's key alone
            if self._stop.is_set():
                return
            try:
                self.step()
            except Exception:
                logger.exception("scheduler step failed")
                time.sleep(0.01)

    def drain(self) -> None:
        """Block until queue and batch are empty.  Steps inline when no
        background loop is running (synchronous/generate mode)."""
        if self._loop_alive():
            while True:
                with self._lock:
                    if self._idle_locked():
                        return
                time.sleep(0.002)
        while True:
            with self._lock:
                if self._idle_locked():
                    return
            self.step()

    def _idle_locked(self) -> bool:
        """Nothing queued, seated or in flight."""
        return (not self._queue and not self._active
                and self._flight is None)

    def _loop_alive(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    # -- graceful drain (SIGTERM lifecycle) ----------------------------------

    @property
    def draining(self) -> bool:
        return self._draining

    def begin_drain(self) -> None:
        """Close admission: every subsequent submit sheds with
        ``reason="draining"`` and ``/healthz`` degrades so load
        balancers stop routing here."""
        with self._cv:
            self._draining = True
            self._cv.notify_all()

    def drain_gracefully(self, budget_s: Optional[float] = None) -> bool:
        """Stop admission, finish in-flight work within ``budget_s``
        (default ``ServeConfig.drain_s``), then cancel whatever is left
        with ``cause="drain"``.  Returns True when everything finished
        inside the budget (no request was cut short)."""
        t0 = time.monotonic()
        self.begin_drain()
        if budget_s is None:
            budget_s = float(getattr(self.engine.config, "drain_s", 10.0))
        loop_running = self._loop_alive()
        while time.monotonic() - t0 < budget_s:
            with self._lock:
                if self._idle_locked():
                    break
            if loop_running:
                time.sleep(0.01)
            else:
                self.step()
        clean = True
        with self._cv:
            if self._flight is not None:
                # what the last launched step finished is not cut short
                self._read_locked()
            leftovers = list(self._queue)
            self._queue.clear()
            for st in leftovers:
                clean = False
                self._finish_evicted_locked(st, "drain")
            for a in self._seated_locked():
                clean = False
                self._drop_locked(a, "drain")
            self._sync_registry_locked(time.monotonic())
        dur = time.monotonic() - t0
        self.stats["drain_seconds"] = dur
        self._book("pt_serve_drain_seconds", kind="gauge", value=dur)
        logger.info("graceful drain %s in %.3fs",
                    "completed" if clean else
                    "cut short (budget exhausted)", dur)
        return clean

    # -- hang watchdog --------------------------------------------------------

    @staticmethod
    def _watchdog_mode() -> Optional[str]:
        mode = os.environ.get("PT_SERVE_WATCHDOG", "").strip().lower()
        if mode in ("", "0", "off", "false", "no"):
            return None
        return "exit" if mode == "exit" else "on"

    def _start_watchdog(self) -> None:
        mode = self._watchdog_mode()
        if mode is None:
            return
        if (self._watchdog_thread is not None
                and self._watchdog_thread.is_alive()):
            return
        factor = float(os.environ.get("PT_SERVE_WATCHDOG_FACTOR", "20"))
        floor = float(os.environ.get("PT_SERVE_WATCHDOG_FLOOR_S", "1.0"))
        self._watchdog_thread = threading.Thread(
            target=self._watchdog_loop, args=(mode, factor, floor),
            name="pt-serve-watchdog", daemon=True)
        self._watchdog_thread.start()

    def _watchdog_loop(self, mode: str, factor: float,
                       floor: float) -> None:
        poll = max(0.02, min(0.25, floor / 4))
        while not self._stop.wait(poll):
            started = self._step_started
            if started is None:
                continue
            times = self._log.periods(_WATCHDOG_STEPS)
            p99 = float(np.percentile(times, 99)) if times else None
            threshold = max(floor, factor * p99) if p99 else floor
            stuck = time.monotonic() - started
            if stuck > threshold:
                self._trip_watchdog(mode, stuck, threshold)
                return

    def _trip_watchdog(self, mode: str, stuck: float,
                       threshold: float) -> None:
        """The oldest unread decode step is hung (NOT merely loaded: the
        threshold tracks the rolling p99 of the step period).  Runs
        WITHOUT the scheduler lock — the hung step's launch or read is
        holding it."""
        self.hang_detected = True
        self.stats["watchdog_trips"] += 1
        try:
            flight = self._flight
            rids = [a.stream.request_id for a in
                    (flight.rows if flight is not None
                     else list(self._active))]
        except Exception:
            rids = []
        logger.error(
            "serve hang watchdog tripped: decode step unread for "
            "%.3fs (threshold %.3fs); active batch %s",
            stuck, threshold, rids)
        self._book("pt_serve_hang_watchdog_trips_total", kind="counter")
        try:
            # what ran before the hang, whether or not the tracer's ring
            # holds spans: the last program calls (time.monotonic stamps)
            get_tracer().flight_dump(
                reason="serve-hang rid=%s stuck=%.3fs" %
                (",".join(map(str, rids)) or "-", stuck),
                extra={"serve_steps": self._log.as_dicts(64),
                       "serve_step_unread_since": self._step_started})
        except Exception:
            pass
        if mode == "exit":
            logger.error("PT_SERVE_WATCHDOG=exit: fast-exiting %d for "
                         "supervisor restart", WATCHDOG_EXIT_CODE)
            os._exit(WATCHDOG_EXIT_CODE)

    # -- observability -------------------------------------------------------

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            if self._flight is not None and not self._loop_alive():
                # stepped inline, the caller owns the batch: the counters
                # then hold the last step too (a running loop reads it
                # within a step)
                self._read_locked()
            self._sync_registry_locked(time.monotonic())
            occ = (self.stats["occupancy_sum"] /
                   max(1, self.stats["occupancy_steps"]))
            periods = self._log.periods()
            p50, p99 = (map(float, np.percentile(periods, (50, 99)))
                        if periods else (None, None))
            return {
                "queue_depth": len(self._queue),
                "active_sequences": len(self._seated_locked()),
                "batch_occupancy_mean": occ,
                "draining": self._draining,
                "hang_detected": self.hang_detected,
                "decode_step_ewma_s": self._step_ewma,
                # over the step log's decode records (the last 4,096
                # program calls): None before the first booked step
                "step_period_p50_s": p50, "step_period_p99_s": p99,
                # running means over the process's life, seconds
                "lock_wait_mean_s": _mean(self.stats, "lock_wait_s",
                                          "submitted"),
                "queue_wait_mean_s": _mean(self.stats, "queue_wait_s",
                                           "admitted"),
                "ttft_mean_s": _mean(self.stats, "ttft_s", "admitted"),
                "tpot_mean_s": _mean(self.stats, "tpot_s",
                                     "tpot_requests"),
                "token_gap_max_mean_s": _mean(self.stats, "token_gap_max_s",
                                              "tpot_requests"),
                # row-seconds that seated rows waited, a prefill
                "prefill_row_stall_mean_s": _mean(
                    self.stats, "prefill_row_stall_s", "prefill_runs"),
                **{k: v for k, v in self.stats.items()
                   if k not in ("occupancy_sum",)},
            }

    def step_log(self, last_n: Optional[int] = None) -> List[tuple]:
        """Copies of the newest ``last_n`` records of the step log (all
        4,096 at most for None), oldest first: module docstring."""
        return self._log.last(last_n)

    def _registry(self):
        """The registry to book into, or None while telemetry is off
        (it must stay empty then).  Instruments are looked up once each
        and kept, until the registry itself is replaced: a new one is
        brought up to ``stats`` whole by the next sync."""
        try:
            if not get_telemetry().enabled:
                return None
            reg = get_registry()
        except Exception:
            return None
        if reg is not self._meter_registry:
            self._meter_registry, self._meters = reg, {}
            self._synced = {}
        return reg

    def _book(self, name: str, *, kind: str, value: float = 1.0,
              labels: Optional[Dict[str, str]] = None, **more) -> None:
        """Metric booking; inert while telemetry is off
        (:meth:`_registry`).  Labels are keywords, or ``labels`` where
        one is named ``kind``."""
        labels = {**(labels or {}), **more}
        try:
            reg = self._registry()
            if reg is None:
                return
            m = self._meters.get(name)
            if m is None:
                m = self._meters[name] = getattr(reg, kind)(
                    name, _METRIC_HELP.get(name, ""),
                    labelnames=tuple(labels))
            if kind == "counter":
                m.inc(value, **labels)
            elif kind == "gauge":
                m.set(value, **labels)
            else:
                m.observe(value, **labels)
        except Exception:
            pass


def _mean(stats, total, count):
    return stats[total] / stats[count] if stats[count] else None


# counters the registry holds of `stats`, brought up to it by
# _sync_registry_locked: (instrument, labels, the key, a key to take off)
_SYNCED = (
    ("pt_serve_requests_total", None, "submitted", None),
    ("pt_serve_completed_total", None, "completed", None),
    ("pt_serve_admission_refusals_total", {"reason": "inflight_cap"},
     "refused_inflight", None),
    ("pt_serve_admission_refusals_total", {"reason": "kv_headroom"},
     "refused_kv", None),
    ("pt_serve_admission_refusals_total", {"reason": "state_slots"},
     "refused_state", None),
    ("pt_serve_tokens_total", None, "tokens_generated", None),
    ("pt_serve_prefill_tokens_total", None, "prefill_tokens", None),
    ("pt_serve_decode_tokens_total", None, "decode_tokens", None),
    ("pt_serve_decode_steps_total", {"launch": "ahead"},
     "decode_steps_ahead", None),
    ("pt_serve_decode_steps_total", {"launch": "sync"},
     "occupancy_steps", "decode_steps_ahead"),
    ("pt_serve_ssm_tokens_scanned_total", None, "ssm_tokens_scanned", None),
    ("pt_serve_moe_tokens_routed_total", None, "moe_tokens_routed", None),
    ("pt_serve_moe_expert_max_tokens_total", None, "moe_expert_max_tokens",
     None),
    ("pt_serve_paged_chunks_total", {"state": "walked"},
     "paged_chunks_walked", None),
    ("pt_serve_paged_chunks_total", {"state": "grid"},
     "paged_grid_steps", None),
    ("pt_serve_sparse_tokens_total", {"kind": "scored"},
     "sparse_tokens_scored", None),
    ("pt_serve_sparse_tokens_total", {"kind": "selected"},
     "sparse_tokens_selected", None),
)


_METRIC_HELP = {
    "pt_serve_requests_total": "Requests accepted by the serve scheduler",
    "pt_serve_completed_total": "Requests completed",
    "pt_serve_admission_refusals_total":
        "Admissions refused, by reason "
        "(inflight_cap|kv_headroom|state_slots)",
    "pt_serve_shed_total":
        "Requests shed at admission, by reason "
        "(deadline_infeasible|queue_full|draining)",
    "pt_serve_cancelled_total":
        "Requests evicted before completing, by cause "
        "(client|timeout|deadline|disconnect|drain)",
    "pt_serve_drain_seconds":
        "Wall time of the last graceful drain",
    "pt_serve_request_failures_total":
        "Requests failed by an exception in the step loop, by stage "
        "(prefill|decode|step)",
    "pt_serve_hang_watchdog_trips_total":
        "Hang-watchdog trips (decode step exceeded Nx rolling p99)",
    "pt_serve_tokens_total": "Tokens generated by the serve engine",
    "pt_serve_prefill_tokens_total": "Prompt positions prefilled",
    "pt_serve_decode_tokens_total": "Rows decoded, summed over steps",
    "pt_serve_ssm_tokens_scanned_total":
        "Prompt positions through the state-space layers' scan",
    "pt_serve_moe_tokens_routed_total":
        "Token-expert pairs routed, summed over layers",
    "pt_serve_moe_expert_max_tokens_total":
        "Tokens of the busiest expert, summed over calls and layers",
    "pt_serve_paged_chunks_total":
        "Paged attention, summed over decode steps and the program's "
        "work lists: chunks the rows' contexts or windows fill (walked) "
        "and grid steps of the bucket's program (grid)",
    "pt_serve_sparse_tokens_total":
        "Learned sparse attention, summed over decode steps and sparse "
        "layers: cached indexer keys scored (scored) and tokens the "
        "layers attended over (selected)",
    "pt_serve_queue_depth": "Requests waiting for admission",
    "pt_serve_active_sequences": "Sequences resident in the decode batch",
    "pt_serve_decode_steps_total":
        "Decode steps booked, by launch: before the step before was read "
        "(ahead) or after (sync)",
    "pt_serve_request_latency_seconds":
        "End-to-end request latency (entry of submit to last token)",
    "pt_serve_queue_wait_seconds":
        "Time a request waited in the queue (enqueued to its prefill)",
    "pt_serve_ttft_seconds":
        "Time to first token (entry of submit to the prefill's token)",
    "pt_serve_tpot_seconds":
        "Mean time per output token after the first, per request",
    "pt_serve_token_gap_max_seconds":
        "Largest gap between two consecutive tokens, per request",
}
