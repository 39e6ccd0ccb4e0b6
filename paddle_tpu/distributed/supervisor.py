"""Self-healing training-job supervisor.

A preemptible TPU fleet fails in three distinct ways, and each needs a
different reflex, not an operator page:

 - a **worker** dies (SIGKILL, OOM, watchdog, drain): the survivors
   notice at the next commit barrier and exit; the supervisor relaunches
   the whole fleet as a fresh *generation* (new run id) and training
   resumes from the last committed checkpoint.  Relaunches are metered
   by a per-rank restart budget over a rolling window
   (``PT_SUPERVISOR_MAX_RESTARTS`` / ``PT_SUPERVISOR_RESTART_WINDOW``)
   so a crash-looping rank fails the job *deterministically*, naming
   the rank — and, when the crashes correlate with one data shard, the
   quarantined shard.
 - the **store master** dies: :class:`StandbyStoreGuard` runs a hot
   standby (:class:`~paddle_tpu.core.store_server.StoreFollower`
   tailing the master's WAL), promotes it, and atomically republishes
   the endpoint file; :class:`~.resilient_store.ResilientStore` clients
   re-resolve and ride through with the generation fence intact —
   **zero worker exits**, no restart budget spent.
 - a rank is **dead past its lease** (its host is gone — spawn keeps
   failing): the supervisor relaunches the survivors at a smaller world
   size; the workers' ``elastic=True`` checkpoint reshard absorbs the
   new partitioning.

Restart granularity is the *fleet generation*, not the single rank:
checkpoint commit-barrier keys include the run id, so every rank of a
step must share one — a per-rank respawn into an old generation would
wedge at the first barrier.  The root-cause rank is whichever exited
with a non-:data:`~.exit_codes.EXIT_SAVE_FAILED` failure first
(survivors of a peer death exit ``EXIT_SAVE_FAILED`` as a
*consequence*), and only the root cause is charged against the budget.

Everything here is subprocess-level and stdlib-only at import time
(observability is imported lazily), so the supervisor itself never
touches jax and survives any worker-side crash.  Proven end-to-end on
CPU by ``paddle_tpu.distributed.drill.run_supervisor_drill``.
"""
from __future__ import annotations

import collections
import logging
import os
import subprocess
import sys
import time

from ..utils.retry import backoff_delays, wait_until
from .exit_codes import EXIT_SAVE_FAILED, classify, describe
from .resilient_store import read_endpoint_file

__all__ = [
    "RestartBudgetExhausted",
    "SpawnFailed",
    "StandbyStoreGuard",
    "Supervisor",
    "supervision_snapshot",
]

logger = logging.getLogger(__name__)

#: restart budget: relaunches allowed per root-cause rank (and for the
#: store) inside one rolling window before the job fails loudly
DEFAULT_MAX_RESTARTS = 5
#: rolling-window length (seconds) for the restart budget
DEFAULT_RESTART_WINDOW = 300.0
#: hardware budget: EXIT_SDC verdicts are charged to a SEPARATE
#: per-rank ledger — a chip flipping bits is not a code crash, and one
#: must not eat the other's budget
DEFAULT_SDC_MAX_RESTARTS = 3
#: consensus verdicts against one rank before it is quarantined and the
#: fleet downsizes around it
DEFAULT_SDC_QUARANTINE_THRESHOLD = 2

_STORE_MASTER_SCRIPT = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "drill", "store_master.py")

# most recent Supervisor in this process; supervision_snapshot() reads it
_LAST_SUPERVISOR = None


class SpawnFailed(RuntimeError):
    """Raised by a spawn callable when a rank cannot be (re)launched.

    The supervisor retries the spawn with backoff until the rank's
    lease expires, then relaunches the survivors at a smaller world.
    """


class RestartBudgetExhausted(RuntimeError):
    """The restart budget ran out; ``rank``/``shard``/``cause`` name
    the root cause (``rank is None`` for store-side exhaustion,
    ``shard`` only when the crash loop correlated with one data
    shard)."""

    def __init__(self, message, *, rank=None, shard=None, cause=None):
        super().__init__(message)
        self.rank = rank
        self.shard = shard
        self.cause = cause


class _ResizeNeeded(Exception):
    """Internal: a rank's spawn lease expired; relaunch smaller."""

    def __init__(self, new_world, dead_ranks):
        super().__init__(f"downsize to world={new_world}")
        self.new_world = new_world
        self.dead_ranks = dead_ranks


def _inc_counter(name, help_, cause=None):
    """Book a metric, tolerating a stripped-down environment: the
    supervisor must keep restarting jobs even if observability is
    broken."""
    try:
        from ..observability.metrics import get_registry
        if cause is None:
            get_registry().counter(name, help_).inc(1)
        else:
            get_registry().counter(name, help_,
                                   labelnames=("cause",)).inc(1, cause=cause)
    except Exception:  # pragma: no cover - observability must not kill us
        logger.exception("metrics booking failed for %s", name)


def _record_replay_badput(seconds):
    """Feed the goodput ledger's ``restart_replay`` badput bucket with
    the wall time a restart cost (drain + backoff + respawn): the best
    process-level proxy for re-executed work the supervisor can
    measure."""
    try:
        from ..observability.goodput import get_goodput
        gp = get_goodput()
        if not gp.enabled:
            gp.enable()
        gp.record_restart_replay(float(seconds))
    except Exception:  # pragma: no cover
        logger.exception("goodput booking failed")


class StandbyStoreGuard:
    """Run a durable store master plus a hot standby; promote on death.

    The master (``drill/store_master.py``, path-loaded and stdlib-only
    so a respawn costs one interpreter start) serves with a WAL; the
    standby tails that WAL with a
    :class:`~paddle_tpu.core.store_server.StoreFollower`.  When
    :meth:`poll` finds the master dead it *unlinks the endpoint file
    first* (clients must not reconnect to the corpse's port), touches
    the standby's promote-trigger file, and waits for the promoted
    server to republish the endpoint — at a bumped generation, so the
    :class:`~.resilient_store.ResilientStore` fence stays intact.  A
    fresh standby is then spawned behind the new master.

    ``track``, when given, observes every child ``Popen`` (the drill
    runner registers them for leak-proof reaping).
    """

    def __init__(self, root, *, host="127.0.0.1", port=0,
                 endpoint_file=None, wal_path=None, log_dir=None,
                 poll_interval=0.05, spawn_timeout=30.0,
                 promote_timeout=30.0, track=None):
        self.root = str(root)
        self.host = host
        self.port = int(port)
        self.endpoint_file = endpoint_file or os.path.join(
            self.root, "store.endpoint")
        self.wal_path = wal_path or os.path.join(self.root, "store.wal")
        self.log_dir = log_dir
        self.poll_interval = float(poll_interval)
        self.spawn_timeout = float(spawn_timeout)
        self.promote_timeout = float(promote_timeout)
        self._track = track
        self.master = None
        self.standby = None
        self.promotions = 0
        self._seq = 0  # unique promote-trigger per standby incarnation
        self._logs = []

    # -- child management ---------------------------------------------------

    def _popen(self, cmd, tag):
        stderr = None
        if self.log_dir:
            os.makedirs(self.log_dir, exist_ok=True)
            f = open(os.path.join(self.log_dir, f"{tag}.log"), "ab")
            self._logs.append(f)
            stderr = f
        proc = subprocess.Popen(cmd, stdin=subprocess.DEVNULL,
                                stdout=stderr, stderr=stderr)
        if self._track is not None:
            self._track(proc)
        return proc

    def _spawn_master(self):
        # stale endpoint from a previous life must not satisfy the
        # "published" wait below
        try:
            os.unlink(self.endpoint_file)
        except FileNotFoundError:
            pass
        cmd = [sys.executable, _STORE_MASTER_SCRIPT,
               "--host", self.host, "--port", str(self.port),
               "--endpoint-file", self.endpoint_file,
               "--wal", self.wal_path]
        proc = self._popen(cmd, f"store-master.{self._seq}")
        wait_until(lambda: read_endpoint_file(self.endpoint_file),
                   timeout=self.spawn_timeout,
                   desc=f"store master publish to {self.endpoint_file}")
        return proc

    def _spawn_standby(self):
        self._seq += 1
        trigger = os.path.join(self.root, f"store.promote.{self._seq}")
        try:
            os.unlink(trigger)
        except FileNotFoundError:
            pass
        cmd = [sys.executable, _STORE_MASTER_SCRIPT,
               "--host", self.host, "--port", str(self.port),
               "--endpoint-file", self.endpoint_file,
               "--wal", self.wal_path,
               "--standby", "--promote-file", trigger,
               "--poll-interval", str(self.poll_interval)]
        proc = self._popen(cmd, f"store-standby.{self._seq}")
        proc.promote_trigger = trigger
        return proc

    # -- lifecycle ----------------------------------------------------------

    def start(self):
        """Spawn master + standby; returns ``(host, port)``."""
        self.master = self._spawn_master()
        self.standby = self._spawn_standby()
        ep = read_endpoint_file(self.endpoint_file)
        logger.info("store guard up: master pid=%d standby pid=%d at %s:%d",
                    self.master.pid, self.standby.pid, ep[0], ep[1])
        return ep

    def poll(self):
        """One health probe; returns True iff a promotion happened."""
        if self.master is None:
            return False
        if self.master.poll() is None:
            # master healthy; resurrect a crashed standby quietly
            if self.standby is not None and self.standby.poll() is not None:
                logger.warning("store standby died (rc=%s); respawning",
                               self.standby.returncode)
                self.standby = self._spawn_standby()
            return False
        self.promote()
        return True

    def promote(self):
        """Master is dead: promote the standby and republish."""
        rc = self.master.returncode
        logger.warning("store master pid=%d dead (rc=%s); promoting standby",
                       self.master.pid, rc)
        if self.standby is None or self.standby.poll() is not None:
            raise RuntimeError(
                "store master died and no live standby to promote "
                f"(master rc={rc})")
        # clients re-resolving must block on the *new* endpoint, never
        # race onto the corpse's port
        try:
            os.unlink(self.endpoint_file)
        except FileNotFoundError:
            pass
        trigger = self.standby.promote_trigger
        with open(trigger, "w", encoding="ascii") as f:
            f.write("promote\n")
        wait_until(lambda: read_endpoint_file(self.endpoint_file),
                   timeout=self.promote_timeout,
                   desc="promoted standby endpoint republish",
                   diag=lambda: (f"standby rc={self.standby.poll()}"))
        self.master, self.standby = self.standby, None
        self.promotions += 1
        _inc_counter("pt_store_promotions_total",
                     "Hot-standby store promotions")
        ep = read_endpoint_file(self.endpoint_file)
        logger.warning("standby promoted: new master pid=%d at %s:%d",
                       self.master.pid, ep[0], ep[1])
        # re-arm: the new master needs its own understudy
        self.standby = self._spawn_standby()
        return ep

    def kill_master(self):
        """Chaos hook: SIGKILL the current master (drills use this)."""
        self.master.kill()

    def stop(self):
        for proc in (self.master, self.standby):
            if proc is not None and proc.poll() is None:
                proc.kill()
        for proc in (self.master, self.standby):
            if proc is not None:
                try:
                    proc.wait(timeout=10.0)
                except subprocess.TimeoutExpired:
                    logger.warning("store child pid %d did not exit "
                                   "after SIGKILL", proc.pid)
        for f in self._logs:
            try:
                f.close()
            except OSError:
                pass
        self._logs.clear()


class Supervisor:
    """Relaunch a worker fleet under a restart budget.

    ``spawn(rank, world, run_id, generation)`` must return a started
    ``subprocess.Popen`` (or raise :class:`SpawnFailed`).  The run id
    is fresh per generation — checkpoint commit barriers key on it, so
    a generation either commits a step together or not at all.

    ``shard_of(rank)`` maps a rank to its data-shard name for
    crash-loop correlation; when every budget-charged failure inside
    the window lands on one shard and that shard reaches
    ``quarantine_threshold`` failures, the shard is quarantined (named
    diagnostic, surfaced on :class:`RestartBudgetExhausted` and in
    :meth:`snapshot`) so the operator knows it is a *data* problem,
    not a host problem.

    ``EXIT_SDC`` verdicts get the mirror-image treatment on the
    *hardware* side: they are charged to a separate per-rank ledger
    (``sdc_max_restarts``, never mixed with code-crash charges), and a
    rank fingered ``sdc_quarantine_threshold`` times by replica
    consensus is quarantined — a named ``RankQuarantine`` diagnostic,
    after which the next generation elastically downsizes around the
    suspect host exactly like an expired spawn lease.
    """

    def __init__(self, spawn, world, *,
                 max_restarts=None, restart_window=None,
                 min_world=1, spawn_lease=5.0,
                 shard_of=None, quarantine_threshold=3,
                 sdc_max_restarts=None, sdc_quarantine_threshold=None,
                 grace=20.0, kill_grace=10.0, generation_timeout=None,
                 store_guard=None, poll_interval=0.1,
                 backoff_base=0.05, backoff_factor=2.0, backoff_max=1.0,
                 run_id_prefix="sup", clock=time.monotonic,
                 sleep=time.sleep):
        if max_restarts is None:
            max_restarts = int(os.environ.get(
                "PT_SUPERVISOR_MAX_RESTARTS", str(DEFAULT_MAX_RESTARTS)))
        if restart_window is None:
            restart_window = float(os.environ.get(
                "PT_SUPERVISOR_RESTART_WINDOW", str(DEFAULT_RESTART_WINDOW)))
        if sdc_max_restarts is None:
            sdc_max_restarts = int(os.environ.get(
                "PT_SUPERVISOR_SDC_MAX_RESTARTS",
                str(DEFAULT_SDC_MAX_RESTARTS)))
        if sdc_quarantine_threshold is None:
            sdc_quarantine_threshold = int(os.environ.get(
                "PT_SUPERVISOR_SDC_QUARANTINE_THRESHOLD",
                str(DEFAULT_SDC_QUARANTINE_THRESHOLD)))
        self._spawn = spawn
        self.world = int(world)
        self.max_restarts = int(max_restarts)
        self.restart_window = float(restart_window)
        self.min_world = int(min_world)
        self.spawn_lease = float(spawn_lease)
        self.shard_of = shard_of if shard_of is not None else str
        self.quarantine_threshold = int(quarantine_threshold)
        self.sdc_max_restarts = int(sdc_max_restarts)
        self.sdc_quarantine_threshold = int(sdc_quarantine_threshold)
        self.grace = float(grace)
        self.kill_grace = float(kill_grace)
        self.generation_timeout = generation_timeout
        self.store_guard = store_guard
        self.poll_interval = float(poll_interval)
        self.run_id_prefix = run_id_prefix
        self._clock = clock
        self._sleep = sleep
        self._delays = backoff_delays(base=backoff_base,
                                      factor=backoff_factor,
                                      max_delay=backoff_max,
                                      clock=clock)
        # budget ledgers: key is a rank (int), "store", or "sdc:<rank>"
        # (the hardware ledger — EXIT_SDC charges never share a key
        # with code-crash charges)
        self._failures = collections.defaultdict(collections.deque)
        self._shard_failures = collections.Counter()
        self._sdc_failures = collections.Counter()  # rank -> verdicts
        self.quarantined_shards = set()
        self.quarantined_ranks = set()
        self.restarts = collections.Counter()  # cause -> count
        self.resizes = []
        self.generation = 0
        self.replay_seconds = 0.0
        global _LAST_SUPERVISOR
        _LAST_SUPERVISOR = self

    # -- spawning -----------------------------------------------------------

    def _spawn_rank(self, rank, world, run_id):
        last = None
        delays = backoff_delays(base=0.05, factor=2.0, max_delay=0.5,
                                deadline=self.spawn_lease,
                                clock=self._clock)
        while True:
            try:
                return self._spawn(rank, world, run_id, self.generation)
            except SpawnFailed as e:
                last = e
                d = next(delays, None)
                if d is None:
                    raise SpawnFailed(
                        f"rank {rank} dead past its {self.spawn_lease}s "
                        f"lease: {last}") from last
                self._sleep(d)

    def _spawn_generation(self, world, run_id):
        procs = {}
        dead = []
        for rank in range(world):
            try:
                procs[rank] = self._spawn_rank(rank, world, run_id)
            except SpawnFailed as e:
                logger.error("generation %d: %s", self.generation, e)
                dead.append(rank)
        if dead:
            # a partial fleet would wedge at the first commit barrier —
            # abort it and relaunch everyone at the smaller world
            self._drain(procs)
            new_world = world - len(dead)
            raise _ResizeNeeded(new_world, dead)
        return procs

    # -- watching -----------------------------------------------------------

    def _drain(self, procs, *, term_first=True):
        running = [p for p in procs.values() if p.poll() is None]
        if term_first:
            for p in running:
                try:
                    p.terminate()
                except OSError:
                    pass
            deadline = self._clock() + self.kill_grace
            wait_until(lambda: (all(p.poll() is not None for p in running)
                                or self._clock() >= deadline),
                       timeout=None, sleep=self._sleep, clock=self._clock,
                       max_delay=self.poll_interval)
        for p in running:
            if p.poll() is None:
                try:
                    p.kill()
                except OSError:
                    pass
        for p in running:
            try:
                p.wait(timeout=10.0)
            except subprocess.TimeoutExpired:
                logger.warning("worker pid %d did not exit after "
                               "SIGKILL", p.pid)

    def _watch(self, procs):
        """Block until every worker of this generation exited; escalate
        SIGTERM→SIGKILL on stragglers once a peer failed, and keep the
        store guard's promote reflex ticking the whole time.  Returns
        ``{rank: returncode}``."""
        state = {"first_fail": None, "termed": None}

        def settled():
            if self.store_guard is not None:
                self.store_guard.poll()
            rcs = {r: p.poll() for r, p in procs.items()}
            if all(rc is not None for rc in rcs.values()):
                return rcs
            now = self._clock()
            if state["first_fail"] is None and any(
                    rc not in (None, 0) for rc in rcs.values()):
                state["first_fail"] = now
            if state["first_fail"] is not None:
                if state["termed"] is None and (
                        now - state["first_fail"] > self.grace):
                    logger.warning(
                        "generation %d: draining stragglers %s after "
                        "%.1fs grace", self.generation,
                        [r for r, rc in rcs.items() if rc is None],
                        self.grace)
                    for r, rc in rcs.items():
                        if rc is None:
                            try:
                                procs[r].terminate()
                            except OSError:
                                pass
                    state["termed"] = now
                elif state["termed"] is not None and (
                        now - state["termed"] > self.kill_grace):
                    for r, rc in rcs.items():
                        if rc is None:
                            try:
                                procs[r].kill()
                            except OSError:
                                pass
            return False

        return wait_until(
            settled, timeout=self.generation_timeout,
            desc=f"generation {self.generation} fleet exit",
            diag=lambda: "rcs=%r" % {r: p.poll() for r, p in procs.items()},
            max_delay=self.poll_interval, sleep=self._sleep,
            clock=self._clock)

    # -- diagnosis / budget -------------------------------------------------

    @staticmethod
    def _diagnose(rcs):
        """Root-cause rank and cause for a failed generation: the first
        rank (by id) whose exit is NOT the save-failed consequence code;
        all-save-failed falls back to the first nonzero rank."""
        root = [(r, rc) for r, rc in sorted(rcs.items())
                if rc not in (0, EXIT_SAVE_FAILED)]
        if not root:
            root = [(r, rc) for r, rc in sorted(rcs.items()) if rc != 0]
        rank, rc = root[0]
        return rank, rc, classify(rc)

    def _charge(self, rank, rc, cause):
        """Charge one failure against the budget; raises
        :class:`RestartBudgetExhausted` when the rolling window
        overflows.  Returns the rank to quarantine when this charge
        crossed the SDC consensus threshold (else ``None``)."""
        if cause == "sdc":
            return self._charge_sdc(rank, rc)
        key = "store" if cause == "store_lost" else rank
        now = self._clock()
        dq = self._failures[key]
        dq.append(now)
        while dq and now - dq[0] > self.restart_window:
            dq.popleft()
        shard = None
        if isinstance(key, int):
            shard = self.shard_of(key)
            self._shard_failures[shard] += 1
            correlated = all(n == 0 for s, n in self._shard_failures.items()
                             if s != shard)
            if (correlated and shard not in self.quarantined_shards
                    and self._shard_failures[shard]
                    >= self.quarantine_threshold):
                self.quarantined_shards.add(shard)
                logger.error(
                    "ShardQuarantine: data shard %r quarantined — %d "
                    "consecutive failures, all on rank %d reading this "
                    "shard; the crash loop is data-correlated (poisoned "
                    "input?), not a host fault", shard,
                    self._shard_failures[shard], rank)
        if len(dq) > self.max_restarts:
            where = (f"rank {rank}" if key != "store" else "store master")
            quarantined = shard if shard in self.quarantined_shards else None
            msg = (f"restart budget exhausted: {where} failed "
                   f"{len(dq)} times inside {self.restart_window:.0f}s "
                   f"(budget {self.max_restarts}); last exit "
                   f"{describe(rc)}")
            if quarantined is not None:
                msg += (f"; data shard {quarantined!r} is quarantined "
                        f"(crash loop correlated with this shard)")
            raise RestartBudgetExhausted(
                msg, rank=None if key == "store" else rank,
                shard=quarantined, cause=cause)
        return None

    def _charge_sdc(self, rank, rc):
        """Charge an ``EXIT_SDC`` verdict to the *hardware* ledger.

        Consensus verdicts never touch the code-crash budget (a flaky
        chip must not exhaust a rank's crash allowance, nor hide behind
        it); instead each verdict accrues toward quarantine, and a rank
        fingered ``sdc_quarantine_threshold`` times is handed back to
        :meth:`run` for an elastic downsize around the suspect host."""
        now = self._clock()
        dq = self._failures[f"sdc:{rank}"]
        dq.append(now)
        while dq and now - dq[0] > self.restart_window:
            dq.popleft()
        self._sdc_failures[rank] += 1
        verdicts = self._sdc_failures[rank]
        if (rank not in self.quarantined_ranks
                and verdicts >= self.sdc_quarantine_threshold):
            self.quarantined_ranks.add(rank)
            logger.error(
                "RankQuarantine: rank %d quarantined — fingered by "
                "replica consensus %d times (%s); silent data "
                "corruption is a hardware fault, and the next "
                "generation downsizes around the suspect host",
                rank, verdicts, describe(rc))
            _inc_counter("pt_supervisor_rank_quarantines_total",
                         "Ranks quarantined after repeated SDC "
                         "consensus verdicts")
            return rank
        if len(dq) > self.sdc_max_restarts:
            raise RestartBudgetExhausted(
                f"hardware restart budget exhausted: rank {rank} was "
                f"fingered by replica consensus {len(dq)} times inside "
                f"{self.restart_window:.0f}s (sdc budget "
                f"{self.sdc_max_restarts}); last exit {describe(rc)}",
                rank=rank, cause="sdc")
        return None

    # -- main loop ----------------------------------------------------------

    def run(self):
        """Supervise until the fleet finishes cleanly (returns a report
        dict) or the budget is exhausted
        (:class:`RestartBudgetExhausted`)."""
        world = self.world
        while True:
            run_id = f"{self.run_id_prefix}-g{self.generation}"
            try:
                procs = self._spawn_generation(world, run_id)
            except _ResizeNeeded as rz:
                if rz.new_world < self.min_world:
                    raise RestartBudgetExhausted(
                        f"cannot downsize below min_world="
                        f"{self.min_world}: ranks {rz.dead_ranks} dead "
                        f"past their {self.spawn_lease}s lease at "
                        f"world={world}", cause="lease_expired")
                logger.warning(
                    "generation %d: ranks %s dead past lease; "
                    "relaunching survivors at world=%d (elastic "
                    "reshard)", self.generation, rz.dead_ranks,
                    rz.new_world)
                self.resizes.append({"generation": self.generation,
                                     "from_world": world,
                                     "to_world": rz.new_world,
                                     "dead_ranks": list(rz.dead_ranks)})
                world = self.world = rz.new_world
                self._book_restart("lease_expired", 0.0)
                self.generation += 1
                continue
            rcs = self._watch(procs)
            if all(rc == 0 for rc in rcs.values()):
                return self._report(world, rcs)
            fail_t = self._clock()
            rank, rc, cause = self._diagnose(rcs)
            logger.warning(
                "generation %d failed: root cause rank %d exited %s "
                "(full rcs: %s)", self.generation, rank, describe(rc),
                {r: rcs[r] for r in sorted(rcs)})
            quarantine = self._charge(rank, rc, cause)
            if quarantine is not None:
                new_world = world - 1
                if new_world < self.min_world:
                    raise RestartBudgetExhausted(
                        f"cannot downsize below min_world="
                        f"{self.min_world}: rank {quarantine} is "
                        f"quarantined after repeated SDC consensus "
                        f"verdicts at world={world}",
                        rank=quarantine, cause="sdc")
                logger.warning(
                    "generation %d: quarantined rank %d absorbed by "
                    "elastic downsize; relaunching survivors at "
                    "world=%d", self.generation, quarantine, new_world)
                self.resizes.append({"generation": self.generation,
                                     "from_world": world,
                                     "to_world": new_world,
                                     "dead_ranks": [quarantine],
                                     "quarantined": True})
                world = self.world = new_world
            self._sleep(next(self._delays))
            outage = max(0.0, self._clock() - fail_t)
            self._book_restart(cause, outage)
            self.generation += 1

    def _book_restart(self, cause, outage_seconds):
        self.restarts[cause] += 1
        self.replay_seconds += outage_seconds
        _inc_counter("pt_supervisor_restarts_total",
                     "Fleet relaunches by the supervisor, by root cause",
                     cause=cause)
        if outage_seconds > 0.0:
            _record_replay_badput(outage_seconds)

    def _report(self, world, rcs):
        logger.info("fleet finished cleanly at generation %d (world=%d, "
                    "%d restarts)", self.generation, world,
                    sum(self.restarts.values()))
        return self.snapshot(final_rcs={r: rcs[r] for r in sorted(rcs)})

    def snapshot(self, **extra):
        """JSON-ready supervision summary (bench records embed this)."""
        snap = {
            "world": self.world,
            "generations": self.generation + 1,
            "restarts_total": sum(self.restarts.values()),
            "restarts_by_cause": dict(self.restarts),
            "promotions": (self.store_guard.promotions
                           if self.store_guard is not None else 0),
            "quarantined_shards": sorted(self.quarantined_shards),
            "quarantined_ranks": sorted(self.quarantined_ranks),
            "sdc_verdicts": {str(r): n
                             for r, n in sorted(self._sdc_failures.items())},
            "resizes": list(self.resizes),
            "restart_replay_seconds": round(self.replay_seconds, 6),
        }
        snap.update(extra)
        return snap

    def close(self):
        if self.store_guard is not None:
            self.store_guard.stop()


def supervision_snapshot():
    """Process-wide supervision summary for bench/serve records.

    Reflects the most recent :class:`Supervisor` in this process; a
    process that never supervised anything gets an all-zero block, so
    consumers (bench.py's record emitter) can embed it unconditionally.
    """
    if _LAST_SUPERVISOR is not None:
        return _LAST_SUPERVISOR.snapshot()
    return {
        "world": 0,
        "generations": 0,
        "restarts_total": 0,
        "restarts_by_cause": {},
        "promotions": 0,
        "quarantined_shards": [],
        "quarantined_ranks": [],
        "sdc_verdicts": {},
        "resizes": [],
        "restart_replay_seconds": 0.0,
    }
