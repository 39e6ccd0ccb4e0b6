"""Drill runner: spawn real worker fleets, kill one, prove recovery.

The runner is the drill's control plane AND its oracle: it hosts the
TCPStore master, launches each generation of workers
(``python -m paddle_tpu.distributed.drill.worker``), waits for the
scripted SIGKILL to play out, then independently replays the
deterministic update (:func:`..drill.worker.advance`) and compares the
newest committed checkpoint bit-for-bit (``ndarray.tobytes()`` — CRC
verification happens inside ``verify_checkpoint`` first).

Every spawned process is tracked in a module-level registry so a test
harness can guarantee no leaked children regardless of how an
assertion fails (see tests/drills/conftest.py's reaper fixture).
"""
from __future__ import annotations

import json
import logging
import os
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request
import uuid

from ...core import TCPStore
from ...utils.retry import wait_until
from ..checkpoint import (CheckpointCorruptError, read_leaf,
                          verify_checkpoint)
from ..checkpoint_manager import CheckpointManager
from ..resilient_store import ResilientStore, read_endpoint_file
from .worker import (EXIT_NUMERICS_HALT, EXIT_OOM, EXIT_SAVE_FAILED,
                     EXIT_SDC, EXIT_STORE_LOST, advance, init_state,
                     numerics_report_path, obs_ready_key,
                     obs_release_key, oom_metrics_path,
                     oom_report_path, sdc_report_path,
                     trace_report_path)

__all__ = ["KillSpec", "StoreKillSpec", "ObsSpec", "TraceSpec",
           "NumericsSpec", "OomSpec", "SdcSpec", "DrillFailure",
           "spawn_worker", "spawn_store_master", "spawn_aggregator",
           "spawn_serve_worker", "poison_shard", "run_drill",
           "run_store_kill_drill", "run_scrape_drill",
           "run_serve_chaos_drill", "run_supervisor_drill",
           "run_trace_drill", "run_numerics_drill", "run_oom_drill",
           "run_sdc_drill", "run_overlap_drill",
           "run_sharded_overlap_drill", "reap_all"]

logger = logging.getLogger(__name__)

# repo root (…/paddle_tpu/distributed/drill/runner.py → 4 levels up) so
# spawned workers can import the package without an install step
_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

_LIVE: set = set()  # every Popen this module ever spawned, minus reaped


class DrillFailure(AssertionError):
    """A drill's recovery invariant did not hold."""


class KillSpec:
    """Scripted kill: SIGKILL ``rank`` at ``phase`` of step ``step``'s
    save (phases: see :mod:`.injector`)."""

    __slots__ = ("phase", "step", "rank")

    def __init__(self, phase, step, rank=1):
        self.phase = phase
        self.step = int(step)
        self.rank = int(rank)

    def expected_commit(self):
        """Newest step that must be committed after this kill plays
        out: ``mid-barrier`` is the one phase where the victim has
        already sealed its part, so rank 0 still promotes step K —
        unless the victim IS rank 0, which dies before promoting."""
        if self.phase == "mid-barrier" and self.rank != 0:
            return self.step
        return self.step - 1


class ObsSpec:
    """Scripted cluster-observability worker (``DRILL_OBS=1``): enable
    real telemetry, publish the /metrics endpoint, record a rank-skewed
    synthetic step profile (+ optionally a genuine recompile-sentinel
    trip), then hold the endpoint open until released."""

    __slots__ = ("telemetry_dir", "step_base", "storm",
                 "sentinel_threshold", "hold_timeout", "anomalies",
                 "mem_bytes", "shed", "served", "sdc_verdicts")

    def __init__(self, telemetry_dir, step_base=0.01, storm=True,
                 sentinel_threshold=3, hold_timeout=120.0,
                 anomalies=0, mem_bytes=0, shed=0, served=0,
                 sdc_verdicts=0):
        self.telemetry_dir = telemetry_dir
        self.step_base = float(step_base)
        self.storm = bool(storm)
        self.sentinel_threshold = int(sentinel_threshold)
        self.hold_timeout = float(hold_timeout)
        self.anomalies = int(anomalies)
        # nonzero: feed a rank-scaled synthetic memory watermark
        # (mem_bytes * (1 + rank)) so the aggregator's skew/near-OOM
        # derivations are assertable
        self.mem_bytes = int(mem_bytes)
        # scripted serve admission profile: each rank books ``shed``
        # load-shed refusals and ``served`` accepted requests, so the
        # aggregator's fleet shed ratio is exactly
        # shed / (shed + served) and its shed-storm alarm assertable
        self.shed = int(shed)
        self.served = int(served)
        # scripted SDC consensus verdicts: each rank books this many
        # pt_sdc_divergence_total increments (fingering a fixed peer,
        # halt disarmed), arming the aggregator's cluster SDC alarm
        self.sdc_verdicts = int(sdc_verdicts)


class TraceSpec:
    """Scripted step-tracing worker (``DRILL_TRACE=1``): enable the
    real tracer, record a deterministic staggered compute/collective
    step profile (synthetic timestamps, no sleeping), export a
    per-rank Chrome trace into ``trace_dir`` and — when ``flight_dir``
    is set — a flight dump, then write a report JSON with the tracer
    snapshot."""

    __slots__ = ("trace_dir", "flight_dir", "step_ms")

    def __init__(self, trace_dir, flight_dir=None, step_ms=10.0):
        self.trace_dir = trace_dir
        self.flight_dir = flight_dir
        self.step_ms = float(step_ms)


class NumericsSpec:
    """Scripted NaN-injection worker (``DRILL_NUMERICS=1``): train a
    real captured MLP with the numerics monitor armed, poison one
    input element with NaN on ``poison_rank`` at ``poison_step``, and
    write a per-rank detection report into ``out_dir``.  ``halt``
    arms ``PT_NUMERICS_HALT`` semantics (worker exits
    ``EXIT_NUMERICS_HALT`` after the sentinel raises)."""

    __slots__ = ("out_dir", "poison_step", "poison_rank", "cadence",
                 "halt")

    def __init__(self, out_dir, poison_step=5, poison_rank=1,
                 cadence=4, halt=False):
        self.out_dir = out_dir
        self.poison_step = int(poison_step)
        self.poison_rank = int(poison_rank)
        self.cadence = int(cadence)
        self.halt = bool(halt)


class OomSpec:
    """Scripted allocator-exhaustion worker (``DRILL_OOM=1``): train a
    real captured MLP with the memory monitor armed, inject a
    ``RESOURCE_EXHAUSTED`` into ``oom_rank``'s compiled entry at
    ``oom_step``, and write the postmortem evidence (report + metrics
    exposition) into ``out_dir``.  ``mem_bytes`` scales each rank's
    synthetic watermark feed (rank r exports ``mem_bytes * (1 + r)``)."""

    __slots__ = ("out_dir", "oom_step", "oom_rank", "mem_bytes")

    def __init__(self, out_dir, oom_step=5, oom_rank=1,
                 mem_bytes=1_000_000):
        self.out_dir = out_dir
        self.oom_step = int(oom_step)
        self.oom_rank = int(oom_rank)
        self.mem_bytes = int(mem_bytes)


class SdcSpec:
    """Scripted silent-data-corruption worker (``DRILL_SDC=1``): every
    rank trains the SAME captured MLP from the SAME seed with the SDC
    sentry armed and its fingerprint exchange wired to the drill
    store; ``poison_rank`` (-1 = nobody) flips one mantissa bit of its
    first captured parameter at ``poison_step``."""

    __slots__ = ("out_dir", "poison_step", "poison_rank", "cadence",
                 "bit", "exchange_timeout")

    def __init__(self, out_dir, poison_step=5, poison_rank=1,
                 cadence=4, bit=3, exchange_timeout=30.0):
        self.out_dir = out_dir
        self.poison_step = int(poison_step)
        self.poison_rank = int(poison_rank)
        self.cadence = int(cadence)
        self.bit = int(bit)
        self.exchange_timeout = float(exchange_timeout)


class StoreKillSpec:
    """Scripted STORE-MASTER kill: every rank rendezvouses at ``phase``
    of step ``step``'s save (``pre-save`` | ``mid-barrier``), and the
    runner SIGKILLs the master inside that window.  ``timeout`` bounds
    each rank's wait for the post-respawn release key."""

    __slots__ = ("phase", "step", "timeout")

    def __init__(self, phase, step, timeout=60.0):
        if phase not in ("pre-save", "mid-barrier"):
            raise ValueError(f"unknown storekill phase {phase!r}")
        self.phase = phase
        self.step = int(step)
        self.timeout = float(timeout)


def reap_all():
    """SIGKILL + wait every worker this module spawned and is still
    tracking — the no-leaked-children guarantee for test harnesses."""
    for p in list(_LIVE):
        if p.poll() is None:
            try:
                p.kill()
            except OSError:
                pass
        try:
            p.wait(timeout=10)
        except Exception:
            logger.warning("drill reaper: pid %s did not exit", p.pid)
        _LIVE.discard(p)


def spawn_worker(rank, world, *, root, port=0, total_steps, run_id,
                 barrier_timeout, kill=None, elastic=True,
                 orphan_age=None, log_path=None, endpoint_file=None,
                 store_deadline=None, storekill=None, obs=None,
                 trace=None, numerics=None, oom=None, sdc=None,
                 restore_integrity=None, flight_dir=None,
                 fail=None, data_shard=None):
    """Launch one drill worker subprocess; returns its Popen (also
    registered for :func:`reap_all`).

    ``endpoint_file`` switches the worker to a ResilientStore resolved
    through that file (the store-failover mode; ``port`` is then
    ignored); ``storekill`` (a :class:`StoreKillSpec`) arms the
    master-kill rendezvous in every rank; ``obs`` (an
    :class:`ObsSpec`) switches the worker to the cluster-observability
    mode (requires ``endpoint_file``; ``total_steps`` becomes the
    synthetic step count); ``trace`` (a :class:`TraceSpec`) switches
    to the storeless step-tracing mode; ``numerics`` (a
    :class:`NumericsSpec`) switches to the storeless NaN-injection
    mode; ``oom`` (an :class:`OomSpec`) switches to the storeless
    OOM-postmortem mode; ``sdc`` (an :class:`SdcSpec`) switches to the
    silent-data-corruption consensus mode (needs a store for the
    fingerprint exchange: ``port`` or ``endpoint_file``);
    ``restore_integrity`` sets the checkpoint-mode resume integrity
    level ("full" also recomputes per-leaf content digests; a refusal
    exits ``EXIT_SDC``); ``flight_dir`` arms the flight recorder
    (``PT_FLIGHT_RECORDER``); ``fail=(step, exit_code)`` scripts a
    deterministic crash at the top of ``step`` (the supervisor drill's
    crash-loop: a resumed worker reaches the same step and dies again);
    ``data_shard`` names the worker's data shard (``PT_DATA_SHARD``)
    for crash/shard correlation diagnostics.
    """
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("DRILL_")}
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PT_RUN_ID": run_id,
        "PT_PROCESS_INDEX": str(rank),
        "DRILL_RANK": str(rank),
        "DRILL_WORLD": str(world),
        "DRILL_CKPT": root,
        "DRILL_STORE_PORT": str(port),
        "DRILL_TOTAL_STEPS": str(total_steps),
        "DRILL_RUN_ID": run_id,
        "DRILL_BARRIER_TIMEOUT": str(barrier_timeout),
        "DRILL_ELASTIC": "1" if elastic else "0",
    })
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    if orphan_age is not None:
        env["DRILL_ORPHAN_AGE"] = str(orphan_age)
    if kill is not None:
        env["DRILL_KILL_PHASE"] = kill.phase
        env["DRILL_KILL_STEP"] = str(kill.step)
        env["DRILL_KILL_RANK"] = str(kill.rank)
    if endpoint_file is not None:
        env["DRILL_ENDPOINT_FILE"] = endpoint_file
    if store_deadline is not None:
        env["DRILL_STORE_DEADLINE"] = str(store_deadline)
    if storekill is not None:
        env["DRILL_STOREKILL_PHASE"] = storekill.phase
        env["DRILL_STOREKILL_STEP"] = str(storekill.step)
        env["DRILL_STOREKILL_TIMEOUT"] = str(storekill.timeout)
    if obs is not None:
        if endpoint_file is None:
            raise ValueError("ObsSpec workers publish endpoints via "
                             "the store: endpoint_file is required")
        env["DRILL_OBS"] = "1"
        env["DRILL_TELEMETRY_DIR"] = obs.telemetry_dir
        env["DRILL_OBS_STEP_BASE"] = str(obs.step_base)
        env["DRILL_OBS_STORM"] = "1" if obs.storm else "0"
        env["DRILL_OBS_TIMEOUT"] = str(obs.hold_timeout)
        env["PT_RECOMPILE_THRESHOLD"] = str(obs.sentinel_threshold)
        if obs.anomalies:
            env["DRILL_OBS_ANOMALIES"] = str(obs.anomalies)
        if obs.mem_bytes:
            env["DRILL_OBS_MEM_BYTES"] = str(obs.mem_bytes)
        if obs.shed:
            env["DRILL_OBS_SHED"] = str(obs.shed)
        if obs.served:
            env["DRILL_OBS_SERVED"] = str(obs.served)
        if obs.sdc_verdicts:
            env["DRILL_OBS_SDC"] = str(obs.sdc_verdicts)
    if trace is not None:
        env["DRILL_TRACE"] = "1"
        env["DRILL_TRACE_DIR"] = trace.trace_dir
        env["DRILL_TRACE_STEP_MS"] = str(trace.step_ms)
        if trace.flight_dir:
            env["PT_FLIGHT_RECORDER"] = trace.flight_dir
    if numerics is not None:
        env["DRILL_NUMERICS"] = "1"
        env["DRILL_NUMERICS_DIR"] = numerics.out_dir
        env["DRILL_POISON_STEP"] = str(numerics.poison_step)
        env["DRILL_POISON_RANK"] = str(numerics.poison_rank)
        env["DRILL_NUMERICS_CADENCE"] = str(numerics.cadence)
        env["DRILL_NUMERICS_HALT"] = "1" if numerics.halt else "0"
    if oom is not None:
        env["DRILL_OOM"] = "1"
        env["DRILL_OOM_DIR"] = oom.out_dir
        env["DRILL_OOM_STEP"] = str(oom.oom_step)
        env["DRILL_OOM_RANK"] = str(oom.oom_rank)
        env["DRILL_OOM_MEM_BYTES"] = str(oom.mem_bytes)
    if sdc is not None:
        env["DRILL_SDC"] = "1"
        env["DRILL_SDC_DIR"] = sdc.out_dir
        env["DRILL_POISON_STEP"] = str(sdc.poison_step)
        env["DRILL_POISON_RANK"] = str(sdc.poison_rank)
        env["DRILL_SDC_CADENCE"] = str(sdc.cadence)
        env["DRILL_SDC_BIT"] = str(sdc.bit)
        env["DRILL_SDC_EXCHANGE_TIMEOUT"] = str(sdc.exchange_timeout)
    if restore_integrity is not None:
        env["DRILL_RESTORE_INTEGRITY"] = str(restore_integrity)
    if flight_dir is not None:
        env["PT_FLIGHT_RECORDER"] = flight_dir
    if fail is not None:
        env["DRILL_FAIL_STEP"] = str(fail[0])
        env["DRILL_FAIL_EXIT"] = str(fail[1])
    if data_shard is not None:
        env["PT_DATA_SHARD"] = str(data_shard)
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.drill.worker"]
    if log_path:
        with open(log_path, "ab") as out:
            p = subprocess.Popen(cmd, env=env, stdout=out,
                                 stderr=subprocess.STDOUT)
    else:
        p = subprocess.Popen(cmd, env=env,
                             stdout=subprocess.DEVNULL,
                             stderr=subprocess.DEVNULL)
    _LIVE.add(p)
    return p


def spawn_store_master(*, endpoint_file, wal_path=None, port=0,
                       log_path=None, spawn_timeout=30.0):
    """Launch (or respawn) a store-master subprocess and wait for it to
    publish its endpoint.  Returns ``(Popen, (host, port))``; the
    process is registered for :func:`reap_all` like any drill child.

    The endpoint file is unlinked FIRST so a client re-resolving during
    the respawn can never read the dead master's address as current.
    """
    try:
        os.unlink(endpoint_file)
    except FileNotFoundError:
        pass
    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "store_master.py")
    cmd = [sys.executable, script, "--endpoint-file", endpoint_file,
           "--port", str(port)]
    if wal_path:
        cmd += ["--wal", wal_path]
    if log_path:
        with open(log_path, "ab") as out:
            p = subprocess.Popen(cmd, stdout=out,
                                 stderr=subprocess.STDOUT)
    else:
        p = subprocess.Popen(cmd, stdout=subprocess.DEVNULL,
                             stderr=subprocess.DEVNULL)
    _LIVE.add(p)

    def _published():
        if p.poll() is not None:
            raise DrillFailure(
                f"store master died during startup (rc {p.poll()})")
        return read_endpoint_file(endpoint_file)

    try:
        ep = wait_until(_published, spawn_timeout,
                        desc="store master to publish its endpoint")
    except TimeoutError as e:
        raise DrillFailure(f"store master never came up: {e}") from e
    logger.info("store master pid %d serving at %s:%d (wal=%s)",
                p.pid, ep[0], ep[1], wal_path)
    return p, ep


def spawn_aggregator(*, endpoint_file, run_id, port_file,
                     interval=0.25, stale_after=2.0, storm_threshold=1,
                     anomaly_threshold=10, sdc_threshold=None,
                     mem_threshold=0, shed_threshold=0.0,
                     scrape_timeout=2.0, store_deadline=10.0,
                     log_path=None, spawn_timeout=60.0):
    """Launch the cluster aggregator as a REAL subprocess
    (``python -m paddle_tpu.observability.aggregator``) discovering
    rank endpoints through the store, and wait for it to publish its
    own bound address into ``port_file``.  Returns
    ``(Popen, (host, port))``; registered for :func:`reap_all`."""
    try:
        os.unlink(port_file)
    except FileNotFoundError:
        pass
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [sys.executable, "-m", "paddle_tpu.observability.aggregator",
           "--run-id", run_id,
           "--store-endpoint-file", endpoint_file,
           "--store-deadline", str(store_deadline),
           "--port-file", port_file,
           "--interval", str(interval),
           "--stale-after", str(stale_after),
           "--scrape-timeout", str(scrape_timeout),
           "--storm-threshold", str(storm_threshold),
           "--anomaly-threshold", str(anomaly_threshold)]
    if sdc_threshold is not None:
        cmd += ["--sdc-threshold", str(sdc_threshold)]
    if mem_threshold:
        cmd += ["--mem-threshold", str(mem_threshold)]
    if shed_threshold:
        cmd += ["--shed-threshold", str(shed_threshold)]
    if log_path:
        with open(log_path, "ab") as out:
            p = subprocess.Popen(cmd, env=env, stdout=out,
                                 stderr=subprocess.STDOUT)
    else:
        p = subprocess.Popen(cmd, env=env, stdout=subprocess.DEVNULL,
                             stderr=subprocess.DEVNULL)
    _LIVE.add(p)

    def _published():
        if p.poll() is not None:
            raise DrillFailure(
                f"aggregator died during startup (rc {p.poll()})")
        return read_endpoint_file(port_file)

    try:
        ep = wait_until(_published, spawn_timeout,
                        desc="aggregator to publish its endpoint")
    except TimeoutError as e:
        raise DrillFailure(f"aggregator never came up: {e}") from e
    logger.info("aggregator pid %d serving at %s:%d", p.pid, ep[0],
                ep[1])
    return p, ep


def _http_get(url, timeout=5.0):
    """Bounded GET returning (status, body-text); a 503 (/healthz with
    the alarm up) still returns its body."""
    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return resp.status, resp.read().decode("utf-8")
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode("utf-8")


def _sample_value(families, name, **labels):
    """First sample of ``name`` whose labels are a superset of
    ``labels`` (None when absent) — tolerant of extra labels like
    run_id so drill assertions only pin what they mean to pin."""
    fam = families.get(name)
    if fam is None:
        for f in families.values():
            for sname, lbls, value in f["samples"]:
                if sname == name and all(
                        lbls.get(k) == v for k, v in labels.items()):
                    return value
        return None
    for sname, lbls, value in fam["samples"]:
        if sname == name and all(lbls.get(k) == v
                                 for k, v in labels.items()):
            return value
    return None


def _wait_fleet(procs, timeout):
    """Block until every proc exits; returns their return codes.  On
    timeout the fleet is reaped and the drill fails."""
    try:
        wait_until(lambda: all(p.poll() is not None for p in procs),
                   timeout, desc=f"drill fleet of {len(procs)} to exit")
    except TimeoutError as e:
        reap_all()
        raise DrillFailure(f"drill generation hung: {e}") from e
    rcs = []
    for p in procs:
        # poll() above proved exit; the wait just reaps, so a short
        # bound is safe
        rcs.append(p.wait(timeout=5.0))
        _LIVE.discard(p)
    return rcs


def _latest_step(root):
    # read-only probe (orphan_age=None: the probe must not janitor)
    return CheckpointManager(root, keep_last_n=None,
                             orphan_age=None).latest_step()


def _verify_bit_for_bit(root, step):
    """CRC-verify step's checkpoint, then compare every leaf byte-wise
    against the replayed oracle."""
    d = os.path.join(root, f"step_{int(step):08d}")
    verify_checkpoint(d, integrity="full")
    w0, b0 = init_state()
    we, be = advance(w0, b0, int(step))
    w = read_leaf(d, "w", integrity="off")
    b = read_leaf(d, "bias", integrity="off")
    if w.tobytes() != we.tobytes() or b.tobytes() != be.tobytes():
        raise DrillFailure(
            f"step {step} restored state is not bit-identical to the "
            f"oracle replay (max |w-we| = {abs(w - we).max()})")


def poison_shard(ckpt_dir, rel_path=None, bit=0, offset=None):
    """Flip one payload bit in a committed shard file AND re-seal the
    COMMIT manifest's crc32 to match the corrupted bytes.

    This models silent corruption that happened between device memory
    and serialization: the file-level CRC was computed over an
    already-corrupt buffer, so manifest verification passes and only
    the per-leaf *content* digest (recorded from the live array at
    save) can refuse the restore.  Returns the relative path of the
    poisoned file.  Canonical here — the restore-refusal leg of
    :func:`run_sdc_drill` is the primary consumer — and re-exported by
    tests/fault_injection.py for the checkpoint-digest unit tests.

    ``offset`` is the byte offset inside the .npy payload to hit
    (defaults to the last byte — element data, safely past the
    header); ``bit`` selects the bit within that byte.
    """
    import zlib

    files = []
    data_root = os.path.join(ckpt_dir, "data")
    for droot, _dirs, fnames in os.walk(data_root):
        for fn in fnames:
            files.append(os.path.relpath(os.path.join(droot, fn),
                                         ckpt_dir))
    files.sort()
    if not files:
        raise ValueError(f"no shard files under {ckpt_dir}")
    rel = rel_path or files[0]
    path = os.path.join(ckpt_dir, rel)
    with open(path, "r+b") as f:
        if offset is None:
            f.seek(-1, os.SEEK_END)
        else:
            f.seek(offset)
        pos = f.tell()
        b = f.read(1)
        if not b:
            raise ValueError(f"offset {offset} out of range for {path}")
        f.seek(pos)
        f.write(bytes([b[0] ^ (1 << (int(bit) % 8))]))
    with open(path, "rb") as f:
        data = f.read()
    crc = zlib.crc32(data) & 0xFFFFFFFF
    patched = False
    for name in os.listdir(ckpt_dir):
        if not name.startswith("COMMIT."):
            continue
        marker_path = os.path.join(ckpt_dir, name)
        with open(marker_path) as f:
            marker = json.load(f)
        entry = marker.get("files", {}).get(rel)
        if entry is None:
            continue
        entry["crc32"] = crc
        entry["size"] = len(data)
        with open(marker_path, "w") as f:
            json.dump(marker, f)
        patched = True
    if not patched:
        raise ValueError(f"{rel} is not covered by any COMMIT manifest")
    return rel


def run_drill(root, generations, total_steps, *, barrier_timeout=6.0,
              gen_timeout=120.0, orphan_age=None, log_dir=None,
              flight_dir=None):
    """Run a multi-generation fault drill.

    ``generations``: list of ``(world_size, KillSpec-or-None)``.  Each
    generation is a full fleet launch sharing the checkpoint ``root``;
    a generation with a kill is expected to end with the victim
    SIGKILLed (rc ``-9``) and every survivor exiting
    ``EXIT_SAVE_FAILED`` after its commit barrier names the dead rank
    — after which the newest committed step must equal the kill's
    :meth:`KillSpec.expected_commit` and verify bit-for-bit.  The last
    generation should have no kill: it must run to ``total_steps`` with
    every rank exiting 0, resuming elastically when its world size
    differs from the writer's.

    ``flight_dir`` arms the flight recorder in every worker: a killed
    generation then additionally asserts the SIGKILLed victim left a
    parseable ``flight-<run_id>-<rank>.json`` behind — the recorder's
    no-handlers-run acceptance (arm-time dump + watchdog refresh).

    Returns a per-generation report (worlds, return codes, newest
    committed step, run_id) for further assertions.
    """
    master = TCPStore("127.0.0.1", 0, is_master=True)
    report = []
    try:
        for g, (world, kill) in enumerate(generations):
            run_id = f"g{g}-{uuid.uuid4().hex[:6]}"
            procs = [
                spawn_worker(
                    r, world, root=root, port=master.port,
                    total_steps=total_steps, run_id=run_id,
                    barrier_timeout=barrier_timeout, kill=kill,
                    orphan_age=orphan_age, flight_dir=flight_dir,
                    log_path=(os.path.join(log_dir, f"gen{g}_rank{r}.log")
                              if log_dir else None))
                for r in range(world)
            ]
            rcs = _wait_fleet(procs, gen_timeout)
            latest = _latest_step(root)
            gen_report = {"world": world, "rcs": rcs, "latest": latest,
                          "run_id": run_id}
            report.append(gen_report)
            if kill is None:
                if any(rc != 0 for rc in rcs):
                    raise DrillFailure(
                        f"generation {g} (no kill) exit codes {rcs}")
                if latest != total_steps:
                    raise DrillFailure(
                        f"generation {g} finished but newest committed "
                        f"step is {latest}, wanted {total_steps}")
            else:
                if rcs[kill.rank] != -signal.SIGKILL:
                    raise DrillFailure(
                        f"generation {g}: victim rank {kill.rank} "
                        f"exited {rcs[kill.rank]}, expected SIGKILL")
                survivors = [rc for r, rc in enumerate(rcs)
                             if r != kill.rank]
                if any(rc != EXIT_SAVE_FAILED for rc in survivors):
                    raise DrillFailure(
                        f"generation {g}: survivor exit codes "
                        f"{survivors}, expected all {EXIT_SAVE_FAILED}")
                want = kill.expected_commit()
                if (latest or 0) != want:
                    raise DrillFailure(
                        f"generation {g}: newest committed step is "
                        f"{latest} after a {kill.phase} kill at step "
                        f"{kill.step}, expected {want}")
                if flight_dir is not None:
                    # SIGKILL runs no handlers: the dump on disk is the
                    # arm-time/watchdog one, and it must be whole
                    fpath = os.path.join(
                        flight_dir,
                        f"flight-{run_id}-{kill.rank}.json")
                    try:
                        with open(fpath, "r", encoding="utf-8") as f:
                            flight = json.load(f)
                    except (OSError, ValueError) as e:
                        raise DrillFailure(
                            f"generation {g}: SIGKILLed rank "
                            f"{kill.rank} left no parseable flight "
                            f"dump at {fpath}: {e}") from e
                    if flight.get("process_index") != kill.rank or \
                            flight.get("run_id") != run_id:
                        raise DrillFailure(
                            f"generation {g}: flight dump identity "
                            f"{flight.get('run_id')!r}/"
                            f"{flight.get('process_index')!r} does not "
                            f"match victim {run_id!r}/{kill.rank}")
                    gen_report["flight"] = fpath
            if latest is not None:
                _verify_bit_for_bit(root, latest)
    finally:
        reap_all()
        master.close()
    return report


def run_store_kill_drill(root, *, world=2, total_steps=5, kill_step=3,
                         phase="mid-barrier", wal=True, respawn=True,
                         respawn_with_wal=True, barrier_timeout=10.0,
                         store_deadline=8.0, storekill_timeout=45.0,
                         gen_timeout=120.0, log_dir=None,
                         relaunch_extra_steps=0):
    """SIGKILL the TCPStore MASTER mid-save and prove the fleet either
    recovers (durable master respawned from its WAL) or degrades
    cleanly (``StoreUnavailableError`` → every rank exits
    ``EXIT_STORE_LOST`` within its deadline — never a hang).

    Deterministic kill window: every rank rendezvouses at ``phase`` of
    step ``kill_step``'s save (``ready`` keys through the doomed
    master, blocking on a ``go`` key), the runner SIGKILLs the master
    only once ALL ranks are provably in-flight, then — when ``respawn``
    — relaunches it (from the WAL, or amnesiac when
    ``respawn_with_wal=False``) and releases ``go`` through the new
    master.  Recovery asserts every rank finishes to ``total_steps``
    with the respawned master sealing the barrier from REPLAYED
    arrivals, bit-for-bit verified; ``relaunch_extra_steps > 0`` then
    runs a fresh no-kill generation against the same master to prove a
    relaunch resumes bit-for-bit too.

    Returns a report dict (``rcs``, ``latest``, ``generations``
    observed from the release client, endpoints, recovery mode).
    """
    endpoint_file = os.path.join(root, "store.endpoint")
    wal_path = os.path.join(root, "store.wal") if wal else None
    expect_recovery = respawn and respawn_with_wal and wal

    def _log(name):
        return os.path.join(log_dir, name) if log_dir else None

    master, ep0 = spawn_store_master(
        endpoint_file=endpoint_file, wal_path=wal_path,
        log_path=_log("store_master_0.log"))
    report = {"endpoints": [ep0], "recovered": expect_recovery}
    try:
        run_id = f"storekill-{uuid.uuid4().hex[:6]}"
        sk = StoreKillSpec(phase, kill_step, timeout=storekill_timeout)
        procs = [
            spawn_worker(
                r, world, root=root, total_steps=total_steps,
                run_id=run_id, barrier_timeout=barrier_timeout,
                endpoint_file=endpoint_file,
                store_deadline=store_deadline, storekill=sk,
                log_path=_log(f"storekill_rank{r}.log"))
            for r in range(world)
        ]

        # wait until EVERY rank is provably inside the kill window
        watch = ResilientStore(endpoint_file=endpoint_file,
                               deadline=store_deadline)
        try:
            for r in range(world):
                watch.get(f"storekill/{run_id}/ready/{r}", wait=True,
                          timeout=gen_timeout / 2)
        finally:
            watch.close()
        logger.info("all %d ranks at the storekill rendezvous; "
                    "SIGKILLing master pid %d", world, master.pid)
        master.kill()
        master.wait(timeout=30)
        _LIVE.discard(master)

        gen = None
        if respawn:
            master, ep1 = spawn_store_master(
                endpoint_file=endpoint_file,
                wal_path=wal_path if respawn_with_wal else None,
                log_path=_log("store_master_1.log"))
            report["endpoints"].append(ep1)
            # release the fleet through the NEW master (fresh client:
            # the release must work even against an amnesiac master —
            # it is the WORKERS whose fence must trip, not ours)
            release = ResilientStore(endpoint_file=endpoint_file,
                                     deadline=store_deadline)
            try:
                release.set(f"storekill/{run_id}/go", b"1")
                gen = release.generation
            finally:
                release.close()
        report["generation"] = gen

        rcs = _wait_fleet(procs, gen_timeout)
        latest = _latest_step(root)
        report.update({"rcs": rcs, "latest": latest})

        if expect_recovery:
            if any(rc != 0 for rc in rcs):
                raise DrillFailure(
                    f"store-kill recovery: exit codes {rcs}, expected "
                    f"all 0 (master respawned from WAL should have "
                    f"sealed the barrier from replayed arrivals)")
            if latest != total_steps:
                raise DrillFailure(
                    f"store-kill recovery: newest committed step is "
                    f"{latest}, wanted {total_steps}")
            if gen is None or gen < 2:
                raise DrillFailure(
                    f"respawned WAL master advertises generation {gen}, "
                    f"expected >= 2 (replay must bump it)")
        else:
            if any(rc != EXIT_STORE_LOST for rc in rcs):
                raise DrillFailure(
                    f"store-kill clean-failure: exit codes {rcs}, "
                    f"expected all {EXIT_STORE_LOST} "
                    f"(StoreUnavailableError)")
            want = kill_step - 1
            if (latest or 0) != want:
                raise DrillFailure(
                    f"store-kill clean-failure: newest committed step "
                    f"is {latest}, expected {want} (step {kill_step} "
                    f"must never have promoted)")
        if latest:
            _verify_bit_for_bit(root, latest)

        if expect_recovery and relaunch_extra_steps > 0:
            # relaunch generation: fresh fleet, same respawned master,
            # resumes from `latest` and runs further — the
            # resume-bit-for-bit half of the acceptance criterion
            run_id2 = f"storekill-relaunch-{uuid.uuid4().hex[:6]}"
            more = total_steps + relaunch_extra_steps
            procs2 = [
                spawn_worker(
                    r, world, root=root, total_steps=more,
                    run_id=run_id2, barrier_timeout=barrier_timeout,
                    endpoint_file=endpoint_file,
                    store_deadline=store_deadline,
                    log_path=_log(f"relaunch_rank{r}.log"))
                for r in range(world)
            ]
            rcs2 = _wait_fleet(procs2, gen_timeout)
            latest2 = _latest_step(root)
            report.update({"relaunch_rcs": rcs2,
                           "relaunch_latest": latest2})
            if any(rc != 0 for rc in rcs2):
                raise DrillFailure(
                    f"relaunch after store failover: exit codes {rcs2}")
            if latest2 != more:
                raise DrillFailure(
                    f"relaunch after store failover: newest step "
                    f"{latest2}, wanted {more}")
            _verify_bit_for_bit(root, latest2)
    finally:
        reap_all()
    return report


def run_scrape_drill(root, *, world=3, steps=12, step_base=0.01,
                     kill_rank=2, storm=True, anomalies=0,
                     sdc_verdicts=0,
                     mem_bytes=0, mem_threshold=0,
                     shed=0, served=0, shed_threshold=0.0,
                     restart_aggregator=False,
                     respawn_master=False, stale_after=2.0,
                     scrape_interval=0.25, store_deadline=10.0,
                     gen_timeout=120.0, log_dir=None):
    """End-to-end cluster-observability drill: ``world`` REAL worker
    processes publish their /metrics endpoints into the store, a REAL
    aggregator subprocess discovers and scrapes them, and the runner
    asserts the cluster view — summed counters, merged histogram
    buckets, a nonzero cross-rank step-time skew (each rank's synthetic
    step profile is ``step_base * (1 + rank)``), and (when ``storm``)
    the recompile-storm alarm tripping on the CROSS-RANK aggregate.

    Every obs worker also feeds a deterministic synthetic goodput
    profile (1/5 data_wait, 4/5 compute per virtual step), so the
    derived ``pt_cluster_goodput`` min/mean must both read exactly
    0.8; ``anomalies`` (per-rank scripted numerics trips) arms the
    cross-rank anomaly alarm, whose threshold is then set to
    ``world * anomalies`` so it trips exactly — and flips /healthz to
    503 even without a recompile storm.  ``sdc_verdicts`` does the
    same for the silent-data-corruption plane: each rank books that
    many scripted consensus divergence verdicts (fingering a fixed
    peer, halt disarmed), the aggregator's SDC threshold is set to
    ``world * sdc_verdicts`` so ``pt_cluster_sdc_alarm`` trips
    exactly, and /healthz must answer 503 on the corruption signal
    alone.  ``mem_bytes`` feeds each rank
    a synthetic allocator watermark (rank r exports
    ``mem_bytes * (1 + r)``) so the cluster memory-skew gauge must
    read exactly ``mem_bytes * (world - 1)``; with ``mem_threshold``
    at or below ``mem_bytes * world`` the near-OOM alarm must trip and
    flip /healthz to 503 on the memory signal alone.  ``shed`` /
    ``served`` script a per-rank serve admission profile (each rank
    books that many ``pt_serve_shed_total`` refusals and accepted
    requests), pinning the aggregator's fleet shed ratio to exactly
    ``shed / (shed + served)``; with ``shed_threshold`` at or below
    that ratio the shed-storm alarm must trip and flip /healthz to
    503 on the load-shedding signal alone.

    ``kill_rank`` (None to skip) is then SIGKILLed while still holding
    its endpoint open: the aggregator must mark it stale
    (``pt_rank_up 0``, ``pt_cluster_ranks_up`` down by one) within
    bounded polls — never hang.  ``restart_aggregator`` kills and
    respawns the aggregator itself mid-drill (its cluster view must
    reconverge from store discovery alone); ``respawn_master``
    SIGKILLs the WAL-backed store master and proves discovery survives
    the failover.  Finally the fleet is released, exit codes checked,
    and ``python -m paddle_tpu.observability.merge`` stitches the
    per-rank telemetry JSONL into one time-ordered rank-labeled stream
    that is validated line-for-line.  Returns a report dict.
    """
    endpoint_file = os.path.join(root, "store.endpoint")
    wal_path = os.path.join(root, "store.wal")
    port_file = os.path.join(root, "aggregator.endpoint")
    telemetry_dir = os.path.join(root, "telemetry")
    os.makedirs(telemetry_dir, exist_ok=True)
    sentinel_threshold = 3
    storm_threshold = world if storm else world * 1000
    anomaly_threshold = world * anomalies if anomalies else world * 1000
    sdc_threshold = (world * sdc_verdicts if sdc_verdicts
                     else world * 1000)

    def _log(name):
        return os.path.join(log_dir, name) if log_dir else None

    master, _ep = spawn_store_master(
        endpoint_file=endpoint_file, wal_path=wal_path,
        log_path=_log("store_master.log"))
    run_id = f"obs-{uuid.uuid4().hex[:6]}"
    spec = ObsSpec(telemetry_dir=telemetry_dir, step_base=step_base,
                   storm=storm, sentinel_threshold=sentinel_threshold,
                   hold_timeout=gen_timeout, anomalies=anomalies,
                   mem_bytes=mem_bytes, shed=shed, served=served,
                   sdc_verdicts=sdc_verdicts)
    mem_alarm_expected = bool(
        mem_bytes and mem_threshold
        and mem_bytes * world >= mem_threshold)
    shed_ratio_expected = (
        shed / float(shed + served) if (shed or served) else None)
    shed_alarm_expected = bool(
        shed_threshold and shed_ratio_expected is not None
        and shed_ratio_expected >= shed_threshold)
    report = {"run_id": run_id, "world": world, "steps": steps,
              "aggregator_restarted": False, "master_respawned": False}
    watch = None
    try:
        procs = [
            spawn_worker(
                r, world, root=root, total_steps=steps, run_id=run_id,
                barrier_timeout=gen_timeout,
                endpoint_file=endpoint_file,
                store_deadline=store_deadline, obs=spec,
                log_path=_log(f"obs_rank{r}.log"))
            for r in range(world)
        ]

        # every rank has published its endpoint, observed its steps
        # (and tripped its sentinel) before we let the aggregator judge
        watch = ResilientStore(endpoint_file=endpoint_file,
                               deadline=store_deadline)
        for r in range(world):
            watch.get(obs_ready_key(run_id, r), wait=True,
                      timeout=gen_timeout / 2)

        agg, (ahost, aport) = spawn_aggregator(
            endpoint_file=endpoint_file, run_id=run_id,
            port_file=port_file, interval=scrape_interval,
            stale_after=stale_after, storm_threshold=storm_threshold,
            anomaly_threshold=anomaly_threshold,
            sdc_threshold=sdc_threshold,
            mem_threshold=mem_threshold,
            shed_threshold=shed_threshold,
            store_deadline=store_deadline,
            log_path=_log("aggregator.log"))
        base = f"http://{ahost}:{aport}"

        from ...observability.aggregator import parse_prometheus_text

        def _cluster_families():
            """One bounded scrape of the aggregator; None while it is
            still converging or between restarts."""
            if agg.poll() is not None:
                raise DrillFailure(
                    f"aggregator exited mid-drill (rc {agg.poll()})")
            try:
                _status, body = _http_get(base + "/metrics", timeout=5.0)
            except OSError:
                return None
            try:
                return parse_prometheus_text(body)
            except ValueError as e:
                raise DrillFailure(
                    f"aggregated /metrics is not valid exposition "
                    f"format: {e}") from e

        def _converged(want_up, want_steps):
            def poll():
                fams = _cluster_families()
                if fams is None:
                    return None
                up = _sample_value(fams, "pt_cluster_ranks_up")
                total = _sample_value(fams, "pt_steps_total",
                                      mode="train")
                if up == want_up and (
                        want_steps is None or total == want_steps):
                    return fams
                return None
            return poll

        fams = wait_until(
            _converged(world, float(world * steps)), gen_timeout / 2,
            desc=f"aggregator to converge on {world} fresh ranks")

        # --- the cluster view: sums, merged buckets, skew, storms ----
        skew = _sample_value(fams, "pt_step_time_skew_seconds",
                             mode="train")
        if not skew or skew <= 0.0:
            raise DrillFailure(
                f"pt_step_time_skew_seconds is {skew!r}; rank-skewed "
                f"step profiles must yield a positive cross-rank skew")
        straggler = _sample_value(
            fams, "pt_step_time_straggler_ratio", mode="train")
        if not straggler or straggler < 1.0:
            raise DrillFailure(
                f"straggler ratio {straggler!r}, expected >= 1.0")
        hist_count = _sample_value(fams, "pt_step_time_seconds_count",
                                   mode="train")
        if hist_count != float(world * steps):
            raise DrillFailure(
                f"merged pt_step_time_seconds_count is {hist_count}, "
                f"expected {world * steps} (bucket merge lost samples)")
        storms_total = _sample_value(
            fams, "pt_cluster_recompile_storms_total")
        alarm = _sample_value(fams, "pt_cluster_recompile_storm_alarm")
        status, hbody = _http_get(base + "/healthz", timeout=5.0)
        health = json.loads(hbody)
        if storm:
            if storms_total != float(world):
                raise DrillFailure(
                    f"cluster recompile storms {storms_total}, expected "
                    f"{world} (one sentinel trip per rank)")
            if alarm != 1.0:
                raise DrillFailure(
                    f"storm alarm is {alarm}, expected 1 at cross-rank "
                    f"aggregate >= threshold {storm_threshold}")
            if status != 503 or not health.get("storm_alarm"):
                raise DrillFailure(
                    f"/healthz returned {status} storm_alarm="
                    f"{health.get('storm_alarm')}, expected 503/true")
        else:
            if alarm not in (0.0, None):
                raise DrillFailure(
                    f"storm alarm tripped ({alarm}) without a storm")
            want = 503 if (anomalies or sdc_verdicts
                           or mem_alarm_expected
                           or shed_alarm_expected) else 200
            if status != want:
                raise DrillFailure(
                    f"/healthz returned {status}, expected {want}")

        # --- derived fleet goodput: every obs worker's synthetic span
        # profile is 1/5 data_wait + 4/5 compute, so min == mean == 0.8
        gp_min = _sample_value(fams, "pt_cluster_goodput", stat="min")
        gp_mean = _sample_value(fams, "pt_cluster_goodput", stat="mean")
        for label, v in (("min", gp_min), ("mean", gp_mean)):
            if v is None or abs(v - 0.8) > 1e-6:
                raise DrillFailure(
                    f"pt_cluster_goodput{{stat={label}}} is {v!r}; the "
                    f"scripted span profile pins it to 0.8 exactly")
        hgp = health.get("cluster_goodput") or {}
        if abs(hgp.get("min", -1.0) - 0.8) > 1e-6:
            raise DrillFailure(
                f"/healthz cluster_goodput {hgp!r}, expected min 0.8")

        # --- cross-rank anomaly storm, mirroring the recompile trip --
        anomalies_total = _sample_value(
            fams, "pt_cluster_numerics_anomalies_total")
        anomaly_alarm = _sample_value(
            fams, "pt_cluster_numerics_anomaly_alarm")
        if anomalies:
            if anomalies_total != float(world * anomalies):
                raise DrillFailure(
                    f"cluster numerics anomalies {anomalies_total}, "
                    f"expected {world * anomalies}")
            if anomaly_alarm != 1.0 or not health.get("anomaly_alarm"):
                raise DrillFailure(
                    f"anomaly alarm metric={anomaly_alarm} "
                    f"healthz={health.get('anomaly_alarm')}, expected "
                    f"tripped at threshold {anomaly_threshold}")
        elif anomaly_alarm not in (0.0, None):
            raise DrillFailure(
                f"anomaly alarm tripped ({anomaly_alarm}) without "
                f"scripted anomalies")

        # --- cluster SDC verdicts + the corruption alarm -------------
        sdc_total = _sample_value(
            fams, "pt_cluster_sdc_divergences_total")
        sdc_alarm = _sample_value(fams, "pt_cluster_sdc_alarm")
        if sdc_verdicts:
            if sdc_total != float(world * sdc_verdicts):
                raise DrillFailure(
                    f"cluster SDC verdicts {sdc_total!r}, expected "
                    f"{world * sdc_verdicts} (scripted divergences "
                    f"summed across ranks)")
            if sdc_alarm != 1.0 or not health.get("sdc_alarm"):
                raise DrillFailure(
                    f"SDC alarm metric={sdc_alarm} "
                    f"healthz={health.get('sdc_alarm')}, expected "
                    f"tripped at threshold {sdc_threshold}")
        elif sdc_alarm not in (0.0, None):
            raise DrillFailure(
                f"SDC alarm tripped ({sdc_alarm}) without scripted "
                f"divergence verdicts")

        # --- fleet memory view: skew gauge + the near-OOM trip -------
        mem_skew = _sample_value(fams, "pt_cluster_memory_skew_bytes")
        mem_alarm = _sample_value(fams, "pt_cluster_memory_alarm")
        if mem_bytes:
            want_skew = float(mem_bytes * (world - 1))
            if mem_skew != want_skew:
                raise DrillFailure(
                    f"pt_cluster_memory_skew_bytes is {mem_skew!r}; "
                    f"rank-scaled watermarks pin it to {want_skew}")
            if mem_alarm != (1.0 if mem_alarm_expected else 0.0):
                raise DrillFailure(
                    f"memory alarm is {mem_alarm!r}, expected "
                    f"{mem_alarm_expected} at threshold "
                    f"{mem_threshold} with max {mem_bytes * world}")
            hmem = health.get("memory") or {}
            if hmem.get("bytes_in_use_max") != mem_bytes * world \
                    or bool(hmem.get("mem_alarm")) != mem_alarm_expected:
                raise DrillFailure(
                    f"/healthz memory block {hmem!r} disagrees with "
                    f"the scripted watermarks (max "
                    f"{mem_bytes * world}, alarm {mem_alarm_expected})")
        elif mem_alarm not in (0.0, None):
            raise DrillFailure(
                f"memory alarm tripped ({mem_alarm}) without scripted "
                f"watermarks")

        # --- fleet load-shedding view: shed ratio + shed-storm trip --
        shed_total = _sample_value(fams, "pt_cluster_serve_shed_total")
        shed_ratio = _sample_value(fams, "pt_cluster_serve_shed_ratio")
        shed_alarm = _sample_value(fams, "pt_cluster_serve_shed_alarm")
        if shed or served:
            if shed_total != float(world * shed):
                raise DrillFailure(
                    f"pt_cluster_serve_shed_total is {shed_total!r}, "
                    f"expected {world * shed} (scripted sheds summed "
                    f"across ranks)")
            if shed_ratio is None \
                    or abs(shed_ratio - shed_ratio_expected) > 1e-6:
                raise DrillFailure(
                    f"pt_cluster_serve_shed_ratio is {shed_ratio!r}; "
                    f"the scripted admission profile pins it to "
                    f"{shed_ratio_expected}")
            if shed_alarm != (1.0 if shed_alarm_expected else 0.0):
                raise DrillFailure(
                    f"shed-storm alarm is {shed_alarm!r}, expected "
                    f"{shed_alarm_expected} at threshold "
                    f"{shed_threshold} with ratio {shed_ratio_expected}")
            hserve = health.get("serve") or {}
            if hserve.get("shed_total") != world * shed \
                    or bool(hserve.get("shed_alarm")) \
                    != shed_alarm_expected:
                raise DrillFailure(
                    f"/healthz serve block {hserve!r} disagrees with "
                    f"the scripted shed profile (total {world * shed},"
                    f" alarm {shed_alarm_expected})")
        elif shed_alarm not in (0.0, None):
            raise DrillFailure(
                f"shed-storm alarm tripped ({shed_alarm}) without "
                f"scripted sheds")
        report.update({
            "skew_seconds": skew, "straggler_ratio": straggler,
            "merged_steps": hist_count, "storms_total": storms_total,
            "storm_alarm": alarm, "healthz": health,
            "cluster_goodput": {"min": gp_min, "mean": gp_mean},
            "anomalies_total": anomalies_total,
            "anomaly_alarm": anomaly_alarm,
            "sdc_divergences_total": sdc_total,
            "sdc_alarm": sdc_alarm,
            "memory_skew_bytes": mem_skew,
            "memory_alarm": mem_alarm,
            "shed_total": shed_total,
            "shed_ratio": shed_ratio,
            "shed_alarm": shed_alarm,
        })

        if respawn_master:
            # store failover: the aggregator's discovery client must
            # ride the endpoint-file re-resolve onto the new master,
            # whose WAL replay still holds every published endpoint
            watch.close()
            watch = None
            master.kill()
            master.wait(timeout=30)
            _LIVE.discard(master)
            master, _ep = spawn_store_master(
                endpoint_file=endpoint_file, wal_path=wal_path,
                log_path=_log("store_master_respawn.log"))
            watch = ResilientStore(endpoint_file=endpoint_file,
                                   deadline=store_deadline)
            # prove the replayed master bumped its generation
            watch.get(obs_ready_key(run_id, 0), wait=False)
            gen = watch.generation
            if gen is None or gen < 2:
                raise DrillFailure(
                    f"respawned store master advertises generation "
                    f"{gen}, expected >= 2")
            report["store_generation"] = gen
            wait_until(
                _converged(world, float(world * steps)), gen_timeout / 2,
                desc="aggregator to reconverge after master respawn")
            report["master_respawned"] = True

        if kill_rank is not None:
            # a rank goes silent mid-run: the aggregator must mark it
            # stale within bounded scrapes — each poll here is itself
            # bounded, so a hang in the aggregator fails loudly
            procs[kill_rank].kill()

            def _stale():
                fams = _cluster_families()
                if fams is None:
                    return None
                dead = _sample_value(fams, "pt_rank_up",
                                     process_index=str(kill_rank))
                up = _sample_value(fams, "pt_cluster_ranks_up")
                if dead == 0.0 and up == float(world - 1):
                    return fams
                return None

            wait_until(
                _stale, gen_timeout / 4,
                desc=f"aggregator to mark killed rank {kill_rank} "
                     f"stale")
            report["stale_after_kill"] = True

        if restart_aggregator:
            # the aggregator itself dies and respawns: its cluster view
            # must reconverge from store discovery alone
            agg.kill()
            agg.wait(timeout=30)
            _LIVE.discard(agg)
            agg, (ahost, aport) = spawn_aggregator(
                endpoint_file=endpoint_file, run_id=run_id,
                port_file=port_file, interval=scrape_interval,
                stale_after=stale_after,
                storm_threshold=storm_threshold,
                anomaly_threshold=anomaly_threshold,
                sdc_threshold=sdc_threshold,
                store_deadline=store_deadline,
                log_path=_log("aggregator_restart.log"))
            base = f"http://{ahost}:{aport}"
            live = world - (0 if kill_rank is None else 1)
            live_steps = float(live * steps)
            wait_until(
                _converged(live, live_steps), gen_timeout / 2,
                desc="respawned aggregator to reconverge")
            report["aggregator_restarted"] = True

        # release the fleet and collect exit codes
        watch.set(obs_release_key(run_id), b"1")
        rcs = _wait_fleet(procs, gen_timeout)
        report["rcs"] = rcs
        for r, rc in enumerate(rcs):
            if kill_rank is not None and r == kill_rank:
                if rc != -signal.SIGKILL:
                    raise DrillFailure(
                        f"killed rank {r} exited {rc}, expected SIGKILL")
            elif rc != 0:
                raise DrillFailure(
                    f"obs rank {r} exited {rc}, expected 0")

        # --- merge CLI: one time-ordered rank-labeled stream ---------
        merged_path = os.path.join(root, "merged.jsonl")
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH",
                                                         "")
        cli = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.observability.merge",
             telemetry_dir, "--output", merged_path],
            env=env, capture_output=True, text=True, timeout=60)
        if cli.returncode != 0:
            raise DrillFailure(
                f"merge CLI exited {cli.returncode}: {cli.stderr}")
        expected_lines = 0
        for name in os.listdir(telemetry_dir):
            if name.endswith(".jsonl") or name.endswith(".jsonl.1"):
                with open(os.path.join(telemetry_dir, name)) as f:
                    expected_lines += sum(1 for ln in f if ln.strip())
        ranks_seen, run_ids, last_ts, merged_lines = set(), set(), "", 0
        with open(merged_path) as f:
            for line in f:
                if not line.strip():
                    continue
                merged_lines += 1
                rec = json.loads(line)
                ranks_seen.add(rec.get("process_index"))
                run_ids.add(rec.get("run_id"))
                ts = rec.get("ts") or ""
                if ts < last_ts:
                    raise DrillFailure(
                        f"merged stream is not time-ordered: {ts!r} "
                        f"after {last_ts!r}")
                last_ts = ts
        if merged_lines != expected_lines:
            raise DrillFailure(
                f"merge CLI wrote {merged_lines} records from "
                f"{expected_lines} input lines")
        if ranks_seen != set(range(world)):
            raise DrillFailure(
                f"merged stream labels ranks {sorted(ranks_seen)}, "
                f"expected 0..{world - 1}")
        if run_ids != {run_id}:
            raise DrillFailure(
                f"merged stream run_ids {run_ids}, expected "
                f"{{{run_id!r}}}")
        report.update({"merge_lines": merged_lines,
                       "expected_lines": expected_lines})
    finally:
        if watch is not None:
            watch.close()
        reap_all()
    return report


def run_trace_drill(root, *, world=2, steps=6, step_ms=10.0,
                    gen_timeout=60.0, log_dir=None):
    """Multi-process step-tracing drill: ``world`` REAL worker
    processes each enable the tracer, record a deterministic staggered
    compute/collective step profile, and export per-rank Chrome traces
    plus flight dumps; the runner then stitches the traces with the
    REAL merge CLI (``python -m paddle_tpu.observability.merge
    --trace``) and asserts ONE schema-valid cluster timeline — every
    rank present as a pid with its process_name metadata, "X" events
    complete and time-ordered — and that each rank's measured
    compute↔collective overlap fraction is strictly positive (the
    scripted stagger makes the analytic value 0.6).  Storeless: no
    TCPStore master, no checkpoints.  Returns a report dict."""
    trace_dir = os.path.join(root, "traces")
    flight_dir = os.path.join(root, "flight")
    os.makedirs(trace_dir, exist_ok=True)
    run_id = f"trace-{uuid.uuid4().hex[:6]}"
    spec = TraceSpec(trace_dir=trace_dir, flight_dir=flight_dir,
                     step_ms=step_ms)
    report = {"run_id": run_id, "world": world, "steps": steps}
    try:
        procs = [
            spawn_worker(
                r, world, root=root, total_steps=steps, run_id=run_id,
                barrier_timeout=gen_timeout, trace=spec,
                log_path=(os.path.join(log_dir, f"trace_rank{r}.log")
                          if log_dir else None))
            for r in range(world)
        ]
        rcs = _wait_fleet(procs, gen_timeout)
        report["rcs"] = rcs
        if any(rc != 0 for rc in rcs):
            raise DrillFailure(f"trace drill exit codes {rcs}, "
                               f"expected all 0")

        # --- per-rank artifacts: report, chrome export, flight dump --
        overlaps = []
        for r in range(world):
            rep_path = trace_report_path(trace_dir, r)
            try:
                with open(rep_path, "r", encoding="utf-8") as f:
                    snap = json.load(f)
            except (OSError, ValueError) as e:
                raise DrillFailure(
                    f"rank {r} wrote no parseable trace report at "
                    f"{rep_path}: {e}") from e
            ov = snap.get("overlap_fraction")
            if not ov or ov <= 0.0:
                raise DrillFailure(
                    f"rank {r} measured overlap fraction {ov!r}; the "
                    f"staggered collectives must yield > 0")
            overlaps.append(ov)
            if not snap.get("phase_ms"):
                raise DrillFailure(
                    f"rank {r} report has no phase percentiles")
            tpath = os.path.join(trace_dir,
                                 f"trace-{run_id}-{r}.json")
            if not os.path.exists(tpath):
                raise DrillFailure(
                    f"rank {r} Chrome export missing at {tpath}")
            fpath = os.path.join(flight_dir,
                                 f"flight-{run_id}-{r}.json")
            try:
                with open(fpath, "r", encoding="utf-8") as f:
                    flight = json.load(f)
            except (OSError, ValueError) as e:
                raise DrillFailure(
                    f"rank {r} flight dump unreadable at {fpath}: "
                    f"{e}") from e
            if flight.get("process_index") != r or not flight.get("spans"):
                raise DrillFailure(
                    f"rank {r} flight dump carries identity "
                    f"{flight.get('process_index')!r} and "
                    f"{len(flight.get('spans') or [])} spans")
        report["overlaps"] = overlaps

        # --- merge CLI: one schema-valid cluster timeline ------------
        merged_path = os.path.join(root, "merged_trace.json")
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH",
                                                         "")
        cli = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.observability.merge",
             "--trace", trace_dir, "--output", merged_path],
            env=env, capture_output=True, text=True, timeout=60)
        if cli.returncode != 0:
            raise DrillFailure(
                f"merge --trace CLI exited {cli.returncode}: "
                f"{cli.stderr}")
        with open(merged_path, "r", encoding="utf-8") as f:
            doc = json.load(f)
        evs = doc.get("traceEvents") if isinstance(doc, dict) else None
        if not isinstance(evs, list) or not evs:
            raise DrillFailure(
                f"merged trace is not a Chrome trace document: "
                f"{type(evs).__name__}")
        pids, meta_ranks, last_ts, x_events = set(), set(), None, 0
        for ev in evs:
            if not isinstance(ev, dict) or "name" not in ev \
                    or "ph" not in ev or "pid" not in ev:
                raise DrillFailure(f"malformed trace event: {ev!r}")
            pids.add(ev["pid"])
            if ev["ph"] == "M" and ev["name"] == "process_name":
                meta_ranks.add(ev["pid"])
            elif ev["ph"] == "X":
                x_events += 1
                if not {"ts", "dur", "cat"} <= ev.keys():
                    raise DrillFailure(
                        f"incomplete X event: {ev!r}")
                if last_ts is not None and ev["ts"] < last_ts:
                    raise DrillFailure(
                        f"merged trace is not time-ordered: "
                        f"{ev['ts']} after {last_ts}")
                last_ts = ev["ts"]
        if pids != set(range(world)):
            raise DrillFailure(
                f"merged trace pids {sorted(pids)}, expected ranks "
                f"0..{world - 1}")
        if meta_ranks != set(range(world)):
            raise DrillFailure(
                f"process_name metadata for ranks "
                f"{sorted(meta_ranks)}, expected all {world}")
        # 4 phase spans per step per rank land in the merged doc
        if x_events != world * steps * 4:
            raise DrillFailure(
                f"merged trace holds {x_events} X events from "
                f"{world} ranks x {steps} steps x 4 phases")
        report.update({"merged_events": x_events,
                       "merged_path": merged_path})
    finally:
        reap_all()
    return report


def run_numerics_drill(root, *, world=2, steps=12, poison_step=5,
                       poison_rank=1, cadence=4, halt=False,
                       gen_timeout=120.0, log_dir=None):
    """NaN-injection numerics drill: ``world`` REAL worker processes
    each train a captured MLP on CPU with the numerics monitor armed;
    ``poison_rank`` overwrites one input element with NaN at
    ``poison_step`` (same shape/dtype — the capture cache must not
    retrace).  The runner asserts from each rank's report that the
    poisoned rank's sentinel fired within ONE cadence window of the
    injection, named a real parameter path (or the loss), and left a
    flight dump whose recorded reason carries that name; that every
    clean rank stayed quiet (zero anomalies); and that every rank
    compiled its captured step exactly once.  With ``halt`` the
    poisoned worker must exit ``EXIT_NUMERICS_HALT`` cleanly (report
    still written); otherwise every rank exits 0.  Storeless: no
    TCPStore master, no checkpoints.  Returns a report dict."""
    out_dir = os.path.join(root, "numerics")
    flight_dir = os.path.join(root, "flight")
    os.makedirs(out_dir, exist_ok=True)
    run_id = f"numerics-{uuid.uuid4().hex[:6]}"
    spec = NumericsSpec(out_dir=out_dir, poison_step=poison_step,
                        poison_rank=poison_rank, cadence=cadence,
                        halt=halt)
    report = {"run_id": run_id, "world": world, "steps": steps,
              "poison_step": poison_step, "poison_rank": poison_rank,
              "cadence": cadence, "halt": halt}
    try:
        procs = [
            spawn_worker(
                r, world, root=root, total_steps=steps, run_id=run_id,
                barrier_timeout=gen_timeout, numerics=spec,
                flight_dir=flight_dir,
                log_path=(os.path.join(log_dir, f"numerics_rank{r}.log")
                          if log_dir else None))
            for r in range(world)
        ]
        rcs = _wait_fleet(procs, gen_timeout)
        report["rcs"] = rcs
        for r, rc in enumerate(rcs):
            want = EXIT_NUMERICS_HALT if (halt and r == poison_rank) \
                else 0
            if rc != want:
                raise DrillFailure(
                    f"numerics rank {r} exited {rc}, expected {want}")

        ranks = {}
        for r in range(world):
            rep_path = numerics_report_path(out_dir, r)
            try:
                with open(rep_path, "r", encoding="utf-8") as f:
                    rep = json.load(f)
            except (OSError, ValueError) as e:
                raise DrillFailure(
                    f"rank {r} wrote no parseable numerics report at "
                    f"{rep_path}: {e}") from e
            ranks[r] = rep
            if rep.get("compiles") != 1:
                raise DrillFailure(
                    f"rank {r} compiled its captured step "
                    f"{rep.get('compiles')} times; the monitored step "
                    f"must stay at exactly 1 compile")
            if rep.get("fallback"):
                raise DrillFailure(
                    f"rank {r} fell back to eager "
                    f"{rep.get('fallback')} times")
        report["ranks"] = ranks

        # --- the poisoned rank: detection, naming, flight dump -------
        rep = ranks[poison_rank]
        detected = rep.get("detected_step")
        if detected is None:
            raise DrillFailure(
                f"poisoned rank {poison_rank} never detected the "
                f"injected NaN: {rep!r}")
        if not poison_step <= detected <= poison_step + cadence:
            raise DrillFailure(
                f"detection at step {detected} is outside one cadence "
                f"window [{poison_step}, {poison_step + cadence}] of "
                f"the injection")
        if not rep.get("anomalies", {}).get("nonfinite"):
            raise DrillFailure(
                f"poisoned rank booked no 'nonfinite' anomaly: "
                f"{rep.get('anomalies')!r}")
        param_trips = [t for t in rep.get("tripped") or []
                       if t != "loss"]
        if not param_trips:
            raise DrillFailure(
                f"sentinel named no parameter path, only "
                f"{rep.get('tripped')!r}; a poisoned input must "
                f"surface non-finite grads by name")
        if halt and not rep.get("halted"):
            raise DrillFailure(
                "halt variant: the sentinel raise was never observed")
        fpath = rep.get("flight")
        try:
            with open(fpath, "r", encoding="utf-8") as f:
                flight = json.load(f)
        except (TypeError, OSError, ValueError) as e:
            raise DrillFailure(
                f"poisoned rank's flight dump unreadable at "
                f"{fpath!r}: {e}") from e
        reason = flight.get("reason") or ""
        named = reason.split(":", 2)[2] if reason.count(":") >= 2 \
            else ""
        if not reason.startswith("numerics:nonfinite") \
                or named not in param_trips:
            raise DrillFailure(
                f"flight dump reason {reason!r} must pin the first "
                f"non-finite trip to a parameter path (one of "
                f"{param_trips!r})")
        if flight.get("process_index") != poison_rank:
            raise DrillFailure(
                f"flight dump identity "
                f"{flight.get('process_index')!r} != poisoned rank "
                f"{poison_rank}")
        report.update({"detected_step": detected,
                       "named_tensor": named,
                       "flight_reason": reason})

        # --- clean ranks stay quiet ----------------------------------
        for r in range(world):
            if r == poison_rank:
                continue
            rep = ranks[r]
            if rep.get("anomalies"):
                raise DrillFailure(
                    f"clean rank {r} booked anomalies "
                    f"{rep['anomalies']!r}; the sentinel must stay "
                    f"quiet on healthy data")
            if rep.get("detected_step") is not None:
                raise DrillFailure(
                    f"clean rank {r} claims detection at step "
                    f"{rep['detected_step']}")
    finally:
        reap_all()
    return report


def run_sdc_drill(root, *, scenario="consensus", world=3, steps=12,
                  poison_step=5, poison_rank=1, cadence=4, bit=3,
                  quarantine_threshold=2, sdc_max_restarts=4,
                  barrier_timeout=6.0, gen_timeout=180.0, log_dir=None):
    """Silent-data-corruption drill: REAL worker processes, a real bit
    flip, and the full detect → attribute → quarantine → refuse chain.
    Three scenarios:

    - ``consensus``: ``world`` dp-replica workers (same seed, same
      data — bit-identical by construction) train a captured MLP with
      the SDC sentry armed, exchanging fingerprints through a real
      TCPStore.  The victim flips ONE mantissa bit of its first
      parameter at ``poison_step``; the majority vote must finger
      exactly that rank within one cadence window, name a divergent
      tensor path, pin a flight dump on the victim, and halt it into
      ``EXIT_SDC`` — while every clean rank books the verdict against
      the victim (and only the victim) and runs to completion with
      exactly one compile.  ``poison_rank=-1`` is the control run:
      everyone must stay verdict-free and exit 0.
    - ``quarantine``: the same poisoned fleet under a real
      :class:`~..supervisor.Supervisor`.  The victim re-poisons every
      generation at the original world size — a sticky bad host — so
      consensus fingers it ``quarantine_threshold`` times; the
      supervisor must charge every ``EXIT_SDC`` to the hardware ledger
      (never the code-crash budget), quarantine the rank, downsize the
      fleet around it, and the downsized generation (poison disabled:
      the bad host left the pool) must finish cleanly.
    - ``restore``: a clean single-rank checkpoint run, then
      :func:`poison_shard` plants a bit flip in the committed shard
      AND re-seals the manifest CRC over the corrupted bytes — the
      corruption a file-level CRC can never catch.  Manifest
      verification must still pass, ``integrity="full"`` must refuse
      naming the leaf and the digests, and a relaunched worker
      resuming with ``DRILL_RESTORE_INTEGRITY=full`` must exit
      ``EXIT_SDC`` instead of training on corrupt state.

    Returns a report dict for further assertions.
    """
    if scenario not in ("consensus", "quarantine", "restore"):
        raise ValueError(f"unknown sdc drill scenario {scenario!r}")
    out_dir = os.path.join(root, "sdc")
    flight_dir = os.path.join(root, "flight")
    os.makedirs(out_dir, exist_ok=True)
    exch_timeout = min(30.0, gen_timeout / 3.0)

    def _log(name):
        return os.path.join(log_dir, name) if log_dir else None

    report = {"scenario": scenario, "world": world, "steps": steps,
              "poison_step": poison_step, "poison_rank": poison_rank,
              "cadence": cadence, "bit": bit}

    if scenario == "restore":
        return _run_sdc_restore_leg(root, report, steps=steps, bit=bit,
                                    barrier_timeout=barrier_timeout,
                                    gen_timeout=gen_timeout, _log=_log)

    master = TCPStore("127.0.0.1", 0, is_master=True)
    try:
        if scenario == "consensus":
            run_id = f"sdc-{uuid.uuid4().hex[:6]}"
            spec = SdcSpec(out_dir=out_dir, poison_step=poison_step,
                           poison_rank=poison_rank, cadence=cadence,
                           bit=bit, exchange_timeout=exch_timeout)
            procs = [
                spawn_worker(
                    r, world, root=root, port=master.port,
                    total_steps=steps, run_id=run_id,
                    barrier_timeout=gen_timeout, sdc=spec,
                    flight_dir=flight_dir,
                    log_path=_log(f"sdc_rank{r}.log"))
                for r in range(world)
            ]
            rcs = _wait_fleet(procs, gen_timeout)
            report["rcs"] = rcs
            _assert_sdc_consensus(report, out_dir, rcs, world=world,
                                  steps=steps, poison_step=poison_step,
                                  poison_rank=poison_rank,
                                  cadence=cadence)
        else:  # quarantine
            from ..supervisor import Supervisor

            world0 = world

            def spawn(rank, w, run_id, generation):
                gdir = os.path.join(out_dir, f"g{generation}")
                os.makedirs(gdir, exist_ok=True)
                # the bad host re-poisons while it is in the pool; the
                # post-quarantine downsized world runs clean
                spec = SdcSpec(
                    out_dir=gdir, poison_step=poison_step,
                    poison_rank=poison_rank if w == world0 else -1,
                    cadence=cadence, bit=bit,
                    exchange_timeout=exch_timeout)
                return spawn_worker(
                    rank, w, root=root, port=master.port,
                    total_steps=steps, run_id=run_id,
                    barrier_timeout=gen_timeout, sdc=spec,
                    log_path=_log(f"sdc_q_g{generation}_rank{rank}.log"))

            sup = Supervisor(
                spawn, world, sdc_max_restarts=sdc_max_restarts,
                sdc_quarantine_threshold=quarantine_threshold,
                grace=3.0 * barrier_timeout,
                generation_timeout=gen_timeout,
                run_id_prefix=f"sdcq-{uuid.uuid4().hex[:6]}")
            snap = sup.run()
            report["supervision"] = snap
            _assert_sdc_quarantine(report, snap,
                                   poison_rank=poison_rank,
                                   threshold=quarantine_threshold,
                                   world=world)
    finally:
        try:
            master.close()
        except Exception as e:
            logger.debug("sdc drill: master close after run: %s", e)
        reap_all()
    return report


def _assert_sdc_consensus(report, out_dir, rcs, *, world, steps,
                          poison_step, poison_rank, cadence):
    """Assertions for the consensus scenario (shared with the control
    run, where ``poison_rank`` is -1 and nobody may be fingered)."""
    clean_run = poison_rank < 0
    for r, rc in enumerate(rcs):
        want = EXIT_SDC if (not clean_run and r == poison_rank) else 0
        if rc != want:
            raise DrillFailure(
                f"sdc rank {r} exited {rc}, expected {want}")
    ranks = {}
    for r in range(world):
        rep_path = sdc_report_path(out_dir, r)
        try:
            with open(rep_path, "r", encoding="utf-8") as f:
                rep = json.load(f)
        except (OSError, ValueError) as e:
            raise DrillFailure(
                f"rank {r} wrote no parseable sdc report at "
                f"{rep_path}: {e}") from e
        ranks[r] = rep
        if rep.get("compiles") != 1:
            raise DrillFailure(
                f"rank {r} compiled its captured step "
                f"{rep.get('compiles')} times; the fingerprinted step "
                f"must stay at exactly 1 compile")
        if rep.get("fallback"):
            raise DrillFailure(
                f"rank {r} fell back to eager "
                f"{rep.get('fallback')} times")
    report["ranks"] = ranks

    if clean_run:
        for r, rep in ranks.items():
            if rep.get("divergences_total"):
                raise DrillFailure(
                    f"control run: rank {r} booked verdicts "
                    f"{rep.get('divergences')!r} on bit-identical "
                    f"replicas")
        return

    # --- the victim: halt, detection window, attribution, flight -----
    rep = ranks[poison_rank]
    if not rep.get("halted"):
        raise DrillFailure(
            f"victim rank {poison_rank} never halted: {rep!r}")
    detected = rep.get("detected_step")
    if detected is None or \
            not poison_step < detected <= poison_step + cadence:
        raise DrillFailure(
            f"detection at step {detected} is outside one cadence "
            f"window ({poison_step}, {poison_step + cadence}] of the "
            f"injection")
    last = rep.get("last_divergence") or {}
    if last.get("rank") != poison_rank:
        raise DrillFailure(
            f"victim's own verdict names rank {last.get('rank')!r}, "
            f"expected {poison_rank}")
    named = last.get("tensor")
    if not named or not (named.startswith("param::")
                         or named.startswith("opt")):
        raise DrillFailure(
            f"consensus named no fingerprinted tensor path: {named!r}")
    fpath = rep.get("flight")
    try:
        with open(fpath, "r", encoding="utf-8") as f:
            flight = json.load(f)
    except (TypeError, OSError, ValueError) as e:
        raise DrillFailure(
            f"victim's flight dump unreadable at {fpath!r}: {e}") from e
    reason = flight.get("reason") or ""
    if not reason.startswith("sdc:divergence:") or named not in reason:
        raise DrillFailure(
            f"flight dump reason {reason!r} must pin the divergent "
            f"tensor {named!r}")
    if flight.get("process_index") != poison_rank:
        raise DrillFailure(
            f"flight dump identity {flight.get('process_index')!r} != "
            f"victim rank {poison_rank}")
    report.update({"detected_step": detected, "named_tensor": named,
                   "flight_reason": reason})

    # --- clean ranks: correct attribution, nothing else --------------
    for r in range(world):
        if r == poison_rank:
            continue
        rep = ranks[r]
        if rep.get("halted"):
            raise DrillFailure(f"clean rank {r} halted")
        div = rep.get("divergences") or {}
        if list(div) != [str(poison_rank)]:
            raise DrillFailure(
                f"clean rank {r} booked verdicts against {sorted(div)}"
                f", expected exactly [{poison_rank!r}] — consensus "
                f"must finger the victim and nobody else")
        peer_last = rep.get("last_divergence") or {}
        if peer_last.get("rank") != poison_rank:
            raise DrillFailure(
                f"clean rank {r} attributes the divergence to rank "
                f"{peer_last.get('rank')!r}, expected {poison_rank}")


def _assert_sdc_quarantine(report, snap, *, poison_rank, threshold,
                           world):
    """Assertions for the quarantine scenario."""
    final_rcs = snap.get("final_rcs") or {}
    if not final_rcs or any(rc != 0 for rc in final_rcs.values()):
        raise DrillFailure(
            f"quarantine: final generation rcs {final_rcs}, expected "
            f"a clean downsized fleet (all 0)")
    if snap.get("quarantined_ranks") != [poison_rank]:
        raise DrillFailure(
            f"quarantine: quarantined_ranks "
            f"{snap.get('quarantined_ranks')}, expected "
            f"[{poison_rank}]")
    verdicts = (snap.get("sdc_verdicts") or {}).get(str(poison_rank), 0)
    if verdicts < threshold:
        raise DrillFailure(
            f"quarantine: only {verdicts} consensus verdicts against "
            f"rank {poison_rank}, expected >= {threshold}")
    by_cause = snap.get("restarts_by_cause") or {}
    if by_cause.get("sdc", 0) < threshold:
        raise DrillFailure(
            f"quarantine: restarts_by_cause {by_cause} books "
            f"{by_cause.get('sdc', 0)} 'sdc' restarts, expected >= "
            f"{threshold} — EXIT_SDC must charge the hardware ledger")
    if any(c in by_cause for c in ("crashed", "killed")):
        raise DrillFailure(
            f"quarantine: consensus verdicts leaked into the "
            f"code-crash budget: {by_cause}")
    quarantine_resizes = [rz for rz in snap.get("resizes") or []
                          if rz.get("quarantined")]
    if not quarantine_resizes or \
            quarantine_resizes[0].get("dead_ranks") != [poison_rank]:
        raise DrillFailure(
            f"quarantine: no elastic downsize around rank "
            f"{poison_rank}: {snap.get('resizes')!r}")
    if snap.get("world") != world - 1:
        raise DrillFailure(
            f"quarantine: final world {snap.get('world')}, expected "
            f"{world - 1} (the suspect host left the pool)")


def _run_sdc_restore_leg(root, report, *, steps, bit, barrier_timeout,
                         gen_timeout, _log):
    """The restore scenario: clean run → poison_shard → manifest still
    verifies → full integrity refuses naming the leaf → resuming
    worker exits ``EXIT_SDC``."""
    ckpt_root = os.path.join(root, "ckpt")
    os.makedirs(ckpt_root, exist_ok=True)
    try:
        p = spawn_worker(0, 1, root=ckpt_root, total_steps=steps,
                         run_id=f"sdcr-{uuid.uuid4().hex[:6]}",
                         barrier_timeout=barrier_timeout,
                         log_path=_log("sdc_restore_g0.log"))
        rcs = _wait_fleet([p], gen_timeout)
        if rcs != [0]:
            raise DrillFailure(
                f"restore: clean generation exited {rcs}, expected [0]")
        latest = _latest_step(ckpt_root)
        if latest != steps:
            raise DrillFailure(
                f"restore: newest committed step {latest}, wanted "
                f"{steps}")
        d = os.path.join(ckpt_root, f"step_{int(latest):08d}")
        verify_checkpoint(d, integrity="full")  # clean before poison
        rel = poison_shard(d, bit=bit)
        report["poisoned_file"] = rel
        leaf = rel.split(os.sep)[1] if rel.count(os.sep) >= 2 else rel
        # the sealed manifest CRC passes — the corruption is silent at
        # the file level...
        verify_checkpoint(d, integrity="size")
        if read_leaf(d, leaf, integrity="size") is None:
            raise DrillFailure("restore: size-integrity read failed")
        # ...and only the content digest refuses, naming the leaf
        try:
            verify_checkpoint(d, integrity="full")
        except CheckpointCorruptError as e:
            msg = str(e)
            if "content digest" not in msg or f"'{leaf}'" not in msg:
                raise DrillFailure(
                    f"restore: refusal does not name the poisoned "
                    f"leaf {leaf!r} and its digest: {msg!r}") from e
            report["refusal"] = msg
        else:
            raise DrillFailure(
                f"restore: poisoned checkpoint (file {rel!r}) passed "
                f"full verification — the content digest caught "
                f"nothing")
        p = spawn_worker(0, 1, root=ckpt_root, total_steps=steps * 2,
                         run_id=f"sdcr-{uuid.uuid4().hex[:6]}",
                         barrier_timeout=barrier_timeout,
                         restore_integrity="full",
                         log_path=_log("sdc_restore_g1.log"))
        rc = _wait_fleet([p], gen_timeout)[0]
        report["resume_rc"] = rc
        if rc != EXIT_SDC:
            raise DrillFailure(
                f"restore: resuming worker exited {rc}, expected "
                f"EXIT_SDC ({EXIT_SDC}) — it must refuse to train on "
                f"bit-rotted state")
        latest2 = _latest_step(ckpt_root)
        if latest2 != steps:
            raise DrillFailure(
                f"restore: refused resume advanced the checkpoint to "
                f"{latest2} (was {steps}) — nothing may be written "
                f"past a refused restore")
    finally:
        reap_all()
    return report


def run_oom_drill(root, *, world=2, steps=8, oom_step=5, oom_rank=1,
                  mem_bytes=1_000_000, mem_threshold=None,
                  gen_timeout=120.0, log_dir=None):
    """OOM-postmortem drill: ``world`` REAL worker processes each
    train a captured MLP on CPU with the memory monitor armed;
    ``oom_rank`` swaps its compiled cache entry for a callable raising
    ``RESOURCE_EXHAUSTED`` at ``oom_step``, so the capture replay's
    intercept must book a flight dump whose reason pins
    ``oom:<program>:<buffer>`` with the buffer being a PARAMETER PATH
    (the drill model's first weight dominates every other live array
    by construction) and whose ``extra.memory`` payload carries the
    census, per-program footprints and watermark history.  The victim
    exits ``EXIT_OOM`` (23) cleanly after writing its report; clean
    ranks exit 0 with zero postmortems; every rank compiles exactly
    once (the armed failure is a cache HIT, never a retrace).

    Each rank also exports a rank-scaled synthetic watermark
    (``mem_bytes * (1 + rank)``) and dumps its /metrics exposition;
    the runner replays those dumps through a LOCAL
    :class:`~paddle_tpu.observability.aggregator.ClusterAggregator`
    (threshold ``mem_threshold``, default ``mem_bytes * world`` so the
    near-OOM trip fires exactly) and asserts the fleet view: skew
    gauge ``mem_bytes * (world - 1)``, per-rank bytes in /healthz, and
    the memory alarm flipping health to not-ok.  Storeless: no
    TCPStore master, no checkpoints.  Returns a report dict."""
    out_dir = os.path.join(root, "oom")
    flight_dir = os.path.join(root, "flight")
    os.makedirs(out_dir, exist_ok=True)
    run_id = f"oom-{uuid.uuid4().hex[:6]}"
    if mem_threshold is None:
        mem_threshold = mem_bytes * world
    spec = OomSpec(out_dir=out_dir, oom_step=oom_step,
                   oom_rank=oom_rank, mem_bytes=mem_bytes)
    report = {"run_id": run_id, "world": world, "steps": steps,
              "oom_step": oom_step, "oom_rank": oom_rank,
              "mem_bytes": mem_bytes, "mem_threshold": mem_threshold}
    try:
        procs = [
            spawn_worker(
                r, world, root=root, total_steps=steps, run_id=run_id,
                barrier_timeout=gen_timeout, oom=spec,
                flight_dir=flight_dir,
                log_path=(os.path.join(log_dir, f"oom_rank{r}.log")
                          if log_dir else None))
            for r in range(world)
        ]
        rcs = _wait_fleet(procs, gen_timeout)
        report["rcs"] = rcs
        for r, rc in enumerate(rcs):
            want = EXIT_OOM if r == oom_rank else 0
            if rc != want:
                raise DrillFailure(
                    f"oom rank {r} exited {rc}, expected {want}")

        ranks = {}
        for r in range(world):
            rep_path = oom_report_path(out_dir, r)
            try:
                with open(rep_path, "r", encoding="utf-8") as f:
                    rep = json.load(f)
            except (OSError, ValueError) as e:
                raise DrillFailure(
                    f"rank {r} wrote no parseable oom report at "
                    f"{rep_path}: {e}") from e
            ranks[r] = rep
            if rep.get("compiles") != 1:
                raise DrillFailure(
                    f"rank {r} compiled its captured step "
                    f"{rep.get('compiles')} times; the armed failure "
                    f"must replay a cache hit, never retrace")
            if rep.get("fallback"):
                raise DrillFailure(
                    f"rank {r} fell back to eager: "
                    f"{rep.get('fallback')!r}")
        report["ranks"] = ranks

        # --- the victim: postmortem booked, flight dump pins a param -
        rep = ranks[oom_rank]
        if not rep.get("caught") or rep.get("oom_events") != 1:
            raise DrillFailure(
                f"victim rank {oom_rank} booked "
                f"{rep.get('oom_events')} postmortems (caught="
                f"{rep.get('caught')!r}), expected exactly 1")
        fpath = rep.get("flight")
        try:
            with open(fpath, "r", encoding="utf-8") as f:
                flight = json.load(f)
        except (TypeError, OSError, ValueError) as e:
            raise DrillFailure(
                f"victim's flight dump unreadable at {fpath!r}: "
                f"{e}") from e
        reason = flight.get("reason") or ""
        named = reason.split(":", 2)[2] if reason.count(":") >= 2 \
            else ""
        if not reason.startswith("oom:") \
                or not named.startswith("param::"):
            raise DrillFailure(
                f"flight dump reason {reason!r} must pin the top live "
                f"buffer to a parameter path (param::...)")
        if flight.get("process_index") != oom_rank:
            raise DrillFailure(
                f"flight dump identity "
                f"{flight.get('process_index')!r} != victim rank "
                f"{oom_rank}")
        mem_doc = (flight.get("extra") or {}).get("memory") or {}
        census = mem_doc.get("census") or {}
        top = census.get("top") or []
        if mem_doc.get("top_buffer") != named or not top \
                or top[0].get("name") != named:
            raise DrillFailure(
                f"postmortem census top {top[:1]!r} disagrees with "
                f"the flight reason's buffer {named!r}")
        if not mem_doc.get("programs"):
            raise DrillFailure(
                "postmortem carries no per-program footprints; the "
                "compile-time harvest must ride into the flight dump")
        if not mem_doc.get("watermarks"):
            raise DrillFailure(
                "postmortem carries no watermark history; the "
                "synthetic samples must ride into the flight dump")
        report.update({"flight_reason": reason, "named_buffer": named,
                       "census_categories":
                           sorted(census.get("by_category") or {})})

        # --- clean ranks booked nothing ------------------------------
        for r in range(world):
            if r == oom_rank:
                continue
            if ranks[r].get("oom_events") or ranks[r].get("caught"):
                raise DrillFailure(
                    f"clean rank {r} booked an OOM postmortem: "
                    f"{ranks[r]!r}")

        # --- fleet view: replay the per-rank expositions through a
        # local aggregator and assert skew + the near-OOM trip --------
        from ...observability.aggregator import (ClusterAggregator,
                                                 parse_prometheus_text)
        agg = ClusterAggregator(
            endpoints={r: f"drill-rank-{r}" for r in range(world)},
            run_id=run_id, mem_threshold=mem_threshold)
        for r in range(world):
            mpath = oom_metrics_path(out_dir, r)
            try:
                with open(mpath, "r", encoding="utf-8") as f:
                    fams = parse_prometheus_text(f.read())
            except (OSError, ValueError) as e:
                raise DrillFailure(
                    f"rank {r} exposition dump unreadable at "
                    f"{mpath}: {e}") from e
            agg._scrapes[r] = {"ts": time.monotonic(),
                               "families": fams, "error": None}
        agg._render()
        fams = parse_prometheus_text(agg.prometheus_text())
        skew = _sample_value(fams, "pt_cluster_memory_skew_bytes")
        # the victim died before feeding a watermark only when the
        # injection step precedes its first sample; every surviving
        # rank r published mem_bytes * (1 + r)
        live = [r for r in range(world)
                if ranks[r].get("watermark_samples")]
        want_skew = float(mem_bytes * (max(live) - min(live)))
        if skew != want_skew:
            raise DrillFailure(
                f"fleet memory skew {skew!r}, expected {want_skew} "
                f"from ranks {live} at base {mem_bytes}")
        health = agg.healthz()
        hmem = health.get("memory") or {}
        want_alarm = mem_bytes * (1 + max(live)) >= mem_threshold
        if bool(hmem.get("mem_alarm")) != want_alarm \
                or health.get("ok") != (not want_alarm):
            raise DrillFailure(
                f"aggregator health {hmem!r} ok={health.get('ok')}; "
                f"expected mem_alarm={want_alarm} at threshold "
                f"{mem_threshold}")
        oom_total = _sample_value(fams, "pt_cluster_oom_events_total")
        if oom_total is None:
            oom_total = sum(
                ranks[r].get("oom_events", 0) for r in range(world))
        report.update({"fleet_skew_bytes": skew,
                       "mem_alarm": bool(hmem.get("mem_alarm")),
                       "healthz": health,
                       "oom_events_total": oom_total})
    finally:
        reap_all()
    return report


def _overlap_param_tree(layers, hidden):
    """Synthetic MLP parameter tree (registration order: first→last)
    plus per-name and total byte counts."""
    import numpy as np

    params = {}
    for i in range(layers):
        params[f"l{i}.weight"] = np.zeros((hidden, hidden), np.float32)
        params[f"l{i}.bias"] = np.zeros((hidden,), np.float32)
    nbytes = {k: v.size * v.dtype.itemsize for k, v in params.items()}
    return params, nbytes, sum(nbytes.values())


def _overlap_replay(params, nbytes, spans_fn, run_id,
                    compute_bytes_per_ns):
    """Replay one reduction mode's span timeline through the REAL
    tracer and return its snapshot.

    The backward is a per-param compute span, last-registered first
    (the order autodiff produces grads); ``spans_fn(tr, ready,
    bwd_end) -> coll_end`` records that mode's collective spans given
    each grad's ready time; the optimizer span starts after the last
    collective (it waits for every reduced grad)."""
    from ...observability.trace import get_tracer, reset_tracer

    total_bytes = sum(nbytes.values())
    reset_tracer()
    tr = get_tracer().enable(process_index=0, run_id=run_id)
    t, ready = 1_000_000, {}
    for name in reversed(params):
        dur = max(int(nbytes[name] / compute_bytes_per_ns), 1)
        tr.phase_record("backward", t, t + dur)
        t += dur
        ready[name] = t
    coll_end = max(spans_fn(tr, ready, t), t)
    opt_end = coll_end + max(int(total_bytes / compute_bytes_per_ns
                                 / 10), 1)
    tr.phase_record("optimizer", coll_end, opt_end)
    tr.on_step((opt_end - 1_000_000) / 1e9)
    snap = tr.snapshot()
    reset_tracer()
    return snap


def _write_overlap_report(root, name, report):
    os.makedirs(root, exist_ok=True)
    path = os.path.join(root, name)
    tmp = f"{path}.tmp{os.getpid()}"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(report, f, indent=2)
    os.replace(tmp, path)
    report["report_path"] = path
    return report


def run_overlap_drill(root, *, layers=8, hidden=256, bucket_kb=256,
                      comm_bytes_per_ns=2.0, compute_bytes_per_ns=1.0):
    """Compute↔collective overlap drill: prove the bucketed gradient
    reduction RAISES the measured overlap fraction vs the monolithic
    post-backward reduction — on the same synthetic model, through the
    REAL partitioner and the REAL tracer.

    The span timelines are the schedules the two reduction modes pin
    down (synthetic timestamps, no sleeping):

    - *unbucketed*: backward compute runs end-to-end, then ONE fused
      all-reduce of every gradient byte, then the optimizer — the
      collective sits alone on the critical path, overlap 0.
    - *bucketed*: ``partition_buckets`` groups the same parameters
      (reverse-backward order); each bucket's fused reduction is issued
      the moment its last member's grad is formed and runs while the
      REMAINING backward compute proceeds — exactly where autodiff
      places the ``bucket_reduce_marker`` pmean in the compiled step.
      Only the final bucket's reduction has no compute left to hide
      under.

    Both timelines feed the real ``Tracer`` (``phase_record`` /
    ``record_span`` → ``pt_compute_collective_overlap_fraction``); the
    drill asserts bucketed > unbucketed ≥ 0 and writes a report JSON.
    Returns the report dict.
    """
    from ..grad_buckets import partition_buckets

    params, nbytes, total_bytes = _overlap_param_tree(layers, hidden)
    plan = partition_buckets(params, int(bucket_kb) * 1024)
    if plan.n_buckets < 2:
        raise DrillFailure(
            f"bucket_kb={bucket_kb} yields {plan.n_buckets} bucket(s); "
            f"the drill needs >= 2 to show overlap")

    def unbucketed(tr, ready, bwd_end):
        dur = max(int(total_bytes / comm_bytes_per_ns), 1)
        tr.record_span("all_reduce", "collective", bwd_end,
                       bwd_end + dur)
        return bwd_end + dur

    def bucketed(tr, ready, bwd_end):
        coll_end = bwd_end
        for b in plan.buckets:
            t0 = max(ready[n] for n in b.names)
            dur = max(int(b.nbytes / comm_bytes_per_ns), 1)
            tr.record_span("all_reduce", "collective", t0, t0 + dur)
            coll_end = max(coll_end, t0 + dur)
        return coll_end

    snap_un = _overlap_replay(params, nbytes, unbucketed,
                              "overlap-unbucketed", compute_bytes_per_ns)
    snap_bk = _overlap_replay(params, nbytes, bucketed,
                              "overlap-bucketed", compute_bytes_per_ns)
    ov_un = snap_un.get("overlap_fraction")
    ov_bk = snap_bk.get("overlap_fraction")
    if ov_un is None or ov_bk is None:
        raise DrillFailure(
            f"tracer measured no overlap fraction (unbucketed={ov_un!r} "
            f"bucketed={ov_bk!r}) — collective spans missing?")
    if not ov_bk > ov_un:
        raise DrillFailure(
            f"bucketed overlap {ov_bk} not strictly above unbucketed "
            f"{ov_un}")
    if ov_bk <= 0.0:
        raise DrillFailure(f"bucketed overlap {ov_bk} not positive")
    report = {
        "n_buckets": plan.n_buckets,
        "bucket_bytes": [b.nbytes for b in plan.buckets],
        "total_bytes": total_bytes,
        "overlap_unbucketed": ov_un,
        "overlap_bucketed": ov_bk,
    }
    return _write_overlap_report(root, "overlap_report.json", report)


def run_sharded_overlap_drill(root, *, layers=8, hidden=256,
                              bucket_kb=256, n_dp=2, n_shard=4,
                              ici_bytes_per_ns=4.0, dcn_bytes_per_ns=1.0,
                              compute_bytes_per_ns=1.0):
    """Sharded-mesh (ZeRO dp×sharding) overlap drill.

    Same replay harness as :func:`run_overlap_drill`, but the two
    timelines are the ones the collective-schedule pass chooses
    between on a ZeRO mesh:

    - *unbucketed (GSPMD)*: backward runs end-to-end, then ONE
      monolithic reduction of every gradient byte over the product
      communicator — the full payload crosses the slow dp links and
      nothing hides it: overlap 0.
    - *bucketed + scheduled*: the REAL partitioner (with the params'
      ``place_axis`` scatter dims) and the REAL planner
      (:func:`~paddle_tpu.distributed.collective_schedule.
      plan_grad_reduction`) produce per-bucket
      ``reduce_scatter(sharding) → all_reduce(dp) → all_gather``
      chains, each issued at its bucket's grad-ready time.  The
      reduce-scatter/all-gather legs move at ICI speed and the dp leg
      carries only ``1/n_shard`` of the bytes at DCN speed, while the
      remaining backward hides all but the last bucket's chain.

    Asserts the scheduled overlap is strictly above the monolithic
    baseline AND above 0.5 — the bar ``dryrun_multichip`` reports for
    sharded configs.  Writes/returns the report dict.
    """
    from jax.sharding import PartitionSpec as P

    from ..auto_parallel.spec_layout import place_axis, spec_axes
    from ..collective_schedule import plan_grad_reduction
    from ..grad_buckets import partition_buckets

    params, nbytes, total_bytes = _overlap_param_tree(layers, hidden)
    scatter_dims = {}
    for k, v in params.items():
        zs = place_axis(P(), v.shape, n_shard, "sharding")
        scatter_dims[k] = next(
            (d for d, e in enumerate(zs) if "sharding" in spec_axes(e)),
            None)
    plan = partition_buckets(params, int(bucket_kb) * 1024,
                             scatter_dims=scatter_dims)
    sched = plan_grad_reduction({"dp": n_dp, "sharding": n_shard}, "os")
    if sched is None or not sched.scatters:
        raise DrillFailure(
            f"planner produced no scatter schedule for dp={n_dp} "
            f"sharding={n_shard}")
    if plan.n_buckets < 2:
        raise DrillFailure(
            f"bucket_kb={bucket_kb} yields {plan.n_buckets} bucket(s); "
            f"the drill needs >= 2 to show overlap")

    def unbucketed(tr, ready, bwd_end):
        # GSPMD's monolithic post-backward reduction: every byte over
        # the slow link, one op, nothing left to hide it under
        dur = max(int(total_bytes / dcn_bytes_per_ns), 1)
        tr.record_span("all_reduce", "collective", bwd_end,
                       bwd_end + dur)
        return bwd_end + dur

    def scheduled(tr, ready, bwd_end):
        coll_end = bwd_end
        for b in plan.buckets:
            t = max(ready[n] for n in b.names)
            for st in sched.stages:
                if b.kind != "reduce_scatter" and st.op != "all_reduce":
                    continue  # unscatterable buckets: plain dp pmean
                payload = b.nbytes
                if b.kind == "reduce_scatter" and st.op != "reduce_scatter":
                    payload = b.nbytes // sched.shard_size
                rate = (dcn_bytes_per_ns if st.axis == "dp"
                        else ici_bytes_per_ns)
                dur = max(int(payload / rate), 1)
                tr.record_span(st.op, "collective", t, t + dur)
                t += dur
            coll_end = max(coll_end, t)
        return coll_end

    snap_un = _overlap_replay(params, nbytes, unbucketed,
                              "sharded-overlap-unbucketed",
                              compute_bytes_per_ns)
    snap_bk = _overlap_replay(params, nbytes, scheduled,
                              "sharded-overlap-scheduled",
                              compute_bytes_per_ns)
    ov_un = snap_un.get("overlap_fraction")
    ov_bk = snap_bk.get("overlap_fraction")
    if ov_un is None or ov_bk is None:
        raise DrillFailure(
            f"tracer measured no overlap fraction (unbucketed={ov_un!r} "
            f"scheduled={ov_bk!r}) — collective spans missing?")
    if not ov_bk > ov_un:
        raise DrillFailure(
            f"scheduled overlap {ov_bk} not strictly above the "
            f"monolithic baseline {ov_un}")
    if not ov_bk > 0.5:
        raise DrillFailure(
            f"scheduled overlap {ov_bk} below the 0.5 bar")
    report = {
        "n_buckets": plan.n_buckets,
        "bucket_bytes": [b.nbytes for b in plan.buckets],
        "total_bytes": total_bytes,
        "schedule": sched.describe(),
        "mesh": {"dp": n_dp, "sharding": n_shard},
        "overlap_unbucketed": ov_un,
        "overlap_scheduled": ov_bk,
    }
    return _write_overlap_report(root, "sharded_overlap_report.json",
                                 report)


# -- serving chaos drill -----------------------------------------------------

def _http_post(url, obj, timeout=30.0):
    """Bounded JSON POST returning (status, body-text, headers); 4xx/5xx
    responses return their body instead of raising."""
    data = json.dumps(obj).encode("utf-8")
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read().decode("utf-8"), resp.headers
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode("utf-8"), e.headers


def spawn_serve_worker(*, root, name, spec, seed=0, request_timeout=60.0,
                       env_extra=None, log_path=None, spawn_timeout=240.0):
    """Launch the serving engine as a REAL subprocess
    (``python -m paddle_tpu.serving --spec ...``) and wait for it to
    build its AOT ladder and publish ``host:port`` into
    ``<root>/<name>.endpoint``.  Returns ``(Popen, (host, port))``;
    registered for :func:`reap_all`."""
    port_file = os.path.join(root, f"{name}.endpoint")
    try:
        os.unlink(port_file)
    except FileNotFoundError:
        pass
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(env_extra or {})
    cmd = [sys.executable, "-m", "paddle_tpu.serving",
           "--spec", json.dumps(spec), "--seed", str(seed),
           "--port-file", port_file,
           "--request-timeout", str(request_timeout)]
    if log_path:
        with open(log_path, "ab") as out:
            p = subprocess.Popen(cmd, env=env, stdout=out,
                                 stderr=subprocess.STDOUT)
    else:
        p = subprocess.Popen(cmd, env=env, stdout=subprocess.DEVNULL,
                             stderr=subprocess.DEVNULL)
    _LIVE.add(p)

    def _published():
        if p.poll() is not None:
            raise DrillFailure(
                f"serve worker {name} died during startup "
                f"(rc {p.poll()})")
        return read_endpoint_file(port_file)

    try:
        # the endpoint lands only AFTER the AOT ladder finished
        # compiling, so this wait covers the whole cold start
        ep = wait_until(_published, spawn_timeout,
                        desc=f"serve worker {name} to publish its "
                             f"endpoint")
    except TimeoutError as e:
        raise DrillFailure(f"serve worker {name} never came up: {e}") \
            from e
    logger.info("serve worker %s pid %d at %s:%d", name, p.pid,
                ep[0], ep[1])
    return p, ep


def run_serve_chaos_drill(root, *, max_new=8, storm_requests=6,
                          request_timeout=60.0, gen_timeout=240.0,
                          log_dir=None):
    """End-to-end serving resilience drill against REAL engine
    subprocesses (``python -m paddle_tpu.serving``), with an in-process
    solo-decode oracle built from the same ModelSpec + seed:

     1. **SIGKILL mid-decode** — generation 1 is killed while /healthz
        shows active sequences; nothing survives it but the OS.
     2. **Relaunch recovers** — generation 2 rebuilds the AOT ladder
        from scratch, reports a consistent empty page pool, serves
        every prompt with tokens bit-identical to the oracle's solo
        decode, and books ZERO request-path compiles.
     3. **Deadline storm sheds, never breaks** — after a warm request
        seeds the throughput EWMA, ``storm_requests`` infeasible
        deadlines (``deadline_ms=0.001``) must ALL be refused with 429
        + ``Retry-After`` (shed, not queued), while an interleaved
        generous request still returns bit-identical tokens; the shed
        counter accounts for every refusal and the pool ends the storm
        with zero used/reserved pages.
     4. **Disconnecting client** — a caller that drops its socket
        mid-request is cancelled (``cause="disconnect"``) and its
        pages come back.
     5. **SIGTERM graceful drain** — in-flight requests submitted just
        before SIGTERM all complete with FULL token counts (no partial
        responses), a request posted during the drain window is
        refused 503 ``draining``, and the process exits 143.

    Returns a report dict; raises :class:`DrillFailure` on any broken
    invariant.
    """
    import threading

    spec = {"vocab_size": 128, "hidden": 64, "layers": 4, "heads": 2,
            "max_seq_len": 64}
    seed = 7
    prompts = [[3, 1, 4, 1, 5], [2, 7, 18, 28], [31, 41, 5, 9, 26, 53]]
    env_serve = {
        "PT_SERVE_BUCKETS": "2,4",
        "PT_SERVE_PREFILL_BUCKETS": "16",
        "PT_SERVE_KV_PAGES": "64",
        "PT_SERVE_PAGE_SIZE": "8",
        "PT_SERVE_DRAIN_S": "20",
    }

    def _log(name):
        return os.path.join(log_dir, name) if log_dir else None

    # ---- the oracle: same spec + seed, solo decode in-process -------
    from ...serving import (ModelSpec, ServeConfig, ServingEngine,
                            init_params)
    mspec = ModelSpec.from_dict(spec)
    # the oracle honors PT_SERVE_PRECISION so the bit-identity legs
    # hold at every fixed precision (the engine subprocesses inherit
    # the same env): int8 oracle vs int8 workers, never cross-precision
    cfg = ServeConfig(decode_buckets=(2, 4), prefill_buckets=(16,),
                      kv_pages=64, page_size=8,
                      precision=os.environ.get("PT_SERVE_PRECISION")
                      or "fp32")
    oracle_engine = ServingEngine(mspec, init_params(mspec, seed), cfg)
    oracle = [oracle_engine.generate([p], max_new_tokens=max_new)[0]
              for p in prompts]
    oracle_engine.scheduler.stop()

    report = {"oracle_lens": [len(t) for t in oracle]}

    def _healthz(base):
        status, body = _http_get(base + "/healthz", timeout=5.0)
        return status, json.loads(body)

    # ---- leg 1: SIGKILL mid-decode ----------------------------------
    p1, (h1, port1) = spawn_serve_worker(
        root=root, name="serve_gen1", spec=spec, seed=seed,
        request_timeout=request_timeout, env_extra=env_serve,
        log_path=_log("serve_gen1.log"), spawn_timeout=gen_timeout)
    base1 = f"http://{h1}:{port1}"

    def _fire(base, body, out):
        try:
            out.append(_http_post(base + "/v1/generate", body,
                                  timeout=request_timeout))
        except OSError as e:       # the SIGKILL resets these sockets
            out.append(("conn-error", str(e), None))

    def _until_active(base, at_least, n, body, out, desc):
        """Send ``n`` requests and wait until /healthz shows ``at_least``
        of them decoding.  The whole batch of a model this small comes
        and goes in a fraction of a second, between two looks of a poll
        on a busy machine: when every request has been answered and none
        was seen, the batch is sent again.  Returns every thread
        started."""
        def _start():
            batch = [threading.Thread(target=_fire, daemon=True,
                                      args=(base, body(i), out))
                     for i in range(n)]
            for t in batch:
                t.start()
            return batch

        started = _start()

        def _look():
            _status, health = _healthz(base)
            if (health.get("active_sequences", 0) or 0) >= at_least:
                return True
            if not any(t.is_alive() for t in started):
                started.extend(_start())
            return None

        wait_until(_look, gen_timeout / 4, desc=desc, max_delay=0.05)
        return started

    def _long(i):
        return {"tokens": prompts[i % len(prompts)], "max_new_tokens": 48}

    doomed = []
    threads = _until_active(
        base1, 1, 6, _long, doomed,
        "generation 1 to show active decode sequences")
    p1.kill()
    rc1 = p1.wait(timeout=30)
    _LIVE.discard(p1)
    if rc1 != -signal.SIGKILL:
        raise DrillFailure(
            f"generation 1 exited {rc1}, expected SIGKILL (-9)")
    for t in threads:
        t.join(timeout=request_timeout)
    report["gen1_rc"] = rc1

    # ---- leg 2: relaunch recovers, zero request-path compiles -------
    p2, (h2, port2) = spawn_serve_worker(
        root=root, name="serve_gen2", spec=spec, seed=seed,
        request_timeout=request_timeout, env_extra=env_serve,
        log_path=_log("serve_gen2.log"), spawn_timeout=gen_timeout)
    base2 = f"http://{h2}:{port2}"
    try:
        status, health = _healthz(base2)
        if status != 200 or not health.get("ok"):
            raise DrillFailure(
                f"relaunched engine unhealthy: {status} {health}")
        kv = health.get("kv") or {}
        if kv.get("used_pages") or kv.get("reserved_pages") \
                or not health.get("kv_consistent"):
            raise DrillFailure(
                f"relaunched page pool not a clean slate: {kv}")
        for i, prompt in enumerate(prompts):
            status, body, _hdrs = _http_post(
                base2 + "/v1/generate",
                {"tokens": prompt, "max_new_tokens": max_new},
                timeout=request_timeout)
            if status != 200:
                raise DrillFailure(
                    f"relaunched engine refused prompt {i}: "
                    f"{status} {body}")
            tokens = json.loads(body)["tokens"]
            if tokens != oracle[i]:
                raise DrillFailure(
                    f"prompt {i} after relaunch decoded {tokens}, "
                    f"oracle solo decode says {oracle[i]} — "
                    f"recovery broke bit-identity")
        _status, health = _healthz(base2)
        if health.get("unexpected_compiles"):
            raise DrillFailure(
                f"{health['unexpected_compiles']} request-path "
                f"compiles after relaunch — the AOT ladder has a hole")
        report["gen2_recovered"] = True

        # ---- leg 3: deadline storm sheds, never breaks --------------
        shed_429 = 0
        for _ in range(storm_requests):
            status, body, hdrs = _http_post(
                base2 + "/v1/generate",
                {"tokens": prompts[0], "max_new_tokens": 32,
                 "deadline_ms": 0.001},
                timeout=request_timeout)
            if status != 429:
                raise DrillFailure(
                    f"infeasible deadline answered {status} {body}, "
                    f"expected 429 (shed)")
            if json.loads(body).get("reason") != "deadline_infeasible":
                raise DrillFailure(
                    f"shed reason {body}, expected deadline_infeasible")
            if int(hdrs.get("Retry-After", 0)) < 1:
                raise DrillFailure(
                    "429 without a usable Retry-After header")
            shed_429 += 1
        # a generous request rides through the storm untouched
        status, body, _hdrs = _http_post(
            base2 + "/v1/generate",
            {"tokens": prompts[1], "max_new_tokens": max_new},
            timeout=request_timeout)
        if status != 200 or json.loads(body)["tokens"] != oracle[1]:
            raise DrillFailure(
                f"generous request during the storm: {status} {body}")
        _status, mbody = _http_get(base2 + "/metrics", timeout=5.0)
        from ...observability.aggregator import parse_prometheus_text
        fams = parse_prometheus_text(mbody)
        shed_metric = _sample_value(fams, "pt_serve_shed_total",
                                    reason="deadline_infeasible")
        if not shed_metric or shed_metric < storm_requests:
            raise DrillFailure(
                f"pt_serve_shed_total{{deadline_infeasible}} is "
                f"{shed_metric!r}, expected >= {storm_requests}")
        report["storm_shed"] = shed_429

        # ---- leg 4: a disconnecting client is cancelled -------------
        # fill the decode batch with long well-behaved requests first,
        # so the disconnectors' requests are still in flight (queued
        # or decoding) when the handler's socket watch looks — a tiny
        # model can otherwise finish before the first check
        import socket as _socket
        payload = json.dumps({"tokens": prompts[2],
                              "max_new_tokens": 48}).encode()

        def _walk_away():
            blocked = []
            blockers = _until_active(
                base2, 2, 4, _long, blocked,
                "blocker requests to fill the decode batch")
            for _ in range(3):      # three callers walk away mid-decode
                s = _socket.create_connection((h2, port2), timeout=5.0)
                s.sendall(b"POST /v1/generate HTTP/1.1\r\n"
                          b"Host: drill\r\n"
                          b"Content-Type: application/json\r\n"
                          + f"Content-Length: {len(payload)}\r\n\r\n"
                          .encode() + payload)
                s.close()
            for t in blockers:
                t.join(timeout=request_timeout)
            if any(status != 200 for status, _b, _h in blocked):
                raise DrillFailure(
                    f"blocker requests failed during the disconnect leg: "
                    f"{[(s, b) for s, b, _h in blocked]}")

        def _disconnect_seen():
            _s, mb = _http_get(base2 + "/metrics", timeout=5.0)
            v = _sample_value(parse_prometheus_text(mb),
                              "pt_serve_cancelled_total",
                              cause="disconnect")
            return True if v else None

        def _walked_away_and_seen():
            # the handler looks at its socket every 50 ms, and a model
            # this small can answer all seven requests inside one look:
            # the callers then walk away again
            if _disconnect_seen():
                return True
            _walk_away()
            return _disconnect_seen()

        wait_until(_walked_away_and_seen, gen_timeout / 4,
                   desc="disconnected client to be cancelled")

        def _pool_quiet():
            _s, health = _healthz(base2)
            kv = health.get("kv") or {}
            if kv.get("used_pages") == 0 and \
                    kv.get("reserved_pages") == 0:
                return True
            return None

        wait_until(_pool_quiet, gen_timeout / 4,
                   desc="page pool to return to baseline after the "
                        "storm (zero leaks)")
        report["disconnect_cancelled"] = True

        # ---- leg 5: SIGTERM graceful drain (exit 143) ---------------
        inflight = []
        dthreads = [
            threading.Thread(
                target=_fire, daemon=True,
                args=(base2,
                      {"tokens": prompts[i], "max_new_tokens": max_new},
                      inflight))
            for i in range(len(prompts))
        ]
        for t in dthreads:
            t.start()

        def _admitted():
            # count responses that already landed as admitted too: on a
            # fast host a request can finish before the last one is even
            # submitted, so instantaneous depth alone never reaches the
            # target and the wait would time out on a healthy server
            _s, health = _healthz(base2)
            depth = (health.get("active_sequences", 0) or 0) + \
                (health.get("queue_depth", 0) or 0)
            return True if depth + len(inflight) >= len(dthreads) else None

        wait_until(_admitted, gen_timeout / 4,
                   desc="drain-leg requests to be admitted")
        p2.send_signal(signal.SIGTERM)
        # the drain window: admission must already be closed while the
        # listener is still up (settle_s keeps it serving 503s); the
        # handler needs a beat to flip the draining flag
        time.sleep(0.1)
        status, body, _hdrs = _http_post(
            base2 + "/v1/generate",
            {"tokens": prompts[0], "max_new_tokens": max_new},
            timeout=request_timeout)
        if status != 503:
            raise DrillFailure(
                f"request during drain answered {status} {body}, "
                f"expected 503 (admission closed)")
        for t in dthreads:
            t.join(timeout=request_timeout)
        if len(inflight) != len(dthreads):
            raise DrillFailure(
                f"only {len(inflight)}/{len(dthreads)} drain-leg "
                f"responses arrived")
        for status, body, _hdrs in inflight:
            if status != 200:
                raise DrillFailure(
                    f"in-flight request cut short by the drain: "
                    f"{status} {body} — partial/failed response")
        # full-length AND bit-identical to the solo oracle: the drain
        # finished these requests, it did not truncate or corrupt them
        got = sorted(tuple(json.loads(body)["tokens"])
                     for _status, body, _hdrs in inflight)
        want = sorted(tuple(t) for t in oracle)
        if got != want:
            raise DrillFailure(
                f"drained responses {got} disagree with the solo "
                f"oracle {want} — partial or corrupted responses")
        rc2 = p2.wait(timeout=60)
        _LIVE.discard(p2)
        if rc2 != 143:
            raise DrillFailure(
                f"drained process exited {rc2}, expected 143 "
                f"(128 + SIGTERM)")
        report["drain_rc"] = rc2
        report["drain_responses"] = len(inflight)
    finally:
        if p2.poll() is None:
            p2.kill()
            p2.wait(timeout=30)
        _LIVE.discard(p2)
    return report


def run_supervisor_drill(root, *, scenario="worker-kill", world=2,
                         total_steps=6, kill_step=3, crash_rank=1,
                         max_restarts=3, restart_window=300.0,
                         quarantine_threshold=2, barrier_timeout=6.0,
                         store_deadline=20.0, gen_timeout=180.0,
                         log_dir=None):
    """Prove the self-healing supervisor end to end, on CPU, with real
    subprocesses.  Three scenarios:

    - ``worker-kill``: generation 0 carries a scripted mid-barrier
      SIGKILL of rank ``crash_rank`` at step ``kill_step``; the
      supervisor must relaunch the fleet at a fresh run id and the
      final checkpoint at ``total_steps`` must verify bit-for-bit
      against the replayed oracle — restart-then-resume loses nothing.
    - ``store-kill``: the fleet runs clean while the runner SIGKILLs
      the TCPStore MASTER mid-run; the supervisor's
      :class:`~..supervisor.StandbyStoreGuard` must promote the
      WAL-tailing standby and republish the endpoint, the workers must
      ride through with ZERO exits (no restart budget spent), and the
      promoted store must advertise generation >= 2.
    - ``crash-loop``: rank ``crash_rank`` crashes deterministically at
      ``kill_step`` every generation; the supervisor must exhaust the
      restart budget and raise
      :class:`~..supervisor.RestartBudgetExhausted` naming the rank
      and — because every failure correlates with that rank's data
      shard — the quarantined shard.

    Returns a report dict (supervision snapshot, final rcs, newest
    step, promotions/generation, exhaustion details).
    """
    from ..supervisor import (RestartBudgetExhausted, StandbyStoreGuard,
                              Supervisor)

    if scenario not in ("worker-kill", "store-kill", "crash-loop"):
        raise ValueError(f"unknown supervisor drill scenario {scenario!r}")
    ckpt_root = os.path.join(root, "ckpt")
    store_root = os.path.join(root, "store")
    os.makedirs(ckpt_root, exist_ok=True)
    os.makedirs(store_root, exist_ok=True)

    def _log(name):
        return os.path.join(log_dir, name) if log_dir else None

    guard = StandbyStoreGuard(store_root, log_dir=log_dir,
                              track=_LIVE.add)
    guard.start()
    final_rcs = {}

    def spawn(rank, w, run_id, generation):
        kill = None
        fail = None
        if scenario == "worker-kill" and generation == 0:
            kill = KillSpec("mid-barrier", kill_step, rank=crash_rank)
        if scenario == "crash-loop" and rank == crash_rank:
            fail = (kill_step, 1)
        return spawn_worker(
            rank, w, root=ckpt_root, total_steps=total_steps,
            run_id=run_id, barrier_timeout=barrier_timeout,
            endpoint_file=guard.endpoint_file,
            store_deadline=store_deadline, kill=kill, fail=fail,
            data_shard=f"shard-{rank}",
            log_path=_log(f"sup_{scenario}_g{generation}_rank{rank}.log"))

    sup = Supervisor(
        spawn, world, max_restarts=max_restarts,
        restart_window=restart_window,
        shard_of=lambda r: f"shard-{r}",
        quarantine_threshold=quarantine_threshold,
        grace=3.0 * barrier_timeout, store_guard=guard,
        generation_timeout=gen_timeout,
        run_id_prefix=f"supdrill-{uuid.uuid4().hex[:6]}")

    report = {"scenario": scenario}
    killer = None
    try:
        if scenario == "store-kill":
            # SIGKILL the master once the fleet is provably mid-run
            # (at least one step committed); the supervisor's watch
            # loop must promote while workers keep training
            import threading as _threading

            def _assassinate():
                try:
                    wait_until(
                        lambda: (_latest_step(ckpt_root) or 0) >= 1,
                        gen_timeout / 2,
                        desc="first committed step before master kill")
                    logger.info("supervisor drill: SIGKILLing store "
                                "master pid %d", guard.master.pid)
                    guard.kill_master()
                except BaseException:
                    logger.exception("store assassin failed")

            killer = _threading.Thread(target=_assassinate, daemon=True)
            killer.start()

        try:
            snap = sup.run()
            report["supervision"] = snap
            final_rcs = snap.get("final_rcs") or {}
        except RestartBudgetExhausted as e:
            report["supervision"] = sup.snapshot()
            report["exhausted"] = {"message": str(e), "rank": e.rank,
                                   "shard": e.shard, "cause": e.cause}
            if scenario != "crash-loop":
                raise DrillFailure(
                    f"{scenario}: restart budget unexpectedly "
                    f"exhausted: {e}") from e

        if killer is not None:
            killer.join(timeout=gen_timeout)

        latest = _latest_step(ckpt_root)
        report["latest"] = latest
        snap = report["supervision"]

        if scenario == "worker-kill":
            if any(rc != 0 for rc in final_rcs.values()):
                raise DrillFailure(
                    f"worker-kill: final generation rcs {final_rcs}, "
                    f"expected all 0")
            if snap["restarts_total"] < 1 or \
                    snap["restarts_by_cause"].get("killed", 0) < 1:
                raise DrillFailure(
                    f"worker-kill: supervisor booked no 'killed' "
                    f"restart: {snap['restarts_by_cause']}")
            if latest != total_steps:
                raise DrillFailure(
                    f"worker-kill: newest committed step {latest}, "
                    f"wanted {total_steps}")
            _verify_bit_for_bit(ckpt_root, latest)
        elif scenario == "store-kill":
            if any(rc != 0 for rc in final_rcs.values()):
                raise DrillFailure(
                    f"store-kill: worker exits {final_rcs}, expected "
                    f"all 0 — workers must ride through a promotion")
            if snap["restarts_total"] != 0:
                raise DrillFailure(
                    f"store-kill: {snap['restarts_total']} restarts "
                    f"booked; promotion must not cost worker restarts")
            if snap["promotions"] < 1:
                raise DrillFailure("store-kill: no promotion happened")
            probe = ResilientStore(endpoint_file=guard.endpoint_file,
                                   deadline=store_deadline)
            try:
                probe.get("store/generation", wait=False)
                gen = probe.generation
            finally:
                probe.close()
            report["generation"] = gen
            if gen is None or gen < 2:
                raise DrillFailure(
                    f"store-kill: promoted master advertises "
                    f"generation {gen}, expected >= 2")
            if latest != total_steps:
                raise DrillFailure(
                    f"store-kill: newest committed step {latest}, "
                    f"wanted {total_steps}")
            _verify_bit_for_bit(ckpt_root, latest)
        else:  # crash-loop
            ex = report.get("exhausted")
            if ex is None:
                raise DrillFailure(
                    "crash-loop: supervisor did not exhaust the "
                    "restart budget")
            if ex["rank"] != crash_rank:
                raise DrillFailure(
                    f"crash-loop: exhaustion names rank {ex['rank']}, "
                    f"expected {crash_rank}")
            if ex["shard"] != f"shard-{crash_rank}":
                raise DrillFailure(
                    f"crash-loop: exhaustion names shard "
                    f"{ex['shard']!r}, expected "
                    f"'shard-{crash_rank}' (data-correlated loop)")
            if f"rank {crash_rank}" not in ex["message"] or \
                    f"shard-{crash_rank}" not in ex["message"]:
                raise DrillFailure(
                    f"crash-loop: diagnostic does not name the rank "
                    f"and shard: {ex['message']!r}")
    finally:
        guard.stop()
        reap_all()
    return report
