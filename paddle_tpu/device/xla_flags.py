"""XLA latency-hiding-scheduler / async-collective enablement.

The compute↔communication overlap built into the train step (bucketed
gradient reductions placed mid-backward, double-buffered pipeline hops —
``distributed/grad_buckets.py``, ``fleet/meta_parallel/pp_spmd.py``)
only pays off if XLA's scheduler is allowed to run collectives
asynchronously under compute. On TPU that is the latency-hiding
scheduler plus the async-collective/collective-fusion passes; they are
process-level compiler flags, not per-program options, so they must be
staged before the backend initializes.

``enable_overlap_flags()`` is called by the hybrid entry points (fleet
init, the MULTICHIP dryrun, bench) and is safe to call any time: it is
idempotent, never overrides a flag the operator already set, and warns
instead of lying when the backend is already up.

The flags are libtpu's, so they travel in ``LIBTPU_INIT_ARGS`` — read by
libtpu alone, inert on a host without one — and NEVER in ``XLA_FLAGS``:
that variable is parsed by jaxlib's own XLA build, which does not
register the TPU names and aborts the process at backend init on any it
does not know (``parse_flags_from_env.cc``; seen on the v5e, PR 21).

Env controls:
 - ``PT_XLA_OVERLAP_FLAGS=0`` — disable entirely (the helper becomes a
   no-op returning []).
 - ``PT_XLA_OVERLAP_EXTRA`` — extra space-separated libtpu flags
   appended after the defaults (operator escape hatch for
   per-generation tuning).
"""
from __future__ import annotations

import os
import warnings

__all__ = ["OVERLAP_XLA_FLAGS", "enable_overlap_flags",
           "overlap_flags_active"]

# Scheduler + async-collective set. The latency-hiding scheduler
# reorders independent collectives under compute; the async flags make
# each collective op non-blocking (start/done pair) so there is
# something to reorder. Names follow the xla repo's debug_options.
OVERLAP_XLA_FLAGS = (
    "--xla_tpu_enable_latency_hiding_scheduler=true",
    "--xla_enable_async_all_gather=true",
    "--xla_enable_async_collective_permute=true",
    "--xla_tpu_enable_async_collective_fusion=true",
    "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
    "--xla_tpu_enable_async_collective_fusion_multiple_steps=true",
    "--xla_tpu_overlap_compute_collective_tc=true",
)


def _flag_name(flag):
    return flag.split("=", 1)[0]


def _merge(env_value, flags):
    """Append flags whose NAME is not already present (operator wins)."""
    present = {_flag_name(f) for f in env_value.split() if f}
    added = [f for f in flags if _flag_name(f) not in present]
    merged = (env_value + " " + " ".join(added)).strip() if added \
        else env_value
    return merged, added


def _backend_initialized():
    # the public probe (jax.devices()) materializes the client, so ask
    # the lower-level registry instead
    from jax._src import xla_bridge
    return xla_bridge.backends_are_initialized()


def overlap_flags_active():
    """True when every overlap flag name is staged in
    ``LIBTPU_INIT_ARGS``."""
    present = {_flag_name(f)
               for f in os.environ.get("LIBTPU_INIT_ARGS", "").split() if f}
    return all(_flag_name(f) in present for f in OVERLAP_XLA_FLAGS)


def enable_overlap_flags(extra=(), warn_if_late=True):
    """Stage the overlap flag set in ``LIBTPU_INIT_ARGS`` (idempotent).

    Returns the list of flags newly added (empty when disabled via
    ``PT_XLA_OVERLAP_FLAGS=0`` or every name was already there). Flags
    the operator already pinned — in ``LIBTPU_INIT_ARGS`` or via
    ``PT_XLA_OVERLAP_EXTRA`` — are never overridden, only absent names
    are appended.
    """
    if os.environ.get("PT_XLA_OVERLAP_FLAGS", "auto") in ("0", "false",
                                                          "off"):
        return []
    extra_env = tuple(os.environ.get("PT_XLA_OVERLAP_EXTRA", "").split())
    flags = tuple(OVERLAP_XLA_FLAGS) + tuple(extra) + extra_env
    merged, added = _merge(os.environ.get("LIBTPU_INIT_ARGS", ""), flags)
    if not added:
        return []
    if _backend_initialized() and warn_if_late:
        warnings.warn(
            "enable_overlap_flags() called after the XLA backend "
            "initialized; the latency-hiding-scheduler flags will only "
            "take effect in processes that stage them before first device "
            "use (export LIBTPU_INIT_ARGS or call this at import time)",
            RuntimeWarning, stacklevel=2)
    os.environ["LIBTPU_INIT_ARGS"] = merged
    return added
