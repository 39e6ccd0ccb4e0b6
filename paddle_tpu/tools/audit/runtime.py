"""Audit runtime: the capture/serving hook layer.

The auditor is OFF by default and costs one dict lookup per captured
signature when off.  Enabled (``PT_AUDIT=1`` read lazily, or
:func:`enable` programmatically — bench does the latter), it runs at
the two points where the framework already pays a compile:

 - ``jit/capture`` first replay: the captured step's jaxpr is
   audited once per signature, right after the
   FLOPs/memory harvests that share the same compile-time window.  The
   replay hot path never pays anything — the 1-compile contract the
   bench capture block pins is untouched.
 - ``serving/engine`` AOT build: every bucket executable's traced
   jaxpr is audited while the ladder compiles (load-time only).

Every finding books ``pt_audit_findings_total{rule,severity}`` and is
kept in a process-wide ledger that :func:`snapshot` renders as the
``audit`` block on bench records.
"""
from __future__ import annotations

import logging
import os
import threading
from typing import Any, Dict, List, Optional, Sequence

from .core import AuditProgram, Finding, run_rules
from .rules import default_rules

__all__ = ["audit_enabled", "enable", "reset", "audit_program",
           "audit_captured_step", "audit_serve_trace", "findings",
           "snapshot"]

logger = logging.getLogger("paddle_tpu.audit")

_FALSY = {"0", "false", "no", "off", ""}

_lock = threading.Lock()
_override: Optional[bool] = None
_findings: List[Finding] = []
_programs: List[str] = []
_metric = None
_metric_failed = False


def audit_enabled() -> bool:
    """Lazy PT_AUDIT knob (default off), overridable via :func:`enable`
    — the PR-3 lazy-env contract."""
    if _override is not None:
        return _override
    return os.environ.get("PT_AUDIT", "0").strip().lower() not in _FALSY


def enable(on: bool = True) -> None:
    global _override
    _override = bool(on)


def reset() -> None:
    """Clear the ledger and any programmatic enable (tests/bench)."""
    global _override
    with _lock:
        _override = None
        _findings.clear()
        _programs.clear()


def findings() -> List[Finding]:
    with _lock:
        return list(_findings)


def snapshot() -> Dict[str, Any]:
    """The ``audit`` block bench records carry: counts by rule and
    severity plus the audited program names — never the full messages
    (records stay one JSON line)."""
    with _lock:
        by_rule: Dict[str, int] = {}
        by_sev: Dict[str, int] = {}
        for f in _findings:
            by_rule[f.rule] = by_rule.get(f.rule, 0) + 1
            by_sev[f.severity] = by_sev.get(f.severity, 0) + 1
        return {
            "enabled": audit_enabled(),
            "programs": list(_programs),
            "findings": len(_findings),
            "by_rule": by_rule,
            "by_severity": by_sev,
        }


def _book(new: Sequence[Finding]) -> None:
    global _metric, _metric_failed
    if not new:
        return
    try:
        if _metric is None and not _metric_failed:
            from ...observability.metrics import get_registry
            _metric = get_registry().counter(
                "pt_audit_findings_total",
                "graph-audit findings booked at capture/serve compile "
                "time", ("rule", "severity"))
    except Exception:  # metrics are optional plumbing
        _metric_failed = True
    if _metric is not None:
        try:
            for f in new:
                _metric.inc(rule=f.rule, severity=f.severity)
        except Exception:
            pass


def audit_program(prog: AuditProgram) -> List[Finding]:
    """Run the default rule set over one program, book and ledger the
    findings.  Never raises — the auditor must not take down a capture
    or an engine build."""
    try:
        found = run_rules([prog], default_rules())
    except Exception:
        logger.debug("audit failed for %s", prog.name, exc_info=True)
        return []
    with _lock:
        _programs.append(prog.name)
        _findings.extend(found)
    _book(found)
    for f in found:
        logger.info("audit: %s", f.render())
    return found


# ---------------------------------------------------------------------------
# framework entry points
# ---------------------------------------------------------------------------
_ARG_LABELS_CAPTURE = ("params", "buffers", "opt_states", "rng_ctr",
                       "lrs", "traced")


def _flat_arg_names(args, labels) -> List[str]:
    """Flat invar names from pytree key paths: ``params['w']`` etc. —
    deterministic (dict insertion order), so donation provenance keys
    are stable across runs."""
    import jax
    names = []
    flat, _ = jax.tree_util.tree_flatten_with_path(tuple(args))
    for path, _leaf in flat:
        label = labels[path[0].idx] if path else "arg"
        names.append(label + jax.tree_util.keystr(path[1:]))
    return names


def audit_captured_step(entry, params, buffers, opt_states, rng_ctr,
                        lrs, traced) -> List[Finding]:
    """Audit one captured step at compile time: take the pure function's
    jaxpr (jax answers from the trace ``jit`` has just made of it) and
    run the rules.  Zero compiles, zero steady-state cost."""
    import jax
    pure = getattr(entry, "pure", None)
    if pure is None:
        return []
    try:
        args = (params, buffers, opt_states, rng_ctr, lrs, traced)
        closed = jax.make_jaxpr(pure)(*args)
        n_donated = len(jax.tree_util.tree_leaves(
            (params, buffers, opt_states)))
        prog = AuditProgram(
            name=entry.name, jaxpr=closed, kind="capture",
            donated=range(n_donated),
            arg_names=_flat_arg_names(args, _ARG_LABELS_CAPTURE),
            memory=entry.memory)
    except Exception:
        logger.debug("captured-step audit trace failed for %s",
                     getattr(entry, "name", "?"), exc_info=True)
        return []
    return audit_program(prog)


_ARG_LABELS_SERVE = ("params", "k_pool", "v_pool", "tokens",
                     "positions", "page_tables")


def audit_serve_trace(name: str, closed, n_params: int,
                      n_kv: int, args=None, labels=None) -> List[Finding]:
    """Audit one AOT serve program from its traced jaxpr.  Donation
    layout mirrors the engine's donate_argnums: the ``n_kv`` KV pool
    leaves (value pools, plus scale pools on a quantized ladder) right
    after the ``n_params`` weight leaves.  ``labels`` overrides the
    positional arg names when the engine's argument layout differs
    from the fp32 default (the int8 ladder inserts k_scale/v_scale)."""
    names = None
    if args is not None:
        try:
            names = _flat_arg_names(args, labels or _ARG_LABELS_SERVE)
        except Exception:
            names = None
    prog = AuditProgram(
        name=name, jaxpr=closed, kind="serve",
        donated=range(n_params, n_params + n_kv),
        arg_names=names)
    return audit_program(prog)
