"""Graph auditor core: findings over jaxpr-level programs.

tpu-lint (``tools/lint``) reads Python source; this package reads the
*lowered program* — the jaxprs the framework already produces for its
captured training steps (``jit/capture``) and AOT-served program
families (``serving/engine``).  The hazards it hunts (implicit
reshards, AMP precision leaks, undonated state buffers, request-path
host transfers, misplaced dequantizes) are invisible at the AST layer
because the compiler, not the source, decides them.

The machinery deliberately mirrors tpu-lint's conventions so one
mental model covers both gates:

 - a rule is a class with an ``AUD0xx`` id registered in ``RULES``
   (:mod:`.rules`);
 - a finding's :attr:`Finding.key` is content-addressed
   (``program::RULE::<provenance>``) and carries no eqn indices, so
   unrelated model edits never invalidate the committed baseline;
 - the baseline file is a multiset of keys diffed exactly like
   ``tools/lint/baseline.py`` does (that module is reused directly);
 - rules are suppressed per-run with ``--select`` / the lazily read
   ``PT_AUDIT_DISABLE`` env knob (the IR has no place to hang a
   ``# tpu-lint: disable=`` comment, so suppression is rule-level).

Nothing in this module executes the audited program: analysis is a
walk over equations of an already-traced jaxpr.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

from jax.extend import core as jcore

__all__ = ["Finding", "AuditProgram", "walk_jaxprs", "GraphView",
           "audit_disabled_rules", "run_rules", "sort_findings"]

_SEVERITIES = ("error", "warning")


# ---------------------------------------------------------------------------
# findings
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Finding:
    """One audit hit: program + rule + content-addressed provenance.

    ``provenance`` is a short, deterministic description of the
    offending site built from primitive names / avals / specs — never
    from eqn indices — so the baseline key survives unrelated edits to
    the model, exactly like tpu-lint's line-number-free keys.
    ``nbytes`` carries the byte weight where the rule has one (the
    donation audit), 0 otherwise.
    """

    rule: str
    severity: str
    program: str
    provenance: str
    message: str
    nbytes: int = 0

    @property
    def key(self) -> str:
        return f"{self.program}::{self.rule}::{self.provenance}"

    def render(self) -> str:
        mib = f" [{self.nbytes / 2**20:.1f} MiB]" if self.nbytes else ""
        return (f"{self.program}: {self.rule} [{self.severity}]"
                f"{mib} {self.message}")


def sort_findings(findings: Sequence[Finding]) -> List[Finding]:
    """Deterministic report order: program, rule, provenance."""
    return sorted(findings, key=lambda f: (f.program, f.rule,
                                           f.provenance, f.message))


# ---------------------------------------------------------------------------
# audited program
# ---------------------------------------------------------------------------
class AuditProgram:
    """One program under audit: a ClosedJaxpr plus the framework-side
    facts the rules need but the IR alone cannot supply.

    ``donated`` is the set of flat invar indices the caller donates
    (``jit(..., donate_argnums=...)`` resolved to leaf positions);
    ``arg_names`` optionally names those flat invars (pytree key paths)
    for readable donation findings; ``memory`` is the PR-14
    ``memory_analysis`` block (per-kind bytes) harvested beside the
    program, used to weight donation findings against the real argument
    footprint.
    """

    __slots__ = ("name", "jaxpr", "kind", "donated", "arg_names", "memory")

    def __init__(self, name: str, jaxpr: Any, kind: str = "generic",
                 donated: Sequence[int] = (),
                 arg_names: Optional[Sequence[str]] = None,
                 memory: Optional[Dict[str, Any]] = None):
        if kind not in ("capture", "serve", "generic"):
            raise ValueError(f"unknown program kind: {kind!r}")
        self.name = name
        self.jaxpr = jaxpr          # jax.core.ClosedJaxpr
        self.kind = kind
        self.donated = frozenset(int(i) for i in donated)
        self.arg_names = list(arg_names) if arg_names is not None else None
        self.memory = dict(memory) if memory else None

    def arg_name(self, i: int) -> str:
        if self.arg_names is not None and 0 <= i < len(self.arg_names):
            return self.arg_names[i]
        return f"arg{i}"


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------
def _inner_jaxprs(params: Dict[str, Any]) -> Iterator[Tuple[str, Any]]:
    """Yield (param_name, jaxpr) for every sub-jaxpr in eqn params —
    pjit bodies, remat bodies, scan/while/cond branches."""
    for k, v in params.items():
        vals = v if isinstance(v, (list, tuple)) else (v,)
        for item in vals:
            inner = getattr(item, "jaxpr", None)
            if inner is not None and hasattr(inner, "eqns"):
                yield k, inner          # ClosedJaxpr -> Jaxpr
            elif hasattr(item, "eqns"):
                yield k, item           # bare Jaxpr


def walk_jaxprs(closed, max_depth: int = 8):
    """Yield ``(jaxpr, path)`` for the top-level jaxpr and every nested
    sub-jaxpr (remat/pjit/scan/cond bodies), depth-first.  ``path`` is
    a ``/``-joined trail of the owning primitives, "" for the top level
    — provenance context only, never part of a baseline key."""
    top = getattr(closed, "jaxpr", closed)

    def _walk(jaxpr, path, depth):
        yield jaxpr, path
        if depth >= max_depth:
            return
        for eqn in jaxpr.eqns:
            for _, inner in _inner_jaxprs(eqn.params):
                sub = f"{path}/{eqn.primitive.name}" if path \
                    else eqn.primitive.name
                yield from _walk(inner, sub, depth + 1)

    yield from _walk(top, "", 0)


class GraphView:
    """Producer/consumer index over one jaxpr level."""

    OUT = -1

    def __init__(self, jaxpr):
        self.jaxpr = jaxpr
        self.eqns = jaxpr.eqns
        self.producer_idx: Dict[Any, int] = {}
        self.consumers: Dict[Any, List[int]] = {}
        for i, eqn in enumerate(self.eqns):
            for ov in eqn.outvars:
                self.producer_idx[ov] = i
            for iv in eqn.invars:
                if not _is_literal(iv):
                    self.consumers.setdefault(iv, []).append(i)
        for ov in jaxpr.outvars:
            if not _is_literal(ov):
                self.consumers.setdefault(ov, []).append(self.OUT)

    def producer(self, v) -> Optional[int]:
        if _is_literal(v):
            return None
        return self.producer_idx.get(v)

    def sole_consumer(self, v) -> Optional[int]:
        cons = self.consumers.get(v, [])
        if len(cons) != 1 or cons[0] == self.OUT:
            return None
        return cons[0]


def _is_literal(v) -> bool:
    return isinstance(v, jcore.Literal)


# ---------------------------------------------------------------------------
# rule selection
# ---------------------------------------------------------------------------
def audit_disabled_rules() -> set:
    """Rule ids disabled via ``PT_AUDIT_DISABLE`` (comma-separated),
    read lazily per run — the PR-3 lazy-knob contract."""
    raw = os.environ.get("PT_AUDIT_DISABLE", "")
    return {t.strip().upper() for t in raw.split(",") if t.strip()}


def run_rules(programs: Sequence[AuditProgram], rules) -> List[Finding]:
    """Apply every rule to every program; deterministic output order.
    A rule that raises poisons neither the run nor its siblings — the
    auditor must never take down a capture or an engine build — but the
    breakage is surfaced as a finding against the rule itself rather
    than swallowed."""
    findings: List[Finding] = []
    for prog in programs:
        for rule in rules:
            try:
                findings.extend(rule.check(prog))
            except Exception as e:  # analysis bug, not a program bug
                findings.append(Finding(
                    rule=rule.id, severity="warning", program=prog.name,
                    provenance="rule-error",
                    message=f"rule crashed: {type(e).__name__}: {e}"))
    return sort_findings(findings)
