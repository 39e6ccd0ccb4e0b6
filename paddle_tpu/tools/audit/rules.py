"""Graph-audit rules AUD001+ — jaxpr-level analyses.

Each rule is a class with an ``AUD0xx`` id registered in ``RULES`` and
a ``check(program) -> [Finding]`` method over an
:class:`~.core.AuditProgram`.  The catalog covers the hazard classes
tpu-lint cannot see from source (ROADMAP "remaining hazard classes"):

======  ===================  ==========================================
id      name                 what it catches
======  ===================  ==========================================
AUD001  implicit-reshard     a value constrained to one PartitionSpec
                             re-constrained to a different one through
                             layout-preserving ops — GSPMD must insert
                             an all-to-all / collective-permute the
                             source never spells out; also flags mesh
                             axes outside the ``SpecLayout`` canon
AUD002  amp-precision-leak   f32 ``dot_general``/reductions reachable
                             from bf16 values through an explicit
                             upcast with no accumulation contract —
                             the MXU runs full-precision silently
AUD003  undonated-buffer     a large argument with a same-shaped
                             output it could alias, dead after last
                             read yet not donated — double allocation,
                             byte-weighted via PR 14 memory_analysis
AUD004  host-transfer        callbacks/infeed/outfeed in the program —
                             the IR-level complement of TPU019; an
                             error on the serving request path
AUD006  dequant-placement    an int8→float dequantize whose result
                             reaches more than one ``dot_general`` —
                             XLA must materialize the full-precision
                             copy in HBM, forfeiting the int8 memory
                             win; an error in serve programs
======  ===================  ==========================================
"""
from __future__ import annotations

import os
from collections import Counter
from typing import List

import numpy as np

from .core import (AuditProgram, Finding, GraphView, audit_disabled_rules,
                   walk_jaxprs)
from .core import _is_literal as _is_lit

__all__ = ["RULES", "register", "Rule", "default_rules", "rule_catalog"]

RULES = {}


def register(cls):
    RULES[cls.id] = cls
    return cls


class Rule:
    """Base: subclasses set ``id``/``name``/``rationale`` and implement
    ``check``."""

    id = "AUD000"
    name = "base"
    rationale = ""

    def check(self, prog: AuditProgram) -> List[Finding]:
        raise NotImplementedError


def default_rules(select=None):
    """Instantiate the rule set: every registered rule, filtered by an
    explicit ``select`` iterable of ids and the lazily read
    ``PT_AUDIT_DISABLE`` knob."""
    disabled = audit_disabled_rules()
    picked = None if select is None else {s.upper() for s in select}
    if picked is not None:
        unknown = picked - set(RULES)
        if unknown:
            raise KeyError(
                f"unknown rule id(s) {sorted(unknown)} "
                f"(known: {', '.join(sorted(RULES))})")
    out = []
    for rid in sorted(RULES):
        if rid in disabled:
            continue
        if picked is not None and rid not in picked:
            continue
        out.append(RULES[rid]())
    return out


def rule_catalog():
    return [(rid, RULES[rid].name, RULES[rid].rationale)
            for rid in sorted(RULES)]


# ---------------------------------------------------------------------------
# shared jaxpr helpers
# ---------------------------------------------------------------------------
_NARROW = ("bfloat16", "float16")
_WIDE = ("float32", "float64")

# ops that forward a value without changing what a sharding spec or an
# upcast provenance means for it
_LAYOUT_TRANSPARENT = frozenset((
    "reshape", "broadcast_in_dim", "squeeze", "rev", "copy",
    "convert_element_type", "stop_gradient", "slice", "dynamic_slice",
))
_ELEMENTWISE = frozenset((
    "add", "sub", "mul", "div", "neg", "exp", "log", "tanh", "logistic",
    "rsqrt", "sqrt", "abs", "max", "min", "pow", "integer_pow", "sign",
    "erf", "select_n",
))


def _dtype_name(aval) -> str:
    return np.dtype(aval.dtype).name


def _aval_bytes(aval) -> int:
    try:
        return int(np.prod(aval.shape, dtype=np.int64)) * \
            np.dtype(aval.dtype).itemsize
    except Exception:
        return 0


def _spec_tuple(spec):
    """PartitionSpec -> hashable normalized tuple (strings/None/tuples)."""
    out = []
    for entry in tuple(spec):
        if isinstance(entry, (list, tuple)):
            out.append(tuple(str(a) for a in entry))
        else:
            out.append(None if entry is None else str(entry))
    return tuple(out)


def _spec_str(tup) -> str:
    def one(e):
        if e is None:
            return "None"
        if isinstance(e, tuple):
            return "(" + ",".join(e) + ")"
        return e
    return "P(" + ",".join(one(e) for e in tup) + ")"


def _spec_axes(tup):
    axes = set()
    for e in tup:
        if isinstance(e, tuple):
            axes.update(e)
        elif e is not None:
            axes.add(e)
    return axes


# ---------------------------------------------------------------------------
# AUD001 — implicit reshard
# ---------------------------------------------------------------------------
@register
class ImplicitReshard(Rule):
    id = "AUD001"
    name = "implicit-reshard"
    rationale = ("two conflicting sharding constraints on one value "
                 "chain make GSPMD materialize an all-to-all or "
                 "collective-permute the source never wrote; specs "
                 "should agree with the SpecLayout canon")

    # walking back through these cannot change which spec the value
    # wants — a transpose/dot DOES, so the walk stops there
    _WALK = _LAYOUT_TRANSPARENT | _ELEMENTWISE

    def _canon_axes(self):
        from ...distributed.auto_parallel.spec_layout import SpecLayout
        lo = SpecLayout()
        return {lo.data_axis, lo.fsdp_axis, lo.tp_axis, lo.sep_axis}

    def check(self, prog: AuditProgram) -> List[Finding]:
        findings: List[Finding] = []
        canon = None
        for jaxpr, _path in walk_jaxprs(prog.jaxpr):
            cons = [(i, e) for i, e in enumerate(jaxpr.eqns)
                    if e.primitive.name == "sharding_constraint"]
            if not cons:
                continue
            g = GraphView(jaxpr)
            spec_of = {}                      # constrained outvar -> spec
            for i, eqn in cons:
                spec = getattr(eqn.params.get("sharding"), "spec", None)
                if spec is None:
                    continue
                spec_of[eqn.outvars[0]] = _spec_tuple(spec)
            for i, eqn in cons:
                spec = getattr(eqn.params.get("sharding"), "spec", None)
                if spec is None:
                    continue
                here = _spec_tuple(spec)
                if canon is None:
                    canon = self._canon_axes()
                alien = _spec_axes(here) - canon
                if alien:
                    findings.append(Finding(
                        rule=self.id, severity="warning",
                        program=prog.name,
                        provenance=f"axis[{','.join(sorted(alien))}]",
                        message=(f"constraint {_spec_str(here)} uses mesh "
                                 f"axes {sorted(alien)} outside the "
                                 "SpecLayout canon (dp/sharding/mp/sep) — "
                                 "a retargeted mesh must rename through "
                                 "SpecLayout, not ad-hoc specs")))
                seen, frontier, hops = set(), [eqn.invars[0]], 0
                while frontier and hops < 64:
                    hops += 1
                    v = frontier.pop()
                    if id(v) in seen:
                        continue
                    seen.add(id(v))
                    up = spec_of.get(v)
                    if up is not None and up != here \
                            and tuple(v.aval.shape) == \
                            tuple(eqn.invars[0].aval.shape):
                        findings.append(Finding(
                            rule=self.id, severity="error",
                            program=prog.name,
                            provenance=(f"reshard[{_spec_str(up)}->"
                                        f"{_spec_str(here)}]"
                                        f"{v.aval.str_short()}"),
                            message=(f"value constrained to {_spec_str(up)} "
                                     f"is re-constrained to "
                                     f"{_spec_str(here)} with only "
                                     "layout-preserving ops between — "
                                     "GSPMD inserts an implicit "
                                     "all-to-all/collective-permute "
                                     "here")))
                        continue
                    pi = g.producer(v)
                    if pi is None:
                        continue
                    peqn = g.eqns[pi]
                    if peqn.primitive.name == "sharding_constraint" or \
                            peqn.primitive.name in self._WALK:
                        frontier.extend(
                            iv for iv in peqn.invars
                            if hasattr(iv, "aval") and not _is_lit(iv)
                            and (g.producer(iv) is not None
                                 or iv in spec_of))
        return findings


# ---------------------------------------------------------------------------
# AUD002 — AMP precision leak
# ---------------------------------------------------------------------------
@register
class AmpPrecisionLeak(Rule):
    id = "AUD002"
    name = "amp-precision-leak"
    rationale = ("an f32 dot_general fed by explicit bf16→f32 upcasts "
                 "runs the MXU at full precision; the sanctioned form "
                 "is bf16 operands with preferred_element_type=f32. "
                 "A dedicated upcast feeding one wide reduction whose "
                 "result never narrows again is the same leak on the "
                 "reduction path")

    _REDUCES = frozenset(("reduce_sum", "reduce_max", "reduce_min",
                          "reduce_prod"))

    @staticmethod
    def _upcast_from_narrow(g: GraphView, v, max_hops: int = 16):
        """Name of the narrow dtype this wide value was explicitly
        upcast from (walking layout-preserving ops), else None."""
        hops = 0
        while hops < max_hops:
            hops += 1
            pi = g.producer(v)
            if pi is None:
                return None
            eqn = g.eqns[pi]
            prim = eqn.primitive.name
            if prim == "convert_element_type":
                src = eqn.invars[0]
                if hasattr(src, "aval") and \
                        _dtype_name(src.aval) in _NARROW and \
                        _dtype_name(v.aval) in _WIDE:
                    return _dtype_name(src.aval)
                v = src
                continue
            if prim in _LAYOUT_TRANSPARENT:
                v = eqn.invars[0]
                continue
            return None
        return None

    def check(self, prog: AuditProgram) -> List[Finding]:
        findings: List[Finding] = []
        for jaxpr, _path in walk_jaxprs(prog.jaxpr):
            g = None
            for eqn in jaxpr.eqns:
                prim = eqn.primitive.name
                if prim == "dot_general":
                    lhs, rhs = eqn.invars[0], eqn.invars[1]
                    if not (hasattr(lhs, "aval") and hasattr(rhs, "aval")):
                        continue
                    if _dtype_name(lhs.aval) not in _WIDE and \
                            _dtype_name(rhs.aval) not in _WIDE:
                        continue
                    if g is None:
                        g = GraphView(jaxpr)
                    src = None
                    for op in (lhs, rhs):
                        if _dtype_name(op.aval) in _WIDE:
                            src = self._upcast_from_narrow(g, op)
                            if src:
                                break
                    if src:
                        findings.append(Finding(
                            rule=self.id, severity="error",
                            program=prog.name,
                            provenance=(f"dot_general[{lhs.aval.str_short()}"
                                        f"x{rhs.aval.str_short()}<-{src}]"),
                            message=(f"wide dot_general fed by an explicit "
                                     f"{src} upcast — keep operands {src} "
                                     "and set preferred_element_type for "
                                     "the f32 accumulation contract")))
                elif prim in self._REDUCES:
                    opnd = eqn.invars[0]
                    if not hasattr(opnd, "aval") or \
                            _dtype_name(opnd.aval) not in _WIDE:
                        continue
                    if g is None:
                        g = GraphView(jaxpr)
                    pi = g.producer(opnd)
                    if pi is None:
                        continue
                    peqn = g.eqns[pi]
                    if peqn.primitive.name != "convert_element_type":
                        continue
                    src = peqn.invars[0]
                    if not hasattr(src, "aval") or \
                            _dtype_name(src.aval) not in _NARROW:
                        continue
                    # a shared upcast is a deliberate f32 island (LN
                    # stats etc.); the leak is the dedicated upcast
                    # whose single purpose is this reduction
                    if g.sole_consumer(peqn.outvars[0]) is None:
                        continue
                    out = eqn.outvars[0]
                    sc = g.sole_consumer(out)
                    if sc is not None and \
                            g.eqns[sc].primitive.name == \
                            "convert_element_type" and \
                            _dtype_name(g.eqns[sc].outvars[0].aval) \
                            in _NARROW:
                        continue  # accumulate-then-narrow: contract held
                    findings.append(Finding(
                        rule=self.id, severity="warning",
                        program=prog.name,
                        provenance=(f"{prim}[{opnd.aval.str_short()}"
                                    f"<-{_dtype_name(src.aval)}]"),
                        message=(f"{prim} over a dedicated "
                                 f"{_dtype_name(src.aval)}→"
                                 f"{_dtype_name(opnd.aval)} upcast whose "
                                 "wide result never narrows again — "
                                 "either narrow the result or drop the "
                                 "upcast")))
        return findings


# ---------------------------------------------------------------------------
# AUD003 — donation audit
# ---------------------------------------------------------------------------
def _donation_min_bytes() -> int:
    """Lazy PT_AUDIT_DONATION_MIN_BYTES knob (default 1 MiB)."""
    try:
        return int(os.environ.get("PT_AUDIT_DONATION_MIN_BYTES",
                                  str(1 << 20)))
    except ValueError:
        return 1 << 20


@register
class UndonatedBuffer(Rule):
    id = "AUD003"
    name = "undonated-buffer"
    rationale = ("an argument with a same-shaped same-dtype output it "
                 "could alias, yet not donated, forces XLA to hold "
                 "both buffers live across the program — state "
                 "threading (params in → params out) must donate")

    def check(self, prog: AuditProgram) -> List[Finding]:
        jaxpr = getattr(prog.jaxpr, "jaxpr", prog.jaxpr)
        min_bytes = _donation_min_bytes()
        out_budget = Counter()
        for ov in jaxpr.outvars:
            if hasattr(ov, "aval") and hasattr(ov.aval, "shape"):
                out_budget[(tuple(ov.aval.shape),
                            _dtype_name(ov.aval))] += 1
        # donated args claim their aliasing opportunity first
        for i, iv in enumerate(jaxpr.invars):
            if i in prog.donated and hasattr(iv, "aval"):
                sig = (tuple(iv.aval.shape), _dtype_name(iv.aval))
                if out_budget.get(sig, 0) > 0:
                    out_budget[sig] -= 1
        candidates = [(i, iv) for i, iv in enumerate(jaxpr.invars)
                      if i not in prog.donated and hasattr(iv, "aval")
                      and _aval_bytes(iv.aval) >= min_bytes]
        # biggest buffers claim the remaining aliases first: the report
        # leads with the bytes that matter
        candidates.sort(key=lambda p: -_aval_bytes(p[1].aval))
        arg_total = (prog.memory or {}).get("argument", 0)
        findings = []
        for i, iv in candidates:
            sig = (tuple(iv.aval.shape), _dtype_name(iv.aval))
            if out_budget.get(sig, 0) <= 0:
                continue
            out_budget[sig] -= 1
            nbytes = _aval_bytes(iv.aval)
            ctx = (f" (program argument footprint "
                   f"{arg_total / 2**20:.1f} MiB)") if arg_total else ""
            findings.append(Finding(
                rule=self.id, severity="warning", program=prog.name,
                provenance=(f"undonated[{prog.arg_name(i)}:"
                            f"{iv.aval.str_short()}]"),
                message=(f"argument {prog.arg_name(i)} "
                         f"({iv.aval.str_short()}, "
                         f"{nbytes / 2**20:.1f} MiB) has a same-shaped "
                         "output it could alias but is not donated — "
                         "XLA holds both buffers live" + ctx),
                nbytes=nbytes))
        return findings


# ---------------------------------------------------------------------------
# AUD004 — host transfer / request-path effects
# ---------------------------------------------------------------------------
@register
class HostTransfer(Rule):
    id = "AUD004"
    name = "host-transfer"
    rationale = ("callbacks/infeed/outfeed round-trip through the host "
                 "every execution; on the serving request path that is "
                 "a per-token stall — the IR-level complement of "
                 "tpu-lint TPU019")

    _HOST_PRIMS = frozenset(("pure_callback", "io_callback",
                             "debug_callback", "debug_print", "infeed",
                             "outfeed"))

    def check(self, prog: AuditProgram) -> List[Finding]:
        severity = "error" if prog.kind == "serve" else "warning"
        findings = []
        for jaxpr, path in walk_jaxprs(prog.jaxpr):
            for eqn in jaxpr.eqns:
                prim = eqn.primitive.name
                if prim not in self._HOST_PRIMS:
                    continue
                cb = eqn.params.get("callback")
                cb_name = "" if cb is None else \
                    (getattr(cb, "__name__", "") or type(cb).__name__)
                where = f" inside {path}" if path else ""
                res = eqn.outvars[0].aval.str_short() \
                    if eqn.outvars and hasattr(eqn.outvars[0], "aval") \
                    else "()"
                findings.append(Finding(
                    rule=self.id, severity=severity, program=prog.name,
                    provenance=f"{prim}[{res}]",
                    message=(f"{prim}"
                             + (f" ({cb_name})" if cb_name else "")
                             + f"{where} forces a host round-trip every "
                             "execution"
                             + (" — on the serving request path this "
                                "stalls every token"
                                if prog.kind == "serve" else ""))))
        return findings


# ---------------------------------------------------------------------------
# AUD006 — dequant placement
# ---------------------------------------------------------------------------
@register
class DequantPlacement(Rule):
    id = "AUD006"
    name = "dequant-placement"
    rationale = ("an int8→float convert_element_type feeding more than "
                 "one dot_general forces XLA to materialize the "
                 "dequantized copy in HBM and keep it live across every "
                 "consumer — the int8 storage win is forfeited exactly "
                 "where it was supposed to pay; dequantize per use site "
                 "(one convert, one dot) so the upcast fuses into the "
                 "dot it feeds, the w8a16_matmul_reference form")

    _QUANT = frozenset(("int8", "uint8", "int4", "uint4"))
    # ops a dequantized value flows through without the copy stopping
    # being "the dequantized copy" — the scale multiply and gathers of
    # the reference kernels live here
    _FOLLOW = _LAYOUT_TRANSPARENT | _ELEMENTWISE | frozenset((
        "transpose", "concatenate", "gather", "dynamic_slice"))

    def _dot_fanout(self, g: GraphView, v, max_nodes: int = 256) -> int:
        """Distinct dot_generals reachable from ``v`` through
        value-forwarding ops."""
        dots, seen, frontier, n = set(), set(), [v], 0
        while frontier and n < max_nodes:
            n += 1
            u = frontier.pop()
            if id(u) in seen:
                continue
            seen.add(id(u))
            for ci in g.consumers.get(u, ()):
                if ci == g.OUT:
                    continue
                eqn = g.eqns[ci]
                prim = eqn.primitive.name
                if prim == "dot_general":
                    dots.add(ci)
                elif prim in self._FOLLOW:
                    frontier.extend(eqn.outvars)
        return len(dots)

    def check(self, prog: AuditProgram) -> List[Finding]:
        severity = "error" if prog.kind == "serve" else "warning"
        findings: List[Finding] = []
        for jaxpr, path in walk_jaxprs(prog.jaxpr):
            g = None
            for eqn in jaxpr.eqns:
                if eqn.primitive.name != "convert_element_type":
                    continue
                src, out = eqn.invars[0], eqn.outvars[0]
                if not (hasattr(src, "aval") and hasattr(out, "aval")):
                    continue
                if _dtype_name(src.aval) not in self._QUANT:
                    continue
                if not np.issubdtype(np.dtype(out.aval.dtype),
                                     np.floating):
                    continue
                if g is None:
                    g = GraphView(jaxpr)
                dots = self._dot_fanout(g, out)
                if dots <= 1:
                    continue
                where = f" inside {path}" if path else ""
                findings.append(Finding(
                    rule=self.id, severity=severity, program=prog.name,
                    provenance=(f"dequant[{src.aval.str_short()}->"
                                f"{_dtype_name(out.aval)}x{dots}]"),
                    message=(f"dequantized {src.aval.str_short()} feeds "
                             f"{dots} dot_generals{where} — XLA holds "
                             "the full-precision copy live across all "
                             "of them; dequantize per dot (one convert "
                             "per use) so the upcast fuses into the "
                             "dot's operand read")))
        return findings
