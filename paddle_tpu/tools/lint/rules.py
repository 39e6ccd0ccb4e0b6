"""tpu-lint rule catalog.

Every rule targets a concrete way Python code silently destroys TPU
throughput (or correctness) in a JAX-backed stack.  The catalog is the
distillation of the failure modes this repo has actually hit or guards
against — retrace storms, host round-trips in step loops, tracer leaks —
plus the classic ones the JAX docs warn about.

Rules are small classes with event hooks (``on_call``, ``on_if``,
``on_assign``, ``on_except``, ``on_while``, ``on_for``, ``on_with``);
the :class:`~.core.Linter` owns all traversal and scope state.  Register
new rules with :func:`register`.
"""
from __future__ import annotations

import ast
import re

from .core import dotted

__all__ = ["Rule", "register", "default_rules", "RULES", "rule_catalog"]

RULES: dict[str, type] = {}


def register(cls):
    """Class decorator adding a rule to the default registry."""
    RULES[cls.id] = cls
    return cls


class Rule:
    id = "TPU000"
    name = "abstract"
    rationale = ""


def default_rules(select=None):
    """Instantiate the registry (optionally only ``select`` rule ids)."""
    ids = sorted(RULES) if select is None else list(select)
    out = []
    for rid in ids:
        if rid not in RULES:
            raise KeyError(f"unknown rule id {rid!r} "
                           f"(known: {', '.join(sorted(RULES))})")
        out.append(RULES[rid]())
    return out


def rule_catalog():
    return [(rid, RULES[rid].name, RULES[rid].rationale)
            for rid in sorted(RULES)]


# -- shared predicates ------------------------------------------------------

_JIT_CONSTRUCTORS = {"jax.jit", "jit", "pjit", "jax.pjit",
                     "jax.experimental.pjit.pjit"}

# attribute reads on a tensor that are static under tracing (shape
# metadata is concrete even on tracers)
_SAFE_ATTRS = {"shape", "ndim", "dtype", "size", "sharding", "name"}
# calls whose result is host-static even when an arg is traced
_SAFE_CALLS = {"isinstance", "len", "hasattr", "getattr", "callable",
               "type", "id"}


def _is_jit_call(node: ast.Call) -> bool:
    name = dotted(node.func)
    if name in ("functools.partial", "partial") and node.args:
        name = dotted(node.args[0])
    return name in _JIT_CONSTRUCTORS


def _literal(node: ast.AST) -> bool:
    return isinstance(node, (ast.Constant, ast.List, ast.Tuple, ast.Dict,
                             ast.Set))


def _receiver_already_synced(recv: ast.AST, methods) -> bool:
    """True when the receiver expression is itself a host-sync call
    (``x.numpy().tolist()``) — the inner call carries the report."""
    return (isinstance(recv, ast.Call)
            and isinstance(recv.func, ast.Attribute)
            and recv.func.attr in methods)


def _hazard_params(expr: ast.AST, params: set) -> list:
    """Parameter references in ``expr`` whose *value* feeds truthiness.

    Skips statically-safe constructs: ``x is None``, ``isinstance(x, T)``,
    ``len(x)``, and metadata reads like ``x.shape[0] > 1``.
    """
    hits = []

    def walk(n, parent_attr=None):
        if isinstance(n, ast.Attribute):
            if n.attr in _SAFE_ATTRS:
                return  # x.shape / x.ndim / x.dtype — static
            walk(n.value)
            return
        if isinstance(n, ast.Call):
            if dotted(n.func) in _SAFE_CALLS:
                return
            for a in n.args:
                walk(a)
            for k in n.keywords:
                walk(k.value)
            walk(n.func)
            return
        if isinstance(n, ast.Compare):
            ops_safe = all(isinstance(o, (ast.Is, ast.IsNot, ast.In,
                                          ast.NotIn)) for o in n.ops)
            if ops_safe:
                return  # `x is None`, `k in d` — identity/containment
            walk(n.left)
            for c in n.comparators:
                walk(c)
            return
        if isinstance(n, ast.Name):
            if n.id in params:
                hits.append(n)
            return
        for c in ast.iter_child_nodes(n):
            walk(c)

    walk(expr)
    return hits


# -- the catalog ------------------------------------------------------------

@register
class JitInLoop(Rule):
    id = "TPU001"
    name = "jit-construction-in-hot-path"
    rationale = ("jax.jit/pjit called inside a loop or per forward call "
                 "builds a fresh cache entry every iteration — a retrace "
                 "storm that recompiles instead of reusing the program")

    def on_call(self, node, ctx):
        if not _is_jit_call(node):
            return
        # a decorator list is visited as part of the funcdef; a
        # decorator on a nested def inside a loop still retraces, so no
        # special-casing needed — position decides.
        if ctx.in_loop:
            ctx.report(node, self.id,
                       "jax.jit constructed inside a loop; hoist it out "
                       "so the compiled program is reused")
        elif ctx.in_forward():
            ctx.report(node, self.id,
                       "jax.jit constructed per call inside "
                       "forward/__call__; build once (e.g. in __init__) "
                       "and reuse")


@register
class TracedBool(Rule):
    id = "TPU002"
    name = "python-branch-on-traced-value"
    rationale = ("`if`/`while` on a traced tensor raises "
                 "TracerBoolConversionError under jit (or silently bakes "
                 "one branch in); use lax.cond/jnp.where/lax.while_loop")

    def _check(self, test, node, ctx, kind):
        fi = ctx.innermost_traced()
        if fi is None:
            return
        for ref in _hazard_params(test, fi.params):
            ctx.report(node, self.id,
                       f"python `{kind}` on traced value {ref.id!r} "
                       f"inside trace target {fi.name!r}; use lax.cond / "
                       f"jnp.where / lax.while_loop")
            return  # one report per statement is enough

    def on_if(self, node, ctx):
        self._check(node.test, node, ctx, "if")

    def on_while(self, node, ctx):
        self._check(node.test, node, ctx, "while")


@register
class HostSyncInForward(Rule):
    id = "TPU003"
    name = "host-sync-in-forward-or-kernel"
    rationale = ("`.item()`/`.numpy()`/np.asarray/float(tensor) in a "
                 "forward or op body blocks on device->host transfer every "
                 "call, serializing the pipeline (and crashes under jit)")

    _SYNC_METHODS = {"item", "numpy", "tolist", "__array__"}
    _NP_FUNCS = {"np.asarray", "np.array", "numpy.asarray", "numpy.array",
                 "jax.device_get", "device_get"}

    def _applicable(self, ctx):
        return (ctx.in_forward() or ctx.innermost_traced() is not None
                or (ctx.kernel_path and ctx.func_stack))

    def on_call(self, node, ctx):
        if not self._applicable(ctx):
            return
        name = dotted(node.func)
        if (isinstance(node.func, ast.Attribute)
                and node.func.attr in self._SYNC_METHODS):
            if _receiver_already_synced(node.func.value,
                                        self._SYNC_METHODS):
                return  # x.numpy().tolist(): one sync, one report
            ctx.report(node, self.id,
                       f".{node.func.attr}() forces a device->host sync "
                       f"in a hot path; keep the value on device "
                       f"(jnp ops accept 0-d arrays)")
            return
        if name in self._NP_FUNCS:
            if node.args and _literal(node.args[0]):
                return  # np.asarray([0, 1]) — host constant, no transfer
            ctx.report(node, self.id,
                       f"{name}() on a device value forces a host "
                       f"round-trip in a hot path; use jnp.asarray or "
                       f"keep the array on device")
            return
        # float(x)/int(x)/bool(x) directly on a forward/traced parameter
        if (name in ("float", "int", "bool") and node.args
                and isinstance(node.args[0], ast.Name)):
            fi = ctx.innermost_traced()
            owners = [f for f in ctx.func_stack
                      if f.is_forward or f is fi]
            if any(node.args[0].id in f.params for f in owners):
                ctx.report(node, self.id,
                           f"{name}() on tensor argument "
                           f"{node.args[0].id!r} synchronizes with the "
                           f"host (TracerConversion under jit)")


@register
class TracerLeak(Rule):
    id = "TPU004"
    name = "tracer-leak-via-side-effect"
    rationale = ("assigning to self.*/globals inside a jitted or traced "
                 "function leaks tracers out of the trace — a "
                 "UnexpectedTracerError later, or stale constants baked in")

    def on_assign(self, node, ctx):
        fi = ctx.innermost_traced()
        if fi is None:
            return
        targets = (node.targets if isinstance(node, ast.Assign)
                   else [node.target])
        for t in targets:
            for sub in ast.walk(t):
                if (isinstance(sub, ast.Attribute)
                        and isinstance(sub.value, ast.Name)
                        and sub.value.id == "self"):
                    ctx.report(node, self.id,
                               f"assignment to self.{sub.attr} inside "
                               f"trace target {fi.name!r} leaks a tracer; "
                               f"return the value instead")
                    return
                if (isinstance(sub, ast.Name)
                        and sub.id in fi.globals_decl):
                    ctx.report(node, self.id,
                               f"assignment to global {sub.id!r} inside "
                               f"trace target {fi.name!r} leaks a tracer")
                    return


@register
class BadStaticArgnums(Rule):
    id = "TPU005"
    name = "invalid-static-argnums"
    rationale = ("static_argnums must be hashable ints (and argnames "
                 "strings); strings/floats/tensors there either raise or "
                 "mark a tensor static, retracing on every distinct value")

    def on_call(self, node, ctx):
        if not _is_jit_call(node):
            return
        for kw in node.keywords:
            if kw.arg == "static_argnums":
                self._check_elems(
                    kw.value, node, ctx, want=int,
                    hint="index positions are ints; for names use "
                         "static_argnames")
            elif kw.arg == "static_argnames":
                self._check_elems(
                    kw.value, node, ctx, want=str,
                    hint="argument names are strings; for positions use "
                         "static_argnums")

    @staticmethod
    def _elems(value):
        if isinstance(value, (ast.Tuple, ast.List, ast.Set)):
            return value.elts
        return [value]

    def _check_elems(self, value, node, ctx, want, hint):
        for el in self._elems(value):
            if isinstance(el, ast.Constant):
                ok = isinstance(el.value, want) and not (
                    want is int and isinstance(el.value, bool))
                if not ok:
                    ctx.report(node, self.id,
                               f"non-{want.__name__} constant "
                               f"{el.value!r} in static_arg spec: {hint}")
            elif _literal(el):
                ctx.report(node, self.id,
                           f"unhashable literal in static_arg spec: "
                           f"{hint}")


@register
class ScanBodyMutation(Rule):
    id = "TPU006"
    name = "captured-mutation-in-scan-body"
    rationale = ("mutating a captured list/dict inside a lax.scan/"
                 "while_loop body runs once at trace time, not per step — "
                 "the mutation silently records only tracer garbage")

    _MUTATORS = {"append", "extend", "insert", "update", "pop", "popitem",
                 "setdefault", "remove", "clear", "add", "discard"}

    def _captured(self, name, ctx):
        fi = ctx.current_func
        return (fi is not None and fi.is_scan_body
                and name not in fi.local_stores)

    def on_call(self, node, ctx):
        f = node.func
        if (isinstance(f, ast.Attribute) and f.attr in self._MUTATORS
                and isinstance(f.value, ast.Name)
                and self._captured(f.value.id, ctx)):
            ctx.report(node, self.id,
                       f"{f.value.id}.{f.attr}() mutates a captured "
                       f"container inside a scan/while_loop body; carry "
                       f"it through the loop state instead")

    def on_assign(self, node, ctx):
        targets = (node.targets if isinstance(node, ast.Assign)
                   else [node.target])
        for t in targets:
            if (isinstance(t, ast.Subscript)
                    and isinstance(t.value, ast.Name)
                    and self._captured(t.value.id, ctx)):
                ctx.report(node, self.id,
                           f"subscript-assignment to captured "
                           f"{t.value.id!r} inside a scan/while_loop "
                           f"body; carry it through the loop state")


@register
class TransferInTrainLoop(Rule):
    id = "TPU007"
    name = "device-transfer-in-train-loop"
    rationale = ("jax.device_get/.numpy()/.item() every training step "
                 "stalls the device pipeline; sync once per logging "
                 "interval, or after the loop")

    _LOOP_FUNC = re.compile(r"(train|fit|epoch|run_steps?|step_loop)",
                            re.IGNORECASE)
    _SYNC_METHODS = {"numpy", "item", "tolist"}
    _SYNC_FUNCS = {"jax.device_get", "device_get", "np.asarray",
                   "numpy.asarray", "np.array", "numpy.array"}

    def on_call(self, node, ctx):
        if not ctx.in_loop:
            return
        if not any(self._LOOP_FUNC.search(fi.name)
                   for fi in ctx.func_stack):
            return
        name = dotted(node.func)
        hit = None
        if (isinstance(node.func, ast.Attribute)
                and node.func.attr in self._SYNC_METHODS):
            if _receiver_already_synced(node.func.value,
                                        self._SYNC_METHODS):
                return
            hit = f".{node.func.attr}()"
        elif name in self._SYNC_FUNCS:
            if node.args and _literal(node.args[0]):
                return
            hit = f"{name}()"
        if hit:
            ctx.report(node, self.id,
                       f"{hit} inside a training-step loop forces a "
                       f"device sync every iteration; hoist it out or "
                       f"sync on a logging interval")


@register
class SwallowedDistributedError(Rule):
    id = "TPU008"
    name = "swallowed-error-in-distributed-path"
    rationale = ("a bare/blanket except around collective or rendezvous "
                 "code turns one dead rank into a silent hang of every "
                 "other rank at the next barrier")

    _BLANKET = {"Exception", "BaseException"}

    def on_except(self, node, ctx):
        if not ctx.distributed_path:
            return
        if node.type is None:
            ctx.report(node, self.id,
                       "bare `except:` in distributed code swallows "
                       "everything incl. KeyboardInterrupt; catch the "
                       "specific failure and at least log it")
            return
        names = {dotted(t) for t in (
            node.type.elts if isinstance(node.type, ast.Tuple)
            else [node.type])}
        if names & self._BLANKET and self._trivial_body(node.body):
            ctx.report(node, self.id,
                       "`except Exception: pass` in distributed code "
                       "hides rank failures (peers hang at the next "
                       "collective); log the error or narrow the type")

    @staticmethod
    def _trivial_body(body):
        for stmt in body:
            if isinstance(stmt, ast.Pass):
                continue
            if isinstance(stmt, ast.Continue):
                continue
            if (isinstance(stmt, ast.Expr)
                    and isinstance(stmt.value, ast.Constant)):
                continue  # `...` or a lone docstring
            return False
        return True


@register
class RawSleepPollLoop(Rule):
    id = "TPU009"
    name = "raw-sleep-poll-loop"
    rationale = ("a bare time.sleep in a poll/retry loop in coordination "
                 "code wakes a whole restarted fleet in lockstep and "
                 "hammers the store; use utils.retry (retry_call / "
                 "wait_until) for jittered backoff with a deadline")

    _SLEEP_NAMES = {"time.sleep", "sleep", "_time.sleep"}

    def on_call(self, node, ctx):
        if not (ctx.distributed_path or ctx.core_path):
            return
        if not ctx.in_loop:
            return
        if dotted(node.func) in self._SLEEP_NAMES:
            ctx.report(node, self.id,
                       "raw sleep() in a poll/retry loop; use "
                       "utils.retry.retry_call/wait_until (jittered "
                       "backoff, deadline) or suppress if a fixed "
                       "cadence is genuinely wanted")


@register
class BarePrintInLibrary(Rule):
    id = "TPU010"
    name = "bare-print-in-library"
    rationale = ("print() in library code writes to stdout unconditionally"
                 " — it can't be filtered, rate-limited, or collected per"
                 " process, and it corrupts machine-read stdout (bench JSON"
                 " lines, launch protocols); route messages through"
                 " paddle_tpu.observability (get_logger / the event sink)."
                 " CLI entry points, tools/ and tests are exempt, as is"
                 " print(..., file=...) which targets a stream on purpose")

    def on_call(self, node, ctx):
        if not ctx.library_path:
            return
        if dotted(node.func) != "print":
            return
        if any(kw.arg == "file" for kw in node.keywords):
            return  # explicit stream choice (stderr protocols etc.)
        ctx.report(node, self.id,
                   "bare print() in paddle_tpu library code; use "
                   "observability.get_logger(__name__) (or emit a "
                   "structured event), or pass an explicit file=")


def _donate_spec(call: ast.Call):
    """Donated positions of a jit construction, or None if it donates
    nothing.  ``"all"`` when the spec is present but not a literal int
    tuple (donate_argnames, computed specs) — every positional arg is
    then treated as consumed."""
    for kw in call.keywords:
        if kw.arg not in ("donate_argnums", "donate_argnames"):
            continue
        v = kw.value
        if isinstance(v, ast.Constant) and isinstance(v.value, int) \
                and not isinstance(v.value, bool):
            return {v.value}
        if isinstance(v, (ast.Tuple, ast.List, ast.Set)):
            out = set()
            for el in v.elts:
                if (isinstance(el, ast.Constant)
                        and isinstance(el.value, int)
                        and not isinstance(el.value, bool)):
                    out.add(el.value)
                else:
                    return "all"
            return out
        return "all"
    return None


@register
class DonatedBufferReuse(Rule):
    id = "TPU011"
    name = "donated-buffer-reuse"
    rationale = ("an argument passed at a donate_argnums position is "
                 "invalidated by the call — XLA aliases its buffer into "
                 "the output — so reading it afterwards raises 'Array "
                 "has been deleted' (or reads reused memory on backends "
                 "that alias eagerly); rebind the name to the call's "
                 "output instead")

    # flow-sensitive, so the analysis is a private in-order scan of each
    # function body rather than the shared on_call/on_assign events
    # (which carry no statement-order state)
    def on_funcdef(self, node, ctx):
        st = ({}, {}, set())  # donating, consumed, reported node ids
        for stmt in node.body:
            self._stmt(stmt, st, ctx)

    def _stmt(self, s, st, ctx):
        if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.ClassDef)):
            return  # nested scopes get their own on_funcdef pass
        if isinstance(s, (ast.For, ast.AsyncFor)):
            self._simple(s.iter, st, ctx)
            self._clear_stores(s.target, st)
            # two passes over a loop body: the second catches
            # loop-carried reuse (f(params) every iteration with no
            # rebind donates an already-deleted buffer on iteration 2)
            for _ in (0, 1):
                for sub in s.body:
                    self._stmt(sub, st, ctx)
            for sub in s.orelse:
                self._stmt(sub, st, ctx)
            return
        if isinstance(s, ast.While):
            self._simple(s.test, st, ctx)
            for _ in (0, 1):
                for sub in s.body:
                    self._stmt(sub, st, ctx)
            for sub in s.orelse:
                self._stmt(sub, st, ctx)
            return
        if isinstance(s, ast.If):
            self._simple(s.test, st, ctx)
            for sub in s.body + s.orelse:
                self._stmt(sub, st, ctx)
            return
        if isinstance(s, (ast.With, ast.AsyncWith)):
            for item in s.items:
                self._simple(item, st, ctx)
            for sub in s.body:
                self._stmt(sub, st, ctx)
            return
        if isinstance(s, ast.Try):
            for sub in s.body:
                self._stmt(sub, st, ctx)
            for h in s.handlers:
                for sub in h.body:
                    self._stmt(sub, st, ctx)
            for sub in s.orelse + s.finalbody:
                self._stmt(sub, st, ctx)
            return
        self._simple(s, st, ctx)

    def _simple(self, s, st, ctx):
        donating, consumed, reported = st
        # consuming calls in this statement: a bound donating callable,
        # or a direct jax.jit(fn, donate_argnums=...)(args) invocation
        consuming = []
        for c in ast.walk(s):
            if not isinstance(c, ast.Call):
                continue
            spec = None
            if isinstance(c.func, ast.Call) and _is_jit_call(c.func):
                spec = _donate_spec(c.func)
            elif not _is_jit_call(c):
                name = dotted(c.func)
                if name:
                    spec = donating.get(name)
            if spec is not None:
                consuming.append((c, spec))
        # reads are checked against names consumed BEFORE this
        # statement, so a consuming call's own arguments only fire when
        # an earlier call already donated them
        for n in ast.walk(s):
            if (isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)
                    and n.id in consumed and id(n) not in reported):
                reported.add(id(n))
                line, callee = consumed[n.id]
                ctx.report(n, self.id,
                           f"{n.id!r} was donated to {callee}() at line "
                           f"{line} and its buffer is no longer valid; "
                           f"rebind the name to the call's output (or "
                           f"drop donate_argnums for this argument)")
        for c, spec in consuming:
            callee = dotted(c.func) or "a jitted callable"
            for pos, a in enumerate(c.args):
                if isinstance(a, ast.Name) and (spec == "all"
                                                or pos in spec):
                    consumed[a.id] = (c.lineno, callee)
        # stores AFTER consumption: `params = f(params)` rebinds the
        # name to the fresh output, clearing the hazard
        if isinstance(s, ast.Assign):
            v = s.value
            if isinstance(v, ast.Call) and _is_jit_call(v) \
                    and _donate_spec(v) is not None:
                for t in s.targets:
                    tname = dotted(t)
                    if tname:
                        donating[tname] = _donate_spec(v)
            for t in s.targets:
                self._clear_stores(t, st)
        elif isinstance(s, (ast.AugAssign, ast.AnnAssign)):
            self._clear_stores(s.target, st)
        elif isinstance(s, ast.Delete):
            for t in s.targets:
                self._clear_stores(t, st)

    @staticmethod
    def _clear_stores(target, st):
        _, consumed, _ = st
        for n in ast.walk(target):
            if isinstance(n, ast.Name):
                consumed.pop(n.id, None)


@register
class RawPallasCall(Rule):
    id = "TPU012"
    name = "raw-pallas-call-outside-ops"
    rationale = ("direct pl.pallas_call outside paddle_tpu/ops/ bypasses "
                 "the kernel dispatch layer — the use_pallas_kernels "
                 "flag, the platform gate (interpret mode off the TPU) "
                 "and the autotuner cache all live there; a raw call "
                 "site can't be switched off and runs with unsearched "
                 "launch configs. Wrap the kernel in paddle_tpu/ops/ "
                 "and dispatch through nn.functional")

    _PALLAS_CALLS = {"pl.pallas_call", "pallas_call",
                     "pallas.pallas_call",
                     "jax.experimental.pallas.pallas_call"}

    def on_call(self, node, ctx):
        if re.search(r"(^|/)paddle_tpu/ops(/|$)", ctx.path_posix):
            return
        if dotted(node.func) in self._PALLAS_CALLS:
            ctx.report(node, self.id,
                       "raw pallas_call outside paddle_tpu/ops/; move "
                       "the kernel into paddle_tpu/ops/ and route "
                       "callers through the dispatch layer (flag + "
                       "platform gate + autotuner)")


@register
class HostSyncInSpan(Rule):
    id = "TPU013"
    name = "host-sync-inside-open-trace-span"
    rationale = ("`.item()`/np.asarray/block_until_ready inside an open "
                 "RecordEvent / tracer phase span blocks the host while "
                 "the span clock runs — the span then measures the "
                 "device drain, not the work it names, poisoning phase "
                 "histograms and the overlap fraction; sync after the "
                 "span closes (spans must time dispatch, not transfers)")

    # `with RecordEvent("name"):` in any spelling, and the step
    # tracer's context managers: `with tr.phase("backward"):` /
    # `with tracer.span(...)`
    _SPAN_FUNCS = {"RecordEvent"}
    _SPAN_ATTRS = {"phase", "span"}
    _SYNC_METHODS = {"item", "numpy", "tolist", "__array__",
                     "block_until_ready"}
    _SYNC_FUNCS = {"np.asarray", "np.array", "numpy.asarray",
                   "numpy.array", "jax.device_get", "device_get",
                   "jax.block_until_ready", "block_until_ready"}

    def _opens_span(self, node):
        for item in node.items:
            ce = item.context_expr
            if not isinstance(ce, ast.Call):
                continue
            name = dotted(ce.func)
            if name in self._SPAN_FUNCS \
                    or name.rpartition(".")[2] in self._SPAN_FUNCS:
                return name or "RecordEvent"
            # attribute form survives non-name receivers
            # (get_tracer().phase(...)) that dotted() can't render
            if isinstance(ce.func, ast.Attribute) \
                    and ce.func.attr in self._SPAN_ATTRS:
                return name or f"<tracer>.{ce.func.attr}"
        return None

    def on_with(self, node, ctx):
        span = self._opens_span(node)
        if span is None:
            return
        for call, what in self._sync_calls(node.body):
            ctx.report(call, self.id,
                       f"{what} while the {span} span is open blocks "
                       f"the host inside the timed window; move the "
                       f"sync outside the span")

    def _sync_calls(self, body):
        hits = []

        def walk(n):
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda, ast.ClassDef)):
                return  # deferred execution — not inside the span
            if isinstance(n, ast.Call):
                name = dotted(n.func)
                if (isinstance(n.func, ast.Attribute)
                        and n.func.attr in self._SYNC_METHODS):
                    if not _receiver_already_synced(n.func.value,
                                                    self._SYNC_METHODS):
                        hits.append((n, f".{n.func.attr}()"))
                elif name in self._SYNC_FUNCS:
                    if not (n.args and _literal(n.args[0])):
                        hits.append((n, f"{name}()"))
            for c in ast.iter_child_nodes(n):
                walk(c)

        for stmt in body:
            walk(stmt)
        return hits


@register
class CollectiveInParamLoop(Rule):
    id = "TPU014"
    name = "unfused-collective-in-param-loop"
    rationale = ("a psum/all_reduce per parameter inside a Python loop "
                 "emits hundreds of latency-bound small collectives per "
                 "step — each pays the full ICI round-trip for a few KB; "
                 "flat-concat the group and reduce once per size-targeted "
                 "bucket (distributed/grad_buckets.py), which also gives "
                 "the latency-hiding scheduler one fusible op to overlap")

    # reduction-family collectives (jax.lax + this repo's wrappers);
    # matched on the last dotted component so `lax.psum`, `dist.
    # all_reduce` and bare `psum` all hit
    _COLLECTIVES = {"psum", "pmean", "psum_scatter", "all_reduce",
                    "all_gather", "reduce_scatter"}
    # the loop looks per-parameter: its target/iterable mentions
    # params/grads/weights (model.parameters(), grads.items(), ...)
    _PARAM_ITER = re.compile(
        r"(param|grad|weight|named_parameters|state_dict|\.values\(\))",
        re.IGNORECASE)

    def _per_param(self, node):
        try:
            text = ast.unparse(node.target) + " " + ast.unparse(node.iter)
        except Exception:
            return False
        return bool(self._PARAM_ITER.search(text))

    def on_for(self, node, ctx):
        if not ctx.library_path:
            return
        if not self._per_param(node):
            return
        for call, name in self._collective_calls(node.body):
            ctx.report(call, self.id,
                       f"{name}() per parameter in a Python loop; "
                       f"flat-concat the group and emit ONE reduction "
                       f"per bucket (distributed/grad_buckets.py "
                       f"partition_buckets/apply_bucketed_reduction)")

    def _collective_calls(self, body):
        hits = []

        def walk(n):
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda, ast.ClassDef)):
                return  # deferred execution — not per-iteration work
            if isinstance(n, ast.For) and self._per_param(n):
                return  # the nested loop's own on_for event reports it
            if isinstance(n, ast.Call):
                name = dotted(n.func)
                if name.rpartition(".")[2] in self._COLLECTIVES:
                    hits.append((n, name))
            for c in ast.iter_child_nodes(n):
                walk(c)

        for stmt in body:
            walk(stmt)
        return hits


@register
class AdHocPartitionSpecInModel(Rule):
    id = "TPU015"
    name = "ad-hoc-partitionspec-in-model-code"
    rationale = ("an inline PartitionSpec in model/bench code forks the "
                 "sharding layout from the canonical SpecLayout table "
                 "(distributed/auto_parallel/spec_layout.py) — a mesh-"
                 "axis rename or a layout fix then silently misses the "
                 "call site, and the Megatron pairing rules (column out-"
                 "dim + its bias over tp; row in-dim over tp, bias "
                 "replicated) stop being reviewable in one place; ask "
                 "the layout table for the role instead")

    # model/bench code — where layouts must come from the table. The
    # layout engine, train_step and the parallel-layer library are the
    # table's implementation/plumbing and stay free to build specs.
    _MODEL_PATHS = re.compile(
        r"((^|/)paddle_tpu/(incubate|vision)/models(/|$)"
        r"|(^|/)bench[^/]*\.py$)")
    _SPEC_CALLS = {"PartitionSpec", "P", "PS"}

    def on_call(self, node, ctx):
        if not self._MODEL_PATHS.search(ctx.path_posix):
            return
        name = dotted(node.func)
        if name.rpartition(".")[2] in self._SPEC_CALLS:
            ctx.report(node, self.id,
                       f"inline {name}(...) in model/bench code; take "
                       f"the spec from the canonical layout table "
                       f"(distributed/auto_parallel/spec_layout."
                       f"SpecLayout) so dp/fsdp/tp placements stay in "
                       f"one reviewable place")


@register
class UnfusedResidualNorm(Rule):
    id = "TPU016"
    name = "manually-composed-fusable-sequence"
    rationale = ("a residual add composed inline with a layer norm "
                 "(`ln(x + attn)`) materializes the sum as a separate HBM "
                 "round-trip; layer_norm and nn.LayerNorm take residual= "
                 "(fused_add_layer_norm is the named form), which feeds "
                 "the fused_layer_norm kernel's in-kernel add")

    # model-layer code where fusable sequences get hand-written; ops/
    # and the lint tool itself stay free to compose primitives
    _FUSABLE_PATHS = re.compile(
        r"(^|/)paddle_tpu/(nn|incubate/models)(/|$)")
    # a LayerNorm module bound on self/a module object: self.ln1, the
    # embedding's self.layer_norm, post_norm, ...
    _NORM_ATTR = re.compile(r"^((layer_?)?norm\d*|ln\d*)$", re.IGNORECASE)

    def _is_norm_call(self, node):
        name = dotted(node.func)
        last = name.rpartition(".")[2]
        if last == "layer_norm":
            return name or last
        # attribute form only for self-bound layers (self.ln1, self.
        # layer_norm) — jnp.linalg.norm and friends are not layer norms
        if (isinstance(node.func, ast.Attribute)
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id == "self"
                and self._NORM_ATTR.match(node.func.attr)):
            return name or node.func.attr
        return None

    @staticmethod
    def _is_add(expr):
        if isinstance(expr, ast.BinOp) and isinstance(expr.op, ast.Add):
            return True
        return (isinstance(expr, ast.Call)
                and dotted(expr.func).rpartition(".")[2] == "add")

    def on_call(self, node, ctx):
        if not self._FUSABLE_PATHS.search(ctx.path_posix):
            return
        name = self._is_norm_call(node)
        if name is None or not node.args:
            return
        if any(kw.arg == "residual" for kw in node.keywords):
            return  # already on the fused entry point
        if self._is_add(node.args[0]):
            ctx.report(node, self.id,
                       f"residual add composed inline with {name}(); "
                       f"pass the addend as residual= (or call "
                       f"fused_add_layer_norm) so the add+LN pair runs "
                       f"as one fused kernel")


@register
class DeviceArrayAccumulation(Rule):
    id = "TPU018"
    name = "device-array-accumulation-in-step-loop"
    rationale = ("appending per-step device results (losses, logits, "
                 "grads) to a Python container inside a training loop "
                 "pins every step's HBM buffer for the life of the list "
                 "— the run leaks device memory linearly in steps and "
                 "OOMs long after the step itself fits; convert to a "
                 "host scalar first (float(loss) / .item() — one sync "
                 "on the logging cadence) or let telemetry keep the "
                 "bounded history")

    # same scope gate as TPU007: only loops owned by a function whose
    # name says it is a training loop
    _LOOP_FUNC = re.compile(r"(train|fit|epoch|run_steps?|step_loop)",
                            re.IGNORECASE)
    _ACCUM_METHODS = {"append", "extend", "insert"}
    # host conversions that detach the value from device memory — an
    # accumulation wrapped in (or chained through) one of these is the
    # correct idiom, not a leak
    _HOST_CASTS = {"float", "int", "bool", "str", "np.asarray",
                   "np.array", "numpy.asarray", "numpy.array",
                   "jax.device_get", "device_get"}
    _SYNC_METHODS = {"item", "numpy", "tolist", "tobytes", "__array__"}
    # identifier components that name per-step device results; matched
    # as WHOLE dotted components so `step_times` / `lossy` never hit
    _DEVICE_NAMES = re.compile(
        r"^(steps?|train_step|model|net|forward|criterion|loss_fn|"
        r"loss(es)?|logits?|grads?|gradients?|preds?|predictions?|"
        r"outputs?|y_hat|activations?)$", re.IGNORECASE)

    def _in_step_loop(self, ctx):
        return any(self._LOOP_FUNC.search(fi.name)
                   for fi in ctx.func_stack)

    def on_for(self, node, ctx):
        if self._in_step_loop(ctx):
            self._scan(node.body, ctx)

    def on_while(self, node, ctx):
        if self._in_step_loop(ctx):
            self._scan(node.body, ctx)

    def _device_callee(self, call):
        """True when a call plausibly returns a device array: a step/
        model/loss-named callable or a jnp/jax.numpy op."""
        name = dotted(call.func)
        if name.startswith(("jnp.", "jax.numpy.")):
            return True
        return any(self._DEVICE_NAMES.match(part)
                   for part in name.split(".") if part)

    def _is_host_conversion(self, call):
        if dotted(call.func) in self._HOST_CASTS:
            return True
        return (isinstance(call.func, ast.Attribute)
                and call.func.attr in self._SYNC_METHODS)

    def _device_value(self, expr, device_names, host_names):
        """The device-ish thing accumulated by ``expr`` (a name), or
        None.  Host conversions prune the walk: float(loss) is safe,
        and so is a name rebound from one (`loss = float(raw)`)."""
        stack = [expr]
        while stack:
            n = stack.pop()
            if isinstance(n, ast.Call):
                if self._is_host_conversion(n):
                    continue  # converted to host — and its args with it
                if self._device_callee(n):
                    return f"{dotted(n.func)}()"
                stack.extend(n.args)
                stack.extend(kw.value for kw in n.keywords)
                continue
            if isinstance(n, ast.Name):
                if n.id in host_names:
                    continue
                if n.id in device_names \
                        or self._DEVICE_NAMES.match(n.id):
                    return n.id
                continue
            stack.extend(ast.iter_child_nodes(n))
        return None

    def _scan(self, body, ctx):
        # names bound to a device-call result earlier in THIS loop body
        # (`loss = step(x, y)`); any other rebind (host conversion,
        # constant) moves the name to the host set
        device_names = set()
        host_names = set()

        def walk(n):
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda, ast.ClassDef, ast.For,
                              ast.AsyncFor, ast.While)):
                return  # nested loops get their own on_for/on_while
            if isinstance(n, ast.Assign):
                names = [sub.id for t in n.targets
                         for sub in ast.walk(t)
                         if isinstance(sub, ast.Name)]
                if (isinstance(n.value, ast.Call)
                        and not self._is_host_conversion(n.value)
                        and self._device_callee(n.value)):
                    device_names.update(names)
                    host_names.difference_update(names)
                else:
                    device_names.difference_update(names)
                    host_names.update(names)
            if (isinstance(n, ast.Call)
                    and isinstance(n.func, ast.Attribute)
                    and n.func.attr in self._ACCUM_METHODS):
                for arg in n.args:
                    what = self._device_value(arg, device_names,
                                              host_names)
                    if what:
                        recv = dotted(n.func.value) or "container"
                        ctx.report(
                            n, self.id,
                            f"{recv}.{n.func.attr}({what}) accumulates "
                            f"a device array per step — every buffer "
                            f"stays live in HBM until the container "
                            f"dies; append float(x)/.item() on the "
                            f"logging cadence instead")
                        break
            for c in ast.iter_child_nodes(n):
                walk(c)

        for stmt in body:
            walk(stmt)


@register
class HostSideNanCheck(Rule):
    id = "TPU017"
    name = "host-side-nan-check"
    rationale = ("pulling a value to the host just to ask `isnan` — "
                 "math.isnan(float(loss)), np.isnan(x.numpy()), "
                 "bool(jnp.isnan(...)) — stalls the device pipeline "
                 "every step for a check the device can run for free; "
                 "fold the flag into the jitted step "
                 "(observability.numerics.health_outputs) and read it "
                 "asynchronously at a cadence "
                 "(NumericsMonitor.watch)")

    _NAN_FUNCS = {"isnan", "isinf", "isfinite"}
    _SYNC_METHODS = {"item", "numpy", "tolist", "__array__"}
    # host casts/transfers that force the device->host sync
    _SYNC_WRAPPERS = {"bool", "float", "int", "np.asarray", "np.array",
                      "numpy.asarray", "numpy.array", "jax.device_get",
                      "device_get"}
    # same scope gate as TPU007: library code, or any function whose
    # name says it is a training loop
    _LOOP_FUNC = re.compile(r"(train|fit|epoch|run_steps?|step_loop)",
                            re.IGNORECASE)

    def _applicable(self, ctx):
        return ctx.library_path or any(
            self._LOOP_FUNC.search(fi.name) for fi in ctx.func_stack)

    def _walk_calls(self, tree):
        """Call nodes under ``tree`` (itself included), skipping
        deferred-execution bodies."""
        stack = [tree]
        while stack:
            n = stack.pop()
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda, ast.ClassDef)):
                continue
            if isinstance(n, ast.Call):
                yield n
            stack.extend(ast.iter_child_nodes(n))

    def _has_nan_call(self, tree):
        return any(
            dotted(c.func).rpartition(".")[2] in self._NAN_FUNCS
            for c in self._walk_calls(tree))

    def _has_sync(self, tree):
        for c in self._walk_calls(tree):
            if (isinstance(c.func, ast.Attribute)
                    and c.func.attr in self._SYNC_METHODS):
                return True
            if dotted(c.func) in self._SYNC_WRAPPERS:
                return True
        return False

    def on_call(self, node, ctx):
        if not self._applicable(ctx):
            return
        name = dotted(node.func)
        # spelling 1: sync method chained onto the device-side check —
        # jnp.isnan(loss).item(), jnp.any(jnp.isnan(g)).numpy()
        if (isinstance(node.func, ast.Attribute)
                and node.func.attr in self._SYNC_METHODS
                and self._has_nan_call(node.func.value)):
            ctx.report(node, self.id,
                       f".{node.func.attr}() on a device-side nan/inf "
                       f"check syncs the host every call; compile the "
                       f"flag into the step (numerics.health_outputs) "
                       f"and read it at a cadence")
            return
        # spelling 2: host cast wrapped around the device-side check —
        # bool(jnp.any(~jnp.isfinite(g))), np.asarray(jnp.isnan(x))
        if name in self._SYNC_WRAPPERS and node.args:
            arg = node.args[0]
            # an inner sync already carries the report (spelling 1/3)
            if self._has_nan_call(arg) and not self._has_sync(arg):
                ctx.report(node, self.id,
                           f"{name}() around a device-side nan/inf "
                           f"check forces a blocking device->host sync; "
                           f"compile the flag into the step "
                           f"(numerics.health_outputs) and read it at "
                           f"a cadence")
            return
        # spelling 3: host-side check fed by an explicit sync —
        # math.isnan(float(loss)), np.isnan(x.numpy())
        if (name.rpartition(".")[2] in self._NAN_FUNCS
                and any(self._has_sync(a) for a in node.args)):
            ctx.report(node, self.id,
                       f"{name}() over a synced host value checks "
                       f"non-finiteness one device round-trip too "
                       f"late; compile the flag into the step "
                       f"(numerics.health_outputs) and read it at a "
                       f"cadence")


@register
class ImportTimeEnvRead(Rule):
    id = "TPU020"
    name = "env-read-at-import-time"
    rationale = ("os.environ read at module import time freezes the "
                 "value at whatever the environment held when the module "
                 "first loaded — exports made after import are silently "
                 "ignored, tests can't override the knob without a "
                 "module reload, and the launcher's per-worker env "
                 "injection races the import order; read the variable "
                 "lazily inside the function that needs it (the repo's "
                 "PT_* knobs all resolve at call time for this reason). "
                 "tools/, tests and CLI entry points are exempt")

    _ENV_CALLS = {"os.getenv", "getenv", "os.environ.get", "environ.get",
                  "os.environ.setdefault", "environ.setdefault"}
    _ENV_OBJS = {"os.environ", "environ"}

    def _applicable(self, node, ctx):
        # module scope only (class bodies included — they run at
        # import); function bodies are the lazy pattern we want
        if not ctx.library_path or ctx.func_stack:
            return False
        # a module-level `lambda: os.getenv(...)` defers the read — the
        # Linter doesn't push a scope for lambdas, so span-check here
        spans = getattr(ctx, "_tpu020_lambda_spans", None)
        if spans is None:
            spans = [(n.lineno, getattr(n, "end_lineno", n.lineno))
                     for n in ast.walk(ctx._tree)
                     if isinstance(n, ast.Lambda)]
            ctx._tpu020_lambda_spans = spans
        line = getattr(node, "lineno", 0)
        return not any(lo <= line <= hi for lo, hi in spans)

    def on_call(self, node, ctx):
        if not self._applicable(node, ctx):
            return
        name = dotted(node.func)
        if name in self._ENV_CALLS:
            ctx.report(node, self.id,
                       f"{name}() at module import time pins the value "
                       f"at first-load; resolve the variable lazily "
                       f"inside the function that uses it")

    def on_assign(self, node, ctx):
        # subscript reads (`X = os.environ["K"]`) aren't calls; catch
        # them on the assignment event
        if not self._applicable(node, ctx):
            return
        value = getattr(node, "value", None)
        if value is None:
            return
        for sub in ast.walk(value):
            if (isinstance(sub, ast.Subscript)
                    and isinstance(sub.ctx, ast.Load)
                    and dotted(sub.value) in self._ENV_OBJS):
                ctx.report(node, self.id,
                           f"{dotted(sub.value)}[...] read at module "
                           f"import time pins the value at first-load; "
                           f"resolve the variable lazily inside the "
                           f"function that uses it")
                return


@register
class RawQuantDtypeCast(Rule):
    id = "TPU022"
    name = "raw-quant-dtype-cast-outside-quant-layers"
    rationale = ("a bare astype(int8)/view(int8) outside paddle_tpu/ops/ "
                 "and paddle_tpu/quantization/ is a lossy cast with no "
                 "scale attached — astype saturates/wraps without "
                 "recording the absmax, view reinterprets bytes, and "
                 "either way the consumer can't dequantize; the "
                 "framework's quant numerics live in "
                 "ops/quant_kernels.py (quantize_weight/quantize_kv "
                 "return the int8 payload WITH its scale) and the "
                 "observer machinery in quantization/ — route casts "
                 "through them so every int8 tensor in flight carries "
                 "its dequant contract")

    _CAST_ATTRS = {"astype", "view"}
    _QUANT_DTYPES = {"int8", "int4", "uint4",
                     "float8_e4m3fn", "float8_e5m2"}
    # astype(uint8) is the image-pixel idiom (vision transforms) and
    # stays legal; view(uint8) is a byte reinterpretation and is not
    _VIEW_ONLY_DTYPES = {"uint8"}
    # the layers that OWN quant casts: the kernel/dispatch layer and the
    # observer/fake-quant machinery
    _EXEMPT = re.compile(r"(^|/)paddle_tpu/(ops|quantization)(/|$)")

    def _quant_dtype(self, node, allowed):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            return node.value if node.value in allowed else None
        name = dotted(node)
        if name.rpartition(".")[2] in allowed:
            return name
        return None

    def on_call(self, node, ctx):
        if not ctx.library_path or self._EXEMPT.search(ctx.path_posix):
            return
        f = node.func
        if not isinstance(f, ast.Attribute) or f.attr not in self._CAST_ATTRS:
            return
        allowed = self._QUANT_DTYPES if f.attr == "astype" \
            else self._QUANT_DTYPES | self._VIEW_ONLY_DTYPES
        dtype_exprs = list(node.args) + [kw.value for kw in node.keywords
                                         if kw.arg == "dtype"]
        for expr in dtype_exprs:
            dt = self._quant_dtype(expr, allowed)
            if dt:
                ctx.report(node, self.id,
                           f".{f.attr}({dt}) outside the quant layers "
                           f"drops the scale the int8 payload needs; use "
                           f"ops.quant_kernels.quantize_weight/"
                           f"quantize_kv (payload + scale together) or "
                           f"move the cast into paddle_tpu/ops/")
                return


@register
class RequestPathCompile(Rule):
    id = "TPU019"
    name = "request-path-compile"
    rationale = ("the serving engine's SLO contract is ZERO compiles on "
                 "the request path — every serveable shape is "
                 "AOT-compiled into the bucket ladder at engine load, "
                 "and any later compile books "
                 "pt_serve_unexpected_compiles_total and trips /healthz; "
                 "a jax.jit/pjit/lower() reachable from serving "
                 "request-handling code stalls a live request behind an "
                 "XLA compile (seconds, not microseconds) the first time "
                 "an unplanned shape arrives — move the compile into the "
                 "engine's build/warmup phase and extend the bucket "
                 "ladder instead")

    _JIT_NAMES = {"jax.jit", "jit", "pjit", "jax.pjit",
                  "jax.experimental.pjit.pjit"}
    # engine phases that are ALLOWED to compile: the AOT build/warmup
    # surface (ServingEngine._build_programs and friends)
    _BUILD_FUNC = re.compile(
        r"(build|warm|aot|compile|lower|export|program|canary|load|init)",
        re.IGNORECASE)

    def _in_build_phase(self, ctx):
        return any(self._BUILD_FUNC.search(fi.name)
                   for fi in ctx.func_stack)

    def on_call(self, node, ctx):
        if not ctx.serving_path or self._in_build_phase(ctx):
            return
        name = dotted(node.func)
        if name in self._JIT_NAMES:
            ctx.report(node, self.id,
                       f"{name}() on the serving request path compiles "
                       f"on first call and stalls a live request; "
                       f"AOT-compile it in the engine's "
                       f"_build_programs/warmup phase and serve from "
                       f"the bucket ladder")
            return
        # AOT entry points invoked outside the build phase:
        # jit(f).lower(...) chains, or .lower(...)/.aot_compile(...)
        # on a stored jitted callable.  str.lower() takes no
        # arguments, so an argumentful .lower(...) is an XLA lowering.
        if isinstance(node.func, ast.Attribute) and node.func.attr in (
                "lower", "aot_compile"):
            if node.args or node.keywords or (
                    isinstance(node.func.value, ast.Call)
                    and dotted(node.func.value.func) in self._JIT_NAMES):
                ctx.report(node, self.id,
                           f".{node.func.attr}() on the serving request "
                           f"path triggers XLA lowering+compilation "
                           f"mid-request; precompile every bucket shape "
                           f"at engine load (the zero-compile sentinel "
                           f"will book this as an SLO violation)")


@register
class UnboundedBlockingCall(Rule):
    id = "TPU021"
    name = "unbounded-blocking-call"
    rationale = ("a .join()/.wait()/.result()/.acquire() with no timeout "
                 "on a serving or distributed request path turns a hung "
                 "peer into a hung server: the caller blocks forever, "
                 "holds its KV pages/locks, and is indistinguishable "
                 "from load to everything upstream — the exact failure "
                 "the serve hang watchdog and drain budgets exist to "
                 "bound.  Pass a timeout (retry in a loop if the wait "
                 "is legitimately long) so a wedged dependency surfaces "
                 "as a timeout the resilience layer can act on instead "
                 "of an invisible stall")

    _BLOCKING = {"join", "wait", "result", "acquire"}
    _TIMEOUT_KWARGS = {"timeout", "timeout_s", "timeout_ms", "deadline"}

    def on_call(self, node, ctx):
        # request-path discipline only: serving/ and the distributed
        # control planes (fleet, collective, drill supervisors)
        if not (ctx.serving_path or ctx.distributed_path):
            return
        f = node.func
        if not isinstance(f, ast.Attribute) or f.attr not in self._BLOCKING:
            return
        # a positional arg (join(5), wait(0.1), acquire(False)) or an
        # explicit timeout/deadline kwarg bounds the call
        if node.args:
            return
        if any(kw.arg in self._TIMEOUT_KWARGS for kw in node.keywords):
            return
        if f.attr == "acquire" and any(
                kw.arg == "blocking" and isinstance(kw.value, ast.Constant)
                and kw.value.value is False for kw in node.keywords):
            return  # non-blocking acquire
        # wrapper deferral: `self.wait()` where this same file defines
        # a `wait` — the wrapper's own body gets linted instead, so a
        # bounded implementation isn't flagged at every internal call
        if dotted(f.value) == "self" and f.attr in ctx._pre.by_name:
            return
        ctx.report(node, self.id,
                   f".{f.attr}() with no timeout blocks this "
                   f"serving/distributed path forever if the other side "
                   f"is wedged; pass a timeout (looping if needed) so a "
                   f"hang surfaces as an actionable error")


@register
class SignalHandlerInLibrary(Rule):
    id = "TPU023"
    name = "signal-handler-in-library"
    rationale = ("signal.signal() registers a PROCESS-global handler — "
                 "there is exactly one disposition per signal, so a "
                 "library module installing one silently evicts the "
                 "owner's (the preemption checkpoint hook, the serving "
                 "drain handler, the launcher's fleet killer) and is "
                 "evicted in turn, which is how a preemption SIGTERM "
                 "stops saving checkpoints; handlers belong to process "
                 "OWNERS — the sanctioned entrypoints "
                 "(fleet/elastic/preemption.py, distributed/launch/, "
                 "serving/http.py's drain installer, the observability "
                 "aggregator's main) — and library code should raise, "
                 "return errors, or accept a callback instead")

    _SIGNAL_CALLS = {"signal.signal", "signal.sigaction", "_signal.signal"}
    # the process-owner surfaces that legitimately install handlers:
    # preemption hook, launcher entrypoints, the serving drain
    # installer, and the aggregator daemon's main
    _SANCTIONED = re.compile(
        r"(^|/)paddle_tpu/(fleet/elastic/preemption\.py"
        r"|distributed/fleet/elastic/preemption\.py"
        r"|distributed/launch/"
        r"|serving/http\.py"
        r"|observability/aggregator\.py)")

    def on_call(self, node, ctx):
        if not ctx.library_path or self._SANCTIONED.search(ctx.path_posix):
            return
        name = dotted(node.func)
        if name in self._SIGNAL_CALLS:
            ctx.report(node, self.id,
                       f"{name}() in library code evicts the process "
                       f"owner's handler (preemption save, serving "
                       f"drain, launcher kill); only the sanctioned "
                       f"entrypoints install handlers — accept a "
                       f"callback or surface an error instead")


@register
class HostNondeterminismInStep(Rule):
    id = "TPU024"
    name = "host-nondeterminism-in-captured-step"
    rationale = ("a nondeterministic host call (time.time(), module-"
                 "level random.*/np.random.* draws, os.urandom, "
                 "uuid.uuid4) inside a traced function is either baked "
                 "in as a compile-time constant (silently frozen at "
                 "first trace) or re-evaluated per step on the HOST — "
                 "and in both cases evaluates DIFFERENTLY on each dp "
                 "replica, so bit-identical replicas diverge without "
                 "any hardware fault and the SDC consensus fingerprint "
                 "vote fingers a healthy rank as corrupt; the same "
                 "hazard hides in host-side step/train loops when such "
                 "a call feeds a tensor constructor or PRNG key.  "
                 "Thread randomness in as a seeded, rank-agnostic "
                 "jax.random key (fold_in(key, step)) or an explicit "
                 "traced input instead")

    # exact nondeterministic host calls.  perf_counter/monotonic are
    # deliberately absent: timing reads are legitimate host telemetry
    # and never belong in tensors anyway — flagging them would bury
    # the signal
    _NONDET = {
        "time.time", "time.time_ns", "os.urandom",
        "uuid.uuid4", "uuid.uuid1",
        "datetime.now", "datetime.utcnow",
        "datetime.datetime.now", "datetime.datetime.utcnow",
    }
    # module-level stateful PRNG draws (random.random(), np.random.*):
    # the global generator's state differs across replicas
    _NONDET_PREFIXES = ("random.", "np.random.", "numpy.random.")
    # names under those prefixes that ARE the seeded discipline —
    # seeding calls and explicit-generator constructors
    _SEEDED_OK = {"seed", "RandomState", "default_rng", "Generator",
                  "get_state", "set_state"}
    # host-side training surfaces: a step/train-named function on the
    # call stack marks the per-step loop
    _STEP_FUNC = re.compile(r"(^|_)(step|train)(_|$)")
    # tensor sinks: a nondet call nested in these args crosses onto
    # the device and into the replicated state
    _SINKS = {"to_tensor", "array", "asarray", "full", "constant",
              "PRNGKey", "key", "fold_in", "seed"}

    def _is_nondet(self, name: str) -> bool:
        if name in self._NONDET:
            return True
        for p in self._NONDET_PREFIXES:
            if name.startswith(p):
                return name.rpartition(".")[2] not in self._SEEDED_OK
        return False

    def on_call(self, node, ctx):
        if not ctx.library_path:
            return
        name = dotted(node.func)
        if ctx.innermost_traced() is not None:
            # under a trace ANY nondeterministic host call is a replica-
            # divergence hazard, tensor-bound or not
            if self._is_nondet(name):
                ctx.report(node, self.id,
                           f"{name}() under jit/grad tracing is frozen "
                           f"at trace time (or re-runs per step on the "
                           f"host) with a DIFFERENT value on every dp "
                           f"replica — replicas diverge bit-for-bit and "
                           f"the SDC consensus vote fingers a healthy "
                           f"rank; pass it in as a traced input or "
                           f"derive it from a seeded key")
            return
        # host side: only step/train loops, and only when the nondet
        # value actually feeds a tensor sink — host-only uses (log
        # timestamps, run ids) are fine
        if name.rpartition(".")[2] not in self._SINKS:
            return
        if not any(self._STEP_FUNC.search(fi.name)
                   for fi in ctx.func_stack):
            return
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            for sub in ast.walk(arg):
                if (isinstance(sub, ast.Call)
                        and self._is_nondet(dotted(sub.func))):
                    src = dotted(sub.func)
                    ctx.report(node, self.id,
                               f"{src}() feeding {name}() in a "
                               f"step/train loop puts a per-replica-"
                               f"different host value into replicated "
                               f"tensor state — dp ranks diverge and "
                               f"the SDC sentry fingers one as corrupt; "
                               f"use a seeded jax.random key "
                               f"(fold_in(key, step)) or a shared "
                               f"traced input")
                    return
