"""Whole-step trace-and-cache capture: the eager fast path.

``@capture_step`` records ONE execution of a user's raw training-loop
body — Layer forward, loss, ``loss.backward()``, ``optimizer.step()`` —
and replays every subsequent call as a single jitted, donation-annotated
pure computation over (params, buffers, opt_state, rng counter, batch).
This is the paper's standalone-executor/dygraph-to-static story for
users who write their own loop instead of ``hapi.Model`` (ref:
``python/paddle/jit/api.py to_static`` + ``fluid/executor.py
_ExecutorCache``): the loop keeps its eager shape, the hardware sees one
XLA program per step.

How the one trace works: the tape stays ON while jax traces the user
function, so ``loss.backward()`` runs the ordinary autograd walk — each
``Node``'s lazy ``jax.vjp`` simply traces into the outer jit.
``optimizer.step()`` is intercepted by a capture hook (see
``Optimizer.step``) that applies the pure ``apply_gradients_tree``
update over the threaded opt-state pytree instead of the eager
per-param jits, so the step counter / lr are runtime arguments, never
baked constants.

Cache key: arg-tree structure + (shape, dtype, stop_gradient) per
tensor leaf + hashable non-tensor leaves + per-layer training mode.
Same shapes → replay with zero retrace (the recompile sentinel stays
quiet); a dtype/shape change compiles exactly one new entry.

Donation safety: at capture time the layer's current arrays are
device-copied into capture-private buffers; only those (and each call's
outputs, which nothing else references) are ever donated. The arrays
the caller held before capturing are never invalidated. Raw ``._data``
references taken BETWEEN captured calls die at the next call — the
hazard tpu-lint TPU011 flags.

Fallback: capture-unsafe code (data-dependent Python control flow, host
syncs like ``float(loss)``) raises a tracer error during the first
trace; the step falls back to plain eager permanently, with a one-shot
diagnostic naming the offending user line. ``PT_CAPTURE=0`` disables
capture globally.
"""
from __future__ import annotations

import functools
import os
import sys
import time
import warnings

import numpy as np
import jax
import jax.numpy as jnp

from ..tensor import Tensor
from ..framework import random as _random
from ..nn.layer.layers import Layer
from ..optimizer.optimizer import Optimizer
from ..observability.logs import get_logger
from .api import _closure_layer_targets, _loaded_global_names, _is_arraylike

__all__ = ["capture_step", "CapturedStep"]

logger = get_logger(__name__)

_TRACE_ERRORS = tuple(
    e for e in (
        getattr(jax.errors, n, None)
        for n in ("ConcretizationTypeError", "TracerArrayConversionError",
                  "TracerBoolConversionError", "TracerIntegerConversionError",
                  "UnexpectedTracerError", "NonConcreteBooleanIndexError"))
    if e is not None)

_FALSY = {"0", "false", "no", "off"}


def _capture_enabled():
    return os.environ.get("PT_CAPTURE", "1").strip().lower() not in _FALSY


def _device_copy(a):
    """A fresh device array with the same contents — the donation
    firewall between capture-private state and caller-held arrays."""
    return jnp.array(a, copy=True)


def _closure_optimizers(fn):
    """Optimizer instances reachable from fn's closure / globals /
    bound self — the same discovery rule as ``_closure_layer_targets``
    (jit/api.py): anything not threaded through the trace would bake
    its state as constants."""
    out, seen = [], set()

    def add(val):
        if isinstance(val, Optimizer) and id(val) not in seen:
            seen.add(id(val))
            out.append(val)

    def add_container(val):
        add(val)
        if isinstance(val, (list, tuple)):
            for v in val:
                add(v)
        elif isinstance(val, dict):
            for v in val.values():
                add(v)

    obj = getattr(fn, "__self__", None)
    if obj is not None and hasattr(obj, "__dict__"):
        for v in vars(obj).values():
            add_container(v)
    raw = getattr(fn, "__wrapped__", fn)
    code = getattr(raw, "__code__", None)
    cells = getattr(raw, "__closure__", None) or ()
    names = code.co_freevars if code is not None else ()
    for name, cell in zip(names, cells):
        try:
            add_container(cell.cell_contents)
        except ValueError:
            continue
    if code is not None:
        g = getattr(raw, "__globals__", {})
        for name in dict.fromkeys(_loaded_global_names(code)):
            if name in g:
                add_container(g[name])
    return out


def _tel():
    from ..observability import get_telemetry
    return get_telemetry()


def _span(name, cat):
    from ..observability.trace import get_tracer, span
    get_tracer()    # PT_TRACE / PT_FLIGHT_RECORDER take effect on first use
    return span(name, cat=cat)


class _LiveState:
    """Capture-private mutable state shared by all signature entries of
    one CapturedStep: the donated param/buffer/opt-state arrays plus the
    live Tensor objects they shadow."""

    __slots__ = ("layers", "param_tensors", "buffer_tensors", "params",
                 "buffers", "opts", "opt_param_names", "opt_states",
                 "rng_base", "rng_ctr")


class _Entry:
    __slots__ = ("jitted", "struct", "traced_idx", "sg_flags", "statics",
                 "n_leaves", "sig", "name", "ran", "memory", "monitored",
                 "monitor_names", "sdc", "sdc_names", "pure", "audit")


class CapturedStep:
    """One captured training-step callable (see module docstring)."""

    def __init__(self, fn):
        self._fn = fn
        self._cache = {}
        self._state = None
        self._fallback_reason = None
        self.stats = {"hits": 0, "misses": 0, "compiles": 0,
                      "fallback": None}
        try:
            functools.update_wrapper(self, fn)
        except AttributeError:
            pass

    # -- public knobs -------------------------------------------------------
    @property
    def fallback_reason(self):
        return self._fallback_reason

    def reset(self):
        """Drop every compiled entry and the private state (tests /
        notebook re-init). Layer tensors keep their current arrays."""
        self._cache.clear()
        self._state = None
        self._fallback_reason = None
        self.stats["fallback"] = None

    # -- dispatch -----------------------------------------------------------
    def __call__(self, *args, **kwargs):
        if self._fallback_reason is not None or not _capture_enabled():
            return self._fn(*args, **kwargs)
        # ONE flatten per call feeds signature, arg screening and replay
        leaves, struct = self._flatten(args, kwargs)
        try:
            sig = self._signature(leaves, struct)
        except TypeError:  # unhashable static leaf
            sig = None
        if sig is None or any(isinstance(l, (Layer, Optimizer))
                              for l in leaves):
            self._fall_back("unsupported_args", None)
            return self._fn(*args, **kwargs)
        entry = self._cache.get(sig)
        tel = _tel()
        if entry is not None:
            self.stats["hits"] += 1
            tel.capture_cache_hit()
            return self._replay(entry, leaves)
        reason = "first_trace" if not self._cache else "signature_change"
        self.stats["misses"] += 1
        tel.capture_cache_miss(reason)
        try:
            # jax.jit is lazy — the trace (where capture-unsafe code
            # raises) happens inside the first replay, so it is covered
            # by this except too
            entry = self._compile(args, kwargs, sig)
            result = self._replay(entry, leaves)
        except _TRACE_ERRORS as e:
            self._fall_back("capture_unsafe", e)
            return self._fn(*args, **kwargs)
        self._cache[sig] = entry
        return result

    # -- signature ----------------------------------------------------------
    def _flatten(self, args, kwargs):
        return jax.tree_util.tree_flatten(
            (args, kwargs), is_leaf=lambda x: isinstance(x, Tensor))

    def _signature(self, leaves, struct):
        key = [struct]
        for leaf in leaves:
            if isinstance(leaf, Tensor):
                d = leaf._data
                # dtype objects hash directly; str() on them is the
                # single hottest line of the naive key (numpy renders
                # the name on every call)
                key.append(("t", d.shape, d.dtype, leaf.stop_gradient))
            elif _is_arraylike(leaf):
                key.append(("a", np.shape(leaf), np.asarray(leaf).dtype))
            else:
                key.append(("s", leaf))
        # training-mode flips (dropout/bn) are baked into a trace, so
        # they key the cache; the scan also catches a rebound global
        # layer (fresh object → fresh ids → honest retrace)
        for pref, ly in _closure_layer_targets(self._fn):
            key.append((id(ly), ly.training))
        return hash(tuple(key))

    # -- capture ------------------------------------------------------------
    def _build_state(self):
        st = _LiveState()
        st.layers = _closure_layer_targets(self._fn)
        st.param_tensors, st.buffer_tensors = {}, {}
        st.params, st.buffers = {}, {}
        for pref, ly in st.layers:
            for k, t in dict(ly.named_parameters()).items():
                name = f"{pref}::{k}"
                if name not in st.param_tensors:
                    st.param_tensors[name] = t
                    st.params[name] = _device_copy(t._data)
            for k, t in dict(ly.named_buffers()).items():
                name = f"{pref}::{k}"
                if name not in st.buffer_tensors:
                    st.buffer_tensors[name] = t
                    st.buffers[name] = _device_copy(t._data)
        st.opts = _closure_optimizers(self._fn)
        by_id = {id(t): n for n, t in st.param_tensors.items()}
        st.opt_param_names, st.opt_states = [], []
        for oi, opt in enumerate(st.opts):
            onames = []
            for p in opt._parameter_list:
                name = by_id.get(id(p))
                if name is None:  # bare Parameter outside any found Layer
                    name = f"opt{oi}::{p.name}"
                    st.param_tensors[name] = p
                    st.params[name] = _device_copy(p._data)
                    by_id[id(p)] = name
                onames.append(name)
            state = opt.init_state_tree({n: st.params[n] for n in onames})
            # seed from live eager accumulators so capture mid-run
            # continues the same trajectory
            for n in onames:
                pname = st.param_tensors[n].name
                for slot in opt._state_slots:
                    cur = opt._accumulators[slot].get(pname)
                    if cur is not None:
                        state["slots"][slot][n] = _device_copy(cur)
                m = opt._master_weights.get(pname)
                if m is not None:
                    state["master"][n] = _device_copy(m)
            state["step"] = jnp.asarray(opt._global_step, jnp.int32)
            st.opt_param_names.append(onames)
            st.opt_states.append(state)
        # the capture's own key chain: a base key closed over as a
        # program constant plus a host-side int counter folded in INSIDE
        # the compiled program. Host-side fold_in costs ~0.5ms/call, and
        # a typed key as a jit *argument* keeps pjit off its C++ fast
        # dispatch path (~70µs/call) — the counter form costs ~6µs
        st.rng_base = _random.next_key()
        st.rng_ctr = 0
        # census attribution: hand the memory monitor a weakly-held
        # view of the capture-private state so live_arrays() bytes
        # resolve to parameter paths (the enable decision is baked at
        # build time, like the numerics sentinel)
        from ..observability import memory as _memory
        if _memory.get_memory_monitor().enabled:
            _memory.get_memory_monitor().register_provider(
                self._memory_named)
        return st

    def _memory_named(self):
        """Attribution view for the memory census/postmortem: every
        capture-private array by qualified path (``param::<path>``,
        ``buffer::<path>``, ``opt<i>::<slot>::<path>``)."""
        st = self._state
        if st is None:
            return {}
        named = {}
        for n, a in st.params.items():
            named[f"param::{n}"] = a
        for n, a in st.buffers.items():
            named[f"buffer::{n}"] = a
        for oi, state in enumerate(st.opt_states):
            for slot, d in state.get("slots", {}).items():
                for n, a in d.items():
                    named[f"opt{oi}::{slot}::{n}"] = a
            for n, a in state.get("master", {}).items():
                named[f"opt{oi}::master::{n}"] = a
        return named

    def _book_oom(self, entry, exc):
        """RESOURCE_EXHAUSTED intercept: pin the memory postmortem
        (census + footprints + watermark history) into the flight
        recorder before the error propagates — the same trip path the
        numerics sentinels use. Never raises; the caller re-raises the
        original error."""
        try:
            from ..observability import memory as _memory
            if not _memory.is_oom_error(exc):
                return
            _memory.oom_postmortem(program=entry.name, exc=exc,
                                   extra_named=self._memory_named())
        except Exception:
            pass

    def _compile(self, args, kwargs, sig):
        if self._state is None:
            self._state = self._build_state()
        st = self._state
        fn = self._fn
        leaves, struct = self._flatten(args, kwargs)
        traced_idx, sg_flags, statics = [], [], []
        for i, leaf in enumerate(leaves):
            if isinstance(leaf, Tensor):
                traced_idx.append(i)
                sg_flags.append(leaf.stop_gradient)
            elif _is_arraylike(leaf):
                traced_idx.append(i)
                sg_flags.append(True)
            else:
                statics.append((i, leaf))
        n_leaves = len(leaves)
        p_tensors, b_tensors, opts = st.param_tensors, st.buffer_tensors, \
            st.opts
        opt_param_names = st.opt_param_names
        rng_base = st.rng_base
        # numerics sentinel: the enable decision is baked per entry at
        # trace time, so a monitored step carries its health outputs in
        # the SAME program — still exactly one compile per signature
        from ..observability import numerics as _numerics
        mon = _numerics.get_monitor()
        mon = mon if mon.enabled else None
        mon_box = []  # filled with the tensor-name tuple during trace
        # SDC sentry: same per-entry bake as the numerics sentinel —
        # the replica fingerprint vector rides the same program
        from ..observability import sdc as _sdc
        smon = _sdc.get_monitor()
        smon = smon if smon.enabled else None
        sdc_box = []  # filled with the fingerprint-name tuple during trace

        def pure(params, buffers, opt_states, ctr, lrs, traced):
            key = jax.random.fold_in(rng_base, ctr)
            new_opt_states = list(opt_states)
            mon_grads = {}

            def mk_hook(oi):
                opt, onames = opts[oi], opt_param_names[oi]

                def hook(_o):
                    cur_params = {n: p_tensors[n]._data for n in onames}
                    grads = {}
                    for n in onames:
                        t = p_tensors[n]
                        if not t.stop_gradient and t._grad is not None:
                            grads[n] = t._grad._data
                    if mon is not None:
                        mon_grads.update(grads)
                    new_p, new_s = opt.apply_gradients_tree(
                        cur_params, grads, new_opt_states[oi], lr=lrs[oi])
                    for n, arr in new_p.items():
                        p_tensors[n]._data = arr
                    new_opt_states[oi] = new_s
                return hook

            saved = [(t, t._data, t._grad, t._node)
                     for t in list(p_tensors.values())
                     + list(b_tensors.values())]
            try:
                for name, t in p_tensors.items():
                    t._data = params[name]
                    t._grad = None
                for name, t in b_tensors.items():
                    t._data = buffers[name]
                for oi, opt in enumerate(opts):
                    opt._capture_hook = mk_hook(oi)
                lvs = [None] * n_leaves
                for i, a, sg in zip(traced_idx, traced, sg_flags):
                    tt = Tensor(a)
                    tt.stop_gradient = sg
                    lvs[i] = tt
                for i, v in statics:
                    lvs[i] = v
                cargs, ckwargs = jax.tree_util.tree_unflatten(struct, lvs)
                with _random.trace_key_scope(key):
                    out = fn(*cargs, **ckwargs)
                out_arrays = jax.tree_util.tree_map(
                    lambda t: t._data if isinstance(t, Tensor) else t, out,
                    is_leaf=lambda t: isinstance(t, Tensor))
                new_params = {n: t._data for n, t in p_tensors.items()}
                new_buffers = {n: t._data for n, t in b_tensors.items()}
                ret = [out_arrays, new_params, new_buffers,
                       new_opt_states]
                if mon is not None:
                    # first scalar inexact output is treated as the loss
                    loss = None
                    for leaf in jax.tree_util.tree_leaves(out_arrays):
                        if (hasattr(leaf, "dtype")
                                and hasattr(leaf, "size")
                                and leaf.size == 1
                                and jnp.issubdtype(leaf.dtype,
                                                   jnp.inexact)):
                            loss = leaf
                            break
                    # flag the UPDATED parameters, not the raw grads:
                    # the new params are already materialized program
                    # outputs, so their per-tensor reductions extend no
                    # intermediate lifetimes (grad-side reductions
                    # measurably inhibit XLA's backward/update fusion),
                    # a non-finite grad corrupts its param in this same
                    # step (same detection latency, same parameter-path
                    # naming), and state corruption — what persists
                    # into every later step — is the thing worth
                    # naming. The explosion detector still watches the
                    # true grad norm via norm_over.
                    monitored = {n: new_params[n] for n in mon_grads}
                    mnames, health = _numerics.health_outputs(
                        monitored, loss=loss, with_stats=mon.stats_on,
                        norm_over=mon_grads)
                    mon_box[:] = [mnames]
                    ret.append(health)
                if smon is not None:
                    # replica fingerprints cover the persistent state a
                    # flipped bit would poison: every updated param plus
                    # every optimizer slot / master weight — all already
                    # materialized program outputs, so the digests cost
                    # one fused reduction each and extend no lifetimes
                    fp_named = {f"param::{n}": a
                                for n, a in new_params.items()}
                    for oi, s in enumerate(new_opt_states):
                        if not isinstance(s, dict):
                            continue
                        for slot, per in (s.get("slots") or {}).items():
                            for n, a in per.items():
                                fp_named[f"opt{oi}::{slot}::{n}"] = a
                        for n, a in (s.get("master") or {}).items():
                            fp_named[f"opt{oi}::master::{n}"] = a
                    snames, fp = _sdc.fingerprint_outputs(fp_named)
                    sdc_box[:] = [snames]
                    ret.append(fp)
                return tuple(ret)
            finally:
                for t, d, g, nd in saved:
                    t._data, t._grad, t._node = d, g, nd
                for opt in opts:
                    opt._capture_hook = None

        fname = getattr(fn, "__name__", "fn")
        pure.__name__ = f"captured_step({fname})"
        pure.__qualname__ = pure.__name__

        entry = _Entry()
        # kept for the graph auditor, which reads its jaxpr under PT_AUDIT=1
        entry.pure = pure
        entry.audit = None
        entry.jitted = jax.jit(pure, donate_argnums=(0, 1, 2))
        entry.struct = struct
        entry.traced_idx = tuple(traced_idx)
        entry.sg_flags = tuple(sg_flags)
        entry.statics = tuple(statics)
        entry.n_leaves = n_leaves
        entry.sig = sig
        entry.name = pure.__name__
        entry.ran = False
        entry.memory = None
        entry.monitored = mon is not None
        entry.monitor_names = mon_box  # resolved after the first trace
        entry.sdc = smon is not None
        entry.sdc_names = sdc_box      # resolved after the first trace
        return entry

    # -- replay -------------------------------------------------------------
    def _replay(self, entry, leaves):
        st = self._state
        traced = [None] * len(entry.traced_idx)
        for j, i in enumerate(entry.traced_idx):
            leaf = leaves[i]
            traced[j] = leaf._data if isinstance(leaf, Tensor) \
                else jnp.asarray(leaf)
        # plain floats: jit lifts them to weak-f32 runtime args, so an
        # lr-schedule change never retraces (train_step.py pattern)
        lrs = [float(opt.get_lr()) for opt in st.opts]
        call = entry.jitted
        # dispatch-side span: async under jax, so this is dispatch + any
        # implicit materialization, never a forced device sync.  The
        # first call is dominated by trace+compile and is billed as such
        # — the goodput ledger classifies it as overhead, not productive
        # compute.
        if entry.ran:
            with _span(entry.name, cat="compute"):
                try:
                    outs = call(st.params, st.buffers, st.opt_states,
                                st.rng_ctr, lrs, traced)
                except Exception as e:
                    self._book_oom(entry, e)
                    raise
        else:
            from ..observability import memory as _memory
            _mm = _memory.get_memory_monitor()
            if _mm.enabled and entry.memory is None:
                # compile-time footprint + pre-flight fit check:
                # memory_analysis() from an AOT compile (cache-shared
                # with the call below), BEFORE the first replay can
                # discover an unfit program as a raw RESOURCE_EXHAUSTED
                entry.memory = _mm.harvest_program(
                    entry.name, call, st.params, st.buffers,
                    st.opt_states, st.rng_ctr, lrs, traced)
            with warnings.catch_warnings(), \
                    _span(f"compile:{entry.name}", cat="host"):
                # backends without donation (cpu) warn once at compile;
                # the annotation is still correct where it counts
                warnings.filterwarnings(
                    "ignore", message="Some donated buffers were not usable")
                try:
                    outs = call(st.params, st.buffers, st.opt_states,
                                st.rng_ctr, lrs, traced)
                except Exception as e:
                    self._book_oom(entry, e)
                    raise
            entry.ran = True  # only after the trace actually succeeded
            self.stats["compiles"] += 1
            tel = _tel()
            if not tel._watcher.installed:
                # feed the recompile sentinel directly when jax's compile
                # log isn't being watched (watcher installed → the log
                # filter records this compile; both would double-count)
                tel.record_compile(entry.name, f"sig={entry.sig}")
            if entry.audit is None:
                # graph audit (tools/audit): static findings over the
                # step jaxpr, harvested once per signature
                # in the same compile-time window as the memory pass
                # above — the replay hot path never pays it
                from ..tools.audit import runtime as _audit_rt
                if _audit_rt.audit_enabled():
                    entry.audit = _audit_rt.audit_captured_step(
                        entry, st.params, st.buffers, st.opt_states,
                        st.rng_ctr, lrs, traced)
                else:
                    entry.audit = ()
        step_idx = st.rng_ctr
        st.rng_ctr += 1
        outs = list(outs)
        fp = outs.pop() if entry.sdc else None
        health = outs.pop() if entry.monitored else None
        out_arrays, st.params, st.buffers, st.opt_states = outs
        for name, t in st.param_tensors.items():
            t._data = st.params[name]
        for name, t in st.buffer_tensors.items():
            t._data = st.buffers[name]
        for oi, opt in enumerate(st.opts):
            opt._global_step += 1
            s = st.opt_states[oi]
            for n in st.opt_param_names[oi]:
                pname = st.param_tensors[n].name
                for slot in opt._state_slots:
                    opt._accumulators[slot][pname] = s["slots"][slot][n]
                if n in s["master"]:
                    opt._master_weights[pname] = s["master"][n]
        # watermark timeline: step-boundary allocator sample. sys.modules-
        # gated like the telemetry hooks — a run that never imported the
        # memory module pays one dict lookup here.
        mem_mod = sys.modules.get("paddle_tpu.observability.memory")
        if mem_mod is not None:
            mm = mem_mod.current_memory_monitor()
            if mm is not None and mm.enabled:
                mm.on_step(step_idx)
        if entry.monitored:
            # hand the (tiny) health arrays to the monitor; it reads
            # the previous packet at cadence boundaries, so this never
            # blocks the step. May raise NumericsHaltError (after the
            # state writeback above) when PT_NUMERICS_HALT=1.
            from ..observability import numerics as _numerics
            m = _numerics.current_monitor()
            if m is not None and entry.monitor_names:
                m.watch(step_idx, entry.monitor_names[0], health)
        if entry.sdc:
            # same discipline for the SDC fingerprint packet: held one
            # dispatch behind, voted on at cadence boundaries. May
            # raise SdcHaltError when consensus fingers this rank.
            from ..observability import sdc as _sdc
            sm = _sdc.current_monitor()
            if sm is not None and entry.sdc_names:
                sm.watch(step_idx, entry.sdc_names[0], fp)
        return jax.tree_util.tree_map(
            lambda a: Tensor(a) if _is_arraylike(a) else a, out_arrays)

    # -- fallback -----------------------------------------------------------
    def _fall_back(self, reason, exc):
        self._fallback_reason = reason
        self.stats["fallback"] = reason
        _tel().capture_cache_miss(reason)
        fname = getattr(self._fn, "__name__", "fn")
        where = self._user_line(exc)
        detail = f": {type(exc).__name__}: {str(exc)[:200]}" if exc else ""
        logger.warning(
            "capture_step(%s): falling back to eager (%s)%s%s — the step "
            "will run un-jitted; remove the host sync / data-dependent "
            "branch (or set PT_CAPTURE=0 to silence)",
            fname, reason, f" at {where}" if where else "", detail)

    def _user_line(self, exc):
        if exc is None:
            return None
        code = getattr(getattr(self._fn, "__wrapped__", self._fn),
                       "__code__", None)
        if code is None:
            return None
        tb, best = exc.__traceback__, None
        while tb is not None:
            if tb.tb_frame.f_code.co_filename == code.co_filename:
                best = f"{code.co_filename}:{tb.tb_lineno}"
            tb = tb.tb_next
        return best


def capture_step(fn=None):
    """Decorator: trace-and-cache a whole training-step function.

    ::

        @paddle_tpu.jit.capture_step
        def step(x, y):
            loss = mse(model(x), y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            return loss

    The model/optimizer must be reachable from the function's closure,
    globals, or bound ``self`` (same rule as ``to_static`` on plain
    functions). See the module docstring for cache-key and fallback
    semantics.
    """
    if fn is None:
        return capture_step
    return CapturedStep(fn)
