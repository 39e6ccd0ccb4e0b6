"""Eager autograd engine.

TPU-native re-design of the reference's dygraph autograd:
 - grad-node graph + queue-based backward walk:
   ``paddle/fluid/eager/backward.cc:104 RunBackward``,
   ``paddle/fluid/eager/grad_node_info.h:168 GradNodeBase``
 - per-op capture: the reference *code-generates* a GradNode class per op
   (``eager/auto_code_generator/generator/eager_gen.py:960``); here a single
   generic tape node captures ``jax.vjp`` of the op's pure function — JAX's
   tracing IS the code generator, so there is nothing to generate.

Key property: ``jax.vjp(fn, *primals)`` runs the forward exactly once on
device and returns a host-side closure over the residuals, so eager mode pays
no double-compute for recording gradients. Under ``to_static``/jit tracing the
tape is bypassed (`functional_guard`) and gradients come from functional
``jax.grad`` over the whole step — the fast path.
"""
from __future__ import annotations

import contextlib
import functools
import threading
import weakref
from collections import deque

import numpy as np
import jax
from jax._src import source_info_util as _source_info

from .framework import flags as _flags

__all__ = [
    "no_grad", "enable_grad", "set_grad_enabled", "is_grad_enabled",
    "backward", "grad", "PyLayer", "PyLayerContext",
    "saved_tensors_hooks", "jacobian", "hessian", "Jacobian", "Hessian",
]

_state = threading.local()


def is_grad_enabled() -> bool:
    return getattr(_state, "enabled", True)


def _set_enabled(v: bool):
    _state.enabled = v


def in_functional_mode() -> bool:
    """True while tracing a functional (jit) program — tape disabled."""
    return getattr(_state, "functional", 0) > 0


@contextlib.contextmanager
def functional_guard():
    _state.functional = getattr(_state, "functional", 0) + 1
    try:
        yield
    finally:
        _state.functional -= 1


class _GradCtx:
    """Context manager / decorator toggling grad recording (paddle.no_grad)."""

    def __init__(self, enable: bool):
        self._enable = enable

    def __enter__(self):
        self._prev = is_grad_enabled()
        _set_enabled(self._enable)
        return self

    def __exit__(self, *exc):
        _set_enabled(self._prev)
        return False

    def __call__(self, fn):
        enable = self._enable

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with _GradCtx(enable):
                return fn(*args, **kwargs)
        return wrapper


class no_grad(_GradCtx):
    def __init__(self):
        super().__init__(False)


class enable_grad(_GradCtx):
    def __init__(self):
        super().__init__(True)


class set_grad_enabled(_GradCtx):
    def __init__(self, mode: bool):
        super().__init__(bool(mode))


class Node:
    """One recorded op on the tape.

    inputs:  Tensors the op consumed (strong refs keep the graph alive as
             long as any output lives — same lifetime rule as the
             reference's shared_ptr grad-node chain).
    vjp_fn:  pullback closure. Funnel-recorded ops build it LAZILY — the
             forward only runs the bare op and stashes (fn, input arrays);
             ``jax.vjp`` is traced at backward time via :meth:`pullback`.
             The reference pays a whole codegen subsystem to keep eager
             dispatch cheap (``paddle/fluid/eager/auto_code_generator/``);
             deferring the trace is the tape's analog — forward dispatch
             drops from one jax trace per op to one jnp call per op
             (bench_eager.py measures it). PyLayer / functional_call nodes
             still pass an explicit vjp_fn.
    outputs: weakrefs to produced Tensors (to locate incoming cotangents).
    scope:   jax's name stack (``jax.named_scope``) at record time.  The
             deferred ``jax.vjp`` runs at ``loss.backward()``, outside
             every layer's scope; :meth:`pullback` re-enters this one, so
             a backward operation carries its forward's scope in HLO op
             names and profiler traces.
    """

    __slots__ = ("inputs", "vjp_fn", "fn", "datas", "out_refs", "out_avals",
                 "name", "scope", "_hooks", "_released", "_unpack",
                 "__weakref__")

    def __init__(self, inputs, vjp_fn, outputs, name="", fn=None,
                 datas=None):
        self.inputs = list(inputs)
        self.vjp_fn = vjp_fn
        self.fn = fn
        self.datas = datas
        self.out_refs = [weakref.ref(t) for t in outputs]
        self.out_avals = [(t.shape, t._data.dtype) for t in outputs]
        self.name = name
        self.scope = _name_stack()
        self._hooks = None
        self._released = False
        self._unpack = None

    def pullback(self, cot):
        if self._released:
            raise RuntimeError(
                "Trying to backward through the graph a second time; "
                "set retain_graph=True if you need to.")
        if self.scope.stack:
            with _source_info.set_name_stack(self.scope):
                return self._pullback(cot)
        return self._pullback(cot)

    def _pullback(self, cot):
        if self.vjp_fn is None:
            # deferred trace: input arrays were captured at record time, so
            # later in-place rebinds of the input Tensors don't corrupt it
            datas = self.datas
            if self._unpack is not None:
                datas = tuple(_unpack_saved(self._unpack, p) for p in datas)
            _, self.vjp_fn = jax.vjp(self.fn, *datas)
        return self.vjp_fn(cot)

    def release(self):
        self.vjp_fn = None
        self.fn = None
        self.datas = None
        self.inputs = []
        self._released = True


# static-graph recorder hook; installed by paddle_tpu.static.graph so the
# one op funnel serves both dygraph (execute + tape) and static (record node)
_static_recorder = None
_STATIC_SENTINEL = None

_node_new = Node.__new__
_name_stack = _source_info.current_name_stack
_flag_values = _flags._values  # direct dict ref for the per-op hot path
_wref = weakref.ref

# single-output fast path: op_utils registers its (_wrap_single,
# _fast_tensor) pair so record() can skip the list-of-outputs protocol
# — no [t] alloc, no comprehensions — for the overwhelmingly common
# one-output op (the per-op dispatch floor bench_eager.py tracks)
_single_wrap_fn = None
_single_ctor = None


def _register_single_wrap(wrap, ctor):
    global _single_wrap_fn, _single_ctor
    _single_wrap_fn, _single_ctor = wrap, ctor


def _repoint_out_ref(node, idx, ref):
    refs = node.out_refs
    if type(refs) is tuple:  # single-output fast path stores a tuple
        node.out_refs = refs[:idx] + (ref,) + refs[idx + 1:]
    else:
        refs[idx] = ref


def _hooks_stack():
    """Per-thread hook stack — a hooks context in one thread must not
    pack tensors recorded concurrently by other threads (all other
    autograd mode state lives on ``_state`` for the same reason)."""
    st = _state.__dict__
    stack = st.get("saved_hooks")
    if stack is None:
        stack = st["saved_hooks"] = []
    return stack


class saved_tensors_hooks:
    """Pack/unpack hooks over tensors saved for backward (ref
    ``python/paddle/autograd/saved_tensors_hooks.py:20``): every array
    the tape captures for a node's deferred vjp is passed (as a Tensor)
    through ``pack_hook`` at record time, and ``unpack_hook`` rebuilds
    it at backward time — the offload-to-CPU/disk extension point."""

    def __init__(self, pack_hook, unpack_hook):
        self.pack_hook = pack_hook
        self.unpack_hook = unpack_hook

    def __enter__(self):
        _hooks_stack().append((self.pack_hook, self.unpack_hook))
        return self

    def __exit__(self, *exc):
        _hooks_stack().pop()
        return False


def _pack_saved(node):
    from .tensor import Tensor
    pack, unpack = _hooks_stack()[-1]
    node.datas = tuple(pack(Tensor(d)) for d in node.datas)
    node._unpack = unpack


def _unpack_saved(unpack, packed):
    t = unpack(packed)
    return t._data if hasattr(t, "_data") else t


def rebind_inplace(x, out):
    """Make ``x`` become ``out`` in place (paddle's ``op_`` variants):
    rebind data + tape linkage, then repoint the producing node's output
    ref at the surviving tensor so backward finds cotangents under it.

    When the op was recorded for grad and ``x`` is among its inputs, the
    pre-inplace producer chain must survive the rebind: a lightweight
    proxy tensor takes ``x``'s place in the node's inputs (and in the
    old producer's out_refs), so backward still reaches everything
    upstream of the overwritten value. A grad-requiring LEAF cannot be
    rebound this way — same rule as the reference
    (``paddle/fluid/eager/api/utils/tensor_utils.cc`` inplace check:
    "Leaf Var that doesn't stop gradient can't use inplace strategy")."""
    node = out._node
    if node is not None:
        # ONE proxy shared by every occurrence of x in the inputs: a
        # proxy per occurrence would fight over the producer's single
        # out_ref and silently drop all but the last cotangent. A
        # stop-gradient leaf gets a constant proxy (_node=None) too —
        # leaving x itself in inputs would make the node consume its
        # own output after the rebind and deadlock the backward walk.
        proxy = None
        for j, t in enumerate(node.inputs):
            if t is x:
                if proxy is None:
                    if x._node is None and not x.stop_gradient:
                        raise RuntimeError(
                            "Leaf Tensor that doesn't stop gradient can't "
                            "use inplace strategy; detach() it or wrap the "
                            "update in no_grad()")
                    proxy = _single_ctor(x._data, not x.stop_gradient)
                    if x._node is not None:
                        proxy._node = x._node
                        proxy._out_idx = x._out_idx
                        _repoint_out_ref(x._node, x._out_idx, _wref(proxy))
                node.inputs[j] = proxy  # strong ref keeps proxy alive
    x._data = out._data
    x._node = out._node
    x._out_idx = out._out_idx
    x.stop_gradient = out.stop_gradient and x.stop_gradient
    if node is not None:
        _repoint_out_ref(node, x._out_idx, _wref(x))
    return x

# op observers: every funnel-recorded op reports (name, inputs, outputs).
# Serves amp.debugging operator-stats / tensor-checker tooling (ref
# ``python/paddle/amp/debugging.py``); empty-list check keeps the hot
# path free when unused.
_op_observers: list = []


def add_op_observer(fn):
    """fn(op_name, input_tensors, output_tensors) on every recorded op."""
    _op_observers.append(fn)
    return fn


def remove_op_observer(fn):
    try:
        _op_observers.remove(fn)
    except ValueError:
        pass


def record(fn, tensors, outputs_wrap, name=""):
    """Run `fn(*datas)` with optional tape capture.

    fn: pure function over raw jax arrays returning array or tuple of arrays.
    tensors: Tensor inputs in fn arg order.
    outputs_wrap: callable(raw_out, requires_grad) -> (tensors_list, result)
    """
    if _static_recorder is not None:
        res = _static_recorder(fn, tensors, outputs_wrap, name)
        if res is not _STATIC_SENTINEL:
            return res
    # inlined is_grad_enabled()/in_functional_mode(): the per-op eager
    # path is the framework's dispatch floor (bench_eager.py tracks it),
    # so thread-local state is read via one __dict__ lookup each; the
    # 1/2-arity cases (the whole elementwise funnel) skip the generic
    # tuple build + stop_gradient loop
    st = _state.__dict__
    n = len(tensors)
    if n == 2:
        a, b = tensors
        datas = (a._data, b._data)
        needs_grad = not (a.stop_gradient and b.stop_gradient)
    elif n == 1:
        a = tensors[0]
        datas = (a._data,)
        needs_grad = not a.stop_gradient
    else:
        datas = tuple(t._data for t in tensors)
        needs_grad = any(not t.stop_gradient for t in tensors)
    if needs_grad and (not st.get("enabled", True) or st.get("functional", 0)):
        needs_grad = False
    raw = fn(*datas)
    if outputs_wrap is _single_wrap_fn:
        t = _single_ctor(raw, needs_grad)
        if needs_grad:
            node = _node_new(Node)
            node.inputs = tensors  # callers pass fresh lists; alias
            node.vjp_fn = None
            node.fn = fn
            node.datas = datas
            node.out_refs = (_wref(t),)
            d = t._data
            node.out_avals = ((d.shape, d.dtype),)
            node.name = name
            node.scope = _name_stack()
            node._hooks = None
            node._released = False
            node._unpack = None
            if st.get("saved_hooks"):
                _pack_saved(node)
            t._node = node  # _out_idx is already 0 from the ctor
        if _flag_values.get("check_nan_inf"):
            _check_nan_inf((t,), name)
        if _op_observers:
            for ob in list(_op_observers):
                ob(name, tensors, (t,))
        return t
    out_tensors, result = outputs_wrap(raw, needs_grad)
    if needs_grad:
        node = _node_new(Node)
        node.inputs = tensors  # callers pass fresh lists; alias, no copy
        node.vjp_fn = None
        node.fn = fn
        node.datas = datas
        node.out_refs = [weakref.ref(t) for t in out_tensors]
        node.out_avals = [(t._data.shape, t._data.dtype)
                          for t in out_tensors]
        node.name = name
        node.scope = _name_stack()
        node._hooks = None
        node._released = False
        node._unpack = None
        if st.get("saved_hooks"):
            _pack_saved(node)
        for i, t in enumerate(out_tensors):
            t._node = node
            t._out_idx = i
    if _flag_values.get("check_nan_inf"):
        _check_nan_inf(out_tensors, name)
    if _op_observers:
        for ob in list(_op_observers):
            ob(name, tensors, out_tensors)
    return result


def _check_nan_inf(tensors, name):
    """FLAGS_check_nan_inf analog (ref: paddle/fluid/eager/nan_inf_utils.cc)."""
    import jax.numpy as jnp
    for t in tensors:
        d = t._data
        if isinstance(d, jax.core.Tracer):
            continue
        if np.issubdtype(np.dtype(d.dtype), np.floating) or d.dtype == jnp.bfloat16:
            # debug-mode op-output audit: concrete (non-tracer)
            # values only, and raising eagerly is the feature
            # tpu-lint: disable=TPU017
            if bool(jnp.any(~jnp.isfinite(d))):
                raise FloatingPointError(
                    f"NaN/Inf detected in output of op '{name or 'unknown'}'")


def _zero_cot(shape, dt):
    if np.issubdtype(np.dtype(dt), np.integer) or np.dtype(dt) == np.bool_:
        return np.zeros(shape, dtype=jax.dtypes.float0)
    import jax.numpy as jnp
    return jnp.zeros(shape, dtype=dt)


def backward(tensors, grad_tensors=None, retain_graph=False,
             create_graph=False):
    """Queue-based reverse walk over the tape.

    Mirrors ``egr::RunBackward`` (``backward.cc:104``): seed cotangents,
    count consumer edges per node, process nodes whose consumers are all
    done, accumulate into leaf ``.grad``.

    ``create_graph=True`` (higher-order, ref ``paddle/fluid/prim/`` +
    ``incubate/autograd/primapi.py:220``): each node's pullback is
    re-executed THROUGH the tape (:func:`_taped_pullback`) and cotangent
    accumulation uses taped adds, so the produced gradients carry their
    own tape and can be differentiated again. Implies retain_graph.
    """
    import jax.numpy as jnp
    from .tensor import Tensor

    if create_graph:
        retain_graph = True
    if not isinstance(tensors, (list, tuple)):
        tensors = [tensors]
    if grad_tensors is None:
        grad_tensors = [None] * len(tensors)
    elif not isinstance(grad_tensors, (list, tuple)):
        grad_tensors = [grad_tensors]

    # cotangent accumulator keyed by tensor identity
    cots: dict[int, object] = {}
    keep: dict[int, object] = {}  # keep tensors alive during walk

    def accum(t, g):
        if g is None or isinstance(g, np.ndarray) and g.dtype == jax.dtypes.float0:
            return
        k = id(t)
        keep[k] = t
        if k in cots:
            cots[k] = cots[k] + g  # taped add when both are Tensors
        else:
            cots[k] = g

    roots = []
    for t, g in zip(tensors, grad_tensors):
        if t._node is None and t.stop_gradient:
            continue
        if g is None:
            if t.size != 1:
                raise RuntimeError(
                    "grad can be implicitly created only for scalar outputs; "
                    f"got shape {t.shape}")
            g = jnp.ones_like(t._data)
        else:
            g = g._data if isinstance(g, Tensor) else jnp.asarray(g)
        if create_graph:
            g = Tensor(g, stop_gradient=True)
        if t._node is not None:
            accum(t, g)
            roots.append(t._node)
        else:
            # root IS a leaf: its seed gradient goes straight to .grad
            _leaf_accum(t, g)

    # reachable node set
    reach: set[int] = set()
    nodes: dict[int, Node] = {}
    stack = list(roots)
    while stack:
        n = stack.pop()
        if id(n) in reach:
            continue
        reach.add(id(n))
        nodes[id(n)] = n
        for t in n.inputs:
            if t._node is not None:
                stack.append(t._node)

    # consumer edge counts
    pending: dict[int, int] = {k: 0 for k in reach}
    for n in nodes.values():
        seen_producers = set()
        for t in n.inputs:
            p = t._node
            if p is not None and id(p) in reach:
                # one edge per (consumer, input-tensor) occurrence
                pending[id(p)] += 1
            del p
        del seen_producers

    # A node is initially ready iff no reachable node consumes its outputs.
    ready = deque(n for k, n in nodes.items() if pending[k] == 0)
    processed = set()
    while ready:
        n = ready.popleft()
        if id(n) in processed:
            continue
        processed.add(id(n))
        # gather cotangents for this node's outputs
        out_cots = []
        for ref, (shape, dt) in zip(n.out_refs, n.out_avals):
            t = ref()
            g = cots.pop(id(t), None) if t is not None else None
            if g is None:
                g = _zero_cot(shape, dt)
                if create_graph and not (isinstance(g, np.ndarray)
                                         and g.dtype == jax.dtypes.float0):
                    from .tensor import Tensor as _T
                    g = _T(g, stop_gradient=True)
            out_cots.append(g)
        if create_graph:
            in_grads = _taped_pullback(n, out_cots)
        else:
            cot_in = out_cots[0] if len(out_cots) == 1 else tuple(out_cots)
            in_grads = n.pullback(cot_in)
        if n._hooks:
            in_grads = list(in_grads)
            for i, h in n._hooks:
                in_grads[i] = h(in_grads[i])
        for t, g in zip(n.inputs, in_grads):
            # a float0 cotangent (int-dtype input) carries no gradient, but
            # the consumer edge must still be counted down or the producer
            # node never becomes ready and valid sibling paths are dropped
            is_f0 = isinstance(g, np.ndarray) and g.dtype == jax.dtypes.float0
            if t._node is None:
                if not t.stop_gradient and not is_f0:
                    _leaf_accum(t, g)
            else:
                if not is_f0:
                    accum(t, g)
                p = t._node
                if id(p) in reach:
                    pending[id(p)] -= 1
                    if pending[id(p)] == 0:
                        ready.append(p)
        if not retain_graph:
            n.release()


def _taped_pullback(n, out_cots):
    """create_graph backward-of-backward: run the node's vjp THROUGH the
    tape so the produced gradients are themselves differentiable.

    The pullback is the pure function ``(cot, *float_inputs) ->
    float_input_grads`` (re-traced from the node's stored ``fn``);
    recording it via :func:`record` gives the grads tape edges back to
    both the cotangents and the node's input tensors. Nodes built from an
    opaque ``vjp_fn`` (PyLayer / functional_call) cannot be re-traced —
    their grads come back as constants (the graph stops there, like a
    non-differentiable custom backward in the reference).
    """
    from .tensor import Tensor
    multi = len(out_cots) > 1

    if n.fn is None or n.datas is None:
        if n._released:
            raise RuntimeError(
                "Trying to backward through the graph a second time; "
                "set retain_graph=True if you need to.")
        raw_cots = [c._data if isinstance(c, Tensor) else c
                    for c in out_cots]
        raw = n.vjp_fn(tuple(raw_cots) if multi else raw_cots[0])
        return [Tensor(g, stop_gradient=True)
                if not (isinstance(g, np.ndarray)
                        and g.dtype == jax.dtypes.float0) else g
                for g in raw]

    # differentiable slots: float cotangents + float node inputs
    slots: list = []          # Tensors handed to record()
    cot_template: list = []   # per-cot: slot index or the constant itself
    for c in out_cots:
        if isinstance(c, Tensor):
            cot_template.append(len(slots))
            slots.append(c)
        else:
            cot_template.append(c)  # float0 constant for int outputs
    fn, datas = n.fn, n.datas
    if n._unpack is not None:  # saved_tensors_hooks pack/unpack
        datas = tuple(_unpack_saved(n._unpack, p) for p in datas)

    def _is_float(a):
        import jax.numpy as jnp
        return (np.issubdtype(np.dtype(a.dtype), np.floating)
                or a.dtype == jnp.bfloat16)

    float_in = [i for i, d in enumerate(datas) if _is_float(d)]
    base = len(slots)
    slots.extend(n.inputs[i] for i in float_in)

    def pb(*arrs):
        cots = [arrs[s] if isinstance(s, int) else s for s in cot_template]
        ds = list(datas)
        for j, i in enumerate(float_in):
            ds[i] = arrs[base + j]
        primal, vjp = jax.vjp(fn, *ds)
        # cotangent structure must mirror fn's own output tree (some op
        # fns return 1-tuples even for single-output nodes)
        cot = tuple(cots) if isinstance(primal, (tuple, list)) else cots[0]
        gin = vjp(cot)
        gout = tuple(gin[i] for i in float_in)
        # single-output nodes carry a bare array (tape cot_in contract)
        return gout[0] if len(gout) == 1 else gout

    def wrap(raw, req):
        raws = raw if isinstance(raw, tuple) else (raw,)
        ts = [Tensor(r, stop_gradient=not req) for r in raws]
        return ts, ts

    grads_f = record(pb, slots, wrap, name=(n.name or "op") + "_grad")
    out = []
    it = iter(grads_f)
    for i, d in enumerate(datas):
        if i in set(float_in):
            out.append(next(it))
        else:
            out.append(np.zeros(d.shape, dtype=jax.dtypes.float0))
    return out


def _leaf_accum(t, g):
    import jax.numpy as jnp
    from .tensor import Tensor
    capture = getattr(_state, "leaf_capture", None)
    if capture is not None:
        # scoped backward (paddle.grad): only capture requested leaves,
        # never touch .grad of anything else
        table, allowed = capture
        if id(t) in allowed:
            prev = table.get(id(t))
            table[id(t)] = g if prev is None else prev + g
        return
    if isinstance(g, Tensor):
        # create_graph backward: keep the taped gradient as .grad so the
        # user can differentiate through it
        t._grad = g if t._grad is None else t._grad + g
    else:
        g = jnp.asarray(g)
        if g.dtype != t._data.dtype:
            g = g.astype(t._data.dtype)
        if t._grad is None:
            t._grad = Tensor(g, stop_gradient=True)
        else:
            t._grad._data = t._grad._data + g
    if t._grad_hooks:
        for h in t._grad_hooks.values():
            out = h(t._grad)
            if out is not None:
                t._grad = out


def grad(outputs, inputs, grad_outputs=None, retain_graph=None,
         create_graph=False, only_inputs=True, allow_unused=False,
         no_grad_vars=None):
    """``paddle.grad`` equivalent: returns grads of `outputs` w.r.t `inputs`
    without touching ``.grad`` accumulators.

    Implemented as a scoped backward: leaf accumulation is redirected to a
    side table covering ONLY `inputs`, so no tensor's ``.grad`` (including
    model parameters reachable from `outputs`) is touched.

    ``create_graph=True`` runs the backward pass THROUGH the tape
    (:func:`_taped_pullback`): the returned grads carry their own graph
    and can be fed back into :func:`grad` for second/higher derivatives
    (ref ``python/paddle/incubate/autograd/primapi.py:220`` double-grad).
    """
    from .tensor import Tensor
    if not isinstance(inputs, (list, tuple)):
        inputs = [inputs]
    sg = [(t, t.stop_gradient) for t in inputs]
    table: dict[int, object] = {}
    _state.leaf_capture = (table, {id(t) for t in inputs})
    try:
        for t in inputs:
            t.stop_gradient = False
        backward(outputs, grad_tensors=grad_outputs,
                 retain_graph=bool(retain_graph) or create_graph,
                 create_graph=create_graph)
        results = []
        for t in inputs:
            g = table.get(id(t))
            if g is None:
                if not allow_unused:
                    raise RuntimeError(
                        "One of the differentiated tensors appears unused; "
                        "pass allow_unused=True to return None for it.")
                results.append(None)
            elif isinstance(g, Tensor):
                results.append(g)  # create_graph: keep the taped grad
            else:
                results.append(Tensor(g, stop_gradient=True))
        return results
    finally:
        _state.leaf_capture = None
        for t, s in sg:
            t.stop_gradient = s


class PyLayerContext:
    """Saved-tensor context for custom ops (ref:
    ``paddle/fluid/eager/pylayer``, python ``paddle.autograd.PyLayer``)."""

    def __init__(self):
        self._saved = ()
        self.materialize_grads = True

    def save_for_backward(self, *tensors):
        # saved tensors route through active saved_tensors_hooks, same
        # contract as the funnel tape (ref saved_tensors_hooks.py:30)
        if _hooks_stack():
            pack, unpack = _hooks_stack()[-1]
            self._saved_packed = tuple(pack(t) for t in tensors)
            self._saved_unpack = unpack
            self._saved = None
        else:
            self._saved = tensors
            self._saved_unpack = None

    def _restore_saved(self):
        if getattr(self, "_saved_unpack", None) is not None:
            self._saved = tuple(self._saved_unpack(p)
                                for p in self._saved_packed)
            self._saved_unpack = None
        return self._saved

    @property
    def saved_tensor(self):
        return self._restore_saved()

    # paddle also exposes it as a method
    def saved_tensors(self):
        return self._restore_saved()


class PyLayer:
    """User-defined differentiable op with explicit forward/backward.

    Subclass and define ``forward(ctx, *args)`` and ``backward(ctx, *grads)``
    as staticmethods operating on Tensors, then call ``MyOp.apply(...)``.
    """

    @staticmethod
    def forward(ctx, *args, **kwargs):
        raise NotImplementedError

    @staticmethod
    def backward(ctx, *grads):
        raise NotImplementedError

    @classmethod
    def apply(cls, *args, **kwargs):
        from .tensor import Tensor
        ctx = PyLayerContext()
        tensor_args = [a for a in args if isinstance(a, Tensor)]
        with no_grad():
            out = cls.forward(ctx, *args, **kwargs)
        multi = isinstance(out, (list, tuple))
        outs = list(out) if multi else [out]
        needs = (is_grad_enabled() and not in_functional_mode()
                 and any(not t.stop_gradient for t in tensor_args))
        if needs:
            def vjp_fn(cot):
                cots = list(cot) if multi else [cot]
                cot_tensors = [Tensor(c, stop_gradient=True) for c in cots]
                with no_grad():
                    gin = cls.backward(ctx, *cot_tensors)
                if not isinstance(gin, (list, tuple)):
                    gin = (gin,)
                return tuple(
                    (g._data if isinstance(g, Tensor) else g) if g is not None
                    else np.zeros(t.shape, dtype=jax.dtypes.float0)
                    for g, t in zip(gin, tensor_args))

            for t in outs:
                t.stop_gradient = False
            node = Node(tensor_args, vjp_fn, outs, name=cls.__name__)
            for i, t in enumerate(outs):
                t._node = node
                t._out_idx = i
        return out if multi else outs[0]


from .autograd_functional import (  # noqa: E402
    Hessian, Jacobian, hessian, jacobian,
)
