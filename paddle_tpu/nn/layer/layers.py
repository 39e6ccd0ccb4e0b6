"""Layer: the module base class.

TPU-native re-design of the reference ``nn.Layer``
(``python/paddle/nn/layer/layers.py:339``): parameter/buffer/sublayer
registries, hooks, state_dict, train/eval — the module *surface* is kept,
while execution is jax eager ops + tape (no static Program attached).

The extra capability over the reference: any Layer can be captured
functionally (`paddle_tpu.jit.functional_call`) so whole training steps
compile to one XLA program — the design center of the framework.
"""
from __future__ import annotations

import collections
from typing import Callable, Iterator

import numpy as np
import jax
import jax.numpy as jnp

from ...tensor import Tensor, Parameter
from ...framework.dtype import to_jax_dtype
from .. import initializer as I

__all__ = ["Layer", "ParamAttr"]


class ParamAttr:
    """Parameter attribute bundle (ref: ``python/paddle/fluid/param_attr.py``)."""

    def __init__(self, name=None, initializer=None, learning_rate=1.0,
                 regularizer=None, trainable=True, do_model_average=True,
                 need_clip=True):
        self.name = name
        self.initializer = initializer
        self.learning_rate = learning_rate
        self.regularizer = regularizer
        self.trainable = trainable
        self.do_model_average = do_model_average
        self.need_clip = need_clip

    @staticmethod
    def _to_attr(attr):
        if attr is None:
            return ParamAttr()
        if isinstance(attr, ParamAttr):
            return attr
        if isinstance(attr, str):
            return ParamAttr(name=attr)
        if isinstance(attr, bool):
            return ParamAttr(trainable=True) if attr else None
        if isinstance(attr, I.Initializer):
            return ParamAttr(initializer=attr)
        raise TypeError(f"Cannot interpret {attr!r} as ParamAttr")


def make_parameter(shape, attr=None, dtype="float32", is_bias=False,
                   default_initializer=None):
    """Shared parameter factory behind ``Layer.create_parameter`` and the
    standalone ``paddle.create_parameter``. Honors ``LazyGuard``: under the
    guard the parameter holds a host-side numpy placeholder (NO device
    allocation) and the initializer runs at ``Parameter.initialize()``."""
    attr = ParamAttr._to_attr(attr)
    if attr is None:
        return None
    init = attr.initializer or default_initializer or (
        I.Constant(0.0) if is_bias else I.XavierNormal())
    from ...framework.lazy_init import lazy_init_active
    if lazy_init_active():
        import numpy as _np
        jdt = to_jax_dtype(dtype)
        try:
            ph_dtype = _np.dtype(jdt)  # bf16/fp16 work via ml_dtypes
        except TypeError:
            ph_dtype = _np.float32
        p = Parameter(_np.zeros((), _np.float32), name=attr.name,
                      trainable=attr.trainable)
        # host placeholder, rebound after ctor so jnp.asarray never runs
        # on the full shape (a model built under the guard must not touch
        # device HBM)
        p._data = _np.zeros(tuple(int(s) for s in shape), dtype=ph_dtype)
        p._lazy = (init, tuple(int(s) for s in shape), jdt)
    else:
        data = init(shape, to_jax_dtype(dtype))
        p = Parameter(data, name=attr.name, trainable=attr.trainable)
    p.optimize_attr = {"learning_rate": attr.learning_rate}
    p.regularizer = attr.regularizer
    p.do_model_average = attr.do_model_average
    p.need_clip = attr.need_clip if hasattr(attr, "need_clip") else True
    return p


class HookRemoveHelper:
    def __init__(self, hooks, hook_id):
        self._hooks = hooks
        self._hook_id = hook_id

    def remove(self):
        self._hooks.pop(self._hook_id, None)


class Layer:
    """Base class for all neural network layers."""

    def __init__(self, name_scope=None, dtype="float32"):
        # use object.__setattr__ to bypass our routing during init
        d = self.__dict__
        d["_parameters"] = collections.OrderedDict()
        d["_buffers"] = collections.OrderedDict()
        d["_non_persistable_buffer_names_set"] = set()
        d["_sub_layers"] = collections.OrderedDict()
        d["_forward_pre_hooks"] = collections.OrderedDict()
        d["_forward_post_hooks"] = collections.OrderedDict()
        d["training"] = True
        d["_dtype"] = dtype
        d["_name_scope"] = name_scope or type(self).__name__.lower()
        d["_hook_id"] = 0

    # -- forward ------------------------------------------------------------
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError(
            f"{type(self).__name__} must implement forward()")

    # the name this layer was registered under in its parent (attribute
    # name, container index); loss layers default to "loss".  __call__
    # runs under jax.named_scope(<it>), so nesting spells the layer's
    # path in HLO op names and profiler traces ("gpt/layers/3/attn/...").
    # A root layer has none and adds no scope.  A container that is never
    # called itself (LayerList, LayerDict) is transparent: its children
    # carry "<its name>/<their key>".
    _scope_name = None
    _scope_transparent = False

    def __call__(self, *inputs, **kwargs):
        if self._scope_name is None:
            return self._call_impl(inputs, kwargs)
        with jax.named_scope(self._scope_name):
            return self._call_impl(inputs, kwargs)

    def _call_impl(self, inputs, kwargs):
        for hook in list(self._forward_pre_hooks.values()):
            result = hook(self, inputs)
            if result is not None:
                inputs = result if isinstance(result, tuple) else (result,)
        outputs = self.forward(*inputs, **kwargs)
        for hook in list(self._forward_post_hooks.values()):
            result = hook(self, inputs, outputs)
            if result is not None:
                outputs = result
        return outputs

    # -- parameter creation --------------------------------------------------
    def create_parameter(self, shape, attr=None, dtype=None, is_bias=False,
                         default_initializer=None):
        """ref: ``layers.py create_parameter`` — default init is Xavier for
        weights, zeros for bias, matching the reference's defaults."""
        return make_parameter(shape, attr=attr,
                              dtype=dtype or self._dtype or "float32",
                              is_bias=is_bias,
                              default_initializer=default_initializer)

    def add_parameter(self, name, parameter):
        if parameter is not None and not isinstance(parameter, Parameter):
            raise TypeError("add_parameter expects a Parameter or None")
        self._parameters[name] = parameter
        return parameter

    def register_buffer(self, name, tensor, persistable=True):
        self._buffers[name] = tensor
        if tensor is not None:
            # scope-resident in static mode (not a baked constant), and
            # included in checkpoints — ref framework.py persistable vars
            tensor.persistable = persistable
        if not persistable:
            self._non_persistable_buffer_names_set.add(name)
        else:
            self._non_persistable_buffer_names_set.discard(name)
        return tensor

    def add_sublayer(self, name, sublayer):
        if sublayer is not None and not isinstance(sublayer, Layer):
            raise TypeError("add_sublayer expects a Layer or None")
        self._sub_layers[name] = sublayer
        _set_scope_name(sublayer, name, self)
        return sublayer

    # -- attribute routing ---------------------------------------------------
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call Layer.__init__ before assigning params")
            _remove_from(name, buffers, layers, self.__dict__)
            params[name] = value
        elif isinstance(value, Layer):
            if layers is None:
                raise RuntimeError("call Layer.__init__ before assigning layers")
            _remove_from(name, params, buffers, self.__dict__)
            layers[name] = value
            _set_scope_name(value, name, self)
        elif params is not None and name in params:
            params[name] = value
        elif layers is not None and name in layers:
            layers[name] = value
        elif buffers is not None and name in buffers:
            buffers[name] = value
        elif isinstance(value, Tensor) and buffers is not None and \
                not name.startswith("_"):
            # plain tensors assigned as attributes become (persistable)
            # buffers, matching the reference's behavior
            self.__dict__.pop(name, None)
            buffers[name] = value
        else:
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        # only called when normal lookup fails
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(
            f"'{type(self).__name__}' object has no attribute '{name}'")

    def __delattr__(self, name):
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    def __dir__(self):
        return list(super().__dir__()) + list(self._parameters) + \
            list(self._buffers) + list(self._sub_layers)

    # -- traversal -----------------------------------------------------------
    def parameters(self, include_sublayers=True) -> list:
        return [p for _, p in self.named_parameters(
            include_sublayers=include_sublayers)]

    def named_parameters(self, prefix="", include_sublayers=True,
                         remove_duplicate=True) -> Iterator:
        seen = set()
        for name, layer in self.named_sublayers(prefix=prefix,
                                                include_self=True):
            if not include_sublayers and layer is not self:
                continue
            for pname, p in layer._parameters.items():
                if p is None or (remove_duplicate and id(p) in seen):
                    continue
                seen.add(id(p))
                yield (f"{name}.{pname}" if name else pname), p

    def buffers(self, include_sublayers=True) -> list:
        return [b for _, b in self.named_buffers(
            include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True) -> Iterator:
        seen = set()
        for name, layer in self.named_sublayers(prefix=prefix,
                                                include_self=True):
            if not include_sublayers and layer is not self:
                continue
            for bname, b in layer._buffers.items():
                if b is None or id(b) in seen:
                    continue
                seen.add(id(b))
                yield (f"{name}.{bname}" if name else bname), b

    def children(self) -> Iterator["Layer"]:
        for _, l in self.named_children():
            yield l

    def named_children(self) -> Iterator:
        seen = set()
        for name, l in self._sub_layers.items():
            if l is not None and id(l) not in seen:
                seen.add(id(l))
                yield name, l

    def sublayers(self, include_self=False) -> list:
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def named_sublayers(self, prefix="", include_self=False,
                        layers_set=None) -> Iterator:
        if layers_set is None:
            layers_set = set()
        if id(self) in layers_set:
            return
        layers_set.add(id(self))
        if include_self:
            yield prefix, self
        for name, l in self._sub_layers.items():
            if l is None:
                continue
            sub_prefix = f"{prefix}.{name}" if prefix else name
            yield from l.named_sublayers(prefix=sub_prefix, include_self=True,
                                         layers_set=layers_set)

    # -- state dict ----------------------------------------------------------
    def state_dict(self, destination=None, include_sublayers=True,
                   structured_name_prefix="", use_hook=True):
        dest = destination if destination is not None else \
            collections.OrderedDict()
        for name, p in self.named_parameters(
                prefix=structured_name_prefix.rstrip("."),
                include_sublayers=include_sublayers):
            dest[name] = p
        for name, layer in self.named_sublayers(include_self=True):
            for bname, b in layer._buffers.items():
                if b is None or bname in layer._non_persistable_buffer_names_set:
                    continue
                full = f"{name}.{bname}" if name else bname
                if structured_name_prefix:
                    full = structured_name_prefix + full
                dest[full] = b
        return dest

    def set_state_dict(self, state_dict, use_structured_name=True):
        """Returns (missing_keys, unexpected_keys) like the reference."""
        own = self.state_dict()
        missing, unexpected = [], []
        for k in own:
            if k not in state_dict:
                missing.append(k)
        for k, v in state_dict.items():
            if k not in own:
                unexpected.append(k)
                continue
            target = own[k]
            arr = v._data if isinstance(v, Tensor) else jnp.asarray(np.asarray(v))
            if tuple(arr.shape) != tuple(target._data.shape):
                raise ValueError(
                    f"shape mismatch for {k}: loaded {tuple(arr.shape)} vs "
                    f"expected {tuple(target._data.shape)}")
            target._data = arr.astype(target._data.dtype)
        return missing, unexpected

    load_dict = set_state_dict
    set_dict = set_state_dict

    # -- mode / movement -----------------------------------------------------
    def train(self):
        self.training = True
        for l in self.sublayers():
            l.training = True
        return self

    def eval(self):
        self.training = False
        for l in self.sublayers():
            l.training = False
        return self

    def apply(self, fn: Callable):
        for l in self.children():
            l.apply(fn)
        fn(self)
        return self

    def to(self, device=None, dtype=None, blocking=None):
        import jax
        from ...framework.device import get_jax_device
        dev = get_jax_device(device) if device is not None else None
        dt = to_jax_dtype(dtype) if dtype is not None else None
        for t in list(self.parameters()) + list(self.buffers()):
            d = t._data
            if dt is not None and np.dtype(d.dtype).kind == "f":
                d = d.astype(dt)
            if dev is not None:
                d = jax.device_put(d, dev)
            t._data = d
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    def float(self):
        return self.to(dtype="float32")

    def bfloat16(self):
        return self.to(dtype="bfloat16")

    # -- hooks ---------------------------------------------------------------
    def register_forward_pre_hook(self, hook):
        self._hook_id += 1
        self._forward_pre_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_pre_hooks, self._hook_id)

    def register_forward_post_hook(self, hook):
        self._hook_id += 1
        self._forward_post_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_post_hooks, self._hook_id)

    # -- misc ----------------------------------------------------------------
    def full_name(self):
        return self._name_scope

    def extra_repr(self):
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, l in self._sub_layers.items():
            mod_str = repr(l)
            mod_str = _addindent(mod_str, 2)
            lines.append(f"({name}): {mod_str}")
        main = type(self).__name__ + "("
        if extra:
            main += extra
        if lines:
            main += "\n  " + "\n  ".join(lines) + "\n"
        return main + ")"

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()


def _set_scope_name(layer, name, parent):
    """Stamp ``layer`` with the name ``parent`` registered it under."""
    if layer is None:
        return
    name = str(name)
    if parent._scope_transparent and parent._scope_name:
        name = f"{parent._scope_name}/{name}"
    layer.__dict__["_scope_name"] = name
    if layer._scope_transparent:
        for key, sub in layer._sub_layers.items():
            _set_scope_name(sub, key, layer)


def _remove_from(name, *dicts):
    for d in dicts:
        if d is not None and name in d:
            del d[name]


def _addindent(s, n):
    lines = s.split("\n")
    if len(lines) == 1:
        return s
    return lines[0] + "\n" + "\n".join(" " * n + l for l in lines[1:])
