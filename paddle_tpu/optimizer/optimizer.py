"""Optimizer base + SGD family.

TPU-native re-design of the reference optimizer stack
(``python/paddle/optimizer/optimizer.py``; ``step`` at ``:1558`` dispatching
to fused CUDA kernels like ``_C_ops.adam_``):

 - every optimizer defines one pure function ``_update(p, g, state, lr,
   **hyper)`` over raw arrays. Eagerly it runs jitted-with-donation (the
   fused-kernel equivalent — XLA fuses the whole update into one kernel);
   under ``to_static`` training the same function is traced into the single
   train-step program.
 - master weights (fp32 copies for bf16/fp16 params) replace the reference's
   multi_precision machinery; enabled automatically for low-precision params.
"""
from __future__ import annotations

import functools
from collections import OrderedDict

import numpy as np
import jax
import jax.numpy as jnp

from ..tensor import Tensor, Parameter
from ..nn.clip import ClipGradBase
from .lr import LRScheduler

__all__ = ["Optimizer", "SGD", "Momentum", "Adagrad", "Adadelta", "RMSProp"]


def _is_low_precision(dt):
    return np.dtype(dt) in (np.dtype(np.float16), jnp.bfloat16)


class Optimizer:
    """Base class (ref: optimizer.py Optimizer)."""

    # subclasses override: state slot names created per parameter
    _state_slots: tuple = ()
    _hyper: dict = {}

    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None, multi_precision=True):
        if parameters is None:
            from ..jit.api import in_dynamic_mode
            if in_dynamic_mode():
                raise ValueError(
                    "parameters must be given in dygraph mode "
                    "(pass model.parameters())")
            parameters = []  # static mode: minimize() finds params via graph
        self._parameter_list = list(parameters)
        self._learning_rate = learning_rate
        self._grad_clip = grad_clip
        self._multi_precision = multi_precision
        if isinstance(weight_decay, float):
            self._weight_decay = weight_decay
            self._wd_mode = "l2"  # L2Decay: applied to grad
        elif weight_decay is not None:
            self._weight_decay = getattr(weight_decay, "_coeff",
                                         getattr(weight_decay, "coeff", 0.0))
            from ..regularizer import L1Decay
            self._wd_mode = "l1" if isinstance(weight_decay, L1Decay) else "l2"
        else:
            self._weight_decay = 0.0
            self._wd_mode = "l2"
        # per-param state: {slot_name: {param_name: array}}
        self._accumulators: dict = {s: {} for s in self._state_slots}
        self._master_weights: dict = {}
        self._global_step = 0
        self._update_jit = None

    # -- lr ------------------------------------------------------------------
    def get_lr(self) -> float:
        if isinstance(self._learning_rate, LRScheduler):
            return float(self._learning_rate())
        return float(self._learning_rate)

    def set_lr(self, value):
        if isinstance(self._learning_rate, LRScheduler):
            raise RuntimeError(
                "cannot set_lr when the learning rate is a scheduler")
        self._learning_rate = float(value)

    def set_lr_scheduler(self, scheduler):
        self._learning_rate = scheduler

    # -- state ---------------------------------------------------------------
    def _ensure_state(self, p: Tensor):
        key = p.name
        for slot in self._state_slots:
            if key not in self._accumulators[slot]:
                self._accumulators[slot][key] = self._init_slot(slot, p)
        if self._multi_precision and _is_low_precision(p._data.dtype) and \
                key not in self._master_weights:
            self._master_weights[key] = p._data.astype(jnp.float32)

    def _init_slot(self, slot, p):
        return jnp.zeros_like(
            p._data, dtype=jnp.float32 if _is_low_precision(p._data.dtype)
            else p._data.dtype)

    # -- the pure update (override) ------------------------------------------
    @staticmethod
    def _update(p, g, state, lr, **hyper):
        """(param, grad, state tuple, lr) -> (new_param, new_state tuple).
        Computed in fp32 when a master weight is threaded as `p`."""
        raise NotImplementedError

    # set while jit.capture_step traces this optimizer: step() must run
    # the pure tree update over the THREADED state (tracer step counter,
    # runtime lr) — the eager per-param path would bake this trace's
    # global_step as a constant into the compiled program
    _capture_hook = None

    # -- eager step ----------------------------------------------------------
    def step(self):
        # the named scope `optimizer` names the update's operations in
        # HLO and in a profiler trace (a captured step is one program:
        # this is how its update is told from its backward)
        with jax.named_scope("optimizer"):
            self._step()

    def _step(self):
        if self._capture_hook is not None:
            self._capture_hook(self)
            return
        self._global_step += 1
        params_grads = [(p, p.grad) for p in self._parameter_list
                        if not p.stop_gradient and p.grad is not None]
        if self._grad_clip is not None:
            params_grads = self._grad_clip(params_grads)
        if self._update_jit is None:
            hyper = dict(self._hyper)
            cls = type(self)
            wd_mode = self._wd_mode

            # one jitted fused update, cached by XLA per (shape, dtype) —
            # the analog of the reference's fused adam/momentum CUDA kernels
            @functools.partial(jax.jit, donate_argnums=(0, 2))
            def upd(p, g, state, lr, wd, step, master):
                compute = master if master is not None else p
                g = g.astype(compute.dtype)
                if not cls._decoupled_wd:
                    # wd==0 is the common case; the extra fused multiply-add
                    # is free inside the XLA kernel
                    g = g + (wd * jnp.sign(compute) if wd_mode == "l1"
                             else wd * compute)
                new_p, new_state = cls._update(
                    compute, g, state, lr, step=step, **hyper)
                if cls._decoupled_wd:
                    new_p = new_p - lr * wd * compute
                if master is not None:
                    return new_p.astype(p.dtype), new_state, new_p
                return new_p, new_state, None
            self._update_jit = upd
        lr = self.get_lr()
        step_arr = jnp.int32(self._global_step)
        for p, g in params_grads:
            self._ensure_state(p)
            key = p.name
            state = tuple(self._accumulators[s][key]
                          for s in self._state_slots)
            master = self._master_weights.get(key)
            p_lr = lr * p.optimize_attr.get("learning_rate", 1.0) \
                if isinstance(p, Parameter) else lr
            wd = self._param_weight_decay(p)
            new_p, new_state, new_master = self._update_jit(
                p._data, g._data, state, jnp.float32(p_lr), jnp.float32(wd),
                step_arr, master)
            p._data = new_p
            for s, v in zip(self._state_slots, new_state):
                self._accumulators[s][key] = v
            if new_master is not None:
                self._master_weights[key] = new_master

    def _param_weight_decay(self, p):
        reg = getattr(p, "regularizer", None)
        if reg is not None:
            return getattr(reg, "_coeff", getattr(reg, "coeff", 0.0))
        return self._weight_decay

    # False: L2 folded into grad (SGD/Momentum); True: decoupled (AdamW)
    _decoupled_wd = False

    def clear_grad(self, set_to_zero=False):
        for p in self._parameter_list:
            p.clear_grad(set_to_zero)

    clear_gradients = clear_grad

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        from ..static.graph import Variable as _StaticVar
        if isinstance(loss, _StaticVar):
            # static mode: record the fused backward+update node
            from ..static.gradients import append_minimize
            return append_minimize(self, loss, parameters=parameters)
        if loss._node is not None:
            loss.backward()
        self.step()
        return None, [(p, p.grad) for p in self._parameter_list]

    # -- functional API for jitted training steps ---------------------------
    def init_state_tree(self, params: dict):
        """params: {name: array} -> opt state pytree (for to_static/hapi)."""
        state = {s: {} for s in self._state_slots}
        master = {}
        for name, arr in params.items():
            for s in self._state_slots:
                state[s][name] = jnp.zeros_like(
                    arr, dtype=jnp.float32 if _is_low_precision(arr.dtype)
                    else arr.dtype)
            if self._multi_precision and _is_low_precision(arr.dtype):
                master[name] = arr.astype(jnp.float32)
        return {"slots": state, "master": master, "step": jnp.zeros((), jnp.int32)}

    def apply_gradients_tree(self, params: dict, grads: dict, state: dict,
                             lr=None):
        """Pure: (params, grads, state) -> (new_params, new_state).
        Traceable under jit; the whole tree updates in one XLA program."""
        lr = jnp.float32(self.get_lr() if lr is None else lr)
        step = state["step"] + 1
        new_params, new_slots, new_master = {}, {s: {} for s in
                                                 self._state_slots}, {}
        # grad clip over the whole tree
        if self._grad_clip is not None:
            names = list(grads)
            clipped = self._grad_clip.apply_arrays([grads[n] for n in names])
            grads = dict(zip(names, clipped))
        for name, p in params.items():
            g = grads.get(name)
            if g is None:
                new_params[name] = p
                for s in self._state_slots:
                    new_slots[s][name] = state["slots"][s][name]
                if name in state["master"]:
                    new_master[name] = state["master"][name]
                continue
            master = state["master"].get(name)
            compute = master if master is not None else p
            g = g.astype(compute.dtype)
            wd = self._weight_decay
            if wd and not self._decoupled_wd:
                g = g + (wd * jnp.sign(compute) if self._wd_mode == "l1"
                         else wd * compute)
            st = tuple(state["slots"][s][name] for s in self._state_slots)
            new_p, new_st = type(self)._update(compute, g, st, lr, step=step,
                                               **self._hyper)
            if wd and self._decoupled_wd:
                new_p = new_p - lr * wd * compute
            if master is not None:
                new_master[name] = new_p
                new_p = new_p.astype(p.dtype)
            new_params[name] = new_p
            for s, v in zip(self._state_slots, new_st):
                new_slots[s][name] = v
        return new_params, {"slots": new_slots, "master": new_master,
                            "step": step}

    # -- checkpointing -------------------------------------------------------
    def state_dict(self):
        out = {}
        for slot, d in self._accumulators.items():
            for pname, arr in d.items():
                out[f"{pname}_{slot}"] = Tensor(arr)
        for pname, arr in self._master_weights.items():
            out[f"{pname}_master"] = Tensor(arr)
        out["global_step"] = self._global_step
        if isinstance(self._learning_rate, LRScheduler):
            out["LR_Scheduler"] = self._learning_rate.state_dict()
        return out

    def set_state_dict(self, state):
        state = dict(state)
        if "LR_Scheduler" in state and isinstance(self._learning_rate,
                                                  LRScheduler):
            self._learning_rate.set_state_dict(state.pop("LR_Scheduler"))
        self._global_step = int(state.pop("global_step", 0))
        for key, val in state.items():
            arr = val._data if isinstance(val, Tensor) else jnp.asarray(
                np.asarray(val))
            if key.endswith("_master"):
                self._master_weights[key[:-7]] = arr
                continue
            for slot in self._state_slots:
                suffix = f"_{slot}"
                if key.endswith(suffix):
                    self._accumulators[slot][key[:-len(suffix)]] = arr
                    break

    @property
    def _learning_rate_scheduler(self):
        return self._learning_rate if isinstance(self._learning_rate,
                                                 LRScheduler) else None


class SGD(Optimizer):
    _state_slots = ()

    @staticmethod
    def _update(p, g, state, lr, step=0):
        return p - lr * g, state


class Momentum(Optimizer):
    """ref: optimizer/momentum.py; use_nesterov supported."""

    _state_slots = ("velocity",)

    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 multi_precision=True, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._hyper = {"momentum": momentum, "nesterov": use_nesterov}

    @staticmethod
    def _update(p, g, state, lr, step=0, momentum=0.9, nesterov=False):
        (v,) = state
        v_new = momentum * v + g
        if nesterov:
            p_new = p - lr * (g + momentum * v_new)
        else:
            p_new = p - lr * v_new
        return p_new, (v_new,)


class Adagrad(Optimizer):
    _state_slots = ("moment",)

    def __init__(self, learning_rate, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None, initial_accumulator_value=0.0,
                 multi_precision=True, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._hyper = {"epsilon": epsilon}
        self._initial_acc = initial_accumulator_value

    def _init_slot(self, slot, p):
        base = super()._init_slot(slot, p)
        return base + self._initial_acc

    @staticmethod
    def _update(p, g, state, lr, step=0, epsilon=1e-6):
        (m,) = state
        m_new = m + g * g
        return p - lr * g / (jnp.sqrt(m_new) + epsilon), (m_new,)


class Adadelta(Optimizer):
    _state_slots = ("avg_squared_grad", "avg_squared_update")

    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None,
                 multi_precision=True, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._hyper = {"epsilon": epsilon, "rho": rho}

    @staticmethod
    def _update(p, g, state, lr, step=0, epsilon=1e-6, rho=0.95):
        sg, su = state
        sg_new = rho * sg + (1 - rho) * g * g
        upd = jnp.sqrt(su + epsilon) / jnp.sqrt(sg_new + epsilon) * g
        su_new = rho * su + (1 - rho) * upd * upd
        return p - lr * upd, (sg_new, su_new)


class RMSProp(Optimizer):
    _state_slots = ("mean_square", "mean_grad", "momentum_acc")

    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=True, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision)
        self._hyper = {"rho": rho, "epsilon": epsilon, "momentum": momentum,
                       "centered": centered}

    @staticmethod
    def _update(p, g, state, lr, step=0, rho=0.95, epsilon=1e-6, momentum=0.0,
                centered=False):
        ms, mg, mom = state
        ms_new = rho * ms + (1 - rho) * g * g
        if centered:
            mg_new = rho * mg + (1 - rho) * g
            denom = jnp.sqrt(ms_new - mg_new * mg_new + epsilon)
        else:
            mg_new = mg
            denom = jnp.sqrt(ms_new + epsilon)
        mom_new = momentum * mom + lr * g / denom
        return p - mom_new, (ms_new, mg_new, mom_new)
