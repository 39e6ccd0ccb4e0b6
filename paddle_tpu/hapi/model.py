"""High-level Model API (ref: ``python/paddle/hapi/model.py:1741 Model.fit``).

TPU-native: `prepare()` builds ONE jitted train-step program
(forward + loss + backward + optimizer update, functional over params/opt
state) — the entire per-step work is a single XLA executable, which is the
performance contract the reference approximates with its static graph mode.
"""
from __future__ import annotations

import os
import time

import numpy as np
import jax
import jax.numpy as jnp

from ..tensor import Tensor
from ..nn.layer.layers import Layer
from ..metric import Metric
from ..framework import random as _random
from ..observability import get_telemetry
from ..observability.trace import get_tracer
from .. import autograd
from .callbacks import config_callbacks

__all__ = ["Model", "LossScalar"]


def _to_list(x):
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


def _arrays(batch):
    out = []
    for b in _to_list(batch):
        if isinstance(b, Tensor):
            out.append(b._data)
        else:
            out.append(jnp.asarray(np.asarray(b)))
    return out


def _unwrap(o):
    return o._sync() if isinstance(o, LossScalar) else o


class LossScalar:
    """Lazy handle over the on-device loss scalar.

    ``train_batch`` returns as soon as the step is DISPATCHED; the
    device→host copy (the per-step sync that stalls the TPU pipeline,
    tpu-lint TPU007) happens at the first read — ``float()``, a
    comparison, formatting — which in the fit loop is the callback/log
    cadence, not every batch. Reads memoize, so the sync is paid once.
    Behaves like the float it wraps everywhere the hapi loop and the
    stock callbacks consume it."""

    __slots__ = ("_arr", "_val")

    def __init__(self, arr):
        self._arr = arr
        self._val = None

    def _sync(self):
        v = self._val
        if v is None:
            v = self._val = float(np.asarray(self._arr))
            self._arr = None  # drop the device buffer once materialized
        return v

    def __float__(self):
        return self._sync()

    def __repr__(self):
        return repr(self._sync())

    def __str__(self):
        return str(self._sync())

    def __format__(self, spec):
        return format(self._sync(), spec)

    def __bool__(self):
        return bool(self._sync())

    def __hash__(self):
        return hash(self._sync())

    def __eq__(self, o):
        return self._sync() == _unwrap(o)

    def __lt__(self, o):
        return self._sync() < _unwrap(o)

    def __le__(self, o):
        return self._sync() <= _unwrap(o)

    def __gt__(self, o):
        return self._sync() > _unwrap(o)

    def __ge__(self, o):
        return self._sync() >= _unwrap(o)

    def __add__(self, o):
        return self._sync() + _unwrap(o)

    __radd__ = __add__

    def __sub__(self, o):
        return self._sync() - _unwrap(o)

    def __rsub__(self, o):
        return _unwrap(o) - self._sync()

    def __mul__(self, o):
        return self._sync() * _unwrap(o)

    __rmul__ = __mul__

    def __truediv__(self, o):
        return self._sync() / _unwrap(o)

    def __rtruediv__(self, o):
        return _unwrap(o) / self._sync()

    def __neg__(self):
        return -self._sync()

    def __array__(self, dtype=None):
        return np.asarray(self._sync(), dtype=dtype)


class Model:
    def __init__(self, network, inputs=None, labels=None):
        self.network = network
        self._inputs = inputs
        self._labels = labels
        self._optimizer = None
        self._loss = None
        self._metrics = []
        self._train_step_fn = None
        self._eval_step_fn = None
        self._opt_state = None
        self.stop_training = False
        self._monitor = None
        self._mon_names = []
        self._mon_step = 0

    # -- setup ---------------------------------------------------------------
    def prepare(self, optimizer=None, loss=None, metrics=None, amp_configs=None):
        self._optimizer = optimizer
        self._loss = loss
        self._metrics = _to_list(metrics)
        for m in self._metrics:
            assert isinstance(m, Metric), "metrics must be paddle metrics"
        self._amp = amp_configs or {}
        self._build_steps()
        return self

    def _build_steps(self):
        net = self.network
        loss_fn = self._loss
        opt = self._optimizer
        fwd = getattr(net, "_orig_forward", None)
        if fwd is None:
            fwd = net.forward
        from ..jit.api import functional_call, StaticFunction
        if isinstance(fwd, StaticFunction):
            fwd = fwd._orig_fn

        def grad_step(params, buffers, key, inputs, labels):
            def loss_of(p):
                with _random.trace_key_scope(key):
                    outs, new_buffers = functional_call(
                        net, p, buffers,
                        tuple(Tensor(x) for x in inputs),
                        training=True, forward_fn=fwd)
                outs = _to_list(outs)
                lbls = [Tensor(l) for l in labels]
                loss = loss_fn(*(outs + lbls))
                if isinstance(loss, (list, tuple)):
                    loss = loss[0]
                preds = [o._data for o in outs]
                return loss._data, (preds, new_buffers)

            (loss_v, (preds, new_buffers)), grads = jax.value_and_grad(
                loss_of, has_aux=True)(params)
            return loss_v, preds, new_buffers, grads

        def apply_step(params, grads, opt_state):
            return opt.apply_gradients_tree(params, grads, opt_state)

        # numerics sentinel: like capture, the decision is baked at
        # build/trace time so health outputs compile into the same
        # program — a monitored fit never gains a second compile or a
        # per-step host sync
        from ..observability import numerics as _numerics
        mon = _numerics.get_monitor()
        mon = mon if mon.enabled else None
        self._monitor = mon
        self._mon_names = mon_box = []
        self._mon_step = 0

        def train_step(params, buffers, opt_state, key, inputs, labels):
            loss_v, preds, new_buffers, grads = grad_step(
                params, buffers, key, inputs, labels)
            new_params, new_opt_state = apply_step(params, grads, opt_state)
            if mon is None:
                return loss_v, preds, new_params, new_buffers, new_opt_state
            names, health = _numerics.health_outputs(
                grads, loss=loss_v, with_stats=mon.stats_on)
            mon_box[:] = [names]
            return (loss_v, preds, new_params, new_buffers, new_opt_state,
                    health)

        def apply_step_mon(params, grads, opt_state, loss_v):
            # split-path twin (tracer on): health rides on the
            # optimizer program, where the grads are already in hand
            new_params, new_opt_state = apply_step(params, grads, opt_state)
            names, health = _numerics.health_outputs(
                grads, loss=loss_v, with_stats=mon.stats_on)
            mon_box[:] = [names]
            return new_params, new_opt_state, health

        def eval_step(params, buffers, inputs, labels):
            outs, _ = functional_call(
                net, params, buffers, tuple(Tensor(x) for x in inputs),
                training=False, forward_fn=fwd)
            outs = _to_list(outs)
            loss_v = None
            # `labels` is a host-side list pytree: its truthiness is the
            # arity of the batch, static under tracing, not a tensor bool
            if loss_fn is not None and labels:  # tpu-lint: disable=TPU002
                lbls = [Tensor(l) for l in labels]
                loss = loss_fn(*(outs + lbls))
                if isinstance(loss, (list, tuple)):
                    loss = loss[0]
                loss_v = loss._data
            return loss_v, [o._data for o in outs]

        # One fused program per step is the perf contract; the split
        # grad/apply pair exists ONLY for the step-phase tracer, which
        # needs a host boundary between backward and optimizer to time.
        # jax.jit is lazy, so the untaken pair never compiles.
        self._train_step_jit = jax.jit(train_step) \
            if opt is not None else None
        self._grad_step_jit = jax.jit(grad_step) \
            if opt is not None else None
        self._apply_step_jit = jax.jit(
            apply_step_mon if mon is not None else apply_step) \
            if opt is not None else None
        self._eval_step_jit = jax.jit(eval_step)

    def _param_arrays(self):
        return {k: p._data for k, p in self.network.named_parameters()}

    def _buffer_arrays(self):
        return {k: b._data for k, b in self.network.named_buffers()}

    def _write_back(self, params, buffers):
        named_p = dict(self.network.named_parameters())
        for k, v in params.items():
            named_p[k]._data = v
        named_b = dict(self.network.named_buffers())
        for k, v in buffers.items():
            named_b[k]._data = v

    # -- single-batch paths --------------------------------------------------
    def train_batch(self, inputs, labels=None, update=True):
        with autograd.functional_guard():
            params = self._param_arrays()
            buffers = self._buffer_arrays()
            if self._opt_state is None:
                self._opt_state = self._optimizer.init_state_tree(params)
            key = _random.next_key()
            tr = get_tracer()
            mon = self._monitor
            health = None
            try:
                if tr.enabled:
                    # split path: "backward" is the fused forward+backward
                    # value_and_grad program (no pure-forward phase exists
                    # in a train step), "optimizer" the parameter update.
                    # Spans time dispatch — never a forced device sync.
                    with tr.phase("backward"):
                        loss_v, preds, new_buffers, grads = \
                            self._grad_step_jit(
                                params, buffers, key,
                                _arrays(inputs), _arrays(labels))
                    with tr.phase("optimizer"):
                        if mon is not None:
                            new_params, new_opt, health = \
                                self._apply_step_jit(
                                    params, grads, self._opt_state, loss_v)
                        else:
                            new_params, new_opt = self._apply_step_jit(
                                params, grads, self._opt_state)
                elif mon is not None:
                    (loss_v, preds, new_params, new_buffers, new_opt,
                     health) = self._train_step_jit(
                        params, buffers, self._opt_state, key,
                        _arrays(inputs), _arrays(labels))
                else:
                    loss_v, preds, new_params, new_buffers, new_opt = \
                        self._train_step_jit(params, buffers,
                                             self._opt_state,
                                             key, _arrays(inputs),
                                             _arrays(labels))
            except Exception as e:
                self._book_oom("hapi.train_batch", e)
                raise
            if update:
                self._write_back(new_params, new_buffers)
                self._opt_state = new_opt
                if self._optimizer._learning_rate_scheduler is not None:
                    pass  # stepped per-epoch by callbacks/fit
            if mon is not None and health is not None and self._mon_names:
                # after the writeback so a PT_NUMERICS_HALT raise leaves
                # the model in the post-step state (same as capture)
                step_i = self._mon_step
                self._mon_step += 1
                mon.watch(step_i, self._mon_names[0], health)
        metrics_out = []
        for m in self._metrics:
            corr = m.compute(Tensor(preds[0]), Tensor(_arrays(labels)[0]))
            metrics_out.append(m.update(corr))
        # lazy: the step stays dispatched-but-unread until a callback or
        # caller actually looks at the number (LossScalar docstring)
        loss_out = [LossScalar(loss_v)]
        return (loss_out, metrics_out) if metrics_out else loss_out

    def _book_oom(self, program, exc):
        """RESOURCE_EXHAUSTED intercept for the hapi step paths: pin
        the memory postmortem (census attributed to this network's
        parameter paths) before the error propagates — same trip path
        as ``jit.capture``. Never raises; callers re-raise."""
        try:
            from ..observability import memory as _memory
            if not _memory.is_oom_error(exc):
                return
            named = {f"param::{k}": p._data
                     for k, p in self.network.named_parameters()}
            named.update({f"buffer::{k}": b._data
                          for k, b in self.network.named_buffers()})
            _memory.oom_postmortem(program=program, exc=exc,
                                   extra_named=named)
        except Exception:
            pass

    def eval_batch(self, inputs, labels=None):
        with autograd.functional_guard():
            try:
                with get_tracer().phase("forward"):
                    loss_v, preds = self._eval_step_jit(
                        self._param_arrays(), self._buffer_arrays(),
                        _arrays(inputs), _arrays(labels))
            except Exception as e:
                self._book_oom("hapi.eval_batch", e)
                raise
        metrics_out = []
        for m in self._metrics:
            corr = m.compute(Tensor(preds[0]), Tensor(_arrays(labels)[0]))
            metrics_out.append(m.update(corr))
        loss_out = [float(np.asarray(loss_v))] if loss_v is not None else []
        return (loss_out, metrics_out) if metrics_out else loss_out

    def predict_batch(self, inputs):
        with autograd.functional_guard():
            try:
                with get_tracer().phase("forward"):
                    _, preds = self._eval_step_jit(
                        self._param_arrays(), self._buffer_arrays(),
                        _arrays(inputs), [])
            except Exception as e:
                self._book_oom("hapi.predict_batch", e)
                raise
        return [Tensor(p) for p in preds]

    # -- loops ---------------------------------------------------------------
    def fit(self, train_data=None, eval_data=None, batch_size=1, epochs=1,
            eval_freq=1, log_freq=10, save_dir=None, save_freq=1, verbose=2,
            drop_last=False, shuffle=True, num_workers=0, callbacks=None,
            accumulate_grad_batches=1, num_iters=None):
        from ..io import DataLoader, Dataset
        if isinstance(train_data, Dataset):
            train_loader = DataLoader(train_data, batch_size=batch_size,
                                      shuffle=shuffle, drop_last=drop_last,
                                      num_workers=num_workers)
        else:
            train_loader = train_data
        if eval_data is not None and isinstance(eval_data, Dataset):
            eval_loader = DataLoader(eval_data, batch_size=batch_size,
                                     num_workers=num_workers)
        else:
            eval_loader = eval_data

        try:
            steps = len(train_loader)
        except TypeError:
            steps = None
        cbks = config_callbacks(
            callbacks, model=self, epochs=epochs, steps=steps,
            log_freq=log_freq, save_freq=save_freq, save_dir=save_dir,
            verbose=verbose,
            metrics=["loss"] + [n for m in self._metrics
                                for n in _to_list(m.name())])
        cbks.on_begin("train")
        for epoch in range(epochs):
            if self.stop_training:
                break
            cbks.on_epoch_begin(epoch)
            logs = self._run_one_epoch(train_loader, cbks, "train")
            if self._optimizer is not None and \
                    self._optimizer._learning_rate_scheduler is not None:
                self._optimizer._learning_rate_scheduler.step()
            # eval metrics merge BEFORE on_epoch_end so callbacks can
            # monitor eval_loss/eval_acc (ReduceLROnPlateau etc.)
            if eval_loader is not None and (epoch + 1) % eval_freq == 0:
                eval_logs = self.evaluate(eval_loader, verbose=0)
                logs.update({f"eval_{k}": v for k, v in eval_logs.items()})
            cbks.on_epoch_end(epoch, logs)
        cbks.on_end("train", logs)
        return self

    def _run_one_epoch(self, loader, cbks, mode):
        for m in self._metrics:
            m.reset()
        logs = {}
        tel = get_telemetry()
        for step, batch in enumerate(loader):
            batch = _to_list(batch)
            # convention: last element is the label set
            inputs, labels = batch[:-1], batch[-1:]
            if len(batch) == 1:
                inputs, labels = batch, []
            cbks.on_batch_begin(mode, step, logs)
            tok = tel.step_start()
            if mode == "train":
                out = self.train_batch(inputs, labels)
            else:
                out = self.eval_batch(inputs, labels)
            tel.step_end(tok, mode=mode,
                         batch_size=(np.shape(labels[0])[0]
                                     if labels else None))
            if isinstance(out, tuple):
                losses, metrics = out
            else:
                losses, metrics = out, []
            logs["loss"] = losses[0] if losses else None
            names = [n for m in self._metrics for n in _to_list(m.name())]
            for n, v in zip(names, metrics):
                # per-batch metric materialization is the callback
                # contract (on_batch_end receives floats, ref hapi)
                # tpu-lint: disable=TPU007
                logs[n] = float(np.asarray(v)) if not isinstance(v, list) \
                    else [float(x) for x in v]
            # np.shape reads metadata without copying device arrays to
            # host (np.asarray here forced a full transfer per batch)
            logs["batch_size"] = (np.shape(labels[0])[0]
                                  if labels else None)
            cbks.on_batch_end(mode, step, logs)
        return logs

    def evaluate(self, eval_data, batch_size=1, log_freq=10, verbose=2,
                 num_workers=0, callbacks=None, num_iters=None):
        from ..io import DataLoader, Dataset
        loader = DataLoader(eval_data, batch_size=batch_size,
                            num_workers=num_workers) \
            if isinstance(eval_data, Dataset) else eval_data
        for m in self._metrics:
            m.reset()
        total_loss, n = 0.0, 0
        tel = get_telemetry()
        for batch in loader:
            batch = _to_list(batch)
            inputs, labels = batch[:-1], batch[-1:]
            tok = tel.step_start()
            out = self.eval_batch(inputs, labels)
            tel.step_end(tok, mode="eval",
                         batch_size=(np.shape(labels[0])[0]
                                     if labels else None))
            losses = out[0] if isinstance(out, tuple) else out
            if losses:
                total_loss += losses[0]
                n += 1
        logs = {"loss": total_loss / max(n, 1)}
        for m in self._metrics:
            acc = m.accumulate()
            for name, v in zip(_to_list(m.name()), _to_list(acc)):
                logs[name] = v
        return logs

    def predict(self, test_data, batch_size=1, num_workers=0,
                stack_outputs=False, callbacks=None, verbose=1):
        from ..io import DataLoader, Dataset
        loader = DataLoader(test_data, batch_size=batch_size,
                            num_workers=num_workers) \
            if isinstance(test_data, Dataset) else test_data
        outputs = []
        for batch in loader:
            batch = _to_list(batch)
            preds = self.predict_batch(batch[:1])
            outputs.append([p.numpy() for p in preds])
        if stack_outputs:
            n_out = len(outputs[0])
            return [np.concatenate([o[i] for o in outputs])
                    for i in range(n_out)]
        return outputs

    # -- io -----------------------------------------------------------------
    def save(self, path, training=True, sharded=False):
        from ..framework.io_state import save as _save
        if sharded:
            # distributed checkpoint: per-host shard files, reshardable on
            # load (ref: auto_parallel dist_saver)
            from ..distributed.checkpoint import save_sharded
            params = {k: t._data for k, t in
                      self.network.state_dict().items()}
            tree = {"params": params}
            if training and self._optimizer is not None:
                # hapi's compiled train step keeps optimizer state in
                # _opt_state (never the eager accumulators) — that tree
                # is the source of truth; zeros if training hasn't started
                tree["opt_tree"] = (
                    self._opt_state if self._opt_state is not None
                    else self._optimizer.init_state_tree(params))
            save_sharded(tree, path)
            return
        if training:
            _save(self.network.state_dict(), path + ".pdparams")
            if self._optimizer is not None:
                _save(self._optimizer.state_dict(), path + ".pdopt")
        else:
            from ..jit import save as jit_save, InputSpec
            if self._inputs is None:
                raise ValueError("save(training=False) requires inputs= spec")
            jit_save(self.network, path, input_spec=self._inputs)

    def load(self, path, skip_mismatch=False, reset_optimizer=False):
        from ..framework.io_state import load as _load
        if os.path.isdir(path):  # sharded checkpoint directory
            from ..distributed.checkpoint import load_sharded
            from ..distributed.checkpoint_manager import latest_checkpoint
            from ..tensor import Tensor
            # a CheckpointManager root (step_<n> subdirs) resolves to its
            # newest committed-and-valid step
            resolved = latest_checkpoint(path)
            if resolved is not None:
                path = resolved
            tree = load_sharded(path)
            self.network.set_state_dict(
                {k: Tensor(v) for k, v in tree["params"].items()})
            if not reset_optimizer and self._optimizer is not None and \
                    "opt_tree" in tree:
                ot = tree["opt_tree"]
                # empty subtrees (no master weights / slot-less SGD) have
                # no leaves to save — restore their containers
                ot.setdefault("slots", {})
                ot.setdefault("master", {})
                for s in self._optimizer._state_slots:
                    ot["slots"].setdefault(s, {})
                self._opt_state = ot
            return
        state = _load(path + ".pdparams") if os.path.exists(
            path + ".pdparams") else _load(path)
        self.network.set_state_dict(state)
        if not reset_optimizer and self._optimizer is not None and \
                os.path.exists(path + ".pdopt"):
            self._optimizer.set_state_dict(_load(path + ".pdopt"))

    def parameters(self, *args, **kwargs):
        return self.network.parameters()

    def summary(self, input_size=None, dtype=None):
        from .summary import summary as _summary
        return _summary(self.network, input_size, dtypes=dtype)
