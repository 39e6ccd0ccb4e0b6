"""Tensor-parallel layers.

ref: ``python/paddle/distributed/fleet/layers/mpu/mp_layers.py``
(``VocabParallelEmbedding :35``, ``ColumnParallelLinear :173``,
``RowParallelLinear :343``, ``ParallelCrossEntropy :524``).

TPU-native design — two execution modes from ONE layer:

 - **GSPMD mode (default)**: the layer holds the FULL logical weight with a
   ``PartitionSpec`` annotation (``Tensor._spec``); forward is plain math
   plus ``with_sharding_constraint`` hints. Under ``jit`` over the global
   mesh, XLA partitions the weight over the ``mp`` axis and inserts the
   same collectives Megatron does by hand — this replaces the reference's
   explicit ``_c_identity/_mp_allreduce`` wiring.
 - **Manual-SPMD mode**: when traced inside ``shard_map`` with the ``mp``
   axis in scope (per-rank weight blocks), forward uses the explicit
   ``mp_ops`` custom-vjp collectives — bit-for-bit the reference's
   comm placement, used by the pipeline schedule and tests.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .....tensor import Tensor
from .....nn.layer.layers import Layer
from .....nn import initializer as I
from .... import mesh as _mesh_mod
from ....collective import _in_axis_scope
from .. import mp_ops

__all__ = ["VocabParallelEmbedding", "ColumnParallelLinear",
           "RowParallelLinear", "ParallelCrossEntropy"]

_MP = "mp"


def _layout():
    # parameter specs come from the canonical layout table (lazy: the
    # auto_parallel package imports the engine, which imports fleet)
    from ....auto_parallel.spec_layout import default_layout
    return default_layout()


def _mp_degree(mp_group):
    if mp_group is not None:
        return mp_group.nranks
    return _mesh_mod.mesh_axis_size(_MP)


def _constraint(arr, spec):
    """Sharding hint under jit when a global mesh exists; no-op eager."""
    mesh = _mesh_mod.get_mesh(create_default=False)
    if mesh is None or not isinstance(arr, jax.core.Tracer):
        return arr
    try:
        return lax.with_sharding_constraint(arr, NamedSharding(mesh, spec))
    except Exception:
        return arr


class VocabParallelEmbedding(Layer):
    """Embedding with the vocab dim split over mp (ref: mp_layers.py:35)."""

    def __init__(self, num_embeddings, embedding_dim, weight_attr=None,
                 mp_group=None, name=None):
        super().__init__()
        self.num_embeddings = num_embeddings
        self.embedding_dim = embedding_dim
        self.mp_group = mp_group
        self.world_size = _mp_degree(mp_group)
        if num_embeddings % max(self.world_size, 1):
            raise ValueError(
                f"vocab {num_embeddings} not divisible by mp degree "
                f"{self.world_size}")
        self.weight = self.create_parameter(
            [num_embeddings, embedding_dim], attr=weight_attr,
            default_initializer=I.XavierNormal())
        self.weight._spec = _layout().vocab_embedding()
        self.weight.is_distributed = self.world_size > 1

    def forward(self, x):
        ax = self.mp_group.axis_name if self.mp_group else _MP
        idx = x._data if isinstance(x, Tensor) else jnp.asarray(x)
        w = self.weight._data
        if _in_axis_scope(ax):
            # manual mode: w is the local vocab block
            n = self.world_size
            per = w.shape[0]
            i = lax.axis_index(ax)
            start = i * per
            mask = (idx >= start) & (idx < start + per)
            local = jnp.clip(idx - start, 0, per - 1)
            out = jnp.where(mask[..., None], jnp.take(w, local, axis=0), 0.0)
            out_t = Tensor(out, stop_gradient=False)
            return mp_ops._mp_allreduce(out_t, self.mp_group)
        # GSPMD mode: full gather; XLA partitions the table over mp
        from .....nn import functional as F
        out = F.embedding(x if isinstance(x, Tensor) else Tensor(x),
                          self.weight)
        out._data = _constraint(out._data, P())
        return out


class ColumnParallelLinear(Layer):
    """Linear with the OUT dim split over mp (ref: mp_layers.py:173).
    Forward comm: identity (f op); backward: all-reduce of input grad."""

    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, gather_output=True, fuse_matmul_bias=False,
                 mp_group=None, name=None):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        self.gather_output = gather_output
        self.mp_group = mp_group
        self.world_size = _mp_degree(mp_group)
        if out_features % max(self.world_size, 1):
            raise ValueError(
                f"out_features {out_features} not divisible by mp degree "
                f"{self.world_size}")
        self.weight = self.create_parameter(
            [in_features, out_features], attr=weight_attr)
        self.weight._spec = _layout().column_weight()
        self.weight.is_distributed = self.world_size > 1
        self.bias = self.create_parameter(
            [out_features], attr=has_bias if has_bias is not True else None,
            is_bias=True) if has_bias else None
        if self.bias is not None:
            self.bias._spec = _layout().column_bias()
            self.bias.is_distributed = self.world_size > 1

    def forward(self, x):
        ax = self.mp_group.axis_name if self.mp_group else _MP
        if _in_axis_scope(ax):
            x = mp_ops._c_identity(x, self.mp_group)
            a = x._data if isinstance(x, Tensor) else x
            y = a @ self.weight._data
            if self.bias is not None:
                y = y + self.bias._data
            out = Tensor(y, stop_gradient=False)
            if self.gather_output:
                out = mp_ops._c_concat(out, self.mp_group)
            return out
        from .....nn import functional as F
        out = F.linear(x if isinstance(x, Tensor) else Tensor(x),
                       self.weight, self.bias)
        out._data = _constraint(
            out._data, P() if self.gather_output
            else P(*([None] * (out.ndim - 1) + [_MP])))
        return out


class RowParallelLinear(Layer):
    """Linear with the IN dim split over mp (ref: mp_layers.py:343).
    Forward comm: all-reduce of partial sums (g op)."""

    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, input_is_parallel=False,
                 fuse_matmul_bias=False, mp_group=None, name=None):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        self.input_is_parallel = input_is_parallel
        self.mp_group = mp_group
        self.world_size = _mp_degree(mp_group)
        if in_features % max(self.world_size, 1):
            raise ValueError(
                f"in_features {in_features} not divisible by mp degree "
                f"{self.world_size}")
        self.weight = self.create_parameter(
            [in_features, out_features], attr=weight_attr)
        self.weight._spec = _layout().row_weight()
        self.weight.is_distributed = self.world_size > 1
        # bias is replicated, added AFTER the reduce (ref :411)
        self.bias = self.create_parameter(
            [out_features], attr=has_bias if has_bias is not True else None,
            is_bias=True) if has_bias else None

    def forward(self, x):
        ax = self.mp_group.axis_name if self.mp_group else _MP
        if _in_axis_scope(ax):
            if not self.input_is_parallel:
                x = mp_ops._c_split(x, self.mp_group)
            a = x._data if isinstance(x, Tensor) else x
            y = a @ self.weight._data
            out = mp_ops._mp_allreduce(Tensor(y, stop_gradient=False),
                                       self.mp_group)
            if self.bias is not None:
                out = Tensor(out._data + self.bias._data,
                             stop_gradient=False)
            return out
        from .....nn import functional as F
        xt = x if isinstance(x, Tensor) else Tensor(x)
        xt._data = _constraint(xt._data,
                               P(*([None] * (xt.ndim - 1) + [_MP])))
        out = F.linear(xt, self.weight, self.bias)
        out._data = _constraint(out._data, P())
        return out


class ParallelCrossEntropy(Layer):
    """Softmax cross-entropy over vocab-sharded logits (ref:
    mp_layers.py:524 → ``c_softmax_with_cross_entropy`` op). Never
    materializes the gathered [tokens, vocab] logits — max and sum-exp are
    reduced across mp with ``pmax``/``psum``; the target logit is fetched
    with a masked psum."""

    def __init__(self, mp_group=None, name=None, ignore_index=-100):
        super().__init__()
        self.mp_group = mp_group
        self.world_size = _mp_degree(mp_group)
        self.ignore_index = ignore_index

    def forward(self, input, label):
        ax = self.mp_group.axis_name if self.mp_group else _MP
        logits = input._data if isinstance(input, Tensor) else input
        y = label._data if isinstance(label, Tensor) else jnp.asarray(label)
        if y.ndim == logits.ndim:  # [.., 1] form like the reference
            y = y.squeeze(-1)
        valid = y != self.ignore_index
        y_safe = jnp.where(valid, y, 0)
        if _in_axis_scope(ax):
            n_local = logits.shape[-1]
            i = lax.axis_index(ax)
            start = i * n_local
            m = lax.pmax(jnp.max(logits, axis=-1), ax)
            shifted = logits - m[..., None]
            sumexp = lax.psum(jnp.sum(jnp.exp(shifted), axis=-1), ax)
            in_range = (y_safe >= start) & (y_safe < start + n_local)
            local_y = jnp.clip(y_safe - start, 0, n_local - 1)
            tgt = jnp.take_along_axis(shifted, local_y[..., None],
                                      axis=-1)[..., 0]
            tgt = lax.psum(jnp.where(in_range, tgt, 0.0), ax)
            loss = jnp.where(valid, jnp.log(sumexp) - tgt, 0.0)
            return Tensor(loss[..., None], stop_gradient=False)
        # GSPMD mode: plain CE on the tape; XLA keeps the logits sharded
        from .....ops.op_utils import nary

        ignore = self.ignore_index

        def ce(lg, yy):
            ok = yy != ignore
            yy_safe = jnp.where(ok, yy, 0)
            m = jnp.max(lg, axis=-1, keepdims=True)
            shifted = lg - jax.lax.stop_gradient(m)
            lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
            tgt = jnp.take_along_axis(shifted, yy_safe[..., None],
                                      axis=-1)[..., 0]
            return jnp.where(ok, lse - tgt, 0.0)[..., None]

        return nary(ce, [input if isinstance(input, Tensor)
                         else Tensor(input), Tensor(y)],
                    name="parallel_cross_entropy")
