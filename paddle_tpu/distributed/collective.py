"""Collective communication API.

TPU-native replacement for the reference's entire ProcessGroup stack
(``paddle/fluid/distributed/collective/process_group.h:53`` with
NCCL/Gloo/BKCL/MPI/custom backends, TCPStore rendezvous
``paddle/phi/core/distributed/store/tcp_store.h:120``, and the Python
surface ``python/paddle/distributed/communication/``): collectives are XLA
collectives (``lax.psum / all_gather / all_to_all / ppermute``) compiled
into the program and routed over ICI/DCN by the compiler. There is no
communicator object to create, no stream ordering to manage, no store —
``Group`` is pure rank bookkeeping plus a named mesh axis.

Two execution modes, one API (mirroring ``paddle.distributed.all_reduce``
semantics for test parity, SURVEY §5):

 - **SPMD (traced) mode** — called inside ``shard_map``/``pjit`` where the
   group's axis name is in scope: ops lower directly to ``jax.lax``
   collectives. This is the real compute path used by TP/PP/EP layers.
 - **Eager mode** — called on concrete arrays in "rank-major layout": a
   per-rank value is axis 0 of a stacked array of shape ``[nranks, ...]``
   (the single-controller representation of "each rank holds a tensor").
   The op runs the SAME ``lax`` collective under a ``shard_map`` over the
   group's devices, so the XLA collective machinery is genuinely exercised
   (the analog of the reference's collective op tests,
   ``test/collective/collective_allreduce_api.py`` et al.).
"""
from __future__ import annotations

import functools
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..tensor import Tensor
from . import mesh as _mesh_mod

__all__ = [
    "ReduceOp", "Group", "new_group", "get_group", "destroy_process_group",
    "is_initialized", "all_reduce", "all_gather", "gather", "all_gather_object",
    "broadcast", "broadcast_object_list", "reduce", "scatter",
    "scatter_object_list", "alltoall", "alltoall_single", "all_to_all",
    "reduce_scatter", "send", "recv", "isend", "irecv", "barrier",
    "P2POp", "batch_isend_irecv", "wait", "get_backend",
]

_RANK_AXIS = "ranks"


class ReduceOp:
    """ref: ``python/paddle/distributed/communication/reduce.py ReduceOp``."""
    SUM = 0
    MAX = 1
    MIN = 2
    PROD = 3
    AVG = 4


_LAX_REDUCE = {
    ReduceOp.SUM: lax.psum,
    ReduceOp.MAX: lax.pmax,
    ReduceOp.MIN: lax.pmin,
    # product = sign * exp(sum(log|x|)); psum of the sign-parity keeps
    # negatives exact and zeros propagate as zeros.
    ReduceOp.PROD: lambda x, ax: (
        jnp.where(lax.psum((x == 0).astype(jnp.int32), ax) > 0, 0.0,
                  (1.0 - 2.0 * (lax.psum((x < 0).astype(jnp.int32), ax) % 2))
                  * jnp.exp(lax.psum(jnp.log(jnp.maximum(jnp.abs(x), 1e-38)),
                                     ax))).astype(x.dtype)),
    ReduceOp.AVG: lax.pmean,
}


class Group:
    """Rank bookkeeping + a device mesh slice (ref:
    ``python/paddle/distributed/communication/group.py:22``).

    ``axis_name`` is the mesh axis this group's collectives reduce over
    when used in SPMD mode; eager mode uses the group's own 1-D sub-mesh.
    """

    def __init__(self, rank, ranks, id=0, axis_name=None, devices=None):
        self._rank = rank            # this process's index within `ranks`
        self.ranks = list(ranks)
        self.id = id
        self.axis_name = axis_name or _RANK_AXIS
        if devices is None:
            devices = jax.devices()
        self._devices = [devices[r % len(devices)] for r in self.ranks]
        self._submesh = None

    # -- rank info ---------------------------------------------------------
    @property
    def rank(self):
        return self._rank

    @property
    def nranks(self):
        return len(self.ranks)

    world_size = nranks

    @property
    def process_group(self):
        return self

    @property
    def name(self):
        return f"_default_pg{self.id}"

    def is_member(self):
        return self._rank >= 0

    def get_group_rank(self, global_rank):
        return self.ranks.index(global_rank) if global_rank in self.ranks \
            else -1

    # -- eager-mode machinery ---------------------------------------------
    def submesh(self) -> Mesh:
        if self._submesh is None:
            self._submesh = Mesh(np.array(self._devices), (self.axis_name,))
        return self._submesh

    def _shard_eval(self, fn, args, in_specs, out_specs):
        """Run `fn` under shard_map over this group's devices."""
        m = self.submesh()
        # check_vma off: collective outputs (all_gather/psum results) ARE
        # replicated but the static varying-axes checker can't always
        # prove it through custom-vjp wrappers
        from ._jax_compat import shard_map
        return shard_map(fn, mesh=m, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(*args)

    def __repr__(self):
        return f"Group(id={self.id}, ranks={self.ranks})"


_GROUP_MAP: dict[int, Group] = {}
_DEFAULT_GROUP: Group | None = None


def _default_group() -> Group:
    global _DEFAULT_GROUP
    if _DEFAULT_GROUP is None:
        n = jax.device_count()
        from .env import get_rank
        _DEFAULT_GROUP = Group(get_rank() % max(n, 1), list(range(n)), id=0)
        _GROUP_MAP[0] = _DEFAULT_GROUP
    return _DEFAULT_GROUP


def is_initialized():
    return _DEFAULT_GROUP is not None


def destroy_process_group(group=None):
    global _DEFAULT_GROUP
    if group is None or group.id == 0:
        _DEFAULT_GROUP = None
        _GROUP_MAP.clear()
    else:
        _GROUP_MAP.pop(group.id, None)


def get_group(id=0) -> Group:
    if id == 0:
        return _default_group()
    return _GROUP_MAP[id]


def new_group(ranks=None, backend=None, timeout=None, axis_name=None) -> Group:
    """ref: ``python/paddle/distributed/collective.py:178 new_group``.

    No communicator handshake happens (XLA owns transport); this is pure
    bookkeeping and is therefore cheap and deterministic across ranks.
    """
    default = _default_group()
    if ranks is None:
        ranks = list(default.ranks)
    gid = max(_GROUP_MAP) + 1 if _GROUP_MAP else 1
    from .env import get_rank
    me = get_rank()
    rank_in = ranks.index(me) if me in ranks else -1
    g = Group(rank_in, ranks, id=gid, axis_name=axis_name)
    _GROUP_MAP[gid] = g
    return g


def get_backend(group=None):
    return "xla"


def _group_of(group) -> Group:
    return group if isinstance(group, Group) else _default_group()


def _in_axis_scope(name: str) -> bool:
    """True when called under a trace with mesh axis `name` in scope
    (manual inside a ``shard_map`` body; an axis left automatic answers
    "no" — mp_layers' dual-mode dispatch depends on this)."""
    try:
        lax.axis_index(name)
        return True
    except Exception:
        return False


def _data(x):
    return x._data if isinstance(x, Tensor) else jnp.asarray(x)


def _observe(op, x):
    """Per-op count + input-byte telemetry. Shape/dtype metadata only —
    works on tracers and device arrays alike, never syncs. In SPMD
    (traced) mode this runs once per trace, which is the honest count:
    the op executes inside ONE compiled program thereafter."""
    from ..observability import get_telemetry
    tel = get_telemetry()
    if not tel.enabled:
        return
    try:
        nbytes = int(x.size) * x.dtype.itemsize
    except Exception:
        nbytes = 0
    tel.collective_op(op, nbytes)


def _timed(op):
    """Per-op host-boundary latency: ``pt_collective_time_seconds{op}``
    around the whole public call (dispatch + the eager shard_map
    execution).  Recorded ONLY outside traces — inside a trace the
    wall clock would measure tracing, not transport, so a dirty trace
    state skips the observation (``_observe``'s count/bytes still fire
    once per trace).  Wall time around async dispatch is a lower
    bound; eager collectives here execute via ``Group._shard_eval``,
    which materializes, so the number is the honest host cost.  The
    same interval feeds the step-phase tracer as a "collective" span —
    the raw material of the compute↔collective overlap fraction."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            from ..observability import get_telemetry
            tel = get_telemetry()
            from ..observability.trace import get_tracer
            tr = get_tracer()
            if not (tel.enabled or tr.enabled):
                return fn(*args, **kwargs)
            if not jax.core.trace_ctx.is_top_level():
                return fn(*args, **kwargs)  # a trace, not a step: don't time
            t0 = time.perf_counter_ns()
            try:
                return fn(*args, **kwargs)
            finally:
                t1 = time.perf_counter_ns()
                tel.collective_time(op, (t1 - t0) / 1e9)
                if tr.enabled:
                    tr.phase_record("collective", t0, t1)
        return wrapper
    return deco


def _ret(x, like):
    if isinstance(like, Tensor):
        like._data = x
        return like
    return Tensor(x)


class _Task:
    """Completed-task handle (ref: ProcessGroup tasks
    ``process_group.h:61``). XLA ops are async by nature; wait() blocks."""

    def __init__(self, arrays=()):
        self._arrays = arrays

    def wait(self):
        for a in self._arrays:
            jax.block_until_ready(a)
        return True

    def is_completed(self):
        return True

    def synchronize(self):
        self.wait()


def wait(tensor, group=None, use_calc_stream=True):
    jax.block_until_ready(_data(tensor))


# ---------------------------------------------------------------------------
# collectives
# ---------------------------------------------------------------------------

@_timed("all_reduce")
def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    """ref: ``communication/all_reduce.py`` → ``ProcessGroupNCCL::AllReduce``
    (``process_group_nccl.cc:160``). SPMD: ``lax.psum`` family. Eager:
    rank-major ``[nranks, ...]`` in/out; every rank slot gets the result."""
    g = _group_of(group)
    red = _LAX_REDUCE[op]
    x = _data(tensor)
    _observe("all_reduce", x)
    if _in_axis_scope(g.axis_name):
        return _ret(red(x, g.axis_name), tensor)

    ax = g.axis_name
    if x.shape[0] != g.nranks:
        raise ValueError(
            f"eager all_reduce expects rank-major layout [nranks={g.nranks},"
            f" ...], got shape {tuple(x.shape)}")

    def f(xs):  # xs: [1, ...] per device
        return red(xs, ax)

    out = g._shard_eval(f, (x,), in_specs=P(ax), out_specs=P(ax))
    res = _ret(out, tensor)
    if not sync_op:
        return _Task((out,))
    return res


@_timed("all_gather")
def all_gather(tensor_or_list, tensor=None, group=None, sync_op=True,
               axis=0):
    """ref: ``communication/all_gather.py``. Two call forms like the
    reference: ``all_gather(tensor_list, tensor)`` fills the list;
    ``all_gather(tensor)`` returns the gathered Tensor (stacked on axis 0
    in eager mode, concatenated on `axis` in SPMD mode)."""
    g = _group_of(group)
    out_list = None
    if isinstance(tensor_or_list, list):
        out_list = tensor_or_list
        src = tensor
    else:
        src = tensor_or_list
    x = _data(src)
    _observe("all_gather", x)

    if _in_axis_scope(g.axis_name):
        gathered = lax.all_gather(x, g.axis_name, axis=axis, tiled=True)
        if out_list is not None:
            parts = jnp.split(gathered, g.nranks, axis=axis)
            out_list.clear()
            out_list.extend(Tensor(p) for p in parts)
            return out_list
        return Tensor(gathered)

    ax = g.axis_name
    if x.shape[0] != g.nranks:
        raise ValueError(
            f"eager all_gather expects rank-major [nranks={g.nranks}, ...]")

    def f(xs):
        return lax.all_gather(xs, ax, axis=0, tiled=True)

    # every device computes the full gather; take the (identical) global view
    out = g._shard_eval(f, (x,), in_specs=P(ax), out_specs=P())
    if out_list is not None:
        out_list.clear()
        out_list.extend(Tensor(out[i]) for i in range(g.nranks))
        return out_list
    return Tensor(out)


@_timed("gather")
def gather(tensor, gather_list=None, dst=0, group=None, sync_op=True):
    """ref: ``communication/gather.py``: collect per-rank tensors into
    ``gather_list`` on ``dst``. Single-controller eager mode sees every
    rank slot, so the list is filled from the rank-major dim (the dst
    restriction is a multi-controller artifact)."""
    g = _group_of(group)
    x = _data(tensor)
    _observe("gather", x)
    if gather_list is None:
        gather_list = []
    if _in_axis_scope(g.axis_name):
        gathered = lax.all_gather(x, g.axis_name, axis=0, tiled=False)
        gather_list.clear()
        gather_list.extend(Tensor(gathered[i]) for i in range(g.nranks))
        return gather_list
    if x.shape[0] != g.nranks:
        raise ValueError(
            f"eager gather expects rank-major [nranks={g.nranks}, ...]")
    gather_list.clear()
    gather_list.extend(Tensor(x[i]) for i in range(g.nranks))
    return gather_list


def all_gather_object(object_list, obj, group=None):
    """Single-controller: every rank slot sees the same object store."""
    g = _group_of(group)
    object_list.clear()
    object_list.extend([obj] * g.nranks)
    return object_list


@_timed("broadcast")
def broadcast(tensor, src=0, group=None, sync_op=True):
    """ref: ``communication/broadcast.py``. SPMD: select src's value via
    all_gather+index (compiled to a broadcast over ICI)."""
    g = _group_of(group)
    x = _data(tensor)
    _observe("broadcast", x)
    if src not in g.ranks:
        raise ValueError(f"broadcast src={src} is not in group {g.ranks}")
    src_local = g.get_group_rank(src)
    if _in_axis_scope(g.axis_name):
        gathered = lax.all_gather(x, g.axis_name, axis=0)
        return _ret(gathered[src_local], tensor)

    ax = g.axis_name
    if x.shape[0] != g.nranks:
        raise ValueError(
            f"eager broadcast expects rank-major [nranks={g.nranks}, ...]")

    def f(xs):
        gathered = lax.all_gather(xs[0], ax, axis=0)
        return gathered[src_local][None]

    out = g._shard_eval(f, (x,), in_specs=P(ax), out_specs=P(ax))
    return _ret(out, tensor)


def broadcast_object_list(object_list, src=0, group=None):
    return object_list


@_timed("reduce")
def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    """ref: ``communication/reduce.py``: only dst's slot keeps the result,
    other slots keep their input (matching NCCL reduce semantics)."""
    g = _group_of(group)
    red = _LAX_REDUCE[op]
    x = _data(tensor)
    _observe("reduce", x)
    if dst not in g.ranks:
        raise ValueError(f"reduce dst={dst} is not in group {g.ranks}")
    dst_local = g.get_group_rank(dst)
    if _in_axis_scope(g.axis_name):
        r = red(x, g.axis_name)
        i = lax.axis_index(g.axis_name)
        return _ret(jnp.where(i == dst_local, r, x), tensor)

    ax = g.axis_name
    if x.shape[0] != g.nranks:
        raise ValueError(
            f"eager reduce expects rank-major [nranks={g.nranks}, ...]")

    def f(xs):
        r = red(xs, ax)
        i = lax.axis_index(ax)
        return jnp.where(i == dst_local, r, xs)

    out = g._shard_eval(f, (x,), in_specs=P(ax), out_specs=P(ax))
    return _ret(out, tensor)


@_timed("scatter")
def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    """ref: ``communication/scatter.py``: src rank's list is distributed,
    one element per rank."""
    g = _group_of(group)
    if tensor_list is not None:
        stacked = jnp.stack([_data(t) for t in tensor_list])
    else:
        stacked = _data(tensor)
        if stacked.shape[0] != g.nranks:
            raise ValueError("scatter needs tensor_list or rank-major input")
    _observe("scatter", stacked)
    if _in_axis_scope(g.axis_name):
        i = lax.axis_index(g.axis_name)
        return _ret(jnp.take(stacked, i, axis=0), tensor)

    ax = g.axis_name

    def f(xs):  # xs replicated [nranks, ...]
        i = lax.axis_index(ax)
        return jnp.take(xs, i, axis=0)[None]

    out = g._shard_eval(f, (stacked,), in_specs=P(),
                        out_specs=P(ax))
    return _ret(out, tensor)


def scatter_object_list(out_object_list, in_object_list=None, src=0,
                        group=None):
    g = _group_of(group)
    out_object_list.clear()
    if in_object_list:
        out_object_list.append(in_object_list[g.rank % len(in_object_list)])
    return out_object_list


@_timed("alltoall")
def alltoall(out_tensor_list, in_tensor_list=None, group=None, sync_op=True):
    """ref: ``communication/all_to_all.py``. Eager rank-major form: input
    ``[nranks, nranks, ...]`` (slot [i, j] = rank i's tensor for rank j)
    → output [i, j] = what rank i received from rank j."""
    g = _group_of(group)
    if in_tensor_list is None and not isinstance(out_tensor_list, list):
        x = _data(out_tensor_list)
        as_list = False
    else:
        x = jnp.stack([_data(t) for t in in_tensor_list])
        as_list = True
    _observe("alltoall", x)

    if _in_axis_scope(g.axis_name):
        # x: [nranks, ...] per rank; swap rank axis with the group axis
        out = lax.all_to_all(x, g.axis_name, split_axis=0, concat_axis=0,
                             tiled=True)
        if as_list:
            parts = jnp.split(out, g.nranks, axis=0)
            out_tensor_list.clear()
            out_tensor_list.extend(Tensor(p[0] if p.shape[0] == 1 else p)
                                   for p in parts)
            return out_tensor_list
        return Tensor(out)

    ax = g.axis_name
    if x.shape[0] != g.nranks:
        raise ValueError(
            f"eager alltoall expects [nranks={g.nranks}, nranks, ...]")

    def f(xs):  # xs: [1, nranks, ...] → [1, nranks, ...], slot j from rank j
        return lax.all_to_all(xs, ax, split_axis=1, concat_axis=1)

    out = g._shard_eval(f, (x,), in_specs=P(ax), out_specs=P(ax))
    # out[i, j] = x[j, i] — transpose over ranks, which IS alltoall
    if as_list:
        me = max(g.rank, 0)
        out_tensor_list.clear()
        out_tensor_list.extend(Tensor(out[me, j]) for j in range(g.nranks))
        return out_tensor_list
    return Tensor(out)


all_to_all = alltoall


@_timed("alltoall_single")
def alltoall_single(in_tensor, out_tensor=None, in_split_sizes=None,
                    out_split_sizes=None, group=None, sync_op=True):
    """Even-split all_to_all on one tensor (ref:
    ``communication/all_to_all.py alltoall_single``)."""
    g = _group_of(group)
    x = _data(in_tensor)
    _observe("alltoall_single", x)
    if _in_axis_scope(g.axis_name):
        out = lax.all_to_all(x, g.axis_name, split_axis=0, concat_axis=0,
                             tiled=True)
        if out_tensor is not None:
            return _ret(out, out_tensor)
        return Tensor(out)
    ax = g.axis_name
    if x.shape[0] != g.nranks or x.shape[1] % g.nranks:
        raise ValueError(
            "eager alltoall_single expects rank-major [nranks, nranks*chunk,"
            f" ...], got {tuple(x.shape)} for nranks={g.nranks}")

    def f(xs):  # xs: [1, nranks, chunk, ...] per device
        return lax.all_to_all(xs, ax, split_axis=1, concat_axis=1)

    chunked = x.reshape(g.nranks, g.nranks, x.shape[1] // g.nranks,
                        *x.shape[2:])
    out = g._shard_eval(f, (chunked,), in_specs=P(ax), out_specs=P(ax))
    out = out.reshape(x.shape)
    if out_tensor is not None:
        return _ret(out, out_tensor)
    return Tensor(out)


@_timed("reduce_scatter")
def reduce_scatter(tensor, tensor_list=None, op=ReduceOp.SUM, group=None,
                   sync_op=True):
    """ref: ``communication/reduce_scatter.py``: each rank's input is the
    concat of per-destination chunks; output is the reduced chunk owned by
    this rank. SPMD: ``lax.psum_scatter``."""
    g = _group_of(group)
    if tensor_list is not None:
        x = jnp.concatenate([_data(t) for t in tensor_list], axis=0)
    else:
        x = _data(tensor)
    _observe("reduce_scatter", x)
    if _in_axis_scope(g.axis_name):
        out = lax.psum_scatter(x, g.axis_name, scatter_dimension=0,
                               tiled=True)
        if op == ReduceOp.AVG:
            out = out / g.nranks
        return _ret(out, tensor)

    ax = g.axis_name
    if x.shape[0] != g.nranks:
        raise ValueError("eager reduce_scatter expects rank-major "
                         f"[nranks={g.nranks}, nranks*chunk, ...]")

    def f(xs):  # xs: [1, nranks*chunk, ...]
        out = lax.psum_scatter(xs[0], ax, scatter_dimension=0, tiled=True)
        return out[None]

    out = g._shard_eval(f, (x,), in_specs=P(ax), out_specs=P(ax))
    if op == ReduceOp.AVG:
        out = out / g.nranks
    return _ret(out, tensor)


# ---------------------------------------------------------------------------
# point-to-point
# ---------------------------------------------------------------------------
# SPMD mode: ppermute (the ICI-native p2p — used by the pipeline schedule).
# Eager single-controller mode: a rank-slot mailbox; a send is visible to
# the matching recv immediately (one process owns all slots). Multi-process
# p2p rides the compiled pipeline path instead (SURVEY §5: ProcessGroup
# send/recv → ppermute inside the pipeline program).

_MAILBOX: dict[tuple, list] = {}


@_timed("send")
def send(tensor, dst=0, group=None, sync_op=True):
    g = _group_of(group)
    if _in_axis_scope(g.axis_name):
        raise RuntimeError(
            "Inside shard_map use paddle_tpu.distributed.p2p helpers "
            "(ppermute) — a lone send has no SPMD meaning")
    x = _data(tensor)
    _observe("send", x)
    _MAILBOX.setdefault((g.id, g.rank, dst), []).append(x)
    return _Task()


@_timed("recv")
def recv(tensor, src=0, group=None, sync_op=True):
    g = _group_of(group)
    box = _MAILBOX.get((g.id, src, max(g.rank, 0)), None)
    if not box:
        raise RuntimeError(f"recv: no message pending from rank {src}")
    _observe("recv", box[0])
    return _ret(box.pop(0), tensor)


isend = send
irecv = recv


class P2POp:
    """ref: ``communication/batch_isend_irecv.py P2POp``."""

    def __init__(self, op, tensor, peer, group=None):
        self.op = op
        self.tensor = tensor
        self.peer = peer
        self.group = group


def batch_isend_irecv(p2p_op_list):
    tasks = []
    for p in p2p_op_list:
        tasks.append(p.op(p.tensor, p.peer, p.group))
    return tasks


@_timed("barrier")
def barrier(group=None):
    """All ranks sync. XLA programs are bulk-synchronous; eager barrier is a
    tiny psum across the group's devices."""
    g = _group_of(group)
    ax = g.axis_name
    one = jnp.ones((g.nranks,), jnp.int32)
    _observe("barrier", one)

    def f(x):
        return lax.psum(x, ax)

    out = g._shard_eval(f, (one,), in_specs=P(ax), out_specs=P(ax))
    jax.block_until_ready(out)
