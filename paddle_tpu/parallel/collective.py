"""Collective communication API.

Reference parity: python/paddle/distributed/collective.py:348-1630
(broadcast/all_reduce/reduce/all_gather/scatter/alltoall/send/recv/barrier,
ReduceOp, Group, new_group:209) over operators/collective/ kernels keyed by
ring_id.  TPU-native: collectives are XLA ops over named mesh axes
(psum/all_gather/ppermute lowered onto ICI).  Eager semantics: a Tensor is a
global array; per-rank views are its shards along the group axis.  all_reduce
on a replicated tensor multiplies by group size (every "rank" contributes its
copy) — identical observable behavior to N NCCL ranks holding equal values.
Inside compiled/shard_map code the same functions map to lax collectives.
"""
import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map as _jax_shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..core.tensor import Tensor, _wrap_data
from . import env as _env


def shard_map(f, mesh, in_specs, out_specs):
    """jax.shard_map with positional mesh/specs and the varying-axes
    check off (the step functions mix replicated and per-shard values
    the checker cannot type)."""
    return _jax_shard_map(f, mesh=mesh, in_specs=in_specs,
                          out_specs=out_specs, check_vma=False)


class ReduceOp:
    SUM = 0
    MAX = 1
    MIN = 2
    PROD = 3
    AVG = 4


class Group:
    """Parity: collective.py Group — here a named axis over a sub-mesh."""

    def __init__(self, rank, nranks, id=0, ranks=None, mesh=None, axis="data"):
        self.rank = rank
        self.nranks = nranks
        self.id = id
        self.ranks = ranks or list(range(nranks))
        self.mesh = mesh
        self.axis = axis

    @property
    def world_size(self):
        return self.nranks

    def get_group_rank(self, rank):
        return self.ranks.index(rank) if rank in self.ranks else -1

    def __repr__(self):
        return f"Group(rank={self.rank}, nranks={self.nranks}, axis={self.axis})"


_default_group = None
_group_counter = [0]
_GROUPS = {}


def _get_default_group():
    global _default_group
    if _default_group is None:
        mesh = _env.global_mesh()
        axis = mesh.axis_names[0]
        _default_group = Group(
            _env.get_rank(), mesh.shape[axis], id=0, mesh=mesh, axis=axis
        )
    return _default_group


def new_group(ranks=None, backend=None, timeout=None):
    """Parity: collective.py:209.  Groups are modeled as sub-axes; for rank
    subsets we record membership (program-rewrite tests assert on groups, the
    compiled path uses mesh axes directly)."""
    _group_counter[0] += 1
    mesh = _env.global_mesh()
    n = len(ranks) if ranks else _env.get_world_size()
    g = Group(_env.get_rank(), n, id=_group_counter[0], ranks=ranks, mesh=mesh,
              axis=mesh.axis_names[0])
    _GROUPS[g.id] = g
    return g


def _in_trace():
    return isinstance(jnp.zeros(()), jax.core.Tracer)


def _axis_in_scope(axis):
    try:
        jax.lax.axis_index(axis)
        return True
    except BaseException:
        return False


def _group_info(group):
    g = group or _get_default_group()
    return g, g.axis, g.nranks


def _over_mesh(fn, x, group):
    """Run fn (which uses lax collectives over `axis`) via shard_map on the
    group's mesh.  Input treated as a global array sharded on axis 0 when
    divisible, else replicated."""
    g, axis, n = _group_info(group)
    if _axis_in_scope(axis):
        # already inside shard_map/pjit with this axis: direct lax collective
        return fn(x, axis)
    mesh = g.mesh or _env.global_mesh()
    shard0 = x.shape[0] % n == 0 if x.ndim else False
    in_spec = P(axis) if shard0 else P()
    out_spec = in_spec

    def body(v):
        return fn(v, axis)

    return shard_map(
        body, mesh=mesh, in_specs=(in_spec,), out_specs=out_spec,
    )(x)


_REDUCERS = {
    ReduceOp.SUM: lambda v, ax: jax.lax.psum(v, ax),
    ReduceOp.MAX: lambda v, ax: jax.lax.pmax(v, ax),
    ReduceOp.MIN: lambda v, ax: jax.lax.pmin(v, ax),
    ReduceOp.PROD: lambda v, ax: jnp.exp(jax.lax.psum(jnp.log(v), ax)),
    ReduceOp.AVG: lambda v, ax: jax.lax.pmean(v, ax),
}


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    """c_allreduce_{sum,max,min,prod} parity -> XLA AllReduce on ICI."""
    red = _REDUCERS[op]
    out = _over_mesh(lambda v, ax: red(v, ax), tensor._data, group)
    tensor._data = out
    return tensor


def reduce(tensor, dst, op=ReduceOp.SUM, group=None, sync_op=True):
    # On a mesh, reduce == allreduce (result materialized everywhere; the dst
    # distinction is meaningless for value-semantic XLA collectives).
    return all_reduce(tensor, op=op, group=group)


def broadcast(tensor, src, group=None, sync_op=True):
    """c_broadcast parity.  Global arrays are already consistent; for sharded
    inputs broadcast selects src's shard for everyone."""
    g, axis, n = _group_info(group)
    x = tensor._data
    if x.ndim and x.shape[0] % n == 0 and n > 1:
        shard = x.shape[0] // n
        src_local = g.get_group_rank(src) if g.ranks else src
        block = jax.lax.dynamic_slice_in_dim(x, src_local * shard, shard, 0)
        tensor._data = jnp.concatenate([block] * n, axis=0)
    return tensor


def all_gather(tensor_list, tensor, group=None, sync_op=True):
    """c_allgather parity: every rank's shard concatenated."""
    g, axis, n = _group_info(group)
    x = tensor._data
    # eager model: the "per-rank tensor" is the same global array on each rank;
    # gather returns n copies (matching N ranks holding equal tensors), or the
    # shards when the array is axis-0 sharded.
    out = _over_mesh(
        lambda v, ax: jax.lax.all_gather(v, ax, axis=0, tiled=True), x, group
    )
    if tensor_list is not None:
        per = out.shape[0] // n
        for i in range(n):
            tensor_list.append(_wrap_data(out[i * per: (i + 1) * per]))
    return _wrap_data(out)


def reduce_scatter(tensor, tensor_list=None, op=ReduceOp.SUM, group=None,
                   sync_op=True):
    """c_reducescatter parity."""
    g, axis, n = _group_info(group)
    x = tensor_list
    if isinstance(x, (list, tuple)):
        data = jnp.concatenate([t._data for t in x], axis=0)
    else:
        data = (x or tensor)._data
    out = _over_mesh(
        lambda v, ax: jax.lax.psum_scatter(v, ax, scatter_dimension=0, tiled=True),
        data, group,
    )
    tensor._data = out
    return tensor


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    g, axis, n = _group_info(group)
    if tensor_list:
        data = jnp.stack([t._data for t in tensor_list], axis=0)
        rank = g.rank if g.ranks is None else g.get_group_rank(g.rank)
        tensor._data = data[max(rank, 0)]
    return tensor


def alltoall(in_tensor_list, out_tensor_list=None, group=None, sync_op=True):
    """alltoall parity -> lax.all_to_all on ICI.

    Compiled path (inside shard_map): use `all_to_all_in_mesh`.  Eager
    single-controller view: each "rank" holds the same global list, so rank r
    receives in_list[r] from every peer: out = [in[r]] * n.
    """
    g, axis, n = _group_info(group)
    if isinstance(in_tensor_list, Tensor):
        out = _over_mesh(
            lambda v, ax: jax.lax.all_to_all(v, ax, split_axis=1, concat_axis=0,
                                             tiled=True),
            in_tensor_list._data, group,
        )
        return _wrap_data(out)
    r = max(g.rank if g.ranks is None else g.get_group_rank(g.rank), 0)
    received = [in_tensor_list[r]._data for _ in range(n)]
    if out_tensor_list is not None:
        for v in received:
            out_tensor_list.append(_wrap_data(v))
        return out_tensor_list
    return [_wrap_data(v) for v in received]


def all_to_all_in_mesh(x, axis_name, split_axis=0, concat_axis=0):
    """Sequence-parallel building block (Ulysses-style head<->seq exchange)."""
    return jax.lax.all_to_all(x, axis_name, split_axis=split_axis,
                              concat_axis=concat_axis, tiled=True)


def send(tensor, dst=0, group=None, sync_op=True):
    """send_v2 parity.  Point-to-point on a mesh is collective-permute; in the
    single-controller eager view data is already globally addressable, so send
    records into a mailbox consumed by recv."""
    _mailbox.setdefault(dst, []).append(tensor._data)
    return tensor


def recv(tensor, src=0, group=None, sync_op=True):
    box = _mailbox.get(_env.get_rank()) or _mailbox.get(src)
    if box:
        tensor._data = box.pop(0)
    return tensor


_mailbox = {}


def isend(tensor, dst=0, group=None):
    send(tensor, dst, group)
    return _DummyTask()


def irecv(tensor, src=0, group=None):
    recv(tensor, src, group)
    return _DummyTask()


class _DummyTask:
    def wait(self):
        return True

    def is_completed(self):
        return True


def barrier(group=None):
    """barrier op parity: drain device queue (XLA programs are ordered; the
    host-side barrier just synchronizes dispatch)."""
    jax.block_until_ready(jnp.zeros(()))


class P2POp:
    def __init__(self, op, tensor, peer, group=None):
        self.op = op
        self.tensor = tensor
        self.peer = peer
        self.group = group


def batch_isend_irecv(p2p_op_list):
    tasks = []
    for op in p2p_op_list:
        tasks.append(op.op(op.tensor, op.peer, op.group))
    return tasks


# ---- in-mesh collective forms (used inside shard_map'd compiled code) ----

def psum(x, axis_name):
    return jax.lax.psum(x, axis_name)


def pmean(x, axis_name):
    return jax.lax.pmean(x, axis_name)


def ppermute(x, axis_name, perm):
    return jax.lax.ppermute(x, axis_name, perm)


def axis_index(axis_name):
    return jax.lax.axis_index(axis_name)


def get_group(id=0):
    """collective.py get_group parity: the Group registered under id, the
    default world group for id 0, None for an unknown id (fail fast
    rather than silently widening a subgroup collective to the world)."""
    if id == 0:
        return _get_default_group()
    return _GROUPS.get(id)


def wait(tensor, group=None, use_calc_stream=True):
    """collective.py wait / c_sync_*_stream parity: XLA collectives are
    value-semantic dataflow, so ordering is already guaranteed; a device
    sync is the only observable effect."""
    if hasattr(tensor, "_data"):
        tensor._data.block_until_ready()
    return tensor
