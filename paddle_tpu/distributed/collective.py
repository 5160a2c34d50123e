"""Collective communication ops.

Reference: python/paddle/distributed/collective.py (c_allreduce/c_broadcast/...
over NCCL, paddle/fluid/operators/collective/). TPU-native: inside a
shard_map/pjit region these lower to XLA collectives over ICI (psum,
all_gather, ppermute, all_to_all). Outside any parallel region (single
controller, eager) they are identities over the full array — matching the
reference's world_size=1 behavior.
"""
import contextlib

import jax
import jax.numpy as jnp

from ..core.dispatch import apply_op
from ..core.tensor import Tensor
from ..fault.inject import inject


class ReduceOp:
    SUM = 'sum'
    MAX = 'max'
    MIN = 'min'
    PROD = 'prod'
    AVG = 'avg'


# axis-name context: set by shard_map-wrapped training steps
_axis_stack = []


@contextlib.contextmanager
def axis_ctx(name):
    _axis_stack.append(name)
    try:
        yield
    finally:
        _axis_stack.pop()


def _cur_axis(group=None):
    if isinstance(group, str):
        return group
    if _axis_stack:
        return _axis_stack[-1]
    return None


def _in_trace(x):
    return isinstance(x, jax.core.Tracer)


def all_reduce(tensor, op=ReduceOp.SUM, group=None, use_calc_stream=True):
    inject('collective.entry')
    axis = _cur_axis(group)

    def pure(v):
        if axis is None or not _in_trace(v):
            return v + 0
        if op in (ReduceOp.SUM, 'sum'):
            return jax.lax.psum(v, axis)
        if op == ReduceOp.MAX:
            return jax.lax.pmax(v, axis)
        if op == ReduceOp.MIN:
            return jax.lax.pmin(v, axis)
        if op == ReduceOp.AVG:
            return jax.lax.pmean(v, axis)
        if op == ReduceOp.PROD:
            return jnp.exp(jax.lax.psum(jnp.log(v), axis))
        return v
    out = apply_op(pure, tensor)
    if isinstance(tensor, Tensor):
        tensor._replace_value(out._value)
        return tensor
    return out


def all_gather(tensor_list, tensor, group=None, use_calc_stream=True, axis=0):
    inject('collective.entry')
    ax = _cur_axis(group)

    def pure(v):
        if ax is None or not _in_trace(v):
            return v[None]
        return jax.lax.all_gather(v, ax)
    out = apply_op(pure, tensor)
    if tensor_list is not None:
        n = out.shape[0]
        for i in range(n):
            tensor_list.append(out[i])
        return tensor_list
    return out


def broadcast(tensor, src=0, group=None, use_calc_stream=True):
    inject('collective.entry')
    ax = _cur_axis(group)

    def pure(v):
        if ax is None or not _in_trace(v):
            return v + 0
        full = jax.lax.all_gather(v, ax)
        return full[src]
    out = apply_op(pure, tensor)
    if isinstance(tensor, Tensor):
        tensor._replace_value(out._value)
        return tensor
    return out


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, use_calc_stream=True):
    return all_reduce(tensor, op, group, use_calc_stream)


def scatter(tensor, tensor_list=None, src=0, group=None, use_calc_stream=True):
    ax = _cur_axis(group)
    if ax is None:
        if tensor_list:
            tensor._replace_value(tensor_list[0]._value if isinstance(tensor_list[0], Tensor)
                                  else jnp.asarray(tensor_list[0]))
        return tensor
    stacked = jnp.stack([t._value if isinstance(t, Tensor) else jnp.asarray(t)
                         for t in tensor_list])

    def pure(s):
        idx = jax.lax.axis_index(ax)
        return jnp.take(s, idx, axis=0)
    out = apply_op(pure, Tensor(stacked))
    tensor._replace_value(out._value)
    return tensor


def reduce_scatter(output, input_list, op=ReduceOp.SUM, group=None):
    inject('collective.entry')
    ax = _cur_axis(group)
    stacked = jnp.concatenate([t._value if isinstance(t, Tensor) else jnp.asarray(t)
                               for t in input_list])

    def pure(v):
        if ax is None or not _in_trace(v):
            return v
        return jax.lax.psum_scatter(v, ax, tiled=True)
    out = apply_op(pure, Tensor(stacked))
    if output is not None:
        output._replace_value(out._value)
        return output
    return out


def alltoall(in_tensor_list, out_tensor_list=None, group=None, use_calc_stream=True):
    inject('collective.entry')
    ax = _cur_axis(group)
    xs = [t._value if isinstance(t, Tensor) else jnp.asarray(t)
          for t in in_tensor_list]
    stacked = jnp.stack(xs)

    def pure(v):
        if ax is None or not _in_trace(v):
            return v
        return jax.lax.all_to_all(v, ax, split_axis=0, concat_axis=0, tiled=False)
    out = apply_op(pure, Tensor(stacked))
    res = [out[i] for i in range(out.shape[0])]
    if out_tensor_list is not None:
        out_tensor_list.extend(res)
        return out_tensor_list
    return res


def send(tensor, dst=0, group=None, use_calc_stream=True):
    """Point-to-point: inside a parallel region use ppermute via isend-style
    ring helper (see parallel.pipeline); eager single-controller is a no-op."""
    return tensor


def recv(tensor, src=0, group=None, use_calc_stream=True):
    return tensor


def barrier(group=None):
    inject('collective.entry')
    jax.effects_barrier()


def new_group(ranks=None, backend=None):
    class _Group:
        def __init__(self, ranks):
            self.ranks = ranks or []
            self.nranks = len(self.ranks)
    return _Group(ranks)


def get_group(gid=0):
    return new_group()


def wait(tensor, group=None, use_calc_stream=True):
    if isinstance(tensor, Tensor):
        try:
            tensor._value.block_until_ready()
        except Exception:
            pass
    return tensor
