"""Pallas kernels under a device mesh.

The SPMD partitioner cannot split a Mosaic kernel: a jit that spans more
than one device refuses it ("Mosaic kernels cannot be automatically
partitioned. Please wrap the call in a shard_map."). ``sharded_call`` is
that wrap, in one place for every kernel in ``ops/``: the call runs under
``jax.shard_map`` over the whole mesh, batch dims split over 'dp' and head
dims over 'mp' — the axes the partitioner rules (parallel/partitioner.py)
already put those dims on — so each device runs the kernel on the block it
holds and nothing is resharded on the way in.

The mesh is the one the enclosing program was built for. Whoever owns the
jit names it: ``jit(fn, mesh, ...)`` (the train steps, the serving
engines) traces ``fn`` inside ``kernel_mesh(mesh)``. With no mesh named, a
one-device mesh, or inside a caller's own shard_map (the sp/pp explicit-
collective paths), the kernel is called as is.
"""
import contextlib
import functools
import threading

import jax
from jax.sharding import PartitionSpec as P

_scope = threading.local()


@contextlib.contextmanager
def kernel_mesh(mesh):
    """Trace-time scope naming the mesh the traced program runs on."""
    prev = getattr(_scope, 'mesh', None)
    _scope.mesh = mesh
    try:
        yield
    finally:
        _scope.mesh = prev


def jit(fn, mesh, **jit_kwargs):
    """``jax.jit(fn, **jit_kwargs)`` whose trace runs in
    ``kernel_mesh(mesh)``, so kernels inside ``fn`` shard over ``mesh``."""
    @functools.wraps(fn)
    def scoped(*args, **kwargs):
        with kernel_mesh(mesh):
            return fn(*args, **kwargs)
    return jax.jit(scoped, **jit_kwargs)


def _plan(batch, heads):
    """(mesh, {'batch'|'heads': mesh axis or None}) for a kernel call with
    these extents, or None when it is called as is: no mesh named, one
    device, or already inside a shard_map (operands are local there).
    batch -> 'dp' and heads -> 'mp', the pairing of partitioner.model_rules.
    A dim rides its axis only when every extent given for it splits evenly;
    otherwise it stays whole on every device of that axis (correct, merely
    redundant)."""
    mesh = getattr(_scope, 'mesh', None)
    if (mesh is None or mesh.size == 1
            or jax.sharding.get_abstract_mesh().manual_axes):
        return None

    def axis(name, extents):
        n = mesh.shape.get(name, 1)
        return name if n > 1 and all(e % n == 0 for e in extents) else None
    return mesh, {'batch': axis('dp', (batch,)), 'heads': axis('mp', heads)}


def shard_grid(batch, heads):
    """(batch blocks, head blocks) a kernel call with these extents is
    split into — (1, 1) when it is not wrapped. ``heads``: every head
    extent that must split (query heads and kv heads)."""
    plan = _plan(batch, heads)
    if plan is None:
        return 1, 1
    mesh, ax = plan
    return tuple(mesh.shape[ax[d]] if ax[d] else 1
                 for d in ('batch', 'heads'))


def sharded_call(fn, args, in_dims, out_dims, *, batch, heads):
    """``fn(*args)``, per device block when a kernel mesh is active.

    args: flat tuple of arrays; fn returns one array. in_dims: one tuple
    per operand naming each dim 'batch', 'heads' or None (whole), or None
    for an operand every device needs whole; out_dims: the same for the
    result. batch / heads: see shard_grid."""
    plan = _plan(batch, heads)
    if plan is None:
        return fn(*args)
    mesh, ax = plan

    def spec(dims):
        return P() if dims is None else P(*(ax.get(d) for d in dims))

    # check_vma=False: pallas_call results carry no varying-axes type
    return jax.shard_map(fn, mesh=mesh,
                         in_specs=tuple(spec(d) for d in in_dims),
                         out_specs=spec(out_dims), check_vma=False)(*args)
