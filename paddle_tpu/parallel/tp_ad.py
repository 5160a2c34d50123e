"""Megatron-style tensor-parallel AD helpers: the f/g conjugate pair.

Reference: fleet/meta_parallel/parallel_layers/mp_layers.py — there the
identity-forward/all-reduce-backward ("f") and all-reduce-forward/identity-
backward ("g") ops are implemented as autograd Functions over NCCL. TPU-native:
jax.custom_vjp over lax.psum on a mesh axis, which also pins the AD semantics
explicitly instead of relying on shard_map's transpose rule for a bare psum
(whose cotangent convention under check_vma=False double-counts sharded
branches when a residual stream bypasses the collective).

Column-parallel matmul: x -> f_identity(x) @ W_col      (backward all-reduces dx)
Row-parallel matmul:    g_allreduce(x @ W_row)          (forward all-reduces y)
"""
from functools import lru_cache

import jax


@lru_cache(maxsize=None)
def _g_op(axis_name):
    @jax.custom_vjp
    def g(x):
        return jax.lax.psum(x, axis_name)

    def fwd(x):
        return jax.lax.psum(x, axis_name), None

    def bwd(_, ct):
        return (ct,)

    g.defvjp(fwd, bwd)
    return g


@lru_cache(maxsize=None)
def _f_op(axis_name):
    @jax.custom_vjp
    def f(x):
        return x

    def fwd(x):
        return x, None

    def bwd(_, ct):
        return (jax.lax.psum(ct, axis_name),)

    f.defvjp(fwd, bwd)
    return f


def g_allreduce(x, axis_name):
    """All-reduce forward, identity backward (row-parallel output)."""
    return _g_op(axis_name)(x)


def f_identity(x, axis_name):
    """Identity forward, all-reduce backward (column-parallel input)."""
    return _f_op(axis_name)(x)
