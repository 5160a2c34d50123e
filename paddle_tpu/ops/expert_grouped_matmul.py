"""Grouped matrix product over the experts held: rows sorted by expert, each
group against its own expert's matrix, no capacity and no dropped row.

    rows     [M, K]   sorted by expert; a group starts on a multiple of
                      ``tm`` rows and is padded with zero rows to the next
    w        [E, K, N]
    tile_expert [M // tm] i32   the expert of each tile of ``tm`` rows
    n_tiles  [1] i32            tiles in use; the rest of M is padding
    -> [M, N]; rows of tiles not in use come out zero

A Pallas kernel (``expert_grouped_matmul``), not ``jax.lax.ragged_dot``:
what the layer needs of the product is that an expert no row was routed to
costs nothing, and that a step's cost follows the rows it really has, while
the shapes are those of the worst case (every row to one expert). Here both
hold by construction and can be read off the code: grid (tiles, N blocks)
with ``tile_expert`` and ``n_tiles`` in scalar prefetch; a tile's weight
block index is its expert's, so an expert with no tile is never fetched; a
tile past ``n_tiles`` takes the block indices of the last one in use (no
fetch) and computes nothing. Whether ragged_dot's lowering skips empty
groups on this chip can only be read in a device trace, which the builder
of this kernel did not have before writing it.

The whole contraction is one block (K is a model width, 2048 or 7168: a
``[K, tn]`` bf16 weight block is 2 to 3.7 MB), so there is no accumulator
and each weight byte is read once a tile.
"""
import importlib

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import mesh_kernel

_fa = importlib.import_module('paddle_tpu.ops.flash_attention')

_W_BLOCK_BYTES = 4 * 2 ** 20     # most a weight block may take in VMEM
_VMEM_LIMIT = 48 * 2 ** 20       # of the v5e's 128 MiB; blocks are doubled


def expert_grouped_matmul_available(rows, w, tm):
    if not _fa._platform_ok():
        return False
    m, k = (int(x) for x in rows.shape)
    return (m % tm == 0 and tm % 16 == 0 and k % 128 == 0
            and int(w.shape[2]) % 128 == 0 and rows.dtype == w.dtype
            and rows.dtype in (jnp.float32, jnp.bfloat16))


def _n_block(k, n, itemsize):
    for tn in (1024, 512, 256, 128):
        if n % tn == 0 and k * tn * itemsize <= _W_BLOCK_BYTES:
            return tn
    return 128


def _gmm_kernel(te_ref, nt_ref, x_ref, w_ref, o_ref):
    t = pl.program_id(0)

    @pl.when(t < nt_ref[0])
    def _compute():
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)

    @pl.when(t >= nt_ref[0])
    def _padding():
        o_ref[...] = jnp.zeros_like(o_ref)


def _gmm(rows, w, tile_expert, n_tiles, tm):
    m, k = rows.shape
    n = int(w.shape[2])
    tn = _n_block(k, n, rows.dtype.itemsize)
    nb = n // tn

    def core(rows, w, tile_expert, n_tiles):
        # a tile past the last in use takes the last one's block indices,
        # at the last N block: nothing new is fetched for it
        last = lambda nt: jnp.maximum(nt[0] - 1, 0)
        used = lambda t, nt: t < nt[0]
        x_map = lambda t, j, te, nt: (jnp.minimum(t, last(nt)), 0)
        w_map = lambda t, j, te, nt: (
            te[jnp.minimum(t, last(nt))], 0,
            jnp.where(used(t, nt), j, nb - 1))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(m // tm, nb),
            in_specs=[pl.BlockSpec((tm, k), x_map),
                      pl.BlockSpec((1, k, tn), w_map)],
            out_specs=pl.BlockSpec((tm, tn), lambda t, j, te, nt: (t, j)),
        )
        return pl.pallas_call(
            _gmm_kernel, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((m, n), rows.dtype),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=_fa._INTERPRET,
            name='expert_grouped_matmul',
        )(tile_expert, n_tiles, rows, w)

    # no batch or heads dim: under a mesh every device holds the rows and
    # the experts whole and computes the same product
    return mesh_kernel.sharded_call(
        core, (rows, w, tile_expert.astype(jnp.int32),
               n_tiles.astype(jnp.int32).reshape(1)),
        (None, None, None, None), None, batch=1, heads=())


def expert_grouped_matmul_fallback(rows, w, tile_expert, n_tiles, tm):
    """Pure jax.numpy: every tile against its expert's gathered matrix (a
    test's comparison and the CPU's path; it reads a matrix a tile)."""
    m, k = rows.shape
    tiles = rows.reshape(m // tm, tm, k)
    out = jnp.einsum('tmk,tkn->tmn', tiles, w[tile_expert],
                     preferred_element_type=jnp.float32)
    live = jnp.arange(m // tm) < n_tiles.reshape(())
    return jnp.where(live[:, None, None], out, 0.0).astype(
        rows.dtype).reshape(m, -1)


def expert_grouped_matmul(rows, w, tile_expert, n_tiles, *, tm):
    """See the module's docstring. ``tm`` static: rows a tile."""
    n_tiles = jnp.asarray(n_tiles, jnp.int32).reshape(1)
    if expert_grouped_matmul_available(rows, w, tm):
        return _gmm(rows, w, tile_expert, n_tiles, int(tm))
    return expert_grouped_matmul_fallback(rows, w, tile_expert, n_tiles,
                                          int(tm))
