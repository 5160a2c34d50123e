"""Latent attention over a paged pool that has no heads axis.

Multi-head latent attention caches one row a token a layer, ``[c | k_rope]``
(the compressed key/value and the one rotary key all heads share), not a K
and a V a head. Two paths read it:

 - ``paged_latent_attention``: decode, in the absorbed form. Each head's
   query is carried into the latent space (``q~ = q_nope W_kvb^K``), so a
   slot's heads are the rows of ONE matrix operand ``[H, rank + rope]``
   against a page ``[page_size, rank + rope]``; the value is the row's first
   ``rank`` columns. A Pallas kernel whose grid is the (slot, page) pairs
   that hold a row this call attends (``paged_attention.page_schedule``:
   the pages slots hold, not the ``slots x P_max`` they may hold; the
   bound is the list's length, read on the device), with the page table,
   the positions and that list in scalar prefetch. The pool is handed over
   whole (``[L * N, page_size, W]``, a
   reshape) and the layer's offset is added to the table: no layer's plane
   is sliced out or copied.
 - ``latent_prefill_attention``: prefill over the fresh rows, in the
   expanded form at q/k width nope + rope and v width v_head_dim. The
   forward flash kernel has one head width, so q and k are padded with zeros
   to the next width it takes and v with zero columns that are cut off the
   result: the scores are the same numbers, no ``[H, T, S]`` array exists,
   and the kernel stays one. (The padded products are the price: 256 / 192
   of the score FLOPs and 256 / 128 of the value's; a value width of its own
   in ``_fwd_kernel`` would save them.)

Inference only (no vjp).
"""
import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as _np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import mesh_kernel
from .paged_attention import (_pages_walked, page_schedule,
                              schedule_index_maps)

# the submodule, not ops/__init__'s same-named function (paged_attention.py)
_fa = importlib.import_module('paddle_tpu.ops.flash_attention')

_NEG_INF = _fa._NEG_INF
_EPS = _fa._EPS
_LANES = _fa._LANES
_FLASH_WIDTHS = (64, 128, 256)


def paged_latent_attention_available(q, pool):
    """Kernel gate. q [B, H, W]; pool [L, N, page_size, W]."""
    if not _fa._platform_ok():
        return False
    ps, w = int(pool.shape[2]), int(pool.shape[3])
    return (ps % 128 == 0 and int(q.shape[-1]) == w
            and q.dtype in (jnp.float32, jnp.bfloat16))


def _latent_kernel(pt_ref, pos_ref, slot_ref, page_ref, q_ref, pg_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, scale, ps, p_max, rank):
    """Grid (the steps of ``page_schedule``), a slot's pages in order: the
    online-softmax state of a slot's heads is carried from page to page in
    scratch, zeroed on its first page and written out on its last."""
    step = pl.program_id(0)
    pos = pos_ref[slot_ref[step]]
    p = page_ref[step]
    first, held = _pages_walked(pos, 1, ps, p_max, None)

    @pl.when(p == first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0]                                   # [H, W]
    page = pg_ref[0]                               # [ps, W]
    s = jax.lax.dot_general(q, page, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32
                            ) * _np.float32(scale)            # [H, ps]
    k_pos = p * jnp.int32(ps) + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    s = jnp.where(k_pos <= pos, s, _NEG_INF)
    m_prev = m_ref[:, :1]
    l_prev = l_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    pr = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = l_prev * alpha + jnp.sum(pr, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        pr.astype(page.dtype), page[:, :rank], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                   # [H, rank]
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(p == held - 1)
    def _emit():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[:, :1], _EPS)).astype(o_ref.dtype)


def _latent_decode(q, pool, page_table, pos, layer, scale, rank):
    b, h, w = q.shape
    n_layers, n, ps, _ = pool.shape
    p_max = int(page_table.shape[1])
    pages = pool.reshape(n_layers * n, ps, w)
    table = page_table.astype(jnp.int32) + jnp.int32(layer * n)

    def core(q, table, pos, pages):
        b = q.shape[0]                        # this device's slots
        # the grid is as long as the pages this device's slots hold
        step_slot, step_page, total = page_schedule(pos, 1, ps, p_max)
        slot_of, page_of = schedule_index_maps(step_slot.shape[0], p_max)
        page = lambda s, *pre: (page_of(s, *pre), 0, 0)
        rows = lambda s, *pre: (slot_of(s, *pre), 0, 0)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(total,),
            in_specs=[pl.BlockSpec((1, h, w), rows),
                      pl.BlockSpec((1, ps, w), page)],
            out_specs=pl.BlockSpec((1, h, rank), rows),
            scratch_shapes=[
                pltpu.VMEM((h, rank), jnp.float32),       # acc
                pltpu.VMEM((h, _LANES), jnp.float32),     # m (lane-bcast)
                pltpu.VMEM((h, _LANES), jnp.float32),     # l
            ],
        )
        return pl.pallas_call(
            functools.partial(_latent_kernel, scale=scale, ps=ps,
                              p_max=p_max, rank=rank),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, h, rank), q.dtype),
            interpret=_fa._INTERPRET,
            name='paged_latent_attention',
        )(table.reshape(-1), pos, step_slot, step_page, q, pages)

    # slots over 'dp'; the pool has no heads axis to split, so every device
    # of 'mp' holds it whole and runs all heads
    return mesh_kernel.sharded_call(
        core, (q, table, jnp.asarray(pos, jnp.int32).reshape(-1), pages),
        (('batch', None, None), ('batch', None), ('batch',), None),
        ('batch', None, None), batch=b, heads=())


def paged_latent_attention_fallback(q, pool, page_table, pos, layer, scale,
                                    rank):
    """Pure jax.numpy: gather each slot's rows through the table."""
    plane = pool[layer]
    rows = jnp.take(plane, page_table, axis=0)        # [B, P_max, ps, W]
    rows = rows.reshape(rows.shape[0], -1, rows.shape[-1])
    s = jnp.einsum('bhw,bsw->bhs', q, rows,
                   preferred_element_type=jnp.float32) * scale
    k_pos = jnp.arange(rows.shape[1])[None, None, :]
    s = jnp.where(k_pos <= jnp.asarray(pos, jnp.int32)[:, None, None], s,
                  _NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum('bhs,bsr->bhr', p, rows[..., :rank],
                      preferred_element_type=jnp.float32).astype(q.dtype)


def paged_latent_attention(q, pool, page_table, pos, layer, *, scale, rank):
    """Absorbed-form decode attention of one layer over the latent pool.

    q [B, H, W]: each head's ``[q_nope W_kvb^K | rope(q_r) | zeros]``; pool
    [L, N, page_size, W], the rows ``[c | k_rope | zeros]``, W = rank + rope
    width padded to whole lanes (a width that is not makes the compiler
    copy the pool before the call);
    page_table [B, P_max] i32; pos [B] i32 (the row being decoded, already
    written); ``layer`` static. -> [B, H, rank]: ``p c`` a head, still to
    be carried out of the latent space (``W_kvb^V``) by the caller."""
    if paged_latent_attention_available(q, pool):
        return _latent_decode(q, pool, page_table, pos, int(layer),
                              float(scale), int(rank))
    return paged_latent_attention_fallback(q, pool, page_table, pos,
                                           int(layer), scale, int(rank))


def latent_prefill_attention(q, k, v, *, scale):
    """Causal attention over fresh rows at q/k width D_qk and v width D_v
    (both [B, T, H, .]) -> [B, T, H, D_v], through the forward flash kernel
    at the next width it takes (see the module's docstring). ``scale`` is
    folded into q in float32 before the cast back, since the kernel's own
    is that of its padded width."""
    d_qk, d_v = int(q.shape[-1]), int(v.shape[-1])
    d = next(x for x in _FLASH_WIDTHS if x >= max(d_qk, d_v))
    q = (q.astype(jnp.float32) * (scale * d ** 0.5)).astype(q.dtype)
    pad = lambda x: jnp.pad(x, ((0, 0),) * 3 + ((0, d - x.shape[-1]),))
    return _fa.flash_attention(pad(q), pad(k), pad(v),
                               causal=True)[..., :d_v]
