"""Paged attention: decode attention over a paged KV cache.

The continuous-batching engine (serving/generation.py) stores each slot's
KV rows in non-contiguous fixed-size pages (ops/paged_kv.py). This module
attends q rows to that paged cache two ways:

 - a Pallas TPU kernel (``_paged_decode_kernel``): grid (B*H, P_max) with
   the flattened page table + per-slot positions riding scalar prefetch,
   so each grid step DMAs exactly the page the table points at — the
   kernel never materializes the gathered cache, and its wrapper never
   re-lays the pool: a page is stored head-major (ops/paged_kv.py), so
   the ``[page_size, D]`` block of one head is read where it lies, for
   every head size the kernel takes. Online-softmax state
   (acc/m/l) lives in VMEM scratch and persists across the sequential
   page dimension, exactly the "Ragged Paged Attention" structure
   (PAPERS.md arxiv 2604.15464). An int8 variant streams int8 pages with
   per-row scales folded into scores/probs like flash_decode_int8.
 - a pure-``jax.numpy`` fallback: gather pages through the table into each
   slot's virtual dense cache and run the SAME masked-softmax sequence as
   the dense decode fallback in models/gpt.cached_attention — op-for-op,
   so paged decode is bit-identical to dense decode on CPU (the tier-1
   parity tests rely on this, and greedy tokens match exactly).

``pos`` is a PER-SLOT [B] i32 vector (slots decode at different depths —
that is the whole point of continuous batching); q row j of slot b attends
virtual positions <= pos[b] + j. Inference only (no vjp).
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as _np

# The submodule, not the package re-export of the same-named function:
# ops/__init__.py rebinds the name ``flash_attention`` to the function, so
# any ``import .. as`` / ``from .. import`` form (both resolve through
# getattr on the package) would hand us the function. import_module goes
# straight to sys.modules. Attribute access on _fa stays late-bound so
# set_interpret() is seen live.
import importlib
_fa = importlib.import_module('paddle_tpu.ops.flash_attention')
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import mesh_kernel
from .paged_kv import gather_virtual
from .weight_only import dequantize_kv, is_weight_only

_NEG_INF = _fa._NEG_INF
_EPS = _fa._EPS
_LANES = _fa._LANES
_TQ = _fa._TQ_DECODE


def paged_attention_available(q, pages):
    """Kernel path gate. q: [B,T,H,D]; ``pages``: the k page pool
    [N, H_kv, page_size, D] (pass the bank's ``['int8']`` plane for int8
    pools). Interpret mode (ops/flash_attention.set_interpret) counts as
    available so CPU tests exercise the kernel."""
    if not _fa._platform_ok():
        return False
    b, t, h, d = (int(x) for x in q.shape)
    n, h_kv, ps = (int(x) for x in pages.shape[:3])
    if h_kv == 0 or h % h_kv != 0:
        return False
    return (t <= _TQ and ps % 128 == 0 and d in (64, 128, 256)
            and q.dtype in (jnp.float32, jnp.bfloat16))


def _paged_decode_kernel(pt_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                         acc_ref, m_ref, l_ref, *, scale, ps, tq, p_max, h):
    """Grid (B*H, P_max); the page dim is sequential so the online-softmax
    scratch carries across pages of one (batch, head) row. Pages past the
    slot's needed count are skipped (their DMA still lands — a trash-page
    read — but no FLOPs run)."""
    i = pl.program_id(0)
    p = pl.program_id(1)
    pos = pos_ref[i // h]

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # pages holding keys for q rows at absolute positions pos..pos+tq-1
    needed = (pos + jnp.int32(tq) + jnp.int32(ps - 1)) // jnp.int32(ps)

    @pl.when(p < needed)
    def _compute():
        q = q_ref[0]                                   # [TQ_PAD, D] native
        kblk = k_ref[0, 0]                             # [ps, D]
        vblk = v_ref[0, 0]
        s = jax.lax.dot_general(q, kblk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32
                                ) * _np.float32(scale)            # [TQ, ps]
        q_row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = p * jnp.int32(ps) + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(k_pos <= pos + q_row, s, _NEG_INF)
        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        pr = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(pr, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            pr.astype(vblk.dtype), vblk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(p == p_max - 1)
    def _emit():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[:, :1], _EPS)).astype(o_ref.dtype)


def _paged_decode_kernel_int8(pt_ref, pos_ref, q_ref, k_ref, v_ref, ks_ref,
                              vs_ref, o_ref, acc_ref, m_ref, l_ref, *,
                              scale, ps, tq, p_max, h):
    """int8-page variant: k scale applied to score columns, v scale folded
    into probability rows (see flash_attention._decode_kernel_int8). The
    scales come as the page's [H_kv, ps] block, as stored; the kernel
    takes its KV head's row."""
    i = pl.program_id(0)
    p = pl.program_id(1)
    pos = pos_ref[i // h]
    head = pl.ds((i % h) // (h // ks_ref.shape[1]), 1)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    needed = (pos + jnp.int32(tq) + jnp.int32(ps - 1)) // jnp.int32(ps)

    @pl.when(p < needed)
    def _compute():
        q = q_ref[0]
        kblk = k_ref[0, 0].astype(q.dtype)             # [ps, D]
        ksc = ks_ref[0, head, :]                       # [1, ps] f32
        s = jax.lax.dot_general(q, kblk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32
                                ) * _np.float32(scale)
        s = s * ksc
        q_row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = p * jnp.int32(ps) + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(k_pos <= pos + q_row, s, _NEG_INF)
        m_prev = m_ref[:, :1]
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        pr = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(pr, axis=-1, keepdims=True)
        vblk = v_ref[0, 0].astype(q.dtype)
        vsc = vs_ref[0, head, :]                       # [1, ps] f32
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            (pr * vsc).astype(q.dtype), vblk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(p == p_max - 1)
    def _emit():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[:, :1], _EPS)).astype(o_ref.dtype)


def _kernel_call(kernel_fn, q, page_table, pos, pools):
    """One paged decode kernel over (under a mesh) the block each device
    holds: slots split over 'dp', heads over 'mp' — the pool's own layout
    (ops/paged_kv.POOL_LOGICAL_AXES), so no page moves between devices.
    pools: planes in pool layout — pages [N, H_kv, page_size, D] and, for
    int8 banks, their scales [N, H_kv, page_size]. N may be every layer's
    pages, with the table offset to one layer's."""
    b, t, h, d = q.shape
    h_kv, ps = (int(x) for x in pools[0].shape[1:3])
    p_max = int(page_table.shape[1])

    def core(q, page_table, pos, *pools):
        b, _, h, _ = q.shape                  # this device's slots / heads
        g = h // pools[0].shape[1]
        bh = b * h
        qt = _fa._pad_seq(q.transpose(0, 2, 1, 3).reshape(bh, t, d), _TQ)
        # nothing is re-laid: a head's rows of a page are one block of the
        # pool as it is stored, and a bank's scales come a page at a time,
        # every head's (a block's last two dims are whole tiles). The page
        # id comes straight out of the prefetched table
        page_id = lambda i, p, pt: pt[(i // h) * p_max + p]
        page = lambda i, p, pt, _pos: (page_id(i, p, pt), (i % h) // g, 0, 0)
        scales = lambda i, p, pt, _pos: (page_id(i, p, pt), 0, 0)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(bh, p_max),
            in_specs=[pl.BlockSpec((1, _TQ, d), lambda i, p, *_: (i, 0, 0))]
            + [pl.BlockSpec((1, 1) + x.shape[2:], page) if x.ndim == 4
               else pl.BlockSpec((1,) + x.shape[1:], scales) for x in pools],
            out_specs=pl.BlockSpec((1, _TQ, d), lambda i, p, *_: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((_TQ, d), jnp.float32),        # acc
                pltpu.VMEM((_TQ, _LANES), jnp.float32),   # m (lane-bcast)
                pltpu.VMEM((_TQ, _LANES), jnp.float32),   # l
            ],
        )
        out = pl.pallas_call(
            functools.partial(kernel_fn, scale=1.0 / math.sqrt(d), ps=ps,
                              tq=t, p_max=p_max, h=h),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((bh, _TQ, d), q.dtype),
            interpret=_fa._INTERPRET,
            name='paged_attention',
        )(page_table.reshape(-1), pos, qt, *pools)
        return out[:, :t].reshape(b, h, t, d).transpose(0, 2, 1, 3)

    return mesh_kernel.sharded_call(
        core,
        (q, page_table.astype(jnp.int32),
         jnp.asarray(pos, jnp.int32).reshape(-1), *pools),
        (_fa._BSHD, ('batch', None), ('batch',),
         *((None, 'heads') + (None,) * (x.ndim - 2) for x in pools)),
        _fa._BSHD, batch=b, heads=(h, h_kv))


def paged_flash_decode(q, k_pages, v_pages, page_table, pos):
    """Pallas paged decode. q: [B,T,H,D]; pages [N, H_kv, page_size, D];
    page_table [B, P_max] i32; pos [B] i32 -> [B,T,H,D]."""
    return _kernel_call(_paged_decode_kernel, q, page_table, pos,
                        [k_pages, v_pages])


def paged_flash_decode_int8(q, k_bank, v_bank, page_table, pos):
    """``paged_flash_decode`` over int8 page pools: banks are
    ``{'int8': [N, H_kv, page_size, D] int8, 'scale': [N, H_kv,
    page_size] f32}`` (ops/paged_kv.paged_write rows)."""
    return _kernel_call(
        _paged_decode_kernel_int8, q, page_table, pos,
        [k_bank['int8'], v_bank['int8'], k_bank['scale'], v_bank['scale']])


def paged_attention_fallback(q, k_pages, v_pages, page_table, pos, cdt):
    """Pure-jnp path: gather each slot's virtual dense cache through the
    page table, then run the EXACT op sequence of the dense decode
    fallback (models/gpt.cached_attention) — einsum in the compute dtype,
    f32 masked softmax, cast back — so when the virtual length equals the
    dense S_max the two paths are bitwise identical."""
    if is_weight_only(k_pages):
        kv = gather_virtual(k_pages, page_table)
        vv = gather_virtual(v_pages, page_table)
        kc = dequantize_kv(kv['int8'], kv['scale'], cdt)
        vc = dequantize_kv(vv['int8'], vv['scale'], cdt)
    else:
        kc = gather_virtual(k_pages, page_table)
        vc = gather_virtual(v_pages, page_table)
    kc, vc = _fa.repeat_kv(kc, vc, int(q.shape[2]))
    B, T = q.shape[:2]
    S = kc.shape[1]
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum('bqhd,bkhd->bhqk', q, kc) * scale          # [B,H,T,S]
    q_pos = (jnp.asarray(pos, jnp.int32)[:, None, None]
             + jnp.arange(T)[None, :, None])                  # [B,T,1]
    k_pos = jnp.arange(S)[None, None, :]                      # [1,1,S]
    mask = (k_pos <= q_pos)[:, None]                          # [B,1,T,S]
    s = jnp.where(mask, s.astype(jnp.float32), jnp.float32(-1e30))
    p = jax.nn.softmax(s, axis=-1).astype(cdt)
    return jnp.einsum('bhqk,bkhd->bqhd', p, vc)


def paged_attention(q, k_pages, v_pages, page_table, pos, cdt=None):
    """Decode attention over a paged KV pool; dispatches to the Pallas
    kernel when the shapes/platform allow, else the jnp gather fallback.

    q: [B, T, H, D]; pools: [N, H_kv, page_size, D] arrays or int8 banks;
    page_table: [B, P_max] i32; pos: [B] i32 (first q row's absolute
    position per slot) -> [B, T, H, D]."""
    cdt = q.dtype if cdt is None else cdt
    int8 = is_weight_only(k_pages)
    k_arr = k_pages['int8'] if int8 else k_pages
    if paged_attention_available(q, k_arr):
        if int8:
            return paged_flash_decode_int8(q, k_pages, v_pages, page_table,
                                           pos)
        return paged_flash_decode(q, k_pages, v_pages, page_table, pos)
    return paged_attention_fallback(q, k_pages, v_pages, page_table, pos,
                                    cdt)
