"""Paged attention: decode attention over a paged KV cache.

The continuous-batching engine (serving/generation.py) stores each slot's
KV rows in non-contiguous fixed-size pages (ops/paged_kv.py). This module
attends q rows to that paged cache two ways:

 - a Pallas TPU kernel (``_paged_decode_kernel``): grid (blocks of KV
   heads, the (slot, page) pairs that hold a key this call attends), with
   the flattened page table, the per-slot positions and that list of
   pairs (``page_schedule``) riding scalar prefetch, so each grid step
   DMAs exactly the page the table points at — the kernel never
   materializes the gathered cache, and its wrapper never re-lays the
   pool: a page is stored head-major (ops/paged_kv.py), so every head of
   it is ONE contiguous block, ``[H_kv, page_size, D]``, which a step
   takes whole (``decode_plan`` says how many heads where that is too much
   for the fast memory). The query heads of a KV group, and a tail call's
   T rows, are stacked as rows against their group's K block, padded to
   one sublane tile (16 rows in bf16), so a grouped model reads a page
   once. The grid walks the pages slots HOLD
   (``ceil((pos + T) / page_size)``; one for an idle slot, the trash
   page), not the pages they may hold: its bound is the list's length, a
   scalar read on the device, so one executable serves every depth and a
   call whose slots are all full walks ``slots x P_max`` steps, the dense
   grid. (Until PR 43 the grid WAS ``slots x P_max``, and a step past a
   slot's pages fetched and computed nothing but cost ~0.27 us: two
   thirds of a call at 48 slots that hold 7 of their 24 pages.)
   Online-softmax state (acc/m/l, a heads axis in front) lives in VMEM
   scratch and persists across the sequential step dimension: zeroed on a
   slot's first page, written out on its last, the
   "Ragged Paged Attention" structure (PAPERS.md arxiv 2604.15464) but
   for who fetches: that paper's kernel copies its own pages out of HBM,
   which Mosaic here refuses for D 64 (PERF.md section 6, PR 30). An
   int8 variant of the same body streams int8 pages with per-row scales
   folded into scores/probs like flash_decode_int8.
 - a pure-``jax.numpy`` fallback: gather pages through the table into each
   slot's virtual dense cache and run the SAME masked-softmax sequence as
   the dense decode fallback in models/gpt.cached_attention — op-for-op,
   so paged decode is bit-identical to dense decode on CPU (the tier-1
   parity tests rely on this, and greedy tokens match exactly).

``pos`` is a PER-SLOT [B] i32 vector (slots decode at different depths —
that is the whole point of continuous batching); q row j of slot b attends
virtual positions <= pos[b] + j. Inference only (no vjp).

With a ``window`` q row j attends only the last ``window`` of those
positions (``pos[b] + j - window < key``): the same kernel body walks from
the first page that holds such a key (``_pages_first``) and not from page
0, a slot takes at most as many steps as a window spans pages
(``window_pages(window + T - 1, page_size)``) however wide the table,
and the rows of the first page that lie before the window are
masked. Pages before the first are never named, so a slot may have given
them back (serving/generation.py frees what leaves the window); the call
is named ``paged_attention_window``, so a trace tells it from the full
layers' call.
"""
import collections
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


# What one grid step of the kernel may hold in fast memory: its page
# buffers, q and output blocks, softmax state and the scores it works on.
# Under the compiler's own default limit for a kernel (16 MiB on a v5e),
# with room for what Mosaic keeps beside them, so no limit is raised.
VMEM_BUDGET = 12 * 2 ** 20
_IN_FLIGHT = 2      # page buffers: one being read, one on its way


class DecodePlan(collections.namedtuple(
        'DecodePlan', 'kv_heads rows in_flight vmem_bytes')):
    """How one call of the kernel is tiled. ``kv_heads``: KV heads of a
    page a grid step takes (a divisor of the heads this device holds);
    ``rows``: q rows against each KV head (its group's query heads times
    T, rounded up to the sublane tile of q's dtype); ``in_flight``: page
    buffers; ``vmem_bytes``: what a step holds of ``VMEM_BUDGET``."""


def decode_plan(h, h_kv, d, page_size, t, kv_itemsize, q_itemsize):
    """The plan for q ``[B, t, h, d]`` over pages ``[N, h_kv, page_size,
    d]`` (the heads ONE device holds), from the shapes alone: the most KV
    heads a step whose buffers fit ``VMEM_BUDGET`` — every head of a page
    where that fits, so a page is one contiguous block of the pool — or
    None where not even one head fits (the caller then gathers)."""
    g = h // h_kv
    tile = 32 // q_itemsize                     # sublanes: 8 f32, 16 bf16
    rows = -(-g * t // tile) * tile
    d = -(-d // _LANES) * _LANES                # a row of 64 fills 128 lanes
    quantized = kv_itemsize == 1
    # int8 banks: every head's scales of a page come whole, K and V
    scales = _IN_FLIGHT * 2 * h_kv * page_size * 4 if quantized else 0

    def step_bytes(heads):
        pages = _IN_FLIGHT * 2 * page_size * d * kv_itemsize     # K and V
        if quantized:
            pages += 2 * page_size * d * q_itemsize    # a head's, widened
        q_out = 2 * 2 * rows * d * q_itemsize          # both double-buffered
        state = rows * (d + 2 * _LANES) * 4            # acc, m, l
        scores = 2 * rows * page_size * 4              # s and p, float32
        return heads * (pages + q_out + state) + scores + scales

    for heads in range(h_kv, 0, -1):
        if h_kv % heads == 0 and step_bytes(heads) <= VMEM_BUDGET:
            return DecodePlan(heads, rows, _IN_FLIGHT, step_bytes(heads))
    return None


def _plan_of(q, pages):
    _, t, h, d = (int(x) for x in q.shape)
    h_kv, ps = (int(x) for x in pages.shape[1:3])
    return decode_plan(h, h_kv, d, ps, t, pages.dtype.itemsize,
                       q.dtype.itemsize)


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
            and q.dtype in (jnp.float32, jnp.bfloat16)
            and _plan_of(q, pages) is not None)


def _pages_held(pos, t, ps, p_max):
    """Pages holding keys for q rows at pos..pos+t-1: at least one (an
    idle slot's, the trash page), never past the table."""
    return jnp.clip((pos + jnp.int32(t + ps - 1)) // jnp.int32(ps), 1, p_max)


def _pages_first(pos, ps, window):
    """The first page that holds a key the q row at ``pos`` attends."""
    return jnp.maximum(pos - jnp.int32(window - 1), 0) // jnp.int32(ps)


def _pages_walked(pos, t, ps, p_max, window):
    """-> (first, held): pages ``first .. held - 1`` of a slot hold the
    keys its q rows attend, in a window or not; at least one."""
    held = _pages_held(pos, t, ps, p_max)
    if window is None:
        return jnp.zeros_like(held), held
    return jnp.minimum(_pages_first(pos, ps, window), held - 1), held


def window_pages(rows, page_size):
    """The most pages ``rows`` consecutive rows span, wherever they start:
    what a slot holds of a window layer, and the most steps a slot takes of
    that layer's grid."""
    return (int(rows) + int(page_size) - 2) // int(page_size) + 1


def page_schedule(pos, t, ps, p_max, window=None):
    """The grid's page axis for q rows at ``pos [B]``..+t-1: the (slot,
    page) pairs that hold a key they attend, slot after slot and each
    slot's pages in order, from its first (``_pages_first``; 0 without a
    window) to the last it holds (``_pages_held``; an idle slot's one, the
    trash page). -> ``step_slot``, ``step_page`` (the slot's OWN page
    number: the table's column) of the static length a call of full slots
    walks, and ``total``, how many of them this call walks. Entries past
    ``total`` are zeros: no step that runs reads them, the pipeline's
    look-ahead past the grid's end does (slot 0's first page, a page that
    exists). From the positions alone: every layer of a step shares one
    schedule."""
    pos = jnp.asarray(pos, jnp.int32).reshape(-1)
    depth = p_max if window is None else min(
        p_max, window_pages(window + t - 1, ps))
    first, held = _pages_walked(pos, t, ps, p_max, window)
    count = held - first
    slots = jnp.arange(pos.shape[0], dtype=jnp.int32)
    # a running sum as a compare-and-reduce over [B, B]: one fusion, where
    # ``cumsum`` is a copy, a reduce-window and a reduce in every layer
    ends = jnp.sum(jnp.where(slots <= slots[:, None], count, 0), axis=1)
    starts = ends - count
    s = jnp.arange(pos.shape[0] * depth, dtype=jnp.int32)[:, None]
    mine = (starts <= s) & (s < ends)                  # [B * depth, B]
    step_slot = jnp.sum(jnp.where(mine, slots, 0), axis=1)
    step_page = jnp.sum(jnp.where(mine, first + s - starts, 0), axis=1)
    return step_slot, step_page, ends[-1]


def schedule_index_maps(steps, p_max):
    """The two pieces every block's index map is made of, over the scalar
    prefetch ``(pt, pos, step_slot, step_page)``: -> ``slot_of(s, *pre)``,
    the slot of grid step ``s``, and ``page_of(s, *pre)``, the id of its
    page out of the flat table. Mosaic's pipeline names steps PAST the
    grid's end too (it looks ahead of the last step it runs), so the step
    is clamped to the schedule's last entry (``steps`` of them): a word
    read behind the arrays is a wild page id, and the chip halts on it
    (PERF.md section 6, PR 43)."""
    at = lambda s: jnp.minimum(s, steps - 1)
    slot_of = lambda s, pt, pos, slot, pg: slot[at(s)]
    page_of = lambda s, pt, pos, slot, pg: pt[slot[at(s)] * p_max + pg[at(s)]]
    return slot_of, page_of


def _paged_decode_kernel(pt_ref, pos_ref, slot_ref, page_ref, q_ref, k_ref,
                         v_ref, *refs, scale, ps, t, p_max, window=None):
    """Grid (blocks of KV heads, the steps of ``page_schedule``); the step
    dim is sequential, a slot's pages in order, so the online-softmax
    scratch carries across the pages of one slot: zeroed on its first
    page, written out on its last. A step holds ``[heads, ps, D]`` of K
    and of V — every head of the page where the plan allows, one
    contiguous piece of the pool — and the q rows of a KV group stacked
    against their group's K block.

    refs: for int8 pages the page's scales ``[H_kv, ps]`` of K and of V
    (k scale on score columns, v scale folded into probability rows, as
    flash_attention._decode_kernel_int8), then the output block and
    acc / m / l."""
    scales, (o_ref, acc_ref, m_ref, l_ref) = refs[:-4], refs[-4:]
    heads, rows = q_ref.shape[1:3]
    j, step = pl.program_id(0), pl.program_id(1)
    pos = pos_ref[slot_ref[step]]
    page = page_ref[step]
    first, held = _pages_walked(pos, t, ps, p_max, window)

    @pl.when(page == first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # row r of a KV head is query head r // t of its group, q row r % t
    k_pos = page * jnp.int32(ps) + jax.lax.broadcasted_iota(
        jnp.int32, (rows, ps), 1)
    q_pos = pos
    if t > 1:
        q_pos = pos + jax.lax.rem(jax.lax.broadcasted_iota(
            jnp.int32, (rows, ps), 0), jnp.int32(t))
    visible = k_pos <= q_pos
    if window is not None:
        visible = visible & (k_pos > q_pos - jnp.int32(window))
    for hd in range(heads):
        q = q_ref[0, hd]                               # [rows, D] native
        kblk, vblk = k_ref[0, hd], v_ref[0, hd]        # [ps, D]
        if scales:
            row = pl.ds(j * heads + hd, 1)
            kblk, vblk = kblk.astype(q.dtype), vblk.astype(q.dtype)
        s = jax.lax.dot_general(q, kblk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32
                                ) * _np.float32(scale)        # [rows, ps]
        if scales:
            s = s * scales[0][0, row, :]               # [1, ps] f32
        s = jnp.where(visible, s, _NEG_INF)
        # m, l and alpha stay as they are stored, one value a row in
        # every lane: taking lane 0 and spreading it again costs a
        # lane permute each, and those were most of a head's time
        m_prev, l_prev = m_ref[hd], l_ref[hd]          # [rows, 128]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        pr = jnp.exp(s - _fa._lanes(m_new, ps))
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(pr, axis=-1, keepdims=True)
        if scales:
            pr = pr * scales[1][0, row, :]
        acc_ref[hd] = (acc_ref[hd] * _fa._lanes(alpha, acc_ref.shape[2])
                       + jax.lax.dot_general(
                           pr.astype(vblk.dtype), vblk,
                           (((1,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32))
        m_ref[hd] = m_new
        l_ref[hd] = l_new

    @pl.when(page == held - 1)
    def _emit():
        for hd in range(heads):
            o_ref[0, hd] = (acc_ref[hd] / _fa._lanes(
                jnp.maximum(l_ref[hd], _EPS), acc_ref.shape[2])
                ).astype(o_ref.dtype)


def _kernel_call(q, page_table, pos, pools, window=None):
    """The paged decode kernel over (under a mesh) the block each device
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
        h_kv = pools[0].shape[1]
        plan = _plan_of(q, pools[0])
        if plan is None:
            raise ValueError(
                f'no head of a page {tuple(pools[0].shape[1:])} fits the '
                f'kernel\'s fast memory: paged_attention_available says so')
        heads, rows = plan.kv_heads, plan.rows
        # a KV group's query heads are neighbours, so stacking them (and
        # their t rows) against the group's K block is a view when t is 1
        qt = q.transpose(0, 2, 1, 3).reshape(b, h_kv, (h // h_kv) * t, d)
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, rows - qt.shape[2]), (0, 0)))

        # nothing is re-laid: the heads of a page are one block of the pool
        # as it is stored, and a bank's scales come a page at a time, every
        # head's. The grid is as long as the pages this device's slots
        # hold; a step's page id comes straight out of the prefetched table
        step_slot, step_page, total = page_schedule(pos, t, ps, p_max, window)
        slot_of, page_of = schedule_index_maps(step_slot.shape[0], p_max)
        page = lambda j, s, *pre: (page_of(s, *pre), j, 0, 0)
        scales = lambda j, s, *pre: (page_of(s, *pre), 0, 0)
        block = pl.BlockSpec((1, heads, rows, d),
                             lambda j, s, *pre: (slot_of(s, *pre), j, 0, 0))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(h_kv // heads, total),
            in_specs=[block] + [
                pl.BlockSpec((1, heads) + x.shape[2:], page) if x.ndim == 4
                else pl.BlockSpec((1,) + x.shape[1:], scales) for x in pools],
            out_specs=block,
            scratch_shapes=[
                pltpu.VMEM((heads, rows, d), jnp.float32),        # acc
                pltpu.VMEM((heads, rows, _LANES), jnp.float32),   # m
                pltpu.VMEM((heads, rows, _LANES), jnp.float32),   # l
            ],
        )
        out = pl.pallas_call(
            functools.partial(_paged_decode_kernel, scale=1.0 / math.sqrt(d),
                              ps=ps, t=t, p_max=p_max, window=window),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
            interpret=_fa._INTERPRET,
            name='paged_attention' if window is None
            else 'paged_attention_window',
        )(page_table.reshape(-1), pos, step_slot, step_page, qt, *pools)
        return out[:, :, :(h // h_kv) * t].reshape(b, h, t, d).transpose(
            0, 2, 1, 3)

    return mesh_kernel.sharded_call(
        core,
        (q, page_table.astype(jnp.int32),
         jnp.asarray(pos, jnp.int32).reshape(-1), *pools),
        (_fa._BSHD, ('batch', None), ('batch',),
         *((None, 'heads') + (None,) * (x.ndim - 2) for x in pools)),
        _fa._BSHD, batch=b, heads=(h, h_kv))


def paged_flash_decode(q, k_pages, v_pages, page_table, pos, window=None):
    """Pallas paged decode. q: [B,T,H,D]; pages [N, H_kv, page_size, D];
    page_table [B, P_max] i32; pos [B] i32 -> [B,T,H,D]."""
    return _kernel_call(q, page_table, pos, [k_pages, v_pages], window)


def paged_flash_decode_int8(q, k_bank, v_bank, page_table, pos, window=None):
    """``paged_flash_decode`` over int8 page pools: banks are
    ``{'int8': [N, H_kv, page_size, D] int8, 'scale': [N, H_kv,
    page_size] f32}`` (ops/paged_kv.paged_write rows)."""
    return _kernel_call(
        q, page_table, pos,
        [k_bank['int8'], v_bank['int8'], k_bank['scale'], v_bank['scale']],
        window)


def paged_attention_fallback(q, k_pages, v_pages, page_table, pos, cdt,
                             window=None):
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
    mask = k_pos <= q_pos
    if window is not None:
        # what lies before the window may be a page given back and written
        # by another slot since: masked like what lies after the position
        mask = mask & (k_pos > q_pos - int(window))
    mask = mask[:, None]                                      # [B,1,T,S]
    s = jnp.where(mask, s.astype(jnp.float32), jnp.float32(-1e30))
    p = jax.nn.softmax(s, axis=-1).astype(cdt)
    return jnp.einsum('bhqk,bkhd->bqhd', p, vc)


def paged_attention(q, k_pages, v_pages, page_table, pos, cdt=None,
                    window=None):
    """Decode attention over a paged KV pool; dispatches to the Pallas
    kernel when the shapes/platform allow, else the jnp gather fallback.

    q: [B, T, H, D]; pools: [N, H_kv, page_size, D] arrays or int8 banks;
    page_table: [B, P_max] i32; pos: [B] i32 (first q row's absolute
    position per slot) -> [B, T, H, D]. ``window`` (static): a q row
    attends its last ``window`` positions only, and the table's entries
    for pages wholly before them are never read."""
    cdt = q.dtype if cdt is None else cdt
    int8 = is_weight_only(k_pages)
    k_arr = k_pages['int8'] if int8 else k_pages
    if paged_attention_available(q, k_arr):
        if int8:
            return paged_flash_decode_int8(q, k_pages, v_pages, page_table,
                                           pos, window)
        return paged_flash_decode(q, k_pages, v_pages, page_table, pos,
                                  window)
    return paged_attention_fallback(q, k_pages, v_pages, page_table, pos,
                                    cdt, window)
