"""Paged KV cache: fixed-size pages in a shared pool + per-slot page tables.

The dense decode cache (`models/gpt.init_kv_cache`) reserves a contiguous
``[L, B, S_max, H_kv, Dh]`` strip per request — at S_max=2048 a slot holds
its worst-case footprint for its whole lifetime even when the sequence is
30 tokens long. The paged layout (vLLM / "Ragged Paged Attention",
PAPERS.md arxiv 2604.15464) breaks the cache into fixed-size pages in one
shared pool:

    pool      [L, N_pages, H_kv, page_size, Dh]   (k and v each)
    table     [slots, P_max] int32                (page ids per slot)

so a sequence only pins ``ceil(len/page_size)`` pages and the continuous-
batching engine (serving/generation.py) packs many ragged sequences into
one fixed-slot decode batch. Page ids are HOST-side state handed to the
compiled step as a traced int32 table — page churn never recompiles.

Conventions shared by every consumer:

 - **Page 0 is the trash page.** The allocator never hands it out. Writes
   that must go nowhere (decode rows of inactive slots, rows past the
   table's end, and in a plane without a heads axis the prompt padding
   rows past a sequence's valid length) are routed to page 0, and
   unassigned page-table entries stay 0 — a gather through a fresh table
   reads page 0, and the attention mask discards those positions anyway.
 - **A page is head-major**: ``[H_kv, page_size, Dh]``, so the rows one
   head holds in a page are one contiguous ``[page_size, Dh]`` block — the
   block the paged kernel (ops/paged_attention.py) fetches, straight out
   of the pool as it is stored. (In rows-major pages, ``[page_size, H_kv,
   Dh]``, a head's rows lie H_kv * Dh values apart, inside the chip's
   (sublane, lane) tiles: no block can name them, and the planes have to
   be transposed for the kernel in every step. PERF.md, PR 28.)
 - The pool is layer-major and a forward pass never takes a layer's plane
   out of it: the layers' loop carries the pool whole, viewed as
   ``[L * N_pages, ...]`` with layer ``l``'s page table offset by
   ``l * N_pages`` (models/gpt.paged_forward_with_cache), and a write
   updates the carried buffer in place: a prefill's rows a page at a time
   (``_write_pages``), a decode step's row by the tile it falls in
   (``_row_write``, the Pallas call ``paged_row_write``).
 - int8-KV pools reuse the ``{'int8', 'scale'}`` bank layout of
   ops/weight_only (per-row scales), so the +32% int8 decode win composes.
"""
import importlib
import threading

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import mesh_kernel
from .weight_only import init_kv_bank, is_weight_only, quantize_kv

# the module, not the function ``ops/__init__`` rebinds the name to
# (ops/paged_attention.py says why); ``_fa._INTERPRET`` stays late-bound
_fa = importlib.import_module('paddle_tpu.ops.flash_attention')

TRASH_PAGE = 0   # reserved; see module docstring

# Logical axes of one pool plane for the partitioner rules table
# (parallel/mesh_engine.py shards 'kv_heads' over mp; 'kv_pages' is
# replicated by rule — the +1 trash page makes the page count indivisible
# by any mesh degree, so a logical page spans every head-shard and the
# HOST-side allocator/table machinery below never sees the mesh).
POOL_LOGICAL_AXES = ('layers', 'kv_pages', 'kv_heads', None, None)


def pages_for(n_tokens, page_size):
    """Pages needed to hold ``n_tokens`` rows."""
    return max(0, -(-int(n_tokens) // int(page_size)))


def init_paged_pool(num_layers, num_pages, page_size, kv_heads, head_dim,
                    dtype, int8=False):
    """Allocate the shared page pool: ``{'k': pages, 'v': pages}`` with
    pages ``[L, N, H_kv, page_size, Dh]`` (int8: weight_only banks of the
    same shape, their scales ``[L, N, H_kv, page_size]``). ``num_pages``
    INCLUDES the reserved trash page 0."""
    if num_pages < 2:
        raise ValueError('num_pages must be >= 2 (page 0 is reserved)')
    shape = (num_layers, num_pages, kv_heads, page_size, head_dim)
    if int8:
        return {'k': init_kv_bank(shape), 'v': init_kv_bank(shape)}
    return {'k': jnp.zeros(shape, dtype), 'v': jnp.zeros(shape, dtype)}


class PageAllocator:
    """Host-side REFCOUNTED free-list over pages ``1..num_pages-1`` (page 0
    reserved — it is never handed out and never re-enters the free list).

    All-or-nothing ``alloc(n)``: a request either gets all n pages or None,
    so a half-admitted sequence never strands pages. A fresh allocation
    carries refcount 1; ``retain()`` lets a second holder (a live slot
    sharing a cached prefix page, or the prefix cache itself) pin the same
    page, and ``free()`` decrements — the page returns to the free list
    only at refcount zero. Freeing a page that holds no references (a
    double free) raises instead of silently corrupting the pool.
    Thread-safe (the engine's scheduler thread and stats readers may
    race); this lock is a LEAF — never call out while holding it."""

    def __init__(self, num_pages):
        if num_pages < 2:
            raise ValueError('num_pages must be >= 2 (page 0 is reserved)')
        self.num_pages = int(num_pages)
        self._free = list(range(self.num_pages - 1, 0, -1))  # pop() -> low ids
        self._refs = {}          # page id -> live reference count (>= 1)
        self._lock = threading.Lock()

    @property
    def free_pages(self):
        with self._lock:
            return len(self._free)

    @property
    def used_pages(self):
        return (self.num_pages - 1) - self.free_pages

    def refcount(self, page):
        """Current reference count of ``page`` (0 when on the free list)."""
        with self._lock:
            return self._refs.get(int(page), 0)

    def alloc(self, n):
        """-> list of n page ids (each at refcount 1), or None if the pool
        can't cover them."""
        n = int(n)
        if n < 0:
            raise ValueError('alloc(n) needs n >= 0')
        with self._lock:
            if n > len(self._free):
                return None
            out = [self._free.pop() for _ in range(n)]
            for p in out:
                self._refs[p] = 1
        return out

    def retain(self, pages):
        """Add one reference to each already-allocated page (page sharing:
        a slot mapping cached prefix pages, or the cache publishing a
        slot's pages). Retaining a free or invalid page raises — sharing
        an unowned page would alias whoever allocates it next."""
        with self._lock:
            for p in pages:
                p = int(p)
                if not 0 < p < self.num_pages:
                    raise ValueError(f'retain() of invalid page id {p}')
                if p not in self._refs:
                    raise ValueError(f'retain() of unallocated page {p}')
            for p in pages:
                self._refs[int(p)] += 1

    def free(self, pages):
        """Drop one reference per page; a page returns to the free list at
        refcount zero. Raises on page 0, out-of-range ids, and double
        frees (the trash page can therefore never reach the free list)."""
        with self._lock:
            for p in pages:
                p = int(p)
                if not 0 < p < self.num_pages:
                    raise ValueError(f'free() of invalid page id {p}')
                if p not in self._refs:
                    raise ValueError(f'double free of page {p}')
            for p in pages:
                p = int(p)
                self._refs[p] -= 1
                if self._refs[p] == 0:
                    del self._refs[p]
                    self._free.append(p)


def flat_write_indices(page_table, pos, n_rows, page_size, valid=None):
    """[B, n_rows] int32 indices into a ``[N*page_size, ...]`` flattened
    pool for the rows a (prefill or decode) step writes.

    ``page_table``: [B, P_max] i32; ``pos``: [B] i32 (absolute position of
    each sequence's first new row); ``valid``: [B] i32 or None — rows with
    j >= valid[b] are padding and route to the trash page (index j inside
    page 0, which real pages can never alias since they start at
    ``page_size``)."""
    ps = int(page_size)
    p_max = int(page_table.shape[1])
    j = jnp.arange(n_rows, dtype=jnp.int32)[None, :]          # [1, T]
    abs_pos = pos.astype(jnp.int32)[:, None] + j              # [B, T]
    logical = jnp.clip(abs_pos // ps, 0, p_max - 1)
    page = jnp.take_along_axis(page_table, logical, axis=1)   # [B, T]
    flat = page * ps + abs_pos % ps
    if valid is not None:
        ok = j < valid.astype(jnp.int32)[:, None]
        # trash rows: distinct offsets inside page 0 (j % ps) — collisions
        # between sequences are fine, the rows are garbage by definition
        flat = jnp.where(ok, flat, j % ps)
    return flat


def _write_pages(plane, rows, page_table, pos, valid):
    """Rows into a head-major plane, a page at a time.

    ``plane``: [N, H, page_size, ...]; ``rows``: [B, T, H, ...]. A row of a
    sequence is H pieces of a page, one a head, and a scatter of single
    pieces takes the chip 70 ns each (a 1024-row prefill: 57 ms, my chip
    run, PR 28). So the pages the rows fall in are read, the rows laid
    over them, and the pages written back whole: T rows from any offset
    span at most ``(T + page_size - 2) // page_size + 1`` pages. Rows past
    ``valid`` are not written at all; a page past the table's end is the
    trash page. The pages a sequence writes are its own (the allocator's,
    or the prefix cache's copy-on-write), so laying the old rows back
    changes nothing anybody reads."""
    ps = int(plane.shape[2])
    b, t = rows.shape[:2]
    p_max = int(page_table.shape[1])
    npg = (t + ps - 2) // ps + 1
    pos = pos.astype(jnp.int32)
    logical = (pos // ps)[:, None] + jnp.arange(npg, dtype=jnp.int32)[None]
    phys = jnp.where(
        logical < p_max,
        jnp.take_along_axis(page_table, jnp.minimum(logical, p_max - 1),
                            axis=1),
        TRASH_PAGE).reshape(-1)                                # [B * npg]
    old = plane[phys]                              # [B * npg, H, ps, ...]

    def window(rows_b, off):
        # this sequence's rows at their offset in the pages they span
        buf = jnp.zeros((npg * ps,) + rows_b.shape[1:], plane.dtype)
        return jax.lax.dynamic_update_slice_in_dim(
            buf, rows_b.astype(plane.dtype), off, axis=0)
    off = pos % ps
    fresh = jax.vmap(window)(rows, off)            # [B, npg * ps, H, ...]
    fresh = jnp.moveaxis(
        fresh.reshape((b * npg, ps) + fresh.shape[2:]), 1, 2)
    at = jnp.arange(npg * ps, dtype=jnp.int32)[None] - off[:, None]
    n_rows = t if valid is None else valid.astype(jnp.int32)[:, None]
    ok = ((at >= 0) & (at < n_rows)).reshape(
        (b * npg, 1, ps) + (1,) * (plane.ndim - 3))
    return plane.at[phys].set(jnp.where(ok, fresh, old))


# ---- a decode step's one row a sequence: the tile it falls in, in place ----

_LANES = 128
_TILE_BYTES = 32       # a sublane tile's rows x itemsize: 8 float32, 16 bf16
# what a grid step of the row kernel holds in fast memory (a tile a
# sequence, and the sequences' rows with both of the pipeline's buffers)
# stays far inside what the compiler grants unasked
_ROW_WRITE_VMEM = 6 << 20


def _rows_a_step(b, h, d):
    """Sequences a grid step of the row kernel takes: the most that divide
    ``b`` and keep the step inside ``_ROW_WRITE_VMEM`` (a sequence holds a
    tile, and its row padded to a tile in each of two buffers)."""
    fits = [g for g in range(1, b + 1)
            if b % g == 0 and 3 * g * h * _TILE_BYTES * d <= _ROW_WRITE_VMEM]
    return max(fits) if fits else None


def _row_write_available(plane, rows, valid):
    """The row kernel's gate, from what the call shows: ONE row a sequence
    (a decode step; a prefill's rows span whole pages, where a page at a
    time is right), none of them padding, a head-major float plane whose
    rows fill whole lanes and whose pages hold whole sublane tiles, a
    sequence's tile inside the budget; on the chip (or interpreted:
    ops/flash_attention.set_interpret)."""
    if (valid is not None or plane.ndim != 4 or rows.ndim != 4
            or rows.shape[1] != 1
            or not jnp.issubdtype(plane.dtype, jnp.floating)):
        return False
    _, h, ps, d = plane.shape
    return (ps % (_TILE_BYTES // plane.dtype.itemsize) == 0
            and d % _LANES == 0
            and _rows_a_step(rows.shape[0], h, d) is not None
            and _fa._platform_ok())


def _row_write(plane, rows, page_table, pos):
    """One row a sequence into a head-major plane, where the plane lies.

    ``plane``: [N, H, page_size, D]; ``rows``: [B, 1, H, D]. The call's
    result is its operand's buffer, left in HBM: for each sequence the
    kernel copies in the ONE sublane tile of the page its row falls in (16
    rows of bf16: a packed tile takes no single-row DMA), lays the row over
    its place and copies the tile back, every sequence's copy in flight at
    once; nothing else of the pool moves. The page comes straight out of
    the prefetched table, chosen as ``_write_pages`` chooses it: past the
    table's end, the trash page.

    The tiles a step writes are its sequences' own, except the trash
    page's: idle slots and positions past the table's end all name page 0,
    so several copies of one call may read and write the same tile of it
    at once. That can only ever corrupt page 0, which holds garbage by
    definition and which no table of a live sequence names
    (``PageAllocator`` never hands it out).

    (The form with a BlockSpec tile a grid step, the pipeline fetching and
    writing back the aliased plane, passed every test of the call alone and
    halted the chip inside the engine's step, ~340 steps into a run:
    PERF.md section 6, PR 41.)"""
    ps, d = (int(x) for x in plane.shape[2:])
    b, p_max = (int(x) for x in page_table.shape)
    r = _TILE_BYTES // plane.dtype.itemsize

    def core(table, pos, rows, plane):
        h = plane.shape[1]                    # this device's heads
        g = _rows_a_step(b, h, d)

        def kernel(table_ref, pos_ref, rows_ref, plane_ref, out_ref, tiles,
                   sems):
            del plane_ref           # the result is the same buffer
            first = pl.program_id(0) * g

            def tile(i, to_plane):
                at = pos_ref[first + i]
                logical = at // ps
                page = jnp.where(
                    logical < p_max,
                    table_ref[(first + i) * p_max
                              + jnp.minimum(logical, p_max - 1)], TRASH_PAGE)
                there = out_ref.at[
                    page, :, pl.ds(pl.multiple_of(at % ps // r * r, r), r), :]
                src, dst = ((tiles.at[i], there) if to_plane
                            else (there, tiles.at[i]))
                return pltpu.make_async_copy(src, dst, sems.at[i])

            def fetch(i, c):
                tile(i, False).start()
                return c
            jax.lax.fori_loop(0, g, fetch, 0)
            row_of = jax.lax.broadcasted_iota(jnp.int32, (h, r, d), 1)

            def lay(i, c):
                tile(i, False).wait()
                tiles[i] = jnp.where(row_of == pos_ref[first + i] % r,
                                     rows_ref[i], tiles[i])
                tile(i, True).start()
                return c
            jax.lax.fori_loop(0, g, lay, 0)

            def done(i, c):
                tile(i, True).wait()
                return c
            jax.lax.fori_loop(0, g, done, 0)

        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(b // g,),
                in_specs=[pl.BlockSpec((g, h, 1, d),
                                       lambda j, *_: (j, 0, 0, 0)),
                          pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec(memory_space=pl.ANY),
                scratch_shapes=[pltpu.VMEM((g, h, r, d), plane.dtype),
                                pltpu.SemaphoreType.DMA((g,))]),
            out_shape=jax.ShapeDtypeStruct(plane.shape, plane.dtype),
            # the plane is operand 3 (the two prefetched arrays come
            # first) and the result: updated where it lies
            input_output_aliases={3: 0},
            interpret=_fa._INTERPRET,
            name='paged_row_write',
        )(table, pos, rows, plane)

    # heads over 'mp' as the pool lies; the sequences stay whole on every
    # device (a plane is whole along 'dp': each copy takes every row)
    heads = (None, 'heads', None, None)
    return mesh_kernel.sharded_call(
        core,
        (page_table.astype(jnp.int32).reshape(-1), pos.astype(jnp.int32),
         jnp.moveaxis(rows, 1, 2).astype(plane.dtype), plane),
        (None, None, heads, heads), heads, batch=b, heads=(plane.shape[1],))


def paged_write(pages, rows, page_table, pos, valid=None):
    """Write new KV rows into a page plane, in place where the caller's
    buffer allows it (a donated pool carried through the layers' loop).

    ``pages``: [N, H, page_size, D] (or an int8 bank of that shape), or a
    plane with no heads axis, [N, page_size, W]; N may span every layer's
    pages when ``page_table`` is offset to the layer's
    (models/gpt.paged_forward_with_cache, models/latent_moe._attention);
    ``rows``: [B, T, H, D] (or [B, T, W]) fresh rows for absolute positions
    ``pos[b] + j``; ``page_table``: [B, P_max]; ``valid``: [B] or None
    (rows past it are padding and reach no page of a sequence). Returns
    the updated plane.

    One algorithm, two forms, chosen by what the call shows
    (``_row_write_available``): a decode step's ONE row a sequence lies in
    one sublane tile of one page, and the row kernel moves that tile
    alone; a prefill's rows span whole pages and go a page at a time.

    int8 banks quantize the incoming rows with the same per-row scheme as
    the dense int8 cache (ops/weight_only.quantize_kv), so paged int8
    decode matches dense int8 decode row-for-row."""
    if is_weight_only(pages):
        q, scale = quantize_kv(rows)
        return {'int8': _write_pages(pages['int8'], q, page_table, pos,
                                     valid),
                'scale': _write_pages(pages['scale'], scale, page_table,
                                      pos, valid)}
    if _row_write_available(pages, rows, valid):
        return _row_write(pages, rows, page_table, pos)
    if rows.ndim == 4:
        return _write_pages(pages, rows, page_table, pos, valid)
    # a plane without a heads axis (a latent cache's): a row is whole, one
    # [W] piece of the plane flattened to rows, and is scattered as such
    b, t = rows.shape[:2]
    n, ps = pages.shape[:2]
    row = pages.shape[2:]
    idx = flat_write_indices(page_table, pos, t, ps, valid).reshape(-1)
    flat = pages.reshape((n * ps,) + row)
    flat = flat.at[idx].set(rows.reshape((b * t,) + row).astype(pages.dtype))
    return flat.reshape(pages.shape)


def copy_page(pool, src, dst):
    """Copy-on-write primitive: duplicate physical page ``src`` into
    ``dst`` across every pool plane (k and v, all layers; int8 banks copy
    both the int8 and scale planes). ``pool`` is the engine's full paged
    cache pytree ``{'k': [L, N, H, ps, D], 'v': ...}``.

    Compiled ONCE per pool signature (src/dst are traced scalars) and the
    input pool is donated, so a divergence mid-page costs one tiny
    executable reused forever — never a retrace per COW, which is what
    keeps "zero new compiles on cache hits" true for the prefix cache."""
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    return _copy_page_jit(pool, src, dst)


def _copy_page_impl(pool, src, dst):
    def one(arr):
        # every pool plane is page-indexed on axis 1 ([L, N, ...])
        row = jax.lax.dynamic_index_in_dim(arr, src, axis=1, keepdims=True)
        return jax.lax.dynamic_update_slice_in_dim(arr, row, dst, axis=1)
    return jax.tree_util.tree_map(one, pool)


_copy_page_jit = jax.jit(_copy_page_impl, donate_argnums=(0,))


def gather_virtual(pages, page_table):
    """Reconstruct each slot's virtual dense cache from its pages:
    ``[N, H, page_size, D]`` + ``[B, P_max]`` -> ``[B, P_max*page_size,
    H, D]`` (a bank's scales ``[N, H, page_size]`` -> ``[B, P_max*
    page_size, H]``). int8 banks gather both planes. This is the pure-jnp
    fallback the paged-attention path (and CPU tier-1 tests) build on: the
    result is value-identical to the dense cache regardless of physical
    page placement, which is what makes paged-vs-dense greedy bit-parity a
    testable property."""
    if is_weight_only(pages):
        return {'int8': gather_virtual(pages['int8'], page_table),
                'scale': gather_virtual(pages['scale'], page_table)}
    g = jnp.take(pages, page_table, axis=0)       # [B, P_max, H, ps, ...]
    g = jnp.moveaxis(g, 2, 3)                     # rows before heads
    b, p_max, ps = g.shape[:3]
    return g.reshape((b, p_max * ps) + g.shape[3:])
