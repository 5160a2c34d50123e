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
   updates the carried buffer in place.
 - int8-KV pools reuse the ``{'int8', 'scale'}`` bank layout of
   ops/weight_only (per-row scales), so the +32% int8 decode win composes.
"""
import threading

import jax
import jax.numpy as jnp

from .weight_only import init_kv_bank, is_weight_only, quantize_kv

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

    int8 banks quantize the incoming rows with the same per-row scheme as
    the dense int8 cache (ops/weight_only.quantize_kv), so paged int8
    decode matches dense int8 decode row-for-row."""
    if is_weight_only(pages):
        q, scale = quantize_kv(rows)
        return {'int8': _write_pages(pages['int8'], q, page_table, pos,
                                     valid),
                'scale': _write_pages(pages['scale'], scale, page_table,
                                      pos, valid)}
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
