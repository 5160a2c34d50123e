"""What the serving engine needs of a model family, and nothing else.

``serving.GenerationEngine`` schedules slots and pages; what a page holds
and how a forward pass reads it is the family's. A family is the pair
(pool maker, cached forward) with what placement and telemetry need beside
it, registered for its config class:

    init_pool(config, num_pages, page_size)
        -> {plane name: array or int8 bank}; every plane's axis 1 is pages
           (``ops/paged_kv.copy_page`` copies a page across all of them) and
           page 0 is the trash page (a ``per_slot`` kind's: slots, below)
    forward_with_cache(params, tokens, cache, pos, config, last_only=False,
                       partitioner=None) -> (logits, cache)
        ``cache`` is the pool's planes beside ``page_table`` [B, P_max],
        ``valid`` [B] (prefill) and the static ``tail`` flag; the cache that
        comes back holds the planes updated, and may hold ``counts``: a
        small int32 array that rides in the step's one host read and is
        handed to ``note_counts(counts, phase)``

A family whose layers do not all keep their rows alike names its KINDS of
plane (``page_kinds``): full-attention layers, whose pages a slot keeps for
its whole life, beside window layers, which read only a slot's last
``window`` rows. The engine then keeps an allocator and a page table a
kind, gives back every page of a window kind that has left the window,
calls ``init_pool(config, {kind: pages}, page_size)`` and hands the forward
``page_table`` as ``{kind: [B, P_max]}``. A table's entries before a slot's
window read 0 (the trash page): the family writes no row there that it
needs and reads none. A family that names no kinds has one, and nothing
about it changes.

A kind may also be a row a SLOT and not pages (``per_slot``): the
recurrent state of a layer that keeps no rows at all, the same size
whatever the sequence's length. Its planes' axis 1 is the engine's slots
(``init_pool`` is handed ``{kind: num_slots}`` for it), it has no
allocator, no table and nothing to release, a prefix cache cannot index it
and ``copy_page`` is never offered it. In place of a table the forward
gets, under the kind's name in ``page_table``, WHICH slots the call's
sequences are: ``[B]`` int32 (a prefill serves one slot, the step all of
them). A prefill starts from row 0 and overwrites the slot's row: what the
last occupant left there is never read.

A family may name per-slot kinds ONLY (a stack with no attention layer at
all: ``models/brumby.py``). The engine then holds no page, no allocator and
no table: ``init_pool`` is handed ``{kind: num_slots}`` and nothing else,
``forward_with_cache`` a ``page_table`` of ``{kind: [B] slots}`` alone,
admission needs a free slot and a prompt within ``prefill_width``, the
context is bounded by ``max_seq_len`` positions, ``page_size`` is the
granule of ``prefill_widths`` and nothing else, and a prefix cache is
refused as for any family that prefills from row 0.

The engine never asks what kind of model it serves: it looks the family up
by the config's class (``family_of``).

How WIDE a prefill runs is the engine's choice, not a family's: a cached
forward runs its layers over the ``[B, T]`` it is given, rows past ``valid``
being padding at any ``T``, and the engine pads a prompt to the narrowest
of ``prefill_widths`` that holds it (one executable a width, all built by
``engine.warmup()``). The same rows give the same numbers at every width
in a family whose rows do not meet outside attention; ``moe_gpt``'s rows
compete for expert capacity, which follows the rows of the call, as they
do in its decode step.
"""
import dataclasses
import typing

import jax
import jax.numpy as jnp

from ..ops.paged_kv import POOL_LOGICAL_AXES


@dataclasses.dataclass(frozen=True)
class PageKind:
    """One kind of pool plane. ``window``: the rows behind its position
    (itself among them) that a slot's layers of this kind still read;
    None: all of them. ``per_slot``: a row a slot, not pages. ``planes``:
    the pool's planes of this kind by name, from which the engine counts a
    page's (a slot's row's) bytes; a family of ONE paged kind may leave
    them unnamed: every plane no other kind names is its."""
    name: str
    window: typing.Optional[int] = None
    per_slot: bool = False
    planes: tuple = ()


ONE_KIND = (PageKind('kv'),)    # a family that names none

MAX_BODY_STEP = 4096    # rows between two neighbouring prefill widths


def prefill_widths(prefill_width, page_size, pages=1):
    """The widths a prompt of up to ``prefill_width`` rows may be padded
    to, ascending, the last ``prefill_width`` itself: the powers of two and
    the midpoints between them, never more than ``MAX_BODY_STEP`` rows
    apart (past 16,384 that is every 4,096), those that are whole pages
    (``pages`` of them at a time: ``GenerationFamily.prefill_pages``) and
    no narrower than an eighth of ``prefill_width``. So a prompt computes
    at most half as many rows again as it has (a page more where that is
    less than a page, and the floor's rows for the shortest), and a long
    one at most 4,095 more. 16,384 rows in pages of 128: 2048, 3072, 4096,
    6144, 8192, 12288, 16384; 1,024: 128, 256, 384, 512, 768, 1024, and
    two pages at a time 256, 512, 768, 1024.

    Every width is an executable that every process traces, lowers and
    loads before traffic, ~3 s each for a five-layer routed model on the
    chip's host whatever the compile cache holds (PERF.md, PR 39): the
    floor and the step are where one more width stopped paying for its
    share of a served cell's set-up."""
    top, page = int(prefill_width), int(page_size) * int(pages)
    rungs = sorted({r for k in range(top.bit_length() + 1)
                    for r in (1 << k, 3 << k >> 1)})
    steps = [w for lo, hi in zip(rungs, rungs[1:])
             for w in range(lo, hi, MAX_BODY_STEP)]
    return tuple(w for w in steps
                 if w % page == 0 and top <= 8 * w and w < top) + (top,)


def stack_layers(n, layer_of):
    """``layer_of(l)`` -> layer ``l``'s leaves, ``n`` layers -> the stack as
    a scan over layers takes it: every leaf ``[n, ...]``. A layer is made,
    written into the stacks where they lie and dropped before the next: two
    copies of the whole never stand side by side."""
    put = jax.jit(lambda stack, leaf, l: jax.lax.dynamic_update_index_in_dim(
        stack, leaf.astype(stack.dtype), l, 0), donate_argnums=0)
    stacks = None
    for l in range(n):
        lp = layer_of(l)
        if stacks is None:
            stacks = jax.tree_util.tree_map(
                lambda a: jnp.zeros((n,) + a.shape, a.dtype), lp)
        stacks = jax.tree_util.tree_map(
            lambda s, a: put(s, a, jnp.int32(l)), stacks, lp)
        del lp
    return stacks


@dataclasses.dataclass(frozen=True)
class GenerationFamily:
    name: str
    init_pool: typing.Callable
    forward_with_cache: typing.Callable
    # params' logical axes for a mesh (parallel/mesh_engine.py); None: the
    # family has no rules table and serves on one chip
    logical_axes: typing.Any = None
    # one pool plane's logical axes; a bank's scale plane drops the last
    pool_logical_axes: tuple = POOL_LOGICAL_AXES
    # float params -> the int8 weight-only snapshot (precision='int8_wo')
    quantize_decode_params: typing.Optional[typing.Callable] = None
    # (params, config) -> params as an engine holds them: every leaf that
    # the cached forward only ever reads as ``.astype(config.dtype)``, the
    # right-hand operand of a product, already in that dtype (a weight-only
    # leaf and a leaf already in it are handed back as they are). The
    # engine calls it once, so no call of its executables makes the cast
    # again. None: the engine holds the parameters as given
    serve_params: typing.Optional[typing.Callable] = None
    # (counts: np.ndarray, phase: 'prefill' | 'decode') -> None
    note_counts: typing.Optional[typing.Callable] = None
    # a prefill may start past row 0 and read the rows before it out of the
    # pool (what a prefix cache's hit needs); False: the engine refuses the
    # family a prefix cache
    tail_prefill: bool = True
    # config -> (PageKind, ...); None: one kind, pool and table as above
    page_kinds: typing.Optional[typing.Callable] = None
    # a prefill width is a whole number of this many pages
    # (``prefill_widths``). Every width is an executable that every process
    # traces, lowers and loads before traffic, whatever the compile cache
    # holds; a family whose prefill is cheap beside that names more than
    # one page and has fewer
    prefill_pages: int = 1


_FAMILIES = {}


def register(config_cls, family):
    _FAMILIES[config_cls] = family
    return family


def family_of(config):
    """The family registered for ``config``'s class (or a base of it)."""
    for cls in type(config).__mro__:
        if cls in _FAMILIES:
            return _FAMILIES[cls]
    raise TypeError(
        f'no generation family is registered for {type(config).__name__}: '
        f'models/family.register(<config class>, GenerationFamily(...))')
