"""A routed expert layer that is told which experts it holds.

Expert parallelism gives each chip some of a layer's experts. The router
keeps its whole width and its experts a token; the chip computes, for the
rows routed to the experts it holds, their weighted part of the result, and
what the other experts would add is the other chips' (on one chip: left
out). ``parallel/moe.py``'s ``top2_gating`` is another layer: two experts a
token, a capacity that drops rows, one-hot dispatch.

WHO chooses is a family's own: a router is the family's code, and the layer
proper starts from its answer, ``(chosen [T, k], weights [T, k])``.

    route          ONE family of routers (``latent_moe``, ``afmoe``):
                   sigmoid scores over all experts, a correction bias that
                   moves the CHOICE and not the weights, group-limited
                   top-k ('noaux_tc'), weights normalised over all chosen.
                   A family with another router (``zaya``: a softmax over
                   an MLP's outputs, one expert a token) calls none of it
    plan           which chosen experts are held: rows sorted by expert
                   into tiles of ``tm`` rows, each group padded to a tile
    held_experts   the layer from a router's answer: the grouped product
                   over the held experts only
                   (ops/expert_grouped_matmul.py), SwiGLU, the weighted
                   combine, and a shared expert where the layer's weights
                   have one
    routed_experts ``route`` and then ``held_experts``: the whole layer of
                   the families whose router ``route`` is

A row may meet NO expert: a choice outside the held range (another chip's
expert, or an index past the router's experts that a family uses for "skip
the experts") is sorted nowhere, costs no row of the product and adds
nothing; what such a row gets instead is the family's.

No capacity, no dropped row: the sorted array is sized for the worst case
(every choice of every row held here) and the kernel skips what is not in
use. The exchange between chips is not here; nothing stands in for it.
"""
import jax
import jax.numpy as jnp

from ..ops.expert_grouped_matmul import expert_grouped_matmul

COUNTS = ('rows_offered', 'rows_held', 'expert_calls', 'experts_touched',
          'group_rows_max')


def route(h, router, bias, *, top_k, n_group, topk_group, scale,
          normalise=True):
    """h [T, H] -> (chosen [T, top_k] i32, weights [T, top_k] f32). The
    router runs in float32 whatever the layer computes in."""
    logits = jnp.dot(h.astype(jnp.float32), router.astype(jnp.float32).T,
                     precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits)
    biased = s + bias.astype(jnp.float32)
    t, e = biased.shape
    by_group = biased.reshape(t, n_group, e // n_group)
    group_score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
    kept = jax.lax.top_k(group_score, topk_group)[1]             # [T, kept]
    keep = jnp.any(kept[:, :, None] == jnp.arange(n_group), axis=1)
    masked = jnp.where(keep[:, :, None], by_group, -jnp.inf).reshape(t, e)
    chosen = jax.lax.top_k(masked, top_k)[1].astype(jnp.int32)
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if normalise:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * scale


def tile_rows(n_choices):
    """Rows a tile: a decode step offers an expert a handful of rows, a
    prefill hundreds (bf16 packs 16 rows a register)."""
    return 16 if n_choices <= 1024 else 128


def plan(chosen, row_ok, held, tm):
    """Sort the choices that meet a held expert into tiles.

    chosen [T, k] i32; row_ok [T] bool (False: a padding row, routed
    nowhere); held (first, count). -> dict:
      dest [T, k] i32   each choice's row in the sorted array (M: not held)
      src [M] i32       the token each sorted row reads (padding reads 0)
      tile_expert [M // tm] i32, n_tiles [] i32
      is_held [T, k] bool, group_sizes [count] i32
    M = ceil(T k / tm) tm + count tm: every choice held, every group with
    a tile's padding."""
    first, count = held
    t, k = chosen.shape
    r = t * k
    m = -(-r // tm) * tm + count * tm
    local = chosen - first
    is_held = (local >= 0) & (local < count) & row_ok[:, None]
    e = jnp.where(is_held, local, count).reshape(r)
    onehot = (e[:, None] == jnp.arange(count)[None, :]).astype(jnp.int32)
    sizes = jnp.sum(onehot, axis=0)                              # [count]
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=1)
    tiles = -(-sizes // tm)
    tile_end = jnp.cumsum(tiles)
    row_start = (tile_end - tiles) * tm
    dest = jnp.where(e < count,
                     jnp.take(row_start, jnp.minimum(e, count - 1)) + rank,
                     m)
    token = jnp.arange(r, dtype=jnp.int32) // k
    src = jnp.zeros((m,), jnp.int32).at[dest].set(token, mode='drop')
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(m // tm), side='right'),
        count - 1).astype(jnp.int32)
    return {'dest': dest.reshape(t, k), 'src': src,
            'tile_expert': tile_expert, 'n_tiles': tile_end[-1],
            'is_held': is_held, 'group_sizes': sizes}


def swiglu(p, h, cdt):
    dot = lambda a, b: jnp.dot(a, b.astype(cdt),
                               preferred_element_type=jnp.float32)
    g, u = dot(h, p['gate']), dot(h, p['up'])
    return dot((jax.nn.silu(g) * u).astype(cdt), p['down']).astype(cdt)


def routed_experts(lp, h, row_ok, *, held, top_k, n_group, topk_group,
                   scale, normalise=True):
    """The whole layer of a family whose router is ``route``: lp 'router'
    [E_all, H], 'router_bias' [E_all] beside what ``held_experts`` takes.
    -> (y [T, H], counts [5] i32 in the order of ``COUNTS``)."""
    with jax.named_scope('router'):
        chosen, w = route(h, lp['router'], lp['router_bias'], top_k=top_k,
                          n_group=n_group, topk_group=topk_group,
                          scale=scale, normalise=normalise)
    return held_experts(lp, h, row_ok, chosen, w, held=held)


def held_experts(lp, h, row_ok, chosen, w, *, held, at=None):
    """The held experts' part of the layer for a router's answer, and the
    shared expert's where there is one.

    lp: 'experts' {'gate', 'up' [count, H, F], 'down' [count, F, H]} and,
    optionally, 'shared' {'gate', 'up', 'down'}; h [T, H] in the compute
    dtype; row_ok [T] bool; chosen [T, k] i32 and w [T, k] f32 from the
    family's router. ``at``: the experts' leaves are a STACK of layers',
    ``[layers * count, ...]``, and this layer's lie from ``at * count`` on
    (a stack scanned over its layers hands the kernel the whole stack and
    an offset, never a layer's slice: that would be a copy of it).
    -> (y [T, H], counts [5] i32 in the order of ``COUNTS``)."""
    cdt = h.dtype
    t, top_k = chosen.shape
    tm = tile_rows(t * top_k)
    with jax.named_scope('dispatch'):
        pl_ = plan(chosen, row_ok, held, tm)
        rows = jnp.take(h, pl_['src'], axis=0)                   # [M, H]
    with jax.named_scope('experts'):
        tile_expert = pl_['tile_expert']
        if at is not None:
            tile_expert = tile_expert + jnp.int32(held[1]) * at
        gmm = lambda x, wt: expert_grouped_matmul(
            x, wt.astype(cdt), tile_expert, pl_['n_tiles'], tm=tm)
        ex = lp['experts']
        act = (jax.nn.silu(gmm(rows, ex['gate']).astype(jnp.float32))
               * gmm(rows, ex['up']).astype(jnp.float32)).astype(cdt)
        out = gmm(act, ex['down'])                               # [M, H]
    y = None
    if 'shared' in lp:
        with jax.named_scope('shared'):
            y = swiglu(lp['shared'], h, cdt)
    with jax.named_scope('combine'):
        m = out.shape[0]
        picked = jnp.take(out, jnp.minimum(pl_['dest'], m - 1), axis=0)
        w_held = jnp.where(pl_['is_held'], w, 0.0).astype(cdt)   # [T, k]
        routed = jnp.einsum('tk,tkh->th', w_held, picked,
                            preferred_element_type=jnp.float32).astype(cdt)
        y = routed if y is None else y + routed
    sizes = pl_['group_sizes']
    counts = jnp.stack([
        jnp.sum(row_ok.astype(jnp.int32)) * top_k,
        jnp.sum(sizes), jnp.int32(held[1]),
        jnp.sum((sizes > 0).astype(jnp.int32)), jnp.max(sizes)])
    return y, counts.astype(jnp.int32)
