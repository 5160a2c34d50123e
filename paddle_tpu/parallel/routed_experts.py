"""A routed expert layer that is told which experts it holds.

Expert parallelism gives each chip some of a layer's experts. The router
keeps its whole width and its experts a token; the chip computes, for the
rows routed to the experts it holds, their weighted part of the result, and
what the other experts would add is the other chips' (on one chip: left
out). ``parallel/moe.py``'s ``top2_gating`` is another layer: two experts a
token, a capacity that drops rows, one-hot dispatch.

    route          sigmoid scores over all experts, a correction bias that
                   moves the CHOICE and not the weights, group-limited
                   top-k ('noaux_tc'), weights normalised over all chosen
    plan           which chosen experts are held: rows sorted by expert
                   into tiles of ``tm`` rows, each group padded to a tile
    routed_experts the grouped product over the held experts only
                   (ops/expert_grouped_matmul.py), SwiGLU, combine

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
    """The held experts' part of the layer and the shared expert's.

    lp: 'router' [E_all, H], 'router_bias' [E_all], 'experts' {'gate',
    'up' [count, H, F], 'down' [count, F, H]}, 'shared' {'gate', 'up',
    'down'}; h [T, H] in the compute dtype; row_ok [T] bool.
    -> (y [T, H], counts [5] i32 in the order of ``COUNTS``)."""
    cdt = h.dtype
    t = h.shape[0]
    with jax.named_scope('router'):
        chosen, w = route(h, lp['router'], lp['router_bias'], top_k=top_k,
                          n_group=n_group, topk_group=topk_group,
                          scale=scale, normalise=normalise)
    tm = tile_rows(t * top_k)
    with jax.named_scope('dispatch'):
        pl_ = plan(chosen, row_ok, held, tm)
        rows = jnp.take(h, pl_['src'], axis=0)                   # [M, H]
    with jax.named_scope('experts'):
        gmm = lambda x, wt: expert_grouped_matmul(
            x, wt.astype(cdt), pl_['tile_expert'], pl_['n_tiles'], tm=tm)
        ex = lp['experts']
        act = (jax.nn.silu(gmm(rows, ex['gate']).astype(jnp.float32))
               * gmm(rows, ex['up']).astype(jnp.float32)).astype(cdt)
        out = gmm(act, ex['down'])                               # [M, H]
    with jax.named_scope('shared'):
        y = swiglu(lp['shared'], h, cdt)
    with jax.named_scope('combine'):
        m = out.shape[0]
        picked = jnp.take(out, jnp.minimum(pl_['dest'], m - 1), axis=0)
        w_held = jnp.where(pl_['is_held'], w, 0.0).astype(cdt)   # [T, k]
        y = y + jnp.einsum('tk,tkh->th', w_held, picked,
                           preferred_element_type=jnp.float32).astype(cdt)
    sizes = pl_['group_sizes']
    counts = jnp.stack([
        jnp.sum(row_ok.astype(jnp.int32)) * top_k,
        jnp.sum(sizes), jnp.int32(held[1]),
        jnp.sum((sizes > 0).astype(jnp.int32)), jnp.max(sizes)])
    return y, counts.astype(jnp.int32)
