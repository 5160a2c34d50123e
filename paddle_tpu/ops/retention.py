"""Power retention of degree 2 (Buckman, Gelada, Zhang: "Scaling Context
Requires Rethinking Attention", arXiv:2507.04239), twice.

For a KV head with keys ``k_t`` and values ``v_t`` in R^d, a decay
``g_t = exp(l_t)`` in (0, 1] a token, and the query heads ``a`` of its group:

    attention form   a_ts = (q_t . k_s)^2 exp(L_t - L_s),  L_t = sum_{s<=t} l_s
                     y_t  = sum_{s<=t} a_ts v_s / (sum_{s<=t} a_ts + eps)
    recurrent form   S_t = g_t S_{t-1} + phi(k_t) v_t^T     S in R^{D x d}
                     z_t = g_t z_{t-1} + phi(k_t)           z in R^D
                     y_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + eps)

with ``phi`` the symmetric second power: ``x_i x_j`` for ``i <= j``, times
``sqrt(2)`` where ``i < j``, ``D = d (d + 1) / 2`` features (8,256 at
``d = 128``), so that ``phi(q) . phi(k) = (q . k)^2`` exactly. The two forms
are one function; nothing is approximated.

 - ``phi``: the features BY DIAGONAL, ``d`` to a tile: tile ``o`` holds
   ``x_i x_{(i + o) mod d}``. Tile 0 is the squares; tiles ``1 .. d/2 - 1``
   hold the pairs ``o`` and ``d - o`` apart, each once; tile ``d/2`` holds
   the pairs ``d/2`` apart in its first half and zeros in its second. So a
   feature vector is ``d/2 + 1`` tiles of ``d`` along one axis: whole lane
   tiles at ``d = 128``, ``D`` features and ``d/2`` zeros (``Dp = D +
   d/2``: 8,320, 0.8 % more than 8,256), made by ``d/2`` lane rotations and
   no gather.
 - ``chunked_retention``: the recurrence over a whole sequence (a prefill)
   in chunks of at most ``CHUNK`` rows: inside a chunk the attention form
   with the decay mask, between chunks the state (what a chunk's rows take
   from the rows before it is ``phi(q_t)^T S`` of the state at the chunk's
   start). The first chunk starts from nothing and reads no state.
   A row whose ``l`` is 0 and whose ``k`` is 0 leaves the state exactly as
   it was, so a padded prompt hands back the state after its last real row.
 - ``state_update``: ONE token for many sequences (a decode step) whose
   states lie in a pool of rows carried whole and updated in place: the
   Pallas call ``retention_state_update`` reads each (sequence, KV head)'s
   state once, writes it once and serves every query head of the group
   from it (the outputs are the inputs' buffers; rows the call does not
   name keep what they hold), or the same in ``jax.numpy`` where the kernel
   does not run.

**The pool's layout.** A KV head's state is held TRANSPOSED, the feature
axis on the chip's lanes: ``s[r, h, j, o d + i] = S[(i, i + o), j]``,
``[R, kv heads, d, Dp]`` float32 (4.26 MB a head at ``d = 128``), and
``z[r, h, o d + i]``, ``[R, kv heads, Dp]``. A step's ``phi(k)`` and
``phi(q)`` are then rows along the lanes as ``phi`` makes them, the value
``v_j`` is one number a sublane, and the read-out is a sum along the lanes:
the prefill's products give ``[d, Dp]`` as it is stored (``v^T phi(k)``)
and read it as the right-hand side of a product contracted over both last
axes, so nothing is transposed on the way in or out.
"""
import math

import jax
import jax.numpy as jnp

# the module, not the function ``ops/__init__`` rebinds the name to
# (ops/paged_attention.py says why); ``_fa._INTERPRET`` stays late-bound
import importlib
_fa = importlib.import_module('paddle_tpu.ops.flash_attention')
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
EPS = 1e-6
# what the kernel may hold in fast memory: a head's state read and written,
# each double buffered (17 MB at d = 128), the step's rows and their copies
_VMEM_LIMIT = 40 * 2 ** 20
# the most rows of a prefill's chunk: inside it the attention form, between
# chunks the state. A row pays 2 x 2 x 128 operations a row before it inside
# a chunk and 2 x 8,256 x 129 to read the state, so a chunk pays for itself
# up to ~4,100 rows; 1,024 is what a chunk's scores, [kv heads, G, CHUNK,
# CHUNK] (168 MB in float32 at 40 heads), leave of the chip beside the pool
CHUNK = 1024


def features(d):
    """(D, Dp): the features of a head of ``d`` and what ``phi`` lays them
    in (``d/2`` zeros close the last tile)."""
    return d * (d + 1) // 2, (d // 2 + 1) * d


def phi(x):
    """[..., d] -> [..., Dp]: the symmetric second power by diagonal, tile
    after tile along one axis (the module's text). ``d`` even."""
    d = x.shape[-1]
    half = d // 2
    first = jnp.arange(d) < half
    tiles = [x * x]
    for o in range(1, half + 1):
        pair = math.sqrt(2.0) * x * jnp.roll(x, -o, axis=-1)
        tiles.append(pair if o < half else jnp.where(first, pair, 0.0))
    return jnp.stack(tiles, axis=-2).reshape(x.shape[:-1] + (-1,))


# ---- a whole sequence: the chunked form ------------------------------------

def chunked_retention(q, k, v, l, cdt, chunk):
    """The recurrence over T rows from a zero state.

    q [B, T, kv heads, G, d] (a KV head's G query heads), k, v [B, T, kv
    heads, d], float32; l [B, T, kv heads] float32, a row's log decay
    (<= 0). A padded row has ``l = 0`` and ``k = 0``. T a multiple of
    ``chunk``. Products take operands in ``cdt`` and accumulate in float32;
    the decays, their sums, the normaliser and the state are float32.
    -> (y [B, T, kv heads, G, d] float32, the state after the last row as
    the pool holds it: s [B, kv heads, d, Dp], z [B, kv heads, Dp])."""
    b, t, h, g, d = q.shape
    c = int(chunk)
    n = t // c
    f32 = jnp.float32

    def dot(spec, x, y):
        return jnp.einsum(spec, x.astype(cdt), y.astype(cdt),
                          preferred_element_type=f32)
    rows = jnp.arange(c)
    seen = rows[:, None] >= rows[None, :]

    def inside(qc, kc, vc, lc):
        """A chunk's rows among themselves, and what they add to the state
        at its end as the pool holds it."""
        cs = jnp.cumsum(lc, axis=1)             # [B, C, H]: L_t - L_start
        # row t takes row s <= t by (q_t.k_s)^2 and the decay between them
        score = dot('bthad,bshd->bhats', qc, kc)            # [B,H,G,C,C]
        csh = jnp.moveaxis(cs, 1, 2)                        # [B, H, C]
        decay = jnp.exp(jnp.where(seen, csh[..., :, None] - csh[..., None, :],
                                  -jnp.inf))                # [B, H, C, C]
        a = score * score * decay[:, :, None]
        num = dot('bhats,bshj->bthaj', a, vc)
        den = jnp.moveaxis(jnp.sum(a, axis=-1), 3, 1)       # [B, C, H, G]
        to_end = jnp.exp(cs[:, -1:] - cs)                   # [B, C, H]
        pk = phi(kc) * to_end[..., None]                    # [B, C, H, Dp]
        return (cs, num, den, dot('bshj,bshd->bhjd', vc, pk),
                jnp.sum(pk, axis=1))

    def later_chunk(state, rows_of):
        s, z = state
        cs, num, den, s_own, z_own = inside(*rows_of)
        # from the rows before the chunk: the state at its start, one
        # query head of every group after another (phi of all of a chunk's
        # queries at once is 1.4 GB at 1,024 rows of 40 heads)
        def of_state(qa):                                   # [B, C, H, d]
            pq = phi(qa)                                    # [B, C, H, Dp]
            return (dot('bthd,bhjd->bthj', pq, s),
                    dot('bthd,bhd->bth', pq, z))
        num_s, den_s = jax.lax.map(of_state, jnp.moveaxis(rows_of[0], 3, 0))
        from_start = jnp.exp(cs)[..., None]                 # [B, C, H, 1]
        num = num + from_start[..., None] * jnp.moveaxis(num_s, 0, 3)
        den = den + from_start * jnp.moveaxis(den_s, 0, 3)
        whole = jnp.exp(cs[:, -1])                          # [B, H]
        return ((whole[..., None, None] * s + s_own,
                 whole[..., None] * z + z_own), num / (den[..., None] + EPS))

    chunks = [jnp.moveaxis(x.reshape((b, n, c) + x.shape[2:]), 1, 0)
              for x in (q, k, v, l)]
    # the first chunk starts from nothing: it reads no state and makes no
    # phi(q) (a prompt of one chunk is the attention form and the state it
    # leaves)
    _, num, den, s, z = inside(*(x[0] for x in chunks))
    y = (num / (den[..., None] + EPS))[None]
    if n > 1:
        (s, z), later = jax.lax.scan(later_chunk, (s, z),
                                     tuple(x[1:] for x in chunks))
        y = jnp.concatenate([y, later])
    return jnp.moveaxis(y, 0, 1).reshape(b, t, h, g, d), s, z


# ---- one token for many sequences: the pool's rows in place ----------------

def state_update_available(s):
    """The kernel's gate: a float32 pool ``[R, kv heads, 128, Dp]`` on the
    chip (or interpreted: ops/flash_attention.set_interpret)."""
    return (_fa._platform_ok() and s.ndim == 4 and s.dtype == jnp.float32
            and s.shape[2] == LANES and s.shape[3] % LANES == 0)


def _lane_sums(x):
    """[n, 128] float32 -> [8, n]: every row the sums along x's lanes, laid
    along the lanes (a product with ones on the matrix unit in three
    bfloat16 parts of x, which hold all of a float32's mantissa: nothing
    else moves a sublane's number onto a lane)."""
    ones = jnp.ones((SUBLANES, LANES), jnp.bfloat16)
    out = jnp.zeros((SUBLANES, x.shape[0]), jnp.float32)
    for _ in range(3):
        part = x.astype(jnp.bfloat16)
        out = out + jax.lax.dot_general(
            ones, part, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        x = x - part.astype(jnp.float32)
    return out


def _state_update_kernel(rows_ref, g_ref, vb_ref, pk_ref, pq_ref, s_ref,
                         z_ref, num_ref, den_ref, so_ref, zo_ref, pkb_ref,
                         pqb_ref, acc_ref):
    """Grid (sequences, KV heads). A step holds one head's state ``[d,
    Dp]``, read once and written once, a tile ``[8, 128]`` at a time: eight
    values' sublanes by 128 features' lanes. ``phi(k)`` and the group's
    ``phi(q)`` are laid over eight sublanes once a step (``pkb``, ``pqb``),
    a value's tile is its number along the lanes as it came (``vb``), the
    decay is a scalar out of scalar memory. ``z`` of all the sequence's
    heads is one block that stays while the heads go by."""
    del rows_ref            # the index maps' (which row of the pool)
    i, h = pl.program_id(0), pl.program_id(1)
    g = g_ref[i, h]
    groups, dp = pq_ref.shape[2:]
    d = s_ref.shape[2]
    tiles = dp // LANES
    tile = lambda o: slice(o * LANES, (o + 1) * LANES)

    pk = pk_ref[0, pl.ds(h, 1), :]                          # [1, Dp]
    pq = pq_ref[0, 0]                                       # [G, Dp]
    z = g * z_ref[0, pl.ds(h, 1), :] + pk
    zo_ref[0, pl.ds(h, 1), :] = z
    zq = pq * z
    den = zq[:, tile(0)]
    for o in range(1, tiles):
        den = den + zq[:, tile(o)]
    den_ref[0, 0] = den         # a lane's share; the caller sums the lanes

    pkb_ref[...] = jnp.broadcast_to(pk, (SUBLANES, dp))
    for a in range(groups):
        pqb_ref[a] = jnp.broadcast_to(pq[a:a + 1], (SUBLANES, dp))

    def values(jt, carry):
        r = pl.multiple_of(jt * SUBLANES, SUBLANES)
        at = pl.ds(r, SUBLANES)
        vt = vb_ref[0, 0, at, :]
        accs = [jnp.zeros((SUBLANES, LANES), jnp.float32)] * groups
        for o in range(tiles):
            s = s_ref[0, 0, at, tile(o)] * g + pkb_ref[:, tile(o)] * vt
            so_ref[0, 0, at, tile(o)] = s
            accs = [acc + pqb_ref[a, :, tile(o)] * s
                    for a, acc in enumerate(accs)]
        for a in range(groups):
            acc_ref[a, at, :] = accs[a]
        return carry
    jax.lax.fori_loop(0, d // SUBLANES, values, 0)
    for a in range(groups):
        num_ref[0, 0, a:a + 1, :] = _lane_sums(acc_ref[a])[:1]


def _state_update_call(s, z, rows, g, pk, pq, v):
    _, h, d, dp = s.shape
    bsz, groups = pq.shape[0], pq.shape[2]
    f32 = jnp.float32
    # a value's number along the lanes: [B, H, d, 128]
    vb = jnp.broadcast_to(v[..., None], v.shape + (LANES,))
    head = lambda i, j, *_: (i, j, 0, 0)      # a (sequence, KV head)'s
    state = pl.BlockSpec((1, 1, d, dp), lambda i, j, rows, *_: (rows[i], j, 0, 0))
    norm = pl.BlockSpec((1, h, dp), lambda i, j, rows, *_: (rows[i], 0, 0))
    num, den, s, z = pl.pallas_call(
        _state_update_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(bsz, h),
            in_specs=[pl.BlockSpec((1, 1, d, LANES), head),
                      pl.BlockSpec((1, h, dp), lambda i, j, *_: (i, 0, 0)),
                      pl.BlockSpec((1, 1, groups, dp), head),
                      state, norm],
            out_specs=[pl.BlockSpec((1, 1, groups, d), head),
                       pl.BlockSpec((1, 1, groups, LANES), head),
                       state, norm],
            scratch_shapes=[pltpu.VMEM((SUBLANES, dp), f32),
                            pltpu.VMEM((groups, SUBLANES, dp), f32),
                            pltpu.VMEM((groups, d, LANES), f32)]),
        out_shape=[jax.ShapeDtypeStruct((bsz, h, groups, d), f32),
                   jax.ShapeDtypeStruct((bsz, h, groups, LANES), f32),
                   jax.ShapeDtypeStruct(s.shape, s.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype)],
        # the planes are operands 5 and 6 (the two prefetched arrays come
        # first) and results 2 and 3: updated where they lie
        input_output_aliases={5: 2, 6: 3},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_fa._INTERPRET,
        name='retention_state_update',
    )(rows, g, vb, pk, pq, s, z)
    return num, jnp.sum(den, axis=-1), s, z


def state_update(s, z, rows, g, pk, pq, v):
    """One token of the recurrence for the sequences whose states are the
    pool's rows ``rows`` (distinct).

    s [R, kv heads, d, Dp] and z [R, kv heads, Dp], float32 or bfloat16 (a
    state is then widened, updated in float32 and rounded again); rows [B]
    int32; g [B, kv heads] the token's decay; pk [B, kv heads, Dp] and pq
    [B, kv heads, G, Dp]: ``phi`` of the key and of the group's queries as
    one axis; v [B, kv heads, d]; all float32
    -> (y [B, kv heads, G, d] float32, s and z with those rows updated)."""
    f32 = jnp.float32
    rows = rows.astype(jnp.int32)
    g, pk, pq, v = (x.astype(f32) for x in (g, pk, pq, v))
    if state_update_available(s):
        num, den, s, z = _state_update_call(s, z, rows, g, pk, pq, v)
    else:
        s1 = (s[rows].astype(f32) * g[..., None, None]
              + v[..., :, None] * pk[..., None, :])
        z1 = z[rows].astype(f32) * g[..., None] + pk
        num = jnp.einsum('bhad,bhjd->bhaj', pq, s1,
                         precision=jax.lax.Precision.HIGHEST)
        den = jnp.einsum('bhad,bhd->bha', pq, z1,
                         precision=jax.lax.Precision.HIGHEST)
        s = s.at[rows].set(s1.astype(s.dtype))
        z = z.at[rows].set(z1.astype(z.dtype))
    return num / (den[..., None] + EPS), s, z
