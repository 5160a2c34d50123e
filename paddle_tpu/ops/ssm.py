"""State-space sequence recurrence (the Mamba-2 layer's), twice.

For head ``h`` with a scalar ``A_h < 0``, inputs ``x_t`` in R^P, ``B_t`` and
``C_t`` in R^N (one group: every head shares them) and a step ``dt_t > 0``:

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T        S in R^{P x N}
    y_t = S_t C_t                                        (+ D_h x_t, the
                                                          caller's)

 - ``chunked_scan``: the recurrence over a whole sequence (a prefill), in
   chunks of ``chunk`` rows: inside a chunk the outputs are products (the
   chunk's own rows through a decay-weighted ``C B^T``, the state at the
   chunk's start through ``C``), between chunks a short scan carries the
   state. A row whose ``dt`` is 0 leaves the state exactly as it was
   (``exp(0) = 1`` and nothing is added), so a padded prompt with ``dt = 0``
   past its last real row hands back ``S_{valid-1}``.
 - ``state_update``: ONE token for many sequences (a decode step), whose
   states lie in a pool of rows, ``[R, N, C/128, 128]`` float32, carried
   whole and updated in place: a Pallas kernel reads each sequence's state
   once and writes it once (the output is the input's buffer; rows the call
   does not name keep what they hold), or the same in ``jax.numpy`` where
   the kernel does not run.

**The pool's layout.** A state is held by CHANNEL, ``c = h P + p`` of
``C = H P``, 128 channels to the chip's lanes: ``pool[r, n, c // 128,
c % 128] = S[h, p, n]`` (``to_lanes`` / ``from_lanes``). What a step needs
a channel (the decay, ``dt x``, the output) is then ``[C/128, 128]``, whole
tiles as the projections make them, one ``n`` of a state is such a piece
too, ``B_t[n]`` and ``C_t[n]`` are scalars beside it (the kernel reads them
from scalar memory), and ``y`` is a sum over ``n`` of whole tiles: nothing
is reduced across lanes or sublanes and nothing is transposed, by the
kernel or by the prefill, whose products give ``[N, C]`` as it is stored.

``causal_conv`` / ``conv_step`` are the layer's depthwise causal
convolution over a sequence and over one token against the last
``K - 1`` input rows.
"""
import functools

import jax
import jax.numpy as jnp

# the module, not the function ``ops/__init__`` rebinds the name to
# (ops/paged_attention.py says why); ``_fa._INTERPRET`` stays late-bound
import importlib
_fa = importlib.import_module('paddle_tpu.ops.flash_attention')
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# what one grid step's state block may take (read and written, each double
# buffered: four of these in fast memory)
_BLOCK_BYTES = 2 ** 20


def to_lanes(state):
    """[..., H, P, N] -> [..., N, H P / 128, 128] (the pool's layout)."""
    *lead, h, p, n = state.shape
    s = jnp.swapaxes(state.reshape(*lead, h * p, n), -1, -2)
    return s.reshape(*lead, n, h * p // LANES, LANES)


def from_lanes(state, heads):
    """The inverse of ``to_lanes``: [..., N, C/128, 128] -> [..., H, P, N]."""
    *lead, n, cb, _ = state.shape
    s = jnp.swapaxes(state.reshape(*lead, n, cb * LANES), -1, -2)
    return s.reshape(*lead, heads, cb * LANES // heads, n)


# ---- the convolution -------------------------------------------------------

def causal_conv(x, w, bias, valid=None):
    """Depthwise causal convolution over a sequence from row 0.

    x [B, T, C]; w [K, C] (``w[K-1]`` multiplies the row itself), bias [C]
    -> (out [B, T, C] float32, tail [B, K-1, C] in x's dtype: the last
    ``K - 1`` INPUT rows before row ``valid[b]`` (T without it), zeros
    where they lie before row 0: what ``conv_step`` continues from)."""
    b, t, _ = x.shape
    k = w.shape[0]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = bias.astype(jnp.float32)
    for j in range(k):
        out = out + (padded[:, j:j + t].astype(jnp.float32)
                     * w[j].astype(jnp.float32))
    ends = (jnp.full((b,), t, jnp.int32) if valid is None
            else valid.astype(jnp.int32))
    # padded row j is input row j - (K - 1): rows valid-K+1 .. valid-1
    tail = jax.vmap(lambda p, e: jax.lax.dynamic_slice_in_dim(
        p, e, k - 1, axis=0))(padded, ends)
    return out, tail


def conv_step(tail, x, w, bias):
    """One token: tail [B, K-1, C] (the rows before it), x [B, C] ->
    (out [B, C] float32, the tail the next token continues from)."""
    rows = jnp.concatenate([tail, x[:, None].astype(tail.dtype)], axis=1)
    out = bias.astype(jnp.float32) + jnp.sum(
        rows.astype(jnp.float32) * w.astype(jnp.float32)[None], axis=1)
    return out, rows[:, 1:]


# ---- a whole sequence: the chunked form ------------------------------------

def chunked_scan(x, dt, a, b, c, chunk, cdt):
    """The recurrence over T rows from a zero state.

    x [B, T, H, P]; dt [B, T, H] float32 (0 on a padded row); a [H] float32
    (negative); b, c [B, T, N]; T a multiple of ``chunk``. Products take
    operands in ``cdt`` and accumulate in float32; the decays, their
    cumulative sums and the state are float32.
    -> (y [B, T, H, P] float32, the state after the last row in the pool's
    layout, [B, N, H P / 128, 128] float32)."""
    bsz, t, h, p = x.shape
    n = b.shape[-1]
    q = int(chunk)
    nc = t // q
    f32 = jnp.float32
    dot = functools.partial(jnp.einsum, preferred_element_type=f32)
    # [B, nc, H, Q]: a row's log-decay, summed from its chunk's first row
    la = jnp.moveaxis((dt * a).reshape(bsz, nc, q, h), 3, 2)
    cs = jnp.cumsum(la, axis=-1)
    xd = (x.astype(f32) * dt[..., None]).reshape(bsz, nc, q, h, p)   # dt x
    bq = b.reshape(bsz, nc, q, n).astype(cdt)
    cq = c.reshape(bsz, nc, q, n).astype(cdt)

    # inside a chunk: row t takes row s <= t through exp(cs_t - cs_s) C_t.B_s
    g = dot('bcqn,bcsn->bcqs', cq, bq)                    # [B, nc, Q, Q]
    rows = jnp.arange(q)
    seen = rows[:, None] >= rows[None, :]
    decay = jnp.exp(jnp.where(seen, cs[..., :, None] - cs[..., None, :],
                              -jnp.inf))                  # [B, nc, H, Q, Q]
    y = dot('bchqs,bcshp->bcqhp', (decay * g[:, :, None]).astype(cdt),
            xd.astype(cdt))

    # a chunk's own contribution to the state at its end, by channel as the
    # pool holds it ([N, H P]: no transpose between here and the pool), and
    # the scan of states between chunks
    to_end = jnp.moveaxis(jnp.exp(cs[..., -1:] - cs), 2, 3)   # [B,nc,Q,H]
    own = dot('bcsn,bcsk->bcnk', bq,
              (xd * to_end[..., None]).astype(cdt).reshape(bsz, nc, q, h * p))
    channels = lambda v: jnp.repeat(v, p, axis=-1)        # a head's, p times
    whole = channels(jnp.exp(cs[..., -1]))                # [B, nc, H P]

    def carry(state, chunk_c):
        own_c, whole_c = chunk_c
        return state * whole_c[:, None, :] + own_c, state
    last, starts = jax.lax.scan(
        carry, jnp.zeros((bsz, n, h * p), f32),
        (jnp.moveaxis(own, 1, 0), jnp.moveaxis(whole, 1, 0)))
    starts = jnp.moveaxis(starts, 0, 1)                   # [B, nc, N, H P]
    from_start = dot('bcqn,bcnk->bcqk', cq, starts.astype(cdt))
    y = y + (jnp.moveaxis(jnp.exp(cs), 2, 3)[..., None]
             * from_start.reshape(bsz, nc, q, h, p))
    return (y.reshape(bsz, t, h, p),
            last.reshape(bsz, n, h * p // LANES, LANES))


# ---- one token for many sequences: the pool's rows in place ----------------

def _block_channels(n, cb):
    """Channel blocks (of 128) a grid step takes: the most that divide
    ``cb``, fill whole sublane tiles (or are all of them) and keep the
    state block inside ``_BLOCK_BYTES``."""
    fits = [k for k in range(1, cb + 1)
            if cb % k == 0 and (k % 8 == 0 or k == cb)
            and k * n * LANES * 4 <= _BLOCK_BYTES]
    return max(fits) if fits else None


def state_update_available(pool):
    """The kernel's gate: a float32 pool ``[R, N, C/128, 128]`` whose block
    fits, on the chip (or interpreted: ops/flash_attention.set_interpret)."""
    return (_fa._platform_ok() and pool.ndim == 4
            and pool.dtype == jnp.float32 and pool.shape[-1] == LANES
            and _block_channels(*pool.shape[1:3]) is not None)


def _state_update_kernel(rows_ref, b_ref, c_ref, da_ref, dtx_ref, s_ref,
                         y_ref, o_ref):
    """Grid (sequences, blocks of channel blocks). A step holds ``[N, k,
    128]`` of one sequence's state, read once and written once; one ``n``
    of it is ``[k, 128]`` like the decay and ``dt x``, and ``B[n]``,
    ``C[n]`` are scalars out of scalar memory."""
    del rows_ref            # the index maps' (which row of the pool)
    i = pl.program_id(0)
    da, dtx = da_ref[0], dtx_ref[0]                       # [k, 128]

    def one(n, y):
        s = s_ref[0, n] * da + b_ref[i, n] * dtx
        o_ref[0, n] = s
        return y + c_ref[i, n] * s

    def some(at, y):        # ``unroll`` n's a trip (Mosaic unrolls a
        for n in range(unroll):     # loop whole or not at all)
            y = one(at * unroll + n, y)
        return y
    n_all = s_ref.shape[1]
    unroll = 8 if n_all % 8 == 0 else 1
    y_ref[0] = jax.lax.fori_loop(0, n_all // unroll, some,
                                 jnp.zeros_like(da))


def _state_update_call(pool, rows, da, dtx, b, c):
    _, n, cb, _ = pool.shape
    bsz = rows.shape[0]
    k = _block_channels(n, cb)
    lane = pl.BlockSpec((1, k, LANES), lambda i, j, *_: (i, j, 0))
    state = pl.BlockSpec((1, n, k, LANES),
                         lambda i, j, rows, *_: (rows[i], 0, j, 0))
    y, pool = pl.pallas_call(
        _state_update_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(bsz, cb // k),
            in_specs=[lane, lane, state], out_specs=[lane, state]),
        out_shape=[jax.ShapeDtypeStruct((bsz, cb, LANES), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # the pool is operand 5 (the three prefetched arrays come first)
        # and result 1: updated where it lies
        input_output_aliases={5: 1},
        interpret=_fa._INTERPRET,
        name='ssm_state_update',
    )(rows, b.astype(jnp.float32), c.astype(jnp.float32), da, dtx, pool)
    return y, pool


def state_update(pool, rows, da, dtx, b, c):
    """One token of the recurrence for the sequences whose states are the
    pool's rows ``rows`` (distinct).

    pool [R, N, C/128, 128] float32 or bfloat16 (a state is then widened,
    updated in float32 and rounded again); rows [B] int32; da = exp(dt A)
    and dtx = dt x, a channel: [B, C] float32; b, c [B, N]
    -> (y [B, C] float32: S_t C_t a channel, the pool with those rows
    updated)."""
    bsz = rows.shape[0]
    cb = pool.shape[2]
    da = da.astype(jnp.float32).reshape(bsz, cb, LANES)
    dtx = dtx.astype(jnp.float32).reshape(bsz, cb, LANES)
    rows = rows.astype(jnp.int32)
    if state_update_available(pool):
        y, pool = _state_update_call(pool, rows, da, dtx, b, c)
    else:
        s = (pool[rows].astype(jnp.float32) * da[:, None]
             + b.astype(jnp.float32)[:, :, None, None] * dtx[:, None])
        y = jnp.sum(s * c.astype(jnp.float32)[:, :, None, None], axis=1)
        pool = pool.at[rows].set(s.astype(pool.dtype))
    return y.reshape(bsz, cb * LANES), pool
