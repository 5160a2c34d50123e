"""Ring attention: exact causal attention over a sequence-sharded axis.

Long-context / context-parallel engine (reference analogue: sequence-parallel
NCCL p2p in fleet meta_parallel + RingFlashAttention-style kernels). Each
device holds a query block [B, S/sp, H, D]; K/V blocks rotate around the 'sp'
ring via ppermute while a running softmax (flash-attention style m/l
accumulators) merges partial results — attention memory stays O(S/sp) per
chip and the permutes overlap with the block matmuls on ICI.

Pure function over arrays: call inside shard_map with axis 'sp'.
"""
import math
from functools import partial

import jax
import jax.numpy as jnp


def _block_attn(q, k, v, mask_val, scale):
    """One block: returns (unnormalized out, running max m, running sum l)."""
    s = jnp.einsum('bqhd,bkhd->bhqk', q, k) * scale
    s = s + mask_val
    m = jnp.max(s, axis=-1)                       # [B,H,Q]
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)                       # [B,H,Q]
    o = jnp.einsum('bhqk,bkhd->bqhd', p, v)
    return o, m, l


def ring_attention(q, k, v, axis_name='sp', causal=True):
    """q/k/v: [B, S_local, H, D] (the 'sp'-local sequence shard).

    Returns [B, S_local, H, D]. Exact softmax over the full sequence.
    """
    sp = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, S, H, D = q.shape
    scale = 1.0 / math.sqrt(D)
    neg = jnp.asarray(-1e30, jnp.float32)

    q32 = q.astype(jnp.float32)

    def mask_for(kv_rank):
        if not causal:
            return jnp.zeros((1, 1, S, S), jnp.float32)
        q_pos = idx * S + jnp.arange(S)[:, None]          # [S,1]
        k_pos = kv_rank * S + jnp.arange(S)[None, :]      # [1,S]
        return jnp.where(q_pos >= k_pos, 0.0, neg)[None, None]

    def body(carry, _):
        o_acc, m_acc, l_acc, k_cur, v_cur, kv_rank = carry
        mask = mask_for(kv_rank)
        o_b, m_b, l_b = _block_attn(q32, k_cur.astype(jnp.float32),
                                    v_cur.astype(jnp.float32), mask, scale)
        m_new = jnp.maximum(m_acc, m_b)
        alpha = jnp.exp(m_acc - m_new)
        beta = jnp.exp(m_b - m_new)
        o_acc = o_acc * alpha.transpose(0, 2, 1)[..., None] + \
            o_b * beta.transpose(0, 2, 1)[..., None]
        l_acc = l_acc * alpha + l_b * beta
        # rotate K/V to the next rank on the ring (overlaps with next block)
        perm = [(i, (i + 1) % sp) for i in range(sp)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        kv_rank = (kv_rank - 1) % sp
        return (o_acc, m_new, l_acc, k_nxt, v_nxt, kv_rank), None

    o0 = jnp.zeros((B, S, H, D), jnp.float32)
    m0 = jnp.full((B, H, S), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, S), jnp.float32)
    (o, m, l, _, _, _), _ = jax.lax.scan(
        body, (o0, m0, l0, k, v, idx), None, length=sp)
    out = o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


# --------------------------------------------------------------------------
# Ring FLASH attention: the ring schedule above, with every block pair
# computed by the pallas flash kernels — no S_local x S_local score matrix
# in HBM, in the forward OR the backward. Exact softmax over the full
# sequence; grads exact (the backward re-runs each pair's tiled kernels
# against the GLOBAL log-sum-exp, the standard ring-flash-attention split).
# --------------------------------------------------------------------------

def ring_flash_available(q, k=None, axis_name='sp'):
    """The pallas kernels must tile the LOCAL sequence shard EXACTLY (the
    ring calls the kernel internals directly, without the public wrapper's
    pad-and-mask) — GQA kv layouts included (the ring then rotates the
    SMALLER kv blocks)."""
    from ..ops import flash_attention as _fa_fn  # noqa: F401
    import sys
    fa = sys.modules['paddle_tpu.ops.flash_attention']
    kv = q if k is None else k
    s_local = int(q.shape[1])
    # blocks are auto-picked per call (fa._pick_blocks); any 128-multiple
    # local shard tiles exactly
    return (fa.flash_attention_available(q, kv, kv, None)
            and s_local % 128 == 0)


def _bhsd(x):
    B, S, H, D = x.shape
    return x.transpose(0, 2, 1, 3).reshape(B * H, S, D)


def _unbhsd(x, B, H):
    BH, S, D = x.shape
    return x.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def _pair_seed(seed, idx, kv_rank, sp):
    """Per-(q rank, kv source rank) dropout seed: both ranks fold in so no
    two pairs share a mask stream (two q ranks visiting the same kv block
    use the same LOCAL coordinates inside the kernels — without the idx
    term their masks would be correlated). Matches between the forward and
    backward ring sweeps because both track kv_rank identically. The fold
    is mix_seed'd so the pair stride can never alias the mask hash's
    coordinate multipliers (review r5h)."""
    from ..ops.flash_attention import mix_seed
    return mix_seed(jnp.asarray(seed, jnp.uint32)
                    + (jnp.asarray(idx, jnp.uint32) * jnp.uint32(sp)
                       + jnp.asarray(kv_rank, jnp.uint32))
                    * jnp.uint32(0xB5297A4D))


def _ring_fwd_impl(q, k, v, axis_name, causal, drop_rate=0.0, seed=None):
    """-> (out [BH,S,D] in q.dtype, lse [BH,S] f32). Layout: kernel-major.
    GQA: k/v may carry H_kv = H/g heads — the ring rotates those smaller
    blocks and the kernels serve each kv row to its query group.

    drop_rate/seed: in-kernel attention dropout per ring pair. Sound under
    the lse merge: each hop's kernel normalizer accumulates UNdropped
    probabilities, so the combined output is exactly
    dropout(global softmax) @ v."""
    if drop_rate > 0.0 and seed is None:
        # matches flash_attention: a silent seed default would make every
        # hop (and every step) reuse the same dropout mask
        raise ValueError('drop_rate > 0 requires seed')
    from ..ops.flash_attention import _flash_fwd
    sp = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, S, H, D = q.shape
    groups = H // k.shape[2]
    qr, kr, vr = _bhsd(q), _bhsd(k), _bhsd(v)
    seed0 = jnp.asarray(0 if seed is None else seed, jnp.uint32)

    def skip(kv):
        return (jnp.zeros(qr.shape, jnp.float32),
                jnp.full((B * H, S), -jnp.inf, jnp.float32))

    def off_diag(kv):
        o, lse = _flash_fwd(qr, kv[0], kv[1], False, g=groups,
                            drop_rate=drop_rate, seed=kv[2])
        return o.astype(jnp.float32), lse

    def diag(kv):
        o, lse = _flash_fwd(qr, kv[0], kv[1], True, g=groups,
                            drop_rate=drop_rate, seed=kv[2])
        return o.astype(jnp.float32), lse

    def body(carry, _):
        o_acc, lse_acc, k_cur, v_cur, kv_rank = carry
        if causal:
            # 0: future block (masked out entirely), 1: past block (dense),
            # 2: diagonal block (causal within the pair)
            branch = jnp.where(kv_rank > idx, 0,
                               jnp.where(kv_rank == idx, 2, 1))
        else:
            branch = jnp.int32(1)
        o_b, lse_b = jax.lax.switch(
            branch, [skip, off_diag, diag],
            (k_cur, v_cur, _pair_seed(seed0, idx, kv_rank, sp)))
        # log-sum-exp merge of two softmax-normalized partials
        lse_new = jnp.logaddexp(lse_acc, lse_b)
        w_a = jnp.exp(lse_acc - lse_new)[..., None]
        w_b = jnp.exp(lse_b - lse_new)[..., None]
        o_acc = o_acc * w_a + o_b * w_b
        perm = [(i, (i + 1) % sp) for i in range(sp)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (o_acc, lse_new, k_nxt, v_nxt, (kv_rank - 1) % sp), None

    o0 = jnp.zeros(qr.shape, jnp.float32)
    lse0 = jnp.full((B * H, S), -jnp.inf, jnp.float32)
    (o, lse, _, _, _), _ = jax.lax.scan(
        body, (o0, lse0, kr, vr, idx), None, length=sp)
    return o.astype(q.dtype), lse


from functools import partial as _partial


@_partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def ring_flash_attention(q, k, v, axis_name='sp', causal=True,
                         drop_rate=0.0, seed=None):
    """q/k/v: [B, S_local, H, D] inside shard_map over ``axis_name``.
    drop_rate (static) / seed (traced u32): in-kernel attention dropout —
    the backward sweep regenerates the identical per-pair masks."""
    B, _, H, _ = q.shape
    out, _ = _ring_fwd_impl(q, k, v, axis_name, causal, drop_rate, seed)
    return _unbhsd(out, B, H)


def _rf_f(q, k, v, axis_name, causal, drop_rate=0.0, seed=None):
    B, _, H, _ = q.shape
    out, lse = _ring_fwd_impl(q, k, v, axis_name, causal, drop_rate, seed)
    return _unbhsd(out, B, H), (q, k, v, seed, out, lse)


def _rf_b(axis_name, causal, drop_rate, res, g):
    from ..ops.flash_attention import _bwd_pallas_pre, bwd_broadcasts
    q, k, v, seed, out, lse = res      # out [BH,S,D] dtype q, lse [BH,S] f32
    sp = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, S, H, D = q.shape
    groups = H // k.shape[2]
    qr, kr, vr, gr = _bhsd(q), _bhsd(k), _bhsd(v), _bhsd(g.astype(q.dtype))
    # global delta/lse lane-broadcasts depend only on (out, g): compute ONCE,
    # reuse on every ring hop. (delta = rowsum(g*out) remains the correct
    # global term under dropout: sum_k D*dD == sum_k P*dP per column block.)
    lse_b, dta_b = bwd_broadcasts(out, lse, gr)
    seed0 = jnp.asarray(0 if seed is None else seed, jnp.uint32)

    def skip(kv):
        z = jnp.zeros(qr.shape, jnp.float32)
        zkv = jnp.zeros(kr.shape, jnp.float32)
        return z, zkv, zkv

    def pair(kv, diag):
        # the kernels recompute p = exp(s - GLOBAL lse) with the global
        # delta, so each pair's tiled kernels emit exactly its
        # contribution to dq / dk / dv
        dq, dk, dv = _bwd_pallas_pre(qr, kv[0], kv[1], gr, lse_b, dta_b,
                                     diag, groups=groups,
                                     drop_rate=drop_rate, seed=kv[2])
        return (dq.astype(jnp.float32), dk.astype(jnp.float32),
                dv.astype(jnp.float32))

    def body(carry, _):
        dq_acc, k_cur, v_cur, dk_cur, dv_cur, kv_rank = carry
        if causal:
            branch = jnp.where(kv_rank > idx, 0,
                               jnp.where(kv_rank == idx, 2, 1))
        else:
            branch = jnp.int32(1)
        dq_b, dk_b, dv_b = jax.lax.switch(
            branch, [skip, _partial(pair, diag=False),
                     _partial(pair, diag=True)],
            (k_cur, v_cur, _pair_seed(seed0, idx, kv_rank, sp)))
        dq_acc = dq_acc + dq_b
        dk_cur = dk_cur + dk_b
        dv_cur = dv_cur + dv_b
        # k/v and THEIR grad accumulators rotate together: after sp hops
        # every block is home again carrying contributions from all ranks
        perm = [(i, (i + 1) % sp) for i in range(sp)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        dk_nxt = jax.lax.ppermute(dk_cur, axis_name, perm)
        dv_nxt = jax.lax.ppermute(dv_cur, axis_name, perm)
        return (dq_acc, k_nxt, v_nxt, dk_nxt, dv_nxt,
                (kv_rank - 1) % sp), None

    z = jnp.zeros(qr.shape, jnp.float32)
    zkv = jnp.zeros(kr.shape, jnp.float32)
    (dq, _, _, dk, dv, _), _ = jax.lax.scan(
        body, (z, kr, vr, zkv, zkv, idx), None, length=sp)
    h_kv = k.shape[2]
    dseed = None
    if seed is not None:
        import numpy as _np
        dseed = _np.zeros(jnp.shape(seed), jax.dtypes.float0)
    return (_unbhsd(dq.astype(q.dtype), B, H),
            _unbhsd(dk.astype(k.dtype), B, h_kv),
            _unbhsd(dv.astype(v.dtype), B, h_kv),
            dseed)


ring_flash_attention.defvjp(_rf_f, _rf_b)


def sequence_parallel_attention(q, k, v, mesh, causal=True):
    """shard_map wrapper: q/k/v are [B, S, H, D] global arrays; runs ring
    attention with S sharded over the mesh 'sp' axis."""
    from jax.sharding import PartitionSpec as P
    spec = P(('dp',), 'sp', None, None)
    f = jax.shard_map(partial(ring_attention, axis_name='sp', causal=causal),
                      mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                      check_vma=False)
    return f(q, k, v)
