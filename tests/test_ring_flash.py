"""Ring FLASH attention: the sp ring schedule computed by the pallas
kernels (interpret mode on the virtual CPU mesh). Exactness is checked
against single-device full attention — forward AND grads — causal and
non-causal, plus the GPT sp train path end to end.
"""
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

import sys

import paddle_tpu.ops.flash_attention  # noqa: F401 (ensure module import)
import paddle_tpu.parallel.ring_attention  # noqa: F401

# package __init__ re-exports shadow the submodule attribute with the
# same-named function; fetch the modules from sys.modules
ra = sys.modules['paddle_tpu.parallel.ring_attention']


@pytest.fixture(autouse=True)
def _interpret():
    fa = sys.modules['paddle_tpu.ops.flash_attention']
    fa.set_interpret(True)
    yield
    fa.set_interpret(False)


def _mesh(sp):
    devs = np.array(jax.devices()[:sp]).reshape(sp)
    return Mesh(devs, ('sp',))


def _naive(q, k, v, causal):
    S = q.shape[1]
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum('bqhd,bkhd->bhqk', q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum('bhqk,bkhd->bqhd', p, v.astype(jnp.float32))


@pytest.mark.parametrize('causal', [True, False])
@pytest.mark.parametrize('sp', [2, 4])
def test_ring_flash_forward_exact(causal, sp):
    B, S, H, D = 1, 512 * sp, 2, 64          # S_local = 512 tiles the kernel
    key = jax.random.PRNGKey(0)
    q, k, v = [jax.random.normal(kk, (B, S, H, D), jnp.float32)
               for kk in jax.random.split(key, 3)]
    mesh = _mesh(sp)
    spec = P(None, 'sp', None, None)
    f = shard_map(partial(ra.ring_flash_attention, axis_name='sp',
                          causal=causal),
                  mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                  check_vma=False)
    out = f(q, k, v)
    ref = _naive(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ring_flash_grads_exact():
    sp, B, S, H, D = 2, 1, 512 * 2, 2, 64
    key = jax.random.PRNGKey(1)
    q, k, v = [jax.random.normal(kk, (B, S, H, D), jnp.float32)
               for kk in jax.random.split(key, 3)]
    mesh = _mesh(sp)
    spec = P(None, 'sp', None, None)

    def ring_loss(q, k, v):
        f = shard_map(partial(ra.ring_flash_attention, axis_name='sp',
                              causal=True),
                      mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                      check_vma=False)
        out = f(q, k, v)
        return jnp.sum(jnp.sin(out.astype(jnp.float32)))

    def ref_loss(q, k, v):
        return jnp.sum(jnp.sin(_naive(q, k, v, True)))

    g_ring = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip('qkv', g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4,
                                   err_msg=f'd{name} mismatch')


def test_ring_flash_matches_jnp_ring():
    """The two ring implementations agree (same schedule, different block
    math) — bf16 inputs as the train step uses."""
    sp, B, S, H, D = 2, 2, 512 * 2, 2, 64
    key = jax.random.PRNGKey(2)
    q, k, v = [jax.random.normal(kk, (B, S, H, D), jnp.bfloat16)
               for kk in jax.random.split(key, 3)]
    mesh = _mesh(sp)
    spec = P(None, 'sp', None, None)

    def run(fn):
        f = shard_map(partial(fn, axis_name='sp', causal=True),
                      mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                      check_vma=False)
        return np.asarray(f(q, k, v), np.float32)

    np.testing.assert_allclose(run(ra.ring_flash_attention),
                               run(ra.ring_attention), rtol=2e-2, atol=2e-2)


def test_gpt_sp_train_step_uses_ring_flash():
    """GPT sp=2 with use_flash: one train step through the ring-flash path
    decreases the loss and stays finite."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.models import gpt

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {'dp_degree': 2, 'sp_degree': 2}
    topo = fleet.init(is_collective=True, strategy=strategy)
    cfg = gpt.GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                        num_heads=1, max_seq_len=1024, dtype='float32',
                        use_flash=True, remat=False, sp=2)
    params = gpt.place_params(gpt.init_params(cfg, jax.random.PRNGKey(0)),
                              cfg, topo.mesh)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3)
    opt_state = opt.functional_init(params)
    step = gpt.make_train_step(cfg, opt, topo.mesh)
    dp = topo.mesh.shape['dp']        # fleet may expand dp to fill devices
    toks = jax.random.randint(jax.random.PRNGKey(1), (dp, 1024), 0, 128)
    losses = []
    for i in range(2):
        loss, params, opt_state = step(params, opt_state,
                                       jax.random.PRNGKey(2 + i),
                                       jnp.asarray(1e-3), toks, toks)
        losses.append(float(loss))
    assert np.isfinite(losses).all() and losses[1] < losses[0]


def test_ring_flash_gqa_parity():
    """GQA through the ring: kv blocks rotate at H_kv size and the kernels
    serve query groups; fwd + grads exact vs full (repeated-kv) attention."""
    mesh = _mesh(2)
    B, S, H, HKV, D = 1, 1024, 4, 2, 64      # S_local = 512 tiles kernels
    q = jax.random.normal(jax.random.PRNGKey(7), (B, S, H, D), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(8), (B, S, HKV, D), jnp.float32)
    v = jax.random.normal(jax.random.PRNGKey(9), (B, S, HKV, D), jnp.float32)
    spec = P(None, 'sp', None, None)

    f = shard_map(partial(ra.ring_flash_attention, axis_name='sp',
                          causal=True),
                  mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                  check_vma=False)
    got = f(q, k, v)
    kr = jnp.repeat(k, H // HKV, axis=2)
    vr = jnp.repeat(v, H // HKV, axis=2)
    want = _naive(q, kr, vr, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=2e-4, rtol=2e-4)

    tgt = jax.random.normal(jax.random.PRNGKey(10), q.shape)

    def loss_ring(q, k, v):
        return jnp.sum((f(q, k, v) - tgt) ** 2)

    def loss_full(q, k, v):
        return jnp.sum((_naive(q, jnp.repeat(k, H // HKV, axis=2),
                               jnp.repeat(v, H // HKV, axis=2),
                               causal=True) - tgt) ** 2)

    g1 = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    for a, b, nm in zip(g1, g2, 'qkv'):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-3, rtol=5e-3, err_msg=f'd{nm}')


def test_ring_gate_requires_tiling_local_shard():
    """The ring path runs the kernels WITHOUT the public wrapper's padding:
    non-block-multiple local shards must be declined (review r4)."""
    ok = jnp.zeros((1, 512, 2, 64))
    ok384 = jnp.zeros((1, 384, 2, 64))   # tiles with auto-picked 128 blocks
    bad = jnp.zeros((1, 320, 2, 64))     # 320 % 128 != 0
    assert ra.ring_flash_available(ok)
    assert ra.ring_flash_available(ok384)
    assert not ra.ring_flash_available(bad)


# ---- ring dropout (r5): in-kernel masks per ring pair ---------------------

def _ring_drop_reference(q, k, v, causal, rate, seed, sp):
    """Global softmax + the EXACT mask the ring kernels sample: per
    (q rank rq, kv rank rk) pair seed (_pair_seed), kernel-LOCAL
    coordinates (bh row, local q, local k)."""
    fa = sys.modules['paddle_tpu.ops.flash_attention']
    B, S, H, D = q.shape
    s_local = S // sp
    scale = 1.0 / math.sqrt(D)
    s = jnp.einsum('bqhd,bkhd->bhqk', q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)                      # [B,H,S,S]
    rows = jnp.arange(B * H, dtype=jnp.uint32).reshape(B, H)[:, :, None,
                                                             None]
    gq = jnp.arange(S, dtype=jnp.int32)[None, None, :, None]
    gk = jnp.arange(S, dtype=jnp.int32)[None, None, None, :]
    rq, lq = gq // s_local, gq % s_local
    rk, lk = gk // s_local, gk % s_local
    pair_seed = ra._pair_seed(jnp.uint32(seed), rq.astype(jnp.uint32),
                              rk.astype(jnp.uint32), sp)
    keep = fa._dropout_keep(pair_seed, rows, lq, lk, rate)
    p = jnp.where(keep, p / (1.0 - rate), 0.0)
    return jnp.einsum('bhqk,bkhd->bqhd', p, v.astype(jnp.float32))


@pytest.mark.parametrize('causal', [True, False])
def test_ring_flash_dropout_forward_exact(causal):
    sp = 4
    B, S, H, D = 1, 128 * sp, 2, 64
    key = jax.random.PRNGKey(1)
    q, k, v = [jax.random.normal(kk, (B, S, H, D), jnp.float32)
               for kk in jax.random.split(key, 3)]
    mesh = _mesh(sp)
    spec = P(None, 'sp', None, None)
    f = shard_map(partial(ra.ring_flash_attention, axis_name='sp',
                          causal=causal, drop_rate=0.3,
                          seed=jnp.uint32(99)),
                  mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                  check_vma=False)
    got = f(q, k, v)
    want = _ring_drop_reference(q, k, v, causal, 0.3, 99, sp)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


def test_ring_flash_dropout_grad_exact():
    """The backward ring sweep regenerates identical per-pair masks:
    dq/dk/dv match the explicit-mask global reference."""
    sp = 2
    B, S, H, D = 1, 128 * sp, 2, 64
    key = jax.random.PRNGKey(2)
    q, k, v = [jax.random.normal(kk, (B, S, H, D), jnp.float32)
               for kk in jax.random.split(key, 3)]
    mesh = _mesh(sp)
    spec = P(None, 'sp', None, None)

    def ring_loss(q, k, v):
        f = shard_map(partial(ra.ring_flash_attention, axis_name='sp',
                              causal=True, drop_rate=0.25,
                              seed=jnp.uint32(7)),
                      mesh=mesh, in_specs=(spec, spec, spec),
                      out_specs=spec, check_vma=False)
        return f(q, k, v).astype(jnp.float32).sum()

    def ref_loss(q, k, v):
        return _ring_drop_reference(q, k, v, True, 0.25, 7, sp).sum()

    g1 = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4)


def test_ring_flash_dropout_gqa_and_zero_rate():
    """GQA composes with ring dropout; drop_rate=0 is bit-identical to
    the no-dropout path (unchanged trace)."""
    sp = 2
    B, S, H, D = 1, 128 * sp, 4, 64
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (B, S, H, D), jnp.float32)
    k, v = [jax.random.normal(kk, (B, S, 2, D), jnp.float32)
            for kk in jax.random.split(key, 2)]
    mesh = _mesh(sp)
    qs = P(None, 'sp', None, None)

    def run(**kw):
        f = shard_map(partial(ra.ring_flash_attention, axis_name='sp',
                              causal=True, **kw),
                      mesh=mesh, in_specs=(qs, qs, qs), out_specs=qs,
                      check_vma=False)
        return np.asarray(f(q, k, v))

    base = run()
    np.testing.assert_array_equal(run(drop_rate=0.0), base)
    dropped = run(drop_rate=0.4, seed=jnp.uint32(5))
    assert not np.allclose(dropped, base)
    assert np.isfinite(dropped).all()


def test_gpt_sp_train_step_with_dropout():
    """GPTConfig.dropout trains through the sp ring path (r5: the sp
    refusal is lifted — in-kernel per-pair masks): finite decreasing loss,
    per-step mask variation via the step key."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed import fleet
    from paddle_tpu.models import gpt

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {'dp_degree': 2, 'sp_degree': 2}
    topo = fleet.init(is_collective=True, strategy=strategy)
    cfg = gpt.GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                        num_heads=1, max_seq_len=512, dtype='float32',
                        use_flash=True, remat=False, sp=2, dropout=0.2)
    params = gpt.place_params(gpt.init_params(cfg, jax.random.PRNGKey(0)),
                              cfg, topo.mesh)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3)
    opt_state = opt.functional_init(params)
    step = gpt.make_train_step(cfg, opt, topo.mesh)
    dp = topo.mesh.shape['dp']
    toks = jax.random.randint(jax.random.PRNGKey(1), (dp, 512), 0, 128)

    # same params, different step keys -> different dropout masks -> losses
    l_a = float(step(jax.tree_util.tree_map(jnp.copy, params),
                     opt.functional_init(params), jax.random.PRNGKey(5),
                     jnp.asarray(1e-3), toks, toks)[0])
    l_b = float(step(jax.tree_util.tree_map(jnp.copy, params),
                     opt.functional_init(params), jax.random.PRNGKey(6),
                     jnp.asarray(1e-3), toks, toks)[0])
    assert l_a != l_b

    losses = []
    for i in range(3):
        loss, params, opt_state = step(params, opt_state,
                                       jax.random.PRNGKey(10 + i),
                                       jnp.asarray(1e-3), toks, toks)
        losses.append(float(loss))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_ring_flash_dropout_gqa_grad_exact():
    """GQA + ring dropout BACKWARD exactness (review r5h: the GQA group
    reduction under per-pair masks was only finiteness-checked). The
    kernels hash rows over B*H query heads with kv rows shared — so the
    reference is the MHA reference over group-repeated kv."""
    fa = sys.modules['paddle_tpu.ops.flash_attention']
    sp = 2
    B, S, H, Hkv, D = 1, 128 * sp, 4, 2, 64
    key = jax.random.PRNGKey(4)
    q = jax.random.normal(key, (B, S, H, D), jnp.float32)
    k, v = [jax.random.normal(kk, (B, S, Hkv, D), jnp.float32)
            for kk in jax.random.split(key, 2)]
    mesh = _mesh(sp)
    spec = P(None, 'sp', None, None)

    def ring_loss(q, k, v):
        f = shard_map(partial(ra.ring_flash_attention, axis_name='sp',
                              causal=True, drop_rate=0.2,
                              seed=jnp.uint32(21)),
                      mesh=mesh, in_specs=(spec, spec, spec),
                      out_specs=spec, check_vma=False)
        return f(q, k, v).astype(jnp.float32).sum()

    def ref_loss(q, k, v):
        kx, vx = fa.repeat_kv(k, v, H)
        return _ring_drop_reference(q, kx, vx, True, 0.2, 21, sp).sum()

    np.testing.assert_allclose(
        float(ring_loss(q, k, v)), float(ref_loss(q, k, v)), rtol=1e-5)
    g1 = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4)


def test_seed_folds_do_not_alias_coordinates():
    """mix_seed folds: adjacent derived seeds must not produce masks that
    are coordinate-shifted copies (review r5h — a linear fold with the
    hash's own multipliers did exactly that)."""
    fa = sys.modules['paddle_tpu.ops.flash_attention']
    q_pos = jnp.arange(64, dtype=jnp.int32)[:, None]
    k_pos = jnp.arange(64, dtype=jnp.int32)[None, :]

    def mask(seed, row=0):
        return np.asarray(fa._dropout_keep(jnp.uint32(seed),
                                           jnp.uint32(row), q_pos, k_pos,
                                           0.5))

    # pair-style fold: masks for adjacent pairs share ~50% of bits (not
    # ~100% under any small coordinate shift)
    s0 = ra._pair_seed(jnp.uint32(9), 0, 0, 2)
    s1 = ra._pair_seed(jnp.uint32(9), 0, 1, 2)
    m0, m1 = mask(int(s0)), mask(int(s1))
    assert 0.35 < (m0 == m1).mean() < 0.65
    for dq in (-2, -1, 1, 2):        # no shifted-copy structure either
        a = m0[2:-2, 2:-2]
        b = np.roll(m1, dq, axis=0)[2:-2, 2:-2]
        assert (a == b).mean() < 0.8, dq


def test_ring_dropout_without_seed_is_rejected():
    """drop_rate > 0 with no seed must raise, matching flash_attention: a
    silent seed default would replay one dropout mask every hop and step
    (regression: the ring path used to default seed to 0)."""
    sp = 2
    B, S, H, D = 1, 512 * sp, 2, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, D), jnp.float32)
    mesh = _mesh(sp)
    spec = P(None, 'sp', None, None)
    fn = shard_map(
        partial(ra.ring_flash_attention, axis_name='sp', causal=True,
                drop_rate=0.5),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    with pytest.raises(ValueError, match='requires seed'):
        fn(q, q, q)
