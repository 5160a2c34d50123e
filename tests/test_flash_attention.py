"""Flash attention kernel coverage (VERDICT r2 #2): the pallas fwd + bwd
kernels run through the pallas interpreter on CPU and are checked for
numerics parity against naive attention, forward and gradient, causal and
non-causal, d in {64, 128}.

Reference analogue: fused attention under paddle/fluid/operators/fused/.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import importlib

fa = importlib.import_module('paddle_tpu.ops.flash_attention')


@pytest.fixture(autouse=True)
def _interpret_mode():
    fa.set_interpret(True)
    yield
    fa.set_interpret(False)


def _naive(q, k, v, causal):
    """Reference attention in plain jnp, [B, S, H, D] layout."""
    b, s, h, d = q.shape
    qt = q.transpose(0, 2, 1, 3).astype(jnp.float32)
    kt = k.transpose(0, 2, 1, 3).astype(jnp.float32)
    vt = v.transpose(0, 2, 1, 3).astype(jnp.float32)
    sc = jnp.einsum('bhqd,bhkd->bhqk', qt, kt) / np.sqrt(d)
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        sc = jnp.where(mask, sc, -1e30)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum('bhqk,bhkd->bhqd', p, vt)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def _rand_qkv(key, b, s, h, d, dtype=jnp.float32):
    k1, k2, k3 = jax.random.split(key, 3)
    shape = (b, s, h, d)
    return (jax.random.normal(k1, shape, dtype),
            jax.random.normal(k2, shape, dtype),
            jax.random.normal(k3, shape, dtype))


@pytest.mark.parametrize('causal', [False, True])
@pytest.mark.parametrize('d', [64, 128])
def test_forward_parity(causal, d):
    q, k, v = _rand_qkv(jax.random.PRNGKey(0), 1, 512, 2, d)
    got = fa.flash_attention(q, k, v, causal=causal)
    want = _naive(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize('causal', [False, True])
@pytest.mark.parametrize('d', [64, 128])
def test_grad_parity(causal, d):
    q, k, v = _rand_qkv(jax.random.PRNGKey(1), 1, 256, 2, d)
    tgt = jax.random.normal(jax.random.PRNGKey(2), q.shape)

    def loss_flash(q, k, v):
        return jnp.sum((fa.flash_attention(q, k, v, causal=causal) - tgt)**2)

    def loss_naive(q, k, v):
        return jnp.sum((_naive(q, k, v, causal) - tgt)**2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_naive = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for gf, gn, name in zip(g_flash, g_naive, 'qkv'):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gn),
                                   atol=1e-3, rtol=1e-3,
                                   err_msg=f'd{name} mismatch')


def _both_backwards(q, k, v, cotangent, drop=0.0, seed=0):
    """(dq, dk, dv) of the causal kernel rows from ``_bwd_pallas`` and from
    ``_bwd_blockwise``, its jnp reference, on one forward's residuals.
    ``cotangent`` maps the forward's output to the gradient that reaches it."""
    _, s, hh, d = q.shape
    groups = hh // k.shape[2]
    bq, bk = fa._pick_blocks(s, s)

    def rows(x):
        return x.transpose(0, 2, 1, 3).reshape(-1, s, d)

    seed = jnp.asarray([seed], jnp.uint32)
    _, (qt, kt, vt, _, _, out, lse) = fa._flash_f(
        rows(q), rows(k), rows(v), None, seed, True, 0, None, hh, groups,
        bq, bk, drop)
    kw = dict(h=hh, groups=groups, bk=bk, drop_rate=drop, seed=seed)
    args = (qt, kt, vt, out, lse, cotangent(out), True)
    return fa._bwd_pallas(*args, bq=bq, **kw), fa._bwd_blockwise(*args, **kw)


def test_grad_parity_vs_jnp_bwd():
    """The pallas backward and the jnp blockwise backward agree on the same
    fwd residuals (same lse)."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(3), 2, 256, 1, 64)
    g_pallas, g_jnp = _both_backwards(q, k, v, lambda out: 2 * out)
    for gp, gj in zip(g_pallas, g_jnp):
        np.testing.assert_allclose(np.asarray(gp), np.asarray(gj),
                                   atol=1e-4, rtol=1e-4)


def test_bfloat16_forward():
    q, k, v = _rand_qkv(jax.random.PRNGKey(4), 1, 256, 2, 64, jnp.bfloat16)
    got = fa.flash_attention(q, k, v, causal=True)
    want = _naive(q.astype(jnp.float32), k.astype(jnp.float32),
                  v.astype(jnp.float32), True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=3e-2, rtol=3e-2)


def test_availability_gate():
    q = jnp.zeros((1, 512, 2, 64))
    assert fa.flash_attention_available(q, q, q, None)       # interpret on
    # r4: key-padding masks and non-multiple-of-256 seqs are now in-gate
    assert fa.flash_attention_available(q, q, q, jnp.ones((1, 512), bool))
    odd = jnp.zeros((1, 200, 2, 64))                         # padded in-op
    assert fa.flash_attention_available(odd, odd, odd, None)
    # dense [B,H,S,S] additive masks still decline to the XLA path
    assert not fa.flash_attention_available(
        q, q, q, jnp.ones((1, 2, 512, 512)))
    # GQA (kv heads dividing q heads) is in-gate since r4
    kv = jnp.zeros((1, 512, 1, 64))
    assert fa.flash_attention_available(q, kv, kv, None)
    # non-dividing head counts decline
    kv3 = jnp.zeros((1, 512, 3, 64))
    assert not fa.flash_attention_available(q, kv3, kv3, None)
    # unsupported head_dim declines
    bad_d = jnp.zeros((1, 512, 2, 32))
    assert not fa.flash_attention_available(bad_d, bad_d, bad_d, None)
    fa.set_interpret(False)
    # off-TPU with interpret off -> unavailable
    assert not fa.flash_attention_available(q, q, q, None)


def test_gpt_layer_uses_flash_under_interpret():
    """End-to-end: a GPT forward+backward with use_flash=True runs through
    the pallas kernels in interpret mode and matches use_flash=False."""
    from paddle_tpu.models import gpt

    def run(use_flash):
        cfg = gpt.GPTConfig(vocab_size=128, hidden_size=128, num_layers=2,
                            num_heads=2, max_seq_len=256, dtype='float32',
                            use_flash=use_flash, remat=False)
        params = gpt.init_params(cfg, jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 256), 0, 128)

        def loss_fn(p):
            logits = gpt.forward(p, toks, cfg)
            return jnp.mean((logits.astype(jnp.float32)) ** 2)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        return loss, grads

    l_flash, g_flash = run(True)
    l_ref, g_ref = run(False)
    np.testing.assert_allclose(float(l_flash), float(l_ref), rtol=1e-4)
    flat_f = jax.tree_util.tree_leaves(g_flash)
    flat_r = jax.tree_util.tree_leaves(g_ref)
    for a, b in zip(flat_f, flat_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-3)


# ---- round-4 widened gate: masks, cross-attention, odd seqs, decode --------

def _naive_full(q, k, v, causal, mask=None):
    """Independent reference: [B,S,H,D], causal aligned-ends, key-padding
    or dense additive/bool mask broadcastable to [B,H,S_q,S_k]."""
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    qt = q.transpose(0, 2, 1, 3).astype(jnp.float32)
    kt = k.transpose(0, 2, 1, 3).astype(jnp.float32)
    vt = v.transpose(0, 2, 1, 3).astype(jnp.float32)
    sc = jnp.einsum('bhqd,bhkd->bhqk', qt, kt) / np.sqrt(d)
    if causal:
        cm = jnp.tril(jnp.ones((s_q, s_k), bool), k=s_k - s_q)
        sc = jnp.where(cm, sc, -1e30)
    if mask is not None:
        m = jnp.asarray(mask)
        while m.ndim < 4:
            m = m[:, None]
        if m.dtype == jnp.bool_:
            sc = jnp.where(m, sc, -1e30)
        else:
            sc = sc + m.astype(jnp.float32)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum('bhqk,bhkd->bhqd', p, vt)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


@pytest.mark.parametrize('mask_kind', ['bool2d', 'bool4d', 'additive'])
def test_key_padding_mask_forward(mask_kind):
    q, k, v = _rand_qkv(jax.random.PRNGKey(10), 2, 512, 2, 64)
    valid = np.ones((2, 512), bool)
    valid[0, 300:] = False            # batch row 0 padded beyond 300
    valid[1, 450:] = False
    if mask_kind == 'bool2d':
        mask = jnp.asarray(valid)
    elif mask_kind == 'bool4d':
        mask = jnp.asarray(valid)[:, None, None, :]
    else:
        mask = jnp.where(jnp.asarray(valid), 0.0, -1e30)[:, None, :]
    got = fa.flash_attention(q, k, v, causal=False, mask=mask)
    want = _naive_full(q, k, v, False, mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_key_padding_mask_grad():
    q, k, v = _rand_qkv(jax.random.PRNGKey(11), 2, 256, 2, 64)
    mask = jnp.asarray(np.arange(256)[None, :] < np.array([[200], [256]]))
    tgt = jax.random.normal(jax.random.PRNGKey(12), q.shape)

    def loss_flash(q, k, v):
        return jnp.sum((fa.flash_attention(q, k, v, causal=True,
                                           mask=mask) - tgt) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum((_naive_full(q, k, v, True, mask) - tgt) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize('causal', [False, True])
def test_cross_attention(causal):
    """s_q != s_k; causal uses the aligned-ends convention."""
    q, _, _ = _rand_qkv(jax.random.PRNGKey(13), 1, 256, 2, 64)
    _, k, v = _rand_qkv(jax.random.PRNGKey(14), 1, 512, 2, 64)
    got = fa.flash_attention(q, k, v, causal=causal)
    want = _naive_full(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_cross_attention_grad():
    q, _, _ = _rand_qkv(jax.random.PRNGKey(15), 1, 256, 2, 64)
    _, k, v = _rand_qkv(jax.random.PRNGKey(16), 1, 512, 2, 64)
    tgt = jax.random.normal(jax.random.PRNGKey(17), q.shape)

    def lf(q, k, v):
        return jnp.sum((fa.flash_attention(q, k, v, causal=True) - tgt) ** 2)

    def lr(q, k, v):
        return jnp.sum((_naive_full(q, k, v, True) - tgt) ** 2)

    g1 = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize('s', [200, 320])
@pytest.mark.parametrize('causal', [False, True])
def test_non_block_multiple_seq(s, causal):
    """Sequences that don't tile to the 256 block: padded+masked in-op."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(18), 2, s, 2, 64)
    got = fa.flash_attention(q, k, v, causal=causal)
    want = _naive_full(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_non_block_multiple_seq_grad():
    q, k, v = _rand_qkv(jax.random.PRNGKey(19), 1, 320, 2, 64)
    tgt = jax.random.normal(jax.random.PRNGKey(20), q.shape)

    def lf(q, k, v):
        return jnp.sum((fa.flash_attention(q, k, v, causal=True) - tgt) ** 2)

    def lr(q, k, v):
        return jnp.sum((_naive_full(q, k, v, True) - tgt) ** 2)

    g1 = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_flash_decode_parity():
    """Decode kernel vs naive cached attention, traced position, under jit."""
    B, S, H, D = 2, 256, 2, 64
    key = jax.random.PRNGKey(21)
    kc = jax.random.normal(key, (B, S, H, D))
    vc = jax.random.normal(jax.random.PRNGKey(22), (B, S, H, D))
    q = jax.random.normal(jax.random.PRNGKey(23), (B, 1, H, D))
    assert fa.flash_decode_available(q, kc)

    @jax.jit
    def run(pos):
        return fa.flash_decode(q, kc, vc, pos)

    for pos in [0, 5, 100, 255]:
        got = run(jnp.int32(pos))
        # reference: q row 0 at absolute position pos attends keys <= pos
        sc = jnp.einsum('bqhd,bkhd->bhqk', q, kc) / np.sqrt(D)
        sc = jnp.where(jnp.arange(S)[None, None, None, :] <= pos, sc, -1e30)
        p = jax.nn.softmax(sc, axis=-1)
        want = jnp.einsum('bhqk,bkhd->bqhd', p, vc)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


def test_flash_decode_multi_row():
    """T>1 rows (chunked prefill): row i attends keys <= pos+i."""
    B, S, H, D, T = 1, 256, 2, 64, 4
    kc = jax.random.normal(jax.random.PRNGKey(24), (B, S, H, D))
    vc = jax.random.normal(jax.random.PRNGKey(25), (B, S, H, D))
    q = jax.random.normal(jax.random.PRNGKey(26), (B, T, H, D))
    pos = 10
    got = fa.flash_decode(q, kc, vc, jnp.int32(pos))
    sc = jnp.einsum('bqhd,bkhd->bhqk', q, kc) / np.sqrt(D)
    valid = (jnp.arange(S)[None, :] <= pos + jnp.arange(T)[:, None])
    sc = jnp.where(valid[None, None], sc, -1e30)
    p = jax.nn.softmax(sc, axis=-1)
    want = jnp.einsum('bhqk,bkhd->bqhd', p, vc)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_decode_int8_parity():
    """int8-KV decode kernel vs naive attention over the DEQUANTIZED cache
    (the quantization error itself is covered in test_weight_only_int8):
    the kernel's post-dot scale application must equal pre-dot dequant."""
    from paddle_tpu.ops.weight_only import dequantize_kv, quantize_kv
    B, S, H, D = 2, 256, 2, 64
    kc = jax.random.normal(jax.random.PRNGKey(31), (B, S, H, D))
    vc = jax.random.normal(jax.random.PRNGKey(32), (B, S, H, D))
    q = jax.random.normal(jax.random.PRNGKey(33), (B, 1, H, D))
    kq, ks = quantize_kv(kc)
    vq, vs = quantize_kv(vc)
    kbank = {'int8': kq, 'scale': ks}
    vbank = {'int8': vq, 'scale': vs}
    assert fa.flash_decode_available(q, kbank['int8'])
    kf = dequantize_kv(kq, ks, jnp.float32)
    vf = dequantize_kv(vq, vs, jnp.float32)

    @jax.jit
    def run(pos):
        return fa.flash_decode_int8(q, kbank, vbank, pos)

    for pos in [0, 5, 100, 255]:
        got = run(jnp.int32(pos))
        sc = jnp.einsum('bqhd,bkhd->bhqk', q, kf) / np.sqrt(D)
        sc = jnp.where(jnp.arange(S)[None, None, None, :] <= pos, sc, -1e30)
        p = jax.nn.softmax(sc, axis=-1)
        want = jnp.einsum('bhqk,bkhd->bqhd', p, vf)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=5e-5, rtol=5e-5)


def test_flash_decode_int8_gqa_multi_row():
    """GQA (2 q heads share 1 kv head) + T>1 rows through the int8 kernel."""
    from paddle_tpu.ops.weight_only import dequantize_kv, quantize_kv
    B, S, Hkv, D, T = 1, 256, 1, 64, 4
    kc = jax.random.normal(jax.random.PRNGKey(34), (B, S, Hkv, D))
    vc = jax.random.normal(jax.random.PRNGKey(35), (B, S, Hkv, D))
    q = jax.random.normal(jax.random.PRNGKey(36), (B, T, 2, D))
    kq, ks = quantize_kv(kc)
    vq, vs = quantize_kv(vc)
    got = fa.flash_decode_int8(q, {'int8': kq, 'scale': ks},
                               {'int8': vq, 'scale': vs}, jnp.int32(10))
    kf = jnp.repeat(dequantize_kv(kq, ks, jnp.float32), 2, axis=2)
    vf = jnp.repeat(dequantize_kv(vq, vs, jnp.float32), 2, axis=2)
    sc = jnp.einsum('bqhd,bkhd->bhqk', q, kf) / np.sqrt(D)
    valid = (jnp.arange(S)[None, :] <= 10 + jnp.arange(T)[:, None])
    sc = jnp.where(valid[None, None], sc, -1e30)
    p = jax.nn.softmax(sc, axis=-1)
    want = jnp.einsum('bhqk,bkhd->bqhd', p, vf)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=5e-5, rtol=5e-5)


def test_gpt_int8_cache_decode_routes_through_kernel():
    """With interpret on, a kv_cache_int8 GPT decode runs the int8 kernel
    path end-to-end and stays close to the fp-cache decode."""
    from paddle_tpu.models import gpt
    kw = dict(vocab_size=128, hidden_size=128, num_layers=2, num_heads=2,
              max_seq_len=256, dtype='float32', remat=False, use_flash=False)
    cfg_fp = gpt.GPTConfig(**kw)
    cfg_q = gpt.GPTConfig(kv_cache_int8=True, **kw)
    params = gpt.init_params(cfg_fp, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, 128)

    def decode(cfg):
        prefill, step = gpt.make_decode_fns(cfg)
        cache = gpt.init_kv_cache(cfg, 1)
        logits, cache = prefill(params, prompt, cache)
        toks = [int(jnp.argmax(logits, -1)[0])]
        for i in range(4):
            logits, cache = step(params, jnp.argmax(logits, -1).astype(jnp.int32),
                                 jnp.int32(8 + i), cache)
            toks.append(int(jnp.argmax(logits, -1)[0]))
        return toks, np.asarray(logits)

    toks_fp, lg_fp = decode(cfg_fp)
    toks_q, lg_q = decode(cfg_q)
    assert toks_q == toks_fp          # greedy agrees on this seed
    cos = (lg_fp * lg_q).sum() / (np.linalg.norm(lg_fp) * np.linalg.norm(lg_q))
    assert cos > 0.999, cos


def test_gpt_decode_routes_through_flash_kernels():
    """With interpret on, gpt's KV-cache decode (prefill + per-token steps)
    runs the pallas kernels and matches the einsum path numerically."""
    from paddle_tpu.models import gpt
    cfg = gpt.GPTConfig(vocab_size=128, hidden_size=128, num_layers=2,
                        num_heads=2, max_seq_len=256, dtype='float32',
                        remat=False, use_flash=False)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, 128)
    tok = jnp.full((1,), 7, jnp.int32)

    def drive():
        prefill, step = gpt.make_decode_fns(cfg)
        cache = gpt.init_kv_cache(cfg, 1)
        logits0, cache = prefill(params, prompt, cache)
        logits1, cache = step(params, tok, jnp.int32(8), cache)
        logits2, _ = step(params, tok, jnp.int32(9), cache)
        return logits0, logits1, logits2

    flash_out = drive()                 # interpret on: kernels active
    fa.set_interpret(False)
    ref_out = drive()                   # einsum fallback path
    for a, b in zip(flash_out, ref_out):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


def test_per_head_mask_declines_and_sdpa_fallback_matches():
    """[B,H,S_k] per-head masks must NOT be squeezed into per-batch rows
    (review r4: with H==B the gate wrongly accepted them); and the XLA
    fallback must accept the same [B,S_k] key-padding masks the kernel does."""
    q = jnp.zeros((2, 256, 2, 64))
    per_head = jnp.ones((2, 2, 256), bool)
    assert not fa.flash_attention_available(q, q, q, per_head)

    # same call works via the transparent fallback inside flash_attention
    qq, kk, vv = _rand_qkv(jax.random.PRNGKey(30), 2, 256, 2, 64)
    m = np.ones((2, 2, 256), bool)
    m[0, 1, 100:] = False                  # head-specific padding
    got = fa.flash_attention(qq, kk, vv, mask=jnp.asarray(m))
    want = _naive_full(qq, kk, vv, False, jnp.asarray(m)[:, :, None, :])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)

    # F.scaled_dot_product_attention with a [B,S_k] mask: flash path and
    # XLA fallback agree (review r4: the fallback used to crash on it)
    import paddle_tpu.nn.functional as F
    pad = jnp.asarray(np.arange(256)[None, :] < np.array([[200], [256]]))
    with_flash = F.scaled_dot_product_attention(qq, kk, vv, attn_mask=pad)
    fa.set_interpret(False)                # kernel declines -> _sdpa_xla
    without = F.scaled_dot_product_attention(qq, kk, vv, attn_mask=pad)
    np.testing.assert_allclose(
        np.asarray(with_flash._value if hasattr(with_flash, '_value')
                   else with_flash),
        np.asarray(without._value if hasattr(without, '_value')
                   else without), atol=2e-5, rtol=2e-5)


# ---- GQA / MQA (r4: kv heads shared across query groups via index maps) ----

def _naive_gqa(q, k, v, causal, mask=None):
    rep = q.shape[2] // k.shape[2]
    return _naive_full(q, jnp.repeat(k, rep, axis=2),
                       jnp.repeat(v, rep, axis=2), causal, mask)


@pytest.mark.parametrize('h_kv', [1, 2])
@pytest.mark.parametrize('causal', [False, True])
def test_gqa_forward_parity(h_kv, causal):
    H = 4
    q, _, _ = _rand_qkv(jax.random.PRNGKey(40), 2, 256, H, 64)
    _, k, v = _rand_qkv(jax.random.PRNGKey(41), 2, 256, h_kv, 64)
    assert fa.flash_attention_available(q, k, v, None)
    got = fa.flash_attention(q, k, v, causal=causal)
    want = _naive_gqa(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_gqa_grad_parity():
    H, h_kv = 4, 2
    q, _, _ = _rand_qkv(jax.random.PRNGKey(42), 1, 256, H, 64)
    _, k, v = _rand_qkv(jax.random.PRNGKey(43), 1, 256, h_kv, 64)
    tgt = jax.random.normal(jax.random.PRNGKey(44), q.shape)

    def lf(q, k, v):
        return jnp.sum((fa.flash_attention(q, k, v, causal=True) - tgt) ** 2)

    def lr(q, k, v):
        return jnp.sum((_naive_gqa(q, k, v, True) - tgt) ** 2)

    g1 = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
    for a, b, nm in zip(g1, g2, 'qkv'):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4,
                                   err_msg=f'd{nm} mismatch')


def test_gqa_grad_parity_jnp_bwd():
    H, h_kv = 4, 1                              # MQA
    q, _, _ = _rand_qkv(jax.random.PRNGKey(45), 1, 256, H, 64)
    _, k, v = _rand_qkv(jax.random.PRNGKey(46), 1, 256, h_kv, 64)
    g1, g2 = _both_backwards(q, k, v, lambda out: 2 * out)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_gqa_flash_decode():
    B, S, H, h_kv, D = 2, 256, 4, 2, 64
    kc = jax.random.normal(jax.random.PRNGKey(47), (B, S, h_kv, D))
    vc = jax.random.normal(jax.random.PRNGKey(48), (B, S, h_kv, D))
    q = jax.random.normal(jax.random.PRNGKey(49), (B, 1, H, D))
    assert fa.flash_decode_available(q, kc)
    got = fa.flash_decode(q, kc, vc, jnp.int32(100))
    kr = jnp.repeat(kc, H // h_kv, axis=2)
    vr = jnp.repeat(vc, H // h_kv, axis=2)
    sc = jnp.einsum('bqhd,bkhd->bhqk', q, kr) / np.sqrt(D)
    sc = jnp.where(jnp.arange(S)[None, None, None, :] <= 100, sc, -1e30)
    want = jnp.einsum('bhqk,bkhd->bqhd', jax.nn.softmax(sc, -1), vr)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_pick_blocks_invariants():
    """Blocks are picked per call from the shapes. Invariants the kernels
    rely on: bk | bq, both divide the padded seqs, 128-row tiling minimum."""
    for s_q in (128, 256, 300, 384, 512, 640, 1024, 4096, 130):
        for s_k in (128, 256, 300, 512, 1024, 4096):
            bq, bk = fa._pick_blocks(s_q, s_k)
            assert bq % 128 == 0 and bk % 128 == 0
            assert bq % bk == 0, (s_q, s_k, bq, bk)
            # padding stays at 128-row granularity: the picker must divide
            # the 128-padded length, never force extra padding beyond it
            s_q128 = -(-s_q // 128) * 128
            s_k128 = -(-s_k // 128) * 128
            assert s_q128 % bq == 0, (s_q, bq)
            assert s_k128 % bk == 0, (s_k, bk)
    # the training cells' shapes (345M, 1.3B) run 512-row blocks
    assert fa._pick_blocks(1024, 1024) == (512, 512)
    assert fa._pick_blocks(2048, 2048) == (512, 512)
    # ragged seqs keep 128-granularity padding
    assert fa._pick_blocks(300, 300)[0] == 128


# ---- in-kernel attention dropout (VERDICT r5 #5) ---------------------------

def _naive_dropout(q, k, v, causal, rate, seed):
    """Reference: softmax then the SAME counter-hash mask the kernels use
    (fa._dropout_keep over the flattened [B*H, S_q, S_k] rows)."""
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    kx, vx = fa.repeat_kv(k, v, h)
    qt = q.transpose(0, 2, 1, 3).astype(jnp.float32)
    kt = kx.transpose(0, 2, 1, 3).astype(jnp.float32)
    vt = vx.transpose(0, 2, 1, 3).astype(jnp.float32)
    sc = jnp.einsum('bhqd,bhkd->bhqk', qt, kt) / np.sqrt(d)
    if causal:
        mask = jnp.tril(jnp.ones((s_q, s_k), bool), k=s_k - s_q)
        sc = jnp.where(mask, sc, -1e30)
    p = jax.nn.softmax(sc, axis=-1)
    row = jnp.arange(b * h, dtype=jnp.uint32).reshape(b, h)[:, :, None, None]
    q_pos = jnp.arange(s_q, dtype=jnp.int32)[None, None, :, None]
    k_pos = jnp.arange(s_k, dtype=jnp.int32)[None, None, None, :]
    keep = fa._dropout_keep(jnp.uint32(seed), row, q_pos, k_pos, rate)
    p = jnp.where(keep, p / (1.0 - rate), 0.0)
    out = jnp.einsum('bhqk,bhkd->bhqd', p, vt)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def test_dropout_keep_rate_statistics():
    """P(keep) ~= 1-rate and masks decorrelate across seeds."""
    q_pos = jnp.arange(256, dtype=jnp.int32)[:, None]
    k_pos = jnp.arange(256, dtype=jnp.int32)[None, :]
    for rate in (0.1, 0.5):
        m = fa._dropout_keep(jnp.uint32(7), jnp.uint32(3), q_pos, k_pos,
                             rate)
        assert abs(float(jnp.mean(m)) - (1 - rate)) < 0.02, rate
    m1 = fa._dropout_keep(jnp.uint32(1), jnp.uint32(0), q_pos, k_pos, 0.5)
    m2 = fa._dropout_keep(jnp.uint32(2), jnp.uint32(0), q_pos, k_pos, 0.5)
    agree = float(jnp.mean(m1 == m2))
    assert 0.4 < agree < 0.6          # independent masks agree ~50%


@pytest.mark.parametrize('causal', [False, True])
def test_dropout_forward_parity(causal):
    """Kernel dropout == softmax + identical hash mask, element-exact."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(0), 2, 256, 2, 64)
    got = fa.flash_attention(q, k, v, causal=causal, dropout_rate=0.3,
                             dropout_seed=42)
    want = _naive_dropout(q, k, v, causal, 0.3, 42)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


def test_dropout_grad_parity():
    """Pallas backward kernels regenerate the same mask: grads match the
    jnp reference with the explicit mask."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(1), 1, 256, 2, 64)

    def loss_flash(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, dropout_rate=0.25,
                                  dropout_seed=7).sum()

    def loss_ref(q, k, v):
        return _naive_dropout(q, k, v, True, 0.25, 7).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4)


def test_dropout_grad_parity_jnp_bwd():
    """The blockwise jnp reference backward regenerates the same mask too."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(2), 1, 256, 2, 64)
    g1, g2 = _both_backwards(q, k, v, jnp.ones_like, drop=0.25, seed=9)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4)


def test_dropout_gqa_parity():
    """GQA + dropout: shared kv rows, per-query-head masks."""
    q, _, _ = _rand_qkv(jax.random.PRNGKey(3), 2, 256, 4, 64)
    _, k, v = _rand_qkv(jax.random.PRNGKey(4), 2, 256, 2, 64)
    got = fa.flash_attention(q, k, v, causal=True, dropout_rate=0.2,
                             dropout_seed=11)
    want = _naive_dropout(q, k, v, True, 0.2, 11)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=3e-5)


def test_dropout_seed_varies_and_traced():
    """Different seeds -> different outputs; a TRACED seed does not
    retrace (one compiled program serves every step's mask)."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(5), 1, 256, 2, 64)
    f = jax.jit(lambda s: fa.flash_attention(
        q, k, v, causal=True, dropout_rate=0.4, dropout_seed=s))
    o1 = f(jnp.asarray([1], jnp.uint32))
    o2 = f(jnp.asarray([2], jnp.uint32))
    assert not np.allclose(np.asarray(o1), np.asarray(o2))
    assert f._cache_size() == 1


def test_sdpa_keeps_flash_path_under_dropout(monkeypatch):
    """scaled_dot_product_attention no longer declines dropout>0 (VERDICT
    r4 weak #8): the flash kernel is invoked, training stats hold, and
    grads flow."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F

    calls = {}
    real = fa.flash_attention

    def spy(*a, **kw):
        calls['dropout_rate'] = kw.get('dropout_rate')
        return real(*a, **kw)

    monkeypatch.setattr(fa, 'flash_attention', spy)
    q = paddle.to_tensor(np.random.rand(1, 256, 2, 64).astype('f4'))
    q.stop_gradient = False
    out = F.scaled_dot_product_attention(q, q, q, dropout_p=0.3,
                                         is_causal=True, training=True)
    assert calls.get('dropout_rate') == 0.3
    out.sum().backward()
    assert np.isfinite(np.asarray(q.grad._value)).all()


@pytest.mark.parametrize('s', [384, 200])
def test_dropout_multiblock_and_padded_parity(s):
    """Multi-tile (s=384 -> 128-row blocks) and padded (s=200) sequences:
    guards the tile-to-GLOBAL position reconstruction in _drop_mult — a
    local-coordinate bug would pass at s=256 (one tile) but corrupt every
    multi-block mask (review r5b)."""
    q, k, v = _rand_qkv(jax.random.PRNGKey(6), 2, s, 2, 64)

    def loss_flash(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, dropout_rate=0.3,
                                  dropout_seed=13).sum()

    def loss_ref(q, k, v):
        return _naive_dropout(q, k, v, True, 0.3, 13).sum()

    np.testing.assert_allclose(
        np.asarray(fa.flash_attention(q, k, v, causal=True,
                                      dropout_rate=0.3, dropout_seed=13)),
        np.asarray(_naive_dropout(q, k, v, True, 0.3, 13)),
        atol=3e-5, rtol=3e-5)
    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4)


def test_dropout_gqa_grad_parity():
    """GQA + dropout BACKWARD: per-query-head masks applied before the
    group-partial dk/dv sum must match the explicit-mask reference
    (review r5c: forward-only GQA coverage left the dkv group reduction
    unguarded)."""
    q, _, _ = _rand_qkv(jax.random.PRNGKey(7), 2, 256, 4, 64)
    _, k, v = _rand_qkv(jax.random.PRNGKey(8), 2, 256, 2, 64)

    def loss_flash(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, dropout_rate=0.2,
                                  dropout_seed=17).sum()

    def loss_ref(q, k, v):
        return _naive_dropout(q, k, v, True, 0.2, 17).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-5, rtol=5e-4)


# ---------------------------------------------------------------------------
# The causal tile schedule (PR 25): interior tiles run mask-free, diagonal
# tiles in sub-tiles (skipped / masked / mask-free), cut tiles masked whole.
# ---------------------------------------------------------------------------

# name: (s_q, s_k, bq, bk, causal, h, h_kv, dropout, key-padding mask)
_SCHEDULE_CASES = {
    # 3 q blocks: interior and diagonal (2 x 2 sub-tiles) tiles in all three
    'causal_self': (768, 768, 256, 256, True, 2, 2, 0.0, False),
    # a q block spans two k/v blocks: dkv's diagonal differs by program
    'bq_gt_bk': (512, 512, 256, 128, True, 2, 2, 0.0, False),
    # the diagonal starts at key 256 (aligned-ends causal cross-attention)
    'cross_q_off': (256, 768, 256, 256, True, 2, 2, 0.0, False),
    # q_off 220 no block multiple, both lengths padded, kv_valid set
    'cross_q_off_ragged': (200, 420, 256, 128, True, 2, 2, 0.0, False),
    # causal self-attention with a cut last k/v block
    'kv_valid': (600, 600, 256, 256, True, 2, 2, 0.0, False),
    'kv_valid_noncausal': (300, 300, 256, 128, False, 2, 2, 0.0, False),
    'key_mask': (512, 512, 256, 256, True, 2, 2, 0.0, True),
    'key_mask_ragged': (300, 300, 128, 128, True, 2, 2, 0.0, True),
    'dropout': (512, 512, 256, 256, True, 2, 2, 0.25, False),
    'dropout_cross': (256, 512, 256, 256, True, 2, 2, 0.25, False),
    'gqa': (512, 512, 256, 256, True, 4, 2, 0.0, False),
    'noncausal': (512, 512, 256, 256, False, 2, 2, 0.0, False),
}


@pytest.mark.parametrize('case', sorted(_SCHEDULE_CASES))
def test_tile_schedule_parity(case, monkeypatch):
    """Forward and all three gradients against _jnp_attention for every
    tile class in every kernel (sub-tiles of 128 so that small blocks
    still split)."""
    s_q, s_k, bq, bk, causal, h, h_kv, drop, masked = _SCHEDULE_CASES[case]
    monkeypatch.setattr(fa, '_SUB', 128)
    monkeypatch.setattr(fa, '_pick_blocks', lambda a, b: (bq, bk))
    ks = jax.random.split(jax.random.PRNGKey(len(case) + s_q), 4)
    q = jax.random.normal(ks[0], (1, s_q, h, 64))
    k = jax.random.normal(ks[1], (1, s_k, h_kv, 64))
    v = jax.random.normal(ks[2], (1, s_k, h_kv, 64))
    tgt = jax.random.normal(ks[3], q.shape)
    mask = (jnp.arange(s_k)[None, :] < s_k - 37) if masked else None
    seed = 11 if drop else None

    def loss(attn):
        return lambda q, k, v: jnp.sum((attn(q, k, v) - tgt) ** 2)

    got, g_got = jax.value_and_grad(loss(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=causal, mask=mask, dropout_rate=drop,
        dropout_seed=seed)), (0, 1, 2))(q, k, v)
    want, g_want = jax.value_and_grad(loss(lambda q, k, v: fa._jnp_attention(
        q, k, v, causal, mask, drop_rate=drop, seed=seed)),
        (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    for a, b, name in zip(g_got, g_want, 'qkv'):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   rtol=5e-4, err_msg=f'd{name} ({case})')


def _brute_plan(s_q, s_k, bq, bk, q_off, kv_valid, causal, plan):
    """The plan's numbers found by LOOKING at the dense keep-mask."""
    r, c = np.arange(s_q)[:, None], np.arange(s_k)[None, :]
    under = (c <= r + q_off) if causal else np.ones((s_q, s_k), bool)
    valid = np.broadcast_to(c < (s_k if kv_valid is None else kv_valid),
                            (s_q, s_k))

    def look(m):
        return 'all' if m.all() else ('mixed' if m.any() else 'none')

    out = {}
    for variant in ('fwd', 'dq', 'dkv'):
        tr, tc = plan[variant]['sub']
        n = dict.fromkeys(('interior', 'diagonal', 'padded', 'sub_skipped',
                           'sub_masked', 'sub_free'), 0)
        for qb in range(s_q // bq):
            for kb in range(s_k // bk):
                rows = slice(qb * bq, (qb + 1) * bq)
                cols = slice(kb * bk, (kb + 1) * bk)
                diag, cut = look(under[rows, cols]), look(valid[rows, cols])
                if diag == 'none' or cut == 'none':
                    continue                       # never visited
                if variant == 'dkv':               # a cut block adds a row
                    cls = ('diagonal' if diag == 'mixed' else
                           'padded' if cut == 'mixed' else 'interior')
                else:                            # a cut tile: masked whole
                    cls = ('padded' if cut == 'mixed' else
                           'diagonal' if diag == 'mixed' else 'interior')
                n[cls] += 1
                if cls != 'diagonal':
                    continue
                tile = under[rows, cols]
                for i in range(bq // tr):
                    for j in range(bk // tc):
                        seen = look(tile[i * tr:(i + 1) * tr,
                                         j * tc:(j + 1) * tc])
                        n[{'all': 'sub_free', 'none': 'sub_skipped',
                           'mixed': 'sub_masked'}[seen]] += 1
        out[variant] = dict(n, sub=(tr, tc))
    return out


@pytest.mark.parametrize('sub', [128, 256])
def test_causal_tile_plan_matches_dense_mask(sub, monkeypatch):
    monkeypatch.setattr(fa, '_SUB', sub)
    grid = [
        # s_q, s_k, bq, bk, q_off, kv_valid, causal
        (1024, 1024, 512, 512, 0, None, True),
        (2048, 2048, 512, 512, 0, None, True),
        (1024, 1024, 256, 256, 0, None, True),
        (1024, 1024, 512, 128, 0, None, True),
        (1152, 1152, 128, 128, 0, None, True),
        (1152, 1152, 128, 128, 0, 1100, True),
        (512, 1024, 512, 512, 512, None, True),
        (512, 1024, 256, 256, 512, None, True),
        (256, 512, 256, 128, 220, 420, True),
        (256, 640, 256, 128, 384, None, True),
        (384, 384, 128, 128, 37, None, True),
        (1024, 1024, 512, 512, 0, None, False),
        (384, 384, 128, 128, 0, 300, False),
        (512, 512, 256, 256, 0, 500, True),
    ]
    for s_q, s_k, bq, bk, q_off, kv_valid, causal in grid:
        plan = fa.causal_tile_plan(s_q, s_k, bq, bk, q_off, kv_valid, causal)
        assert plan == _brute_plan(s_q, s_k, bq, bk, q_off, kv_valid, causal,
                                   plan), (s_q, s_k, bq, bk, q_off, kv_valid)
        assert plan['fwd'] == plan['dq']
    # the two benchmark cells, per head (ISSUE 25)
    for s, interior, diagonal in ((1024, 1, 2), (2048, 6, 4)):
        for n in fa.causal_tile_plan(s, s, 512, 512).values():
            assert (n['interior'], n['diagonal'], n['padded']) == (
                interior, diagonal, 0)
            assert n['sub'] == (sub, sub) and n['sub_skipped'] > 0


def test_tile_counters_hold_the_plan_after_one_traced_call():
    from paddle_tpu import observability as obs

    def value(name, kernel):
        c = obs.find(name, {'kernel': kernel})
        return c.value if c is not None else 0

    names = ('flash.tiles_unmasked_total', 'flash.tiles_masked_total')
    kernels = {'flash_fwd': 'fwd', 'flash_bwd_dq': 'dq',
               'flash_bwd_dkv': 'dkv'}
    before = {(n, k): value(n, k) for n in names for k in kernels}
    q, k, v = _rand_qkv(jax.random.PRNGKey(5), 2, 1024, 3, 64)
    step = jax.jit(jax.grad(
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True).sum(),
        (0, 1, 2)))
    step.lower(q, k, v)                            # traced, never run
    plan = fa.causal_tile_plan(1024, 1024, *fa._pick_blocks(1024, 1024))
    rows = 2 * 3                                   # B x H attention rows
    for kernel, variant in kernels.items():
        n = plan[variant]
        got = [value(name, kernel) - before[name, kernel] for name in names]
        assert got == [rows * n['interior'],
                       rows * (n['diagonal'] + n['padded'])], kernel
