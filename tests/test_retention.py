"""Power retention of degree 2 (ops/retention.py): the feature map, the
chunked form of a prefill and the one-token update of a pool's rows, each
against the attention form written out here (no feature, no state), at small
sizes on the CPU and the kernel through the Pallas interpreter."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import retention as ret

pytestmark = pytest.mark.gen
fa = importlib.import_module('paddle_tpu.ops.flash_attention')


@pytest.fixture
def interpret():
    fa.set_interpret(True)
    yield
    fa.set_interpret(False)


def attention_form(q, k, v, l, eps=ret.EPS):
    """q [B, T, H, G, d], k, v [B, T, H, d], l [B, T, H] -> y: every row
    against every row before it."""
    t = q.shape[1]
    big_l = jnp.moveaxis(jnp.cumsum(l, axis=1), 1, 2)           # [B, H, T]
    dots = jnp.einsum('bthad,bshd->bhats', q, k, precision='highest')
    seen = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    fade = jnp.exp(jnp.where(seen, big_l[..., :, None] - big_l[..., None, :],
                             -jnp.inf))
    w = dots * dots * fade[:, :, None]
    y = jnp.einsum('bhats,bshj->bthaj', w, v, precision='highest')
    return y / (jnp.moveaxis(jnp.sum(w, -1), 3, 1)[..., None] + eps)


def rows_of(seed, b=2, t=24, h=2, g=2, d=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, t, h, g, d))
    k = jax.random.normal(ks[1], (b, t, h, d))
    v = jax.random.normal(ks[2], (b, t, h, d))
    l = jax.nn.log_sigmoid(jax.random.normal(ks[3], (b, t, h)) + 2.0)
    return q, k, v, l


def token_at_a_time(q, k, v, l, rows, spare=1):
    """The recurrence through ``state_update``, a token a call, in a pool
    with ``spare`` rows no call names."""
    b, t, h, g, d = q.shape
    dp = ret.features(d)[1]
    s = jnp.full((b + spare, h, d, dp), 7.0).at[rows].set(0.0)
    z = jnp.full((b + spare, h, dp), 7.0).at[rows].set(0.0)
    ys = []
    for i in range(t):
        y, s, z = ret.state_update(
            s, z, rows, jnp.exp(l[:, i]), ret.phi(k[:, i]),
            ret.phi(q[:, i]), v[:, i])
        ys.append(y)
    return jnp.stack(ys, axis=1), s, z


@pytest.mark.parametrize('d', [2, 16, 128])
def test_phi_is_the_symmetric_second_power(d):
    """phi(q) . phi(k) == (q . k)^2, D = d (d + 1) / 2 features and d / 2
    zeros in whole tiles of d."""
    q, k = jax.random.normal(jax.random.PRNGKey(d), (2, 5, d))
    pq, pk = ret.phi(q), ret.phi(k)
    assert pq.shape == (5, (d // 2 + 1) * d)
    np.testing.assert_allclose(jnp.sum(pq * pk, axis=-1),
                               jnp.sum(q * k, axis=-1) ** 2, rtol=2e-4)
    n, padded = ret.features(d)
    assert (n, padded) == (d * (d + 1) // 2, pq.shape[1])
    assert int(jnp.sum(pq[0] != 0)) == n
    assert ret.features(128) == (8256, 8320)


def test_phi_without_its_root_two_is_not():
    q, k = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 16))
    cut = lambda x: ret.phi(x).at[..., 16:].divide(np.sqrt(2.0))
    assert not np.allclose(jnp.sum(cut(q) * cut(k), axis=-1),
                           jnp.sum(q * k, axis=-1) ** 2, rtol=1e-2)


@pytest.mark.parametrize('chunk', [24, 8, 4, 1])
def test_the_chunked_form_is_the_attention_form(chunk):
    """One chunk (the state it reads is zeros), chunk boundaries inside the
    sequence, and a chunk a row (the state alone carries everything)."""
    q, k, v, l = rows_of(1)
    y, _, _ = ret.chunked_retention(q, k, v, l, jnp.float32, chunk)
    np.testing.assert_allclose(y, attention_form(q, k, v, l), atol=2e-5)


def test_the_recurrence_is_the_attention_form_and_leaves_other_rows():
    q, k, v, l = rows_of(2)
    rows = jnp.asarray([2, 0])          # row 1 is nobody's
    y, s, z = token_at_a_time(q, k, v, l, rows)
    # a first row's weight (q.k)^2 can lie near 0: relative, then
    np.testing.assert_allclose(y, attention_form(q, k, v, l), atol=5e-5,
                               rtol=5e-3)
    assert float(jnp.min(s[1])) == float(jnp.max(z[1])) == 7.0
    _, s_c, z_c = ret.chunked_retention(q, k, v, l, jnp.float32, 8)
    np.testing.assert_allclose(s[rows], s_c, atol=2e-5)
    np.testing.assert_allclose(z[rows], z_c, atol=2e-5)


@pytest.mark.parametrize('valid', [1, 7, 8, 9, 21])
def test_padded_rows_leave_the_state_exactly_as_it_was(valid):
    """Rows past ``valid`` with l = 0 and k = 0: the state that comes back
    is the state after row ``valid - 1``, to the bit, at a chunk's boundary
    and inside a chunk."""
    q, k, v, l = rows_of(3)
    real = (jnp.arange(q.shape[1]) < valid)[None, :, None]
    lp, kp = jnp.where(real, l, 0.0), jnp.where(real[..., None], k, 0.0)
    y, s, z = ret.chunked_retention(q, kp, v, lp, jnp.float32, 8)
    pad = -valid % 8
    cut = lambda x: jnp.pad(x[:, :valid], ((0, 0), (0, pad)) + (
        (0, 0),) * (x.ndim - 2))
    y0, s0, z0 = ret.chunked_retention(cut(q), cut(kp), cut(v), cut(lp),
                                       jnp.float32, 8)
    np.testing.assert_array_equal(s, s0)
    np.testing.assert_array_equal(z, z0)
    np.testing.assert_allclose(y[:, :valid], attention_form(
        q, k, v, l)[:, :valid], atol=2e-5)


def test_bfloat16_operands_stay_near_and_the_state_stays_float32():
    q, k, v, l = rows_of(4)
    y, s, z = ret.chunked_retention(q, k, v, l, jnp.bfloat16, 8)
    assert y.dtype == s.dtype == z.dtype == jnp.float32
    want = attention_form(q, k, v, l)
    err = jnp.sum((y - want) ** 2) / jnp.sum(want ** 2)
    assert 1e-7 < float(err) < 1e-3


def _kernel_case(seed=5, b=3, h=2, g=5, rows=(3, 0, 2), pool=5):
    d = 128
    dp = ret.features(d)[1]
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    k = jax.random.normal(ks[0], (pool, h, d))
    # states that retention makes: sums of phi(k) v^T, so that z > 0 where
    # phi(q) looks
    s = jnp.einsum('rhj,rhd->rhjd', jax.random.normal(ks[1], (pool, h, d)),
                   ret.phi(k))
    z = ret.phi(k)
    return (s, z, jnp.asarray(rows, jnp.int32),
            jax.random.uniform(ks[2], (b, h), minval=0.5),
            ret.phi(jax.random.normal(ks[3], (b, h, d))),
            ret.phi(jax.random.normal(ks[4], (b, h, g, d))),
            jax.random.normal(ks[5], (b, h, d)))


def test_the_kernel_interpreted_is_the_jnp_form_and_leaves_unnamed_rows(
        interpret):
    """``retention_state_update`` at the published head (128, D = 8,256 in
    65 lane tiles), five query heads a state: the states it names updated
    in place, the others untouched to the bit."""
    args = _kernel_case()
    assert ret.state_update_available(args[0])
    y, s, z = jax.jit(ret.state_update)(*args)
    fa.set_interpret(False)
    assert not ret.state_update_available(args[0])
    y0, s0, z0 = ret.state_update(*args)
    np.testing.assert_allclose(y, y0, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(s, s0, rtol=1e-6, atol=1e-5)
    np.testing.assert_allclose(z, z0, rtol=1e-6, atol=1e-5)
    for row in (1, 4):
        np.testing.assert_array_equal(s[row], args[0][row])
        np.testing.assert_array_equal(z[row], args[1][row])


def test_the_kernel_is_not_taken_for_another_state_dtype_or_head(interpret):
    s = _kernel_case()[0]
    assert not ret.state_update_available(s.astype(jnp.bfloat16))
    assert not ret.state_update_available(jnp.zeros((2, 2, 16, 144)))


def test_a_bfloat16_pool_is_widened_updated_and_rounded_again():
    q, k, v, l = rows_of(6, t=1)
    dp = ret.features(16)[1]
    s = jnp.zeros((2, 2, 16, dp), jnp.bfloat16)
    z = jnp.zeros((2, 2, dp), jnp.bfloat16)
    y, s, z = ret.state_update(
        s, z, jnp.arange(2), jnp.exp(l[:, 0]), ret.phi(k[:, 0]),
        ret.phi(q[:, 0]), v[:, 0])
    assert s.dtype == z.dtype == jnp.bfloat16 and y.dtype == jnp.float32
    np.testing.assert_allclose(y, attention_form(q, k, v, l)[:, 0],
                               atol=1e-5)
