"""The state-space / attention hybrid family (models/granite_hybrid.py) and
what it forced: a kind of pool plane that is a row a SLOT (models/family.py,
serving/generation.py), the recurrence over a sequence and over one token
(ops/ssm.py), two KV heads of 64 sharing a 128-lane pool row. Small sizes
on the CPU: two periods of [mamba, mamba, attention, mamba] at hidden 64
through the jnp paths, and the kernels through the Pallas interpreter. The
engine's contract is tests/family_contract.py's, bound to this family's row
of tests/served_families.py; what stays here is the family's own."""
import importlib

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.models import family
from paddle_tpu.models import granite_hybrid as gh
from paddle_tpu.ops import ssm
from paddle_tpu.serving import GenerationEngine

from family_contract import Contract, borrow, served_of
from served_families import FAMILIES

pytestmark = pytest.mark.gen
fa = importlib.import_module('paddle_tpu.ops.flash_attention')
M, A = gh.MAMBA, gh.ATTENTION
row = FAMILIES['granite_hybrid']
served = served_of(row)
tiny_shape, program_config = row.shape, row.config
prompts_of = served.prompts
ENGINE = row.engine


class TestGraniteHybridContract(Contract):
    row = FAMILIES['granite_hybrid']


@pytest.fixture
def interpret():
    fa.set_interpret(True)
    yield
    fa.set_interpret(False)


# ---- the recurrence, twice -------------------------------------------------

def _sequential(x, dt, a, b, c):
    """S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T; y_t = S_t C_t, a row at a
    time in float64. -> (y [T, H, P], every S_t [T, H, P, N])."""
    x, dt, a, b, c = (np.asarray(v, np.float64) for v in (x, dt, a, b, c))
    t, h, p = x.shape
    state = np.zeros((h, p, b.shape[-1]))
    ys, states = [], []
    for i in range(t):
        state = (np.exp(dt[i] * a)[:, None, None] * state
                 + (dt[i][:, None] * x[i])[..., None] * b[i])
        ys.append(state @ c[i])
        states.append(state)
    return np.stack(ys), np.stack(states)


def _recurrence_inputs(t, h=4, p=32, n=16, seed=0):
    rng = np.random.RandomState(seed)
    return (rng.randn(t, h, p).astype(np.float32),
            np.exp(rng.uniform(np.log(1e-3), np.log(0.3), (t, h))).astype(
                np.float32),
            -rng.uniform(1.0, 16.0, (h,)).astype(np.float32),
            rng.randn(t, n).astype(np.float32),
            rng.randn(t, n).astype(np.float32))


@pytest.mark.parametrize('t,chunk,valid', [
    (8, 8, 8), (16, 8, 16), (24, 8, 13), (24, 8, 1), (24, 8, 2), (24, 8, 3),
    (24, 8, 8), (24, 8, 9), (32, 16, 17), (40, 8, 39), (12, 12, 5)])
def test_chunked_scan_is_the_sequential_recurrence_up_to_valid(t, chunk,
                                                               valid):
    """The chunked form over a padded width whose ``dt`` is 0 past
    ``valid``: the outputs of the real rows and the state it hands back
    are the sequential recurrence's after row ``valid - 1``, for lengths
    that end inside a chunk, at its edge, and in the first rows."""
    x, dt, a, b, c = _recurrence_inputs(t)
    want_y, want_s = _sequential(x[:valid], dt[:valid], a, b[:valid],
                                 c[:valid])
    dt = np.where(np.arange(t)[:, None] < valid, dt, 0.0).astype(np.float32)
    y, last = ssm.chunked_scan(
        jnp.asarray(x)[None], jnp.asarray(dt)[None], jnp.asarray(a),
        jnp.asarray(b)[None], jnp.asarray(c)[None], chunk, jnp.float32)
    np.testing.assert_allclose(y[0, :valid], want_y, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(ssm.from_lanes(last[0], 4), want_s[-1],
                               atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize('valid', [1, 2, 3, 4, 7, 16])
def test_the_convolution_hands_back_the_rows_before_valid(valid):
    """The tail is input rows ``valid - 3 .. valid - 1``, zeros where they
    lie before row 0, and a step from it is the sequence's next row."""
    rng = np.random.RandomState(valid)
    x = rng.randn(2, 16, 24).astype(np.float32)
    w, bias = rng.randn(4, 24).astype(np.float32), rng.randn(24).astype(
        np.float32)
    out, tail = ssm.causal_conv(jnp.asarray(x), jnp.asarray(w),
                                jnp.asarray(bias),
                                jnp.asarray([valid, 16], jnp.int32))
    want = np.zeros((3, 24), np.float32)
    have = x[0, max(0, valid - 3):valid]
    want[3 - len(have):] = have
    np.testing.assert_array_equal(tail[0], want)
    np.testing.assert_array_equal(tail[1], x[1, 13:])
    if valid < 16:
        nxt, rolled = ssm.conv_step(tail[:1], jnp.asarray(x[:1, valid]),
                                    jnp.asarray(w), jnp.asarray(bias))
        np.testing.assert_allclose(nxt[0], out[0, valid], atol=1e-5)
        np.testing.assert_array_equal(rolled[0, -1], x[0, valid])


@pytest.mark.parametrize('valid', [1, 2, 3, 5, 8, 11, 16])
def test_a_padded_prefill_leaves_the_state_and_tail_of_its_last_real_row(
        valid):
    """The family's prefill over a prompt padded to 16 rows writes to its
    slot what the same prompt unpadded writes: ``S_{valid-1}`` and the
    last three input rows before ``valid`` in every state-space layer, and
    the same last row's logits."""
    cfg, stacked = served.config, served.stacked
    prompt = prompts_of((16,))[0]

    def prefill(tokens, n_valid):
        cache = dict(
            gh.init_pool(cfg, {'kv': 9, 'state': 3}, 4),
            page_table={'kv': jnp.arange(1, 5, dtype=jnp.int32)[None],
                        'state': jnp.asarray([1], jnp.int32)},
            valid=jnp.asarray([n_valid], jnp.int32))
        return gh.forward_with_cache(
            stacked, jnp.asarray(tokens)[None], cache,
            jnp.zeros((1,), jnp.int32), cfg, last_only=True)
    padded = np.concatenate([prompt[:valid], np.zeros(16 - valid, np.int32)])
    (lg_pad, pad), (lg, exact) = prefill(padded, valid), prefill(
        prompt[:valid], valid)
    np.testing.assert_allclose(lg_pad, lg, atol=2e-6, rtol=0)
    for plane in ('ssm', 'conv'):
        np.testing.assert_allclose(pad[plane][:, 1], exact[plane][:, 1],
                                   atol=2e-6, rtol=0)
        assert not np.any(np.asarray(pad[plane])[:, [0, 2]])    # others'
    assert np.any(np.asarray(pad['ssm'][:, 1]))


@pytest.mark.parametrize('pool_dtype,order', [
    ('float32', (0, 1, 2)), ('float32', (2, 0, 1)), ('float32', (4, 1)),
    ('bfloat16', (0, 1, 2))])
def test_state_update_kernel_is_the_jnp_update_in_place(interpret,
                                                        pool_dtype, order):
    """One token for the pool rows named, in any order: the kernel
    (interpreted) and the jnp form agree, the rows not named keep what they
    held, and a bfloat16 pool (the control's) takes the jnp form."""
    rng = np.random.RandomState(0)
    pool = jnp.asarray(rng.randn(6, 16, 2, 128), pool_dtype)
    rows = jnp.asarray(order, jnp.int32)
    n = len(order)
    da = jnp.asarray(rng.uniform(0.2, 1.0, (n, 256)), jnp.float32)
    dtx = jnp.asarray(rng.randn(n, 256), jnp.float32)
    b, c = (jnp.asarray(rng.randn(n, 16), jnp.float32) for _ in range(2))
    assert ssm.state_update_available(pool) == (pool_dtype == 'float32')
    y, new = ssm.state_update(pool, rows, da, dtx, b, c)
    fa.set_interpret(False)
    y_ref, new_ref = ssm.state_update(pool, rows, da, dtx, b, c)
    np.testing.assert_allclose(y, y_ref, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(new, np.float32),
                               np.asarray(new_ref, np.float32), atol=1e-6)
    others = np.asarray([r for r in range(6) if r not in order])
    np.testing.assert_array_equal(np.asarray(new[others], np.float32),
                                  np.asarray(pool[others], np.float32))
    # against the recurrence itself, a head's [P, N] at a time
    s0 = np.asarray(ssm.from_lanes(pool[rows].astype(jnp.float32), 4))
    want = (np.asarray(da).reshape(n, 4, 64, 1) * s0
            + np.asarray(dtx).reshape(n, 4, 64, 1)
            * np.asarray(b)[:, None, None, :])
    np.testing.assert_allclose(     # a bfloat16 pool rounds s0 and the result
        ssm.from_lanes(new_ref[rows].astype(jnp.float32), 4), want,
        **(dict(atol=1e-2, rtol=1e-2) if pool_dtype == 'bfloat16'
           else dict(atol=1e-5)))


def test_the_pool_layout_round_trips():
    s = jnp.asarray(np.random.RandomState(0).randn(3, 4, 64, 16), jnp.float32)
    lanes = ssm.to_lanes(s)
    assert lanes.shape == (3, 16, 2, 128)
    assert float(lanes[1, 5, 1, 7]) == float(s[1, 2, 7, 5])   # c = 2*64 + 7
    np.testing.assert_array_equal(ssm.from_lanes(lanes, 4), s)


# ---- the per-slot kind in the engine ---------------------------------------

def test_the_per_slot_kind_gets_no_pages_and_is_counted_as_state():
    cfg, stacked = served.config, served.stacked
    kinds = family.family_of(cfg).page_kinds(cfg)
    assert [(k.name, k.per_slot) for k in kinds] == [('kv', False),
                                                     ('state', True)]
    eng = GenerationEngine(stacked, cfg, autostart=False, **ENGINE)
    borrow(eng, served.standard.engine)     # the same geometry
    # pages, an allocator and a table for the paged kind alone (the
    # contract's case); nothing to release: no window
    assert eng._num_pages == {'kv': 49}
    assert eng.num_pages == 49 and eng._c_released == {}
    # its planes have a row a slot
    assert eng._pool['ssm'].shape == (6, 3, 16, 1, 128)
    assert eng._pool['conv'].shape == (6, 3, 3 * (128 + 32))
    per_slot = 6 * (16 * 128 * 4 + 3 * 160 * 4)
    assert eng.stats()['state_bytes_per_slot'] == per_slot
    assert eng.stats()['state_bytes'] == 0 == eng.stats()['page_bytes']
    fut = eng.submit(np.arange(5, dtype=np.int32), max_new_tokens=40)
    eng.start()
    stream = fut.stream(timeout=300)
    next(stream)
    busy = eng.stats()
    page = 2 * 2 * 1 * 4 * 32 * 4       # K and V: 2 layers, 1 packed head
    assert busy['state_bytes'] == per_slot
    assert busy['page_bytes'] % page == 0 and busy['page_bytes'] >= 2 * page
    labels = eng.labels
    assert obs.find('kv.state_bytes_held', labels).value == per_slot
    assert obs.find('kv.page_bytes_held', labels).value == busy['page_bytes']
    assert obs.find('kv.pages_in_use', {**labels, 'kind': 'kv'}).value >= 2
    assert obs.find('kv.pages_in_use', {**labels, 'kind': 'state'}) is None
    fut.result(timeout=300)
    done = eng.stats()
    assert done['state_bytes'] == 0 == done['page_bytes']
    assert done['free_pages'] == 48
    eng.shutdown()


def test_num_pages_names_the_paged_kinds_alone():
    cfg, stacked = served.config, served.stacked
    with pytest.raises(ValueError, match='num_pages names'):
        GenerationEngine(stacked, cfg, num_slots=2, page_size=4,
                         num_pages={'kv': 9, 'state': 2}, autostart=False)
    eng = GenerationEngine(stacked, cfg, num_slots=2, page_size=4,
                           num_pages={'kv': 9}, autostart=False)
    assert eng._pool['k'].shape[1] == 9 and eng._pool['ssm'].shape[1] == 2
    eng.shutdown()


def test_the_counters_count_what_a_step_served():
    """A prefill counts its real rows and the chunks its scan ran (padding
    too), a decode step every slot: one state-space layer's worth."""
    def read():
        get = lambda n, p: getattr(obs.find(n, {'phase': p}), 'value', 0)
        return (get('ssm.state_rows_total', 'prefill'),
                get('ssm.scan_chunks_total', 'prefill'),
                get('ssm.state_rows_total', 'decode'))
    delta, stats = served.counted(lambda: dict(enumerate(read())))
    rows, chunks, decoded = (delta[i] for i in range(3))
    assert rows == 5 + 11
    assert chunks == 1 + 2      # bodies of 8 and 12 rows in chunks of 8
    assert decoded == 3 * stats['steps']    # every slot, busy or idle


# ---- the configuration -----------------------------------------------------

@pytest.mark.parametrize('types,period', [
    ([M, M, A, M] * 2, (M, M, A, M)),
    ([M] * 5 + [A] + [M] * 4, (M,) * 5 + (A,) + (M,) * 4),
    ([M, A] * 3, (M, A)), ([A] * 4, (A,)), ([M, M, A], (M, M, A))])
def test_the_scan_takes_the_shortest_period(types, period):
    cfg = program_config(tiny_shape(num_hidden_layers=len(types),
                                    layer_types=types))
    assert cfg.period == period


def test_the_published_defaults_are_the_catalog_rows():
    cfg = gh.GraniteHybridConfig()
    assert cfg.layers_of(A) == [5, 15, 25, 35] and len(cfg.layers_of(M)) == 36
    assert len(cfg.period) == 10 and cfg.period.index(A) == 5
    assert (cfg.d_inner, cfg.conv_dim, cfg.head_dim) == (4096, 4352, 64)
    assert gh._pack(cfg) == 2
    assert cfg.d_inner + cfg.conv_dim + cfg.mamba_n_heads == 8512
    # and the kernel-sized shape of the interpreter's run packs two too
    assert gh._pack(program_config(row.kernel()[0])) == 2
