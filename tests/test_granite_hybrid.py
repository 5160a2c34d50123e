"""The state-space / attention hybrid family (models/granite_hybrid.py) and
what it forced: a kind of pool plane that is a row a SLOT (models/family.py,
serving/generation.py), the recurrence over a sequence and over one token
(ops/ssm.py), two KV heads of 64 sharing a 128-lane pool row. Small sizes
on the CPU: two periods of [mamba, mamba, attention, mamba] at hidden 64
through the jnp paths, and the kernels through the Pallas interpreter."""
import importlib
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.models import family
from paddle_tpu.models import granite_hybrid as gh
from paddle_tpu.ops import ssm
from paddle_tpu.serving import GenerationEngine

pytestmark = pytest.mark.gen
fa = importlib.import_module('paddle_tpu.ops.flash_attention')
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
M, A = gh.MAMBA, gh.ATTENTION


def _reference():
    """benchmark/reference/granite_hybrid.py: plain jnp, a token-by-token
    recurrence, imports nothing of the program."""
    path = os.path.join(REPO, 'benchmark', 'reference', 'granite_hybrid.py')
    spec = importlib.util.spec_from_file_location('ref_granite_hybrid', path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()


def tiny_shape(**over):
    shape = dict(
        vocab_size=96, hidden_size=64, shared_intermediate_size=96,
        num_hidden_layers=8, layer_types=[M, M, A, M] * 2,
        num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=4,
        mamba_d_head=32, mamba_d_state=16, mamba_d_conv=4, mamba_expand=2,
        mamba_n_groups=1, mamba_chunk_size=8, attention_multiplier=0.0625,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        logits_scaling=8.0, rms_norm_eps=1e-5, max_position_embeddings=64)
    shape.update(over)
    return shape


def kernel_shape():
    """Heads of 64 over pages of 128 rows: what the kernels take."""
    return tiny_shape(hidden_size=128, num_attention_heads=2,
                      num_key_value_heads=2, mamba_d_head=64,
                      max_position_embeddings=512)


def program_config(shape, **over):
    own = {k: v for k, v in shape.items()
           if k in gh.GraniteHybridConfig.__dataclass_fields__}
    own.update(dtype='float32', param_dtype='float32')
    own.update(over)
    return gh.GraniteHybridConfig(**own)


def weights(shape, seed=3):
    """(the reference's float32 weights, the same as the family scans
    them)."""
    layers = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        ref.init_params(shape, jax.random.PRNGKey(seed)))
    cfg = program_config(shape)
    return layers, {
        'embed': layers['embed'], 'norm_f': layers['norm_f'],
        'periods': gh.stack_periods(cfg, lambda l: layers['layers'][l])}


def prompts_of(lens, vocab=96, seed=1):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, size=n).astype(np.int32) for n in lens]


@pytest.fixture
def interpret():
    fa.set_interpret(True)
    yield
    fa.set_interpret(False)


def _serve(shape, engine_kw, prompts, max_new, params=None, **submit_kw):
    layers, stacked = weights(shape)
    with GenerationEngine(params or stacked, program_config(shape),
                          **engine_kw) as eng:
        futs = [eng.submit(p, max_new_tokens=max_new, want_logits=True,
                           **submit_kw) for p in prompts]
        served = [(f.result(timeout=600), f.logits()) for f in futs]
        stats = eng.stats()
    return layers, served, stats


def _held_to_reference(shape, layers, prompts, served, max_new, tol):
    for p, (toks, rows) in zip(prompts, served):
        seq = np.concatenate([p, np.asarray(toks[:-1], np.int32)])
        want = np.asarray(ref.forward(layers, jnp.asarray(seq)[None],
                                      shape)[0])[len(p) - 1:]
        assert len(toks) == max_new == len(rows)
        np.testing.assert_allclose(np.stack(rows), want, atol=tol, rtol=0)
        assert toks == [int(np.argmax(r)) for r in rows]


# ---- served rows against the plain reference -------------------------------

def test_engine_serves_the_reference_rows_through_state_and_pages(
        traces_for):
    """The jnp paths: prompts of 1, 2 and 3 rows (a convolution's tail
    that reaches before row 0) among longer ones, 20 tokens each through
    the slots' state rows and the pages, seven requests on three slots: the
    later ones are admitted while the first decode, into slots and pages
    that others left."""
    shape = tiny_shape()
    prompts = prompts_of((5, 21, 33, 12, 1, 2, 3))
    layers, served, stats = _serve(
        shape, dict(num_slots=3, page_size=4, prefill_width=40), prompts, 20)
    _held_to_reference(shape, layers, prompts, served, 20, 2e-5)
    assert stats['evictions'] == 0
    assert stats['traces'] == traces_for(stats['prefill_widths'],
                                         map(len, prompts)) == 1 + 4
    assert stats['free_pages'] == stats['num_pages'] - 1    # the trash page


def test_engine_serves_the_reference_rows_through_the_kernels(interpret):
    """The same through the Pallas interpreter: the flash forward in the
    prefills (three bodies: 128, 256 and 384 rows), the paged kernel over
    rows that hold two KV heads of 64 side by side, and the state update's
    kernel in the steps."""
    shape = kernel_shape()
    assert gh._pack(program_config(shape)) == 2
    prompts = prompts_of((300, 140, 380, 100))
    layers, served, _ = _serve(
        shape, dict(num_slots=2, page_size=128, prefill_width=384), prompts,
        6)
    _held_to_reference(shape, layers, prompts, served, 6, 5e-5)


def test_the_whole_forward_is_the_references():
    shape = tiny_shape()
    layers, stacked = weights(shape)
    tokens = jnp.asarray(np.stack(prompts_of((21, 21))))
    np.testing.assert_allclose(
        gh.forward(stacked, tokens, program_config(shape)),
        ref.forward(layers, tokens, shape), atol=2e-6, rtol=0)


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
    shape = tiny_shape()
    cfg = program_config(shape)
    _, stacked = weights(shape)
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


# ---- slots: filled again, filled while others decode, evicted --------------

def test_a_slot_filled_a_second_time_serves_what_a_fresh_engine_serves():
    """One slot, three requests one after another: each starts from a zero
    state and a zero tail in a row the last occupant left full, and serves
    exactly what an engine that never held another serves."""
    shape = tiny_shape()
    prompts = prompts_of((9, 3, 17))
    kw = dict(num_slots=1, page_size=4, prefill_width=24)
    _, again, _ = _serve(shape, kw, prompts, 10)
    for p, (toks, rows) in zip(prompts, again):
        _, fresh, _ = _serve(shape, kw, [p], 10)
        assert toks == fresh[0][0]
        np.testing.assert_array_equal(np.stack(rows), np.stack(fresh[0][1]))


def test_a_request_admitted_while_others_decode_serves_what_it_serves_alone():
    shape = tiny_shape()
    layers, stacked = weights(shape)
    first, late = prompts_of((11, 6))
    kw = dict(num_slots=2, page_size=4, prefill_width=24)
    _, alone, _ = _serve(shape, kw, [late], 12)
    with GenerationEngine(stacked, program_config(shape), **kw) as eng:
        running = eng.submit(first, max_new_tokens=30)
        stream = running.stream(timeout=300)
        for _ in range(5):                  # the first is five tokens deep
            next(stream)
        fut = eng.submit(late, max_new_tokens=12, want_logits=True)
        toks, rows = fut.result(timeout=300), fut.logits()
        assert not running.done()           # and still decoding
        assert len(running.result(timeout=300)) == 30
    assert toks == alone[0][0]
    np.testing.assert_allclose(np.stack(rows), np.stack(alone[0][1]),
                               atol=1e-6, rtol=0)


def test_an_evicted_request_regenerates_its_tokens():
    """A pool too small for three growing sequences: the engine evicts,
    the evicted restart from row 0 (their state is rebuilt with their
    pages) and every request's tokens are an unconstrained engine's."""
    shape = tiny_shape()
    prompts = prompts_of((7, 6, 5))
    wide = dict(num_slots=3, page_size=4, prefill_width=16)
    _, want, _ = _serve(shape, wide, prompts, 18)
    _, got, stats = _serve(shape, dict(wide, num_pages=11), prompts, 18)
    assert stats['evictions'] >= 1
    assert [t for t, _ in got] == [t for t, _ in want]


@pytest.mark.parametrize('kw,lens,note', [
    # seven requests on three slots: a slot is filled again while a step
    # computed for its last occupant is still in flight
    (dict(num_slots=3, page_size=4, prefill_width=40),
     (5, 21, 33, 12, 1, 2, 3), 'refilled'),
    # one slot: every request starts in the row the last one left full
    (dict(num_slots=1, page_size=4, prefill_width=24), (9, 3, 17), 'alone'),
    # a pool too small: slots evicted with a step in flight start again
    (dict(num_slots=3, page_size=4, prefill_width=16, num_pages=11),
     (7, 6, 5), 'evicted'),
], ids=lambda x: x if isinstance(x, str) else None)
def test_one_step_ahead_serves_what_reading_first_serves(
        kw, lens, note, read_first, traces_for):
    """The decode loop dispatches step N+1 before it reads step N (PR 36).
    A step updates EVERY slot's state row, so the step in flight when a
    slot changes hands writes the old occupant's row once more: the new
    occupant's prefill, queued behind it, overwrites state and tail before
    the first step that reads them. Same tokens and the same rows, to the
    last bit, as a loop that reads each step before it dispatches the
    next."""
    shape = tiny_shape()
    prompts = prompts_of(lens)
    n_new = 18 if note == 'evicted' else 14
    _, got, stats = _serve(shape, kw, prompts, n_new, seed=7)
    with read_first():
        _, want, base = _serve(shape, kw, prompts, n_new, seed=7)
    assert base['steps_overlapped'] == 0 < stats['steps_overlapped']
    assert stats['traces'] == base['traces'] == traces_for(
        stats['prefill_widths'], lens)
    assert (stats['evictions'] >= 1) is (note == 'evicted')
    for (toks, rows), (want_toks, want_rows) in zip(got, want):
        assert toks == want_toks
        np.testing.assert_array_equal(np.stack(rows), np.stack(want_rows))


# ---- the per-slot kind in the engine ---------------------------------------

def test_the_per_slot_kind_gets_no_pages_and_is_counted_as_state():
    shape = tiny_shape()
    cfg = program_config(shape)
    _, stacked = weights(shape)
    kinds = family.family_of(cfg).page_kinds(cfg)
    assert [(k.name, k.per_slot) for k in kinds] == [('kv', False),
                                                     ('state', True)]
    eng = GenerationEngine(stacked, cfg, num_slots=2, page_size=4,
                           prefill_width=16, autostart=False)
    # pages, an allocator and a table for the paged kind alone
    assert [k.name for k in eng._kinds] == ['kv']
    assert [k.name for k in eng._slot_kinds] == ['state']
    assert list(eng._allocs) == ['kv'] and eng._num_pages == {'kv': 33}
    assert eng.num_pages == 33 and eng._c_released == {}
    # its planes have a row a slot, and a call is told which slots
    assert eng._pool['ssm'].shape == (6, 2, 16, 1, 128)
    assert eng._pool['conv'].shape == (6, 2, 3 * (128 + 32))
    tables = eng._tables(2, slots=np.asarray([1, 0], np.int32))
    assert tables['kv'].shape == (2, 16) and list(tables['state']) == [1, 0]
    assert list(eng._tables(1)['state']) == [0]         # what warmup lowers
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
    assert done['free_pages'] == 32
    eng.shutdown()


def test_the_family_declines_a_prefix_cache():
    shape = tiny_shape()
    _, stacked = weights(shape)
    with pytest.raises(ValueError, match='no prefix cache'):
        GenerationEngine(stacked, program_config(shape), num_slots=2,
                         page_size=4, prefix_cache=True, autostart=False)


def test_num_pages_names_the_paged_kinds_alone():
    shape = tiny_shape()
    _, stacked = weights(shape)
    with pytest.raises(ValueError, match='num_pages names'):
        GenerationEngine(stacked, program_config(shape), num_slots=2,
                         page_size=4, num_pages={'kv': 9, 'state': 2},
                         autostart=False)
    eng = GenerationEngine(stacked, program_config(shape), num_slots=2,
                           page_size=4, num_pages={'kv': 9}, autostart=False)
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
    shape = tiny_shape()
    before = read()
    _, _, stats = _serve(shape, dict(num_slots=2, page_size=4,
                                     prefill_width=24),
                         prompts_of((5, 11)), 4)
    rows, chunks, decoded = (a - b for a, b in zip(read(), before))
    assert rows == 5 + 11
    assert chunks == 1 + 2      # bodies of 8 and 12 rows in chunks of 8
    assert decoded == 2 * stats['steps']    # both slots, busy or idle


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


@pytest.mark.parametrize('over,match', [
    (dict(layer_types=[M, 'window'] * 4), 'layer_types'),
    (dict(num_key_value_heads=3), 'num_key_value_heads'),
    (dict(mamba_n_groups=2), 'one group'),
    (dict(mamba_n_heads=3), 'mamba_expand'),
    (dict(hidden_size=48, mamba_n_heads=3, num_attention_heads=2,
          num_key_value_heads=2), '128')])
def test_a_shape_the_family_does_not_write_is_refused(over, match):
    with pytest.raises(ValueError, match=match):
        program_config(tiny_shape(**over))


def test_an_engine_holds_matrices_in_the_compute_type_and_scalars_float32():
    shape = tiny_shape()
    cfg = program_config(shape, dtype='bfloat16')
    _, stacked = weights(shape)
    held = gh.serve_params(stacked, cfg)
    mamba, attn = held['periods'][0], held['periods'][2]
    for name in ('in_proj', 'out_proj', 'mlp_in', 'mlp_out'):
        assert mamba[name].dtype == jnp.bfloat16
    for name in ('a_log', 'd', 'dt_bias', 'conv_w', 'conv_b', 'norm_gate',
                 'norm_in', 'norm_mlp'):
        assert mamba[name].dtype == jnp.float32
    assert {attn[n].dtype for n in 'qkvo'} == {jnp.dtype('bfloat16')}
    assert held['embed'].dtype == jnp.bfloat16
    assert held['norm_f'].dtype == jnp.float32
    # leaves already as wanted are handed back themselves
    again = gh.serve_params(held, cfg)
    assert again['embed'] is held['embed']
    assert again['periods'][0]['a_log'] is mamba['a_log']


def _family_case(name):
    from paddle_tpu.models import afmoe, gpt
    if name == 'gpt':
        cfg = gpt.GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                            num_heads=2, max_seq_len=32)
        return cfg, gpt.init_params(cfg, jax.random.PRNGKey(0))
    if name == 'afmoe':
        cfg = afmoe.AfmoeConfig(
            vocab_size=64, hidden_size=32, intermediate_size=64,
            moe_intermediate_size=16, num_hidden_layers=2,
            num_dense_layers=1, num_attention_heads=2,
            num_key_value_heads=1, head_dim=8, sliding_window=8,
            layer_types=('sliding_attention', 'full_attention'),
            num_experts=4, num_experts_per_tok=2,
            max_position_embeddings=32, dtype='float32',
            param_dtype='float32')
        return cfg, afmoe.init_params(cfg, jax.random.PRNGKey(0))
    shape = tiny_shape(max_position_embeddings=32)
    return program_config(shape), weights(shape)[1]


@pytest.mark.parametrize('name,paged,per_slot', [
    ('gpt', ['kv'], []), ('afmoe', ['full', 'window'], []),
    ('granite_hybrid', ['kv'], ['state'])])
def test_every_family_keeps_its_kinds_and_only_the_new_one_a_row_a_slot(
        name, paged, per_slot):
    """What ``PageKind.per_slot`` added changes nothing for a family that
    names no such kind: its engine has the allocators and tables it had,
    no state gauges, and ``state_bytes`` 0."""
    cfg, params = _family_case(name)
    eng = GenerationEngine(params, cfg, num_slots=2, page_size=8,
                           autostart=False)
    assert [k.name for k in eng._kinds] == paged == list(eng._allocs)
    assert [k.name for k in eng._slot_kinds] == per_slot
    assert (eng._g_bytes is not None) == bool(per_slot)
    tables = eng._tables(2)
    if name == 'gpt':
        assert tables.shape == (2, 4)
    else:
        assert sorted(tables) == sorted(paged + per_slot)
        assert all(tables[k].shape == (2, 4) for k in paged)
        assert all(tables[k].shape == (2,) for k in per_slot)
    stats = eng.stats()
    assert stats['state_bytes'] == 0
    assert (stats['state_bytes_per_slot'] > 0) == bool(per_slot)
    assert set(eng._unit_bytes) == set(paged + per_slot)
    assert all(v > 0 for v in eng._unit_bytes.values())
    eng.shutdown()
