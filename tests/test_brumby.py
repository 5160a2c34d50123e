"""The power-retention family (models/brumby.py) served by an engine that
holds no page pool: small sizes on the CPU against
benchmark/reference/brumby.py, whose retention is the attention form (no
feature map, no state, no chunks), and the kernel through the Pallas
interpreter."""
import importlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.models import brumby, family
from paddle_tpu.ops import retention as ret
from paddle_tpu.serving import GenerationEngine

pytestmark = pytest.mark.gen
fa = importlib.import_module('paddle_tpu.ops.flash_attention')
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-5      # float32 program against the float32 'highest' reference


def _reference():
    """benchmark/reference/brumby.py: plain jnp, imports nothing of the
    program."""
    path = os.path.join(REPO, 'benchmark', 'reference', 'brumby.py')
    spec = importlib.util.spec_from_file_location('ref_brumby', path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()


def tiny_shape(**over):
    shape = dict(vocab_size=96, hidden_size=64, intermediate_size=96,
                 num_hidden_layers=2, num_attention_heads=4,
                 num_key_value_heads=2, head_dim=16, rms_norm_eps=1e-6,
                 rope_theta=1000000.0, max_position_embeddings=64)
    shape.update(over)
    return shape


def program_config(shape, **over):
    own = dict(shape, dtype='float32', param_dtype='float32')
    own.update(over)
    return brumby.BrumbyConfig(**own)


def weights(shape, seed=3, edit=None):
    """(the reference's float32 weights, the same as the family scans
    them: ``edit(layer's leaves)`` changes what the PROGRAM gets)."""
    layers = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        ref.init_params(shape, jax.random.PRNGKey(seed)))
    edit = edit or (lambda lp: lp)
    return layers, dict(
        {k: layers[k] for k in ('embed', 'head', 'norm_f')},
        layers=family.stack_layers(
            shape['num_hidden_layers'],
            lambda l: edit(dict(layers['layers'][l]))))


def prompts_of(lens, vocab=96, seed=1):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, size=n).astype(np.int32) for n in lens]


@pytest.fixture(autouse=True)
def chunks_of_8_rows(monkeypatch):
    """A prefill's chunk cut to the tiny prompts' size, so that they cross
    chunks as the real ones cross chunks of 128."""
    monkeypatch.setattr(ret, 'CHUNK', 8)


@pytest.fixture
def interpret():
    fa.set_interpret(True)
    yield
    fa.set_interpret(False)


def _serve(shape, engine_kw, prompts, max_new, edit=None, config=None,
           **submit_kw):
    layers, stacked = weights(shape, edit=edit)
    with GenerationEngine(stacked, config or program_config(shape),
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


ENGINE = dict(num_slots=3, page_size=8, prefill_width=40)
PROMPTS = (5, 21, 33, 12, 1, 8, 16)


# ---- served rows against the plain reference -------------------------------

def test_engine_serves_the_reference_rows_through_the_state(traces_for):
    """Logits, not tokens: prompts of one row, of a whole chunk and of two
    among others, each padded to the narrowest of the widths (``valid``
    short of it), 20 tokens each through the slots' state, seven requests
    on three slots: the later ones are admitted while the first decode,
    into rows that others left full."""
    shape = tiny_shape()
    prompts = prompts_of(PROMPTS)
    layers, served, stats = _serve(shape, ENGINE, prompts, 20)
    _held_to_reference(shape, layers, prompts, served, 20, TOL)
    assert stats['evictions'] == 0 and len(stats['prefill_widths']) >= 2
    assert stats['traces'] == traces_for(stats['prefill_widths'],
                                         map(len, prompts))
    assert stats['num_pages'] == stats['free_pages'] == 0


def test_engine_serves_the_reference_rows_through_the_kernel(interpret,
                                                            monkeypatch):
    """The same through the Pallas interpreter at a head of 128 (D = 8,256
    features in 65 lane tiles), two query heads a KV head."""
    shape = tiny_shape(hidden_size=128, num_attention_heads=2,
                      num_key_value_heads=1, head_dim=128,
                      max_position_embeddings=128)
    prompts = prompts_of((40, 9))
    monkeypatch.setattr(ret, 'CHUNK', 16)
    layers, served, _ = _serve(
        shape, dict(num_slots=2, page_size=16, prefill_width=64), prompts, 4)
    _held_to_reference(shape, layers, prompts, served, 4, 1e-4)


def test_the_whole_forward_is_the_references():
    shape = tiny_shape()
    layers, stacked = weights(shape)
    tokens = jnp.asarray(np.stack(prompts_of((21, 21))))
    np.testing.assert_allclose(
        brumby.forward(stacked, tokens, program_config(shape)),
        ref.forward(layers, tokens, shape), atol=5e-6, rtol=0)


# ---- each left-out term fails the same comparison --------------------------

def _gate_closed(lp):
    """The gate's columns and bias gone: l = logsigmoid(0) on every head."""
    return dict(lp, qkvg=lp['qkvg'].at[:, -2:].set(0.0),
                gate_bias=jnp.zeros_like(lp['gate_bias']))


def _unit_gain(name):
    return lambda lp: dict(lp, **{name: jnp.ones_like(lp[name])})


def _patched(monkeypatch, what):
    if what == 'normaliser_left_out':
        # the sum of the weights gives way to a constant, in a step and in
        # a chunk
        monkeypatch.setattr(ret, 'EPS', 1e30)
    elif what == 'root_two_left_out':
        phi = ret.phi
        monkeypatch.setattr(ret, 'phi', lambda x: phi(x).at[
            ..., 1:, :].divide(np.sqrt(2.0)))
    elif what == 'rotary_left_out':
        monkeypatch.setattr(brumby, '_rope', lambda x, positions, theta: x)
    elif what == 'state_not_reset_at_a_prefill':
        monkeypatch.setattr(
            brumby, '_write_prefill', lambda pool, left, slots: {
                name: plane.at[:, slots].add(left[name])
                for name, plane in pool.items()})


@pytest.mark.parametrize('what,edit', [
    ('gate_left_out', _gate_closed),
    ('q_norm_left_out', _unit_gain('q_norm')),
    ('k_norm_left_out', _unit_gain('k_norm')),
    ('normaliser_left_out', None),
    ('root_two_left_out', None),
    ('rotary_left_out', None),
    ('state_not_reset_at_a_prefill', None),
    ('bfloat16_state', None),
])
def test_a_term_left_out_fails_the_same_comparison(monkeypatch, what, edit):
    """The comparison the engine passes is tight enough to see each term of
    the family: a program given weights with the term's leaf neutral (the
    reference keeping its own), or patched to leave the term out, or
    holding its state in bfloat16, is refused by it."""
    shape = tiny_shape()
    # one slot: the second and third requests fill a row the first left
    prompts = prompts_of((5, 21, 12))
    kw = dict(num_slots=1, page_size=8, prefill_width=24)
    config = program_config(shape, **(
        {'state_dtype': 'bfloat16'} if what == 'bfloat16_state' else {}))
    _patched(monkeypatch, what)
    layers, served, _ = _serve(shape, kw, prompts, 8, edit=edit,
                               config=config)
    with pytest.raises(AssertionError):
        _held_to_reference(shape, layers, prompts, served, 8, TOL)


# ---- slots, admission, the step in flight ----------------------------------

def test_a_slot_filled_a_second_time_serves_what_a_fresh_engine_serves():
    """One slot, four requests one after another: each starts from a zero
    state in a row the last occupant left full, and serves exactly what an
    engine that never held another serves."""
    shape = tiny_shape()
    prompts = prompts_of((9, 1, 2, 17))
    kw = dict(num_slots=1, page_size=8, prefill_width=24)
    _, again, _ = _serve(shape, kw, prompts, 10)
    for p, (toks, rows) in zip(prompts, again):
        _, fresh, _ = _serve(shape, kw, [p], 10)
        assert toks == fresh[0][0]
        np.testing.assert_array_equal(np.stack(rows), np.stack(fresh[0][1]))


def test_a_request_admitted_while_others_decode_serves_what_it_serves_alone():
    shape = tiny_shape()
    _, stacked = weights(shape)
    first, late = prompts_of((11, 6))
    kw = dict(num_slots=2, page_size=8, prefill_width=24)
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


def test_an_engine_with_no_page_kind_counts_no_paged_step():
    """``paged_steps_walked`` / ``paged_steps_dense`` (PR 43) count the
    paged decode kernel's grid: this family calls no such kernel."""
    shape = tiny_shape()
    _, _, stats = _serve(shape, ENGINE, prompts_of((5, 12)), 6)
    assert stats['steps'] > 0
    assert stats['paged_steps_walked'] == stats['paged_steps_dense'] == 0


@pytest.mark.parametrize('kw,lens', [
    (ENGINE, PROMPTS),
    (dict(num_slots=1, page_size=8, prefill_width=24), (9, 3, 17)),
], ids=['refilled', 'alone'])
def test_one_step_ahead_serves_what_reading_first_serves(kw, lens,
                                                         read_first):
    """A step updates EVERY slot's row, so the step in flight when a slot
    changes hands decays the old occupant's state once more: the new
    occupant's prefill, queued behind it, overwrites the row before the
    first step that reads it. Same tokens and rows as a loop that reads
    each step before it dispatches the next."""
    shape = tiny_shape()
    prompts = prompts_of(lens)
    _, got, stats = _serve(shape, kw, prompts, 14, seed=7)
    with read_first():
        _, want, base = _serve(shape, kw, prompts, 14, seed=7)
    assert base['steps_overlapped'] == 0 < stats['steps_overlapped']
    for (toks, rows), (want_toks, want_rows) in zip(got, want):
        assert toks == want_toks
        np.testing.assert_allclose(np.stack(rows), np.stack(want_rows),
                                   atol=1e-6, rtol=0)


# ---- the family's own surface ----------------------------------------------

def test_the_one_kind_is_a_row_a_slot_and_the_pool_is_its_two_planes():
    cfg = program_config(tiny_shape())
    (kind,) = brumby.page_kinds(cfg)
    assert (kind.name, kind.per_slot, kind.planes) == (
        'state', True, ('s', 'z'))
    pool = brumby.init_pool(cfg, {'state': 5}, 8)
    dp = ret.features(16)[1]
    assert {k: (v.shape, v.dtype) for k, v in pool.items()} == {
        's': ((2, 5, 2, 16, dp), jnp.float32),
        'z': ((2, 5, 2, dp), jnp.float32)}
    fam = family.family_of(cfg)
    assert fam.name == 'brumby' and fam.tail_prefill is False


def test_the_published_state_is_the_bytes_the_file_states():
    """8 layers x 8 heads x (128 + 1) x 8,320 x 4 B = 274.8 MB a slot."""
    cfg = brumby.BrumbyConfig(num_hidden_layers=8)
    pool = jax.eval_shape(lambda: brumby.init_pool(cfg, {'state': 16}, 128))
    per_slot = sum(int(np.prod(a.shape)) * 4 for a in pool.values()) // 16
    assert per_slot == 8 * 8 * 129 * 8320 * 4 == 274_759_680


def test_the_published_defaults_are_the_catalog_rows():
    path = '/opt/skills/guides/model-configs/architectures.jsonl'
    if not os.path.isfile(path):
        pytest.skip('no catalog beside the guides here')
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r['name'] == 'Brumby-14B-Base')
    cfg = brumby.BrumbyConfig()
    for key, value in row['config'].items():
        if hasattr(cfg, key):
            assert getattr(cfg, key) == value, key
    assert (cfg.group, cfg.widths) == (5, (5120, 1024, 1024, 8))


def test_the_gates_bias_draws_a_memory_of_16_to_4096_tokens():
    cfg = program_config(tiny_shape(num_key_value_heads=4))
    bias = jnp.concatenate([brumby.init_layer(
        cfg, jax.random.PRNGKey(i))['gate_bias'] for i in range(16)])
    memory = 1.0 / (1.0 - jax.nn.sigmoid(bias))
    assert 16.0 <= float(jnp.min(memory)) < 64.0
    assert 1024.0 < float(jnp.max(memory)) <= 4096.0 * 1.001


def test_the_counters_count_what_a_call_served():
    def read():
        out = {}
        for name, phase in (('state_rows', 'prefill'),
                            ('state_rows', 'decode'), ('chunks', 'prefill')):
            got = obs.find(f'retention.{name}_total', {'phase': phase})
            out[name, phase] = got.value if got else 0
        return out
    shape = tiny_shape()
    before = read()
    _serve(shape, dict(num_slots=2, page_size=8, prefill_width=24),
           prompts_of((5, 12)), 4)
    after = read()
    delta = {k: after[k] - before[k] for k in after}
    # prompts of 5 and 12 rows in widths of 8 and 16: 1 + 2 chunks of 8
    assert delta['state_rows', 'prefill'] == 17
    assert delta['chunks', 'prefill'] == 3
    # a step counts every slot, busy or idle: 2 a step
    assert delta['state_rows', 'decode'] % 2 == 0
    assert delta['state_rows', 'decode'] >= 2 * 3


def test_an_engine_holds_matrices_in_the_compute_type_and_the_rest_float32():
    shape = tiny_shape()
    _, stacked = weights(shape)
    eng = GenerationEngine(stacked, program_config(shape, dtype='bfloat16'),
                           num_slots=1, page_size=8, autostart=False)
    try:
        held = eng._params
        for name in brumby.MATRICES:
            leaf = held[name] if name in held else held['layers'][name]
            assert leaf.dtype == jnp.bfloat16, name
        for name in ('norm_in', 'q_norm', 'k_norm', 'gate_bias'):
            assert held['layers'][name].dtype == jnp.float32, name
        assert eng._pool['s'].dtype == jnp.float32
    finally:
        eng.shutdown(drain=False)


@pytest.mark.parametrize('over,match', [
    (dict(num_key_value_heads=3), 'must divide'),
    (dict(head_dim=15), 'even'),
])
def test_a_shape_the_family_does_not_write_is_refused(over, match):
    with pytest.raises(ValueError, match=match):
        program_config(tiny_shape(**over))
