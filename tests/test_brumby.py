"""The power-retention family (models/brumby.py) served by an engine that
holds no page pool: small sizes on the CPU against
benchmark/reference/brumby.py, whose retention is the attention form (no
feature map, no state, no chunks), and the kernel through the Pallas
interpreter. The engine's contract is tests/family_contract.py's, bound to
this family's row of tests/served_families.py; what stays here is the
family's own: each term of its arithmetic, its state's planes and bytes,
its counters."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.models import brumby, family
from paddle_tpu.ops import retention as ret

from family_contract import Contract, served_of
from served_families import FAMILIES

pytestmark = pytest.mark.gen
row = FAMILIES['brumby']
served = served_of(row)
ref = row.ref
tiny_shape, program_config = row.shape, row.config
TOL = row.tol   # float32 program against the float32 'highest' reference


class TestBrumbyContract(Contract):
    row = FAMILIES['brumby']


@pytest.fixture(autouse=True)
def chunks_of_8_rows():
    """A prefill's chunk cut to the tiny prompts' size (the row's
    ``patches``), so that they cross chunks as the real ones cross chunks
    of 128."""
    with row.patched():
        yield


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
    prompts = served.prompts((5, 21, 12))
    config = program_config(shape, **(
        {'state_dtype': 'bfloat16'} if what == 'bfloat16_state' else {}))
    _patched(monkeypatch, what)
    # weights with a leaf changed run the one-slot engine's executables; a
    # patched program or another configuration traces its own
    stacked = row.weights(shape, edit=edit)[1] if edit else None
    run = served.serve(served.one_slot_engine, prompts, 8, stacked=stacked,
                       config=config,
                       like=served.widths[0].engine if edit else None)
    with pytest.raises(AssertionError):
        served.held_to_reference(run, 8, TOL)


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
    # the state is float32 whatever the compute type
    low = brumby.init_pool(program_config(tiny_shape(), dtype='bfloat16'),
                           {'state': 5}, 8)
    assert low['s'].dtype == low['z'].dtype == jnp.float32


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
    delta, stats = served.counted(read, lens=(5, 12))
    # prompts of 5 and 12 rows in widths of 8 and 16: 1 + 2 chunks of 8
    assert delta['state_rows', 'prefill'] == 17
    assert delta['chunks', 'prefill'] == 3
    # a step counts every slot, busy or idle: 3 a step
    assert delta['state_rows', 'decode'] == 3 * stats['steps'] >= 3 * 3
