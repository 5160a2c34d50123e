"""The compressed-convolutional-attention / top-1 expert family
(models/zaya.py) and what it forced: a router that is a family's own and
hands ``parallel/routed_experts.py`` ``held_experts`` its answer, a row that meets no expert,
a per-slot kind (the convolutions' tail and the value's) beside ONE paged
kind in an attention layer itself, a stack scanned with the router's state
in the carry. Small sizes on the CPU against benchmark/reference/zaya.py,
and the kernels through the Pallas interpreter. The engine's contract is
tests/family_contract.py's, bound to this family's row of
tests/served_families.py; what stays here is the family's own."""
import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.models import family, zaya
from paddle_tpu.parallel import routed_experts as re_
from paddle_tpu.serving import GenerationEngine

from family_contract import Contract, borrow, served_of
from served_families import FAMILIES, REPO

pytestmark = pytest.mark.gen
row = FAMILIES['zaya']
served = served_of(row)
ref = row.ref
tiny_shape, program_config, weights = row.shape, row.config, row.weights
prompts_of = served.prompts
TOL = row.tol   # float32 program against the float32 'highest' reference
ENGINE = row.engine


class TestZayaContract(Contract):
    row = FAMILIES['zaya']


# ---- each left-out term fails the comparison the engine passes -------------

def _zeroed(name, at=None):
    """An edit of a layer's leaves: ``name`` (a path a.b) made zero."""
    def edit(lp):
        node, path = lp, name.split('.')
        for key in path[:-1]:
            node[key] = dict(node[key])
            node = node[key]
        leaf = node[path[-1]]
        node[path[-1]] = (jnp.zeros_like(leaf) if at is None
                          else leaf.at[at].set(0.0))
        return lp
    return edit


def _neutral_merge(lp):
    one = jnp.asarray([1.0, 0.0, 1.0, 0.0])[:, None]
    return dict(lp, merge_attn=jnp.broadcast_to(one, lp['merge_attn'].shape),
                merge_moe=jnp.broadcast_to(one, lp['merge_moe'].shape))


@pytest.mark.parametrize('what,edit,config', [
    ('value_shift_dropped', _zeroed('v2'), {}),
    ('router_state_not_carried', _zeroed('router.gamma'), {}),
    ('grouped_convolution_reaches_no_row_back', _zeroed('conv1', 0), {}),
    ('depthwise_convolution_reaches_no_row_back', _zeroed('conv0', 0), {}),
    ('temperature_left_out', _zeroed('temp'), {}),
    ('balancing_bias_left_out', _zeroed('router.bias'), {}),
    ('merges_neutral', _neutral_merge, {}),
    ('bfloat16_router', None, {'router_dtype': 'bfloat16'}),
])
def test_a_term_left_out_or_a_lower_precision_fails_the_same_comparison(
        what, edit, config):
    """The comparison the engine passes is tight enough to see each term of
    the family and each precision the configuration states: a program
    given weights with the term's leaf zeroed (the reference keeping its
    own), or run with its router or everything in bfloat16, is refused by
    it."""
    shape = tiny_shape()
    # weights with a leaf changed run the standard engine's executables;
    # another configuration traces its own
    run = served.serve(
        ENGINE, prompts_of((5, 21, 2)), 8,
        stacked=weights(shape, edit=edit)[1] if edit else None,
        config=program_config(shape, **config),
        like=served.standard.engine if edit else None)
    with pytest.raises(AssertionError):
        served.held_to_reference(run, 8, TOL)


# ---- the router again, on the program's own rows ---------------------------

def _routers_again(shape, layers, notes):
    """``ref.router_again`` of every layer over a request's noted rows
    [rows, layers * note] -> {'state', 'weight', 'choice'} [layers, rows]."""
    widths = zaya.note_widths(program_config(shape))
    n_layers = shape['num_hidden_layers']
    notes = np.stack(notes).reshape(len(notes), n_layers, -1)
    at = np.cumsum([0] + list(widths.values()))
    part = {k: notes[..., lo:hi] for k, lo, hi in zip(widths, at, at[1:])}
    got = []
    for l in range(n_layers):
        above = (part['router_state'][:, l - 1] if l
                 else np.zeros_like(part['router_state'][:, 0]))
        got.append(ref.router_again(layers['layers'][l]['router'], {
            'input': part['router_input'][:, l], 'above': above,
            'state': part['router_state'][:, l],
            'probability': part['probability'][:, l, 0],
            'choice': part['choice'][:, l, 0].astype(np.int32)}, shape))
    return {k: np.stack([np.asarray(g[k]) for g in got]) for k in got[0]}


@pytest.mark.parametrize('router,told', [('float32', False),
                                         ('bfloat16', True)])
def test_the_routers_notes_tell_its_arithmetic_from_the_streams(router, told):
    """A request that asked for its logits also gets, a row and a layer,
    what the router was given and what it answered (``zaya.NOTE``): the
    prompt's last row from the prefill (``valid`` short of the width) and
    every decoded row, aligned with the tokens. The reference's router run
    again on those rows agrees with a float32 router to float32's rounding
    whatever the rest of the program computes in, and a bfloat16 router
    stands three orders of magnitude away."""
    shape = tiny_shape()
    layers = served.weights[0]
    if told:
        run = served.serve(ENGINE, prompts_of((5, 21, 2)), 8,
                           config=program_config(shape, router_dtype=router))
    else:
        run = served.standard       # seven requests, 20 rows each
        eng = GenerationEngine(served.stacked, served.config,
                               autostart=False, **ENGINE)
        borrow(eng, run.engine)
        with eng:
            other = eng.submit(run.prompts[0], max_new_tokens=8)
            other.result(timeout=600)
        with pytest.raises(ValueError):
            other.row_notes()
    for toks, notes in zip(run.tokens, run.notes):
        assert len(notes) == len(toks) >= 8
        got = _routers_again(shape, layers, notes)
        assert got['state'].shape == (shape['num_hidden_layers'], len(toks))
        if told:
            assert np.median(got['state']) > 1e-3
            assert np.median(got['weight']) > 1e-3
        else:
            assert np.max(got['state']) < 1e-5
            assert np.max(got['weight']) < 1e-5
            assert not got['choice'].any()


# ---- the tails across the prefill / decode boundary ------------------------

def _prefill(shape, stacked, prompt, width, slot=0, pool=None, slots=2):
    """One padded prefill into slot ``slot`` -> the pool."""
    cfg = program_config(shape)
    pool = pool or zaya.init_pool(cfg, {'kv': 40, 'tail': slots}, 4)
    tokens = np.zeros((1, width), np.int32)
    tokens[0, :len(prompt)] = prompt
    table = np.zeros((1, 16), np.int32)
    table[0, :-(-width // 4)] = 1 + slot * 10 + np.arange(-(-width // 4))
    cache = dict(pool, valid=jnp.asarray([len(prompt)], jnp.int32),
                 page_table={'kv': jnp.asarray(table),
                             'tail': jnp.asarray([slot], jnp.int32)})
    _, cache = zaya.forward_with_cache(
        stacked, jnp.asarray(tokens), cache, jnp.zeros((1,), jnp.int32), cfg,
        last_only=True)
    return {k: cache[k] for k in pool}


@pytest.mark.parametrize('valid', [1, 2, 3, 5, 8])
def test_a_padded_prefill_leaves_the_tails_of_its_last_real_rows(valid):
    """Rows ``valid - 2``, ``valid - 1`` of ``[q~ | k~]`` and row ``valid -
    1`` of ``u W_v2``, zeros where they lie before row 0, whatever the
    width; the first layer's are the reference's own projections."""
    shape = tiny_shape()
    layers, stacked = served.weights
    prompt = prompts_of((valid,))[0]
    narrow = _prefill(shape, stacked, prompt, 8)
    wide = _prefill(shape, stacked, prompt, 16)
    for name in ('conv', 'vtail'):
        np.testing.assert_allclose(narrow[name], wide[name], atol=1e-6)
        assert not np.any(np.asarray(narrow[name][:, 1]))   # the other slot
    lp = layers['layers'][0]
    x = ref.embed(layers, jnp.asarray(prompt)[None], shape)
    u = ref.rms(x, lp['norm_attn'], shape['rms_norm_eps'])
    qk = np.concatenate([u @ lp['q'], u @ lp['k']], axis=-1)[0]
    want = np.zeros((2, qk.shape[-1]), np.float32)
    want[2 - min(valid, 2):] = qk[max(valid - 2, 0):]
    np.testing.assert_allclose(
        np.asarray(narrow['conv'][0, 0]).reshape(2, -1), want, atol=1e-5)
    np.testing.assert_allclose(narrow['vtail'][0, 0], (u @ lp['v2'])[0, -1],
                               atol=1e-5)


# ---- the router: its carry, its skip choice, the shares --------------------

def _half_inputs(shape, rows=24, seed=5):
    layers, _ = served.weights if shape == tiny_shape() else weights(shape)
    lp = layers['layers'][1]
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    u = jax.random.normal(k1, (rows, shape['hidden_size']), jnp.float32)
    r = jax.random.normal(k2, (rows, shape['router_hidden_size']),
                          jnp.float32)
    return lp, u, r


def _program_half(shape, lp, u, r_above, held=None, row_ok=None):
    """models/zaya.py's expert half over the reference's leaves, the share
    ``held`` of them."""
    cfg = program_config(shape, held=held)
    first, count = cfg.held
    experts = {k: a[first:first + count] for k, a in lp['experts'].items()}
    ok = jnp.ones((u.shape[0],), bool) if row_ok is None else row_ok
    return zaya._expert_half(lp, experts, u, r_above, ok, jnp.int32(0),
                             jnp.arange(1, dtype=jnp.int32), cfg)[:3]


def test_a_layers_choice_follows_the_state_of_the_layer_above():
    """Layer l's choice changes when layer l - 1's state does, by the
    state alone (same input), and not when ``gamma`` is zero; the state
    handed on is the one AFTER the averaging."""
    shape = tiny_shape(num_experts=8)
    lp, u, r = _half_inputs(shape, rows=64)
    cfg = program_config(shape)
    chosen, _, state = zaya._router(lp['router'], u, r, cfg)
    other, _, _ = zaya._router(lp['router'], u, -r, cfg)
    assert 8 <= int(jnp.sum(chosen != other))
    np.testing.assert_allclose(
        state, u @ lp['router']['down'] + lp['router']['gamma'] * r,
        atol=1e-5)
    still = dict(lp['router'], gamma=jnp.zeros(()))
    np.testing.assert_array_equal(zaya._router(still, u, r, cfg)[0],
                                  zaya._router(still, u, -r, cfg)[0])
    want, p, _ = ref.router(lp['router'], u, r, shape)
    np.testing.assert_array_equal(chosen, want)


def test_the_stack_hands_each_layer_the_router_state_of_the_one_above():
    """Through the whole forward: the second layer's router state is its
    own projection plus ``gamma`` times the first layer's (the scan's
    carry), which a forward with the first layer's ``W_down`` zeroed shows
    by what it changes in layers below."""
    shape = tiny_shape()
    tokens = jnp.asarray(np.stack(prompts_of((12,))))
    layers, stacked = served.weights
    _, cut = weights(shape, edit=_zeroed('router.gamma'))
    cfg = program_config(shape)
    with_carry = zaya.forward(stacked, tokens, cfg)
    np.testing.assert_allclose(with_carry, ref.forward(layers, tokens, shape),
                               atol=5e-6)
    assert float(jnp.max(jnp.abs(with_carry - zaya.forward(cut, tokens,
                                                           cfg)))) > 1e-3


def test_a_skip_row_meets_no_expert_and_gets_its_scaled_input():
    """With the balancing bias pushing every row to the skip choice the
    half is ``p_skip u`` on every real row, no row is sorted to an expert,
    every real row is counted as skipped, and a padding row gets nothing."""
    shape = tiny_shape()
    lp, u, r = _half_inputs(shape)
    e = shape['num_experts']
    lp = dict(lp, router=dict(lp['router'], bias=jnp.zeros((e + 1,)).at[
        e].set(10.0)))
    row_ok = jnp.arange(u.shape[0]) < 20
    y, _, counts = _program_half(shape, lp, u, r, row_ok=row_ok)
    _, p, _ = ref.router(lp['router'], u, r, shape)
    np.testing.assert_allclose(y[:20], (p[:, None] * u)[:20], atol=1e-6)
    assert not np.any(np.asarray(y[20:]))
    vals = dict(zip(re_.COUNTS + zaya.OWN_COUNTS[:1], np.asarray(counts)))
    assert vals['rows_offered'] == 20 == vals['rows_skipped']
    assert vals['rows_held'] == 0 == vals['experts_touched']


def test_some_rows_skip_and_the_rest_meet_one_expert():
    shape = tiny_shape()
    lp, u, r = _half_inputs(shape, rows=96)
    y, state, counts = _program_half(shape, lp, u, r)
    want, want_state = ref.expert_half(lp, u, r, shape)
    np.testing.assert_allclose(y, want, atol=1e-5)
    np.testing.assert_allclose(state, want_state, atol=1e-5)
    vals = dict(zip(re_.COUNTS + zaya.OWN_COUNTS[:1], np.asarray(counts)))
    assert 0 < vals['rows_skipped'] < 96
    assert vals['rows_held'] + vals['rows_skipped'] == vals['rows_offered']


@pytest.mark.parametrize('shares', [[(0, 2), (2, 2)], [(0, 1), (1, 3)],
                                    [(0, 4)]])
def test_the_shares_add_up_to_the_uncut_half(shares):
    """The parts of one expert half that the shares give, the skip term
    (which every share computes alike) counted once, sum to the uncut
    reference's half; so do the reference's own shares."""
    shape = tiny_shape()
    lp, u, r = _half_inputs(shape, rows=96)
    whole, _ = ref.expert_half(lp, u, r, shape)
    chosen, p, _ = ref.router(lp['router'], u, r, shape)
    skip = jnp.where(chosen == shape['num_experts'], p, 0.0)[:, None] * u
    assert float(jnp.max(jnp.abs(skip))) > 0
    parts = ref_parts = 0.0
    for first, count in shares:
        parts = parts + _program_half(shape, lp, u, r, (first, count))[0] \
            - skip
        cut = dict(lp, experts={k: a[first:first + count]
                                for k, a in lp['experts'].items()})
        ref_parts = ref_parts + ref.expert_half(cut, u, r, dict(
            shape, num_experts=count, held_first=first,
            router_width=shape['num_experts']))[0] - skip
    np.testing.assert_allclose(parts + skip, whole, atol=1e-5)
    np.testing.assert_allclose(ref_parts + skip, whole, atol=1e-5)


# ---- held_experts on its own ------------------------------------------------

def test_a_layer_without_a_shared_expert_and_an_offset_into_a_stack():
    """``held_experts`` with no 'shared' leaf gives the held experts'
    part alone, and ``at`` reads layer ``at``'s experts out of a stack of
    layers' as the layer's own leaves give them."""
    h = jax.random.normal(jax.random.PRNGKey(0), (24, 32))
    stack = {k: jax.random.normal(jax.random.PRNGKey(i), (3 * 4,) + s)
             for i, (k, s) in enumerate((('gate', (32, 16)), ('up', (32, 16)),
                                         ('down', (16, 32))))}
    chosen = jax.random.randint(jax.random.PRNGKey(9), (24, 1), 0, 5)
    w = jnp.full((24, 1), 0.5)
    ok = jnp.ones((24,), bool)
    for layer in range(3):
        own = {k: a[4 * layer:4 * layer + 4] for k, a in stack.items()}
        want, counts = re_.held_experts({'experts': own}, h, ok, chosen, w,
                                        held=(0, 4))
        got, _ = re_.held_experts({'experts': stack}, h, ok, chosen, w,
                                  held=(0, 4), at=jnp.int32(layer))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        e = int(chosen[0, 0])
        row = (0.5 * re_.swiglu({k: a[e] for k, a in own.items()}, h[:1],
                                h.dtype) if e < 4 else jnp.zeros((1, 32)))
        np.testing.assert_allclose(got[:1], row, rtol=1e-5, atol=1e-4)
    assert int(counts[1]) == int(jnp.sum(chosen < 4))     # 4: meets none


# ---- the per-slot kind in the engine ---------------------------------------

def test_the_tails_are_a_row_a_slot_beside_one_paged_kind():
    cfg, stacked = served.config, served.stacked
    kinds = family.family_of(cfg).page_kinds(cfg)
    assert [(k.name, k.per_slot) for k in kinds] == [('kv', False),
                                                     ('tail', True)]
    eng = GenerationEngine(stacked, cfg, autostart=False, **ENGINE)
    borrow(eng, served.standard.engine)     # the same geometry
    c = (4 + 2) * 16
    assert eng._pool['k'].shape == (3, eng.num_pages, 2, 4, 16)
    assert eng._pool['conv'].shape == (3, 3, 2 * c)
    assert eng._pool['vtail'].shape == (3, 3, 16)
    per_slot = 3 * (2 * c + 16) * 4
    assert eng.stats()['state_bytes_per_slot'] == per_slot
    fut = eng.submit(np.arange(5, dtype=np.int32), max_new_tokens=40)
    eng.start()
    next(fut.stream(timeout=300))
    busy = eng.stats()
    page = 2 * 3 * 2 * 4 * 16 * 4       # K and V: 3 layers, 2 heads of 16
    assert busy['state_bytes'] == per_slot
    assert busy['page_bytes'] % page == 0 and busy['page_bytes'] >= 2 * page
    fut.result(timeout=300)
    assert eng.stats()['state_bytes'] == 0 == eng.stats()['page_bytes']
    eng.shutdown()


def test_the_counters_count_what_a_call_served():
    """A prefill counts its real rows, a decode step every slot, a layer
    at a time; a row is held or skipped; the attended keys are the rows a
    step's slots hold."""
    def read():
        get = lambda n, p: getattr(obs.find(n, {'phase': p}), 'value', 0)
        return [get(f'moe.{n}_total', p) for p in ('prefill', 'decode')
                for n in ('rows_offered', 'rows_held', 'rows_skipped',
                          'expert_calls')]
    shape = tiny_shape()
    delta, stats = served.counted(lambda: dict(enumerate(read())))
    p_off, p_held, p_skip, p_calls, d_off, d_held, d_skip, d_calls = (
        delta[i] for i in range(8))
    layers, experts = shape['num_hidden_layers'], shape['num_experts']
    assert p_off == (5 + 11) * layers == p_held + p_skip
    # a step counts every slot, busy or idle: 3 a step
    assert d_off == 3 * stats['steps'] * layers == d_held + d_skip
    assert p_calls == 2 * layers * experts
    assert d_calls == stats['steps'] * layers * experts
    assert obs.find('attn.keys_attended_total', {'kind': 'kv'}).value > 0


# ---- the configuration -----------------------------------------------------

def test_the_published_rotary_base_and_the_derived_widths():
    """Beside the keys the contract reads off the cell's file: the rotary
    base lies a level down in it, and the benchmark's configuration
    changes depth and context alone."""
    path = os.path.join(REPO, 'benchmark', 'configs', row.cell + '.json')
    with open(path) as f:
        doc = json.load(f)
    cfg = zaya.ZayaConfig()
    assert cfg.rope_theta == doc['rope_parameters']['hybrid']['rope_theta']
    assert doc['reduced'] == ['num_hidden_layers', 'max_position_embeddings']
    assert cfg.held == (0, 16) and cfg.conv_dim == 1280


def test_an_engine_holds_the_router_float32_unless_told_otherwise():
    """Beside the contract's case (matrices in the compute type, the rest
    float32): the router's own matrices follow ``router_dtype``."""
    stacked = served.stacked
    cfg = program_config(tiny_shape(), dtype='bfloat16')
    lay = zaya.serve_params(stacked, cfg)['layers']
    assert {a.dtype for a in lay['experts'].values()} == {jnp.dtype(
        'bfloat16')}
    assert {a.dtype for a in lay['router'].values()} == {jnp.dtype('float32')}
    lower = zaya.serve_params(stacked, dataclasses.replace(
        cfg, router_dtype='bfloat16'))['layers']['router']
    assert lower['down'].dtype == lower['w3'].dtype == jnp.bfloat16
    assert lower['gamma'].dtype == lower['bias'].dtype == jnp.float32


def test_the_stack_is_the_layers_packed_and_stacked():
    shape = tiny_shape()
    layers, stacked = served.weights
    lay = stacked['layers']
    for l, lp in enumerate(layers['layers']):
        np.testing.assert_array_equal(lay['qkv'][l], np.concatenate(
            [lp['q'], lp['k'], lp['v1'], lp['v2']], axis=1))
        np.testing.assert_array_equal(lay['experts']['down'][l],
                                      lp['experts']['down'])
        assert float(lay['router']['gamma'][l]) == float(
            lp['router']['gamma'])
    own = zaya.init_params(program_config(shape), jax.random.PRNGKey(0))
    assert (jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), own)
            == jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), stacked))
