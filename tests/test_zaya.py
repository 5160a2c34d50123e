"""The compressed-convolutional-attention / top-1 expert family
(models/zaya.py) and what it forced: a router that is a family's own and
hands ``parallel/routed_experts.py`` ``held_experts`` its answer, a row that meets no expert,
a per-slot kind (the convolutions' tail and the value's) beside ONE paged
kind in an attention layer itself, a stack scanned with the router's state
in the carry. Small sizes on the CPU against benchmark/reference/zaya.py,
and the kernels through the Pallas interpreter."""
import dataclasses
import importlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.models import afmoe, family, latent_moe, zaya
from paddle_tpu.ops.expert_grouped_matmul import expert_grouped_matmul
from paddle_tpu.parallel import routed_experts as re_
from paddle_tpu.serving import GenerationEngine

pytestmark = pytest.mark.gen
fa = importlib.import_module('paddle_tpu.ops.flash_attention')
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-5      # float32 program against the float32 'highest' reference


def _reference():
    """benchmark/reference/zaya.py: plain jnp, imports nothing of the
    program."""
    path = os.path.join(REPO, 'benchmark', 'reference', 'zaya.py')
    spec = importlib.util.spec_from_file_location('ref_zaya', path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()


def tiny_shape(**over):
    shape = dict(
        vocab_size=96, hidden_size=64, moe_intermediate_size=32,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, cca_time0=2, cca_time1=2, num_experts=4,
        num_experts_per_tok=1, router_hidden_size=32,
        partial_rotary_factor=0.5, rope_theta=5000000.0, rms_norm_eps=1e-5,
        max_position_embeddings=64)
    shape.update(over)
    return shape


def kernel_shape():
    """Heads of 128 over pages of 128 rows, widths of whole lanes: what the
    kernels take."""
    return tiny_shape(hidden_size=128, moe_intermediate_size=128,
                      num_hidden_layers=2, num_attention_heads=2,
                      head_dim=128, max_position_embeddings=512)


def program_config(shape, **over):
    own = {k: v for k, v in shape.items()
           if k in zaya.ZayaConfig.__dataclass_fields__}
    own.update(dtype='float32', param_dtype='float32')
    own.update(over)
    return zaya.ZayaConfig(**own)


def weights(shape, seed=3, edit=None):
    """(the reference's float32 weights, the same as the family scans
    them: ``edit(layer's leaves)`` changes what the PROGRAM gets)."""
    layers = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32),
        ref.init_params(shape, jax.random.PRNGKey(seed)))
    cfg = program_config(shape)
    edit = edit or (lambda lp: lp)
    return layers, {
        'embed': layers['embed'], 'norm_f': layers['norm_f'],
        'layers': zaya.stack_layers(
            cfg, lambda l: edit(dict(layers['layers'][l])))}


def prompts_of(lens, vocab=96, seed=1):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, size=n).astype(np.int32) for n in lens]


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


# ---- served rows against the plain reference -------------------------------

ENGINE = dict(num_slots=3, page_size=4, prefill_width=40)
PROMPTS = (5, 21, 33, 12, 1, 2, 3)


def test_engine_serves_the_reference_rows_through_tails_and_pages(
        traces_for):
    """Logits, not tokens: prompts of 1, 2 and 3 rows (a tail that reaches
    before row 0) among longer ones, each padded to the narrowest of four
    widths (``valid`` short of it), 20 tokens each through the slots' tails
    and the pages, seven requests on three slots: the later ones are
    admitted while the first decode, into slots and pages others left."""
    shape = tiny_shape()
    prompts = prompts_of(PROMPTS)
    layers, served, stats = _serve(shape, ENGINE, prompts, 20)
    _held_to_reference(shape, layers, prompts, served, 20, TOL)
    assert stats['evictions'] == 0
    assert len(stats['prefill_widths']) >= 2
    assert stats['traces'] == traces_for(stats['prefill_widths'],
                                         map(len, prompts)) == 1 + 4
    assert stats['free_pages'] == stats['num_pages'] - 1    # the trash page


def test_engine_serves_the_reference_rows_through_the_kernels(interpret):
    """The same through the Pallas interpreter: the flash forward in the
    prefills (two widths), the paged kernel over two KV heads of 128 and
    the grouped expert product with the layer as an offset into the
    experts' stack."""
    shape = kernel_shape()
    prompts = prompts_of((200, 140, 100))
    layers, served, stats = _serve(
        shape, dict(num_slots=2, page_size=128, prefill_width=256), prompts,
        5)
    assert len({next(w for w in stats['prefill_widths'] if w >= len(p))
                for p in prompts}) == 2
    _held_to_reference(shape, layers, prompts, served, 5, 1e-4)


def test_the_whole_forward_is_the_references():
    shape = tiny_shape()
    layers, stacked = weights(shape)
    tokens = jnp.asarray(np.stack(prompts_of((21, 21))))
    np.testing.assert_allclose(
        zaya.forward(stacked, tokens, program_config(shape)),
        ref.forward(layers, tokens, shape), atol=5e-6, rtol=0)


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
    prompts = prompts_of((5, 21, 2))
    layers, served, _ = _serve(
        shape, ENGINE, prompts, 8, edit=edit,
        config=program_config(shape, **config))
    with pytest.raises(AssertionError):
        _held_to_reference(shape, layers, prompts, served, 8, TOL)


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
    prompts = prompts_of((5, 21, 2))
    layers, stacked = weights(shape)
    with GenerationEngine(stacked, program_config(
            shape, router_dtype=router), **ENGINE) as eng:
        futs = [eng.submit(p, max_new_tokens=8, want_logits=True)
                for p in prompts]
        other = eng.submit(prompts[0], max_new_tokens=8)
        served = [(f.result(timeout=600), f.row_notes()) for f in futs]
        other.result(timeout=600)
    with pytest.raises(ValueError):
        other.row_notes()
    for toks, notes in served:
        assert len(notes) == len(toks) == 8
        got = _routers_again(shape, layers, notes)
        assert got['state'].shape == (shape['num_hidden_layers'], 8)
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
    layers, stacked = weights(shape)
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


def test_a_slot_filled_a_second_time_serves_what_a_fresh_engine_serves():
    """One slot, three requests one after another: each starts from zero
    tails in a row the last occupant left full (a prompt of 1 and of 2
    rows among them: their tails reach before row 0, where the last
    occupant's rows lie), and serves exactly what an engine that never held
    another serves."""
    shape = tiny_shape()
    prompts = prompts_of((9, 1, 2, 17))
    kw = dict(num_slots=1, page_size=4, prefill_width=24)
    _, again, _ = _serve(shape, kw, prompts, 10)
    for p, (toks, rows) in zip(prompts, again):
        _, fresh, _ = _serve(shape, kw, [p], 10)
        assert toks == fresh[0][0]
        np.testing.assert_array_equal(np.stack(rows), np.stack(fresh[0][1]))


def test_a_request_admitted_while_others_decode_serves_what_it_serves_alone():
    shape = tiny_shape()
    _, stacked = weights(shape)
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
    shape = tiny_shape()
    prompts = prompts_of((7, 6, 5))
    wide = dict(num_slots=3, page_size=4, prefill_width=16)
    _, want, _ = _serve(shape, wide, prompts, 18)
    _, got, stats = _serve(shape, dict(wide, num_pages=11), prompts, 18)
    assert stats['evictions'] >= 1
    assert [t for t, _ in got] == [t for t, _ in want]


@pytest.mark.parametrize('kw,lens,note', [
    (ENGINE, PROMPTS, 'refilled'),
    (dict(num_slots=1, page_size=4, prefill_width=24), (9, 3, 17), 'alone'),
    (dict(num_slots=3, page_size=4, prefill_width=16, num_pages=11),
     (7, 6, 5), 'evicted'),
], ids=lambda x: x if isinstance(x, str) else None)
def test_one_step_ahead_serves_what_reading_first_serves(
        kw, lens, note, read_first):
    """A step rewrites EVERY slot's tails, so the step in flight when a
    slot changes hands writes the old occupant's row once more: the new
    occupant's prefill, queued behind it, overwrites both tails before the
    first step that reads them. Same tokens as a loop that reads each step
    before it dispatches the next, and the same rows (to rounding: which
    rows share a step's expert tiles differs between the two orders)."""
    shape = tiny_shape()
    prompts = prompts_of(lens)
    n_new = 18 if note == 'evicted' else 14
    _, got, stats = _serve(shape, kw, prompts, n_new, seed=7)
    with read_first():
        _, want, base = _serve(shape, kw, prompts, n_new, seed=7)
    assert base['steps_overlapped'] == 0 < stats['steps_overlapped']
    assert (stats['evictions'] >= 1) is (note == 'evicted')
    for (toks, rows), (want_toks, want_rows) in zip(got, want):
        assert toks == want_toks
        np.testing.assert_allclose(np.stack(rows), np.stack(want_rows),
                                   atol=1e-6, rtol=0)


# ---- the router: its carry, its skip choice, the shares --------------------

def _half_inputs(shape, rows=24, seed=5):
    layers, _ = weights(shape)
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
    layers, stacked = weights(shape)
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


# ---- routed_experts after the split ----------------------------------------

def _accepted_layer(lp, h, row_ok, *, held, top_k, n_group, topk_group,
                    scale, normalise=True):
    """``parallel/routed_experts.routed_experts`` as PR 39 had it, router
    and layer in one: what the two accepted families' numbers were made
    by."""
    cdt = h.dtype
    t = h.shape[0]
    chosen, w = re_.route(h, lp['router'], lp['router_bias'], top_k=top_k,
                          n_group=n_group, topk_group=topk_group,
                          scale=scale, normalise=normalise)
    tm = re_.tile_rows(t * top_k)
    pl_ = re_.plan(chosen, row_ok, held, tm)
    rows = jnp.take(h, pl_['src'], axis=0)
    gmm = lambda x, wt: expert_grouped_matmul(
        x, wt.astype(cdt), pl_['tile_expert'], pl_['n_tiles'], tm=tm)
    ex = lp['experts']
    act = (jax.nn.silu(gmm(rows, ex['gate']).astype(jnp.float32))
           * gmm(rows, ex['up']).astype(jnp.float32)).astype(cdt)
    out = gmm(act, ex['down'])
    y = re_.swiglu(lp['shared'], h, cdt)
    m = out.shape[0]
    picked = jnp.take(out, jnp.minimum(pl_['dest'], m - 1), axis=0)
    w_held = jnp.where(pl_['is_held'], w, 0.0).astype(cdt)
    return y + jnp.einsum('tk,tkh->th', w_held, picked,
                          preferred_element_type=jnp.float32).astype(cdt)


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
@pytest.mark.parametrize('name', ['afmoe', 'latent_moe'])
def test_the_split_leaves_the_routed_families_their_bits(name, dtype,
                                                         monkeypatch):
    """``routed_experts`` is ``route`` and then ``held_experts``: for
    ``latent_moe`` and ``afmoe``, which call it as they did, the layer's
    output and the whole forward's are what the one function of PR 39
    gave, to the bit; and a family that hands ``held_experts`` the same
    router's answer itself gets the same."""
    if name == 'afmoe':
        mod, cfg = afmoe, afmoe.AfmoeConfig(
            vocab_size=128, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=3,
            num_dense_layers=1, num_attention_heads=4,
            num_key_value_heads=2, head_dim=16, sliding_window=8,
            num_experts=8, num_experts_per_tok=2, held=(2, 4),
            max_position_embeddings=64, dtype=dtype, param_dtype=dtype)
        kw = dict(held=cfg.held, top_k=2, n_group=1, topk_group=1,
                  scale=cfg.route_scale, normalise=cfg.route_norm)
    else:
        mod, cfg = latent_moe, latent_moe.LatentMoEConfig(
            vocab_size=128, hidden_size=64, intermediate_size=128,
            moe_intermediate_size=32, num_hidden_layers=3,
            first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=32,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, n_routed_experts=8, num_experts_per_tok=2,
            n_group=2, topk_group=1, held=(0, 4),
            max_position_embeddings=64, dtype=dtype, param_dtype=dtype)
        kw = dict(held=cfg.held, top_k=2, n_group=2, topk_group=1,
                  scale=cfg.routed_scaling_factor,
                  normalise=cfg.norm_topk_prob)
    params = mod.init_params(cfg, jax.random.PRNGKey(4))
    lp = params['layers'][-1]
    h = jax.random.normal(jax.random.PRNGKey(6), (40, 64)).astype(dtype)
    row_ok = jnp.arange(40) < 33
    got, _ = re_.routed_experts(lp, h, row_ok, **kw)
    np.testing.assert_array_equal(
        np.asarray(got, np.float32),
        np.asarray(_accepted_layer(lp, h, row_ok, **kw), np.float32))
    chosen, w = re_.route(h, lp['router'], lp['router_bias'],
                          **{k: v for k, v in kw.items() if k != 'held'})
    again, _ = re_.held_experts(lp, h, row_ok, chosen, w, held=kw['held'])
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(again, np.float32))
    tokens = jnp.asarray(np.stack(prompts_of((24, 24), vocab=128)))
    after = np.asarray(mod.forward(params, tokens, cfg), np.float32)
    monkeypatch.setattr(
        re_, 'routed_experts',
        lambda lp, h, row_ok, **kw: (_accepted_layer(lp, h, row_ok, **kw),
                                     jnp.zeros((5,), jnp.int32)))
    np.testing.assert_array_equal(
        after, np.asarray(mod.forward(params, tokens, cfg), np.float32))


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
    shape = tiny_shape()
    cfg = program_config(shape)
    _, stacked = weights(shape)
    kinds = family.family_of(cfg).page_kinds(cfg)
    assert [(k.name, k.per_slot) for k in kinds] == [('kv', False),
                                                     ('tail', True)]
    eng = GenerationEngine(stacked, cfg, num_slots=2, page_size=4,
                           prefill_width=16, autostart=False)
    assert [k.name for k in eng._kinds] == ['kv']
    assert [k.name for k in eng._slot_kinds] == ['tail']
    assert list(eng._allocs) == ['kv']
    c = (4 + 2) * 16
    assert eng._pool['k'].shape == (3, eng.num_pages, 2, 4, 16)
    assert eng._pool['conv'].shape == (3, 2, 2 * c)
    assert eng._pool['vtail'].shape == (3, 2, 16)
    tables = eng._tables(2, slots=np.asarray([1, 0], np.int32))
    assert tables['kv'].shape == (2, 16) and list(tables['tail']) == [1, 0]
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


def test_the_family_declines_a_prefix_cache():
    shape = tiny_shape()
    _, stacked = weights(shape)
    with pytest.raises(ValueError, match='no prefix cache'):
        GenerationEngine(stacked, program_config(shape), num_slots=2,
                         page_size=4, prefix_cache=True, autostart=False)


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
    before = read()
    _, _, stats = _serve(shape, dict(num_slots=2, page_size=4,
                                     prefill_width=24),
                         prompts_of((5, 11)), 4)
    p_off, p_held, p_skip, p_calls, d_off, d_held, d_skip, d_calls = (
        a - b for a, b in zip(read(), before))
    layers, experts = shape['num_hidden_layers'], shape['num_experts']
    assert p_off == (5 + 11) * layers == p_held + p_skip
    assert d_off == 2 * stats['steps'] * layers == d_held + d_skip
    assert p_calls == 2 * layers * experts
    assert d_calls == stats['steps'] * layers * experts
    assert obs.find('attn.keys_attended_total', {'kind': 'kv'}).value > 0


# ---- the configuration -----------------------------------------------------

def test_the_published_defaults_are_the_catalog_rows():
    """``ZayaConfig()`` is ZAYA1-8B as its config.json states it, and the
    benchmark's configuration changes depth and context alone."""
    path = os.path.join(REPO, 'benchmark', 'configs',
                        'zaya1-8b-pp2-serve.json')
    with open(path) as f:
        doc = json.load(f)
    cfg = zaya.ZayaConfig()
    same = ('vocab_size', 'hidden_size', 'moe_intermediate_size',
            'num_attention_heads', 'num_key_value_heads', 'head_dim',
            'cca_time0', 'cca_time1', 'num_experts', 'num_experts_per_tok',
            'router_hidden_size', 'partial_rotary_factor', 'rms_norm_eps')
    for key in same:
        assert getattr(cfg, key) == doc[key], key
    assert cfg.rope_theta == doc['rope_parameters']['hybrid']['rope_theta']
    assert doc['reduced'] == ['num_hidden_layers', 'max_position_embeddings']
    for key in doc['reduced']:
        assert getattr(cfg, key) == doc['published'][key] != doc[key]
    assert cfg.held == (0, 16) and cfg.conv_dim == 1280


@pytest.mark.parametrize('over,match', [
    (dict(held=(8, 9)), 'outside'),
    (dict(num_attention_heads=3), 'must divide'),
    (dict(cca_time1=3), 'what is written'),
    (dict(num_experts_per_tok=2), 'what is written'),
    (dict(num_key_value_heads=4), 'what is written'),
])
def test_a_shape_the_family_does_not_write_is_refused(over, match):
    with pytest.raises(ValueError, match=match):
        zaya.ZayaConfig(**over)


def test_an_engine_holds_matrices_in_the_compute_type_and_the_router_float32():
    shape = tiny_shape()
    _, stacked = weights(shape)
    cfg = program_config(shape, dtype='bfloat16')
    held = zaya.serve_params(stacked, cfg)
    lay = held['layers']
    for name in ('qkv', 'o', 'conv1'):
        assert lay[name].dtype == jnp.bfloat16, name
    assert {a.dtype for a in lay['experts'].values()} == {jnp.dtype(
        'bfloat16')}
    assert held['embed'].dtype == jnp.bfloat16
    small = ('norm_attn', 'norm_moe', 'merge_attn', 'merge_moe', 'conv0',
             'temp')
    assert {lay[n].dtype for n in small} == {jnp.dtype('float32')}
    assert {a.dtype for a in lay['router'].values()} == {jnp.dtype('float32')}
    lower = zaya.serve_params(stacked, dataclasses.replace(
        cfg, router_dtype='bfloat16'))['layers']['router']
    assert lower['down'].dtype == lower['w3'].dtype == jnp.bfloat16
    assert lower['gamma'].dtype == lower['bias'].dtype == jnp.float32


def test_the_stack_is_the_layers_packed_and_stacked():
    shape = tiny_shape()
    layers, stacked = weights(shape)
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
