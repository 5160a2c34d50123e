"""The engine hands out the logits a token was chosen from
(``submit(..., want_logits=True)`` -> ``GenerationFuture.logits()``), and the
future's listener is public (``subscribe``). One executable either way:
the tokens do not depend on who asked."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models import family as _family
from paddle_tpu.models import gpt, moe_gpt
from paddle_tpu.serving import GenerationEngine, sharded_generation_engine

pytestmark = pytest.mark.gen

BASE = dict(vocab_size=97, hidden_size=32, num_layers=2, num_heads=2,
            max_seq_len=64, remat=False, use_flash=False)
KW = dict(num_slots=3, page_size=8, prefill_width=24)
# variant -> (config overrides, engine keywords, mp)
VARIANTS = {
    'bf16': (dict(dtype='bfloat16'), {}, 1),
    'int8_wo': (dict(dtype='bfloat16'), dict(precision='int8_wo'), 1),
    'kv_cache_int8': (dict(dtype='bfloat16', kv_cache_int8=True), {}, 1),
    'prefix_cache': (dict(dtype='bfloat16'), dict(prefix_cache=True), 1),
    'mp2': (dict(dtype='float32'), {}, 2),
}
PROMPTS = [np.random.RandomState(s).randint(1, 97, size=n).astype(np.int32)
           for s, n in ((1, 5), (2, 17), (3, 11))]
NEW = 6
# the step, and the prefill at the two widths of KW (two pages of 8 rows at
# a time: 16 and 24) that PROMPTS' rows are padded to
TRACES = 1 + 2


def _engine(variant, **over):
    cfg_over, kw, mp = VARIANTS[variant]
    cfg = gpt.GPTConfig(**dict(BASE, **cfg_over))
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    kw = dict(KW, **kw, **over)
    if mp > 1:
        return sharded_generation_engine(params, cfg, mp=mp, **kw)
    return GenerationEngine(params, cfg, **kw)


def _serve(engine, want, seeds=(0, 1, 2)):
    futs = [engine.submit(p, max_new_tokens=NEW, seed=s, want_logits=want)
            for p, s in zip(PROMPTS, seeds)]
    return [f.result(timeout=300) for f in futs], futs


@pytest.mark.parametrize('variant', ['bf16', 'int8_wo', 'kv_cache_int8',
                                     'mp2'])
def test_each_rows_argmax_is_the_token_at_temperature_zero(variant):
    engine = _engine(variant)
    try:
        tokens, futs = _serve(engine, want=True)
        for toks, fut in zip(tokens, futs):
            rows = fut.logits()
            assert len(rows) == len(toks) == NEW
            for tok, row in zip(toks, rows):
                assert row.shape == (BASE['vocab_size'],)
                assert row.dtype == np.float32
                assert int(np.argmax(row)) == tok
            # widened once, when first asked for; this family notes nothing
            assert all(a is b for a, b in zip(rows, fut.logits()))
            assert fut.row_notes() == [None] * NEW
        assert engine.stats()['traces'] == TRACES
    finally:
        engine.shutdown(drain=False)


@pytest.mark.parametrize('variant', sorted(VARIANTS))
def test_tokens_do_not_depend_on_who_asked_for_logits(variant):
    engine = _engine(variant)
    try:
        plain, _ = _serve(engine, want=False)
        if variant == 'prefix_cache':
            # the same prompts and seeds again are full hits of the cache
            assert engine.stats()['prefix']['hits'] == 0
        asked, futs = _serve(engine, want=True)
        assert asked == plain
        assert all(len(f.logits()) == NEW for f in futs)
        if variant == 'prefix_cache':
            assert engine.stats()['prefix']['hits'] == len(PROMPTS)
        assert engine.stats()['traces'] == TRACES
    finally:
        engine.shutdown(drain=False)


def test_sampled_tokens_do_not_depend_on_who_asked_for_logits():
    engine = _engine('bf16', temperature=0.8, top_k=20)
    try:
        plain, _ = _serve(engine, want=False, seeds=(5, 6, 7))
        asked, futs = _serve(engine, want=True, seeds=(5, 6, 7))
        assert asked == plain
        # sampling happened on the device, from these rows: the token need
        # not be the argmax, but it is among the top_k of its row
        for toks, fut in zip(asked, futs):
            for tok, row in zip(toks, fut.logits()):
                assert tok in np.argsort(row)[-20:]
    finally:
        engine.shutdown(drain=False)


def test_a_full_prefix_hit_that_wants_logits_prefills_its_last_row():
    engine = _engine('prefix_cache')
    try:
        first = engine.submit(PROMPTS[1], max_new_tokens=NEW, seed=3)
        want = first.result(timeout=300)
        replay = engine.submit(PROMPTS[1], max_new_tokens=NEW, seed=3)
        assert replay.result(timeout=300) == want
        assert engine.stats()['prefix']['full_hits'] == 1
        asked = engine.submit(PROMPTS[1], max_new_tokens=NEW, seed=3,
                              want_logits=True)
        assert asked.result(timeout=300) == want
        rows = asked.logits()
        assert [int(np.argmax(r)) for r in rows] == want
        # a hit, but no replay of the recorded first token
        stats = engine.stats()['prefix']
        assert stats['full_hits'] == 1 and stats['hits'] == 2
    finally:
        engine.shutdown(drain=False)


def test_rows_are_not_appended_again_after_eviction_and_readmission():
    # 4 allocatable pages of 8 rows for two sequences that grow to 5 + 20
    # and 17 + 20 rows: the younger one is evicted and regenerates
    engine = _engine('bf16', num_pages=6)
    try:
        futs = [engine.submit(p, max_new_tokens=20, seed=i, want_logits=True)
                for i, p in enumerate(PROMPTS[:2])]
        tokens = [f.result(timeout=300) for f in futs]
        assert engine.stats()['evictions'] >= 1
        for toks, fut in zip(tokens, futs):
            rows = fut.logits()
            assert len(rows) == len(toks) == 20
            assert [int(np.argmax(r)) for r in rows] == toks
    finally:
        engine.shutdown(drain=False)


def test_int8_rows_differ_from_bf16_rows_by_more_than_rounding():
    """What a check on logits will separate: against the float32 engine,
    the first token's row of the int8 engine (weights and KV) lies several
    times farther off than the bf16 engine's."""
    def first_rows(cfg_over, **kw):
        cfg = gpt.GPTConfig(**dict(BASE, **cfg_over))
        params = gpt.init_params(cfg, jax.random.PRNGKey(0))
        engine = GenerationEngine(params, cfg, **dict(KW, **kw))
        try:
            futs = [engine.submit(p, max_new_tokens=1, want_logits=True)
                    for p in PROMPTS]
            for f in futs:
                f.result(timeout=300)
            return np.stack([f.logits()[0] for f in futs])
        finally:
            engine.shutdown(drain=False)

    exact = first_rows(dict(dtype='float32'))
    bf16 = first_rows(dict(dtype='bfloat16'))
    int8 = first_rows(dict(dtype='bfloat16', kv_cache_int8=True),
                      precision='int8_wo')
    scale = np.abs(exact).max()
    err_bf16 = np.abs(bf16 - exact).max() / scale
    err_int8 = np.abs(int8 - exact).max() / scale
    assert 0 < err_bf16 < 0.05
    assert err_int8 > 2 * err_bf16
    assert np.abs(int8 - bf16).max() / scale > err_bf16


# ---------------------------------------------------------------------------
# what the engine holds (PR 32): the family's product operands in the compute
# dtype, cast once at construction; the same bits as casting in every call
# ---------------------------------------------------------------------------

MOE = dict(vocab_size=97, hidden_size=32, num_layers=2, num_heads=2,
           n_experts=4, capacity_factor=8.0, max_seq_len=64, remat=False,
           use_flash=False, dtype='bfloat16')
# variant -> (module, config, engine keywords, mp); all from float32
# parameters under bfloat16 compute ('mp2' of VARIANTS computes in float32)
HELD = {
    'bf16': (gpt, gpt.GPTConfig(**BASE, dtype='bfloat16'), {}, 1),
    'kv_cache_int8': (gpt, gpt.GPTConfig(**BASE, dtype='bfloat16',
                                         kv_cache_int8=True), {}, 1),
    'prefix_cache': (gpt, gpt.GPTConfig(**BASE, dtype='bfloat16'),
                     dict(prefix_cache=True), 1),
    'mp2': (gpt, gpt.GPTConfig(**BASE, dtype='bfloat16'), {}, 2),
    'moe_gpt': (moe_gpt, moe_gpt.MoEConfig(**MOE), {}, 1),
}


def _held_engine(variant):
    model, cfg, kw, mp = HELD[variant]
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    assert {str(a.dtype) for a in jax.tree_util.tree_leaves(params)} == {
        'float32'}
    kw = dict(KW, **kw)
    if mp > 1:
        return sharded_generation_engine(params, cfg, mp=mp, **kw), params
    return GenerationEngine(params, cfg, **kw), params


def _rows_and_tokens(engine):
    try:
        tokens, futs = _serve(engine, want=True)
        assert engine.stats()['traces'] == TRACES
        return tokens, [np.stack(f.logits()) for f in futs]
    finally:
        engine.shutdown(drain=False)


@pytest.mark.parametrize('variant', sorted(HELD))
def test_an_engine_that_casts_once_serves_the_bits_of_one_that_casts_each_call(
        variant, monkeypatch):
    model, cfg = HELD[variant][:2]
    family = _family.family_of(cfg)
    operands = model.PRODUCT_OPERANDS
    engine, params = _held_engine(variant)
    held = engine._params['blocks']
    assert {k: str(v.dtype) for k, v in held.items()} == {
        k: 'bfloat16' if k in operands else 'float32'
        for k in params['blocks']}
    assert all(engine._params[k].dtype == jnp.float32
               for k in params if k != 'blocks')
    tokens, rows = _rows_and_tokens(engine)

    # the parent's engine: it holds the leaves as given and casts in
    # every call of both executables
    monkeypatch.setitem(
        _family._FAMILIES, type(cfg),
        dataclasses.replace(family, serve_params=None))
    engine, params = _held_engine(variant)
    assert all(engine._params['blocks'][k].dtype == jnp.float32
               for k in operands)
    tokens_each_call, rows_each_call = _rows_and_tokens(engine)
    assert tokens == tokens_each_call
    for a, b in zip(rows, rows_each_call):
        assert np.array_equal(a, b)


def test_the_int8_snapshot_is_made_of_the_parameters_as_given():
    engine = _engine('int8_wo')
    try:
        cfg = gpt.GPTConfig(**dict(BASE, dtype='bfloat16'))
        want = gpt.quantize_decode_params(
            gpt.init_params(cfg, jax.random.PRNGKey(0)))
        got = engine._params
        assert (jax.tree_util.tree_structure(got)
                == jax.tree_util.tree_structure(want))
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            assert a.dtype == b.dtype and np.array_equal(a, b)
        assert set(engine.stats()['param_bytes']) == {'int8', 'float32'}
    finally:
        engine.shutdown(drain=False)


@pytest.mark.parametrize('dtype', ['float32', 'bfloat16'])
def test_where_the_two_dtypes_agree_the_engine_holds_the_given_leaves(dtype):
    cfg = gpt.GPTConfig(**BASE, dtype=dtype, param_dtype=dtype)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    engine = GenerationEngine(params, cfg, autostart=False, **KW)
    try:
        given = jax.tree_util.tree_leaves(params)
        held = jax.tree_util.tree_leaves(engine._params)
        assert len(given) == len(held)
        assert all(a is b for a, b in zip(given, held))
        assert engine.stats()['param_bytes'] == {
            dtype: sum(a.nbytes for a in given)}
    finally:
        engine.shutdown(drain=False)


def test_logits_of_a_request_that_did_not_ask_raise():
    engine = _engine('bf16')
    try:
        fut = engine.submit(PROMPTS[0], max_new_tokens=2)
        fut.result(timeout=300)
        with pytest.raises(ValueError, match='want_logits'):
            fut.logits()
    finally:
        engine.shutdown(drain=False)


def test_subscribe_is_public_replays_and_reports_the_finish():
    engine = _engine('bf16')
    try:
        fut = engine.submit(PROMPTS[0], max_new_tokens=4)
        tokens = fut.result(timeout=300)
        seen = []
        fut.subscribe(lambda kind, *args: seen.append((kind,) + args))
        assert seen == [('token', i, t) for i, t in enumerate(tokens)] + [
            ('finish', None)]
        assert not hasattr(fut, '_subscribe')
        live, got = engine.submit(PROMPTS[2], max_new_tokens=4), []
        live.subscribe(lambda kind, *args: got.append((kind,) + args))
        tokens = live.result(timeout=300)
        assert sorted(e for e in got if e[0] == 'token') == [
            ('token', i, t) for i, t in enumerate(tokens)]
        assert got[-1] == ('finish', None)
    finally:
        engine.shutdown(drain=False)


@pytest.mark.parametrize('askers', [1, 5, 11], ids=lambda n: f'{n}_of_11')
def test_a_step_hands_the_host_the_asking_slots_rows_alone(askers,
                                                           monkeypatch):
    """A row is the whole vocabulary wide (1 MB at 262,272 logits), so a
    step's logits stay on the device and the host reads a gather of the
    asking slots' rows, ``ASK_ROWS`` at a time through ONE executable built
    by ``warmup()``: never the whole ``[slots, vocab]`` array, and the rows
    and tokens are those of an engine that serves each request alone."""
    from paddle_tpu.serving import generation
    cfg = gpt.GPTConfig(**BASE)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    prompts = [np.random.RandomState(s).randint(1, 97, size=4 + s % 9)
               .astype(np.int32) for s in range(11)]
    read = []
    take = generation._take_rows
    monkeypatch.setattr(generation, '_take_rows',
                        lambda lg, idx: read.append(lg.shape) or take(lg, idx))
    with GenerationEngine(params, cfg, num_slots=11, page_size=8,
                          prefill_width=16, autostart=False) as eng:
        eng.warmup()
        built = take._cache_size()
        del read[:]
        futs = [eng.submit(p, max_new_tokens=NEW, want_logits=i < askers)
                for i, p in enumerate(prompts)]
        eng.start()
        tokens = [f.result(timeout=300) for f in futs]
        stats = eng.stats()
    assert take._cache_size() == built          # no compile under traffic
    assert set(read) == {(11, 97)}
    # a gather for every ASK_ROWS askers of a step, none for the others
    per_step = -(-askers // generation.ASK_ROWS)
    assert per_step <= len(read) <= per_step * stats['steps']
    for i, (p, fut) in enumerate(zip(prompts, futs)):
        if i >= askers:
            with pytest.raises(ValueError):
                fut.logits()
            continue
        with GenerationEngine(params, cfg, num_slots=1, page_size=8,
                              prefill_width=16) as alone:
            one = alone.submit(p, max_new_tokens=NEW, want_logits=True)
            assert one.result(timeout=300) == tokens[i]
            np.testing.assert_allclose(np.stack(fut.logits()),
                                       np.stack(one.logits()), atol=1e-5)
        assert tokens[i] == [int(np.argmax(r)) for r in fut.logits()]


@pytest.mark.parametrize('dtype', ['bfloat16', 'float32'])
def test_a_decode_steps_row_written_in_place_serves_the_page_forms_bits(
        dtype, monkeypatch):
    """A decode step's K and V rows go through the row kernel
    (``ops/paged_kv._row_write``: heads of 128, pages of whole sublane
    tiles, interpreted here), a prefill's through the page form: over a
    prefill and 40 decode steps, which cross two page boundaries, tokens
    and kept logits are those of the same engine with the row kernel's
    gate shut."""
    import importlib
    from paddle_tpu.ops import paged_kv
    fa = importlib.import_module('paddle_tpu.ops.flash_attention')
    cfg = gpt.GPTConfig(**dict(BASE, hidden_size=256, dtype=dtype))
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))

    def serve():
        engine = GenerationEngine(params, cfg, num_slots=3, page_size=16,
                                  prefill_width=32)
        try:
            futs = [engine.submit(p, max_new_tokens=40, seed=i,
                                  want_logits=True)
                    for i, p in enumerate(PROMPTS[:2])]
            return ([f.result(timeout=300) for f in futs],
                    [np.stack(f.logits()) for f in futs])
        finally:
            engine.shutdown(drain=False)

    row_writes = []
    real = paged_kv._row_write

    def counted(plane, rows, *rest):
        row_writes.append(rows.shape)
        return real(plane, rows, *rest)
    monkeypatch.setattr(paged_kv, '_row_write', counted)
    fa.set_interpret(True)
    try:
        tokens, rows = serve()
        # K and V in the scanned layers' one body, in the step's one trace
        assert row_writes == [(3, 1, 2, 128)] * 2
        monkeypatch.setattr(paged_kv, '_row_write_available',
                            lambda *a: False)
        tokens_by_page, rows_by_page = serve()
    finally:
        fa.set_interpret(False)
    assert len(row_writes) == 2
    assert tokens == tokens_by_page and len(tokens[0]) == 40
    for a, b in zip(rows, rows_by_page):
        assert a.shape == (40, BASE['vocab_size']) and np.array_equal(a, b)
