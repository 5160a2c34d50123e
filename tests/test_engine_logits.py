"""The engine hands out the logits a token was chosen from
(``submit(..., want_logits=True)`` -> ``GenerationFuture.logits()``), and the
future's listener is public (``subscribe``). One executable either way:
the tokens do not depend on who asked."""
import numpy as np
import pytest

import jax

from paddle_tpu.models import gpt
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
        assert engine.stats()['traces'] == 2
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
        assert engine.stats()['traces'] == 2
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
