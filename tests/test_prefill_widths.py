"""How wide a prefill runs: ``models/family.prefill_widths`` is the one
rule, the engine pads a prompt (with a prefix cache: its uncached tail) to
the narrowest width that holds it and calls the ONE jitted prefill at that
shape, ``warmup()`` builds every width, and the counters say what the
prompts asked and what the bodies computed. Every family serves, at every
width, the tokens and the logits rows it serves through the full-width
body: rows past ``valid`` are padding in all of them.

The rows are held to float32's rounding (``ROUNDING``), not to the bit: XLA's
multi-threaded CPU products split their sums by the operand's shape, so a
row of a 4-row call and the same row of a 32-row call differ in the last
bits (with ``--xla_cpu_multi_thread_eigen=false`` the dense families'
rows are bit-equal at every width; a routed family's grouped rows still
follow their neighbours, as tests/test_generation.py says of its steps)."""
import jax
import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.models import (afmoe, family, gpt, granite_hybrid,
                               latent_moe, moe_gpt)
from paddle_tpu.serving import GenerationEngine

pytestmark = pytest.mark.gen


# ---- the rule --------------------------------------------------------------

PUBLISHED = {
    # trinity-large-ep8-serve; gpt-1.3b-serve and dots-vlm1-ep16-serve;
    # granite-4.0-h-micro-serve: each in pages of 128 rows
    16384: (2048, 3072, 4096, 6144, 8192, 12288, 16384),
    1024: (128, 256, 384, 512, 768, 1024),
    768: (128, 256, 384, 512, 768),
}


@pytest.mark.parametrize('width', sorted(PUBLISHED))
def test_the_served_cells_widths(width):
    assert family.prefill_widths(width, 128) == PUBLISHED[width]


def test_a_family_of_two_pages_at_a_time_has_fewer_widths():
    """``gpt`` and ``moe_gpt`` (``GenerationFamily.prefill_pages`` 2): a
    width is an executable every process traces, lowers and loads."""
    assert family.prefill_widths(1024, 128, 2) == (256, 512, 768, 1024)
    assert [family.family_of(cfg).prefill_pages for cfg in (
        _gpt()[0], _moe_gpt()[0], _latent_moe()[0], _afmoe()[0],
        _granite_hybrid()[0])] == [2, 2, 1, 1, 1]


@pytest.mark.parametrize('width,page,pages', [
    (768, 128, 1), (1024, 128, 1), (2048, 128, 1), (16384, 128, 1),
    (65536, 128, 1), (1000, 128, 1), (40, 4, 1), (64, 8, 1), (32, 128, 1),
    (1, 1, 1), (4096, 1, 1), (1024, 128, 2), (2048, 128, 2), (32, 4, 2)])
def test_the_rule_holds_its_properties(width, page, pages):
    widths = family.prefill_widths(width, page, pages)
    page *= pages
    assert list(widths) == sorted(set(widths)) and widths[-1] == width
    assert all(w % page == 0 for w in widths[:-1])
    for narrow, wide in zip(widths, widths[1:]):
        # half as much again, or one page where that is less than a page
        assert wide <= max(narrow + narrow // 2, narrow + page)
        assert wide - narrow <= family.MAX_BODY_STEP
    # none narrower than a page or an eighth of the widest, and the
    # narrowest no wider than it must be
    assert all(8 * w >= width and w >= min(page, width) for w in widths)
    assert widths[0] <= max(2 * page, width // 4, 1) or len(widths) == 1


# ---- every family, around every boundary -----------------------------------

WIDTH, PAGE, NEW = 32, 4, 3
ROUNDING = 5e-6     # of float32 logits of order 1 (bfloat16's step: 8e-3)
WIDTHS = (4, 8, 12, 16, 24, 32)
TWO_PAGES = (8, 16, 24, 32)     # gpt's and moe_gpt's: two pages at a time
VOCAB = 96


def _gpt():
    cfg = gpt.GPTConfig(vocab_size=VOCAB, hidden_size=32, num_layers=2,
                        num_heads=2, max_seq_len=48, dtype='float32',
                        remat=False, use_flash=False)
    return cfg, gpt.init_params(cfg, jax.random.PRNGKey(0))


def _moe_gpt():
    # capacity for every row whatever the routing: a body's rows compete
    # for expert capacity, which follows the rows of the call
    cfg = moe_gpt.MoEConfig(vocab_size=VOCAB, hidden_size=32, num_layers=2,
                            num_heads=2, n_experts=4, max_seq_len=48,
                            dtype='float32', remat=False, use_flash=False,
                            capacity_factor=8.0)
    return cfg, moe_gpt.init_params(cfg, jax.random.PRNGKey(1))


def _latent_moe():
    cfg = latent_moe.LatentMoEConfig(
        vocab_size=VOCAB, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=2,
        first_k_dense_replace=1, num_attention_heads=2, q_lora_rank=16,
        kv_lora_rank=128, qk_nope_head_dim=8, qk_rope_head_dim=8,
        v_head_dim=8, n_routed_experts=8, held=(0, 4), n_shared_experts=1,
        num_experts_per_tok=2, n_group=2, topk_group=1,
        max_position_embeddings=48, dtype='float32', param_dtype='float32')
    return cfg, latent_moe.init_params(cfg, jax.random.PRNGKey(2))


def _afmoe():
    cfg = afmoe.AfmoeConfig(
        vocab_size=VOCAB, hidden_size=32, intermediate_size=64,
        moe_intermediate_size=16, num_hidden_layers=3, num_dense_layers=1,
        num_attention_heads=4, num_key_value_heads=1, head_dim=8,
        sliding_window=8,
        layer_types=('sliding_attention', 'sliding_attention',
                     'full_attention'),
        num_experts=8, held=(0, 4), num_experts_per_tok=2,
        max_position_embeddings=48, dtype='float32', param_dtype='float32')
    return cfg, afmoe.init_params(cfg, jax.random.PRNGKey(3))


def _granite_hybrid():
    # chunks of a page: every width is whole chunks, as the published
    # widths (128 and up) are of the published 256 but for 128 and 384
    cfg = granite_hybrid.GraniteHybridConfig(
        vocab_size=VOCAB, hidden_size=64, shared_intermediate_size=96,
        num_hidden_layers=4,
        layer_types=('mamba', 'mamba', 'attention', 'mamba'),
        num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=4,
        mamba_d_head=32, mamba_d_state=16, mamba_chunk_size=PAGE,
        attention_multiplier=0.0625, max_position_embeddings=48,
        dtype='float32', param_dtype='float32')
    return cfg, granite_hybrid.init_params(cfg, jax.random.PRNGKey(4))


FAMILIES = {'gpt': _gpt, 'moe_gpt': _moe_gpt, 'latent_moe': _latent_moe,
            'afmoe': _afmoe, 'granite_hybrid': _granite_hybrid}


def widths_of(name):
    return TWO_PAGES if name in ('gpt', 'moe_gpt') else WIDTHS


# w - 1, w and w + 1 rows around every width (the widest takes no more)
LENGTHS = sorted({n for w in WIDTHS for n in (w - 1, w, w + 1)
                  if 1 <= n <= WIDTH})


def _serve_all(cfg, params):
    """Every length of ``LENGTHS``, one request at a time on one slot (a
    routed layer groups a step's rows: alone, a request's steps hold the
    same rows in every run). -> {rows: (tokens, logits rows)}, stats."""
    rng = np.random.RandomState(7)
    prompts = {n: rng.randint(1, VOCAB, size=n).astype(np.int32)
               for n in LENGTHS}
    with GenerationEngine(params, cfg, num_slots=1, page_size=PAGE,
                          prefill_width=WIDTH) as eng:
        out = {}
        for n, p in prompts.items():
            fut = eng.submit(p, max_new_tokens=NEW, want_logits=True)
            out[n] = (fut.result(timeout=300), np.stack(fut.logits()))
        return out, eng.stats()


@pytest.fixture(scope='module')
def served(full_body):
    """family -> (what the narrow bodies served, what the full-width body
    served, the narrow engine's stats): each family serves once."""
    cache = {}

    def of(name):
        if name not in cache:
            cfg, params = FAMILIES[name]()
            narrow, stats = _serve_all(cfg, params)
            with full_body():
                full, full_stats = _serve_all(cfg, params)
            assert full_stats['prefill_widths'] == (WIDTH,)
            assert full_stats['prefill_rows_computed'] == WIDTH * len(LENGTHS)
            cache[name] = narrow, full, stats
        return cache[name]
    return of


@pytest.mark.parametrize('name,width', [
    (name, width) for name in sorted(FAMILIES) for width in widths_of(name)])
def test_a_family_serves_at_every_width_what_the_full_body_serves(
        served, name, width):
    narrow, full, stats = served(name)
    assert stats['prefill_widths'] == widths_of(name)
    for n in (width - 1, width, width + 1):
        if n not in narrow:
            continue
        tokens, rows = narrow[n]
        want_tokens, want_rows = full[n]
        assert len(tokens) == NEW and rows.shape == (NEW, VOCAB)
        assert tokens == want_tokens, (name, n)
        np.testing.assert_allclose(rows, want_rows, rtol=0, atol=ROUNDING,
                                   err_msg=f'{name}, {n} rows')


@pytest.mark.parametrize('name', sorted(FAMILIES))
def test_the_counters_add_up_over_a_run(served, name):
    """What the prompts asked and what their bodies computed, in
    ``stats()`` and in ``gen.prefill_rows_total``; a trace a width and
    the step's."""
    _, _, stats = served(name)
    widths = widths_of(name)
    body = lambda n: next(w for w in widths if w >= n)      # noqa: E731
    assert stats['prefills'] == len(LENGTHS)
    assert stats['prefill_rows_asked'] == sum(LENGTHS)
    assert stats['prefill_rows_computed'] == sum(body(n) for n in LENGTHS)
    assert stats['traces'] == 1 + len(widths)


# ---- the engine's choice ---------------------------------------------------

def test_the_body_is_noted_on_the_request_record_and_counted():
    cfg, params = _gpt()
    with GenerationEngine(params, cfg, num_slots=2, page_size=PAGE,
                          prefill_width=WIDTH) as eng:
        futs = [eng.submit(np.arange(1, n + 1, dtype=np.int32),
                           max_new_tokens=2) for n in (3, 9, 17, 32)]
        for f in futs:
            f.result(timeout=120)
        stats, labels = eng.stats(), eng.labels
    assert stats['prefill_rows_asked'] == 3 + 9 + 17 + 32
    assert stats['prefill_rows_computed'] == 8 + 16 + 24 + 32
    for rows, want in (('asked', 61), ('computed', 80)):
        counter = obs.find('gen.prefill_rows_total', {**labels, 'rows': rows})
        assert counter is not None and counter.value == want
    for fut, body in zip(futs, (8, 16, 24, 32)):
        timeline = obs.recorder().lookup(fut.request_id)['timeline']
        assert [e['body'] for e in timeline if e['ev'] == 'prefill'] == [body]


def test_a_cached_prefix_leaves_a_tail_that_picks_a_narrow_body():
    """With the prefix cache on the body follows the UNCACHED tail: 28
    rows of which 24 are cached run the 8-row body, the narrowest
    (``start`` stays a traced argument), and serve what a cold engine
    serves."""
    cfg, params = _gpt()
    rng = np.random.RandomState(11)
    shared = rng.randint(1, VOCAB, size=24).astype(np.int32)
    first = np.concatenate([shared, rng.randint(1, VOCAB, size=3)]).astype(
        np.int32)
    second = np.concatenate([shared, rng.randint(1, VOCAB, size=4)]).astype(
        np.int32)
    kw = dict(num_slots=1, page_size=PAGE, prefill_width=WIDTH)
    with GenerationEngine(params, cfg, **kw) as cold:
        fut = cold.submit(second, max_new_tokens=4, want_logits=True)
        want, want_rows = fut.result(timeout=120), np.stack(fut.logits())
    with GenerationEngine(params, cfg, prefix_cache=True, **kw) as eng:
        eng.submit(first, max_new_tokens=4).result(timeout=120)
        before = eng.stats()
        fut = eng.submit(second, max_new_tokens=4, want_logits=True)
        got, rows = fut.result(timeout=120), np.stack(fut.logits())
        after = eng.stats()
    assert after['prefix_tokens_saved'] - before['prefix_tokens_saved'] == 24
    assert after['prefill_rows_asked'] - before['prefill_rows_asked'] == 4
    assert before['prefill_rows_computed'] == 32     # 27 rows, cold
    assert (after['prefill_rows_computed']
            - before['prefill_rows_computed']) == 8
    assert got == want
    np.testing.assert_allclose(rows, want_rows, atol=2e-5)


# ---- the warm-up -----------------------------------------------------------

_compiles = []
jax.monitoring.register_event_duration_secs_listener(
    lambda event, _secs, **kw: _compiles.append(kw.get('fun_name'))
    if event.endswith('backend_compile_duration') else None)


@pytest.mark.parametrize('name', ['gpt', 'granite_hybrid'])
def test_after_warmup_no_width_traces_or_compiles(name):
    cfg, params = FAMILIES[name]()
    widths = widths_of(name)
    eng = GenerationEngine(params, cfg, num_slots=2, page_size=PAGE,
                           prefill_width=WIDTH, autostart=False)
    report = eng.warmup()
    assert report['prebuilt'] == 1 + len(widths) and report['skipped'] == 0
    assert set(eng._aot) == {'gen_decode'} | {
        f'gen_prefill.{w}' for w in widths}
    traces = eng._trace_count
    assert traces == 1 + len(widths)
    del _compiles[:]
    with eng:
        for w in widths:
            eng.submit(np.arange(1, w + 1, dtype=np.int32),
                       max_new_tokens=2).result(timeout=120)
        stats = eng.stats()
    assert eng._trace_count == traces
    assert [n for n in _compiles if n in ('prefill', 'step')] == []
    assert stats['prefill_rows_computed'] == sum(widths)


def test_an_entry_of_a_width_the_engine_has_not_is_stale():
    from paddle_tpu import warmup
    from paddle_tpu.warmup.manifest import generation_entry
    cfg, params = _gpt()
    eng = GenerationEngine(params, cfg, num_slots=2, page_size=PAGE,
                           prefill_width=WIDTH, autostart=False)
    geom = dict(slots=2, page_size=PAGE, num_pages=eng.num_pages,
                prefill_width=WIDTH, table_width=eng.p_max)
    # an entry written before the engine chose among widths names none:
    # it is the widest's
    old = generation_entry('gen_prefill', **geom)
    assert old.pop('body') == WIDTH
    man = warmup.Manifest([old, generation_entry('gen_prefill', body=20,
                                                 **geom)])
    with pytest.warns(RuntimeWarning, match='body=20'):
        report = warmup.prebuild(man, generation=eng)
    assert report['prebuilt'] == 1 and report['skipped'] == 1
    assert set(eng._aot) == {f'gen_prefill.{WIDTH}'}
    eng.shutdown(drain=False)
