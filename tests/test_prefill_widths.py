"""How wide a prefill runs: ``models/family.prefill_widths`` is the one
rule, the engine pads a prompt (with a prefix cache: its uncached tail) to
the narrowest width that holds it and calls the ONE jitted prefill at that
shape, ``warmup()`` builds every width, and the counters say what the
prompts asked and what the bodies computed. That every family serves, at
every width, the tokens and the logits rows it serves through the
full-width body is a case of the engine's contract
(tests/family_contract.py), which every family's own file binds to its row
of tests/served_families.py.

The rows are held there to float32's rounding (``WIDTH_TOL``), not to
the bit: XLA's multi-threaded CPU products split their sums by the operand's
shape, so a row of a 4-row call and the same row of a 32-row call differ in
the last bits (with ``--xla_cpu_multi_thread_eigen=false`` the dense
families' rows are bit-equal at every width; a routed family's grouped rows
still follow their neighbours, as tests/test_generation.py says of its
steps)."""
import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.models import family
from paddle_tpu.serving import GenerationEngine

from family_contract import served_of
from served_families import FAMILIES

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
    """``GenerationFamily.prefill_pages`` 2: a width is an executable every
    process traces, lowers and loads. The table's gpt is such a family, and
    its engine's widths are whole pairs of pages; that every row's engine
    has the rule's widths at ITS family's ``prefill_pages`` is the
    contract's counters case."""
    assert family.prefill_widths(1024, 128, 2) == (256, 512, 768, 1024)
    assert family.prefill_widths(1024, 128, 1) == PUBLISHED[1024]
    cfg, params = _gpt()
    eng = GenerationEngine(params, cfg, num_slots=1, page_size=PAGE,
                           prefill_width=WIDTH, autostart=False)
    try:
        assert family.family_of(cfg).prefill_pages == 2
        assert eng.stats()['prefill_widths'] == (8, 16, 24, 32)
    finally:
        eng.shutdown(drain=False)


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


WIDTH, PAGE = 32, 4
VOCAB = FAMILIES['gpt'].vocab


def _gpt():
    """The table's gpt: its configuration and its weights, made once."""
    served = served_of(FAMILIES['gpt'])
    return served.config, served.stacked


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
