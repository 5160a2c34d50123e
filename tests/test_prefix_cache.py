"""Prefix caching + KV reuse (ISSUE 15): refcounted PageAllocator
semantics (double-free raises, retain/free pairing), the PrefixCache trie
(hits, COW, LRU eviction, capacity, tenant namespacing), the
GenerationEngine reuse path (byte-identical cold/warm/partial streams at
exactly two traces, cross-tenant isolation, pressure yielding, leak-free
drain), the ModelHost residency knob, and the gen.prefix obs namespace."""
import importlib.util
import os

import numpy as np
import pytest

import jax

from paddle_tpu.models import gpt
from paddle_tpu.ops import paged_kv
from paddle_tpu.serving import GenerationEngine, ModelHost, PrefixCache

pytestmark = pytest.mark.prefix

CFG = gpt.GPTConfig(vocab_size=97, hidden_size=32, num_layers=2, num_heads=2,
                    max_seq_len=32, dtype='float32', remat=False,
                    use_flash=False)
PS = 8


@pytest.fixture(scope='module')
def params():
    return gpt.init_params(CFG, jax.random.PRNGKey(0))


def _engine(params, **kw):
    kw.setdefault('num_slots', 2)
    kw.setdefault('page_size', PS)
    kw.setdefault('prefill_width', 16)
    kw.setdefault('prefix_cache', True)
    return GenerationEngine(params, CFG, **kw)


def _prompt(seed, n):
    return np.random.RandomState(seed).randint(
        1, CFG.vocab_size, size=n).astype(np.int32)


# ---------------------------------------------------------------------------
# refcounted allocator (satellite: double-free must raise, never leak)
# ---------------------------------------------------------------------------

def test_allocator_double_free_raises():
    alloc = paged_kv.PageAllocator(8)
    (p,) = alloc.alloc(1)
    alloc.free([p])
    with pytest.raises(ValueError, match='double free'):
        alloc.free([p])
    # the raise must not have corrupted the free list
    assert alloc.free_pages == 7


def test_allocator_rejects_trash_page_and_bad_ids():
    alloc = paged_kv.PageAllocator(8)
    for bad in (0, -1, 8, 99):
        with pytest.raises(ValueError):
            alloc.free([bad])
        with pytest.raises(ValueError):
            alloc.retain([bad])


def test_allocator_retain_defers_release_until_refcount_zero():
    alloc = paged_kv.PageAllocator(4)
    (p,) = alloc.alloc(1)
    alloc.retain([p])               # refs: 2
    before = alloc.free_pages
    alloc.free([p])                 # refs: 1 — still owned
    assert alloc.free_pages == before
    alloc.free([p])                 # refs: 0 — back on the free list
    assert alloc.free_pages == before + 1
    with pytest.raises(ValueError):
        alloc.retain([p])           # retain of a freed page must fail


# ---------------------------------------------------------------------------
# engine reuse path: determinism + the 2-executable invariant
# ---------------------------------------------------------------------------

def test_warm_repeat_is_byte_identical_with_zero_new_traces(params):
    prompt = _prompt(3, 12)
    ref = GenerationEngine(params, CFG, num_slots=2, page_size=PS,
                           prefill_width=16)   # cache OFF reference
    try:
        want = ref.submit(prompt, max_new_tokens=6, seed=5).result(
            timeout=120)
    finally:
        ref.shutdown()

    with _engine(params) as eng:
        cold = eng.submit(prompt, max_new_tokens=6, seed=5).result(
            timeout=120)
        traces = eng._trace_count
        warm = eng.submit(prompt, max_new_tokens=6, seed=5).result(
            timeout=120)
        st = eng.stats()['prefix']
        assert eng._trace_count == traces == 2
        assert st['full_hits'] >= 1
        # a full hit skips the whole prompt's prefill
        assert eng.stats()['prefix_tokens_saved'] == len(prompt)
    assert cold == want and warm == want


def test_partial_hit_shared_prefix_matches_cold(params):
    shared = _prompt(7, PS)                       # one full page
    a = np.concatenate([shared, _prompt(8, 4)])
    b = np.concatenate([shared, _prompt(9, 5)])
    ref = GenerationEngine(params, CFG, num_slots=2, page_size=PS,
                           prefill_width=16)
    try:
        want_b = ref.submit(b, max_new_tokens=6, seed=2).result(timeout=120)
    finally:
        ref.shutdown()

    with _engine(params) as eng:
        eng.submit(a, max_new_tokens=6, seed=1).result(timeout=120)
        got_b = eng.submit(b, max_new_tokens=6, seed=2).result(timeout=120)
        st = eng.stats()
        assert st['prefix']['hits'] >= 1
        assert st['prefix_tokens_saved'] >= PS
        # the tail reuses the executable (one width here; a tail picks
        # a narrower one where the engine has it: test_prefill_widths.py)
        assert eng._trace_count == 2
        assert st['prefill_rows_asked'] == len(a) + len(b) - PS
        assert st['prefill_rows_computed'] == 16 + 16
    assert got_b == want_b


def test_cow_divergence_inside_a_cached_page(params):
    """Two prompts sharing 12 of 16 tokens: the second's page 1 diverges
    mid-page, so its admission copies the donor page (COW) and re-prefills
    the divergent tail. Repeats of BOTH must stay byte-identical."""
    head = _prompt(11, 12)
    a = np.concatenate([head, _prompt(12, 4)])
    b = np.concatenate([head, _prompt(13, 4)])
    ref = GenerationEngine(params, CFG, num_slots=2, page_size=PS,
                           prefill_width=16)
    try:
        want_a = ref.submit(a, max_new_tokens=5, seed=4).result(timeout=120)
        want_b = ref.submit(b, max_new_tokens=5, seed=4).result(timeout=120)
    finally:
        ref.shutdown()

    with _engine(params) as eng:
        assert eng.submit(a, max_new_tokens=5, seed=4).result(
            timeout=120) == want_a
        for _ in range(2):                      # repeat hits stay stable
            assert eng.submit(b, max_new_tokens=5, seed=4).result(
                timeout=120) == want_b
            assert eng.submit(a, max_new_tokens=5, seed=4).result(
                timeout=120) == want_a


# ---------------------------------------------------------------------------
# tenant namespacing
# ---------------------------------------------------------------------------

def test_cross_tenant_never_shares_pages(params):
    prompt = _prompt(21, 12)
    with _engine(params) as eng:
        a = eng.submit(prompt, max_new_tokens=5, seed=0,
                       tenant='alpha').result(timeout=120)
        st = eng.stats()['prefix']
        b = eng.submit(prompt, max_new_tokens=5, seed=0,
                       tenant='beta').result(timeout=120)
        st2 = eng.stats()['prefix']
        # identical prompt under another tenant is a structural MISS ...
        assert st2['misses'] == st['misses'] + 1
        assert st2['hits'] == st['hits']
        # ... and the cached physical pages are disjoint sets
        pages = eng.prefix_cache.debug_pages()
        assert set(pages['alpha']) & set(pages['beta']) == set()
        # isolation is about pages, not outputs: same prompt+seed, same
        # stream
        assert a == b


# ---------------------------------------------------------------------------
# pressure, capacity, drain
# ---------------------------------------------------------------------------

def test_cache_yields_pages_under_pool_pressure(params):
    """Default pool (num_slots * p_max + 1 pages) with the cache holding
    finished sequences: fresh distinct prompts must keep admitting — the
    cache LRU-evicts instead of starving live traffic."""
    with _engine(params) as eng:
        for i in range(10):
            p = _prompt(100 + i, 12)
            assert eng.submit(p, max_new_tokens=5, seed=i).result(
                timeout=120)
        st = eng.stats()
        assert st['prefix']['evictions'] > 0
        assert st['prefix_evictions'] > 0


def test_capacity_knob_bounds_residency(params):
    with _engine(params, prefix_cache_pages=2) as eng:
        for i in range(4):
            eng.submit(_prompt(200 + i, 12), max_new_tokens=4,
                       seed=i).result(timeout=120)
        assert eng.prefix_cache.cached_pages <= 2
        eng.set_prefix_capacity(0)
        assert eng.prefix_cache.cached_pages == 0


def test_drain_plus_clear_restores_every_page(params):
    with _engine(params) as eng:
        for i in range(4):
            eng.submit(_prompt(300 + i, 13), max_new_tokens=4,
                       seed=i).result(timeout=120)
        assert eng.prefix_cache.cached_pages > 0
        eng.clear_prefix_cache()
        assert eng.prefix_cache.cached_pages == 0
        # every page back on the free list; page 0 stays reserved
        assert eng._alloc.free_pages == eng.num_pages - 1


def test_page_utilization_excludes_trash_page(params):
    """Satellite: the gen.page_utilization denominator must exclude the
    reserved trash page 0 — a fully loaded pool reads exactly 1.0."""
    with _engine(params) as eng:
        pages = eng._alloc.alloc(eng.num_pages - 1)   # every allocatable
        assert pages is not None
        with eng._lock:
            eng._update_gauges_locked()
        assert eng._g['pages'].value == pytest.approx(1.0)
        eng._alloc.free(pages)


def test_prefix_cache_off_by_default(params):
    eng = GenerationEngine(params, CFG, num_slots=2, page_size=PS,
                           prefill_width=16)
    try:
        assert eng.prefix_cache is None
        assert eng.stats()['prefix'] is None
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# trie unit behavior (no engine)
# ---------------------------------------------------------------------------

def test_trie_acquire_retains_and_release_lru_frees():
    alloc = paged_kv.PageAllocator(16)
    cache = PrefixCache(alloc, PS)
    toks = list(range(1, 2 * PS + 5))             # 2 full pages + partial
    table = np.array(list(alloc.alloc(3)) + [0], np.int32)
    cache.publish('t', toks, table, len(toks), prompt_len=len(toks),
                  seed=0, first_tok=None)
    alloc.free([int(p) for p in table[:3]])       # caller's refs released
    held = cache.cached_pages
    assert held == 3
    hit = cache.acquire('t', np.array(toks, np.int32), seed=0)
    assert hit is not None and len(hit['pages']) >= 1
    alloc.free([int(p) for p in hit['pages']])    # consumer done with them
    free_before = alloc.free_pages
    assert cache.release_lru(held) == held        # drop everything (LRU)
    assert cache.cached_pages == 0
    assert alloc.free_pages == free_before + held


# ---------------------------------------------------------------------------
# host knob + obs namespace
# ---------------------------------------------------------------------------

def test_host_residency_knob_reaches_engine(params):
    def factory():
        return GenerationEngine(params, CFG, num_slots=2, page_size=PS,
                                prefill_width=16, prefix_cache=True)
    with ModelHost(hbm_watermark_bytes=256 * 2 ** 20, name='pfx') as host:
        host.deploy('chat', factory, prefix_cache_pages=3)
        host.submit('chat', np.array([3, 1, 4, 1, 5]),
                    max_new_tokens=4).result(timeout=120)
        assert host.models()['chat']['prefix_cache_pages'] == 3
        eng = host._models['chat'].engine
        assert eng.prefix_cache.capacity_pages == 3


def test_obs_report_groups_prefix_namespace():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), 'tools', 'obs_report.py')
    spec = importlib.util.spec_from_file_location('_obs_report', path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod._namespace('gen.prefix.hits') == 'gen.prefix'
    assert mod._namespace('gen_prefix_cached_pages') == 'gen.prefix'
    assert mod._namespace('gen.page_utilization') == 'gen'
    assert mod._namespace('gen_tokens_total') == 'gen'
