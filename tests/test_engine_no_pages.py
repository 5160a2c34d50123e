"""An engine of per-slot state ALONE (serving/generation.py): a family whose
one kind of plane is a row a slot is served with no page pool, no allocator
and no page table; admission counts slots, the context is bounded by
positions, and what the busy slots hold is their state's bytes."""
import jax
import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.models import brumby
from paddle_tpu.serving import GenerationEngine, QueueFullError

pytestmark = pytest.mark.gen


def tiny(**over):
    kw = dict(vocab_size=64, hidden_size=32, intermediate_size=48,
              num_hidden_layers=1, num_attention_heads=2,
              num_key_value_heads=1, head_dim=8, max_position_embeddings=48,
              dtype='float32', param_dtype='float32')
    kw.update(over)
    return brumby.BrumbyConfig(**kw)


@pytest.fixture(scope='module')
def model():
    cfg = tiny()
    return brumby.init_params(cfg, jax.random.PRNGKey(0)), cfg


def engine(model, **kw):
    base = dict(num_slots=2, page_size=8, prefill_width=24)
    base.update(kw)
    return GenerationEngine(*model, **base)


def prompt(n, seed=0):
    return np.random.RandomState(seed).randint(0, 64, size=n).astype(np.int32)


def test_it_holds_no_allocator_no_table_and_no_page(model):
    with engine(model) as eng:
        assert eng._kinds == () and eng._allocs == {} and eng._alloc is None
        assert [k.name for k in eng._slot_kinds] == ['state']
        assert eng.num_pages == 0 and eng.prefix_cache is None
        assert set(eng._pool) == {'s', 'z'}
        assert eng._pool['s'].shape[1] == eng.num_slots
        # what a compiled call is told: which slots, and no table
        tables = eng._tables(2, slots=np.arange(2, dtype=np.int32))
        assert list(tables) == ['state'] and tables['state'].shape == (2,)
        stats = eng.stats()
        assert stats['num_pages'] == stats['free_pages'] == 0
        assert stats['page_bytes'] == 0
        # page_size stays the granule of the prefill's widths
        assert stats['prefill_widths'] == (8, 16, 24)


def test_admission_counts_slots_and_nothing_else(model):
    """Five requests on two slots: three wait for a slot, none for a page,
    none is evicted, and every one is served to its count."""
    with engine(model) as eng:
        futs = [eng.submit(prompt(n, n), max_new_tokens=9)
                for n in (3, 24, 11, 8, 17)]
        assert [len(f.result(timeout=300)) for f in futs] == [9] * 5
        stats = eng.stats()
    assert stats['evictions'] == stats['failed'] == 0
    assert stats['completed'] == stats['prefills'] == 5


def test_a_prompt_past_the_prefill_width_is_refused_at_submit(model):
    with engine(model) as eng:
        with pytest.raises(ValueError, match='prefill_width'):
            eng.submit(prompt(25))
        assert eng.submit(prompt(24), max_new_tokens=2).result(
            timeout=300)


def test_the_context_is_bounded_by_positions_alone(model):
    """max_seq_len 48: a prompt of 24 rows yields at most 25 tokens."""
    with engine(model) as eng:
        toks = eng.submit(prompt(24), max_new_tokens=999).result(timeout=300)
    assert len(toks) == 48 - 24 + 1


@pytest.mark.parametrize('kw', [dict(prefix_cache=True),
                                dict(prefix_cache_pages=4)])
def test_a_prefix_cache_is_refused_with_the_existing_message(model, kw):
    with pytest.raises(ValueError, match='prefills from row 0 only'):
        engine(model, autostart=False, **kw)


def test_num_pages_is_nobodys_to_give(model):
    with pytest.raises(ValueError, match='num_pages names'):
        engine(model, autostart=False, num_pages={'kv': 9})


def test_the_state_held_follows_the_busy_slots(model):
    """stats() and the gauges: zero pages, and ``kv.state_bytes_held`` is
    the busy slots' rows' bytes from admission to the end."""
    with engine(model) as eng:
        per_slot = eng.stats()['state_bytes_per_slot']
        dp = (8 // 2 + 1) * 8
        assert per_slot == 1 * 1 * (8 + 1) * dp * 4
        gauge = lambda name, **k: obs.find(name, {**eng.labels, **k})
        assert eng.stats()['state_bytes'] == 0
        running = [eng.submit(prompt(5, i), max_new_tokens=40)
                   for i in range(2)]
        for f in running:
            next(f.stream(timeout=300))     # both admitted and decoding
        assert eng.stats()['active_slots'] == 2
        assert eng.stats()['state_bytes'] == 2 * per_slot
        assert gauge('kv.state_bytes_held').value == 2 * per_slot
        assert gauge('kv.page_bytes_held').value == 0
        assert gauge('gen.page_utilization').value == 0
        assert gauge('kv.pages_in_use', kind='state') is None
        for f in running:
            f.result(timeout=300)
        assert eng.stats()['state_bytes'] == 0
        assert gauge('kv.state_bytes_held').value == 0


def test_a_full_queue_still_refuses(model):
    eng = engine(model, autostart=False, queue_capacity=2)
    try:
        eng.submit(prompt(3)), eng.submit(prompt(3))
        with pytest.raises(QueueFullError):
            eng.submit(prompt(3))
    finally:
        eng.shutdown(drain=False)


def test_warmup_builds_every_width_and_traffic_traces_nothing(model):
    with engine(model) as eng:
        eng.warmup()
        traces = eng.stats()['traces']
        assert traces == 1 + len(eng.prefill_widths)
        for n in (2, 9, 20):
            eng.submit(prompt(n), max_new_tokens=3).result(timeout=300)
        assert eng.stats()['traces'] == traces


def test_a_device_failure_rebuilds_the_pool_with_no_pages(model):
    with engine(model) as eng:
        eng.submit(prompt(4), max_new_tokens=2).result(timeout=300)
        eng._handle_device_failure(RuntimeError('lost'))
        assert set(eng._pool) == {'s', 'z'} and eng.num_pages == 0
        assert eng.submit(prompt(4), max_new_tokens=2).result(timeout=300)
