"""Continuous batching + paged KV cache (ISSUE 7): paged-vs-dense decode
parity (gpt, moe_gpt, int8 KV) and the GenerationEngine's scheduling
behaviors — EOS, cache-filling prompts, mid-stream admission determinism,
eviction/readmission, streaming, warmup zero-retrace, admission control,
the loop one step ahead of its read-back, and gen.* telemetry. ``gpt`` and
``moe_gpt`` are bound here to the engine's contract
(tests/family_contract.py, their rows of tests/served_families.py); the
pool's kernels, schedule and allocator are tests/test_paged_kernels.py's."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import observability as obs
from paddle_tpu.models import gpt, moe_gpt
from paddle_tpu.ops import paged_kv
from paddle_tpu.serving import (DeadlineExceededError, EngineClosedError,
                                GenerationEngine, QueueFullError)

from family_contract import Contract, Run, served_of
from served_families import FAMILIES

pytestmark = pytest.mark.gen

# max_seq_len 32 with page_size 8 -> p_max 4: the virtual cache length
# (p_max * ps = 32) equals the dense S_max, the precondition for bitwise
# fallback parity at matched shapes
CFG = gpt.GPTConfig(vocab_size=97, hidden_size=32, num_layers=2, num_heads=2,
                    max_seq_len=32, dtype='float32', remat=False,
                    use_flash=False)
PS = 8


@pytest.fixture(scope='module')
def params():
    return gpt.init_params(CFG, jax.random.PRNGKey(0))


class TestGptContract(Contract):
    row = FAMILIES['gpt']


class TestMoeGptContract(Contract):
    row = FAMILIES['moe_gpt']


# the families that keep a dense cache beside the paged one
DENSE = sorted(name for name, row in FAMILIES.items()
               if hasattr(row.module, 'init_kv_cache'))


def _prompts(lens, seed=0, vocab=None):
    rng = np.random.RandomState(seed)
    v = vocab or CFG.vocab_size
    return [rng.randint(0, v, size=t).astype(np.int32) for t in lens]


_DENSE_FNS = {}


def _dense_fns(cfg, fwd):
    """(prefill, step) of the dense cache under ``cfg``, jitted once a
    configuration: the step takes its position as data, so a sequence's
    steps, and every test's, run ONE executable (called eagerly each was
    traced anew, op by op)."""
    key = (repr(cfg), fwd)
    if key not in _DENSE_FNS:
        _DENSE_FNS[key] = (
            jax.jit(lambda params, toks, cache: fwd(
                params, toks, cache, 0, cfg, last_only=True)),
            jax.jit(lambda params, tok, cache, pos: fwd(
                params, tok, cache, pos, cfg)))
    return _DENSE_FNS[key]


def _dense_rows(params, cfg, prompt, n_new, fwd=gpt.forward_with_cache):
    """Reference: dense-cache greedy decode of ONE sequence -> (tokens,
    the float32 logits row each was chosen from)."""
    prefill, step = _dense_fns(cfg, fwd)
    cache = gpt.init_kv_cache(cfg, 1)
    lg, cache = prefill(params, jnp.asarray(prompt[None]), cache)
    rows = [np.asarray(lg[0, -1], np.float32)]
    toks = [int(np.argmax(rows[-1]))]
    for pos in range(len(prompt), len(prompt) + n_new - 1):
        lg, cache = step(params, jnp.asarray([[toks[-1]]], jnp.int32), cache,
                         jnp.int32(pos))
        rows.append(np.asarray(lg[0, -1], np.float32))
        toks.append(int(np.argmax(rows[-1])))
    return toks, np.stack(rows)


def _dense_greedy(params, cfg, prompt, n_new):
    return _dense_rows(params, cfg, prompt, n_new)[0]


def _moe_case():
    cfg = moe_gpt.MoEConfig(vocab_size=97, hidden_size=32, num_layers=2,
                            num_heads=2, n_experts=4, max_seq_len=32,
                            dtype='float32', remat=False, use_flash=False,
                            capacity_factor=8.0)
    return cfg, moe_gpt.init_params(cfg, jax.random.PRNGKey(1))


def _paged_greedy_batch(params, cfg, prompts, n_new, ps=PS,
                        fwd=gpt.forward_with_cache):
    """Greedy-decode a ragged batch through the paged cache directly (no
    engine): one padded prefill with per-slot `valid`, then batched
    single-token steps at per-slot positions."""
    b = len(prompts)
    p_max = paged_kv.pages_for(cfg.max_seq_len, ps)
    pool = gpt.init_paged_kv_cache(cfg, b * p_max + 1, ps)
    alloc = paged_kv.PageAllocator(b * p_max + 1)
    table = np.zeros((b, p_max), np.int32)
    for i in range(b):
        table[i] = alloc.alloc(p_max)
    w = max(len(p) for p in prompts)
    toks_in = np.zeros((b, w), np.int32)
    valid = np.zeros((b,), np.int32)
    for i, p in enumerate(prompts):
        toks_in[i, :len(p)] = p
        valid[i] = len(p)
    cache = {'k': pool['k'], 'v': pool['v'],
             'page_table': jnp.asarray(table), 'valid': jnp.asarray(valid)}
    logits, cache = fwd(params, jnp.asarray(toks_in), cache,
                        jnp.zeros((b,), jnp.int32), cfg, last_only=True)
    out = [[int(jnp.argmax(logits[i, 0]))] for i in range(b)]
    cache = {'k': cache['k'], 'v': cache['v'],
             'page_table': cache['page_table']}      # decode: no padding
    pos = valid.copy()
    for _ in range(n_new - 1):
        step_in = np.asarray([[o[-1]] for o in out], np.int32)
        lg, cache = fwd(params, jnp.asarray(step_in), cache,
                        jnp.asarray(pos), cfg)
        for i in range(b):
            out[i].append(int(jnp.argmax(lg[i, 0])))
        pos += 1
    return out, logits


# ---------------------------------------------------------------------------
# paged-vs-dense decode parity
# ---------------------------------------------------------------------------

def test_paged_vs_dense_parity_gpt_ragged(params):
    prompts = _prompts([5, 8])
    want = [_dense_greedy(params, CFG, p, 6) for p in prompts]
    got, _ = _paged_greedy_batch(params, CFG, prompts, 6)
    assert got == want


def test_paged_vs_dense_bitwise_at_matched_shape(params):
    # equal-length prompts, prefill width == T0, same batch: the fallback
    # runs the exact op sequence of the dense path -> bitwise logits
    prompts = _prompts([8, 8], seed=3)
    dense = gpt.init_kv_cache(CFG, 2)
    dlg, _ = gpt.forward_with_cache(
        params, jnp.asarray(np.stack(prompts)), dense, 0, CFG,
        last_only=True)
    _, plg = _paged_greedy_batch(params, CFG, prompts, 1)
    np.testing.assert_array_equal(np.asarray(dlg), np.asarray(plg))


def test_paged_vs_dense_parity_moe():
    mcfg, mp = _moe_case()
    prompts = _prompts([4, 7], seed=5)
    want = [_dense_rows(mp, mcfg, p, 5, moe_gpt.forward_with_cache)[0]
            for p in prompts]
    got, _ = _paged_greedy_batch(mp, mcfg, prompts, 5,
                                 fwd=moe_gpt.forward_with_cache)
    assert got == want


def test_paged_vs_dense_parity_int8_kv(params):
    icfg = gpt.GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                         num_heads=2, max_seq_len=32, dtype='float32',
                         remat=False, use_flash=False, kv_cache_int8=True)
    prompts = _prompts([6, 8], seed=7)
    want = [_dense_greedy(params, icfg, p, 5) for p in prompts]
    got, _ = _paged_greedy_batch(params, icfg, prompts, 5)
    assert got == want


# ---------------------------------------------------------------------------
# GenerationEngine
# ---------------------------------------------------------------------------

def _engine(params, cfg=CFG, **kw):
    kw.setdefault('num_slots', 2)
    kw.setdefault('page_size', PS)
    kw.setdefault('prefill_width', 16)
    return GenerationEngine(params, cfg, **kw)


def test_engine_greedy_matches_dense_reference(params):
    prompts = _prompts([5, 9, 3, 12], seed=11)
    want = [_dense_greedy(params, CFG, p, 6) for p in prompts]
    with _engine(params) as eng:
        futs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        got = [f.result(timeout=120) for f in futs]
    assert got == want


# case -> (config overrides, engine keywords, prompt lengths, new tokens)
ENGINE_CASES = {
    'float32': ({}, {}, [5, 9, 3, 12], 6),
    'bf16_pool': (dict(dtype='bfloat16'), {}, [5, 9, 3, 12], 6),
    'kv_cache_int8': (dict(kv_cache_int8=True), {}, [6, 8, 11], 5),
    # six pages for two sequences that want eight: a slot is evicted and
    # re-admitted onto whatever pages are free then
    'evicted_and_readmitted': ({}, dict(num_pages=6), [9, 9], 16),
    'moe_gpt': (None, {}, [4, 7, 10], 5),
}


@pytest.mark.parametrize('case', sorted(ENGINE_CASES))
def test_engine_tokens_and_rows_against_the_dense_cache(params, case):
    """The pool carried whole through the layers, written a page at a
    time and read head-major serves what the dense cache computes: the
    same tokens, and the row each was chosen from."""
    over, kw, lens, n_new = ENGINE_CASES[case]
    if over is None:
        cfg, weights = _moe_case()
        fwd = moe_gpt.forward_with_cache
    else:
        cfg = gpt.GPTConfig(**{**CFG.__dict__, **over})
        weights, fwd = params, gpt.forward_with_cache
    prompts = _prompts(lens, seed=len(case))
    want = [_dense_rows(weights, cfg, p, n_new, fwd) for p in prompts]
    with _engine(weights, cfg, **kw) as eng:
        futs = [eng.submit(p, max_new_tokens=n_new, want_logits=True)
                for p in prompts]
        got = [(f.result(timeout=300), np.stack(f.logits())) for f in futs]
        stats = eng.stats()
    if 'num_pages' in kw:
        assert stats['evictions'] >= 1
    # bf16 rounds a product by its shape: a padded prefill's row may differ
    # from the unpadded one's in the last place
    tol = 4e-2 if cfg.dtype == 'bfloat16' else 2e-5
    for (toks, rows), (want_toks, want_rows) in zip(got, want):
        np.testing.assert_allclose(rows, want_rows, rtol=tol, atol=tol)
        if cfg.dtype != 'bfloat16':
            assert toks == want_toks
        assert toks == [int(np.argmax(r)) for r in rows]


@pytest.mark.parametrize('family', DENSE)
def test_a_dense_cached_decode_of_grouped_heads_serves_the_uncached_forwards_rows(  # noqa: E501
        family):
    """Two query heads a KV head (``g = 2``, the table's shape): a cached
    block makes q, k, v by the product and THEN the split
    (``gpt._cached_qkv``, PR 44), the uncached forward by ``_block_qkv`` as
    training does. The dense cache serves the uncached forward's rows, as
    the contract's first case holds the engine to them."""
    served = served_of(FAMILIES[family])
    weights, cfg = served.stacked, served.config
    assert weights['blocks']['qkv_w'].shape[-1] == (4 + 2 * 2) * 16
    prompts, n_new = _prompts([5, 9, 12], seed=13, vocab=96), 6
    got = [_dense_rows(weights, cfg, p, n_new,
                       FAMILIES[family].module.forward_with_cache)
           for p in prompts]
    served.held_to_reference(Run(prompts, got, None, None), n_new, 2e-5)


@pytest.mark.parametrize('shared', [8, 11, 16],
                         ids=['page_boundary', 'mid_page', 'two_pages'])
def test_prefix_tail_prefill_against_the_dense_cache(params, shared):
    """A prefix-cache hit prefills only the tail: rows from a traced
    start, over pages another sequence wrote (copied first when the
    prefix ends mid-page). Same tokens and rows as the dense cache."""
    rng = np.random.RandomState(shared)
    first = rng.randint(0, CFG.vocab_size, size=shared).astype(np.int32)
    second = np.concatenate([first,
                             rng.randint(0, 97, size=4).astype(np.int32)])
    want = [_dense_rows(params, CFG, p, 5) for p in (first, second)]
    with _engine(params, prefix_cache=True, prefill_width=24) as eng:
        got = []
        for p in (first, second):           # the second arrives after
            f = eng.submit(p, max_new_tokens=5, want_logits=True)
            got.append((f.result(timeout=300), np.stack(f.logits())))
        stats = eng.stats()
    assert stats['prefix']['hits'] >= 1
    # the whole first prompt is reused: its last page, when it ends
    # mid-page, as a private copy the tail's rows are laid into
    assert stats['prefix_tokens_saved'] == shared
    for (toks, rows), (want_toks, want_rows) in zip(got, want):
        assert toks == want_toks
        np.testing.assert_allclose(rows, want_rows, rtol=2e-5, atol=2e-5)


def test_copy_page_between_steps_changes_nothing(params):
    """``copy_page`` on the head-major pool: every slot's pages copied to
    spare ones and the table turned to the copies between two decode
    steps; the rows that follow are the dense cache's."""
    prompts = _prompts([6, 8], seed=41)
    want = [_dense_rows(params, CFG, p, 6) for p in prompts]
    b, p_max = 2, 4
    pool = gpt.init_paged_kv_cache(CFG, 2 * b * p_max + 1, PS)
    table = np.arange(1, b * p_max + 1, dtype=np.int32).reshape(b, p_max)
    toks_in = np.zeros((b, 8), np.int32)
    valid = np.asarray([len(p) for p in prompts], np.int32)
    for i, p in enumerate(prompts):
        toks_in[i, :len(p)] = p
    cache = dict(pool, page_table=jnp.asarray(table),
                 valid=jnp.asarray(valid))
    lg, cache = gpt.forward_with_cache(params, jnp.asarray(toks_in), cache,
                                       jnp.zeros((b,), jnp.int32), CFG,
                                       last_only=True)
    rows = [[np.asarray(lg[i, 0], np.float32)] for i in range(b)]
    pos = valid.copy()
    for step in range(5):
        pool = {k: cache[k] for k in ('k', 'v')}
        if step == 2:
            for page in table.reshape(-1):
                pool = paged_kv.copy_page(pool, int(page),
                                          int(page) + b * p_max)
            table = table + b * p_max
        tok = np.asarray([[int(np.argmax(r[-1]))] for r in rows], np.int32)
        cache = dict(pool, page_table=jnp.asarray(table))
        lg, cache = gpt.forward_with_cache(params, jnp.asarray(tok), cache,
                                           jnp.asarray(pos), CFG)
        for i in range(b):
            rows[i].append(np.asarray(lg[i, 0], np.float32))
        pos += 1
    for i in range(b):
        np.testing.assert_allclose(np.stack(rows[i]), want[i][1],
                                   rtol=2e-5, atol=2e-5)


def test_prompt_exactly_fills_cache(params):
    # a prompt of max_seq_len still yields exactly ONE token: the final
    # decode write would fall outside the window, but the prefill's own
    # last-row logits are valid
    prompt = _prompts([CFG.max_seq_len], seed=13)[0]
    with _engine(params, prefill_width=CFG.max_seq_len) as eng:
        fut = eng.submit(prompt, max_new_tokens=8)
        toks = fut.result(timeout=120)
    assert len(toks) == 1
    dlg, _ = gpt.forward_with_cache(
        params, jnp.asarray(prompt[None]), gpt.init_kv_cache(CFG, 1), 0,
        CFG, last_only=True)
    assert toks[0] == int(jnp.argmax(dlg[0, -1]))


def test_per_sequence_eos_inside_batch(params):
    prompts = _prompts([5, 9], seed=17)
    base = [_dense_greedy(params, CFG, p, 8) for p in prompts]
    eos = base[0][2]        # learned from the greedy stream, not guessed
    assert eos not in base[1][:3]
    with _engine(params, eos_id=eos) as eng:
        futs = [eng.submit(p, max_new_tokens=8) for p in prompts]
        got = [f.result(timeout=120) for f in futs]
    # each sequence truncates at (and emits) ITS OWN first EOS, or runs
    # the full budget — batch-mates are independent
    def trunc(stream):
        return stream[:stream.index(eos) + 1] if eos in stream else stream

    assert got[0] == trunc(base[0])
    assert got[1] == trunc(base[1])
    assert len(got[0]) < len(base[0])   # the EOS actually truncated seq 0


def test_mid_stream_admission_determinism(params):
    # seeded sampling: a request admitted while others are mid-decode
    # produces the same tokens as the same request alone in an engine of
    # the same geometry (batch composition independence)
    prompts = _prompts([5, 9, 7], seed=19)
    kw = dict(temperature=0.8, top_k=20)
    with _engine(params, **kw) as eng:
        futs = [eng.submit(p, max_new_tokens=6, seed=i)
                for i, p in enumerate(prompts)]
        batched = [f.result(timeout=120) for f in futs]
    for i, p in enumerate(prompts):
        with _engine(params, **kw) as eng:
            alone = eng.submit(p, max_new_tokens=6, seed=i).result(timeout=120)
        assert alone == batched[i], f'sequence {i} diverged'


def test_eviction_determinism_and_no_duplicates(params):
    # pool too small for both sequences' full demand: evictions must fire,
    # and every stream must still equal the unconstrained run with no
    # token re-emitted
    prompts = _prompts([9, 9], seed=23)
    n_new = 16
    want = [_dense_greedy(params, CFG, p, n_new) for p in prompts]
    with _engine(params, num_pages=6) as eng:
        futs = [eng.submit(p, max_new_tokens=n_new) for p in prompts]
        streams = [list(f.stream(timeout=120)) for f in futs]
        stats = eng.stats()
    assert stats['evictions'] >= 1
    assert streams == want
    assert all(len(s) == n_new for s in streams)


def test_streaming_matches_result(params):
    prompt = _prompts([6], seed=29)[0]
    with _engine(params) as eng:
        fut = eng.submit(prompt, max_new_tokens=5)
        streamed = list(fut.stream(timeout=120))
        assert streamed == fut.result()
        assert fut.done()


def test_warmup_two_traces_and_zero_retrace(params):
    """Two jitted functions: the step, and the prefill at each of the
    engine's widths (one here: the family's widths are two pages of 8 rows
    at a time, tests/test_prefill_widths.py serves several)."""
    eng = _engine(params, autostart=False)
    assert eng.prefill_widths == (16,)
    report = eng.warmup()
    assert report['prebuilt'] == 2
    assert eng._trace_count == 2
    assert set(eng._aot) == {'gen_prefill.16', 'gen_decode'}
    # a second warmup finds every executable already built
    assert eng.warmup()['already_cached'] == 2
    with eng:
        futs = [eng.submit(p, max_new_tokens=4)
                for p in _prompts([5, 9], seed=31)]
        for f in futs:
            f.result(timeout=120)
    assert eng._trace_count == 2        # live traffic retraced nothing


def test_manifest_capture_records_generation_entries(params):
    from paddle_tpu import warmup
    eng = _engine(params)
    try:
        with warmup.capture() as man:
            eng.submit(_prompts([5])[0], max_new_tokens=2).result(timeout=120)
        kinds = {e['kind'] for e in man}
        assert {'gen_prefill', 'gen_decode'} <= kinds
        entry = next(e for e in man if e['kind'] == 'gen_decode')
        assert entry['slots'] == eng.num_slots
        assert entry['page_size'] == eng.page_size
        # a prefill entry a width, whatever width the traffic ran
        assert sorted(e['body'] for e in man
                      if e['kind'] == 'gen_prefill') == [16]
        # a fresh engine of the same geometry prebuilds from the capture
        eng2 = _engine(params, autostart=False)
        report = warmup.prebuild(man, generation=eng2)
        assert report['prebuilt'] == 2 and report['skipped'] == 0
    finally:
        eng.shutdown()


def test_queue_full_and_deadline(params):
    eng = _engine(params, autostart=False, queue_capacity=2)
    p = _prompts([4])[0]
    eng.submit(p, max_new_tokens=2)
    eng.submit(p, max_new_tokens=2)
    with pytest.raises(QueueFullError):
        eng.submit(p, max_new_tokens=2)
    eng.shutdown(drain=False)
    eng2 = _engine(params, autostart=False)
    # an already-expired deadline fast-fails at submit instead of queueing
    # a request the scheduler could only expire once it reached a slot
    with pytest.raises(DeadlineExceededError):
        eng2.submit(p, max_new_tokens=2, deadline_ms=0)
    assert eng2.stats()['expired'] == 1
    # a deadline that lapses WHILE queued still expires through the drain
    import time as _time
    fut = eng2.submit(p, max_new_tokens=2, deadline_ms=20)
    _time.sleep(0.05)
    eng2.shutdown()                     # inline drain: expires the request
    assert isinstance(fut.exception(timeout=10), DeadlineExceededError)


def test_requeue_preserves_enqueue_time_for_slo_accounting(params):
    # an evicted request is requeued as the SAME _Request object: its
    # submit-time enqueue timestamp survives, so the queue-wait recorded
    # at re-admission keeps growing instead of resetting — truthful SLO
    # accounting across evictions (and, via the same hooks, failovers)
    prompts = _prompts([9, 9], seed=23)
    with _engine(params, num_pages=6) as eng:
        futs = [eng.submit(p, max_new_tokens=16) for p in prompts]
        [f.result(timeout=120) for f in futs]
        assert eng.stats()['evictions'] >= 1
        label = eng.labels['engine']
        recs = [obs.recorder().lookup(f.request_id) for f in futs]
    evicted = next(r for r in recs
                   if any(e['ev'] == 'evict' for e in r['timeline']))
    admits = [e for e in evicted['timeline'] if e['ev'] == 'admit']
    assert len(admits) >= 2, 'evicted request was never re-admitted'
    waits = [e['waited_ms'] for e in admits]
    assert waits == sorted(waits) and waits[-1] > waits[0]
    # every admission feeds the serve.queue_wait histogram the fleet
    # autoscaler and shed hint read
    h = obs.find('serve.queue_wait_ms', {'engine': label})
    assert h is not None and h.count >= len(admits)


def test_resubmission_hooks_preserve_record_and_deadline(params):
    import time as _time
    eng = _engine(params, autostart=False)
    p = _prompts([4])[0]
    now = _time.monotonic()
    rec = obs.start_request('gen', engine=eng.labels['engine'])
    # a failed-over request arriving with its ORIGINAL absolute deadline
    # already in the past fast-fails at submit — but the accounting is
    # still measured from the original enqueue, not this resubmission
    with pytest.raises(DeadlineExceededError) as ei:
        eng.submit(p, max_new_tokens=2, _record=rec,
                   _enqueue_t=now - 5.0, _deadline_t=now - 1.0)
    assert ei.value.waited_ms >= 4900.0
    assert 3900.0 <= ei.value.deadline_ms <= 4100.0
    looked = obs.recorder().lookup(rec.rid)
    assert looked['outcome'] == 'expired'  # the SAME record was sealed
    assert any(e['ev'] == 'expire' and e.get('fast_fail')
               for e in looked['timeline'])
    # a resubmission whose deadline is still ahead rides the hooks into
    # the queue under the original record — no new record minted
    rec2 = obs.start_request('gen', engine=eng.labels['engine'])
    fut = eng.submit(p, max_new_tokens=2, _record=rec2,
                     _enqueue_t=now - 5.0, _deadline_t=now + 30.0)
    assert fut.request_id == rec2.rid
    eng.shutdown(drain=False)
    assert isinstance(fut.exception(timeout=10), EngineClosedError)
    assert obs.recorder().lookup(rec2.rid)['outcome'] == 'cancelled'


def test_prompt_validation(params):
    eng = _engine(params, autostart=False)
    try:
        with pytest.raises(ValueError):
            eng.submit(np.zeros((0,), np.int32))
        with pytest.raises(ValueError):
            eng.submit(np.zeros((eng.prefill_width + 1,), np.int32))
        with pytest.raises(ValueError):
            eng.submit(_prompts([4])[0], max_new_tokens=0)
    finally:
        eng.shutdown(drain=False)


def test_gen_metrics_present(params):
    with _engine(params) as eng:
        eng.submit(_prompts([5], seed=37)[0], max_new_tokens=3).result(
            timeout=120)
        stats = eng.stats()
    assert stats['completed'] == 1
    assert stats['tokens'] == 3
    assert stats['traces'] == 2
    snap = obs.snapshot()
    names = set(snap.get('counters', {})) | set(snap.get('histograms', {}))
    for want in ('gen.requests_submitted', 'gen.requests_completed',
                 'gen.tokens', 'gen.decode_step_ms', 'gen.ttft_ms'):
        assert any(k.startswith(want) for k in names), want


# ---------------------------------------------------------------------------
# what the engine holds, by dtype (PR 32)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('family', DENSE)
def test_param_bytes_names_what_a_bf16_engine_holds_of_float32_weights(
        family):
    import gc
    import weakref

    from paddle_tpu.serving import host
    row = FAMILIES[family]
    model = row.module
    cfg = row.config(row.shape(), dtype='bfloat16', param_dtype='float32')
    # weights of its own: the test drops them and watches them go
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    blocks = params['blocks']
    cast = sum(blocks[k].size for k in model.PRODUCT_OPERANDS)
    total = sum(a.size for a in jax.tree_util.tree_leaves(params))
    given = [weakref.ref(blocks[k]) for k in model.PRODUCT_OPERANDS]
    kept = weakref.ref(params['wte'])
    eng = GenerationEngine(params, cfg, num_slots=3, page_size=PS,
                           autostart=False)
    try:
        stats = eng.stats()
        assert stats['param_bytes'] == {'bfloat16': 2 * cast,
                                        'float32': 4 * (total - cast)}
        assert stats['precision'] == 'float32'   # "not the int8 snapshot"
        # the host's estimate of the model follows what is held
        assert host._tree_nbytes(eng._params) == 2 * cast + 4 * (
            total - cast)
        # the caller's float32 matrices are the caller's alone: dropped,
        # they are freed, while a leaf the engine took as given lives on
        del params, blocks
        gc.collect()
        assert [ref() for ref in given] == [None] * len(given)
        assert kept() is eng._params['wte']
    finally:
        eng.shutdown(drain=False)


# ---------------------------------------------------------------------------
# the decode loop one step ahead of its read-back (PR 36)
# ---------------------------------------------------------------------------

# case -> (config overrides or None for moe_gpt, engine keywords)
LOOP_CASES = {
    'bf16': (dict(dtype='bfloat16'), {}),
    'kv_cache_int8': (dict(kv_cache_int8=True), {}),
    'prefix_cache': ({}, dict(prefix_cache=True, prefill_width=24)),
    'moe_gpt': (None, {}),
    # five pages for two sequences that want eight: slots are evicted with
    # a step in flight and regenerate what they had
    'evicted': ({}, dict(num_pages=6)),
}


def _loop_case(params, case):
    over, kw = LOOP_CASES[case]
    if over is None:
        cfg, weights = _moe_case()
    else:
        cfg, weights = gpt.GPTConfig(**{**CFG.__dict__, **over}), params
    return cfg, weights, kw


def _serve_waves(weights, cfg, case, **kw):
    """Waves of requests on two slots, the first queued before the engine
    starts (so its steps hold the same rows in every run), each next one
    sent when the last has ended. -> [(tokens, rows or None) a request],
    the engine's stats."""
    first = _prompts([5, 9, 3, 8], seed=53)
    if case == 'evicted':
        waves, n_new = [_prompts([9, 9], seed=23)], (lambda i: 16)
    elif case == 'moe_gpt':
        # a routed layer groups a step's rows, so a row's last bits follow
        # its neighbours: the rows are held equal where both orders put
        # the same rows in every step, which is one wave of a request a
        # slot (an end by count leaves the row idle in both)
        waves, n_new = [first[:2]], (lambda i: 6 + 5 * i)
    else:
        # slots are re-admitted while their neighbours decode; the second
        # wave repeats the first's prompts with a tail (what a prefix
        # cache shares)
        waves = [first, [np.concatenate([p, _prompts([3], seed=59 + i)[0]])
                         for i, p in enumerate(first[:3])]]
        n_new = lambda i: 4 + 3 * (i % 3)                   # noqa: E731
    eng = _engine(weights, cfg, autostart=False, **kw)
    out = []
    try:
        for wave in waves:
            futs = [eng.submit(p, max_new_tokens=n_new(i), seed=100 + i,
                               want_logits=(i % 2 == 0))
                    for i, p in enumerate(wave)]
            eng.start()
            for i, f in enumerate(futs):
                toks = f.result(timeout=300)
                out.append((toks, np.stack(f.logits()) if i % 2 == 0
                            else None))
        return out, eng.stats()
    finally:
        eng.shutdown()


@pytest.mark.parametrize('sampled', [False, True], ids=['greedy', 'sampled'])
@pytest.mark.parametrize('case', sorted(LOOP_CASES))
def test_one_step_ahead_serves_what_reading_first_serves(
        params, case, sampled, read_first):
    """Step N+1 is dispatched before step N is read, its input tokens fed
    back on the device: the same tokens, and for a request that asked the
    same logits rows to the last bit, as a loop that reads every step
    before it dispatches the next and feeds the host's tokens."""
    cfg, weights, kw = _loop_case(params, case)
    if sampled:
        kw = dict(kw, temperature=0.8, top_k=20)
    got, stats = _serve_waves(weights, cfg, case, **kw)
    with read_first():
        want, base = _serve_waves(weights, cfg, case, **kw)
    assert base['steps_overlapped'] == 0 and base['rows_discarded'] == 0
    assert stats['steps_overlapped'] > 0
    # the step, and the prefill at each width a prompt's rows needed
    assert (2 <= stats['traces'] == base['traces']
            <= 1 + len(stats['prefill_widths']))
    if case == 'evicted':
        # the victim's row of the step in flight is dropped unread
        assert stats['evictions'] >= 1 and stats['rows_discarded'] >= 1
    if case == 'moe_gpt':
        assert stats['steps'] == base['steps']      # the same rows a step
    assert len(got) == len(want) >= 2
    for (toks, rows), (want_toks, want_rows) in zip(got, want):
        assert toks == want_toks
        if want_rows is not None:
            assert np.array_equal(rows, want_rows)
    if case == 'prefix_cache':
        assert stats['prefix']['hits'] >= 3


def test_an_end_by_eos_costs_one_discarded_row(params):
    """The host learns an end by EOS one step late: the row the next step
    computed for that slot is dropped (no token, no listener), its pages
    go back to the allocator and the slot takes the queued request. An end
    by count is known at dispatch and costs nothing."""
    prompts = _prompts([5, 9, 7], seed=17)
    n_new, kw = 8, dict(temperature=0.8, top_k=20)

    def serve(**more):
        heard = [[] for _ in prompts]
        with _engine(params, **kw, **more) as eng:
            futs = [eng.submit(p, max_new_tokens=n_new, seed=i)
                    for i, p in enumerate(prompts)]
            for f, log in zip(futs, heard):
                f.subscribe(lambda *ev, log=log: log.append(ev))
            got = [f.result(timeout=120) for f in futs]
            return got, heard, eng.stats(), eng._alloc.free_pages

    base, _, stats, _ = serve()
    assert stats['rows_discarded'] == 0     # every end known by its count
    # a token the first stream meets first in mid-stream
    at = next(j for j in range(2, n_new - 1)
              if base[0][j] not in base[0][:j])
    eos = base[0][at]
    got, heard, stats, free = serve(eos_id=eos)
    want = [s[:s.index(eos) + 1] if eos in s else s for s in base]
    assert got == want and len(got[0]) == at + 1
    # one overrun row for every stream that EOS cut after its first token
    # and before its count would have
    overrun = sum(1 for s in base if eos in s
                  and 0 < s.index(eos) < n_new - 1)
    assert overrun >= 1 and stats['rows_discarded'] == overrun
    assert stats['tokens'] == sum(len(s) for s in want)
    assert stats['completed'] == 3          # the third took a freed slot
    assert free == 4 * 2                    # every page came back
    for log, toks in zip(heard, want):
        assert [e[2] for e in log if e[0] == 'token'] == toks
        assert log[-1] == ('finish', None)


def test_a_fault_with_a_step_in_flight_fails_each_sequence_once(params):
    """``gen.step`` raising at the dispatch that follows an unread step:
    every active future fails once, the step in flight is dropped unread,
    the pool is rebuilt and the next request is served."""
    from paddle_tpu import fault
    prompts = _prompts([5, 9], seed=61)
    armed = []

    def arm(kind, *args):
        if kind == 'token' and args[0] == 2 and not armed:
            armed.append(True)
            fault.configure({'gen.step': (1.0, 'raise')}, max_faults=1)
    try:
        with _engine(params) as eng:
            futs = [eng.submit(p, max_new_tokens=12) for p in prompts]
            futs[0].subscribe(arm)
            excs = [f.exception(timeout=120) for f in futs]
            assert all(isinstance(e, fault.InjectedFault) for e in excs)
            stats = eng.stats()
            assert stats['failed'] == 2 and stats['completed'] == 0
            assert eng._inflight is None
            assert eng._alloc.free_pages == eng.num_pages - 1
            # both streams stopped short, and nothing came after the fault
            assert all(2 <= f._count() < 12 for f in futs)
            again = eng.submit(prompts[1], max_new_tokens=6)
            assert again.result(timeout=120) == _dense_greedy(
                params, CFG, prompts[1], 6)
    finally:
        fault.configure(None)


@pytest.mark.parametrize('thread', [True, False],
                         ids=['scheduler_thread', 'inline'])
def test_a_draining_shutdown_reads_the_step_in_flight(params, thread):
    prompts = _prompts([5, 9, 4], seed=67)
    want = [_dense_greedy(params, CFG, p, 9) for p in prompts]
    eng = _engine(params, autostart=thread)
    futs = [eng.submit(p, max_new_tokens=9) for p in prompts]
    if thread:
        next(futs[0].stream(timeout=120))       # decoding has begun
    eng.shutdown(drain=True)
    assert [f.result(timeout=10) for f in futs] == want
    assert eng._inflight is None
    assert eng.stats()['rows_discarded'] == 0


def test_steps_overlap_when_slots_are_full_and_not_for_a_lone_token(params):
    """``steps_overlapped`` beside ``steps``: with every slot full a step
    always has a forerunner in flight; a request of one token takes no
    decode step, one of two tokens a single step with nothing to overlap."""
    prompts = _prompts([5, 9, 3, 7, 4, 8, 6, 5], seed=71)
    eng = _engine(params, autostart=False)
    futs = [eng.submit(p, max_new_tokens=10 + 2 * (i % 4))
            for i, p in enumerate(prompts)]
    with eng:
        for f in futs:
            f.result(timeout=300)
        stats = eng.stats()
    assert stats['steps'] >= 40
    assert stats['steps_overlapped'] / stats['steps'] > 0.9
    assert stats['rows_discarded'] == 0
    # a discarded row is no token and a step counts once: every decoded
    # token is a row of a counted step
    assert stats['tokens'] - stats['prefills'] <= stats['steps'] * 2
    for n_new, steps in ((1, 0), (2, 1)):
        with _engine(params) as eng:
            eng.submit(prompts[0], max_new_tokens=n_new).result(timeout=120)
            stats = eng.stats()
        assert (stats['steps'], stats['steps_overlapped']) == (steps, 0)


@pytest.mark.parametrize('num_slots,lens', [(4, (11,)), (3, (5, 14)),
                                            (1, (9,))])
def test_stats_say_what_share_of_the_dense_grid_the_steps_walked(
        params, num_slots, lens):
    """``paged_steps_walked`` beside ``paged_steps_dense``: every decode
    step counts the pages its busy slots hold at the row they write, one
    for each idle slot, against ``num_slots * p_max``: what the paged
    kernel's grid walks a full layer (ops/paged_attention.page_schedule)
    and what it walked before PR 43."""
    n_new = 12
    prompts = _prompts(list(lens), seed=5)
    eng = _engine(params, num_slots=num_slots, autostart=False)
    futs = [eng.submit(p, max_new_tokens=n_new) for p in prompts]
    with eng:
        for f in futs:
            f.result(timeout=300)
        stats = eng.stats()
    # all admitted before the first step: each of the n_new - 1 steps
    # writes row len + k of every busy slot
    steps = n_new - 1
    walked = sum(min((n + k) // PS + 1, eng.p_max)
                 for n in lens for k in range(steps))
    walked += steps * (num_slots - len(lens))
    assert stats['steps'] == steps
    assert stats['paged_steps_walked'] == walked
    assert stats['paged_steps_dense'] == steps * num_slots * eng.p_max
    assert 0 < walked <= stats['paged_steps_dense']
