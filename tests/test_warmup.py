"""paddle_tpu.warmup — persistent compile cache, manifest capture/prebuild,
per-key bucket-cache locking, and the integration hooks (ISSUE 5)."""
import json
import os
import subprocess
import sys
import threading
import time
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import fault, nn, serving, warmup
from paddle_tpu import observability as obs
from paddle_tpu.serving import InferenceEngine, bucket_sizes
from paddle_tpu.serving.bucket_cache import BucketCompileCache

pytestmark = pytest.mark.warmup


def _net():
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    net.eval()
    return net


def _fwd(net, x):
    return np.asarray(net(paddle.to_tensor(np.asarray(x))))


@pytest.fixture(autouse=True)
def _clean_capture_state():
    """A failed test must not leak an active capture (or a persistent cache
    dir) into its neighbours."""
    yield
    warmup.capture_stop()
    warmup.disable_persistent_cache()


# ---------------------------------------------------------------------------
# manifest
# ---------------------------------------------------------------------------

def test_manifest_roundtrip_dedup_and_counts(tmp_path):
    man = warmup.Manifest()
    e1 = warmup.serving_bucket_entry(4, (((8,), 'float32'),), 'float32')
    assert man.add(e1) is True
    assert man.add(dict(e1)) is False           # identical entry dedups
    man.add(warmup.train_step_entry([((16, 8), 'float32')],
                                    [((16, 1), 'int64')]))
    man.add(warmup.train_step_entry([((16, 8), 'float32')],
                                    [((16, 1), 'int64')], accumulate=True))
    man.add(warmup.eval_step_entry([((16, 8), 'float32')], []))
    man.add(warmup.predictor_entry((((4, 8), 'float32'),)))
    assert len(man) == 5
    assert man.counts() == {'serving_bucket': 1, 'train_step': 1,
                            'accum_step': 1, 'eval_step': 1, 'predictor': 1}
    path = str(tmp_path / 'warmup.json')
    man.save(path)
    loaded = warmup.Manifest.load(path)
    assert len(loaded) == 5
    assert loaded.entries == man.entries
    assert loaded.meta.get('framework')         # versions stamped at save


def test_manifest_load_rejects_garbage(tmp_path):
    bad = tmp_path / 'bad.json'
    bad.write_text('[1, 2, 3]')
    with pytest.raises(ValueError):
        warmup.Manifest.load(str(bad))
    worse = tmp_path / 'worse.json'
    worse.write_text('{truncated')
    with pytest.raises(Exception):
        warmup.Manifest.load(str(worse))


def test_capture_is_process_global_and_reentrant():
    assert not warmup.capturing()
    warmup.record({'kind': 'predictor', 'inputs': []})   # no-op when idle
    with warmup.capture() as man:
        assert warmup.capturing()
        inner = warmup.capture_start()
        assert inner is man                     # joins the active capture
        warmup.record(warmup.eval_step_entry([((2, 8), 'float32')], []))
    assert not warmup.capturing()
    assert len(man) == 1


# ---------------------------------------------------------------------------
# serving engine: capture -> prebuild
# ---------------------------------------------------------------------------

def test_engine_capture_then_prebuild_zero_live_compiles():
    net = _net()
    x3 = np.random.rand(3, 8).astype('float32')
    x7 = np.random.rand(7, 8).astype('float32')
    with warmup.capture() as man:
        with InferenceEngine(net, max_batch_size=8, max_delay_ms=0.2) as eng:
            ref3 = eng.submit(x3).result(timeout=60)
            eng.submit(x7).result(timeout=60)
    assert man.counts() == {'serving_bucket': 2}

    eng2 = InferenceEngine(net, max_batch_size=8, max_delay_ms=0.2,
                           warmup=man)
    assert eng2._cache.prebuilt == 2
    assert eng2._cache.misses == 0
    traces_after_prebuild = eng2._trace_count
    with eng2:
        out3 = eng2.submit(x3).result(timeout=60)
        eng2.submit(x7).result(timeout=60)
    # live traffic hit only prebuilt executables: no compile, no retrace
    assert eng2._cache.misses == 0
    assert eng2._trace_count == traces_after_prebuild
    np.testing.assert_allclose(out3, ref3, rtol=1e-6)
    st = eng2.stats()
    assert st['prebuilt'] == 2 and st['cache_misses'] == 0


def test_engine_warmup_all_buckets_with_input_spec():
    eng = InferenceEngine(_net(), max_batch_size=8, max_delay_ms=0.2,
                          warmup='all_buckets',
                          input_spec=[((8,), 'float32')])
    assert len(eng._cache) == len(bucket_sizes(8))
    with eng:
        for n in (1, 3, 8):
            eng.submit(np.random.rand(n, 8).astype('float32')).result(
                timeout=60)
    assert eng._cache.misses == 0
    eng.shutdown()


def test_engine_warmup_all_buckets_needs_a_spec():
    with pytest.raises(ValueError, match='input signature'):
        InferenceEngine(_net(), max_batch_size=4, warmup='all_buckets')


def test_engine_all_buckets_spec_from_hapi_model():
    from paddle_tpu.static import InputSpec
    net = _net()
    model = paddle.Model(net, inputs=[InputSpec([None, 8], 'float32')])
    eng = InferenceEngine(model, max_batch_size=4, max_delay_ms=0.2,
                          warmup='all_buckets')
    assert len(eng._cache) == len(bucket_sizes(4))
    eng.shutdown()


def test_stale_serving_entry_skipped_not_fatal():
    # feature dim 9 against a Linear(8, ...): lower() must fail, prebuild
    # must warn + skip and still build the valid entry
    man = warmup.Manifest()
    man.add(warmup.serving_bucket_entry(2, (((9,), 'float32'),), 'float32'))
    man.add(warmup.serving_bucket_entry(2, (((8,), 'float32'),), 'float32'))
    eng = InferenceEngine(_net(), max_batch_size=4, max_delay_ms=0.2)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter('always')
        report = eng.warmup(man)
    assert report['skipped'] == 1 and report['prebuilt'] == 1
    assert any('stale' in str(w.message) for w in caught)
    # shut down too: an engine left open keeps its not-ready probe in the
    # process, and /readyz tests that run after it read 503
    strict = InferenceEngine(_net(), max_batch_size=4, max_delay_ms=0.2)
    try:
        with pytest.raises(Exception):
            warmup.prebuild(man, engine=strict, strict=True)
    finally:
        strict.shutdown()
        eng.shutdown()


def test_oversized_bucket_entry_skipped():
    man = warmup.Manifest()
    man.add(warmup.serving_bucket_entry(64, (((8,), 'float32'),), 'float32'))
    eng = InferenceEngine(_net(), max_batch_size=4, max_delay_ms=0.2)
    with warnings.catch_warnings(record=True):
        warnings.simplefilter('ignore')
        report = eng.warmup(man)
    assert report['skipped'] == 1 and report['prebuilt'] == 0
    eng.shutdown()


def test_prebuild_untargeted_and_already_cached():
    man = warmup.Manifest()
    man.add(warmup.serving_bucket_entry(2, (((8,), 'float32'),), 'float32'))
    man.add(warmup.train_step_entry([((4, 8), 'float32')],
                                    [((4, 1), 'int64')]))
    eng = InferenceEngine(_net(), max_batch_size=4, max_delay_ms=0.2)
    report = warmup.prebuild(man, engine=eng)   # no model target
    assert report['prebuilt'] == 1 and report['untargeted'] == 1
    again = warmup.prebuild(man, engine=eng)
    assert again['prebuilt'] == 0 and again['already_cached'] == 1
    eng.shutdown()


# ---------------------------------------------------------------------------
# bucket cache: per-key locking (satellite)
# ---------------------------------------------------------------------------

def test_bucket_cache_foreign_compile_does_not_block_hits():
    release_a = threading.Event()
    started_a = threading.Event()

    def builder(bucket, sig, precision):
        if bucket == 1:
            started_a.set()
            assert release_a.wait(timeout=10)
        return lambda *a: bucket

    cache = BucketCompileCache(builder)
    sig = (((8,), 'float32'),)
    cache.get(2, sig, 'float32')                 # pre-compile key B

    results = {}
    t_a = threading.Thread(
        target=lambda: results.setdefault('a', cache.get(1, sig, 'float32')))
    t_a.start()
    assert started_a.wait(timeout=10)            # A is inside its build
    t0 = time.monotonic()
    results['b'] = cache.get(2, sig, 'float32')  # hit on another key
    hit_latency = time.monotonic() - t0
    release_a.set()
    t_a.join(timeout=10)
    assert results['b'](None) == 2
    assert results['a'](None) == 1
    # the hit completed while A's compile was still holding its key
    assert hit_latency < 1.0
    assert cache.misses == 2 and len(cache) == 2


def test_bucket_cache_same_key_coalesces_to_one_build():
    builds = []
    gate = threading.Event()

    def builder(bucket, sig, precision):
        builds.append(bucket)
        gate.wait(timeout=10)
        return lambda *a: bucket

    cache = BucketCompileCache(builder)
    sig = (((8,), 'float32'),)
    out = []
    threads = [threading.Thread(
        target=lambda: out.append(cache.get(4, sig, 'float32')))
        for _ in range(4)]
    for t in threads:
        t.start()
    time.sleep(0.1)
    gate.set()
    for t in threads:
        t.join(timeout=10)
    assert len(builds) == 1                      # one build, three waiters
    assert len(out) == 4 and all(f(None) == 4 for f in out)
    assert cache.misses == 1


def test_bucket_cache_failed_build_retried_by_waiter():
    calls = []

    def builder(bucket, sig, precision):
        calls.append(bucket)
        if len(calls) == 1:
            raise RuntimeError('first build dies')
        return lambda *a: 'ok'

    cache = BucketCompileCache(builder)
    sig = (((8,), 'float32'),)
    with pytest.raises(RuntimeError):
        cache.get(1, sig, 'float32')
    assert cache.get(1, sig, 'float32')(None) == 'ok'
    assert cache.misses == 1                     # only the success counts


def test_bucket_cache_put_counts_prebuilt_not_miss():
    cache = BucketCompileCache(lambda *a: (lambda *x: 'built'))
    sig = (((8,), 'float32'),)
    assert cache.put(2, sig, 'float32', lambda *x: 'seeded') is True
    assert cache.put(2, sig, 'float32', lambda *x: 'loser') is False
    assert cache.peek(2, sig, 'float32')(None) == 'seeded'
    assert cache.get(2, sig, 'float32')(None) == 'seeded'
    assert cache.misses == 0 and cache.prebuilt == 1 and len(cache) == 1


# ---------------------------------------------------------------------------
# hapi: train/eval prebuild
# ---------------------------------------------------------------------------

def _hapi_model():
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    model = paddle.Model(net)
    model.prepare(
        paddle.optimizer.Adam(parameters=net.parameters(),
                              learning_rate=1e-3),
        paddle.nn.CrossEntropyLoss())
    return model


def test_hapi_capture_prebuild_no_retrace_on_first_batch():
    x = np.random.rand(16, 8).astype('float32')
    y = np.random.randint(0, 4, size=(16, 1)).astype('int64')
    with warmup.capture() as man:
        m_src = _hapi_model()
        m_src.train_batch([x], [y])
        m_src.eval_batch([x], [y])
    assert man.counts() == {'train_step': 1, 'eval_step': 1}

    model = _hapi_model()
    report = model.prebuild_warmup(man)
    assert report['prebuilt'] == 2 and report['skipped'] == 0
    steps, evals = model._step_traces, model._eval_traces
    model.train_batch([x], [y])                 # first REAL batch
    model.eval_batch([x], [y])
    assert model._step_traces == steps          # compiled ahead: no retrace
    assert model._eval_traces == evals


def test_hapi_prebuild_preserves_net_mode_and_rng():
    from paddle_tpu.tensor.random import next_key
    x = np.random.rand(8, 8).astype('float32')
    y = np.random.randint(0, 4, size=(8, 1)).astype('int64')
    man = warmup.Manifest()
    man.add(warmup.train_step_entry(warmup.array_sig([x]),
                                    warmup.array_sig([y])))
    model = _hapi_model()
    model.train_batch([x], [y])                 # establish train mode
    assert model._net_mode is True
    key_before = np.asarray(next_key())
    man.add(warmup.eval_step_entry(warmup.array_sig([x]),
                                   warmup.array_sig([y])))
    model.prebuild_warmup(man)                  # flips to eval internally
    assert model._net_mode is True              # restored afterwards
    # abstract prebuild must not consume the training RNG stream
    key_after = np.asarray(next_key())
    rng_states_differ_by_exactly_one_draw = not np.array_equal(
        key_before, key_after)
    assert rng_states_differ_by_exactly_one_draw  # sanity: stream advances
    # the real invariant: two identical models warmup'd vs not produce the
    # same next key sequence — checked via a fresh pair
    m1, m2 = _hapi_model(), _hapi_model()
    paddle.seed(123)
    k1 = np.asarray(next_key())
    paddle.seed(123)
    m2.prebuild_warmup(man)
    k2 = np.asarray(next_key())
    np.testing.assert_array_equal(k1, k2)


def test_hapi_stale_train_entry_skipped():
    man = warmup.Manifest()
    man.add(warmup.train_step_entry([((8, 9), 'float32')],
                                    [((8, 1), 'int64')]))
    model = _hapi_model()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter('always')
        report = model.prebuild_warmup(man)
    assert report['skipped'] == 1 and report['prebuilt'] == 0
    assert any('stale' in str(w.message) for w in caught)


def test_fit_warmup_kwarg_prebuilds_before_first_step():
    from paddle_tpu.io import Dataset

    class _DS(Dataset):
        def __len__(self):
            return 8

        def __getitem__(self, i):
            rng = np.random.RandomState(i)
            return (rng.rand(8).astype('float32'),
                    np.array([i % 4], dtype='int64'))

    with warmup.capture() as man:
        src = _hapi_model()
        src.fit(_DS(), batch_size=4, epochs=1, verbose=0)
    assert 'train_step' in man.counts()

    model = _hapi_model()
    model.fit(_DS(), batch_size=4, epochs=1, verbose=0, warmup=man)
    # the prebuild compiled the step; fit's own batches reused it
    assert model._step_traces == 1


# ---------------------------------------------------------------------------
# predictor prebuild
# ---------------------------------------------------------------------------

def test_predictor_capture_prebuild_no_retrace(tmp_path):
    from paddle_tpu.inference import Config, create_predictor
    from paddle_tpu.static import InputSpec
    net = _net()
    prefix = str(tmp_path / 'm' / 'model')
    paddle.jit.save(net, prefix,
                    input_spec=[InputSpec([None, 8], 'float32')])

    def make_pred():
        pred = create_predictor(Config(prefix + '.pdmodel',
                                       prefix + '.pdiparams'))
        pred.attach_layer(_net())
        return pred

    x = np.random.rand(4, 8).astype('float32')
    src = make_pred()
    with warmup.capture() as man:
        ref = src.run([x])
    assert man.counts() == {'predictor': 1}

    pred = make_pred()
    report = pred.warmup(man)
    assert report['prebuilt'] == 1
    traces = pred._trace_count
    out = pred.run([x])
    assert pred._trace_count == traces          # AOT executable served it
    np.testing.assert_allclose(out[0], ref[0], rtol=1e-6)
    again = pred.warmup(man)
    assert again['already_cached'] == 1


# ---------------------------------------------------------------------------
# persistent cache
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('placed', [True, False])
def test_ensure_persistent_cache_placement(tmp_path, monkeypatch, placed):
    """Placed from outside: with JAX_COMPILATION_CACHE_DIR set the code
    sets NO directory (JAX reads the variable itself) and only counts
    hits/misses; unset, the cache goes to the one fixed in-checkout path."""
    import jax
    from paddle_tpu.warmup import persistent
    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, 'update',
        lambda k, v: (updates.append((k, v)), real_update(k, v))[1])
    monkeypatch.setattr(persistent, '_ensured', False)
    monkeypatch.setattr(persistent, '_cache_dir', None)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if placed:
        monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', str(tmp_path))
        assert warmup.ensure_persistent_cache() == str(tmp_path)
        assert updates == []                   # no config touched at all
        assert list(tmp_path.iterdir()) == []  # nothing appended/created
    else:
        monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR', raising=False)
        assert warmup.DEFAULT_CACHE_DIR == os.path.join(repo, '.jax_cache')
        assert warmup.ensure_persistent_cache() == warmup.DEFAULT_CACHE_DIR
        assert ('jax_compilation_cache_dir',
                warmup.DEFAULT_CACHE_DIR) in updates
    assert persistent._listener_installed
    # idempotent: construction paths call it every time
    n = len(updates)
    assert warmup.ensure_persistent_cache() == warmup.persistent_cache_dir()
    assert len(updates) == n


def test_persistent_cache_enable_write_and_stats(tmp_path):
    root = str(tmp_path / 'cache')
    resolved = warmup.enable_persistent_cache(root)
    assert resolved == root                    # no sub-directory appended
    assert warmup.persistent_cache_dir() == resolved
    import jax
    jax.jit(lambda a: a * 2 + 1).lower(
        jax.ShapeDtypeStruct((4, 4), np.float32)).compile()
    stats = warmup.cache_stats()
    assert stats['entries'] >= 1 and stats['bytes'] > 0
    assert obs.gauge('warmup.cache.entries').value >= 1
    warmup.disable_persistent_cache()
    assert warmup.persistent_cache_dir() is None


def test_persistent_cache_corrupted_dir_falls_back(tmp_path):
    root = str(tmp_path / 'bad')
    # a FILE squatting on the cache path: makedirs must fail, the engine
    # must degrade to cold compiles instead of crashing
    with open(root, 'w') as f:
        f.write('not a directory')
    before = obs.counter('warmup.cache.fallback_total').value
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter('always')
        assert warmup.enable_persistent_cache(root) is None
    assert any('unavailable' in str(w.message) for w in caught)
    assert obs.counter('warmup.cache.fallback_total').value == before + 1
    # cold compiles still work after the fallback
    import jax
    assert int(jax.jit(lambda a: a + 1)(np.int32(1))) == 2


def test_persistent_cache_inject_point_falls_back(tmp_path):
    fault.configure('warmup.cache:1.0')
    try:
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter('always')
            assert warmup.enable_persistent_cache(
                str(tmp_path / 'cache')) is None
        assert any('unavailable' in str(w.message) for w in caught)
    finally:
        fault.configure(None)
    # disarmed: the same directory now activates
    assert warmup.enable_persistent_cache(str(tmp_path / 'cache'))
    warmup.disable_persistent_cache()


# ---------------------------------------------------------------------------
# observability surface
# ---------------------------------------------------------------------------

def test_warmup_metrics_and_obs_report(tmp_path):
    eng = InferenceEngine(_net(), max_batch_size=4, max_delay_ms=0.2,
                          warmup='all_buckets',
                          input_spec=[((8,), 'float32')])
    eng.shutdown()
    snap = obs.snapshot()
    assert any(k.startswith('warmup.prebuild_ms')
               for k in snap['histograms'])
    assert any(k.startswith('warmup.prebuilt_total')
               for k in snap['counters'])
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), 'tools'))
    import obs_report
    report = obs_report.build_report(snap)
    assert 'warmup' in report['namespaces']
    text = obs_report.render_text(report)
    assert 'warmup.prebuild_ms' in text


# ---------------------------------------------------------------------------
# fresh-subprocess round trip (the acceptance shape)
# ---------------------------------------------------------------------------

_CHILD_SRC = r'''
import json, os, sys
import numpy as np
os.environ.setdefault('JAX_PLATFORMS', 'cpu')
sys.path.insert(0, sys.argv[3])
import paddle_tpu as paddle
from paddle_tpu import nn, serving, warmup
from paddle_tpu import observability as obs

warmup.enable_persistent_cache(sys.argv[2])
net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
net.eval()
engine = serving.InferenceEngine(net, max_batch_size=8, max_delay_ms=0.2,
                                 warmup=sys.argv[1])
prebuilt = engine._cache.prebuilt
rng = np.random.RandomState(0)
with engine:
    for n in (3, 7, 1):
        engine.submit(rng.rand(n, 8).astype('float32')).result(timeout=300)
snap = obs.snapshot()
compiles = sum(v for k, v in snap['counters'].items()
               if k.startswith('serve.compiles'))
print(json.dumps({'prebuilt': prebuilt, 'misses': engine._cache.misses,
                  'serve_compiles': compiles,
                  'cache_hits': snap['counters'].get(
                      'warmup.cache.hit_total', 0)}))
'''


def test_manifest_roundtrip_fresh_subprocess(tmp_path):
    """Capture + persistent cache in THIS process; a brand-new process
    prebuilds from the saved manifest and serves live traffic with zero
    serve.compiles increments."""
    cache_dir = str(tmp_path / 'cache')
    manifest_path = str(tmp_path / 'warmup.json')
    warmup.enable_persistent_cache(cache_dir)
    net = _net()
    with warmup.capture() as man:
        with InferenceEngine(net, max_batch_size=8, max_delay_ms=0.2) as eng:
            for n in (3, 7, 1):
                eng.submit(np.random.rand(n, 8).astype('float32')).result(
                    timeout=60)
    man.save(manifest_path)
    warmup.disable_persistent_cache()
    assert len(man) >= 2

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, '-c', _CHILD_SRC, manifest_path, cache_dir,
         repo_root],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, 'JAX_PLATFORMS': 'cpu'})
    assert proc.returncode == 0, proc.stderr
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result['prebuilt'] == len(man)
    assert result['misses'] == 0                # zero live compiles
    assert result['serve_compiles'] == 0        # counter agrees
    assert result['cache_hits'] > 0             # persistent cache was hit
