"""Performance explainability (ISSUE 6): XLA cost/memory analysis, live
MFU + roofline accounting, HBM tracking, and the SLO watcher.

Covers the acceptance criteria: cost-model MFU within 20% of the analytic
``6*N*tokens`` estimate on a CPU GPT config, perf_report classifying
executables compute/memory-bound, an SLO rule on serving queue-wait p99
firing under injected saturation and resolving on healthy traffic, plus
the satellite checklist (StepTimer exception safety, trace name metas,
Prometheus label escaping, disabled-mode nulls, report tooling exits).
"""
import json
import re
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import fault, nn, observability as obs
from paddle_tpu.observability import perf, slo

pytestmark = pytest.mark.perf_obs


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts from an enabled, empty registry/trace/perf state
    and leaves the process the same way."""
    obs.set_enabled(True)
    obs.reset()
    yield
    fault.configure(None)
    obs.set_enabled(True)
    obs.reset()


def _net():
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    net.eval()
    return net


def _import_tool(name):
    sys.path.insert(0, 'tools')
    try:
        return __import__(name)
    finally:
        sys.path.pop(0)


# ---------------------------------------------------------------------------
# peaks table
# ---------------------------------------------------------------------------

def test_peaks_table_and_env_override(monkeypatch):
    monkeypatch.delenv(perf.ENV_PEAK_FLOPS, raising=False)
    monkeypatch.delenv(perf.ENV_PEAK_BW, raising=False)
    f, b, src = perf.peaks('TPU v5p')
    assert (f, b, src) == (459e12, 2.76e12, 'table')
    # what a v5e chip reports as its device_kind lands on the v5e row
    assert perf.peaks('TPU v5 lite') == perf.peaks('v5e') \
        == (197e12, 0.82e12, 'table')
    assert perf.peaks('TPU v5 lite', precision='int8')[0] == 394e12
    with pytest.raises(ValueError, match='sparkletron-9000'):
        perf.peaks('sparkletron-9000')     # unknown kind: error, no default
    # env overrides win and are read per call (no import-time freeze)
    monkeypatch.setenv(perf.ENV_PEAK_FLOPS, '2e12')
    monkeypatch.setenv(perf.ENV_PEAK_BW, '1e11')
    f, b, src = perf.peaks('TPU v5p')
    assert (f, b, src) == (2e12, 1e11, 'env')


# ---------------------------------------------------------------------------
# analyze: static costs, no-retrace proof, failure accounting
# ---------------------------------------------------------------------------

def test_analyze_publishes_roofline_series_without_retrace():
    import jax
    import jax.numpy as jnp
    traces = []

    @jax.jit
    def f(x):
        traces.append(1)           # trace-time side effect
        return (x @ x.T).sum()

    x = jnp.ones((16, 16), jnp.float32)
    f(x).block_until_ready()
    assert len(traces) == 1
    rec = perf.analyze('t.fn', f, (x,))
    assert len(traces) == 1        # lower().compile() was a pure cache hit
    assert rec is not None and rec['flops'] > 0 and rec['bytes_accessed'] > 0
    assert rec['bound_by'] in ('compute', 'memory')
    assert perf.analyzed('t.fn') == rec

    g = obs.snapshot()['gauges']
    assert g['perf.flops{fn=t.fn}'] == rec['flops']
    assert g['perf.bytes_accessed{fn=t.fn}'] == rec['bytes_accessed']
    assert g['perf.arithmetic_intensity{fn=t.fn}'] == rec['intensity']
    assert g['perf.compute_bound{fn=t.fn}'] in (0.0, 1.0)
    assert g['perf.peak_flops'] > 0 and g['perf.peak_bw'] > 0
    assert g['perf.ridge'] == pytest.approx(
        g['perf.peak_flops'] / g['perf.peak_bw'], rel=1e-3)
    # HBM footprint by kind from memory_analysis()
    kinds = {k for k in g if k.startswith('perf.hbm_bytes{fn=t.fn,')}
    assert kinds, g
    assert g[f'perf.hbm_bytes{{fn=t.fn,kind=argument}}'] >= x.nbytes


def test_analyze_failure_is_counted_never_raised():
    assert perf.analyze('bad.fn', object(), ()) is None
    snap = obs.snapshot()
    assert snap['counters']['perf.analyze_errors{fn=bad.fn}'] == 1
    assert 'perf.flops{fn=bad.fn}' not in snap['gauges']


def test_note_step_joins_static_flops_with_wall_time(monkeypatch):
    import jax
    import jax.numpy as jnp
    monkeypatch.setenv(perf.ENV_PEAK_FLOPS, '1e12')
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((32, 32), jnp.float32)
    f(x).block_until_ready()

    assert perf.note_step('t.mm', 0.001) is None    # before analyze: no-op
    rec = perf.analyze('t.mm', f, (x,))
    mfu = perf.note_step('t.mm', 0.001)
    assert mfu == pytest.approx(rec['flops'] / 0.001 / 1e12, rel=1e-6)
    assert perf.note_step('t.mm', 0.0) is None      # degenerate wall time

    snap = obs.snapshot()
    assert snap['gauges']['perf.mfu{fn=t.mm}'] == pytest.approx(mfu, abs=1e-6)
    assert snap['gauges']['perf.mfu'] == pytest.approx(mfu, abs=1e-6)
    assert snap['gauges']['perf.achieved_flops{fn=t.mm}'] == pytest.approx(
        rec['flops'] / 0.001, rel=1e-6)
    assert snap['histograms']['perf.step_ms{fn=t.mm}']['count'] == 1

    rep = perf.report()
    assert rep['peak_source'] == 'env' and rep['peak_flops'] == 1e12
    row = next(r for r in rep['executables'] if r['fn'] == 't.mm')
    assert row['mfu'] == pytest.approx(mfu, abs=1e-6)
    assert row['frac_of_peak'] == pytest.approx(mfu, abs=1e-3)

    perf.reset_perf()
    assert perf.analyzed('t.mm') is None
    assert perf.report()['executables'] == []


# ---------------------------------------------------------------------------
# acceptance: cost-model MFU vs analytic 6*N*tokens on a CPU GPT config
# ---------------------------------------------------------------------------

def test_gpt_mfu_cost_model_within_20pct_of_analytic(monkeypatch):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import gpt

    # scan_unroll=num_layers matters: XLA's cost_analysis counts a While
    # body once regardless of trip count, so a scanned layer stack would
    # undercount FLOPs ~L×. Fully unrolled, the compiler's count and the
    # 6*N*tokens estimate must agree.
    cfg = gpt.GPTConfig(vocab_size=1024, hidden_size=192, num_layers=3,
                        num_heads=4, max_seq_len=128, remat=False,
                        use_flash=False, scan_unroll=3, dtype='float32')
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    n_params = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
    opt = paddle.optimizer.AdamW(learning_rate=2e-4, weight_decay=0.01)
    opt_state = opt.functional_init(params)
    step = gpt.make_train_step(cfg, opt)
    B, S = 2, cfg.max_seq_len
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    args = (params, opt_state, jax.random.PRNGKey(2), jnp.asarray(2e-4),
            toks, toks)
    loss, params, opt_state = step(*args)
    loss.block_until_ready()

    monkeypatch.setenv(perf.ENV_PEAK_FLOPS, '1e12')   # the nominal CPU peak
    rec = perf.analyze('gpt.train_step', step, args)
    assert rec is not None and rec['flops'] > 0
    analytic_flops = 6.0 * n_params * B * S
    ratio = rec['flops'] / analytic_flops
    assert 0.8 <= ratio <= 1.25, (rec['flops'], analytic_flops, ratio)

    # the MFU join uses the same peak for both estimates, so the live
    # perf.mfu gauge must agree with the analytic MFU within the same band
    wall = 0.05
    perf.note_step('gpt.train_step', wall)
    mfu_cost = obs.snapshot()['gauges']['perf.mfu{fn=gpt.train_step}']
    mfu_analytic = analytic_flops / wall / 1e12
    assert 0.8 <= mfu_cost / mfu_analytic <= 1.25

    # perf_report classifies the executable from the same snapshot
    perf_report = _import_tool('perf_report')
    report = perf_report.collect(obs.snapshot())
    row = next(r for r in report['executables']
               if r['fn'] == 'gpt.train_step')
    assert row['bound_by'] in ('compute', 'memory')
    assert row['flops'] == rec['flops']
    text = perf_report.render_text(report)
    assert 'gpt.train_step' in text and row['bound_by'] in text


# ---------------------------------------------------------------------------
# wiring: hapi train/eval steps, serving buckets, Predictor feeds
# ---------------------------------------------------------------------------

class _ToyDS(paddle.io.Dataset):
    def __len__(self):
        return 32

    def __getitem__(self, i):
        rng = np.random.RandomState(i)
        return (rng.randn(8).astype('float32'),
                np.array([i % 2], dtype='int64'))


def _toy_model():
    from paddle_tpu.hapi.model import Model
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 2))
    m = Model(net)
    m.prepare(
        optimizer=paddle.optimizer.SGD(learning_rate=0.1,
                                       parameters=net.parameters()),
        loss=nn.CrossEntropyLoss())
    return m, net


def test_hapi_fit_and_evaluate_publish_perf_series():
    m, _ = _toy_model()
    m.fit(_ToyDS(), batch_size=8, epochs=1, verbose=0)
    m.evaluate(_ToyDS(), batch_size=8, verbose=0)

    snap = obs.snapshot()
    g = snap['gauges']
    assert g['perf.flops{fn=hapi.train_step}'] > 0
    assert g['perf.flops{fn=hapi.eval_step}'] > 0
    # the measured-step join ran: MFU gauges + step_ms histogram exist
    assert 'perf.mfu{fn=hapi.train_step}' in g and g['perf.mfu'] > 0
    assert snap['histograms']['perf.step_ms{fn=hapi.train_step}']['count'] >= 1
    # the fit loop swept HBM at readback points
    assert any(k.startswith('perf.hbm_used_bytes{') for k in g), g


def test_serving_bucket_analyze_and_steady_state_mfu():
    from paddle_tpu.serving import InferenceEngine
    eng = InferenceEngine(_net(), max_batch_size=8, autostart=False)
    x = np.random.rand(2, 8).astype('float32')
    for _ in range(2):                      # miss, then steady-state hit
        fut = eng.submit(x)
        eng._drain_inline()
        assert fut.result(timeout=30).shape == (2, 4)
    eng.shutdown()

    snap = obs.snapshot()
    assert snap['gauges']['perf.flops{fn=serving.bucket2}'] > 0
    # note_step runs on the steady-state execution only
    assert snap['histograms']['perf.step_ms{fn=serving.bucket2}']['count'] == 1
    assert 'perf.mfu{fn=serving.bucket2}' in snap['gauges']


def test_predictor_feed_analyze(tmp_path):
    class Net(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(4, 3)

        def forward(self, x):
            return self.fc(x)

    net = Net()
    net.eval()
    path = str(tmp_path / 'inf')
    paddle.jit.save(net, path,
                    input_spec=[paddle.static.InputSpec([2, 4], 'float32')])
    from paddle_tpu.inference import Config, create_predictor
    pred = create_predictor(Config(path + '.pdmodel'))
    pred.attach_layer(Net())
    pred.run([np.random.rand(2, 4).astype('float32')])
    g = obs.snapshot()['gauges']
    assert g['perf.flops{fn=predictor.2x4}'] > 0
    assert 'perf.arithmetic_intensity{fn=predictor.2x4}' in g


# ---------------------------------------------------------------------------
# HBM tracking
# ---------------------------------------------------------------------------

def test_sweep_hbm_samples_real_devices():
    import jax.numpy as jnp
    keep = jnp.ones((4096,), jnp.float32) + 1
    keep.block_until_ready()
    out = perf.sweep_hbm()
    assert out and all(v >= 0 for v in out.values())
    g = obs.snapshot()['gauges']
    for key, used in out.items():
        assert g[f'perf.hbm_used_bytes{{device={key}}}'] == used
    del keep


class _FakeDev:
    platform = 'fake'
    id = 0
    used = 0

    def memory_stats(self):
        return {'bytes_in_use': self.used}


def test_hbm_leak_detector_fires_once_per_streak():
    d = _FakeDev()
    for i in range(4):                       # 4 strictly-increasing sweeps
        d.used = 1000 + i * 100
        perf.sweep_hbm(devices=[d], streak=3)
    snap = obs.snapshot()
    assert snap['counters']['perf.hbm_leak_suspect{device=fake:0}'] == 1
    assert snap['gauges']['perf.hbm_used_bytes{device=fake:0}'] == 1300
    assert any(e['name'] == 'perf.hbm_leak_suspect'
               for e in obs.trace_events())
    # steady usage: the history was reset, no follow-on false positives
    for _ in range(4):
        perf.sweep_hbm(devices=[d], streak=3)
    snap = obs.snapshot()
    assert snap['counters']['perf.hbm_leak_suspect{device=fake:0}'] == 1
    # a fresh strictly-increasing run fires again
    for i in range(4):
        d.used = 2000 + i * 100
        perf.sweep_hbm(devices=[d], streak=3)
    assert obs.snapshot()['counters'][
        'perf.hbm_leak_suspect{device=fake:0}'] == 2


def test_hbm_plateau_never_fires():
    d = _FakeDev()
    for used in (100, 200, 200, 300, 400, 400):   # growth with plateaus
        d.used = used
        perf.sweep_hbm(devices=[d], streak=3)
    assert 'perf.hbm_leak_suspect{device=fake:0}' not in \
        obs.snapshot()['counters']


# ---------------------------------------------------------------------------
# SLO watcher
# ---------------------------------------------------------------------------

def test_slo_rule_validation_and_duplicates():
    with pytest.raises(ValueError):
        slo.Rule('r', 's', 1.0, stat='p42')
    with pytest.raises(ValueError):
        slo.Rule('r', 's', 1.0, cmp='!=')
    w = slo.watcher()
    w.rule('r1', 'some.series', 1.0)
    with pytest.raises(ValueError):
        w.rule('r1', 'other.series', 2.0)
    assert [r.name for r in w.rules] == ['r1']
    assert 'p99' in slo.Rule('p', 's', 1.0, stat='p99').describe() or True
    assert w.rules[0].describe().startswith('r1:')


def test_slo_missing_series_is_not_created():
    w = slo.watcher()
    w.rule('ghost', 'never.reported', 1.0, stat='p99')
    assert w.evaluate() == []
    assert w.states() == {'ghost': 'ok'}
    snap = obs.snapshot()
    assert 'never.reported' not in json.dumps(snap)   # find() never creates


def test_slo_gauge_fire_debounce_resolve_callbacks():
    g = obs.gauge('app.depth')
    fired, resolved = [], []
    w = slo.watcher()
    w.rule('depth', 'app.depth', 10.0, stat='value', debounce=2,
           on_fire=lambda r, v: fired.append((r.name, v)),
           on_resolve=lambda r, v: resolved.append((r.name, v)))
    g.set(50)
    assert w.evaluate() == []                 # breach 1 of 2: debounced
    assert w.states() == {'depth': 'ok'}
    assert w.evaluate() == [('depth', 'fire', 50.0)]
    assert w.states() == {'depth': 'firing'}
    assert fired == [('depth', 50.0)]
    assert w.evaluate() == []                 # still breached: no re-fire
    snap = obs.snapshot()
    assert snap['counters']['slo.breaches{rule=depth}'] == 1
    assert snap['gauges']['slo.firing{rule=depth}'] == 1
    g.set(3)
    assert w.evaluate() == [('depth', 'resolve', 3.0)]
    assert resolved == [('depth', 3.0)]
    snap = obs.snapshot()
    assert snap['gauges']['slo.firing{rule=depth}'] == 0
    names = {e['name'] for e in obs.trace_events()}
    assert {'slo.fire', 'slo.resolve'} <= names
    # a dip below threshold resets the debounce streak
    g.set(50)
    w.evaluate()
    g.set(1)
    w.evaluate()
    g.set(50)
    assert w.evaluate() == []                 # streak restarted


def test_slo_histogram_delta_window_resolves_on_fresh_traffic():
    h = obs.histogram('app.lat_ms')
    w = slo.watcher()
    w.rule('p99', 'app.lat_ms', 50.0, stat='p99')
    for _ in range(20):
        h.observe(200.0)
    assert w.evaluate() == [('p99', 'fire', 200.0)]
    # stale slow samples are still inside the histogram window, but the
    # delta window sees only the fresh healthy traffic -> resolve now
    for _ in range(5):
        h.observe(2.0)
    assert w.evaluate() == [('p99', 'resolve', 2.0)]
    # no new data: state unchanged, no flapping
    assert w.evaluate() == []
    assert w.states() == {'p99': 'ok'}


def test_slo_rate_and_mean_stats():
    c = obs.counter('app.errors')
    w = slo.watcher()
    w.rule('err_rate', 'app.errors', 5.0, stat='rate')
    assert w.evaluate(now=100.0) == []        # first sample primes the rate
    c.inc(100)
    assert w.evaluate(now=110.0) == [('err_rate', 'fire', 10.0)]
    h = obs.histogram('app.ms')
    w.rule('mean', 'app.ms', 10.0, stat='mean')
    h.observe(5.0)
    h.observe(25.0)
    w.evaluate(now=120.0)
    assert w.rules[1].last_value == pytest.approx(15.0)


def test_slo_callback_errors_counted_not_raised():
    g = obs.gauge('app.x')
    g.set(100)
    w = slo.watcher()

    def boom(rule, value):
        raise RuntimeError('callback bug')

    w.rule('x', 'app.x', 1.0, on_fire=boom)
    assert w.evaluate() == [('x', 'fire', 100.0)]   # still transitions
    assert obs.snapshot()['counters']['slo.callback_errors{rule=x}'] == 1


def test_slo_watcher_background_thread():
    g = obs.gauge('app.bg')
    g.set(100)
    fired = threading.Event()
    with slo.watcher(interval=0.01) as w:
        w.rule('bg', 'app.bg', 1.0, on_fire=lambda r, v: fired.set())
        assert fired.wait(timeout=5.0)
        assert w.states() == {'bg': 'firing'}
    assert w._thread is None                  # stopped on context exit


def test_slo_serving_queue_saturation_fires_and_resolves():
    """Acceptance: a rule on serve.queue_wait_ms p99 fires while the engine
    is saturated (every dispatch raising via the serving.dispatch inject
    point) and resolves once traffic drains promptly again."""
    from paddle_tpu.serving import InferenceEngine
    eng = InferenceEngine(_net(), max_batch_size=8, autostart=False)
    fired, resolved = [], []
    w = slo.watcher()
    w.rule('queue_p99', 'serve.queue_wait_ms', 50.0,
           labels=dict(eng._stats.labels), stat='p99',
           on_fire=lambda r, v: fired.append(v),
           on_resolve=lambda r, v: resolved.append(v))

    fault.configure({'serving.dispatch': (1.0, 'raise')})
    x = np.random.rand(2, 8).astype('float32')
    futs = [eng.submit(x) for _ in range(3)]
    time.sleep(0.08)                          # queue wait accrues: >50ms
    eng._drain_inline()                       # dispatch raises InjectedFault
    for f in futs:
        with pytest.raises(fault.InjectedFault):
            f.result(timeout=30)
    snap = obs.snapshot()
    assert snap['counters']['fault.injected{point=serving.dispatch}'] >= 1

    trans = w.evaluate()
    assert [(n, k) for n, k, _ in trans] == [('queue_p99', 'fire')]
    assert fired and fired[0] >= 50.0
    snap = obs.snapshot()
    assert snap['counters']['slo.breaches{rule=queue_p99}'] == 1
    assert snap['gauges']['slo.firing{rule=queue_p99}'] == 1

    fault.configure(None)                     # saturation ends
    futs = [eng.submit(x) for _ in range(3)]
    eng._drain_inline()                       # immediate: queue wait ~0
    for f in futs:
        assert f.result(timeout=30).shape == (2, 4)
    trans = w.evaluate()
    assert [(n, k) for n, k, _ in trans] == [('queue_p99', 'resolve')]
    assert resolved and resolved[0] < 50.0
    assert obs.snapshot()['gauges']['slo.firing{rule=queue_p99}'] == 0
    eng.shutdown()


# ---------------------------------------------------------------------------
# satellite: disabled mode — NULL singletons, no registry families
# ---------------------------------------------------------------------------

def test_disabled_mode_perf_and_slo_are_null():
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: x * 2)
    x = jnp.ones((4,), jnp.float32)
    f(x).block_until_ready()

    obs.set_enabled(False)
    assert perf.analyze('x', f, (x,)) is None
    assert perf.analyze_compiled('x', None) is None
    assert perf.note_step('x', 1.0) is None
    assert perf.sweep_hbm() is None
    assert perf.report() is None
    w = slo.watcher()
    assert w is slo.NULL_WATCHER
    assert w.rule('r', 's', 1.0) is None
    assert w.evaluate() == [] and w.states() == {}
    with w as entered:
        assert entered is w
    assert w.start() is w
    w.stop()
    assert obs.find('anything') is None

    obs.set_enabled(True)
    snap = obs.snapshot()
    assert not snap['counters'] and not snap['gauges'] \
        and not snap['histograms']             # nothing materialized


# ---------------------------------------------------------------------------
# satellite: Prometheus label escaping round-trip
# ---------------------------------------------------------------------------

def test_prometheus_label_escaping_roundtrip():
    originals = {'path': 'a\\b', 'msg': 'line1\nline2 "quoted"'}
    obs.gauge('esc.g', originals).set(1.0)
    text = obs.to_prometheus()
    sample = [l for l in text.splitlines()
              if l.startswith('esc_g{')]
    assert len(sample) == 1                   # newline never splits a sample
    line = sample[0]
    assert '\\n' in line and '\\"' in line and '\\\\' in line
    # round-trip: unescape per the Prometheus text-format rules
    recovered = {}
    for k, v in re.findall(r'(\w+)="((?:[^"\\]|\\.)*)"', line):
        recovered[k] = (v.replace('\\n', '\n').replace('\\"', '"')
                        .replace('\\\\', '\\'))
    assert recovered == originals


# ---------------------------------------------------------------------------
# satellite: StepTimer exception safety
# ---------------------------------------------------------------------------

def test_steptimer_span_books_nothing_when_step_raises():
    from paddle_tpu.profiler import StepTimer
    t = StepTimer()
    with t.span('dispatch'):
        time.sleep(0.001)
    assert t._pending['dispatch'] > 0
    t.step_done()
    assert t.steps == 1

    with pytest.raises(RuntimeError):
        with t.span('dispatch'):
            time.sleep(0.001)
            raise RuntimeError('step blew up')
    assert t._pending['dispatch'] == 0.0      # partial duration dropped

    def flaky():
        yield 1
        raise RuntimeError('iterator blew up')

    it = t.timed_iter('data', flaky())
    assert next(it) == 1
    booked = t._pending['data']
    with pytest.raises(RuntimeError):
        next(it)
    assert t._pending['data'] == booked       # raising next() books nothing

    t.add('readback', 1.0)
    t.abort_step()
    assert all(v == 0.0 for v in t._pending.values())
    t.step_done()
    assert t.steps == 2
    assert t._histogram('readback').percentile(99) == 0.0


def test_fit_aborts_timer_on_raising_step():
    from paddle_tpu.profiler import StepTimer

    class _BadDS(paddle.io.Dataset):
        def __len__(self):
            return 8

        def __getitem__(self, i):
            if i >= 4:
                raise RuntimeError('poisoned sample')
            rng = np.random.RandomState(i)
            return (rng.randn(8).astype('float32'),
                    np.array([i % 2], dtype='int64'))

    m, _ = _toy_model()
    timer = m._step_timer = StepTimer()
    with pytest.raises(RuntimeError):
        m.fit(_BadDS(), batch_size=4, epochs=1, verbose=0, shuffle=False)
    # the aborted step left no partial booking behind
    assert all(v == 0.0 for v in timer._pending.values())


# ---------------------------------------------------------------------------
# satellite: Chrome-trace process/thread name metadata
# ---------------------------------------------------------------------------

def test_trace_process_and_thread_name_metas(tmp_path):
    with obs.span('main.work'):
        pass
    t = threading.Thread(target=lambda: obs.record_event('worker.evt'),
                         name='wk-thread')
    t.start()
    t.join()
    path = tmp_path / 'trace.json'
    obs.dump_trace(str(path))
    doc = json.loads(path.read_text())
    metas = [e for e in doc['traceEvents'] if e.get('ph') == 'M']
    assert any(e['name'] == 'process_name' and 'args' in e for e in metas)
    tnames = {e['args']['name'] for e in metas
              if e['name'] == 'thread_name'}
    assert 'wk-thread' in tnames
    assert threading.current_thread().name in tnames
    # metas carry pid/tid like real samples so chrome://tracing groups them
    for e in metas:
        assert 'pid' in e
        if e['name'] == 'thread_name':
            assert 'tid' in e


# ---------------------------------------------------------------------------
# satellite: report tooling exit codes + rendering
# ---------------------------------------------------------------------------

def test_report_tools_fail_loudly_on_empty_snapshot(tmp_path, capsys):
    (tmp_path / 'snapshot.json').write_text(json.dumps(
        {'ts': 0, 'counters': {}, 'gauges': {}, 'histograms': {}}))
    obs_report = _import_tool('obs_report')
    perf_report = _import_tool('perf_report')
    assert obs_report.main([str(tmp_path)]) == 3
    assert perf_report.main([str(tmp_path)]) == 3
    err = capsys.readouterr().err
    assert 'no metrics' in err and 'no perf.* series' in err
    assert obs_report.main([str(tmp_path / 'missing.json')]) == 2
    assert perf_report.main([str(tmp_path / 'missing.json')]) == 2
    # metrics present but nothing perf-instrumented: perf_report still 3
    (tmp_path / 'snapshot.json').write_text(json.dumps(
        {'ts': 0, 'counters': {'train.steps': 4}, 'gauges': {},
         'histograms': {}}))
    assert obs_report.main([str(tmp_path)]) == 0
    assert perf_report.main([str(tmp_path)]) == 3


def test_perf_report_renders_roofline_from_dump(tmp_path, capsys):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((32, 32), jnp.float32)
    f(x).block_until_ready()
    perf.analyze('demo.mm', f, (x,))
    perf.note_step('demo.mm', 0.002)
    perf.sweep_hbm(devices=[_FakeDev()])
    obs.dump(str(tmp_path / 'd'))

    perf_report = _import_tool('perf_report')
    assert perf_report.main([str(tmp_path / 'd')]) == 0
    out = capsys.readouterr().out
    assert 'roofline' in out and 'demo.mm' in out
    assert 'compute' in out or 'memory' in out
    assert 'hbm' in out
    assert perf_report.main([str(tmp_path / 'd'), '--json']) == 0
    doc = json.loads(capsys.readouterr().out)
    row = next(r for r in doc['executables'] if r['fn'] == 'demo.mm')
    assert row['flops'] > 0 and row['step_ms_p50'] is not None

    # obs_report folds the new namespaces into its per-namespace rollup
    obs_report = _import_tool('obs_report')
    assert 'perf' in obs_report.NAMESPACES and 'slo' in obs_report.NAMESPACES
    assert obs_report.main([str(tmp_path / 'd')]) == 0
    assert 'perf' in capsys.readouterr().out
