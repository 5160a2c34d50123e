"""Device-time attribution + goodput ledger tests (ISSUE 19).

Golden-trace classification (categories, overlap, idle, unknown
fallback, gz + B/E schema tolerance), the measured-MFU join, goodput/
badput bookkeeping, the /debug/goodput endpoint, profile-artifact
retention, registry self-metrics, and a live CPU end-to-end capture.
"""
import gzip
import json
import os
import shutil
import threading
import time
import urllib.request

import pytest

pytestmark = pytest.mark.devtime

from paddle_tpu import observability as obs
from paddle_tpu.observability import devtime, fleetobs, goodput

FIXTURE = os.path.join(os.path.dirname(__file__), 'fixtures', 'devtime',
                       'golden.trace.json')


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.set_enabled(True)
    obs.reset()
    yield
    obs.set_enabled(True)
    obs.reset()


def _get(url, timeout=15):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.read()


# ---------------------------------------------------------------------------
# golden trace: classification + sweep math
# ---------------------------------------------------------------------------

def test_golden_category_bucketing():
    s = devtime.attribute(FIXTURE, publish=False)
    assert s['classifier_version'] == devtime.CLASSIFIER_VERSION
    assert s['window_source'] == 'events'
    # exclusive attribution: collective [5,15], matmul [0,10] minus the
    # collective overlap, copy [20,22], the unknown op as compute [23,24]
    assert s['categories_ms']['collective'] == 10.0
    assert s['categories_ms']['matmul'] == 5.0
    assert s['categories_ms']['copy'] == 2.0
    assert s['categories_ms']['infeed'] == 0.0
    assert s['categories_ms']['compute'] == 1.0
    assert s['device_lanes'] == 2
    assert s['per_lane_busy_ms'] == {'1': 18.0, '2': 14.0}
    # lane 1 last op ends at 24 ms, lane 2 at 14 ms
    assert s['straggler_skew_ms'] == 10.0
    # host lane (PjitFunction + buffer wait) never counts as device time
    assert s['host_events'] == 2


def test_golden_sum_invariant_and_idle_gap():
    # events window: [0, 24] ms -> idle fills the uncovered 6 ms
    spans = len(obs.trace_events())
    s = devtime.attribute(FIXTURE, publish=False)
    assert len(obs.trace_events()) == spans    # attribution opens no span
    assert s['window_ms'] == 24.0
    assert s['idle_ms'] == 6.0
    assert sum(s['categories_ms'].values()) == pytest.approx(
        s['window_ms'], abs=1e-6)
    # a pinned capture window stretches idle, never the busy categories
    s = devtime.attribute(FIXTURE, window_ms=25, publish=False)
    assert s['window_source'] == 'capture'
    assert s['window_ms'] == 25.0
    assert s['idle_ms'] == 7.0
    assert s['categories_ms']['collective'] == 10.0
    assert sum(s['categories_ms'].values()) == pytest.approx(25.0, abs=1e-6)


def test_golden_overlap_fraction():
    s = devtime.attribute(FIXTURE, publish=False)
    # collective spans [5,15] (10 ms); matmul runs under it in [5,10]
    assert s['overlap']['collective_ms'] == 10.0
    assert s['overlap']['hidden_ms'] == 5.0
    assert s['overlap']['fraction'] == 0.5


def test_golden_unknown_event_fallback():
    s = devtime.attribute(FIXTURE, publish=False)
    # 'zorble-op.9' matches no rule: compute fallback on a device lane,
    # counted so schema drift is visible
    assert s['unknown_events'] == 1
    assert s['categories_ms']['compute'] == 1.0


def test_gz_and_plain_json_give_identical_results(tmp_path):
    plain = devtime.attribute(FIXTURE, publish=False)
    gz = tmp_path / 'host.trace.json.gz'
    with open(FIXTURE, 'rb') as f:
        gz.write_bytes(gzip.compress(f.read()))
    assert devtime.find_trace_files(str(tmp_path)) == [str(gz)]
    zipped = devtime.attribute(str(tmp_path), publish=False)
    assert zipped['categories_ms'] == plain['categories_ms']
    assert zipped['overlap'] == plain['overlap']
    doc = devtime.load_trace(str(gz))
    assert len(doc['traceEvents']) == 13


def test_begin_end_pair_folding():
    events = [
        {'ph': 'B', 'pid': 1, 'tid': 1, 'ts': 100, 'name': 'fusion.1'},
        {'ph': 'B', 'pid': 1, 'tid': 1, 'ts': 200, 'name': 'fusion.1'},
        {'ph': 'E', 'pid': 1, 'tid': 1, 'ts': 300, 'name': 'fusion.1'},
        {'ph': 'E', 'pid': 1, 'tid': 1, 'ts': 600, 'name': 'fusion.1'},
        {'ph': 'E', 'pid': 2, 'tid': 1, 'ts': 900, 'name': 'orphan'},
    ]
    out = devtime._complete_events(events)
    # LIFO pairing per (pid, tid, name); the unmatched E is dropped
    assert [(e['ts'], e['dur']) for e in out] == [(200, 100), (100, 500)]


def test_classifier_versioning():
    assert devtime.classifier().version == devtime.CLASSIFIER_VERSION
    with pytest.raises(ValueError, match='unknown classifier version'):
        devtime.classifier(99)
    c = devtime.classifier(1)
    assert c.classify('all-reduce.17') == ('collective', True)
    assert c.classify('dot.3') == ('matmul', True)
    assert c.classify('copy-start.1') == ('copy', True)
    assert c.classify('infeed.0') == ('infeed', True)
    assert c.classify('fusion.42') == ('compute', True)
    assert c.classify('PjitFunction(step)') == ('host', True)
    assert c.classify('mystery-op', device_lane=True) == ('compute', False)
    assert c.classify('mystery-op', device_lane=False) == ('host', True)


# ---------------------------------------------------------------------------
# measured MFU join
# ---------------------------------------------------------------------------

def test_mfu_join_counts_outermost_execs(monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_PEAK_FLOPS', '1e9')
    doc = {'traceEvents': [
        {'ph': 'M', 'pid': 1, 'name': 'process_name',
         'args': {'name': '/device:TPU:0'}},
        {'ph': 'X', 'pid': 1, 'tid': 1, 'ts': 0, 'dur': 1000,
         'name': 'jit_train_step'},
        {'ph': 'X', 'pid': 1, 'tid': 1, 'ts': 2000, 'dur': 1000,
         'name': 'jit_train_step'},
        # nested profiler duplicate of the second call: must not count
        {'ph': 'X', 'pid': 1, 'tid': 1, 'ts': 2000, 'dur': 500,
         'name': 'jit_train_step'},
    ]}
    records = {'hapi.train_step': {'flops': 1.5e6, 'module':
                                   'jit_train_step', 'pyname': 'train_step',
                                   'precision': None}}
    s = devtime.attribute(doc, publish=False, records=records)
    m = s['mfu_measured']['hapi.train_step']
    # 2 outermost execs x 1.5e6 flops over a 3 ms window at 1 GFLOP/s peak
    assert m['execs'] == 2
    assert m['mfu'] == pytest.approx(1.0)
    assert s['mfu_measured']['total'] == pytest.approx(1.0)


def test_mfu_join_falls_back_to_dispatch_name(monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_PEAK_FLOPS', '1e9')
    # CPU-backend shape: no device lanes, only the host dispatch events
    doc = {'traceEvents': [
        {'ph': 'X', 'pid': 1, 'tid': 1, 'ts': 0, 'dur': 1000,
         'name': 'PjitFunction(train_step)'},
        {'ph': 'X', 'pid': 1, 'tid': 1, 'ts': 5000, 'dur': 1000,
         'name': 'PjitFunction(train_step)'},
        {'ph': 'X', 'pid': 1, 'tid': 1, 'ts': 0, 'dur': 10000,
         'name': 'TfrtCpuExecutable::Execute'},
    ]}
    records = {'fn': {'flops': 2e6, 'module': None,
                      'pyname': 'train_step', 'precision': None}}
    s = devtime.attribute(doc, publish=False, records=records)
    assert s['mfu_measured']['fn']['execs'] == 2
    assert s['mfu_measured']['fn']['mfu'] == pytest.approx(0.4)


def test_attribute_publishes_gauges(monkeypatch):
    monkeypatch.setenv('PADDLE_TPU_PEAK_FLOPS', '1e9')
    records = {'fn': {'flops': 1.5e6, 'module': 'dot.1',
                      'pyname': None, 'precision': None}}
    devtime.attribute(FIXTURE, records=records)
    g = obs.snapshot()['gauges']
    assert g['devtime.window_ms'] == 24.0
    assert g['devtime.category_ms{category=collective}'] == 10.0
    assert g['devtime.category_ms{category=idle}'] == 6.0
    assert g['devtime.overlap_fraction'] == 0.5
    assert g['devtime.straggler_skew_ms'] == 10.0
    assert g['devtime.unknown_events'] == 1
    assert g['perf.mfu_measured{fn=fn}'] > 0
    assert g['perf.mfu_measured'] == g['perf.mfu_measured{fn=fn}']
    c = obs.snapshot()['counters']
    assert c['devtime.captures_analyzed'] == 1


# ---------------------------------------------------------------------------
# goodput ledger
# ---------------------------------------------------------------------------

def test_ledger_run_window_and_ratio():
    led = goodput.GoodputLedger()
    assert led.ratio() == 1.0            # no run yet
    led.run_start()
    time.sleep(0.05)
    led.note_badput('checkpoint', 0.02)
    led.note_step(0.001)
    led.run_end()
    snap = led.snapshot()
    assert snap['runs'] == 1 and snap['steps'] == 1
    assert not snap['run_active']
    assert snap['elapsed_s'] >= 0.05
    assert snap['badput_s']['checkpoint'] == pytest.approx(0.02)
    assert 0.0 < snap['ratio'] < 1.0
    assert snap['goodput_s'] == pytest.approx(
        snap['elapsed_s'] - 0.02, abs=1e-6)


def test_a_stalled_save_is_booked_as_checkpoint_badput(tmp_path):
    """paddle.save reports its whole duration, an injected stall included,
    to the process ledger under 'checkpoint'."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import fault
    led = goodput.ledger()
    led.run_start()
    fault.configure('ckpt.write:1.0:delay:0.2', seed=7, max_faults=1)
    try:
        paddle.save({'w': np.ones(4, np.float32)}, str(tmp_path / 'w.pd'))
    finally:
        fault.configure(None)
        led.run_end()
    assert goodput.snapshot()['badput_s']['checkpoint'] >= 0.2


def test_badput_outside_run_counts_lifetime_only():
    led = goodput.GoodputLedger()
    led.note_badput('compile', 1.0)
    snap = led.snapshot()
    assert snap['badput_s']['compile'] == 0.0
    assert snap['badput_lifetime_s']['compile'] == 1.0
    assert snap['ratio'] == 1.0          # no elapsed window to steal from


def test_unknown_cause_maps_to_requeue():
    led = goodput.GoodputLedger()
    led.run_start()
    led.note_badput('cosmic_rays', 0.01)
    led.run_end()
    assert led.snapshot()['badput_s']['requeue'] == pytest.approx(0.01)


def test_data_wait_floor(monkeypatch):
    monkeypatch.setenv(goodput.ENV_DATA_FLOOR, '10')
    led = goodput.GoodputLedger()
    led.run_start()
    led.note_data_wait(0.005)            # under the 10 ms floor: hidden
    led.note_data_wait(0.025)            # 15 ms over the floor: stall
    led.run_end()
    assert led.snapshot()['badput_s']['data_stall'] == pytest.approx(
        0.015, abs=1e-9)


def test_ratio_clamps_to_zero():
    led = goodput.GoodputLedger()
    led.run_start()
    led.note_badput('preemption', 1e6)
    led.run_end()
    assert led.ratio() == 0.0


def test_data_iter_wraps_and_preserves_items():
    led = goodput.GoodputLedger()
    led.run_start()
    assert list(led.data_iter(iter([1, 2, 3]))) == [1, 2, 3]
    led.run_end()


def test_ledger_disabled_is_noop():
    obs.set_enabled(False)
    led = goodput.GoodputLedger()
    led.run_start()
    led.note_step(0.1)
    led.note_badput('checkpoint', 5.0)
    snap = led.snapshot()
    assert snap['enabled'] is False
    assert snap['runs'] == 0 and snap['steps'] == 0
    assert snap['badput_s']['checkpoint'] == 0.0
    it = [1, 2]
    assert led.data_iter(it) is it


def test_debug_goodput_endpoint():
    goodput.reset_goodput()
    led = goodput.ledger()
    led.run_start()
    led.note_badput('checkpoint', 0.01)
    led.run_end()
    srv = obs.serve_telemetry(port=0)
    try:
        code, body = _get(srv.url + '/debug/goodput')
        doc = json.loads(body)
        assert code == 200
        assert doc['runs'] == 1
        assert doc['badput_s']['checkpoint'] == pytest.approx(0.01)
        assert 0.0 <= doc['ratio'] <= 1.0
    finally:
        srv.stop()
        goodput.reset_goodput()


# ---------------------------------------------------------------------------
# artifact retention + registry self-metrics
# ---------------------------------------------------------------------------

def test_profile_gc_keeps_newest(tmp_path, monkeypatch):
    monkeypatch.setenv(fleetobs.ENV_PROFILE_KEEP, '2')
    dirs = []
    for i in range(5):
        d = tmp_path / f'{fleetobs.PROFILE_DIR_PREFIX}{i}'
        d.mkdir()
        (d / 'x.trace.json').write_text('{}')
        os.utime(d, (1000 + i, 1000 + i))
        dirs.append(d)
    removed = fleetobs._gc_profile_dirs(str(dirs[-1]))
    assert removed == 3
    left = sorted(p.name for p in tmp_path.iterdir())
    assert left == [f'{fleetobs.PROFILE_DIR_PREFIX}3',
                    f'{fleetobs.PROFILE_DIR_PREFIX}4']
    assert obs.snapshot()['counters']['fleet.obs.profile_gc_total'] == 3


def test_obs_self_metrics():
    obs.counter('some.counter').inc()
    obs.gauge('some.gauge').set(1.0)
    cap0 = obs.trace_cap()
    obs.set_trace_cap(4)
    try:
        for i in range(10):
            with obs.span(f'ev{i}'):
                pass
        snap = obs.snapshot()
    finally:
        obs.set_trace_cap(cap0)
    assert snap['gauges']['obs.series_total'] >= 2
    assert snap['gauges']['obs.trace_dropped_total'] >= 6


# ---------------------------------------------------------------------------
# live CPU end-to-end
# ---------------------------------------------------------------------------

def test_live_capture_attributes_real_trace(tmp_path, monkeypatch):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.observability import perf

    monkeypatch.setenv(fleetobs.ENV_PROFILE_DIR, str(tmp_path))

    def train_step(x):
        return (x @ x).sum()

    jstep = jax.jit(train_step)
    x = jnp.ones((128, 128), jnp.float32)
    jstep(x).block_until_ready()
    perf.analyze('e2e.train_step', jstep, (x,))

    stop = threading.Event()

    def traffic():
        while not stop.is_set():
            jstep(x).block_until_ready()
            time.sleep(0.001)   # yield: a busy spin starves the profiler

    th = threading.Thread(target=traffic, daemon=True)
    th.start()
    try:
        summary = fleetobs.capture_profile(150)
    finally:
        stop.set()
        th.join()
    try:
        dv = summary['devtime']
        assert 'error' not in dv
        assert dv['events'] > 0
        assert dv['busy_ms'] > 0
        total = sum(dv['categories_ms'].values())
        assert total == pytest.approx(dv['window_ms'], rel=0.05), (total, dv)
        assert 0.0 <= dv['overlap']['fraction'] <= 1.0
        g = obs.snapshot()['gauges']
        assert g['devtime.window_ms'] == dv['window_ms']
    finally:
        shutil.rmtree(summary['artifact_dir'], ignore_errors=True)
