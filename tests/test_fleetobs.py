"""Fleet observability plane (ISSUE 14): metric federation with semantic
aggregates, cross-replica request stitching, and bounded on-demand device
profiling.

Covers: the shared Prometheus exposition parser round-tripping escaped
label values, ``# HELP`` lines in the registry exposition, the flight
recorder's evicted archive keeping rid lookups alive past ring eviction,
counter sums that are bit-equal to the per-replica totals, gauge
federation semantics (sum/min/mean + runtime registration), histogram
quantiles over the merged sample window vs the conservative max degrade
for URL sources, per-replica staleness and scrape-error accounting, the
stitcher collapsing duplicate parts/events and deriving failover
attempts, the ``/debug/fleet`` and ``/debug/profile`` endpoints (second
concurrent capture → 409), ``ModelHost.debug_table``, telemetry-server
shutdown racing a concurrent scrape, and disabled-mode inertness.
"""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax  # noqa: F401  (profiler capture needs jax importable)

from paddle_tpu import nn
from paddle_tpu import observability as obs
from paddle_tpu.observability import fleetobs, promparse
from paddle_tpu.observability import server as _server
from paddle_tpu.serving import InferenceEngine, ModelHost

pytestmark = pytest.mark.fleetobs

MB = 1 << 20


@pytest.fixture(autouse=True)
def _clean_obs():
    obs.set_enabled(True)
    obs.reset()
    with _server._probes_lock:
        probes0 = dict(_server._probes)
    yield
    obs.shutdown_telemetry()
    with _server._probes_lock:
        _server._probes.clear()
        _server._probes.update(probes0)
    obs.set_enabled(True)
    obs.reset()


def _get(url, timeout=30):
    try:
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return r.status, r.read().decode('utf-8')
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode('utf-8')


class _FakeRep:
    def __init__(self, name, label, state='ready', kind='infer'):
        self.name = name
        self._label = label
        self.state = state
        self.kind = kind

    @property
    def label(self):
        return self._label

    def probe(self):
        return {'ready': self.state == 'ready', 'warm': True,
                'breaker': 'closed', 'queue_depth': 0,
                'queue_capacity': 16}


class _FakeSet:
    def __init__(self, reps, name='fakefleet'):
        self._reps = list(reps)
        self.name = name

    def snapshot(self):
        return list(self._reps)


class _FakeRouter:
    def __init__(self, reps, name='fakefleet'):
        self.set = _FakeSet(reps, name=name)
        self.name = name


def _two_replica_metrics():
    """Two in-process 'replicas' (engine labels e0/e1) with counters,
    gauges, and histograms in the shared registry."""
    obs.counter('serve.requests', {'engine': 'e0'},
                help='requests accepted').inc(3)
    obs.counter('serve.requests', {'engine': 'e1'}).inc(4)
    obs.gauge('perf.mfu', {'engine': 'e0'}).set(0.5)
    obs.gauge('perf.mfu', {'engine': 'e1'}).set(0.7)
    obs.gauge('host.hbm_watermark_bytes', {'engine': 'e0'}).set(100.0)
    obs.gauge('host.hbm_watermark_bytes', {'engine': 'e1'}).set(60.0)
    h0 = obs.histogram('serve.queue_wait_ms', {'engine': 'e0'})
    for v in (1.0, 2.0, 3.0, 10.0):
        h0.observe(v)
    h1 = obs.histogram('serve.queue_wait_ms', {'engine': 'e1'})
    for v in (5.0, 6.0):
        h1.observe(v)
    fed = fleetobs.MetricFederator(name='t')
    fed.add_replica_set(_FakeSet([_FakeRep('r0', 'e0'),
                                  _FakeRep('r1', 'e1')]))
    return fed


# ---------------------------------------------------------------------------
# promparse: the one shared exposition parser
# ---------------------------------------------------------------------------

def test_promparse_roundtrip_escaped_labels():
    gnarly = 'a\\b"c\nd,e=f{g}'
    obs.counter('serve.requests', {'route': gnarly}, help='with\nnewline') \
        .inc(7)
    obs.gauge('gen.occupancy').set(0.25)
    text = obs.to_prometheus()
    snap = promparse.parse_text(text)
    key = promparse.fmt_key('serve_requests', {'route': gnarly})
    assert snap['counters'][key] == 7
    # the exact-labels map preserves values that would corrupt a naive
    # key re-split (commas, equals, braces inside label values)
    assert snap['labels'][key] == {'route': gnarly}
    assert snap['gauges']['gen_occupancy'] == 0.25
    assert snap['help']['serve_requests'] == 'with\nnewline'


def test_promparse_unescape_label_roundtrip():
    for raw in ('plain', 'back\\slash', 'quo"te', 'new\nline',
                'mix\\"\n\\\\end'):
        esc = (raw.replace('\\', '\\\\').replace('"', '\\"')
               .replace('\n', '\\n'))
        assert promparse.unescape_label(esc) == raw


def test_promparse_summary_quantiles():
    h = obs.histogram('serve.batch_ms')
    for v in range(1, 101):
        h.observe(float(v))
    snap = promparse.parse_text(obs.to_prometheus())
    st = snap['histograms']['serve_batch_ms']
    assert st['count'] == 100 and st['sum'] == 5050.0
    # nearest-rank convention (registry.percentile): s[int(n*q/100)]
    assert st['p50'] == 51.0 and st['p99'] == 100.0
    assert st['mean'] == pytest.approx(50.5)


# ---------------------------------------------------------------------------
# registry HELP lines
# ---------------------------------------------------------------------------

def test_exposition_has_help_for_every_family():
    obs.counter('serve.requests', help='requests accepted').inc()
    obs.gauge('gen.occupancy').set(0.5)          # no explicit help
    lines = obs.to_prometheus().splitlines()
    assert '# HELP serve_requests requests accepted' in lines
    # default help is the metric name, so strict scrapers always see one
    assert '# HELP gen_occupancy gen.occupancy' in lines
    # HELP immediately precedes its TYPE for every family
    for i, ln in enumerate(lines):
        if ln.startswith('# TYPE '):
            fam = ln.split()[2]
            assert lines[i - 1].startswith(f'# HELP {fam} ')


def test_help_upgrades_from_default_but_explicit_wins():
    obs.counter('fault.retries')                       # default (name)
    assert obs.registry().help_text('fault.retries') == 'fault.retries'
    obs.counter('fault.retries', help='retry attempts')
    assert obs.registry().help_text('fault.retries') == 'retry attempts'
    obs.counter('fault.retries', help='something else')
    assert obs.registry().help_text('fault.retries') == 'retry attempts'


# ---------------------------------------------------------------------------
# flight recorder: evicted archive
# ---------------------------------------------------------------------------

def test_requests_by_rid_survive_ring_eviction():
    rec = obs.recorder()
    rec.set_capacity(4)
    try:
        r = rec.start('serve', engine='e0')
        r.note('enqueue')
        r.finish('ok')
        # fresh healthy traffic pushes it out of the main ring (the
        # archive is itself bounded at `capacity`, so stay within one
        # extra generation)
        for _ in range(6):
            rec.start('serve', engine='e0').finish('ok')
        done_ids = {d['id'] for d in rec.requests()}
        assert r.rid not in done_ids          # out of the main ring...
        found = rec.requests(rid=r.rid)       # ...but the archive has it
        assert len(found) == 1 and found[0]['outcome'] == 'ok'
        assert rec.lookup(r.rid) is not None
    finally:
        rec.set_capacity(256)
        rec.reset()


# ---------------------------------------------------------------------------
# federation math
# ---------------------------------------------------------------------------

def test_counters_sum_bit_equal_and_replica_rows():
    fed = _two_replica_metrics()
    snap = fed.collect()
    assert snap.aggregate('serve_requests') == 3 + 4
    text = snap.to_prometheus()
    lines = text.splitlines()
    assert 'serve_requests 7' in lines
    assert 'serve_requests{replica="r0"} 3' in lines
    assert 'serve_requests{replica="r1"} 4' in lines


def test_gauge_semantics_min_mean_sum_and_registration():
    fed = _two_replica_metrics()
    snap = fed.collect()
    # watermark federates as the binding constraint (min)
    assert snap.aggregate('host_hbm_watermark_bytes') == 60.0
    # MFU-style ratios average
    assert snap.aggregate('perf_mfu') == pytest.approx(0.6)
    obs.gauge('data.prefetch_depth', {'engine': 'e0'}).set(2.0)
    obs.gauge('data.prefetch_depth', {'engine': 'e1'}).set(5.0)
    assert fed.collect().aggregate('data_prefetch_depth') == 7.0  # default
    fleetobs.register_gauge_semantics('data.prefetch_depth', 'max')
    assert fed.collect().aggregate('data_prefetch_depth') == 5.0
    with pytest.raises(ValueError):
        fleetobs.register_gauge_semantics('x', 'median')


def test_histogram_quantiles_from_merged_window():
    fed = _two_replica_metrics()
    agg = fed.collect().aggregate('serve_queue_wait_ms')
    assert agg['count'] == 6
    assert agg['sum'] == pytest.approx(27.0)
    assert agg['merged_window'] is True
    # nearest-rank over the MERGED window [1,2,3,5,6,10], not an average
    # of per-replica quantiles
    assert agg['p50'] == 5.0
    assert agg['p99'] == 10.0


def test_url_source_federates_and_degrades_quantiles():
    obs.counter('serve.requests').inc(5)
    h = obs.histogram('serve.batch_ms')
    for v in (2.0, 4.0, 8.0):
        h.observe(v)
    srv = obs.serve_telemetry(port=0)
    fed = fleetobs.MetricFederator(name='u')
    fed.add_url('remote0', srv.url)
    snap = fed.collect()
    assert snap.aggregate('serve_requests') == 5
    agg = snap.aggregate('serve_batch_ms')
    # a URL source only exposes p50/p90/p99 — no raw window, so the fleet
    # quantile is the conservative per-replica maximum
    assert agg['merged_window'] is False
    assert agg['count'] == 3 and agg['p99'] == 8.0
    srv.stop()


def test_staleness_and_scrape_errors():
    fed = fleetobs.MetricFederator(name='s')
    rep = _FakeRep('r0', 'e0')
    fed.add_replica_set(_FakeSet([rep]))
    fed.add_url('ghost', 'http://127.0.0.1:9/')   # nothing listens there
    obs.counter('serve.requests', {'engine': 'e0'}).inc(2)
    snap = fed.collect()
    assert snap.staleness['r0'] == 0.0
    assert snap.staleness['ghost'] is None        # never reported
    assert 'ghost' in snap.errors
    errs = obs.find('fleet.obs.scrape_errors', {'replica': 'ghost'})
    assert errs is not None and errs.value >= 1
    # the replica dies: cached series keep serving, staleness grows
    rep.state = 'dead'
    time.sleep(0.02)
    snap2 = fed.collect()
    assert snap2.aggregate('serve_requests') == 2     # from the cache
    assert snap2.staleness['r0'] > 0.0
    text = snap2.to_prometheus()
    assert 'fleet_obs_staleness_s{replica="ghost"} -1' in text


# ---------------------------------------------------------------------------
# stitching
# ---------------------------------------------------------------------------

def _failover_parts(rid):
    base = time.time()
    part = {'id': rid, 'kind': 'fleet', 'engine': 'fleet0',
            'wall_start': base, 'outcome': 'ok', 'error': None,
            'duration_ms': 30.0, 'attrs': {},
            'timeline': [
                {'ev': 'enqueue', 't_ms': 0.0},
                {'ev': 'route', 't_ms': 1.0, 'replica': 'r0'},
                {'ev': 'failover', 't_ms': 10.0, 'frm': 'r0',
                 'error': 'ReplicaDeadError'},
                {'ev': 'route', 't_ms': 11.0, 'replica': 'r1'},
                {'ev': 'retire', 't_ms': 30.0}]}
    return part


def test_stitch_derives_failover_attempts():
    rid = 'fleet-abc-000001'
    st = fleetobs.stitch_records(rid, [_failover_parts(rid)])
    assert st['found'] and st['parts'] == 1
    assert st['replicas'] == ['r0', 'r1']
    assert [a['outcome'] for a in st['attempts']] == ['failover', 'ok']
    assert st['attempts'][0]['error'] == 'ReplicaDeadError'
    assert st['outcome'] == 'ok'


def test_stitch_dedups_identical_parts_and_events():
    rid = 'fleet-abc-000002'
    p = _failover_parts(rid)
    # the same record reached through the local recorder AND a peer URL
    st = fleetobs.stitch_records(rid, [p, json.loads(json.dumps(p))])
    assert st['parts'] == 1
    assert len(st['timeline']) == 5               # zero duplicate events
    evs = [e['ev'] for e in st['timeline']]
    assert evs.count('failover') == 1


def test_stitch_merges_parts_on_wall_clock():
    rid = 'serve-abc-000003'
    base = time.time()
    part_a = {'id': rid, 'engine': 'e0', 'kind': 'serve',
              'wall_start': base, 'outcome': 'error',
              'error': 'ReplicaDeadError', 'duration_ms': 5.0, 'attrs': {},
              'timeline': [{'ev': 'enqueue', 't_ms': 0.0},
                           {'ev': 'route', 't_ms': 0.5, 'replica': 'r0'}]}
    part_b = {'id': rid, 'engine': 'e1', 'kind': 'serve',
              'wall_start': base + 0.010, 'outcome': None, 'error': None,
              'duration_ms': None, 'attrs': {},
              'timeline': [{'ev': 'enqueue', 't_ms': 0.0},
                           {'ev': 'retire', 't_ms': 2.0}]}
    st = fleetobs.stitch_records(rid, [part_b, part_a])
    assert st['parts'] == 2
    # wall-clock ordering interleaves the two parts' events correctly
    assert [e['ev'] for e in st['timeline']] == [
        'enqueue', 'route', 'enqueue', 'retire']
    assert st['timeline'][2]['t_ms'] == pytest.approx(10.0, abs=0.5)
    assert st['timeline'][2]['source'] == 'e1'


def test_stitch_unknown_rid():
    st = fleetobs.stitch('no-such-rid')
    assert st == {'id': 'no-such-rid', 'found': False, 'parts': 0,
                  'attempts': [], 'timeline': []}


# ---------------------------------------------------------------------------
# the HTTP face: federated /metrics, /debug/fleet, stitched ?id=
# ---------------------------------------------------------------------------

def test_fleetobs_server_federates_and_stitches():
    obs.counter('serve.requests', {'engine': 'e0'}).inc(2)
    fobs = fleetobs.FleetObs(name='httpfleet')
    fobs.watch_router(_FakeRouter([_FakeRep('r0', 'e0')]))
    srv = fobs.serve(port=0)
    code, body = _get(srv.url + '/metrics')
    assert code == 200
    assert 'serve_requests{replica="r0"} 2' in body
    assert 'fleet_obs_collect_ms' in body

    code, body = _get(srv.url + '/debug/fleet')
    table = json.loads(body)
    assert code == 200
    row = table['replicas'][0]
    assert row['replica'] == 'r0' and row['state'] == 'ready'
    assert row['breaker'] == 'closed' and row['queue_depth'] == 0
    assert table['hosts'] == []
    assert table['profile_in_flight'] is False

    r = obs.start_request('serve', engine='e0')
    r.note('enqueue')
    r.note('route', replica='r0')
    r.finish('ok')
    code, body = _get(srv.url + '/debug/requests?id=' + r.rid)
    doc = json.loads(body)
    assert doc['stitched']['found']
    assert doc['stitched']['attempts'][0]['replica'] == 'r0'
    srv.stop()


def test_debug_fleet_404_without_plane():
    srv = obs.serve_telemetry(port=0)
    code, body = _get(srv.url + '/debug/fleet')
    assert code == 404 and 'no fleet observability' in json.loads(body)[
        'error']
    srv.stop()


# ---------------------------------------------------------------------------
# on-demand profiling
# ---------------------------------------------------------------------------

def test_capture_profile_writes_artifacts(tmp_path):
    out = tmp_path / 'prof'
    s = fleetobs.capture_profile(ms=40, out_dir=str(out))
    assert s['window_ms'] == 40.0
    assert s['wall_ms'] >= 40.0
    assert s['artifact_dir'] == str(out)
    assert s['bytes'] > 0 and s['files']          # non-empty on CPU
    summary = json.loads((out / 'summary.json').read_text())
    assert summary['window_ms'] == 40.0
    assert not fleetobs.profile_in_flight()


def test_profile_window_clamped_to_floor_and_ceiling(tmp_path):
    s = fleetobs.capture_profile(ms=0.0, out_dir=str(tmp_path / 'a'))
    assert s['window_ms'] == 1.0                  # floor of the clamp
    cap0 = fleetobs.MAX_PROFILE_WINDOW_MS
    fleetobs.MAX_PROFILE_WINDOW_MS = 50.0
    try:
        s = fleetobs.capture_profile(ms=10_000, out_dir=str(tmp_path / 'b'))
        assert s['window_ms'] == 50.0             # ceiling of the clamp
    finally:
        fleetobs.MAX_PROFILE_WINDOW_MS = cap0


def test_concurrent_profile_second_gets_409():
    fobs = fleetobs.FleetObs(name='proffleet')
    srv = fobs.serve(port=0)
    results = []

    def grab():
        results.append(_get(srv.url + '/debug/profile?ms=400'))

    threads = [threading.Thread(target=grab) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    codes = sorted(c for c, _ in results)
    assert codes == [200, 409], results
    ok = next(json.loads(b) for c, b in results if c == 200)
    assert ok['bytes'] > 0 and ok['window_ms'] == 400.0
    busy = next(json.loads(b) for c, b in results if c == 409)
    assert busy['busy'] is True
    # the lock is released once the winner finishes
    assert not fleetobs.profile_in_flight()
    srv.stop()


# ---------------------------------------------------------------------------
# shutdown vs scrape race
# ---------------------------------------------------------------------------

def test_shutdown_races_concurrent_scrapes():
    obs.counter('serve.requests').inc()
    srv = obs.serve_telemetry(port=0)
    stop = threading.Event()
    errors = []

    def hammer():
        while not stop.is_set():
            try:
                _get(srv.url + '/metrics', timeout=5)
            except (OSError, urllib.error.URLError):
                return                    # server went away mid-scrape: fine
            except Exception as e:        # anything else is a real bug
                errors.append(e)
                return

    threads = [threading.Thread(target=hammer) for _ in range(4)]
    for t in threads:
        t.start()
    time.sleep(0.1)
    srv.stop(timeout=10)                  # must not deadlock or raise
    stop.set()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    assert errors == []
    with pytest.raises(OSError):
        urllib.request.urlopen(srv.url + '/healthz', timeout=2)


# ---------------------------------------------------------------------------
# ModelHost.debug_table
# ---------------------------------------------------------------------------

def _infer_factory(**kw):
    def factory():
        kw.setdefault('max_batch_size', 4)
        kw.setdefault('max_delay_ms', 0.5)
        kw.setdefault('queue_capacity', 8)
        net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
        return InferenceEngine(net, **kw)
    return factory


def test_every_counter_family_of_a_live_fleet_sums_its_replicas():
    """Two real engines behind a router, scraped over HTTP: the fleet row
    of EVERY counter family the engines publish is the exact sum of its
    replicas' rows."""
    from paddle_tpu.serving import FleetRouter, ReplicaSet
    rset = ReplicaSet(_infer_factory(), initial=2)
    router = FleetRouter(rset, tick_s=0.01)
    fobs = fleetobs.FleetObs(name=rset.name).watch_router(router)
    srv = fobs.serve(port=0)
    try:
        rng = np.random.RandomState(0)
        for f in [router.submit(rng.rand(n, 8).astype('float32'))
                  for n in (1, 3, 2, 4, 1, 3, 2, 1)]:
            f.result(timeout=120)
        code, text = _get(srv.url + '/metrics')
        assert code == 200
        snap = promparse.parse_text(text)
        fleet_rows, replica_rows = {}, {}
        for key, val in snap['counters'].items():
            labels = dict(snap['labels'][key])
            replica = labels.pop('replica', None)
            family = promparse.fmt_key(key.split('{', 1)[0], labels)
            if replica is None:
                fleet_rows[family] = val
            else:
                replica_rows.setdefault(family, []).append(val)
        assert 'serve_requests_submitted' in replica_rows
        assert sum(replica_rows['serve_requests_submitted']) == 8
        assert len(replica_rows) >= 9
        for family, vals in replica_rows.items():
            assert fleet_rows[family] == sum(vals), family
    finally:
        srv.stop()
        router.close(drain=False)


def test_host_debug_table_reports_residency_and_sheds():
    with ModelHost(hbm_watermark_bytes=256 * MB, name='dbghost') as host:
        host.deploy('a', _infer_factory(), input_spec=[((8,), 'float32')])
        host.deploy('b', _infer_factory(), input_spec=[((8,), 'float32')])
        host.set_quota('acme', 0)         # every acme submit sheds
        with pytest.raises(Exception):
            host.submit('a', np.zeros((8,), np.float32), tenant='acme')
        host.evict('b')
        table = host.debug_table()
        assert table['host'] == 'dbghost'
        assert table['resident'] == ['a'] and table['evicted'] == ['b']
        assert table['hbm_used_bytes'] <= table['hbm_watermark_bytes']
        assert table['hbm_free_bytes'] == (table['hbm_watermark_bytes']
                                           - table['hbm_used_bytes'])
        assert table['lane_sheds'] == 1
        assert table['models']['a']['state'] == 'live'
        assert table['models']['b']['state'] == 'evicted'
        assert table['models']['b']['warm_retained'] is True
        assert table['models']['b']['evictions'] == 1
        # the /debug/fleet host table rides the same dict
        fobs = fleetobs.FleetObs(name='hostfleet').watch_host(host)
        doc = fobs.fleet_table()
        assert doc['hosts'][0]['host'] == 'dbghost'


# ---------------------------------------------------------------------------
# disabled mode
# ---------------------------------------------------------------------------

def test_disabled_mode_is_inert():
    obs.set_enabled(False)
    assert fleetobs.capture_profile(ms=50) == {'disabled': True}
    fobs = fleetobs.FleetObs(name='off')
    assert fobs.serve(port=0) is _server.NULL_SERVER
    # no recorder, so stitching finds nothing — and never raises
    assert fleetobs.stitch('any')['found'] is False
