"""Multi-tenant hosting (ISSUE 13): HBM-aware admission with LRU
eviction of cold models, zero-retrace swap-in from warmth snapshots,
priority lanes with SLO-driven batch shedding, per-tenant quotas and
request accounting, fleet ``model@host`` targeting, and the
``host.admit`` / ``host.evict`` chaos points."""
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu import fault, nn
from paddle_tpu import observability as obs
from paddle_tpu.models import gpt
from paddle_tpu.serving import (DeadlineExceededError, FleetRouter,
                                GenerationEngine, HBMAdmissionError,
                                InferenceEngine, ModelHost, QueueFullError,
                                ReplicaSet, get_host, resolve_target)

pytestmark = pytest.mark.tenant

MB = 1 << 20

CFG = gpt.GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                    num_heads=2, max_seq_len=32, dtype='float32',
                    remat=False, use_flash=False)


@pytest.fixture(scope='module')
def params():
    return gpt.init_params(CFG, jax.random.PRNGKey(0))


def _gen_factory(params, **kw):
    def factory():
        kw.setdefault('num_slots', 2)
        kw.setdefault('page_size', 8)
        kw.setdefault('prefill_width', 16)
        kw.setdefault('queue_capacity', 16)
        return GenerationEngine(params, CFG, **kw)
    return factory


def _net():
    return nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))


def _infer_factory(**kw):
    def factory():
        kw.setdefault('max_batch_size', 8)
        kw.setdefault('max_delay_ms', 0.5)
        kw.setdefault('queue_capacity', 16)
        return InferenceEngine(_net(), **kw)
    return factory


def _served_alone(params, prompt, n_new, seed=0):
    eng = GenerationEngine(params, CFG, num_slots=2, page_size=8,
                           prefill_width=16)
    try:
        return eng.submit(prompt, max_new_tokens=n_new,
                          seed=seed).result(timeout=120)
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# deploy / submit / registry
# ---------------------------------------------------------------------------

def test_host_serves_heterogeneous_models(params):
    prompt = np.array([3, 1, 4, 1, 5])
    want = _served_alone(params, prompt, 8, seed=7)
    with ModelHost(hbm_watermark_bytes=256 * MB, name='hetero') as host:
        host.deploy('chat', _gen_factory(params))
        host.deploy('vision', _infer_factory(),
                    input_spec=[((8,), 'float32')])
        got = host.submit('chat', prompt, tenant='acme',
                          max_new_tokens=8, seed=7).result(timeout=120)
        assert got == want
        out = host.submit('vision', np.zeros((8,), np.float32),
                          tenant='acme').result(timeout=120)
        assert np.asarray(out[0] if isinstance(out, list) else out).shape \
            == (4,)
        models = host.models()
        assert models['chat']['kind'] == 'gen'
        assert models['vision']['kind'] == 'infer'
        assert all(d['state'] == 'live' for d in models.values())
        # measured footprints are real and accounted against the watermark
        st = host.stats()
        assert 0 < st['hbm_used_bytes'] <= host.watermark_bytes
        # the registry resolves model@host targets
        assert get_host('hetero') is host
        h, m = resolve_target('chat@hetero')
        assert h is host and m == 'chat'
    with pytest.raises(ValueError):
        resolve_target('no-at-sign')


def test_admission_refused_over_watermark_without_stripping(params):
    with ModelHost(hbm_watermark_bytes=11 * MB, name='tight') as host:
        host.deploy('a', _gen_factory(params), footprint_bytes=4 * MB)
        host.deploy('b', _gen_factory(params), footprint_bytes=4 * MB)
        # 40 MB can never fit, even after evicting every cold model:
        # the host must refuse up front and evict NOTHING
        with pytest.raises(HBMAdmissionError) as ei:
            host.deploy('huge', _gen_factory(params),
                        footprint_bytes=40 * MB)
        assert ei.value.needed_bytes == 40 * MB
        assert ei.value.watermark_bytes == 11 * MB
        states = {n: d['state'] for n, d in host.models().items()}
        assert states == {'a': 'live', 'b': 'live'}
        assert host.stats()['rejected'] == 1
        assert host.stats()['evictions'] == 0


def test_lru_eviction_and_zero_trace_swap_in(params):
    prompt = np.array([2, 7, 1, 8])
    want = _served_alone(params, prompt, 6, seed=3)
    with ModelHost(hbm_watermark_bytes=9 * MB, name='lru') as host:
        host.deploy('a', _gen_factory(params), footprint_bytes=4 * MB)
        host.deploy('b', _gen_factory(params), footprint_bytes=4 * MB)
        # touch 'b' so 'a' is the LRU victim
        host.submit('b', prompt, max_new_tokens=2).result(timeout=120)
        host.deploy('c', _gen_factory(params), footprint_bytes=4 * MB)
        states = {n: d['state'] for n, d in host.models().items()}
        assert states == {'a': 'evicted', 'b': 'live', 'c': 'live'}
        desc = host.models()['a']
        assert desc['has_warmth'] and desc['has_manifest']
        assert host.stats()['hbm_used_bytes'] <= 9 * MB
        # submitting to the evicted model swaps it back in transparently
        # (cascading the LRU eviction onto 'b') with ZERO new traces and
        # byte-identical output
        got = host.submit('a', prompt, max_new_tokens=6,
                          seed=3).result(timeout=120)
        assert got == want
        assert host.models()['a']['state'] == 'live'
        assert host._models['a'].engine.stats()['traces'] == 0
        assert host.stats()['swap_ins'] == 1
        assert host.stats()['hbm_used_bytes'] <= 9 * MB


def test_evict_and_swap_in_mid_traffic_lose_no_interactive_request(params):
    """A hot model's interactive stream goes on, every request answered
    with the single engine's tokens, while a deploy evicts the cold model
    beside it and a later submit swaps that one back in."""
    prompt = np.array([2, 7, 1, 8])
    want = _served_alone(params, prompt, 4, seed=3)
    answers, errors, stop = [], [], threading.Event()
    with ModelHost(hbm_watermark_bytes=13 * MB, name='midtraffic') as host:
        host.deploy('draft', _gen_factory(params), footprint_bytes=4 * MB)
        host.deploy('side', _gen_factory(params), footprint_bytes=4 * MB)
        host.deploy('chat', _gen_factory(params), footprint_bytes=4 * MB)

        def pacer():
            while not stop.is_set():
                try:
                    answers.append(host.submit(
                        'chat', prompt, tenant='acme', lane='interactive',
                        max_new_tokens=4, seed=3).result(timeout=120))
                except Exception as e:       # noqa: BLE001 - recorded
                    errors.append(e)

        pace = threading.Thread(target=pacer)
        pace.start()
        try:
            # 'draft' is the LRU cold model: the deploy must evict it
            host.submit('side', prompt, max_new_tokens=2).result(timeout=120)
            host.deploy('extra', _gen_factory(params),
                        footprint_bytes=4 * MB)
            assert host.models()['draft']['state'] == 'evicted'
            before = len(answers)
            got = host.submit('draft', prompt, max_new_tokens=4,
                              seed=3).result(timeout=120)
            deadline = time.time() + 60     # the stream outlives the swap
            while len(answers) <= before and time.time() < deadline:
                time.sleep(0.01)
        finally:
            stop.set()
            pace.join(timeout=120)
        assert got == want
        assert host.models()['draft']['state'] == 'live'
        assert host.models()['chat']['state'] == 'live'
        assert host._models['draft'].engine.stats()['traces'] == 0
        assert host.stats()['evictions'] >= 2 and host.stats()['swap_ins'] == 1
        assert host.stats()['hbm_used_bytes'] <= 13 * MB
    assert not errors, errors[:3]
    assert len(answers) > before and all(a == want for a in answers)


def test_explicit_evict_refuses_inflight_and_pinned(params):
    with ModelHost(hbm_watermark_bytes=64 * MB, name='pin') as host:
        host.deploy('a', _infer_factory(autostart=False), warm=False,
                    footprint_bytes=MB)
        host.deploy('p', _gen_factory(params), pin=True,
                    footprint_bytes=MB)
        host.submit('a', np.zeros((8,), np.float32))
        with pytest.raises(RuntimeError, match='in flight'):
            host.evict('a')
        # a pinned model is never an eviction candidate: 64 MB would fit
        # only by evicting 'p' too, so admission must refuse up front
        with pytest.raises(HBMAdmissionError):
            host.deploy('big', _gen_factory(params),
                        footprint_bytes=64 * MB)
        assert host.models()['p']['state'] == 'live'
        assert host.models()['a']['state'] == 'live'
        host.close(drain=False)


# ---------------------------------------------------------------------------
# lanes / quotas / shedding
# ---------------------------------------------------------------------------

def test_batch_lane_capped_with_retry_hint(params):
    with ModelHost(hbm_watermark_bytes=64 * MB, name='lanes',
                   batch_share=0.25) as host:
        # stalled engine: submissions queue but never complete, so lane
        # accounting is fully deterministic
        host.deploy('m', _infer_factory(autostart=False), warm=False)
        x = np.zeros((8,), np.float32)
        cap = max(1, int(16 * 0.25))
        for _ in range(cap):
            host.submit('m', x, lane='batch', tenant='bulk')
        with pytest.raises(QueueFullError) as ei:
            host.submit('m', x, lane='batch', tenant='bulk')
        assert ei.value.retry_after_ms is not None
        assert ei.value.retry_after_ms > 0
        # the interactive lane is NOT subject to the batch cap
        host.submit('m', x, lane='interactive', tenant='acme')
        assert host.stats()['shed'] == 1
        host.close(drain=False)


def test_slo_breach_sheds_batch_lane_only(params):
    with ModelHost(hbm_watermark_bytes=64 * MB, name='slo',
                   interactive_p99_ms=1e-6, slo_interval=0.02,
                   slo_debounce=1) as host:
        host.deploy('chat', _gen_factory(params))
        # any real queue wait breaches a ~0 p99 budget; generate samples
        # until the host's watcher flips the model into batch shedding
        deadline = time.time() + 30
        while not host.models()['chat']['shed_batch']:
            host.submit('chat', np.array([3, 1, 4]),
                        max_new_tokens=2).result(timeout=120)
            assert time.time() < deadline, 'SLO rule never fired'
            time.sleep(0.02)
        with pytest.raises(QueueFullError) as ei:
            host.submit('chat', np.array([3, 1, 4]), lane='batch',
                        max_new_tokens=2)
        assert ei.value.retry_after_ms is not None
        # interactive traffic still flows while batch is shed
        got = host.submit('chat', np.array([3, 1, 4]), lane='interactive',
                          max_new_tokens=2).result(timeout=120)
        assert len(got) == 2
        shed = obs.find('host.shed', {'host': 'slo', 'model': 'chat',
                                      'tenant': 'default', 'lane': 'batch',
                                      'reason': 'slo'})
        assert shed is not None and shed.value >= 1


def test_tenant_quota_and_accounting(params):
    with ModelHost(hbm_watermark_bytes=64 * MB, name='quota') as host:
        host.deploy('m', _infer_factory(autostart=False), warm=False)
        host.set_quota('acme', 1)
        x = np.zeros((8,), np.float32)
        host.submit('m', x, tenant='acme')
        with pytest.raises(QueueFullError):
            host.submit('m', x, tenant='acme')
        # another tenant is unaffected by acme's quota
        host.submit('m', x, tenant='other')
        t = host.tenants()
        assert t['acme'] == {'inflight': 1, 'quota': 1}
        assert t['other'] == {'inflight': 1, 'quota': None}
        host.close(drain=False)


def test_per_tenant_flight_recorder_and_debug_endpoint(params):
    obs.reset_requests()
    with ModelHost(hbm_watermark_bytes=64 * MB, name='trace') as host:
        host.deploy('m', _infer_factory())
        x = np.zeros((8,), np.float32)
        host.submit('m', x, tenant='acme').result(timeout=120)
        host.submit('m', x, tenant='acme', lane='batch').result(timeout=120)
        host.submit('m', x, tenant='bulk').result(timeout=120)
        recs = obs.recorder().requests(tenant='acme')
        assert len(recs) == 2
        assert all(r['attrs']['tenant'] == 'acme' for r in recs)
        assert {r['attrs']['lane'] for r in recs} == \
            {'interactive', 'batch'}
        assert all(r['attrs']['host'] == 'trace' for r in recs)
        # the tenant filter is live on the telemetry plane too
        srv = obs.serve_telemetry(port=0)
        try:
            with urllib.request.urlopen(
                    f'{srv.url}/debug/requests?tenant=bulk') as resp:
                doc = json.loads(resp.read())
            assert doc['count'] == 1
            assert doc['requests'][0]['attrs']['tenant'] == 'bulk'
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# deadlines (satellite: fast-fail at submit time)
# ---------------------------------------------------------------------------

def test_expired_deadline_fast_fails_infer_submit():
    with InferenceEngine(_net(), autostart=False) as eng:
        t0 = time.perf_counter()
        with pytest.raises(DeadlineExceededError):
            eng.submit(np.zeros((8,), np.float32), deadline_ms=0)
        # raised synchronously from submit(), not after a queue timeout
        assert (time.perf_counter() - t0) < 1.0
        assert eng.stats()['expired'] == 1


def test_expired_deadline_fast_fails_gen_submit(params):
    eng = GenerationEngine(params, CFG, num_slots=1, page_size=8,
                           prefill_width=16, autostart=False)
    try:
        t0 = time.perf_counter()
        with pytest.raises(DeadlineExceededError):
            eng.submit(np.array([3, 1, 4]), max_new_tokens=4,
                       deadline_ms=0)
        assert (time.perf_counter() - t0) < 1.0
    finally:
        eng.shutdown(drain=False)


# ---------------------------------------------------------------------------
# predict retry backoff (satellite: honor retry_after_ms)
# ---------------------------------------------------------------------------

def test_model_predict_honors_retry_after_hint(monkeypatch):
    from paddle_tpu.hapi import model as model_mod

    class _Fut:
        def __init__(self, x):
            self._x = x

        def result(self):
            return [np.zeros((self._x.shape[0], 4), np.float32)]

    class _SheddingEngine:
        queue_capacity = 8

        def __init__(self):
            self.rejected = False

        def submit(self, *arrs):
            if not self.rejected:
                self.rejected = True
                raise QueueFullError(8, 8, retry_after_ms=37.0)
            return _Fut(arrs[0])

    slept = []
    monkeypatch.setattr(model_mod.time, 'sleep',
                        lambda s: slept.append(s))
    model = paddle.Model(_net())
    model.prepare(None, None)
    xs = np.random.rand(4, 8).astype('float32')
    out = model.predict([(xs,)], engine=_SheddingEngine())
    assert np.asarray(out[0][0]).shape == (4, 4)
    # the first submit shed with a hint; predict backed off exactly that
    # long instead of the blind 1ms default
    assert slept == [37.0 / 1e3]


# ---------------------------------------------------------------------------
# fleet front door: model@host targeting
# ---------------------------------------------------------------------------

def test_fleet_router_targets_hosted_model(params):
    prompt = np.array([5, 2, 9])
    want = _served_alone(params, prompt, 6, seed=11)
    with ModelHost(hbm_watermark_bytes=64 * MB, name='behind') as host:
        host.deploy('chat', _gen_factory(params))
        rs = ReplicaSet(replicas=[GenerationEngine(
            params, CFG, num_slots=1, page_size=8, prefill_width=16)])
        router = FleetRouter(rs, tick_s=0.05)
        try:
            got = router.submit(prompt, max_new_tokens=6, seed=11,
                                target='chat@behind',
                                tenant='acme').result(timeout=120)
            assert got == want
            routed = obs.find('fleet.host_routed', {'fleet': rs.name})
            assert routed is not None and routed.value == 1
            # host-targeted traffic is attributed to the tenant
            c = obs.find('host.requests',
                         {'host': 'behind', 'model': 'chat',
                          'tenant': 'acme', 'lane': 'interactive'})
            assert c is not None and c.value == 1
        finally:
            router.close(drain=False)


# ---------------------------------------------------------------------------
# chaos points
# ---------------------------------------------------------------------------

def test_chaos_host_admit_aborts_deploy_cleanly(params):
    with ModelHost(hbm_watermark_bytes=64 * MB, name='chaos1') as host:
        host.deploy('a', _gen_factory(params), footprint_bytes=MB)
        used = host.stats()['hbm_used_bytes']
        fault.configure('host.admit:1.0', seed=1, max_faults=1)
        try:
            with pytest.raises(fault.InjectedFault):
                host.deploy('b', _gen_factory(params), footprint_bytes=MB)
        finally:
            fault.configure(None)
        # the aborted deploy left no trace: no model, no reserved bytes
        assert 'b' not in host.models()
        assert host.stats()['hbm_used_bytes'] == used
        # and a retry (fault disarmed) succeeds
        host.deploy('b', _gen_factory(params), footprint_bytes=MB)
        assert host.models()['b']['state'] == 'live'


def test_chaos_host_evict_aborts_leaving_victim_live(params):
    with ModelHost(hbm_watermark_bytes=64 * MB, name='chaos2') as host:
        host.deploy('a', _gen_factory(params), footprint_bytes=MB)
        fault.configure('host.evict:1.0', seed=1, max_faults=1)
        try:
            with pytest.raises(fault.InjectedFault):
                host.evict('a')
        finally:
            fault.configure(None)
        assert host.models()['a']['state'] == 'live'
        assert host.stats()['evictions'] == 0
        # still serving after the aborted eviction
        got = host.submit('a', np.array([3, 1, 4]),
                          max_new_tokens=2).result(timeout=120)
        assert len(got) == 2
