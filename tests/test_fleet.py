"""Fleet front door (ISSUE 12): health-gated replica routing, failover
without request loss or duplicate stream tokens, load shedding with a
backoff hint, SLO-driven autoscaling from a warm template, and graceful
drain for zero-drop rolling restarts."""
import threading
import time

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu import fault, nn
from paddle_tpu import observability as obs
from paddle_tpu.models import gpt
from paddle_tpu.serving import (Autoscaler, FleetRouter, InferenceEngine,
                                QueueFullError, ReplicaSet,
                                sharded_generation_engine)

pytestmark = pytest.mark.fleet

CFG = gpt.GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                    num_heads=2, max_seq_len=32, dtype='float32',
                    remat=False, use_flash=False)
PS = 8


@pytest.fixture(scope='module')
def params():
    return gpt.init_params(CFG, jax.random.PRNGKey(0))


def _gen_engine(params, mp=1, **kw):
    kw.setdefault('num_slots', 2)
    kw.setdefault('page_size', PS)
    kw.setdefault('prefill_width', 16)
    kw.setdefault('queue_capacity', 64)
    return sharded_generation_engine(params, CFG, mp=mp, **kw)  # 1: plain


def _prompts(lens, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, CFG.vocab_size, size=n) for n in lens]


def _reference(params, prompts, n_new):
    eng = _gen_engine(params)
    try:
        return [eng.submit(p, max_new_tokens=n_new, seed=i)
                .result(timeout=120) for i, p in enumerate(prompts)]
    finally:
        eng.shutdown()


def _warm(*engines):
    """Warm each engine directly (one short generation) so fleet routing
    starts from a deterministic all-warm state."""
    for e in engines:
        e.submit(np.array([3, 1, 4]), max_new_tokens=2,
                 seed=1234).result(timeout=120)
    return engines


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------

def test_router_spreads_load_and_matches_single_engine(params):
    prompts = _prompts([5, 7, 3, 9, 4, 6], seed=11)
    want = _reference(params, prompts, 16)
    engines = _warm(_gen_engine(params, num_slots=1),
                    _gen_engine(params, num_slots=1))
    rs = ReplicaSet(replicas=list(engines))
    router = FleetRouter(rs, tick_s=0.01)
    try:
        futs = [router.submit(p, max_new_tokens=16, seed=i)
                for i, p in enumerate(prompts)]
        got = [f.result(timeout=120) for f in futs]
        assert got == want
        # least-queue-depth routing over a 6-deep burst on two 1-slot
        # replicas lands work on both
        per_replica = [r.engine.stats()['submitted'] - 1  # minus warm-up
                       for r in rs.snapshot()]
        assert sum(per_replica) == len(prompts)
        assert all(n > 0 for n in per_replica), per_replica
    finally:
        router.close()


def test_router_skips_replica_with_open_breaker(params):
    broken = _gen_engine(
        params, breaker=fault.CircuitBreaker(failure_threshold=1,
                                             recovery_timeout=300.0))
    broken._breaker.record_failure()            # open, stays open
    healthy = _gen_engine(params)
    rs = ReplicaSet(replicas=[broken, healthy])
    router = FleetRouter(rs, tick_s=0.01)
    try:
        prompts = _prompts([4, 6, 5], seed=13)
        futs = [router.submit(p, max_new_tokens=4, seed=i)
                for i, p in enumerate(prompts)]
        [f.result(timeout=120) for f in futs]
        assert broken.stats()['submitted'] == 0
        assert healthy.stats()['submitted'] == len(prompts)
    finally:
        router.close(drain=False)


# ---------------------------------------------------------------------------
# failover: kill a replica mid-decode (fleet.failover inject point)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('mp', [1, 2], ids=['mp1+mp1', 'mp1+mp2'])
def test_failover_mid_decode_byte_identical_no_duplicates(params, mp):
    """``mp1+mp2``: the router cannot tell a mesh-sharded replica from a
    one-device one, and a stream that fails over between the two shapes is
    still the single engine's (vocab 97 does not split over 2: the
    embedding rides the fall-back-to-replicated rule on purpose)."""
    prompts = _prompts([9, 7, 8, 6, 9, 5], seed=17)
    n_new = 24
    want = _reference(params, prompts, n_new)
    engines = _warm(_gen_engine(params), _gen_engine(params, mp=mp))
    rs = ReplicaSet(replicas=list(engines))
    router = FleetRouter(rs, tick_s=0.005)
    try:
        futs = [router.submit(p, max_new_tokens=n_new, seed=i)
                for i, p in enumerate(prompts)]
        time.sleep(0.05)                      # let streams start decoding
        fault.configure('fleet.failover:1.0', seed=7, max_faults=1)
        try:
            streams = [list(f.stream(timeout=120)) for f in futs]
        finally:
            fault.configure(None)
        # zero lost requests, zero duplicate emissions, byte-identical
        assert streams == want
        states = [r.state for r in rs.snapshot()]
        assert states.count('dead') == 1, states
        killed = obs.find('fleet.replicas_killed', {'fleet': rs.name})
        assert killed is not None and killed.value == 1
    finally:
        router.close(drain=False)


def test_failover_keeps_one_master_record_with_failover_event(params):
    obs.reset_requests()
    prompts = _prompts([8, 8, 7, 9, 6, 8], seed=19)
    engines = _warm(_gen_engine(params), _gen_engine(params))
    rs = ReplicaSet(replicas=list(engines))
    router = FleetRouter(rs, tick_s=0.005)
    try:
        futs = [router.submit(p, max_new_tokens=24, seed=i)
                for i, p in enumerate(prompts)]
        time.sleep(0.05)
        fault.configure('fleet.failover:1.0', seed=3, max_faults=1)
        try:
            [f.result(timeout=120) for f in futs]
        finally:
            fault.configure(None)
        done = obs.recorder().requests(outcome='ok')
        fleet_recs = [r for r in done if r['kind'] == 'fleet']
        failed_over = [r for r in fleet_recs
                       if any(e['ev'] == 'failover' for e in r['timeline'])]
        assert failed_over, 'no master record carries the failover event'
        rec = failed_over[0]
        # ONE record spans both attempts — routed, failed over, re-routed
        # — and finished ok exactly once
        routes = [e for e in rec['timeline'] if e['ev'] == 'route']
        assert len(routes) >= 2
        assert rec['outcome'] == 'ok'
    finally:
        router.close(drain=False)


# ---------------------------------------------------------------------------
# shedding
# ---------------------------------------------------------------------------

def test_shed_only_when_all_replicas_saturated(params):
    rs = ReplicaSet(lambda: _gen_engine(params, num_slots=1,
                                        queue_capacity=2), initial=2)
    router = FleetRouter(rs, tick_s=0.01)
    try:
        accepted, shed = [], None
        for i in range(40):
            try:
                accepted.append(router.submit(
                    _prompts([8], seed=i)[0], max_new_tokens=24, seed=i))
            except QueueFullError as e:
                shed = e
                break
        assert shed is not None, 'saturated fleet never shed'
        assert shed.retry_after_ms is not None and shed.retry_after_ms > 0
        # shedding lost nothing that was admitted
        assert all(len(f.result(timeout=120)) == 24 for f in accepted)
        c = obs.find('fleet.shed', {'fleet': rs.name})
        assert c is not None and c.value >= 1
    finally:
        router.close()


# ---------------------------------------------------------------------------
# graceful drain / rolling restart
# ---------------------------------------------------------------------------

def test_rolling_restart_drops_nothing(params):
    rs = ReplicaSet(replicas=[_gen_engine(params) for _ in range(2)])
    router = FleetRouter(rs, tick_s=0.005)
    errors, results = [], []
    stop = threading.Event()

    def client(cid):
        rng = np.random.default_rng(cid)
        i = 0
        while not stop.is_set():
            try:
                f = router.submit(rng.integers(1, CFG.vocab_size, size=6),
                                  max_new_tokens=4, seed=cid * 997 + i)
                results.append(f.result(timeout=120))
            except Exception as e:           # noqa: BLE001 - recorded
                errors.append(e)
            i += 1

    threads = [threading.Thread(target=client, args=(c,)) for c in range(3)]
    try:
        for t in threads:
            t.start()
        time.sleep(0.25)
        first, second = [r.name for r in rs.snapshot()]
        router.drain(first)                  # rolling restart, replica 1
        rs.add(_gen_engine(params))          # replacement joins
        time.sleep(0.15)
        router.drain(second)                 # rolling restart, replica 2
        time.sleep(0.15)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=120)
    assert not errors, f'rolling restart dropped requests: {errors[:3]}'
    assert results, 'clients made no progress'
    router.close()


# ---------------------------------------------------------------------------
# autoscaling
# ---------------------------------------------------------------------------

def test_autoscaler_scales_up_warm_then_back_down(params):
    rs = ReplicaSet(lambda: _gen_engine(params, num_slots=1),
                    initial=1, min_replicas=1, max_replicas=3)
    # a clone is as warm as its template: spawn() copies the executables
    # warmup() built, so the template holds them all (the step and a
    # prefill a width) before the burst, whenever the breach is first seen
    # (a template compiled by live traffic has none to copy, and its clone
    # would trace on its first request)
    template = rs.snapshot()[0].engine
    assert template.warmup()['prebuilt'] == 1 + len(template.prefill_widths)
    asc = Autoscaler(qwait_p99_ms=1.0, idle_s=0.4, cooldown_s=0.2,
                     debounce=1)
    router = FleetRouter(rs, autoscaler=asc, tick_s=0.01)
    held = threading.Event()
    try:
        futs = [router.submit(_prompts([8], seed=i)[0], max_new_tokens=16,
                              seed=i) for i in range(12)]
        # the burst stays in flight until the scale-up has been seen: the
        # last request's first token parks its engine's thread here, with
        # every queue wait before it already observed
        futs[-1].subscribe(lambda *event: held.wait(120))
        # the serve.queue_wait p99 breach must spawn a replica while the
        # burst is still in flight
        spawned = None
        deadline = time.time() + 60
        while time.time() < deadline and spawned is None:
            extra = rs.snapshot()[1:]
            spawned = extra[0] if extra else None
            time.sleep(0.02)
        assert spawned is not None, 'queue-wait breach never scaled up'
        # warm template clone: the new replica serves with ZERO retraces
        assert spawned.engine.stats()['traces'] == 0
        assert spawned.engine._warmed
        held.set()
        [f.result(timeout=120) for f in futs]
        assert spawned.engine.stats()['traces'] == 0
        # idle replicas drain back down to the floor
        deadline = time.time() + 60
        while time.time() < deadline and rs.counts()[0] > 1:
            time.sleep(0.05)
        assert rs.counts()[0] == 1, 'idle fleet never scaled down'
        h = obs.find('fleet.scale_up_ms', {'fleet': rs.name})
        assert h is not None and h.count >= 1
    finally:
        held.set()
        router.close()


# ---------------------------------------------------------------------------
# readiness aggregation
# ---------------------------------------------------------------------------

def test_readyz_aggregates_to_at_least_one_ready_replica(params):
    e0, e1 = _gen_engine(params), _gen_engine(params)
    rs = ReplicaSet(replicas=[e0, e1])
    router = FleetRouter(rs, tick_s=0.01)
    try:
        # engines joined the fleet aggregate; their individual probes no
        # longer gate the process /readyz
        checks = obs.readiness()['checks']
        assert e0._probe_name not in checks
        assert e1._probe_name not in checks
        router.submit(_prompts([5], seed=29)[0],
                      max_new_tokens=2).result(timeout=120)   # warms r0
        agg = obs.readiness()['checks'][f'fleet.{rs.name}']
        assert agg['ready'] is True
        names = [r.name for r in rs.snapshot()]
        # one dead replica must NOT 503 the fleet (r1 is the cold one)
        rs.kill(names[1])
        assert obs.readiness()['checks'][f'fleet.{rs.name}']['ready']
        # every replica gone -> not ready
        rs.kill(names[0])
        assert not obs.readiness()['checks'][f'fleet.{rs.name}']['ready']
    finally:
        router.close(drain=False)


# ---------------------------------------------------------------------------
# hedged retries (batch inference only)
# ---------------------------------------------------------------------------

def test_hedge_rescues_request_stuck_on_stalled_replica():
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    net.eval()
    # autostart=False and never started: admitted work sits forever — a
    # stall the circuit breaker cannot see
    stalled = InferenceEngine(net, autostart=False)
    healthy = InferenceEngine(net, max_batch_size=8, max_delay_ms=0.5)
    rs = ReplicaSet(replicas=[stalled, healthy])
    router = FleetRouter(rs, hedge_ms=60, tick_s=0.01)
    try:
        x = np.random.rand(3, 8).astype('float32')
        want = np.asarray(net(paddle.to_tensor(x)))
        got = np.asarray(router.submit(x).result(timeout=60))
        np.testing.assert_allclose(got, want, rtol=1e-5)
        c = obs.find('fleet.hedge', {'fleet': rs.name})
        assert c is not None and c.value >= 1
    finally:
        router.close(drain=False)


def test_hedge_winner_with_breaker_opening_midflight_delivers_once():
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    net.eval()
    stalled = InferenceEngine(
        net, autostart=False,
        breaker=fault.CircuitBreaker(failure_threshold=1,
                                     recovery_timeout=300.0))
    healthy = InferenceEngine(net, max_batch_size=8, max_delay_ms=0.5)
    rs = ReplicaSet(replicas=[stalled, healthy])
    router = FleetRouter(rs, hedge_ms=40, tick_s=0.01)
    try:
        x = np.random.rand(3, 8).astype('float32')
        want = np.asarray(net(paddle.to_tensor(x)))
        fut = router.submit(x)
        got = np.asarray(fut.result(timeout=60))      # hedge twin wins
        np.testing.assert_allclose(got, want, rtol=1e-5)
        c = obs.find('fleet.hedge', {'fleet': rs.name})
        assert c is not None and c.value >= 1
        # now the primary's replica breaker opens while its attempt is
        # still queued, and THEN the stalled engine wakes up: the
        # abandoned attempt fails on its open breaker (CircuitOpenError)
        # and must be recognized as stale — the master future keeps the
        # hedge winner's result (no second set_result, no
        # InvalidStateError) and no in-flight request leaks
        stalled._breaker.record_failure()
        assert stalled.stats()['circuit_state'] == 'open'
        stalled.start()
        deadline = time.time() + 30
        while True:
            with router._lock:
                if not router._inflight:
                    break               # primary attempt fully resolved
            assert time.time() < deadline, 'primary attempt never drained'
            time.sleep(0.01)
        np.testing.assert_allclose(np.asarray(fut.result(timeout=1)),
                                   want, rtol=1e-5)
        # with the primary's breaker open, new traffic routes around it
        got2 = np.asarray(router.submit(x).result(timeout=60))
        np.testing.assert_allclose(got2, want, rtol=1e-5)
        assert stalled.stats()['completed'] == 0
        errors = obs.find('fleet.control_errors', {'fleet': rs.name})
        assert errors is None or errors.value == 0
    finally:
        router.close(drain=False)
