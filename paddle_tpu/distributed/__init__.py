"""paddle.distributed parity over JAX single-controller SPMD.

Reference: python/paddle/distributed/__init__.py. Key difference from the
reference's multi-process NCCL world: JAX is single-controller per host —
"rank" maps to jax.process_index() (multi-host) and parallelism inside a host
is expressed with the device mesh, not processes.
"""
import os

import jax

from .collective import (  # noqa: F401
    ReduceOp, all_gather, all_reduce, alltoall, barrier, broadcast, get_group,
    new_group, recv, reduce, reduce_scatter, scatter, send, wait)
from .topology import (  # noqa: F401
    HybridTopology, get_mesh, get_topology, set_topology)
from .parallel import DataParallel, init_parallel_env  # noqa: F401
from . import fleet  # noqa: F401
from .auto_parallel import (  # noqa: F401
    ProcessMesh, set_offload_device, set_pipeline_stage, set_shard_mask,
    shard_op, shard_tensor, split)
from . import utils  # noqa: F401  (fleet.utils stays at distributed.fleet.utils)
from . import cloud_utils  # noqa: F401
from .entry_attr import CountFilterEntry, ProbabilityEntry  # noqa: F401
from .ps_dataset import BoxPSDataset, InMemoryDataset, QueueDataset  # noqa: F401


def gloo_init_parallel_env(rank_id, rank_num, server_endpoint):
    pass


def gloo_barrier():
    pass


def gloo_release():
    pass


def get_rank(group=None):
    return jax.process_index()


def get_world_size(group=None):
    return jax.process_count()


class ParallelEnv:
    @property
    def rank(self):
        return get_rank()

    @property
    def world_size(self):
        return get_world_size()

    @property
    def device_id(self):
        return 0

    @property
    def local_rank(self):
        return get_rank()

    @property
    def nranks(self):
        return get_world_size()


def _spawn_target(func, args, rank, nprocs, master_port, errq):
    """Worker body (top-level for pickling). Wires the reference trainer-env
    contract, forces the CPU jax platform (a chip belongs to one process:
    N local workers cannot share the host's TPU — multi-process spawn is
    the multi-host-emulation path), then runs ``func``."""
    os.environ['JAX_PLATFORMS'] = 'cpu'
    os.environ['PADDLE_TRAINERS_NUM'] = str(nprocs)
    os.environ['PADDLE_TRAINER_ID'] = str(rank)
    os.environ['PADDLE_LOCAL_RANK'] = str(rank)
    os.environ['PADDLE_MASTER'] = '127.0.0.1'
    os.environ['MASTER_PORT'] = str(master_port)
    try:
        func(*args)
        errq.put((rank, None))
    except BaseException:
        import traceback
        errq.put((rank, traceback.format_exc()))
        raise


class MultiprocessContext:
    """Handle returned by spawn(join=False) (reference spawn.py's context:
    .join() re-raises the first worker failure)."""

    def __init__(self, procs, errq):
        self.processes = procs
        self._errq = errq

    def join(self, timeout=None):
        import time
        deadline = None if timeout is None else time.time() + timeout
        for p in self.processes:
            p.join(None if deadline is None
                   else max(0.0, deadline - time.time()))
        if any(p.is_alive() for p in self.processes):
            return False
        fails = []
        while not self._errq.empty():
            rank, tb = self._errq.get_nowait()
            if tb is not None:
                fails.append((rank, tb))
        for p in self.processes:
            if p.exitcode not in (0, None) and not fails:
                fails.append((p.pid, f'exitcode {p.exitcode}'))
        if fails:
            rank, tb = fails[0]
            raise RuntimeError(
                f'spawn: worker {rank} failed:\n{tb}' +
                (f'\n({len(fails) - 1} more worker(s) also failed)'
                 if len(fails) > 1 else ''))
        return True


def spawn(func, args=(), nprocs=-1, join=True, daemon=False, **options):
    """Reference: python/paddle/distributed/spawn.py:1 (forks one worker per
    device, wires trainer env, joins with error propagation).

    TPU-native semantics: JAX is single-controller — ONE process drives all
    local chips, so nprocs<=1 (or the default -1) runs ``func`` directly in
    this process, which IS the one-worker-per-host layout on a TPU host. A
    chip belongs to one process at a time, so nprocs>1 never touches the
    TPU: it forks real workers on the CPU platform (JAX_PLATFORMS=cpu in
    each child) with the same env contract as ``distributed.launch``
    (jax.distributed multi-process emulation), joins them, and re-raises
    the first failure.
    """
    if nprocs is not None and (nprocs == 0 or nprocs < -1):
        raise ValueError(f'spawn: nprocs must be -1 (all local devices) or '
                         f'a positive worker count, got {nprocs}')
    if nprocs is None or nprocs in (-1, 1):
        from .fleet.strategy import warn_na_once
        warn_na_once('spawn_single', (
            'paddle.distributed.spawn: JAX is single-controller — one '
            'process already drives every local TPU chip, so func runs '
            'in-process (no fork). Use nprocs>1 for a real multi-process '
            'CPU run, or distributed.launch for multi-host.'))
        func(*args)
        return None
    import multiprocessing as mp
    ctx = mp.get_context('spawn')
    errq = ctx.Queue()
    port = int(options.get('master_port', 0)) or (8476 + os.getpid() % 500)
    procs = []
    for rank in range(nprocs):
        p = ctx.Process(target=_spawn_target,
                        args=(func, args, rank, nprocs, port, errq),
                        daemon=daemon)
        p.start()
        procs.append(p)
    context = MultiprocessContext(procs, errq)
    if join:
        context.join()
        return None
    return context


def launch():
    from . import launch as launch_mod
    launch_mod.main()


def init_process_group(*args, **kwargs):
    return init_parallel_env()
