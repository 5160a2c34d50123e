"""Data parallel. Reference: python/paddle/distributed/parallel.py +
fleet/meta_parallel (DataParallel with NCCL grad allreduce).

TPU-native: DataParallel shards the batch over the mesh 'dp' axis. The
wrapped layer's jitted step (built by fleet.distributed_model / hapi) places
inputs with batch-axis NamedSharding; XLA inserts the grad all-reduce during
backward — no hooks, no bucketing (the compiler fuses and overlaps them).
Eagerly it is transparent (identity wrapper), like world_size=1 reference.
"""
import os

import jax

from ..nn.layer_base import Layer
from .topology import get_topology


def init_parallel_env():
    """Multi-host: initialize jax.distributed from env (PADDLE_TRAINERS_NUM /
    coordinator address), mirroring the reference's env-var contract."""
    coord = os.environ.get('PADDLE_MASTER') or os.environ.get('MASTER_ADDR')
    nprocs = int(os.environ.get('PADDLE_TRAINERS_NUM', '1'))
    rank = int(os.environ.get('PADDLE_TRAINER_ID', '0'))
    # probe the distributed-client state WITHOUT jax.process_count(): that
    # would initialize the XLA backend, after which initialize() is illegal
    already = jax.distributed.is_initialized()
    if coord and nprocs > 1 and not already:
        port = os.environ.get('MASTER_PORT', '8476')
        jax.distributed.initialize(f'{coord}:{port}', num_processes=nprocs,
                                   process_id=rank)
    return None


class DataParallel(Layer):
    def __init__(self, layers, strategy=None, comm_buffer_size=25,
                 last_comm_buffer_size=1, find_unused_parameters=False,
                 group=None):
        super().__init__()
        self._layers = layers

    def forward(self, *inputs, **kwargs):
        return self._layers(*inputs, **kwargs)

    def state_dict(self, *args, **kwargs):
        return self._layers.state_dict(*args, **kwargs)

    def set_state_dict(self, state_dict, *args, **kwargs):
        return self._layers.set_state_dict(state_dict, *args, **kwargs)

    def scale_loss(self, loss):
        return loss

    def apply_collective_grads(self):
        pass
