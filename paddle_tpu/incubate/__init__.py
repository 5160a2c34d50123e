"""paddle.incubate parity: experimental features.
Reference: python/paddle/incubate/ (LookAhead/ModelAverage optimizers,
softmax_mask_fuse, graph ops)."""
import jax.numpy as jnp

from ..core.dispatch import op
from ..optimizer.optimizer import Optimizer


@op
def softmax_mask_fuse(x, mask, name=None):
    import jax
    return jax.nn.softmax(x + mask, axis=-1)


@op
def softmax_mask_fuse_upper_triangle(x):
    import jax
    S = x.shape[-1]
    mask = jnp.triu(jnp.full((S, S), -1e30, x.dtype), k=1)
    return jax.nn.softmax(x + mask, axis=-1)


class LookAhead(Optimizer):
    """Reference: python/paddle/incubate/optimizer/lookahead.py."""

    def __init__(self, inner_optimizer, alpha=0.5, k=5, name=None):
        super().__init__(inner_optimizer._lr, inner_optimizer._parameters)
        self.inner = inner_optimizer
        self.alpha = alpha
        self.k = k
        self._slow = {}
        self._step_count = 0

    def step(self):
        self.inner.step()
        self._step_count += 1
        if self._step_count % self.k == 0:
            for p in self.inner._parameters:
                sid = id(p)
                if sid not in self._slow:
                    self._slow[sid] = p._value
                slow = self._slow[sid] + self.alpha * (p._value - self._slow[sid])
                self._slow[sid] = slow
                p._replace_value(slow)

    def clear_grad(self, *a, **k):
        self.inner.clear_grad(*a, **k)


class ModelAverage(Optimizer):
    """Reference: python/paddle/incubate/optimizer/modelaverage.py."""

    def __init__(self, average_window_rate, parameters=None, min_average_window=10000,
                 max_average_window=10000, name=None):
        super().__init__(0.0, parameters)
        self._sums = {id(p): jnp.zeros_like(p._value) for p in self._parameters}
        self._counts = {id(p): 0 for p in self._parameters}
        self._backup = {}

    def step(self):
        for p in self._parameters:
            self._sums[id(p)] = self._sums[id(p)] + p._value
            self._counts[id(p)] += 1

    def apply(self, executor=None, need_restore=True):
        import contextlib

        @contextlib.contextmanager
        def _ctx():
            for p in self._parameters:
                self._backup[id(p)] = p._value
                if self._counts[id(p)]:
                    p._replace_value(self._sums[id(p)] / self._counts[id(p)])
            try:
                yield
            finally:
                if need_restore:
                    self.restore()
        return _ctx()

    def restore(self, executor=None):
        for p in self._parameters:
            if id(p) in self._backup:
                p._replace_value(self._backup[id(p)])


def graph_send_recv(x, src_index, dst_index, pool_type='sum', out_size=None):
    from ..core.dispatch import apply_op
    import jax

    def pure(v, si, di):
        n = out_size or v.shape[0]
        gathered = jnp.take(v, jnp.asarray(si).astype(jnp.int32), axis=0)
        seg = jnp.asarray(di).astype(jnp.int32)
        if pool_type == 'sum':
            return jax.ops.segment_sum(gathered, seg, num_segments=n)
        if pool_type == 'mean':
            s = jnp.zeros((n,) + v.shape[1:], v.dtype).at[seg].add(gathered)
            c = jnp.zeros((n,), v.dtype).at[seg].add(1.0)
            return s / jnp.maximum(c, 1.0)[:, None]
        if pool_type == 'max':
            base = jnp.full((n,) + v.shape[1:], -jnp.inf, v.dtype)
            return base.at[seg].max(gathered)
        if pool_type == 'min':
            base = jnp.full((n,) + v.shape[1:], jnp.inf, v.dtype)
            return base.at[seg].min(gathered)
        raise ValueError(pool_type)
    return apply_op(pure, x, src_index, dst_index)


# ---- segment ops (reference: python/paddle/incubate/tensor/math.py) ------
# TPU-native: jax.ops.segment_* lower to sorted scatter-adds that XLA
# vectorizes; num_segments is taken from the ids (eager) so the API matches
# the reference's dynamic behaviour.

def _num_segments(segment_ids):
    import numpy as np
    ids = segment_ids._value if hasattr(segment_ids, '_value') else segment_ids
    return int(np.asarray(ids.max())) + 1 if ids.size else 0


@op
def segment_sum(data, segment_ids, name=None):
    import jax
    return jax.ops.segment_sum(data, segment_ids,
                               num_segments=_num_segments(segment_ids))


@op
def segment_mean(data, segment_ids, name=None):
    import jax
    n = _num_segments(segment_ids)
    s = jax.ops.segment_sum(data, segment_ids, num_segments=n)
    cnt = jax.ops.segment_sum(jnp.ones_like(segment_ids, data.dtype),
                              segment_ids, num_segments=n)
    shape = (n,) + (1,) * (data.ndim - 1)
    return s / jnp.maximum(cnt.reshape(shape), 1)


@op
def segment_max(data, segment_ids, name=None):
    import jax
    return jax.ops.segment_max(data, segment_ids,
                               num_segments=_num_segments(segment_ids))


@op
def segment_min(data, segment_ids, name=None):
    import jax
    return jax.ops.segment_min(data, segment_ids,
                               num_segments=_num_segments(segment_ids))


# ---- auto_checkpoint (reference: incubate/checkpoint/auto_checkpoint.py) -
class _AutoCheckpoint:
    """The reference's ACP hooks training loops to snapshot/restore
    transparently on preemption. This stack reaches the same goal through
    hapi.Model + orbax CheckpointManager auto-resume (see hapi/model.py);
    these entry points adapt that machinery to the ACP API names."""

    def __init__(self):
        self._enabled = False

    def train_epoch_range(self, max_epoch_num, save_checkpoint_inter=None):
        """Iterate epochs, resuming from the last completed one if a
        checkpoint range-state file exists."""
        import json
        import os
        base = os.environ.get('PADDLE_CHECKPOINT_DIR', '.acp')
        os.makedirs(base, exist_ok=True)
        state = os.path.join(base, 'epoch_range.json')
        start = 0
        if os.path.exists(state):
            with open(state) as f:
                start = json.load(f).get('next_epoch', 0)
        for e in range(start, max_epoch_num):
            yield e
            with open(state, 'w') as f:
                json.dump({'next_epoch': e + 1}, f)


auto_checkpoint = _AutoCheckpoint()


class LayerHelper:
    """Reference: fluid/layer_helper.py — static-graph op/param factory.
    Eager stack: thin adapter exposing the attribute surface old custom-op
    code probes (main_program/startup_program naming, create_parameter)."""

    def __init__(self, layer_type, **kwargs):
        self.layer_type = layer_type
        self.kwargs = kwargs

    def create_parameter(self, attr=None, shape=None, dtype='float32',
                         is_bias=False, default_initializer=None):
        from ..core.tensor import Tensor
        from ..nn.initializer import Constant, XavierNormal
        init = default_initializer or (Constant(0.0) if is_bias
                                       else XavierNormal())
        return Tensor(init(shape, dtype), stop_gradient=False)

# ASP structured sparsity (reference later moves fluid.contrib.sparsity here)
from .. import sparsity as asp  # noqa: F401,E402
