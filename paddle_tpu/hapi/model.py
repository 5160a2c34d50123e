"""High-level Model API: prepare/fit/evaluate/predict.

Reference: python/paddle/hapi/model.py. TPU-native core: the whole train step
(forward + loss + backward + optimizer update) is ONE jitted XLA program over
the param pytree — the eager tape is bypassed entirely, giving the compiled
performance path that the reference gets from static graph + Executor.

Async executor: params/buffers/opt_state stay device-resident in a
``_TrainState`` between steps (no per-batch Python dict rebuild / write-back),
the compiled step donates them to XLA so updates happen in place, the loss
comes back as a lazy device array resolved only at logging points, and batches
are prefetched to the device ahead of compute (``DataLoader.prefetch_to_device``).
Layer objects get the values written back lazily — on first read, at
checkpoints, and at fit() exit. ``PADDLE_TPU_SYNC_EXECUTOR=1`` restores the
fully synchronous per-step behavior.
"""
import collections
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import observability as _obs
from ..core import tensor as _core_tensor
from ..core.tensor import DeviceResidentRef, Tensor, no_grad_ctx
from ..nn.layer_base import Layer, functional_call
from ..tensor.random import rng_scope, next_key
from ..io import DataLoader, Dataset


def _to_list(x):
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


def _perf_analyze(label, jitted, args):
    """One-shot XLA cost/memory analysis of a compiled step (perf.* series).

    Called right AFTER the live call with the same concrete args, so
    ``lower().compile()`` inside is a pure executable-cache hit — no
    retrace (donated/deleted buffers are fine, only avals are read). The
    ``analyzed`` probe keeps steps 2+ at one dict lookup."""
    if _obs.enabled() and _obs.perf.analyzed(label) is None:
        _obs.perf.analyze(label, jitted, args)


class _TrainState:
    """Device-resident training state: the single owner of the live
    param/buffer/opt-state arrays between compiled steps. ``mut_version``
    snapshots the global Tensor mutation counter so external writes
    (set_state_dict, user set_value, an eager optimizer) are detected and
    folded back in before the next step; ``refs_dirty`` marks that some
    Layer tensor materialized its placeholder and needs a fresh ref before
    the next donated step invalidates what it is holding."""

    __slots__ = ('params', 'buffers', 'opt_state', 'mut_version',
                 'refs_dirty')


class Model:
    def __init__(self, network, inputs=None, labels=None):
        self.network = network
        self._inputs = _to_list(inputs)
        self._labels = _to_list(labels)
        self._loss = None
        self._optimizer = None
        self._metrics = []
        self._train_step = None
        self._eval_step = None
        self._train_steps = {}      # mode signature -> (step, accum, apply)
        self._eval_steps = {}       # mode signature -> eval step
        self._tstate = None
        self._opt_state_host = None
        self._opt_restored = False
        self._opt_init_pending = True
        self._grad_acc = None
        self._accum_count = 0
        self._net_mode = None
        self._mode_sig_cache = None
        self._step_traces = 0
        self._eval_traces = 0
        self._last_outputs = None
        self._inflight = collections.deque()
        self._scale_cache = None
        self._step_timer = None
        self._engine = None
        self._engine_kwargs = None
        self._strategy = None
        self._partitioner = None
        self._async = os.environ.get('PADDLE_TPU_SYNC_EXECUTOR') != '1'
        try:
            self._inflight_window = max(
                1, int(os.environ.get('PADDLE_TPU_INFLIGHT', '2')))
        except ValueError:
            self._inflight_window = 2
        self.stop_training = False

    # ---- setup -----------------------------------------------------------
    def prepare(self, optimizer=None, loss=None, metrics=None,
                amp_configs=None, warmup=None, strategy=None):
        """strategy (fleet.DistributedStrategy, optional): compiles down to
        a partitioner rules table (parallel/partitioner.py) — the train
        state is placed over the strategy's mesh (params per their
        logical_axes annotations, batches sharded over the 'batch' rule,
        optimizer state ZeRO-sharded when strategy.sharding) and the
        already-donating async-executor jit then runs the whole state as
        one SPMD program with device residency and buffer reuse. Set it
        before the first train_batch."""
        self._optimizer = optimizer
        self._loss = loss
        self._metrics = _to_list(metrics)
        self._train_step = None
        self._eval_step = None
        self._train_steps = {}
        self._eval_steps = {}
        self._opt_init_pending = True
        if strategy is not None:
            self._strategy = strategy
            self._partitioner = strategy.to_partition_rules()
        from .. import warmup as _warmup_mod
        _warmup_mod.ensure_persistent_cache()
        if warmup is not None:
            self.prebuild_warmup(warmup)

    def prebuild_warmup(self, manifest):
        """AOT-prebuild the train/eval step signatures recorded in a warmup
        manifest (a ``warmup.Manifest`` or a path): the first real batch
        then runs an already-compiled program. Returns the prebuild
        report. Also reachable as ``prepare(warmup=)`` / ``fit(warmup=)``."""
        from .. import warmup as _warmup_mod
        return _warmup_mod.prebuild(manifest, model=self)

    # ---- functional plumbing --------------------------------------------
    def _pack(self):
        net = self.network
        pnames = [n for n, _ in net.named_parameters()]
        bnames = [n for n, _ in net.named_buffers()]
        return pnames, bnames

    @staticmethod
    def _real_value(t):
        v = t._value
        if type(v) is DeviceResidentRef:
            return v.materialize()
        return v if isinstance(v, (jax.Array, jax.core.Tracer)) \
            else jnp.asarray(v)

    def _params_dict(self):
        return {n: self._real_value(p)
                for n, p in self.network.named_parameters()}

    def _buffers_dict(self):
        return {n: self._real_value(b)
                for n, b in self.network.named_buffers()}

    # ---- device-resident train state ------------------------------------
    @property
    def _opt_state(self):
        ts = self._tstate
        return ts.opt_state if ts is not None else self._opt_state_host

    @_opt_state.setter
    def _opt_state(self, value):
        ts = self._tstate
        if ts is not None:
            ts.opt_state = value
        else:
            self._opt_state_host = value

    def _ensure_tstate(self):
        """Capture (or reconcile) the device-resident train state. Layer
        tensors keep only DeviceResidentRef placeholders while the executor
        owns the arrays; an externally mutated tensor (detected via the
        global mutation counter) always wins over the captured copy."""
        ts = self._tstate
        if (ts is not None
                and ts.mut_version == _core_tensor.mutation_version()
                and not (self._async and ts.refs_dirty)):
            # steady-state fast path: no external mutation, no structural
            # change (registration paths bump the counter too), and every
            # Layer tensor still holds its placeholder — nothing to do
            return ts
        named_p = list(self.network.named_parameters())
        named_b = list(self.network.named_buffers())
        if (ts is None or set(ts.params) != {n for n, _ in named_p}
                or set(ts.buffers) != {n for n, _ in named_b}):
            prev_opt = self._opt_state
            ts = _TrainState()
            ts.params = {n: self._real_value(p) for n, p in named_p}
            ts.buffers = {n: self._real_value(b) for n, b in named_b}
            if self._partitioner is not None:
                # place the captured state over the strategy mesh: params
                # per their resolved specs, buffers replicated — the jit'd
                # step propagates these in-shardings (GSPMD) and donation
                # keeps the outputs aliased in place
                from jax.sharding import NamedSharding, PartitionSpec
                from ..parallel.parallelize import param_spec
                mesh = self._partitioner.mesh

                def _put(v, spec):
                    try:
                        return jax.device_put(v, NamedSharding(mesh, spec))
                    except Exception:
                        return v
                ts.params = {
                    n: _put(ts.params[n],
                            param_spec(p, n, self._partitioner))
                    for n, p in named_p}
                ts.buffers = {n: _put(v, PartitionSpec())
                              for n, v in ts.buffers.items()}
            ts.opt_state = prev_opt
            ts.mut_version = _core_tensor.mutation_version()
            ts.refs_dirty = True
            self._tstate = ts
        elif ts.mut_version != _core_tensor.mutation_version():
            for n, p in named_p:
                v = p._value
                if type(v) is not DeviceResidentRef and v is not ts.params[n]:
                    ts.params[n] = v if isinstance(
                        v, (jax.Array, jax.core.Tracer)) else jnp.asarray(v)
            for n, b in named_b:
                v = b._value
                if type(v) is not DeviceResidentRef and v is not ts.buffers[n]:
                    ts.buffers[n] = v if isinstance(
                        v, (jax.Array, jax.core.Tracer)) else jnp.asarray(v)
            ts.mut_version = _core_tensor.mutation_version()
        if self._async and ts.refs_dirty:
            # donation will invalidate the arrays a materialized tensor is
            # holding — swap the placeholders back in before the next step
            for n, p in named_p:
                if type(p._value) is not DeviceResidentRef:
                    arr = ts.params[n]
                    p._value = DeviceResidentRef(ts, 'params', n, p,
                                                 arr.shape, arr.dtype)
            for n, b in named_b:
                if type(b._value) is not DeviceResidentRef:
                    arr = ts.buffers[n]
                    b._value = DeviceResidentRef(ts, 'buffers', n, b,
                                                 arr.shape, arr.dtype)
            ts.refs_dirty = False
        return ts

    def _sync_train_state(self):
        """Lazy write-back: put the live device arrays back into the Layer
        tree (fit exit, save(), checkpoint callbacks). Only placeholders are
        overwritten — a tensor the user replaced keeps the user's value."""
        ts = self._tstate
        if ts is None:
            return
        for n, p in self.network.named_parameters():
            if type(p._value) is DeviceResidentRef and n in ts.params:
                p._value = ts.params[n]
                p._node = None
        for n, b in self.network.named_buffers():
            if type(b._value) is DeviceResidentRef and n in ts.buffers:
                b._value = ts.buffers[n]
                b._node = None
        ts.refs_dirty = True

    def _write_back_from_state(self, ts):
        """Synchronous-mode write-back: unconditionally push the state's
        arrays into the Layer tree after every step (legacy behavior)."""
        for n, p in self.network.named_parameters():
            if n in ts.params:
                p._value = ts.params[n]
                p._node = None
        for n, b in self.network.named_buffers():
            if n in ts.buffers:
                b._value = ts.buffers[n]
                b._node = None

    def _finish_step(self, loss):
        if not self._async:
            self._write_back_from_state(self._tstate)
            return [np.asarray(loss)]
        # bounded in-flight window: block on the oldest dispatched step so a
        # NaN or injected fault surfaces within ~window steps of its batch
        self._inflight.append(loss)
        while len(self._inflight) > self._inflight_window:
            old = self._inflight.popleft()
            try:
                old.block_until_ready()
            except AttributeError:
                pass
        return [loss]

    def _drain_inflight(self):
        while self._inflight:
            old = self._inflight.popleft()
            try:
                old.block_until_ready()
            except AttributeError:
                pass

    def _lr_scalar(self):
        fn = getattr(self._optimizer, '_lr_device', None)
        if fn is not None:
            return fn()
        return jnp.asarray(self._optimizer.get_lr(), jnp.float32)

    def _accum_scale(self, value):
        cache = self._scale_cache
        if cache is None or cache[0] != value:
            cache = (value, jax.device_put(np.float32(value)))
            self._scale_cache = cache
        return cache[1]

    def _compute_loss(self, outputs, labels):
        outs = outputs if isinstance(outputs, (list, tuple)) else [outputs]
        with no_grad_ctx():
            out_t = [Tensor(o) for o in outs]
            lab_t = [Tensor(l) for l in labels]
            loss = self._loss(*out_t, *lab_t)
        if isinstance(loss, (list, tuple)):
            total = loss[0]
            for l in loss[1:]:
                total = total + l
            loss = total
        return loss._value if isinstance(loss, Tensor) else loss

    def _asp_masks_by_name(self):
        """ASP masks for this network's params keyed by name (None when
        none registered) — the fused functional step bypasses the eager
        optimizer.step that sparsity.decorate wraps, so mask re-application
        is traced into the step itself."""
        from ..sparsity import ASPHelper
        if not ASPHelper._masks:
            return None          # nothing registered: skip the traversal
        masks = {}
        for n, p in self.network.named_parameters():
            ent = ASPHelper._masks.get(id(p))
            # the registry keys by id(param): a reused id from a dead
            # parameter must not map a stale mask onto this one
            if ent is not None and ent[0]() is p:
                masks[n] = ent[1]
        return masks or None

    def _asp_signature(self):
        # mask IDENTITY, not just names: re-pruning the same params installs
        # new mask arrays that must force a train-step rebuild (advisor r3)
        m = self._asp_masks_by_name()
        return tuple(sorted((n, id(v)) for n, v in m.items())) if m else None

    # ---- mode handling ---------------------------------------------------
    def _enter_mode(self, training):
        """Hoisted out of the traced step (the old in-trace ``l.training``
        writes left stale flags baked into the jit cache). The network is
        flipped only when crossing the train/eval boundary, so fine-grained
        user overrides (e.g. freezing one BatchNorm with ``bn.eval()``
        mid-training) persist and simply select a differently-keyed
        compiled step."""
        if self._net_mode is not training:
            if training:
                self.network.train()
            else:
                self.network.eval()
            self._net_mode = training

    def _mode_sig(self):
        from ..nn import layer_base as _lb
        mv = _lb.mode_version()
        cache = self._mode_sig_cache
        if cache is not None and cache[0] == mv:
            return cache[1]
        sig = tuple(l.training
                    for l in self.network.sublayers(include_self=True))
        self._mode_sig_cache = (mv, sig)
        return sig

    def _amp_sig(self):
        """Active auto_cast configuration (level/dtype/custom lists), or
        None when amp is off. The amp hook fires at op dispatch — which
        includes jit TRACING — so a step traced under one auto_cast config
        bakes that config in; keying the step caches on this signature
        makes toggling auto_cast (or editing its lists) retrace instead of
        silently reusing the stale step."""
        from .. import amp as _amp
        return _amp._amp_signature()

    # ---- compiled steps --------------------------------------------------
    def _build_train_step(self):
        net = self.network
        opt = self._optimizer
        asp_masks = self._asp_masks_by_name()

        def remask(params):
            if asp_masks is None:
                return params
            return {n: (v * asp_masks[n] if n in asp_masks else v)
                    for n, v in params.items()}

        def loss_and_grads(params, buffers, key, inputs, labels):
            def loss_fn(p):
                with rng_scope(key):
                    out, new_buf = functional_call(net, p, buffers, *inputs)
                loss = self._compute_loss(out, labels)
                return loss, (out, new_buf)
            return jax.value_and_grad(loss_fn, has_aux=True)(params)

        def step(params, buffers, opt_state, key, lr, inputs, labels):
            self._step_traces += 1      # trace-time side effect: retraces
            (loss, (out, new_buf)), grads = loss_and_grads(
                params, buffers, key, inputs, labels)
            new_params, new_state = opt.functional_apply(params, grads,
                                                         opt_state, lr)
            return loss, out, remask(new_params), new_buf, new_state

        def accum_step(params, buffers, grad_acc, key, inputs, labels):
            """Gradient-merge micro-step: accumulate grads, no update.
            Reference: fleet/meta_optimizers/gradient_merge_optimizer.py."""
            (loss, (out, new_buf)), grads = loss_and_grads(
                params, buffers, key, inputs, labels)
            grad_acc = jax.tree_util.tree_map(jnp.add, grad_acc, grads)
            return loss, out, new_buf, grad_acc

        def apply_accum(params, opt_state, grad_acc, lr, scale):
            grads = jax.tree_util.tree_map(lambda g: g * scale, grad_acc)
            new_p, new_s = opt.functional_apply(params, grads, opt_state, lr)
            return remask(new_p), new_s

        # donation lets XLA update params/opt-state in place instead of
        # doubling HBM traffic; params survive accum micro-steps (they are
        # re-fed to the final apply), so only buffers/grad_acc donate there
        if self._async:
            # apply_accum does NOT donate grad_acc: it has no same-shaped
            # output to alias with (XLA would warn and ignore the donation)
            return (jax.jit(step, donate_argnums=(0, 1, 2)),
                    jax.jit(accum_step, donate_argnums=(1, 2)),
                    jax.jit(apply_accum, donate_argnums=(0, 1)))
        # the sync path re-reads params/opt_state after each step (metric
        # hooks, host-side inspection), so donating would invalidate them
        # pt-lint: disable=trace-missing-donate
        return jax.jit(step), jax.jit(accum_step), jax.jit(apply_accum)

    def _build_eval_step(self):
        net = self.network

        def step(params, buffers, key, inputs, labels):
            self._eval_traces += 1
            with rng_scope(key):
                out, _ = functional_call(net, params, buffers, *inputs)
            loss = None
            if self._loss is not None and labels:
                loss = self._compute_loss(out, labels)
            return loss, out

        return jax.jit(step)

    def _split_batch(self, batch):
        batch = list(batch) if isinstance(batch, (list, tuple)) else [batch]
        arrs = [self._as_device(b) for b in batch]
        n_in = len(self._inputs) if self._inputs else (
            len(arrs) - len(self._labels) if self._labels else
            (len(arrs) - 1 if self._loss is not None and len(arrs) > 1 else len(arrs)))
        return arrs[:n_in], arrs[n_in:]

    @staticmethod
    def _as_device(t):
        """Tensor/device-array/numpy -> jax array without forcing an extra
        host round-trip: device arrays pass through untouched, numpy goes
        through jnp.asarray once (zero-copy where the backend allows)."""
        if isinstance(t, Tensor):
            v = t._value
            return v.materialize() if type(v) is DeviceResidentRef else v
        if isinstance(t, (jax.Array, jax.core.Tracer)):
            return t
        return jnp.asarray(t)

    def _maybe_place_batch(self, arr):
        """Shard a batch array's leading dim per the partitioner's 'batch'
        rule (no-op without a strategy, or for scalars)."""
        pt = self._partitioner
        if pt is None or getattr(arr, 'ndim', 0) == 0:
            return arr
        return pt.place_batch(arr)

    # ---- public batch APIs ----------------------------------------------
    def train_batch(self, inputs, labels=None, update=True):
        from ..distributed.launch import touch_heartbeat
        touch_heartbeat()   # liveness signal for the elastic launcher
        self._enter_mode(True)
        sig = self._asp_signature()
        if self._train_steps and getattr(self, '_asp_sig', None) != sig:
            # prune_model after a warmup fit (the standard ASP recipe):
            # rebuild so the new masks trace into the step
            self._train_steps.clear()
            self._opt_init_pending = True
        mode_key = (self._mode_sig(), self._amp_sig())
        fns = self._train_steps.get(mode_key)
        if fns is None:
            self._asp_sig = sig
            fns = self._build_train_step()
            self._train_steps[mode_key] = fns
        self._train_step, self._accum_step, self._apply_accum = fns
        ts = self._ensure_tstate()
        if ts.opt_state is None or (self._opt_init_pending
                                    and not self._opt_restored):
            # a restored opt_state (Model.load / AutoResume) must survive
            # the lazy first-step build instead of being re-initialized
            ts.opt_state = self._optimizer.functional_init(ts.params)
            if (self._partitioner is not None and self._strategy is not None
                    and getattr(self._strategy, 'sharding', False)):
                # ZeRO-1: optimizer states sharded over the data axes
                ts.opt_state = self._partitioner.place_zero(ts.opt_state)
        self._opt_init_pending = False
        inputs = [self._maybe_place_batch(self._as_device(t))
                  for t in _to_list(inputs)]
        labels = [self._maybe_place_batch(self._as_device(t))
                  for t in _to_list(labels)]
        wm = sys.modules.get('paddle_tpu.warmup.manifest')
        if wm is not None and wm.capturing():
            wm.record(wm.train_step_entry(
                wm.array_sig(inputs), wm.array_sig(labels),
                accumulate=(not update) or self._grad_acc is not None))
        lr = self._lr_scalar()
        key = next_key()
        if not update:
            # gradient-merge micro step: accumulate into self._grad_acc
            if self._grad_acc is None:
                self._grad_acc = jax.tree_util.tree_map(jnp.zeros_like,
                                                        ts.params)
                self._accum_count = 0
            acc_args = (ts.params, ts.buffers, self._grad_acc, key,
                        tuple(inputs), tuple(labels))
            loss, out, new_b, self._grad_acc = self._accum_step(*acc_args)
            _perf_analyze('hapi.accum_step', self._accum_step, acc_args)
            ts.buffers = new_b
            self._accum_count += 1
            self._last_outputs = out
            return self._finish_step(loss)
        if self._grad_acc is not None:
            # final micro step: accumulate then apply averaged grads
            acc_args = (ts.params, ts.buffers, self._grad_acc, key,
                        tuple(inputs), tuple(labels))
            loss, out, new_b, self._grad_acc = self._accum_step(*acc_args)
            _perf_analyze('hapi.accum_step', self._accum_step, acc_args)
            self._accum_count += 1
            apply_args = (ts.params, ts.opt_state, self._grad_acc, lr,
                          self._accum_scale(1.0 / self._accum_count))
            new_p, new_s = self._apply_accum(*apply_args)
            _perf_analyze('hapi.apply_accum', self._apply_accum, apply_args)
            ts.params, ts.buffers, ts.opt_state = new_p, new_b, new_s
            self._grad_acc = None
            self._last_outputs = out
            return self._finish_step(loss)
        step_args = (ts.params, ts.buffers, ts.opt_state, key, lr,
                     tuple(inputs), tuple(labels))
        loss, out, new_p, new_b, new_s = self._train_step(*step_args)
        _perf_analyze('hapi.train_step', self._train_step, step_args)
        ts.params, ts.buffers, ts.opt_state = new_p, new_b, new_s
        self._last_outputs = out
        return self._finish_step(loss)

    def _flush_grad_acc(self):
        """Apply any pending accumulated grads (partial gradient-merge cycle)."""
        if self._grad_acc is None:
            return
        ts = self._ensure_tstate()
        new_p, new_s = self._apply_accum(
            ts.params, ts.opt_state, self._grad_acc, self._lr_scalar(),
            self._accum_scale(1.0 / max(self._accum_count, 1)))
        ts.params, ts.opt_state = new_p, new_s
        self._grad_acc = None
        self._accum_count = 0
        if not self._async:
            self._write_back_from_state(ts)

    def eval_batch(self, inputs, labels=None):
        self._enter_mode(False)
        _obs.counter('train.eval_batches').inc()
        inputs = [self._maybe_place_batch(self._as_device(t))
                  for t in _to_list(inputs)]
        labels = [self._maybe_place_batch(self._as_device(t))
                  for t in _to_list(labels)]
        # cache keyed on (mode, input signature) like the train path keys on
        # mode: a predict stream with a ragged tail batch (or alternating
        # labeled/unlabeled calls) selects its cached step by shape/dtype
        # tree instead of churning one entry
        key = (self._mode_sig(), self._amp_sig(),
               tuple((tuple(getattr(a, 'shape', ())),
                      str(getattr(a, 'dtype', ''))) for a in inputs),
               tuple((tuple(getattr(a, 'shape', ())),
                      str(getattr(a, 'dtype', ''))) for a in labels))
        step = self._eval_steps.get(key)
        if step is None:
            step = self._build_eval_step()
            self._eval_steps[key] = step
        self._eval_step = step
        wm = sys.modules.get('paddle_tpu.warmup.manifest')
        if wm is not None and wm.capturing():
            wm.record(wm.eval_step_entry(key[2], key[3]))
        if self._tstate is not None:
            ts = self._ensure_tstate()
            params, buffers = ts.params, ts.buffers
        else:
            params, buffers = self._params_dict(), self._buffers_dict()
        eval_args = (params, buffers, next_key(),
                     tuple(inputs), tuple(labels))
        loss, out = step(*eval_args)
        _perf_analyze('hapi.eval_step', step, eval_args)
        return ([np.asarray(loss)] if loss is not None else None,
                out)

    def predict_batch(self, inputs):
        _, out = self.eval_batch(inputs, [])
        outs = out if isinstance(out, (list, tuple)) else [out]
        return [np.asarray(o) for o in outs]

    # ---- fit/evaluate/predict -------------------------------------------
    def _as_loader(self, data, batch_size, shuffle):
        if data is None or isinstance(data, DataLoader):
            return data
        if isinstance(data, Dataset):
            return DataLoader(data, batch_size=batch_size, shuffle=shuffle)
        return data

    def fit(self, train_data=None, eval_data=None, batch_size=1, epochs=1,
            eval_freq=1, log_freq=10, save_dir=None, save_freq=1, verbose=2,
            drop_last=False, shuffle=True, num_workers=0, callbacks=None,
            accumulate_grad_batches=1, num_iters=None, resume=None,
            warmup=None, telemetry_port=None):
        from .callbacks import (AutoResume, CallbackList, ModelCheckpoint,
                                ProgBarLogger)
        if telemetry_port is not None:
            # fit-time telemetry opt-in: serve /metrics (+ /healthz,
            # /debug/trace) for this training run; lives until process exit
            # (daemon thread), reachable at self.telemetry.url
            self.telemetry = _obs.serve_telemetry(port=telemetry_port)
        if warmup is not None:
            # compile the recorded step signatures before the first batch so
            # step 0 runs at steady-state latency (and hits the persistent
            # cache when enabled)
            self.prebuild_warmup(warmup)
        loader = self._as_loader(train_data, batch_size, shuffle)
        eval_loader = self._as_loader(eval_data, batch_size, False)
        callbacks = list(callbacks or [])
        if resume:
            # resume=<dir> (or resume=True with save_dir) restores the newest
            # verified checkpoint and continues mid-run — the elastic-relaunch
            # recovery path. Delegates to an AutoResume callback (one owner).
            rdir = resume if isinstance(resume, str) else save_dir
            if rdir and not any(isinstance(c, AutoResume) for c in callbacks):
                callbacks.append(AutoResume(rdir, save_freq=save_freq))
        if save_dir and not any(isinstance(c, ModelCheckpoint)
                                for c in callbacks):
            # reference config_callbacks: save_dir/save_freq delegate to a
            # ModelCheckpoint — ONE owner of the save schedule (review r4b:
            # an inline copy here had drifted from the callback's)
            callbacks.append(ModelCheckpoint(save_freq, save_dir))
        auto_resume = next((c for c in callbacks if isinstance(c, AutoResume)),
                           None)
        cbks = CallbackList(callbacks, self, verbose=verbose,
                            log_freq=log_freq)
        cbks.on_begin('train', {'epochs': epochs,
                                'steps': len(loader) if hasattr(loader, '__len__') else None,
                                'metrics': ['loss'] + sum([m.name() if isinstance(m.name(), list)
                                                           else [m.name()] for m in self._metrics], [])})
        it_count = 0
        logs = {}
        timer = self._step_timer
        start_epoch, skip_steps = 0, 0
        if auto_resume is not None and auto_resume.resume_info:
            info = auto_resume.resume_info
            if info.get('step') is None:      # epoch boundary checkpoint
                start_epoch = info['epoch'] + 1
            else:                             # mid-epoch: redo epoch tail
                start_epoch = info['epoch']
                skip_steps = info['step'] + 1
            it_count = info.get('global_step', 0)
        use_prefetch = self._async and isinstance(loader, DataLoader)
        # manual enter/exit: the whole epoch loop is one 'train.fit' span
        # without re-indenting it (complete events nest by ts/dur anyway)
        fit_span = _obs.span('train.fit', epochs=epochs,
                             start_epoch=start_epoch)
        fit_span.__enter__()
        step_ms = _obs.histogram('train.step_ms')
        step_counter = _obs.counter('train.steps')
        loss_gauge = _obs.gauge('train.loss')
        # always-on goodput accounting: the run window opens here; steps,
        # data stalls, and compile steps are classified below, checkpoint/
        # preemption/requeue badput arrives from the ckpt + retry paths
        goodput = _obs.goodput.ledger()
        goodput.run_start()
        for epoch in range(start_epoch, epochs):
            if auto_resume is not None:
                # deterministic per-epoch shuffle so a resumed lifetime sees
                # the same batch order the interrupted one did
                np.random.seed((auto_resume.seed_base + epoch) % (2 ** 32))
                bs = getattr(loader, 'batch_sampler', None)
                if bs is not None and hasattr(bs, 'set_epoch'):
                    bs.set_epoch(epoch)
            cbks.on_epoch_begin(epoch)
            for m in self._metrics:
                m.reset()
            logs = {}
            prefetch_gen = (loader.prefetch_to_device() if use_prefetch
                            else None)
            batch_iter = prefetch_gen if prefetch_gen is not None else loader
            # innermost wrapper: measures the raw loader/prefetch wait so
            # blocking batch waits above the stall floor book as data_stall
            batch_iter = goodput.data_iter(batch_iter)
            if timer is not None:
                batch_iter = timer.timed_iter('data', batch_iter)
            try:
                for step_idx, batch in enumerate(batch_iter):
                    if epoch == start_epoch and step_idx < skip_steps:
                        continue      # already trained before the restart
                    cbks.on_batch_begin('train', step_idx, logs)
                    inputs, labels = self._split_batch(batch)
                    do_update = (step_idx + 1) % accumulate_grad_batches == 0
                    if timer is not None:
                        t0 = time.perf_counter()
                    traces_before = self._step_traces
                    try:
                        with _obs.span('train.step', step=it_count) as sp:
                            loss = self.train_batch(inputs, labels,
                                                    update=do_update)
                    except BaseException:
                        # a raising step must not book a partial duration
                        # into the phase histograms (satellite: StepTimer
                        # exception safety)
                        if timer is not None:
                            timer.abort_step()
                        raise
                    step_ms.observe(1e3 * sp.duration)
                    step_counter.inc()
                    _obs.perf.note_step('hapi.train_step', sp.duration)
                    if self._step_traces > traces_before:
                        # the step retraced/compiled: the whole step wall
                        # time is compile badput (goodput convention)
                        goodput.note_badput('compile', sp.duration)
                    goodput.note_step(sp.duration)
                    if timer is not None:
                        timer.add('dispatch', time.perf_counter() - t0)
                    lval = loss[0]
                    if not self._async or step_idx % log_freq == 0:
                        # deferred loss readback: the device scalar is only
                        # resolved to a python float at logging points
                        if timer is not None:
                            t0 = time.perf_counter()
                        lval = float(np.asarray(lval))
                        if timer is not None:
                            timer.add('readback', time.perf_counter() - t0)
                        loss_gauge.set(lval)
                        if step_idx % log_freq == 0:
                            # HBM sweep at log points only: live_arrays()
                            # every sync step would blow the <5% obs budget
                            _obs.perf.sweep_hbm()
                    logs = {'loss': lval, 'step': step_idx}
                    self._update_metrics(logs, inputs, labels)
                    cbks.on_batch_end('train', step_idx, logs)
                    if timer is not None:
                        timer.step_done()
                    it_count += 1
                    if num_iters is not None and it_count >= num_iters:
                        break
            finally:
                if prefetch_gen is not None:
                    prefetch_gen.close()   # stop the producer thread
            # flush a partial gradient-merge cycle so stale grads never leak
            # into the next epoch (or a later fit call) with a wrong divisor
            self._flush_grad_acc()
            self._drain_inflight()
            if 'loss' in logs and not isinstance(logs['loss'], float):
                logs['loss'] = float(np.asarray(logs['loss']))
            from ..optimizer.lr import LRScheduler, ReduceOnPlateau
            if isinstance(self._optimizer._lr, LRScheduler) and \
                    not isinstance(self._optimizer._lr, ReduceOnPlateau):
                self._optimizer._lr.step()
            if eval_loader is not None and (epoch + 1) % eval_freq == 0:
                eval_logs = self.evaluate(eval_loader, verbose=0)
                logs.update({'eval_' + k: v for k, v in eval_logs.items()})
            cbks.on_epoch_end(epoch, logs)
            _obs.counter('train.epochs').inc()
            if self.stop_training:
                break
        goodput.run_end()
        fit_span.__exit__(None, None, None)
        # fit() exit is a read point: device-resident state flows back into
        # the Layer objects before user code (or on_train_end callbacks,
        # e.g. the final ModelCheckpoint) can look at them
        self._drain_inflight()
        self._sync_train_state()
        cbks.on_end('train', logs)

    def _update_metrics(self, logs, inputs, labels):
        if not self._metrics or not labels:
            return
        # reuse the forward outputs already computed inside the train step
        out = self._last_outputs
        if out is None:
            preds = self.predict_batch([Tensor(i) for i in inputs])
            first = jnp.asarray(preds[0])
        else:
            first = out[0] if isinstance(out, (list, tuple)) else out
        for m in self._metrics:
            res = m.compute(Tensor(first), Tensor(labels[0]))
            # reference contract: a tuple-returning compute() is UNPACKED
            # into update(*results)
            acc = m.update(*(res if isinstance(res, (list, tuple))
                             else (res,)))
            if acc is None:
                # Precision/Recall/Auc-style updates return nothing; the
                # running value comes from accumulate()
                acc = m.accumulate()
            names = m.name() if isinstance(m.name(), list) else [m.name()]
            vals = acc if isinstance(acc, list) else [acc]
            for n, v in zip(names, vals):
                logs[n] = float(v)

    def evaluate(self, eval_data, batch_size=1, log_freq=10, verbose=2,
                 num_workers=0, callbacks=None, num_iters=None):
        loader = self._as_loader(eval_data, batch_size, False)
        for m in self._metrics:
            m.reset()
        losses = []
        for batch in loader:
            inputs, labels = self._split_batch(batch)
            loss, out = self.eval_batch(inputs, labels)
            if loss is not None:
                losses.append(loss[0])
            if self._metrics and labels:
                outs = out if isinstance(out, (list, tuple)) else [out]
                for m in self._metrics:
                    res = m.compute(Tensor(outs[0]), Tensor(labels[0]))
                    m.update(*(res if isinstance(res, (list, tuple))
                               else (res,)))
        logs = {}
        if losses:
            logs['loss'] = float(np.mean([np.asarray(l) for l in losses]))
        for m in self._metrics:
            names = m.name() if isinstance(m.name(), list) else [m.name()]
            vals = m.accumulate()
            vals = vals if isinstance(vals, list) else [vals]
            for n, v in zip(names, vals):
                logs[n] = float(v)
        return logs

    def predict(self, test_data, batch_size=1, num_workers=0, stack_outputs=False,
                verbose=1, callbacks=None, bucket_pad=True, engine=None):
        """Run inference over ``test_data``.

        ``bucket_pad`` (default on) pads a ragged tail batch up to the
        nominal batch size (repeating the last row) and slices the outputs
        back, so the whole loader is served by ONE compiled eval step
        instead of retracing for the leftover batch. ``engine`` routes the
        batches through a ``serving.InferenceEngine`` instead: pass an
        engine instance, or ``True`` to use ``self.serving_engine()``.
        Outputs stay on device until the end — no per-batch host round-trip
        — so dispatch overlaps the next batch's collation.
        """
        loader = self._as_loader(test_data, batch_size, False)
        if engine is not None:
            from ..serving.errors import QueueFullError
            eng = self.serving_engine() if engine is True else engine
            # bounded in-flight window: submitting the whole loader up front
            # would trip the engine's own admission control (QueueFullError
            # past queue_capacity). Results are consumed in submission order
            # so output ordering is preserved.
            window = max(1, getattr(eng, 'queue_capacity', 256) // 2)
            pending = collections.deque()
            outputs = []

            def _consume(f):
                res = f.result()
                outputs.append(res if isinstance(res, list) else [res])

            for batch in loader:
                inputs, _ = self._split_batch(batch)
                arrs = [np.asarray(i) for i in inputs]
                while len(pending) >= window:
                    _consume(pending.popleft())
                while True:
                    try:
                        pending.append(eng.submit(*arrs))
                        break
                    except QueueFullError as e:
                        # other submitters (or split chunks) filled the
                        # queue: drain one of ours and retry
                        if pending:
                            _consume(pending.popleft())
                        elif e.retry_after_ms:
                            # a shedding engine/host advertised when
                            # capacity should exist again — honor it
                            # instead of hot-spinning on the admission gate
                            time.sleep(e.retry_after_ms / 1e3)
                        else:
                            time.sleep(1e-3)
            while pending:
                _consume(pending.popleft())
        else:
            device_outs = []
            nominal = None
            for batch in loader:
                inputs, _ = self._split_batch(batch)
                first = inputs[0] if inputs else None
                n = (first.shape[0]
                     if getattr(first, 'ndim', 0) >= 1 else None)
                if nominal is None:
                    nominal = n
                padded = (bucket_pad and n is not None and nominal is not None
                          and n < nominal)
                if padded:
                    pad = nominal - n
                    inputs = [jnp.concatenate(
                        [x, jnp.repeat(x[-1:], pad, axis=0)], axis=0)
                        if getattr(x, 'ndim', 0) >= 1 and x.shape[0] == n
                        else x for x in inputs]
                _, out = self.eval_batch(inputs, [])
                outs = out if isinstance(out, (list, tuple)) else [out]
                if padded:
                    outs = [o[:n] if (getattr(o, 'ndim', 0) >= 1
                                      and o.shape[0] == nominal) else o
                            for o in outs]
                device_outs.append(outs)
            # single host materialization point: device work for every batch
            # was already dispatched asynchronously above
            outputs = [[np.asarray(o) for o in outs] for outs in device_outs]
        n_out = len(outputs[0])
        grouped = [[o[i] for o in outputs] for i in range(n_out)]
        if stack_outputs:
            grouped = [np.concatenate(g, axis=0) for g in grouped]
        return grouped

    def serving_engine(self, **kwargs):
        """Lazily build (and cache) a ``serving.InferenceEngine`` over this
        model's network — the dynamic-batching path for online traffic
        (``Model.predict(..., engine=True)`` routes through it)."""
        if self._engine is not None and kwargs and \
                kwargs != self._engine_kwargs:
            # a different config was requested: rebuild instead of silently
            # returning the previously-configured engine
            self._engine.shutdown()
            self._engine = None
        if self._engine is None:
            from ..serving import InferenceEngine
            self._engine = InferenceEngine(self, **kwargs)
            self._engine_kwargs = kwargs
        return self._engine

    # ---- persistence -----------------------------------------------------
    def save(self, path, training=True):
        from ..framework_io import save as fsave
        self._drain_inflight()
        self._sync_train_state()
        fsave(self.network.state_dict(), path + '.pdparams')
        if training and self._optimizer is not None:
            opt_state = {'opt_state': jax.tree_util.tree_map(np.asarray, self._opt_state)
                         if self._opt_state is not None else None}
            fsave(opt_state, path + '.pdopt')

    def load(self, path, skip_mismatch=False, reset_optimizer=False):
        from ..framework_io import load as fload
        state = fload(path + '.pdparams')
        self.network.set_state_dict(state)
        opt_path = path + '.pdopt'
        if not reset_optimizer and os.path.exists(opt_path):
            st = fload(opt_path)
            if st.get('opt_state') is not None:
                self._opt_state = jax.tree_util.tree_map(jnp.asarray, st['opt_state'])
                self._opt_restored = True

    def parameters(self, *args, **kwargs):
        return self.network.parameters()

    def summary(self, input_size=None, dtype=None):
        from . import summary as _summary
        return _summary(self.network, input_size, dtype)
