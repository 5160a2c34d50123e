"""Train a GPT through the program's own entry points (fleet.init,
make_train_step, LMTokenLoader), time every whole step of the window, and
hold the first three steps to the plain reference."""
import gc
import math
import time

import numpy as np

from benchmark.harness import context as _ctx
from benchmark.harness import manifest as _manifest
from benchmark.harness import stats
from benchmark.harness.tracing import TailTrace, span

SPANS = ('bench.data', 'bench.dispatch', 'bench.loss_read')
BLOCK_SECONDS = 2.0       # a block's reading (per-layer) spans 2 s
WARM_STEPS = 5


class _GcPauses:
    """How long the collector held the interpreter, collection by
    collection, while this object is open."""

    def __init__(self):
        self.seconds, self._t = [], None
        gc.callbacks.append(self._note)

    def _note(self, phase, info):
        if phase == 'start':
            self._t = time.perf_counter()
        elif self._t is not None:
            self.seconds.append(time.perf_counter() - self._t)

    def close(self):
        gc.callbacks.remove(self._note)


def _moments(opt_state):
    import jax
    return jax.tree_util.tree_map(
        lambda s: s['moment1'], opt_state,
        is_leaf=lambda x: isinstance(x, dict) and 'moment1' in x)


def run(ctx):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import paddle_tpu as paddle
    from paddle_tpu import warmup
    from paddle_tpu.distributed import fleet
    from paddle_tpu.io.native_loader import LMTokenLoader
    from paddle_tpu.models import gpt

    warmup.ensure_persistent_cache()
    ref = _manifest.load_module('reference', ctx.config['reference'])
    gen = _manifest.load_module('generators', ctx.traffic['generator'])
    shape = dict(ctx.config['model'])
    program = dict(ctx.config['program'], **(ctx.control or {}))
    hyper = ctx.config['optimizer']
    tp = ctx.traffic['params']
    batch, seq = tp['batch'], tp['seq']
    chips = len(ctx.devices)
    mesh_degrees = ctx.config.get('mesh', {})

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {f'{k}_degree': v
                               for k, v in mesh_degrees.items()}
    mesh = fleet.init(is_collective=True, strategy=strategy).mesh
    if mesh.size != chips:
        raise RuntimeError(f'mesh of {mesh.size} devices, cell asks {chips}')
    cfg = gpt.GPTConfig(**shape, mp=mesh_degrees.get('mp', 1), **program)
    fp8 = cfg.matmul_precision == 'fp8'

    # weights: made on the device in one jitted call, from the seed, laid
    # out as the program's own placement rules say
    key = jax.random.PRNGKey(ctx.seed % 2 ** 31)
    layout = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s),
                                    gpt.train_specs(cfg))
    make = jax.jit(lambda k: ref.init_params(shape, k), out_shardings=layout)
    params = make(key)
    opt = paddle.optimizer.AdamW(
        learning_rate=hyper['lr'], beta1=hyper['beta1'], beta2=hyper['beta2'],
        epsilon=hyper['epsilon'], weight_decay=hyper['weight_decay'])
    state = (params, opt.functional_init(params))
    if fp8:
        state += (gpt.init_fp8_state(cfg),)
    step_fn = gpt.make_train_step(cfg, opt, mesh)
    norms = jax.jit(ref.leaf_norms)
    # the seeded weights are made again INSIDE the program that takes the
    # difference, so no second copy of them is ever held beside the state
    delta = jax.jit(lambda p, k: ref.leaf_norms(jax.tree_util.tree_map(
        lambda x, y: x - y, p, ref.init_params(shape, k))))

    stream = gen.make(tp, ctx.seed, shape['vocab_size'])
    loader = LMTokenLoader(stream, batch, seq + 1, n_workers=2)
    rows = NamedSharding(mesh, P('dp', None))
    step_key = jax.random.PRNGKey(0)            # dropout is 0: never read
    lr = jnp.asarray(hyper['lr'], jnp.float32)
    waits = []

    def feed():
        with span('bench.data'):
            t = time.perf_counter()
            b = loader.next_batch()
            toks = jax.device_put(np.ascontiguousarray(b[:, :-1]), rows)
            tgts = jax.device_put(np.ascontiguousarray(b[:, 1:]), rows)
            waits.append(time.perf_counter() - t)
        return b, toks, tgts

    def dispatch(state, toks, tgts):
        with span('bench.dispatch'):
            out = step_fn(*state, step_key, lr, toks, tgts)
        return out[0], tuple(out[1:])

    def read(loss):
        with span('bench.loss_read'):
            return float(loss)

    try:
        # ---- the first three steps, which the reference follows ---------
        first, prog = [], {'loss': []}
        for i in range(3):
            b, toks, tgts = feed()
            first.append(b)
            loss, state = dispatch(state, toks, tgts)
            prog['loss'].append(read(loss))
            if i == 0:
                # Adam's first moment after one step is (1 - beta1) g
                prog['grad_norm'] = np.asarray(
                    norms(_moments(state[1]))) / (1 - hyper['beta1'])
        prog['delta_norm'] = np.asarray(delta(state[0], key))
        ctx.log('first_steps', loss=prog['loss'],
                compile_requests=dict(ctx.compiles.requests))

        # ---- warm-up: the steady step time sets the block length --------
        t = time.perf_counter()
        for i in range(WARM_STEPS):
            _, toks, tgts = feed()
            loss, state = dispatch(state, toks, tgts)
        read(loss)
        warm_step = (time.perf_counter() - t) / WARM_STEPS
        k = max(1, math.ceil(BLOCK_SECONDS / warm_step))

        # ---- the window --------------------------------------------------
        result = {'facts': {}, 'end_to_end': {}}
        blocks, step_done, losses = [], [], []
        if ctx.seconds > 0:
            compiles0 = ctx.compiles.total()
            n_wait0 = len(waits)
            tracer = None
            setup_s = ctx.since_start()
            t0 = time.perf_counter()
            t_end = t0 + ctx.seconds
            if ctx.trace:
                tracer = TailTrace(ctx.out_dir, ctx.traffic.get(
                    'trace_seconds', 3.0))
                tracer.arm(t_end)
            pending, in_block, block_start = None, 0, t0
            pauses = _GcPauses()
            while time.perf_counter() < t_end - warm_step:
                _, toks, tgts = feed()
                loss, state = dispatch(state, toks, tgts)
                if pending is not None:
                    losses.append(read(pending))
                    step_done.append(time.perf_counter())
                pending, in_block = loss, in_block + 1
                if in_block == k:       # the block's last loss: a fence
                    losses.append(read(pending))
                    now = time.perf_counter()
                    step_done.append(now)
                    blocks.append((block_start, now))
                    pending, in_block, block_start = None, 0, now
            if pending is not None:
                losses.append(read(pending))
                step_done.append(time.perf_counter())
            t1 = time.perf_counter()
            pauses.close()
            loaded = tracer.finish(SPANS) if tracer else None
            tokens_per_step = batch * seq
            readings = stats.block_readings(
                [b for _, b in blocks], [a for a, _ in blocks], k,
                tokens_per_step, chips)
            whole = len(step_done) * tokens_per_step / (t1 - t0) / chips
            step_ms = [1e3 * (b - a)
                       for a, b in zip([t0] + step_done, step_done)]
            if not readings:
                raise RuntimeError('the window held no whole block')
            ctx.log('window', steps=len(step_done), window_s=t1 - t0,
                    tokens_per_s_chip=whole, k=k,
                    warm_step_ms=1e3 * warm_step, n_blocks=len(blocks),
                    block_tokens_per_s_chip=readings,
                    block_median=stats.median(readings),
                    # what makes a run that stands apart explainable: its
                    # slowest steps, where they fell, and the collector
                    slowest_steps_at_s_ms=[
                        (round(step_done[i] - t0, 2), round(step_ms[i], 1))
                        for i in sorted(range(len(step_ms)),
                                        key=lambda i: -step_ms[i])[:3]],
                    gc_pause_ms_max=1e3 * max(pauses.seconds, default=0.0),
                    gc_pause_ms_total=1e3 * sum(pauses.seconds),
                    loss_first=losses[:2], loss_last=losses[-2:])
            # end to end: ALL the window's whole steps over ALL its time
            result['end_to_end'] = {'train_tokens_per_s_chip': whole,
                                    'setup_s': setup_s}
            result['facts'] = {
                'compiles_in_window': ctx.compiles.total() - compiles0,
                'data_wait_ms': [1e3 * w for w in waits[n_wait0:]],
                'step_ms': step_ms,
                'block_tokens_per_s_chip': readings,
                'tokens_per_s_chip': whole,
                'steps': len(step_done), 'window_s': t1 - t0,
                'shape': shape, 'seq': seq, 'batch': batch,
                'chips': chips, 'layers': shape['num_layers'],
                'mesh': mesh_degrees, 'remat_policy': cfg.remat_policy,
                'span_names': list(SPANS), 'trace': loaded,
            }
    finally:
        loader.close()

    from benchmark.harness import device as _device
    result['device'] = _device.info(ctx.devices)
    finite = all(math.isfinite(x) for x in prog['loss'] + losses)
    del state, params, step_fn, loss

    # ---- the reference, after the program's state is freed --------------
    t = time.perf_counter()
    want = ref.train_three_steps(
        shape, key, first, hyper, ctx.devices,
        ctx.config.get('reference_checkpoint_layers', False))
    limits = ctx.config['limits']
    checks = compare(prog, want, limits)
    checks.append(_ctx.check('losses_not_finite', int(not finite), 0, True))
    if ctx.seconds > 0:
        checks.append(_ctx.check(
            'compiles_in_window',
            result['facts']['compiles_in_window'], 0, True))
    ctx.log('reference', seconds=time.perf_counter() - t, loss=want['loss'],
            program_loss=prog['loss'])
    result.update(correct=all(c['ok'] for c in checks), checks=checks,
                  attempted=len(step_done) + 3 + WARM_STEPS, failed=0)
    return result


def compare(prog, want, limits):
    """The numbers compared, each beside its limit. Norms by the worst
    leaf: the gap between the program's norm and the reference's, against
    the reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero)."""
    def worst(got, ref):
        floor = np.maximum(ref, np.median(ref))
        rel = np.abs(np.asarray(got) - ref) / floor
        i = int(np.argmax(rel))
        return float(rel[i]), want['leaves'][i]

    out = []
    per_step = limits['loss_rel']
    if not isinstance(per_step, list):
        per_step = [per_step] * 3
    for i, (a, b) in enumerate(zip(prog['loss'], want['loss']), start=1):
        out.append(_ctx.check(f'loss_step{i}_rel', abs(a - b) / abs(b),
                              per_step[i - 1]))
    g, leaf = worst(prog['grad_norm'], want['grad_norm'])
    out.append(dict(_ctx.check('grad_norm_worst_leaf_rel', g,
                               limits['grad_norm_rel']), leaf=leaf))
    d, leaf = worst(prog['delta_norm'], want['delta_norm'])
    out.append(dict(_ctx.check('delta_norm_worst_leaf_rel', d,
                               limits['delta_norm_rel']), leaf=leaf))
    return out
