"""Serve a decoder of power-retention layers (models/brumby.py) through the
program's ``serving.GenerationEngine``, as a client of the engine and
nothing more: an engine that holds no page pool at all. The client, the
closed loop and the window's reduction are ``runners/serve_gpt.py``'s, the
control's rounding is ``runners/serve_latent_moe.py``'s, the list of the
prefills that ran whole inside the traced stretch
``runners/serve_afmoe.py``'s and the comparison's driver (the reference a
layer at a time over every sampled request, each in a block of its own
padded to a power of two of rows; the median, the 99th percentile and the
largest of the rows' error energies) ``runners/serve_granite_hybrid.py``'s:
loaded from those files and not copied. What is this runner's own:

 - the family's configuration from the file's published keys, and the
   weights in the layout the program scans, made from the reference's own
   leaves a layer at a time so that two copies of the model never stand
   side by side;
 - which requests are sampled (``on_open``): the window's first request and
   every second after it, 9-10 of the ~19 a window of 16 slots sends;
 - the state's counters (``retention.*``) and the engine's bytes of state
   held, read at the window's two ends.

This model is dense (no routed expert whose choice can flip), so a row's
error energy has one mode: the median, the 99th percentile and the largest
are all compared (the configuration's file says why each limit)."""
import gc
import math
import time

import numpy as np

from benchmark.harness import context as _ctx
from benchmark.harness import device as _device
from benchmark.harness import manifest as _manifest
from benchmark.harness.tracing import TailTrace

LOGITS_EVERY = 2        # the window's first request and every 2nd after it
MODEL_KEYS = (
    'vocab_size', 'hidden_size', 'intermediate_size', 'num_hidden_layers',
    'num_attention_heads', 'num_key_value_heads', 'head_dim', 'rms_norm_eps',
    'rope_theta', 'max_position_embeddings')
COUNTERS = (('state_rows', 'prefill'), ('state_rows', 'decode'),
            ('chunks', 'prefill'))


def model_shape(config):
    """The reference's ``shape``: the file's published keys as run, and
    what the comparison's driver walks (every layer is of the one kind)."""
    shape = {k: config[k] for k in MODEL_KEYS}
    shape['max_seq_len'] = shape['max_position_embeddings']
    shape['layer_types'] = ['retention'] * shape['num_hidden_layers']
    return shape


def program_config(shape, program):
    from paddle_tpu.models import brumby
    own = {k: v for k, v in shape.items()
           if k in brumby.BrumbyConfig.__dataclass_fields__}
    return brumby.BrumbyConfig(**own, **program)


def program_params(ref, shape, cfg, key):
    """The reference's weights as the program scans them."""
    from paddle_tpu.models import family
    return dict(ref.init_ends(shape, key), layers=family.stack_layers(
        cfg.num_hidden_layers, lambda l: ref.init_layer(shape, key, l)))


def _counts():
    """{'state_rows_prefill': ..} of the state's counters so far; None
    where the program has no such counter yet."""
    from paddle_tpu import observability
    out = {}
    for name, phase in COUNTERS:
        got = observability.find(f'retention.{name}_total', {'phase': phase})
        out[f'{name}_{phase}'] = got.value if got else None
    return out


def run(ctx):
    import jax

    from paddle_tpu import observability, warmup
    from paddle_tpu.serving import (EngineClosedError, GenerationEngine,
                                    QueueFullError)

    if ctx.seconds <= 0:
        raise ValueError('a served cell needs a window: --seconds > 0')
    base = _manifest.load_module('runners', 'serve_gpt')
    latent = _manifest.load_module('runners', 'serve_latent_moe')
    afmoe = _manifest.load_module('runners', 'serve_afmoe')
    granite = _manifest.load_module('runners', 'serve_granite_hybrid')
    base.LOGITS_EVERY = LOGITS_EVERY        # this load's own copy
    warmup.ensure_persistent_cache()
    ctx.log('setup', done='imports_and_chip')
    ref = _manifest.load_module('reference', ctx.config['reference'])
    gen = _manifest.load_module('generators', ctx.traffic['generator'])
    shape = model_shape(ctx.config)
    tp = ctx.traffic['params']
    over = dict(ctx.control or {})
    weights = over.pop('weights', None)
    cfg = program_config(shape, dict(ctx.config['program'], **over))
    chips = len(ctx.devices)

    key = jax.random.PRNGKey(ctx.seed % 2 ** 31)
    params = program_params(ref, shape, cfg, key)
    if weights == 'int8_per_channel':
        params = latent.round_to_int8(params)
    elif weights is not None:
        raise ValueError(f'unknown control weights {weights!r}')
    jax.block_until_ready(params)
    ctx.log('setup', done='weights', control_weights=weights,
            control_program=over or None)
    engine = GenerationEngine(params, cfg, **ctx.config['engine'])
    del params
    report = engine.warmup()
    engine.start()
    warm = engine.submit(np.arange(16, dtype=np.int32) % shape['vocab_size'],
                         max_new_tokens=3, want_logits=True)
    warm.result(timeout=900)
    ctx.log('setup', done='warmup', prebuilt=report.get('prebuilt'),
            param_bytes=engine.stats()['param_bytes'],
            state_bytes_per_slot=engine.stats()['state_bytes_per_slot'],
            num_pages=engine.stats()['num_pages'],
            compile_requests=dict(ctx.compiles.requests))

    traffic = gen.make(tp, ctx.seed, shape['vocab_size'],
                       shape['max_seq_len'], ctx.seconds)
    client = base._Client(engine, traffic, ctx.seed % LOGITS_EVERY,
                          (QueueFullError, EngineClosedError))
    marks = {}
    tracer = TailTrace(ctx.out_dir, ctx.traffic.get(
        'trace_seconds', 4.0)) if ctx.trace else None

    def mark(at):
        marks[at] = engine.stats()
        marks['ret' + at] = _counts()
        marks['compiles' + at] = ctx.compiles.total()

    def on_open(t0):
        mark('0')
        # the sample: the window's first request and every LOGITS_EVERY-th
        # after it, so its size follows the window's requests (9-10 of
        # ~19); none for being the longest so far (the base client's other
        # rule). What it costs the timed path: the engine gathers the
        # asking slots' rows on the device (8 x 304 KB a gather) and the
        # host reads them under the next step
        client.offset = len(client.sent) % LOGITS_EVERY
        client.longest = math.inf
        if tracer:
            tracer.arm(t0 + ctx.seconds)
        ctx.log('setup', done='lead_in', active_slots=marks['0'][
            'active_slots'], queue_depth=marks['0']['queue_depth'])

    try:
        t0, t1 = base._closed_loop(client, tp, ctx.seconds, on_open,
                                   lambda: mark('1'))
        setup_s = t0 - ctx.started
        loaded = tracer.finish(base.SPANS) if tracer else None
        deadline = time.perf_counter() + base.DRAIN_SECONDS
        for rec in client.sent:
            if rec['fut'] is not None:
                try:
                    rec['fut'].exception(
                        timeout=max(0.0, deadline - time.perf_counter()))
                except TimeoutError:
                    pass
        drained = time.perf_counter()
        marks.update(open=marks['0'], close=marks['1'])
        window = base._reduce_window(client, t0, t1, chips, marks,
                                     engine.num_slots,
                                     observability.recorder(), tracer)
        prefills = afmoe._prefills_in_trace(
            client, observability.recorder(), tracer)
    finally:
        engine.shutdown(drain=False)
    ctx.log('window', drain_s=drained - t1, **window['log'])

    facts = dict(window['facts'],
                 shape=shape, chips=chips,
                 page_rows=engine.page_size, span_names=list(base.SPANS),
                 trace=loaded, prefill_rows_in_trace=prefills)
    for k, v in marks['ret1'].items():
        if v is not None:
            facts[f'retention_{k}'] = v - (marks['ret0'][k] or 0)
    # what the busy slots held at the window's two ends, in bytes
    held = [marks[at].get('state_bytes') for at in '01']
    if None not in held:
        facts['state_bytes_held'] = sum(held) / 2
    ctx.log('state', prefills_in_trace=prefills,
            pages=[marks[at].get('num_pages') for at in '01'],
            **{k: facts.get(k) for k in (
                'retention_state_rows_prefill', 'retention_state_rows_decode',
                'retention_chunks_prefill', 'state_bytes_held')})
    result = {'device': _device.info(ctx.devices),
              'end_to_end': dict(window['end_to_end'], setup_s=setup_s),
              'facts': facts}
    sample = base._served_sample(client)
    client.engine = None
    del engine, warm
    gc.collect()

    in_use = max(int((d.memory_stats() or {}).get('bytes_in_use', 0))
                 for d in ctx.devices)
    t = time.perf_counter()
    limits = ctx.config['limits']
    readings = granite.hold_to_reference(ref, shape, key, sample)
    checks = [_ctx.check(name, readings[name], limits[name])
              for name in limits]
    unheard = sum(1 for r in client.sent
                  if r['in_window'] and r['want']) - len(sample)
    checks += [
        _ctx.check('tokens_not_their_rows_best',
                   readings['tokens_not_best'], 0, True),
        _ctx.check('rows_not_finite', readings['rows_not_finite'], 0, True),
        _ctx.check('rows_not_one_a_token', readings['rows_off'], 0, True),
        _ctx.check('sampled_requests_unserved', unheard, 0, True),
        _ctx.check('no_row_compared', int(readings['rows'] == 0), 0, True),
        _ctx.check('compiles_in_window', facts['compiles_in_window'], 0,
                   True)]
    ctx.log('reference', seconds=time.perf_counter() - t,
            requests=len(sample), bytes_in_use_before=in_use, **readings)
    result.update(correct=all(c['ok'] for c in checks), checks=checks,
                  attempted=window['attempted'], failed=window['failed'])
    return result
