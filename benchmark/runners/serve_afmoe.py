"""Serve a decoder of window and full attention mixed with routed expert
layers (models/afmoe.py) through the program's ``serving.GenerationEngine``,
as a client of the engine and nothing more. The client, the two loops and
the window's reduction are ``runners/serve_gpt.py``'s, and the control's
rounding, the routed layers' counters and the size of a request's block are
``runners/serve_latent_moe.py``'s: loaded from those files and not copied.
What is this runner's own:

 - the family's configuration from the file's published keys, the chip's
   share beside them (``held``);
 - the attention's counters by kind of layer (keys and pages a decode step
   attended, pages a window gave back) read at the window's ends, and the
   prompts of the prefills that ran whole inside the traced stretch (what
   the prefill flash kernel's roofline needs);
 - the comparison's driver: the reference goes ONE LAYER AT A TIME over all
   the sampled requests, each in a block of its own padded to a power of two
   of rows (at least 1,024: five block sizes up to 16,384), the layer
   compiled once a block size and kind of layer.

Rows are bimodal with random weights, as in the latent cell (a near-tie
between a token's fourth and fifth expert flips under bfloat16 hidden
states): what is compared is the median and the 90th percentile of the
rows' error energies and the share of rows beyond a bound (the
configuration's file says which, and why each limit)."""
import gc
import math
import time

import numpy as np

from benchmark.harness import context as _ctx
from benchmark.harness import device as _device
from benchmark.harness import manifest as _manifest
from benchmark.harness.tracing import TailTrace

LOGITS_EVERY = 64       # every 64th request of the window, and the longest
MIN_BLOCK = 1024        # a request's block of rows, at least
MODEL_KEYS = (
    'vocab_size', 'hidden_size', 'intermediate_size',
    'moe_intermediate_size', 'num_hidden_layers', 'num_dense_layers',
    'num_attention_heads', 'num_key_value_heads', 'head_dim',
    'sliding_window', 'layer_types', 'num_experts', 'num_shared_experts',
    'num_experts_per_tok', 'n_group', 'topk_group', 'route_scale',
    'route_norm', 'mup_enabled', 'rms_norm_eps', 'rope_theta',
    'max_position_embeddings')
KINDS = ('full', 'window')
QUANTILES = (75, 95, 99)
BOUNDS = (1e-3, 3e-3, 1e-2, 3e-2)


def model_shape(config):
    """The reference's ``shape``: the file's published keys as run, the
    experts held counted by ``num_experts`` and placed by ``held``."""
    shape = {k: config[k] for k in MODEL_KEYS}
    first, count = config['held']['experts']
    if count != shape['num_experts']:
        raise ValueError('held.experts and num_experts disagree')
    shape.update(held_first=first,
                 router_width=config['held']['router_width'],
                 max_seq_len=shape['max_position_embeddings'])
    return shape


def program_config(shape, program):
    from paddle_tpu.models import afmoe
    own = {k: v for k, v in shape.items()
           if k in afmoe.AfmoeConfig.__dataclass_fields__}
    own.update(num_experts=shape['router_width'],
               held=(shape['held_first'], shape['num_experts']))
    return afmoe.AfmoeConfig(**own, **program)


def _attention_counts():
    """{counter: value} of the attention's and the pool's counters by kind
    so far; None where the program has no such counter yet."""
    from paddle_tpu import observability
    out = {}
    for kind in KINDS:
        for what in ('keys', 'pages'):
            got = observability.find(f'attn.{what}_attended_total',
                                     {'kind': kind})
            out[f'attn_{what}_{kind}'] = got.value if got else None
    return out


def _pages_released(engine):
    """Pages the engine's window kinds gave back so far (its own counter,
    labelled by engine and kind)."""
    from paddle_tpu import observability
    got = observability.find('kv.pages_released_total',
                             {**engine.labels, 'kind': 'window'})
    return got.value if got else None


def _prefills_in_trace(client, recorder, tracer):
    """Prompt lengths of the prefills that began and ended inside the
    traced stretch: the program's own record of a request notes 'prefill'
    as the call is handed to the device and 'first_emit' when its token is
    out, in ms since submit."""
    if tracer is None or tracer.t_start is None:
        return None
    rows = []
    for r in client.sent:
        rec = recorder.lookup(getattr(r['fut'], 'request_id', None))
        at = {}
        for e in (rec or {}).get('timeline', ()):
            at.setdefault(e['ev'], r['submit_t'] + e['t_ms'] / 1e3)
        if ('prefill' in at and 'first_emit' in at
                and tracer.t_start <= at['prefill']
                and at['first_emit'] <= tracer.t_stop):
            rows.append(len(r['prompt']))
    return rows


def run(ctx):
    import jax

    from paddle_tpu import observability, warmup
    from paddle_tpu.serving import (EngineClosedError, GenerationEngine,
                                    QueueFullError)

    if ctx.seconds <= 0:
        raise ValueError('a served cell needs a window: --seconds > 0')
    base = _manifest.load_module('runners', 'serve_gpt')
    latent = _manifest.load_module('runners', 'serve_latent_moe')
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
    params = ref.init_params(shape, key)
    if weights == 'int8_per_channel':
        params = latent.round_to_int8(params)
    elif weights is not None:
        raise ValueError(f'unknown control weights {weights!r}')
    jax.block_until_ready(params)
    ctx.log('setup', done='weights', control_weights=weights)
    engine = GenerationEngine(params, cfg, **ctx.config['engine'])
    del params
    report = engine.warmup()
    engine.start()
    warm = engine.submit(np.arange(16, dtype=np.int32) % shape['vocab_size'],
                         max_new_tokens=3, want_logits=True)
    warm.result(timeout=900)
    ctx.log('setup', done='warmup', prebuilt=report.get('prebuilt'),
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
        marks['moe' + at], marks['attn' + at] = (latent._moe_counts(),
                                                 _attention_counts())
        marks['released' + at] = _pages_released(engine)
        marks['compiles' + at] = ctx.compiles.total()

    def on_open(t0):
        mark('0')
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
        prefills = _prefills_in_trace(client, observability.recorder(),
                                      tracer)
    finally:
        engine.shutdown(drain=False)
    a, b = marks['0'], marks['1']
    moe = latent._moe_window(marks['moe0'], marks['moe1'], {
        'prefill': b['prefills'] - a['prefills'],
        'decode': b['steps'] - a['steps']})
    ctx.log('window', drain_s=drained - t1, moe=moe, **window['log'])

    facts = dict(window['facts'], shape=shape, chips=chips,
                 page_rows=engine.page_size, span_names=list(base.SPANS),
                 trace=loaded, moe_window=moe,
                 prefill_rows_in_trace=prefills)
    if moe is not None:
        for k in latent.MOE_COUNTERS:
            facts[f'moe_{k}'] = sum(moe[p][k] for p in moe)
    for k, v in marks['attn1'].items():
        if v is not None:
            facts[k] = v - (marks['attn0'][k] or 0)
    if marks['released1'] is not None:
        facts['window_pages_released'] = (marks['released1']
                                          - (marks['released0'] or 0))
    ctx.log('attention', **{k: facts.get(k) for k in (
        *marks['attn1'], 'window_pages_released')},
        prefills_in_trace=prefills)
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
    readings = hold_to_reference(ref, shape, key, sample,
                                 limits['row_energy_bound'])
    checks = [_ctx.check(name, readings[name], limits[name])
              for name in limits if name != 'row_energy_bound']
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
    # no request is counted as failed for its rows' energies: the rows are
    # held in aggregate, by ``correct`` (runners/serve_latent_moe.py)
    ctx.log('reference', seconds=time.perf_counter() - t,
            requests=len(sample), bytes_in_use_before=in_use, **readings)
    result.update(correct=all(c['ok'] for c in checks), checks=checks,
                  attempted=window['attempted'], failed=window['failed'])
    return result


def _block_rows(n):
    return max(MIN_BLOCK, 1 << (int(n) - 1).bit_length())


def hold_to_reference(ref, shape, key, sample, bound):
    """Every served row against the reference's row.

    The reference makes its own weights from the seed, a layer at a time,
    and runs its float32 'highest' layer over each sampled request's prompt
    and served tokens (teacher-forced), so that one layer's weights are all
    it holds beside the requests' activations. A row's distance is
    ||served - reference|| / ||reference||; what is compared is its square,
    the error's energy over the row's (benchmark/runners/serve_gpt.py says
    why). Every served token is also held, exactly, to the served row it was
    chosen from.

    -> {'rows', 'logit_err_energy_median', 'logit_err_energy_p90',
        'rows_beyond_bound_share', and what is reported beside them}"""
    import jax
    import jax.numpy as jnp
    context = shape['max_seq_len']
    layer = jax.jit(lambda lp, x, kind: ref.layer(lp, x, shape, kind),
                    donate_argnums=1, static_argnums=2)

    @jax.jit
    def compare(ends, x, at, served_rows, served_tokens):
        want = ref.head(ends, x[0][at], shape)                     # [R, V]
        energy = (jnp.sum(jnp.square(served_rows - want), axis=-1)
                  / jnp.sum(jnp.square(want), axis=-1))
        best = jnp.max(want, axis=-1)
        gap = best - jnp.take_along_axis(want, served_tokens[:, None],
                                         axis=-1)[:, 0]
        finite = jnp.all(jnp.isfinite(served_rows), axis=-1)
        return energy, gap / jnp.std(want, axis=-1), finite

    ends = ref.init_ends(shape, key)
    held, rows_off, not_best = [], 0, 0
    for s in sample:
        n = len(s['tokens'])
        rows_off += abs(len(s['rows']) - n) + abs(s['heard'] - n)
        n = min(n, len(s['rows']))
        seq = np.concatenate([s['prompt'], np.asarray(s['tokens'][:-1],
                                                      np.int32)])[:context]
        if n == 0:
            continue
        tokens = np.zeros((_block_rows(len(seq)),), np.int32)
        tokens[:len(seq)] = seq
        # rows, their places and their tokens in a block too (padded, so
        # that one comparison compiles a block size and not a request)
        m = _block_rows(n)
        rows = np.zeros((m, shape['vocab_size']), np.float32)
        rows[:n] = np.stack(s['rows'][:n])
        served, at = np.zeros((m,), np.int32), np.zeros((m,), np.int32)
        served[:n] = s['tokens'][:n]
        at[:n] = len(s['prompt']) - 1 + np.arange(n)
        not_best += int(np.sum(rows[np.arange(n), served[:n]]
                               < np.max(rows[:n], axis=-1)))
        held.append({'x': ref.embed(ends, jnp.asarray(tokens)[None], shape),
                     'n': n, 'at': at, 'rows': rows, 'served': served})
    if not held:
        return {'rows': 0, 'logit_err_energy_median': math.inf,
                'logit_err_energy_p90': math.inf,
                'rows_beyond_bound_share': 1.0, 'rows_not_finite': 0,
                'rows_off': rows_off, 'tokens_not_best': not_best}
    for l in range(int(shape['num_hidden_layers'])):
        lp = ref.init_layer(shape, key, l)
        for h in held:
            h['x'] = layer(lp, h['x'], shape['layer_types'][l])
        del lp
    energies, gaps, not_finite = [], [], 0
    for h in held:
        energy, gap, finite = (np.asarray(v)[:h['n']] for v in compare(
            ends, h['x'], jnp.asarray(h['at']), jnp.asarray(h['rows']),
            jnp.asarray(h['served'])))
        not_finite += int(np.sum(~finite))
        energies.append(energy)
        gaps.append(gap)
        h['energy'] = energy
    energy = np.concatenate(energies)
    out = {'rows': int(energy.size),
           'logit_err_energy_median': float(np.median(energy)),
           'logit_err_energy_p90': float(np.quantile(energy, 0.9)),
           'rows_beyond_bound_share': float(np.mean(energy > bound)),
           'logit_err_energy_mean': float(np.mean(energy)),
           'logit_err_energy_max': float(np.max(energy)),
           'token_gap_max_sigma': float(np.max(np.concatenate(gaps))),
           'rows_not_finite': not_finite, 'rows_off': rows_off,
           'tokens_not_best': not_best}
    for q in QUANTILES:
        out[f'energy_p{q}'] = float(np.quantile(energy, q / 100.0))
    for x in BOUNDS:
        out[f'rows_beyond_{x:g}_share'] = float(np.mean(energy > x))
    # each request: its prompt's rows, its served rows, their median energy,
    # how many lie beyond the bound, and the same of its rows past the window
    window = int(shape['sliding_window'])
    out['by_request'] = [
        [int(h['at'][0]) + 1, h['n'], float(np.median(h['energy'])),
         int(np.sum(h['energy'] > bound))] for h in held]
    deep = np.concatenate([h['energy'][h['at'][:h['n']] >= window]
                           for h in held])
    out['rows_past_window'] = int(deep.size)
    if deep.size:
        out['energy_median_past_window'] = float(np.median(deep))
    return out
