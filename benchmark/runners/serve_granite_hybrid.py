"""Serve a decoder of state-space layers with attention layers between them
(models/granite_hybrid.py) through the program's
``serving.GenerationEngine``, as a client of the engine and nothing more.
The client, the closed loop and the window's reduction are
``runners/serve_gpt.py``'s, the control's rounding is
``runners/serve_latent_moe.py``'s and the list of the prefills that ran
whole inside the traced stretch ``runners/serve_afmoe.py``'s: loaded from
those files and not copied. What is this runner's own:

 - the family's configuration from the file's published keys, and the
   weights in the layout the program scans (a period's position at a time,
   stacked over the periods), made from the reference's own leaves so that
   two copies of the model never stand side by side;
 - which requests are sampled (``on_open``): a count fixed by the window's
   requests, not by which of them set a record of length;
 - the state's counters (``ssm.*``) and the engine's bytes of state and of
   pages held, read at the window's two ends;
 - the comparison's driver: the reference goes ONE LAYER AT A TIME over all
   the sampled requests, each in a block of its own padded to a power of
   two of rows, its recurrence a token at a time.

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

LOGITS_EVERY = 16       # the window's first request and every 16th after it
MIN_BLOCK = 256         # a request's block of rows, at least
MODEL_KEYS = (
    'vocab_size', 'hidden_size', 'shared_intermediate_size',
    'num_hidden_layers', 'layer_types', 'num_attention_heads',
    'num_key_value_heads', 'mamba_n_heads', 'mamba_d_head', 'mamba_d_state',
    'mamba_d_conv', 'mamba_expand', 'mamba_n_groups', 'mamba_chunk_size',
    'attention_multiplier', 'embedding_multiplier', 'residual_multiplier',
    'logits_scaling', 'rms_norm_eps', 'max_position_embeddings')
SSM_COUNTERS = (('state_rows', 'prefill'), ('state_rows', 'decode'),
                ('scan_chunks', 'prefill'))
QUANTILES = (75, 90, 95)         # reported beside what is compared


def model_shape(config):
    """The reference's ``shape``: the file's published keys as run."""
    shape = {k: config[k] for k in MODEL_KEYS}
    shape['max_seq_len'] = shape['max_position_embeddings']
    return shape


def program_config(shape, program):
    from paddle_tpu.models import granite_hybrid
    own = {k: v for k, v in shape.items()
           if k in granite_hybrid.GraniteHybridConfig.__dataclass_fields__}
    return granite_hybrid.GraniteHybridConfig(**own, **program)


def program_params(ref, shape, cfg, key):
    """The reference's weights as the program scans them."""
    from paddle_tpu.models import granite_hybrid
    ends = ref.init_ends(shape, key)
    return {'embed': ends['embed'], 'norm_f': ends['norm_f'],
            'periods': granite_hybrid.stack_periods(
                cfg, lambda l: ref.init_layer(shape, key, l))}


def facts_shape(shape):
    """``shape`` as the accepted readers of the paged kernel and of the
    prefill's flash forward read one: this family's attention layers attend
    everything, which those readers count under 'full_attention', and no
    layer has a window."""
    return dict(shape, sliding_window=None,
                head_dim=shape['hidden_size'] // shape['num_attention_heads'],
                layer_types=['full_attention' if t == 'attention' else t
                             for t in shape['layer_types']])


def _ssm_counts():
    """{'state_rows_prefill': ..} of the state's counters so far; None
    where the program has no such counter yet."""
    from paddle_tpu import observability
    out = {}
    for name, phase in SSM_COUNTERS:
        got = observability.find(f'ssm.{name}_total', {'phase': phase})
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
        marks['ssm' + at] = _ssm_counts()
        marks['compiles' + at] = ctx.compiles.total()

    def on_open(t0):
        mark('0')
        # the sample: the window's first request and every LOGITS_EVERY-th
        # after it, so its size follows the window's requests (9-10 here);
        # none for being the longest so far (the base client's other rule:
        # 3-9 more a seed, each decoding to the window's end). Sampling
        # costs the timed path: a step in which any slot wants its logits
        # reads all slots' rows (12.8 MB here) and widens the wanted ones,
        # ~5 ms of a 39 ms step (PERF.md section 6, PR 34)
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

    facts = dict(window['facts'], shape=facts_shape(shape), chips=chips,
                 page_rows=engine.page_size, span_names=list(base.SPANS),
                 trace=loaded, prefill_rows_in_trace=prefills)
    for k, v in marks['ssm1'].items():
        if v is not None:
            facts[f'ssm_{k}'] = v - (marks['ssm0'][k] or 0)
    # what the busy slots held at the window's two ends, in bytes
    held = {k: (marks['0'].get(k), marks['1'].get(k))
            for k in ('state_bytes', 'page_bytes')}
    if all(v is not None for pair in held.values() for v in pair):
        facts['state_bytes_held'] = sum(held['state_bytes']) / 2
        facts['state_and_page_bytes_held'] = sum(
            held['state_bytes'] + held['page_bytes']) / 2
    ctx.log('state', prefills_in_trace=prefills, **{k: facts.get(k) for k in (
        'ssm_state_rows_prefill', 'ssm_state_rows_decode',
        'ssm_scan_chunks_prefill', 'state_bytes_held',
        'state_and_page_bytes_held')})
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
    readings = hold_to_reference(ref, shape, key, sample)
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


def _block_rows(n):
    return max(MIN_BLOCK, 1 << (int(n) - 1).bit_length())


def hold_to_reference(ref, shape, key, sample):
    """Every served row against the reference's row.

    The reference makes its own weights from the seed, a layer at a time,
    and runs its float32 'highest' layer (its recurrence a token at a time)
    over each sampled request's prompt and served tokens (teacher-forced).
    A row's distance is ||served - reference|| / ||reference||; what is
    compared is its square, the error's energy over the row's
    (benchmark/runners/serve_gpt.py says why). Every served token is also
    held, exactly, to the served row it was chosen from.

    -> {'rows', 'logit_err_energy_median', 'logit_err_energy_p99',
        'logit_err_energy_max', and what is reported beside them}"""
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
        served = np.asarray(s['tokens'][:n], np.int32)
        rows = np.stack(s['rows'][:n])
        not_best += int(np.sum(rows[np.arange(n), served]
                               < np.max(rows, axis=-1)))
        held.append({'x': ref.embed(ends, jnp.asarray(tokens)[None], shape),
                     'n': n, 'prompt': len(s['prompt']), 'rows': rows,
                     'served': served})
    if not held:
        return {'rows': 0, 'logit_err_energy_median': math.inf,
                'logit_err_energy_p99': math.inf,
                'logit_err_energy_max': math.inf, 'rows_not_finite': 0,
                'rows_off': rows_off, 'tokens_not_best': not_best}
    for l in range(int(shape['num_hidden_layers'])):
        lp = ref.init_layer(shape, key, l)
        for h in held:
            h['x'] = layer(lp, h['x'], shape['layer_types'][l])
        del lp
    energies, gaps, not_finite = [], [], 0
    for h in held:
        # rows, their places and their tokens in a block too (padded, so
        # that one comparison compiles a block size and not a request), a
        # block of at most MIN_BLOCK rows at a time: a row is the whole
        # vocabulary wide
        n, h['energy'] = h['n'], []
        for lo in range(0, n, MIN_BLOCK):
            m = min(MIN_BLOCK, n - lo)
            rows = np.zeros((MIN_BLOCK,) + h['rows'].shape[1:], np.float32)
            rows[:m] = h['rows'][lo:lo + m]
            served = np.zeros((MIN_BLOCK,), np.int32)
            served[:m] = h['served'][lo:lo + m]
            at = np.zeros((MIN_BLOCK,), np.int32)
            at[:m] = h['prompt'] - 1 + lo + np.arange(m)
            energy, gap, finite = (np.asarray(v)[:m] for v in compare(
                ends, h['x'], jnp.asarray(at), jnp.asarray(rows),
                jnp.asarray(served)))
            not_finite += int(np.sum(~finite))
            h['energy'].append(energy)
            gaps.append(gap)
        h['energy'] = np.concatenate(h['energy'])
        energies.append(h['energy'])
    energy = np.concatenate(energies)
    out = {'rows': int(energy.size),
           'logit_err_energy_median': float(np.median(energy)),
           'logit_err_energy_p99': float(np.quantile(energy, 0.99)),
           'logit_err_energy_max': float(np.max(energy)),
           'logit_err_energy_mean': float(np.mean(energy)),
           'token_gap_max_sigma': float(np.max(np.concatenate(gaps))),
           'rows_not_finite': not_finite, 'rows_off': rows_off,
           'tokens_not_best': not_best}
    for q in QUANTILES:
        out[f'energy_p{q}'] = float(np.quantile(energy, q / 100.0))
    # each request: its prompt's rows, its served rows, their median energy
    # and their largest; and the prefill's rows beside the decoded ones
    out['by_request'] = [
        [h['prompt'], h['n'], float(np.median(h['energy'])),
         float(np.max(h['energy']))] for h in held]
    out['energy_median_prefill_rows'] = float(np.median(
        [h['energy'][0] for h in held]))
    deep = np.concatenate([h['energy'][h['n'] // 2:] for h in held])
    out['energy_median_later_half'] = float(np.median(deep))
    return out
