"""Serve a decoder of compressed convolutional attention and top-1 routed
experts (models/zaya.py) through the program's ``serving.GenerationEngine``,
as a client of the engine and nothing more. The client, the closed loop and
the window's reduction are ``runners/serve_gpt.py``'s, the control's
rounding and the routed layers' counters ``runners/serve_latent_moe.py``'s
and the list of the prefills that ran whole inside the traced stretch
``runners/serve_afmoe.py``'s: loaded from those files and not copied. What
is this runner's own:

 - the family's configuration from the file's published keys (the rotary's
   base out of ``rope_parameters``), and the weights in the layout the
   program scans (every leaf stacked over the layers), made from the
   reference's own leaves a layer at a time so that two copies of the model
   never stand side by side;
 - which requests are sampled (``on_open``): a count fixed by the window's
   requests, every ``LOGITS_EVERY``-th of them and none for being the
   longest: 9-10 of ~75. A request that flips an expert early reads high
   for the rest of its rows, so the statistics want many requests; a served
   row is 262,272 logits, 1.05 MB in float32, so they cannot have all (ten
   answers of ~1,000 tokens are 10 GB of the host's memory);
 - the rows that skipped the experts (``moe.rows_skipped_total``) beside
   the routed layers' counters, and the engine's bytes of tails and of
   pages held, read at the window's two ends;
 - the comparison's driver: the reference goes ONE LAYER AT A TIME over all
   the sampled requests, the router's state of each beside its
   activations, each request in a block of its own padded to a power of two
   of rows;
 - the router run again: a sampled request's rows come with the program's
   own notes of what each layer's router was given and what it answered
   (``GenerationFuture.row_notes``, models/zaya.py ``NOTE``), and the
   reference's router of that layer runs on them (``ref.router_again``),
   which holds the router to its stated float32 whatever the residual
   stream rounds to.

Rows are bimodal with random weights, and more of them than in the other
routed cells lie in the upper mode: a near-tie between a row's first and
second choice flips under bfloat16 hidden states though the router runs in
float32, one expert a token means a flip moves the whole half, and the
router's state carries a flipped layer's difference down the stack. What is
compared is the median and a quantile of the rows' error energies and the
share of rows beyond a bound; of the router run again, the state's and the
weight's relative difference and the share of choices that differ (the
configuration's file says which, and why each limit)."""
import gc
import math
import time

import numpy as np

from benchmark.harness import context as _ctx
from benchmark.harness import device as _device
from benchmark.harness import manifest as _manifest
from benchmark.harness.tracing import TailTrace

LOGITS_EVERY = 8        # the window's first request and every 8th after it
MIN_BLOCK = 256         # a request's block of rows, at least
MODEL_KEYS = (
    'vocab_size', 'hidden_size', 'moe_intermediate_size',
    'num_hidden_layers', 'num_attention_heads', 'num_key_value_heads',
    'head_dim', 'cca_time0', 'cca_time1', 'num_experts',
    'num_experts_per_tok', 'router_hidden_size', 'partial_rotary_factor',
    'rms_norm_eps', 'max_position_embeddings')
QUANTILES = (10, 75, 90, 95, 99)     # reported beside what is compared
BOUNDS = (1e-3, 3e-3, 1e-2, 3e-2)
ROUTER_BLOCK = 2048     # rows of one call of the router run again


def model_shape(config):
    """The reference's ``shape``: the file's published keys as run, the
    experts held counted by ``num_experts`` and placed by ``held``."""
    shape = {k: config[k] for k in MODEL_KEYS}
    first, count = config['held']['experts']
    if count != shape['num_experts']:
        raise ValueError('held.experts and num_experts disagree')
    shape.update(
        rope_theta=config['rope_parameters']['hybrid']['rope_theta'],
        held_first=first, router_width=config['held']['router_width'],
        max_seq_len=shape['max_position_embeddings'])
    return shape


def program_config(shape, program):
    from paddle_tpu.models import zaya
    own = {k: v for k, v in shape.items()
           if k in zaya.ZayaConfig.__dataclass_fields__}
    own.update(num_experts=shape['router_width'],
               held=(shape['held_first'], shape['num_experts']))
    return zaya.ZayaConfig(**own, **program)


def program_params(ref, shape, cfg, key):
    """The reference's weights as the program scans them."""
    from paddle_tpu.models import zaya
    ends = ref.init_ends(shape, key)
    return {'embed': ends['embed'], 'norm_f': ends['norm_f'],
            'layers': zaya.stack_layers(
                cfg, lambda l: ref.init_layer(shape, key, l))}


def facts_shape(shape):
    """``shape`` as the accepted readers of the paged kernel, of the
    prefill's flash forward and of the grouped product read one: every
    layer attends everything, which those readers count under
    'full_attention', and no layer has a window."""
    return dict(shape, sliding_window=None,
                layer_types=['full_attention'] * shape['num_hidden_layers'])


def _served_notes(client, sample):
    """What the program's router noted of each served row of ``sample``
    (``base._served_sample``'s requests, in its order): a request's
    ``[rows, layers * note]`` float32."""
    futs = [rec['fut'] for k, rec in enumerate(client.sent)
            if rec['in_window'] and rec['want'] and client.done.get(k)
            and client.done[k][1] is None]
    assert len(futs) == len(sample)
    return [np.stack([n for n in f.row_notes() if n is not None]
                     or [np.zeros((0,), np.float32)]) for f in futs]


def _skipped():
    """{phase: rows that skipped the experts so far}; None where the
    program has no such counter yet."""
    from paddle_tpu import observability
    out = {}
    for phase in ('prefill', 'decode'):
        got = observability.find('moe.rows_skipped_total', {'phase': phase})
        out[phase] = got.value if got is not None else None
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
        marks['moe' + at], marks['skip' + at] = (latent._moe_counts(),
                                                 _skipped())
        marks['compiles' + at] = ctx.compiles.total()

    def on_open(t0):
        mark('0')
        # the sample: the window's first request and every LOGITS_EVERY-th
        # after it, so its size follows the window's requests and not which
        # of them set a record of length (the base client's other rule).
        # What it costs the timed path: the engine gathers the asking
        # slots' rows and the router's notes on them on the device (one
        # executable, ~6 of 48 a step: 8 x 709 KB read) and the host reads
        # them under the next step, as they were computed (widened after
        # the window, by ``logits()``); the cell with no request asking
        # read 1,878.4 against 1,876.8 on the same seed, +0.09 % (PERF.md
        # section 6, PR 40)
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
    a, b = marks['0'], marks['1']
    moe = latent._moe_window(marks['moe0'], marks['moe1'], {
        'prefill': b['prefills'] - a['prefills'],
        'decode': b['steps'] - a['steps']})
    ctx.log('window', drain_s=drained - t1, moe=moe, **window['log'])

    facts = dict(window['facts'], shape=facts_shape(shape), chips=chips,
                 page_rows=engine.page_size, span_names=list(base.SPANS),
                 trace=loaded, moe_window=moe,
                 prefill_rows_in_trace=prefills)
    if moe is not None:
        for k in latent.MOE_COUNTERS:
            facts[f'moe_{k}'] = sum(moe[p][k] for p in moe)
    if all(v is not None for v in marks['skip1'].values()):
        facts['moe_rows_skipped'] = sum(
            v - (marks['skip0'][p] or 0) for p, v in marks['skip1'].items())
    # what the busy slots held at the window's two ends, in bytes
    held = {k: (a.get(k), b.get(k)) for k in ('state_bytes', 'page_bytes')}
    if all(v is not None for pair in held.values() for v in pair):
        facts['state_bytes_held'] = sum(held['state_bytes']) / 2
        facts['state_and_page_bytes_held'] = sum(
            held['state_bytes'] + held['page_bytes']) / 2
    ctx.log('state', prefills_in_trace=prefills, **{k: facts.get(k) for k in (
        'moe_rows_skipped', 'moe_rows_offered', 'state_bytes_held',
        'state_and_page_bytes_held')})
    result = {'device': _device.info(ctx.devices),
              'end_to_end': dict(window['end_to_end'], setup_s=setup_s),
              'facts': facts}
    sample = base._served_sample(client)
    for s, notes in zip(sample, _served_notes(client, sample)):
        s['notes'] = notes
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
              for name in limits if name in readings]
    unheard = sum(1 for r in client.sent
                  if r['in_window'] and r['want']) - len(sample)
    checks += [
        _ctx.check('tokens_not_their_rows_best',
                   readings['tokens_not_best'], 0, True),
        _ctx.check('rows_not_finite', readings['rows_not_finite'], 0, True),
        _ctx.check('rows_not_one_a_token', readings['rows_off'], 0, True),
        _ctx.check('sampled_requests_unserved', unheard, 0, True),
        _ctx.check('no_row_compared', int(readings['rows'] == 0), 0, True),
        _ctx.check('rows_without_router_note',
                   readings['rows_without_router_note'], 0, True),
        _ctx.check('compiles_in_window', facts['compiles_in_window'], 0,
                   True)]
    # no request is counted as failed for its rows' energies: the rows are
    # held in aggregate, by ``correct`` (runners/serve_latent_moe.py)
    ctx.log('reference', seconds=time.perf_counter() - t,
            requests=len(sample), bytes_in_use_before=in_use, **readings)
    result.update(correct=all(c['ok'] for c in checks), checks=checks,
                  attempted=window['attempted'], failed=window['failed'])
    return result


def _noted_rows(sample, shape):
    """The program's router notes of every served row of every sampled
    request, one array a field of ``models/zaya.py``'s ``NOTE``: -> ({'input'
    [N, layers, H], 'state' [N, layers, R], 'probability', 'choice' [N,
    layers]} padded to whole blocks of ``ROUTER_BLOCK`` rows with 'rows' N,
    or None; the rows that came without a note)."""
    h, r = int(shape['hidden_size']), int(shape['router_hidden_size'])
    layers = int(shape['num_hidden_layers'])
    have = [s['notes'] for s in sample
            if s['notes'].shape[1:] == (layers * (h + r + 2),)]
    unnoted = sum(len(s['tokens']) for s in sample) - sum(
        len(n) for n in have)
    if not have:
        return None, unnoted
    notes = np.concatenate(have).reshape(-1, layers, h + r + 2)
    n = len(notes)
    pad = -n % ROUTER_BLOCK
    notes = np.concatenate([notes, np.repeat(notes[:1], pad, axis=0)])
    return {'input': notes[..., :h], 'state': notes[..., h:h + r],
            'probability': notes[..., h + r],
            'choice': notes[..., h + r + 1].astype(np.int32),
            'rows': n}, unnoted


def _router_again(again, rp, noted, l):
    """Layer ``l``'s router of the reference on the program's noted rows,
    a block at a time. -> ``ref.router_again``'s readings over the rows."""
    out = {'state': [], 'weight': [], 'choice': []}
    for lo in range(0, len(noted['choice']), ROUTER_BLOCK):
        rows = slice(lo, lo + ROUTER_BLOCK)
        above = (noted['state'][rows, l - 1] if l else
                 np.zeros_like(noted['state'][rows, 0]))
        got = again(rp, {'input': noted['input'][rows, l], 'above': above,
                         **{k: noted[k][rows, l] for k in (
                             'state', 'probability', 'choice')}})
        for k in out:
            out[k].append(np.asarray(got[k]))
    return {k: np.concatenate(v)[:noted['rows']] for k, v in out.items()}


def _block_rows(n):
    return max(MIN_BLOCK, 1 << (int(n) - 1).bit_length())


def hold_to_reference(ref, shape, key, sample, bound):
    """Every served row against the reference's row.

    The reference makes its own weights from the seed, a layer at a time,
    and runs its float32 'highest' layer over each sampled request's prompt
    and served tokens (teacher-forced), the router's state of the layer
    above beside the activations. A row's distance is ||served -
    reference|| / ||reference||; what is compared is its square, the
    error's energy over the row's (benchmark/runners/serve_gpt.py says
    why). Every served token is also held, exactly, to the served row it
    was chosen from.

    THE ROUTER AGAIN. The rows' energies cannot tell a router computed in
    bfloat16 from one in float32: the bfloat16 residual stream already
    flips a near-tie in a quarter of the rows, and a bfloat16 router adds
    half as many again (PERF.md section 2, PR 40). So the program notes,
    for every served row and layer, what its router was GIVEN (the normed
    row, the state of the layer above) and what it ANSWERED (its state,
    the choice, the chosen one's probability), and the reference's router
    runs again on those rows (``ref.router_again``): the stream's rounding
    is in both, and what is left is the router's own arithmetic, float32
    against float32 'highest'. Compared: the 99th percentiles of the
    state's and of the weight's relative difference over every (row,
    layer), and the share of them whose choice is not the reference's.

    -> {'rows', 'logit_err_energy_median', 'logit_err_energy_p25',
        'rows_beyond_bound_share', 'router_state_err_p99',
        'router_weight_err_p99', 'router_choice_off_share', and what is
        reported beside them}"""
    import jax
    import jax.numpy as jnp
    context = shape['max_seq_len']
    layer = jax.jit(lambda lp, x, r: ref.layer(lp, x, r, shape),
                    donate_argnums=(1, 2))

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

    again = jax.jit(lambda rp, noted: ref.router_again(rp, noted, shape))
    ends = ref.init_ends(shape, key)
    held, rows_off, not_best = [], 0, 0
    noted, unnoted = _noted_rows(sample, shape)
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
        best = np.asarray([float(np.max(r)) for r in s['rows'][:n]])
        took = np.asarray([float(r[t]) for r, t in zip(s['rows'][:n],
                                                       served)])
        not_best += int(np.sum(took < best))
        x = ref.embed(ends, jnp.asarray(tokens)[None], shape)
        held.append({'x': x, 'r': ref.router_start(x, shape), 'n': n,
                     'prompt': len(s['prompt']), 'rows': s['rows'],
                     'served': served})
    if not held:
        return {'rows': 0, 'logit_err_energy_median': math.inf,
                'logit_err_energy_p25': math.inf,
                'rows_beyond_bound_share': 1.0, 'rows_not_finite': 0,
                'rows_off': rows_off, 'tokens_not_best': not_best,
                'rows_without_router_note': unnoted}
    routers = []
    for l in range(int(shape['num_hidden_layers'])):
        lp = ref.init_layer(shape, key, l)
        for h in held:
            h['x'], h['r'] = layer(lp, h['x'], h['r'])
        if noted is not None:
            routers.append(_router_again(again, lp['router'], noted, l))
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
            rows = np.zeros((MIN_BLOCK, shape['vocab_size']), np.float32)
            rows[:m] = np.stack(h['rows'][lo:lo + m])
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
        h['rows'] = None
        energies.append(h['energy'])
    energy = np.concatenate(energies)
    out = {'rows': int(energy.size),
           'logit_err_energy_median': float(np.median(energy)),
           'logit_err_energy_p25': float(np.quantile(energy, 0.25)),
           'rows_beyond_bound_share': float(np.mean(energy > bound)),
           'logit_err_energy_mean': float(np.mean(energy)),
           'logit_err_energy_max': float(np.max(energy)),
           'token_gap_max_sigma': float(np.max(np.concatenate(gaps))),
           'rows_not_finite': not_finite, 'rows_off': rows_off,
           'tokens_not_best': not_best, 'rows_without_router_note': unnoted}
    if routers:
        for name in ('state', 'weight'):
            err = np.concatenate([r[name] for r in routers])
            err = np.where(np.isfinite(err), err, np.inf)
            out[f'router_{name}_err_p99'] = float(np.quantile(err, 0.99))
            out[f'router_{name}_err_median'] = float(np.median(err))
            out[f'router_{name}_err_max'] = float(np.max(err))
        off = np.stack([r['choice'] for r in routers])       # [layers, N]
        out['router_choice_off_share'] = float(np.mean(off))
        out['router_choice_off_by_layer_max'] = float(np.max(np.mean(
            off, axis=1)))
        out['router_rows_again'] = int(off.size)
    for q in QUANTILES:
        out[f'energy_p{q}'] = float(np.quantile(energy, q / 100.0))
    for x in BOUNDS:
        out[f'rows_beyond_{x:g}_share'] = float(np.mean(energy > x))
    # each request: its prompt's rows, its served rows, their median energy
    # and how many lie beyond the bound; the prefill's rows beside the
    # decoded ones, and a request's later half beside its earlier one
    out['by_request'] = [
        [h['prompt'], h['n'], float(np.median(h['energy'])),
         int(np.sum(h['energy'] > bound))] for h in held]
    out['energy_median_prefill_rows'] = float(np.median(
        [h['energy'][0] for h in held]))
    out['energy_median_later_half'] = float(np.median(np.concatenate(
        [h['energy'][h['n'] // 2:] for h in held])))
    return out
