"""Serve a latent-attention, routed-expert decoder (models/latent_moe.py)
through the program's ``serving.GenerationEngine``, as a client of the
engine and nothing more. The client, the two loops and the window's
reduction are ``runners/serve_gpt.py``'s, loaded from that file and not
copied; what is this runner's own:

 - the family's configuration from the file's published keys, the chip's
   share beside them (``held``), weights in bfloat16 made leaf by leaf;
 - the lower-precision control: the same engine on weights rounded to int8
   a channel and widened again (done here, so the program needs no
   quantised path for this family);
 - the routed layers' counters (``moe.*``) read at the window's ends;
 - the comparison's driver: the reference goes ONE LAYER AT A TIME over all
   the sampled requests (at these widths a layer's float32 block is 3.75 GB
   and the whole model's 18 GB), each request in a block of its own padded
   to a power of two of rows.

Rows are bimodal with random weights: a near-tie between a token's eighth
and ninth expert flips under bfloat16 hidden states though the router runs
in float32, and a flipped held expert moves that row far more than rounding
does. So no maximum is compared: the statistics are the median and a
quantile of the rows' error energies and the share of rows beyond a bound
(the configuration's file says which, and why each limit)."""
import gc
import math
import time

import numpy as np

from benchmark.harness import context as _ctx
from benchmark.harness import device as _device
from benchmark.harness import manifest as _manifest
from benchmark.harness.tracing import TailTrace

LOGITS_EVERY = 16       # every sixteenth request of the window, and the
MIN_BLOCK = 256         # longest; a request's block of rows, at least
MODEL_KEYS = (
    'vocab_size', 'hidden_size', 'intermediate_size',
    'moe_intermediate_size', 'num_hidden_layers', 'first_k_dense_replace',
    'num_attention_heads', 'q_lora_rank', 'kv_lora_rank',
    'qk_nope_head_dim', 'qk_rope_head_dim', 'v_head_dim', 'n_routed_experts',
    'n_shared_experts', 'num_experts_per_tok', 'n_group', 'topk_group',
    'routed_scaling_factor', 'norm_topk_prob', 'rms_norm_eps', 'rope_theta',
    'rope_scaling', 'max_position_embeddings')
MOE_COUNTERS = ('rows_offered', 'rows_held', 'expert_calls',
                'experts_touched')
QUANTILES = (75, 95, 99)         # reported beside the median and p90
BOUNDS = (1e-3, 3e-3, 1e-2, 3e-2)    # reported shares of rows beyond each


def model_shape(config):
    """The reference's ``shape``: the file's published keys as run, the
    experts held counted by ``n_routed_experts`` and placed by ``held``."""
    shape = {k: config[k] for k in MODEL_KEYS}
    first, count = config['held']['experts']
    if count != shape['n_routed_experts']:
        raise ValueError('held.experts and n_routed_experts disagree')
    shape.update(held_first=first,
                 router_width=config['held']['router_width'],
                 max_seq_len=shape['max_position_embeddings'])
    return shape


def program_config(shape, program):
    from paddle_tpu.models import latent_moe
    own = {k: v for k, v in shape.items()
           if k in latent_moe.LatentMoEConfig.__dataclass_fields__}
    own.update(n_routed_experts=shape['router_width'],
               held=(shape['held_first'], shape['n_routed_experts']))
    return latent_moe.LatentMoEConfig(**own, **program)


def round_to_int8(params):
    """The control's weights: every matrix rounded to int8 with one scale an
    output channel (an embedding row) and widened to its own type again,
    leaf by leaf. Norm gains and the float32 router are left as they are."""
    import jax
    import jax.numpy as jnp

    def fake(axis):
        def one(w):
            w32 = w.astype(jnp.float32)
            scale = jnp.max(jnp.abs(w32), axis=axis, keepdims=True) / 127.0
            q = jnp.clip(jnp.round(w32 / jnp.maximum(scale, 1e-30)),
                         -127, 127)
            return (q * scale).astype(w.dtype)
        return jax.jit(one, donate_argnums=(0,))
    by_channel, by_row = fake(-2), fake(-1)

    def walk(node, name=''):
        if isinstance(node, dict):
            return {k: walk(v, k) for k, v in node.items()}
        if isinstance(node, list):
            return [walk(v, name) for v in node]
        if node.ndim < 2 or node.dtype == jnp.float32:
            return node
        return by_row(node) if name == 'embed' else by_channel(node)
    return walk(params)


def _moe_counts():
    """{phase: {counter: value}} of the routed layers' counters so far."""
    from paddle_tpu import observability
    out = {}
    for phase in ('prefill', 'decode'):
        out[phase] = {}
        for name in MOE_COUNTERS:
            got = observability.find(f'moe.{name}_total', {'phase': phase})
            out[phase][name] = got.value if got is not None else None
    return out


def _moe_window(a, b, runs):
    """The window's counts by phase beside how often the phase ran, or None
    where the program counts nothing."""
    out = {}
    for phase in a:
        if any(v is None for v in b[phase].values()):
            return None
        out[phase] = {k: b[phase][k] - (a[phase][k] or 0) for k in b[phase]}
        out[phase]['runs'] = runs[phase]
    return out


def run(ctx):
    import jax

    from paddle_tpu import observability, warmup
    from paddle_tpu.serving import (EngineClosedError, GenerationEngine,
                                    QueueFullError)

    if ctx.seconds <= 0:
        raise ValueError('a served cell needs a window: --seconds > 0')
    base = _manifest.load_module('runners', 'serve_gpt')
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
        params = round_to_int8(params)
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
    warm.result(timeout=600)
    ctx.log('setup', done='warmup', prebuilt=report.get('prebuilt'),
            compile_requests=dict(ctx.compiles.requests))

    traffic = gen.make(tp, ctx.seed, shape['vocab_size'],
                       shape['max_seq_len'], ctx.seconds)
    client = base._Client(engine, traffic, ctx.seed % LOGITS_EVERY,
                          (QueueFullError, EngineClosedError))
    marks = {}
    tracer = TailTrace(ctx.out_dir, ctx.traffic.get(
        'trace_seconds', 4.0)) if ctx.trace else None

    def on_open(t0):
        marks['open'], marks['moe0'] = engine.stats(), _moe_counts()
        marks['compiles0'] = ctx.compiles.total()
        if tracer:
            tracer.arm(t0 + ctx.seconds)
        ctx.log('setup', done='lead_in', active_slots=marks['open'][
            'active_slots'], queue_depth=marks['open']['queue_depth'])

    def on_close():
        marks['close'], marks['moe1'] = engine.stats(), _moe_counts()
        marks['compiles1'] = ctx.compiles.total()

    try:
        if traffic['loop'] == 'open':
            t0, t1 = base._open_loop(
                client, time.perf_counter() + tp['lead_in_s'] + 0.05,
                ctx.seconds, on_open, on_close)
        else:
            t0, t1 = base._closed_loop(client, tp, ctx.seconds, on_open,
                                       on_close)
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
        window = base._reduce_window(client, t0, t1, chips, marks,
                                     engine.num_slots,
                                     observability.recorder(), tracer)
    finally:
        engine.shutdown(drain=False)
    a, b = marks['open'], marks['close']
    moe = _moe_window(marks['moe0'], marks['moe1'], {
        'prefill': b['prefills'] - a['prefills'],
        'decode': b['steps'] - a['steps']})
    ctx.log('window', drain_s=drained - t1, moe=moe, **window['log'])

    facts = dict(window['facts'], shape=shape, chips=chips,
                 page_rows=engine.page_size, span_names=list(base.SPANS),
                 trace=loaded, moe_window=moe)
    if moe is not None:
        for k in MOE_COUNTERS:
            facts[f'moe_{k}'] = sum(moe[p][k] for p in moe)
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
    readings, _ = hold_to_reference(
        ref, shape, key, sample, limits['row_energy_bound'])
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
    # no request is counted as failed for its rows' energies: rows are
    # bimodal here (a flipped expert) and requests of few rows read up to
    # five times the median of all (22 rows: 6.9e-4 against 1.4e-4, my chip
    # runs, PR 27), so the rows are held in aggregate, by ``correct``
    ctx.log('reference', seconds=time.perf_counter() - t,
            requests=len(sample), bytes_in_use_before=in_use, **readings)
    result.update(correct=all(c['ok'] for c in checks), checks=checks,
                  attempted=window['attempted'],
                  failed=window['failed'])
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

    -> ({'rows', 'logit_err_energy_median', 'logit_err_energy_p90',
         'rows_beyond_bound_share', and what is reported beside them},
        [each request's median energy])"""
    import jax
    import jax.numpy as jnp
    context = shape['max_seq_len']
    layer = jax.jit(lambda lp, x: ref.layer(lp, x, shape), donate_argnums=1)

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
        held.append({'x': ref.embed(ends, jnp.asarray(tokens)[None]),
                     'n': n, 'at': at, 'rows': rows, 'served': served})
    if not held:
        return ({'rows': 0, 'logit_err_energy_median': math.inf,
                 'logit_err_energy_p90': math.inf,
                 'rows_beyond_bound_share': 1.0, 'rows_not_finite': 0,
                 'rows_off': rows_off, 'tokens_not_best': not_best}, [])
    for l in range(int(shape['num_hidden_layers'])):
        lp = ref.init_layer(shape, key, l)
        for h in held:
            h['x'] = layer(lp, h['x'])
        del lp
    energies, gaps, medians, not_finite = [], [], [], 0
    for h in held:
        energy, gap, finite = (np.asarray(v)[:h['n']] for v in compare(
            ends, h['x'], jnp.asarray(h['at']), jnp.asarray(h['rows']),
            jnp.asarray(h['served'])))
        not_finite += int(np.sum(~finite))
        energies.append(energy)
        gaps.append(gap)
        medians.append(float(np.median(energy)))
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
    # each request: its prompt's rows, its served rows, their median energy
    # and how many lie beyond the bound
    out['by_request'] = [
        [int(h['at'][0]) + 1, h['n'], float(np.median(h['energy'])),
         int(np.sum(h['energy'] > bound))] for h in held]
    return out, medians
