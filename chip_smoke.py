"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py              one TPU chip: kernels, train, serve, fence
    python chip_smoke.py --chips 4    one four-chip host: ONLY the mesh paths
                                      (dp2 x mp2 train step, mp=4 engine) and
                                      the one-device runs they are compared with

One process, no child that touches jax (a chip belongs to one process).
The run fails — non-zero exit, last line ``{"ok": false, ...}`` — the moment
jax's first device is not a TPU, and whenever a phase's assertion fails:
nothing is caught on the way to exit 0. On success the last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

and every earlier line is one JSON object per phase. The ``smoke_seconds_*``
fields are host wall-clock notes on this run (compilation included), NOT
measurements: no speed claim is made from them.

Phases (default run, in order; each one asserts, see the functions):
  kernels  tools/tpu_kernel_check.run_battery(): every Pallas kernel vs its
           jax.numpy reference, compiled by Mosaic (never interpreted)
  train    the 337M GPT (hidden 1024, 24 layers, 16 heads, vocab 32768, seq
           1024, batch 8, bf16, flash, remat 'dots') through fleet.init ->
           init_params -> place_params -> make_train_step, fed by the native
           LMTokenLoader from a seeded token stream
  serve    serving.GenerationEngine(params, cfg, num_slots=8) on the same
           model: warmup, 8 seeded requests (prompts of 16..768 tokens, 32
           new tokens each), two of them submitted while others decode
  fence    whether block_until_ready waits for the device on this platform
"""
import argparse
import collections
import dataclasses
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, 'tools'))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

COLLECTIVES = ('all-reduce', 'all-gather', 'reduce-scatter', 'all-to-all',
               'collective-permute')
# dp2 x mp2 against one device, same weights and batches: both run the
# matmuls in bf16 (relative precision 2^-8 = 3.9e-3) with different
# reduction orders, and mp>1 takes the full-logits loss where one device
# takes the blockwise one. Losses must agree well inside 1e-2 relative.
MESH_LOSS_RTOL = 1e-2
# Small on purpose. A fresh init on the skewed stream below overshoots at the
# usual 3e-4 with or without warm-up or cosine decay (losses 10.3, 8.7, 14.9,
# 9.0, 13.9, 12.8 on the chip, PR 21); at 3e-5 it reaches 7.6 in six steps.
TRAIN_LR = 3e-5

# XLA compile requests by jitted function name (a request the persistent
# cache answers still counts: the question is how often jit asked)
COMPILES = collections.Counter()
jax.monitoring.register_event_duration_secs_listener(
    lambda event, _secs, fun_name=None, **_: COMPILES.update(
        [fun_name] if event.endswith('backend_compile_duration') else []))


@dataclasses.dataclass
class Sizes:
    """What the run is sized to. The defaults ARE the smoke (the 337M GPT
    that has a 2026-07-31 chip number, at full width and depth); only the
    off-chip rehearsal of this script's control flow builds a smaller one."""
    vocab: int = 32768
    hidden: int = 1024
    layers: int = 24
    heads: int = 16
    seq: int = 1024
    batch: int = 8
    train_steps: int = 8
    mesh_steps: int = 3
    slots: int = 8
    prompt_lens: tuple = (16, 48, 96, 160, 256, 384, 512, 768)
    new_tokens: int = 32
    fence_dim: int = 4096
    fence_matmuls: int = 512


def emit(phase, **fields):
    print(json.dumps({'phase': phase, **fields}), flush=True)


def device_info(count=None):
    devs = jax.devices()
    return {'platform': devs[0].platform, 'kind': devs[0].device_kind,
            'count': len(devs) if count is None else count}


def cache_counters():
    from paddle_tpu import warmup
    st = warmup.cache_stats()
    return {'dir': st['dir'], 'entries': st['entries'],
            'hits': st['hit_total'], 'misses': st['miss_total']}


def gpt_config(sz, **kw):
    from paddle_tpu.models import gpt
    return gpt.GPTConfig(vocab_size=sz.vocab, hidden_size=sz.hidden,
                         num_layers=sz.layers, num_heads=sz.heads,
                         max_seq_len=sz.seq, dtype='bfloat16',
                         use_flash=True, remat_policy='dots', **kw)


def token_stream(sz, seed):
    """Seeded, learnable token stream: a Zipf unigram law, so a few steps
    already pull the loss below its value at initialisation."""
    rng = np.random.RandomState(seed)
    n = max(4_000_000, 4 * sz.batch * (sz.seq + 1) * sz.train_steps)
    return ((rng.zipf(1.3, n) - 1) % sz.vocab).astype(np.int32)


def kernel_calls(compiled):
    return compiled.as_text().count('tpu_custom_call')


def on_tpu(tree):
    return all(d.platform == 'tpu' for leaf in jax.tree_util.tree_leaves(tree)
               for d in leaf.devices())


# ---------------------------------------------------------------------------
# default phases (one chip)
# ---------------------------------------------------------------------------

def phase_kernels():
    import tpu_kernel_check
    t0 = time.time()
    results = tpu_kernel_check.run_battery()
    bad = {k: v for k, v in results.items() if not v['ok']}
    emit('kernels', ok=not bad, checks=len(results),
         max_diff={k: v.get('max_diff') for k, v in results.items()},
         failed=bad, smoke_seconds_total=round(time.time() - t0, 1),
         cache=cache_counters())
    assert not bad, f'kernel parity failed: {bad}'
    for need in ('dropout_fwd', 'dropout_grad', 'paged_bf16_d64',
                 'paged_int8_d64', 'paged_bf16_d128', 'paged_int8_d128'):
        assert need in results, f'battery lost its {need} check'


def train_steps(cfg, mesh, sz, seed, n_steps, phase):
    """n_steps of make_train_step(cfg, opt, mesh) on seeded batches through
    examples/train_gpt.py's entry points. -> (losses, compiled step text,
    params) after asserting placement and a single compilation."""
    import paddle_tpu as paddle
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.io.native_loader import LMTokenLoader
    from paddle_tpu.models import gpt

    t0, compiles_before = time.time(), COMPILES['jit(step)']
    opt = paddle.optimizer.AdamW(learning_rate=TRAIN_LR, weight_decay=0.01)
    params = gpt.place_params(
        gpt.init_params(cfg, jax.random.PRNGKey(seed)), cfg, mesh)
    opt_state = opt.functional_init(params)
    assert on_tpu((params, opt_state)), \
        'a parameter or optimizer leaf is not on a TPU device'
    step_fn = gpt.make_train_step(cfg, opt, mesh)
    loader = LMTokenLoader(token_stream(sz, seed), sz.batch, sz.seq + 1,
                           n_workers=2)
    data = NamedSharding(mesh, P('dp', None))

    losses, step_secs, compiled = [], [], None
    try:
        for i in range(n_steps):
            batch = loader.next_batch()
            toks = jax.device_put(batch[:, :-1].astype(np.int32), data)
            tgts = jax.device_put(batch[:, 1:].astype(np.int32), data)
            args = (params, opt_state, jax.random.PRNGKey(i),
                    jnp.asarray(TRAIN_LR, jnp.float32), toks, tgts)
            if compiled is None:
                # the program the steps below run, as text (the jit call
                # shares this compilation; a second one would be a retrace)
                compiled = step_fn.lower(*args).compile()
            t1 = time.time()
            loss, params, opt_state = step_fn(*args)
            losses.append(float(loss))          # host read: waits for it
            step_secs.append(round(time.time() - t1, 3))
    finally:
        loader.close()
    n_compiles = COMPILES['jit(step)'] - compiles_before
    calls = kernel_calls(compiled)
    text = compiled.as_text()
    mem = compiled.memory_analysis()
    emit(phase, ok=True, steps=n_steps, losses=losses,
         mesh={k: v for k, v in mesh.shape.items() if v > 1},
         n_params=sum(int(x.size) for x in jax.tree_util.tree_leaves(params)),
         tpu_custom_calls=calls, step_compilations=n_compiles,
         collectives=[c for c in COLLECTIVES if c in text],
         argument_bytes=int(mem.argument_size_in_bytes),
         temp_bytes=int(mem.temp_size_in_bytes),
         smoke_seconds_per_step=step_secs,
         smoke_seconds_total=round(time.time() - t0, 1),
         cache=cache_counters())
    assert all(np.isfinite(losses)), f'non-finite loss: {losses}'
    assert calls > 0, 'no flash kernel (tpu_custom_call) in the train step'
    assert n_compiles == 1, f'train step compiled {n_compiles} times'
    assert on_tpu((params, opt_state))
    return losses, text, params


def phase_train(sz, seed):
    from paddle_tpu.distributed import fleet
    topo = fleet.init(is_collective=True,
                      strategy=fleet.DistributedStrategy())
    losses, _, _ = train_steps(gpt_config(sz), topo.mesh, sz, seed,
                               sz.train_steps, 'train')
    assert losses[-1] < losses[0], f'loss did not fall: {losses}'


def make_requests(sz, seed):
    rng = np.random.RandomState(seed + 1)
    return [rng.randint(1, sz.vocab, size=n).tolist() for n in sz.prompt_lens]


def serve_requests(engine, prompts, sz, late=2):
    """Submit all but ``late`` requests, wait until the first one streams
    its second token (which only a decode step emits, so the engine is
    decoding), submit the rest, drain every stream. -> (token lists in
    request order, slots active when the late requests arrived)."""
    early = len(prompts) - late
    futs = [engine.submit(p, max_new_tokens=sz.new_tokens, seed=i)
            for i, p in enumerate(prompts[:early])]
    first = futs[0].stream(timeout=600)
    head = [next(first), next(first)]
    decoding = engine.stats()['active_slots']
    futs += [engine.submit(p, max_new_tokens=sz.new_tokens, seed=early + i)
             for i, p in enumerate(prompts[early:])]
    streams = [head + list(first)]
    streams += [list(f.stream(timeout=600)) for f in futs[1:]]
    return streams, decoding


def check_streams(streams, sz):
    for i, toks in enumerate(streams):
        assert len(toks) == sz.new_tokens, \
            f'request {i}: {len(toks)} tokens, wanted {sz.new_tokens}'
        assert all(0 <= t < sz.vocab for t in toks), \
            f'request {i}: token outside the vocabulary'


def engine_report(engine):
    """Counts read off a warmed engine: traces, kernels per executable
    (the prefill's at the widest of its widths)."""
    st = engine.stats()
    return {'traces': st['traces'], 'evictions': st['evictions'],
            'circuit_state': st['circuit_state'],
            'decode_steps': st['steps'],
            'decode_step_ms_p50': st['decode_step_ms_p50'],
            'prefill_ms_p50': st['prefill_ms_p50'],
            'decode_tpu_custom_calls': kernel_calls(engine._aot['gen_decode']),
            'prefill_tpu_custom_calls': kernel_calls(
                engine._aot[f'gen_prefill.{engine.prefill_width}']),
            'mesh': st['mesh']}


def run_engine(params, cfg, prompts, sz, phase, **engine_kw):
    """README "Continuous batching & paged KV": warmup, submit, stream.
    -> (streams, report) after the asserts every engine must meet."""
    from paddle_tpu import serving
    t0 = time.time()
    engine = serving.GenerationEngine(params, cfg, num_slots=sz.slots,
                                      **engine_kw)
    try:
        # the step, and the prefill at every width a prompt is padded to
        executables = 1 + len(engine.prefill_widths)
        warm = engine.warmup()
        assert warm['prebuilt'] == executables, warm
        assert on_tpu((engine._params, engine._pool))
        streams, decoding = serve_requests(engine, prompts, sz)
        check_streams(streams, sz)
        # determinism: the same (prompt, seed) again, byte for byte
        again = list(engine.submit(prompts[3], max_new_tokens=sz.new_tokens,
                                   seed=3).stream(timeout=600))
        rep = engine_report(engine)
        sharded = sorted({d.id for leaf in jax.tree_util.tree_leaves(
            (engine._params, engine._pool)) for d in leaf.devices()})
    finally:
        engine.shutdown()
    leaked = engine.num_pages - 1 - engine._alloc.free_pages
    emit(phase, ok=True, requests=len(streams),
         tokens=sum(len(s) for s in streams),
         decoding_when_late_requests_arrived=decoding,
         repeat_identical=again == streams[3], pages_leaked=leaked,
         devices=sharded, smoke_seconds_total=round(time.time() - t0, 1),
         cache=cache_counters(), **rep)
    assert decoding >= 1, 'late requests were not submitted mid-decode'
    assert again == streams[3], 'same prompt and seed, different stream'
    assert rep['traces'] == executables, \
        f"trace count {rep['traces']} != {executables}"
    assert rep['decode_tpu_custom_calls'] > 0, \
        'no paged kernel (tpu_custom_call) in the decode executable'
    assert rep['evictions'] == 0 and rep['circuit_state'] == 'closed', rep
    assert leaked == 0, f'{leaked} pages leaked after shutdown'
    return streams, sharded, rep


def dense_agreement(params, cfg, prompts, streams, sz, n=2):
    """Share of engine tokens equal to the dense-cache greedy path's, on
    the n shortest prompts. INFORMATION, never asserted: with near-uniform
    logits from a fresh init, bf16 kernels and jax.numpy attention
    legitimately disagree on near-ties. Parity is the kernels phase's job."""
    from paddle_tpu.models import gpt
    prefill, step = gpt.make_decode_fns(cfg)
    same = total = 0
    for prompt, want in list(zip(prompts, streams))[:n]:
        cache = gpt.init_kv_cache(cfg, 1)
        logits, cache = prefill(params, jnp.asarray([prompt], jnp.int32),
                                cache)
        got = []
        for i in range(sz.new_tokens):
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            got.append(int(tok[0]))
            logits, cache = step(params, tok, jnp.int32(len(prompt) + i),
                                 cache)
        same += sum(a == b for a, b in zip(got, want))
        total += len(want)
    return round(same / total, 4)


def phase_serve(sz, seed):
    from paddle_tpu.models import gpt
    cfg = gpt_config(sz)
    params = gpt.init_params(cfg, jax.random.PRNGKey(seed))
    prompts = make_requests(sz, seed)
    streams, _, _ = run_engine(params, cfg, prompts, sz, 'serve')
    emit('serve_vs_dense_cache', info_only=True,
         token_agreement=dense_agreement(params, cfg, prompts, streams, sz))


def phase_fence(sz):
    """Does block_until_ready wait for the device here? One long chain of
    matmuls, timed to dispatch, to block_until_ready, and to a host read."""
    n, k = sz.fence_dim, sz.fence_matmuls

    @jax.jit
    def chain(x, w):
        def body(_, x):
            y = jnp.dot(x, w, preferred_element_type=jnp.float32)
            return (y * jax.lax.rsqrt(jnp.mean(y * y) + 1e-6)).astype(x.dtype)
        return jax.lax.fori_loop(0, k, body, x)

    x = jax.random.normal(jax.random.PRNGKey(0), (n, n), jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (n, n), jnp.bfloat16)
    float(chain(x, w)[0, 0])                      # compile + settle
    t0 = time.perf_counter()
    y = chain(x, w)
    t_dispatch = time.perf_counter() - t0
    y.block_until_ready()
    t_block = time.perf_counter() - t0
    val = float(y[0, 0])
    t_read = time.perf_counter() - t0
    fences = t_block > 0.5 * t_read
    emit('fence', ok=True, block_until_ready_waits=fences,
         matmuls=k, dim=n, smoke_seconds_dispatch=round(t_dispatch, 4),
         smoke_seconds_block_until_ready=round(t_block, 4),
         smoke_seconds_host_read=round(t_read, 4))
    assert np.isfinite(val)


# ---------------------------------------------------------------------------
# --chips 4: only what exists across chips, and what it is compared with
# ---------------------------------------------------------------------------

def phase_mesh_train(sz, seed):
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.topology import HybridTopology
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {'dp_degree': 2, 'mp_degree': 2}
    topo = fleet.init(is_collective=True, strategy=strategy)
    assert topo.mesh.size == 4, dict(topo.mesh.shape)
    losses, text, params = train_steps(
        gpt_config(sz, mp=2), topo.mesh, sz, seed, sz.mesh_steps,
        'mesh_train_dp2xmp2')
    spans = [len({s.device.id for s in leaf.addressable_shards})
             for leaf in jax.tree_util.tree_leaves(params)]
    split = sum(1 for leaf in jax.tree_util.tree_leaves(params)
                if leaf.addressable_shards[0].data.size < leaf.size)
    del params
    assert all(n == 4 for n in spans), \
        f'parameter leaves on {sorted(set(spans))} devices, wanted 4'
    assert split > 0, 'no parameter is actually split over the mesh'
    assert any(c in text for c in COLLECTIVES), 'no collective in the step'

    one = HybridTopology(devices=jax.devices()[:1]).mesh
    ref, _, ref_params = train_steps(gpt_config(sz), one, sz, seed,
                                     sz.mesh_steps, 'mesh_train_one_device')
    del ref_params
    rel = [abs(a - b) / abs(b) for a, b in zip(losses, ref)]
    emit('mesh_train_compare', ok=max(rel) <= MESH_LOSS_RTOL,
         losses_dp2xmp2=losses, losses_one_device=ref,
         relative_difference=rel, rtol=MESH_LOSS_RTOL,
         parameter_leaves_split=split)
    assert max(rel) <= MESH_LOSS_RTOL, \
        f'dp2xmp2 and one-device losses differ by {max(rel):.2e}'


def phase_mesh_serve(sz, seed):
    from paddle_tpu.models import gpt
    cfg = gpt_config(sz)
    params = gpt.init_params(cfg, jax.random.PRNGKey(seed))
    prompts = make_requests(sz, seed)
    got, devices, rep = run_engine(params, cfg, prompts, sz,
                                   'mesh_serve_mp4', mp=4)
    assert len(devices) == 4, f'weights and pool on devices {devices}'
    assert rep['mesh']['mp'] == 4 and rep['mesh']['fallbacks'] == [], rep
    ref, _, _ = run_engine(params, cfg, prompts, sz, 'mesh_serve_mp1')
    same = sum(a == b for g, r in zip(got, ref) for a, b in zip(g, r))
    emit('mesh_serve_compare', info_only=True,      # near-ties, see above
         token_agreement_mp4_vs_mp1=round(
             same / sum(len(r) for r in ref), 4))


# ---------------------------------------------------------------------------

def run(chips, seed, sz):
    """Every phase of one mode, in order; the first failed assert ends it."""
    from paddle_tpu import warmup
    cache_dir = warmup.ensure_persistent_cache()
    assert cache_dir and warmup.persistent_cache_dir() == cache_dir, \
        'the persistent compile cache is not active'
    placed = os.environ.get('JAX_COMPILATION_CACHE_DIR')
    assert jax.config.jax_compilation_cache_dir == (placed or cache_dir)
    emit('cache', ok=True, placed_by='JAX_COMPILATION_CACHE_DIR' if placed
         else 'paddle_tpu.warmup.DEFAULT_CACHE_DIR', **cache_counters())
    if chips == 4:
        assert len(jax.devices()) >= 4, \
            f'--chips 4 needs four devices, jax has {len(jax.devices())}'
        phase_mesh_train(sz, seed)
        phase_mesh_serve(sz, seed)
    else:
        phase_kernels()
        phase_train(sz, seed)
        phase_serve(sz, seed)
        phase_fence(sz)
    c = cache_counters()
    assert c['hits'] + c['misses'] > 0, \
        'no compile went through the persistent cache'
    emit('cache_end', ok=True, **c)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--chips', type=int, choices=(1, 4), default=1)
    ap.add_argument('--seed', type=int, default=0)
    args = ap.parse_args(argv)
    count = 4 if args.chips == 4 else None
    if jax.devices()[0].platform != 'tpu':
        print(json.dumps({
            'ok': False, 'device': device_info(count),
            'reason': f'jax found no TPU (first device: '
                      f'{jax.devices()[0].platform}); this smoke only '
                      f'means something on the chip'}))
        return 1
    try:
        run(args.chips, args.seed, Sizes())
    except BaseException as e:
        import traceback
        traceback.print_exc()
        print(json.dumps({'ok': False, 'device': device_info(count),
                          'reason': f'{type(e).__name__}: {e}'[:500]}))
        return 1
    print(json.dumps({'ok': True, 'device': device_info(count)}))
    return 0


if __name__ == '__main__':
    sys.exit(main())
