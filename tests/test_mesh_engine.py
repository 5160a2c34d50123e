"""Mesh-sharded serving replicas: Partitioner resolution for the paged-KV
axes, MeshContext placement, mp=1 vs mp>1 byte parity at matched seeds,
trace-count uniformity, warm clone portability, and per-chip ModelHost
admission (8-device CPU mesh)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from paddle_tpu.models import gpt
from paddle_tpu.ops.paged_kv import POOL_LOGICAL_AXES
from paddle_tpu.parallel import (MeshContext, Partitioner,
                                 ShardingRuleError, mesh_engine,
                                 serving_rules)
from paddle_tpu.serving import (GenerationEngine, InferenceEngine,
                                MeshReplica, ModelHost,
                                sharded_generation_engine,
                                sharded_inference_engine)

pytestmark = pytest.mark.mesh


def tiny_cfg(**over):
    kw = dict(vocab_size=96, hidden_size=32, num_layers=2, num_heads=2,
              max_seq_len=64, dtype='float32', remat=False, use_flash=False)
    kw.update(over)
    return gpt.GPTConfig(**kw)


def tiny_params(cfg, seed=0):
    return gpt.init_params(cfg, jax.random.PRNGKey(seed))


ENGINE_KW = dict(num_slots=4, page_size=16, prefill_width=32,
                 queue_capacity=16)


def gen_engine(params, cfg, mp, **over):
    kw = dict(ENGINE_KW)
    kw.update(over)
    if mp > 1:
        return sharded_generation_engine(params, cfg, mp=mp, **kw)
    return GenerationEngine(params, cfg, **kw)


# ---------------------------------------------------------------------------
# rule resolution: kv_heads / kv_pages under mp=1/2/4  (satellite 3)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize('mp', [1, 2, 4])
def test_serving_rules_resolve_pool_axes(mp):
    # GSPMD convention: kv_heads maps to 'mp' at every degree (a size-1
    # mesh axis is a no-op), kv_pages is pinned replicated
    pt = Partitioner(rules=serving_rules(mp=mp))
    # pool plane [layers, pages, kv_heads, page_size, head_dim]
    spec = pt.spec(POOL_LOGICAL_AXES)
    assert spec == P(None, None, 'mp', None, None)


@pytest.mark.parametrize('mp', [1, 2, 4])
def test_pool_spec_on_live_mesh(mp):
    # against a real mesh: heads shard over mp-of-N devices, and the
    # mp=1 mesh resolves the same rule to an effective no-op
    ctx = MeshContext.build(mp)
    sh = ctx.pool_sharding()
    assert tuple(sh.spec)[:3] == (None, None, 'mp')
    assert sh.mesh.size == mp


@pytest.mark.parametrize('mp', [2, 4])
def test_kv_pages_explicitly_replicated(mp):
    # the trash page makes the pool page count slots*p_max+1 — indivisible
    # by any mp>1 — so the rules table pins kv_pages to None outright
    pt = Partitioner(rules=serving_rules(mp=mp))
    assert pt.spec(('kv_pages',)) == P(None)


def test_trash_page_count_indivisible_raises_without_none_rule():
    # a hypothetical kv_pages->mp rule would RAISE on the odd page count
    # (divisibility failure does not fall through); the shipped table's
    # explicit None rule is what keeps the pool admissible at any mp
    pt = Partitioner(rules=(('kv_pages', 'mp'),),
                     mesh=mesh_engine.build_mesh(2))
    with pytest.raises(ShardingRuleError):
        pt.spec(('kv_pages',), shape=(9,))   # 4 slots * 2 pages + trash


def test_taken_axis_falls_through_to_replicated():
    # within one spec a mesh axis is used once: heads takes 'mp' first,
    # so a second kv_heads dim falls through the table to replicated
    pt = Partitioner(rules=serving_rules(mp=2))
    assert pt.spec(('kv_heads', 'kv_heads')) == P('mp', None)


def test_model_and_pool_rules_coexist():
    pt = Partitioner(rules=serving_rules(mp=2))
    assert pt.spec(('layers', 'embed', 'heads')) == P(None, None, 'mp')
    assert pt.spec(('kv_heads',)) == P('mp')


# ---------------------------------------------------------------------------
# MeshContext placement
# ---------------------------------------------------------------------------

def test_mesh_context_build_and_describe():
    ctx = MeshContext.build(2)
    d = ctx.describe()
    assert d['mp'] == 2 and d['devices'] == 2
    assert d['axes']['mp'] == 2
    assert all(v == 1 for k, v in d['axes'].items() if k != 'mp')


def test_build_mesh_uses_exactly_mp_devices():
    # HybridTopology must not auto-grow dp over the remaining devices
    mesh = mesh_engine.build_mesh(2)
    assert mesh.size == 2


def test_place_pool_shards_heads_axis():
    cfg = tiny_cfg()
    ctx = MeshContext.build(2)
    pool = gpt.init_paged_kv_cache(cfg, num_pages=9, page_size=16)
    placed = ctx.place_pool(pool)
    for plane in (placed['k'], placed['v']):
        sh = plane.sharding
        assert isinstance(sh, NamedSharding)
        assert tuple(sh.spec)[:3] == (None, None, 'mp')


def test_indivisible_param_falls_back_replicated():
    # vocab 97 does not divide 2: wte lands replicated and the fallback is
    # recorded (memory, never correctness)
    cfg = tiny_cfg(vocab_size=97)
    ctx = MeshContext.build(2)
    placed = ctx.place_params(tiny_params(cfg), cfg)
    assert placed['wte'].sharding.spec == P()
    assert any(f['tensor'] == 'wte' for f in ctx.fallbacks)


def test_resolve_normalizes_engine_mesh_arg():
    assert mesh_engine.resolve(None) is None
    assert mesh_engine.resolve(None, mp=1) is None
    ctx = mesh_engine.resolve(None, mp=2)
    assert isinstance(ctx, MeshContext) and ctx.mp == 2
    assert mesh_engine.resolve(ctx) is ctx


def test_sharded_structs_preserve_placement():
    ctx = MeshContext.build(2)
    x = jax.device_put(np.zeros((4, 8), np.float32),
                       ctx.sharding(('kv_heads', None), (4, 8)))
    st = mesh_engine.sharded_structs({'x': x})['x']
    assert st.sharding == x.sharding
    # host-side numpy leaves stay plain structs
    st2 = mesh_engine.sharded_structs({'y': np.zeros((3,), np.int32)})['y']
    assert getattr(st2, 'sharding', None) is None


# ---------------------------------------------------------------------------
# engine byte parity + trace uniformity (the acceptance gate's core claim)
# ---------------------------------------------------------------------------

def _run_stream(engine, prompt, n_new, seed=7):
    try:
        fut = engine.submit(prompt, max_new_tokens=n_new, seed=seed)
        toks = list(fut.result(timeout=120))
        return toks, engine.stats()
    finally:
        engine.shutdown()


# mp=4 splits four heads; its greedy tokens are held to the dense cache by
# test_sharded_engine_rows_against_the_dense_cache[mp4_heads4]
@pytest.mark.parametrize('mp, temperature', [(2, 0.0), (2, 0.8), (4, 0.8)],
                         ids=['greedy', 'sampled', 'mp4-sampled'])
def test_byte_parity_mp1_vs_mp2(mp, temperature):
    cfg = tiny_cfg(num_heads=mp)
    params = tiny_params(cfg)
    prompt = [5, 11, 23, 42]
    t1, s1 = _run_stream(gen_engine(params, cfg, 1,
                                    temperature=temperature), prompt, 12)
    t2, s2 = _run_stream(gen_engine(params, cfg, mp,
                                    temperature=temperature), prompt, 12)
    assert t1 == t2
    assert s1['traces'] == 2 and s2['traces'] == 2
    assert s1['mesh'] is None
    assert s2['mesh']['mp'] == mp


@pytest.mark.parametrize('temperature', [0.0, 0.8],
                         ids=['greedy', 'sampled'])
def test_mp2_one_step_ahead_serves_what_reading_first_serves(
        temperature, read_first):
    """The decode loop dispatches step N+1 before it reads step N (PR 36):
    under a mesh the fed-back tokens stay replicated over it, so the step
    is still ONE executable (two traces), and six requests on four slots
    are served the tokens and the rows, to the last bit, that a loop
    reading each step before it dispatches the next serves."""
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 96, size=n).astype(np.int32)
               for n in (5, 11, 3, 8, 13, 6)]

    def serve():
        eng = gen_engine(params, cfg, 2, temperature=temperature)
        try:
            futs = [eng.submit(p, max_new_tokens=6 + 3 * (i % 3), seed=i,
                               want_logits=True)
                    for i, p in enumerate(prompts)]
            return ([(f.result(timeout=300), np.stack(f.logits()))
                     for f in futs], eng.stats())
        finally:
            eng.shutdown()

    got, stats = serve()
    with read_first():
        want, base = serve()
    assert base['steps_overlapped'] == 0 < stats['steps_overlapped']
    assert stats['traces'] == base['traces'] == 2
    assert stats['mesh']['mp'] == 2 and stats['rows_discarded'] == 0
    for (toks, rows), (want_toks, want_rows) in zip(got, want):
        assert toks == want_toks
        assert np.array_equal(rows, want_rows)


# case -> (mesh degree, config overrides, engine keywords)
DENSE_CASES = {
    'mp2': (2, {}, {}),
    'mp4_heads4': (4, dict(num_heads=4), {}),
    'mp2_gqa': (2, dict(num_heads=4, num_kv_heads=2), {}),
    'mp2_kv_cache_int8': (2, dict(kv_cache_int8=True), {}),
    'mp2_prefix_tail': (2, {}, dict(prefix_cache=True)),
}


@pytest.mark.parametrize('case', sorted(DENSE_CASES))
def test_sharded_engine_rows_against_the_dense_cache(case):
    """A pool whose pages are split over 'mp' by heads, carried whole
    through the layers and written a page at a time, serves what the
    dense cache on one device computes: the same greedy tokens and the
    row each was chosen from (PR 28: a head's rows of a page stay one
    block inside a shard)."""
    mp, over, kw = DENSE_CASES[case]
    cfg = tiny_cfg(**over)
    params = tiny_params(cfg)
    rng = np.random.RandomState(len(case))
    first = rng.randint(1, 96, size=21).astype(np.int32)
    prompts = [first, np.concatenate([first, [7, 9, 3]]).astype(np.int32),
               rng.randint(1, 96, size=5).astype(np.int32)]
    n_new = 6

    def dense(prompt):
        cache = gpt.init_kv_cache(cfg, 1)
        lg, cache = gpt.forward_with_cache(
            params, jnp.asarray(prompt[None]), cache, 0, cfg, last_only=True)
        rows = [np.asarray(lg[0, -1], np.float32)]
        for pos in range(len(prompt), len(prompt) + n_new - 1):
            tok = jnp.asarray([[int(np.argmax(rows[-1]))]], jnp.int32)
            lg, cache = gpt.forward_with_cache(params, tok, cache, pos, cfg)
            rows.append(np.asarray(lg[0, -1], np.float32))
        return np.stack(rows)

    engine = gen_engine(params, cfg, mp, **kw)
    try:
        got = []
        for p in prompts:       # one after the other: the second's prefix
            fut = engine.submit(p, max_new_tokens=n_new, want_logits=True)
            got.append((fut.result(timeout=300), np.stack(fut.logits())))
        stats = engine.stats()
    finally:
        engine.shutdown()
    # the step and the prefill (one width, two pages of 16 rows): a mesh
    # changes neither the functions nor how many shapes they meet
    assert stats['traces'] == 2 and stats['mesh']['mp'] == mp
    if kw.get('prefix_cache'):
        assert stats['prefix_tokens_saved'] == len(first)    # ends mid-page
    for p, (toks, rows) in zip(prompts, got):
        want = dense(p)
        np.testing.assert_allclose(rows, want, rtol=2e-5, atol=2e-5)
        assert list(toks) == [int(np.argmax(r)) for r in want]


def test_mesh_gauge_and_uniform_labels():
    # the mesh degree is published as its OWN gauge series; the engine's
    # label set stays exactly {'engine': ...} so every control-plane
    # exact-match lookup treats mp=2 like mp=1 (uniformity)
    from paddle_tpu import observability as obs
    cfg = tiny_cfg()
    eng = gen_engine(tiny_params(cfg), cfg, 2)
    try:
        assert set(eng.labels) == {'engine'}
        g = obs.find('gen.mesh_devices',
                     {**eng.labels, 'mesh': 'mp2'})
        assert g is not None and g.value == 2
    finally:
        eng.shutdown()


def test_warmup_then_traffic_keeps_two_traces():
    """Two functions under a mesh as without one: the step, and the
    prefill at each of the engine's widths (one here)."""
    cfg = tiny_cfg()
    eng = gen_engine(tiny_params(cfg), cfg, 2)
    try:
        eng.warmup()
        assert eng._trace_count == 1 + len(eng.prefill_widths) == 2
        assert set(eng._aot) == {'gen_prefill.32', 'gen_decode'}
        list(eng.submit([3, 1, 4], max_new_tokens=6).result(timeout=120))
        assert eng._trace_count == 2
    finally:
        eng.shutdown()


def test_warm_clone_gives_zero_retrace_mesh_spawn():
    from paddle_tpu.serving.fleet import _clone_warmth
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    src = gen_engine(params, cfg, 2)
    dst = gen_engine(params, cfg, 2)
    try:
        src.warmup()
        out1 = list(src.submit([3, 1, 4],
                               max_new_tokens=6).result(timeout=120))
        _clone_warmth(src, dst)
        out2 = list(dst.submit([3, 1, 4],
                               max_new_tokens=6).result(timeout=120))
        assert dst._trace_count == 0
        assert out1 == out2
    finally:
        src.shutdown()
        dst.shutdown()


def test_mesh_engine_rejects_int8_wo():
    cfg = tiny_cfg()
    with pytest.raises(ValueError, match='int8_wo'):
        sharded_generation_engine(tiny_params(cfg), cfg, mp=2,
                                  precision='int8_wo', **ENGINE_KW)


def test_inference_engine_parity_mp2():
    cfg = tiny_cfg()
    net = gpt.GPTForCausalLM(cfg)
    x = (np.arange(8, dtype=np.int32) % cfg.vocab_size).reshape(1, 8)
    e1 = InferenceEngine(net, max_batch_size=4, max_delay_ms=1)
    y1 = np.asarray(e1.submit(x).result(timeout=120))
    e1.shutdown()
    e2 = sharded_inference_engine(net, mp=2, max_batch_size=4,
                                  max_delay_ms=1)
    try:
        y2 = np.asarray(e2.submit(x).result(timeout=120))
        assert e2.stats()['mesh']['mp'] == 2
    finally:
        e2.shutdown()
    np.testing.assert_allclose(y1, y2, atol=1e-5)


def test_mesh_replica_wrapper():
    cfg = tiny_cfg()
    rep = MeshReplica(tiny_params(cfg), cfg, mp=2, **ENGINE_KW)
    try:
        list(rep.submit([9, 9], max_new_tokens=4).result(timeout=120))
        st = rep.stats()
        assert rep.mp == 2
        assert st['mesh']['mp'] == 2
        assert 'per_chip_tokens_per_sec' in st
    finally:
        rep.shutdown()


def test_mesh_replica_mp1_is_plain_engine():
    cfg = tiny_cfg()
    rep = MeshReplica(tiny_params(cfg), cfg, mp=1, **ENGINE_KW)
    try:
        assert rep.mp == 1 and rep.mesh_ctx is None
    finally:
        rep.shutdown()


# ---------------------------------------------------------------------------
# per-chip ModelHost admission (satellite 1 + acceptance)
# ---------------------------------------------------------------------------

def _mesh_factory(params, cfg):
    def factory(mp=2):
        return sharded_generation_engine(params, cfg, mp=mp, **ENGINE_KW)
    return factory


def _per_chip_footprint(params, cfg):
    """Learn the measured per-chip footprint of the tiny mp=2 model by
    deploying it onto an effectively-unbounded host."""
    with ModelHost(hbm_watermark_bytes=1 << 40,
                   name='mesh-probe') as probe:
        m = probe.deploy('probe', _mesh_factory(params, cfg), mp=2)
        return m.footprint_bytes


def test_host_admits_mp2_under_per_chip_watermark():
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    per_chip = _per_chip_footprint(params, cfg)
    # watermark between per-chip and whole-mesh footprint: admission must
    # account per chip for the deploy to succeed at all
    with ModelHost(hbm_watermark_bytes=int(per_chip * 1.5),
                   name='mesh-admit') as host:
        m = host.deploy('sharded', _mesh_factory(params, cfg), mp=2)
        assert m.footprint_bytes <= host.watermark_bytes
        fut = host.submit('sharded', [1, 2, 3], max_new_tokens=4)
        assert len(list(fut.result(timeout=120))) == 4


def test_host_swaps_mp2_model_with_zero_retraces():
    cfg = tiny_cfg()
    params = tiny_params(cfg)
    per_chip = _per_chip_footprint(params, cfg)

    # room for ~1 model at a time: deploying the second LRU-evicts the
    # sharded one
    with ModelHost(hbm_watermark_bytes=int(per_chip * 1.6),
                   name='mesh-swap') as host:
        host.deploy('a', _mesh_factory(params, cfg), mp=2)
        out1 = list(host.submit('a', [1, 2],
                                max_new_tokens=4).result(timeout=120))
        host.deploy('b', _mesh_factory(params, cfg), mp=2)
        assert host.models()['a']['state'] == 'evicted'
        # swap-in rebuilds the SAME mesh shape (factory re-invoked with
        # mp=2) and restores warmth: zero retraces
        out2 = list(host.submit('a', [1, 2],
                                max_new_tokens=4).result(timeout=120))
        rec = host.models()['a']
        assert rec['state'] == 'live'
        assert rec['swap_ins'] >= 1
        eng = host._models['a'].engine
        assert eng._trace_count == 0
        from paddle_tpu.parallel.mesh_engine import mesh_size
        assert mesh_size(eng) == 2
        assert out1 == out2
