"""The program names its own work (PERF.md section 3): ``gpt.*`` scopes in
the compiled train step beside jax's own marks of forward, backward and
recomputation; a name on every ``pallas_call``; ``observability.span``
attributes on the profiler's host events; ``data.next_batch`` and
``train.dispatch`` once a call, nothing under ``PADDLE_TPU_OBS=0``, and the
same losses either way."""
import glob
import importlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import observability as obs
from paddle_tpu.distributed import fleet
from paddle_tpu.io.native_loader import LMTokenLoader
from paddle_tpu.models import gpt
from paddle_tpu.ops import mesh_kernel

fa = importlib.import_module('paddle_tpu.ops.flash_attention')
pa = importlib.import_module('paddle_tpu.ops.paged_attention')

SCOPES = ['gpt.embed', 'gpt.layers', 'gpt.block/attn', 'gpt.block/mlp',
          'gpt.head', 'gpt.optimizer']


def _step(layout, **cfg_kw):
    """(step, args) of a two-layer train step on the virtual CPU mesh."""
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = layout
    mesh = fleet.init(is_collective=True, strategy=strategy).mesh
    cfg = gpt.GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_heads=4, max_seq_len=32, dtype='float32',
                        use_flash=False, remat_policy='dots', **cfg_kw)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3)
    step = gpt.make_train_step(cfg, opt, mesh)

    def args():
        params = gpt.place_params(
            gpt.init_params(cfg, jax.random.PRNGKey(0)), cfg, mesh)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 64)
        return (params, opt.functional_init(params), jax.random.PRNGKey(2),
                jnp.float32(1e-3), toks, toks)
    return step, args


def _op_names(step, args):
    text = step.lower(*args()).compile().as_text()
    return sorted(set(re.findall(r'op_name="([^"]+)"', text)))


@pytest.fixture(scope='module')
def gspmd_names():
    return _op_names(*_step({'dp_degree': 2, 'mp_degree': 2}, mp=2))


@pytest.fixture(scope='module')
def shard_map_names():
    # sequence parallelism takes the explicit-collective builder
    return _op_names(*_step({'dp_degree': 2, 'sp_degree': 2}, sp=2))


@pytest.mark.parametrize('scope', SCOPES)
def test_the_gspmd_step_names_its_scopes(gspmd_names, scope):
    assert any(scope in n for n in gspmd_names), scope


@pytest.mark.parametrize('scope', SCOPES + ['gpt.grad_reduce'])
def test_a_shard_map_step_names_its_scopes(shard_map_names, scope):
    assert any(scope in n for n in shard_map_names), scope


@pytest.mark.parametrize('names', ['gspmd_names', 'shard_map_names'])
@pytest.mark.parametrize('half', ['gpt.block/attn', 'gpt.block/mlp'])
def test_a_block_half_occurs_forward_backward_and_recomputed(
        request, names, half):
    """jax itself says which pass an operation belongs to; the program adds
    no scope for that."""
    # whole paths only: a reduction's inlined sub-computation keeps a
    # relative one
    mine = [n for n in request.getfixturevalue(names)
            if half in n and n.startswith('jit(')]
    recomputed = [n for n in mine if 'rematted_computation' in n]
    backward = [n for n in mine
                if 'transpose(' in n and 'rematted_computation' not in n]
    forward = [n for n in mine if 'jvp(' in n and 'transpose(' not in n]
    assert forward and backward and recomputed
    assert all('gpt.layers' in n for n in forward + backward + recomputed)


def test_head_and_embed_occur_forward_and_backward(gspmd_names):
    for scope in ('gpt.head', 'gpt.embed'):
        mine = [n for n in gspmd_names if scope in n]
        assert any('transpose(' in n for n in mine), scope
        assert any('transpose(' not in n for n in mine), scope
    assert not any('jvp(' in n for n in gspmd_names if 'gpt.optimizer' in n)


def test_the_step_keeps_its_name(gspmd_names):
    # the device trace's module jit_step is what flash_roofline counts by
    assert any(n.startswith('jit(step)/') for n in gspmd_names)


def test_the_decode_block_names_the_same_halves():
    cfg = gpt.GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                        num_heads=4, max_seq_len=32, dtype='float32',
                        use_flash=False, remat=False)
    params = gpt.init_params(cfg, jax.random.PRNGKey(0))
    cache = gpt.init_kv_cache(cfg, 1)
    text = jax.jit(
        lambda p, t, c: gpt.forward_with_cache(p, t, c, 0, cfg)).lower(
            params, jnp.zeros((1, 4), jnp.int32), cache).as_text(
                debug_info=True)
    assert 'gpt.block/attn' in text and 'gpt.block/mlp' in text


# ---- kernel names ---------------------------------------------------------

def _pallas_names(fn, *args):
    """Names of every pallas_call in the traced function, nested calls
    (custom_vjp, shard_map, jit) included."""
    out = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == 'pallas_call':
                out.append(eqn.params['name'])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return out


@pytest.fixture()
def interpret():
    fa.set_interpret(True)
    yield
    fa.set_interpret(False)


def _qkv(b=2, s=128, h=2, d=64):
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    return [jax.random.normal(k, (b, s, h, d)) for k in keys]


def _flash_loss(q, k, v):
    return jnp.sum(fa.flash_attention(q, k, v, causal=True) ** 2)


def test_flash_forward_and_backward_kernels_are_named(interpret):
    names = _pallas_names(jax.grad(_flash_loss, argnums=(0, 1, 2)), *_qkv())
    assert sorted(set(names)) == ['flash_bwd_dkv', 'flash_bwd_dq',
                                  'flash_fwd']


def test_kernel_names_survive_the_mesh_wrap(interpret):
    mesh = jax.make_mesh((2, 2), ('dp', 'mp'))
    fn = mesh_kernel.jit(jax.grad(_flash_loss, argnums=(0, 1, 2)), mesh)
    names = _pallas_names(fn, *_qkv())
    assert sorted(set(names)) == ['flash_bwd_dkv', 'flash_bwd_dq',
                                  'flash_fwd']
    text = fn.lower(*_qkv()).as_text(debug_info=True)
    assert 'shard_map' in text
    for name in ('flash_fwd', 'flash_bwd_dq', 'flash_bwd_dkv'):
        assert f'{name}/' in text or f'/{name}"' in text, name


def test_the_decode_kernel_is_named(interpret):
    kc = jnp.ones((2, 256, 2, 64))
    q = jnp.ones((2, 1, 2, 64))
    names = _pallas_names(lambda pos: fa.flash_decode(q, kc, kc, pos),
                          jnp.int32(5))
    assert names == ['flash_decode']


@pytest.mark.parametrize('int8', [False, True])
def test_the_paged_kernel_is_named(interpret, int8):
    n, ps, h, d = 5, 128, 2, 64
    q = jnp.ones((2, 1, h, d))
    pages = jnp.ones((n, h, ps, d), jnp.int8 if int8 else jnp.float32)
    table = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    pos = jnp.asarray([130, 7], jnp.int32)
    if int8:
        bank = {'int8': pages, 'scale': jnp.ones((n, h, ps), jnp.float32)}
        names = _pallas_names(
            lambda: pa.paged_flash_decode_int8(q, bank, bank, table, pos))
    else:
        names = _pallas_names(
            lambda: pa.paged_flash_decode(q, pages, pages, table, pos))
    assert names == ['paged_attention']


def test_every_pallas_call_in_ops_has_a_name():
    import os
    ops = os.path.dirname(fa.__file__)
    for path in glob.glob(os.path.join(ops, '*.py')):
        src = open(path).read()
        calls = [m.start() for m in re.finditer(r'pl\.pallas_call\(', src)]
        for at in calls:
            depth, i = 0, src.index('(', at)
            while True:            # the call's own argument list
                depth += {'(': 1, ')': -1}.get(src[i], 0)
                if depth == 0:
                    break
                i += 1
            assert re.search(r"\bname='\w+'", src[at:i]), (path, at)


def test_no_module_in_ops_reads_the_environment():
    """A kernel's choice is made from shapes and platform, not by whoever
    launched the process."""
    import ast
    import os
    ops = os.path.dirname(fa.__file__)
    for path in glob.glob(os.path.join(ops, '*.py')):
        for node in ast.walk(ast.parse(open(path).read())):
            if isinstance(node, ast.Attribute):
                assert node.attr not in ('environ', 'getenv'), (
                    path, node.lineno)
            if isinstance(node, ast.ImportFrom) and node.module == 'os':
                assert not {a.name for a in node.names} & {
                    'environ', 'getenv'}, (path, node.lineno)


# ---- spans ----------------------------------------------------------------

def _captured(tmp_path, fn):
    """Host events {name: [attribute dicts]} of a real profiler capture."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(str(tmp_path / 'plugins' / 'profile' / '*' /
                          '*.xplane.pb'))
    data = jax.profiler.ProfileData.from_file(found[0])
    out = {}
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                out.setdefault(e.name, []).append(dict(e.stats))
    return out


def test_a_spans_attributes_are_statistics_of_the_profilers_event(tmp_path):
    def work():
        with obs.span('gen.decode_step', slots=3, req_ids=['a', 'b']):
            jnp.ones((8,)).block_until_ready()
    got = _captured(tmp_path, work)['gen.decode_step']
    assert got == [{'slots': 3, 'req_ids': "['a', 'b']"}]


def _loader():
    stream = np.arange(5000, dtype=np.int32) % 97
    return LMTokenLoader(stream, 4, 17, n_workers=1)


def test_next_batch_opens_one_span_a_call_with_its_bytes():
    loader = _loader()
    try:
        obs.reset_trace()
        for _ in range(3):
            loader.next_batch()
        spans = [e for e in obs.trace_events()
                 if e['name'] == 'data.next_batch']
        assert len(spans) == 3
        assert all(e['args'] == {'n_bytes': 4 * 17 * 4} for e in spans)
        assert all(e['dur'] > 0 for e in spans)
    finally:
        loader.close()


def test_dispatch_opens_one_numbered_span_a_call(tmp_path):
    step, args = _step({'dp_degree': 1})
    obs.reset_trace()
    state = args()
    out = step(*state)

    def again():
        step(out[1], out[2], *state[2:])[0].block_until_ready()
    got = _captured(tmp_path, again)
    assert got['train.dispatch'] == [{'step': 2}]
    ring = [e['args'] for e in obs.trace_events()
            if e['name'] == 'train.dispatch']
    assert ring == [{'step': 1}, {'step': 2}]


def test_with_observability_off_both_are_the_null_span(monkeypatch):
    seen = []
    real = obs.trace.span
    monkeypatch.setattr(obs.trace, 'span',
                        lambda *a, **kw: seen.append(real(*a, **kw))
                        or seen[-1])
    for mod in ('paddle_tpu.io.native_loader',
                'paddle_tpu.parallel.train_jit'):
        m = importlib.import_module(mod)
        monkeypatch.setattr(m._obs, 'span', obs.trace.span)
    obs.set_enabled(False)
    try:
        loader = _loader()
        try:
            obs.reset_trace()
            loader.next_batch()
        finally:
            loader.close()
        step, args = _step({'dp_degree': 1})
        step(*args())
        assert len(seen) == 2 and all(s is obs.NULL_SPAN for s in seen)
        assert obs.trace_events() == []
    finally:
        obs.set_enabled(True)


def test_losses_are_bit_equal_with_and_without_observability():
    step, args = _step({'dp_degree': 1})

    def three():
        state = args()
        losses = []
        for _ in range(3):
            out = step(*state)
            losses.append(np.asarray(out[0]))
            state = (out[1], out[2]) + state[2:]
        return np.stack(losses)
    on = three()
    obs.set_enabled(False)
    try:
        off = three()
    finally:
        obs.set_enabled(True)
    assert on.tobytes() == off.tobytes()
