"""The readers of the program's own names (scope paths, kernel names,
spans, counters) on a hand-made fixture whose answers are worked out here,
on a clipped piece of a real chip trace, and on a real capture made on the
CPU; and that none of them gives a number from another run's trace."""
import glob
import json
import os
import time

import pytest

from benchmark.harness import device, manifest, trace, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
# what the runner's reduction gives the readers (harness/trace.reduce)
REDUCED = {'t0_ns': 1000.0, 't1_ns': 11000.0, 'window_s': 10000e-9,
           'busy_s': 8800e-9, 'devices': 1, 'module_runs': {'jit_step': 1.0}}
FACTS = {'shape': {'num_heads': 1, 'hidden_size': 64}, 'batch': 1,
         'seq': 128, 'mesh': {}, 'remat_policy': 'dots', 'layers': 1,
         'device_kind': 'TPU v5 lite'}
SHARES = ['train_fwd_time_share', 'train_recompute_time_share',
          'train_bwd_time_share', 'train_optimizer_time_share',
          'train_unscoped_time_share']


def _fixture(name):
    with open(os.path.join(HERE, 'fixtures', name)) as f:
        doc = json.load(f)
    for key in ('ops', 'modules'):
        doc[key] = {int(dev): ev for dev, ev in doc[key].items()}
    return doc


@pytest.fixture()
def small(monkeypatch):
    """The hand-made trace, standing where the run's newest file would."""
    monkeypatch.setattr(xplane, 'newest', lambda root=None: 'small')
    monkeypatch.setattr(xplane, '_loaded',
                        {'small': [_fixture('xplane_small.json'), {}]})


def read(metric, facts=FACTS, reduced=REDUCED):
    """The metric as run.py reads it: its own file, its own reader."""
    spec = manifest.Manifest().metric_spec(metric)
    reader = manifest.load_module('readers', spec['reader'])
    return reader.read(spec['params'], facts, reduced)


def test_every_operation_gets_the_path_of_its_instruction(small):
    tr = xplane.load(REDUCED)
    paths = {xplane._instruction(n): p for n, _, _, p in tr['ops'][0]}
    assert paths['flash_fwd.4'].endswith(
        'rematted_computation/gpt.block/attn/flash_fwd/pallas_call')
    # copy.11 has a path in ANOTHER module; in the one that ran it has none
    assert paths['copy.11'] == '' and paths['copy.9'] == ''
    # the operation that began before the window is clipped to it
    assert tr['ops'][0][0][1:3] == [1000.0, 500.0]


# busy 8800 ns: forward = fusion.0 inside the window 500 + the first
# loop's own 1500 + flash_fwd.3 1000 + copy.9 500 (no path: its loop's)
# + all-reduce.1 1000; recomputed = flash_fwd.4 800 + all-reduce.2 400;
# backward = flash_bwd_dq.1 600 + all-reduce.3 500 + the second loop's own
# 700 + fusion.5 (the head's) 500; optimizer = fusion.6 600; no scope =
# copy.11 200, at the top level
@pytest.mark.parametrize('metric,ns', [
    ('train_fwd_time_share', 4500), ('train_recompute_time_share', 1200),
    ('train_bwd_time_share', 2300), ('train_optimizer_time_share', 600),
    ('train_unscoped_time_share', 200), ('train_head_time_share', 500)])
def test_phase_shares_of_busy_time(small, metric, ns):
    assert read(metric) == pytest.approx(100.0 * ns / 8800)


def test_the_phases_and_the_unscoped_rest_are_all_of_busy_time(small):
    assert sum(read(m) for m in SHARES) == pytest.approx(100.0)


@pytest.mark.parametrize('metric,ns', [
    ('collective_exposed_fwd_share', 1000),
    ('collective_exposed_recompute_share', 400),
    ('collective_exposed_bwd_share', 500)])
def test_collectives_by_phase_are_shares_of_the_window(small, metric, ns):
    assert read(metric) == pytest.approx(100.0 * ns / 10000)
    # together: what collective_exposed_share counts (all three all-reduces
    # lie under a gpt.* scope here)


def test_collective_shares_add_up_to_the_old_readers_number(small):
    tr = xplane.load(REDUCED)
    old = manifest.load_module('readers', 'collective_exposed')
    whole = old.read({}, {}, dict(REDUCED, events={
        0: [e[:3] for e in tr['ops'][0]]}))
    parts = sum(read(f'collective_exposed_{p}_share')
                for p in ('fwd', 'recompute', 'bwd'))
    assert parts == pytest.approx(whole) == pytest.approx(19.0)


@pytest.mark.parametrize('metric,variant,ns,calls', [
    ('flash_fwd_roofline', 'fwd', 1800, 2),     # forward and recomputed
    ('flash_dq_roofline', 'dq', 600, 1)])
def test_a_named_kernels_roofline(small, metric, variant, ns, calls):
    fa = manifest.load_module('kernels', 'flash_attention')
    flops, byts = fa.call_cost(variant, 1, 1, 128, 64)
    peaks = device.peaks('TPU v5 lite')
    least = max(flops / peaks['bf16_flops_per_s'],
                byts / peaks['hbm_bytes_per_s'])
    assert read(metric) == pytest.approx(100 * calls * least / (ns * 1e-9))


def test_a_kernel_the_trace_does_not_name_gives_no_number(small):
    assert read('flash_dkv_roofline') is None


def test_the_forward_kernel_counts_once_a_layer_without_remat(small):
    once = read('flash_fwd_roofline', dict(FACTS, remat_policy='none'))
    assert read('flash_fwd_roofline') == pytest.approx(2 * once)


def test_program_spans_that_begin_inside_the_window(small):
    # the first data.next_batch began before the window: 100 and 300 ns
    assert read('train_loader_wait_ms') == pytest.approx(200e-6)
    assert read('train_dispatch_ms') == pytest.approx(1500e-6)
    tr = xplane.load(REDUCED)
    assert [e[3] for e in tr['spans'] if e[0] == 'train.dispatch'] == [
        {'step': 5}, {'step': 6}]


@pytest.mark.parametrize('metric', SHARES + [
    'train_head_time_share', 'collective_exposed_fwd_share',
    'collective_exposed_recompute_share', 'collective_exposed_bwd_share',
    'flash_fwd_roofline', 'flash_dq_roofline', 'flash_dkv_roofline',
    'train_loader_wait_ms', 'train_dispatch_ms'])
def test_no_number_from_a_trace_that_is_not_this_runs(small, metric):
    assert read(metric, reduced=dict(REDUCED, t0_ns=999.0)) is None
    assert read(metric, reduced=None) is None


def test_no_number_without_a_trace_on_disk(monkeypatch, tmp_path):
    monkeypatch.setattr(xplane, '_loaded', {})
    assert xplane.load(REDUCED, root=str(tmp_path)) is None


def test_a_program_without_the_scopes_gives_no_share(small, monkeypatch):
    """The parent of the PR that brought the scopes: paths, but no gpt.*"""
    raw = _fixture('xplane_small.json')
    raw['scopes'] = {'jit_step(7)': {
        k: v.replace('gpt.', 'x.') for k, v in
        raw['scopes']['jit_step(7)'].items()}}
    monkeypatch.setattr(xplane, '_loaded', {'small': [raw, {}]})
    for metric in SHARES + ['collective_exposed_bwd_share']:
        assert read(metric) is None


def test_the_newest_trace_of_any_cell_is_the_one_read(tmp_path):
    paths = []
    for cell, stamp in (('a', '2026_01_01'), ('b', '2025_01_01')):
        d = tmp_path / '.bench_out' / cell / 'trace' / 'plugins' / \
            'profile' / stamp
        d.mkdir(parents=True)
        paths.append(d / 'host.xplane.pb')
        paths[-1].write_bytes(b'')
        now = time.time()
        os.utime(paths[-1], (now + len(paths), now + len(paths)))
    assert xplane.newest(str(tmp_path)) == str(paths[-1])
    assert xplane.newest(str(tmp_path / 'nowhere')) is None


def test_the_cache_counter_reads_zero_only_where_the_program_counts():
    from paddle_tpu import observability as obs
    reader = manifest.load_module('readers', 'program_counter')
    params = {'counter': 'bench_test.miss_total',
              'zero_if': 'bench_test.hit_total'}
    assert reader.read(params, {}, None) is None
    obs.counter('bench_test.hit_total').inc()
    assert reader.read(params, {}, None) == 0
    obs.counter('bench_test.miss_total').inc(3)
    assert reader.read(params, {}, None) == 3
    spec = manifest.Manifest().metric_spec('setup_cache_misses')
    assert spec['params']['counter'] == 'warmup.cache.miss_total'


# ---- a real capture, made here on the CPU ---------------------------------

def test_scopes_and_span_attributes_of_a_real_capture(tmp_path):
    """The wire-format walk finds the HLO protos jax's profiler really
    writes, and a program span comes back with its attributes."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu import observability as obs

    @jax.jit
    def step(x):
        with jax.named_scope('gpt.block'):
            return jnp.tanh(x @ x)
    x = jnp.ones((64, 64))
    step(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with obs.span('train.dispatch', step=3):
        step(x).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / 'plugins' / 'profile' / '*' /
                         '*.xplane.pb'))[0]
    raw = xplane.read_file(path)
    mine = [names for mod, names in raw['scopes'].items()
            if mod.startswith('jit_step(')]
    assert len(mine) == 1
    assert 'jit(step)/gpt.block/tanh' in mine[0].values()
    assert 'jit(step)/gpt.block/dot_general' in mine[0].values()
    spans = [e for e in raw['spans'] if e[0] == 'train.dispatch']
    assert len(spans) == 1 and spans[0][3] == {'step': 3}
    assert spans[0][2] > 0


# ---- a clipped piece of a real chip trace ---------------------------------

@pytest.fixture()
def chip(monkeypatch):
    raw = _fixture('xplane_chip_345m.json')
    monkeypatch.setattr(xplane, 'newest', lambda root=None: 'chip')
    monkeypatch.setattr(xplane, '_loaded', {'chip': [raw, {}]})
    t0, t1 = raw['window']
    events = trace.clip(raw['ops'][0], t0, t1)
    busy, _ = trace.busy_and_gaps(events, t0, t1)
    return raw, {'t0_ns': t0, 't1_ns': t1, 'window_s': (t1 - t0) / 1e9,
                 'busy_s': busy / 1e9, 'devices': 1,
                 'module_runs': trace.module_runs(
                     {'planes': [{'name': '/device:TPU:0', 'lines': [
                         {'name': trace.MODULES_LINE,
                          'events': raw['modules'][0]}]}]}, t0, t1)}


def test_on_the_chips_trace_every_kernel_lies_under_its_blocks_scope(chip):
    raw, reduced = chip
    tr = xplane.load(reduced)
    kernels = [(xplane._instruction(n), p) for n, _, _, p in tr['ops'][0]
               if ' custom-call(' in n and n.startswith('%flash_')]
    assert len(kernels) >= 4
    for ins, path in kernels:
        kernel = ins.split('.')[0]
        assert kernel in ('flash_fwd', 'flash_bwd_dq', 'flash_bwd_dkv')
        assert path.endswith(f'gpt.block/attn/{kernel}/pallas_call'), path
    fwd = [p for k, p in kernels if k.startswith('flash_fwd')]
    assert any('rematted_computation' in p for p in fwd)
    assert any('jvp(gpt.layers)' in p and 'transpose(' not in p for p in fwd)


def test_on_the_chips_trace_the_shares_are_all_of_busy_time(chip):
    _, reduced = chip
    got = {m: read(m, reduced=reduced) for m in SHARES}
    assert sum(got.values()) == pytest.approx(100.0, abs=1e-6)
    assert got['train_unscoped_time_share'] < 3.0
    # the piece holds no optimizer update; every pass of the model is there
    assert got.pop('train_optimizer_time_share') == 0.0
    assert all(v > 0 for v in got.values())


def test_on_the_chips_trace_a_share_agrees_with_the_accepted_self_times(chip):
    """The head's share against the accepted reduction's own self times
    (harness/trace.self_times), joined to the paths by hand. The head runs
    two loops of its own (the blockwise loss), so the share here is that
    plus the little the compiler put into those loops without a path."""
    _, reduced = chip
    tr = xplane.load(reduced)
    path_of = {n: p for n, _, _, p in tr['ops'][0]}
    accepted = sum(t for n, t in trace.self_times(
        [e[:3] for e in tr['ops'][0]]) if 'gpt.head' in path_of[n])
    mine = read('train_head_time_share', reduced=reduced)
    accepted = 100.0 * accepted / 1e9 / reduced['busy_s']
    assert 50.0 < accepted <= mine <= 1.02 * accepted


def test_on_the_chips_trace_the_three_kernels_make_up_flash_roofline(chip):
    """Weighted by their kernels' times, the three shares give the share
    the accepted flash_roofline reads from the same events."""
    raw, reduced = chip
    facts = {'shape': {'num_heads': 16, 'hidden_size': 1024}, 'batch': 8,
             'seq': 1024, 'mesh': {}, 'remat_policy': 'dots', 'layers': 24,
             'device_kind': 'TPU v5 lite'}
    tr = xplane.load(reduced)
    events = [e[:3] for e in tr['ops'][0]]
    spec = manifest.Manifest().metric_spec('flash_roofline')
    old = manifest.load_module('readers', spec['reader']).read(
        spec['params'], facts, dict(reduced, events={0: events}))
    least = took = 0.0
    for m in ('flash_fwd_roofline', 'flash_dq_roofline',
              'flash_dkv_roofline'):
        share = read(m, facts, reduced)
        pattern = manifest.Manifest().metric_spec(m)['params']['pattern']
        seconds = trace.matching_time(events, pattern)[0]
        took += seconds
        least += share / 100.0 * seconds
    assert 100.0 * least / took == pytest.approx(old, rel=1e-3)
