"""The configuration granite-4.0-h-micro-serve and its cell
serve-granite-4.0-h-micro-sharegpt-full: the manifest takes them (metrics
taken BY NAME), the file keeps every published number but the served
context, its parameters count what the issue counts, the mix fits the
context, the two new counts files against a brute-force count at a small
size, the new readers on hand-made facts, and a tiny cell of the same
family laid over the copy (drive_granite.py) and run end to end on the CPU:
sound, with a planted altered token, and with each named control."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark.harness import manifest  # noqa: E402

CONFIG = 'granite-4.0-h-micro-serve'
CELL = 'serve-granite-4.0-h-micro-sharegpt-full'
CATALOG = '/opt/skills/guides/model-configs/architectures.jsonl'
NEW = {'tps.ssm_layer_time_share', 'tps.ssm_state_update_time_share',
       'tps.ssm_state_update_roofline', 'tps.ssm_scan_time_share',
       'tps.ssm_scan_roofline', 'tps.hybrid_attn_time_share',
       'tps.hybrid_mlp_time_share', 'tps.state_bytes_share'}
ACCEPTED = {'compiles_in_window', 'setup_cache_misses',
            'tps.slot_occupancy_mean', 'tps.prefill_time_share',
            'tps.decode_step_ms_p50', 'tps.decode_host_gap_ms',
            'tps.device_idle_share', 'tps.paged_gqa_kernel_time_share',
            'tps.paged_gqa_kernel_roofline', 'tps.prefill_flash_time_share',
            'tps.prefill_flash_roofline'}
PEAKS = {'bf16_flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9}


@pytest.fixture(scope='module')
def man():
    return manifest.Manifest(REPO)


@pytest.fixture(scope='module')
def cfg(man):
    return man.config(man.cell(CELL))


def test_the_manifest_takes_the_new_configuration_and_cell(man):
    assert man.check() is True
    cell = man.cell(CELL)
    assert (cell['config'], cell['traffic'], cell['chips']) == (
        CONFIG, 'chat-sharegpt-closed-128', 1)
    assert len(cell['why']) <= 200 and '4.8 GB of state' in cell['why']
    assert len(man.configs[CONFIG]['why']) <= 200
    assert len(man.doc['workloads']) == 7
    assert sum(w['chips'] == 4 for w in man.doc['workloads']) == 1
    ends = {m['name'] for m in man.cell_metrics(CELL, 'end_to_end')}
    assert ends == {'serve_tokens_per_s_chip', 'setup_s'}
    layers = {m['name'] for m in man.cell_metrics(CELL, 'per_layer')}
    assert layers == NEW | ACCEPTED
    by_name = {m['name']: m for m in man.doc['per_layer']}
    for name in NEW:                    # this PR's: the new cell only
        m = by_name[name]
        assert m['workloads'] == [CELL] and m['unit'] == '%'
        assert m['moves'] == 'serve_tokens_per_s_chip'
        assert set(m) == {'name', 'unit', 'better', 'source', 'layer',
                          'moves', 'workloads'}
    for name in ACCEPTED:               # appended to, and last
        assert by_name[name]['workloads'][-1] == CELL
        assert len(by_name[name]['workloads']) >= 2
    assert by_name['tps.state_bytes_share']['source'] == 'program_counter'
    assert {by_name[n]['source'] for n in NEW - {'tps.state_bytes_share'}
            } == {'device_trace'}


@pytest.mark.parametrize('metric', sorted(NEW | ACCEPTED))
def test_every_metric_of_the_cell_has_its_file_and_reader(man, metric):
    spec = man.metric_spec(metric)
    reader = manifest.load_module('readers', spec['reader'])
    # a program or a trace with nothing to read gives no number, not an
    # error (the parent, under this PR's benchmark files)
    if metric not in ('compiles_in_window', 'setup_cache_misses'):
        assert reader.read(spec.get('params', {}), {'shape': {}}, None) is None


def test_the_configuration_is_the_published_one_but_for_the_context(man, cfg):
    entry = man.configs[CONFIG]
    assert entry['source'] == cfg['source']
    assert 'ibm-granite/granite-4.0-h-micro' in cfg['source']
    assert entry['reduced'] == cfg['reduced'] == ['max_position_embeddings']
    assert set(cfg['reduced_why']) == {'max_position_embeddings'}
    assert cfg['published']['max_position_embeddings'] == 131072
    assert cfg['left_out'] == []
    assert cfg['num_hidden_layers'] == 40 == len(cfg['layer_types'])
    assert [i for i, t in enumerate(cfg['layer_types'])
            if t == 'attention'] == [5, 15, 25, 35]
    assert cfg['vocab_size'] == 100352 and cfg['tie_word_embeddings']
    assert cfg['num_local_experts'] == 0
    assert cfg['position_embedding_type'] == 'nope'
    assert cfg['attention_multiplier'] == 1 / 64
    eng = cfg['engine']
    assert eng['num_slots'] == 64 and eng['page_size'] == 128
    assert eng['num_pages'] == {'kv': eng['num_slots'] * cfg[
        'max_position_embeddings'] // eng['page_size'] + 1}
    assert eng['prefix_cache'] is False and eng['temperature'] == 0.0
    assert cfg['prefill_bodies'][-1] == eng['prefill_width']
    assert cfg['program'] == {'dtype': 'bfloat16', 'param_dtype': 'bfloat16',
                              'state_dtype': 'float32'}
    assert cfg['controls'] == {
        'int8_weights': {'weights': 'int8_per_channel'},
        'bfloat16_state': {'state_dtype': 'bfloat16'}}
    assert cfg['control'] == 'int8_weights'
    assert set(cfg['limits']) <= set(cfg['limits_from'])
    for key in ('assumed', 'precision', 'stands_for'):
        assert cfg[key]


def test_every_published_number_is_kept_or_listed_as_reduced(cfg):
    if not os.path.isfile(CATALOG):
        pytest.skip('the catalog of architectures is not on this machine')
    with open(CATALOG) as f:
        row = [r for r in map(json.loads, f)
               if r['name'] == 'granite-4.0-h-micro'][0]
    assert cfg['source'] == row['source_url']
    differ = {k for k, v in row['config'].items() if cfg.get(k) != v}
    assert differ == set(cfg['reduced'])


def test_the_runner_hands_program_and_reference_the_published_shape(cfg):
    runner = manifest.load_module('runners', cfg['runner'])
    shape = runner.model_shape(cfg)
    program = runner.program_config(shape, cfg['program'])
    for key in runner.MODEL_KEYS:
        if key == 'layer_types':
            assert list(program.layer_types) == cfg[key]
        else:
            assert getattr(program, key) == cfg[key], key
    assert program.max_seq_len == 2048 and program.state_dtype == 'float32'
    assert len(program.period) == 10
    facts = runner.facts_shape(shape)
    assert facts['layer_types'].count('full_attention') == 4
    assert facts['layer_types'].count('mamba') == 36
    assert facts['head_dim'] == 64 and facts['sliding_window'] is None
    assert shape['layer_types'] == cfg['layer_types']       # not touched


def test_the_parameters_are_the_3192_million_the_issue_counts(cfg):
    """Counted from the reference's own weights (abstractly: no array is
    made), by kind of layer, and from the program's as the engine holds
    them."""
    import jax
    runner = manifest.load_module('runners', cfg['runner'])
    ref = manifest.load_module('reference', cfg['reference'])
    shape = runner.model_shape(cfg)
    key = jax.random.PRNGKey(0)
    count = lambda tree: sum(                               # noqa: E731
        int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))
    mamba = count(jax.eval_shape(lambda: ref.init_layer(shape, key, 0)))
    attn = count(jax.eval_shape(lambda: ref.init_layer(shape, key, 5)))
    ends = count(jax.eval_shape(lambda: ref.init_ends(shape, key)))
    assert mamba == (2048 * 8512 + 4 * 4352 + 4352 + 4096 * 2048
                     + 3 * 64 + 4096 + 2 * 2048 + 2048 * 16384
                     + 8192 * 2048)
    assert round(mamba / 1e6, 1) == 76.2 and round(attn / 1e6, 1) == 60.8
    assert round(ends / 1e6, 1) == 205.5
    total = 36 * mamba + 4 * attn + ends
    assert abs(total / 1e6 - 3192) < 1      # 3,191.4 M: the issue's, rounded
    program = runner.program_config(shape, cfg['program'])
    held = jax.eval_shape(lambda: runner.program_params(ref, shape, program,
                                                        key))
    assert count(held) == total
    by_dtype = {}
    for a in jax.tree_util.tree_leaves(held):
        by_dtype[a.dtype.name] = by_dtype.get(a.dtype.name, 0) + int(
            np.prod(a.shape)) * a.dtype.itemsize
    assert round(by_dtype['bfloat16'] / 1e9, 2) == 6.38
    assert by_dtype['float32'] < 6e6        # gains, conv, a head's scalars


def test_the_mix_fits_the_context_and_the_prefill(man, cfg):
    tr = man.traffic(man.cell(CELL))
    gen = manifest.load_module('generators', tr['generator'])
    p = tr['params']
    assert p['clients'] == 2 * cfg['engine']['num_slots'] == 128
    assert p['lead_in_finished'] == cfg['engine']['num_slots']
    context, vocab = cfg['max_position_embeddings'], cfg['vocab_size']
    assert p['prompt']['hi'] + p['answer']['hi'] == 1788 <= context
    assert p['prompt']['hi'] <= cfg['engine']['prefill_width']
    a = gen.make(p, 3000000019, vocab, context, 30.0)   # past 31 bits
    plen = np.array([len(x) for x in a['prompts']])
    assert int(np.max(plen + np.array(a['max_new']))) <= 1788
    assert max(int(np.max(x)) for x in a['prompts']) < vocab
    assert max(int(np.max(x)) for x in a['prompts']) > 0.99 * vocab
    # which body a prompt runs: most the narrowest
    assert np.mean(plen <= 128) > 0.5 and np.mean(plen > 512) < 0.05


# ---- the counts files against brute force ----------------------------------

def test_state_update_counts_against_a_brute_force_count():
    k = manifest.load_module('kernels', 'ssm_state_update')
    heads, p, n = 3, 4, 5
    reads = writes = flops = 0
    for _ in range(heads):
        for _ in range(p):
            for _ in range(n):          # S[h, p, n]: read, updated, written
                reads += 4
                writes += 4
                flops += 5
    small = 2 * heads * p * 2 + 2 * n * 2 + heads * 4
    assert k.call_cost(1, heads, p, n) == (flops, reads + writes + small)
    assert k.call_cost(7, heads, p, n) == (7 * flops,
                                          7 * (reads + writes + small))
    # the published sizes: 4.2 MB a sequence a layer, the bytes bind
    f, b = k.call_cost(1, 64, 64, 128)
    assert b == 2 * 64 * 64 * 128 * 4 + 2 * 4096 * 2 + 2 * 128 * 2 + 64 * 4
    least = k.least_seconds(64 * 10, 36, 64, 64, 128, PEAKS)
    assert least['bound'] == 'memory'
    assert least['seconds'] == pytest.approx(36 * 640 * b / 819e9)
    # the issue's arithmetic: 9.66 GB a step of 64 slots, 11.8 ms
    step = k.least_seconds(64, 36, 64, 64, 128, PEAKS)['seconds']
    assert step == pytest.approx(11.8e-3, rel=0.01)


@pytest.mark.parametrize('rows,chunk', [(5, 4), (8, 4), (3, 8), (13, 5)])
def test_chunked_scan_counts_against_a_brute_force_count(rows, chunk):
    k = manifest.load_module('kernels', 'ssm_chunked_scan')
    heads, p, n = 2, 3, 4
    flops = 0
    for t in range(rows):
        first = t - t % chunk               # its chunk's first row
        for s in range(first, t + 1):       # the rows of its chunk it sees
            flops += 2 * n                  # C_t . B_s
            flops += 2 * heads * p          # that weight times dt x_s
        flops += 2 * heads * p * n          # row t into its chunk's state
        if first > 0:
            flops += 2 * heads * p * n      # the state before the chunk, by C_t
    f, b = k.call_cost(rows, heads, p, n, chunk)
    assert f == flops
    assert b == (2 * rows * heads * p * 2 + 2 * rows * n * 2
                 + rows * heads * 4 + heads * p * n * 4)
    assert sum(k.chunks_of(rows, chunk)) == rows


def test_chunked_scan_takes_the_longer_bound_a_call():
    k = manifest.load_module('kernels', 'ssm_chunked_scan')
    f, b = k.call_cost(161, 64, 64, 128, 256)
    assert f / 197e12 < b / 819e9           # a short prompt: the bytes bind
    least = k.least_seconds([161], 36, 64, 64, 128, 256, PEAKS)
    assert least == {'seconds': pytest.approx(36 * b / 819e9),
                     'bound': 'memory'}
    # at the published sizes and chunks of 256 the bytes bind at every
    # length the mix offers; one chunk of 2,048 rows would be the products'
    f2, b2 = k.call_cost(768, 64, 64, 128, 256)
    assert f2 / 197e12 < b2 / 819e9
    f3, b3 = k.call_cost(2048, 64, 64, 128, 2048)
    assert f3 / 197e12 > b3 / 819e9
    both = k.least_seconds([161, 2048], 36, 64, 64, 128, 2048, PEAKS)
    assert both == {'seconds': pytest.approx(
        36 * (b / 819e9 + f3 / 197e12)), 'bound': 'compute'}


def test_the_new_readers_on_hand_made_facts(man, cfg, monkeypatch):
    """Each reader's arithmetic with the trace stubbed: one second of the
    kernel (of the scope) on one device."""
    from benchmark.harness import device, trace, xplane
    runner = manifest.load_module('runners', cfg['runner'])
    facts = {'shape': runner.facts_shape(runner.model_shape(cfg)),
             'device_kind': 'x', 'page_rows': 128,
             'paged_rows_in_trace': [500] * 640,
             'prefill_rows_in_trace': [161, 768],
             'state_bytes_held': 3.0, 'state_and_page_bytes_held': 4.0}
    monkeypatch.setattr(device, 'peaks', lambda kind: PEAKS)
    monkeypatch.setattr(xplane, 'load', lambda reduced: {
        'ops': {0: [['%x = ', 0, 1]]}, 'devices': 1, 'busy_s': 4.0,
        'self': {0: [('%a', 1e9, 'jit(prefill)/granite.block/ssm/scan/dot'),
                     ('%b', 5e8, 'jit(step)/granite.block/ssm/state_update'),
                     ('%c', 5e8, 'jit(step)/granite.block/attn/dot'),
                     ('%d', 2e9, 'jit(step)/granite.block/mlp/dot')]}})
    monkeypatch.setattr(trace, 'matching_time', lambda ev, pat: (1.0, 1))

    def read(name, facts=facts):
        spec = man.metric_spec(name)
        reader = manifest.load_module('readers', spec['reader'])
        return reader.read(spec['params'], facts, {})
    update = manifest.load_module('kernels', 'ssm_state_update')
    scan = manifest.load_module('kernels', 'ssm_chunked_scan')
    paged = manifest.load_module('kernels', 'paged_gqa_attention')
    flash = manifest.load_module('kernels', 'flash_window_fwd')
    assert read('tps.ssm_state_update_roofline') == pytest.approx(
        100 * update.least_seconds(640, 36, 64, 64, 128, PEAKS)['seconds'])
    assert read('tps.ssm_scan_roofline') == pytest.approx(
        100 * scan.least_seconds([161, 768], 36, 64, 64, 128, 256,
                                 PEAKS)['seconds'])
    assert read('tps.state_bytes_share') == 75.0
    assert read('tps.ssm_layer_time_share') == pytest.approx(37.5)
    assert read('tps.ssm_scan_time_share') == pytest.approx(25.0)
    assert read('tps.hybrid_attn_time_share') == pytest.approx(12.5)
    assert read('tps.hybrid_mlp_time_share') == pytest.approx(50.0)
    # the accepted readers hold for this family's shapes as they stand:
    # four layers that attend everything, heads of 64, 4 : 1
    assert read('tps.paged_gqa_kernel_roofline') == pytest.approx(
        100 * paged.least_seconds([500] * 640, 4, 32, 8, 64, None,
                                  PEAKS)['seconds'])
    assert read('tps.prefill_flash_roofline') == pytest.approx(
        100 * flash.least_seconds([161, 768], 4, 0, 32, 8, 64, None,
                                  PEAKS)['seconds'])
    bare = {'shape': facts['shape'], 'device_kind': 'x'}
    for name in ('tps.ssm_state_update_roofline', 'tps.ssm_scan_roofline',
                 'tps.state_bytes_share'):
        assert read(name, bare) is None
    monkeypatch.setattr(trace, 'matching_time', lambda ev, pat: (0.0, 0))
    assert read('tps.ssm_state_update_roofline') is None


# ---- a tiny cell of the family, end to end on the CPU ----------------------

def drive(tmp_path, *args):
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, 'drive_granite.py'),
         str(tmp_path), *args], capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS='cpu'), timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith('{')]
    return lines[-1], {c['name']: c for c in lines
                       if c.get('phase') == 'compared'}, lines


ENERGY = {'logit_err_energy_median', 'logit_err_energy_p99',
          'logit_err_energy_max'}
EXACT = {'tokens_not_their_rows_best', 'rows_not_finite',
         'rows_not_one_a_token', 'sampled_requests_unserved',
         'no_row_compared', 'compiles_in_window'}


def test_a_sound_run_of_the_tiny_cell_is_correct(tmp_path):
    last, compared, lines = drive(tmp_path, '3')
    assert last['correct'] is True and last['failed'] == 0
    assert set(compared) == ENERGY | EXACT
    assert set(last['metrics']) == {'serve_tokens_per_s_chip', 'setup_s'}
    window = [ln for ln in lines if ln.get('phase') == 'window'][0]
    state = [ln for ln in lines if ln.get('phase') == 'state'][0]
    # a decode step counts all four slots; a prefill its prompt's rows and
    # the eight chunks its 64-row body ran
    assert state['ssm_state_rows_decode'] == 4 * window['decode_steps']
    assert state['ssm_scan_chunks_prefill'] == 8 * window['prefills']
    assert 0 < state['ssm_state_rows_prefill'] < 64 * window['prefills']
    # four busy slots hold their state whatever their length
    per_slot = 6 * (16 * 128 * 4 + 3 * 160 * 4)
    assert state['state_bytes_held'] == 4 * per_slot
    assert state['state_and_page_bytes_held'] > state['state_bytes_held']
    ref = [ln for ln in lines if ln.get('phase') == 'reference'][0]
    assert ref['rows'] > 50 and ref['logit_err_energy_max'] < 1e-9
    # slots filled before and requests admitted while others decoded
    assert len(ref['by_request']) >= 4
    assert max(r[0] + r[1] for r in ref['by_request']) > 24


def test_an_altered_token_fails_by_its_own_row_only(tmp_path):
    last, compared, _ = drive(tmp_path, '3', '--fault', 'altered_token')
    assert last['correct'] is False
    assert [n for n, c in compared.items() if not c['ok']] == [
        'tokens_not_their_rows_best']


@pytest.mark.parametrize('control,least', [('int8_weights', 1e-5),
                                           ('bfloat16_state', 1e-9)])
def test_a_named_control_fails_by_the_rows_energies(tmp_path, control, least):
    """At float32 the tiny cell tells both controls from the program: the
    int8-rounded weights by four orders of magnitude, the bfloat16 state
    by one (its prefill rows, which pass through no stored state, read as
    the program's)."""
    last, compared, lines = drive(tmp_path, '3', '--control', control)
    assert last['correct'] is False
    assert {n for n, c in compared.items() if not c['ok']} == ENERGY
    assert compared['logit_err_energy_median']['value'] > least
    ref = [ln for ln in lines if ln.get('phase') == 'reference'][0]
    if control == 'bfloat16_state':
        assert ref['energy_median_prefill_rows'] < 1e-12
