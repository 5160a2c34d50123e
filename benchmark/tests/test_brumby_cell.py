"""The configuration brumby-14b-pp5-serve and its cell
serve-brumby-14b-longgen-full: the manifest takes them (BY NAME and by
membership, not by place), the file keeps every published number but the
depth and the served context, its bytes are recomputed from its keys, the
mix's clips are counted, the two new counts files against a hand-worked
case, the new readers on hand-made facts, and a tiny float32 cell of the
same family laid over the copy (drive_brumby.py) and run end to end on the
CPU with no page pool: sound, with each planted fault, and with each named
control."""
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

CONFIG = 'brumby-14b-pp5-serve'
CELL = 'serve-brumby-14b-longgen-full'
CATALOG = '/opt/skills/guides/model-configs/architectures.jsonl'
NEW = {'tps.retention_layer_time_share', 'tps.retention_chunked_time_share',
       'tps.brumby_mlp_time_share', 'tps.brumby_head_time_share',
       'tps.retention_state_update_time_share',
       'tps.retention_state_update_roofline',
       'tps.retention_chunked_roofline'}
ACCEPTED = {'compiles_in_window', 'setup_cache_misses',
            'tps.slot_occupancy_mean', 'tps.prefill_time_share',
            'tps.decode_step_ms_p50', 'tps.decode_host_gap_ms',
            'tps.device_idle_share'}
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
        CONFIG, 'longgen-closed-32', 1)
    assert len(cell['why']) <= 200 and len(man.configs[CONFIG]['why']) <= 200
    ends = {m['name'] for m in man.cell_metrics(CELL, 'end_to_end')}
    assert ends == {'serve_tokens_per_s_chip', 'setup_s'}
    layers = {m['name'] for m in man.cell_metrics(CELL, 'per_layer')}
    assert layers == NEW | ACCEPTED
    by_name = {m['name']: m for m in man.doc['per_layer']}
    for name in NEW:                    # this PR's: the new cell only
        m = by_name[name]
        assert m['workloads'] == [CELL] and m['unit'] == '%'
        assert m['moves'] == 'serve_tokens_per_s_chip'
        assert m['source'] == 'device_trace'
        assert set(m) == {'name', 'unit', 'better', 'source', 'layer',
                          'moves', 'workloads'}
    for name in ACCEPTED:               # a member, among the accepted cells
        assert CELL in by_name[name]['workloads']
        assert len(by_name[name]['workloads']) >= 2
    # and of nothing that reads pages, a flash forward or a row write
    for name, m in by_name.items():
        if name not in NEW | ACCEPTED:
            assert CELL not in m.get('workloads', [CELL]), name


@pytest.mark.parametrize('metric', sorted(NEW | ACCEPTED))
def test_every_metric_of_the_cell_has_its_file_and_reader(man, metric):
    spec = man.metric_spec(metric)
    reader = manifest.load_module('readers', spec['reader'])
    assert callable(reader.read)
    # a program without the span, counter or kernel: nothing, and no raise
    assert reader.read(spec.get('params', {}), {}, None) is None


def test_the_configuration_is_the_published_one_but_for_depth_and_context(
        man, cfg):
    entry = man.configs[CONFIG]
    assert entry['source'] == cfg['source']
    assert 'manifestai/Brumby-14B-Base' in cfg['source']
    assert entry['reduced'] == cfg['reduced'] == [
        'num_hidden_layers', 'max_position_embeddings']
    assert set(cfg['reduced_why']) == set(cfg['reduced'])
    assert cfg['published']['max_position_embeddings'] == 32768
    assert cfg['published']['num_hidden_layers'] == 40
    assert (cfg['num_hidden_layers'], cfg['max_position_embeddings']) == (
        8, 3072)
    assert (cfg['hidden_size'], cfg['intermediate_size'], cfg['head_dim'],
            cfg['num_attention_heads'], cfg['num_key_value_heads'],
            cfg['vocab_size'], cfg['rope_theta']) == (
                5120, 17408, 128, 40, 8, 151936, 1000000)
    assert cfg['tie_word_embeddings'] is False and cfg['left_out'] == []
    eng = cfg['engine']
    assert eng['num_slots'] == 16 and eng['prefill_width'] == 1024
    assert 'num_pages' not in eng
    assert eng['prefix_cache'] is False and eng['temperature'] == 0.0
    assert cfg['program'] == {'dtype': 'bfloat16', 'param_dtype': 'bfloat16',
                              'state_dtype': 'float32'}
    assert cfg['controls'] == {
        'int8_weights': {'weights': 'int8_per_channel'},
        'bfloat16_state': {'state_dtype': 'bfloat16'}}
    assert cfg['control'] == 'int8_weights'
    assert set(cfg['limits']) <= set(cfg['limits_from'])
    told = ' '.join(cfg['assumed'])
    for said in ('degree is 2', 'logsigmoid', 'normaliser', 'RMSNorms',
                 'rotary', 'eps', 'float32 state', '[16, 4,096]',
                 '16 slots'):
        assert said in told, said
    for key in ('precision', 'stands_for'):
        assert cfg[key]


def test_every_published_number_is_kept_or_listed_as_reduced(cfg):
    if not os.path.isfile(CATALOG):
        pytest.skip('the catalog of architectures is not on this machine')
    with open(CATALOG) as f:
        row = [r for r in map(json.loads, f)
               if r['name'] == 'Brumby-14B-Base'][0]
    assert cfg['source'] == row['source_url']
    differ = {k for k, v in row['config'].items() if cfg.get(k) != v}
    assert differ == set(cfg['reduced'])


def test_the_runner_hands_program_and_reference_the_published_shape(cfg):
    runner = manifest.load_module('runners', cfg['runner'])
    shape = runner.model_shape(cfg)
    program = runner.program_config(shape, cfg['program'])
    for key in runner.MODEL_KEYS:
        assert getattr(program, key) == cfg[key], key
    assert program.max_seq_len == 3072 and program.state_dtype == 'float32'
    assert program.group == 5
    assert shape['layer_types'] == ['retention'] * 8


def test_the_bytes_are_recomputed_from_the_keys(cfg):
    """Counted from the reference's own weights (abstractly: no array is
    made) and from the program's pool: 330.4 M a layer, 8.40 GB of weights,
    274.8 MB of state a slot, 4.40 GB for 16."""
    import jax
    runner = manifest.load_module('runners', cfg['runner'])
    ref = manifest.load_module('reference', cfg['reference'])
    shape = runner.model_shape(cfg)
    key = jax.random.PRNGKey(0)
    count = lambda tree: sum(                               # noqa: E731
        int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))
    layer = count(jax.eval_shape(lambda: ref.init_layer(shape, key, 0)))
    ends = count(jax.eval_shape(lambda: ref.init_ends(shape, key)))
    assert layer == (5120 * (5120 + 1024 + 1024 + 8) + 5120 * 5120
                     + 3 * 5120 * 17408 + 2 * 5120 + 2 * 128 + 8)
    assert round(layer / 1e6, 1) == 330.4
    assert ends == 2 * 151936 * 5120 + 5120
    program = runner.program_config(shape, cfg['program'])
    held = jax.eval_shape(lambda: runner.program_params(ref, shape, program,
                                                        key))
    assert count(held) == 8 * layer + ends
    by_dtype = {}
    for a in jax.tree_util.tree_leaves(held):
        by_dtype[a.dtype.name] = by_dtype.get(a.dtype.name, 0) + int(
            np.prod(a.shape)) * a.dtype.itemsize
    assert round(by_dtype['bfloat16'] / 1e9, 2) == 8.40
    assert by_dtype['float32'] < 1e6            # gains and the gate's bias
    assert '8.40 GB' in cfg['stands_for']
    from paddle_tpu.models import brumby
    pool = jax.eval_shape(lambda: brumby.init_pool(
        program, {'state': cfg['engine']['num_slots']}, 128))
    state = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                for a in pool.values())
    assert state == 16 * 8 * 8 * 129 * 8320 * 4
    assert round(state / 16 / 1e6, 1) == 274.8 and '274.8 MB' in cfg[
        'stands_for']
    assert round(state / 1e9, 2) == 4.40 and '4.40 GB' in cfg['stands_for']
    assert (by_dtype['bfloat16'] + state) / 16e9 > 0.79     # of the chip


def test_the_mix_fits_the_context_and_its_clips_are_counted(man, cfg):
    tr = man.traffic(man.cell(CELL))
    gen = manifest.load_module('generators', tr['generator'])
    p = tr['params']
    assert p == {
        'loop': 'closed', 'clients': 32, 'lead_in_finished': 16,
        'requests': 1536,
        'prompt': {'dist': 'normal', 'mean': 512, 'stddev': 128, 'lo': 128,
                   'hi': 1024},
        'answer': {'dist': 'exponential', 'mean': 1200, 'lo': 16,
                   'hi': 2048}}
    assert tr['trace_seconds'] == 4.0
    assert p['clients'] == 2 * cfg['engine']['num_slots']
    assert p['lead_in_finished'] == cfg['engine']['num_slots']
    context, vocab = cfg['max_position_embeddings'], cfg['vocab_size']
    assert p['prompt']['hi'] + p['answer']['hi'] == 3072 == context
    assert p['prompt']['hi'] == cfg['engine']['prefill_width']
    a = gen.make(p, 3000000019, vocab, context, 30.0)   # past 31 bits
    plen = np.array([len(x) for x in a['prompts']])
    new = np.array(a['max_new'])
    assert len(plen) == 1536 and int(np.max(plen + new)) <= 3072
    assert max(int(np.max(x)) for x in a['prompts']) > 0.99 * vocab
    # the clips: a group of 32 clients takes the 32 quantile midpoints, so
    # no prompt reaches 128 or 1,024; 6 answers of every 32 are cut to
    # 2,048, none by the context
    assert (int(np.min(plen)), int(np.max(plen))) == (236, 788)
    assert sorted(plen[:32]) == sorted(plen[32:64])
    assert float(np.mean(plen)) == 512.0
    assert float(np.mean(new == 2048)) == 6 / 32
    assert abs(float(np.mean(new)) - 982) < 1
    assert int(np.max(plen + new)) == 2836
    for said in ('236 to 788', '18.75 %', '2,836'):
        assert said in tr['clipped_by_the_context'], said
    # tokens decoded of all tokens served: a prefill serves one
    assert float(np.sum(new - 1)) / float(np.sum(new)) > 0.998


# ---- the counts files against a hand-worked case ---------------------------

def test_state_update_counts_a_hand_worked_case():
    """One sequence, one KV head of 4 read by 2 query heads: D = 10, S and
    z 10 x 5 = 50 floats read and written; q, y 2 x 4, k, v 4 bfloat16; one
    gate."""
    k = manifest.load_module('kernels', 'retention_state_update')
    assert k.features(4) == 10 and k.features(128) == 8256
    flops, byts = k.call_cost(1, 1, 2, 4)
    assert byts == 2 * 50 * 4 + 2 * 8 * 2 + 2 * 4 * 2 + 4
    assert flops == (3 + 2 * 2) * 50
    f3, b3 = k.call_cost(3, 1, 2, 4)
    assert (f3, b3) == (3 * flops, 3 * byts)
    # the published head: 8 KV heads x 8,256 x 129 x 4 B x 2 = 68.2 MB a
    # sequence a layer, bound by bytes
    _, byts = k.call_cost(1, 8, 5, 128)
    assert abs(byts - 2 * 8 * 8256 * 129 * 4) < 30000
    least = k.least_seconds(16, 8, 8, 5, 128, PEAKS)
    assert least['bound'] == 'memory'
    assert least['seconds'] == pytest.approx(16 * 8 * byts / 819e9)
    assert 0.0105 < least['seconds'] < 0.0108       # 10.7 ms a full step


def test_chunked_counts_a_hand_worked_case():
    """8 rows of one KV head of 4 read by 2 query heads, D = 10, a state
    of 50: a row reads the rows up to itself by the attention form (16 t)
    up to row 6 and by the state's form (100) from row 7, whatever chunks
    a program takes them in."""
    k = manifest.load_module('kernels', 'retention_chunked')
    flops, byts = k.call_cost(8, 1, 2, 4)
    read = 16 * (1 + 2 + 3 + 4 + 5 + 6) + 2 * 100
    assert flops == 2 * read + 2 * 8 * 50
    assert byts == (2 * 8 * 8 * 2 + 2 * 8 * 4 * 2 + 8 * 4 + 50 * 4)
    # at the published head the attention form is the cheaper up to 4,160
    # rows: a prompt of 1,024 is 2 x 2 x 128 x (1,024 x 1,025 / 2) a query
    # head and its state
    f, b = k.call_cost(1024, 8, 5, 128)
    assert f == 8 * (5 * 512 * 524800 + 2 * 1024 * 8256 * 129)
    # the longer bound a call: a long prompt by its products, a one-row
    # prompt by the state it leaves
    long = k.least_seconds([1024], 8, 8, 5, 128, PEAKS)
    short = k.least_seconds([1], 8, 8, 5, 128, PEAKS)
    assert (long['bound'], short['bound']) == ('compute', 'memory')
    assert long['seconds'] == pytest.approx(8 * f / 197e12)
    assert short['seconds'] == pytest.approx(
        8 * k.call_cost(1, 8, 5, 128)[1] / 819e9)
    both = k.least_seconds([1024, 1], 8, 8, 5, 128, PEAKS)
    assert both['seconds'] == pytest.approx(long['seconds']
                                            + short['seconds'])


def test_the_new_readers_on_hand_made_facts(man, cfg, monkeypatch):
    """Each reader's arithmetic with the trace stubbed: one second of the
    kernel (of the scope) on one device."""
    from benchmark.harness import device, trace, xplane
    runner = manifest.load_module('runners', cfg['runner'])
    facts = {'shape': runner.model_shape(cfg),
             'device_kind': 'x', 'paged_rows_in_trace': [700] * 640,
             'prefill_rows_in_trace': [300, 1024]}
    monkeypatch.setattr(device, 'peaks', lambda kind: PEAKS)
    path = 'jit(step)/while/body/closed_call/brumby.block/retention/'
    monkeypatch.setattr(xplane, 'load', lambda reduced: {
        'ops': {0: [['%x = ', 0, 1]]}, 'devices': 1, 'busy_s': 4.0,
        'self': {0: [('%a', 1e9, 'jit(prefill)/brumby.block/retention/'
                      'chunked/dot'),
                     ('%b', 5e8, path + 'state_update/pallas_call'),
                     ('%c', 5e8, 'jit(step)/brumby.head/dot'),
                     ('%d', 2e9, 'jit(step)/brumby.block/mlp/dot')]}})
    monkeypatch.setattr(trace, 'matching_time', lambda ev, pat: (1.0, 1))

    def read(name, facts=facts):
        spec = man.metric_spec(name)
        reader = manifest.load_module('readers', spec['reader'])
        return reader.read(spec['params'], facts, {})
    update = manifest.load_module('kernels', 'retention_state_update')
    chunked = manifest.load_module('kernels', 'retention_chunked')
    assert read('tps.retention_state_update_roofline') == pytest.approx(
        100 * update.least_seconds(640, 8, 8, 5, 128, PEAKS)['seconds'])
    assert read('tps.retention_chunked_roofline') == pytest.approx(
        100 * chunked.least_seconds([300, 1024], 8, 8, 5, 128,
                                    PEAKS)['seconds'])
    assert read('tps.retention_layer_time_share') == pytest.approx(37.5)
    assert read('tps.retention_chunked_time_share') == pytest.approx(25.0)
    assert read('tps.brumby_head_time_share') == pytest.approx(12.5)
    assert read('tps.brumby_mlp_time_share') == pytest.approx(50.0)
    bare = {'shape': facts['shape'], 'device_kind': 'x'}
    for name in ('tps.retention_state_update_roofline',
                 'tps.retention_chunked_roofline'):
        assert read(name, bare) is None
    monkeypatch.setattr(trace, 'matching_time', lambda ev, pat: (0.0, 0))
    assert read('tps.retention_state_update_roofline') is None
    # a program without the family's scopes: no share of them
    monkeypatch.setattr(xplane, 'load', lambda reduced: {
        'ops': {}, 'devices': 1, 'busy_s': 4.0,
        'self': {0: [('%d', 2e9, 'jit(step)/gpt.block/mlp/dot')]}})
    none = {'events': {}, 'devices': 1, 'busy_s': 4.0}
    for name in sorted(NEW):
        spec = man.metric_spec(name)
        reader = manifest.load_module('readers', spec['reader'])
        assert reader.read(spec['params'], facts, none) is None, name


# ---- a tiny cell of the family, end to end on the CPU ----------------------

def drive(tmp_path, *args):
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, 'drive_brumby.py'),
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
    warm = [ln for ln in lines if ln.get('done') == 'warmup'][0]
    assert warm['num_pages'] == 0 and state['pages'] == [0, 0]
    # a decode step counts all four slots; a prefill its prompt's rows and
    # the chunks of 8 rows its width ran
    assert state['retention_state_rows_decode'] == 4 * window['decode_steps']
    assert window['prefills'] <= state['retention_chunks_prefill'] <= (
        8 * window['prefills'])
    assert 0 < state['retention_state_rows_prefill'] <= (
        8 * state['retention_chunks_prefill'])
    # four busy slots hold their state whatever their length
    per_slot = 2 * 2 * (16 + 1) * 144 * 4
    assert warm['state_bytes_per_slot'] == per_slot
    # (the two ends' mean: a slot may stand free at either instant)
    assert 3 * per_slot <= state['state_bytes_held'] <= 4 * per_slot
    assert state['state_bytes_held'] % (per_slot // 2) == 0
    ref = [ln for ln in lines if ln.get('phase') == 'reference'][0]
    assert ref['rows'] > 50 and ref['logit_err_energy_max'] < 1e-9
    # slots filled before and requests admitted while others decoded
    assert len(ref['by_request']) >= 4
    assert max(r[0] + r[1] for r in ref['by_request']) > 24


@pytest.mark.parametrize('fault', ['gate_dropped', 'state_carried_over',
                                   'normaliser_dropped'])
def test_a_planted_fault_fails_by_the_rows_energies(tmp_path, fault):
    last, compared, _ = drive(tmp_path, '2', '--fault', fault)
    assert last['correct'] is False
    assert {n for n, c in compared.items() if not c['ok']} == ENERGY
    assert compared['logit_err_energy_median']['value'] > 1e-6


def test_an_altered_token_fails_by_its_own_row(tmp_path):
    last, compared, _ = drive(tmp_path, '2', '--fault', 'altered_token')
    assert last['correct'] is False
    assert compared['tokens_not_their_rows_best']['ok'] is False


@pytest.mark.parametrize('control,least', [('int8_weights', 1e-5),
                                           ('bfloat16_state', 1e-7)])
def test_a_named_control_fails_by_the_rows_energies(tmp_path, control, least):
    last, compared, _ = drive(tmp_path, '2', '--control', control)
    assert last['correct'] is False
    assert {n for n, c in compared.items() if not c['ok']} == ENERGY
    assert compared['logit_err_energy_median']['value'] > least
