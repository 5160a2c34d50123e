"""The configuration trinity-large-ep8-serve and its cell
serve-trinity-large-mixedlen-full: the manifest takes them, the file states
its cut, the two new kernel counts against hand-worked cases, the new
readers on hand-made facts, the mix's lengths counted, and a tiny cell of
the same family (a window of 8 rows over pages of 4) laid over the copy
(drive_afmoe.py) and run end to end on the CPU: sound, with a planted
altered token, and with the lower-precision control, each failing
``correct`` by the number meant to catch it."""
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

CONFIG = 'trinity-large-ep8-serve'
CELL = 'serve-trinity-large-mixedlen-full'
CATALOG = '/opt/skills/guides/model-configs/architectures.jsonl'
NEW = {'tps.window_attn_time_share', 'tps.full_attn_time_share',
       'tps.afmoe_moe_time_share', 'tps.paged_window_kernel_time_share',
       'tps.paged_window_kernel_roofline', 'tps.paged_gqa_kernel_time_share',
       'tps.paged_gqa_kernel_roofline', 'tps.prefill_flash_time_share',
       'tps.prefill_flash_roofline', 'tps.window_pages_held_share'}
PEAKS = {'bf16_flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9}


@pytest.fixture(scope='module')
def man():
    return manifest.Manifest(REPO)


def test_the_manifest_takes_the_new_configuration_and_cell(man):
    assert man.check() is True
    cell = man.cell(CELL)
    assert (cell['config'], cell['traffic'], cell['chips']) == (
        CONFIG, 'doc-mixedlen-closed-48', 1)
    assert len(cell['why']) <= 200 and '0.4 rows' in cell['why']
    assert sum(w['chips'] == 4 for w in man.doc['workloads']) <= max(
        1, len(man.doc['workloads']) // 4)
    ends = {m['name'] for m in man.cell_metrics(CELL, 'end_to_end')}
    assert ends == {'serve_tokens_per_s_chip', 'setup_s'}
    layers = {m['name'] for m in man.cell_metrics(CELL, 'per_layer')}
    assert layers == NEW | {
        'compiles_in_window', 'setup_cache_misses',
        'tps.slot_occupancy_mean', 'tps.prefill_time_share',
        'tps.decode_step_ms_p50', 'tps.decode_host_gap_ms',
        'tps.device_idle_share', 'tps.expert_matmul_time_share',
        'tps.expert_matmul_roofline', 'tps.expert_rows_per_call',
        'tps.experts_touched_share'}
    for m in man.doc['per_layer']:
        if m['name'] in NEW:            # this PR's: the new cell only
            assert m['workloads'] == [CELL]
            assert m['moves'] == 'serve_tokens_per_s_chip'


def test_the_configuration_states_its_cut(man):
    entry = man.configs[CONFIG]
    cfg = man.config(man.cell(CELL))
    assert entry['source'] == cfg['source'] and 'Trinity-Large-Preview' in (
        cfg['source'])
    assert entry['reduced'] == cfg['reduced'] == [
        'num_hidden_layers', 'num_dense_layers', 'num_experts', 'vocab_size',
        'max_position_embeddings', 'layer_types']
    assert {k: cfg['published'][k] for k in (
        'num_hidden_layers', 'num_dense_layers', 'expert_layers',
        'num_experts', 'vocab_size', 'max_position_embeddings')} == {
        'num_hidden_layers': 60, 'num_dense_layers': 6, 'expert_layers': 54,
        'num_experts': 256, 'vocab_size': 200192,
        'max_position_embeddings': 262144}
    assert cfg['held'] == {'experts': [0, 32], 'router_width': 256,
                           'vocabulary_rows': [0, 25024],
                           'chips_sharing_a_layer': 8}
    assert '8 chips share each layer' in cfg['stands_for']
    assert cfg['left_out'] == []
    assert set(cfg['reduced_why']) == set(cfg['reduced'])
    # the floors: a whole period and four expert layers in the published
    # 3 : 1 pattern, 8 experts, an eighth of the vocabulary; no width cut
    assert cfg['layer_types'] == ['sliding_attention'] * 4 + [
        'full_attention']
    assert cfg['layer_types'][cfg['num_dense_layers']:] == [
        'sliding_attention'] * 3 + ['full_attention']
    assert cfg['num_hidden_layers'] - cfg['num_dense_layers'] >= 4
    assert cfg['num_experts'] >= 8
    assert cfg['vocab_size'] * 8 >= cfg['published']['vocab_size']
    assert not any(k.endswith(('_dim', '_rank', '_size')) and k != 'vocab_size'
                   for k in cfg['reduced'])
    assert (cfg['sliding_window'], cfg['num_experts_per_tok'],
            cfg['num_attention_heads'], cfg['num_key_value_heads']) == (
        4096, 4, 48, 8)
    # the engine's pool: a window's pages a slot and the trash page
    eng = cfg['engine']
    per_slot = (cfg['sliding_window'] + eng['page_size'] - 2) // eng[
        'page_size'] + 1
    assert eng['num_pages'] == {
        'full': eng['num_slots'] * cfg['max_position_embeddings']
        // eng['page_size'] + 1,
        'window': eng['num_slots'] * per_slot + 1}
    assert cfg['controls'][cfg['control']] == {'weights': 'int8_per_channel'}
    assert set(cfg['limits']) - {'row_energy_bound'} <= set(
        cfg['limits_from'])


def test_every_published_number_is_kept_or_listed_as_reduced(man):
    if not os.path.isfile(CATALOG):
        pytest.skip('the catalog of architectures is not on this machine')
    with open(CATALOG) as f:
        row = [r for r in map(json.loads, f)
               if r['name'] == 'Trinity-Large-Preview'][0]
    cfg = man.config(man.cell(CELL))
    assert cfg['source'] == row['source_url']
    differ = {k for k, v in row['config'].items() if cfg.get(k) != v}
    assert differ == set(cfg['reduced'])


def test_paged_gqa_counts_against_a_hand_worked_case():
    k = manifest.load_module('kernels', 'paged_gqa_attention')
    # one slot of 5,000 keys, 48 query heads on 8 KV heads of 128. A full
    # layer attends all: K and V rows once a KV head, q and out a query head
    flops, byts = k.call_cost([5000], 48, 8, 128)
    assert flops == 2 * 2 * 5000 * 128 * 48
    assert byts == 2 * 5000 * 8 * 128 * 2 + 2 * 48 * 128 * 2
    # a window layer the last 4,096; a slot inside its window all it has
    wf, wb = k.call_cost([5000], 48, 8, 128, window=4096)
    assert wf == 2 * 2 * 4096 * 128 * 48
    assert wb == 2 * 4096 * 8 * 128 * 2 + 2 * 48 * 128 * 2
    assert k.call_cost([300], 48, 8, 128, window=4096) == k.call_cost(
        [300], 48, 8, 128)
    assert k.call_cost([5000, 5000], 48, 8, 128) == (2 * flops, 2 * byts)
    # 12 flops a byte of K and V (6 query heads a KV head), under the
    # chip's 240: the bytes bind
    least = k.least_seconds([5000] * 24, 3, 48, 8, 128, 4096, PEAKS)
    assert least['bound'] == 'memory'
    assert least['seconds'] == pytest.approx(3 * 24 * wb / 819e9)


def test_windowed_flash_forward_counts_against_a_hand_worked_case():
    k = manifest.load_module('kernels', 'flash_window_fwd')
    assert k.scores_kept(100) == 5050
    assert k.scores_kept(100, window=4096) == 5050
    # 10 rows, window 4: rows 0-3 keep 1, 2, 3, 4 and the six after 4 each
    assert k.scores_kept(10, window=4) == 10 + 6 * 4
    flops, byts = k.call_cost(8192, 48, 8, 128, window=4096)
    kept = 4096 * 4097 // 2 + 4096 * 4096
    assert flops == 2 * 2 * kept * 128 * 48
    assert byts == (2 * 8192 * 48 * 128 * 2 + 2 * 8192 * 8 * 128 * 2
                    + 8192 * 48 * 4)
    # the accepted count of a causal forward, 2 S S D a head, to within
    # the diagonal
    ff, _ = k.call_cost(8192, 48, 8, 128)
    assert ff == pytest.approx(2 * 8192 * 8192 * 128 * 48, rel=2e-4)
    least = k.least_seconds([8192], 1, 4, 48, 8, 128, 4096, PEAKS)
    assert least['bound'] == 'compute'
    assert least['seconds'] == pytest.approx((ff + 4 * flops) / 197e12)


def test_the_new_readers_on_hand_made_facts(man, monkeypatch):
    """Each reader's arithmetic with the trace stubbed: one second of the
    kernel on one device; and no number, not an error, where the program
    has no such counter or the trace no such operation (the parent)."""
    from benchmark.harness import device, trace, xplane
    cfg = man.config(man.cell(CELL))
    shape = {k: cfg[k] for k in (
        'layer_types', 'sliding_window', 'num_attention_heads',
        'num_key_value_heads', 'head_dim')}
    facts = {'shape': shape, 'device_kind': 'x', 'page_rows': 128,
             'paged_rows_in_trace': [5000] * 24,
             'prefill_rows_in_trace': [8192],
             'attn_pages_window': 33, 'attn_pages_full': 66}
    monkeypatch.setattr(device, 'peaks', lambda kind: PEAKS)
    monkeypatch.setattr(xplane, 'load', lambda reduced: {
        'ops': {0: [['%x = ', 0, 1]]}, 'devices': 1})
    monkeypatch.setattr(trace, 'matching_time', lambda ev, pat: (1.0, 1))

    def read(name, facts=facts):
        spec = man.metric_spec(name)
        reader = manifest.load_module('readers', spec['reader'])
        return reader.read(spec['params'], facts, {})
    paged = manifest.load_module('kernels', 'paged_gqa_attention')
    flash = manifest.load_module('kernels', 'flash_window_fwd')
    assert read('tps.paged_window_kernel_roofline') == pytest.approx(
        100 * paged.least_seconds([5000] * 24, 4, 48, 8, 128, 4096,
                                  PEAKS)['seconds'])
    assert read('tps.paged_gqa_kernel_roofline') == pytest.approx(
        100 * paged.least_seconds([5000] * 24, 1, 48, 8, 128, None,
                                  PEAKS)['seconds'])
    assert read('tps.prefill_flash_roofline') == pytest.approx(
        100 * flash.least_seconds([8192], 1, 4, 48, 8, 128, 4096,
                                  PEAKS)['seconds'])
    assert read('tps.window_pages_held_share') == 50.0
    bare = {'shape': shape, 'device_kind': 'x'}
    for name in ('tps.paged_window_kernel_roofline',
                 'tps.paged_gqa_kernel_roofline',
                 'tps.prefill_flash_roofline',
                 'tps.window_pages_held_share'):
        assert read(name, bare) is None
    monkeypatch.setattr(trace, 'matching_time', lambda ev, pat: (0.0, 0))
    assert read('tps.paged_window_kernel_roofline') is None
    assert read('tps.prefill_flash_roofline') is None


def test_the_new_mix_is_short_and_long_prompts_in_one_queue(man):
    cell = man.cell(CELL)
    tr, cfg = man.traffic(cell), man.config(cell)
    gen = manifest.load_module('generators', tr['generator'])
    p = tr['params']
    assert p == {
        'loop': 'closed', 'clients': 48, 'lead_in_finished': 24,
        'requests': 1536,
        'prompt': {'dist': 'lognormal', 'median': 3000, 'sigma': 1.1,
                   'lo': 64, 'hi': 15360},
        'answer': {'dist': 'exponential', 'mean': 182, 'lo': 4, 'hi': 1020}}
    assert p['clients'] == 2 * cfg['engine']['num_slots']
    context, vocab = cfg['max_position_embeddings'], cfg['vocab_size']
    assert p['prompt']['hi'] + p['answer']['hi'] <= context
    assert p['prompt']['hi'] <= cfg['engine']['prefill_width']
    a, b = (gen.make(p, seed, vocab, context, 30.0) for seed in (1, 2))
    plen = np.array([len(x) for x in a['prompts']])
    assert len(plen) == 1536 and a['loop'] == 'closed'
    want = np.tile(gen.quantile_lengths(p['answer'], 48), 32)
    assert sorted(a['max_new']) == sorted(want)         # none cut
    assert int(np.max(plen + np.array(a['max_new']))) <= 15360 + 831
    # the shares the issue states, by the 48 quantile midpoints
    assert np.mean(plen) == pytest.approx(4627, abs=1)
    assert np.mean(plen < 1024) == pytest.approx(8 / 48)
    assert np.mean(plen > cfg['sliding_window']) == pytest.approx(19 / 48)
    assert np.mean(plen == 15360) == pytest.approx(3 / 48)
    assert int(np.min(plen)) > 64
    assert np.mean(a['max_new']) == pytest.approx(182, rel=0.01)
    assert max(a['max_new']) == 831
    assert max(int(np.max(x)) for x in a['prompts']) < vocab
    # every seed offers the same set of lengths in another order
    assert sorted(plen) == sorted(len(x) for x in b['prompts'])
    assert list(plen) != [len(x) for x in b['prompts']]


# ---- a tiny cell of the family, end to end on the CPU ----------------------

def drive(tmp_path, *args):
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, 'drive_afmoe.py'),
         str(tmp_path), *args], capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS='cpu'), timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith('{')]
    return lines[-1], {c['name']: c for c in lines
                       if c.get('phase') == 'compared'}, lines


ENERGY = {'logit_err_energy_median', 'logit_err_energy_p90',
          'rows_beyond_bound_share'}
EXACT = {'tokens_not_their_rows_best', 'rows_not_finite',
         'rows_not_one_a_token', 'sampled_requests_unserved',
         'no_row_compared', 'compiles_in_window'}


def test_a_sound_run_of_the_tiny_cell_is_correct(tmp_path):
    last, compared, lines = drive(tmp_path, '3')
    assert last['correct'] is True and last['failed'] == 0
    assert set(compared) == ENERGY | EXACT
    assert set(last['metrics']) == {'serve_tokens_per_s_chip', 'setup_s'}
    window = [ln for ln in lines if ln.get('phase') == 'window'][0]
    moe = window['moe']
    # four routed layers of four held experts a call; every decode step
    # offers 4 slots x 4 choices to each
    assert moe['decode']['expert_calls'] == 4 * 4 * moe['decode']['runs']
    assert moe['decode']['rows_offered'] == 4 * 16 * moe['decode']['runs']
    assert moe['prefill']['runs'] == window['prefills'] > 0
    att = [ln for ln in lines if ln.get('phase') == 'attention'][0]
    # window layers attended fewer keys and held fewer pages than the full
    # one, and what left the window was given back
    assert 0 < att['attn_keys_window'] < att['attn_keys_full']
    assert 0 < att['attn_pages_window'] < att['attn_pages_full']
    assert att['window_pages_released'] > 0
    ref = [ln for ln in lines if ln.get('phase') == 'reference'][0]
    assert ref['rows'] > 50 and ref['logit_err_energy_max'] < 1e-9
    # rows several windows deep were among those compared
    assert ref['rows_past_window'] > 20
    assert max(r[0] + r[1] for r in ref['by_request']) > 4 * 8


def test_an_altered_token_fails_by_its_own_row_only(tmp_path):
    last, compared, _ = drive(tmp_path, '3', '--fault', 'altered_token')
    assert last['correct'] is False
    assert [n for n, c in compared.items() if not c['ok']] == [
        'tokens_not_their_rows_best']


def test_the_int8_rounded_control_fails_by_the_rows_energies(tmp_path):
    last, compared, _ = drive(tmp_path, '3', '--control')
    assert last['correct'] is False
    assert {n for n, c in compared.items() if not c['ok']} == ENERGY
    assert compared['logit_err_energy_median']['value'] > 1e-5
