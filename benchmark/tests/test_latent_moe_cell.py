"""The configuration dots-vlm1-ep16-serve and its cell
serve-dots-vlm1-sharegpt-full: the manifest takes them, the file states its
cut, the two new kernels' counts against hand-worked cases, the new mix's
lengths counted, and a tiny cell of the same family laid over the copy
(drive_latent.py) and run end to end on the CPU: sound, with a planted
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

CONFIG = 'dots-vlm1-ep16-serve'
CELL = 'serve-dots-vlm1-sharegpt-full'
CATALOG = '/opt/skills/guides/model-configs/architectures.jsonl'


@pytest.fixture(scope='module')
def man():
    return manifest.Manifest(REPO)


def test_the_manifest_takes_the_new_configuration_and_cell(man):
    assert man.check() is True
    cell = man.cell(CELL)
    assert (cell['config'], cell['traffic'], cell['chips']) == (
        CONFIG, 'chat-sharegpt-closed-128', 1)
    assert sum(w['chips'] == 4 for w in man.doc['workloads']) <= max(
        1, len(man.doc['workloads']) // 4)
    ends = {m['name'] for m in man.cell_metrics(CELL, 'end_to_end')}
    assert ends == {'serve_tokens_per_s_chip', 'setup_s'}
    layers = {m['name'] for m in man.cell_metrics(CELL, 'per_layer')}
    assert layers == {
        'compiles_in_window', 'setup_cache_misses',
        'tps.slot_occupancy_mean', 'tps.prefill_time_share',
        'tps.decode_step_ms_p50', 'tps.decode_host_gap_ms',
        'tps.device_idle_share', 'tps.latent_kernel_time_share',
        'tps.latent_kernel_roofline', 'tps.expert_matmul_time_share',
        'tps.expert_matmul_roofline', 'tps.latent_attn_time_share',
        'tps.moe_time_share', 'tps.moe_dispatch_time_share',
        'tps.latent_pool_copy_time_share', 'tps.expert_rows_per_call',
        'tps.experts_touched_share'}
    for m in man.doc['per_layer'][-10:]:      # this PR's: the new cell only
        assert m['workloads'] == [CELL]
        assert m['moves'] == 'serve_tokens_per_s_chip'


def test_the_configuration_states_its_cut(man):
    entry = man.configs[CONFIG]
    cfg = man.config(man.cell(CELL))
    assert entry['source'] == cfg['source'] and 'dots.vlm1.inst' in cfg[
        'source']
    assert entry['reduced'] == cfg['reduced'] == [
        'num_hidden_layers', 'first_k_dense_replace', 'n_routed_experts',
        'vocab_size', 'max_position_embeddings']
    assert cfg['published'] == {
        'num_hidden_layers': 61, 'first_k_dense_replace': 3,
        'expert_layers': 58, 'n_routed_experts': 256, 'vocab_size': 129280,
        'max_position_embeddings': 163840}
    assert cfg['held'] == {'experts': [0, 16], 'router_width': 256,
                           'vocabulary_rows': [0, 16160],
                           'chips_sharing_a_layer': 16}
    assert '16 chips share each layer' in cfg['stands_for']
    assert len(cfg['left_out']) == 2 and 'vision tower' in cfg['left_out'][0]
    assert set(cfg['reduced_why']) == set(cfg['reduced'])
    # the floors: a whole period and four expert layers, 8 experts, an
    # eighth of the vocabulary; no width among the keys reduced
    assert cfg['num_hidden_layers'] - cfg['first_k_dense_replace'] >= 4
    assert cfg['n_routed_experts'] >= 8
    assert cfg['vocab_size'] * 8 >= cfg['published']['vocab_size']
    assert not any(k.endswith(('_dim', '_rank', '_size')) and k != 'vocab_size'
                   for k in cfg['reduced'])
    assert cfg['controls'][cfg['control']] == {'weights': 'int8_per_channel'}
    assert set(cfg['limits']) - {'row_energy_bound'} <= set(
        cfg['limits_from'])


def test_every_published_number_is_kept_or_listed_as_reduced(man):
    if not os.path.isfile(CATALOG):
        pytest.skip('the catalog of architectures is not on this machine')
    with open(CATALOG) as f:
        row = [r for r in map(json.loads, f)
               if r['name'] == 'dots.vlm1.inst'][0]
    cfg = man.config(man.cell(CELL))
    assert cfg['source'] == row['source_url']
    differ = {k for k, v in row['config'].items() if cfg.get(k) != v}
    assert differ == set(cfg['reduced'])


def test_latent_kernel_counts_against_a_hand_worked_case():
    k = manifest.load_module('kernels', 'paged_latent_attention')
    # one slot of 130 rows: 2 pages of 128 rows x 576 values x 2 bytes;
    # q 128 x 576 and out 128 x 512, bfloat16
    flops, byts = k.call_cost([130], 128, 512, 64, 128)
    assert flops == 2 * 128 * 130 * (512 + 64 + 512)
    assert byts == 2 * 128 * 1152 + 128 * 576 * 2 + 128 * 512 * 2
    # an idle slot costs nothing but is not listed; two slots add up
    assert k.call_cost([130, 130], 128, 512, 64, 128) == (2 * flops,
                                                          2 * byts)
    peaks = {'bf16_flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9}
    least = k.least_seconds([500] * 64, 5, 128, 512, 64, 128, peaks)
    # 500 rows: 139 MFLOP against 4 pages and q and out, 868 KB: 160 FLOP
    # a byte, under the chip's 240, so the bytes bind
    assert least['bound'] == 'memory'
    assert least['seconds'] == pytest.approx(
        5 * 64 * (4 * 128 * 1152 + 128 * 1152 + 128 * 1024) / 819e9)


def test_grouped_product_counts_against_a_hand_worked_case():
    k = manifest.load_module('kernels', 'expert_grouped_matmul')
    # 32 rows over 14 touched experts at hidden 7168, width 2048
    flops, byts = k.layer_call_cost(32, 14, 7168, 2048)
    assert flops == 3 * 2 * 32 * 7168 * 2048
    one_matrix = 7168 * 2048 * 2
    assert byts == 14 * 3 * one_matrix + 32 * 2 * (
        2 * (7168 + 2048) + (2048 + 7168))
    peaks = {'bf16_flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9}
    least = k.least_seconds(32, 14, 7168, 2048, peaks)
    assert least['bound'] == 'memory'
    assert least['seconds'] == pytest.approx(byts / 819e9)
    # an expert no row met costs nothing
    assert k.layer_call_cost(0, 0, 7168, 2048) == (0, 0)


def test_the_counter_ratio_reader():
    r = manifest.load_module('readers', 'fact_ratio')
    facts = {'a': 30, 'b': 12, 'z': 0}
    assert r.read({'over': ['a', 'b']}, facts, None) == 2.5
    assert r.read({'over': ['a', 'b'], 'scale': 100.0}, facts, None) == 250.0
    assert r.read({'over': ['a', 'z']}, facts, None) is None
    assert r.read({'over': ['a', 'missing']}, facts, None) is None


def test_the_new_mix_lengths_are_the_sharegpt_means_and_fit_the_context(man):
    cell = man.cell(CELL)
    tr, cfg = man.traffic(cell), man.config(cell)
    gen = manifest.load_module('generators', tr['generator'])
    p = tr['params']
    assert (p['loop'], p['clients'], p['lead_in_finished'], p['requests']
            ) == ('closed', 128, 64, 2048)
    assert p['clients'] == 2 * cfg['engine']['num_slots']
    context, vocab = cfg['max_position_embeddings'], cfg['vocab_size']
    # no answer is cut by the context: the clips alone bound a request
    assert p['prompt']['hi'] + p['answer']['hi'] <= context
    a, b = (gen.make(p, seed, vocab, context, 30.0) for seed in (1, 2))
    plen = np.array([len(x) for x in a['prompts']])
    assert len(plen) == 2048 and a['loop'] == 'closed'
    want = np.tile(gen.quantile_lengths(p['answer'], 128), 16)
    assert sorted(a['max_new']) == sorted(want)         # none cut
    assert int(np.max(plen + np.array(a['max_new']))) <= 1788
    assert np.mean(plen) == pytest.approx(161.31, rel=0.03)
    assert np.mean(a['max_new']) == pytest.approx(337.99, rel=0.05)
    assert np.mean(plen == 768) == pytest.approx(0.009, abs=0.004)
    assert max(int(np.max(x)) for x in a['prompts']) < vocab
    # every seed offers the same set of lengths in another order
    assert sorted(plen) == sorted(len(x) for x in b['prompts'])
    assert list(plen) != [len(x) for x in b['prompts']]
    assert p['prompt'] == man.traffic(man.cell('serve-1.3b-chat'))[
        'params']['prompt']


# ---- a tiny cell of the family, end to end on the CPU ----------------------

def drive(tmp_path, *args):
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, 'drive_latent.py'),
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


def test_a_sound_run_of_the_tiny_latent_cell_is_correct(tmp_path):
    last, compared, lines = drive(tmp_path, '3')
    assert last['correct'] is True and last['failed'] == 0
    assert set(compared) == ENERGY | EXACT
    assert set(last['metrics']) == {'serve_tokens_per_s_chip', 'setup_s'}
    window = [ln for ln in lines if ln.get('phase') == 'window'][0]
    moe = window['moe']
    # the program's counters, read at the window's two ends: two routed
    # layers of four held experts a call; every decode step offers
    # 4 slots x 4 choices to each
    assert moe['decode']['expert_calls'] == 2 * 4 * moe['decode']['runs']
    assert moe['decode']['rows_offered'] == 2 * 16 * moe['decode']['runs']
    assert 0 < moe['decode']['rows_held'] < moe['decode']['rows_offered']
    assert moe['prefill']['runs'] == window['prefills'] > 0
    ref = [ln for ln in lines if ln.get('phase') == 'reference'][0]
    assert ref['rows'] > 50 and ref['logit_err_energy_max'] < 1e-9


def test_the_counter_metrics_read_the_runners_window_counts(man):
    """The two metrics of the routed layer's counters, through their
    files: rows that met a held expert, and held experts touched, over
    held experts offered."""
    facts = {'moe_rows_held': 6400, 'moe_expert_calls': 3200,
             'moe_experts_touched': 2800}
    values = {}
    for name in ('tps.expert_rows_per_call', 'tps.experts_touched_share'):
        spec = man.metric_spec(name)
        reader = manifest.load_module('readers', spec['reader'])
        values[name] = reader.read(spec['params'], facts, None)
        assert reader.read(spec['params'], {}, None) is None  # the parent
    assert values == {'tps.expert_rows_per_call': 2.0,
                      'tps.experts_touched_share': 87.5}


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
