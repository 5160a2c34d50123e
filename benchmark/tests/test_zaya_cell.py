"""The configuration zaya1-8b-pp2-serve and its cell
serve-zaya1-8b-reason-full: the manifest takes them (metrics taken BY NAME
and membership in the shared metrics' lists, never "alone" or "last"), the
file keeps every published number but depth and the served context, its
parameters count what the issue counts, the mix's quantile lengths and
clips counted, the kernel-free reference against itself in two block
sizes, the new metrics' readers on hand-made facts, and a tiny cell of the
same family laid over the copy (drive_zaya.py) and run end to end on the
CPU: sound, with each planted fault, and with each named control."""
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

CONFIG = 'zaya1-8b-pp2-serve'
CELL = 'serve-zaya1-8b-reason-full'
CATALOG = '/opt/skills/guides/model-configs/architectures.jsonl'
NEW = {'tps.cca_attn_time_share', 'tps.cca_mix_time_share',
       'tps.zaya_router_time_share', 'tps.zaya_moe_time_share',
       'tps.zaya_head_time_share', 'tps.zaya_pool_write_time_share',
       'tps.moe_skip_rows_share'}
ACCEPTED = {'compiles_in_window', 'setup_cache_misses',
            'tps.slot_occupancy_mean', 'tps.prefill_time_share',
            'tps.decode_step_ms_p50', 'tps.decode_host_gap_ms',
            'tps.device_idle_share', 'tps.expert_matmul_time_share',
            'tps.expert_matmul_roofline', 'tps.expert_rows_per_call',
            'tps.experts_touched_share', 'tps.paged_gqa_kernel_time_share',
            'tps.paged_gqa_kernel_roofline', 'tps.prefill_flash_time_share',
            'tps.prefill_flash_roofline', 'tps.state_bytes_share'}
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
        CONFIG, 'reason-closed-96', 1)
    assert len(cell['why']) <= 200 and '7.0 GB of experts' in cell['why']
    assert len(man.configs[CONFIG]['why']) <= 200
    assert CELL in [w['name'] for w in man.doc['workloads']]
    assert sum(w['chips'] == 4 for w in man.doc['workloads']) == 1
    ends = {m['name'] for m in man.cell_metrics(CELL, 'end_to_end')}
    assert ends == {'serve_tokens_per_s_chip', 'setup_s'}
    layers = {m['name'] for m in man.cell_metrics(CELL, 'per_layer')}
    assert layers == NEW | ACCEPTED
    by_name = {m['name']: m for m in man.doc['per_layer']}
    for name in NEW:                    # this PR's: they list the new cell
        m = by_name[name]
        assert CELL in m['workloads'] and m['unit'] == '%'
        assert m['moves'] == 'serve_tokens_per_s_chip'
        assert set(m) == {'name', 'unit', 'better', 'source', 'layer',
                          'moves', 'workloads'}
    for name in ACCEPTED:               # shared: the new cell AMONG theirs
        assert CELL in by_name[name]['workloads']
        assert len(by_name[name]['workloads']) >= 2
    assert by_name['tps.moe_skip_rows_share']['source'] == 'program_counter'
    assert by_name['tps.moe_skip_rows_share']['layer'] == by_name[
        'tps.expert_rows_per_call']['layer']
    assert {by_name[n]['source'] for n in NEW - {'tps.moe_skip_rows_share'}
            } == {'device_trace'}


@pytest.mark.parametrize('metric', sorted(NEW | ACCEPTED))
def test_every_metric_of_the_cell_has_its_file_and_reader(man, metric):
    spec = man.metric_spec(metric)
    reader = manifest.load_module('readers', spec['reader'])
    # a program or a trace with nothing to read gives no number, not an
    # error (the parent, under this PR's benchmark files)
    if metric not in ('compiles_in_window', 'setup_cache_misses'):
        assert reader.read(spec.get('params', {}), {'shape': {}}, None) is None


def test_the_configuration_is_the_published_one_but_for_depth_and_context(
        man, cfg):
    entry = man.configs[CONFIG]
    assert entry['source'] == cfg['source']
    assert 'Zyphra/ZAYA1-8B' in cfg['source']
    assert entry['reduced'] == cfg['reduced'] == [
        'num_hidden_layers', 'max_position_embeddings']
    assert set(cfg['reduced_why']) == set(cfg['reduced'])
    assert cfg['published']['max_position_embeddings'] == 131072
    assert cfg['published']['num_hidden_layers'] == 40
    assert cfg['left_out'] == []
    assert cfg['num_hidden_layers'] == 20 and len(cfg['layer_types']) == 40
    assert set(cfg['layer_types']) == {'hybrid'}
    assert cfg['vocab_size'] == 262272 and cfg['tie_word_embeddings']
    assert cfg['num_experts'] == 16 == cfg['held']['experts'][1]
    assert cfg['held']['experts'][0] == 0 and cfg['held'][
        'router_width'] == 16
    assert cfg['held']['pipeline_stages'] == 2
    eng = cfg['engine']
    assert eng['num_slots'] == 48 and eng['page_size'] == 128
    assert eng['num_pages'] == {'kv': eng['num_slots'] * cfg[
        'max_position_embeddings'] // eng['page_size'] + 1} == {'kv': 1153}
    assert eng['prefill_width'] == 1024 and eng['queue_capacity'] == 256
    assert eng['prefix_cache'] is False and eng['temperature'] == 0.0
    assert cfg['program'] == {'dtype': 'bfloat16', 'param_dtype': 'bfloat16',
                              'router_dtype': 'float32'}
    assert cfg['controls'] == {
        'int8_weights': {'weights': 'int8_per_channel'},
        'bfloat16_router': {'router_dtype': 'bfloat16'}}
    assert cfg['control'] == 'int8_weights'
    assert set(cfg['limits']) <= set(cfg['limits_from'])
    for key in ('assumed', 'precision', 'stands_for'):
        assert cfg[key]


def test_every_published_number_is_kept_or_listed_as_reduced(cfg):
    if not os.path.isfile(CATALOG):
        pytest.skip('the catalog of architectures is not on this machine')
    with open(CATALOG) as f:
        row = [r for r in map(json.loads, f) if r['name'] == 'ZAYA1-8B'][0]
    assert cfg['source'] == row['source_url']
    differ = {k for k, v in row['config'].items() if cfg.get(k) != v}
    assert differ == set(cfg['reduced'])
    widths = ('hidden_size', 'moe_intermediate_size', 'head_dim',
              'router_hidden_size', 'num_experts_per_tok')
    assert not set(widths) & differ


def test_the_runner_hands_program_and_reference_the_published_shape(cfg):
    runner = manifest.load_module('runners', cfg['runner'])
    shape = runner.model_shape(cfg)
    program = runner.program_config(shape, cfg['program'])
    for key in runner.MODEL_KEYS:
        assert getattr(program, key) == cfg[key], key
    assert program.rope_theta == 5000000 == shape['rope_theta']
    assert program.max_seq_len == 3072 and program.held == (0, 16)
    assert program.router_dtype == 'float32' and program.conv_dim == 1280
    facts = runner.facts_shape(shape)
    assert facts['layer_types'] == ['full_attention'] * 20
    assert facts['head_dim'] == 128 and facts['sliding_window'] is None
    assert (facts['num_attention_heads'], facts['num_key_value_heads']) == (
        8, 2)
    assert 'layer_types' not in shape                       # not touched


def test_the_parameters_are_the_4689_million_the_issue_counts(cfg):
    """Counted from the reference's own weights (abstractly: no array is
    made), and from the program's as the engine holds them."""
    import jax
    runner = manifest.load_module('runners', cfg['runner'])
    ref = manifest.load_module('reference', cfg['reference'])
    shape = runner.model_shape(cfg)
    key = jax.random.PRNGKey(0)
    count = lambda tree: sum(                               # noqa: E731
        int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))
    lp = jax.eval_shape(lambda: ref.init_layer(shape, key, 0))
    attention = count({k: lp[k] for k in ('q', 'k', 'v1', 'v2', 'o', 'conv0',
                                          'conv1', 'temp')})
    assert attention == (2048 * 1024 + 2048 * 256 + 2 * 2048 * 128
                         + 1024 * 2048 + 2 * 1280 + 2 * 10 * 128 * 128 + 2)
    assert round(attention / 1e6, 2) == 5.57
    router = count(lp['router'])
    assert router == 2048 * 256 + 1 + 256 + 2 * 256 * 256 + 256 * 17 + 17
    assert round(router / 1e6, 2) == 0.66
    experts = count(lp['experts'])
    assert experts == 16 * 3 * 2048 * 2048
    layer = count(lp)
    assert layer == attention + router + experts + 2 * 2048 + 2 * 4 * 2048
    assert round(layer / 1e6, 1) == 207.6
    assert round(40 * layer / 1e9, 2) == 8.30               # the published
    active = layer - experts + 3 * 2048 * 2048
    assert abs(40 * active / 1e9 - 0.76) < 0.01             # A0.76B
    ends = count(jax.eval_shape(lambda: ref.init_ends(shape, key)))
    assert round(ends / 1e6, 1) == 537.1
    total = 20 * layer + ends
    assert round(total / 1e6) == 4689
    program = runner.program_config(shape, cfg['program'])
    held = jax.eval_shape(lambda: runner.program_params(ref, shape, program,
                                                        key))
    assert count(held) == total
    by_dtype = {}
    for a in jax.tree_util.tree_leaves(held):
        by_dtype[a.dtype.name] = by_dtype.get(a.dtype.name, 0) + int(
            np.prod(a.shape)) * a.dtype.itemsize
    assert round(by_dtype['bfloat16'] / 1e9, 2) == 9.35
    assert by_dtype['float32'] < 6e7        # the routers, gains, merges
    # the pool: a page over 20 layers 2.62 MB, a slot's tails 5.4 KB a layer
    eng = cfg['engine']
    page = 2 * 2 * 128 * 128 * 2 * 20
    assert round(page / 1e6, 2) == 2.62
    assert round(eng['num_pages']['kv'] * page / 1e9, 2) == 3.02
    assert (2 * 1280 + 128) * 2 == 5376
    chip = (by_dtype['bfloat16'] + by_dtype['float32']
            + eng['num_pages']['kv'] * page + 48 * 20 * 5376)
    assert round(chip / 1e9, 1) == 12.4 and chip / 16e9 > 0.25


def test_the_mixs_quantile_lengths_and_clips(man, cfg):
    tr = man.traffic(man.cell(CELL))
    gen = manifest.load_module('generators', tr['generator'])
    p = tr['params']
    assert p == {
        'loop': 'closed', 'clients': 96, 'lead_in_finished': 48,
        'requests': 1536,
        'prompt': {'dist': 'exponential', 'mean': 200, 'lo': 16, 'hi': 1024},
        'answer': {'dist': 'exponential', 'mean': 1200, 'lo': 16,
                   'hi': 2048}}
    assert tr['trace_seconds'] == 4
    assert p['clients'] == 2 * cfg['engine']['num_slots']
    assert p['lead_in_finished'] == cfg['engine']['num_slots']
    context, vocab = cfg['max_position_embeddings'], cfg['vocab_size']
    assert p['prompt']['hi'] + p['answer']['hi'] == 3072 == context
    assert p['prompt']['hi'] == cfg['engine']['prefill_width']
    prompts = gen.quantile_lengths(p['prompt'], 96)
    answers = gen.quantile_lengths(p['answer'], 96)
    assert abs(float(np.mean(answers)) - 982) < 1           # after the clip
    assert abs(float(np.mean(prompts)) - 200) < 1
    assert int(np.sum(answers == 2048)) == 17               # 18 % clipped
    assert int(np.sum(prompts == 1024)) == 1
    assert int(np.min(prompts)) == 16 == int(np.min(answers))
    decoded = float(np.sum(answers)) / float(np.sum(answers + prompts))
    assert 0.82 < decoded < 0.84                            # five in six
    a = gen.make(p, 3000000019, vocab, context, 30.0)       # past 31 bits
    plen = np.array([len(x) for x in a['prompts']])
    new = np.array(a['max_new'])
    assert len(plen) == 1536 and int(np.max(plen + new)) <= context
    # nothing is cut by the context: every answer keeps its length
    assert sorted(new[:96]) == sorted(answers)
    assert max(int(np.max(x)) for x in a['prompts']) < vocab
    assert max(int(np.max(x)) for x in a['prompts']) > 0.99 * vocab
    # which width a prompt runs: most the narrowest two
    assert np.mean(plen <= 256) > 0.7 and np.mean(plen > 768) < 0.03


# ---- the reference against itself ------------------------------------------

def test_the_reference_in_two_block_sizes_is_one_reference():
    """A request's rows in a block of 32 and in a block of 64 (zeros after
    it), a layer at a time with the router's state beside the activations,
    as the runner drives it: the same rows, and the whole forward's."""
    import jax
    import jax.numpy as jnp
    ref = manifest.load_module('reference', 'zaya')
    drive = manifest.load_module('tests', 'drive_zaya')
    shape = dict({k: v for k, v in drive.TINY.items()
                  if k not in ('rope_parameters', 'held')},
                 rope_theta=5000000)
    key = jax.random.PRNGKey(11)
    params = ref.init_params(shape, key)
    seq = np.random.RandomState(0).randint(0, 256, size=27)
    rows = {}
    for block in (32, 64):
        tokens = np.zeros((1, block), np.int32)
        tokens[0, :27] = seq
        x = ref.embed(params, jnp.asarray(tokens), shape)
        r = ref.router_start(x, shape)
        for l in range(shape['num_hidden_layers']):
            x, r = ref.layer(ref.init_layer(shape, key, l), x, r, shape)
        rows[block] = np.asarray(ref.head(params, x[0, :27], shape))
    np.testing.assert_allclose(rows[32], rows[64], atol=1e-5)
    whole = ref.forward(params, jnp.asarray(seq)[None], shape)[0]
    np.testing.assert_allclose(rows[32], whole, atol=1e-5)


def test_the_new_readers_on_hand_made_facts(man, cfg, monkeypatch):
    """Each reader's arithmetic with the trace stubbed: one second of the
    kernel (of the scope) on one device."""
    from benchmark.harness import device, trace, xplane
    runner = manifest.load_module('runners', cfg['runner'])
    moe = {'prefill': {'rows_held': 0, 'experts_touched': 0, 'runs': 0},
           'decode': {'rows_held': 900 * 20, 'experts_touched': 300 * 20,
                      'runs': 20}}
    facts = {'shape': runner.facts_shape(runner.model_shape(cfg)),
             'device_kind': 'x', 'page_rows': 128, 'moe_window': moe,
             'paged_rows_in_trace': [900] * 480,
             'prefill_rows_in_trace': [200, 1024],
             'moe_rows_skipped': 6.0, 'moe_rows_offered': 96.0,
             'state_bytes_held': 1.0, 'state_and_page_bytes_held': 200.0}
    monkeypatch.setattr(device, 'peaks', lambda kind: PEAKS)
    monkeypatch.setattr(xplane, 'load', lambda reduced: {
        'ops': {0: [['%x = ', 0, 1]]}, 'devices': 1, 'busy_s': 4.0,
        'self': {0: [
            ('%a', 4e8, 'jit(step)/while/body/zaya.block/attn/conv/dot'),
            ('%b', 2e8, 'jit(step)/while/body/zaya.block/attn/rope/mul'),
            ('%c', 1.4e8, 'jit(step)/while/body/zaya.block/attn/attend/x'),
            ('%g', 6e7, 'jit(step)/while/body/zaya.block/attn/attend/'
                        'page_write/scatter'),
            ('%h', 4e7, 'jit(prefill)/zaya.page_write/scatter'),
            ('%d', 2e8, 'jit(step)/while/body/zaya.block/moe/router/dot'),
            ('%e', 2e9, 'jit(step)/while/body/zaya.block/moe/experts/x'),
            ('%f', 1e9, 'jit(step)/zaya.head/dot')]}})
    monkeypatch.setattr(trace, 'matching_time', lambda ev, pat: (1.0, 1))

    def read(name, facts=facts, reduced=None):
        spec = man.metric_spec(name)
        reader = manifest.load_module('readers', spec['reader'])
        return reader.read(spec['params'], facts, reduced or {})
    assert read('tps.cca_attn_time_share') == pytest.approx(20.0)
    assert read('tps.cca_mix_time_share') == pytest.approx(15.0)
    assert read('tps.zaya_pool_write_time_share') == pytest.approx(2.5)
    assert read('tps.zaya_router_time_share') == pytest.approx(5.0)
    assert read('tps.zaya_moe_time_share') == pytest.approx(55.0)
    assert read('tps.zaya_head_time_share') == pytest.approx(25.0)
    assert read('tps.moe_skip_rows_share') == pytest.approx(6.25)
    assert read('tps.state_bytes_share') == pytest.approx(0.5)
    # the accepted readers hold for this family's shapes as they stand: 20
    # layers that attend everything, 8 heads on 2 of 128; experts of three
    # 2,048 x 2,048 matrices
    paged = manifest.load_module('kernels', 'paged_gqa_attention')
    flash = manifest.load_module('kernels', 'flash_window_fwd')
    grouped = manifest.load_module('kernels', 'expert_grouped_matmul')
    assert read('tps.paged_gqa_kernel_roofline') == pytest.approx(
        100 * paged.least_seconds([900] * 480, 20, 8, 2, 128, None,
                                  PEAKS)['seconds'])
    assert read('tps.prefill_flash_roofline') == pytest.approx(
        100 * flash.least_seconds([200, 1024], 20, 0, 8, 2, 128, None,
                                  PEAKS)['seconds'])
    assert read('tps.expert_matmul_roofline', reduced={
        'module_runs': {'jit_step': 10}}) == pytest.approx(
        100 * grouped.least_seconds(9000, 3000, 2048, 2048,
                                    PEAKS)['seconds'])
    bare = {'shape': facts['shape'], 'device_kind': 'x'}
    for name in ('tps.moe_skip_rows_share', 'tps.state_bytes_share',
                 'tps.expert_matmul_roofline'):
        assert read(name, bare) is None
    # a program with no such scopes (the parent): no number
    monkeypatch.setattr(xplane, 'load', lambda reduced: {
        'devices': 1, 'busy_s': 4.0,
        'self': {0: [('%a', 4e8, 'jit(step)/granite.block/attn/dot')]}})
    for name in sorted(NEW - {'tps.moe_skip_rows_share'}):
        assert read(name) is None


# ---- a tiny cell of the family, end to end on the CPU ----------------------

def drive(tmp_path, *args):
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, 'drive_zaya.py'),
         str(tmp_path), *args], capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS='cpu'), timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith('{')]
    return lines[-1], {c['name']: c for c in lines
                       if c.get('phase') == 'compared'}, lines


ENERGY = {'logit_err_energy_median', 'logit_err_energy_p25',
          'rows_beyond_bound_share'}
ROUTER = {'router_state_err_p99', 'router_weight_err_p99',
          'router_choice_off_share'}
EXACT = {'tokens_not_their_rows_best', 'rows_not_finite',
         'rows_not_one_a_token', 'sampled_requests_unserved',
         'no_row_compared', 'rows_without_router_note', 'compiles_in_window'}


def test_a_sound_run_of_the_tiny_cell_is_correct(tmp_path):
    last, compared, lines = drive(tmp_path, '3')
    assert last['correct'] is True and last['failed'] == 0
    assert set(compared) == ENERGY | ROUTER | EXACT
    # the router run again on every served row and layer: float32's rounding
    assert compared['router_state_err_p99']['value'] < 1e-5
    assert compared['router_weight_err_p99']['value'] < 1e-5
    assert compared['router_choice_off_share']['value'] == 0
    assert set(last['metrics']) == {'serve_tokens_per_s_chip', 'setup_s'}
    window = [ln for ln in lines if ln.get('phase') == 'window'][0]
    state = [ln for ln in lines if ln.get('phase') == 'state'][0]
    moe = window['moe']
    # a decode step offers its four slots to three layers of four experts;
    # a row is held or has skipped
    assert moe['decode']['rows_offered'] == 4 * 3 * window['decode_steps']
    assert moe['decode']['expert_calls'] == 4 * 3 * window['decode_steps']
    offered = sum(moe[p]['rows_offered'] for p in moe)
    held = sum(moe[p]['rows_held'] for p in moe)
    assert state['moe_rows_offered'] == offered
    assert state['moe_rows_skipped'] == offered - held > 0
    # four busy slots hold their tails whatever their length
    per_slot = 3 * (2 * (4 + 2) * 16 + 16) * 4
    assert state['state_bytes_held'] == 4 * per_slot
    assert state['state_and_page_bytes_held'] > state['state_bytes_held']
    ref = [ln for ln in lines if ln.get('phase') == 'reference'][0]
    assert ref['rows'] > 50 and ref['logit_err_energy_max'] < 1e-9
    # slots filled before and requests admitted while others decoded
    assert len(ref['by_request']) >= 4
    assert max(r[0] + r[1] for r in ref['by_request']) > 24


@pytest.mark.parametrize('fault,least', [
    ('value_shift_dropped', 1e-2), ('router_state_not_carried', 1e-4),
    ('skip_row_to_expert_0', 1e-4)])
def test_a_planted_fault_fails_by_the_rows_energies(tmp_path, fault, least):
    """A program that drops the value shift, does not carry the router's
    state down the stack, or sends a skip row to the first expert, serves
    rows the whole reference refuses."""
    last, compared, _ = drive(tmp_path, '3', '--fault', fault)
    assert last['correct'] is False
    failed = {n for n, c in compared.items() if not c['ok']}
    assert failed <= ENERGY | ROUTER and {'logit_err_energy_median',
                                          'rows_beyond_bound_share'} <= failed
    # a fault in the router itself is also told by the router run again
    # (the reference's holds gamma and knows the skip choice); one outside
    # it is not: both routers were given the same rows
    assert bool(failed & ROUTER) == (fault != 'value_shift_dropped')
    assert compared['logit_err_energy_median']['value'] > least
    assert compared['tokens_not_their_rows_best']['ok']


@pytest.mark.parametrize('control,least', [('int8_weights', 1e-5),
                                           ('bfloat16_router', 1e-7)])
def test_a_named_control_fails_by_the_rows_energies(tmp_path, control, least):
    """At float32 the tiny cell tells both controls from the program by the
    rows' energies: the int8-rounded weights by nine orders of magnitude,
    the bfloat16 router by seven (its state and its probabilities are
    rounded, and the rows it flips move far more). The bfloat16 router is
    ALSO told by the router run again on the program's own rows, by all
    three of its numbers and three orders of magnitude: that holds at any
    precision of the residual stream, which the energies do not (the real
    cell's bfloat16 stream hides it from them)."""
    last, compared, _ = drive(tmp_path, '3', '--control', control)
    assert last['correct'] is False
    failed = {n for n, c in compared.items() if not c['ok']}
    assert ENERGY <= failed <= ENERGY | ROUTER
    assert compared['logit_err_energy_median']['value'] > least
    if control == 'bfloat16_router':
        assert ROUTER <= failed
        assert compared['router_state_err_p99']['value'] > 1e-3
        assert compared['router_weight_err_p99']['value'] > 1e-3
