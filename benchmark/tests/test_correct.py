"""``correct`` comes out true for a sound run and false where it must: the
lower-precision control in the program's place, and the timed path broken
underneath (a step that returns its state unchanged, half a batch left
out). Each case drives a whole run of a tiny cell on the CPU through
benchmark/run.py, skipping only the look for a chip (drive.py), in a process
of its own."""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))


def drive(tmp_path, *args, devices=1):
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    if devices > 1:
        env['XLA_FLAGS'] = (f'--xla_force_host_platform_device_count='
                            f'{devices}')
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, 'drive.py'), str(tmp_path),
         *args], capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith('{')]
    return lines[-1], {c['name']: c for c in lines
                       if c.get('phase') == 'compared'}, lines


def test_a_sound_training_run_is_correct_and_prints_the_contracts_line(
        tmp_path):
    last, compared, lines = drive(tmp_path, 'tiny-train', '5')
    assert set(last) == {'correct', 'attempted', 'failed', 'metrics',
                         'device'}
    assert last['correct'] is True and last['failed'] == 0
    assert set(last['metrics']) == {'train_tokens_per_s_chip', 'setup_s'}
    assert all(set(v) == {'value', 'unit'} for v in last['metrics'].values())
    assert {'platform', 'kind', 'count', 'memory_peak_bytes'} <= set(
        last['device'])
    # every number compared is printed beside its limit
    assert {'loss_step1_rel', 'loss_step3_rel', 'grad_norm_worst_leaf_rel',
            'delta_norm_worst_leaf_rel', 'compiles_in_window'} <= set(
        compared)
    assert all('limit' in c and 'value' in c for c in compared.values())
    window = [ln for ln in lines if ln.get('phase') == 'window'][0]
    assert window['n_blocks'] == len(window['block_tokens_per_s_chip']) >= 1
    # the metric is ALL the window's whole steps over ALL its time
    assert last['metrics']['train_tokens_per_s_chip']['value'] == \
        pytest.approx(window['steps'] * 4 * 32 / window['window_s'])


@pytest.mark.parametrize('fault, fails', [
    ('state_unchanged', 'delta_norm_worst_leaf_rel'),
    ('half_batch', 'loss_step1_rel'),
])
def test_a_broken_training_step_is_not_correct(tmp_path, fault, fails):
    last, compared, _ = drive(tmp_path, 'tiny-train', '0', '--fault', fault)
    assert last['correct'] is False
    assert compared[fails]['ok'] is False


def test_the_training_control_in_lower_precision_is_not_correct(tmp_path):
    # float32 is what the tiny cell states; bfloat16 is the step below it
    last, compared, _ = drive(tmp_path, 'tiny-train', '0', '--control')
    assert last['correct'] is False
    assert compared['delta_norm_worst_leaf_rel']['ok'] is False
    assert compared['grad_norm_worst_leaf_rel']['ok'] is False


def test_the_mesh_path_runs_and_agrees_with_the_reference(tmp_path):
    last, _, _ = drive(tmp_path, 'tiny-train-mesh', '5', devices=4)
    assert last['correct'] is True and last['device']['count'] == 4


def test_without_a_chip_there_is_no_result(tmp_path):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, 'benchmark', 'run.py'),
         '--workload', 'train-345m-1chip', '--seed', '1', '--seconds', '1',
         '--trace', '0'], capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS='cpu'), timeout=300)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout


def test_in_a_directory_with_the_benchmark_alone_there_is_no_result(
        tmp_path):
    import shutil
    shutil.copytree(os.path.join(REPO, 'benchmark'),
                    tmp_path / 'benchmark',
                    ignore=shutil.ignore_patterns('__pycache__'))
    shutil.copy(os.path.join(REPO, 'BENCHMARK.json'), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != 'PYTHONPATH'}
    proc = subprocess.run(
        [sys.executable, 'benchmark/run.py', '--workload',
         'train-345m-1chip', '--seed', '1', '--seconds', '1', '--trace',
         '0'], capture_output=True, text=True, cwd=tmp_path,
        env=dict(env, JAX_PLATFORMS='cpu'), timeout=300)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
