"""A temporary copy of the benchmark with two tiny configurations, a tiny
mix, their cells and one more per-layer metric ADDED as new files and new
entries: what a later PR may do, and small enough for a CPU."""
import importlib.util
import json
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_MODEL = {'vocab_size': 512, 'hidden_size': 64, 'num_layers': 2,
              'num_heads': 2, 'max_seq_len': 128, 'ffn_mult': 4}
# the tiny cells state float32; their limits sit between what float32 and
# the lower-precision control read at this size (test_correct.py)
LOOSE = {'loss_rel': 1e-4, 'grad_norm_rel': 1e-3, 'delta_norm_rel': 0.03}


def _dump(path, doc):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, 'w') as f:
        json.dump(doc, f)


def make_copy(tmp):
    """-> root of the copy. Nothing that was there is edited except
    BENCHMARK.json, which gains entries."""
    root = os.path.join(str(tmp), 'copy')
    shutil.copytree(os.path.join(REPO, 'benchmark'),
                    os.path.join(root, 'benchmark'),
                    ignore=shutil.ignore_patterns('__pycache__', 'tests'))
    with open(os.path.join(REPO, 'BENCHMARK.json')) as f:
        doc = json.load(f)
    b = os.path.join(root, 'benchmark')
    opt = {'lr': 3e-5, 'beta1': 0.9, 'beta2': 0.999, 'epsilon': 1e-8,
           'weight_decay': 0.01}
    _dump(os.path.join(b, 'configs', 'tiny-train.json'), {
        'source': 'test', 'runner': 'train_gpt', 'reference': 'gpt',
        'model': TINY_MODEL, 'optimizer': opt, 'mesh': {},
        'program': {'dtype': 'float32', 'use_flash': False,
                    'xent_chunk': 128},
        'control': {'dtype': 'bfloat16'}, 'limits': LOOSE})
    _dump(os.path.join(b, 'configs', 'tiny-train-mesh.json'), {
        'source': 'test', 'runner': 'train_gpt', 'reference': 'gpt',
        'model': TINY_MODEL, 'optimizer': opt, 'mesh': {'dp': 2, 'mp': 2},
        'program': {'dtype': 'float32', 'use_flash': False},
        'reference_checkpoint_layers': True,
        'control': {'dtype': 'bfloat16'}, 'limits': LOOSE})
    _dump(os.path.join(b, 'traffic', 'tiny-lm.json'), {
        'generator': 'lm_stream', 'why': 'test',
        'params': {'zipf_a': 1.3, 'stream_tokens': 50000, 'batch': 4,
                   'seq': 32}})
    _dump(os.path.join(b, 'metrics', 'tiny_step_ms_max.json'), {
        'reader': 'fact', 'params': {'key': 'step_ms', 'reduce': 'max'}})
    doc['configs'] += [
        {'name': 'tiny-train', 'source': 'test', 'reduced': [], 'why': 't',
         'file': 'benchmark/configs/tiny-train.json'},
        {'name': 'tiny-train-mesh', 'source': 'test', 'reduced': [],
         'why': 't', 'file': 'benchmark/configs/tiny-train-mesh.json'}]
    doc['workloads'] += [
        {'name': 'tiny-train', 'config': 'tiny-train', 'traffic': 'tiny-lm',
         'chips': 1, 'why': 't'},
        {'name': 'tiny-train-mesh', 'config': 'tiny-train-mesh',
         'traffic': 'tiny-lm', 'chips': 4, 'why': 't'}]
    for m in doc['end_to_end']:
        if m['name'] == 'train_tokens_per_s_chip':
            m['workloads'] += ['tiny-train', 'tiny-train-mesh']
    doc['per_layer'].append({
        'name': 'tiny_step_ms_max', 'unit': 'ms', 'better': 'lower',
        'source': 'host_clock', 'layer': 'train step program',
        'moves': 'train_tokens_per_s_chip', 'workloads': ['tiny-train']})
    _dump(os.path.join(root, 'BENCHMARK.json'), doc)
    return root


def load_run(root):
    """The copy's run.py as a module, with the repo importable behind it."""
    for name in [n for n in sys.modules if n.startswith('benchmark')]:
        del sys.modules[name]
    sys.path[:] = [p for p in sys.path if p != REPO] + [REPO]
    spec = importlib.util.spec_from_file_location(
        'bench_run_copy', os.path.join(root, 'benchmark', 'run.py'))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
