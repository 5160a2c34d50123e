"""Drive one run of a tiny served cell of the state-space / attention hybrid
family on the CPU: ``drive_latent.py``'s way (a temporary copy of the
benchmark, the look for a chip replaced, a fault planted underneath the
timed path), with the tiny configuration (two periods of [mamba, mamba,
attention, mamba] at hidden 64, chunks of 8 rows), its mix and its cell
laid over ``util.make_copy``'s copy by this file, as new files and new
entries.

    python drive_granite.py <tmpdir> <seconds> [--seed N] [--trace 0|1]
                            [--fault altered_token|...]
                            [--control int8_weights|bfloat16_state]
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import drive  # noqa: E402
import util  # noqa: E402

CELL = 'tiny-granite-chat'
REAL = 'serve-granite-4.0-h-micro-sharegpt-full'
TINY = {
    'vocab_size': 256, 'hidden_size': 64, 'shared_intermediate_size': 128,
    'num_hidden_layers': 8,
    'layer_types': ['mamba', 'mamba', 'attention', 'mamba'] * 2,
    'num_attention_heads': 4, 'num_key_value_heads': 2, 'mamba_n_heads': 4,
    'mamba_d_head': 32, 'mamba_d_state': 16, 'mamba_d_conv': 4,
    'mamba_expand': 2, 'mamba_n_groups': 1, 'mamba_chunk_size': 8,
    'attention_multiplier': 0.0625, 'embedding_multiplier': 12,
    'residual_multiplier': 0.22, 'logits_scaling': 8, 'rms_norm_eps': 1e-5,
    'max_position_embeddings': 96}
# the tiny cell states float32: a float32 row lies under 1e-9 of the
# reference's in energy, the int8-rounded control's median over 1e-5 and
# the bfloat16-state control's over 1e-7
LIMITS = {'logit_err_energy_median': 1e-9, 'logit_err_energy_p99': 1e-8,
          'logit_err_energy_max': 1e-8}


def lay_over(root):
    """The tiny configuration, mix and cell, added to the copy."""
    b = os.path.join(root, 'benchmark')
    util._dump(os.path.join(b, 'configs', 'tiny-granite.json'), dict(
        TINY, source='test', runner='serve_granite_hybrid',
        reference='granite_hybrid',
        program={'dtype': 'float32', 'param_dtype': 'float32',
                 'state_dtype': 'float32'},
        engine={'num_slots': 4, 'page_size': 4, 'num_pages': {'kv': 97},
                'prefill_width': 64, 'queue_capacity': 64},
        control='int8_weights',
        controls={'int8_weights': {'weights': 'int8_per_channel'},
                  'bfloat16_state': {'state_dtype': 'bfloat16'}},
        limits=LIMITS))
    util._dump(os.path.join(b, 'traffic', 'tiny-granite-chat.json'), {
        'generator': 'serve_requests', 'why': 'test', 'trace_seconds': 1.0,
        'params': {'loop': 'closed', 'clients': 8, 'lead_in_finished': 4,
                   'requests': 128,
                   'prompt': {'dist': 'exponential', 'mean': 16, 'lo': 1,
                              'hi': 60},
                   'answer': {'dist': 'exponential', 'mean': 12, 'lo': 2,
                              'hi': 32}}})
    path = os.path.join(root, 'BENCHMARK.json')
    with open(path) as f:
        doc = json.load(f)
    doc['configs'].append({'name': 'tiny-granite', 'source': 'test',
                           'reduced': [], 'why': 't',
                           'file': 'benchmark/configs/tiny-granite.json'})
    doc['workloads'].append({'name': CELL, 'config': 'tiny-granite',
                             'traffic': 'tiny-granite-chat', 'chips': 1,
                             'why': 't'})
    for m in doc['end_to_end'] + doc['per_layer']:
        if REAL in m.get('workloads', ()):
            m['workloads'].append(CELL)
    util._dump(path, doc)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('tmp')
    ap.add_argument('seconds')
    ap.add_argument('--seed', default='7')
    ap.add_argument('--trace', default='0')
    ap.add_argument('--fault', default=None)
    ap.add_argument('--control', default=None)
    a = ap.parse_args()
    root = util.make_copy(a.tmp)
    lay_over(root)
    run = util.load_run(root)
    import jax
    from benchmark.harness import device, manifest
    device.require_tpu = lambda chips: jax.devices()[:chips]
    device.peaks = lambda kind: {'bf16_flops_per_s': 1e12,
                                 'hbm_bytes_per_s': 1e11}
    if a.fault:
        drive.plant_serving(a.fault)
    control = None
    if a.control:
        man = manifest.Manifest(root)
        control = man.control(man.cell(CELL), a.control)
    line, _ = run.execute(run.parse([
        '--workload', CELL, '--seed', a.seed, '--seconds', a.seconds,
        '--trace', a.trace]), control=control)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
