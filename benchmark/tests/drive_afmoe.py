"""Drive one run of a tiny served cell of the window-and-full-attention
family on the CPU: ``drive_latent.py``'s way (a temporary copy of the
benchmark, the look for a chip replaced, a fault planted underneath the
timed path), with the tiny configuration (a window of 8 rows over pages of
4, contexts several windows deep), its mix and its cell laid over
``util.make_copy``'s copy by this file, as new files and new entries.

    python drive_afmoe.py <tmpdir> <seconds> [--seed N] [--trace 0|1]
                          [--fault altered_token|...] [--control]
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import drive  # noqa: E402
import util  # noqa: E402

CELL = 'tiny-afmoe-doc'
REAL = 'serve-trinity-large-mixedlen-full'
TINY = {
    'vocab_size': 256, 'hidden_size': 64, 'intermediate_size': 128,
    'moe_intermediate_size': 32, 'num_hidden_layers': 5,
    'num_dense_layers': 1, 'num_attention_heads': 6,
    'num_key_value_heads': 1, 'head_dim': 16, 'sliding_window': 8,
    'layer_types': ['sliding_attention'] * 4 + ['full_attention'],
    'num_experts': 4, 'num_shared_experts': 1, 'num_experts_per_tok': 4,
    'n_group': 1, 'topk_group': 1, 'route_scale': 2.448, 'route_norm': True,
    'mup_enabled': True, 'rms_norm_eps': 1e-5, 'rope_theta': 10000,
    'max_position_embeddings': 96}
# the tiny cell states float32: a float32 row lies under 1e-9 of the
# reference's in energy and the int8-rounded control's median over 1e-5
LIMITS = {'row_energy_bound': 1e-7, 'logit_err_energy_median': 1e-8,
          'logit_err_energy_p90': 1e-7, 'rows_beyond_bound_share': 0.05}


def lay_over(root):
    """The tiny configuration, mix and cell, added to the copy."""
    b = os.path.join(root, 'benchmark')
    util._dump(os.path.join(b, 'configs', 'tiny-afmoe.json'), dict(
        TINY, source='test', runner='serve_afmoe', reference='trinity_large',
        held={'experts': [4, 4], 'router_width': 16},
        program={'dtype': 'float32', 'param_dtype': 'float32'},
        engine={'num_slots': 4, 'page_size': 4,
                'num_pages': {'full': 97, 'window': 13},
                'prefill_width': 64, 'queue_capacity': 64},
        control='int8_weights',
        controls={'int8_weights': {'weights': 'int8_per_channel'}},
        limits=LIMITS))
    util._dump(os.path.join(b, 'traffic', 'tiny-afmoe-doc.json'), {
        'generator': 'serve_requests', 'why': 'test', 'trace_seconds': 1.0,
        'params': {'loop': 'closed', 'clients': 8, 'lead_in_finished': 4,
                   'requests': 128,
                   'prompt': {'dist': 'lognormal', 'median': 16,
                              'sigma': 1.1, 'lo': 4, 'hi': 60},
                   'answer': {'dist': 'exponential', 'mean': 12, 'lo': 2,
                              'hi': 32}}})
    path = os.path.join(root, 'BENCHMARK.json')
    with open(path) as f:
        doc = json.load(f)
    doc['configs'].append({'name': 'tiny-afmoe', 'source': 'test',
                           'reduced': [], 'why': 't',
                           'file': 'benchmark/configs/tiny-afmoe.json'})
    doc['workloads'].append({'name': CELL, 'config': 'tiny-afmoe',
                             'traffic': 'tiny-afmoe-doc', 'chips': 1,
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
    ap.add_argument('--control', action='store_true')
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
        control = man.control(man.cell(CELL))
    line, _ = run.execute(run.parse([
        '--workload', CELL, '--seed', a.seed, '--seconds', a.seconds,
        '--trace', a.trace]), control=control)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
