"""Drive one run of a tiny served cell of the compressed-convolutional-
attention / top-1 expert family on the CPU: ``drive_granite.py``'s way (a
temporary copy of the benchmark, the look for a chip replaced, a fault
planted underneath the timed path), with the tiny configuration (three
layers at hidden 64, four experts and the skip choice), its mix and its
cell laid over ``util.make_copy``'s copy by this file, as new files and new
entries.

    python drive_zaya.py <tmpdir> <seconds> [--seed N] [--trace 0|1]
                         [--fault value_shift_dropped|router_state_not_carried
                                  |skip_row_to_expert_0]
                         [--control int8_weights|bfloat16_router]

The faults are planted in the PROGRAM (``paddle_tpu.models.zaya``), which
the runner is about to serve; the reference keeps the model whole."""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import util  # noqa: E402

CELL = 'tiny-zaya-reason'
REAL = 'serve-zaya1-8b-reason-full'
TINY = {
    'vocab_size': 256, 'hidden_size': 64, 'moe_intermediate_size': 32,
    'num_hidden_layers': 3, 'num_attention_heads': 4,
    'num_key_value_heads': 2, 'head_dim': 16, 'cca_time0': 2,
    'cca_time1': 2, 'num_experts': 4, 'num_experts_per_tok': 1,
    'router_hidden_size': 32, 'partial_rotary_factor': 0.5,
    'rms_norm_eps': 1e-5, 'max_position_embeddings': 96,
    'rope_parameters': {'hybrid': {'rope_theta': 5000000}},
    'held': {'experts': [0, 4], 'router_width': 4}}
# the tiny cell states float32: a float32 row lies under 1e-9 of the
# reference's in energy and no choice flips; the int8-rounded control's
# median lies over 1e-5, and the bfloat16 router flips a row in five. The
# router run again on the program's noted rows is held to the real cell's
# limits: its arithmetic is float32 there too
LIMITS = {'row_energy_bound': 1e-7, 'logit_err_energy_median': 1e-9,
          'logit_err_energy_p25': 1e-9, 'rows_beyond_bound_share': 0.02,
          'router_state_err_p99': 3e-5, 'router_weight_err_p99': 2e-4,
          'router_choice_off_share': 3e-4}
FAULTS = ('value_shift_dropped', 'router_state_not_carried',
          'skip_row_to_expert_0')


def lay_over(root):
    """The tiny configuration, mix and cell, added to the copy."""
    b = os.path.join(root, 'benchmark')
    util._dump(os.path.join(b, 'configs', 'tiny-zaya.json'), dict(
        TINY, source='test', runner='serve_zaya', reference='zaya',
        program={'dtype': 'float32', 'param_dtype': 'float32',
                 'router_dtype': 'float32'},
        engine={'num_slots': 4, 'page_size': 4, 'num_pages': {'kv': 97},
                'prefill_width': 64, 'queue_capacity': 64},
        control='int8_weights',
        controls={'int8_weights': {'weights': 'int8_per_channel'},
                  'bfloat16_router': {'router_dtype': 'bfloat16'}},
        limits=LIMITS))
    util._dump(os.path.join(b, 'traffic', 'tiny-zaya-reason.json'), {
        'generator': 'serve_requests', 'why': 'test', 'trace_seconds': 1.0,
        'params': {'loop': 'closed', 'clients': 8, 'lead_in_finished': 4,
                   'requests': 128,
                   'prompt': {'dist': 'exponential', 'mean': 12, 'lo': 1,
                              'hi': 60},
                   'answer': {'dist': 'exponential', 'mean': 16, 'lo': 2,
                              'hi': 36}}})
    path = os.path.join(root, 'BENCHMARK.json')
    with open(path) as f:
        doc = json.load(f)
    doc['configs'].append({'name': 'tiny-zaya', 'source': 'test',
                           'reduced': [], 'why': 't',
                           'file': 'benchmark/configs/tiny-zaya.json'})
    doc['workloads'].append({'name': CELL, 'config': 'tiny-zaya',
                             'traffic': 'tiny-zaya-reason', 'chips': 1,
                             'why': 't'})
    for m in doc['end_to_end'] + doc['per_layer']:
        if REAL in m.get('workloads', ()):
            m['workloads'].append(CELL)
    util._dump(path, doc)


def plant(fault):
    """Break the family the runner is about to serve."""
    import jax.numpy as jnp
    from paddle_tpu.models import zaya

    def zeroed(*path):
        stack = zaya.stack_layers

        def without(config, layer_of):
            def cut(l):
                lp = dict(layer_of(l))
                node = lp
                for key in path[:-1]:
                    node[key] = dict(node[key])
                    node = node[key]
                node[path[-1]] = jnp.zeros_like(node[path[-1]])
                return lp
            return stack(config, cut)
        zaya.stack_layers = without
    if fault == 'value_shift_dropped':
        # the second KV head's value is the row's own and none of the row
        # before it: W_v2 gone
        zeroed('v2')
    elif fault == 'router_state_not_carried':
        # every layer's router starts from its own projection alone
        zeroed('router', 'gamma')
    elif fault == 'skip_row_to_expert_0':
        # a row that chose to skip the experts is sent to the first
        router = zaya._router

        def no_skip(rp, u, r_above, config):
            chosen, p, r = router(rp, u, r_above, config)
            return jnp.where(chosen == config.num_experts, 0, chosen), p, r
        zaya._router = no_skip
    else:
        raise ValueError(fault)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('tmp')
    ap.add_argument('seconds')
    ap.add_argument('--seed', default='7')
    ap.add_argument('--trace', default='0')
    ap.add_argument('--fault', default=None, choices=FAULTS)
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
        plant(a.fault)
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
