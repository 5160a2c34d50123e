"""Drive one run of a tiny served cell of the power-retention family on the
CPU: ``drive_latent.py``'s way (a temporary copy of the benchmark, the look
for a chip replaced, a fault planted underneath the timed path), with the
tiny configuration (two layers at hidden 64, four query heads on two KV
heads of 16, D = 136, NO page pool), its mix and its cell laid over
``util.make_copy``'s copy by this file, as new files and new entries; a
prefill's chunk is cut to 8 rows (``ops/retention.CHUNK``), so that the
tiny prompts cross chunks as the real ones do.

    python drive_brumby.py <tmpdir> <seconds> [--seed N] [--trace 0|1]
        [--fault gate_dropped|state_carried_over|normaliser_dropped|
                 altered_token]
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

CELL = 'tiny-brumby-longgen'
REAL = 'serve-brumby-14b-longgen-full'
TINY = {
    'vocab_size': 256, 'hidden_size': 64, 'intermediate_size': 96,
    'num_hidden_layers': 2, 'num_attention_heads': 4,
    'num_key_value_heads': 2, 'head_dim': 16, 'rms_norm_eps': 1e-6,
    'rope_theta': 1000000, 'max_position_embeddings': 96}
# the tiny cell states float32: a float32 row lies under 1e-9 of the
# reference's in energy, every planted fault's median over 1e-6
LIMITS = {'logit_err_energy_median': 1e-9, 'logit_err_energy_p99': 1e-8,
          'logit_err_energy_max': 1e-8}
FAULTS = ('gate_dropped', 'state_carried_over', 'normaliser_dropped',
          'altered_token')


def lay_over(root):
    """The tiny configuration, mix and cell, added to the copy."""
    b = os.path.join(root, 'benchmark')
    util._dump(os.path.join(b, 'configs', 'tiny-brumby.json'), dict(
        TINY, source='test', runner='serve_brumby', reference='brumby',
        program={'dtype': 'float32', 'param_dtype': 'float32',
                 'state_dtype': 'float32'},
        engine={'num_slots': 4, 'page_size': 8, 'prefill_width': 64,
                'queue_capacity': 64},
        control='int8_weights',
        controls={'int8_weights': {'weights': 'int8_per_channel'},
                  'bfloat16_state': {'state_dtype': 'bfloat16'}},
        limits=LIMITS))
    util._dump(os.path.join(b, 'traffic', 'tiny-brumby-longgen.json'), {
        'generator': 'serve_requests', 'why': 'test', 'trace_seconds': 1.0,
        'params': {'loop': 'closed', 'clients': 8, 'lead_in_finished': 4,
                   'requests': 128,
                   'prompt': {'dist': 'normal', 'mean': 24, 'stddev': 8,
                              'lo': 4, 'hi': 60},
                   'answer': {'dist': 'exponential', 'mean': 12, 'lo': 2,
                              'hi': 32}}})
    path = os.path.join(root, 'BENCHMARK.json')
    with open(path) as f:
        doc = json.load(f)
    doc['configs'].append({'name': 'tiny-brumby', 'source': 'test',
                           'reduced': [], 'why': 't',
                           'file': 'benchmark/configs/tiny-brumby.json'})
    doc['workloads'].append({'name': CELL, 'config': 'tiny-brumby',
                             'traffic': 'tiny-brumby-longgen', 'chips': 1,
                             'why': 't'})
    for m in doc['end_to_end'] + doc['per_layer']:
        if REAL in m.get('workloads', ()):
            m['workloads'].append(CELL)
    util._dump(path, doc)


def plant(fault):
    """Break the program underneath the engine the runner builds."""
    if fault == 'altered_token':
        return drive.plant_serving(fault)
    import jax.numpy as jnp
    from paddle_tpu.models import brumby
    from paddle_tpu.ops import retention
    if fault == 'gate_dropped':
        # no head forgets: the program's recurrence takes every token's
        # log decay as 0, in a prefill and in a step
        chunked, update = retention.chunked_retention, retention.state_update
        retention.chunked_retention = lambda q, k, v, l, *a: chunked(
            q, k, v, jnp.zeros_like(l), *a)
        retention.state_update = lambda s, z, rows, g, *a: update(
            s, z, rows, jnp.ones_like(g), *a)
    elif fault == 'state_carried_over':
        # a prefill adds its state to what the slot's last occupant left
        brumby._write_prefill = lambda pool, left, slots: {
            name: plane.at[:, slots].add(left[name].astype(plane.dtype))
            for name, plane in pool.items()}
    elif fault == 'normaliser_dropped':
        # a decode step's read-out is not divided by phi(q)^T z
        update = retention.state_update

        def unnormalised(s, z, rows, g, pk, pq, v):
            y, s, z = update(s, z, rows, g, pk, pq, v)
            den = jnp.einsum('bhad,bhd->bha', pq, z[rows])
            return y * (den[..., None] + retention.EPS), s, z
        retention.state_update = unnormalised
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
    from paddle_tpu.ops import retention
    retention.CHUNK = 8
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
