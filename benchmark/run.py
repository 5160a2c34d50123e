"""The benchmark's command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s>
                             --trace <0|1>

One run of one cell of BENCHMARK.json on the machine it is started on. The
last line of standard output is the result (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, and with ``--trace 1`` ``breakdown``);
every earlier line is one JSON object: the readings the result was reduced
from, and each number compared for ``correct`` beside its limit. Without a
TPU, or with fewer chips than the cell asks for, it prints no result and
exits 3.
"""
import time
STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.harness import manifest as _manifest  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seed', type=int, default=0)
    ap.add_argument('--seconds', type=float, default=None)
    ap.add_argument('--trace', type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def metric_values(man, cell_name, kind, result, reduced):
    """{name: {'value', 'unit'}} of the cell's metrics of ``kind``. An
    end-to-end metric is the runner's own number; a per-layer metric is read
    by its reader, and one whose reader finds nothing is left out."""
    out = {}
    for m in man.cell_metrics(cell_name, kind):
        if kind == 'end_to_end':
            value = result['end_to_end'].get(m['name'])
        else:
            spec = man.metric_spec(m['name'])
            reader = _manifest.load_module('readers', spec['reader'],
                                           man.root)
            value = reader.read(spec.get('params', {}), result['facts'],
                                reduced)
        if value is not None:
            out[m['name']] = {'value': float(value), 'unit': m['unit']}
    return out


def execute(args, control=None):
    """One run. -> (result line or None without a chip, runner's result).
    ``control`` is the lower-precision control's overrides of the
    configuration's ``program`` (benchmark/control.py); the benchmark's own
    runs pass none."""
    man = _manifest.Manifest(ROOT)
    cell = man.cell(args.workload)
    config, traffic = man.config(cell), man.traffic(cell)
    seconds = (args.seconds if args.seconds is not None
               else man.doc['run_seconds'])
    runner = _manifest.load_module('runners', config['runner'], man.root)

    import jax
    from benchmark.harness import context, device, trace
    # the program places the persistent cache (JAX_COMPILATION_CACHE_DIR,
    # else <checkout>/.jax_cache); small programs are cached too
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)
    try:
        devices = device.require_tpu(cell['chips'])
        device.peaks(devices[0].device_kind)
    except (device.NoChip, KeyError) as e:
        print(f'benchmark: {e}', file=sys.stderr)
        return None, None
    out_dir = os.path.join(ROOT, '.bench_out', cell['name'])
    os.makedirs(out_dir, exist_ok=True)
    ctx = context.Context(
        config=config, traffic=traffic,
        seed=args.seed, seconds=seconds, trace=bool(args.trace),
        devices=devices, started=STARTED,
        compiles=device.CompileCounter(), out_dir=out_dir, control=control)
    ctx.log('start', workload=cell['name'], seed=args.seed, seconds=seconds,
            trace=args.trace, device=device.info(devices))
    result = runner.run(ctx)
    result['facts']['device_kind'] = devices[0].device_kind
    for c in result['checks']:
        ctx.log('compared', **c)

    line = {'correct': result['correct'], 'attempted': result['attempted'],
            'failed': result['failed']}
    dev = result['device']
    if args.trace:
        loaded = result['facts'].get('trace')
        reduced = trace.reduce(loaded, result['facts']['span_names'])
        with open(os.path.join(out_dir, 'trace_summary.json'), 'w') as f:
            json.dump(trace.summary(loaded), f)
        line['metrics'] = metric_values(man, cell['name'], 'per_layer',
                                        result, reduced)
        dev = dict(dev, busy_s=reduced['busy_s'],
                   window_s=reduced['window_s'])
        line['breakdown'] = {'device_ops': reduced['device_ops'],
                             'idle_gaps': reduced['idle_gaps']}
    else:
        line['metrics'] = metric_values(man, cell['name'], 'end_to_end',
                                        result, None)
    line['device'] = dev
    return line, result


def main(argv=None):
    line, _ = execute(parse(argv))
    if line is None:
        return 3
    print(json.dumps(line), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
