"""Read the numbers ``correct`` compares, over many seeds: the program as the
configuration states it, and the lower-precision control (the configuration
file's ``control``). The limits in a configuration's file are set from what
this prints; the benchmark's own runs never run it.

    python3 benchmark/control.py --workload W --seeds 1,2,3 \
        --control-seeds 4,5,6 --seconds 0

Every seed gets a process of its own and this parent never touches jax: a
chip belongs to one process, and the program cannot build a second train
step in one process (PERF.md, Open questions). ``--seconds 0`` skips the
window: training's readings need none.
"""
import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def child(args):
    """One seed, in this process. Prints the run's lines and its readings."""
    sys.path.insert(0, ROOT)
    from benchmark import run as bench
    from benchmark.harness import manifest
    control = None
    if args.child == 'control':
        man = manifest.Manifest(ROOT)
        control = man.config(man.cell(args.workload))['control']
    run_args = argparse.Namespace(workload=args.workload,
                                  seed=int(args.seeds),
                                  seconds=args.seconds, trace=0)
    line, result = bench.execute(run_args, control=control)
    if line is None:
        return 3
    print(json.dumps({'phase': 'readings', 'side': args.child,
                      'seed': run_args.seed, 'correct': result['correct'],
                      'checks': {c['name']: c['value']
                                 for c in result['checks']}}), flush=True)
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    ap.add_argument('--workload', required=True)
    ap.add_argument('--seeds', default='')
    ap.add_argument('--control-seeds', default='')
    ap.add_argument('--seconds', type=float, default=0.0)
    ap.add_argument('--child', choices=('program', 'control'), default=None)
    args = ap.parse_args(argv)
    if args.child:
        return child(args)
    table = {}
    for side, seeds in (('program', args.seeds),
                        ('control', args.control_seeds)):
        for seed in [s for s in seeds.split(',') if s]:
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), '--workload',
                 args.workload, '--child', side, '--seeds', seed,
                 '--seconds', str(args.seconds)],
                capture_output=True, text=True)
            got = [json.loads(ln) for ln in proc.stdout.splitlines()
                   if ln.startswith('{"phase": "readings"')]
            if not got:     # a control that crashes has failed, and sets
                print(json.dumps({          # no upper end of a limit
                    'phase': 'no_readings', 'side': side, 'seed': seed,
                    'rc': proc.returncode, 'err': proc.stderr[-800:]}),
                    flush=True)
                continue
            print(json.dumps(got[0]), flush=True)
            for name, value in got[0]['checks'].items():
                table.setdefault(name, {}).setdefault(side, []).append(value)
    for name, sides in table.items():
        row = {'phase': 'limits_from', 'number': name}
        if sides.get('program'):
            row['program_largest'] = max(sides['program'])
        if sides.get('control'):
            row['control_smallest'] = min(sides['control'])
        print(json.dumps(row), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
