"""Drive one run of a tiny cell on the CPU, in a temporary copy of the
benchmark, without the harness's look for a chip:

    python drive.py <tmpdir> <workload> <seconds> [--seed N] [--trace 0|1]
                    [--fault state_unchanged|half_batch] [--control]

Prints what benchmark/run.py prints. The look for a chip is replaced and a
fault is planted HERE, underneath the timed path, in the program's
``make_train_step``; the benchmark's own code carries no hook for either.
A process of its own for every run: the program cannot build a second train
step in one process."""
import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import util  # noqa: E402


def plant(fault):
    """Break the step the runner is about to build."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import gpt
    make = gpt.make_train_step

    def broken_make(*args, **kw):
        step = make(*args, **kw)

        def state_unchanged(*a):
            # the step's work thrown away (the step donates what it is
            # given, so what is kept is a copy)
            kept = jax.tree_util.tree_map(jnp.copy, a)
            out = step(*a)
            return (out[0],) + tuple(kept[:len(out) - 1])

        def half_batch(*a):
            toks, tgts = a[-2:]
            half = toks.shape[0] // 2
            return step(*a[:-2], toks.at[half:].set(0),
                        tgts.at[half:].set(0))
        return {'state_unchanged': state_unchanged,
                'half_batch': half_batch}[fault]
    gpt.make_train_step = broken_make


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('tmp')
    ap.add_argument('workload')
    ap.add_argument('seconds')
    ap.add_argument('--seed', default='7')
    ap.add_argument('--trace', default='0')
    ap.add_argument('--fault', default=None)
    ap.add_argument('--control', action='store_true')
    a = ap.parse_args()
    root = util.make_copy(a.tmp)
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
        control = man.config(man.cell(a.workload))['control']
    import json
    line, _ = run.execute(run.parse([
        '--workload', a.workload, '--seed', a.seed, '--seconds', a.seconds,
        '--trace', a.trace]), control=control)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
