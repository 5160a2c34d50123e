"""Arithmetic on samples. Every function is pure and tested on hand-made
inputs (benchmark/tests/test_stats.py)."""
import math
import statistics


def nearest_rank(samples, q):
    """q-th percentile (0 < q <= 100) by nearest rank: the smallest sample
    with at least q% of the samples at or below it. -> (value, n)."""
    xs = sorted(samples)
    if not xs:
        raise ValueError('percentile of no samples')
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1], len(xs)


def median(samples):
    return statistics.median(samples)


def block_readings(step_ends, block_starts, k, tokens_per_step, chips):
    """Tokens/s/chip of each whole block of k steps. ``block_starts[i]`` is
    the host time block i began, ``step_ends[i]`` the host time the last
    loss of block i had been read (the fence)."""
    return [k * tokens_per_step / (end - start) / chips
            for start, end in zip(block_starts, step_ends)]
