"""A span the PROGRAM opens (``observability.span``, which reaches the
profiler's host planes with its attributes; benchmark/harness/xplane.py):
the mean duration in ms of the spans of that name that begin inside the
traced window."""
from benchmark.harness import xplane


def read(params, facts, reduced):
    tr = xplane.load(reduced)
    if tr is None:
        return None
    ms = [d / 1e6 for name, s, d, _ in tr['spans']
          if name == params['span'] and s > tr['t0_ns']]
    return sum(ms) / len(ms) if ms else None
