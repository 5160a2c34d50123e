"""Collective time on a device during which no compute runs there, over
the traced window. A device's operations line runs one operation at a time,
so a collective's own time on it (an all-reduce, or the wait in an
``-done``) is time in which nothing else ran."""
from benchmark.harness import trace

# the trace names an operation by its HLO text: ``%x = shape opcode(...``
PATTERN = (r' (all-reduce|all-gather|reduce-scatter|all-to-all|'
           r'collective-permute)(-start|-done)?\(')


def read(params, facts, reduced):
    if reduced is None:
        return None
    total = 0.0
    for events in reduced['events'].values():
        for name, t in trace.self_times(events):
            if trace.re.search(PATTERN, name):
                total += t
    return 100.0 * total / 1e9 / reduced['devices'] / reduced['window_s']
