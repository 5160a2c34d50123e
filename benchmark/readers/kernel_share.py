"""Share of the device's busy time spent in the operations whose name
matches the metric's pattern (device trace)."""
from benchmark.harness import trace


def read(params, facts, reduced):
    if reduced is None:
        return None
    total = 0.0
    for events in reduced['events'].values():
        total += trace.matching_time(events, params['pattern'])[0]
    n = reduced['devices']
    if total == 0.0:
        return None
    return 100.0 * total / n / reduced['busy_s']
