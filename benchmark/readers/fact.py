"""A number the runner counted or timed itself (spans of the benchmark's
own, counters and histograms of the program), reduced as the metric's file
says: value | mean | median | max | p50 | p95 | p99."""
from benchmark.harness import stats


def read(params, facts, reduced):
    got = facts.get(params['key'])
    if got is None or (isinstance(got, list) and not got):
        return None
    how = params.get('reduce', 'value')
    if how == 'value':
        return got
    if how == 'mean':
        return sum(got) / len(got)
    if how == 'median':
        return stats.median(got)
    if how == 'max':
        return max(got)
    if how.startswith('p'):
        return stats.nearest_rank(got, float(how[1:]))[0]
    raise ValueError(f'unknown reduce {how!r}')
