"""One number the runner counted over another (two counters of the program
read at the window's two ends, say), times ``scale``; no number where either
is missing or the divisor is zero."""


def read(params, facts, reduced):
    num, den = facts.get(params['over'][0]), facts.get(params['over'][1])
    if num is None or not den:
        return None
    return params.get('scale', 1.0) * num / den
