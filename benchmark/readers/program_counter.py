"""A counter of the program's own registry (``paddle_tpu.observability``),
read when the run is over. A counter is made at its first increment, so one
that is not there reads 0 if ``zero_if`` (a counter the same code feeds) is
there, and gives no number if neither is: that program does not count."""


def read(params, facts, reduced):
    from paddle_tpu import observability
    reg = observability.registry()
    got = reg.find(params['counter'])
    if got is not None:
        return got.value
    if 'zero_if' in params and reg.find(params['zero_if']) is not None:
        return 0
    return None
