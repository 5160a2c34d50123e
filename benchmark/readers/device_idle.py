"""Share of the traced window in which no operation ran on the device,
averaged over the chips used (device trace)."""


def read(params, facts, reduced):
    if reduced is None:
        return None
    return 100.0 * (1.0 - reduced['busy_s'] / reduced['window_s'])
