"""A kernel's share of its roofline: the least time the chip could take for
the calls the traced window made (the larger of operations over peak and
bytes over peak bandwidth, from shapes, by benchmark/kernels/<kernel>.py)
over the time the device trace gives those calls."""
from benchmark.harness import device, manifest, trace


def read(params, facts, reduced):
    if reduced is None:
        return None
    seconds = 0.0
    for events in reduced['events'].values():
        seconds += trace.matching_time(events, params['pattern'])[0]
    if seconds == 0.0:
        return None
    # a device's events do not count calls (one kernel call shows as several
    # events): the calls follow from how often the program ran
    runs = reduced['module_runs'].get(params.get('module'), 0.0)
    kernel = manifest.load_module('kernels', params['kernel'])
    peaks = device.peaks(facts['device_kind'])
    least = kernel.least_seconds(facts, runs, peaks)
    if least is None:
        return None
    return 100.0 * least['seconds'] / (seconds / reduced['devices'])
