"""The grouped expert product's share of its roofline: the least time the
chip could take for the products the traced stretch made over the time the
device trace gives the operations whose HLO text matches ``pattern``.

What the products had to do follows from the program's own counters
(``moe.*``, read at the window's two ends by the runner): for each phase
(prefill, decode) the window's mean rows routed to held experts and mean
experts touched a run, times the runs of that phase's module inside the
traced stretch (``modules``: phase -> module name). The traced stretch is
the window's last seconds, so its runs are taken to route as the window's
do on average."""
from benchmark.harness import device, manifest, trace, xplane


def read(params, facts, reduced):
    tr = xplane.load(reduced)
    moe = facts.get('moe_window')
    if tr is None or not moe:
        return None
    seconds = sum(
        trace.matching_time([e[:3] for e in events], params['pattern'])[0]
        for events in tr['ops'].values())
    if seconds == 0.0:
        return None
    rows = touched = 0.0
    for phase, module in params['modules'].items():
        runs = reduced['module_runs'].get(module, 0.0)
        if moe[phase]['runs']:
            rows += runs * moe[phase]['rows_held'] / moe[phase]['runs']
            touched += runs * moe[phase]['experts_touched'] / moe[phase][
                'runs']
    shape = facts['shape']
    kernel = manifest.load_module('kernels', params['kernel'])
    least = kernel.least_seconds(
        rows, touched, shape['hidden_size'], shape['moe_intermediate_size'],
        device.peaks(facts['device_kind']))
    return 100.0 * least['seconds'] / (seconds / tr['devices'])
