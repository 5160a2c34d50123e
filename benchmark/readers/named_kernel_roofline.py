"""One named kernel's share of its roofline: the least time the chip could
take for the calls the traced window made of it (``call_cost`` of its
variant in benchmark/kernels/<kernel>.py against benchmark/peaks.json) over
the time the device trace gives the operations whose HLO text starts with
the kernel's name (``%flash_fwd.12 = ...``: the ``name=`` of its
``pallas_call``). Calls are counted as kernel_roofline counts them, from
how often the module ran: one a layer a step, and one more where the
backward pass runs the forward again (``twice_under_remat``)."""
from benchmark.harness import device, manifest, trace, xplane


def read(params, facts, reduced):
    tr = xplane.load(reduced)
    if tr is None:
        return None
    seconds = sum(
        trace.matching_time([e[:3] for e in events], params['pattern'])[0]
        for events in tr['ops'].values())
    if seconds == 0.0:
        return None
    calls = facts['layers'] * reduced['module_runs'].get(params['module'],
                                                        0.0)
    if params.get('twice_under_remat') and facts.get('remat_policy') in (
            'dots', 'full'):
        calls *= 2
    mesh, shape = facts.get('mesh', {}), facts['shape']
    kernel = manifest.load_module('kernels', params['kernel'])
    flops, byts = kernel.call_cost(
        params['variant'], facts['batch'] // mesh.get('dp', 1),
        shape['num_heads'] // mesh.get('mp', 1), facts['seq'],
        shape['hidden_size'] // shape['num_heads'])
    peaks = device.peaks(facts['device_kind'])
    least = max(flops / peaks['bf16_flops_per_s'],
                byts / peaks['hbm_bytes_per_s'])
    return 100.0 * least * calls / (seconds / tr['devices'])
