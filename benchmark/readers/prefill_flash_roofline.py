"""The forward flash kernel's share of its roofline as a served prefill
runs it: the least time the chip could take for the kernel calls of the
prefills that ran WHOLE inside the traced stretch (the runner lists their
prompts' lengths, ``facts[rows_key]``, from the program's own record of
each request; benchmark/kernels/flash_window_fwd.py counts a prompt's
calls, a layer of each kind at a time) over the time the device trace gives
the operations whose HLO text matches ``pattern`` (both the full layers'
``flash_fwd`` and the window layers' ``flash_fwd_window``).

A prefill the stretch cuts at either end adds its kernels' time and no
work: the share reads low by that, never high."""
from benchmark.harness import device, manifest, trace, xplane


def read(params, facts, reduced):
    tr = xplane.load(reduced)
    rows = facts.get(params['rows_key'])
    if tr is None or not rows:
        return None
    seconds = sum(
        trace.matching_time([e[:3] for e in events], params['pattern'])[0]
        for events in tr['ops'].values())
    if seconds == 0.0:
        return None
    shape = facts['shape']
    kinds = list(shape['layer_types'])
    kernel = manifest.load_module('kernels', params['kernel'])
    least = kernel.least_seconds(
        rows, kinds.count('full_attention'),
        kinds.count('sliding_attention'), shape['num_attention_heads'],
        shape['num_key_value_heads'], shape['head_dim'],
        shape['sliding_window'], device.peaks(facts['device_kind']))
    return 100.0 * least['seconds'] / (seconds / tr['devices'])
