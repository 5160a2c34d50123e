"""The paged decode kernel's share of its roofline in a model of
grouped-query heads whose layers are of two kinds: as
readers/paged_roofline.py (the least time for the kernel calls of the decode
steps the traced stretch made, from the rows the clients' tokens then
spanned, over the kernel's time in the device trace), for the layers of ONE
kind (``kind``: 'full' or 'window'), whose call the trace names apart
(``pattern``). Heads, KV heads, head size, the window and how many layers
are of the kind come from the cell's configuration
(benchmark/kernels/paged_gqa_attention.py counts the rest)."""
from benchmark.harness import device, manifest, trace, xplane

KINDS = {'full': 'full_attention', 'window': 'sliding_attention'}


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
    layers = list(shape['layer_types']).count(KINDS[params['kind']])
    window = shape['sliding_window'] if params['kind'] == 'window' else None
    kernel = manifest.load_module('kernels', params['kernel'])
    least = kernel.least_seconds(
        rows, layers, shape['num_attention_heads'],
        shape['num_key_value_heads'], shape['head_dim'], window,
        device.peaks(facts['device_kind']))
    return 100.0 * least['seconds'] / (seconds / tr['devices'])
