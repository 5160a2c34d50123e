"""The retention state update's share of its roofline: the least time the
chip could take to read and write once the state of every sequence that the
decode steps of the traced stretch served, a layer at a time
(benchmark/kernels/retention_state_update.py; a (sequence, step) pair is
one token a client heard inside the stretch: ``facts[rows_key]`` lists
them), over the time the device trace gives the operations whose HLO text
matches ``pattern`` (the kernel's ``name=``). Sizes come from the cell's
configuration. A program without the kernel has no such operation and
there is no number."""
from benchmark.harness import device, manifest, trace, xplane


def read(params, facts, reduced):
    tr = xplane.load(reduced)
    served = facts.get(params['rows_key'])
    if tr is None or not served:
        return None
    seconds = sum(
        trace.matching_time([e[:3] for e in events], params['pattern'])[0]
        for events in tr['ops'].values())
    if seconds == 0.0:
        return None
    shape = facts['shape']
    kernel = manifest.load_module('kernels', params['kernel'])
    least = kernel.least_seconds(
        len(served), shape['num_hidden_layers'],
        shape['num_key_value_heads'],
        shape['num_attention_heads'] // shape['num_key_value_heads'],
        shape['head_dim'], device.peaks(facts['device_kind']))
    return 100.0 * least['seconds'] / (seconds / tr['devices'])
