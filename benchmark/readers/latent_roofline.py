"""The paged latent decode kernel's share of its roofline: as
readers/paged_roofline.py (the least time for the kernel calls of the decode
steps the traced stretch made, from the rows the clients' tokens then
spanned, over the kernel's time in the device trace), with the kernel's
costs taken from a latent cache's shape: heads, rank and rotary width, no
heads axis in the pool."""
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
    kernel = manifest.load_module('kernels', params['kernel'])
    least = kernel.least_seconds(
        rows, shape['num_hidden_layers'], shape['num_attention_heads'],
        shape['kv_lora_rank'], shape['qk_rope_head_dim'], facts['page_rows'],
        device.peaks(facts['device_kind']))
    return 100.0 * least['seconds'] / (seconds / tr['devices'])
