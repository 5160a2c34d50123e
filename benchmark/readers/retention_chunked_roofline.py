"""The prefill's chunked retention's share of its roofline: the least time
the chip could take for the layers' calls of the prefills that ran WHOLE
inside the traced stretch (the runner lists their prompts' lengths,
``facts[rows_key]``; benchmark/kernels/retention_chunked.py counts a
prompt's call, for each call the longer of its two bounds) over the
device's self time in the operations whose scope path matches ``scope``
(the program's ``jax.named_scope``; readers/scope_share.py says how a path
reaches an operation).

A prefill the stretch cuts at either end adds its operations' time and no
work: the share reads low by that, never high. A program without the scope
gives no number."""
import re

from benchmark.harness import device, manifest, xplane


def read(params, facts, reduced):
    tr = xplane.load(reduced)
    rows = facts.get(params['rows_key'])
    if tr is None or not rows:
        return None
    scope = re.compile(params['scope'])
    nanos = sum(t for times in tr['self'].values()
                for _, t, path in times if scope.search(path))
    if nanos == 0.0:
        return None
    shape = facts['shape']
    kernel = manifest.load_module('kernels', params['kernel'])
    least = kernel.least_seconds(
        rows, shape['num_hidden_layers'], shape['num_key_value_heads'],
        shape['num_attention_heads'] // shape['num_key_value_heads'],
        shape['head_dim'], device.peaks(facts['device_kind']))
    return 100.0 * least['seconds'] / (nanos / 1e9 / tr['devices'])
