"""The device a run is on: that it is the chip the cell asks for, its
published peaks, its memory peak, and how often jit compiled."""
import collections
import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class NoChip(Exception):
    pass


def require_tpu(chips):
    import jax
    devs = jax.devices()
    if devs[0].platform != 'tpu':
        raise NoChip(f'jax found no TPU (first device: {devs[0].platform})')
    if len(devs) < chips:
        raise NoChip(f'the cell asks for {chips} chips, jax has {len(devs)}')
    return devs[:chips]


def peaks(device_kind):
    with open(os.path.join(HERE, 'peaks.json')) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith('_'):
        raise KeyError(f'no published peaks for device kind {device_kind!r} '
                       f'in benchmark/peaks.json: add them with their source')
    return table[device_kind]


def info(devices):
    """The ``device`` object of the result line; memory_peak_bytes is the
    peak on the fullest chip so far."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get('peak_bytes_in_use', 0)))
    return {'platform': devices[0].platform, 'kind': devices[0].device_kind,
            'count': len(devices), 'memory_peak_bytes': peak}


class CompileCounter:
    """Compile requests by jitted function name, counted from jax's
    monitoring events (a request the persistent cache answers still counts:
    the question is whether jit asked inside the window)."""

    def __init__(self):
        import jax
        self.requests = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _duration(self, event, _secs, fun_name=None, **_):
        if event.endswith('backend_compile_duration'):
            self.requests[fun_name or '?'] += 1

    def total(self):
        return sum(self.requests.values())
