"""From a profiler trace to device metrics: busy time as the union of the
intervals in which an operation ran, idle gaps and the host span each lies
under, time by operation name. Works on a neutral form

    {'planes': [{'name', 'lines': [{'name', 'events': [[name, start_ns,
                                                         dur_ns], ...]}]}]}

so that the arithmetic is checked on a small hand-made fixture
(benchmark/tests/test_trace.py); ``load_xplane`` makes that form from the
``.xplane.pb`` jax's profiler writes."""
import glob
import os
import re

DEVICE_PLANE = re.compile(r'^/device:TPU:(\d+)$')
OPS_LINE = 'XLA Ops'
MODULES_LINE = 'XLA Modules'
WINDOW_SPAN = 'bench.trace_window'


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, 'plugins', 'profile', '*', '*.xplane.pb')))
    if not found:
        raise FileNotFoundError(f'no .xplane.pb under {trace_dir}')
    return found[-1]


def load_xplane(path, host_names):
    """Device planes whole; of the host planes only the events named in
    ``host_names`` (the program's and the benchmark's spans)."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    keep = set(host_names) | {WINDOW_SPAN}
    planes = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if device:
                events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                          for e in line.events]
            else:
                events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                          for e in line.events if e.name in keep]
            if events:
                lines.append({'name': line.name, 'events': events})
        if lines:
            planes.append({'name': plane.name, 'lines': lines})
    return {'planes': planes}


def summary(trace, n=12):
    """What the planes and lines hold, for reading a new trace by hand."""
    out = []
    for plane in trace['planes']:
        for line in plane['lines']:
            names = {}
            for name, _, dur in line['events']:
                names[name] = names.get(name, 0.0) + dur
            top = sorted(names.items(), key=lambda kv: -kv[1])[:n]
            out.append({'plane': plane['name'], 'line': line['name'],
                        'events': len(line['events']),
                        'top': [[k, v / 1e9] for k, v in top]})
    return out


def device_ops(trace):
    """{device id: [[name, start_ns, dur_ns], ...] sorted by start} from
    each device plane's operations line."""
    out = {}
    for plane in trace['planes']:
        m = DEVICE_PLANE.match(plane['name'])
        if not m:
            continue
        for line in plane['lines']:
            if line['name'] == OPS_LINE:
                out[int(m.group(1))] = sorted(line['events'],
                                              key=lambda e: (e[1], -e[2]))
    return out


def module_runs(trace, t0, t1):
    """{module name: runs inside [t0, t1]} averaged over the device planes;
    a run that straddles an edge counts by the part of it inside. One run
    of ``jit_step`` is one training step."""
    totals, planes = {}, 0
    for plane in trace['planes']:
        if not DEVICE_PLANE.match(plane['name']):
            continue
        planes += 1
        for line in plane['lines']:
            if line['name'] != MODULES_LINE:
                continue
            for name, s, d in line['events']:
                inside = min(s + d, t1) - max(s, t0)
                if inside > 0 and d > 0:
                    key = name.split('(')[0]
                    totals[key] = totals.get(key, 0.0) + inside / d
    return {k: v / planes for k, v in totals.items()}


def host_spans(trace, names):
    """[[name, start_ns, end_ns], ...] of the named spans on any host
    thread."""
    names = set(names)
    out = []
    for plane in trace['planes']:
        if DEVICE_PLANE.match(plane['name']):
            continue
        for line in plane['lines']:
            out += [[n, s, s + d] for n, s, d in line['events'] if n in names]
    return sorted(out, key=lambda e: e[1])


def window(trace):
    """(start_ns, end_ns) of the benchmark's own window span; where the
    trace holds none, the first start and last end of any device event."""
    spans = host_spans(trace, [WINDOW_SPAN])
    if spans:
        return spans[0][1], spans[0][2]
    ops = [e for evs in device_ops(trace).values() for e in evs]
    if not ops:
        raise ValueError('trace holds no device operation')
    return min(e[1] for e in ops), max(e[1] + e[2] for e in ops)


def clip(events, t0, t1):
    out = []
    for name, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append([name, a, b - a])
    return out


def busy_and_gaps(events, t0, t1):
    """Union of the events' intervals inside [t0, t1]. -> (busy_ns,
    [(gap_start, gap_end), ...]); the gaps are what is left of the
    window."""
    busy, gaps, cursor = 0.0, [], t0
    for _, s, d in sorted(clip(events, t0, t1), key=lambda e: e[1]):
        e = s + d
        if s > cursor:
            gaps.append((cursor, s))
            busy += d
            cursor = e
        elif e > cursor:
            busy += e - cursor
            cursor = e
    if cursor < t1:
        gaps.append((cursor, t1))
    return busy, gaps


_LAYOUT = re.compile(r'\{[^}]*\}')
_HLO = re.compile(r'^%?(\S+) = (\(.*?\)|\S+) ([\w\-]+)\(')
_SHAPE = re.compile(r'\w+\[[\d,]*\]')


def label(name):
    """A device operation's name is its whole HLO text; the breakdown
    prints ``instruction opcode first-shape``."""
    m = _HLO.match(_LAYOUT.sub('', name))
    if not m:
        return name[:64]
    shape = _SHAPE.search(m.group(2))
    return f"{m.group(1)} {m.group(3)} {shape.group(0) if shape else ''}"[
        :64].strip()


def self_times(events):
    """[(name, self_ns)]: an event's duration less the events nested in it
    (a while loop's body operations lie inside the loop's own event)."""
    out, stack = [], []          # stack of [name, end, self]
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= s:
            top = stack.pop()
            out.append((top[0], top[2]))
        if stack:
            stack[-1][2] -= min(d, stack[-1][1] - s)
        stack.append([name, s + d, d])
    out += [(n, t) for n, _, t in stack]
    return out


def time_by_name(events):
    totals = {}
    for name, t in self_times(events):
        totals[name] = totals.get(name, 0.0) + t
    return totals


def matching_time(events, pattern):
    """(seconds, count) of the events whose name matches ``pattern``. On a
    TPU an operation's name is its whole HLO text, opcode included."""
    rx, verdict = re.compile(pattern), {}
    total, count = 0.0, 0
    for n, _, d in events:
        if n not in verdict:
            verdict[n] = bool(rx.search(n))
        if verdict[n]:
            total, count = total + d, count + 1
    return total / 1e9, count


def attribute(gaps, spans):
    """[(span name or 'none', gap_ns)]: each gap goes to the shortest host
    span that covers its midpoint."""
    out = []
    for a, b in gaps:
        mid = (a + b) / 2
        under = [s for s in spans if s[1] <= mid < s[2]]
        name = min(under, key=lambda s: s[2] - s[1])[0] if under else 'none'
        out.append((name, b - a))
    return out


def reduce(trace, span_names):
    """The device numbers of one traced window, averaged over the devices
    that ran an operation: busy_s, window_s, idle share, breakdown."""
    t0, t1 = window(trace)
    per_device = device_ops(trace)
    spans = host_spans(trace, span_names)
    busy, gaps_all, ops_all = [], [], {}
    for dev, events in sorted(per_device.items()):
        events = clip(events, t0, t1)
        if not events:
            continue
        b, gaps = busy_and_gaps(events, t0, t1)
        busy.append(b)
        gaps_all += attribute(gaps, spans)
        for name, t in time_by_name(events).items():
            ops_all[name] = ops_all.get(name, 0.0) + t
    if not busy:
        raise ValueError('no operation ran on a device inside the window')
    n = len(busy)
    by_span = {}
    for name, g in gaps_all:
        by_span[name] = by_span.get(name, 0.0) + g
    totals = sorted(((f'all_gaps_under_{k}', v / n / 1e9)
                     for k, v in by_span.items()), key=lambda kv: -kv[1])
    singles = sorted(((f'one_gap_under_{k}', v / 1e9) for k, v in gaps_all),
                     key=lambda kv: -kv[1])
    top = sorted(ops_all.items(), key=lambda kv: -kv[1])[:10]
    return {
        'busy_s': sum(busy) / n / 1e9,
        'window_s': (t1 - t0) / 1e9,
        'devices': n,
        't0_ns': t0, 't1_ns': t1,
        'device_ops': [[label(k), v / n / 1e9] for k, v in top],
        'idle_gaps': [[k[:64], v] for k, v in
                      (totals[:4] + singles)[:10]],
        'events': {dev: clip(ev, t0, t1) for dev, ev in per_device.items()},
        'spans': spans,
        'module_runs': module_runs(trace, t0, t1),
    }
