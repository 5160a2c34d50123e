"""The run's own profiler trace, read a second time for what the runner's
reduction leaves out: the scope path the program gave every device
operation, and the program's own host spans with their attributes.

The runner keeps three span names of its own and no more, and it is not this
module's to edit. So the readers that need more ask ``load``: the newest
``.xplane.pb`` under ``.bench_out/*/trace/``, accepted only if its
``bench.trace_window`` span starts where the runner's reduction says this
run's window started; read once a process, and joined, clipped to the
window and reduced to self times once a run. A trace that is not this run's
gives None, and the reader gives no number.

Where the scope path lives (settled on the chip, PERF.md section 6): a TPU
operation's event carries its HLO text as its name and three timing
statistics, no ``op_name``. The path (``jit(step)/transpose(jvp(gpt.layers))
/while/body/closed_call/checkpoint/gpt.block/attn/flash_bwd_dq/
pallas_call``) is the instruction's ``metadata.op_name`` in the module's HLO
proto, which the profiler stores once a program in the ``/host:metadata``
plane (statistic ``Hlo Proto``). jax's ``ProfileData`` does not show that
plane's content, so ``hlo_scopes`` walks the protobuf wire format itself:
five message types, field numbers in the comments.

Neutral forms (plain lists and dicts, so a fixture is a JSON file):

    read_file -> {'ops':     {device: [[name, start_ns, dur_ns], ...]},
                  'modules': {device: [[name, start_ns, dur_ns], ...]},
                  'scopes':  {module: {instruction: op_name}},
                  'spans':   [[name, start_ns, dur_ns, {attribute: value}]]}
    of_run    -> {'t0_ns', 't1_ns', 'window_s', 'devices', 'busy_s',
                  'ops':   {device: [[name, start_ns, dur_ns, path], ...]},
                  'self':  {device: [(name, self_ns, path), ...]},
                  'spans': [...]}

``busy_s`` is the runner's own (a device's mean), so that a share of busy
time here has the denominator ``flash_time_share`` has.
"""
import bisect
import glob
import os

from . import trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# path -> [read_file's form, {t0_ns: of_run's form}] (one trace a process)
_loaded = {}


def newest(root=ROOT):
    """Path of the newest .xplane.pb any cell of this checkout wrote."""
    found = glob.glob(os.path.join(root, '.bench_out', '*', 'trace',
                                   'plugins', 'profile', '*', '*.xplane.pb'))
    return max(found, key=os.path.getmtime) if found else None


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint
    or a fixed-width field, a memoryview for a length-delimited one."""
    i, n = 0, len(buf)

    def varint():
        nonlocal i
        v = shift = 0
        while True:
            b = buf[i]
            i += 1
            v |= (b & 0x7f) << shift
            shift += 7
            if b < 0x80:
                return v
    while i < n:
        key = varint()
        num, wire = key >> 3, key & 7
        if wire == 0:
            yield num, varint()
        elif wire == 2:
            size = varint()
            yield num, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            yield num, int.from_bytes(buf[i:i + size], 'little')
            i += size
        else:
            raise ValueError(f'protobuf wire type {wire}')


def _get(buf, num):
    return [v for n, v in _fields(buf) if n == num]


def _text(buf, num):
    got = _get(buf, num)
    return bytes(got[-1]).decode() if got else ''


def _walk(buf, *nums):
    """Every sub-message reached from ``buf`` through this chain of field
    numbers."""
    level = [buf]
    for num in nums:
        level = [v for b in level for v in _get(b, num)]
    return level


def hlo_scopes(path):
    """{module name as the profiler names it, 'jit_step(<id>)':
    {instruction name: op_name}} from the HLO protos in ``/host:metadata``.

    XSpace.planes=1; XPlane.name=2, .event_metadata=4 (map: value=2);
    XEventMetadata.name=2, .stats=5; XStat.bytes_value=6;
    HloProto.hlo_module=1; HloModuleProto.computations=3;
    HloComputationProto.instructions=2; HloInstructionProto.name=1,
    .metadata=7; OpMetadata.op_name=2."""
    with open(path, 'rb') as f:
        space = memoryview(f.read())
    out = {}
    for plane in _get(space, 1):
        if _text(plane, 2) != '/host:metadata':
            continue
        for meta in _walk(plane, 4, 2):
            names = {_text(ins, 1): ''.join(_text(md, 2)
                                            for md in _get(ins, 7))
                     for ins in _walk(meta, 5, 6, 1, 3, 2)}
            if names:
                out[_text(meta, 2)] = names
    return out


def read_file(path):
    """The file in the first neutral form, unclipped."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    ops, modules, spans = {}, {}, []
    for plane in data.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (trace.OPS_LINE, trace.MODULES_LINE):
                into = ops if line.name == trace.OPS_LINE else modules
                into[int(m.group(1))] = [
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events]
            elif not m:
                spans += [[e.name, float(e.start_ns), float(e.duration_ns),
                           dict(e.stats)] for e in line.events]
    return {'ops': ops, 'modules': modules, 'scopes': hlo_scopes(path),
            'spans': sorted(spans, key=lambda e: e[1])}


def clip(events, t0, t1):
    """Events cut to [t0, t1]; whatever follows start and duration is kept."""
    out = []
    for name, s, d, *rest in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append([name, a, b - a, *rest])
    return out


def _instruction(name):
    """'%flash_fwd.12 = (bf16[...' -> 'flash_fwd.12'."""
    end = name.find(' = ')
    return name[1:end] if name.startswith('%') and end > 0 else name


def with_paths(ops, modules, scopes):
    """[[name, start, dur, path]]: each operation beside the op_name its
    instruction has in the module that was running when it started."""
    by_prefix = {k.split('(')[0]: v for k, v in scopes.items()}
    runs = sorted(modules, key=lambda e: e[1])
    starts = [r[1] for r in runs]
    out = []
    for name, s, d in ops:
        i = bisect.bisect_right(starts, s) - 1
        names = {}
        if i >= 0 and s < runs[i][1] + runs[i][2]:
            mod = runs[i][0]
            names = scopes.get(mod) or by_prefix.get(mod.split('(')[0], {})
        out.append([name, s, d, names.get(_instruction(name), '')])
    return out


def of_run(raw, reduced):
    """``raw`` (read_file's form) joined and clipped to the window of
    ``reduced`` (the runner's reduction), or None where the trace's window
    span does not start at this run's."""
    t0, t1 = reduced['t0_ns'], reduced['t1_ns']
    if not any(e[0] == trace.WINDOW_SPAN and abs(e[1] - t0) < 1.0
               for e in raw['spans']):
        return None
    ops = {}
    for dev, events in raw['ops'].items():
        events = clip(with_paths(events, raw['modules'].get(dev, []),
                                 raw['scopes']), t0, t1)
        if events:
            ops[dev] = events
    return {'t0_ns': t0, 't1_ns': t1, 'window_s': reduced['window_s'],
            'devices': reduced['devices'], 'busy_s': reduced['busy_s'],
            'ops': ops,
            'self': {dev: self_times(ev) for dev, ev in ops.items()},
            'spans': clip(raw['spans'], t0, t1)}


def load(reduced, root=ROOT):
    """This run's trace in the neutral form, or None: no traced run, no
    trace on disk, or the newest trace is another run's."""
    if reduced is None:
        return None
    path = newest(root)
    if path is None:
        return None
    if path not in _loaded:
        _loaded.clear()
        _loaded[path] = [read_file(path), {}]
    raw, runs = _loaded[path]
    if reduced['t0_ns'] not in runs:
        runs[reduced['t0_ns']] = of_run(raw, reduced)
    return runs[reduced['t0_ns']]


def self_times(events):
    """[(name, self_ns, path)]: harness/trace.self_times with the scope
    path kept. An operation with no path of its own takes that of the event
    it lies in (a copy the compiler made inside a loop's body belongs to
    the loop, whose own event covers the whole loop)."""
    out, stack = [], []          # stack of [name, end, self, path]
    for name, s, d, path in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= s:
            top = stack.pop()
            out.append((top[0], top[2], top[3]))
        if stack:
            stack[-1][2] -= min(d, stack[-1][1] - s)
        stack.append([name, s + d, d,
                      path or (stack[-1][3] if stack else '')])
    out += [(n, t, path) for n, _, t, path in stack]
    return out
