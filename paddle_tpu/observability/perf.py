"""Performance explainability: XLA cost/memory analysis, MFU, roofline.

BENCH reports wall-time MFU but nothing attributes the gap to specific
executables. This module joins XLA's own static cost model with measured
step times into a per-executable roofline (arxiv 2104.05755's framing):

- ``analyze(label, jitted, args)`` re-enters the AOT path
  (``jitted.lower(*args).compile()`` — a cache hit after the first real
  call, no retrace) and publishes ``compiled.cost_analysis()`` /
  ``compiled.memory_analysis()`` as registry series: ``perf.flops{fn}``,
  ``perf.bytes_accessed{fn}``, ``perf.arithmetic_intensity{fn}``,
  ``perf.hbm_bytes{fn,kind}`` (kind: argument/output/temp/code), and a
  compute-vs-memory-bound verdict against the device roofline ridge.
- ``note_step(label, seconds)`` joins the static FLOPs with a measured
  wall time into ``perf.mfu`` / ``perf.mfu{fn}`` and ``perf.step_ms{fn}``.
- ``sweep_hbm()`` samples ``device.memory_stats()`` (falling back to
  summing ``jax.live_arrays()`` on backends without an allocator stats
  API, e.g. CPU) into ``perf.hbm_used_bytes{device}`` gauges, with a
  cross-sweep growth detector that increments ``perf.hbm_leak_suspect``
  after ``streak`` strictly-increasing sweeps.

Peaks come from a per-device-kind table; ``PADDLE_TPU_PEAK_FLOPS`` /
``PADDLE_TPU_PEAK_BW`` override both numbers for unlisted hardware (read
per call so tests and long-lived processes can re-point them).

Multi-device executables are accounted PER CHIP: the peak table is
per-chip, so the cost-model FLOPs joined against it must be too. Whether
``cost_analysis()`` reports per-partition or whole-module numbers for an
SPMD executable varies by XLA version, so a one-shot calibration probe
(``_cost_convention``: a 2-device-sharded matmul vs the same matmul on one
device) decides the convention once per process; under 'total' the
figures are divided by the executable's addressable device count. Records
carry ``n_devices`` and ``perf.devices{fn}`` either way, and
``perf.mfu{fn}`` is per-chip — invariant to mesh width.

Disabled mode (``PADDLE_TPU_OBS=0``): every entry point is a no-op
returning ``None`` — no compile-cache touches, no registry families.
"""
import collections
import os
import threading

from .registry import cfg, registry as _registry
from .trace import record_event

ENV_PEAK_FLOPS = 'PADDLE_TPU_PEAK_FLOPS'
ENV_PEAK_BW = 'PADDLE_TPU_PEAK_BW'
ENV_PEAK_FLOPS_FP8 = 'PADDLE_TPU_PEAK_FLOPS_FP8'
ENV_PEAK_FLOPS_INT8 = 'PADDLE_TPU_PEAK_FLOPS_INT8'

# (peak_flops/s, peak_HBM_bytes/s) by device-kind substring, checked in
# order. The v5e row is benchmark/peaks.json's; 'cpu' is nominal so
# ratios stay comparable across runs, not a physical claim. A kind that
# matches no row is an error, never a default.
PEAKS = (
    ('v6e', (918e12, 1.64e12)),
    ('v5p', (459e12, 2.76e12)),
    ('v5e', (197e12, 0.82e12)),
    ('v4', (275e12, 1.2e12)),
    ('cpu', (1e12, 100e9)),
)

# Per-precision peak FLOPs by device-kind substring: an fp8/int8 step
# measured against the bf16 peak would report a flattering MFU on parts
# whose MXU doubles low-precision throughput. Kinds absent here fall back
# to the base peak (conservative: MFU can only read lower, never inflated).
PRECISION_PEAKS = (
    ('v6e', {'fp8': 1836e12, 'int8': 1836e12}),
    ('v5p', {'int8': 918e12}),
    ('v5e', {'int8': 394e12}),
)
_PRECISION_ENV = {'fp8': ENV_PEAK_FLOPS_FP8, 'int8': ENV_PEAK_FLOPS_INT8}


def _norm_precision(precision):
    """Collapse precision spellings onto the peak-table keys: fp8 training
    and int8 weight-only serving share MXU families with 'fp8'/'int8';
    full/half-width precisions use the base (bf16) peak -> None."""
    if precision in (None, 'none', 'float32', 'bfloat16', 'float16'):
        return None
    if precision in ('fp8', 'float8'):
        return 'fp8'
    if precision in ('int8', 'int8_wo'):
        return 'int8'
    return None

_lock = threading.Lock()
_records = {}            # label -> roofline record dict
_hbm_history = {}        # device key -> deque of recent used-bytes samples
_mfu_handles = {}        # label -> (mfu_gauge, step_hist) hot-path cache

_MEM_KINDS = (('argument', 'argument_size_in_bytes'),
              ('output', 'output_size_in_bytes'),
              ('temp', 'temp_size_in_bytes'),
              ('code', 'generated_code_size_in_bytes'))


_kind_cache = None


def _device_kind():
    # cached: jax.devices() per note_step() call is measurable against the
    # obs-overhead budget, and the device set never changes in-process
    global _kind_cache
    if _kind_cache is None:
        import jax
        _kind_cache = jax.devices()[0].device_kind.lower()
    return _kind_cache


def peaks(kind=None, precision=None):
    """-> ``(peak_flops_per_s, peak_bw_bytes_per_s, source)`` for a device
    kind (default: device 0); a kind the table does not know raises
    ValueError. Env overrides win over the table; source is 'env' or
    'table'. ``precision`` ('fp8'/'float8',
    'int8'/'int8_wo') swaps in that precision's peak FLOPs where the part
    has one (``PRECISION_PEAKS``; ``PADDLE_TPU_PEAK_FLOPS_FP8``/``_INT8``
    env overrides win) so MFU denominators stay honest per precision."""
    env_f = os.environ.get(ENV_PEAK_FLOPS)
    env_b = os.environ.get(ENV_PEAK_BW)
    # a v5e chip reports its device_kind as 'TPU v5 lite'
    kind = (kind or _device_kind()).lower().replace('v5 lite', 'v5e')
    for sub, (flops, bw) in PEAKS:
        if sub in kind:
            break
    else:
        raise ValueError(
            f'no peak FLOP/s and bytes/s known for device kind {kind!r}: '
            f'add a row to observability.perf.PEAKS with its source')
    source = 'table'
    if env_f:
        flops, source = float(env_f), 'env'
    if env_b:
        bw, source = float(env_b), 'env'
    prec = _norm_precision(precision)
    if prec is not None:
        env_p = os.environ.get(_PRECISION_ENV[prec])
        if env_p:
            flops, source = float(env_p), 'env'
        else:
            for sub, table in PRECISION_PEAKS:
                if sub in kind and prec in table:
                    flops, source = table[prec], 'table'
                    break
    return flops, bw, source


def _extract(compiled):
    """Pull (flops, bytes_accessed, {kind: bytes}) out of a compiled
    executable; cost_analysis() is a list-of-dicts on current jax."""
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    flops = float(ca.get('flops', 0.0) or 0.0)
    nbytes = float(ca.get('bytes accessed', 0.0) or 0.0)
    mem = {}
    try:
        ma = compiled.memory_analysis()
        for kind, attr in _MEM_KINDS:
            mem[kind] = int(getattr(ma, attr, 0) or 0)
    except Exception:
        pass
    return flops, nbytes, mem


def _n_devices(compiled):
    """Addressable device count of one executable (1 on any failure)."""
    try:
        return max(1, len(compiled.runtime_executable().local_devices()))
    except Exception:
        return 1


_convention = None


def _cost_convention():
    """Does cost_analysis() report per-partition or whole-module numbers
    for SPMD executables? Calibrated once per process: compile the same
    matmul sharded over 2 devices and unsharded, compare FLOPs. Falls back
    to 'per_partition' (measured on the pinned jax) when <2 devices or the
    probe fails."""
    global _convention
    if _convention is not None:
        return _convention
    try:
        import numpy as np
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        devs = jax.devices()
        if len(devs) < 2:
            _convention = 'per_partition'
            return _convention
        x = jnp.ones((256, 256), jnp.float32)
        f = jax.jit(lambda a: a @ a)
        flops1 = _extract(f.lower(x).compile())[0]
        mesh = Mesh(np.asarray(devs[:2]).reshape(2), ('_probe',))
        xs = jax.device_put(x, NamedSharding(
            mesh, PartitionSpec('_probe', None)))
        flops2 = _extract(f.lower(xs).compile())[0]
        _convention = ('per_partition' if 0 < flops2 <= 0.75 * flops1
                       else 'total')
    except Exception:
        _convention = 'per_partition'
    return _convention


def _module_name(compiled):
    """The compiled HLO module name (``jit_<fn>``) — the event name this
    executable shows up under on device lanes in a profiler trace, which
    is how ``devtime.attribute`` counts its executions. None on failure."""
    try:
        mods = compiled.runtime_executable().hlo_modules()
        return mods[0].name if mods else None
    except Exception:
        return None


def analyze_compiled(label, compiled, precision=None, pyname=None):
    """Publish one compiled executable's static costs under ``fn=label``.
    All figures are PER CHIP (see module docstring) so the roofline/MFU
    join against the per-chip peak table stays honest under a mesh.
    ``precision`` tags the series (``precision=fp8/int8``) and selects that
    precision's peak for the roofline verdict; None keeps the legacy
    untagged series. Returns the roofline record (also stored for
    ``note_step``/``report``) or ``None`` when disabled / the runtime
    exposes no cost model."""
    if not cfg.enabled:
        return None
    try:
        flops, nbytes, mem = _extract(compiled)
    except Exception:
        _registry().counter('perf.analyze_errors', {'fn': label}).inc()
        return None
    n_dev = _n_devices(compiled)
    if n_dev > 1 and _cost_convention() == 'total':
        flops, nbytes = flops / n_dev, nbytes / n_dev
        mem = {k: v // n_dev for k, v in mem.items()}
    prec = _norm_precision(precision)
    peak_f, peak_bw, _ = peaks(precision=prec)
    ridge = peak_f / peak_bw
    intensity = flops / nbytes if nbytes else 0.0
    bound_by = 'compute' if intensity >= ridge else 'memory'
    lbl = {'fn': label}
    if prec is not None:
        lbl['precision'] = prec
    reg = _registry()
    reg.gauge('perf.flops', lbl).set(flops)
    reg.gauge('perf.devices', lbl).set(n_dev)
    reg.gauge('perf.bytes_accessed', lbl).set(nbytes)
    reg.gauge('perf.arithmetic_intensity', lbl).set(round(intensity, 4))
    reg.gauge('perf.compute_bound', lbl).set(
        1.0 if bound_by == 'compute' else 0.0)
    for kind, v in mem.items():
        mlbl = dict(lbl)
        mlbl['kind'] = kind
        reg.gauge('perf.hbm_bytes', mlbl).set(v)
    reg.gauge('perf.peak_flops').set(peak_f)
    reg.gauge('perf.peak_bw').set(peak_bw)
    reg.gauge('perf.ridge').set(round(ridge, 4))
    rec = {'fn': label, 'flops': flops, 'bytes_accessed': nbytes,
           'n_devices': n_dev, 'intensity': round(intensity, 4),
           'bound_by': bound_by, 'hbm': mem, 'mfu': None,
           'step_ms_p50': None, 'precision': prec,
           'module': _module_name(compiled), 'pyname': pyname}
    with _lock:
        _records[label] = rec
        _mfu_handles.pop(label, None)
    return rec


def analyze(label, jitted, args=(), kwargs=None, precision=None):
    """Analyze a jitted callable at a signature it has already executed.

    Passing the *same concrete arguments* as the live call guarantees
    ``lower().compile()`` is a pure cache hit (no retrace, no recompile —
    deleted/donated buffers are fine, only avals are read). Analysis
    failures are counted (``perf.analyze_errors{fn}``), never raised into
    the training/serving path.
    """
    if not cfg.enabled:
        return None
    try:
        compiled = jitted.lower(*args, **(kwargs or {})).compile()
    except Exception:
        _registry().counter('perf.analyze_errors', {'fn': label}).inc()
        return None
    pyname = getattr(jitted, '__name__', None)
    return analyze_compiled(label, compiled, precision=precision,
                            pyname=pyname)


def analyzed(label):
    """The stored roofline record for ``label`` (or None) — cheap probe the
    wiring sites use to analyze each executable exactly once."""
    with _lock:
        return _records.get(label)


def records():
    """Copies of every stored roofline record, keyed by label — the join
    source for ``devtime.attribute``'s measured-MFU computation."""
    with _lock:
        return {k: dict(v) for k, v in _records.items()}


def note_step(label, seconds, precision=None):
    """Join a measured wall-time with ``label``'s static per-chip FLOPs:
    observes ``perf.step_ms{fn}`` and sets ``perf.mfu{fn}`` (per-chip —
    mesh-width invariant) + the headline ``perf.mfu`` gauge. The MFU
    denominator uses the record's precision peak (``analyze``'s
    ``precision=``, overridable here). No-op (still timing-safe) before
    ``analyze``."""
    if not cfg.enabled or seconds <= 0:
        return None
    with _lock:
        rec = _records.get(label)
        handles = _mfu_handles.get(label)
    if rec is None:
        return None
    prec = _norm_precision(precision) or rec.get('precision')
    if handles is None:
        reg = _registry()
        lbl = {'fn': label}
        if prec is not None:
            lbl['precision'] = prec
        handles = (reg.gauge('perf.mfu', lbl), reg.gauge('perf.mfu'),
                   reg.histogram('perf.step_ms', lbl),
                   reg.gauge('perf.achieved_flops', lbl))
        with _lock:
            _mfu_handles[label] = handles
    mfu_g, mfu_top, step_h, ach_g = handles
    peak_f, _, _ = peaks(precision=prec)
    achieved = rec['flops'] / seconds
    mfu = achieved / peak_f
    step_h.observe(1e3 * seconds)
    mfu_g.set(round(mfu, 6))
    mfu_top.set(round(mfu, 6))
    ach_g.set(achieved)
    with _lock:
        # p50 is NOT refreshed here: percentile() sorts the whole window,
        # too expensive per step — report() computes it on demand
        rec['mfu'] = round(mfu, 6)
    return mfu


def _live_bytes_by_device():
    import jax
    used = {}
    for arr in jax.live_arrays():
        try:
            devs = list(arr.devices())
            share = arr.nbytes // max(1, len(devs))
            for d in devs:
                used[d] = used.get(d, 0) + share
        except Exception:
            continue
    return used


def sweep_hbm(devices=None, streak=3):
    """Sample per-device memory into ``perf.hbm_used_bytes{device}``.

    Uses the allocator's ``memory_stats()['bytes_in_use']`` where the
    backend provides it; otherwise (CPU) sums ``jax.live_arrays()``. A
    device whose usage grows strictly for ``streak`` consecutive sweeps
    increments ``perf.hbm_leak_suspect{device}`` and emits a trace event;
    the history then resets so one leak fires once per streak, not every
    subsequent sweep. Returns ``{device_key: used_bytes}``.
    """
    if not cfg.enabled:
        return None
    import jax
    devices = list(devices) if devices is not None else jax.devices()
    live = None
    reg = _registry()
    out = {}
    for d in devices:
        stats = None
        try:
            stats = d.memory_stats()
        except Exception:
            pass
        if stats and 'bytes_in_use' in stats:
            used = int(stats['bytes_in_use'])
        else:
            if live is None:
                live = _live_bytes_by_device()
            used = int(live.get(d, 0))
        key = f'{d.platform}:{d.id}'
        out[key] = used
        reg.gauge('perf.hbm_used_bytes', {'device': key}).set(used)
        with _lock:
            hist = _hbm_history.get(key)
            if hist is None or hist.maxlen != streak + 1:
                hist = collections.deque(maxlen=streak + 1)
                _hbm_history[key] = hist
            hist.append(used)
            growing = (len(hist) == streak + 1 and
                       all(b > a for a, b in zip(hist, list(hist)[1:])))
            if growing:
                hist.clear()
                hist.append(used)
        if growing:
            reg.counter('perf.hbm_leak_suspect', {'device': key}).inc()
            record_event('perf.hbm_leak_suspect', device=key, bytes=used)
    return out


def report():
    """Roofline records joined with peaks — the dict behind
    ``tools/perf_report.py``."""
    if not cfg.enabled:
        return None
    peak_f, peak_bw, source = peaks()
    reg = _registry()
    with _lock:
        rows = [dict(r) for r in _records.values()]
    for r in rows:
        h = reg.find('perf.step_ms', {'fn': r['fn']})
        if h is not None:
            r['step_ms_p50'] = h.percentile(50)
        ach = (r['flops'] * 1e3 / r['step_ms_p50']
               if r.get('step_ms_p50') else None)
        r['achieved_flops_per_s'] = ach
        r['frac_of_peak'] = round(ach / peak_f, 4) if ach else None
    rows.sort(key=lambda r: -r['flops'])
    return {'device_kind': _device_kind(), 'peak_flops': peak_f,
            'peak_bw': peak_bw, 'peak_source': source,
            'ridge': round(peak_f / peak_bw, 4), 'executables': rows}


def reset_perf():
    """Drop stored records + HBM histories (tests, run restarts)."""
    with _lock:
        _records.clear()
        _hbm_history.clear()
        _mfu_handles.clear()
