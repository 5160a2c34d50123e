"""Persistent XLA compile cache, placed from outside.

Where the cache lives is the environment's decision, not the program's:

 - ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself. This
   module sets no directory and appends nothing to it; it only counts
   hits and misses.
 - not set: ``ensure_persistent_cache()`` puts the cache at one fixed path
   inside the checkout (``<repo>/.jax_cache``, git-ignored). It never
   moves — a temp name, pid or timestamp in the path would never hit —
   and it needs no version component: JAX's own cache key already covers
   the jax/jaxlib versions, the backend and the device kind.

The serving engines and hapi Model call ``ensure_persistent_cache()`` on
construction. ``enable_persistent_cache(dir)`` points the cache at an
explicit directory (tests, tools). Activation is corruption tolerant: the
directory probe runs under ``fault.retry`` with a ``warmup.cache`` inject
point, and any persistent failure degrades to cold in-process compiles
with a warning instead of taking the run down. Individual corrupt cache
*entries* are handled by JAX itself
(``jax_raise_persistent_cache_errors=False`` → the entry is recompiled,
never raised).

Cache traffic is observable: JAX's monitoring events are forwarded into
the registry as ``warmup.cache.hit_total`` / ``warmup.cache.miss_total``
counters, and ``cache_stats()`` reports entry count / on-disk bytes (also
exported as ``warmup.cache.bytes`` / ``warmup.cache.entries`` gauges).
"""
import os
import threading
import warnings

import jax

from .. import fault
from .. import observability as _obs

JAX_ENV_CACHE_DIR = 'JAX_COMPILATION_CACHE_DIR'
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), '.jax_cache')

_HIT_EVENT = '/jax/compilation_cache/cache_hits'
_MISS_EVENT = '/jax/compilation_cache/cache_misses'

_lock = threading.Lock()
_cache_dir = None
_listener_installed = False
_ensured = False


def _on_monitoring_event(name, **kwargs):
    if name == _HIT_EVENT:
        _obs.counter('warmup.cache.hit_total').inc()
    elif name == _MISS_EVENT:
        _obs.counter('warmup.cache.miss_total').inc()


def _reset_jax_cache():
    """Drop JAX's in-memory cache singleton so the next compile
    re-initializes it from the just-updated config — the singleton is
    pinned at first compile, so enabling mid-process (or re-pointing the
    dir) is silently ignored without this."""
    try:
        from jax.experimental.compilation_cache import (
            compilation_cache as _cc)
        _cc.reset_cache()
    except Exception:
        pass


def _install_listener():
    global _listener_installed
    with _lock:
        if _listener_installed:
            return
        try:
            jax.monitoring.register_event_listener(_on_monitoring_event)
            _listener_installed = True
        except Exception:
            # monitoring API unavailable: counters stay 0, cache still works
            pass


def enable_persistent_cache(directory, *, min_compile_time_secs=0.0):
    """Enable the on-disk compile cache at ``directory``. Returns the
    directory, or None when it is unusable — the process then falls back
    to cold compiles and keeps running."""
    global _cache_dir, _ensured
    _ensured = True          # an explicit placement is never re-placed
    resolved = os.path.abspath(os.path.expanduser(str(directory)))

    def _activate():
        fault.inject('warmup.cache')
        os.makedirs(resolved, exist_ok=True)
        # Write probe: catch read-only mounts / quota exhaustion / a file
        # squatting on the path now, not at the first compile. Dot-named,
        # so it cannot be mistaken for a cache entry.
        probe = os.path.join(resolved, f'.probe.{os.getpid()}')
        with open(probe, 'w') as f:
            f.write('ok')
        os.remove(probe)
        jax.config.update('jax_compilation_cache_dir', resolved)
        jax.config.update('jax_persistent_cache_min_compile_time_secs',
                          float(min_compile_time_secs))
        jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)
        # A corrupt/unreadable entry must mean "recompile", never "crash".
        jax.config.update('jax_raise_persistent_cache_errors', False)
        _reset_jax_cache()

    try:
        fault.retry(_activate, retries=3, backoff=0.05,
                    exceptions=(OSError, fault.InjectedFault))
    except Exception as e:
        warnings.warn(
            f'paddle_tpu.warmup: persistent compile cache unavailable at '
            f'{resolved!r} ({e!r}); continuing with cold compiles',
            RuntimeWarning, stacklevel=2)
        _obs.counter('warmup.cache.fallback_total').inc()
        with _lock:
            _cache_dir = None
        return None
    _install_listener()
    with _lock:
        _cache_dir = resolved
    return resolved


def disable_persistent_cache():
    """Detach the on-disk cache (compiles stay in-process only)."""
    global _cache_dir, _ensured
    with _lock:
        _cache_dir = None
        _ensured = True      # an explicit off is not undone by ensure
    try:
        jax.config.update('jax_compilation_cache_dir', None)
        _reset_jax_cache()
    except Exception:
        pass


def persistent_cache_dir():
    """The active resolved cache path, or None."""
    return _cache_dir


def ensure_persistent_cache():
    """Idempotent activation, once per process. With
    ``JAX_COMPILATION_CACHE_DIR`` set the directory is JAX's business and
    only the hit/miss listener is installed; otherwise the cache goes to
    ``DEFAULT_CACHE_DIR``. A failed attempt is remembered so construction
    paths don't retry the probe forever. Returns the active directory."""
    global _ensured, _cache_dir
    if _ensured:
        return _cache_dir
    placed = os.environ.get(JAX_ENV_CACHE_DIR)
    if placed:
        _ensured = True
        _install_listener()
        with _lock:
            _cache_dir = placed
        return placed
    return enable_persistent_cache(DEFAULT_CACHE_DIR)


def cache_stats():
    """Hit/miss counters plus on-disk entry count and bytes of the active
    cache dir. Also refreshes the ``warmup.cache.bytes``/``entries``
    gauges."""
    directory = _cache_dir
    stats = {'dir': directory, 'entries': 0, 'bytes': 0,
             'hit_total': _obs.counter('warmup.cache.hit_total').value,
             'miss_total': _obs.counter('warmup.cache.miss_total').value}
    if directory and os.path.isdir(directory):
        for root, _dirs, files in os.walk(directory):
            for name in files:
                try:
                    stats['bytes'] += os.path.getsize(
                        os.path.join(root, name))
                    stats['entries'] += 1
                except OSError:
                    continue
    _obs.gauge('warmup.cache.bytes').set(stats['bytes'])
    _obs.gauge('warmup.cache.entries').set(stats['entries'])
    return stats
