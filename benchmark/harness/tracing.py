"""Trace the last seconds of the measured window. The profiler is started
from a helper thread shortly before the window's end and stopped after the
window has closed, so that stopping (which is slow) never falls inside it.
The traced stretch is marked by a span of the benchmark's own,
``bench.trace_window``, which the reduction clips everything to."""
import os
import shutil
import threading
import time

import jax

from . import trace as _trace


def span(name):
    """A host span on the profiler's own clock (free when nothing traces)."""
    return jax.profiler.TraceAnnotation(name)


class TailTrace:
    def __init__(self, out_dir, seconds):
        self.dir = os.path.join(out_dir, 'trace')
        self.seconds = seconds
        self._stop = threading.Event()
        self._thread = None
        self.error = None
        self.t_start = self.t_stop = None   # time.perf_counter() of the span

    def arm(self, window_end):
        """window_end: time.perf_counter() at which the window closes."""
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        self._thread = threading.Thread(
            target=self._run, args=(window_end,), name='bench-trace',
            daemon=True)
        self._thread.start()

    def _run(self, window_end):
        try:
            delay = window_end - self.seconds - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            with span(_trace.WINDOW_SPAN):
                self.t_start = time.perf_counter()
                self._stop.wait()
                self.t_stop = time.perf_counter()
        except BaseException as e:      # relayed by finish()
            self.error = e

    def finish(self, span_names):
        """Called once the window has closed. -> the reduced trace."""
        self._stop.set()
        self._thread.join()
        if self.error is not None:
            raise self.error
        jax.profiler.stop_trace()
        loaded = _trace.load_xplane(_trace.find_xplane(self.dir), span_names)
        return loaded
