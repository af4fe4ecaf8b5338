"""The traced sub-window of a ``--trace 1`` run.

The profiler is on for ``trace_seconds`` in the middle of the measured
window, never for all of it: traces are large and tracing slows the host.
Host spans come from the benchmark's own files (``span``), around its calls
into each layer; spans inside the program are a later tracing issue's.
"""
import contextlib
import glob
import os
import shutil
import tempfile
import time

import jax

from . import trace_reduce

TRACE_SECONDS = 8.0     # device events of a few hundred decode steps or
                        # some tens of training steps: enough to average,
                        # small enough to come back and be read in seconds


def span(name):
    """A host span on the profiler's clock (a no-op when it is off)."""
    return jax.profiler.TraceAnnotation("bench:" + name)


class Tracer:
    """start()/stop() around the traced part; reduce() afterwards.
    Disabled, every method does nothing and reduce() returns None."""

    def __init__(self, enabled):
        self.enabled = bool(enabled)
        self.dir = None
        self.started = self.stopped = None

    def start(self):
        if not self.enabled or self.started is not None:
            return
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # no Python frames: they slow
        opts.host_tracer_level = 2        # the host; keep TraceMe spans
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.started = time.monotonic()

    def stop(self):
        if not self.enabled or self.started is None \
                or self.stopped is not None:
            return
        self.stopped = time.monotonic()
        jax.profiler.stop_trace()

    @contextlib.contextmanager
    def around(self):
        self.start()
        try:
            yield
        finally:
            self.stop()

    def reduce(self, n_devices):
        """The reduced trace (see trace_reduce.reduce_events), or None
        where nothing was traced."""
        if not self.enabled or self.stopped is None:
            return None
        try:
            paths = sorted(glob.glob(
                os.path.join(self.dir, "**", "*.xplane.pb"),
                recursive=True))
            if not paths:
                return None
            events = trace_reduce.read_xplane(paths[-1])
            return trace_reduce.reduce_events(
                events, window_s=self.stopped - self.started,
                n_devices=n_devices)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
