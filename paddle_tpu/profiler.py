"""Profiler (reference python/paddle/fluid/profiler.py).

The reference profiles per-op kernel launches and can emit a chrome
tracing timeline (reference python/paddle/fluid/profiler.py:221,
paddle/fluid/platform/profiler.cc). Under XLA there is one fused
executable per program, so there is ONE timeline: the XLA trace
(jax.profiler; ``<profile_path>/plugins/profile/<time>/`` holds an
``.xplane.pb`` for TensorBoard / ``jax.profiler.ProfileData`` and a
``.trace.json.gz`` that Perfetto and chrome://tracing open). Host spans
(``record_event``) land in it on the same clock as the device's
"XLA Ops" and "XLA Modules" lines; the program's own spans are named
``pt:<layer>/<what>`` (docs/SERVING.md, "Metrics reference").
``profiler`` / ``start_profiler`` / ``stop_profiler`` keep the
reference's names and print the host-side summary per region.
"""
import contextlib
import time

import jax

__all__ = ["cuda_profiler", "reset_profiler", "start_profiler",
           "stop_profiler", "profiler", "record_event"]

_records = []          # (name, seconds), only while a session is open
_active = None         # (state, trace_dir, t0)
_depth = 0             # nesting level; only the outermost start/stop act


@contextlib.contextmanager
def cuda_profiler(output_file, output_mode=None, config=None):
    """No CUDA here; kept for source compatibility — delegates to the
    XLA trace profiler with ``output_file`` as the trace directory."""
    with profiler("All", profile_path=output_file):
        yield


def reset_profiler():
    _records.clear()


def start_profiler(state, profile_path="/tmp/paddle_tpu_profile"):
    """state: 'CPU' | 'GPU' | 'All' (accepted for parity; all mean the
    same thing — trace the XLA device)."""
    global _active, _depth
    if state not in ("CPU", "GPU", "All"):
        raise ValueError("state must be 'CPU', 'GPU' or 'All'")
    _depth += 1
    if _active is not None:
        return
    trace_dir = profile_path
    try:
        jax.profiler.start_trace(trace_dir)
    except Exception:          # tracing unavailable (e.g. nested) — keep timers
        trace_dir = None
    _active = (state, trace_dir, time.perf_counter())


def stop_profiler(sorted_key=None, profile_path="/tmp/paddle_tpu_profile"):
    global _active, _depth
    if _active is None:
        return
    _depth = max(0, _depth - 1)
    if _depth > 0:          # inner stop of a nested session: outer still owns it
        return
    state, trace_dir, t0 = _active
    _active = None
    if trace_dir is not None:
        try:
            jax.profiler.stop_trace()
        except Exception:
            pass
    _records.append(("<session>", time.perf_counter() - t0))
    _print_summary(sorted_key)


def _print_summary(sorted_key):
    rows = list(_records)
    if sorted_key in ("total", "max", "ave"):
        rows.sort(key=lambda r: r[1], reverse=True)
    width = max([len(n) for n, _ in rows] + [8])
    print(f"{'Event':<{width}}  Time(s)")
    for name, secs in rows:
        print(f"{name:<{width}}  {secs:.6f}")


@contextlib.contextmanager
def profiler(state="All", sorted_key=None,
             profile_path="/tmp/paddle_tpu_profile"):
    start_profiler(state, profile_path)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


class record_event:
    """The one way to open a host span: a ``jax.profiler.
    TraceAnnotation`` named ``name`` with ``attrs`` as its metadata, so
    a running profiler trace (this module's session or any
    ``jax.profiler.start_trace``) holds it on the device's clock, and
    nothing is recorded when none runs. A span opened inside another on
    the same thread is its child. ``seconds`` is the span's own
    duration once it has closed, for a counter that must share the
    span's boundaries; inside a ``profiler`` session the span is also a
    row of the printed summary. Attribute values are numbers or text
    without commas (the trace's metadata is comma-separated)."""

    __slots__ = ("name", "seconds", "_annotation", "_t0")

    def __init__(self, name, **attrs):
        self.name = name
        self.seconds = None
        self._annotation = jax.profiler.TraceAnnotation(name, **attrs)

    def __enter__(self):
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        if _active is not None:
            _records.append((self.name, self.seconds))
        return False
