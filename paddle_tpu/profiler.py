"""Profiler (reference python/paddle/fluid/profiler.py).

The reference profiles per-op kernel launches and can emit a chrome
tracing timeline (reference python/paddle/fluid/profiler.py:221,
paddle/fluid/platform/profiler.cc). Under XLA there is one fused
executable per program, so the useful signals are (a) the XLA trace
(jax.profiler, viewable in TensorBoard/Perfetto), (b) host-side
compile/step wall-times per region, and (c) a chrome://tracing
timeline of executor dispatches + record_event regions, written by
``stop_profiler`` / ``export_chrome_tracing``. ``profiler`` /
``start_profiler`` / ``stop_profiler`` keep the reference's names.
"""
import contextlib
import json
import os
import time

import jax

__all__ = ["cuda_profiler", "reset_profiler", "start_profiler",
           "stop_profiler", "profiler", "record_event",
           "export_chrome_tracing", "device_kernel_profile"]

_records = []          # (name, seconds)
_events = []           # chrome-trace events: dicts with name/ts/dur (us)
_active = None         # (state, trace_dir, t0)
_depth = 0             # nesting level; only the outermost start/stop act

# Wall-clock anchor pairing one time.time_ns() with one
# time.perf_counter(): perf_counter's origin is arbitrary per process,
# so timeline ts are emitted as epoch-anchored microseconds — timelines
# from different processes (or the XLA device trace) share a timebase.
_EPOCH_NS = time.time_ns()
_EPOCH_PERF = time.perf_counter()


def _to_epoch_us(perf_seconds):
    return _EPOCH_NS / 1e3 + (perf_seconds - _EPOCH_PERF) * 1e6


def profiling_active():
    """True while a profiler session is open (the Executor uses this to
    decide whether to record dispatch timeline events)."""
    return _active is not None


def add_timeline_event(name, t0, t1, tid="executor", args=None):
    """Record one complete chrome-trace slice ('X' phase). ``t0``/``t1``
    are time.perf_counter() seconds; stored as epoch-anchored
    microseconds (see ``_EPOCH_NS``) as the chrome tracing spec
    wants."""
    ev = {"name": name, "ph": "X", "ts": _to_epoch_us(t0),
          "dur": max(0.0, (t1 - t0) * 1e6), "pid": os.getpid(),
          "tid": tid}
    if args:
        ev["args"] = args
    _events.append(ev)


@contextlib.contextmanager
def cuda_profiler(output_file, output_mode=None, config=None):
    """No CUDA here; kept for source compatibility — delegates to the
    XLA trace profiler with ``output_file`` as the trace directory."""
    with profiler("All", profile_path=output_file):
        yield


def reset_profiler():
    _records.clear()
    _events.clear()


def start_profiler(state, profile_path="/tmp/paddle_tpu_profile"):
    """state: 'CPU' | 'GPU' | 'All' (accepted for parity; all mean the
    same thing — trace the XLA device)."""
    global _active, _depth
    if state not in ("CPU", "GPU", "All"):
        raise ValueError("state must be 'CPU', 'GPU' or 'All'")
    _depth += 1
    if _active is not None:
        return
    # the timeline file is PER SESSION (unlike _records, whose
    # cross-session aggregate matches the reference's summary): a new
    # outermost session starts a fresh trace
    _events.clear()
    trace_dir = profile_path
    try:
        jax.profiler.start_trace(trace_dir)
    except Exception:          # tracing unavailable (e.g. nested) — keep timers
        trace_dir = None
    _active = (state, trace_dir, time.perf_counter(), time.time())


def stop_profiler(sorted_key=None, profile_path="/tmp/paddle_tpu_profile"):
    global _active, _depth
    if _active is None:
        return
    _depth = max(0, _depth - 1)
    if _depth > 0:          # inner stop of a nested session: outer still owns it
        return
    state, trace_dir, t0, wall0 = _active
    _active = None
    if trace_dir is not None:
        try:
            jax.profiler.stop_trace()
        except Exception:
            pass
    total = time.perf_counter() - t0
    _records.append(("<session>", total))
    if profile_path:
        try:
            export_chrome_tracing(os.path.join(profile_path,
                                               "host_timeline.json"))
        except OSError:
            pass               # unwritable path: keep the printed summary
    _print_summary(sorted_key)
    if trace_dir is not None and _has_trace_since(trace_dir, wall0):
        # device-side view of the same session (the reference's
        # device_tracer summary): top kernels by actual device time.
        # Gated on an xplane file written SINCE this session started —
        # a reused trace_dir with a leftover file from an earlier
        # session (e.g. when stop_trace failed) must not be reported
        # as this session's device view.
        try:
            prof = device_kernel_profile(trace_dir, top_k=10)
        except Exception:
            prof = None        # parsing must never break a session
        if prof and prof["n_kernels"]:
            print(f"Device kernels: {prof['n_kernels']} events, "
                  f"{prof['device_total_ms']:.3f} ms total")
            for k in prof["top_kernels"]:
                print(f"  {k['total_ms']:10.3f} ms  x{k['count']:<6} "
                      f"{k['name']}")


def export_chrome_tracing(path):
    """Write the host-side timeline (executor dispatches + record_event
    regions) as chrome://tracing / Perfetto-loadable JSON — the
    reference's profile-proto → chrome-trace path, host-side. The XLA
    device timeline itself lives in the jax trace directory."""
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path, "w") as f:
        json.dump({"traceEvents": _events,
                   "displayTimeUnit": "ms"}, f)
    return path


def _has_trace_since(trace_dir, wall0):
    import glob as _glob
    paths = _glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                       recursive=True)
    try:
        return any(os.path.getmtime(p) >= wall0 - 1.0 for p in paths)
    except OSError:
        return False


def device_kernel_profile(trace_dir, top_k=25):
    """Parse a jax.profiler trace directory (written by a
    ``profiler()`` session or ``jax.profiler.start_trace``) into
    per-kernel DEVICE durations — the reference device_tracer's role
    (paddle/fluid/platform/device_tracer.cc: CUPTI activity records →
    per-op device spans) done the XLA way, from the xplane proto.

    Returns {"planes": [names...], "device_total_ms", "n_kernels",
    "top_kernels": [{"name", "total_ms", "count"}...]} for the first
    device plane found, or None when the trace holds no device plane
    (e.g. a CPU-only run). tools/device_profile.py is the CLI
    harness; not re-verified on this installation."""
    import glob as _glob
    import re as _re
    paths = _glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                       recursive=True)
    if not paths:
        return None
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2
    except ImportError:                      # tf not in this image
        return None
    space = xplane_pb2.XSpace()
    with open(sorted(paths)[-1], "rb") as f:
        space.ParseFromString(f.read())
    planes = [p.name for p in space.planes]
    device = next((p for p in space.planes
                   if "/device:" in p.name and "CUSTOM" not in p.name
                   and any(len(ln.events) for ln in p.lines)), None)
    if device is None:
        return {"planes": planes, "device_total_ms": 0.0,
                "n_kernels": 0, "top_kernels": []}
    meta = {i: m.name for i, m in device.event_metadata.items()}
    agg = {}
    # the "XLA Ops" line carries the real kernel occupancy; async lines
    # duplicate spans as wall-intervals and would overcount. Some
    # profiler versions spell the line "Ops" — accept either, but pick
    # exactly ONE name per plane: a plane carrying both spellings for
    # the same spans must not double-count kernel time.
    line_names = {ln.name for ln in device.lines}
    pick = "XLA Ops" if "XLA Ops" in line_names else "Ops"
    for line in device.lines:
        if line.name != pick:
            continue
        for ev in line.events:
            nm = meta.get(ev.metadata_id, str(ev.metadata_id))
            # event names are full HLO expressions; key on the defined
            # op (lhs) so operand text can't alias kernels together
            key = _re.sub(r"[.\d]+$", "",
                          nm.partition(" = ")[0].lstrip("%")) or nm[:40]
            ms = ev.duration_ps / 1e9
            tot, cnt = agg.get(key, (0.0, 0))
            agg[key] = (tot + ms, cnt + 1)
    top = sorted(agg.items(), key=lambda kv: -kv[1][0])[:top_k]
    return {
        "planes": planes,
        "device_total_ms": round(sum(t for t, _ in agg.values()), 3),
        "n_kernels": sum(c for _, c in agg.values()),
        "top_kernels": [{"name": n, "total_ms": round(t, 3), "count": c}
                        for n, (t, c) in top],
    }


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


@contextlib.contextmanager
def record_event(name):
    """Host-side named timer; shows up in the printed summary, the
    chrome timeline, and (when a trace is active) as a TraceAnnotation
    in the XLA timeline."""
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        t1 = time.perf_counter()
        _records.append((name, t1 - t0))
        if _active is not None:
            add_timeline_event(name, t0, t1, tid="events")
