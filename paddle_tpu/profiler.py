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

The same module keeps the COMPILE LOG: one entry for every executable
an executor compiles, by phase, written where the compile happens
(``compile_log`` / ``compile_totals``; the bottom of this file).
"""
import contextlib
import threading
import time

import jax

__all__ = ["cuda_profiler", "reset_profiler", "start_profiler",
           "stop_profiler", "profiler", "record_event", "compile_log",
           "compile_totals"]

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
    span's boundaries, and ``t0`` the ``time.monotonic()`` it opened at;
    inside a ``profiler`` session the span is also a row of the printed
    summary. Attribute values are numbers or text without commas (the
    trace's metadata is comma-separated)."""

    __slots__ = ("name", "seconds", "t0", "_annotation")

    def __init__(self, name, **attrs):
        self.name = name
        self.seconds = None
        self._annotation = jax.profiler.TraceAnnotation(name, **attrs)

    def __enter__(self):
        self._annotation.__enter__()
        self.t0 = time.monotonic()
        return self

    def note(self, **attrs):
        """Attributes known only once the span is open (what it built),
        added to its metadata before it closes."""
        self._annotation.set_metadata(**attrs)

    def __exit__(self, *exc):
        self.seconds = time.monotonic() - self.t0
        self._annotation.__exit__(*exc)
        if _active is not None:
            _records.append((self.name, self.seconds))
        return False


# ----------------------------------------------------------------------
# The compile log: what a set-up is made of, written where a compile
# happens. Always on; an entry costs clock reads and three listener
# calls, a dispatch that compiles nothing costs nothing.
#
# An entry is one executable an executor compiled:
#   program, version   the Program's uid and version
#   executor           "Executor" | "ParallelExecutor"
#   shapes             {feed name: "dtype[d0,d1,...]"}
#   t0, t1             time.monotonic() at the two ends of the bracket:
#                      the top of the executor's run to the return of
#                      the first call of the jitted step
#   verify_s           Executor._validate, once a new program
#   build_s            the rest up to the jitted step (graph rewrites,
#                      state and feeds staged, lower_program)
#   trace_s, lower_s, compile_s
#                      JAX's own spans inside the bracket, each phase
#                      the UNION of its spans (a traced program holds
#                      nested jits whose spans lie inside the outer
#                      one's); where phases overlap, compile takes its
#                      seconds first, then trace, and lower what is left
#                      (a jit traced from inside a lowering rule reads as
#                      tracing)
#   cache_hit          whether the persistent cache held the executable
#                      (None where the cache is off), cache_read_s the
#                      seconds of compile_s its read took
#   run_s              what is left of t1 - t0: arguments placed, the
#                      executable launched
# so verify_s + build_s + trace_s + lower_s + compile_s + run_s is
# t1 - t0. A compile that a CACHED jitted step makes for a new feed shape
# is found where the step is traced (make_stepped's ``on_trace``): its
# bracket runs from there to the end of its backend compile, so it has
# neither verify_s, build_s nor run_s. Compile events on a thread with
# no bracket open (a builder's jax.jit, an eager jnp.zeros) add up in
# the one running entry ``stray``.
# ----------------------------------------------------------------------
_PHASES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
           "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
           "/jax/core/compile/backend_compile_duration": "compile"}
# heard once a compile that goes by the cache's key (also where no
# directory is set: the cache is on only where one is), on a hit, and
# with the seconds a hit's read took (cache_misses says only that an
# entry was WRITTEN, which a quick compile never is)
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
# an entry's seconds, by name: the first five and run_s (what is left)
# add up to its bracket, cache_read_s lies inside compile_s
COMPILE_PHASES = ("verify_s", "build_s", "trace_s", "lower_s",
                  "compile_s", "cache_read_s", "run_s")
_COMPILE_SPANS = {"Executor": "pt:executor/compile",
                  "ParallelExecutor": "pt:pexecutor/compile"}

_log = []                       # closed entries, in the order they closed
_stray = {"count": 0, "trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0}
_log_lock = threading.Lock()
_inflight = threading.local()   # .compile: this thread's open bracket


def _merged(spans):
    """Sorted, disjoint: the union of ``spans`` as intervals."""
    out = []
    for a, b in sorted(map(tuple, spans)):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _seconds(spans):
    return sum(b - a for a, b in spans)


class _Compile:
    """The bracket of one compiling dispatch, open on its thread from
    ``open_compile`` (an executor's cache miss) or ``compile_traced`` (a
    cached step traced anew) to ``close``. It is the
    ``pt:executor/compile`` / ``pt:pexecutor/compile`` span too."""

    __slots__ = ("entry", "span", "skew", "built", "spans", "own",
                 "asked", "hit")

    def __init__(self, executor, program, feeds, t0, verify_s, own):
        now = time.monotonic()
        # JAX stamps its spans with time.time(): containment is judged
        # on that clock, the entry is written on ours
        self.skew = time.time() - now
        self.built = now
        self.own = own
        self.asked = self.hit = False
        self.spans = {"trace": [], "lower": [], "compile": []}
        shapes = {}
        for name, v in feeds.items():
            leaves = [v] if hasattr(v, "shape") \
                else jax.tree_util.tree_leaves(v)
            shapes[name] = " ".join(
                f"{x.dtype}[{','.join(map(str, x.shape))}]" for x in leaves)
        t0 = now if t0 is None else t0
        self.entry = {
            "program": program.uid, "version": program.version,
            "executor": executor, "shapes": shapes, "t0": t0, "t1": None,
            "verify_s": verify_s, "build_s": now - t0 - verify_s,
            "trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
            "cache_hit": None, "cache_read_s": 0.0, "run_s": 0.0}
        self.span = record_event(
            _COMPILE_SPANS[executor], program=program.uid,
            shapes=" ".join(f"{k}:{v.replace(',', 'x')}"
                            for k, v in shapes.items()))
        self.span.__enter__()
        _inflight.compile = self

    def close(self, log=True):
        """The dispatch returned: the entry is written (``log=False``:
        it failed, and compiled nothing to enter)."""
        t1 = time.monotonic()
        if getattr(_inflight, "compile", None) is self:
            _inflight.compile = None
        self.span.__exit__(None, None, None)
        if not log:
            return
        e = self.entry
        lo, hi = self.built + self.skew, t1 + self.skew
        taken = []
        for phase in ("compile", "trace", "lower"):
            both = _merged(taken + [
                (max(a, lo), min(b, hi)) for a, b in self.spans[phase]
                if min(b, hi) > max(a, lo)])
            e[phase + "_s"] = _seconds(both) - _seconds(taken)
            taken = both
        e["t1"] = t1
        e["run_s"] = (t1 - e["t0"]) - sum(e[k] for k in COMPILE_PHASES[:5])
        with _log_lock:
            _log.append(e)


def open_compile(executor, program, feeds, t0, verify_s=0.0):
    """An executor found no jitted step for this dispatch and has built
    one: the bracket, open from ``t0`` (the top of its run). The executor
    closes it when the step's first call returns."""
    drop_compile()
    return _Compile(executor, program, feeds, t0, verify_s, own=False)


def compile_traced(executor, program, feeds):
    """Called where an executor's step function is TRACED (which a
    cached dispatch never does): on a thread whose dispatch is not in a
    bracket already, a cached step is compiling for a new feed shape,
    and the bracket opened here closes with that compile."""
    if getattr(_inflight, "compile", None) is None:
        _Compile(executor, program, feeds, None, 0.0, own=True)


def drop_compile():
    """Closes this thread's bracket, if one is open, without an entry:
    its dispatch failed, or its trace was never compiled."""
    c = getattr(_inflight, "compile", None)
    if c is not None:
        c.close(log=False)


def _heard(event, *values, **_):
    """JAX's monitoring events, all three kinds (a time span: start and
    end; a duration; a plain event), on the thread that compiles."""
    c = getattr(_inflight, "compile", None)
    phase = _PHASES.get(event)
    if phase is not None:
        if len(values) != 2:        # the same event as a duration
            return
        if c is None:
            with _log_lock:
                _stray[phase + "_s"] += values[1] - values[0]
                _stray["count"] += phase == "compile"
            return
        c.spans[phase].append(values)
        if phase == "compile":
            c.entry["cache_hit"] = c.hit if c.asked \
                and jax.config.jax_compilation_cache_dir else None
            c.asked = c.hit = False
            if c.own:
                c.close()
    elif c is not None:
        if event == _CACHE_ASKED:
            c.asked = True
        elif event == _CACHE_HIT:
            c.hit = True
        elif event == _CACHE_READ:
            c.entry["cache_read_s"] += values[0]


jax.monitoring.register_event_listener(_heard)
jax.monitoring.register_event_duration_secs_listener(_heard)
jax.monitoring.register_event_time_span_listener(_heard)


def compile_log(since=None):
    """The entries (copies), oldest first; ``since``: those that closed
    at or after that ``time.monotonic()``."""
    with _log_lock:
        return [dict(e, shapes=dict(e["shapes"])) for e in _log
                if since is None or e["t1"] >= since]


def compile_totals(until=None):
    """Sums over the entries that closed by ``until`` (all of them where
    None): every phase, ``programs`` (the executables), ``cold_programs``
    (those the persistent cache was asked for and did not hold),
    ``bracket_s`` (the union of the brackets), and ``stray`` as it
    stands now."""
    with _log_lock:
        entries = [e for e in _log if until is None or e["t1"] <= until]
        stray = dict(_stray)
    totals = {k: sum(e[k] for e in entries) for k in COMPILE_PHASES}
    totals.update(
        programs=len(entries),
        cold_programs=sum(e["cache_hit"] is False for e in entries),
        bracket_s=_seconds(_merged((e["t0"], e["t1"]) for e in entries)),
        stray=stray)
    return totals
