"""What the set-up readers share: ``setup_s`` read apart from the compile
log the program keeps where a compile happens (paddle_tpu/profiler.py
``compile_totals``, PR 53), up to the window's first edge. Nothing where
the program keeps no such log (the parent commit, on which the driver also
runs these files)."""


def totals(run):
    """The log's sums over the executables compiled before the window, or
    None without a log."""
    try:
        from paddle_tpu import profiler
        read = profiler.compile_totals
    except (ImportError, AttributeError):
        return None
    return read(until=run["edges"]["start"]["t"])


def _engine_build_s(run):
    """The engine's own build (``engine_build_s_total``: programs made,
    pools allocated) less what any executor bracket inside it holds
    already; 0.0 for a training run and an engine without the counter."""
    edge = run["edges"]["start"]
    build_s = edge.get("engine_build_s_total")
    if not build_s:
        return 0.0
    from paddle_tpu import profiler
    t1 = edge.get("engine_built_at")
    if t1 is not None:
        build_s -= sum(
            max(0.0, min(e["t1"], t1) - max(e["t0"], t1 - build_s))
            for e in profiler.compile_log())
    return build_s


def phases(run):
    """``setup_s`` in six parts that add up to it: tracing, lowering,
    compiling (or the cache's read), the first runs, building (the
    verifier, the executors' builds and the engine's), and what lies
    outside the program. None without a log."""
    t = totals(run)
    if t is None:
        return None
    out = {"trace": t["trace_s"], "lower": t["lower_s"],
           "compile": t["compile_s"], "first_run": t["run_s"],
           "build": t["verify_s"] + t["build_s"] + _engine_build_s(run)}
    out["outside"] = run["setup_s"] - sum(out.values())
    return out


def phase(run, name):
    p = phases(run)
    return None if p is None else p[name]


def count(run, name):
    t = totals(run)
    return None if t is None else t[name]
