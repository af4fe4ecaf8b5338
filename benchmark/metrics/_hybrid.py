"""What the readers of a mixed-attention model's counters have in common:
they read only a serving run of a configuration whose layers are full or
sliding-window by a pattern (``hybrid_layer_pattern`` in its file). On any
other run they return None."""


def is_hybrid(run):
    return run.get("kind") == "serve" \
        and "hybrid_layer_pattern" in run.get("config", {})


def decode_program(run):
    """The traced decode program of a cell whose chunk program runs about
    as often: of the two traced programs whose numbers of complete
    executions lie nearest to the engine's decode_batches_total over the
    traced window, the one with the shorter mean execution (a decode
    dispatch of a few steps is shorter than a chunk of thousands of
    tokens; the count alone took the chunk program in xing4-serve-docs).
    None without a trace."""
    trace = run.get("trace")
    if not trace or not trace["programs"] \
            or "trace_end" not in run["edges"]:
        return None
    n = run["edges"]["trace_end"]["decode_batches_total"] \
        - run["edges"]["trace_start"]["decode_batches_total"]
    near = sorted((p for p in trace["programs"].values() if p["count"]),
                  key=lambda p: abs(p["count"] - n))[:2]
    if not near:
        return None
    return min(near, key=lambda p: p["seconds"] / p["count"])
