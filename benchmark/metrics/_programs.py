"""Which traced program is which, for the readers of program times."""


def by_count(trace, count):
    """The traced program whose number of complete executions is nearest
    to ``count`` (a counter's delta over the traced window; the two clocks
    differ by an execution or two at the edges). None without programs."""
    if not trace or not trace["programs"]:
        return None
    return min(trace["programs"].values(),
               key=lambda p: abs(p["count"] - count))


def longest(trace):
    """The traced program with most device time: a training cell's step."""
    if not trace or not trace["programs"]:
        return None
    return max(trace["programs"].values(), key=lambda p: p["seconds"])


def decode_step_ms(run):
    """Mean device time of the decode program's complete executions in
    the trace over its decode_block token-steps, or None. The decode
    program is the one whose executions match the engine's
    decode_batches_total over the traced window."""
    if run["kind"] != "serve" or "trace_end" not in run["edges"]:
        return None
    n = run["edges"]["trace_end"]["decode_batches_total"] \
        - run["edges"]["trace_start"]["decode_batches_total"]
    p = by_count(run.get("trace"), n)
    if p is None or not p["count"]:
        return None
    return 1e3 * p["seconds"] / p["count"] / run["engine"]["decode_block"]
