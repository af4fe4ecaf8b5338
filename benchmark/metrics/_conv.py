"""What the readers of a gated short-convolution model's counters have in
common: they read only a serving run of a configuration with conv layers
(``conv_L_cache`` in its file) whose experts are all held. On any other
run, and on a program that lacks the counters, they return None."""
from benchmark import work_hybrid_conv
from benchmark.metrics._engine_clock import deltas


def is_conv(run):
    return run.get("kind") == "serve" \
        and "conv_L_cache" in run.get("config", {})


def experts_touched(run):
    """The experts of ONE routed layer that a token reached in a decode
    step, the window's mean (the two decode counters of PAGED_STATS), or
    None."""
    d = deltas(run, "moe_decode_experts_touched_total",
               "moe_decode_expert_calls_total") if is_conv(run) else None
    if d is None or not d[1]:
        return None
    return run["config"]["num_experts"] * d[0] / d[1]


def decode_rows(run):
    """(decode steps of the window, the active rows of a step summed over
    the conv layers: CONV_STATS), or None."""
    d = deltas(run, "decode_batches_total", "conv_state_updates_total") \
        if is_conv(run) else None
    if d is None or not d[0]:
        return None
    return d[0] * run["engine"]["decode_block"], d[1]


def decode_step_parts(run):
    """work_hybrid_conv.decode_step_parts at the window's means (other
    weights, routed experts, cache, logits: bytes a step), from the
    counters the programs sum on the device; None where they are not
    there or did not move."""
    touched, rows = experts_touched(run), decode_rows(run)
    d = deltas(run, "attn_full_positions_total") if touched and rows \
        else None
    if d is None:
        return None
    steps, updates = rows
    return work_hybrid_conv.decode_step_parts(
        run["config"], positions=d[0] / steps, rows=updates / steps,
        experts_touched=touched)


def traced_kernel(run, name):
    """(device seconds, calls) of the traced operations whose name holds
    ``name``, or None where the trace holds none."""
    trace = run.get("trace")
    if not trace:
        return None
    hits = [v for op, v in trace["ops"].items() if name in op]
    seconds, calls = (sum(v[i] for v in hits) for i in (0, 1))
    return (seconds, calls) if seconds else None
