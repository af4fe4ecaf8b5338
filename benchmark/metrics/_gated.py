"""What the readers of a gated mixed-attention model's counters have in
common: they read only a serving run of a configuration whose layers name
their own query heads (``num_attention_heads_per_layer`` in its file) and
whose experts are all held. On any other run they return None."""
from benchmark import work_hybrid_gated
from benchmark.metrics._engine_clock import deltas


def is_gated(run):
    return run.get("kind") == "serve" \
        and "num_attention_heads_per_layer" in run.get("config", {})


def experts_touched(run):
    """The experts of ONE sparse layer that a token reached in a decode
    step, the window's mean (the two decode counters of PAGED_STATS), or
    None."""
    d = deltas(run, "moe_decode_experts_touched_total",
               "moe_decode_expert_calls_total") if is_gated(run) else None
    if d is None or not d[1]:
        return None
    return run["config"]["num_experts"] * d[0] / d[1]


def decode_step_parts(run):
    """work_hybrid_gated.decode_step_parts at the window's means (other
    weights, routed experts, cache: bytes a step), from the counters the
    programs sum on the device (HYBRID_STATS); None where they are not
    there or did not move."""
    touched = experts_touched(run)
    d = deltas(run, "decode_batches_total", "attn_full_positions_total",
               "attn_window_positions_total") if touched else None
    if d is None or not d[0]:
        return None
    steps = d[0] * run["engine"]["decode_block"]
    return work_hybrid_gated.decode_step_parts(
        run["config"], full_positions=d[1] / steps,
        window_positions=d[2] / steps, experts_touched=touched)
