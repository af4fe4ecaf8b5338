"""Share of its roofline that the decode step of one chip's share of a
model with full and sliding-window attention layers reaches. The bound
taken is bandwidth: a step has to read every weight outside the routed
experts, the held experts a token reached, and the cache entries its rows
attend, a full layer's over the row's whole length and a window layer's
over its window (work_hybrid_share.decode_step_bytes); that over the chip's
peak bytes/s is the least time a step could take, and its share of the
decode program's time a step is the metric. Positions attended and experts
touched are the window's means, from the counters the programs sum on the
device (HYBRID_STATS); the step's time is the traced decode program's
(_hybrid.decode_program)."""
from benchmark import work_hybrid_share
from benchmark.metrics._engine_clock import deltas
from benchmark.metrics._hybrid import decode_program, is_hybrid


def read(run):
    d = deltas(run, "decode_batches_total", "attn_full_positions_total",
               "attn_window_positions_total",
               "moe_decode_experts_touched_total",
               "moe_decode_expert_calls_total") if is_hybrid(run) else None
    p = decode_program(run) if d else None
    if p is None or not d[0] or not d[4]:
        return None
    model = run["config"]
    steps = d[0] * run["engine"]["decode_block"]
    least_s = work_hybrid_share.decode_step_bytes(
        model, full_positions=d[1] / steps, window_positions=d[2] / steps,
        experts_touched=model["experts_held"]["count"] * d[3] / d[4]) \
        / run["peaks"]["hbm_bytes_per_s"]
    step_s = p["seconds"] / p["count"] / run["engine"]["decode_block"]
    return 100.0 * least_s / step_s
