"""Share of the experts held that at least one token reached, over the
decode steps' routed-layer calls: what a step must read of the expert
weights."""
from benchmark.metrics._engine_clock import per


def read(run):
    return per(run, "moe_decode_experts_touched_total",
               "moe_decode_expert_calls_total", 100.0)
