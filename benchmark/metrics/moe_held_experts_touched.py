"""Share of the experts HELD that at least one token reached, over the
decode steps' routed-layer calls: what a step must read of this chip's
expert weights."""
from benchmark.metrics._engine_clock import per
from benchmark.metrics._share import is_share


def read(run):
    if not is_share(run):
        return None
    return per(run, "moe_decode_experts_touched_total",
               "moe_decode_expert_calls_total", 100.0)
