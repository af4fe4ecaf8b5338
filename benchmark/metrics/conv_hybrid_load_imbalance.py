"""The fullest expert's tokens over an even share of them, over every
routed-layer call of the window (prefill and decode), for a gated
short-convolution model: 1.0 is a perfectly even router (as
gated_hybrid_load_imbalance, which asks the file for its layers' own
heads)."""
from benchmark.metrics._conv import is_conv
from benchmark.metrics._engine_clock import per


def read(run):
    if not is_conv(run):
        return None
    return per(run, "moe_max_load_total", "moe_assignments_total",
               float(run["config"]["num_experts"]))
