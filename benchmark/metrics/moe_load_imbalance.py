"""The fullest expert's tokens over an even share of them, over every
routed-layer call of the window (prefill and decode): 1.0 is a perfectly
even router."""
from benchmark.metrics._engine_clock import per


def read(run):
    if run["kind"] != "serve" or "n_routed_experts" not in run["config"]:
        return None
    return per(run, "moe_max_load_total", "moe_assignments_total",
               float(run["config"]["n_routed_experts"]))
