"""The fullest held expert's tokens over the held experts' mean, over
every routed-layer call of the window (prefill and decode): 1.0 is an even
spread over the experts this chip holds."""
from benchmark.metrics._engine_clock import per
from benchmark.metrics._share import is_share


def read(run):
    if not is_share(run):
        return None
    return per(run, "moe_max_load_total", "moe_held_assignments_total",
               float(run["config"]["experts_held"]["count"]))
