"""The fullest HELD expert's tokens over an even share of the pairs that
fell on held experts, over every routed-layer call of the window (prefill
and decode), for a Kimi-delta-attention / latent-attention share: 1.0 is a
perfectly even router (moe_held_load_imbalance's quantity, which lists
the shares whose files it was written for)."""
from benchmark.metrics._engine_clock import per
from benchmark.metrics._kda import is_kda


def read(run):
    if not is_kda(run):
        return None
    return per(run, "moe_max_load_total", "moe_held_assignments_total",
               float(run["config"]["experts_held"]["count"]))
