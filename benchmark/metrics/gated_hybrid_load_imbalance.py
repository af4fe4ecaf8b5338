"""The fullest expert's tokens over an even share of them, over every
sparse-layer call of the window (prefill and decode), for a model whose
file counts its experts as ``num_experts``: 1.0 is a perfectly even
router (moe_load_imbalance asks for ``n_routed_experts``)."""
from benchmark.metrics._engine_clock import per
from benchmark.metrics._gated import is_gated


def read(run):
    if not is_gated(run):
        return None
    return per(run, "moe_max_load_total", "moe_assignments_total",
               float(run["config"]["num_experts"]))
