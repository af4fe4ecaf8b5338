"""Share of the token-expert pairs, routed over the router's whole width,
that fell on the experts held on this chip, over every routed-layer call of
the window: 100 x held / of under an even router (6.25% for 16 of 256), and
the check that the router really runs at its published width."""
from benchmark.metrics._engine_clock import per
from benchmark.metrics._share import is_share


def read(run):
    if not is_share(run):
        return None
    return per(run, "moe_held_assignments_total", "moe_assignments_total",
               100.0)
