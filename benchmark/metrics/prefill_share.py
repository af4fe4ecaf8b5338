"""Share of the window the engine's loop spent in prefill dispatches
(call to first tokens on the host)."""
from benchmark.metrics._engine_clock import share_of_window


def read(run):
    return share_of_window(run, "prefill_dispatch_s_total")
