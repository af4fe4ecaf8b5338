"""Share of the window the engine's worker waited with nothing to do
(no queued request, no active slot)."""
from benchmark.metrics._engine_clock import share_of_window


def read(run):
    return share_of_window(run, "loop_idle_s_total")
