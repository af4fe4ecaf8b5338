"""Mean time a request waited in the engine's queue, from submit to the
instant its prefill was dispatched, over the requests prefilled inside
the window. A mean, because the counters have edges and a reservoir's
percentile has none."""
from benchmark.metrics._engine_clock import per


def read(run):
    return per(run, "queue_wait_s_total", "prefill_total", 1e3)
