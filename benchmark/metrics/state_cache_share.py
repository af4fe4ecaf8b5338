"""Share of the cache bytes the active slots hold that is STATE: entries
of the ``state`` cache kind (one a request in every Mamba layer, whatever
the request's length) over the pages of every kind, both summed once a
decode dispatch over the window (state_bytes_held_total,
cache_bytes_held_total; pages counted whole, the budget a request was
granted for its answer included). In every other cell the cache is
positions; here most of it is not."""
from benchmark.metrics._engine_clock import per
from benchmark.metrics._ssm import is_ssm


def read(run):
    if not is_ssm(run):
        return None
    return per(run, "state_bytes_held_total", "cache_bytes_held_total",
               100.0)
