"""Share of the cache bytes the active slots hold that is STATE, for a
model of gated delta-rule layers: entries of the ``state`` cache kind (one
a request in every delta-rule layer, 27.4 MB whatever the request's
length) over the pages of every kind, both summed once a decode dispatch
over the window (state_bytes_held_total, cache_bytes_held_total; pages
counted whole, the budget a request was granted for its answer included).
state_cache_share's quantity, which reads a configuration with Mamba
layers alone (``_ssm.is_ssm``); there most of the cache is state, here a
few percent: what rows of ten thousand positions do to a hybrid's cache."""
from benchmark.metrics._delta import is_delta
from benchmark.metrics._engine_clock import per


def read(run):
    if not is_delta(run):
        return None
    return per(run, "state_bytes_held_total", "cache_bytes_held_total",
               100.0)
