"""Share of the cache positions the decode steps attended that window
layers attended: positions x layers of each kind, summed on the device over
the active rows (HYBRID_STATS). 5 layers x at most 128 positions against
2 layers x the row's length: the longer the sequences, the smaller; 71%
(5 of 7 layers) were the window not applied."""
from benchmark.metrics._engine_clock import deltas
from benchmark.metrics._hybrid import is_hybrid


def read(run):
    d = deltas(run, "attn_window_positions_total",
               "attn_full_positions_total") if is_hybrid(run) else None
    if d is None or not d[0] + d[1]:
        return None
    return 100.0 * d[0] / (d[0] + d[1])
