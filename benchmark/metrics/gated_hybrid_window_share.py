"""Share of the cache positions the decode steps attended that window
layers attended: positions x layers of each kind, summed on the device over
the active rows (HYBRID_STATS). 3 layers x at most 512 positions against
2 layers x the row's length (window_attended_share asks for
``hybrid_layer_pattern``)."""
from benchmark.metrics._engine_clock import deltas
from benchmark.metrics._gated import is_gated


def read(run):
    d = deltas(run, "attn_window_positions_total",
               "attn_full_positions_total") if is_gated(run) else None
    if d is None or not d[0] + d[1]:
        return None
    return 100.0 * d[0] / (d[0] + d[1])
