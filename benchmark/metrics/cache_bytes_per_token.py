"""Bytes of cache the engine holds for each position resident in it, over
the window's decode dispatches: the bytes of the pages the active slots
held (every cache kind, pages counted whole, the budget a request was
granted for its answer included) over the positions those slots had in
their caches, both summed once a decode dispatch (cache_bytes_held_total,
cache_positions_resident_total). For mimo-v2-flash-ep16 a position would
hold 30,720 B were every layer kept whole; with the window layers' rings
it holds the full layers' 5,120 B and the ring's share."""
from benchmark.metrics._engine_clock import per
from benchmark.metrics._hybrid import is_hybrid


def read(run):
    if not is_hybrid(run):
        return None
    return per(run, "cache_bytes_held_total",
               "cache_positions_resident_total", 1.0)
