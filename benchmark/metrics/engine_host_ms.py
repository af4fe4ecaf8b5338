"""Host time the engine's loop spends outside its dispatches, per
dispatch: (loop busy - decode, prefill and chunk dispatch seconds) over
the dispatches of all three kinds, between the window's edges. It is
scheduling, feed building and per-row bookkeeping: what the device sees
as the gap between two programs."""
from benchmark.metrics._engine_clock import deltas


def read(run):
    d = deltas(run, "loop_busy_s_total", "decode_dispatch_s_total",
               "prefill_dispatch_s_total", "chunk_dispatch_s_total",
               "decode_batches_total", "prefill_dispatch_total",
               "chunk_prefill_total")
    if d is None or not sum(d[4:]):
        return None
    return 1e3 * (d[0] - sum(d[1:4])) / sum(d[4:])
