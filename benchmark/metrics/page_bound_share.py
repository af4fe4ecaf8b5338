"""Share of the window's decode dispatches that ran while the POOL bound
the batch: a request was queued, a slot stood free, and the queue's head
had been refused its pages (decode_page_bound_total over
decode_batches_total). 0%: slots (or the callers) set the batch, as in
every cell whose pool holds ``max_batch`` longest requests; near 100%:
pages do, and a larger pool or a smaller cache entry is what would raise
the batch. None for an engine that keeps no such counter."""
from benchmark.metrics._engine_clock import per
from benchmark.metrics._loop import is_looped


def read(run):
    if not is_looped(run):
        return None
    return per(run, "decode_page_bound_total", "decode_batches_total",
               100.0)
