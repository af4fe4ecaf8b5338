"""Real prompt tokens over the tokens the prefill dispatches paid for
(prefill_batch rows of the bucket's length, each dispatch)."""
from benchmark.metrics._engine_clock import per


def read(run):
    return per(run, "prefill_tokens_total", "prefill_padded_tokens_total",
               100.0)
