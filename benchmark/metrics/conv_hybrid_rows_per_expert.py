"""Pairs a REACHED expert gets in a decode step, the window's mean: the
active rows of a step (CONV_STATS, over the conv layers) x
num_experts_per_tok over the experts of one routed layer that a row
reached (PAGED_STATS). The regime's own number: 16 where 256 rows pick 4
of 64 experts evenly; 2 in the agent cell, 1 in docs."""
from benchmark import work_hybrid_conv
from benchmark.metrics._conv import decode_rows, experts_touched


def read(run):
    touched, rows = experts_touched(run), decode_rows(run)
    if not touched or not rows:
        return None
    m = run["config"]
    steps, updates = rows
    return updates / work_hybrid_conv.layers_of(m, work_hybrid_conv.CONV) \
        / steps * m["num_experts_per_tok"] / touched
