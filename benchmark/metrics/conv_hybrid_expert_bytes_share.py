"""Share of the bytes a decode step has to move that are routed experts'
weights (work_hybrid_conv.decode_step_parts at the window's means): what
holding every expert of a layer whole costs a step, beside the other
weights, the cache and the logits."""
from benchmark.metrics._conv import decode_step_parts


def read(run):
    parts = decode_step_parts(run)
    return None if parts is None else 100.0 * parts[1] / sum(parts)
