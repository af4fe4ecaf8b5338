"""Share of the bytes a decode step has to read that are routed experts'
weights (work_hybrid_gated.decode_step_parts at the window's means): what
holding every expert of a layer whole costs a step, beside the other
weights and the cache."""
from benchmark.metrics._gated import decode_step_parts


def read(run):
    parts = decode_step_parts(run)
    return None if parts is None else 100.0 * parts[1] / sum(parts)
