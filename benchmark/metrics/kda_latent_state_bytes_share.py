"""Share of the bytes a decode step has to move that are the kda layers'
STATE, read and written (work_kda_latent.decode_step_parts at the window's
means): what a matrix a head a layer a request costs a step, beside the
weights, the held experts reached and the latent pages."""
from benchmark.metrics._kda import decode_step_parts


def read(run):
    parts = decode_step_parts(run)
    return None if parts is None else 100.0 * parts[2] / sum(parts)
