"""Share of its roofline that the decode step of a gated mixed-attention
model with every expert held reaches. The bound taken is bandwidth: a step
has to read every weight outside the routed experts, the routed experts a
token reached, and the cache entries its rows attend, a full layer's over
the row's whole length and a window layer's over its window
(work_hybrid_gated.decode_step_parts); that over the chip's peak bytes/s
is the least time a step could take, and its share of the decode program's
time a step is the metric. The step's time is the traced decode program's,
found by count AND duration (_ssm.decode_program: the chunk program runs
about twice as often as the decode program here)."""
from benchmark.metrics._gated import decode_step_parts
from benchmark.metrics._ssm import decode_program


def read(run):
    parts = decode_step_parts(run)
    p = decode_program(run) if parts else None
    if p is None:
        return None
    step_s = p["seconds"] / p["count"] / run["engine"]["decode_block"]
    return 100.0 * sum(parts) / run["peaks"]["hbm_bytes_per_s"] / step_s
