"""Share of its roofline that the decode step of a gated short-convolution
model with every expert held reaches. The bound taken is bandwidth: a step
has to read every weight outside the routed experts, the routed experts a
token reached, the entries its rows attend in the attention layers and
their tails in the conv layers, and to write its float32 logits
(work_hybrid_conv.decode_step_parts); that over the chip's peak bytes/s is
the least time a step could take, and its share of the decode program's
time a step is the metric. The step's time is the traced decode program's,
found by count AND duration (_ssm.decode_program)."""
from benchmark.metrics._conv import decode_step_parts
from benchmark.metrics._ssm import decode_program


def read(run):
    parts = decode_step_parts(run)
    p = decode_program(run) if parts else None
    if p is None:
        return None
    step_s = p["seconds"] / p["count"] / run["engine"]["decode_block"]
    return 100.0 * sum(parts) / run["peaks"]["hbm_bytes_per_s"] / step_s
