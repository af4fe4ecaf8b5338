"""Share of its roofline that the decode step of a Kimi-delta-attention /
latent-attention share reaches. The bound taken is bandwidth: a step has
to read every weight outside the routed experts, the held experts a token
reached, its live rows' states (and to write them back) and the latent
entries they attend (work_kda_latent.decode_step_parts); that over the
chip's peak bytes/s is the least time a step could take, and its share of
the decode program's time a step is the metric. The step's time is the
traced decode program's, found by count AND duration
(_ssm.decode_program)."""
from benchmark.metrics._kda import decode_step_parts
from benchmark.metrics._ssm import decode_program


def read(run):
    parts = decode_step_parts(run)
    p = decode_program(run) if parts else None
    if p is None:
        return None
    step_s = p["seconds"] / p["count"] / run["engine"]["decode_block"]
    return 100.0 * sum(parts) / run["peaks"]["hbm_bytes_per_s"] / step_s
