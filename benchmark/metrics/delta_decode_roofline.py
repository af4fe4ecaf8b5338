"""Share of its roofline that the decode step of a model of gated
delta-rule layers and a few full-attention layers reaches. The bound taken
is bandwidth: a step has to read every weight outside the embedding once,
read AND write the state entry of every live row in every delta-rule
layer, and read the keys and values its rows attend
(work_hybrid_delta.decode_step_bytes); that over the chip's peak bytes/s
is the least time a step could take, and its share of the decode
program's time a step is the metric. State entries updated and positions
attended are the window's means, from the counters the programs sum on the
device (DELTA_STATS); the step's time is the traced decode program's,
picked by its count AND its duration (_ssm.decode_program: a chunk program
runs about as often here)."""
from benchmark import work_hybrid_delta
from benchmark.metrics._delta import is_delta
from benchmark.metrics._engine_clock import deltas
from benchmark.metrics._ssm import decode_program


def read(run):
    d = deltas(run, "decode_batches_total", "delta_state_updates_total",
               "attn_full_positions_total") if is_delta(run) else None
    p = decode_program(run) if d else None
    if p is None or not d[0] or not d[1]:
        return None
    steps = d[0] * run["engine"]["decode_block"]
    least_s = work_hybrid_delta.decode_step_bytes(
        run["config"], state_updates=d[1] / steps,
        full_positions=d[2] / steps) / run["peaks"]["hbm_bytes_per_s"]
    step_s = p["seconds"] / p["count"] / run["engine"]["decode_block"]
    return 100.0 * least_s / step_s
