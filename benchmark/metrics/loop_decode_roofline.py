"""Share of its roofline that the decode step of a looped model reaches.
The bound taken is bandwidth: a step has to read the layers' weights once
A PASS, the head once, and the keys and values its rows attend in every
layer of every pass (work_looped.decode_step_bytes); that over the chip's
peak bytes/s is the least time a step could take, and its share of the
decode program's time a step is the metric. Positions attended are the
window's mean a step, from the counter the programs sum on the device
(LOOP_STATS: loop_positions_attended_total); the step's time is the traced
decode program's, picked by its count AND its duration
(_ssm.decode_program)."""
from benchmark import work_looped
from benchmark.metrics._engine_clock import deltas
from benchmark.metrics._loop import is_looped
from benchmark.metrics._ssm import decode_program


def read(run):
    d = deltas(run, "decode_batches_total",
               "loop_positions_attended_total") if is_looped(run) else None
    p = decode_program(run) if d else None
    if p is None or not d[0] or not d[1]:
        return None
    steps = d[0] * run["engine"]["decode_block"]
    least_s = work_looped.decode_step_bytes(
        run["config"], positions_attended=d[1] / steps) \
        / run["peaks"]["hbm_bytes_per_s"]
    step_s = p["seconds"] / p["count"] / run["engine"]["decode_block"]
    return 100.0 * least_s / step_s
