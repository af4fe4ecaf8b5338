"""Share of its roofline that the kernel ``moe_few_rows`` reaches (a decode
step's routed experts of one sparse layer where every expert is held:
ops/moe.py). A call has to read the weights of the experts that a row
reached, once, and multiplies each with all the step's rows
(work_hybrid_gated.few_rows_call): the larger of bytes over the chip's peak
bytes/s and operations over its peak operations/s is the least time a call
could take (the bytes bound it at 64 rows), and that times the kernel's
calls in the traced window over their device time is the metric. Experts
touched are the window's mean, from the counters the programs sum on the
device. None where the trace holds no such kernel (a program that sorts
its pairs: the parent's, a CPU's)."""
from benchmark import work_hybrid_gated
from benchmark.metrics._gated import experts_touched


def read(run):
    touched = experts_touched(run)
    trace = run.get("trace")
    if not touched or not trace:
        return None
    hits = [v for name, v in trace["ops"].items() if "moe_few_rows" in name]
    seconds, calls = (sum(v[i] for v in hits) for i in (0, 1))
    if not seconds:
        return None
    flops, nbytes = work_hybrid_gated.few_rows_call(
        run["config"], touched, run["engine"]["max_batch"])
    least_s = max(flops / run["peaks"]["bf16_flops"],
                  nbytes / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least_s * calls / seconds
