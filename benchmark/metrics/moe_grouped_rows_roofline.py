"""Share of its roofline that the kernel ``moe_grouped_rows`` reaches (the
sorted pairs of one routed layer where every expert is held: a prefill
window's, and a decode step's of more than 128 rows; ops/moe.py). A call
has to read the three matrices of every expert that a pair reached, once
(PERF.md section 7's formula: experts reached x three matrices' bytes x
calls over the chip's peak bytes/s, over the kernel's device time; the
bytes bound it, the rows' own traffic is left out). Experts reached are
the window's mean over DECODE steps, from the counters the programs sum on
the device; a prefill window reaches at least as many, so the share is not
counted too high. None where the trace holds no such kernel."""
from benchmark import work_hybrid_conv
from benchmark.metrics._conv import experts_touched, traced_kernel


def read(run):
    touched = experts_touched(run)
    hit = traced_kernel(run, "moe_grouped_rows") if touched else None
    if hit is None:
        return None
    seconds, calls = hit
    nbytes = 2 * touched * work_hybrid_conv.expert_params(run["config"])
    return 100.0 * nbytes * calls / run["peaks"]["hbm_bytes_per_s"] \
        / seconds
