"""Share of its roofline that the kernel ``paged_flat_packed_decode``
reaches (a decode step's attention where a value head is half a lane
tile: ops/pallas_attention.py). Its calls in the traced window have to
read the entries of the positions their rows attended, keys and values
(work_hybrid_conv.attn_decode_call; the positions from the counter the
decode programs sum on the device over the traced window, layers x rows x
positions): that over the chip's peak bytes/s, over the kernel's device
time. None where the trace holds no such kernel (the parent's program, a
CPU's)."""
from benchmark import work_hybrid_conv
from benchmark.metrics._conv import is_conv, traced_kernel


def read(run):
    hit = traced_kernel(run, "paged_flat_packed_decode") \
        if is_conv(run) and "trace_end" in run.get("edges", {}) else None
    if hit is None:
        return None
    a, b = run["edges"]["trace_start"], run["edges"]["trace_end"]
    try:
        positions = b["attn_full_positions_total"] \
            - a["attn_full_positions_total"]
    except KeyError:
        return None
    nbytes = work_hybrid_conv.attn_decode_call(run["config"], positions)
    return 100.0 * nbytes / run["peaks"]["hbm_bytes_per_s"] / hit[0]
