"""End-to-end utilization of a training cell: the operations the forward
and backward passes need per item (work.train_flops_per_item; recomputed
operations not counted) x items per second over chips x the chip's peak
bf16 rate. Not a kernel's roofline share. In a traced run the rate is that
of the window's part before the profiler came on, whose start and stop
stall the loop."""
from benchmark import work


def read(run):
    if run["kind"] != "train":
        return None
    steps, seconds = run.get("untraced") or (run["steps"],
                                             run["window_s"])
    if not seconds:
        return None
    rate = steps * run["items_per_step"] / seconds
    return 100.0 * work.train_flops_per_item(run["config"]) * rate \
        / (run["chips"] * run["peaks"]["bf16_flops"])
