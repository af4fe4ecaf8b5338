"""Device time of one optimizer step: mean duration of the step program's
complete executions in the trace over the steps fused in a dispatch."""
from benchmark.metrics._programs import longest


def read(run):
    if run["kind"] != "train":
        return None
    p = longest(run.get("trace"))
    if p is None or not p["count"]:
        return None
    return 1e3 * p["seconds"] / p["count"] / run["repeats"]
