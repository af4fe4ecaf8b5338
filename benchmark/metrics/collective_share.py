"""Share of device 0's busy time in which a collective ran (all-reduce,
all-gather, all-to-all, collective-permute, reduce-scatter; operations
and asynchronous operations, their union)."""


def read(run):
    t = run.get("trace")
    if not t or run["chips"] < 2 or not t["busy0_s"]:
        return None
    return 100.0 * t["collectives_s"] / t["busy0_s"]
