"""What the readers of a state-space model's counters have in common: they
read only a serving run of a configuration with Mamba layers
(``mamba_d_state`` in its file). On any other run they return None."""


def is_ssm(run):
    return run.get("kind") == "serve" \
        and "mamba_d_state" in run.get("config", {})


def decode_program(run):
    """The traced decode program of a cell with five prefill programs
    beside it: the traced program whose number of complete executions AND
    whose mean execution lie nearest to what the engine's own counters say
    of its decode dispatches over the traced window (how many there were,
    and their mean time on the host's clock, which is the program and a
    few ms of launch and return). The count alone took the chunk program
    in xing4-serve-docs; the shorter of the two nearest in count
    (_hybrid.decode_program) took a whole-prompt program here, whose
    buckets run about as often as the decode program in the traced 8 s
    (my chip run, PR 39). None without a trace."""
    trace = run.get("trace")
    if not trace or not trace["programs"] \
            or "trace_end" not in run["edges"]:
        return None
    a, b = run["edges"]["trace_start"], run["edges"]["trace_end"]
    n = b["decode_batches_total"] - a["decode_batches_total"]
    if not n:
        return None
    host_s = (b["decode_dispatch_s_total"]
              - a["decode_dispatch_s_total"]) / n
    ran = [p for p in trace["programs"].values() if p["count"]]
    if not ran or not host_s:
        return None
    return min(ran, key=lambda p: abs(p["count"] - n) / n
               + abs(p["seconds"] / p["count"] - host_s) / host_s)
