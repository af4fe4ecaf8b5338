"""Bytes one device needs while a training step runs, in GB, by XLA's own
memory analysis of the executable that ran (the executors' compiled_stats:
arguments + outputs + temporaries - aliased). A compile-time analysis of the
very program, not a counter read while it ran: BENCHMARK.json's four
``source`` words have none for that, and ``program_counter`` stands for "a
number the program reports". A cell whose builder reports none (serving:
DecodeEngine exposes no memory analysis) leaves the metric out."""


def read(run):
    footprint = run.get("step_footprint_bytes")
    return None if not footprint else footprint / 1e9
