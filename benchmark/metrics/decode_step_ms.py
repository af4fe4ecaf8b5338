"""Device time of one decode step, from the trace (see _programs)."""
from benchmark.metrics._programs import decode_step_ms


def read(run):
    return decode_step_ms(run)
