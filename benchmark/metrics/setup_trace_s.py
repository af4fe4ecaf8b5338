"""Seconds of set-up inside JAX's tracing of the executors' programs
(the union of its trace spans inside the compile brackets): the Python of
every op's lowering rule, run again at every start."""
from benchmark.metrics._setup import phase


def read(run):
    return phase(run, "trace")
