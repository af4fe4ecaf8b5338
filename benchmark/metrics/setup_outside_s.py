"""``setup_s`` less the five phases the compile log and the engine hold:
process start, imports, the backend's start, the builder's weights, stray
compiles, the waits for the first executions: what the program's compile
path cannot shorten."""
from benchmark.metrics._setup import phase


def read(run):
    return phase(run, "outside")
