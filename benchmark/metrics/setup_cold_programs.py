"""Of the executables compiled before the window, those the persistent
cache was asked for and did not hold: 0 in a warm set-up, all of them in a
fresh checkout."""
from benchmark.metrics._setup import count


def read(run):
    return count(run, "cold_programs")
