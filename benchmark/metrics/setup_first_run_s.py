"""Seconds of set-up left of the compile brackets once building,
tracing, lowering and compiling are taken out: arguments placed, the
executable launched for the first time."""
from benchmark.metrics._setup import phase


def read(run):
    return phase(run, "first_run")
