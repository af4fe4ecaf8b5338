"""Seconds of set-up inside XLA's compile, or inside the persistent
cache's read where it held the executable."""
from benchmark.metrics._setup import phase


def read(run):
    return phase(run, "compile")
