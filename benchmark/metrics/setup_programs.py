"""Executables the executors compiled before the window (the compile
log's entries: one a program and feed shape; stray compiles are not
among them)."""
from benchmark.metrics._setup import count


def read(run):
    return count(run, "programs")
