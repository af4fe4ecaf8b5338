"""Seconds of set-up building programs: the verifier and the executors'
builds (graph rewrites, state staged, lower_program) from the compile log,
and the engine's own build (its programs made, its pools allocated)."""
from benchmark.metrics._setup import phase


def read(run):
    return phase(run, "build")
