"""Seconds of set-up turning the traced programs into StableHLO, Mosaic
kernels' bodies among it (JAX's lowering spans inside the compile brackets,
less the tracing inside them)."""
from benchmark.metrics._setup import phase


def read(run):
    return phase(run, "lower")
