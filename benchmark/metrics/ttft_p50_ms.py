"""Median time from the instant a request was due to its first token."""
from benchmark.loadgen import percentile
from benchmark.metrics._requests import ttft_ms


def read(run):
    if run["kind"] != "serve":
        return None
    return percentile(ttft_ms(run), 50)
