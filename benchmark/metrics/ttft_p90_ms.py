"""90th percentile of the time from due to first token. Recorded, not
judged: the first candidate for promotion to an end-to-end metric."""
from benchmark.loadgen import percentile
from benchmark.metrics._requests import ttft_ms


def read(run):
    if run["kind"] != "serve":
        return None
    return percentile(ttft_ms(run), 90)
