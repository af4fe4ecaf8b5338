"""90th percentile over the sampled requests of the time per output
token after the first."""
from benchmark.loadgen import percentile
from benchmark.metrics._requests import tpot_ms


def read(run):
    if run["kind"] != "serve":
        return None
    return percentile(tpot_ms(run), 90)
