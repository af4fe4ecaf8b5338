"""Mean time of one decode dispatch as the engine's loop sees it, from
the call to the tokens on the host: decode_block steps on the device,
plus launch and fetch."""
from benchmark.metrics._engine_clock import per


def read(run):
    return per(run, "decode_dispatch_s_total", "decode_batches_total", 1e3)
