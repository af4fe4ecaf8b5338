"""What the engine-clock readers share: the window's differences of the
counters DecodeEngine keeps at its own span boundaries (PR 24:
loop_busy_s_total, *_dispatch_s_total, prefill_*tokens_total,
queue_wait_s_total; docs/SERVING.md, "Metrics reference")."""
from benchmark.metrics._requests import window_delta


def deltas(run, *counters):
    """The window's difference of each counter, in the order given; None
    for a training run, and for a program whose engine does not keep one
    of them (the edges then lack the key)."""
    if run["kind"] != "serve":
        return None
    try:
        return [window_delta(run, c) for c in counters]
    except KeyError:
        return None


def per(run, seconds_or_count, count, scale):
    """``scale`` x the window's difference of one counter over that of
    another; None where ``deltas`` is, and where the second did not move."""
    d = deltas(run, seconds_or_count, count)
    if d is None or not d[1]:
        return None
    return scale * d[0] / d[1]


def share_of_window(run, seconds):
    """The window's difference of a ``*_s_total`` counter as a percentage
    of the time between the window's two edges, on the host's clock."""
    d = deltas(run, seconds)
    window = run["edges"]["end"]["t"] - run["edges"]["start"]["t"]
    if d is None or not window:
        return None
    return 100.0 * d[0] / window
