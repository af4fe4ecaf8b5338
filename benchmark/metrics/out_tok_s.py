"""Output tokens generated inside the window over its seconds: the
engine's generated_tokens_total at the window's two edges, whatever
request the tokens belong to. Not a sum over finished requests."""
from benchmark.metrics._requests import window_delta


def read(run):
    if run["kind"] != "serve":
        return None
    seconds = run["edges"]["end"]["t"] - run["edges"]["start"]["t"]
    return window_delta(run, "generated_tokens_total") / seconds
