"""Process start to the end of warm-up: import, weights made, compile or
cache read, shapes warmed."""


def read(run):
    return run["setup_s"]
