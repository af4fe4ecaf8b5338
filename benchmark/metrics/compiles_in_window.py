"""XLA compilations between the window's two edges (total_compiles() of
the executor that dispatches, or JAX's own compile events where the
executor has no such count). There should be none."""


def read(run):
    return run["edges"]["end"]["compiles"] \
        - run["edges"]["start"]["compiles"]
