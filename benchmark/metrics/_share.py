"""What the readers of a share's counters have in common: they read only
a serving run of a configuration that is one chip's share of an
expert-parallel layer (``experts_held`` in its file). On any other run
they return None, as the engine-clock readers do for a program without
the counters."""


def is_share(run):
    return run.get("kind") == "serve" \
        and "experts_held" in run.get("config", {})
