"""What the readers of a looped model's counters have in common: they read
only a serving run of a configuration whose stack is run several times a
token (``total_ut_steps`` in its file). On any other run they return
None."""


def is_looped(run):
    return run.get("kind") == "serve" \
        and "total_ut_steps" in run.get("config", {})
