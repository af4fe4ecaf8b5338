"""Items (images or tokens) in the optimizer steps completed inside the
window over its seconds, the clock stopped by block_until_ready."""


def read(run):
    if run["kind"] != "train":
        return None
    return run["steps"] * run["items_per_step"] / run["window_s"]
