"""What several readers share: the sampled requests of a serving run."""


def sampled(run):
    """The requests the run judges: due (open loop) or completed (closed
    loop) inside the window, that settled with tokens and no error."""
    return [r for r in run["requests"]
            if r["in_sample"] and r["error"] is None and r["n_out"] > 0]


def tpot_ms(run):
    """Per request, (last token - first token) / (tokens - 1), in ms."""
    return [1e3 * (r["done"] - r["first_token"]) / (r["n_out"] - 1)
            for r in sampled(run)
            if r["n_out"] > 1 and r["first_token"] is not None]


def ttft_ms(run):
    """Per request, first token - the instant it was due, in ms."""
    return [1e3 * (r["first_token"] - r["due"]) for r in sampled(run)
            if r["first_token"] is not None]


def window_delta(run, counter):
    return run["edges"]["end"][counter] - run["edges"]["start"][counter]
