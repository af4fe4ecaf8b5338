"""Highest share of the page pool in use, sampled at each completion
inside the window."""


def read(run):
    if run["kind"] != "serve":
        return None
    seen = [r["pages_in_use"] for r in run["requests"]
            if r["pages_in_use"] is not None and r["done"] is not None
            and run["t0"] <= r["done"] < run["t_end"]]
    if not seen:
        return None
    return 100.0 * max(seen) / run["engine"]["pool_pages"]
