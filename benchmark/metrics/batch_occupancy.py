"""Share of decode row-steps that produced a token: (generated tokens -
prefills, each of which yields one) over decode dispatches x decode_block
x max_batch, all between the window's edges."""
from benchmark.metrics._requests import window_delta


def read(run):
    if run["kind"] != "serve":
        return None
    e = run["engine"]
    room = window_delta(run, "decode_batches_total") \
        * e["decode_block"] * e["max_batch"]
    if not room:
        return None
    return 100.0 * (window_delta(run, "generated_tokens_total")
                    - window_delta(run, "prefill_total")) / room
