"""Share of its roofline that the decode step reaches. The bound is
bandwidth: the step has to read every int8 weight once and the K and V of
each active row at that row's own length (work.llama_decode_step_bytes);
that over the chip's peak bytes/s is the least time a step could take, and
its share of decode_step_ms is the metric. Rows and lengths are the
window's means: active rows from the counters, the length a decoding row
has on average from the sampled requests (prompt + half the answer,
weighted by the answer's tokens)."""
from benchmark import work
from benchmark.metrics._programs import decode_step_ms
from benchmark.metrics._requests import sampled, window_delta


def read(run):
    if run["kind"] != "serve":
        return None
    step_ms = decode_step_ms(run)
    steps = window_delta(run, "decode_batches_total") \
        * run["engine"]["decode_block"]
    reqs = sampled(run)
    if not step_ms or not steps or not reqs:
        return None
    rows = (window_delta(run, "generated_tokens_total")
            - window_delta(run, "prefill_total")) / steps
    out = sum(r["n_out"] for r in reqs)
    mean_len = sum(r["n_out"] * (r["prompt_len"] + r["n_out"] / 2)
                   for r in reqs) / out
    quantized = bool(run["config"]["builder"]["engine"].get("quantize"))
    least_s = work.llama_decode_step_bytes(
        run["config"], rows, mean_len,
        weight_bytes=1 if quantized else 2) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (step_ms / 1e3)
