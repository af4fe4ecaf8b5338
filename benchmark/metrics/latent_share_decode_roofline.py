"""Share of its roofline that the decode step of one chip's share of a
latent-attention, routed-experts model reaches. The bound taken is
bandwidth: a step has to read every weight outside the routed experts, the
held experts a token reached, and the cache entries its rows attend
(work_latent_share.decode_step_bytes); that over the chip's peak bytes/s is
the least time a step could take, and its share of decode_step_ms is the
metric. (At 128 heads the absorbed attention's operations a byte of cache
stand at the chip's ridge, so its least time by operations is about the
same as by bytes; the bound stays a lower one either way.) Rows' positions
and the experts touched are the window's means, from the counters the
programs sum on the device (PAGED_STATS)."""
from benchmark import work_latent_share
from benchmark.metrics._engine_clock import deltas
from benchmark.metrics._programs import decode_step_ms
from benchmark.metrics._share import is_share


def read(run):
    d = deltas(run, "decode_batches_total", "latent_tokens_read_total",
               "moe_decode_experts_touched_total",
               "moe_decode_expert_calls_total") if is_share(run) else None
    if d is None or not d[0] or not d[3]:
        return None
    step_ms = decode_step_ms(run)
    if not step_ms:
        return None
    model = run["config"]
    steps = d[0] * run["engine"]["decode_block"]
    least_s = work_latent_share.decode_step_bytes(
        model, positions=d[1] / steps,
        experts_touched=model["experts_held"]["count"] * d[2] / d[3]) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (step_ms / 1e3)
