"""What the readers of a Kimi-delta-attention / latent-attention share's
counters have in common: they read only a serving run of a configuration
with kda layers (``kda_lower_bound`` in its file). On any other run, and
on a program that lacks the counters, they return None."""
from benchmark import work_kda_latent
from benchmark.metrics._engine_clock import deltas


def is_kda(run):
    return run.get("kind") == "serve" \
        and "kda_lower_bound" in run.get("config", {})


def experts_touched(run):
    """The HELD experts of ONE routed layer that a token reached in a
    decode step, the window's mean (the two decode counters of
    PAGED_STATS, over the experts held), or None."""
    d = deltas(run, "moe_decode_experts_touched_total",
               "moe_decode_expert_calls_total") if is_kda(run) else None
    if d is None or not d[1]:
        return None
    return run["config"]["experts_held"]["count"] * d[0] / d[1]


def decode_rows(run):
    """(decode steps of the window, the active rows of a step summed over
    the kda layers: KDA_LATENT_STATS), or None."""
    d = deltas(run, "decode_batches_total", "kda_state_updates_total") \
        if is_kda(run) else None
    if d is None or not d[0]:
        return None
    return d[0] * run["engine"]["decode_block"], d[1]


def decode_step_parts(run):
    """work_kda_latent.decode_step_parts at the window's means (other
    weights, held experts, state, latent pages: bytes a step), from the
    counters the programs sum on the device; None where they are not
    there or did not move."""
    touched, rows = experts_touched(run), decode_rows(run)
    d = deltas(run, "attn_latent_positions_total") if touched and rows \
        else None
    if d is None:
        return None
    steps, updates = rows
    return work_kda_latent.decode_step_parts(
        run["config"], state_updates=updates / steps,
        latent_positions=d[0] / steps, experts_touched=touched)
