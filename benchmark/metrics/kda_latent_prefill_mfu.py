"""Share of the chip's peak operations/s that prefill reaches while it
runs, for a Kimi-delta-attention / latent-attention share: the operations
the prompts prefilled in the window need on this chip, each at its own
length and with the share of its picks that fell on held experts as
measured (work_kda_latent.prefill_flops), over the seconds the engine's
loop spent in prefill and chunk dispatches (call to first token on the
host's clock), over the peak. As latent_share_prefill_mfu: the prompts are
those whose first token fell inside the window, scaled by the prompt
tokens the window's dispatches really carried."""
from benchmark import work_kda_latent
from benchmark.metrics._engine_clock import deltas
from benchmark.metrics._kda import is_kda


def read(run):
    d = deltas(run, "prefill_dispatch_s_total", "chunk_dispatch_s_total",
               "prefill_tokens_total", "moe_held_assignments_total",
               "moe_assignments_total") if is_kda(run) else None
    if d is None:
        return None
    seconds = d[0] + d[1]
    lens = [r["prompt_len"] for r in run["requests"]
            if r["first_token"] is not None
            and run["t0"] <= r["first_token"] < run["t_end"]]
    if not seconds or not lens or not d[4]:
        return None
    flops = sum(work_kda_latent.prefill_flops(run["config"], n, d[3] / d[4])
                for n in lens) * d[2] / sum(lens)
    return 100.0 * flops / seconds / (run["chips"]
                                      * run["peaks"]["bf16_flops"])
