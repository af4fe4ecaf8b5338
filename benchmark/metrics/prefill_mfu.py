"""Share of the chip's peak operations/s that prefill reaches while it
runs: the operations the prompts prefilled in the window need, each at its
own length (work_latent_moe.prefill_flops), over the seconds the engine's
loop spent in whole-prompt and chunk dispatches (call to first token on the
host's clock), over the peak. The prompts are those whose first token fell
inside the window; a prompt's slices may straddle an edge, so their
operations are scaled by the prompt tokens the dispatches of the window
really carried (prefill_tokens_total) over the tokens of those prompts."""
from benchmark import work_latent_moe
from benchmark.metrics._engine_clock import deltas


def read(run):
    d = deltas(run, "prefill_dispatch_s_total", "chunk_dispatch_s_total",
               "prefill_tokens_total")
    if d is None or "kv_lora_rank" not in run["config"]:
        return None
    seconds = d[0] + d[1]
    lens = [r["prompt_len"] for r in run["requests"]
            if r["first_token"] is not None
            and run["t0"] <= r["first_token"] < run["t_end"]]
    if not seconds or not lens:
        return None
    flops = sum(work_latent_moe.prefill_flops(run["config"], n)
                for n in lens) * d[2] / sum(lens)
    return 100.0 * flops / seconds / (run["chips"]
                                      * run["peaks"]["bf16_flops"])
