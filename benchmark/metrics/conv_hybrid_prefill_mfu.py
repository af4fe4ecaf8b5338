"""Share of the chip's peak operations/s that prefill reaches while it
runs, for a gated short-convolution model with every expert held: the
operations the prompts prefilled in the window need, each at its own
length (work_hybrid_conv.prefill_flops), over the seconds the engine's
loop spent in prefill and chunk dispatches (call to first token on the
host's clock), over the peak. As gated_hybrid_prefill_mfu: the prompts are
those whose first token fell inside the window, scaled by the prompt
tokens the window's dispatches really carried."""
from benchmark import work_hybrid_conv
from benchmark.metrics._conv import is_conv
from benchmark.metrics._engine_clock import deltas


def read(run):
    d = deltas(run, "prefill_dispatch_s_total", "chunk_dispatch_s_total",
               "prefill_tokens_total") if is_conv(run) else None
    if d is None:
        return None
    seconds = d[0] + d[1]
    lens = [r["prompt_len"] for r in run["requests"]
            if r["first_token"] is not None
            and run["t0"] <= r["first_token"] < run["t_end"]]
    if not seconds or not lens:
        return None
    flops = sum(work_hybrid_conv.prefill_flops(run["config"], n)
                for n in lens) * d[2] / sum(lens)
    return 100.0 * flops / seconds / (run["chips"]
                                      * run["peaks"]["bf16_flops"])
