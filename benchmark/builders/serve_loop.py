"""The serving cells of a model whose ONE stack of layers is run several
times a token, every pass with keys and values of its own (kind
``serve_loop``): dense layers with a norm on each side of both sublayers,
served whole. The engine, the traffic and the window are builders/
serve.py's; the weights' drawing and the probe of the engine's own
programs are builders/serve_blocks.py's, the probes' lengths builders/
serve_share.py's and ``rel_l2`` builders/serve_ssm.py's; what is added is
this model's configuration, the books of the loop's counters after the
window, the comparison of the engine's own logits with the plain
reference at the published sizes (reference/looped.py), which decides
``correct``, and the wait for serve.measure's own probe request where the
window shut on it still queued (``late_probe``).
"""
import time

import jax
import numpy as np

import paddle_tpu as fluid
from paddle_tpu.models.looped import LoopedConfig
from paddle_tpu.serving.decode_engine import DecodeConfig, DecodeEngine

from ..reference import looped as reference
from ..tracing import span
from . import serve
from .serve_blocks import PROBE_STEPS, engine_logits, make_weights
from .serve_share import probe_prompts   # three quarters of every bucket
from .serve_ssm import rel_l2

# ---------------------------------------------------------------------
# The limit of the comparison that decides ``correct`` (PERF.md section 4
# has the readings it was set between; my chip runs, PR 43).
#
# REL_L2: ||engine logits - reference logits|| / ||reference logits|| at
# one position. The engine computes in bf16 with float32 accumulation and
# keeps its residual stream and its keys and values in bf16; the reference
# computes in float32 from the same bf16-valued weights. The limit is set
# from controls AT THIS DEPTH, 192 layer passes (PR 39: 28 layers read
# 0.040-0.046 where 5-7 read 0.013-0.024): above the engine's largest
# reading over the seeds, below the reference itself computed from float8
# (e4m3) weights, the nearest precision below the published bf16, and
# below each planted fault: a pass reading the pass before's cache layers,
# one cache shared by all passes, the final norm after the last pass
# alone, the post-norms left out, three passes for four. That the engine's
# 0.16-0.37 IS its precision: the reference rounded to bf16 at every place
# a bf16 program rounds (``_round_dtype``) reads 0.15-0.25 at this depth.
#
# The model has no discrete choice: nothing is forced, there is no margin.
# ---------------------------------------------------------------------
REL_L2 = 0.45

# serve.measure's finding where its probe request, the shortest answer of
# the callers' second and third round, had not settled when the window
# shut, and the longest this builder lets the engine run on for it
NEVER_RAN = "the probe request never ran inside the mix"
LATE_PROBE_WAIT_S = 30.0


def model_config(model):
    """LoopedConfig from the published config.json keys in the file."""
    if model["model_type"] != "ouro" or model["hidden_act"] != "silu" \
            or model["tie_word_embeddings"] or model["use_sliding_window"] \
            or model["sliding_window"] is not None \
            or model["rope_scaling"] is not None \
            or model["early_exit_threshold"] != 1 \
            or set(model["layer_types"]) != {"full_attention"} \
            or len(model["layer_types"]) != model["num_hidden_layers"]:
        raise ValueError("not the untied, windowless, unscaled-rotary "
                         "looped model with an exit threshold of 1 that "
                         "this builder's is")
    return LoopedConfig(
        name=model["name"], vocab_size=model["vocab_size"],
        dim=model["hidden_size"], n_layers=model["num_hidden_layers"],
        passes=model["total_ut_steps"],
        n_heads=model["num_attention_heads"],
        n_kv=model["num_key_value_heads"], head_dim=model["head_dim"],
        ffn_hidden=model["intermediate_size"],
        rope_base=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]), dtype=model["torch_dtype"])


class HandleKeepingEngine(DecodeEngine):
    """A DecodeEngine that keeps the handle of every request it took, in
    the order they came: serve.measure returns neither its probe's tokens
    alone nor the handles of the requests still in flight when the window
    shut, and ``late_probe`` reads both."""

    def __init__(self, *args, **kwargs):
        self.handles = []
        super().__init__(*args, **kwargs)

    def submit(self, *args, **kwargs):
        handle = super().submit(*args, **kwargs)
        self.handles.append(handle)
        return handle


class ServeLoopSystem:
    def __init__(self, config, seed):
        self.config = config
        self.cfg = model_config(config)
        self.scope = fluid.Scope()
        with span("make_weights"):
            self.weights = make_weights(self.cfg, seed)
            for name, value in self.weights.items():
                self.scope.set(name, value)
        with span("engine_warmup"):
            self.engine = HandleKeepingEngine(
                self.cfg, scope=self.scope,
                config=DecodeConfig(**config["builder"]["engine"]))
            self.warmup = self.engine.warmup()
        pools = self.engine.programs.pool_specs
        print(f"serve_loop: engine up, warm-up {self.warmup}, pool "
              f"{self.engine.allocator.usable_pages} pages of "
              f"{self.engine.config.page_size} in {pools[0][0][0]} cache "
              f"layers ({sum(np.prod(s) * 2 for s, _ in pools) / 1e9:.3f}"
              " GB), "
              f"{sum(v.nbytes for v in self.weights.values()) / 1e9:.3f}"
              " GB of weights, decode in place: "
              f"{self.engine.programs.decode['in_place']}", flush=True)

    def compiles(self):
        return self.engine.exe.total_compiles()

    def close(self):
        self.engine.close()


def set_up(config, traffic, seed):
    return ServeLoopSystem(config, seed)


def reference_logits(system, sequence, positions, model=None, through=None):
    """The plain reference's logits at ``positions`` of ``sequence``, from
    the very arrays the engine serves. ``model``: the configuration with a
    term switched off; ``through``: the weights rounded to that type on
    their way."""
    weights = reference.from_stacked(system.weights, through)
    with jax.default_matmul_precision("highest"):
        return np.asarray(reference.forward(
            weights, sequence, model or system.config, positions))


def compare_with_reference(system, seed):
    """The findings of the logit comparison (none: correct), printing its
    figures. See the limit at the top of this file. The probes run one
    after the other on slot 0 and the pool's first pages, which the
    window's requests used before them."""
    problems, n_compared = [], 0
    t = time.monotonic()
    probes = [(prompt,) + engine_logits(system.engine, prompt, PROBE_STEPS)
              for prompt in probe_prompts(system, seed)]
    print(f"engine: {len(probes)} probes in {time.monotonic() - t:.1f} s",
          flush=True)
    # the engine is done: its pools and kept outputs make room for the
    # reference's float32 casts
    del system.engine._pools[:]
    system.engine.kept.clear()
    for prompt, got, _, decoded in probes:
        t = time.monotonic()
        sequence = np.concatenate([prompt, decoded[:-1]])
        positions = prompt.size - 1 + np.arange(1 + PROBE_STEPS)
        want = reference_logits(system, sequence, positions)
        err = rel_l2(got, want)
        agree = np.argmax(got, -1) == np.argmax(want, -1)
        n_compared += err.size
        print(f"probe of {prompt.size} tokens + {PROBE_STEPS} decoded: "
              f"rel_l2 {np.round(err, 4).tolist()}  argmax agrees "
              f"{int(agree.sum())}/{agree.size}  reference "
              f"{time.monotonic() - t:.1f} s", flush=True)
        for i in np.flatnonzero(~(err <= REL_L2)):
            problems.append(f"probe {prompt.size}: position "
                            f"{positions[i]} rel_l2 {err[i]:.4f} over "
                            f"{REL_L2}")
    print(f"logit comparison: {n_compared} positions, limit {REL_L2}",
          flush=True)
    return problems


def loop_findings(system):
    """What the engine's counters say of the loop once the engine is
    closed (none: as it must be): no pool was lost; every decode dispatch
    ran against the pools themselves (the dense view of this cache cannot
    exist on the chip; a CPU run at a tiny size, as the tests make, runs
    the dense form and says so); and every row-step of a decode dispatch went
    through ``passes x layers`` layer passes, counted on the device by the
    loop that ran them. Row-steps are the tokens decode produced, and for
    each request retired at most ``decode_block - 1`` steps that its last
    dispatch ran beyond its answer."""
    engine, s = system.engine, system.engine.stats()
    layers, block = system.cfg.cache_layers, engine.config.decode_block
    decoded = s["generated_tokens_total"] - s["prefill_total"]
    held, resident = (s["cache_bytes_held_total"],
                      s["cache_positions_resident_total"])
    print("loop after the window:", {k: s[k] for k in (
        "loop_layer_passes_total", "loop_positions_attended_total",
        "decode_batches_total", "decode_in_place_total",
        "decode_page_bound_total", "page_wait_total", "pools_lost_total",
        "pools_consumed_total", "retired_total")},
        "tokens decode produced:", decoded,
        "cache bytes held a resident position:",
        round(held / resident) if resident else None, flush=True)
    problems = []
    if s["pools_lost_total"]:
        problems.append(f"pools_lost_total {s['pools_lost_total']}")
    in_place = engine.programs.decode["in_place"]
    if jax.default_backend() == "tpu" and not in_place:
        problems.append("the decode program was built in the dense form, "
                        "whose view of this cache the chip cannot hold")
    if s["decode_in_place_total"] != (s["decode_batches_total"]
                                      if in_place else 0):
        problems.append(
            f"decode_in_place_total {s['decode_in_place_total']} of "
            f"{s['decode_batches_total']} decode dispatches, built in "
            f"place: {in_place}")
    steps, rest = divmod(s["loop_layer_passes_total"], layers)
    if rest or steps % block or not \
            decoded <= steps <= decoded + (block - 1) * s["retired_total"]:
        problems.append(
            f"loop_layer_passes_total {s['loop_layer_passes_total']} is "
            f"not {layers} a row-step: {decoded} tokens decoded, "
            f"{s['retired_total']} requests retired")
    return problems


def late_probe(handles, problems, wait_s=LATE_PROBE_WAIT_S):
    """``problems`` with serve.measure's NEVER_RAN settled one way or the
    other. serve.measure takes a closed loop's probe from list indices
    ``clients`` to ``3 x clients`` and looks for it the instant the window
    shuts, which supposes a run that retires three rounds of the callers'
    list. A pool that holds 8-9 of 32 callers' requests retires 1.4: the
    probe (the list's 53rd) was submitted inside the window and is queued
    or running when it shuts, behind requests that reserved their pages
    before it, and the closed loop's other 31 requests are in flight
    around it still. So the SAME request is waited for, ``wait_s`` at
    most, and the SAME comparison made: its tokens inside that mix against
    its tokens alone. ``handles``: every request the engine took since
    ``measure`` began; the first is the probe alone (serve.measure's first
    request), those after it with its prompt and answer length the probe
    inside the mix. Not met in time, or never submitted: NEVER_RAN stays."""
    if NEVER_RAN not in problems or not handles:
        return problems
    alone = handles[0]
    mixed = [h for h in handles[1:] if h.max_new == alone.max_new
             and np.array_equal(h.prompt, alone.prompt)]
    t, end = time.monotonic(), time.monotonic() + wait_s
    met = [h for h in mixed if h.wait(max(0.0, end - time.monotonic()))]
    print(f"late probe: {len(mixed)} submitted inside the mix, {len(met)} "
          f"settled {time.monotonic() - t:.1f} s after the window shut",
          flush=True)
    if not met:
        return problems
    problems = [p for p in problems if p != NEVER_RAN]
    try:
        same = all(np.array_equal(h.result(0), alone.result(0))
                   for h in met)
    except Exception as e:      # the engine's typed error
        return problems + [f"the probe request inside the mix: "
                           f"{type(e).__name__}: {e}"[:200]]
    if not same:
        problems.append("the probe request alone != inside the mix")
    return problems


def measure(system, traffic, seconds, seed, tracer):
    """serve.measure, then the wait for its probe where the window shut on
    it (``late_probe``), the loop's books and the comparison with the
    reference, outside the window and outside set-up: as
    serve_blocks.measure. The closed loop leaves requests in flight; the
    engine is closed once the probe is in, which drops them (they are not
    in the sample), and the probes then drive its programs alone."""
    del system.engine.handles[:]
    run = serve.measure(system, traffic, seconds, seed, tracer)
    run["problems"] = late_probe(system.engine.handles, run["problems"])
    del system.engine.handles[:]
    system.engine.close()
    print("allocator peak before the comparison:",
          (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use"),
          flush=True)
    run["problems"] += loop_findings(system)
    with span("compare_with_reference"):
        run["problems"] += compare_with_reference(system, seed)
    return run
