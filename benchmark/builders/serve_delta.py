"""The serving cells of a model most of whose layers are gated delta-rule
linear attention (kind ``serve_delta``): layers whose cache is ONE entry a
request, a matrix state a head and the convolution's tail, beside a few
full-attention layers with pages; dense, untied, one pipeline stage of the
published model. The engine, the traffic and the window are builders/
serve.py's; the weights' drawing is builders/serve_blocks.py's; the probe
of the engine's own programs, the spoiling of a state entry and ``rel_l2``
are builders/serve_ssm.py's; the engine that keeps its handles and the
wait for serve.measure's probe request (``late_probe``) are builders/
serve_loop.py's, imported as the guard they are there. What is added is
this model's configuration, the decay parameters that take their published
initialisation, the probes' lengths, the books of the state kind and of
the rule's counters after the window, and the comparison of the engine's
own logits and first-layer state with the plain reference at the published
widths (reference/hybrid_delta.py), which decides ``correct``. (A sixth
system class of the same five lines: the ``benchmark`` issue that merges
the builders' classes takes it with the others.)
"""
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as fluid
from paddle_tpu.models.hybrid_delta import HybridDeltaConfig
from paddle_tpu.serving.decode_engine import DecodeConfig

from ..reference import hybrid_delta as reference
from ..tracing import span
from . import serve
from .serve_blocks import PROBE_STEPS, make_weights
from .serve_loop import HandleKeepingEngine, late_probe
from .serve_ssm import engine_logits, rel_l2, spoil_entry

# ---------------------------------------------------------------------
# The limits of the comparison that decides ``correct``, set FROM CONTROLS
# AT THIS DEPTH (16 layers) AND THESE LENGTHS (1,536 to 16,500 tokens), as
# serve_ssm.py and serve_loop.py set theirs (PERF.md section 4 has the
# readings they were set between; my chip runs, PR 47).
#
# REL_L2: ||engine logits - reference logits|| / ||reference logits|| at
# one position. The engine computes in bf16 with float32 accumulation,
# keeps its residual stream, its keys and values and the convolution's
# tail in bf16, the heads' states in float32, and takes the chunk's
# products at the chip's default precision (bf16 operands) but for the
# triangular system; the reference computes in float32 from the same
# bf16-valued weights, the rule position after position. The engine reads
# 0.156-0.250 (252 readings of 7 runs: mean 0.194, deviation 0.018; the
# limit is 8.9 of them above the mean), and that IS its precision: this
# block has no norm BEFORE a sublayer and a unit-sized update behind each,
# and with random weights it AMPLIFIES a perturbation (the reference with
# nothing but its residual stream rounded to bf16 behind every layer
# reads 0.035-0.049 against itself at this depth; at a mid size on the CPU
# that reading grows 0.0016, 0.0028, 0.0089, 0.045, 0.13 over 1, 2, 4, 8,
# 16 layers, and a bf16 engine without any kernel reads 0.25-0.40 there).
# Above it: the reference from float8 (e4m3) weights, the nearest
# precision below the published bf16, 0.99-1.11; beta without its factor 2
# 1.23-1.31; the decay left out 1.33-1.39; queries and keys not L2-normed
# 1.35-1.42; the rule's alpha missing from the correction 0.50-0.58; the
# convolution's tail not carried across an engine chunk 0.99-1.11 at
# 2,099 tokens and 0.90-0.96 at 16,500 (0.33-0.44 at 5,000: three
# positions of 5,000, seen there by neither limit) and, as it must, the
# engine's own 0.18-0.21 at 1,536.
#
# STATE_REL_L2: the same measure on the state itself, the FIRST delta-rule
# layer's entry [30, 96, 192] as the probe left it against the reference's
# state there. That layer's input is the embedding, so the engine's
# reading is its own rounding alone: 0.00443-0.00455 at every probe of
# every seed. Above the limit: alpha missing 0.0146-0.0166, the tail not
# carried 0.125 / 0.075 (2,099 / 16,500), float8 weights 0.098, beta 0.53,
# no L2 norm 0.99, no decay 1.98. WHAT IT CANNOT SEE: the state POOL in
# bf16 (the programs computing in float32) reads 0.0064 at every probe,
# under the limit, and its logits the engine's own 0.17-0.22: it rounds
# once a program and once a step and a probe decodes 8, as jamba's
# (serve_ssm.py); a limit between 0.0046 and 0.0064 would see it with a
# sixth of room on either side, which a fresh seed is not to be trusted
# with. The pool's type is held by tests/test_hybrid_delta.py.
#
# A start that READS its entry is left to no limit: the entry is filled
# with NaN before each probe (``spoil_entry``); planted, the whole-prompt
# program's reads NaN at 1,536 alone, the first chunk's at 2,099, 5,000
# and 16,500 alone. All readings: tests/hybrid_delta_faults.py on the chip
# at the published sizes, seed 2147483777, and the cell's runs (PERF.md
# section 4; my chip runs, PR 47).
#
# The model has no discrete choice: nothing is forced, there is no margin.
# ---------------------------------------------------------------------
REL_L2 = 0.35
STATE_REL_L2 = 0.009

A_RANGE = (1.0, 16.0)       # exp(A_log) is spread evenly over, by head
DT_RANGE = (1e-3, 1e-1)     # the steps softplus(dt_bias) is spread over


def model_config(model):
    """HybridDeltaConfig from the published config.json keys in the file."""
    H, n = model["num_attention_heads"], model["num_hidden_layers"]
    types = model["layer_types"]
    period = types.index("full_attention") + 1
    if model["model_type"] != "olmo_hybrid" or model["hidden_act"] != "silu" \
            or model["attention_bias"] or model["tie_word_embeddings"] \
            or model["rope_parameters"]["rope_theta"] is not None \
            or not model["linear_allow_neg_eigval"] \
            or model["linear_num_key_heads"] \
            != model["linear_num_value_heads"] \
            or model["hidden_size"] % H or len(types) != n \
            or types != [("full_attention" if i % period == period - 1
                          else "linear_attention") for i in range(n)]:
        raise ValueError("not the untied, unrotated model of periods of "
                         "delta-rule layers closed by a full-attention "
                         "layer, negative eigenvalues allowed, that this "
                         "builder's is")
    return HybridDeltaConfig(
        name=model["name"], vocab_size=model["vocab_size"],
        dim=model["hidden_size"], n_layers=n, attn_period=period,
        n_heads=H, n_kv=model["num_key_value_heads"],
        head_dim=model["hidden_size"] // H,
        ffn_hidden=model["intermediate_size"],
        delta_heads=model["linear_num_value_heads"],
        delta_key_dim=model["linear_key_head_dim"],
        delta_value_dim=model["linear_value_head_dim"],
        d_conv=model["linear_conv_kernel_dim"],
        norm_eps=float(model["rms_norm_eps"]), dtype=model["torch_dtype"])


def stand_ins(cfg):
    """The two tensors that a draw of normal(0, 0.02) would make
    invisible, the same for every seed and layer (the configuration's
    ``departures``). Drawn so, ``exp(A_log)`` is about 1 and
    ``softplus(dt_bias)`` about 0.7: every head forgets half its state a
    token, and ten tokens hide a stale, unreset or mis-carried state. The
    published initialisation instead: ``exp(A_log)`` spread evenly over
    A_RANGE and the step log-uniformly over DT_RANGE across the heads:
    decays from 0.999 to about 0.2 a token."""
    L, H = cfg.layers_of(1), cfg.delta_heads
    at = jnp.arange(H, dtype=jnp.float32) / max(1, H - 1)
    lo, hi = (math.log(x) for x in DT_RANGE)
    dt = jnp.exp(lo + (hi - lo) * at)
    return {
        "delta.a_log": jnp.broadcast_to(
            jnp.log(A_RANGE[0] + (A_RANGE[1] - A_RANGE[0]) * at), (L, H)),
        "delta.dt_bias": jnp.broadcast_to(
            dt + jnp.log(-jnp.expm1(-dt)), (L, H))}


class ServeDeltaSystem:
    def __init__(self, config, seed):
        self.config = config
        self.cfg = model_config(config)
        self.scope = fluid.Scope()
        with span("make_weights"):
            self.weights = make_weights(self.cfg, seed)
            self.weights.update(stand_ins(self.cfg))
            for name, value in self.weights.items():
                self.scope.set(name, value)
        with span("engine_warmup"):
            self.engine = HandleKeepingEngine(
                self.cfg, scope=self.scope,
                config=DecodeConfig(**config["builder"]["engine"]))
            self.warmup = self.engine.warmup()
        a, p = self.engine.allocator, self.engine.programs
        pools = sum(math.prod(shape) * jnp.dtype(dt).itemsize
                    for shape, dt in p.pool_specs)
        print(f"serve_delta: engine up, warm-up {self.warmup}, pools "
              f"{a.usable_pages} sequence pages of "
              f"{self.engine.config.page_size} + "
              f"{a.usable_of(self.engine.STATE)} state entries "
              f"({pools / 1e9:.3f} GB), "
              f"{sum(v.nbytes for v in self.weights.values()) / 1e9:.3f}"
              f" GB of weights, decode in place: {p.decode['in_place']}, "
              "prefill attention in the kernel: "
              f"{p.chunk['attn_in_kernel']}", flush=True)

    def compiles(self):
        return self.engine.exe.total_compiles()

    def close(self):
        self.engine.close()


def set_up(config, traffic, seed):
    return ServeDeltaSystem(config, seed)


def reference_logits(system, sequence, positions, through=None):
    """(the plain reference's logits at ``positions`` of ``sequence``, the
    first delta-rule layer's states after the whole of it [H, dk, dv]),
    from the very arrays the engine serves. ``through``: the weights
    rounded to that type on their way."""
    weights = reference.from_stacked(system.weights, system.config, through)
    with jax.default_matmul_precision("highest"):
        logits, states = reference.forward(
            weights, sequence, system.config, positions, return_states=True)
    return np.asarray(logits), np.asarray(states[min(states)])


def probe_prompts(system, seed):
    """One prompt for each prefill path the traffic reaches: three
    quarters of every whole-prompt bucket (1,536 of 2,048: padded by a
    quarter, the state has to stop at the last real position), one JUST
    OVER a chunk (2,099 at a chunk of 2,048: the chunk program's own start
    from zeros, ``offset == 0``, is another branch than the whole-prompt
    program's), one of two chunks and most of a third (5,000), and one of
    eight chunks and a little of a ninth (16,500: the state after 258
    chunks of the rule, the attention layers' fold over eight visits)."""
    engine = system.engine
    rng = np.random.RandomState((seed + 1) % (2 ** 32))
    cs = engine.programs.chunk_size
    longest = engine.config.prompt_buckets[-1]
    sizes = [b * 3 // 4 for b in sorted(engine.programs.prefill)] + [
        min(longest, n) for n in (
            cs + max(1, cs // 40),
            2 * cs + cs * 7 // 16 + max(1, cs // 256),
            8 * cs + max(1, cs * 29 // 512))]
    return [rng.randint(0, system.cfg.vocab_size, n).astype(np.int64)
            for n in dict.fromkeys(sizes)]


def compare_with_reference(system, seed):
    """The findings of the comparison of logits and state (none: correct),
    printing its figures. See the limits at the top of this file. The
    probes run one after the other on ONE state entry and the same first
    pages, which the window's requests used before them; the entry is
    spoiled before each (``spoil_entry``), so a probe whose first program
    looks at what the entry held reads NaN ever after (a NaN is over
    every limit)."""
    problems, n_compared = [], 0
    t = time.monotonic()
    probes = []
    for prompt in probe_prompts(system, seed):
        spoil_entry(system.engine)
        probes.append((prompt,) + engine_logits(system.engine, prompt,
                                                PROBE_STEPS))
    print(f"engine: {len(probes)} probes in {time.monotonic() - t:.1f} s",
          flush=True)
    # the engine is done: its pools and kept outputs make room for the
    # reference's float32 casts
    del system.engine._pools[:]
    system.engine.kept.clear()
    for prompt, got, decoded, state in probes:
        t = time.monotonic()
        sequence = np.concatenate([prompt, decoded[:-1]])
        positions = prompt.size - 1 + np.arange(1 + PROBE_STEPS)
        want, want_state = reference_logits(system, sequence, positions)
        err = rel_l2(got, want)
        state_err = float(rel_l2(state.reshape(-1), want_state.reshape(-1)))
        agree = np.argmax(got, -1) == np.argmax(want, -1)
        n_compared += err.size
        print(f"probe of {prompt.size} tokens + {PROBE_STEPS} decoded: "
              f"rel_l2 {np.round(err, 4).tolist()}  argmax agrees "
              f"{int(agree.sum())}/{agree.size}  first delta layer's "
              f"state rel_l2 {state_err:.5f}  reference "
              f"{time.monotonic() - t:.1f} s", flush=True)
        for i in np.flatnonzero(~(err <= REL_L2)):
            problems.append(f"probe {prompt.size}: position "
                            f"{positions[i]} rel_l2 {err[i]:.4f} over "
                            f"{REL_L2}")
        if not state_err <= STATE_REL_L2:
            problems.append(f"probe {prompt.size}: the first delta "
                            f"layer's state rel_l2 {state_err:.5f} over "
                            f"{STATE_REL_L2}")
    print(f"logit comparison: {n_compared} positions, limit {REL_L2}; "
          f"{len(probes)} states, limit {STATE_REL_L2}", flush=True)
    return problems


def state_findings(system, jobs_in_flight):
    """What the engine's counters say of the state kind and of the rule
    once the engine is closed (none: as it must be): no pool was lost;
    every request that got a first token had its state started from zeros
    exactly once (the requests whose chunks were still running at the
    close were reset too and are the only surplus); on the chip every
    decode dispatch attended through the kernel; the positions the
    delta-rule layers computed are ``layers`` x the prompt tokens the
    engine's prefill and chunk dispatches carried; and the states the
    decode steps updated are ``layers`` x the row-steps, which are the
    tokens decode produced and for each request at most ``decode_block -
    1`` steps that its last dispatch ran beyond its answer."""
    engine, s = system.engine, system.engine.stats()
    layers, block = system.cfg.layers_of(1), engine.config.decode_block
    decoded = s["generated_tokens_total"] - s["prefill_total"]
    print("state kind after the window:", {k: s[k] for k in (
        "state_resets_total", "prefill_total", "prefill_tokens_total",
        "pools_lost_total", "pools_consumed_total",
        "delta_state_updates_total", "delta_prefill_positions_total",
        "state_bytes_held_total", "cache_bytes_held_total",
        "decode_batches_total", "decode_in_place_total",
        "decode_page_bound_total", "page_wait_total",
        "attn_full_positions_total", "retired_total")},
        "tokens decode produced:", decoded,
        "chunk jobs in flight at the close:", jobs_in_flight, flush=True)
    problems = []
    if s["pools_lost_total"]:
        problems.append(f"pools_lost_total {s['pools_lost_total']}")
    surplus = s["state_resets_total"] - s["prefill_total"]
    if not 0 <= surplus <= jobs_in_flight:
        problems.append(
            f"state_resets_total {s['state_resets_total']} against "
            f"{s['prefill_total']} requests started and {jobs_in_flight} "
            "chunk jobs in flight")
    in_place = engine.programs.decode["in_place"]
    if jax.default_backend() == "tpu" and not in_place:
        problems.append("the decode program attends its pages through the "
                        "jax.numpy reference on the chip")
    if s["decode_in_place_total"] != (s["decode_batches_total"]
                                      if in_place else 0):
        problems.append(
            f"decode_in_place_total {s['decode_in_place_total']} of "
            f"{s['decode_batches_total']} decode dispatches")
    if s["delta_prefill_positions_total"] \
            != layers * s["prefill_tokens_total"]:
        problems.append(
            f"delta_prefill_positions_total "
            f"{s['delta_prefill_positions_total']} is not {layers} x "
            f"{s['prefill_tokens_total']} prompt tokens dispatched")
    steps, rest = divmod(s["delta_state_updates_total"], layers)
    beyond = (block - 1) * (s["retired_total"] + engine.config.max_batch)
    if rest or not decoded <= steps <= decoded + beyond:
        problems.append(
            f"delta_state_updates_total {s['delta_state_updates_total']} "
            f"is not {layers} a row-step: {decoded} tokens decoded, "
            f"{s['retired_total']} requests retired")
    return problems


def measure(system, traffic, seconds, seed, tracer):
    """serve.measure, then the wait for its probe where the window shut on
    it (serve_loop.late_probe), the state kind's books and the comparison
    with the reference, outside the window and outside set-up: as
    serve_ssm.measure and serve_loop.measure. serve.measure runs the probe
    request ALONE first, on entries nothing has used, and meets it again
    inside the mix on entries other requests left, and wants the same
    tokens."""
    engine = system.engine
    del engine.handles[:]
    run = serve.measure(system, traffic, seconds, seed, tracer)
    run["problems"] = late_probe(engine.handles, run["problems"])
    del engine.handles[:]
    # where in the callers' list the window opened and shut: a run that
    # lost a second of its lead-in to the machine opens on another stretch
    # of it, and 35 completions a window are no average over the list
    print("progress at the window's edges (prompt tokens dispatched, "
          "tokens generated, seconds the loop was busy):",
          [[round(run["edges"][w][k], 1) for k in (
              "prefill_tokens_total", "generated_tokens_total",
              "loop_busy_s_total")] for w in ("start", "end")], flush=True)
    a = engine.allocator
    print("cache kinds after the window:", {
        kind: f"{a.in_use_of(kind)}/{a.usable_of(kind)} in use"
        for kind in a.kinds},
        "allocator peak before the comparison:",
        (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use"),
        flush=True)
    # a chunk job can finish or start between this reading and the close
    jobs = engine.stats()["active_chunk_jobs"] + 1
    engine.close()
    run["problems"] += state_findings(system, jobs)
    with span("compare_with_reference"):
        run["problems"] += compare_with_reference(system, seed)
    return run
