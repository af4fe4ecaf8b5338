"""The serving cells of a model most of whose layers are KIMI DELTA
ATTENTION (a matrix state a head with a decay a CHANNEL, one ``state``
entry a request) beside LATENT attention layers (one 576-value entry a
position in ``sequence`` pages), a run of each layer's routed experts held
(kind ``serve_kda_latent``): Ling-3.0-flash's leading layers as one chip
of four that share each layer. The engine, the traffic and the window are
builders/serve.py's; the weights' drawing is builders/serve_blocks.py's;
the probe of the engine's own programs (a state table, the picks at the
positions before a compared one) is builders/serve_hybrid_conv.py's; the
spoiling of a state entry, ``rel_l2`` and the state kind's books are
builders/serve_ssm.py's; the selection bias is builders/serve_share.py's;
the engine that keeps its handles and the wait for serve.measure's probe
request (``late_probe``) are builders/serve_loop.py's.
What is added is this model's configuration, the tensors a plain draw
would hide (``stand_ins``), the probes' lengths and, after the window, the
comparison of the engine's own logits and first-layer state with the plain
reference of the same share at the published widths
(reference/kda_latent_moe_share.py), which decides ``correct``.
"""
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as fluid
from paddle_tpu.models.kda_latent_moe import KDA, LATENT, KdaLatentMoEConfig
from paddle_tpu.serving.decode_engine import DecodeConfig

from ..reference import kda_latent_moe_share as reference
from ..tracing import span
from . import serve
from .serve_blocks import PROBE_STEPS, make_weights
from .serve_hybrid_conv import engine_logits
from .serve_loop import HandleKeepingEngine, late_probe
from .serve_share import selection_bias
from .serve_ssm import rel_l2, state_findings

# ---------------------------------------------------------------------
# The limits of the comparison that decides ``correct`` (PERF.md section 4
# has the readings these were set between; my chip runs, PR 62).
#
# REL_L2: ||engine logits - reference logits|| / ||reference logits|| at
# one position. The engine computes in bf16 with float32 accumulation,
# keeps its residual stream, the latent entries and the convolutions' tails
# in bf16 and the heads' states in float32, and takes the chunk's products
# at the chip's default precision but for the triangular system; the
# reference computes in float32 from the same bf16-valued weights, the
# rule position after position. The limit lies between the engine's
# largest reading over seeds, probes and positions (0.0092 to 0.0117: six
# layers' rounding, whatever the prompt's length) and the reference itself
# computed from float8 (e4m3) weights, the nearest precision below the
# published bf16 (0.088 to 0.103). Above it too: the reference without
# the latent layer's gate a head 0.036-0.050, the write strength doubled
# 0.126-0.177, a SiLU output gate 0.35-0.41, a decay a head in a channel's
# place 0.39-0.47, the older taps dropped 0.45-0.54. (With every tensor
# drawn at 0.02, before ``stand_ins`` gave the embedding and the kda
# layers' ``wo`` their sizes, the same readings were 0.032-0.051 | 0.27-0.30
# | 0.088-0.115 | 0.66-0.83 | 1.14-1.24 | 1.22-1.30 | 1.30-1.38: the mixers
# were most of the stream and amplified everything, the engine's rounding
# too.)
#
# STATE_REL_L2: the same measure on the state itself, the FIRST kda
# layer's entry [32, 128, 128] as the probe left it against the
# reference's state there. That layer's input is the embedding, so the
# engine's reading is its own rounding alone: 0.0040-0.0045 at every probe
# of every seed. Above the limit: the reference with its state ROUNDED TO
# BF16 behind every position (what a bf16 state pool would keep) 0.0148 to
# 0.0201 (its logits 0.0104-0.0136, UNDER REL_L2: this is the limit that
# sees it), float8 weights 0.075-0.078, the write strength doubled 0.45,
# the older taps dropped 2.2, a decay a head 6.5-7.5.
#
# MARGIN: the reference is computed WITH the engine's picks (routing is
# discrete, and here twice so: a group is kept or not, then an expert) at
# the positions compared AND at the nine before them, whose routing
# reaches them through the taps of the three kda layers behind the first
# routed one (``picks_reach``, as serve_hybrid_conv.py's). A pick that is
# not the reference's own is accepted only where the reference's gap for
# it (how far its group scores under the last group kept, or its selection
# score under the last expert picked: reference/latent_moe_share.py) lies
# under MARGIN, at the positions compared: the engine's largest such gap
# is 0.0008 to 0.0033 (a third of the positions have one: 8 picks of 512
# scores leave margins of 0.0000-0.0001 at the least), the float8
# reference's 0.0175 to 0.0250.
#
# A start that READS its entry is left to no limit: the entry is filled
# with NaN before every run of a probe's path (serve_ssm.spoil_entry,
# inside serve_hybrid_conv.engine_logits).
# ---------------------------------------------------------------------
REL_L2 = 0.03
STATE_REL_L2 = 0.009
MARGIN = 0.01

# how long after the window serve.measure's probe request is waited for: it
# is admitted once 442 of the list's requests have retired and then lives
# 64 dispatches (PERF.md section 4)
PROBE_WAIT_S = 300.0

# exp(A_log) a head and the step a channel that the decays are spread over
A_RANGE = (1.0, 4.0)
X_RANGE = (-9.0, 5.0)
# the embedding and the kda layers' output projection against their draws
SCALED = {"tok_emb": 50.0, "lead.wo": 0.3, "kda.wo": 0.3}


def model_config(model):
    """KdaLatentMoEConfig from the published config.json keys in the file,
    ``layer_types`` and ``experts_held``, the run of the router's experts
    this chip has."""
    kinds, held = model["layer_types"], model["experts_held"]
    group = model["layer_group_size"]
    first = model["published_first_layer"]
    if model["score_function"] != "sigmoid" \
            or not model["moe_router_enable_expert_bias"] \
            or not model["norm_topk_prob"] or not model["kda_safe_gate"] \
            or not model["no_kda_lora"] or model["q_lora_rank"] is not None \
            or model["gated_attention_proj_granularity_type"] != "head_wise" \
            or model["group_norm_size"] != 1 \
            or held["count"] != model["num_experts"] \
            or len(kinds) != model["num_hidden_layers"] \
            or kinds != [reference.MLA if (first + i + 1) % group == 0
                         else reference.KDA for i in range(len(kinds))] \
            or model["moe_shared_expert_intermediate_size"] \
            != model["moe_intermediate_size"] \
            or any(model[key][first:first + len(kinds)] != [0] * len(kinds)
                   for key in ("expert_swiglu_limit_list",
                               "share_expert_swiglu_limit_list")):
        raise ValueError("not the router, the gates, the layer pattern, "
                         "the share or the unclamped experts this "
                         "builder's model has")
    return KdaLatentMoEConfig(
        name=model["name"], vocab_size=model["vocab_size"],
        dim=model["hidden_size"],
        layer_pattern=tuple(LATENT if k == reference.MLA else KDA
                            for k in kinds),
        n_dense_layers=model["first_k_dense_replace"],
        n_heads=model["num_attention_heads"],
        kv_rank=model["kv_lora_rank"], nope_dim=model["qk_nope_head_dim"],
        rope_dim=model["qk_rope_head_dim"], v_dim=model["v_head_dim"],
        rope_base=float(model["rope_theta"]),
        kda_key_dim=model["head_dim"], kda_value_dim=model["head_dim"],
        d_conv=model["short_conv_kernel_size"],
        gate_floor=float(model["kda_lower_bound"]),
        ffn_hidden=model["intermediate_size"],
        n_experts=held["count"], router_width=held["of"],
        experts_first=held["first"], n_group=model["n_group"],
        topk_group=model["topk_group"],
        moe_top_k=model["num_experts_per_tok"],
        expert_hidden=model["moe_intermediate_size"], n_shared=1,
        route_scale=float(model["routed_scaling_factor"]),
        norm_eps=float(model["rms_norm_eps"]), dtype=model["torch_dtype"])


def stand_ins(cfg, weights):
    """The tensors that a draw of normal(0, 0.02) would make invisible,
    the same for every seed and layer (the configuration's ``departures``).
    THE TAPS: of a Conv1d's own initial size (rms ``k ** -0.5``: cosines a
    channel, a fourth of a turn apart a tap), so that the three older
    inputs weigh as much as the newest and a tail that is dropped, stale
    or shifted moves the logits. THE DECAYS: drawn so, ``exp(A_log)`` is 1
    and the gate ``-5 sigmoid(0) = -2.5`` in every channel: every channel
    forgets nine tenths of its state a token and no state older than three
    tokens matters. Instead ``exp(A_log)`` is spread evenly over A_RANGE
    across the heads and ``dt_bias`` evenly over X_RANGE across a head's
    channels (in an order that is no run of neighbours), so that a head's
    channels decay from ``exp(-5 sigmoid(-9)) = 0.9994`` a token or slower
    (what such a channel holds it keeps for thousands of positions) down
    to ``exp(-5 sigmoid(5)) = exp(-4.97)``, the gate's floor, beside a
    data-dependent part of unit size (``u Wf``): a decay taken a head at a
    time, a stale state and a state kept in bf16 all show. THE
    SELECTION BIAS: +-0.02 by expert, as DeepSeek-V3's share has it
    (serve_share.selection_bias). THE GATES a head (``wg``) and a value
    channel (``wz``) need none: drawn at 0.02 against a normed input of
    2,560 widths their products have unit size, so their sigmoids spread
    over (0.1, 0.9) and a gate left out, or a SiLU in a sigmoid's place,
    is no constant factor that the next norm removes. THE ROUTER'S INPUT:
    with random projections a kda layer's ``o`` collapses onto ONE direction
    (queries, keys and values behind a SiLU have positive means, so after a
    few dozen positions the state's common part outgrows every token's
    own), and the layer's output is half a CONSTANT, the same for every
    token of every request; drawn at 0.02 beside an embedding of 0.02 that
    constant is a quarter of what the routers read, every token's scores
    lean the same way, and the top 8 of 512 send a third of the held
    experts nothing in a step of 256 rows (a fullest expert at 12-17 times
    the mean, moving with the seed, and ``out_tok_s`` with it: PERF.md
    section 6, PR 62). A trained router spreads its load. So the EMBEDDING
    is at unit size (the seed's draw x 50: a token's own identity is most
    of the stream) and the kda layers' ``wo`` at 0.3 of their draw: the
    constant is then 3% of a router's input, 90% of the held experts are
    reached and the fullest has 3-4 times the mean, as the other cells'
    routers have it, while a fault in the rule still moves the logits five
    times over the limit (the readings above the limits, this file's
    head)."""
    out = {}
    k, c, h, dk = cfg.d_conv, cfg.conv_channels, cfg.n_heads, \
        cfg.kda_key_dim
    turn = 2.0 * jnp.pi
    taps = k ** -0.5 * jnp.sqrt(2.0) * jnp.cos(
        turn * (0.618 * jnp.arange(c)[None] + jnp.arange(k)[:, None] / k))
    at_h = jnp.arange(h, dtype=jnp.float32) / max(1, h - 1)
    at_c = ((jnp.arange(dk) * 37) % dk).astype(jnp.float32) / max(1, dk - 1)
    a_log = jnp.log(A_RANGE[0] + (A_RANGE[1] - A_RANGE[0]) * at_h)
    dt_bias = jnp.tile(X_RANGE[0] + (X_RANGE[1] - X_RANGE[0]) * at_c, h)
    for name, value in weights.items():
        if name.endswith(".conv_w"):
            made = taps
        elif name.endswith(".a_log"):
            made = a_log
        elif name.endswith(".dt_bias"):
            made = dt_bias
        elif name.endswith(".moe_bias"):
            made = selection_bias(cfg)[0]
        elif name in SCALED:
            out[name] = (value.astype(jnp.float32)
                         * SCALED[name]).astype(value.dtype)
            continue
        else:
            continue
        out[name] = jnp.broadcast_to(made.astype(value.dtype), value.shape)
    return out


class ServeKdaLatentSystem:
    def __init__(self, config, seed):
        self.config = config
        self.cfg = model_config(config)
        self.scope = fluid.Scope()
        with span("make_weights"):
            self.weights = make_weights(self.cfg, seed)
            self.weights.update(stand_ins(self.cfg, self.weights))
            for name, value in self.weights.items():
                self.scope.set(name, value)
        with span("engine_warmup"):
            self.engine = HandleKeepingEngine(
                self.cfg, scope=self.scope,
                config=DecodeConfig(**config["builder"]["engine"]))
            self.warmup = self.engine.warmup()
        a, p = self.engine.allocator, self.engine.programs
        held = config["experts_held"]
        pools = sum(math.prod(shape) * jnp.dtype(dt).itemsize
                    for shape, dt in p.pool_specs)
        print(f"serve_kda_latent: engine up, warm-up {self.warmup}, pools "
              f"{a.usable_pages} sequence pages of "
              f"{self.engine.config.page_size} + "
              f"{a.usable_of(self.engine.STATE)} state entries "
              f"({pools / 1e9:.3f} GB), experts {held['first']}-"
              f"{held['first'] + held['count'] - 1} of {held['of']} held, "
              f"{sum(v.nbytes for v in self.weights.values()) / 1e9:.3f}"
              f" GB of weights, decode in place: {p.decode['in_place']}, "
              "prefill attention in the kernel: "
              f"{p.chunk['attn_in_kernel']}, decode experts in the "
              f"kernel: {p.decode['experts_in_kernel']}", flush=True)

    def compiles(self):
        return self.engine.exe.total_compiles()

    def close(self):
        self.engine.close()


def set_up(config, traffic, seed):
    return ServeKdaLatentSystem(config, seed)


def picks_reach(cfg):
    """How many positions BEFORE a compared one the reference is routed as
    the engine routed: a routed layer's output at position q enters
    position t > q sharply through the TAPS of the kda layers behind it
    (``d_conv - 1`` positions a layer; 9 in the published cut: three kda
    layers of four taps behind the first routed one), and through the
    states and the latent layer's attention only as one position among
    all that came before. Routing is discrete and a near-tie falls either
    way under bf16's rounding (serve_hybrid_conv.picks_reach)."""
    behind = cfg.layer_pattern[cfg.n_dense_layers + 1:]
    return (cfg.d_conv - 1) * sum(1 for k in behind if k == KDA)


def first_state(engine, entry=1):
    """The first kda layer's heads' states in entry ``entry``, [H, dk, dv]
    float32, as the last dispatch left them."""
    return np.asarray(engine._pools[engine._pool_kind.index(
        engine.STATE)][0, entry])


def reference_logits(system, sequence, positions, picks=None, model=None,
                     through=None):
    """serve_hybrid_conv.reference_logits against this model's reference:
    (its logits at ``positions`` of ``sequence``, selection margins and
    forced-pick gaps, the first kda layer's states after the whole
    sequence), from the very arrays the engine serves; with ``picks`` [n,
    layers, K], routed as the engine routed at the LAST n of ``positions``
    and the n - len(positions) positions before the first (the margins
    and gaps are then of all n). ``model``: the configuration with a term
    switched off; ``through``: the weights rounded to that type on their
    way."""
    weights = reference.from_stacked(system.weights, system.config,
                                     through)
    forced, at_all = None, np.asarray(positions)
    if picks is not None:
        at_all = positions[-1] + 1 - picks.shape[0] \
            + np.arange(picks.shape[0])
        at = np.zeros((sequence.size,), bool)
        at[at_all] = True
        forced = {}
        for layer in range(picks.shape[1]):
            full = np.zeros((sequence.size, picks.shape[2]), np.int32)
            full[at_all] = picks[:, layer]
            forced[layer] = (at, full)
    with jax.default_matmul_precision("highest"):
        logits, margins, gaps, states = reference.forward(
            weights, sequence, model or system.config, at_all, forced,
            return_states=True)
    return (np.asarray(logits)[-len(positions):], np.asarray(margins),
            np.asarray(gaps), np.asarray(states[min(states)]))


def probe_prompts(system, seed):
    """One prompt for each prefill path the traffic reaches: a SHORT one
    (a tenth of the smallest bucket: what the entry's last holder left is
    then most of what a stale state would hold), three quarters of every
    whole-prompt bucket, and one of TWO CHUNKS, the second a short one
    (2,099 at a chunk of 2,048: its first taps reach into the chunk
    before, its state and its latent pages are the first chunk's), ids
    from the vocabulary's slice."""
    engine = system.engine
    rng = np.random.RandomState((seed + 1) % (2 ** 32))
    cs = engine.programs.chunk_size
    buckets = sorted(engine.programs.prefill)
    sizes = [max(system.cfg.d_conv + 1, buckets[0] // 10)] \
        + [b * 3 // 4 for b in buckets]
    if cs is not None:
        sizes.append(cs + max(1, cs // 40))
    return [rng.randint(0, system.cfg.vocab_size, n).astype(np.int64)
            for n in sizes]


def compare_with_reference(system, seed):
    """The findings of the comparison of logits, picks and state (none:
    correct), printing its figures. See the limits at the top of this
    file. The probes run one after the other on ONE state entry and the
    same first pages, which the window's requests used before them."""
    problems, n_compared, n_rerouted = [], 0, 0
    t = time.monotonic()
    back = picks_reach(system.cfg)
    probes = []
    for prompt in probe_prompts(system, seed):
        probes.append((prompt,) + engine_logits(
            system.engine, prompt, PROBE_STEPS, back)
            + (first_state(system.engine),))
    print(f"engine: {len(probes)} probes in {time.monotonic() - t:.1f} s",
          flush=True)
    # the engine is done: its pools and kept outputs make room for the
    # reference's float32 casts
    del system.engine._pools[:]
    system.engine.kept.clear()
    first = system.cfg.experts_first
    last = first + system.cfg.n_experts - 1
    for prompt, got, picks, decoded, state in probes:
        t = time.monotonic()
        sequence = np.concatenate([prompt, decoded[:-1]])
        positions = prompt.size - 1 + np.arange(1 + PROBE_STEPS)
        want, margins, gaps, want_state = reference_logits(
            system, sequence, positions, picks)
        err = rel_l2(got, want)
        s_err = float(np.linalg.norm(state - want_state)
                      / np.linalg.norm(want_state))
        agree = np.argmax(got, -1) == np.argmax(want, -1)
        own = gaps[:, -err.size:]
        rerouted = (gaps > 0).any(axis=0)
        n_compared += err.size
        n_rerouted += int(rerouted[-err.size:].sum())
        print(f"probe of {prompt.size} tokens + {PROBE_STEPS} decoded: "
              f"rel_l2 {np.round(err, 4).tolist()}  state rel_l2 "
              f"{s_err:.5f}  argmax agrees {int(agree.sum())}/{agree.size}"
              f"  picks on held experts "
              f"{int(((picks >= first) & (picks <= last)).sum())} of "
              f"{picks.size}  picks not the reference's own at "
              f"{int(rerouted.sum())} of the {picks.shape[0]} positions "
              f"routed as the engine routed, largest gap {own.max():.4f} "
              f"at those compared ({gaps.max():.4f} at all; least margin "
              f"{margins.min():.4f})  reference "
              f"{time.monotonic() - t:.1f} s", flush=True)
        for i in np.flatnonzero(~(err <= REL_L2)):
            problems.append(f"probe {prompt.size}: position "
                            f"{positions[i]} rel_l2 {err[i]:.4f} over "
                            f"{REL_L2}")
        if not s_err <= STATE_REL_L2:
            problems.append(f"probe {prompt.size}: the first kda layer's "
                            f"state rel_l2 {s_err:.5f} over {STATE_REL_L2}")
        for layer, i in zip(*np.nonzero(own >= MARGIN)):
            problems.append(
                f"probe {prompt.size}: position {positions[i]}, routed "
                f"layer {layer}: the engine's picks "
                f"{picks[i - err.size, layer].tolist()} lie "
                f"{own[layer, i]:.4f} from the reference's, over {MARGIN}")
    print(f"logit comparison: {n_compared} positions, {n_rerouted} of them "
          f"routed not as the reference alone would (every gap under "
          f"{MARGIN})", flush=True)
    return problems


def measure(system, traffic, seconds, seed, tracer):
    """serve.measure, then the state kind's books (serve_ssm's: no pool
    lost, every request's state started from zeros exactly once) and the
    comparison with the reference, outside the window and outside set-up:
    as serve_hybrid_conv.measure. serve.measure's probe is the 699th of
    the callers' list (the shortest answer of its second round): submitted
    inside the window and queued or running when it shuts, so the SAME
    request is waited for (serve_loop.late_probe, PROBE_WAIT_S at most)
    before the engine is closed."""
    del system.engine.handles[:]
    run = serve.measure(system, traffic, seconds, seed, tracer)
    run["problems"] = late_probe(system.engine.handles, run["problems"],
                                 PROBE_WAIT_S)
    del system.engine.handles[:]
    engine = system.engine
    a = engine.allocator
    print("cache kinds after the window:", {
        kind: f"{a.in_use_of(kind)}/{a.usable_of(kind)} in use"
        for kind in a.kinds}, {
        k: v for k, v in engine.stats().items()
        if k.startswith(("cache_", "attn_", "moe_", "kda_", "page_",
                         "pages_", "admit_", "decode_page"))},
        "allocator peak before the comparison:",
        (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use"),
        flush=True)
    # a chunk job can finish or start between this reading and the close
    jobs = engine.stats()["active_chunk_jobs"] + 1
    engine.close()
    run["problems"] += state_findings(engine, jobs)
    with span("compare_with_reference"):
        run["problems"] += compare_with_reference(system, seed)
    return run
