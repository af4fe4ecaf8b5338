"""The serving cells of a model most of whose layers are GATED SHORT
CONVOLUTIONS whose whole cache is two inputs a sequence, beside attention
layers with 64-wide heads and a norm a head, every layer's routed experts
ALL held (kind ``serve_hybrid_conv``): LFM2-24B-A2B's leading layers as
the first of eight pipeline stages. The engine, the traffic and the window
are builders/serve.py's; the weights' drawing is builders/
serve_blocks.py's; the spoiling of a state entry before a probe is
builders/serve_ssm.py's; what is added is this model's configuration, the
tensors a plain draw would hide (``stand_ins``), its probes (which carry
the rows' state table AND return the routed layers' picks) and, after the
window, the comparison of the engine's own logits with the plain reference
at the published widths (reference/hybrid_conv_moe.py), which decides
``correct``.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as fluid
from paddle_tpu.models.hybrid_conv_moe import (CONV, FULL,
                                               HybridConvMoEConfig)
from paddle_tpu.serving.decode_engine import DecodeConfig, DecodeEngine

from ..reference import hybrid_conv_moe as reference
from ..tracing import span
from . import serve
from .serve_blocks import PROBE_STEPS, make_weights
from .serve_ssm import spoil_entry, state_findings

# ---------------------------------------------------------------------
# The limits of the comparison that decides ``correct`` (PERF.md section 4
# has the readings these were set between; my chip runs, PR 58).
#
# REL_L2: ||engine logits - reference logits|| / ||reference logits|| at
# one position. The engine computes in bf16 with float32 accumulation and
# keeps its residual stream, its keys and values and the convolutions'
# tails in bf16; the reference computes in float32 from the same
# bf16-valued weights. The limit lies between the engine's largest reading
# over seeds, probes and positions (0.0236; 0.0173 the least: five layers'
# rounding, whatever the prompt's length) and the reference itself
# computed from float8 (e4m3) weights, the nearest precision below the
# published bf16 (0.278 to 0.328): three times of room above the one and
# four under the other. The reference without its ``B`` gate reads
# 1.38-1.44, without its ``C`` gate 1.37-1.45, with the two older taps
# dropped (a tail not carried) 1.17-1.31, with one norm over the whole
# projection in place of a head's 0.209-0.334, without the bias in the
# selection 0.166-0.291 where it routes by itself.
#
# MARGIN: the reference is computed WITH the engine's picks (routing is
# discrete) at the positions compared AND at the six before them, whose
# routing reaches them through the taps (``picks_reach``: with the picks
# before left to the reference a compared position read 0.031-0.077 where
# a neighbour's expert was swapped). A pick that is not the reference's
# own is accepted only where its selection score (sigmoid + bias) lies
# less than MARGIN under the reference's last own pick's, AT THE POSITIONS
# COMPARED (the six before them have neighbours of their own that are left
# to the reference, and one of them read 0.0282 in one run of seven): the
# engine's largest such gap is 0.0086 over six runs (0.0094 over all fifteen
# positions in seven more; a quarter of the positions have one: the
# margin between the 4th and the 5th of 64 scores is 0.011-0.015 in the
# median and 0.0000-0.0011 at the least), the float8 reference's 0.086 to
# 0.173, and a reference WITHOUT the selection bias, made to take the
# engine's picks, finds them 0.091 to 0.108 under its own.
#
# A tail has no limit of its own: a start that READS what its entry held
# is NaN ever after (the entry is spoiled before every run of a probe's
# path, as serve_ssm.py's), and a tail that is not carried from a chunk to
# the next or from a step to the next is the reference with its older
# taps dropped.
# ---------------------------------------------------------------------
REL_L2 = 0.07
MARGIN = 0.025


def model_config(model):
    """HybridConvMoEConfig from the published config.json keys in the file
    (and ``head_dim``, which the file assumes)."""
    kinds = model["layer_types"]
    r = model["rope_parameters"]
    if model["model_type"] != "lfm2_moe" or model["conv_bias"] \
            or not model["norm_topk_prob"] or not model["use_expert_bias"] \
            or r["rope_type"] != "default" \
            or set(kinds) != {reference.FULL, reference.CONV} \
            or len(kinds) != model["num_hidden_layers"] \
            or not model["tie_word_embeddings"]:
        raise ValueError("not the convolution, the router, the rotation or "
                         "the tied head this builder's model has")
    return HybridConvMoEConfig(
        name=model["name"], vocab_size=model["vocab_size"],
        dim=model["hidden_size"],
        layer_pattern=tuple(CONV if k == reference.CONV else FULL
                            for k in kinds),
        n_dense_layers=model["num_dense_layers"],
        n_heads=model["num_attention_heads"],
        n_kv=model["num_key_value_heads"], head_dim=model["head_dim"],
        rope_base=float(r["rope_theta"]), d_conv=model["conv_L_cache"],
        ffn_hidden=model["intermediate_size"],
        n_experts=model["num_experts"],
        moe_top_k=model["num_experts_per_tok"],
        expert_hidden=model["moe_intermediate_size"],
        route_scale=float(model["routed_scaling_factor"]),
        route_eps=reference.ROUTE_EPS, norm_eps=float(model["norm_eps"]),
        dtype=model["torch_dtype"])


def stand_ins(cfg, weights):
    """The tensors that a draw of normal(0, 0.02) would make invisible,
    the same for every seed (the configuration's ``departures``), and the
    tied head. THE TAPS: drawn so, a convolution's output is a fiftieth of
    its input and the layer adds nothing to the stream, whatever the taps
    do; a Conv1d's own initialisation is uniform(+-1 / sqrt(k)), and these
    are of that size, three cosines a channel a third of a turn apart, so
    that every channel's older taps weigh as much as its newest and a tail
    that is dropped, stale or shifted by one moves the logits. THE
    SELECTION BIAS: spread evenly over +-0.08 across the experts (in an
    order that is no run of neighbours) against sigmoid scores of 0.3-0.7,
    so that it changes the picks (the reference without it finds the
    engine's 0.09-0.11 under its own). THE NORMS A HEAD: 2 +- 0.5 across a
    head's widths, queries and keys a quarter turn apart: four times the
    scores of a norm of ones, a softmax sharp enough that a norm taken
    over the whole projection (a head's scale off by a tenth) shows
    (0.21-0.33 where the engine's rounding is 0.02). ``lm_head``
    is the embedding's transpose (the programs read a head of their own,
    [dim, vocab])."""
    out = {"lm_head": weights["tok_emb"].T}
    k, d, e, hd = cfg.d_conv, cfg.dim, cfg.n_experts, cfg.head_dim
    turn = 2.0 * jnp.pi
    taps = k ** -0.5 * jnp.sqrt(2.0) * jnp.cos(
        turn * (0.618 * jnp.arange(d)[None] + jnp.arange(k)[:, None] / k))
    bias = 0.08 * (2.0 * ((jnp.arange(e) * 37) % e) / max(1, e - 1) - 1.0)
    head = turn * 3.0 * jnp.arange(hd) / hd
    for name, value in weights.items():
        if name.endswith(".conv_w"):
            made = taps
        elif name.endswith(".moe_bias"):
            made = bias
        elif name.endswith(".q_norm"):
            made = 2.0 + 0.5 * jnp.sin(head)
        elif name.endswith(".k_norm"):
            made = 2.0 + 0.5 * jnp.cos(head)
        else:
            continue
        out[name] = jnp.broadcast_to(made.astype(value.dtype), value.shape)
    return out


class ServeHybridConvSystem:
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
            self.engine = DecodeEngine(
                self.cfg, scope=self.scope,
                config=DecodeConfig(**config["builder"]["engine"]))
            self.warmup = self.engine.warmup()
        a = self.engine.allocator
        print(f"serve_hybrid_conv: engine up, warm-up {self.warmup}, pools "
              f"{a.usable_pages} sequence pages + "
              f"{a.usable_of(self.engine.STATE)} state entries (a tail "
              f"each), all {self.cfg.n_experts} experts of a layer held, "
              f"{sum(v.nbytes for v in self.weights.values()) / 1e9:.3f}"
              " GB of weights (the tied head held twice: the programs "
              "read its transpose)", flush=True)

    def compiles(self):
        return self.engine.exe.total_compiles()

    def close(self):
        self.engine.close()


def set_up(config, traffic, seed):
    return ServeHybridConvSystem(config, seed)


def picks_reach(cfg):
    """How many positions BEFORE a compared one have routing that can
    reach it: a routed layer's output at position q enters position t > q
    through the taps of the conv layers behind it alone (``d_conv - 1``
    positions a layer), where no attention layer follows the first routed
    one, as in the published cut (6: three conv layers of three taps);
    None where one does (the tiny test models: any earlier position can).
    Routing is discrete and a near-tie falls either way under bf16's
    rounding, so the reference has to take the engine's picks at every
    such position, or a position compared reads the neighbour's swapped
    expert through the taps (0.06-0.08 where the engine's own rounding is
    0.02: my chip run, PR 58)."""
    behind = cfg.layer_pattern[cfg.n_dense_layers + 1:]
    if FULL in behind:
        return None
    return (cfg.d_conv - 1) * len(behind)


def engine_logits(engine, prompt, steps, back=0, entry=1):
    """serve_ssm.engine_logits for a model with routed layers: what the
    engine's own programs gave at the prompt's last position and at
    ``steps`` decoded ones, (logits [1 + steps, V] float32, the routed
    layers' picks at those positions AND at the ``back`` before them
    [back + 1 + steps, layers, K], the tokens decoded). The prompt goes
    through the path a request of its length takes (a prompt over
    ``chunk_size``: the chunk program, its tails carried from chunk to
    chunk), then the decode program at its full width from slot 0, the
    other slots inactive. A program reports its picks at a row's LAST real
    token, so the picks at the ``back`` positions before it are those of
    the same path run on the prompt cut short there (same programs, same
    bucket; a position's values depend on no later one), each run from a
    spoiled entry as the last is. The engine must be closed: the probe
    takes the first pages and the state entry ``entry`` for itself."""
    c = engine.config
    table = np.zeros((1, engine.pages_per_seq), np.int32)
    need = engine.allocator.pages_for(prompt.size + steps + c.decode_block)
    table[0, :need] = 1 + np.arange(need)
    held = {engine.STATE: [entry]}
    state = engine._kind_tables([held])
    cs = engine.programs.chunk_size
    chunked = cs is not None and prompt.size > cs
    bucket = None if chunked else engine._bucket_for(prompt.size)

    def run_prompt(n):
        """The prompt's first ``n`` tokens through its path: (next token,
        what the last dispatch kept)."""
        spoil_entry(engine, entry)
        if not chunked:
            tokens = np.zeros((1, bucket), np.int64)
            tokens[0, :n] = prompt[:n]
            nxt = engine._run_prefill_program(
                bucket, tokens, np.asarray([n], np.int32), table, *state)
            return nxt, engine.kept[f"prefill_{bucket}"]
        for off in range(0, n, cs):
            sl = prompt[off:min(n, off + cs)]
            tokens = np.zeros((1, cs), np.int64)
            tokens[0, :sl.size] = sl
            nxt = engine._run_chunk_program(
                tokens, np.asarray([sl.size], np.int32),
                np.asarray([off], np.int32), table, *state)
        return nxt, engine.kept["chunk"]

    floor = cs + 1 if chunked else 1    # cut short, the path stays the path
    picks = [np.asarray(run_prompt(prompt.size - j)[1]["picks"])[:1]
             for j in range(min(back, prompt.size - floor), 0, -1)]
    nxt, kept = run_prompt(prompt.size)
    logits = [np.asarray(kept["logits"])[:1]]
    picks.append(np.asarray(kept["picks"])[:1])
    n_back = len(picks) - 1
    decoded = [int(nxt[0])]
    toks = np.zeros((c.max_batch,), np.int64)
    pos = np.ones((c.max_batch,), np.int32)
    tables = np.zeros((c.max_batch, engine.pages_per_seq), np.int32)
    tables[0] = table[0]
    states = engine._kind_tables([held] + [None] * (c.max_batch - 1))
    while len(decoded) <= steps:
        toks[0], pos[0] = decoded[-1], prompt.size + len(decoded) - 1
        out = engine._run_decode_program(toks, pos, tables, *states)
        logits.append(np.asarray(engine.kept["decode"]["logits"])[0])
        picks.append(np.asarray(engine.kept["decode"]["picks"])[0])
        decoded.extend(int(t) for t in out[0])
    return (np.concatenate(logits)[:1 + steps],
            np.concatenate(picks)[:n_back + 1 + steps],
            np.asarray(decoded[:1 + steps], np.int64))


def reference_logits(system, sequence, positions, picks=None, model=None,
                     through=None):
    """serve_hybrid.reference_logits against this model's reference: its
    logits, selection margins and forced-pick gaps at ``positions`` of
    ``sequence``, from the very arrays the engine serves; with ``picks``
    [n, layers, K], routed as the engine routed at the LAST n of
    ``positions`` and the n - len(positions) positions before the first
    (``engine_logits``' ``back``; the margins and gaps are then of all n).
    ``model``: the configuration with a term switched off; ``through``:
    the weights rounded to that type on their way."""
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
        logits, margins, gaps = reference.forward(
            weights, sequence, model or system.config, at_all, forced)
    return (np.asarray(logits)[-len(positions):], np.asarray(margins),
            np.asarray(gaps))


def probe_prompts(system, seed):
    """One prompt for each prefill path the traffic reaches: a SHORT one
    (a tenth of the smallest bucket and at least the taps: what the
    entry's last holder left is then most of what a stale tail would
    hold), three quarters of every whole-prompt bucket (so each is padded
    by a quarter and the tail has to be the last REAL inputs), and one of
    TWO CHUNKS, the second a short one (2,099 at a chunk of 2,048: 51
    tokens whose first two taps reach into the chunk before, so a tail
    that is not carried shows at once), whose decoded positions are the
    late ones."""
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
    """The findings of the logit comparison (none: correct), printing its
    figures. See the limits at the top of this file. The probes run one
    after the other on ONE state entry and the same first pages, which the
    window's requests used before them; the entry is filled with NaN
    before every run of a prompt's path (serve_ssm.spoil_entry). The
    reference routes as the engine routed at every position whose routing
    can reach a position compared (``picks_reach``)."""
    problems, n_compared, n_rerouted = [], 0, 0
    t = time.monotonic()
    back = picks_reach(system.cfg) or 0
    probes = [(prompt,) + engine_logits(system.engine, prompt, PROBE_STEPS,
                                        back)
              for prompt in probe_prompts(system, seed)]
    print(f"engine: {len(probes)} probes in {time.monotonic() - t:.1f} s",
          flush=True)
    # the engine is done: its pools and kept outputs make room for the
    # reference's float32 casts
    del system.engine._pools[:]
    system.engine.kept.clear()
    for prompt, got, picks, decoded in probes:
        t = time.monotonic()
        sequence = np.concatenate([prompt, decoded[:-1]])
        positions = prompt.size - 1 + np.arange(1 + PROBE_STEPS)
        want, margins, gaps = reference_logits(system, sequence, positions,
                                               picks)
        err = np.linalg.norm(got - want, axis=-1) \
            / np.linalg.norm(want, axis=-1)
        agree = np.argmax(got, -1) == np.argmax(want, -1)
        # the margin is held at the positions compared: there everything
        # that reaches a position is routed as the engine routed it; the
        # positions before them have neighbours of their own that are not
        own = gaps[:, -err.size:]
        rerouted = (gaps > 0).any(axis=0)
        n_compared += err.size
        n_rerouted += int(rerouted[-err.size:].sum())
        print(f"probe of {prompt.size} tokens + {PROBE_STEPS} decoded: "
              f"rel_l2 {np.round(err, 4).tolist()}  argmax agrees "
              f"{int(agree.sum())}/{agree.size}  picks not the "
              f"reference's own at {int(rerouted.sum())} of the "
              f"{picks.shape[0]} positions routed as the engine routed, "
              f"largest gap {own.max():.4f} at those compared "
              f"({gaps.max():.4f} at all; least margin "
              f"{margins.min():.4f})  reference "
              f"{time.monotonic() - t:.1f} s", flush=True)
        for i in np.flatnonzero(~(err <= REL_L2)):
            problems.append(f"probe {prompt.size}: position "
                            f"{positions[i]} rel_l2 {err[i]:.4f} over "
                            f"{REL_L2}")
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
    lost, every request's tails started from zeros exactly once; this
    mixer's own counters are in the line before) and the comparison with
    the reference, outside the window and outside set-up: as
    serve_ssm.measure."""
    run = serve.measure(system, traffic, seconds, seed, tracer)
    engine = system.engine
    a = engine.allocator
    print("cache kinds after the window:", {
        kind: f"{a.in_use_of(kind)}/{a.usable_of(kind)} in use"
        for kind in a.kinds}, {
        k: v for k, v in engine.stats().items()
        if k.startswith(("cache_", "attn_", "moe_", "conv_"))},
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
