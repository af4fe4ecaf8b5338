"""The serving cells of a model that mixes full and sliding-window
attention layers and is ONE CHIP'S SHARE of an expert-parallel deployment
(kind ``serve_hybrid``): two kinds of cache under one engine, the router as
wide as published, a run of its experts held here. The engine, the traffic
and the window are builders/serve.py's; the weights' making is builders/
serve_blocks.py's; what is added is this model's configuration, its
stand-in sinks and selection bias, its probes (which carry the rows' ring
tables) and, after the window, the comparison of the engine's own logits
with the plain reference of the same share at the published widths
(reference/hybrid_moe_share.py), which decides ``correct``.
"""
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as fluid
from paddle_tpu.models.hybrid_moe import HybridMoEConfig
from paddle_tpu.serving.decode_engine import DecodeConfig, DecodeEngine

from ..reference import hybrid_moe_share as reference
from ..tracing import span
from . import serve
from .serve_blocks import PROBE_STEPS, make_weights

# ---------------------------------------------------------------------
# The limits of the comparison that decides ``correct``, as serve_share.py
# sets its own (PERF.md section 4 has the readings these were set between).
#
# REL_L2: ||engine logits - reference logits|| / ||reference logits|| at
# one position; between the engine's largest reading over seeds, probes
# and positions, and the reference itself computed from float8 (e4m3)
# weights, the nearest precision below the published bf16. The reference
# without its sinks, without its value scale or without its window reads
# further off still.
#
# MARGIN: the reference is computed WITH the engine's picks at the
# positions compared (routing is discrete), and a pick that is not the
# reference's own is accepted only where its selection score lies less
# than MARGIN under the reference's last own pick.
# ---------------------------------------------------------------------
REL_L2 = 0.04
MARGIN = 0.03


def model_config(model):
    """HybridMoEConfig from the published config.json keys in the file
    and ``experts_held``, the run of the router's experts this chip has."""
    held = model["experts_held"]
    freq = model["moe_layer_freq"]
    n_dense = freq.index(1) if 1 in freq else len(freq)
    if model["scoring_func"] != "sigmoid" \
            or model["topk_method"] != "noaux_tc" \
            or model["n_group"] != 1 or model["topk_group"] != 1 \
            or model["n_shared_experts"] or not model["norm_topk_prob"] \
            or held["count"] != model["n_routed_experts"] \
            or 0 in freq[n_dense:] \
            or (model["swa_num_attention_heads"], model["swa_head_dim"],
                model["swa_v_head_dim"]) != (
                model["num_attention_heads"], model["head_dim"],
                model["v_head_dim"]) \
            or model["sliding_window"] != model["sliding_window_size"] \
            or not len(model["hybrid_layer_pattern"]) \
            == model["num_hidden_layers"] == len(freq):
        raise ValueError("not the router, the dense layers, the window "
                         "layers' head shape or the share this builder's "
                         "model has")
    return HybridMoEConfig(
        name=model["name"], vocab_size=model["vocab_size"],
        dim=model["hidden_size"],
        layer_pattern=tuple(model["hybrid_layer_pattern"]),
        n_dense_layers=n_dense, n_heads=model["num_attention_heads"],
        head_dim=model["head_dim"], v_head_dim=model["v_head_dim"],
        n_kv_full=model["num_key_value_heads"],
        n_kv_window=model["swa_num_key_value_heads"],
        rope_base_full=float(model["rope_theta"]),
        rope_base_window=float(model["swa_rope_theta"]),
        rotary_dim=reference.rotary_dim(model),
        value_scale=float(model["attention_value_scale"]),
        window=model["sliding_window"],
        sink_full=model["add_full_attention_sink_bias"],
        sink_window=model["add_swa_attention_sink_bias"],
        ffn_hidden=model["intermediate_size"],
        n_experts=held["count"], router_width=held["of"],
        experts_first=held["first"],
        moe_top_k=model["num_experts_per_tok"],
        expert_hidden=model["moe_intermediate_size"],
        route_scale=float(model["routed_scaling_factor"] or 1.0),
        norm_eps=float(model["layernorm_epsilon"]),
        dtype=model["torch_dtype"])


def stand_ins(cfg, shapes):
    """The tensors that a draw of normal(0, 0.02) would make invisible, the
    same for every seed (the configuration's ``departures``). The selection
    bias, as deepseek-v3-ep16 has it: +0.02 for even experts, -0.02 for odd
    ones. The window layers' sinks: head h carries the share m_h = 0.05 +
    0.7 (h mod 8) / 7 of a softmax over ``window`` near-zero scores, so
    sink_h = ln(window) + ln(m_h / (1 - m_h)): between a twentieth and
    three quarters of the mass, and a missing column fails the comparison."""
    out = {}
    for name, (shape, _) in shapes.items():
        if name.endswith(".moe_bias"):
            sign = 1.0 - 2.0 * (jnp.arange(shape[-1]) % 2)
            out[name] = jnp.broadcast_to(
                (0.02 * sign).astype(jnp.float32), shape)
        elif name.endswith(".sink"):
            share = 0.05 + 0.7 * (jnp.arange(shape[-1]) % 8) / 7.0
            out[name] = jnp.broadcast_to(
                (math.log(cfg.window) + jnp.log(share / (1.0 - share)))
                .astype(jnp.float32), shape)
    return out


class ServeHybridSystem:
    def __init__(self, config, seed):
        self.config = config
        self.cfg = model_config(config)
        self.scope = fluid.Scope()
        with span("make_weights"):
            self.weights = make_weights(self.cfg, seed)
            self.weights.update(stand_ins(self.cfg,
                                          self.cfg.param_shapes()))
            for name, value in self.weights.items():
                self.scope.set(name, value)
        with span("engine_warmup"):
            self.engine = DecodeEngine(
                self.cfg, scope=self.scope,
                config=DecodeConfig(**config["builder"]["engine"]))
            self.warmup = self.engine.warmup()
        held, a = config["experts_held"], self.engine.allocator
        print(f"serve_hybrid: engine up, warm-up {self.warmup}, pools "
              f"{a.usable_pages} sequence pages + "
              f"{a.usable_of(self.engine.RING)} window pages (rings of "
              f"{self.engine.ring['pages_per_seq']}), experts "
              f"{held['first']}-{held['first'] + held['count'] - 1} of "
              f"{held['of']} held, "
              f"{sum(v.nbytes for v in self.weights.values()) / 1e9:.3f}"
              " GB of weights", flush=True)

    def compiles(self):
        return self.engine.exe.total_compiles()

    def close(self):
        self.engine.close()


def set_up(config, traffic, seed):
    return ServeHybridSystem(config, seed)


def engine_logits(engine, prompt, steps):
    """serve_blocks.engine_logits for an engine whose programs take the
    rows' ring tables too: what the engine's own programs gave at the
    prompt's last position and at ``steps`` decoded ones, (logits
    [1 + steps, V] float32, the routed layers' picks there, the tokens
    decoded). The prompt goes through the path a request of its length
    takes, then the decode program from slot 0, the other slots inactive.
    The engine must be closed: the probe takes the first pages of both
    kinds for itself."""
    c = engine.config
    table = np.zeros((1, engine.pages_per_seq), np.int32)
    need = engine.allocator.pages_for(prompt.size + steps + c.decode_block)
    table[0, :need] = 1 + np.arange(need)
    ring = engine._ring_rows(
        [1 + np.arange(engine.ring["pages_per_seq"])])
    cs = engine.programs.chunk_size
    if cs is not None and prompt.size > cs:
        for off in range(0, prompt.size, cs):
            sl = prompt[off:off + cs]
            tokens = np.zeros((1, cs), np.int64)
            tokens[0, :sl.size] = sl
            nxt = engine._run_chunk_program(
                tokens, np.asarray([sl.size], np.int32),
                np.asarray([off], np.int32), table, ring)
        kept = engine.kept["chunk"]
    else:
        bucket = engine._bucket_for(prompt.size)
        tokens = np.zeros((1, bucket), np.int64)
        tokens[0, :prompt.size] = prompt
        nxt = engine._run_prefill_program(
            bucket, tokens, np.asarray([prompt.size], np.int32), table,
            ring)
        kept = engine.kept[f"prefill_{bucket}"]
    logits = [np.asarray(kept["logits"])[:1]]
    picks = [np.asarray(kept["picks"])[:1]]
    decoded = [int(nxt[0])]
    toks = np.zeros((c.max_batch,), np.int64)
    pos = np.ones((c.max_batch,), np.int32)
    tables = np.zeros((c.max_batch, engine.pages_per_seq), np.int32)
    tables[0] = table[0]
    rings = engine._ring_rows([ring[0]] + [None] * (c.max_batch - 1))
    while len(decoded) <= steps:
        toks[0], pos[0] = decoded[-1], prompt.size + len(decoded) - 1
        out = engine._run_decode_program(toks, pos, tables, rings)
        logits.append(np.asarray(engine.kept["decode"]["logits"])[0])
        picks.append(np.asarray(engine.kept["decode"]["picks"])[0])
        decoded.extend(int(t) for t in out[0])
    return (np.concatenate(logits)[:1 + steps],
            np.concatenate(picks)[:1 + steps],
            np.asarray(decoded[:1 + steps], np.int64))


def reference_logits(system, sequence, positions, picks=None, model=None,
                     through=None):
    """The plain reference's logits, selection margins and forced-pick
    gaps at ``positions`` of ``sequence``, from the very arrays the engine
    serves; with ``picks`` [positions, layers, K], routed as the engine
    routed there. ``model``: the configuration with a term switched off;
    ``through``: the weights rounded to that type on their way."""
    weights = reference.from_stacked(system.weights, system.config,
                                     through)
    forced = None
    if picks is not None:
        at = np.zeros((sequence.size,), bool)
        at[positions] = True
        forced = {}
        for layer in range(picks.shape[1]):
            full = np.zeros((sequence.size, picks.shape[2]), np.int32)
            full[positions] = picks[:, layer]
            forced[layer] = (at, full)
    with jax.default_matmul_precision("highest"):
        out = reference.forward(weights, sequence, model or system.config,
                                positions, forced)
    return tuple(np.asarray(x) for x in out)


def probe_prompts(system, seed):
    """One prompt for each prefill path the traffic reaches: three
    quarters of every whole-prompt bucket, and one of two chunks and a
    half and a few tokens (5,157 at a chunk of 2,048), so that a window
    layer's ring turns many times, its last chunk is a short one and a
    full layer attends beyond its chunk."""
    engine = system.engine
    rng = np.random.RandomState((seed + 1) % (2 ** 32))
    cs = engine.programs.chunk_size
    sizes = [bucket * 3 // 4 for bucket in sorted(engine.programs.prefill)]
    if cs is not None:
        sizes.append(2 * cs + cs // 2 + max(1, cs // 56))
    return [rng.randint(0, system.cfg.vocab_size, n).astype(np.int64)
            for n in sizes]


def compare_with_reference(system, seed):
    """The findings of the logit comparison (none: correct), printing its
    figures. See the limits at the top of this file."""
    problems, n_compared, n_rerouted = [], 0, 0
    t = time.monotonic()
    probes = [(prompt,) + engine_logits(system.engine, prompt, PROBE_STEPS)
              for prompt in probe_prompts(system, seed)]
    print(f"engine: {len(probes)} probes in {time.monotonic() - t:.1f} s",
          flush=True)
    # the engine is done: its pools and kept outputs make room for the
    # reference's float32 casts
    del system.engine._pools[:]
    system.engine.kept.clear()
    first = system.cfg.experts_first
    last = first + system.cfg.n_experts - 1
    for prompt, got, picks, decoded in probes:
        t = time.monotonic()
        sequence = np.concatenate([prompt, decoded[:-1]])
        positions = prompt.size - 1 + np.arange(1 + PROBE_STEPS)
        want, margins, gaps = reference_logits(system, sequence, positions,
                                               picks)
        err = np.linalg.norm(got - want, axis=-1) \
            / np.linalg.norm(want, axis=-1)
        agree = np.argmax(got, -1) == np.argmax(want, -1)
        rerouted = (gaps > 0).any(axis=0)
        n_compared += err.size
        n_rerouted += int(rerouted.sum())
        print(f"probe of {prompt.size} tokens + {PROBE_STEPS} decoded: "
              f"rel_l2 {np.round(err, 4).tolist()}  argmax agrees "
              f"{int(agree.sum())}/{agree.size}  picks on held experts "
              f"{int(((picks >= first) & (picks <= last)).sum())} of "
              f"{picks.size}  picks not the reference's own at "
              f"{int(rerouted.sum())} positions, largest gap "
              f"{gaps.max():.4f} (least margin there {margins.min():.4f})"
              f"  reference {time.monotonic() - t:.1f} s", flush=True)
        for i in np.flatnonzero(~(err <= REL_L2)):
            problems.append(f"probe {prompt.size}: position "
                            f"{positions[i]} rel_l2 {err[i]:.4f} over "
                            f"{REL_L2}")
        for layer, i in zip(*np.nonzero(gaps >= MARGIN)):
            problems.append(
                f"probe {prompt.size}: position {positions[i]}, routed "
                f"layer {layer}: the engine's picks "
                f"{picks[i, layer].tolist()} lie {gaps[layer, i]:.4f} "
                f"from the reference's, over {MARGIN}")
    print(f"logit comparison: {n_compared} positions, {n_rerouted} of them "
          f"routed not as the reference alone would (every gap under "
          f"{MARGIN})", flush=True)
    return problems


def measure(system, traffic, seconds, seed, tracer):
    """serve.measure, then the comparison with the reference, outside the
    window and outside set-up: as serve_share.measure."""
    run = serve.measure(system, traffic, seconds, seed, tracer)
    a = system.engine.allocator
    print("cache kinds after the window:", {
        kind: f"{a.in_use_of(kind)}/{a.usable_of(kind)} pages in use"
        for kind in a.kinds}, {
        k: v for k, v in system.engine.stats().items()
        if k.startswith(("window_pages", "cache_", "attn_"))},
        "allocator peak before the comparison:",
        (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use"),
        flush=True)
    system.engine.close()
    with span("compare_with_reference"):
        run["problems"] += compare_with_reference(system, seed)
    return run
