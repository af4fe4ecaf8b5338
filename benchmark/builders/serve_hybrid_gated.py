"""The serving cells of a model that mixes full and sliding-window
attention layers WITH THEIR OWN QUERY-HEAD COUNTS, gates every head's
result and holds EVERY routed expert of a layer beside a shared one (kind
``serve_hybrid_gated``): Laguna-XS.2's leading layers as the first of
eight pipeline stages. The engine, the traffic and the window are
builders/serve.py's; the weights' making is builders/serve_blocks.py's; the
probes (which carry the rows' ring tables) are builders/serve_hybrid.py's;
what is added is this model's configuration and, after the window, the
comparison of the engine's own logits with the plain reference at the
published widths (reference/hybrid_moe_gated.py), which decides
``correct``. No tensor needs a stand-in: a gate, a router and a shared
expert drawn normal(0, 0.02) all move the logits by far more than the
tolerance (the configuration's ``departures``).
"""
import time

import jax
import numpy as np

import paddle_tpu as fluid
from paddle_tpu.models.hybrid_moe import HybridMoEConfig
from paddle_tpu.serving.decode_engine import DecodeConfig, DecodeEngine

from ..reference import hybrid_moe_gated as reference
from ..tracing import span
from . import serve
from .serve_blocks import PROBE_STEPS, make_weights
from .serve_hybrid import engine_logits, probe_prompts

# ---------------------------------------------------------------------
# The limits of the comparison that decides ``correct`` (PERF.md section 4
# has the readings these were set between).
#
# REL_L2: ||engine logits - reference logits|| / ||reference logits|| at
# one position; between the engine's largest reading over seeds, probes
# and positions (0.0158 over 20 seeds x 27 positions; 0.0112 the least)
# and the reference itself computed from float8 (e4m3) weights, the
# nearest precision below the published bf16 (0.166 to 0.200): three
# times of room on both sides. The reference without its routed scale
# reads 0.22-0.26, without its shared expert 0.40-0.49, without its gate
# 0.80-0.92, without YaRN 0.88-1.02, with a full layer's heads grouped
# eight to a key/value head 1.21-1.39.
#
# MARGIN: the reference is computed WITH the engine's picks at the
# positions compared (routing is discrete), and a pick that is not the
# reference's own is accepted only where its router LOGIT lies less than
# MARGIN under the reference's last own pick's: the engine's largest such
# gap is 0.031 (a third of the positions have one: the margin between the
# 8th and the 9th of 256 logits is 0.001 to 0.003), the float8 reference's
# 0.49 to 0.56.
# ---------------------------------------------------------------------
REL_L2 = 0.05
MARGIN = 0.1


def model_config(model):
    """HybridMoEConfig from the published config.json keys in the file."""
    kinds, mlps = model["layer_types"], model["mlp_layer_types"]
    heads = model["num_attention_heads_per_layer"]
    n_dense = mlps.index("sparse") if "sparse" in mlps else len(mlps)
    by_kind = {k: {h for h, kind in zip(heads, kinds) if kind == k}
               for k in (reference.FULL, reference.WINDOW)}
    rope = model["rope_parameters"]
    full, window = rope[reference.FULL], rope[reference.WINDOW]
    if not len(kinds) == len(mlps) == len(heads) \
            == model["num_hidden_layers"] \
            or "dense" in mlps[n_dense:] \
            or any(len(h) != 1 for h in by_kind.values()) \
            or by_kind[reference.FULL] != {model["num_attention_heads"]} \
            or full["rope_type"] != "yarn" \
            or window["rope_type"] != "default" \
            or model["attention_bias"] or model["tie_word_embeddings"] \
            or model["moe_apply_router_weight_on_input"] \
            or model["gating"] is not True:
        raise ValueError("not the layers, the rotations, the gate or the "
                         "router this builder's model has")
    d = model["head_dim"]
    return HybridMoEConfig(
        name=model["name"], vocab_size=model["vocab_size"],
        dim=model["hidden_size"],
        layer_pattern=tuple(int(k == reference.WINDOW) for k in kinds),
        n_dense_layers=n_dense, n_heads=model["num_attention_heads"],
        n_heads_window=by_kind[reference.WINDOW].pop(),
        head_dim=d, v_head_dim=d,
        n_kv_full=model["num_key_value_heads"],
        n_kv_window=model["num_key_value_heads"],
        rope_base_full=float(full["rope_theta"]),
        rope_base_window=float(window["rope_theta"]),
        rotary_dim=int(full["partial_rotary_factor"] * d),
        rotary_dim_window=int(window["partial_rotary_factor"] * d),
        yarn_full=dict(
            factor=float(full["factor"]),
            original_max=full["original_max_position_embeddings"],
            beta_fast=float(full["beta_fast"]),
            beta_slow=float(full["beta_slow"]),
            attention_factor=float(full["attention_factor"])),
        value_scale=1.0, window=model["sliding_window"],
        sink_full=False, sink_window=False, head_gate=True,
        scoring="softmax", ffn_hidden=model["intermediate_size"],
        n_experts=model["num_experts"],
        moe_top_k=model["num_experts_per_tok"],
        expert_hidden=model["moe_intermediate_size"],
        shared_hidden=model["shared_expert_intermediate_size"],
        route_scale=float(model["moe_routed_scaling_factor"]),
        norm_eps=float(model["rms_norm_eps"]), dtype=model["torch_dtype"])


class ServeHybridGatedSystem:
    def __init__(self, config, seed):
        self.config = config
        self.cfg = model_config(config)
        self.scope = fluid.Scope()
        with span("make_weights"):
            self.weights = make_weights(self.cfg, seed)
            for name, value in self.weights.items():
                self.scope.set(name, value)
        with span("engine_warmup"):
            self.engine = DecodeEngine(
                self.cfg, scope=self.scope,
                config=DecodeConfig(**config["builder"]["engine"]))
            self.warmup = self.engine.warmup()
        a = self.engine.allocator
        print(f"serve_hybrid_gated: engine up, warm-up {self.warmup}, pools "
              f"{a.usable_pages} sequence pages + "
              f"{a.usable_of(self.engine.RING)} window pages (rings of "
              f"{self.engine.ring['pages_per_seq']}), all "
              f"{self.cfg.n_experts} experts of a layer held, "
              f"{sum(v.nbytes for v in self.weights.values()) / 1e9:.3f}"
              " GB of weights", flush=True)

    def compiles(self):
        return self.engine.exe.total_compiles()

    def close(self):
        self.engine.close()


def set_up(config, traffic, seed):
    return ServeHybridGatedSystem(config, seed)


def reference_logits(system, sequence, positions, picks=None, model=None,
                     through=None):
    """serve_hybrid.reference_logits against this model's reference."""
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
              f"{int(agree.sum())}/{agree.size}  picks not the "
              f"reference's own at {int(rerouted.sum())} positions, "
              f"largest gap {gaps.max():.4f} (least margin there "
              f"{margins.min():.4f})  reference "
              f"{time.monotonic() - t:.1f} s", flush=True)
        for i in np.flatnonzero(~(err <= REL_L2)):
            problems.append(f"probe {prompt.size}: position "
                            f"{positions[i]} rel_l2 {err[i]:.4f} over "
                            f"{REL_L2}")
        for layer, i in zip(*np.nonzero(gaps >= MARGIN)):
            problems.append(
                f"probe {prompt.size}: position {positions[i]}, sparse "
                f"layer {layer}: the engine's picks "
                f"{picks[i, layer].tolist()} lie {gaps[layer, i]:.4f} "
                f"from the reference's, over {MARGIN}")
    print(f"logit comparison: {n_compared} positions, {n_rerouted} of them "
          f"routed not as the reference alone would (every gap under "
          f"{MARGIN})", flush=True)
    return problems


def measure(system, traffic, seconds, seed, tracer):
    """serve.measure, then the comparison with the reference, outside the
    window and outside set-up: as serve_hybrid.measure."""
    run = serve.measure(system, traffic, seconds, seed, tracer)
    a = system.engine.allocator
    print("cache kinds after the window:", {
        kind: f"{a.in_use_of(kind)}/{a.usable_of(kind)} pages in use"
        for kind in a.kinds}, {
        k: v for k, v in system.engine.stats().items()
        if k.startswith(("window_pages", "cache_", "attn_", "moe_"))},
        "allocator peak before the comparison:",
        (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use"),
        flush=True)
    system.engine.close()
    with span("compare_with_reference"):
        run["problems"] += compare_with_reference(system, seed)
    return run
