"""The serving cells of a latent-attention / routed-experts model that is
ONE CHIP'S SHARE of an expert-parallel deployment (kind ``serve_share``):
the router as wide as published, a run of its experts held here, the plain
residual path. The engine, the traffic and the window are builders/
serve.py's; the weights' making and the engine's own logits are builders/
serve_blocks.py's; what is added is this model's configuration, its probes
and, after the window, the comparison of the engine's own logits with the
plain reference of the same share at the published widths
(reference/latent_moe_share.py), which decides ``correct``.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as fluid
from paddle_tpu.models.latent_moe import LatentMoEConfig
from paddle_tpu.serving.decode_engine import DecodeConfig, DecodeEngine

from ..reference import latent_moe_share as reference
from ..tracing import span
from . import serve
from .serve_blocks import PROBE_STEPS, engine_logits, make_weights

# ---------------------------------------------------------------------
# The limits of the comparison that decides ``correct``, as serve_blocks.py
# sets its own (PERF.md section 4 has the readings these were set between).
#
# REL_L2: ||engine logits - reference logits|| / ||reference logits|| at
# one position; between the engine's largest reading over seeds, probes
# and positions, and the reference itself computed from float8 (e4m3)
# weights, the nearest precision below the published bf16.
#
# MARGIN: the reference is computed WITH the engine's picks at the
# positions compared (routing is discrete, and here twice so: a group is
# kept or not, then an expert), and a pick that is not the reference's own
# is accepted only where the reference's gap for it (how far its group
# scores under the last group kept, or its selection score under the last
# expert picked) lies under MARGIN.
# ---------------------------------------------------------------------
REL_L2 = 0.04
MARGIN = 0.03


def model_config(model):
    """LatentMoEConfig from the published config.json keys in the file
    and ``experts_held``, the run of the router's experts this chip has."""
    r, held = model["rope_scaling"], model["experts_held"]
    if r["type"] != "yarn" or model["scoring_func"] != "sigmoid" \
            or model["topk_method"] != "noaux_tc" or "hc_mult" in model \
            or held["count"] != model["n_routed_experts"]:
        raise ValueError("not the rope scaling, the router, the residual "
                         "path or the share this builder's model has")
    return LatentMoEConfig(
        name=model["name"], vocab_size=model["vocab_size"],
        dim=model["hidden_size"], n_layers=model["num_hidden_layers"],
        n_dense_layers=model["first_k_dense_replace"],
        n_heads=model["num_attention_heads"],
        q_rank=model["q_lora_rank"], kv_rank=model["kv_lora_rank"],
        nope_dim=model["qk_nope_head_dim"],
        rope_dim=model["qk_rope_head_dim"], v_dim=model["v_head_dim"],
        ffn_hidden=model["intermediate_size"],
        n_experts=held["count"], router_width=held["of"],
        experts_first=held["first"], n_group=model["n_group"],
        topk_group=model["topk_group"],
        moe_top_k=model["num_experts_per_tok"],
        expert_hidden=model["moe_intermediate_size"],
        n_shared=model["n_shared_experts"],
        route_scale=float(model["routed_scaling_factor"]),
        residual="plain", n_streams=1, sinkhorn_iters=0,
        norm_eps=float(model["rms_norm_eps"]),
        rope_base=float(model["rope_theta"]),
        rope_factor=float(r["factor"]),
        rope_original_max=r["original_max_position_embeddings"],
        rope_beta_fast=float(r["beta_fast"]),
        rope_beta_slow=float(r["beta_slow"]),
        rope_mscale_all_dim=float(r["mscale_all_dim"]),
        dtype=model["torch_dtype"])


def selection_bias(cfg):
    """The stand-in for e_score_correction_bias, [routed layers, router
    width] float32: +0.02 for even experts, -0.02 for odd ones. Non-zero
    and of the size of the gaps between selection scores, so that a
    missing term changes the picks; and the same for every seed, every
    group and every chip's run of experts, because a trained bias is
    there to even the experts' load out. Drawn at random it does the
    opposite: normal(0, 0.02) moved a single expert's share of the tokens
    by a third and this chip's 16 by +/- 9% between seeds (6.06% to 7.2%
    of the pairs), and the decode step with it (PERF.md section 6,
    PR 31)."""
    sign = 1.0 - 2.0 * (jnp.arange(cfg.router_width) % 2)
    return jnp.broadcast_to((0.02 * sign).astype(jnp.float32), (
        cfg.n_layers - cfg.n_dense_layers, cfg.router_width))


class ServeShareSystem:
    def __init__(self, config, seed):
        self.config = config
        self.cfg = model_config(config)
        self.scope = fluid.Scope()
        with span("make_weights"):
            self.weights = make_weights(self.cfg, seed)
            self.weights["blocks.moe_bias"] = selection_bias(self.cfg)
            for name, value in self.weights.items():
                self.scope.set(name, value)
        with span("engine_warmup"):
            self.engine = DecodeEngine(
                self.cfg, scope=self.scope,
                config=DecodeConfig(**config["builder"]["engine"]))
            self.warmup = self.engine.warmup()
        held = config["experts_held"]
        print(f"serve_share: engine up, warm-up {self.warmup}, pool "
              f"{self.engine.allocator.usable_pages} pages, experts "
              f"{held['first']}-{held['first'] + held['count'] - 1} of "
              f"{held['of']} held, "
              f"{sum(v.nbytes for v in self.weights.values()) / 1e9:.3f}"
              " GB of weights", flush=True)

    def compiles(self):
        return self.engine.exe.total_compiles()

    def close(self):
        self.engine.close()


def set_up(config, traffic, seed):
    return ServeShareSystem(config, seed)


def reference_logits(system, sequence, positions, picks=None):
    """The plain reference's logits, selection margins and forced-pick
    gaps at ``positions`` of ``sequence``, from the very arrays the engine
    serves; with ``picks`` [positions, layers, K], routed as the engine
    routed there."""
    weights = reference.from_stacked(system.weights,
                                     system.cfg.n_dense_layers)
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
        out = reference.forward(weights, sequence, system.config,
                                positions, forced)
    return tuple(np.asarray(x) for x in out)


def probe_prompts(system, seed):
    """One prompt for each prefill program the traffic reaches: three
    quarters of every bucket, ids from the vocabulary's slice."""
    rng = np.random.RandomState((seed + 1) % (2 ** 32))
    return [rng.randint(0, system.cfg.vocab_size,
                        bucket * 3 // 4).astype(np.int64)
            for bucket in sorted(system.engine.programs.prefill)]


def compare_with_reference(system, seed):
    """The findings of the logit comparison (none: correct), printing its
    figures. See the limits at the top of this file."""
    problems, n_compared, n_rerouted = [], 0, 0
    t = time.monotonic()
    probes = [(prompt,) + engine_logits(system.engine, prompt, PROBE_STEPS)
              for prompt in probe_prompts(system, seed)]
    print(f"engine: {len(probes)} probes in {time.monotonic() - t:.1f} s",
          flush=True)
    # the engine is done: its pool and kept outputs make room for the
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
    window and outside set-up: as serve_blocks.measure."""
    run = serve.measure(system, traffic, seconds, seed, tracer)
    system.engine.close()
    print("allocator peak before the comparison:",
          (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use"),
          flush=True)
    with span("compare_with_reference"):
        run["problems"] += compare_with_reference(system, seed)
    return run
