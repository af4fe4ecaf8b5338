"""The serving cells of a model whose block kinds are data of its
configuration (kind ``serve_blocks``): latent attention, routed experts
with a shared one, hyper-connections. The engine, the traffic and the
window are builders/serve.py's; what is added is the model (through the
program's own constructor, its weights made on the device from the seed in
one jitted call) and, after the window, the comparison of the engine's own
logits with the plain reference at the published widths, which decides
``correct``.
"""
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as fluid
from paddle_tpu.models.latent_moe import LatentMoEConfig
from paddle_tpu.serving.decode_engine import DecodeConfig, DecodeEngine

from ..reference import latent_moe_mhc as reference
from ..tracing import span
from . import serve

# ---------------------------------------------------------------------
# The limits of the comparison that decides ``correct`` (PERF.md section 4
# has the readings they were set between).
#
# REL_L2: ||engine logits - reference logits|| / ||reference logits|| at
# one position. The engine computes in bf16 with float32 accumulation and
# keeps its residual streams and its cache in bf16; the reference computes
# in float32 from the same bf16-valued weights. The limit lies between the
# largest reading of the engine over seeds and positions and the reading
# of the reference itself computed from float8 (e4m3) weights, the nearest
# precision below the published one.
#
# MARGIN: routing is discrete. Where the reference's selection scores
# leave little room between the last expert picked and the first one not
# picked, bf16 rounding upstream of the router picks the other one
# (measured: about one position in five, at margins up to 0.004), and the
# position's logits then differ by a swapped expert (a REL_L2 of 0.1-0.6),
# not by a fault. So the programs return the experts they picked beside
# their logits, and the reference is computed WITH those picks at the
# positions compared: the logits then have to meet REL_L2 everywhere, and
# a pick that is not the reference's own is accepted only where its
# selection score lies less than MARGIN under the reference's last own
# pick. Such positions are counted and printed.
# ---------------------------------------------------------------------
REL_L2 = 0.04
MARGIN = 0.03

PROBE_STEPS = 8         # decoded positions compared after each prompt


def model_config(model):
    """LatentMoEConfig from the published config.json keys in the file."""
    r = model["rope_scaling"]
    if r["type"] != "yarn" or model["scoring_func"] != "sigmoid" \
            or model["n_group"] != 1 or model["topk_group"] != 1:
        raise ValueError("not the rope scaling or the router this "
                         "builder's model has")
    return LatentMoEConfig(
        name=model["name"], vocab_size=model["vocab_size"],
        dim=model["hidden_size"], n_layers=model["num_hidden_layers"],
        n_dense_layers=model["first_k_dense_replace"],
        n_heads=model["num_attention_heads"],
        q_rank=model["q_lora_rank"], kv_rank=model["kv_lora_rank"],
        nope_dim=model["qk_nope_head_dim"],
        rope_dim=model["qk_rope_head_dim"], v_dim=model["v_head_dim"],
        ffn_hidden=model["intermediate_size"],
        n_experts=model["n_routed_experts"],
        moe_top_k=model["num_experts_per_tok"],
        expert_hidden=model["moe_intermediate_size"],
        n_shared=model["n_shared_experts"],
        route_scale=float(model["routed_scaling_factor"]),
        n_streams=model["hc_mult"],
        sinkhorn_iters=model["hc_sinkhorn_iters"],
        hc_eps=float(model["hc_eps"]),
        hc_clamp=(float(model["mhc_h_res_clamp_min"]),
                  float(model["mhc_h_res_clamp_max"])),
        norm_eps=float(model["rms_norm_eps"]),
        rope_base=float(model["rope_theta"]),
        rope_factor=float(r["factor"]),
        rope_original_max=r["original_max_position_embeddings"],
        rope_beta_fast=float(r["beta_fast"]),
        rope_beta_slow=float(r["beta_slow"]),
        rope_mscale_all_dim=float(r["mscale_all_dim"]),
        dtype=model["torch_dtype"])


def _draw(key, name, shape, dtype):
    """One tensor of the configuration's ``departures``."""
    if name.endswith("norm"):
        return jnp.ones(shape, dtype)
    if name.endswith("alpha"):          # a_pre, a_post, a_res
        return jnp.broadcast_to(jnp.asarray([0.5, 0.5, 1.0], dtype), shape)
    std = 0.3 if name.endswith("_bias") and ".hc_" in name else 0.02
    return (std * jax.random.normal(key, shape)).astype(dtype)


def make_weights(cfg, seed):
    """Every parameter ``cfg.param_shapes()`` names, made on the default
    device in one jitted call from the seed, in the type it is served in.
    A tensor of more than 2**28 values is drawn a slice of its leading
    axis at a time, so that the float32 draw of the largest (the experts)
    never stands whole beside the weights."""
    shapes = cfg.param_shapes()

    def make(key):
        out = {}
        keys = jax.random.split(key, len(shapes))
        for k, (name, (shape, dtype)) in zip(keys, sorted(shapes.items())):
            n = math.prod(shape)
            parts = next((c for c in range(1, shape[0] + 1)
                          if shape[0] % c == 0 and n // c <= 2 ** 28), None)
            if parts in (None, 1):
                out[name] = _draw(k, name, shape, dtype)
            else:
                part = [shape[0] // parts] + list(shape[1:])
                out[name] = jax.lax.map(
                    lambda kk: _draw(kk, name, part, dtype),
                    jax.random.split(k, parts)).reshape(shape)
        return out

    return jax.jit(make)(serve.seed_key(seed))


class ServeBlocksSystem:
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
        print(f"serve_blocks: engine up, warm-up {self.warmup}, pool "
              f"{self.engine.allocator.usable_pages} pages, "
              f"{sum(v.nbytes for v in self.weights.values()) / 1e9:.3f}"
              " GB of weights", flush=True)

    def compiles(self):
        return self.engine.exe.total_compiles()

    def close(self):
        self.engine.close()


def set_up(config, traffic, seed):
    return ServeBlocksSystem(config, seed)


def engine_logits(engine, prompt, steps):
    """What the engine's own programs gave at the prompt's last position
    and at ``steps`` decoded ones: (logits [1 + steps, V] float32, the
    routed layers' picks there [1 + steps, layers, K], the tokens
    decoded). The prompt goes through the path a request of its length
    takes (the whole-prompt program of its bucket, or the chunk program a
    slice at a time), then the decode program from slot 0, the other
    slots inactive. The engine must be closed: the probe takes the first
    pages of the pool for itself."""
    c = engine.config
    table = np.zeros((1, engine.pages_per_seq), np.int32)
    need = engine.allocator.pages_for(prompt.size + steps + c.decode_block)
    table[0, :need] = 1 + np.arange(need)
    cs = engine.programs.chunk_size
    if cs is not None and prompt.size > cs:
        for off in range(0, prompt.size, cs):
            sl = prompt[off:off + cs]
            tokens = np.zeros((1, cs), np.int64)
            tokens[0, :sl.size] = sl
            nxt = engine._run_chunk_program(
                tokens, np.asarray([sl.size], np.int32),
                np.asarray([off], np.int32), table)
        kept = engine.kept["chunk"]
    else:
        bucket = engine._bucket_for(prompt.size)
        pb = c.prefill_batch
        tokens = np.zeros((pb, bucket), np.int64)
        tokens[0, :prompt.size] = prompt
        lens = np.ones((pb,), np.int32)
        lens[0] = prompt.size
        nxt = engine._run_prefill_program(
            bucket, tokens, lens, np.concatenate(
                [table, np.zeros((pb - 1, table.shape[1]), np.int32)]))
        kept = engine.kept[f"prefill_{bucket}"]
    logits = [np.asarray(kept["logits"])[:1]]
    picks = [np.asarray(kept["picks"])[:1]]
    decoded = [int(nxt[0])]
    toks = np.zeros((c.max_batch,), np.int64)
    pos = np.ones((c.max_batch,), np.int32)
    tables = np.zeros((c.max_batch, engine.pages_per_seq), np.int32)
    tables[0] = table[0]
    while len(decoded) <= steps:
        toks[0], pos[0] = decoded[-1], prompt.size + len(decoded) - 1
        out = engine._run_decode_program(toks, pos, tables)
        logits.append(np.asarray(engine.kept["decode"]["logits"])[0])
        picks.append(np.asarray(engine.kept["decode"]["picks"])[0])
        decoded.extend(int(t) for t in out[0])
    return (np.concatenate(logits)[:1 + steps],
            np.concatenate(picks)[:1 + steps],
            np.asarray(decoded[:1 + steps], np.int64))


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
    """One prompt for each prefill path the traffic reaches: one that fits
    a whole-prompt bucket (where the engine keeps such a program) and one
    of more than two chunks."""
    engine = system.engine
    rng = np.random.RandomState((seed + 1) % (2 ** 32))
    cs = engine.programs.chunk_size
    sizes = []
    if engine.programs.prefill:
        top = max(engine.programs.prefill)
        sizes.append(min(top, cs or top) * 3 // 4)
    if cs is not None:
        sizes.append(2 * cs + max(1, cs // 16))
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
    # the engine is done: its pool and kept outputs make room, so that the
    # reference's temporaries stay under the peak the window itself set
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
                f"probe {prompt.size}: position {positions[i]}, routed "
                f"layer {layer}: the engine's picks "
                f"{picks[i, layer].tolist()} lie {gaps[layer, i]:.4f} "
                f"under the reference's, over {MARGIN}")
    print(f"logit comparison: {n_compared} positions, {n_rerouted} of them "
          f"routed not as the reference alone would (every gap under "
          f"{MARGIN})", flush=True)
    return problems


def measure(system, traffic, seconds, seed, tracer):
    """serve.measure, then the comparison with the reference, outside the
    window and outside set-up. The closed loop leaves requests in flight;
    the engine is closed first, which drops them (they are not in the
    sample), and the probe then drives its programs alone."""
    run = serve.measure(system, traffic, seconds, seed, tracer)
    system.engine.close()
    print("allocator peak before the comparison:",
          (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use"),
          flush=True)
    with span("compare_with_reference"):
        run["problems"] += compare_with_reference(system, seed)
    return run
