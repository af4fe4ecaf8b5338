"""How a router spreads its load over the held experts of
ling-3.0-flash-ep4 under a draw of weights, WITHOUT the chip: the plain
reference (benchmark/reference/kda_latent_moe_share.py) at the published
widths over one sequence on the CPU, the routed experts themselves left
out (8 held of 512: their part of the stream is small), and of the last
256 positions' picks the share of this chip's 128 experts that a position
reached and the fullest one's load over the mean. ISSUE 62's cell read
0.55-0.61 reached and 12-17 times the mean on the chip with every tensor
drawn at 0.02, moving with the seed and ``out_tok_s`` with it; this script
read the same (0.57-0.65, 8-12) and is where the configuration's stand-ins
for the embedding and the kda layers' ``wo`` were chosen (PERF.md section
6, PR 62):

    JAX_PLATFORMS=cpu python3 tests/kda_latent_routing.py [tokens] [seed]

About 1.3 GB of weights and ten seconds a reading.
"""
import json
import os
import sys

import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":          # run as a script from a checkout
    sys.path.insert(0, ROOT)

from benchmark.builders import serve_kda_latent as builder
from benchmark.builders.serve_blocks import make_weights
from benchmark.reference import kda_latent_moe_share as ref


def spread(weights, model, tokens, rows=256, held=128):
    """[(fullest held expert's load over the mean, share of the held
    experts reached, the share of a router's input that is the same for
    every position)] a routed layer, over the last ``rows`` positions."""
    w = ref.from_stacked(weights, model)
    x = ref.f32(w["tok_emb"][jnp.asarray(tokens)])
    eps, out = float(model["rms_norm_eps"]), []
    for i, kind in enumerate(model["layer_types"]):
        u = ref.rms_norm(x, w[f"l{i}.attn_norm"], eps)
        h = x + (ref.kda(w, i, u, model)[0] if kind == ref.KDA
                 else ref.latent(w, i, u, model))
        u = ref.rms_norm(h, w[f"l{i}.mlp_norm"], eps)
        if i < model["first_k_dense_replace"]:
            x = h + ref.swiglu(u, w[f"l{i}.w_gate"], w[f"l{i}.w_up"],
                               w[f"l{i}.w_down"])
            continue
        picked = np.asarray(ref.route(w, i, u, model)[0])[-rows:]
        load = np.bincount(picked[picked < held], minlength=held)
        last = np.asarray(u)[-rows:]
        out.append((load.max() * held / max(1, load.sum()),
                    (load > 0).mean(),
                    (last.mean(0) ** 2).sum() / (last ** 2).sum(1).mean()))
        x = h + ref.swiglu(u, w[f"l{i}.sh_w_gate"], w[f"l{i}.sh_w_up"],
                           w[f"l{i}.sh_w_down"])
    return out


def main(n_tokens=768, seed=1):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ling-3.0-flash-ep4.json")) as f:
        model = json.load(f)
    model = dict(model, vocab_size=4096, num_experts=8,
                 experts_held={"first": 0, "count": 8, "of": 512})
    cfg = builder.model_config(model)
    tokens = np.random.RandomState(seed).randint(0, cfg.vocab_size,
                                                 n_tokens)
    drawn = make_weights(cfg, seed)
    stood = dict(drawn, **builder.stand_ins(cfg, drawn))
    plain = dict(stood, **{k: drawn[k] for k in ("tok_emb", "lead.wo",
                                                 "kda.wo")})
    for name, weights in (("embedding and kda wo as drawn", plain),
                          ("with their stand-ins", stood)):
        print(name + ":", [tuple(round(float(v), 2) for v in layer)
                           for layer in spread(weights, model, tokens)],
              flush=True)


if __name__ == "__main__":
    main(*(int(a) for a in sys.argv[1:3]))
