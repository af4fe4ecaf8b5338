"""MiMo-V2-Flash's decoder in plain float32, as ONE CHIP'S SHARE of an
expert-parallel deployment: grouped-query attention whose layers are full
or sliding-window (other key/value head counts, other rotary bases, a
learned sink in the window layers' softmax), keys wider than values, a
part of each head rotated, the values scaled; a sigmoid router over ALL the
routed experts of which this chip holds a run and computes that run's part
of the sum. One sequence, no batch, no cache, no ring, no blocks of keys:
every layer builds its whole [T, T] scores and masks them, every held
expert is applied to every token by a Python loop and masked. Independent
of paddle_tpu. Every product is taken at "highest" precision.

The layer, for a token x [D] at position t (ISSUE 33 writes it out from the
catalog row's config.json keys; the configuration file's ``assumed`` lists
what those leave open):

    h = x + A(RMSNorm(x));   y = h + M(RMSNorm(h))
    layer i is FULL where hybrid_layer_pattern[i] is 0, WINDOW where 1
    attention A:  q = u Wq as [heads, 192];  k = u Wk as [G, 192]
                  v = value_scale * (u Wv) as [G, 128];  G, base by kind
                  the first int(partial_rotary_factor * 192) widths of
                  every q and k head rotated (half-rotation), the rest pass
                  s_tj = q_t . k_j * 192^-0.5 for j <= t and, in a window
                  layer, t - j < sliding_window
                  out_t = sum_j e^(s_tj - m) v_j
                          / (sum_j e^(s_tj - m) + e^(sink_h - m))
                  (the sink column in window layers only; m the maximum
                  over scores and sink);  A = concat(out) Wo
    router:       sc = sigmoid(u Wg) over all E; the K largest of sc + b
                  are picked;  w = sc[picked] / (sum + 1e-20) * scale
    experts M:    sum over picked e HELD HERE of w_e SwiGLU_e(u);
                  a SwiGLU of intermediate_size where moe_layer_freq[i] is 0

What the picked experts that are not held would add is left out, as in
latent_moe_share.py. ``model["experts_held"]`` = {"first", "count", "of"}.

Weights come as ``l{i}.<suffix>`` (from_stacked() reads the program's
layout so: ``lead.*`` the leading dense layers, ``full.*`` / ``window.*``
the routed layers of each kind, in the pattern's order).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .latent_moe_mhc import (HIGHEST, _add_expert, _route, f32, mm,
                             rms_norm, swiglu)
from .latent_moe_share import _Experts

HEADS_AT_A_TIME = 4      # attention's [heads, T, T] scores, in groups


def layer_names(model):
    """[(stack, index in it)] of every layer: ``lead`` where
    moe_layer_freq is 0, else ``full`` | ``window`` by the pattern."""
    seen, out = {}, []
    for kind, routed in zip(model["hybrid_layer_pattern"],
                            model["moe_layer_freq"]):
        stack = ("full", "window")[kind] if routed else "lead"
        out.append((stack, seen.get(stack, 0)))
        seen[stack] = out[-1][1] + 1
    return out


class from_stacked:
    """The program's stacked layout read as ``l{i}.*``, a layer's tensor
    sliced out when it is asked for and not before. With ``through`` (a
    dtype) every matrix but the router is rounded to that type on its way:
    the reference computed from weights of a lower precision, for the
    reading that sets the comparison's limits."""

    def __init__(self, weights, model, through=None):
        self.weights, self.through = weights, through
        self.names = layer_names(model)

    def _cast(self, name):
        if self.through is None or name.endswith(
                ("norm", "_bias", "moe_router", "sink")):
            return lambda x: x
        return lambda x: x.astype(self.through).astype(x.dtype)

    def __getitem__(self, key):
        if key in self.weights:
            return self._cast(key)(self.weights[key])
        i, suffix = key[1:].split(".", 1)
        stack, j = self.names[int(i)]
        name = f"{stack}.{suffix}"
        w = self.weights[name]
        return _Experts(w, j, self._cast(name)) if w.ndim >= 4 \
            else self._cast(name)(w[j])

    def __contains__(self, key):
        if key in self.weights:
            return True
        i, suffix = key[1:].split(".", 1)
        return f"{self.names[int(i)][0]}.{suffix}" in self.weights


def rope(x, base, rd):
    """x [T, heads, d]: the first ``rd`` widths of every head rotated as
    (first half, second half) pairs at positions 0..T-1, inverse
    frequencies base^(-2j / rd); the other widths pass."""
    inv = base ** (-jnp.arange(0, rd, 2, dtype=jnp.float32) / rd)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :rd // 2], x[..., rd // 2:rd]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                            x[..., rd:]], -1)


@functools.partial(jax.jit, static_argnames=(
    "H", "G", "kd", "vd", "rd", "base", "window", "value_scale"))
def _attention(u, wq, wk, wv, wo, sink, *, H, G, kd, vd, rd, base, window,
               value_scale):
    T = u.shape[0]
    q = rope(mm(u, wq).reshape(T, H, kd), base, rd)
    k = rope(mm(u, wk).reshape(T, G, kd), base, rd)
    v = value_scale * mm(u, wv).reshape(T, G, vd)
    t, j = jnp.arange(T)[:, None], jnp.arange(T)[None]
    seen = j <= t
    if window is not None:
        seen = seen & (t - j < window)
    out, r = [], H // G         # r query heads share a key/value head
    for h in [h for g in range(G)
              for h in range(g * r, (g + 1) * r, HEADS_AT_A_TIME)]:
        g = h // r
        hs = slice(h, min(h + HEADS_AT_A_TIME, (g + 1) * r))
        s = jnp.einsum("qhd,kd->hqk", q[:, hs], k[:, g],
                       precision=HIGHEST) * kd ** -0.5
        s = jnp.where(seen, s, -jnp.inf)
        m = jnp.max(s, -1, keepdims=True)
        if sink is not None:
            sk = f32(sink)[hs, None, None]
            m = jnp.maximum(m, sk)
        e = jnp.exp(s - m)
        den = jnp.sum(e, -1, keepdims=True)
        if sink is not None:
            den = den + jnp.exp(sk - m)
        out.append(jnp.einsum("hqk,kd->qhd", e / den, v[:, g],
                              precision=HIGHEST))
    return mm(jnp.concatenate(out, 1).reshape(T, H * vd), wo)


def attention(w, i, u, m):
    """Layer ``i``'s attention on one sequence u [T, D]. Keys of ``m`` that
    start with ``_`` switch single terms off or over, for the tests that
    show the comparison has teeth."""
    windowed = bool(m["hybrid_layer_pattern"][i])
    kd = m["head_dim"]
    H = m["num_attention_heads"]
    bases = (m["rope_theta"], m["swa_rope_theta"])
    if m.get("_swap_bases"):
        bases = bases[::-1]
    use_sink = m["add_swa_attention_sink_bias"] if windowed \
        else m["add_full_attention_sink_bias"]
    G = m["swa_num_key_value_heads"] if windowed \
        else m["num_key_value_heads"]
    return _attention(
        u, w[f"l{i}.wq"], w[f"l{i}.wk"], w[f"l{i}.wv"], w[f"l{i}.wo"],
        w[f"l{i}.sink"] if use_sink and m.get("_use_sink", True) else None,
        H=H, G=G, kd=kd, vd=m["v_head_dim"],
        rd=m.get("_rotary_dim", rotary_dim(m)), base=float(bases[windowed]),
        window=m.get("_window", m["sliding_window"]) if windowed else None,
        value_scale=float(m.get("_value_scale",
                                m["attention_value_scale"])))


def rotary_dim(m):
    return int(m["partial_rotary_factor"] * m["head_dim"])


def route(w, i, u, m, forced=None):
    """(picked [T, K] over all the experts, their weights [T, K], margin
    [T], gap [T]): latent_moe_mhc.py's router (sigmoid scores, the K
    largest of scores + bias, the picked experts' own scores renormalised),
    times routed_scaling_factor (null: 1). ``forced`` as there."""
    T, K = u.shape[0], m["num_experts_per_tok"]
    at, picks = forced if forced is not None else (
        np.zeros((T,), bool), np.zeros((T, K), np.int32))
    if m["n_group"] != 1 or m["topk_group"] != 1 \
            or m["scoring_func"] != "sigmoid":
        raise ValueError("not this reference's router")
    return _route(u, w[f"l{i}.moe_router"], w[f"l{i}.moe_bias"],
                  jnp.asarray(at), jnp.asarray(picks, jnp.int32), K=K,
                  scale=float(m["routed_scaling_factor"] or 1.0),
                  use_bias=m.get("_use_bias", True), router_dtype=None)


def experts(w, i, u, m, forced=None):
    """The held experts on every token, masked by the routing over all the
    experts. Returns (out [T, D], margin [T], gap [T], picked [T, K])."""
    picked, g, margin, gap = route(w, i, u, m, forced)
    held = m["experts_held"]
    out = jnp.zeros_like(u)
    for e in range(held["count"]):
        out = _add_expert(out, picked, g, held["first"] + e, swiglu(
            u, w[f"l{i}.moe_w_gate"][e], w[f"l{i}.moe_w_up"][e],
            w[f"l{i}.moe_w_down"][e]))
    return out, margin, gap, picked


def layer(w, i, x, m, forced=None):
    """Layer ``i`` on one sequence x [T, D]: (y [T, D], margin, gap,
    picked), the last three None for a dense layer."""
    eps = m["layernorm_epsilon"]
    h = x + attention(w, i, rms_norm(x, w[f"l{i}.attn_norm"], eps), m)
    u = rms_norm(h, w[f"l{i}.mlp_norm"], eps)
    if not m["moe_layer_freq"][i]:
        return h + swiglu(u, w[f"l{i}.w_gate"], w[f"l{i}.w_up"],
                          w[f"l{i}.w_down"]), None, None, None
    out, margin, gap, picked = experts(w, i, u, m, forced)
    return h + out, margin, gap, picked


def forward(weights, tokens, model, positions=None, forced=None):
    """Float32 logits of one sequence ``tokens`` [T] at ``positions`` (all
    of them when None), [len(positions), V] over the vocabulary's slice
    held here; each routed layer's selection margin at those positions,
    [routed layers, len(positions)]; and the gaps of the picks that were
    ``forced``, same shape. ``forced``: {routed layer's ordinal: (at [T],
    picks [T, K])}."""
    w = weights
    tokens = jnp.asarray(tokens)
    x = f32(w["tok_emb"][tokens])
    pos = jnp.arange(tokens.shape[0]) if positions is None \
        else jnp.asarray(positions)
    margins, gaps = [], []
    for i in range(model["num_hidden_layers"]):
        x, margin, gap, _ = layer(w, i, x, model,
                                  (forced or {}).get(len(margins)))
        if margin is not None:
            margins.append(margin[pos])
            gaps.append(gap[pos])
    h = rms_norm(x[pos], w["final_norm"], model["layernorm_epsilon"])
    head = w["lm_head"]              # cast up 16k columns at a time
    logits = jnp.concatenate(
        [mm(h, head[:, c:c + 16384])
         for c in range(0, head.shape[1], 16384)], axis=-1)
    return logits, jnp.stack(margins), jnp.stack(gaps)
