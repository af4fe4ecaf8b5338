"""Laguna-XS.2's decoder in plain float32: grouped-query attention whose
layers are full or sliding-window, EACH KIND WITH ITS OWN NUMBER OF QUERY
HEADS over the same key/value heads and its own rotation (full: the first
half of every head rotated, YaRN's inverse frequencies, the cosines and
sines times its attention factor; window: every width, plain), a sigmoid
gate on every head's result before the output projection, and behind a
leading dense layer a float32 softmax router over ALL the experts, every
one held, the picked ones' renormalised weights scaled, beside a shared
expert that is added plainly. One sequence, no batch, no cache, no ring,
no blocks of keys: every layer builds its whole [T, T] scores and masks
them, every expert a token picked is applied to every token by a Python
loop and masked. Independent of paddle_tpu. Every product is taken at
"highest" precision.

The layer, for x [T, D] (ISSUE 55 writes it out from the catalog row's
config.json keys; the configuration file's ``assumed`` lists what those
leave open, each with its ground):

    u = RMSNorm(x);  layer i is FULL where layer_types[i] is
        "full_attention", WINDOW where "sliding_attention";  H =
        num_attention_heads_per_layer[i] query heads over G =
        num_key_value_heads, d = head_dim
    q = u Wq as [H, d];  k = u Wk, v = u Wv as [G, d]
    the first partial_rotary_factor * d widths of every q and k head
        rotated as (first half, second half) pairs by the kind's
        rope_parameters: "yarn" frequencies (theta, factor, original
        context, beta_fast, beta_slow) with cos and sin times
        attention_factor, or "default" theta ** (-2j / rotated widths)
    s_tj = q_t . k_j * d^-0.5 for j <= t and, in a window layer,
        t - j < sliding_window;  a = softmax(s) v as [H, d]
    g = sigmoid(u Wg) as [H];  h = x + concat(g_h a_h) Wo
    w = RMSNorm(h)
    dense (mlp_layer_types[i] "dense"):  y = h + SwiGLU(w)
    sparse:  p = softmax(w Wr) over all E in float32; the K largest are
        picked;  c = moe_routed_scaling_factor * p[picked] / sum
        y = h + sum over picked e of c_e SwiGLU_e(w) + SwiGLU_shared(w)
    logits = RMSNorm(y_last) W_head

Weights come as ``l{i}.<suffix>`` (from_stacked() reads the program's
layout so: ``lead.*`` the dense layers, ``full.*`` / ``window.*`` the
sparse layers of each kind, in the layers' order). Keys of the model that
start with ``_`` switch single terms off or over, for the tests and the
readings that show the comparison has teeth.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import hybrid_moe_share
from .latent_moe_mhc import (HIGHEST, _add_expert, f32, mm, rms_norm,
                             swiglu, yarn_inv_freq)

FULL, WINDOW = "full_attention", "sliding_attention"


def layer_names(model):
    """[(stack, index in it)] of every layer: ``lead`` where it is dense,
    else ``full`` | ``window`` by its attention."""
    seen, out = {}, []
    for kind, mlp in zip(model["layer_types"], model["mlp_layer_types"]):
        stack = "lead" if mlp == "dense" \
            else {FULL: "full", WINDOW: "window"}[kind]
        out.append((stack, seen.get(stack, 0)))
        seen[stack] = out[-1][1] + 1
    return out


class from_stacked(hybrid_moe_share.from_stacked):
    """hybrid_moe_share.from_stacked over this model's layer names."""

    def __init__(self, weights, model, through=None):
        self.weights, self.through = weights, through
        self.names = layer_names(model)


def rotation(m, i):
    """(widths rotated, their inverse frequencies [widths / 2], the factor
    on cos and sin) of layer ``i``, by its kind's ``rope_parameters``."""
    r = m["rope_parameters"][m["layer_types"][i]]
    rd = int(r["partial_rotary_factor"] * m["head_dim"])
    if r["rope_type"] == "yarn" and m.get("_use_yarn", True):
        return rd, yarn_inv_freq(
            rd, r["rope_theta"], r["factor"],
            r["original_max_position_embeddings"], r["beta_fast"],
            r["beta_slow"]), float(r["attention_factor"])
    if r["rope_type"] not in ("yarn", "default"):
        raise ValueError(f"not this reference's rotation: {r['rope_type']}")
    return rd, jnp.asarray(
        float(r["rope_theta"]) ** (-np.arange(0, rd, 2) / rd),
        jnp.float32), 1.0


def rope(x, inv_freq, rd, factor):
    """x [T, heads, d]: the first ``rd`` widths of every head rotated as
    (first half, second half) pairs at positions 0..T-1; the rest pass."""
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = factor * jnp.cos(ang)[:, None], factor * jnp.sin(ang)[:, None]
    x1, x2 = x[..., :rd // 2], x[..., rd // 2:rd]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                            x[..., rd:]], -1)


@functools.partial(jax.jit, static_argnames=(
    "H", "G", "d", "rd", "factor", "window", "rep"))
def _attention(u, wq, wk, wv, wg, wo, inv_freq, *, H, G, d, rd, factor,
               window, rep):
    T = u.shape[0]
    q = rope(mm(u, wq).reshape(T, H, d), inv_freq, rd, factor)
    k = rope(mm(u, wk).reshape(T, G, d), inv_freq, rd, factor)
    v = mm(u, wv).reshape(T, G, d)
    t, j = jnp.arange(T)[:, None], jnp.arange(T)[None]
    seen = j <= t
    if window is not None:
        seen = seen & (t - j < window)
    # a head at a time, [T, T] scores; ``rep`` query heads a key/value head
    of = np.minimum(np.arange(H) // rep, G - 1)

    def head(qkv):
        qh, kh, vh = qkv
        s = jnp.einsum("qd,kd->qk", qh, kh, precision=HIGHEST) * d ** -0.5
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        return jnp.einsum("qk,kd->qd", p, vh, precision=HIGHEST)

    a = jnp.moveaxis(jax.lax.map(head, (
        jnp.moveaxis(q, 1, 0), jnp.moveaxis(k, 1, 0)[of],
        jnp.moveaxis(v, 1, 0)[of])), 0, 1)                  # [T, H, d]
    if wg is not None:
        a = a * jax.nn.sigmoid(mm(u, wg))[..., None]
    return mm(a.reshape(T, H * d), wo)


def attention(w, i, u, m):
    """Layer ``i``'s attention on one sequence u [T, D]."""
    windowed = m["layer_types"][i] == WINDOW
    H, G = m["num_attention_heads_per_layer"][i], m["num_key_value_heads"]
    rd, inv_freq, factor = rotation(m, i)
    rep = H // G
    if not windowed and m.get("_full_heads"):   # grouped as if it had them
        rep = m["_full_heads"] // G
    gated = m["gating"] and m.get("_use_gate", True)
    return _attention(
        u, w[f"l{i}.wq"], w[f"l{i}.wk"], w[f"l{i}.wv"],
        w[f"l{i}.wg"] if gated else None, w[f"l{i}.wo"], inv_freq,
        H=H, G=G, d=m["head_dim"], rd=rd, factor=factor,
        window=m.get("_window", m["sliding_window"]) if windowed else None,
        rep=rep)


@functools.partial(jax.jit, static_argnames=("K", "scale"))
def _route(u, router, forced_at, forced, *, K, scale):
    logits = mm(u, router)
    p = jax.nn.softmax(logits, -1)
    top, order = jax.lax.top_k(logits, K + 1)
    picked = jnp.where(forced_at[:, None], forced, order[:, :K])
    g = jnp.take_along_axis(p, picked, -1)
    gap = top[:, K - 1] - jnp.min(
        jnp.take_along_axis(logits, picked, -1), -1)
    return picked, scale * g / jnp.sum(g, -1, keepdims=True), \
        top[:, K - 1] - top[:, K], jnp.maximum(gap, 0.0)


def route(w, i, u, m, forced=None):
    """(picked [T, K] over all the experts, their weights [T, K], margin
    [T], gap [T]): a float32 softmax over the router's logits, the K
    largest renormalised, times moe_routed_scaling_factor. The softmax
    keeps the logits' order, so the margin (between the K-th and the
    (K+1)-th) and the gap of ``forced`` picks (latent_moe_mhc.route) are
    taken on the LOGITS, where a rounding upstream acts."""
    T, K = u.shape[0], m["num_experts_per_tok"]
    at, picks = forced if forced is not None else (
        np.zeros((T,), bool), np.zeros((T, K), np.int32))
    return _route(u, w[f"l{i}.moe_router"], jnp.asarray(at),
                  jnp.asarray(picks, jnp.int32), K=K, scale=float(
                      m.get("_routed_scale", m["moe_routed_scaling_factor"])))


def experts(w, i, u, m, forced=None):
    """Every expert that a token picked on every token, masked by the
    routing, and the shared expert on all. Returns (out [T, D], margin
    [T], gap [T], picked [T, K])."""
    picked, g, margin, gap = route(w, i, u, m, forced)
    out = jnp.zeros_like(u)
    for e in np.unique(np.asarray(picked)):
        out = _add_expert(out, picked, g, int(e), swiglu(
            u, w[f"l{i}.moe_w_gate"][e], w[f"l{i}.moe_w_up"][e],
            w[f"l{i}.moe_w_down"][e]))
    if m["shared_expert_intermediate_size"] and m.get("_use_shared", True):
        out = out + swiglu(u, w[f"l{i}.sh_w_gate"], w[f"l{i}.sh_w_up"],
                           w[f"l{i}.sh_w_down"])
    return out, margin, gap, picked


def layer(w, i, x, m, forced=None):
    """Layer ``i`` on one sequence x [T, D]: (y [T, D], margin, gap,
    picked), the last three None for a dense layer."""
    eps = m["rms_norm_eps"]
    h = x + attention(w, i, rms_norm(x, w[f"l{i}.attn_norm"], eps), m)
    u = rms_norm(h, w[f"l{i}.mlp_norm"], eps)
    if m["mlp_layer_types"][i] == "dense":
        return h + swiglu(u, w[f"l{i}.w_gate"], w[f"l{i}.w_up"],
                          w[f"l{i}.w_down"]), None, None, None
    out, margin, gap, picked = experts(w, i, u, m, forced)
    return h + out, margin, gap, picked


def forward(weights, tokens, model, positions=None, forced=None):
    """Float32 logits of one sequence ``tokens`` [T] at ``positions`` (all
    of them when None), [len(positions), V]; each sparse layer's selection
    margin at those positions, [sparse layers, len(positions)]; and the
    gaps of the picks that were ``forced``, same shape. ``forced``:
    {sparse layer's ordinal: (at [T], picks [T, K])}."""
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
    h = rms_norm(x[pos], w["final_norm"], model["rms_norm_eps"])
    head = w["lm_head"]              # cast up 16k columns at a time
    logits = jnp.concatenate(
        [mm(h, head[:, c:c + 16384])
         for c in range(0, head.shape[1], 16384)], axis=-1)
    return logits, jnp.stack(margins), jnp.stack(gaps)
