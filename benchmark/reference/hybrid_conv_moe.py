"""LFM2-24B-A2B's decoder in plain float32: layers that are a GATED SHORT
CONVOLUTION (two gates around a causal depthwise convolution of three
taps, no bias, no activation) or grouped-query attention whose queries and
keys are normed A HEAD AT A TIME before the rotation, and behind the
leading dense layers a float32 sigmoid router over all the experts, a
bias steering the selection alone, every expert held. One sequence, no
batch, no cache, no tail, no blocks of keys: the convolution is three
shifted products over the whole sequence, every attention layer builds
its whole [T, T] scores a head and masks them, every expert a token
picked is applied to every token by a Python loop and masked. Independent
of paddle_tpu. Every product is taken at "highest" precision.

The layer, for x [T, D] (ISSUE 58 writes it out from the catalog row's
config.json keys and transformers' modeling_lfm2.py, the dense sibling's:
``Lfm2ShortConv``, ``Lfm2Attention``, ``Lfm2DecoderLayer``; the
configuration file's ``assumed`` lists what those leave open):

    u = RMSNorm(x)                                  (operator_norm)
    layer_types[i] "conv":
        [B | C | z] = u W_in;  g = B * z
        c_t = w_0 g_{t-2} + w_1 g_{t-1} + w_2 g_t   (zeros before 0)
        h = x + (C * c) W_out
    layer_types[i] "full_attention":  H query heads over G key/value
        heads, d = head_dim
        q = u Wq as [H, d];  k = u Wk, v = u Wv as [G, d]
        q, k each through an RMSNorm over a head's d widths, ONE [d]
            weight for all heads, then all d widths of every head rotated
            as (first half, second half) pairs, theta ** (-2j / d)
        s_tj = q_t . k_j * d^-0.5 for j <= t;  a = softmax(s) v
        h = x + concat(a_h) Wo
    w = RMSNorm(h)                                  (ffn_norm)
    i < num_dense_layers:  y = h + SwiGLU(w)
    else:  s = sigmoid(w Wr) over all E in float32; the K largest of
        s + expert_bias are picked;  c = routed_scaling_factor *
        s[picked] / (sum + 1e-6);  y = h + sum over picked e of c_e
        SwiGLU_e(w)
    logits = RMSNorm(y_last) W_head,  W_head the embedding's transpose

Weights come as ``l{i}.<suffix>`` (from_stacked() reads the program's
layout so: ``lead.*`` the dense layers, ``full.*`` / ``conv.*`` the routed
layers of each kind, in the layers' order). Keys of the model that start
with ``_`` switch single terms off or over, for the tests and the
readings that show the comparison has teeth.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import hybrid_moe_share
from .latent_moe_mhc import HIGHEST, _add_expert, f32, mm, rms_norm, swiglu

FULL, CONV = "full_attention", "conv"
ROUTE_EPS = 1e-6        # beside the picked scores' sum (the family's)


def layer_names(model):
    """[(stack, index in it)] of every layer: ``lead`` where it is dense,
    else ``full`` | ``conv`` by its operator."""
    seen, out = {}, []
    for i, kind in enumerate(model["layer_types"]):
        stack = "lead" if i < model["num_dense_layers"] \
            else {FULL: "full", CONV: "conv"}[kind]
        out.append((stack, seen.get(stack, 0)))
        seen[stack] = out[-1][1] + 1
    return out


class from_stacked(hybrid_moe_share.from_stacked):
    """hybrid_moe_share.from_stacked over this model's layer names."""

    def __init__(self, weights, model, through=None):
        self.weights, self.through = weights, through
        self.names = layer_names(model)


@functools.partial(jax.jit, static_argnames=("in_gate", "out_gate", "taps"))
def _short_conv(u, w_in, conv_w, w_out, *, in_gate, out_gate, taps):
    T = u.shape[0]
    gate_in, gate_out, z = jnp.split(mm(u, w_in), 3, axis=-1)
    g = gate_in * z if in_gate else z
    k = conv_w.shape[0]
    w = f32(conv_w)
    # tap j meets the input k - 1 - j positions back: k shifted products
    c = jnp.zeros_like(g)
    for j in range(k - taps, k):
        back = k - 1 - j
        c = c + w[j] * jnp.pad(g, ((back, 0), (0, 0)))[:T]
    return mm(gate_out * c if out_gate else c, w_out)


def short_conv(w, i, u, m):
    """Layer ``i``'s gated short convolution on one sequence u [T, D]."""
    if m["conv_bias"]:
        raise ValueError("not this reference's convolution: it has a bias")
    return _short_conv(
        u, w[f"l{i}.w_in"], w[f"l{i}.conv_w"], w[f"l{i}.w_out"],
        in_gate=m.get("_use_in_gate", True),
        out_gate=m.get("_use_out_gate", True),
        taps=m["conv_L_cache"] if m.get("_older_taps", True) else 1)


def rope(x, theta):
    """x [T, heads, d]: every head's d widths rotated as (first half,
    second half) pairs at positions 0..T-1."""
    d = x.shape[-1]
    inv_freq = jnp.asarray(float(theta) ** (-np.arange(0, d, 2) / d),
                           jnp.float32)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=(
    "H", "G", "d", "theta", "eps", "norm"))
def _attention(u, wq, wk, wv, wo, q_norm, k_norm, *, H, G, d, theta, eps,
               norm):
    T = u.shape[0]

    def normed(x, n, weight):
        if norm == "head":
            return rms_norm(x.reshape(T, n, d), weight, eps)
        # the fault: one norm over the whole projection, the [d] weight
        # repeated along it
        return rms_norm(x, jnp.tile(f32(weight), n), eps).reshape(T, n, d)

    q = rope(normed(mm(u, wq), H, q_norm), theta)
    k = rope(normed(mm(u, wk), G, k_norm), theta)
    v = mm(u, wv).reshape(T, G, d)
    seen = jnp.arange(T)[None] <= jnp.arange(T)[:, None]
    of = np.arange(H) // (H // G)      # a query head's key/value head

    def head(qkv):
        qh, kh, vh = qkv
        s = jnp.einsum("qd,kd->qk", qh, kh, precision=HIGHEST) * d ** -0.5
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        return jnp.einsum("qk,kd->qd", p, vh, precision=HIGHEST)

    a = jnp.moveaxis(jax.lax.map(head, (
        jnp.moveaxis(q, 1, 0), jnp.moveaxis(k, 1, 0)[of],
        jnp.moveaxis(v, 1, 0)[of])), 0, 1)                  # [T, H, d]
    return mm(a.reshape(T, H * d), wo)


def attention(w, i, u, m):
    """Layer ``i``'s attention on one sequence u [T, D]."""
    r = m["rope_parameters"]
    if r["rope_type"] != "default":
        raise ValueError(f"not this reference's rotation: {r['rope_type']}")
    return _attention(
        u, w[f"l{i}.wq"], w[f"l{i}.wk"], w[f"l{i}.wv"], w[f"l{i}.wo"],
        w[f"l{i}.q_norm"], w[f"l{i}.k_norm"],
        H=m["num_attention_heads"], G=m["num_key_value_heads"],
        d=m["head_dim"], theta=float(r["rope_theta"]),
        eps=float(m["norm_eps"]), norm=m.get("_head_norm", "head"))


@functools.partial(jax.jit, static_argnames=("K", "scale", "use_bias"))
def _route(u, router, bias, forced_at, forced, *, K, scale, use_bias):
    s = jax.nn.sigmoid(mm(u, router))
    sel = s + f32(bias) if use_bias else s
    top, order = jax.lax.top_k(sel, K + 1)
    picked = jnp.where(forced_at[:, None], forced, order[:, :K])
    g = jnp.take_along_axis(s, picked, -1)
    gap = top[:, K - 1] - jnp.min(jnp.take_along_axis(sel, picked, -1), -1)
    return picked, scale * g / (jnp.sum(g, -1, keepdims=True) + ROUTE_EPS), \
        top[:, K - 1] - top[:, K], jnp.maximum(gap, 0.0)


def route(w, i, u, m, forced=None):
    """(picked [T, K] over all the experts, their weights [T, K], margin
    [T], gap [T]): float32 sigmoid scores, the K largest of ``score +
    expert_bias`` picked, the picked ones' OWN scores divided by their sum
    + 1e-6, times routed_scaling_factor. The margin (between the K-th and
    the (K+1)-th) and the gap of ``forced`` picks (latent_moe_mhc.route)
    are taken on the selection scores."""
    if not (m["norm_topk_prob"] and m["use_expert_bias"]):
        raise ValueError("not this reference's router")
    T, K = u.shape[0], m["num_experts_per_tok"]
    at, picks = forced if forced is not None else (
        np.zeros((T,), bool), np.zeros((T, K), np.int32))
    return _route(u, w[f"l{i}.moe_router"], w[f"l{i}.moe_bias"],
                  jnp.asarray(at), jnp.asarray(picks, jnp.int32), K=K,
                  scale=float(m["routed_scaling_factor"]),
                  use_bias=m.get("_use_bias", True))


def experts(w, i, u, m, forced=None):
    """Every expert that a token picked on every token, masked by the
    routing. Returns (out [T, D], margin [T], gap [T], picked [T, K])."""
    picked, g, margin, gap = route(w, i, u, m, forced)
    out = jnp.zeros_like(u)
    for e in np.unique(np.asarray(picked)):
        out = _add_expert(out, picked, g, int(e), swiglu(
            u, w[f"l{i}.moe_w_gate"][e], w[f"l{i}.moe_w_up"][e],
            w[f"l{i}.moe_w_down"][e]))
    return out, margin, gap, picked


def layer(w, i, x, m, forced=None):
    """Layer ``i`` on one sequence x [T, D]: (y [T, D], margin, gap,
    picked), the last three None for a dense layer."""
    eps = m["norm_eps"]
    op = {FULL: attention, CONV: short_conv}[m["layer_types"][i]]
    h = x + op(w, i, rms_norm(x, w[f"l{i}.attn_norm"], eps), m)
    u = rms_norm(h, w[f"l{i}.mlp_norm"], eps)
    if i < m["num_dense_layers"]:
        return h + swiglu(u, w[f"l{i}.w_gate"], w[f"l{i}.w_up"],
                          w[f"l{i}.w_down"]), None, None, None
    out, margin, gap, picked = experts(w, i, u, m, forced)
    return h + out, margin, gap, picked


def forward(weights, tokens, model, positions=None, forced=None):
    """Float32 logits of one sequence ``tokens`` [T] at ``positions`` (all
    of them when None), [len(positions), V]; each routed layer's selection
    margin at those positions, [routed layers, len(positions)]; and the
    gaps of the picks that were ``forced``, same shape. ``forced``:
    {routed layer's ordinal: (at [T], picks [T, K])}."""
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
    h = rms_norm(x[pos], w["final_norm"], model["norm_eps"])
    emb = w["tok_emb"]               # tied; cast up 16k rows at a time
    logits = jnp.concatenate(
        [mm(h, f32(emb[r:r + 16384]).T)
         for r in range(0, emb.shape[0], 16384)], axis=-1)
    return logits, jnp.stack(margins), jnp.stack(gaps)
