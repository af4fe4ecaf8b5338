"""Olmo-Hybrid-7B's decoder (``model_type`` ``olmo_hybrid``) in plain
float32: gated delta-rule linear attention in the layers ``layer_types``
calls ``linear_attention`` and full attention WITHOUT any position
embedding in those it calls ``full_attention``, the family's reordered
norm around both sublayers, a SwiGLU in every layer, an untied head. One
sequence, no batch, no cache, no buckets, no chunks: the delta rule is the
RECURRENCE, one sequential ``lax.scan`` over the positions with the
heads' matrices carried (the engine computes it 64 positions at a time as
matrix products: the two are independent derivations); every attention
layer builds its scores against every key and masks them. Independent of
paddle_tpu. Every product is taken at "highest" precision. Where the
chip's memory asks, the rows of a sequence go through the matrices and
the attention a block at a time (ROWS, QUERIES): the same numbers.

The layer, for a token x [D] at position t (ISSUE 47 writes it out; the
configuration file's ``assumed`` lists what the catalog row's keys leave
open), N an RMSNorm with a plain weight and eps = rms_norm_eps:

    h = x + N1(Mixer(x));   y = h + N2(W_down(silu(W_gate h) * W_up h))
    full attention:  q = N_q(W_q x), k = N_k(W_k x) (over the whole
                  projection), v = W_v x, as heads of hd; s_tj = q_t . k_j
                  * hd^-0.5 for j <= t; softmax; W_o. No rotation.
    delta rule:   [q | k | v]_t = silu(sum_{j<4} w_conv[j] *
                        [W_q x | W_k x | W_v x]_{t-3+j})  (depthwise,
                        causal, zeros before position 0, no bias)
                  per head: q_t <- q_t / |q_t| * dk^-0.5, k_t <- k_t /
                        |k_t|  (|.| = sqrt(sum of squares + 1e-6))
                  beta_t = 2 sigmoid(W_b x_t);  g_t = -exp(A_log) *
                        softplus(W_a x_t + dt_bias);  alpha_t = exp(g_t)
                  S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t
                        S_{t-1}^T k_t)^T,  S_0 = 0,  S [dk, dv] a head
                  o_t = S_t^T q_t;  out = W_o (N_g(o_t) * silu(W_z x_t)),
                        N_g over each head's dv
    after the last layer N_f; logits = that times the head.

Weights come as ``l{i}.<suffix>`` (from_stacked() reads the program's
layout so: ``full.*`` the attention layers, ``delta.*`` the others, in the
order of the layers).
"""
import functools

import jax
import jax.numpy as jnp

from . import hybrid_ssm
from .latent_moe_mhc import HIGHEST, f32, mm, rms_norm, swiglu

ROWS = 4096         # rows a block through a layer's matrices
QUERIES = 512       # queries a block of attention's [heads, queries, T]
L2_EPS = 1e-6


def is_attention(model, i):
    return model["layer_types"][i] == "full_attention"


def layer_names(model):
    """[(stack, index in it)] of every layer: ``full`` | ``delta``."""
    seen, out = {}, []
    for i in range(model["num_hidden_layers"]):
        stack = "full" if is_attention(model, i) else "delta"
        out.append((stack, seen.get(stack, 0)))
        seen[stack] = out[-1][1] + 1
    return out


class from_stacked(hybrid_ssm.from_stacked):
    """reference/hybrid_ssm.py's reading of the program's stacked layout
    as ``l{i}.*``, over this model's layers; with ``through`` (a dtype)
    every matrix is rounded to that type on its way, the norms and the
    decay's ``a_log`` and ``dt_bias`` not."""

    KEEP = ("norm", "a_log", "dt_bias")

    def __init__(self, weights, model, through=None):
        self.weights, self.through = weights, through
        self.names = layer_names(model)


def by_blocks(fn, x, rows):
    """``fn`` over x (an array [T, ...] or a tuple of them) a block of
    ``rows`` rows at a time (fn maps rows to rows; the last block is
    padded and cut)."""
    tree = jax.tree_util.tree_map
    t = jax.tree_util.tree_leaves(x)[0].shape[0]
    if t <= rows:
        return fn(x)
    n = -(-t // rows)
    x = tree(lambda y: jnp.pad(
        y, [(0, n * rows - t)] + [(0, 0)] * (y.ndim - 1)).reshape(
            (n, rows) + y.shape[1:]), x)
    return tree(lambda y: y.reshape((n * rows,) + y.shape[2:])[:t],
                jax.lax.map(fn, x))


@functools.partial(jax.jit, static_argnames=("H", "G", "hd", "eps"))
def _attention(x, wq, wk, wv, wo, q_norm, k_norm, *, H, G, hd, eps):
    T = x.shape[0]
    q, k, v = by_blocks(
        lambda u: (rms_norm(mm(u, wq), q_norm, eps),
                   rms_norm(mm(u, wk), k_norm, eps), mm(u, wv)), x, ROWS)
    k = jnp.repeat(k.reshape(T, G, hd), H // G, axis=1)
    v = jnp.repeat(v.reshape(T, G, hd), H // G, axis=1)

    def attend(qp):
        qb, pos = qp
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) \
            * hd ** -0.5
        s = jnp.where(jnp.arange(T)[None] <= pos[:, None], s, -jnp.inf)
        e = jnp.exp(s - jnp.max(s, -1, keepdims=True))
        return jnp.einsum("hqk,khd->qhd", e / jnp.sum(e, -1, keepdims=True),
                          v, precision=HIGHEST).reshape(-1, H * hd)

    out = by_blocks(attend, (q.reshape(T, H, hd), jnp.arange(T)), QUERIES)
    return by_blocks(lambda a: mm(a, wo), out, ROWS)


def attention(w, i, x, m):
    H = m["num_attention_heads"]
    return _attention(
        x, *(w[f"l{i}.{s}"] for s in ("wq", "wk", "wv", "wo", "q_norm",
                                      "k_norm")),
        H=H, G=m["num_key_value_heads"], hd=m["hidden_size"] // H,
        eps=m["rms_norm_eps"])


def _l2(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


@functools.partial(jax.jit, static_argnames=("H", "dk", "dv", "eps",
                                             "beta_max"))
def _delta(x, wq, wk, wv, wz, wa, wb, conv_w, a_log, dt_bias, g_norm, wo,
           *, H, dk, dv, eps, beta_max):
    """One sequence x [T, D] through a gated delta-rule mixer from S = 0:
    (out [T, D], the heads' states after the last position [H, dk, dv])."""
    T, k_taps = x.shape[0], conv_w.shape[0]
    z, gate, a, b = by_blocks(
        lambda u: (jnp.concatenate([mm(u, wq), mm(u, wk), mm(u, wv)], -1),
                   mm(u, wz), mm(u, wa), mm(u, wb)), x, ROWS)
    full = jnp.pad(z, [(k_taps - 1, 0), (0, 0)])
    c = sum(full[j:j + T] * f32(conv_w)[j] for j in range(k_taps))
    c = jax.nn.silu(c)
    q = _l2(c[:, :H * dk].reshape(T, H, dk)) * dk ** -0.5
    k = _l2(c[:, H * dk:2 * H * dk].reshape(T, H, dk))
    v = c[:, 2 * H * dk:].reshape(T, H, dv)
    beta = beta_max * jax.nn.sigmoid(b)                   # [T, H]
    alpha = jnp.exp(-jnp.exp(f32(a_log))
                    * jax.nn.softplus(a + f32(dt_bias)))

    def step(state, xs):
        q_t, k_t, v_t, alpha_t, beta_t = xs
        state = alpha_t[:, None, None] * state
        u = beta_t[:, None] * (v_t - jnp.sum(state * k_t[:, :, None], 1))
        state = state + k_t[:, :, None] * u[:, None, :]
        return state, jnp.sum(state * q_t[:, :, None], 1)

    state, o = jax.lax.scan(step, jnp.zeros((H, dk, dv), jnp.float32),
                            (q, k, v, alpha, beta))
    o = rms_norm(o, g_norm, eps).reshape(T, H * dv)
    return by_blocks(lambda og: mm(og[0] * jax.nn.silu(og[1]), wo),
                     (o, gate), ROWS), state


def delta(w, i, x, m):
    """Layer ``i``'s delta-rule mixer on one sequence x [T, D]: (out, the
    heads' states it leaves)."""
    if m["linear_num_key_heads"] != m["linear_num_value_heads"]:
        raise ValueError("value heads grouped over key heads: not this "
                         "reference's model")
    return _delta(
        x, *(w[f"l{i}.{s}"] for s in (
            "wq", "wk", "wv", "wz", "wa", "wb", "conv_w", "a_log",
            "dt_bias", "g_norm", "wo")),
        H=m["linear_num_key_heads"], dk=m["linear_key_head_dim"],
        dv=m["linear_value_head_dim"], eps=m["rms_norm_eps"],
        beta_max=2.0 if m["linear_allow_neg_eigval"] else 1.0)


@functools.partial(jax.jit, static_argnames=("eps",))
def _residuals(x, mixed, n1, w_gate, w_up, w_down, n2, *, eps):
    h = x + rms_norm(mixed, n1, eps)
    ffn = by_blocks(lambda u: swiglu(u, w_gate, w_up, w_down), h, ROWS)
    return h + rms_norm(ffn, n2, eps)


def layer(w, i, x, m):
    """Layer ``i`` on one sequence x [T, D]: (y [T, D], the states a
    delta-rule layer leaves, None for an attention layer)."""
    if is_attention(m, i):
        mixed, state = attention(w, i, x, m), None
    else:
        mixed, state = delta(w, i, x, m)
    return _residuals(
        x, mixed, *(w[f"l{i}.{s}"] for s in (
            "attn_post_norm", "w_gate", "w_up", "w_down", "mlp_post_norm")),
        eps=m["rms_norm_eps"]), state


def forward(weights, tokens, model, positions=None, return_states=False):
    """Float32 logits of one sequence ``tokens`` [T] at ``positions`` (all
    of them when None), [len(positions), V]; ``return_states``: also
    {layer: the heads' states [H, dk, dv]} every delta-rule layer leaves
    after the whole sequence. A key ``_stream_dtype`` of the model rounds
    the residual stream to that type behind every layer and nothing else:
    the reading that tells the engine's own precision from a fault."""
    w = weights
    tokens = jnp.asarray(tokens)
    x = f32(w["tok_emb"][tokens])
    pos = jnp.arange(tokens.shape[0]) if positions is None \
        else jnp.asarray(positions)
    states = {}
    for i in range(model["num_hidden_layers"]):
        x, state = layer(w, i, x, model)
        if state is not None:
            states[i] = state
        if "_stream_dtype" in model:    # the stream rounded a layer
            x = f32(x.astype(model["_stream_dtype"]))
    h = rms_norm(x[pos], w["final_norm"], model["rms_norm_eps"])
    head = w["lm_head"]              # [D, V]; cast up 16k columns at a time
    logits = jnp.concatenate(
        [mm(h, head[:, c:c + 16384])
         for c in range(0, head.shape[1], 16384)], axis=-1)
    return (logits, states) if return_states else logits
