"""Ling-3.0-flash's language model in plain float32, as ONE CHIP'S SHARE of
an expert-parallel deployment: layers that are KIMI DELTA ATTENTION (the
delta rule with a decay a CHANNEL behind a causal convolution of four
taps) or LATENT attention with a gate a head, and behind the leading dense
layers a group-limited sigmoid router over ALL the routed experts, of which
this chip holds a run and computes that run's part of the sum, plus the
shared expert. One sequence, no batch, no cache, no chunks, no absorbed
form: the delta rule is the RECURRENCE, one sequential ``lax.scan`` over
the positions with the heads' matrices carried (the engine computes it 64
positions at a time as matrix products: the two are independent
derivations); the latent layer expands every position's keys and values
and masks its whole [T, T] scores; every held expert is applied to every
token by a Python loop and masked. Independent of
paddle_tpu. Every product is taken at "highest" precision.

The layer, for x [T, D] (ISSUE 62 writes it out from the catalog row's
config.json keys, arXiv:2510.26692 and arXiv:2405.04434; the configuration
file's ``assumed`` lists what the keys leave open), H heads:

    u = RMSNorm(x)
    layer_types[i] "kda"  (dk = dv = head_dim):
        [q | k | v]_t = silu(sum_{j<4} w_conv[j] [u Wq | u Wk | u Wv]_{t-3+j})
                    (depthwise, causal, zeros before position 0, no bias)
        per head: q_t <- q_t / |q_t| * dk^-0.5,  k_t <- k_t / |k_t|
                    (|.| = sqrt(sum of squares + 1e-6))
        g_t = kda_lower_bound * sigmoid(exp(A_log[h]) * (u_t Wf + dt_bias))
                    [H, dk], in (kda_lower_bound, 0): the log decay A CHANNEL
        beta_t = sigmoid(u_t Wb)                          [H]
        S_t = (I - beta_t k_t k_t^T) diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
                    S_0 = 0,  S [dk, dv] a head
        o_t = S_t^T q_t;  h = x + (N_g(o_t) * sigmoid(u_t Wz)) Wo,
                    N_g an RMSNorm over each head's dv
    layer_types[i] "mla":
        q = u Wq as [H, nope + rope];  [c | k_pe] = u Wkva;  c = RMSNorm(c)
        q_pe, k_pe = RoPE(de-interleaved pairs, t; theta, no scaling)
        [k_nope | v] = c Wkvb per head
        s_tj = (q_nope.k_nope + q_pe.k_pe) (nope + rope)^-0.5, causal softmax
        h = x + concat_h(a_h * sigmoid(u Wg)[h]) Wo
    w = RMSNorm(h)
    i < first_k_dense_replace:  y = h + SwiGLU(w)
    else: router and experts as latent_moe_share.py's (sigmoid scores, a
        bias on the selection, n_group groups scored by their two best,
        topk_group kept, the K best picked, their own scores / (sum +
        1e-20) * routed_scaling_factor; the experts HELD HERE + the shared
        expert)
    logits = RMSNorm(y) W_head over the vocabulary's slice held here

What the picked experts that are not held would add is left out, here as
in the program. Weights come as ``l{i}.<suffix>`` (from_stacked() reads the
program's layout so: ``lead.*`` the dense layers, ``latent.*`` / ``kda.*``
the routed layers of each kind, in the layers' order; ``wa`` is ``Wf``).
Keys of the model that start with ``_`` switch single terms off or over,
for the tests and the readings that show the comparison has teeth.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import hybrid_moe_share, latent_moe_share
from .latent_moe_mhc import (HEADS_AT_A_TIME, HIGHEST, f32, mm, rms_norm,
                             rope, swiglu)
from .latent_moe_share import route

KDA, MLA = "kda", "mla"
L2_EPS = 1e-6


def layer_names(model):
    """[(stack, index in it)] of every layer: ``lead`` where it is dense,
    else ``latent`` | ``kda`` by its mixer."""
    seen, out = {}, []
    for i, kind in enumerate(model["layer_types"]):
        stack = "lead" if i < model["first_k_dense_replace"] \
            else {MLA: "latent", KDA: "kda"}[kind]
        out.append((stack, seen.get(stack, 0)))
        seen[stack] = out[-1][1] + 1
    return out


class from_stacked(hybrid_moe_share.from_stacked):
    """hybrid_moe_share.from_stacked over this model's layer names;
    ``through`` leaves the decay's ``a_log`` as it is too (float32 in the
    program, as ``dt_bias``, the norms, the router and its bias are)."""

    def __init__(self, weights, model, through=None):
        self.weights, self.through = weights, through
        self.names = layer_names(model)

    def _cast(self, name):
        if name.endswith("a_log"):
            return lambda x: x
        return super()._cast(name)


def _l2(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


@functools.partial(jax.jit, static_argnames=(
    "H", "dk", "dv", "eps", "floor", "beta_max", "decay", "gate", "taps",
    "state_dtype"))
def _kda(u, wq, wk, wv, wz, wf, wb, conv_w, a_log, dt_bias, g_norm, wo, *,
         H, dk, dv, eps, floor, beta_max, decay, gate, taps, state_dtype):
    """One sequence u [T, D] (normed) through a Kimi-delta-attention mixer
    from S = 0: (out [T, D], the heads' states after the last position
    [H, dk, dv])."""
    T, k_taps = u.shape[0], conv_w.shape[0]
    z = jnp.concatenate([mm(u, wq), mm(u, wk), mm(u, wv)], -1)
    full = jnp.pad(z, [(k_taps - 1, 0), (0, 0)])
    c = jax.nn.silu(sum(full[j:j + T] * f32(conv_w)[j]
                        for j in range(k_taps - taps, k_taps)))
    q = _l2(c[:, :H * dk].reshape(T, H, dk)) * dk ** -0.5
    k = _l2(c[:, H * dk:2 * H * dk].reshape(T, H, dk))
    v = c[:, 2 * H * dk:].reshape(T, H, dv)
    beta = beta_max * jax.nn.sigmoid(mm(u, wb))                  # [T, H]
    g = floor * jax.nn.sigmoid(
        jnp.exp(f32(a_log))[:, None]
        * (mm(u, wf) + f32(dt_bias)).reshape(T, H, dk))          # [T, H, dk]
    if decay == "head":         # the fault: ONE decay a head, the mean
        g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
    elif decay == "none":
        g = jnp.zeros_like(g)
    alpha = jnp.exp(g)

    def step(state, xs):
        q_t, k_t, v_t, alpha_t, beta_t = xs
        state = alpha_t[:, :, None] * state         # the state's ROWS
        w = beta_t[:, None] * (v_t - jnp.sum(state * k_t[:, :, None], 1))
        state = state + k_t[:, :, None] * w[:, None, :]
        if state_dtype is not None:     # the fault: a state kept rounded
            # (reduce_precision and not a cast there and back, which XLA
            # may take for the identity: excess precision allowed)
            fi = jnp.finfo(state_dtype)
            state = jax.lax.reduce_precision(state, fi.nexp, fi.nmant)
        return state, jnp.sum(state * q_t[:, :, None], 1)

    state, o = jax.lax.scan(step, jnp.zeros((H, dk, dv), jnp.float32),
                            (q, k, v, alpha, beta))
    o = rms_norm(o, g_norm, eps).reshape(T, H * dv)
    act = {"sigmoid": jax.nn.sigmoid, "silu": jax.nn.silu}[gate]
    return mm(o * act(mm(u, wz)), wo), state


def kda(w, i, u, m):
    """Layer ``i``'s delta-rule mixer on one sequence u [T, D]: (out, the
    heads' states it leaves)."""
    sd = m.get("_state_dtype")
    return _kda(
        u, *(w[f"l{i}.{s}"] for s in (
            "wq", "wk", "wv", "wz", "wa", "wb", "conv_w", "a_log",
            "dt_bias", "g_norm", "wo")),
        H=m["num_attention_heads"], dk=m["head_dim"],
        dv=m.get("_kda_value_dim", m["head_dim"]),
        eps=float(m["rms_norm_eps"]), floor=float(m["kda_lower_bound"]),
        beta_max=float(m.get("_beta_max", 1.0)),
        decay=m.get("_decay", "channel"),
        gate=m.get("_out_gate", "sigmoid"),
        taps=m["short_conv_kernel_size"] if m.get("_older_taps", True)
        else 1, state_dtype=None if sd is None else jnp.dtype(sd).name)


@functools.partial(jax.jit, static_argnames=(
    "H", "nope", "rd", "vd", "R", "eps", "theta", "head_gate"))
def _latent(u, wq, wkva, kv_norm, wkvb, wg, wo, *, H, nope, rd, vd, R,
            eps, theta, head_gate):
    T = u.shape[0]
    inv_freq = jnp.asarray(theta ** (-np.arange(0, rd, 2) / rd),
                           jnp.float32)
    q = mm(u, wq).reshape(T, H, nope + rd)
    ckv = mm(u, wkva)
    c = rms_norm(ckv[:, :R], kv_norm, eps)
    q_pe = rope(q[..., nope:], inv_freq)
    k_pe = rope(ckv[:, None, R:], inv_freq)[:, 0]
    kv = mm(c, wkvb).reshape(T, H, nope + vd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    out = []
    for h in range(0, H, HEADS_AT_A_TIME):
        hs = slice(h, h + HEADS_AT_A_TIME)
        s = (jnp.einsum("qhd,khd->hqk", q[:, hs, :nope], kv[:, hs, :nope],
                        precision=HIGHEST)
             + jnp.einsum("qhd,kd->hqk", q_pe[:, hs], k_pe,
                          precision=HIGHEST)) * (nope + rd) ** -0.5
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
        out.append(jnp.einsum("hqk,khd->qhd", p, kv[:, hs, nope:],
                              precision=HIGHEST))
    a = jnp.concatenate(out, 1)                              # [T, H, vd]
    if head_gate:
        a = a * jax.nn.sigmoid(mm(u, wg))[..., None]
    return mm(a.reshape(T, H * vd), wo)


def latent(w, i, u, m):
    """Layer ``i``'s latent attention on one sequence u [T, D]."""
    if m["q_lora_rank"] is not None:
        raise ValueError("not this reference's layer: its query has a "
                         "low-rank pair")
    return _latent(
        u, *(w[f"l{i}.{s}"] for s in ("wq", "wkva", "kv_norm", "wkvb",
                                      "wg", "wo")),
        H=m["num_attention_heads"], nope=m["qk_nope_head_dim"],
        rd=m["qk_rope_head_dim"], vd=m["v_head_dim"], R=m["kv_lora_rank"],
        eps=float(m["rms_norm_eps"]), theta=float(m["rope_theta"]),
        head_gate=m.get("_use_head_gate", True))


def experts(w, i, u, m, forced=None):
    """latent_moe_share.experts (the held experts on every token, masked
    by the routing over all the experts; then the shared expert) with this
    model's ONE shared expert under that file's key. Returns (out [T, D],
    margin [T], gap [T], picked [T, K])."""
    return latent_moe_share.experts(w, i, u, dict(m, n_shared_experts=1),
                                    forced)


def layer(w, i, x, m, forced=None):
    """Layer ``i`` on one sequence x [T, D]: (y [T, D], margin, gap,
    picked: None for a dense layer; the heads' states a kda layer leaves,
    None for a latent one)."""
    eps = float(m["rms_norm_eps"])
    u = rms_norm(x, w[f"l{i}.attn_norm"], eps)
    if m["layer_types"][i] == KDA:
        mixed, state = kda(w, i, u, m)
    else:
        mixed, state = latent(w, i, u, m), None
    h = x + mixed
    u = rms_norm(h, w[f"l{i}.mlp_norm"], eps)
    if i < m["first_k_dense_replace"]:
        return h + swiglu(u, w[f"l{i}.w_gate"], w[f"l{i}.w_up"],
                          w[f"l{i}.w_down"]), None, None, None, state
    out, margin, gap, picked = experts(w, i, u, m, forced)
    return h + out, margin, gap, picked, state


def forward(weights, tokens, model, positions=None, forced=None,
            return_states=False):
    """Float32 logits of one sequence ``tokens`` [T] at ``positions`` (all
    of them when None), [len(positions), V] over the vocabulary's slice
    held here; each routed layer's selection margin at those positions,
    [routed layers, len(positions)]; the gaps of the picks that were
    ``forced`` (latent_moe_share.route), same shape; ``return_states``:
    also {layer: the heads' states [H, dk, dv]} every kda layer leaves
    after the whole sequence. ``forced``: {routed layer's ordinal: (at
    [T], picks [T, K])}."""
    w = weights
    if model["score_function"] != "sigmoid" or not model["norm_topk_prob"] \
            or not model["moe_router_enable_expert_bias"]:
        raise ValueError("not this reference's router")
    tokens = jnp.asarray(tokens)
    x = f32(w["tok_emb"][tokens])
    pos = jnp.arange(tokens.shape[0]) if positions is None \
        else jnp.asarray(positions)
    margins, gaps, states = [], [], {}
    for i in range(model["num_hidden_layers"]):
        x, margin, gap, _, state = layer(w, i, x, model,
                                         (forced or {}).get(len(margins)))
        if margin is not None:
            margins.append(margin[pos])
            gaps.append(gap[pos])
        if state is not None:
            states[i] = state
    h = rms_norm(x[pos], w["final_norm"], float(model["rms_norm_eps"]))
    head = w["lm_head"]              # [D, V]; cast up 16k columns at a time
    logits = jnp.concatenate(
        [mm(h, head[:, c:c + 16384])
         for c in range(0, head.shape[1], 16384)], axis=-1)
    out = (logits, jnp.stack(margins), jnp.stack(gaps))
    return out + (states,) if return_states else out
