"""Xing4.0-29B-A4B's decoder in plain float32: latent attention with
YaRN rotary positions, sigmoid-routed experts with a selection bias and a
shared expert, and manifold-constrained hyper-connections around every
sublayer. One sequence, no batch, no cache, no absorbed form, no sort:
every expert is applied to every token by a Python loop and masked.
Independent of paddle_tpu. Every product is taken at "highest" precision.

The layer, for a token's n residual streams x [n, D] at position t
(ISSUE 27 writes it out; the configuration file's ``assumed`` lists what
was inferred):

    residual(F):  xv = RMSNorm(vec(x));  z = xv Phi                [2n + n*n]
                  Hpre = sigmoid(a0 z[:n] + b[:n])
                  Hpost = 2 sigmoid(a1 z[n:2n] + b[n:2n])
                  Hres = SinkhornKnopp(exp(clip(a2 z[2n:] + b[2n:], lo, hi)))
                  u = Hpre x;  y = F(RMSNorm_F(u));  x <- Hres x + Hpost^T y
    attention A:  cq = RMSNorm(u Wqa); q = cq Wqb -> per head [q_nope | q_pe]
                  [c | k_pe] = u Wkva;  c = RMSNorm(c)
                  q_pe, k_pe = RoPE_yarn(de-interleaved pairs, t)
                  [k_nope | v] = c Wkvb per head
                  scores (q_nope.k_nope + q_pe.k_pe) * s, causal softmax
    experts M:    sc = sigmoid(u Wg); pick the K largest of sc + b;
                  w = sc[picked] / (sum + 1e-20) * scale
                  M(u) = sum_e w_e E_e(u) + E_shared(u)

Weights come as ``l{i}.<suffix>`` (from_stacked() makes them of the
program's stacked layout), in whatever dtype they are served in, and are
cast up a matrix, and within the experts an expert, at a time, so that
the published widths fit beside what else the device holds. The pieces
are jitted one by one (a sublayer's mixing, the attention, the router,
one expert), which bounds their temporaries and lets the compile cache
keep them; the loops over layers and experts are Python's.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
HEADS_AT_A_TIME = 4      # attention's [heads, T, T] scores, in groups


class _Layer:
    """Layer ``i`` of a stacked expert tensor [L, E, ...], sliced only
    when an expert is asked for: a whole layer's experts are never copied."""

    def __init__(self, stacked, i):
        self.stacked, self.i = stacked, i
        self.shape = stacked.shape[1:]

    def __getitem__(self, e):
        return self.stacked[self.i, e]


def from_stacked(weights, n_dense):
    """``lead.*`` [n_dense, ...] and ``blocks.*`` [L, ...] -> ``l{i}.*``."""
    out = {k: v for k, v in weights.items()
           if not k.startswith(("lead.", "blocks."))}
    for name, w in weights.items():
        for prefix, first in (("lead.", 0), ("blocks.", n_dense)):
            if name.startswith(prefix):
                for i in range(w.shape[0]):
                    out[f"l{first + i}.{name[len(prefix):]}"] = \
                        _Layer(w, i) if w.ndim >= 4 else w[i]
    return out


def f32(x):
    return jnp.asarray(x, jnp.float32)


def rms_norm(x, w, eps):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return y if w is None else y * f32(w)


def mm(a, b):
    return jnp.matmul(a, f32(b), precision=HIGHEST)


def yarn_inv_freq(dim, base, factor, original_max, beta_fast, beta_slow):
    """As DeepseekV3YarnRotaryEmbedding: fast-turning pairs keep their
    frequency, slow ones are divided by ``factor``, a ramp between."""
    def pair_of(turns):
        return dim * math.log(original_max / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    plain = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    low = max(math.floor(pair_of(beta_fast)), 0)
    high = min(math.ceil(pair_of(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return jnp.asarray(plain / factor * ramp + plain * (1 - ramp),
                       jnp.float32)


def rope(x, inv_freq):
    """x [T, heads, d] with published (interleaved) pairs: de-interleaved,
    then rotated as (first half, second half) pairs."""
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, -1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def softmax_scale(m):
    r = m["rope_scaling"]
    mscale = 0.1 * r["mscale_all_dim"] * math.log(r["factor"]) + 1.0 \
        if r["factor"] > 1 else 1.0
    return (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) ** -0.5 \
        * mscale ** m.get("_mscale_power", 2)


@functools.partial(jax.jit, static_argnames=("H", "nope", "rd", "vd", "R",
                                             "eps", "scale"))
def _attention(u, wqa, q_norm, wqb, wkva, kv_norm, wkvb, wo, inv_freq, *,
               H, nope, rd, vd, R, eps, scale):
    T = u.shape[0]
    q = mm(rms_norm(mm(u, wqa), q_norm, eps), wqb).reshape(T, H, nope + rd)
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
                          precision=HIGHEST)) * scale
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1)
        out.append(jnp.einsum("hqk,khd->qhd", p, kv[:, hs, nope:],
                              precision=HIGHEST))
    return mm(jnp.concatenate(out, 1).reshape(T, H * vd), wo)


def attention(w, i, u, m):
    r = m["rope_scaling"]
    inv = yarn_inv_freq(m["qk_rope_head_dim"], m["rope_theta"], r["factor"],
                        r["original_max_position_embeddings"],
                        r["beta_fast"], r["beta_slow"])
    return _attention(
        u, w[f"l{i}.wqa"], w[f"l{i}.q_norm"], w[f"l{i}.wqb"],
        w[f"l{i}.wkva"], w[f"l{i}.kv_norm"], w[f"l{i}.wkvb"],
        w[f"l{i}.wo"], inv, H=m["num_attention_heads"],
        nope=m["qk_nope_head_dim"], rd=m["qk_rope_head_dim"],
        vd=m["v_head_dim"], R=m["kv_lora_rank"], eps=m["rms_norm_eps"],
        scale=softmax_scale(m))


@jax.jit
def swiglu(x, w_gate, w_up, w_down):
    return mm(jax.nn.silu(mm(x, w_gate)) * mm(x, w_up), w_down)


@functools.partial(jax.jit, static_argnames=("K", "scale", "use_bias",
                                             "router_dtype"))
def _route(u, router, bias, forced_at, forced, *, K, scale, use_bias,
           router_dtype):
    if router_dtype is None:
        logits = mm(u, router)
    else:                           # the router's product in a lower type
        logits = jnp.matmul(u.astype(router_dtype),
                            jnp.asarray(router, router_dtype),
                            preferred_element_type=jnp.float32)
    sc = jax.nn.sigmoid(logits)
    sel = sc + f32(bias) if use_bias else sc
    top, order = jax.lax.top_k(sel, K + 1)
    picked = jnp.where(forced_at[:, None], forced, order[:, :K])
    g = jnp.take_along_axis(sc, picked, -1)
    gap = top[:, K - 1] - jnp.min(jnp.take_along_axis(sel, picked, -1), -1)
    return picked, g / (jnp.sum(g, -1, keepdims=True) + 1e-20) * scale, \
        top[:, K - 1] - top[:, K], jnp.maximum(gap, 0.0)


def route(w, i, u, m, forced=None):
    """(picked [T, K], their weights [T, K], margin [T], gap [T]). The
    margin is the room between the K-th and the (K+1)-th selection score:
    what a rounding upstream has before it changes which experts a token
    gets. ``forced`` = (at [T] bool, picks [T, K]): at those tokens the
    given experts are taken in place of the reference's own (their weights
    still from the reference's scores), and ``gap`` says how far under
    the reference's K-th selection score the lowest of them lies: 0 where
    they are the reference's own picks, the margin where the runner-up
    took the last place."""
    T, K = u.shape[0], m["num_experts_per_tok"]
    at, picks = forced if forced is not None else (
        np.zeros((T,), bool), np.zeros((T, K), np.int32))
    rdt = m.get("_router_dtype")
    return _route(u, w[f"l{i}.moe_router"], w[f"l{i}.moe_bias"],
                  jnp.asarray(at), jnp.asarray(picks, jnp.int32), K=K,
                  scale=float(m["routed_scaling_factor"]),
                  use_bias=m.get("_use_bias", True),
                  router_dtype=None if rdt is None else jnp.dtype(rdt).name)


@jax.jit
def _add_expert(out, picked, g, e, y):
    return out + jnp.sum(jnp.where(picked == e, g, 0.0), -1)[:, None] * y


def experts(w, i, u, m, forced=None):
    """Every expert on every token, masked by the routing; then the shared
    expert. Returns (out [T, D], margin [T], gap [T])."""
    picked, g, margin, gap = route(w, i, u, m, forced)
    out = jnp.zeros_like(u)
    for e in range(w[f"l{i}.moe_w_gate"].shape[0]):
        out = _add_expert(out, picked, g, e, swiglu(
            u, w[f"l{i}.moe_w_gate"][e], w[f"l{i}.moe_w_up"][e],
            w[f"l{i}.moe_w_down"][e]))
    if m["n_shared_experts"] and m.get("_use_shared", True):
        out = out + swiglu(u, w[f"l{i}.sh_w_gate"], w[f"l{i}.sh_w_up"],
                           w[f"l{i}.sh_w_down"])
    return out, margin, gap


def sinkhorn_knopp(mat, iters, eps):
    for _ in range(iters):
        mat = mat / (jnp.sum(mat, -1, keepdims=True) + eps)
        mat = mat / (jnp.sum(mat, -2, keepdims=True) + eps)
    return mat


@functools.partial(jax.jit, static_argnames=("eps", "lo", "hi", "iters",
                                             "hc_eps"))
def _mix_in(x, phi, a, b, norm_w, *, eps, lo, hi, iters, hc_eps):
    T, n, D = x.shape
    z = mm(rms_norm(x.reshape(T, n * D), None, eps), phi)
    a, b = f32(a), f32(b)
    pre = jax.nn.sigmoid(a[0] * z[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * z[:, n:2 * n] + b[n:2 * n])
    res = jnp.clip(a[2] * z[:, 2 * n:] + b[2 * n:], lo, hi)
    res = sinkhorn_knopp(jnp.exp(res).reshape(T, n, n), iters, hc_eps)
    u = jnp.einsum("tn,tnd->td", pre, x, precision=HIGHEST)
    return rms_norm(u, norm_w, eps), res, post


@jax.jit
def _mix_out(x, res, post, y):
    return jnp.einsum("tij,tjd->tid", res, x, precision=HIGHEST) \
        + post[:, :, None] * y[:, None, :]


def residual(w, i, which, x, m, sublayer):
    """x [T, n, D] -> x [T, n, D] around ``sublayer`` ([T, D] -> [T, D])."""
    u, res, post = _mix_in(
        x, w[f"l{i}.hc_{which}_phi"], w[f"l{i}.hc_{which}_alpha"],
        w[f"l{i}.hc_{which}_bias"], w[f"l{i}.{which}_norm"],
        eps=m["rms_norm_eps"], lo=float(m["mhc_h_res_clamp_min"]),
        hi=float(m["mhc_h_res_clamp_max"]),
        iters=m.get("_sinkhorn_iters", m["hc_sinkhorn_iters"]),
        hc_eps=m["hc_eps"])
    return _mix_out(x, res, post, sublayer(u))


def forward(weights, tokens, model, positions=None, forced=None):
    """Float32 logits of one sequence ``tokens`` [T] at ``positions``
    (all of them when None), [len(positions), V]; each routed layer's
    selection margin at those positions, [routed layers, len(positions)];
    and the gaps of the picks that were ``forced`` (see route()), same
    shape. ``model`` holds the published config.json keys; keys that
    start with ``_`` switch single terms off, for the tests that show the
    comparison has teeth. ``forced``: {routed layer's ordinal: (at [T],
    picks [T, K])}."""
    w = weights
    tokens = jnp.asarray(tokens)
    n = model["hc_mult"]
    x = jnp.repeat(f32(w["tok_emb"][tokens])[:, None, :], n, axis=1)
    pos = jnp.arange(tokens.shape[0]) if positions is None \
        else jnp.asarray(positions)
    margins, gaps = [], []
    for i in range(model["num_hidden_layers"]):
        x = residual(w, i, "attn", x, model,
                     lambda u: attention(w, i, u, model))
        if i < model["first_k_dense_replace"]:
            x = residual(w, i, "mlp", x, model, lambda u: swiglu(
                u, w[f"l{i}.w_gate"], w[f"l{i}.w_up"], w[f"l{i}.w_down"]))
        else:
            def moe(u):
                out, margin, gap = experts(
                    w, i, u, model, (forced or {}).get(len(margins)))
                margins.append(margin[pos])
                gaps.append(gap[pos])
                return out
            x = residual(w, i, "mlp", x, model, moe)
    h = rms_norm(jnp.sum(x, axis=1)[pos], w["final_norm"],
                 model["rms_norm_eps"])
    head = w["lm_head"]              # cast up 16k columns at a time
    logits = jnp.concatenate(
        [mm(h, head[:, c:c + 16384])
         for c in range(0, head.shape[1], 16384)], axis=-1)
    return logits, jnp.stack(margins), jnp.stack(gaps)
