"""AI21-Jamba2-3B's decoder (the Jamba family's, ``model_type`` ``jamba``,
dense: ``num_experts`` 1) in plain float32: Mamba-1 layers with Jamba's
three inner RMSNorms and, where ``i % attn_layer_period ==
attn_layer_offset``, grouped-query attention WITHOUT any position
embedding; a SwiGLU in every layer; tied embeddings. One sequence, no
batch, no cache, no buckets, no chunks: the state is carried by one
sequential ``lax.scan`` over the positions, every attention layer builds
its whole [T, T] scores and masks them. Independent of paddle_tpu. Every
product is taken at "highest" precision.

The layer, for a token x [D] at position t (ISSUE 39 writes it out from
HF's modeling_jamba.py as recalled; the configuration file's ``assumed``
lists what the catalog row's keys leave open); d_in = mamba_expand x D,
N = mamba_d_state, R = mamba_dt_rank, k = mamba_d_conv, eps = rms_norm_eps
in every RMSNorm:

    h = x + Mixer(RMSNorm_in(x));   y = h + W_down(silu(W_gate u) * W_up u),
                                    u = RMSNorm_ff(h)
    Mamba mixer:  [z, g] = W_in u  (each d_in wide)
                  c_t = silu(b_conv + sum_{j<k} w_conv[j] * z_{t-(k-1)+j})
                        (depthwise, causal, zeros before position 0)
                  [dt_r, B, C] = W_x c_t  (R | N | N), each through its own
                        RMSNorm (dt_layernorm, b_layernorm, c_layernorm)
                  dt = softplus(W_dt dt_r + b_dt);   A = -exp(A_log)
                  S_t = exp(dt_t * A) * S_{t-1} + (dt_t * c_t) * B_t,
                        S_{-1} = 0, S [N, d_in]
                  y_t = S_t . C_t + D * c_t;   out = W_out (y_t * silu(g_t))
    attention:    q = u Wq as [heads, hd]; k, v = u Wk, u Wv as [G, hd];
                  s_tj = q_t . k_j * hd^-0.5 for j <= t; softmax; Wo.
                  No rotation, no window, no bias.
    after the last layer RMSNorm_final; logits = that times the
    embedding's transpose.

Weights come as ``l{i}.<suffix>`` (from_stacked() reads the program's
layout so: ``full.*`` the attention layers, ``ssm.*`` the Mamba layers, in
the order of the layers; ``a_log`` lies [N, d_in] there, as the state).
Keys of the model that start with ``_`` switch single terms off, for the
readings that show the comparison has teeth: ``_inner_norms`` False leaves
the three inner norms out, ``_state_dtype`` keeps S in that type.
"""
import functools

import jax
import jax.numpy as jnp

from .latent_moe_mhc import HIGHEST, f32, mm, rms_norm, swiglu

HEADS_AT_A_TIME = 4      # attention's [heads, T, T] scores, in groups


def is_attention(model, i):
    return i % model["attn_layer_period"] == model["attn_layer_offset"]


def layer_names(model):
    """[(stack, index in it)] of every layer: ``full`` | ``ssm``."""
    seen, out = {}, []
    for i in range(model["num_hidden_layers"]):
        stack = "full" if is_attention(model, i) else "ssm"
        out.append((stack, seen.get(stack, 0)))
        seen[stack] = out[-1][1] + 1
    return out


class from_stacked:
    """The program's stacked layout read as ``l{i}.*``, a layer's tensor
    sliced out when it is asked for and not before. With ``through`` (a
    dtype) every matrix is rounded to that type on its way (the norms and
    the Mamba layers' biases, ``a_log`` and ``d`` are not): the reference
    computed from weights of a lower precision, for the reading that sets
    the comparison's limits."""

    KEEP = ("norm", "_b", "_bias", "a_log", ".d")

    def __init__(self, weights, model, through=None):
        self.weights, self.through = weights, through
        self.names = layer_names(model)

    def _cast(self, name, x):
        if self.through is None or name.endswith(self.KEEP):
            return x
        return x.astype(self.through).astype(x.dtype)

    def __getitem__(self, key):
        if key in self.weights:
            return self._cast(key, self.weights[key])
        i, suffix = key[1:].split(".", 1)
        stack, j = self.names[int(i)]
        name = f"{stack}.{suffix}"
        return self._cast(name, self.weights[name][j])


@functools.partial(jax.jit, static_argnames=("H", "G", "hd"))
def _attention(u, wq, wk, wv, wo, *, H, G, hd):
    T = u.shape[0]
    q = mm(u, wq).reshape(T, H, hd)
    k = mm(u, wk).reshape(T, G, hd)
    v = mm(u, wv).reshape(T, G, hd)
    seen = jnp.arange(T)[None] <= jnp.arange(T)[:, None]
    out, r = [], H // G         # r query heads share a key/value head
    for h in [h for g in range(G)
              for h in range(g * r, (g + 1) * r, HEADS_AT_A_TIME)]:
        g = h // r
        hs = slice(h, min(h + HEADS_AT_A_TIME, (g + 1) * r))
        s = jnp.einsum("qhd,kd->hqk", q[:, hs], k[:, g],
                       precision=HIGHEST) * hd ** -0.5
        s = jnp.where(seen, s, -jnp.inf)
        e = jnp.exp(s - jnp.max(s, -1, keepdims=True))
        out.append(jnp.einsum("hqk,kd->qhd",
                              e / jnp.sum(e, -1, keepdims=True), v[:, g],
                              precision=HIGHEST))
    return mm(jnp.concatenate(out, 1).reshape(T, H * hd), wo)


def attention(w, i, u, m):
    H = m["num_attention_heads"]
    return _attention(u, w[f"l{i}.wq"], w[f"l{i}.wk"], w[f"l{i}.wv"],
                      w[f"l{i}.wo"], H=H, G=m["num_key_value_heads"],
                      hd=m["hidden_size"] // H)


@functools.partial(jax.jit, static_argnames=("R", "N", "eps", "inner_norms",
                                             "state_dtype"))
def _mamba(u, w_in, conv_w, conv_b, w_x, dt_norm, b_norm, c_norm, w_dt,
           dt_bias, a_log, d, w_out, state0, tail0, *, R, N, eps,
           inner_norms, state_dtype):
    """One sequence u [T, D] through a Mamba mixer from the state
    ``state0`` [N, d_in] and the tail ``tail0`` [k - 1, d_in] (zeros at a
    sequence's start): (out [T, D], state after the last position, the
    last k - 1 inputs of the convolution)."""
    T, k = u.shape[0], conv_w.shape[0]
    z, g = jnp.split(mm(u, w_in), 2, axis=-1)
    full = jnp.concatenate([f32(tail0), z], axis=0)      # [T + k - 1, d_in]
    c = f32(conv_b)
    for j in range(k):
        c = c + full[j:j + T] * f32(conv_w)[j]
    c = jax.nn.silu(c)
    x = mm(c, w_x)
    norm = rms_norm if inner_norms else (lambda v, scale, eps: v)
    dt_r = norm(x[:, :R], dt_norm, eps)
    bm = norm(x[:, R:R + N], b_norm, eps)
    cm = norm(x[:, R + N:], c_norm, eps)
    dt = jax.nn.softplus(mm(dt_r, w_dt) + f32(dt_bias))   # [T, d_in]
    a = -jnp.exp(f32(a_log))                              # [N, d_in]

    def step(state, xs):
        dt_t, c_t, b_t, c_out = xs
        state = (jnp.exp(dt_t[None] * a) * f32(state)
                 + (dt_t * c_t)[None] * b_t[:, None]).astype(state_dtype)
        return state, jnp.sum(f32(state) * c_out[:, None], axis=0)

    state, y = jax.lax.scan(step, state0.astype(state_dtype),
                            (dt, c, bm, cm))
    y = y + f32(d) * c
    return mm(y * jax.nn.silu(g), w_out), f32(state), full[T:]


def mamba(w, i, u, m, carried=None):
    """Layer ``i``'s Mamba mixer on one sequence u [T, D]; ``carried``:
    (state, tail) another call left, where the sequence goes on from it.
    Returns (out, (state, tail))."""
    d_in = m["mamba_expand"] * m["hidden_size"]
    N, k = m["mamba_d_state"], m["mamba_d_conv"]
    if carried is None:
        carried = (jnp.zeros((N, d_in), jnp.float32),
                   jnp.zeros((k - 1, d_in), jnp.float32))
    p = [w[f"l{i}.{s}"] for s in (
        "w_in", "conv_w", "conv_b", "w_x", "dt_norm", "b_norm", "c_norm",
        "w_dt", "dt_bias", "a_log", "d", "w_out")]
    out, state, tail = _mamba(
        u, *p, *carried, R=m["mamba_dt_rank"], N=N,
        eps=m["rms_norm_eps"], inner_norms=m.get("_inner_norms", True),
        state_dtype=jnp.dtype(m.get("_state_dtype", "float32")))
    return out, (state, tail)


def layer(w, i, x, m, carried=None):
    """Layer ``i`` on one sequence x [T, D]: (y [T, D], what a Mamba layer
    carries on, None for an attention layer)."""
    eps = m["rms_norm_eps"]
    u = rms_norm(x, w[f"l{i}.attn_norm"], eps)
    if is_attention(m, i):
        mixed, carry = attention(w, i, u, m), None
    else:
        mixed, carry = mamba(w, i, u, m, carried)
    h = x + mixed
    return h + swiglu(rms_norm(h, w[f"l{i}.mlp_norm"], eps),
                      w[f"l{i}.w_gate"], w[f"l{i}.w_up"],
                      w[f"l{i}.w_down"]), carry


def forward(weights, tokens, model, positions=None, carried=None,
            return_carried=False):
    """Float32 logits of one sequence ``tokens`` [T] at ``positions`` (all
    of them when None), [len(positions), V]. ``carried``: {layer: (state,
    tail)} that the Mamba layers START from instead of zeros (what
    another sequence left: the reading of a state that was not reset; the
    attention layers carry nothing, so it is no continuation);
    ``return_carried``: also what this sequence leaves."""
    w = weights
    tokens = jnp.asarray(tokens)
    x = f32(w["tok_emb"][tokens])
    pos = jnp.arange(tokens.shape[0]) if positions is None \
        else jnp.asarray(positions)
    left = {}
    for i in range(model["num_hidden_layers"]):
        x, carry = layer(w, i, x, model, (carried or {}).get(i))
        if carry is not None:
            left[i] = carry
    h = rms_norm(x[pos], w["final_norm"], model["rms_norm_eps"])
    emb = w["tok_emb"]               # tied; cast up 16k rows at a time
    logits = jnp.concatenate(
        [jnp.matmul(h, f32(emb[r:r + 16384]).T, precision=HIGHEST)
         for r in range(0, emb.shape[0], 16384)], axis=-1)
    return (logits, left) if return_carried else logits
