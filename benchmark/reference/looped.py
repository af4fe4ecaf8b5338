"""Ouro-2.6B's decoder (``model_type`` ``ouro``: a looped language model,
arXiv:2510.25741) in plain float32: ONE stack of dense layers run
``total_ut_steps`` times, the same weights in every pass. One sequence, no
batch, no cache, no buckets, no kernel: a Python loop over the passes and
the layers, every layer building its whole [heads, T, T] scores and masking
them. Independent of paddle_tpu. Every product is taken at "highest"
precision.

The equations (ISSUE 43 writes them out from HF's modeling_ouro.py and the
paper as recalled; the configuration file's ``assumed`` lists what the
catalog row's keys leave open). D = hidden_size, H heads of hd = head_dim
(num_key_value_heads of them for keys and values), eps = rms_norm_eps in
every RMSNorm, no bias in any projection:

    layer j on x:   a = Attn(N1(x));  h = x + N2(a)
                    u = N3(h);  m = W_down(silu(W_gate u) * W_up u)
                    y = h + N4(m)
                    four RMSNorms a layer (HF: input_layernorm,
                    input_layernorm_2, post_attention_layernorm,
                    post_attention_layernorm_2): a norm on each side of
                    both sublayers
    Attn:           q, k, v = W_q u, W_k u, W_v u as heads of hd; q and k
                    rotated over the whole head, pairs (first half, second
                    half), angle position x rope_theta^(-2i/hd);
                    s_tj = q_t . k_j x hd^-0.5 for j <= t; softmax; W_o
    the model:      h_0 = Emb[tokens]
                    for s in 0 .. T-1:  z = h_s
                                        for j in 0 .. L-1: z = Layer_j(z)
                                        h_{s+1} = N_f(z)
                                        g_s = w_g . h_{s+1} + b_g
                    logits = W_head h_T

The ONE final norm N_f closes every pass and its output is what the next
pass starts from; layer j of pass s attends the keys and values that layer
j of pass s made at the earlier positions (a cache would hold them at
index s x L + j; there is none here). g_s is the exit gate: at
early_exit_threshold 1 the exit distribution built from sigmoid(g_s)
reaches the threshold at the last pass alone, so every pass runs, the head
reads the last and no logit depends on a gate; ``forward`` returns them
when asked.

Weights come as ``l{j}.<suffix>`` (from_stacked() reads the program's
stacked ``blocks.*`` so). Keys of the model that start with ``_`` switch
single terms off, for the readings that show the comparison has teeth:
``_post_norms`` False leaves N2 and N4 out; ``_norm_every_pass`` False
applies N_f after the last pass alone. Three passes for four is the model
with ``total_ut_steps`` 3. ``_stream_dtype`` rounds what a layer hands the
next to that type: the reference at a served model's precision in ONE
place of the dozen a layer has, for the reading that says what rounding
alone costs at this depth. ``_round_dtype`` rounds to that type at EVERY
place a program that computes in it does (the output of each norm, product,
rotation and sum; the softmax's weights before they meet the values; the
final norm; sums inside a product stay float32): the reading that says
whether what the served model differs by IS its precision.
"""
import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def f32(x):
    return jnp.asarray(x, jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * f32(w)


def mm(a, b):
    return jnp.matmul(a, f32(b), precision=HIGHEST)


class from_stacked:
    """The program's stacked layout read as ``l{j}.*``, a layer's tensor
    sliced out when it is asked for and not before. With ``through`` (a
    dtype) every matrix is rounded to that type on its way (the norms are
    not): the reference computed from weights of a lower precision, for
    the reading that sets the comparison's limit."""

    def __init__(self, weights, through=None):
        self.weights, self.through = weights, through

    def _cast(self, name, x):
        if self.through is None or name.endswith("norm"):
            return x
        return x.astype(self.through).astype(x.dtype)

    def __getitem__(self, key):
        if key in self.weights:
            return self._cast(key, self.weights[key])
        j, suffix = key[1:].split(".", 1)
        name = "blocks." + suffix
        return self._cast(name, self.weights[name][int(j)])


def rope(x, base):
    """x [T, heads, hd]: pairs (first half, second half) rotated by
    position x base^(-2i/hd)."""
    d = x.shape[-1]
    inv = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def rounded(x, dtype):
    """x through ``dtype`` and back (None: x as it is)."""
    return x if dtype is None else f32(x.astype(dtype))


@functools.partial(jax.jit, static_argnames=("H", "G", "hd", "base", "eps",
                                             "post_norms", "rnd"))
def _layer(x, n1, n2, n3, n4, wq, wk, wv, wo, w_gate, w_up, w_down, *,
           H, G, hd, base, eps, post_norms, rnd=None):
    T = x.shape[0]

    def r(y):
        return rounded(y, rnd)

    def norm(y, w):
        return r(rms_norm(y, w, eps))

    u = norm(x, n1)
    q = r(rope(r(mm(u, wq)).reshape(T, H, hd), base))
    k = jnp.repeat(r(rope(r(mm(u, wk)).reshape(T, G, hd), base)), H // G,
                   axis=1)
    v = jnp.repeat(r(mm(u, wv)).reshape(T, G, hd), H // G, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * hd ** -0.5
    s = jnp.where(jnp.arange(T)[None] <= jnp.arange(T)[:, None], s,
                  -jnp.inf)
    a = r(mm(r(jnp.einsum("hqk,khd->qhd", r(jax.nn.softmax(s, -1)), v,
                          precision=HIGHEST)).reshape(T, H * hd), wo))
    h = r(x + (norm(a, n2) if post_norms else a))
    u = norm(h, n3)
    m = r(mm(r(r(jax.nn.silu(r(mm(u, w_gate)))) * r(mm(u, w_up))), w_down))
    return r(h + (norm(m, n4) if post_norms else m)), \
        k[:, ::H // G], v[:, ::H // G]


def layer(w, j, x, m):
    """Layer ``j`` on one sequence x [T, D]: (y [T, D], the keys it
    attended, rotated, and the values, [T, kv heads, hd] each)."""
    p = [w[f"l{j}.{s}"] for s in (
        "attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm", "wq",
        "wk", "wv", "wo", "w_gate", "w_up", "w_down")]
    return _layer(x, *p, H=m["num_attention_heads"],
                  G=m["num_key_value_heads"], hd=m["head_dim"],
                  base=float(m["rope_theta"]), eps=m["rms_norm_eps"],
                  post_norms=m.get("_post_norms", True),
                  rnd=m.get("_round_dtype"))


def forward(weights, tokens, model, positions=None, return_gates=False,
            return_entries=False):
    """Float32 logits of one sequence ``tokens`` [T] at ``positions`` (all
    of them when None), [len(positions), V]; ``return_gates``: also the
    exit gate of every pass there, [passes, len(positions)];
    ``return_entries``: also the keys and values every layer of every pass
    attended, [(k, v)] in the order a cache would hold them, pass ``s``
    layer ``j`` at ``s x L + j``."""
    w, eps = weights, model["rms_norm_eps"]
    passes = model["total_ut_steps"]
    tokens = jnp.asarray(tokens)
    h = f32(w["tok_emb"][tokens])
    pos = jnp.arange(tokens.shape[0]) if positions is None \
        else jnp.asarray(positions)
    gates, entries = [], []
    for s in range(passes):
        for j in range(model["num_hidden_layers"]):
            h, k, v = layer(w, j, h, model)
            if return_entries:
                entries.append((k, v))
            if "_stream_dtype" in model:
                h = f32(h.astype(model["_stream_dtype"]))
        if model.get("_norm_every_pass", True) or s == passes - 1:
            h = rounded(rms_norm(h, w["final_norm"], eps),
                        model.get("_round_dtype"))
        gates.append(mm(h[pos], w["exit_gate.w"]) + f32(w["exit_gate.b"]))
    head = w["lm_head"]              # [D, V]; cast up 16k columns at a time
    logits = jnp.concatenate(
        [mm(h[pos], head[:, c:c + 16384])
         for c in range(0, head.shape[1], 16384)], axis=-1)
    out = (logits,) + ((jnp.stack(gates),) if return_gates else ()) \
        + ((entries,) if return_entries else ())
    return out if len(out) > 1 else logits
