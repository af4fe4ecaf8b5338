"""DeepSeek-V3's decoder in plain float32, as ONE CHIP'S SHARE of an
expert-parallel deployment: latent attention with YaRN rotary positions on
the plain residual path, and a group-limited sigmoid router over ALL the
routed experts of which this chip holds a run and computes that run's part
of the sum. One sequence, no batch, no cache, no absorbed form, no sort:
every held expert is applied to every token by a Python loop and masked.
Independent of paddle_tpu. Every product is taken at "highest" precision.

The layer, for a token x [D] at position t (ISSUE 31 writes it out; HF
modeling_deepseek_v3.py and arXiv:2412.19437 for what the config.json keys
leave open; the configuration file's ``assumed`` lists it):

    h = x + A(RMSNorm(x));   y = h + M(RMSNorm(h))
    attention A:  cq = RMSNorm(u Wqa); q = cq Wqb -> per head [q_nope | q_pe]
                  [c | k_pe] = u Wkva;  c = RMSNorm(c)
                  q_pe, k_pe = RoPE_yarn(de-interleaved pairs, t)
                  [k_nope | v] = c Wkvb per head
                  scores (q_nope.k_nope + q_pe.k_pe) * s, causal softmax
    router:       sc = sigmoid(u Wg) over all E;  sel = sc + b
                  a group (E / n_group experts) scores the sum of its two
                  largest sel; the topk_group best groups keep their sel,
                  the others' is set to 0; the K largest of that are picked
                  w = sc[picked] / (sum + 1e-20) * scale
    experts M:    E_shared(u) + sum over picked e HELD HERE of w_e E_e(u)

What the picked experts that are not held would add is left out: it is
another chip's to compute, and the partial sum is what goes on to the next
layer, here as in the program. ``model["experts_held"]`` = {"first",
"count", "of"} names the run; the router and its bias are ``of`` wide, the
expert tensors ``count``.

The attention, the rotary embedding, the SwiGLU and the blocked casts are
latent_moe_mhc.py's (the same mathematics: DeepseekV3Attention); weights
come as ``l{i}.<suffix>`` (from_stacked() reads the program's layout so).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from .latent_moe_mhc import (_add_expert, attention, f32, mm, rms_norm,
                             swiglu)


class _Experts:
    """Layer ``i`` of a stacked expert tensor [L, E, ...], sliced only
    when an expert is asked for: a whole layer's experts are never copied."""

    def __init__(self, stacked, i, cast):
        self.stacked, self.i, self.cast = stacked, i, cast

    def __getitem__(self, e):
        return self.cast(self.stacked[self.i, e])


class from_stacked:
    """The program's stacked layout (``lead.*`` [n_dense, ...] and
    ``blocks.*`` [L, ...]) read as ``l{i}.*``, a layer's tensor sliced out
    when it is asked for and not before, so that no second copy of the
    weights stands beside the first. With ``through`` (a dtype) every
    matrix but the router is rounded to that type on its way: the
    reference computed from weights of a lower precision, for the reading
    that sets the comparison's limits."""

    def __init__(self, weights, n_dense, through=None):
        self.weights, self.n_dense, self.through = weights, n_dense, through

    def _cast(self, name):
        if self.through is None or name.endswith(("norm", "_bias",
                                                  "moe_router")):
            return lambda x: x
        return lambda x: x.astype(self.through).astype(x.dtype)

    def __getitem__(self, key):
        if key in self.weights:
            return self._cast(key)(self.weights[key])
        i, suffix = key[1:].split(".", 1)
        i = int(i)
        name, j = (f"lead.{suffix}", i) if i < self.n_dense \
            else (f"blocks.{suffix}", i - self.n_dense)
        w = self.weights[name]
        return _Experts(w, j, self._cast(name)) if w.ndim >= 4 \
            else self._cast(name)(w[j])


def _kept(group_score, n):
    """[T, G] bool: each token's ``n`` best groups."""
    g = group_score.shape[-1]
    return jax.nn.one_hot(jax.lax.top_k(group_score, n)[1], g,
                          dtype=bool).any(axis=1)


@functools.partial(jax.jit, static_argnames=("K", "scale", "G", "KG"))
def _route(u, router, bias, forced_at, forced, *, K, scale, G, KG):
    sc = jax.nn.sigmoid(mm(u, router))
    sel = sc + f32(bias)
    T, E = sel.shape
    grouped = sel.reshape(T, G, E // G)
    group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], -1)     # [T, G]
    kept = _kept(group_score, KG)
    masked = jnp.where(kept[..., None], grouped, 0.0).reshape(T, E)
    top, order = jax.lax.top_k(masked, K + 1)
    margin = top[:, K - 1] - top[:, K]
    last_group = jnp.min(jnp.where(kept, group_score, jnp.inf), -1)
    if KG < G:      # and the room before another group would be kept
        margin = jnp.minimum(margin, last_group - jnp.max(
            jnp.where(kept, -jnp.inf, group_score), -1))
    picked = jnp.where(forced_at[:, None], forced, order[:, :K])
    # how far the picks lie from being the reference's own: their groups
    # under the last group kept, and their selection scores under the
    # K-th largest once their groups are among those kept
    theirs = jax.nn.one_hot(picked // (E // G), G, dtype=bool).any(axis=1)
    gap_g = jnp.max(jnp.where(theirs, last_group[:, None] - group_score,
                              0.0), -1)
    with_theirs = _kept(jnp.where(theirs, jnp.inf, group_score), KG)
    kth = jax.lax.top_k(jnp.where(with_theirs[..., None], grouped, 0.0)
                        .reshape(T, E), K)[0][:, K - 1]
    gap_e = kth - jnp.min(jnp.take_along_axis(sel, picked, -1), -1)
    g = jnp.take_along_axis(sc, picked, -1)
    return picked, g / (jnp.sum(g, -1, keepdims=True) + 1e-20) * scale, \
        margin, jnp.maximum(jnp.maximum(gap_g, gap_e), 0.0)


def route(w, i, u, m, forced=None):
    """(picked [T, K] over all the experts, their weights [T, K], margin
    [T], gap [T]). The margin is the room a rounding upstream has before
    it changes which experts a token gets: between the K-th and the
    (K+1)-th selection score, or between the last group kept and the best
    one dropped, whichever is less. ``forced`` = (at [T] bool, picks
    [T, K]): at those tokens the given experts are taken in place of the
    reference's own (their weights still from the reference's scores), and
    ``gap`` says how far from the reference's own choice they lie: 0 where
    they are its own picks; else the larger of how far a picked expert's
    group scores under the last group kept, and how far the lowest picked
    expert's selection score lies under the K-th largest among the groups
    kept once the picks' groups are."""
    T, K = u.shape[0], m["num_experts_per_tok"]
    at, picks = forced if forced is not None else (
        np.zeros((T,), bool), np.zeros((T, K), np.int32))
    return _route(u, w[f"l{i}.moe_router"], w[f"l{i}.moe_bias"],
                  jnp.asarray(at), jnp.asarray(picks, jnp.int32), K=K,
                  scale=float(m["routed_scaling_factor"]),
                  G=m["n_group"], KG=m["topk_group"])


def experts(w, i, u, m, forced=None):
    """The held experts on every token, masked by the routing over all
    the experts; then the shared expert. Returns (out [T, D], margin [T],
    gap [T], picked [T, K])."""
    picked, g, margin, gap = route(w, i, u, m, forced)
    held = m["experts_held"]
    out = jnp.zeros_like(u)
    for e in range(held["count"]):
        out = _add_expert(out, picked, g, held["first"] + e, swiglu(
            u, w[f"l{i}.moe_w_gate"][e], w[f"l{i}.moe_w_up"][e],
            w[f"l{i}.moe_w_down"][e]))
    if m["n_shared_experts"] and m.get("_use_shared", True):
        out = out + swiglu(u, w[f"l{i}.sh_w_gate"], w[f"l{i}.sh_w_up"],
                           w[f"l{i}.sh_w_down"])
    return out, margin, gap, picked


def layer(w, i, x, m, forced=None):
    """Layer ``i`` on one sequence x [T, D]: (y [T, D], margin, gap,
    picked), the last three None for a dense layer."""
    eps = m["rms_norm_eps"]
    h = x + attention(w, i, rms_norm(x, w[f"l{i}.attn_norm"], eps), m)
    u = rms_norm(h, w[f"l{i}.mlp_norm"], eps)
    if i < m["first_k_dense_replace"]:
        return h + swiglu(u, w[f"l{i}.w_gate"], w[f"l{i}.w_up"],
                          w[f"l{i}.w_down"]), None, None, None
    out, margin, gap, picked = experts(w, i, u, m, forced)
    return h + out, margin, gap, picked


def forward(weights, tokens, model, positions=None, forced=None):
    """Float32 logits of one sequence ``tokens`` [T] at ``positions`` (all
    of them when None), [len(positions), V] over the vocabulary's slice
    held here; each routed layer's selection margin at those positions,
    [routed layers, len(positions)]; and the gaps of the picks that were
    ``forced`` (see route()), same shape. ``model`` holds the published
    config.json keys and ``experts_held``; keys that start with ``_``
    switch single terms off, for the tests that show the comparison has
    teeth. ``forced``: {routed layer's ordinal: (at [T], picks [T, K])}."""
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
