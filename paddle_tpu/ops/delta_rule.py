"""The gated delta rule (Gated Delta Networks, arXiv:2412.06464; its
chunked form: arXiv:2406.06484) as functions of a window of positions or
of one step, the state handed in and handed back, as ops/ssm.py has the
selective state-space mixer.

A head keeps a MATRIX ``S`` [dk, dv]. For a position's query and key
``q_t``, ``k_t`` [dk] (L2-normed a head, the query times ``dk^-0.5``), its
value ``v_t`` [dv], a decay ``alpha_t = exp(g_t)`` in (0, 1) and a write
strength ``beta_t`` in (0, BETA_MAX), each a scalar a head::

    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
    o_t = S_t^T q_t

The update is NOT diagonal: the correction term READS the state (``S^T
k``), so a prompt cannot be scanned channel by channel. ``chunk_rule``
computes a window ``CHUNK`` positions at a time as matrix products. With
``gamma_i = g_1 + .. + g_i`` inside the chunk and ``S_0`` the state the
chunk starts from::

    A[i, j] = beta_i exp(gamma_i - gamma_j) (k_i . k_j)   (j < i, else 0)
    U = (I + A)^-1 diag(beta) (V - (exp(gamma) * K) S_0)
    O = (exp(gamma) * Q) S_0 + (M * (Q K^T)) U,
        M[i, j] = exp(gamma_i - gamma_j)                  (j <= i, else 0)
    S_C = exp(gamma_C) S_0 + (exp(gamma_C - gamma) * K)^T U

``(I + A)^-1`` does not depend on ``S_0``: every chunk's is computed
before the loop that carries ``S``, which has one step a chunk and holds
four products. Every exponent is <= 0. A position at or past ``lens`` gets
``beta = 0``, ``g = 0``: it writes nothing and decays nothing.

What the mixer is given is ``z`` [..., C + 2H]: the C = H (2 dk + dv)
channels ``[q | k | v]`` before their convolution (ops/ssm.py's, causal,
depthwise, without a bias, then SiLU) and behind them the H decay and the H
write projections ``[a | b]``, which are not convolved. What a sequence
carries from one call to the next is ``S`` [H, dk, dv] float32 (whatever the
model's type) and the TAIL, the convolution's last ``k - 1`` inputs [k - 1,
C]: ONE entry a sequence, as ops/ssm.py's, and as little protected by any
length mask.

Parameters ``p`` of one layer, by slot: ``ConvW`` [k, C], ``ALog`` [H] and
``DtBias`` [H] float32, ``GNorm`` [dv] (the mixer's output norm: the sizes
are read off these: H from ``ALog``, dv from ``GNorm``, dk from C).
"""
import jax
import jax.numpy as jnp

from . import ssm

__all__ = ["window", "step", "step_in_kernel", "gates", "chunk_rule",
           "rule_step", "CHUNK", "BETA_MAX"]

_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST

CHUNK = 64          # positions a chunk of the rule: one triangular system
# the write strength is ``BETA_MAX * sigmoid(.)``: 2 lets ``1 - beta`` reach
# (-1, 1), a negative eigenvalue of the state's transition
# (``linear_allow_neg_eigval``); a model published without it is not built
BETA_MAX = 2.0
L2_EPS = 1e-6


def _sizes(p):
    """(heads, dk, dv) of a layer, off its parameters."""
    h, dv = p["ALog"].shape[-1], p["GNorm"].shape[-1]
    return h, (p["ConvW"].shape[-1] // h - dv) // 2, dv


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                             + L2_EPS)


def gates(p, ab):
    """The log decay and the write strength of every position and head,
    float32: ab [..., 2H] (``[a | b]``) -> (g [..., H] <= 0, beta [...,
    H])."""
    a, b = jnp.split(ab.astype(_F32), 2, axis=-1)
    g = -jnp.exp(p["ALog"].astype(_F32)) \
        * jax.nn.softplus(a + p["DtBias"].astype(_F32))
    return g, BETA_MAX * jax.nn.sigmoid(b)


def _heads(p, c):
    """The convolved channels c [..., C] as (q, k [..., H, dk], v [..., H,
    dv]) float32: queries and keys L2-normed a head, the query scaled."""
    h, dk, dv = _sizes(p)
    c = c.astype(_F32)
    q = _l2(c[..., :h * dk].reshape(c.shape[:-1] + (h, dk))) * dk ** -0.5
    k = _l2(c[..., h * dk:2 * h * dk].reshape(c.shape[:-1] + (h, dk)))
    return q, k, c[..., 2 * h * dk:].reshape(c.shape[:-1] + (h, dv))


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower triangular a [..., n, n], n a
    power of two: the inverses of the diagonal blocks of 1, 2, 4, ..
    rows, each level from the one before (``[[P, 0], [R, Q]]^-1 = [[P^-1,
    0], [-Q^-1 R P^-1, Q^-1]]``): log2 n levels of batched products and
    no loop over rows. A sum of powers of ``a`` would be as many products
    and cancels catastrophically where ``beta k_i . k_j`` nears 2."""
    n = a.shape[-1]
    lead = a.shape[:-2]
    inv = jnp.ones(lead + (n, 1, 1), a.dtype)
    s = 1
    while s < n:
        blocks = a.reshape(lead + (n // (2 * s), 2, s, n // (2 * s), 2, s))
        m = jnp.arange(n // (2 * s))
        r = jnp.moveaxis(blocks[..., m, 1, :, m, 0, :], 0, -3)
        pq = inv.reshape(lead + (n // (2 * s), 2, s, s))
        pi, qi = pq[..., 0, :, :], pq[..., 1, :, :]
        low = -jnp.einsum("...ij,...jk,...kl->...il", qi, r, pi,
                          precision=_HI)
        zero = jnp.zeros_like(pi)
        inv = jnp.concatenate(
            [jnp.concatenate([pi, zero], axis=-1),
             jnp.concatenate([low, qi], axis=-1)], axis=-2)
        s *= 2
    return inv[..., 0, :, :]


def chunk_rule(q, k, v, g, beta, state0, chunk=CHUNK):
    """The rule over a window, ``chunk`` positions at a time (a power of
    two; a window it does not divide is padded with positions that move
    nothing). q, k [B, T, H, dk]; v [B, T, H, dv]; g, beta [B, T, H]
    (``beta = 0, g = 0`` at padding); state0 [B, H, dk, dv]; all float32
    -> (o [B, T, H, dv], the state after the last position)."""
    b, t, h, dk = q.shape
    n = -(-t // chunk)

    def chunks(x):      # [B, T, H, ...] -> [n, B, H, chunk, ...]
        x = jnp.pad(x, [(0, 0), (0, n * chunk - t)]
                    + [(0, 0)] * (x.ndim - 2))
        x = x.reshape((b, n, chunk) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 1), 2, 0)

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    gamma = jnp.cumsum(g, axis=-1)                       # [n, B, H, C]
    diff = gamma[..., :, None] - gamma[..., None, :]     # gamma_i - gamma_j
    i, j = jnp.arange(chunk)[:, None], jnp.arange(chunk)[None]
    m = jnp.exp(jnp.where(j <= i, diff, -jnp.inf))
    a = jnp.where(j < i, m, 0.0) * beta[..., None] * jnp.einsum(
        "...id,...jd->...ij", k, k, preferred_element_type=_F32)
    solve = _unit_lower_inverse(a) * beta[..., None, :]  # (I+A)^-1 diag(beta)
    qk = m * jnp.einsum("...id,...jd->...ij", q, k,
                        preferred_element_type=_F32)
    decay = jnp.exp(gamma)[..., None]
    k_in, q_in = decay * k, decay * q
    k_out = jnp.exp(gamma[..., -1:] - gamma)[..., None] * k
    last = jnp.exp(gamma[..., -1])[..., None, None]

    def body(state, xs):
        k_in, q_in, v, solve, qk, k_out, last = xs
        u = jnp.matmul(solve, v - jnp.matmul(k_in, state), precision=_HI)
        o = jnp.matmul(q_in, state) + jnp.matmul(qk, u)
        return last * state + jnp.einsum("...cd,...ce->...de", k_out, u), o

    state, o = jax.lax.scan(body, state0.astype(_F32),
                            (k_in, q_in, v, solve, qk, k_out, last))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3)   # [B, n, chunk, H, dv]
    return o.reshape((b, n * chunk) + o.shape[3:])[:, :t], state


def rule_step(q, k, v, g, beta, state):
    """One position of every row: q, k [B, H, dk]; v [B, H, dv]; g, beta
    [B, H]; state [B, H, dk, dv] float32 -> (o [B, H, dv], the state after
    it)."""
    state = jnp.exp(g)[..., None, None] * state
    u = beta[..., None] * (v - jnp.sum(state * k[..., None], axis=-2))
    state = state + k[..., None] * u[..., None, :]
    return jnp.sum(state * q[..., None], axis=-2), state


def _split(p, z):
    c = p["ConvW"].shape[-1]
    return z[..., :c], z[..., c:]


def window(p, z, state0, tail0, lens, eps):
    """A window of positions through the mixer's recurrent part. z [B, T,
    C + 2H]; state0 [B, H, dk, dv] float32 and tail0 [B, k - 1, C]: what
    the rows carried in (zeros for a row that starts here); lens [B]: the
    rows' real positions, the rest of T is padding and moves nothing.
    Returns (o [B, T, H * dv] in z's type, state after position ``lens -
    1``, tail [B, k - 1, C]). ``eps`` is the block's, for its norms: the
    rule has its own (L2_EPS)."""
    x, ab = _split(p, z)
    with jax.named_scope("delta/conv"):
        c, tail = ssm.conv_window(
            x, tail0, p["ConvW"], jnp.zeros((x.shape[-1],), _F32), lens)
    with jax.named_scope("delta/chunk"):
        g, beta = gates(p, ab)
        real = (jnp.arange(z.shape[1], dtype=jnp.int32)[None]
                < lens[:, None])[:, :, None]
        o, state = chunk_rule(*_heads(p, c), jnp.where(real, g, 0.0),
                              jnp.where(real, beta, 0.0), state0)
    return o.reshape(o.shape[:2] + (-1,)).astype(z.dtype), state, tail


def step_in_kernel(pool_shape, pool_dtype):
    """Whether ``step`` runs a Pallas kernel over a pool of this shape and
    type (ops/ssm.py's has one): never, it is jax.numpy everywhere."""
    return False


def step(p, z, s_pool, layer, held, tail0, eps):
    """A decode step of one layer IN THE ENTRIES' ORDER against the state
    pool itself, as ops/ssm.py's: z [n, C + 2H], an entry's input; s_pool
    [L, n, H, dk, dv]; held [n] bool; tail0 [n, (k - 1) * C], flat as the
    tail pool stores it -> (o [n, H * dv] in z's type, the pool with layer
    ``layer`` written, tail [n, (k - 1) * C]). The layer's entries are
    sliced out of the pool, stepped (``rule_step``: ``window`` for one
    position) and set back, an entry that is not ``held`` as it was."""
    state0 = s_pool[layer]
    x, ab = _split(p, z)
    n = z.shape[0]
    with jax.named_scope("delta/conv"):
        c, tail = ssm.conv_step(
            x, tail0.reshape(n, -1, x.shape[-1]), p["ConvW"],
            jnp.zeros((x.shape[-1],), _F32))
    with jax.named_scope("delta/step"):
        o, state = rule_step(*_heads(p, c), *gates(p, ab),
                             state0.astype(_F32))
        o = o.reshape(n, -1).astype(z.dtype)
        s_pool = s_pool.at[layer].set(jnp.where(
            held[:, None, None, None], state.astype(s_pool.dtype), state0))
    return o, s_pool, tail.reshape(n, -1)
