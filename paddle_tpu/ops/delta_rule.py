"""The gated delta rule (Gated Delta Networks, arXiv:2412.06464; its
chunked form: arXiv:2406.06484) as functions of a window of positions or
of one step, the state handed in and handed back, as ops/ssm.py has the
selective state-space mixer.

A head keeps a MATRIX ``S`` [dk, dv]. For a position's query and key
``q_t``, ``k_t`` [dk] (L2-normed a head, the query times ``dk^-0.5``), its
value ``v_t`` [dv], a decay ``alpha_t = exp(g_t)`` in (0, 1) and a write
strength ``beta_t`` in (0, ``beta_max``), each a scalar a head::

    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
    o_t = S_t^T q_t

WHAT A KIND OF DELTA-RULE LAYER CARRIES beyond its sizes is three data,
keywords of ``window`` / ``step`` (a model's ``attn_kinds`` dict hands them
over as its ``rule``; the defaults are Gated Delta Networks' and give
Olmo-Hybrid's programs letter for letter): ``beta_max``, the write
strength's ceiling (2 lets ``1 - beta`` reach (-1, 1), a negative eigenvalue
of the state's transition, ``linear_allow_neg_eigval``; 1 is a plain
sigmoid); ``floor``, the gate's form (None: ``g = -exp(A_log) softplus(a +
dt_bias)``, unbounded below; a number < 0: the lower-bound gate ``g = floor
* sigmoid(exp(A_log) (a + dt_bias))`` in (floor, 0), Kimi Delta Attention's
safe gate, arXiv:2510.26692); ``scope``, the name its spans go under. And
the decay is A HEAD's or A CHANNEL's, read off the width of what the mixer
is given: with ``a`` [H dk] wide ``alpha_t`` is a vector over the key
channels and scales the state's ROWS, ``S_t = (I - beta k k^T) diag(alpha_t)
S_{t-1} + beta k v^T`` (the same update with ``alpha_t S`` read as
``diag(alpha_t) S``).

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

With a decay a CHANNEL the mask sits inside the contraction, ``sum_c x_i[c]
exp(gamma_i[c] - gamma_j[c]) k_j[c]``, and the factored form ``(x_i
exp(gamma_i)) . (k_j exp(-gamma_j))`` raises ``exp`` to a positive power
(64 positions at -5 each: ``exp(320)``). ``_channel_terms`` cuts a chunk
into RUNS of ``RUN`` positions and takes the decays relative to the runs'
ends: a pair inside one run elementwise over the channels (``exp(gamma_i -
gamma_j)``, j <= i), a pair of two runs I > J as ``(x_i exp(gamma_i -
start_I)) . (k_j exp(end_J - gamma_j)) exp(start_I - end_J)``, three
factors with exponents <= 0 BY CONSTRUCTION, whatever the gate's floor: no
``exp`` of a positive number is taken anywhere in this module. The same
``(I + A)^-1``, loop and four products follow.

What the mixer is given is ``z`` [..., C + A + H]: the C = H (2 dk + dv)
channels ``[q | k | v]`` before their convolution (ops/ssm.py's, causal,
depthwise, without a bias, then SiLU) and behind them the decay's (A = H
wide, or H dk: a channel's) and the H write projections ``[a | b]``, which
are not convolved. What a sequence carries from one call to the next is
``S`` [H, dk, dv] float32 (whatever the model's type) and the TAIL, the
convolution's last ``k - 1`` inputs [k - 1, C]: ONE entry a sequence, as
ops/ssm.py's, and as little protected by any length mask.

A DECODE STEP runs against the state pool itself (``step``): a layer's
entries are stepped where they lie, in the entries' order. Where the gate
passes (``step_in_kernel``: a backend that runs Pallas kernels and a
float32 pool ``[L, n, H, dk, dv]`` whose states are whole tiles, ``dk % 8
== 0 and dv % 128 == 0``) ONE kernel, ``delta_state_step``
(``step_entries``), passes over the layer's slab once, the pool aliased to
the result: a block of entries x heads is read, decayed, reduced against
its key and its query, corrected and written back where it was read, a
decay a head or a channel alike (the broadcast differs). Everywhere else
the same equations (``rule_step``) in jax.numpy over a slice of the pool,
which XLA makes three reads and a write of the slab.

Parameters ``p`` of one layer, by slot: ``ConvW`` [k, C], ``ALog`` [H] and
``DtBias`` [H] (a decay a channel: [H dk]) float32, ``GNorm`` [dv] (the
mixer's output norm: the sizes are read off these: H from ``ALog``, dv
from ``GNorm``, dk from C).
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_attention as pa
from . import ssm

__all__ = ["window", "step", "step_entries", "step_in_kernel", "gates",
           "chunk_rule", "rule_step", "CHUNK", "RUN", "BETA_MAX"]

_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST

CHUNK = 64          # positions a chunk of the rule: one triangular system
RUN = 16            # positions a run of a chunk whose decay is a channel's:
                    # pairs inside one are taken channel by channel
# the write strength is ``beta_max * sigmoid(.)``, and this the ceiling of a
# kind that names none: 2 lets ``1 - beta`` reach (-1, 1), a negative
# eigenvalue of the state's transition (``linear_allow_neg_eigval``)
BETA_MAX = 2.0
L2_EPS = 1e-6


def _sizes(p):
    """(heads, dk, dv) of a layer, off its parameters."""
    h, dv = p["ALog"].shape[-1], p["GNorm"].shape[-1]
    return h, (p["ConvW"].shape[-1] // h - dv) // 2, dv


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                             + L2_EPS)


def gates(p, ab, beta_max=None, floor=None):
    """The log decay and the write strength of every position and head,
    float32: ab [..., A + H] (``[a | b]``) -> (g <= 0, beta [..., H] in (0,
    ``beta_max``)). A = H: a decay a head, g [..., H]; A = H dk: a decay a
    channel, g [..., H, dk]. ``floor`` None: ``g = -exp(A_log) softplus(a +
    dt_bias)``; a number: the lower-bound gate, ``g = floor * sigmoid(exp(
    A_log) (a + dt_bias))`` in (floor, 0). ``beta_max`` None: BETA_MAX."""
    h = p["ALog"].shape[-1]
    ab = ab.astype(_F32)
    rate, bias = (lambda: jnp.exp(p["ALog"].astype(_F32))), \
        (lambda: p["DtBias"].astype(_F32))
    if ab.shape[-1] == 2 * h:
        a, b = jnp.split(ab, 2, axis=-1)
        x = lambda: a + bias()
    else:
        a, b, head = ab[..., :-h], ab[..., -h:], rate
        rate = lambda: head()[..., None]
        x = lambda: (a + bias()).reshape(a.shape[:-1] + (h, -1))
    # taken where the expression reads them: a decay a head with no floor is
    # the program it was, operation for operation
    g = -rate() * jax.nn.softplus(x()) if floor is None \
        else floor * jax.nn.sigmoid(rate() * x())
    return g, (BETA_MAX if beta_max is None else beta_max) \
        * jax.nn.sigmoid(b)


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


def _head_terms(q, k, g, beta):
    """What a chunk's loop is given, with a decay a head: q, k [..., C,
    dk]; g, beta [..., C] -> (k_in, q_in [..., C, dk], ``(I + A)^-1
    diag(beta)`` and the masked ``Q K^T`` [..., C, C], k_out [..., C, dk],
    the chunk's whole decay [..., 1, 1])."""
    chunk = g.shape[-1]
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
    return k_in, q_in, solve, qk, k_out, last


def _channel_terms(q, k, g, beta, run=RUN):
    """The same with a decay a CHANNEL, g [..., C, dk] (the chunk's whole
    decay [..., dk, 1]: the state's rows): the chunk in runs of ``run``
    positions, every exponent <= 0 by construction (the module's
    docstring)."""
    lead, (c, dk) = g.shape[:-2], g.shape[-2:]
    m = c // run
    runs = lambda x: x.reshape(lead + (m, run, dk))
    # a difference of two sums of gates is <= 0 but for their rounding:
    # held to it, so that no exponent is positive whatever the sums' order
    decay = lambda x: jnp.exp(jnp.minimum(x, 0.0))
    gl = jnp.cumsum(runs(g), axis=-2)       # from its run's start, <= 0
    end = jnp.cumsum(gl[..., -1, :], axis=-2)       # [.., m, dk] a run's end
    start = end - gl[..., -1, :]                    # and its start
    i, j = jnp.arange(run)[:, None], jnp.arange(run)[None]
    inside = decay(jnp.where(
        (j <= i)[..., None], gl[..., :, None, :] - gl[..., None, :, :],
        -jnp.inf))                                  # [.., m, run, run, dk]
    big, small = jnp.arange(m)[:, None], jnp.arange(m)[None]
    between = decay(jnp.where(
        (small < big)[..., None],
        start[..., :, None, :] - end[..., None, :, :], -jnp.inf))
    kr = runs(k)
    k_end = kr * decay(gl[..., -1:, :] - gl)        # at its run's end

    def pairs(x):
        """sum_c x_i[c] exp(gamma_i[c] - gamma_j[c]) k_j[c], j <= i."""
        xr = runs(x)
        same = jnp.sum(xr[..., :, None, :] * kr[..., None, :, :] * inside,
                       axis=-1)                     # [.., m, run, run]
        x_in = (xr * decay(gl))[..., :, :, None, :] \
            * between[..., :, None, :, :]           # [.., m, run, m, dk]
        cross = jnp.einsum("...IiJc,...Jjc->...IiJj", x_in, k_end,
                           preferred_element_type=_F32)
        on = (big == small)[:, None, :, None]
        return (cross + jnp.where(on, same[..., :, :, None, :], 0.0)
                ).reshape(lead + (c, c))

    i, j = jnp.arange(c)[:, None], jnp.arange(c)[None]
    a = jnp.where(j < i, pairs(k), 0.0) * beta[..., None]
    solve = _unit_lower_inverse(a) * beta[..., None, :]
    gamma = (gl + start[..., None, :]).reshape(g.shape)
    since = decay(gamma)                    # from the chunk's start
    return (since * k, since * q, solve, pairs(q),
            decay(gamma[..., -1:, :] - gamma) * k,
            decay(gamma[..., -1, :])[..., None])


def chunk_rule(q, k, v, g, beta, state0, chunk=CHUNK):
    """The rule over a window, ``chunk`` positions at a time (a power of
    two; a window it does not divide is padded with positions that move
    nothing). q, k [B, T, H, dk]; v [B, T, H, dv]; beta [B, T, H] and g
    [B, T, H] (a decay a head) or [B, T, H, dk] (a channel) (``beta = 0, g =
    0`` at padding); state0 [B, H, dk, dv]; all float32 -> (o [B, T, H,
    dv], the state after the last position)."""
    b, t, h, dk = q.shape
    n = -(-t // chunk)

    def chunks(x):      # [B, T, H, ...] -> [n, B, H, chunk, ...]
        x = jnp.pad(x, [(0, 0), (0, n * chunk - t)]
                    + [(0, 0)] * (x.ndim - 2))
        x = x.reshape((b, n, chunk) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 1), 2, 0)

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    k_in, q_in, solve, qk, k_out, last = (
        _head_terms if g.ndim == beta.ndim else _channel_terms)(
            q, k, g, beta)

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
    """One position of every row: q, k [B, H, dk]; v [B, H, dv]; beta [B,
    H]; g [B, H], or [B, H, dk] where the decay is a channel's and scales
    the state's ROWS; state [B, H, dk, dv] float32 -> (o [B, H, dv], the
    state after it)."""
    state = jnp.exp(g)[(...,) + (None,) * (state.ndim - g.ndim)] * state
    u = beta[..., None] * (v - jnp.sum(state * k[..., None], axis=-2))
    state = state + k[..., None] * u[..., None, :]
    return jnp.sum(state * q[..., None], axis=-2), state


def _split(p, z):
    c = p["ConvW"].shape[-1]
    return z[..., :c], z[..., c:]


def window(p, z, state0, tail0, lens, eps, scope="delta", **gate):
    """A window of positions through the mixer's recurrent part. z [B, T,
    C + A + H] (A = H, or H dk where the decay is a channel's); state0 [B,
    H, dk, dv] float32 and tail0 [B, k - 1, C]: what the rows carried in
    (zeros for a row that starts here); lens [B]: the rows' real
    positions, the rest of T is padding and moves nothing.
    Returns (o [B, T, H * dv] in z's type, state after position ``lens -
    1``, tail [B, k - 1, C]). ``eps`` is the block's, for its norms: the
    rule has its own (L2_EPS). ``scope`` and ``gate`` (``beta_max``,
    ``floor``: ``gates``' keywords): the kind's data (the module's
    docstring)."""
    x, ab = _split(p, z)
    with jax.named_scope(scope + "/conv"):
        c, tail = ssm.conv_window(
            x, tail0, p["ConvW"], jnp.zeros((x.shape[-1],), _F32), lens)
    with jax.named_scope(scope + "/chunk"):
        g, beta = gates(p, ab, **gate)
        real = (jnp.arange(z.shape[1], dtype=jnp.int32)[None]
                < lens[:, None])[:, :, None]
        o, state = chunk_rule(
            *_heads(p, c),
            jnp.where(real if g.ndim == 3 else real[..., None], g, 0.0),
            jnp.where(real, beta, 0.0), state0)
    return o.reshape(o.shape[:2] + (-1,)).astype(z.dtype), state, tail


# entries and heads a block of ``delta_state_step`` holds (a block of the pool
# is entries x heads x dk x dv float32, in VMEM four times: read and written,
# each twice), by the chip at Ling's slab, 257 entries of [32, 128, 128]
# float32, 1.08 GB read and written a layer: 633 GB/s at 4 x 8 (2 MB a
# block), 616 at 1 x 8, 633 at 2 x 8, 628 at 8 x 8, 620 at 16 x 8, 634 at 2 x
# 16 and 4 x 16, 634-635 at 1 x 32 and 2 x 32; a kernel that only COPIES its
# blocks reads 631-639 at every one of them: the copies bound it, not the
# arithmetic (the jax.numpy step: 328) (PERF.md section 6, PR 64). The body
# LOOPS over the block's entries and is unrolled over its heads alone, which
# lie on the lanes of the block's columns: 8, a sublane tile of the rows'
# blocks (32 unrolled cost 0.4-0.6 s more of tracing and lowering an
# instance, three instances a decode program)
STEP_BLOCK_ENTRIES = 4
STEP_BLOCK_HEADS = 8


def step_in_kernel(pool_shape, pool_dtype):
    """The gate of ``step_entries``, as ops/ssm.py's: the backend runs
    Pallas kernels (``flash_attention``'s own gate), and the pool is float32
    ``[L, entries, H, dk, dv]`` with ``dk`` whole sublane tiles and ``dv``
    whole lane tiles."""
    return (pa._use_pallas() and len(pool_shape) == 5
            and jnp.dtype(pool_dtype) == _F32
            and pool_shape[3] % 8 == 0 and pool_shape[4] % 128 == 0)


def _step_kernel(layer_ref, held_ref, cols_ref, rows_ref, s_ref, y_ref,
                 o_ref, *, n_heads):
    """A block of ``be`` entries x ``bh`` heads of one layer's slab, read
    once: each state's ``rule_step`` in VMEM, written where it was read from
    (or what it held, where no live row holds its entry), its output reduced
    over dk beside it. ``cols_ref`` [be, dk, W]: the decay, the key and the
    query of ALL the entries' heads with the heads on lanes (lane ``c H +
    head``), so a head's dk values lie down the sublanes as its state's rows
    do and are spread over the dv lanes by a broadcast (``n_heads``: H);
    the block's heads are rolled to lanes ``c H + 0 .. bh``. ``rows_ref`` [be, 3, bh, dv]: the
    value, and the write strength and ``k . q`` spread over dv. Both
    reductions are taken off the decayed state: ``S''^T q = S'^T q + (k . q)
    u``."""
    be, bh = s_ref.shape[:2]
    width = cols_ref.shape[-1]
    first = pl.program_id(0) * be
    last = held_ref.shape[0] - 1
    shift = (width - pl.program_id(1) * bh) % width

    def entry(e, carry):
        # a ragged last block's entries past the pool are never written
        kept = held_ref[jnp.minimum(first + e, last)] != 0
        cols = pltpu.roll(cols_ref[e], shift, 1)
        for h in range(bh):
            s = s_ref[e, h]
            k = cols[:, n_heads + h:n_heads + h + 1]
            decayed = cols[:, h:h + 1] * s
            u = rows_ref[e, 1, h:h + 1] * (rows_ref[e, 0, h:h + 1] - jnp.sum(
                decayed * k, axis=0, keepdims=True))
            y_ref[e, h:h + 1] = jnp.sum(
                decayed * cols[:, 2 * n_heads + h:2 * n_heads + h + 1],
                axis=0, keepdims=True) + rows_ref[e, 2, h:h + 1] * u
            o_ref[e, h] = jnp.where(kept, decayed + k * u, s)
        return carry

    jax.lax.fori_loop(0, be, entry, 0)


def step_entries(q, k, v, g, beta, held, s_pool, layer):
    """``rule_step`` of EVERY entry of layer ``layer`` of the state pool
    ``s_pool`` [L, n, H, dk, dv], in the entries' order and where they lie,
    through ONE kernel, ``delta_state_step`` (the caller has asked
    ``step_in_kernel``): q, k [n, H, dk]; v [n, H, dv]; beta [n, H]; g [n,
    H] (a decay a head) or [n, H, dk] (a channel); held [n] bool; all
    float32 -> (o [n, H, dv] float32, the pool written). An entry that is
    ``held`` is stepped by ``rule_step``'s equations in float32; one that
    is not keeps what it held bit for bit, a NaN too (its ``o`` is whatever
    its inputs give: the caller gathers the held entries'). An entry's
    result depends on its own state and inputs alone, whatever else the
    layer holds. The kernel passes over the slab once: a block is read,
    stepped, reduced to its output and written back to the same place of
    the same buffer (the pool is aliased to the result: no temporary of the
    slab's size, no update-slice over it, no second and third read for the
    two reductions)."""
    _, n, h, dk, dv = s_pool.shape
    be = min(STEP_BLOCK_ENTRIES, n)
    # the rows' blocks: whole sublane tiles of heads, or every head
    bh = STEP_BLOCK_HEADS if h % STEP_BLOCK_HEADS == 0 else h
    # a decay a head scales every row of its state alike
    decay = jnp.broadcast_to(jnp.exp(g).reshape(n, h, -1), (n, h, dk))
    # [3, n, H, dk] -> [n, dk, 3 H] in whole lane tiles: heads on lanes
    cols = jnp.transpose(jnp.stack([decay, k, q]), (1, 3, 0, 2)).reshape(
        n, dk, 3 * h)
    cols = jnp.pad(cols, ((0, 0), (0, 0), (0, -3 * h % 128)))
    rows = jnp.stack([v] + [jnp.broadcast_to(x[..., None], v.shape) for x in (
        beta, jnp.sum(k * q, axis=-1))], axis=1)
    slab = pl.BlockSpec((None, be, bh, dk, dv),
                        lambda i, j, lyr, _: (lyr[0], i, j, 0, 0))
    # what the blocks hold in VMEM, each twice
    blocks = 2 * 4 * be * (2 * bh * dk * dv + dk * cols.shape[-1]
                           + 4 * bh * dv)
    return pa._pcall(
        functools.partial(_step_kernel, n_heads=h), name="delta_state_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            # heads innermost: an entry's columns are fetched once
            grid=(-(-n // be), h // bh),
            in_specs=[pl.BlockSpec((be, dk, cols.shape[-1]),
                                   lambda i, j, *_: (i, 0, 0)),
                      pl.BlockSpec((be, 3, bh, dv),
                                   lambda i, j, *_: (i, 0, j, 0)),
                      slab],
            out_specs=[pl.BlockSpec((be, bh, dv),
                                    lambda i, j, *_: (i, j, 0)), slab]),
        out_shape=[jax.ShapeDtypeStruct((n, h, dv), _F32),
                   jax.ShapeDtypeStruct(s_pool.shape, s_pool.dtype)],
        input_output_aliases={4: 1},
        **pa._vmem_asked(blocks),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), held.astype(jnp.int32),
      cols, rows, s_pool)


def step(p, z, s_pool, layer, held, tail0, eps, scope="delta", **gate):
    """A decode step of one layer IN THE ENTRIES' ORDER against the state
    pool itself, as ops/ssm.py's: z [n, C + A + H], an entry's input; s_pool
    [L, n, H, dk, dv]; held [n] bool; tail0 [n, (k - 1) * C], flat as the
    tail pool stores it -> (o [n, H * dv] in z's type, the pool with layer
    ``layer`` written, tail [n, (k - 1) * C]). The entries that are ``held``
    step as ``window`` steps one position (``rule_step``), the others keep
    their state (their tail is the caller's to keep). Where the gate passes
    (``step_in_kernel``) the layer's slab is stepped where it lies by ONE
    kernel (``step_entries``); elsewhere its entries are sliced out of the
    pool, stepped in jax.numpy and set back."""
    in_kernel = step_in_kernel(s_pool.shape, s_pool.dtype)
    # sliced here, ahead of the convolution: outside the gate the program is
    # the one it was, operation for operation (Olmo-Hybrid's pinned text)
    state0 = None if in_kernel else s_pool[layer]
    x, ab = _split(p, z)
    n = z.shape[0]
    with jax.named_scope(scope + "/conv"):
        c, tail = ssm.conv_step(
            x, tail0.reshape(n, -1, x.shape[-1]), p["ConvW"],
            jnp.zeros((x.shape[-1],), _F32))
    with jax.named_scope(scope + "/step"):
        inputs = *_heads(p, c), *gates(p, ab, **gate)
        if in_kernel:
            o, s_pool = step_entries(*inputs, held, s_pool, layer)
            o = o.reshape(n, -1).astype(z.dtype)
        else:
            o, state = rule_step(*inputs, state0.astype(_F32))
            o = o.reshape(n, -1).astype(z.dtype)
            s_pool = s_pool.at[layer].set(jnp.where(
                held[:, None, None, None], state.astype(s_pool.dtype),
                state0))
    return o, s_pool, tail.reshape(n, -1)
