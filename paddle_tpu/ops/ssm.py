"""The selective state-space mixer (Mamba-1, arXiv:2312.00752, with the
Jamba family's three inner RMSNorms, arXiv:2403.19887) as functions of a
window of positions or of one step, the state handed in and handed back.

For an input ``z_t`` [C] (C = ``d_in`` channels, N states a channel)::

    c_t = silu(b_conv + sum_{j<k} w_conv[j] * z_{t-(k-1)+j})   (zeros before 0)
    [dt_r, B, C] = W_x c_t,  each through its own RMSNorm
    dt = softplus(W_dt dt_r + b_dt)
    S_t = exp(dt_t * A) * S_{t-1} + (dt_t * c_t) * B_t,   A = -exp(A_log)
    y_t = S_t . C_t + D * c_t

What a sequence carries from one call to the next is ``S`` [N, C] float32
and the TAIL, its last ``k - 1`` inputs ``z`` [k - 1, C]. Neither is
indexed by position: a cache of this kind is ONE entry a sequence, and it
is not protected by any length mask, so the caller starts a new sequence
from zeros (``window``: ``state0``/``tail0``) and never from what an entry
held. S lies [N, C], channels on the minor axis (C is tens of lane
tiles; N = 16 as a minor axis would be stored 128 wide), and is float32
whatever the model's type: a decay of 0.999 a token is lost in bfloat16.

``window`` scans a row's REAL positions: a position at or past ``lens``
gets ``dt = 0``, which leaves S as it was (exp(0) = 1, no input), and the
tail is taken from the real inputs alone. The scan is CHUNKED
(``scan_window``): blocks of ``SCAN_BLOCK`` positions, the state carried
from position to position; nothing of ``[T, N, C]`` is ever materialised
(671 MB a layer for a 2,048-token window at C = 5,120).

Parameters ``p`` of one layer, by slot: ``ConvW`` [k, C], ``ConvB`` [C],
``WX`` [C, R + 2N], ``DtNorm`` [R], ``BNorm`` [N], ``CNorm`` [N], ``WDt``
[R, C], ``DtBias`` [C] float32, ``ALog`` [N, C] float32, ``D`` [C] float32.
"""
import jax
import jax.numpy as jnp

__all__ = ["window", "step", "conv_window", "conv_step", "dt_b_c",
           "scan_window", "scan_step"]

_F32 = jnp.float32

# positions a loop iteration of the prefill scan takes (scan_window). One
# 2,048-token row at C = 5,120 alone on the chip: 3.16 us a position at 1,
# 1.10 at 4, 1.16 at 8, 1.21 at 16, 1.52 at 64; the results bit for bit the
# same (my chip run, PR 39)
SCAN_BLOCK = 4


def _rms(x, scale, eps):
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * scale.astype(_F32)


def conv_window(z, tail0, w, b, lens):
    """The causal depthwise convolution of a window. z [B, T, C]; tail0
    [B, k - 1, C], the inputs before the window's first (zeros at a
    sequence's start); w [k, C]; b [C]; lens [B], the rows' real
    positions. Returns (c [B, T, C] in z's type, the tail the window
    leaves: the last k - 1 REAL inputs, [B, k - 1, C])."""
    t, k = z.shape[1], w.shape[0]
    full = jnp.concatenate([tail0.astype(z.dtype), z], axis=1)
    acc = b.astype(_F32)
    for j in range(k):
        acc = acc + full[:, j:j + t].astype(_F32) * w[j].astype(_F32)
    # input t lies at full[t + k - 1]: the last k - 1 real ones start at
    # full[lens]
    at = lens[:, None] + jnp.arange(k - 1, dtype=jnp.int32)[None]
    tail = jnp.take_along_axis(full, at[:, :, None], axis=1)
    return jax.nn.silu(acc).astype(z.dtype), tail


def conv_step(z, tail0, w, b):
    """conv_window for one position a row: z [B, C], tail0 [B, k - 1, C]
    -> (c [B, C], tail [B, k - 1, C])."""
    full = jnp.concatenate([tail0.astype(z.dtype), z[:, None]], axis=1)
    acc = b.astype(_F32) + jnp.sum(
        full.astype(_F32) * w.astype(_F32)[None], axis=1)
    return jax.nn.silu(acc).astype(z.dtype), full[:, 1:]


def dt_b_c(p, c, eps):
    """The step size, the input map and the output map of every position,
    float32: c [..., C] -> (dt [..., C], B [..., N], C [..., N])."""
    n = p["BNorm"].shape[-1]
    r = p["DtNorm"].shape[-1]
    x = jnp.matmul(c, p["WX"], preferred_element_type=_F32)
    dt_r = _rms(x[..., :r], p["DtNorm"], eps)
    bm = _rms(x[..., r:r + n], p["BNorm"], eps)
    cm = _rms(x[..., r + n:], p["CNorm"], eps)
    dt = jnp.matmul(dt_r.astype(c.dtype), p["WDt"],
                    preferred_element_type=_F32) + p["DtBias"]
    return jax.nn.softplus(dt), bm, cm


def scan_step(dt, c, bm, cm, a, d, state):
    """One position of every row: dt, c [B, C]; bm, cm [B, N]; a [N, C]
    (= -exp(A_log)); d [C]; state [B, N, C] float32 -> (y [B, C] float32,
    the state after it)."""
    cf = c.astype(_F32)
    state = jnp.exp(dt[:, None, :] * a[None]) * state \
        + (dt * cf)[:, None, :] * bm[:, :, None]
    y = jnp.sum(state * cm[:, :, None], axis=1) + d.astype(_F32) * cf
    return y, state


def scan_window(dt, c, bm, cm, a, d, state0, block=SCAN_BLOCK):
    """The recurrence over a window, position after position with the
    state carried: one ``lax.scan`` over T whose loop takes ``block``
    positions an iteration (any ``block`` gives the same positions in the
    same order; one that does not divide T leaves a shorter last run).
    Only the running state [B, N, C] lives between positions: nothing of
    ``[T, N, C]`` is materialised.

    dt, c [B, T, C]; bm, cm [B, T, N]; state0 [B, N, C] float32 -> (y
    [B, T, C] float32, the state after the last position)."""
    def body(state, xs):
        y, state = scan_step(*xs, a, d, state)
        return state, y

    state, ys = jax.lax.scan(
        body, state0.astype(_F32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (dt, c, bm, cm)),
        unroll=max(1, min(int(block), dt.shape[1])))
    return jnp.moveaxis(ys, 0, 1), state


def _a_of(p):
    return -jnp.exp(p["ALog"].astype(_F32))


def window(p, z, state0, tail0, lens, eps):
    """A window of positions through the mixer's recurrent part. z [B, T,
    C]; state0 [B, N, C] float32 and tail0 [B, k - 1, C]: what the rows
    carried in (zeros for a row that starts here); lens [B]: the rows'
    real positions, the rest of T is padding and moves nothing. Returns
    (y [B, T, C] in z's type, state [B, N, C] float32 after position
    ``lens - 1``, tail [B, k - 1, C])."""
    with jax.named_scope("ssm/conv"):
        c, tail = conv_window(z, tail0, p["ConvW"], p["ConvB"], lens)
    with jax.named_scope("ssm/scan"):
        dt, bm, cm = dt_b_c(p, c, eps)
        real = jnp.arange(z.shape[1], dtype=jnp.int32)[None] < lens[:, None]
        dt = jnp.where(real[:, :, None], dt, 0.0)
        y, state = scan_window(dt, c, bm, cm, _a_of(p), p["D"], state0)
    return y.astype(z.dtype), state, tail


def step(p, z, state0, tail0, eps):
    """``window`` for one position a row: z [B, C] -> (y [B, C] in z's
    type, state [B, N, C] float32, tail [B, k - 1, C])."""
    with jax.named_scope("ssm/conv"):
        c, tail = conv_step(z, tail0, p["ConvW"], p["ConvB"])
    with jax.named_scope("ssm/step"):
        dt, bm, cm = dt_b_c(p, c, eps)
        y, state = scan_step(dt, c, bm, cm, _a_of(p), p["D"],
                             state0.astype(_F32))
    return y.astype(z.dtype), state, tail
