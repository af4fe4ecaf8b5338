"""The selective state-space mixer (Mamba-1, arXiv:2312.00752, with the
Jamba family's three inner RMSNorms, arXiv:2403.19887) as functions of a
window of positions, the state handed in and handed back, or of one decode
step of a layer's entries where they lie in the state pool (``step``,
``step_entries``: one Pallas kernel over the layer's slab where its gate
passes).

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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_attention as pa

__all__ = ["window", "step", "conv_window", "conv_step", "conv_step_flat",
           "dt_b_c", "scan_window", "scan_step", "step_entries",
           "step_in_kernel"]

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


def conv_step_flat(z, tail0, w, b):
    """``conv_step`` with the tail FLAT, as the tail pool stores an entry:
    z [B, C], tail0 [B, (k - 1) * C], the inputs before this one, oldest
    first, one after the other along the minor axis -> (c [B, C], tail [B,
    (k - 1) * C]). Every tap is a whole-tile slice of the entry where C is
    whole lane tiles: nothing is re-laid between the pool and the taps
    ([B, k - 1, C] puts k - 1 = 3 rows on the sublanes: the chip stored
    that view in tiles of 4 rows and copied an entry's 4 MB into it and
    out of it every layer of every step, PERF.md section 6, PR 49)."""
    k, width = w.shape
    taps = [tail0[:, j * width:(j + 1) * width] for j in range(k - 1)] + [z]
    wf = w.astype(_F32)
    acc = taps[0].astype(_F32) * wf[0]
    for j in range(1, k):
        acc = acc + taps[j].astype(_F32) * wf[j]
    tail = jnp.concatenate(taps[1:], axis=1).astype(tail0.dtype)
    return jax.nn.silu(b.astype(_F32) + acc).astype(z.dtype), tail


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


# ---------------------------------------------------------------------------
# a decode step of one layer's entries where they lie in the state pool. The
# CALL decides what it is, as ``flash_attention`` and the paged decode calls
# do: the Pallas kernel where its gate passes (the chip; the tests'
# interpreter hook), the jax.numpy step everywhere else, under one promise
# ---------------------------------------------------------------------------

# entries and channels a block of the kernel holds (a block of the pool is
# entries x N x channels float32, in VMEM four times: read and written, each
# twice), by the chip at Jamba2's slab, 129 entries of [16, 5120] float32,
# 84.5 MB read and written a layer: 592-598 GB/s at 16 x 1,280, 579 at 16 x
# 640, 597 at 16 x 2,560, 604 at 16 x 5,120, 594 at 32 x 1,280, 549 at 64 x
# 320, 612-618 at 64 x 640, 615 at 48 x 1,280, 625 at 64 x 1,280 (21 MB
# held: over the default limit), 509 at 128 x 640; a kernel that only
# COPIES its blocks reads 602-617 and one without the ``exp`` 598-612: the
# copies bound it, not the arithmetic (the jax.numpy step alone: 400). The
# kernel's body is unrolled over its entries and TRACED at every start of
# a process, once a run of layers: 0.34-0.56 s an instance at 64 entries,
# 0.09 at 16, and a warm ``setup_s`` read 2-4 s of 36 higher at 64 x 640:
# 16 kept, for 3% of the kernel (PERF.md section 6, PR 49). The entries
# are a multiple of a 16-bit ``c``'s sublane tile
STEP_BLOCK_ENTRIES = 16
STEP_BLOCK_CHANNELS = 1280


def step_in_kernel(pool_shape, pool_dtype):
    """The gate of ``step_entries``: the backend runs Pallas kernels
    (``flash_attention``'s own gate), and the pool is float32 ``[L,
    entries, N, C]`` with ``C`` whole lane tiles and ``N`` whole sublane
    tiles."""
    return (pa._use_pallas() and len(pool_shape) == 4
            and jnp.dtype(pool_dtype) == _F32
            and pool_shape[2] % 8 == 0 and pool_shape[3] % 128 == 0)


def _step_kernel(layer_ref, held_ref, dt_ref, c_ref, maps_ref, a_ref, d_ref,
                 s_ref, y_ref, o_ref):
    """A block of ``be`` entries x ``tc`` channels of one layer's slab,
    read once: each entry's ``scan_step`` in VMEM, its new state written
    where it was read from (or what it held, where no live row holds it),
    its output reduced over N beside it. ``maps_ref`` [2N, be]: the block's
    input maps over its output maps with the ENTRIES on lanes, so an
    entry's N values lie down the sublanes as its state's rows do and are
    spread over the channels' lanes by a broadcast."""
    be, n_state = s_ref.shape[:2]
    first = pl.program_id(1) * be
    last = held_ref.shape[0] - 1
    a = a_ref[...]
    dt = dt_ref[...]
    x = c_ref[...].astype(_F32)
    dtx = dt * x
    dx = d_ref[...] * x
    for k in range(be):
        s = s_ref[k]
        new = jnp.exp(dt[k:k + 1] * a) * s \
            + dtx[k:k + 1] * maps_ref[:n_state, k:k + 1]
        y_ref[k:k + 1, :] = jnp.sum(new * maps_ref[n_state:, k:k + 1],
                                    axis=0, keepdims=True) + dx[k:k + 1]
        # a ragged last block's rows past the pool are never written
        kept = held_ref[jnp.minimum(first + k, last)] != 0
        o_ref[k] = jnp.where(kept, new, s)


def step_entries(dt, c, bm, cm, held, a, d, s_pool, layer):
    """``scan_step`` of EVERY entry of layer ``layer`` of the state pool
    ``s_pool`` [L, n, N, C], in the entries' order and where they lie: dt
    [n, C] float32; c [n, C]; bm, cm [n, N] float32; held [n] bool; a [N,
    C]; d [C] -> (y [n, C] float32, the pool written). An entry that is
    ``held`` is stepped by ``scan_step``'s equations in float32; one that
    is not keeps what it held bit for bit, a NaN too (its ``y`` is
    whatever its inputs give: the caller gathers the held entries'). An
    entry's result depends on its own state and inputs alone, whatever
    else the layer holds. Where the gate passes (``step_in_kernel``) ONE
    kernel passes over the slab once: a block is read, stepped, reduced to
    its output and written back to the same place of the same buffer (the
    pool is aliased to the result: no temporary of the slab's size, no
    update-slice over it, no second read for the output); elsewhere the
    jax.numpy step over a slice of the pool."""
    if not step_in_kernel(s_pool.shape, s_pool.dtype):
        state0 = s_pool[layer]
        y, state = scan_step(dt, c, bm, cm, a, d, state0.astype(_F32))
        return y, s_pool.at[layer].set(jnp.where(
            held[:, None, None], state.astype(s_pool.dtype), state0))
    _, n, n_state, width = s_pool.shape
    be = min(STEP_BLOCK_ENTRIES, n)
    tc = max(t for t in range(128, min(width, STEP_BLOCK_CHANNELS) + 1, 128)
             if width % t == 0)
    nb = -(-n // be)
    # [n, 2N] -> [nb, 2N, be]: a block's maps, the entries on lanes
    maps = jnp.pad(jnp.concatenate([bm, cm], axis=1).astype(_F32),
                   ((0, nb * be - n), (0, 0)))
    maps = jnp.swapaxes(maps.reshape(nb, be, 2 * n_state), 1, 2)
    rows = pl.BlockSpec((be, tc), lambda j, i, *_: (i, j))
    slab = pl.BlockSpec((None, be, n_state, tc),
                        lambda j, i, lyr, _: (lyr[0], i, 0, j))
    # what the blocks hold in VMEM, each twice
    blocks = 2 * 4 * (2 * be * n_state * tc + 3 * be * tc
                      + (n_state + 1) * tc + 2 * n_state * 128)
    return pa._pcall(
        _step_kernel, name="ssm_state_step",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            # entries innermost: a channel tile's ``a`` and ``d`` are
            # fetched once
            grid=(width // tc, nb),
            in_specs=[rows, rows,
                      pl.BlockSpec((None, 2 * n_state, be),
                                   lambda j, i, *_: (i, 0, 0)),
                      pl.BlockSpec((n_state, tc), lambda j, i, *_: (0, j)),
                      pl.BlockSpec((1, tc), lambda j, i, *_: (0, j)),
                      slab],
            out_specs=[rows, slab]),
        out_shape=[jax.ShapeDtypeStruct((n, width), _F32),
                   jax.ShapeDtypeStruct(s_pool.shape, s_pool.dtype)],
        input_output_aliases={7: 1},
        **pa._vmem_asked(blocks),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), held.astype(jnp.int32),
      dt, c, maps, a, d.astype(_F32)[None], s_pool)


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


def step(p, z, s_pool, layer, held, tail0, eps):
    """A decode step of one layer IN THE ENTRIES' ORDER against the state
    pool itself: z [n, C], an entry's input (whatever, where no live row
    holds it); s_pool [L, n, N, C]; held [n] bool; tail0 [n, (k - 1) * C],
    FLAT as the tail pool stores it -> (y [n, C] in z's type, the pool
    with layer ``layer`` written, tail [n, (k - 1) * C]). The entries that
    are ``held`` step as ``window`` steps one position; the others keep
    their state (``step_entries``; their tail is the caller's to keep)."""
    with jax.named_scope("ssm/conv"):
        c, tail = conv_step_flat(z, tail0, p["ConvW"], p["ConvB"])
    with jax.named_scope("ssm/step"):
        dt, bm, cm = dt_b_c(p, c, eps)
        y, s_pool = step_entries(dt, c, bm, cm, held, _a_of(p), p["D"],
                                 s_pool, layer)
    return y.astype(z.dtype), s_pool, tail
