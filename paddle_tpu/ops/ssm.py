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
tail is taken from the real inputs alone. The scan (``scan_window``) walks
the positions in their order, in float32, the state carried from position
to position; nothing of ``[T, N, C]`` is ever materialised (671 MB a layer
for a 2,048-token window at C = 5,120). What runs it is the call's to
decide, as ``flash_attention`` decides it: on the chip at whole-tile
widths ONE Pallas kernel a call, ``ssm_state_scan``, the running state in
VMEM from a window's first position to its last (``state0`` read once and
the last state written once a window); everywhere else (every CPU, a
narrow model) one carried ``lax.scan`` of ``SCAN_BLOCK`` positions a loop
iteration, under the same promise.

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
           "dt_b_c", "scan_window", "scan_in_kernel", "scan_step",
           "step_entries", "step_in_kernel"]

_F32 = jnp.float32

# positions a loop iteration of the carried scan takes (scan_window, where
# its kernel's gate fails: no cell). The chip ran this loop until PR 52;
# one 2,048-token row at C = 5,120 alone on the chip: 3.16 us a position at
# 1, 1.10 at 4, 1.16 at 8, 1.21 at 16, 1.52 at 64; the results bit for bit
# the same (my chip run, PR 39); inside Jamba2's prefill programs 0.72 at 4,
# by what the kernel took out of them (PR 52)
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


# positions a block and channels a tile of the prefill scan's kernel
# (``scan_window``), by the chip: one 2,048-token row at C = 5,120, 26 layer
# calls one after the other, us a position a layer at 128 / 256 / 512
# positions a block: 0.323 / 0.318 / 0.315 at 256 channels a tile, 0.235 /
# 0.233 / 0.232 at 512, 0.2135 / 0.2132 / 0.2134 at 1,280, 0.232 / 0.233 /
# 0.238 at 2,560, 0.302 / 0.303 / 0.308 at 5,120 (its first call 0.9-1.2 s
# where 1,280's took 0.35), 0.218 at 1,024 x 256 and 0.573 at 640 x 256 (one
# reading, not looked into); the carried scan beside them 0.43, the results
# the carried scan's bit for bit at every pair; in the cell's programs 0.2105
# at every bucket. The positions hardly matter, the channels do. At 1,280 x
# 256 a body that only copies its blocks takes 0.046, one that never reads
# the state out 0.140, one without the sum over N 0.162, one without the
# ``exp`` 0.193: the vector unit's passes bound it (143 operations a
# position a tile in 79 cycles), not the memory and not the ``exp``; the
# sums of 8 positions as one butterfly of selects and rotations read 0.192-
# 0.195 and were left out, 10% of a twentieth of the chip (my chip runs, PR
# 52; PERF.md section 6)
SCAN_KERNEL_POSITIONS = 256
SCAN_KERNEL_CHANNELS = 1280
# positions a pass of the kernel's loop takes, unrolled in its body: the
# sublane tile of a 16-bit ``c`` (and two of a float32 ``dt``), so a pass
# reads and writes whole tiles
_SCAN_GROUP = 16


def scan_in_kernel(width, n_state, state_dtype):
    """The gate of ``scan_window``: the backend runs Pallas kernels
    (``flash_attention``'s own gate), the state is float32, ``C`` whole
    lane tiles and ``N`` whole sublane tiles."""
    return (pa._use_pallas() and jnp.dtype(state_dtype) == _F32
            and n_state % 8 == 0 and width % 128 == 0)


def _scan_kernel(dt_ref, c_ref, maps_ref, a_ref, d_ref, s0_ref, y_ref, s_ref,
                 acc_ref):
    """A block of ``tb`` positions x ``tc`` channels of one row, the
    positions one after the other: ``s_ref`` [N, tc], the block of the
    state handed back, stays in VMEM from a tile's first block of
    positions (where it is ``s0_ref``) to its last, and is the running
    state between blocks; inside a block the state is the loop's carry.
    ``maps_ref`` [tb / G, 2N, G]: a pass's input maps over its output maps
    with N down the sublanes, as the state's rows lie, so a position's
    column is spread over the channels' lanes by a broadcast; ``dt`` and
    ``c`` are rows that broadcast over the N sublanes. ``acc_ref`` [G,
    tc]: a pass's reductions over N, a row a position."""
    n_state = a_ref.shape[0]
    g = _SCAN_GROUP

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = s0_ref[...]

    def group(i, s):
        rows = pl.ds(pl.multiple_of(i * g, g), g)
        dt = dt_ref[rows, :]
        x = c_ref[rows, :].astype(_F32)
        dtx = dt * x
        a = a_ref[...]
        for k in range(g):
            s = jnp.exp(dt[k:k + 1] * a) * s \
                + dtx[k:k + 1] * maps_ref[i, :n_state, k:k + 1]
            acc_ref[k:k + 1, :] = jnp.sum(
                s * maps_ref[i, n_state:, k:k + 1], axis=0, keepdims=True)
        y_ref[rows, :] = (acc_ref[...] + d_ref[...] * x).astype(y_ref.dtype)
        return s

    s_ref[...] = jax.lax.fori_loop(0, dt_ref.shape[0] // g, group,
                                   s_ref[...])


def _scan_in_kernel(dt, c, bm, cm, a, d, state0):
    """``scan_window`` as ONE kernel: a grid over (row, channel tile, block
    of positions), the positions innermost and in order, the channel
    tiles independent (a channel's recurrence touches no other channel).
    ``state0`` is read once and the last state written once a row and
    tile; a position brings its ``dt``, ``c`` and maps and takes its ``y``
    away, and nothing else crosses HBM."""
    b, t, width = dt.shape
    n_state, g = a.shape[0], _SCAN_GROUP
    tb = min(SCAN_KERNEL_POSITIONS, -(-t // g) * g)
    nb = -(-t // tb)
    tc = max(w for w in range(128, min(width, SCAN_KERNEL_CHANNELS) + 1, 128)
             if width % w == 0)
    maps = jnp.concatenate([bm, cm], axis=2).astype(_F32)
    if nb * tb > t:
        # positions past the window: no step (dt = 0), no input
        dt, c, maps = (jnp.pad(x, ((0, 0), (0, nb * tb - t), (0, 0)))
                       for x in (dt, c, maps))
    # [B, T, 2N] -> [B, T / G, 2N, G]: a pass's maps, N on sublanes
    maps = jnp.swapaxes(maps.reshape(b, nb * tb // g, g, 2 * n_state), 2, 3)
    rows = pl.BlockSpec((None, tb, tc), lambda i, j, k: (i, k, j))
    state = pl.BlockSpec((None, n_state, tc), lambda i, j, k: (i, 0, j))
    y, state = pa._pcall(
        _scan_kernel, name="ssm_state_scan",
        grid=(b, width // tc, nb),
        in_specs=[rows, rows,
                  pl.BlockSpec((None, tb // g, 2 * n_state, g),
                               lambda i, j, k: (i, k, 0, 0)),
                  pl.BlockSpec((n_state, tc), lambda i, j, k: (0, j)),
                  pl.BlockSpec((1, tc), lambda i, j, k: (0, j)),
                  state],
        out_specs=[rows, state],
        out_shape=[jax.ShapeDtypeStruct((b, nb * tb, width), c.dtype),
                   jax.ShapeDtypeStruct(state0.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((g, tc), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(dt, c, maps, a, d.astype(_F32)[None], state0)
    return y[:, :t], state


def scan_window(dt, c, bm, cm, a, d, state0):
    """The recurrence over a window, position after position in float32
    with the state carried: ``scan_step``'s equations at every position,
    in the positions' order. Only the running state [B, N, C] lives
    between positions: nothing of ``[T, N, C]`` is materialised. Where
    the gate passes (``scan_in_kernel``: the chip at whole-tile widths)
    ONE Pallas kernel walks the window with the state in VMEM
    (``_scan_in_kernel``); everywhere else one ``lax.scan`` over T, whose
    loop takes ``SCAN_BLOCK`` positions an iteration (any number gives the
    same positions in the same order; one that does not divide T leaves a
    shorter last run).

    dt [B, T, C] float32; c [B, T, C]; bm, cm [B, T, N]; state0 [B, N, C]
    float32 -> (y [B, T, C] in ``c``'s type, the state after the last
    position)."""
    if scan_in_kernel(dt.shape[2], a.shape[0], state0.dtype):
        return _scan_in_kernel(dt, c, bm, cm, a, d, state0)

    def body(state, xs):
        y, state = scan_step(*xs, a, d, state)
        return state, y.astype(c.dtype)

    state, ys = jax.lax.scan(
        body, state0.astype(_F32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (dt, c, bm, cm)),
        unroll=max(1, min(SCAN_BLOCK, dt.shape[1])))
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
