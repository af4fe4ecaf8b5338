"""Flash attention — Pallas TPU kernel with online softmax.

This is the framework's hot-op kernel path (the reference's analogue is
the fused attention CUDA kernels under paddle/fluid/operators/, e.g.
attention_lstm_op.cc / the cuDNN softmax+matmul fusions). Design per the
TPU kernel playbook: Q/K/V blocks staged in VMEM, S = QK^T on the MXU in
fp32, online (streaming) softmax with running max/denominator in VMEM
scratch so the T×T score matrix never materializes in HBM.

The public entry ``flash_attention`` is differentiable: forward uses the
Pallas kernel on TPU (pure-jax reference elsewhere / under interpret),
backward recomputes attention with the standard jax formulation, which
XLA fuses well.

Also exposes ``attention_with_lse`` (returns log-sum-exp) — the building
block ring attention (parallel/ring_attention.py) uses to combine
per-shard partial results exactly.
"""
import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# test hook: run the kernels through the pallas interpreter on CPU so
# their numerics are exercised without TPU hardware
_FORCE_INTERPRET = False


def _use_pallas():
    return _FORCE_INTERPRET or jax.default_backend() == "tpu"


def _kernel_shapes_ok(q, k):
    """The one shape gate of all three kernels: whole 128-row blocks
    (a ragged last block would read padding), lane-aligned head_dim,
    and equal q/k length (the kernels' causal mask has no offset)."""
    t, d = q.shape[2], q.shape[3]
    return t % 128 == 0 and d % 128 == 0 and k.shape[2] == t


def _pcall(kernel, *, name, **kwargs):
    """pallas_call under a stable name: the kernel's own ``name`` plus
    a named scope around the call, so the Mosaic custom call can be
    found in optimized HLO and in a profiler trace after a refactor."""
    call = pl.pallas_call(kernel, name=name, interpret=_FORCE_INTERPRET,
                          **kwargs)

    def run(*operands):
        with jax.named_scope(name):
            return call(*operands)

    return run


# ---------------------------------------------------------------------------
# pallas kernel
# ---------------------------------------------------------------------------


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
               *, scale, causal, block_q, block_k, nk):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # causal: a block entirely above the diagonal contributes nothing
    if causal:
        live = qi * block_q + block_q - 1 >= ki * block_k
    else:
        live = jnp.bool_(True)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)            # [bq, d]
        k = k_ref[0].astype(jnp.float32)            # [bk, d]
        v = v_ref[0].astype(jnp.float32)            # [bk, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        if causal:
            rows = qi * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_ref[:, :1]                        # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                       # [bq, bk]
        corr = jnp.exp(m_prev - m_new)               # [bq, 1]
        l_new = corr * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_ref[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        lse = m_ref[:, :1] + jnp.log(safe_l)
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref[0].shape)


def _flash_fwd_pallas(q, k, v, scale, causal, block_q=128, block_k=128):
    """q,k,v: [BH, T, D] (heads folded into batch). Returns (o, lse[BH,T])."""
    bh, tq, d = q.shape
    tk = k.shape[1]
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    nq = pl.cdiv(tq, block_q)
    nk = pl.cdiv(tk, block_k)

    kernel = functools.partial(_fa_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k, nk=nk)
    out_shape = [
        jax.ShapeDtypeStruct(q.shape, q.dtype),
        jax.ShapeDtypeStruct((bh, tq, 128), jnp.float32),  # lse, lane-padded
    ]
    o, lse = _pcall(
        kernel,
        name="flash_fwd",
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        out_shape=out_shape,
    )(q, k, v)
    return o, lse    # [bh, tq, 128] lane-padded; callers slice [..., 0]


# ---------------------------------------------------------------------------
# pallas backward kernels (FlashAttention-2 style)
#
# Round-3 measurement forced this: the round-2 backward fell back to
# jax.vjp of the naive reference, which materializes the [B, H, T, T]
# f32 score matrix — at dim-4096 train shapes that buffer alone is
# 1-2 GB per layer (the OOMs that killed the b16 configs) and its HBM
# traffic dominated the step. The blockwise backward below recomputes
# scores from the saved (lse, delta) per VMEM tile, exactly like the
# forward — nothing T x T ever touches HBM.
# ---------------------------------------------------------------------------


def _recompute_ds(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                  qi, ki, scale, causal, block_q, block_k):
    """Shared backward tile math (FA-2): recompute the score tile from
    q,k and the saved lse, mask it, and form p, dv-contribution inputs
    and ds. One copy so dq and dk/dv can never diverge."""
    q = q_ref[0].astype(jnp.float32)             # [bq, d]
    k = k_ref[0].astype(jnp.float32)             # [bk, d]
    v = v_ref[0].astype(jnp.float32)             # [bk, d]
    do = do_ref[0].astype(jnp.float32)           # [bq, d]
    lse = lse_ref[0][:, :1]                      # [bq, 1]
    delta = dl_ref[0][:, :1]                     # [bq, 1]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if causal:
        rows = qi * block_q + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = ki * block_k + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(rows >= cols, s, NEG_INF)
    p = jnp.exp(s - lse)                         # [bq, bk]
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)      # [bq, bk]
    ds = p * (dp - delta) * scale
    return q, do, p, ds


def _fa_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                       dk_ref, dv_ref, dk_acc, dv_acc,
                       *, scale, causal, block_q, block_k, nq):
    ki = pl.program_id(1)
    qi = pl.program_id(2)           # inner accumulation dim

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    if causal:
        live = qi * block_q + block_q - 1 >= ki * block_k
    else:
        live = jnp.bool_(True)

    @pl.when(live)
    def _compute():
        q, do, p, ds = _recompute_ds(q_ref, k_ref, v_ref, do_ref,
                                     lse_ref, dl_ref, qi, ki, scale,
                                     causal, block_q, block_k)
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                      dq_ref, dq_acc,
                      *, scale, causal, block_q, block_k, nk):
    qi = pl.program_id(1)
    ki = pl.program_id(2)           # inner accumulation dim

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    if causal:
        live = qi * block_q + block_q - 1 >= ki * block_k
    else:
        live = jnp.bool_(True)

    @pl.when(live)
    def _compute():
        _, _, _, ds = _recompute_ds(q_ref, k_ref, v_ref, do_ref,
                                    lse_ref, dl_ref, qi, ki, scale,
                                    causal, block_q, block_k)
        dq_acc[:] = dq_acc[:] + jax.lax.dot_general(
            ds, k_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd_pallas(q, k, v, o, lse, do, scale, causal,
                      block_q=128, block_k=128):
    """q,k,v,o,do: [BH, T, D]; lse: [BH, T, 128] lane-padded f32.
    Returns (dq, dk, dv)."""
    bh, tq, d = q.shape
    tk = k.shape[1]
    block_q = min(block_q, tq)
    block_k = min(block_k, tk)
    nq = pl.cdiv(tq, block_q)
    nk = pl.cdiv(tk, block_k)
    # delta = rowsum(do * o) — the dsoftmax correction (FA-2 eq. 4);
    # lse arrives already lane-padded [BH, T, 128] from the forward
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                                  # [BH, T]
    lse128 = lse
    dl128 = jnp.broadcast_to(delta[..., None], delta.shape + (128,))

    qspec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    kspec = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0))
    row_q = pl.BlockSpec((1, block_q, 128), lambda b, i, j: (b, i, 0))

    dq = _pcall(
        functools.partial(_fa_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nk=nk),
        name="flash_bwd_dq",
        grid=(bh, nq, nk),
        in_specs=[
            qspec,                                              # q
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            qspec,                                              # do
            row_q,                                              # lse
            row_q,                                              # delta
        ],
        out_specs=[qspec],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype)],
    )(q, k, v, do, lse128, dl128)[0]

    dkv_q = pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0))
    dkv_row = pl.BlockSpec((1, block_q, 128), lambda b, j, i: (b, i, 0))
    dk, dv = _pcall(
        functools.partial(_fa_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nq=nq),
        name="flash_bwd_dkv",
        grid=(bh, nk, nq),
        in_specs=[
            dkv_q,                                              # q
            kspec,                                              # k
            kspec,                                              # v
            dkv_q,                                              # do
            dkv_row,                                            # lse
            dkv_row,                                            # delta
        ],
        out_specs=[kspec, kspec],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
    )(q, k, v, do, lse128, dl128)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# paged decode: one query a row against the row's pages, where they lie.
# Each of the three calls asks its own gate, as ``flash_attention`` does:
# the Pallas kernel where it passes (the chip; the tests' interpreter
# hook), ``_ref_paged_attention`` everywhere else, under one promise
# ---------------------------------------------------------------------------

# positions a block of keys holds (whole pages: 8 of Mistral's 16). Fixed
# here, never derived from a batch: a row's blocks, and so the order its
# softmax is folded in, are the same whatever rows it shares a dispatch
# with
PAGED_BLOCK_KEYS = 128
# and a block of flat entries (8 pages of 64), by the chip: one block is
# in flight while one is folded, and MiMo's 24 rows of 17,408 positions
# read 495 GB/s at 128 positions a block (0.33 MB), 692 at 256, 735 at 512
# and no more at 1,024 or 2,048 (PERF.md section 6, PR 42)
PAGED_FLAT_BLOCK_KEYS = 512
# rows of a float32 tile: the kernel slices [heads, keys] weights a group
_SUBLANES = 8
# and of latent entries (8 pages of 64, 640 wide: 0.66 MB), by the chip:
# xing4's 16 rows x 32 heads of 1,100-8,400 positions (58 operations a
# byte: the copies bound it) read 294 GB/s at 128 positions a block, 434
# at 256, 562 at 512 and 618 at 1,024; DeepSeek-V3's 64 rows x 128 heads
# of 320-2,050 (230 operations a byte, the chip's ridge: the fold bounds
# it, its two products one after the other with the float32 passes over
# the scores between them) 210, 302, 340 and 347 GB/s (48 to 80 of 197
# TFLOP/s), and 64 rows of 320 positions 205 at 512 against 135 at 1,024:
# one size for both, the longer rows' tenth left (PERF.md section 6, PR 45)
PAGED_LATENT_BLOCK_KEYS = 512


def paged_gqa_usable(k_shape, v_shape):
    """The gate of ``paged_gqa_decode``: the backend runs Pallas kernels
    (``flash_attention``'s own gate), and the pools are two ``[L, pages,
    page_size, g, hd]`` of one shape, a head a whole number of lane
    tiles."""
    return (_use_pallas() and len(k_shape) == 5
            and tuple(k_shape) == tuple(v_shape) and k_shape[-1] % 128 == 0)


def _paged_rows_kernel(layer_ref, table_ref, len_ref, q_ref, *refs, fold):
    """The schedule of the paged decode kernels, over one pool or two
    (``refs``: the pools in HBM, the output, a VMEM buffer a pool, the
    copies' semaphores; the LAST pool's buffer is the one a fold takes its
    values from). Every row of the batch, one after the other: a row's
    pages are copied to VMEM a block of ``ppb`` at a time, only those that
    hold a position the row attends, the next block (the next row's first,
    at a row's end) in flight while ``fold(q, bufs, slot, length, blk, (m,
    l, acc)) -> (m, l, acc)`` takes this one into the row's running
    softmax (float32 maximum, denominator and accumulator)."""
    n_pools = (len(refs) - 2) // 2
    pools, o_ref = refs[:n_pools], refs[n_pools]
    bufs, sems = refs[n_pools + 1:-1], refs[-1]
    n_rows, n_heads, _ = q_ref.shape
    ppb, ps = bufs[0].shape[1:3]
    pps = table_ref.shape[1]
    bk = ppb * ps
    lyr = layer_ref[0]

    def block_copies(row, blk, slot, act):
        """Start or wait for the copies of the pages of block ``blk`` of
        ``row`` that hold a position the row attends."""
        for p in range(ppb):
            page_no = blk * ppb + p

            @pl.when(page_no * ps < len_ref[row])
            def _():
                page = table_ref[row, jnp.minimum(page_no, pps - 1)]
                for i, (hbm, buf) in enumerate(zip(pools, bufs)):
                    act(pltpu.make_async_copy(
                        hbm.at[lyr, page], buf.at[slot, p],
                        sems.at[i, slot]))

    # a page that is not copied leaves what its place in the buffer held,
    # under a weight of exactly 0.0: that has to be a number
    bufs[-1][...] = jnp.zeros_like(bufs[-1])
    block_copies(0, 0, 0, lambda c: c.start())

    def row_body(row, slot):
        length = len_ref[row]
        n_blocks = lax.div(length + bk - 1, bk)
        q = q_ref[row]

        def block_body(blk, carry):
            *folded, slot = carry
            last = blk + 1 == n_blocks
            nxt_row = jnp.where(last, row + 1, row)
            nxt_blk = jnp.where(last, 0, blk + 1)

            @pl.when(nxt_row < n_rows)
            def _():
                block_copies(jnp.minimum(nxt_row, n_rows - 1), nxt_blk,
                             1 - slot, lambda c: c.start())

            block_copies(row, blk, slot, lambda c: c.wait())
            return (*fold(q, bufs, slot, length, blk, folded), 1 - slot)

        m, l, acc, slot = lax.fori_loop(
            0, n_blocks, block_body,
            (jnp.full((n_heads, 1), NEG_INF, jnp.float32),
             jnp.zeros((n_heads, 1), jnp.float32),
             jnp.zeros((n_heads, o_ref.shape[-1]), jnp.float32), slot))
        o_ref[row] = (acc / l).astype(o_ref.dtype)
        return slot

    lax.fori_loop(0, n_rows, row_body, 0)


def _running_softmax(s, m, l):
    """A block's masked scores ``s`` [heads, columns] into a row's running
    maximum ``m`` and denominator ``l``: (the new maximum, the new
    denominator, the block's weights, what the accumulator so far is
    scaled by)."""
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    e = jnp.exp(s - m_new)
    corr = jnp.exp(m - m_new)
    return m_new, corr * l + jnp.sum(e, axis=1, keepdims=True), e, corr


def _fold_heads(q, bufs, slot, length, blk, carry, *, scale, rep):
    """A block of pages that lie ``[page_size, g, hd]``, heads inside
    positions, read FLAT, ``[positions x g, hd]``: every query head meets
    every kv head's keys in one product and the columns of the other
    groups are masked with the positions past the row's length. The g-fold
    product is the price of leaving the pools as they are stored; it is
    the MXU's, which a decode step leaves idle."""
    m, l, acc = carry
    k_buf, v_buf = bufs
    n_heads, hd = q.shape
    _, ppb, ps, g, _ = k_buf.shape
    bk = ppb * ps
    k = k_buf[slot].reshape(bk * g, hd)
    v = v_buf[slot].reshape(bk * g, hd)
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    # column c of the block: position c // g, kv head c % g
    cols = lax.broadcasted_iota(jnp.int32, (1, bk * g), 1)
    heads = lax.broadcasted_iota(jnp.int32, (n_heads, 1), 0)
    seen = (lax.rem(cols, g) == lax.div(heads, rep)) \
        & (lax.div(cols, g) < length - blk * bk)
    s = jnp.where(seen, s, NEG_INF)
    m_new, l, e, corr = _running_softmax(s, m, l)
    acc = corr * acc + lax.dot_general(
        e.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return m_new, l, acc


# what a kernel may hold in VMEM where nothing is asked of the compiler:
# v5e's scoped default, 16 MiB of the core's 128
_VMEM_DEFAULT = 16 * 2 ** 20


def _vmem_asked(held):
    """The ``pallas_call`` arguments of a kernel whose blocks hold ``held``
    bytes of VMEM: nothing under three quarters of the default limit, else
    what it holds and 8 MiB."""
    if held <= 3 * _VMEM_DEFAULT // 4:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=held + 8 * 2 ** 20)}


def _paged_decode_call(name, fold, q, pools, layer, table, lengths,
                       out_width, block_keys):
    """One program over the batch's rows (``_paged_rows_kernel`` with
    ``fold``): layer, table and lengths are scalar-prefetch operands, the
    pools stay in HBM in the layout they are stored in, a block of
    ``block_keys`` positions of each in VMEM twice. ``lengths`` is
    held to 1 and to the table's positions. The queries and the results
    are whole in VMEM. Where those and the blocks pass three quarters of
    the default limit the call asks for what it holds and 8 MiB:
    DeepSeek-V3's 64 rows x 128 heads x 640 are 10.5 MB of queries and 8.4
    MB of results, 19.25 MiB held, which Mosaic refuses under the default
    16 (Mistral's queries are 0.13 MB, xing4's 0.66: nothing is asked, and
    their programs' text stays what it was)."""
    n_rows, n_heads, _ = q.shape
    ps = pools[0].shape[2]
    lengths = jnp.clip(lengths, 1, table.shape[1] * ps)
    ppb = max(1, block_keys // ps)
    out = (n_rows, n_heads, out_width)
    buffers = [(2, ppb) + pool.shape[2:] for pool in pools]
    held = (q.size + int(np.prod(out))) * q.dtype.itemsize + sum(
        int(np.prod(shape)) * pool.dtype.itemsize
        for shape, pool in zip(buffers, pools))
    return _pcall(
        functools.partial(_paged_rows_kernel, fold=fold),
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[pl.BlockSpec(q.shape, lambda i, *_: (0, 0, 0))]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
            out_specs=pl.BlockSpec(out, lambda i, *_: (0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM(shape, pool.dtype)
                for shape, pool in zip(buffers, pools)]
            + [pltpu.SemaphoreType.DMA((len(pools), 2))]),
        out_shape=jax.ShapeDtypeStruct(out, q.dtype),
        **_vmem_asked(held),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), table.astype(jnp.int32),
      lengths.astype(jnp.int32), q, *pools)


def paged_gqa_decode(q, k_pool, v_pool, layer, table, lengths):
    """One decode step's attention of layer ``layer`` read off the cache
    pools as they are stored. q [B, heads, hd]; k_pool, v_pool [L, pages,
    page_size, g, hd] (left in HBM, never copied whole); table [B,
    pages_per_seq] int32; lengths [B] int32 (held to 1 and to the table's
    positions): row b attends the first ``lengths[b]`` positions of its
    pages ``table[b]`` and nothing else, so its result depends on those
    pages and that length alone (float32 scores and accumulator, products
    in the cache's type). Returns [B, heads, hd] in q's type."""
    _, n_heads, hd = q.shape
    if not paged_gqa_usable(k_pool.shape, v_pool.shape):
        return _ref_paged_attention(q, k_pool, v_pool, layer, table,
                                    lengths, k_pool.shape[3], hd ** -0.5)
    fold = functools.partial(_fold_heads, scale=hd ** -0.5,
                             rep=n_heads // k_pool.shape[3])
    return _paged_decode_call("paged_gqa_decode", fold, q, (k_pool, v_pool),
                              layer, table, lengths, hd, PAGED_BLOCK_KEYS)


def paged_flat_usable(k_shape, v_shape, n_kv):
    """The gate of ``paged_flat_decode``: the backend runs Pallas kernels,
    and the pools are ``[L, pages, page_size, n_kv * dk]`` keys beside
    ``[.., n_kv * dv]`` values, an entry FLAT in its page: the keys' width
    whole lane tiles (a head's own need not be: 192), a value head's too."""
    return (_use_pallas() and len(k_shape) == len(v_shape) == 4
            and tuple(k_shape[:3]) == tuple(v_shape[:3])
            and k_shape[3] % 128 == 0 and k_shape[3] % n_kv == 0
            and v_shape[3] % (128 * n_kv) == 0)


def paged_packed_usable(k_shape, v_shape, n_kv):
    """The gate of ``paged_flat_decode``'s PACKED form, beside
    ``paged_flat_usable`` and in its form: the same flat pools, the keys'
    width whole lane tiles, and a value head NARROWER than a lane tile that
    divides it (64: two heads a tile), the key/value heads filling whole
    tiles (8 heads of 64: four)."""
    dv = v_shape[3] // n_kv if len(v_shape) == 4 and n_kv else 0
    return (_use_pallas() and len(k_shape) == len(v_shape) == 4
            and tuple(k_shape[:3]) == tuple(v_shape[:3])
            and k_shape[3] % 128 == 0 and k_shape[3] % n_kv == 0
            and v_shape[3] % n_kv == 0 and 0 < dv < 128 and 128 % dv == 0
            and v_shape[3] % 128 == 0)


def _fold_flat(q, bufs, slot, length, blk, carry, *, scale, g):
    """A block of pages whose entries lie FLAT, ``[page_size, g * dk]``
    keys and ``[page_size, g * dv]`` values, against a ZERO-EXPANDED
    query, [heads, g * dk]: a head's ``dk`` values in its own group's
    columns and zeros in the others', so ONE product with the block as it
    lies gives [heads, positions] scores, whatever ``dk`` is in lane tiles
    (192 is one and a half: a head's slice of a key would be re-laid),
    with no column of another group to mask. g-fold products of zeros, the
    MXU's. A group's heads then meet their own ``dv`` columns of the
    values, a whole-tile slice."""
    m, l, acc = carry
    k_buf, v_buf = bufs
    rep = q.shape[0] // g
    _, ppb, ps, kw = k_buf.shape
    bk, dv = ppb * ps, v_buf.shape[3] // g
    s = lax.dot_general(q, k_buf[slot].reshape(bk, kw),
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    cols = lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    s = jnp.where(cols < length - blk * bk, s, NEG_INF)
    m_new, l, e, corr = _running_softmax(s, m, l)
    acc = corr * acc + jnp.concatenate([
        lax.dot_general(
            e[i * rep:(i + 1) * rep].astype(v_buf.dtype),
            v_buf[slot, :, :, i * dv:(i + 1) * dv].reshape(bk, dv),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        for i in range(g)])
    return m_new, l, acc


def paged_flat_decode(q, k_pool, v_pool, layer, table, lengths, sink=None):
    """``paged_gqa_decode`` for pools whose entries lie flat in their
    pages, keys and values of unequal widths: q [B, heads, dk]; k_pool [L,
    pages, page_size, g * dk]; v_pool [.., g * dv] (a model that mixes
    kinds of layer stores its sequence kind so: _PagedRunner). The same
    schedule and the same promise: a row's result depends on its pages
    and its length alone. Scores are scaled by ``dk ** -0.5``. ``sink``
    [heads]: one more column of each head's denominator, which no kernel
    takes. Returns [B, heads, dv] in q's type.

    Where a value head is a PART of a lane tile (``paged_packed_usable``:
    64, two heads a tile) the kernel is the PACKED form,
    ``paged_flat_packed_decode`` in a trace: the scores as ever, one
    product of the zero-expanded query with the block of keys as it lies,
    at any key width; the values met a LANE TILE at a time, the ``pack =
    128 // dv`` key/value heads whose values share a tile and all their
    query heads in one product with that tile as it lies, so that no
    half-tile slice of a page is re-laid (``_fold_flat`` over ``g / pack``
    groups of 128-wide values: pack-fold products of columns nobody
    reads, the MXU's); a head's row then holds its own ``dv`` columns
    beside its tile-mates' and is cut to them here, outside the kernel."""
    n_rows, n_heads, dk = q.shape
    g = k_pool.shape[3] // dk
    packed = paged_packed_usable(k_pool.shape, v_pool.shape, g)
    if sink is not None or not (packed or paged_flat_usable(
            k_pool.shape, v_pool.shape, g)):
        return _ref_paged_attention(q, k_pool, v_pool, layer, table,
                                    lengths, g, dk ** -0.5, sink)
    dv = v_pool.shape[3] // g
    pack = 128 // dv if packed else 1       # value heads a lane tile
    tiles, rep = g // pack, n_heads // g
    # the kernel slices the [heads, keys] weights a tile of values, so the
    # heads of one (pack groups of rep) go in as whole sublane tiles: 6 a
    # group (48 over 8) as 8, the two behind them zero queries whose
    # results are cut off again (their weights are uniform over the row's
    # own positions: a number, and nobody's). 16 and 8 a group, one tile,
    # and one head a tile (a slice of one row) go in as they are
    padded = rep if tiles == 1 or pack * rep == 1 else next(
        r for r in range(rep, rep + _SUBLANES + 1)
        if (pack * r) % _SUBLANES == 0)
    if padded != rep:
        q = jnp.pad(q.reshape(n_rows, g, rep, dk),
                    ((0, 0), (0, 0), (0, padded - rep), (0, 0))).reshape(
                        n_rows, g * padded, dk)
    own = (jnp.arange(g * padded)[:, None] // padded
           == jnp.arange(g)[None])                          # [heads, g]
    expanded = jnp.where(own[None, :, :, None], q[:, :, None], 0).reshape(
        n_rows, g * padded, g * dk)
    out = _paged_decode_call(
        "paged_flat_packed_decode" if packed else "paged_flat_decode",
        functools.partial(_fold_flat, scale=dk ** -0.5, g=tiles),
        expanded, (k_pool, v_pool), layer, table, lengths, pack * dv,
        PAGED_FLAT_BLOCK_KEYS)
    if packed:      # [.., head in the tile, rep, ITS part of the tile's]
        out = out.reshape(n_rows, tiles, pack, padded, pack, dv)
        out = jnp.stack([out[:, :, j, :, j] for j in range(pack)], axis=2)
    if padded != rep:
        out = out.reshape(n_rows, g, padded, dv)[:, :, :rep]
    return out.reshape(n_rows, n_heads, dv)


def paged_latent_usable(pool_shapes):
    """The gate of ``paged_latent_decode``: the backend runs Pallas
    kernels, and the cache is ONE pool ``[L, pages, page_size, entry]``
    whose entry is whole lane tiles (latent attention's 512 + 64 stored
    640 wide: _PagedRunner)."""
    return (_use_pallas() and len(pool_shapes) == 1
            and len(pool_shapes[0]) == 4 and pool_shapes[0][3] % 128 == 0)


def _fold_latent(q, bufs, slot, length, blk, carry, *, scale, width):
    """A block of a latent model's pages, ``[page_size, entry]``, copied
    ONCE and met twice: whole, as the keys of the absorbed query (``q_abs
    | q_pe`` and zeros against the entry's padding: one product with the
    block as it lies), and at its leading ``width`` columns (the latent's,
    whole lane tiles) as the values the weights attend."""
    m, l, acc = carry
    (buf,) = bufs
    _, ppb, ps, entry = buf.shape
    bk = ppb * ps
    s = lax.dot_general(q, buf[slot].reshape(bk, entry),
                        (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    cols = lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    s = jnp.where(cols < length - blk * bk, s, NEG_INF)
    m_new, l, e, corr = _running_softmax(s, m, l)
    acc = corr * acc + lax.dot_general(
        e.astype(buf.dtype), buf[slot, :, :, :width].reshape(bk, width),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    return m_new, l, acc


def paged_latent_decode(q, pool, layer, table, lengths, *, scale, width):
    """``paged_gqa_decode`` for a latent model's ONE pool, [L, pages,
    page_size, entry], the absorbed form: q [B, heads, entry] is ``q_abs |
    q_pe`` with zeros against the entry's padding, and a row's result is
    the latent its weights attend, [B, heads, ``width``] in q's type:
    the entry's leading ``width`` columns, the whole lane tiles that hold
    the latent (the cut to its rank and the value half of the expansion
    are the caller's). A block of pages is copied to VMEM once and serves
    as keys and as values. The same schedule and the same promise: a
    row's result depends on its pages and its length alone."""
    if not paged_latent_usable([pool.shape]):
        return _ref_paged_attention(q, pool, pool, layer, table, lengths,
                                    1, scale)[..., :width]
    return _paged_decode_call(
        "paged_latent_decode", functools.partial(
            _fold_latent, scale=scale, width=width),
        q, (pool,), layer, table, lengths, width, PAGED_LATENT_BLOCK_KEYS)


# ---------------------------------------------------------------------------
# prefill fold: a window of queries against one block of a row's keys, the
# scores never leaving VMEM
# ---------------------------------------------------------------------------

# queries and keys a tile (one grid step's [queries, keys] float32 scores
# live in VMEM and nowhere else), and the keys one call folds (what its
# caller gathers, or expands, at a time), by the chip: a grid step costs
# some 2 us beside its products, so 32 heads of 128 | 64 | 128 over 2,048
# queries and 2,048 keys all seen take 3.14 ms in tiles of 256 x 256 (14%
# of 197 TFLOP/s), 1.48 at 512 x 512 (29%), 0.906 at 512 x 1,024 and
# 0.785 at 1,024 x 1,024 (56%; MiMo's 64 heads over 4: 1.53 ms, 57%); 1,024
# x 2,048 reads 0.702 and needs a raised VMEM limit, and folds a diagonal
# block at full price where these tiles skip a quarter of it. Per key the
# call costs 0.402 us at 1,024 keys a visit, 0.385 at 2,048, 0.364 at
# 4,096 (the carry's trip through HBM, 0.115 ms a visit) (PERF.md section
# 6, PR 44)
PREFILL_BLOCK_Q = 1024
PREFILL_BLOCK_KEYS = 1024
PREFILL_VISIT_KEYS = 2048


def _tile(n, block):
    """The tile ``n`` positions are cut in: all of them where they are at
    most ``block``, else the largest of block, block / 2, ... (no smaller
    than a lane tile, or ``block`` itself where that is smaller) that
    divides them; None where none does."""
    if n <= block:
        return n
    d = block
    while d >= min(block, 128):
        if n % d == 0:
            return d
        d //= 2
    return None


def prefill_fold_usable(t, kb, *widths):
    """The gate of ``prefill_fold``: the backend runs Pallas kernels, every
    width (keys, values, a shared key part) is whole lane tiles, and the
    window's ``t`` queries and a call's ``kb`` keys cut into tiles."""
    return bool(_use_pallas() and all(w % 128 == 0 for w in widths)
                and _tile(t, PREFILL_BLOCK_Q)
                and _tile(kb, PREFILL_BLOCK_KEYS))


def _prefill_fold_kernel(pos0_ref, k0_ref, *refs, scale, bq, bk, shared):
    """One (row, head, query tile) of ``prefill_fold`` against key tile
    ``program_id(3)``: the running softmax lives in the OUTPUT blocks,
    which stay in VMEM over the key tiles (taken from the carry at the
    first, written back after the last). A tile no query of which sees a
    key is skipped; one every query sees whole is not masked."""
    if shared:
        q_ref, k_ref, v_ref, q2_ref, k2_ref, acc_in, ml_in, acc_ref, \
            ml_ref = refs
    else:
        q_ref, k_ref, v_ref, acc_in, ml_in, acc_ref, ml_ref = refs
    ki = pl.program_id(3)
    q_lo = pos0_ref[pl.program_id(0)] + pl.program_id(2) * bq
    k_lo = k0_ref[0] + ki * bk

    @pl.when(ki == 0)
    def _():
        acc_ref[...] = acc_in[...]
        ml_ref[...] = ml_in[...]

    def fold(masked):
        s = lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        if shared:
            s = s + lax.dot_general(
                q2_ref[0], k2_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
        s = s * scale
        if masked:      # key k_lo + c at or before query q_lo + r
            ahead = lax.broadcasted_iota(jnp.int32, (bq, bk), 1) \
                - lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            s = jnp.where(ahead <= q_lo - k_lo, s, NEG_INF)
        ml = ml_ref[0, 0]
        m, l = ml[:, :1], ml[:, 1:2]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        w = jnp.exp(s - m_new)
        a = jnp.exp(m - m_new)
        acc_ref[0, 0] = acc_ref[0, 0] * a + lax.dot_general(
            w.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        lane = lax.broadcasted_iota(jnp.int32, ml.shape, 1)
        ml_ref[0, 0] = jnp.where(
            lane == 0, m_new, l * a + jnp.sum(w, axis=1, keepdims=True))

    whole = k_lo + bk - 1 <= q_lo       # the tile's last key, its first query
    pl.when(whole)(lambda: fold(False))
    pl.when(jnp.logical_and(jnp.logical_not(whole),
                            k_lo <= q_lo + bq - 1))(lambda: fold(True))


def prefill_fold(q, k, v, pos0, k0, acc, ml, *, scale, shared=None):
    """One block of keys folded into the running softmax of a prefill
    window's attention, the [queries, keys] scores, their exponents and
    the weights in VMEM alone (``_PagedRunner._gqa_blocked`` and
    ``_latent_expanded`` call it once a block of a row's pages; they are
    its reference where it is not usable). q [B, T, heads * dk], a head's
    width whole lane tiles; k [B, kb, g * dk] and v [B, kb, g * dv], ``g``
    key/value heads each shared by heads / g query heads; ``shared``: (q2
    [B, T, heads * d2], k2 [B, kb, d2]), a second part of every head's key
    that all heads share (latent attention's rotated part), its product
    added to the scores. Query i of row b stands at ``pos0[b] + i`` and
    key j at ``k0 + j`` (both traced); a query sees the keys at or before
    it. The carry: acc [B, heads, T, dv] float32 and ml [B, heads, T, 128]
    float32 (lane 0 the running maximum, lane 1 the denominator), aliased
    to the results. Operands in their own type, scores, maximum, sum and
    accumulator float32, the weights in v's type for their product."""
    b, t, _ = q.shape
    kb = k.shape[1]
    n_heads, dv = acc.shape[1], acc.shape[3]
    dk = q.shape[2] // n_heads
    # query heads a key head, and a value head (the same, but where a
    # caller hands the keys of all its groups as one head's)
    rep_k, rep_v = (n_heads * w // x.shape[2] for w, x in ((dk, k), (dv, v)))
    bq, bk = _tile(t, PREFILL_BLOCK_Q), _tile(kb, PREFILL_BLOCK_KEYS)
    nk = kb // bk

    def last_seen(bi, qi, pos0_ref, k0_ref):
        # the last key tile a query of this tile sees: the tiles behind it
        # are not fetched (the same block index asks for no new copy)
        return jnp.clip((pos0_ref[bi] + (qi + 1) * bq - 1 - k0_ref[0])
                        // bk, 0, nk - 1)

    def q_spec(width):
        return pl.BlockSpec((1, bq, width),
                            lambda bi, h, qi, ki, *_: (bi, qi, h))

    def k_spec(width, col):
        return pl.BlockSpec(
            (1, bk, width), lambda bi, h, qi, ki, *s: (
                bi, jnp.minimum(ki, last_seen(bi, qi, *s)), col(h)))

    def carry_spec(width):
        return pl.BlockSpec((1, 1, bq, width),
                            lambda bi, h, qi, ki, *_: (bi, h, qi, 0))

    operands = [q, k, v]
    in_specs = [q_spec(dk), k_spec(dk, lambda h: h // rep_k),
                k_spec(dv, lambda h: h // rep_v)]
    if shared is not None:
        d2 = shared[1].shape[2]
        operands += list(shared)
        in_specs += [q_spec(d2), k_spec(d2, lambda h: 0)]
    n_in = 2 + len(operands)
    return _pcall(
        functools.partial(_prefill_fold_kernel, scale=scale, bq=bq, bk=bk,
                          shared=shared is not None),
        name="prefill_fold",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_heads, t // bq, nk),
            in_specs=in_specs + [carry_spec(dv), carry_spec(128)],
            out_specs=[carry_spec(dv), carry_spec(128)]),
        out_shape=[jax.ShapeDtypeStruct(acc.shape, acc.dtype),
                   jax.ShapeDtypeStruct(ml.shape, ml.dtype)],
        input_output_aliases={n_in: 0, n_in + 1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    )(pos0.astype(jnp.int32), jnp.reshape(k0, (1,)).astype(jnp.int32),
      *operands, acc, ml)


# ---------------------------------------------------------------------------
# jax reference path (CPU tests, backward, and lse building block)
# ---------------------------------------------------------------------------


def _ref_attention_lse(q, k, v, scale, causal, bias=None):
    """[..., T, D] attention returning (out, lse)."""
    s = jnp.einsum("...qd,...kd->...qk", q, k).astype(jnp.float32) * scale
    if bias is not None:
        s = s + bias
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        rows = lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
        cols = lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        s = jnp.where(rows + (tk - tq) >= cols, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("...qk,...kd->...qd", (p / l).astype(v.dtype), v)
    lse = (m + jnp.log(l))[..., 0]
    return o, lse


def masked_attention(q, k_all, v_all, q_pos, k_pos=None, window=None,
                     sink=None, scale=None):
    """GQA attention of queries q [B, T, heads, kd] at ``q_pos`` [B, T]
    over keys [B, K, g, kd] and values [B, K, g, vd] in the cache's type,
    accumulated in float32: float32 scores and softmax, the weights
    rounded to the cache's type before they meet the values. Key j of row
    b is position ``k_pos[b, j]`` (None: j; negative: no key there); a
    query sees a key at or before itself and, with ``window``, fewer than
    ``window`` positions back. ``sink`` [heads]: one more column of the
    softmax's denominator a head, that adds to nothing else. Scores are
    scaled by ``scale`` (None: ``kd ** -0.5``). Returns [B, T, heads *
    vd] in q's type."""
    b, t = q_pos.shape
    g, n_keys = k_all.shape[2], k_all.shape[1]
    f32 = jnp.float32
    qg = q.reshape(b, t, g, q.shape[2] // g, q.shape[-1])
    kp = jnp.arange(n_keys, dtype=jnp.int32)[None] \
        if k_pos is None else k_pos
    back = q_pos[:, :, None] - kp[:, None, :]            # [B, T, K]
    mask = (back >= 0) & (kp[:, None, :] >= 0)
    if window is not None:
        mask = mask & (back < window)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k_all,
                   preferred_element_type=f32) \
        * (q.shape[-1] ** -0.5 if scale is None else scale)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    if sink is not None:
        sk = sink.astype(f32).reshape(1, g, -1, 1, 1)
        m = jnp.maximum(m, sk)
    e = jnp.exp(s - m)
    l = jnp.sum(e, axis=-1, keepdims=True)
    if sink is not None:
        l = l + jnp.exp(sk - m)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", (e / l).astype(v_all.dtype),
                     v_all, preferred_element_type=f32)
    return out.astype(q.dtype).reshape(b, t, -1)


def _ref_paged_attention(q, k_pool, v_pool, layer, table, lengths, g,
                         scale, sink=None):
    """What the three paged decode kernels promise, in plain jax.numpy,
    for pools of any width: q [B, heads, dk] over layer ``layer`` of
    ``k_pool`` and ``v_pool`` [L, pages, page_size, ...], an entry ``g``
    key/value heads of ``dk`` | ``dv`` (inside positions or flat; a latent
    model's one pool is both, ``g`` 1). Each row's pages are gathered in
    its table's order (``pool[layer, table]``: a layer's own [B, kmax,
    ...], never the layers') and attended to the row's ``lengths`` (held
    to 1 and to the table's positions) by ``masked_attention``, a sink
    too, which no kernel takes. Returns [B, heads, dv] in q's type."""
    b, n_heads, _ = q.shape
    kmax = table.shape[1] * k_pool.shape[2]
    keys = k_pool[layer, table].reshape(b, kmax, g, -1)
    values = keys if v_pool is k_pool \
        else v_pool[layer, table].reshape(b, kmax, g, -1)
    last = jnp.clip(lengths, 1, kmax)[:, None] - 1
    return masked_attention(q[:, None], keys, values, last, sink=sink,
                            scale=scale).reshape(b, n_heads, -1)


def attention_with_lse(q, k, v, scale=None, causal=False):
    """Per-chunk attention that also returns log-sum-exp — used by ring
    attention to exactly merge partial softmax results across shards.
    q,k,v: [B, H, T, D]."""
    scale = scale or (1.0 / np.sqrt(q.shape[-1]))
    return _ref_attention_lse(q, k, v, scale, causal)


# ---------------------------------------------------------------------------
# public differentiable entry
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal=True, scale=None):
    """q,k,v: [B, H, T, D] → [B, H, T, D]."""
    o, _ = _flash_fwd(q, k, v, causal, scale)
    return o


def _flash_fwd(q, k, v, causal, scale):
    sc = scale or (1.0 / np.sqrt(q.shape[-1]))
    b, h, t, d = q.shape
    if _use_pallas() and _kernel_shapes_ok(q, k):
        qf = q.reshape(b * h, t, d)
        kf = k.reshape(b * h, k.shape[2], d)
        vf = v.reshape(b * h, v.shape[2], d)
        o, lse128 = _flash_fwd_pallas(qf, kf, vf, sc, causal)
        # store the residual COMPACT ([B,H,T] f32, not the lane-padded
        # [B,H,T,128] the kernel emits): with remat off the residual
        # persists through fwd+bwd per layer, and the padded form is
        # 128x the bytes actually needed. The backward re-broadcasts
        # per row-block; that copy is transient and fuses.
        return o.reshape(q.shape), lse128[:, :, 0].reshape(b, h, t)
    o, lse = _ref_attention_lse(q, k, v, sc, causal)
    return o, lse


def _flash_vjp_fwd(q, k, v, causal, scale):
    o, lse = _flash_fwd(q, k, v, causal, scale)
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(causal, scale, res, do):
    q, k, v, o, lse = res
    sc = scale or (1.0 / np.sqrt(q.shape[-1]))
    b, h, t, d = q.shape
    if _use_pallas() and _kernel_shapes_ok(q, k):
        fold = lambda a: a.reshape(b * h, a.shape[2], d)  # noqa: E731
        lse128 = jnp.broadcast_to(
            lse.reshape(b * h, t)[..., None], (b * h, t, 128))
        dq, dk, dv = _flash_bwd_pallas(
            fold(q), fold(k), fold(v), fold(o),
            lse128.astype(jnp.float32), fold(do), sc, causal)
        return dq.reshape(q.shape), dk.reshape(k.shape), \
            dv.reshape(v.shape)

    def ref(q, k, v):
        return _ref_attention_lse(q, k, v, sc, causal)[0]

    _, vjp = jax.vjp(ref, q, k, v)
    return vjp(do)


flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)
