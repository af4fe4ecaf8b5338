"""Mixture-of-Experts FFN op — expert-parallel over the mesh 'ep' axis.

The reference has no MoE (it predates them); this extends the framework
the way its fused contrib ops extend the op set, but designed TPU-first
after the GShard/Switch recipe: top-k gating with a *static* per-expert
capacity, dispatch/combine expressed as einsums (MXU-friendly, static
shapes), and expert weights sharded over the mesh 'ep' axis so GSPMD
inserts the token all_to_all over ICI automatically via sharding
constraints on the [experts, capacity, dim] intermediates.

Everything is one fused XLA program: no per-expert Python loops, no
dynamic shapes, no host round-trips.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.registry import register_op
from . import pallas_attention as _pa

__all__ = ["top_k_gating", "moe_apply", "moe_route", "moe_load",
           "moe_apply_sorted", "moe_apply_no_drop", "moe_apply_no_drop_q",
           "few_rows_usable", "moe_apply_few_rows",
           "grouped_rows_usable", "moe_grouped_rows"]

# rows the few-rows kernel takes: one pass of the MXU's 128-row tile
FEW_ROWS = 128

# Rows of the sorted pairs that one grid step of the grouped-rows kernel
# multiplies with an expert: the MXU's tile (a taller one holds an expert's
# matrices longer for every group edge inside it).
GROUPED_ROW_TILE = 128

# What two experts' matrices may take of VMEM in that kernel, of the 128 MiB
# a v5e core has (the rows' tiles and the products' results come on top):
# xing4's experts, 3,584 x 1,024, are 44 MB twice over and go through it
# whole at half the three ragged_dot's time (PERF.md section 6, PR 56).
GROUPED_VMEM = 48 * 2 ** 20

# Rows of a share's sorted pairs that one trip of the un-sort's loop sums
# back to their tokens (moe_apply_sorted): swept on the chip at 256 / 512 /
# 1,024 (PERF.md section 6, PR 48).
UNSORT_BLOCK = 256


def _ep_constraint(x, spec):
    """Pin ``x``'s sharding when the active mesh has a real 'ep' axis, so
    GSPMD materialises the expert all_to_all; no-op otherwise."""
    from ..parallel.mesh import current_mesh
    mesh = current_mesh()
    if mesh is None or mesh.axes.get("ep", 1) <= 1:
        return x
    if x.shape[0] % mesh.axes["ep"] != 0:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh.mesh, P(*spec)))


def top_k_gating(probs, top_k, capacity):
    """GShard-style gating. probs: [T, E] router softmax.

    Returns (combine [T, E, C] float, dispatch [T, E, C] bool, aux):
    combine carries the (renormalised) gate weight of token t in expert
    e's capacity slot c; tokens past an expert's capacity are dropped
    (their combine row is zero — the residual stream carries them, as in
    Switch). aux is the Switch load-balancing loss E * sum_e(f_e * P_e).
    """
    t, e = probs.shape
    gates, idx = jax.lax.top_k(probs, top_k)               # [T, K]
    gates = gates / jnp.maximum(
        jnp.sum(gates, axis=-1, keepdims=True), 1e-9)

    combine = jnp.zeros((t, e, capacity), dtype=probs.dtype)
    counts = jnp.zeros((e,), dtype=jnp.int32)
    for k in range(top_k):
        onehot = jax.nn.one_hot(idx[:, k], e, dtype=jnp.int32)   # [T, E]
        # position of each token within its chosen expert's queue,
        # offset by tokens already enqueued by earlier k-slots
        pos = jnp.cumsum(onehot, axis=0) - 1 + counts[None, :]
        pos_k = jnp.sum(pos * onehot, axis=-1)                   # [T]
        counts = counts + jnp.sum(onehot, axis=0)
        fits = (pos_k < capacity).astype(probs.dtype) * gates[:, k]
        slot = jax.nn.one_hot(pos_k, capacity, dtype=probs.dtype)
        combine = combine + (fits[:, None, None]
                             * onehot.astype(probs.dtype)[:, :, None]
                             * slot[:, None, :])
    dispatch = combine > 0

    # Switch aux loss on the top-1 assignment: mean prob vs dispatch freq
    top1 = jax.nn.one_hot(idx[:, 0], e, dtype=probs.dtype)
    aux = e * jnp.sum(jnp.mean(probs, axis=0) * jnp.mean(top1, axis=0))
    return combine, dispatch, aux


def _router_probs(xt, wg):
    """Router in f32 for stable softmax/top-k regardless of dtype."""
    logits = jnp.dot(xt.astype(jnp.float32), wg.astype(jnp.float32))
    return jax.nn.softmax(logits, axis=-1)


def moe_apply(xt, wg, w_gate, w_up, w_down, top_k, cap_factor):
    """Training-form MoE on flat tokens xt [T, D]: GShard top-k gating
    with static capacity (tokens past capacity fall back to the
    residual stream). Returns (out [T, D], aux scalar)."""
    t = xt.shape[0]
    e = w_up.shape[0]
    capacity = max(1, int(cap_factor * t * top_k / e))
    probs = _router_probs(xt, wg)
    combine, dispatch, aux = top_k_gating(probs, top_k, capacity)
    cdt = xt.dtype
    expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(cdt), xt)
    expert_in = _ep_constraint(expert_in, ("ep", None, None))
    gate_h = jnp.einsum("ecd,edh->ech", expert_in, w_gate)
    up_h = jnp.einsum("ecd,edh->ech", expert_in, w_up)
    h = (gate_h * jax.nn.sigmoid(gate_h)) * up_h
    expert_out = jnp.einsum("ech,ehd->ecd", h, w_down)
    expert_out = _ep_constraint(expert_out, ("ep", None, None))
    out = jnp.einsum("tec,ecd->td", combine.astype(cdt), expert_out)
    return out, aux


def moe_route(xt, wg, top_k, scoring="softmax", bias=None, scale=1.0,
              n_group=1, topk_group=1, eps=1e-20):
    """Exact top-k routing of flat tokens xt [T, D] over the router's
    whole width E (``wg`` [D, E], however many of those experts are held
    here), in float32 whatever the model's dtype (the products at
    "highest" precision: a bf16 pass over the router reorders near-ties).
    Returns (experts [T, K] int32, gates [T, K] float32).

    ``softmax``: the K largest probabilities, renormalised.
    ``sigmoid``: scores ``sigmoid(x Wg)``; the K largest of
    ``scores + bias`` are picked (``bias`` steers selection only), their
    own scores are renormalised (divided by their sum + ``eps``, the
    model's own constant: 1e-20 in DeepSeek-V3's rule, 1e-6 in the LFM2
    family's) and multiplied by ``scale``. With
    ``n_group`` > 1 the selection is group-limited (DeepSeek-V3,
    arXiv:2412.19437): the experts are ``n_group`` equal runs, a group
    scores the sum of its two largest ``scores + bias``, the
    ``topk_group`` best groups are kept and the others' selection scores
    set to 0 before the K largest are taken."""
    logits = jnp.dot(xt.astype(jnp.float32), wg.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if scoring == "softmax":
        gates, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
        return idx, scale * gates / jnp.maximum(
            jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    if scoring != "sigmoid":
        raise ValueError(f"unknown router scoring {scoring!r}")
    scores = jax.nn.sigmoid(logits)
    picked = scores if bias is None else scores + bias.astype(jnp.float32)
    if n_group > 1:
        t, e = picked.shape
        grouped = picked.reshape(t, n_group, e // n_group)
        best = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        kept = jax.nn.one_hot(jax.lax.top_k(best, topk_group)[1], n_group,
                              dtype=jnp.bool_).any(axis=1)
        picked = jnp.where(kept[..., None], grouped, 0.0).reshape(t, e)
    idx = jax.lax.top_k(picked, top_k)[1]
    gates = jnp.take_along_axis(scores, idx, axis=-1)
    return idx, scale * gates / (
        jnp.sum(gates, axis=-1, keepdims=True) + eps)


def moe_load(idx, n_experts, valid=None, first=0):
    """Tokens each of the ``n_experts`` experts from ``first`` on was
    given, [n_experts] int32; picks outside that range count nowhere.
    ``valid`` [T] bool leaves padding and inactive rows out of the count
    (they are still computed: shapes are static)."""
    hits = jax.nn.one_hot(idx - first, n_experts,
                          dtype=jnp.int32).sum(axis=1)
    if valid is not None:
        hits = hits * valid.astype(jnp.int32)[:, None]
    return hits.sum(axis=0)


def _hidden_tile(d, f, itemsize):
    """The run of an expert's hidden width ``f`` that one grid step of the
    few-rows kernel takes: ``f`` itself, the expert uncut, where its three
    matrices fit VMEM's default limit twice over; else the widest run of
    whole lane tiles that divides ``f`` and keeps two runs' three blocks
    (one in flight, one multiplied) within ``GROUPED_VMEM``; None where
    not even one lane tile does."""
    if 6 * d * f * itemsize <= _pa._VMEM_DEFAULT:
        return f
    return max((tf for tf in range(128, f + 1, 128)
                if f % tf == 0 and 6 * d * tf * itemsize <= GROUPED_VMEM),
               default=None)


def few_rows_usable(t, w_gate, w_down, held=None):
    """The gate of ``moe_apply_few_rows``: the backend runs Pallas kernels,
    the rows are at most one MXU tile (a decode step's: a prefill window
    sorts its pairs), the widths whole lane tiles, one type for the three
    matrices, and a tile of an expert exists within the kernel's budget
    (``_hidden_tile``: 512-wide experts of a 2,048-wide model are 12 MB
    twice over and go uncut; DeepSeek-V3's 7,168 x 2,048 go 512 of their
    hidden width a grid step, MiMo's 4,096 x 2,048 go 1,024, xing4's
    3,584 x 1,024 whole under a raised limit). More rows than that are
    ``grouped_rows_usable``'s to admit. ``held`` refuses nothing: a share
    only changes which columns of the rows' weights are non-zero. It is
    taken so that whoever asks hands over what the call is given."""
    del held
    d, f = w_gate.shape[-2:]
    return (_pa._use_pallas() and t <= FEW_ROWS
            and d % 128 == 0 and f % 128 == 0
            and w_gate.dtype == w_down.dtype
            and _hidden_tile(d, f, w_gate.dtype.itemsize) is not None)


def _few_rows_kernel(layer_ref, touched_ref, n_ref, x_ref, c_ref, wg_ref,
                     wu_ref, wd_ref, o_ref, *, tiled):
    """Grid step ``i`` (``(i, j)`` where the hidden width is ``tiled``):
    the ``i``-th expert that a row reached, its three matrices (their
    ``j``-th runs of the hidden width) in VMEM, the next ones in flight,
    on ALL the rows; a row that did not pick it has weight 0.0 there and
    is left as it was. A run's ``h`` needs no other run, and its down
    product is added to the rows' result, which stays in VMEM from the
    first step to the last."""
    i = pl.program_id(0)
    first = i == 0
    if tiled:
        first &= pl.program_id(1) == 0

    @pl.when(first)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(i < n_ref[0])
    def _():
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
        h = ((g * jax.nn.sigmoid(g)) * u).astype(x.dtype)
        o_ref[...] += c_ref[...] * jnp.dot(
            h, wd_ref[...], preferred_element_type=jnp.float32)


def moe_apply_few_rows(xt, idx, gates, w_gate, w_up, w_down, layer=None,
                       held=None):
    """``moe_apply_sorted`` for A FEW ROWS (a decode step: 64 rows x 8
    picks over 256 experts are 2 rows an expert, and a grouped matmul
    over 2-row groups reads their weights at a third of the chip's
    bandwidth: PERF.md section 6, PR 55). No sort and no gather: the
    experts that a row reached are visited in ascending order, one a grid
    step, each multiplied with ALL the rows (at most one MXU tile of
    them: the products cost what the copy of the expert costs) and added
    under the rows' own weights for it, 0.0 for a row that did not pick
    it. An expert nobody picked is never copied. A row's result is the
    float32 sum of its own experts' outputs in ascending order of expert:
    it depends on no other row. Returns [T, D] in xt's dtype.

    With ``held`` = (first, width) the weights are one chip's share of an
    expert-parallel layer (``moe_apply_sorted``): the columns of the
    rows' weights are the E experts from ``first`` on, a pick outside
    them is no column, and what the absent experts would add is left out.
    A row none of whose picks is held comes back as zeros; so does every
    row of a step that reached no held expert, at the cost of the one
    block the pipeline fetches before it looks.

    An expert too wide for VMEM twice over goes ``_hidden_tile`` of its
    hidden width a grid step, an inner axis of the grid: a decode step
    has ONE row tile, so every run of every touched expert is copied
    exactly once either way, and a run's gate and up blocks are rows of
    ``tile * itemsize`` bytes of the stored matrices. Where the expert
    fits uncut the grid is ``(E,)`` and the blocks whole, the kernel PR
    55 measured. On the chip (PERF.md section 6, PR 61: 64 rows over 16
    experts of 7,168 x 2,048, all reached, 1.72 ms at 819 GB/s): runs of
    512 read 1.90 ms a call, 0.91 of the bandwidth, where the sort, the
    three ``ragged_dot`` and the un-sort read 2.36, 0.73; runs of 256 and
    128 read 1.97 and 1.90; the other cut, the CONTRACTION of gate and up
    in contiguous ``[1024, f]`` blocks into float32 scratch and then the
    down product in ``[512, d]`` blocks, 1.96 (1.93 at half those
    blocks): strided rows of 1 KB cost the copy nothing, and this form
    needs no scratch and no second phase. xing4's 3,584 x 1,024 whole
    under a raised limit read 1.11 ms for 37 experts against the sorted
    form's 1.29, and 1.20 cut in two."""
    t, d = xt.shape
    e, f = w_gate.shape[-3], w_gate.shape[-1]
    if layer is None:
        w_gate, w_up, w_down = (w[None] for w in (w_gate, w_up, w_down))
        layer = 0
    if held is not None:        # a pick of another chip's: no column
        idx = idx - held[0]
    # [T, E]: a row's weight for every expert held
    weights = jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.float32)
                      * gates[..., None].astype(jnp.float32), axis=1)
    reached = moe_load(idx, e) > 0
    n = jnp.sum(reached.astype(jnp.int32))
    touched = jnp.argsort(~reached, stable=True).astype(jnp.int32)
    # behind the last expert reached: itself again, so no copy is asked
    touched = jnp.where(jnp.arange(e) < n, touched,
                        touched[jnp.maximum(n - 1, 0)])
    rows = -(-t // 16) * 16             # whole tiles of the rows' type
    if rows != t:
        xt = jnp.pad(xt, ((0, rows - t), (0, 0)))
        weights = jnp.pad(weights, ((0, rows - t), (0, 0)))
    by_expert = weights.T[touched][..., None]               # [E, rows, 1]
    tf = _hidden_tile(d, f, w_gate.dtype.itemsize)
    tiled = tf != f
    in_vmem = 6 * d * tf * w_gate.dtype.itemsize \
        + rows * d * (xt.dtype.itemsize + 8)
    if tiled:
        # behind the last expert reached: its last run again
        last = f // tf - 1

        def run(i, j, n):
            return jnp.where(i < n[0], j, last)

        def gate_up(i, j, lyr, tch, n):
            return lyr[0], tch[i], 0, run(i, j, n)

        def down(i, j, lyr, tch, n):
            return lyr[0], tch[i], run(i, j, n), 0
    else:
        def gate_up(i, lyr, tch, n):
            return lyr[0], tch[i], 0, 0

        down = gate_up

    out = _pa._pcall(
        functools.partial(_few_rows_kernel, tiled=tiled),
        name="moe_few_rows",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(e, f // tf) if tiled else (e,),
            in_specs=[
                pl.BlockSpec((rows, d), lambda *_: (0, 0)),
                pl.BlockSpec((None, rows, 1), lambda i, *_: (i, 0, 0)),
                pl.BlockSpec((None, None, d, tf), gate_up),
                pl.BlockSpec((None, None, d, tf), gate_up),
                pl.BlockSpec((None, None, tf, d), down)],
            out_specs=pl.BlockSpec((rows, d), lambda *_: (0, 0))),
        out_shape=jax.ShapeDtypeStruct((rows, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * (1 + tiled),
            vmem_limit_bytes=in_vmem + 8 * 2 ** 20),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), touched,
      jnp.reshape(n, (1,)), xt, by_expert, w_gate, w_up, w_down)
    return out[:t].astype(xt.dtype)


def grouped_rows_usable(t, w_gate, w_down, held=None):
    """The gate of ``moe_grouped_rows``, beside ``few_rows_usable`` and in
    its form: the backend runs Pallas kernels, the rows are MORE than one
    MXU tile (a prefill window, or a decode step of more slots than that:
    128 rows or fewer keep the few-rows kernel), the widths whole lane
    tiles, one type for the three matrices, and an expert's three matrices
    WHOLE (their hidden width uncut) twice over within ``GROUPED_VMEM``,
    one in flight while one is multiplied (Ling's 2,560 x 768 are 23.6 MB
    twice over, xing4's 3,584 x 1,024 44 MB; DeepSeek-V3's 7,168 x 2,048
    and MiMo's 4,096 x 2,048 are 176 and 100 MB and keep ``ragged_dot``).
    ``held`` refuses nothing, as in ``few_rows_usable``: a share's pairs
    to absent experts sort behind the last held group, where no work item
    names them (``_work_items``). It is taken so that whoever asks hands
    over what the call is given."""
    del held
    d, f = w_gate.shape[-2:]
    return (_pa._use_pallas() and t > FEW_ROWS
            and d % 128 == 0 and f % 128 == 0
            and w_gate.dtype == w_down.dtype
            and 6 * d * f * w_gate.dtype.itemsize <= GROUPED_VMEM)


def _grouped_rows_kernel(layer_ref, expert_ref, tile_ref, starts_ref, n_ref,
                         x_ref, wg_ref, wu_ref, wd_ref, o_ref, *acc):
    """Grid step ``(i, j)``: work item ``i`` is one expert and one row tile
    that holds rows of its group; ``j`` a tile of the hidden width. The
    tile's rows through the expert's three blocks, and the rows that are
    the expert's own written over what the tile held: the others are their
    own experts' to write, in the steps before and behind this one."""
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(i < n_ref[0])
    def _():
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
        h = ((g * jax.nn.sigmoid(g)) * u).astype(x.dtype)
        y = jnp.dot(h, wd_ref[...], preferred_element_type=jnp.float32)
        e = expert_ref[i]
        row = tile_ref[i] * x.shape[0] + jax.lax.broadcasted_iota(
            jnp.int32, (x.shape[0], 1), 0)
        mine = (row >= starts_ref[e]) & (row < starts_ref[e + 1])
        if not acc:                     # the hidden width in one tile
            o_ref[...] = jnp.where(mine, y, o_ref[...])
            return
        acc_ref, = acc

        @pl.when(j == 0)
        def _():
            acc_ref[...] = y

        @pl.when(j > 0)
        def _():
            acc_ref[...] += y

        @pl.when(j == pl.num_programs(1) - 1)
        def _():
            o_ref[...] = jnp.where(mine, acc_ref[...], o_ref[...])


def _work_items(sizes, tile, n_tiles):
    """The grouped-rows kernel's work list for groups of ``sizes`` [E] rows
    laid end to end from row 0 of ``n_tiles`` row tiles of ``tile``:
    (expert [N], row tile [N], the groups' first rows and the last one's
    end [E + 1], how many of the N = ``n_tiles + E - 1`` items are work).
    An item is an expert and a row tile that holds rows of its group, in
    ascending order of both; an empty group has none, and neither has a
    tile behind the last group's end (a share's pairs to absent experts);
    behind the last item the list repeats it, so that nothing more is
    copied. Where every group is empty NO item is work and the list names
    the last expert and tile 0 throughout: the one block the pipeline
    fetches before it looks."""
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tile
    spans = jnp.where(sizes > 0, (ends - 1) // tile - first + 1, 0)
    upto = jnp.cumsum(spans)
    i = jnp.minimum(jnp.arange(n_tiles + sizes.shape[0] - 1),
                    jnp.maximum(upto[-1] - 1, 0))
    expert = jnp.minimum(
        jnp.searchsorted(upto, i, side="right", method="compare_all"),
        sizes.shape[0] - 1).astype(jnp.int32)
    row_tile = first[expert] + i - (upto - spans)[expert]
    return (expert, row_tile.astype(jnp.int32),
            jnp.concatenate([starts, ends[-1:]]).astype(jnp.int32),
            upto[-1:].astype(jnp.int32))


def moe_grouped_rows(xs, sizes, w_gate, w_up, w_down, layer=None,
                     hidden_tile=None):
    """The SORTED pairs' rows ``xs`` [P, D] (group ``e`` is the ``sizes[e]``
    rows behind the groups before it; ``sizes`` [E] sums to P, or to FEWER
    where the experts are a share and the pairs of absent ones lie behind
    the last group) through their experts' SwiGLU as ONE Pallas kernel,
    ``moe_grouped_rows`` in a trace: what three ``jax.lax.ragged_dot`` and
    the SwiGLU between them compute, float32 [P, D]. A row of no group is
    written by nobody and holds whatever the buffer held, not 0.0 as
    ``ragged_dot`` leaves it: the caller masks it (``moe_apply_sorted``
    does, by ``where`` and not by a product, which a NaN would pass).

    The experts that a pair reached are visited in ascending order, each
    multiplied with ITS OWN rows only, a row tile of ``GROUPED_ROW_TILE``
    at a time, the three products fused (``h`` never leaves VMEM; operands
    in the weights' type, float32 accumulation, ``h`` cast to the rows'
    type before the down product). A group starts wherever the sort put
    it: a tile that two groups share is multiplied once with each and
    each writes its own rows of the result (``_work_items``). An expert's
    matrices are blocks of the stack as it is stored (``[L, E, ...]``
    with ``layer`` a traced index: no slice of a layer's size), copied
    when the list moves on to it, the next one's in flight while this one
    is multiplied, and NOT again for its second row tile; an expert
    nobody reached is never copied. A row's result depends on no other
    row. ``hidden_tile`` cuts the hidden width (an inner grid axis, the
    down products summed in VMEM): then an expert's blocks are copied
    once a ROW TILE of its group, and the chip read that form a fifth to
    a third slower than the uncut one at both widths it was tried at,
    which is why the gate asks for the width uncut (PERF.md section 6,
    PR 56)."""
    p, d = xs.shape
    f = w_gate.shape[-1]
    if layer is None:
        w_gate, w_up, w_down = (w[None] for w in (w_gate, w_up, w_down))
        layer = 0
    tile = GROUPED_ROW_TILE
    tf = f if hidden_tile is None else hidden_tile
    n_tiles = -(-p // tile)
    if n_tiles * tile != p:     # rows of no group: written by nobody
        xs = jnp.pad(xs, ((0, n_tiles * tile - p), (0, 0)))
    expert, row_tile, starts, n = _work_items(sizes, tile, n_tiles)
    # two experts' blocks, two tiles of rows in and out, the products
    held = 6 * d * tf * w_gate.dtype.itemsize \
        + 2 * tile * d * (xs.dtype.itemsize + 4) + tile * (d + 3 * tf) * 4

    def rows(i, j, lyr, exp, til, *_):
        return til[i], 0

    def gate_up(i, j, lyr, exp, *_):
        return lyr[0], exp[i], 0, j

    def down(i, j, lyr, exp, *_):
        return lyr[0], exp[i], j, 0

    out = _pa._pcall(
        _grouped_rows_kernel, name="moe_grouped_rows",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(expert.shape[0], f // tf),
            in_specs=[
                pl.BlockSpec((tile, d), rows),
                pl.BlockSpec((None, None, d, tf), gate_up),
                pl.BlockSpec((None, None, d, tf), gate_up),
                pl.BlockSpec((None, None, tf, d), down)],
            out_specs=pl.BlockSpec((tile, d), rows),
            scratch_shapes=[pltpu.VMEM((tile, d), jnp.float32)]
            if tf != f else []),
        out_shape=jax.ShapeDtypeStruct((n_tiles * tile, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=held + 8 * 2 ** 20),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), expert, row_tile, starts,
      n, xs, w_gate, w_up, w_down)
    return out[:p]


def moe_apply_sorted(xt, idx, gates, w_gate, w_up, w_down, layer=None,
                     held=None):
    """The drop-free expert layer: the T x K token-expert pairs sorted by
    expert, one grouped matmul a projection over the experts held (the
    leading dimension of the weights; ``jax.lax.ragged_dot``: on the TPU
    a Mosaic kernel that reads an expert's weights only where a pair
    reached it), then the gates applied and the pairs summed back in
    token order. Every pair is computed, so a token's output depends on
    no other token. Returns [T, D] in xt's dtype.

    With ``layer`` (a traced index) the weights are a model's stacked
    [L, E, ...] experts, taken as L x E groups of which only layer
    ``layer``'s are non-empty: slicing the layer out first would copy all
    its experts on every call (3.2 ms against 0.4 for the matmul itself
    at 64 rows: PERF.md section 6, PR 27), and empty groups cost
    nothing.

    With ``held`` = (first, width) the weights are one chip's share of an
    expert-parallel layer: ``idx`` runs over a router ``width`` wide and
    the E experts held are ``first .. first + E - 1``. A pair routed to
    an absent expert sorts behind the last held group and is neither
    gathered nor multiplied: what the absent experts would add is
    another chip's to compute, and is left out here. Only the leading
    rows of the sorted order go through the matmuls: four times an even
    router's share of the pairs, and where the router sends this chip
    more than that, all of them, so no pair to a held expert is ever
    dropped. The sum back to the tokens walks those rows in blocks of
    ``UNSORT_BLOCK`` and stops behind the last pair held, so its cost
    follows the pairs this chip holds and not the rows set aside for
    them. A share of a QUARTER of the router or more sets no row aside:
    where the walk would take more than one block its rows go back to
    token order as the whole layer's do, and a token's few held pairs
    are summed in the token's own order.

    The CALL decides its form, as the paged attention calls do, from
    what it is given (the backend or the tests' interpreter hook, the
    rows, ``held``, the experts' widths and type): a few rows go through
    the kernel ``moe_apply_few_rows`` where ``few_rows_usable`` says so
    (a decode step of 128 rows or fewer: a whole layer or a share of
    one, an expert taken in runs of its hidden width where two of it
    pass the kernel's budget: no sort, no gather and no un-sort there);
    more rows than that keep the sort, the gather and the un-sort and
    put the sorted rows through the kernel ``moe_grouped_rows`` in place
    of the three ``ragged_dot`` where ``grouped_rows_usable`` says so (a
    prefill window or a decode step of more than 128 slots, over a whole
    layer or a share of one, whichever of the share's branches below
    takes it: Laguna's, xing4's and LFM2's experts all held, Ling's 128
    of 512); and this function is the reference of both, on every CPU,
    and the form of more than 128 rows over experts two of which are
    over the grouped kernel's budget (DeepSeek-V3's and MiMo's prefill
    windows). No training path differentiates through it (a
    Pallas call has no gradient here): ``moe_ffn`` trains through
    ``moe_apply``, and the paged programs and ``llama_generate`` only
    infer."""
    t, k = idx.shape
    e = w_gate.shape[-3]
    if few_rows_usable(t, w_gate, w_down, held):
        return moe_apply_few_rows(xt, idx, gates, w_gate, w_up, w_down,
                                  layer, held)
    local = idx
    if held is not None:
        first, width = held
        local = jnp.where((idx >= first) & (idx < first + e),
                          idx - first, e)
    order = jnp.argsort(local.reshape(t * k), stable=True)
    sizes = moe_load(local, e)
    n_held = jnp.sum(sizes)
    cdt = xt.dtype
    in_kernel = grouped_rows_usable(t, w_gate, w_down, held)
    if layer is not None and not in_kernel:
        n_layers = w_gate.shape[0]
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((n_layers * e,), jnp.int32), sizes, (layer * e,))
        w_gate, w_up, w_down = (w.reshape((n_layers * e,) + w.shape[2:])
                                for w in (w_gate, w_up, w_down))

    def grouped(pairs):
        """The sorted pairs ``pairs`` through their experts, float32."""
        xs = xt[pairs // k]
        if in_kernel:
            return moe_grouped_rows(xs, sizes, w_gate, w_up, w_down, layer)
        gate_h = jax.lax.ragged_dot(xs, w_gate, sizes)
        up_h = jax.lax.ragged_dot(xs, w_up, sizes)
        h = ((gate_h * jax.nn.sigmoid(gate_h)) * up_h).astype(cdt)
        return jax.lax.ragged_dot(h, w_down, sizes,
                                  preferred_element_type=jnp.float32)

    if held is None:
        pairs = grouped(order)[jnp.argsort(order)].reshape(t, k, -1)
        return jnp.sum(pairs * gates[..., None], axis=1).astype(cdt)

    def leading(rows):
        """The first ``rows`` sorted pairs, gated and summed by token, in
        float32; a row behind the last held group counts for nothing (the
        kernel leaves it unwritten).

        Still a product and not a scatter-add, which goes row by row on
        the chip, half a microsecond each (PERF.md section 6, PR 31): the
        gate goes onto the rows first, so the matrix on the left is 0/1
        (row r is token ``pairs[r] // k``'s), and the gated rows are split
        into their three bfloat16 parts (3 x 8 bits: the whole float32
        mantissa), laid end to end along the contraction of ONE
        single-pass product. Every term of it is 1 x a bfloat16 number,
        exact, and the accumulation is float32 over at most ``k`` live
        pairs a token: the float32 sum at half the passes
        ``Precision.HIGHEST`` takes. The rows are walked a block at a
        time by a loop of ``ceil(n_held / block)`` trips, read on the
        device; where they are one block or less (every decode step) the
        product stands alone, with no loop."""
        pairs = order[:rows]
        ys = grouped(pairs)
        with jax.named_scope("moe/unsort"):
            tokens = pairs // k
            g = gates.reshape(t * k)[pairs]
            size = min(rows, UNSORT_BLOCK)

            def block(i):
                """[T, D]: what rows ``i * size`` on add to their tokens;
                the last block is drawn back inside the rows, and the
                rows it shares with the one before count there alone."""
                start = jnp.minimum(i * size, rows - size)
                row = start + jnp.arange(size)
                zs = jnp.where(
                    ((row >= i * size) & (row < n_held))[:, None],
                    jax.lax.dynamic_slice_in_dim(g, start, size)[:, None]
                    * jax.lax.dynamic_slice_in_dim(ys, start, size), 0.0)
                # reduce_precision and not a cast there and back, which
                # XLA may take for the identity (excess precision allowed)
                hi = jax.lax.reduce_precision(zs, 8, 7)
                rest = zs - hi
                mid = jax.lax.reduce_precision(rest, 8, 7)
                parts = jnp.concatenate([rest - mid, mid, hi])
                of_token = jnp.tile(jax.lax.dynamic_slice_in_dim(
                    tokens, start, size), 3)[None] == jnp.arange(t)[:, None]
                return jnp.dot(of_token.astype(jnp.bfloat16),
                               parts.astype(jnp.bfloat16),
                               preferred_element_type=jnp.float32)

            if rows == size:
                return block(0).astype(cdt)
            return jax.lax.fori_loop(
                0, -(-n_held // size), lambda i, acc: acc + block(i),
                jnp.zeros((t, ys.shape[-1]), jnp.float32)).astype(cdt)

    few = -(-4 * t * k * e // width // 8) * 8
    if few >= t * k > UNSORT_BLOCK:
        # a share of a quarter of the router or more, over more rows than
        # one block: no row is set aside, so every sorted row is computed
        # and each goes back to its place in token order, as the whole
        # layer's do; a pair not held counts for nothing. A token's pairs
        # are then summed in ITS order, a few of them held: walked in
        # blocks, the order of a token's sum followed where its pairs fell
        # among the other rows', and one request's tokens moved with its
        # company (PERF.md section 6, PR 62)
        back = jnp.argsort(order)
        pairs = jnp.where((back < n_held)[:, None], grouped(order)[back],
                          0.0).reshape(t, k, -1)
        return jnp.sum(pairs * gates[..., None], axis=1).astype(cdt)
    if few >= t * k:
        return leading(t * k)
    return jax.lax.cond(n_held <= few, lambda: leading(few),
                        lambda: leading(t * k))


def moe_apply_no_drop(xt, wg, w_gate, w_up, w_down, top_k):
    """Inference-form MoE: exact top-k softmax routing with NO capacity
    drops. Training capacity makes a token's output depend on which
    OTHER tokens competed for its experts — under KV-cache decoding
    that would make cached and recomputed logits diverge, so
    eval/serving routes drop-free (moe_route + moe_apply_sorted)."""
    idx, gates = moe_route(xt, wg, top_k)
    return moe_apply_sorted(xt, idx, gates, w_gate, w_up, w_down)


def _topk_combine(probs, top_k):
    """Dense [T, E] combine weights of exact top-k routing (renormed
    gates scattered to their experts) — the ONE copy of the routing
    semantics shared by the float and W8A8 drop-free paths."""
    e = probs.shape[-1]
    gates, idx = jax.lax.top_k(probs, top_k)                 # [T, K]
    gates = gates / jnp.maximum(
        jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    w = jnp.zeros_like(probs)                                # [T, E]
    for k in range(top_k):
        w = w + gates[:, k:k + 1] * jax.nn.one_hot(
            idx[:, k], e, dtype=probs.dtype)
    return w


def _act_quant(x):
    """Per-row dynamic activation quantization (absmax over the
    contracted axis): int8 values + float scale, the A half of W8A8."""
    xf = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1, keepdims=True),
                    1e-8) / 127.0
    return jnp.round(xf / s).astype(jnp.int8), s


def moe_apply_no_drop_q(xt, wg, w_gate, w_up, w_down, scales, top_k):
    """W8A8 drop-free MoE serving: same routing/combine as
    :func:`moe_apply_no_drop` (the ROUTER stays float — it is tiny and
    its softmax ranking is precision-sensitive), but the three expert
    matmul stacks run natively int8 x int8 -> int32 on the MXU with
    dynamic per-row activation quantization — the same native path as
    the dense qmat (transformer_ops.py): TPU XLA does not fuse a
    convert into a dot operand, so dequantize-then-matmul would
    materialize full float copies of every expert weight per step.

    w_gate/w_up: int8 [E, D, H]; w_down: int8 [E, H, D];
    scales: {"gate": [E,1,H], "up": [E,1,H], "down": [E,1,D]} float.
    """
    probs = _router_probs(xt, wg)
    e = probs.shape[-1]
    w = _topk_combine(probs, top_k)                          # [T, E]
    cdt = xt.dtype
    xq, xs = _act_quant(xt)                        # [T,D] i8, [T,1] f32
    sg = scales["gate"].reshape(1, e, -1)                    # [1,E,H]
    su = scales["up"].reshape(1, e, -1)
    sd = scales["down"].reshape(1, e, -1)                    # [1,E,D]
    g32 = jnp.einsum("td,edh->teh", xq, w_gate,
                     preferred_element_type=jnp.int32)
    u32 = jnp.einsum("td,edh->teh", xq, w_up,
                     preferred_element_type=jnp.int32)
    gate_h = g32.astype(jnp.float32) * xs[:, :, None] * sg
    up_h = u32.astype(jnp.float32) * xs[:, :, None] * su
    h = (gate_h * jax.nn.sigmoid(gate_h)) * up_h             # [T,E,H]
    hq, hs = _act_quant(h)                                   # [T,E,1]
    d32 = jnp.einsum("teh,ehd->ted", hq, w_down,
                     preferred_element_type=jnp.int32)
    expert_out = d32.astype(jnp.float32) * hs * sd           # [T,E,D]
    return jnp.einsum("te,ted->td", w.astype(jnp.float32),
                      expert_out).astype(cdt)


@register_op("moe_ffn")
def _moe_ffn(ctx, ins, attrs):
    """X [B,S,D]; GateW [D,E]; W_up/W_gate [E,D,H]; W_down [E,H,D].

    SwiGLU experts: down(silu(gate(x)) * up(x)), matching the dense
    Llama FFN so a dense layer can be swapped for an MoE one 1:1.
    Outputs: Out [B,S,D], AuxLoss [] (scalar, pre-weighted by caller).
    Test mode routes drop-free (see moe_apply_no_drop).
    """
    x = ins["X"][0]
    wg = ins["GateW"][0]
    w_up, w_gate, w_down = ins["WUp"][0], ins["WGate"][0], ins["WDown"][0]
    top_k = int(attrs.get("top_k", 2))
    cap_factor = float(attrs.get("capacity_factor", 2.0))
    e = w_up.shape[0]
    b, s, d = x.shape
    # the ep sharding P('ep', ...) splits the EXPERT axis of [E, C, ...]
    # — E must divide evenly or experts silently replicate
    from ..parallel.mesh import current_mesh
    mesh = current_mesh()
    if mesh is not None and mesh.axes.get("ep", 1) > 1:
        ep = mesh.axes["ep"]
        if e % ep != 0:
            raise ValueError(
                f"moe_ffn: num_experts={e} is not divisible by the mesh "
                f"'ep' axis size {ep}; expert weights cannot shard — "
                "resize the mesh or the expert count")

    xt = x.reshape(b * s, d)
    if ctx.is_test:
        out = moe_apply_no_drop(xt, wg, w_gate, w_up, w_down, top_k)
        aux = jnp.float32(0.0)
    else:
        out, aux = moe_apply(xt, wg, w_gate, w_up, w_down, top_k,
                             cap_factor)
    return {"Out": [out.reshape(b, s, d)],
            "AuxLoss": [aux.astype(jnp.float32)]}
