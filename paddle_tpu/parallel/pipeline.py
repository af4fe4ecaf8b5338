"""Pipeline parallelism over the mesh 'pp' axis — GPipe microbatch
schedule, TPU-native.

Where the reference would time-slice a program across devices with
send/recv ops (its section_worker / pipeline trainer lineage, and the
NCCL send/recv ops in paddle/fluid/operators), the TPU form keeps ONE
SPMD program: stage parameters live stacked with a leading [n_stages]
axis sharded over 'pp', activations rotate between neighbor stages with
``lax.ppermute`` inside ``shard_map``, and a ``lax.scan`` over
n_micro + n_stages - 1 ticks realizes the pipeline (bubbles included).
``jax.grad`` differentiates straight through the scan, giving the GPipe
backward schedule for free; wrap ``stage_fn`` in ``jax.checkpoint`` to
trade recompute for activation memory like the reference's
memory_optimization pass would.
"""
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

__all__ = ["gpipe", "one_f_one_b"]


def gpipe(stage_fn, mesh, axis="pp", checkpoint_stages=True):
    """Build a pipelined apply over ``mesh.axes[axis]`` stages.

    stage_fn(stage_params, x) -> y, the computation of ONE stage; all
    stages must share this shape signature (x and y alike), e.g. a
    block of transformer layers.

    Returns ``pipelined(stacked_params, micro) -> out`` where
    ``stacked_params`` is a pytree whose leaves lead with the
    [n_stages] axis (shard it over 'pp'), ``micro`` is
    [n_micro, micro_batch, ...], and ``out`` is [n_micro, micro_batch,
    ...] — the last stage's outputs in microbatch order, replicated
    across the pipeline group.
    """
    n_stages = mesh.axes[axis]
    fn = jax.checkpoint(stage_fn) if checkpoint_stages else stage_fn
    other_axes = tuple(a for a in mesh.axes if a != axis)

    def per_group(params_local, micro):
        # inside shard_map: params_local leads with a length-1 stage
        # slice; micro is this data-parallel shard's microbatches,
        # replicated along 'pp'
        params_here = jax.tree_util.tree_map(lambda p: p[0], params_local)
        idx = jax.lax.axis_index(axis)
        n_micro = micro.shape[0]
        ticks = n_micro + n_stages - 1
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            prev_out, outputs = carry
            recv = jax.lax.ppermute(prev_out, axis, perm)
            feed_t = jnp.clip(t, 0, n_micro - 1)
            x_in = jnp.where(idx == 0, micro[feed_t], recv)
            y = fn(params_here, x_in)
            out_t = t - (n_stages - 1)
            write = (idx == n_stages - 1) & (out_t >= 0)
            safe_t = jnp.maximum(out_t, 0)
            cur = jax.lax.dynamic_index_in_dim(outputs, safe_t, 0,
                                               keepdims=False)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(write, y, cur), safe_t, 0)
            return (y, outputs), None

        zero = jnp.zeros_like(micro[0])
        outs0 = jnp.zeros_like(micro)
        (_, outputs), _ = jax.lax.scan(
            tick, (zero, outs0), jnp.arange(ticks))
        # only the last stage holds real outputs — share them along the
        # pipeline axis so every stage returns the same value
        outputs = jax.lax.psum(
            jnp.where(idx == n_stages - 1, outputs, 0.0), axis)
        return outputs

    # stage params enter sharded over 'pp' on their stacked axis; data
    # shards its microbatch dim over 'dp' when the mesh has one
    param_spec = P(axis)

    def pipelined(stacked_params, micro):
        in_specs = (jax.tree_util.tree_map(lambda _: param_spec,
                                           stacked_params),
                    P(None, "dp") if "dp" in other_axes else P())
        sm = shard_map(
            per_group, mesh=mesh.mesh, in_specs=in_specs,
            out_specs=P(None, "dp") if "dp" in other_axes else P(),
            check_vma=False)
        return sm(stacked_params, micro)

    return pipelined


def one_f_one_b(stage_fn, loss_fn, mesh, axis="pp", loss_params=False,
                return_dx=False):
    """1F1B pipeline schedule (PipeDream-flush) — the GPipe upgrade the
    reference's section-based pipeline trainer never got.

    Where :func:`gpipe` differentiates through the whole forward
    schedule (so every stage holds inputs for ALL ``n_micro``
    microbatches until the backward sweep), 1F1B interleaves each
    microbatch's backward as soon as the last stage finishes its
    forward: stage ``s`` holds at most ``n_stages - s`` in-flight
    stage-inputs, the steady state alternates one-forward/one-backward
    per tick, and parameter gradients accumulate inside the schedule.
    Same bubble as GPipe, ~n_micro/n_stages× less activation memory.

    stage_fn(stage_params, x) -> y (same x/y shape across stages);
    loss_fn(y, target) -> scalar per-microbatch loss (mean-reduced).

    Returns ``step(stacked_params, micro_x, micro_y) -> (loss, grads)``
    where ``stacked_params`` leads with [n_stages] (shard over 'pp'),
    ``micro_x``/``micro_y`` are [n_micro, micro_batch, ...], ``loss``
    is the mean over microbatches, and ``grads`` matches
    ``stacked_params`` — gradients of that mean loss, computed by the
    schedule itself (do NOT wrap in jax.grad).

    ``loss_params=True`` changes ``loss_fn`` to
    ``loss_fn(lparams, y, target)`` (the last stage's head/loss
    weights, replicated across stages) and ``step`` to
    ``step(stacked_params, lparams, micro_x, micro_y)``; the return
    gains ``dlparams``. ``return_dx=True`` appends ``dx_micro``
    (d loss / d micro_x, same [n_micro, ...] layout) — what an
    upstream embedding needs to keep training through the pipeline.

    Tick algebra (stage s, microbatch k, n_stages S): forward of k runs
    at tick ``s + 2k``, backward at ``2S - 1 - s + 2k`` — ticks at a
    stage strictly alternate F/B, values permuted at tick end arrive
    exactly when the neighbor consumes them, and a slot ring of size S
    holds the in-flight stage inputs for backward recomputation
    (jax.vjp re-runs the stage, i.e. remat is built in).
    """
    n_stages = mesh.axes[axis]
    other_axes = tuple(a for a in mesh.axes if a != axis)
    has_dp = "dp" in other_axes

    def per_group(params_local, lparams, micro_x, micro_y):
        params = jax.tree_util.tree_map(lambda p: p[0], params_local)
        idx = jax.lax.axis_index(axis)
        n_micro = micro_x.shape[0]
        # last event: backward of microbatch M-1 at stage 0, tick
        # 2S - 1 + 2(M-1) — so 2(M + S) - 2 ticks run in total
        ticks = 2 * (n_micro + n_stages) - 2
        fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        bwd_perm = [((i + 1) % n_stages, i) for i in range(n_stages)]

        zero_x = jnp.zeros_like(micro_x[0])
        zero_g = jax.tree_util.tree_map(jnp.zeros_like, params)
        zero_lg = jax.tree_util.tree_map(jnp.zeros_like, lparams)
        dx_buf0 = (jnp.zeros_like(micro_x) if return_dx else ())

        def tick(carry, t):
            y_send, g_send, x_ring, grad_acc, lg_acc, dx_buf, \
                loss_acc = carry
            y_in = jax.lax.ppermute(y_send, axis, fwd_perm)
            g_in = jax.lax.ppermute(g_send, axis, bwd_perm)

            k_f = (t - idx) // 2
            is_f = ((t - idx) % 2 == 0) & (k_f >= 0) & (k_f < n_micro)
            k_b = (t - (2 * n_stages - 1 - idx)) // 2
            is_b = (~((t - idx) % 2 == 0)) & (k_b >= 0) & (k_b < n_micro)

            def fwd_branch(args):
                (y_in, g_in, x_ring, grad_acc, lg_acc, dx_buf,
                 loss_acc) = args
                kf = jnp.clip(k_f, 0, n_micro - 1)
                x_in = jnp.where(idx == 0, micro_x[kf], y_in)
                y = stage_fn(params, x_in)
                x_ring = jax.lax.dynamic_update_index_in_dim(
                    x_ring, x_in, kf % n_stages, 0)
                return (y, zero_x, x_ring, grad_acc, lg_acc, dx_buf,
                        loss_acc)

            def bwd_branch(args):
                (y_in, g_in, x_ring, grad_acc, lg_acc, dx_buf,
                 loss_acc) = args
                kb = jnp.clip(k_b, 0, n_micro - 1)
                x_in = jax.lax.dynamic_index_in_dim(
                    x_ring, kb % n_stages, 0, keepdims=False)
                y, pull = jax.vjp(stage_fn, params, x_in)
                inv_m = jnp.ones((), jnp.float32) / n_micro

                if loss_params:
                    loss_k, pull_l = jax.vjp(
                        lambda lp, yy: loss_fn(lp, yy, micro_y[kb]),
                        lparams, y)
                    dlp_k, g_last = pull_l(inv_m.astype(loss_k.dtype))
                else:
                    loss_k, pull_l = jax.vjp(
                        lambda yy: loss_fn(yy, micro_y[kb]), y)
                    (g_last,) = pull_l(inv_m.astype(loss_k.dtype))
                    dlp_k = zero_lg
                loss_k = loss_k / n_micro

                is_last = idx == n_stages - 1
                cot = jnp.where(is_last, g_last, g_in)
                dparams, dx = pull(cot)
                grad_acc = jax.tree_util.tree_map(
                    lambda a, d: a + d, grad_acc, dparams)
                lg_acc = jax.tree_util.tree_map(
                    lambda a, d: a + jnp.where(is_last, d, 0.0),
                    lg_acc, dlp_k)
                if return_dx:
                    dx_buf = jax.lax.dynamic_update_index_in_dim(
                        dx_buf, jnp.where(idx == 0, dx, 0.0), kb, 0)
                loss_acc = loss_acc + jnp.where(is_last, loss_k, 0.0)
                return (zero_x, dx, x_ring, grad_acc, lg_acc, dx_buf,
                        loss_acc)

            def idle_branch(args):
                (y_in, g_in, x_ring, grad_acc, lg_acc, dx_buf,
                 loss_acc) = args
                return (zero_x, zero_x, x_ring, grad_acc, lg_acc,
                        dx_buf, loss_acc)

            branch = jnp.int32(0) + jnp.where(is_f, 1, 0) \
                + jnp.where(is_b, 2, 0)
            out = jax.lax.switch(
                branch, [idle_branch, fwd_branch, bwd_branch],
                (y_in, g_in, x_ring, grad_acc, lg_acc, dx_buf,
                 loss_acc))
            return out, None

        ring0 = jnp.zeros((n_stages,) + micro_x.shape[1:],
                          micro_x.dtype)
        carry0 = (zero_x, zero_x, ring0, zero_g, zero_lg, dx_buf0,
                  jnp.zeros((), jnp.float32))
        (_, _, _, grads, lgrads, dx_out, loss), _ = jax.lax.scan(
            tick, carry0, jnp.arange(ticks))

        # loss and head grads live on the last stage, dx on stage 0,
        # stage grads on their own stage. Share along 'pp'; average
        # across 'dp' shards.
        loss = jax.lax.psum(loss, axis)
        lgrads = jax.tree_util.tree_map(
            lambda g: jax.lax.psum(g, axis), lgrads)
        if return_dx:
            dx_out = jax.lax.psum(dx_out, axis)
            if has_dp:
                # dx is per-shard data (not summed over dp): the global
                # loss is the MEAN over dp shards, so each shard's
                # cotangent carries a 1/|dp| factor
                dx_out = dx_out / mesh.axes["dp"]
        if has_dp:
            loss = jax.lax.pmean(loss, "dp")
            grads = jax.tree_util.tree_map(
                lambda g: jax.lax.pmean(g, "dp"), grads)
            lgrads = jax.tree_util.tree_map(
                lambda g: jax.lax.pmean(g, "dp"), lgrads)
        # re-stack the local stage grads with the leading [1] axis so
        # the out_spec P(axis) reassembles [n_stages, ...]
        grads = jax.tree_util.tree_map(lambda g: g[None], grads)
        out = (loss, grads)
        if loss_params:
            out = out + (lgrads,)
        if return_dx:
            out = out + (dx_out,)
        return out

    param_spec = P(axis)

    def step(stacked_params, *rest):
        if loss_params:
            lparams, micro_x, micro_y = rest
        else:
            micro_x, micro_y = rest
            lparams = ()
        pspecs = jax.tree_util.tree_map(lambda _: param_spec,
                                        stacked_params)
        lspecs = jax.tree_util.tree_map(lambda _: P(), lparams)
        data_spec = P(None, "dp") if has_dp else P()
        out_specs = (P(), pspecs)
        if loss_params:
            out_specs = out_specs + (lspecs,)
        if return_dx:
            out_specs = out_specs + (data_spec,)
        sm = shard_map(
            per_group, mesh=mesh.mesh,
            in_specs=(pspecs, lspecs, data_spec, data_spec),
            out_specs=out_specs, check_vma=False)
        return sm(stacked_params, lparams, micro_x, micro_y)

    return step
