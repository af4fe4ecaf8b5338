"""Program → JAX lowering.

This replaces Fluid's two executors:
  * framework/executor.cc — a per-op interpreter that walks BlockDesc and
    launches one kernel per OpDesc, and
  * framework/parallel_executor.cc — an SSA-graph multi-stream scheduler.

On TPU the idiomatic design is the opposite: lower the ENTIRE program
(forward ops, autodiff, optimizer update ops) into one pure function,
let `jax.jit` trace it once and XLA fuse/schedule it. Autodiff is done
with `jax.value_and_grad` over the forward segment instead of per-op
grad kernels (reference paddle/fluid/framework/grad_op_desc_maker.h) —
same capability, compiler-native mechanism.
"""
import jax
import jax.numpy as jnp

from . import framework
from .registry import get_op
# the AMP dtype policy (which ops compute bf16, which flow bf16 under
# O2) lives in amp_policy.py — pure data, shared with the jax-free
# static analyses (analysis/numcheck.py replays the same decisions)
from .amp_policy import (AMP_MATMUL_OPS, AMP_BF16_FLOW_OPS,  # noqa: F401
                         AMP_SELF_MANAGED_DTYPE_OPS)

__all__ = ["LoweringContext", "Env", "lower_program", "written_names"]


class Env:
    """Name → traced-value environment with lexical parent chaining, the
    functional analogue of Fluid's Scope hierarchy (reference
    paddle/fluid/framework/scope.h)."""

    __slots__ = ("d", "parent")

    def __init__(self, parent=None):
        self.d = {}
        self.parent = parent

    def __getitem__(self, name):
        e = self
        while e is not None:
            if name in e.d:
                return e.d[name]
            e = e.parent
        raise KeyError(f"variable {name!r} has no value (not fed, not in "
                       f"scope, and not produced by a prior op)")

    def __setitem__(self, name, value):
        self.d[name] = value

    def __contains__(self, name):
        e = self
        while e is not None:
            if name in e.d:
                return True
            e = e.parent
        return False

    def get(self, name, default=None):
        try:
            return self[name]
        except KeyError:
            return default

    def update(self, other):
        self.d.update(other)


class LoweringContext:
    """Carries trace-wide services to op lowering rules: deterministic RNG
    key derivation, train/test mode, and sub-block evaluation for
    control-flow ops."""

    def __init__(self, program, mode, base_key):
        self.program = program
        self.mode = mode  # "train" | "test"
        self._base_key = base_key
        self._key_count = 0
        self.op = None    # current op (set by eval_op)
        self.env = None   # current env (set by eval_op)
        # (label, is-finite scalar) per float op output when the
        # program's NaN/Inf guard mode is on (debugger.enable_nan_guard)
        self.guard = []

    @property
    def is_test(self):
        return self.mode == "test"

    def next_key(self):
        k = jax.random.fold_in(self._base_key, self._key_count)
        self._key_count += 1
        return k

    # ------ block evaluation -------------------------------------------
    def eval_block(self, block, env):
        for op in block.ops:
            self.eval_op(op, env)

    def eval_op(self, op, env):
        try:
            return self._eval_op(op, env)
        except Exception as e:
            # Dynamic complement to the static verifier (analysis/):
            # a tracer error deep inside a rule re-raises carrying op
            # type, block/op index, and the variable wiring — without
            # changing the exception type (tests and callers pin
            # types/messages). Annotate once, at the innermost op.
            if not getattr(e, "_lowering_ctx_added", False):
                e._lowering_ctx_added = True
                block = op.block
                try:
                    op_idx = block.ops.index(op)
                except ValueError:
                    op_idx = -1
                note = (f"while lowering op {op.type!r} "
                        f"(block {block.idx}, op #{op_idx}): "
                        f"inputs {op.inputs} -> outputs {op.outputs}")
                if hasattr(e, "add_note"):
                    e.add_note(note)
                elif e.args and isinstance(e.args[0], str):
                    e.args = (e.args[0] + "\n  [" + note + "]",) \
                        + e.args[1:]
            raise

    def _eval_op(self, op, env):
        from .sequence import SequenceBatch

        opdef = get_op(op.type)
        ins = {}
        seq_lengths = None
        seq_counts = None
        for slot, names in op.inputs.items():
            vals = [env[n] for n in names]
            if not opdef.seq_aware:
                # transparently unwrap padded sequences for dense ops;
                # remember lengths to rewrap lod-level outputs
                unwrapped = []
                for v in vals:
                    if isinstance(v, SequenceBatch):
                        if seq_lengths is None:
                            seq_lengths = v.lengths
                            seq_counts = v.outer_counts
                        unwrapped.append(v.data)
                    else:
                        unwrapped.append(v)
                vals = unwrapped
            ins[slot] = vals
        amp_level = getattr(self.program, "_amp", False)
        amp = amp_level and op.type in AMP_MATMUL_OPS
        o2 = amp_level == "O2"
        o2_flow = o2 and not amp and op.type in AMP_BF16_FLOW_OPS
        flow_had_bf16 = False
        if amp:
            # bf16 mixed precision (transpiler/amp.py): matmul-shaped
            # ops compute in bf16 on the MXU; the surrounding casts
            # fuse away and master values stay f32
            ins = {slot: [_amp_cast(v, jnp.float32, jnp.bfloat16)
                          for v in vals]
                   for slot, vals in ins.items()}
        elif o2 and not o2_flow:
            # O2: activations flow bf16 between matmul/flow ops; any
            # other op (softmax, losses, metrics, optimizer math) gets
            # f32 inputs — the upcast fuses into its first read
            ins = {slot: [_amp_cast(v, jnp.bfloat16, jnp.float32)
                          for v in vals]
                   for slot, vals in ins.items()}
        elif o2_flow:
            flow_had_bf16 = any(
                getattr(v, "dtype", None) == jnp.bfloat16
                for vals in ins.values() for v in vals)
        prev_op, prev_env = self.op, self.env
        self.op, self.env = op, env
        try:
            outs = opdef.lower(self, ins, op.attrs)
        finally:
            self.op, self.env = prev_op, prev_env
        out_cast = None      # (from_dtype, to_dtype) for op outputs
        if amp and not o2:
            out_cast = (jnp.bfloat16, jnp.float32)
        elif o2_flow and flow_had_bf16 \
                and op.type not in AMP_SELF_MANAGED_DTYPE_OPS:
            # Mixed-dtype flow ops (e.g. a bf16 activation + f32 bias
            # add) promote to f32 under jnp rules; compute in f32 is
            # fine (it fuses) but the WRITE must stay bf16 or the
            # traffic saving silently evaporates. Self-managing ops
            # (batch_norm: bf16 Y, f32 moving/saved stats) are exempt.
            out_cast = (jnp.float32, jnp.bfloat16)
        if out_cast is not None and outs is not None:
            outs = {slot: [_amp_cast(v, *out_cast)
                           for v in (vals if isinstance(
                               vals, (list, tuple)) else [vals])]
                    for slot, vals in outs.items()}
        if outs is None:
            return
        block = op.block
        for slot, names in op.outputs.items():
            if slot not in outs:
                continue
            vals = outs[slot]
            if not isinstance(vals, (list, tuple)):
                vals = [vals]
            for name, val in zip(names, vals):
                var = block._find_var_recursive(name)
                if (var is not None and var.lod_level > 0
                        and seq_lengths is not None
                        and not isinstance(val, SequenceBatch)
                        and getattr(val, "ndim", 0) >= 2):
                    val = SequenceBatch(val, seq_lengths, seq_counts)
                if (var is not None and var.stop_gradient
                        and not isinstance(var, framework.Parameter)
                        and not isinstance(val, SequenceBatch)
                        and _is_float(val)):
                    val = jax.lax.stop_gradient(val)
                env[name] = val
                if getattr(self.program, "_nan_guard", False):
                    v = val.data if isinstance(val, SequenceBatch) \
                        else val
                    if _is_float(v):
                        self.guard.append(
                            (f"{op.type} -> {name}",
                             jnp.isfinite(v).all()))


def _amp_cast(v, from_dtype, to_dtype):
    """Cast ``v`` to ``to_dtype`` iff its dtype is ``from_dtype``.
    SequenceBatch values (which expose .dtype but not .astype) cast
    their padded data and keep lengths/outer_counts."""
    if getattr(v, "dtype", None) != from_dtype:
        return v
    from .sequence import SequenceBatch
    if isinstance(v, SequenceBatch):
        return SequenceBatch(v.data.astype(to_dtype), v.lengths,
                             v.outer_counts)
    return v.astype(to_dtype)


def _is_float(v):
    try:
        return jnp.issubdtype(jnp.asarray(v).dtype, jnp.floating)
    except Exception:
        return False


def written_names(block, recursive=True):
    """Statically computes the set of variable names any op in ``block``
    (and its control-flow sub-blocks) writes. Used by the Executor to
    decide which persistables flow back to the Scope."""
    out = set()
    for op in block.ops:
        for names in op.outputs.values():
            out.update(names)
        if recursive:
            for v in op.attrs.values():
                if isinstance(v, framework.Block):
                    out |= written_names(v, recursive=True)
    return out


def lower_program(program, fetch_names, mode):
    """Builds the pure step function for a Program.

    Returns ``fn(state_rw, state_ro, feed, key) -> (new_state_rw, fetches)``
    where ``state_rw`` holds persistables some op writes (donated by the
    executor), ``state_ro`` holds read-only persistables, and ``key`` is a
    per-step PRNG key.

    If the program contains a ``backward`` marker op (from
    ``append_backward``), the ops before it are evaluated inside
    ``jax.value_and_grad`` w.r.t. the marked parameters, the resulting
    gradients are bound to the ``<param>@GRAD`` names, and the remaining
    (optimizer) ops run on top — producing a single fused train step.
    """
    gb = program.global_block()
    ops = gb.ops
    bwd_idx = None
    for i, op in enumerate(ops):
        if op.type == "backward":
            bwd_idx = i
            break

    def fn(state_rw, state_ro, feed, key):
        ctx = LoweringContext(program, mode, key)
        env = Env()
        env.update(state_ro)
        env.update(state_rw)
        env.update(feed)

        if bwd_idx is None:
            for op in ops:
                ctx.eval_op(op, env)
        else:
            bwd_op = ops[bwd_idx]
            loss_name = bwd_op.input("Loss")[0]
            param_names = bwd_op.attr("parameter_names")
            base = dict(env.d)
            param_vals = {p: base.pop(p) for p in param_names}

            # only forward values referenced later (fetches, optimizer-op
            # inputs, updated persistables) escape the forward segment —
            # everything else stays internal so rematerialization can
            # actually free it
            needed_after = set(fetch_names)
            for op in ops[bwd_idx + 1:]:
                for ns in op.inputs.values():
                    needed_after.update(ns)
            for name, var in gb.vars.items():
                if var.persistable:
                    needed_after.add(name)

            def fwd(pv):
                e = Env()
                e.update(base)
                e.update(pv)
                for op in ops[:bwd_idx]:
                    ctx.eval_op(op, e)
                loss = jnp.reshape(e[loss_name], ())
                return loss, {n: v for n, v in e.d.items()
                              if n in needed_after}

            if program._remat_policy:
                # memory_optimize(): recompute forward activations in the
                # backward pass per the chosen jax.checkpoint policy.
                # "recompute_norms" is ours: save everything EXCEPT the
                # named batch_norm outputs (ops/nn.py tags them) — conv
                # outputs stay saved (BN's backward needs them anyway),
                # the normalize+activation recomputes from them, so the
                # post-norm activation is never stored across fwd->bwd.
                if program._remat_policy == "recompute_norms":
                    policy = jax.checkpoint_policies.\
                        save_anything_except_these_names("batch_norm_out")
                elif program._remat_policy == "save_conv_only":
                    # restrictive conv-net policy: the tagged conv
                    # outputs (ops/nn.py) are the ONLY residuals kept
                    # across fwd->bwd; BN/activation/pool recompute
                    # from them in the backward. Small residual set =
                    # small HLO, unlike recompute_norms' allow-most
                    # form (compile-OOM at bench scale; builder,
                    # round 4, an earlier installation).
                    policy = jax.checkpoint_policies.\
                        save_only_these_names("conv_out")
                else:
                    policy = getattr(jax.checkpoint_policies,
                                     program._remat_policy, None)
                fwd = jax.checkpoint(fwd, policy=policy)
            grad_fn = jax.value_and_grad(fwd, has_aux=True)
            (_, fwd_vals), grads = grad_fn(param_vals)
            env.update(fwd_vals)
            for p in param_names:
                env[framework.grad_var_name(p)] = grads[p]
            for op in ops[bwd_idx + 1:]:
                ctx.eval_op(op, env)

        new_state = {}
        for name in state_rw:
            new_state[name] = env[name]
        # persistables created (not pre-existing) by this program, e.g.
        # startup-program initializers
        for name, var in gb.vars.items():
            if var.persistable and name in env.d and name not in new_state \
                    and name not in state_ro:
                new_state[name] = env.d[name]
        fetches = [env[n] for n in fetch_names]
        if getattr(program, "_nan_guard", False):
            # NaN/Inf guard mode: ship one finite-flag per float op
            # output back with the step; the Executor raises host-side
            # naming the first op that went non-finite. Emitted whenever
            # the mode is ON (even with zero float outputs) so the
            # output pytree structure is decidable before tracing —
            # ParallelExecutor pins out_shardings from the flag alone.
            fn.guard_labels = [g[0] for g in ctx.guard]
            new_state["__nan_guard__"] = (
                jnp.stack([g[1] for g in ctx.guard]) if ctx.guard
                else jnp.ones((0,), jnp.bool_))
        return new_state, fetches

    return fn
