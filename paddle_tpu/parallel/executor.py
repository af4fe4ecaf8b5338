"""ParallelExecutor — SPMD execution of a Program over a device mesh.

Capability parity with fluid's ParallelExecutor (reference
paddle/fluid/framework/parallel_executor.cc + details/
multi_devices_graph_builder.cc): where the reference replicates the
graph per GPU, scatters batches, and inserts NCCL AllReduceOpHandle on
every gradient, we jit the SAME lowered step function with sharding
annotations — feeds sharded over 'dp', parameters sharded per their
transpiler-assigned PartitionSpec (or replicated) — and XLA GSPMD
partitions the program and places all-reduces on ICI automatically.
Gradient averaging falls out of the math: the loss mean over a
dp-sharded batch axis becomes a psum.
"""
import re

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import framework
from ..core.executor import (Executor, global_scope, make_stepped,
                             step_arg, check_nan_guard)
from ..core.lowering import lower_program, written_names
from .. import profiler
from ..profiler import record_event
from .mesh import make_mesh, DeviceMesh, mesh_scope

# GSPMD collective opcodes in optimized HLO. Each collective counts
# once: the pattern requires "(" directly after the base opcode or its
# "-start" async form, so "all-reduce-done(...)" (whose operand list
# follows "-done", not the base name) can never double-count.
_COLLECTIVE_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)(?:-start)?\(")

__all__ = ["ParallelExecutor", "ExecutionStrategy", "BuildStrategy"]


class ExecutionStrategy:
    """fluid-compat knob bag (reference ExecutionStrategy). Most knobs are
    meaningless under XLA (num_threads, allow_op_delay); kept for API
    parity."""

    def __init__(self):
        self.num_threads = 0
        self.use_cuda = False
        self.allow_op_delay = False
        self.num_iteration_per_drop_scope = 1


class BuildStrategy:
    """fluid-compat build options. gradient_scale maps to loss scaling;
    reduce_strategy is subsumed by GSPMD."""

    class ReduceStrategy:
        AllReduce = 0
        Reduce = 1

    class GradientScaleStrategy:
        CoeffNumDevice = 0
        One = 1
        Customized = 2

    def __init__(self):
        self.reduce_strategy = BuildStrategy.ReduceStrategy.AllReduce
        self.gradient_scale_strategy = \
            BuildStrategy.GradientScaleStrategy.CoeffNumDevice
        self.debug_graphviz_path = ""


class ParallelExecutor:
    def __init__(self, use_cuda=False, loss_name=None, main_program=None,
                 share_vars_from=None, exec_strategy=None,
                 build_strategy=None, num_trainers=1, trainer_id=0,
                 scope=None, mesh=None):
        self.program = main_program or framework.default_main_program()
        self.scope = scope or global_scope()
        self.mesh = mesh or make_mesh()
        self.loss_name = loss_name
        self._cache = {}
        self._step = 0
        if share_vars_from is not None:
            self.scope = share_vars_from.scope

    # ------------------------------------------------------------------
    def _spec_fits(self, spec, shape):
        """A PartitionSpec only applies if every sharded dim divides by the
        mesh axis size (XLA GSPMD requirement)."""
        if shape is None:
            return True
        for dim, axes in zip(shape, spec):
            if axes is None:
                continue
            axes = (axes,) if isinstance(axes, str) else axes
            n = 1
            for a in axes:
                n *= self.mesh.axes.get(a, 1)
            if dim % n != 0:
                return False
        return True

    def _spec_axes_known(self, spec):
        """A spec naming a mesh axis this mesh doesn't have (e.g. 'ep'
        weights on a dp-only mesh) falls back to replicated."""
        for axes in spec:
            if axes is None:
                continue
            axes = (axes,) if isinstance(axes, str) else axes
            if any(a not in self.mesh.axes for a in axes):
                return False
        return True

    def _var_sharding(self, name):
        gb = self.program.global_block()
        var = gb.vars.get(name)
        spec = getattr(var, "sharding", None) if var is not None else None
        if spec is None or not self._spec_axes_known(spec):
            return self.mesh.replicated()
        shape = None
        if var.shape is not None and -1 not in var.shape:
            shape = var.shape
        else:
            val = self.scope.find_var(name)
            shape = getattr(val, "shape", None)
        if not self._spec_fits(spec, shape):
            return self.mesh.replicated()
        return NamedSharding(self.mesh.mesh, spec)

    def _feed_sharding(self, name):
        gb = self.program.global_block()
        var = gb.vars.get(name)
        spec = getattr(var, "sharding", None) if var is not None else None
        if spec is not None and self._spec_axes_known(spec):
            return NamedSharding(self.mesh.mesh, spec)
        if "dp" in self.mesh.axis_names:
            return NamedSharding(self.mesh.mesh, P("dp"))
        return self.mesh.replicated()

    # ------------------------------------------------------------------
    def _prepare(self, feed, fetch_list):
        """run()/compiled_stats() shared preamble: fetch names, scope
        state split (donated vs read-only), staged + validated feeds.
        One copy so the stats path provably lowers the same executable
        run() dispatches."""
        feed = feed or {}
        fetch_names = [v.name if isinstance(v, framework.Variable) else v
                       for v in fetch_list]
        gb = self.program.global_block()
        written = written_names(gb)
        persistables = {n for n, v in gb.vars.items() if v.persistable}

        state_rw, state_ro = {}, {}
        for n in sorted(persistables):
            val = self.scope.find_var(n)
            if val is None:
                if n not in written:
                    raise RuntimeError(
                        f"persistable variable {n!r} uninitialized — run "
                        "the startup program on a plain Executor first")
                continue
            (state_rw if n in written else state_ro)[n] = val

        feed_vals = {k: jnp.asarray(np.asarray(v)) for k, v in feed.items()}
        for k, v in feed_vals.items():
            sh = self._feed_sharding(k)
            for dim, axes in zip(v.shape, sh.spec):
                if axes is None:
                    continue
                axes = (axes,) if isinstance(axes, str) else axes
                n = int(np.prod([self.mesh.axes.get(a, 1) for a in axes]))
                if dim % n != 0:
                    raise ValueError(
                        f"feed {k!r} dim of size {dim} is not divisible by "
                        f"the mesh axes {axes} (size {n}); pad the batch or "
                        "resize the mesh")
        return fetch_names, state_rw, state_ro, feed_vals

    def _build_fn(self, fetch_names, state_rw, state_ro, feed_vals):
        """jit the lowered step with this mesh's shardings pinned (the
        cache-miss path of run(); also the stats path)."""
        program = self.program
        step_fn = lower_program(program, fetch_names, "train")
        rw_sh = {n: self._var_sharding(n) for n in state_rw}
        ro_sh = {n: self._var_sharding(n) for n in state_ro}
        fd_sh = {n: self._feed_sharding(n) for n in feed_vals}
        rep = self.mesh.replicated()
        # pin the output state to the same shardings as the input state
        # so donated buffers round-trip with a stable placement; the
        # NaN-guard flags vector is an extra (replicated) output key
        rw_sh_out = dict(rw_sh)
        if getattr(program, "_nan_guard", False):
            rw_sh_out["__nan_guard__"] = rep
        fn = jax.jit(
            make_stepped(
                step_fn, on_trace=lambda feed: profiler.compile_traced(
                    "ParallelExecutor", program, feed)),
            in_shardings=(rw_sh, ro_sh, fd_sh, rep),
            out_shardings=(rw_sh_out, None),
            donate_argnums=(0,))
        fn.step_fn = step_fn
        return fn

    # ------------------------------------------------------------------
    def run(self, fetch_list, feed=None, feed_dict=None, return_numpy=True):
        feed = feed if feed is not None else (feed_dict or {})
        with record_event("pt:pexecutor/run", program=self.program.uid,
                          step=self._step + 1) as span:
            return self._run(fetch_list, feed, return_numpy, span.t0)

    def _run(self, fetch_list, feed, return_numpy, t0):
        program = self.program
        # its own span: _prepare pulls a device-resident feed to the
        # host and back (jnp.asarray(np.asarray(v))) on every step
        with record_event("pt:pexecutor/prepare"):
            fetch_names, state_rw, state_ro, feed_vals = \
                self._prepare(feed, fetch_list)

        key = (program.uid, program.version, tuple(fetch_names))
        fn = self._cache.get(key)
        compiling = None
        if fn is None:
            fn = self._build_fn(fetch_names, state_rw, state_ro,
                                feed_vals)
            self._cache[key] = fn
            # the compile log's bracket, as core Executor.run opens it
            compiling = profiler.open_compile(
                "ParallelExecutor", program, feed_vals, t0)

        self._step += 1

        try:
            with mesh_scope(self.mesh), \
                    record_event("pt:pexecutor/dispatch"):
                new_state, fetches = fn(state_rw, state_ro, feed_vals,
                                        step_arg(self._step,
                                                 program.random_seed))
        except BaseException:
            profiler.drop_compile()     # nothing compiled: no entry
            raise
        if compiling is not None:
            compiling.close()

        # scope first: state_rw was donated, so a guard raise before
        # this write would leave the scope aimed at deleted buffers
        # (same ordering as core Executor.run)
        for n, v in new_state.items():
            self.scope.set(n, v)

        check_nan_guard(new_state, fn)
        if return_numpy:
            fetches = [np.asarray(v) for v in fetches]
        return fetches

    # ------------------------------------------------------------------
    def compiled_stats(self, fetch_list, feed=None, top_k=10,
                       include_hlo=False):
        """Measured multichip compile evidence: AOT-lowers exactly the
        sharded executable ``run`` would dispatch (same shardings, same
        lowering) and reports XLA's numbers (flops / bytes_accessed /
        n_kernels / kernel_histogram, as Executor.compiled_stats does)
        PLUS a ``collectives`` histogram — how many all-reduce /
        all-gather / reduce-scatter / collective-permute / all-to-all
        ops GSPMD inserted for this mesh. ``collectives`` is OMITTED
        (not ``{}``) when the optimized HLO text is unavailable
        (``n_kernels == -1``), so callers can tell "no collectives
        inserted" from "text unavailable". This is the compile-time
        artifact behind SURVEY §6's allreduce story: single-process
        environments can't measure collective BANDWIDTH, but the
        compiled module proves which collectives a given sharding
        induces (reference: ParallelExecutor's NCCL AllReduce op
        handles, paddle/fluid/framework/details/).
        ``include_hlo=True`` keeps the optimized module text under
        ``hlo_text`` (megabytes)."""
        from ..core.executor import compiled_cost_stats
        fetch_names, state_rw, state_ro, feed_vals = \
            self._prepare(feed or {}, fetch_list)
        fn = self._build_fn(fetch_names, state_rw, state_ro, feed_vals)
        with mesh_scope(self.mesh):
            compiled = fn.lower(
                state_rw, state_ro, feed_vals,
                step_arg(1, self.program.random_seed)).compile()
        stats = compiled_cost_stats(compiled, top_k, include_hlo=True)
        stats["mesh"] = dict(self.mesh.axes)
        hlo_text = stats.get("hlo_text")
        if not include_hlo:
            stats.pop("hlo_text", None)
        if hlo_text is None:
            # n_kernels == -1: the optimized module text was unavailable.
            # Leaving "collectives" out (rather than {}) lets consumers —
            # notably dryrun_multichip, which treats a missing histogram
            # as fatal — distinguish "no collectives inserted" from
            # "HLO text unavailable".
            return stats
        coll = {}
        for m in _COLLECTIVE_RE.finditer(hlo_text):
            coll[m.group(1)] = coll.get(m.group(1), 0) + 1
        stats["collectives"] = coll
        return stats

    # ------------------------------------------------------------------
    def compile_counts(self):
        """``{cache_key: n_shape_specializations}`` with ``Executor.
        compile_counts``'s meaning: how many XLA executables stand
        behind each lowered program (jax.jit re-specializes per
        feed-shape signature). Keys are ``(program_uid,
        program_version, fetch_names)``."""
        return {k: int(fn._cache_size()) for k, fn in self._cache.items()}

    def total_compiles(self):
        """Total XLA executables cached across every lowered program —
        the scalar a no-recompile check compares."""
        return sum(self.compile_counts().values())

    @property
    def device_count(self):
        return self.mesh.size()
