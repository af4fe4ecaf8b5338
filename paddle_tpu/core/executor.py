"""Executor, Scope, Place.

Capability parity with Fluid's Executor/Scope/Place (reference
paddle/fluid/framework/executor.cc, scope.h, platform/place.h) with a
TPU-native execution model: ``Executor.run`` lowers the whole Program
into one function, ``jax.jit``-compiles it per (program-version, mode,
fetch-set) — JAX itself re-specializes on feed shapes — and donates the
read-write state so parameter updates are in-place in HBM.
"""
import os
import time
import warnings

import numpy as np

import jax
import jax.numpy as jnp

from . import framework
from .lowering import lower_program, written_names
from .. import profiler
from ..profiler import record_event
from ..resilience import faultinject as _faultinject
from ..resilience.retry import (TransientDeviceError, default_policy,
                                with_retries)

__all__ = ["Scope", "global_scope", "scope_guard", "Executor",
           "Place", "CPUPlace", "TPUPlace", "CUDAPlace", "EOFException",
           "force_cpu", "enable_compile_cache"]


class EOFException(Exception):
    """A started in-graph reader ran out of data (parity with
    fluid.core.EOFException — reference catches it to end an epoch)."""


class Scope:
    """Flat name → array store for persistable state (parameters, optimizer
    accumulators, batch-norm statistics). Reference
    paddle/fluid/framework/scope.h; hierarchy is unnecessary here because
    intermediate values live inside the XLA executable, never in host maps.
    """

    def __init__(self):
        self.vars = {}

    def find_var(self, name):
        return self.vars.get(name)

    def var(self, name):
        return self.vars.setdefault(name, None)

    def set(self, name, value):
        self.vars[name] = value

    def has(self, name):
        return name in self.vars

    def keys(self):
        return self.vars.keys()

    def drop_kids(self):  # fluid-compat no-op
        pass


_global_scope = Scope()


def global_scope():
    return _global_scope


import contextlib


def _switch_scope(scope):
    """Swap the global scope, returning the previous one (reference
    executor.py _switch_scope)."""
    global _global_scope
    old = _global_scope
    _global_scope = scope
    return old


@contextlib.contextmanager
def scope_guard(scope):
    global _global_scope
    old = _global_scope
    _global_scope = scope
    try:
        yield
    finally:
        _global_scope = old


class Place:
    """A device JAX can see. The base class is the process's default
    device (``jax.devices()[device_id]``): the chip where there is one,
    the CPU under ``JAX_PLATFORMS=cpu``. ``Executor()`` takes it."""
    platform = None

    def __init__(self, device_id=0):
        self.device_id = device_id

    @property
    def device(self):
        devs = jax.devices(self.platform)
        return devs[min(self.device_id, len(devs) - 1)]

    def __repr__(self):
        return f"{type(self).__name__}({self.device_id})"


class CPUPlace(Place):
    """The host CPU on every machine, whatever the default backend is
    (``jax.devices("cpu")``)."""
    platform = "cpu"


class TPUPlace(Place):
    """The point of the whole exercise — fluid.TPUPlace(). Resolves to a
    TPU device and raises where JAX finds none: a script that names the
    chip must not run on the CPU without a word."""
    platform = "tpu"

    @property
    def device(self):
        found = sorted({d.platform for d in jax.devices()})
        if "tpu" not in found:
            raise RuntimeError(
                f"TPUPlace: JAX found no TPU device (platforms found: "
                f"{found}). Use Executor() for the default device or "
                "CPUPlace() for the host.")
        return super().device


# CUDA does not exist here; alias to the accelerator so reference scripts
# using CUDAPlace keep working on TPU.
CUDAPlace = TPUPlace


def force_cpu():
    """The in-process form of launching with ``JAX_PLATFORMS=cpu``:
    call BEFORE the first device op. ``import paddle_tpu`` has already
    imported jax, which read the variable then, so this sets the config
    value as well as the environment (child processes inherit the
    latter). Safe to call more than once."""
    # racecheck: ok(global-mutation) — force_cpu IS the sanctioned
    # process-global switch (documented call-before-first-op contract);
    # racecheck flags its *callers* outside entrypoints instead
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")


def enable_compile_cache():
    """Turn on JAX's persistent compilation cache and return its
    directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX has read
    it already and nothing is set here; otherwise the cache sits at
    ``<checkout>/.jax_cache`` — a fixed path, because the path is part
    of the cache key and a directory that moves never hits."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir",
            os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__)))), ".jax_cache"))
    return jax.config.jax_compilation_cache_dir


def step_arg(step, seed):
    """The [step, seed] uint32 vector make_stepped consumes."""
    return np.asarray([step, seed or 0], dtype=np.uint32)


def check_nan_guard(new_state, fn):
    """Pop the guard flags (if guard mode emitted them) and raise naming
    the first non-finite op. Shared by both executors."""
    guard = new_state.pop("__nan_guard__", None)
    if guard is None:
        return
    flags = np.asarray(guard)
    if not flags.all():
        labels = getattr(fn.step_fn, "guard_labels", [])
        bad = [labels[i] if i < len(labels) else f"op#{i}"
               for i in np.nonzero(~flags)[0][:8]]
        raise FloatingPointError(
            "NaN/Inf guard tripped — first non-finite op "
            f"outputs: {bad}")


def make_stepped(step_fn, repeats=1, on_trace=None):
    """Wrap a lowered step function so the per-step rng derives INSIDE
    the executable from a tiny [step, seed] uint32 argument: a host-side
    fold_in would be a second device dispatch per step (its cost is not
    re-measured on this installation), and keeping the seed a
    runtime input (not a closure constant) means changing
    program.random_seed never recompiles. Shared by Executor and
    ParallelExecutor so their random streams cannot drift apart.

    ``repeats`` > 1 unrolls that many optimizer steps into ONE
    executable (same feed, rng advancing per sub-step exactly as
    separate runs would) — one dispatch instead of k. What a launch
    costs is not re-measured on this installation.

    ``on_trace(feed)`` is called where the step is TRACED, which a
    dispatch of an executable jax.jit already holds never does: the
    executors' hook for the compile log (profiler.compile_traced)."""
    def stepped(rw, ro, feed, step_seed):
        if on_trace is not None:
            on_trace(feed)
        fetches = None
        for i in range(repeats):
            rng = jax.random.fold_in(jax.random.PRNGKey(step_seed[1]),
                                     step_seed[0] + i)
            new_state, fetches = step_fn(rw, ro, feed, rng)
            # thread updated persistables into the next sub-step; the
            # env seeds from this dict by name, so extra keys (newly
            # created persistables) ride along harmlessly
            rw = new_state
        return rw, fetches
    return stepped


class Executor:
    """Whole-program XLA executor (vs. fluid's per-op interpreter,
    reference paddle/fluid/framework/executor.cc)."""

    def __init__(self, place=None, retry_policy=None,
                 donate_state=True):
        self.place = place or Place()
        self._cache = {}
        self._validated = set()
        # PADDLE_TPU_OPTIMIZE: (program uid, fetch names) -> (source
        # version, optimized clone) — the DCE/CSE'd twin actually
        # lowered when the opt-in hook is on
        self._opt_cache = {}
        self._step = 0
        # None → resilience.retry.default_policy() resolved per run, so
        # PADDLE_TPU_MAX_RETRIES / PADDLE_TPU_RETRY_BACKOFF changes in
        # a live process (or a test) take effect immediately
        self._retry_policy = retry_policy
        # donate_state=False keeps written-state buffers alive across a
        # dispatch (donation deletes them). Required when several
        # executors serve ONE scope concurrently — cluster replicas
        # sharing parameters: a donated buffer one replica deleted is a
        # buffer its peers still hold. Costs one buffer copy per
        # written state var per step, so training keeps the default.
        self._donate_state = bool(donate_state)

    # ------------------------------------------------------------------
    def run(self, program=None, feed=None, fetch_list=None, scope=None,
            return_numpy=True, mode=None, repeats=1, validate=None,
            donate_feeds=()):
        """``repeats`` > 1 runs that many train steps in ONE device
        dispatch on the same feed (rng advances per sub-step exactly as
        separate calls would); fetches are the LAST sub-step's. Not
        compatible with NaN-guard mode (the guard reports per
        dispatch).

        What a dispatch donates: the persistables the program writes
        (unless ``donate_state=False``), and the feeds ``donate_feeds``
        names, which the caller gives up: pass those as device arrays
        and hold them no longer, the call deletes each one XLA could
        alias to a fetch of its shape (the first named to the first
        fetched, so name them in fetch order) and the fetch is the same
        buffer written in place. No other feed is donated, and the
        persistables a program only reads (the weights) never are.

        ``validate`` gates the static verifier (analysis/) run once per
        newly-compiled program, BEFORE lowering: None reads
        ``PADDLE_TPU_VALIDATE`` (default "1" — cheap structural checks,
        error findings surface as VerifyWarning); "strict" runs the
        full pass pipeline and raises VerifyError on any error-level
        diagnostic; "0"/False disables."""
        program = program or framework.default_main_program()
        with record_event("pt:executor/run", program=program.uid,
                          step=self._step + 1, repeats=repeats) as span:
            return self._run(program, feed, fetch_list, scope,
                             return_numpy, mode, repeats, validate,
                             tuple(donate_feeds), span.t0)

    def _run(self, program, feed, fetch_list, scope, return_numpy, mode,
             repeats, validate, donate_feeds, t0):
        if not 1 <= repeats <= 32:
            # an unroll, deliberately: a lax.scan over sub-steps would
            # keep the executable O(1) in k at the price of a while-loop
            # iteration per sub-step (that price is not re-measured on
            # this installation) — small k is the design point, and the
            # cap keeps trace/compile time bounded
            raise ValueError(f"repeats must be in [1, 32], got {repeats}")
        if repeats > 1 and getattr(program, "_nan_guard", False):
            raise ValueError("repeats > 1 does not compose with the "
                             "NaN guard — flags are per dispatch")
        scope = scope or global_scope()
        feed = dict(feed) if feed else {}
        # in-graph readers (layers.py_reader / open_files / ...): any
        # started reader supplies its variables unless explicitly fed
        for r in getattr(program, "_readers", []):
            if r.started() and not all(n in feed for n in r.var_names()):
                for k, v in r.next_feed().items():
                    feed.setdefault(k, v)   # explicit feed keys win
        # static verification BEFORE anything is prepared or lowered,
        # once per (program version, fetch set, validate mode)
        verify_s = self._validate(program, fetch_list, feed, validate)
        # opt-in graph rewrites (PADDLE_TPU_OPTIMIZE): lower a DCE/CSE'd
        # clone instead of the caller's program — numerics-preserving by
        # construction (analysis/optimize.py), cached per fetch set
        program = self._maybe_optimize(program, fetch_list)
        fetch_names, mode, state_rw, state_ro, feed_vals = \
            self._prepare(program, feed, fetch_list, scope, mode)

        key = (program.uid, program.version, mode, tuple(fetch_names),
               repeats, donate_feeds)
        fn = self._cache.get(key)
        compiling = None
        if fn is None:
            # evict executables for older versions of this program so a
            # mutate-and-run loop doesn't leak compiled programs
            stale = [k for k in self._cache
                     if k[0] == program.uid and k[1] != program.version]
            for k in stale:
                del self._cache[k]
            fn = self._jit(program, fetch_names, mode, repeats,
                           donate_feeds, self._donate_state)
            self._cache[key] = fn
            # the compile log's bracket (profiler.py): from the top of
            # this run to the return of fn's first call
            compiling = profiler.open_compile(
                "Executor", program, feed_vals, t0, verify_s)

        self._step += 1
        first_step = self._step
        self._step += repeats - 1

        args = self._jit_args(state_rw, state_ro, feed_vals,
                              step_arg(first_step, program.random_seed),
                              donate_feeds)

        def _dispatch():
            # deterministic transient-fault point (resilience/
            # faultinject.py "device_error") — raises BEFORE the
            # executable consumes its donated buffers, like the real
            # transient class (enqueue/connection failures), so a retry
            # re-dispatches the same staged state safely. A failure
            # AFTER donation is not retryable this way: the second
            # attempt hits deleted buffers and propagates, which is the
            # pre-retry behavior — never worse.
            if _faultinject.fires("device_error"):
                raise TransientDeviceError(
                    "injected transient device error (UNAVAILABLE)")
            with jax.default_device(self.place.device):
                return fn(*args)

        policy = self._retry_policy or default_policy()
        # async: the span is host-side enqueue time; the device's time
        # is on the trace's own device lines, on the same clock
        try:
            with record_event("pt:executor/dispatch"):
                new_state, fetches = with_retries(
                    _dispatch, policy=policy,
                    on_retry=lambda exc, n, delay: warnings.warn(
                        f"transient device error on dispatch (failure "
                        f"{n}): {exc}; retrying in {delay:.3g}s",
                        stacklevel=3))
        except BaseException:
            profiler.drop_compile()     # nothing compiled: no entry
            raise
        if compiling is not None:
            compiling.close()

        # write the scope FIRST: state_rw was donated (its old buffers
        # are already deleted), so if the guard raises and the scope
        # still pointed at them, every later run would touch freed
        # device memory. The guard only inspects values.
        for n, v in new_state.items():
            scope.set(n, v)

        check_nan_guard(new_state, fn)

        if return_numpy:
            # SequenceBatch is a registered pytree, so this converts its
            # data/lengths leaves while keeping the container
            fetches = jax.tree_util.tree_map(np.asarray, fetches)
        return fetches

    # ------------------------------------------------------------------
    @staticmethod
    def _jit(program, fetch_names, mode, repeats, donate_feeds,
             donate_state):
        """The jitted step of ``program``: (written state, read state,
        feeds, [step, seed]) and, where the caller gives feeds up, a
        fifth argument that holds those alone, in the order named, so
        that they are donated and the feeds beside them are not."""
        step_fn = lower_program(program, fetch_names, mode)
        stepped = make_stepped(
            step_fn, repeats,
            lambda feed: profiler.compile_traced("Executor", program, feed))
        donate = (0,) if donate_state else ()
        if donate_feeds:
            kept_feeds = stepped

            def stepped(rw, ro, feed, step_seed, given):
                return kept_feeds(
                    rw, ro, dict(feed, **dict(zip(donate_feeds, given))),
                    step_seed)
            donate += (4,)
        fn = jax.jit(stepped, donate_argnums=donate)
        fn.step_fn = step_fn         # keeps NaN-guard labels reachable
        return fn

    @staticmethod
    def _jit_args(state_rw, state_ro, feed_vals, step_seed, donate_feeds):
        """``_jit``'s arguments: the feeds given up leave ``feed_vals``
        for a list of their own."""
        if not donate_feeds:
            return state_rw, state_ro, feed_vals, step_seed
        missing = [n for n in donate_feeds if n not in feed_vals]
        if missing:
            raise KeyError(f"donate_feeds names {missing}, which the "
                           f"feed does not hold")
        given = [feed_vals.pop(n) for n in donate_feeds]
        return state_rw, state_ro, feed_vals, step_seed, given

    # ------------------------------------------------------------------
    def _maybe_optimize(self, program, fetch_list):
        """The PADDLE_TPU_OPTIMIZE opt-in hook: returns the program to
        actually lower. "1"/"on" runs the full rewrite pipeline
        (fold + fuse + cse + dce, analysis/optimize.py); a
        comma-separated value ("fold,dce") selects exactly those
        passes. The rewrites run over an internal CLONE keyed by
        (program uid, fetch set), never the caller's program:
        fetch-set-specific dead-code removal must not leak into a
        program another call site fetches differently from. The clone
        is re-derived when the source program's version moves; a
        rewrite failure degrades to running the original (never blocks
        the run)."""
        flag = os.environ.get("PADDLE_TPU_OPTIMIZE", "0")
        if flag in ("0", "", "off", "none") or not fetch_list:
            return program
        fetch_names = tuple(
            v.name if isinstance(v, framework.Variable) else v
            for v in fetch_list)
        okey = (program.uid, fetch_names)
        cached = self._opt_cache.get(okey)
        if cached is not None and cached[0] == program.version:
            return cached[1]
        try:
            from ..analysis.optimize import parse_passes
            clone = program.clone(for_test=program._is_test)
            clone._nan_guard = getattr(program, "_nan_guard", False)
            clone.optimize(fetch_list=list(fetch_names),
                           passes=parse_passes(flag))
        except Exception as e:   # an optimizer bug must not block runs
            warnings.warn(
                f"PADDLE_TPU_OPTIMIZE rewrite failed ({e!r}); running "
                "the program unoptimized", stacklevel=3)
            clone = program
        if cached is not None:
            # the source program changed: drop executables lowered
            # from the stale clone
            for k in [k for k in self._cache if k[0] == cached[1].uid]:
                del self._cache[k]
        self._opt_cache[okey] = (program.version, clone)
        return clone

    # ------------------------------------------------------------------
    def _validate(self, program, fetch_list, feed, validate):
        """Pre-lowering static verification (analysis/), gated by the
        ``validate`` argument / PADDLE_TPU_VALIDATE env var, cached so
        each (program version, fetch set, mode) is checked ONCE — the
        same cadence as compilation, never per step. Cheap mode must
        never block a run: any error-level finding (or a verifier
        crash) degrades to a VerifyWarning. Strict mode runs the full
        pipeline and raises VerifyError before anything is lowered.
        Returns the seconds the verifier took (0.0 where it did not
        run), for the compile log's ``verify_s``."""
        mode = validate
        if mode is None:
            mode = os.environ.get("PADDLE_TPU_VALIDATE", "1")
        if mode in (False, "0", "off", "none"):
            return 0.0
        fetch_names = tuple(
            v.name if isinstance(v, framework.Variable) else v
            for v in (fetch_list or []))
        vkey = (program.uid, program.version, fetch_names, str(mode))
        if vkey in self._validated:
            return 0.0
        t0 = time.monotonic()
        from ..analysis import VerifyError, VerifyWarning, errors, \
            verify_program
        feed_names = sorted(feed) if feed else []
        if mode == "strict":
            diags = verify_program(program, fetch_list=fetch_names,
                                   feed_names=feed_names, level="full")
            if errors(diags):
                raise VerifyError(diags)
        else:
            try:
                diags = verify_program(program, fetch_list=fetch_names,
                                       feed_names=feed_names,
                                       level="cheap")
                for d in errors(diags):
                    warnings.warn(d.format(), VerifyWarning,
                                  stacklevel=3)
            except Exception as e:  # verifier bug — never block the run
                warnings.warn(f"program validation crashed ({e!r}); "
                              "set PADDLE_TPU_VALIDATE=0 to silence",
                              VerifyWarning, stacklevel=3)
        self._validated.add(vkey)
        return time.monotonic() - t0

    # ------------------------------------------------------------------
    def _prepare(self, program, feed, fetch_list, scope, mode,
                 strict=True):
        """The run()/compiled_stats() shared preamble: normalize fetch
        names, resolve mode, split scope persistables into donated
        (written) vs read-only state, stage feeds. One copy, so the
        stats path provably lowers the same executable run() uses."""
        gb = program.global_block()
        fetch_names = [v.name if isinstance(v, framework.Variable) else v
                       for v in (fetch_list or [])]
        if mode is None:
            mode = "test" if program._is_test else "train"
        written = written_names(gb)
        persistables = {n for n, v in gb.vars.items() if v.persistable}
        state_rw, state_ro = {}, {}
        for n in sorted(persistables):
            val = scope.find_var(n)
            if val is None:
                if n not in written and strict:
                    raise RuntimeError(
                        f"persistable variable {n!r} has no value in the "
                        "scope and is not produced by this program — did "
                        "you forget to run the startup program first?")
                continue  # created by this program (startup initializer)
            if isinstance(val, np.ndarray):
                # stage host values to the device ONCE and keep the
                # resident copy in the scope — otherwise every run()
                # re-uploads them (a host-written scope entry, e.g.
                # quantize_generator_weights' int8 tables, is gigabytes
                # per call)
                val = jnp.asarray(val)
                scope.set(n, val)
            if n in written:
                state_rw[n] = val
            else:
                state_ro[n] = val
        feed_vals = {k: self._to_array(v, gb) for k, v in feed.items()}
        return fetch_names, mode, state_rw, state_ro, feed_vals

    # ------------------------------------------------------------------
    def compiled_stats(self, program=None, feed=None, fetch_list=None,
                       scope=None, mode=None, repeats=1, top_k=10,
                       include_hlo=False, donate_feeds=()):
        """Measured (not inferred) compile-time evidence for a step:
        AOT-lowers exactly the executable ``run`` would use for this
        (program, feed, fetch, repeats) and reports XLA's own numbers —
        {'flops', 'bytes_accessed', 'n_kernels', 'peak_memory_bytes',
        'aliased_bytes', 'generated_code_size_bytes'}, with the feeds
        ``donate_feeds`` names given up as ``run`` would. ``n_kernels``
        counts non-trivial
        instructions in the optimized HLO entry computation (fusions,
        convolutions, custom calls, loops...) — each is roughly one
        kernel launch per step, the quantity a per-kernel-overhead
        gap analysis needs. The reference's profiler
        (paddle/fluid/platform/profiler.cc) answers this with a runtime
        per-op timeline; under whole-program XLA the compiled module IS
        the schedule, so the compiler's analysis replaces the tracer.

        With ``top_k`` (default 10) the dict additionally carries the
        per-kernel attribution the reference's chrome-trace timeline
        gives (python/paddle/fluid/profiler.py:221): a
        ``kernel_histogram`` — opcode → {count, mbytes} over the entry
        computation, fusions labeled by their fused root op — and the
        ``top_kernels`` list (kind, output shape, estimated bytes
        moved), so gap analyses can name WHICH kernels a step spends
        its launches on rather than only how many there are.
        ``include_hlo=True`` adds the optimized module text under
        ``hlo_text`` (megabytes), for checks that must see inside loop
        bodies — the entry computation does not show a scanned layer's
        kernels."""
        program = program or framework.default_main_program()
        scope = scope or global_scope()
        feed = dict(feed) if feed else {}
        fetch_names, mode, state_rw, state_ro, feed_vals = \
            self._prepare(program, feed, fetch_list, scope, mode,
                          strict=False)
        donate_feeds = tuple(donate_feeds)
        fn = self._jit(program, fetch_names, mode, repeats, donate_feeds,
                       True)
        compiled = fn.lower(*self._jit_args(
            state_rw, state_ro, feed_vals,
            step_arg(1, program.random_seed), donate_feeds)).compile()
        return compiled_cost_stats(compiled, top_k, include_hlo)

    # ------------------------------------------------------------------
    # compile-cache introspection (serving/ warmup leans on this to
    # PROVE bucket reuse: after pre-compiling every declared shape
    # bucket, steady-state traffic must not grow these numbers)
    def compile_cache_keys(self):
        """Snapshot of lowered-program cache keys, each
        ``(program_uid, program_version, mode, fetch_names, repeats,
        donate_feeds)``
        — one entry per distinct lowered step function."""
        return sorted(self._cache)

    def compile_counts(self):
        """``{cache_key: n_shape_specializations}`` — how many XLA
        executables stand behind each lowered program (jax.jit
        re-specializes per feed-shape signature, so each declared
        serving bucket contributes exactly one)."""
        return {k: int(fn._cache_size()) for k, fn in self._cache.items()}

    def total_compiles(self):
        """Total XLA executables currently cached across every lowered
        program — the scalar warmup assertions compare."""
        return sum(self.compile_counts().values())

    # ------------------------------------------------------------------
    @staticmethod
    def _to_array(v, block):
        from .sequence import SequenceBatch
        if isinstance(v, SequenceBatch):
            return v
        if isinstance(v, (jax.Array,)):
            return v
        arr = np.asarray(v)
        return jnp.asarray(arr)

    def close(self):
        self._cache.clear()
        self._opt_cache.clear()


def compiled_cost_stats(compiled, top_k=10, include_hlo=False):
    """Shared assembly of XLA's analyses for a compiled executable —
    used by Executor.compiled_stats and ParallelExecutor.compiled_stats
    so the two cannot drift when jax's cost_analysis shape changes.
    Returns {'flops','bytes_accessed'[,'peak_memory_bytes',
    'aliased_bytes','generated_code_size_bytes'],'n_kernels'
    [,'kernel_histogram','top_kernels']}; aliased_bytes is what the
    outputs share with donated arguments (written in place); n_kernels
    is -1 when the optimized module text is unavailable. include_hlo=True additionally returns the module text
    under 'hlo_text' (megabytes — callers that serialize the stats,
    like bench.py's KSTATS record, must leave it off)."""
    cost = compiled.cost_analysis()
    stats = {"flops": float(cost.get("flops", 0.0)),
             "bytes_accessed": float(cost.get("bytes accessed", 0.0))}
    try:
        mem = compiled.memory_analysis()
        stats["peak_memory_bytes"] = int(
            getattr(mem, "temp_size_in_bytes", 0)
            + getattr(mem, "argument_size_in_bytes", 0)
            + getattr(mem, "output_size_in_bytes", 0)
            - getattr(mem, "alias_size_in_bytes", 0))
        stats["aliased_bytes"] = int(
            getattr(mem, "alias_size_in_bytes", 0))
        stats["generated_code_size_bytes"] = int(
            getattr(mem, "generated_code_size_in_bytes", 0))
    except Exception:
        pass
    try:
        hlo = compiled.as_text()
        kernels = _entry_kernels(hlo)
        stats["n_kernels"] = len(kernels)
        if include_hlo:
            stats["hlo_text"] = hlo
        if top_k:
            stats["kernel_histogram"] = _kernel_histogram(kernels)
            stats["top_kernels"] = [
                {"kind": k, "shape": s, "mbytes": round(b / 2**20, 2)}
                for k, s, b in sorted(kernels, key=lambda t: -t[2])
                [:top_k]]
    except Exception:
        stats["n_kernels"] = -1
    return stats


# ----------------------------------------------------------------------
# Optimized-HLO kernel attribution (compiled_stats top_k support).
# Text-based on purpose: compiled.as_text() is the one stable window
# into the post-optimization module across jax versions/backends.
import re as _re

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e5m2fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}
_ARRAY_SHAPE_RE = _re.compile(r"([a-z]\w*)\[([\d,]*)\]")
_COMP_DEF_RE = _re.compile(r"^(?:ENTRY )?%?([\w.\-]+)[ (].*\{\s*$")
_INSTR_RE = _re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_TARGET_RE = _re.compile(r'custom_call_target="([^"]+)"')
_CALLS_RE = _re.compile(r"calls=%?([\w.\-]+)")
# pure data plumbing — not a device kernel launch.  Keep this set
# EXACTLY as it is: kernel counts compare across PRs.
_SKIP_OPS = {"parameter", "constant", "tuple", "get-tuple-element",
             "bitcast", "bitcast-convert"}


def _shape_bytes(s):
    """Total bytes of every array shape literal appearing in s."""
    total = 0
    for dt, dims in _ARRAY_SHAPE_RE.findall(s):
        nb = _DTYPE_BYTES.get(dt)
        if nb is None:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += nb * n
    return total


def _split_shape_opcode(rhs):
    """HLO rhs is '<shape> <opcode>(operands...), attrs'; the shape may
    be a (parenthesized, spaced) tuple."""
    rhs = rhs.strip()
    if rhs.startswith("("):
        depth = 0
        for i, c in enumerate(rhs):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                shape, rest = rhs[:i + 1], rhs[i + 1:].strip()
                break
        else:
            return rhs, "", ""
    else:
        cut = rhs.find(" ")
        if cut < 0:
            return rhs, "", ""
        shape, rest = rhs[:cut], rhs[cut + 1:].strip()
    par = rest.find("(")
    if par < 0:
        return shape, rest, ""
    return shape, rest[:par], rest[par:]


def _entry_kernels(hlo):
    """Parse optimized HLO text into [(kind, out_shape, est_bytes)] for
    every device-work instruction in the ENTRY computation.  Fusions
    are labeled fusion(<root op of the fused computation>), custom
    calls by their target.  est_bytes = output bytes + known operand
    output bytes (an instruction-level stand-in for bytes_accessed)."""
    comp_root = {}          # computation name -> ROOT opcode
    cur_comp = None
    entry_lines = []
    in_entry = False
    for line in hlo.splitlines():
        stripped = line.rstrip()
        if not stripped:
            continue
        if not stripped.startswith(" "):        # a computation header?
            m = _COMP_DEF_RE.match(stripped)
            if m and stripped.endswith("{"):
                cur_comp = m.group(1)
                in_entry = stripped.startswith("ENTRY")
            elif stripped.startswith("}"):
                cur_comp, in_entry = None, False
            continue
        if stripped.strip() == "}":
            cur_comp, in_entry = None, False
            continue
        if cur_comp is None:
            continue
        if in_entry:
            entry_lines.append(stripped)
        if "ROOT" in stripped:
            m = _INSTR_RE.match(stripped)
            if m:
                _, op, _ = _split_shape_opcode(m.group(2))
                comp_root.setdefault(cur_comp, op)

    sizes = {}              # defined name -> output bytes (entry scope)
    kernels = []
    for line in entry_lines:
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, rhs = m.groups()
        shape, op, args = _split_shape_opcode(rhs)
        out_bytes = _shape_bytes(shape)
        sizes[name] = out_bytes
        if not op or op in _SKIP_OPS:
            continue
        kind = op
        if op == "fusion":
            c = _CALLS_RE.search(args)
            root = comp_root.get(c.group(1)) if c else None
            kind = f"fusion({root})" if root else "fusion"
        elif op == "custom-call":
            t = _TARGET_RE.search(args)
            if t:
                kind = f"custom-call({t.group(1)})"
        operand_bytes = 0
        if args.startswith("("):
            # only the first balanced paren group is the operand list —
            # trailing attributes (metadata={op_name="..."} etc.) carry
            # tokens that collide with real instruction names
            depth = 0
            end = len(args)
            for i, c in enumerate(args):
                depth += (c == "(") - (c == ")")
                if depth == 0:
                    end = i
                    break
            for tok in _re.findall(r"%?([\w.\-]+)", args[1:end]):
                operand_bytes += sizes.get(tok, 0)
        kernels.append((kind, shape, out_bytes + operand_bytes))
    return kernels


def _kernel_histogram(kernels):
    """Aggregate [(kind, shape, bytes)] into a kind-keyed table sorted
    by total estimated bytes."""
    agg = {}
    for kind, _, b in kernels:
        cnt, tot = agg.get(kind, (0, 0))
        agg[kind] = (cnt + 1, tot + b)
    return [{"kind": k, "count": c, "mbytes": round(t / 2**20, 2)}
            for k, (c, t) in
            sorted(agg.items(), key=lambda kv: -kv[1][1])]
