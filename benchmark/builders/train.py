"""The training cells: a model from a configuration file, trained through
Executor (one chip, ``repeats`` steps fused per dispatch, as bench.py's
ResNet rung does) or ParallelExecutor (a mesh), on one synthetic batch made
on the device from the seed and staged there.

set_up() builds the program, runs the startup program, stages the batch and
runs one step alone (its loss is the one checked against the
initialisation) and the dispatch twice (compile or cache read, then once
warm). measure()
keeps one dispatch in flight and counts the optimizer steps of the
dispatches that completed inside the window.
"""
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

import paddle_tpu as fluid

from ..tracing import TRACE_SECONDS, span
from .serve import llama_config, seed_key


def _compile_events():
    """Count of JAX's own backend-compile events so far: the count for
    an executor that keeps none of its own (ParallelExecutor)."""
    if not _compile_events.seen:
        _compile_events.seen.append(0)

        def listen(name, _seconds, **_):
            if name.endswith("backend_compile_duration"):
                _compile_events.seen[0] += 1

        jax.monitoring.register_event_duration_secs_listener(listen)
    return _compile_events.seen[0]


_compile_events.seen = []


def _optimizer(spec):
    if spec["kind"] == "momentum":
        return fluid.optimizer.Momentum(learning_rate=spec["lr"],
                                        momentum=spec["momentum"])
    if spec["kind"] == "adam":
        return fluid.optimizer.Adam(learning_rate=spec["lr"])
    raise ValueError(f"unknown optimizer {spec!r}")


def _build_resnet(config, b):
    from paddle_tpu.models.resnet import resnet50
    from paddle_tpu.transpiler import amp_transpile
    size, classes = config["image_size"], config["num_classes"]
    img = fluid.layers.data(name="img", shape=[3, size, size],
                            dtype="float32")
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    loss, _, _ = resnet50(img, label, class_num=classes,
                          layout=b["layout"])
    _optimizer(b["optimizer"]).minimize(loss)
    if b.get("amp"):
        amp_transpile(fluid.default_main_program(), level=b["amp"])

    def feed(key):
        k1, k2 = jax.random.split(key)
        return {"img": jax.random.uniform(
                    k1, (b["batch"], 3, size, size), jnp.float32),
                "label": jax.random.randint(
                    k2, (b["batch"], 1), 0, classes, jnp.int32)}
    return loss, feed, b["batch"], math.log(classes)


def _build_llama(config, b):
    from paddle_tpu.models.llama import build_llama
    cfg = llama_config(config)
    seq, batch = b["seq"], b["batch"]
    tokens = fluid.layers.data(name="tokens", shape=[-1, seq],
                               dtype="int64", append_batch_size=False)
    targets = fluid.layers.data(name="targets", shape=[-1, seq],
                                dtype="int64", append_batch_size=False)
    _, loss = build_llama(cfg, tokens, targets, **b["build_llama"])
    _optimizer(b["optimizer"]).minimize(loss)

    def feed(key):
        toks = jax.random.randint(key, (batch, seq), 0, cfg.vocab_size,
                                  jnp.int32)
        # the NEXT token, not the token itself
        return {"tokens": toks, "targets": jnp.roll(toks, -1, axis=1)}
    return loss, feed, batch * seq, math.log(cfg.vocab_size)


MODELS = {"resnet50": _build_resnet, "llama": _build_llama}


class TrainSystem:
    def __init__(self, config, seed):
        b = config["builder"]
        self.config, self.b = config, b
        self.repeats = int(b.get("repeats", 1))
        main_p, startup_p = fluid.Program(), fluid.Program()
        main_p.random_seed = startup_p.random_seed = \
            seed % (2 ** 31 - 1) + 1
        with span("build_program"), \
                fluid.program_guard(main_p, startup_p):
            self.loss, make_feed, self.items_per_step, self.ln_classes = \
                MODELS[b["model"]](config, b)
        self.main_p = main_p
        self.scope = fluid.Scope()
        self.exe = fluid.Executor()
        with span("startup"), fluid.scope_guard(self.scope):
            self.exe.run(startup_p)
        # made on the device in one jitted call, and left uncommitted as
        # the startup program's state is: a committed feed commits the
        # step's outputs and the second step compiles again
        self.feed = jax.jit(make_feed)(seed_key(seed))
        self.pe = None
        if b["executor"] == "parallel":
            from paddle_tpu.parallel.mesh import make_mesh
            self.mesh = make_mesh(dict(b["mesh"]))
            with span("shard_state"):
                self._shard_state()
            self.pe = fluid.ParallelExecutor(
                loss_name=self.loss.name, main_program=main_p,
                scope=self.scope, mesh=self.mesh)
        self.losses = []
        with span("warmup"):
            # the loss of the very first step, before any update, is the
            # one the configuration can state from the initialisation
            # alone: a fused dispatch returns only its last step's
            self.losses.append(self.dispatch(repeats=1))
            for _ in range(2 if self.repeats > 1 else 1):
                self.losses.append(self.dispatch())
            jax.block_until_ready(self.losses[-1])
        print(f"train: {b['model']} through "
              f"{'ParallelExecutor ' + str(b.get('mesh')) if self.pe else 'Executor'}"
              f", {self.items_per_step} items a step, {self.repeats} "
              "step(s) a dispatch", flush=True)

    def _shard_state(self):
        """Moves the startup program's state, which it made whole on one
        device, to the sharding its variable asks for on the mesh (the
        annotation build_llama leaves on it; none means replicated). The
        first sharded step would do the same, but with the whole copy
        still alive beside the shards and the step's temporaries: that
        peak, not the step's own, would then set the depth that fits."""
        from jax.sharding import NamedSharding, PartitionSpec
        mesh = self.mesh.mesh
        gb = self.main_p.global_block()
        for name in list(self.scope.vars):
            value = self.scope.find_var(name)   # one at a time: the whole
            if value is None:                   # copy is freed as its
                continue                        # shards replace it
            spec = getattr(gb.vars.get(name), "sharding", None) \
                or PartitionSpec()
            fits = all(
                axes is None or (
                    set((axes,) if isinstance(axes, str) else axes)
                    <= set(mesh.axis_names)
                    and dim % math.prod(
                        mesh.shape[a] for a in
                        ((axes,) if isinstance(axes, str) else axes)) == 0)
                for dim, axes in zip(value.shape, spec))
            self.scope.set(name, jax.device_put(
                value, NamedSharding(mesh, spec if fits
                                     else PartitionSpec())))

    def dispatch(self, repeats=None):
        """One dispatch (``repeats`` optimizer steps, the configuration's
        unless given); returns the loss of its last step as a device
        array, not waited for."""
        with span("dispatch"):
            if self.pe is not None:
                return self.pe.run([self.loss], feed=self.feed,
                                   return_numpy=False)[0]
            with fluid.scope_guard(self.scope):
                return self.exe.run(
                    self.main_p, feed=self.feed, fetch_list=[self.loss],
                    return_numpy=False,
                    repeats=repeats or self.repeats)[0]

    def step_footprint_bytes(self):
        """Bytes one device needs while the step runs, by XLA's own
        memory analysis of the executable that ran (arguments + outputs
        + temporaries - aliased): the executors' compiled_stats. On this
        installation the allocator's peak_bytes_in_use leaves a running
        program's temporaries out (ResNet-50 at batch 256 reads 0.84 GB
        there, less than three of its saved activations), so the two are
        reported apart (peak_hbm_gb.train, step_footprint_gb) and the
        cell's memory_peak_bytes is the larger."""
        if self.pe is not None:
            stats = self.pe.compiled_stats([self.loss], feed=self.feed,
                                           top_k=0)
        else:
            with fluid.scope_guard(self.scope):
                stats = self.exe.compiled_stats(
                    self.main_p, feed=self.feed, fetch_list=[self.loss],
                    repeats=self.repeats, top_k=0)
        return int(stats.get("peak_memory_bytes", 0))

    def compiles(self):
        if self.pe is not None:
            return _compile_events()
        return self.exe.total_compiles()

    def close(self):
        self.exe.close()


def set_up(config, job, seed):
    _compile_events()
    return TrainSystem(config, seed)


def measure(system, job, seconds, seed, tracer):
    """Lead-in dispatches, then the window: from the completion of the
    last lead-in dispatch to the first completion at or after
    ``seconds`` later. One dispatch is always in flight behind the one
    being waited for, so the device never waits for the host."""
    edges = {}
    losses = list(system.losses)
    pending = system.dispatch()
    for _ in range(int(job.get("lead_in_dispatches", 1))):
        nxt = system.dispatch()
        with span("wait"):
            jax.block_until_ready(pending)
        losses.append(pending)
        pending = nxt
    t_start = time.monotonic()
    edges["start"] = {"t": t_start, "compiles": system.compiles()}
    trace_at = t_start + max(0.0, (seconds - TRACE_SECONDS) / 2)
    done = 0
    untraced = None     # (steps, seconds) before the profiler came on
    while True:
        nxt = system.dispatch()
        with span("wait"):
            jax.block_until_ready(pending)
        now = time.monotonic()
        losses.append(pending)
        pending = nxt
        done += 1
        if tracer.enabled and tracer.started is None and now >= trace_at:
            untraced = (done * system.repeats, now - t_start)
            tracer.start()
        elif tracer.started is not None \
                and now >= tracer.started + min(TRACE_SECONDS, seconds):
            tracer.stop()
        if now - t_start >= seconds:
            break
    edges["end"] = {"t": now, "compiles": system.compiles()}
    tracer.stop()
    jax.block_until_ready(pending)      # not counted: it ended outside
    footprint = system.step_footprint_bytes()

    losses = [float(np.asarray(x).reshape(())) for x in losses]
    problems = []
    if not np.isfinite(losses).all():
        problems.append(f"a loss is not finite: {losses[:8]}...")
    want, tol = system.b["first_loss"], system.b["first_loss_tolerance"]
    if abs(losses[0] - want) > tol:
        problems.append(
            f"the first step's loss {losses[0]:.4f} is not within {tol} "
            f"of {want}, which the configuration states for its "
            f"initialisation (ln(classes) = {system.ln_classes:.4f})")
    moved = edges["end"]["compiles"] - edges["start"]["compiles"]
    if moved:
        problems.append(f"{moved} compilation(s) inside the window")
    print("losses (the first step's, then the last step's of each "
          "dispatch):",
          [round(x, 4) for x in losses[:6]], "...",
          [round(x, 4) for x in losses[-3:]], flush=True)
    return {
        "kind": "train", "edges": edges,
        "steps": done * system.repeats, "dispatches": done,
        "repeats": system.repeats,
        "items_per_step": system.items_per_step,
        "window_s": edges["end"]["t"] - edges["start"]["t"],
        # starting and stopping the profiler stalls this loop for seconds:
        # a traced run's rate is that of the part before it came on
        "untraced": untraced, "step_footprint_bytes": footprint,
        "losses": losses, "attempted": done * system.repeats,
        "failed": 0, "problems": problems,
    }
