"""chip_smoke.py — does the system still start on the chip?

One process drives the two normal entry points once, at the widths of
one model the repo supports (dense Llama at the Llama-3-8B widths:
hidden 4096, 32 query / 8 KV heads of 128, FFN 14336, vocabulary
128,256), and checks what comes out by the repo's own means:

  0. device   JAX must report a TPU; there is no CPU mode, flag or switch
  1. kernel   the three Pallas flash kernels against the float32 reference
  2. trainer  build_llama -> Executor(TPUPlace()): loss falls, the compiled
              step holds the Mosaic calls, nothing recompiles or retries
  3. server   DecodeEngine, all 32 layers in int8: 12 overlapping requests
              over both prompt buckets, one repeated alone
  4. four chips (only where JAX reports >= 4 devices): the trainer's model
              through ParallelExecutor on a dp2 x tp2 mesh

Run it through the chip tool, one process per chip:  python chip_smoke.py
It exits non-zero and prints no result where JAX finds no TPU. A phase
that fails stops the run: the summary says which, and the exit code is 1.
The last two lines of stdout are one JSON object each: the summary (JAX
version, every phase's status, reason and seconds, "claim": null), then
the driver's line, exactly {"ok": ..., "device": {"platform", "kind",
"count"}} as JAX reports the device. Weights are random, made
from a seed; depth (and for training the vocabulary rows) is cut to what
one 16 GB chip holds, and the cut is printed. Every time printed here is
a smoke timing, not a benchmark.

The phases are plain functions of a SmokeConfig so that
tests/test_chip_smoke.py can rehearse them at LLAMA_TINY on the CPU.
"""
import contextlib
import dataclasses
import gc
import json
import re
import sys
import time
import traceback
import warnings

import numpy as np

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.models.llama import (LLAMA3_8B, LlamaConfig, build_llama,
                                     random_int8_generator_weights)
from paddle_tpu.ops import pallas_attention
from paddle_tpu.parallel.mesh import make_mesh
from paddle_tpu.serving.decode_engine import DecodeConfig, DecodeEngine


@dataclasses.dataclass(frozen=True)
class SmokeConfig:
    """The sizes of one smoke run. main() uses CHIP and nothing else."""
    place: type                 # the chip: the trainer's Executor place,
                                # and where every array must live
    model: LlamaConfig          # the served model; its widths are the point
    kernel_shape: tuple         # [B, H, T, D] of the kernel check
    mosaic_min: dict            # Mosaic custom calls the HLO must hold
    train_layers: int           # depth cut for training
    train_vocab: int            # vocabulary rows cut for training
    train_batch: int
    train_seq: int
    fused_head_chunk: int
    decode: dict                # DecodeConfig arguments
    prompt_lens: tuple          # one request each, spread over the buckets
    multichip_layers: int
    train_steps: int = 5
    multichip_steps: int = 3
    seed: int = 0


CHIP = SmokeConfig(
    place=fluid.TPUPlace, model=LLAMA3_8B,
    kernel_shape=(1, 8, 1024, 128),
    mosaic_min={"kernel": 1, "train_fwd": 1, "train_bwd": 2},
    # bf16 weights and two bf16 Adam moments stay resident, 6 bytes a
    # parameter, and gradients come and go: 4 of 32 layers (872M) and a
    # quarter of the vocabulary rows (2 x 134M) peak at 6.9 GB of the
    # chip's 16 (memory_stats, PR 21's chip run)
    train_layers=4, train_vocab=32768, train_batch=4, train_seq=1024,
    fused_head_chunk=2048,
    decode=dict(quantize=True, max_batch=8, prompt_buckets=(128, 512),
                max_new_tokens=64),
    prompt_lens=(16, 100, 128, 40, 300, 512, 129, 77, 450, 200, 5, 350),
    multichip_layers=2)

# a warning with one of these in it is a failure that was degraded
# instead of reported: a retried dispatch (core/executor.py), a rewrite
# that fell back to the unoptimized program
_DEGRADED = ("transient device error on dispatch", "rewrite failed")


def _compile_clock():
    """Seconds JAX has spent in backend compilation (or loading from the
    persistent cache) so far in this process, by the program's compile
    log (paddle_tpu/profiler.py): the executors' entries and the stray
    compiles beside them. Tracing and lowering are not in it: no cache
    saves those."""
    totals = fluid.profiler.compile_totals()
    return totals["compile_s"] + totals["stray"]["compile_s"]


@contextlib.contextmanager
def _phase(result):
    """Times one phase into ``result`` (compile_s, run_s), collects the
    warnings raised in it from any thread, and fails it if one of them
    is a degraded failure."""
    c0, t0 = _compile_clock(), time.perf_counter()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        yield
    wall = time.perf_counter() - t0
    result["compile_s"] = round(_compile_clock() - c0, 2)
    result["run_s"] = round(wall - result["compile_s"], 2)
    fired = sorted({f"{w.category.__name__}: {str(w.message)[:160]}"
                    for w in caught})
    result["warnings"] = fired
    degraded = [w for w in fired if any(d in w for d in _DEGRADED)]
    assert not degraded, f"a failure was degraded to a warning: {degraded}"


def _mosaic_lines(hlo_text):
    """The Mosaic custom calls in optimized HLO text, loop bodies
    included, one line each."""
    return [line for line in hlo_text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


def mosaic_calls(hlo_text):
    """{kernel name: count} of the Mosaic custom calls. The name is the
    one pallas_attention gives each pallas_call; a call without one
    counts as 'unnamed'."""
    found = {}
    for line in _mosaic_lines(hlo_text):
        m = re.search(r"flash_(?:fwd|bwd_dq|bwd_dkv)", line)
        name = m.group(0) if m else "unnamed"
        found[name] = found.get(name, 0) + 1
    return found


def _platforms(arrays):
    return {d.platform for a in arrays for d in a.devices()}


def _scope_arrays(scope):
    return [v for v in scope.vars.values() if v is not None]


def _device_bytes_in_use(devices):
    return [d.memory_stats()["bytes_in_use"] for d in devices]


# ----------------------------------------------------------------------
# phase 0
# ----------------------------------------------------------------------
def phase_device():
    """What JAX sees. Sets no JAX_PLATFORMS; exits non-zero, with no
    result printed, unless the platform is tpu."""
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"jax {jax.__version__}  platform={device['platform']}  "
          f"device_kind={device['kind']}  devices={device['count']}",
          flush=True)
    if device["platform"] != "tpu":
        sys.exit(f"chip_smoke: JAX found platform {device['platform']!r}, "
                 "not 'tpu'; this script has no CPU mode")
    return device


# ----------------------------------------------------------------------
# phase 1
# ----------------------------------------------------------------------
def phase_kernel(cfg):
    """flash_attention (forward, dq, dk/dv kernels) against
    _ref_attention_lse on one seeded bf16 block, both under
    jax.default_matmul_precision("highest")."""
    result = {}
    keys = jax.random.split(jax.random.PRNGKey(cfg.seed), 4)
    q, k, v, do = (
        (0.5 * jax.random.normal(kk, cfg.kernel_shape)).astype(jnp.bfloat16)
        for kk in keys)
    assert pallas_attention._use_pallas() \
        and pallas_attention._kernel_shapes_ok(q, k), \
        f"shape {cfg.kernel_shape} does not land on the kernel"
    scale = 1.0 / np.sqrt(cfg.kernel_shape[-1])

    def kernel_out(q, k, v):
        return pallas_attention.flash_attention(q, k, v, True, None)

    def ref_out(q, k, v):
        # the same bf16 values, widened: the reference does every
        # product and sum in float32 and rounds nothing
        return pallas_attention._ref_attention_lse(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), scale, True)[0]

    def with_grads(fn):
        def loss(q, k, v):
            return jnp.sum(fn(q, k, v).astype(jnp.float32)
                           * do.astype(jnp.float32))
        return jax.jit(lambda q, k, v: (
            fn(q, k, v), *jax.grad(loss, argnums=(0, 1, 2))(q, k, v)))

    with _phase(result), jax.default_matmul_precision("highest"):
        compiled = with_grads(kernel_out).lower(q, k, v).compile()
        got = jax.block_until_ready(compiled(q, k, v))
        want = jax.block_until_ready(with_grads(ref_out)(q, k, v))
        result["mosaic_calls"] = mosaic_calls(compiled.as_text())
        assert sum(result["mosaic_calls"].values()) \
            >= cfg.mosaic_min["kernel"], result["mosaic_calls"]
        # Tolerance, as a share of each tensor's largest element. The
        # kernels round to bf16 (relative step 2**-8) where the
        # reference rounds nothing: every result once on its way out,
        # and in the backward the saved output o, which enters
        # delta = rowsum(do * o) and through it every ds tile. With
        # exact float32 arithmetic (the Pallas interpreter on the CPU,
        # this seed and shape) that comes to 1.1e-3 for out and at most
        # 6.5e-3 for a gradient (dk). On the chip "highest" adds about
        # 1e-6. 2e-2 is three times the measured figure: it passes
        # that, and fails a wrong mask, scale or block index, which
        # move a result by tens of percent.
        tol = 2e-2
        result["max_rel_err"] = {}
        for name, a, w in zip(("out", "dq", "dk", "dv"), got, want):
            a = np.asarray(a, np.float32)
            w = np.asarray(w, np.float32)
            assert a.shape == w.shape and np.isfinite(a).all(), name
            err = float(np.max(np.abs(a - w)) / np.max(np.abs(w)))
            result["max_rel_err"][name] = round(err, 5)
            assert err <= tol, f"{name}: {err:.3e} > {tol}"
    return result


# ----------------------------------------------------------------------
# phase 2
# ----------------------------------------------------------------------
def _train_program(cfg, n_layers, **shard):
    """The trainer's model: cfg.model's widths, depth and vocabulary rows
    cut, next-token loss, Adam. Returns (main, startup, loss, feed)."""
    mcfg = dataclasses.replace(cfg.model, n_layers=n_layers,
                               vocab_size=cfg.train_vocab)
    main_p, startup_p = fluid.Program(), fluid.Program()
    main_p.random_seed = startup_p.random_seed = cfg.seed + 1
    with fluid.program_guard(main_p, startup_p):
        tokens = fluid.layers.data(
            name="tokens", shape=[-1, cfg.train_seq], dtype="int64",
            append_batch_size=False)
        targets = fluid.layers.data(
            name="targets", shape=[-1, cfg.train_seq], dtype="int64",
            append_batch_size=False)
        _, loss = build_llama(mcfg, tokens, targets,
                              fused_head_chunk=cfg.fused_head_chunk,
                              **shard)
        # Adam, not the momentum of the earlier Llama rung: parameters
        # are stored in bf16, and a momentum step at a usable learning
        # rate is below half a bf16 ulp of a 0.02-sized weight, so it
        # rounds away and the loss cannot fall
        fluid.optimizer.Adam(learning_rate=1e-4).minimize(loss)
    toks = np.random.RandomState(cfg.seed).randint(
        0, cfg.train_vocab, (cfg.train_batch, cfg.train_seq))
    feed = {"tokens": toks.astype(np.int64),
            # the NEXT token, not the token itself
            "targets": np.roll(toks, -1, axis=1).astype(np.int64)}
    return main_p, startup_p, loss, feed


def phase_trainer(cfg):
    """build_llama(shard_pp=True, fused_head_chunk=...) on
    Executor(cfg.place()): startup, then train_steps steps on one fixed
    seeded batch."""
    result = {"cut": f"layers {cfg.model.n_layers} -> {cfg.train_layers}, "
                     f"vocabulary rows {cfg.model.vocab_size} -> "
                     f"{cfg.train_vocab}, batch {cfg.train_batch} x "
                     f"seq {cfg.train_seq}"}
    print("trainer:", result["cut"], flush=True)
    main_p, startup_p, loss, feed = _train_program(
        cfg, cfg.train_layers, shard_pp=True)
    exe = fluid.Executor(cfg.place())
    scope = fluid.Scope()
    with _phase(result), fluid.scope_guard(scope):
        exe.run(startup_p)
        # staged once, and uncommitted like the state the startup
        # program made: a committed feed commits the step's outputs
        # and the second step compiles again
        with jax.default_device(exe.place.device):
            feed = {k: jax.device_put(v) for k, v in feed.items()}
        losses, step_s, compiles = [], [], []
        for _ in range(cfg.train_steps):
            t0 = time.perf_counter()
            out = exe.run(main_p, feed=feed, fetch_list=[loss])
            step_s.append(round(time.perf_counter() - t0, 3))
            losses.append(float(np.asarray(out[0]).reshape(())))
            compiles.append(exe.total_compiles())
        result["losses"] = [round(x, 4) for x in losses]
        result["step_s"] = step_s
        print(f"trainer: losses {result['losses']}  step seconds "
              f"{step_s} (smoke timing, not a benchmark)", flush=True)
        assert np.isfinite(losses).all(), losses
        assert losses[-1] < losses[0], \
            f"loss did not fall over {cfg.train_steps} steps: {losses}"
        assert compiles[-1] == compiles[1], \
            f"a step after the second compiled again: {compiles}"
        found = _platforms(_scope_arrays(scope))
        assert found == {exe.place.device.platform}, \
            f"scope arrays live on {found}, not on {exe.place.device}"
        stats = exe.compiled_stats(main_p, feed=feed, fetch_list=[loss],
                                   include_hlo=True)
        calls = mosaic_calls(stats["hlo_text"])
        result["mosaic_calls"] = calls
        result["n_kernels"] = stats["n_kernels"]
        fwd = calls.get("flash_fwd", 0)
        bwd = calls.get("flash_bwd_dq", 0) + calls.get("flash_bwd_dkv", 0)
        assert fwd >= cfg.mosaic_min["train_fwd"] \
            and bwd >= cfg.mosaic_min["train_bwd"], \
            f"Mosaic calls in the compiled step: {calls}"
    return result


# ----------------------------------------------------------------------
# phase 3
# ----------------------------------------------------------------------
def phase_server(cfg):
    """DecodeEngine on cfg.model at full depth with int8 weights made on
    the device: warmup, one request per cfg.prompt_lens submitted
    together so they overlap in the slots, then one of them again
    alone."""
    m = cfg.model
    result = {"cut": f"none: {m.n_layers} layers, vocabulary "
                     f"{m.vocab_size}, int8 weights"}
    print("server:", result["cut"], flush=True)
    scope = fluid.Scope()
    engine = None
    chip = cfg.place().device.platform
    with _phase(result):
        try:
            random_int8_generator_weights(
                m, fluid.Executor(cfg.place()), scope)
            # `place` left at its default: the engine must find the
            # chip by itself
            engine = DecodeEngine(m, scope=scope,
                                  config=DecodeConfig(**cfg.decode))
            result["warmup"] = engine.warmup()
            rng = np.random.RandomState(cfg.seed + 2)
            prompts = [rng.randint(0, m.vocab_size, n)
                       for n in cfg.prompt_lens]
            t0 = time.monotonic()
            done_at = {}
            reqs = []
            for i, p in enumerate(prompts):
                req = engine.submit(p)
                req.add_done_callback(
                    lambda _r, i=i: done_at.setdefault(i, time.monotonic()))
                reqs.append(req)
            outs = [np.asarray(r.result(timeout=300)) for r in reqs]
            n_new = engine.config.max_new_tokens
            for i, toks in enumerate(outs):
                assert toks.shape == (n_new,), (i, toks.shape)
                assert toks.min() >= 0 and toks.max() < m.vocab_size, i
                assert len(set(toks.tolist())) > 1, \
                    f"request {i} returned one value {n_new} times"
            # a request from the middle of the crowd, again and alone:
            # co-scheduling must not have changed its tokens
            again = len(prompts) // 2
            alone = np.asarray(engine.generate(prompts[again]))
            assert np.array_equal(alone, outs[again]), \
                f"request {again} alone != in the batch: " \
                f"{alone.tolist()} vs {outs[again].tolist()}"
            engine.assert_no_recompiles()
            assert engine.exe.place.device.platform == chip
            found = _platforms(list(engine._pools)
                               + _scope_arrays(scope))
            assert found == {chip}, \
                f"pools/weights live on {found}, not on {chip}"
            stats = engine.stats()
            must_be_zero = {
                k: v for k, v in stats.items()
                if k in ("breaker_open_total", "worker_died_total",
                         "errors_total", "timeouts_total")
                or "retr" in k}
            result["counters"] = must_be_zero
            assert "retries_total" in must_be_zero \
                and not any(must_be_zero.values()), must_be_zero
            result["ttft_s"] = [round(r.ttft_s, 3) for r in reqs]
            result["request_s"] = [round(done_at[i] - t0, 3)
                                   for i in range(len(reqs))]
            result["decode_batches"] = stats["decode_batches_total"]
            print(f"server: time to first token {result['ttft_s']}  "
                  f"per-request wall {result['request_s']} "
                  "(smoke timing, not a benchmark)", flush=True)
        finally:
            if engine is not None:
                engine.close()
    return result


# ----------------------------------------------------------------------
# phase 4
# ----------------------------------------------------------------------
def phase_multichip(cfg):
    """The trainer's model through ParallelExecutor on a dp2 x tp2 mesh.
    shard_pp does not compose with shard_tp, so this is the per-layer
    graph (multihead_attention op), not the stacked one."""
    n = len(jax.devices())
    if n < 4:
        print(f"multichip: not_run ({n} devices)", flush=True)
        return {"status": "not_run", "reason": f"{n} devices"}
    result = {"cut": f"layers {cfg.model.n_layers} -> "
                     f"{cfg.multichip_layers}, vocabulary rows "
                     f"{cfg.model.vocab_size} -> {cfg.train_vocab}"}
    print("multichip:", result["cut"], flush=True)
    main_p, startup_p, loss, feed = _train_program(
        cfg, cfg.multichip_layers, shard_dp=True, shard_tp=True)
    scope = fluid.Scope()
    mesh = make_mesh({"dp": 2, "tp": 2})
    with _phase(result):
        with fluid.scope_guard(scope):
            fluid.Executor(cfg.place()).run(startup_p)
        pe = fluid.ParallelExecutor(loss_name=loss.name,
                                    main_program=main_p, scope=scope,
                                    mesh=mesh)
        losses = [float(np.asarray(pe.run([loss], feed=feed)[0]).reshape(()))
                  for _ in range(cfg.multichip_steps)]
        result["losses"] = [round(x, 4) for x in losses]
        assert np.isfinite(losses).all(), losses
        wq = scope.find_var("l0.wq")            # P(None, "tp")
        shard = wq.addressable_shards[0].data.shape
        result["l0.wq"] = {"global": list(wq.shape), "shard": list(shard)}
        assert shard == (wq.shape[0], wq.shape[1] // 2), result["l0.wq"]
        in_use = _device_bytes_in_use(mesh.mesh.devices.flat)
        result["bytes_in_use"] = in_use
        print(f"multichip: bytes_in_use per device {in_use}", flush=True)
        assert in_use[0] < 0.5 * sum(in_use), \
            f"device 0 holds most of the memory in use: {in_use}"
        stats = pe.compiled_stats([loss], feed=feed, include_hlo=True)
        hlo = stats["hlo_text"]
        result["collectives"] = stats["collectives"]
        assert stats["collectives"], "no collective in the sharded step"
        # reported, not asserted: what the partitioner did with the
        # attention kernel (a Mosaic call cannot be partitioned)
        result["mosaic_calls"] = mosaic_calls(hlo)
        # the shapes each call returns say how it was split: the global
        # attention output is [batch * heads, seq, head_dim]
        result["mosaic_results"] = sorted({
            " ".join(re.findall(r"\w+\[[\d,]*\]",
                                line.split(" custom-call(", 1)[0]))
            for line in _mosaic_lines(hlo)})
        gathers = re.findall(
            r"(\w+\[[\d,]*\])\S* all-gather(?:-start)?\(", hlo)
        result["all_gathers"] = {s: gathers.count(s)
                                 for s in sorted(set(gathers))}
        print(f"multichip: losses {result['losses']}  collectives "
              f"{result['collectives']}  Mosaic calls "
              f"{result['mosaic_calls']}", flush=True)
    return result


# ----------------------------------------------------------------------
def main():
    device = phase_device()
    print("compile cache:", fluid.enable_compile_cache(), flush=True)
    phases = {"device": {"status": "pass", **device}}
    order = (("kernel", phase_kernel), ("trainer", phase_trainer),
             ("server", phase_server), ("multichip", phase_multichip))
    ok = True
    for name, fn in order:
        if not ok:
            phases[name] = {"status": "not_run",
                            "reason": "an earlier phase failed"}
            continue
        t0 = time.perf_counter()
        try:
            phases[name] = {"status": "pass", **fn(CHIP)}
        except Exception as e:   # reported below; nothing runs after it
            traceback.print_exc()
            ok = False
            phases[name] = {"status": "fail",
                            "reason": f"{type(e).__name__}: {e}"[:2000]}
        gc.collect()             # the next phase needs the memory
        print(f"{name}: {phases[name]['status']} in "
              f"{time.perf_counter() - t0:.1f}s  device 0 bytes_in_use="
              f"{_device_bytes_in_use(jax.devices()[:1])[0]}", flush=True)
    # two lines: the summary for a reader, then the line the driver
    # parses, which holds these keys and no other
    print(json.dumps({"jax": jax.__version__, "phases": phases,
                      "claim": None}), flush=True)
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
