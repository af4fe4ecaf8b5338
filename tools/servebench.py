#!/usr/bin/env python
"""servebench — serving load generator: batched vs single-request.

Builds a tiny model-zoo entry, stands up a
``paddle_tpu.serving.ServingEngine`` over it (warmup pre-compiles
every declared bucket), then drives the same request set two ways:

1. **baseline** — the pre-serving story: one synchronous
   ``Executor.run`` per request, one device dispatch each.
2. **batched** — ``--concurrency`` client threads submitting through
   the engine, which coalesces them into bucket-padded micro-batches.

Reports requests/s for both, the speedup, the engine's metrics
snapshot (batch-fill ratio, latency percentiles), and a correctness
sweep: every request's served rows must match its single-request rows
(the per-row fetch is the cross_entropy input — the model's
prediction head — so batch-mean scalars never blur the comparison).
The cross-shape comparison is tolerance-based (rtol 1e-5): XLA
legitimately re-tiles a matmul per batch shape, so batch-8 rows can
differ from batch-1 rows by an ulp — bit-for-bit equality holds
WITHIN a bucket shape and is pinned that way in tests/test_serving.py;
across buckets "zero dropped-correctness" means zero beyond-float-
tolerance divergences. ``assert_no_recompiles`` additionally proves
zero XLA compiles happened during traffic.

Usage:
  python tools/servebench.py [--model mnist_mlp] [--requests 128]
      [--concurrency 16] [--max-batch 8] [--max-wait-ms 2.0]
      [--assert-speedup 1.0] [--json] [--out FILE]

Exit 0 on success; exit 1 when correctness drops or the measured
speedup falls below ``--assert-speedup`` (tools/selfcheck.sh stage 3
gates on both). CPU-only, seconds.

Chaos mode (``--chaos``, tools/selfcheck.sh stage 4) swaps the
speedup race for a fault drill: it injects ``serving_device_error``
mid-load and asserts the hardening contract (docs/SERVING.md
"Operating under failure") — ZERO lost requests (every submission
terminates with a result or a typed error), the circuit breaker
demonstrably opens and then recovers once the fault clears, post-
recovery traffic is all-success with measurable throughput,
``close(drain=True)`` completes every in-flight request, and
``assert_no_recompiles`` still holds in steady state.

Decode mode (``--decode``, tools/selfcheck.sh stage 6) benchmarks the
continuous-batching decode engine (docs/SERVING.md "Continuous decode
batching") on a tiny llama config: baseline is sequential per-request
generation through the fused ``build_llama_generator`` program (one
request at a time — the pre-engine story), continuous is concurrent
submission through ``serving.DecodeEngine``. Reports aggregate tok/s
both ways, per-request greedy-token equality (exact), TTFT/TPOT
percentiles, a zero-recompile check, and a BENCH-compatible record
under ``bench_record`` (metric ``llama_decode_serving_tok_s``).
``--spec`` runs the engine in speculative mode (perfect draft).

SLO mode (``--decode --slo``, tools/selfcheck.sh stage 13) swaps the
throughput race for a scheduling-policy gate: a mixed short/long
interference trace runs under FIFO admission, the EDF SLO scheduler,
and a 2-prefill/2-decode disaggregated pool (docs/SERVING.md
"Disaggregated decode serving"), with the ``serving_handoff_drop``
chaos drill riding the pool arm. The interactive TTFT target is
calibrated to a quarter of FIFO's measured queue-wait TTFT, so the
pass/fail is scheduling-order-driven on any CPU speed: exit 1 unless
the SLO scheduler's TTFT attainment STRICTLY beats FIFO's, tokens are
bit-identical across all arms, and the chaos drill loses zero
requests. Records ``llama_decode_slo_attainment`` and
``llama_decode_mixed_tok_s``.

Arrival modes (both main and decode): ``--arrival closed`` (default —
every client re-submits as soon as its request finishes) or
``--arrival poisson --rate R`` — open-loop Poisson arrivals at R req/s,
the first slice of the trace-driven load story (ROADMAP item 5): the
generator does NOT slow down when the server does, so overload shows
up as shed/timeout counts (reported per run) instead of silently
stretched client think time.
"""
import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np  # noqa: E402

import paddle_tpu as fluid  # noqa: E402
from paddle_tpu.models import zoo  # noqa: E402
from paddle_tpu import serving  # noqa: E402


def synth_feed(program, feed_names, batch, rng):
    """Random single-request feed shaped from the program's data vars
    (-1 dims become ``batch``; int vars get small non-negative ids)."""
    gb = program.global_block()
    feed = {}
    for name in feed_names:
        var = gb.var(name)
        shape = [batch if (d is None or d < 0) else d for d in var.shape]
        shape[0] = batch
        dtype = str(var.dtype)
        if "int" in dtype:
            feed[name] = rng.randint(0, 2, size=shape).astype(dtype)
        else:
            feed[name] = rng.randn(*shape).astype(dtype)
    return feed


# loss-op input slot that carries the model's per-row prediction head
_PRED_SLOTS = {"cross_entropy": "X", "softmax_with_cross_entropy":
               "Logits", "square_error_cost": "X"}


def row_fetch(program, fallback):
    """The per-row output to serve: the first loss op's prediction
    input ([rows, ...] — row independent, so batched vs single
    comparisons are exact). Falls back to the zoo fetch list when no
    known loss op exists — correctness is then NOT comparable (those
    fetches are batch-mean scalars) and the sweep is skipped."""
    for op in program.global_block().ops:
        slot = _PRED_SLOTS.get(op.type)
        if slot is not None:
            return [op.input(slot)[0]], True
    return fallback, False


def _setup(args):
    """Shared bench scaffolding: zoo model, inference program, fetch,
    initialized private scope, and one single-row feed per request."""
    # racecheck: ok(global-mutation) — bench CLI entrypoint: pins the
    # backend before any serving thread exists
    fluid.force_cpu()
    zp = zoo.build_zoo_program(args.model)
    infer = zp.main.clone(for_test=True)
    fetch, per_row = row_fetch(infer, zp.fetch_list)
    scope = fluid.Scope()
    startup_exe = fluid.Executor(fluid.CPUPlace())
    # racecheck: ok(global-mutation) — driver-thread setup before any
    # serving engine thread starts; the scope is bench-private
    with fluid.scope_guard(scope):
        startup_exe.run(zp.startup)
    rng = np.random.RandomState(0)
    feeds = [synth_feed(infer, zp.feed_names, 1, rng)
             for _ in range(args.requests)]
    return zp, infer, fetch, per_row, scope, feeds


def _drive_closed(eng, feeds, concurrency, timeout=60.0, repeats=3):
    """One closed-loop drive of ``feeds`` (cycled ``repeats`` times so
    the timed window dwarfs scheduler jitter) through ``eng``; returns
    requests/s."""
    wave = list(feeds) * repeats
    with ThreadPoolExecutor(concurrency) as pool:
        t0 = time.perf_counter()
        list(pool.map(lambda f: eng.infer(f, timeout=timeout), wave))
        dt = time.perf_counter() - t0
    return len(wave) / dt if dt > 0 else 0.0


def _opt_compare_classifier(args, eng_on, infer, zp, fetch, scope,
                            feeds):
    """Opt-on vs opt-off serving throughput (the measured-win record
    for the graph-rewrite pipeline). ``eng_on`` is the already-warm
    default engine; an identical engine with ``optimize=False`` serves
    the same program unrewritten. Both sides get two alternating
    closed-loop rounds and keep their best, so a CI scheduling stall
    on one round can't flip the comparison."""
    from paddle_tpu.analysis.optimize import DEFAULT_PASSES
    eng_off = serving.ServingEngine(
        infer, zp.feed_names, fetch, scope=scope,
        place=fluid.CPUPlace(), optimize=False,
        buckets=serving.BucketSpec(
            batch_sizes=_bucket_sizes(args.max_batch)),
        config=serving.ServingConfig(
            max_wait_ms=args.max_wait_ms,
            max_queue=max(2 * args.requests, 64)))
    try:
        eng_off.warmup()
        on_samples, off_samples = [], []
        for _ in range(5):       # alternating so drift hits both
            off_samples.append(_drive_closed(
                eng_off, feeds, args.concurrency))
            on_samples.append(_drive_closed(
                eng_on, feeds, args.concurrency))
        on_rps = float(np.median(on_samples))
        off_rps = float(np.median(off_samples))
        eng_off.assert_no_recompiles()
    finally:
        eng_off.close()
    opt_stats = (eng_on.stats().get("optimize") or {})
    return {
        "metric": f"{args.model}_serving_optimize_speedup",
        "value": round(on_rps / off_rps, 3) if off_rps else None,
        "unit": "x",
        "opt_on_rps": round(on_rps, 1),
        "opt_off_rps": round(off_rps, 1),
        "optimize_passes": ",".join(DEFAULT_PASSES),
        "rewrites": {k: opt_stats.get(k) for k in
                     ("folded", "fused", "merged", "removed")},
        "backend": "cpu",
    }


def _bucket_sizes(max_batch):
    sizes = []
    b = 1
    while b < max_batch:
        sizes.append(b)
        b *= 2
    sizes.append(max_batch)
    return tuple(sizes)


def poisson_arrivals(n, rate, rng):
    """Absolute arrival offsets (seconds) for ``n`` open-loop requests
    at ``rate`` req/s — exponential inter-arrival gaps, the memoryless
    arrival process real traffic is usually modeled by."""
    if rate <= 0:
        raise ValueError(f"--rate must be > 0, got {rate}")
    return np.cumsum(rng.exponential(1.0 / rate, size=n))


def synth_trace(n, rate, rng, burst_factor=4.0, burst_len=16,
                cycle=64, tail_sigma=0.8):
    """Synthetic bursty, heavy-tailed arrival trace (ROADMAP item 5):
    ``burst_len`` of every ``cycle`` requests arrive at
    ``burst_factor`` x the base rate (the diurnal-spike shape), and
    every inter-arrival gap is jittered by a lognormal factor
    (sigma ``tail_sigma``) — heavy-tailed gaps, so quiet stretches and
    pile-ups both happen, unlike pure Poisson. Returns (offsets,
    burst_mask); mean arrival rate stays ≈ ``rate`` (the lognormal's
    mean is divided back out)."""
    if rate <= 0:
        raise ValueError(f"trace rate must be > 0, got {rate}")
    gaps = np.empty(n)
    burst = np.zeros(n, dtype=bool)
    correction = np.exp(tail_sigma ** 2 / 2.0)
    for i in range(n):
        in_burst = (i % cycle) < burst_len
        burst[i] = in_burst
        r = rate * (burst_factor if in_burst else 1.0)
        gaps[i] = rng.exponential(1.0 / r) \
            * rng.lognormal(0.0, tail_sigma) / correction
    return np.cumsum(gaps), burst


def load_rich_trace(path):
    """A recorded trace (docs/SERVING.md "Trace-file schema"): JSON —
    either a bare list of absolute arrival offsets (seconds), or a
    dict with ``offsets`` plus optional per-request columns:

    - ``class``:  priority tier per request ("interactive" /
      "standard" / "batch")
    - ``bucket``: prompt-length bucket per request (int)
    - ``phase``:  segment label per request ("diurnal" / "flash" ...);
      ``"flash"`` rows double as the burst mask
    - ``burst``:  explicit bool burst mask (overrides ``phase``)

    Returns a dict with ``offsets`` (float64 array), ``burst`` (bool
    array) and — None when the file doesn't carry them — ``classes``,
    ``buckets``, ``phases``. Every present column must match
    ``offsets`` in length."""
    with open(path) as f:
        data = json.load(f)
    if not isinstance(data, dict):
        data = {"offsets": data}
    offsets = np.asarray(data["offsets"], dtype=np.float64)
    n = len(offsets)
    phases = data.get("phase")
    if "burst" in data:
        burst = np.asarray(data["burst"], dtype=bool)
    elif phases is not None:
        burst = np.asarray([p == "flash" for p in phases], dtype=bool)
    else:
        burst = np.zeros(n, dtype=bool)
    classes = data.get("class")
    buckets = data.get("bucket")
    buckets = None if buckets is None else [int(b) for b in buckets]
    for col_name, col in (("class", classes), ("bucket", buckets),
                          ("phase", phases), ("burst", burst)):
        if col is not None and len(col) != n:
            raise ValueError(
                f"trace column {col_name!r} has {len(col)} entries "
                f"for {n} offsets — every per-request column must "
                "align with 'offsets'")
    return {"offsets": offsets, "burst": burst, "classes": classes,
            "buckets": buckets, "phases": phases}


def load_trace(path):
    """Back-compat view of :func:`load_rich_trace`: (offsets,
    burst_mask) — what the plain ``--arrival trace`` ladder needs."""
    rich = load_rich_trace(path)
    return rich["offsets"], rich["burst"]


def gen_overload_trace(n, rate, rng, buckets=(8, 16), flash_factor=4.0,
                       diurnal_cycles=2.0, flash_start=0.55,
                       flash_len=0.15, mix=(0.2, 0.45, 0.35)):
    """Deterministic overload trace (the --overload referee's input):
    ``n`` arrivals whose instantaneous rate follows ``diurnal_cycles``
    sinusoidal day/night cycles around ``rate`` (0.4x troughs, 1.0x
    peaks), with one contiguous FLASH CROWD — the ``flash_len``
    fraction of the trace starting at the ``flash_start`` fraction
    arrives at ``flash_factor`` x the diurnal rate. Request classes
    are drawn from ``mix`` = (interactive, standard, batch) fractions,
    and the prompt-bucket skew DRIFTS long across the trace (20% long
    at the start, 80% at the end) so bucketed prefill sees a changing
    shape mix, not a stationary one. Same shape as
    :func:`load_rich_trace`'s return."""
    if rate <= 0:
        raise ValueError(f"trace rate must be > 0, got {rate}")
    names = ("interactive", "standard", "batch")
    cum = np.cumsum(np.asarray(mix, dtype=np.float64))
    if abs(cum[-1] - 1.0) > 1e-9:
        raise ValueError(f"class mix must sum to 1, got {mix}")
    gaps = np.empty(n)
    bucket_col = []
    classes = []
    phases = []
    for i in range(n):
        frac = i / max(1, n - 1)
        m = 0.7 + 0.3 * np.sin(2.0 * np.pi * diurnal_cycles * frac)
        in_flash = flash_start <= frac < flash_start + flash_len
        if in_flash:
            m *= flash_factor
        gaps[i] = rng.exponential(1.0 / (rate * m))
        phases.append("flash" if in_flash else "diurnal")
        classes.append(names[int(np.searchsorted(cum, rng.uniform(),
                                                 side="left"))])
        p_long = 0.2 + 0.6 * frac       # bucket-skew drift
        bucket_col.append(int(buckets[-1] if rng.uniform() < p_long
                              else buckets[0]))
    return {"offsets": np.cumsum(gaps),
            "burst": np.asarray([p == "flash" for p in phases]),
            "classes": classes, "buckets": bucket_col,
            "phases": phases}


def open_loop_drive(submit, items, offsets, result_timeout=120.0):
    """Submit ``items`` at the given absolute arrival offsets
    regardless of server state (open loop), then collect every handle.
    Returns (outcomes dict, results list aligned with items — None
    where the request was shed or failed, wall seconds, per-item
    client-side latency list — None where unserved). ``submit``
    returns a handle with ``.done()``/``.result(timeout)``; typed
    serving errors count as shed / timeout / error, never raise.

    Latencies are captured by a collector thread sampling ``done()``,
    so a request that finished long before collection is timestamped
    when it SETTLED, not when the tail of the run got around to it —
    p99-under-burst depends on that."""
    import threading
    from paddle_tpu.serving import (QueueFullError, RequestTimeoutError,
                                    ServingError)
    counts = {"ok": 0, "shed": 0, "timeout": 0, "error": 0}
    handles = [None] * len(items)
    submitted_at = [None] * len(items)
    settled_at = {}
    stop = threading.Event()

    def collect():
        while not stop.is_set():
            for i, h in enumerate(handles):
                if h is not None and i not in settled_at and h.done():
                    settled_at[i] = time.perf_counter()
            stop.wait(0.001)

    collector = threading.Thread(target=collect, daemon=True)
    collector.start()
    t0 = time.perf_counter()
    for i, (item, off) in enumerate(zip(items, offsets)):
        delay = t0 + off - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        try:
            submitted_at[i] = time.perf_counter()
            handles[i] = submit(item)
        except QueueFullError:
            counts["shed"] += 1
        except ServingError:
            counts["error"] += 1
    results = [None] * len(items)
    for i, h in enumerate(handles):
        if h is None:
            continue
        try:
            results[i] = h.result(result_timeout)
            counts["ok"] += 1
        except RequestTimeoutError:
            counts["timeout"] += 1
        except Exception:               # noqa: BLE001 — tallied
            counts["error"] += 1
        settled_at.setdefault(i, time.perf_counter())
    wall = time.perf_counter() - t0
    stop.set()
    collector.join(1.0)
    latencies = [None] * len(items)
    for i in range(len(items)):
        if results[i] is not None and submitted_at[i] is not None \
                and i in settled_at:
            latencies[i] = settled_at[i] - submitted_at[i]
    return counts, results, wall, latencies


def trace_ladder(submit, items, args, rng):
    """Max-sustainable-QPS search: replay the bursty trace at a ladder
    of base rates (``--rate`` x growth^k); the highest rung with ZERO
    shed/timeout/error is the sustained capacity, and its p99 over
    burst-phase requests is the p99-under-burst number. Stops at the
    first dirty rung (open loop: past the knee, everything sheds)."""
    report = {"rungs": [], "max_sustained_qps": None,
              "p99_burst_ms": None}
    rate = args.rate
    for _ in range(args.ladder_rungs):
        if args.trace_file:
            base, burst = load_trace(args.trace_file)
            # replaying a recorded trace faster = scaling time down
            offsets = base * (args.rate / rate)
        else:
            offsets, burst = synth_trace(
                len(items), rate, rng,
                burst_factor=args.burst_factor)
        counts, _results, wall, lats = open_loop_drive(
            submit, items, offsets,
            result_timeout=args.request_timeout + 30.0)
        achieved = counts["ok"] / wall if wall > 0 else 0.0
        burst_lats = [l for l, b in zip(lats, burst)
                      if l is not None and b]
        p99b = (round(float(np.percentile(burst_lats, 99.0)) * 1e3, 2)
                if burst_lats else None)
        clean = (counts["shed"] == 0 and counts["timeout"] == 0
                 and counts["error"] == 0)
        report["rungs"].append({
            "base_rate": round(rate, 1),
            "achieved_qps": round(achieved, 1),
            "counts": counts, "p99_burst_ms": p99b,
            "clean": clean})
        if not clean:
            break
        report["max_sustained_qps"] = round(achieved, 1)
        report["p99_burst_ms"] = p99b
        rate *= args.ladder_growth
    return report


def _decode_model(args):
    """Tiny llama config + initialized serving scope + prompts (+ the
    fused-generator baseline programs, one per prompt bucket; the
    FIRST one's startup initializes the shared serving scope)."""
    from paddle_tpu.models.llama import (LlamaConfig,
                                         build_llama_generator)
    # racecheck: ok(global-mutation) — bench CLI entrypoint: pins the
    # backend before any serving thread exists
    fluid.force_cpu()
    cfg = LlamaConfig(vocab_size=128, dim=32, n_layers=2, n_heads=4,
                      n_kv_heads=2, ffn_hidden=64, dtype="float32")
    buckets = (8, 16)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    gen = {}
    for j, L in enumerate(buckets):
        prog, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(prog, startup):
            ptok = fluid.layers.data(name="ptok", shape=[1, L],
                                     dtype="int64",
                                     append_batch_size=False)
            out = build_llama_generator(cfg, ptok,
                                        max_new_tokens=args.max_new)
        gen[L] = (prog, out)
        if j == 0:
            # racecheck: ok(global-mutation) — driver-thread setup,
            # no serving threads yet; bench-private scope
            with fluid.scope_guard(scope):
                exe.run(startup)
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size,
                           (int(rng.choice(buckets)),)).astype(np.int64)
               for _ in range(args.requests)]
    return cfg, buckets, scope, exe, gen, prompts


def _decode_config(args, buckets):
    from paddle_tpu import serving
    max_queue = (max(2 * args.requests, 64)
                 if getattr(args, "max_queue", None) is None
                 else args.max_queue)
    return serving.DecodeConfig(
        max_batch=args.max_batch, prompt_buckets=buckets,
        max_new_tokens=args.max_new, page_size=8,
        decode_block=args.decode_block,
        prefill_batch=args.prefill_batch,
        max_queue=max_queue,
        default_timeout_s=120.0)


def decode_main(args):
    """--decode: continuous batching vs sequential per-request
    generation on a tiny-config llama."""
    from paddle_tpu.models.llama import copy_weights_as_draft
    from paddle_tpu import serving

    cfg, buckets, scope, exe, gen, prompts = _decode_model(args)
    max_new = args.max_new

    baseline_tok_s = None
    baseline_out = None
    if not args.skip_baseline:
        # racecheck: ok(global-mutation) — single-threaded baseline
        # measurement in the driver; bench-private scope
        with fluid.scope_guard(scope):
            for L in buckets:           # compile outside the clock
                # racecheck: ok(run-without-scope) — inside the
                # bench-private scope_guard, single-threaded
                exe.run(gen[L][0],
                        feed={"ptok": np.zeros((1, L), np.int64)},
                        fetch_list=[gen[L][1]], mode="test")
            t0 = time.perf_counter()
            baseline_out = []
            for p in prompts:
                # racecheck: ok(run-without-scope) — ditto: private
                # scope_guard, single-threaded baseline
                full = np.asarray(exe.run(
                    gen[len(p)][0], feed={"ptok": p[None]},
                    fetch_list=[gen[len(p)][1]], mode="test")[0])
                baseline_out.append(full[0, len(p):])
            base_s = time.perf_counter() - t0
        baseline_tok_s = args.requests * max_new / base_s

    draft_cfg = None
    if args.spec:
        # racecheck: ok(global-mutation) — driver-thread setup before
        # the decode engine starts; bench-private scope
        with fluid.scope_guard(scope):
            copy_weights_as_draft(scope)
        draft_cfg = cfg
    eng = serving.DecodeEngine(
        cfg, scope=scope, place=fluid.CPUPlace(), draft_cfg=draft_cfg,
        config=_decode_config(args, buckets))
    failures = []
    arrival_counts = None
    try:
        warm = eng.warmup()
        rng_a = np.random.RandomState(7)
        if args.arrival == "poisson":
            arrival_counts, served, eng_s, _lats = open_loop_drive(
                lambda p: eng.submit(p, timeout=args.request_timeout),
                prompts,
                poisson_arrivals(len(prompts), args.rate, rng_a),
                result_timeout=120.0)
            n_tokens = sum(len(r) for r in served if r is not None)
        else:
            t0 = time.perf_counter()
            reqs = [eng.submit(p, timeout=120.0) for p in prompts]
            served = [r.result(120.0) for r in reqs]
            eng_s = time.perf_counter() - t0
            n_tokens = sum(len(r) for r in served)
        engine_tok_s = n_tokens / eng_s if eng_s > 0 else 0.0
        try:
            eng.assert_no_recompiles()
            recompiled = False
        except AssertionError as exc:
            recompiled = True
            failures.append(str(exc))
        stats = eng.stats()
    finally:
        eng.close()

    # opt-on vs opt-off decode throughput (--opt-compare, closed loop
    # only): a second engine serves the same scope with the rewrite
    # pipeline disabled; both get a fresh closed-loop drive and the
    # better of two rounds each, alternating
    opt_record = None
    if getattr(args, "opt_compare", False) and args.arrival == "closed":
        from paddle_tpu.analysis.optimize import DEFAULT_PASSES

        def _tok_s(engine):
            t0 = time.perf_counter()
            rs = [engine.submit(p, timeout=120.0) for p in prompts]
            toks = sum(len(r.result(120.0)) for r in rs)
            dt = time.perf_counter() - t0
            return toks / dt if dt > 0 else 0.0

        on_tok_s, off_tok_s = engine_tok_s, 0.0
        for flag in (False, True, False, True):
            e2 = serving.DecodeEngine(
                cfg, scope=scope, place=fluid.CPUPlace(),
                draft_cfg=draft_cfg, optimize=flag,
                config=_decode_config(args, buckets))
            try:
                e2.warmup()
                v = _tok_s(e2)
            finally:
                e2.close()
            if flag:
                on_tok_s = max(on_tok_s, v)
            else:
                off_tok_s = max(off_tok_s, v)
        opt_record = {
            "metric": "llama_decode_serving_optimize_speedup",
            "value": (round(on_tok_s / off_tok_s, 3)
                      if off_tok_s else None),
            "unit": "x",
            "opt_on_tok_s": round(on_tok_s, 1),
            "opt_off_tok_s": round(off_tok_s, 1),
            "optimize_passes": ",".join(DEFAULT_PASSES),
            "backend": "cpu", "max_batch": args.max_batch,
        }

    mismatches = None
    if baseline_out is not None:
        mismatches = sum(
            1 for ref, got in zip(baseline_out, served)
            if got is not None and not np.array_equal(ref, got))
        if mismatches:
            failures.append(
                f"{mismatches} request(s) diverged from the "
                "sequential fused-generator baseline")
    if engine_tok_s <= 0:
        failures.append("engine produced no tokens")
    speedup = (engine_tok_s / baseline_tok_s
               if baseline_tok_s else None)
    if args.assert_speedup is not None and speedup is not None \
            and speedup < args.assert_speedup:
        failures.append(
            f"decode speedup {speedup:.2f}x below the "
            f"--assert-speedup {args.assert_speedup}x floor")

    report = {
        "mode": "decode",
        "requests": args.requests,
        "max_batch": args.max_batch,
        "max_new": max_new,
        "decode_block": args.decode_block,
        "spec": bool(args.spec),
        "arrival": args.arrival,
        "warmup": warm,
        "baseline_tok_s": (None if baseline_tok_s is None
                           else round(baseline_tok_s, 1)),
        "engine_tok_s": round(engine_tok_s, 1),
        "speedup": None if speedup is None else round(speedup, 2),
        "mismatched_requests": mismatches,
        "recompiled": recompiled,
        "arrival_counts": arrival_counts,
        "bench_record": {
            "metric": "llama_decode_serving_tok_s",
            "value": round(engine_tok_s, 1), "unit": "tok/s",
            "backend": "cpu", "max_batch": args.max_batch,
            "spec": bool(args.spec),
            "see_also_published": {
                "llama8b_int8_serving_tok_s": 4963.7}},
        "bench_record_optimize": opt_record,
        "serving_stats": stats,
        "failures": failures,
    }
    text = json.dumps(report, indent=2)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    if args.json:
        print(text)
    else:
        shed = ("" if arrival_counts is None else
                f", shed {arrival_counts['shed']} / timeout "
                f"{arrival_counts['timeout']}")
        opt_line = ""
        if opt_record is not None:
            opt_line = (f", opt {opt_record['opt_on_tok_s']} vs "
                        f"{opt_record['opt_off_tok_s']} tok/s "
                        f"({opt_record['value']}x)")
        print(f"servebench --decode: baseline "
              f"{report['baseline_tok_s']} tok/s, engine "
              f"{report['engine_tok_s']} tok/s "
              f"({report['speedup']}x), ttft p95 "
              f"{stats['ttft_s']['p95_ms']} ms, tpot p95 "
              f"{stats['tpot_s']['p95_ms']} ms, "
              f"{mismatches} mismatches, "
              f"{warm['compiles']} warmup compiles, "
              f"{'RECOMPILED' if recompiled else '0 recompiles'}"
              f"{shed}{opt_line}")
    if failures:
        for f in failures:
            print(f"servebench --decode: FAILED — {f}",
                  file=sys.stderr)
        return 1
    return 0


# --slo trace shape: longs flood the queue FIRST, then shorts with a
# tight TTFT target arrive behind them. All requests are enqueued
# before the engine starts, so the measured difference is pure
# scheduling order — FIFO must burn through every long before the
# first short prefills (hundreds of decode steps of queue wait),
# while EDF admits the shorts immediately (a couple of dispatches).
# The interactive TTFT target is CALIBRATED, not absolute: an unscored
# FIFO run measures the shorts' queue-wait TTFT on this machine, and
# the scored target is a quarter of it — so FIFO violates with 4x
# margin and the SLO scheduler (measured ~15x lower TTFT) meets with
# comparable margin, on any CPU speed.
_SLO_LONGS, _SLO_SHORTS = 16, 6
_SLO_LONG_NEW, _SLO_SHORT_NEW = 96, 8
_SLO_TTFT_FLOOR_S = 0.02      # never score below dispatch noise


def _slo_classes(ttft_interactive_s):
    interactive = serving.SLOClass(
        ttft_target_s=ttft_interactive_s, tpot_target_s=1.0,
        name="interactive")
    batch = serving.SLOClass(ttft_target_s=30.0, tpot_target_s=5.0,
                             name="batch")
    return interactive, batch


def _slo_trace(cfg):
    rng = np.random.RandomState(11)
    longs = [rng.randint(0, cfg.vocab_size, (16,)).astype(np.int64)
             for _ in range(_SLO_LONGS)]
    shorts = [rng.randint(0, cfg.vocab_size, (8,)).astype(np.int64)
              for _ in range(_SLO_SHORTS)]
    return longs, shorts


def _slo_decode_config(scheduler):
    # 2 slots + small decode blocks keep admission contended: queue
    # order decides everything
    return serving.DecodeConfig(
        max_batch=2, prompt_buckets=(8, 16),
        max_new_tokens=_SLO_LONG_NEW, page_size=8,
        decode_block=8, prefill_batch=2, max_queue=256,
        default_timeout_s=240.0, scheduler=scheduler)


def _ttft_attainment(stats):
    met = stats["slo_ttft_met"]
    total = met + stats["slo_ttft_violated"]
    return round(met / total, 4) if total else None


def _slo_arm(cfg, scope, scheduler, longs, shorts, failures, label,
             classes):
    """One single-engine run of the mixed trace under ``scheduler``.
    Everything is enqueued before start() so admission order is the
    scheduler's choice alone."""
    interactive, batch = classes
    eng = serving.DecodeEngine(
        cfg, scope=scope, place=fluid.CPUPlace(),
        config=_slo_decode_config(scheduler), auto_start=False)
    try:
        eng.warmup()
        handles = [eng.submit(p, max_new=_SLO_LONG_NEW, timeout=240.0,
                              slo=batch) for p in longs]
        handles += [eng.submit(p, max_new=_SLO_SHORT_NEW, timeout=240.0,
                               slo=interactive) for p in shorts]
        t0 = time.perf_counter()
        eng.start()
        outs = [np.asarray(h.result(240.0)) for h in handles]
        wall = time.perf_counter() - t0
        try:
            eng.assert_no_recompiles()
        except AssertionError as exc:
            failures.append(f"{label}: {exc}")
        stats = eng.stats()
    finally:
        eng.close()
    n_tok = sum(len(o) for o in outs)
    return {"outs": outs,
            "tok_s": round(n_tok / wall, 1) if wall > 0 else 0.0,
            "ttft_attainment": _ttft_attainment(stats),
            "stats": stats}


def _slo_disagg_arm(cfg, scope, longs, shorts, ref_outs, failures,
                    classes):
    """The same mixed trace over a disaggregated 2-prefill/2-decode
    pool via Router.generate, then the serving_handoff_drop chaos
    drill on the SAME pool: the prefill replica dies holding the
    finished KV blob, and the router must re-prefill on the survivor
    with zero lost requests."""
    from paddle_tpu.cluster import ReplicaPool, Router
    from paddle_tpu.resilience import faultinject

    interactive, batch = classes
    pool = ReplicaPool(
        lambda: serving.DecodeEngine(
            cfg, scope=scope, place=fluid.CPUPlace(),
            config=_slo_decode_config("slo")),
        replicas=4, warmup=True)
    for i, rep in enumerate(pool.replicas()):
        rep.role = "prefill" if i < 2 else "decode"
    router = Router(pool)
    work = ([(p, _SLO_LONG_NEW, batch) for p in longs]
            + [(p, _SLO_SHORT_NEW, interactive) for p in shorts])

    def one(item):
        p, max_new, slo = item
        return np.asarray(router.generate(p, max_new=max_new,
                                          timeout=240.0, slo=slo))

    try:
        with ThreadPoolExecutor(max_workers=8) as tp:
            t0 = time.perf_counter()
            outs = list(tp.map(one, work))
            wall = time.perf_counter() - t0
        mism = sum(1 for a, b in zip(ref_outs, outs)
                   if not np.array_equal(a, b))
        if mism:
            failures.append(f"disaggregated: {mism} request(s) "
                            "diverged from the single-engine tokens "
                            "(must be bit-exact)")
        snap = pool.stats()
        if not snap["handoffs_total"]:
            failures.append("disaggregated: no handoffs happened — "
                            "the role split did not engage")

        # chaos: drop the first two handoffs mid-flight
        chaos_work = work[:2] + work[-2:]
        chaos_ref = ref_outs[:2] + ref_outs[-2:]
        faultinject.arm("serving_handoff_drop", at=0, times=2)
        try:
            with ThreadPoolExecutor(max_workers=4) as tp:
                chaos_outs = list(tp.map(one, chaos_work))
        finally:
            faultinject.disarm("serving_handoff_drop")
        lost = sum(1 for a, b in zip(chaos_ref, chaos_outs)
                   if not np.array_equal(a, b))
        if lost:
            failures.append(f"handoff chaos: {lost} request(s) lost "
                            "or diverged after the drop")
        snap = pool.stats()
        if not snap["handoff_redrives_total"]:
            failures.append("handoff chaos: the armed drop never "
                            "fired (redrive counter is zero)")
        n_tok = sum(len(o) for o in outs)
        cluster = snap["cluster"] or {}
        return {"tok_s": round(n_tok / wall, 1) if wall > 0 else 0.0,
                "ttft_attainment": (_ttft_attainment(cluster)
                                    if "slo_ttft_met" in cluster
                                    else None),
                "mismatched_requests": mism,
                "chaos_lost": lost,
                "handoffs_total": snap["handoffs_total"],
                "handoff_redrives_total":
                    snap["handoff_redrives_total"]}
    finally:
        router.close()
        pool.close()


def slo_main(args):
    """--decode --slo: SLO-attainment benchmark on a mixed short/long
    interference trace — FIFO vs EDF (SLO scheduler) vs disaggregated
    prefill/decode, plus the serving_handoff_drop chaos drill. Gated:
    the SLO scheduler's TTFT attainment must be STRICTLY better than
    FIFO's on the same trace, tokens must stay bit-identical across
    all three arms, and the chaos drill must lose zero requests."""
    cfg, buckets, scope, exe, gen, prompts = _decode_model(args)
    del buckets, exe, gen, prompts      # scheduling bench builds its own
    longs, shorts = _slo_trace(cfg)
    failures = []

    # calibration: the same trace, FIFO, targets too huge to violate —
    # its interactive-class TTFT window measures what FIFO queue wait
    # costs the shorts on THIS machine
    cal = _slo_arm(cfg, scope, "fifo", longs, shorts, failures,
                   "calibration arm", _slo_classes(1e6))
    cal_win = cal["stats"].get("interactive.ttft_s") or {}
    cal_p50_s = (cal_win.get("p50_ms") or 0.0) / 1e3
    ttft_target_s = max(_SLO_TTFT_FLOOR_S, cal_p50_s / 4.0)
    classes = _slo_classes(ttft_target_s)

    fifo = _slo_arm(cfg, scope, "fifo", longs, shorts, failures,
                    "fifo arm", classes)
    # --slo-force-fifo runs the "slo" arm on the FIFO scheduler too —
    # the attainment gate below must then FAIL (selfcheck stage 13's
    # toothless-gate check)
    slo_sched = "fifo" if args.slo_force_fifo else "slo"
    slo = _slo_arm(cfg, scope, slo_sched, longs, shorts, failures,
                   "slo arm", classes)

    mism = sum(1 for a, b in zip(fifo["outs"], slo["outs"])
               if not np.array_equal(a, b))
    if mism:
        failures.append(f"{mism} request(s) decoded different tokens "
                        "under FIFO vs SLO scheduling (admission "
                        "order must never change greedy outputs)")
    fifo_att, slo_att = fifo["ttft_attainment"], slo["ttft_attainment"]
    if fifo_att is None or slo_att is None:
        failures.append("TTFT attainment was not scored (SLO counters "
                        "empty) — every request carries an SLO class")
    elif slo_att <= fifo_att:
        failures.append(
            f"SLO-scheduler TTFT attainment {slo_att} is not strictly "
            f"better than FIFO's {fifo_att} on the interference trace")

    mism_cal = sum(1 for a, b in zip(cal["outs"], fifo["outs"])
                   if not np.array_equal(a, b))
    if mism_cal:
        failures.append(f"{mism_cal} request(s) decoded different "
                        "tokens across runs on the SAME scheduler")

    disagg = (None if args.skip_disagg else
              _slo_disagg_arm(cfg, scope, longs, shorts, fifo["outs"],
                              failures, classes))

    fifo_stats, slo_stats = fifo.pop("stats"), slo.pop("stats")
    fifo.pop("outs"), slo.pop("outs")
    report = {
        "mode": "decode-slo",
        "trace": {"longs": _SLO_LONGS, "long_new": _SLO_LONG_NEW,
                  "shorts": _SLO_SHORTS, "short_new": _SLO_SHORT_NEW,
                  "calibrated_fifo_ttft_p50_s": round(cal_p50_s, 4),
                  "interactive_ttft_s": round(ttft_target_s, 4)},
        "fifo": fifo, "slo": slo, "disaggregated": disagg,
        "slo_counters": {
            k: slo_stats[k]
            for k in ("slo_ttft_met", "slo_ttft_violated",
                      "slo_tpot_met", "slo_tpot_violated",
                      "chunk_prefill_total")},
        "interactive_ttft_ms": {
            "fifo": fifo_stats.get("interactive.ttft_s"),
            "slo": slo_stats.get("interactive.ttft_s")},
        "bench_records": [
            {"metric": "llama_decode_slo_attainment", "value": slo_att,
             "unit": "frac", "fifo_attainment": fifo_att,
             "disagg_attainment":
                 None if disagg is None else disagg["ttft_attainment"],
             "scheduler": slo_sched, "backend": "cpu"},
            {"metric": "llama_decode_mixed_tok_s",
             "value": slo["tok_s"], "unit": "tok/s",
             "fifo_tok_s": fifo["tok_s"],
             "disagg_tok_s":
                 None if disagg is None else disagg["tok_s"],
             "backend": "cpu"}],
        "failures": failures,
    }
    text = json.dumps(report, indent=2)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    if args.json:
        print(text)
    else:
        d = ("skipped" if disagg is None else
             f"{disagg['ttft_attainment']} att / {disagg['tok_s']} "
             f"tok/s, {disagg['handoffs_total']} handoffs, "
             f"{disagg['handoff_redrives_total']} chaos redrives")
        print(f"servebench --decode --slo: ttft attainment fifo "
              f"{fifo_att} vs slo {slo_att}, mixed {slo['tok_s']} "
              f"tok/s (fifo {fifo['tok_s']}), disagg: {d}")
    if failures:
        for f in failures:
            print(f"servebench --decode --slo: FAILED — {f}",
                  file=sys.stderr)
        return 1
    return 0


def chaos_main(args):
    """--chaos: fault-injection drill over the serving engine."""
    from paddle_tpu.resilience import faultinject
    from paddle_tpu.resilience.retry import (RetryPolicy,
                                             TransientDeviceError)
    from paddle_tpu.serving import ServingError

    zp, infer, fetch, _per_row, scope, feeds = _setup(args)
    eng = serving.ServingEngine(
        infer, zp.feed_names, fetch, scope=scope,
        place=fluid.CPUPlace(),
        buckets=serving.BucketSpec(
            batch_sizes=_bucket_sizes(args.max_batch)),
        config=serving.ServingConfig(
            max_wait_ms=args.max_wait_ms,
            max_queue=max(2 * args.requests, 64),
            breaker_threshold=3, breaker_cooldown_s=0.3,
            # no dispatch retries: every injected fault is a terminal
            # batch failure, so the breaker cycle is deterministic
            retry_policy=RetryPolicy(max_attempts=1)))

    def drive(wave, timeout=30.0):
        """Run one request wave; every submission must TERMINATE.
        Returns (counts-by-outcome, wall seconds). 'lost' counts
        untyped failures — the contract violation."""
        counts = {"ok": 0, "lost": 0}

        def one(f):
            try:
                eng.infer(f, timeout=timeout)
                return "ok"
            except (ServingError, TransientDeviceError) as exc:
                return type(exc).__name__
            except Exception as exc:            # noqa: BLE001 — tallied
                return f"lost:{type(exc).__name__}"
        with ThreadPoolExecutor(args.concurrency) as pool:
            t0 = time.perf_counter()
            for outcome in pool.map(one, wave):
                if outcome.startswith("lost:"):
                    counts["lost"] += 1
                counts[outcome] = counts.get(outcome, 0) + 1
            return counts, time.perf_counter() - t0

    failures = []
    try:
        warm = eng.warmup()

        # phase 1 — steady state: all success, zero recompiles
        steady, steady_s = drive(feeds)
        if steady["ok"] != len(feeds):
            failures.append(f"steady-state failures: {steady}")

        # phase 2 — fault window: the breaker must open; nothing lost
        faultinject.arm("serving_device_error", at=0, times=6)
        chaos, _ = drive(feeds)
        faultinject.disarm("serving_device_error")   # fault clears
        mid = eng.stats()
        if mid["breaker_open_total"] < 1:
            failures.append("breaker never opened under injected faults")

        # phase 3 — recovery: cooldown, half-open probe closes, full
        # throughput returns, still zero recompiles
        time.sleep(0.35)
        recovery, rec_s = drive(feeds)
        post = eng.stats()
        if recovery["ok"] != len(feeds):
            failures.append(f"post-recovery failures: {recovery}")
        if post["breaker"]["state"] != "closed":
            failures.append(f"breaker stuck {post['breaker']['state']}")
        try:
            eng.assert_no_recompiles()
        except AssertionError as exc:
            failures.append(str(exc))

        # phase 4 — graceful drain: every queued request completes
        drain_reqs = [eng.submit(f, timeout=30.0) for f in feeds[:8]]
        eng.close(drain=True)
        drained = 0
        for req in drain_reqs:
            try:
                req.result(timeout=1.0)
                drained += 1
            except ServingError:
                pass
        if drained != len(drain_reqs):
            failures.append(
                f"drain completed {drained}/{len(drain_reqs)} requests")
    finally:
        faultinject.disarm()
        eng.close()

    lost = steady["lost"] + chaos["lost"] + recovery["lost"]
    if lost:
        failures.append(f"{lost} request(s) lost (untyped failure)")
    report = {
        "mode": "chaos",
        "model": args.model,
        "requests_per_wave": len(feeds),
        "warmup": warm,
        "steady": steady,
        "chaos": chaos,
        "recovery": recovery,
        "recovery_rps": round(len(feeds) / rec_s, 1),
        "steady_rps": round(len(feeds) / steady_s, 1),
        "breaker_open_total": post["breaker_open_total"],
        "breaker_shed_total": post["breaker_shed_total"],
        "breaker_probe_total": post["breaker_probe_total"],
        "drained": drained,
        "lost": lost,
        "failures": failures,
    }
    text = json.dumps(report, indent=2)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    if args.json:
        print(text)
    else:
        print(f"servebench --chaos {args.model}: lost {lost}, breaker "
              f"opened {post['breaker_open_total']}x / shed "
              f"{post['breaker_shed_total']}, recovery "
              f"{report['recovery_rps']} req/s, drained {drained}/8, "
              f"{len(failures)} failure(s)")
    if failures:
        for f in failures:
            print(f"servebench --chaos: FAILED — {f}", file=sys.stderr)
        return 1
    return 0


def _classifier_factory(args, infer, zp, fetch, scope):
    """Engine factory for the pool: identical engines over one
    read-only parameter scope, each with its own worker + compile
    cache. ``--max-queue`` pins the per-engine admission bound (trace
    mode needs a production-like fixed bound — a queue scaled to the
    request count can never exhibit the shed knee)."""
    max_queue = (max(2 * args.requests, 64) if args.max_queue is None
                 else args.max_queue)

    def factory():
        return serving.ServingEngine(
            infer, zp.feed_names, fetch, scope=scope,
            place=fluid.CPUPlace(),
            buckets=serving.BucketSpec(
                batch_sizes=_bucket_sizes(args.max_batch)),
            config=serving.ServingConfig(
                max_wait_ms=args.max_wait_ms,
                max_queue=max_queue))
    return factory


def _closed_loop(infer_fn, items, concurrency, timeout=60.0):
    """Closed-loop drive: ``concurrency`` clients, each re-submitting
    as soon as its request finishes. Returns (results, wall_s)."""
    with ThreadPoolExecutor(concurrency) as pool:
        t0 = time.perf_counter()
        out = list(pool.map(lambda it: infer_fn(it, timeout=timeout),
                            items))
        return out, time.perf_counter() - t0


def _burst_goodput(submit, items, offsets, timeout):
    """One overload-trace drive; returns (ok, shed+timeout+error,
    goodput req/s)."""
    counts, _res, wall, _lats = open_loop_drive(
        submit, items, offsets, result_timeout=timeout + 30.0)
    refused = counts["shed"] + counts["timeout"] + counts["error"]
    return counts["ok"], refused, (counts["ok"] / wall if wall else 0.0)


def cluster_main(args):
    """--cluster N: replica-pool vs ONE engine on the same load —
    closed-loop throughput AND goodput under a bursty overload trace
    (the pool's queues absorb bursts a single engine must shed) —
    plus (--rolling-restart) a zero-downtime restart under sustained
    mixed traffic. The acceptance drill for the cluster subsystem
    (docs/SERVING.md "Running a replica pool")."""
    import argparse as _argparse
    import threading
    from paddle_tpu import cluster
    from paddle_tpu.serving import ServingError

    zp, infer, fetch, per_row, scope, feeds = _setup(args)
    factory = _classifier_factory(args, infer, zp, fetch, scope)
    failures = []

    # ---- reference: ONE engine, same concurrency, same feeds ---------
    eng = factory()
    try:
        eng.warmup()
        single_out, single_s = _closed_loop(eng.infer, feeds,
                                            args.concurrency)
    finally:
        eng.close()
    single_rps = len(feeds) / single_s

    # ---- burst-overload goodput: same offered load, 1 vs N -----------
    # bursts at 8x the sustained rate overflow one engine's bounded
    # queue; the pool's N queues absorb them — the capacity win that
    # holds on ANY host (a 1-core CI box cannot show a parallel-compute
    # win, so the gate lives here; host_cores is recorded)
    bargs = _argparse.Namespace(**vars(args))
    bargs.max_queue = 32
    bfactory = _classifier_factory(bargs, infer, zp, fetch, scope)
    rng_b = np.random.RandomState(13)
    n_over = max(192, args.requests)
    over_feeds = (feeds * ((n_over + len(feeds) - 1)
                           // len(feeds)))[:n_over]
    offsets, _burst = synth_trace(n_over, max(single_rps, 200.0),
                                  rng_b, burst_factor=8.0,
                                  burst_len=32)
    eng_b = bfactory()
    try:
        eng_b.warmup()
        s_ok, s_refused, s_goodput = _burst_goodput(
            lambda f: eng_b.submit(f, timeout=10.0), over_feeds,
            offsets, 10.0)
    finally:
        eng_b.close()
    router_b = cluster.serve_cluster(bfactory, replicas=args.cluster,
                                     warmup=True)
    try:
        c_ok, c_refused, c_goodput = _burst_goodput(
            lambda f: router_b.submit(f, timeout=10.0), over_feeds,
            offsets, 10.0)
    finally:
        router_b.close()
    if c_ok < s_ok:
        failures.append(
            f"pool served fewer requests than one engine on the same "
            f"overload trace ({c_ok} vs {s_ok})")

    # ---- the pool: N replicas behind the router ----------------------
    router = cluster.serve_cluster(factory, replicas=args.cluster,
                                   warmup=True)
    restart_report = None
    min_ready_seen = None
    restart_drive = None
    try:
        served, cluster_s = _closed_loop(router.infer, feeds,
                                         args.concurrency)
        cluster_rps = len(feeds) / cluster_s
        if per_row:
            mismatches = sum(
                1 for ref, got in zip(single_out, served)
                if not np.allclose(np.asarray(ref[0]),
                                   np.asarray(got[0]),
                                   rtol=1e-5, atol=1e-7))
            if mismatches:
                failures.append(
                    f"{mismatches} request(s) diverged between the "
                    "single engine and the pool")
        else:
            mismatches = None

        if args.rolling_restart:
            # sustained MIXED load (1- and 2-row requests) while every
            # replica is drained + rebuilt, one at a time; the
            # contract: zero losses, never fewer than N-1 READY
            rng = np.random.RandomState(3)
            mixed = [synth_feed(infer, zp.feed_names, rows, rng)
                     for rows in ([1, 2] * 8)]
            outcomes = {"ok": 0, "typed": 0, "lost": 0}
            olock = threading.Lock()
            stop = threading.Event()

            def client(idx):
                k = idx
                while not stop.is_set():
                    f = mixed[k % len(mixed)]
                    k += args.concurrency
                    try:
                        router.infer(f, timeout=30.0)
                        key = "ok"
                    except ServingError:
                        key = "typed"
                    except Exception:       # noqa: BLE001 — tallied
                        key = "lost"
                    with olock:
                        outcomes[key] += 1

            ready_samples = []

            def poll_ready():
                while not stop.is_set():
                    ready_samples.append(
                        router.pool.ready_count())
                    stop.wait(0.01)

            clients = [threading.Thread(target=client, args=(i,),
                                        daemon=True)
                       for i in range(args.concurrency)]
            poller = threading.Thread(target=poll_ready, daemon=True)
            for t in clients:
                t.start()
            poller.start()
            time.sleep(0.2)          # load established before restart
            restart_report = router.pool.rolling_restart()
            time.sleep(0.2)          # load continues after restart
            stop.set()
            for t in clients:
                t.join(30.0)
            poller.join(5.0)
            restart_drive = dict(outcomes)
            min_ready_seen = min(
                [restart_report["min_ready_observed"]]
                + (ready_samples or []))
            if outcomes["lost"]:
                failures.append(
                    f"rolling restart lost {outcomes['lost']} "
                    "request(s) (untyped failure)")
            if outcomes["typed"]:
                failures.append(
                    f"rolling restart failed {outcomes['typed']} "
                    "request(s) with typed errors — drain+failover "
                    "should complete every request")
            if outcomes["ok"] == 0:
                failures.append("no traffic flowed during the "
                                "rolling restart")
            if len(restart_report["restarted"]) != args.cluster:
                failures.append(
                    f"rolling restart covered "
                    f"{len(restart_report['restarted'])}/"
                    f"{args.cluster} replicas")
            if min_ready_seen < args.cluster - 1:
                failures.append(
                    f"pool dropped to {min_ready_seen} READY "
                    f"replicas (floor {args.cluster - 1})")
        stats = router.stats()
    finally:
        router.close()

    speedup = cluster_rps / single_rps if single_rps else None
    if args.assert_speedup is not None and speedup is not None \
            and speedup < args.assert_speedup:
        failures.append(
            f"cluster speedup {speedup:.2f}x below the "
            f"--assert-speedup {args.assert_speedup}x floor")
    import os as _os
    report = {
        "mode": "cluster",
        "model": args.model,
        "replicas": args.cluster,
        "requests": args.requests,
        "concurrency": args.concurrency,
        "host_cores": _os.cpu_count(),
        "single_engine_rps": round(single_rps, 1),
        "cluster_rps": round(cluster_rps, 1),
        "cluster_vs_single_speedup": (None if speedup is None
                                      else round(speedup, 2)),
        "burst_overload": {
            "offered": n_over, "queue_per_engine": 32,
            "single": {"ok": s_ok, "refused": s_refused,
                       "goodput_qps": round(s_goodput, 1)},
            "cluster": {"ok": c_ok, "refused": c_refused,
                        "goodput_qps": round(c_goodput, 1)}},
        "mismatched_requests": mismatches,
        "rolling_restart": restart_report,
        "rolling_restart_drive": restart_drive,
        "min_ready_observed": min_ready_seen,
        "bench_record": {
            "metric": "serving_cluster_burst_goodput_qps",
            "value": round(c_goodput, 1), "unit": "req/s",
            "backend": "cpu", "replicas": args.cluster,
            "host_cores": _os.cpu_count(),
            "single_engine_goodput_qps": round(s_goodput, 1),
            "cluster_served": c_ok, "single_served": s_ok,
            "offered": n_over,
            "closed_loop_cluster_rps": round(cluster_rps, 1),
            "closed_loop_single_rps": round(single_rps, 1)},
        "pool_stats": stats,
        "failures": failures,
    }
    text = json.dumps(report, indent=2)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    if args.json:
        print(text)
    else:
        rr = ("" if restart_report is None else
              f", rolling restart {len(restart_report['restarted'])}"
              f" replicas in {restart_report['wall_s']}s "
              f"(min ready {min_ready_seen}, "
              f"drive {restart_drive})")
        print(f"servebench --cluster {args.cluster} {args.model}: "
              f"single {single_rps:.0f} req/s, cluster "
              f"{cluster_rps:.0f} req/s ({speedup:.2f}x){rr}")
    if failures:
        for f in failures:
            print(f"servebench --cluster: FAILED — {f}",
                  file=sys.stderr)
        return 1
    return 0


def canary_main(args):
    """--canary: the versioned-deployment drill (selfcheck stage 10).

    Exports the bench model twice (v1/v2, identical weights, monotone
    model_version stamps), serves v1 from a replica pool under
    sustained client load, records a golden set, then walks the full
    deployment gauntlet:

    1. dark-deploy v2 as a canary (zero traffic) — the clean
       pre-traffic numerics gate must PASS (the weights are
       identical);
    2. briefly split traffic 50/50 to prove the per-version metrics
       separation (both versions' counters visible, nothing collides);
    3. arm ``serving_canary_regression`` and ``promote()`` — the 1%
       stage's in-flight numerics re-sample must AUTO-REJECT and roll
       back;
    4. assert the rollback contract: zero lost requests across the
       whole drill, weights instantly repointed, post-rollback traffic
       all-success.

    BENCH record: ``serving_rollback_s`` — weight repoint + canary
    drain + rebuild, wall-clock."""
    import shutil
    import tempfile
    import threading
    from paddle_tpu import cluster
    from paddle_tpu.resilience import faultinject
    from paddle_tpu.serving import ServingError

    failures = []
    workdir = tempfile.mkdtemp(prefix="servebench_canary_")
    router = None
    try:
        zp, infer, fetch, per_row, scope, feeds = _setup(args)
        fetch_names = (fetch if isinstance(fetch[0], str)
                       else [v.name for v in fetch])
        exe = fluid.Executor(fluid.CPUPlace())
        buckets = serving.BucketSpec(
            batch_sizes=_bucket_sizes(args.max_batch))
        v1_dir = os.path.join(workdir, "v1")
        v2_dir = os.path.join(workdir, "v2")
        # racecheck: ok(global-mutation) — driver-thread export before
        # the deployment engine starts; bench-private scope
        with fluid.scope_guard(scope):
            for dirname, mv in ((v1_dir, 1), (v2_dir, 2)):
                fluid.io.save_inference_model(
                    dirname, zp.feed_names, fetch_names, exe,
                    main_program=infer, serving_buckets=buckets,
                    model_version=mv)

        replicas = max(2, args.cluster or 2)
        router = cluster.serve_cluster(
            lambda: serving.ServingEngine.from_saved_model(
                v1_dir, place=fluid.CPUPlace()),
            replicas=replicas, warmup=True)
        mgr = cluster.DeploymentManager(router)
        v1 = mgr.register("v1", model_dir=v1_dir)
        v2 = mgr.register("v2", model_dir=v2_dir)
        if (v1.model_version, v2.model_version) != (1, 2):
            failures.append(
                f"model_version stamps wrong: v1={v1.model_version} "
                f"v2={v2.model_version} (expected 1, 2)")
        mgr.set_incumbent("v1")
        mgr.record_golden(feeds[:8])

        # ---- sustained client load for the whole gauntlet ----------
        outcomes = {"ok": 0, "typed": 0, "lost": 0}
        olock = threading.Lock()
        stop = threading.Event()

        def client(idx):
            k = idx
            while not stop.is_set():
                f = feeds[k % len(feeds)]
                k += args.concurrency
                try:
                    router.infer(f, timeout=30.0)
                    key = "ok"
                except ServingError:
                    key = "typed"
                except Exception:           # noqa: BLE001 — tallied
                    key = "lost"
                with olock:
                    outcomes[key] += 1

        clients = [threading.Thread(target=client, args=(i,),
                                    daemon=True)
                   for i in range(args.concurrency)]
        for t in clients:
            t.start()
        time.sleep(0.2)                  # load established

        # ---- 1. dark deploy + clean pre-traffic gate ---------------
        deploy = mgr.deploy_canary("v2", replicas=1)
        if not deploy["accepted"]:
            failures.append(
                "clean canary (identical weights) was rejected: "
                f"{deploy.get('numerics', {}).get('worst')}")

        # ---- 2. per-version metrics separation at 50/50 ------------
        status_mid = None
        if deploy["accepted"]:
            router.set_weights({"v1": 0.5, "v2": 0.5})
            time.sleep(0.6)
            status_mid = mgr.status()
            versions = status_mid["versions"] or {}
            for v in ("v1", "v2"):
                if not (versions.get(v) or {}).get("requests_total"):
                    failures.append(
                        f"per-version metrics show no traffic for "
                        f"{v} at 50/50 split")
            combined = status_mid["combined"] or {}
            if not combined.get("v2/requests_total"):
                failures.append(
                    "label-namespaced combined metrics are missing "
                    "v2/requests_total")

            # ---- 3. regression injected → promote must auto-reject -
            faultinject.arm("serving_canary_regression", at=0,
                            times=100)
            promote = mgr.promote(stages=(0.01, 0.5, 1.0),
                                  stage_s=0.4, poll_s=0.02)
            faultinject.disarm()
            if promote["accepted"]:
                failures.append(
                    "promote ACCEPTED a numerics-regressed canary")
            elif promote.get("rejected") != "numerics":
                failures.append(
                    f"canary rejected by {promote.get('rejected')!r}, "
                    "expected the numerics gate")
            rollback = promote.get("rollback") or {}
        else:
            promote = None
            rollback = mgr.rollback(reason="drill: deploy rejected")

        # ---- 4. rollback contract ---------------------------------
        time.sleep(0.2)                  # load continues post-rollback
        stop.set()
        for t in clients:
            t.join(30.0)
        weights = router.weights()
        if weights != {"v1": 1.0}:
            failures.append(
                f"post-rollback weights are {weights}, expected "
                "v1-only")
        wrong = [r.name for r in router.pool.replicas()
                 if r.version != "v1"]
        if wrong:
            failures.append(
                f"replicas {wrong} are not back on the incumbent")
        for name in rollback.get("replicas", []):
            for r in router.pool.replicas():
                if r.name == name and hasattr(r, "engine"):
                    if r.engine.model_version != 1:
                        failures.append(
                            f"re-warmed incumbent {name} serves "
                            f"model_version "
                            f"{r.engine.model_version}, expected 1")
        if outcomes["lost"]:
            failures.append(
                f"deployment gauntlet lost {outcomes['lost']} "
                "request(s) (untyped failure)")
        if outcomes["typed"]:
            failures.append(
                f"deployment gauntlet failed {outcomes['typed']} "
                "request(s) with typed errors — drain + weighted "
                "failover should complete every request")
        if outcomes["ok"] == 0:
            failures.append("no traffic flowed during the drill")

        # post-rollback wave: the restored incumbent must serve
        post, _ = _closed_loop(router.infer, feeds[:16],
                               args.concurrency, timeout=30.0)
        if len(post) != 16:
            failures.append("post-rollback wave did not complete")
        stats = router.stats()
    finally:
        if router is not None:
            router.close()
        shutil.rmtree(workdir, ignore_errors=True)

    rollback_s = rollback.get("serving_rollback_s")
    report = {
        "mode": "canary",
        "model": args.model,
        "replicas": replicas,
        "concurrency": args.concurrency,
        "deploy": deploy,
        "status_at_split": status_mid,
        "promote": promote,
        "rollback": rollback,
        "drive": dict(outcomes),
        "bench_record": {
            "metric": "serving_rollback_s",
            "value": rollback_s, "unit": "s", "backend": "cpu",
            "repoint_s": rollback.get("repoint_s"),
            "rewarm_compiles": rollback.get("rewarm_compiles"),
            "lost_requests": outcomes["lost"],
            "replicas": replicas},
        "pool_stats": stats,
        "failures": failures,
    }
    text = json.dumps(report, indent=2)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    if args.json:
        print(text)
    else:
        print(f"servebench --canary {args.model}: deploy "
              f"{'accepted' if deploy['accepted'] else 'REJECTED'}, "
              f"regressed canary "
              f"{'auto-rejected' if promote and not promote['accepted'] else 'NOT rejected'}, "
              f"rollback {rollback_s}s "
              f"({rollback.get('rewarm_compiles')} compiles), "
              f"drive {dict(outcomes)}")
    if failures:
        for f in failures:
            print(f"servebench --canary: FAILED — {f}",
                  file=sys.stderr)
        return 1
    return 0


def _export_remote_model(args, workdir):
    """Export the bench model with serving buckets — the dir a remote
    host provisions from."""
    zp, infer, fetch, per_row, scope, feeds = _setup(args)
    model_dir = os.path.join(workdir, "model")
    exe = fluid.Executor(fluid.CPUPlace())
    # racecheck: ok(global-mutation) — driver-thread export before any
    # serving thread starts; bench-private scope
    with fluid.scope_guard(scope):
        fluid.io.save_inference_model(
            model_dir, zp.feed_names,
            fetch if isinstance(fetch[0], str)
            else [v.name for v in fetch],
            exe, main_program=infer,
            serving_buckets=serving.BucketSpec(
                batch_sizes=_bucket_sizes(args.max_batch)))
    return model_dir, feeds, per_row


def remote_main(args):
    """--remote N: the cross-host serving fabric on loopback sockets —
    N ReplicaServers provisioned from one exported dir, a
    socket-backed pool behind the stock Router, closed-loop QPS
    (``serving_remote_qps``), plus the provisioning gate: a fresh
    server stood up from the saved-model dir (and another provisioned
    purely OVER THE WIRE) must answer within float tolerance of a
    lone local engine (docs/DISTRIBUTED.md "Serving across hosts")."""
    import os as _os
    import shutil
    import tempfile
    from paddle_tpu import cluster

    failures = []
    workdir = tempfile.mkdtemp(prefix="servebench_remote_")
    servers = []
    router = None
    try:
        model_dir, feeds, per_row = _export_remote_model(args, workdir)

        # ---- reference: a lone local engine on the same artifact ----
        ref_eng = serving.ServingEngine.from_saved_model(
            model_dir, place=fluid.CPUPlace())
        try:
            refs = [ref_eng.infer(f, timeout=60.0) for f in feeds]
            single_out, single_s = _closed_loop(
                ref_eng.infer, feeds, args.concurrency)
        finally:
            ref_eng.close()
        single_rps = len(feeds) / single_s

        # ---- cold provision: saved dir -> serving socket ------------
        t0 = time.perf_counter()
        first = cluster.ReplicaServer(model_dir, name="remote-0")
        cold_provision_s = time.perf_counter() - t0
        servers.append(first)

        # ---- wire provision: socket -> fresh dir -> serving socket --
        wire_dir = _os.path.join(workdir, "wire_provisioned")
        t0 = time.perf_counter()
        wire_report = cluster.provision_from_remote(first.addr,
                                                    wire_dir)
        wire = cluster.ReplicaServer(wire_dir, name="remote-1")
        wire_provision_s = time.perf_counter() - t0
        servers.append(wire)
        for _ in range(max(2, int(args.remote)) - 2):
            servers.append(cluster.ReplicaServer(model_dir))

        # ---- the fabric: Router over socket replicas ----------------
        router = cluster.serve_remotes([s.addr for s in servers],
                                       refresh_interval_s=0.2)
        served, remote_s = _closed_loop(router.infer, feeds,
                                        args.concurrency)
        remote_rps = len(feeds) / remote_s
        lost = sum(1 for out in served if out is None)
        if lost:
            failures.append(f"{lost} request(s) lost on the fabric")
        if per_row:
            # tolerance rule, same as --cluster: concurrent clients
            # co-batch into different bucket shapes than the
            # sequential reference, and XLA legitimately re-tiles per
            # shape — within a bucket the fabric is bit-exact (pinned
            # in tests/test_net_cluster.py)
            mismatches = sum(
                1 for ref, got in zip(refs, served)
                if got is None
                or not np.allclose(np.asarray(ref[0]),
                                   np.asarray(got[0]),
                                   rtol=1e-5, atol=1e-7))
            if mismatches:
                failures.append(
                    f"{mismatches} request(s) diverged beyond float "
                    "tolerance between the local engine and the "
                    "socket fabric")
        else:
            mismatches = None
        stats = router.stats()
        member_view = router.membership.view()
    finally:
        if router is not None:
            router.close()
        for s in servers:
            s.close()
        shutil.rmtree(workdir, ignore_errors=True)

    report = {
        "mode": "remote",
        "model": args.model,
        "remotes": len(servers),
        "requests": args.requests,
        "concurrency": args.concurrency,
        "host_cores": _os.cpu_count(),
        "local_engine_rps": round(single_rps, 1),
        "remote_qps": round(remote_rps, 1),
        "cold_provision_s": round(cold_provision_s, 3),
        "wire_provision_s": round(wire_provision_s, 3),
        "wire_provision": wire_report,
        "mismatched_requests": mismatches,
        "membership": member_view,
        "bench_record": {
            "metric": "serving_remote_qps",
            "value": round(remote_rps, 1), "unit": "req/s",
            "backend": "cpu", "remotes": len(servers),
            "host_cores": _os.cpu_count(),
            "local_engine_rps": round(single_rps, 1),
            "cold_provision_s": round(cold_provision_s, 3),
            "wire_provision_s": round(wire_provision_s, 3)},
        "pool_stats": stats,
        "failures": failures,
    }
    text = json.dumps(report, indent=2)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    if args.json:
        print(text)
    else:
        print(f"servebench --remote {len(servers)} {args.model}: "
              f"local {single_rps:.0f} req/s, fabric "
              f"{remote_rps:.0f} req/s, cold provision "
              f"{cold_provision_s:.2f}s, wire provision "
              f"{wire_provision_s:.2f}s "
              f"({wire_report['files']} files), "
              f"{mismatches} mismatches")
    if failures:
        for f in failures:
            print(f"servebench --remote: FAILED — {f}",
                  file=sys.stderr)
        return 1
    return 0


def remote_chaos_main(args):
    """--chaos --remote N: the partition drill on loopback sockets —
    net_partition + net_frame_drop armed mid-load against a socket
    pool must lose ZERO requests (every submit resolves to a result
    or a typed serving error), open and re-close the per-connection
    breaker, and rejoin the partitioned replica within one membership
    refresh of the fault clearing."""
    import shutil
    import tempfile
    import threading
    from paddle_tpu import cluster
    from paddle_tpu.resilience import faultinject
    from paddle_tpu.serving import ServingError

    n_remotes = max(2, int(args.remote))
    failures = []
    workdir = tempfile.mkdtemp(prefix="servebench_remote_chaos_")
    servers = []
    router = None
    try:
        model_dir, feeds, _per_row = _export_remote_model(args,
                                                          workdir)
        servers = [cluster.ReplicaServer(model_dir)
                   for _ in range(n_remotes)]
        router = cluster.serve_remotes(
            [s.addr for s in servers], refresh_interval_s=0.05,
            breaker_threshold=2, breaker_cooldown_s=0.1,
            reconnect_backoff_s=0.01, reconnect_attempts=2)
        outcomes = {"ok": 0, "typed": 0, "lost": 0}
        lock = threading.Lock()
        stop = threading.Event()

        def client(idx):
            k = idx
            while not stop.is_set():
                feed = feeds[k % len(feeds)]
                k += args.concurrency
                try:
                    router.infer(feed, timeout=5.0)
                    key = "ok"
                except ServingError:
                    key = "typed"
                except Exception:           # noqa: BLE001 — tallied
                    key = "lost"
                with lock:
                    outcomes[key] += 1
                time.sleep(0.002)

        threads = [threading.Thread(target=client, args=(i,),
                                    daemon=True)
                   for i in range(args.concurrency)]
        for t in threads:
            t.start()
        time.sleep(0.3)                     # load established
        # The partition window is progress-gated, not wall-clock: hold
        # the fault until a breaker has provably opened. When the
        # partition blackholes frames instead of erroring fast, the
        # first failures only resolve at the request-deadline sweep —
        # a fixed 1s window could close before any connection saw
        # breaker_threshold consecutive failures, flaking the drill.
        faultinject.arm("net_partition", at=0, times=1_000_000)
        faultinject.arm("net_frame_drop", at=0, times=4)
        gate = time.monotonic() + 30.0
        while time.monotonic() < gate and \
                sum(r.breaker_opens_total()
                    for r in router.pool.replicas()) == 0:
            time.sleep(0.02)
        time.sleep(0.2)                     # let the open breaker shed
        faultinject.disarm()
        time.sleep(1.0)                     # healing window
        stop.set()
        for t in threads:
            t.join(30.0)
        replicas = router.pool.replicas()
        breaker_opens = sum(r.breaker_opens_total()
                            for r in replicas)
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and \
                not all(r.alive() for r in replicas):
            time.sleep(0.02)
        rejoined = all(r.alive() for r in replicas)
        reclosed = all(
            r.breaker.state != "open" for r in replicas)
        member = router.membership.stats()
        # post-heal traffic must be clean
        post = 0
        try:
            for feed in feeds[:8]:
                router.infer(feed, timeout=30.0)
                post += 1
        except ServingError as exc:
            failures.append(f"post-heal traffic failed typed: {exc}")
        if outcomes["lost"]:
            failures.append(
                f"{outcomes['lost']} request(s) LOST under partition "
                "(untyped failure — every submit must resolve to a "
                "result or a typed serving error)")
        if outcomes["ok"] == 0:
            failures.append("no traffic flowed during the drill")
        if breaker_opens == 0:
            failures.append("no per-connection breaker opened under "
                            "a full partition")
        if not rejoined:
            failures.append("a partitioned replica failed to rejoin "
                            "after the fault cleared")
        if not reclosed:
            failures.append("a breaker stayed open after recovery")
        stats = router.stats()
    finally:
        faultinject.disarm()
        if router is not None:
            router.close()
        for s in servers:
            s.close()
        shutil.rmtree(workdir, ignore_errors=True)

    report = {
        "mode": "remote-chaos",
        "model": args.model,
        "remotes": n_remotes,
        "drive": outcomes,
        "breaker_opens": breaker_opens,
        "rejoined": rejoined,
        "breakers_reclosed": reclosed,
        "membership": member,
        "post_heal_ok": post,
        "pool_stats": stats,
        "failures": failures,
    }
    text = json.dumps(report, indent=2)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    if args.json:
        print(text)
    else:
        print(f"servebench --chaos --remote {n_remotes} "
              f"{args.model}: drive {outcomes}, "
              f"{breaker_opens} breaker opens, "
              f"rejoined={rejoined}, "
              f"rejoins={member['rejoins_total']}, "
              f"post-heal {post} ok")
    if failures:
        for f in failures:
            print(f"servebench --chaos --remote: FAILED — {f}",
                  file=sys.stderr)
        return 1
    return 0


def chaos_cluster_main(args):
    """--chaos --cluster N: the replica-crash drill. A replica is
    killed mid-load via the ``serving_replica_crash`` fault point; the
    router must reroute + fail over (ZERO lost requests, zero typed
    errors surfacing to callers), the pool must revive the dead
    replica, and post-recovery traffic must be all-success."""
    import threading
    from paddle_tpu import cluster
    from paddle_tpu.resilience import faultinject
    from paddle_tpu.serving import ServingError

    zp, infer, fetch, _per_row, scope, feeds = _setup(args)
    factory = _classifier_factory(args, infer, zp, fetch, scope)
    router = cluster.serve_cluster(factory, replicas=args.cluster,
                                   warmup=True,
                                   revive_interval_s=0.05)

    def drive(wave, timeout=30.0):
        counts = {"ok": 0, "typed": 0, "lost": 0}
        lock = threading.Lock()

        def one(f):
            try:
                router.infer(f, timeout=timeout)
                return "ok"
            except ServingError:
                return "typed"
            except Exception:               # noqa: BLE001 — tallied
                return "lost"
        with ThreadPoolExecutor(args.concurrency) as pool:
            for outcome in pool.map(one, wave):
                with lock:
                    counts[outcome] += 1
        return counts

    failures = []
    try:
        # phase 1 — steady state
        steady = drive(feeds)
        if steady["ok"] != len(feeds):
            failures.append(f"steady-state failures: {steady}")

        # phase 2 — a replica dies under the load
        faultinject.arm("serving_replica_crash", at=0)
        chaos = drive(feeds)
        faultinject.disarm("serving_replica_crash")
        if chaos["lost"]:
            failures.append(
                f"{chaos['lost']} request(s) lost in the crash wave")
        if chaos["typed"]:
            failures.append(
                f"{chaos['typed']} request(s) surfaced typed errors "
                "— failover should have absorbed the crash")

        # phase 3 — the pool revives the dead replica
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline and (
                router.pool.ready_count() < args.cluster):
            time.sleep(0.02)
        post = router.stats()
        if post["ready_replicas"] < args.cluster:
            failures.append(
                f"pool never recovered: {post['ready_replicas']}/"
                f"{args.cluster} READY")
        if post["revives_total"] < 1:
            failures.append("no revival recorded — did the crash "
                            "fault point fire?")

        # phase 4 — recovery traffic, then graceful drain
        recovery = drive(feeds)
        if recovery["ok"] != len(feeds):
            failures.append(f"post-recovery failures: {recovery}")
        drain_handles = [router.submit(f, timeout=30.0)
                         for f in feeds[:8]]
        router.close(drain=True)
        drained = 0
        for h in drain_handles:
            try:
                h.result(timeout=5.0)
                drained += 1
            except ServingError:
                pass
        if drained != len(drain_handles):
            failures.append(
                f"drain completed {drained}/{len(drain_handles)}")
    finally:
        faultinject.disarm()
        router.close()

    report = {
        "mode": "chaos-cluster",
        "model": args.model,
        "replicas": args.cluster,
        "requests_per_wave": len(feeds),
        "steady": steady,
        "chaos": chaos,
        "recovery": recovery,
        "revives_total": post["revives_total"],
        "reroutes_total": post["reroutes_total"],
        "failovers_total": post["failovers_total"],
        "drained": drained,
        "failures": failures,
    }
    text = json.dumps(report, indent=2)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    if args.json:
        print(text)
    else:
        print(f"servebench --chaos --cluster {args.cluster}: "
              f"chaos wave {chaos}, revives "
              f"{post['revives_total']}, failovers "
              f"{post['failovers_total']}, drained {drained}/8, "
              f"{len(failures)} failure(s)")
    if failures:
        for f in failures:
            print(f"servebench --chaos --cluster: FAILED — {f}",
                  file=sys.stderr)
        return 1
    return 0


def trace_main(args):
    """--arrival trace: trace-driven load (ROADMAP item 5) — replay a
    bursty, heavy-tailed arrival trace (synthetic by default,
    ``--trace-file`` to replay a recorded one) at a ladder of rates
    against the engine / router, and record the capacity answers: max
    sustainable QPS before any shed and p99 latency during burst
    phases. Works for both the classifier engine (default) and the
    decode engine (--decode), single-engine or --cluster N."""
    from paddle_tpu import cluster

    failures = []
    rng = np.random.RandomState(11)
    if args.max_queue is None:
        args.max_queue = 32     # a fixed bound makes the knee real
    if args.decode:
        cfg, buckets, scope, _exe, _gen, prompts = _decode_model(args)

        def factory():
            return serving.DecodeEngine(
                cfg, scope=scope, place=fluid.CPUPlace(),
                config=_decode_config(args, buckets))
        items = prompts
        metric = "llama_decode_trace_max_qps"
    else:
        zp, infer, fetch, _per_row, scope, feeds = _setup(args)
        factory = _classifier_factory(args, infer, zp, fetch, scope)
        items = feeds
        metric = "serving_trace_max_qps"

    if args.cluster:
        target = cluster.serve_cluster(factory, replicas=args.cluster,
                                       warmup=True)
    else:
        target = factory()
        target.warmup()
    try:
        ladder = trace_ladder(
            lambda it: target.submit(it,
                                     timeout=args.request_timeout),
            items, args, rng)
    finally:
        target.close()
    if ladder["max_sustained_qps"] is None:
        failures.append(
            "no clean rung: the base --rate already sheds — lower it")
    report = {
        "mode": "trace",
        "decode": bool(args.decode),
        "model": None if args.decode else args.model,
        "replicas": args.cluster or 1,
        "requests_per_rung": len(items),
        "base_rate": args.rate,
        "ladder_growth": args.ladder_growth,
        "burst_factor": args.burst_factor,
        "trace_file": args.trace_file,
        "ladder": ladder,
        "bench_record": {
            "metric": metric,
            "value": ladder["max_sustained_qps"], "unit": "req/s",
            "backend": "cpu", "replicas": args.cluster or 1,
            "p99_burst_ms": ladder["p99_burst_ms"]},
        "failures": failures,
    }
    text = json.dumps(report, indent=2)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    if args.json:
        print(text)
    else:
        print(f"servebench --arrival trace"
              f"{' --decode' if args.decode else ''}"
              f"{f' --cluster {args.cluster}' if args.cluster else ''}"
              f": max sustained {ladder['max_sustained_qps']} req/s, "
              f"p99 under burst {ladder['p99_burst_ms']} ms "
              f"({len(ladder['rungs'])} rungs)")
    if failures:
        for f in failures:
            print(f"servebench --arrival trace: FAILED — {f}",
                  file=sys.stderr)
        return 1
    return 0


# Priority-weighted goodput: an answered interactive request is worth
# 4x an answered batch request — the number the graceful-vs-flat-shed
# comparison is scored on.
_GOODPUT_WEIGHTS = {"interactive": 4.0, "standard": 2.0, "batch": 1.0}


def _overload_slo_classes():
    return {
        "interactive": serving.SLOClass(name="chat", ttft_target_s=1.0,
                                        priority="interactive"),
        "standard": serving.SLOClass(name="api", ttft_target_s=4.0,
                                     priority="standard"),
        "batch": serving.SLOClass(name="bulk", priority="batch"),
    }


def _overload_timeouts(request_timeout):
    """Per-class request deadlines: interactive callers give up fast
    (a chat user will not wait out a batch scrape's deadline), batch
    callers wait the full bound. This is what makes flat shedding
    LOSE: a queue-blind pool converts overload into queueing latency,
    which blows exactly the deadlines the valuable traffic carries."""
    rt = float(request_timeout)
    return {"interactive": rt * 0.25, "standard": rt * 0.6, "batch": rt}


def _overload_model(args):
    """A deliberately heavier llama for the overload referee (~an
    order of magnitude more work per token than _decode_model's tiny
    config): the pool's capacity must sit at human-scale req/s so a
    finite trace can genuinely saturate it — against the tiny config,
    any plausible trace drains inside its own deadlines and the knee
    is never real."""
    from paddle_tpu.models.llama import (LlamaConfig,
                                         build_llama_generator)
    # racecheck: ok(global-mutation) — bench CLI entrypoint: pins the
    # backend before any serving thread exists
    fluid.force_cpu()
    cfg = LlamaConfig(vocab_size=256, dim=256, n_layers=4, n_heads=8,
                      n_kv_heads=4, ffn_hidden=512, dtype="float32")
    buckets = (8, 16)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        ptok = fluid.layers.data(name="ptok", shape=[1, buckets[0]],
                                 dtype="int64",
                                 append_batch_size=False)
        build_llama_generator(cfg, ptok, max_new_tokens=2)
    # racecheck: ok(global-mutation) — driver-thread setup, no serving
    # threads yet; bench-private scope
    with fluid.scope_guard(scope):
        exe.run(startup)
    return cfg, buckets, scope


def _drive_overload(router, trace, prompts, rate_scale,
                    request_timeout):
    """Replay the rich trace (offsets scaled by ``rate_scale``)
    through ``router`` open-loop, tagging every request with its
    class's SLO and per-class deadline. Returns (counts, per_class
    {cls: {n, ok}}, wall, goodput) — goodput is the priority-weighted
    answered count."""
    slo_by_class = _overload_slo_classes()
    timeouts = _overload_timeouts(request_timeout)
    items = list(zip(prompts, trace["classes"]))

    def submit(item):
        prompt, cls = item
        return router.submit(prompt, timeout=timeouts[cls],
                             slo=slo_by_class[cls])

    counts, results, wall, _lats = open_loop_drive(
        submit, items, trace["offsets"] * rate_scale,
        result_timeout=float(request_timeout) + 30.0)
    per_class = {cls: {"n": 0, "ok": 0} for cls in _GOODPUT_WEIGHTS}
    for i, cls in enumerate(trace["classes"]):
        per_class[cls]["n"] += 1
        if results[i] is not None:
            per_class[cls]["ok"] += 1
    goodput = sum(_GOODPUT_WEIGHTS[c] * v["ok"]
                  for c, v in per_class.items())
    return counts, per_class, wall, goodput


def overload_main(args):
    """--overload: the graceful-degradation referee (selfcheck stage
    14). One deterministic diurnal/flash-crowd trace drives four
    phases against a decode replica pool:

    1. KNEE — a rate ladder through the graceful router (adaptive
       admission + priority tiers + brownout + retry budget) finds the
       highest rate the pool sustains with zero shed/timeout/error:
       ``serving_overload_knee_qps``.
    2. DRILL — the trace replays at 3x that knee. The counters must
       prove strict priority shedding (ZERO interactive sheds while
       batch sheds), metered brownout (engaged > 0, every step
       reverted, final level 0), and typed outcomes only.
    3. STORM — ``serving_retry_storm`` drops one answer in flight per
       closed-loop request; the retry budget must bound amplification
       (retries <= capacity) and then fail FAST typed
       (RetryBudgetExhaustedError), never storm.
    4. FLAT BASELINE — the same 3x-knee trace through a static-bound
       router (no admission, no tiers, no brownout, no budget);
       priority-weighted goodput graceful/flat must exceed 1.0:
       ``serving_overload_goodput_ratio``.

    ``--overload-flat-shed`` runs phases 1-3 on the FLAT config too —
    the inverted-teeth switch: the drill's shed-ordering, brownout,
    and storm assertions must then FAIL (exit 1), proving the gate
    has teeth."""
    from paddle_tpu.cluster import ReplicaPool, Router
    from paddle_tpu.resilience import faultinject
    from paddle_tpu.serving.overload import (AdmissionController,
                                             RetryBudget,
                                             RetryBudgetExhaustedError)

    failures = []
    flat_main = bool(args.overload_flat_shed)
    replicas = args.cluster or 2
    ceiling = 32 if args.max_queue is None else args.max_queue
    cfg, buckets, scope = _overload_model(args)

    if args.trace_file:
        trace = load_rich_trace(args.trace_file)
        n = len(trace["offsets"])
        if trace["classes"] is None or trace["buckets"] is None:
            fill = np.random.RandomState(23)
            mix = ("interactive", "standard", "batch")
            if trace["classes"] is None:
                trace["classes"] = [mix[int(fill.randint(3))]
                                    for _ in range(n)]
            if trace["buckets"] is None:
                trace["buckets"] = [int(fill.choice(buckets))
                                    for _ in range(n)]
    else:
        trace = gen_overload_trace(args.requests, args.rate,
                                   np.random.RandomState(23),
                                   buckets=buckets)
        n = args.requests
    offered = n / float(trace["offsets"][-1])    # trace's own mean qps
    prng = np.random.RandomState(7)
    prompts = [prng.randint(0, cfg.vocab_size,
                            (int(L),)).astype(np.int64)
               for L in trace["buckets"]]

    brownout_cfg = {"engage_at": 0.8, "revert_at": 0.4,
                    "dwell_s": 0.05, "queue_target_s": 0.15}

    def make_factory(brownout, scheduler):
        def factory():
            return serving.DecodeEngine(
                cfg, scope=scope, place=fluid.CPUPlace(),
                config=serving.DecodeConfig(
                    max_batch=args.max_batch, prompt_buckets=buckets,
                    max_new_tokens=args.max_new, page_size=8,
                    decode_block=args.decode_block,
                    prefill_batch=args.prefill_batch,
                    max_queue=ceiling, default_timeout_s=120.0,
                    scheduler=scheduler, brownout=brownout))
        return factory

    def graceful_router():
        pool = ReplicaPool(make_factory(dict(brownout_cfg), "slo"),
                           replicas=replicas, warmup=True)
        return Router(
            pool, max_cluster_queue=ceiling,
            admission=AdmissionController(hard_ceiling=ceiling,
                                          start_limit=ceiling // 4,
                                          target_delay_s=0.8),
            retry_budget=RetryBudget(capacity=16))

    def flat_router():
        # the pre-PR-19 story: fixed bound, FIFO admission,
        # first-come-first-shed, no brownout, no budget
        pool = ReplicaPool(make_factory(None, None),
                           replicas=replicas, warmup=True)
        return Router(pool, max_cluster_queue=ceiling)

    main_router = flat_router if flat_main else graceful_router

    # ---- phase 1: knee ladder ---------------------------------------
    # Climb EVERY rung (rungs past the knee are the cheapest — their
    # walls shrink with rate). The KNEE is the highest throughput any
    # rung actually achieved: on clean rungs achieved == offered (an
    # under-estimate of capacity), on saturated rungs achieved == the
    # pool's real service rate — so the max across the sweep is the
    # saturation throughput. A barely-dirty rung alone would lag it,
    # under-dosing the 3x-knee drill below.
    ladder = {"rungs": [], "max_sustained_qps": None, "knee_qps": None}
    rate = args.rate
    router = main_router()
    dirty_seen = False
    try:
        for _ in range(args.ladder_rungs):
            counts, per_class, wall, _g = _drive_overload(
                router, trace, prompts, offered / rate,
                args.request_timeout)
            achieved = counts["ok"] / wall if wall > 0 else 0.0
            clean = (counts["shed"] == 0 and counts["timeout"] == 0
                     and counts["error"] == 0)
            ladder["rungs"].append({
                "rate": round(rate, 1),
                "achieved_qps": round(achieved, 1),
                "counts": counts, "clean": clean})
            if clean and not dirty_seen:
                ladder["max_sustained_qps"] = round(achieved, 1)
            dirty_seen = dirty_seen or not clean
            ladder["knee_qps"] = max(ladder["knee_qps"] or 0.0,
                                     round(achieved, 1))
            rate *= args.ladder_growth
    finally:
        router.close()
    if ladder["max_sustained_qps"] is None:
        failures.append("no clean rung: the base --rate already sheds "
                        "— the clean side of the knee was never seen; "
                        "lower --rate")
    if not dirty_seen:
        # every rung clean = the ladder topped out UNDER the knee, so
        # "3x the knee" would not actually overload the pool and the
        # drill below would assert against thin air
        failures.append(
            "ladder exhausted --ladder-rungs with every rung clean — "
            "the knee was never crossed; raise --ladder-rungs or "
            "--rate")
    knee = float(ladder["knee_qps"] or args.rate)

    # ---- phase 2: flash-crowd drill at 3x the knee -------------------
    drill_rate = 3.0 * knee
    router = main_router()
    try:
        counts, per_class, wall, goodput_main = _drive_overload(
            router, trace, prompts, offered / drill_rate,
            args.request_timeout)
        # recovery: with the queues drained, pressure is 0 — every
        # brownout step must walk back down (counted) within seconds
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            levels = [r.engine.brownout.level()
                      for r in router.pool.replicas()
                      if getattr(r.engine, "brownout", None) is not None]
            if all(lv == 0 for lv in levels):
                break
            time.sleep(0.05)
        stats = router.stats()
        merged = stats.get("cluster") or {}

        def both(counter):
            return stats.get(counter, 0) + merged.get(counter, 0)

        shed_by_class = {c: both(f"shed_{c}_total")
                         for c in _GOODPUT_WEIGHTS}
        engaged = merged.get("brownout_engage_total", 0)
        reverted = merged.get("brownout_revert_total", 0)
        levels = [r.engine.brownout.level()
                  for r in router.pool.replicas()
                  if getattr(r.engine, "brownout", None) is not None]
        drill = {
            "rate": round(drill_rate, 1),
            "overload_factor": 3.0,
            "counts": counts,
            "per_class": per_class,
            "shed_by_class": shed_by_class,
            "brownout": {"engaged": engaged, "reverted": reverted,
                         "final_levels": levels,
                         "steps": {k: merged.get(k, 0) for k in
                                   ("brownout_cap_max_new_total",
                                    "brownout_spec_off_total",
                                    "brownout_chunk_defer_total")}},
            "router_overload": stats.get("overload"),
        }
        if counts["error"]:
            failures.append(f"drill: {counts['error']} request(s) "
                            "ended in an untyped/unexpected error — "
                            "overload must stay typed")
        if counts["timeout"]:
            failures.append(f"drill: {counts['timeout']} admitted "
                            "request(s) timed out — admission let in "
                            "more than the pool could serve")
        if shed_by_class["interactive"] != 0:
            failures.append(
                f"drill: {shed_by_class['interactive']} interactive-"
                "tier shed(s) at 3x the knee — priority shedding must "
                "protect the interactive tier")
        if shed_by_class["batch"] == 0:
            failures.append("drill: zero batch-tier sheds at 3x the "
                            "knee — the pool should be shedding batch "
                            "traffic first")
        if engaged == 0:
            failures.append("drill: brownout never engaged at 3x the "
                            "knee — the pressure signal is dead")
        if reverted != engaged or any(lv != 0 for lv in levels):
            failures.append(
                f"drill: brownout did not fully revert (engaged "
                f"{engaged}, reverted {reverted}, final levels "
                f"{levels}) — every degradation step must be undone "
                "on recovery")

        # ---- phase 3: retry-storm teeth (closed loop) ----------------
        before = router.stats()
        budget_cap = 4
        router.retry_budget = (None if flat_main
                               else RetryBudget(capacity=budget_cap))
        storm_calls, storm_ok, storm_exhausted, storm_untyped = 8, 0, 0, 0
        try:
            for _ in range(storm_calls):
                # one dropped answer per request: the retry must pass
                # the budget gate (re-armed so firings never burn
                # through a single call's whole failover ladder)
                faultinject.arm("serving_retry_storm", at=0, times=1)
                try:
                    router.infer(prompts[0],
                                 timeout=args.request_timeout,
                                 priority="standard")
                    storm_ok += 1
                except RetryBudgetExhaustedError:
                    storm_exhausted += 1
                except Exception:               # noqa: BLE001
                    storm_untyped += 1
        finally:
            faultinject.disarm("serving_retry_storm")
        after = router.stats()
        storm_retries = (after.get("failovers_total", 0)
                         - before.get("failovers_total", 0))
        recovered_ok = True
        try:
            router.infer(prompts[0], timeout=args.request_timeout,
                         priority="standard")
        except Exception:                       # noqa: BLE001
            recovered_ok = False
        storm = {"calls": storm_calls, "ok": storm_ok,
                 "budget_capacity": budget_cap,
                 "retries": storm_retries,
                 "exhausted_failfast": storm_exhausted,
                 "untyped": storm_untyped,
                 "exhausted_counter_delta":
                     (after.get("retry_budget_exhausted_total", 0)
                      - before.get("retry_budget_exhausted_total", 0)),
                 "recovered_after_disarm": recovered_ok}
        if storm_untyped:
            failures.append(f"storm: {storm_untyped} call(s) died "
                            "untyped under serving_retry_storm")
        if storm_retries > budget_cap:
            failures.append(
                f"storm: {storm_retries} retries burned against a "
                f"budget of {budget_cap} — the retry budget is not "
                "bounding amplification")
        if storm_exhausted == 0:
            failures.append("storm: RetryBudgetExhaustedError never "
                            "surfaced — beyond-budget retries must "
                            "fail fast typed, not keep retrying")
        if not recovered_ok:
            failures.append("storm: traffic did not recover after the "
                            "fault was disarmed")
    finally:
        faultinject.disarm("serving_retry_storm")
        router.close()

    # ---- phase 4: flat-shed baseline at the same 3x rate -------------
    router = flat_router()
    try:
        flat_counts, flat_per_class, _w, goodput_flat = _drive_overload(
            router, trace, prompts, offered / drill_rate,
            args.request_timeout)
    finally:
        router.close()
    ratio = (round(goodput_main / goodput_flat, 3)
             if goodput_flat > 0 else None)
    if ratio is None or ratio <= 1.0:
        failures.append(
            f"goodput: graceful/flat priority-weighted ratio {ratio} "
            "must exceed 1.0 — priority shedding + brownout must BUY "
            "goodput over flat shedding at the same overload")

    report = {
        "mode": "overload",
        "flat_shed": flat_main,
        "replicas": replicas,
        "requests": n,
        "hard_ceiling": ceiling,
        "trace": {"file": args.trace_file,
                  "offered_qps": round(offered, 2),
                  "classes": {c: trace["classes"].count(c)
                              for c in _GOODPUT_WEIGHTS}},
        "ladder": ladder,
        "drill": drill,
        "storm": storm,
        "flat_baseline": {"counts": flat_counts,
                          "per_class": flat_per_class},
        "goodput": {"graceful": goodput_main, "flat": goodput_flat,
                    "ratio": ratio, "weights": _GOODPUT_WEIGHTS},
        "bench_records": [
            {"metric": "serving_overload_knee_qps",
             "value": ladder["knee_qps"], "unit": "req/s",
             "backend": "cpu", "replicas": replicas,
             "hard_ceiling": ceiling},
            {"metric": "serving_overload_goodput_ratio",
             "value": ratio, "unit": "x", "backend": "cpu",
             "replicas": replicas, "overload_factor": 3.0,
             "weights": _GOODPUT_WEIGHTS},
        ],
        "failures": failures,
    }
    text = json.dumps(report, indent=2)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    if args.json:
        print(text)
    else:
        print(f"servebench --overload{' --overload-flat-shed' if flat_main else ''}: "
              f"knee {ladder['knee_qps']} req/s, drill at "
              f"{drill['rate']} req/s -> sheds {drill['shed_by_class']}, "
              f"brownout engaged {drill['brownout']['engaged']}/"
              f"reverted {drill['brownout']['reverted']}, storm "
              f"retries {storm['retries']}/{storm['budget_capacity']} "
              f"(fail-fast {storm['exhausted_failfast']}), goodput "
              f"ratio {ratio}x")
    if failures:
        for f in failures:
            print(f"servebench --overload: FAILED — {f}",
                  file=sys.stderr)
        return 1
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="serving load benchmark: batched vs single-request")
    ap.add_argument("--model", default="mnist_mlp",
                    choices=zoo.zoo_model_names())
    ap.add_argument("--requests", type=int, default=128)
    ap.add_argument("--concurrency", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=None,
                    help="batch bucket ceiling (default 8) / decode "
                         "slots (default 16 with --decode)")
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--assert-speedup", type=float, default=None,
                    help="exit 1 unless batched/baseline >= this")
    ap.add_argument("--chaos", action="store_true",
                    help="fault-injection drill instead of the "
                         "speedup race (selfcheck stage 4)")
    ap.add_argument("--decode", action="store_true",
                    help="continuous-batching decode benchmark on a "
                         "tiny llama (selfcheck stage 6)")
    ap.add_argument("--max-new", type=int, default=32,
                    help="tokens generated per request (--decode)")
    ap.add_argument("--decode-block", type=int, default=16,
                    help="decode steps per dispatch (--decode)")
    ap.add_argument("--prefill-batch", type=int, default=8,
                    help="same-bucket prompts one admission pass takes "
                         "together, each its own dispatch (--decode)")
    ap.add_argument("--spec", action="store_true",
                    help="speculative engine mode, perfect draft "
                         "(--decode)")
    ap.add_argument("--slo", action="store_true",
                    help="with --decode: SLO-attainment benchmark on "
                         "a mixed short/long interference trace — "
                         "FIFO vs SLO scheduler vs disaggregated "
                         "prefill/decode, plus the handoff chaos "
                         "drill (selfcheck stage 13)")
    ap.add_argument("--slo-force-fifo", action="store_true",
                    help="run the --slo comparison arm on the FIFO "
                         "scheduler — the attainment gate must then "
                         "FAIL (selfcheck's toothless-gate check)")
    ap.add_argument("--skip-disagg", action="store_true",
                    help="with --slo: skip the disaggregated pool arm "
                         "and its chaos drill")
    ap.add_argument("--opt-compare", action="store_true",
                    help="with --decode: also measure opt-on vs "
                         "opt-off engine throughput (classifier mode "
                         "always records the comparison)")
    ap.add_argument("--skip-baseline", action="store_true",
                    help="skip the sequential baseline (--decode)")
    ap.add_argument("--arrival", choices=("closed", "poisson", "trace"),
                    default="closed",
                    help="closed loop (default), open-loop Poisson "
                         "arrivals, or trace replay (bursty, "
                         "heavy-tailed; --trace-file to replay a "
                         "recorded trace)")
    ap.add_argument("--rate", type=float, default=50.0,
                    help="open-loop arrival rate, requests/s (trace "
                         "mode: the ladder's base rate)")
    ap.add_argument("--request-timeout", type=float, default=10.0,
                    help="per-request deadline in open-loop mode (s)")
    ap.add_argument("--cluster", type=int, default=0,
                    help="serve through a replica pool of N engines "
                         "behind the cluster router (0 = single "
                         "engine)")
    ap.add_argument("--remote", type=int, default=0,
                    help="N>0: drive N loopback ReplicaServers over "
                    "the socket fabric (serving_remote_qps + the "
                    "wire provisioning gate); "
                    "with --chaos, the partition drill instead")
    ap.add_argument("--canary", action="store_true",
                    help="versioned-deployment drill: canary traffic "
                         "shifting, numerics-gated promotion, instant "
                         "rollback (selfcheck stage 10)")
    ap.add_argument("--rolling-restart", action="store_true",
                    help="with --cluster: roll-restart every replica "
                         "under sustained mixed load and assert zero "
                         "losses (selfcheck stage 7)")
    ap.add_argument("--overload", action="store_true",
                    help="graceful-degradation referee: knee ladder, "
                         "3x-knee flash-crowd drill (priority shed "
                         "ordering + brownout round-trip), retry-"
                         "storm budget teeth, and the flat-shed "
                         "goodput comparison (selfcheck stage 14)")
    ap.add_argument("--overload-flat-shed", action="store_true",
                    help="run the --overload drill on the static-"
                         "bound flat-shed config — the shed-ordering/"
                         "brownout/goodput gates must then FAIL "
                         "(selfcheck's toothless-gate check)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="per-engine admission bound (default: scaled "
                         "to --requests; trace mode defaults to 32 so "
                         "the shed knee is observable)")
    ap.add_argument("--trace-file", default=None,
                    help="recorded arrival trace to replay (JSON "
                         "offsets) instead of the synthetic one")
    ap.add_argument("--burst-factor", type=float, default=4.0,
                    help="synthetic-trace burst rate multiplier")
    ap.add_argument("--ladder-rungs", type=int, default=4,
                    help="trace mode: max rate rungs to try")
    ap.add_argument("--ladder-growth", type=float, default=1.6,
                    help="trace mode: rate multiplier per rung")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if args.max_batch is None:
        args.max_batch = 16 if args.decode else 8

    if args.canary:
        return canary_main(args)
    if args.chaos and args.remote:
        return remote_chaos_main(args)
    if args.remote:
        return remote_main(args)
    if args.chaos and args.cluster:
        return chaos_cluster_main(args)
    if args.chaos:
        return chaos_main(args)
    if args.overload:
        return overload_main(args)
    if args.arrival == "trace":
        return trace_main(args)
    if args.decode and args.slo:
        return slo_main(args)
    if args.decode:
        return decode_main(args)
    if args.cluster:
        return cluster_main(args)

    zp, infer, fetch, per_row, scope, feeds = _setup(args)

    # ---- baseline: one synchronous Executor.run per request ----------
    base_exe = fluid.Executor(fluid.CPUPlace())
    # racecheck: ok(global-mutation, run-without-scope) — synchronous
    # single-threaded baseline in the driver; bench-private scope
    with fluid.scope_guard(scope):
        base_exe.run(infer, feed=feeds[0], fetch_list=fetch,
                     mode="test")                       # compile once
        t0 = time.perf_counter()
        # racecheck: ok(run-without-scope) — same private scope_guard
        baseline = [np.asarray(base_exe.run(infer, feed=f,
                                            fetch_list=fetch,
                                            mode="test")[0])
                    for f in feeds]
        base_s = time.perf_counter() - t0
    base_rps = args.requests / base_s

    # ---- batched: concurrent clients through the serving engine ------
    eng = serving.ServingEngine(
        infer, zp.feed_names, fetch, scope=scope,
        place=fluid.CPUPlace(),
        buckets=serving.BucketSpec(
            batch_sizes=_bucket_sizes(args.max_batch)),
        config=serving.ServingConfig(
            max_wait_ms=args.max_wait_ms,
            max_queue=max(2 * args.requests, 64)))
    arrival_counts = None
    try:
        warm = eng.warmup()
        if args.arrival == "poisson":
            # open loop: arrivals don't slow down with the server, so
            # overload surfaces as shed/timeout counts, not stretched
            # client think time
            arrival_counts, served, batched_s, _lats = open_loop_drive(
                lambda f: eng.submit(f, timeout=args.request_timeout),
                feeds,
                poisson_arrivals(len(feeds), args.rate,
                                 np.random.RandomState(7)),
                result_timeout=60.0)
            completed = arrival_counts["ok"]
        else:
            with ThreadPoolExecutor(args.concurrency) as pool:
                t0 = time.perf_counter()
                served = list(pool.map(
                    lambda f: eng.infer(f, timeout=60.0), feeds))
                batched_s = time.perf_counter() - t0
            completed = len(served)
        eng.assert_no_recompiles()
        # opt-on vs opt-off (closed loop only: open-loop throughput is
        # arrival-bound, so the comparison would measure the generator)
        opt_record = None
        if args.arrival == "closed":
            opt_record = _opt_compare_classifier(
                args, eng, infer, zp, fetch, scope, feeds)
        stats = eng.stats()
    finally:
        eng.close()
    batched_rps = completed / batched_s if batched_s > 0 else 0.0

    if per_row:
        pairs = [(ref, got) for ref, got in zip(baseline, served)
                 if got is not None]
        bitexact = sum(
            1 for ref, got in pairs
            if np.array_equal(ref, np.asarray(got[0])))
        mismatches = sum(
            1 for ref, got in pairs
            if not np.allclose(ref, np.asarray(got[0]),
                               rtol=1e-5, atol=1e-7))
    else:
        # batch-mean fetches aren't comparable across batch shapes
        bitexact, mismatches = None, None
    speedup = batched_rps / base_rps
    report = {
        "model": args.model,
        "requests": args.requests,
        "arrival": args.arrival,
        "arrival_counts": arrival_counts,
        "concurrency": args.concurrency,
        "fetch": list(fetch if isinstance(fetch[0], str)
                      else [v.name for v in fetch]),
        "per_row_fetch": per_row,
        "warmup": warm,
        "baseline_rps": round(base_rps, 1),
        "batched_rps": round(batched_rps, 1),
        "speedup": round(speedup, 2),
        "bitexact_requests": bitexact,
        "mismatched_requests": mismatches,
        "bench_record": opt_record,
        "serving_stats": stats,
    }
    text = json.dumps(report, indent=2)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    if args.json:
        print(text)
    else:
        opt_line = ""
        if opt_record is not None:
            opt_line = (f", opt {opt_record['opt_on_rps']:.0f} vs "
                        f"{opt_record['opt_off_rps']:.0f} req/s "
                        f"({opt_record['value']}x)")
        print(f"servebench {args.model}: baseline {base_rps:.0f} req/s, "
              f"batched {batched_rps:.0f} req/s ({speedup:.2f}x), "
              f"fill {stats['batch_fill_ratio']}, "
              f"p95 {stats['request_latency']['p95_ms']} ms, "
              f"{mismatches} mismatches, "
              f"{warm['compiles']} warmup compiles, 0 recompiles"
              f"{opt_line}")
    if mismatches:
        print(f"servebench: CORRECTNESS DROPPED — {mismatches} of "
              f"{args.requests} requests diverged from the "
              "single-request baseline", file=sys.stderr)
        return 1
    if args.assert_speedup is not None and args.arrival == "closed" \
            and speedup < args.assert_speedup:
        # open-loop throughput is bounded by the arrival rate, not the
        # server, so the closed-loop speedup floor doesn't apply there
        print(f"servebench: speedup {speedup:.2f}x below the "
              f"--assert-speedup {args.assert_speedup}x floor",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
