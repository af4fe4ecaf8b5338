"""The compile log (paddle_tpu/profiler.py ``compile_log`` /
``compile_totals``): one entry for every executable an executor compiles,
by phase, written where the compile happens; nothing on a cached dispatch;
the engine's two set-up counters and its warm-up by label; the spans.
"""
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import profiler

import test_engine_tracing as tracing
from test_engine_tracing import pt_events, scope     # noqa: F401 (fixture)

SIX = ("verify_s", "build_s", "trace_s", "lower_s", "compile_s", "run_s")
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"


def regression(width=8):
    """(executor, program, run(n rows, **run's options)) of a one-layer
    regression in programs and a scope of its own."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[width], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        loss = fluid.layers.mean(fluid.layers.square_error_cost(
            fluid.layers.fc(x, size=1), y))
        fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(0)

    def feed(n):
        return {"x": rng.rand(n, width).astype("float32"),
                "y": rng.rand(n, 1).astype("float32")}

    def run(n, **kw):
        return exe.run(main, feed=feed(n), fetch_list=[loss], scope=scope,
                       **kw)
    return exe, main, run


def make_engine(scope, **over):
    return tracing.make_engine(scope, auto_start=False, **over)


def of(program, entries):
    return [e for e in entries if e["program"] == program.uid]


# ---------------------------------------------------------------------
# (a) an entry a compile, nothing on a cached dispatch
# ---------------------------------------------------------------------

def test_one_entry_a_compile_and_none_on_a_cached_dispatch():
    exe, main, run = regression()
    t_before = time.monotonic()
    n0 = len(profiler.compile_log())        # the startup program's is in
    run(4)
    log = profiler.compile_log()
    assert len(log) == n0 + 1
    e = profiler.compile_log()[-1]
    assert e == log[-1]
    assert e["program"] == main.uid and e["version"] == main.version
    assert e["executor"] == "Executor"
    assert e["shapes"] == {"x": "float32[4,8]", "y": "float32[4,1]"}
    assert t_before <= e["t0"] < e["t1"] <= time.monotonic()
    assert all(e[k] >= 0 for k in SIX)
    assert e["trace_s"] > 0 and e["lower_s"] > 0 and e["compile_s"] > 0
    for _ in range(10_000):
        run(4, return_numpy=False)
    assert len(profiler.compile_log()) == n0 + 1
    assert exe.total_compiles() == 2         # the startup program's too
    # copies: a reader cannot write the log
    log[-1]["shapes"]["x"] = log[-1]["program"] = None
    assert profiler.compile_log()[-1] == e


def test_the_startup_program_and_each_program_version_is_an_entry():
    exe, main, run = regression()
    run(4)
    with fluid.program_guard(main):
        fluid.layers.scale(main.global_block().var("x"), scale=2.0)
    run(4)                                   # the version moved: a new step
    versions = [e["version"] for e in of(main, profiler.compile_log())]
    assert len(versions) == 2 and versions[0] < versions[1]


def test_a_new_feed_shape_on_a_cached_program_is_logged_with_its_uid():
    exe, main, run = regression()
    run(4)
    run(4)
    run(6)                 # jax.jit re-specialises the CACHED step
    run(6)
    mine = of(main, profiler.compile_log())
    assert [e["shapes"]["x"] for e in mine] == ["float32[4,8]",
                                                "float32[6,8]"]
    first, again = mine
    assert again["version"] == first["version"]
    assert again["executor"] == "Executor"
    # found where the step is traced: no build of its own, and it ends
    # with its compile
    assert again["verify_s"] == again["build_s"] == 0.0
    assert again["trace_s"] > 0 and again["compile_s"] > 0
    assert again["t0"] > first["t1"]
    assert exe.total_compiles() == 3         # the startup program's too


def test_a_dispatch_that_fails_leaves_no_entry_and_no_open_bracket():
    exe, main, run = regression()
    n0 = len(profiler.compile_log())
    with pytest.raises(Exception):
        exe.run(main, feed={"x": np.zeros((4, 8), "float32")},
                fetch_list=["no_such_variable"])
    assert len(profiler.compile_log()) == n0
    stray = profiler.compile_totals()["stray"]["count"]
    jax.jit(lambda v: v * 3 + 1)(np.ones((5,), np.float32))
    assert profiler.compile_totals()["stray"]["count"] == stray + 1


# ---------------------------------------------------------------------
# (b) phases are unions, and an entry's six add up to its bracket
# ---------------------------------------------------------------------

def test_the_six_phases_of_every_entry_sum_to_its_bracket(scope):
    eng = make_engine(scope, chunk_size=4, prompt_buckets=(4, 16),
                      page_size=4)
    t = time.monotonic()
    try:
        eng.warmup()     # scanned layers, nested jits: prefill, chunk, decode
    finally:
        eng.close()
    run = regression()[2]
    run(4)
    run(5)
    entries = profiler.compile_log(since=t)
    assert len(entries) >= 5
    for e in entries:
        assert sum(e[k] for k in SIX) == pytest.approx(e["t1"] - e["t0"],
                                                       abs=1e-9)
        assert all(e[k] >= 0 for k in SIX), e
        assert e["trace_s"] + e["lower_s"] + e["compile_s"] \
            <= e["t1"] - e["t0"]
        assert 0 <= e["cache_read_s"] <= e["compile_s"]


def test_a_phase_is_the_union_of_its_spans_never_their_sum():
    """JAX's events fired by hand into an open bracket: an outer trace
    span with two nested ones inside it, a lowering that holds a trace
    (a jit traced from a lowering rule) and a compile."""
    _, main, _ = regression()
    n0 = len(profiler.compile_log())
    t0 = time.monotonic()
    c = profiler.open_compile("Executor", main, {}, t0)
    w = time.time()
    time.sleep(0.06)
    span = jax.monitoring.record_event_time_span
    span(TRACE, w + 0.010, w + 0.015, fun_name="inner")
    span(TRACE, w + 0.012, w + 0.018, fun_name="inner2")
    span(TRACE, w + 0.000, w + 0.020, fun_name="stepped")
    span(TRACE, w + 0.024, w + 0.026, fun_name="from_a_lowering_rule")
    span(LOWER, w + 0.020, w + 0.030, fun_name="jit_stepped")
    span(COMPILE, w + 0.030, w + 0.050, fun_name="jit_stepped")
    c.close()
    e = profiler.compile_log()[-1]
    assert len(profiler.compile_log()) == n0 + 1
    assert e["trace_s"] == pytest.approx(0.022, abs=1e-6)
    assert e["lower_s"] == pytest.approx(0.008, abs=1e-6)
    assert e["compile_s"] == pytest.approx(0.020, abs=1e-6)
    assert sum(e[k] for k in SIX) == pytest.approx(e["t1"] - t0, abs=1e-9)
    # a span that began before the bracket counts from the bracket on
    c = profiler.open_compile("Executor", main, {}, time.monotonic())
    w = time.time()
    time.sleep(0.01)
    span(TRACE, w - 5.0, time.time(), fun_name="stepped")
    c.close()
    e = profiler.compile_log()[-1]
    assert 0.01 <= e["trace_s"] <= e["t1"] - e["t0"] < 1.0


def test_totals_sum_the_entries_up_to_an_instant():
    _, main, run = regression()
    run(4)
    mid = time.monotonic()
    run(7)
    before, after = (profiler.compile_totals(until=mid),
                     profiler.compile_totals())
    assert after["programs"] == before["programs"] + 1
    last = profiler.compile_log()[-1]
    for k in profiler.COMPILE_PHASES:
        assert after[k] == pytest.approx(before[k] + last[k])
    assert after["bracket_s"] == pytest.approx(
        before["bracket_s"] + last["t1"] - last["t0"])
    assert before["bracket_s"] <= sum(
        e["t1"] - e["t0"] for e in profiler.compile_log() if e["t1"] <= mid)
    assert profiler.compile_totals(until=0.0)["programs"] == 0
    assert set(after["stray"]) == {"count", "trace_s", "lower_s",
                                   "compile_s"}


# ---------------------------------------------------------------------
# (c) whether the persistent cache held the executable
# ---------------------------------------------------------------------

def test_cache_hit_is_false_then_true_across_clear_caches_and_null_when_off(
        tmp_path, no_compile_cache):
    from jax.experimental.compilation_cache import compilation_cache
    exe, main, run = regression(width=24)
    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    try:
        compilation_cache.reset_cache()
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        run(4)
        cold = profiler.compile_totals()["cold_programs"]
        jax.clear_caches()             # the process forgets; the disk not
        run(4)
        first, again = of(main, profiler.compile_log())
        assert first["cache_hit"] is False and again["cache_hit"] is True
        assert 0 < again["cache_read_s"] <= again["compile_s"]
        assert first["cache_read_s"] == 0
        assert profiler.compile_totals()["cold_programs"] == cold
    finally:
        for k, v in keep.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    jax.clear_caches()
    run(4)
    assert of(main, profiler.compile_log())[-1]["cache_hit"] is None


# ---------------------------------------------------------------------
# (d) the other executor, and the threads
# ---------------------------------------------------------------------

def test_parallel_executor_logs_its_compiles():
    from paddle_tpu.parallel import ParallelExecutor, make_mesh
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        loss = fluid.layers.mean(fluid.layers.square_error_cost(
            fluid.layers.fc(x, size=1), y))
        fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
    scope = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    pe = ParallelExecutor(loss_name=loss.name, main_program=main,
                          scope=scope, mesh=make_mesh({"dp": 2}))
    rng = np.random.RandomState(0)
    for n in (4, 4, 4, 8):
        pe.run([loss], feed={"x": rng.rand(n, 8).astype("float32"),
                             "y": rng.rand(n, 1).astype("float32")})
    mine = of(main, profiler.compile_log())
    # every executable jax.jit holds for the step is an entry: the first
    # dispatch's, the one its committed outputs force, the new shape's
    assert len(mine) == pe.total_compiles() >= 2
    assert {e["executor"] for e in mine} == {"ParallelExecutor"}
    assert mine[0]["build_s"] > 0 and mine[0]["shapes"]["x"] \
        == "float32[4,8]"
    assert mine[-1]["shapes"]["x"] == "float32[8,8]"
    for e in mine:
        assert sum(e[k] for k in SIX) == pytest.approx(e["t1"] - e["t0"],
                                                       abs=1e-9)


def test_a_compile_on_a_thread_with_no_dispatch_lands_in_stray():
    n0 = len(profiler.compile_log())
    before = profiler.compile_totals()["stray"]

    def work():
        jax.jit(lambda v: jnp.tanh(v) * 5 - 2)(np.ones((3, 7), np.float32))

    th = threading.Thread(target=work)
    th.start()
    th.join()
    after = profiler.compile_totals()["stray"]
    assert len(profiler.compile_log()) == n0
    assert after["count"] == before["count"] + 1
    for k in ("trace_s", "lower_s", "compile_s"):
        assert after[k] > before[k]


def test_two_engines_warming_on_two_threads_keep_their_entries_apart(scope):
    engines = [make_engine(scope), make_engine(scope, max_batch=2)]
    warmed = [None, None]

    def warm(i):
        warmed[i] = engines[i].warmup()

    t = time.monotonic()
    threads = [threading.Thread(target=warm, args=(i,)) for i in (0, 1)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    try:
        entries = profiler.compile_log(since=t)
        for eng, w in zip(engines, warmed):
            uids = {b["program"].uid: label
                    for label, b in eng._bundles().items()}
            mine = [e for e in entries if e["program"] in uids]
            assert sorted(uids[e["program"]] for e in mine) \
                == sorted(w["by_label"]) == ["decode", "prefill_4",
                                             "prefill_8"]
            assert w["compiles"] == len(mine) == 3
            rows = "4" if eng is engines[0] else "2"
            for e in mine:
                label = uids[e["program"]]
                # its own feeds, its own phases: nothing of the other
                # thread's compile, which ran at the same time
                assert e["shapes"]["dc_tokens" if label == "decode"
                                   else "pp_tokens"].startswith(
                    f"int32[{rows}" if label == "decode" else "int32[1,")
                assert w["by_label"][label]["trace_s"] \
                    == round(e["trace_s"], 3)
                assert sum(e[k] for k in SIX) == pytest.approx(
                    e["t1"] - e["t0"], abs=1e-9)
                assert e["t1"] - e["t0"] \
                    <= w["by_label"][label]["seconds"] + 1e-3
        assert not set(e["program"] for e in entries
                       if e["program"] in {b["program"].uid for b in
                                           engines[0]._bundles().values()}) \
            & {b["program"].uid for b in engines[1]._bundles().values()}
    finally:
        for eng in engines:
            eng.close()


# ---------------------------------------------------------------------
# (e) the engine's counters, its warm-up by label, what recompiled
# ---------------------------------------------------------------------

def test_the_engine_counts_its_build_and_its_warmup(scope, no_compile_cache):
    t = time.monotonic()
    eng = make_engine(scope)
    built = time.monotonic()
    try:
        s = eng.stats()
        assert 0 < s["engine_build_s_total"] <= built - t
        assert t <= s["engine_built_at"] <= built
        assert s["warmup_s_total"] == 0
        w = eng.warmup()
        after = time.monotonic()
        s = eng.stats()
        assert s["warmup_s_total"] == pytest.approx(w["seconds"], abs=1e-3)
        assert 0 < s["warmup_s_total"] <= after - built
        assert w["programs"] == w["compiles"] == 3 == s["warmup_compiles"]
        assert sorted(w["by_label"]) == ["decode", "prefill_4", "prefill_8"]
        for label, row in w["by_label"].items():
            assert row["seconds"] > 0 and row["cache_hit"] is None
            assert sum(row[k] for k in SIX) <= row["seconds"] + 5e-3
            assert row["trace_s"] > 0 and row["compile_s"] > 0
        assert sum(r["seconds"] for r in w["by_label"].values()) \
            <= w["seconds"] + 5e-3
        # no program compiles after warm-up, whatever is served
        eng.start()
        n = len(profiler.compile_log())
        for p in ([1, 2, 3], [4, 5, 6, 7, 8, 9], [3]):
            eng.generate(np.asarray(p), max_new=5)
        assert len(profiler.compile_log()) == n
        eng.assert_no_recompiles()
    finally:
        eng.close()


def test_assert_no_recompiles_names_what_compiled(scope, no_compile_cache):
    eng = make_engine(scope)
    try:
        eng.warmup()
        eng.assert_no_recompiles()
        eng._run_decode_program(           # two rows where four warmed
            np.zeros((2,), np.int64), np.ones((2,), np.int32),
            np.zeros((2, eng.pages_per_seq), np.int32))
        with pytest.raises(AssertionError) as err:
            eng.assert_no_recompiles()
    finally:
        eng.close()
    said = str(err.value)
    # the message's words: how long the compile took is the machine's
    # affair (1.055 s once, under six workers, where this read "in 0.")
    assert re.search(r"compiled decode in \d+\.\d{3} s ", said[:70])
    assert "(cache_hit None)" in said[:200]       # what a builder prints
    assert "dc_tokens:int32[2]" in said and "prefill" not in said[:200]


# ---------------------------------------------------------------------
# (f) the spans
# ---------------------------------------------------------------------

def test_the_spans_of_a_set_up_and_their_parents(scope, tmp_path):
    exe, main, run = regression()
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng = make_engine(scope)
        eng.warmup()
        eng.close()
        run(4)
        run(4)
        run(9)
    finally:
        jax.profiler.stop_trace()
    events = pt_events(str(tmp_path))
    by_name = {}
    for e in events:
        by_name.setdefault(e[0], []).append(e)

    def inside(child, parent):
        return (child[4] == parent[4] and parent[1] <= child[1]
                and child[2] <= parent[2])

    compiles = by_name["pt:executor/compile"]
    runs = by_name["pt:executor/run"]
    # three by the engine's warm-up, the program's first and its new shape
    assert len(compiles) == 5 and len(runs) == 6
    for c in compiles:
        assert sum(inside(c, r) for r in runs) == 1
    assert sum(any(inside(c, r) for c in compiles) for r in runs) == 5
    mine = [c for c in compiles if c[3]["program"] == main.uid]
    assert [c[3]["shapes"] for c in mine] == [
        "x:float32[4x8] y:float32[4x1]", "x:float32[9x8] y:float32[9x1]"]
    dispatches = by_name["pt:executor/dispatch"]
    assert any(inside(d, mine[0]) for d in dispatches)   # to fn's return
    build, = by_name["pt:engine/build"]
    assert build[3]["programs"] == 3 and build[3]["pool_bytes"] > 0
    warmup, = by_name["pt:engine/warmup"]
    assert build[2] <= warmup[1]
    warms = sorted(by_name["pt:engine/warm"], key=lambda e: e[1])
    assert [w[3]["label"] for w in warms] == ["prefill_4", "prefill_8",
                                             "decode"]
    for w in warms:
        assert inside(w, warmup)
        assert sum(inside(c, w) for c in compiles) == 1
    assert "pt:pexecutor/compile" not in by_name


def test_nothing_is_kept_of_a_span_when_no_trace_runs():
    exe, main, run = regression()
    profiler.reset_profiler()
    run(4)
    run(5)
    assert profiler._records == []
    assert len(of(main, profiler.compile_log())) == 2    # the log is
