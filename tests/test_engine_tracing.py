"""Spans and phase counters inside the program (paddle_tpu/profiler.py
``record_event``; DecodeEngine's loop; Executor / ParallelExecutor
dispatch): the loop's clock is a partition, the prefill accounting is
exact, the spans land in the profiler's own trace beside what the
counters say, nothing is kept when no trace runs.
"""
import glob
import os
import threading
import time

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import profiler
from paddle_tpu.models.llama import LlamaConfig, build_llama_generator
from paddle_tpu.serving import DecodeConfig, DecodeEngine

pytestmark = pytest.mark.serving

CFG = LlamaConfig(vocab_size=64, dim=32, n_layers=2, n_heads=4,
                  n_kv_heads=2, ffn_hidden=64, dtype="float32")
SECONDS = ("loop_busy_s_total", "loop_idle_s_total",
           "decode_dispatch_s_total", "chunk_dispatch_s_total",
           "prefill_dispatch_s_total", "queue_wait_s_total")
COUNTS = ("prefill_dispatch_total", "prefill_tokens_total",
          "prefill_padded_tokens_total", "prefill_total",
          "decode_batches_total", "generated_tokens_total")


@pytest.fixture(scope="module")
def scope():
    gen_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(gen_p, startup):
        ptok = fluid.layers.data(name="ptok", shape=[1, 6], dtype="int64",
                                 append_batch_size=False)
        build_llama_generator(CFG, ptok, max_new_tokens=8)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        fluid.Executor(fluid.CPUPlace()).run(startup)
    return scope


def make_engine(scope, auto_start=True, **over):
    conf = dict(max_batch=4, prompt_buckets=(4, 8), max_new_tokens=8,
                page_size=8, decode_block=4, prefill_batch=2,
                default_timeout_s=120.0)
    conf.update(over)
    return DecodeEngine(CFG, scope=scope, place=fluid.CPUPlace(),
                        config=DecodeConfig(**conf),
                        auto_start=auto_start)


def prompts(n, seed, lo=2, hi=8):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, CFG.vocab_size,
                        (int(rng.randint(lo, hi + 1)),)).astype(np.int64)
            for _ in range(n)]


def pt_events(trace_dir):
    """[(name, start_ns, end_ns, stats, thread line)] of the ``pt:`` spans
    (and any other TraceAnnotation asked for by name) in the newest
    xplane under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    assert paths, "the trace wrote no xplane"
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("pt:") or ev.name == "feed":
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats), (plane.name, i)))
    return out


# ---------------------------------------------------------------------
# (a) the loop's clock is a partition of the worker's life
# ---------------------------------------------------------------------

def test_loop_clock_partitions_the_workers_life(scope):
    eng = make_engine(scope, auto_start=False)
    eng.warmup()
    before = eng.stats()
    born = time.perf_counter()
    eng.start()
    snaps = []
    reqs = []
    for wave in range(3):                # bursts with idle time between
        reqs += [eng.submit(p, max_new=8) for p in prompts(12, wave)]
        for r in reqs:
            r.result(60.0)
        snaps.append(eng.stats())
        time.sleep(0.2)
    eng._stop.set()              # the worker's own death, not close()'s
    eng._worker.join(10.0)       # joins of the watchdog and the rest
    life = time.perf_counter() - born
    assert not eng.worker_alive()
    eng.close()
    snaps.append(eng.stats())
    d = {k: snaps[-1][k] - before[k] for k in SECONDS + COUNTS}
    assert d["prefill_total"] == 36 and d["decode_batches_total"] >= 6
    # busy + idle = the worker's life (thread start and join are the 2%)
    assert d["loop_busy_s_total"] + d["loop_idle_s_total"] == \
        pytest.approx(life, rel=0.02)
    assert d["loop_idle_s_total"] >= 0.5          # three sleeps of 0.2 s
    dispatches = (d["decode_dispatch_s_total"]
                  + d["prefill_dispatch_s_total"]
                  + d["chunk_dispatch_s_total"])
    assert 0 < dispatches <= d["loop_busy_s_total"]
    assert d["chunk_dispatch_s_total"] == 0       # no chunk program here
    # every counter is monotonic from snapshot to snapshot
    for a, b in zip([before] + snaps, snaps):
        for k in SECONDS + COUNTS:
            assert b[k] >= a[k], k
    assert all(isinstance(snaps[-1][k], float) for k in SECONDS[:2])


# ---------------------------------------------------------------------
# (b) prefill accounting, exact for known prompts and buckets
# ---------------------------------------------------------------------

@pytest.mark.parametrize("lengths,prefill_batch,dispatches,padded", [
    # a dispatch carries one request and pays its bucket's length
    ([3], 2, 1, 4),
    ([5], 2, 1, 8),
    # queued together: a group leaves the queue together, and still each
    # request is a dispatch of its own
    ([2, 3, 4, 4], 2, 4, 4 * 4),
    ([3, 7, 4, 8, 6], 2, 5, 2 * 4 + 3 * 8),
    ([5, 6, 7, 8], 4, 4, 4 * 8),
])
def test_prefill_accounting_is_exact(scope, lengths, prefill_batch,
                                     dispatches, padded):
    eng = make_engine(scope, auto_start=False,
                      prefill_batch=prefill_batch)
    eng.warmup()
    before = eng.stats()
    rng = np.random.RandomState(len(lengths))
    reqs = [eng.submit(rng.randint(0, CFG.vocab_size, (n,)), max_new=2)
            for n in lengths]          # all queued before the worker runs
    time.sleep(0.05)
    eng.start()
    for r in reqs:
        assert r.result(60.0).size == 2
    eng.close()
    after = eng.stats()
    d = {k: after[k] - before[k] for k in SECONDS + COUNTS}
    assert d["prefill_total"] == len(lengths)
    assert d["prefill_dispatch_total"] == dispatches
    assert d["prefill_tokens_total"] == sum(lengths)
    assert d["prefill_padded_tokens_total"] == padded
    # each request waited the 50 ms before the worker was started, and
    # queue_wait's count is prefill_total: its mean is at least that
    assert d["queue_wait_s_total"] / d["prefill_total"] >= 0.05
    assert d["queue_wait_s_total"] / d["prefill_total"] < 30.0
    assert [r.seq for r in reqs] == list(range(1, len(lengths) + 1))


def test_the_first_of_a_group_has_its_token_before_the_last_is_dispatched(
        scope, tmp_path):
    """Four same-bucket requests leave the queue as one group and are
    dispatched one by one: four single-row spans in the group's order,
    the first request settled (it asked for one token) before the
    second's dispatch starts, and first tokens as far apart as the
    dispatches between them take."""
    eng = make_engine(scope, auto_start=False, prefill_batch=4)
    eng.warmup()
    rng = np.random.RandomState(4)
    reqs = [eng.submit(rng.randint(0, CFG.vocab_size, (n,)),
                       max_new=1 if i == 0 else 2)
            for i, n in enumerate((5, 8, 6, 7))]
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.start()
        for r in reqs:
            r.result(60.0)
        eng.close()
    finally:
        jax.profiler.stop_trace()
    events = pt_events(str(tmp_path))
    spans = sorted((e for e in events
                    if e[0] == "pt:engine/prefill_dispatch"),
                   key=lambda e: e[1])
    assert [e[3]["req"] for e in spans] == [r.seq for r in reqs]
    assert all(e[3]["rows"] == 1 and e[3]["bucket"] == 8 for e in spans)
    admits = [a for a in events if a[0] == "pt:engine/admit"
              and a[1] <= spans[0][1] and spans[0][2] <= a[2]]
    assert len(admits) == 1                    # one pass took all four
    assert all(admits[0][1] <= e[1] and e[2] <= admits[0][2]
               for e in spans)
    for a, b in zip(spans, spans[1:]):
        assert a[2] <= b[1]                    # one after the other
    retired = [e for e in events if e[0] == "pt:engine/retire"
               and e[3]["req"] == reqs[0].seq]
    assert len(retired) == 1
    assert spans[0][2] <= retired[0][1] and retired[0][2] <= spans[1][1]
    # the instant of each first token, on the clock ttft_s is taken from
    first = [r.enqueued_at + r.ttft_s for r in reqs]
    assert first == sorted(first)
    later = sum(e[2] - e[1] for e in spans[1:]) / 1e9
    assert first[-1] - first[0] >= later > 0


def test_chunked_prefill_counts_its_queue_wait_and_dispatch_time(scope):
    eng = make_engine(scope, prompt_buckets=(4, 16), prefill_batch=1,
                      chunk_size=4, page_size=4)
    try:
        eng.warmup()
        before = eng.stats()
        out = eng.generate(prompts(1, 7, lo=13, hi=13)[0], max_new=2)
        assert out.size == 2
        after = eng.stats()
    finally:
        eng.close()
    d = {k: after[k] - before[k]
         for k in SECONDS + COUNTS + ("chunk_prefill_total",)}
    assert d["chunk_prefill_total"] == 4           # 13 tokens, slices of 4
    assert d["chunk_dispatch_s_total"] > 0
    assert d["prefill_total"] == 1 and d["queue_wait_s_total"] > 0
    assert d["prefill_dispatch_total"] == 0        # no whole-prompt prefill


# ---------------------------------------------------------------------
# (c) the spans are in the profiler's own trace, beside the counters
# ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def traced(scope, tmp_path_factory):
    """One engine driven under jax.profiler.start_trace: the spans, the
    counters' movement during the trace, and the requests."""
    trace_dir = str(tmp_path_factory.mktemp("engine_trace"))
    eng = make_engine(scope)
    try:
        eng.warmup()
        eng.generate(prompts(1, 0)[0], max_new=4)
        jax.profiler.start_trace(trace_dir)
        try:
            before = eng.stats()
            reqs = [eng.submit(p, max_new=8) for p in prompts(10, 3)]
            for r in reqs:
                r.result(60.0)
            time.sleep(0.05)               # the last retire's span closes
            after = eng.stats()
        finally:
            jax.profiler.stop_trace()
    finally:
        eng.close()
    return pt_events(trace_dir), before, after, reqs


def test_decode_dispatch_spans_match_the_counter(traced):
    events, before, after, _ = traced
    spans = [e for e in events if e[0] == "pt:engine/decode_dispatch"]
    batches = after["decode_batches_total"] - before["decode_batches_total"]
    assert batches >= 2 and abs(len(spans) - batches) <= 1
    seconds = (after["decode_dispatch_s_total"]
               - before["decode_dispatch_s_total"])
    assert sum(e[2] - e[1] for e in spans) / 1e9 == \
        pytest.approx(seconds, rel=0.05)
    assert all(e[3]["rows"] >= 1 and e[3]["spec"] == 0 for e in spans)


def test_spans_nest_on_the_workers_thread(traced):
    events = traced[0]

    def inside(child, parent):
        return (child[4] == parent[4] and parent[1] <= child[1]
                and child[2] <= parent[2])

    by_name = {}
    for e in events:
        by_name.setdefault(e[0], []).append(e)
    for child, parent in (
            ("pt:executor/dispatch", "pt:executor/run"),
            ("pt:executor/run", "pt:engine/decode_dispatch"),
            ("pt:engine/decode_dispatch", "pt:engine/step"),
            ("pt:engine/step", "pt:engine/loop"),
            ("pt:engine/prefill_dispatch", "pt:engine/admit"),
            ("pt:engine/admit", "pt:engine/loop"),
            ("pt:engine/retire", "pt:engine/loop")):
        assert by_name.get(parent), parent
        # an executor span may also belong to a prefill: every parent
        # holds a child, and every engine child sits in some parent
        for p in by_name[parent]:
            if parent in ("pt:engine/decode_dispatch", "pt:executor/run"):
                assert any(inside(c, p) for c in by_name[child]), \
                    (child, parent)
        if child.startswith("pt:engine/"):
            for c in by_name[child]:
                assert any(inside(c, p) for p in by_name[parent]), \
                    (child, parent)
    run = by_name["pt:executor/run"][0][3]
    assert run["repeats"] == 1 and run["step"] >= 1 and "program" in run
    # the wait with nothing to do is no part of a loop span
    for idle in by_name.get("pt:engine/idle", []):
        assert not any(inside(idle, p) for p in by_name["pt:engine/loop"])


def test_a_requests_spans_share_its_number(traced):
    events, _, _, reqs = traced
    for r in (reqs[0], reqs[-1]):
        mine = {}
        for name, _, _, stats, _ in events:
            if "req" in stats and str(r.seq) in str(stats["req"]).split():
                mine.setdefault(name, []).append(stats)
        assert set(mine) == {"pt:engine/submit",
                             "pt:engine/prefill_dispatch",
                             "pt:engine/retire"}
        assert all(len(v) == 1 for v in mine.values())
        assert mine["pt:engine/submit"][0]["prompt_len"] == r.prompt.size
        assert mine["pt:engine/submit"][0]["max_new"] == 8
        assert mine["pt:engine/retire"][0]["tokens"] == 8
        assert mine["pt:engine/prefill_dispatch"][0]["bucket"] in (4, 8)


# ---------------------------------------------------------------------
# (d) nothing is kept when no trace runs and no session is open
# ---------------------------------------------------------------------

def test_spans_leave_no_record_without_a_session():
    lists = {k: len(v) for k, v in vars(profiler).items()
             if isinstance(v, list) and k != "__all__"}
    assert "_records" in lists
    for i in range(10_000):
        with profiler.record_event("pt:test/span", req=i) as ev:
            pass
    assert ev.seconds is not None and ev.seconds >= 0
    assert {k: len(v) for k, v in vars(profiler).items()
            if isinstance(v, list) and k != "__all__"} == lists


def test_a_session_still_prints_its_spans(tmp_path, capsys):
    profiler.reset_profiler()
    with profiler.profiler("All", profile_path=str(tmp_path)):
        with profiler.record_event("feed"):
            pass
    out = capsys.readouterr().out
    assert "feed" in out and "<session>" in out
    n = len(profiler._records)
    with profiler.record_event("feed"):
        pass
    assert len(profiler._records) == n
    profiler.reset_profiler()
    assert profiler._records == []


def test_the_profiler_keeps_one_timeline_and_one_list():
    """The public names, and the module's state: the session's summary
    rows, and since PR 53 the compile log with its one running entry for
    stray compiles; nothing else (no second timeline beside the trace)."""
    assert sorted(profiler.__all__) == sorted(
        ["cuda_profiler", "reset_profiler", "start_profiler",
         "stop_profiler", "profiler", "record_event", "compile_log",
         "compile_totals"])
    state = {k for k, v in vars(profiler).items()
             if isinstance(v, (list, dict)) and not k.startswith("__")
             and k not in ("_PHASES", "_COMPILE_SPANS")}     # constants
    assert state == {"_records", "_log", "_stray"}


# ---------------------------------------------------------------------
# (f) ParallelExecutor counts its compiles as Executor does, and its
# spans are in the trace
# ---------------------------------------------------------------------

@pytest.mark.parametrize("staged,second", [
    (True, 1),
    # the startup program's state is uncommitted and the first step's
    # outputs come back sharded on the mesh: the second step specialises
    # once more, and it is steady from there (.claude/skills/verify)
    (False, 2),
])
def test_parallel_executor_total_compiles_and_spans(tmp_path, staged,
                                                    second):
    from jax.sharding import NamedSharding, PartitionSpec
    from paddle_tpu.parallel import ParallelExecutor, make_mesh
    x = fluid.layers.data(name="x", shape=[8], dtype="float32")
    y = fluid.layers.data(name="y", shape=[1], dtype="float32")
    loss = fluid.layers.mean(fluid.layers.square_error_cost(
        fluid.layers.fc(x, size=1), y))
    fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
    fluid.Executor(fluid.CPUPlace()).run(fluid.default_startup_program())
    mesh = make_mesh({"dp": 2})
    pe = ParallelExecutor(loss_name=loss.name, mesh=mesh)
    if staged:
        scope = fluid.global_scope()
        for name in list(scope.vars):
            scope.set(name, jax.device_put(
                scope.find_var(name),
                NamedSharding(mesh.mesh, PartitionSpec())))
    assert pe.total_compiles() == 0 and pe.compile_counts() == {}
    rng = np.random.RandomState(0)

    def feed(n):
        return {"x": rng.rand(n, 8).astype("float32"),
                "y": rng.rand(n, 1).astype("float32")}

    pe.run([loss], feed=feed(4))
    assert pe.total_compiles() == 1
    pe.run([loss], feed=feed(4))
    assert pe.total_compiles() == second
    jax.profiler.start_trace(str(tmp_path))
    try:
        pe.run([loss], feed=feed(4))
    finally:
        jax.profiler.stop_trace()
    assert pe.total_compiles() == second     # same shapes: no compile
    pe.run([loss], feed=feed(8))
    assert pe.total_compiles() == second + 1  # a new feed shape: one more
    assert list(pe.compile_counts().values()) == [second + 1]
    spans = {e[0]: e for e in pt_events(str(tmp_path))}
    run = spans["pt:pexecutor/run"]
    assert run[3]["step"] == 3
    for child in ("pt:pexecutor/prepare", "pt:pexecutor/dispatch"):
        assert run[1] <= spans[child][1] and spans[child][2] <= run[2]
    assert spans["pt:pexecutor/prepare"][2] \
        <= spans["pt:pexecutor/dispatch"][1]


def test_spans_from_two_threads_keep_their_own_parents(tmp_path):
    """A child is the span open on the SAME thread: a submit on a
    caller's thread is never inside the worker's loop span."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        gate = threading.Event()

        def other():
            with profiler.record_event("pt:test/other"):
                gate.wait(5.0)

        th = threading.Thread(target=other)
        with profiler.record_event("pt:test/outer"):
            th.start()
            time.sleep(0.01)
            with profiler.record_event("pt:test/inner", req=1):
                pass
            gate.set()
            th.join(5.0)
        assert not th.is_alive()
    finally:
        jax.profiler.stop_trace()
    ev = {e[0]: e for e in pt_events(str(tmp_path))}
    assert ev["pt:test/inner"][4] == ev["pt:test/outer"][4]
    assert ev["pt:test/other"][4] != ev["pt:test/outer"][4]
    assert ev["pt:test/inner"][3] == {"req": 1}
