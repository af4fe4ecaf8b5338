"""The readers that take ``setup_s`` apart (benchmark/metrics/setup_*.py
over metrics/_setup.py) from the compile log the program keeps
(paddle_tpu/profiler.py): their arithmetic on a hand-made run and log, the
identity to ``setup_s``, nothing where the program keeps no log (the parent
commit, on which the driver also runs these files), their entries in
BENCHMARK.json, and the log of a real tiny engine and training executor."""
import json
import os
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import profiler

from benchmark.run import metric_reader

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)

COUNTS = ("setup_programs", "setup_cold_programs")
SECONDS = ("setup_trace_s", "setup_lower_s", "setup_compile_s",
           "setup_first_run_s", "setup_build_s", "setup_outside_s")
TEN_CELLS = [
    "mistral7b-serve-batch", "mistral7b-serve-chat", "resnet50-train-b256",
    "mistral7b-train-dp2tp2", "xing4-serve-docs", "deepseekv3-serve-reason",
    "mimov2flash-serve-mixed", "jamba2-serve-reason",
    "ouro26b-serve-assist", "olmohybrid-serve-longdocs"]


def entry(t0, t1, verify, build, trace, lower, compile_, hit, read=0.0):
    return {"program": 1, "version": 1, "executor": "Executor",
            "shapes": {}, "t0": t0, "t1": t1, "verify_s": verify,
            "build_s": build, "trace_s": trace, "lower_s": lower,
            "compile_s": compile_, "cache_hit": hit, "cache_read_s": read,
            "run_s": (t1 - t0) - verify - build - trace - lower - compile_}


@pytest.fixture
def log(monkeypatch):
    """A set-up of 30 s that began at 970: an executor's startup program
    inside the engine's build (983.5 to 985), three programs warmed, one
    of them cold, and a compile after the window's first edge (1000)."""
    entries = [
        entry(984.0, 984.5, 0.0, 0.1, 0.1, 0.1, 0.1, None),
        entry(986.0, 990.0, 0.1, 0.4, 1.0, 0.5, 1.5, True, read=1.2),
        entry(990.5, 993.0, 0.0, 0.2, 0.8, 0.6, 0.5, True, read=0.4),
        entry(993.0, 999.0, 0.1, 0.3, 1.2, 0.9, 3.0, False),
        entry(1000.5, 1003.0, 0.0, 0.5, 0.5, 0.5, 0.5, False)]
    monkeypatch.setattr(profiler, "_log", entries)
    return entries


@pytest.fixture
def serve_run():
    return {"kind": "serve", "setup_s": 30.0, "edges": {
        "start": {"t": 1000.0, "engine_build_s_total": 1.5,
                  "engine_built_at": 985.0, "warmup_s_total": 13.2},
        "end": {"t": 1050.0}}}


@pytest.fixture
def train_run():
    return {"kind": "train", "setup_s": 30.0,
            "edges": {"start": {"t": 1000.0, "compiles": 3},
                      "end": {"t": 1050.0, "compiles": 3}}}


EXPECTED = {
    "setup_programs": 4, "setup_cold_programs": 1,
    "setup_trace_s": 0.1 + 1.0 + 0.8 + 1.2,
    "setup_lower_s": 0.1 + 0.5 + 0.6 + 0.9,
    "setup_compile_s": 0.1 + 1.5 + 0.5 + 3.0,
    # what is left of the four brackets (0.5 + 4 + 2.5 + 6 s)
    "setup_first_run_s": 0.1 + 0.5 + 0.4 + 0.5,
    # the log's verifier and builds, and the engine's 1.5 s less the
    # half second of the bracket inside it
    "setup_build_s": 0.2 + (0.1 + 0.4 + 0.2 + 0.3) + (1.5 - 0.5),
}
EXPECTED["setup_outside_s"] = 30.0 - sum(
    EXPECTED[k] for k in SECONDS[:-1])


@pytest.mark.parametrize("name", COUNTS + SECONDS)
def test_reader_on_a_hand_made_run_and_log(log, serve_run, name):
    assert metric_reader(name)(serve_run) \
        == pytest.approx(EXPECTED[name], abs=1e-9)


def test_the_six_seconds_add_up_to_setup_s(log, serve_run, train_run):
    for run in (serve_run, train_run):
        assert sum(metric_reader(n)(run) for n in SECONDS) \
            == pytest.approx(run["setup_s"], abs=1e-9)


def test_a_training_run_has_the_logs_build_alone(log, train_run):
    assert metric_reader("setup_build_s")(train_run) \
        == pytest.approx(0.2 + 1.0, abs=1e-9)
    assert metric_reader("setup_programs")(train_run) == 4


def test_an_engine_without_the_instant_keeps_its_whole_build(log,
                                                             serve_run):
    del serve_run["edges"]["start"]["engine_built_at"]
    assert metric_reader("setup_build_s")(serve_run) \
        == pytest.approx(0.2 + 1.0 + 1.5, abs=1e-9)


def test_entries_after_the_windows_first_edge_are_left_out(log, serve_run):
    serve_run["edges"]["start"]["t"] = 1003.0
    assert metric_reader("setup_programs")(serve_run) == 5
    assert metric_reader("setup_cold_programs")(serve_run) == 2
    serve_run["edges"]["start"]["t"] = 992.0
    assert metric_reader("setup_programs")(serve_run) == 2
    assert metric_reader("setup_trace_s")(serve_run) \
        == pytest.approx(1.1, abs=1e-9)


@pytest.mark.parametrize("name", COUNTS + SECONDS)
def test_nothing_where_the_program_keeps_no_log(monkeypatch, serve_run,
                                                train_run, name):
    monkeypatch.delattr(profiler, "compile_totals")
    assert metric_reader(name)(serve_run) is None
    assert metric_reader(name)(train_run) is None


@pytest.mark.parametrize("name", COUNTS + SECONDS)
def test_the_entry_in_benchmark_json(name):
    found = [m for m in BENCHMARK["per_layer"] if m["name"] == name]
    assert len(found) == 1
    m = found[0]
    assert m["layer"] == "Entry" and m["moves"] == "setup_s"
    assert m["better"] == "lower"
    assert (m["unit"], m["source"]) == (
        ("count", "program_counter") if name in COUNTS
        else ("s", "host_clock"))
    assert m["workloads"] == TEN_CELLS
    assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                      "workloads"}
    cells = {w["name"] for w in BENCHMARK["workloads"]}
    assert set(m["workloads"]) <= cells
    assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics",
                                       name + ".py"))


def test_benchmark_json_is_under_its_limit_and_setup_s_is_as_it_was():
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    setup, = [m for m in BENCHMARK["end_to_end"] if m["name"] == "setup_s"]
    assert setup == {"name": "setup_s", "unit": "s", "better": "lower",
                     "bound": 0.1, "source": "host_clock"}
    names = [m["name"] for m in BENCHMARK["per_layer"]]
    assert len(names) == len(set(names))


# ---------------------------------------------------------------------
# the log of a real set-up, in this process
# ---------------------------------------------------------------------

def read_all(run):
    return {n: metric_reader(n)(run) for n in COUNTS + SECONDS}


def test_the_readers_over_a_tiny_engines_set_up(monkeypatch):
    from paddle_tpu.models.llama import LlamaConfig, build_llama_generator
    from paddle_tpu.serving import DecodeConfig, DecodeEngine
    monkeypatch.setattr(profiler, "_log", [])     # this set-up's alone
    t_process = time.monotonic()
    cfg = LlamaConfig(vocab_size=64, dim=32, n_layers=2, n_heads=4,
                      n_kv_heads=2, ffn_hidden=64, dtype="float32")
    gen_p, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(gen_p, startup):
        ptok = fluid.layers.data(name="ptok", shape=[1, 6], dtype="int64",
                                 append_batch_size=False)
        build_llama_generator(cfg, ptok, max_new_tokens=8)
    scope = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    eng = DecodeEngine(cfg, scope=scope, place=fluid.CPUPlace(),
                       config=DecodeConfig(
                           max_batch=4, prompt_buckets=(4, 8),
                           max_new_tokens=8, page_size=8, decode_block=4,
                           default_timeout_s=120.0))
    try:
        warm = eng.warmup()
        setup_s = time.monotonic() - t_process
        # what a builder does before its window: a request alone, then
        # the lead-in; no program compiles there
        for p in ([1, 2, 3], [4, 5, 6, 7, 8, 9], [7]):
            eng.generate(np.asarray(p), max_new=6)
        stats = eng.stats()
        edge = {k: v for k, v in stats.items()
                if isinstance(v, (int, float)) and not isinstance(v, bool)}
        edge["t"] = time.monotonic()
    finally:
        eng.close()
    run = {"kind": "serve", "setup_s": setup_s,
           "edges": {"start": edge, "end": dict(edge, t=edge["t"] + 1)}}
    m = read_all(run)
    # the startup program's executable and the engine's three
    assert m["setup_programs"] == 1 + warm["compiles"] == 4
    assert stats["warmup_compiles"] == warm["compiles"]
    assert len(profiler.compile_log(since=eng._warmed_at)) == 0
    assert m["setup_cold_programs"] == 0          # no persistent cache here
    assert all(m[n] > 0 for n in SECONDS)
    assert sum(m[n] for n in SECONDS) == pytest.approx(setup_s, abs=1e-9)
    assert m["setup_build_s"] >= stats["engine_build_s_total"]
    # the engine's brackets lie inside its warm-up
    inside = [e for e in profiler.compile_log()
              if e["t0"] >= stats["engine_built_at"]]
    assert len(inside) == 3
    assert sum(e["t1"] - e["t0"] for e in inside) \
        <= stats["warmup_s_total"]
    assert m["setup_outside_s"] < setup_s - sum(
        e["t1"] - e["t0"] for e in inside)


def test_the_readers_over_a_tiny_training_set_up(monkeypatch):
    monkeypatch.setattr(profiler, "_log", [])
    t_process = time.monotonic()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        loss = fluid.layers.mean(fluid.layers.square_error_cost(
            fluid.layers.fc(x, size=1), y))
        fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    feed = {"x": np.ones((4, 8), "float32"), "y": np.ones((4, 1), "float32")}
    for repeats in (1, 4, 4):       # as builders/train.py warms its step
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope,
                repeats=repeats)
    setup_s = time.monotonic() - t_process
    exe.run(main, feed=feed, fetch_list=[loss], scope=scope, repeats=4)
    run = {"kind": "train", "setup_s": setup_s, "edges": {
        "start": {"t": time.monotonic(), "compiles": exe.total_compiles()}}}
    # after the window: the footprint's compile is not the set-up's
    exe.compiled_stats(main, feed=feed, fetch_list=[loss], scope=scope,
                       repeats=4, top_k=0)
    assert len(profiler.compile_log()) == 4
    m = read_all(run)
    assert m["setup_programs"] == exe.total_compiles() == 3
    assert all(m[n] > 0 for n in SECONDS)
    assert sum(m[n] for n in SECONDS) == pytest.approx(setup_s, abs=1e-9)
