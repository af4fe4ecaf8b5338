"""The readers of the engine's own clock (benchmark/metrics/: engine_host_ms,
decode_dispatch_ms, prefill_share, prefill_fill, queue_wait_mean_ms,
engine_idle_share) on a synthetic run: the arithmetic of each, nothing for
a training run, and nothing for a program whose engine keeps no such
counter (the parent commit, on which the driver also runs these files)."""
import json
import os

import pytest

from benchmark.run import metric_reader, metrics_of

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)

NEW = ("engine_host_ms", "decode_dispatch_ms", "prefill_share",
       "prefill_fill", "queue_wait_mean_ms", "engine_idle_share")
# what a window of the batch cell looks like: 50 s, 310 decode dispatches
# of 119.5 ms, 104 one-request prefills of 132 ms in a [4, 512] bucket
START = {"t": 1000.0, "loop_busy_s_total": 10.0, "loop_idle_s_total": 2.0,
         "decode_dispatch_s_total": 7.0, "prefill_dispatch_s_total": 2.0,
         "chunk_dispatch_s_total": 0.0, "decode_batches_total": 60,
         "prefill_dispatch_total": 20, "chunk_prefill_total": 0,
         "prefill_tokens_total": 7_000,
         "prefill_padded_tokens_total": 40_960, "queue_wait_s_total": 1.5,
         "prefill_total": 20, "generated_tokens_total": 4_000}
END = {"t": 1050.0, "loop_busy_s_total": 59.75, "loop_idle_s_total": 2.25,
       "decode_dispatch_s_total": 7.0 + 310 * 0.1195,
       "prefill_dispatch_s_total": 2.0 + 104 * 0.132,
       "chunk_dispatch_s_total": 0.0, "decode_batches_total": 370,
       "prefill_dispatch_total": 124, "chunk_prefill_total": 0,
       "prefill_tokens_total": 7_000 + 104 * 384,
       "prefill_padded_tokens_total": 40_960 + 104 * 4 * 512,
       "queue_wait_s_total": 1.5 + 104 * 0.075, "prefill_total": 124,
       "generated_tokens_total": 24_000}


@pytest.fixture
def serve_run():
    return {"kind": "serve", "loop": "closed", "requests": [],
            "edges": {"start": dict(START), "end": dict(END)},
            "engine": {"max_batch": 16, "decode_block": 4}}


@pytest.fixture
def train_run():
    return {"kind": "train",
            "edges": {"start": {"t": 0.0, "compiles": 2},
                      "end": {"t": 50.3, "compiles": 2}}}


EXPECTED = {
    # (49.75 busy - 37.045 decode - 13.728 prefill) s over 414 dispatches
    "engine_host_ms.batch":
        1e3 * (49.75 - 310 * 0.1195 - 104 * 0.132) / (310 + 104),
    "decode_dispatch_ms.chat": 119.5,
    "prefill_share.batch": 100.0 * 104 * 0.132 / 50.0,
    "prefill_fill.batch": 100.0 * 384 / (4 * 512),
    "queue_wait_mean_ms": 75.0,
    "engine_idle_share.chat": 100.0 * 0.25 / 50.0,
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_engine_clock_reader(serve_run, name):
    assert metric_reader(name)(serve_run) \
        == pytest.approx(EXPECTED[name], rel=1e-9)


def test_chunk_dispatches_count_as_dispatches_and_as_dispatch_time(
        serve_run):
    serve_run["edges"]["end"]["chunk_prefill_total"] = 86
    serve_run["edges"]["end"]["chunk_dispatch_s_total"] = 1.0
    want = 1e3 * (49.75 - 310 * 0.1195 - 104 * 0.132 - 1.0) / 500
    assert metric_reader("engine_host_ms")(serve_run) \
        == pytest.approx(want, rel=1e-9)


@pytest.mark.parametrize("name", NEW)
def test_nothing_for_a_training_run(train_run, name):
    assert metric_reader(name)(train_run) is None


@pytest.mark.parametrize("name,counter", [
    ("engine_host_ms", "loop_busy_s_total"),
    ("engine_host_ms", "chunk_dispatch_s_total"),
    ("decode_dispatch_ms", "decode_dispatch_s_total"),
    ("prefill_share", "prefill_dispatch_s_total"),
    ("prefill_fill", "prefill_padded_tokens_total"),
    ("queue_wait_mean_ms", "queue_wait_s_total"),
    ("engine_idle_share", "loop_idle_s_total"),
])
def test_nothing_where_the_engine_keeps_no_such_counter(serve_run, name,
                                                        counter):
    for edge in serve_run["edges"].values():
        del edge[counter]
    assert metric_reader(name)(serve_run) is None


@pytest.mark.parametrize("name,counter", [
    ("engine_host_ms", "decode_batches_total"),
    ("decode_dispatch_ms", "decode_batches_total"),
    ("prefill_fill", "prefill_padded_tokens_total"),
    ("queue_wait_mean_ms", "prefill_total"),
])
def test_nothing_where_the_window_holds_no_dispatch(serve_run, name,
                                                    counter):
    e = serve_run["edges"]
    for c in ((counter, "prefill_dispatch_total", "chunk_prefill_total")
              if name == "engine_host_ms" else (counter,)):
        e["end"][c] = e["start"][c]
    assert metric_reader(name)(serve_run) is None


def test_the_new_entries_and_their_cells():
    """Eleven entries, appended: five in the batch cell and six in the
    chat cell, none in a training cell; the judged metrics as they were."""
    names = [m["name"] for m in BENCHMARK["per_layer"]]
    new = [n for n in names if n.split(".")[0] in NEW]
    assert len(new) == 11 and names[-11:] == new
    per_cell = {w["name"]: [m["name"] for m in metrics_of(
        BENCHMARK, "per_layer", w["name"])] for w in BENCHMARK["workloads"]}
    assert len(per_cell["mistral7b-serve-batch"]) == 8 + 5
    assert len(per_cell["mistral7b-serve-chat"]) == 9 + 6
    for cell in ("resnet50-train-b256", "mistral7b-train-dp2tp2"):
        assert not set(per_cell[cell]) & set(new)
    for m in BENCHMARK["per_layer"][-11:]:
        suffix = m["name"].partition(".")[2] or "chat"
        assert m["moves"] == {"batch": "out_tok_s",
                              "chat": "tpot_p90_ms"}[suffix]
        assert m["layer"] == ("Program" if m["name"].startswith(
            "decode_dispatch_ms") else "Scheduler")
    assert [(m["name"], m["bound"]) for m in BENCHMARK["end_to_end"]] == [
        ("tpot_p90_ms", 0.1), ("out_tok_s", 0.02),
        ("train_items_s", 0.01), ("setup_s", 0.1)]
