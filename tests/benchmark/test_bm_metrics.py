"""Every metric reader on a small recorded run: the arithmetic of each,
and that a reader with nothing to read returns nothing."""
import json
import os

import pytest

from benchmark.run import metric_reader, metrics_of

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)
with open(os.path.join(ROOT, "benchmark", "configs",
                       "mistral-7b-v0.3.json")) as f:
    MISTRAL = json.load(f)
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def request(i, due, first, done, n_out, prompt_len, in_sample=True,
            error=None, pages=100):
    return {"idx": i, "phase": "window", "due": due, "submitted": due,
            "first_token": first, "done": done, "n_out": n_out,
            "max_new": n_out, "error": error, "pages_in_use": pages,
            "in_sample": in_sample, "prompt_len": prompt_len}


@pytest.fixture
def serve_run():
    """Ten sampled requests with 11 tokens each: time per token 10, 20,
    ... 100 ms and time to first token 100, 200, ... 1000 ms; one
    request outside the sample and one that failed."""
    reqs = [request(i, 1000.0 + i, 1000.0 + i + 0.1 * (i + 1),
                    1000.0 + i + 0.1 * (i + 1) + 10 * 0.01 * (i + 1),
                    11, 100, pages=100 + 10 * i) for i in range(10)]
    reqs.append(request(10, 990.0, 990.5, 995.0, 11, 100, in_sample=False,
                        pages=700))
    reqs.append(request(11, 1005.0, None, 1006.0, 0, 100,
                        error="QueueFullError: shed"))
    edge = {"generated_tokens_total": 1000, "prefill_total": 10,
            "decode_batches_total": 20, "compiles": 3, "t": 1000.0}
    return {
        "kind": "serve", "loop": "open", "requests": reqs,
        "t0": 1000.0, "t_end": 1050.0,
        "edges": {"start": edge,
                  "end": {"generated_tokens_total": 26000,
                          "prefill_total": 130, "decode_batches_total": 420,
                          "compiles": 3, "t": 1050.0},
                  "trace_start": {"decode_batches_total": 100},
                  "trace_end": {"decode_batches_total": 164}},
        "engine": {"max_batch": 16, "decode_block": 4, "page_size": 16,
                   "pool_pages": 784},
        "setup_s": 19.5, "config": MISTRAL, "peaks": PEAKS, "chips": 1,
        "device": {"memory_peak_bytes": 13_150_000_000},
        "allocator_peak_bytes": 13_150_000_000,
        "trace": {"busy_s": 7.76, "window_s": 8.0, "busy0_s": 7.76,
                  "collectives_s": 0.0,
                  "programs": {
                      "jit_stepped(1)": {"seconds": 7.5904, "count": 64},
                      "jit_stepped(2)": {"seconds": 2.112, "count": 16}}},
    }


@pytest.fixture
def train_run():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "resnet50-imagenet.json")) as f:
        config = json.load(f)
    return {
        "kind": "train", "steps": 520, "dispatches": 65, "repeats": 8,
        "items_per_step": 256, "window_s": 50.3,
        "edges": {"start": {"t": 0.0, "compiles": 2},
                  "end": {"t": 50.3, "compiles": 2}},
        "setup_s": 22.5, "config": config, "peaks": PEAKS, "chips": 1,
        "device": {"memory_peak_bytes": 9_500_000_000},
        "allocator_peak_bytes": 740_000_000,
        "step_footprint_bytes": 9_500_000_000,
        "trace": {"busy_s": 7.95, "window_s": 8.0, "busy0_s": 7.95,
                  "collectives_s": 1.59,
                  "programs": {
                      "jit_stepped(9)": {"seconds": 7.74, "count": 10}}},
    }


SERVE_EXPECTED = {
    "tpot_p90_ms": 91.0,            # 90th percentile of 10, 20 ... 100
    "ttft_p50_ms": 550.0, "ttft_p90_ms": 910.0,
    "out_tok_s": 25000 / 50.0,
    "setup_s": 19.5,
    "compiles_in_window.chat": 0,
    # (25000 tokens - 120 first tokens) / (400 dispatches x 4 x 16)
    "batch_occupancy.chat": 100.0 * 24880 / 25600,
    "pages_peak.batch": 100.0 * 190 / 784,   # the one outside the window: not it
    "decode_step_ms.batch": 1e3 * 7.5904 / 64 / 4,
    "device_idle.chat": 100.0 * (1 - 7.76 / 8.0),
    "peak_hbm_gb.chat": 13.15,
    "tpot_p90_ms.batch": 91.0,       # the judged tail's reader, by suffix
}


@pytest.mark.parametrize("name", sorted(SERVE_EXPECTED))
def test_serving_reader(serve_run, name):
    assert metric_reader(name)(serve_run) \
        == pytest.approx(SERVE_EXPECTED[name], rel=1e-9)


def test_a_cell_with_no_step_footprint_leaves_the_metric_out(serve_run):
    assert metric_reader("step_footprint_gb")(serve_run) is None


def test_decode_roofline_is_bytes_over_bandwidth_over_step_time(serve_run):
    from benchmark import work
    rows = 24880 / 1600                      # active rows a step
    mean_len = 100 + 11 / 2                  # prompt + half the answer
    least = work.llama_decode_step_bytes(MISTRAL, rows, mean_len) / 819e9
    want = 100.0 * least / (7.5904 / 64 / 4)
    got = metric_reader("decode_roofline")(serve_run)
    assert got == pytest.approx(want, rel=1e-9)
    assert 25.0 < got < 40.0                 # 7.3 GB in 29.65 ms


TRAIN_EXPECTED = {
    "train_items_s": 520 * 256 / 50.3,
    "setup_s": 22.5,
    "compiles_in_window.train": 0,
    "train_step_ms": 1e3 * 7.74 / 10 / 8,
    "device_idle.train": 100.0 * (1 - 7.95 / 8.0),
    "peak_hbm_gb.train": 0.74,       # the allocator's counter alone
    "step_footprint_gb": 9.5,        # XLA's analysis of the step alone
}


@pytest.mark.parametrize("name", sorted(TRAIN_EXPECTED))
def test_training_reader(train_run, name):
    assert metric_reader(name)(train_run) \
        == pytest.approx(TRAIN_EXPECTED[name], rel=1e-9)


def test_train_mfu_counts_two_operations_a_multiply_add(train_run):
    rate = 520 * 256 / 50.3
    want = 100.0 * 6 * 3_857_973_248 * rate / 197e12
    assert metric_reader("train_mfu")(train_run) \
        == pytest.approx(want, rel=1e-9)


def test_collective_share_is_for_cells_on_several_chips(train_run):
    assert metric_reader("collective_share")(train_run) is None
    train_run["chips"] = 4
    assert metric_reader("collective_share")(train_run) \
        == pytest.approx(100.0 * 1.59 / 7.95)


@pytest.mark.parametrize("name", [
    "decode_step_ms", "decode_roofline", "device_idle.chat",
    "train_step_ms", "device_idle.train", "collective_share"])
def test_a_reader_with_no_trace_returns_nothing(serve_run, train_run,
                                                name):
    for run in (serve_run, train_run):
        run["trace"] = None
        run["edges"].pop("trace_start", None)
        run["edges"].pop("trace_end", None)
        assert metric_reader(name)(run) is None


@pytest.mark.parametrize("name", ["tpot_p90_ms", "ttft_p50_ms",
                                  "ttft_p90_ms", "out_tok_s",
                                  "batch_occupancy", "pages_peak",
                                  "decode_step_ms", "decode_roofline"])
def test_a_serving_reader_returns_nothing_for_a_training_run(train_run,
                                                             name):
    assert metric_reader(name)(train_run) is None


@pytest.mark.parametrize("name", ["train_items_s", "train_step_ms",
                                  "train_mfu"])
def test_a_training_reader_returns_nothing_for_a_serving_run(serve_run,
                                                             name):
    assert metric_reader(name)(serve_run) is None


def test_every_metric_in_benchmark_json_has_a_reader_and_its_cells():
    cells = {w["name"] for w in BENCHMARK["workloads"]}
    e2e = {m["name"] for m in BENCHMARK["end_to_end"]}
    for group in ("end_to_end", "per_layer"):
        for m in BENCHMARK[group]:
            assert callable(metric_reader(m["name"]))
            assert set(m.get("workloads", cells)) <= cells
    cells_of = {m["name"]: set(m.get("workloads", cells))
                for m in BENCHMARK["end_to_end"]}
    for m in BENCHMARK["per_layer"]:
        # the metric it should move is reported wherever this one is
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells_of[m["moves"]]
        moved = next(x for x in BENCHMARK["end_to_end"]
                     if x["name"] == m["moves"])
        # the end-to-end metric is reported wherever this one is
        assert set(m.get("workloads", cells)) \
            <= set(moved.get("workloads", cells))
    for w in cells:
        names = [m["name"] for m in metrics_of(BENCHMARK, "end_to_end", w)]
        assert "setup_s" in names and len(names) >= 2
        assert metrics_of(BENCHMARK, "per_layer", w)
