"""The harness is driven by data: a configuration, a traffic mix and a
per-layer metric added as new files are found by name; the last line holds
the contract's keys and no other; without a TPU nothing is printed."""
import importlib.util
import json
import math
import os
import shutil
import subprocess

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

TINY_MODEL = dict(hidden_size=64, intermediate_size=128,
                  num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16, vocab_size=256,
                  torch_dtype="float32")
TINY_LENGTHS = {
    "prompt_len": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                   "min": 4, "max": 32},
    "output_len": {"dist": "lognormal", "median": 10, "sigma": 0.4,
                   "min": 4, "max": 24}}


def _read(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _write(doc, *parts):
    with open(os.path.join(*parts), "w") as f:
        json.dump(doc, f)


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A copy of BENCHMARK.json and benchmark/ to which a later PR's
    files are ADDED, none that is there edited: three configurations at
    tiny sizes, two traffic mixes, one per-layer metric, and their
    entries. (peaks.json gets a line for the CPU, which only a test may
    do: a run here must never print a device metric.)"""
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = _read(ROOT, "BENCHMARK.json")

    serve = _read(ROOT, "benchmark", "configs", "mistral-7b-v0.3.json")
    serve.update(TINY_MODEL, name="tiny-serve")
    serve["builder"]["engine"].update(
        quantize=False, max_batch=4, prompt_buckets=[16, 32],
        max_new_tokens=24)
    _write(serve, root, "benchmark", "configs", "tiny-serve.json")
    train = _read(ROOT, "benchmark", "configs",
                  "mistral-7b-v0.3-train-4chip.json")
    train.update(TINY_MODEL, name="tiny-train")
    # 64 unit-sized features x 0.02^2: the logits are all but uniform
    train["builder"].update(batch=4, seq=32, first_loss=math.log(256),
                            first_loss_tolerance=0.05)
    train["builder"]["build_llama"]["fused_head_chunk"] = 64
    _write(train, root, "benchmark", "configs", "tiny-train.json")

    _write(dict(TINY_LENGTHS, name="tiny-open", loop="open", rate_rps=20.0,
                order_seed=0, lead_in_s=0.5, why="test"),
           root, "benchmark", "traffic", "tiny-open.json")
    _write(dict(TINY_LENGTHS, name="tiny-closed", loop="closed", clients=6,
                list_len=64, order_seed=0, lead_in_s=0.5, why="test"),
           root, "benchmark", "traffic", "tiny-closed.json")
    with open(os.path.join(root, "benchmark", "metrics",
                           "prefills_in_window.py"), "w") as f:
        f.write("def read(run):\n"
                "    if run['kind'] != 'serve':\n"
                "        return None\n"
                "    e = run['edges']\n"
                "    return e['end']['prefill_total']"
                " - e['start']['prefill_total']\n")

    for name in ("tiny-serve", "tiny-train"):
        bench["configs"].append({
            "name": name, "source": "test", "reduced": [], "why": "test",
            "file": f"benchmark/configs/{name}.json"})
    cells = {"tiny-open-cell": ("tiny-serve", "tiny-open", 1),
             "tiny-closed-cell": ("tiny-serve", "tiny-closed", 1),
             "tiny-train-cell": ("tiny-train", "train-steps", 4)}
    for cell, (config, traffic, chips) in cells.items():
        bench["workloads"].append({"name": cell, "config": config,
                                   "traffic": traffic, "chips": chips,
                                   "why": "test"})
    # the open-loop cell stands where the chat cell does, the closed-loop
    # cell where the batch cell does, the training cell with the training
    # cells
    like = {"mistral7b-serve-chat": "tiny-open-cell",
            "mistral7b-serve-batch": "tiny-closed-cell",
            "mistral7b-train-dp2tp2": "tiny-train-cell"}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] += [like[w] for w in m["workloads"]
                               if w in like]
    bench["per_layer"].append({
        "name": "prefills_in_window", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "Scheduler",
        "moves": "out_tok_s", "workloads": ["tiny-closed-cell"]})
    _write(bench, root, "BENCHMARK.json")
    peaks = _read(root, "benchmark", "peaks.json")
    peaks["cpu"] = dict(peaks["TPU v5 lite"], source="test only")
    _write(peaks, root, "benchmark", "peaks.json")
    return root


@pytest.fixture(scope="module")
def run_py(checkout):
    """The copy's run.py, told that the CPU is the device (a test's
    doing: the program has no such switch). The persistent compile cache
    stays off: it is a process-wide JAX setting, and the tests that share
    this worker must not inherit it."""
    import paddle_tpu
    keep = paddle_tpu.enable_compile_cache
    paddle_tpu.enable_compile_cache = lambda: "(off in tests)"
    try:
        yield _load_run_py(checkout)
    finally:
        paddle_tpu.enable_compile_cache = keep


def _load_run_py(checkout):
    spec = importlib.util.spec_from_file_location(
        "bm_checkout_run", os.path.join(checkout, "benchmark", "run.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    assert module.ROOT == checkout
    module.device_report = lambda jax, chips: {
        "platform": jax.devices()[0].platform, "kind": "cpu",
        "count": len(jax.devices())}
    module.memory_peak_bytes = lambda jax, chips: 123456
    return module


def _last_line(capsys, run_py, *argv):
    capsys.readouterr()
    assert run_py.main(list(argv)) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-1]), lines[:-1]


@pytest.fixture(scope="module")
def results(run_py, checkout):
    """Each tiny cell run once, standard output kept."""
    import contextlib
    import io
    out = {}
    for cell, trace in (("tiny-open-cell", 0), ("tiny-closed-cell", 0),
                        ("tiny-closed-cell", 1), ("tiny-train-cell", 0),
                        ("tiny-train-cell", 1)):
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = run_py.main(["--workload", cell, "--seed", "2147483999",
                              "--seconds", "2", "--trace", str(trace)])
        lines = buf.getvalue().strip().splitlines()
        out[cell, trace] = (rc, json.loads(lines[-1]), lines[:-1])
    return out


CELLS = [("tiny-open-cell", 0), ("tiny-closed-cell", 0),
         ("tiny-closed-cell", 1), ("tiny-train-cell", 0),
         ("tiny-train-cell", 1)]


@pytest.mark.parametrize("cell,trace", CELLS)
def test_last_line_holds_the_contracts_keys_and_no_other(results, cell,
                                                         trace):
    rc, line, before = results[cell, trace]
    assert rc == 0
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device"}            # no breakdown without a chip
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    assert line["attempted"] > 0 and line["failed"] == 0
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"}
        assert isinstance(m["value"], float)
    assert before and not any(l.startswith("{\"correct\"") for l in before)


def test_added_config_traffic_and_metric_files_are_found_by_name(results):
    _, line, before = results["tiny-closed-cell", 1]
    assert line["metrics"]["prefills_in_window"]["value"] > 0
    assert line["metrics"]["prefills_in_window"]["unit"] == "count"
    assert any("config tiny-serve, traffic tiny-closed" in l
               for l in before)


def test_end_to_end_metrics_without_trace_per_layer_with(results):
    _, open_e2e, _ = results["tiny-open-cell", 0]
    assert set(open_e2e["metrics"]) == {"tpot_p90_ms", "setup_s"}
    _, closed_e2e, _ = results["tiny-closed-cell", 0]
    assert set(closed_e2e["metrics"]) == {"out_tok_s", "setup_s"}
    _, train_e2e, _ = results["tiny-train-cell", 0]
    assert set(train_e2e["metrics"]) == {"train_items_s", "setup_s"}
    assert open_e2e["correct"] and closed_e2e["correct"] \
        and train_e2e["correct"]


@pytest.mark.parametrize("cell", ["tiny-closed-cell", "tiny-train-cell"])
def test_a_cpu_run_writes_no_device_metric(results, cell):
    _, line, before = results[cell, 1]
    # counters are read on any machine; what needs the trace is left out
    traced = {"decode_step_ms.batch", "decode_roofline.batch",
              "device_idle.batch", "device_idle.train", "train_step_ms",
              "collective_share"}
    assert not traced & set(line["metrics"])
    assert "busy_s" not in line["device"] and "breakdown" not in line
    assert line["correct"] is False
    assert any("holds no device operation" in l for l in before)
    counted = {"tiny-closed-cell": "batch_occupancy.batch",
               "tiny-train-cell": "compiles_in_window.train"}[cell]
    assert counted in line["metrics"]


def test_serving_lines_before_the_last_say_what_was_offered(results):
    _, _, before = results["tiny-open-cell", 0]
    text = "\n".join(before)
    assert "offered:" in text and "generator lateness ms" in text
    assert "counters over the window" in text and "set-up" in text


def test_training_cell_reports_steps_and_losses(results):
    _, line, before = results["tiny-train-cell", 0]
    assert line["attempted"] >= 1
    assert any(l.startswith("losses") for l in before)


def test_unknown_workload_and_missing_reader_are_errors(run_py):
    with pytest.raises(SystemExit):
        run_py.main(["--workload", "no-such-cell", "--seed", "1",
                     "--seconds", "1", "--trace", "0"])
    with pytest.raises(SystemExit):
        run_py.metric_reader("no_such_metric")


def test_suffixed_metric_names_share_their_bases_reader(run_py):
    a = run_py.metric_reader("device_idle.batch")
    b = run_py.metric_reader("device_idle.train")
    assert a.__code__.co_filename == b.__code__.co_filename


def test_without_a_tpu_the_command_exits_nonzero_and_prints_nothing():
    bench = _read(ROOT, "BENCHMARK.json")
    cell = bench["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        bench["command"] + ["--workload", cell, "--seed", "1",
                            "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "{" not in p.stdout and "metrics" not in p.stdout
    assert "TPU" in p.stderr


def test_benchmark_json_keeps_to_the_contracts_form():
    import re
    b = _read(ROOT, "BENCHMARK.json")
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert name.match(c["name"])
        assert any(c["file"].startswith(p + "/") for p in b["paths"])
        doc = _read(ROOT, c["file"])
        assert doc["reduced"] == c["reduced"]
    pairs = set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert name.match(w["name"]) and name.match(w["traffic"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "traffic", w["traffic"] + ".json"))
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(
        1, len(b["workloads"]) // 4)
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert name.match(m["name"])
    assert "setup_s" in {m["name"] for m in b["end_to_end"]}
    assert len(json.dumps(b)) < 64 * 1024
