"""bench.py parent harness. Pins JSON recovery from streamed child
output, metric naming, the streamed-child timeout path, and that a
child which finds no chip fails before it writes a metric."""
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

_BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench.py")


def _load_bench():
    spec = importlib.util.spec_from_file_location("bench_mod", _BENCH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


bench = _load_bench()


def test_extract_json_takes_last_record():
    lines = ["noise", '{"a": 1}', "more noise", '{"metric": "x"}']
    assert bench._extract_json(lines) == {"metric": "x"}


def test_extract_json_none_on_garbage():
    assert bench._extract_json(["no json here"]) is None
    assert bench._extract_json([]) is None
    # a malformed trailing record must not resurrect an earlier one
    # from a DIFFERENT attempt
    assert bench._extract_json(['{"ok": 1}', "{broken"]) is None


def test_metric_names_cover_every_mode():
    for model in ("resnet50", "vgg16", "transformer", "llama-decode",
                  "llama-8b-decode", "seq2seq", "stacked-lstm",
                  "resnet50-pipe", "deepfm", "llama-spec-decode"):
        metric, unit = bench._metric_for(model)
        assert metric.endswith("per_chip") and unit


def test_every_ladder_rung_has_a_metric():
    """A rung added to _LADDER without a _metric_for mapping would make
    the parent's failure record name the resnet metric under the wrong
    mode — keep the two lists in lockstep."""
    default = bench._metric_for("resnet50")
    for model, _env, _est in bench._LADDER:
        if model != "resnet50":
            assert bench._metric_for(model) != default, model


@pytest.mark.slow      # waits out a real 12s child timeout
def test_run_child_timeout_after_record_is_a_failure(tmp_path):
    """A child that printed its record and then hung did not run to an
    end: it is killed and counts as a failure, record or not."""
    fake = tmp_path / "fake_bench.py"
    fake.write_text(
        "import sys, time, json\n"
        "print(json.dumps({'metric': 'm', 'value': 1.0}), flush=True)\n"
        "time.sleep(600)\n")
    real = bench._CHILD_SCRIPT
    try:
        bench._CHILD_SCRIPT = str(fake)
        ok, obj, tail = bench._run_child({}, timeout=12, tag="t")
    finally:
        bench._CHILD_SCRIPT = real
    assert not ok and obj is None
    assert "timeout" in tail and "metric" in tail


@pytest.mark.slow      # waits out a real 12s child timeout
def test_run_child_timeout_without_record(tmp_path):
    fake = tmp_path / "fake_bench.py"
    fake.write_text("import time\nprint('warming', flush=True)\n"
                    "time.sleep(600)\n")
    real = bench._CHILD_SCRIPT
    try:
        bench._CHILD_SCRIPT = str(fake)
        # window sized for child startup under load (a 6 s variant
        # flaked while the full suite saturated the host)
        ok, obj, tail = bench._run_child({}, timeout=12, tag="t")
    finally:
        bench._CHILD_SCRIPT = real
    assert not ok and obj is None
    assert "timeout" in tail and "warming" in tail


def test_run_child_nonzero_exit_after_record_is_a_failure(tmp_path):
    fake = tmp_path / "fake_bench.py"
    fake.write_text(
        "import sys, json\n"
        "print(json.dumps({'metric': 'm', 'value': 1.0}), flush=True)\n"
        "sys.exit(3)\n")
    real = bench._CHILD_SCRIPT
    try:
        bench._CHILD_SCRIPT = str(fake)
        ok, obj, tail = bench._run_child({}, timeout=60, tag="t")
    finally:
        bench._CHILD_SCRIPT = real
    assert not ok and obj is None
    assert "rc=3" in tail


def test_child_on_cpu_backend_exits_nonzero_without_a_metric():
    """There is no CPU mode: a bench.py child whose backend is not tpu
    exits non-zero before it builds anything and prints no metric
    line (JAX_PLATFORMS=cpu forces the situation here)."""
    out = subprocess.run(
        [sys.executable, _BENCH, "--child"],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert bench._extract_json(out.stdout.splitlines()) is None
    assert "metric" not in out.stdout
    assert "'cpu'" in out.stderr and "not 'tpu'" in out.stderr
