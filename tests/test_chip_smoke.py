"""chip_smoke.py rehearsed on the CPU, and the no-fallback rules it
rests on.

The kernel, trainer and server phases run here at LLAMA_TINY (the
kernel check through the Pallas interpreter), so that their logic is
known to work before chip time is spent on it. The subprocess checks
pin what a run without a chip must do: chip_smoke.py fails and names
the platform, TPUPlace raises, CPUPlace is the CPU, Executor() takes
the default device, and the compile cache sits where
JAX_COMPILATION_CACHE_DIR says or in <checkout>/.jax_cache.
"""
import dataclasses
import os
import subprocess
import sys

import pytest

import paddle_tpu as fluid
from paddle_tpu.models.llama import LLAMA_TINY
from paddle_tpu.ops import pallas_attention

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
import chip_smoke  # noqa: E402

TINY = dataclasses.replace(
    chip_smoke.CHIP, place=fluid.CPUPlace,
    model=LLAMA_TINY, kernel_shape=(1, 2, 256, 128),
    # head_dim 16 misses the kernel's gate and the CPU has no Mosaic
    mosaic_min={"kernel": 0, "train_fwd": 0, "train_bwd": 0},
    train_layers=2, train_vocab=128, train_batch=2, train_seq=32,
    fused_head_chunk=64,
    decode=dict(quantize=True, max_batch=2, prompt_buckets=(8, 16),
                max_new_tokens=8),
    prompt_lens=(3, 8, 9, 16, 12))

_PLACES = """
import jax
import paddle_tpu as fluid
try:
    fluid.TPUPlace().device
except RuntimeError as e:
    assert "cpu" in str(e), e
else:
    raise SystemExit("TPUPlace() resolved to a device without a TPU")
assert fluid.CUDAPlace is fluid.TPUPlace
assert fluid.CPUPlace().device.platform == "cpu"
assert fluid.Executor().place.device == jax.devices()[0]
"""

_CACHE = """
import os, sys
import jax
import paddle_tpu as fluid
want = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
    sys.argv[1], ".jax_cache")
assert fluid.enable_compile_cache() == want, fluid.enable_compile_cache()
assert jax.config.jax_compilation_cache_dir == want
"""


def _spawn(args, **env):
    """Start a python child on the CPU backend; the children run while
    the phases below do."""
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    return subprocess.Popen(
        [sys.executable, *args], cwd=_REPO, text=True,
        env={**base, "JAX_PLATFORMS": "cpu", "PYTHONPATH": _REPO, **env},
        stdout=subprocess.PIPE, stderr=subprocess.PIPE)


@pytest.fixture(scope="module", autouse=True)
def children():
    procs = {
        "smoke": _spawn(["chip_smoke.py"]),
        "places": _spawn(["-c", _PLACES]),
        "cache_default": _spawn(["-c", _CACHE, _REPO]),
        "cache_env": _spawn(["-c", _CACHE, _REPO],
                            JAX_COMPILATION_CACHE_DIR="/tmp/_smoke_cache"),
    }
    yield procs
    for p in procs.values():
        if p.poll() is None:
            p.kill()
        p.communicate()


def _finish(proc):
    out, err = proc.communicate(timeout=240)
    return proc.returncode, out, err


def test_kernel_phase_in_the_interpreter():
    pallas_attention._FORCE_INTERPRET = True
    try:
        result = chip_smoke.phase_kernel(TINY)
    finally:
        pallas_attention._FORCE_INTERPRET = False
    assert set(result["max_rel_err"]) == {"out", "dq", "dk", "dv"}


def test_kernel_phase_fails_off_the_kernel():
    """Without the interpreter hook the CPU has no kernel to land on:
    the phase must say so, not compare the reference with itself."""
    with pytest.raises(AssertionError, match="does not land on the kernel"):
        chip_smoke.phase_kernel(TINY)


def test_trainer_phase_at_tiny():
    result = chip_smoke.phase_trainer(TINY)
    assert result["losses"][-1] < result["losses"][0]
    assert result["mosaic_calls"] == {}


def test_server_phase_at_tiny():
    result = chip_smoke.phase_server(TINY)
    assert len(result["ttft_s"]) == len(TINY.prompt_lens)
    assert not any(result["counters"].values())


def test_multichip_phase_needs_four_devices(monkeypatch):
    monkeypatch.setattr(chip_smoke.jax, "devices", lambda *a: [object()])
    assert chip_smoke.phase_multichip(TINY) == {
        "status": "not_run", "reason": "1 devices"}


def test_degraded_failure_fails_the_phase():
    import warnings
    with pytest.raises(AssertionError, match="degraded"):
        with chip_smoke._phase({}):
            warnings.warn("transient device error on dispatch (failure 1)")


def test_mosaic_calls_are_counted_by_kernel_name():
    hlo = "\n".join([
        '  %a = bf16[8] custom-call(%x), custom_call_target="tpu_custom_call"'
        ', metadata={op_name="jit(f)/flash_fwd/pallas_call"}',
        '  %b = bf16[8] custom-call(%x), custom_call_target="tpu_custom_call"'
        ', metadata={op_name="jit(f)/transpose/flash_bwd_dkv/pallas_call"}',
        '  %c = bf16[8] custom-call(%x), custom_call_target="tpu_custom_call"',
        '  %d = f32[8] custom-call(%x), custom_call_target="Sharding"'])
    assert chip_smoke.mosaic_calls(hlo) == {
        "flash_fwd": 1, "flash_bwd_dkv": 1, "unnamed": 1}


@pytest.mark.parametrize("failing", [None, "trainer"])
def test_main_ends_with_the_drivers_line(monkeypatch, capsys, failing):
    """The last line of stdout holds exactly ok and device (platform,
    kind, count); the summary, ending in "claim": null, is the line
    before it. A failed phase gives ok false, exit code 1, and nothing
    runs after it."""
    import json
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(chip_smoke, "phase_device", lambda: dict(device))
    monkeypatch.setattr(chip_smoke, "_device_bytes_in_use", lambda d: [0])
    monkeypatch.setattr(chip_smoke.fluid, "enable_compile_cache",
                        lambda: "unused")
    for name in ("kernel", "trainer", "server", "multichip"):
        def phase(cfg, name=name):
            assert name != failing, "made to fail"
            return {"compile_s": 0.0, "run_s": 0.0}
        monkeypatch.setattr(chip_smoke, f"phase_{name}", phase)
    rc = chip_smoke.main()
    lines = capsys.readouterr().out.strip().splitlines()
    last, summary = json.loads(lines[-1]), json.loads(lines[-2])
    assert last == {"ok": failing is None, "device": device}
    assert rc == (0 if failing is None else 1)
    assert list(summary)[-1] == "claim" and summary["claim"] is None
    want = {"device": "pass", "kernel": "pass", "trainer": "pass",
            "server": "pass", "multichip": "pass"}
    if failing:
        want.update(trainer="fail", server="not_run", multichip="not_run")
    assert {k: v["status"] for k, v in summary["phases"].items()} == want


def test_script_without_a_chip_fails_and_names_the_platform(children):
    rc, out, err = _finish(children["smoke"])
    assert rc != 0
    assert "'cpu'" in err and "no CPU mode" in err
    assert "{" not in out            # no result line of any kind


def test_places_without_a_chip(children):
    rc, out, err = _finish(children["places"])
    assert rc == 0, err


@pytest.mark.parametrize("which", ["cache_default", "cache_env"])
def test_compile_cache_directory(children, which):
    rc, out, err = _finish(children[which])
    assert rc == 0, err
