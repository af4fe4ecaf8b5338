"""Test config: force an 8-device virtual CPU mesh so sharding tests run
without TPU hardware (the driver separately dry-runs the multi-chip path).
Must set env before jax initializes."""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def fresh_programs():
    """Each test gets fresh default programs, a fresh scope, and a fresh
    name generator — mirrors fluid unittests' per-test Program isolation."""
    import paddle_tpu as fluid
    from paddle_tpu.core import framework, unique_name
    from paddle_tpu.core import executor as executor_mod

    old_main = framework.switch_main_program(fluid.Program())
    old_startup = framework.switch_startup_program(fluid.Program())
    old_scope = executor_mod._global_scope
    executor_mod._global_scope = fluid.Scope()
    with unique_name.guard():
        yield
    framework.switch_main_program(old_main)
    framework.switch_startup_program(old_startup)
    executor_mod._global_scope = old_scope
