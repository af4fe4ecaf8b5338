"""Test config: force an 8-device virtual CPU mesh so sharding tests run
without TPU hardware (the driver separately dry-runs the multi-chip path).
Must set env before jax initializes. The session then runs under the
repository's one compile cache, as benchmark/run.py and chip_smoke.py do:
every worker and every later run reads what any of them compiled."""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import pytest  # noqa: E402

import paddle_tpu as fluid  # noqa: E402

# a tiny model's programs compile in under a second to a few kilobytes,
# which JAX's defaults keep out of the cache: let them in
fluid.enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

# the one test outside a PR's reach (tests/benchmark/ is a ``benchmark``
# issue's) that asserts the compile log's word for "no cache was asked"
_ASSERTS_NO_CACHE = (
    "tests/benchmark/test_bm_setup_phases.py"
    "::test_the_readers_over_a_tiny_engines_set_up",)


@pytest.fixture
def no_compile_cache():
    """The persistent cache off for the test's duration: for a test OF the
    compile log where no cache was asked (``cache_hit`` None), or of a
    cache of its own, which it points at its ``tmp_path``."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_compilation_cache_dir
    compilation_cache.reset_cache()
    jax.config.update("jax_compilation_cache_dir", None)
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


@pytest.fixture(autouse=True)
def fresh_programs(request):
    """Each test gets fresh default programs, a fresh scope, and a fresh
    name generator — mirrors fluid unittests' per-test Program isolation."""
    from paddle_tpu.core import framework, unique_name
    from paddle_tpu.core import executor as executor_mod

    if request.node.nodeid in _ASSERTS_NO_CACHE:
        request.getfixturevalue("no_compile_cache")

    old_main = framework.switch_main_program(fluid.Program())
    old_startup = framework.switch_startup_program(fluid.Program())
    old_scope = executor_mod._global_scope
    executor_mod._global_scope = fluid.Scope()
    with unique_name.guard():
        yield
    framework.switch_main_program(old_main)
    framework.switch_startup_program(old_startup)
    executor_mod._global_scope = old_scope
