"""The in-place decode program as the chip's compiler leaves it.

Compiled here for a described TPU v5e, with no chip attached (the
``on-chip-measurement`` guide, section 2), at the benchmark's Mistral
shapes: what the CPU backend and the Pallas interpreter cannot show.
The pools are donated and carried through two nested loops in which a
scatter writes them and a custom call reads them; that is where XLA has
twice decided to copy 0.82 GB a layer (PERF.md section 6, PR 25 and
PR 34). The describing call is made inside a fixture and in this file
alone: one process at a time may load the TPU's library.
"""
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark.builders.serve import llama_config
from paddle_tpu.ops import pallas_attention as pa

import program_text

HERE = os.path.dirname(os.path.abspath(__file__))
# the engine of benchmark/configs/mistral-7b-v0.3.json: 16 slots of 512 +
# 256 positions in pages of 16, the pool those slots fill, 4 steps
GEOMETRY = dict(max_batch=16, page_size=16, n_pages=784, pages_per_seq=49,
                prompt_buckets=(128, 512), decode_block=4, quantize=True)
POOL = "32,784,16,8,128"


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 - whatever libtpu raises
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mistral():
    with open(os.path.join(HERE, os.pardir, "benchmark", "configs",
                           "mistral-7b-v0.3.json")) as f:
        return llama_config(json.load(f))


def test_the_kernel_compiles_at_mistrals_shapes(one_chip):
    """Mosaic takes the pools as they are stored: no operand is re-laid
    on its way into the call."""
    def abstract(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = abstract((32, 784, 16, 8, 128), jnp.bfloat16)
    text = jax.jit(pa.paged_gqa_decode).lower(
        abstract((16, 32, 128), jnp.bfloat16), pool, pool,
        abstract((), jnp.int32), abstract((16, 49), jnp.int32),
        abstract((16,), jnp.int32)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert not re.findall(rf"bf16\[{POOL}\]\S* copy\(", text)


def test_the_decode_program_copies_no_pool(one_chip, mistral, monkeypatch):
    """One kernel instance, both pools aliased from the donated inputs to
    the outputs, no ``copy`` of a pool and no array of the dense view's
    shapes anywhere in the module."""
    # the gate as the chip passes it; the kernel is lowered, not interpreted
    monkeypatch.setattr(pa, "_use_pallas", lambda: True)
    programs = mistral.build_paged_programs(**GEOMETRY)
    assert programs.decode["in_place"]
    compiled = program_text.lower_bundle(programs.decode, 2,
                                         sharding=one_chip).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "paged_gqa_decode" in text
    assert not re.findall(rf"bf16\[{POOL}\]\S* copy\(", text)
    for view in ("1,16,784,8,128", "32,16,784,8,128", "784,32,16,8,128",
                 "32,16,49,16,8,128"):
        assert f"bf16[{view}]" not in text, view
    pool_bytes = 32 * 784 * 16 * 8 * 128 * 2
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 2 * pool_bytes
    # what the program holds beside its arguments: no third pool
    assert memory.temp_size_in_bytes < pool_bytes


# -- the state cache kind's programs (models/hybrid_ssm.py) ---------------

@pytest.fixture(scope="module")
def jamba():
    """The programs of benchmark/configs/ai21-jamba2-3b.json at its
    ``builder.engine`` (128 slots of 5,120 + 2,048 positions in pages of
    64, chunks of 2,048, 4 steps), the pages sized as DecodeEngine sizes
    them."""
    from benchmark.builders.serve_ssm import model_config
    with open(os.path.join(HERE, os.pardir, "benchmark", "configs",
                           "ai21-jamba2-3b.json")) as f:
        config = json.load(f)
    e = config["builder"]["engine"]
    per_seq = -(-(e["prompt_buckets"][-1] + e["max_new_tokens"]
                  + e["decode_block"]) // e["page_size"])
    return model_config(config).build_paged_programs(
        max_batch=e["max_batch"], page_size=e["page_size"],
        n_pages=e["max_batch"] * per_seq + 1, pages_per_seq=per_seq,
        prompt_buckets=tuple(e["prompt_buckets"]),
        decode_block=e["decode_block"], chunk_size=e["chunk_size"])


def _hlo_type(shape, dtype):
    return {"float32": "f32", "bfloat16": "bf16"}[dtype] \
        + "[" + ",".join(map(str, shape)) + "]"


@pytest.mark.parametrize("label", ["decode", "chunk", "prefill_512"])
def test_no_program_re_lays_or_copies_a_state_pool(one_chip, jamba, label):
    """The state pools lie with whole lane tiles on their minor axis
    ([.., 16, 5120] float32, [.., 15360] bf16), so the chip takes them as
    they are stored: no ``copy`` of a pool of any kind in any program, all
    four aliased from the donated inputs to the outputs; the decode
    program has no view of the states (its steps run against the pool),
    and a prefill holds nothing of [T, N, C] (671 MB a layer at 2,048
    positions): the scan carries one state from position to position."""
    (s_shape, _), (t_shape, _) = jamba.pool_specs[2:]
    assert s_shape[2:] == [16, 5120] and t_shape[2:] == [3 * 5120]
    compiled = program_text.lower_bundle(
        program_text.bundles_of(jamba)[label], 4,
        sharding=one_chip).compile()
    text = compiled.as_text()
    for shape, dtype in jamba.pool_specs:
        pool = _hlo_type(shape, dtype)
        assert pool in text
        assert not re.findall(re.escape(pool) + r"\S* copy\(", text), pool
    pools = sum(math.prod(s) * (4 if dt == "float32" else 2)
                for s, dt in jamba.pool_specs)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= pools
    if label == "decode":
        # the attention layers' dense view (0.95 GB) and no state view
        rows = _hlo_type([s_shape[0], jamba.max_batch] + s_shape[2:],
                         "float32")
        assert rows not in text
        assert memory.temp_size_in_bytes < 1.3e9
    else:
        assert memory.temp_size_in_bytes < 0.5e9 < 2048 * 16 * 5120 * 4
