"""The in-place decode programs as the chip's compiler leaves them.

Compiled here for a described TPU v5e, with no chip attached (the
``on-chip-measurement`` guide, section 2), at the benchmark's shapes
(Mistral's plain GQA pools; the sequence kind of MiMo-V2-Flash's share
and of Jamba2, entries flat in their pages, beside their rings and their
states; the one pool of xing4's and of DeepSeek-V3's share's latent
entries, 640 wide): what the CPU backend and the Pallas interpreter cannot
show.
The pools are donated and carried through two nested loops in which a
scatter writes them and a custom call reads them; that is where XLA has
twice decided to copy 0.82 GB a layer (PERF.md section 6, PR 25 and
PR 34). The describing call is made inside a fixture and in this file
alone: one process at a time may load the TPU's library.
"""
import functools
import importlib
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
from paddle_tpu.ops import ssm
from paddle_tpu.serving import DecodeConfig

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


def test_the_kernel_compiles_at_mistrals_shapes(one_chip, monkeypatch):
    """Mosaic takes the pools as they are stored: no operand is re-laid
    on its way into the call."""
    monkeypatch.setattr(pa, "_use_pallas", lambda: True)
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


# program_text.chip_fingerprint of Mistral's three programs at GEOMETRY,
# taken on PR 40's tree (the parent of PR 42, which gave the kernel's
# schedule to a second kernel and a mixed model its own form a kind): the
# batch and chat cells are the control of every change to the paged
# programs. A PR that means to change one of these takes the new value
# from this test's failure message.
MISTRAL_PINNED = {"prefill_128": "6aa7644d2bc2aab5",
                  "prefill_512": "2412bb7a5021351b",
                  "decode": "78e8a6fabc7b841a"}


@pytest.mark.parametrize("label", sorted(MISTRAL_PINNED))
def test_mistrals_programs_are_what_the_chip_was_asked_before(
        one_chip, mistral, label, monkeypatch):
    """The text each program lowers to for the chip, its kernel printed
    without the lines it was traced through."""
    monkeypatch.setattr(pa, "_use_pallas", lambda: True)
    bundle = program_text.bundles_of(
        mistral.build_paged_programs(**GEOMETRY))[label]
    got = program_text.chip_fingerprint(
        program_text.lower_bundle(bundle, 2, sharding=one_chip))
    assert got == MISTRAL_PINNED[label], (label, got)


# -- models that mix kinds of layer (models/hybrid_moe.py, hybrid_ssm.py) --

# model -> (its file under benchmark/configs, its builder)
MIXED = {"mimo": ("mimo-v2-flash-ep16.json", "serve_hybrid"),
         "jamba": ("ai21-jamba2-3b.json", "serve_ssm"),
         "laguna": ("laguna-xs.2.json", "serve_hybrid_gated")}


def _programs_of(config_file, builder):
    """The programs of a configuration under benchmark/configs at its own
    ``builder.engine``, the pages sized as DecodeEngine sizes them: (the
    file's ``model_config``, the programs)."""
    with open(os.path.join(HERE, os.pardir, "benchmark", "configs",
                           config_file)) as f:
        config = json.load(f)
    e = config["builder"]["engine"]
    block = e.get("decode_block", DecodeConfig().decode_block)
    per_seq = -(-(e["prompt_buckets"][-1] + e["max_new_tokens"] + block)
                // e["page_size"])
    cfg = importlib.import_module(
        "benchmark.builders." + builder).model_config(config)
    return cfg, cfg.build_paged_programs(
        max_batch=e["max_batch"], page_size=e["page_size"],
        n_pages=e.get("n_pages", e["max_batch"] * per_seq + 1),
        pages_per_seq=per_seq, prompt_buckets=tuple(e["prompt_buckets"]),
        decode_block=block, chunk_size=e.get("chunk_size"))


def _decode_form(programs, form):
    """The decode bundle as the loop dispatches it (``serving``) or with
    its whole fetch set (``probe``)."""
    return programs.decode if form == "serving" \
        else program_text.probe_form(programs.decode)


@pytest.fixture(scope="module")
def jamba():
    """benchmark/configs/ai21-jamba2-3b.json: 128 slots of 5,120 + 2,048
    positions in pages of 64, chunks of 2,048, 4 steps."""
    return _programs_of(*MIXED["jamba"])[1]


@pytest.fixture(params=sorted(MIXED))
def mixed(request, monkeypatch):
    """(a mixed model's configuration, its programs), with the gate as the
    chip passes it while the test runs: the kernel is lowered, not
    interpreted."""
    monkeypatch.setattr(pa, "_use_pallas", lambda: True)
    return _programs_of(*MIXED[request.param])


def _hlo_type(shape, dtype):
    return {"float32": "f32", "bfloat16": "bf16"}[dtype] \
        + "[" + ",".join(map(str, shape)) + "]"


def _assert_held_uncopied(text, pool_specs):
    """Every pool is in the module as it is stored, and never copied."""
    for shape, dtype in pool_specs:
        pool = _hlo_type(shape, dtype)
        assert pool in text
        assert not re.findall(re.escape(pool) + r"\S* copy\(", text), pool


def _assert_the_state_pool_is_only_carried(text, s_shape):
    """Nothing MAKES an array of the float32 state pool's shape or of a
    layer's slab's: the pool is a parameter, a loop's carry and a kernel's
    own result, and nothing else (no copy, fusion, update-slice, select)."""
    pool, slab = (_hlo_type(shape, "float32")
                  for shape in (s_shape, s_shape[1:]))
    made_by = set(re.findall(
        r" = \(?(?:[^()]*, )?" + re.escape(pool) + r"[^ ]* ([\w\-]+)\(",
        text))
    assert made_by <= {"parameter", "get-tuple-element", "custom-call",
                       "while", "tuple", "conditional"}, made_by
    assert slab not in text


def test_the_flat_kernel_compiles_at_the_mixed_models_shapes(
        one_chip, mixed):
    """Mosaic takes the sequence kind's pools as they are stored, keys 768
    wide beside values 512 (four heads of 192 | 128), one head of 128, and
    eight heads of 128 | 128 under 48 query heads (6 a group, which go in
    as 8): no pool is re-laid on its way into the call."""
    cfg, programs = mixed

    def abstract(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    (k_shape, _), (v_shape, _) = programs.pool_specs[:2]
    rows = programs.max_batch
    text = jax.jit(pa.paged_flat_decode).lower(
        abstract((rows, cfg.n_heads, cfg.head_dim)), abstract(k_shape),
        abstract(v_shape), abstract((), jnp.int32),
        abstract((rows, programs.pages_per_seq), jnp.int32),
        abstract((rows,), jnp.int32)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    _assert_held_uncopied(text, programs.pool_specs[:2])


def test_a_mixed_decode_program_holds_no_view_of_its_sequence_kind(
        one_chip, mixed):
    """A kernel instance a layer of the kind (those layers are taken by
    number), every pool of every kind aliased from the donated inputs to
    the outputs and none copied, and no buffer of the view's shape
    ([rows, kv heads, kmax, width] a layer, or as gathered) anywhere in
    the module: 2.15 GB of MiMo's 3.56 GB of temporaries, 0.95 of Jamba's
    1.07 (PERF.md section 6, PR 42)."""
    cfg, programs = mixed
    assert programs.decode["in_place"]
    compiled = program_text.lower_bundle(
        programs.decode, len(programs.pool_specs),
        sharding=one_chip).compile()
    text = compiled.as_text()
    (k_shape, _), (v_shape, _) = programs.pool_specs[:2]
    assert len(re.findall(r"tpu_custom_call.*paged_flat_decode", text)) \
        == k_shape[0] == 2
    # the routed layers' experts a decode step: ONE few-rows kernel a layer
    # body and no grouped product, for MiMo's share as for Laguna's whole
    # layers (PR 61); Jamba has no routed experts
    calls = len(re.findall(r"tpu_custom_call.*moe_few_rows", text))
    assert calls == {24: 3, 128: 0, 64: 2}[programs.max_batch]
    assert programs.decode["experts_in_kernel"] is bool(calls)
    assert "ragged" not in text
    _assert_held_uncopied(text, programs.pool_specs)
    rows = programs.max_batch
    kmax = programs.pages_per_seq * programs.page_size
    assert (rows, kmax) in ((24, 17472), (128, 7232), (64, 13376))
    views = re.findall(rf"\w+\[(?:\d+,)*{rows},(?:\d+,)?(?:{kmax}|"
                       rf"{programs.pages_per_seq},{programs.page_size})"
                       r"(?:,\d+)*\]", text)
    assert not views, sorted(set(views))
    memory = compiled.memory_analysis()
    pools = sum(math.prod(s) * (4 if dt == "float32" else 2)
                for s, dt in programs.pool_specs)
    assert memory.alias_size_in_bytes >= pools
    view = rows * kmax * (k_shape[3] + v_shape[3]) * 2 * k_shape[0]
    assert memory.temp_size_in_bytes < view / 2


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
    _assert_held_uncopied(text, jamba.pool_specs)
    pools = sum(math.prod(s) * (4 if dt == "float32" else 2)
                for s, dt in jamba.pool_specs)
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= pools
    if label == "decode":
        # the gate is not passed here (behind the kernel: above): ONE
        # attention layer's rows as the reference gathers them (0.47 GB)
        # and no state view
        rows = _hlo_type([s_shape[0], jamba.max_batch] + s_shape[2:],
                         "float32")
        assert rows not in text
        assert memory.temp_size_in_bytes < 1.3e9
    else:
        assert memory.temp_size_in_bytes < 0.5e9 < 2048 * 16 * 5120 * 4


def test_the_state_step_kernel_compiles_at_jambas_slab(one_chip,
                                                       monkeypatch):
    """Mosaic takes the state pool as it is stored and writes it where it
    lies: ONE custom call, the pool aliased from the donated argument to
    the result, nothing of the slab's size beside it (the input and output
    maps ride with the entries on lanes, [3, 16, 64]: no [129, 16] stored
    128 wide)."""
    monkeypatch.setattr(pa, "_use_pallas", lambda: True)

    def abstract(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    n, states, width = 129, 16, 5120
    pool = (26, n, states, width)
    assert ssm.step_in_kernel(pool, "float32")
    compiled = jax.jit(ssm.step_entries, donate_argnums=(7,)).lower(
        abstract((n, width)), abstract((n, width), jnp.bfloat16),
        abstract((n, states)), abstract((n, states)),
        abstract((n,), jnp.bool_), abstract((states, width)),
        abstract((width,)), abstract(pool),
        abstract((), jnp.int32)).compile()
    text = compiled.as_text()
    assert len(re.findall(r"tpu_custom_call.*ssm_state_step", text)) == 1
    _assert_held_uncopied(text, [(pool, "float32")])
    assert _hlo_type(pool[1:], "float32") not in text
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= math.prod(pool) * 4
    assert memory.temp_size_in_bytes < 1e6


def test_the_state_scan_kernel_compiles_at_jambas_window(one_chip,
                                                       monkeypatch):
    """Mosaic takes a 2,048-token window of Jamba2's widths as its inputs
    are stored: ONE custom call, no operand copied on its way in (the
    maps alone are made for it, [1, 128, 32, 16]: N down the sublanes), no
    loop over positions beside it, and nothing of [T, N, C] anywhere."""
    monkeypatch.setattr(pa, "_use_pallas", lambda: True)

    def abstract(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    t, states, width = 2048, 16, 5120
    assert ssm.scan_in_kernel(width, states, "float32")
    compiled = jax.jit(ssm.scan_window).lower(
        abstract((1, t, width)), abstract((1, t, width), jnp.bfloat16),
        abstract((1, t, states)), abstract((1, t, states)),
        abstract((states, width)), abstract((width,)),
        abstract((1, states, width))).compile()
    text = compiled.as_text()
    assert len(re.findall(r"tpu_custom_call.*ssm_state_scan", text)) == 1
    assert not re.findall(r" while\(", text)
    for shape, dtype in (([1, t, width], "float32"),
                         ([1, t, width], "bfloat16"),
                         ([1, states, width], "float32")):
        assert not re.findall(re.escape(_hlo_type(shape, dtype))
                              + r"\S* copy\(", text), (shape, dtype)
    assert compiled.memory_analysis().temp_size_in_bytes < 4e6


@pytest.mark.parametrize("label", ["chunk", "prefill_512"])
def test_jambas_prefill_programs_scan_in_one_kernel_a_run(
        one_chip, label, monkeypatch):
    """A whole-prompt program and the chunk program at the cell's
    geometry with the gate as the chip passes it: the kernel ONCE A RUN of
    Mamba layers (the layer scans of 13, 7 and 6 layers), and no loop
    over the window's positions: the loops left are the three runs of
    layers and, in the chunk program, the two attention layers' visits of
    their pages (the carried scan left three more, each a quarter of the
    window's positions long, a handful of small fusions in its body)."""
    monkeypatch.setattr(pa, "_use_pallas", lambda: True)
    programs = _programs_of(*MIXED["jamba"])[1]
    compiled = program_text.lower_bundle(
        program_text.bundles_of(programs)[label],
        len(programs.pool_specs), sharding=one_chip).compile()
    text = compiled.as_text()
    assert len(re.findall(r"tpu_custom_call.*ssm_state_scan", text)) == 3
    s_shape, _ = programs.pool_specs[2]
    pool = _hlo_type(s_shape, "float32")
    running = _hlo_type([1] + s_shape[2:], "float32")
    loops = [line for line in text.splitlines() if " while(" in line]
    # the three runs of layers carry the pool; no loop carries ONE state
    # from position to position (the carried scan's three did)
    assert sum(pool in line for line in loops) == 3
    assert not [line for line in loops if running in line]
    assert len(loops) == (5 if label == "chunk" else 3)
    _assert_held_uncopied(text, programs.pool_specs)
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 0.5e9


def test_jambas_decode_program_steps_its_states_in_one_kernel_a_run(
        one_chip, monkeypatch):
    """The decode program at the cell's geometry with the gate as the chip
    passes it: the kernel ONCE A RUN of Mamba layers (the layer scans of
    13, 7 and 6 layers between and around the two attention layers), all
    four pools aliased from the donated inputs to the outputs, and nothing
    that MAKES an array of the pool's or of a layer's slab's shape: no
    copy, no fusion, no update-slice, no select; the pool is a parameter,
    a loop's carry and the kernel's own result, and nothing else (PERF.md
    section 6, PRs 25 and 34: XLA has twice answered an aliased custom
    call in these two nested loops with a copy of the pool)."""
    monkeypatch.setattr(pa, "_use_pallas", lambda: True)
    programs = _programs_of(*MIXED["jamba"])[1]
    assert programs.decode["state_in_kernel"] and programs.decode["in_place"]
    compiled = program_text.lower_bundle(
        programs.decode, len(programs.pool_specs),
        sharding=one_chip).compile()
    text = compiled.as_text()
    assert len(re.findall(r"tpu_custom_call.*ssm_state_step", text)) == 3
    _assert_held_uncopied(text, programs.pool_specs)
    s_shape, _ = programs.pool_specs[2]
    assert s_shape == [26, 129, 16, 5120]
    _assert_the_state_pool_is_only_carried(text, s_shape)
    # the tails' taps are whole-tile slices of an entry as its pool stores
    # it: no [entries, 3, channels] view, which the chip stores in tiles of
    # 4 rows and copied an entry into and out of, a layer a step
    assert not re.findall(r"bf16\[(?:1,)?129,3,5120\]", text)
    memory = compiled.memory_analysis()
    pools = sum(math.prod(s) * (4 if dt == "float32" else 2)
                for s, dt in programs.pool_specs)
    assert memory.alias_size_in_bytes >= pools
    # beside its arguments: the 134 MB of float32 logits it returns and the
    # steps' rows; no slab (42 MB a layer was the jax.numpy step's)
    assert memory.temp_size_in_bytes < 0.2e9


# tests/program_text.py ``compiled_fingerprint`` of the delta cell's decode
# program (benchmark/configs/olmo-hybrid-7b.json at its own geometry), taken
# on PR 48's tree, the parent of PR 49, which moved the update of a layer's
# slab out of ``_state_step`` into each mixer's own ``step``
# (ops/delta_rule.py: the same slice, step, ``where`` and set; the StableHLO
# differs in the ORDER of the state's slice and the tail's slice and
# reshape, independent reads, and in nothing else: ``chip_fingerprint``
# 087b7b82ca75ef6f before, b803baf44dde286d after): what the chip's
# compiler leaves is the same module, instruction for instruction. PR 59
# gave the decode bundle two fetch sets over the one Program. With nothing
# else changed the PROBE form, the whole set, still compiled to that value
# (e7fcd32965a238a4) and the form the loop dispatches, which fetches no
# ``Logits`` and no ``Picks``, to 2b3021d94c20afa4: those two results went
# and nothing else. The values below were taken after the same PR's second
# change, one ``reduce_precision`` of a step's logits to bfloat16 before
# their argmax (``_paged_decode``), which is in both forms
DELTA_COMPILED = {"probe": "1580d25c5d040dc5", "serving": "969ce41a30244cbf"}


@pytest.mark.parametrize("form", sorted(DELTA_COMPILED))
def test_the_delta_cells_decode_program_compiles_to_what_it_did(
        one_chip, form, monkeypatch):
    monkeypatch.setattr(pa, "_use_pallas", lambda: True)
    programs = _programs_of("olmo-hybrid-7b.json", "serve_delta")[1]
    assert programs.decode["in_place"]
    assert not programs.decode["state_in_kernel"]
    assert programs.pool_specs[2] == ([12, 9, 30, 96, 192], "float32")
    got = program_text.compiled_fingerprint(program_text.lower_bundle(
        _decode_form(programs, form), len(programs.pool_specs),
        sharding=one_chip))
    assert got == DELTA_COMPILED[form], got


# -- a model whose stack is run several times a token (models/looped.py) ---

@pytest.fixture(scope="module")
def looped():
    """benchmark/configs/ouro-2.6b.json: 16 slots of 512 + 512 positions in
    pages of 16 over a pool of 300 pages, 192 cache layers deep, 4 steps."""
    with open(os.path.join(HERE, os.pardir, "benchmark", "configs",
                           "ouro-2.6b.json")) as f:
        config = json.load(f)
    e = config["builder"]["engine"]
    per_seq = -(-(e["prompt_buckets"][-1] + e["max_new_tokens"]
                  + e["decode_block"]) // e["page_size"])
    cfg = importlib.import_module(
        "benchmark.builders.serve_loop").model_config(config)
    return cfg, dict(
        max_batch=e["max_batch"], page_size=e["page_size"],
        n_pages=e["n_pages"], pages_per_seq=per_seq,
        prompt_buckets=tuple(e["prompt_buckets"]),
        decode_block=e["decode_block"])


def test_the_kernel_compiles_at_a_key_value_head_a_query_head(one_chip,
                                                              monkeypatch):
    """16 key/value heads of 128 with ONE query head each and a layer
    number up to 191: Mosaic takes the pools as they are stored."""
    monkeypatch.setattr(pa, "_use_pallas", lambda: True)
    def abstract(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = abstract((192, 300, 16, 16, 128))
    text = jax.jit(pa.paged_gqa_decode).lower(
        abstract((16, 16, 128)), pool, pool, abstract((), jnp.int32),
        abstract((16, 65), jnp.int32),
        abstract((16,), jnp.int32)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert not re.findall(r"bf16\[192,300,16,16,128\]\S* copy\(", text)


@pytest.mark.parametrize("label", ["decode", "prefill_512"])
def test_a_looped_program_holds_one_layer_body_and_copies_no_pool(
        one_chip, looped, label, monkeypatch):
    """The passes are a loop around the layers' loop: ONE kernel instance
    in the decode program for 192 calls a step, both pools (3.77 GB each)
    aliased from the donated inputs to the outputs through both loops and
    never copied, no buffer of a view of the layers' shape (26 GB: it
    could not exist for this model), and beside its arguments the
    program holds less than a third of one pool: the compiler's own
    re-laid copy of the q, k and v matrices (1.2 GB: PERF.md section 6,
    PR 43), not a pass's copy of the layers."""
    monkeypatch.setattr(pa, "_use_pallas", lambda: True)
    cfg, geometry = looped
    programs = cfg.build_paged_programs(**geometry)
    assert programs.decode["in_place"]
    assert programs.pool_specs == [
        ([192, 300, 16, 16, 128], "bfloat16")] * 2
    compiled = program_text.lower_bundle(
        program_text.bundles_of(programs)[label], 2,
        sharding=one_chip).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') \
        == (label == "decode")
    _assert_held_uncopied(text, programs.pool_specs)
    for view in ("192,16,1040,16,128", "16,1040,16,128",
                 "192,16,65,16,16,128"):
        assert f"bf16[{view}]" not in text, view
    pool_bytes = 192 * 300 * 16 * 16 * 128 * 2
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 2 * pool_bytes
    assert memory.temp_size_in_bytes < pool_bytes / 3
    # the layers' matrices are read where they lie, four times: no copy of
    # a SwiGLU matrix (1.1 GB each) anywhere
    assert not re.findall(r"bf16\[48,(?:2048,5632|5632,2048)\]\S* copy\(",
                          text)


# -- prefill attention through the kernel (prefill_fold; PR 44) -------------

# cell -> (its file under benchmark/configs, its builder, the float32
# [.., heads, 2,048 queries, a block of keys] arrays its chunk program
# holds where the fold is plain jax.numpy, kernel instances)
FOLDED = {
    "docs": ("xing4.0-29b-a4b.json", "serve_blocks",
             {"f32[1,32,2048,2048]", "f32[32,2048,2048]"}, 2),
    "mixed": ("mimo-v2-flash-ep16.json", "serve_hybrid",
              {"f32[1,4,16,2048,1024]"}, 2),
}


@pytest.mark.parametrize("cell", sorted(FOLDED))
def test_a_chunk_program_holds_no_score_block(one_chip, cell, monkeypatch):
    """The chunk program at the benchmark's shapes (2,048 queries over a
    row of 8,512 | 17,472 positions) with its fold in jax.numpy, then
    through the kernel: no float32 array with the 2,048-wide query axis
    AND a key-block axis is left anywhere in the module, the program's
    temporaries shrink (0.74 -> 0.45 GB docs, 0.75 -> 0.39 GB mixed), one
    kernel instance a fold (the leading layer's and the layer scan's; the
    two full layers', taken by number), no ``copy`` or ``transpose`` makes
    an operand of the kernel (the queries, the expanded or gathered keys
    and values and the carry reach it as their producers wrote them; a
    192-wide key block is padded to 256 a head, 4 MB a visit), and no
    pool is copied."""
    config_file, builder, scores, instances = FOLDED[cell]
    wide = r"f32\[(?:\d+,)+2048,(?:1024|2048)\]"
    temporaries = {}
    for in_kernel in (False, True):
        monkeypatch.setattr(pa, "_use_pallas", lambda: in_kernel)
        _, programs = _programs_of(config_file, builder)
        assert programs.chunk["attn_in_kernel"] is in_kernel
        compiled = program_text.lower_bundle(
            programs.chunk, len(programs.pool_specs),
            sharding=one_chip).compile()
        text = compiled.as_text()
        calls = re.findall(r"custom-call\(([^)]*)\).*prefill_fold", text)
        assert len(calls) == (instances if in_kernel else 0)
        assert set(re.findall(wide, text)) == (
            set() if in_kernel else scores)
        for operands in calls:
            for name in re.findall(r"%([\w.\-]+)", operands):
                made_by = re.search(
                    rf"%{re.escape(name)} = \S+ ([\w\-]+)\(", text)
                assert made_by and made_by.group(1) not in (
                    "copy", "transpose"), (name, made_by)
        _assert_held_uncopied(text, programs.pool_specs)
        # nor is the carry's first value a literal of the carry's size
        assert not re.search(r"f32\[[\d,]*2048,128\]\S* constant\(", text)
        temporaries[in_kernel] = \
            compiled.memory_analysis().temp_size_in_bytes
    assert temporaries[True] < 0.65 * temporaries[False]


# -- latent attention in place (paged_latent_decode; PR 45) -----------------

# cell -> (its file under benchmark/configs, its builder, rows, query
# heads, positions a row): docs' 16 rows of 133 pages of 64, reason's 64
# rows x 128 heads, whose queries and results (18.9 MB) pass the default
# VMEM limit
LATENT = {"docs": ("xing4.0-29b-a4b.json", "serve_blocks", 16, 32, 8512),
          "reason": ("deepseek-v3-ep16.json", "serve_share", 64, 128, 2112)}


@pytest.fixture(params=sorted(LATENT))
def latent(request, monkeypatch):
    """(a latent cell's rows, heads and ``kmax``, its programs), with the
    gate as the chip passes it while the test runs."""
    monkeypatch.setattr(pa, "_use_pallas", lambda: True)
    config_file, builder, *shape = LATENT[request.param]
    return shape, _programs_of(config_file, builder)[1]


def test_the_latent_kernel_compiles_at_the_cells_shapes(one_chip, latent):
    """Mosaic takes the one pool as it is stored, 640 wide, a block of it
    in VMEM twice beside the whole queries and results: no pool is re-laid
    or copied on its way into the call."""
    (rows, heads, kmax), programs = latent

    def abstract(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    (shape, _), = programs.pool_specs
    assert shape[2:] == [64, 640] and kmax == programs.pages_per_seq * 64
    text = jax.jit(functools.partial(
        pa.paged_latent_decode, scale=0.1, width=512)).lower(
        abstract((rows, heads, 640)), abstract(shape),
        abstract((), jnp.int32),
        abstract((rows, programs.pages_per_seq), jnp.int32),
        abstract((rows,), jnp.int32)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert f"bf16[{rows},{heads},512]" in text
    _assert_held_uncopied(text, programs.pool_specs)


def test_a_latent_decode_program_holds_no_view(one_chip, latent):
    """One kernel instance a layer body (the leading dense layer's and the
    layer scan's), the pool aliased from the donated input to the output
    and never copied, and no array with the view's ``kmax`` axis anywhere
    in the module: not the gathered view ``bf16[.., kmax, 640]`` (1.04 GB
    docs, 0.87 GB reason), not the float32 scores over every position."""
    (rows, heads, kmax), programs = latent
    assert programs.decode["in_place"]
    assert (programs.max_batch, programs.pages_per_seq * programs.page_size) \
        == (rows, kmax)
    compiled = program_text.lower_bundle(programs.decode, 1,
                                         sharding=one_chip).compile()
    text = compiled.as_text()
    assert len(re.findall(r"tpu_custom_call.*paged_latent_decode", text)) == 2
    # and ONE few-rows kernel, the routed layers' scan's: reason's share
    # (64 rows, 88 MB an expert in runs of 512) and docs' 64 experts whole
    # (16 rows) leave no grouped product in a decode step (PR 61)
    assert programs.decode["experts_in_kernel"]
    assert len(re.findall(r"tpu_custom_call.*moe_few_rows", text)) == 1
    assert "ragged" not in text
    _assert_held_uncopied(text, programs.pool_specs)
    views = re.findall(rf"\w+\[(?:\d+,)*(?:{kmax}|"
                       rf"{programs.pages_per_seq},{programs.page_size})"
                       r"(?:,\d+)*\]", text)
    assert not views, sorted(set(views))
    (shape, _), = programs.pool_specs
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= math.prod(shape) * 2
    # beside its arguments the program holds less than one view (the
    # dense form PR 45 replaced: 1.73 GB reason, 2.10 GB docs; 0.59 and
    # 0.14 since)
    assert memory.temp_size_in_bytes < shape[0] * rows * kmax * 640 * 2


# program_text.chip_fingerprint of the other decode programs at their
# configurations' own engines, as PR 44 recorded them (PERF.md section 6)
# and PR 45, which gave the schedule a third fold and one pool or two,
# left them; the two latent models' as PR 45 made them, taken on its tree
# before PR 46 merged the decode forms into one step. The two SHARES of an
# expert-parallel layer (mimo, deepseek) as PR 48 made them: their held
# pairs are summed back to their tokens in three exact bfloat16 passes
# (ops/moe.py; 599ecd3567fa65c8 and ea419789abe205cd before); xing4, which
# holds every expert, kept its text. Jamba2's as PR 49 made it: its 26 state
# layers step their entries through the kernel ``ssm_state_step``
# (9e718d24c1afa2e7 before); Olmo-Hybrid's taken on PR 49's tree, whose
# compiled module is PR 48's (DELTA_COMPILED, above). PR 59 made two
# changes to every one of them, taken one after the other on its tree. A
# decode bundle has two fetch sets over its one Program: the PROBE form (the
# whole set, the first of each pair below) kept the text it had (mimo
# e3ec26ac94aea7da, jamba 2e58799dbdc7d219, ouro 4862221ab083276b, xing4
# f9c8c1dbb68a814f, deepseek 7f3ae63f3374ef41, olmo b803baf44dde286d) and the
# form the loop dispatches (the second) lost its ``Logits`` and ``Picks``
# results and nothing else (66f567f60fbba837, 8c6bf1ef9f1acf13,
# f5b2bb1bc7704ea6, 983ff2884a43e182, e1c6f34ad4c863c6, da2370972a93574b).
# Then a step's bfloat16 logits are rounded in so many words before their
# argmax (one ``reduce_precision`` in ``_paged_decode``, in both forms, so
# that both break ties alike on the chip: PERF.md section 6; mimo
# 709778c50a5d8e3e | cd3c6d2fbdbf9b5c, xing4 5eba9bd50298ccc5 |
# d847159f6ff1abbf, deepseek 1fce1d82787bf467 | bc5a4bc7c4822632). PR 61
# put the decode step's routed experts of those three through the kernel
# ``moe_few_rows`` (a share's, and experts wider than VMEM's default twice
# over): their sort, three ``ragged_dot`` and un-sort left the text, the
# other three kept theirs: the values below
OTHERS_PINNED = {"mimo": ("08727de06028a35d", "472ac0e9409ef1ab"),
                 "jamba": ("f93ae67235d28a87", "62afaf6af85d580f"),
                 "ouro": ("bec3361482f58ada", "ce139c92c00c35fb"),
                 "xing4": ("fe1152bd40131699", "24ba454eed626407"),
                 "deepseek": ("86cfcb5f7edc1737", "502a46a8d63a9ea6"),
                 "olmo": ("ec1662f42fb73b82", "7602b73ab9aba4af")}


@pytest.mark.parametrize("form", ["probe", "serving"])
@pytest.mark.parametrize("model", sorted(OTHERS_PINNED))
def test_the_other_decode_programs_are_what_the_chip_was_asked_before(
        one_chip, model, form, looped, monkeypatch):
    monkeypatch.setattr(pa, "_use_pallas", lambda: True)
    if model == "ouro":
        cfg, geometry = looped
        programs = cfg.build_paged_programs(**geometry)
    else:
        programs = _programs_of(*dict(
            MIXED, xing4=LATENT["docs"][:2], deepseek=LATENT["reason"][:2],
            olmo=("olmo-hybrid-7b.json", "serve_delta"))[model])[1]
    got = program_text.chip_fingerprint(program_text.lower_bundle(
        _decode_form(programs, form), len(programs.pool_specs),
        sharding=one_chip))
    assert got == OTHERS_PINNED[model][form == "serving"], (model, got)


def _few_rows_call(jaxpr):
    """(the grid, each operand's block sizes) of the ONE ``moe_few_rows``
    call in ``jaxpr``'s text."""
    assert len(re.findall(r"name=moe_few_rows", jaxpr)) == 1
    grid, = re.findall(r"GridMapping\(grid=\(([\d, ]*)\)", jaxpr)
    blocks = [tuple(int(n) for n in re.findall(r"block_size=(\d+)", b))
              for b in re.findall(r"BlockMapping\(block_shape=\((.*?)\)\)",
                                  jaxpr)]
    return tuple(int(n) for n in re.findall(r"\d+", grid)), blocks


def test_the_few_rows_kernel_compiles_at_lagunas_experts(one_chip,
                                                         monkeypatch):
    """A decode step's experts at Laguna-XS.2's window stack (3 layers x
    256 experts of 2,048 x 512, 64 rows x 8 picks): ONE custom call, the
    expert stacks taken as they are stored (a block is one expert's
    matrix), nothing of a stack's size beside them, and the call PR 55
    measured: a grid of the 256 experts, whole ``[2048, 512]`` blocks. A
    share of such a layer and xing4's experts (3,584 x 1,024: 44 MB twice
    over, whole under a raised limit) are the kernel's too since PR 61."""
    from paddle_tpu.ops import moe
    monkeypatch.setattr(pa, "_use_pallas", lambda: True)

    def abstract(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    up, down = abstract((3, 256, 2048, 512)), abstract((3, 256, 512, 2048))
    assert moe.few_rows_usable(64, up, down)
    assert not moe.few_rows_usable(2048, up, down)
    assert moe.few_rows_usable(64, up, down, held=(0, 256))
    assert moe.few_rows_usable(16, abstract((5, 64, 3584, 1024)),
                               abstract((5, 64, 1024, 3584)))

    def step(x, idx, gates, wg, wu, wd, layer):
        return moe.moe_apply_sorted(x, idx, gates, wg, wu, wd, layer=layer)

    args = (abstract((64, 2048)), abstract((64, 8), jnp.int32),
            abstract((64, 8), jnp.float32), up, up, down,
            abstract((), jnp.int32))
    assert _few_rows_call(str(jax.make_jaxpr(step)(*args))) == (
        (256,), [(64, 2048), (64, 1), (2048, 512), (2048, 512),
                 (512, 2048), (64, 2048)])
    compiled = jax.jit(step).lower(*args).compile()
    text = compiled.as_text()
    assert len(re.findall(r"tpu_custom_call.*moe_few_rows", text)) == 1
    assert "ragged" not in text
    _assert_held_uncopied(text, [([3, 256, 2048, 512], "bfloat16"),
                                 ([3, 256, 512, 2048], "bfloat16")])
    assert compiled.memory_analysis().temp_size_in_bytes < 4e6


# a decode step's held experts at the three cells PR 61 admitted: (rows,
# picks, the stack [layers, experts held, model width, hidden width],
# ``held``, the grid, the gate and up block, the down block)
FEW_ROWS_CELLS = {
    # DeepSeek-V3's share: 88 MB an expert, 512 of its hidden width a step
    "reason": (64, 8, (4, 16, 7168, 2048), (0, 256), (16, 4),
               (7168, 512), (512, 7168)),
    # MiMo-V2-Flash's share: 50 MB an expert, halves of 1,024
    "mixed": (24, 8, (4, 16, 4096, 2048), (16, 256), (16, 2),
              (4096, 1024), (1024, 4096)),
    # xing4's, all held: 22 MB an expert, whole under a raised limit
    "docs": (16, 4, (5, 64, 3584, 1024), None, (64,),
             (3584, 1024), (1024, 3584)),
}


@pytest.mark.parametrize("cell", sorted(FEW_ROWS_CELLS))
def test_the_few_rows_kernel_compiles_at_a_share_and_at_wide_experts(
        one_chip, cell, monkeypatch):
    """Mosaic takes the kernel at the published widths: ONE custom call, no
    ``ragged_dot``, the stacks as they are stored, an expert cut where two
    of it pass ``GROUPED_VMEM`` (an inner grid axis over runs of the hidden
    width: the tile is read off the shapes), and nothing of a stack's size
    beside the arguments."""
    from paddle_tpu.ops import moe
    monkeypatch.setattr(pa, "_use_pallas", lambda: True)
    rows, picks, (layers, e, d, f), held, grid, gate_up, down_block = \
        FEW_ROWS_CELLS[cell]

    def abstract(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    up, down = abstract((layers, e, d, f)), abstract((layers, e, f, d))
    assert moe.few_rows_usable(rows, up, down, held)
    assert not moe.few_rows_usable(moe.FEW_ROWS + 1, up, down, held)

    def step(x, idx, gates, wg, wu, wd, layer):
        return moe.moe_apply_sorted(x, idx, gates, wg, wu, wd, layer=layer,
                                    held=held)

    args = (abstract((rows, d)), abstract((rows, picks), jnp.int32),
            abstract((rows, picks), jnp.float32), up, up, down,
            abstract((), jnp.int32))
    padded = -(-rows // 16) * 16
    assert _few_rows_call(str(jax.make_jaxpr(step)(*args))) == (
        grid, [(padded, d), (padded, 1), gate_up, gate_up, down_block,
               (padded, d)])
    compiled = jax.jit(step).lower(*args).compile()
    text = compiled.as_text()
    assert len(re.findall(r"tpu_custom_call.*moe_few_rows", text)) == 1
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "ragged" not in text
    _assert_held_uncopied(text, [([layers, e, d, f], "bfloat16"),
                                 ([layers, e, f, d], "bfloat16")])
    assert compiled.memory_analysis().temp_size_in_bytes < 4e6


def test_the_grouped_rows_kernel_compiles_at_lagunas_window(one_chip,
                                                            monkeypatch):
    """A prefill window's experts at Laguna-XS.2's window stack (2,048
    tokens x 8 picks over 3 layers x 256 experts of 2,048 x 512): ONE
    custom call, ``moe_grouped_rows``, in place of three ``ragged_dot``,
    the expert stacks taken as they are stored, no more held beside the
    arguments than the sorted form holds (both peak at the float32
    ``[16384, 2048]`` result and its un-sorted copy, 268 MB). A decode
    step's 64 rows still compile to
    ``moe_few_rows``, a share's WINDOW to the kernel as a whole layer's
    does, and a share of experts over the budget to ``ragged_dot``; xing4's
    window (2,048 tokens x 4 picks over 64 experts of 3,584 x 1,024, 44 MB
    twice over) compiles to the kernel too, whole experts under a raised
    VMEM limit: the gate's budget admits it since the probe read it at
    half the three ``ragged_dot``'s time (PERF.md section 6, PR 56), and
    its decode step (16 rows) is the few-rows kernel's since PR 61."""
    from paddle_tpu.ops import moe
    monkeypatch.setattr(pa, "_use_pallas", lambda: True)

    def abstract(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def compiled(tokens, picks, up, down, held=None):
        return jax.jit(
            lambda x, idx, gates, wg, wu, wd, layer: moe.moe_apply_sorted(
                x, idx, gates, wg, wu, wd, layer=layer, held=held)).lower(
            abstract((tokens, up.shape[2])),
            abstract((tokens, picks), jnp.int32),
            abstract((tokens, picks), jnp.float32), up, up, down,
            abstract((), jnp.int32)).compile()

    up, down = abstract((3, 256, 2048, 512)), abstract((3, 256, 512, 2048))
    stacks = [([3, 256, 2048, 512], "bfloat16"),
              ([3, 256, 512, 2048], "bfloat16")]
    assert moe.grouped_rows_usable(2048, up, down)
    window = compiled(2048, 8, up, down)
    text = window.as_text()
    grouped = r"tpu_custom_call.*moe_grouped_rows"
    assert len(re.findall(grouped, text)) == 1
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "ragged" not in text and "moe_few_rows" not in text
    _assert_held_uncopied(text, stacks)
    with monkeypatch.context() as m:
        m.setattr(moe, "grouped_rows_usable", lambda *a, **k: False)
        parent = compiled(2048, 8, up, down)
    assert "ragged" in parent.as_text()
    assert window.memory_analysis().temp_size_in_bytes \
        <= parent.memory_analysis().temp_size_in_bytes

    step = compiled(64, 8, up, down).as_text()
    assert len(re.findall(r"tpu_custom_call.*moe_few_rows", step)) == 1
    assert not re.findall(grouped, step)
    # a SHARE of such experts is the kernel's too (PR 63): a quarter of
    # the router, every sorted row back in token order, ONE call; a
    # sixteenth, the leading rows or all of them, a call each side of the
    # cond; a share of experts over the budget (DeepSeek-V3's) keeps
    # ``ragged_dot``
    quarter = compiled(2048, 8, up, down, held=(0, 1024)).as_text()
    assert len(re.findall(grouped, quarter)) == 1 and "ragged" not in quarter
    sixteenth = compiled(2048, 8, up, down, held=(256, 4096)).as_text()
    assert len(re.findall(grouped, sixteenth)) == 2
    assert "ragged" not in sixteenth
    share = compiled(2048, 8, abstract((4, 16, 7168, 2048)),
                     abstract((4, 16, 2048, 7168)), held=(0, 256)).as_text()
    assert "ragged" in share and not re.findall(grouped, share)

    xing4 = (abstract((5, 64, 3584, 1024)), abstract((5, 64, 1024, 3584)))
    assert moe.grouped_rows_usable(2048, *xing4)
    docs = compiled(2048, 4, *xing4).as_text()
    assert len(re.findall(grouped, docs)) == 1 and "ragged" not in docs
    _assert_held_uncopied(docs, [([5, 64, 3584, 1024], "bfloat16"),
                                 ([5, 64, 1024, 3584], "bfloat16")])
    docs_step = compiled(16, 4, *xing4).as_text()
    assert "ragged" not in docs_step and not re.findall(grouped, docs_step)
    assert len(re.findall(r"tpu_custom_call.*moe_few_rows", docs_step)) == 1


# -- a model of gated short convolutions (models/hybrid_conv_moe.py) -------

@pytest.fixture(scope="module")
def lfm2():
    """benchmark/configs/lfm2-24b-a2b.json: 256 slots of 4,096 + 1,536
    positions in pages of 64, heads of 64, 64 experts of 2,048 x 1,536 a
    routed layer, chunks of 2,048, 4 steps: (its configuration, the
    programs)."""
    with pytest.MonkeyPatch.context() as m:
        m.setattr(pa, "_use_pallas", lambda: True)
        return _programs_of("lfm2-24b-a2b.json", "serve_hybrid_conv")


def test_the_packed_kernel_compiles_at_lfm2s_shapes(one_chip, lfm2,
                                                    monkeypatch):
    """Mosaic takes the pools as they are stored, keys and values 512 wide
    (eight heads of 64: HALF a lane tile a value head, which the flat
    form's gate refuses), 32 query heads over 256 rows: ONE custom call,
    ``paged_flat_packed_decode``, no pool re-laid on its way into it."""
    monkeypatch.setattr(pa, "_use_pallas", lambda: True)
    cfg, programs = lfm2

    def abstract(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    (k_shape, _), (v_shape, _) = programs.pool_specs[:2]
    assert k_shape == v_shape == [1, 22785, 64, 512]
    assert pa.paged_packed_usable(k_shape, v_shape, cfg.n_kv)
    assert not pa.paged_flat_usable(k_shape, v_shape, cfg.n_kv)
    rows = programs.max_batch
    text = jax.jit(pa.paged_flat_decode).lower(
        abstract((rows, cfg.n_heads, cfg.head_dim)), abstract(k_shape),
        abstract(v_shape), abstract((), jnp.int32),
        abstract((rows, programs.pages_per_seq), jnp.int32),
        abstract((rows,), jnp.int32)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert len(re.findall(r"tpu_custom_call.*paged_flat_packed_decode",
                          text)) == 1
    _assert_held_uncopied(text, programs.pool_specs[:2])


@pytest.mark.parametrize("label", ["decode", "prefill_512", "chunk"])
def test_lfm2s_programs_hold_their_kernels_and_copy_no_page(
        one_chip, lfm2, label, monkeypatch):
    """The decode program at 256 rows: ONE instance of the packed paged
    kernel (one attention layer of five) and the routed layers' sorted
    pairs through ``moe_grouped_rows`` (the attention layer's by its own
    number, the conv layers' inside their scan: two instances), no
    ``ragged_dot`` and no few-rows kernel (256 rows are two MXU tiles); the
    prefill programs the same two instances of the grouped kernel and
    their attention in plain XLA. The pages are aliased from the donated
    inputs and never copied; the experts' stacks are taken as stored."""
    monkeypatch.setattr(pa, "_use_pallas", lambda: True)
    _, programs = lfm2
    assert programs.decode["in_place"]
    assert not programs.decode["state_in_kernel"]
    assert programs.chunk["experts_in_kernel"]
    assert not programs.chunk["attn_in_kernel"]
    compiled = program_text.lower_bundle(
        program_text.bundles_of(programs)[label],
        len(programs.pool_specs), sharding=one_chip).compile()
    text = compiled.as_text()
    assert len(re.findall(r"tpu_custom_call.*paged_flat_packed_decode",
                          text)) == (label == "decode")
    assert len(re.findall(r"tpu_custom_call.*moe_grouped_rows", text)) == 2
    assert "ragged" not in text and "moe_few_rows" not in text
    assert "prefill_fold" not in text
    _assert_held_uncopied(text, programs.pool_specs[:2] + [
        ([3, 64, 2048, 1536], "bfloat16"), ([3, 64, 1536, 2048], "bfloat16"),
        ([1, 64, 2048, 1536], "bfloat16")])
    memory = compiled.memory_analysis()
    pages = 2 * math.prod(programs.pool_specs[0][0]) * 2
    assert memory.alias_size_in_bytes >= pages
    assert memory.temp_size_in_bytes < 0.7e9


# -- the two forms of a block-kind model's decode program (PR 59) ----------

def test_lfm2s_128_token_bucket_goes_through_the_few_rows_kernel(
        one_chip, lfm2, monkeypatch):
    """The one window of 128 rows or fewer that a cell's programs hold:
    LFM2's smallest whole-prompt bucket. Its 512 pairs over 64 experts of
    2,048 x 1,536 (37.7 MB twice over: whole under a raised limit) were
    three ``ragged_dot`` a routed layer until PR 61, the boundary both
    gates shared; they are the few-rows kernel's now, an instance a layer
    body, and the grouped kernel keeps every larger bucket."""
    monkeypatch.setattr(pa, "_use_pallas", lambda: True)
    _, programs = lfm2
    assert min(programs.prefill) == 128
    assert not programs.prefill[128]["experts_in_kernel"]   # the grouped one
    text = program_text.lower_bundle(
        program_text.bundles_of(programs)["prefill_128"],
        len(programs.pool_specs), sharding=one_chip).compile().as_text()
    assert len(re.findall(r"tpu_custom_call.*moe_few_rows", text)) == 2
    assert "ragged" not in text and "moe_grouped_rows" not in text


def _step_logits(text, steps, rows, vocab):
    """(the float32 arrays of ``text`` whose dimensions are steps, rows
    and vocabulary in any order, its dynamic-update-slices of vocabulary
    width)."""
    stacked = [m.group(0) for m in re.finditer(r"f32\[([\d,]+)\]", text)
               if sorted(map(int, m.group(1).split(",")))
               == sorted((steps, rows, vocab))]
    updates = [m.group(0) for m in re.finditer(
        r"= \w+\[([\d,]*)\][^ ]* dynamic-update-slice\(", text)
        if str(vocab) in m.group(1).split(",")]
    return stacked, updates


@pytest.mark.parametrize("model", ["jamba", "lfm2"])
def test_the_serving_decode_program_holds_no_step_logits(one_chip, model,
                                                         request):
    """The decode program the loop dispatches, at the cell's shapes (LFM2:
    4 steps of 256 rows over 65,536 words; Jamba2: of 128 rows): the
    chip's compiler leaves no float32 array of steps x rows x vocabulary
    and no dynamic-update-slice of vocabulary width, because nothing
    fetches the scan's stacked logits (as a scan output their layout puts
    the steps in every tile's sublanes, and a step's write goes through
    the whole 268 MB: PERF.md section 6, PR 59). The probe form, the same
    Program with its whole fetch set, holds both."""
    programs = request.getfixturevalue(model)
    programs = programs[1] if model == "lfm2" else programs
    steps, rows, vocab = 4, programs.max_batch, 65536
    assert rows == {"jamba": 128, "lfm2": 256}[model]
    decode = programs.decode
    whole = [v.name for v in decode["probe"]["fetch"]]
    assert [v.name for v in decode["fetch"]] == whole[:-3] + whole[-1:]

    def compiled(bundle):
        return program_text.lower_bundle(
            bundle, len(programs.pool_specs), sharding=one_chip).compile()

    probe = compiled(program_text.probe_form(decode))
    stacked, updates = _step_logits(probe.as_text(), steps, rows, vocab)
    assert stacked and updates
    serving = compiled(decode)
    assert _step_logits(serving.as_text(), steps, rows, vocab) == ([], [])
    # where the argmax is fused into the head the product's rounding to
    # bfloat16 is the program's own word, not the compiler's choice
    assert re.search(rf"f32\[{rows},{vocab}\][^ ]* reduce-precision\(.*"
                     r"exponent_bits=8, mantissa_bits=7", serving.as_text())
    assert probe.memory_analysis().output_size_in_bytes \
        - serving.memory_analysis().output_size_in_bytes \
        >= steps * rows * vocab * 4


# -- a latent kind beside a state kind in one stack (PR 62) ----------------

@pytest.fixture(scope="module")
def ling():
    """benchmark/configs/ling-3.0-flash-ep4.json: 256 slots, ONE latent
    layer's 16,384 pages of 64 positions stored 640 wide beside five kda
    layers' float32 states [257, 32, 128, 128] and tails, 128 held experts
    of 2,560 x 768 a routed layer, chunks of 2,048, 4 steps: (its
    configuration, the programs)."""
    with pytest.MonkeyPatch.context() as m:
        m.setattr(pa, "_use_pallas", lambda: True)
        return _programs_of("ling-3.0-flash-ep4.json", "serve_kda_latent")


@pytest.mark.parametrize("label", ["decode", "chunk"])
def test_lings_programs_hold_the_latent_kernels_and_copy_no_pool(
        one_chip, ling, label, monkeypatch):
    """The latent KIND's one layer among five kda layers goes through the
    kernels a whole-stack latent model has, ONE instance each: the decode
    program ``paged_latent_decode`` against the pool itself, the chunk
    program ``prefill_fold``. The state pool, 2.7 GB of float32 that every
    layer's step (or window) writes where it lies, is aliased from the
    donated input and never copied, nor are the latent pages and the
    tails; the held experts' sorted pairs go through ``moe_grouped_rows``,
    ONE call a body of routed layers, at the step's 256 rows as in the
    chunk's 2,048 (a share of experts that fit the kernel's budget is
    admitted as a whole layer is, PR 63), and no ``ragged_dot`` is left.
    The decode program steps a kda layer's states through ONE kernel,
    ``delta_state_step`` (PR 64), once a run of kda layers (the leading
    dense layer, the scan of layers 2-4, layer 6), the pool aliased into
    and out of it: nothing MAKES an array of the pool's or of a layer's
    slab's shape, no copy, no fusion, no update-slice, no select (the
    jax.numpy step's two reductions and select-update were three reads and
    a write of 0.54 GB a layer-step, half the program); the chunk program
    holds no such call (its windows are ``chunk_rule``'s)."""
    monkeypatch.setattr(pa, "_use_pallas", lambda: True)
    _, programs = ling
    assert programs.pool_specs == [
        ([1, 16384, 64, 640], "bfloat16"),
        ([5, 257, 32, 128, 128], "float32"), ([5, 257, 36864], "bfloat16")]
    assert programs.decode["in_place"] and programs.chunk["attn_in_kernel"]
    assert programs.decode["state_in_kernel"]
    assert programs.decode["experts_in_kernel"]
    assert programs.chunk["experts_in_kernel"]
    compiled = program_text.lower_bundle(
        program_text.bundles_of(programs)[label],
        len(programs.pool_specs), sharding=one_chip).compile()
    text = compiled.as_text()
    kernel = {"decode": "paged_latent_decode", "chunk": "prefill_fold"}
    for name in kernel.values():
        assert len(re.findall(rf"tpu_custom_call.*{name}", text)) \
            == (name == kernel[label]), name
    assert "ragged" not in text
    assert not re.findall(r"tpu_custom_call.*moe_few_rows", text)
    # the routed layers are three runs of the stack, a body each: the kda
    # layers 2-4, the latent layer 5, the kda layer 6
    assert len(re.findall(r"tpu_custom_call.*moe_grouped_rows", text)) == 3
    assert len(re.findall(r"tpu_custom_call.*delta_state_step", text)) \
        == (3 if label == "decode" else 0)
    _assert_held_uncopied(text, programs.pool_specs)
    if label == "decode":
        _assert_the_state_pool_is_only_carried(text, programs.pool_specs[1][0])
    memory = compiled.memory_analysis()
    pools = sum(math.prod(shape) * jnp.dtype(dt).itemsize
                for shape, dt in programs.pool_specs)
    assert memory.alias_size_in_bytes >= pools
    assert memory.temp_size_in_bytes < {"decode": 0.3e9, "chunk": 3e9}[label]
