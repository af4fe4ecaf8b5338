"""Executor.compiled_stats per-kernel attribution (round-4 addition).

The reference profiler names which ops a step spends its time on via a
runtime chrome-trace timeline (reference
python/paddle/fluid/profiler.py:221, paddle/fluid/platform/profiler.cc);
under whole-program XLA the optimized module IS the schedule, so
compiled_stats walks the entry computation instead and attributes
kernels by opcode (fusions labeled with their fused root op).
"""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core.executor import (_entry_kernels, _kernel_histogram,
                                      _shape_bytes, _split_shape_opcode)


def _small_train_stats(top_k=10):
    main_p, startup_p = fluid.Program(), fluid.Program()
    with fluid.program_guard(main_p, startup_p):
        x = fluid.layers.data(name="x", shape=[64], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="int64")
        h = fluid.layers.fc(x, size=32, act="relu")
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            fluid.layers.fc(h, size=10), y))
        fluid.optimizer.Adam(learning_rate=0.01).minimize(loss)
    exe = fluid.Executor()
    exe.run(startup_p)
    feed = {"x": np.zeros((4, 64), np.float32),
            "y": np.zeros((4, 1), np.int64)}
    return exe.compiled_stats(main_p, feed=feed, fetch_list=[loss],
                              top_k=top_k)


def test_histogram_attributes_every_kernel():
    st = _small_train_stats()
    assert st["n_kernels"] > 0
    hist = st["kernel_histogram"]
    # every counted kernel lands in exactly one histogram bucket
    assert sum(h["count"] for h in hist) == st["n_kernels"]
    kinds = {h["kind"] for h in hist}
    # a trained fc stack must show MXU work and optimizer fusions
    assert any(k == "dot" or k.startswith("fusion") for k in kinds)
    # sorted by total estimated bytes, descending
    mb = [h["mbytes"] for h in hist]
    assert mb == sorted(mb, reverse=True)


def test_top_kernels_shape_and_order():
    st = _small_train_stats(top_k=5)
    top = st["top_kernels"]
    assert 0 < len(top) <= 5
    for k in top:
        assert set(k) == {"kind", "shape", "mbytes"}
        assert "[" in k["shape"]          # an HLO array/tuple shape
    mb = [k["mbytes"] for k in top]
    assert mb == sorted(mb, reverse=True)


def test_top_k_zero_disables_attribution():
    st = _small_train_stats(top_k=0)
    assert st["n_kernels"] > 0
    assert "kernel_histogram" not in st
    assert "top_kernels" not in st


def test_shape_bytes():
    assert _shape_bytes("f32[128]{0}") == 512
    assert _shape_bytes("bf16[2,3]{1,0}") == 12
    assert _shape_bytes("(f32[4]{0}, s8[8]{0})") == 24
    assert _shape_bytes("pred[]") == 1          # scalar = one element
    assert _shape_bytes("token[]") == 0         # unknown dtype ignored


def test_split_shape_opcode():
    s, op, args = _split_shape_opcode(
        "f32[8,16]{1,0} dot(%a, %b), contracting_dims={1}")
    assert (s, op) == ("f32[8,16]{1,0}", "dot")
    assert args.startswith("(%a, %b)")
    s, op, _ = _split_shape_opcode(
        "(f32[2]{0}, s32[]) while(%init), condition=%c, body=%b")
    assert s == "(f32[2]{0}, s32[])"
    assert op == "while"


def test_entry_kernels_labels_fusion_roots():
    hlo = """HloModule m

%fused_add (p0: f32[4], p1: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  %p1 = f32[4]{0} parameter(1)
  ROOT %r = f32[4]{0} add(%p0, %p1)
}

ENTRY %main (a: f32[4], b: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %b = f32[4]{0} parameter(1)
  %f = f32[4]{0} fusion(%a, %b), kind=kLoop, calls=%fused_add
  ROOT %c = f32[4]{0} copy(%f)
}
"""
    kernels = _entry_kernels(hlo)
    kinds = [k for k, _, _ in kernels]
    assert kinds == ["fusion(add)", "copy"]
    # fusion bytes: 16B out + 16B per operand
    assert kernels[0][2] == 48
    hist = _kernel_histogram(kernels)
    assert hist[0]["count"] == 1


def test_operand_bytes_ignore_metadata_attributes():
    # metadata strings carry tokens (op names, file paths) that collide
    # with real entry instruction names; only the operand list counts
    hlo = """HloModule m

ENTRY %main (p: f32[1024]) -> f32[1024] {
  %p = f32[1024]{0} parameter(0)
  %add = f32[1024]{0} add(%p, %p), metadata={op_name="jit(f)/add" source_file="/home/u/add.py"}
  ROOT %exp = f32[1024]{0} exponential(%add), metadata={op_name="jit(f)/exp (add)" source_file="/x/add.py"}
}
"""
    kernels = _entry_kernels(hlo)
    assert [(k, b) for k, _, b in kernels] == [
        ("add", 4096 * 3),          # out + two %p operands
        ("exponential", 4096 * 2),  # out + %add only, not metadata hits
    ]
