"""trace_reduce on a small recorded trace and on hand-made events, and
work.py's shape functions against figures known from the literature."""
import json
import os

import pytest

from benchmark import trace_reduce as T
from benchmark import work

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "data", "trace_small.json")) as f:
        doc = json.load(f)
    return [{"plane": doc["planes"][p], "line": doc["lines"][ln],
             "name": name, "start_ns": s, "dur_ns": d}
            for p, ln, name, s, d in doc["events"]]


def ev(line, name, start, dur, plane="/device:TPU:0"):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": float(start), "dur_ns": float(dur)}


def test_recorded_trace_busy_window_and_programs(recorded):
    r = T.reduce_events(recorded, window_s=0.016, n_devices=1)
    assert r["n_devices"] == 1
    # one decode program's first 9.7 ms, the device busy throughout
    assert r["window_s"] == pytest.approx(0.0096994, rel=1e-4)
    assert r["busy_s"] == pytest.approx(r["window_s"], rel=1e-4)
    assert r["host_window_s"] == 0.016
    assert r["collectives_s"] == 0.0
    # two module events, the first and the last of the line: either may
    # have been cut by the trace's start or stop, so neither is counted
    assert r["programs"] == {}


def test_recorded_trace_self_times_do_not_count_a_loop_twice(recorded):
    r = T.reduce_events(recorded, window_s=0.016, n_devices=1)
    ops = r["ops"]
    own = sum(v[0] for v in ops.values())
    assert own == pytest.approx(r["busy_s"], rel=1e-3)
    loop = next(k for k in ops if k.startswith("while.30"))
    body = next(k for k in ops
                if k.startswith("bitcast_dynamic-update-slice_fusion.9"))
    assert ops[body][1] == 784                 # one a loop iteration
    assert ops[loop][0] < 1e-4 < ops[body][0]  # the time is the body's
    top = T.breakdown(r)["device_ops"]
    assert len(top) == 10 and top[0][0].startswith(
        "bitcast_dynamic-update-slice_fusion.9_bf16_784_32_16_8_128")
    assert all(set(name) <= set("abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMN"
                                "OPQRSTUVWXYZ0123456789_.-")
               and len(name) <= 64 for name, _ in top)


def test_names_and_shapes():
    name = ("%copy.111 = bf16[32,16,784,8,128]{4,3,2,1,0:T(8,128)(2,1)} "
            "copy(bf16[32,16,784,8,128]{4,2,3,1,0} %x)")
    assert T.op_key(name) == "copy.111"
    assert T.op_shape(name) == "bf16[32,16,784,8,128]"
    tup = "%fusion.214 = (f32[16]{0:T(128)S(1)}, bf16[16,14336]{1,0}) fusion(%a)"
    assert T.op_shape(tup) == "(f32[16], bf16[16,14336])"
    assert T.op_key("jit_stepped(152075)") == "jit_stepped(152075)"
    assert T.op_shape("fusion.3") == ""


def test_busy_is_a_union_and_idle_gaps_are_named_by_what_covered_them():
    events = [
        ev(T.MODULES_LINE, "jit_stepped(1)", 0, 100),      # first: left out
        ev(T.MODULES_LINE, "jit_stepped(1)", 200, 300),
        ev(T.MODULES_LINE, "jit_stepped(2)", 600, 200),
        ev(T.MODULES_LINE, "jit_stepped(1)", 900, 100),    # last: left out
        ev(T.OPS_LINE, "%a = f32[4] fusion()", 0, 100),
        ev(T.OPS_LINE, "%while.1 = (s32[]) while()", 200, 250),
        ev(T.OPS_LINE, "%b = f32[4] fusion()", 210, 100),   # nested
        ev(T.OPS_LINE, "%b = f32[4] fusion()", 320, 100),   # nested
        ev(T.OPS_LINE, "%c = f32[4] fusion()", 460, 40),    # gap 450-460
        ev(T.OPS_LINE, "%all-reduce.7 = f32[4] all-reduce()", 600, 50),
        ev(T.ASYNC_LINE, "%all-gather-start.2 = f32[8] all-gather-start()",
           640, 60),
        ev(T.OPS_LINE, "%d = f32[4] fusion()", 700, 100),
        ev(T.OPS_LINE, "%e = f32[4] fusion()", 900, 100),
        ev("python3", "bench:wait", 480, 200, plane="/host:CPU"),
        ev("python3", "something else", 0, 1000, plane="/host:CPU"),
    ]
    r = T.reduce_events(events, window_s=1e-6, n_devices=1)
    assert r["window_s"] == pytest.approx(1000e-9)
    # 0-100, 200-450, 460-500, 600-650, 700-800, 900-1000
    assert r["busy_s"] == pytest.approx(640e-9)
    assert r["ops"]["while.1 (s32[])"][0] == pytest.approx(50e-9)
    assert r["ops"]["b f32[4]"] == [pytest.approx(200e-9), 2]
    assert r["programs"] == {
        "jit_stepped(1)": {"seconds": pytest.approx(300e-9), "count": 1},
        "jit_stepped(2)": {"seconds": pytest.approx(200e-9), "count": 1}}
    assert r["collectives_s"] == pytest.approx(100e-9)   # 600-700, union
    g = r["gaps"]
    assert g["inside_programs"]["all"] == pytest.approx(60e-9)   # 10 + 50
    assert g["wait"]["all"] == pytest.approx(100e-9)             # 500-600
    assert g["between_dispatches"] == {
        "all": pytest.approx(200e-9), "longest": pytest.approx(100e-9)}
    b = T.breakdown(r)
    assert ["between_dispatches__all_gaps", pytest.approx(200e-9)] \
        in b["idle_gaps"]


def test_busy_is_averaged_over_devices_and_no_device_gives_none():
    events = [ev(T.OPS_LINE, "%a = f32[4] fusion()", 0, 100),
              ev(T.OPS_LINE, "%a = f32[4] fusion()", 0, 50,
                 plane="/device:TPU:1"),
              ev(T.OPS_LINE, "%a = f32[4] fusion()", 150, 50,
                 plane="/device:TPU:1")]
    r = T.reduce_events(events, 1e-6, 2)
    assert r["n_devices"] == 2
    assert r["busy_s"] == pytest.approx(100e-9)
    assert r["window_s"] == pytest.approx(200e-9)
    assert r["ops"]["a f32[4]"] == [pytest.approx(100e-9), 3]
    host_only = [ev("python3", "bench:wait", 0, 10, plane="/host:CPU")]
    assert T.reduce_events(host_only, 1e-6, 1) is None


def test_read_xplane_reads_a_trace_this_process_records(tmp_path):
    import glob
    import jax
    import jax.numpy as jnp
    from benchmark.tracing import Tracer, span
    tracer = Tracer(True)
    with tracer.around():
        with span("dispatch"):
            jnp.ones((8, 8)).sum().block_until_ready()
    paths = glob.glob(os.path.join(tracer.dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert paths
    events = T.read_xplane(paths[-1])
    assert any(e["name"] == "bench:dispatch" for e in events)
    # a CPU trace holds no TPU plane: nothing to reduce, no device metric
    assert tracer.reduce(1) is None
    assert not os.path.exists(tracer.dir)
    assert jax.devices()[0].platform == "cpu"


def test_resnet50_multiply_adds_match_the_papers_3_8_billion():
    macs = work.resnet50_forward_macs(224, 1000)
    assert macs == 3_857_973_248            # He et al. table 1: 3.8e9
    assert work.resnet50_train_flops_per_image() == 6 * macs


def test_mistral_7b_sizes():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mistral-7b-v0.3.json")) as f:
        m = json.load(f)
    layer, head = work.llama_matmul_params(m)
    assert layer == 218_103_808 and head == 134_217_728
    # 7.25 billion parameters with the embedding: the published size
    assert 32 * layer + 2 * head == pytest.approx(7.248e9, rel=1e-3)
    f = work.llama_train_flops_per_token(m, 2048)
    assert f == 3 * (2 * (32 * layer + head) + 32 * 2 * 2048 * 4096)
    # decode: int8 weights once, plus the rows' K and V
    none = work.llama_decode_step_bytes(m, 0, 0)
    assert none == 32 * layer + head + 4 * (32 * (4096 + 2 * 1024 + 4096
                                                  + 2 * 14336 + 4096)
                                            + 32768)
    kv = work.llama_decode_step_bytes(m, 16, 500) - none
    assert kv == 16 * 500 * 2 * 32 * 8 * 128 * 2
    bf16 = work.llama_decode_step_bytes(m, 0, 0, weight_bytes=2)
    assert bf16 == 2 * (32 * layer + head)


def test_train_flops_per_item_follows_the_configuration():
    cfg = {"builder": {"model": "resnet50"}, "image_size": 224,
           "num_classes": 1000}
    assert work.train_flops_per_item(cfg) \
        == work.resnet50_train_flops_per_image()
    with pytest.raises(ValueError):
        work.train_flops_per_item({"builder": {"model": "vgg"}})
