"""The cell lfm2moe-serve-assist-wide: its configuration against the
catalog's row, its traffic, builder, reference, work file and readers, at a
tiny size on the CPU, on a run made by hand and on a run RECORDED on the
chip (tests/benchmark/data/run_lfm2moe_assist_wide.json), as
test_bm_hybrid_gated.py does for lagunaxs2-serve-agent. Entries of
BENCHMARK.json are found BY NAME, never by position or count.
"""
import contextlib
import importlib.util
import io
import json
import os
import shutil

import numpy as np
import pytest

from benchmark import work_hybrid_conv as work
from benchmark.builders import serve_hybrid_conv
from benchmark.reference import hybrid_conv_moe as ref

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL, CONFIG, TRAFFIC = ("lfm2moe-serve-assist-wide", "lfm2-24b-a2b",
                         "assist-wide-closed")
CUT = ["num_hidden_layers", "layer_types", "num_dense_layers"]
NEW = ("conv_hybrid_decode_roofline", "conv_hybrid_prefill_mfu",
       "conv_hybrid_attn_decode_roofline", "moe_grouped_rows_roofline",
       "conv_hybrid_rows_per_expert", "conv_hybrid_load_imbalance",
       "conv_hybrid_expert_bytes_share")
TRACED = ("conv_hybrid_decode_roofline", "conv_hybrid_attn_decode_roofline",
          "moe_grouped_rows_roofline")
BATCH = ("compiles_in_window.batch", "batch_occupancy.batch",
         "pages_peak.batch", "tpot_p90_ms.batch", "decode_step_ms.batch",
         "device_idle.batch", "peak_hbm_gb.batch", "engine_host_ms.batch",
         "decode_dispatch_ms.batch", "prefill_fill.batch",
         "engine_idle_share.batch")

TINY = dict(hidden_size=32, intermediate_size=64, num_attention_heads=4,
            num_key_value_heads=2, head_dim=8, vocab_size=96,
            num_experts=8, num_experts_per_tok=2, moe_intermediate_size=16,
            torch_dtype="float32")
TINY_ENGINE = {"max_batch": 4, "prompt_buckets": [8, 16, 48],
               "max_new_tokens": 8, "page_size": 2, "prefill_batch": 1,
               "decode_block": 2, "chunk_size": 16, "max_queue": 16,
               "default_timeout_s": 120.0}


def _read(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def published():
    return _read(ROOT, "benchmark", "configs", CONFIG + ".json")


def tiny_config():
    c = dict(published(), **TINY, name="tiny-conv")
    c["builder"] = {"kind": "serve_hybrid_conv",
                    "engine": dict(TINY_ENGINE)}
    return c


def by_name(entries, name):
    return next(e for e in entries if e["name"] == name)


# -- the configuration ----------------------------------------------------

def test_configuration_carries_every_published_key_and_cuts_depth_alone():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "LFM2-24B-A2B")
    c = published()
    assert c["source"] == row["source_url"]
    assert sorted(k for k, v in row["config"].items()
                  if c.get(k) != v) == sorted(CUT)
    assert c["reduced"] == CUT and sorted(c["reduced_why"]) == sorted(CUT)
    assert c["published"] == {k: row["config"][k] for k in CUT}
    assert c["layer_types"] == row["config"]["layer_types"][1:6] \
        == [ref.CONV, ref.FULL, ref.CONV, ref.CONV, ref.CONV]
    assert (c["num_hidden_layers"], c["num_dense_layers"]) == (5, 1)
    entry = by_name(_read(ROOT, "BENCHMARK.json")["configs"], CONFIG)
    assert entry["reduced"] == CUT and entry["source"] == c["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    # no width, expert count or vocabulary differs
    assert (c["hidden_size"], c["intermediate_size"], c["num_experts"],
            c["num_experts_per_tok"], c["moe_intermediate_size"],
            c["vocab_size"], c["num_attention_heads"],
            c["num_key_value_heads"], c["conv_L_cache"]) \
        == (2048, 11776, 64, 4, 1536, 65536, 32, 8, 3)


def test_configuration_states_its_deployment_assumptions_and_departures():
    c = published()
    for said in ("EIGHT PIPELINE STAGES", "WHOLE on its chip",
                 "published layers 1-5", "LAST stage",
                 "16 rows a decode step", "6.2 GB"):
        assert said in c["deployment"], said
    assert {"head_dim", "tie_word_embeddings", "torch_dtype", "conv",
            "head_norm", "rotary", "block", "dense_layers", "router",
            "tail_dtype", "max_position_embeddings"} <= set(c["assumed"])
    assert (c["head_dim"], c["tie_word_embeddings"], c["torch_dtype"]) \
        == (64, True, "bfloat16")
    assert "1e-6" in c["assumed"]["router"]
    assert "BEFORE the rotation" in c["assumed"]["head_norm"]
    said = " ".join(c["departures"])
    for fault in ("without the B gate", "without the C gate",
                  "two older taps dropped", "without the bias",
                  "whole projection", "float8"):
        assert fault in said, fault
    assert {"expert", "router", "conv_mixer", "attention", "dense_layer",
            "embedding", "weights", "cache", "second_period"} \
        <= set(c["bytes"])
    assert c["builder"]["kind"] == "serve_hybrid_conv"
    e = c["builder"]["engine"]
    assert (e["max_batch"], e["max_new_tokens"], e["decode_block"],
            e["page_size"], e["chunk_size"]) == (256, 1536, 4, 64, 2048)
    assert e["prefill_batch"] in (1, 4) and "n_pages" not in e
    assert e["max_queue"] >= 512 and "quantize" not in e
    assert e["prompt_buckets"] == [128, 256, 512, 1024, 2048, 4096]
    for said in ("prefill_batch", "128", "512"):
        assert said in c["builder"]["engine_why"], said


def test_model_config_carries_the_published_widths():
    m = published()
    cfg = serve_hybrid_conv.model_config(m)
    assert (cfg.dim, cfg.n_layers, cfg.layer_pattern, cfg.n_dense_layers) \
        == (2048, 5, (1, 0, 1, 1, 1), 1)
    assert (cfg.n_heads, cfg.n_kv, cfg.head_dim, cfg.rope_base,
            cfg.d_conv) == (32, 8, 64, 1e6, 3)
    assert (cfg.n_experts, cfg.moe_top_k, cfg.expert_hidden,
            cfg.ffn_hidden, cfg.route_scale, cfg.route_eps,
            cfg.norm_eps) == (64, 4, 1536, 11776, 1.0, 1e-6, 1e-5)
    attrs = cfg.block_attrs(64)
    assert attrs["route_eps"] == 1e-6 and attrs["scoring"] == "sigmoid"
    assert [k.get("mixer") for k in attrs["attn_kinds"]] == [None, "conv"]
    assert [k["pools"] for k in attrs["attn_kinds"]] == [[0, 1], [2]]
    assert attrs["rotary_dim"] == attrs["key_dim"] == attrs["v_dim"] == 64


def test_the_bytes_the_configuration_states_are_its_shapes():
    m = published()
    cfg = serve_hybrid_conv.model_config(m)
    shapes = cfg.param_shapes()
    count = lambda pre: sum(int(np.prod(s)) for n, (s, _) in shapes.items()
                            if n.startswith(pre))
    conv = 2048 * 6144 + 2048 * 2048 + 3 * 2048                 # 16.78 M
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64            # 10.49 M
    routed = 64 * 3 * 2048 * 1536 + 2048 * 64 + 64              # 604.1 M
    norms = 2 * 2048
    assert conv == pytest.approx(16.78e6, rel=0.001)
    assert attn == pytest.approx(10.49e6, rel=0.001)
    assert 64 * work.expert_params(m) == pytest.approx(604.0e6, rel=0.001)
    assert count("lead.") == conv + 3 * 2048 * 11776 + norms
    assert count("lead.") == pytest.approx(89.13e6, rel=0.001)
    assert count("full.") == attn + routed + norms
    assert count("conv.") == 3 * (conv + routed + norms)
    assert count("tok_emb") == 134217728 == count("lm_head")
    total = sum(int(np.prod(s)) for s, _ in shapes.values()) \
        - count("lm_head")                      # tied: counted once
    assert total == pytest.approx(2.701e9, rel=0.001)
    assert 2 * total == pytest.approx(5.40e9, rel=0.002)
    assert [work.mixer_params(m, i) for i in (0, 1)] \
        == [4 * 2048 * 2048, 2 * 2048 * 2048 + 2 * 2048 * 512]
    assert work.expert_params(m) == 9437184
    e = m["builder"]["engine"]
    per_seq = -(-(e["prompt_buckets"][-1] + e["max_new_tokens"]
                  + e["decode_block"]) // e["page_size"])
    assert per_seq == 89
    specs = cfg.build_paged_programs(
        max_batch=256, page_size=64, n_pages=256 * per_seq + 1,
        pages_per_seq=per_seq, prompt_buckets=(128, 256),
        chunk_size=2048).pool_specs
    assert specs == [([1, 22785, 64, 512], "bfloat16")] * 2 + [
        ([4, 257, 4096], "bfloat16")]
    sizes = [int(np.prod(s)) * 2 for s, _ in specs]
    assert sum(sizes[:2]) == pytest.approx(2.99e9, rel=0.002)
    assert sizes[2] == pytest.approx(8.4e6, rel=0.01)
    assert (work.entry_bytes(m), work.tail_bytes(m)) == (2048, 8192)


# -- the work file --------------------------------------------------------

TINY_M = dict(hidden_size=8, num_attention_heads=4, num_key_value_heads=2,
              head_dim=4, intermediate_size=10, vocab_size=7, num_experts=6,
              num_experts_per_tok=2, moe_intermediate_size=3,
              conv_L_cache=3, num_hidden_layers=3, num_dense_layers=1,
              layer_types=[ref.CONV, ref.FULL, ref.CONV])


def test_work_counts_one_tiny_layer_of_each_kind_by_hand():
    m = TINY_M
    # conv: in_proj 8x24 and out_proj 8x8; attention: q and o 8x16 each,
    # k and v 8x8 each
    assert work.mixer_params(m, 0) == 192 + 64
    assert work.mixer_params(m, 1) == 2 * 128 + 2 * 64
    assert (work.entry_bytes(m), work.tail_bytes(m)) == (32, 32)
    assert (work.expert_params(m), work.routed_layers(m)) == (72, 2)
    assert work.layers_of(m, ref.CONV) == 2
    dense, routed = 3 * 8 * 10, 8 * 6 + 2 * 72
    # 5 tokens: attention sees 1+2+3+4+5 keys; a conv layer 3 taps and two
    # gates a width a token
    assert work.prefill_flops(m, 5) == 2 * 8 * 7 \
        + 2 * 5 * (256 + dense) + 5 * 8 * 8 \
        + 2 * 5 * (384 + routed) + 2 * 4 * 2 * 4 * 15 \
        + 2 * 5 * (256 + routed) + 5 * 8 * 8
    fixed, experts, cache, logits = work.decode_step_parts(m, 0, 0, 0)
    assert (experts, cache, logits) == (0, 0, 0)
    assert fixed == 2 * (8 * 7 + 256 + dense + 384 + 256) + 2 * 4 * 8 * 6
    # 10 positions attended, 3 rows x 2 conv layers, 1.5 experts reached
    assert work.decode_step_parts(m, 10, 6, 1.5)[1:] == (
        2 * 2 * 1.5 * 72, 32 * 10 + 2 * 32 * 6, 4 * 7 * 3)
    assert work.grouped_rows_call(m, 1.5, 12) == (
        2 * 12 * 72, 2 * 1.5 * 72 + 12 * 8 * 6)
    assert work.attn_decode_call(m, 10) == 320


def test_decode_step_bytes_at_the_published_widths():
    m = published()
    fixed, experts, cache, logits = work.decode_step_parts(
        m, positions=256 * 800, rows=4 * 256, experts_touched=64)
    # the head 0.27 GB, the dense layer's SwiGLU 0.145, the five mixers
    # 0.155, four float32 routers 2 MB
    assert fixed == pytest.approx(0.57e9, rel=0.01)
    assert experts == 4 * 64 * 18874368
    assert experts == pytest.approx(4.83e9, rel=0.001)
    assert cache == 2048 * 204800 + 2 * 8192 * 1024
    assert logits == 4 * 65536 * 256
    total = fixed + experts + cache + logits
    assert total / 819e9 == pytest.approx(7.2e-3, rel=0.02)
    assert experts / total == pytest.approx(0.82, abs=0.01)
    # a prompt token: 2 x (the mixers 4 x 16.8 M + 10.5 M, the dense
    # SwiGLU 72.4 M, 4 x (router 0.13 M + 4 experts 37.7 M)) products
    f1, f2 = (work.prefill_flops(m, n) for n in (1, 2))
    per_token = (f2 - f1) - 2 * 32 * 2 * 64 * 2 - 4 * 2048 * 8
    assert per_token == pytest.approx(2 * 301.4e6, rel=0.005)
    flops, nbytes = work.grouped_rows_call(m, 64, 1024)
    assert flops / 197e12 < nbytes / 819e9      # the bytes bound a step's
    assert nbytes == pytest.approx(1.208e9 + 1024 * 2048 * 6, rel=0.001)


# -- run.py finds the cell's files by name --------------------------------

@pytest.fixture(scope="module")
def results(tmp_path_factory):
    """A checkout to which a tiny copy of the cell is ADDED the way this
    PR added the cell, run once without and once with the trace."""
    root = str(tmp_path_factory.mktemp("checkout"))
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = _read(ROOT, "BENCHMARK.json")
    with open(os.path.join(root, "benchmark", "configs",
                           "tiny-conv.json"), "w") as f:
        json.dump(tiny_config(), f)
    traffic = _read(ROOT, "benchmark", "traffic", TRAFFIC + ".json")
    traffic.update(name="tiny-wide", clients=8, list_len=32,
                   lead_in_s=0.5,
                   prompt_len=dict(traffic["prompt_len"], median=10, min=4,
                                   max=40),
                   output_len=dict(traffic["output_len"], median=5, min=2,
                                   max=8))
    with open(os.path.join(root, "benchmark", "traffic",
                           "tiny-wide.json"), "w") as f:
        json.dump(traffic, f)
    bench["configs"].append({"name": "tiny-conv", "source": "test",
                             "file": "benchmark/configs/tiny-conv.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-wide-cell",
                               "config": "tiny-conv",
                               "traffic": "tiny-wide", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny-wide-cell")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    peaks = _read(root, "benchmark", "peaks.json")
    peaks["cpu"] = dict(peaks["TPU v5 lite"], source="test only")
    with open(os.path.join(root, "benchmark", "peaks.json"), "w") as f:
        json.dump(peaks, f)

    import paddle_tpu
    keep = paddle_tpu.enable_compile_cache
    paddle_tpu.enable_compile_cache = lambda: "(off in tests)"
    spec = importlib.util.spec_from_file_location(
        "bm_conv_run", os.path.join(root, "benchmark", "run.py"))
    run_py = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run_py)
    run_py.device_report = lambda jax, chips: {
        "platform": jax.devices()[0].platform, "kind": "cpu",
        "count": len(jax.devices())}
    run_py.memory_peak_bytes = lambda jax, chips: 123456
    out = {}
    try:
        for trace in (0, 1):
            buf = io.StringIO()
            with contextlib.redirect_stdout(buf):
                rc = run_py.main(["--workload", "tiny-wide-cell",
                                  "--seed", "2147483999", "--seconds", "2",
                                  "--trace", str(trace)])
            lines = buf.getvalue().strip().splitlines()
            out[trace] = (rc, json.loads(lines[-1]), lines[:-1])
    finally:
        paddle_tpu.enable_compile_cache = keep
    return out


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_correct_through_run_py(results, trace):
    rc, line, before = results[trace]
    problems = [x for x in before if x.startswith("PROBLEM")]
    # a traced run on the CPU holds no device operation, and says so
    assert rc == 0 and problems == [
        "PROBLEM: the traced run holds no device operation"][:trace]
    assert line["correct"] is (not trace)
    assert line["attempted"] > 10 and line["failed"] == 0
    # a short probe (4), three quarters of each whole-prompt program's
    # bucket (6, 12) and two chunks, the second of one token (17): 9
    # positions each
    assert any(x.startswith("logit comparison: 36 positions")
               for x in before), [
                   x for x in before if x.startswith(("logit", "probe"))]
    assert any(x.startswith("probe of 17 tokens") for x in before)
    assert any("serve_hybrid_conv: engine up" in x
               and "all 8 experts of a layer held" in x for x in before)
    assert any(x.startswith("state kind after the window") for x in before)


def test_end_to_end_line_reports_out_tok_s_and_setup_s(results):
    metrics = results[0][1]["metrics"]
    assert set(metrics) == {"out_tok_s", "setup_s"}
    assert metrics["out_tok_s"]["value"] > 0


def test_traced_line_reports_the_new_counters_and_no_device_metric(results):
    metrics = results[1][1]["metrics"]
    assert set(BATCH) - {"decode_step_ms.batch", "device_idle.batch"} \
        <= set(metrics)
    assert set(NEW) - set(TRACED) <= set(metrics)
    assert metrics["compiles_in_window.batch"]["value"] == 0
    assert 1.0 <= metrics["conv_hybrid_load_imbalance"]["value"] < 8.0
    assert 0 < metrics["conv_hybrid_rows_per_expert"]["value"] <= 4 * 2
    assert 0 < metrics["conv_hybrid_expert_bytes_share"]["value"] < 100
    assert metrics["conv_hybrid_prefill_mfu"]["value"] > 0
    # a CPU run holds no device trace: the shares of a roofline are left out
    assert not set(TRACED) & set(metrics)
    # and the readers of other models' files find nothing to read
    for other in ("gated_hybrid_load_imbalance", "ssm_decode_roofline",
                  "moe_load_imbalance", "moe_few_rows_roofline"):
        assert other not in metrics


# -- the readers on a run made by hand --------------------------------------

def handmade_run():
    # 400 steps x 256 rows: 1 attention layer x 800 positions, 4 conv
    # layers; 4 routed layers x 64 experts a step, 62 reached
    start = {"t": 100.0, "decode_batches_total": 10,
             "attn_full_positions_total": 1000,
             "conv_state_updates_total": 500,
             "moe_decode_experts_touched_total": 100,
             "moe_decode_expert_calls_total": 1024,
             "moe_max_load_total": 50, "moe_assignments_total": 4000,
             "prefill_dispatch_s_total": 1.0, "chunk_dispatch_s_total": 2.0,
             "prefill_tokens_total": 10000}
    end = {"t": 150.0, "decode_batches_total": 110,
           "attn_full_positions_total": 1000 + 400 * 256 * 800,
           "conv_state_updates_total": 500 + 400 * 256 * 4,
           "moe_decode_expert_calls_total": 1024 + 400 * 256,
           "moe_decode_experts_touched_total": 100 + 400 * 248,
           "moe_max_load_total": 50 + 9000,
           "moe_assignments_total": 4000 + 256000,
           "prefill_dispatch_s_total": 6.5, "chunk_dispatch_s_total": 2.5,
           "prefill_tokens_total": 10000 + 3 * 400}
    requests = [{"first_token": 110.0 + i, "prompt_len": 400,
                 "in_sample": True, "error": None, "n_out": 100}
                for i in range(3)]
    requests.append({"first_token": 99.0, "prompt_len": 64,
                     "in_sample": False, "error": None, "n_out": 10})
    # whole-prompt programs ran about as often as the decode program: the
    # decode program is the one whose count AND duration are the engine's
    trace = {"programs": {"decode": {"count": 16, "seconds": 16 * 0.048},
                          "prefill": {"count": 15, "seconds": 15 * 0.011},
                          "chunk": {"count": 2, "seconds": 2 * 0.030}},
             # 16 dispatches x 4 steps: the attention layer once a step,
             # 4 routed layers (1 + a scan of 3) and 17 prefills' 4
             "ops": {"paged_flat_packed_decode.3 bf16[256,32,128]":
                     [64 * 0.001, 64],
                     "moe_grouped_rows.3 f32[1024,2048]": [64 * 0.0021, 64],
                     "moe_grouped_rows.4 f32[1024,2048]":
                     [192 * 0.0021, 192],
                     "moe_grouped_rows.5 f32[2048,2048]": [68 * 0.0021, 68],
                     "ragged-dot.5 bf16[512,1536]": [1.0, 99]}}
    return {"kind": "serve", "config": published(), "chips": 1,
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
            "engine": {"decode_block": 4, "max_batch": 256},
            "t0": 100.0, "t_end": 150.0, "requests": requests,
            "trace": trace,
            "edges": {"start": start, "end": end,
                      "trace_start": {"decode_batches_total": 50,
                                      "decode_dispatch_s_total": 3.0,
                                      "attn_full_positions_total": 7000},
                      "trace_end": {
                          "decode_batches_total": 66,
                          "decode_dispatch_s_total": 3.0 + 16 * 0.05,
                          "attn_full_positions_total":
                          7000 + 64 * 256 * 800}}}


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "bm_reader_" + name,
        os.path.join(ROOT, "benchmark", "metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def test_roofline_reader_takes_the_program_by_count_and_duration():
    run = handmade_run()
    parts = work.decode_step_parts(
        published(), positions=256 * 800, rows=256 * 4,
        experts_touched=62.0)
    got = reader("conv_hybrid_decode_roofline")(run)
    assert got == pytest.approx(100 * (sum(parts) / 819e9) / 0.012)
    assert 50 < got < 100
    assert reader("conv_hybrid_expert_bytes_share")(run) \
        == pytest.approx(100 * parts[1] / sum(parts))
    assert 75 < reader("conv_hybrid_expert_bytes_share")(run) < 90
    run["trace"] = None
    assert reader("conv_hybrid_decode_roofline")(run) is None
    assert reader("conv_hybrid_expert_bytes_share")(run) is not None


def test_the_kernels_roofline_readers_take_their_calls_and_their_time():
    run = handmade_run()
    got = reader("moe_grouped_rows_roofline")(run)
    assert got == pytest.approx(
        100 * (62.0 * 18874368 / 819e9) / 0.0021)
    assert 50 < got < 100
    attn = reader("conv_hybrid_attn_decode_roofline")(run)
    assert attn == pytest.approx(
        100 * (2048 * 256 * 800 / 819e9) / 0.001)
    assert 30 < attn < 100
    for name in list(run["trace"]["ops"]):
        if name.startswith(("moe_grouped", "paged_flat")):
            del run["trace"]["ops"][name]
    assert reader("moe_grouped_rows_roofline")(run) is None
    assert reader("conv_hybrid_attn_decode_roofline")(run) is None


def test_prefill_mfu_reader_takes_each_prompt_at_its_length():
    run = handmade_run()
    flops = 3 * work.prefill_flops(published(), 400)
    assert reader("conv_hybrid_prefill_mfu")(run) == pytest.approx(
        100 * flops / 6.0 / 197e12)


def test_the_counter_readers_take_the_windows_differences():
    run = handmade_run()
    assert reader("conv_hybrid_load_imbalance")(run) == pytest.approx(
        64 * 9000 / 256000)
    assert reader("conv_hybrid_rows_per_expert")(run) == pytest.approx(
        256 * 4 / 62.0)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_in_a_run_of_another_configuration(name):
    """What the driver's traced runs of the parent, and of every other
    cell, hand these readers: nothing is read and nothing raised."""
    run = handmade_run()
    run["config"] = _read(ROOT, "benchmark", "configs", "laguna-xs.2.json")
    assert reader(name)(run) is None
    assert reader(name)({"kind": "train", "config": {}}) is None
    mine = handmade_run()
    for edge in mine["edges"].values():     # a program without the counters
        edge.pop("conv_state_updates_total", None)
        edge.pop("attn_full_positions_total", None)
        edge.pop("moe_max_load_total", None)
        edge.pop("prefill_tokens_total", None)
        edge.pop("moe_decode_expert_calls_total", None)
    assert reader(name)(mine) is None


# -- the readers on a run recorded on the chip -------------------------------

def recorded_run():
    run = _read(HERE, "data", "run_lfm2moe_assist_wide.json")
    assert run.pop("config_name") == CONFIG
    run["config"] = published()
    return run


@pytest.mark.parametrize("name", NEW)
def test_every_new_reader_reads_the_recorded_run(name):
    """The traced chip run this PR recorded (seed in the file's ``line``):
    every reader gives what the run's own result line printed, and every
    share of a roofline or of a peak lies under 100%."""
    run = recorded_run()
    got = reader(name)(run)
    assert got == pytest.approx(run["line"]["metrics"][name]["value"],
                                rel=1e-6)
    if by_name(_read(ROOT, "BENCHMARK.json")["per_layer"],
               name)["unit"] == "%":
        assert 0 < got < 100
    ops = run["trace"]["ops"]
    assert any("moe_grouped_rows" in op for op in ops)
    assert any("paged_flat_packed_decode" in op for op in ops)


# -- BENCHMARK.json, by name ------------------------------------------------

def test_benchmark_json_names_the_cell_its_traffic_and_its_metrics():
    bench = _read(ROOT, "BENCHMARK.json")
    cell = by_name(bench["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, TRAFFIC, 1)
    assert len(cell["why"]) <= 200
    assert len(by_name(bench["configs"], CONFIG)["why"]) <= 200
    assert CELL in by_name(bench["end_to_end"], "out_tok_s")["workloads"]
    assert "workloads" not in by_name(bench["end_to_end"], "setup_s")
    for name in BATCH:
        assert CELL in by_name(bench["per_layer"], name)["workloads"], name
    for name in NEW:
        m = by_name(bench["per_layer"], name)
        assert (m["workloads"], m["moves"]) == ([CELL], "out_tok_s"), name
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "metrics", name + ".py")), name
    for name in TRACED:
        assert by_name(bench["per_layer"], name)["source"] == "device_trace"
    # the cell joins no other list
    named = {m["name"] for m in bench["per_layer"]
             if CELL in m.get("workloads", ())}
    assert named == set(BATCH) | set(NEW)
    assert sum(c["chips"] == 4 for c in bench["workloads"]) == 1
    traffic = _read(ROOT, "benchmark", "traffic", TRAFFIC + ".json")
    assert (traffic["loop"], traffic["clients"], traffic["list_len"],
            traffic["order_seed"]) == ("closed", 512, 1024, 0)
    assert traffic["prompt_len"] == dict(dist="lognormal", median=384,
                                         sigma=0.9, min=64, max=4096)
    assert traffic["output_len"] == dict(dist="lognormal", median=384,
                                         sigma=0.6, min=64, max=1536)
    assert 40.0 <= traffic["lead_in_s"] <= 60.0
