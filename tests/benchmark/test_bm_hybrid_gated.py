"""The cell lagunaxs2-serve-agent: its configuration against the catalog's
row, its traffic, builder, reference, work file and readers, at a tiny size
on the CPU and on a recorded run, as test_bm_hybrid_ssm.py does for
jamba2-serve-reason. Entries of BENCHMARK.json are found BY NAME, never by
position or count.
"""
import contextlib
import importlib.util
import io
import json
import os
import shutil

import numpy as np
import pytest

from benchmark import work_hybrid_gated as work
from benchmark.builders import serve_hybrid_gated
from benchmark.reference import hybrid_moe_gated as ref

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL, CONFIG, TRAFFIC = ("lagunaxs2-serve-agent", "laguna-xs.2",
                         "agent-closed")
CUT = ["num_hidden_layers", "layer_types", "mlp_layer_types",
       "num_attention_heads_per_layer"]
NEW = ("gated_hybrid_decode_roofline", "gated_hybrid_prefill_mfu",
       "gated_hybrid_expert_bytes_share", "gated_hybrid_load_imbalance",
       "gated_hybrid_window_share", "moe_few_rows_roofline",
       "moe_experts_touched.fine")
BATCH = ("compiles_in_window.batch", "batch_occupancy.batch",
         "pages_peak.batch", "tpot_p90_ms.batch", "decode_step_ms.batch",
         "device_idle.batch", "peak_hbm_gb.batch", "engine_host_ms.batch",
         "decode_dispatch_ms.batch", "prefill_fill.batch",
         "engine_idle_share.batch")

TINY = dict(hidden_size=32, intermediate_size=64, num_attention_heads=6,
            num_key_value_heads=2, head_dim=8, vocab_size=96,
            num_experts=8, num_experts_per_tok=3, moe_intermediate_size=16,
            shared_expert_intermediate_size=16, sliding_window=4,
            num_attention_heads_per_layer=[6, 8, 8, 8, 6],
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
    c = dict(published(), **TINY, name="tiny-gated")
    full = dict(c["rope_parameters"][ref.FULL],
                original_max_position_embeddings=8, factor=8.0,
                beta_fast=4.0, rope_theta=500.0)
    c["rope_parameters"] = dict(c["rope_parameters"], **{ref.FULL: full})
    c["builder"] = {"kind": "serve_hybrid_gated",
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
                   if r["name"] == "Laguna-XS.2")
    c = published()
    assert c["source"] == row["source_url"]
    assert sorted(k for k, v in row["config"].items()
                  if c.get(k) != v) == sorted(CUT)
    assert c["reduced"] == CUT and sorted(c["reduced_why"]) == sorted(CUT)
    for k in CUT[1:]:
        assert c[k] == row["config"][k][:5] == c["published"][k][:5]
        assert len(c["published"][k]) == 40
    assert (c["num_hidden_layers"], c["published"]["num_hidden_layers"]) \
        == (5, 40)
    entry = by_name(_read(ROOT, "BENCHMARK.json")["configs"], CONFIG)
    assert entry["reduced"] == CUT and entry["source"] == c["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    # no width, expert count or vocabulary differs
    assert (c["hidden_size"], c["num_experts"], c["num_experts_per_tok"],
            c["moe_intermediate_size"], c["vocab_size"], c["head_dim"]) \
        == (2048, 256, 8, 512, 100352, 128)


def test_configuration_states_its_deployment_assumptions_and_departures():
    c = published()
    for said in ("EIGHT PIPELINE STAGES", "WHOLE on its chip",
                 "FIRST stage", "LAST stage", "2 tokens a decode step"):
        assert said in c["deployment"], said
    assert {"torch_dtype", "gate", "qk_norm", "rotary", "softmax_scale",
            "window", "heads", "router", "shared_expert", "block"} \
        <= set(c["assumed"])
    assert "NOT followed" in c["assumed"]["shared_expert"]
    assert "per-head" in c["assumed"]["gate"]
    assert any("NO tensor needs a stand-in" in d for d in c["departures"])
    assert {"attention", "dense_layer", "expert_layer", "weights", "cache",
            "embedding_and_head", "second_period"} <= set(c["bytes"])
    assert c["builder"]["kind"] == "serve_hybrid_gated"
    e = c["builder"]["engine"]
    assert (e["max_batch"], e["max_new_tokens"], e["decode_block"],
            e["page_size"], e["chunk_size"], e["n_pages"]) \
        == (64, 1024, 4, 64, 2048, 7000)
    assert e["max_queue"] >= 128 and "quantize" not in e
    assert e["prompt_buckets"][:2] == [1536, 2048]
    assert e["prompt_buckets"][-1] == 12288


def test_model_config_carries_the_published_widths():
    m = published()
    cfg = serve_hybrid_gated.model_config(m)
    assert (cfg.dim, cfg.n_layers, cfg.layer_pattern, cfg.n_dense_layers) \
        == (2048, 5, (0, 1, 1, 1, 0), 1)
    assert (cfg.heads(0), cfg.heads(1), cfg.n_kv(0), cfg.n_kv(1),
            cfg.head_dim, cfg.v_head_dim) == (48, 64, 8, 8, 128, 128)
    assert (cfg.rotary(0), cfg.rotary(1), cfg.window) == (64, 128, 512)
    assert (cfg.n_experts, cfg.router_width, cfg.moe_top_k, cfg.scoring,
            cfg.route_scale, cfg.shared_hidden, cfg.expert_hidden) \
        == (256, 256, 8, "softmax", 2.5, 512, 512)
    assert cfg.head_gate and not cfg.sink_window and cfg.ring_pages(64) == 8
    kinds = cfg.block_attrs(64)["attn_kinds"]
    assert kinds[0]["rope_factor"] == pytest.approx(0.1 * np.log(64) + 1)
    assert len(kinds[0]["inv_freq"]) == 32 and "inv_freq" not in kinds[1]
    # YaRN: the fastest pair keeps its frequency, the slowest is / 64
    assert kinds[0]["inv_freq"][0] == 1.0
    assert kinds[0]["inv_freq"][-1] == pytest.approx(
        500000 ** (-62 / 64) / 64, rel=1e-6)
    for wrong in (dict(gating=False), dict(attention_bias=True),
                  dict(num_attention_heads_per_layer=[48, 64, 64, 48, 48]),
                  dict(mlp_layer_types=["dense", "sparse", "dense",
                                        "sparse", "sparse"])):
        with pytest.raises(ValueError):
            serve_hybrid_gated.model_config(dict(m, **wrong))


def test_the_bytes_the_configuration_states_are_its_shapes():
    m = published()
    cfg = serve_hybrid_gated.model_config(m)
    shapes = cfg.param_shapes()
    count = lambda pre: sum(int(np.prod(s)) for n, (s, _) in shapes.items()
                            if n.startswith(pre))
    assert count("lead.") == 79790080 + 2 * 2048          # + its norms
    assert count("window.") / 3 == pytest.approx(846.9e6, rel=0.001)
    assert count("full.") == pytest.approx(838.4e6, rel=0.001)
    total = sum(int(np.prod(s)) for s, _ in shapes.values())
    assert total == pytest.approx(3.870e9, rel=0.001)
    assert [work.attention_params(m, i) for i in (0, 1)] \
        == [29458432, 37879808]
    assert work.expert_params(m) == work.shared_params(m) == 3145728
    specs = cfg.build_paged_programs(
        max_batch=64, page_size=64, n_pages=7001, pages_per_seq=209,
        prompt_buckets=(1536, 2048), chunk_size=2048).pool_specs
    assert specs == [([2, 7001, 64, 1024], "bfloat16")] * 2 + [
        ([3, 513, 64, 1024], "bfloat16")] * 2
    assert 2 * work.entry_bytes(m) == 8192
    assert 3 * 512 * work.entry_bytes(m) == pytest.approx(6.29e6, rel=0.001)


# -- the work file --------------------------------------------------------

TINY_M = dict(hidden_size=8, num_key_value_heads=2, head_dim=4,
              intermediate_size=10, vocab_size=7, num_experts=6,
              num_experts_per_tok=2, moe_intermediate_size=3,
              shared_expert_intermediate_size=5, sliding_window=3,
              layer_types=[ref.FULL, ref.WINDOW],
              mlp_layer_types=["dense", "sparse"],
              num_attention_heads_per_layer=[4, 6])


def test_work_counts_one_tiny_layer_of_each_kind_by_hand():
    m = TINY_M
    # q and o 8x16 | 8x24 each, k and v 8x8 each, the gate 8x4 | 8x6
    assert work.attention_params(m, 0) == 2 * 128 + 2 * 64 + 32
    assert work.attention_params(m, 1) == 2 * 192 + 2 * 64 + 48
    assert work.entry_bytes(m) == 2 * 2 * 2 * 4
    assert (work.expert_params(m), work.shared_params(m)) == (72, 120)
    # 5 tokens: a full layer sees 1+2+3+4+5 keys, a window of 3 1+2+3+3+3
    assert [work.keys_attended(m, i, 5) for i in (0, 1)] == [15, 12]
    dense, sparse = 3 * 8 * 10, 8 * 6 + 2 * 72 + 120
    assert work.prefill_flops(m, 5) == 2 * 8 * 7 \
        + 2 * 5 * (416 + dense) + 2 * 4 * 8 * 15 \
        + 2 * 5 * (560 + sparse) + 2 * 6 * 8 * 12
    fixed, experts, cache = work.decode_step_parts(m, 0, 0, 0)
    assert (experts, cache) == (0, 0)
    assert fixed == 2 * (8 * 7 + 416 + dense + 560 + 120) + 4 * 8 * 6
    assert work.decode_step_parts(m, 10, 4, 1.5)[1:] == (
        2 * 1 * 1.5 * 72, 32 * 14)
    assert work.decode_step_bytes(m, 10, 4, 1.5) == fixed + 216 + 448


def test_decode_step_bytes_at_the_published_widths():
    m = published()
    fixed, experts, cache = work.decode_step_parts(
        m, full_positions=2 * 64 * 5000, window_positions=3 * 64 * 512,
        experts_touched=0.86 * 256)
    # the head 0.41 GB, five layers' attention 0.35, the dense SwiGLU 0.10
    assert fixed == pytest.approx(0.89e9, rel=0.02)
    assert experts == pytest.approx(5.54e9, rel=0.01)
    assert cache == 4096 * (640000 + 98304)
    assert cache == pytest.approx(3.02e9, rel=0.01)
    total = work.decode_step_bytes(m, 640000, 98304, 0.86 * 256)
    assert total / 819e9 == pytest.approx(11.5e-3, rel=0.02)
    # a prompt token: 2 x (attention 172.6 M + dense 50.3 M + 4 x (router
    # 0.5 M + 9 experts 28.3 M)) products
    f1, f2 = (work.prefill_flops(m, n) for n in (1, 2))
    per_token = (f2 - f1) - 2 * 2 * 128 * 2 * (48 * 2 + 64 * 3)
    assert per_token == pytest.approx(2 * 338.3e6, rel=0.005)
    assert work.prefill_flops(m, 4620) == pytest.approx(3.87e12, rel=0.01)


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
                           "tiny-gated.json"), "w") as f:
        json.dump(tiny_config(), f)
    traffic = _read(ROOT, "benchmark", "traffic", TRAFFIC + ".json")
    traffic.update(name="tiny-agent", clients=8, list_len=32,
                   lead_in_s=0.5,
                   prompt_len=dict(traffic["prompt_len"], median=14, min=4,
                                   max=40),
                   output_len=dict(traffic["output_len"], median=5, min=2,
                                   max=8))
    with open(os.path.join(root, "benchmark", "traffic",
                           "tiny-agent.json"), "w") as f:
        json.dump(traffic, f)
    bench["configs"].append({"name": "tiny-gated", "source": "test",
                             "file": "benchmark/configs/tiny-gated.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-agent-cell",
                               "config": "tiny-gated",
                               "traffic": "tiny-agent", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny-agent-cell")
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
        "bm_gated_run", os.path.join(root, "benchmark", "run.py"))
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
                rc = run_py.main(["--workload", "tiny-agent-cell",
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
    # three quarters of each whole-prompt program's bucket (6, 12) and two
    # chunks and a half and a token (41), 9 positions each
    assert any(x.startswith("logit comparison: 27 positions")
               for x in before), [
                   x for x in before if x.startswith(("logit", "probe"))]
    assert any(x.startswith("probe of 41 tokens") for x in before)
    assert any("serve_hybrid_gated: engine up" in x
               and "all 8 experts of a layer held" in x for x in before)


def test_end_to_end_line_reports_out_tok_s_and_setup_s(results):
    metrics = results[0][1]["metrics"]
    assert set(metrics) == {"out_tok_s", "setup_s"}
    assert metrics["out_tok_s"]["value"] > 0


def test_traced_line_reports_the_new_counters_and_no_device_metric(results):
    metrics = results[1][1]["metrics"]
    assert set(BATCH) - {"decode_step_ms.batch", "device_idle.batch"} \
        <= set(metrics)
    assert set(NEW) - {"gated_hybrid_decode_roofline",
                       "moe_few_rows_roofline"} <= set(metrics)
    assert metrics["compiles_in_window.batch"]["value"] == 0
    assert 1.0 <= metrics["gated_hybrid_load_imbalance"]["value"] < 8.0
    assert 0 < metrics["moe_experts_touched.fine"]["value"] <= 100
    assert 0 < metrics["gated_hybrid_window_share"]["value"] < 100
    assert 0 < metrics["gated_hybrid_expert_bytes_share"]["value"] < 100
    # a CPU run holds no device trace: the share of a roofline is left out
    assert "gated_hybrid_decode_roofline" not in metrics
    assert "moe_few_rows_roofline" not in metrics
    # and the readers of other models' files find nothing to read
    for other in ("hybrid_share_decode_roofline", "window_attended_share",
                  "moe_load_imbalance", "moe_held_share"):
        assert other not in metrics


# -- the readers on a recorded run ----------------------------------------

def recorded_run():
    start = {"t": 100.0, "decode_batches_total": 10,
             "attn_full_positions_total": 1000,
             "attn_window_positions_total": 500,
             "moe_decode_experts_touched_total": 100,
             "moe_decode_expert_calls_total": 1024,
             "moe_max_load_total": 50, "moe_assignments_total": 4000,
             "prefill_dispatch_s_total": 1.0, "chunk_dispatch_s_total": 2.0,
             "prefill_tokens_total": 10000}
    end = {"t": 150.0, "decode_batches_total": 110,
           # 400 steps x 64 rows: 2 full layers x 5,000 positions, 3 window
           # layers x 512; 4 sparse layers x 256 experts a step, 86% touched
           "attn_full_positions_total": 1000 + 400 * 64 * 2 * 5000,
           "attn_window_positions_total": 500 + 400 * 64 * 3 * 512,
           "moe_decode_expert_calls_total": 1024 + 400 * 1024,
           "moe_decode_experts_touched_total": 100 + 344 * 1024,
           "moe_max_load_total": 50 + 1500,
           "moe_assignments_total": 4000 + 256000,
           "prefill_dispatch_s_total": 1.5, "chunk_dispatch_s_total": 5.5,
           "prefill_tokens_total": 10000 + 3 * 4096}
    requests = [{"first_token": 110.0 + i, "prompt_len": 4096,
                 "in_sample": True, "error": None, "n_out": 100}
                for i in range(3)]
    requests.append({"first_token": 99.0, "prompt_len": 1024,
                     "in_sample": False, "error": None, "n_out": 10})
    # the chunk program ran twice as often as the decode program, a
    # whole-prompt program as often: the decode program is the one whose
    # count AND duration are the engine's own
    trace = {"programs": {"decode": {"count": 16, "seconds": 16 * 0.060},
                          "prefill": {"count": 15, "seconds": 15 * 0.040},
                          "chunk": {"count": 33, "seconds": 33 * 0.100}},
             # 16 dispatches x 4 steps: 3 window layers and 1 full layer
             "ops": {"moe_few_rows.3 f32[64,2048]": [192 * 0.0024, 192],
                     "moe_few_rows.4 f32[64,2048]": [64 * 0.0024, 64],
                     "ragged-dot.5 bf16[16384,512]": [1.0, 99]}}
    return {"kind": "serve", "config": published(), "chips": 1,
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
            "engine": {"decode_block": 4, "max_batch": 64},
            "t0": 100.0, "t_end": 150.0, "requests": requests,
            "trace": trace,
            "edges": {"start": start, "end": end,
                      "trace_start": {"decode_batches_total": 50,
                                      "decode_dispatch_s_total": 3.0},
                      "trace_end": {"decode_batches_total": 65,
                                    "decode_dispatch_s_total": 3.96}}}


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "bm_reader_" + name,
        os.path.join(ROOT, "benchmark", "metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def test_roofline_reader_takes_the_program_by_count_and_duration():
    run = recorded_run()
    parts = work.decode_step_parts(
        published(), full_positions=64 * 2 * 5000,
        window_positions=64 * 3 * 512, experts_touched=0.86 * 256)
    got = reader("gated_hybrid_decode_roofline")(run)
    assert got == pytest.approx(100 * (sum(parts) / 819e9) / 0.015)
    assert 50 < got < 100
    assert reader("gated_hybrid_expert_bytes_share")(run) \
        == pytest.approx(100 * parts[1] / sum(parts))
    assert 50 < reader("gated_hybrid_expert_bytes_share")(run) < 70
    run["trace"] = None
    assert reader("gated_hybrid_decode_roofline")(run) is None
    assert reader("gated_hybrid_expert_bytes_share")(run) is not None


def test_the_kernels_roofline_reader_takes_its_calls_and_their_time():
    run = recorded_run()
    flops, nbytes = work.few_rows_call(published(), 0.86 * 256, 64)
    assert nbytes == pytest.approx(0.86 * 256 * 6291456)
    assert flops == 64 * nbytes         # 2 operations a weight a row
    assert flops / 197e12 < nbytes / 819e9      # the bytes bound it
    got = reader("moe_few_rows_roofline")(run)
    assert got == pytest.approx(100 * (nbytes / 819e9) / 0.0024)
    assert 50 < got < 100
    del run["trace"]["ops"]["moe_few_rows.3 f32[64,2048]"]
    del run["trace"]["ops"]["moe_few_rows.4 f32[64,2048]"]
    assert reader("moe_few_rows_roofline")(run) is None


def test_prefill_mfu_reader_takes_chunks_and_each_prompt_at_its_length():
    run = recorded_run()
    flops = 3 * work.prefill_flops(published(), 4096)
    assert reader("gated_hybrid_prefill_mfu")(run) == pytest.approx(
        100 * flops / 4.0 / 197e12)


def test_the_counter_readers_take_the_windows_differences():
    run = recorded_run()
    assert reader("gated_hybrid_load_imbalance")(run) == pytest.approx(
        256 * 1500 / 256000)
    assert reader("gated_hybrid_window_share")(run) == pytest.approx(
        100 * 3 * 512 / (3 * 512 + 2 * 5000))
    assert reader("moe_experts_touched")(run) == pytest.approx(86.0)


@pytest.mark.parametrize("name", NEW[:-1])   # the last has no reader of its own
def test_a_reader_finds_nothing_in_a_run_of_another_configuration(name):
    """What the driver's traced runs of the parent, and of every other
    cell, hand these readers: nothing is read and nothing raised."""
    run = recorded_run()
    run["config"] = _read(ROOT, "benchmark", "configs",
                          "mimo-v2-flash-ep16.json")
    assert reader(name)(run) is None
    assert reader(name)({"kind": "train", "config": {}}) is None
    mine = recorded_run()
    for edge in mine["edges"].values():     # a program without the counters
        edge.pop("attn_window_positions_total", None)
        edge.pop("moe_max_load_total", None)
        edge.pop("prefill_tokens_total", None)
        edge.pop("moe_decode_expert_calls_total", None)
    assert reader(name)(mine) is None


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
    assert by_name(bench["per_layer"],
                   "gated_hybrid_decode_roofline")["source"] \
        == "device_trace"
    # the cell joins no other list
    named = {m["name"] for m in bench["per_layer"]
             if CELL in m.get("workloads", ())}
    assert named == set(BATCH) | set(NEW)
    traffic = _read(ROOT, "benchmark", "traffic", TRAFFIC + ".json")
    assert (traffic["loop"], traffic["clients"], traffic["list_len"],
            traffic["order_seed"]) == ("closed", 128, 1024, 0)
    assert traffic["prompt_len"] == dict(dist="lognormal", median=4096,
                                         sigma=0.5, min=1024, max=12288)
    assert traffic["output_len"] == dict(dist="lognormal", median=320,
                                         sigma=0.5, min=64, max=1024)
    assert 40.0 <= traffic["lead_in_s"] <= 60.0
