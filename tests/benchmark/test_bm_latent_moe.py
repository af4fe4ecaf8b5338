"""The cell xing4-serve-docs: its configuration against the catalog's row,
its builder, work file and readers, at a tiny size on the CPU and on a
recorded run, as test_bm_harness.py does for the other cells."""
import contextlib
import importlib.util
import io
import json
import os
import shutil

import jax
import numpy as np
import pytest

from benchmark import work_latent_moe as work
from benchmark.builders import serve_blocks

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

TINY = dict(hidden_size=32, intermediate_size=64, kv_lora_rank=16,
            q_lora_rank=24, moe_intermediate_size=16, n_routed_experts=8,
            num_experts_per_tok=2, num_attention_heads=4,
            qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
            vocab_size=96, num_hidden_layers=3, torch_dtype="float32")
TINY_ENGINE = {"max_batch": 4, "prompt_buckets": [8, 40],
               "max_new_tokens": 8, "page_size": 4, "chunk_size": 8,
               "prefill_batch": 1, "decode_block": 2,
               "default_timeout_s": 120.0}


def _read(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def published():
    return _read(ROOT, "benchmark", "configs", "xing4.0-29b-a4b.json")


def tiny_config():
    c = dict(published(), **TINY, name="tiny-xing")
    c["rope_scaling"] = dict(c["rope_scaling"],
                             original_max_position_embeddings=16)
    c["builder"] = {"kind": "serve_blocks", "engine": dict(TINY_ENGINE)}
    return c


# -- the configuration ----------------------------------------------------

def test_configuration_carries_every_published_key_or_names_it_reduced():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Xing4.0-29B-A4B")
    c = published()
    assert c["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if c.get(k) != v}
    assert differs == set(c["reduced"]) == {"num_hidden_layers",
                                            "first_k_dense_replace"}
    assert c["published"] == {k: row["config"][k] for k in c["reduced"]}
    # the floors: a whole period, four routed layers, no width touched
    assert c["num_hidden_layers"] - c["first_k_dense_replace"] >= 4
    assert c["first_k_dense_replace"] >= 1


def test_configuration_states_its_deployment_assumptions_and_departures():
    c = published()
    assert "one chip shares each layer" in c["deployment"]
    assert {"torch_dtype", "attention", "router", "mhc"} <= set(c["assumed"])
    assert any("multi-token-prediction" in d for d in c["departures"])
    assert c["torch_dtype"] == "bfloat16"
    assert c["builder"]["kind"] == "serve_blocks"
    e = c["builder"]["engine"]
    assert e["max_batch"] == 16 and e["max_new_tokens"] == 256
    assert e["prompt_buckets"][-1] == 8192 and "quantize" not in e
    assert 1024 <= e["chunk_size"] <= 2048


def test_model_config_carries_the_published_widths():
    cfg = serve_blocks.model_config(published())
    assert (cfg.dim, cfg.n_heads, cfg.q_rank, cfg.kv_rank, cfg.rope_dim,
            cfg.nope_dim, cfg.v_dim) == (3584, 32, 768, 512, 64, 128, 128)
    assert (cfg.ffn_hidden, cfg.n_experts, cfg.expert_hidden,
            cfg.moe_top_k, cfg.n_shared) == (9216, 64, 1024, 4, 1)
    assert (cfg.vocab_size, cfg.n_streams, cfg.sinkhorn_iters) \
        == (131072, 4, 20)
    assert cfg.n_layers == 6 and cfg.n_dense_layers == 1
    assert cfg.entry_dim == 576 and cfg.dtype == "bfloat16"
    assert cfg.softmax_scale() == pytest.approx(192 ** -0.5 * 1.4159 ** 2,
                                                rel=1e-4)
    with pytest.raises(ValueError):
        serve_blocks.model_config(dict(published(), scoring_func="softmax"))


def test_the_bytes_the_configuration_states_are_its_shapes():
    cfg = serve_blocks.model_config(published())
    shapes = cfg.param_shapes()
    count = lambda pre: sum(int(np.prod(s)) for n, (s, _) in shapes.items()
                            if n.startswith(pre))
    assert count("blocks.") / 5 == pytest.approx(745e6, rel=0.005)
    assert count("lead.") == pytest.approx(128e6, rel=0.02)
    assert count("tok_emb") + count("lm_head") == pytest.approx(940e6,
                                                                rel=0.001)
    total = sum(int(np.prod(s)) * (4 if dt == "float32" else 2)
                for s, dt in shapes.values())
    assert total == pytest.approx(9.59e9, rel=0.005)
    assert cfg.n_layers * cfg.entry_dim * 2 == 6912


# -- the work file --------------------------------------------------------

def test_decode_step_bytes_count_what_a_step_must_read():
    m = published()
    none = work.decode_step_bytes(m, positions=0, experts_touched=0)
    # attention, shared experts, the dense SwiGLU and the head in bf16
    assert none == pytest.approx(
        2 * (6 * 28.4e6 + 5 * 11.0e6 + 99.1e6 + 469.8e6)
        + 4 * (6 * 688e3 + 5 * 229e3), rel=0.01)
    one_expert = work.decode_step_bytes(m, 0, 1) - none
    assert one_expert == 2 * 5 * 3 * 3584 * 1024
    assert work.decode_step_bytes(m, 1000, 0) - none == 1000 * 6912
    everything = work.decode_step_bytes(m, 0, 64)
    assert everything == pytest.approx(9.59e9 - 2 * 469.8e6, rel=0.01)


def test_prefill_flops_grow_with_the_square_of_the_prompt():
    m = published()
    f1, f2, f4 = (work.prefill_flops(m, n) for n in (1024, 2048, 4096))
    head = 2 * 3584 * 131072
    per_token = (f1 - head) / 1024
    # 1.0 G of matmuls a token and 10 k per key attended
    assert 1.0e9 < per_token < 1.3e9
    attend = 2 * 32 * 320 * 6
    assert (f4 - head) - 4 * (f1 - head) == pytest.approx(
        attend * (4096 * 4097 // 2 - 4 * 1024 * 1025 // 2))
    assert f1 < f2 < f4


# -- the builder at a tiny size -------------------------------------------

@pytest.mark.parametrize("seed", [0, 2 ** 31 + 12345])
def test_weights_have_the_models_names_shapes_and_types(seed):
    cfg = serve_blocks.model_config(tiny_config())
    a = serve_blocks.make_weights(cfg, seed)
    b = serve_blocks.make_weights(cfg, seed)
    c = serve_blocks.make_weights(cfg, seed + 1)
    shapes = cfg.param_shapes()
    assert set(a) == set(shapes)
    for name, (shape, dtype) in shapes.items():
        assert a[name].shape == tuple(shape) and a[name].dtype == dtype
    assert np.array_equal(a["blocks.wqa"], b["blocks.wqa"])
    assert not np.array_equal(a["blocks.wqa"], c["blocks.wqa"])
    assert a["blocks.moe_router"].dtype == np.float32
    assert np.asarray(a["blocks.attn_norm"]).min() == 1.0
    assert np.abs(np.asarray(a["blocks.moe_bias"])).min() > 0
    assert np.asarray(a["lead.hc_attn_alpha"]).tolist() == [[0.5, 0.5, 1.0]]


def test_large_tensors_are_drawn_in_slices_of_the_same_values(monkeypatch):
    cfg = serve_blocks.model_config(tiny_config())
    whole = serve_blocks.make_weights(cfg, 3)
    calls = []
    keep = jax.lax.map
    monkeypatch.setattr(jax.lax, "map",
                        lambda f, xs: calls.append(1) or keep(f, xs))
    monkeypatch.setattr(serve_blocks.math, "prod",
                        lambda shape: 2 ** 29)   # everything is "large"
    sliced = serve_blocks.make_weights(cfg, 3)
    assert calls and set(sliced) == set(whole)
    for name in whole:
        assert sliced[name].shape == whole[name].shape


@pytest.fixture(scope="module")
def system():
    s = serve_blocks.set_up(tiny_config(), {}, 5)
    yield s
    s.close()


def test_probe_prompts_reach_both_prefill_paths(system):
    short, long_ = serve_blocks.probe_prompts(system, 5)
    assert short.size <= 8 < 2 * 8 < long_.size
    assert serve_blocks.probe_prompts(system, 5)[1].tolist() \
        == long_.tolist()


def test_comparison_follows_the_engines_picks_within_the_margin(
        system, monkeypatch, capsys):
    system.engine.close()
    pools = list(system.engine._pools)
    assert serve_blocks.compare_with_reference(system, 5) == []
    assert "0 of them routed not as the reference alone would" \
        in capsys.readouterr().out
    # an engine whose router took the runner-up once: its logits are the
    # reference's under that routing, and the comparison follows it there
    honest = serve_blocks.engine_logits
    reference_logits = serve_blocks.reference_logits

    def rerouted(engine, prompt, steps):
        got, picks, decoded = honest(engine, prompt, steps)
        seq = np.concatenate([prompt, decoded[:-1]])
        pos = prompt.size - 1 + np.arange(1 + steps)
        cfg = system.cfg
        sel = np.random.RandomState(0).rand(cfg.n_experts)
        others = [e for e in np.argsort(-sel) if e not in picks[2, 1]]
        picks = picks.copy()
        picks[2, 1, -1] = others[0]
        want, _, gaps = reference_logits(system, seq, pos, picks)
        assert gaps[1, 2] > 0 and (np.delete(gaps, 2, 1) == 0).all()
        return want, picks, decoded

    system.engine._pools[:] = pools
    monkeypatch.setattr(serve_blocks, "engine_logits", rerouted)
    monkeypatch.setattr(serve_blocks, "MARGIN", 1.0)
    monkeypatch.setattr(serve_blocks, "REL_L2", 1e-4)
    assert serve_blocks.compare_with_reference(system, 5) == []
    assert "2 of them routed not as the reference alone would" \
        in capsys.readouterr().out
    # the same picks are a finding once they lie further under the
    # reference's own than the margin allows
    system.engine._pools[:] = pools
    monkeypatch.setattr(serve_blocks, "MARGIN", 1e-9)
    problems = serve_blocks.compare_with_reference(system, 5)
    assert len(problems) == 2 and "under the reference's" in problems[0]
    # and with the reference left to route alone they fail on the logits
    system.engine._pools[:] = pools
    monkeypatch.setattr(serve_blocks, "MARGIN", 1.0)
    monkeypatch.setattr(
        serve_blocks, "reference_logits",
        lambda system, seq, pos, picks=None: reference_logits(system, seq,
                                                              pos))
    problems = serve_blocks.compare_with_reference(system, 5)
    assert len(problems) == 2 and "rel_l2" in problems[0]


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
                           "tiny-xing.json"), "w") as f:
        json.dump(tiny_config(), f)
    traffic = _read(ROOT, "benchmark", "traffic", "docs-closed.json")
    traffic.update(name="tiny-docs", clients=6, list_len=32, lead_in_s=0.5,
                   prompt_len=dict(traffic["prompt_len"], median=20, min=8,
                                   max=40),
                   output_len=dict(traffic["output_len"], median=5, min=2,
                                   max=8))
    with open(os.path.join(root, "benchmark", "traffic",
                           "tiny-docs.json"), "w") as f:
        json.dump(traffic, f)
    bench["configs"].append({"name": "tiny-xing", "source": "test",
                             "file": "benchmark/configs/tiny-xing.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-docs-cell",
                               "config": "tiny-xing",
                               "traffic": "tiny-docs", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "xing4-serve-docs" in m.get("workloads", ()):
            m["workloads"].append("tiny-docs-cell")
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
        "bm_latent_run", os.path.join(root, "benchmark", "run.py"))
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
                rc = run_py.main(["--workload", "tiny-docs-cell", "--seed",
                                  "2147483999", "--seconds", "2",
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
    assert set(line) - {"breakdown"} == {"correct", "attempted", "failed",
                                         "metrics", "device"}
    assert line["attempted"] > 10 and line["failed"] == 0
    assert any(x.startswith("logit comparison: 18 positions")
               for x in before)
    assert any("serve_blocks: engine up" in x for x in before)


def test_end_to_end_line_reports_out_tok_s_and_setup_s(results):
    metrics = results[0][1]["metrics"]
    assert set(metrics) == {"out_tok_s", "setup_s"}
    assert metrics["out_tok_s"]["value"] > 0


def test_traced_line_reports_the_counters_metrics_and_no_device_metric(
        results):
    metrics = results[1][1]["metrics"]
    assert {"moe_experts_touched", "moe_load_imbalance", "prefill_mfu",
            "prefill_fill.batch", "batch_occupancy.batch",
            "compiles_in_window.batch"} <= set(metrics)
    assert 0 < metrics["moe_experts_touched"]["value"] <= 100
    assert metrics["moe_load_imbalance"]["value"] >= 1.0
    assert 0 < metrics["prefill_fill.batch"]["value"] <= 100
    assert metrics["compiles_in_window.batch"]["value"] == 0
    # a CPU run holds no device trace: the roofline share is left out
    assert "latent_moe_decode_roofline" not in metrics
    assert "decode_step_ms.batch" not in metrics


# -- the readers on a recorded run ----------------------------------------

def recorded_run():
    start = {"t": 100.0, "decode_batches_total": 10,
             "latent_tokens_read_total": 1000,
             "moe_decode_experts_touched_total": 500,
             "moe_decode_expert_calls_total": 1000,
             "moe_max_load_total": 100, "moe_assignments_total": 4000,
             "prefill_dispatch_s_total": 1.0, "chunk_dispatch_s_total": 2.0,
             "prefill_tokens_total": 10000, "generated_tokens_total": 50,
             "prefill_total": 5}
    end = {"t": 150.0, "decode_batches_total": 110,
           "latent_tokens_read_total": 1000 + 400 * 16 * 4000,
           "moe_decode_experts_touched_total": 500 + 400 * 5 * 40,
           "moe_decode_expert_calls_total": 1000 + 400 * 5 * 64,
           "moe_max_load_total": 100 + 3000,
           "moe_assignments_total": 4000 + 64000,
           "prefill_dispatch_s_total": 2.0, "chunk_dispatch_s_total": 21.0,
           "prefill_tokens_total": 10000 + 3 * 4096,
           "generated_tokens_total": 6000, "prefill_total": 45}
    requests = [{"first_token": 110.0 + i, "prompt_len": 4096,
                 "in_sample": True, "error": None, "n_out": 100}
                for i in range(3)]
    requests.append({"first_token": 99.0, "prompt_len": 8192,
                     "in_sample": False, "error": None, "n_out": 10})
    trace = {"programs": {"decode": {"count": 16, "seconds": 16 * 0.080},
                          "chunk": {"count": 40, "seconds": 4.0}}}
    return {"kind": "serve", "config": published(), "chips": 1,
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
            "engine": {"decode_block": 4, "max_batch": 16},
            "t0": 100.0, "t_end": 150.0, "requests": requests,
            "trace": trace,
            "edges": {"start": start, "end": end,
                      "trace_start": {"decode_batches_total": 50},
                      "trace_end": {"decode_batches_total": 66}}}


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "bm_reader_" + name,
        os.path.join(ROOT, "benchmark", "metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def test_roofline_reader_divides_the_needed_bytes_by_the_step():
    run = recorded_run()
    needed = work.decode_step_bytes(published(), positions=16 * 4000,
                                    experts_touched=40)
    assert reader("latent_moe_decode_roofline")(run) == pytest.approx(
        100 * (needed / 819e9) / 0.020)
    assert reader("latent_moe_decode_roofline")(run) < 100
    run["trace"] = None
    assert reader("latent_moe_decode_roofline")(run) is None


def test_prefill_mfu_reader_scales_by_the_tokens_the_window_carried():
    run = recorded_run()
    flops = 3 * work.prefill_flops(published(), 4096)
    assert reader("prefill_mfu")(run) == pytest.approx(
        100 * flops / 20.0 / 197e12)
    run["edges"]["end"]["prefill_tokens_total"] -= 4096
    assert reader("prefill_mfu")(run) == pytest.approx(
        100 * flops * 2 / 3 / 20.0 / 197e12)


def test_counter_readers_take_the_windows_differences():
    run = recorded_run()
    assert reader("moe_experts_touched")(run) == pytest.approx(62.5)
    assert reader("moe_load_imbalance")(run) == pytest.approx(3.0)


@pytest.mark.parametrize("name", ["latent_moe_decode_roofline",
                                  "prefill_mfu", "moe_experts_touched",
                                  "moe_load_imbalance"])
def test_readers_return_nothing_for_a_program_without_the_counters(name):
    """The parent's engine keeps none of PR 27's counters, a Llama
    configuration none of its keys, a training run neither."""
    run = recorded_run()
    for edge in ("start", "end"):
        run["edges"][edge] = {
            k: v for k, v in run["edges"][edge].items()
            if not k.startswith(("moe_", "latent_"))}
    run["config"] = _read(ROOT, "benchmark", "configs",
                          "mistral-7b-v0.3.json")
    assert reader(name)(run) is None
    assert reader(name)({"kind": "train", "config": {}}) is None


def test_benchmark_json_names_the_cell_where_its_readers_read_true():
    b = _read(ROOT, "BENCHMARK.json")
    cell = next(w for w in b["workloads"] if w["name"] == "xing4-serve-docs")
    assert cell == dict(cell, config="xing4.0-29b-a4b",
                        traffic="docs-closed", chips=1)
    listed = {m["name"] for m in b["end_to_end"] + b["per_layer"]
              if "xing4-serve-docs" in m.get("workloads", ())}
    assert {"out_tok_s", "latent_moe_decode_roofline", "prefill_mfu",
            "moe_experts_touched", "moe_load_imbalance",
            "prefill_fill.batch", "decode_step_ms.batch"} <= listed
    # left out, and PERF.md says why: Llama's shapes; whole-prompt
    # dispatches alone
    assert not {"decode_roofline.batch", "prefill_share.batch"} & listed
    for m in b["per_layer"]:
        if m["name"] in ("latent_moe_decode_roofline", "prefill_mfu",
                         "moe_experts_touched", "moe_load_imbalance"):
            assert m["workloads"] == ["xing4-serve-docs"]
            assert m["moves"] == "out_tok_s"
