"""The cell mimov2flash-serve-mixed: its configuration against the
catalog's row, its traffic, builder, reference, work file and readers, at a
tiny size on the CPU and on a recorded run, as test_bm_latent_share.py does
for deepseekv3-serve-reason. Entries of BENCHMARK.json are found by name.
"""
import contextlib
import importlib.util
import io
import json
import os
import shutil

import jax
import numpy as np
import pytest

from benchmark import work_hybrid_share as work
from benchmark.builders import serve_hybrid
from benchmark.reference import hybrid_moe_share as ref

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL, CONFIG, TRAFFIC = ("mimov2flash-serve-mixed", "mimo-v2-flash-ep16",
                         "mixed-closed")
NEW = ("hybrid_share_decode_roofline", "hybrid_share_prefill_mfu",
       "cache_bytes_per_token", "window_attended_share")
SHARE = ("moe_held_share", "moe_held_experts_touched",
         "moe_held_load_imbalance")
REDUCED = ["num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
           "n_routed_experts", "vocab_size"]

TINY = dict(hidden_size=32, intermediate_size=64, moe_intermediate_size=16,
            n_routed_experts=4, num_experts_per_tok=3,
            num_attention_heads=4, swa_num_attention_heads=4,
            num_key_value_heads=2, swa_num_key_value_heads=4, head_dim=12,
            swa_head_dim=12, v_head_dim=8, swa_v_head_dim=8,
            sliding_window=4, sliding_window_size=4, rope_theta=5e4,
            swa_rope_theta=1e2, vocab_size=96, num_hidden_layers=5,
            hybrid_layer_pattern=[0, 1, 1, 0, 1],
            moe_layer_freq=[0, 1, 1, 1, 1], torch_dtype="float32",
            experts_held={"first": 4, "count": 4, "of": 16})
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
    c = dict(published(), **TINY, name="tiny-hybrid")
    c["builder"] = {"kind": "serve_hybrid", "engine": dict(TINY_ENGINE)}
    return c


def by_name(entries, name):
    return next(e for e in entries if e["name"] == name)


# -- the configuration ----------------------------------------------------

def test_configuration_carries_every_published_key_or_names_it_reduced():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "MiMo-V2-Flash")
    c = published()
    assert c["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if c.get(k) != v]
    assert sorted(differs) == sorted(c["reduced"]) == sorted(REDUCED)
    assert c["published"] == {k: row["config"][k] for k in REDUCED}
    entry = by_name(_read(ROOT, "BENCHMARK.json")["configs"], CONFIG)
    assert entry["reduced"] == c["reduced"] == REDUCED
    assert entry["source"] == c["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    # the cut: the leading dense layer and one whole period, the
    # published 5 window : 1 full among the expert layers
    assert c["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 0, 1] \
        == row["config"]["hybrid_layer_pattern"][:7]
    assert c["moe_layer_freq"] == [0, 1, 1, 1, 1, 1, 1] \
        == row["config"]["moe_layer_freq"][:7]
    assert c["num_hidden_layers"] == 7 and c["n_routed_experts"] == 16 >= 8
    assert c["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert c["experts_held"] == {"first": 0, "count": 16, "of": 256}
    assert c["vocab_rows_held"] == {"first": 0, "count": 19072,
                                    "of": 152576}


def test_configuration_states_its_deployment_assumptions_and_departures():
    c = published()
    for said in ("16 TPU v5e chips", "WITHOUT ITS EXCHANGE",
                 "ONE SIXTEENTH", "sixteen times its share", "8 slices",
                 "7 layers", "0.75 tokens"):
        assert said in c["deployment"], said
    assert {"torch_dtype", "rotary", "value_scale", "window",
            "attention_chunk_size", "sink", "router", "block"} \
        <= set(c["assumed"])
    assert any("multi-token-prediction" in d for d in c["departures"])
    assert any("no exchange" in d for d in c["departures"])
    assert any("ring" in d for d in c["departures"])
    assert set(c["reduced_why"]) == set(c["reduced"])
    assert c["torch_dtype"] == "bfloat16"
    assert c["builder"]["kind"] == "serve_hybrid"
    e = c["builder"]["engine"]
    assert (e["max_batch"], e["max_new_tokens"], e["decode_block"],
            e["page_size"], e["chunk_size"]) == (24, 1024, 4, 64, 2048)
    assert e["prompt_buckets"][-1] == 16384 and "quantize" not in e
    assert e["max_queue"] >= 48


def test_model_config_carries_the_published_widths_and_the_share():
    cfg = serve_hybrid.model_config(published())
    assert (cfg.dim, cfg.n_heads, cfg.head_dim, cfg.v_head_dim,
            cfg.rotary_dim) == (4096, 64, 192, 128, 64)
    assert (cfg.n_kv_full, cfg.n_kv_window, cfg.window) == (4, 8, 128)
    assert (cfg.rope_base_full, cfg.rope_base_window,
            cfg.value_scale) == (5e6, 1e4, 0.707)
    assert (cfg.sink_full, cfg.sink_window) == (False, True)
    assert (cfg.ffn_hidden, cfg.expert_hidden, cfg.moe_top_k,
            cfg.route_scale) == (16384, 2048, 8, 1.0)
    assert (cfg.router_width, cfg.n_experts, cfg.experts_first) \
        == (256, 16, 0)
    assert cfg.layer_pattern == (0, 1, 1, 1, 1, 0, 1)
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.vocab_size,
            cfg.norm_eps, cfg.dtype) == (7, 1, 19072, 1e-5, "bfloat16")
    assert (cfg.layers_of(0), cfg.layers_of(1), cfg.layers_of(0, True),
            cfg.layers_of(1, True)) == (2, 5, 1, 5)
    assert (work.entry_bytes(published(), work.FULL),
            work.entry_bytes(published(), work.WINDOW)) == (2560, 5120)
    assert cfg.ring_pages(64) == 2
    for wrong in (dict(scoring_func="softmax"), dict(n_shared_experts=1),
                  dict(n_routed_experts=256), dict(swa_head_dim=128),
                  dict(moe_layer_freq=[0, 1, 0, 1, 1, 1, 1])):
        with pytest.raises(ValueError):
            serve_hybrid.model_config(dict(published(), **wrong))


def test_the_bytes_the_configuration_states_are_its_shapes():
    cfg = serve_hybrid.model_config(published())
    shapes = cfg.param_shapes()
    count = lambda pre: sum(int(np.prod(s)) for n, (s, _) in shapes.items()
                            if n.startswith(pre))
    assert count("lead.") == pytest.approx(290.5e6, rel=0.001)
    assert count("window.") / 5 == pytest.approx(498.1e6, rel=0.001)
    assert count("full.") == pytest.approx(492.8e6, rel=0.001)
    assert count("tok_emb") + count("lm_head") == pytest.approx(156.2e6,
                                                                rel=0.001)
    assert work.attention_params(published(), work.FULL) == 89128960
    assert work.attention_params(published(), work.WINDOW) == 94371840
    total = sum(int(np.prod(s)) * (4 if dt == "float32" else 2)
                for s, dt in shapes.values())
    assert total == pytest.approx(6.87e9, rel=0.003)
    assert shapes["window.moe_router"] == ([5, 4096, 256], "float32")
    assert shapes["window.moe_w_gate"][0] == [5, 16, 4096, 2048]
    assert shapes["window.sink"] == ([5, 64], "float32")
    assert "full.sink" not in shapes and "lead.sink" not in shapes
    assert shapes["full.wk"][0] == [1, 4096, 4 * 192]
    assert shapes["window.wv"][0] == [5, 4096, 8 * 128]


def test_the_stand_in_sinks_carry_a_visible_share_of_a_windows_softmax():
    cfg = serve_hybrid.model_config(published())
    made = serve_hybrid.stand_ins(cfg, cfg.param_shapes())
    assert sorted(made) == ["full.moe_bias", "window.moe_bias",
                            "window.sink"]
    sink = np.asarray(made["window.sink"])
    assert sink.shape == (5, 64) and (sink == sink[0]).all()
    share = np.exp(sink[0]) / (np.exp(sink[0]) + 128)   # 128 zero scores
    assert share.min() == pytest.approx(0.05, abs=1e-3)
    assert share.max() == pytest.approx(0.75, abs=1e-3)
    bias = np.asarray(made["window.moe_bias"])
    assert bias.shape == (5, 256)
    assert np.allclose(bias[:, 0::2], 0.02) \
        and np.allclose(bias[:, 1::2], -0.02)


# -- the work file --------------------------------------------------------

TINY_M = dict(hidden_size=8, num_attention_heads=4, head_dim=6,
              v_head_dim=4, num_key_value_heads=1,
              swa_num_key_value_heads=2, sliding_window=3,
              intermediate_size=10, moe_intermediate_size=5,
              num_experts_per_tok=2, vocab_size=7,
              hybrid_layer_pattern=[0, 1], moe_layer_freq=[0, 1],
              experts_held={"first": 0, "count": 2, "of": 8})


def test_work_counts_one_tiny_layer_of_each_kind_by_hand():
    m = TINY_M
    # full: Wq 8x24, Wk 8x6, Wv 8x4, Wo 16x8; window: two key/value heads
    assert work.attention_params(m, work.FULL) == 192 + 48 + 32 + 128
    assert work.attention_params(m, work.WINDOW) == 192 + 96 + 64 + 128
    assert (work.entry_bytes(m, work.FULL),
            work.entry_bytes(m, work.WINDOW)) == (2 * 10, 2 * 20)
    # 5 tokens: a full layer sees 1+2+3+4+5 keys, a window of 3: 1+2+3+3+3
    assert work.keys_attended(m, work.FULL, 5) == 15
    assert work.keys_attended(m, work.WINDOW, 5) == 12
    assert work.keys_attended(m, work.WINDOW, 2) == 3
    dense = 400 + 3 * 8 * 10                 # the full layer, dense FFN
    routed = 480 + 8 * 8 + 2 * 0.5 * 3 * 8 * 5     # router + 1 held expert
    attend = 2 * 4 * (6 + 4)
    assert work.prefill_flops(m, 5, 0.5) == 2 * 5 * (dense + routed) \
        + attend * (15 + 12) + 2 * 8 * 7
    # a decode step: every weight outside the experts, the router float32
    none = work.decode_step_bytes(m, 0, 0, 0)
    assert none == 2 * (400 + 480 + 3 * 8 * 10 + 8 * 7) + 4 * 8 * 8
    assert work.decode_step_bytes(m, 0, 0, 1.5) - none \
        == 2 * 1.5 * 3 * 8 * 5
    assert work.decode_step_bytes(m, 100, 30, 0) - none \
        == 100 * 20 + 30 * 40


def test_decode_step_bytes_at_the_published_widths():
    m = published()
    none = work.decode_step_bytes(m, 0, 0, 0)
    assert none == 2 * (2 * 89128960 + 5 * 94371840 + 3 * 4096 * 16384
                        + 4096 * 19072) + 4 * 6 * 4096 * 256
    assert none == pytest.approx(1.88e9, rel=0.01)
    assert work.decode_step_bytes(m, 1000, 0, 0) - none == 1000 * 2560
    assert work.decode_step_bytes(m, 0, 1000, 0) - none == 1000 * 5120
    assert work.decode_step_bytes(m, 0, 0, 16) - none \
        == 2 * 6 * 16 * 3 * 4096 * 2048
    # 1.87 G matmul operations a prompt token beside attention and head
    head = 2 * 4096 * 19072
    f1, f2 = (work.prefill_flops(m, n, 1 / 16) for n in (1, 2))
    per_token = (f2 - f1) - 2 * 64 * 320 * (2 * 2 + 5 * 2)
    assert per_token == pytest.approx(2 * 0.951e9, rel=0.02)
    assert f1 - head - 2 * 64 * 320 * 7 == per_token


# -- the reference against a second hand computation ---------------------

def test_reference_window_layer_is_the_equations_written_out_again():
    """One tiny routed WINDOW layer with sinks, token by token in numpy
    float64, from ISSUE 33's equations and nothing of the reference's
    code."""
    m = tiny_config()
    cfg = serve_hybrid.model_config(m)
    w = {k: np.asarray(v, np.float64) for k, v in jax.tree_util.tree_map(
        np.asarray, serve_hybrid.make_weights(cfg, 11)).items()}
    rng = np.random.RandomState(1)
    for k in w:                               # alive norms, bias and sinks
        if k.endswith("norm"):
            w[k] = w[k] + 0.1 * np.sin(np.arange(w[k].size)).reshape(
                w[k].shape)
        elif k.endswith("moe_bias"):
            w[k] = 0.1 * rng.randn(*w[k].shape)
        elif k.endswith("sink"):
            w[k] = rng.randn(*w[k].shape)
        elif k not in ("tok_emb",):
            w[k] = w[k] * 10
    T, D, H, G, kd, vd, W, rd = 9, 32, 4, 4, 12, 8, 4, 4
    x = np.random.RandomState(0).randn(T, D)
    layer = 2                                 # window.*[1]
    got, _, _, picked = ref.layer(
        ref.from_stacked({k: v.astype(np.float32) for k, v in w.items()},
                         m), layer, x.astype(np.float32), m)

    norm = lambda v, g: v / np.sqrt((v * v).mean(-1, keepdims=True)
                                    + 1e-5) * g
    silu = lambda v: v / (1 + np.exp(-v))
    p = {k[len("window."):]: v[1] for k, v in w.items()
         if k.startswith("window.")}
    u = norm(x, p["attn_norm"])
    q = (u @ p["wq"]).reshape(T, H, kd)
    k = (u @ p["wk"]).reshape(T, G, kd)
    v = 0.707 * (u @ p["wv"]).reshape(T, G, vd)
    inv = 1e2 ** (-np.arange(0, rd, 2) / rd)  # the window layers' base

    def rot(vec, t):               # the first 4 of 12 widths, half-rotation
        a, b = vec[:2], vec[2:4]
        cs, sn = np.cos(t * inv), np.sin(t * inv)
        return np.concatenate([a * cs - b * sn, a * sn + b * cs, vec[4:]])

    attn = np.zeros((T, H, vd))
    for t in range(T):
        for h in range(H):
            g = h // (H // G)
            seen = [j for j in range(T) if j <= t and t - j < W]
            s = np.array([rot(q[t, h], t) @ rot(k[j, g], j) * kd ** -0.5
                          for j in seen])
            top = max(s.max(), p["sink"][h])
            e = np.exp(s - top)
            den = e.sum() + np.exp(p["sink"][h] - top)
            attn[t, h] = sum(e[i] / den * v[j, g]
                             for i, j in enumerate(seen))
    assert len([j for j in range(T) if j <= 8 and 8 - j < W]) == 4
    hidden = x + attn.reshape(T, H * vd) @ p["wo"]
    u = norm(hidden, p["mlp_norm"])
    want = np.zeros((T, D))
    for t in range(T):
        sc = 1 / (1 + np.exp(-(u[t] @ p["moe_router"])))
        picks = np.argsort(-(sc + p["moe_bias"]))[:3]
        assert sorted(picks) == sorted(np.asarray(picked)[t].tolist())
        gates = sc[picks] / (sc[picks].sum() + 1e-20)
        y = np.zeros(D)
        for e, g in zip(picks, gates):
            if 4 <= e < 8:                     # held here
                j = e - 4
                y = y + g * (silu(u[t] @ p["moe_w_gate"][j])
                             * (u[t] @ p["moe_w_up"][j])
                             @ p["moe_w_down"][j])
        want[t] = hidden[t] + y
    err = np.linalg.norm(np.asarray(got) - want, axis=-1) \
        / np.linalg.norm(want, axis=-1)
    assert err.max() < 1e-5


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
                           "tiny-hybrid.json"), "w") as f:
        json.dump(tiny_config(), f)
    traffic = _read(ROOT, "benchmark", "traffic", TRAFFIC + ".json")
    traffic.update(name="tiny-mixed", clients=8, list_len=32,
                   lead_in_s=0.5,
                   prompt_len=dict(traffic["prompt_len"], median=14, min=4,
                                   max=48),
                   output_len=dict(traffic["output_len"], median=5, min=2,
                                   max=8))
    with open(os.path.join(root, "benchmark", "traffic",
                           "tiny-mixed.json"), "w") as f:
        json.dump(traffic, f)
    bench["configs"].append({"name": "tiny-hybrid", "source": "test",
                             "file": "benchmark/configs/tiny-hybrid.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-mixed-cell",
                               "config": "tiny-hybrid",
                               "traffic": "tiny-mixed", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny-mixed-cell")
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
        "bm_hybrid_run", os.path.join(root, "benchmark", "run.py"))
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
                rc = run_py.main(["--workload", "tiny-mixed-cell",
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
    # a probe through each of the two whole-prompt programs and one of
    # three chunks (16 + 16 + 9 tokens), 9 positions each
    assert any(x.startswith("logit comparison: 27 positions")
               for x in before)
    assert any(x.startswith("probe of 41 tokens") for x in before)
    assert any("serve_hybrid: engine up" in x and "experts 4-7 of 16 held"
               in x and "8 window pages (rings of 2)" in x for x in before)


def test_end_to_end_line_reports_out_tok_s_and_setup_s(results):
    metrics = results[0][1]["metrics"]
    assert set(metrics) == {"out_tok_s", "setup_s"}
    assert metrics["out_tok_s"]["value"] > 0


def test_traced_line_reports_the_cache_kinds_and_no_device_metric(results):
    metrics = results[1][1]["metrics"]
    assert {"cache_bytes_per_token", "window_attended_share",
            "hybrid_share_prefill_mfu", "moe_held_share",
            "moe_held_experts_touched", "moe_held_load_imbalance",
            "prefill_fill.batch", "batch_occupancy.batch",
            "pages_peak.batch", "compiles_in_window.batch"} <= set(metrics)
    # a position of the tiny model: 2 full layers x 2 heads x 20 x 4 B
    # were it held exactly; pages whole and the ring make it more, and
    # every layer kept whole would be 2 x 160 + 3 x 320
    assert 320 < metrics["cache_bytes_per_token"]["value"] < 2 * 1280
    # 3 window layers x 4 positions against 2 full layers x the length
    assert 5 < metrics["window_attended_share"]["value"] < 60
    assert 5 < metrics["moe_held_share"]["value"] < 60
    assert metrics["compiles_in_window.batch"]["value"] == 0
    assert 0 < metrics["pages_peak.batch"]["value"] <= 100
    # a CPU run holds no device trace: the roofline share is left out
    assert "hybrid_share_decode_roofline" not in metrics
    assert "latent_share_decode_roofline" not in metrics


# -- the readers on a recorded run ----------------------------------------

def recorded_run():
    start = {"t": 100.0, "decode_batches_total": 10,
             "attn_full_positions_total": 1000,
             "attn_window_positions_total": 500,
             "moe_decode_experts_touched_total": 500,
             "moe_decode_expert_calls_total": 1000,
             "moe_max_load_total": 100, "moe_assignments_total": 4000,
             "moe_held_assignments_total": 300,
             "cache_bytes_held_total": 10 ** 9,
             "cache_positions_resident_total": 10 ** 5,
             "prefill_dispatch_s_total": 1.0, "chunk_dispatch_s_total": 2.0,
             "prefill_tokens_total": 10000, "generated_tokens_total": 50,
             "prefill_total": 5}
    end = {"t": 150.0, "decode_batches_total": 110,
           # 400 steps x 24 rows: 2 full layers x 3,000 positions, 5
           # window layers x 128
           "attn_full_positions_total": 1000 + 400 * 24 * 2 * 3000,
           "attn_window_positions_total": 500 + 400 * 24 * 5 * 128,
           "moe_decode_experts_touched_total": 500 + 400 * 6 * 8,
           "moe_decode_expert_calls_total": 1000 + 400 * 6 * 16,
           "moe_max_load_total": 100 + 9000,
           "moe_assignments_total": 4000 + 960000,
           "moe_held_assignments_total": 300 + 60000,
           "cache_bytes_held_total": 10 ** 9 + 100 * 24 * 21 * 10 ** 6,
           "cache_positions_resident_total": 10 ** 5 + 100 * 24 * 3000,
           "prefill_dispatch_s_total": 3.0, "chunk_dispatch_s_total": 4.0,
           "prefill_tokens_total": 10000 + 3 * 512,
           "generated_tokens_total": 6000, "prefill_total": 45}
    requests = [{"first_token": 110.0 + i, "prompt_len": 512,
                 "in_sample": True, "error": None, "n_out": 100}
                for i in range(3)]
    requests.append({"first_token": 99.0, "prompt_len": 1024,
                     "in_sample": False, "error": None, "n_out": 10})
    # the chunk program ran nearly as often as the decode program, and a
    # whole-prompt program seldom
    trace = {"programs": {"decode": {"count": 16, "seconds": 16 * 0.060},
                          "chunk": {"count": 15, "seconds": 15 * 0.150},
                          "prefill": {"count": 4, "seconds": 0.1}}}
    return {"kind": "serve", "config": published(), "chips": 1,
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
            "engine": {"decode_block": 4, "max_batch": 24},
            "t0": 100.0, "t_end": 150.0, "requests": requests,
            "trace": trace,
            "edges": {"start": start, "end": end,
                      "trace_start": {"decode_batches_total": 50},
                      "trace_end": {"decode_batches_total": 65}}}


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "bm_reader_" + name,
        os.path.join(ROOT, "benchmark", "metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def test_roofline_reader_takes_the_shorter_of_the_two_nearest_programs():
    run = recorded_run()
    # the count alone (15 dispatches in the traced window) names the
    # chunk program; the decode program is the shorter of the two nearest
    needed = work.decode_step_bytes(
        published(), full_positions=24 * 2 * 3000,
        window_positions=24 * 5 * 128, experts_touched=8)
    got = reader("hybrid_share_decode_roofline")(run)
    assert got == pytest.approx(100 * (needed / 819e9) / 0.015)
    assert 30 < got < 100
    run["trace"] = None
    assert reader("hybrid_share_decode_roofline")(run) is None


def test_prefill_mfu_reader_takes_chunks_and_the_measured_held_share():
    run = recorded_run()
    flops = 3 * work.prefill_flops(published(), 512, 1 / 16)
    assert reader("hybrid_share_prefill_mfu")(run) == pytest.approx(
        100 * flops / 4.0 / 197e12)


def test_counter_readers_take_the_windows_differences():
    run = recorded_run()
    assert reader("cache_bytes_per_token")(run) == pytest.approx(7000.0)
    assert reader("window_attended_share")(run) == pytest.approx(
        100 * 5 * 128 / (5 * 128 + 2 * 3000))
    assert reader("moe_held_share")(run) == pytest.approx(6.25)
    assert reader("moe_held_experts_touched")(run) == pytest.approx(50.0)


@pytest.mark.parametrize("name", NEW)
def test_readers_return_nothing_on_the_other_configurations(name):
    """No other configuration has a layer pattern, though every engine
    keeps the cache counters; the parent's engine lacks the counters; a
    training run has nothing."""
    for config in ("deepseek-v3-ep16.json", "xing4.0-29b-a4b.json",
                   "mistral-7b-v0.3.json"):
        run = recorded_run()
        run["config"] = _read(ROOT, "benchmark", "configs", config)
        assert reader(name)(run) is None
    run = recorded_run()
    for edge in ("start", "end"):
        run["edges"][edge] = {
            k: v for k, v in run["edges"][edge].items()
            if not k.startswith(("moe_", "attn_", "cache_"))}
    assert reader(name)(run) is None
    assert reader(name)({"kind": "train", "config": {}}) is None


# -- BENCHMARK.json, by name ---------------------------------------------

def test_benchmark_json_names_the_cell_its_traffic_and_its_metric_lists():
    b = _read(ROOT, "BENCHMARK.json")
    cell = by_name(b["workloads"], CELL)
    assert cell == dict(cell, config=CONFIG, traffic=TRAFFIC, chips=1)
    assert len(cell["why"]) <= 200 and "48 callers over 24 slots" \
        in cell["why"]
    assert len(by_name(b["configs"], CONFIG)["why"]) <= 200
    listed = {m["name"] for m in b["end_to_end"] + b["per_layer"]
              if CELL in m.get("workloads", ())}
    generic = {n + ".batch" for n in (
        "compiles_in_window", "batch_occupancy", "pages_peak",
        "tpot_p90_ms", "decode_step_ms", "device_idle", "peak_hbm_gb",
        "engine_host_ms", "decode_dispatch_ms", "prefill_fill",
        "engine_idle_share")}
    assert listed == {"out_tok_s"} | generic | set(SHARE) | set(NEW)
    for name in NEW:
        m = by_name(b["per_layer"], name)
        assert m["workloads"] == [CELL] and m["moves"] == "out_tok_s"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    roof = by_name(b["per_layer"], "hybrid_share_decode_roofline")
    assert (roof["unit"], roof["better"], roof["source"], roof["layer"]) \
        == ("%", "higher", "device_trace", "Kernels")
    held = by_name(b["per_layer"], "cache_bytes_per_token")
    assert (held["unit"], held["better"], held["source"], held["layer"]) \
        == ("B", "lower", "program_counter", "Scheduler")
    share = by_name(b["per_layer"], "window_attended_share")
    assert (share["unit"], share["better"], share["source"]) \
        == ("%", "lower", "program_counter")
    for name in SHARE:
        assert by_name(b["per_layer"], name)["workloads"] \
            == ["deepseekv3-serve-reason", CELL]
    # what the file already had stands as it was
    assert [w["name"] for w in b["workloads"]][:6] == [
        "mistral7b-serve-batch", "mistral7b-serve-chat",
        "resnet50-train-b256", "mistral7b-train-dp2tp2",
        "xing4-serve-docs", "deepseekv3-serve-reason"]
    assert b["run_seconds"] == 50


def test_traffic_file_is_the_issues_letter_for_letter():
    t = _read(ROOT, "benchmark", "traffic", TRAFFIC + ".json")
    assert (t["name"], t["loop"], t["clients"], t["list_len"],
            t["order_seed"]) == (TRAFFIC, "closed", 48, 512, 0)
    assert t["prompt_len"] == {"dist": "lognormal", "median": 2048,
                               "sigma": 1.0, "min": 128, "max": 16384}
    assert t["output_len"] == {"dist": "lognormal", "median": 256,
                               "sigma": 0.6, "min": 32, "max": 1024}
    assert t["lead_in_s"] >= 20 and "measured" in t["lead_in_why"]
    assert t["sharing"].startswith("none")
    e = published()["builder"]["engine"]
    assert t["prompt_len"]["max"] <= e["prompt_buckets"][-1]
    assert t["output_len"]["max"] <= e["max_new_tokens"]
    assert t["clients"] <= e["max_queue"] and t["clients"] == 2 \
        * e["max_batch"]
    # what the issue says of the lengths: a sixth under 750, a sixth over
    # 5,600, one in fifty at the cap
    from benchmark.loadgen import quantile_lengths
    lens = quantile_lengths(t["prompt_len"], t["list_len"])
    share = lambda ok: sum(1 for n in lens if ok(n)) / len(lens)
    assert share(lambda n: n < 750) == pytest.approx(1 / 6, abs=0.02)
    assert share(lambda n: n > 5600) == pytest.approx(1 / 6, abs=0.02)
    assert share(lambda n: n == 16384) == pytest.approx(1 / 50, abs=0.005)
