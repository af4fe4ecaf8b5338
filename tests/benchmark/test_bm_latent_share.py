"""The cell deepseekv3-serve-reason: its configuration against the
catalog's row, its builder, reference, work file and readers, at a tiny
size on the CPU and on a recorded run, as test_bm_latent_moe.py does for
xing4-serve-docs."""
import contextlib
import importlib.util
import io
import json
import os
import shutil

import jax
import numpy as np
import pytest

from benchmark import work_latent_share as work
from benchmark.builders import serve_share
from benchmark.reference import latent_moe_share as ref

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "deepseekv3-serve-reason"
NEW = ("latent_share_decode_roofline", "latent_share_prefill_mfu",
       "moe_held_share", "moe_held_experts_touched",
       "moe_held_load_imbalance")
XING4_ONLY = ("latent_moe_decode_roofline", "prefill_mfu",
              "moe_experts_touched", "moe_load_imbalance")

TINY = dict(hidden_size=32, intermediate_size=64, kv_lora_rank=16,
            q_lora_rank=24, moe_intermediate_size=16, n_routed_experts=4,
            num_experts_per_tok=3, n_group=4, topk_group=2,
            num_attention_heads=4, qk_nope_head_dim=8, qk_rope_head_dim=8,
            v_head_dim=8, vocab_size=96, num_hidden_layers=3,
            torch_dtype="float32",
            experts_held={"first": 4, "count": 4, "of": 16})
TINY_ENGINE = {"max_batch": 4, "prompt_buckets": [8, 40],
               "max_new_tokens": 8, "page_size": 4, "prefill_batch": 1,
               "decode_block": 2, "max_queue": 16,
               "default_timeout_s": 120.0}


def _read(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def published():
    return _read(ROOT, "benchmark", "configs", "deepseek-v3-ep16.json")


def tiny_config():
    c = dict(published(), **TINY, name="tiny-share")
    c["rope_scaling"] = dict(c["rope_scaling"],
                             original_max_position_embeddings=16)
    c["builder"] = {"kind": "serve_share", "engine": dict(TINY_ENGINE)}
    return c


# -- the configuration ----------------------------------------------------

def test_configuration_carries_every_published_key_or_names_it_reduced():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "DeepSeek-V3")
    c = published()
    assert c["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if c.get(k) != v}
    assert differs == set(c["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size"}
    assert c["published"] == {k: row["config"][k] for k in c["reduced"]}
    entry = next(e for e in _read(ROOT, "BENCHMARK.json")["configs"]
                 if e["name"] == "deepseek-v3-ep16")
    assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"]
    # the floors: a whole period and four routed layers, 8 experts a layer
    # that has them, an eighth of the vocabulary, no width touched
    assert c["num_hidden_layers"] - c["first_k_dense_replace"] == 4
    assert c["first_k_dense_replace"] == 1
    assert c["n_routed_experts"] == 16 >= 8
    assert c["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert c["experts_held"] == {"first": 0, "count": 16, "of": 256}
    assert c["vocab_rows_held"] == {"first": 0, "count": 16160,
                                    "of": 129280}


def test_configuration_states_its_deployment_assumptions_and_departures():
    c = published()
    for said in ("16 TPU v5e chips", "WITHOUT ITS EXCHANGE",
                 "ONE SIXTEENTH", "sixteen times its share",
                 "8 slices"):
        assert said in c["deployment"], said
    assert {"torch_dtype", "attention", "router", "block"} \
        <= set(c["assumed"])
    assert any("multi-token-prediction" in d for d in c["departures"])
    assert any("no exchange" in d for d in c["departures"])
    assert set(c["reduced_why"]) == set(c["reduced"])
    assert c["torch_dtype"] == "bfloat16"
    assert c["builder"]["kind"] == "serve_share"
    e = c["builder"]["engine"]
    assert (e["max_batch"], e["max_new_tokens"], e["decode_block"],
            e["page_size"]) == (64, 1024, 4, 64)
    assert e["prompt_buckets"][-1] == 1024 and "quantize" not in e
    assert "chunk_size" not in e and e["max_queue"] >= 128


def test_model_config_carries_the_published_widths_and_the_share():
    cfg = serve_share.model_config(published())
    assert (cfg.dim, cfg.n_heads, cfg.q_rank, cfg.kv_rank, cfg.rope_dim,
            cfg.nope_dim, cfg.v_dim) == (7168, 128, 1536, 512, 64, 128, 128)
    assert (cfg.ffn_hidden, cfg.expert_hidden, cfg.moe_top_k,
            cfg.n_shared, cfg.route_scale) == (18432, 2048, 8, 1, 2.5)
    assert (cfg.router_width, cfg.n_group, cfg.topk_group, cfg.n_experts,
            cfg.experts_first) == (256, 8, 4, 16, 0)
    assert (cfg.vocab_size, cfg.residual) == (16160, "plain")
    assert cfg.n_layers == 5 and cfg.n_dense_layers == 1
    assert cfg.entry_dim == 576 and cfg.dtype == "bfloat16"
    mscale = 0.1 * np.log(40.0) + 1.0
    assert cfg.softmax_scale() == pytest.approx(192 ** -0.5 * mscale ** 2,
                                                rel=1e-6)
    for wrong in (dict(scoring_func="softmax"), dict(hc_mult=4),
                  dict(n_routed_experts=256)):
        with pytest.raises(ValueError):
            serve_share.model_config(dict(published(), **wrong))


def test_the_bytes_the_configuration_states_are_its_shapes():
    cfg = serve_share.model_config(published())
    shapes = cfg.param_shapes()
    assert not [n for n in shapes if ".hc_" in n]
    count = lambda pre: sum(int(np.prod(s)) for n, (s, _) in shapes.items()
                            if n.startswith(pre))
    assert count("blocks.") / 4 == pytest.approx(937.6e6, rel=0.001)
    assert count("lead.") == pytest.approx(583.5e6, rel=0.001)
    assert count("tok_emb") + count("lm_head") == pytest.approx(231.7e6,
                                                                rel=0.001)
    assert work.attention_params(published()) == 187105280
    total = sum(int(np.prod(s)) * (4 if dt == "float32" else 2)
                for s, dt in shapes.values())
    assert total == pytest.approx(9.13e9, rel=0.003)
    assert cfg.n_layers * cfg.entry_dim * 2 == 5760
    assert shapes["blocks.moe_router"] == ([4, 7168, 256], "float32")
    assert shapes["blocks.moe_w_gate"][0] == [4, 16, 7168, 2048]


# -- the work file --------------------------------------------------------

def test_decode_step_bytes_count_what_a_step_must_read():
    m = published()
    none = work.decode_step_bytes(m, positions=0, experts_touched=0)
    # attention, shared experts, the dense SwiGLU, the head's slice in
    # bf16; the 256-wide router in float32
    assert none == 2 * (5 * 187105280 + 4 * 3 * 7168 * 2048
                        + 3 * 7168 * 18432 + 7168 * 16160) \
        + 4 * 4 * 7168 * 256
    assert none == pytest.approx(3.28e9, rel=0.01)
    assert work.decode_step_bytes(m, 0, 1) - none == 2 * 4 * 3 * 7168 * 2048
    assert work.decode_step_bytes(m, 1000, 0) - none == 1000 * 5760
    # every held expert touched: all the weights but the embedding
    assert work.decode_step_bytes(m, 0, 16) == pytest.approx(
        9.13e9 - 2 * 7168 * 16160, rel=0.003)


def test_prefill_flops_count_the_held_share_of_a_tokens_picks():
    m = published()
    f0, f1 = (work.prefill_flops(m, 512, s) for s in (0.0, 1.0))
    assert f1 - f0 == 2 * 512 * 4 * 8 * 3 * 7168 * 2048
    even = work.prefill_flops(m, 512, 1 / 16)
    assert f0 < even < f1
    head = 2 * 7168 * 16160
    g1, g2 = (work.prefill_flops(m, n, 1 / 16) for n in (512, 1024))
    attend = 2 * 128 * 320 * 5
    assert (g2 - head) - 2 * (g1 - head) == pytest.approx(
        attend * (1024 * 1025 // 2 - 2 * 512 * 513 // 2))
    # about 3.5 G operations a token outside attention
    assert 3.2e9 < (g1 - head - attend * 512 * 513 // 2) / 512 < 3.8e9


# -- the reference against a second hand computation ---------------------

def test_reference_layer_is_the_equations_written_out_again():
    """One tiny routed layer, token by token in numpy float64, from
    ISSUE 31's equations and nothing of the reference's code."""
    cfg = serve_share.model_config(tiny_config())
    w = {k: np.asarray(v, np.float64) for k, v in jax.tree_util.tree_map(
        np.asarray, serve_share.make_weights(cfg, 11)).items()}
    for k in w:                               # alive norms and bias
        if k.endswith("norm"):
            w[k] = w[k] + 0.1 * np.sin(np.arange(w[k].size)).reshape(
                w[k].shape)
        elif k.endswith("moe_bias"):
            w[k] = w[k] * 5
        elif k not in ("tok_emb",):
            w[k] = w[k] * 10
    m = tiny_config()
    T, D, H = 6, 32, 4
    x = np.random.RandomState(0).randn(T, D)
    got, _, _, picked = ref.layer(
        ref.from_stacked({k: v.astype(np.float32) for k, v in w.items()},
                         1), 1, x.astype(np.float32), m)

    norm = lambda v, g: v / np.sqrt((v * v).mean(-1, keepdims=True)
                                    + 1e-6) * g
    silu = lambda v: v / (1 + np.exp(-v))
    L = 0                                      # blocks.*[0] is layer 1
    p = {k[len("blocks."):]: v[L] for k, v in w.items()
         if k.startswith("blocks.")}
    u = norm(x, p["attn_norm"])
    q = (norm(u @ p["wqa"], p["q_norm"]) @ p["wqb"]).reshape(T, H, 16)
    ckv = u @ p["wkva"]
    c, k_pe = norm(ckv[:, :16], p["kv_norm"]), ckv[:, 16:]
    kv = (c @ p["wkvb"]).reshape(T, H, 16)
    # YaRN at factor 40 over an original window of 16, rope width 8
    dim, base, factor, orig = 8, 10000.0, 40.0, 16
    pair_of = lambda turns: dim * np.log(orig / (turns * 2 * np.pi)) \
        / (2 * np.log(base))
    low = max(int(np.floor(pair_of(32))), 0)
    high = min(int(np.ceil(pair_of(1))), dim - 1)
    plain = base ** (-np.arange(0, dim, 2) / dim)
    ramp = np.clip((np.arange(dim // 2) - low)
                   / ((high - low) or 0.001), 0, 1)
    inv = plain / factor * ramp + plain * (1 - ramp)

    def rot(v, t):                 # v [8], published pairs interleaved
        a, b = v[0::2], v[1::2]
        cs, sn = np.cos(t * inv), np.sin(t * inv)
        return np.concatenate([a * cs - b * sn, a * sn + b * cs])

    scale = 16 ** -0.5 * (0.1 * np.log(40.0) + 1) ** 2
    attn = np.zeros((T, H, 8))
    for t in range(T):
        for h in range(H):
            s = np.array([
                (q[t, h, :8] @ kv[j, h, :8]
                 + rot(q[t, h, 8:], t) @ rot(k_pe[j], j)) * scale
                for j in range(t + 1)])
            pr = np.exp(s - s.max())
            pr /= pr.sum()
            attn[t, h] = sum(pr[j] * kv[j, h, 8:] for j in range(t + 1))
    hidden = x + attn.reshape(T, H * 8) @ p["wo"]
    u = norm(hidden, p["mlp_norm"])
    want = np.zeros((T, D))
    for t in range(T):
        sc = 1 / (1 + np.exp(-(u[t] @ p["moe_router"])))
        sel = sc + p["moe_bias"]
        groups = sel.reshape(4, 4)
        best = np.argsort(-np.sort(groups, -1)[:, -2:].sum(-1))[:2]
        masked = np.where(np.isin(np.arange(16) // 4, best), sel, 0.0)
        picks = np.argsort(-masked)[:3]
        assert sorted(picks) == sorted(np.asarray(picked)[t].tolist())
        gates = sc[picks] / (sc[picks].sum() + 1e-20) * 2.5
        y = silu(u[t] @ p["sh_w_gate"]) * (u[t] @ p["sh_w_up"]) \
            @ p["sh_w_down"]
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
                           "tiny-share.json"), "w") as f:
        json.dump(tiny_config(), f)
    traffic = _read(ROOT, "benchmark", "traffic", "reason-closed.json")
    traffic.update(name="tiny-reason", clients=8, list_len=32,
                   lead_in_s=0.5,
                   prompt_len=dict(traffic["prompt_len"], median=12, min=4,
                                   max=40),
                   output_len=dict(traffic["output_len"], median=5, min=2,
                                   max=8))
    with open(os.path.join(root, "benchmark", "traffic",
                           "tiny-reason.json"), "w") as f:
        json.dump(traffic, f)
    bench["configs"].append({"name": "tiny-share", "source": "test",
                             "file": "benchmark/configs/tiny-share.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-reason-cell",
                               "config": "tiny-share",
                               "traffic": "tiny-reason", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny-reason-cell")
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
        "bm_share_run", os.path.join(root, "benchmark", "run.py"))
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
                rc = run_py.main(["--workload", "tiny-reason-cell",
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
    # a probe through each of the two prefill programs, 9 positions each
    assert any(x.startswith("logit comparison: 18 positions")
               for x in before)
    assert any("serve_share: engine up" in x and "experts 4-7 of 16 held"
               in x for x in before)


def test_end_to_end_line_reports_out_tok_s_and_setup_s(results):
    metrics = results[0][1]["metrics"]
    assert set(metrics) == {"out_tok_s", "setup_s"}
    assert metrics["out_tok_s"]["value"] > 0


def test_traced_line_reports_the_share_and_no_device_metric(results):
    metrics = results[1][1]["metrics"]
    assert {"moe_held_share", "moe_held_experts_touched",
            "moe_held_load_imbalance", "latent_share_prefill_mfu",
            "prefill_fill.batch", "prefill_share.batch",
            "batch_occupancy.batch", "compiles_in_window.batch"} \
        <= set(metrics)
    # 4 of 16 experts held; a tiny random router is not even
    assert 5 < metrics["moe_held_share"]["value"] < 60
    assert 0 < metrics["moe_held_experts_touched"]["value"] <= 100
    assert metrics["moe_held_load_imbalance"]["value"] >= 1.0
    assert metrics["compiles_in_window.batch"]["value"] == 0
    # a CPU run holds no device trace: the roofline share is left out;
    # and xing4's own readers are not this cell's
    assert "latent_share_decode_roofline" not in metrics
    assert not set(XING4_ONLY) & set(metrics)


# -- the readers on a recorded run ----------------------------------------

def recorded_run():
    start = {"t": 100.0, "decode_batches_total": 10,
             "latent_tokens_read_total": 1000,
             "moe_decode_experts_touched_total": 500,
             "moe_decode_expert_calls_total": 1000,
             "moe_max_load_total": 100, "moe_assignments_total": 4000,
             "moe_held_assignments_total": 300,
             "prefill_dispatch_s_total": 1.0, "chunk_dispatch_s_total": 0.0,
             "prefill_tokens_total": 10000, "generated_tokens_total": 50,
             "prefill_total": 5}
    end = {"t": 150.0, "decode_batches_total": 110,
           "latent_tokens_read_total": 1000 + 400 * 64 * 1000,
           "moe_decode_experts_touched_total": 500 + 400 * 4 * 14,
           "moe_decode_expert_calls_total": 1000 + 400 * 4 * 16,
           "moe_max_load_total": 100 + 9000,
           "moe_assignments_total": 4000 + 960000,
           "moe_held_assignments_total": 300 + 60000,
           "prefill_dispatch_s_total": 5.0, "chunk_dispatch_s_total": 0.0,
           "prefill_tokens_total": 10000 + 3 * 512,
           "generated_tokens_total": 6000, "prefill_total": 45}
    requests = [{"first_token": 110.0 + i, "prompt_len": 512,
                 "in_sample": True, "error": None, "n_out": 100}
                for i in range(3)]
    requests.append({"first_token": 99.0, "prompt_len": 1024,
                     "in_sample": False, "error": None, "n_out": 10})
    trace = {"programs": {"decode": {"count": 16, "seconds": 16 * 0.080},
                          "prefill": {"count": 4, "seconds": 0.1}}}
    return {"kind": "serve", "config": published(), "chips": 1,
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
            "engine": {"decode_block": 4, "max_batch": 64},
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
    needed = work.decode_step_bytes(published(), positions=64 * 1000,
                                    experts_touched=14)
    assert reader("latent_share_decode_roofline")(run) == pytest.approx(
        100 * (needed / 819e9) / 0.020)
    assert reader("latent_share_decode_roofline")(run) < 100
    run["trace"] = None
    assert reader("latent_share_decode_roofline")(run) is None


def test_prefill_mfu_reader_takes_the_measured_held_share():
    run = recorded_run()
    flops = 3 * work.prefill_flops(published(), 512, 1 / 16)
    assert reader("latent_share_prefill_mfu")(run) == pytest.approx(
        100 * flops / 4.0 / 197e12)


def test_counter_readers_take_the_windows_differences():
    run = recorded_run()
    assert reader("moe_held_share")(run) == pytest.approx(6.25)
    assert reader("moe_held_experts_touched")(run) == pytest.approx(87.5)
    assert reader("moe_held_load_imbalance")(run) == pytest.approx(
        16 * 9000 / 60000)


@pytest.mark.parametrize("name", NEW)
def test_readers_return_nothing_on_the_other_configurations(name):
    """xing4's configuration holds every expert (no ``experts_held``) though
    its engine keeps the counters; the parent's engine lacks the new
    counter; a Llama configuration has neither; a training run nothing."""
    for config in ("xing4.0-29b-a4b.json", "mistral-7b-v0.3.json"):
        run = recorded_run()
        run["config"] = _read(ROOT, "benchmark", "configs", config)
        assert reader(name)(run) is None
    run = recorded_run()
    for edge in ("start", "end"):
        run["edges"][edge] = {
            k: v for k, v in run["edges"][edge].items()
            if not k.startswith(("moe_", "latent_"))}
    assert reader(name)(run) is None
    assert reader(name)({"kind": "train", "config": {}}) is None


# -- BENCHMARK.json ---------------------------------------------------------

def test_benchmark_json_names_the_cell_its_traffic_and_its_metric_lists():
    b = _read(ROOT, "BENCHMARK.json")
    cell = b["workloads"][-1]
    assert cell == dict(cell, name=CELL, config="deepseek-v3-ep16",
                        traffic="reason-closed", chips=1)
    assert len(cell["why"]) <= 200 and "1/16" in cell["why"]
    assert b["configs"][-1]["name"] == "deepseek-v3-ep16"
    listed = {m["name"] for m in b["end_to_end"] + b["per_layer"]
              if CELL in m.get("workloads", ())}
    generic = {n + ".batch" for n in (
        "compiles_in_window", "batch_occupancy", "pages_peak",
        "tpot_p90_ms", "decode_step_ms", "device_idle", "peak_hbm_gb",
        "engine_host_ms", "decode_dispatch_ms", "prefill_share",
        "prefill_fill", "engine_idle_share")}
    assert listed == {"out_tok_s"} | generic | set(NEW)
    assert [m["name"] for m in b["per_layer"][-5:]] == list(NEW)
    for m in b["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "out_tok_s"
            assert m["unit"] == ("ratio" if "imbalance" in m["name"]
                                 else "%")
        if m["name"] in XING4_ONLY:
            assert m["workloads"] == ["xing4-serve-docs"]
    roof = next(m for m in b["per_layer"]
                if m["name"] == "latent_share_decode_roofline")
    assert (roof["source"], roof["layer"]) == ("device_trace", "Kernels")


def test_traffic_file_is_the_issues_letter_for_letter():
    t = _read(ROOT, "benchmark", "traffic", "reason-closed.json")
    assert (t["loop"], t["clients"], t["list_len"], t["order_seed"]) \
        == ("closed", 128, 512, 0)
    assert t["prompt_len"] == {"dist": "lognormal", "median": 256,
                               "sigma": 0.7, "min": 64, "max": 1024}
    assert t["output_len"] == {"dist": "lognormal", "median": 512,
                               "sigma": 0.5, "min": 256, "max": 1024}
    assert t["lead_in_s"] >= 15 and "measured" in t["lead_in_why"]
    e = published()["builder"]["engine"]
    assert t["prompt_len"]["max"] <= e["prompt_buckets"][-1]
    assert t["output_len"]["max"] <= e["max_new_tokens"]
    assert t["clients"] <= e["max_queue"]
