"""The cell ling3flash-serve-longanswers: its configuration against the
catalog's row, its traffic against ISSUE 62, its builder, reference, work
file and readers, at a tiny size on the CPU, on a run made by hand and on a
run RECORDED on the chip (tests/benchmark/data/
run_ling3flash_longanswers.json), as test_bm_hybrid_conv.py does for
lfm2moe-serve-assist-wide. Entries of BENCHMARK.json are found BY NAME,
never by position or count.
"""
import contextlib
import importlib.util
import io
import json
import math
import os
import shutil

import pytest

from benchmark import work_kda_latent as work
from benchmark.builders import serve_kda_latent

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL, CONFIG, TRAFFIC = ("ling3flash-serve-longanswers",
                         "ling-3.0-flash-ep4", "longanswers-closed")
CUT = {"num_hidden_layers": (42, 6), "first_k_dense_replace": (2, 1),
       "num_experts": (512, 128), "vocab_size": (157184, 39296)}
NEW = ("kda_latent_decode_roofline", "kda_latent_prefill_mfu",
       "kda_latent_state_bytes_share", "kda_latent_rows_per_expert",
       "kda_latent_load_imbalance")
BATCH = ("compiles_in_window.batch", "batch_occupancy.batch",
         "pages_peak.batch", "tpot_p90_ms.batch", "decode_step_ms.batch",
         "device_idle.batch", "peak_hbm_gb.batch", "engine_host_ms.batch",
         "decode_dispatch_ms.batch", "prefill_fill.batch",
         "engine_idle_share.batch")

TINY = dict(hidden_size=32, intermediate_size=64, num_attention_heads=2,
            head_dim=8, kv_lora_rank=16, qk_nope_head_dim=8,
            qk_rope_head_dim=8, v_head_dim=8, vocab_size=96,
            num_experts=4, num_experts_per_tok=3, n_group=4, topk_group=2,
            moe_intermediate_size=16,
            moe_shared_expert_intermediate_size=16,
            experts_held={"first": 4, "count": 4, "of": 16},
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
    c = dict(published(), **TINY, name="tiny-kda")
    c["builder"] = {"kind": "serve_kda_latent", "engine": dict(TINY_ENGINE)}
    return c


def by_name(entries, name):
    return next(e for e in entries if e["name"] == name)


# -- the configuration ----------------------------------------------------

def test_configuration_carries_every_published_key_and_cuts_four():
    """Letter for letter against the catalog's row: every key of its
    ``config`` is in the file under the same name with the same value
    (nested groups whole), but for the four that ``reduced`` names, whose
    published values stand under ``published``."""
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Ling-3.0-flash-VL")
    c = published()
    entry = by_name(_read(ROOT, "BENCHMARK.json")["configs"], CONFIG)
    assert entry["source"] == c["source"] == row["source_url"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert entry["reduced"] == c["reduced"] == list(CUT)
    for key, value in row["config"].items():
        if key in CUT:
            assert (value, c[key]) == CUT[key], key
            assert c["published"][key] == value, key
            assert key in c["reduced_why"], key
        else:
            assert c[key] == value, key
    assert c["experts_held"] == {"first": 0, "count": 128, "of": 512}
    assert c["vocab_rows_held"] == {"first": 0, "count": 39296,
                                    "of": 157184}
    assert c["layer_types"] == ["kda", "kda", "kda", "kda", "mla", "kda"]
    assert c["published_first_layer"] == 1
    # no width among the cuts
    assert not set(c["reduced"]) & {
        "hidden_size", "intermediate_size", "moe_intermediate_size",
        "moe_shared_expert_intermediate_size", "head_dim", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "num_attention_heads", "num_experts_per_tok"}


def test_configuration_states_its_deployment_assumptions_and_departures():
    c = published()
    for words in ("Four TPU v5e chips", "expert-parallel", "experts 0-127",
                  "rows 0-39,295", "WITHOUT its exchange", "a quarter"):
        assert words in c["deployment"], words
    for key in ("layer_types", "kda_projections", "kda_conv", "kda_gate",
                "kda_qk_norm", "kda_output", "kda_state", "mla", "mla_gate",
                "block", "router", "shared_expert", "torch_dtype"):
        assert key in c["assumed"], key
    said = " ".join(c["departures"])
    for words in ("random from --seed", "TAPS", "A_LOG", "SELECTION BIAS",
                  "vision tower", "multi-token-prediction", "clamp",
                  "11,264"):
        assert words in said, words
    assert c["reference"].startswith(
        "benchmark/reference/kda_latent_moe_share.py")
    e = c["builder"]["engine"]
    assert (e["max_batch"], e["page_size"], e["chunk_size"],
            e["max_new_tokens"], e["decode_block"]) == (256, 64, 2048,
                                                        3072, 4)
    assert max(e["prompt_buckets"]) + e["max_new_tokens"] == 11264
    assert "TO BE WRITTEN" not in c["builder"]["engine_why"]


def test_model_config_carries_the_published_widths():
    cfg = serve_kda_latent.model_config(published())
    assert (cfg.dim, cfg.n_heads, cfg.kda_key_dim, cfg.kda_value_dim,
            cfg.d_conv, cfg.gate_floor) == (2560, 32, 128, 128, 4, -5.0)
    assert (cfg.kv_rank, cfg.nope_dim, cfg.rope_dim, cfg.v_dim,
            cfg.rope_base, cfg.stored_dim) == (512, 128, 64, 128, 6e6, 640)
    assert (cfg.router_width, cfg.n_experts, cfg.experts_first, cfg.n_group,
            cfg.topk_group, cfg.moe_top_k, cfg.expert_hidden,
            cfg.route_scale) == (512, 128, 0, 8, 4, 8, 768, 2.5)
    assert (cfg.ffn_hidden, cfg.vocab_size, cfg.n_dense_layers,
            cfg.layer_pattern) == (6144, 39296, 1, (1, 1, 1, 1, 0, 1))
    assert serve_kda_latent.picks_reach(cfg) == 9
    with pytest.raises(ValueError):
        serve_kda_latent.model_config(dict(published(), q_lora_rank=1536))
    with pytest.raises(ValueError):     # a layer retyped
        serve_kda_latent.model_config(dict(
            published(), layer_types=["kda"] * 5 + ["mla"]))


def test_the_bytes_the_configuration_states_are_its_shapes():
    c, m = published()["bytes"], published()
    cfg = serve_kda_latent.model_config(m)
    shapes = cfg.param_shapes()

    def count(names):
        return sum(math.prod(shapes[n][0]) for n in names)

    kda = [n for n in shapes if n.startswith("lead.")
           and not n.startswith(("lead.w_", "lead.attn_norm",
                                 "lead.mlp_norm"))]
    assert count(kda) == 63_049_888 and "63.05 M" in c["kda_mixer"]
    assert work.mixer_params(m, 0) == 6 * 2560 * 4096 + 2560 * 32
    assert work.mixer_params(m, 4) == 31_965_184 and "31.96 M" in c[
        "mla_mixer"]
    assert work.expert_params(m) == 5_898_240 and "5.898 M" in c["expert"]
    total = count(shapes)
    assert total == 4_406_550_816 and "4.407 G" in c["weights"]
    assert work.state_bytes(m) == 32 * 128 * 128 * 4 + 2 * 3 * 12288
    assert "10.49 MB" in c["state"] and "1,280 B" in c["latent_pool"]
    assert work.entry_bytes(m) == 1152


# -- the work file, by hand -------------------------------------------------

TINY_M = dict(hidden_size=8, num_attention_heads=2, head_dim=4,
              kv_lora_rank=6, qk_nope_head_dim=4, qk_rope_head_dim=2,
              v_head_dim=4, vocab_size=10, intermediate_size=16,
              moe_intermediate_size=3, num_experts_per_tok=2,
              short_conv_kernel_size=4, num_hidden_layers=3,
              first_k_dense_replace=1, layer_types=["kda", "mla", "kda"],
              experts_held={"first": 0, "count": 2, "of": 8})


def test_work_counts_one_tiny_layer_of_each_kind_by_hand():
    m = TINY_M
    kda = 6 * 8 * 8 + 8 * 2
    mla = 8 * 2 * 6 + 8 * 8 + 6 * 2 * 8 + 8 * 2 + 2 * 4 * 8
    assert (work.mixer_params(m, 0), work.mixer_params(m, 1)) == (kda, mla)
    assert (work.layers_of(m, "kda"), work.routed_layers(m)) == (2, 2)
    assert work.state_bytes(m) == 4 * 2 * 4 * 4 + 2 * 3 * 3 * 2 * 4
    assert work.entry_bytes(m) == 2 * 8
    # a prompt of 5, half of a token's 2 picks held: the head once; the
    # dense kda layer; the routed mla layer (router 8 wide, 1 + 1
    # experts); the routed kda layer; the rule and its taps twice; the
    # latent layer's 15 keys
    expert = 3 * 8 * 3
    routed = 8 * 8 + (2 * 0.5 + 1) * expert
    rule = 2 * 5 * 2 * (3 * 16 + 2 * 64 * 4) + 2 * 5 * 3 * 2 * 4 * 4
    want = 2 * 8 * 10 + 2 * 5 * (kda + 3 * 8 * 16) \
        + 2 * 5 * (mla + routed) + 2 * 5 * (kda + routed) + 2 * rule \
        + 2 * 2 * (4 + 2 + 4) * 15
    assert work.prefill_flops(m, 5, 0.5) == want
    # a step of 3 live rows: 2 kda layers' states both ways, 40 latent
    # positions, 1.5 held experts reached a routed layer
    fixed = 2 * 8 * 10 + 2 * (2 * kda + mla) + 2 * 3 * 8 * 16 \
        + 2 * (4 * 8 * 8 + 2 * expert)
    assert work.decode_step_parts(m, 6, 40, 1.5) == (
        fixed, 2 * 2 * 1.5 * expert, 2 * work.state_bytes(m) * 6, 16 * 40)


def test_decode_step_bytes_at_the_published_widths():
    """ISSUE 62's count of a 256-row step: 7.4 GB of held experts (98% of
    5 x 128 reached), 5.5 GB of state both ways, 1.1 GB of other weights,
    0.6-0.9 GB of latent pages: the two new mechanisms most of its
    bytes."""
    m = published()
    fixed, experts, state, latent = work.decode_step_parts(
        m, state_updates=5 * 256, latent_positions=256 * 2000,
        experts_touched=125)
    assert (round(fixed / 1e9, 2), round(experts / 1e9, 2),
            round(state / 1e9, 2), round(latent / 1e9, 2)) == (
                1.07, 7.37, 5.56, 0.59)
    total = fixed + experts + state + latent
    assert 0.85 < (experts + state) / total < 0.90
    assert 17.5 < 1e3 * total / 819e9 < 18.0            # ms a step
    # a prompt of 2,048 with a quarter of its picks held: 2.1 TFLOP
    assert 2.0e12 < work.prefill_flops(m, 2048, 0.25) < 2.2e12


# -- the cell through run.py, tiny -------------------------------------------

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
                           "tiny-kda.json"), "w") as f:
        json.dump(tiny_config(), f)
    traffic = _read(ROOT, "benchmark", "traffic", TRAFFIC + ".json")
    traffic.update(name="tiny-long", clients=8, list_len=32, lead_in_s=0.5,
                   prompt_len=dict(traffic["prompt_len"], median=10, min=4,
                                   max=40),
                   output_len=dict(traffic["output_len"], median=5, min=2,
                                   max=8))
    with open(os.path.join(root, "benchmark", "traffic",
                           "tiny-long.json"), "w") as f:
        json.dump(traffic, f)
    bench["configs"].append({"name": "tiny-kda", "source": "test",
                             "file": "benchmark/configs/tiny-kda.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-long-cell",
                               "config": "tiny-kda", "traffic": "tiny-long",
                               "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny-long-cell")
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
        "bm_kda_run", os.path.join(root, "benchmark", "run.py"))
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
                rc = run_py.main(["--workload", "tiny-long-cell",
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
    assert any("serve_kda_latent: engine up" in x
               and "experts 4-7 of 16 held" in x for x in before)
    assert any(x.startswith("state kind after the window") for x in before)
    assert set(line["metrics"]) >= ({"out_tok_s", "setup_s"} if not trace
                                    else {"kda_latent_state_bytes_share",
                                          "kda_latent_rows_per_expert",
                                          "kda_latent_load_imbalance",
                                          "kda_latent_prefill_mfu"})
    # no device trace on a CPU: the share of a roofline is left out
    assert "kda_latent_decode_roofline" not in line["metrics"]


# -- the readers, on a run made by hand --------------------------------------

def handmade_run():
    # 400 steps x 256 rows: 5 kda layers, 1 latent layer x 1,500
    # positions; 5 routed layers x 128 held experts a step, 125 reached
    start = {"t": 100.0, "decode_batches_total": 10,
             "attn_latent_positions_total": 1000,
             "kda_state_updates_total": 500,
             "moe_decode_experts_touched_total": 100,
             "moe_decode_expert_calls_total": 1280,
             "moe_max_load_total": 50, "moe_assignments_total": 4000,
             "moe_held_assignments_total": 1000,
             "prefill_dispatch_s_total": 1.0, "chunk_dispatch_s_total": 2.0,
             "prefill_tokens_total": 10000}
    end = {"t": 150.0, "decode_batches_total": 110,
           "attn_latent_positions_total": 1000 + 400 * 256 * 1500,
           "kda_state_updates_total": 500 + 400 * 256 * 5,
           "moe_decode_expert_calls_total": 1280 + 400 * 640,
           "moe_decode_experts_touched_total": 100 + 400 * 625,
           "moe_max_load_total": 50 + 9000,
           "moe_assignments_total": 4000 + 2560000,
           "moe_held_assignments_total": 1000 + 640000,
           "prefill_dispatch_s_total": 6.5, "chunk_dispatch_s_total": 2.5,
           "prefill_tokens_total": 10000 + 3 * 400}
    requests = [{"first_token": 110.0 + i, "prompt_len": 400}
                for i in range(3)]
    requests.append({"first_token": 99.0, "prompt_len": 64})
    trace = {"programs": {"decode": {"count": 16, "seconds": 16 * 0.100},
                          "prefill": {"count": 15, "seconds": 15 * 0.011},
                          "chunk": {"count": 2, "seconds": 2 * 0.030}},
             "ops": {}}
    return {"kind": "serve", "config": published(), "chips": 1,
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
            "engine": {"decode_block": 4, "max_batch": 256},
            "t0": 100.0, "t_end": 150.0, "requests": requests,
            "trace": trace,
            "edges": {"start": start, "end": end,
                      "trace_start": {"decode_batches_total": 50,
                                      "decode_dispatch_s_total": 3.0},
                      "trace_end": {
                          "decode_batches_total": 66,
                          "decode_dispatch_s_total": 3.0 + 16 * 0.102}}}


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "bm_reader_" + name,
        os.path.join(ROOT, "benchmark", "metrics", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def test_the_readers_take_the_windows_differences():
    run = handmade_run()
    m = run["config"]
    parts = work.decode_step_parts(m, 5 * 256, 256 * 1500, 125.0)
    # the decode program by count AND duration: 100 ms a dispatch of 4
    assert reader("kda_latent_decode_roofline")(run) == pytest.approx(
        100.0 * sum(parts) / 819e9 / 0.025)
    assert reader("kda_latent_state_bytes_share")(run) == pytest.approx(
        100.0 * parts[2] / sum(parts))
    # 256 rows x 8 picks x a quarter held over 125 reached
    assert reader("kda_latent_rows_per_expert")(run) == pytest.approx(
        256 * 8 * 0.25 / 125.0)
    assert reader("kda_latent_load_imbalance")(run) == pytest.approx(
        9000 * 128 / 640000)
    # three prompts of 400, a quarter of their picks held, in 6 s
    assert reader("kda_latent_prefill_mfu")(run) == pytest.approx(
        100.0 * 3 * work.prefill_flops(m, 400, 0.25) / 6.0 / 197e12)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_in_a_run_of_another_configuration(name):
    """On the parent's program (no such counters at the edges), on another
    model's run and on a training run the readers return None and do not
    raise."""
    run = handmade_run()
    other = dict(run, config=_read(ROOT, "benchmark", "configs",
                                   "lfm2-24b-a2b.json"))
    assert reader(name)(other) is None
    bare = dict(run, edges={k: {"t": v["t"]} if "t" in v else {}
                            for k, v in run["edges"].items()})
    assert reader(name)(bare) is None
    assert reader(name)(dict(run, kind="train")) is None
    if name == "kda_latent_decode_roofline":
        assert reader(name)(dict(run, trace=None)) is None


# -- the readers on a run recorded on the chip -------------------------------

def recorded_run():
    run = _read(HERE, "data", "run_ling3flash_longanswers.json")
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
    assert run["line"]["correct"] and not run["line"]["failed"]
    assert run["line"]["metrics"]["compiles_in_window.batch"]["value"] == 0
    ops = run["trace"]["ops"]
    # the 48 longest of the trace: the latent layer's decode kernel, the
    # held experts' three ragged_dot, the state's slab where it lies
    assert any("paged_latent_decode" in op for op in ops)
    assert any("ragged-dot" in op for op in ops)
    assert any("f32[5,257,32,128,128]" in op for op in ops)


# -- BENCHMARK.json and the traffic, by name ---------------------------------

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
    assert by_name(bench["per_layer"],
                   "kda_latent_decode_roofline")["source"] == "device_trace"
    # the cell joins no other list
    named = {m["name"] for m in bench["per_layer"]
             if CELL in m.get("workloads", ())}
    assert named == set(BATCH) | set(NEW)
    assert sum(c["chips"] == 4 for c in bench["workloads"]) == 1


def test_the_traffic_file_is_issue_62s_letter_for_letter():
    traffic = _read(ROOT, "benchmark", "traffic", TRAFFIC + ".json")
    assert (traffic["loop"], traffic["clients"], traffic["list_len"],
            traffic["order_seed"]) == ("closed", 512, 1024, 0)
    assert traffic["prompt_len"] == dict(dist="lognormal", median=1024,
                                         sigma=0.9, min=128, max=8192)
    assert traffic["output_len"] == dict(dist="lognormal", median=1024,
                                         sigma=0.5, min=256, max=3072)
    assert 60.0 <= traffic["lead_in_s"] <= 90.0
    assert traffic["sharing"].startswith("none")
    e = published()["builder"]["engine"]
    assert traffic["clients"] == 2 * e["max_batch"]
    assert traffic["prompt_len"]["max"] == max(e["prompt_buckets"])
    assert traffic["output_len"]["max"] == e["max_new_tokens"]
