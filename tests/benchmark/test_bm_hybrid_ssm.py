"""The cell jamba2-serve-reason: its configuration against the catalog's
row, its traffic, builder, reference, work file and readers, at a tiny size
on the CPU and on a recorded run, as test_bm_hybrid_share.py does for
mimov2flash-serve-mixed. Entries of BENCHMARK.json are found by name.
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

from benchmark import work_hybrid_ssm as work
from benchmark.builders import serve_ssm
from benchmark.reference import hybrid_ssm as ref

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL, CONFIG, TRAFFIC = ("jamba2-serve-reason", "ai21-jamba2-3b",
                         "reason-wide-closed")
NEW = ("ssm_decode_roofline", "ssm_prefill_mfu", "state_cache_share")
BATCH = ("compiles_in_window.batch", "batch_occupancy.batch",
         "pages_peak.batch", "tpot_p90_ms.batch", "decode_step_ms.batch",
         "device_idle.batch", "peak_hbm_gb.batch", "engine_host_ms.batch",
         "decode_dispatch_ms.batch", "prefill_fill.batch",
         "engine_idle_share.batch")

TINY = dict(hidden_size=24, intermediate_size=48, num_attention_heads=4,
            num_key_value_heads=1, num_hidden_layers=6,
            attn_layer_period=3, attn_layer_offset=1, mamba_d_state=4,
            mamba_d_conv=4, mamba_dt_rank=6, mamba_expand=2, vocab_size=96,
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
    c = dict(published(), **TINY, name="tiny-ssm")
    c["builder"] = {"kind": "serve_ssm", "engine": dict(TINY_ENGINE)}
    return c


def by_name(entries, name):
    return next(e for e in entries if e["name"] == name)


# -- the configuration ----------------------------------------------------

def test_configuration_carries_every_published_key_and_cuts_nothing():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "AI21-Jamba2-3B")
    c = published()
    assert c["source"] == row["source_url"]
    assert [k for k, v in row["config"].items() if c.get(k) != v] == []
    assert c["reduced"] == [] and c["published"] == {}
    entry = by_name(_read(ROOT, "BENCHMARK.json")["configs"], CONFIG)
    assert entry["reduced"] == [] and entry["source"] == c["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    assert (c["num_hidden_layers"], c["vocab_size"]) == (28, 65536)
    assert [i for i in range(28) if ref.is_attention(c, i)] == [7, 21]


def test_configuration_states_its_deployment_assumptions_and_departures():
    c = published()
    for said in ("one TPU v5e chip", "WHOLE model", "all 28 layers",
                 "Nothing is cut", "a replica a chip"):
        assert said in c["deployment"], said
    assert {"layer_order", "torch_dtype", "head_dim", "attention", "mamba",
            "block", "state"} <= set(c["assumed"])
    assert "NO rotary" in c["assumed"]["attention"]
    assert "FLOAT32" in c["assumed"]["state"]
    assert any("PUBLISHED INITIALISATION" in d for d in c["departures"])
    assert any("0.999 to 0.2" in d for d in c["departures"])
    assert any("transpose" in d for d in c["departures"])
    assert {"mamba_layer", "attention_layer", "weights", "state",
            "attention_cache"} <= set(c["bytes"])
    assert c["torch_dtype"] == "bfloat16"
    assert c["builder"]["kind"] == "serve_ssm"
    e = c["builder"]["engine"]
    assert (e["max_batch"], e["max_new_tokens"], e["decode_block"],
            e["page_size"], e["chunk_size"], e["max_queue"]) \
        == (128, 2048, 4, 64, 2048, 256)
    assert e["prompt_buckets"][:4] == [256, 512, 1024, 2048]
    assert e["prompt_buckets"][-1] >= 5000 and "quantize" not in e


def test_model_config_carries_the_published_widths():
    cfg = serve_ssm.model_config(published())
    assert (cfg.dim, cfg.n_layers, cfg.n_heads, cfg.n_kv, cfg.head_dim,
            cfg.ffn_hidden) == (2560, 28, 20, 1, 128, 8192)
    assert (cfg.d_inner, cfg.d_state, cfg.d_conv, cfg.dt_rank) \
        == (5120, 16, 4, 160)
    assert (cfg.attn_period, cfg.attn_offset, cfg.vocab_size,
            cfg.norm_eps, cfg.dtype) == (14, 7, 65536, 1e-6, "bfloat16")
    assert (cfg.layers_of(0), cfg.layers_of(1)) == (2, 26)
    # three runs of Mamba layers around attention layers 7 and 21
    runs = [len(list(g)) for k, g in __import__("itertools").groupby(
        cfg.layer_kinds) if k == 1]
    assert runs == [7, 13, 6]
    for wrong in (dict(num_experts=16), dict(sliding_window=4096),
                  dict(tie_word_embeddings=False),
                  dict(mamba_proj_bias=True), dict(model_type="mamba")):
        with pytest.raises(ValueError):
            serve_ssm.model_config(dict(published(), **wrong))


def test_the_bytes_the_configuration_states_are_its_shapes():
    m = published()
    cfg = serve_ssm.model_config(m)
    shapes = cfg.param_shapes()
    count = lambda pre: sum(int(np.prod(s)) for n, (s, _) in shapes.items()
                            if n.startswith(pre))
    assert count("ssm.") / 26 == pytest.approx(104.2e6, rel=0.001)
    assert count("full.") / 2 == pytest.approx(76.7e6, rel=0.001)
    assert count("tok_emb") == 65536 * 2560 == count("lm_head")
    # the work file counts what the programs hold, the tied head once
    held = sum(count(p) for p in ("ssm.", "full.", "tok_emb", "final"))
    assert work.parameters(m) == held
    assert held == pytest.approx(3.03e9, rel=0.002)
    assert 2 * held == pytest.approx(6.06e9, rel=0.002)
    assert work.mamba_params(m) + work.mamba_small_params(m) \
        == pytest.approx(41.25e6, rel=0.001)
    assert work.attention_params(m) == pytest.approx(13.76e6, rel=0.001)
    assert work.swiglu_params(m) == 3 * 2560 * 8192
    # the state: S float32 and a bf16 tail, 26 layers, whatever the length
    assert work.state_entry_bytes(m) == 5120 * 16 * 4 + 3 * 5120 * 2
    assert work.state_bytes(m) == pytest.approx(9.32e6, rel=0.001)
    specs = cfg.build_paged_programs(
        max_batch=128, page_size=64, n_pages=14465, pages_per_seq=113,
        prompt_buckets=(256, 5120), chunk_size=2048).pool_specs
    assert specs == [([2, 14465, 64, 128], "bfloat16")] * 2 + [
        ([26, 129, 16, 5120], "float32"), ([26, 129, 15360], "bfloat16")]
    state_pools = sum(int(np.prod(s)) * (4 if dt == "float32" else 2)
                      for s, dt in specs[2:])
    assert state_pools == 129 * work.state_bytes(m)
    assert state_pools == pytest.approx(1.20e9, rel=0.005)
    # 1,024 B a position over the two attention layers
    assert work.n_layers(m, True) * work.kv_entry_bytes(m) == 1024
    kv_pools = sum(int(np.prod(s)) * 2 for s, _ in specs[:2])
    assert kv_pools == 14465 * 65536 == pytest.approx(0.95e9, rel=0.005)


def test_the_stand_ins_are_the_published_initialisation_for_every_seed():
    cfg = serve_ssm.model_config(tiny_config())
    made = [serve_ssm.stand_ins(cfg, serve_ssm.make_weights(cfg, s))
            for s in (1, 2)]
    for name in ("ssm.a_log", "ssm.dt_bias", "ssm.d"):
        assert np.array_equal(made[0][name], made[1][name])
    a_log = np.asarray(made[0]["ssm.a_log"])
    assert a_log.shape == (4, 4, 48)
    assert np.allclose(np.exp(a_log[0, :, 0]), [1, 2, 3, 4])
    assert (a_log == a_log[0][:, :1]).all()
    dt = np.log1p(np.exp(np.asarray(made[0]["ssm.dt_bias"], np.float64)))
    assert dt.shape == (4, 48) and (dt == dt[0]).all()
    assert dt[0, 0] == pytest.approx(1e-3, rel=1e-3)
    assert dt[0, -1] == pytest.approx(1e-1, rel=1e-3)
    assert np.allclose(np.diff(np.log(dt[0])), np.log(100) / 47, rtol=1e-3)
    # decays a token from 0.999 (slowest state, smallest step) to 0.67 at
    # N = 4; at the published N = 16 and dt = 0.1: exp(-1.6) = 0.2
    assert np.exp(-dt[0, 0]) == pytest.approx(0.999, abs=1e-4)
    assert np.exp(-16 * 0.1) == pytest.approx(0.2, abs=0.002)
    assert (np.asarray(made[0]["ssm.d"]) == 1).all()
    w = serve_ssm.make_weights(cfg, 1)
    assert np.array_equal(made[0]["lm_head"], np.asarray(w["tok_emb"]).T)


# -- the work file --------------------------------------------------------

TINY_M = dict(hidden_size=8, num_attention_heads=4, num_key_value_heads=1,
              intermediate_size=10, vocab_size=7, num_hidden_layers=3,
              attn_layer_period=3, attn_layer_offset=1, mamba_expand=2,
              mamba_d_state=3, mamba_d_conv=4, mamba_dt_rank=2)


def test_work_counts_one_tiny_layer_of_each_kind_by_hand():
    m = TINY_M
    assert (work.n_layers(m, False), work.n_layers(m, True)) == (2, 1)
    assert work.d_inner(m) == 16 and work.head_dim(m) == 2
    # in_proj 8x32, x_proj 16x(2+6), dt_proj 2x16, out_proj 16x8
    assert work.mamba_params(m) == 256 + 128 + 32 + 128
    # A_log 16x3, conv 16x4, conv bias + D + dt bias 3x16, norms 2+3+3
    assert work.mamba_small_params(m) == 48 + 64 + 48 + 8
    # q and o 8x8 each, k and v 8x2 each
    assert work.attention_params(m) == 2 * 64 + 2 * 16
    assert work.state_entry_bytes(m) == 16 * 3 * 4 + 3 * 16 * 2
    assert work.kv_entry_bytes(m) == 2 * 2 * 2
    per_token = 2 * (544 + 240) + (160 + 240)
    # 5 tokens: an attention layer sees 1+2+3+4+5 keys
    assert work.prefill_flops(m, 5) == 2 * 5 * per_token + 2 * 8 * 7 \
        + 2 * 4 * 2 * 2 * 15
    assert work.scan_flops(m, 5) == 7 * 2 * 5 * 16 * 3
    none = work.decode_step_bytes(m, 0, 0)
    assert none == 2 * (per_token + 8 * 7) + 4 * 2 * 16 * (3 + 2)
    # a live row in one layer: its entry read and written
    assert work.decode_step_bytes(m, 1, 0) - none == 2 * (192 + 96)
    assert work.decode_step_bytes(m, 0, 10) - none == 10 * 8


def test_decode_step_bytes_at_the_published_widths():
    m = published()
    none = work.decode_step_bytes(m, 0, 0)
    assert none == pytest.approx(6.06e9, rel=0.002)
    # 128 live rows: 2 x 128 x 9.32 MB of state a step
    state = work.decode_step_bytes(m, 26 * 128, 0) - none
    assert state == 2 * 128 * work.state_bytes(m)
    assert state == pytest.approx(2.39e9, rel=0.002)
    # 128 rows at 1,000 positions in both attention layers: 0.13 GB
    assert work.decode_step_bytes(m, 0, 2 * 128 * 1000) - none \
        == 128 * 1000 * 1024
    total = work.decode_step_bytes(m, 26 * 128, 2 * 128 * 1000)
    assert total / 819e9 == pytest.approx(10.5e-3, rel=0.02)
    # 2 x 2.86 G products a prompt token beside attention and the head
    f1, f2 = (work.prefill_flops(m, n) for n in (1, 2))
    head = 2 * 2560 * 65536
    per_token = (f2 - f1) - 2 * 2 * 20 * 2 * 128 * 2
    assert per_token == pytest.approx(2 * 2.86e9, rel=0.005)
    assert f1 - head - 2 * 2 * 20 * 2 * 128 == per_token
    assert work.prefill_flops(m, 512) == pytest.approx(2.9e12, rel=0.02)


# -- the reference against a second hand computation ---------------------

def test_reference_mamba_layer_is_the_equations_written_out_again():
    """One tiny Mamba layer on TWO tokens in numpy float64, from ISSUE
    39's equations and nothing of the reference's code: the convolution's
    zeros before position 0, the three inner norms, the recurrence from
    S = 0, the skip and the gate."""
    m = tiny_config()
    cfg = serve_ssm.model_config(m)
    w = serve_ssm.make_weights(cfg, 11)
    w.update(serve_ssm.stand_ins(cfg, w))
    w = {k: np.asarray(v, np.float64) for k, v in w.items()}
    rng = np.random.RandomState(1)
    for k in w:                               # alive norms, larger matrices
        if k.endswith("norm"):
            w[k] = w[k] + 0.1 * np.sin(np.arange(w[k].size)).reshape(
                w[k].shape)
        elif k.endswith(("conv_b", "ssm.d")):
            w[k] = 0.5 * rng.randn(*w[k].shape)
        elif not k.endswith(("a_log", "dt_bias")):
            w[k] = w[k] * 10
    D, C, N, R = 24, 48, 4, 6
    x = np.random.RandomState(0).randn(2, D)
    layer = 3                                 # ssm.*[2]
    got, (state, tail) = ref.layer(
        ref.from_stacked({k: v.astype(np.float32) for k, v in w.items()},
                         m), layer, x.astype(np.float32), m)

    norm = lambda v, g: v / np.sqrt((v * v).mean(-1, keepdims=True)
                                    + 1e-6) * g
    silu = lambda v: v / (1 + np.exp(-v))
    p = {k[len("ssm."):]: v[2] for k, v in w.items()
         if k.startswith("ssm.")}
    u = norm(x, p["attn_norm"])
    zg = u @ p["w_in"]
    z, g = zg[:, :C], zg[:, C:]
    # taps j = 0..3 meet inputs t - 3 + j; before position 0 they are zero
    c0 = silu(p["conv_b"] + p["conv_w"][3] * z[0])
    c1 = silu(p["conv_b"] + p["conv_w"][2] * z[0] + p["conv_w"][3] * z[1])
    S, ys = np.zeros((C, N)), []
    for c in (c0, c1):
        xp = c @ p["w_x"]
        dt_r = norm(xp[:R], p["dt_norm"])
        B = norm(xp[R:R + N], p["b_norm"])
        Cm = norm(xp[R + N:], p["c_norm"])
        dt = np.log1p(np.exp(dt_r @ p["w_dt"] + p["dt_bias"]))
        A = -np.exp(p["a_log"]).T             # [C, N], as published
        S = np.exp(dt[:, None] * A) * S + (dt * c)[:, None] * B[None, :]
        ys.append(S @ Cm + p["d"] * c)
    mixed = (np.stack(ys) * silu(g)) @ p["w_out"]
    h = x + mixed
    uf = norm(h, p["mlp_norm"])
    want = h + (silu(uf @ p["w_gate"]) * (uf @ p["w_up"])) @ p["w_down"]
    err = np.linalg.norm(np.asarray(got) - want, axis=-1) \
        / np.linalg.norm(want, axis=-1)
    assert err.max() < 1e-5
    assert np.allclose(np.asarray(state).T, S, rtol=1e-4, atol=1e-7)
    # the tail: the zeros before position 0, then the two inputs
    assert np.allclose(np.asarray(tail), [np.zeros(C), z[0], z[1]],
                       atol=1e-5)
    # and it has teeth: without the inner norms another answer
    off, _ = ref.layer(
        ref.from_stacked({k: v.astype(np.float32) for k, v in w.items()},
                         m), layer, x.astype(np.float32),
        dict(m, _inner_norms=False))
    assert np.abs(np.asarray(off) - want).max() > 1e-3


def test_reference_attention_layer_has_no_position_embedding():
    """Keys and values permuted together leave the last position's output
    as it was: nothing but the causal mask knows an order."""
    m = tiny_config()
    cfg = serve_ssm.model_config(m)
    w = ref.from_stacked({k: np.asarray(v) * (1 if k.endswith("norm")
                                              else 10)
                          for k, v in serve_ssm.make_weights(cfg, 5)
                          .items()}, m)
    x = np.random.RandomState(2).randn(6, 24).astype(np.float32)
    assert ref.is_attention(m, 1)
    out = np.asarray(ref.attention(w, 1, x, m))
    perm = [3, 0, 4, 2, 1, 5]               # the last token stays last
    again = np.asarray(ref.attention(w, 1, x[perm], m))
    assert np.allclose(out[-1], again[-1], atol=1e-5)


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
                           "tiny-ssm.json"), "w") as f:
        json.dump(tiny_config(), f)
    traffic = _read(ROOT, "benchmark", "traffic", TRAFFIC + ".json")
    traffic.update(name="tiny-reason", clients=8, list_len=32,
                   lead_in_s=0.5,
                   prompt_len=dict(traffic["prompt_len"], median=12, min=4,
                                   max=40),
                   output_len=dict(traffic["output_len"], median=5, min=2,
                                   max=8))
    with open(os.path.join(root, "benchmark", "traffic",
                           "tiny-reason.json"), "w") as f:
        json.dump(traffic, f)
    bench["configs"].append({"name": "tiny-ssm", "source": "test",
                             "file": "benchmark/configs/tiny-ssm.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-reason-cell",
                               "config": "tiny-ssm",
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
        "bm_ssm_run", os.path.join(root, "benchmark", "run.py"))
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
    # a short probe, one through each of the two whole-prompt programs,
    # one just over a chunk (16 + 1) and one of three chunks (16 + 16 + 8
    # tokens), 9 positions each
    assert any(x.startswith("logit comparison: 45 positions, limit 0.1; "
                            "5 states, limit 0.008") for x in before)
    assert any(x.startswith("probe of 2 tokens") for x in before)
    assert any(x.startswith("probe of 17 tokens") for x in before)
    assert any(x.startswith("probe of 40 tokens") for x in before)
    assert any("serve_ssm: engine up" in x and "4 state entries" in x
               for x in before)
    # every request that started was reset once; nothing was lost
    books = next(x for x in before
                 if x.startswith("state kind after the window"))
    assert "'pools_lost_total': 0" in books


def test_end_to_end_line_reports_out_tok_s_and_setup_s(results):
    metrics = results[0][1]["metrics"]
    assert set(metrics) == {"out_tok_s", "setup_s"}
    assert metrics["out_tok_s"]["value"] > 0


def test_traced_line_reports_the_state_kind_and_no_device_metric(results):
    metrics = results[1][1]["metrics"]
    assert {"state_cache_share", "ssm_prefill_mfu", "prefill_fill.batch",
            "batch_occupancy.batch", "pages_peak.batch",
            "compiles_in_window.batch", "engine_host_ms.batch",
            "decode_dispatch_ms.batch", "engine_idle_share.batch",
            "tpot_p90_ms.batch", "peak_hbm_gb.batch"} <= set(metrics)
    # an entry of the tiny model: 4 layers x (4 x 48 + 3 x 48) float32 =
    # 5,376 B beside a few pages of 2 positions x 2 layers x 2 x 6 x 4 B
    assert 50 < metrics["state_cache_share"]["value"] < 100
    assert metrics["compiles_in_window.batch"]["value"] == 0
    # a CPU run holds no device trace: the shares of a roofline are left out
    assert "ssm_decode_roofline" not in metrics
    assert "hybrid_share_decode_roofline" not in metrics
    assert "cache_bytes_per_token" not in metrics


# -- the readers on a recorded run ----------------------------------------

def recorded_run():
    start = {"t": 100.0, "decode_batches_total": 10,
             "attn_full_positions_total": 1000,
             "ssm_state_updates_total": 500,
             "state_bytes_held_total": 10 ** 9,
             "cache_bytes_held_total": 2 * 10 ** 9,
             "prefill_dispatch_s_total": 1.0, "chunk_dispatch_s_total": 2.0,
             "prefill_tokens_total": 10000, "generated_tokens_total": 50,
             "prefill_total": 5}
    end = {"t": 150.0, "decode_batches_total": 110,
           # 400 steps x 128 rows: 26 Mamba layers; 2 attention layers x
           # 1,000 positions
           "ssm_state_updates_total": 500 + 400 * 128 * 26,
           "attn_full_positions_total": 1000 + 400 * 128 * 2 * 1000,
           "state_bytes_held_total": 10 ** 9 + 100 * 128 * 9318400,
           "cache_bytes_held_total": 2 * 10 ** 9
           + 100 * 128 * (9318400 + 18 * 65536),
           "prefill_dispatch_s_total": 3.0, "chunk_dispatch_s_total": 4.0,
           "prefill_tokens_total": 10000 + 3 * 512,
           "generated_tokens_total": 6000, "prefill_total": 45}
    requests = [{"first_token": 110.0 + i, "prompt_len": 512,
                 "in_sample": True, "error": None, "n_out": 100}
                for i in range(3)]
    requests.append({"first_token": 99.0, "prompt_len": 1024,
                     "in_sample": False, "error": None, "n_out": 10})
    # a whole-prompt program ran as often as the decode program and is
    # shorter, the chunk program seldom: the decode program is the one
    # whose count AND duration are the engine's own
    trace = {"programs": {"decode": {"count": 16, "seconds": 16 * 0.060},
                          "prefill": {"count": 15, "seconds": 15 * 0.020},
                          "chunk": {"count": 3, "seconds": 3 * 0.150}}}
    return {"kind": "serve", "config": published(), "chips": 1,
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
            "engine": {"decode_block": 4, "max_batch": 128},
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
    needed = work.decode_step_bytes(
        published(), state_updates=128 * 26, full_positions=128 * 2 * 1000)
    got = reader("ssm_decode_roofline")(run)
    assert got == pytest.approx(100 * (needed / 819e9) / 0.015)
    assert 30 < got < 100
    # the shorter of the two nearest in count would be the prefill program
    from benchmark.metrics._hybrid import decode_program
    assert decode_program(run)["count"] == 15
    run["trace"] = None
    assert reader("ssm_decode_roofline")(run) is None


def test_prefill_mfu_reader_takes_chunks_and_each_prompt_at_its_length():
    run = recorded_run()
    flops = 3 * work.prefill_flops(published(), 512)
    assert reader("ssm_prefill_mfu")(run) == pytest.approx(
        100 * flops / 4.0 / 197e12)


def test_state_share_reader_takes_the_windows_differences():
    run = recorded_run()
    assert reader("state_cache_share")(run) == pytest.approx(
        100 * 9318400 / (9318400 + 18 * 65536))


@pytest.mark.parametrize("name", NEW)
def test_readers_return_nothing_on_the_other_configurations(name):
    run = recorded_run()
    for other in ("mimo-v2-flash-ep16", "deepseek-v3-ep16",
                  "mistral-7b-v0.3"):
        run["config"] = _read(ROOT, "benchmark", "configs",
                              other + ".json")
        assert reader(name)(run) is None
    assert reader(name)({"kind": "train", "config": published()}) is None
    # a program without the counters (the parent of this PR): nothing
    run = recorded_run()
    for edge in ("start", "end"):
        for k in ("ssm_state_updates_total", "state_bytes_held_total"):
            run["edges"][edge].pop(k)
    if name != "ssm_prefill_mfu":
        assert reader(name)(run) is None


# -- BENCHMARK.json and the traffic file ----------------------------------

def test_benchmark_json_names_the_cell_its_traffic_and_its_metric_lists():
    bench = _read(ROOT, "BENCHMARK.json")
    cell = by_name(bench["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, TRAFFIC, 1)
    assert len(cell["why"]) <= 200 and "128 slots" in cell["why"]
    assert len(by_name(bench["configs"], CONFIG)["why"]) <= 200
    assert sum(c["chips"] == 4 for c in bench["workloads"]) == 1
    assert CELL in by_name(bench["end_to_end"], "out_tok_s")["workloads"]
    assert "workloads" not in by_name(bench["end_to_end"], "setup_s")
    for name in BATCH:
        assert CELL in by_name(bench["per_layer"], name)["workloads"], name
    for name in ("decode_roofline.batch", "prefill_share.batch",
                 "cache_bytes_per_token", "hybrid_share_decode_roofline",
                 "moe_held_share", "window_attended_share"):
        assert CELL not in by_name(bench["per_layer"], name)["workloads"]
    layers = {"ssm_decode_roofline": ("Kernels", "device_trace"),
              "ssm_prefill_mfu": ("Program", "host_clock"),
              "state_cache_share": ("Scheduler", "program_counter")}
    for name in NEW:
        m = by_name(bench["per_layer"], name)
        assert m["workloads"] == [CELL] and m["moves"] == "out_tok_s"
        assert (m["layer"], m["source"]) == layers[name]
        assert (m["unit"], m["better"]) == ("%", "higher")
        assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics",
                                           name + ".py"))
    # every reader of the cell exists under its name or its stem
    for m in bench["per_layer"] + bench["end_to_end"]:
        if "workloads" not in m or CELL in m["workloads"]:
            stem = m["name"].split(".")[0]
            assert any(os.path.exists(os.path.join(
                ROOT, "benchmark", "metrics", n + ".py"))
                for n in (m["name"], stem)), m["name"]


def test_traffic_file_is_the_issues_letter_for_letter():
    t = _read(ROOT, "benchmark", "traffic", TRAFFIC + ".json")
    assert (t["loop"], t["clients"], t["list_len"], t["order_seed"],
            t["lead_in_s"]) == ("closed", 256, 1024, 0, 40.0)
    assert t["prompt_len"] == {"dist": "lognormal", "median": 512,
                               "sigma": 0.9, "min": 128, "max": 4096}
    assert t["output_len"] == {"dist": "lognormal", "median": 512,
                               "sigma": 0.6, "min": 128, "max": 2048}
    assert t["sharing"].startswith("none")
    from benchmark import loadgen
    reqs = loadgen.make_requests(t, 50, 2147483999, 65536)
    assert len(reqs) == 1024
    lens = np.asarray([r["prompt"].size for r in reqs])
    # about one prompt in sixteen goes through two chunks
    assert 0.05 < (lens > 2048).mean() < 0.075 and lens.max() == 4096
    e = published()["builder"]["engine"]
    assert lens.max() <= e["prompt_buckets"][-1]
    assert max(r["max_new"] for r in reqs) == e["max_new_tokens"]
    assert t["clients"] == 2 * e["max_batch"] <= e["max_queue"]
