"""The cell olmohybrid-serve-longdocs: its configuration against the
catalog's row, its traffic, builder, reference, work file and readers, at a
tiny size on the CPU and on a recorded run, as test_bm_hybrid_ssm.py does
for jamba2-serve-reason. Entries of BENCHMARK.json are found by name; no
entry is pinned by its position or by the number of cells.
"""
import contextlib
import importlib.util
import io
import json
import math
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import work_hybrid_delta as work
from benchmark.builders import serve_delta
from benchmark.reference import hybrid_delta as ref

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL, CONFIG, TRAFFIC = ("olmohybrid-serve-longdocs", "olmo-hybrid-7b",
                         "longdocs-closed")
NEW = ("delta_decode_roofline", "delta_prefill_mfu", "delta_state_share")
BATCH = ("compiles_in_window.batch", "batch_occupancy.batch",
         "pages_peak.batch", "tpot_p90_ms.batch", "decode_step_ms.batch",
         "device_idle.batch", "peak_hbm_gb.batch", "engine_host_ms.batch",
         "decode_dispatch_ms.batch", "prefill_fill.batch",
         "engine_idle_share.batch")

TINY = dict(hidden_size=24, intermediate_size=48, num_attention_heads=4,
            num_key_value_heads=4, num_hidden_layers=6,
            layer_types=["linear_attention", "linear_attention",
                         "full_attention"] * 2,
            linear_num_key_heads=3, linear_num_value_heads=3,
            linear_key_head_dim=4, linear_value_head_dim=10, vocab_size=96,
            torch_dtype="float32")
TINY_ENGINE = {"max_batch": 4, "prompt_buckets": [8, 16, 48],
               "max_new_tokens": 8, "page_size": 2, "n_pages": 61,
               "prefill_batch": 1, "decode_block": 2, "chunk_size": 16,
               "max_queue": 16, "default_timeout_s": 120.0}


def _read(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def published():
    return _read(ROOT, "benchmark", "configs", CONFIG + ".json")


def tiny_config():
    c = dict(published(), **TINY, name="tiny-delta")
    c["builder"] = {"kind": "serve_delta", "engine": dict(TINY_ENGINE)}
    return c


def by_name(entries, name):
    return next(e for e in entries if e["name"] == name)


# -- the configuration ----------------------------------------------------

def test_configuration_carries_every_published_key_and_names_its_cut():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Olmo-Hybrid-7B")
    c = published()
    assert c["source"] == row["source_url"]
    changed = sorted(k for k, v in row["config"].items() if c.get(k) != v)
    assert changed == sorted(c["reduced"]) \
        == ["layer_types", "num_hidden_layers"]
    assert c["published"] == {k: row["config"][k] for k in c["reduced"]}
    assert c["num_hidden_layers"] == 16
    assert c["layer_types"] == row["config"]["layer_types"][:16]
    entry = by_name(_read(ROOT, "BENCHMARK.json")["configs"], CONFIG)
    assert entry["reduced"] == c["reduced"]
    assert entry["source"] == c["source"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    # no width among the keys cut
    for k in c["reduced"]:
        assert not k.endswith(("_dim", "_rank", "_size"))
    assert [i for i in range(16) if ref.is_attention(c, i)] == [3, 7, 11, 15]


def test_configuration_states_its_deployment_assumptions_and_departures():
    c = published()
    for said in ("TWO pipeline stages", "chip (16 GB) a stage",
                 "each layer whole", "the embedding and the untied head", "65,536 positions",
                 "8 state entries", "not built"):
        assert said in c["deployment"], said
    assert {"block", "layer_order", "full_attention", "rotation",
            "linear_attention", "head_dim", "torch_dtype", "state"} \
        <= set(c["assumed"])
    assert "x + N1(Mixer(x))" in c["assumed"]["block"]
    assert "WHOLE" in c["assumed"]["full_attention"]
    assert "NONE" in c["assumed"]["rotation"]
    assert "NO BIAS" in c["assumed"]["linear_attention"]
    assert "FLOAT32" in c["assumed"]["state"]
    assert any("PUBLISHED INITIALISATION" in d for d in c["departures"])
    assert any("0.999 to about 0.2" in d for d in c["departures"])
    assert any("33,028" in d for d in c["departures"])
    assert {"linear_layer", "attention_layer", "embedding_and_head",
            "weights", "state", "attention_cache", "total"} <= set(c["bytes"])
    assert c["torch_dtype"] == "bfloat16"
    assert c["builder"]["kind"] == "serve_delta"
    e = c["builder"]["engine"]
    assert (e["max_batch"], e["max_new_tokens"], e["decode_block"],
            e["page_size"], e["chunk_size"], e["max_queue"],
            e["prefill_batch"], e["default_timeout_s"]) \
        == (8, 256, 4, 64, 2048, 32, 1, 600.0)
    assert e["prompt_buckets"] == [2048 * i for i in range(1, 17)]
    assert 768 <= e["n_pages"] <= 1025 and "quantize" not in e


def test_model_config_carries_the_published_widths():
    cfg = serve_delta.model_config(published())
    assert (cfg.dim, cfg.n_layers, cfg.n_heads, cfg.n_kv, cfg.head_dim,
            cfg.ffn_hidden) == (3840, 16, 30, 30, 128, 11008)
    assert (cfg.delta_heads, cfg.delta_key_dim, cfg.delta_value_dim,
            cfg.d_conv, cfg.conv_channels) == (30, 96, 192, 4, 11520)
    assert (cfg.attn_period, cfg.vocab_size, cfg.norm_eps, cfg.dtype) \
        == (4, 100352, 1e-6, "bfloat16")
    assert (cfg.layers_of(0), cfg.layers_of(1)) == (4, 12)
    runs = [(k, len(list(g))) for k, g in __import__("itertools").groupby(
        cfg.layer_kinds)]
    assert runs == [(1, 3), (0, 1)] * 4
    assert cfg.state_spec() == [((30, 96, 192), "float32"),
                                ((34560,), "bfloat16")]
    # the uncut model is a value of the same class
    whole = published()
    whole.update(whole["published"])
    assert serve_delta.model_config(whole).layers_of(1) == 24
    for wrong in (dict(linear_allow_neg_eigval=False),
                  dict(tie_word_embeddings=True), dict(attention_bias=True),
                  dict(model_type="olmo2"),
                  dict(rope_parameters={"rope_theta": 500000.0})):
        with pytest.raises(ValueError):
            serve_delta.model_config(dict(published(), **wrong))


def test_the_bytes_the_configuration_states_are_its_shapes():
    m = published()
    cfg = serve_delta.model_config(m)
    shapes = cfg.param_shapes()
    count = lambda pre: sum(int(np.prod(s)) for n, (s, _) in shapes.items()
                            if n.startswith(pre))
    linear, full = count("delta.") // 12, count("full.") // 4
    assert linear == 215_570_172 and "215.6 M" in m["bytes"]["linear_layer"]
    assert linear - 3 * 3840 * 11008 - 2 * 3840 == 88_750_332
    assert "88.75 M" in m["bytes"]["linear_layer"]
    assert full == 4 * 3840 ** 2 + 3 * 3840 * 11008 + 4 * 3840
    assert "185.8 M" in m["bytes"]["attention_layer"]
    total = sum(int(np.prod(s)) for s, _ in shapes.values())
    assert total == work.parameters(m)
    assert round(2 * total / 1e9, 2) == 8.20 and "8.20 GB" in \
        m["bytes"]["weights"]
    whole = dict(m, **m["published"])
    assert round(work.parameters(whole) / 1e9, 2) == 7.43
    assert work.state_entry_bytes(m) == 2_280_960
    assert "2,280,960 B" in m["bytes"]["state"]
    assert round(work.state_bytes(m) / 1e6, 1) == 27.4
    assert work.kv_entry_bytes(m) == 15_360
    assert "61,440 B a position" in m["bytes"]["attention_cache"]
    # the pools the programs declare are those bytes
    e = m["builder"]["engine"]
    specs = cfg.build_paged_programs(
        max_batch=e["max_batch"], page_size=64, n_pages=e["n_pages"],
        pages_per_seq=517, prompt_buckets=tuple(e["prompt_buckets"]),
        decode_block=4, chunk_size=2048).pool_specs
    assert specs[0] == ([4, e["n_pages"], 64, 3840], "bfloat16")
    assert specs[2] == ([12, 9, 30, 96, 192], "float32")
    assert specs[3] == ([12, 9, 34560], "bfloat16")
    per_entry = sum(math.prod(s[2:]) * jnp.dtype(d).itemsize
                    for s, d in specs[2:])
    assert per_entry == work.state_entry_bytes(m)


def test_the_stand_ins_are_the_published_initialisation_for_every_seed():
    cfg = serve_delta.model_config(published())
    s = serve_delta.stand_ins(cfg)
    assert set(s) == {"delta.a_log", "delta.dt_bias"}
    assert s["delta.a_log"].shape == s["delta.dt_bias"].shape == (12, 30)
    a = np.exp(np.asarray(s["delta.a_log"][0], np.float64))
    dt = np.log1p(np.exp(np.asarray(s["delta.dt_bias"][0], np.float64)))
    assert np.allclose(a, np.linspace(1, 16, 30), rtol=1e-5)
    assert np.allclose(dt, np.exp(np.linspace(np.log(1e-3), np.log(1e-1),
                                              30)), rtol=1e-4)
    decay = np.exp(-a * dt)
    assert 0.9985 < decay.max() < 0.9995 and 0.19 < decay.min() < 0.21
    assert (np.asarray(s["delta.a_log"]) == np.asarray(
        s["delta.a_log"][0])).all()


# -- the work file, by hand on one tiny layer of each kind -----------------

TINY_M = dict(hidden_size=8, num_attention_heads=4, num_key_value_heads=4,
              intermediate_size=16, vocab_size=32, num_hidden_layers=2,
              layer_types=["linear_attention", "full_attention"],
              linear_num_key_heads=2, linear_num_value_heads=2,
              linear_key_head_dim=3, linear_value_head_dim=5,
              linear_conv_kernel_dim=4)


def test_work_counts_one_tiny_layer_of_each_kind_by_hand():
    m = TINY_M
    assert (work.n_layers(m, False), work.n_layers(m, True)) == (1, 1)
    assert work.conv_channels(m) == 2 * (3 + 3 + 5) == 22
    # q, k, v 8 x 22; z 8 x 10; o 10 x 8; a, b 8 x 2 each
    assert work.delta_params(m) == 8 * 22 + 8 * 10 + 10 * 8 + 2 * 8 * 2
    assert work.delta_small_params(m) == 4 * 22 + 2 * 2 + 5
    assert work.attention_params(m) == 4 * 8 * 8
    assert work.swiglu_params(m) == 3 * 8 * 16
    assert work.parameters(m) == (368 + 97 + 384 + 16) + (
        256 + 384 + 16 + 16) + 2 * 32 * 8 + 8
    assert work.state_entry_bytes(m) == 2 * 3 * 5 * 4 + 3 * 22 * 2
    assert work.kv_entry_bytes(m) == 2 * 4 * 2 * 2
    # 70 positions: two chunks of 64 a head
    C = 64
    a_chunk = 2 * (2 * C * C * 3 + 3 * C * 3 * 5 + 2 * C * C * 5)
    assert work.rule_flops(m, 70) == 2 * 2 * a_chunk
    assert work.rule_flops(m, 64) == 2 * a_chunk
    per_token = 368 + 384 + 256 + 384
    assert work.prefill_flops(m, 70) == 2 * 70 * per_token + 2 * 8 * 32 \
        + work.rule_flops(m, 70) + 2 * 4 * 2 * 2 * (70 * 71 // 2)
    assert work.rule_elementwise_flops(m, 70) > 0
    assert work.decode_step_bytes(m, state_updates=3, full_positions=50) \
        == 2 * (368 + 384 + 256 + 384 + 8 * 32) \
        + 2 * 3 * work.state_entry_bytes(m) + 50 * work.kv_entry_bytes(m)


def test_work_at_the_published_widths_is_the_issues_arithmetic():
    m = published()
    # about 70 MFLOP a token in the rule's products over 12 layers
    assert 60e6 < 12 * work.rule_flops(m, 2048) / 2048 < 75e6
    # a chunk program: 13.6 TFLOP of projections and SwiGLU
    chunk = work.prefill_flops(m, 2048)
    assert 13.5e12 < chunk < 14.5e12
    # a decode step at 6 live rows of 10,000 positions: 11.4-11.6 GB
    step = work.decode_step_bytes(m, state_updates=12 * 6,
                                  full_positions=4 * 6 * 10000)
    assert 11.3e9 < step < 11.7e9
    assert step > 2 * (work.parameters(m) - 100352 * 3840 - 200_000)


# -- the reference is the equations written out again ----------------------

def test_reference_delta_layer_is_the_equations_written_out_again():
    rng = np.random.RandomState(0)
    T, D, H, dk, dv = 9, 8, 2, 3, 5
    C = H * (2 * dk + dv)
    w = {n: rng.randn(*s).astype(np.float32) * 0.5 for n, s in dict(
        wq=(D, H * dk), wk=(D, H * dk), wv=(D, H * dv), wz=(D, H * dv),
        wa=(D, H), wb=(D, H), conv_w=(4, C), wo=(H * dv, D)).items()}
    w.update(a_log=np.log([1.0, 4.0]).astype(np.float32),
             dt_bias=np.asarray([-1.0, 0.5], np.float32),
             g_norm=(1 + 0.1 * rng.randn(dv)).astype(np.float32))
    x = rng.randn(T, D).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        out, state = ref._delta(
            x, *(w[k] for k in ("wq", "wk", "wv", "wz", "wa", "wb",
                                "conv_w", "a_log", "dt_bias", "g_norm",
                                "wo")),
            H=H, dk=dk, dv=dv, eps=1e-6, beta_max=2.0)
    x64 = x.astype(np.float64)
    z = np.concatenate([x64 @ w["wq"], x64 @ w["wk"], x64 @ w["wv"]], -1)
    full = np.concatenate([np.zeros((3, C)), z])
    c = sum(full[j:j + T] * w["conv_w"][j] for j in range(4))
    c = c / (1 + np.exp(-c))
    S = np.zeros((H, dk, dv))
    want = np.zeros((T, H * dv))
    for t in range(T):
        o = []
        for h in range(H):
            q = c[t, h * dk:(h + 1) * dk]
            k = c[t, H * dk + h * dk:H * dk + (h + 1) * dk]
            v = c[t, 2 * H * dk + h * dv:2 * H * dk + (h + 1) * dv]
            q = q / np.sqrt(q @ q + 1e-6) * dk ** -0.5
            k = k / np.sqrt(k @ k + 1e-6)
            beta = 2 / (1 + np.exp(-(x64[t] @ w["wb"][:, h])))
            alpha = np.exp(-np.exp(w["a_log"][h]) * np.log1p(np.exp(
                x64[t] @ w["wa"][:, h] + w["dt_bias"][h])))
            S[h] = alpha * S[h]
            S[h] = S[h] + beta * np.outer(k, v - S[h].T @ k)
            oh = S[h].T @ q
            o.append(oh / np.sqrt((oh * oh).mean() + 1e-6) * w["g_norm"])
        gate = x64[t] @ w["wz"]
        want[t] = np.concatenate(o) * gate / (1 + np.exp(-gate))
    assert np.allclose(out, want @ w["wo"], rtol=1e-4, atol=1e-5)
    assert np.allclose(state, S, rtol=1e-4, atol=1e-6)


def test_reference_attention_norms_the_whole_projection_and_rotates_nothing():
    rng = np.random.RandomState(1)
    T, D, H, hd = 7, 8, 2, 4
    w = {n: rng.randn(D, D).astype(np.float32) * 0.5
         for n in ("wq", "wk", "wv", "wo")}
    qn, kn = (1 + 0.1 * rng.randn(2, D)).astype(np.float32)
    x = rng.randn(T, D).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        out = ref._attention(x, w["wq"], w["wk"], w["wv"], w["wo"], qn, kn,
                             H=H, G=H, hd=hd, eps=1e-6)
        # blocks of queries and of rows give the same numbers
        keep = ref.QUERIES, ref.ROWS
        ref.QUERIES, ref.ROWS = 2, 3
        try:
            blocked = ref._attention.__wrapped__(
                x, w["wq"], w["wk"], w["wv"], w["wo"], qn, kn, H=H, G=H,
                hd=hd, eps=1e-6)
        finally:
            ref.QUERIES, ref.ROWS = keep

    def norm(y, s):
        return y / np.sqrt((y * y).mean(-1, keepdims=True) + 1e-6) * s

    x64 = x.astype(np.float64)
    q = norm(x64 @ w["wq"], qn).reshape(T, H, hd)
    k = norm(x64 @ w["wk"], kn).reshape(T, H, hd)
    v = (x64 @ w["wv"]).reshape(T, H, hd)
    want = np.zeros((T, H, hd))
    for t in range(T):
        for h in range(H):
            s = k[:t + 1, h] @ q[t, h] * hd ** -0.5
            p = np.exp(s - s.max())
            want[t, h] = (p / p.sum()) @ v[:t + 1, h]
    want = want.reshape(T, D) @ w["wo"]
    assert np.allclose(out, want, rtol=1e-4, atol=1e-5)
    assert np.allclose(blocked, want, rtol=1e-4, atol=1e-5)
    # a permutation of the earlier positions changes nothing at the last:
    # no position is embedded
    perm = np.concatenate([np.random.RandomState(2).permutation(T - 1),
                           [T - 1]])
    with jax.default_matmul_precision("highest"):
        moved = ref._attention(x[perm], w["wq"], w["wk"], w["wv"], w["wo"],
                               qn, kn, H=H, G=H, hd=hd, eps=1e-6)
    assert np.allclose(moved[-1], out[-1], rtol=1e-4, atol=1e-5)


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
                           "tiny-delta.json"), "w") as f:
        json.dump(tiny_config(), f)
    traffic = _read(ROOT, "benchmark", "traffic", TRAFFIC + ".json")
    traffic.update(name="tiny-longdocs", clients=6, list_len=32,
                   lead_in_s=0.5,
                   prompt_len=dict(traffic["prompt_len"], median=20, min=8,
                                   max=48),
                   output_len=dict(traffic["output_len"], median=5, min=2,
                                   max=8))
    with open(os.path.join(root, "benchmark", "traffic",
                           "tiny-longdocs.json"), "w") as f:
        json.dump(traffic, f)
    bench["configs"].append({"name": "tiny-delta", "source": "test",
                             "file": "benchmark/configs/tiny-delta.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny-longdocs-cell",
                               "config": "tiny-delta",
                               "traffic": "tiny-longdocs", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("tiny-longdocs-cell")
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
        "bm_delta_run", os.path.join(root, "benchmark", "run.py"))
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
                rc = run_py.main(["--workload", "tiny-longdocs-cell",
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
    # a traced run on the CPU holds no device operation, and says so; the
    # CPU's decode program attends through the jax.numpy reference
    assert rc == 0 and problems == [
        "PROBLEM: the traced run holds no device operation"][:trace]
    assert line["correct"] is (not trace)
    assert line["attempted"] > 10 and line["failed"] == 0
    # three quarters of each whole-prompt bucket (6, 12), one just over a
    # chunk (17), one of three chunks (40) and the longest (48), 9
    # positions each
    assert any(x.startswith("logit comparison: 45 positions, limit 0.35; "
                            "5 states, limit 0.009") for x in before)
    for n in (6, 12, 17, 40, 48):
        assert any(x.startswith(f"probe of {n} tokens") for x in before), n
    assert any("serve_delta: engine up" in x and "4 state entries" in x
               and "60 sequence pages" in x for x in before)
    # every request that started was reset once; nothing was lost
    books = next(x for x in before
                 if x.startswith("state kind after the window"))
    assert "'pools_lost_total': 0" in books
    assert "'delta_prefill_positions_total'" in books


def test_end_to_end_line_reports_out_tok_s_and_setup_s(results):
    metrics = results[0][1]["metrics"]
    assert set(metrics) == {"out_tok_s", "setup_s"}
    assert metrics["out_tok_s"]["value"] > 0


def test_traced_line_reports_the_state_kind_and_no_device_metric(results):
    metrics = results[1][1]["metrics"]
    assert {"delta_state_share", "delta_prefill_mfu", "prefill_fill.batch",
            "batch_occupancy.batch", "pages_peak.batch",
            "compiles_in_window.batch", "engine_host_ms.batch",
            "decode_dispatch_ms.batch", "engine_idle_share.batch",
            "tpot_p90_ms.batch", "peak_hbm_gb.batch"} <= set(metrics)
    assert 0 < metrics["delta_state_share"]["value"] < 100
    assert metrics["compiles_in_window.batch"]["value"] == 0
    # a CPU run holds no device trace: the shares of a roofline are left
    # out; and no other configuration's reader speaks here
    assert "delta_decode_roofline" not in metrics
    for other in ("ssm_decode_roofline", "state_cache_share",
                  "ssm_prefill_mfu", "cache_bytes_per_token",
                  "page_bound_share"):
        assert other not in metrics


# -- the readers on a recorded run ----------------------------------------

def recorded_run():
    start = {"t": 100.0, "decode_batches_total": 10,
             "attn_full_positions_total": 1000,
             "delta_state_updates_total": 500,
             "state_bytes_held_total": 10 ** 9,
             "cache_bytes_held_total": 2 * 10 ** 9,
             "prefill_dispatch_s_total": 1.0, "chunk_dispatch_s_total": 2.0,
             "prefill_tokens_total": 10000, "generated_tokens_total": 50,
             "prefill_total": 5}
    end = {"t": 150.0, "decode_batches_total": 110,
           # 400 steps x 6 rows: 12 delta-rule layers; 4 attention layers
           # x 10,000 positions
           "delta_state_updates_total": 500 + 400 * 6 * 12,
           "attn_full_positions_total": 1000 + 400 * 6 * 4 * 10000,
           "state_bytes_held_total": 10 ** 9 + 100 * 6 * 27371520,
           "cache_bytes_held_total": 2 * 10 ** 9
           + 100 * 6 * (27371520 + 169 * 64 * 61440),
           "prefill_dispatch_s_total": 1.5, "chunk_dispatch_s_total": 9.5,
           "prefill_tokens_total": 10000 + 2048 + 2 * 8192,
           "generated_tokens_total": 2400, "prefill_total": 8}
    requests = [{"first_token": 110.0 + i, "prompt_len": n,
                 "in_sample": True, "error": None, "n_out": 100}
                for i, n in enumerate((2048, 8192, 8192))]
    requests.append({"first_token": 99.0, "prompt_len": 4096,
                     "in_sample": False, "error": None, "n_out": 10})
    # the chunk program ran about as often as the decode program: the
    # decode program is the one whose count AND duration are the engine's
    trace = {"programs": {"decode": {"count": 16, "seconds": 16 * 0.080},
                          "chunk": {"count": 15, "seconds": 15 * 0.220},
                          "prefill": {"count": 1, "seconds": 0.2}}}
    return {"kind": "serve", "config": published(), "chips": 1,
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12},
            "engine": {"decode_block": 4, "max_batch": 8},
            "t0": 100.0, "t_end": 150.0, "requests": requests,
            "trace": trace,
            "edges": {"start": start, "end": end,
                      "trace_start": {"decode_batches_total": 50,
                                      "decode_dispatch_s_total": 3.0},
                      "trace_end": {"decode_batches_total": 65,
                                    "decode_dispatch_s_total": 4.26}}}


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
        published(), state_updates=6 * 12, full_positions=6 * 4 * 10000)
    got = reader("delta_decode_roofline")(run)
    assert got == pytest.approx(100 * (needed / 819e9) / 0.020)
    assert 30 < got < 100
    # the count alone is a coin's toss between the two
    from benchmark.metrics._programs import by_count
    assert by_count(run["trace"], 15)["count"] == 15
    run["trace"] = None
    assert reader("delta_decode_roofline")(run) is None


def test_prefill_mfu_reader_takes_chunks_and_each_prompt_at_its_length():
    run = recorded_run()
    m = published()
    flops = work.prefill_flops(m, 2048) + 2 * work.prefill_flops(m, 8192)
    assert reader("delta_prefill_mfu")(run) == pytest.approx(
        100 * flops / 8.0 / 197e12)
    assert 5 < reader("delta_prefill_mfu")(run) < 100


def test_state_share_reader_takes_the_windows_differences():
    run = recorded_run()
    assert reader("delta_state_share")(run) == pytest.approx(
        100 * 27371520 / (27371520 + 169 * 64 * 61440))


@pytest.mark.parametrize("name", NEW)
def test_readers_return_nothing_on_the_other_configurations(name):
    run = recorded_run()
    for other in ("ai21-jamba2-3b", "mimo-v2-flash-ep16", "ouro-2.6b",
                  "deepseek-v3-ep16", "mistral-7b-v0.3"):
        run["config"] = _read(ROOT, "benchmark", "configs",
                              other + ".json")
        assert reader(name)(run) is None
    assert reader(name)({"kind": "train", "config": published()}) is None
    # a program without the counters (the parent of this PR): nothing
    run = recorded_run()
    for edge in ("start", "end"):
        for k in ("delta_state_updates_total", "state_bytes_held_total"):
            run["edges"][edge].pop(k)
    if name != "delta_prefill_mfu":
        assert reader(name)(run) is None


@pytest.mark.parametrize("name", ("ssm_decode_roofline", "ssm_prefill_mfu",
                                  "state_cache_share"))
def test_the_state_space_models_readers_say_nothing_of_this_one(name):
    assert reader(name)(recorded_run()) is None


# -- BENCHMARK.json and the traffic file ----------------------------------

def test_benchmark_json_names_the_cell_its_traffic_and_its_metric_lists():
    bench = _read(ROOT, "BENCHMARK.json")
    cell = by_name(bench["workloads"], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (CONFIG, TRAFFIC, 1)
    assert len(cell["why"]) <= 200
    assert "12 callers over 8 slots" in cell["why"]
    assert "2,048-32,768" in cell["why"] and "32-256" in cell["why"]
    config = by_name(bench["configs"], CONFIG)
    assert len(config["why"]) <= 200
    assert config["reduced"] == ["num_hidden_layers", "layer_types"]
    assert CELL in by_name(bench["end_to_end"], "out_tok_s")["workloads"]
    assert "workloads" not in by_name(bench["end_to_end"], "setup_s")
    for name in BATCH:
        assert CELL in by_name(bench["per_layer"], name)["workloads"], name
    for name in ("decode_roofline.batch", "prefill_share.batch",
                 "cache_bytes_per_token", "ssm_decode_roofline",
                 "state_cache_share", "page_bound_share"):
        assert CELL not in by_name(bench["per_layer"], name)["workloads"]
    layers = {"delta_decode_roofline": ("Kernels", "device_trace"),
              "delta_prefill_mfu": ("Program", "host_clock"),
              "delta_state_share": ("Scheduler", "program_counter")}
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
            t["lead_in_s"]) == ("closed", 12, 256, 0, 40.0)
    assert t["prompt_len"] == {"dist": "lognormal", "median": 8192,
                               "sigma": 0.6, "min": 2048, "max": 32768}
    assert t["output_len"] == {"dist": "lognormal", "median": 128,
                               "sigma": 0.5, "min": 32, "max": 256}
    assert t["sharing"].startswith("none")
    from benchmark import loadgen
    reqs = loadgen.make_requests(t, 50, 2147483999, 100352)
    assert len(reqs) == 256
    lens = np.asarray([r["prompt"].size for r in reqs])
    assert lens.min() == 2048 and lens.max() == 32768
    assert 9000 < lens.mean() < 10500
    e = published()["builder"]["engine"]
    assert lens.max() <= e["prompt_buckets"][-1]
    assert max(r["max_new"] for r in reqs) == e["max_new_tokens"]
    assert e["max_batch"] < t["clients"] <= e["max_queue"]
    # pages bound admission: the pool holds fewer of the mean request than
    # there are slots
    reserved = np.ceil((lens + 256) / 2048) * 2048
    assert (e["n_pages"] - 1) * e["page_size"] / reserved.mean() \
        < e["max_batch"]
    # serve.measure's probe is a request among list indices 12..35
    probe = min(range(12, 36), key=lambda i: (reqs[i]["max_new"],
                                              reqs[i]["prompt"].size))
    assert 12 <= probe < 36
