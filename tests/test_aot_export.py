"""AOT inference export (io/aot.py) — the python-free serving path.

Round-trips: save_inference_model writes a jax.export StableHLO
artifact beside the JSON program; CompiledPredictor runs it without the
Program IR in the loop; outputs pin to the executor's. The subprocess
test proves framework-freeness: the serving process loads aot.py by
file path and never imports paddle_tpu.

Reference analogue: paddle/fluid/inference/api/paddle_inference_api.h:90
(PaddlePredictor), inference/io.cc:146 (Load).
"""
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.io import load_compiled_predictor


def _train_and_save(tmp_path):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[16], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="int64")
        h = fluid.layers.fc(x, size=32, act="relu")
        h = fluid.layers.dropout(h, dropout_prob=0.5)   # test-mode: id
        logits = fluid.layers.fc(h, size=4)
        prob = fluid.layers.softmax(logits)
        loss = fluid.layers.mean(
            fluid.layers.cross_entropy(prob, y))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor()
    exe.run(startup)
    rng = np.random.RandomState(0)
    for _ in range(3):
        exe.run(main, feed={
            "x": rng.rand(8, 16).astype(np.float32),
            "y": rng.randint(0, 4, (8, 1)).astype(np.int64)},
            fetch_list=[loss])
    d = str(tmp_path / "model")
    fluid.io.save_inference_model(d, ["x"], [prob], exe, main)
    return d, main, prob, exe


def test_aot_artifact_written_and_pins_to_executor(tmp_path):
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        d, main, prob, exe = _train_and_save(tmp_path)
        assert os.path.exists(os.path.join(d, "__compiled__.stablehlo"))
        rng = np.random.RandomState(1)
        x = rng.rand(8, 16).astype(np.float32)
        # executor path (re-traced inference program)
        inf_prog, feeds, fetches = fluid.io.load_inference_model(d, exe)
        ref = exe.run(inf_prog, feed={"x": x}, fetch_list=fetches,
                      mode="test")[0]
    # compiled path — fresh scope: nothing but the artifact dir
    pred = load_compiled_predictor(d)
    assert pred.feed_names == ["x"]
    out = pred.run({"x": x})[0]
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


def test_aot_symbolic_batch_serves_any_batch(tmp_path):
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        d, *_ = _train_and_save(tmp_path)
    pred = load_compiled_predictor(d)
    for b in (1, 5, 32):
        out = pred.run({"x": np.random.rand(b, 16).astype(np.float32)})
        assert out[0].shape == (b, 4)
        s = out[0].sum(axis=1)
        np.testing.assert_allclose(s, np.ones(b), rtol=1e-4)


def test_aot_missing_feed_raises(tmp_path):
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        d, *_ = _train_and_save(tmp_path)
    pred = load_compiled_predictor(d)
    with pytest.raises(KeyError, match="missing feed 'x'"):
        pred.run({})


def test_aot_serving_is_framework_free(tmp_path):
    """The serving process loads io/aot.py BY FILE PATH — paddle_tpu is
    never imported (sys.modules is asserted clean) — and still
    reproduces the in-framework prediction."""
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        d, main, prob, exe = _train_and_save(tmp_path)
        x = np.random.RandomState(2).rand(4, 16).astype(np.float32)
        inf_prog, feeds, fetches = fluid.io.load_inference_model(d, exe)
        ref = exe.run(inf_prog, feed={"x": x}, fetch_list=fetches,
                      mode="test")[0]
    np.save(tmp_path / "x.npy", x)
    np.save(tmp_path / "ref.npy", ref)
    aot_path = os.path.join(
        os.path.dirname(fluid.__file__), "io", "aot.py")
    script = f"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
import importlib.util, sys
import numpy as np
import jax
spec = importlib.util.spec_from_file_location("aot", {aot_path!r})
aot = importlib.util.module_from_spec(spec)
spec.loader.exec_module(aot)
pred = aot.load_compiled_predictor({d!r})
out = pred.run({{"x": np.load({str(tmp_path / "x.npy")!r})}})[0]
ref = np.load({str(tmp_path / "ref.npy")!r})
np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)
assert not any(m.startswith("paddle_tpu") for m in sys.modules), (
    "framework leaked into the serving process")
print("SERVED_OK")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "SERVED_OK" in proc.stdout


def test_aot_generator_export_roundtrip(tmp_path):
    """The fused Llama generator exports and serves AOT too (greedy,
    temperature 0 — deterministic)."""
    from paddle_tpu.models.llama import LLAMA_TINY, build_llama_generator

    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        gen_p, startup_p = fluid.Program(), fluid.Program()
        with fluid.program_guard(gen_p, startup_p):
            toks = fluid.layers.data(name="toks", shape=[-1, 6],
                                     dtype="int64",
                                     append_batch_size=False)
            out = build_llama_generator(LLAMA_TINY, toks,
                                        max_new_tokens=5)
        exe = fluid.Executor()
        exe.run(startup_p)
        pv = np.random.RandomState(0).randint(
            0, LLAMA_TINY.vocab_size, (2, 6)).astype(np.int64)
        ref = exe.run(gen_p, feed={"toks": pv}, fetch_list=[out],
                      mode="test")[0]
        d = str(tmp_path / "gen")
        fluid.io.save_inference_model(d, ["toks"], [out], exe, gen_p)
        assert os.path.exists(os.path.join(d, "__compiled__.stablehlo"))
    pred = load_compiled_predictor(d)
    got = pred.run({"toks": pv})[0]
    np.testing.assert_array_equal(got, ref)


def _seq_model():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        words = fluid.layers.data(name="words", shape=[1],
                                  dtype="int64", lod_level=1)
        emb = fluid.layers.embedding(input=words, size=[100, 16])
        gru = fluid.layers.dynamic_gru(
            fluid.layers.fc(emb, size=48), size=16)
        pool = fluid.layers.sequence_pool(gru, pool_type="max")
        prob = fluid.layers.fc(pool, size=3, act="softmax")
    return main, startup, prob


def test_aot_exports_sequence_program(tmp_path):
    """The round-3 gap: SequenceBatch-input programs (dynamic_gru et
    al.) must AOT-export — the signature carries the padded
    (data, lengths) decomposition, with batch AND padded length
    symbolic, so one artifact serves any geometry."""
    import warnings
    d = str(tmp_path / "seqmodel")
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    sb = fluid.to_sequence_batch(
        [rng.randint(1, 100, (n, 1)).astype(np.int64)
         for n in (5, 3, 7)])
    with fluid.scope_guard(scope):
        main, startup, prob = _seq_model()
        exe = fluid.Executor()
        exe.run(startup)
        ref = exe.run(main, feed={"words": sb}, fetch_list=[prob],
                      mode="test")[0]
        with warnings.catch_warnings():
            warnings.simplefilter("error")     # no silent fallback
            fluid.io.save_inference_model(d, ["words"], [prob], exe,
                                          main)
        assert os.path.exists(os.path.join(d, "__compiled__.stablehlo"))
        # executor parity at the export geometry, SequenceBatch feed
        pred = load_compiled_predictor(d)
        np.testing.assert_allclose(np.asarray(ref),
                                   pred.run({"words": sb})[0],
                                   rtol=1e-5, atol=1e-6)
        # a DIFFERENT batch and padded length through the same
        # artifact, tuple feed form
        sb2 = fluid.to_sequence_batch(
            [rng.randint(1, 100, (n, 1)).astype(np.int64)
             for n in (2, 9, 4, 6, 1)])
        ref2 = exe.run(main, feed={"words": sb2}, fetch_list=[prob],
                       mode="test")[0]
        got2 = pred.run({"words": (np.asarray(sb2.data),
                                   np.asarray(sb2.lengths))})[0]
    np.testing.assert_allclose(np.asarray(ref2), got2,
                               rtol=1e-5, atol=1e-6)


def test_aot_sequence_predictor_feed_forms(tmp_path):
    d = str(tmp_path / "seqmodel2")
    scope = fluid.Scope()
    rng = np.random.RandomState(1)
    sb = fluid.to_sequence_batch(
        [rng.randint(1, 100, (n, 1)).astype(np.int64)
         for n in (4, 2)])
    with fluid.scope_guard(scope):
        main, startup, prob = _seq_model()
        exe = fluid.Executor()
        exe.run(startup)
        fluid.io.save_inference_model(d, ["words"], [prob], exe, main)
    pred = load_compiled_predictor(d)
    a = pred.run({"words": sb})[0]                       # duck-typed
    b = pred.run({"words": {"data": np.asarray(sb.data),
                            "lengths": np.asarray(sb.lengths)}})[0]
    np.testing.assert_allclose(a, b, rtol=0, atol=0)
    with pytest.raises(TypeError, match="sequence feed"):
        pred.run({"words": np.asarray(sb.data)})


def test_aot_exports_two_level_lod_program(tmp_path):
    from paddle_tpu.core.sequence import to_nested_sequence_batch
    import warnings
    d = str(tmp_path / "lod2model")
    scope = fluid.Scope()
    rng = np.random.RandomState(2)
    nested = [[rng.randn(t, 4).astype(np.float32) for t in ts]
              for ts in ((3, 2), (4,), (1, 2, 5))]
    sb = to_nested_sequence_batch(nested)
    with fluid.scope_guard(scope):
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[4],
                                  dtype="float32", lod_level=2)
            sent = fluid.layers.sequence_pool(x, "sum")
            doc = fluid.layers.sequence_pool(sent, "sum")
            out = fluid.layers.fc(doc, size=2)
        exe = fluid.Executor()
        exe.run(startup)
        ref = exe.run(main, feed={"x": sb}, fetch_list=[out],
                      mode="test")[0]
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            fluid.io.save_inference_model(d, ["x"], [out], exe, main)
        pred = load_compiled_predictor(d)
        got = pred.run({"x": sb})[0]
    np.testing.assert_allclose(np.asarray(ref), got,
                               rtol=1e-5, atol=1e-6)


def test_aot_exports_llama_generator(tmp_path):
    """The fused KV-cache generator program (prefill + decode scan)
    AOT-exports: greedy tokens from the framework-free predictor equal
    the executor's, for both the float and int8-quantized scopes —
    the LLM serving artifact needs no Program IR/registry/re-trace."""
    from paddle_tpu.models.llama import (LlamaConfig,
                                         build_llama_generator,
                                         quantize_generator_weights)
    cfg = LlamaConfig(vocab_size=64, dim=32, n_layers=2, n_heads=4,
                      n_kv_heads=2, ffn_hidden=64, dtype="float32")
    prompt_len, new = 6, 5
    for quant in (False, True):
        d = str(tmp_path / ("gen_int8" if quant else "gen_f32"))
        gen_p, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(gen_p, startup):
            ptok = fluid.layers.data(name="ptok", shape=[-1, prompt_len],
                                     dtype="int64",
                                     append_batch_size=False)
            out = build_llama_generator(cfg, ptok, max_new_tokens=new,
                                        quantize=quant)
        scope = fluid.Scope()
        exe = fluid.Executor()
        with fluid.scope_guard(scope):
            exe.run(startup)
            if quant:
                quantize_generator_weights(scope)
            prompt = (np.arange(2 * prompt_len).reshape(2, prompt_len)
                      % (cfg.vocab_size - 4)).astype(np.int64)
            want = np.asarray(exe.run(gen_p, feed={"ptok": prompt},
                                      fetch_list=[out], mode="test")[0])
            fluid.io.save_inference_model(d, ["ptok"], [out], exe,
                                          main_program=gen_p)
        pred = load_compiled_predictor(d)
        got = np.asarray(pred.run({"ptok": prompt})[0])
        np.testing.assert_array_equal(got, want)
        assert got.shape == (2, prompt_len + new)


# ---------------------------------------------------------------------
# params.npz sha256 manifest (CompiledPredictor verification)
# ---------------------------------------------------------------------

def _export_model(tmp_path):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        y = fluid.layers.fc(input=x, size=4, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    model_dir = str(tmp_path / "model")
    fluid.io.save_inference_model(model_dir, ["x"], [y], exe,
                                  main_program=main)
    return model_dir


def test_compiled_predictor_verifies_params_manifest(tmp_path):
    from paddle_tpu.io import PARAMS_MANIFEST
    model_dir = _export_model(tmp_path)
    assert os.path.exists(os.path.join(model_dir, PARAMS_MANIFEST))
    pred = fluid.io.load_compiled_predictor(model_dir)   # clean: loads
    out = pred.run({"x": np.zeros((2, 8), np.float32)})
    assert out[0].shape == (2, 4)


def test_compiled_predictor_quarantines_corrupt_params(tmp_path):
    from paddle_tpu.resilience.checkpoint import ChecksumMismatch
    model_dir = _export_model(tmp_path)
    ppath = os.path.join(model_dir, "params.npz")
    with open(ppath, "r+b") as f:
        f.seek(30)
        f.write(b"\x00" * 16)                  # torn copy / bit rot
    with pytest.raises(ChecksumMismatch, match="sha256 mismatch"):
        fluid.io.load_compiled_predictor(model_dir)
    assert not os.path.exists(ppath)           # moved, not deleted
    qdir = os.path.join(model_dir, "quarantine")
    assert os.path.isdir(qdir) and os.listdir(qdir)


def test_compiled_predictor_legacy_artifact_loads_unchecked(tmp_path):
    from paddle_tpu.io import PARAMS_MANIFEST
    model_dir = _export_model(tmp_path)
    os.remove(os.path.join(model_dir, PARAMS_MANIFEST))  # old export
    pred = fluid.io.load_compiled_predictor(model_dir)
    assert pred.run({"x": np.zeros((1, 8), np.float32)})[0].shape == \
        (1, 4)
